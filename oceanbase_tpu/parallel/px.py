"""PX: distributed plan execution as one SPMD program over a device mesh.

Reference surface: the parallel-execution component (sql/engine/px) — the
coordinator splits the plan into DFOs at TRANSMIT/RECEIVE pairs
(ObDfoMgr::do_split, ob_dfo_mgr.cpp:462), dispatches SQCs to nodes, workers
pull granules (ObGranuleIteratorOp) and rows cross DTL channels routed by
ObSliceIdxCalc; admission bounds cluster DOP (ObPxAdmission,
ob_px_target_mgr.h); join-filter pushdown ships build-side bloom filters to
probe-side scans (ob_px_bloom_filter_simd.cpp).

The TPU redesign collapses the DFO graph into ONE shard_map program:

  * DFO boundary      -> an exchange INSIDE the traced program
                         (all_to_all / all_gather collective, exchange.py)
  * granule iterator  -> static row-block shard of each table (device
                         sharding over the mesh axis IS the granule map)
  * SQC/worker threads-> the mesh devices themselves
  * DTL channel       -> collective lanes with static capacity + overflow
                         retry (no credit flow control: the collective is
                         the synchronization)
  * datahub rollup    -> psum/pmin/pmax partial-aggregate merges
  * join bloom filter -> build-side key bitset OR-reduced with psum,
                         applied to the probe mask BEFORE the all_to_all
                         (cuts exchanged rows, the pushdown's purpose)

Every intermediate carries a distribution state, the DFO data-layout
analog: SHARDED (rows split over the mesh axis) or REPLICATED (every
device holds all rows), each with a variant that also knows the rows'
ORDER, which the direct-address join needs (`_affine_build_info`):
ROW_SLICED is SHARDED with shard i holding rows [i*n, (i+1)*n) of a base
Scan in storage order (only the sel mask differs), TABLE_ORDER is
REPLICATED with every row of a base Scan at its storage index (a
ROW_SLICED batch gathered whole). Placement rules:

  scan -> ROW_SLICED.  filter/project preserve; so does the probe side
        of a join that emits probe columns untouched (semi/anti, the
        merge/affine inner join) when nothing exchanged it. Every hash
        or range exchange -> SHARDED: the order is gone for good.
  join: build(right) REPLICATED -> local; small build -> broadcast build
        (ROW_SLICED build -> TABLE_ORDER, the one case that may take the
        direct-address join); else hash-repartition both sides on the
        join keys.
  group-by: small-domain direct aggregation -> local partials + merge
        (REPLICATED out); generic hash group-by -> hash-repartition on the
        group keys (SHARDED out); scalar aggregate -> partials + merge.
  sort/limit/distinct: gather (REPLICATED), then identical local compute.
  root: gathered if still SHARDED.
"""

from __future__ import annotations

import threading
import time
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..core.column import ColumnBatch
from ..core.dtypes import Schema
from ..engine.chunked import ChunkWindowMixin
from ..engine.executor import (
    DIRECT_GROUPBY_MAX_DOMAIN,
    Executor,
    _dict_domain,
    _number_nodes,
)
from ..expr import ir as E
from ..expr.compile import evaluate
from ..ops.hashing import hash32_combine, next_pow2
from ..sql.logical import (
    Aggregate,
    Distinct,
    JoinOp,
    Limit,
    Scan,
    SetOp,
    Sort,
    TopN,
    Window,
)
from .exchange import (
    broadcast_rows,
    dest_by_hash,
    repartition,
    ring_broadcast_rows,
)
from .mesh import SHARD_AXIS, mesh_signature, shard_map_compat
from .spmd import ShardedResidency, SpmdLowering, shard_put

SHARDED = "sharded"
REPLICATED = "replicated"
ROW_SLICED = "row_sliced"  # SHARDED, and a base Scan's contiguous row slice
TABLE_ORDER = "table_order"  # REPLICATED, and a base Scan's rows in place


def _is_sharded(dist: str) -> bool:
    return dist in (SHARDED, ROW_SLICED)

# synthesized PhysicalParams ids for exchange lanes (disjoint from plan
# node ids, which are small pre-order indexes)
_EXCH_BASE = 1_000_000


def _exch_id(nid: int, slot: int) -> int:
    return _EXCH_BASE + nid * 4 + slot


_AGG_CHILD, _JOIN_LEFT, _JOIN_RIGHT, _SORT_CHILD = 0, 1, 2, 3


class PxAdmission:
    """Cluster-wide DOP quota (ObPxAdmission / ObPxTargetMgr analog).

    acquire() grants up to `dop` workers, degrading to whatever quota
    remains (minimum 1, like the reference's min-DOP admission). When
    nothing is free the caller QUEUES (FIFO, condition-variable wait)
    up to `queue_timeout_s` — the reference's admission behavior
    (ob_px_admission.h waits on the target manager rather than failing
    a concurrent burst); only a timeout raises."""

    def __init__(self, target: int, queue_timeout_s: float = 10.0):
        self.target = target
        self.queue_timeout_s = queue_timeout_s
        self._used = 0
        self._lock = threading.Lock()
        self._free_cv = threading.Condition(self._lock)
        self._waiters = 0
        self.queued_total = 0  # observability: how often a burst queued

    def acquire(self, dop: int, timeout: float | None = None) -> int:
        deadline = time.monotonic() + (
            self.queue_timeout_s if timeout is None else timeout
        )
        with self._free_cv:
            first = True
            while self.target - self._used <= 0:
                if first:
                    self.queued_total += 1
                    self._waiters += 1
                    first = False
                remain = deadline - time.monotonic()
                timed_out = remain <= 0 or not self._free_cv.wait(remain)
                # a release can land between the wait timing out and the
                # lock reacquisition: re-check the predicate before
                # failing a query that would now be admissible
                if timed_out and self.target - self._used <= 0:
                    if not first:
                        self._waiters -= 1
                    raise RuntimeError(
                        f"PX admission: queue timeout "
                        f"({self._used}/{self.target} in use, "
                        f"{self._waiters} queued)"
                    )
            if not first:
                self._waiters -= 1
            granted = min(dop, self.target - self._used)
            self._used += granted
            return granted

    def release(self, granted: int) -> None:
        with self._free_cv:
            self._used = max(0, self._used - granted)
            self._free_cv.notify_all()


class PxExecutor(Executor):
    """Compiles logical plans into shard_map SPMD programs over a mesh."""

    # out-of-core streaming composes with PX: each chunk of the streamed
    # table dispatches as one shard_map program over the mesh; partials
    # merge on the (small) single-chip merge plan exactly as single-chip
    chunking_enabled = True
    # shard inputs are row slices — full-table fk_ranges would misindex
    # (PX compile never seeds clustered_aggs either; this is the belt)
    clustered_agg_enabled = False
    # likewise: dynamic-slice range pruning indexes whole-table columns
    scan_slice_enabled = False

    def make_chunk_source(self, stream_table: str, chunk_rows: int):
        # per-shard granularity: the chunk capacity must shard evenly
        unit = 1024 * self.nsh
        rows = -(-chunk_rows // unit) * unit
        src = _PxChunkSourceExecutor(
            self.catalog, stream_table, rows, mesh=self.mesh,
            unique_keys=self.unique_keys, stats=self.stats,
            default_rows_estimate=self.default_rows_estimate,
            broadcast_threshold=self.broadcast_threshold,
            join_bloom=self.join_bloom,
            bloom_max_bits=self.bloom_max_bits,
            hybrid_hash=self.hybrid_hash,
            broadcast_impl=self.broadcast_impl,
            tracer=self.tracer, metrics=self.metrics,
            access=self.access,
        )
        # the streamed path re-crosses the host every chunk: it must share
        # the observability channels so those hops are COUNTED, and the
        # residency ledger so resident side tables charge the governor once
        src.timeline = self.timeline
        src.governor = self.governor
        src.residency = self.residency
        return src

    def _affine_build_info(self, op):
        # inside shard_map a batch is a per-shard slice, and a hash or
        # range exchange reorders rows besides, so the storage-layout
        # affinity the direct-address join relies on does not hold, with
        # one exception: a ROW_SLICED build side gathered whole lies in
        # the table's storage order again (shard i's block at offset
        # i * n, the padding behind the last row). `_emit_join_px` marks
        # that build TABLE_ORDER; every other join sort-merges.
        if self._dist.get(id(op.right)) == TABLE_ORDER:
            return super()._affine_build_info(op)
        return None

    # the distribution state of every node of the plan being traced. One
    # PxExecutor serves every session of a Database and a program is
    # traced on the thread that first calls it, so the state is the
    # thread's own: two sessions compiling at once do not read each
    # other's layout.
    @property
    def _dist(self) -> dict[int, str]:
        return self._trace_local.__dict__.setdefault("dist", {})

    @_dist.setter
    def _dist(self, value: dict[int, str]) -> None:
        self._trace_local.dist = value

    @property
    def _delivered(self) -> list:
        """Of the program being traced: the live rows each row exchange
        (`_gather_batch`, `_exchange_dest`) left on this shard, one traced
        scalar per exchange, in the order of `prepared.px_exchanges`."""
        return self._trace_local.__dict__.setdefault("delivered", [])

    def __init__(self, catalog, mesh: Mesh, unique_keys=None,
                 default_rows_estimate=1 << 16,
                 broadcast_threshold: int = 1 << 16,
                 join_bloom: bool = True,
                 bloom_max_bits: int = 1 << 20,
                 hybrid_hash: "bool | str" = "auto",
                 broadcast_impl: str = "all_gather", stats=None,
                 device_budget=None, chunk_rows=None,
                 tracer=None, metrics=None, access=None):
        if stats is None:
            # histogram-backed cardinalities drive the exchange-method
            # choice (broadcast-vs-hash cost, skew-triggered hybrid hash)
            from ..share.stats import StatsManager

            stats = StatsManager(catalog)
        super().__init__(catalog, unique_keys=unique_keys,
                         default_rows_estimate=default_rows_estimate,
                         stats=stats, device_budget=device_budget,
                         chunk_rows=chunk_rows)
        self.mesh = mesh
        self.nsh = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
        self.mesh_sig = mesh_signature(mesh)
        # BROADCAST lowering schedule: "all_gather" (bisection, default) or
        # "ring" (ppermute pipeline — flat per-link pressure on congested
        # torus axes). Bit-identical outputs; the MeshPlan records which
        # collective actually compiled.
        if broadcast_impl not in ("all_gather", "ring"):
            raise ValueError(f"unknown broadcast_impl {broadcast_impl!r}")
        self.broadcast_impl = broadcast_impl
        # partitioned residency: row sharding leaves each device holding
        # total/nsh bytes of every resident table — the ledger the memory
        # governor charges per device (register_sharded_residency)
        self.residency = ShardedResidency(self.nsh)
        # a plan's input bytes spread over nsh devices, so the per-device
        # budget admits nsh x the single-chip working set before the
        # prepare path degrades to chunk streaming (engine.Executor.prepare
        # multiplies its budget by this)
        self.budget_scale = self.nsh
        # no narrow frame under PX: the result is the mesh program's own
        # output (Executor.fuses_frame; ROADMAP S4)
        self.fuses_frame = False
        # per-compile mesh-plan recorder; bound (and reset) at trace entry
        # of the compiled program — jit traces lazily, so the MeshPlan
        # attached at prepare() time fills in during the first dispatch
        self._lowering: SpmdLowering | None = None
        self.broadcast_threshold = broadcast_threshold
        self.join_bloom = join_bloom
        self.bloom_max_bits = bloom_max_bits
        # skew-adaptive hybrid-hash joins (HYBRID_HASH_BROADCAST/RANDOM):
        # "auto" consults the optimizer histograms (the planner-side analog
        # of the reference's runtime sampling datahub decision,
        # ob_sql_define.h:393); True forces it, False disables
        self.hybrid_hash = hybrid_hash
        # workload repository (server/workload.TableAccessStats): observed
        # NDV / heavy-hitter evidence consulted by the skew heuristic
        # BEFORE the optimizer histograms — measured key frequencies beat
        # quantile-edge inference (JSPIM's sampled skew detection)
        self.access = access
        self._trace_local = threading.local()
        # observability hooks (server/diag.Tracer + share/metrics registry).
        # Exchange helpers run INSIDE traced shard_map code, so accounting
        # happens host-side: once per compile at emission time (static
        # capacities/column counts are Python ints during tracing) and per
        # execute around the dispatch.
        self.tracer = tracer
        self.metrics = metrics
        # (ncols, lane_cap) per exchange emitted by the LAST compile —
        # execute() turns these into per-DFO worker spans
        self._exch_log: list[tuple[str, int, int]] = []

    def _note_exchange(self, kind: str, ncols: int, cap: int,
                       collective: str | None = None) -> None:
        """Host-side DTL accounting, called at TRACE time (once per
        compile): per-lane capacity x lane count x 8-byte columns is the
        shuffle volume the program moves each dispatch."""
        # broadcast all_gathers cap rows per shard; repartition is an
        # all_to_all over nsh^2 (src,dst) lanes of cap rows each
        lanes = self.nsh if kind == "broadcast" else self.nsh * self.nsh
        low = self._lowering
        if low is not None:
            # note() appends the legacy triple too — and _exch_log IS
            # lowering.legacy_log once the traced body bound it
            low.note(kind, ncols, cap, lanes, collective=collective)
        else:
            self._exch_log.append((kind, ncols, cap))
        m = self.metrics
        if m is not None:
            m.add("px exchanges compiled")
            m.add("px exchange rows capacity", cap * lanes)
            m.add("px exchange bytes capacity", ncols * cap * lanes * 8)

    def _note_merge(self, kind: str, ncols: int, cap: int,
                    elem_bytes: int = 8) -> None:
        """Record a reduction collective (psum/pmin/pmax families) in the
        mesh plan. These move O(groups) or O(bitset) data — tiny next to
        the row exchanges — so they stay out of the legacy exchange log
        (whose consumers size worker spans and peak-exchange bytes), but
        the mesh plan must show them: they ARE collectives the hot loop
        dispatches, and the zero-host-hop invariant counts them."""
        low = self._lowering
        if low is not None:
            low.note(kind, ncols, cap, self.nsh, collective="psum",
                     elem_bytes=elem_bytes, legacy=False)

    def execute(self, plan, max_retries: int = 3):
        """Coordinator-side execution wrapper: when a tracer is wired, the
        whole distributed query runs under one coordinator span and every
        compiled exchange gets a worker span nested inside it — so all PX
        spans share the coordinator's trace_id (the DTL channel-id ->
        trace propagation of the reference's full-link tracing)."""
        tr, m = self.tracer, self.metrics
        if tr is None and m is None:
            return super().execute(plan, max_retries)
        import time as _time
        from contextlib import nullcontext

        cm = (tr.span("px_coordinator", dop=self.nsh)
              if tr is not None else nullcontext())
        with cm as root:
            self._exch_log = []
            t0 = _time.perf_counter()
            prepared = self.prepare(plan)
            compile_s = _time.perf_counter() - t0
            retries0 = prepared.retries
            t0 = _time.perf_counter()
            cursor = prepared.dispatch(
                (), max_retries=max_retries, fused=False)
            out = cursor.batch()
            exec_s = _time.perf_counter() - t0
            if tr is not None:
                # per-DFO worker spans (one per exchange boundary the
                # compile emitted), inside the coordinator span. Read from
                # the prepared plan, not self._exch_log: the layout rides
                # the plan (filled at first-dispatch trace), so CACHED
                # plans — which never retrace — still get their spans.
                for i, (kind, ncols, cap) in enumerate(
                        prepared.px_exchanges):
                    with tr.span("px_worker", dfo=i, exchange=kind,
                                 lane_cap=cap, cols=ncols):
                        pass
                root.tags["compile_us"] = int(compile_s * 1e6)
                root.tags["exec_us"] = int(exec_s * 1e6)
            if m is not None:
                m.add("px executions")
                m.add("px exchange rows", cursor.exchange_rows)
                m.add("px exchange slots", prepared.exchange_slots)
                retries = prepared.retries - retries0
                if retries > 0:
                    m.add("px overflow recompiles", retries)
                m.observe("px compile", compile_s)
                m.observe("px execute", exec_s)
                m.wait("px dispatch", exec_s)
            mp = prepared.mesh_plan
            if mp is not None and mp.total_ops:
                if m is not None:
                    for coll, cnt in mp.ops_by_collective().items():
                        m.add(f"px collective {coll}", cnt)
                    m.add("px collective bytes", mp.total_bytes)
                tl = self.timeline
                if tl is not None:
                    tl.record_collective(mp.total_ops, mp.total_bytes)
        return out

    def prepare(self, plan):
        """Compile + attach the mesh plan to the prepared plan, so a
        session executing a CACHED PX plan can still emit per-DFO worker
        spans and per-collective counters (the exchange layout is a
        compile-time artifact; re-deriving it per execution would mean
        re-tracing).

        The attachment is BY REFERENCE, not a snapshot: jax.jit traces at
        first dispatch, so the emission-site notes land in the
        SpmdLowering compile() created only when the program first runs.
        The prepared plan and the traced closure share the same MeshPlan
        object; it fills in during dispatch and every later consumer
        (session folds, artifact save) reads the populated layout."""
        self._lowering = None
        prepared = super().prepare(plan)
        self.sync_prepared(prepared)
        return prepared

    def sync_prepared(self, prepared) -> None:
        """(Re)attach the current compile's mesh plan to a prepared plan —
        called from prepare() and again by PreparedPlan.recompile(), whose
        overflow-retry recompiles build a fresh SpmdLowering that the
        cached plan must follow."""
        low = self._lowering
        if low is None:
            # chunk-streamed plans compile inside the chunk-source
            # executor; the outer plan keeps an empty mesh plan (its
            # per-chunk programs are accounted by the source executor)
            low = SpmdLowering(self.mesh_sig, self.nsh)
        prepared.mesh_plan = low.plan
        prepared.px_exchanges = low.legacy_log
        prepared.px_nsh = self.nsh
        prepared.mesh_sig = self.mesh_sig

    # ------------------------------------------------------------ inputs
    def table_batch(self, name: str, cols: tuple[str, ...]):
        """Raw sharded input: cols/valid/sel arrays padded to a multiple of
        nsh*1024 and placed with row sharding (the granule map)."""
        is_private = getattr(self.catalog, "is_private", None)
        if is_private is not None and is_private(name):
            # tx-private view: shard + upload fresh, NEVER through the
            # shared cache (same isolation contract as the base executor).
            # No residency charge: the view dies with the statement.
            return self._shard_upload(name, cols, resident=False)
        key = (name, cols)
        if key not in self._batch_cache:
            self._batch_cache[key] = self._shard_upload(name, cols)
        return self._batch_cache[key]

    def invalidate_table(self, name: str) -> None:
        super().invalidate_table(name)
        self.residency.discharge(name)

    def _shard_upload(self, name: str, cols: tuple[str, ...],
                      resident: bool = True):
        from ..core.column import make_batch

        t = self.catalog[name]
        sub_schema = Schema(
            tuple(f for f in t.schema.fields if f.name in cols)
        )
        unit = 1024 * self.nsh
        cap = max(unit, -(-(t.nrows or 1) // unit) * unit)
        b = make_batch(
            {c: t.data[c] for c in sub_schema.names()},
            sub_schema,
            {c: d for c, d in t.dicts.items() if c in cols},
            capacity=cap,
            valid={c: v for c, v in t.valid.items() if c in cols},
        )
        raw, nbytes = shard_put(self.mesh, b)
        self.h2d_bytes += nbytes
        if resident:
            # partitioned residency: each device of the mesh now holds
            # nbytes/nsh of this table; the governor charges per device
            self.residency.charge(name, nbytes)
        tl = self.timeline
        if tl is not None:
            tl.record_transfer(nbytes)
        m = self.metrics
        if m is not None:
            m.add("px sharded upload bytes", nbytes)
        return raw

    # ------------------------------------------------------- capacities
    def seed_params(self, plan):
        params = super().seed_params(plan)
        nodes = _number_nodes(plan)
        est = self._est_rows

        def lane_cap(rows: float) -> int:
            # per (src,dst) lane of an all_to_all: expected rows/nsh^2
            # with 2x skew headroom, on the power-of-two grid the root
            # compaction and the result frames use. A capacity is a shape
            # of the program: on a 128-row grid an estimate that moves by
            # a third of a per cent (other data of the same distribution,
            # another first literal) was another program to compile, and
            # a persistent compile cache never hit
            return next_pow2(int(rows * 2 / (self.nsh * self.nsh)) + 512)

        for nid, op in nodes.items():
            if isinstance(op, JoinOp) and op.left_keys:
                params.exchange_cap[_exch_id(nid, _JOIN_LEFT)] = lane_cap(
                    est(op.left))
                params.exchange_cap[_exch_id(nid, _JOIN_RIGHT)] = lane_cap(
                    est(op.right))
            if isinstance(op, Aggregate) and (
                op.group_keys
                # scalar DISTINCT (and approx_ndv) aggs exchange by the
                # distinct argument
                or any(a[3] or a[1] == "approx_ndv" for a in op.aggs)
            ):
                params.exchange_cap[_exch_id(nid, _AGG_CHILD)] = lane_cap(
                    est(op.child))
            if isinstance(op, Sort) and self._sortable_by_range(op):
                params.exchange_cap[_exch_id(nid, _SORT_CHILD)] = lane_cap(
                    est(op.child))
            if isinstance(op, Distinct):
                params.exchange_cap[_exch_id(nid, _AGG_CHILD)] = lane_cap(
                    est(op.child))
            if isinstance(op, SetOp) and not (op.kind == "union" and op.all):
                # UNION ALL never exchanges; every other set op
                # co-partitions both sides by whole-row hash
                params.exchange_cap[_exch_id(nid, _JOIN_LEFT)] = lane_cap(
                    est(op.left))
                params.exchange_cap[_exch_id(nid, _JOIN_RIGHT)] = lane_cap(
                    est(op.right))
            if isinstance(op, Window) and self._window_common_pk(op):
                params.exchange_cap[_exch_id(nid, _AGG_CHILD)] = lane_cap(
                    est(op.child))
        return params

    @staticmethod
    def _sortable_by_range(op: Sort) -> bool:
        """RANGE exchange needs an integer-typed leading sort key (ints,
        dates, dict codes, scaled decimals — everything the engine stores
        as integers)."""
        from ..expr.compile import infer_type
        from ..sql.logical import output_schema

        try:
            dt = infer_type(op.keys[0][0], output_schema(op.child))
        except Exception:
            return False
        return np.issubdtype(dt.storage_np, np.integer)

    @staticmethod
    def _window_common_pk(op: Window):
        """The shared partition-key tuple of all window specs, or None.
        With a common non-empty PARTITION BY, hash repartitioning on it is
        semantics-preserving (each partition lands whole on one shard) —
        the reference's range-dist parallel window (datahub winbuf) analog."""
        pks = {pk for _n, _f, _a, pk, _ok, _x in op.funcs}
        if len(pks) == 1:
            pk = next(iter(pks))
            if pk:
                return pk
        return None

    # -------------------------------------------------------- exchanges
    def _gather_batch(self, b: ColumnBatch, lane,
                      kind: str = "broadcast") -> ColumnBatch:
        """GATHER/BROADCAST: replicate all rows on every shard, via
        all_gather (bisection) or the ppermute ring per broadcast_impl.
        The HLO it emits carries `Exchange:<kind>#<lane>`, as a plan
        node's carries `<kind>#<nid>` (`_emit_scoped`): `broadcast` where
        a join's build side goes to every probe shard, `gather` where a
        sharded relation is collected for a replicated operator (top-n,
        sort; the statement's root, whose lane is the root node's id)."""
        ring = self.broadcast_impl == "ring"
        self._note_exchange("broadcast", len(b.cols) + len(b.valid),
                            int(b.sel.shape[0]),
                            collective="ppermute" if ring else "all_gather")
        payload = {f"c:{n}": a for n, a in b.cols.items()}
        payload.update({f"v:{n}": a for n, a in b.valid.items()})
        with jax.named_scope(f"Exchange:{kind}#{lane}"):
            if ring:
                out, mask = ring_broadcast_rows(payload, b.sel, self.nsh)
            else:
                out, mask = broadcast_rows(payload, b.sel)
            nrows = jnp.sum(mask, dtype=jnp.int64)
        self._delivered.append(nrows)
        return ColumnBatch(
            cols={n: out[f"c:{n}"] for n in b.cols},
            valid={n: out[f"v:{n}"] for n in b.valid},
            sel=mask,
            nrows=nrows,
            schema=b.schema,
            dicts=b.dicts,
        )

    def _exchange_dest(self, b: ColumnBatch, dest_of, cap: int, lane: int,
                       kind: str = "hash"):
        """Redistribute rows of a batch to the shards `dest_of()` names,
        row by row (all_to_all), `cap` rows a lane. The destination
        computation, the lane packing, the collective and what follows it
        carry `Exchange:<kind>#<lane>` in their HLO."""
        self._note_exchange("repartition", len(b.cols) + len(b.valid), cap)
        payload = {f"c:{n}": a for n, a in b.cols.items()}
        payload.update({f"v:{n}": a for n, a in b.valid.items()})
        with jax.named_scope(f"Exchange:{kind}#{lane}"):
            out, mask, ovf = repartition(
                payload, b.sel, dest_of(), self.nsh, cap)
            nrows = jnp.sum(mask, dtype=jnp.int64)
        self._delivered.append(nrows)
        nb = ColumnBatch(
            cols={n: out[f"c:{n}"] for n in b.cols},
            valid={n: out[f"v:{n}"] for n in b.valid},
            sel=mask,
            nrows=nrows,
            schema=b.schema,
            dicts=b.dicts,
        )
        return nb, ovf

    def _exchange_hash(self, b: ColumnBatch, key_exprs, cap: int, lane: int):
        """HASH distribution: co-partition rows by key hash (all_to_all)."""
        return self._exchange_dest(
            b, lambda: dest_by_hash(
                [evaluate(e, b)[0] for e in key_exprs], self.nsh),
            cap, lane)

    def _concat_batches(self, a: ColumnBatch, b: ColumnBatch) -> ColumnBatch:
        """Row-concatenate two same-schema batches (static capacities add)."""
        cols = {n: jnp.concatenate([a.cols[n], b.cols[n]]) for n in a.cols}
        valid = {n: jnp.concatenate([a.valid[n], b.valid[n]]) for n in a.valid}
        sel = jnp.concatenate([a.sel, b.sel])
        return ColumnBatch(
            cols=cols, valid=valid, sel=sel,
            nrows=jnp.sum(sel, dtype=jnp.int64),
            schema=a.schema, dicts=a.dicts,
        )

    def _hybrid_exchange(self, probe: ColumnBatch, probe_keys,
                         build: ColumnBatch, build_keys,
                         cap_probe: int, cap_build: int,
                         lane_probe: int, lane_build: int):
        """HYBRID_HASH_BROADCAST/RANDOM: skew-adaptive repartition.

        The reference samples probe keys through the datahub and routes
        popular values BROADCAST (build side) / RANDOM-local (probe side)
        while normal values go HASH (ob_sql_define.h:393, hybrid-hash with
        the dynamic-sample msg). SPMD analog: a psum'd hash-bucket
        histogram of probe keys picks the popular buckets identically on
        every shard; popular probe rows stay local, popular build rows
        all_gather, normal rows of both sides all_to_all by key hash."""
        hb = 4096
        # two psum'd histograms (probe + build) pick the hot buckets
        self._note_merge("skew_histogram", 2, hb)
        pk = [evaluate(e, probe)[0] for e in probe_keys]
        ph = (hash32_combine(pk) % jnp.uint32(hb)).astype(jnp.int32)
        bk = [evaluate(e, build)[0] for e in build_keys]
        bh = (hash32_combine(bk) % jnp.uint32(hb)).astype(jnp.int32)

        def hot_buckets(h, sel):
            cnt = jnp.zeros(hb, dtype=jnp.int64).at[
                jnp.where(sel, h, hb)
            ].add(1, mode="drop")
            cnt = lax.psum(cnt, SHARD_AXIS)
            # a bucket is popular when its rows would overload one shard's
            # fair share by 2x
            return cnt > jnp.maximum(jnp.sum(cnt) * 2 // self.nsh, 1)

        # skew on EITHER side forces the hybrid route for that key: a
        # heavily-duplicated build key would overload its hash lane exactly
        # like a popular probe key would
        popular = hot_buckets(ph, probe.sel) | hot_buckets(bh, build.sel)
        p_pop = popular[ph] & probe.sel

        probe_norm, ox_p = self._exchange_hash(
            probe.with_sel(probe.sel & ~p_pop), probe_keys, cap_probe,
            lane_probe)
        probe_loc = probe.with_sel(p_pop)
        # align capacities: exchanged batch is nsh*cap rows; local popular
        # rows keep their original capacity — concat handles both
        new_probe = self._concat_batches(probe_norm, probe_loc)

        b_pop = popular[bh] & build.sel
        build_norm, ox_b = self._exchange_hash(
            build.with_sel(build.sel & ~b_pop), build_keys, cap_build,
            lane_build)
        build_bc = self._gather_batch(build.with_sel(b_pop), lane_build)
        new_build = self._concat_batches(build_norm, build_bc)
        return new_probe, new_build, ox_p, ox_b

    def _bloom_prefilter(self, probe: ColumnBatch, probe_keys, build: ColumnBatch,
                         build_keys, est_build: float) -> ColumnBatch:
        """Join-filter pushdown: OR-reduce a build-side key bitset across
        shards, drop probe rows that cannot match BEFORE the exchange."""
        m = min(self.bloom_max_bits, next_pow2(max(int(4 * est_build), 1024)))
        self._note_merge("bloom", 1, m, elem_bytes=4)
        bk = [evaluate(e, build)[0] for e in build_keys]
        h = (hash32_combine(bk) % jnp.uint32(m)).astype(jnp.int32)
        bits = jnp.zeros(m, dtype=jnp.int32).at[
            jnp.where(build.sel, h, m)
        ].set(1, mode="drop")
        bits = lax.psum(bits, SHARD_AXIS) > 0
        pk = [evaluate(e, probe)[0] for e in probe_keys]
        ph = (hash32_combine(pk) % jnp.uint32(m)).astype(jnp.int32)
        return probe.with_sel(probe.sel & bits[ph])

    # ------------------------------------------------------- emission
    def _emit_node(self, op, inputs, emit, params, id_of):
        nid = id_of[id(op)]

        if isinstance(op, Scan):
            out, ovf = super()._emit_node(op, inputs, emit, params, id_of)
            self._dist[id(op)] = ROW_SLICED
            return out, ovf

        if isinstance(op, JoinOp):
            return self._emit_join_px(op, nid, inputs, emit, params, id_of)

        if isinstance(op, Aggregate):
            return self._emit_agg_px(op, nid, inputs, emit, params, id_of)

        if isinstance(op, Sort):
            return self._emit_sort_px(op, nid, inputs, emit, params, id_of)

        if isinstance(op, TopN):
            # two-phase top-n: per-shard top (n+offset) local rows, gather
            # the small survivors, final top-n (the merge-sort-receive
            # coordinator analog, ob_px_ms_receive_vec_op.h)
            child, covf = emit(op.child, inputs)
            if _is_sharded(self._dist[id(op.child)]):
                local = self._topn_batch(
                    child, op.keys, op.n, op.offset, apply_offset=False)
                gathered = self._gather_batch(
                    local, _exch_id(nid, _SORT_CHILD), kind="gather")
                out = self._topn_batch(gathered, op.keys, op.n, op.offset)
            else:
                out = self._topn_batch(child, op.keys, op.n, op.offset)
            self._dist[id(op)] = REPLICATED
            return out, covf

        if isinstance(op, Window):
            return self._emit_window_px(op, nid, inputs, emit, params, id_of)

        if isinstance(op, Limit):
            # per-shard prelimit + compacted gather: moves O(n + offset)
            # rows per shard, never the relation
            child, covf = emit(op.child, inputs)
            if _is_sharded(self._dist[id(op.child)]):
                from ..engine.executor import compact_batch

                k = op.n + op.offset
                pos = jnp.cumsum(child.sel.astype(jnp.int64)) - 1
                local = child.with_sel(child.sel & (pos < k))
                cap2 = min(child.capacity, max(8, -(-k // 8) * 8))
                local, _oc = compact_batch(local, cap2)  # k <= cap2: no ovf
                child = self._gather_batch(
                    local, _exch_id(nid, _SORT_CHILD), kind="gather")
                covf = dict(covf)
            out, ovf = super()._emit_node(
                op, inputs, _override(emit, op.child, (child, covf)),
                params, id_of)
            self._dist[id(op)] = REPLICATED
            return out, ovf

        if isinstance(op, Distinct):
            # hash-repartition on the whole row, then each shard owns its
            # value space: local dedup is globally exact and no shard ever
            # holds the relation (the reference's HASH distinct,
            # ObPQDistributeMethod::HASH)
            child, covf = emit(op.child, inputs)
            cd = self._dist[id(op.child)]
            exch = _exch_id(nid, _AGG_CHILD)
            if (
                _is_sharded(cd)
                and exch in params.exchange_cap
                and self._est_rows(op.child) > self.broadcast_threshold
            ):
                child2, xovf = self._exchange_dest(
                    child, lambda: dest_by_hash(
                        self._row_hash_keys(child), self.nsh),
                    params.exchange_cap[exch], exch)
                out, ovf = super()._emit_node(
                    op, inputs, _override(emit, op.child, (child2, covf)),
                    params, id_of)
                ovf = dict(ovf)
                ovf[exch] = xovf
                self._dist[id(op)] = SHARDED
                return out, ovf
            if _is_sharded(cd):
                child = self._gather_batch(child, exch, kind="gather")
            out, ovf = super()._emit_node(
                op, inputs, _override(emit, op.child, (child, covf)),
                params, id_of)
            self._dist[id(op)] = REPLICATED
            return out, ovf

        if isinstance(op, SetOp):
            return self._emit_setop_px(op, nid, inputs, emit, params, id_of)

        # Filter / Project: local, distribution-preserving
        out, ovf = super()._emit_node(op, inputs, emit, params, id_of)
        child = getattr(op, "child", None)
        self._dist[id(op)] = self._dist[id(child)] if child is not None else SHARDED
        return out, ovf

    # ---- set operations --------------------------------------------------
    def _row_hash_keys(self, b: ColumnBatch):
        """Whole-row hash key columns with set-op NULL normalization
        (validity bits join as int32 so hash32_combine sees integers)."""
        keys = self._setop_key_cols(b.cols, b.valid, b.schema)
        return [
            k.astype(jnp.int32) if k.dtype == jnp.bool_ else k for k in keys
        ]

    def _copartition_side(self, b: ColumnBatch, dist: str, cap: int,
                          lane: int):
        """Bring one promoted set-op side onto the whole-row hash
        partitioning. SHARDED: all_to_all exchange. REPLICATED: free —
        every shard already holds all rows, so each just keeps the ones
        hashing to itself (a mask, no collective)."""
        def dest_of():
            return dest_by_hash(self._row_hash_keys(b), self.nsh)

        if dist == REPLICATED:
            dest = dest_of()
            me = lax.axis_index(SHARD_AXIS).astype(dest.dtype)
            return b.with_sel(b.sel & (dest == me)), None
        return self._exchange_dest(b, dest_of, cap, lane)

    def _emit_setop_px(self, op: SetOp, nid, inputs, emit, params, id_of):
        left, lovf = emit(op.left, inputs)
        right, rovf = emit(op.right, inputs)
        ld, rd = self._dist[id(op.left)], self._dist[id(op.right)]
        ovf = {**lovf, **rovf}
        lb, rb, out_schema, dicts = self._setop_promote(op, left, right)

        if op.kind == "union" and op.all:
            # pure concatenation: SHARDED++SHARDED stays sharded with no
            # exchange; a REPLICATED side spreads by row index so its rows
            # exist exactly once globally
            if ld == rd == REPLICATED:
                out, ovf = self._setop_combine(
                    op, lb, rb, out_schema, dicts, ovf)
                self._dist[id(op)] = REPLICATED
                return out, ovf
            me = lax.axis_index(SHARD_AXIS)
            if ld == REPLICATED:
                ridx = jnp.arange(lb.capacity) % self.nsh
                lb = lb.with_sel(lb.sel & (ridx == me))
            if rd == REPLICATED:
                ridx = jnp.arange(rb.capacity) % self.nsh
                rb = rb.with_sel(rb.sel & (ridx == me))
            out, ovf = self._setop_combine(op, lb, rb, out_schema, dicts, ovf)
            self._dist[id(op)] = SHARDED
            return out, ovf

        cap_l = params.exchange_cap.get(_exch_id(nid, _JOIN_LEFT))
        cap_r = params.exchange_cap.get(_exch_id(nid, _JOIN_RIGHT))
        big = (
            self._est_rows(op.left) + self._est_rows(op.right)
            > self.broadcast_threshold
        )
        if big and cap_l is not None and cap_r is not None \
                and (_is_sharded(ld) or _is_sharded(rd)):
            # co-partition both sides by whole-row hash: every equal row
            # lands on one shard, so the local dedup/bag kernels are
            # globally exact and the output stays SHARDED
            lb2, xl = self._copartition_side(
                lb, ld, cap_l, _exch_id(nid, _JOIN_LEFT))
            rb2, xr = self._copartition_side(
                rb, rd, cap_r, _exch_id(nid, _JOIN_RIGHT))
            out, ovf = self._setop_combine(op, lb2, rb2, out_schema, dicts, ovf)
            ovf = dict(ovf)
            if xl is not None:
                ovf[_exch_id(nid, _JOIN_LEFT)] = xl
            if xr is not None:
                ovf[_exch_id(nid, _JOIN_RIGHT)] = xr
            self._dist[id(op)] = SHARDED
            return out, ovf

        if _is_sharded(ld):
            lb = self._gather_batch(
                lb, _exch_id(nid, _JOIN_LEFT), kind="gather")
        if _is_sharded(rd):
            rb = self._gather_batch(
                rb, _exch_id(nid, _JOIN_RIGHT), kind="gather")
        out, ovf = self._setop_combine(op, lb, rb, out_schema, dicts, ovf)
        self._dist[id(op)] = REPLICATED
        return out, ovf

    # ---- sort / window --------------------------------------------------
    def _emit_sort_px(self, op: Sort, nid, inputs, emit, params, id_of):
        """Large SHARDED sorts exchange by RANGE on the leading key (the
        reference's ObPQDistributeMethod::RANGE, ob_sql_define.h:390):
        every shard gets one contiguous key range, sorts locally, and the
        shard-order concatenation at gather time IS the global order —
        nothing ever holds the whole relation. Small or already-replicated
        inputs keep the gather-then-sort path."""
        from .exchange import dest_by_range, sample_range_bounds

        child, covf = emit(op.child, inputs)
        cd = self._dist[id(op.child)]
        exch = _exch_id(nid, _SORT_CHILD)
        use_range = (
            _is_sharded(cd)
            and exch in params.exchange_cap
            and self._est_rows(op.child) > self.broadcast_threshold
        )
        if not use_range:
            if _is_sharded(cd):
                child = self._gather_batch(child, exch, kind="gather")
            out, ovf = super()._emit_node(
                op, inputs, _override(emit, op.child, (child, covf)),
                params, id_of)
            self._dist[id(op)] = REPLICATED
            return out, ovf

        key_expr, desc0 = op.keys[0]
        self._note_merge("range_sample", 1, 4096)

        def dest_of():
            kv = evaluate(key_expr, child)[0]
            bounds = sample_range_bounds(kv, child.sel, self.nsh)
            dest = dest_by_range(kv.astype(jnp.int64), bounds)
            # shard 0 must hold the HIGHEST range of a descending sort so
            # the gathered concatenation reads in descending order
            return (self.nsh - 1) - dest if desc0 else dest

        child2, xovf = self._exchange_dest(
            child, dest_of, params.exchange_cap[exch], exch, kind="range")
        out, ovf = super()._emit_node(
            op, inputs, _override(emit, op.child, (child2, covf)),
            params, id_of)
        ovf = dict(ovf)
        ovf[exch] = xovf
        # rows stay sharded; each shard holds one globally-contiguous,
        # locally-sorted range (ties colocate: equal keys share a dest)
        self._dist[id(op)] = SHARDED
        return out, ovf

    def _emit_window_px(self, op: Window, nid, inputs, emit, params, id_of):
        """Windows with a common PARTITION BY hash-repartition on it — each
        partition lands whole on one shard, so per-shard evaluation is
        exact and O(rows/shard). Mixed/empty partition keys gather."""
        child, covf = emit(op.child, inputs)
        cd = self._dist[id(op.child)]
        exch = _exch_id(nid, _AGG_CHILD)
        pk = self._window_common_pk(op)
        if (
            _is_sharded(cd)
            and pk is not None
            and exch in params.exchange_cap
            and self._est_rows(op.child) > self.broadcast_threshold
        ):
            child2, xovf = self._exchange_hash(
                child, list(pk), params.exchange_cap[exch], exch)
            out, ovf = super()._emit_node(
                op, inputs, _override(emit, op.child, (child2, covf)),
                params, id_of)
            ovf = dict(ovf)
            ovf[exch] = xovf
            self._dist[id(op)] = SHARDED
            return out, ovf
        if _is_sharded(cd):
            child = self._gather_batch(child, exch, kind="gather")
        out, ovf = super()._emit_node(
            op, inputs, _override(emit, op.child, (child, covf)),
            params, id_of)
        self._dist[id(op)] = REPLICATED
        return out, ovf

    # ---- joins ----------------------------------------------------------
    def _skewed_key(self, side_op, keys) -> bool:
        """Histogram skew signal for auto hybrid-hash: a value repeated
        across r consecutive equi-height bucket edges carries >= (r-1)/N
        of the rows; when one value would overload a shard's fair lane by
        2x, plain hash distribution will hot-spot that shard."""
        from ..share.stats import N_BUCKETS
        from ..sql.logical import Filter, Project, Scan

        if len(keys) != 1 or self.stats is None:
            return False
        e = keys[0]
        name = e.name if isinstance(e, E.ColRef) else None
        if name is None:
            return False
        node = side_op
        while isinstance(node, (Filter, Project)):
            if isinstance(node, Project):
                nxt = dict(node.exprs).get(name)
                if not isinstance(nxt, E.ColRef):
                    return False
                name = nxt.name
            node = node.child
        if not isinstance(node, Scan) or "." not in name:
            return False
        alias, col = name.split(".", 1)
        if alias != node.alias:
            return False
        # runtime evidence first: the workload repository's measured
        # NDV / heavy-hitter fraction for this key column. One observed
        # value carrying >= 2/nsh of the rows overloads its shard's fair
        # lane 2x under plain hash distribution — exactly the condition
        # the quantile-edge walk below infers, but measured, not inferred
        if self.access is not None:
            ev = self.access.key_evidence(
                node.table, col, self.catalog.get(node.table))
            if ev is not None and ev[1] >= 2.0 / self.nsh:
                return True
        ts = self.stats.table_stats(node.table)
        cs = ts.cols.get(col) if ts is not None else None
        if cs is None or cs.edges is None:
            return False
        edges = np.asarray(cs.edges)
        # longest run of identical consecutive edges
        eq = edges[1:] == edges[:-1]
        best = run = 0
        for x in eq:
            run = run + 1 if x else 0
            best = max(best, run)
        hot_frac = best / N_BUCKETS
        return hot_frac >= 2.0 / self.nsh

    def _emit_join_px(self, op, nid, inputs, emit, params, id_of):
        left, lovf = emit(op.left, inputs)
        right, rovf = emit(op.right, inputs)
        ld, rd = self._dist[id(op.left)], self._dist[id(op.right)]
        ovf = {**lovf, **rovf}
        lane_l = _exch_id(nid, _JOIN_LEFT)
        lane_r = _exch_id(nid, _JOIN_RIGHT)

        # choose distribution method (the optimizer's exchange allocation)
        if op.kind == "full" and (_is_sharded(ld) or _is_sharded(rd)):
            # a broadcast build would duplicate unmatched-right rows on
            # every shard: FULL joins must co-partition both sides
            method = "hash" if op.left_keys else "gather_both"
        elif rd == REPLICATED:
            method = "local"  # build already everywhere; probe drives output
        elif not op.left_keys:
            method = "broadcast"  # cross join: replicate the build side
        elif ld == REPLICATED:
            method = "broadcast"  # make both sides replicated
        elif self._est_rows(op.right) <= self.broadcast_threshold or (
            # cost model: broadcast ships est_r to every shard; hash moves
            # each row of both sides once (ObLogPlan's exchange costing)
            self._est_rows(op.right) * (self.nsh - 1)
            <= self._est_rows(op.left)
        ):
            method = "broadcast"
        else:
            method = "hash"

        if method == "hash":
            # bloom pushdown is only sound where dropping non-matching
            # probe rows is a no-op: inner and semi joins (an anti/left
            # join must KEEP unmatched probe rows)
            if self.join_bloom and op.kind in ("inner", "cross", "semi"):
                left = self._bloom_prefilter(
                    left, op.left_keys, right, op.right_keys,
                    self._est_rows(op.right))
            cap_l = params.exchange_cap[lane_l]
            cap_r = params.exchange_cap[lane_r]
            use_hybrid = op.kind == "inner" and (
                self.hybrid_hash is True
                or (
                    self.hybrid_hash == "auto"
                    and (
                        self._skewed_key(op.left, op.left_keys)
                        or self._skewed_key(op.right, op.right_keys)
                    )
                )
            )
            if use_hybrid:
                left, right, xl, xr = self._hybrid_exchange(
                    left, op.left_keys, right, op.right_keys, cap_l, cap_r,
                    lane_l, lane_r)
            else:
                left, xl = self._exchange_hash(
                    left, op.left_keys, cap_l, lane_l)
                right, xr = self._exchange_hash(
                    right, op.right_keys, cap_r, lane_r)
            ovf = dict(ovf)
            ovf[lane_l] = xl
            ovf[lane_r] = xr
            self._dist[id(op.right)] = SHARDED  # exchanged: no order left
            out_dist = SHARDED
        elif method == "broadcast":
            right = self._gather_batch(right, lane_r)
            # the batch that stands for op.right from here on: a scan's
            # row slices gathered whole are the table in storage order
            self._dist[id(op.right)] = (
                TABLE_ORDER if rd == ROW_SLICED else REPLICATED)
            out_dist = ld
        elif method == "gather_both":
            if _is_sharded(ld):
                left = self._gather_batch(left, lane_l, kind="gather")
            if _is_sharded(rd):
                right = self._gather_batch(right, lane_r, kind="gather")
            out_dist = REPLICATED
        else:
            out_dist = ld
        if out_dist == ROW_SLICED and not (
            op.kind in ("semi", "anti")
            or (op.kind == "inner" and self._merge_joinable(op))
        ):
            # only the joins `_resolve_layout_col` walks through emit the
            # probe's columns untouched; an expanding join reorders them
            out_dist = SHARDED

        emit2 = _override(
            _override(emit, op.left, (left, {})), op.right, (right, {}))
        out, jovf = super()._emit_join(op, nid, inputs, emit2, params)
        ovf.update({k: v for k, v in jovf.items() if k not in ovf})
        self._dist[id(op)] = out_dist
        return out, ovf

    # ---- aggregation -----------------------------------------------------
    def _emit_agg_px(self, op, nid, inputs, emit, params, id_of):
        child, covf = emit(op.child, inputs)
        cd = self._dist[id(op.child)]
        lane = _exch_id(nid, _AGG_CHILD)

        if cd == REPLICATED:
            out, ovf = super()._emit_aggregate(
                op, nid, inputs, _override(emit, op.child, (child, covf)),
                params)
            self._dist[id(op)] = REPLICATED
            return out, ovf

        domains = [_dict_domain(child, e) for _, e in op.group_keys]
        direct = (
            bool(op.group_keys)
            and all(d is not None for d in domains)
            and int(np.prod([d for d in domains])) <= DIRECT_GROUPBY_MAX_DOMAIN
        )

        # DISTINCT aggregates: a shard's partial over its local first
        # occurrences double-counts values present on other shards, so the
        # rows must be colocated by the dedup domain BEFORE aggregating.
        # Grouped: the generic hash-repartition on group keys below already
        # does that. Scalar: repartition on the (single) distinct argument,
        # then partials are disjoint and psum-merge correctly.
        # approx_ndv joins the distinct-colocation set: once rows are
        # hash-colocated by the argument, each shard sketches a DISJOINT
        # value set and the estimates psum-merge (union of disjoint sets)
        distinct_args = {a[2] for a in op.aggs if a[3] or a[1] == "approx_ndv"}
        if distinct_args and not op.group_keys:
            if len(distinct_args) == 1:
                child, xovf = self._exchange_hash(
                    child, [next(iter(distinct_args))],
                    params.exchange_cap[lane], lane)
                covf = dict(covf)
                covf[lane] = xovf
            else:
                # two different distinct domains cannot both colocate by
                # one exchange: replicate (rare shape; correct, not fast)
                child = self._gather_batch(child, lane, kind="gather")
                out, ovf = super()._emit_aggregate(
                    op, nid, inputs,
                    _override(emit, op.child, (child, covf)), params)
                self._dist[id(op)] = REPLICATED
                return out, ovf
        elif distinct_args:
            direct = False  # partials+psum would double-count: repartition

        if direct or not op.group_keys:
            # local partials + datahub-rollup merge: moves O(groups), not
            # O(rows) — the right plan for small-domain group-bys (Q1) and
            # scalar aggregates (Q6)
            out, ovf = super()._emit_aggregate(
                op, nid, inputs, _override(emit, op.child, (child, covf)),
                params)
            # datahub-rollup merge: one reduction over the partial-agg
            # columns + sel/valid masks (O(groups) data, not O(rows))
            self._note_merge(
                "merge", len(out.cols) + len(out.valid) + 1,
                int(out.sel.shape[0]))
            merged = dict(out.cols)
            with jax.named_scope(f"Exchange:merge#{nid}"):
                for name, fn, _arg, _d in op.aggs:
                    col = out.cols[name]
                    if fn in ("sum", "count", "approx_ndv"):
                        merged[name] = lax.psum(col, SHARD_AXIS)
                    elif fn == "min":
                        merged[name] = lax.pmin(col, SHARD_AXIS)
                    elif fn == "max":
                        merged[name] = lax.pmax(col, SHARD_AXIS)
                    else:
                        raise NotImplementedError(f"PX merge for {fn}")
                sel = lax.psum(out.sel.astype(jnp.int32), SHARD_AXIS) > 0
                valid = {
                    n: lax.psum(v.astype(jnp.int32), SHARD_AXIS) > 0
                    for n, v in out.valid.items()
                }
                nrows = jnp.sum(sel, dtype=jnp.int64)
            out = replace(
                out, cols=merged, valid=valid, sel=sel, nrows=nrows,
            )
            self._dist[id(op)] = REPLICATED
            return out, ovf

        # generic hash group-by: co-partition rows on the group keys (the
        # ones no other key determines: rows equal on those are equal on
        # all), then each shard owns its key space entirely
        child2, xovf = self._exchange_hash(
            child, [e for _n, e in op.sorted_keys],
            params.exchange_cap[lane], lane)
        out, ovf = super()._emit_aggregate(
            op, nid, inputs, _override(emit, op.child, (child2, covf)), params)
        ovf = dict(ovf)
        ovf[lane] = xovf
        self._dist[id(op)] = SHARDED
        return out, ovf

    # ------------------------------------------------------ compilation
    def compile(self, plan, params):
        self.compiles += 1
        nodes = _number_nodes(plan)
        id_of = {id(o): i for i, o in nodes.items()}
        needed = self._needed_columns(plan)
        scans = self._collect_scans(plan)
        input_spec = []
        side: dict[str, tuple[Schema, dict]] = {}
        for s in scans:
            cols = needed.get(s.alias, set())
            if not cols:
                cols = {self.catalog[s.table].schema.fields[0].name}
            cols = tuple(sorted(cols))
            input_spec.append((s.alias, s.table, cols))
            t = self.catalog[s.table]
            sub_schema = Schema(
                tuple(f for f in t.schema.fields if f.name in cols))
            side[s.alias] = (
                sub_schema,
                {c: d for c, d in t.dicts.items() if c in cols},
            )

        from ..engine.executor import PACK_GUARD_BASE

        overflow_nodes = sorted(
            set(params.groupby_size) | set(params.join_cap)
            | set(params.exchange_cap)
            | {
                PACK_GUARD_BASE + nid
                for nid in params.pack_guard
                if nid not in params.groupby_nopack
            }
        )

        def emit(op, inputs):
            return self._emit_scoped(op, inputs, emit, params, id_of)

        from ..engine.executor import (
            _collect_qparam_spec,
            _unpack_qparams,
            program_name,
        )

        qparam_spec = _collect_qparam_spec(plan)
        # the mesh-plan recorder for THIS compile. jit traces lazily, so
        # run_local binds it (and resets it — a retrace replays every
        # note) at trace entry; prepare() attaches the same object to the
        # prepared plan so the layout is visible once the program has run
        lowering = SpmdLowering(self.mesh_sig, self.nsh)
        self._lowering = lowering

        def run_local(raw_inputs, qparams):
            from ..expr import compile as expr_compile

            # trace-entry binding: emission-site notes (and the legacy
            # exchange log execute() reads) land in this compile's
            # recorder regardless of which plan this executor traced last
            self._lowering = lowering
            self._exch_log = lowering.legacy_log
            lowering.reset()
            # packed-vector ABI parity with the single-chip PreparedPlan
            # (a packed array here would otherwise hit bool(tracer))
            qparams = _unpack_qparams(qparams, qparam_spec)
            inputs = {}
            for alias, raw in raw_inputs.items():
                schema, dicts = side[alias]
                sel = raw["sel"]
                inputs[alias] = ColumnBatch(
                    cols=dict(raw["cols"]),
                    valid=dict(raw["valid"]),
                    sel=sel,
                    nrows=jnp.sum(sel, dtype=jnp.int64),
                    schema=schema,
                    dicts=dicts,
                )
            self._dist = {}
            del self._delivered[:]
            prev = expr_compile.set_params(qparams if qparams else None)
            try:
                out, ovf = emit(plan, inputs)
            finally:
                expr_compile.set_params(prev)
            # compact BEFORE the root gather: the collective then moves
            # O(result) rows per shard instead of the full capacity
            from ..engine.executor import ROOT_COMPACT, compact_batch

            out, oc = compact_batch(out, params.join_cap[ROOT_COMPACT])
            ovf = dict(ovf)
            ovf[ROOT_COMPACT] = oc
            if _is_sharded(self._dist[id(plan)]):
                # the statement's own gather: its lane is the root node's
                # id, as a merge's is its aggregate's (every other lane is
                # an `_exch_id`, a million and up)
                out = self._gather_batch(out, id_of[id(plan)], kind="gather")
            # overflow counters must leave the shard_map replicated; psum
            # may multiply already-replicated counters by nsh, which is
            # harmless (the driver only tests >0)
            # one more element rides the vector the host reads at the sync
            # anyway: the live rows the exchanges delivered, over all shards
            # (`DeviceResult.exchange_rows`, counter `px exchange rows`)
            delivered = sum(self._delivered, jnp.zeros((), jnp.int64))
            ovf_vec = jnp.stack([
                lax.psum(v, SHARD_AXIS) for v in (
                    *(ovf.get(n, jnp.zeros((), jnp.int64))
                      for n in overflow_nodes), delivered)
            ])
            return out, ovf_vec

        def run(raw_inputs, qparams):
            in_specs = (
                jax.tree.map(lambda _: P(SHARD_AXIS), raw_inputs),
                jax.tree.map(lambda _: P(), qparams),
            )
            # no replication check: replication of the outputs
            # (all_gathered or psum-merged) is guaranteed by construction
            # but not statically inferable through gather-then-local-
            # compute chains; the PX test suite verifies it against
            # single-chip results
            return shard_map_compat(
                run_local,
                mesh=self.mesh,
                in_specs=in_specs,
                out_specs=P(),
                check_replication=False,
            )(raw_inputs, qparams)

        run.__name__ = program_name(plan) + "_px"
        return jax.jit(run), input_spec, overflow_nodes


class _PxChunkSourceExecutor(ChunkWindowMixin, PxExecutor):
    """PxExecutor whose streamed table reads one fixed-capacity chunk —
    every chunk of the out-of-core loop is one shard_map dispatch over
    the mesh (engine/chunked.py drives it exactly like the single-chip
    chunk executor; the slice/estimate logic lives in ChunkWindowMixin)."""

    chunking_enabled = False

    def _affine_build_info(self, op):
        # the streamed table's batch is one chunk of it, not the table
        return None

    # legacy host-slice chunk loop: PX uploads must shard over the mesh
    # (jax.device_put of a staged pytree would land whole on one device),
    # so the streaming prefetch/decode pipeline stays single-chip
    supports_staged = False

    def __init__(self, catalog, stream_table: str, chunk_rows: int,
                 mesh=None, **kw):
        super().__init__(catalog, mesh, **kw)
        self.stream_table = stream_table
        self.chunk_rows = chunk_rows
        self._chunk: tuple[int, int] | None = None

    def table_batch(self, name: str, cols: tuple[str, ...]):
        if name != self.stream_table or self._chunk is None:
            return super().table_batch(name, cols)
        b = self._chunk_slice_batch(name, cols)
        # THE host-mediated DTL hop: each chunk of the streamed table
        # crosses host->device per dispatch. Counted so the mesh smoke can
        # assert the resident SPMD hot loop performs ZERO of these —
        # collectives move all steady-state data.
        m = self.metrics
        if m is not None:
            m.add("px dtl host hops")
        low = self._lowering
        if low is not None:
            low.note_host_hop()
        raw, _nbytes = shard_put(self.mesh, b)
        return raw


def _override(emit, node, result):
    """An emit view that returns a precomputed (exchanged) batch for one
    child node and delegates everything else."""

    def emit2(op, inputs):
        if op is node:
            return result
        return emit(op, inputs)

    return emit2
