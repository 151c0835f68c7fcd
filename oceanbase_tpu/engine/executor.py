"""Physical codegen + execution: logical plan -> one jitted XLA program.

Reference surface: the code generator (ObStaticEngineCG,
sql/code_generator/ob_static_engine_cg.h:185) that lowers the logical plan
to an ObOpSpec tree, plus the ObOperator::get_next_batch driver loop
(sql/engine/ob_operator.cpp:1425). The TPU redesign collapses the operator
pull-loop entirely: the whole plan (or later, each DFO) traces into ONE XLA
computation over table ColumnBatches — scan masks, join gathers, group-by
scatters, sort permutations all fuse into a single device program, which is
the idiomatic TPU replacement for per-batch virtual dispatch.

Static-shape discipline (the ObBatchRows analog): every intermediate keeps
its producer's capacity with a live-row `sel` mask. Operators that change
cardinality (expand joins, group-bys) emit into planner-chosen static
capacities and return overflow counters; the host driver checks the
counters and re-executes with larger capacities (the TPU analog of the
reference's spill-to-disk: respill-to-a-larger-compile).

Physical choices made here (the optimizer's physical half):
- join: unique-build hash join when the build side's key covers a declared
  unique key of its base table; expand (sort+searchsorted) join otherwise.
- group-by: direct-addressed scatter when all keys are small-domain
  dictionary/bounded columns (packed perfect hash); open-addressing hash
  table otherwise (the reference's adaptive bypass, chosen statically).
"""

from __future__ import annotations

import threading
import time
import weakref
from dataclasses import dataclass, field, replace

import jax
import jax.numpy as jnp
import numpy as np

from ..core.column import ColumnBatch, batch_to_host, pin_dicts, unpin_dicts
from ..core.dtypes import DataType, Field, Schema, TypeKind
from ..expr import ir as E
from ..expr.compile import (
    bind_value,
    compile_predicate,
    count_lowering,
    derive_dict_column,
    evaluate,
    infer_type,
)
from ..ops.compact import live_positions
from ..ops.hashagg import assign_group_slots, sort_groupby
from ..ops.hashing import next_pow2, pack_keys
from ..ops.join import (
    build_hash_table,
    expand_join,
    hash_join_probe,
    join_keys64,
    merge_join_unique,
    probe_run_any,
    sort_build_side,
)
from ..ops.sort import sort_indices
from ..share import gap_ledger as _gap
from ..ops.window import peer_ends, segment_starts
from ..sql.logical import (
    Aggregate,
    Distinct,
    Filter,
    JoinOp,
    Limit,
    LogicalOp,
    Project,
    Scan,
    SetOp,
    Sort,
    TopN,
    Window,
    op_kind,
    output_schema,
    setop_schema,
    unique_key_sets,
    window_out_type,
)

# direct group-by = one fused masked reduction per (slot, aggregate): dirt
# cheap on the VPU for small domains (measured ~2.4ms for 8 slots over 8M
# rows) but linear in the domain, so the cap is small; larger domains ride
# the sort-based path (a TPU scatter costs ~1.1s per 8M rows, so the old
# scatter-direct design lost to sorting even at domain 8)
DIRECT_GROUPBY_MAX_DOMAIN = 1 << 6

# synthetic PhysicalParams id for the root result-compaction capacity
ROOT_COMPACT = -1

# synthetic overflow-node id space for the pack-validity guards (disjoint
# from plan node ids and the PX exchange-lane ids, parallel/px.py)
PACK_GUARD_BASE = 5_000_000

# synthetic overflow-node id space for ANN over-probe escalation: a
# candidate-starvation counter (live re-rank candidates < k) rides the
# overflow channel at ANN_PROBE_BASE + nid and bumps the node's
# effective nprobe instead of a capacity
ANN_PROBE_BASE = 9_000_000


def gather_payload(cols: dict, valid: dict, idx, sel=None):
    """Gather a whole batch payload by one index array via the packed
    row-gather (ops/gather.py). Use where len(idx) is comparable to the
    table length — the packing pass scans the full table once, so tiny
    index sets (top-n, root compaction) keep plain element gathers."""
    from ..ops.gather import gather_rows

    payload = {("c", n): c for n, c in cols.items()}
    payload.update({("v", n): v for n, v in valid.items()})
    if sel is not None:
        payload[("s", "")] = sel
    out = gather_rows(payload, idx)
    cols2 = {n: out[("c", n)] for n in cols}
    valid2 = {n: out[("v", n)] for n in valid}
    return cols2, valid2, out.get(("s", ""))


def compact_batch(b: ColumnBatch, cap2: int):
    """Compact live rows to a smaller capacity, preserving their relative
    order (stable sort by deadness). Returns (batch, overflow count).
    Used at plan roots so device->host result transfer moves O(result)
    bytes, not O(input capacity)."""
    if b.capacity <= cap2:
        return b, jnp.zeros((), jnp.int64)
    idx = jnp.arange(b.capacity, dtype=jnp.int32)
    _dead, sidx = jax.lax.sort((~b.sel, idx), num_keys=2)
    take = sidx[:cap2]
    nlive = jnp.sum(b.sel, dtype=jnp.int64)
    sel = jnp.arange(cap2, dtype=jnp.int64) < nlive
    out = ColumnBatch(
        cols={n: c[take] for n, c in b.cols.items()},
        valid={n: v[take] for n, v in b.valid.items()},
        sel=sel,
        nrows=jnp.minimum(nlive, cap2),
        schema=b.schema,
        dicts=b.dicts,
    )
    return out, jnp.maximum(nlive - cap2, 0)


@dataclass
class PhysicalParams:
    """Static capacities per plan node (keyed by pre-order node index;
    exchange lanes use synthesized ids, see parallel/px.py)."""

    groupby_size: dict[int, int] = field(default_factory=dict)
    join_cap: dict[int, int] = field(default_factory=dict)
    exchange_cap: dict[int, int] = field(default_factory=dict)
    # stats-packed group keys: nid -> ((vmin, bits) per key). A runtime
    # pack-validity counter rides the overflow channel (PACK_GUARD_BASE +
    # nid); overflow disables packing for that node and recompiles.
    pack_guard: dict[int, tuple] = field(default_factory=dict)
    groupby_nopack: set = field(default_factory=set)
    # clustered-FK segment aggregation specs (nid -> ClusteredAggSpec),
    # re-detected on every compile (deterministic from plan + catalog)
    clustered_aggs: dict = field(default_factory=dict)
    # range-pruned sorted-projection scans: nid -> _SliceSpec, with the
    # static slice capacity in scan_cap (overflow-bumped like join caps)
    scan_slice: dict = field(default_factory=dict)
    scan_cap: dict[int, int] = field(default_factory=dict)
    # top-k candidate prefilter capacities (TopN via lax.top_k on the
    # first key, exact under the tie-overflow guard)
    topn_cand: dict[int, int] = field(default_factory=dict)
    # ANN: TopN-over-vec_l2 nodes served by an IVF index (nid -> spec)
    vector_topns: dict = field(default_factory=dict)
    # ANN over-probe state: nid -> effective nprobe (survives the
    # per-compile vector_topns re-detection so an escalation sticks),
    # nid -> total list count (the escalation ceiling — probing every
    # list IS the exact answer, so the retry always resolves there)
    ann_nprobe: dict[int, int] = field(default_factory=dict)
    ann_lists: dict[int, int] = field(default_factory=dict)
    ann_escalations: int = 0  # lifetime over-probe bumps (sysstat delta)

    def bump(self, overflows: dict[int, int]):
        for nid in overflows:
            if nid >= ANN_PROBE_BASE:
                # candidate starvation (the filter decimated the probed
                # lists): escalate nprobe x8 toward the full list count —
                # recall-preserving over-probe, not post-filtering a
                # fixed-k result. x8 reaches any ceiling within the
                # standard retry budget (8 -> 64 -> 512 -> 4096).
                vid = nid - ANN_PROBE_BASE
                cur = self.ann_nprobe.get(vid)
                if cur is not None:
                    self.ann_nprobe[vid] = min(
                        cur * 8, self.ann_lists.get(vid, cur * 8))
                    self.ann_escalations += 1
                continue
            if nid >= PACK_GUARD_BASE:
                self.groupby_nopack.add(nid - PACK_GUARD_BASE)
                continue
            if nid in self.groupby_size:
                self.groupby_size[nid] *= 4
            if nid in self.join_cap:
                self.join_cap[nid] *= 4
            if nid in self.exchange_cap:
                self.exchange_cap[nid] *= 4
            if nid in self.scan_cap:
                # the slice capacity was seeded from ONE representative
                # parameter value; a wider runtime range is the normal
                # plan-cache reuse case, so the retry must always
                # resolve: drop back to the unsliced full scan (cap >=
                # table rows disables slicing in the Scan emission)
                self.scan_cap[nid] = 1 << 62
            if nid in self.topn_cand:
                # ties on a low-cardinality first key can exceed ANY
                # candidate budget: the retry must always resolve, so
                # one overflow disables the prefilter (cand >= capacity
                # skips it at emit) and the exact full sort runs
                self.topn_cand[nid] = 1 << 62


class ClusteredPremiseInvalidated(Exception):
    """A cached plan's clustered-FK premise no longer holds (the probe
    table's data changed and its fk column is no longer monotone);
    PreparedPlan.run recompiles, which re-detects and drops the spec."""


@dataclass(frozen=True)
class _SliceSpec:
    """Range bounds of a sorted-projection scan: the scan reads only the
    contiguous key range [max(lows), min(highs)) via device binary search
    + dynamic_slice (engine/executor.py Scan emission). Bounds are
    (Literal, searchsorted side) pairs so slotted literals keep the plan
    reusable across parameter values."""

    key: str                   # qualified sort-key column
    lows: tuple = ()           # (E.Literal, 'left'|'right') lower bounds
    highs: tuple = ()          # (E.Literal, 'left'|'right') upper bounds


@dataclass(frozen=True)
class VectorTopNSpec:
    """ORDER BY vec_l2(col, q) LIMIT k over an IVF-indexed scan: probe =
    centroid matmul + top-nprobe + contiguous-list candidate gather +
    exact re-rank matmul + top-k (storage/vector_index.py). Filter
    predicates between the TopN and the Scan ride INTO the fused kernel
    (evaluated as selection masks before the candidate re-rank) with
    recall preserved by over-probe: a starvation counter on the overflow
    channel escalates nprobe when the filter decimates the probed
    lists."""

    table: str
    column: str        # unqualified vector column
    qual_col: str      # alias-qualified name in the scan batch
    input_alias: str
    nprobe: int        # static: probed lists (over-probe escalated)
    max_list: int      # static: per-list read window
    nrows: int         # static: live rows of the table at compile
    k: int
    key: object        # the vec_l2 Func (resolved through the Project)
    scan: object       # the Scan node to emit
    proj: object       # Project between TopN and Scan (or None)
    filters: tuple = ()    # Filter predicates fused into the kernel
    lists: int = 0         # total IVF list count (escalation ceiling)
    base_nprobe: int = 0   # registered nprobe before over-probe seeding
    est_sel: float = 1.0   # estimated filter selectivity at compile
    ivf_cost: float = 0.0  # optimizer route cost, IVF side (EXPLAIN)
    brute_cost: float = 0.0  # route cost of the brute-force matmul
    cost_basis: str = "flops"  # "measured" when calibration records won


@dataclass(frozen=True)
class ClusteredAggSpec:
    """One Aggregate-over-PK-FK-join collapsed into segment reductions
    (see Executor._clustered_agg_spec)."""

    ji: object        # the JoinOp replaced by per-build-row range sums
    probe_table: str
    fk_col: str       # clustered probe key (unqualified storage column)
    fk_name: str      # qualified probe-side join key name
    build_table: str
    pk_col: str
    input_alias: str  # inputs key carrying the (starts, ends) arrays
    # the ranges tile the probe table (Executor._fk_ranges proved it on
    # the host): a group's lower bound IS its neighbour's upper bound, so
    # the program gathers the running table once per group, not twice
    tiled: bool = False


def segment_bounds(running: dict, starts, ends, tiled: bool):
    """(at_hi, at_lo): every running column of the clustered-FK aggregate
    at row `ends - 1` and at row `starts - 1` of the probe table, one
    value per build row (`upto(x) = c[x-1] if x > 0 else 0`; the caller
    masks x == 0). ONE packed row-gather per bound materializes every
    aggregate's running value (ops/gather.py). When the ranges tile
    (ClusteredAggSpec.tiled), starts[j] == ends[j-1] on every real build
    row, so the lower bound's row is the one the neighbour just fetched:
    the second gather is the first one's output shifted down a row, bit
    for bit (row 0 and the padded tail have starts == 0)."""
    from ..ops.gather import gather_rows

    cap = next(iter(running.values())).shape[0]
    at_hi = gather_rows(running, jnp.clip(ends - 1, 0, cap - 1))
    if tiled:
        count_lowering("clustered agg bounds shared")
        at_lo = {
            k: jnp.concatenate([jnp.zeros(1, v.dtype), v[:-1]])
            for k, v in at_hi.items()
        }
    else:
        count_lowering("clustered agg bounds gathered")
        at_lo = gather_rows(running, jnp.clip(starts - 1, 0, cap - 1))
    return at_hi, at_lo


def _number_nodes(plan: LogicalOp) -> dict[int, LogicalOp]:
    out = {}

    def rec(op):
        out[len(out)] = op
        for c in _children(op):
            rec(c)

    rec(plan)
    return out


def _children(op: LogicalOp):
    if isinstance(op, (Filter, Project, Sort, Limit, Distinct, Aggregate,
                       Window, TopN)):
        return [op.child]
    if isinstance(op, (JoinOp, SetOp)):
        return [op.left, op.right]
    return []


def _row_key_operands(cols, valid, schema):
    """Whole-row lexicographic sort operands with NULLs-compare-equal
    semantics: nullable columns contribute (zeroed values, validity flag)
    pairs; int64 columns split into two int32 planes (the multi-i64
    sort cliff, ops/sort.py). Returns (operands, spec) where spec records
    (name, nullable, dtype, nplanes) for _unpack_sorted. Shared by dedup
    and bag set-op kernels."""
    from ..ops.sort import split_sort_key

    operands: list[jnp.ndarray] = []
    spec: list[tuple[str, bool, object, int]] = []
    for f in schema.fields:
        c = cols[f.name]
        v = valid.get(f.name)
        cz = jnp.where(v, c, jnp.zeros((), c.dtype)) if v is not None else c
        planes = split_sort_key(cz)
        operands.extend(planes)
        if v is not None:
            operands.append(v)
        spec.append((f.name, v is not None, c.dtype, len(planes)))
    return operands, spec


def _run_boundaries(sorted_operands):
    """True at positions where any sorted operand differs from the previous
    row — the first row of each equal-value run."""
    n = sorted_operands[0].shape[0]
    new = jnp.zeros(n, jnp.bool_)
    for sv in sorted_operands:
        new = new | jnp.concatenate(
            [jnp.ones(1, jnp.bool_), sv[1:] != sv[:-1]]
        )
    return new


def _unpack_sorted(svals, spec):
    """Rebuild (cols, valid) dicts from sorted operands per the spec that
    _row_key_operands produced (int64 columns reassemble from planes)."""
    from ..ops.sort import rebuild_i64

    cols, valid = {}, {}
    i = 0
    for name, nullable, dtype, nplanes in spec:
        if nplanes == 2:
            cols[name] = rebuild_i64(svals[i], svals[i + 1])
        else:
            cols[name] = svals[i].astype(dtype)
        i += nplanes
        if nullable:
            valid[name] = svals[i]
            i += 1
    return cols, valid


def _dict_domain(batch: ColumnBatch, e: E.Expr) -> int | None:
    """Static domain size of a group key expr (dict columns, bools)."""
    if isinstance(e, E.ColRef):
        d = batch.dicts.get(e.name)
        if d is not None:
            # all the direct path asks of the domain: a pinned dictionary
            # that grows past the cap serves the same program
            return d.domain(DIRECT_GROUPBY_MAX_DOMAIN)
        t = batch.schema[e.name]
        if t.kind is TypeKind.BOOL:
            return 2
        if t.kind is TypeKind.INT8:
            return 256
    return None


def _device_nbytes(obj) -> int:
    """Sum nbytes over the device arrays inside an executor input — a
    ColumnBatch, the PX raw cols/valid/sel dict, or derived-structure
    tuples (fk_ranges, ivf arrays)."""
    if hasattr(obj, "nbytes"):  # an array, or a ColumnBatch's planes
        return int(obj.nbytes)
    if isinstance(obj, dict):
        return sum(_device_nbytes(v) for v in obj.values())
    if isinstance(obj, (tuple, list)):
        return sum(_device_nbytes(v) for v in obj)
    return 0


class Executor:
    # subclasses that manage their own placement (PX) disable chunking
    chunking_enabled = True
    # clustered-FK segment aggregation requires whole-table inputs in
    # storage order; sharded (PX) and chunk-streamed executors disable it
    clustered_agg_enabled = True
    # range-pruned slicing of sorted-projection scans needs whole-table
    # device columns (shards/chunks would misindex); the projection SWAP
    # itself is layout-only and stays on everywhere
    scan_slice_enabled = True

    def __init__(self, catalog, unique_keys=None, default_rows_estimate=1 << 16,
                 stats=None, device_budget=None, chunk_rows=None):
        self.catalog = catalog
        # kept by reference (not `or {}`): the server hands in its live,
        # initially empty registry and fills it as tables are created
        self.unique_keys = unique_keys if unique_keys is not None else {}
        self.default_rows_estimate = default_rows_estimate
        # share/stats.StatsManager: NDV/histogram-backed cardinalities for
        # static capacities (None = heuristic constants)
        self.stats = stats
        # out-of-core: inputs beyond this many bytes stream through the
        # plan in chunks (engine/chunked.py); None = library default
        from .chunked import DEFAULT_CHUNK_ROWS, DEFAULT_DEVICE_BUDGET

        self.device_budget = (
            device_budget if device_budget is not None else DEFAULT_DEVICE_BUDGET
        )
        self.chunk_rows = chunk_rows or DEFAULT_CHUNK_ROWS
        self._batch_cache: dict[tuple[str, tuple], ColumnBatch] = {}
        # bumped by invalidate_table; derived device structures that span
        # TWO tables (fk_ranges) revalidate against both versions, since
        # the key-prefix delete in invalidate_table only covers one
        self._table_version: dict[str, int] = {}
        # an invalidation (version bump + drop) and an upload's entry into
        # the cache are atomic to each other: table_batch stores what it
        # uploaded only while the version it started at still holds, so
        # an upload of a table a publish replaced meanwhile never outlives
        # that publish's invalidation
        self._cache_lock = threading.Lock()
        # lifetime host->device upload bytes (QueryProfile reads the delta
        # around one execution: cache hits upload nothing, which is the
        # point of the per-column device cache)
        self.h2d_bytes = 0
        # the part of it uploaded into the device cache: what a plan's
        # footprint memo keys on (an upload past the cache, of a private
        # or statement-scoped view, changes no input the cache holds)
        self.cache_h2d_bytes = 0
        # hook: share/timeline.ServingTimeline — cold uploads land as
        # transfer-interference events (the server wires it)
        self.timeline = None
        # assembled-ColumnBatch memo over the per-column cache: a warm
        # statement's _inputs() otherwise rebuilds the batch wrapper —
        # including a jnp.sum dispatch for nrows — on EVERY dispatch
        # (serving-path profile: ~80us/stmt). Validated by table version.
        self._assembled: dict[tuple, tuple[int, ColumnBatch]] = {}
        # cross-session micro-batching: lifetime count of batched-bucket
        # executables built (one per (plan, pow2 bucket) — the bench
        # asserts this stays bounded by the bucket count, not traffic)
        self.batched_compiles = 0
        # lifetime count of Executor.compile invocations (cold compiles +
        # overflow recompiles). Artifact-hydrated statements never come
        # through compile(), which is what the warm-boot smoke pins:
        # compiles + batched_compiles stays 0 across a warm replay
        self.compiles = 0
        # whole-statement fusion: lifetime count of narrowed (result-frame)
        # executables built — one per (plan, pow2 narrow bucket), same
        # bounding argument as batched_compiles
        self.narrow_compiles = 0
        # ANN observability: (table, col) -> last-build metadata (the
        # __all_virtual_vector_index rows' build side) and cumulative
        # per-index [queries, probes, escalations] counters folded by
        # the serving session per executed ANN statement
        self.ann_builds: dict = {}
        self.ann_stats: dict = {}
        # hook: engine/plan_profile.OperatorProfileStore — when wired
        # (server layer), measured TopN-route rates calibrate the
        # IVF-vs-brute cost comparison in _vector_topn_spec
        self.profile_store = None
        # hook: engine/memory_governor.MemoryGovernor — when wired, its
        # (OOM-shrunk) effective budget clamps the static device budget
        # so prepare() routes oversized inputs through the chunked path
        # instead of attempting an unguarded whole-table upload
        self.governor = None
        # set on degraded host-fallback executors: disables the
        # EN_DEVICE_OOM injection point (host execution cannot device-OOM)
        self.host_fallback = False
        # whether this executor's plans hand a client the fused narrow
        # frame (PreparedPlan.dispatch). The executors that stand in for
        # a session's own set it false and get the plain frame: PX, a
        # streamed plan's merge, the degraded ladder's chunked and host
        # rungs
        self.fuses_frame = True
        # streaming pipeline knobs (engine/pipeline.py): prefetch depth 0
        # disables the prefetch thread (strictly alternating wire/compute
        # — the bench A/B baseline); stream_compress off ships raw
        # frame-of-reference chunks instead of the advisor encodings
        import os as _os

        self.stream_prefetch_depth = max(0, int(_os.environ.get(
            "OB_STREAM_PREFETCH",
            _os.environ.get("OB_STREAM_PIPELINE", "2"))))
        self.stream_compress = _os.environ.get(
            "OB_STREAM_COMPRESS", "1") not in ("0", "false", "off")

    # ---- input preparation -------------------------------------------
    def _collect_scans(self, plan: LogicalOp) -> list[Scan]:
        out = []

        def rec(op):
            if isinstance(op, Scan):
                out.append(op)
            for c in _children(op):
                rec(c)

        rec(plan)
        return out

    def _needed_columns(self, plan: LogicalOp) -> dict[str, set[str]]:
        """alias -> set of unqualified column names referenced anywhere."""
        needed: dict[str, set[str]] = {}

        def note(e: E.Expr):
            for q in E.referenced_columns(e):
                if "." in q:
                    a, c = q.split(".", 1)
                    needed.setdefault(a, set()).add(c)

        def rec(op):
            if isinstance(op, Scan) and op.pushed_filter is not None:
                note(op.pushed_filter)
            if isinstance(op, Filter):
                note(op.pred)
            if isinstance(op, Project):
                for _, e in op.exprs:
                    note(e)
            if isinstance(op, JoinOp):
                for e in op.left_keys + op.right_keys:
                    note(e)
                if op.residual is not None:
                    note(op.residual)
            if isinstance(op, Aggregate):
                for _, e in op.group_keys:
                    note(e)
                for _, _, a, _ in op.aggs:
                    if a is not None:
                        note(a)
            if isinstance(op, (Sort, TopN)):
                for e, _ in op.keys:
                    note(e)
            if isinstance(op, Window):
                for _name, fn, a, pk, ok, extra in op.funcs:
                    if a is not None:
                        note(a)
                    if fn in ("lag", "lead") and extra is not None \
                            and extra[1] is not None:
                        note(extra[1])
                    for p in pk:
                        note(p)
                    for oe, _d in ok:
                        note(oe)
            for c in _children(op):
                rec(c)

        rec(plan)
        return needed

    def _access_columns(self, plan: LogicalOp) -> dict[str, set]:
        """alias -> set of (column, role) pairs for the workload access
        stats: which columns the plan uses as filter predicates, join
        keys, group keys, or sort keys (server/workload.ROLE_* indices).
        Same reference walk as _needed_columns, keeping the role."""
        from ..server.workload import (
            ROLE_FILTER,
            ROLE_GROUP,
            ROLE_JOIN,
            ROLE_SORT,
        )

        acc: dict[str, set] = {}
        # output name -> defining expr across every Project in the plan:
        # the planner rewrites sort/group keys into synthetic projected
        # columns ($ordN), so an unqualified ColRef must chase its
        # definition back to the base columns it computes from
        defs: dict[str, E.Expr] = {}

        def collect_defs(op):
            if isinstance(op, Project):
                for name, e in op.exprs:
                    defs.setdefault(name, e)
            for c in _children(op):
                collect_defs(c)

        collect_defs(plan)

        def note(e: E.Expr, role: int, depth: int = 0):
            for q in E.referenced_columns(e):
                if "." in q:
                    a, c = q.split(".", 1)
                    acc.setdefault(a, set()).add((c, role))
                elif depth < 4 and q in defs:
                    note(defs[q], role, depth + 1)

        def rec(op):
            if isinstance(op, Scan) and op.pushed_filter is not None:
                note(op.pushed_filter, ROLE_FILTER)
            if isinstance(op, Filter):
                note(op.pred, ROLE_FILTER)
            if isinstance(op, JoinOp):
                for e in op.left_keys + op.right_keys:
                    note(e, ROLE_JOIN)
            if isinstance(op, Aggregate):
                for _, e in op.group_keys:
                    note(e, ROLE_GROUP)
            if isinstance(op, (Sort, TopN)):
                for e, _ in op.keys:
                    note(e, ROLE_SORT)
            for c in _children(op):
                rec(c)

        rec(plan)
        return acc

    def _access_profile(self, scans0: list, routed_plan: LogicalOp,
                        roles: dict[str, set]) -> tuple:
        """Static per-compiled-plan access profile: one entry per scan —
        (base table, row count at compile time, has sorted projections,
        routed to one, ((column, role), ...)). scans0 are the PRE-routing
        scans; routing is identity-preserving for plan structure, so the
        post-routing scan list pairs positionally (projection hits show
        as a changed scan.table). Virtual tables are excluded — querying
        the stats must not pollute them."""
        scans1 = self._collect_scans(routed_plan)
        out = []
        cat = self.catalog
        for s0, s1 in zip(scans0, scans1):
            if s0.table.startswith(("__all_virtual", "$")):
                # virtual tables and planner-internal relations (chunked
                # $partials overlays) are not workload objects
                continue
            t = cat[s0.table] if s0.table in cat else None
            rows = t.nrows if t is not None else 0
            has_proj = bool(getattr(t, "sorted_projections", None))
            cols = tuple(sorted(roles.get(s0.alias, ())))
            out.append((s0.table, rows, has_proj, s1.table != s0.table,
                        cols))
        return tuple(out)

    def invalidate_table(self, name: str) -> None:
        """Drop cached device batches of one table (its data changed)."""
        with self._cache_lock:
            self._table_version[name] = self._table_version.get(name, 0) + 1
            for key in [k for k in self._batch_cache if k[0] == name]:
                del self._batch_cache[key]
            for key in [k for k in self._assembled if k[0] == name]:
                del self._assembled[key]

    def input_device_bytes(self, input_spec) -> int:
        """Device-resident footprint of a prepared plan's inputs (array
        nbytes at the operator boundary) — QueryProfile's device_bytes
        source. Called after execution, so every input is already in the
        device cache and this walks cached arrays without new uploads."""
        total = 0
        for alias, table, cols in input_spec:
            try:
                total += _device_nbytes(self.input_batch(alias, table, cols))
            except Exception:  # noqa: BLE001 - accounting must never fail a query
                continue
        return total

    def fk_ranges(self, probe_table: str, fk_col: str,
                  build_table: str, pk_col: str, tiled: bool = False):
        """Device (starts, ends) int32 arrays over build-table rows: build
        row i joins exactly the probe rows [starts[i], ends[i]) — valid
        because the probe's fk column is stored CLUSTERED (monotone
        nondecreasing, checked by _monotone_col before any caller gets
        here). Host-precomputed by binary search once per table version and
        cached like device columns; this is the LSM analog of the
        reference's ordered-index row ranges (an FK sstable scan range per
        PK, cf. storage/access table scan ranges) and what lets a PK-FK
        join + group-by collapse into segment reductions with no sort and
        no per-probe-row gather.

        `tiled` is what the calling program was compiled to assume
        (ClusteredAggSpec.tiled, carried by its '#fkr:' input_spec entry):
        a program that shares each group's lower bound with its
        neighbour's upper bound must never meet ranges that stopped
        tiling, so that raises the recompile signal. The reverse is
        harmless (two gathers are exact over any ranges)."""
        dev, tiles = self._fk_ranges(probe_table, fk_col, build_table, pk_col)
        if tiled and not tiles:
            raise ClusteredPremiseInvalidated(
                f"{probe_table}.{fk_col} ranges no longer tile over "
                f"{build_table}.{pk_col}"
            )
        return dev

    def _fk_ranges(self, probe_table: str, fk_col: str,
                   build_table: str, pk_col: str):
        """((starts, ends), tiles) of fk_ranges, cached by the two table
        versions. `tiles`: the ranges of the real build rows partition a
        prefix of the probe table in build order — starts[0] == 0 and
        starts[j] == ends[j-1] — which holds whenever the build table lies
        in key order and every fk value below the last pk has its pk (any
        valid foreign key over tables stored by key)."""
        vp = self._table_version.get(probe_table, 0)
        vb = self._table_version.get(build_table, 0)
        key = (probe_table, ("#fkr", fk_col, build_table, pk_col))
        hit = self._batch_cache.get(key)
        if hit is not None and hit[0] == (vp, vb):
            return hit[1], hit[2]
        # data changed since the spec was detected: the clustering premise
        # must be re-proven, not assumed — a cached plan over a now
        # unsorted fk would binary-search garbage and silently mis-group
        if not self._monotone_col(probe_table, fk_col):
            raise ClusteredPremiseInvalidated(
                f"{probe_table}.{fk_col} is no longer monotone"
            )
        tp = self.catalog[probe_table]
        tb = self.catalog[build_table]
        fk = np.asarray(tp.data[fk_col])
        pk = np.asarray(tb.data[pk_col])
        lo = np.searchsorted(fk, pk, side="left").astype(np.int32)
        hi = np.searchsorted(fk, pk, side="right").astype(np.int32)
        tiles = bool(
            len(lo) >= 1 and lo[0] == 0 and np.array_equal(lo[1:], hi[:-1])
        )
        cap = max(1024, -(-max(tb.nrows, 1) // 1024) * 1024)
        if cap > len(lo):
            pad = np.zeros(cap - len(lo), dtype=np.int32)
            lo = np.concatenate([lo, pad])
            hi = np.concatenate([hi, pad])
        dev = (jnp.asarray(lo), jnp.asarray(hi))
        self._batch_cache[key] = ((vp, vb), dev, tiles)
        return dev, tiles

    def input_batch(self, alias: str, table: str, cols: tuple):
        """One jit input from its input_spec entry: a table ColumnBatch,
        or a derived structure ('#fkr:' = clustered-FK join ranges,
        '#ivf:' = IVF vector-index arrays)."""
        if alias.startswith("#fkr:"):
            return self.fk_ranges(*cols)
        if alias.startswith("#ivf:"):
            tname, col, max_list = cols
            return self.ivf_device(tname, col, max_list)
        return self.table_batch(table, cols)

    def ivf_host(self, table: str, col: str):
        """Built IvfIndex for (table, col), staleness-checked two ways:
        the table VERSION (DML through invalidate_table bumps it) AND the
        column array's IDENTITY (weakref, same discipline as
        _monotone_col) — a memtable mutation that swapped t.data[col]
        without an invalidation hook must never serve a stale index
        silently. Invalidation = lazy rebuild on next use, same contract
        as sorted projections."""
        from ..storage.vector_index import build_ivf

        t = self.catalog[table]
        spec = getattr(t, "vector_indexes", {}).get(col)
        if spec is None:
            return None
        arr = t.data[col]
        v = self._table_version.get(table, 0)
        key = (table, ("#ivfh", col))
        hit = self._batch_cache.get(key)
        if hit is not None and hit[0] == v and hit[2]() is arr:
            return hit[1]
        t0 = time.perf_counter()
        idx = build_ivf(np.asarray(arr), lists=spec.lists)
        # weakref: a strong array ref would double-count host bytes in
        # the device census walk; the catalog holds the array anyway
        self._batch_cache[key] = (v, idx, weakref.ref(arr))
        self.ann_builds[(table, col)] = {
            "build_version": v,
            "build_unix": time.time(),
            "build_s": time.perf_counter() - t0,
            "rows": int(len(arr)),
        }
        return idx

    def ivf_device(self, table: str, col: str, expect_max_list: int):
        """(centroids, perm, offsets, lengths) device arrays; raises the
        premise-invalidated recompile signal when a rebuild changed the
        static window shape the compiled program assumed. Keyed on the
        host index OBJECT identity, not just the table version — an
        identity-detected rebuild (ivf_host's stale-array path) must
        re-upload even though the version never moved."""
        idx = self.ivf_host(table, col)
        if idx is None or idx.max_list != expect_max_list:
            raise ClusteredPremiseInvalidated(
                f"vector index on {table}.{col} changed shape"
            )
        v = self._table_version.get(table, 0)
        key = (table, ("#ivfd", col))
        hit = self._batch_cache.get(key)
        if hit is not None and hit[0] == v and hit[2] is idx:
            return hit[1]
        dev = (
            jnp.asarray(idx.centroids),
            jnp.asarray(idx.perm),
            jnp.asarray(idx.offsets),
            jnp.asarray(idx.lengths),
        )
        self._batch_cache[key] = (v, dev, idx)
        return dev

    def ann_residency(self) -> dict:
        """(table, column) -> device bytes of uploaded IVF artifacts.
        The governor charges these against tenant residency (an index the
        advisor keeps hot is memory the admission path must see), and
        __all_virtual_vector_index reads the same walk."""
        out: dict = {}
        for k, hit in list(self._batch_cache.items()):
            if (isinstance(k, tuple) and len(k) == 2
                    and isinstance(k[1], tuple) and k[1]
                    and k[1][0] == "#ivfd"):
                dev = hit[1]
                out[(k[0], k[1][1])] = sum(
                    int(getattr(a, "nbytes", 0)) for a in dev)
        return out

    def ann_device_bytes(self) -> int:
        return sum(self.ann_residency().values())

    # host-side monotonicity cache (id+weakref discipline: see
    # _affine_cache below for why a bare id is not enough)
    _monotone_cache: dict = {}

    def _monotone_col(self, table: str, col: str) -> bool:
        """True when the stored column array is monotone NONDECREASING —
        i.e. the table is physically clustered by this column (LSM tables
        laid out in key order; TPC-H lineitem by l_orderkey). Nullable
        columns are excluded: NULL rows carry arbitrary storage values."""
        try:
            t = self.catalog[table]
            arr = t.data[col]
        except (KeyError, AttributeError):
            return False
        if col in getattr(t, "valid", {}):
            return False
        if not isinstance(arr, np.ndarray) or arr.ndim != 1 or len(arr) < 1:
            return False
        if not np.issubdtype(arr.dtype, np.integer):
            return False
        key = id(arr)
        hit = Executor._monotone_cache.get(key)
        if hit is not None and hit[0]() is arr:
            return hit[1]
        if len(Executor._monotone_cache) > 4096:
            Executor._monotone_cache.clear()
        out = bool(np.all(arr[1:] >= arr[:-1]))
        Executor._monotone_cache[key] = (weakref.ref(arr), out)
        return out

    def table_batch(self, name: str, cols: tuple[str, ...]) -> ColumnBatch:
        if name == "$dual":  # FROM-less SELECT: one anonymous row
            return ColumnBatch(
                cols={"$one": jnp.zeros(1, jnp.int8)},
                valid={},
                sel=jnp.ones(1, jnp.bool_),
                nrows=jnp.ones((), jnp.int64),
                schema=Schema((Field("$one", DataType.int8()),)),
                dicts={},
            )
        is_private = getattr(self.catalog, "is_private", None)
        if is_private is not None and is_private(name):
            # tx-private view: never enters (or reads) the shared device
            # cache, so other sessions can't see uncommitted rows
            return self._build_batch(name, cols)
        # the device cache is PER COLUMN, not per column-set: queries with
        # overlapping needs share one H2D upload per column (uploads over
        # the network-attached chip cost ~seconds/GB and dominated the
        # bench when q1/q6/q3/q14 each re-shipped lineitem)
        ver = self._table_version.get(name, 0)
        memo = self._assembled.get((name, cols))
        if memo is not None and memo[0] == ver:
            return memo[1]
        t = self.catalog[name]
        sub_schema = Schema(
            tuple(f for f in t.schema.fields if f.name in cols)
        )
        n = t.nrows
        cap = max(1024, -(-max(n, 1) // 1024) * 1024)
        cache = self._batch_cache
        skey = (name, "#sel")
        have = {f.name: cache.get((name, f.name)) for f in sub_schema.fields}
        sel = cache.get(skey)
        cold = [f for f in sub_schema.fields if have[f.name] is None]
        if cold or sel is None:
            # one span per table batch that moves columns to the device
            with _gap.span("h2d") as sp:
                up, new_sel, nb = self._upload_cold(t, cold, cap, sel is None)
                sp.moved(nb)
            with self._cache_lock:
                if self._table_version.get(name, 0) == ver:
                    cache.update({(name, c): v for c, v in up.items()})
                    if new_sel is not None:
                        cache[skey] = new_sel
            have.update(up)
            if new_sel is not None:
                sel = new_sel
        dcols: dict[str, jnp.ndarray] = {}
        dvalid: dict[str, jnp.ndarray] = {}
        for f in sub_schema.fields:
            dev, vdev = have[f.name]
            dcols[f.name] = dev
            if vdev is not None:
                dvalid[f.name] = vdev
        batch = ColumnBatch(
            cols=dcols,
            valid=dvalid,
            sel=sel,
            nrows=jnp.sum(sel, dtype=jnp.int64),
            schema=sub_schema,
            dicts={c: d for c, d in t.dicts.items() if c in cols},
        )
        with self._cache_lock:
            if self._table_version.get(name, 0) == ver:
                self._assembled[(name, cols)] = (ver, batch)
        return batch

    def _upload_cold(self, t, fields, cap: int, with_sel: bool):
        """Upload the `fields` of `t` the device cache lacks, and its
        `#sel` plane `with_sel`: ({column: (data, validity)}, the plane or
        None, bytes), for table_batch to cache."""
        from ..core.column import narrowed_upload

        n = t.nrows
        out = {}
        sel = None
        nb = 0
        for f in fields:
            a = np.asarray(t.data[f.name], dtype=f.dtype.storage_np)
            dev = narrowed_upload(a, cap)
            vdev = None
            if f.dtype.nullable:
                v = (
                    np.asarray(t.valid[f.name], dtype=np.bool_)
                    if f.name in t.valid
                    else np.ones(n, dtype=np.bool_)
                )
                if cap > n:
                    v = np.concatenate(
                        [v, np.zeros(cap - n, dtype=np.bool_)])
                vdev = jnp.asarray(v)
            out[f.name] = (dev, vdev)
            nb += int(dev.nbytes) + (
                int(vdev.nbytes) if vdev is not None else 0)
        if with_sel:
            s = np.zeros(cap, dtype=np.bool_)
            s[:n] = True
            sel = jnp.asarray(s)
            nb += int(sel.nbytes)
        self.h2d_bytes += nb
        self.cache_h2d_bytes += nb
        tl = self.timeline
        if tl is not None and tl.enabled:
            # a cold-column upload steals device time from the serving
            # stream: transfer interference
            tl.record_transfer(nb)
        return out, sel, nb

    def _build_batch(self, name: str, cols: tuple[str, ...]) -> ColumnBatch:
        t = self.catalog[name]
        sub_schema = Schema(
            tuple(f for f in t.schema.fields if f.name in cols)
        )
        from ..core.column import make_batch

        with _gap.span("h2d") as sp:
            b = make_batch(
                {c: t.data[c] for c in sub_schema.names()},
                sub_schema,
                {c: d for c, d in t.dicts.items() if c in cols},
                valid={c: v for c, v in t.valid.items() if c in cols},
            )
            nb = b.nbytes
            sp.moved(nb)
        # counted like a cache upload: the plan monitor's and the workload
        # repository's transfer bytes read the delta
        self.h2d_bytes += nb
        return b

    # ---- physical parameter seeding ----------------------------------
    def _est_rows(self, op) -> float:
        """Cardinality estimate driving static capacities (and the PX
        layer's distribution-method choice)."""
        est_rows = self._est_rows
        if isinstance(op, Scan):
            if op.table == "$dual":
                return 1.0
            t = self.catalog[op.table]
            base = t.nrows or 1
            if op.pushed_filter is not None:
                ts = self.stats.table_stats(op.table) if self.stats else None
                if ts is not None and ts.nrows > 0:
                    base *= ts.selectivity(op.pushed_filter, t)
                else:
                    base *= 0.25 ** min(
                        len(self._conjuncts(op.pushed_filter)), 3
                    )
            return max(base, 1.0)
        if isinstance(op, Filter):
            return max(est_rows(op.child) * 0.5, 1.0)
        if isinstance(op, JoinOp):
            l = est_rows(op.left)
            r = est_rows(op.right)
            if op.kind in ("semi", "anti"):
                return max(l * 0.5, 1.0)
            if op.kind == "left":
                return l * 2
            if op.kind == "full":
                return l + r
            if not op.left_keys:  # cross / scalar broadcast
                return l if self._is_scalar_relation(op.right) else l * r
            if self._join_build_unique(op):
                # each probe row matches at most one build row; the MATCH
                # RATE is the filtered fraction of the build's key space
                # (containment): est(right)/|build base|. Floored at 0.05
                # — correlated filters make underestimates, and every
                # overflow retry is a recompile
                rb = self._build_base_rows(op.right)
                if rb and rb > 0:
                    return max(l * max(min(r / rb, 1.0), 0.05), 1.0)
                return l
            # M:N equi-join: |L||R| / max(ndv(Lkeys), ndv(Rkeys)) — the
            # textbook containment estimate (ob_opt_selectivity analog)
            lndv = self._keys_ndv(op.left, op.left_keys)
            rndv = self._keys_ndv(op.right, op.right_keys)
            if lndv is not None and rndv is not None:
                denom = max(min(lndv, l), min(rndv, r), 1.0)
                return max((l * r) / denom, 1.0)
            return max(l, r) * 2
        if isinstance(op, Aggregate):
            child = est_rows(op.child)
            nd = self._group_ndv(op)
            if nd is not None:
                return max(min(child, nd), 1.0)
            return min(child, float(self.default_rows_estimate))
        if isinstance(op, (Project, Sort, Distinct, Window)):
            return est_rows(op.child)
        if isinstance(op, (Limit, TopN)):
            return float(op.n + op.offset)
        if isinstance(op, SetOp):
            l, r = est_rows(op.left), est_rows(op.right)
            if op.kind == "union":
                return l + r
            if op.kind == "intersect":
                return min(l, r)
            return l  # except
        return float(self.default_rows_estimate)

    def _static_key_range(self, child: LogicalOp, e) -> tuple[int, int] | None:
        """(vmin, bits) for a group-key expr whose value domain is known
        statically: dictionary codes (exact domain from the dict length)
        or stats min/max (exact at collection; 4x headroom covers drift,
        and the runtime pack guard catches anything beyond). None = not
        packable."""
        name = e.name if isinstance(e, E.ColRef) else None
        if name is None:
            return None

        def resolve(node, name):
            if isinstance(node, Filter):
                return resolve(node.child, name)
            if isinstance(node, Project):
                nxt = dict(node.exprs).get(name)
                if not isinstance(nxt, E.ColRef):
                    return None
                return resolve(node.child, nxt.name)
            if isinstance(node, JoinOp):
                return resolve(node.left, name) or resolve(node.right, name)
            if isinstance(node, Scan) and "." in name:
                alias, col = name.split(".", 1)
                if alias == node.alias:
                    return (node.table, col)
            return None

        hit = resolve(child, name)
        if hit is None:
            return None
        table, col = hit
        try:
            t = self.catalog[table]
        except KeyError:
            return None
        d = t.dicts.get(col)
        if d is not None:
            dom = max(len(d), 1)
            # append-dictionaries can grow: headroom + runtime guard
            return 0, max((4 * dom - 1).bit_length(), 1)
        try:
            ct = t.schema[col]
        except Exception:
            return None
        if not np.issubdtype(ct.storage_np, np.integer):
            # float keys would TRUNCATE into the packed int domain and
            # merge distinct groups without tripping the range guard
            return None
        ts = self.stats.table_stats(table) if self.stats else None
        cs = ts.cols.get(col) if ts is not None else None
        if cs is None or cs.ndv <= 0:
            return None
        span = int(cs.vmax) - int(cs.vmin) + 1
        if span <= 0:
            return None
        return int(cs.vmin), max((4 * span - 1).bit_length(), 1)

    def seed_params(self, plan: LogicalOp) -> PhysicalParams:
        params = PhysicalParams()
        nodes = _number_nodes(plan)
        est_rows = self._est_rows

        # root compaction capacity: results travel device->host compacted
        # to the estimated output size (pulling a full input-capacity batch
        # to the host costs seconds at SF>=1); overflow retries apply
        params.join_cap[ROOT_COMPACT] = next_pow2(
            int(2 * est_rows(plan)) + 1024
        )
        # group-by / distinct / set-op dedup are sort-based: output reuses
        # the input capacity, so no table sizes (and no overflow retries)
        # are seeded for them
        for nid, op in nodes.items():
            if isinstance(op, Scan) and self.scan_slice_enabled:
                ps = getattr(self, "_pending_slices", {}).get(id(op))
                if ps is not None and nid not in params.scan_slice:
                    params.scan_slice[nid], params.scan_cap[nid] = ps
            if (
                isinstance(op, TopN)
                and self.clustered_agg_enabled  # whole-batch executors only
                and op.n + op.offset <= 1024
                and nid not in params.topn_cand
            ):
                params.topn_cand[nid] = max(
                    256, -(-4 * (op.n + op.offset) // 64) * 64
                )
            if isinstance(op, Aggregate) and op.grouping_sets is None:
                # multi-key sort group-bys pack into ONE int64 sort key
                # when every key's domain is statically known: wide
                # multi-operand sorts go superlinear past ~16M rows on
                # v5e, a packed key keeps the canonical fast sort shape.
                # The keys that are sorted: dependent ones are carried
                ranges = [
                    self._static_key_range(op.child, e)
                    for _n, e in op.sorted_keys
                ]
                if len(ranges) > 1 and all(
                    r is not None for r in ranges
                ) and sum(
                    b for _v, b in ranges
                ) <= 62:
                    params.pack_guard[nid] = tuple(ranges)
            if isinstance(op, JoinOp):
                needs_cap = (
                    (op.kind in ("inner", "cross")
                     and not self._merge_joinable(op))
                    or (op.kind in ("semi", "anti") and op.residual is not None)
                    or op.kind in ("left", "full")
                )
                if needs_cap:
                    if op.kind in ("semi", "anti", "left", "full"):
                        # candidate-pair capacity, not output rows
                        cap = int(
                            max(est_rows(op.left), est_rows(op.right)) * 2
                        ) + 1024
                    else:
                        cap = int(est_rows(op)) * 2 + 1024
                    params.join_cap[nid] = -(-cap // 1024) * 1024
        return params

    # host-side column-layout property cache. Keyed by id(array) with a
    # WEAK reference in the value: a bare id can be reused by a new array
    # after the old one is GC'd (catalog refreshes replace DML tables'
    # arrays), which would silently apply a stale (a0, stride) to an
    # unrelated column and drop matching join rows. The weakref keeps the
    # check honest (dead ref or different object -> recompute) without
    # pinning superseded multi-MB columns until the 4096-entry clear.
    _affine_cache: dict[int, tuple["weakref.ref", tuple[int, int] | None]] = {}

    def _resolve_layout_col(self, node: LogicalOp, name: str):
        """(table, col) when output column `name` of `node` IS a base
        Scan's stored array (same length, same order — only the sel mask
        differs), seen through the layout-preserving ops: Filter, Project
        renames, and the PROBE side of joins that keep the probe layout
        (semi/anti always; inner via the merge/affine path, which emits
        probe columns untouched and only gathers build columns). None
        when the column is computed, gathered, or re-ordered."""
        while True:
            if isinstance(node, Filter):
                node = node.child
            elif isinstance(node, Project):
                nxt = dict(node.exprs).get(name)
                if not isinstance(nxt, E.ColRef):
                    return None
                name = nxt.name
                node = node.child
            elif isinstance(node, JoinOp) and (
                node.kind in ("semi", "anti")
                or (node.kind == "inner" and self._merge_joinable(node))
            ):
                # a build-side column would gather (new layout), but then
                # its alias only exists in the right subtree and the final
                # Scan-alias check below fails — the walk stays honest
                node = node.left
            else:
                break
        if not isinstance(node, Scan) or "." not in name:
            return None
        alias, col = name.split(".", 1)
        if alias != node.alias:
            return None
        return node.table, col

    def _affine_build_info(self, op: JoinOp) -> tuple[int, int] | None:
        """(a0, stride) when the build side's single join-key column is an
        AFFINE sequence in storage order (key[i] = a0 + stride*i) — true
        for identifier columns of LSM tables laid out in key order with
        regular keys (every TPC-H key column). Such joins skip sorting
        entirely: the matching build row is (key - a0) / stride, verified
        by one gather — a direct-address join (the TPU answer to the
        reference's hash table; cf. dense dict decoders in
        blocksstable/encoding). Filters/projections/layout-preserving
        joins above the scan keep the array layout (they only mask or
        rename), so the property holds through them."""
        if not op.left_keys or len(op.right_keys) != 1:
            return None
        e = op.right_keys[0]
        if not isinstance(e, E.ColRef):
            return None
        hit = self._resolve_layout_col(op.right, e.name)
        if hit is None:
            return None
        table, col = hit
        if "#sp:" in table:
            # routed projection scans may be DYNAMICALLY SLICED
            # (params.scan_slice): affine candidates index full-table
            # rows and would misindex the sliced batch
            return None
        try:
            arr = self.catalog[table].data[col]
        except (KeyError, AttributeError):
            return None
        if not isinstance(arr, np.ndarray) or arr.ndim != 1 or len(arr) < 2:
            return None
        key = id(arr)
        hit = Executor._affine_cache.get(key)
        if hit is not None and hit[0]() is arr:
            return hit[1]
        if len(Executor._affine_cache) > 4096:
            Executor._affine_cache.clear()
        out = None
        if np.issubdtype(arr.dtype, np.integer):
            stride = int(arr[1]) - int(arr[0])
            if stride > 0:
                d = np.diff(arr)
                if (d == stride).all():
                    out = (int(arr[0]), stride)
        Executor._affine_cache[key] = (weakref.ref(arr), out)
        return out

    def _merge_joinable(self, op: JoinOp) -> bool:
        """True when the join rides the combined-sort unique-build merge
        path (no pair expansion, no capacity): unique build side and one
        integer-typed key per side (dates, dict codes, ints, decimals —
        everything the engine stores as integers). Multi-column or
        non-integer keys go through expand_join, whose exact pair
        verification is collision-safe for hashed keys."""
        if not self._join_build_unique(op):
            return False
        if not op.left_keys:  # scalar-subquery cross: constant int key
            return True
        if len(op.left_keys) != 1:
            return False
        from ..expr.compile import infer_type

        try:
            lt = infer_type(op.left_keys[0], output_schema(op.left))
            rt = infer_type(op.right_keys[0], output_schema(op.right))
        except Exception:
            return False
        return (
            np.issubdtype(lt.storage_np, np.integer)
            and np.issubdtype(rt.storage_np, np.integer)
        )

    @staticmethod
    def _conjuncts(e):
        from ..sql.planner import split_conjuncts

        return split_conjuncts(e)

    def _keys_ndv(self, side: LogicalOp, keys) -> float | None:
        """Product of base-column NDVs for join keys resolvable to scans of
        `side` (None when any key isn't a plain column or stats are off)."""
        if self.stats is None:
            return None
        amap = {s.alias: s.table for s in self._collect_scans(side)}
        prod = 1.0
        for k in keys:
            if not isinstance(k, E.ColRef) or "." not in k.name:
                return None
            a, c = k.name.split(".", 1)
            tname = amap.get(a)
            if tname is None:
                return None
            ts = self.stats.table_stats(tname)
            nd = ts.ndv_of(c) if ts is not None else None
            if nd is None or nd <= 0:
                return None
            prod *= nd
        return prod

    def _build_base_rows(self, node: LogicalOp) -> float | None:
        """UNFILTERED row count of the base relation a unique-build side
        reads — the denominator of the join match-rate estimate. Walks
        the same layout chain as _join_build_unique."""
        while isinstance(node, (Filter, Project)):
            node = node.child
        if isinstance(node, JoinOp) and node.kind in ("inner", "semi", "anti"):
            return self._build_base_rows(node.left)
        if isinstance(node, Scan):
            try:
                return float(self.catalog[node.table].nrows or 1)
            except KeyError:
                return None
        return None

    def _group_ndv(self, op: Aggregate) -> float | None:
        """Product of group-key NDVs (grouping cardinality upper bound)."""
        if self.stats is None or not op.group_keys:
            return None
        prod = 1.0
        amap = {s.alias: s.table for s in self._collect_scans(op.child)}
        for _name, e in op.group_keys:
            if not isinstance(e, E.ColRef) or "." not in e.name:
                return None
            a, c = e.name.split(".", 1)
            tname = amap.get(a)
            if tname is None:
                return None
            ts = self.stats.table_stats(tname)
            nd = ts.ndv_of(c) if ts is not None else None
            if nd is None or nd <= 0:
                return None
            prod *= nd
        return prod

    @staticmethod
    def _is_scalar_relation(node: LogicalOp) -> bool:
        """True for a guaranteed-1-row relation (grand aggregate, possibly
        under projections/filters) — the broadcast side of a scalar-subquery
        join."""
        while isinstance(node, (Filter, Project)):
            node = node.child
        return isinstance(node, Aggregate) and not node.group_keys

    def _join_build_unique(self, op: JoinOp) -> bool:
        """True if the build (right) side's join keys cover a unique key of
        its source: a base table's declared unique key, an Aggregate's full
        group-key set, or a Distinct's full column set — seen through
        Filter/Project (renames followed) and through joins that cannot
        duplicate probe rows (semi/anti, and inner joins whose own build
        side is unique: each probe row matches at most one build row, so
        output rows are a subset of the probe side's rows and a unique key
        of the probe side stays unique)."""
        if self._is_scalar_relation(op.right):
            return True
        names = []
        for e in op.right_keys:
            if not isinstance(e, E.ColRef):
                return False
            names.append(e.name)
        node = op.right
        while True:
            if isinstance(node, Filter):
                node = node.child
            elif isinstance(node, Project):
                rename = {n: ex for n, ex in node.exprs}
                nxt = []
                for n in names:
                    ex = rename.get(n)
                    if not isinstance(ex, E.ColRef):
                        return False
                    nxt.append(ex.name)
                names = nxt
                node = node.child
            elif isinstance(node, JoinOp) and (
                node.kind in ("semi", "anti")
                or (node.kind == "inner" and self._join_build_unique(node))
            ):
                node = node.left
            else:
                break
        if isinstance(node, Aggregate):
            gk = {n for n, _ in node.group_keys}
            return bool(gk) and gk <= set(names)
        if isinstance(node, Distinct):
            cols = set(output_schema(node).names())
            return cols <= set(names)
        if isinstance(node, Scan):
            # a routed sorted projection keeps the base table's rows (and
            # so its unique keys) under the '#sp:' name
            base = node.table.split("#sp:", 1)[0]
            uks = unique_key_sets(self.unique_keys, node.table) \
                + unique_key_sets(self.unique_keys, base)
            key_cols = {
                n.split(".", 1)[1] for n in names if n.startswith(node.alias + ".")
            }
            return any(set(uk) <= key_cols for uk in uks)
        return False

    # ---- sorted-projection scan routing -------------------------------
    _RANGE_KINDS = (TypeKind.DATE, TypeKind.INT8, TypeKind.INT16,
                    TypeKind.INT32, TypeKind.INT64)

    def _route_projections(self, plan: LogicalOp) -> LogicalOp:
        """Swap eligible Scans to sorted projections of their table (the
        index-selection step: a selective range predicate on a projection's
        sort key + covered columns). The swap alone is layout-only (same
        rows, different order) and correct under every executor; the
        contiguous-slice optimization rides separately via
        params.scan_slice where scan_slice_enabled."""
        self._pending_slices = {}
        needed = self._needed_columns(plan)

        def rec(op):
            # identity-preserving: PX keys distribution decisions by plan
            # node id, so untouched subtrees must come back AS-IS
            if isinstance(op, Scan):
                out = self._projection_choice(op, needed.get(op.alias, set()))
                return out if out is not None else op
            if isinstance(op, (JoinOp, SetOp)):
                left, right = rec(op.left), rec(op.right)
                if left is op.left and right is op.right:
                    return op
                return replace(op, left=left, right=right)
            if hasattr(op, "child"):
                child = rec(op.child)
                return op if child is op.child else replace(op, child=child)
            return op

        return rec(plan)

    def _projection_choice(self, scan: Scan, needed_cols: set):
        if scan.pushed_filter is None:
            return None
        try:
            t = self.catalog[scan.table]
        except KeyError:
            return None
        projs = getattr(t, "sorted_projections", None)
        if not projs:
            return None
        from ..expr.compile import bind_value

        conj = self._conjuncts(scan.pushed_filter)
        best = None
        for key_col, pname in projs.items():
            if key_col in t.dicts:
                continue  # dict codes are not value-ordered
            try:
                kt = t.schema[key_col]
            except Exception:
                continue
            if kt.kind not in self._RANGE_KINDS:
                continue  # decimal scales / floats: sides would mis-round
            qual = f"{scan.alias}.{key_col}"
            lows, highs = [], []
            for c in conj:
                for kind, lit in _range_bounds(c, qual):
                    if not (lit.value is not None
                            and lit.dtype.kind in self._RANGE_KINDS):
                        continue
                    if kind in ("ge", "gt"):
                        lows.append(
                            (lit, "left" if kind == "ge" else "right"))
                    elif kind in ("lt", "le"):
                        highs.append(
                            (lit, "left" if kind == "lt" else "right"))
                    else:  # eq
                        lows.append((lit, "left"))
                        highs.append((lit, "right"))
            if not lows and not highs:
                continue
            try:
                pt = self.catalog[pname]
            except KeyError:
                continue
            pcols = {f.name for f in pt.schema.fields}
            if not needed_cols <= pcols:
                continue
            arr = pt.data[key_col]
            n = len(arr)
            if n < 2:
                continue
            # representative bounds (parameterized literals keep their
            # planning-time value) -> exact count for the static capacity;
            # a different runtime value overflows and bumps the capacity
            lo_i = max(
                (int(np.searchsorted(arr, bind_value(l.value, l.dtype), s))
                 for l, s in lows), default=0,
            )
            hi_i = min(
                (int(np.searchsorted(arr, bind_value(h.value, h.dtype), s))
                 for h, s in highs), default=n,
            )
            cnt = max(hi_i - lo_i, 0)
            if cnt > 0.25 * n:
                continue  # not selective enough to beat the masked scan
            # tie-break equally selective candidates by covered width: a
            # narrower column-subset projection uploads fewer device
            # columns for the same slice
            width = len(pt.schema.fields)
            if best is None or (cnt, width) < (best[0], best[3]):
                best = (cnt, pname,
                        _SliceSpec(qual, tuple(lows), tuple(highs)), width)
        if best is None:
            return None
        cnt, pname, spec, _width = best
        new_scan = replace(scan, table=pname)
        cap = -(-int(cnt * 1.25 + 1024) // 1024) * 1024
        self._pending_slices[id(new_scan)] = (spec, cap)
        return new_scan

    # ---- ANN vector top-n ---------------------------------------------
    def _vector_topn_spec(self, op: TopN):
        """Match ORDER BY vec_l2(col, q) [ASC] LIMIT k over a Scan of a
        table with an IVF index on `col` — through an optional Project
        (hoisted $ordN) and any Filter chain / pushed scan filter — the
        ANN index route (the reference's vector-index DAS iterator,
        src/sql/das/iter). Index presence is the opt-in for approximate
        results, like obvec; whether the route actually wins is COSTED
        against the brute-force matmul (centroid pass + probed re-rank
        vs full-table distance), calibrated by measured TopN-route rates
        from the operator profile store when records exist. Filters ride
        into the fused kernel as selection masks; the filtered case
        seeds a recall-preserving over-probe from estimated selectivity
        and escalates at runtime via the overflow channel."""
        if op.offset != 0 or len(op.keys) != 1:
            return None
        e, desc = op.keys[0]
        if desc:
            return None
        node = op.child
        proj = None
        if isinstance(node, Project):
            # the planner hoists ORDER BY exprs into the projection as
            # $ordN; resolve the key ColRef back to its expression
            proj = node
            if isinstance(e, E.ColRef):
                e = dict(node.exprs).get(e.name, e)
            node = node.child
        if not isinstance(e, E.Func) or e.name != "vec_l2":
            return None
        filters = []
        filt_top = node
        while isinstance(node, Filter):
            filters.append(node.pred)
            node = node.child
        if not isinstance(node, Scan):
            return None
        colref = e.args[0]
        if not isinstance(colref, E.ColRef) or "." not in colref.name:
            return None
        alias, col = colref.name.split(".", 1)
        if alias != node.alias:
            return None
        try:
            t = self.catalog[node.table]
        except KeyError:
            return None
        spec = getattr(t, "vector_indexes", {}).get(col)
        if spec is None:
            return None
        idx = self.ivf_host(node.table, col)
        if idx is None or idx.max_list == 0:
            return None
        lists = len(idx.lengths)
        base_nprobe = max(1, min(spec.nprobe, lists))
        nprobe = base_nprobe
        filtered = bool(filters) or node.pushed_filter is not None
        est_sel = 1.0
        if filtered:
            # estimated survivor fraction under the predicate chain —
            # the over-probe seed: probing nprobe/est_sel lists keeps
            # the EXPECTED live candidate count at the unfiltered level
            # instead of post-filtering a decimated fixed-k result
            try:
                est_sel = float(self._est_rows(filt_top)) / max(
                    float(t.nrows), 1.0)
            except Exception:  # noqa: BLE001 - stats must not kill the route
                est_sel = 1.0
            est_sel = min(1.0, max(est_sel, 1e-6))
            boost = min(8, max(1, int(np.ceil(1.0 / max(est_sel, 0.125)))))
            nprobe = min(lists, nprobe * boost)
        # optimizer route: IVF work = centroid pass + probed-window
        # re-rank; brute work = full-table distance. Both are d-dim
        # matmul rows, so the un-calibrated comparison is row counts;
        # measured per-row rates from profiled TopN stages (PR 17
        # calibration records) replace the equal-rate assumption when
        # both routes have been observed
        d = int(np.asarray(idx.centroids).shape[1]) if lists else 1
        cand_rows = lists + nprobe * idx.max_list
        brute_rows = max(int(t.nrows), 1)
        ivf_cost = float(cand_rows * d)
        brute_cost = float(brute_rows * d)
        cost_basis = "flops"
        rates = None
        store = getattr(self, "profile_store", None)
        if store is not None:
            try:
                rates = store.ann_route_rates()
            except Exception:  # noqa: BLE001
                rates = None
        if rates is not None:
            ivf_cost = float(cand_rows) * rates[0]
            brute_cost = float(brute_rows) * rates[1]
            cost_basis = "measured"
        if ivf_cost >= brute_cost:
            # the index loses (tiny table, nprobe escalated to nearly
            # every list): brute-force exactly through the generic TopN
            return None
        return VectorTopNSpec(
            table=node.table,
            column=col,
            qual_col=colref.name,
            input_alias=f"#ivf:{node.table}.{col}",
            nprobe=nprobe,
            max_list=idx.max_list,
            nrows=t.nrows,
            k=op.n,
            key=e,
            scan=node,
            proj=proj,
            filters=tuple(filters),
            lists=lists,
            base_nprobe=base_nprobe,
            est_sel=est_sel,
            ivf_cost=ivf_cost,
            brute_cost=brute_cost,
            cost_basis=cost_basis,
        )

    def _emit_vector_topn(self, op: TopN, nid, spec: VectorTopNSpec,
                          inputs, emit, params):
        from ..expr.compile import evaluate_vector_literal

        # emit the SCAN, not the projection above it: the hoisted $ordN
        # distance column would otherwise evaluate over every row,
        # exactly the full matmul the index exists to avoid — the
        # projection re-applies over the k winners below
        child, ovf = emit(spec.scan, inputs)
        # fused filtered ANN: the Filter chain's predicates become
        # selection masks INSIDE this program (elementwise over the
        # batch — cheap next to the avoided full-table matmul); the
        # candidate re-rank below drops dead rows via child.sel
        for pred in spec.filters:
            child = child.with_sel(compile_predicate(pred, child))
        cent, perm, offs, lens = inputs[spec.input_alias]
        q = evaluate_vector_literal(spec.key.args[1])
        # round 1: nearest lists by centroid distance (rank-invariant
        # form drops ||q||^2 and ||x||^2-of-centroids keeps)
        cdist = jnp.sum(cent * cent, axis=1) - 2.0 * (cent @ q)
        _, probes = jax.lax.top_k(-cdist, spec.nprobe)
        starts = offs[probes]
        ll = lens[probes]
        win = starts[:, None] + jnp.arange(spec.max_list, dtype=jnp.int32)
        wvalid = (
            jnp.arange(spec.max_list, dtype=jnp.int32)[None, :] < ll[:, None]
        )
        n = spec.nrows
        rows = perm[jnp.clip(win, 0, max(n - 1, 0))].reshape(-1)
        wv = wvalid.reshape(-1)
        # round 2: exact re-rank of the candidate windows
        xv = child.cols[spec.qual_col][rows]          # (C, d) row gather
        dist = jnp.sum(xv * xv, axis=1) - 2.0 * (xv @ q)
        live = wv & child.sel[rows]
        dist = jnp.where(live, dist, jnp.inf)
        k = min(spec.k, rows.shape[0])
        if spec.nprobe < spec.lists:
            # over-probe escalation: when the fused filter decimates the
            # probed candidate windows below k live rows, report the
            # shortfall on the overflow channel; bump() widens nprobe and
            # the retry recompiles — recall-preserving, unlike
            # post-filtering a fixed-k result. Once nprobe == lists the
            # probe is exhaustive (exact), so no counter is emitted and
            # the retry ladder always terminates.
            ovf = dict(ovf)
            ovf[ANN_PROBE_BASE + nid] = jnp.maximum(
                jnp.int64(k) - jnp.sum(live, dtype=jnp.int64), jnp.int64(0))
        neg, top_i = jax.lax.top_k(-dist, k)
        win_rows = rows[top_i]
        cols, valid, _ = gather_payload(child.cols, child.valid, win_rows)
        sel = neg > -jnp.inf
        out = ColumnBatch(
            cols=cols,
            valid=valid,
            sel=sel,
            nrows=jnp.sum(sel, dtype=jnp.int64),
            schema=child.schema,
            dicts=child.dicts,
        )
        if spec.proj is not None:
            out = self._project_batch(spec.proj, out)
        return out, ovf

    # ---- clustered-FK segment aggregation -----------------------------
    def _clustered_agg_spec(self, op: Aggregate):
        """Match Aggregate directly over an inner PK-FK join whose probe
        (left) side is a Filter chain over a Scan stored CLUSTERED by the
        single join key (monotone nondecreasing storage). The join +
        group-by then collapse into segment reductions: per-aggregate
        cumsums over the probe side in storage order, differenced at the
        host-precomputed per-build-row ranges (fk_ranges) — no sort, no
        hash table, no per-probe-row gather. The TPU redesign of the
        reference's group-by pushdown + vectorized hash join pair
        (rewrite/ob_transform_groupby_pushdown.cpp,
        engine/join/hash_join/ob_hash_join_vec_op.h:402): on a TPU the
        winning join is the one the storage layout already did.

        Matched shape:
        - group keys: exprs over the join key and/or build-side columns
          (each group IS one build row — build-side keys are functionally
          dependent on it because the build side is unique per key)
        - aggregates: non-DISTINCT sum/count over probe-side exprs
        - join: merge-joinable (unique build, single integer key both
          sides with equal storage types), no residual
        """
        if not op.group_keys or op.grouping_sets is not None:
            return None
        ji = op.child
        if (
            not isinstance(ji, JoinOp)
            or ji.kind != "inner"
            or ji.residual is not None
            or len(ji.left_keys) != 1
            or not isinstance(ji.left_keys[0], E.ColRef)
            or not isinstance(ji.right_keys[0], E.ColRef)
        ):
            return None
        if not self._merge_joinable(ji):
            return None
        try:
            lt = infer_type(ji.left_keys[0], output_schema(ji.left))
            rt = infer_type(ji.right_keys[0], output_schema(ji.right))
        except Exception:
            return None
        if lt.storage_np != rt.storage_np:
            # the group-key output substitutes the build pk for the probe
            # fk; a dtype mismatch would change the output column type
            return None
        node = ji.left
        while isinstance(node, Filter):
            node = node.child
        if not isinstance(node, Scan):
            return None
        base = node
        if "#sp:" in base.table:
            # routed sorted-projection scans may be DYNAMICALLY SLICED
            # (params.scan_slice): fk_ranges index full-table rows and
            # would misindex the sliced batch — never combine the two
            return None
        fk_name = ji.left_keys[0].name
        if "." not in fk_name:
            return None
        alias, fk_col = fk_name.split(".", 1)
        if alias != base.alias or not self._monotone_col(base.table, fk_col):
            return None
        hit = self._resolve_layout_col(ji.right, ji.right_keys[0].name)
        if hit is None:
            return None
        build_table, pk_col = hit
        if "#sp:" in build_table:
            return None  # same slicing hazard on the build side
        build_names = set(output_schema(ji.right).names())
        # groups must be 1:1 with build rows: some group key must BE the
        # join key itself (injective by identity). Keys that are merely
        # functions of the build side (group by customer attrs over an
        # orders build, TPC-H Q10) make groups COARSER than build rows
        # and need a second aggregation — generic path handles those.
        if not any(
            e == ji.left_keys[0] or e == ji.right_keys[0]
            for _n, e in op.group_keys
        ):
            return None
        for _name, e in op.group_keys:
            if not set(E.referenced_columns(e)) <= (build_names | {fk_name}):
                return None
        probe_names = set(output_schema(ji.left).names())
        for _name, fn, arg, distinct in op.aggs:
            if distinct or fn not in ("sum", "count"):
                return None
            if arg is not None and not (
                set(E.referenced_columns(arg)) <= probe_names
            ):
                return None
        input_alias = (
            f"#fkr:{base.table}.{fk_col}->{build_table}.{pk_col}"
        )
        _ranges, tiled = self._fk_ranges(
            base.table, fk_col, build_table, pk_col)
        return ClusteredAggSpec(
            ji, base.table, fk_col, fk_name, build_table, pk_col,
            input_alias, tiled,
        )

    def _emit_grouping_sets(self, op: Aggregate, nid, inputs, emit, params):
        """ROLLUP/CUBE/GROUPING SETS: aggregate once per set and stack
        the results, NULL-filling keys absent from a set — the engine's
        EXPAND (reference: the EXPAND phy operator replicates each input
        row per grouping set and NULL-masks; here the replication
        happens at the AGGREGATE level instead, which aggregates G
        smaller problems rather than one G-times-larger sort and lets
        each set reuse the engine's direct/packed/sort group-by routes).
        XLA CSE collapses the G re-traced child subtrees."""
        out_schema = _agg_schema(op, output_schema(op.child))
        parts = []
        ovf_all: dict = {}
        for si, idxs in enumerate(op.grouping_sets):
            sub = Aggregate(
                op.child,
                tuple(op.group_keys[i] for i in idxs),
                op.aggs,
            )
            # pseudo node id: nothing seeded, so sub-aggregates take the
            # parameter-free group-by routes (direct or unpacked sort)
            pseudo = -(1_000_000 + nid * 64 + si)
            b, ovf = self._emit_aggregate(sub, pseudo, inputs, emit, params)
            ovf_all.update(ovf)
            parts.append((idxs, b))
        cols: dict[str, list] = {n: [] for n in out_schema.names()}
        valid: dict[str, list] = {}
        sels = []
        key_names = [n for n, _e in op.group_keys]
        for idxs, b in parts:
            cap = b.capacity
            present = {key_names[i] for i in idxs}
            for f in out_schema.fields:
                n = f.name
                if n in present or n not in key_names:
                    cols[n].append(
                        b.cols[n].astype(f.dtype.storage_np))
                    v = b.valid.get(n)
                    if f.dtype.nullable:
                        valid.setdefault(n, []).append(
                            v if v is not None
                            else jnp.ones(cap, jnp.bool_)
                        )
                else:  # key absent from this set: NULL
                    cols[n].append(
                        jnp.zeros(cap, dtype=f.dtype.storage_np))
                    valid.setdefault(n, []).append(
                        jnp.zeros(cap, jnp.bool_))
            sels.append(b.sel)
        out = ColumnBatch(
            cols={n: jnp.concatenate(v) for n, v in cols.items()},
            valid={n: jnp.concatenate(v) for n, v in valid.items()},
            sel=jnp.concatenate(sels),
            nrows=sum(
                (jnp.sum(s, dtype=jnp.int64) for s in sels),
                jnp.zeros((), jnp.int64),
            ),
            schema=out_schema,
            dicts={
                n: d
                for _idxs, b in parts
                for n, d in b.dicts.items()
            },
        )
        return out, ovf_all

    def _emit_clustered_agg(self, op: Aggregate, nid, spec: ClusteredAggSpec,
                            inputs, emit, params):
        """Emit the matched Aggregate-over-join as segment reductions.

        Probe side (storage order, filters as sel): one cumsum per
        aggregate plus a live-row cumsum; build side: the group table —
        each live build row with >= 1 joined live probe row becomes a
        group, its aggregates the cumsum differences at [start, end).
        Exact (no hashing, no capacities, no overflow): the ranges are
        host-precomputed from the clustered key, and the count/sum
        semantics match the generic paths (NULL args skipped via
        validity; sum over an empty/all-NULL group yields 0 like
        sort_groupby's masked segmented cumsum)."""
        from ..sql.planner import _substitute

        ji = spec.ji
        L, lovf = emit(ji.left, inputs)
        R, rovf = emit(ji.right, inputs)
        ovf = {**lovf, **rovf}
        starts, ends = inputs[spec.input_alias]
        base_mask = L.sel
        running: dict = {"#cnt": jnp.cumsum(base_mask.astype(jnp.int64))}
        for i, (_name, fn, arg, _d) in enumerate(op.aggs):
            if arg is None:
                continue  # count(*) counts joined live rows == "#cnt"
            v, vv = evaluate(arg, L)
            am = base_mask if vv is None else base_mask & vv
            if fn == "count":
                running[i] = jnp.cumsum(am.astype(jnp.int64))
            else:
                acc = (
                    jnp.int64
                    if jnp.issubdtype(v.dtype, jnp.integer)
                    else v.dtype
                )
                running[i] = jnp.cumsum(jnp.where(am, v, 0).astype(acc))
        at_hi, at_lo = segment_bounds(running, starts, ends, spec.tiled)

        def seg(k):
            h = jnp.where(ends > 0, at_hi[k], 0)
            lo = jnp.where(starts > 0, at_lo[k], 0)
            return h - lo

        cnt = seg("#cnt")
        sel = R.sel & (cnt > 0)
        # group keys evaluate on the build side; the probe fk substitutes
        # to the build pk (equal on every surviving group by definition)
        sub = {ji.left_keys[0]: ji.right_keys[0]}
        cols, valid, dicts = {}, {}, {}
        for name, e in op.group_keys:
            e2 = _substitute(e, sub)
            v, vv = evaluate(e2, R)
            cols[name] = v
            if vv is not None:
                valid[name] = vv
            if isinstance(e2, E.ColRef) and e2.name in R.dicts:
                dicts[name] = R.dicts[e2.name]
        for i, (name, fn, arg, _d) in enumerate(op.aggs):
            cols[name] = cnt if arg is None else seg(i)
        out_schema = _agg_schema(op, output_schema(op.child))
        out = ColumnBatch(
            cols=cols,
            valid=valid,
            sel=sel,
            nrows=jnp.sum(sel, dtype=jnp.int64),
            schema=out_schema,
            dicts=dicts,
        )
        # NOTE: compacting this output before the downstream TopN was
        # tried and measured SLOWER on chip (the sort-based compaction
        # costs a full extra build-capacity pass, more than the TopN
        # saves) — keep the full-capacity batch
        return out, ovf

    # ---- tracing ------------------------------------------------------
    def compile(self, plan: LogicalOp, params: PhysicalParams):
        self.compiles += 1
        nodes = _number_nodes(plan)
        id_of = {id(op): nid for nid, op in nodes.items()}
        needed = self._needed_columns(plan)
        # make sure every scan uploads at least one column (for row count)
        scans = self._collect_scans(plan)
        input_spec = []
        for s in scans:
            cols = needed.get(s.alias, set())
            if not cols:
                cols = (
                    {"$one"} if s.table == "$dual"
                    else {self.catalog[s.table].schema.fields[0].name}
                )
            input_spec.append((s.alias, s.table, tuple(sorted(cols))))

        # clustered-FK aggregates + ANN top-n: re-detect every compile
        # (deterministic from plan + catalog) and feed the precomputed
        # derived structures as inputs
        params.clustered_aggs.clear()
        params.vector_topns.clear()
        if self.clustered_agg_enabled:
            for nid2, op2 in nodes.items():
                if isinstance(op2, TopN):
                    vspec = self._vector_topn_spec(op2)
                    if vspec is not None:
                        # over-probe escalations survive re-detection: a
                        # prior bump() widened this node's nprobe and the
                        # recompile must honour it or the retry loops
                        esc = params.ann_nprobe.get(nid2)
                        if esc is not None and esc > vspec.nprobe:
                            vspec = replace(
                                vspec, nprobe=min(esc, vspec.lists))
                        params.ann_nprobe[nid2] = vspec.nprobe
                        params.ann_lists[nid2] = vspec.lists
                        params.vector_topns[nid2] = vspec
                        if all(a != vspec.input_alias
                               for a, _t, _c in input_spec):
                            input_spec.append((
                                vspec.input_alias,
                                vspec.table,
                                (vspec.table, vspec.column, vspec.max_list),
                            ))
                if not isinstance(op2, Aggregate):
                    continue
                spec = self._clustered_agg_spec(op2)
                if spec is not None:
                    params.clustered_aggs[nid2] = spec
                    if all(a != spec.input_alias for a, _t, _c in input_spec):
                        input_spec.append((
                            spec.input_alias,
                            spec.probe_table,
                            (spec.probe_table, spec.fk_col,
                             spec.build_table, spec.pk_col, spec.tiled),
                        ))

        overflow_nodes: list[int] = sorted(
            set(params.groupby_size) | set(params.join_cap)
            | set(params.scan_cap) | set(params.topn_cand)
            | {
                PACK_GUARD_BASE + nid
                for nid in params.pack_guard
                if nid not in params.groupby_nopack
            }
            | {
                ANN_PROBE_BASE + nid
                for nid, vs in params.vector_topns.items()
                if vs.nprobe < vs.lists
            }
        )

        def emit(op, inputs) -> tuple[ColumnBatch, dict[int, jnp.ndarray]]:
            return self._emit_scoped(op, inputs, emit, params, id_of)

        qparam_spec = _collect_qparam_spec(plan)

        def run(inputs: dict[str, ColumnBatch], qparams: tuple = ()):
            from ..expr import compile as expr_compile

            qparams = _unpack_qparams(qparams, qparam_spec)
            prev = expr_compile.set_params(qparams if qparams else None)
            try:
                out, ovf = emit(plan, inputs)
            finally:
                expr_compile.set_params(prev)
            out, oc = compact_batch(out, params.join_cap[ROOT_COMPACT])
            ovf = dict(ovf)
            ovf[ROOT_COMPACT] = oc
            # ONE stacked vector: the host reads every counter in a single
            # fetch (per-scalar int() costs one device sync EACH)
            ovf_vec = jnp.stack([
                ovf.get(nid, jnp.zeros((), jnp.int64)) for nid in overflow_nodes
            ]) if overflow_nodes else jnp.zeros((0,), jnp.int64)
            return out, ovf_vec

        run.__name__ = program_name(plan)
        return jax.jit(run), input_spec, overflow_nodes

    def _emit_scoped(self, op, inputs, emit, params, id_of):
        """`_emit_node` under the plan node's named scope: every HLO op
        the node emits carries `<kind>#<nid>` in its `op_name`, children
        nested inside parents, so a device trace reads in plan nodes (the
        innermost scope is the node's self time). Every `emit` closure
        (single-chip, PX, profiled stages) recurses through here."""
        with jax.named_scope(f"{op_kind(op)}#{id_of[id(op)]}"):
            return self._emit_node(op, inputs, emit, params, id_of)

    def _emit_node(self, op, inputs, emit, params, id_of):
        """Emit one plan node into the traced program (dispatch shared by
        the single-chip and PX executors)."""
        nid = id_of[id(op)]
        if isinstance(op, Scan):
            b = inputs[op.alias]
            # qualify names
            qschema = Schema(
                tuple(
                    Field(f"{op.alias}.{f.name}", f.dtype)
                    for f in b.schema.fields
                )
            )
            qb = ColumnBatch(
                cols={f"{op.alias}.{n}": c for n, c in b.cols.items()},
                valid={f"{op.alias}.{n}": v for n, v in b.valid.items()},
                sel=b.sel,
                nrows=b.nrows,
                schema=qschema,
                dicts={f"{op.alias}.{n}": d for n, d in b.dicts.items()},
            )
            ovf = {}
            sl = params.scan_slice.get(nid)
            if sl is not None and sl.key in qb.cols:
                cap = params.scan_cap[nid]
                n = self.catalog[op.table].nrows
                if cap < n:
                    qb, over = _slice_sorted_scan(qb, sl, cap, n)
                    ovf[nid] = over
            if op.pushed_filter is not None:
                qb = qb.with_sel(compile_predicate(op.pushed_filter, qb))
            return qb, ovf

        if isinstance(op, Filter):
            child, ovf = emit(op.child, inputs)
            return child.with_sel(compile_predicate(op.pred, child)), ovf

        if isinstance(op, Project):
            child, ovf = emit(op.child, inputs)
            return self._project_batch(op, child), ovf

        if isinstance(op, JoinOp):
            return self._emit_join(op, nid, inputs, emit, params)

        if isinstance(op, Aggregate):
            return self._emit_aggregate(op, nid, inputs, emit, params)

        if isinstance(op, Distinct):
            child, ovf = emit(op.child, inputs)
            return self._dedup_batch(child, ovf)

        if isinstance(op, Sort):
            child, ovf = emit(op.child, inputs)
            keys, desc = [], []
            for e, d in op.keys:
                v, _ = evaluate(e, child)
                keys.append(v)
                desc.append(d)
            order = sort_indices(keys, desc, child.sel)
            cols, valid, ssel = gather_payload(
                child.cols, child.valid, order, child.sel
            )
            return (
                replace(child, cols=cols, valid=valid, sel=ssel),
                ovf,
            )

        if isinstance(op, Limit):
            child, ovf = emit(op.child, inputs)
            pos = jnp.cumsum(child.sel.astype(jnp.int64)) - 1
            keep = (
                child.sel
                & (pos >= op.offset)
                & (pos < op.offset + op.n)
            )
            return child.with_sel(keep), ovf

        if isinstance(op, TopN):
            vspec = params.vector_topns.get(nid)
            if vspec is not None and vspec.input_alias in inputs:
                return self._emit_vector_topn(
                    op, nid, vspec, inputs, emit, params
                )
            child, ovf = emit(op.child, inputs)
            cand = params.topn_cand.get(nid)
            if cand is not None and cand < child.capacity:
                got = self._topn_candidates(child, op.keys, cand)
                if got is not None:
                    mini, over = got
                    ovf = dict(ovf)
                    ovf[nid] = over
                    return (
                        self._topn_batch(mini, op.keys, op.n, op.offset),
                        ovf,
                    )
            return (
                self._topn_batch(child, op.keys, op.n, op.offset),
                ovf,
            )

        if isinstance(op, SetOp):
            return self._emit_setop(op, nid, inputs, emit, params)

        if isinstance(op, Window):
            return self._emit_window(op, nid, inputs, emit, params)

        raise NotImplementedError(type(op))

    def _project_batch(self, op: Project, child: ColumnBatch) -> ColumnBatch:
        cols, valid, dicts, fields = {}, {}, {}, []
        for name, e in op.exprs:
            derived = derive_dict_column(e, child)
            if derived is not None:
                # string transform (substr): new dict column
                v, vv, d2 = derived
                dicts[name] = d2
            else:
                v, vv = evaluate(e, child)
            if getattr(v, "ndim", 1) == 0:
                # all-literal expression: broadcast the scalar to the
                # batch (FROM-less SELECT constants)
                v = jnp.broadcast_to(v, (child.capacity,))
            cols[name] = v
            if vv is not None:
                valid[name] = vv
            t = infer_type(e, child.schema)
            fields.append(Field(name, t))
            if isinstance(e, E.ColRef) and e.name in child.dicts:
                dicts[name] = child.dicts[e.name]
        return ColumnBatch(
            cols=cols,
            valid=valid,
            sel=child.sel,
            nrows=child.nrows,
            schema=Schema(tuple(fields)),
            dicts=dicts,
        )

    def _topn_candidates(self, child: ColumnBatch, keys, C: int):
        """EXACT top-k candidate prefilter: lax.top_k on the FIRST sort
        key picks C candidates; any true top-(n+offset) row under the
        full lexicographic order has a first-key value >= the worst
        candidate's, so when at most C live rows tie-or-beat that value
        the candidate set is a superset — otherwise the tie count rides
        the overflow channel and the plan retries with 4x candidates.
        Replaces a full-capacity multi-operand sort (Q3: 15M rows) with
        one top_k + a C-row sort. None = ineligible (nullable,
        non-integer, or no key) and the generic sort path runs."""
        if not keys:
            return None
        e0, desc0 = keys[0]
        v, vv = evaluate(e0, child)
        if vv is not None or getattr(v, "ndim", 1) != 1:
            return None
        if not jnp.issubdtype(v.dtype, jnp.integer):
            return None  # float NaNs would outrank everything in top_k
        flip = v.astype(jnp.int64)
        if not desc0:
            flip = ~flip  # exact order reversal, no int64-min overflow
        dead = jnp.iinfo(jnp.int64).min
        masked = jnp.where(child.sel, flip, dead)
        cand_v, cand_i = jax.lax.top_k(masked, C)
        kth = cand_v[C - 1]
        cnt = jnp.sum((masked >= kth) & child.sel, dtype=jnp.int64)
        cols, valid, csel = gather_payload(
            child.cols, child.valid, cand_i, child.sel
        )
        # guard BOTH clip hazards: boundary ties beyond C, and a LIVE
        # row whose flipped key equals the dead sentinel being displaced
        # by dead rows inside top_k's index tie-break (it would vanish
        # with cnt <= C) — fewer live candidates than min(C, nlive)
        # means something real was dropped
        nlive = jnp.sum(child.sel, dtype=jnp.int64)
        live_cand = jnp.sum(csel, dtype=jnp.int64)
        short = jnp.maximum(
            jnp.minimum(jnp.int64(C), nlive) - live_cand, 0
        )
        over = jnp.maximum(cnt - C, 0) + short
        mini = ColumnBatch(
            cols=cols,
            valid=valid,
            sel=csel,
            nrows=jnp.sum(csel, dtype=jnp.int64),
            schema=child.schema,
            dicts=child.dicts,
        )
        return mini, over

    def _topn_batch(self, child: ColumnBatch, keys, n: int, offset: int,
                    apply_offset: bool = True) -> ColumnBatch:
        """Fused ORDER BY + LIMIT: sort for the order, materialize only the
        top n+offset rows (tiny gathers instead of a full-capacity payload
        permutation). The output keeps global order in its row order."""
        key_vals, desc = [], []
        for e, d in keys:
            v, _ = evaluate(e, child)
            key_vals.append(v)
            desc.append(d)
        order = sort_indices(key_vals, desc, child.sel)
        k = n + offset
        cap2 = min(child.capacity, max(8, -(-k // 8) * 8))
        take = order[:cap2]
        pos = jnp.arange(cap2, dtype=jnp.int64)
        nlive = jnp.sum(child.sel, dtype=jnp.int64)
        lo = offset if apply_offset else 0
        sel = (pos >= lo) & (pos < jnp.minimum(k, nlive))
        cols = {nm: c[take] for nm, c in child.cols.items()}
        valid = {nm: v[take] for nm, v in child.valid.items()}
        return ColumnBatch(
            cols=cols, valid=valid, sel=sel,
            nrows=jnp.sum(sel, dtype=jnp.int64),
            schema=child.schema, dicts=child.dicts,
        )

    # ---- join emission -------------------------------------------------
    def _emit_join(self, op: JoinOp, nid, inputs, emit, params):
        if op.kind in ("semi", "anti"):
            return self._emit_semi_anti(op, nid, inputs, emit, params)
        if op.kind == "left":
            return self._emit_left(op, nid, inputs, emit, params)
        if op.kind == "full":
            return self._emit_full(op, nid, inputs, emit, params)
        left, lovf = emit(op.left, inputs)
        right, rovf = emit(op.right, inputs)
        ovf = {**lovf, **rovf}
        lkeys = [evaluate(e, left)[0] for e in op.left_keys]
        rkeys = [evaluate(e, right)[0] for e in op.right_keys]
        if not lkeys:
            # cross join: constant key makes every probe row match every
            # build row; a 1-row build (scalar subquery) rides the unique
            # hash path as a broadcast, general cross uses expand
            lkeys = [jnp.zeros(left.capacity, dtype=jnp.int32)]
            rkeys = [jnp.zeros(right.capacity, dtype=jnp.int32)]
        merged_dicts = {**left.dicts, **right.dicts}

        if self._merge_joinable(op):
            aff = self._affine_build_info(op) if op.left_keys else None
            cols = dict(left.cols)
            valid = dict(left.valid)
            if aff is not None:
                # direct address + ONE packed gather carrying the verify
                # key, build liveness, and every payload column together
                candc, in_range = _affine_candidates(
                    lkeys[0], aff, right.capacity)
                rcols, rvalid, rsel = gather_payload(
                    {**right.cols, "#bk": rkeys[0]},
                    right.valid, candc, right.sel,
                )
                bk_at = rcols.pop("#bk")
                sel = (
                    left.sel & in_range & (bk_at == lkeys[0]) & rsel
                )
            else:
                # which programs hold a sort-merge join at all (one count
                # a join, at trace time): its run heads ride a scan
                count_lowering("merge join scan-carried")
                match = merge_join_unique(
                    rkeys[0], right.sel, lkeys[0], left.sel
                )
                sel = left.sel & (match >= 0)
                idx = jnp.clip(match, 0, None)
                rcols, rvalid, _ = gather_payload(
                    right.cols, right.valid, idx)
            cols.update(rcols)
            valid.update(rvalid)
            out_schema = _join_schema(left.schema, right.schema)
            out = ColumnBatch(
                cols=cols,
                valid=valid,
                sel=sel,
                nrows=jnp.sum(sel, dtype=jnp.int64),
                schema=out_schema,
                dicts=merged_dicts,
            )
        else:
            cap = params.join_cap[nid]
            skeys, order = sort_build_side(rkeys, right.sel)
            pr, br, valid_rows, total, _st, _of = expand_join(
                skeys, order, right.nrows, lkeys, left.sel, cap
            )
            cols, valid, _ = gather_payload(left.cols, left.valid, pr)
            rcols, rvalid, _ = gather_payload(right.cols, right.valid, br)
            cols.update(rcols)
            valid.update(rvalid)
            sel = valid_rows
            # multi-column keys ride a hash: exact-verify the expansion
            if len(op.left_keys) > 1:
                for le, re_ in zip(op.left_keys, op.right_keys):
                    lv, _ = evaluate(le, left)
                    rv, _ = evaluate(re_, right)
                    sel = sel & (lv[pr] == rv[br])
            out_schema = _join_schema(left.schema, right.schema)
            out = ColumnBatch(
                cols=cols,
                valid=valid,
                sel=sel,
                nrows=jnp.sum(sel, dtype=jnp.int64),
                schema=out_schema,
                dicts=merged_dicts,
            )
            ovf = dict(ovf)
            ovf[nid] = jnp.maximum(total - cap, 0)
        if op.residual is not None:
            out = out.with_sel(compile_predicate(op.residual, out))
        return out, ovf

    def _emit_semi_anti(self, op: JoinOp, nid, inputs, emit, params):
        """Semi/anti join: output = left rows with (without) a matching right
        row. No residual, single integer key: sorted-build + searchsorted
        range counts (exact — true keys, no hashing, no table). No residual,
        multi-column keys: the open-addressing existence probe (cold path).
        With residual: expand candidate pairs, evaluate the residual per
        pair, and reduce a has-match bit per left row scatter-free via the
        pair-run cumsum (probe_run_any)."""
        left, lovf = emit(op.left, inputs)
        right, rovf = emit(op.right, inputs)
        ovf = {**lovf, **rovf}
        lkeys = [evaluate(e, left)[0] for e in op.left_keys]
        rkeys = [evaluate(e, right)[0] for e in op.right_keys]
        if op.residual is None:
            if len(lkeys) == 1 and jnp.issubdtype(lkeys[0].dtype, jnp.integer) \
                    and jnp.issubdtype(rkeys[0].dtype, jnp.integer):
                aff = self._affine_build_info(op)
                if aff is not None:
                    has = _affine_probe(
                        rkeys[0], right.sel, lkeys[0], left.sel, aff
                    ) >= 0
                    sel = left.sel & (has if op.kind == "semi" else ~has)
                    return left.with_sel(sel), ovf
                skeys, _order = sort_build_side(rkeys, right.sel)
                pk = lkeys[0].astype(jnp.int64)
                lo = jnp.searchsorted(skeys, pk, side="left", method="sort")
                hi = jnp.searchsorted(skeys, pk, side="right", method="sort")
                # dead build rows sit at sorted positions >= right.nrows
                # with int64-max placeholders; clamp so a live probe key
                # of int64 max can't match them (dead probe rows are
                # masked by left.sel below)
                n_live = right.nrows.astype(lo.dtype)
                has = left.sel & (
                    jnp.minimum(hi, n_live) > jnp.minimum(lo, n_live)
                )
            else:
                nb = rkeys[0].shape[0]
                ts = next_pow2(max(2 * nb, 16))
                slot_key, slot_row = build_hash_table(rkeys, right.sel, ts)
                match = hash_join_probe(
                    slot_key, slot_row, rkeys, lkeys, left.sel
                )
                has = match >= 0
        else:
            cap = params.join_cap[nid]
            skeys, order = sort_build_side(rkeys, right.sel)
            pr, br, valid_rows, total, starts, offs = expand_join(
                skeys, order, right.nrows, lkeys, left.sel, cap
            )
            pair_sel = valid_rows
            if len(op.left_keys) > 1:
                for le, re_ in zip(op.left_keys, op.right_keys):
                    lv, _ = evaluate(le, left)
                    rv, _ = evaluate(re_, right)
                    pair_sel = pair_sel & (lv[pr] == rv[br])
            # pair batch: left cols gathered by pr, right cols by br
            pair_cols, pair_valid, _ = gather_payload(
                left.cols, left.valid, pr)
            _rc, _rv, _ = gather_payload(right.cols, right.valid, br)
            pair_cols.update(_rc)
            pair_valid.update(_rv)
            pair_batch = ColumnBatch(
                cols=pair_cols,
                valid=pair_valid,
                sel=pair_sel,
                nrows=jnp.sum(pair_sel, dtype=jnp.int64),
                schema=_join_schema(left.schema, right.schema),
                dicts={**left.dicts, **right.dicts},
            )
            pair_ok = compile_predicate(op.residual, pair_batch)
            has = probe_run_any(pair_ok, starts, offs)
            ovf = dict(ovf)
            ovf[nid] = jnp.maximum(total - cap, 0)
        sel = left.sel & (has if op.kind == "semi" else ~has)
        return left.with_sel(sel), ovf

    def _emit_left(self, op: JoinOp, nid, inputs, emit, params):
        """Left outer join via expansion: matched pairs plus, appended at a
        left-capacity tail, one all-NULL-right row for every unmatched left
        row. Right columns gain validity masks (they are nullable now)."""
        left, lovf = emit(op.left, inputs)
        right, rovf = emit(op.right, inputs)
        ovf = {**lovf, **rovf}
        lkeys = [evaluate(e, left)[0] for e in op.left_keys]
        rkeys = [evaluate(e, right)[0] for e in op.right_keys]
        cap = params.join_cap[nid]
        skeys, order = sort_build_side(rkeys, right.sel)
        pr, br, valid_rows, total, starts, offs = expand_join(
            skeys, order, right.nrows, lkeys, left.sel, cap
        )
        pair_sel = valid_rows
        if len(op.left_keys) > 1:
            for le, re_ in zip(op.left_keys, op.right_keys):
                lv, _ = evaluate(le, left)
                rv, _ = evaluate(re_, right)
                pair_sel = pair_sel & (lv[pr] == rv[br])
        merged_dicts = {**left.dicts, **right.dicts}
        if op.residual is not None:
            pair_cols, pair_valid, _ = gather_payload(
                left.cols, left.valid, pr)
            _rc, _rv, _ = gather_payload(right.cols, right.valid, br)
            pair_cols.update(_rc)
            pair_valid.update(_rv)
            pair_batch = ColumnBatch(
                cols=pair_cols,
                valid=pair_valid,
                sel=pair_sel,
                nrows=jnp.sum(pair_sel, dtype=jnp.int64),
                schema=_join_schema(left.schema, right.schema),
                dicts=merged_dicts,
            )
            pair_sel = compile_predicate(op.residual, pair_batch)
        nl = left.capacity
        has = probe_run_any(pair_sel, starts, offs)
        # output = [cap matched-pair slots] ++ [nl unmatched-left slots]
        lc_pr, lv_pr, _ = gather_payload(left.cols, left.valid, pr)
        rc_br, rv_br, _ = gather_payload(right.cols, right.valid, br)
        cols, valid = {}, {}
        for n, c in left.cols.items():
            cols[n] = jnp.concatenate([lc_pr[n], c])
        for n, v in left.valid.items():
            valid[n] = jnp.concatenate([lv_pr[n], v])
        for n, c in right.cols.items():
            cols[n] = jnp.concatenate(
                [rc_br[n], jnp.zeros_like(c, shape=(nl,))])
            matched_valid = (
                rv_br[n] if n in rv_br else jnp.ones(cap, jnp.bool_))
            valid[n] = jnp.concatenate([matched_valid, jnp.zeros(nl, jnp.bool_)])
        sel = jnp.concatenate([pair_sel, left.sel & ~has])
        rs_nullable = Schema(
            tuple(
                Field(f.name, f.dtype.with_nullable(True))
                for f in right.schema.fields
            )
        )
        out = ColumnBatch(
            cols=cols,
            valid=valid,
            sel=sel,
            nrows=jnp.sum(sel, dtype=jnp.int64),
            schema=_join_schema(left.schema, rs_nullable),
            dicts=merged_dicts,
        )
        ovf = dict(ovf)
        ovf[nid] = jnp.maximum(total - cap, 0)
        return out, ovf

    # ---- set-operation emission ----------------------------------------
    @staticmethod
    def _cast_col(c, from_t: DataType, to_t: DataType):
        """Physically convert one column to the promoted set-op type."""
        if from_t.kind == to_t.kind and not to_t.is_decimal:
            return c.astype(to_t.storage_np) if c.dtype != to_t.storage_np else c
        if from_t.is_decimal and to_t.is_decimal:
            shift = 10 ** (to_t.scale - from_t.scale)
            return (c.astype(to_t.storage_np) * shift) if shift != 1 else c.astype(to_t.storage_np)
        if to_t.kind is TypeKind.FLOAT64:
            if from_t.is_decimal:
                return c.astype(jnp.float64) / from_t.decimal_factor
            return c.astype(jnp.float64)
        if to_t.is_integer:
            return c.astype(to_t.storage_np)
        raise NotImplementedError(f"set-op cast {from_t} -> {to_t}")

    @staticmethod
    def _setop_key_cols(cols, valids, schema: Schema):
        """Dedup/compare key columns with SQL set-op NULL semantics (NULLs
        compare equal): NULL payloads normalize to 0 and the validity bit
        joins the key."""
        keys = []
        for f in schema.fields:
            c = cols[f.name]
            v = valids.get(f.name)
            if v is not None:
                keys.append(jnp.where(v, c, jnp.zeros((), c.dtype)))
                keys.append(v)
            else:
                keys.append(c)
        return keys

    def _setop_promote(self, op: SetOp, left: ColumnBatch, right: ColumnBatch):
        """Positionally align both sides onto the common promoted schema:
        merged dictionaries, numeric casts, materialized validity. Returns
        (lb, rb, out_schema, dicts) — promoted same-schema batches. Split
        from the combine step so the PX layer can hash-exchange promoted
        rows (raw dict codes from different dictionaries would NOT
        co-partition equal strings)."""
        from ..core.dictionary import Dictionary

        out_schema = setop_schema(left.schema, right.schema)
        lcols, rcols, lvalid, rvalid, dicts = {}, {}, {}, {}, {}
        for i, f in enumerate(out_schema.fields):
            ln = left.schema.fields[i].name
            rn = right.schema.fields[i].name
            lt = left.schema.fields[i].dtype
            rt = right.schema.fields[i].dtype
            lc, rc = left.cols[ln], right.cols[rn]
            if f.dtype.kind is TypeKind.VARCHAR:
                md, lmap, rmap = Dictionary.merge(
                    left.dicts.get(ln), right.dicts.get(rn)
                )
                if md is not None:
                    dicts[f.name] = md
                if lmap is not None:
                    lc = jnp.asarray(lmap)[jnp.clip(lc, 0, len(lmap) - 1)]
                if rmap is not None:
                    rc = jnp.asarray(rmap)[jnp.clip(rc, 0, len(rmap) - 1)]
            else:
                lc = self._cast_col(lc, lt, f.dtype)
                rc = self._cast_col(rc, rt, f.dtype)
            lcols[f.name], rcols[f.name] = lc, rc
            if f.dtype.nullable:
                lv = left.valid.get(ln)
                rv = right.valid.get(rn)
                lvalid[f.name] = (
                    lv if lv is not None else jnp.ones(left.capacity, jnp.bool_)
                )
                rvalid[f.name] = (
                    rv if rv is not None else jnp.ones(right.capacity, jnp.bool_)
                )
        lb = ColumnBatch(
            cols=lcols, valid=lvalid, sel=left.sel, nrows=left.nrows,
            schema=out_schema, dicts=dicts,
        )
        rb = ColumnBatch(
            cols=rcols, valid=rvalid, sel=right.sel, nrows=right.nrows,
            schema=out_schema, dicts=dicts,
        )
        return lb, rb, out_schema, dicts

    def _emit_setop(self, op: SetOp, nid, inputs, emit, params):
        left, lovf = emit(op.left, inputs)
        right, rovf = emit(op.right, inputs)
        ovf = {**lovf, **rovf}
        lb, rb, out_schema, dicts = self._setop_promote(op, left, right)
        return self._setop_combine(op, lb, rb, out_schema, dicts, ovf)

    def _setop_combine(self, op: SetOp, left: ColumnBatch, right: ColumnBatch,
                       out_schema, dicts, ovf):
        """Combine two PROMOTED same-schema sides per the set-op kind."""
        lcols, rcols = left.cols, right.cols
        lvalid, rvalid = left.valid, right.valid

        if op.kind == "union":
            cols = {n: jnp.concatenate([lcols[n], rcols[n]]) for n in lcols}
            valid = {n: jnp.concatenate([lvalid[n], rvalid[n]]) for n in lvalid}
            sel = jnp.concatenate([left.sel, right.sel])
            out = ColumnBatch(
                cols=cols, valid=valid, sel=sel,
                nrows=jnp.sum(sel, dtype=jnp.int64),
                schema=out_schema, dicts=dicts,
            )
            if op.all:
                return out, ovf
            return self._dedup_batch(out, ovf)

        if op.all:
            # INTERSECT ALL / EXCEPT ALL (bag semantics): one combined
            # lexicographic sort of both sides with the side flag as the
            # LAST key, so within each equal-value run all left copies
            # precede the right copies. Per run with l left and r right
            # copies, the k-th left copy (k = 0..l-1) survives iff
            # k < r (INTERSECT ALL → min(l, r) copies) or k >= r
            # (EXCEPT ALL → max(l - r, 0) copies) — the run-length
            # counting form of ObHashSetVecOp's bag semantics
            # (sql/engine/set), recast as sort + prefix sums for the TPU.
            return self._emit_setop_all(
                op.kind, lcols, rcols, lvalid, rvalid,
                left, right, out_schema, dicts, ovf,
            )

        # INTERSECT / EXCEPT (distinct semantics): sort-dedup the left
        # side, then an existence probe against the right side decides each
        # surviving row
        lb = ColumnBatch(
            cols=lcols, valid=lvalid, sel=left.sel,
            nrows=left.nrows, schema=out_schema, dicts=dicts,
        )
        db, ovf = self._dedup_batch(lb, ovf)
        lkeys = self._setop_key_cols(db.cols, db.valid, out_schema)
        rkeys = self._setop_key_cols(rcols, rvalid, out_schema)
        # build table sized by right capacity: always large enough, so the
        # build needs no overflow accounting
        bts = next_pow2(max(2 * right.capacity, 16))
        slot_key, bslot_row = build_hash_table(rkeys, right.sel, bts)
        match = hash_join_probe(slot_key, bslot_row, rkeys, lkeys, db.sel)
        has = match >= 0
        sel = db.sel & (has if op.kind == "intersect" else ~has)
        return db.with_sel(sel), ovf

    def _emit_setop_all(self, kind, lcols, rcols, lvalid, rvalid,
                        left, right, out_schema, dicts, ovf):
        """INTERSECT ALL / EXCEPT ALL kernel (see caller comment)."""
        nl, nr = left.capacity, right.capacity
        n = nl + nr
        cols = {
            f.name: jnp.concatenate([lcols[f.name], rcols[f.name]])
            for f in out_schema.fields
        }
        valid = {
            name: jnp.concatenate([lvalid[name], rvalid[name]])
            for name in lvalid
        }
        live = jnp.concatenate([left.sel, right.sel])
        side = jnp.concatenate(
            [jnp.zeros(nl, jnp.int32), jnp.ones(nr, jnp.int32)]
        )
        operands, spec = _row_key_operands(cols, valid, out_schema)
        sorted_ = jax.lax.sort(
            (~live,) + tuple(operands) + (side,),
            num_keys=2 + len(operands),
        )
        sdead = sorted_[0]
        svals = sorted_[1:-1]
        sside = sorted_[-1]
        pos = jnp.arange(n, dtype=jnp.int64)
        # runs are delimited by value (and deadness) changes — NOT side
        new_run = _run_boundaries((sdead,) + tuple(svals))
        run_start = segment_starts(new_run)
        # exclusive run end = start of the NEXT run
        run_end = peer_ends(new_run) + 1
        is_left = sside == 0
        cum_left = jnp.cumsum(is_left.astype(jnp.int64))

        def left_before(x):
            return jnp.where(x > 0, cum_left[jnp.clip(x - 1, 0, n - 1)], 0)

        l_run = left_before(run_end) - left_before(run_start)
        r_run = (run_end - run_start) - l_run
        left_rank = pos - run_start
        keep = left_rank < r_run if kind == "intersect" \
            else left_rank >= r_run
        sel = ~sdead & is_left & keep
        out_cols, out_valid = _unpack_sorted(svals, spec)
        out = ColumnBatch(
            cols=out_cols, valid=out_valid, sel=sel,
            nrows=jnp.sum(sel, dtype=jnp.int64),
            schema=out_schema, dicts=dicts,
        )
        return out, ovf

    def _dedup_batch(self, b: ColumnBatch, ovf):
        """Distinct over all columns with NULLs-compare-equal key semantics
        (shared by UNION and the Distinct operator). Sort-based: one
        multi-operand lexicographic sort, run boundaries mark the surviving
        representative rows — no hash table, no scatter, no capacity."""
        operands, spec = _row_key_operands(b.cols, b.valid, b.schema)
        sorted_ = jax.lax.sort(
            (~b.sel,) + tuple(operands), num_keys=1 + len(operands)
        )
        sdead = sorted_[0]
        svals = sorted_[1:]
        new = _run_boundaries((sdead,) + tuple(svals))
        sel = new & ~sdead
        cols, valid = _unpack_sorted(svals, spec)
        out = ColumnBatch(
            cols=cols, valid=valid, sel=sel,
            nrows=jnp.sum(sel, dtype=jnp.int64),
            schema=b.schema, dicts=b.dicts,
        )
        return out, ovf

    # ---- window emission ------------------------------------------------
    def _emit_window(self, op: Window, nid, inputs, emit, params):
        from ..ops.window import (
            agg_identity,
            boundaries,
            peer_ends,
            segment_starts,
            segmented_scan_minmax,
            suffix_scan_minmax,
        )

        child, ovf = emit(op.child, inputs)
        n = child.capacity
        out_cols = dict(child.cols)
        out_valid = dict(child.valid)
        out_dicts = dict(child.dicts)
        fields = list(child.schema.fields)

        by_spec: dict[tuple, list] = {}
        for name, fn, arg, pk, ok, extra in op.funcs:
            by_spec.setdefault((pk, ok), []).append((name, fn, arg, extra))

        idx = jnp.arange(n, dtype=jnp.int64)
        for (pk, ok), funcs in by_spec.items():
            pkv = [evaluate(e, child)[0] for e in pk]
            okv, odesc = [], []
            for e, d in ok:
                v, _ = evaluate(e, child)
                okv.append(v)
                odesc.append(d)
            order = sort_indices(
                pkv + okv, [False] * len(pkv) + odesc, child.sel
            )
            ssel = child.sel[order]
            spk = [v[order] for v in pkv]
            sok = [v[order] for v in okv]
            if pk:
                new_seg = boundaries(spk)
            else:
                new_seg = jnp.zeros(n, jnp.bool_).at[0].set(True)
            # dead rows (capacity padding / filter-masked) sort to the
            # tail; the live->dead transition must start its OWN segment
            # or seg_end-based frames (ntile, lead defaults, UNBOUNDED
            # FOLLOWING) would count dead slots into the last partition
            new_seg = new_seg | jnp.concatenate(
                [jnp.ones(1, jnp.bool_), ssel[1:] != ssel[:-1]]
            )
            seg_start = segment_starts(new_seg)
            seg_end = peer_ends(new_seg)
            if ok:
                new_peer = new_seg | boundaries(sok)
                peer_start = segment_starts(new_peer)
                pend_idx = peer_ends(new_peer)
            else:
                # no ORDER BY: the frame is the whole partition — same code
                # as the running case with the peer group = the segment
                new_peer = peer_start = None
                pend_idx = seg_end
            # inverse permutation for the writeback: a sort, not a scatter
            # (a TPU scatter costs ~1.1s per 8M rows; argsort ~20ms)
            inv = jnp.argsort(order)

            def frame_lo_hi(extra):
                """Per-row inclusive frame bounds [lo, hi] in sorted space.
                None = the SQL default frame (partition start .. last peer
                with ORDER BY, whole partition without)."""
                if extra is None:
                    return seg_start, pend_idx
                unit, lo_b, hi_b = extra
                if unit == "rows":
                    lo = seg_start if lo_b is None else jnp.maximum(
                        seg_start, idx + lo_b)
                    hi = seg_end if hi_b is None else jnp.minimum(
                        seg_end, idx + hi_b)
                    return lo, hi
                # RANGE: value-based bounds on the single ASC-normalized
                # order key; CURRENT ROW maps to the peer group edges
                lo = hi = None
                if lo_b is None:
                    lo = seg_start
                elif lo_b == 0:
                    lo = peer_start
                if hi_b is None:
                    hi = seg_end
                elif hi_b == 0:
                    hi = pend_idx
                if lo is not None and hi is not None:
                    return lo, hi
                # numeric offset: binary search over a packed composite
                # (partition rank, key) that is globally nondecreasing —
                # the TPU replacement for the reference's per-row frame
                # cursor walk (ob_window_function_vec_op.cpp frames)
                kk = sok[0].astype(jnp.int64)
                kt = infer_type(ok[0][0], child.schema)
                if kt.is_decimal:
                    # RANGE offsets are in VALUE units; the key column
                    # stores scaled integers
                    lo_b = None if lo_b is None else lo_b * kt.decimal_factor
                    hi_b = None if hi_b is None else hi_b * kt.decimal_factor
                if odesc[0]:
                    # ~k = -k - 1: order-reversing like negation but with
                    # no int64-min overflow; the uniform -1 shift cancels
                    # in every key-vs-target comparison
                    kk = ~kk
                live_k = jnp.where(ssel, kk, 0)
                kmin = jnp.min(jnp.where(ssel, kk, jnp.iinfo(jnp.int64).max))
                kmax = jnp.max(jnp.where(ssel, kk, jnp.iinfo(jnp.int64).min))
                span = jnp.maximum(kmax - kmin + 1, 1)
                seg_rank = jnp.cumsum(new_seg.astype(jnp.int64)) - 1
                nseg_total = jnp.maximum(seg_rank[-1] + 1, 1)
                # (rank, key) packs into one int64 only while
                # nseg * span < 2^62; wide-domain keys fall back to an
                # exact per-segment binary search (33 gather rounds)
                # chosen at RUNTIME by lax.cond — wrong frames are not an
                # acceptable failure mode for silent wide domains
                pack_ok = span <= (1 << 62) // nseg_total
                span_c = jnp.minimum(span, (1 << 62) // nseg_total)
                packed = jnp.where(
                    ssel,
                    seg_rank * span_c + jnp.clip(live_k - kmin, 0, span_c),
                    jnp.iinfo(jnp.int64).max,
                )

                def _lex_bound(target, right):
                    """Insertion point of per-row `target` within the
                    row's own [seg_start, seg_end] run of the
                    segment-ascending key array — exact for any key
                    domain, ~log2(n) element-gather rounds."""
                    lo_ = seg_start.astype(jnp.int64)
                    hi_ = seg_end.astype(jnp.int64) + 1

                    def body(_i, lh):
                        l_, h_ = lh
                        mid = (l_ + h_) >> 1
                        kv = sok[0].astype(jnp.int64)[
                            jnp.clip(mid, 0, n - 1)
                        ]
                        if odesc[0]:
                            kv = ~kv
                        go = (kv <= target) if right else (kv < target)
                        act = l_ < h_
                        return (
                            jnp.where(act & go, mid + 1, l_),
                            jnp.where(act & ~go, mid, h_),
                        )

                    l_, _h = jax.lax.fori_loop(0, 34, body, (lo_, hi_))
                    return l_

                def _sat_add(v, off):
                    # saturating v + off: a wrapped target would flip the
                    # comparison direction; saturation costs at most the
                    # single boundary value int64 min/max
                    t = v + off
                    if off >= 0:
                        return jnp.where(
                            t < v, jnp.iinfo(jnp.int64).max, t)
                    return jnp.where(t > v, jnp.iinfo(jnp.int64).min, t)

                def bound_at(off, side):
                    # out-of-domain targets must yield EMPTY frames, not
                    # clamp onto the edge rows: a frame-start above the
                    # segment's keys resolves past its end (rel=span ->
                    # next segment's base -> lo > hi), a frame-end below
                    # resolves before its start (rel=-1 -> hi < lo)
                    off = max(min(off, (1 << 63) - 1), -(1 << 63))
                    if side == "lo":
                        def packed_fn(_):
                            rel = jnp.clip(
                                _sat_add(live_k - kmin, off), 0, span_c)
                            target = seg_rank * span_c + rel
                            return jnp.searchsorted(
                                packed, target, side="left", method="sort"
                            ).astype(jnp.int64)

                        return jax.lax.cond(
                            pack_ok, packed_fn,
                            lambda _: _lex_bound(_sat_add(live_k, off), False),
                            0,
                        )

                    def packed_fn(_):
                        rel = jnp.clip(
                            _sat_add(live_k - kmin, off), -1, span_c - 1)
                        target = seg_rank * span_c + rel
                        return jnp.searchsorted(
                            packed, target, side="right", method="sort"
                        ).astype(jnp.int64) - 1

                    return jax.lax.cond(
                        pack_ok, packed_fn,
                        lambda _: _lex_bound(_sat_add(live_k, off), True) - 1,
                        0,
                    )

                if lo is None:
                    lo = bound_at(lo_b, "lo")
                if hi is None:
                    hi = bound_at(hi_b, "hi")
                return lo, hi

            def csum_range(masked_vals, lo, hi):
                """Sum over [lo, hi] via one global inclusive cumsum
                (frames never cross segment bounds by construction)."""
                c = jnp.cumsum(masked_vals)
                hi_v = c[jnp.clip(hi, 0, n - 1)]
                lo_v = jnp.where(lo > 0, c[jnp.clip(lo - 1, 0, n - 1)], 0)
                return jnp.where(hi >= lo, hi_v - lo_v, 0)

            pending_cols: dict[str, jnp.ndarray] = {}
            pending_valid: dict[str, jnp.ndarray] = {}
            for name, fn, arg, extra in funcs:
                res_valid_sorted = None
                if fn == "row_number":
                    res_sorted = idx - seg_start + 1
                elif fn == "rank":
                    res_sorted = peer_start - seg_start + 1
                elif fn == "dense_rank":
                    dcum = jnp.cumsum(new_peer.astype(jnp.int64))
                    res_sorted = dcum - dcum[seg_start] + 1
                elif fn == "ntile":
                    k = jnp.int64(extra)
                    cnt = seg_end - seg_start + 1
                    j = idx - seg_start
                    q = cnt // k
                    r = cnt % k
                    cut = r * (q + 1)
                    res_sorted = jnp.where(
                        j < cut,
                        j // (q + 1),
                        r + (j - cut) // jnp.maximum(q, 1),
                    ) + 1
                elif fn in ("lag", "lead"):
                    off, dflt = extra
                    av, avv = evaluate(arg, child)
                    av_s = av[order]
                    srcvalid = ssel if avv is None else (ssel & avv[order])
                    src = idx - off if fn == "lag" else idx + off
                    inside = (
                        src >= seg_start if fn == "lag" else src <= seg_end
                    )
                    srcc = jnp.clip(src, 0, n - 1)
                    val = av_s[srcc]
                    vvalid = srcvalid[srcc]
                    if dflt is None:
                        res_sorted = jnp.where(inside, val, 0)
                        res_valid_sorted = inside & vvalid
                    else:
                        dv, dvv = evaluate(dflt, child)
                        dv_s = jnp.broadcast_to(dv, (n,))[order]
                        dvalid = (
                            jnp.ones(n, jnp.bool_)
                            if dvv is None else dvv[order]
                        )
                        res_sorted = jnp.where(
                            inside, val, dv_s.astype(val.dtype))
                        res_valid_sorted = jnp.where(
                            inside, vvalid, dvalid)
                elif fn in ("first_value", "last_value"):
                    av, avv = evaluate(arg, child)
                    av_s = av[order]
                    srcvalid = ssel if avv is None else (ssel & avv[order])
                    lo, hi = frame_lo_hi(extra)
                    at = lo if fn == "first_value" else hi
                    atc = jnp.clip(at, 0, n - 1)
                    res_sorted = av_s[atc]
                    res_valid_sorted = (hi >= lo) & srcvalid[atc]
                else:
                    # frame aggregate: count / sum via prefix-sum range
                    # reads; min/max via one-end-bounded segmented scans
                    if arg is None:
                        av_s, avv_s = None, None
                    else:
                        av, avv = evaluate(arg, child)
                        av_s = av[order]
                        avv_s = avv[order] if avv is not None else None
                    vmask = ssel if avv_s is None else (ssel & avv_s)
                    lo, hi = frame_lo_hi(extra)
                    frame_cnt = csum_range(vmask.astype(jnp.int64), lo, hi)
                    if fn == "count":
                        res_sorted = frame_cnt
                    elif fn == "sum":
                        acc = (
                            jnp.int64
                            if jnp.issubdtype(av_s.dtype, jnp.integer)
                            else av_s.dtype
                        )
                        mv = jnp.where(vmask, av_s.astype(acc), 0)
                        res_sorted = csum_range(mv, lo, hi)
                        res_valid_sorted = frame_cnt > 0
                    elif fn in ("min", "max"):
                        is_min = fn == "min"
                        ident = agg_identity(av_s.dtype, is_min)
                        mv = jnp.where(vmask, av_s, ident)
                        lo_unbounded = extra is None or extra[1] is None
                        if lo_unbounded:
                            res_sorted = segmented_scan_minmax(
                                mv, new_seg, is_min
                            )[jnp.clip(hi, 0, n - 1)]
                        else:
                            # hi unbounded (resolver guarantees one end)
                            res_sorted = suffix_scan_minmax(
                                mv, new_seg, is_min
                            )[jnp.clip(lo, 0, n - 1)]
                        res_valid_sorted = frame_cnt > 0
                    else:
                        raise NotImplementedError(f"window function {fn}")

                dt = window_out_type(fn, arg, child.schema)
                pending_cols[name] = res_sorted.astype(dt.storage_np)
                if res_valid_sorted is not None:
                    pending_valid[name] = res_valid_sorted
                    dt = dt.with_nullable(True)
                fields.append(Field(name, dt))
                if (
                    fn in ("min", "max", "lag", "lead",
                           "first_value", "last_value")
                    and isinstance(arg, E.ColRef)
                    and arg.name in child.dicts
                ):
                    out_dicts[name] = child.dicts[arg.name]

            # ONE packed writeback gather per window spec group (the
            # per-func res[inv] element gathers were the hot cost)
            wc, wv, _ = gather_payload(pending_cols, pending_valid, inv)
            out_cols.update(wc)
            out_valid.update(wv)

        out = ColumnBatch(
            cols=out_cols, valid=out_valid, sel=child.sel, nrows=child.nrows,
            schema=Schema(tuple(fields)), dicts=out_dicts,
        )
        return out, ovf

    def _emit_full(self, op: JoinOp, nid, inputs, emit, params):
        """Full outer join: matched pairs ++ unmatched-left tail (NULL
        right) ++ unmatched-right tail (NULL left). Both sides' columns
        gain validity masks. Cold path: the per-build-row matched bit uses
        one scatter (pairs are ordered by probe row, not build row)."""
        left, lovf = emit(op.left, inputs)
        right, rovf = emit(op.right, inputs)
        ovf = {**lovf, **rovf}
        lkeys = [evaluate(e, left)[0] for e in op.left_keys]
        rkeys = [evaluate(e, right)[0] for e in op.right_keys]
        cap = params.join_cap[nid]
        skeys, order = sort_build_side(rkeys, right.sel)
        pr, br, valid_rows, total, starts, offs = expand_join(
            skeys, order, right.nrows, lkeys, left.sel, cap
        )
        pair_sel = valid_rows
        if len(op.left_keys) > 1:
            for le, re_ in zip(op.left_keys, op.right_keys):
                lv, _ = evaluate(le, left)
                rv, _ = evaluate(re_, right)
                pair_sel = pair_sel & (lv[pr] == rv[br])
        merged_dicts = {**left.dicts, **right.dicts}
        if op.residual is not None:
            pair_cols, pair_valid, _ = gather_payload(
                left.cols, left.valid, pr)
            _rc, _rv, _ = gather_payload(right.cols, right.valid, br)
            pair_cols.update(_rc)
            pair_valid.update(_rv)
            pair_batch = ColumnBatch(
                cols=pair_cols, valid=pair_valid, sel=pair_sel,
                nrows=jnp.sum(pair_sel, dtype=jnp.int64),
                schema=_join_schema(left.schema, right.schema),
                dicts=merged_dicts,
            )
            pair_sel = compile_predicate(op.residual, pair_batch)
        nl, nr = left.capacity, right.capacity
        has_l = probe_run_any(pair_sel, starts, offs)
        has_r = (
            jnp.zeros(nr, dtype=jnp.bool_).at[br].max(pair_sel, mode="drop")
        )
        lc_pr, lv_pr, _ = gather_payload(left.cols, left.valid, pr)
        rc_br, rv_br, _ = gather_payload(right.cols, right.valid, br)
        cols, valid = {}, {}
        for n, c in left.cols.items():
            cols[n] = jnp.concatenate(
                [lc_pr[n], c, jnp.zeros_like(c, shape=(nr,))]
            )
            lv = left.valid.get(n)
            mv = lv_pr[n] if n in lv_pr else jnp.ones(cap, jnp.bool_)
            tv = lv if lv is not None else jnp.ones(nl, jnp.bool_)
            valid[n] = jnp.concatenate([mv, tv, jnp.zeros(nr, jnp.bool_)])
        for n, c in right.cols.items():
            cols[n] = jnp.concatenate(
                [rc_br[n], jnp.zeros_like(c, shape=(nl,)), c]
            )
            rv = right.valid.get(n)
            mv = rv_br[n] if n in rv_br else jnp.ones(cap, jnp.bool_)
            tv = rv if rv is not None else jnp.ones(nr, jnp.bool_)
            valid[n] = jnp.concatenate([mv, jnp.zeros(nl, jnp.bool_), tv])
        sel = jnp.concatenate(
            [pair_sel, left.sel & ~has_l, right.sel & ~has_r]
        )
        out_schema = output_schema(op)
        out = ColumnBatch(
            cols=cols, valid=valid, sel=sel,
            nrows=jnp.sum(sel, dtype=jnp.int64),
            schema=out_schema, dicts=merged_dicts,
        )
        ovf = dict(ovf)
        ovf[nid] = jnp.maximum(total - cap, 0)
        return out, ovf

    # ---- aggregate emission --------------------------------------------
    def _emit_aggregate(self, op: Aggregate, nid, inputs, emit, params):
        if any(fn == "approx_ndv" for _n, fn, _a, _d in op.aggs) and (
            op.group_keys or op.grouping_sets is not None
        ):
            # grouped approx NDV: per-group register arrays would need a
            # [groups, 16K] sketch — the exact first-occurrence distinct
            # count is the better grouped plan (bounded by group rows)
            op = replace(op, aggs=tuple(
                (n, "count", a, True) if fn == "approx_ndv"
                else (n, fn, a, d)
                for n, fn, a, d in op.aggs
            ))
        if op.grouping_sets is not None:
            return self._emit_grouping_sets(op, nid, inputs, emit, params)
        spec = params.clustered_aggs.get(nid)
        if spec is not None and spec.input_alias in inputs:
            return self._emit_clustered_agg(
                op, nid, spec, inputs, emit, params
            )
        child, ovf = emit(op.child, inputs)
        key_vals = []
        key_valids = []
        domains = []
        for _, e in op.group_keys:
            v, vv = evaluate(e, child)
            if vv is None and isinstance(e, E.ColRef):
                vv = child.valid.get(e.name)
            if vv is not None:
                # SQL: NULLs form ONE group — canonicalize the value under
                # invalidity (it is arbitrary there) and key on (value,
                # validity) so NULL cannot merge with a genuine 0/""-coded
                # row (review: json_extract NULLs vs real empty strings)
                v = jnp.where(vv, v, jnp.zeros_like(v))
            key_vals.append(v)
            key_valids.append(vv)
            domains.append(_dict_domain(child, e))
        n_nullable = sum(1 for vv in key_valids if vv is not None)

        # per-aggregate (op, values, effective row mask): count(col)/sum/min/
        # max skip NULL inputs via the argument's validity mask (SQL null
        # semantics; count(*) has arg None and counts all live rows)
        agg_ops, agg_vals, agg_masks = [], [], []
        for name, fn, arg, distinct in op.aggs:
            if arg is None:
                agg_ops.append("count")
                agg_vals.append(None)
                agg_masks.append(child.sel)
            else:
                v, vv = evaluate(arg, child)
                am = child.sel if vv is None else child.sel & vv
                if distinct and fn in ("count", "sum", "avg"):
                    # DISTINCT: restrict the agg's mask to the first live
                    # occurrence of each (group keys, value); min/max are
                    # distinct-invariant and skip the extra sort. Validity
                    # planes join the dedup key — the NULL group must not
                    # share first-occurrences with the canonical-0 group
                    from ..ops.hashagg import distinct_first_mask

                    dk = key_vals + [
                        kv.astype(jnp.int32)
                        for kv in key_valids if kv is not None
                    ]
                    am = am & distinct_first_mask(dk, v, am)
                agg_ops.append(fn)
                agg_vals.append(None if fn == "count" else v)
                agg_masks.append(am)

        out_schema = _agg_schema(op, child.schema)

        out_valid = {}
        if (
            op.group_keys
            and all(d is not None for d in domains)
            and int(np.prod([d for d in domains])) * (2 ** n_nullable)
            <= DIRECT_GROUPBY_MAX_DOMAIN
        ):
            # direct path: one fused masked reduction per (slot, aggregate);
            # nullable keys contribute a domain-2 validity plane
            pk_vals, pk_doms = list(key_vals), list(domains)
            for vv in key_valids:
                if vv is not None:
                    pk_vals.append(vv.astype(jnp.int64))
                    pk_doms.append(2)
            packed, domain = pack_keys(pk_vals, pk_doms)
            slot_is = [packed == g for g in range(domain)]
            live = jnp.stack([
                jnp.sum(child.sel & g_, dtype=jnp.int64) for g_ in slot_is
            ])
            slot_used = live > 0
            # unpack keys from slot index
            bits = [max(1, int(d - 1).bit_length()) for d in pk_doms]
            slots = jnp.arange(domain, dtype=jnp.int64)
            cols = {}
            shift = 0
            for (name, e), b in zip(op.group_keys, bits):
                t = infer_type(e, child.schema)
                cols[name] = ((slots >> shift) & ((1 << b) - 1)).astype(
                    t.storage_np
                )
                shift += b
            for (name, _e), vv in zip(op.group_keys, key_valids):
                if vv is not None:
                    # each validity plane is exactly one bit, in key order
                    out_valid[name] = ((slots >> shift) & 1) == 1
                    shift += 1
            for (name, _, _, _), aop, av, am in zip(
                op.aggs, agg_ops, agg_vals, agg_masks
            ):
                cols[name] = _direct_slot_agg(aop, slot_is, am, av)
            sel = slot_used
        elif op.group_keys:
            # sort-based group-by: no hash table, no scatter, no capacity
            pack_spec = (
                params.pack_guard.get(nid)
                if nid not in params.groupby_nopack else None
            )
            # keys the others determine (`Aggregate.dependent_keys`) are
            # no sort operand: they are carried to their group's row
            dep = [n for n, _t in op.dependent_keys]
            if dep:
                count_lowering("group keys dependent", len(dep))
            carry, sort_keys = {}, []
            for (name, _e), v, vv in zip(op.group_keys, key_vals, key_valids):
                if name not in dep:
                    sort_keys.append((name, v, vv))
                    continue
                carry[name] = v
                if vv is not None:
                    carry["valid:" + name] = vv
            if any(vv is not None for _n, _v, vv in sort_keys):
                # validity planes don't fit the static pack spec: take the
                # multi-operand sort path (nullable keys are rare and never
                # the TPC-H hot group-bys)
                pack_spec = None
            if pack_spec is not None:
                # pack all keys into ONE int64 sort key (static bits from
                # stats/dict domains); a validity counter rides the
                # overflow channel — domain drift disables packing and
                # recompiles rather than mis-grouping
                pk = jnp.zeros(child.capacity, dtype=jnp.int64)
                invalid = jnp.zeros(child.capacity, dtype=jnp.bool_)
                for (_n, v, _vv), (vmin, bits) in zip(sort_keys, pack_spec):
                    off = v.astype(jnp.int64) - vmin
                    invalid = invalid | (off < 0) | (off >= (1 << bits))
                    pk = (pk << bits) | jnp.clip(off, 0, (1 << bits) - 1)
                ovf = dict(ovf)
                ovf[PACK_GUARD_BASE + nid] = jnp.sum(
                    invalid & child.sel, dtype=jnp.int64
                )
                skeys_p, sel, agg_cols, carried = sort_groupby(
                    [pk], child.sel, agg_ops, agg_vals, agg_masks, carry
                )
                # decode the original key columns from the packed bits
                cols = {}
                shift = 0
                for (name, v, _vv), (vmin, bits) in zip(
                    reversed(sort_keys), reversed(pack_spec),
                ):
                    part = (skeys_p[0] >> shift) & ((1 << bits) - 1)
                    cols[name] = (part + vmin).astype(v.dtype)
                    shift += bits
            else:
                vplanes = [
                    vv.astype(jnp.int32) for _n, _v, vv in sort_keys
                    if vv is not None
                ]
                skeys, sel, agg_cols, carried = sort_groupby(
                    [v for _n, v, _vv in sort_keys] + vplanes, child.sel,
                    agg_ops, agg_vals, agg_masks, carry
                )
                cols = {}
                for (name, _v, _vv), kv in zip(sort_keys, skeys):
                    cols[name] = kv
                vi = len(sort_keys)
                for name, _v, vv in sort_keys:
                    if vv is not None:
                        out_valid[name] = skeys[vi].astype(jnp.bool_)
                        vi += 1
            for name in dep:
                cols[name] = carried[name]
                if "valid:" + name in carried:
                    out_valid[name] = carried["valid:" + name]
            for (name, _, _, _), av in zip(op.aggs, agg_cols):
                cols[name] = av
        else:
            # scalar aggregate: single-row output, per-agg masks; SQL
            # semantics: sum/min/max over ZERO rows is NULL (count is 0)
            from ..ops.hashagg import scalar_aggregate

            cols = {}
            for (name, _, _, _), aop, av, am in zip(
                op.aggs, agg_ops, agg_vals, agg_masks
            ):
                (v,) = scalar_aggregate(am, [aop], [av])
                cols[name] = v[None]
                if aop not in ("count", "approx_ndv"):
                    out_valid[name] = jnp.any(am)[None]
            sel = jnp.ones(1, dtype=jnp.bool_)

        dicts = {}
        for name, e in op.group_keys:
            if isinstance(e, E.ColRef) and e.name in child.dicts:
                dicts[name] = child.dicts[e.name]
        out = ColumnBatch(
            cols=cols,
            valid=out_valid,
            sel=sel,
            nrows=jnp.sum(sel, dtype=jnp.int64),
            schema=out_schema,
            dicts=dicts,
        )
        return out, ovf

    # ---- execution ------------------------------------------------------
    def make_chunk_source(self, stream_table: str, chunk_rows: int):
        """Chunk-program executor for out-of-core streaming (overridden by
        the PX layer so each chunk dispatches as one shard_map program)."""
        from .chunked import _ChunkSourceExecutor

        return _ChunkSourceExecutor(
            self.catalog, stream_table, chunk_rows,
            unique_keys=self.unique_keys, stats=self.stats,
        )

    def _clamped_chunk_rows(self, plan, stream, budget: int) -> int:
        """Chunk rows sized from the DECODED on-device width of the
        streamed columns: the pipeline holds up to depth+1 decoded chunks
        in flight, so each must fit its slice of the budget. The staged
        (compressed) host bytes are charged separately through the
        governor's staged ledger and do not enter this sizing — sizing
        from wire bytes would let a high-ratio RLE column overcommit HBM
        by its encoding ratio."""
        from .memory_governor import derive_chunk_rows
        from .pipeline import decoded_row_bytes

        needed = self._needed_columns(plan).get(stream.alias) or set()
        row_b = decoded_row_bytes(
            self.catalog, stream.table, sorted(needed))
        slots = max(1, int(getattr(self, "stream_prefetch_depth", 2))) + 1
        return derive_chunk_rows(
            max(1, budget // slots), self.chunk_rows, row_bytes=row_b)

    def prepare(self, plan: LogicalOp):
        """Compile once; the returned PreparedPlan caches the XLA executable
        (the expensive artifact — this is what the plan cache stores).
        Inputs beyond the device budget return a ChunkedPreparedPlan that
        streams the biggest table through the program (engine/chunked.py)."""
        scans0 = self._collect_scans(plan)
        roles = self._access_columns(plan)
        plan = self._route_projections(plan)
        # workload access heat: computed ONCE at compile time, folded per
        # execution from the prepared plan (no plan walks on the hot path)
        access = self._access_profile(scans0, plan, roles)
        if self.chunking_enabled:
            from .chunked import (
                ChunkedPreparedPlan,
                NotStreamable,
                _find_stream_split,
                plan_input_bytes,
            )

            # the memory governor's effective budget (shrunk after any
            # observed OOM) clamps the static streaming threshold, so an
            # oversized scan is routed through the chunked path up front
            # instead of gambling on a whole-table upload
            budget = self.device_budget
            gov = self.governor
            if gov is not None:
                budget = min(budget, gov.upload_budget())
            # mesh executors shard every upload over N devices, so the
            # per-device budget admits N x the single-chip working set
            # before degrading to chunk streaming (PxExecutor sets
            # budget_scale = mesh size; single-chip has no attribute)
            budget *= max(1, int(getattr(self, "budget_scale", 1)))
            if plan_input_bytes(self, plan) > budget:
                try:
                    stream, split, kind = _find_stream_split(
                        self, plan, budget)
                    chunk_rows = self._clamped_chunk_rows(
                        plan, stream, budget)
                    cp = ChunkedPreparedPlan(
                        self, plan, stream, split, kind, chunk_rows
                    )
                    cp.access_profile = access
                    return cp
                except NotStreamable:
                    # grace-hash partitioned spill: when even the BUILD
                    # side exceeds the budget, partition both sides to
                    # host segments and stream partition pairs through
                    # one static program (engine/pipeline.py). Mesh
                    # executors shard instead (budget_scale > 1).
                    if int(getattr(self, "budget_scale", 1)) == 1:
                        from .pipeline import NotPartitionable, try_grace_hash

                        try:
                            gp = try_grace_hash(self, plan, budget)
                            gp.access_profile = access
                            return gp
                        except NotPartitionable:
                            pass
                    # whole-table upload: governor-accounted at admission;
                    # a residual device OOM is absorbed by the retry
                    # ladder (evict -> chunk -> host), never a crash
                    pass
        params = self.seed_params(plan)
        jitted, input_spec, overflow_nodes = self.compile(plan, params)
        prepared = PreparedPlan(
            self, plan, params, jitted, input_spec, overflow_nodes)
        prepared.access_profile = access
        # optimizer estimates pinned at compile time: the calibration
        # half of every (estimate, actual) pair the operator profiler
        # records (engine/plan_profile.py)
        from ..sql.planner import capture_node_estimates

        prepared.node_estimates = capture_node_estimates(self, plan)
        return prepared

    def execute(self, plan: LogicalOp, max_retries: int = 3):
        return self.prepare(plan).run(max_retries)


def program_name(plan: LogicalOp) -> str:
    """`ob_select_<plan fingerprint, 8 hex>`: the `__name__` of a plan's
    jitted program, so the profiler shows it as `jit_ob_select_...`. The
    executors compile query plans only (a DML statement's qualification
    scan is a select too); a launch that is not `jit_ob_*` is not a
    statement program."""
    from ..sql.plan_cache import plan_fingerprint

    return f"ob_select_{plan_fingerprint(plan)[:8]}"


def _collect_qparam_spec(plan) -> list | None:
    """Parameter slots of a parameterized plan, in slot order: list of
    (DataType, offset, width) per slot, or None when any parameter cannot
    ride the packed int64 vector. Scalars take one int64 lane; VECTOR
    slots take `precision` lanes (each float32 component widened to
    float64 bits) so a query embedding is ONE bound parameter block and
    ANN statements batch like point reads. The packed form exists because
    every separate qparam scalar is one more host->device transfer per
    dispatch."""
    import dataclasses as _dc

    slots: dict[int, object] = {}
    bad = False

    def expr_walk(e):
        nonlocal bad
        if isinstance(e, E.Literal):
            if e.slot is not None:
                if (e.dtype.kind is TypeKind.VECTOR
                        and int(e.dtype.precision or 0) <= 0):
                    bad = True  # unknown dimension: cannot size the block
                slots[e.slot] = e.dtype
            return
        if not hasattr(e, "__dataclass_fields__"):
            return
        for f in _dc.fields(e):
            v = getattr(e, f.name)
            if isinstance(v, E.Expr):
                expr_walk(v)
            elif isinstance(v, tuple):
                for x in v:
                    if isinstance(x, E.Expr):
                        expr_walk(x)
                    elif isinstance(x, tuple):
                        for y in x:
                            if isinstance(y, E.Expr):
                                expr_walk(y)

    def op_walk(op):
        for f in _dc.fields(op):
            v = getattr(op, f.name)
            if isinstance(v, LogicalOp):
                op_walk(v)
            elif isinstance(v, E.Expr):
                expr_walk(v)
            elif isinstance(v, tuple):
                for x in v:
                    if isinstance(x, LogicalOp):
                        op_walk(x)
                    elif isinstance(x, E.Expr):
                        expr_walk(x)
                    elif isinstance(x, tuple):
                        for y in x:
                            if isinstance(y, LogicalOp):
                                op_walk(y)
                            elif isinstance(y, E.Expr):
                                expr_walk(y)

    op_walk(plan)
    if bad:
        return None
    if not slots:
        return []
    if sorted(slots) != list(range(len(slots))):
        return None  # non-dense slots: stay on the legacy tuple
    spec = []
    off = 0
    for i in range(len(slots)):
        dt = slots[i]
        w = (int(dt.precision) if dt.kind is TypeKind.VECTOR else 1)
        spec.append((dt, off, w))
        off += w
    return spec


def packed_width(spec) -> int:
    """Total int64 lanes of a packed qparam vector for `spec`."""
    if not spec:
        return 0
    _dt, off, w = spec[-1]
    return off + w


def _unpack_qparams(qparams, spec):
    """Inside the traced program: rebuild the per-slot value tuple from
    the packed int64 vector (floats ride as bitcast bits; VECTOR slots
    come back as (d,) arrays)."""
    if not isinstance(qparams, jnp.ndarray):
        return qparams  # legacy tuple path (PX, chunked, direct callers)
    if spec is None:
        raise AssertionError("packed qparams without a pack spec")
    out = []
    for dt, off, w in spec:
        if dt.kind is TypeKind.VECTOR:
            raw = jax.lax.dynamic_slice_in_dim(qparams, off, w)
            v = jax.lax.bitcast_convert_type(raw, jnp.float64)
            out.append(v.astype(dt.storage_np))
            continue
        raw = qparams[off]
        if dt.is_float:
            v = jax.lax.bitcast_convert_type(raw, jnp.float64)
            out.append(v.astype(dt.storage_np))
        else:
            out.append(raw.astype(dt.storage_np))
    return tuple(out)


def pack_qparams(values, dtypes, spec) -> "np.ndarray | tuple":
    """Host side of the packed-parameter ABI: one int64 vector for the
    whole parameter set (or the legacy tuple when the spec opted out)."""
    if spec is None or len(spec) != len(values):
        from ..sql.plan_cache import bind

        return bind(values, dtypes)
    out = np.empty(packed_width(spec), dtype=np.int64)
    for (t, off, w), v in zip(spec, values):
        if w != 1:
            # VECTOR slot: parse + dim-check once on the host, widen each
            # float32 component to float64 bits so the device-side bitcast
            # is uniform across slot kinds
            a = np.asarray(bind_value(v, t), dtype=np.float64)
            out[off:off + w] = a.view(np.int64)
            continue
        if type(v) is int:
            # integer literal into an integer slot: the generic path costs
            # three numpy scalar hops per parameter, and this is THE shape
            # of a point read. Assignment range-checks against int64;
            # int32 slots get the same explicit bound bind_value enforces.
            k = t.kind
            if k is TypeKind.INT64:
                out[off] = v
                continue
            if k is TypeKind.INT32 and -2147483648 <= v <= 2147483647:
                out[off] = v
                continue
        s = bind_value(v, t)
        a = np.asarray(s)
        if a.dtype.kind == "f":
            out[off] = np.float64(a).view(np.int64)
        else:
            out[off] = np.int64(a)
    return out


def _narrow_seed(plan, default_rows: int) -> int:
    """Row-count seed for the fused result-narrowing frame: how many live
    rows the client can actually receive from this plan root. LIMIT/TopN
    roots bound it exactly (n + offset — the engine's limit op keeps the
    offset rows live and the cursor slices); a group-less aggregate yields
    one row; everything else falls back to the caller's default (grown on
    narrow-overflow like any other static capacity)."""
    node = plan
    while isinstance(node, Project):
        node = node.child
    if isinstance(node, (Limit, TopN)):
        return max(1, int(node.n) + int(getattr(node, "offset", 0) or 0))
    if isinstance(node, Aggregate) and not node.group_keys and (
        getattr(node, "grouping_sets", None) is None
    ):
        return 1
    return max(1, int(default_rows))


class Dispatchable:
    """What `Session._execute_entry` reads off anything it can dispatch
    (PreparedPlan, and the out-of-core plans of engine/chunked.py and
    engine/pipeline.py), with the value a plan has that never sets it."""

    retries = 0            # lifetime overflow-recompile count (plan monitor)
    params = None
    input_spec = None      # device inputs; None: nothing resident to weigh
    stream_stats = None    # engine/pipeline.StreamStats of a streamed plan
    mesh_plan = None       # PxExecutor.sync_prepared attaches these three
    px_nsh = 0
    px_exchanges = None    # None: not a PX plan; (): a PX plan, no exchange
    access_profile = ()
    node_estimates = None
    _qparam_spec = None    # None: parameters ride as the per-slot tuple
    _dev_bytes_memo = None
    _access_memo = None

    def bind(self, values, dtypes):
        """Values -> the dispatch form (one packed int64 vector when the
        plan's parameter set allows it — one upload instead of N; the
        per-slot tuple otherwise: a streamed plan's chunk and merge
        programs each read a sparse subset of the statement's slots)."""
        return pack_qparams(values, dtypes, self._qparam_spec)

    @property
    def batchable(self) -> bool:
        """Eligible for the statement micro-batcher: the plan rides the
        packed int64 qparam ABI with at least one slot (a 0-slot plan has
        nothing to vary per lane — every concurrent hit is the SAME
        dispatch and the solo path already amortizes it via the XLA
        result cache; vector/legacy-tuple plans opted out of packing)."""
        return bool(self._qparam_spec)

    @property
    def exchange_slots(self) -> int:
        """Rows of capacity this plan's row exchanges deliver in one
        execution, over all shards (counter `px exchange slots`; over it
        `px exchange rows`, the live ones, is lane occupancy): each of
        `px_nsh` shards receives `px_nsh` lanes of an exchange's capacity,
        from an all_to_all and from an all_gather alike. Static; 0 off PX."""
        return self.px_nsh * self.px_nsh * sum(
            cap for _kind, _ncols, cap in self.px_exchanges or ())

    def run(self, max_retries: int = 3, qparams: tuple = ()):
        """The synced device batch at the plan's own capacities — what
        the internal consumers take (Executor.execute, PxExecutor.execute,
        a streamed plan's merge)."""
        return self.dispatch(
            qparams, max_retries=max_retries, fused=False).batch()


class PreparedPlan(Dispatchable):
    """A compiled plan: jitted XLA program + static capacities. Re-runnable;
    transparently recompiles at larger capacities on overflow."""

    def __init__(self, executor, plan, params, jitted, input_spec, overflow_nodes):
        self.executor = executor
        self.plan = plan
        self.params = params
        self.jitted = jitted
        self.input_spec = input_spec
        self.overflow_nodes = overflow_nodes
        self._qparam_spec = _collect_qparam_spec(plan)
        # cross-session micro-batching: pow2 bucket -> vmapped executable
        # (cleared by recompile(): a capacity bump makes them stale)
        self._batched: dict[int, object] = {}
        # whole-statement fusion: pow2 narrow cap -> fused executable that
        # inlines the plan program AND the result-frame gather into ONE
        # dispatch (cleared by recompile() like the batched buckets)
        self._narrow: dict[int, object] = {}
        self._narrow_cap = 0   # current pow2 frame width (0 = unseeded)
        self._narrow_off = False  # result too wide for fusion: plain path
        # persistent-artifact state (engine/plan_artifact.py): True means
        # jitted is a live traceable jit (vmap-able for batched buckets);
        # False means it is a deserialized AOT executable that must
        # recompile before any new trace. artifact_ref = (store, aid)
        # once this plan has an on-disk artifact.
        self._traceable = True
        self.artifact_ref = None
        # compile-time optimizer row estimates per node id (filled by
        # prepare(); restored from ArtifactMeta on warm hydrate) — the
        # estimate half of the operator profiler's calibration pairs
        self.node_estimates: dict[int, int] = {}
        # what this plan's traces read of each dictionary lineage (the
        # `DictPin`s of its calls share it): a dictionary that grew serves
        # the compiled programs unless they read its strings
        self._dict_deps: dict = {}
        # an AOT-hydrated executable's (key function, tables, key it was
        # exported under): its output dictionaries are the exported ones,
        # right while the key reads the same
        self._warm_key: tuple | None = None

    def recompile(self) -> None:
        """Refresh the jitted executable after a capacity/spec change.
        EVERY recompile path must come through here: the batched bucket
        executables close over the old capacities and must drop with it."""
        self.jitted, self.input_spec, self.overflow_nodes = (
            self.executor.compile(self.plan, self.params)
        )
        self._batched.clear()
        self._narrow.clear()
        self._traceable = True
        # mesh executors rebuild their exchange recorder per compile; the
        # cached plan must follow the fresh one or its mesh plan (worker
        # spans, collective counters) would freeze at the old capacities
        sync = getattr(self.executor, "sync_prepared", None)
        if sync is not None:
            sync(self)
        if self.artifact_ref is not None:
            # the executable just changed capacity under a persisted
            # artifact: re-export at the new capacity, or the overflow
            # replays on every warm boot
            try:
                self.artifact_ref[0].on_recompile(self)
            except Exception:
                pass

    def _inputs(self):
        try:
            inputs = {
                alias: self.executor.input_batch(alias, table, cols)
                for alias, table, cols in self.input_spec
            }
        except ClusteredPremiseInvalidated:
            # the probe's clustering dissolved under a cached plan:
            # recompile (spec re-detection drops the fast path) and
            # assemble again
            self.recompile()
            return self._inputs()
        w = self._warm_key
        if w is not None and not self._traceable and w[0](w[1]) != w[2]:
            # a dictionary grew under an AOT-hydrated executable: one
            # honest recompile, as for any drift
            self.recompile()
            return self._inputs()
        return inputs

    def pinned(self, inputs) -> dict:
        """`inputs` as this plan's programs are called with them: their
        dictionaries pinned (`pin_dicts`), so a string appended to a
        column traces nothing again unless a program read the strings."""
        return pin_dicts(inputs, self._dict_deps)

    def jit_call(self, inputs, qparams):
        """Every dispatch funnels through here. A warm (artifact-loaded)
        executable validates its input signature per call; any drift (a
        table's device capacity moved since export) raises ArtifactStale
        and we recompile from the logical plan — one honest compile,
        never a stale program over wrong-shaped buffers."""
        from .plan_artifact import ArtifactStale

        inputs = self.pinned(inputs)
        try:
            out, ovf_vec = self.jitted(inputs, qparams)
        except ArtifactStale:
            self.recompile()
            inputs = self.pinned(self._inputs())
            out, ovf_vec = self.jitted(inputs, qparams)
        return unpin_dicts(out, inputs), ovf_vec

    def dispatch(self, qparams: tuple = (), max_retries: int = 3,
                 fused: bool = True) -> "DeviceResult":
        """THE way to run a prepared plan: enqueue ONE program WITHOUT any
        host sync, start the device-to-host copies its completion sync
        will read, and return the cursor. JAX async dispatch returns as
        soon as the program is enqueued, so the caller's host work (audit,
        metrics, trace assembly) overlaps device compute and the copies;
        the overflow check is the cursor's first read (DeviceResult._sync).

        This is also the one place that chooses the frame that crosses
        the link, from what the plan can see: the fused narrow frame of
        `_narrow_frame()` rows when there is one, the plan's own output
        otherwise. `fused=False` is for callers that want the device
        batch itself (`run`), not a result to hand a client."""
        from ..share.interrupt import checkpoint

        checkpoint()
        ncap = self._narrow_frame() if fused else 0
        if ncap:
            out, ovf_vec, novf = self._run_narrow(qparams, ncap)
        else:
            out, ovf_vec = self.jit_call(self._inputs(), qparams)
            novf = None
        cursor = DeviceResult(self, qparams, out, ovf_vec, novf=novf,
                              ncap=ncap, max_retries=max_retries)
        cursor.start_copies()
        return cursor

    def _overflows(self, hovf) -> dict:
        return {
            nid: int(v)
            for nid, v in zip(self.overflow_nodes, hovf)
            if int(v) > 0
        }

    def _grow(self, overflows: dict, attempt: int, max_retries: int,
              frame_short: int = 0) -> None:
        """THE overflow step of every redrive loop (the cursor's sync, a
        batched bucket): give up after `max_retries`, else bump the
        capacities that overflowed and recompile. `frame_short` is the
        cursor's own shortfall (rows its narrow frame could not hold): an
        overflow to report, with no plan capacity to bump."""
        if attempt == max_retries:
            raise RuntimeError(
                f"capacity overflow after {max_retries} retries: "
                f"{overflows or {'narrow': frame_short}}")
        if overflows:
            self.retries += 1
            self.params.bump(overflows)
            self.recompile()

    # ---- whole-statement fusion (result narrowing) --------------------
    def _narrow_frame(self) -> int:
        """Pow2 width of the fused result frame, or 0 for the plain one:
        this plan has opted out (result provably wider than the ceiling,
        or a prior narrow run overflowed past it); its executor's plans
        do not fuse (PX, a streamed merge, the degraded ladder); or the
        executable is AOT-hydrated and stays un-narrowed until a natural
        recompile makes it traceable again (building the narrow program
        would force the honest recompile that the zero-compile warm-boot
        promise forbids). Seeded from the plan root (LIMIT/aggregate
        bounds), clamped to the root-compaction capacity — narrowing past
        what compact_batch already emits moves no fewer bytes."""
        if (self._narrow_off or not self._traceable
                or not self.executor.fuses_frame):
            return 0
        ncap = self._narrow_cap
        if ncap == 0:
            ncap = next_pow2(
                _narrow_seed(self.plan, DeviceResult.NARROW_SEED_ROWS))
            root = self.params.join_cap.get(ROOT_COMPACT)
            if root:
                ncap = min(ncap, next_pow2(int(root)))
            self._narrow_cap = ncap
        if ncap > DeviceResult.NARROW_MAX_ROWS:
            self._narrow_off = True
            return 0
        return ncap

    def _build_narrow(self, ncap: int):
        """One jitted program = the plan program (inlined: calling the
        live jit inside jit fuses the traces, same mechanism as the
        batched buckets' vmap) + the final result-frame gather.
        `live_positions` keeps live rows in their original relative
        order, so the frame is bit-identical to the plain path's
        host-side sel masking."""
        inner = self.jitted

        def run_narrow(inputs, qparams):
            out, ovf_vec = inner(inputs, qparams)
            with jax.named_scope("frame"):
                nlive = jnp.sum(out.sel, dtype=jnp.int64)
                count_lowering("result frame positions searched")
                idx = live_positions(out.sel, ncap)
                cols = {n: jnp.take(c, idx, axis=0)
                        for n, c in out.cols.items()}
                valid = {n: jnp.take(v, idx, axis=0)
                         for n, v in out.valid.items()}
                nkeep = jnp.minimum(nlive, jnp.int64(ncap))
                lanes = jnp.arange(ncap, dtype=jnp.int64) < nkeep
                novf = jnp.maximum(nlive - ncap, 0)
            nb = ColumnBatch(cols=cols, valid=valid, sel=lanes,
                             nrows=nkeep, schema=out.schema,
                             dicts=out.dicts)
            return nb, ovf_vec, novf

        run_narrow.__name__ = program_name(self.plan) + "_narrow"
        return jax.jit(run_narrow)

    def _run_narrow(self, qparams: tuple, ncap: int):
        """Fused dispatch WITHOUT host sync: returns (narrowed ColumnBatch,
        plan overflow vector, narrow-overflow scalar) as device refs —
        ONE enqueued program covering predicate through final frame, so
        the statement's only host roundtrip is the cursor's completion
        sync."""
        from .plan_artifact import ArtifactStale

        for _attempt in range(3):
            # inputs before the executable: assembling them re-proves the
            # clustered premises and may recompile, which drops every
            # narrow executable built on the old program
            inputs = self.pinned(self._inputs())
            fn = self._narrow.get(ncap)
            if fn is None:
                if not self._traceable:
                    # AOT-deserialized executable: cannot re-trace inside
                    # a fresh jit — one honest recompile restores
                    # traceability (the backend hits the XLA disk cache)
                    self.recompile()
                    inputs = self.pinned(self._inputs())
                # build + first-trace under the lock: tracing re-enters
                # plan emission's process-global parameter frame, exactly
                # like the batched buckets
                with _BATCH_COMPILE_LOCK:
                    fn = self._narrow.get(ncap)
                    if fn is None:
                        fn = self._build_narrow(ncap)
                        self.executor.narrow_compiles += 1
                        try:
                            out, ovf_vec, novf = fn(inputs, qparams)
                        except ArtifactStale:
                            self.recompile()
                            continue
                        self._narrow[ncap] = fn
                        return unpin_dicts(out, inputs), ovf_vec, novf
            try:
                out, ovf_vec, novf = fn(inputs, qparams)
                return unpin_dicts(out, inputs), ovf_vec, novf
            except ArtifactStale:
                self._narrow.pop(ncap, None)
                self.recompile()
        raise RuntimeError("narrowed executable stale after recompiles")

    # ---- cross-session micro-batching ---------------------------------
    def run_batched_host(self, qblock: np.ndarray, max_retries: int = 3):
        """ONE device dispatch for B same-plan statements: `qblock` is
        the [B, nslots] stack of packed parameter vectors. The executable
        is `vmap` over the packed-parameter argument only (in_axes=(None,
        0)) — the scan/shared subplan traces against un-batched inputs,
        so XLA sees one pass over the data and per-lane work only where a
        predicate/projection actually consumes a parameter.

        B pads to a power-of-two bucket (repeat lane 0: a duplicate query
        whose lane is never scattered back) so the number of XLA
        compilations is bounded by the bucket count regardless of traffic
        shape. Returns (hcols, hvalid, hsel, schema, dicts) with a
        leading [bucket] axis on every array — the caller scatters lane i
        to waiting session i. Overflow on ANY lane takes the shared
        `_grow` step (max over lanes, exactly what the cursor's sync does
        for one) and redrives the bucket."""
        from ..share.interrupt import checkpoint

        b = int(qblock.shape[0])
        bucket = next_pow2(b)
        if bucket > b:
            qblock = np.concatenate(
                [qblock, np.repeat(qblock[:1], bucket - b, axis=0)])
        from .plan_artifact import ArtifactStale

        for attempt in range(max_retries + 1):
            checkpoint()
            # inputs before the executable, as in _run_narrow
            inputs = self.pinned(self._inputs())
            fn = self._batched.get(bucket)
            if fn is None and not self._traceable:
                # warm (artifact-loaded) plan: vmap over a deserialized
                # call is unsupported, so hydrate the persisted bucket
                # variant if one exists; else restore traceability with
                # one honest recompile (counted; the backend compile hits
                # the XLA disk cache) and build below as usual
                store = self.artifact_ref[0] if self.artifact_ref else None
                fn = (store.load_bucket(self, bucket)
                      if store is not None else None)
                if fn is not None:
                    self._batched[bucket] = fn
                else:
                    self.recompile()
                    inputs = self.pinned(self._inputs())
            if fn is None:
                # build + first-trace under the lock: tracing re-enters
                # plan emission, which installs the process-global active
                # parameter frame (expr.compile.set_params) — two leaders
                # tracing concurrently would cross their frames
                with _BATCH_COMPILE_LOCK:
                    fn = self._batched.get(bucket)
                    if fn is None:
                        fn = jax.jit(jax.vmap(self.jitted,
                                              in_axes=(None, 0)))
                        self.executor.batched_compiles += 1
                        out, ovf_vec = fn(inputs, qblock)
                        self._batched[bucket] = fn
                        if self.artifact_ref is not None:
                            try:
                                self.artifact_ref[0].export_bucket(
                                    self, bucket, fn)
                            except Exception:
                                pass
                    else:
                        out, ovf_vec = fn(inputs, qblock)
            else:
                try:
                    out, ovf_vec = fn(inputs, qblock)
                except ArtifactStale:
                    # catalog drift under a hydrated bucket executable:
                    # drop it and redrive through a clean rebuild
                    self._batched.pop(bucket, None)
                    self.recompile()
                    continue
            hovf, hcols, hvalid, hsel = jax.device_get(
                (ovf_vec, out.cols, out.valid, out.sel))
            overflows = self._overflows(np.asarray(hovf).max(axis=0))
            if not overflows:
                return (hcols, hvalid, hsel, out.schema,
                        unpin_dicts(out, inputs).dicts)
            self._grow(overflows, attempt, max_retries)
        raise AssertionError


# serializes batched-bucket trace/compile across leader threads (see
# PreparedPlan.run_batched_host)
_BATCH_COMPILE_LOCK = threading.Lock()


# fetch_head's compaction gather, jitted with a STATIC width so the
# executable is shared across results of the same shape. The trace
# counter is a mutable cell bumped inside the traced body: it moves only
# when XLA actually (re)compiles, which is what the regression test
# pins — distinct LIMIT values within one pow2 bucket must not retrace.
_head_gather_traces = [0]


def _head_gather_impl(cols, valid, sel, k):
    _head_gather_traces[0] += 1
    idx = live_positions(sel, k)
    return (
        {n: jnp.take(c, idx) for n, c in cols.items()},
        {n: jnp.take(v, idx) for n, v in valid.items()},
    )


_head_gather = jax.jit(_head_gather_impl, static_argnums=(3,))


class DeviceResult:
    """Lazy device-resident result cursor (the serving-path half of the
    fast path: `SELECT ... LIMIT 10` over a 60M-row result must transfer
    KB, not GB), and the record of the execution it is the result of.

    The frame behind it is in one of two states. `narrow(ncap)`: `out` is
    the final ncap-row result frame (plan program + compaction gather in
    ONE XLA program, PreparedPlan._run_narrow), so the whole client-
    visible payload is in flight from dispatch on. `plain` (ncap 0): `out`
    is the plan's own output at its static capacities.

    `start_copies` (called once at dispatch, and again on every redriven
    output) starts the device-to-host copy of every leaf the completion
    sync will read, while the program still runs, so `_sync` waits for
    the program once and finds the copies landed or landing. Which leaves
    those are depends on the frame alone:

      * narrow, or plain of at most FRAME_PREFETCH_BYTES (static bytes):
        the overflow counters and the whole frame (columns, validity
        vectors, sel) — no separate d2h leg and no O(capacity) host fold;
      * a larger plain frame: ONLY the overflow counters and the live row
        count (two scalars). Column data transfers on demand: per touched
        column, or LIMIT-bounded via a device-side compaction gather when
        the caller wants the first k rows of a large result.

    The sync is the async-dispatch sync point and owns THE overflow loop:
    a capacity overflow bumps, recompiles and redrives here; a narrow
    frame too small for the live rows grows to the next power of two and
    redrives, and past NARROW_MAX_ROWS the plan surrenders fusion (for
    good: `_narrow_off`) and this cursor moves to the plain state."""

    # a plain frame this small (bytes of its leaves' static shapes)
    # crosses the link whole with the completion sync; a larger one stays
    # lazy
    FRAME_PREFETCH_BYTES = 65536
    # the narrow frame: rows it is seeded with where the plan root gives
    # no bound, and the width past which a result is not worth fusing
    NARROW_SEED_ROWS = 256
    NARROW_MAX_ROWS = 4096
    # fetch_head gathers on the device a head of at most one in this
    # many rows of the capacity; a larger one brings the whole columns,
    # which is where finding and gathering the rows on a v5e comes to
    # cost more than the bytes it spares the link (PERF.md section 6,
    # PR 36)
    HEAD_GATHER_SHARE = 4

    def __init__(self, prepared, qparams, out, ovf_vec, novf=None,
                 ncap: int = 0, max_retries: int = 3):
        self.prepared = prepared
        self._qparams = qparams
        self._out = out
        self._ovf = ovf_vec
        self._novf = novf      # narrow state: rows the frame fell short by
        self._ncap = int(ncap)  # narrow state: the frame's width; 0 = plain
        self._max_retries = max_retries
        # the execution's record, updated in place as transfers happen:
        # server/diag.QueryProfile (fetch_s / d2h_bytes), the statement's
        # phase walls, and the per-operator profile of a profiled run
        self.profile = None
        self.phases = None
        self.op_profile = None
        self._nrows: int | None = None
        self._hcols: dict = {}
        self._hvalid: dict = {}
        self._hsel = None
        # set by start_copies: the frame's static bytes, and whether the
        # whole of it was started (and so is what _sync reads)
        self.frame_bytes = 0
        self.prefetched = False
        self._hovf = None      # the clean run's overflow vector, on the host

    @property
    def exchange_rows(self) -> int:
        """Live rows the program's exchanges delivered, over all shards: the
        element a PX program appends to its overflow vector (0 off PX),
        which the sync has read anyway."""
        self._sync()
        return int(self._hovf[len(self.prepared.overflow_nodes):].sum())

    @property
    def narrowed(self) -> bool:
        """The frame is the fused program's: already cut to the rows a
        client can receive, whatever its bytes."""
        return self._ncap > 0

    def start_copies(self) -> None:
        """Start the device-to-host copy of exactly the leaves `_sync` is
        going to read (nothing blocks here: the runtime queues each copy
        behind the program that defines its buffer)."""
        out = self._out
        frame = [*out.cols.values(), *out.valid.values(), out.sel]
        self.frame_bytes = sum(int(a.nbytes) for a in frame)
        self.prefetched = (self.narrowed
                           or self.frame_bytes <= self.FRAME_PREFETCH_BYTES)
        leaves = ([self._ovf, *frame] if self.prefetched
                  else [self._ovf, out.nrows])
        if self.narrowed:
            leaves.append(self._novf)
        for a in leaves:
            a.copy_to_host_async()

    def _observe(self, seconds: float, nbytes: int,
                 kind: str = "sync") -> None:
        if self.profile is not None:
            self.profile.fetch_s += seconds
            self.profile.d2h_bytes += nbytes
        if self.phases is not None:
            self.phases["fetch_s"] = self.phases.get("fetch_s", 0.0) + seconds
            if kind == "d2h":
                # column-data transfers, split out of the dispatch sync so
                # the host-tax ledger can carve "d2h" from "device wait"
                self.phases["d2h_s"] = (
                    self.phases.get("d2h_s", 0.0) + seconds)

    def _sync(self) -> None:
        """Overflow check + row count: the deferred tail of the dispatch,
        and the one bump / recompile / redrive loop."""
        if self._nrows is not None:
            return
        import time as _time

        from ..share.interrupt import checkpoint

        p = self.prepared
        for attempt in range(self._max_retries + 1):
            t0 = _time.perf_counter()
            # every read below was started by start_copies: the first
            # waits for the program, the rest are landed or landing
            whole = self.prefetched
            hovf = np.asarray(self._ovf)
            short = int(np.asarray(self._novf)) if self.narrowed else 0
            if whole:
                harrs = {n: np.asarray(a)
                         for n, a in self._out.cols.items()}
                hvals = {n: np.asarray(a)
                         for n, a in self._out.valid.items()}
                hsel = np.asarray(self._out.sel)
                # the device nrows scalar is sum(sel); with sel crossing
                # anyway the sum runs host-side, one fewer leaf
                hn = int(hsel.sum())
            else:
                hn = int(np.asarray(self._out.nrows))
            self._observe(_time.perf_counter() - t0,
                          int(getattr(hovf, "nbytes", 0)) + 8)
            overflows = p._overflows(hovf)
            if not overflows and not short:
                self._nrows = hn
                self._hovf = hovf
                if whole:
                    # commit ONLY on a clean run: an overflowed attempt's
                    # arrays are garbage and must not seed the host cache
                    self._hcols.update(harrs)
                    self._hvalid.update(hvals)
                    self._hsel = hsel
                    self._observe(0.0, sum(
                        int(getattr(a, "nbytes", 0))
                        for d in (harrs, hvals) for a in d.values()
                    ) + int(hsel.nbytes))
                return
            p._grow(overflows, attempt, self._max_retries, short)
            if short:
                grown = next_pow2(self._ncap + short)
                p._narrow_cap = max(p._narrow_cap, grown)
                if grown > self.NARROW_MAX_ROWS:
                    # frame too wide to fuse: remember on the plan (next
                    # warm hit skips fusion outright) and finish THIS
                    # statement in the plain state
                    p._narrow_off = True
                    grown = 0
                self._ncap = grown
            checkpoint()
            if self.narrowed:
                self._out, self._ovf, self._novf = p._run_narrow(
                    self._qparams, self._ncap)
            else:
                self._out, self._ovf = p.jit_call(
                    p._inputs(), self._qparams)
            self.start_copies()
        raise AssertionError

    def batch(self):
        """The synced device batch itself (PreparedPlan.run)."""
        self._sync()
        return self._out

    @property
    def nrows(self) -> int:
        self._sync()
        return self._nrows

    @property
    def schema(self):
        return self._out.schema

    @property
    def dicts(self):
        return self._out.dicts

    def fetch_columns(self, names=None) -> dict:
        """Host rows (sel-compacted, dict-decoded) for the requested
        columns — all of them when names is None. Each column transfers
        at most once; repeats serve from the host cache."""
        import time as _time

        from ..core.column import host_rows

        self._sync()
        fields = [f for f in self._out.schema.fields
                  if names is None or f.name in names]
        need = [f.name for f in fields if f.name not in self._hcols]
        if need or self._hsel is None:
            arrs = {n: self._out.cols[n] for n in need}
            vals = {n: self._out.valid[n] for n in need
                    if n in self._out.valid}
            tl = _gap.tracing()
            if tl is not None:
                tl.leaf("d2h")
            t0 = _time.perf_counter()
            sel_fetched = self._hsel is None
            if sel_fetched:
                harrs, hvals, hsel = jax.device_get(
                    (arrs, vals, self._out.sel))
                self._hsel = np.asarray(hsel)
            else:
                harrs, hvals = jax.device_get((arrs, vals))
            nbytes = sum(int(getattr(a, "nbytes", 0))
                         for d in (harrs, hvals) for a in d.values())
            if sel_fetched:
                nbytes += int(self._hsel.nbytes)
            self._observe(_time.perf_counter() - t0, nbytes,
                          kind="d2h")
            if tl is not None:
                tl.leaf_end()
            self._hcols.update(harrs)
            self._hvalid.update(hvals)
        sub = Schema(tuple(fields))
        return host_rows(sub, self._out.dicts, self._hcols, self._hvalid,
                         self._hsel)

    def fetch_head(self, limit: int) -> dict:
        """First `limit` live rows via a device-side compaction gather:
        ~k rows per column cross the link instead of the full static
        capacity. The gather width buckets to a power of two so a client
        sweeping LIMIT values (pagination) reuses log2(cap) executables
        instead of compiling one per distinct k. Serves from the host
        cache when a full fetch already happened, and makes that full
        fetch where the head is a large share of the capacity."""
        import time as _time

        from ..core.column import host_rows

        self._sync()
        k = min(int(limit), self._nrows)
        cap = int(self._out.sel.shape[-1])
        kb = min(next_pow2(max(k, 1)), cap)
        fetched = self._hsel is not None and not (
            set(f.name for f in self._out.schema.fields) - set(self._hcols))
        if fetched or kb * self.HEAD_GATHER_SHARE > cap:
            host = self.fetch_columns()
            return {n: v[:k] for n, v in host.items()}
        arrs, vals = _head_gather(self._out.cols, self._out.valid,
                                  self._out.sel, kb)
        tl = _gap.tracing()
        if tl is not None:
            tl.leaf("d2h")
        t0 = _time.perf_counter()
        harrs, hvals = jax.device_get((arrs, vals))
        nbytes = sum(int(getattr(a, "nbytes", 0))
                     for d in (harrs, hvals) for a in d.values())
        self._observe(_time.perf_counter() - t0, nbytes, kind="d2h")
        if tl is not None:
            tl.leaf_end()
        host = host_rows(self._out.schema, self._out.dicts, harrs, hvals,
                         np.ones(kb, dtype=np.bool_))
        return {n: v[:k] for n, v in host.items()}


def _range_bounds(c: E.Expr, qual: str) -> list:
    """Classify one conjunct as bounds on column `qual`: a list of
    ('gt'|'ge'|'lt'|'le'|'eq', Literal) pairs (empty = not a bound).
    Handles both operand orders and non-negated BETWEEN."""
    if isinstance(c, E.Between) and not c.negated:
        if (
            isinstance(c.arg, E.ColRef) and c.arg.name == qual
            and isinstance(c.low, E.Literal)
            and isinstance(c.high, E.Literal)
        ):
            return [("ge", c.low), ("le", c.high)]
        return []
    if not isinstance(c, E.Compare):
        return []
    flip = {"<": ">", "<=": ">=", ">": "<", ">=": "<=", "=": "="}
    op, lhs, rhs = c.op, c.left, c.right
    if isinstance(rhs, E.ColRef) and isinstance(lhs, E.Literal):
        op, lhs, rhs = flip.get(op), rhs, lhs
    if not (
        isinstance(lhs, E.ColRef) and lhs.name == qual
        and isinstance(rhs, E.Literal) and op in flip
    ):
        return []
    kind = {"<": "lt", "<=": "le", ">": "gt", ">=": "ge", "=": "eq"}[op]
    return [(kind, rhs)]


def _slice_sorted_scan(qb: ColumnBatch, sl: _SliceSpec, cap: int, n: int):
    """Read only the qualifying key range of a sorted-projection scan.

    Device binary search finds [lo, hi) from the (possibly parameterized)
    bounds, one dynamic_slice per column reads `cap` rows from lo, and
    rows outside [lo, hi) mask off. Returns (sliced batch, overflow =
    max(hi-lo-cap, 0)) — a runtime range wider than the static capacity
    rides the usual overflow-retry recompile. The TPU redesign of the
    reference's index range scan (ob_das_scan_op.h): the 'index' is the
    projection's physical order, the 'scan range' a device slice."""
    from ..expr.compile import literal_scalar

    kcol = jax.lax.slice_in_dim(qb.cols[sl.key], 0, n)  # drop capacity pad
    lo = jnp.zeros((), jnp.int64)
    hi = jnp.full((), n, jnp.int64)
    for lit, side in sl.lows:
        v = literal_scalar(lit).astype(kcol.dtype)
        lo = jnp.maximum(
            lo, jnp.searchsorted(kcol, v, side=side).astype(jnp.int64)
        )
    for lit, side in sl.highs:
        v = literal_scalar(lit).astype(kcol.dtype)
        hi = jnp.minimum(
            hi, jnp.searchsorted(kcol, v, side=side).astype(jnp.int64)
        )
    hi = jnp.maximum(hi, lo)
    cap2 = qb.capacity
    start = jnp.clip(lo, 0, cap2 - cap)
    gidx = start + jnp.arange(cap, dtype=jnp.int64)
    in_range = (gidx >= lo) & (gidx < hi)

    def dsl(c):
        return jax.lax.dynamic_slice_in_dim(c, start, cap)

    cols = {k: dsl(c) for k, c in qb.cols.items()}
    valid = {k: dsl(c) for k, c in qb.valid.items()}
    sel = dsl(qb.sel) & in_range
    out = ColumnBatch(
        cols=cols,
        valid=valid,
        sel=sel,
        nrows=jnp.sum(sel, dtype=jnp.int64),
        schema=qb.schema,
        dicts=qb.dicts,
    )
    return out, jnp.maximum((hi - lo) - cap, 0)


def _affine_candidates(probe_key, aff, nb):
    """Direct-address candidate build rows against an affine build key
    column: cand = (key - a0) / stride — no sorts, no gathers. Callers
    verify via gathered build key + liveness (folded into the packed
    payload gather so the verify costs no extra gather pass)."""
    a0, stride = aff
    off = probe_key.astype(jnp.int64) - a0
    cand = off // stride
    in_range = (off >= 0) & (off % stride == 0) & (cand < nb)
    candc = jnp.clip(cand, 0, nb - 1).astype(jnp.int32)
    return candc, in_range


def _affine_probe(build_key, build_sel, probe_key, probe_sel, aff):
    """Verified affine probe for callers that need ONLY the match row
    (semi/anti). The verify gather rides one packed row-gather."""
    candc, in_range = _affine_candidates(probe_key, aff, build_key.shape[0])
    got = gather_payload(
        {"#k": build_key}, {}, candc, build_sel
    )
    hit = (
        probe_sel & in_range
        & (got[0]["#k"] == probe_key)
        & got[2]
    )
    return jnp.where(hit, candc, -1)


def _direct_slot_agg(op: str, slot_is, mask, values):
    """One aggregate over a small packed-key domain as fused masked
    reductions (the scatter-free direct group-by)."""
    if op == "count":
        return jnp.stack(
            [jnp.sum(mask & g, dtype=jnp.int64) for g in slot_is]
        )
    if op == "sum":
        acc = (
            jnp.int64
            if jnp.issubdtype(values.dtype, jnp.integer)
            else values.dtype
        )
        return jnp.stack([
            jnp.sum(jnp.where(mask & g, values, 0).astype(acc))
            for g in slot_is
        ])
    if op == "min":
        ident = (
            jnp.iinfo(values.dtype).max
            if jnp.issubdtype(values.dtype, jnp.integer)
            else jnp.inf
        )
        return jnp.stack([
            jnp.min(jnp.where(mask & g, values, ident)) for g in slot_is
        ])
    if op == "max":
        ident = (
            jnp.iinfo(values.dtype).min
            if jnp.issubdtype(values.dtype, jnp.integer)
            else -jnp.inf
        )
        return jnp.stack([
            jnp.max(jnp.where(mask & g, values, ident)) for g in slot_is
        ])
    raise NotImplementedError(op)


def _join_schema(ls: Schema, rs: Schema) -> Schema:
    return Schema(tuple(list(ls.fields) + list(rs.fields)))


def _agg_schema(op: Aggregate, child_schema: Schema) -> Schema:
    fields = []
    gs = op.grouping_sets
    for i, (name, e) in enumerate(op.group_keys):
        t = infer_type(e, child_schema)
        if gs is not None and any(i not in s for s in gs):
            t = replace(t, nullable=True)  # NULL-filled in coarser sets
        fields.append(Field(name, t))
    for name, fn, arg, _ in op.aggs:
        if fn in ("count", "approx_ndv"):
            fields.append(Field(name, DataType.int64()))
        else:
            t = infer_type(arg, child_schema)
            if fn == "sum" and t.is_decimal:
                t = DataType.decimal(18, t.scale)
            elif fn == "sum" and t.is_integer:
                t = DataType.int64()
            fields.append(Field(name, t))
    return Schema(tuple(fields))
