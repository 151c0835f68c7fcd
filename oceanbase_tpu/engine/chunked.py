"""Out-of-core execution: stream chunks of a too-big table through the
jitted plan, merge partial aggregates.

Reference surface: the spill machinery of the vectorized engine — hash
partitioning infrastructure (sql/engine/basic/ob_hp_infras_vec_op.h),
sort/hash-join/hash-agg spill to tmp files (src/storage/tmp_file), and the
SQL memory manager that decides when operators go out-of-core
(ob_tenant_sql_memory_manager.h:580).

TPU redesign: instead of spilling operator state to disk mid-run, the
engine keeps the DEVICE program dense and static — the biggest input table
streams through it in fixed-capacity row chunks (the host arrays are the
"spill tier"), and the plan is algebraically split at its lowest blocking
operator above the streamed scan:

    original:  above_plan( Aggregate_A( stream_path(scan_T, residents...) ) )
    streamed:  for each chunk c of T:   partial_c = Aggregate_A(... chunk ...)
    merged:    above_plan( MergeAggregate( concat(partial_c) ) )

sum/count/min/max partials merge exactly (count merges by sum); avg was
already decomposed into sum/count by the resolver. Joins on the stream path
keep the streamed side as the probe (left) input, so every chunk probes the
same resident build sides — the ObHJPartition analog with the roles fixed
by planning instead of runtime respill.

The chunk capacity is constant across chunks (the last chunk is padded), so
XLA compiles the chunk program exactly once.
"""

from __future__ import annotations

import os
from dataclasses import replace as dc_replace

import numpy as np

from ..core.dtypes import DataType, Field, Schema, TypeKind
from ..core.table import Table
from ..expr import ir as E
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
    output_schema,
)
from .executor import Dispatchable, Executor, _children
from .pipeline import StreamStats, assemble_partials_table, run_stream

import jax
import jax.numpy as jnp


@jax.jit
def _decode_chunk(narrow, bases, count):
    """One-dispatch decode of a narrowed chunk upload: cast each column
    back to its storage width, add its frame-of-reference base, and
    derive the live-row mask. Marker keys '#v:<col>' are validity masks
    (uint8 -> bool)."""
    out = {}
    for k, a in narrow.items():
        if k.startswith("#v:"):
            out[k] = a != 0
        else:
            b = bases[k]
            out[k] = a.astype(b.dtype) + b
    cap = next(iter(narrow.values())).shape[0] if narrow else 0
    sel = jnp.arange(cap, dtype=jnp.int64) < count
    return out, sel


DEFAULT_DEVICE_BUDGET = int(
    os.environ.get("OB_TPU_DEVICE_BUDGET", str(6 << 30))
)
DEFAULT_CHUNK_ROWS = int(os.environ.get("OB_TPU_CHUNK_ROWS", str(1 << 23)))

_MERGE_FN = {"sum": "sum", "count": "sum", "min": "min", "max": "max"}


class NotStreamable(Exception):
    """The plan cannot be split for chunked execution (caller falls back to
    whole-table upload and may simply run out of device memory — the same
    contract as an unspillable operator in the reference)."""


def scan_bytes(catalog, scan: Scan, needed_cols) -> int:
    if scan.table == "$dual":
        return 1
    t = catalog[scan.table]
    cols = needed_cols.get(scan.alias) or set(
        [t.schema.fields[0].name]
    )
    per_row = 0
    for c in cols:
        if c in t.schema:
            per_row += t.schema[c].storage_np.itemsize
    return (t.nrows or 0) * max(per_row, 1)


def plan_input_bytes(executor: Executor, plan: LogicalOp) -> int:
    needed = executor._needed_columns(plan)
    return sum(
        scan_bytes(executor.catalog, s, needed)
        for s in executor._collect_scans(plan)
    )


def _row_bytes(schema: Schema) -> int:
    return max(sum(f.dtype.storage_np.itemsize for f in schema.fields), 1)


def _find_stream_split(executor: Executor, plan: LogicalOp, budget: int):
    """Choose the streamed scan and the chunk-accumulation split node.

    Returns (stream_scan, split_node, kind). `split_node` is the node run
    per chunk; its per-chunk outputs (the "partials") concatenate into the
    $partials relation which the merge plan consumes. Kinds, tried
    most-reducing first along the root->scan path (every node between the
    split and the scan must stream rows: Filter / Project /
    Join-with-stream-on-probe-side):

      agg         lowest Aggregate with mergeable aggs -> re-aggregate
      topn        lowest TopN -> per-chunk top (n+offset), final top-n
      distinct    lowest Distinct -> per-chunk dedup, final dedup
      passthrough the maximal streamable prefix itself (filters, projects,
                  probe joins): partials are the surviving rows; the rest
                  of the plan (sort / window / distinct / set ops / any
                  aggregate) runs unchanged on $partials. Guarded by the
                  optimizer estimate of surviving rows fitting the budget.
    """
    needed = executor._needed_columns(plan)
    scans = executor._collect_scans(plan)
    if not scans:
        raise NotStreamable("no scans")
    sizes = [(scan_bytes(executor.catalog, s, needed), s) for s in scans]
    sizes.sort(key=lambda p: -p[0])
    big, stream = sizes[0]
    rest = sum(b for b, _ in sizes[1:])
    if rest > budget:
        raise NotStreamable("multiple over-budget inputs")
    if sum(1 for s in scans if s.table == stream.table) > 1:
        raise NotStreamable("streamed table scanned more than once")

    # path from root to the streamed scan
    path: list[LogicalOp] = []

    def find(op) -> bool:
        path.append(op)
        if op is stream:
            return True
        for c in _children(op):
            if find(c):
                return True
        path.pop()
        return False

    assert find(plan)

    def path_streams(from_pos: int) -> bool:
        """All nodes strictly below path[from_pos] down to the scan move
        rows chunk-wise."""
        for parent, child in zip(path[from_pos + 1:], path[from_pos + 2:]):
            if isinstance(parent, (Filter, Project)):
                continue
            if isinstance(parent, JoinOp):
                if child is not parent.left:
                    return False
                continue
            if isinstance(parent, Scan):
                continue
            return False
        return True

    # lowest (nearest-scan) candidates per kind
    def lowest(pred):
        best = None
        for i, node in enumerate(path):
            if pred(node):
                best = i
        return best

    i = lowest(lambda n: isinstance(n, Aggregate))
    if i is not None and path_streams(i):
        agg = path[i]
        if all(
            not d and fn in _MERGE_FN for _nm, fn, _a, d in agg.aggs
        ):
            return stream, agg, "agg"

    i = lowest(lambda n: isinstance(n, TopN))
    if i is not None and path_streams(i):
        topn = path[i]
        if all(isinstance(e, E.ColRef) for e, _d in topn.keys):
            return stream, topn, "topn"

    i = lowest(lambda n: isinstance(n, Distinct))
    if i is not None and path_streams(i):
        return stream, path[i], "distinct"

    # passthrough: the TOPMOST node that itself streams and whose whole
    # lower path streams (the maximal streamable prefix)
    best = None
    for i in range(len(path) - 1):
        node = path[i]
        ok_self = isinstance(node, (Filter, Project)) or (
            isinstance(node, JoinOp) and path[i + 1] is node.left
        )
        if ok_self and path_streams(i):
            best = i
            break
    if best is not None:
        split = path[best]
        est = executor._est_rows(split)
        out_b = est * _row_bytes(output_schema(split))
        if out_b <= budget:
            return stream, split, "passthrough"
        raise NotStreamable("passthrough partials exceed budget")
    # last resort: stream the scan itself (its pushed filter reduces per
    # chunk); everything above — window, sort, set ops — runs on $partials.
    # Partial width counts only the columns the plan reads, matching the
    # narrowed chunk program ChunkedPreparedPlan builds for this kind
    est = executor._est_rows(stream)
    t = executor.catalog[stream.table]
    cols = needed.get(stream.alias) or {t.schema.fields[0].name}
    per_row = max(sum(
        f.dtype.storage_np.itemsize
        for f in t.schema.fields if f.name in cols
    ), 1)
    if est * per_row <= budget:
        return stream, stream, "scan"
    raise NotStreamable("no streamable split above the streamed scan")


def _replace_node(plan: LogicalOp, target: LogicalOp, replacement: LogicalOp):
    if plan is target:
        return replacement
    kids = _children(plan)
    if not kids:
        return plan
    if isinstance(plan, (JoinOp, SetOp)):
        return dc_replace(
            plan,
            left=_replace_node(plan.left, target, replacement),
            right=_replace_node(plan.right, target, replacement),
        )
    return dc_replace(
        plan, child=_replace_node(plan.child, target, replacement)
    )


def _partials_scan(out_s: Schema, alias: str = "$m") -> Scan:
    """Scan($partials) with an extra `$live` int8 column: the relation is
    padded to a stable power-of-two capacity so the merge program's input
    shapes — and therefore its XLA executable — are reused across runs;
    pad rows are filtered by the pushed `$live = 1` predicate."""
    fields = [Field(f"{alias}.{f.name}", f.dtype) for f in out_s.fields]
    fields.append(Field(f"{alias}.$live", DataType.int8()))
    return Scan(
        "$partials", alias, Schema(tuple(fields)),
        pushed_filter=E.Compare("=", E.ColRef(f"{alias}.$live"), E.lit(1)),
    )


def _merge_plan(split: LogicalOp, kind: str, alias: str = "$m"):
    """(chunk_plan, merge_node): the program run per chunk and the node
    that replaces `split` in the surrounding plan, reading $partials.

    agg:         partial = Aggregate output rows; merge = re-aggregate
                 (sum/count->sum, min->min, max->max)
    topn:        partial = top (n+offset) rows per chunk; merge = the
                 original TopN over the concatenated partials
    distinct:    partial = per-chunk dedup; merge = final dedup
    passthrough: partial = the surviving rows themselves; merge = a rename
                 projection (the rest of the plan runs unchanged)
    """
    out_s = output_schema(split)
    scan = _partials_scan(out_s, alias)
    if kind == "agg":
        group_keys = tuple(
            (name, E.ColRef(f"{alias}.{name}"))
            for name, _e in split.group_keys
        )
        aggs = tuple(
            (name, _MERGE_FN[fn], E.ColRef(f"{alias}.{name}"), False)
            for name, fn, _arg, _d in split.aggs
        )
        return split, scan, Aggregate(scan, group_keys, aggs)
    # rename projection: "$m.x" -> "x" so the surrounding plan sees the
    # split node's original output names
    rename = Project(
        scan,
        tuple((f.name, E.ColRef(f"{alias}.{f.name}")) for f in out_s.fields),
    )
    if kind == "topn":
        chunk = dc_replace(split, n=split.n + split.offset, offset=0)
        return chunk, scan, dc_replace(split, child=rename)
    if kind == "distinct":
        return split, scan, Distinct(rename)
    if kind == "passthrough":
        return split, scan, rename
    raise AssertionError(kind)


class _OverlayCatalog:
    """Base catalog plus extra tables (the $partials relation)."""

    def __init__(self, base, extra: dict):
        self.base = base
        self.extra = extra

    def __getitem__(self, name):
        if name in self.extra:
            return self.extra[name]
        return self.base[name]

    def __contains__(self, name):
        return name in self.extra or name in self.base

    def is_private(self, name):
        if name in self.extra:
            return False
        f = getattr(self.base, "is_private", None)
        return f(name) if f is not None else False


class ChunkWindowMixin:
    """Shared chunk-window behavior of the single-chip and PX chunk
    executors: the [start, end) slice state, the host-side slice batch,
    and chunk-sized cardinality estimates. Subclasses provide
    `table_batch` (the device placement differs: plain arrays vs sharded
    device_put)."""

    #: single-chip chunk sources accept prefetch-staged compressed chunks
    #: (engine/pipeline.py); the PX source keeps the legacy host-slice
    #: path (its uploads must shard over the mesh, not ride device_put)
    supports_staged = False

    def set_chunk(self, start: int, end: int):
        self._chunk = (start, end)
        item = getattr(self, "_staged_item", None)
        if item is not None and item.win != (start, end):
            self._staged_item = None
        # drop only the streamed table's cached device batch
        self.invalidate_table(self.stream_table)

    def set_stager(self, stager) -> None:
        """Attach/detach the wire-encoding stager for the streaming run
        (pipeline.run_stream brackets the chunk loop with this)."""
        self._stager = stager
        self._staged_item = None

    def set_chunk_staged(self, start: int, end: int, item) -> None:
        """Position the window on a chunk whose wire-encoded arrays are
        already on device (prefetched): the next table read decodes the
        staged tree instead of re-slicing host arrays."""
        self._staged_item = item
        self.set_chunk(start, end)

    def _chunk_slice_batch(self, name, cols):
        """Host ColumnBatch of the current chunk window, padded to the
        constant chunk capacity (one XLA compile for every chunk).

        Wire discipline (the streaming hot path is bound by host->device
        bytes): integer columns ship
        frame-of-reference NARROWED (min-subtracted, downcast per the
        shared tier rule) and decode in ONE jitted dispatch; per-column
        eager device ops would pay a dispatch each. Tiers
        freeze per column from TABLE-level min/max on first use so the
        decode signature — and with it the chunk program's XLA cache
        entry — stays stable across every chunk; a chunk that falls
        outside the frozen frame (data changed under a cached plan)
        falls back to full width for that chunk, trading one recompile
        for correctness."""
        from ..core.column import ColumnBatch, narrow_tier

        s, e = self._chunk
        item = getattr(self, "_staged_item", None)
        stager = getattr(self, "_stager", None)
        if item is not None and stager is not None \
                and item.win == (s, e):
            # decode-on-device path: the wire-encoded chunk is already on
            # device (prefetched); ONE jitted kernel expands it
            return stager.decode_batch(item, cols)
        t = self.catalog[name]
        sub_schema = Schema(
            tuple(f for f in t.schema.fields if f.name in cols)
        )
        cap = self.chunk_rows
        narrow: dict = {}
        bases: dict = {}
        if not hasattr(self, "_narrow_plan"):
            self._narrow_plan: dict = {}

        def tier_of(key, full, storage):
            hit = self._narrow_plan.get(key)
            if hit is None:
                a = np.asarray(full)
                if (np.dtype(storage).kind in "iu" and a.ndim == 1
                        and len(a)):
                    amin = int(a.min())
                    nt = narrow_tier(
                        amin, int(a.max()), np.dtype(storage).itemsize)
                    hit = (nt, amin) if nt is not None else (None, 0)
                else:
                    hit = (None, 0)
                self._narrow_plan[key] = hit
            return hit

        def add(key, a, storage, full):
            a = np.asarray(a, dtype=storage)
            nt, base = tier_of(key, full, storage)
            if cap > len(a):
                # pad INSIDE the frozen frame (dead rows are masked by
                # sel; zeros would fall below a positive table min and
                # force the full-width fallback on every final chunk)
                padv = base if nt is not None else 0
                a = np.concatenate(
                    [a, np.full((cap - len(a),) + a.shape[1:], padv,
                                dtype=a.dtype)])
            if nt is not None:
                d = a.astype(np.int64) - base
                if 0 <= int(d.min()) and int(d.max()) <= np.iinfo(nt).max:
                    narrow[key] = d.astype(nt)
                    bases[key] = a.dtype.type(base)
                    return
            narrow[key] = a
            if not key.startswith("#v:"):
                bases[key] = a.dtype.type(0)

        for f in sub_schema.fields:
            add(f.name, t.data[f.name][s:e], f.dtype.storage_np,
                t.data[f.name])
        for c, v in t.valid.items():
            if c in cols:
                add(f"#v:{c}", np.asarray(v[s:e], np.uint8), np.uint8, v)
        decoded, sel = _decode_chunk(narrow, bases, e - s)
        dcols = {k: v for k, v in decoded.items() if not k.startswith("#v:")}
        dvalid = {k[3:]: v for k, v in decoded.items() if k.startswith("#v:")}
        return ColumnBatch(
            cols=dcols,
            valid=dvalid,
            sel=sel,
            nrows=jnp.sum(sel, dtype=jnp.int64),
            schema=sub_schema,
            dicts={c: d for c, d in t.dicts.items() if c in cols},
        )

    def _est_rows(self, op):
        # the streamed scan sees chunk_rows per execution, not table rows
        if isinstance(op, Scan) and op.table == self.stream_table:
            est = float(self.chunk_rows)
            if op.pushed_filter is not None:
                t = self.catalog[op.table]
                ts = self.stats.table_stats(op.table) if self.stats else None
                if ts is not None and ts.nrows > 0:
                    est *= ts.selectivity(op.pushed_filter, t)
                else:
                    est *= 0.25 ** min(
                        len(self._conjuncts(op.pushed_filter)), 3
                    )
            return max(est, 1.0)
        return super()._est_rows(op)


class _ChunkSourceExecutor(ChunkWindowMixin, Executor):
    """Executor whose streamed table reads one fixed-capacity chunk."""

    supports_staged = True
    chunking_enabled = False
    # chunk windows break the whole-table storage-order premise of the
    # clustered-FK segment aggregation (fk_ranges index full-table rows)
    # and of dynamic-slice range pruning (bounds index full-table rows)
    clustered_agg_enabled = False
    scan_slice_enabled = False

    def __init__(self, catalog, stream_table: str, chunk_rows: int, **kw):
        super().__init__(catalog, **kw)
        self.stream_table = stream_table
        self.chunk_rows = chunk_rows
        self._chunk: tuple[int, int] | None = None

    def table_batch(self, name, cols):
        # the streamed table must NOT ride the per-column device cache
        # (each chunk is a different host slice); every read rebuilds
        # from the current chunk window
        if name == self.stream_table and self._chunk is not None:
            return self._chunk_slice_batch(name, cols)
        return super().table_batch(name, cols)

    def _build_batch(self, name, cols):
        if name != self.stream_table or self._chunk is None:
            return super()._build_batch(name, cols)
        return self._chunk_slice_batch(name, cols)


class ChunkedPreparedPlan(Dispatchable):
    """Drop-in replacement for PreparedPlan when inputs exceed the device
    budget: runs the chunk program per chunk, then the merge plan."""

    def __init__(self, executor: Executor, plan: LogicalOp,
                 stream: Scan, split: LogicalOp, kind: str,
                 chunk_rows: int):
        self.executor = executor
        self.plan = plan
        self.stream = stream
        self.split = split
        self.kind = kind
        self.chunk_rows = chunk_rows
        self.stream_stats = StreamStats()

        if kind == "scan":
            # chunk program = the scan narrowed to the raw columns the
            # plan reads; the rename projection restores the scan's
            # qualified output names for the surrounding plan
            t = executor.catalog[stream.table]
            needed = executor._needed_columns(plan).get(stream.alias) or {
                t.schema.fields[0].name
            }
            chunk_plan = Project(
                stream,
                tuple(
                    (c, E.ColRef(f"{stream.alias}.{c}"))
                    for c in sorted(needed)
                ),
            )
            out_s = output_schema(chunk_plan)
            scan2 = _partials_scan(out_s)
            merge_node = Project(
                scan2,
                tuple(
                    (f"{stream.alias}.{f.name}", E.ColRef(f"$m.{f.name}"))
                    for f in out_s.fields
                ),
            )
            self.above_plan = _replace_node(plan, split, merge_node)
            self.partial_schema = out_s
        else:
            chunk_plan, _scan, merge_node = _merge_plan(split, kind)
            self.above_plan = _replace_node(plan, split, merge_node)
            self.partial_schema = output_schema(split)

        self.chunk_exec = executor.make_chunk_source(
            stream.table, chunk_rows
        )
        self.chunk_prepared = self.chunk_exec.prepare(chunk_plan)

        # persistent merge executor: $partials is swapped per run at a
        # grow-only power-of-two capacity so the merge XLA executable is
        # compiled once and reused (review r2: no re-jit per execution)
        self._overlay_extra: dict = {}
        self.merge_exec = Executor(
            _OverlayCatalog(executor.catalog, self._overlay_extra),
            unique_keys=executor.unique_keys, stats=None,
        )
        self.merge_exec.chunking_enabled = False
        self.merge_exec.fuses_frame = False
        self._partial_cap = 1024
        self._merge_prepared = None
        self._merge_cap = 0

    def dispatch(self, qparams: tuple = (), max_retries: int = 3,
                 fused: bool = True):
        """The chunk loop, then the merge plan's dispatch: the cursor is
        the merge's (engine/executor.PreparedPlan.dispatch)."""
        if getattr(self.chunk_exec, "supports_staged", False):
            # streaming pipeline (engine/pipeline.py): prefetch-staged
            # wire-encoded chunks, decode-on-device, overlap metering
            cols, valids, dicts = run_stream(
                self, qparams=qparams, max_retries=max_retries)
        else:
            cols, valids, dicts = self._run_legacy(max_retries, qparams)
        partials, self._partial_cap = assemble_partials_table(
            self.partial_schema, cols, valids, dicts, self._partial_cap)
        self._overlay_extra["$partials"] = partials
        self.merge_exec.invalidate_table("$partials")
        if self._merge_prepared is None or self._merge_cap != self._partial_cap:
            self._merge_prepared = self.merge_exec.prepare(self.above_plan)
            self._merge_cap = self._partial_cap
        return self._merge_prepared.dispatch(
            qparams, max_retries=max_retries, fused=fused)

    def _run_legacy(self, max_retries: int = 3, qparams: tuple = ()):
        import os
        from collections import deque

        import jax

        t = self.executor.catalog[self.stream.table]
        n = t.nrows or 0
        from ..share.interrupt import checkpoint

        # ---- pipelined chunk loop (double buffering) ------------------
        # Dispatch runs DEPTH chunks ahead of the draining fetch: while
        # the host decodes/accumulates chunk k's partial, the device is
        # already computing k+1 and the wire is carrying k+2's upload —
        # the H2D link and device compute overlap instead of alternating
        # (the pre-PR-1 SF100 streaming record was fully serialized on
        # the wire). Each drain is ONE device_get.
        depth = max(1, int(os.environ.get("OB_STREAM_PIPELINE", "2")))
        if depth > 1 and n:
            # the pipeline holds `depth` chunk slices on device at once;
            # the split's budget math sized ONE chunk — cap depth so the
            # in-flight residency stays inside the device budget (review)
            needed = self.executor._needed_columns(self.plan).get(
                self.stream.alias
            ) or set()
            per_row = max(1, sum(
                self.executor.catalog[self.stream.table].schema[c]
                .storage_np.itemsize
                for c in needed
            )) if needed else 8
            chunk_bytes = per_row * self.chunk_rows
            fit = max(1, int(self.executor.device_budget * 0.5)
                      // max(chunk_bytes, 1))
            depth = max(1, min(depth, fit))
        windows: deque = deque()
        s = 0
        while s < n:
            e = min(s + self.chunk_rows, n)
            windows.append((s, e))
            s = e
        if n == 0:
            windows.append((0, 0))
        pending: deque = deque()  # (s, e, gen, out, ovf_dev)
        attempts_of: dict = {}
        params_gen = 0  # bumps once per recompile (review: two in-flight
        # chunks overflowing the same node must not DOUBLE-bump capacities)
        cols: dict[str, list] = {f.name: [] for f in self.partial_schema.fields}
        valids: dict[str, list] = {}
        dicts = {}

        def dispatch(win):
            ws, we = win
            self.chunk_exec.set_chunk(ws, we)
            out, ovf = self.chunk_prepared.jitted(
                self.chunk_prepared._inputs(), qparams)
            pending.append((ws, we, params_gen, out, ovf))

        while windows or pending:
            checkpoint()  # a killed query stops between chunks
            while windows and len(pending) < depth:
                dispatch(windows.popleft())
            ws, we, gen, out, ovf = pending.popleft()
            fetch_cols = {
                f.name: out.cols[f.name] for f in self.partial_schema.fields
            }
            fetch_valid = {
                k: v for k, v in out.valid.items()
                if k in fetch_cols
            }
            hovf, hcols, hvalid, hsel = jax.device_get(
                (ovf, fetch_cols, fetch_valid, out.sel))
            overflows = self.chunk_prepared._overflows(np.asarray(hovf))
            if overflows:
                if gen == params_gen:
                    # first overflow since the last recompile: bump and
                    # rebuild. Only THIS path consumes a retry attempt —
                    # a sibling chunk dispatched pre-bump re-runs on the
                    # grown capacities for free (its overflow may already
                    # be covered; capacities grow monotonically, so the
                    # loop always progresses)
                    a = attempts_of.get(ws, 0)
                    if a >= max_retries:
                        raise RuntimeError(
                            f"chunk [{ws},{we}) capacity overflow after "
                            f"{max_retries} retries: {overflows}")
                    attempts_of[ws] = a + 1
                    self.retries += 1
                    self.chunk_prepared.retries += 1
                    self.chunk_prepared.params.bump(overflows)
                    (self.chunk_prepared.jitted,
                     self.chunk_prepared.input_spec,
                     self.chunk_prepared.overflow_nodes) = (
                        self.chunk_prepared.executor.compile(
                            self.chunk_prepared.plan,
                            self.chunk_prepared.params))
                    params_gen += 1
                # in-flight chunks used the SMALL capacities: their own
                # counters decide their fate when drained; this chunk
                # re-dispatches at the head of the queue
                windows.appendleft((ws, we))
                continue
            sel = np.asarray(hsel)
            for f in self.partial_schema.fields:
                cols[f.name].append(np.asarray(hcols[f.name])[sel])
                v = hvalid.get(f.name)
                if v is not None:
                    valids.setdefault(f.name, []).append(np.asarray(v)[sel])
                elif f.name in valids:
                    valids[f.name].append(np.ones(int(sel.sum()), np.bool_))
            dicts.update(out.dicts)

        return cols, valids, dicts
