"""Streaming pipeline engine: the out-of-core data path that decouples
data scale from HBM.

Reference surface: the reference engine's DTL-fed pipelined operators and
ObHJPartition (sql/engine/join/hash_join) crossed with Tailwind's "keep
the accelerator saturated" discipline — the device must never idle on the
host<->device wire, and the wire must never carry bytes the storage
encodings already removed.

Three mechanisms, composed by engine/chunked.ChunkedPreparedPlan:

  1. double-buffered H2D prefetch (ChunkPrefetcher): while chunk k's
     program computes, chunk k+1 is already host-encoded and its
     device_put is in flight on a staging thread. The queue depth bounds
     in-flight staged chunks; every staged chunk holds a governor staged
     lease so host-pinned wire buffers are accounted (and provably
     released — the ledger balances even when a statement dies with a
     prefetch in flight).

  2. compressed chunk streaming with decode-on-device (ChunkStager +
     _decode_staged): each streamed column freezes a per-column *wire
     plan* on first chunk — FOR (frame-of-reference at byte width), RLE
     (run values + run lengths at a frozen power-of-two run capacity) or
     raw — chosen by the same cost model the storage encodings use
     (storage/encoding.choose_encoding). The H2D transfer carries the
     encoded form; ONE jitted kernel expands it on device (FOR: widen +
     add base; RLE: cumsum + searchsorted gather; validity: bit-unpack),
     so the wire bytes shrink by the encoding ratio while the device
     program still sees full-width columns. A chunk that falls outside
     its frozen frame (narrow overflow / run-cap overflow) ships raw for
     that chunk — one recompile, never a wrong answer, mirroring the
     _narrow_plan fallback discipline.

  3. grace-hash partitioned join/group-by (GraceHashPreparedPlan): when
     the BUILD side also exceeds the budget (chunked.NotStreamable), both
     sides hash-partition by a join key to host tmp-file segments
     (storage/tmp_file), and ONE static device program — the split
     subtree over fixed-capacity $live-masked overlay tables — streams
     the partition pairs. Partition counts derive from the governor's
     remaining budget. Group-by mode partitions a single table by a
     GROUP BY key, which makes even non-mergeable aggregates (count
     distinct) exactly computable per partition: groups are
     partition-disjoint, so the merge is pure concatenation.

Overlap is measured, not assumed: OverlapMeter does exact interval-union
accounting of h2d-busy vs compute-busy wall time; the fraction surfaces
in __all_virtual_sql_plan_monitor.h2d_overlap_pct, the "stream h2d
overlap" sysstat counter and the serving timeline's per-bucket
h2d_overlap_frac.
"""

from __future__ import annotations

import queue
import threading
import time
from contextlib import contextmanager
from dataclasses import replace as dc_replace
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from ..core.dtypes import DataType, Field, Schema
from ..core.table import Table
from ..expr import ir as E
from ..sql.logical import (
    Aggregate,
    Filter,
    JoinOp,
    Project,
    Scan,
    output_schema,
)
from ..share import gap_ledger as _gap
from ..storage.encoding import ENC_FOR, ENC_RLE, analyze_ints, choose_encoding
from .executor import Dispatchable

# ---------------------------------------------------------------------------
# telemetry


class StreamStats:
    """Cumulative streaming counters carried by a prepared plan; the
    session folds per-run deltas into the plan monitor / sysstat /
    timeline (snapshot-diff, like overflow retries)."""

    __slots__ = ("chunks", "staged_bytes", "decoded_bytes", "h2d_s",
                 "compute_s", "overlap_s", "spill_partitions")

    def __init__(self):
        self.chunks = 0
        self.staged_bytes = 0
        self.decoded_bytes = 0
        self.h2d_s = 0.0
        self.compute_s = 0.0
        self.overlap_s = 0.0
        self.spill_partitions = 0

    @property
    def h2d_overlap_pct(self) -> float:
        return 100.0 * self.overlap_s / self.h2d_s if self.h2d_s else 0.0

    def snapshot(self) -> tuple:
        return (self.chunks, self.staged_bytes, self.decoded_bytes,
                self.h2d_s, self.compute_s, self.overlap_s,
                self.spill_partitions)


class OverlapMeter:
    """Exact interval-union accounting of two activity sides ("h2d" and
    "compute"): on every enter/exit event the elapsed slice since the
    previous event is credited to whichever sides were active — and to
    `overlap_s` when both were. Thread-safe (the prefetch thread meters
    h2d while the consumer meters compute)."""

    def __init__(self, clock=time.perf_counter):
        self._lock = threading.Lock()
        self._clock = clock
        self._active = {"h2d": 0, "compute": 0}
        self._last: float | None = None
        self.h2d_s = 0.0
        self.compute_s = 0.0
        self.overlap_s = 0.0

    def _account(self, now: float) -> None:
        if self._last is not None:
            dt = now - self._last
            if dt > 0:
                h = self._active["h2d"] > 0
                c = self._active["compute"] > 0
                if h:
                    self.h2d_s += dt
                if c:
                    self.compute_s += dt
                if h and c:
                    self.overlap_s += dt
        self._last = now

    def enter(self, side: str) -> None:
        with self._lock:
            self._account(self._clock())
            self._active[side] += 1

    def exit(self, side: str) -> None:
        with self._lock:
            self._account(self._clock())
            self._active[side] = max(0, self._active[side] - 1)

    @contextmanager
    def track(self, side: str):
        self.enter(side)
        try:
            yield
        finally:
            self.exit(side)


# ---------------------------------------------------------------------------
# compressed chunk staging + decode-on-device

# wire-plan entry kinds (per streamed column, frozen on first chunk)
_W_RAW = "raw"      # full storage width, zero base
_W_FOR = "for"      # frame-of-reference: narrow deltas + base
_W_RLE = "rle"      # run values (narrow) + run lengths, frozen run cap
_W_BITS = "bits"    # validity bitmap, packbits little-endian

_NARROW = (np.dtype(np.uint8), np.dtype(np.uint16), np.dtype(np.uint32))


def _narrow_for(span: int) -> np.dtype | None:
    for dt in _NARROW:
        if span <= int(np.iinfo(dt).max):
            return dt
    return None


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


@partial(jax.jit, static_argnames=("meta", "cap"))
def _decode_staged(staged, bases, count, *, meta, cap):
    """ONE dispatch expanding a staged (wire-encoded) chunk to full-width
    device columns + the live-row mask. `meta` is the static wire plan:
    a tuple of (key, kind) pairs; shapes are constant across chunks so
    XLA compiles this exactly once per frozen plan."""
    out = {}
    for k, kind in meta:
        if kind == _W_BITS:
            packed = staged[k]
            idx = jnp.arange(cap, dtype=jnp.int32)
            bits = (packed[idx >> 3] >> (idx & 7).astype(jnp.uint8)) & 1
            out[k] = bits != 0
        elif kind == _W_RLE:
            vals, lens = staged[k]
            b = bases[k]
            ends = jnp.cumsum(lens.astype(jnp.int64))
            idx = jnp.searchsorted(
                ends, jnp.arange(cap, dtype=jnp.int64), side="right")
            idx = jnp.clip(idx, 0, vals.shape[0] - 1)
            out[k] = vals[idx].astype(b.dtype) + b
        else:  # raw / for: widen + add base (base is 0 for raw)
            b = bases[k]
            out[k] = staged[k].astype(b.dtype) + b
    sel = jnp.arange(cap, dtype=jnp.int64) < count
    return out, sel


class ChunkStager:
    """Host-side encoder for one streamed table: freezes a per-column
    wire plan on first chunk (cost model: storage/encoding), then turns
    each [start, end) window into a staged tree of wire-encoded arrays
    whose SHAPES are constant across chunks (the decode kernel compiles
    once). `compress=False` pins every column to the raw/FOR baseline —
    the bench A/B lever."""

    def __init__(self, table: Table, cols, cap: int, compress: bool = True):
        self.table = table
        self.cols = tuple(sorted(set(cols)))
        self.cap = int(cap)
        self.compress = compress
        self.sub_schema = Schema(tuple(
            f for f in table.schema.fields if f.name in self.cols))
        # key -> (_W_*, narrow_dtype|None, base, run_cap) frozen entries
        self._plan: dict[str, tuple] = {}

    # -------------------------------------------------------- wire plan
    def _freeze(self, key: str, full: np.ndarray, storage: np.dtype) -> tuple:
        hit = self._plan.get(key)
        if hit is not None:
            return hit
        a = np.asarray(full)
        entry = (_W_RAW, None, 0, 0)
        if np.dtype(storage).kind in "iu" and a.ndim == 1 and len(a):
            st = analyze_ints(a.astype(np.int64, copy=False))
            span = st.vmax - st.vmin
            nt = _narrow_for(span)
            enc = _W_RAW
            if self.compress:
                e, _p = choose_encoding(a.astype(np.int64, copy=False), st)
                if e == ENC_RLE:
                    enc = _W_RLE
                elif e == ENC_FOR and nt is not None and (
                        nt.itemsize < np.dtype(storage).itemsize):
                    enc = _W_FOR
            elif nt is not None and nt.itemsize < np.dtype(storage).itemsize:
                # baseline keeps the pre-existing FOR narrowing (the wire
                # discipline chunked streaming always had)
                enc = _W_FOR
            if enc == _W_RLE and nt is None:
                enc = _W_RAW
            if enc == _W_RLE:
                # frozen run capacity: 2x the table-wide per-chunk run
                # density (a chunk of cap rows holds ~nruns*cap/n runs),
                # clamped to the chunk capacity itself
                n = max(len(a), 1)
                est = int(st.nruns * self.cap / n) + 1
                run_cap = min(_next_pow2(max(2 * est, 16)), self.cap)
                entry = (_W_RLE, nt, st.vmin, run_cap)
            elif enc == _W_FOR:
                entry = (_W_FOR, nt, st.vmin, 0)
        self._plan[key] = entry
        return entry

    # ---------------------------------------------------------- staging
    def stage(self, s: int, e: int):
        """Encode one window. Returns (staged, bases, meta, wire_bytes,
        decoded_bytes): `staged` is the host tree to device_put, `meta`
        the static decode plan for THIS chunk (normally the frozen plan;
        a frame-violating chunk degrades its column to raw)."""
        t = self.table
        cap = self.cap
        staged: dict = {}
        bases: dict = {}
        meta: list[tuple[str, str]] = []
        decoded = 0

        def add_raw(key, a, storage):
            pad = cap - len(a)
            if pad:
                a = np.concatenate(
                    [a, np.zeros((pad,) + a.shape[1:], dtype=a.dtype)])
            staged[key] = np.ascontiguousarray(a, dtype=storage)
            bases[key] = np.dtype(storage).type(0)
            meta.append((key, _W_RAW))

        def add(key, full, storage):
            nonlocal decoded
            a = np.asarray(full[s:e], dtype=storage)
            decoded += cap * np.dtype(storage).itemsize
            kind, nt, base, run_cap = self._freeze(key, full, storage)
            if kind == _W_RLE:
                starts = np.flatnonzero(
                    np.concatenate(([True], a[1:] != a[:-1]))
                ) if len(a) else np.zeros(0, np.int64)
                nruns = len(starts)
                if 0 < nruns <= run_cap:
                    vals = a[starts].astype(np.int64) - base
                    if int(vals.min()) >= 0 and int(vals.max()) <= int(
                            np.iinfo(nt).max):
                        lens = np.diff(
                            np.concatenate((starts, [len(a)]))
                        ).astype(np.int32)
                        vpad = np.zeros(run_cap - nruns, dtype=nt)
                        lpad = np.zeros(run_cap - nruns, dtype=np.int32)
                        staged[key] = (
                            np.concatenate([vals.astype(nt), vpad]),
                            np.concatenate([lens, lpad]),
                        )
                        bases[key] = np.dtype(storage).type(base)
                        meta.append((key, _W_RLE))
                        return
                # run blow-up / frame violation: this chunk ships wide
                add_raw(key, a, storage)
                return
            if kind == _W_FOR:
                d = a.astype(np.int64) - base
                if len(d) == 0 or (int(d.min()) >= 0 and int(d.max())
                                   <= int(np.iinfo(nt).max)):
                    d = d.astype(nt)
                    pad = cap - len(d)
                    if pad:
                        # pad INSIDE the frame (zero delta = table min)
                        d = np.concatenate([d, np.zeros(pad, dtype=nt)])
                    staged[key] = d
                    bases[key] = np.dtype(storage).type(base)
                    meta.append((key, _W_FOR))
                    return
                add_raw(key, a, storage)
                return
            add_raw(key, a, storage)

        for f in self.sub_schema.fields:
            add(f.name, t.data[f.name], f.dtype.storage_np)
        for c, v in t.valid.items():
            if c in self.cols:
                decoded += cap
                bits = np.packbits(
                    np.asarray(v[s:e], np.bool_), bitorder="little")
                nbytes = (cap + 7) >> 3
                if len(bits) < nbytes:
                    # pad rows read as INVALID; sel masks them anyway
                    bits = np.concatenate(
                        [bits, np.zeros(nbytes - len(bits), np.uint8)])
                staged[f"#v:{c}"] = bits
                meta.append((f"#v:{c}", _W_BITS))

        wire = sum(
            (a[0].nbytes + a[1].nbytes) if isinstance(a, tuple) else a.nbytes
            for a in staged.values())
        return staged, bases, tuple(sorted(meta)), wire, decoded

    def decode_batch(self, item: "StagedChunk", cols=None):
        """Decoded-on-device ColumnBatch for a staged chunk (the chunk
        executor's table read for the streamed table). `cols` narrows
        the batch to a requested subset (must be ⊆ the staged set)."""
        from ..core.column import ColumnBatch

        want = self.cols if cols is None else tuple(sorted(set(cols)))
        decoded, sel = _decode_staged(
            item.staged, item.bases, item.count,
            meta=item.meta, cap=self.cap)
        dcols = {k: v for k, v in decoded.items()
                 if not k.startswith("#v:") and k in want}
        dvalid = {k[3:]: v for k, v in decoded.items()
                  if k.startswith("#v:") and k[3:] in want}
        t = self.table
        schema = self.sub_schema if want == self.cols else Schema(tuple(
            f for f in t.schema.fields if f.name in want))
        return ColumnBatch(
            cols=dcols,
            valid=dvalid,
            sel=sel,
            nrows=jnp.sum(sel, dtype=jnp.int64),
            schema=schema,
            dicts={c: d for c, d in t.dicts.items() if c in want},
        )


class StagedChunk:
    """One wire-encoded chunk, device_put in flight: the prefetcher's
    unit of work. Holds the governor staged lease for its host-pinned
    wire buffers; release is idempotent and always reached (drain path
    or prefetcher close)."""

    __slots__ = ("win", "staged", "bases", "meta", "count", "wire_bytes",
                 "decoded_bytes", "lease")

    def __init__(self, win, staged, bases, meta, count, wire_bytes,
                 decoded_bytes, lease):
        self.win = win
        self.staged = staged
        self.bases = bases
        self.meta = meta
        self.count = count
        self.wire_bytes = wire_bytes
        self.decoded_bytes = decoded_bytes
        self.lease = lease

    def release(self) -> None:
        if self.lease is not None:
            self.lease.release()


class ChunkPrefetcher:
    """Stages chunk windows `depth` ahead of the consumer on a small
    thread: host encode + jax.device_put + block_until_ready (the H2D
    side of the overlap meter runs HERE, concurrent with the consumer's
    compute side). The bounded queue is the backpressure: at most
    `depth` staged chunks are in flight, each holding a governor staged
    lease. close() drains and releases everything — the ledger balances
    even when the consumer dies mid-stream."""

    _SENTINEL = object()

    def __init__(self, stager: ChunkStager, windows, depth: int,
                 meter: OverlapMeter, governor=None, tenant: str = "sys"):
        self.stager = stager
        self.windows = list(windows)
        self.depth = max(1, int(depth))
        self.meter = meter
        self.governor = governor
        self.tenant = tenant
        self._q: queue.Queue = queue.Queue(maxsize=self.depth)
        self._closed = threading.Event()
        self._exc: BaseException | None = None
        self._thread = threading.Thread(
            target=self._run, name="ob-stream-prefetch", daemon=True)
        self._thread.start()

    def _stage_one(self, win) -> StagedChunk:
        s, e = win
        staged, bases, meta, wire, dec = self.stager.stage(s, e)
        lease = None
        if self.governor is not None:
            lease = self.governor.stage(self.tenant, wire)
        try:
            with self.meter.track("h2d"):
                staged = jax.device_put(staged)
                jax.block_until_ready(staged)
        except BaseException:
            if lease is not None:
                lease.release()
            raise
        return StagedChunk(win, staged, bases, meta, e - s, wire, dec, lease)

    def _run(self) -> None:
        try:
            for win in self.windows:
                if self._closed.is_set():
                    return
                item = self._stage_one(win)
                while not self._closed.is_set():
                    try:
                        self._q.put(item, timeout=0.05)
                        break
                    except queue.Full:
                        continue
                else:
                    item.release()
                    return
        except BaseException as exc:  # surfaced at the consumer's get()
            self._exc = exc
        finally:
            while True:
                try:
                    self._q.put(self._SENTINEL, timeout=0.05)
                    break
                except queue.Full:
                    if self._closed.is_set():
                        break

    def get(self) -> StagedChunk | None:
        """Next staged chunk, or None when the stream is exhausted.
        Re-raises a staging error on the consumer thread."""
        while True:
            try:
                item = self._q.get(timeout=0.05)
            except queue.Empty:
                if self._exc is not None and self._q.empty():
                    raise self._exc
                continue
            if item is self._SENTINEL:
                if self._exc is not None:
                    raise self._exc
                return None
            return item

    def restage(self, win) -> StagedChunk:
        """Synchronous re-stage for the rare overflow redispatch path
        (the forward pipeline stays one-directional)."""
        return self._stage_one(win)

    def close(self) -> None:
        """Stop the thread and release every undelivered staged lease.
        Idempotent; called from the consumer's finally so a statement
        error/timeout cannot leak staged bytes."""
        self._closed.set()
        while True:
            try:
                item = self._q.get_nowait()
            except queue.Empty:
                break
            if item is not self._SENTINEL:
                item.release()
        self._thread.join(timeout=5.0)
        # anything the thread pushed between drain and join
        while True:
            try:
                item = self._q.get_nowait()
            except queue.Empty:
                break
            if item is not self._SENTINEL:
                item.release()


# ---------------------------------------------------------------------------
# sizing helpers


def decoded_row_bytes(catalog, table: str, cols) -> int:
    """Per-row DECODED (on-device) bytes of the streamed columns — what
    chunk sizing must budget for. The staged (compressed) host bytes are
    charged separately through the governor's staged ledger; sizing from
    wire bytes would let a high-ratio RLE column overcommit HBM by its
    encoding ratio."""
    t = catalog[table]
    per = 0
    for c in cols:
        if c in t.schema:
            per += t.schema[c].storage_np.itemsize
        if c in t.valid:
            per += 1
    return max(per, 1)


def assemble_partials_table(partial_schema: Schema, cols, valids, dicts,
                            cap: int):
    """Concatenate per-chunk/per-partition partial outputs into the
    padded $partials overlay Table at a grow-only power-of-two capacity
    (the merge executable's input shape — stable across runs). Returns
    (table, new_cap)."""
    data = {k: np.concatenate(v) for k, v in cols.items()}
    vdata = {k: np.concatenate(v) for k, v in valids.items()}
    n_part = len(next(iter(data.values()))) if data else 0
    while cap < n_part:
        cap *= 2
    pad = cap - n_part
    if pad:
        data = {
            k: np.concatenate([v, np.zeros(pad, dtype=v.dtype)])
            for k, v in data.items()
        }
        vdata = {
            k: np.concatenate([v, np.zeros(pad, dtype=np.bool_)])
            for k, v in vdata.items()
        }
    data["$live"] = np.concatenate(
        [np.ones(n_part, np.int8), np.zeros(pad, np.int8)]
    )
    part_fields = [
        Field(f.name,
              f.dtype.with_nullable(f.dtype.nullable or f.name in vdata))
        for f in partial_schema.fields
    ]
    part_fields.append(Field("$live", DataType.int8()))
    table = Table(
        "$partials", Schema(tuple(part_fields)), data,
        {k: d for k, d in dicts.items() if k in data},
        valid=vdata,
    )
    return table, cap


# ---------------------------------------------------------------------------
# the pipelined chunk loop


def run_stream(cp, qparams: tuple = (), max_retries: int = 3):
    """The streaming chunk loop of ChunkedPreparedPlan for single-chip
    chunk sources: prefetch-staged compressed chunks, decode on device,
    dispatch `depth` ahead of the draining fetch, fold partials.

    Returns (cols, valids, dicts) accumulators for the $partials
    assembly. Overflow keeps the params-generation discipline of the
    legacy loop: one bump+recompile per generation, in-flight siblings
    re-dispatch for free on the grown capacities."""
    from collections import deque

    from ..share.interrupt import checkpoint

    ex = cp.executor
    t = ex.catalog[cp.stream.table]
    n = t.nrows or 0
    stats = cp.stream_stats
    meter = OverlapMeter()

    depth = max(0, int(getattr(ex, "stream_prefetch_depth", 2)))
    compress = bool(getattr(ex, "stream_compress", True))
    governor = getattr(ex, "governor", None)
    tenant = getattr(ex, "tenant", "sys")

    windows: deque = deque()
    s = 0
    while s < n:
        e = min(s + cp.chunk_rows, n)
        windows.append((s, e))
        s = e
    if n == 0:
        windows.append((0, 0))

    # the streamed table's columns per the compiled chunk program
    stream_cols: tuple = ()
    for _alias, tname, tcols in cp.chunk_prepared.input_spec:
        if tname == cp.stream.table:
            stream_cols = tcols
            break
    stager = ChunkStager(t, stream_cols, cp.chunk_rows, compress=compress)
    cp.chunk_exec.set_stager(stager)

    # in-flight device residency: decoded chunk + staged wire buffers per
    # pipeline slot; cap the dispatch depth inside the device budget
    # exactly like the legacy loop did for its two slots
    row_b = decoded_row_bytes(ex.catalog, cp.stream.table, stream_cols)
    chunk_bytes = row_b * cp.chunk_rows
    fit = max(1, int(ex.device_budget * 0.5) // max(chunk_bytes, 1))
    dispatch_depth = max(1, min(max(depth, 1) + 1, fit))

    prefetch = ChunkPrefetcher(
        stager, list(windows), depth, meter, governor=governor,
        tenant=tenant) if depth > 0 else None

    pending: deque = deque()  # (item, gen, out, ovf)
    redispatch: deque = deque()  # overflow re-runs (StagedChunk)
    attempts_of: dict = {}
    params_gen = 0
    cols: dict[str, list] = {f.name: [] for f in cp.partial_schema.fields}
    valids: dict[str, list] = {}
    dicts: dict = {}
    drained = 0
    total = len(windows)

    def dispatch(item: StagedChunk):
        ws, we = item.win
        cp.chunk_exec.set_chunk_staged(ws, we, item)
        try:
            with meter.track("compute"):
                out, ovf = cp.chunk_prepared.jitted(
                    cp.chunk_prepared._inputs(), qparams)
        except BaseException:
            # a failed dispatch is the item's last owner: release here or
            # the staged ledger leaks on statement error
            item.release()
            raise
        pending.append((item, params_gen, out, ovf))

    try:
        while drained < total:
            checkpoint()  # a killed query stops between chunks
            while redispatch and len(pending) < dispatch_depth:
                dispatch(redispatch.popleft())
            while (prefetch is not None and len(pending) < dispatch_depth
                   and drained + len(pending) + len(redispatch) < total):
                item = prefetch.get()
                if item is None:
                    break
                windows.popleft()
                dispatch(item)
            if prefetch is None and not pending and windows:
                # prefetch off (A/B baseline): stage synchronously — the
                # wire and the device strictly alternate
                win = windows.popleft()
                ws, we = win
                staged, bases, meta, wire, dec = stager.stage(ws, we)
                lease = governor.stage(tenant, wire) \
                    if governor is not None else None
                try:
                    with meter.track("h2d"):
                        staged = jax.device_put(staged)
                        jax.block_until_ready(staged)
                except BaseException:
                    if lease is not None:
                        lease.release()
                    raise
                dispatch(StagedChunk(win, staged, bases, meta, we - ws,
                                     wire, dec, lease))
            if not pending:
                continue
            item, gen, out, ovf = pending.popleft()
            try:
                fetch_cols = {
                    f.name: out.cols[f.name]
                    for f in cp.partial_schema.fields
                }
                fetch_valid = {
                    k: v for k, v in out.valid.items() if k in fetch_cols
                }
                with meter.track("compute"):
                    hovf, hcols, hvalid, hsel = jax.device_get(
                        (ovf, fetch_cols, fetch_valid, out.sel))
            except BaseException:
                # popped from pending → the finally can no longer see it
                item.release()
                raise
            overflows = cp.chunk_prepared._overflows(np.asarray(hovf))
            if overflows:
                ws, we = item.win
                if gen == params_gen:
                    a = attempts_of.get(ws, 0)
                    if a >= max_retries:
                        raise RuntimeError(
                            f"chunk [{ws},{we}) capacity overflow after "
                            f"{max_retries} retries: {overflows}")
                    attempts_of[ws] = a + 1
                    cp.retries += 1
                    cp.chunk_prepared.retries += 1
                    cp.chunk_prepared.params.bump(overflows)
                    (cp.chunk_prepared.jitted,
                     cp.chunk_prepared.input_spec,
                     cp.chunk_prepared.overflow_nodes) = (
                        cp.chunk_prepared.executor.compile(
                            cp.chunk_prepared.plan,
                            cp.chunk_prepared.params))
                    params_gen += 1
                redispatch.appendleft(item)
                continue
            item.release()
            stats.chunks += 1
            stats.staged_bytes += item.wire_bytes
            stats.decoded_bytes += item.decoded_bytes
            drained += 1
            sel = np.asarray(hsel)
            for f in cp.partial_schema.fields:
                cols[f.name].append(np.asarray(hcols[f.name])[sel])
                v = hvalid.get(f.name)
                if v is not None:
                    valids.setdefault(f.name, []).append(np.asarray(v)[sel])
                elif f.name in valids:
                    valids[f.name].append(
                        np.ones(int(sel.sum()), np.bool_))
            dicts.update(out.dicts)
    finally:
        if prefetch is not None:
            prefetch.close()
        for item, _gen, _out, _ovf in pending:
            item.release()
        for item in redispatch:
            item.release()
        cp.chunk_exec.set_stager(None)
        stats.h2d_s += meter.h2d_s
        stats.compute_s += meter.compute_s
        stats.overlap_s += meter.overlap_s
        # host-tax ledger: a streamed plan's per-chunk walls would
        # otherwise vanish inside the statement's dispatch span — hint
        # the non-overlapped H2D as wall and the chunk compute as device
        # busy onto the current statement's ledger (the window clamp in
        # the serving layer keeps these inside the dispatch wall)
        led = _gap.current()
        if led is not None:
            led.add("h2d", max(0.0, meter.h2d_s - meter.overlap_s))
            led.device(meter.compute_s)

    return cols, valids, dicts


# ---------------------------------------------------------------------------
# grace-hash partitioned join / group-by


class NotPartitionable(Exception):
    """The plan shape does not admit grace-hash partitioning (caller
    falls through to whole-table upload, same contract as
    chunked.NotStreamable)."""


def _path_to_scan(plan, scan):
    path = []

    def find(op) -> bool:
        from .executor import _children

        path.append(op)
        if op is scan:
            return True
        for c in _children(op):
            if find(c):
                return True
        path.pop()
        return False

    if not find(plan):
        raise NotPartitionable("scan not reachable")
    return path


def _streams_down(path, from_pos: int) -> bool:
    """Filter/Project-only (plus probe-side joins) below path[from_pos]."""
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


def _resolve_base_col(path_tail, name: str) -> str | None:
    """Trace a column name down a Filter/Project chain to its base-table
    column (None when any hop is a computed expression). `path_tail`
    runs from the chain's top node down to the Scan."""
    cur = name
    for node in path_tail:
        if isinstance(node, Project):
            hit = None
            for out_name, expr in node.exprs:
                if out_name == cur:
                    hit = expr
                    break
            if not isinstance(hit, E.ColRef):
                return None
            cur = hit.name
        elif isinstance(node, Filter):
            continue
        elif isinstance(node, Scan):
            a, _, c = cur.partition(".")
            return c if a == node.alias and c else None
        else:
            return None
    return None


def _live_scan(scan: Scan, overlay_name: str, cols) -> Scan:
    """The scan rewritten onto its overlay partition table: same alias,
    schema narrowed to the partitioned columns plus a `$live` guard whose
    pushed predicate masks the pad rows (one static program serves every
    partition)."""
    live = E.Compare("=", E.ColRef(f"{scan.alias}.$live"), E.lit(1))
    pushed = live if scan.pushed_filter is None else E.BoolOp(
        "and", (scan.pushed_filter, live))
    fields = tuple(
        f for f in scan.schema.fields
        if f.name.split(".", 1)[1] in cols
    ) + (Field(f"{scan.alias}.$live", DataType.int8()),)
    return dc_replace(
        scan, table=overlay_name, schema=Schema(fields),
        pushed_filter=pushed, needed=None)


def _hash_partition(n_parts: int, key: np.ndarray) -> np.ndarray:
    h = (key.astype(np.uint64, copy=False)
         * np.uint64(0x9E3779B97F4A7C15)) >> np.uint64(33)
    return (h % np.uint64(n_parts)).astype(np.int64)


def _spill_partitions(tmp, table: Table, cols, key_col: str,
                      n_parts: int):
    """Hash-partition the needed columns (+ validity) of one table into
    per-partition tmp-file segments (the host 'spill tier'). Returns
    (segments per partition, max partition rows)."""
    key = np.asarray(table.data[key_col]).astype(np.int64, copy=False)
    part = _hash_partition(n_parts, key)
    segs: list[list[str]] = [[] for _ in range(n_parts)]
    names = [c for c in cols if c in table.schema]
    max_rows = 0
    for p in range(n_parts):
        m = part == p
        rows = int(m.sum())
        max_rows = max(max_rows, rows)
        if not rows:
            continue
        seg = {c: np.asarray(table.data[c])[m] for c in names}
        for c, v in table.valid.items():
            if c in cols:
                seg[f"#v:{c}"] = np.asarray(v, np.bool_)[m]
        segs[p].append(tmp.write_segment(seg))
    return segs, max_rows


def derive_partition_count(total_bytes: int, budget: int,
                           governor=None) -> int:
    """Power-of-two partition count sized so one partition PAIR fits
    comfortably on device: target ~budget/4 per partition (two sides +
    decode headroom), clamped to [2, 256]. The governor's remaining
    budget — what is actually free right now — tightens the target."""
    avail = max(int(budget), 1)
    if governor is not None:
        rem = governor.remaining()
        if rem > 0:
            avail = min(avail, rem)
    target = max(avail // 4, 1 << 16)
    p = _next_pow2(max(2, -(-int(total_bytes) // target)))
    return min(p, 256)


class GraceHashPreparedPlan(Dispatchable):
    """Out-of-core execution when chunk streaming is NOT enough: the
    build side of a join (or the whole input of a keyed group-by) also
    exceeds the budget. Each grace input hash-partitions by its
    join/group key into host tmp-file segments; ONE static device
    program — the split subtree over fixed-capacity $live-masked overlay
    tables — runs per partition (pair); partials merge through the same
    $partials machinery as chunk streaming.

    mode "join":    partials re-aggregate / pass through exactly as
                    chunked partials do (a group may span partitions).
    mode "groupby": partitioning ON a group key makes groups partition-
                    disjoint, so ANY aggregate — including count
                    distinct — is exact per partition and the merge is
                    pure concatenation.
    """

    def __init__(self, executor, plan, split, kind: str, mode: str,
                 scans: dict[str, tuple[Scan, str, frozenset]],
                 n_parts: int):
        # scans: alias -> (scan node, partition-key base column,
        #                  needed base columns)
        from .chunked import (_merge_plan, _partials_scan, _replace_node,
                              _OverlayCatalog)
        from .executor import Executor

        self.executor = executor
        self.plan = plan
        self.split = split
        self.kind = kind
        self.mode = mode
        self.n_parts = n_parts
        self.stream_stats = StreamStats()
        self._scans = scans

        if mode == "groupby":
            # per-partition output is FINAL for its groups: the merge is
            # the rename projection (passthrough shape) regardless of
            # what the aggregate computes
            out_s = output_schema(split)
            pscan = _partials_scan(out_s)
            merge_node = Project(
                pscan,
                tuple((f.name, E.ColRef(f"$m.{f.name}"))
                      for f in out_s.fields),
            )
            part_plan = split
            self.above_plan = _replace_node(plan, split, merge_node)
            self.partial_schema = out_s
        else:
            part_plan, _scan, merge_node = _merge_plan(split, kind)
            self.above_plan = _replace_node(plan, split, merge_node)
            self.partial_schema = output_schema(split)

        # rewrite every partitioned scan onto its overlay table
        self._overlay_names = {}
        for alias, (scan, _key, cols) in scans.items():
            oname = f"$gh_{alias}"
            self._overlay_names[alias] = oname
            part_plan = _replace_node(
                part_plan, scan, _live_scan(scan, oname, cols))
        self.part_plan = part_plan

        # per-partition executor over the overlay catalog: chunking off
        # (partitions are already bounded), whole-table premises off
        # (partition rows are permuted slices)
        self._overlay_extra: dict = {}
        self.part_exec = Executor(
            _OverlayCatalog(executor.catalog, self._overlay_extra),
            unique_keys={}, stats=None,
        )
        self.part_exec.chunking_enabled = False
        self.part_exec.clustered_agg_enabled = False
        self.part_exec.scan_slice_enabled = False
        self._part_prepared = None
        self._out_dicts: dict = {}

        self.merge_exec = Executor(
            _OverlayCatalog(executor.catalog, self._overlay_extra),
            unique_keys=executor.unique_keys, stats=None,
        )
        self.merge_exec.chunking_enabled = False
        self.merge_exec.fuses_frame = False
        self._partial_cap = 1024
        self._merge_prepared = None
        self._merge_cap = 0

    # ------------------------------------------------------------- run
    def _overlay_for(self, alias: str, scan: Scan, cols, segs, tmp,
                     cap: int) -> Table:
        """One partition of one grace input as a padded overlay Table."""
        t = self.executor.catalog[scan.table]
        names = [c for c in sorted(cols) if c in t.schema]
        parts = [tmp.read_segment(path) for path in segs]
        if parts:
            data = {c: np.concatenate([p[c] for p in parts])
                    for c in names}
            vdata = {
                c: np.concatenate([p[f"#v:{c}"] for p in parts])
                for c in t.valid if c in cols
            }
        else:
            data = {c: np.zeros(0, dtype=t.schema[c].storage_np)
                    for c in names}
            vdata = {c: np.zeros(0, np.bool_)
                     for c in t.valid if c in cols}
        n = len(next(iter(data.values()))) if data else 0
        pad = cap - n
        if pad:
            data = {
                c: np.concatenate([v, np.zeros(pad, dtype=v.dtype)])
                for c, v in data.items()
            }
            vdata = {
                c: np.concatenate([v, np.zeros(pad, np.bool_)])
                for c, v in vdata.items()
            }
        data["$live"] = np.concatenate(
            [np.ones(n, np.int8), np.zeros(pad, np.int8)])
        fields = [f for f in t.schema.fields if f.name in data]
        fields.append(Field("$live", DataType.int8()))
        return Table(
            self._overlay_names[alias], Schema(tuple(fields)), data,
            {c: d for c, d in t.dicts.items() if c in data}, valid=vdata,
        )

    def dispatch(self, qparams: tuple = (), max_retries: int = 3,
                 fused: bool = True):
        """Partition, run every partition (pair), then the merge plan's
        dispatch: the cursor is the merge's."""
        from ..share.interrupt import checkpoint
        from ..storage.tmp_file import TmpFileManager

        stats = self.stream_stats
        cols: dict[str, list] = {
            f.name: [] for f in self.partial_schema.fields}
        valids: dict[str, list] = {}
        with TmpFileManager(
                tenant=getattr(self.executor, "tenant", "sys"),
                metrics=getattr(self.executor, "metrics", None)) as tmp:
            # phase 1: co-partition every grace input by its key column;
            # the fixed per-input capacity (max partition, pow2) is what
            # lets ONE compiled program serve all partitions
            segs: dict[str, list[list[str]]] = {}
            caps: dict[str, int] = {}
            for alias, (scan, key_col, pcols) in self._scans.items():
                t = self.executor.catalog[scan.table]
                segs[alias], mx = _spill_partitions(
                    tmp, t, pcols, key_col, self.n_parts)
                caps[alias] = _next_pow2(max(mx, 16))
                checkpoint()
            stats.spill_partitions += self.n_parts

            # phase 2: one static program over every partition (pair)
            for p in range(self.n_parts):
                checkpoint()
                for alias, (scan, _k, pcols) in self._scans.items():
                    oname = self._overlay_names[alias]
                    self._overlay_extra[oname] = self._overlay_for(
                        alias, scan, pcols, segs[alias][p], tmp,
                        caps[alias])
                    self.part_exec.invalidate_table(oname)
                if self._part_prepared is None:
                    self._part_prepared = self.part_exec.prepare(
                        self.part_plan)
                hcols, hvalid, hsel = self._run_partition(
                    max_retries, qparams)
                sel = np.asarray(hsel)
                for f in self.partial_schema.fields:
                    cols[f.name].append(np.asarray(hcols[f.name])[sel])
                    v = hvalid.get(f.name)
                    if v is not None:
                        valids.setdefault(f.name, []).append(
                            np.asarray(v)[sel])
                    elif f.name in valids:
                        valids[f.name].append(
                            np.ones(int(sel.sum()), np.bool_))
                for alias in segs:
                    for path in segs[alias][p]:
                        tmp.free_segment(path)

        partials, self._partial_cap = assemble_partials_table(
            self.partial_schema, cols, valids, dict(self._out_dicts),
            self._partial_cap)
        self._overlay_extra["$partials"] = partials
        self.merge_exec.invalidate_table("$partials")
        if self._merge_prepared is None or \
                self._merge_cap != self._partial_cap:
            self._merge_prepared = self.merge_exec.prepare(self.above_plan)
            self._merge_cap = self._partial_cap
        return self._merge_prepared.dispatch(
            qparams, max_retries=max_retries, fused=fused)

    def _run_partition(self, max_retries: int, qparams: tuple):
        prepared = self._part_prepared
        for attempt in range(max_retries + 1):
            out, ovf_vec = prepared.jit_call(prepared._inputs(), qparams)
            fetch_cols = {
                f.name: out.cols[f.name]
                for f in self.partial_schema.fields
            }
            fetch_valid = {
                k: v for k, v in out.valid.items() if k in fetch_cols
            }
            hovf, hcols, hvalid, hsel = jax.device_get(
                (ovf_vec, fetch_cols, fetch_valid, out.sel))
            overflows = prepared._overflows(np.asarray(hovf))
            if not overflows:
                self._out_dicts.update(out.dicts)
                return hcols, hvalid, hsel
            if attempt == max_retries:
                raise RuntimeError(
                    f"grace partition overflow after {max_retries} "
                    f"retries: {overflows}")
            self.retries += 1
            prepared.retries += 1
            prepared.params.bump(overflows)
            prepared.recompile()
        raise AssertionError


def try_grace_hash(executor, plan, budget: int):
    """Entry hook from Executor.prepare's `except NotStreamable` branch:
    find a grace-hash-partitionable shape or raise NotPartitionable.

    join mode:    the two biggest scans both exceed the budget, they meet
                  at a JoinOp whose probe path streams and whose build
                  chain is Filter/Project-only, and one equi-key pair
                  resolves to base integer columns on both sides.
    groupby mode: one over-budget input under a keyed Aggregate whose
                  path streams and one group key resolves to a base
                  integer column (then ANY aggregate — incl. distinct —
                  is exact per partition).
    """
    from .chunked import _MERGE_FN, _row_bytes, scan_bytes

    needed = executor._needed_columns(plan)
    scans = executor._collect_scans(plan)
    if not scans:
        raise NotPartitionable("no scans")
    sizes = sorted(
        ((scan_bytes(executor.catalog, s, needed), s) for s in scans),
        key=lambda p: -p[0])

    def single_scan(s: Scan):
        if sum(1 for x in scans if x.table == s.table) > 1:
            raise NotPartitionable(
                "partitioned table scanned more than once")

    def needed_cols(s: Scan, key_col: str) -> frozenset:
        t = executor.catalog[s.table]
        base = needed.get(s.alias) or {t.schema.fields[0].name}
        return frozenset(set(base) | {key_col})

    big_bytes, big = sizes[0]
    single_scan(big)
    path = _path_to_scan(plan, big)
    gov = getattr(executor, "governor", None)

    def lowest(pred):
        best = None
        for i, node in enumerate(path):
            if pred(node):
                best = i
        return best

    # ---- join mode: second scan also over budget --------------------
    if len(sizes) > 1 and sizes[1][0] > budget:
        build_bytes, build = sizes[1]
        single_scan(build)
        if sum(b for b, _ in sizes[2:]) > budget:
            raise NotPartitionable("three or more over-budget inputs")
        # the JoinOp on the probe path whose RIGHT subtree holds `build`
        join_i = None
        for i, node in enumerate(path):
            if isinstance(node, JoinOp) and path[i + 1] is node.left:
                if any(sc is build
                       for sc in executor._collect_scans(node.right)):
                    join_i = i
                    break
        if join_i is None:
            raise NotPartitionable(
                "no probe-side join over the build scan")
        join = path[join_i]
        if join.kind not in ("inner", "left", "semi", "anti"):
            raise NotPartitionable(f"{join.kind} join not partitionable")
        build_path = _path_to_scan(join.right, build)
        if not all(isinstance(nd, (Filter, Project, Scan))
                   for nd in build_path):
            raise NotPartitionable("build chain not Filter/Project-only")
        # an equi-key pair resolving to base integer columns both sides
        probe_col = build_col = None
        for lk, rk in zip(join.left_keys, join.right_keys):
            if not (isinstance(lk, E.ColRef) and isinstance(rk, E.ColRef)):
                continue
            pc = _resolve_base_col(path[join_i + 1:], lk.name)
            bc = _resolve_base_col(build_path, rk.name)
            if pc is None or bc is None:
                continue
            t1 = executor.catalog[big.table]
            t2 = executor.catalog[build.table]
            if pc in t1.schema and bc in t2.schema \
                    and t1.schema[pc].storage_np.kind in "iu" \
                    and t2.schema[bc].storage_np.kind in "iu":
                probe_col, build_col = pc, bc
                break
        if probe_col is None:
            raise NotPartitionable("no base-resolvable equi-key pair")
        # the split above the join: lowest mergeable aggregate, else the
        # join itself as a passthrough split (budget-guarded partials)
        split_i = kind = None
        i = lowest(lambda nd: isinstance(nd, Aggregate))
        if i is not None and i < join_i and _streams_down(path, i) \
                and not path[i].grouping_sets and all(
                    not d and fn in _MERGE_FN
                    for _nm, fn, _a, d in path[i].aggs):
            split_i, kind = i, "agg"
        if split_i is None:
            if not _streams_down(path, join_i):
                raise NotPartitionable(
                    "no mergeable split above the join")
            est = executor._est_rows(join)
            if est * _row_bytes(output_schema(join)) > budget:
                raise NotPartitionable(
                    "passthrough partials exceed budget")
            split_i, kind = join_i, "passthrough"
        split = path[split_i]
        n_parts = derive_partition_count(
            big_bytes + build_bytes, budget, gov)
        return GraceHashPreparedPlan(
            executor, plan, split, kind, "join",
            {big.alias: (big, probe_col,
                         needed_cols(big, probe_col)),
             build.alias: (build, build_col,
                           needed_cols(build, build_col))},
            n_parts)

    # ---- groupby mode: one big input, keyed aggregate ---------------
    if sum(b for b, _ in sizes[1:]) > budget:
        raise NotPartitionable("multiple over-budget inputs, no join")
    i = lowest(lambda nd: isinstance(nd, Aggregate))
    if i is None or not path[i].group_keys or not _streams_down(path, i):
        raise NotPartitionable("no keyed aggregate over the big scan")
    agg = path[i]
    if agg.grouping_sets is not None:
        raise NotPartitionable("grouping sets span partitions")
    key_col = None
    for _name, e in agg.group_keys:
        if not isinstance(e, E.ColRef):
            continue
        c = _resolve_base_col(path[i + 1:], e.name)
        if c is None:
            continue
        t = executor.catalog[big.table]
        if c in t.schema and t.schema[c].storage_np.kind in "iu":
            key_col = c
            break
    if key_col is None:
        raise NotPartitionable("no base-resolvable group key")
    n_parts = derive_partition_count(big_bytes, budget, gov)
    return GraceHashPreparedPlan(
        executor, plan, agg, "agg", "groupby",
        {big.alias: (big, key_col, needed_cols(big, key_col))}, n_parts)


__all__ = [
    "StreamStats", "OverlapMeter", "ChunkStager", "StagedChunk",
    "ChunkPrefetcher", "run_stream", "decoded_row_bytes",
    "assemble_partials_table", "GraceHashPreparedPlan", "try_grace_hash",
    "NotPartitionable", "derive_partition_count",
]
