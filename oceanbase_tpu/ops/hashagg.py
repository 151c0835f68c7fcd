"""Vectorized group-by aggregation kernels.

Reference surface: ObHashGroupByVecOp (sql/engine/aggregate) + the new
aggregate framework (src/share/aggregate/agg_ctx.h) and its adaptive bypass
for low-NDV keys (ob_adaptive_bypass_ctrl.h). The TPU redesign replaces
pointer-chasing hash tables with two scatter-native strategies:

1. direct:  bounded key domains bit-pack into a dense int (ops/hashing.py);
   the packed key IS the slot — aggregation is one scatter-add per agg.
   This is the TPU analog of the reference's bypass/"no hash table" path.

2. hashed:  arbitrary int64 keys go through vectorized open-addressing slot
   assignment: all rows probe in lockstep; each round, unclaimed rows try to
   claim their probe slot with a scatter-min arbitration, losers against a
   different key advance their probe, losers against the same key match next
   round. Terminates in <= table_size rounds (lax.while_loop, static shapes).

Both return fixed-capacity group tables (capacity + occupancy mask), the
static-shape discipline XLA needs; the engine layer sizes capacity from
optimizer NDV estimates and retries bigger on overflow (the spill analog —
reference spills to tmp files, we respill to a larger compile).

All aggregates accumulate via segment scatter-adds/min/max which XLA lowers
to efficient TPU scatters. SUM of decimals stays in int64.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from .hashing import hash_combine, next_pow2

_I32_MAX = jnp.iinfo(jnp.int32).max
_I64_MAX = jnp.iinfo(jnp.int64).max
_I64_MIN = jnp.iinfo(jnp.int64).min


def assign_group_slots(
    key_cols: list[jnp.ndarray], mask: jnp.ndarray, table_size: int
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Assign each live row a slot in an open-addressing table.

    Returns (row_slot [N] int32, slot_used [T] bool, slot_of_first_row [T]
    int32 — for materializing key columns per group via gather).
    Dead rows get slot -1.

    The table stores a 32-bit hash TAG per slot (TPUs emulate 64-bit int
    multiplies, so both the mix and the per-probe tag compare run 32-bit);
    "same key" additionally compares every real key column against the
    slot's first claimant, so tag collisions only cost an extra probe.
    """
    from .hashing import hash32_combine, inherit_vma

    n = key_cols[0].shape[0]
    ts = table_size
    tags = hash32_combine(key_cols).astype(jnp.int32)
    h = (tags.astype(jnp.uint32) & jnp.uint32(ts - 1)).astype(jnp.int32)

    rows = jnp.arange(n, dtype=jnp.int32)

    def cond(state):
        _, _, _, pending, probe, _ = state
        return jnp.logical_and(jnp.any(pending), probe < ts)

    def body(state):
        slot_tag, slot_row, row_slot, pending, probe, probe_of = state
        pos = ((h + probe_of) & (ts - 1)).astype(jnp.int32)
        at_used = slot_row[pos] >= 0
        at_tag = slot_tag[pos]
        # exact key equality vs the slot's first claimant (the tag alone
        # could merge distinct keys; the reference compares real keys too)
        at_row = jnp.clip(slot_row[pos], 0, n - 1)
        exact = jnp.ones(n, dtype=jnp.bool_)
        for c in key_cols:
            exact = exact & (c[at_row] == c)
        same = pending & at_used & (at_tag == tags) & exact
        # claim arbitration: lowest row id wins each empty slot
        claim = jnp.full(ts, _I32_MAX, dtype=jnp.int32)
        claim = claim.at[jnp.where(pending & ~at_used, pos, ts)].min(
            rows, mode="drop"
        )
        winner = pending & ~at_used & (claim[pos] == rows)
        # winners write their tag + row id
        wpos = jnp.where(winner, pos, ts)
        slot_tag = slot_tag.at[wpos].set(tags, mode="drop")
        slot_row = slot_row.at[wpos].set(rows, mode="drop")
        matched = winner | same
        row_slot = jnp.where(matched, pos, row_slot)
        pending = pending & ~matched
        # advance probe only for rows that saw a different-key occupied slot
        advance = pending & at_used & ~((at_tag == tags) & exact)
        probe_of = probe_of + advance.astype(jnp.int32)
        return slot_tag, slot_row, row_slot, pending, probe + 1, probe_of

    init = (
        inherit_vma(jnp.zeros(ts, dtype=jnp.int32), tags),  # slot_tag
        inherit_vma(jnp.full(ts, -1, dtype=jnp.int32), tags),  # slot_row
        inherit_vma(jnp.full(n, -1, dtype=jnp.int32), tags),  # row_slot
        mask,  # pending
        inherit_vma(jnp.zeros((), dtype=jnp.int32), tags),  # round counter
        inherit_vma(jnp.zeros(n, dtype=jnp.int32), tags),  # per-row probe
    )
    slot_tag, slot_row, row_slot, pending, _, _ = jax.lax.while_loop(
        cond, body, init
    )
    slot_used = slot_row >= 0
    return row_slot, slot_used, slot_row


@dataclass(frozen=True)
class AggSpec:
    """One aggregate: op in {sum, count, min, max}; values = input array
    (ignored for count). Decimal sums pass int64 values."""

    op: str
    name: str


def _apply_agg(op: str, row_slot, mask, values, table_size: int):
    idx = jnp.where(mask, row_slot, table_size)  # dead rows dropped
    if op == "count":
        out = jnp.zeros(table_size, dtype=jnp.int64)
        return out.at[idx].add(1, mode="drop")
    if op == "sum":
        acc_dtype = (
            jnp.int64
            if jnp.issubdtype(values.dtype, jnp.integer)
            else values.dtype
        )
        out = jnp.zeros(table_size, dtype=acc_dtype)
        return out.at[idx].add(values.astype(acc_dtype), mode="drop")
    if op == "min":
        init = (
            jnp.iinfo(values.dtype).max
            if jnp.issubdtype(values.dtype, jnp.integer)
            else jnp.inf
        )
        out = jnp.full(table_size, init, dtype=values.dtype)
        return out.at[idx].min(values, mode="drop")
    if op == "max":
        init = (
            jnp.iinfo(values.dtype).min
            if jnp.issubdtype(values.dtype, jnp.integer)
            else -jnp.inf
        )
        out = jnp.full(table_size, init, dtype=values.dtype)
        return out.at[idx].max(values, mode="drop")
    raise NotImplementedError(op)


def groupby_hash(
    key_cols: list[jnp.ndarray],
    mask: jnp.ndarray,
    agg_ops: list[str],
    agg_values: list[jnp.ndarray | None],
    table_size: int,
):
    """General hash group-by.

    Returns (group_keys: list of arrays [T] — key columns gathered from each
    group's first row, slot_used [T], aggs: list of arrays [T]).
    table_size must be a power of two >= 2 * expected NDV.
    """
    assert table_size == next_pow2(table_size)
    row_slot, slot_used, slot_row = assign_group_slots(key_cols, mask, table_size)
    gk = [
        jnp.where(slot_used, c[jnp.clip(slot_row, 0, c.shape[0] - 1)], 0)
        for c in key_cols
    ]
    aggs = [
        _apply_agg(op, row_slot, mask, v, table_size)
        for op, v in zip(agg_ops, agg_values)
    ]
    return gk, slot_used, aggs


def groupby_direct(
    packed_keys: jnp.ndarray,
    domain: int,
    mask: jnp.ndarray,
    agg_ops: list[str],
    agg_values: list[jnp.ndarray | None],
):
    """Direct-addressed group-by for bit-packed bounded keys.

    packed_keys in [0, domain). Returns (slot_used [domain], aggs [domain]).
    The group's key columns are recovered by unpacking the slot index.

    Computed as `domain` MASKED REDUCTIONS per aggregate, not scatters:
    on TPU a fused masked-sum sweep over 8M rows costs ~2.4ms for 8 slots
    while one scatter-add costs ~1.1s. The reductions share the row scan
    (XLA fuses them), so cost scales with domain * passes, which is why the
    engine caps the direct path at a small domain.
    """
    aggs: list[jnp.ndarray] = []
    slot_is = [packed_keys == g for g in range(domain)]
    counts = jnp.stack(
        [jnp.sum(mask & is_g, dtype=jnp.int64) for is_g in slot_is]
    )
    slot_used = counts > 0
    for op, v in zip(agg_ops, agg_values):
        if op == "count":
            aggs.append(counts)
            continue
        if op == "sum":
            acc = (
                jnp.int64
                if jnp.issubdtype(v.dtype, jnp.integer)
                else v.dtype
            )
            aggs.append(jnp.stack([
                jnp.sum(jnp.where(mask & is_g, v, 0).astype(acc))
                for is_g in slot_is
            ]))
        elif op == "min":
            ident = (
                jnp.iinfo(v.dtype).max
                if jnp.issubdtype(v.dtype, jnp.integer) else jnp.inf
            )
            aggs.append(jnp.stack([
                jnp.min(jnp.where(mask & is_g, v, ident)) for is_g in slot_is
            ]))
        elif op == "max":
            ident = (
                jnp.iinfo(v.dtype).min
                if jnp.issubdtype(v.dtype, jnp.integer) else -jnp.inf
            )
            aggs.append(jnp.stack([
                jnp.max(jnp.where(mask & is_g, v, ident)) for is_g in slot_is
            ]))
        else:
            raise NotImplementedError(op)
    return slot_used, aggs


def distinct_first_mask(
    key_vals: list[jnp.ndarray], val: jnp.ndarray, mask: jnp.ndarray
) -> jnp.ndarray:
    """First-occurrence mask for DISTINCT aggregates: True for exactly one
    live row per (group keys, value) combination, in ORIGINAL row order.

    The reference routes distinct aggregates through a dedicated hash-set
    pass (sql/engine/aggregate distinct-agg infra); the TPU redesign is the
    usual scatter-free recipe: one combined sort with the row index as the
    trailing operand, run-boundary detection, and an argsort-based inverse
    permutation to map the per-run winner bit back."""
    from .sort import split_sort_key

    n = mask.shape[0]
    dead = (~mask).astype(jnp.int32)
    planes: list[jnp.ndarray] = [dead]
    for k in (*key_vals, val):
        planes.extend(split_sort_key(k))
    ops = tuple(planes) + (jnp.arange(n, dtype=jnp.int32),)
    sorted_ = jax.lax.sort(ops, num_keys=len(ops) - 1)
    sdead = sorted_[0]
    sidx = sorted_[-1]
    new_run = jnp.zeros(n, jnp.bool_)
    for sv in sorted_[:-1]:
        new_run = new_run | jnp.concatenate(
            [jnp.ones(1, jnp.bool_), sv[1:] != sv[:-1]]
        )
    first = new_run & (sdead == 0)
    return first[jnp.argsort(sidx)]


def sort_groupby(
    key_cols: list[jnp.ndarray],
    mask: jnp.ndarray,
    agg_ops: list[str],
    agg_values: list[jnp.ndarray | None],
    agg_masks: list[jnp.ndarray | None] = None,
    carry: dict[str, jnp.ndarray] | None = None,
):
    """Sort-based group-by: the TPU default for unbounded key domains.

    One multi-operand lexicographic sort (dead rows last), segment
    boundaries by exact key comparison, then every aggregate is a
    segmented cumsum / associative scan read at the segment end — no hash
    table, no scatter, no capacity/overflow: the output reuses the input
    capacity with one live row per group (at its segment start, in sorted
    key order).

    Returns (group_keys: list [N] arrays, sel [N] bool group-start mask,
    aggs: list [N] arrays, carried: {name: [N] array}).
    agg_masks[i] (optional) restricts which rows feed aggregate i (SQL
    null-skipping); rows outside `mask` never contribute. `carry` holds
    columns the keys determine (equal on every row of a group): they are
    no sort operand, ride the packed row-gather of the aggregate inputs
    into sorted order, and a group's live row (its segment start) then
    holds its value.
    """
    from .sort import rebuild_i64, split_sort_key
    from .window import (
        peer_ends,
        segment_starts,
        segmented_cumsum,
        segmented_scan_minmax,
    )

    n = key_cols[0].shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    # int64 keys split into i32 planes: multi-i64-operand sorts hit a
    # superlinear cliff past ~16M rows on v5e (see ops/sort.py)
    planes: list[jnp.ndarray] = []
    plane_spec: list[tuple[object, int]] = []  # (orig dtype, nplanes)
    for k in key_cols:
        p = split_sort_key(k)
        plane_spec.append((k.dtype, len(p)))
        planes.extend(p)
    operands = (~mask,) + tuple(planes) + (idx,)
    sorted_ = jax.lax.sort(operands, num_keys=1 + len(planes))
    sdead = sorted_[0]
    sp = list(sorted_[1:-1])
    order = sorted_[-1]
    ssel = ~sdead
    # reconstruct the sorted key columns from their planes
    skeys: list[jnp.ndarray] = []
    i = 0
    for dtype, np_ in plane_spec:
        if np_ == 2:
            skeys.append(rebuild_i64(sp[i], sp[i + 1]))
        else:
            skeys.append(sp[i].astype(dtype))
        i += np_

    new_seg = jnp.zeros(n, jnp.bool_).at[0].set(True)
    for k in skeys:
        new_seg = new_seg | jnp.concatenate(
            [jnp.ones(1, jnp.bool_), k[1:] != k[:-1]]
        )
    # dead rows sort last; the first dead row must not join the previous
    # live segment
    new_seg = new_seg | jnp.concatenate(
        [jnp.ones(1, jnp.bool_), sdead[1:] != sdead[:-1]]
    )
    seg_start = segment_starts(new_seg)
    seg_end = peer_ends(new_seg)

    # ONE packed row-gather brings every agg value/mask into sorted order
    # (per-agg element gathers at int64 cost ~42M/s each; the packed form
    # moves all of them at ~175M rows/s — ops/gather.py)
    from .gather import gather_rows

    to_sort: dict = {}
    for i, (v, op) in enumerate(zip(agg_values, agg_ops)):
        if v is not None:
            to_sort[("v", i)] = v
        am = agg_masks[i] if agg_masks is not None else None
        if am is not None:
            to_sort[("m", i)] = am
    for name, a in (carry or {}).items():
        to_sort[("c", name)] = a
    sorted_in = gather_rows(to_sort, order) if to_sort else {}

    # accumulate every per-agg running array, then ONE packed gather at
    # the segment ends materializes all the results together
    running: dict = {}
    for i, (op, v) in enumerate(zip(agg_ops, agg_values)):
        am_s = sorted_in.get(("m", i))
        vm = ssel if am_s is None else (ssel & am_s)
        if op == "count":
            running[i] = segmented_cumsum(vm.astype(jnp.int64), seg_start)
            continue
        sv = sorted_in[("v", i)]
        if op == "sum":
            acc = (
                jnp.int64
                if jnp.issubdtype(sv.dtype, jnp.integer)
                else sv.dtype
            )
            mv = jnp.where(vm, sv.astype(acc), 0)
            running[i] = segmented_cumsum(mv, seg_start)
        elif op in ("min", "max"):
            is_min = op == "min"
            ident = (
                (jnp.iinfo(sv.dtype).max if is_min else jnp.iinfo(sv.dtype).min)
                if jnp.issubdtype(sv.dtype, jnp.integer)
                else (jnp.inf if is_min else -jnp.inf)
            )
            mv = jnp.where(vm, sv, ident)
            running[i] = segmented_scan_minmax(mv, new_seg, is_min)
        else:
            raise NotImplementedError(op)
    ends = gather_rows(running, seg_end) if running else {}
    aggs_out = [ends[i] for i in range(len(agg_ops))]
    sel = new_seg & ssel
    return skeys, sel, aggs_out, {n: sorted_in[("c", n)] for n in carry or {}}


def scalar_aggregate(
    mask: jnp.ndarray, agg_ops: list[str], agg_values: list[jnp.ndarray | None]
):
    """Ungrouped aggregation (reference: ObScalarAggregateOp) — one masked
    reduction per agg; XLA fuses these with the producing expressions."""
    out = []
    for op, v in zip(agg_ops, agg_values):
        if op == "count":
            out.append(jnp.sum(mask, dtype=jnp.int64))
            continue
        if op == "approx_ndv":
            from .hll import hll_count

            out.append(hll_count(v, mask))
            continue
        if op == "sum":
            acc = (
                jnp.int64 if jnp.issubdtype(v.dtype, jnp.integer) else v.dtype
            )
            out.append(jnp.sum(jnp.where(mask, v, 0).astype(acc)))
        elif op == "min":
            init = (
                jnp.iinfo(v.dtype).max
                if jnp.issubdtype(v.dtype, jnp.integer)
                else jnp.inf
            )
            out.append(jnp.min(jnp.where(mask, v, init)))
        elif op == "max":
            init = (
                jnp.iinfo(v.dtype).min
                if jnp.issubdtype(v.dtype, jnp.integer)
                else -jnp.inf
            )
            out.append(jnp.max(jnp.where(mask, v, init)))
        else:
            raise NotImplementedError(op)
    return out
