"""Vectorized join kernels.

Reference surface: ObHashJoinVecOp (sql/engine/join/hash_join/
ob_hash_join_vec_op.h:316 — build :402, probe :425), merge join, and
nested-loop join.

TPU redesign, driven by what the benchmark's ledger reads on a v5e
(PERF_LEDGER.jsonl, PR 31 / PR 32; PERF.md section 5): a 1-D element gather
~20 ns an element whatever its index pattern, a packed row gather 2-6 ns a
row, a 64-bit (two-plane) scan ~1.3 ns an element, a 32-bit scan ~0.2 ns, a
5-plane sort of 2.5 M entries ~4.5 ns an entry; a scatter ~1.1 s and an
open-addressing while-loop ~30 s per 8 M rows (pre-PR-1 probes). So the hot
joins are SORT-based, scatter-free, and gather nothing a scan or a sort can
carry.

- merge_join_unique (unique single-int-key build — the PK-FK case that
  covers most TPC-H/TPC-DS joins): one combined sort of (dead, key, side,
  row) over build++probe; within a key run the build row (if any) sorts
  first. The value at each run's head rides a scan (a two-plane running
  max under the head's position), and original probe order is restored by
  a second sort that carries the match as its operand — no gather, no
  inverse permutation. Output keeps the probe side's static capacity: each
  probe row gets the matching build row index (or -1); the caller
  materializes payload columns by one packed row gather.

- expand_join (M:N general case): sort the build side by key once, binary
  search each probe key's [lo, hi) duplicate range (searchsorted
  method='sort' — the scan variant costs 20x on TPU), prefix-sum the
  counts, and gather-expand into a static output capacity. The engine
  chooses capacity from optimizer cardinality estimates and re-executes
  with a larger capacity on overflow (detected via the returned total).

- build_hash_table / hash_join_probe (open-addressing lockstep loops) stay
  for cold paths that need multi-column existence probes (set operations);
  they are correct everywhere but orders of magnitude slower on TPU.

All paths are pure jittable functions with static shapes; XLA fuses the
surrounding filters/projections into the gathers.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .hashagg import assign_group_slots
from .hashing import hash_combine, next_pow2

_I64_MIN = jnp.iinfo(jnp.int64).min


def join_keys64(key_cols: list[jnp.ndarray]) -> jnp.ndarray:
    """Canonical 64-bit join key. Single integer key columns pass through
    exactly (no collision risk); multi-column keys hash-combine (the engine
    routes multi-key M:N joins through an extra exact post-filter on the
    expanded pairs, so a 2^-64 collision cannot fabricate a result row)."""
    if len(key_cols) == 1 and jnp.issubdtype(key_cols[0].dtype, jnp.integer):
        return key_cols[0].astype(jnp.int64)
    return hash_combine(key_cols).astype(jnp.int64)


def build_hash_table(
    key_cols: list[jnp.ndarray], mask: jnp.ndarray, table_size: int
):
    """Insert build rows into an open-addressing table.

    Unique keys assumed (duplicates: one winner per key survives — callers
    needing M:N semantics use expand_join). Returns (slot_tag [T] int32
    32-bit hash tags, slot_row [T] int32; empty slots have slot_row < 0).
    """
    from .hashing import hash32_combine

    row_slot, slot_used, slot_row = assign_group_slots(key_cols, mask, table_size)
    tags = hash32_combine(key_cols).astype(jnp.int32)
    n = key_cols[0].shape[0]
    slot_tag = jnp.where(slot_used, tags[jnp.clip(slot_row, 0, n - 1)], 0)
    return slot_tag, slot_row


def hash_join_probe(
    slot_tag: jnp.ndarray,
    slot_row: jnp.ndarray,
    build_key_cols: list[jnp.ndarray],
    probe_key_cols: list[jnp.ndarray],
    probe_mask: jnp.ndarray,
) -> jnp.ndarray:
    """Probe the table; returns match_row [N] int32 (build row idx or -1).

    A hit requires tag equality AND exact equality of every key column, so
    32-bit tag collisions cost an extra probe step, never a wrong match."""
    from .hashing import hash32_combine, inherit_vma

    ts = slot_tag.shape[0]
    nb = build_key_cols[0].shape[0]
    n = probe_key_cols[0].shape[0]
    tags = hash32_combine(probe_key_cols).astype(jnp.int32)
    h = (tags.astype(jnp.uint32) & jnp.uint32(ts - 1)).astype(jnp.int32)

    def cond(state):
        pending, probe, _ = state
        return jnp.logical_and(jnp.any(pending), probe < ts)

    def body(state):
        pending, probe, match_row = state
        pos = ((h + probe) & (ts - 1)).astype(jnp.int32)
        at_row_raw = slot_row[pos]
        empty = at_row_raw < 0
        at_row = jnp.clip(at_row_raw, 0, nb - 1)
        exact = jnp.ones(n, dtype=jnp.bool_)
        for bc, pc in zip(build_key_cols, probe_key_cols):
            exact = exact & (bc[at_row] == pc)
        hit = pending & ~empty & (slot_tag[pos] == tags) & exact
        match_row = jnp.where(hit, at_row_raw, match_row)
        pending = pending & ~hit & ~empty
        return pending, probe + 1, match_row

    init = (
        probe_mask,
        inherit_vma(jnp.zeros((), jnp.int32), tags),
        inherit_vma(jnp.full(n, -1, jnp.int32), tags),
    )
    _, _, match_row = jax.lax.while_loop(cond, body, init)
    return match_row


def _later_head(a, b):
    """reduce_window combiner over (head position, carried value) pairs:
    the pair with the larger position. Positions are distinct (-1 for
    "no head", whose value is -1 too), so this is a max over a total
    order — commutative and associative, whatever tree XLA reduces by."""
    take = b[0] > a[0]
    return jnp.where(take, b[0], a[0]), jnp.where(take, b[1], a[1])


def merge_join_unique(
    build_key: jnp.ndarray,
    build_mask: jnp.ndarray,
    probe_key: jnp.ndarray,
    probe_mask: jnp.ndarray,
) -> jnp.ndarray:
    """Unique-build join on ONE integer key column via a combined sort.

    Returns match_row [Np] int32 in ORIGINAL probe order (-1 = no match).
    Exact (sorts true keys, no hashing). Duplicate build keys: one winner
    per key (the one sorting first), same contract as build_hash_table.

    Deadness rides as a separate LEADING sort operand rather than an
    in-band sentinel value, so the full int64 key domain (including
    2^62.. and int64 max) joins correctly.

    Two sorts and one scan, no gather: values at run heads ride a scan,
    order is restored by a carrying sort (a 1-D element gather costs ~20 ns
    an element on a v5e, the scan ~1, the carrying sort ~2: PERF.md
    section 5). The scan is two int32 planes and not one 64-bit
    `lax.cummax`: two of those in one program crash the installed v5e
    compiler (tests/test_ops.py `test_position_scans_are_32_bit`), and a
    plan may hold several joins.
    """
    nb = build_key.shape[0]
    npr = probe_key.shape[0]
    n = nb + npr
    keys = jnp.concatenate(
        [build_key.astype(jnp.int64), probe_key.astype(jnp.int64)]
    )
    dead = jnp.concatenate([~build_mask, ~probe_mask]).astype(jnp.int32)
    side = jnp.concatenate(
        [jnp.zeros(nb, jnp.int32), jnp.ones(npr, jnp.int32)]
    )
    idx = jnp.concatenate(
        [jnp.arange(nb, dtype=jnp.int32), jnp.arange(npr, dtype=jnp.int32)]
    )
    sdead, sk, sside, sidx = jax.lax.sort(
        (dead, keys, side, idx), num_keys=3
    )
    pos = jnp.arange(n, dtype=jnp.int32)
    new_run = jnp.concatenate(
        [jnp.ones(1, jnp.bool_),
         (sk[1:] != sk[:-1]) | (sdead[1:] != sdead[:-1])]
    )
    # the run head's build row (-1: the head is no live build row) rides a
    # running max under the head's position: positions strictly increase,
    # so the max at any entry IS its own run's head — a two-plane scan,
    # where sidx[run_start] would be a 1-D element gather. A dead entry's
    # run has a dead head, so dead probe rows read -1 with no mask of
    # their own.
    head_b = new_run & (sside == 0) & (sdead == 0)
    _, match_sorted = jax.lax.reduce_window(
        (jnp.where(new_run, pos, -1), jnp.where(head_b, sidx, -1)),
        (jnp.int32(-1), jnp.int32(-1)),
        _later_head, (n,), (1,), [(n - 1, 0)],
    )
    # back to probe order by a sort that carries the match: probe entries
    # first by original row, build entries (dropped) behind them — never
    # a scatter, and no inverse permutation to gather through
    back = jnp.where(sside == 1, sidx, sidx + npr)
    _, out = jax.lax.sort((back, match_sorted), num_keys=1)
    return out[:npr]


def gather_payload(
    columns: dict[str, jnp.ndarray], match_row: jnp.ndarray
) -> dict[str, jnp.ndarray]:
    """Materialize build-side payload columns for matched probe rows."""
    idx = jnp.clip(match_row, 0, None)
    return {name: c[idx] for name, c in columns.items()}


def expand_join(
    build_sorted_keys64: jnp.ndarray,
    build_order: jnp.ndarray,
    build_nrows: jnp.ndarray,
    probe_key_cols: list[jnp.ndarray],
    probe_mask: jnp.ndarray,
    out_capacity: int,
):
    """M:N join expansion against a key-sorted build side.

    build_sorted_keys64: 64-bit mixed keys of build rows, ascending, with
    dead rows sorted to the end (callers pass +inf-like sentinel);
    build_order: original build row index per sorted position;
    Returns (out_probe_row [C] int32, out_build_row [C] int32, out_valid [C]
    bool, total matches [scalar int64], pair_starts [N] int64, pair_offs [N]
    int64). pair_starts/offs delimit each probe row's pair run in output-slot
    space (for scatter-free per-probe reductions, see probe_run_any). If
    total > out_capacity the output is truncated — the engine checks and
    re-runs with a larger capacity.
    """
    keys64 = join_keys64(probe_key_cols)
    # method='sort': the binary-search variant ('scan') lowers to a gather
    # loop that costs ~20x on TPU
    lo = jnp.searchsorted(
        build_sorted_keys64, keys64, side="left", method="sort"
    )
    hi = jnp.searchsorted(
        build_sorted_keys64, keys64, side="right", method="sort"
    )
    # dead build rows occupy sorted positions [build_nrows, nb) (they carry
    # int64-max placeholders); clamping keeps a live int64-max probe key
    # from matching them
    n_live = build_nrows.astype(lo.dtype)
    lo = jnp.minimum(lo, n_live)
    hi = jnp.minimum(hi, n_live)
    cnt = jnp.where(probe_mask, (hi - lo).astype(jnp.int64), 0)
    offs = jnp.cumsum(cnt)  # inclusive prefix sum
    total = offs[-1] if cnt.shape[0] > 0 else jnp.zeros((), jnp.int64)
    starts = offs - cnt  # exclusive
    # for each output slot t: probe row p = first row with offs[p] > t
    t = jnp.arange(out_capacity, dtype=jnp.int64)
    p = jnp.searchsorted(offs, t, side="right", method="sort").astype(jnp.int32)
    pc = jnp.clip(p, 0, cnt.shape[0] - 1)
    k = t - starts[pc]
    b_sorted_pos = (lo[pc].astype(jnp.int64) + k).astype(jnp.int32)
    out_valid = t < total
    nb = build_order.shape[0]
    out_build_row = build_order[jnp.clip(b_sorted_pos, 0, nb - 1)]
    return pc, out_build_row, out_valid, total, starts, offs


def probe_run_any(pair_ok: jnp.ndarray, starts: jnp.ndarray, offs: jnp.ndarray):
    """Per-probe-row OR over its pair run [starts, offs) in output-slot
    space — the scatter-free replacement for `.at[probe].max(pair_ok)`
    (cumsum + two monotone gathers instead of a ~1s TPU scatter)."""
    c = jnp.cumsum(pair_ok.astype(jnp.int64))
    cap = c.shape[0]

    def upto(x):
        return jnp.where(x > 0, c[jnp.clip(x - 1, 0, cap - 1)], 0)

    return (upto(jnp.minimum(offs, cap)) - upto(jnp.minimum(starts, cap))) > 0


def sort_build_side(key_cols: list[jnp.ndarray], mask: jnp.ndarray):
    """Sort build rows by mixed 64-bit key for expand_join; dead rows
    strictly last (deadness is a separate leading sort operand, so live
    rows whose key happens to equal int64 max still precede every dead
    row; expand_join then clamps searchsorted ranges to the live count)."""
    keys64 = join_keys64(key_cols)
    n = keys64.shape[0]
    dead = (~mask).astype(jnp.int32)
    sdead, skeys, order = jax.lax.sort(
        (dead, keys64, jnp.arange(n, dtype=jnp.int32)), num_keys=2
    )
    # dead tail carries int64 max so the array stays nondecreasing for
    # the binary search (live rows can also hold int64 max — harmless,
    # the clamp excludes the tail)
    skeys = jnp.where(sdead == 0, skeys, jnp.iinfo(jnp.int64).max)
    return skeys, order
