"""Window-function kernels: segmented scans over sorted partitions.

Reference surface: the vectorized window operator
(src/sql/engine/window_function, ObWindowFunctionVecOp) which materializes
partitions and evaluates ranking/aggregate functions per frame. The TPU
redesign sorts the whole batch once by (partition keys, order keys) —
masked-out rows to the tail — and then every window function is a
branch-free segmented scan over the sorted array:

  row_number  position - segment start + 1
  rank        peer-group start - segment start + 1
  dense_rank  segmented count of peer-group starts
  sum/count   running: segmented cumsum read at the END of the peer group
              (the SQL default frame RANGE UNBOUNDED PRECEDING..CURRENT ROW
              includes peers); whole-partition when there is no ORDER BY
  min/max     segmented associative scan (flag, value) pairs

Results scatter back to the original row positions, so the operator is
order-preserving like the reference's. Static shapes throughout; dead rows
ride along masked and cannot influence any frame because all value
accumulations are masked to the aggregate's identity.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def boundaries(sorted_keys: list[jnp.ndarray]) -> jnp.ndarray:
    """True where any key column differs from the previous row (or row 0)."""
    n = sorted_keys[0].shape[0] if sorted_keys else 0
    if not sorted_keys:
        return jnp.zeros(0, jnp.bool_)
    new = jnp.zeros(n, dtype=jnp.bool_).at[0].set(True)
    for k in sorted_keys:
        d = jnp.concatenate([jnp.ones(1, jnp.bool_), k[1:] != k[:-1]])
        new = new | d
    return new


def row_positions(n: int) -> jnp.ndarray:
    """0..n-1 in the narrowest dtype that holds a row position. Running
    max/min over POSITIONS (segment starts, peer ends, run bounds) scan
    these, and the width matters on the chip: a 64-bit cummax/cummin
    lowers to a variadic (u32, u32) reduce-window, and two of those in
    one program crash the installed v5e compiler (SIGSEGV in its
    tpu-reduce-window-rewriter pass — TPC-H Q15, whose plan holds two
    copies of the revenue0 group-by). 32-bit scans compile, and cost
    half the lanes."""
    return jnp.arange(n, dtype=jnp.int32 if n < 2**31 else jnp.int64)


def segment_starts(new_seg: jnp.ndarray) -> jnp.ndarray:
    """Index of the segment's first row, per row (int64)."""
    idx = row_positions(new_seg.shape[0])
    return lax.cummax(jnp.where(new_seg, idx, 0)).astype(jnp.int64)


def peer_ends(new_peer: jnp.ndarray) -> jnp.ndarray:
    """Index of the peer group's last row, per row (int64)."""
    n = new_peer.shape[0]
    idx = row_positions(n)
    arr = jnp.where(new_peer, idx, n)
    # min over j >= i of boundary positions, then shift to "strictly after"
    suffix_min = lax.cummin(arr[::-1])[::-1]
    after = jnp.concatenate([suffix_min[1:], jnp.full(1, n, dtype=idx.dtype)])
    return (after - 1).astype(jnp.int64)


def segmented_cumsum(values: jnp.ndarray, seg_start: jnp.ndarray) -> jnp.ndarray:
    """Inclusive running sum within each segment. `values` must already be
    masked (dead/NULL rows contribute the identity 0)."""
    c = jnp.cumsum(values)
    return c - c[seg_start] + values[seg_start]


def segmented_scan_minmax(
    values: jnp.ndarray, new_seg: jnp.ndarray, is_min: bool
) -> jnp.ndarray:
    """Inclusive segmented running min/max; masked rows must carry the
    identity (+inf/-inf or int extremes) in `values`."""

    def comb(a, b):
        fa, va = a
        fb, vb = b
        v = jnp.where(fb, vb, jnp.minimum(va, vb) if is_min else jnp.maximum(va, vb))
        return fa | fb, v

    _, out = lax.associative_scan(comb, (new_seg, values))
    return out


def suffix_scan_minmax(
    values: jnp.ndarray, new_seg: jnp.ndarray, is_min: bool
) -> jnp.ndarray:
    """Inclusive segmented running min/max from the SEGMENT END backwards:
    out[i] = min/max over [i, seg_end]. Implemented by reversing, running
    the forward scan with reversed segment-start flags (= forward segment
    ENDS), and reversing back."""
    n = new_seg.shape[0]
    # forward seg-last flag: next row starts a new segment (or is row n-1)
    seg_last = jnp.concatenate([new_seg[1:], jnp.ones(1, jnp.bool_)])
    out_rev = segmented_scan_minmax(values[::-1], seg_last[::-1], is_min)
    return out_rev[::-1]


def agg_identity(dtype, is_min: bool):
    if jnp.issubdtype(dtype, jnp.integer):
        info = jnp.iinfo(dtype)
        return info.max if is_min else info.min
    return jnp.inf if is_min else -jnp.inf


