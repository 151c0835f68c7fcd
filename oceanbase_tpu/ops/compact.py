"""Positions of the first live rows of a selection mask — what a result
frame gathers by.

`jax.numpy`'s `nonzero(sel, size=k)` lowers (JAX 0.9) to `cumsum(
bincount(cumsum(sel), length=k))`: a scatter-add of one update per row
of CAPACITY into k bins, serialised on a v5e whatever the number of live
rows. The same positions come from searching a running count: position j
is the first index whose count of live rows reaches j + 1. The count is
kept per block of `_BLOCK` rows, so the scan runs over capacity / 128
block totals and each wanted row costs one search over them, one
128-wide row of the mask and one rank inside it. The chip's readings of
both, and of the forms not kept, are in PERF.md section 6 (PR 36).
"""

from __future__ import annotations

import jax.numpy as jnp

_BLOCK = 128  # one lane row: the in-block rank is one MXU pass


def live_positions(sel: jnp.ndarray, k: int) -> jnp.ndarray:
    """int32[k]: the ascending positions of the first `k` true entries of
    the 1-D mask `sel`, 0 where it has fewer — letter for letter
    `jax.numpy.nonzero(sel, size=k, fill_value=0)[0]`, with no scatter. Counts
    are int32: a capacity is under 2**31, and a 64-bit scan costs a v5e
    several times a 32-bit one."""
    n = sel.shape[0]
    nblocks = -(-n // _BLOCK)
    rows = jnp.pad(sel, (0, nblocks * _BLOCK - n)).reshape(nblocks, _BLOCK)
    per_block = jnp.sum(rows, axis=1, dtype=jnp.int32)
    upto = jnp.cumsum(per_block, dtype=jnp.int32)
    want = jnp.arange(1, k + 1, dtype=jnp.int32)
    # the block that holds the want-th live row (the totals under its
    # rank, counted in one fused compare-and-sum), and its rank inside it
    blk = jnp.searchsorted(upto, want, side="left", method="compare_all")
    blk = jnp.minimum(blk, nblocks - 1).astype(jnp.int32)
    rank = want - jnp.take(upto - per_block, blk)
    # running count along each wanted block: 0/1 values and sums of at
    # most 128 are exact in bfloat16 products summed in float32
    upper = jnp.triu(jnp.ones((_BLOCK, _BLOCK), jnp.bfloat16))
    seen = jnp.dot(jnp.take(rows, blk, axis=0).astype(jnp.bfloat16), upper,
                   preferred_element_type=jnp.float32)
    inner = jnp.sum(seen < rank[:, None].astype(jnp.float32), axis=1,
                    dtype=jnp.int32)
    return jnp.where(want <= upto[-1], blk * _BLOCK + inner, 0)
