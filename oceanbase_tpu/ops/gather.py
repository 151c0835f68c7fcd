"""Packed multi-column row gather — the join/sort payload hot path.

Reference surface: the row-payload materialization of the vectorized hash
join (ObHashJoinVecOp probe output, sql/engine/join/hash_join) and the
generic permutation writebacks of sort/window operators.

Why this exists (measured on a v5e before PR 1, 33M probes):
XLA lowers a 1-D element gather to ~100M elements/s regardless of table
size or index order (int64: 42M/s) — each column of a join payload paid
that full price. A 2-D ROW gather from an (N, K) int32 matrix runs at
~175M rows/s for K=8 (1.4B values/s): the minor dimension is dense, so
the gather vectorizes across lanes. So: bitcast every payload column into
int32 "planes" (int64/float64 -> 2 planes, int32/bool/int8 -> 1), pack
the planes into (N, <=8) matrices, row-gather, unpack. The packing itself
is elementwise VPU work that XLA fuses; K=16 regresses (44M rows/s), so
plane groups cap at 8.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_GROUP = 8  # planes per row-gather (K=8 is the measured sweet spot)


def _to_planes(a: jnp.ndarray) -> list[jnp.ndarray] | None:
    """Split one column into int32 planes (bit-preserving). None = this
    dtype must not be packed (f64 bitcast-convert is rejected by the TPU
    AOT x64-rewriting pass; floats keep the element gather)."""
    if a.dtype == jnp.int64 or a.dtype == jnp.uint64:
        lo = a.astype(jnp.int32)  # wrap-around truncation: low 32 bits
        hi = (a >> 32).astype(jnp.int32)
        return [lo, hi]
    if a.dtype == jnp.float64 or a.dtype == jnp.float32:
        return None
    if a.dtype == jnp.bool_:
        return [a.astype(jnp.int32)]
    return [a.astype(jnp.int32)]


def _from_planes(planes: list[jnp.ndarray], dtype) -> jnp.ndarray:
    if dtype == jnp.int64 or dtype == jnp.uint64:
        lo, hi = planes
        v = (hi.astype(jnp.int64) << 32) | (
            lo.astype(jnp.int64) & jnp.int64(0xFFFFFFFF)
        )
        return v.astype(dtype)
    if dtype == jnp.bool_:
        return planes[0] != 0
    return planes[0].astype(dtype)


def gather_rows(
    cols: dict[str, jnp.ndarray], idx: jnp.ndarray
) -> dict[str, jnp.ndarray]:
    """{name: column[idx]} for every column, via packed row gathers.

    Columns must share a common length. A single int32-plane column skips
    packing (a (N,1) row gather is no better than the element gather)."""
    if not cols:
        return {}
    out: dict[str, jnp.ndarray] = {}
    plan: list[tuple[str, object, int]] = []  # (name, dtype, nplanes)
    planes: list[jnp.ndarray] = []
    for name, a in cols.items():
        p = _to_planes(a)
        if p is None:
            out[name] = a[idx]  # unpackable dtype: element gather
            continue
        plan.append((name, a.dtype, len(p)))
        planes.extend(p)
    if len(planes) == 1:
        name, dtype, _ = plan[0]
        out[name] = cols[name][idx]
        return out
    out_planes: list[jnp.ndarray] = []
    for g in range(0, len(planes), _GROUP):
        group = planes[g:g + _GROUP]
        packed = jnp.stack(group, axis=1)  # (N, K) int32
        got = packed[idx]  # (M, K) row gather — the fast path
        out_planes.extend(got[:, j] for j in range(len(group)))
    i = 0
    for name, dtype, np_ in plan:
        out[name] = _from_planes(out_planes[i:i + np_], dtype)
        i += np_
    return out
