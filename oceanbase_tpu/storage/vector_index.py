"""IVF-flat vector index: ANN search as two rounds of matmul + top-k.

Reference surface: src/storage/vector_index (obvec's IVF/HNSW index
tables) and the ANN DAS iterators (src/sql/das/iter/ob_das_vec_*). The
reference walks graph/list structures pointer by pointer; the TPU
redesign picks the ONE ANN family whose probe is pure dense algebra:

  build:  k-means over the column (assignment = argmin of an (n, L)
          distance matmul — MXU work; centroid update = segment means)
  layout: rows permuted cluster-contiguous (perm), one offset per list —
          the same clustered-layout trick the engine uses everywhere
          (sorted projections, clustered-FK ranges)
  probe:  q @ centroids -> top-nprobe lists -> gather their contiguous
          row windows -> candidates @ q -> top-k.  Two matmuls, two
          top-ks, one gather: everything the MXU/VPU like.

The index is a derived structure cached like device columns: the
executor rebuilds it when the table version bumps (DML maintenance =
invalidate + lazy rebuild, the same contract as sorted projections and
fk_ranges; incremental list-append is a noted future refinement).
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass
class IvfSpec:
    """Registration of a vector index on a Table (survives catalog
    snapshots via re-registration; the built artifact is cached in the
    executor keyed by table version)."""

    column: str
    lists: int = 0       # 0 = auto (~sqrt(n), power-of-two clamped)
    nprobe: int = 8


@dataclass
class IvfIndex:
    centroids: np.ndarray   # (L, d) float32
    perm: np.ndarray        # (n,) int32 — rows in cluster-contiguous order
    offsets: np.ndarray     # (L,) int32 — start of each list in perm
    lengths: np.ndarray     # (L,) int32
    max_list: int           # static per-list read window


import functools

import jax
import jax.numpy as jnp


@jax.jit
def _kmeans_assign(xd, cd):
    # ||x-c||^2 = ||x||^2 - 2 x.c + ||c||^2; argmin drops ||x||^2
    d2 = -2.0 * (xd @ cd.T) + jnp.sum(cd * cd, axis=1)[None, :]
    return jnp.argmin(d2, axis=1)


@functools.partial(jax.jit, static_argnums=2)
def _kmeans_update(xd, a_dev, L):
    # segment means on device: one scatter-add per iteration beats a
    # host np.add.at sweep by orders of magnitude at 1M x 128
    sums = jax.ops.segment_sum(xd, a_dev, num_segments=L)
    cnt = jax.ops.segment_sum(
        jnp.ones(xd.shape[0], jnp.float32), a_dev, num_segments=L)
    return sums, cnt


def _auto_lists(n: int) -> int:
    L = 1
    while L * L < n:
        L *= 2
    return max(4, min(L, 4096))


def build_ivf(x: np.ndarray, lists: int = 0, iters: int = 10,
              seed: int = 0) -> IvfIndex:
    """k-means build on device (jnp) — assignment distance matrices are
    matmuls, so a 1M x 128d build is sub-second on a v5e chip and still
    tractable on CPU test shapes."""
    x = np.asarray(x, dtype=np.float32)
    n, d = x.shape
    L = lists or _auto_lists(n)
    L = min(L, n)
    rng = np.random.default_rng(seed)
    cent = x[rng.choice(n, size=L, replace=False)].copy()

    # the data matrix rides as a jit ARGUMENT, never a closure capture: a
    # captured array becomes a program constant, and the compiled module
    # would carry the whole 512MB at 1M x 128
    xd = jnp.asarray(x)

    a = np.asarray(_kmeans_assign(xd, jnp.asarray(cent)))
    for _ in range(iters):
        sums, cnt = (
            np.asarray(v) for v in _kmeans_update(xd, jnp.asarray(a), L)
        )
        nonempty = cnt > 0
        cent[nonempty] = (
            sums[nonempty] / cnt[nonempty, None]).astype(np.float32)
        # re-seed empty clusters from random points
        for li in np.nonzero(~nonempty)[0]:
            cent[li] = x[rng.integers(0, n)]
        a2 = np.asarray(_kmeans_assign(xd, jnp.asarray(cent)))
        if np.array_equal(a2, a):
            a = a2
            break
        a = a2

    perm = np.argsort(a, kind="stable").astype(np.int32)
    lengths = np.bincount(a, minlength=L).astype(np.int32)
    offsets = np.concatenate(
        [[0], np.cumsum(lengths)[:-1]]).astype(np.int32)
    return IvfIndex(
        centroids=cent,
        perm=perm,
        offsets=offsets,
        lengths=lengths,
        max_list=int(lengths.max()) if L else 0,
    )


def register_vector_index(catalog, table: str, column: str,
                          lists: int = 0, nprobe: int = 8) -> None:
    """CREATE VECTOR INDEX surface: registers the spec on the Table; the
    executor builds (and version-caches) the artifact on first use."""
    t = catalog[table]
    t.vector_indexes = {
        **getattr(t, "vector_indexes", {}),
        column: IvfSpec(column, lists, nprobe),
    }


def drop_vector_index(catalog, table: str, column: str) -> None:
    t = catalog[table]
    vi = dict(getattr(t, "vector_indexes", {}))
    vi.pop(column, None)
    t.vector_indexes = vi
