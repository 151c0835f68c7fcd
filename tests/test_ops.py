"""Operator kernel tests vs numpy oracles (reference test model:
unittest/sql/engine with fake tables + data generators, SURVEY.md §4)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oceanbase_tpu.ops import (
    build_hash_table,
    expand_join,
    groupby_direct,
    groupby_hash,
    hash_join_probe,
    next_pow2,
    pack_keys,
    scalar_aggregate,
    sort_build_side,
    sort_indices,
    topn_indices,
)


def test_pack_keys():
    a = jnp.array([0, 1, 2, 3], dtype=jnp.int32)
    b = jnp.array([0, 1, 0, 1], dtype=jnp.int32)
    packed, space = pack_keys([a, b], [4, 2])
    assert space == 8
    assert packed.tolist() == [0, 5, 2, 7]


def test_groupby_direct_matches_numpy(rng):
    n = 5000
    k = rng.integers(0, 7, n)
    v = rng.integers(-100, 100, n)
    mask = rng.random(n) < 0.8
    slot_used, (s, c, mn, mx) = _run_direct(k, v, mask, 8)
    for g in range(7):
        m = mask & (k == g)
        if m.sum() == 0:
            assert not bool(slot_used[g])
            continue
        assert bool(slot_used[g])
        assert int(s[g]) == v[m].sum()
        assert int(c[g]) == m.sum()
        assert int(mn[g]) == v[m].min()
        assert int(mx[g]) == v[m].max()


def _run_direct(k, v, mask, domain):
    @jax.jit
    def run(k, v, mask):
        return groupby_direct(
            k, domain, mask, ["sum", "count", "min", "max"], [v, None, v, v]
        )

    return run(
        jnp.asarray(k, jnp.int32), jnp.asarray(v, jnp.int64), jnp.asarray(mask)
    )


def test_groupby_hash_matches_numpy(rng):
    n = 8192
    # keys with big sparse domain -> forces real hashing + collisions
    k1 = rng.integers(0, 1 << 40, 50)[rng.integers(0, 50, n)]
    k2 = rng.integers(0, 97, n)
    v = rng.integers(-1000, 1000, n)
    mask = rng.random(n) < 0.9
    ts = next_pow2(50 * 97 * 2)

    @jax.jit
    def run(k1, k2, v, mask):
        return groupby_hash([k1, k2], mask, ["sum", "count"], [v, None], ts)

    gk, slot_used, (s, c) = run(
        jnp.asarray(k1), jnp.asarray(k2), jnp.asarray(v), jnp.asarray(mask)
    )
    gk1, gk2 = np.asarray(gk[0]), np.asarray(gk[1])
    used = np.asarray(slot_used)
    s, c = np.asarray(s), np.asarray(c)

    # oracle
    import collections

    sums = collections.Counter()
    cnts = collections.Counter()
    for i in range(n):
        if mask[i]:
            sums[(k1[i], k2[i])] += v[i]
            cnts[(k1[i], k2[i])] += 1
    got = {(int(gk1[i]), int(gk2[i])): (int(s[i]), int(c[i]))
           for i in range(len(used)) if used[i]}
    assert len(got) == len(cnts)
    for key, cnt in cnts.items():
        assert got[key] == (sums[key], cnt)


def test_scalar_aggregate(rng):
    n = 4096
    v = rng.integers(-50, 50, n)
    mask = rng.random(n) < 0.5

    @jax.jit
    def run(v, mask):
        return scalar_aggregate(mask, ["sum", "count", "min", "max"], [v, None, v, v])

    s, c, mn, mx = run(jnp.asarray(v), jnp.asarray(mask))
    assert int(s) == v[mask].sum()
    assert int(c) == mask.sum()
    assert int(mn) == v[mask].min()
    assert int(mx) == v[mask].max()


def test_hash_join_unique_build(rng):
    nb, np_ = 512, 4096
    build_keys = rng.permutation(100000)[:nb]  # unique
    build_mask = rng.random(nb) < 0.9
    probe_keys = build_keys[rng.integers(0, nb, np_)]
    # half the probes miss
    miss = rng.random(np_) < 0.5
    probe_keys = np.where(miss, probe_keys + 200000, probe_keys)
    probe_mask = rng.random(np_) < 0.9
    ts = next_pow2(nb * 2)

    @jax.jit
    def run(bk, bm, pk, pm):
        slot_key, slot_row = build_hash_table([bk], bm, ts)
        return hash_join_probe(slot_key, slot_row, [bk], [pk], pm)

    match = np.asarray(
        run(
            jnp.asarray(build_keys),
            jnp.asarray(build_mask),
            jnp.asarray(probe_keys),
            jnp.asarray(probe_mask),
        )
    )
    key_to_row = {int(k): i for i, k in enumerate(build_keys) if build_mask[i]}
    for i in range(np_):
        want = key_to_row.get(int(probe_keys[i]), -1) if probe_mask[i] else -1
        assert match[i] == want, (i, match[i], want)


def test_expand_join_mn(rng):
    nb, np_ = 300, 1000
    build_keys = rng.integers(0, 50, nb)  # heavy duplicates
    build_mask = rng.random(nb) < 0.9
    probe_keys = rng.integers(0, 60, np_)
    probe_mask = rng.random(np_) < 0.9
    cap = 16384

    @jax.jit
    def run(bk, bm, pk, pm):
        skeys, order = sort_build_side([bk], bm)
        return expand_join(skeys, order, bm.sum(), [pk], pm, cap)

    op, ob, ov, total, _starts, _offs = run(
        jnp.asarray(build_keys),
        jnp.asarray(build_mask),
        jnp.asarray(probe_keys),
        jnp.asarray(probe_mask),
    )
    op, ob, ov = np.asarray(op), np.asarray(ob), np.asarray(ov)
    pairs = {(int(p), int(b)) for p, b, v in zip(op, ob, ov) if v}
    want_pairs = set()
    cnt = 0
    for p in range(np_):
        if not probe_mask[p]:
            continue
        for b in range(nb):
            if build_mask[b] and build_keys[b] == probe_keys[p]:
                want_pairs.add((p, b))
                cnt += 1
    assert int(total) == cnt
    assert pairs == want_pairs


def test_sort_and_topn(rng):
    n = 2048
    a = rng.integers(0, 50, n)
    b = rng.integers(0, 1000, n)
    mask = rng.random(n) < 0.7

    @jax.jit
    def run(a, b, mask):
        return sort_indices([a, b], [False, True], mask)

    order = np.asarray(run(jnp.asarray(a), jnp.asarray(b), jnp.asarray(mask)))
    live = int(mask.sum())
    got = [(a[i], b[i]) for i in order[:live]]
    want = sorted(
        [(a[i], b[i]) for i in range(n) if mask[i]], key=lambda t: (t[0], -t[1])
    )
    assert got == want
    # dead rows at tail
    assert not mask[order[live:]].any()

    @jax.jit
    def run_top(a, b, mask):
        return topn_indices([a, b], [False, True], mask, 10)

    top, valid = run_top(jnp.asarray(a), jnp.asarray(b), jnp.asarray(mask))
    assert np.asarray(valid).all()
    got_top = [(a[i], b[i]) for i in np.asarray(top)]
    assert got_top == want[:10]


@pytest.mark.parametrize("name", ["segment_starts", "peer_ends"])
def test_position_scans_are_32_bit(name):
    """Segment starts / peer ends scan row POSITIONS, so the running
    max/min must stay 32-bit: two 64-bit cummax/cummin in one program
    crash the installed v5e compiler (TPC-H Q15). The int64 result dtype
    the callers index with is kept by a cast AFTER the scan."""
    from oceanbase_tpu.ops import window

    new_seg = jnp.asarray(np.array([1, 0, 0, 1, 0, 1, 1, 0], bool))
    fn = getattr(window, name)
    out = fn(new_seg)
    assert out.dtype == jnp.int64
    want = {"segment_starts": [0, 0, 0, 3, 3, 5, 6, 6],
            "peer_ends": [2, 2, 2, 4, 4, 5, 7, 7]}[name]
    assert np.asarray(out).tolist() == want
    scans = [e for e in jax.make_jaxpr(fn)(new_seg).eqns
             if e.primitive.name in ("cummax", "cummin")]
    assert scans and all(
        e.outvars[0].aval.dtype == jnp.int32 for e in scans)
