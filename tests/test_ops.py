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
from oceanbase_tpu.ops.join import merge_join_unique


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


_I64 = np.iinfo(np.int64)


def _mju_case(name, rng):
    """(build keys, build mask, probe keys, probe mask) of one case."""
    def unique(n, space):
        return rng.permutation(space)[:n].astype(np.int64)

    def probes(bk, n, miss=0.3):
        pk = bk[rng.integers(0, len(bk), n)]
        return np.where(rng.random(n) < miss, pk + 10**9, pk)

    if name == "dead rows on both sides":
        bk = unique(700, 5000)
        return bk, rng.random(700) < 0.6, probes(bk, 3000), \
            rng.random(3000) < 0.7
    if name == "no live build row":
        bk = unique(256, 1000)
        return bk, np.zeros(256, bool), probes(bk, 1000, 0.0), \
            rng.random(1000) < 0.9
    if name == "every probe key missing":
        bk = unique(512, 2000)
        return bk, rng.random(512) < 0.9, unique(2048, 5000) + 10**6, \
            np.ones(2048, bool)
    if name == "int64 extremes":
        # deadness is no in-band sentinel: min, max and 2^62 are keys,
        # on live rows and on dead ones
        edge = np.array([_I64.min, _I64.min + 1, -1, 0, 1, 2**62,
                         2**62 + 1, _I64.max - 1, _I64.max], np.int64)
        bk = np.concatenate([edge, edge[::2] + 7])
        bm = np.ones(len(bk), bool)
        bm[[1, 7, 10]] = False
        pk = np.concatenate([edge, edge, unique(40, 100)])
        pm = np.ones(len(pk), bool)
        pm[len(edge):2 * len(edge):2] = False
        return bk, bm, pk, pm
    if name == "duplicate build keys":
        bk = rng.integers(0, 60, 400).astype(np.int64)
        return bk, rng.random(400) < 0.7, \
            rng.integers(0, 80, 2000).astype(np.int64), \
            rng.random(2000) < 0.9
    if name == "nb >> np":
        bk = unique(8192, 50000)
        return bk, rng.random(8192) < 0.9, probes(bk, 37), \
            rng.random(37) < 0.9
    if name == "np >> nb":
        bk = unique(13, 100)
        return bk, rng.random(13) < 0.8, probes(bk, 9000), \
            rng.random(9000) < 0.9
    if name == "5% live build in a power-of-two lane":
        # Q10's build under PX: exchange lanes at their capacity, the
        # dead slots holding whatever the lane was padded with (zeros)
        live = rng.random(4096) < 0.05
        bk = np.where(live, unique(4096, 30000) + 1, 0)
        return bk, live, np.where(rng.random(6000) < 0.2, 0,
                                  probes(bk[live], 6000)), \
            rng.random(6000) < 0.95
    raise KeyError(name)


@pytest.mark.parametrize("name", [
    "dead rows on both sides", "no live build row",
    "every probe key missing", "int64 extremes", "duplicate build keys",
    "nb >> np", "np >> nb", "5% live build in a power-of-two lane"])
def test_merge_join_unique_matches_dictionary_join(name, rng):
    """merge_join_unique against a plain dictionary join: every probe row,
    in its original place, gets the live build row with its key (where
    build keys repeat, the one winner is the first such row), a dead probe
    row or a key no live build row has gets -1."""
    bk, bm, pk, pm = _mju_case(name, rng)
    match = np.asarray(jax.jit(merge_join_unique)(
        jnp.asarray(bk), jnp.asarray(bm), jnp.asarray(pk), jnp.asarray(pm)))
    assert match.shape == pk.shape and match.dtype == np.int32
    first_live = {}
    for i in range(len(bk) - 1, -1, -1):
        if bm[i]:
            first_live[int(bk[i])] = i
    want = np.array([first_live.get(int(k), -1) if m else -1
                     for k, m in zip(pk, pm)], np.int32)
    assert (match == want).all(), np.flatnonzero(match != want)[:10]
    hit = match >= 0
    assert bm[match[hit]].all() and (bk[match[hit]] == pk[hit]).all()
    if name in ("no live build row", "every probe key missing"):
        assert not hit.any()
    else:
        assert hit.any() and (~hit).any()


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
