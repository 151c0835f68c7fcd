"""`ops/compact.py` `live_positions` (ISSUE 36): the positions a result
frame gathers by, held to `np.flatnonzero`, and the narrow program that
calls it: no scatter under the `frame` scope, one count of the lowering
counter a program, the counter in `__all_virtual_sysstat`.
"""

from __future__ import annotations

import jax
import numpy as np
import pytest

from oceanbase_tpu.core.dtypes import DataType, Field, Schema, TypeKind
from oceanbase_tpu.core.table import Table
from oceanbase_tpu.engine import Session
from oceanbase_tpu.engine.executor import DeviceResult
from oceanbase_tpu.expr import compile as C
from oceanbase_tpu.ops.compact import live_positions
from oceanbase_tpu.server.database import Database
from oceanbase_tpu.share.metrics import MetricsRegistry

I64 = DataType(TypeKind.INT64)
COUNTER = "result frame positions searched"

CAPS = (1, 16, 1000, 65536)
KS = (1, 16, 256, 4096)


def _mask(kind: str, cap: int, k: int) -> np.ndarray:
    rng = np.random.default_rng(cap * 31 + k)
    m = np.zeros(cap, np.bool_)
    if kind == "full":
        m[:] = True
    elif kind == "first":
        m[0] = True
    elif kind == "last":
        m[-1] = True
    elif kind == "sparse":  # 0.01 %
        m = rng.random(cap) < 1e-4
    elif kind == "half":
        m = rng.random(cap) < 0.5
    elif kind == "over_k":  # more live rows than k wherever capacity allows
        m[rng.permutation(cap)[:min(cap, 2 * k + 3)]] = True
    else:
        assert kind == "empty"
    return m


def _want(m: np.ndarray, k: int) -> np.ndarray:
    want = np.zeros(k, np.int32)
    live = np.flatnonzero(m)[:k]
    want[:len(live)] = live
    return want


_jitted = jax.jit(live_positions, static_argnums=1)


@pytest.mark.parametrize("how", ["jit", "eager"])
@pytest.mark.parametrize("kind", ["empty", "full", "first", "last",
                                  "sparse", "half", "over_k"])
@pytest.mark.parametrize("k", KS)
@pytest.mark.parametrize("cap", CAPS)
def test_equals_flatnonzero(cap, k, kind, how):
    m = _mask(kind, cap, k)
    got = np.asarray((_jitted if how == "jit" else live_positions)(m, k))
    assert got.dtype == np.int32 and got.shape == (k,)
    np.testing.assert_array_equal(got, _want(m, k))
    np.testing.assert_array_equal(
        got, np.asarray(jax.numpy.nonzero(m, size=k, fill_value=0)[0]))


@pytest.mark.parametrize("kind", ["full", "last", "sparse", "half",
                                  "over_k"])
@pytest.mark.parametrize("cap,k", [(300_000, 32_768), (70_000, 70_000),
                                   (70_000, 131_072)])
def test_equals_flatnonzero_at_a_large_k(cap, k, kind):
    """`fetch_head` asks for up to a quarter of a capacity (a `LIMIT` in the
    tens of thousands), and nothing stops a caller at more."""
    m = _mask(kind, cap, k)
    np.testing.assert_array_equal(np.asarray(_jitted(m, k)), _want(m, k))


def test_narrow_program_searches_and_does_not_scatter():
    n = 100_000
    t = Table("t", Schema((Field("id", I64), Field("v", I64))),
              {"id": np.arange(n, dtype=np.int64),
               "v": (np.arange(n, dtype=np.int64) * 7) % 1000})
    sess = Session({"t": t})
    reg = MetricsRegistry()
    prev = C.set_lookup_metrics(reg)
    try:
        rs = sess.sql("select id, v from t where id >= 4711 and id < 4811")
        assert rs.rows() == [(i, i * 7 % 1000) for i in range(4711, 4811)]
        cur = rs._cursor
        assert cur.narrowed, "the plan did not fuse its frame"
        assert reg.counter(COUNTER) == 1
        prepared = cur.prepared
        (ncap,) = prepared._narrow
        inputs = prepared._inputs()
        assert int(np.shape(jax.tree_util.tree_leaves(inputs)[0])[0]) >= n
        lowered = prepared._build_narrow(ncap).lower(inputs, cur._qparams)
        assert reg.counter(COUNTER) == 2, "one count a program lowered"
    finally:
        C.set_lookup_metrics(prev)
    # a filter and a frame: the plan itself has no scatter, so none in
    # the whole StableHLO text is none under the `frame` scope (where
    # `jnp.nonzero` put one: `.../frame/scatter-add`)
    text = lowered.as_text(debug_info=True)
    assert "/frame/" in text, "no op names the frame scope"
    assert "stablehlo.scatter" not in text
    # a second run is the cached program: the counter stands
    prev = C.set_lookup_metrics(reg)
    try:
        sess.sql("select id, v from t where id >= 5 and id < 9").rows()
    finally:
        C.set_lookup_metrics(prev)
    assert reg.counter(COUNTER) == 2


@pytest.fixture(scope="module")
def wide():
    """A session whose statement returns 90,000 of 100,000 rows: too wide
    to fuse, so every cursor after the first holds a plain lazy frame."""
    n = 100_000
    v = (np.arange(n, dtype=np.int64) * 7) % 1000
    t = Table("t", Schema((Field("id", I64), Field("v", I64))),
              {"id": np.arange(n, dtype=np.int64), "v": v})
    sess = Session({"t": t})
    q = "select id, v from t where v < 900"
    sess.sql(q).rows(limit=1)
    keep = v < 900
    return sess, q, list(zip(np.arange(n)[keep].tolist(), v[keep].tolist()))


@pytest.mark.parametrize("where", ["tiny", "edge", "past_edge", "most",
                                   "all"])
def test_fetch_head_gathers_a_small_share_and_fetches_a_large_one(
        wide, where):
    """A head of at most one in `HEAD_GATHER_SHARE` of the capacity is
    gathered on the device; a larger one brings the whole columns."""
    sess, q, want = wide
    rs = sess.sql(q)
    cur = rs._cursor
    assert not cur.narrowed and cur._hsel is None
    cap = int(cur._out.sel.shape[-1])
    edge = 1 << ((cap // DeviceResult.HEAD_GATHER_SHARE).bit_length() - 1)
    limit = {"tiny": 10, "edge": edge, "past_edge": edge + 1,
             "most": len(want) - 7, "all": 10**9}[where]
    gathered = where in ("tiny", "edge")
    before = rs.profile.d2h_bytes
    assert rs.rows(limit=limit) == want[:limit]
    assert (cur._hsel is None) == gathered
    moved = rs.profile.d2h_bytes - before
    row = 8 + 8 + sum(a.dtype.itemsize for a in cur._out.valid.values())
    kb = 1 << (limit - 1).bit_length()
    assert moved == (kb * row if gathered else cap * (row + 1))


def test_counter_shows_in_sysstat():
    db = Database(n_nodes=3, n_ls=2)
    s = db.session()
    s.sql("create table kv (k bigint primary key, v bigint)")
    s.sql("insert into kv values " + ", ".join(
        f"({i}, {i * 3})" for i in range(40)))
    assert s.sql("select k, v from kv where v > 100").rows() == [
        (i, i * 3) for i in range(34, 40)]
    got = s.sql("select value from __all_virtual_sysstat "
                f"where name = '{COUNTER}'").rows()
    assert got and int(got[0][0]) >= 1
