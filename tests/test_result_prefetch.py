"""The result frame crosses the link in one wait (ISSUE 29), behind one
cursor with one overflow loop (ISSUE 30).

Every leaf the completion sync is going to read has its device-to-host copy
started at dispatch (`DeviceResult.start_copies`), so `_sync` waits for the
program once instead of paying one blocking round trip a leaf. These tests
hold the order and the counts of that, never a time: which copies start,
that they start before the first blocking read, that a frame over the
threshold stays lazy, that a redriven output gets its copies too whatever
state the cursor is in and whichever plan dispatched it, and that the two
sysstat counters say which way each served SELECT went.
"""

from __future__ import annotations

import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

from oceanbase_tpu.core.column import batch_to_host
from oceanbase_tpu.core.dtypes import DataType, Field, Schema, TypeKind
from oceanbase_tpu.core.table import Table
from oceanbase_tpu.engine import Session
from oceanbase_tpu.engine import executor as EX
from oceanbase_tpu.engine.executor import ROOT_COMPACT, DeviceResult, PreparedPlan
from oceanbase_tpu.server.database import Database
from oceanbase_tpu.share.metrics import MetricsRegistry

I64 = DataType(TypeKind.INT64)
I32 = DataType(TypeKind.INT32)

PREFETCHED, LAZY = "result frames prefetched", "result frames lazy"


# ---- (a), (b): order and extent of the copies, on leaf stand-ins --------------


class Leaf:
    """A device array's stand-in: static `nbytes`, and a log of when its
    copy was started and when it was read (blocking)."""

    def __init__(self, log: list, name: str, value):
        self.log, self.name = log, name
        self.value = np.asarray(value)
        self.nbytes = self.value.nbytes
        self.shape = self.value.shape

    def copy_to_host_async(self):
        self.log.append(("start", self.name))

    def __array__(self, dtype=None, copy=None):
        self.log.append(("read", self.name))
        return self.value


def stand_in_cursor(kind: str, rows: int):
    """A cursor of `kind` over a two-column frame of `rows` rows whose
    leaves are stand-ins; the log they share."""
    log: list = []
    sel = np.arange(rows) % 2 == 0
    out = SimpleNamespace(
        cols={n: Leaf(log, f"cols.{n}", np.arange(rows, dtype=np.int64))
              for n in ("a", "b")},
        valid={"b": Leaf(log, "valid.b", np.ones(rows, dtype=np.bool_))},
        sel=Leaf(log, "sel", sel),
        nrows=Leaf(log, "nrows", np.int64(sel.sum())))
    prepared = SimpleNamespace(_overflows=lambda hovf: {})
    ovf = Leaf(log, "ovf", np.zeros(3, dtype=np.int64))
    if kind == "narrow":
        cur = DeviceResult(prepared, (), out, ovf,
                           novf=Leaf(log, "novf", np.int64(0)), ncap=rows)
    else:
        cur = DeviceResult(prepared, (), out, ovf)
    return cur, log, int(sel.sum())


FRAME = {"cols.a", "cols.b", "valid.b", "sel"}
OVER = DeviceResult.FRAME_PREFETCH_BYTES // 18 + 1  # 18 B a row: just over


@pytest.mark.parametrize("kind,rows,whole,leaves", [
    ("narrow", 256, True, FRAME | {"ovf", "novf"}),
    # the narrow frame is bounded by the fused program, whatever its bytes
    ("narrow", OVER, True, FRAME | {"ovf", "novf"}),
    ("plain", 256, True, FRAME | {"ovf"}),
    ("plain", OVER - 1, True, FRAME | {"ovf"}),
    ("plain", OVER, False, {"ovf", "nrows"}),
])
def test_every_copy_starts_before_the_first_read(kind, rows, whole, leaves):
    cur, log, live = stand_in_cursor(kind, rows)
    cur.start_copies()
    assert cur.prefetched is whole
    assert log and all(what == "start" for what, _ in log), (
        "start_copies blocked on a leaf")
    started = [name for _, name in log]
    assert sorted(started) == sorted(leaves), "one copy a leaf, no more"
    assert cur.frame_bytes == rows * 18
    assert cur.nrows == live
    reads = [name for what, name in log[len(started):] if what == "read"]
    assert len(log) == len(started) + len(reads), (
        "a copy was started after the first blocking read")
    # _sync reads what was started: nothing else, and all of it
    assert set(reads) == leaves
    if whole:
        # the frame is on the host: a fetch moves nothing more
        n = len(log)
        assert cur._hsel is not None and set(cur._hcols) == {"a", "b"}
        cur._sync()
        assert len(log) == n
    else:
        assert cur._hsel is None and not cur._hcols


# ---- real sessions ------------------------------------------------------------


def catalog(nprobe=20000, nbuild=400, seed=11):
    rng = np.random.default_rng(seed)
    probe = Table(
        "probe",
        Schema((Field("fk", I64), Field("val", I64), Field("flt", I32))),
        {"fk": np.sort(rng.integers(0, nbuild * 2, nprobe)).astype(np.int64),
         "val": rng.integers(-50, 50, nprobe).astype(np.int64),
         "flt": rng.integers(0, 10, nprobe).astype(np.int32)})
    build = Table(
        "build",
        Schema((Field("pk", I64), Field("battr", I32))),
        {"pk": rng.permutation(nbuild * 2)[:nbuild].astype(np.int64),
         "battr": rng.integers(0, 5, nbuild).astype(np.int32)})
    return {"probe": probe, "build": build}


@pytest.fixture
def sess():
    return Session(catalog(), unique_keys={"build": (("pk",),)},
                   metrics=MetricsRegistry())


def test_large_frame_stays_lazy(sess):
    """(b) A result over the threshold starts two scalars; the first ten
    rows then cost what they cost at the parent: the sync's counters and
    one 16-row gather a leaf, not the frame."""
    q = "select fk, val, flt from probe where flt < 9"
    # the first run tries the 256-row narrow frame, overflows past the
    # ceiling and finishes in the plain state: lazy all the same
    first = sess.sql(q)._cursor
    assert not first.narrowed and not first.prefetched
    assert first.prepared._narrow_off, "the plan did not give up fusion"
    rs = sess.sql(q)
    cur = rs._cursor
    assert not cur.narrowed and not cur.prefetched
    assert cur.frame_bytes > DeviceResult.FRAME_PREFETCH_BYTES
    assert cur._hsel is None and not cur._hcols, "the frame moved unasked"
    assert sess.metrics.counter(LAZY) == 2
    assert sess.metrics.counter(PREFETCHED) == 0
    synced = rs.profile.d2h_bytes
    assert synced == cur._ovf.nbytes + 8
    head = rs.rows(limit=10)
    assert len(head) == 10
    # 16 rows (10 rounded up to a power of two) of fk, val (8 B) and flt (4 B)
    assert rs.profile.d2h_bytes - synced == 16 * (8 + 8 + 4) + sum(
        16 * v.dtype.itemsize for v in cur._out.valid.values())
    assert cur._hsel is None and not cur._hcols
    p = sess.catalog["probe"].data
    keep = p["flt"] < 9
    assert head == list(zip(p["fk"][keep][:10].tolist(),
                            p["val"][keep][:10].tolist(),
                            p["flt"][keep][:10].tolist()))


def shrink(prepared, cap):
    """Cut a plan's root capacity under the rows it is about to return:
    its next run overflows, and each bump is fourfold."""
    prepared.params.join_cap[ROOT_COMPACT] = cap
    prepared.recompile()


Q_RANGE = "select fk, sum(val) as s from probe where fk >= {} and fk < {} " \
          "group by fk order by fk"
Q_WIDE = "select fk, val, flt from probe where fk >= {} and fk < {}"


def rows_of(sess, q) -> list:
    """The statement's rows from the table itself."""
    p = sess.catalog["probe"].data
    lo, hi = (int(t) for t in q.replace("group", "and").split("and")[:2]
              for t in [t.split()[-1]])
    keep = (p["fk"] >= lo) & (p["fk"] < hi)
    if "sum(" not in q:
        return list(zip(p["fk"][keep].tolist(), p["val"][keep].tolist(),
                        p["flt"][keep].tolist()))
    keys = np.unique(p["fk"][keep])
    return [(int(k), int(p["val"][keep][p["fk"][keep] == k].sum()))
            for k in keys]


def warm(sess, q):
    """Run `q` once; its cached plan and bound parameters."""
    sess.sql(q).rows()
    entry, qparams = sess.cached_entry(q)
    return entry.prepared, qparams


def plain_small(sess, monkeypatch):
    q = Q_RANGE.format(100, 140)
    p, qp = warm(sess, q)
    p._narrow_off = True  # the plan's own opt-out
    shrink(p, 16)
    return q, p, p, dict(state="plain", whole=True, retries=1)


def plain_large(sess, monkeypatch):
    q = Q_WIDE.format(0, 700)
    p, qp = warm(sess, q)  # 17 k rows: gave up the fused frame by itself
    shrink(p, 8192)
    return q, p, p, dict(state="plain", whole=False, retries=1)


def narrow(sess, monkeypatch):
    q = Q_RANGE.format(100, 140)
    p, qp = warm(sess, q)
    shrink(p, 16)
    return q, p, p, dict(state="narrow", whole=True, retries=1)


def narrow_grown(sess, monkeypatch):
    q = Q_RANGE.format(100, 140)
    p, qp = warm(sess, q)
    p._narrow_cap = 2  # a frame of two rows for forty
    return q, p, p, dict(state="narrow", whole=True, retries=0, ncap=64)


def narrow_surrendered(sess, monkeypatch):
    q, p, _, _ = narrow_grown(sess, monkeypatch)
    # ...and a ceiling it cannot grow under: the plan gives up fusion
    monkeypatch.setattr(DeviceResult, "NARROW_MAX_ROWS", 4)
    # (the plain frame is the root capacity's 4096 rows: over the
    # threshold, lazy)
    return q, p, p, dict(state="plain", whole=False, retries=0, off=True)


def chunked_merge(sess, monkeypatch):
    # a budget the probe table does not fit: the plan streams it in
    # chunks and its cursor is the merge plan's
    sess.executor.device_budget = 64 * 1024
    q = Q_RANGE.format(100, 140)
    cp, qp = warm(sess, q)
    assert type(cp).__name__ == "ChunkedPreparedPlan"
    shrink(cp._merge_prepared, 16)
    return q, cp, cp._merge_prepared, dict(
        state="plain", whole=True, retries=1)


def batched(sess, monkeypatch):
    q = Q_RANGE.format(100, 140)
    p, qp = warm(sess, q)
    shrink(p, 16)
    return q, p, p, dict(state="batched", retries=1)


@pytest.mark.parametrize("case", [
    plain_small, plain_large, narrow, narrow_grown, narrow_surrendered,
    chunked_merge, batched])
def test_one_overflow_loop_whatever_the_entry(sess, monkeypatch, case):
    """(c) Every way into a prepared plan redrives an overflow by the same
    step: one error text when the retries are spent, `retries` counted
    once a recompile, the copies started again on the redriven output,
    and nothing of an overflowed attempt in the rows."""
    q, entry_plan, p, want = case(sess, monkeypatch)
    _, qp = sess.cached_entry(q)
    recompiles = []
    real = PreparedPlan.recompile
    monkeypatch.setattr(PreparedPlan, "recompile", lambda self: (
        recompiles.append(self), real(self))[1])
    calls = []
    real_start = DeviceResult.start_copies
    monkeypatch.setattr(DeviceResult, "start_copies", lambda self: (
        calls.append((self, self._out)), real_start(self))[1])
    retries0, cap0, off0 = p.retries, p._narrow_cap, p._narrow_off

    # out of retries: the one error, and nothing bumped on the way to it
    with pytest.raises(RuntimeError,
                       match=r"^capacity overflow after 0 retries: \{"):
        if want["state"] == "batched":
            p.run_batched_host(np.stack([qp, qp]), max_retries=0)
        else:
            entry_plan.dispatch(qp, max_retries=0).nrows
    assert (p.retries, p._narrow_cap, recompiles) == (retries0, cap0, [])
    assert p._narrow_off is off0
    del calls[:]

    rows = rows_of(sess, q)
    assert len(rows) > 16
    if want["state"] == "batched":
        other = Q_RANGE.format(100, 130)
        _, qp2 = sess.cached_entry(other)
        hcols, hvalid, hsel, schema, dicts = p.run_batched_host(
            np.stack([qp, qp2, qp]))
        for lane, text in enumerate([q, other, q]):
            keep = hsel[lane]
            assert list(zip(hcols["fk"][lane][keep].tolist(),
                            hcols["s"][lane][keep].tolist())) \
                == rows_of(sess, text)
    else:
        rs = sess.sql(q)
        cur = rs._cursor
        assert cur.prepared is p
        mine = [out for c, out in calls if c is cur]
        assert len(mine) >= 2, "the redriven output was read unprefetched"
        assert mine[0] is not mine[-1] and mine[-1] is cur._out
        assert cur.narrowed is (want["state"] == "narrow")
        assert cur.prefetched is want["whole"]
        assert cur.prefetched is (cur.narrowed or cur.frame_bytes
                                  <= DeviceResult.FRAME_PREFETCH_BYTES)
        if cur.prefetched:
            # what the sync kept is the redriven frame's, leaf for leaf
            assert cur._hsel.shape == cur._out.sel.shape
            assert all(cur._hcols[n].shape == a.shape
                       for n, a in cur._out.cols.items())
        else:
            assert cur._hsel is None and not cur._hcols
        assert rs.nrows == len(rows) and rs.rows() == rows
        # the same run read leaf by leaf, with none of the cursor's cache
        host = batch_to_host(entry_plan.run(qparams=qp))
        assert [tuple(r) for r in zip(*(host[n] for n in rs.names))] == [
            tuple(r) for r in rows]
        # the statement is counted once, by what its sync read in the end
        assert sess.metrics.counter(PREFETCHED) + sess.metrics.counter(LAZY) \
            == 2
        assert sess.metrics.counter("overflow recompiles") == (
            want["retries"] if p is entry_plan else 0)
    assert p.retries - retries0 == want["retries"] == len(recompiles)
    assert p._narrow_cap == want.get("ncap", cap0) or want.get("off")
    assert p._narrow_off is (off0 or bool(want.get("off")))


# ---- (d) the served PX route --------------------------------------------------


def test_px_route_prefetches_and_equals_one_chip():
    db = Database(n_nodes=1, n_ls=1)
    try:
        admin = db.session()  # opened before the ALTER SYSTEM: one chip
        admin.sql("create table t (k int primary key, g int, v int)")
        admin.sql("insert into t values " + ", ".join(
            f"({i}, {i % 7}, {i * 3 % 101})" for i in range(500)))
        admin.sql("alter system set ob_px_dop = 4")
        px = db.session()
        assert px._vars["ob_px_dop"] == 4 and admin._vars["ob_px_dop"] == 0
        statements = [
            "select g, sum(v), count(*) from t group by g order by g",
            "select k, v from t where v > 90 order by k",
            "select g, sum(v), count(*) from t group by g order by g"]
        for q in statements:
            want = admin.sql(q).rows()
            runs0 = db.metrics.counter("px executions")
            pre0, lazy0 = (db.metrics.counter(n) for n in (PREFETCHED, LAZY))
            got = px.sql(q).rows()
            assert got == want and len(got) > 0
            assert db.metrics.counter("px executions") - runs0 == 1
            assert db.metrics.counter(PREFETCHED) - pre0 == 1
            assert db.metrics.counter(LAZY) - lazy0 == 0
    finally:
        db.close()


# ---- (e) the counters add up --------------------------------------------------


def test_counters_add_up_to_the_selects_served():
    db = Database(n_nodes=1, n_ls=1)
    try:
        s = db.session()
        s.sql("set ob_enable_result_cache = false")
        s.sql("create table t (k int primary key, g int, v int)")
        for lo in range(0, 6000, 1000):
            s.sql("insert into t values " + ", ".join(
                f"({i}, {i % 7}, {i * 3 % 101})" for i in range(lo, lo + 1000)))
        pre0, lazy0 = (db.metrics.counter(n) for n in (PREFETCHED, LAZY))
        small = ["select g, sum(v) from t group by g order by g",
                 "select v from t where k = 17",
                 "select count(*) from t where v > 50",
                 "select v from t where k = 4242"]
        large = ["select k, g, v from t where v >= 0",
                 "select v, g, k from t where g < 7"]
        for q in small + large + small:
            assert s.sql(q).nrows > 0
        pre, lazy = (db.metrics.counter(n) for n in (PREFETCHED, LAZY))
        assert lazy - lazy0 == len(large)
        assert pre - pre0 == 2 * len(small)
        rows = dict(s.sql(
            "select name, value from __all_virtual_sysstat "
            "where name like 'result frames%'").rows())
        assert int(rows[LAZY]) == lazy and int(rows[PREFETCHED]) >= pre
    finally:
        db.close()


# ---- tools/bench_profile.py: the three parts of a `device wait` leaf ----------


def test_device_wait_split_on_a_hand_built_timeline():
    tools = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools")
    sys.path.insert(0, tools)
    try:
        import bench_profile
    finally:
        sys.path.remove(tools)
    ms = 1_000_000
    op = lambda dev, s, d: (dev, s * ms, d * ms, None, None, "x")  # noqa: E731
    ev = {"windows": [("q3", 0, 100 * ms), ("q14", 100 * ms, 200 * ms)],
          # chip 0: 2 ms to the first op, 7 busy, 1 between, 4 of tail;
          # chip 1: 1 ms, 9 busy, 0 between, 4 of tail
          "ops": [op(0, 12, 5), op(0, 18, 2), op(1, 11, 9),
                  # running before its leaf opens: no launch part
                  op(0, 105, 10)],
          # the programs: 8 and 9 ms on the two chips; 10 ms; an eager
          # module is none of the statement's
          "modules": [(0, "jit_ob_select_ab_px(1)", 12 * ms, 8 * ms),
                      (1, "jit_ob_select_ab_px(1)", 11 * ms, 9 * ms),
                      (0, "jit_convert_element_type(2)", 9 * ms, 1 * ms),
                      (0, "jit_ob_select_cd_narrow(3)", 105 * ms, 10 * ms)],
          "phases": [(1, "device dispatch", 8 * ms, 1 * ms, 1),
                     (1, "device wait", 10 * ms, 14 * ms, 1),
                     (1, "device wait", 50 * ms, 4 * ms, 2),   # no op at all
                     (1, "parse bind", 0, 1 * ms, 1),
                     (1, "device dispatch", 104 * ms, 2 * ms, 3),
                     (2, "device dispatch", 107 * ms, 1 * ms, 9),  # a peer's
                     (1, "device wait", 108 * ms, 10 * ms, 3),
                     # not wholly inside a window: left out
                     (1, "device wait", 195 * ms, 10 * ms, 4)]}
    got = bench_profile.wait_split(
        ev, {"q3": 2, "q14": 1},
        {"q3": {"device wait": 0.020}, "q14": {"device wait": 0.003}})
    assert got["q3"] == pytest.approx({
        "leaves": 2, "no_op_leaves": 1, "programs_seen": 1,
        "before_first_op_ms": 1.5, "busy_ms": 8.0, "between_ops_ms": 0.5,
        "after_last_op_ms": 4.0, "leaf_ms": 9.0, "program_ms": 8.5,
        "dispatch_to_wait_end_ms": 16.0, "around_program_ms": 7.5,
        "idle_ms_per_stmt": 10.0})
    assert got["q14"] == pytest.approx({
        "leaves": 1, "no_op_leaves": 0, "programs_seen": 1,
        "before_first_op_ms": 0.0, "busy_ms": 7.0, "between_ops_ms": 0.0,
        "after_last_op_ms": 3.0, "leaf_ms": 10.0, "program_ms": 10.0,
        "dispatch_to_wait_end_ms": 14.0, "around_program_ms": 4.0,
        "idle_ms_per_stmt": 3.0})


def test_no_second_fetch_path():
    """One rule for what the sync reads: the per-executable memo that used
    to decide it (and needed profiling on to be written) is gone."""
    import inspect

    from oceanbase_tpu.engine import session

    for mod in (EX, session):
        assert "_result_bytes_memo" not in inspect.getsource(mod)
