"""The names a profiler trace carries (PR 26).

Device side: every plan node is emitted under `jax.named_scope("<kind>#<nid>")`
(engine/executor.py `_emit_scoped`), expression work under `expr`
(expr/compile.py), the fused result-frame gather under `frame`, and a
statement program is called `jit_ob_select_<fingerprint>[_narrow]`. Host
side: while a `jax.profiler` session is active the gap ledger's phases are
leaf `TraceAnnotation("ob:<phase>", stmt=<id>)` events on the statement's
thread (share/gap_ledger.py); with no session nothing is built. Beside them:
thread-CPU seconds per digest and the front end's pool hand-off wait.
"""

import collections
import glob
import os
import re

import jax
import pytest

from oceanbase_tpu.engine import Session
from oceanbase_tpu.models.tpch import datagen
from oceanbase_tpu.models.tpch.sql_suite import QUERIES, UNIQUE_KEYS
from oceanbase_tpu.server.async_front import AsyncMySqlFrontend
from oceanbase_tpu.server.database import Database
from oceanbase_tpu.share import gap_ledger as GL

from test_mysql_front import MiniMySqlClient


# ---- device side: scopes and program names ------------------------------------


@pytest.fixture(scope="module")
def tpch_session():
    return Session(datagen.generate(0.005), unique_keys=UNIQUE_KEYS)


@pytest.mark.parametrize("q", [3, 14])
def test_lowered_plan_carries_scopes_and_program_name(tpch_session, q):
    sess = tpch_session
    sess.sql(QUERIES[q]).rows()
    entry, qp = sess.cached_entry(QUERIES[q])
    prepared = entry.prepared
    assert re.fullmatch(r"ob_select_[0-9a-f]{8}", prepared.jitted.__name__)
    assert prepared._narrow, "the CPU run did not take the fused frame"
    fn = next(iter(prepared._narrow.values()))
    lowered = fn.lower(prepared._inputs(), qp)
    text = lowered.as_text(debug_info=True)
    assert re.search(r"module @jit_ob_select_[0-9a-f]{8}_narrow\b", text)
    locs = re.findall(r'loc\("([^"]*)"', text)
    for want in (r"Join:inner#\d+", r"Aggregate#\d+", r"Scan#\d+",
                 r"#\d+/expr(/|$)", r"(^|/)frame(/|$)"):
        assert any(re.search(want, n) for n in locs), want
    # children nest inside parents: a scan's scope sits under its join's
    assert any(re.search(r"Join:inner#\d+/Scan#\d+", n) for n in locs)
    # and the compiled module keeps them as op_name metadata
    ops = re.findall(r'op_name="([^"]*)"', lowered.compile().as_text())
    assert any(re.search(r"Aggregate#\d+/.*expr", n) for n in ops)
    assert any("/frame/" in n for n in ops)


def test_program_name_follows_the_plan_not_its_literals(tpch_session):
    sess = tpch_session
    a = QUERIES[14]
    b = a.replace("1995-09-01", "1996-03-01").replace("1995-10-01",
                                                      "1996-04-01")
    assert a != b
    names = []
    for text in (a, b, QUERIES[3]):
        sess.sql(text).rows()
        names.append(sess.cached_entry(text)[0].prepared.jitted.__name__)
    assert names[0] == names[1] != names[2]


# ---- host side: the ledger's phases in the profiler's trace ------------------


def _mkdb():
    db = Database(n_nodes=1, n_ls=1)
    s = db.session()
    s.sql("create table kv (id int primary key, k int, v int)")
    s.sql("insert into kv values " + ", ".join(
        f"({i + 1}, {i}, {i * 7 + 3})" for i in range(200)))
    s.sql("set ob_enable_result_cache = 0")
    for k in range(4):  # warm: fast tier, index route, group-by
        for text in _statements(k):
            s.sql(text).rows()
    return db, s


def _statements(k):
    return (f"select v from kv where k = {k}",
            f"select v from kv where id = {k + 1}",
            f"select sum(v) from kv where k < {k + 1} group by k % 3")


@pytest.fixture(scope="module")
def served():
    db, s = _mkdb()
    yield db, s
    db.close()


def _ob_events(logdir):
    """[(thread line, start_ns, dur_ns, phase, stats)] of every ob: leaf."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        # a host thread is a line; Python's threads all carry the
        # process's name, so the line's position tells them apart
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(GL.TRACE_PREFIX):
                    out.append((f"{line.name}#{i}", ev.start_ns,
                                ev.duration_ns,
                                ev.name[len(GL.TRACE_PREFIX):],
                                {k: v for k, v in ev.stats}))
    return out


def _trace(tmp_path, body):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    return _ob_events(str(tmp_path))


def test_ledger_phases_are_leaves_of_the_profile(served, tmp_path):
    db, s = served
    ledgers = {}

    def body():
        s.sql("select v from kv where k = 9").rows()  # the session's first
        for k in range(10, 14):
            for text in _statements(k):
                s.sql(text).rows()
                led = s._gap
                assert led.stmt == s._stmt_id  # a session was active
                ledgers[led.stmt] = (dict(led.phases), text)

    events = _trace(tmp_path, body)
    s.sql("select 1").rows()
    assert s._gap.stmt == 0  # and is over: nothing is annotated now

    by_stmt = collections.defaultdict(lambda: collections.defaultdict(float))
    threads = collections.defaultdict(set)
    for line, _s, dur, phase, stats in events:
        by_stmt[stats["stmt"]][phase] += dur / 1e9
        threads[stats["stmt"]].add(line)
    assert set(ledgers) <= set(by_stmt)
    # blocked phases are ledger phases, never annotations
    assert not {ph for d in by_stmt.values() for ph in d} & GL.BLOCKED_PHASES
    agree = []
    for stmt, (phases, text) in ledgers.items():
        got = by_stmt[stmt]
        assert len(threads[stmt]) == 1, text  # its own thread only
        want = {p: v for p, v in phases.items()
                if p not in GL.BLOCKED_PHASES}
        # every phase the ledger recorded is there, under its name
        assert set(want) <= set(got), (text, set(want) - set(got))
        # within 5 %, and the few microseconds that pass between one
        # leaf closing and the next opening (ten a statement; they weigh
        # on these sub-millisecond CPU statements, not on the chip's)
        agree.append(
            all(got[p] == pytest.approx(want[p], rel=0.05, abs=50e-6)
                for p in ("device dispatch", "device wait", "fast lookup",
                          "plan compile", "parse bind") if p in want)
            and sum(got.values()) == pytest.approx(
                sum(want.values()), rel=0.05, abs=150e-6))
    # the ledger's clock and the profiler's are read microseconds apart:
    # a statement whose thread lost the CPU in between may disagree, the
    # others must not
    assert sum(agree) >= len(agree) - 2, agree
    # leaves never overlap on a thread (one open annotation at a time)
    per_thread = collections.defaultdict(list)
    for line, start, dur, _p, _st in events:
        per_thread[line].append((start, start + dur))
    for ivs in per_thread.values():
        ivs.sort()
        for (_a0, a1), (b0, _b1) in zip(ivs, ivs[1:]):
            assert a1 <= b0 + 1
    # the digest, known only at completion, rides the last leaf; the
    # `sql` span of SHOW TRACE carries the same statement id
    tagged = [st for _l, _s, _d, p, st in events if "digest" in st]
    assert {st["stmt"] for st in tagged} >= set(ledgers)
    spans = {sp.tags.get("stmt") for sp in db.tracer.spans()
             if sp.name == "sql"}
    assert set(ledgers) <= spans


def test_wire_statements_are_annotated_on_pool_threads(served, tmp_path):
    db, s = served
    fe = AsyncMySqlFrontend(db).start()
    try:
        c = MiniMySqlClient(fe.port)
        c.query("set ob_enable_result_cache = 0")
        for k in range(3):
            c.query(f"select v from kv where k = {k}")
        mine = []

        def body():
            s.sql("select v from kv where k = 19").rows()  # this thread
            mine.append(s._stmt_id)
            for k in range(20, 30):
                c.query(f"select v from kv where k = {k}")

        tax0 = db.host_tax.snapshot()["digests"]
        events = _trace(tmp_path, body)
        tax1 = db.host_tax.snapshot()["digests"]
        c.close()
    finally:
        fe.stop()
    here = {line for line, *_r, st in events if st["stmt"] == mine[0]}
    pool = {line for line, *_r, st in events if st["stmt"] != mine[0]}
    assert len(here) == 1 and pool and not here & pool
    assert len({st["stmt"] for *_r, st in events}) == 11
    # over the traced window the leaves sum to the registry's deltas of
    # the same phases
    got = collections.defaultdict(float)
    for _l, _s, dur, phase, _st in events:
        got[phase] += dur / 1e9
    want = collections.defaultdict(float)
    for dg, a in tax1.items():
        for ph, v in a["phases"].items():
            want[ph] += v - tax0.get(dg, {"phases": {}})["phases"].get(ph, 0)
    # wire read and wire write are folded in by the front end after the
    # statement's ledger closed: they have no thread of the statement's
    names = [p for p in got if p not in ("wire read", "wire write")]
    assert {"device dispatch", "device wait", "fast lookup"} <= set(names)
    # (less the few microseconds between one leaf closing and the next
    # opening, which weigh on sub-millisecond CPU statements under an
    # event loop that shares the interpreter lock; never more)
    leaves = sum(got[p] for p in names)
    ledger = sum(want[p] for p in names)
    assert 0.7 * ledger <= leaves <= 1.02 * ledger + 1e-4, (leaves, ledger)


class _CountingAnnotation:
    """Stands in for jax.profiler.TraceAnnotation with no session."""

    built = 0
    asked = 0

    def __init__(self, *a, **kw):
        type(self).built += 1

    @classmethod
    def is_enabled(cls):
        cls.asked += 1
        return False


def test_no_profiler_session_builds_no_annotation(served, monkeypatch):
    _db, s = served
    monkeypatch.setattr(GL, "_TraceAnnotation", _CountingAnnotation)
    _CountingAnnotation.built = _CountingAnnotation.asked = 0
    n = 0
    for k in range(30, 34):
        for text in _statements(k):
            s.sql(text).rows()
            n += 1
            assert s._gap.stmt == 0 and s._gap._ann is None
    assert _CountingAnnotation.built == 0
    assert _CountingAnnotation.asked == n  # one read per statement


class _RecordingAnnotation:
    log = []

    def __init__(self, name, **stats):
        self.name, self.stats = name, stats

    @staticmethod
    def is_enabled():
        return True

    def __enter__(self):
        self.log.append(("open", self.name, self.stats))

    def __exit__(self, *exc):
        self.log.append(("close", self.name, self.stats))

    def set_metadata(self, **stats):
        self.stats.update(stats)


def test_one_annotation_is_open_at_a_time_and_blocked_phases_are_not(
        monkeypatch):
    monkeypatch.setattr(GL, "_TraceAnnotation", _RecordingAnnotation)
    _RecordingAnnotation.log = log = []
    led = GL.GapLedger().begin(stmt=7)
    led.cut("setup", "admission queue")       # parked: nothing opens
    led.cut("admission queue", "setup")
    led.cut("setup", "setup")                 # same phase: stays open
    led.cut("setup", "fast lookup")
    led.cut("fast lookup")
    led.window_start("engine host")
    led.leaf("device dispatch")
    led.leaf_end()
    led.leaf(None)                            # a governor wait
    led.add("governor reserve", 0.001)
    led.leaf_end()
    led.leaf("device wait")
    led.leaf_end()
    led.window_end("engine host")
    led.tag(digest="d")
    led.cut("completion fold")
    led.close()
    opened = [n for what, n, _ in log if what == "open"]
    assert opened == ["ob:setup", "ob:setup", "ob:fast lookup",
                      "ob:engine host", "ob:device dispatch",
                      "ob:engine host", "ob:engine host", "ob:device wait",
                      "ob:engine host", "ob:completion fold"]
    depth = 0
    for what, _n, stats in log:
        depth += 1 if what == "open" else -1
        assert depth in (0, 1) and stats["stmt"] == 7
    assert depth == 0 and log[-1][2]["digest"] == "d"
    # a statement no session offers an id for is never annotated
    log.clear()
    led.begin()
    led.cut("setup", "fast lookup")
    led.close()
    assert not log


# ---- thread CPU seconds per digest ----------------------------------------------


def test_cpu_seconds_ride_the_host_tax_registry(served):
    db, s = served

    def mine():
        return {d: a for d, a in db.host_tax.snapshot()["digests"].items()
                if "from kv where k = " in str(d)}

    for k in range(40, 44):
        s.sql(f"select v from kv where k = {k}").rows()
    (dg, a0), = mine().items()
    assert 0.0 < a0["cpu_s"] <= a0["e2e_s"]
    for k in range(44, 52):
        s.sql(f"select v from kv where k = {k}").rows()
    a1 = mine()[dg]
    assert a1["count"] == a0["count"] + 8
    assert a0["cpu_s"] < a1["cpu_s"] <= a1["e2e_s"]
    rows = s.sql("select digest, executions, cpu_us, e2e_us "
                 "from __all_virtual_host_tax").rows()
    row = next(r for r in rows if r[0] == str(dg))
    assert 0 < int(row[2]) <= int(row[3])
    assert int(row[2]) >= int(a1["cpu_s"] * 1e6)


# ---- the front end's pool hand-off wait -------------------------------------------


def test_front_pool_queue_counts_each_served_statement(served):
    db, _s = served
    fe = AsyncMySqlFrontend(db).start()
    try:
        c = MiniMySqlClient(fe.port)
        c.query("select v from kv where k = 1")  # its digest is tracked

        def wire_read():
            return sum(a["phases"].get("wire read", 0.0) for a in
                       db.host_tax.snapshot()["digests"].values())

        w0 = db.metrics.wait_event("front pool queue")
        count0, total0 = w0.count, w0.total_s
        depth0 = db.metrics.counter("front pool depth")
        read0 = wire_read()
        n = 25
        for k in range(n):
            c.query(f"select v from kv where k = {k}")
        c.close()
    finally:
        fe.stop()
    w1 = db.metrics.wait_event("front pool queue")
    assert w1.count - count0 == n
    assert w1.total_s - total0 == pytest.approx(wire_read() - read0,
                                                rel=1e-9, abs=1e-12)
    assert w1.max_s >= (w1.total_s - total0) / n > 0.0
    # one connection, one statement in flight: nobody was ahead of it
    assert db.metrics.counter("front pool depth") == depth0
    names = [r[0] for r in db.session().sql(
        "select event from __all_virtual_system_event").rows()]
    assert "front pool queue" in names
