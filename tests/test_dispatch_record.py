"""One way to run a prepared plan, and the record rides the result (ISSUE 30).

`PreparedPlan.dispatch` returns the cursor and the cursor carries the
execution's record (phases, profile, operator profile); the result set hands
it on. Nothing of a statement is left on the engine session that every
worker shares, so a worker can only account a statement with that
statement's own numbers.
"""

from __future__ import annotations

import threading

import pytest

from oceanbase_tpu.engine import Session
from oceanbase_tpu.server.database import Database, SqlError
from oceanbase_tpu.share.config import ConfigError


@pytest.fixture(scope="module")
def db():
    d = Database(n_nodes=1, n_ls=1)
    s = d.session()
    s.sql("create table small (k int primary key, v int)")
    s.sql("insert into small values " + ", ".join(
        f"({i}, {i * 7 % 13})" for i in range(64)))
    s.sql("create table big (k int primary key, g int, v int)")
    for lo in range(0, 40000, 4000):
        s.sql("insert into big values " + ", ".join(
            f"({i}, {i % 97}, {i * 3 % 101})" for i in range(lo, lo + 4000)))
    yield d
    d.close()


# two statements whose device halves differ by orders of magnitude: a
# worker that accounts one with the other's phases is out by as much
POINT = "select v from small where v = {}"
HEAVY = "select g, sum(v), count(*) from big where v > {} group by g order by g"


def test_a_statement_is_carved_with_its_own_phases(db):
    """Eight threads, two statements, one Database: each statement's
    ledger shows the `device dispatch` and `device wait` of its own
    result's phases, whatever its peers were doing meanwhile."""
    warm = db.session()
    for text in (POINT, HEAVY):
        for lit in (1, 2):
            warm.sql(text.format(lit)).rows()
    nthreads, reps = 8, 12
    barrier = threading.Barrier(nthreads)
    checked, wrong, errors = [], [], []

    def worker(i: int) -> None:
        try:
            s = db.session()
            # every statement runs, alone: no cached frame, no cohort
            s.sql("set ob_enable_result_cache = 0")
            s.sql("set ob_batch_max_size = 1")
            text = (POINT, HEAVY)[i % 2]
            barrier.wait(timeout=60)
            for rep in range(reps):
                rs = s.sql(text.format(3 + rep % 5))
                led, ph = s._gap, rs.phases
                assert led is not None and led.closed and ph is not None
                assert rs.fast_path_hit, "the warm statement left the tier"
                want = {"device dispatch": ph["dispatch_s"],
                        "device wait": ph["fetch_s"] - ph.get("d2h_s", 0.0)}
                got = {k: led.phases.get(k, 0.0) for k in want}
                checked.append(i)
                if got != pytest.approx(want, rel=1e-6, abs=1e-12):
                    wrong.append((i, rep, got, want))
        except BaseException as e:  # noqa: BLE001 - reported below
            errors.append((i, repr(e)))

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(nthreads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert not errors, errors
    assert len(checked) == nthreads * reps
    assert not wrong, (
        f"{len(wrong)} of {len(checked)} statements were carved with "
        f"another statement's phases, e.g. {wrong[0]}")


def test_the_engine_session_keeps_nothing_of_a_statement(db):
    s = db.session()
    s.sql("set ob_enable_result_cache = 0")
    rs = s.sql(HEAVY.format(50))
    assert rs.phases["rows"] == rs.nrows and rs.profile.dispatch_s > 0.0
    assert rs.plan is not None and rs.op_profile is None
    assert rs.record() == {"profile": rs.profile, "phases": rs.phases,
                           "plan": rs.plan, "op_profile": None}
    for name in ("last_phases", "last_profile", "last_plan",
                 "last_op_profile"):
        assert not hasattr(db.engine, name)
    # a statement that never reached the engine has no record, and a DML
    # statement has its qualification scan's
    ddl = s.sql("create table scratch (k int primary key, v int)")
    assert ddl.record() == dict.fromkeys(
        ("profile", "phases", "plan", "op_profile"))
    s.sql("insert into scratch values (1, 1), (2, 2), (3, 3)")
    upd = s.sql("update scratch set v = v + 1 where v >= 2")
    assert upd.affected == 2 and upd.phases["rows"] == 2
    assert upd.profile is not None and upd.plan is not None
    s.sql("drop table scratch")


def test_a_json_split_statement_takes_the_same_route(db):
    """The eager result of a JSON-split statement is the cursor's columns
    (there is no second fetch path) and carries the same record."""
    s = db.session()
    rs = s.sql("select json_object('k', k, 'v', v) as j from small "
               "where k < 3 order by k")
    assert rs.rows() == [('{"k": 0, "v": 0}',), ('{"k": 1, "v": 7}',),
                         ('{"k": 2, "v": 1}',)]
    assert rs.phases["rows"] == 3 and rs.phases["fetch_s"] > 0.0
    assert rs.profile is not None and rs.plan is not None


@pytest.mark.parametrize("name,value", [
    ("ob_enable_result_narrow", "false"),
    ("ob_result_narrow_rows", "128"),
    ("ob_result_narrow_max_rows", "1024"),
    ("ob_enable_completion_drain", "true"),
    ("ob_completion_drain_depth", "64"),
])
def test_a_removed_parameter_is_an_unknown_one(db, name, value):
    s = db.session()

    def refusal(n: str) -> str:
        with pytest.raises((SqlError, ConfigError)) as e:
            s.sql(f"alter system set {n} = {value}")
        return str(e.value).replace(n, "<name>")

    assert refusal(name) == refusal("ob_no_such_parameter_at_all")
    with pytest.raises(ConfigError):
        db.config[name]


def test_every_dispatchable_plan_has_the_one_entry():
    from oceanbase_tpu.engine.chunked import ChunkedPreparedPlan
    from oceanbase_tpu.engine.executor import Dispatchable, PreparedPlan
    from oceanbase_tpu.engine.pipeline import GraceHashPreparedPlan

    for cls in (PreparedPlan, ChunkedPreparedPlan, GraceHashPreparedPlan):
        assert issubclass(cls, Dispatchable)
        assert "dispatch" in vars(cls) and "run" not in vars(cls)
        for gone in ("run_host", "run_nocheck", "run_device",
                     "run_device_narrow", "narrow_frame"):
            assert not hasattr(cls, gone), (cls.__name__, gone)
    assert not hasattr(Session, "narrow_enabled_fn")
