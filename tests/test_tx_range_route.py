"""A range on a single-column integer primary key inside a transaction
whose table has an open writer reads only its key range from the tablet at
the BEGIN snapshot (DbSession._range_route), not a rescan of the whole
table. Every answer must equal the rescan's at the same snapshot."""

import threading

import jax
import numpy as np
import pytest

from oceanbase_tpu.core.dtypes import DataType, Schema
from oceanbase_tpu.server import Database
from oceanbase_tpu.server.database import DbSession
from oceanbase_tpu.storage import OP_DELETE, OP_PUT, Tablet, scan_merge

ROUTE = "tx range route reads"
SHARED = "tx snapshot shared reads"
PRIVATE = "tx snapshot private reads"
N = 400
# the four ranges of sysbench's oltp_read_only / oltp_read_write
SHAPES = (
    "select c from rr_t where id between {a} and {b}",
    "select sum(k) as s from rr_t where id between {a} and {b}",
    "select c from rr_t where id between {a} and {b} order by c",
    "select distinct c from rr_t where id between {a} and {b} order by c",
)


def _row(i: int) -> str:
    return f"({i}, {7 * i % 101}, 'c{i % 37}')"


def _rows(db, n=N):
    s = db.session()
    s.sql("create table rr_t (id bigint primary key, k bigint not null,"
          " c varchar(16) not null)")
    s.sql("insert into rr_t values " + ", ".join(
        _row(i) for i in range(1, n + 1)))


@pytest.fixture(scope="module")
def db():
    d = Database(n_nodes=3, n_ls=2)
    _rows(d)
    return d


@pytest.fixture
def writer(db):
    """Another session holding a writer open on rr_t for the test."""
    w = db.session()
    w.sql("begin")
    w.sql(f"update rr_t set k = k where id = {N}")
    assert db.tables["rr_t"].writers >= 1
    yield w
    w.sql("rollback")


def _counts(db):
    return tuple(db.metrics.counter(n) for n in (ROUTE, SHARED, PRIVATE))


def _moved(db, c0):
    return tuple(b - a for a, b in zip(c0, _counts(db)))


def _both(db, s, q, monkeypatch):
    """(the route's answer, the rescan's) to `q` in `s`'s open
    transaction: one snapshot, the route turned off for the second."""
    c0 = _counts(db)
    got = sorted(s.sql(q).rows())
    moved = _moved(db, c0)
    monkeypatch.setattr(DbSession, "_range_route", lambda self, ast: None)
    c0 = _counts(db)
    want = sorted(s.sql(q).rows())
    assert _moved(db, c0) == (0, 0, 1)
    monkeypatch.undo()
    return got, want, moved


@pytest.mark.parametrize("shape", SHAPES)
def test_sysbench_ranges_under_an_open_writer(db, writer, shape,
                                              monkeypatch):
    s = db.session()
    s.sql("begin")
    for a in (1, 150, 301):
        q = shape.format(a=a, b=a + 99)
        got, want, moved = _both(db, s, q, monkeypatch)
        assert got == want and got
        assert moved == (1, 0, 0)
    s.sql("commit")


def test_a_commit_after_begin_inside_the_range_is_not_seen(
        db, writer, monkeypatch):
    s, o = db.session(), db.session()
    q = "select id, k, c from rr_t where id between 40 and 60"
    s.sql("begin")
    before = sorted(s.sql(q).rows())
    o.sql("update rr_t set k = k + 1000 where id = 50")
    o.sql("delete from rr_t where id = 51")
    o.sql("insert into rr_t values (100000, 1, 'x')")
    got, want, moved = _both(db, s, q, monkeypatch)
    assert got == want == before and moved == (1, 0, 0)
    s.sql("commit")
    after = dict((r[0], r[1:]) for r in s.sql(q).rows())
    assert after[50][0] == dict((r[0], r[1:]) for r in before)[50][0] + 1000
    assert 51 not in after
    o.sql("update rr_t set k = k - 1000 where id = 50")
    o.sql(f"insert into rr_t values {_row(51)}")
    o.sql("delete from rr_t where id = 100000")


def test_own_writes_inside_the_range_are_seen(db, monkeypatch):
    s = db.session()
    q = "select id, k, c from rr_t where id >= 70 and id < 90"
    s.sql("begin")
    s.sql("update rr_t set k = -5, c = 'mine' where id = 72")
    s.sql("delete from rr_t where id = 73")
    s.sql("insert into rr_t values (100001, 3, 'far')")
    s.sql("delete from rr_t where id = 74")
    s.sql("insert into rr_t values (74, 9, 'again')")
    got, want, moved = _both(db, s, q, monkeypatch)
    assert got == want and moved == (1, 0, 0)
    rows = dict((r[0], r[1:]) for r in got)
    assert rows[72] == (-5, "mine") and 73 not in rows
    assert rows[74] == (9, "again") and len(rows) == 19
    s.sql("rollback")
    assert db.session().sql(
        "select c from rr_t where id = 72").rows() != [("mine",)]


def test_deleted_and_reinserted_ids(db, writer, monkeypatch):
    o, s = db.session(), db.session()
    o.sql("delete from rr_t where id = 90")
    o.sql("delete from rr_t where id = 91")
    o.sql("insert into rr_t values (90, 5, 'back')")
    q = "select id, k, c from rr_t where id between 85 and 95"
    s.sql("begin")
    got, want, moved = _both(db, s, q, monkeypatch)
    assert got == want and moved == (1, 0, 0)
    ids = [r[0] for r in got]
    assert 91 not in ids and (90, 5, "back") in got
    s.sql("commit")
    o.sql("delete from rr_t where id = 90")
    o.sql(f"insert into rr_t values {_row(90)}, {_row(91)}")


@pytest.mark.parametrize("where", [
    "id between 50 and 49",         # reversed: no key lies between
    "id between 1000000 and 1000100",  # empty: past the last key
    "id between 9.5 and 20.5",      # whole numbers outward, WHERE decides
    "id > 9.5 and id <= 20.000001",
    "id >= -3 and id < 4",
    "20 >= id and id > 10",         # the literal on the left
    "id between 5 and 10 and k > 30",  # another conjunct on the device
    "rr_t.id between 5 and 15",
    "id between 1 + 1 and 3 * 4",
])
def test_bounds(db, writer, where, monkeypatch):
    s = db.session()
    s.sql("begin")
    got, want, moved = _both(
        db, s, f"select id, k from rr_t where {where}", monkeypatch)
    assert got == want and moved == (1, 0, 0)
    s.sql("commit")


@pytest.mark.parametrize("where", [
    "id between k and 20",          # a bound that is no literal
    "id >= 5",                      # one side open
    "k between 5 and 20",           # not the key
    "id between 5 and 20 or k = 3",  # no range conjunct
    "id not between 5 and 390",
])
def test_falls_back_to_the_rescan(db, writer, where):
    s = db.session()
    q = f"select id, k from rr_t where {where} order by id"
    want = s.sql(q).rows()
    s.sql("begin")
    c0 = _counts(db)
    assert s.sql(q).rows() == want
    assert _moved(db, c0) == (0, 0, 1)
    s.sql("commit")


def test_string_bounds_are_left_to_the_engine(db, writer):
    """A string bound is no number: the route reads nothing for it, and
    the statement meets the engine as it does outside a transaction."""
    q = "select id from rr_t where id between '5' and '20'"
    s = db.session()
    with pytest.raises(Exception) as outside:
        s.sql(q)
    s.sql("begin")
    c0 = _counts(db)
    with pytest.raises(type(outside.value)):
        s.sql(q)
    assert _moved(db, c0)[0] == 0
    s.sql("rollback")


def test_more_rows_than_the_route_reads_fall_back(db, writer, monkeypatch):
    q = "select count(*) as n from rr_t where id between 1 and 50"
    s = db.session()
    s.sql("begin")
    monkeypatch.setattr(DbSession, "_INDEX_ROUTE_MAX_ROWS", 49)
    c0 = _counts(db)
    assert s.sql(q).rows() == [(50,)]
    assert _moved(db, c0) == (0, 0, 1)
    monkeypatch.setattr(DbSession, "_INDEX_ROUTE_MAX_ROWS", 50)
    c0 = _counts(db)
    assert s.sql(q).rows() == [(50,)]
    assert _moved(db, c0) == (1, 0, 0)
    s.sql("commit")


def test_a_composite_key_falls_back():
    d = Database(n_nodes=3, n_ls=2)
    s, w = d.session(), d.session()
    s.sql("create table ck_t (a bigint not null, b bigint not null,"
          " v bigint not null, primary key (a, b))")
    s.sql("insert into ck_t values " + ", ".join(
        f"({i % 10}, {i}, {i * 3})" for i in range(60)))
    q = "select b, v from ck_t where a between 2 and 4 order by b"
    want = s.sql(q).rows()
    w.sql("begin")
    w.sql("update ck_t set v = 0 where a = 9 and b = 59")
    s.sql("begin")
    c0 = _counts(d)
    assert s.sql(q).rows() == want and len(want) == 18
    assert _moved(d, c0) == (0, 0, 1)
    s.sql("commit")
    w.sql("rollback")


def test_a_partitioned_table(monkeypatch):
    d = Database(n_nodes=3, n_ls=2)
    s, w = d.session(), d.session()
    s.sql("create table pr_t (id bigint primary key, k bigint not null)"
          " partition by hash(id) partitions 4")
    s.sql("insert into pr_t values " + ", ".join(
        f"({i}, {i * i % 53})" for i in range(1, 201)))
    w.sql("begin")
    w.sql("update pr_t set k = 0 where id = 200")
    s.sql("begin")
    s.sql("update pr_t set k = -1 where id = 33")
    s.sql("delete from pr_t where id = 34")
    q = "select id, k from pr_t where id between 20 and 60"
    got, want, moved = _both(d, s, q, monkeypatch)
    assert got == want and len(got) == 40 and moved == (1, 0, 0)
    assert (33, -1) in got
    s.sql("rollback")
    w.sql("rollback")


def test_counted_only_where_the_table_has_an_open_writer(db):
    s = db.session()
    q = "select sum(k) as s from rr_t where id between 10 and 30"
    want = s.sql(q).rows()  # autocommit
    c0 = _counts(db)
    assert s.sql(q).rows() == want
    assert _moved(db, c0) == (0, 0, 0)
    s.sql("begin")  # a clean table: the shared entry
    assert s.sql(q).rows() == want
    assert _moved(db, c0) == (0, 1, 0)
    s.sql("commit")
    w = db.session()
    w.sql("begin")
    w.sql("update rr_t set k = k where id = 399")
    s.sql("begin")
    c0 = _counts(db)
    assert s.sql(q).rows() == want
    assert _moved(db, c0) == (1, 0, 0)
    s.sql("commit")
    w.sql("rollback")


def test_reads_agree_under_a_concurrent_writer(db):
    stop = threading.Event()
    errors: list = []

    def write():
        w = db.session()
        try:
            while not stop.is_set():
                w.sql("begin")
                w.sql("update rr_t set k = k + 1 where id = 120")
                w.sql("update rr_t set k = k - 1 where id = 130")
                w.sql("commit")
        except Exception as e:  # noqa: BLE001 - reported below
            errors.append(e)

    th = threading.Thread(target=write)
    th.start()
    r = db.session()
    q = "select id, k from rr_t where id between 120 and 130 order by id"
    try:
        for _ in range(20):
            r.sql("begin")
            a = r.sql(q).rows()
            b = r.sql(q).rows()
            r.sql("commit")
            assert a == b
            # one commit moves both ids: a snapshot holds it whole or not
            assert a[0][1] + a[-1][1] == (7 * 120 % 101) + (7 * 130 % 101)
    finally:
        stop.set()
        th.join()
    assert not errors


def _compiles():
    seen = [0]

    def on(event, _s, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            seen[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on)
    return seen


def test_live_rows_up_to_a_capacity_share_one_program():
    """99 and 100 live rows run one compiled program, and a statement
    first run on the shared entry has its route programs compiled before
    a writer ever opens on the table."""
    d = Database(n_nodes=3, n_ls=2)
    _rows(d, n=1500)  # the shared entry's capacity 2,048, the route's 1,024
    compiles = _compiles()
    s, w = d.session(), d.session()
    for a in (1, 11):  # the plans, on the shared entry
        s.sql("begin")
        for shape in SHAPES:
            s.sql(shape.format(a=a, b=a + 99))
        s.sql("commit")
    assert all(d.range_shapes.values()) and len(d.range_shapes) >= 4
    w.sql("begin")
    w.sql("update rr_t set k = k where id = 1500")
    s.sql("begin")
    s.sql("delete from rr_t where id = 250")
    c0, n0 = compiles[0], d.metrics.counter(ROUTE)
    for a in (201, 150):  # 99 live rows, then 100
        for shape in SHAPES:
            s.sql(shape.format(a=a, b=a + 99))
    assert d.metrics.counter(ROUTE) == n0 + 8
    assert compiles[0] == c0
    s.sql("rollback")
    w.sql("rollback")


SCHEMA = Schema.of(k=DataType.int64(), a=DataType.int32())


def test_memtable_rows_outside_the_key_range_are_not_built(monkeypatch):
    """scan_merge with a key range returns what the unfiltered merge
    returns inside it, across sstables, tombstones, newer versions and
    the reader's own staged rows; the memtable builds only the range."""
    from oceanbase_tpu.storage import memtable as M

    rng = np.random.default_rng(5)
    t = Tablet(1, SCHEMA, ["k"])
    keys = rng.choice(2000, 600, replace=False)
    for k in keys:
        t.stage(1, 0, (int(k),), OP_PUT, (int(k), int(k) % 13))
    t.active.commit(1, 10)
    t.freeze()
    t.dump_mini()
    some = sorted(int(k) for k in keys)
    for k in some[::3]:
        t.stage(2, 10, (k,), OP_DELETE, None)
    for k in some[1::3]:
        t.stage(2, 10, (k,), OP_PUT, (k, -k))
    t.active.commit(2, 20)
    for k in some[2::5]:
        t.stage(7, 20, (k,), OP_PUT, (k, 99))  # tx 7's own, uncommitted
    built = []
    rows_of = M.Memtable.snapshot_rows

    def counted(self, *a, **kw):
        out = rows_of(self, *a, **kw)
        built.append(len(out))
        return out

    monkeypatch.setattr(M.Memtable, "snapshot_rows", counted)
    ssts = [t.base] if t.base else list(t.deltas)
    assert ssts
    for snap, tx in ((20, 7), (20, 0), (10, 0), (15, 7)):
        full = scan_merge(SCHEMA, ["k"], ssts, [t.active], snap, tx_id=tx)
        n_all = built[-1]
        for lo, hi in ((300.0, 900.0), (0.0, 5.0), (1500.0, 1500.0),
                       (-10.0, 3000.0), (700.0, 600.0)):
            got = scan_merge(SCHEMA, ["k"], ssts, [t.active], snap,
                             ranges={"k": (lo, hi)}, tx_id=tx)
            m = (full["k"] >= lo) & (full["k"] <= hi)
            for c in ("k", "a"):
                np.testing.assert_array_equal(got[c], full[c][m])
            assert built[-1] <= n_all
            if hi - lo < 100 and n_all:
                assert built[-1] < n_all
