"""A SELECT inside a transaction takes the autocommit route where the table
is clean for the transaction's snapshot (Database.tx_shared_entry): the
index route at the BEGIN snapshot for a table it has not written, else the
shared committed entry. Every answer must equal the rescan's."""

import threading

import pytest

from oceanbase_tpu.server import Database

SHARED = "tx snapshot shared reads"
PRIVATE = "tx snapshot private reads"
# a list of keys, not a key range: the shared entry or the rescan answers
# it, never the range route (tests/test_tx_range_route.py)
RANGE = "select id, k, c from sr_t where id in (2, 3, 4, 5, 6) order by id"


@pytest.fixture(scope="module")
def db():
    d = Database(n_nodes=3, n_ls=2)
    s = d.session()
    s.sql("create table sr_t (id bigint primary key, k bigint not null,"
          " c varchar(16) not null)")
    s.sql("insert into sr_t values " + ", ".join(
        f"({i}, {10 * i}, 'c{i}')" for i in range(1, 21)))
    return d


def _counts(db):
    return db.metrics.counter(SHARED), db.metrics.counter(PRIVATE)


def _moved(db, before):
    sh, pr = _counts(db)
    return sh - before[0], pr - before[1]


def test_clean_table_takes_the_shared_route(db):
    s = db.session()
    want = s.sql(RANGE).rows()  # autocommit: the shared entry
    s.sql("begin")
    c0 = _counts(db)
    got = s.sql(RANGE).rows()
    assert _moved(db, c0) == (1, 0)
    assert s.sql("select sum(k) as s from sr_t where id between 1 and 20"
                 ).rows()[0][0] == sum(10 * i for i in range(1, 21))
    assert s.sql("select c from sr_t where id = 4").rows() == [("c4",)]
    assert _moved(db, c0) == (3, 0)
    s.sql("commit")
    assert got == want
    # the same statement on the rescan: a transaction that wrote the table
    s.sql("begin")
    s.sql("update sr_t set k = k where id = 20")
    c0 = _counts(db)
    assert s.sql(RANGE).rows() == want
    assert _moved(db, c0) == (0, 1)
    s.sql("rollback")


def test_commit_between_begin_and_first_read_is_not_seen(db):
    s1, s2 = db.session(), db.session()
    before = s2.sql(RANGE).rows()
    s1.sql("begin")
    s2.sql("update sr_t set k = k + 1 where id = 3")
    c0 = _counts(db)
    assert s1.sql(RANGE).rows() == before
    assert _moved(db, c0) == (0, 1)
    s1.sql("commit")
    assert s1.sql(RANGE).rows() != before


def test_commit_between_two_reads_of_one_transaction(db):
    s1, s2 = db.session(), db.session()
    s2.sql(RANGE)
    s1.sql("begin")
    c0 = _counts(db)
    first = s1.sql(RANGE).rows()
    assert _moved(db, c0) == (1, 0)
    s2.sql("update sr_t set c = 'moved' where id = 5")
    c0 = _counts(db)
    assert s1.sql(RANGE).rows() == first
    assert _moved(db, c0) == (0, 1)
    s1.sql("commit")
    assert ("moved",) in [r[2:] for r in s1.sql(RANGE).rows()]


def test_point_select_reads_the_begin_snapshot_through_the_index_route(db):
    s1, s2 = db.session(), db.session()
    old = s2.sql("select k from sr_t where id = 7").rows()
    s1.sql("begin")
    s2.sql("update sr_t set k = -1 where id = 7")
    c0 = _counts(db)
    assert s1.sql("select k from sr_t where id = 7").rows() == old
    assert _moved(db, c0) == (1, 0)
    s1.sql("commit")
    assert s1.sql("select k from sr_t where id = 7").rows() == [(-1,)]


def test_a_transaction_that_wrote_the_table_sees_its_own_rows(db):
    s1, s2 = db.session(), db.session()
    s1.sql("begin")
    s1.sql("insert into sr_t values (100, 1000, 'mine')")
    c0 = _counts(db)
    assert s1.sql("select c from sr_t where id = 100").rows() == [("mine",)]
    assert s1.sql("select count(*) as n from sr_t where id >= 100"
                  ).rows() == [(1,)]
    assert _moved(db, c0) == (0, 2)
    # another session sees nothing of it, and reads on the shared route
    assert s2.sql("select c from sr_t where id = 100").rows() == []
    s1.sql("rollback")
    assert s1.sql("select c from sr_t where id = 100").rows() == []


def test_reads_of_one_transaction_agree_under_a_concurrent_writer(db):
    stop = threading.Event()
    errors: list = []

    def writer():
        w = db.session()
        try:
            while not stop.is_set():
                w.sql("begin")
                w.sql("update sr_t set k = k + 1 where id = 2")
                w.sql("update sr_t set k = k + 1 where id = 6")
                w.sql("commit")
        except Exception as e:  # noqa: BLE001 - reported below
            errors.append(e)

    th = threading.Thread(target=writer)
    th.start()
    r = db.session()
    c0 = _counts(db)
    try:
        for _ in range(25):
            r.sql("begin")
            a = r.sql(RANGE).rows()
            p = r.sql("select k from sr_t where id = 2").rows()
            b = r.sql(RANGE).rows()
            r.sql("commit")
            assert a == b
            assert p == [(a[0][1],)]
            # one commit moves both ids: a snapshot holds it whole or not
            assert a[0][1] - 20 == a[4][1] - 60
    finally:
        stop.set()
        th.join()
    assert not errors
    sh, pr = _moved(db, c0)
    assert sh + pr >= 75  # the writer's qualifications count too


def test_a_statement_whose_entry_moved_runs_again_on_the_rescan(
        db, monkeypatch):
    s = db.session()
    want = s.sql(RANGE).rows()
    s.sql("begin")
    asked = []
    monkeypatch.setattr(db, "tx_shared_holds",
                        lambda shared: asked.append(set(shared)))
    c0 = _counts(db)
    assert s.sql(RANGE).rows() == want
    assert asked == [{"sr_t"}]
    assert _moved(db, c0) == (0, 1)
    s.sql("commit")


def test_a_refresh_that_races_a_commit_is_labelled_stale(db, monkeypatch):
    """The shared entry's label is the data version read before its scan:
    a commit that lands during the scan leaves it stale, not current
    without the commit's rows."""
    from oceanbase_tpu.storage.tablet import Tablet

    s1, s2 = db.session(), db.session()
    s1.sql("update sr_t set k = k where id = 1")  # the entry goes stale
    ti = db.tables["sr_t"]
    assert ti.cached_data_version != ti.data_version
    scan = Tablet.scan
    fired = []

    def scan_then_commit(self, *a, **kw):
        out = scan(self, *a, **kw)
        if not fired:
            fired.append(1)
            th = threading.Thread(target=s2.sql, args=(
                "update sr_t set c = 'raced' where id = 8",))
            th.start()
            th.join()
        return out

    monkeypatch.setattr(Tablet, "scan", scan_then_commit)
    assert s1.sql("select c from sr_t where id between 8 and 8"
                  ).rows() == [("c8",)]
    monkeypatch.undo()
    assert ti.cached_data_version != ti.data_version
    s1.sql("begin")
    c0 = _counts(db)
    assert s1.sql("select c from sr_t where id between 8 and 8"
                  ).rows() == [("raced",)]
    assert _moved(db, c0) == (1, 0)
    s1.sql("commit")


def test_an_open_writer_keeps_readers_on_the_rescan(db):
    s1, w = db.session(), db.session()
    s1.sql(RANGE)
    w.sql("begin")
    w.sql("update sr_t set k = k + 1 where id = 9")
    assert db.tables["sr_t"].writers == 1
    s1.sql("begin")
    c0 = _counts(db)
    s1.sql(RANGE)
    assert _moved(db, c0) == (0, 1)
    s1.sql("commit")
    w.sql("rollback")
    assert db.tables["sr_t"].writers == 0


def test_an_upload_raced_by_a_publish_does_not_outlive_it(db, monkeypatch):
    """A device-cache upload of an entry that a publish replaced while it
    ran serves its own statement and is not cached for the next one."""
    ex = db.engine.executor
    db.session().sql(RANGE)
    ex.invalidate_table("sr_t")
    upload = ex._upload_cold

    def publish_meanwhile(*a, **kw):
        out = upload(*a, **kw)
        ex.invalidate_table("sr_t")
        return out

    monkeypatch.setattr(ex, "_upload_cold", publish_meanwhile)
    assert int(ex.table_batch("sr_t", ("id", "k")).nrows) == 20
    assert not [k for k in ex._batch_cache if k[0] == "sr_t"]
    assert not [k for k in ex._assembled if k[0] == "sr_t"]
    monkeypatch.undo()
    ex.table_batch("sr_t", ("id", "k"))
    assert ("sr_t", "k") in ex._batch_cache


def test_an_index_built_after_begin_is_not_read_at_its_snapshot(db):
    """CREATE INDEX backfills at its build version: a transaction whose
    snapshot lies below it reads the table, not the index."""
    s1, s2 = db.session(), db.session()
    s2.sql("create table ix_t (id bigint primary key, g bigint not null)")
    s2.sql("insert into ix_t values " + ", ".join(
        f"({i}, {i % 3})" for i in range(1, 13)))
    q = "select id from ix_t where g = 1 order by id"
    want = s2.sql(q).rows()
    assert len(want) == 4
    s1.sql("begin")
    assert s1.sql("select count(*) as n from ix_t").rows() == [(12,)]
    s2.sql("create index ix_g on ix_t (g)")
    assert s1.sql(q).rows() == want
    s1.sql("commit")
    # a transaction begun after the build reads through the index
    reads = db.tables["ix_t"].indexes["ix_g"].reads
    s1.sql("begin")
    assert s1.sql(q).rows() == want
    s1.sql("commit")
    assert db.tables["ix_t"].indexes["ix_g"].reads == reads + 1


def test_a_commit_in_flight_at_its_end_keeps_its_writer_open(
        db, monkeypatch):
    """A commit wait that times out leaves the decision in flight: the
    writer stays open, so readers rescan, until the decision is seen to
    land, and then the table's version moves."""
    s1, w = db.session(), db.session()
    ti = db.tables["sr_t"]
    q = "select c from sr_t where id in (11)"
    old = s1.sql(q).rows()
    seen = []

    def commit_unobserved(svc, ctx, max_time=30.0):
        svc.commit(ctx)
        seen.append((svc, ctx))
        raise TimeoutError("decision not observed")

    monkeypatch.setattr(db.cluster, "commit_sync", commit_unobserved)
    w.sql("begin")
    w.sql("update sr_t set c = 'late' where id = 11")
    with pytest.raises(Exception):
        w.sql("commit")
    monkeypatch.undo()
    svc, ctx = seen[0]
    assert not ctx.is_done
    assert ti.writers == 1
    v0 = ti.data_version
    s1.sql("begin")
    c0 = _counts(db)
    assert s1.sql(q).rows() == old
    assert _moved(db, c0) == (0, 1)
    s1.sql("commit")

    def decided():
        svc.retry_decisions(ctx)
        return ctx.is_done

    assert db.cluster.drive_until(decided)
    s1.sql("begin")
    c0 = _counts(db)
    assert s1.sql(q).rows() == [("late",)]
    assert _moved(db, c0) == (1, 0)
    s1.sql("commit")
    assert ti.writers == 0 and ti.data_version == v0 + 1
    assert ti.last_commit_version == ctx.commit_version


def test_a_written_table_is_read_by_its_primary_key_with_own_writes(db):
    """After a transaction's first write of a table, a read by the full
    primary key (a DML's qualification among them) takes the key at the
    BEGIN snapshot with the transaction's own staged rows over it: no
    rescan, and the answers the rescan gave."""
    s1, s2 = db.session(), db.session()
    (k8, c8), = s2.sql("select k, c from sr_t where id = 8").rows()
    (c9,), = s2.sql("select c from sr_t where id = 9").rows()
    s1.sql("begin")
    s1.sql("update sr_t set c = 'mine' where id = 8")
    s2.sql("update sr_t set c = 'theirs' where id = 9")  # after the BEGIN
    c0 = _counts(db)
    scans0 = db.metrics.counter("catalog refreshes")
    assert s1.sql("select c from sr_t where id = 8").rows() == [("mine",)]
    assert s1.sql("select c from sr_t where id = 9").rows() == [(c9,)]
    assert s1.sql("update sr_t set k = k + 1 where id = 8").affected == 1
    assert s1.sql("select k, c from sr_t where id = 8").rows() == [
        (k8 + 1, "mine")]
    s1.sql("delete from sr_t where id = 8")
    assert s1.sql("select c from sr_t where id = 8").rows() == []
    assert s1.sql("update sr_t set k = 0 where id = 8").affected == 0
    s1.sql("insert into sr_t values (8, 7, 'again')")
    assert s1.sql("select k, c from sr_t where id = 8").rows() == [
        (7, "again")]
    # eight reads by the key (three of them qualifications), none rescanned
    assert _moved(db, c0) == (0, 8)
    assert db.metrics.counter("catalog refreshes") == scans0
    # first committer wins: the key another session changed after BEGIN
    with pytest.raises(Exception, match="modified at .* > snapshot"):
        s1.sql("update sr_t set k = 1 where id = 9")
    s1.sql("rollback")
    s2.sql(f"update sr_t set c = '{c9}' where id = 9")
    assert s2.sql("select k, c from sr_t where id = 8").rows() == [(k8, c8)]
