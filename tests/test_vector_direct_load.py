"""A VECTOR column through the bulk-load path: DDL, `direct_load`, the
sstable's fixed-width row blocks, the tablets of three replicas.

  * a VECTOR(d) table loaded by `direct_load` scans back bit-equal, from the
    leader and from each follower, and through a SELECT;
  * INSERT / UPDATE / DELETE leave memtable rows over the sstable, and the
    merged scan is the oracle, row for row and bit for bit; dumped to a
    mini sstable (a tombstone among the rows) and compacted into the base,
    they still are;
  * `direct_load` refuses a vector array of the wrong width;
  * an sstable micro block of `(rows, d)` float32 round-trips, and a scalar
    table's sstable blob is byte for byte what it was before the row-width
    blocks existed (its sha256, taken on the tree before them).
"""

import hashlib

import numpy as np
import pytest

from oceanbase_tpu.server.database import Database
from oceanbase_tpu.server.direct_load import DirectLoadError, direct_load
from oceanbase_tpu.storage.microblock import BlockReader, write_block

D = 8
N = 3000


def _replica_scans(db, name):
    """{node id: scan} of every replica of the table's one partition, at
    the current snapshot."""
    ti = db.tables[name]
    snap = db.cluster.gts.current()
    (ls_id, tablet_id), = ti.all_partitions()
    return {nid: rep.tablets[tablet_id].scan(snap)
            for nid, rep in sorted(db.cluster.ls_groups[ls_id].items())}


@pytest.fixture
def loaded():
    db = Database(n_nodes=3, n_ls=2)
    s = db.session()
    s.sql(f"CREATE TABLE vt (id BIGINT PRIMARY KEY, cat INT NOT NULL, "
          f"emb VECTOR({D}))")
    rng = np.random.default_rng(5)
    ids = rng.permutation(np.arange(1, N + 1, dtype=np.int64))
    emb = rng.standard_normal((N, D)).astype(np.float32) * 40.0
    cat = rng.integers(0, 100, N).astype(np.int32)
    assert direct_load(db, "vt", {"id": ids, "cat": cat, "emb": emb}) == N
    order = np.argsort(ids)
    oracle = {int(i): (int(c), e) for i, c, e in
              zip(ids[order], cat[order], emb[order])}
    yield db, s, oracle
    db.close()


def _as_oracle(scan):
    return {int(i): (int(c), e) for i, c, e in
            zip(scan["id"], scan["cat"], scan["emb"])}


def _assert_same(got, oracle):
    assert sorted(got) == sorted(oracle)
    for k, (c, e) in oracle.items():
        gc, ge = got[k]
        assert gc == c, k
        assert ge.dtype == np.float32 and ge.shape == (D,)
        assert ge.tobytes() == np.asarray(e, np.float32).tobytes(), k


def test_direct_loaded_vectors_scan_back_bit_equal_on_every_replica(loaded):
    db, s, oracle = loaded
    scans = _replica_scans(db, "vt")
    assert len(scans) == 3
    for nid, scan in scans.items():
        assert scan["emb"].shape == (N, D), nid
        _assert_same(_as_oracle(scan), oracle)
    # the sstable holds the rows; no memtable row stands in for them
    ti = db.tables["vt"]
    (ls_id, tablet_id), = ti.all_partitions()
    for rep in db.cluster.ls_groups[ls_id].values():
        tab = rep.tablets[tablet_id]
        assert tab.active.nkeys == 0 and len(tab.deltas) == 1
    assert int(s.sql("select count(*) from vt").rows()[0][0]) == N
    rows = s.sql("select id, emb from vt where id <= 5 order by id").rows()
    for i, e in rows:
        assert np.asarray(e, np.float32).tobytes() == \
            oracle[int(i)][1].tobytes()


def test_dml_over_direct_loaded_vectors_merges_with_the_sstable(loaded):
    db, s, oracle = loaded
    s.sql("insert into vt values (100001, 7, "
          f"'[{','.join(['1.5'] * D)}]')")
    oracle[100001] = (7, np.full(D, 1.5, np.float32))
    s.sql("update vt set cat = 99 where id = 3")
    oracle[3] = (99, oracle[3][1])
    s.sql(f"update vt set emb = '[{','.join(['-2.25'] * D)}]' where id = 4")
    oracle[4] = (oracle[4][0], np.full(D, -2.25, np.float32))
    s.sql("delete from vt where id = 5")
    del oracle[5]
    ti = db.tables["vt"]
    (ls_id, tablet_id), = ti.all_partitions()
    lead = db._leader_replica_ls(ls_id)
    assert lead.tablets[tablet_id].active.nkeys == 4
    scan = lead.tablets[tablet_id].scan(db.cluster.gts.current())
    _assert_same(_as_oracle(scan), oracle)
    got = {int(r[0]): int(r[1]) for r in
           s.sql("select id, cat from vt where id < 7").rows()}
    assert got == {k: oracle[k][0] for k in oracle if k < 7}


def test_direct_load_refuses_a_vector_of_the_wrong_width():
    db = Database(n_nodes=3, n_ls=2)
    try:
        db.session().sql(f"CREATE TABLE w (id BIGINT PRIMARY KEY, "
                         f"emb VECTOR({D}))")
        with pytest.raises(DirectLoadError, match="VECTOR"):
            direct_load(db, "w", {"id": np.arange(4),
                                  "emb": np.zeros((4, D + 1), np.float32)})
        with pytest.raises(DirectLoadError, match="VECTOR"):
            direct_load(db, "w", {"id": np.arange(4),
                                  "emb": np.zeros(4 * D, np.float32)})
    finally:
        db.close()


def test_row_width_block_round_trips():
    rng = np.random.default_rng(9)
    vec = rng.standard_normal((300, D)).astype(np.float32)
    ids = np.arange(300, dtype=np.int64)
    blob, zones = write_block([ids, vec], [None, None])
    r = BlockReader.open(blob)
    got_ids, _ = r.column(0)
    got_vec, valid = r.column(1)
    assert np.array_equal(got_ids, ids)
    assert got_vec.shape == (300, D) and valid is None
    assert got_vec.tobytes() == vec.tobytes()
    # no zone map: no range can prune a vector block
    assert zones[1].vmin == -np.inf and zones[1].vmax == np.inf


# sha256 of the three replicas' sstable blob of the table below, taken on
# the tree before VECTOR columns had row-width blocks: a scalar table's
# bytes must not move
SCALAR_BLOB_SHA256 = (
    "efb296c35a67ac7e2666ee5e343d8f5f06a40ccab87403e53e3da5f7d4dfb2ca")


def test_scalar_table_sstable_bytes_unchanged():
    db = Database(n_nodes=3, n_ls=2)
    try:
        db.session().sql(
            "create table sbtest1 (id int not null, k int not null, "
            "c char(120) not null, pad char(60) not null, primary key (id))")
        n = 40000
        rng = np.random.default_rng(7)
        ids = np.arange(1, n + 1)
        k = rng.integers(1, n + 1, n)
        c = np.array(["%011d-%011d" % (a, b)
                      for a, b in rng.integers(0, 10**11, (n, 2))])
        pad = np.array(["%011d" % a for a in rng.integers(0, 10**11, n)])
        direct_load(db, "sbtest1", {"id": ids[::-1].copy(), "k": k,
                                    "c": c, "pad": pad})
        ti = db.tables["sbtest1"]
        (ls_id, tablet_id), = ti.all_partitions()
        digests = {hashlib.sha256(bytes(rep.tablets[tablet_id].deltas[0].buf))
                   .hexdigest()
                   for rep in db.cluster.ls_groups[ls_id].values()}
        assert digests == {SCALAR_BLOB_SHA256}
    finally:
        db.close()


def test_dumped_and_compacted_vector_rows_scan_back(loaded):
    """The memtable's rows over the loaded sstable, a DELETE's tombstone
    among them, dumped to a mini sstable and then compacted into the base:
    both are row-width blocks, and every scan is the oracle."""
    db, s, oracle = loaded
    s.sql(f"update vt set emb = '[{','.join(['3.5'] * D)}]' where id = 7")
    oracle[7] = (oracle[7][0], np.full(D, 3.5, np.float32))
    s.sql("delete from vt where id = 8")
    del oracle[8]
    ti = db.tables["vt"]
    (ls_id, tablet_id), = ti.all_partitions()
    tab = db._leader_replica_ls(ls_id).tablets[tablet_id]
    tab.freeze()
    assert tab.dump_mini() is not None and len(tab.deltas) == 2
    snap = db.cluster.gts.current()
    _assert_same(_as_oracle(tab.scan(snap)), oracle)
    tab.major_compact(snap)
    assert tab.base is not None and not tab.deltas
    _assert_same(_as_oracle(tab.scan(snap)), oracle)
