"""ANN vector search: exact brute force (ORDER BY vec_l2 LIMIT k = plain
TopN over a matmul-scored key) and the IVF-flat index fast path
(storage/vector_index.py, reference src/storage/vector_index +
src/sql/das/iter ANN iterators)."""

import numpy as np
import pytest

from oceanbase_tpu.core.dtypes import DataType, Field, Schema, TypeKind
from oceanbase_tpu.core.table import Table
from oceanbase_tpu.engine import Session
from oceanbase_tpu.storage.vector_index import (
    build_ivf,
    register_vector_index,
)

I64 = DataType(TypeKind.INT64)


def _vec_table(n=20000, d=32, seed=0):
    rng = np.random.default_rng(seed)
    # clustered data (what embeddings look like): 40 gaussian blobs
    centers = rng.normal(size=(40, d)).astype(np.float32) * 4
    a = rng.integers(0, 40, n)
    x = centers[a] + rng.normal(size=(n, d)).astype(np.float32)
    t = Table(
        "docs",
        Schema((Field("id", I64), Field("emb", DataType.vector(d)))),
        {"id": np.arange(n, dtype=np.int64), "emb": x},
    )
    return {"docs": t}, x, rng


def _qtext(q, k):
    lit = "[" + ",".join(f"{v:.6f}" for v in q) + "]"
    return f"select id from docs order by vec_l2(emb, '{lit}') limit {k}"


def _exact(x, q, k):
    d = ((x - q[None, :]) ** 2).sum(axis=1)
    return np.argsort(d, kind="stable")[:k]


def test_brute_force_exact():
    cat, x, rng = _vec_table(n=5000)
    sess = Session(cat)
    for _ in range(3):
        q = x[rng.integers(0, len(x))] + 0.1
        rs = sess.sql(_qtext(q, 10))
        got = [int(v) for v in rs.columns["id"]]
        want = [int(v) for v in _exact(x, q, 10)]
        assert got == want


def test_ivf_recall_at_10():
    cat, x, rng = _vec_table()
    register_vector_index(cat, "docs", "emb", lists=64, nprobe=8)
    sess = Session(cat)
    hits = total = 0
    first_entry = None
    for i in range(25):
        q = x[rng.integers(0, len(x))] + rng.normal(size=x.shape[1]).astype(
            np.float32) * 0.05
        rs = sess.sql(_qtext(q, 10))
        got = {int(v) for v in rs.columns["id"]}
        want = {int(v) for v in _exact(x, q, 10)}
        hits += len(got & want)
        total += 10
        entry, _ = sess.cached_entry(_qtext(q, 10))
        assert entry.prepared.params.vector_topns, "ANN path did not engage"
        if first_entry is None:
            first_entry = entry
        else:
            # every distinct query vector reuses ONE compiled program
            assert entry is first_entry
    recall = hits / total
    assert recall >= 0.9, f"recall@10 = {recall}"


def test_index_rebuild_after_dml():
    cat, x, rng = _vec_table(n=4000)
    register_vector_index(cat, "docs", "emb", lists=32, nprobe=32)
    sess = Session(cat)
    q = x[7]
    rs = sess.sql(_qtext(q, 1))
    assert int(rs.columns["id"][0]) == 7
    # replace the data in place: id 3 becomes the exact query point
    t = cat["docs"]
    x2 = x.copy()
    x2[3] = q + 100.0  # move 7's twin far away? no: make 3 the nearest
    x2[7] += 50.0
    x2[3] = q
    t.data["emb"] = x2
    sess.executor.invalidate_table("docs")
    rs2 = sess.sql(_qtext(q, 1))
    assert int(rs2.columns["id"][0]) == 3, "stale vector index served"


def test_nprobe_full_is_exact():
    """Probing every list must equal brute force (IVF covers the space)."""
    cat, x, rng = _vec_table(n=3000)
    register_vector_index(cat, "docs", "emb", lists=16, nprobe=16)
    sess = Session(cat)
    for _ in range(3):
        q = rng.normal(size=x.shape[1]).astype(np.float32) * 3
        rs = sess.sql(_qtext(q, 5))
        got = [int(v) for v in rs.columns["id"]]
        want = [int(v) for v in _exact(x, q, 5)]
        assert got == want


def test_ip_and_cosine_metrics():
    """vec_ip / vec_cosine rank by negative inner product and cosine
    DISTANCE (brute-force matmul+top-k; ASC LIMIT k = nearest for every
    metric)."""
    cat, x, rng = _vec_table(n=3000)
    sess = Session(cat)
    q = x[11]
    lit = "[" + ",".join(f"{v:.6f}" for v in q) + "]"
    rs = sess.sql(
        f"select id from docs order by vec_ip(emb, '{lit}') limit 5"
    )
    got = [int(v) for v in rs.columns["id"]]
    want = np.argsort(-(x @ q), kind="stable")[:5]
    assert got == [int(v) for v in want]
    rs = sess.sql(
        f"select id from docs order by vec_cosine(emb, '{lit}') limit 5"
    )
    got = [int(v) for v in rs.columns["id"]]
    sims = (x @ q) / (
        np.linalg.norm(x, axis=1) * np.linalg.norm(q) + 1e-30
    )
    want = np.argsort(-sims, kind="stable")[:5]
    assert got == [int(v) for v in want]


def test_build_ivf_structure():
    x = np.random.default_rng(1).normal(size=(1000, 8)).astype(np.float32)
    idx = build_ivf(x, lists=16)
    assert idx.centroids.shape == (16, 8)
    assert sorted(idx.perm.tolist()) == list(range(1000))
    assert int(idx.lengths.sum()) == 1000
    # the read window: the longest list rounded up to 2^k or 3 x 2^(k-1)
    longest = int(idx.lengths.max())
    assert longest <= idx.max_list < longest * 4 // 3 + 1
    assert idx.max_list in {1 << k for k in range(12)} | {
        3 << k for k in range(11)}
    # offsets delimit the lists
    ends = idx.offsets + idx.lengths
    assert int(ends.max()) == 1000


def test_cap_lists_moves_rows_to_the_nearest_list_with_room():
    from oceanbase_tpu.storage.vector_index import _cap_lists

    rng = np.random.default_rng(3)
    x = rng.normal(size=(600, 8)).astype(np.float32)
    cent = rng.normal(size=(6, 8)).astype(np.float32)
    d2 = ((x[:, None, :] - cent[None, :, :]) ** 2).sum(axis=2)
    a0 = np.argmin(d2, axis=1)
    cap = 110
    assert np.bincount(a0, minlength=6).max() > cap
    a = _cap_lists(x, cent, a0, cap)
    counts = np.bincount(a, minlength=6)
    assert counts.max() <= cap and counts.sum() == 600
    own = d2[np.arange(600), a]
    for r in range(600):
        # a row is away from a nearer list only where that list is full
        nearer = np.flatnonzero(d2[r] < own[r] - 1e-4)
        assert (counts[nearer] == cap).all()
    # an over-full list kept the rows nearest its centroid
    for li in np.flatnonzero(np.bincount(a0, minlength=6) > cap):
        kept = d2[a == li, li]
        gone = d2[(a0 == li) & (a != li), li]
        assert kept.max() <= gone.min() + 1e-4


def test_build_ivf_caps_lists(monkeypatch):
    """A capped index: no list over the cap, every row in one list, and
    the probe answers the nearest rows of the lists it probes."""
    from oceanbase_tpu.storage import vector_index

    monkeypatch.setattr(vector_index, "LIST_CAP_FACTOR", 1)
    cat, x, rng = _vec_table(n=3000)
    idx = build_ivf(x, lists=16)
    cap = vector_index._list_cap(3000, 16)
    assert int(idx.lengths.max()) <= cap
    assert idx.max_list <= cap
    assert sorted(idx.perm.tolist()) == list(range(3000))
    register_vector_index(cat, "docs", "emb", lists=16, nprobe=8)
    sess = Session(cat)
    c = idx.centroids
    for _ in range(5):
        q = x[rng.integers(0, len(x))] + 0.05
        rs = sess.sql(_qtext(q, 5))
        entry, _ = sess.cached_entry(_qtext(q, 5))
        assert entry.prepared.params.vector_topns, "ANN path did not engage"
        probes = np.argsort((c * c).sum(axis=1) - 2.0 * (c @ q))[:8]
        rows = np.concatenate([
            idx.perm[idx.offsets[p]:idx.offsets[p] + idx.lengths[p]]
            for p in probes])
        want = rows[_exact(x[rows], q, 5)]
        assert {int(v) for v in rs.columns["id"]} == {int(v) for v in want}


def test_server_ddl_and_query():
    from oceanbase_tpu.server.database import Database

    db = Database(n_nodes=1, n_ls=1)
    try:
        s = db.session()
        s.sql("create table docs (id int primary key, emb vector(4))")
        rng = np.random.default_rng(2)
        for i in range(64):
            v = rng.normal(size=4)
            lit = "[" + ",".join(f"{a:.4f}" for a in v) + "]"
            s.sql(f"insert into docs values ({i}, '{lit}')")
        s.sql("create vector index ix on docs (emb) with (lists = 8, nprobe = 8)")
        q = "[0.0,0.0,0.0,0.0]"
        rs = s.sql(
            f"select id from docs order by vec_l2(emb, '{q}') limit 3"
        )
        assert rs.nrows == 3
        # oracle through the freshly read snapshot
        t = db.catalog["docs"]
        x = np.asarray(t.data["emb"], dtype=np.float32)
        want = np.argsort((x * x).sum(axis=1), kind="stable")[:3]
        ids = t.data["id"]
        assert [int(v) for v in rs.columns["id"]] == [
            int(ids[i]) for i in want
        ]
    finally:
        db.close()
