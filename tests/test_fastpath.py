"""Clustered-FK segment aggregation, sorted-projection range scans, and
affine-through-join propagation (round 4 join fast paths).

Strategy mirrors the engine's own discipline elsewhere: every fast path
must produce bit-identical results to the generic path it replaces, on
data with the awkward cases present (unmatched keys on both sides, NULL
aggregate inputs, empty groups, duplicate fk runs, parameter values that
overflow the seeded capacity)."""

import numpy as np
import pytest

from oceanbase_tpu.core.dtypes import DataType, Field, Schema, TypeKind
from oceanbase_tpu.core.table import Table
from oceanbase_tpu.engine import Session
from oceanbase_tpu.engine.executor import Executor
from oceanbase_tpu.storage.sorted_projection import (
    drop_projections,
    make_sorted_projection,
)

I64 = DataType(TypeKind.INT64)
I32 = DataType(TypeKind.INT32)
F64 = DataType(TypeKind.FLOAT64)
I64N = DataType(TypeKind.INT64, nullable=True)


def _tables(seed=7, nprobe=5000, nbuild=400, fk_pool=None, pk=None):
    rng = np.random.default_rng(seed)
    # clustered fk: sorted, with runs, referencing ~half the build keys,
    # plus some fk values that exist in no build row
    if fk_pool is None:
        fk = rng.integers(0, nbuild * 2, nprobe)
    else:
        fk = rng.choice(fk_pool, nprobe)
    fk = np.sort(fk).astype(np.int64)
    val = rng.integers(-50, 50, nprobe).astype(np.int64)
    val_null = rng.random(nprobe) < 0.15
    flt = rng.integers(0, 10, nprobe).astype(np.int32)
    probe = Table(
        "probe",
        Schema((
            Field("fk", I64),
            Field("val", I64N),
            Field("flt", I32),
        )),
        {"fk": fk, "val": val, "flt": flt},
        valid={"val": ~val_null},
    )
    if pk is None:
        pk = rng.permutation(nbuild * 2)[:nbuild]
    pk = np.asarray(pk, dtype=np.int64)
    nbuild = len(pk)
    battr = rng.integers(0, 5, nbuild).astype(np.int32)
    build = Table(
        "build",
        Schema((Field("pk", I64), Field("battr", I32))),
        {"pk": pk, "battr": battr},
    )
    return {"probe": probe, "build": build}


Q_CLUSTERED = """
select fk, battr, sum(val) as s, count(val) as c, count(*) as n
from probe, build
where fk = pk and flt < 7 and battr <> 3
group by fk, battr
order by fk
"""


def _run(catalog, q, clustered: bool):
    sess = Session(catalog, unique_keys={"build": (("pk",),)})
    prev = Executor.clustered_agg_enabled
    Executor.clustered_agg_enabled = clustered
    try:
        rs = sess.sql(q)
    finally:
        Executor.clustered_agg_enabled = prev
    # the fast path must actually have fired (or not)
    entry, _ = sess.cached_entry(q)
    specs = entry.prepared.params.clustered_aggs
    assert bool(specs) == clustered
    return rs.rows()


def test_clustered_agg_matches_generic():
    got = _run(_tables(), Q_CLUSTERED, clustered=True)
    want = _run(_tables(), Q_CLUSTERED, clustered=False)
    assert len(got) == len(want) and len(got) > 5
    assert got == want


def test_clustered_agg_declines_unclustered_fk():
    cat = _tables()
    # shuffle the fk column: monotonicity gone -> generic path
    rng = np.random.default_rng(0)
    order = rng.permutation(len(cat["probe"].data["fk"]))
    for c in ("fk", "val", "flt"):
        cat["probe"].data[c] = cat["probe"].data[c][order]
    cat["probe"].valid["val"] = cat["probe"].valid["val"][order]
    sess = Session(cat, unique_keys={"build": (("pk",),)})
    rs = sess.sql(Q_CLUSTERED)
    entry, _ = sess.cached_entry(Q_CLUSTERED)
    assert not entry.prepared.params.clustered_aggs
    want = _run(_tables(), Q_CLUSTERED, clustered=False)
    # same multiset of rows modulo fk order (ordered by fk both ways)
    assert rs.rows() == want


def test_clustered_agg_declines_coarser_groups():
    """Group keys that don't pin the join key (TPC-H Q10 shape) must NOT
    ride the per-build-row path."""
    cat = _tables()
    q = """
    select battr, sum(val) as s from probe, build
    where fk = pk group by battr order by battr
    """
    sess = Session(cat, unique_keys={"build": (("pk",),)})
    rs = sess.sql(q)
    entry, _ = sess.cached_entry(q)
    assert not entry.prepared.params.clustered_aggs
    # numpy oracle
    p, b = cat["probe"], cat["build"]
    pos = {int(k): i for i, k in enumerate(b.data["pk"])}
    s = {}
    for i in range(p.nrows):
        j = pos.get(int(p.data["fk"][i]))
        if j is None or not p.valid["val"][i]:
            continue
        a = int(b.data["battr"][j])
        s[a] = s.get(a, 0) + int(p.data["val"][i])
    want = [(a, s[a]) for a in sorted(s)]
    assert [(int(a), int(v)) for a, v in rs.rows()] == want


def test_sorted_projection_slice_and_params():
    cat = _tables(nprobe=20000)
    make_sorted_projection(cat, "probe", "fk")
    sess = Session(cat, unique_keys={"build": (("pk",),)})
    q = "select sum(val) as s, count(*) as n from probe where fk >= 100 and fk < 140"
    rs = sess.sql(q)
    entry, _ = sess.cached_entry(q)
    assert entry.prepared.params.scan_cap, "slice did not engage"
    p = cat["probe"]
    m = (p.data["fk"] >= 100) & (p.data["fk"] < 140) & p.valid["val"]
    assert int(rs.columns["s"][0]) == int(p.data["val"][m].sum())
    # same plan, range wide enough to overflow the seeded capacity
    q2 = "select sum(val) as s, count(*) as n from probe where fk >= 0 and fk < 600"
    rs2 = sess.sql(q2)
    assert rs2.plan_cache_hit
    m2 = (p.data["fk"] >= 0) & (p.data["fk"] < 600) & p.valid["val"]
    assert int(rs2.columns["s"][0]) == int(p.data["val"][m2].sum())
    assert entry.prepared.retries >= 1


def test_projection_not_routed_when_unselective():
    cat = _tables(nprobe=20000)
    make_sorted_projection(cat, "probe", "fk")
    sess = Session(cat, unique_keys={"build": (("pk",),)})
    q = "select count(*) as n from probe where fk >= 1"  # ~all rows
    rs = sess.sql(q)
    entry, _ = sess.cached_entry(q)
    assert not entry.prepared.params.scan_cap
    assert int(rs.columns["n"][0]) == int((cat["probe"].data["fk"] >= 1).sum())


def test_drop_projections():
    cat = _tables()
    pname = make_sorted_projection(cat, "probe", "fk")
    assert pname in cat
    drop_projections(cat, "probe")
    assert pname not in cat
    assert not cat["probe"].sorted_projections
    sess = Session(cat, unique_keys={"build": (("pk",),)})
    q = "select count(*) as n from probe where fk >= 100 and fk < 140"
    rs = sess.sql(q)
    entry, _ = sess.cached_entry(q)
    assert not entry.prepared.params.scan_cap  # no projection, no slice


def test_clustered_never_combines_with_sliced_projection():
    """A projection sorted by the clustered fk makes BOTH fast paths
    eligible; combining them misindexes fk_ranges against the sliced
    batch (review finding r4). Exactly one may fire, and results must
    stay correct."""
    cat = _tables()
    make_sorted_projection(cat, "probe", "fk")
    q = """
    select fk, battr, sum(val) as s from probe, build
    where fk = pk and fk >= 100 and fk < 140 and flt < 7
    group by fk, battr order by fk
    """
    sess = Session(cat, unique_keys={"build": (("pk",),)})
    rs = sess.sql(q)
    entry, _ = sess.cached_entry(q)
    p = entry.prepared.params
    assert not (p.clustered_aggs and p.scan_cap), "both fast paths fired"
    # oracle
    cat2 = _tables()
    pr, b = cat2["probe"], cat2["build"]
    pos = {int(k): i for i, k in enumerate(b.data["pk"])}
    agg = {}
    for i in range(pr.nrows):
        fk = int(pr.data["fk"][i])
        if not (100 <= fk < 140) or pr.data["flt"][i] >= 7:
            continue
        j = pos.get(fk)
        if j is None:
            continue
        k = (fk, int(b.data["battr"][j]))
        agg.setdefault(k, 0)
        if pr.valid["val"][i]:
            agg[k] += int(pr.data["val"][i])
    want = [(fk, a, agg[(fk, a)]) for fk, a in sorted(agg)]
    assert [(int(x), int(y), int(z)) for x, y, z in rs.rows()] == want


def test_clustered_premise_revalidated_after_dml():
    """In-place data change that breaks the fk clustering must NOT let a
    cached clustered plan mis-group (review finding r4): the premise is
    re-proven when versions bump, and the plan recompiles generic."""
    cat = _tables()
    sess = Session(cat, unique_keys={"build": (("pk",),)})
    rs1 = sess.sql(Q_CLUSTERED)
    entry, _ = sess.cached_entry(Q_CLUSTERED)
    assert entry.prepared.params.clustered_aggs
    # permute the probe rows in place: same multiset, clustering gone
    rng = np.random.default_rng(3)
    order = rng.permutation(cat["probe"].nrows)
    p = cat["probe"]
    p.data = {c: p.data[c][order] for c in p.data}
    p.valid = {c: p.valid[c][order] for c in p.valid}
    sess.executor.invalidate_table("probe")
    rs2 = sess.sql(Q_CLUSTERED)
    # grouped sums are permutation-invariant: identical rows expected
    assert rs2.rows() == rs1.rows()


def _run_counted(sess, q):
    """(rows, the one clustered spec, counter moves) of one statement run
    with a registry installed as the server installs its tenant's."""
    from oceanbase_tpu.expr import compile as C
    from oceanbase_tpu.share.metrics import MetricsRegistry

    reg = MetricsRegistry()
    prev = C.set_lookup_metrics(reg)
    try:
        rows = sess.sql(q).rows()
    finally:
        C.set_lookup_metrics(prev)
    entry, _ = sess.cached_entry(q)
    (spec,) = entry.prepared.params.clustered_aggs.values()
    moved = {k: int(reg.counter(f"clustered agg bounds {k}"))
             for k in ("shared", "gathered")}
    return rows, spec, moved


# build keys 0..n-1 in storage order unless said; the probe's fk values
# are drawn from fk_pool. Tiled = every real build row's range starts
# where its neighbour's ends and the first starts at row 0.
RANGE_SHAPES = {
    # (a) sorted build, every fk has its pk, no padded tail (cap == n)
    "sorted_complete": (dict(pk=np.arange(1024),
                             fk_pool=np.arange(1024)), True),
    # (b) build rows that own no probe row: at the head, in the middle
    # (a run of them) and at the tail — empty ranges still tile
    "empty_ranges": (dict(pk=np.arange(1024), fk_pool=np.r_[
        3:200, 260:1000]), True),
    # (c) build capacity not a multiple of 1024 (padded tail of
    # starts == ends == 0), with fk values past the last pk
    "padded_tail": (dict(pk=np.arange(1500),
                         fk_pool=np.arange(1520)), True),
    # (d) orphan fk values before the first pk: starts[0] > 0
    "orphans_before_first_pk": (dict(pk=np.arange(5, 1029),
                                     fk_pool=np.arange(1029)), False),
    # (e) the build a permutation with orphan fks (Q_CLUSTERED's own)
    "permuted_build": (dict(), False),
}


@pytest.mark.parametrize("shape", list(RANGE_SHAPES))
def test_clustered_agg_bounds_by_range_shape(shape):
    """Where the host proves that the FK ranges tile, the program fetches
    each group's bound once (the lower bound is the neighbour's upper
    bound); elsewhere it gathers both. Either way the rows are the generic
    path's, and the counter says which program was compiled."""
    kw, tiled = RANGE_SHAPES[shape]
    sess = Session(_tables(**kw), unique_keys={"build": (("pk",),)})
    got, spec, moved = _run_counted(sess, Q_CLUSTERED)
    assert spec.tiled is tiled
    assert moved == {"shared": int(tiled), "gathered": int(not tiled)}
    want = _run(_tables(**kw), Q_CLUSTERED, clustered=False)
    assert len(got) > 5 and got == want


def _delete_pk(cat):
    """A PK deleted that FKs still name: its probe rows become orphans
    between two build rows' ranges."""
    b = cat["build"]
    keep = b.data["pk"] != 500
    b.data = {c: a[keep] for c, a in b.data.items()}
    return ["build"]


def _append_out_of_key_order(cat):
    """Key 700 arrives late: its build row is appended after key 1023,
    its probe rows are merged in at their clustered position."""
    b, p = cat["build"], cat["probe"]
    b.data = {"pk": np.r_[b.data["pk"], 700],
              "battr": np.r_[b.data["battr"], 1].astype(np.int32)}
    at = int(np.searchsorted(p.data["fk"], 700))
    ins = {"fk": [700] * 3, "val": [5, -7, 11], "flt": [1, 2, 9]}
    p.data = {c: np.insert(a, at, ins[c]) for c, a in p.data.items()}
    p.valid = {"val": np.insert(p.valid["val"], at, [True, False, True])}
    return ["build", "probe"]


@pytest.mark.parametrize("dml", [_delete_pk, _append_out_of_key_order])
def test_tiled_premise_revalidated_after_build_dml(dml):
    """A plan compiled to share bounds must not meet ranges that stopped
    tiling: the DML bumps a version, fk_ranges re-proves on the host, and
    the plan recompiles once into the two-gather program."""
    keys = np.r_[0:700, 701:1024]  # key 700 is not there yet
    kw = dict(pk=keys, fk_pool=keys)
    cat = _tables(**kw)
    sess = Session(cat, unique_keys={"build": (("pk",),)})
    _rows, spec, _moved = _run_counted(sess, Q_CLUSTERED)
    assert spec.tiled
    for name in dml(cat):
        sess.executor.invalidate_table(name)
    compiles = sess.executor.compiles
    got, spec, moved = _run_counted(sess, Q_CLUSTERED)
    assert not spec.tiled
    assert sess.executor.compiles == compiles + 1
    assert moved == {"shared": 0, "gathered": 1}
    cat2 = _tables(**kw)
    dml(cat2)
    assert got == _run(cat2, Q_CLUSTERED, clustered=False)
    # settled: the next statement compiles nothing
    assert _run_counted(sess, Q_CLUSTERED)[0] == got
    assert sess.executor.compiles == compiles + 1


def test_topn_prefilter_hazards():
    """The top-k candidate prefilter must stay EXACT under (a) massive
    first-key ties (low-NDV key: overflow must disable the prefilter,
    not error) and (b) a live row whose key collides with the dead-row
    sentinel (int64 extremes)."""
    n = 20000
    rng = np.random.default_rng(9)
    low_ndv = rng.integers(0, 3, n).astype(np.int64)  # 3 distinct values
    tiebreak = rng.permutation(n).astype(np.int64)
    ext = np.arange(n, dtype=np.int64)
    ext[0] = np.iinfo(np.int64).max  # collides with ASC flip sentinel
    ext[1] = np.iinfo(np.int64).min  # collides with DESC sentinel
    t = Table(
        "t",
        Schema((Field("a", I64), Field("b", I64), Field("x", I64))),
        {"a": low_ndv, "b": tiebreak, "x": ext},
    )
    sess = Session({"t": t})
    # (a) low-NDV first key: ties >> candidate budget
    rs = sess.sql("select a, b from t order by a desc, b limit 15")
    want = sorted(zip(low_ndv, tiebreak), key=lambda r: (-r[0], r[1]))[:15]
    assert [(int(x), int(y)) for x, y in rs.rows()] == \
        [(int(x), int(y)) for x, y in want]
    # (b) sentinel-valued rows must appear at their true positions
    rs = sess.sql("select x from t order by x limit 3")
    assert int(rs.columns["x"][0]) == np.iinfo(np.int64).min
    rs = sess.sql("select x from t order by x desc limit 3")
    assert int(rs.columns["x"][0]) == np.iinfo(np.int64).max


def test_affine_through_join():
    """Build side that is itself a merge-joinable join output keeps the
    affine direct-address property of its probe-side key column."""
    n = 2000
    a = Table(
        "a", Schema((Field("ak", I64), Field("av", I64))),
        {"ak": np.arange(1, n + 1, dtype=np.int64) * 3,
         "av": np.arange(n, dtype=np.int64)},
    )
    b = Table(
        "b", Schema((Field("bk", I64), Field("bv", I64))),
        {"bk": np.arange(1, n + 1, dtype=np.int64),
         "bv": np.arange(n, dtype=np.int64) * 7},
    )
    big = Table(
        "big", Schema((Field("gk", I64), Field("gv", I64))),
        {"gk": (np.arange(4 * n, dtype=np.int64) % (2 * n)) * 3,
         "gv": np.arange(4 * n, dtype=np.int64)},
    )
    cat = {"a": a, "b": b, "big": big}
    uk = {"a": (("ak",),), "b": (("bk",),)}
    q = """
    select sum(gv) as s, sum(bv) as t from big, a, b
    where gk = ak and av + 1 = bk
    """
    sess = Session(cat, unique_keys=uk)
    rs = sess.sql(q)
    # oracle
    amap = {int(k): int(v) for k, v in zip(a.data["ak"], a.data["av"])}
    bmap = {int(k): int(v) for k, v in zip(b.data["bk"], b.data["bv"])}
    s = t = 0
    for gk, gv in zip(big.data["gk"], big.data["gv"]):
        av = amap.get(int(gk))
        if av is None:
            continue
        bv = bmap.get(av + 1)
        if bv is None:
            continue
        s += int(gv)
        t += bv
    assert int(rs.columns["s"][0]) == s
    assert int(rs.columns["t"][0]) == t
    # the planner rotated and the executor resolved the (a join b) build
    # side's ak column through the join to the affine base column
    entry, _ = sess.cached_entry(q)
    from oceanbase_tpu.sql.logical import JoinOp

    def find_joins(op, out):
        for c in (getattr(op, "child", None), getattr(op, "left", None),
                  getattr(op, "right", None)):
            if c is not None:
                find_joins(c, out)
        if isinstance(op, JoinOp):
            out.append(op)
        return out

    joins = find_joins(entry.prepared.plan, [])
    ex = sess.executor
    outer = [j for j in joins if j.left_keys
             and j.left_keys[0].name == "big.gk"]
    assert outer and ex._affine_build_info(outer[0]) == (3, 3)
