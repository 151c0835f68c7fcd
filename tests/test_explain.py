"""EXPLAIN: plan rendering with the engine's physical annotations
(sorted-projection slices, join routes, clustered-FK aggregation, ANN
top-n) — the plan-printer surface, never compiling anything."""

import pytest

from oceanbase_tpu.models.tpch import datagen
from oceanbase_tpu.models.tpch.sql_suite import QUERIES, UNIQUE_KEYS
from oceanbase_tpu.server.database import Database
from oceanbase_tpu.storage.sorted_projection import make_sorted_projection


@pytest.fixture(scope="module")
def db():
    d = Database(n_nodes=1, n_ls=1, extra_catalog=datagen.generate(0.01))
    # preloaded benchmark tables carry no DDL primary keys; register
    # their unique keys on the live planner/executor so the physical
    # fast paths (merge/affine/clustered) are eligible
    d._unique_keys.update(UNIQUE_KEYS)
    d.engine.executor.unique_keys = d._unique_keys
    d.engine.planner.unique_keys = d._unique_keys
    make_sorted_projection(d.catalog, "lineitem", "l_shipdate")
    yield d
    d.close()


def _text(db, sql):
    return "\n".join(
        r[0] for r in db.session().sql("explain " + sql).rows()
    )


def test_q6_shows_projection_slice(db):
    t = _text(db, QUERIES[6])
    assert "sorted projection" in t
    assert "sliced cap=" in t
    assert "lineitem#sp:l_shipdate" in t


def test_q3_shows_clustered_aggregation(db):
    t = _text(db, QUERIES[3])
    assert "clustered-FK segment reduction" in t
    # which program the user got: datagen's orders lie in key order and
    # every l_orderkey has its order, so the ranges tile
    assert "lineitem.l_orderkey -> orders.o_orderkey, bounds shared" in t
    assert "direct-address (affine build key)" in t  # orders x customer


def test_q3_counts_its_bounds_in_sysstat(db):
    """The program EXPLAIN names is the one a served Q3 compiles, and the
    tenant's sysstat counts it per compiled program."""
    s = db.session()
    assert len(s.sql(QUERIES[3]).rows()) > 0
    stat = {
        r[0]: float(r[1]) for r in s.sql(
            "select name, value from __all_virtual_sysstat "
            "where name like 'clustered agg bounds%'").rows()
    }
    assert stat.get("clustered agg bounds shared", 0) >= 1
    assert stat.get("clustered agg bounds gathered", 0) == 0


def test_q17_shows_and_counts_its_sort_merge_join(db):
    """`part` meets a grouped subquery: a unique build that is no table in
    storage order, so the join sort-merges. EXPLAIN names the lowering and
    a served Q17 counts the join, once per compiled program; Q3's joins
    above are direct-address and count nothing."""
    assert ("merge (combined sort, run heads scan-carried, unique build)"
            in _text(db, QUERIES[17]))
    s = db.session()

    def counted():
        rows = s.sql("select value from __all_virtual_sysstat "
                     "where name = 'merge join scan-carried'").rows()
        return float(rows[0][0]) if rows else 0.0

    before = counted()
    s.sql(QUERIES[3]).rows()
    assert counted() == before
    s.sql(QUERIES[17]).rows()
    assert counted() == before + 1


def test_ann_route_annotated(db):
    import numpy as np

    from oceanbase_tpu.core.dtypes import DataType, Field, Schema, TypeKind
    from oceanbase_tpu.core.table import Table
    from oceanbase_tpu.storage.vector_index import register_vector_index

    rng = np.random.default_rng(0)
    db.catalog["docs"] = Table(
        "docs",
        Schema((
            Field("id", DataType(TypeKind.INT64)),
            Field("emb", DataType.vector(8)),
        )),
        {"id": np.arange(512, dtype=np.int64),
         "emb": rng.normal(size=(512, 8)).astype(np.float32)},
    )
    register_vector_index(db.catalog, "docs", "emb", lists=16, nprobe=4)
    lit = "[" + ",".join("0.1" for _ in range(8)) + "]"
    t = _text(
        db, f"select id from docs order by vec_l2(emb, '{lit}') limit 5"
    )
    assert "ANN IVF probe" in t
    assert "nprobe=4" in t


def test_explain_respects_privileges(db):
    """A plan leaks table/column names and estimates: EXPLAIN demands
    the same SELECT grants as the statement (review finding)."""
    from oceanbase_tpu.server.database import SqlError

    root = db.session()
    try:
        root.sql("create user peek")
    except SqlError:
        pass  # module fixture reuse
    peek = db.session(user="peek")
    with pytest.raises(SqlError) as e:
        peek.sql("explain select count(*) as n from lineitem")
    assert e.value.code == 1142
    # leading whitespace / odd casing still routes (and still checks)
    with pytest.raises(SqlError):
        peek.sql("   EXPLAIN select count(*) as n from lineitem")


def test_explain_never_executes(db):
    """EXPLAIN of a statement over a huge hypothetical limit is instant
    and returns only plan text (no result columns of the query)."""
    rs = db.session().sql("explain select count(*) as n from lineitem")
    assert rs.names == ("plan",)
    assert any("AGGREGATE" in r[0] for r in rs.rows())
