"""Group keys that a unique key among them determines (`Aggregate.
dependent_keys`, `sql/logical.py` `dependent_group_keys`): which keys the
planner marks, that the marked keys are no sort operand and still come back
as written, and that every answer equals the sqlite oracle of
`tests/test_tpch_full.py`. Narrow on purpose: nothing is inferred through a
join equality, so TPC-H Q3 and Q14 keep the plans (and the program names)
they had.
"""

from __future__ import annotations

import re

import pytest

from oceanbase_tpu.engine.executor import Executor
from oceanbase_tpu.models.tpch.sql_suite import QUERIES, UNIQUE_KEYS
from oceanbase_tpu.sql.explain import explain_plan
from oceanbase_tpu.sql.logical import Aggregate
from oceanbase_tpu.sql.parser import parse
from oceanbase_tpu.sql.plan_cache import plan_fingerprint
from oceanbase_tpu.sql.planner import Planner

from test_tpch_full import _norm, _norm_engine_value, db, to_sqlite  # noqa: F401


def aggregates(op):
    found = [op] if isinstance(op, Aggregate) else []
    for attr in ("child", "left", "right"):
        c = getattr(op, attr, None)
        if c is not None:
            found += aggregates(c)
    return found


def top_aggregate(tables, sql: str) -> Aggregate:
    plan = Planner(tables, unique_keys=UNIQUE_KEYS).plan(parse(sql)).plan
    return aggregates(plan)[0]


# (name, statement, the keys that must be marked dependent -> their table)
CASES = [
    ("q10", QUERIES[10], {
        "customer.c_name": "customer", "customer.c_acctbal": "customer",
        "customer.c_phone": "customer", "customer.c_address": "customer",
        "customer.c_comment": "customer"}),
    ("q18", QUERIES[18], {
        "customer.c_name": "customer", "orders.o_orderdate": "orders",
        "orders.o_totalprice": "orders"}),
    ("q3", QUERIES[3], {}),  # o_orderkey is not among its keys
    ("unique_key_whole", """
        select c_custkey, c_name, c_phone, count(*) as n, sum(o_totalprice) as t
        from customer, orders where c_custkey = o_custkey and c_custkey < 200
        group by c_custkey, c_name, c_phone""",
     {"customer.c_name": "customer", "customer.c_phone": "customer"}),
    ("compound_key_whole", """
        select ps_partkey, ps_suppkey, ps_availqty, count(*) as n
        from partsupp, lineitem
        where ps_partkey = l_partkey and ps_suppkey = l_suppkey
          and ps_partkey < 50
        group by ps_partkey, ps_suppkey, ps_availqty""",
     {"partsupp.ps_availqty": "partsupp"}),
    ("compound_key_partly", """
        select ps_partkey, ps_availqty, count(*) as n from partsupp
        where ps_partkey < 50 group by ps_partkey, ps_availqty""", {}),
    ("dependent_is_an_expression", """
        select c_custkey, c_acctbal + 1 as b, count(*) as n
        from customer, orders where c_custkey = o_custkey and c_custkey < 200
        group by c_custkey, c_acctbal + 1""", {}),
    ("unique_key_is_an_expression", """
        select c_custkey + 0 as k, c_name, count(*) as n
        from customer, orders where c_custkey = o_custkey and c_custkey < 200
        group by c_custkey + 0, c_name""", {}),
    ("two_aliases_other_instance", """
        select a.c_custkey, b.c_name, count(*) as n
        from customer a, customer b
        where a.c_nationkey = b.c_nationkey and a.c_custkey < 40
          and b.c_custkey < 40
        group by a.c_custkey, b.c_name""", {}),
    ("two_aliases_same_instance", """
        select a.c_custkey, a.c_name as an, b.c_name as bn, count(*) as n
        from customer a, customer b
        where a.c_nationkey = b.c_nationkey and a.c_custkey < 40
          and b.c_custkey < 40
        group by a.c_custkey, a.c_name, b.c_name""",
     {"a.c_name": "customer"}),
    ("null_supplying_side", """
        select c_custkey, c_name, count(*) as n
        from orders left join customer
          on o_custkey = c_custkey and c_acctbal > 5000
        where o_orderkey < 2000
        group by c_custkey, c_name""", {}),
    ("preserved_side", """
        select c_custkey, c_name, count(o_orderkey) as n
        from customer left join orders on o_custkey = c_custkey
        where c_custkey < 200
        group by c_custkey, c_name""", {"customer.c_name": "customer"}),
]


@pytest.mark.parametrize("name,sql,dependent",
                         CASES, ids=[c[0] for c in CASES])
def test_marked_keys_and_answers(db, name, sql, dependent):  # noqa: F811
    tables, sess, conn = db
    agg = top_aggregate(tables, sql)
    assert dict(agg.dependent_keys) == dependent
    # the keys stay in the list, in the order written
    assert {n for n, _t in agg.dependent_keys} <= {
        n for n, _e in agg.group_keys}
    rs = sess.sql(sql)
    want = [tuple(_norm(v) for v in row)
            for row in conn.execute(to_sqlite(sql)).fetchall()]
    got = [tuple(_norm_engine_value(rs.columns[n][i], n) for n in rs.names)
           for i in range(rs.nrows)]
    assert len(got) == len(want) > 0
    for g, w in zip(sorted(got, key=repr), sorted(want, key=repr)):
        for gv, wv in zip(g, w):
            if isinstance(gv, float) or isinstance(wv, float):
                assert gv == pytest.approx(wv, rel=1e-4, abs=1e-2), (g, w)
            else:
                assert gv == wv, (g, w)


def test_grouping_sets_keep_every_key(db):  # noqa: F811
    """Under ROLLUP a set without the unique key still groups by the other
    keys: nothing is dependent. (sqlite has no ROLLUP: the answer is held to
    the same statement planned without the unique keys.)"""
    tables, sess, _conn = db
    sql = """select c_custkey, c_name, count(*) as n from customer
             where c_custkey < 30 group by rollup(c_custkey, c_name)"""
    agg = top_aggregate(tables, sql)
    assert agg.grouping_sets is not None and agg.dependent_keys == ()
    plain = Executor(tables).execute(Planner(tables).plan(parse(sql)).plan)
    assert sess.sql(sql).nrows == int(plain.nrows) == 29 * 2 + 1


# the fingerprints of the parent commit's plans (SF 0.01, UNIQUE_KEYS): the
# program's name is made of them, so a plan that keeps its fingerprint keeps
# its compiled program
PARENT_FINGERPRINT = {
    1: "64fb53a8be3d4a13eb29944f0ddf91c3",
    3: "db26d5f1b6c3ae78fcf0332d2136506a",
    5: "2bdc210968c0236a9c6e5230197a81ec",
    14: "a331a1d099afe530bdaab785843b9123",
}


@pytest.mark.parametrize("q", sorted(PARENT_FINGERPRINT))
def test_plans_without_dependents_are_the_parents(db, q):  # noqa: F811
    tables, _sess, _conn = db
    plan = Planner(tables, unique_keys=UNIQUE_KEYS).plan(
        parse(QUERIES[q])).plan
    assert all(a.dependent_keys == () for a in aggregates(plan))
    assert "dependent_keys" not in repr(plan)
    assert plan_fingerprint(plan) == PARENT_FINGERPRINT[q]


def test_dependents_change_the_fingerprint(db):  # noqa: F811
    tables, _sess, _conn = db
    with_rule = Planner(tables, unique_keys=UNIQUE_KEYS).plan(
        parse(QUERIES[10])).plan
    without = Planner(tables).plan(parse(QUERIES[10])).plan
    assert "dependent_keys=(('customer.c_name', 'customer')" in repr(with_rule)
    assert plan_fingerprint(with_rule) != plan_fingerprint(without)


def test_explain_and_sort_operands(db):  # noqa: F811
    """EXPLAIN names what the rule did, and the lowered program shows it:
    Q10's widest sort has 4 operands (dead flag, c_custkey, n_name, row
    index) where the seven keys made 10."""
    tables, _sess, _conn = db
    ex = Executor(tables, unique_keys=UNIQUE_KEYS)
    prepared = ex.prepare(Planner(tables, unique_keys=UNIQUE_KEYS).plan(
        parse(QUERIES[10])).plan)
    line = next(ln for ln in explain_plan(ex, prepared.plan, prepared.params)
                if "AGGREGATE" in ln)
    assert ("keys=['customer.c_custkey', 'nation.n_name'] (+5 dependent on "
            "the unique key of customer)") in line
    text = prepared.jitted.lower(prepared._inputs(), ()).as_text()
    operands = [len(m.split(",")) for m in re.findall(
        r'"stablehlo\.sort"\(([^)]*)\)', text)]
    assert operands and max(operands) <= 6, operands


def test_nullable_dependents_carry_their_validity():
    """A dependent that is NULL on some rows is carried with its validity
    plane: a nullable text column and a nullable integer, against a python
    oracle."""
    import numpy as np

    from oceanbase_tpu.core.dtypes import DataType, Field, Schema
    from oceanbase_tpu.core.table import Table
    from oceanbase_tpu.engine import Session

    ids = np.arange(1, 41)
    I32 = DataType.int32()
    acct = Table.from_pydict("acct", Schema((
        Field("id", I32), Field("name", DataType.varchar().with_nullable(True)),
        Field("tier", I32.with_nullable(True)), Field("region", I32))), {
            "id": ids, "name": [f"n{i % 5}" for i in ids], "tier": ids % 2,
            "region": ids % 3})
    acct.valid = {"name": ids % 3 != 0, "tier": ids % 4 != 0}
    m = np.arange(1, 401)
    moves = Table.from_pydict("moves", Schema((
        Field("mid", I32), Field("acct_id", I32), Field("amount", I32))), {
            "mid": m, "acct_id": 1 + m % 40, "amount": m * 7 % 11})
    sess = Session({"acct": acct, "moves": moves},
                   unique_keys={"acct": (("id",),), "moves": (("mid",),)})
    sql = ("select id, name, tier, region, sum(amount) as total, "
           "count(*) as n from acct, moves where id = acct_id "
           "group by id, name, tier, region")
    agg = aggregates(sess.planner.plan(parse(sql)).plan)[0]
    assert [n for n, _t in agg.dependent_keys] == [
        "acct.name", "acct.tier", "acct.region"]
    # a unique key with a nullable column is no premise: its NULLs group
    # as one and are many rows
    loose = Session({"acct": acct, "moves": moves},
                    unique_keys={"acct": (("tier",),)})
    by_tier = "select tier, region, count(*) as n from acct group by tier, region"
    assert aggregates(loose.planner.plan(parse(by_tier)).plan)[0] \
        .dependent_keys == ()
    want = {}
    for k in m:
        i = int(1 + k % 40)
        total, n = want.get(i, (0, 0))
        want[i] = (total + int(k * 7 % 11), n + 1)
    got = {r[0]: tuple(r[1:]) for r in sess.sql(sql).rows()}
    assert got == {int(i): (None if i % 3 == 0 else f"n{i % 5}",
                            None if i % 4 == 0 else int(i % 2), int(i % 3),
                            *want[int(i)]) for i in ids}
