"""dict_lookup: the one place a per-dictionary-entry host table becomes a
per-row device array (expr/compile.py). Bit for bit `table[clip(codes)]`
under each lowering, through SQL on sorted and appended-to dictionaries
with NULLs, and no gather in the HLO where the table allows a test of
the code."""

import jax
import numpy as np
import pytest

from oceanbase_tpu.core.dictionary import Dictionary
from oceanbase_tpu.core.dtypes import DataType, Field, Schema, TypeKind
from oceanbase_tpu.core.table import Table
from oceanbase_tpu.engine import Session
from oceanbase_tpu.expr import BinaryOp, Case, Func, col, evaluate, lit
from oceanbase_tpu.expr import compile as C
from oceanbase_tpu.share.metrics import MetricsRegistry

R = C.LOOKUP_MAX_RUNS
LOWERINGS = ("constant", "runs", "gather")


def _runs(n, k, width=1):
    """k runs of `width` true entries, evenly spaced, none at an end."""
    t = np.zeros(n, np.bool_)
    for i in range(k):
        lo = 1 + i * (n - 2) // k
        t[lo:lo + width] = True
    return t


def _scattered(n, seed=7):
    return np.random.default_rng(seed).random(n) < 0.5


def _at(n, lo, hi):
    t = np.zeros(n, np.bool_)
    t[lo:hi] = True
    return t


# (id, table, the lowering the table must choose)
TABLES = [
    ("empty_bool", np.zeros(0, np.bool_), "constant"),
    ("empty_int64", np.zeros(0, np.int64), "constant"),
    ("one_entry_true", np.ones(1, np.bool_), "constant"),
    ("one_entry_false", np.zeros(1, np.bool_), "constant"),
    ("all_true", np.ones(150, np.bool_), "constant"),
    ("all_false", np.zeros(150, np.bool_), "constant"),
    ("run_at_start", _at(150, 0, 25), "runs"),
    ("run_in_middle", _at(150, 100, 125), "runs"),
    ("run_at_end", _at(150, 125, 150), "runs"),
    ("single_entry_run", _at(150, 77, 78), "runs"),
    ("max_runs", _runs(150, R, 3), "runs"),
    ("max_runs_plus_one", _runs(150, R + 1, 3), "gather"),
    ("one_hole", ~_at(150, 100, 125), "runs"),
    ("max_runs_less_one_holes", ~_runs(150, R - 1, 3), "runs"),
    ("max_runs_holes", ~_runs(150, R, 3), "gather"),
    ("run_over_200k", _at(200_000, 81_234, 82_345), "runs"),
    ("max_runs_over_200k", _runs(200_000, R, 1000), "runs"),
    ("two_entries", np.array([False, True]), "runs"),
    ("seven_entries", np.array([1, 0, 1, 1, 0, 0, 1], np.bool_), "runs"),
    ("scattered_32", _scattered(32), "runs"),
    ("scattered_33", _scattered(33), "runs"),
    ("alternating_2r", np.arange(2 * R) % 2 == 0, "runs"),
    ("alternating_2r_plus_1", np.arange(2 * R + 1) % 2 == 0, "gather"),
    ("scattered_150", _scattered(150), "gather"),
    ("scattered_4096", _scattered(4096), "gather"),
    ("scattered_200k", _scattered(200_000), "gather"),
    ("int64_table", np.arange(150, dtype=np.int64)[::-1] * 3, "gather"),
    ("int32_constant_table", np.full(9, 5, np.int32), "gather"),
    ("float64_table", np.linspace(-1.0, 1.0, 41), "gather"),
]


class _Counting:
    """Count this thread's lowerings in a registry of the test's own."""

    def __enter__(self):
        self.m = MetricsRegistry()
        self.prev = C.set_lookup_metrics(self.m)
        return self

    def __exit__(self, *exc):
        C.set_lookup_metrics(self.prev)

    def chosen(self):
        return {k: int(self.m.counter(f"dict lookup {k}")) for k in LOWERINGS
                if self.m.counter(f"dict lookup {k}")}


def _check_table(table, lowering):
    n = len(table)
    rng = np.random.default_rng(n)
    codes = np.r_[
        np.arange(-2, n + 3), rng.integers(-3, n + 3, 300),
        [-1, n + 1, np.iinfo(np.int32).min, np.iinfo(np.int32).max],
    ].astype(np.int32)
    with _Counting() as cnt:
        got = np.asarray(jax.jit(lambda c: C.dict_lookup(table, c))(codes))
    want = (table[np.clip(codes, 0, n - 1)] if n
            else np.zeros(len(codes), table.dtype))
    assert got.dtype == want.dtype
    assert got.tobytes() == want.tobytes()
    if lowering is not None:
        assert cnt.chosen() == {lowering: 1}


# --- through SQL -----------------------------------------------------------

# TPC-H cl. 4.2.2.13 p_type: 6 x 5 x 5 = 150 values
P_TYPES = [
    f"{a} {b} {c}"
    for a in ("STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO")
    for b in ("ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED")
    for c in ("TIN", "NICKEL", "BRASS", "STEEL", "COPPER")
]


def _session(kind):
    """400 rows over the 150 p_type values, every seventh NULL; `sorted`
    as a load leaves the dictionary, `appended` as DML leaves it (a
    sorted load of 100 values, the other 50 appended after it)."""
    rng = np.random.default_rng(11)
    strings = [P_TYPES[i] for i in rng.integers(0, len(P_TYPES), 400)]
    if kind == "sorted":
        d = Dictionary(sorted(P_TYPES), sorted_=True)
    else:
        first = set(rng.choice(len(P_TYPES), 100, replace=False).tolist())
        d = Dictionary(sorted(P_TYPES[i] for i in first), sorted_=True)
        d.encode([p for i, p in enumerate(P_TYPES) if i not in first])
        assert not d.sorted
    valid = np.arange(400) % 7 != 3
    codes = d.encode(strings, add=False)
    codes[~valid] = -1  # what a NULL's slot may hold is not a code
    t = Table(
        "part",
        Schema((Field("id", DataType(TypeKind.INT64)),
                Field("p_type", DataType.varchar(nullable=True)))),
        {"id": np.arange(400, dtype=np.int64), "p_type": codes},
        {"p_type": d},
        {"p_type": valid},
    )
    rows = [s if v else None for s, v in zip(strings, valid)]
    return Session({"part": t}), rows


# every fourth value in sorted order: 38 runs of one entry
_EVERY_FOURTH = tuple(sorted(P_TYPES)[::4])

# (id, predicate, python oracle over a non-NULL value, lowering when sorted)
PREDICATES = [
    ("in_list_scattered",
     "p_type in (%s)" % ", ".join(f"'{v}'" for v in _EVERY_FOURTH),
     lambda s: s in _EVERY_FOURTH, "gather"),
    ("like_prefix", "p_type like 'PROMO%'",
     lambda s: s.startswith("PROMO"), "runs"),
    ("like_suffix", "p_type like '%BRASS'",
     lambda s: s.endswith("BRASS"), "runs"),
    ("like_infix", "p_type like '%POLISHED%'",
     lambda s: "POLISHED" in s, "runs"),
    ("not_like", "p_type not like 'PROMO%'",
     lambda s: not s.startswith("PROMO"), "runs"),
    ("in_list", "p_type in ('SMALL PLATED TIN', 'LARGE BRUSHED STEEL', "
     "'PROMO ANODIZED BRASS', 'no such type')",
     lambda s: s in ("SMALL PLATED TIN", "LARGE BRUSHED STEEL",
                     "PROMO ANODIZED BRASS"), "runs"),
    ("not_in_list", "p_type not in ('SMALL PLATED TIN', 'ECONOMY PLATED TIN')",
     lambda s: s not in ("SMALL PLATED TIN", "ECONOMY PLATED TIN"), "runs"),
    ("substr_eq", "substring(p_type, 1, 5) = 'SMALL'",
     lambda s: s[:5] == "SMALL", "runs"),
    ("fts_match", "fts_match(p_type, 'brushed copper')",
     lambda s: {"brushed", "copper"} <= set(s.lower().split()), "runs"),
    ("like_everything", "p_type like '%'", lambda s: True, "constant"),
]


def _check_sql(kind, pred, oracle, lowering):
    sess, rows = _session(kind)
    with _Counting() as cnt:
        rs = sess.sql(f"select id from part where {pred} order by id")
    got = [int(r[0]) for r in rs.rows()]
    want = [i for i, s in enumerate(rows) if s is not None and oracle(s)]
    assert want and got == want
    chosen = cnt.chosen()
    assert chosen, "the predicate never reached dict_lookup"
    if kind == "sorted":
        assert set(chosen) == {lowering}


CASES = [
    pytest.param(_check_table, (t, low), id=f"table-{name}")
    for name, t, low in TABLES
] + [
    pytest.param(_check_sql, (kind, pred, oracle, low),
                 id=f"sql-{kind}-{name}")
    for kind in ("sorted", "appended")
    for name, pred, oracle, low in PREDICATES
]


@pytest.mark.parametrize("check,args", CASES)
def test_dict_lookup(check, args):
    check(*args)


# --- what reaches the compiler ---------------------------------------------


def _q14_shape_hlo(names, pattern):
    """HLO of `sum(case when s like <pattern> then x * 2 else 0 end)`."""
    d = Dictionary(sorted(names), sorted_=True)
    n = 1000
    t = Table(
        "t",
        Schema((Field("s", DataType.varchar()),
                Field("x", DataType.decimal(12, 2)))),
        {"s": (np.arange(n) % len(d)).astype(np.int32),
         "x": np.arange(n, dtype=np.int64)},
        {"s": d},
    )
    batch = t.to_batch()
    e = Case(
        whens=((Func("like", (col("s"), lit(pattern))),
                BinaryOp("*", col("x"), lit(2))),),
        default=lit(0),
    )

    def q14(b):
        v, _ = evaluate(e, b)
        return v.sum()

    return jax.jit(q14).lower(batch).as_text()


def test_hlo_like_prefix_has_no_gather_and_counters_in_sysstat():
    """A prefix LIKE over a sorted 150-entry dictionary compiles to
    compares; an infix LIKE over a large scattered one keeps today's
    gather; both choices are counted, and sysstat shows the counters."""
    from oceanbase_tpu.server.database import Database

    rng = np.random.default_rng(5)
    colours = ("green", "blue", "red", "ivory", "khaki", "plum", "linen")
    # numbered first, so the names with a green in them lie scattered
    names = [f"{i:05d} {rng.choice(colours)} {rng.choice(colours)}"
             for i in range(5000)]
    db = Database(n_nodes=1, n_ls=1)
    try:
        prev = C.set_lookup_metrics(db.metrics)
        try:
            before = db.metrics.counters_snapshot()
            hlo = _q14_shape_hlo(P_TYPES, "P%")
            assert "gather" not in hlo and "stablehlo.compare" in hlo
            hlo = _q14_shape_hlo(names, "%green%")
            assert hlo.count('"stablehlo.gather"(') == 1
        finally:
            C.set_lookup_metrics(prev)
        after = db.metrics.counters_snapshot()
        delta = {k: int(after.get(f"dict lookup {k}", 0)
                        - before.get(f"dict lookup {k}", 0))
                 for k in LOWERINGS}
        assert delta == {"constant": 0, "runs": 1, "gather": 1}
        # a served statement counts into the same registry, by itself
        s = db.session()
        s.sql("create table p (id int primary key, ty varchar(32))")
        s.sql("insert into p values (1, 'PROMO TIN'), (2, 'SMALL TIN'), "
              "(3, 'PROMO BRASS')")
        rs = s.sql("select id from p where ty like 'PROMO%' order by id")
        assert [int(r[0]) for r in rs.rows()] == [1, 3]
        stat = {
            r[0]: float(r[1]) for r in s.sql(
                "select name, value from __all_virtual_sysstat "
                "where name like 'dict lookup%'").rows()
        }
        assert stat["dict lookup runs"] >= 2
        assert stat["dict lookup gather"] == 1
    finally:
        db.close()
