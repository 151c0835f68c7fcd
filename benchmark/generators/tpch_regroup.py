"""TPC-H Q10 for the benchmark: statement, substitution parameter and plain
numpy reference, on `tpch`'s tables.

Q10 (cl. 2.4.10, "returned item reporting") groups `lineitem ⋈ orders` by
the customer: the rows lie where `l_orderkey` put them and `o_custkey` is
random (cl. 4.2.3), so on a row-sharded deployment every group has rows on
every chip and the hash exchange before the group-by is what brings them
together. It has a module of its own because `tpch.py`, which the accepted
cells run, is not this PR's to edit, and `run.py` and `loadgen.py` take the
generator module from the traffic file.

The data, the DDL and the loader's forms are `tpch`'s, byte for byte for a
seed. Everything here is numpy and the standard library: nothing of the
program is imported. Money is exact integer arithmetic (prices in cents, the
discount in hundredths, so revenue in units of 10^-4).
"""

from __future__ import annotations

from decimal import Decimal

import numpy as np

from benchmark.generators import tpch
from benchmark.generators.tpch import (  # noqa: F401 - the generator's API
    DECIMAL_SCALE,
    _day,
    as_strings,
    generate,
    row_counts,
)

Q10 = """
select
    c_custkey, c_name,
    sum(l_extendedprice * (1 - l_discount)) as revenue,
    c_acctbal, n_name, c_address, c_phone, c_comment
from customer, orders, lineitem, nation
where c_custkey = o_custkey
  and l_orderkey = o_orderkey
  and o_orderdate >= date '{date}'
  and o_orderdate < date '{date_end}'
  and l_returnflag = 'R'
  and c_nationkey = n_nationkey
group by c_custkey, c_name, c_acctbal, c_phone, n_name, c_address, c_comment
order by revenue desc
limit 20
"""
TEXT = {"q10": Q10}
LIMIT = 20
#: cl. 2.4.10.3: DATE is the first day of a month from 1993-02 to 1995-01
MONTHS = 24

# Columns the reference reads; the rest of a table is dropped once loaded.
_REFERENCE_COLUMNS = {
    "lineitem": ("l_orderkey", "l_extendedprice", "l_discount",
                 "l_returnflag"),
    "orders": ("o_orderkey", "o_custkey", "o_orderdate"),
    "customer": ("c_custkey", "c_name", "c_acctbal", "c_phone", "c_address",
                 "c_comment", "c_nationkey"),
    "nation": ("n_nationkey", "n_name"),
}
# the same columns, for the necessary-bytes function of the harness
REFERENCED_COLUMNS = {"q10": {t: list(c)
                              for t, c in _REFERENCE_COLUMNS.items()}}


def reference_columns(config: dict) -> dict:
    return _REFERENCE_COLUMNS


#: What the cell asks of a program before its first table is made: the
#: per-plan lane occupancy it is read by (PR 32). A program without those
#: columns fails the run here, seconds after boot. That is how the parent
#: of PR 32 fails on this cell, over whose checkout the driver lays these
#: files: it has the statement, but groups by all seven keys, and the
#: compile of that program alone is longer than a run (PERF.md section 6).
PROBE = ("select px_exchange_rows, px_exchange_slots "
         "from __all_virtual_sql_plan_monitor limit 1")


def ddl(config: dict) -> list[tuple[str, list[str]]]:
    """`tpch`'s tables, the probe before the first."""
    tables = tpch.ddl(config)
    (first, stmts), rest = tables[0], tables[1:]
    return [(first, [PROBE, *stmts]), *rest]


# ------------------------------------------------------------ statements

def _month(k: int) -> str:
    """The first day of the k-th month after 1993-01."""
    return f"{1993 + k // 12}-{k % 12 + 1:02d}-01"


def draw_literals(kind: str, rng, config: dict) -> dict:
    """One set of substitution parameters (cl. 2.4.10.3): DATE, and the end
    of its three-month interval."""
    if kind != "q10":
        raise KeyError(kind)
    k = 1 + int(rng.integers(0, MONTHS))
    return {"date": _month(k), "date_end": _month(k + 3)}


#: the validation literal of cl. 2.4.10.4
VALIDATION = {"q10": {"date": "1993-10-01", "date_end": "1994-01-01"}}


def render(kind: str, lit: dict) -> str:
    return TEXT[kind].format(**lit)


def draw_pools(traffic: dict, config: dict, seed: int, data=None):
    """(kind -> list of distinct literal sets, literal sets rejected).

    The statement's ORDER BY is not total. The reference breaks ties by
    `c_custkey` and the program may break them otherwise, so a literal set
    whose answer has two equal revenues at or inside rank 21 is rejected:
    what it may return is not one answer. That needs the data, which is
    made here from the seed where the caller has none (the load generator's
    process)."""
    if data is None:
        data = generate(config, seed)
    rng = np.random.default_rng([seed, 0x7C9])
    out, rejected = {}, []
    for kind in traffic["kinds"]:
        pool, seen = [], set()
        while len(pool) < int(traffic["pool"]):
            if len(seen) == MONTHS:
                raise RuntimeError(f"{kind}: the parameter domain holds no "
                                   f"{traffic['pool']} tie-free literal sets")
            lit = draw_literals(kind, rng, config)
            key = tuple(sorted(lit.items()))
            if key in seen:
                continue
            seen.add(key)
            if _tied(_revenue(lit, data)):
                rejected.append(lit)
            else:
                pool.append(lit)
        out[kind] = pool
    return out, rejected


def pools(traffic: dict, config: dict, seed: int) -> dict:
    """kind -> list of distinct literal sets, reproducible from the seed."""
    return draw_pools(traffic, config, seed)[0]


class Stream(tpch.Stream):
    """One closed-loop client's statements: `tpch.Stream`'s rule (the kinds
    in turn, each kind's pool walked in an order drawn from the seed), with
    this module's statement text."""

    def next(self, only: str | None = None):
        kind = only or self.kinds[self.i % len(self.kinds)]
        self.i += 1
        if not self.order[kind]:
            self.order[kind] = list(self.rng.permutation(len(self.pools[kind])))
        lit = self.pools[kind][int(self.order[kind].pop())]
        return kind, lit, render(kind, lit)


def warmup(traffic, config, pools_) -> list:
    """(kind, literals, text) of the validation literal, then of every pool
    member: each runs in set-up. The validation literal comes first because
    the program fixes a plan's lane capacities at the first literal set it
    sees, from an estimate that moves by half with DATE (250 K to 400 K
    `orders` rows for the 57 K a quarter holds): with the pool's own first
    member, which the seed draws, one seed in two got lanes of 32,768 rows
    and the other of 65,536, another program and 20 % of a statement's time
    apart (PR 32's chip runs, PERF.md section 6). The spec's own literal
    makes every seed's program the same one."""
    return [(k, lit, render(k, lit)) for k in traffic["kinds"]
            for lit in [VALIDATION[k], *pools_[k]]]


# ------------------------------------------------------------ reference

def _text(col, i: int) -> str:
    """Row i of a string column in any of the generator's forms."""
    if isinstance(col, tuple):
        codes, vocab = col
        return str(vocab[codes[i]])
    v = col[i]
    return v.decode("ascii") if isinstance(v, bytes) else str(v)


def _revenue(lit: dict, data: dict, acc=np.int64):
    """(customer keys with a returned line ordered in the quarter, their
    revenue in units of 10^-4), by customer key."""
    li, od = data["lineitem"], data["orders"]
    lo, hi = _day(lit["date"]), _day(lit["date_end"])
    o_ok = (od["o_orderdate"] >= lo) & (od["o_orderdate"] < hi)
    flags, vocab = li["l_returnflag"]
    returned = flags == list(vocab).index("R")
    # the order of each returned line: o_orderkey is ascending (cl. 4.2.3)
    okeys = od["o_orderkey"]
    pos = np.searchsorted(okeys, li["l_orderkey"][returned])
    keep = o_ok[pos]
    pos = pos[keep]
    cust = od["o_custkey"][pos]
    ext = li["l_extendedprice"][returned][keep]
    disc = li["l_discount"][returned][keep]
    rev_row = ext * (100 - disc)
    n = int(cust.max()) + 1 if len(cust) else 1
    if acc is np.int64:
        # exact: every partial sum is an integer far below 2**53
        rev = np.bincount(cust, weights=rev_row.astype(np.float64),
                          minlength=n).astype(np.int64)
    else:
        rev = np.zeros(n, dtype=acc)
        np.add.at(rev, cust, rev_row.astype(acc))
    hit = np.flatnonzero(np.bincount(cust, minlength=n))
    return hit, rev[hit]


def _tied(hit_rev) -> bool:
    """Two equal revenues at or inside rank LIMIT + 1."""
    _hit, rev = hit_rev
    top = np.sort(rev)[::-1][:LIMIT + 1]
    return bool((top[1:] == top[:-1]).any())


def reference(kind: str, lit: dict, data: dict, acc=np.int64):
    """Rows the statement must return, as tuples of int / str / Decimal in
    the statement's column order, revenue descending, ties by `c_custkey`.

    `acc` is the accumulator type: int64 is exact; the control of
    tests/test_px_regroup.py passes float32 (the precision below)."""
    if kind != "q10":
        raise KeyError(kind)
    cu, na = data["customer"], data["nation"]
    hit, rev = _revenue(lit, data, acc)
    order = np.lexsort((hit, -rev))
    row_of = np.full(int(cu["c_custkey"].max()) + 1, -1, dtype=np.int64)
    row_of[cu["c_custkey"]] = np.arange(len(cu["c_custkey"]))
    nation_of = np.full(int(na["n_nationkey"].max()) + 1, -1, dtype=np.int64)
    nation_of[na["n_nationkey"]] = np.arange(len(na["n_nationkey"]))

    def money(v, places):
        if acc is np.int64:
            return Decimal(int(v)).scaleb(-places)
        return Decimal(repr(float(v))) * Decimal(10) ** -places

    rows = []
    for j in order[:LIMIT]:
        c = int(row_of[hit[j]])
        n = int(nation_of[cu["c_nationkey"][c]])
        # the inner joins: a customer and its nation exist (cl. 4.2.3)
        assert c >= 0 and n >= 0, (hit[j], c, n)
        rows.append((int(hit[j]), _text(cu["c_name"], c), money(rev[j], 4),
                     Decimal(int(cu["c_acctbal"][c])).scaleb(-2),
                     _text(na["n_name"], n), _text(cu["c_address"], c),
                     _text(cu["c_phone"], c), _text(cu["c_comment"], c)))
    return rows
