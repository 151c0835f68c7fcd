"""Hand-composed physical plans for TPC-H queries (kernel-level).

These are the reference physical plans the SQL compiler (oceanbase_tpu/sql)
must eventually reproduce from text; until then they serve as the
end-to-end slice (SURVEY.md §7 step 4) and the benchmark bodies. Each
builder returns a jitted device function over ColumnBatch pytrees plus a
host-side finisher that shapes the device outputs into result rows.

Q6: scan + fused filter + masked sum (one pass over 4 columns — the
    TPU analog of the reference's pushdown-filter + pushdown-aggregate path,
    storage/access/ob_aggregated_store_vec.h).
Q1: scan + filter + direct-addressed 8-slot group-by with 7 aggregates
    (packed returnflag×linestatus key — the adaptive low-NDV path).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ...core.column import ColumnBatch
from ...expr import BinaryOp, Compare, and_, col, compile_predicate, evaluate, lit
from ...ops import groupby_direct, pack_keys, scalar_aggregate


# ---------------------------------------------------------------------------
# Q6 — forecasting revenue change
# ---------------------------------------------------------------------------

Q6_PRED = and_(
    Compare(">=", col("l_shipdate"), lit("1994-01-01")),
    Compare("<", col("l_shipdate"), lit("1995-01-01")),
    Compare(">=", col("l_discount"), lit(0.05)),
    Compare("<=", col("l_discount"), lit(0.07)),
    Compare("<", col("l_quantity"), lit(24)),
)


def build_q6():
    rev = BinaryOp("*", col("l_extendedprice"), col("l_discount"))

    @jax.jit
    def q6(batch: ColumnBatch):
        mask = compile_predicate(Q6_PRED, batch)
        vals, _ = evaluate(rev, batch)
        (s,) = scalar_aggregate(mask, ["sum"], [vals])
        return s

    def finish(dev_out) -> float:
        return float(dev_out) / 1e4  # scale-4 decimal

    return q6, finish


# ---------------------------------------------------------------------------
# Q1 — pricing summary report
# ---------------------------------------------------------------------------


def build_q1(rf_domain: int, ls_domain: int):
    """rf_domain/ls_domain: dictionary sizes of returnflag/linestatus."""
    pred = Compare("<=", col("l_shipdate"), lit("1998-09-02"))
    disc_price = BinaryOp(
        "*", col("l_extendedprice"), BinaryOp("-", lit(1), col("l_discount"))
    )
    charge = BinaryOp(
        "*", disc_price, BinaryOp("+", lit(1), col("l_tax"))
    )

    @jax.jit
    def q1(batch: ColumnBatch):
        mask = compile_predicate(pred, batch)
        keys, domain = pack_keys(
            [batch.col("l_returnflag"), batch.col("l_linestatus")],
            [rf_domain, ls_domain],
        )
        qty = batch.col("l_quantity")
        price = batch.col("l_extendedprice")
        disc = batch.col("l_discount")
        dp, _ = evaluate(disc_price, batch)
        ch, _ = evaluate(charge, batch)
        slot_used, aggs = groupby_direct(
            keys,
            domain,
            mask,
            ["sum", "sum", "sum", "sum", "sum", "count"],
            [qty, price, dp, ch, disc, None],
        )
        return slot_used, aggs

    def finish(dev_out, rf_dict, ls_dict):
        slot_used, (s_qty, s_price, s_dp, s_ch, s_disc, cnt) = dev_out
        slot_used = np.asarray(slot_used)
        rows = []
        rf_bits = max(1, (rf_domain - 1).bit_length())
        for slot in np.nonzero(slot_used)[0]:
            rf_code = slot & ((1 << rf_bits) - 1)
            ls_code = slot >> rf_bits
            c = int(cnt[slot])
            rows.append(
                dict(
                    l_returnflag=rf_dict.decode_one(int(rf_code)),
                    l_linestatus=ls_dict.decode_one(int(ls_code)),
                    sum_qty=int(s_qty[slot]) / 100,
                    sum_base_price=int(s_price[slot]) / 100,
                    sum_disc_price=int(s_dp[slot]) / 1e4,
                    sum_charge=int(s_ch[slot]) / 1e6,
                    avg_qty=int(s_qty[slot]) / 100 / c,
                    avg_price=int(s_price[slot]) / 100 / c,
                    avg_disc=int(s_disc[slot]) / 100 / c,
                    count_order=c,
                )
            )
        rows.sort(key=lambda r: (r["l_returnflag"], r["l_linestatus"]))
        return rows

    return q1, finish


# ---------------------------------------------------------------------------
# numpy oracles (CPU vectorized baseline — the "reference CPU engine" side
# of BASELINE.json's >=5x target; measured, not cited)
# ---------------------------------------------------------------------------


def q6_numpy(lineitem) -> float:
    d = lineitem.data
    d0 = int(np.datetime64("1994-01-01", "D").astype(int))
    d1 = int(np.datetime64("1995-01-01", "D").astype(int))
    m = (
        (d["l_shipdate"] >= d0)
        & (d["l_shipdate"] < d1)
        & (d["l_discount"] >= 5)
        & (d["l_discount"] <= 7)
        & (d["l_quantity"] < 2400)
    )
    return float(
        np.sum(
            d["l_extendedprice"][m].astype(np.int64)
            * d["l_discount"][m].astype(np.int64)
        )
        / 1e4
    )


def q1_numpy_fast(lineitem):
    """Vectorized CPU Q1 (bincount on packed keys) — the honest baseline
    an optimized CPU vectorized engine would run; used for timing."""
    d = lineitem.data
    cutoff = int(np.datetime64("1998-09-02", "D").astype(int))
    m = d["l_shipdate"] <= cutoff
    rf = d["l_returnflag"].astype(np.int64)
    ls = d["l_linestatus"].astype(np.int64)
    nls = len(lineitem.dicts["l_linestatus"])
    key = (rf * nls + ls)[m]
    dom = len(lineitem.dicts["l_returnflag"]) * nls
    qty = d["l_quantity"].astype(np.int64)[m]
    price = d["l_extendedprice"].astype(np.int64)[m]
    disc = d["l_discount"].astype(np.int64)[m]
    tax = d["l_tax"].astype(np.int64)[m]
    dp = price * (100 - disc)
    ch = dp * (100 + tax)
    out = {
        "count": np.bincount(key, minlength=dom),
        "sum_qty": np.bincount(key, weights=qty, minlength=dom),
        "sum_price": np.bincount(key, weights=price, minlength=dom),
        "sum_dp": np.bincount(key, weights=dp.astype(np.float64), minlength=dom),
        "sum_ch": np.bincount(key, weights=ch.astype(np.float64), minlength=dom),
        "sum_disc": np.bincount(key, weights=disc, minlength=dom),
    }
    return out


def q1_numpy(lineitem):
    d = lineitem.data
    cutoff = int(np.datetime64("1998-09-02", "D").astype(int))
    m = d["l_shipdate"] <= cutoff
    rf = lineitem.dicts["l_returnflag"].decode(d["l_returnflag"])
    ls = lineitem.dicts["l_linestatus"].decode(d["l_linestatus"])
    rf = np.asarray(rf, dtype=object)
    ls = np.asarray(ls, dtype=object)
    qty = d["l_quantity"].astype(np.int64)
    price = d["l_extendedprice"].astype(np.int64)
    disc = d["l_discount"].astype(np.int64)
    tax = d["l_tax"].astype(np.int64)
    dp = price * (100 - disc)  # scale 4
    ch = dp * (100 + tax)  # scale 6
    rows = []
    for rfv in sorted(set(rf[m])):
        for lsv in sorted(set(ls[m])):
            g = m & (rf == rfv) & (ls == lsv)
            c = int(g.sum())
            if c == 0:
                continue
            rows.append(
                dict(
                    l_returnflag=rfv,
                    l_linestatus=lsv,
                    sum_qty=qty[g].sum() / 100,
                    sum_base_price=price[g].sum() / 100,
                    sum_disc_price=dp[g].sum() / 1e4,
                    sum_charge=ch[g].sum() / 1e6,
                    avg_qty=qty[g].sum() / 100 / c,
                    avg_price=price[g].sum() / 100 / c,
                    avg_disc=disc[g].sum() / 100 / c,
                    count_order=c,
                )
            )
    return rows


def _day(s: str) -> int:
    return int(np.datetime64(s, "D").astype(int))


def q3_cpu(cust, orders, li):
    """Plain numpy Q3 (top 10 by revenue): [orderkey, revenue, orderdate,
    shippriority] rows, revenue from the integer domain."""
    cut = _day("1995-03-15")
    seg = cust.dicts["c_mktsegment"].encode_one("BUILDING", add=False)
    ckeys = cust.data["c_custkey"][np.asarray(cust.data["c_mktsegment"]) == seg]
    om = (np.asarray(orders.data["o_orderdate"]) < cut) & np.isin(
        orders.data["o_custkey"], ckeys
    )
    okeys = orders.data["o_orderkey"][om]  # ascending (generator invariant)
    odate = orders.data["o_orderdate"][om]
    oprio = orders.data["o_shippriority"][om]
    lm = np.asarray(li.data["l_shipdate"]) > cut
    lok = li.data["l_orderkey"][lm]
    pos = np.searchsorted(okeys, lok)
    pos_c = np.minimum(pos, len(okeys) - 1)
    hit = len(okeys) > 0
    sel = (okeys[pos_c] == lok) if hit else np.zeros(len(lok), bool)
    rev = (
        li.data["l_extendedprice"][lm][sel].astype(np.int64)
        * (100 - li.data["l_discount"][lm][sel].astype(np.int64))
    )
    gkey = pos_c[sel]
    sums = np.zeros(len(okeys), np.int64)
    np.add.at(sums, gkey, rev)
    nz = np.nonzero(sums)[0]
    order = np.lexsort((odate[nz], -sums[nz]))[:10]
    top = nz[order]
    return [
        [int(okeys[i]), sums[i] / 1e4, int(odate[i]), int(oprio[i])]
        for i in top
    ]


def q14_cpu(part, li):
    """Plain numpy Q14 (promo revenue share, percent)."""
    lm = (np.asarray(li.data["l_shipdate"]) >= _day("1995-09-01")) & (
        np.asarray(li.data["l_shipdate"]) < _day("1995-10-01")
    )
    pk = li.data["l_partkey"][lm]
    rev = li.data["l_extendedprice"][lm].astype(np.int64) * (
        100 - li.data["l_discount"][lm].astype(np.int64)
    )
    types = np.array(part.dicts["p_type"].values())
    promo_code = np.char.startswith(types, "PROMO")
    is_promo = promo_code[np.asarray(part.data["p_type"])][pk - 1]
    return float(100.0 * rev[is_promo].sum() / max(rev.sum(), 1))
