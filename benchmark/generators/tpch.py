"""TPC-H for the benchmark: data, statements and plain numpy references.

Everything here is the benchmark's own (numpy only, nothing imported from
the program): the tables are made from the seed, the statements carry the
substitution parameters of TPC-H cl. 2.4, and each reference answers one
statement with those literals on the generated arrays, in exact integer
arithmetic (money in cents, discount and tax in hundredths).

The data follows cl. 4.2.3 column by column: every column of cl. 1.4 is
there at its declared width, keys, dates, quantities, prices and flags from
their domains, names and phone numbers in their formats, addresses as
v-strings, and every comment a substring of pseudo text made by the grammar
of cl. 4.2.2.10, of a length drawn from the column's range, so that text is
as wide and as nearly unique as dbgen's. What is not dbgen's: the random
streams (numpy's, from the seed) and the size of the text pool (`TEXT_POOL`,
the configuration's `assumed`).

Low-cardinality strings are kept as `(codes, vocab)` pairs and wide text as
fixed-width bytes; `as_strings` expands both for the loader.
"""

from __future__ import annotations

import datetime as _dt
import random
from decimal import Decimal

import numpy as np

BASE_ROWS = {"lineitem": 6_001_215, "orders": 1_500_000, "customer": 150_000,
             "part": 200_000, "supplier": 10_000, "partsupp": 800_000,
             "nation": 25, "region": 5}


def _day(s: str) -> int:
    return (_dt.date.fromisoformat(s) - _dt.date(1970, 1, 1)).days


START, END, CURRENT = _day("1992-01-01"), _day("1998-12-01"), _day("1995-06-17")

SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
SHIPMODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]
INSTRUCTS = ["DELIVER IN PERSON", "COLLECT COD", "NONE", "TAKE BACK RETURN"]
NATIONS = [
    ("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1),
    ("EGYPT", 4), ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3),
    ("INDIA", 2), ("INDONESIA", 2), ("IRAN", 4), ("IRAQ", 4),
    ("JAPAN", 2), ("JORDAN", 4), ("KENYA", 0), ("MOROCCO", 0),
    ("MOZAMBIQUE", 0), ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3),
    ("SAUDI ARABIA", 4), ("VIETNAM", 2), ("RUSSIA", 3),
    ("UNITED KINGDOM", 3), ("UNITED STATES", 1),
]
REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
TYPE_S1 = ["STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO"]
TYPE_S2 = ["ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED"]
TYPE_S3 = ["TIN", "NICKEL", "BRASS", "STEEL", "COPPER"]
CONTAINERS_1 = ["SM", "LG", "MED", "JUMBO", "WRAP"]
CONTAINERS_2 = ["CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN", "DRUM"]
P_NAME_WORDS = (
    "almond antique aquamarine azure beige bisque black blanched blue blush "
    "brown burlywood burnished chartreuse chiffon chocolate coral cornflower "
    "cornsilk cream cyan dark deep dim dodger drab firebrick floral forest "
    "frosted gainsboro ghost goldenrod green grey honeydew hot indian ivory "
    "khaki lace lavender lawn lemon light lime linen magenta maroon medium "
    "metallic midnight mint misty moccasin navajo navy olive orange orchid "
    "pale papaya peach peru pink plum powder puff purple red rose rosy royal "
    "saddle salmon sandy seashell sienna sky slate smoke snow spring steel tan "
    "thistle tomato turquoise violet wheat white yellow").split()

# cl. 4.2.2.10: the word lists of the pseudo-text grammar
GRAMMAR = {
    "N": "foxes|ideas|theodolites|pinto beans|instructions|dependencies|"
         "excuses|platelets|asymptotes|courts|dolphins|multipliers|sauternes|"
         "warthogs|frets|dinos|attainments|somas|Tiresias'|patterns|forges|"
         "braids|hockey players|frays|warhorses|dugouts|notornis|epitaphs|"
         "pearls|tithes|waters|orbits|gifts|sheaves|depths|sentiments|decoys|"
         "realms|pains|grouches|escapades",
    "V": "sleep|wake|are|cajole|haggle|nag|use|boost|affix|detect|integrate|"
         "maintain|nod|was|lose|sublate|solve|thrash|promise|engage|hinder|"
         "print|x-ray|breach|eat|grow|impress|mold|poach|serve|run|dazzle|"
         "snooze|doze|unwind|kindle|play|hang|believe|doubt",
    "J": "furious|sly|careful|blithe|quick|fluffy|slow|quiet|ruthless|thin|"
         "close|dogged|daring|brave|stealthy|permanent|enticing|idle|busy|"
         "regular|final|ironic|even|bold|silent",
    "D": "sometimes|always|never|furiously|slyly|carefully|blithely|quickly|"
         "fluffily|slowly|quietly|ruthlessly|thinly|closely|doggedly|daringly|"
         "bravely|stealthily|permanently|enticingly|idly|busily|regularly|"
         "finally|ironically|evenly|boldly|silently",
    "P": "about|above|according to|across|after|against|along|alongside of|"
         "among|around|at|atop|before|behind|beneath|beside|besides|between|"
         "beyond|by|despite|during|except|for|from|in place of|inside|"
         "instead of|into|near|of|on|outside|over|past|since|through|"
         "throughout|to|toward|under|until|up|upon|without|with|within",
    "X": "do|may|might|shall|will|would|can|could|should|ought to|must|"
         "will have to|shall have to|could have to|should have to|"
         "must have to|need to|try to",
    "T": ".|;|:|?|!|--",
}
GRAMMAR = {k: v.split("|") for k, v in GRAMMAR.items()}
SENTENCES = ["np vp", "np vp pp", "np vp np", "np pp vp", "np pp vp np"]
PHRASES = {"np": ["N", "J N", "J, J N", "D J N"],
           "vp": ["V", "X V", "V D", "X V D"],
           "pp": ["P the np"]}
#: bytes of pseudo text that comments are cut from (dbgen's pool is 300 MB)
TEXT_POOL = 4 << 20
ALNUM = np.frombuffer(b"0123456789abcdefghijklmnopqrstuvwxyz"
                      b"ABCDEFGHIJKLMNOPQRSTUVWXYZ,. ", dtype=np.uint8)

# DDL: cl. 1.4's columns, each at its declared width, every column NOT NULL,
# the primary keys of cl. 1.4.2; numeric types as the program's TPC-H schema
# declares them (identifiers and integers by their domains, decimals scaled)
DDL = {
    "region": "create table region (r_regionkey int8 not null, "
              "r_name char(25) not null, r_comment varchar(152) not null, "
              "primary key (r_regionkey))",
    "nation": "create table nation (n_nationkey int8 not null, "
              "n_name char(25) not null, n_regionkey int8 not null, "
              "n_comment varchar(152) not null, primary key (n_nationkey))",
    "supplier": "create table supplier (s_suppkey int32 not null, "
                "s_name char(25) not null, s_address varchar(40) not null, "
                "s_nationkey int8 not null, s_phone char(15) not null, "
                "s_acctbal decimal(12,2) not null, "
                "s_comment varchar(101) not null, primary key (s_suppkey))",
    "customer": "create table customer (c_custkey int32 not null, "
                "c_name varchar(25) not null, c_address varchar(40) not null, "
                "c_nationkey int8 not null, c_phone char(15) not null, "
                "c_acctbal decimal(12,2) not null, "
                "c_mktsegment char(10) not null, "
                "c_comment varchar(117) not null, primary key (c_custkey))",
    "part": "create table part (p_partkey int32 not null, "
            "p_name varchar(55) not null, p_mfgr char(25) not null, "
            "p_brand char(10) not null, p_type varchar(25) not null, "
            "p_size int32 not null, p_container char(10) not null, "
            "p_retailprice decimal(12,2) not null, "
            "p_comment varchar(23) not null, primary key (p_partkey))",
    "partsupp": "create table partsupp (ps_partkey int32 not null, "
                "ps_suppkey int32 not null, ps_availqty int32 not null, "
                "ps_supplycost decimal(12,2) not null, "
                "ps_comment varchar(199) not null, "
                "primary key (ps_partkey, ps_suppkey))",
    "orders": "create table orders (o_orderkey int64 not null, "
              "o_custkey int32 not null, o_orderstatus char(1) not null, "
              "o_totalprice decimal(12,2) not null, o_orderdate date not null, "
              "o_orderpriority char(15) not null, o_clerk char(15) not null, "
              "o_shippriority int32 not null, o_comment varchar(79) not null, "
              "primary key (o_orderkey))",
    "lineitem": "create table lineitem (l_orderkey int64 not null, "
                "l_partkey int32 not null, l_suppkey int32 not null, "
                "l_linenumber int8 not null, "
                "l_quantity decimal(9,2) not null, "
                "l_extendedprice decimal(12,2) not null, "
                "l_discount decimal(9,2) not null, l_tax decimal(9,2) not null, "
                "l_returnflag char(1) not null, l_linestatus char(1) not null, "
                "l_shipdate date not null, l_commitdate date not null, "
                "l_receiptdate date not null, l_shipinstruct char(25) not null, "
                "l_shipmode char(10) not null, l_comment varchar(44) not null, "
                "primary key (l_orderkey, l_linenumber))",
}
LOAD_ORDER = ("region", "nation", "supplier", "customer", "part", "partsupp",
              "orders", "lineitem")
# columns (scaled integers) whose DDL type is DECIMAL(p,2): the loader takes
# integer arrays as already scaled
DECIMAL_SCALE = 100


# ------------------------------------------------------------------ data

def _coded(values, idx):
    """(codes, vocab) for rows drawing strings by index into `values`."""
    return np.asarray(idx, dtype=np.int32), np.asarray(values)


def text_pool(size: int = TEXT_POOL) -> np.ndarray:
    """`size` bytes of the grammar's pseudo text (cl. 4.2.2.10), the same in
    every run: dbgen too cuts its comments out of one fixed pool."""
    rnd = random.Random(0x7E87)
    pick = lambda xs: xs[int(rnd.random() * len(xs))]  # noqa: E731

    def expand(form: str) -> str:
        out = []
        for w in form.split(" "):
            word, comma = w.rstrip(","), w[len(w.rstrip(",")):]
            if word in PHRASES:
                out.append(expand(pick(PHRASES[word])))
            elif word in GRAMMAR:
                out.append(pick(GRAMMAR[word]) + comma)
            else:
                out.append(word)
        return " ".join(out)

    parts, n = [], 0
    while n < size:
        s = expand(pick(SENTENCES)) + pick(GRAMMAR["T"]) + " "
        parts.append(s)
        n += len(s)
    return np.frombuffer("".join(parts)[:size].encode(), dtype=np.uint8)


def _cut(mat: np.ndarray, lens: np.ndarray) -> np.ndarray:
    """Rows of a byte matrix as bytes strings of the given lengths."""
    mat[np.arange(mat.shape[1])[None, :] >= lens[:, None]] = 0
    return np.ascontiguousarray(mat).view(f"S{mat.shape[1]}").ravel()


def _text(rng, pool, n: int, lo: int, hi: int, block: int = 1 << 20):
    """n comments: substrings of the pool, lengths uniform in [lo, hi]."""
    windows = np.lib.stride_tricks.sliding_window_view(pool, hi)
    out = np.empty(n, dtype=f"S{hi}")
    for a in range(0, n, block):
        m = min(block, n - a)
        mat = windows[rng.integers(0, len(windows), m)]
        out[a:a + m] = _cut(mat, rng.integers(lo, hi + 1, m))
    return out


def _vstring(rng, n: int, lo: int, hi: int) -> np.ndarray:
    """n v-strings (cl. 4.2.2.7): random characters, lengths in [lo, hi]."""
    mat = ALNUM[rng.integers(0, len(ALNUM), (n, hi))]
    return _cut(mat, rng.integers(lo, hi + 1, n))


def _digits(x: np.ndarray, width: int) -> np.ndarray:
    """(n, width) ASCII digits of x, zero-filled."""
    p = 10 ** np.arange(width - 1, -1, -1, dtype=np.int64)
    return ((x.astype(np.int64)[:, None] // p) % 10 + ord("0")).astype(np.uint8)


def _joined(*parts) -> np.ndarray:
    """Byte matrices and literal strings side by side, as bytes strings."""
    n = next(len(p) for p in parts if not isinstance(p, str))
    cols = [np.tile(np.frombuffer(p.encode(), dtype=np.uint8), (n, 1))
            if isinstance(p, str) else p for p in parts]
    mat = np.ascontiguousarray(np.concatenate(cols, axis=1))
    return mat.view(f"S{mat.shape[1]}").ravel()


def _names(prefix: str, keys) -> np.ndarray:
    return _joined(prefix, _digits(keys, 9))


def _phones(rng, nationkey) -> np.ndarray:
    """cl. 4.2.2.9: country code = nation + 10, three random local parts."""
    n = len(nationkey)
    return _joined(_digits(nationkey + 10, 2), "-",
                   _digits(rng.integers(100, 1000, n), 3), "-",
                   _digits(rng.integers(100, 1000, n), 3), "-",
                   _digits(rng.integers(1000, 10000, n), 4))


def _money_cents(rng, n, lo, hi):
    return rng.integers(int(lo * 100), int(hi * 100) + 1, n)


def generate(config: dict, seed: int) -> dict:
    """All eight tables at config['scale_factor'], from the seed."""
    sf = float(config["scale_factor"])
    rng = np.random.default_rng(seed)
    pool = text_pool()
    t = {}
    t["region"] = {"r_regionkey": np.arange(5),
                   "r_name": _coded(REGIONS, np.arange(5)),
                   "r_comment": _text(rng, pool, 5, 31, 115)}
    t["nation"] = {"n_nationkey": np.arange(25),
                   "n_name": _coded([n for n, _ in NATIONS], np.arange(25)),
                   "n_regionkey": np.array([r for _, r in NATIONS]),
                   "n_comment": _text(rng, pool, 25, 31, 114)}
    n_supp = max(1, int(BASE_ROWS["supplier"] * sf))
    sk = np.arange(1, n_supp + 1)
    s_nation = rng.integers(0, 25, n_supp)
    s_comment = _text(rng, pool, n_supp, 25, 100)
    # cl. 4.2.3: 5 rows per 10,000 complain and 5 recommend
    marked = rng.permutation(n_supp)[:2 * max(1, n_supp // 2000)]
    for rows, word in ((marked[::2], b"Customer Complaints"),
                       (marked[1::2], b"Customer Recommends")):
        s_comment[rows] = [word + bytes(s)[len(word):] for s in s_comment[rows]]
    t["supplier"] = {
        "s_suppkey": sk, "s_name": _names("Supplier#", sk),
        "s_address": _vstring(rng, n_supp, 10, 40),
        "s_nationkey": s_nation, "s_phone": _phones(rng, s_nation),
        "s_acctbal": _money_cents(rng, n_supp, -999.99, 9999.99),
        "s_comment": s_comment}
    n_cust = max(1, int(BASE_ROWS["customer"] * sf))
    ck = np.arange(1, n_cust + 1)
    c_nation = rng.integers(0, 25, n_cust)
    t["customer"] = {
        "c_custkey": ck, "c_name": _names("Customer#", ck),
        "c_address": _vstring(rng, n_cust, 10, 40),
        "c_nationkey": c_nation, "c_phone": _phones(rng, c_nation),
        "c_acctbal": _money_cents(rng, n_cust, -999.99, 9999.99),
        "c_mktsegment": _coded(SEGMENTS, rng.integers(0, 5, n_cust)),
        "c_comment": _text(rng, pool, n_cust, 29, 116)}
    n_part = max(1, int(BASE_ROWS["part"] * sf))
    pk = np.arange(1, n_part + 1)
    # five different colour words per name (cl. 4.2.3 P_NAME)
    w = np.argsort(rng.random((n_part, len(P_NAME_WORDS))), axis=1)[:, :5]
    vocab = np.array(P_NAME_WORDS)
    names = vocab[w[:, 0]]
    for j in range(1, 5):
        names = np.char.add(np.char.add(names, " "), vocab[w[:, j]])
    mfgr = rng.integers(1, 6, n_part)
    brand = rng.integers(1, 6, n_part)
    types = [f"{a} {b} {c}" for a in TYPE_S1 for b in TYPE_S2 for c in TYPE_S3]
    type_idx = (rng.integers(0, 6, n_part) * 25 + rng.integers(0, 5, n_part) * 5
                + rng.integers(0, 5, n_part))
    conts = [f"{a} {b}" for a in CONTAINERS_1 for b in CONTAINERS_2]
    t["part"] = {
        "p_partkey": pk, "p_name": names,
        "p_mfgr": _coded([f"Manufacturer#{m}" for m in range(1, 6)], mfgr - 1),
        "p_brand": _coded([f"Brand#{m}{n}" for m in range(1, 6)
                           for n in range(1, 6)], (mfgr - 1) * 5 + brand - 1),
        "p_type": _coded(types, type_idx),
        "p_size": rng.integers(1, 51, n_part),
        "p_container": _coded(conts, rng.integers(0, 5, n_part) * 8
                              + rng.integers(0, 8, n_part)),
        "p_retailprice": 90000 + (pk // 10) % 20001 + 100 * (pk % 1000),
        "p_comment": _text(rng, pool, n_part, 5, 22)}
    ps_pk = np.repeat(pk, 4)
    j = np.tile(np.arange(4), n_part)
    t["partsupp"] = {
        "ps_partkey": ps_pk,
        "ps_suppkey": ((ps_pk + (j * (n_supp // 4 + (ps_pk - 1) // n_supp)))
                       % n_supp) + 1,
        "ps_availqty": rng.integers(1, 10000, len(ps_pk)),
        "ps_supplycost": _money_cents(rng, len(ps_pk), 1.00, 1000.00),
        "ps_comment": _text(rng, pool, len(ps_pk), 49, 198)}

    n_ord = max(1, int(BASE_ROWS["orders"] * sf))
    okey = np.arange(1, n_ord + 1, dtype=np.int64) * 4  # sparse, as the spec's
    ock = rng.integers(1, max(n_cust, 2), n_ord).astype(np.int64)
    # a third of the customers have no orders (cl. 4.2.3)
    ock = np.where(ock % 3 == 0, np.maximum((ock + 1) % (n_cust + 1), 1), ock)
    odate = rng.integers(START, END - 151, n_ord)
    # 1..7 lineitems per order (cl. 4.2.3), as a shuffled fixed multiset: every
    # seed gives the same row count, so every seed runs the same device shapes
    per = rng.permutation(np.resize(np.arange(1, 8), n_ord))
    nl = int(per.sum())
    li_order = np.repeat(np.arange(n_ord), per)
    starts = np.cumsum(per) - per
    l_partkey = rng.integers(1, n_part + 1, nl)
    qty = rng.integers(1, 51, nl)
    ext = qty * (90000 + (l_partkey // 10) % 20001 + 100 * (l_partkey % 1000))
    disc = rng.integers(0, 11, nl)
    tax = rng.integers(0, 9, nl)
    o_li = odate[li_order]
    ship = o_li + rng.integers(1, 122, nl)
    commit = o_li + rng.integers(30, 91, nl)
    receipt = ship + rng.integers(1, 31, nl)
    returned = receipt <= CURRENT
    rf = np.where(returned, np.where(rng.random(nl) < 0.5, 2, 0), 1)
    is_open = ship > CURRENT
    t["lineitem"] = {
        "l_orderkey": okey[li_order], "l_partkey": l_partkey,
        "l_suppkey": rng.integers(1, n_supp + 1, nl),
        "l_linenumber": np.arange(nl) - np.repeat(starts, per) + 1,
        "l_quantity": qty * DECIMAL_SCALE, "l_extendedprice": ext,
        "l_discount": disc, "l_tax": tax,
        "l_returnflag": _coded(["A", "N", "R"], rf),
        "l_linestatus": _coded(["F", "O"], is_open),
        "l_shipdate": ship, "l_commitdate": commit, "l_receiptdate": receipt,
        "l_shipinstruct": _coded(INSTRUCTS, rng.integers(0, 4, nl)),
        "l_shipmode": _coded(SHIPMODES, rng.integers(0, 7, nl)),
        "l_comment": _text(rng, pool, nl, 10, 43)}
    charge = ext * (100 - disc) * (100 + tax)  # 1e-6 dollars
    total = np.add.reduceat(charge, starts)
    n_open = np.add.reduceat(is_open.astype(np.int64), starts)
    status = np.where(n_open == 0, 0, np.where(n_open == per, 1, 2))
    n_clerks = max(1, int(1000 * sf))
    t["orders"] = {
        "o_orderkey": okey, "o_custkey": ock,
        "o_orderstatus": _coded(["F", "O", "P"], status),
        "o_totalprice": (total + 5000) // 10000, "o_orderdate": odate,
        "o_orderpriority": _coded(PRIORITIES, rng.integers(0, 5, n_ord)),
        "o_clerk": _coded([f"Clerk#{k:09d}" for k in range(1, n_clerks + 1)],
                          rng.integers(0, n_clerks, n_ord)),
        "o_shippriority": np.zeros(n_ord, dtype=np.int64),
        "o_comment": _text(rng, pool, n_ord, 19, 78)}
    return t


def ddl(config: dict) -> list[tuple[str, list[str]]]:
    return [(name, [DDL[name]]) for name in LOAD_ORDER]


def as_strings(col):
    """A column as the loader takes it: `(codes, vocab)` expanded, bytes as
    text."""
    if isinstance(col, tuple):
        codes, vocab = col
        return vocab[codes]
    if col.dtype.kind == "S":
        return col.astype(f"U{col.dtype.itemsize}")
    return col


def row_counts(data: dict) -> dict:
    def rows(col):
        return len(col[0] if isinstance(col, tuple) else col)

    return {name: rows(next(iter(cols.values())))
            for name, cols in data.items()}


# Columns the references read; the rest of a table is dropped once loaded.
_REFERENCE_COLUMNS = {
    "lineitem": ("l_orderkey", "l_partkey", "l_quantity", "l_extendedprice",
                 "l_discount", "l_tax", "l_returnflag", "l_linestatus",
                 "l_shipdate"),
    "orders": ("o_orderkey", "o_custkey", "o_orderdate", "o_shippriority"),
    "customer": ("c_custkey", "c_mktsegment"),
    "part": ("p_partkey", "p_type"),
}


def reference_columns(config: dict) -> dict:
    return _REFERENCE_COLUMNS


# ------------------------------------------------------------ statements

Q1 = """
select
    l_returnflag, l_linestatus,
    sum(l_quantity) as sum_qty,
    sum(l_extendedprice) as sum_base_price,
    sum(l_extendedprice * (1 - l_discount)) as sum_disc_price,
    sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) as sum_charge,
    avg(l_quantity) as avg_qty,
    avg(l_extendedprice) as avg_price,
    avg(l_discount) as avg_disc,
    count(*) as count_order
from lineitem
where l_shipdate <= date '1998-12-01' - interval '{delta}' day
group by l_returnflag, l_linestatus
order by l_returnflag, l_linestatus
"""
Q3 = """
select
    l_orderkey,
    sum(l_extendedprice * (1 - l_discount)) as revenue,
    o_orderdate, o_shippriority
from customer, orders, lineitem
where c_mktsegment = '{segment}'
  and c_custkey = o_custkey
  and l_orderkey = o_orderkey
  and o_orderdate < date '{date}'
  and l_shipdate > date '{date}'
group by l_orderkey, o_orderdate, o_shippriority
order by revenue desc, o_orderdate
limit 10
"""
Q6 = """
select sum(l_extendedprice * l_discount) as revenue
from lineitem
where l_shipdate >= date '{date}'
  and l_shipdate < date '{date_end}'
  and l_discount between {disc_lo} and {disc_hi}
  and l_quantity < {quantity}
"""
Q14 = """
select
    100.00 * sum(case when p_type like 'PROMO%'
        then l_extendedprice * (1 - l_discount) else 0 end)
    / sum(l_extendedprice * (1 - l_discount)) as promo_revenue
from lineitem, part
where l_partkey = p_partkey
  and l_shipdate >= date '{date}'
  and l_shipdate < date '{date_end}'
"""
TEXT = {"q1": Q1, "q3": Q3, "q6": Q6, "q14": Q14}


def _next_month(y, m):
    return (y + 1, 1) if m == 12 else (y, m + 1)


def draw_literals(kind: str, rng, config: dict) -> dict:
    """One set of substitution parameters (TPC-H cl. 2.4.x.3). Q3's SEGMENT
    comes from the configuration's `q3_segment_domain` where it states one."""
    if kind == "q1":
        return {"delta": int(rng.integers(60, 121))}
    if kind == "q3":
        segments = config.get("q3_segment_domain", SEGMENTS)
        return {"segment": segments[int(rng.integers(0, len(segments)))],
                "date": f"1995-03-{int(rng.integers(1, 32)):02d}"}
    if kind == "q6":
        y = int(rng.integers(1993, 1998))
        d = int(rng.integers(2, 10))
        return {"date": f"{y}-01-01", "date_end": f"{y + 1}-01-01",
                "disc_lo": f"0.{d - 1:02d}", "disc_hi": f"0.{d + 1:02d}",
                "quantity": int(rng.integers(24, 26))}
    if kind == "q14":
        y, m = int(rng.integers(1993, 1998)), int(rng.integers(1, 13))
        y2, m2 = _next_month(y, m)
        return {"date": f"{y}-{m:02d}-01", "date_end": f"{y2}-{m2:02d}-01"}
    raise KeyError(kind)


#: the validation literals of cl. 2.4.x.4, for the self-check against the
#: program's fixed-parameter references
VALIDATION = {
    "q1": {"delta": 90},
    "q3": {"segment": "BUILDING", "date": "1995-03-15"},
    "q6": {"date": "1994-01-01", "date_end": "1995-01-01",
           "disc_lo": "0.05", "disc_hi": "0.07", "quantity": 24},
    "q14": {"date": "1995-09-01", "date_end": "1995-10-01"},
}


def render(kind: str, lit: dict) -> str:
    return TEXT[kind].format(**lit)


def pools(traffic: dict, config: dict, seed: int) -> dict:
    """kind -> list of distinct literal sets, reproducible from the seed."""
    rng = np.random.default_rng([seed, 0x7C9])
    out = {}
    for kind in traffic["kinds"]:
        pool, seen = [], set()
        while len(pool) < int(traffic["pool"]):
            lit = draw_literals(kind, rng, config)
            key = tuple(sorted(lit.items()))
            if key not in seen:
                seen.add(key)
                pool.append(lit)
        out[kind] = pool
    return out


class Stream:
    """One closed-loop client's statements: the kinds in turn, each kind's
    pool walked in an order drawn from the seed (every seed sends the same
    number of each kind; only the order and the literals differ)."""

    def __init__(self, traffic, config, seed, client, pools_):
        self.kinds = list(traffic["kinds"])
        self.pools = pools_
        self.rng = np.random.default_rng([seed, 0x51, client])
        self.i = 0
        self.order = {k: [] for k in self.kinds}

    def next(self, only: str | None = None):
        kind = only or self.kinds[self.i % len(self.kinds)]
        self.i += 1
        if not self.order[kind]:
            self.order[kind] = list(self.rng.permutation(len(self.pools[kind])))
        lit = self.pools[kind][int(self.order[kind].pop())]
        return kind, lit, render(kind, lit)


def warmup(traffic, config, pools_) -> list:
    """(kind, literals, text) of every pool member: each is executed in
    set-up, so a literal baked into a program compiles there."""
    return [(k, lit, render(k, lit)) for k in traffic["kinds"]
            for lit in pools_[k]]


# ------------------------------------------------------------ references

def _dec(n: int, places: int) -> Decimal:
    return Decimal(int(n)).scaleb(-places)


def reference(kind: str, lit: dict, data: dict, acc=np.int64):
    """Rows the statement must return, as tuples of Decimal / int / str.

    `acc` is the accumulator type: int64 is exact; the control of
    tests/test_correct.py passes float32 (the precision below)."""
    li = data["lineitem"]
    ship = li["l_shipdate"]
    ext, disc = li["l_extendedprice"], li["l_discount"]

    def total(x):
        if acc is np.int64:
            return int(x.sum())
        return float(x.astype(acc).sum(dtype=acc))

    def out(v, places):
        if acc is np.int64:
            return _dec(v, places)
        return Decimal(repr(float(v))) * Decimal(10) ** -places

    if kind == "q6":
        m = ((ship >= _day(lit["date"])) & (ship < _day(lit["date_end"]))
             & (disc >= int(round(float(lit["disc_lo"]) * 100)))
             & (disc <= int(round(float(lit["disc_hi"]) * 100)))
             & (li["l_quantity"] < int(lit["quantity"]) * DECIMAL_SCALE))
        return [(out(total(ext[m] * disc[m]), 4),)]
    if kind == "q1":
        m = ship <= END - int(lit["delta"])
        rf, ls = li["l_returnflag"], li["l_linestatus"]
        key = rf[0][m] * len(ls[1]) + ls[0][m]
        qty, e, d, t = (li["l_quantity"][m], ext[m], disc[m], li["l_tax"][m])
        rows = []
        for g in np.unique(key):
            s = key == g
            n = int(s.sum())
            dp = e[s] * (100 - d[s])
            sq, se, sd = total(qty[s]), total(e[s]), total(d[s])
            rows.append((
                str(rf[1][g // len(ls[1])]), str(ls[1][g % len(ls[1])]),
                out(sq, 2), out(se, 2), out(total(dp), 4),
                out(total(dp * (100 + t[s])), 6),
                out(sq, 2) / n, out(se, 2) / n, out(sd, 2) / n, n))
        return rows
    if kind == "q14":
        pa = data["part"]
        m = (ship >= _day(lit["date"])) & (ship < _day(lit["date_end"]))
        promo_type = np.char.startswith(pa["p_type"][1], "PROMO")
        is_promo = np.zeros(int(pa["p_partkey"].max()) + 1, dtype=bool)
        is_promo[pa["p_partkey"]] = promo_type[pa["p_type"][0]]
        rev = ext[m] * (100 - disc[m])
        num = total(rev[is_promo[li["l_partkey"][m]]])
        den = total(rev)
        return [((Decimal(100) * Decimal(num)) / Decimal(den),)]
    if kind == "q3":
        cu, od = data["customer"], data["orders"]
        day = _day(lit["date"])
        seg = list(cu["c_mktsegment"][1]).index(lit["segment"])
        in_seg = np.zeros(int(cu["c_custkey"].max()) + 1, dtype=bool)
        in_seg[cu["c_custkey"]] = cu["c_mktsegment"][0] == seg
        o_ok = in_seg[od["o_custkey"]] & (od["o_orderdate"] < day)
        # o_orderkey = 4 * (position + 1): the generator's own rule
        pos = li["l_orderkey"] // 4 - 1
        lm = (ship > day) & o_ok[pos]
        pos_m = pos[lm]
        rev_row = ext[lm] * (100 - disc[lm])
        if acc is np.int64:
            rev = np.bincount(pos_m, weights=rev_row.astype(np.float64),
                              minlength=len(o_ok)).astype(np.int64)
        else:
            rev = np.zeros(len(o_ok), dtype=acc)
            np.add.at(rev, pos_m, rev_row.astype(acc))
        hit = np.flatnonzero(np.bincount(pos_m, minlength=len(o_ok)))
        order = np.lexsort((od["o_orderdate"][hit], -rev[hit].astype(np.float64)))
        top = hit[order[:10]]
        # the wire carries a DATE as its day number
        return [(int(od["o_orderkey"][i]), out(rev[i], 4),
                 int(od["o_orderdate"][i]), int(od["o_shippriority"][i]))
                for i in top]
    raise KeyError(kind)


# columns each kind reads, for the necessary-bytes function of the harness
REFERENCED_COLUMNS = {
    "q1": {"lineitem": ["l_returnflag", "l_linestatus", "l_quantity",
                        "l_extendedprice", "l_discount", "l_tax",
                        "l_shipdate"]},
    "q6": {"lineitem": ["l_shipdate", "l_discount", "l_quantity",
                        "l_extendedprice"]},
    "q3": {"customer": ["c_custkey", "c_mktsegment"],
           "orders": ["o_orderkey", "o_custkey", "o_orderdate",
                      "o_shippriority"],
           "lineitem": ["l_orderkey", "l_extendedprice", "l_discount",
                        "l_shipdate"]},
    "q14": {"part": ["p_partkey", "p_type"],
            "lineitem": ["l_partkey", "l_extendedprice", "l_discount",
                         "l_shipdate"]},
}
