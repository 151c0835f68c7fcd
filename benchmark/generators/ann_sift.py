"""ANN-benchmarks' `sift-128-euclidean` (SIFT1M) for the benchmark: table,
statements and plain reference (numpy and the standard library only).

The table is `sift1m(id BIGINT PRIMARY KEY, cat INT NOT NULL, emb
VECTOR(128))` with an IVF-flat index on `emb`, created by DDL over the wire
and filled through `direct_load`. Rows and queries have SIFT's shape: 128
non-negative integer-valued components in 0..255 held as float32, row norms
near SIFT's 512. SIFT1M's own vectors are not in the repository (nor
fetched), so each row is a draw from a seeded mixture (`_mixture`): a
component's mean is a non-negative profile, and a row lies on a
low-dimensional patch around it (a few directions of large spread, as image
descriptors vary along a few factors) plus a little noise in every
direction, then clipped to 0..255 and rounded. The configuration's
`assumed.mixture` sets the number of components and their spreads. `cat` is
uniform over 0..99 and independent of `emb` (SIFT1M has no attribute: the
filters of the hybrid kinds read it). The query pool is drawn from the same
mixture with streams of its own; no query equals a base row.

Three kinds, k = 10, nearest by `vec_l2` (the squared L2 distance):

  knn        SELECT id, vec_l2(emb, '<q>') AS dist FROM sift1m
             ORDER BY dist LIMIT 10
  knn_sel10  the same WHERE cat < 10      (selectivity 0.1)
  knn_sel1   the same WHERE cat = <c>     (selectivity 0.01, c uniform)

The plain reference (`judge`, after the windows) is exact k = 10 by brute
force over the rows that pass the statement's filter, for the (query,
filter) pairs the run sent: a float32 shortlist of `SHORTLIST` rows a query
over blocks of rows, then every distance of it in float64; where the
shortlist's margin over the 10th is inside float32's rounding, that query is
redone in float64 over all rows. The true set is every id at most the 10th
distance x (1 + `TIE`) away, so that equal distances cannot flake.
"""

from __future__ import annotations

import numpy as np

TABLE = "sift1m"
KINDS = ("knn", "knn_sel10", "knn_sel1")
K = 10
TIE = 1e-6          # the true set: distance <= the 10th x (1 + TIE)
DIST_REL = 1e-4     # a returned distance against its id's float64 distance
SHORTLIST = 64      # rows a query keeps from the float32 pass
ROW_BLOCK = 1 << 17
QUERY_BLOCK = 512

_SELECT = "SELECT id, vec_l2(emb, '{vec}') AS dist FROM sift1m "
TEXT = {
    "knn": _SELECT + "ORDER BY dist LIMIT 10",
    "knn_sel10": _SELECT + "WHERE cat < 10 ORDER BY dist LIMIT 10",
    "knn_sel1": _SELECT + "WHERE cat = {c} ORDER BY dist LIMIT 10",
}


def _passes(kind: str, lit: dict, cat: np.ndarray) -> np.ndarray:
    """The rows a statement's filter keeps."""
    if kind == "knn":
        return np.ones(len(cat), dtype=bool)
    if kind == "knn_sel10":
        return cat < 10
    if kind == "knn_sel1":
        return cat == int(lit["c"])
    raise KeyError(kind)


# ------------------------------------------------------------------ data

def _mixture(config: dict, seed: int) -> dict:
    """The seeded mixture both the base rows and the queries are drawn
    from: one non-negative profile of norm `mean_norm` shared by every
    component, each component's mean `separation` away from it in a
    direction of its own, and `rank` orthonormal directions of spread
    `spread` around that mean (at most a quarter of the dimensions)."""
    m = config["assumed"]["mixture"]
    g, d = int(m["components"]), int(config["dim"])
    r = min(int(m["rank"]), d // 4)  # a rehearsal's few dimensions
    rng = np.random.default_rng([seed, 0xA5, 0])
    prof = rng.gamma(float(m["profile_shape"]), 1.0, d)
    away = rng.standard_normal((g, d))
    means = (prof * (float(m["mean_norm"]) / np.linalg.norm(prof))
             + away * (float(m["separation"])
                       / np.linalg.norm(away, axis=1, keepdims=True)))
    basis = np.linalg.qr(rng.standard_normal((g, d, r)))[0]  # (g, d, r)
    return {"means": means, "basis": basis * float(m["spread"]),
            "noise": float(m["noise"])}


def _draw(mix: dict, n: int, rng) -> np.ndarray:
    """n rows of the mixture: (n, d) float32, integers in 0..255."""
    g, d, r = mix["basis"].shape
    comp = rng.integers(0, g, n)
    x = rng.standard_normal((n, d), dtype=np.float32) * mix["noise"]
    z = rng.standard_normal((n, r), dtype=np.float32)
    order = np.argsort(comp, kind="stable")
    bounds = np.searchsorted(comp[order], np.arange(g + 1))
    for c in range(g):
        rows = order[bounds[c]:bounds[c + 1]]
        if len(rows):
            x[rows] += (mix["means"][c] + z[rows] @ mix["basis"][c].T
                        ).astype(np.float32)
    np.clip(np.rint(x, out=x), 0.0, 255.0, out=x)
    return x


def _row_hash(x: np.ndarray) -> np.ndarray:
    """A 64-bit hash of each integer-valued row (equal rows, equal hash)."""
    w = np.random.default_rng(0x51F7).integers(
        1, 2**62, x.shape[1], dtype=np.uint64)
    with np.errstate(over="ignore"):
        return (x.astype(np.uint64) * w).sum(axis=1, dtype=np.uint64)


def queries(config: dict, seed: int) -> np.ndarray:
    """The query pool, (queries, d) float32, from the seed alone."""
    rng = np.random.default_rng([seed, 0xA5, 2])
    return _draw(_mixture(config, seed), int(config["queries"]), rng)


def generate(config: dict, seed: int) -> dict:
    n = int(config["rows"])
    rng = np.random.default_rng([seed, 0xA5, 1])
    emb = _draw(_mixture(config, seed), n, rng)
    cat = rng.integers(0, 100, n).astype(np.int32)
    pool = queries(config, seed)
    same = np.isin(_row_hash(pool), _row_hash(emb))
    for i in np.flatnonzero(same):
        if (emb == pool[i]).all(axis=1).any():
            raise RuntimeError(f"seed {seed}: query {i} equals a base row")
    return {TABLE: {"id": np.arange(1, n + 1, dtype=np.int64), "cat": cat,
                    "emb": emb},
            "queries": pool}


def ddl(config: dict) -> list[tuple[str, list[str]]]:
    return [(TABLE, [
        f"CREATE TABLE {TABLE} (id BIGINT PRIMARY KEY, cat INT NOT NULL, "
        f"emb VECTOR({int(config['dim'])}))",
        f"CREATE VECTOR INDEX ix_emb ON {TABLE} (emb) WITH "
        f"(lists = {int(config['lists'])}, nprobe = {int(config['nprobe'])})",
    ])]


def as_strings(col):
    return col


def row_counts(data: dict) -> dict:
    return {TABLE: len(data[TABLE]["id"])}


def reference_columns(config: dict) -> dict:
    return {TABLE: ("cat", "emb")}


# ------------------------------------------------------------ statements

def vec_literal(v) -> str:
    return "[" + ",".join(str(int(a)) for a in v) + "]"


def render(kind: str, lit: dict, pool_text) -> str:
    return TEXT[kind].format(vec=pool_text[int(lit["q"])], c=lit.get("c"))


def pools(traffic: dict, config: dict, seed: int) -> dict:
    """The query pool's literals, rendered once."""
    return {"text": [vec_literal(q) for q in queries(config, seed)]}


def _draw_lit(kind: str, rng, nq: int) -> dict:
    lit = {"q": int(rng.integers(nq))}
    if kind == "knn_sel1":
        lit["c"] = int(rng.integers(100))
    return lit


class Stream:
    """One closed-loop connection: a kind by the traffic's `mix`, a query
    uniform over the pool, for `knn_sel1` a category uniform over 0..99."""

    def __init__(self, traffic, config, seed, client, pools_):
        self.kinds = list(traffic["kinds"])
        w = np.array([float(traffic["mix"][k]) for k in self.kinds])
        self.p = w / w.sum()
        self.text = pools_["text"]
        self.rng = np.random.default_rng([seed, 0xA5, 3, client])

    def next(self, only: str | None = None):
        kind = only or self.kinds[int(self.rng.choice(len(self.kinds),
                                                      p=self.p))]
        lit = _draw_lit(kind, self.rng, len(self.text))
        return kind, lit, render(kind, lit, self.text)


def warmup(traffic, config, pools_) -> list:
    """`warm_queries` distinct statements of every kind (distinct, so the
    result cache answers none of them), from a stream of their own."""
    rng = np.random.default_rng([0xA5, 4])
    text = pools_["text"]
    out = []
    for kind in traffic["kinds"]:
        for _ in range(int(traffic["warm_queries"])):
            lit = _draw_lit(kind, rng, len(text))
            out.append((kind, lit, render(kind, lit, text)))
    return out


# ------------------------------------------------------------- reference

def _dist32(qb: np.ndarray, xb: np.ndarray, xn: np.ndarray) -> np.ndarray:
    """(queries, rows) squared L2 in float32, in the expanded form."""
    d = qb @ xb.T
    d *= -2.0
    d += xn[None, :]
    d += np.einsum("ij,ij->i", qb, qb)[:, None]
    return d


def _shortlists(x: np.ndarray, qs: np.ndarray, keep: int) -> np.ndarray:
    """Row positions (nq, keep) of the `keep` rows nearest each query in
    float32, `keep` <= len(x). A query's threshold is its keep-th distance
    over the first block of rows; every block then gives up only the rows
    at or under it, and the nearest `keep` of those are kept."""
    xn = np.einsum("ij,ij->i", x, x)
    out = np.zeros((len(qs), keep), dtype=np.int64)
    for qlo in range(0, len(qs), QUERY_BLOCK):
        qb = qs[qlo:qlo + QUERY_BLOCK]
        hq, hr, hd = [], [], []
        tau = None
        for lo in range(0, len(x), ROW_BLOCK):
            d = _dist32(qb, x[lo:lo + ROW_BLOCK], xn[lo:lo + ROW_BLOCK])
            if tau is None:
                tau = np.partition(d, keep - 1, axis=1)[:, keep - 1:keep]
            q, r = np.nonzero(d <= tau)
            hq.append(q)
            hr.append(lo + r)
            hd.append(d[q, r])
        q, r, d = (np.concatenate(a) for a in (hq, hr, hd))
        order = np.lexsort((r, d, q))
        q, r = q[order], r[order]
        start = np.searchsorted(q, np.arange(len(qb)))
        rank = np.arange(len(q)) - start[q]
        first = rank < keep
        out[qlo + q[first], rank[first]] = r[first]
    return out


def exact_neighbours(x: np.ndarray, qs: np.ndarray) -> list:
    """Per query the row positions of its true set: every row at most the
    10th distance x (1 + TIE) away, in float64."""
    keep = min(SHORTLIST, len(x))
    cand = _shortlists(x, qs, keep)
    # float32's rounding of the expanded form, over-estimated
    err = 1e-5 * (2.0 * float(np.einsum("ij,ij->i", x, x).max())
                  + float(np.einsum("ij,ij->i", qs, qs).max()) + 1.0)
    out = []
    for q, rows in zip(qs, cand):
        q64 = q.astype(np.float64)
        d64 = ((x[rows].astype(np.float64) - q64) ** 2).sum(axis=1)
        kth = np.sort(d64)[min(K, len(d64)) - 1]
        # a row outside the shortlist is at least its last member's float32
        # distance away: where that could lie inside the true set's
        # threshold, the query is redone in float64 over every row
        if keep < len(x) and d64.max() <= kth * (1 + TIE) + 2 * err:
            d64 = ((x.astype(np.float64) - q64) ** 2).sum(axis=1)
            rows = np.arange(len(x))
            kth = np.sort(d64)[K - 1]
        out.append(rows[d64 <= kth * (1 + TIE)])
    return out


def _parse(rows):
    """[(id, dist)] of an answer, or None where its shape is wrong."""
    try:
        return [(int(i), float(d)) for i, d in rows]
    except (TypeError, ValueError):
        return None


def judge(records, data: dict, config: dict, traffic: dict) -> dict:
    """`check.judge`'s dictionary for every kNN statement of the run (the
    warm-up, the windows and the traced sub-windows): `missing_answers`,
    `wrong_answers` and `recall_at_10` (the mean over the statements, per
    kind too), each with its limit."""
    cols = data[TABLE]
    x, cat = cols["emb"], cols["cat"]
    pool = data["queries"]
    n = len(x)
    numbers = {"missing_answers": 0, "wrong_answers": 0}
    first_bad = None
    hits = {k: [0, 0] for k in KINDS}
    # the (filter, query) pairs the run sent, their true sets once each
    wanted: dict = {}
    for kind, lit, *_ in records:
        if kind in KINDS:
            fkey = (kind, lit.get("c"))
            wanted.setdefault(fkey, set()).add(int(lit["q"]))
    truth: dict = {}
    for (kind, c), qset in wanted.items():
        mask = _passes(kind, {"c": c}, cat)
        rows = np.flatnonzero(mask)
        qidx = sorted(qset)
        found = exact_neighbours(x[rows], pool[qidx]) if len(rows) else \
            [np.zeros(0, np.int64)] * len(qidx)
        for qi, pos in zip(qidx, found):
            truth[(kind, c, qi)] = rows[pos]
    for rec in records:
        kind, lit, rows = rec[0], rec[1], rec[4]
        if kind not in KINDS:
            continue
        why = None
        if isinstance(rows, str):
            numbers["missing_answers"] += 1
            first_bad = first_bad or f"{kind} {lit}: {rows[:200]}"
            continue
        got = _parse(rows)
        mask = _passes(kind, lit, cat)
        qualify = int(mask.sum())
        true_rows = truth[(kind, lit.get("c"), int(lit["q"]))]
        if got is None:
            why = "not (id, distance) rows"
        else:
            ids = [i for i, _ in got]
            pos = np.array(ids, dtype=np.int64) - 1
            q64 = pool[int(lit["q"])].astype(np.float64)
            if len(set(ids)) != len(ids):
                why = "a duplicate id"
            elif len(pos) and (pos.min() < 0 or pos.max() >= n):
                why = "an unknown id"
            elif len(got) != min(K, qualify):
                why = f"{len(got)} rows where {min(K, qualify)} qualify"
            elif len(pos) and not mask[pos].all():
                why = "a row that fails the filter"
            elif any(b[1] < a[1] for a, b in zip(got, got[1:])):
                why = "rows not in ascending distance"
            else:
                ref = ((x[pos].astype(np.float64) - q64) ** 2).sum(axis=1)
                gap = np.abs(np.array([d for _, d in got]) - ref)
                if (gap > DIST_REL * np.maximum(ref, 1.0)).any():
                    j = int(np.argmax(gap / np.maximum(ref, 1.0)))
                    why = (f"id {ids[j]} distance {got[j][1]!r}, "
                           f"float64 {ref[j]!r}")
            if why is None and qualify:
                due = min(K, qualify)
                found = len(np.intersect1d(pos, true_rows))
                hits[kind][0] += min(found, due)
                hits[kind][1] += due
        if why is not None:
            numbers["wrong_answers"] += 1
            first_bad = first_bad or f"{kind} {lit}: {why}: {str(rows)[:300]}"
    per_kind = {k: h / d for k, (h, d) in hits.items() if d}
    total = sum(d for _, d in hits.values())
    recall = sum(h for h, _ in hits.values()) / total if total else 0.0
    limit = float(config["correct"]["recall_at_10"])
    compared = {
        "missing_answers": {"value": numbers["missing_answers"],
                            "limit": 0},
        "wrong_answers": {"value": numbers["wrong_answers"], "limit": 0},
        "recall_at_10": {"value": recall, "limit": limit,
                         "better": "higher", "by_kind": per_kind},
    }
    correct = (total > 0 and numbers["missing_answers"] == 0
               and numbers["wrong_answers"] == 0 and recall >= limit)
    return {"correct": correct, "compared": compared,
            "first_bad": first_bad}


REFERENCED_COLUMNS = {k: {} for k in KINDS}
