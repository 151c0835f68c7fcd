#!/usr/bin/env python
"""Headline benchmark: TPC-H on the TPU engine vs a CPU vectorized baseline.

Per BASELINE.json the metric is TPC-H rows/sec/chip with the CPU vectorized
engine as the measured baseline. Queries run through the real SQL engine
(parse -> plan -> stats-seeded capacities -> jitted XLA program, plan-cache
warm), not hand-built kernels.

Budget discipline (round 3 lost both join numbers to the budget):
- joins run BEFORE Q1 (its 65s 1-core CPU baseline ate the r3 budget);
- generated tables cache to .bench_cache/*.npz and load via mmap (the r3
  run spent 52.7s just reading the cache eagerly);
- CPU baseline times AND values cache to .bench_cache/cpu_base.json —
  datagen is deterministic (seeded), so a baseline measured once on this
  machine stays valid and repeat runs spend zero seconds on numpy;
- a CUMULATIVE summary line prints after every step: at any kill point the
  last stdout line is a complete, parseable record of everything measured.

Engine features exercised (and reported in detail):
- sorted projection on lineitem(l_shipdate) — the TPC-H-legal date-column
  index (spec 1.5.4); Q6/Q14 scans become contiguous device slices;
- clustered-FK segment aggregation: Q3's join+group-by ride cumsums over
  lineitem's l_orderkey clustering plus host-precomputed FK ranges;
- out-of-core streaming: an SF>=30 section runs Q6/Q1 through the chunked
  executor with a reduced device budget (streamed: true in detail).

Every line honors the one-line summary contract:
  {"metric": ..., "value": ..., "unit": ..., "vs_baseline": ..., "detail": {...}}

Env knobs: BENCH_SF (default 10), BENCH_REPS (default 5), BENCH_BUDGET_S
(default 420; enforced INSIDE rep loops — a long step stops repping near
the budget instead of running into the driver's hard kill), BENCH_STREAM_SF
(default 30; 0 disables the streamed section), BENCH_STREAM=1 to add the
pipeline A/B legs (prefetch on/off x compressed/raw wire on the same warm
streamed plans, emitting stream_prefetch_speedup), OB_TPU_DEVICE_BUDGET for
the non-streamed device budget. Exit code is always 0 with a parseable final
summary line, even on a crash.
"""

import json
import os
import subprocess
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
CACHE = os.path.join(REPO, ".bench_cache")
# cheap-first so a slow run still lands every headline query:
# q6/q14 slice-scans, q1 (46ms device + CACHED 65s cpu baseline), q3 last
# (the join that ate the r4 budget) — and results PERSIST across runs, so
# nothing measured is ever lost to a kill (r4 verdict weak #1)
ORDER = ["q6", "q14", "q1", "q3"]
QID = {"q1": 1, "q6": 6, "q3": 3, "q14": 14}
START = time.monotonic()


def _git_rev() -> str:
    """HEAD short rev + a working-tree diff hash: uncommitted engine
    changes must invalidate persisted measurements too."""
    try:
        rev = subprocess.run(
            ["git", "-C", REPO, "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
        ).stdout.strip() or "unknown"
        diff = subprocess.run(
            ["git", "-C", REPO, "diff", "HEAD", "--", "oceanbase_tpu",
             "bench.py"],
            capture_output=True, text=True, timeout=20,
        ).stdout
        if diff:
            import hashlib

            rev += "-dirty" + hashlib.md5(diff.encode()).hexdigest()[:8]
        return rev
    except Exception:
        return "unknown"


REV = _git_rev()
_RESULTS_PATH = os.path.join(CACHE, "results_v5.json")


def _results() -> dict:
    try:
        with open(_RESULTS_PATH) as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


def _results_put(key: str, rec: dict) -> None:
    r = _results()
    rec["rev"] = REV
    r[key] = rec
    try:
        os.makedirs(CACHE, exist_ok=True)
        tmp = _RESULTS_PATH + f".tmp{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(r, f)
        os.replace(tmp, _RESULTS_PATH)
    except OSError:
        pass


def _results_get(key: str) -> dict | None:
    rec = _results().get(key)
    if rec is not None and rec.get("rev") == REV:
        return rec
    return None

# lineitem columns covered by the l_shipdate sorted projection (every
# column the four headline queries touch)
SP_COLS = [
    "l_shipdate", "l_quantity", "l_extendedprice", "l_discount", "l_tax",
    "l_returnflag", "l_linestatus", "l_partkey", "l_orderkey",
]


# BENCH_OUT=<path>: also write each emitted summary as a JSON line to a
# stable artifact path (truncated on the first emit of a run) so CI can
# collect results without scraping stdout.
_BENCH_OUT = os.environ.get("BENCH_OUT")
_bench_out_started = False
_META = None


def _meta() -> dict:
    """Provenance stamp (tools/bench_meta.py): rev + config fingerprint
    + active overrides. Lazy — collect() touches the engine package, and
    nothing heavy may import before the env knobs are read."""
    global _META
    if _META is None:
        try:
            import sys

            tools = os.path.join(REPO, "tools")
            if tools not in sys.path:
                sys.path.insert(0, tools)
            from bench_meta import collect

            _META = collect()
        except Exception:
            _META = {"git_rev": REV}
    return _META


def emit(obj):
    obj.setdefault("meta", _meta())
    print(json.dumps(obj), flush=True)
    global _bench_out_started
    if _BENCH_OUT:
        with open(_BENCH_OUT, "a" if _bench_out_started else "w") as f:
            f.write(json.dumps(obj) + "\n")
        _bench_out_started = True


def elapsed():
    return time.monotonic() - START


# the budget is enforced INSIDE rep loops, not just between steps: round 5
# died to rc=124 because a single _best() over a 65s CPU baseline ran all
# its reps past BENCH_BUDGET_S and the driver's hard timeout hit first.
# BUDGET is set once in main() from the env knob.
BUDGET: float | None = None


def over_budget(margin: float = 0.0) -> bool:
    return BUDGET is not None and elapsed() > BUDGET - margin


# ---------------------------------------------------------------------------
# Cached TPC-H tables (mmap: only touched columns hit the disk)
# ---------------------------------------------------------------------------

def cache_path(sf: float) -> str:
    """Directory of raw .npy files — np.load(mmap_mode='r') only works on
    standalone .npy (inside an npz zip numpy silently reads eagerly: the
    r3 bench spent 52.7s 'loading the cache')."""
    return os.path.join(CACHE, f"tpch_sf{sf:g}.d")


def _legacy_npz(sf: float) -> str:
    return os.path.join(CACHE, f"tpch_sf{sf:g}.npz")


def _write_npy_dir(path: str, arrs: dict) -> None:
    tmp = path + f".tmp{os.getpid()}"
    os.makedirs(tmp, exist_ok=True)
    for k, a in arrs.items():
        np.save(os.path.join(tmp, k + ".npy"), np.asarray(a))
    os.replace(tmp, path)


def load_or_generate(sf: float):
    """Tables from the on-disk cache (true mmap: columns hit the disk
    only when touched), else generate + cache. A legacy npz converts to
    the directory format once."""
    from oceanbase_tpu.core.dictionary import Dictionary
    from oceanbase_tpu.core.table import Table
    from oceanbase_tpu.models.tpch import datagen
    from oceanbase_tpu.models.tpch import schema as S

    d = cache_path(sf)
    npz = _legacy_npz(sf)
    if not os.path.isdir(d) and os.path.exists(npz):
        try:
            z = np.load(npz, allow_pickle=False)
            _write_npy_dir(d, {k: z[k] for k in z.files})
            os.remove(npz)
        except OSError:
            pass
    if os.path.isdir(d):
        files = set(os.listdir(d))
        tables = {}
        for name, schema in S.TABLES.items():
            data, dicts = {}, {}
            for f in schema.fields:
                data[f.name] = np.load(
                    os.path.join(d, f"{name}|{f.name}.npy"), mmap_mode="r"
                )
                dk = f"{name}|{f.name}#dict.npy"
                if dk in files:
                    dicts[f.name] = Dictionary(
                        np.load(os.path.join(d, dk)).tolist(), sorted_=True
                    )
            tables[name] = Table(name, schema, data, dicts)
        return tables, "cache"
    tables = datagen.generate(sf)
    try:
        os.makedirs(CACHE, exist_ok=True)
        arrs = {}
        for n, t in tables.items():
            for c, a in t.data.items():
                arrs[f"{n}|{c}"] = a
            for c, dd in t.dicts.items():
                arrs[f"{n}|{c}#dict"] = np.array(dd.values())
        _write_npy_dir(d, arrs)
    except OSError:
        pass  # cache is an optimization; never fail the bench on disk
    return tables, "generated"


def seed_stats(sess, tables, sf: float) -> None:
    """Optimizer stats from a pickle cache (collection scans every column
    — tens of seconds at SF10 through mmap; deterministic data makes the
    cache exact)."""
    import pickle

    p = os.path.join(CACHE, f"stats_sf{sf:g}.pkl")
    sm = sess.stats
    if os.path.exists(p):
        try:
            with open(p, "rb") as f:
                blob = pickle.load(f)
            for name, ts in blob.items():
                t = tables.get(name)
                if t is not None:
                    sm._cache[name] = (t, ts)
            return
        except Exception:
            pass
    blob = {}
    for name in tables:
        ts = sm.table_stats(name)
        if ts is not None:
            blob[name] = ts
    try:
        os.makedirs(CACHE, exist_ok=True)
        tmp = p + f".tmp{os.getpid()}"
        with open(tmp, "wb") as f:
            pickle.dump(blob, f)
        os.replace(tmp, p)
    except OSError:
        pass


def ensure_projection(tables, sf: float) -> float:
    """lineitem sorted by l_shipdate via make_sorted_projection, with an
    npz cache wrapper (the argsort costs ~20s at SF10, paid once per
    machine). Returns seconds spent."""
    from oceanbase_tpu.storage.sorted_projection import (
        make_sorted_projection,
        projection_name,
    )

    t0 = time.perf_counter()
    li = tables["lineitem"]
    keep = [f.name for f in li.schema.fields if f.name in SP_COLS]
    d = os.path.join(CACHE, f"tpch_sf{sf:g}_sp.d")
    if os.path.isdir(d):
        pname = projection_name("lineitem", "l_shipdate")
        from oceanbase_tpu.core.dtypes import Schema
        from oceanbase_tpu.core.table import Table

        tables[pname] = Table(
            pname,
            Schema(tuple(f for f in li.schema.fields if f.name in keep)),
            {c: np.load(os.path.join(d, c + ".npy"), mmap_mode="r")
             for c in keep},
            {c: dd for c, dd in li.dicts.items() if c in keep},
        )
        li.sorted_projections = {"l_shipdate": pname}
    else:
        pname = make_sorted_projection(
            tables, "lineitem", "l_shipdate", cols=keep
        )
        try:
            os.makedirs(CACHE, exist_ok=True)
            _write_npy_dir(d, tables[pname].data)
        except OSError:
            pass
    return time.perf_counter() - t0


# ---------------------------------------------------------------------------
# CPU vectorized baselines (numpy; measured, not cited) with a persistent
# time+value cache: datagen is deterministic, so a baseline measured once
# on this machine stays valid across runs.
# ---------------------------------------------------------------------------


_CPU_CACHE_PATH = os.path.join(CACHE, "cpu_base.json")


def _cpu_cache():
    try:
        with open(_CPU_CACHE_PATH) as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


def _cpu_cache_put(key, t, val):
    c = _cpu_cache()
    c[key] = {"t": t, "val": val}
    try:
        os.makedirs(CACHE, exist_ok=True)
        tmp = _CPU_CACHE_PATH + f".tmp{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(c, f)
        os.replace(tmp, _CPU_CACHE_PATH)
    except OSError:
        pass


def _best(f, reps):
    ts, out = [], None
    for _ in range(reps):
        t0 = time.perf_counter()
        out = f()
        ts.append(time.perf_counter() - t0)
        # best-of-fewer beats the driver's rc=124 with nothing emitted
        if over_budget(margin=15.0):
            break
    return min(ts), out


def _reps_all(f, reps):
    """Every rep's seconds (budget-bounded) + the LAST result — the
    warm-serving variant of _best: q*_vs_e2e ratios report the per-rep
    MEDIAN with the spread alongside, so one lucky (or profiled) rep
    can't flatter or smear the serving number the way min-of-reps did."""
    ts, out = [], None
    for _ in range(max(1, reps)):
        t0 = time.perf_counter()
        out = f()
        ts.append(time.perf_counter() - t0)
        if over_budget(margin=15.0):
            break
    return ts, out


def cpu_baseline(qname, sf, fn, reps):
    """(best_seconds, value, source) with the persistent cache."""
    key = f"{qname}@sf{sf:g}"
    hit = _cpu_cache().get(key)
    if hit is not None:
        return float(hit["t"]), hit["val"], "cache"
    t, val = _best(fn, reps)
    try:
        json.dumps(val)
    except TypeError:
        val = None  # q1 returns arrays; its check lives in the test suite
    _cpu_cache_put(key, t, val)
    return t, val, "measured"


def check_result(qname, rs, cpu_val):
    """Per-query correctness cross-check vs the CPU baseline value."""
    if cpu_val is None:
        return True
    if qname == "q6":
        got = float(rs.columns["revenue"][0])
        return abs(got - cpu_val) <= 1e-6 * max(1.0, abs(cpu_val))
    if qname == "q3":
        got3 = [
            (int(rs.columns["l_orderkey"][i]), float(rs.columns["revenue"][i]))
            for i in range(rs.nrows)
        ]
        want3 = [(int(k), float(r)) for k, r, _d, _p in cpu_val]
        return len(got3) == len(want3) and all(
            gk == wk and abs(gr - wr) < 1e-2
            for (gk, gr), (wk, wr) in zip(got3, want3)
        )
    if qname == "q14":
        return abs(float(rs.columns["promo_revenue"][0]) - cpu_val) < 1e-3
    return True  # q1: full-table check is in tests/test_tpch_full.py


# ---------------------------------------------------------------------------


def cpu_suite_main(sf: float) -> None:
    """Measure the 22-query warm end-to-end suite on THIS jax backend and
    persist to cpu_suite_sf{sf}.json (the TPU run's engine-vs-engine
    baseline). Incremental: a partial run resumes where it stopped."""
    from oceanbase_tpu.engine import Session
    from oceanbase_tpu.models.tpch.sql_suite import QUERIES, UNIQUE_KEYS

    path = os.path.join(CACHE, f"cpu_suite_sf{sf:g}.json")
    out = {}
    try:
        with open(path) as f:
            out = json.load(f)
    except (OSError, ValueError):
        pass
    if out.get("_rev") != REV:
        out = {}  # partial suite from another engine build: start fresh
    tables, source = load_or_generate(sf)
    ensure_projection(tables, sf)
    sess = Session(tables, unique_keys=UNIQUE_KEYS)
    seed_stats(sess, tables, sf)
    for qid in range(1, 23):
        if f"q{qid}" in out:
            continue
        text = QUERIES[qid]
        t0 = time.perf_counter()
        sess.sql(text)  # compile + first run
        first = time.perf_counter() - t0
        e2e, _ = _best(lambda t=text: sess.sql(t), 2)
        out[f"q{qid}"] = round(e2e, 6)
        out["_rev"] = REV  # provenance: which engine build measured these
        tmp = path + f".tmp{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(out, f)
        os.replace(tmp, path)
        emit({"metric": "cpu_suite_progress", "value": qid,
              "unit": "queries",
              "detail": {"q": qid, "e2e_s": out[f"q{qid}"],
                         "first_s": round(first, 2)}})
    emit({"metric": "cpu_suite_done", "value": len(out), "unit": "queries",
          "detail": out})


def advisor_ab(tables, sf: float, reps: int) -> dict:
    """Layout-advisor A/B leg: hand-tuned lineitem(l_shipdate) projection
    vs the advisor's own pick from a COLD catalog (no projection, no
    hints — only the access evidence a short shipdate-heavy warmup
    leaves behind). Reports what fraction of the hand-tuned warm-Q6 e2e
    win the closed loop recovers, and whether the advisor-routed result
    is bit-identical to the hand-routed one (same stable argsort, same
    reduction order, so equality is exact, not approximate)."""
    from oceanbase_tpu.core.table import Table
    from oceanbase_tpu.engine import Session
    from oceanbase_tpu.models.tpch.sql_suite import QUERIES, UNIQUE_KEYS
    from oceanbase_tpu.server.layout_advisor import propose
    from oceanbase_tpu.server.workload import TableAccessStats
    from oceanbase_tpu.storage.sorted_projection import (
        make_sorted_projection,
        projection_name,
    )

    q6 = QUERIES[QID["q6"]]
    q14 = QUERIES[QID["q14"]]
    pname = projection_name("lineitem", "l_shipdate")
    d = {}

    def warm(sess):
        sess.sql(q6)  # compile + route through the current layout
        t, rs = _best(lambda: sess.sql(q6), max(3, reps))
        return t, float(rs.columns["revenue"][0])

    # hand-tuned leg: the catalog exactly as ensure_projection left it
    hand = Session(tables, unique_keys=UNIQUE_KEYS)
    seed_stats(hand, tables, sf)
    t_hand, v_hand = warm(hand)

    # cold leg: same column data, fresh lineitem (no projection attached)
    cold_tables = {n: t for n, t in tables.items() if "#sp:" not in n}
    li = tables["lineitem"]
    cold_tables["lineitem"] = Table(
        "lineitem", li.schema, dict(li.data), dict(li.dicts))
    cold = Session(cold_tables, unique_keys=UNIQUE_KEYS)
    seed_stats(cold, cold_tables, sf)
    cold.access = TableAccessStats()
    t_cold, v_cold = warm(cold)
    cold.sql(q14)  # the headline workload is shipdate-heavy; a second
    cold.sql(q14)  # query breaks the q6 filter-count tie in its favor

    # the advisor's pick from the cold session's evidence alone
    recs = propose(cold.access.snapshot(), cold_tables)
    pick = next((r for r in recs if r.action == "create_projection"
                 and r.table == "lineitem"), None)
    d["advisor_pick"] = (f"{pick.table}({pick.column})" if pick else "none")
    if pick is None or pick.column != "l_shipdate":
        d["advisor_error"] = "advisor did not pick lineitem(l_shipdate)"
        return d
    cols = None
    if pick.detail.startswith("cover=") and pick.detail != "cover=all":
        cols = pick.detail[len("cover="):].split(",")
    t0 = time.perf_counter()
    make_sorted_projection(cold_tables, "lineitem", pick.column, cols)
    d["advisor_build_s"] = round(time.perf_counter() - t0, 1)
    cold.plan_cache.flush()  # cached plans predate the new layout
    t_adv, v_adv = warm(cold)
    assert cold_tables[pname] is not None

    d["advisor_cover"] = pick.detail
    d["t_cold_s"] = round(t_cold, 6)
    d["t_hand_s"] = round(t_hand, 6)
    d["t_advisor_s"] = round(t_adv, 6)
    d["bit_identical_vs_hand"] = bool(v_adv == v_hand)
    d["correct_vs_cold"] = bool(
        abs(v_adv - v_cold) <= 1e-6 * max(1.0, abs(v_cold)))
    win_hand = t_cold - t_hand
    recovered = (t_cold - t_adv) / win_hand if win_hand > 1e-9 else 0.0
    d["win_recovered"] = round(recovered, 3)
    emit({
        "metric": f"layout_advisor_q6_sf{sf:g}_win_recovered",
        "value": round(recovered, 3),
        "unit": "fraction",
        "detail": d,
    })
    return d


def skew_join_ab(reps: int) -> dict:
    """Zipfian skew-join leg: one probe-side key value holds 60% of the
    rows, so plain hash repartition funnels 60% of the probe onto a
    single shard and every shard's exchange lane pads to that hot lane's
    capacity. The hybrid hot-key-broadcast route — chosen automatically
    by PxExecutor._skewed_key from TableAccessStats key evidence
    (measured NDV / top-value fraction, consulted before the optimizer
    histograms) — keeps hot probe rows local and broadcasts their build
    matches. Reports warm e2e for hybrid_hash='auto' with access
    evidence vs hybrid_hash=False on the same catalog, plus the measured
    evidence that made the call. Results must be bit-identical: both
    routes feed the same join kernel, only row placement differs."""
    import jax

    from oceanbase_tpu.core.dtypes import DataType, Field, Schema
    from oceanbase_tpu.core.table import Table
    from oceanbase_tpu.engine import Session
    from oceanbase_tpu.parallel.mesh import make_mesh
    from oceanbase_tpu.parallel.px import PxExecutor
    from oceanbase_tpu.server.workload import TableAccessStats
    from oceanbase_tpu.sql import parser as P

    d = {}
    nsh = len(jax.devices())
    if nsh < 4:
        d["skipped"] = f"{nsh} device(s): the 2/nsh skew threshold needs >= 4"
        return d
    rng = np.random.default_rng(7)
    n, nkeys, hot_frac = 1 << 18, 1 << 17, 0.6
    hot = rng.random(n) < hot_frac
    fk = np.where(hot, 7, rng.integers(0, nkeys, n)).astype(np.int64)
    i64 = DataType.int64()
    fact = Table.from_pydict(
        "skew_fact", Schema((Field("k", i64), Field("v", i64))),
        {"k": fk, "v": rng.integers(0, 1000, n).astype(np.int64)})
    # build side big enough that the exchange costing picks hash
    # repartition (not plain broadcast): > broadcast_threshold rows and
    # nkeys * (nsh-1) > n
    dim = Table.from_pydict(
        "skew_dim", Schema((Field("k", i64), Field("w", i64))),
        {"k": np.arange(nkeys, dtype=np.int64),
         "w": rng.integers(0, 1000, nkeys).astype(np.int64)})
    tables = {"skew_fact": fact, "skew_dim": dim}
    text = ("SELECT SUM(f.v + d.w) AS s FROM skew_fact f "
            "JOIN skew_dim d ON f.k = d.k")
    fkey, _, _ = P.fast_normalize(text)
    norm = fkey.replace("?n", "?").replace("?s", "?")

    access = TableAccessStats()
    ev = access.key_evidence("skew_fact", "k", fact)
    d["evidence_ndv"] = round(ev[0], 1) if ev else None
    d["evidence_top_frac"] = round(ev[1], 4) if ev else None
    d["skew_threshold"] = round(2.0 / nsh, 4)
    d["nsh"] = nsh

    def leg(hybrid, access_obj):
        sess = Session(tables)
        px = PxExecutor(sess.catalog, make_mesh(), stats=sess.stats,
                        hybrid_hash=hybrid, access=access_obj)
        sess.run_ast(P.parse(text), norm, executor=px)  # compile + run
        t, rs = _best(
            lambda: sess.run_ast(P.parse(text), norm, executor=px),
            max(3, reps))
        return t, int(rs.columns["s"][0])

    t_hash, v_hash = leg(False, None)
    t_auto, v_auto = leg("auto", access)
    d["t_plain_hash_s"] = round(t_hash, 6)
    d["t_hybrid_auto_s"] = round(t_auto, 6)
    d["bit_identical"] = bool(v_hash == v_auto)
    speedup = t_hash / t_auto if t_auto > 0 else 0.0
    d["hybrid_speedup"] = round(speedup, 3)
    emit({
        "metric": "skew_join_zipf_hybrid_speedup",
        "value": round(speedup, 3),
        "unit": "x",
        "detail": d,
    })
    return d


def main():
    # every emitted line is a COMPLETE cumulative summary, so a driver
    # kill mid-run never loses captured results — the self-budget only
    # orders what gets measured first
    global BUDGET
    budget = BUDGET = float(os.environ.get("BENCH_BUDGET_S", "420"))
    reps = int(os.environ.get("BENCH_REPS", "5"))
    stream_sf = float(os.environ.get("BENCH_STREAM_SF", "30"))

    import jax

    from oceanbase_tpu.share.compile_cache import enable_compile_cache

    enable_compile_cache()

    sf = float(os.environ.get("BENCH_SF", "10"))
    cpu_reps = 2 if sf <= 1 else 1

    if os.environ.get("BENCH_CPU_SUITE") == "1":
        # offline populator: the engine itself on the CPU backend is the
        # suite baseline (run with JAX_PLATFORMS=cpu); writes
        # cpu_suite_sf{sf}.json incrementally
        return cpu_suite_main(sf)

    from oceanbase_tpu.engine import Session
    from oceanbase_tpu.models.tpch.sql_suite import QUERIES, UNIQUE_KEYS
    from oceanbase_tpu.share import gap_ledger as _GL

    t0 = time.perf_counter()
    tables, source = load_or_generate(sf)
    gen_s = time.perf_counter() - t0
    sp_s = ensure_projection(tables, sf)
    li = tables["lineitem"]
    n = li.nrows

    detail = {
        "platform": jax.devices()[0].platform,
        "sf": sf,
        "rows": int(n),
        "datagen_s": round(gen_s, 1),
        "projection_s": round(sp_s, 1),
        "tables_source": source,
        "budget_s": budget,
        "sorted_projection": "lineitem(l_shipdate) [TPC-H 1.5.4 date index]",
    }

    from oceanbase_tpu.models.tpch.queries import (
        q1_numpy_fast,
        q3_cpu,
        q6_numpy,
        q14_cpu,
    )

    cpu_fns = {
        "q6": lambda: q6_numpy(li),
        "q1": lambda: q1_numpy_fast(li),
        "q3": lambda: q3_cpu(tables["customer"], tables["orders"], li),
        "q14": lambda: q14_cpu(tables["part"], li),
    }

    def summary(tpu_t, cpu_t):
        """Cumulative summary of everything measured so far — printed
        after every query so the last stdout line is always complete."""
        sps = [cpu_t[q] / tpu_t[q] for q in tpu_t]
        if sps:
            detail["geomean_speedup"] = round(
                float(np.exp(np.mean(np.log(sps)))), 3
            )
        detail["total_s"] = round(elapsed(), 1)
        q6_rows_s = n / tpu_t["q6"] if "q6" in tpu_t else 0.0
        vs = (q6_rows_s / (n / cpu_t["q6"])) if "q6" in tpu_t else 0.0
        emit({
            "metric": f"tpch_q6_sf{sf:g}_rows_per_sec_chip",
            "value": round(q6_rows_s, 1),
            "unit": "rows/s",
            "vs_baseline": round(vs, 3),
            "detail": detail,
        })

    sess = Session(tables, unique_keys=UNIQUE_KEYS)
    t0 = time.perf_counter()
    seed_stats(sess, tables, sf)
    detail["stats_s"] = round(time.perf_counter() - t0, 1)
    tpu_t, cpu_t = {}, {}
    summary(tpu_t, cpu_t)  # tables line: a kill during q6 still parses

    def _restore(qname: str) -> bool:
        """Reuse a persisted same-rev measurement (kills never erase)."""
        rec = _results_get(f"head:{qname}@sf{sf:g}")
        if rec is None or rec.get("correct") is not True:
            return False  # never immortalize a wrong-result measurement
        tpu_t[qname] = rec["tpu_s"]
        cpu_t[qname] = rec["cpu_s"]
        for k, v in rec.items():
            if k != "rev":
                detail[f"{qname}_{k}"] = v
        detail[f"{qname}_restored"] = True
        return True

    # conservative fresh-measurement cost estimates (seconds); cached CPU
    # baselines make repeat runs far cheaper than these
    est_cost = {"q6": 60.0, "q14": 60.0, "q1": 90.0, "q3": 120.0}
    for qname in ORDER:
        if _restore(qname):
            summary(tpu_t, cpu_t)
            continue
        if elapsed() > budget - est_cost[qname]:
            detail[f"{qname}_skipped"] = "budget"
            continue
        text = QUERIES[QID[qname]]
        try:
            cpu_t[qname], cpu_val, src = cpu_baseline(
                qname, sf, cpu_fns[qname], cpu_reps
            )
            rs = sess.sql(text)  # compile + first run
            ok = check_result(qname, rs, cpu_val)
            sess.sql(text)  # 2nd warm rep: past the profiled-run sample
            ets, rs_on = _reps_all(lambda t=text: sess.sql(t), max(3, reps))
            e2e = float(np.median(ets))
            phases_on = sess.last_phases
            # fused-spine A/B: same cached plan, narrowing forced OFF →
            # full-frame D2H + host-side slicing. Prices exactly what the
            # whole-statement fused program + on-device narrowing buy.
            sess.narrow_enabled_fn = lambda: False
            try:
                sess.sql(text)  # warm the unfused leg
                uts, rs_off = _reps_all(
                    lambda t=text: sess.sql(t), max(2, reps // 2))
            finally:
                sess.narrow_enabled_fn = None  # default: narrowing on
            unfused = float(np.median(uts))
            # device-path timing through the SAME cached executable the
            # session compiled (a separately prepared plan would re-trace
            # and compile a second time)
            entry, qp = sess.cached_entry(text)
            assert entry is not None, "plan cache miss on timed re-fetch"
            prepared = entry.prepared
            prepared.run(qparams=qp)  # warm
            # amortized dispatch: K back-to-back executions, one sync;
            # short programs re-measure at K=64
            def _run_k(K, p=prepared, q=qp):
                out = None
                for _ in range(K):
                    out = p.run_nocheck(qparams=q)
                return int(out.nrows)

            K = 8
            t, _ = _best(lambda: _run_k(K), reps)
            if t / K < 0.03:
                K = 64
                t, _ = _best(lambda: _run_k(K), max(2, reps // 2))
            tpu_t[qname] = t / K
            qd = {
                "dispatch_k": K,
                "tpu_s": round(tpu_t[qname], 6),
                "cpu_s": round(cpu_t[qname], 6),
                "cpu_source": src,
                # e2e_s is the per-rep MEDIAN of the warm serving leg
                # (min-of-reps let one lucky rep flatter the ratio);
                # the spread bounds run-to-run noise in the artifact
                "e2e_s": round(e2e, 6),
                "e2e_reps": len(ets),
                "e2e_spread_s": round(float(max(ets) - min(ets)), 6),
                "unfused_e2e_s": round(unfused, 6),
                "fused_speedup": round(unfused / e2e, 3) if e2e > 0 else 0.0,
                "fused_identical": bool(rs_on.rows() == rs_off.rows()),
                "speedup": round(cpu_t[qname] / tpu_t[qname], 3),
                "vs_e2e": round(cpu_t[qname] / e2e, 3),
                "rows_per_s": round(n / tpu_t[qname], 1),
                "correct": bool(ok),
                # host tax: the e2e-vs-chip gap, conservation-accounted.
                # The amortized device time is the chip's share; the
                # engine's own phase timings (last_phases from the timed
                # e2e reps) carve the host share into named ledger
                # phases with an explicit unattributed residual.
                "host_tax_s": round(max(0.0, e2e - tpu_t[qname]), 6),
                "host_tax": _GL.GapLedger.from_phases(
                    e2e, phases_on,
                    device_s=tpu_t[qname]).to_dict(),
            }
            for k, v in qd.items():
                detail[f"{qname}_{k}"] = v
            _results_put(f"head:{qname}@sf{sf:g}", qd)
        except Exception as e:  # pragma: no cover — keep partial results
            detail[f"{qname}_error"] = f"{type(e).__name__}: {e}"
        summary(tpu_t, cpu_t)

    # consolidated host-tax artifact: one JSON with every headline
    # query's gap attribution (fresh or restored), provenance-stamped,
    # next to the BENCH_OUT line file so CI collects it directly
    ht_rows = {q: {"host_tax_s": detail.get(f"{q}_host_tax_s"),
                   "e2e_s": detail.get(f"{q}_e2e_s"),
                   "tpu_s": detail.get(f"{q}_tpu_s"),
                   **detail[f"{q}_host_tax"]}
               for q in ORDER if f"{q}_host_tax" in detail}
    if _BENCH_OUT and ht_rows:
        ht_path = os.path.join(os.path.dirname(_BENCH_OUT) or ".",
                               "HOSTTAX_r01.json")
        try:
            with open(ht_path, "w") as f:
                json.dump({"bench_meta": _meta(), "sf": sf,
                           "queries": ht_rows}, f, indent=1)
            detail["hosttax_artifact"] = ht_path
        except OSError as e:  # pragma: no cover
            detail["hosttax_artifact_error"] = str(e)

    # ---- layout-advisor A/B leg (hand-tuned vs advisor-chosen) --------
    # the closed loop must recover >= 90% of the hand-tuned projection's
    # warm-Q6 win starting from a cold catalog (full-cover build over
    # lineitem: the argsort + gather dominate, hence the budget margin)
    if (os.environ.get("BENCH_ADVISOR", "1") == "1"
            and not over_budget(margin=40.0 + 10.0 * sf)):
        try:
            for k, v in advisor_ab(tables, sf, reps).items():
                detail[f"advisor_{k}" if not k.startswith("advisor")
                       else k] = v
        except Exception as e:  # pragma: no cover — keep partial results
            detail["advisor_error"] = f"{type(e).__name__}: {e}"
        summary(tpu_t, cpu_t)
    elif os.environ.get("BENCH_ADVISOR", "1") == "1":
        detail["advisor_skipped"] = "budget"

    # ---- zipfian skew-join leg (hybrid hot-key-broadcast A/B) ---------
    # the hot-key-broadcast route must beat plain hash repartition when
    # measured key evidence says one value overloads its hash lane
    if (os.environ.get("BENCH_SKEW", "1") == "1"
            and not over_budget(margin=60.0)):
        try:
            for k, v in skew_join_ab(reps).items():
                detail[f"skew_{k}"] = v
        except Exception as e:  # pragma: no cover — keep partial results
            detail["skew_error"] = f"{type(e).__name__}: {e}"
        summary(tpu_t, cpu_t)
    elif os.environ.get("BENCH_SKEW", "1") == "1":
        detail["skew_skipped"] = "budget"

    # ---- full 22-query timed suite (QphH-style composite) -------------
    # Every query times its WARM end-to-end latency through the session;
    # per-query results persist across runs (the XLA persistent cache
    # makes repeat compiles cheap), so the suite fills incrementally and
    # a complete composite emerges even under tight budgets. Baseline:
    # the SAME engine on the CPU backend (a vectorized CPU engine),
    # measured offline into cpu_suite_sf{sf}.json.
    run_suite = os.environ.get("BENCH_SUITE", "1") == "1"
    if run_suite and elapsed() < budget - 30:
        cpu_suite = {}
        try:
            with open(os.path.join(CACHE, f"cpu_suite_sf{sf:g}.json")) as f:
                cpu_suite = json.load(f)
        except (OSError, ValueError):
            pass
        suite_times = {}
        for qid in range(1, 23):
            key = f"suite:q{qid}@sf{sf:g}"
            rec = _results_get(key)
            if rec is not None:
                suite_times[qid] = rec["e2e_s"]
                continue
            if elapsed() > budget - 45:
                break
            try:
                text = QUERIES[qid]
                sess.sql(text)  # compile (persistent-cache assisted)
                e2e, _ = _best(lambda t=text: sess.sql(t), 2)
                suite_times[qid] = e2e
                _results_put(key, {"e2e_s": round(e2e, 6)})
            except Exception as e:
                detail[f"suite_q{qid}_error"] = f"{type(e).__name__}: {e}"
        if suite_times:
            ts = list(suite_times.values())
            geo = float(np.exp(np.mean(np.log(ts))))
            detail["suite_queries_timed"] = len(suite_times)
            detail["suite_total_s"] = round(float(np.sum(ts)), 3)
            detail["suite_geomean_s"] = round(geo, 4)
            # QphH-style power metric: 3600 * SF / geometric-mean seconds
            detail["suite_power_at_sf"] = round(3600.0 * sf / geo, 1)
            detail["suite_times_s"] = {
                f"q{q}": round(t, 4) for q, t in sorted(suite_times.items())
            }
            if cpu_suite:
                sps = [
                    cpu_suite[f"q{q}"] / t
                    for q, t in suite_times.items()
                    if f"q{q}" in cpu_suite
                ]
                if sps:
                    detail["suite_geomean_speedup_vs_cpu_engine"] = round(
                        float(np.exp(np.mean(np.log(sps)))), 3
                    )
                    detail["suite_cpu_engine_source"] = (
                        f"cpu_suite_sf{sf:g}.json (same engine, cpu backend)"
                    )
                    # provenance: the CPU numbers' engine build vs this one
                    detail["suite_cpu_engine_rev"] = cpu_suite.get(
                        "_rev", "unknown")
                    detail["suite_tpu_engine_rev"] = REV
        summary(tpu_t, cpu_t)

    # ---- out-of-core streamed section (SF >= 30 through the chunked
    # executor with a reduced device budget) ---------------------------
    stream_cached = os.path.isdir(cache_path(stream_sf)) or os.path.exists(
        _legacy_npz(stream_sf)
    )
    if stream_sf > 0 and stream_cached and elapsed() < budget - 90:
        try:
            t0 = time.perf_counter()
            tables_s, src_s = load_or_generate(stream_sf)
            li_s = tables_s["lineitem"]
            n_s = li_s.nrows
            sess_s = Session(tables_s, unique_keys=UNIQUE_KEYS)
            seed_stats(sess_s, tables_s, stream_sf)
            # force real streaming: lineitem may NOT ride up whole
            stream_budget = int(
                os.environ.get("BENCH_STREAM_BUDGET", str(2 << 30)))
            sess_s.executor.device_budget = stream_budget
            detail["stream_sf"] = stream_sf
            detail["stream_rows"] = int(n_s)
            detail["stream_tables_source"] = src_s
            detail["stream_device_budget"] = stream_budget
            detail["streamed"] = True
            for qname in ("q6", "q1"):
                if elapsed() > budget - 45:
                    detail[f"stream_{qname}_skipped"] = "budget"
                    continue
                text = QUERIES[QID[qname]]
                fn = {"q6": lambda: q6_numpy(li_s),
                      "q1": lambda: q1_numpy_fast(li_s)}[qname]
                cpu_s, cpu_val, src = cpu_baseline(
                    qname, stream_sf, fn, 1
                )
                t1 = time.perf_counter()
                rs = sess_s.sql(text)  # compile + stream
                first_s = time.perf_counter() - t1
                ok = check_result(qname, rs, cpu_val)
                t1 = time.perf_counter()
                rs = sess_s.sql(text)  # warm plan: pure streaming cost
                warm_s = time.perf_counter() - t1
                detail[f"stream_{qname}_e2e_s"] = round(warm_s, 3)
                detail[f"stream_{qname}_first_s"] = round(first_s, 3)
                detail[f"stream_{qname}_cpu_s"] = round(cpu_s, 3)
                detail[f"stream_{qname}_cpu_source"] = src
                detail[f"stream_{qname}_vs_e2e"] = round(cpu_s / warm_s, 3)
                detail[f"stream_{qname}_rows_per_s"] = round(n_s / warm_s, 1)
                detail[f"stream_{qname}_correct"] = bool(ok)
                summary(tpu_t, cpu_t)

            # ---- BENCH_STREAM=1: pipeline A/B legs over the SAME warm
            # plans — prefetch on/off x compressed/raw wire. The knobs
            # are read per-run from the executor, so toggling them
            # between runs isolates the pipeline effect (same chunk
            # grid, same compiled program). ---------------------------
            if os.environ.get("BENCH_STREAM") == "1":
                def _stream_snap():
                    tots = [0.0] * 7
                    for e_ in sess_s.plan_cache._entries.values():
                        ss = getattr(
                            getattr(e_, "prepared", None),
                            "stream_stats", None)
                        if ss is not None:
                            for i, v in enumerate(ss.snapshot()):
                                tots[i] += v
                    return tots

                ex_s = sess_s.executor
                knobs0 = (ex_s.stream_prefetch_depth, ex_s.stream_compress)
                ab = {}
                for leg, depth, comp in (
                    ("prefetch_compressed", knobs0[0] or 2, True),
                    ("noprefetch_compressed", 0, True),
                    ("prefetch_raw", knobs0[0] or 2, False),
                ):
                    if elapsed() > budget - 30:
                        detail[f"stream_ab_{leg}_skipped"] = "budget"
                        continue
                    ex_s.stream_prefetch_depth = depth
                    ex_s.stream_compress = comp
                    s0 = _stream_snap()
                    t1 = time.perf_counter()
                    for qname in ("q6", "q1"):
                        sess_s.sql(QUERIES[QID[qname]])
                    leg_s = time.perf_counter() - t1
                    d = [b - a for a, b in zip(s0, _stream_snap())]
                    ab[leg] = leg_s
                    detail[f"stream_ab_{leg}_s"] = round(leg_s, 3)
                    detail[f"stream_ab_{leg}_overlap_pct"] = round(
                        100.0 * d[5] / d[3] if d[3] else 0.0, 1)
                    detail[f"stream_ab_{leg}_wire_ratio"] = round(
                        d[1] / d[2] if d[2] else 1.0, 3)
                ex_s.stream_prefetch_depth, ex_s.stream_compress = knobs0
                if "prefetch_compressed" in ab and \
                        "noprefetch_compressed" in ab:
                    emit({
                        "metric": "stream_prefetch_speedup",
                        "value": round(
                            ab["noprefetch_compressed"]
                            / ab["prefetch_compressed"], 3),
                        "unit": "x",
                        "detail": {k: round(v, 3) for k, v in ab.items()},
                    })
                summary(tpu_t, cpu_t)
        except Exception as e:  # pragma: no cover
            detail["stream_error"] = f"{type(e).__name__}: {e}"
    elif stream_sf > 0 and not stream_cached:
        detail["stream_skipped"] = "no cached tables (populate offline)"

    # final line re-emits with any budget-skip markers included
    summary(tpu_t, cpu_t)


if __name__ == "__main__":
    import sys

    # the one-line summary contract holds even on a crash or a soft kill:
    # the last stdout line is always parseable, and the exit code is 0 so
    # the driver reads the partial results instead of discarding an rc=124
    try:
        main()
    except BaseException as e:
        emit({
            "metric": "bench_error", "value": 0.0, "unit": "error",
            "detail": {"error": f"{type(e).__name__}: {e}",
                       "total_s": round(elapsed(), 1)},
        })
    sys.exit(0)
