#!/usr/bin/env python
"""Filtered-ANN serving smoke: the vector-route promises, gated.

Four legs, each pinning one promise of ISSUE 20's served IVF route:

  1. RECALL — filtered ANN (predicate fused into the probe kernel)
     at n=100k through a real DbSession: recall@10 vs the exact numpy
     answer must be >= RECALL_GATE, and the plan must actually take
     the IVF route ("ann probes" sysstat moves).
  2. E2E VS DEVICE — warm filtered-ANN per-rep MEDIAN end-to-end
     through the session (distinct query vector per rep, so nothing
     result-caches) vs the amortized device-only time through the
     engine's cached executable: the ratio must stay within
     E2E_VS_DEVICE_GATE (the acceptance's 10x at n=100k).
  3. WIRE COALESCING — vector statements through the async MySQL
     front end from WIRE_SESSIONS real socket connections: the
     continuous batcher must coalesce >= COALESCE_GATE lanes into one
     device dispatch (embedding rides the packed qparam block, so
     distinct query vectors share one executable), with zero failed
     statements.
  4. ADVISOR HEAT — brute vec_l2 sorts on an UNINDEXED vector column
     must make the layout advisor recommend create_vector_index, and
     auto mode must build it as a BACKGROUND dag: the next plan takes
     the ANN route and __all_virtual_vector_index reports the build.

The last stdout line is the machine-readable JSON verdict (with
bench_meta provenance; also appended to $BENCH_OUT when set); exit
code 1 on any gate failure.

    JAX_PLATFORMS=cpu python tools/ann_smoke.py [--n N] [--reps N]
"""

import argparse
import json
import os
import statistics
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tools"))

RECALL_GATE = 0.9
E2E_VS_DEVICE_GATE = 10.0
COALESCE_GATE = 4
WIRE_SESSIONS = 8

D = 32
LISTS = 256
NPROBE = 8
K = 10

_BENCH_OUT = os.environ.get("BENCH_OUT")


def emit(obj) -> None:
    print(json.dumps(obj), flush=True)
    if _BENCH_OUT:
        with open(_BENCH_OUT, "a") as f:
            f.write(json.dumps(obj) + "\n")


def _qtext(q, where=""):
    lit = "[" + ",".join(f"{v:.5f}" for v in q) + "]"
    return (f"select id from docs {where}"
            f"order by vec_l2(emb, '{lit}') limit {K}")


def build_db(n: int):
    """Preloaded docs table (clustered embeddings + a selectivity
    column) with a registered IVF index, on a 1-node Database."""
    from oceanbase_tpu.core.dtypes import DataType, Field, Schema, TypeKind
    from oceanbase_tpu.core.table import Table
    from oceanbase_tpu.server.database import Database
    from oceanbase_tpu.storage.vector_index import register_vector_index

    rng = np.random.default_rng(11)
    centers = rng.normal(size=(LISTS, D)).astype(np.float32) * 4
    x = (centers[rng.integers(0, LISTS, n)]
         + rng.normal(size=(n, D)).astype(np.float32))
    grp = np.arange(n, dtype=np.int64) % 10
    db = Database(n_nodes=1, n_ls=1)
    db.catalog["docs"] = Table("docs", Schema((
        Field("id", DataType(TypeKind.INT64)),
        Field("grp", DataType(TypeKind.INT64)),
        Field("emb", DataType.vector(D)),
    )), {"id": np.arange(n, dtype=np.int64), "grp": grp, "emb": x})
    # preloaded read-only table: register the index spec directly (the
    # DDL path wants a served table; the advisor leg covers that flow)
    db._vector_specs.setdefault("docs", {})["emb"] = (LISTS, NPROBE)
    register_vector_index(db.catalog, "docs", "emb",
                          lists=LISTS, nprobe=NPROBE)
    queries = (x[rng.integers(0, n, 64)]
               + rng.normal(size=(64, D)).astype(np.float32) * 0.05)
    return db, x, grp, queries


def recall_leg(db, s, x, grp, queries, fails: list) -> dict:
    """Filtered recall@10 vs exact numpy, and route engagement."""
    mask = grp < 5
    xf = x[mask]
    idf = np.arange(len(x), dtype=np.int64)[mask]
    c0 = db.metrics.counters_snapshot()
    hits = total = 0
    for q in queries[:16]:
        got = [int(v[0]) for v in s.sql(_qtext(q, "where grp < 5 ")).rows()]
        d2 = ((xf - q) ** 2).sum(axis=1)
        want = set(idf[np.argsort(d2, kind="stable")[:K]].tolist())
        hits += len(set(got) & want)
        total += K
    recall = hits / total if total else 0.0
    c1 = db.metrics.counters_snapshot()
    probes = int(c1.get("ann probes", 0) - c0.get("ann probes", 0))
    if recall < RECALL_GATE:
        fails.append(f"recall: filtered recall@10 {recall:.3f} < "
                     f"{RECALL_GATE}")
    if probes <= 0:
        fails.append("recall: 'ann probes' never moved — the filtered "
                     "statement did not take the IVF route")
    return {"queries": 16, "recall_at_10": round(recall, 4),
            "gate": RECALL_GATE, "ann_probes": probes}


def ratio_leg(db, s, queries, reps: int, fails: list) -> dict:
    """Warm filtered e2e (per-rep median, distinct vectors) vs the
    amortized device path through the engine's cached executable."""
    where = "where grp < 5 "
    # vectors disjoint from the recall leg's: a repeated embedding
    # would serve from the result cache and fake the e2e median
    queries = queries[16:16 + reps]
    for q in queries[:2]:
        s.sql(_qtext(q, where)).rows()
    ets = []
    for q in queries:
        t0 = time.perf_counter()
        s.sql(_qtext(q, where)).rows()
        ets.append(time.perf_counter() - t0)
    e2e = statistics.median(ets)

    eng = db.engine
    eng.sql(_qtext(queries[0], where))
    entry, _ = eng.cached_entry(_qtext(queries[0], where))
    if entry is None:
        fails.append("ratio: engine plan cache miss on the device leg")
        return {}
    prepared = entry.prepared
    binds = [eng.cached_entry(_qtext(q, where))[1] for q in queries]
    prepared.run(qparams=binds[0])  # warm + capacity check
    t0 = time.perf_counter()
    for qp in binds:
        cur = prepared.dispatch(qp, fused=False)
    cur.nrows  # one sync for the burst
    dev = (time.perf_counter() - t0) / len(binds)
    ratio = e2e / dev if dev > 0 else float("inf")
    if ratio > E2E_VS_DEVICE_GATE:
        fails.append(f"ratio: warm filtered e2e/device {ratio:.2f} > "
                     f"{E2E_VS_DEVICE_GATE}")
    return {"reps": reps,
            "e2e_us": round(e2e * 1e6, 1),
            "device_us": round(dev * 1e6, 1),
            "e2e_vs_device": round(ratio, 3),
            "gate": E2E_VS_DEVICE_GATE}


def wire_leg(db, queries, seconds: float, fails: list) -> dict:
    """Vector statements through the async front end: real sockets,
    closed loop, distinct embeddings — the batcher must coalesce."""
    import threading

    from benchmark.harness.wire import WireClient
    from oceanbase_tpu.server.async_front import AsyncMySqlFrontend

    # distinct vectors per lane and per iteration; result cache off so
    # every statement actually dispatches (and can coalesce)
    texts = [[_qtext(queries[(i * 7 + j) % len(queries)])
              for j in range(16)] for i in range(WIRE_SESSIONS)]
    afe = AsyncMySqlFrontend(db, workers=16).start()
    stop = threading.Event()
    done = [0] * WIRE_SESSIONS

    def drive(i: int) -> None:
        c = WireClient(afe.port, timeout=30.0)
        try:
            c.query("set ob_enable_result_cache = 0")
            while not stop.is_set():
                c.query(texts[i][done[i] % len(texts[i])])
                done[i] += 1
        finally:
            c.close()

    try:
        c0 = db.metrics.counters_snapshot()
        threads = [threading.Thread(target=drive, args=(i,), daemon=True)
                   for i in range(WIRE_SESSIONS)]
        for t in threads:
            t.start()
        time.sleep(seconds)
        stop.set()
        for t in threads:
            t.join(timeout=30)
        c1 = db.metrics.counters_snapshot()
    finally:
        afe.stop()

    def delta(name: str) -> int:
        return int(c1.get(name, 0) - c0.get(name, 0))

    stmts = sum(done)
    max_lanes = 0
    for name in c1:
        if name.startswith("stmt batch size ") and delta(name) > 0:
            max_lanes = max(max_lanes, int(name.rsplit(" ", 1)[1]))
    if stmts <= 0:
        fails.append("wire: no statements completed over the wire")
    if max_lanes < COALESCE_GATE:
        fails.append(f"wire: max coalesced ANN batch {max_lanes} lanes "
                     f"< {COALESCE_GATE}")
    return {"sessions": WIRE_SESSIONS,
            "stmts": stmts,
            "batched_stmts": delta("stmt batched statements"),
            "batched_dispatches": delta("stmt batched dispatches"),
            "max_coalesced_lanes": max_lanes,
            "gate": COALESCE_GATE,
            "ann_probes": delta("ann probes")}


def advisor_leg(fails: list) -> dict:
    """Query heat on an unindexed vector column -> recommendation ->
    background auto-build -> the ANN route and the VT row."""
    from oceanbase_tpu.core.dtypes import DataType, Field, Schema, TypeKind
    from oceanbase_tpu.core.table import Table
    from oceanbase_tpu.server.database import Database

    rng = np.random.default_rng(23)
    n = 20000
    x = rng.standard_normal((n, D)).astype(np.float32)
    db = Database(n_nodes=1, n_ls=1)
    try:
        s = db.session()
        db.catalog["docs"] = Table("docs", Schema((
            Field("id", DataType(TypeKind.INT64)),
            Field("emb", DataType.vector(D)),
        )), {"id": np.arange(n, dtype=np.int64), "emb": x})
        for _ in range(6):
            s.sql(_qtext(rng.standard_normal(D))).rows()
        rs = s.sql("alter system run layout advisor")
        acts = set(zip(rs.columns["action"], rs.columns["table_name"],
                       rs.columns["column_name"]))
        if ("create_vector_index", "docs", "emb") not in acts:
            fails.append(f"advisor: no create_vector_index from vec_l2 "
                         f"heat: {sorted(acts)}")
            return {}
        s.sql("alter system set ob_layout_advisor_mode = auto")
        db.dag_scheduler.start(1)
        s.sql("alter system run layout advisor")
        deadline = time.monotonic() + 60
        while (db.dag_scheduler.pending
               or "emb" not in getattr(db.catalog["docs"],
                                       "vector_indexes", {})):
            if time.monotonic() > deadline:
                fails.append("advisor: background IVF build never "
                             "finished")
                return {}
            time.sleep(0.01)
        db.dag_scheduler.stop()
        q = rng.standard_normal(D)
        routed = any("ANN IVF probe" in r[0]
                     for r in s.sql("explain " + _qtext(q)).rows())
        if not routed:
            fails.append("advisor: built index but EXPLAIN still shows "
                         "the brute route")
        vt = s.sql("select table_name, column_name, build_rows from "
                   "__all_virtual_vector_index").rows()
        if not any(r[0] == "docs" and r[1] == "emb" and int(r[2]) == n
                   for r in vt):
            fails.append(f"advisor: __all_virtual_vector_index missing "
                         f"the built index: {vt}")
        built = int(db.metrics.counters_snapshot().get(
            "layout advisor vector indexes built", 0))
        return {"rows": n, "routed": routed, "builds": built,
                "vt_rows": len(vt)}
    finally:
        db.close()


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=100_000)
    ap.add_argument("--reps", type=int, default=24)
    ap.add_argument("--wire-seconds", type=float, default=1.5)
    args = ap.parse_args()

    from bench_meta import collect as bench_meta

    fails: list = []
    report = {"legs": {}}
    db, x, grp, queries = build_db(args.n)
    try:
        s = db.session()
        report["legs"]["recall"] = recall_leg(db, s, x, grp, queries,
                                              fails)
        report["legs"]["ratio"] = ratio_leg(db, s, queries, args.reps,
                                            fails)
        report["legs"]["wire"] = wire_leg(db, queries,
                                          args.wire_seconds, fails)
    finally:
        db.close()
    report["legs"]["advisor"] = advisor_leg(fails)

    report["meta"] = bench_meta(db)
    report["fails"] = fails
    report["ok"] = not fails
    for f in fails:
        print("FAIL:", f, file=sys.stderr)
    emit(report)
    return 0 if not fails else 1


if __name__ == "__main__":
    sys.exit(main())
