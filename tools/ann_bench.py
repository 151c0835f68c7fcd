#!/usr/bin/env python
"""ANN vector-index benchmark on the real chip: recall@10, warm
latency, and the serving-spine ratio.

Per VERDICT r3 item 4's done-bar (r05: served-route edition): IVF-flat
over 1M x 128d synthetic embeddings, recall@10 >= 0.9 vs brute force,
plus measured on-chip numbers shaped like bench.py's PR 18 legs:

  warm e2e         per-rep MEDIAN of the full SQL path (parse -> plan
                   cache -> fused probe kernel -> narrowed D2H), one
                   distinct query vector per rep
  device           amortized device-only time through the SAME cached
                   executable (per-query parameter vectors, one sync)
  e2e_vs_device    the serving-spine ratio — the host tax on a vector
                   query (ISSUE 20 gates it at smoke size)
  fused A/B        the filtered leg: predicate fused into the probe
                   kernel vs the same filtered query brute-forced with
                   the index dropped (exact reference) — recall AND
                   warm-median timing for both routes

Usage:

    python tools/ann_bench.py [ANNBENCH_r05.json] [n] [d]

Writes one JSON artifact with bench_meta provenance (git rev + config
fingerprint); also prints it, and appends to $BENCH_OUT when set."""

import json
import os
import statistics
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tools"))

LISTS = 1024
NPROBE = 32


def _qtext(q, k, where=""):
    lit = "[" + ",".join(f"{v:.5f}" for v in q) + "]"
    return (f"select id from docs {where}"
            f"order by vec_l2(emb, '{lit}') limit {k}")


def _warm_median(sess, queries, k, where="") -> float:
    """Per-rep median over distinct query vectors, plan warm."""
    for q in queries[:2]:
        sess.sql(_qtext(q, k, where))
    ets = []
    for q in queries:
        t0 = time.perf_counter()
        sess.sql(_qtext(q, k, where))
        ets.append(time.perf_counter() - t0)
    return statistics.median(ets)


def main():
    out_path = sys.argv[1] if len(sys.argv) > 1 else "ANNBENCH_r05.json"
    n = int(sys.argv[2]) if len(sys.argv) > 2 else 1_000_000
    d = int(sys.argv[3]) if len(sys.argv) > 3 else 128
    nq = 50
    k = 10

    import jax

    from bench_meta import collect as bench_meta
    from oceanbase_tpu.core.dtypes import DataType, Field, Schema, TypeKind
    from oceanbase_tpu.core.table import Table
    from oceanbase_tpu.engine import Session
    from oceanbase_tpu.storage.vector_index import (
        drop_vector_index,
        register_vector_index,
    )

    rng = np.random.default_rng(4)
    t0 = time.perf_counter()
    centers = rng.normal(size=(256, d)).astype(np.float32) * 4
    x = (
        centers[rng.integers(0, 256, n)]
        + rng.normal(size=(n, d)).astype(np.float32)
    )
    gen_s = time.perf_counter() - t0
    grp = (np.arange(n, dtype=np.int64) % 10)
    cat = {
        "docs": Table(
            "docs",
            Schema((
                Field("id", DataType(TypeKind.INT64)),
                Field("grp", DataType(TypeKind.INT64)),
                Field("emb", DataType.vector(d)),
            )),
            {"id": np.arange(n, dtype=np.int64), "grp": grp, "emb": x},
        )
    }
    queries = x[rng.integers(0, n, nq)] + rng.normal(
        size=(nq, d)).astype(np.float32) * 0.05

    # ---- ground truth: brute force through the engine (exact) --------
    sess = Session(cat)
    t0 = time.perf_counter()
    truth = []
    for q in queries[:10]:
        truth.append([int(v) for v in sess.sql(_qtext(q, k)).columns["id"]])
    brute_s = (time.perf_counter() - t0) / 10
    ftruth = []
    for q in queries[:10]:
        ftruth.append([int(v) for v in sess.sql(
            _qtext(q, k, "where grp < 5 ")).columns["id"]])
    brute_filtered_s = _warm_median(
        sess, queries[:10], k, "where grp < 5 ")

    # ---- index build -------------------------------------------------
    register_vector_index(cat, "docs", "emb", lists=LISTS, nprobe=NPROBE)
    sess2 = Session(cat)
    t0 = time.perf_counter()
    sess2.executor.ivf_host("docs", "emb")  # force the build
    build_s = time.perf_counter() - t0

    # ---- recall (first 10 queries have exact truth) ------------------
    hits = 0
    for q, want in zip(queries[:10], truth):
        got = [int(v) for v in sess2.sql(_qtext(q, k)).columns["id"]]
        hits += len(set(got) & set(want))
    recall = hits / (10 * k)

    # ---- fused A/B: predicate INSIDE the probe kernel ----------------
    fhits = 0
    for q, want in zip(queries[:10], ftruth):
        got = [int(v) for v in sess2.sql(
            _qtext(q, k, "where grp < 5 ")).columns["id"]]
        fhits += len(set(got) & set(want))
    recall_filtered = fhits / (10 * k)
    ann_filtered_s = _warm_median(sess2, queries[:10], k, "where grp < 5 ")

    # ---- warm e2e: per-rep median, distinct query vectors ------------
    ann_e2e = _warm_median(sess2, queries, k)

    # amortized device path: pipeline dispatches through the ONE cached
    # executable with per-query parameter vectors, sync once
    entry, _ = sess2.cached_entry(_qtext(queries[0], k))
    prepared = entry.prepared
    binds = [sess2.cached_entry(_qtext(q, k))[1] for q in queries]
    out = prepared.run(qparams=binds[0])  # warm + capacity check
    t0 = time.perf_counter()
    for qp in binds:
        out = prepared.run_nocheck(qparams=qp)
    _sync = int(out.nrows)
    ann_dev = (time.perf_counter() - t0) / nq
    ratio = ann_e2e / ann_dev if ann_dev > 0 else float("inf")

    artifact = {
        "metric": "ann_ivf_recall_at_10",
        "value": round(recall, 4),
        "unit": "recall",
        "vs_baseline": round(brute_s / ann_e2e, 3),
        "detail": {
            "platform": jax.devices()[0].platform,
            "n": n,
            "d": d,
            "lists": LISTS,
            "nprobe": NPROBE,
            "datagen_s": round(gen_s, 1),
            "build_s": round(build_s, 1),
            "qps_e2e": round(1.0 / ann_e2e, 1),
            "qps_device": round(1.0 / ann_dev, 1),
            "ann_query_s": round(ann_e2e, 5),
            "ann_query_device_s": round(ann_dev, 5),
            "e2e_vs_device": round(ratio, 3),
            "brute_force_query_s": round(brute_s, 5),
            "recall_at_10": round(recall, 4),
            "filtered": {
                "predicate": "grp < 5 (sel 0.5)",
                "recall_at_10": round(recall_filtered, 4),
                "fused_query_s": round(ann_filtered_s, 5),
                "brute_query_s": round(brute_filtered_s, 5),
                "fused_vs_brute": round(
                    brute_filtered_s / ann_filtered_s, 3)
                if ann_filtered_s > 0 else 0.0,
            },
        },
        "meta": bench_meta(),
    }
    drop_vector_index(cat, "docs", "emb")
    with open(os.path.join(REPO, out_path), "w") as f:
        json.dump(artifact, f, indent=1)
    print(json.dumps(artifact))
    bench_out = os.environ.get("BENCH_OUT")
    if bench_out:
        with open(bench_out, "a") as f:
            f.write(json.dumps(artifact) + "\n")


if __name__ == "__main__":
    main()
