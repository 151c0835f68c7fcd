#!/usr/bin/env python
"""Plan-profile smoke: the --planprof leg of tools/run_tier1.sh.

Runs a warm TPC-H mix (Q1/Q6/Q3) through a live Database and asserts
the promises the operator-profiling subsystem makes:

  1. bit-identity — a profiled execution (segmented per-operator stages
     with fences) returns EXACTLY the rows the fused program returns,
     for every query of the mix, on the warm plan-cache entry;
  2. full coverage — after profiling, __all_virtual_sql_plan_monitor
     carries one per-operator row for EVERY executed node of each
     profiled plan (the plan's EXPLAIN rendering emits one line per
     node, so the expected node count is the EXPLAIN line count minus
     the nodes the executor absorbs into a parent, e.g. the Join under
     a clustered-FK aggregate), each with fenced device time;
  3. surfaces live — EXPLAIN ANALYZE annotates the plan tree with
     est/actual/miss/device and appends the statement chip_idle_pct
     line, and the store's calibration records carry the compile-time
     estimates next to measured actuals.

Emits one JSON summary line (stdout, appended to $BENCH_OUT when set)
with bench_meta provenance.

    JAX_PLATFORMS=cpu python tools/planprof_smoke.py
"""

from __future__ import annotations

import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tools"))

_BENCH_OUT = os.environ.get("BENCH_OUT")

QIDS = (1, 6, 3)
WARM_REPS = 2


def fail(msg: str) -> int:
    print(f"PLANPROF-SMOKE FAIL: {msg}", file=sys.stderr)
    return 1


def main() -> int:
    from oceanbase_tpu.models.tpch import datagen
    from oceanbase_tpu.models.tpch.sql_suite import QUERIES, UNIQUE_KEYS
    from oceanbase_tpu.server.database import Database
    from oceanbase_tpu.sql import parser as P

    db = Database(n_nodes=1, n_ls=1, extra_catalog=datagen.generate(0.01))
    db._unique_keys.update(UNIQUE_KEYS)
    db.engine.executor.unique_keys = db._unique_keys
    db.engine.planner.unique_keys = db._unique_keys
    s = db.session()

    # ---- fused baseline: profiling off, plans compiled + cached ------
    db.config.set("enable_plan_profile", "false")
    fused = {}
    for q in QIDS:
        fused[q] = s.sql(QUERIES[q]).rows()
        if not fused[q]:
            return fail(f"Q{q} returned no rows")

    # ---- profiled runs on the WARM entries: bit-identity -------------
    db.config.set("enable_plan_profile", "true")
    digests = {q: P.digest_text(QUERIES[q]) for q in QIDS}
    profiled_stmts = 0
    absorbed = {}
    for rep in range(WARM_REPS):
        for q in QIDS:
            db.plan_profiler.force_next(digests[q])
            rs = s.sql(QUERIES[q])
            got, opp = rs.rows(), rs.op_profile
            if opp is None:
                return fail(f"Q{q} rep {rep}: forced profile did not run")
            profiled_stmts += 1
            if got != fused[q]:
                return fail(f"Q{q} rep {rep}: profiled rows differ from "
                            "the fused program")
            if not opp["samples"]:
                return fail(f"Q{q} rep {rep}: profile carried no samples")
            # nodes the executor never emits standalone (e.g. a Join
            # absorbed by a clustered-FK aggregate) carry no sample
            absorbed[q] = set(opp.get("absorbed", {}))

    # ---- coverage: every plan node present in the VT ------------------
    vt = s.sql(
        "select query_sql, node_id, op_kind, est_rows, actual_rows, "
        "miss_factor, device_us, out_bytes, executions "
        "from __all_virtual_sql_plan_monitor"
    ).rows()
    op_rows = [r for r in vt if r[1] >= 0]
    nodes_checked = 0
    for q in QIDS:
        n_nodes = len(s.sql("explain " + QUERIES[q]).rows())
        mine = {r[1]: r for r in op_rows if r[0] == digests[q]}
        executed = [nid for nid in range(n_nodes)
                    if nid not in absorbed[q]]
        missing = [nid for nid in executed if nid not in mine]
        if missing:
            return fail(f"Q{q}: plan has {n_nodes} nodes but VT is "
                        f"missing node_ids {missing}")
        if any(nid in mine for nid in absorbed[q]):
            return fail(f"Q{q}: absorbed nodes {sorted(absorbed[q])} "
                        "must not carry VT operator rows — they never "
                        "execute standalone")
        if any(mine[nid][8] < WARM_REPS for nid in executed):
            return fail(f"Q{q}: VT operator rows report fewer than "
                        f"{WARM_REPS} profiled executions")
        if sum(mine[nid][6] for nid in executed) <= 0:
            return fail(f"Q{q}: no fenced device time in VT rows")
        nodes_checked += len(executed)

    # ---- EXPLAIN ANALYZE: annotated tree + chip_idle_pct line ---------
    ea = [r[0] for r in s.sql("explain analyze " + QUERIES[6]).rows()]
    if not any("actual_rows=" in ln and "device=" in ln for ln in ea):
        return fail("EXPLAIN ANALYZE carries no operator annotations")
    if not any("chip_idle_pct:" in ln for ln in ea):
        return fail("EXPLAIN ANALYZE carries no chip_idle_pct line")

    # ---- calibration records: estimates captured at compile time ------
    recs = [r for q in QIDS
            for r in db.plan_profiler.store.digest_profile(digests[q])]
    if not any(r["est_rows"] > 0 for r in recs):
        return fail("no calibration record carries a compile-time "
                    "row estimate")

    from bench_meta import collect as bench_meta

    summary = {
        "bench": "planprof_smoke",
        "queries": [f"q{q}" for q in QIDS],
        "warm_reps": WARM_REPS,
        "profiled_statements": profiled_stmts,
        "nodes_checked": nodes_checked,
        "store_profiles": db.plan_profiler.store.profiles,
        "vt_operator_rows": len(op_rows),
        "total_device_us": round(float(sum(r[6] for r in op_rows)), 1),
        "meta": bench_meta(None),
    }
    line = json.dumps(summary)
    print(line, flush=True)
    if _BENCH_OUT:
        with open(_BENCH_OUT, "a") as f:
            f.write(line + "\n")
    print(f"planprof smoke OK: {profiled_stmts} profiled executions "
          f"bit-identical to fused, {nodes_checked} plan nodes covered "
          "in __all_virtual_sql_plan_monitor")
    db.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
