#!/usr/bin/env python
"""Host-tax ledger smoke: conservation + warm residual gate.

Drives a warm point read (statement fast path) and a warm Q6-style
aggregate (full path, cached plan) on a 1-node Database and checks the
per-statement GapLedger against the promises the observability layer
makes:

  1. CONSERVATION — for every statement, sum(phases) <= e2e exactly and
     sum(phases) + unattributed == e2e to float precision. No second of
     wall is counted twice and none is silently absorbed.
  2. WARM RESIDUAL GATE — the median ``unattributed`` share over the
     warm reps stays under 5% for BOTH statement classes. A regression
     that opens an unexplained gap in the serving path fails the smoke.
  3. FROZEN PHASE BUDGETS — each phase's median share of e2e stays
     under a frozen ceiling (generous, machine-independent shares, not
     absolute us). A refactor that quietly moves wall into e.g. "setup"
     or "completion fold" trips the table before it costs a millisecond.
  4. SURFACE LIVENESS — the statements show up in
     __all_virtual_host_tax (with phases_json), sysstat carries
     "host tax statements", and sql_audit rows carry chip_idle_us.

The last stdout line is the machine-readable JSON verdict (the tier-1
--hosttax lane greps it); exit code 1 on any gate failure.

    JAX_PLATFORMS=cpu python tools/hosttax_smoke.py [--reps N]
"""

import argparse
import json
import os
import statistics
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tools"))

POINT = "select v from kv where k = {}"
Q6 = ("select count(*) as n, sum(v) as rev from kv "
      "where k >= 100 and k < 600 and grp < 8")

RESIDUAL_GATE_PCT = 5.0

# Frozen warm budgets: max median share of e2e per phase (fractions).
# Ceilings are deliberately loose — they catch a phase DOUBLING its
# share, not scheduler jitter. "device dispatch"/"device wait"/"engine
# host" dominate by design (that's the point of the ledger: the host
# glue around them must stay small and named).
BUDGETS = {
    "point": {
        "setup": 0.20, "fast lookup": 0.35, "param pack": 0.15,
        "device dispatch": 0.75, "device wait": 0.55,
        "engine host": 0.60, "completion fold": 0.25,
    },
    "q6": {
        "setup": 0.20, "fast lookup": 0.20, "parse bind": 0.35,
        "plan compile": 0.30, "param pack": 0.15,
        "device dispatch": 0.80, "device wait": 0.60, "d2h": 0.30,
        "engine host": 0.70, "completion fold": 0.25,
    },
}


def run_class(sess, stmts, reps: int):
    """Run the warm reps; return the list of per-statement ledger dicts
    (read off the session between statements — same thread, so the
    closed ledger is this statement's)."""
    out = []
    for i in range(reps):
        sess.sql(stmts[i % len(stmts)]).rows()
        led = sess._gap
        assert led is not None and led.closed, "ledger did not close"
        # conservation, on the raw ledger (not the rounded dict)
        attributed = sum(led.phases.values())
        assert attributed <= led.e2e_s + 1e-9, (
            f"over-attribution: sum(phases)={attributed} > e2e={led.e2e_s}")
        assert abs(attributed + led.unattributed_s - led.e2e_s) < 1e-9, (
            "conservation broke: phases + unattributed != e2e")
        out.append(led.to_dict())
    return out

def median_shares(dicts):
    """Median per-phase share of e2e plus median residual pct."""
    keys = set()
    for d in dicts:
        keys.update(d["phases"])
    shares = {
        k: round(statistics.median(
            d["phases"].get(k, 0.0) / d["e2e_s"] if d["e2e_s"] else 0.0
            for d in dicts), 4)
        for k in sorted(keys)
    }
    resid = round(statistics.median(d["unattributed_pct"] for d in dicts), 3)
    return shares, resid


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=40)
    args = ap.parse_args()

    import numpy as np

    from oceanbase_tpu.server.database import Database

    db = Database(n_nodes=1, n_ls=1)
    s = db.session()
    s.sql("create table kv (id int primary key, k int, v int, grp int)")
    vals = np.random.default_rng(7).integers(0, 1000, size=2000)
    for lo in range(0, 2000, 500):
        s.sql("insert into kv values " + ", ".join(
            f"({i + 1}, {i}, {int(vals[i])}, {i % 16})"
            for i in range(lo, lo + 500)))
    fails = []

    # -- warmup: register the fast path (varying literals) + cache Q6 --
    for i in range(12):
        s.sql(POINT.format(i)).rows()
    for _ in range(3):
        s.sql(Q6).rows()
    rec = [a for a in db.audit.records() if a.stmt_type == "Select"]
    if not any(r.is_fast_path for r in rec):
        fails.append("warmup never engaged the statement fast path")

    # -- warm reps ----------------------------------------------------
    point_leds = run_class(
        s, [POINT.format(20 + i) for i in range(8)], args.reps)
    q6_leds = run_class(s, [Q6], args.reps)

    report = {"reps": args.reps, "classes": {}}
    for name, leds in (("point", point_leds), ("q6", q6_leds)):
        shares, resid = median_shares(leds)
        ok_resid = resid < RESIDUAL_GATE_PCT
        if not ok_resid:
            fails.append(f"{name}: warm residual {resid}% >= "
                         f"{RESIDUAL_GATE_PCT}%")
        over = {k: (s_, BUDGETS[name][k]) for k, s_ in shares.items()
                if k in BUDGETS[name] and s_ > BUDGETS[name][k]}
        unbudgeted = [k for k in shares
                      if k not in BUDGETS[name] and shares[k] > 0.05]
        for k, (got, cap) in over.items():
            fails.append(f"{name}: phase '{k}' median share {got} > "
                         f"frozen budget {cap}")
        for k in unbudgeted:
            fails.append(f"{name}: unbudgeted phase '{k}' at share "
                         f"{shares[k]} (> 5% of e2e)")
        report["classes"][name] = {
            "median_e2e_us": round(statistics.median(
                d["e2e_s"] for d in leds) * 1e6, 1),
            "median_chip_idle_pct": round(statistics.median(
                d["chip_idle_pct"] for d in leds), 2),
            "median_residual_pct": resid,
            "residual_gate_pct": RESIDUAL_GATE_PCT,
            "phase_shares": shares,
            "budgets": BUDGETS[name],
        }

    # -- surface liveness ---------------------------------------------
    vt = s.sql("select digest, executions, unattributed_pct, phases_json "
               "from __all_virtual_host_tax").rows()
    if not vt:
        fails.append("__all_virtual_host_tax returned no rows")
    else:
        try:
            ph = json.loads(vt[0][3])
            if not ph:
                fails.append("host-tax VT phases_json is empty")
        except Exception as e:  # noqa: BLE001 — malformed VT payload
            fails.append(f"host-tax VT phases_json unparsable: {e}")
    n_stat = db.metrics.counter("host tax statements")
    if n_stat < 2 * args.reps:
        fails.append(f"sysstat 'host tax statements'={n_stat} < "
                     f"{2 * args.reps}")
    if not any(r.chip_idle_us > 0 for r in db.audit.records()
               if r.stmt_type == "Select"):
        fails.append("no audit record carries chip_idle_us")

    report["vt_digests"] = len(vt)
    report["host_tax_statements"] = n_stat
    report["fails"] = fails
    report["ok"] = not fails
    for f in fails:
        print("FAIL:", f, file=sys.stderr)
    print(json.dumps(report))
    return 0 if not fails else 1


if __name__ == "__main__":
    sys.exit(main())
