"""End-to-end metrics, each a file `benchmark/end_to_end/<name>.json`.

All are taken on the client's clock by the benchmark itself, over all the
statements and all the time of the window:

  completed_per_s        statements answered inside the window / its seconds; a
                         statement in flight when the window closes counts by
                         the share of its time that lay inside (whole
                         statements alone step by 1/n: 0.6 % where a window holds 166)
  latency_percentile_ms  the p-th percentile over every statement started in
                         the window (those still in flight at its close are
                         waited for, and their wait counts)
  kind_geomean_ms        per statement kind the summed client seconds over the
                         count; the geometric mean of those across kinds (the
                         form of TPC-H Power@Size)
  setup_s                process start to the first timed statement
"""

from __future__ import annotations

import json
import math
import os

DIR = os.path.join(os.path.dirname(os.path.dirname(__file__)), "end_to_end")


def percentile(values, p: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    xs = sorted(values)
    if not xs:
        raise ValueError("no statements in the window")
    k = (len(xs) - 1) * p / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def by_kind(records) -> dict:
    """{kind: {"n", "mean_ms", "max_ms"}} over the client's clock."""
    out = {}
    for r in records:
        k = out.setdefault(r[0], {"n": 0, "mean_ms": 0.0, "max_ms": 0.0})
        k["n"] += 1
        k["mean_ms"] += (r[3] - r[2]) * 1e3
        k["max_ms"] = max(k["max_ms"], (r[3] - r[2]) * 1e3)
    for k in out.values():
        k["mean_ms"] /= k["n"]
    return out


def evaluate(spec: dict, ctx: dict) -> float:
    stat = spec["stat"]
    recs = ctx["records"]
    if stat == "completed_per_s":
        t_end = ctx["t_end"]
        done = sum(1.0 if r[3] <= t_end else (t_end - r[2]) / (r[3] - r[2])
                   for r in recs)
        return done / ctx["window_s"]
    if stat == "latency_percentile_ms":
        return percentile([(r[3] - r[2]) * 1e3 for r in recs], spec["p"])
    if stat == "kind_geomean_ms":
        kinds = by_kind(recs)
        return math.exp(sum(math.log(k["mean_ms"]) for k in kinds.values())
                        / len(kinds))
    if stat == "setup_s":
        return ctx["setup_s"]
    raise ValueError(f"unknown end-to-end stat {stat!r}")


def read_all(ctx: dict, wanted) -> dict:
    out = {}
    for name in wanted:
        with open(os.path.join(DIR, name + ".json")) as f:
            out[name] = float(evaluate(json.load(f), ctx))
    return out
