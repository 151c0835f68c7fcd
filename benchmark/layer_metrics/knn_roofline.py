"""The kNN statements' share of the HBM roofline, in %.

Necessary bytes a statement, a function of the configuration alone
(`configs/ann-sift1m.json`): the IVF probe reads every centroid once and
the rows of `nprobe` lists of the mean length once,

    (lists x dim + nprobe x rows / lists x dim) x 4 bytes

(0.52 + 16.0 MB at SIFT1M's 1,000,000 x 128, 1024 lists, nprobe 32). Over
the traced sub-windows of the three kinds: statements x those bytes / the
chip's peak HBM bytes/s (`harness/peaks.json`) / the device's busy seconds
there, x 100. Over-probe, list padding and a statement that took the
brute-force route read as a lower share; the share cannot pass 100 % unless
the busy time leaves out work.

None without a trace or without a traced kNN statement.
"""

from __future__ import annotations

import json
import os

KINDS = ("knn", "knn_sel10", "knn_sel1")
CONFIG = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs", "ann-sift1m.json")


def necessary_bytes(config: dict) -> float:
    d, lists = int(config["dim"]), int(config["lists"])
    return 4.0 * (lists * d + int(config["nprobe"]) * int(config["rows"])
                  / lists * d)


def read(ctx: dict):
    red = ctx.get("trace")
    traced = ctx.get("traced_statements") or {}
    n = sum(v for k, v in traced.items() if k in KINDS)
    if red is None or not n:
        return None
    busy = sum(red["per_kind"][k]["busy_s"] for k in KINDS
               if k in red["per_kind"])
    if not busy:
        return None
    import jax

    from benchmark.harness.peaks import peaks

    with open(CONFIG) as f:
        config = json.load(f)
    peak = peaks(jax.devices()[0].device_kind)["hbm_bytes_per_s"]
    return n * necessary_bytes(config) / peak / busy * 100.0
