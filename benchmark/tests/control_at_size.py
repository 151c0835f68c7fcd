#!/usr/bin/env python3
"""The control at a cell's own size: `python benchmark/tests/control_at_size.py
--workload <cell> --seeds a,b,c`.

For each seed: the data at the configuration's full size, the statements the
window would send (the pools, or 2000 drawn keys), the plain reference, and
the control in its place (float32 sums for the exact decimal answers; a stale
read where the configuration states no precision). Prints what `correct`'s
numbers read for the control: it has to fail at least one of them. Needs no
accelerator; run on the machine with the chip so that the size is the cell's.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import cells, check  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--override", default="")
    args = ap.parse_args()
    _, _, config, traffic = cells.load_cell(args.workload, args.override)
    gen = importlib.import_module("benchmark.generators." + traffic["generator"])
    limit = float(config["correct"]["rel_err_max"])
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        data = gen.generate(config, seed)
        pools = gen.pools(traffic, config, seed)
        stmts = [(k, lit) for k, lit, _ in gen.warmup(traffic, config, pools)]
        if not pools:
            st = gen.Stream(traffic, config, seed, 0, pools)
            stmts = [st.next()[:2] for _ in range(2000)]
        low = ({"acc": np.float32} if config["guarantees"]["answers"] == "exact"
               else {"stale": 1})
        wire = lambda rows: [tuple(str(v) for v in r) for r in rows]  # noqa: E731
        recs = [(k, lit, 0.0, 0.0, wire(gen.reference(k, lit, data, **low)), 0)
                for k, lit in stmts]
        out = check.judge(recs, lambda k, lit: gen.reference(k, lit, data),
                          limit)
        print(json.dumps({
            "control": args.workload, "seed": seed, "statements": len(recs),
            "correct": out["correct"],
            "compared": out["compared"],
            "seconds": time.perf_counter() - t0}), flush=True)
        if out["correct"]:
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
