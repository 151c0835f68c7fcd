#!/usr/bin/env python3
"""The control and the planted faults of `sysbench-1m-trx.read_only` at the
cell's own size, on the machine with the chip:

  python benchmark/tests/oltp_at_size.py --control a,b,c
      for each seed: the data at the configuration's full size, the first
      statements of a window (`--transactions` whole transactions a
      connection over the cell's 32 connections), the plain reference, and
      the control in its place (the `stale` reference: the neighbouring id's
      row, a range shifted by one). Prints what `correct`'s numbers read;
      exit code 1 if a control reads correct. Needs no accelerator.
  python benchmark/tests/oltp_at_size.py --fault cell|commit_error --seed n
      one whole run of run.py at the cell's size with the fault of
      `test_sysbench_oltp.py` planted underneath: `correct` must read false.
      Exit code 1 if it reads true.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import test_sysbench_oltp as t  # noqa: E402
from benchmark.generators import sysbench_oltp as gen  # noqa: E402
from benchmark.harness import cells, check  # noqa: E402


def control(seeds, transactions: int, override: str) -> int:
    _, _, config, traffic = cells.load_cell(t.CELL, override)
    for seed in seeds:
        t0 = time.perf_counter()
        data = gen.generate(config, seed)
        sent = []
        for i in range(int(traffic["clients"])):
            st = gen.Stream(traffic, config, seed, i, {})
            sent += [st.next()[:2] for _ in range(16 * transactions)]
        ref = lambda k, lit: gen.reference(k, lit, data)  # noqa: E731
        recs = [(k, lit, 0.0, 0.0, t.as_wire(gen.reference(
            k, lit, data, stale=1)), 0) for k, lit in sent]
        out = check.judge(recs, ref, float(config["correct"]["rel_err_max"]))
        by_kind = {}
        for kind in gen.SELECTS:
            one = [r for r in recs if r[0] == kind]
            by_kind[kind] = [check.judge(one, ref, 0.0)["compared"][
                "wrong_answers"]["value"], len(one)]
        print(json.dumps({
            "control": t.CELL, "seed": seed, "statements": len(recs),
            "correct": out["correct"], "compared": out["compared"],
            "wrong_of_sent_by_kind": by_kind,
            "seconds": time.perf_counter() - t0}), flush=True)
        if out["correct"] or not all(w for w, _ in by_kind.values()):
            return 1
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--control", default=None, help="seeds, a,b,c")
    ap.add_argument("--transactions", type=int, default=4)
    ap.add_argument("--override", default="")
    ap.add_argument("--fault", choices=("cell", "commit_error"))
    ap.add_argument("--seed", default="2147490001")
    ap.add_argument("--seconds", default="40")
    args = ap.parse_args()
    if args.control:
        return control([int(s) for s in args.control.split(",")],
                       args.transactions, args.override)
    argv = ["--workload", t.CELL, "--seed", args.seed, "--seconds",
            args.seconds, "--trace", "0"]
    if args.override:
        argv += ["--rehearse", args.override]
    line = t.planted_run(args.fault, argv)
    print(json.dumps({"fault": args.fault, "cell": t.CELL, "seed": args.seed,
                      "correct": line["correct"], "device": line["device"],
                      "attempted": line["attempted"],
                      "compared": line["compared"]}), flush=True)
    return int(bool(line["correct"]))


if __name__ == "__main__":
    sys.exit(main())
