#!/usr/bin/env python3
"""The control and the planted fault of `sysbench-1m-rw.read_write` at the
cell's own size, on the machine with the chip. Each is one whole run of
`run.py` per seed (the window at the cell's own load: a transaction takes
half of it), with `test_sysbench_rw.py`'s plant underneath:

  python benchmark/tests/rw_at_size.py --control a,b,c
      the read-back answered from the generated data, as if every write
      were lost: `wrong_answers` > 0 on each seed.
  python benchmark/tests/rw_at_size.py --fault a,b,c
      every second acknowledged COMMIT rolled back underneath: `correct`
      false.
  python benchmark/tests/rw_at_size.py --spurious a,b,c
      past the warm-up every 23rd staged write refused as a write conflict
      that nothing caused: `missing_answers` > 0 and no restart excused.
  python benchmark/tests/rw_at_size.py --follower a,b,c
      no fault: every row of the read-back asked again at
      ob_read_consistency = weak and counted (PERF.md section 7).

Prints what `correct`'s numbers read; exit code 1 if a planted run reads
correct or the number named for it reads 0.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import test_sysbench_rw as t  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--control", default=None, help="seeds, a,b,c")
    ap.add_argument("--fault", default=None, help="seeds, a,b,c")
    ap.add_argument("--spurious", default=None, help="seeds, a,b,c")
    ap.add_argument("--follower", default=None, help="seeds, a,b,c")
    ap.add_argument("--override", default="")
    ap.add_argument("--seconds", default="40")
    args = ap.parse_args()
    plant, seeds = next((p, s) for p, s in (
        ("control", args.control), ("lost_commit", args.fault),
        ("spurious_conflict", args.spurious), ("follower", args.follower))
        if s)
    number = t.RUNS.get(plant)
    rc = 0
    for seed in seeds.split(","):
        argv = ["--workload", t.CELL, "--seed", seed, "--seconds",
                args.seconds, "--trace", "0"]
        if args.override:
            argv += ["--rehearse", args.override]
        line = t.planted_run(plant, argv)
        print(json.dumps({plant: t.CELL, "seed": int(seed),
                          "correct": line["correct"],
                          "device": line["device"],
                          "attempted": line["attempted"],
                          "readback": line["readback"],
                          "follower": line.get("follower"),
                          "errors": line.get("errors"),
                          "compared": line["compared"]}), flush=True)
        if number:
            rc |= int(bool(line["correct"])
                      or not line["compared"][number]["value"])
    return rc


if __name__ == "__main__":
    sys.exit(main())
