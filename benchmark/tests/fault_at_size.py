#!/usr/bin/env python3
"""The planted fault and the Q6 order pair at a cell's own size, on the
machine with the chip:

  python benchmark/tests/fault_at_size.py --workload <cell> --seed n --seconds s
      one whole run of run.py at the cell's size with an answer altered where
      it is produced (tests/test_correct.py's fault): `correct` must read
      false. Exit code 1 if it reads true.
  python benchmark/tests/fault_at_size.py --q6-order <scale factor>
      PERF.md Open questions 1 at size: the Q6 sequence that shows the
      program's fault, then the same statements in the witness's order.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import test_correct as tc  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload")
    ap.add_argument("--seed", default="2147490001")
    ap.add_argument("--seconds", default="10")
    ap.add_argument("--q6-order", type=float, default=None)
    args = ap.parse_args()
    if args.q6_order is not None:
        for name, order in (
                ("fault", [("0.08", "0.10"), ("0.05", "0.07")]),
                ("witness", [("0.05", "0.07"), ("0.08", "0.10"),
                             ("0.03", "0.05")])):
            print(json.dumps({"q6_order": name, "scale_factor": args.q6_order,
                              "bounds": order, "got_due": tc.q6_sequence(
                                  order, sf=args.q6_order)}), flush=True)
        return 0
    line = tc.planted_run(["--workload", args.workload, "--seed", args.seed,
                           "--seconds", args.seconds, "--trace", "0"], True)
    print(json.dumps({"fault": args.workload, "seed": args.seed,
                      "correct": line["correct"], "device": line["device"],
                      "attempted": line["attempted"],
                      "compared": line["compared"]}), flush=True)
    return int(bool(line["correct"]))


if __name__ == "__main__":
    sys.exit(main())
