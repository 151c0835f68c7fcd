"""The planted fault that `tpch-sf1-px4.join` could not hold: Q3's hash
lanes left out read `correct` true there, because placement colocates its
groups (`test_px_fault.py`). Here the same fault on
`tpch-sf1-px4-regroup.q10`, whose groups are customers: a customer's orders
lie on every chip (`o_custkey` is random, cl. 4.2.3), so the lanes move every
group and nothing about the placement stands in for them.

Run with `python -m pytest benchmark/tests -q` (not part of the repo's
tier-1 suite, whose twin is `tests/test_px_regroup.py`) or through
`python -m benchmark.selfcheck --rehearse`.

A whole run of the cell on the CPU's four host devices at rehearsal scale,
**on the cell's own data**, no witness planted, with one of the program's
exchanges returning its input, so that no row changes chips there:

  repartition     the hash lanes on `c_custkey` before the group-by: each
                  chip groups the rows it already had, and a customer comes
                  back as up to four partial revenues, none of them the sum.
                  The top 20 is wrong. HELD.
  broadcast_rows  the build sides of the joins stay a quarter: most matches
                  are lost. HELD.

With nothing left out the same run reads `correct` true.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

from test_px_fault import CHIPS, LEFT_OUT  # noqa: E402,F401 - the same faults

CELL = "tpch-sf1-px4-regroup.q10"

RUN = """
import sys
sys.argv = ["run.py"] + {argv!r}
sys.path.insert(0, {root!r})
sys.path.insert(0, {here!r})
import oceanbase_tpu.parallel.px as PX
import test_px_fault as F

left_out = {left_out!r}
if left_out:
    setattr(PX, left_out, F.LEFT_OUT[left_out])
import runpy
runpy.run_path({run!r}, run_name="__main__")
"""


def rehearse(left_out: str | None) -> dict:
    """One whole rehearsal run of the cell on four host devices, with the
    named exchange left out (or none)."""
    argv = ["--workload", CELL, "--seed", "3100000001", "--seconds", "2",
            "--trace", "0", "--rehearse", "scale_factor=0.01"]
    code = RUN.format(argv=argv, root=ROOT, left_out=left_out, here=HERE,
                      run=os.path.join(ROOT, "benchmark", "run.py"))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={CHIPS}")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=1200)
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


# exchange left out -> what `correct` must read, on the cell's own data
CASES = {None: True, "repartition": False, "broadcast_rows": False}


@pytest.mark.parametrize("left_out", sorted(CASES, key=lambda c: c or ""))
def test_exchange_left_out(left_out):
    line = rehearse(left_out)
    assert line["device"] == {"platform": "cpu", "kind": "cpu",
                              "count": CHIPS, "memory_peak_bytes": 0}, line
    assert line["correct"] == CASES[left_out], line["compared"]
    if not line["correct"]:
        assert line["compared"]["wrong_answers"]["value"] > 0
    else:
        assert line["window"]["compiles"] == 0
        assert set(line["window"]["by_kind"]) == {"q10"}
