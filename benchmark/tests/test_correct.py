"""`correct` has to be able to fail: the control and the planted fault.

Run with `python -m pytest benchmark/tests -q` (not part of the repo's
tier-1 suite) or through `python -m benchmark.selfcheck`.

  control   the plain reference put in the program's place, computed in the
            precision below the one the configuration states (float32 sums
            for exact decimal answers; for sysbench, which states no
            precision, a stale read: the row of the neighbouring id). It
            must come out not correct, and the exact reference correct.
  fault     the rest of a run driven on the CPU at rehearsal scale with the
            timed path broken underneath: an answer altered where it is
            produced (the server's cell encoder). `correct` must read false.
            (A step that returns its state unchanged, half a batch left out
            and an exchange between chips left out are faults these
            one-chip, read-only cells cannot have.)
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.generators import sysbench, tpch  # noqa: E402
from benchmark.harness import check  # noqa: E402


def as_wire(rows):
    return [tuple(str(v) for v in row) for row in rows]


def records_from(gen, kinds, pools, data, **kw):
    return [(k, lit, 0.0, 0.0, as_wire(gen.reference(k, lit, data, **kw)), 0)
            for k in kinds for lit in pools[k]]


@pytest.fixture(scope="module")
def tpch_data():
    return tpch.generate({"scale_factor": 0.02}, 4_000_000_007)


# q6/q1 are the statements of the scan cell, which is out of BENCHMARK.json
# until the program's fault below is mended (PERF.md Open questions 1)
@pytest.mark.parametrize("kinds", [["q3", "q14"], ["q6", "q1"]])
def test_control_lower_precision_is_not_correct(tpch_data, kinds):
    limit = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "tpch-sf1.json")))["correct"]["rel_err_max"]
    pools = tpch.pools({"kinds": kinds, "pool": 8}, {}, 4_000_000_007)
    ref = lambda k, lit: tpch.reference(k, lit, tpch_data)  # noqa: E731
    exact = check.judge(records_from(tpch, kinds, pools, tpch_data), ref, limit)
    assert exact["correct"], exact
    low = check.judge(records_from(tpch, kinds, pools, tpch_data,
                                   acc=np.float32), ref, limit)
    assert not low["correct"], low
    assert (low["compared"]["rel_err_max"]["value"] > 3 * limit
            or low["compared"]["wrong_answers"]["value"] > 0)


def test_control_stale_read_is_not_correct():
    cfg = {"tables": 2, "table_size": 500}
    data = sysbench.generate(cfg, 5)
    st = sysbench.Stream({"kinds": ["point_select"]}, cfg, 5, 0, {})
    lits = [st.next()[:2] for _ in range(50)]
    ref = lambda k, lit: sysbench.reference(k, lit, data)  # noqa: E731
    good = [(k, lit, 0.0, 0.0, as_wire(ref(k, lit)), 0) for k, lit in lits]
    assert check.judge(good, ref, 0.0)["correct"]
    stale = [(k, lit, 0.0, 0.0,
              as_wire(sysbench.reference(k, lit, data, stale=1)), 0)
             for k, lit in lits]
    out = check.judge(stale, ref, 0.0)
    assert not out["correct"] and out["compared"]["wrong_answers"]["value"] == 50


def test_failed_statement_is_not_correct():
    ref = lambda k, lit: [("x",)]  # noqa: E731
    out = check.judge([("k", {}, 0.0, 0.0, "WireError: boom", 0)], ref, 0.0)
    assert not out["correct"]
    assert out["compared"]["missing_answers"]["value"] == 1
    assert not check.judge([], ref, 0.0)["correct"]  # nothing compared


FAULT = """
import sys
sys.argv = ["run.py"] + {argv!r}
sys.path.insert(0, {root!r})
import oceanbase_tpu.server.mysql_front as F
real = F._cell
n = [0]
def broken(v):
    # an answer altered where it is produced: every 7th cell
    n[0] += 1
    if {plant} and n[0] % 7 == 0 and v is not None:
        if isinstance(v, (float, F.np.floating)):
            v = float(v) * (1 + 1e-6)
        elif isinstance(v, str) and v:
            v = v[:-1] + ("0" if v[-1] != "0" else "1")
    return real(v)
F._cell = broken
import runpy
runpy.run_path({run!r}, run_name="__main__")
"""

CELLS = {
    "tpch-sf1.join": "scale_factor=0.01",
    "sysbench-10x1m.point_select": "tables=2,table_size=2000,warm_window_s=1",
}


def planted_run(argv: list, plant: bool, env=None) -> dict:
    """One whole run of run.py with the fault planted (or not) underneath."""
    code = FAULT.format(argv=argv, root=ROOT, plant=plant,
                        run=os.path.join(ROOT, "benchmark", "run.py"))
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=1200)
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def rehearse(cell: str, plant: bool) -> dict:
    argv = ["--workload", cell, "--seed", "3100000001", "--seconds", "2",
            "--trace", "0", "--rehearse", CELLS[cell]]
    return planted_run(argv, plant, dict(os.environ, JAX_PLATFORMS="cpu"))


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_fault_answer_altered_reads_not_correct(cell):
    sound = rehearse(cell, plant=False)
    assert sound["correct"] and sound["device"]["platform"] == "cpu", sound
    broken = rehearse(cell, plant=True)
    assert not broken["correct"], broken["compared"]


WITNESS = """
import json, sys
sys.path.insert(0, {root!r})
from benchmark.generators import tpch
from benchmark.harness.server import Served
from benchmark.harness.wire import WireClient
config = json.load(open({root!r} + "/benchmark/configs/tpch-sf1.json"))
config["scale_factor"] = {sf!r}
data = {{"lineitem": tpch.generate(config, 11)["lineitem"]}}
sv = Served(config)
c = WireClient(sv.port)
sv.apply_settings(c, config)
sv.load(c, tpch, config, data)
out = []
for lo, hi in {order!r}:
    lit = dict(tpch.VALIDATION["q6"], date="1995-01-01", date_end="1996-01-01",
               disc_lo=lo, disc_hi=hi)
    got = c.query(tpch.render("q6", lit))[0][0]
    out.append([got, str(tpch.reference("q6", lit, data)[0][0])])
sv.free()
print(json.dumps(out))
"""


def q6_sequence(order, sf=0.01, env=None):
    """[got, due] of each Q6 of `order`, sent in turn to a fresh server that
    holds lineitem alone."""
    p = subprocess.run(
        [sys.executable, "-c", WITNESS.format(root=ROOT, order=order, sf=sf)],
        capture_output=True, text=True, timeout=1200, env=env)
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def _q6_sequence(order):
    return [(g is not None and abs(float(g) - float(r)) < 1e-3)
            for g, r in q6_sequence(order, env=dict(os.environ,
                                                    JAX_PLATFORMS="cpu"))]


def test_program_fault_q6_witness_order():
    """The second witness: the same statements, the plan first compiled with
    an upper bound inside the column's domain, all agree with the reference."""
    assert all(_q6_sequence([("0.05", "0.07"), ("0.08", "0.10"),
                             ("0.03", "0.05")]))


@pytest.mark.xfail(reason="program fault (PERF.md Open questions 1): a Q6 "
                   "plan first compiled with `l_discount <= 0.10`, the "
                   "column's maximum, drops the bound; later literals bound "
                   "to that cached plan answer as if it were still 0.10",
                   strict=False)
def test_program_fault_q6_upper_bound_dropped():
    assert all(_q6_sequence([("0.08", "0.10"), ("0.05", "0.07")]))
