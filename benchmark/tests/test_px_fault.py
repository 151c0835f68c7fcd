"""The planted fault the one-chip cells could not have: an exchange between
chips left out (`test_correct.py`'s docstring names it), and what the cell's
comparison holds of it.

Run with `python -m pytest benchmark/tests -q` (not part of the repo's
tier-1 suite, whose twin is `tests/test_px_served.py`) or through
`python -m benchmark.selfcheck --rehearse`.

A whole run of `tpch-sf1-px4.join` on the CPU's four host devices at
rehearsal scale, with one of the program's exchanges returning its input, so
that no row changes chips there, first **on the cell's own data**, as the
timed run sees it:

  broadcast_rows  the build side of Q3's and Q14's joins stays a quarter: most
                  matches are lost, and `correct` reads false. HELD.
  repartition     Q3's hash lanes on the group keys. lineitem is stored in key
                  order and a chip holds a contiguous range of it, so an
                  order's lines already lie on one chip, save the one order
                  that straddles each of the three boundaries: with the lanes
                  left out every answer of the pool still agrees with the
                  reference. NOT HELD: the cell times the lanes and its
                  `correct` does not see them (`BENCHMARK.json` says so in the
                  cell's `why`; PERF.md section 7 has the cell that would).
                  `run.py` takes the generator from the traffic file
                  (`traffic/join.json`: `tpch`), which the cell shares with
                  `tpch-sf1.join`, so this PR cannot hand the timed run other
                  data.

Then with a witness: `plant_witness` makes the order that straddles a
boundary the first row of every Q3, so the reference gives it once and the
program without its lanes twice, a part from each chip. That is the data a
cell that holds the lanes needs; here it shows that the comparison would
catch the fault, given rows that cross.
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

from benchmark.generators import tpch  # noqa: E402

CELL = "tpch-sf1-px4.join"
CHIPS = 4


def plant_witness(data: dict, shards: int) -> int:
    """Make an order whose lines lie on two of `shards` contiguous row ranges
    of lineitem (each a multiple of 1024 rows, as the program pads them) the
    first row of every Q3 of cl. 2.4.3.3: BUILDING, ordered before and
    shipped after every DATE of March 1995, dearer than any other. Edits
    `data` in place, so the plain reference reads the same rows as the
    program; returns the order's key."""
    li, od, cu = data["lineitem"], data["orders"], data["customer"]
    keys = li["l_orderkey"]
    unit = 1024 * shards
    per_shard = -(-len(keys) // unit) * unit // shards
    edge = next((b for b in range(per_shard, len(keys), per_shard)
                 if keys[b - 1] == keys[b]), None)
    if edge is None:
        raise AssertionError("no order straddles a boundary on this seed")
    lines = np.flatnonzero(keys == keys[edge])
    order = int(np.flatnonzero(od["o_orderkey"] == keys[edge])[0])
    od["o_orderdate"][order] = tpch._day("1995-02-01")
    li["l_shipdate"][lines] = tpch._day("1995-05-01")
    li["l_extendedprice"][lines] = 10 ** 9  # cents: 1e7 dollars a line
    li["l_discount"][lines] = 0
    codes, vocab = cu["c_mktsegment"]
    codes[int(od["o_custkey"][order]) - 1] = list(vocab).index("BUILDING")
    return int(keys[edge])


def identity_repartition(cols, mask, dest, n_shards, cap, axis_name=None):
    """`exchange.repartition` returning its input: no row changes chips."""
    import jax.numpy as jnp

    return cols, mask, jnp.zeros((), jnp.int64)


def identity_broadcast(cols, mask, axis_name=None):
    """`exchange.broadcast_rows` returning its input."""
    return cols, mask


LEFT_OUT = {"repartition": identity_repartition,
            "broadcast_rows": identity_broadcast}

RUN = """
import sys
sys.argv = ["run.py"] + {argv!r}
sys.path.insert(0, {root!r})
sys.path.insert(0, {here!r})
import oceanbase_tpu.parallel.px as PX
from benchmark.generators import tpch
import test_px_fault as F

if {witness!r}:
    generate = tpch.generate
    def with_witness(config, seed):
        data = generate(config, seed)
        F.plant_witness(data, F.CHIPS)
        return data
    tpch.generate = with_witness
left_out = {left_out!r}
if left_out:
    setattr(PX, left_out, F.LEFT_OUT[left_out])
import runpy
runpy.run_path({run!r}, run_name="__main__")
"""


def rehearse(left_out: str | None, witness: bool) -> dict:
    """One whole rehearsal run of the cell on four host devices, on the
    generator's data or with the witness order in it, with the named
    exchange left out (or none)."""
    argv = ["--workload", CELL, "--seed", "3100000001", "--seconds", "2",
            "--trace", "0", "--rehearse", "scale_factor=0.01"]
    code = RUN.format(argv=argv, root=ROOT, left_out=left_out,
                      witness=witness,
                      here=os.path.dirname(os.path.abspath(__file__)),
                      run=os.path.join(ROOT, "benchmark", "run.py"))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={CHIPS}")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=1200)
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


# (exchange left out, witness order planted) -> what `correct` must read.
# The second line is the finding, kept as a test so that the cell's `why`
# cannot drift from it: the day placement or the plan changes and the lanes'
# fault shows on the cell's own data, this fails and the `why` is rewritten.
CASES = {
    ("broadcast_rows", False): False,  # held by the cell as timed
    ("repartition", False): True,      # NOT held: placement colocates
    (None, True): True,
    ("repartition", True): False,      # held once a group's rows cross
}


def test_witness_order_is_first_of_every_q3():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "tpch-sf1-px4.json")) as f:
        config = dict(json.load(f), scale_factor=0.01)
    data = tpch.generate(config, 3_100_000_001)
    key = plant_witness(data, CHIPS)
    traffic = {"kinds": ["q3"], "pool": 8}
    for lit in tpch.pools(traffic, config, 3_100_000_001)["q3"]:
        assert tpch.reference("q3", lit, data)[0][0] == key


@pytest.mark.parametrize("left_out,witness", sorted(
    CASES, key=lambda c: (c[1], c[0] or "")))
def test_exchange_left_out(left_out, witness):
    line = rehearse(left_out, witness)
    assert line["device"] == {"platform": "cpu", "kind": "cpu",
                              "count": CHIPS, "memory_peak_bytes": 0}, line
    assert line["correct"] == CASES[left_out, witness], line["compared"]
    if not line["correct"]:
        assert line["compared"]["wrong_answers"]["value"] > 0
