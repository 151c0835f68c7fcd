"""What the comparison that decides `ann-sift1m.knn`'s `correct` catches.

Run with `python -m pytest benchmark/tests -q` (not part of the repo's
tier-1 suite, whose twin is `tests/test_ann_served.py`). Each case is one
whole rehearsal of the cell (`benchmark/run.py --rehearse`, 20,000 x 16,
IVF 64/8, on the CPU), the program as it is or with one fault planted where
the answer is made:

  (none)          the program as it is: `correct` true
  nprobe_1        the control: the index probes 1 list of 64 (`nprobe = 1`
                  in its DDL); the filtered kinds may still over-probe:
                  recall_at_10 under its limit
  rerank_skipped  the probe's candidates in list order, not re-ranked by
                  distance: rows out of order and far from the nearest
  filter_dropped  every predicate of a plan keeps every row: rows that fail
                  `cat < 10` / `cat = c`
  bf16_distance   vec_l2's inner product rounded to bfloat16, a matmul
                  that gives its result in the TPU's default precision:
                  distances off by ~1e-3 of themselves, over the 1e-4 the
                  judge allows

`python benchmark/tests/test_ann_sift.py <fault> <run.py arguments>` runs
one such run with the fault planted, at whatever size the arguments give
(the chip's machine: the cell's own size).
"""

from __future__ import annotations

import inspect
import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "ann-sift1m.knn"
REHEARSE = "rows=20000,dim=16,lists=64,nprobe=8,queries=400,warm_window_s=1"

# fault -> (module, qualified name, text replaced, replacement)
EDITS = {
    "rerank_skipped": (
        "oceanbase_tpu.engine.executor", "Executor._emit_vector_topn",
        "neg, top_i = jax.lax.top_k(-dist, k)",
        "neg, top_i = jax.lax.top_k(jnp.where(live, -jnp.arange("
        "dist.shape[0], dtype=dist.dtype), -jnp.inf), k)"),
    "bf16_distance": (
        "oceanbase_tpu.expr.compile", "_eval_func",
        "xq = xv @ q",
        # rounded as a bfloat16 result: an explicit rounding, which the
        # CPU's compiler keeps where it would fold a pair of converts
        "xq = jax.lax.reduce_precision(xv @ q, exponent_bits=8, "
        "mantissa_bits=7)"),
}


def plant(fault: str | None) -> None:
    """Put `fault` into the program of this process."""
    import importlib

    if fault == "filter_dropped":
        import oceanbase_tpu.engine.executor as ex

        ex.compile_predicate = lambda e, batch: batch.sel
    elif fault in EDITS:
        modname, qual, old, new = EDITS[fault]
        mod = importlib.import_module(modname)
        owner, _, name = qual.rpartition(".")
        host = getattr(mod, owner) if owner else mod
        src = textwrap.dedent(inspect.getsource(getattr(host, name)))
        assert src.count(old) == 1, (fault, qual)
        scope = dict(vars(mod))
        exec(compile(src.replace(old, new), f"<{fault}>", "exec"), scope)
        setattr(host, name, scope[name])


RUN = """
import runpy, sys
sys.argv = ["run.py"] + {argv!r}
sys.path.insert(0, {root!r})
sys.path.insert(0, {here!r})
import test_ann_sift as F
F.plant({fault!r})
runpy.run_path({run!r}, run_name="__main__")
"""


def run_with(fault: str | None, argv: list, env=None, timeout=1200):
    code = RUN.format(argv=argv, root=ROOT, here=HERE, fault=fault,
                      run=os.path.join(ROOT, "benchmark", "run.py"))
    return subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, env=env, timeout=timeout)


def rehearse(fault: str | None) -> dict:
    overrides = REHEARSE.replace("nprobe=8", "nprobe=1") \
        if fault == "nprobe_1" else REHEARSE
    argv = ["--workload", CELL, "--seed", "3100000007", "--seconds", "3",
            "--trace", "0", "--rehearse", overrides]
    p = run_with(None if fault == "nprobe_1" else fault, argv,
                 env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


# fault -> the number of the comparison it must move past its limit
CASES = {None: None, "nprobe_1": "recall_at_10",
         "rerank_skipped": "wrong_answers", "filter_dropped": "wrong_answers",
         "bf16_distance": "wrong_answers"}


@pytest.mark.parametrize("fault", sorted(CASES, key=lambda c: c or ""))
def test_fault_reads_incorrect(fault):
    line = rehearse(fault)
    c = line["compared"]
    assert line["device"]["platform"] == "cpu"
    if fault is None:
        assert line["correct"], c
        assert c["wrong_answers"]["value"] == 0
        assert c["missing_answers"]["value"] == 0
        assert set(line["window"]["by_kind"]) == {"knn", "knn_sel10",
                                                   "knn_sel1"}
        return
    assert not line["correct"], c
    assert c["missing_answers"]["value"] == 0
    if CASES[fault] == "recall_at_10":
        assert c["recall_at_10"]["value"] < c["recall_at_10"]["limit"], c
    else:
        assert c["wrong_answers"]["value"] > 0, c


if __name__ == "__main__":
    p = run_with(sys.argv[1], sys.argv[2:])
    sys.stderr.write(p.stderr[-4000:])
    print(p.stdout.strip().splitlines()[-1] if p.stdout.strip() else "")
    sys.exit(p.returncode)
