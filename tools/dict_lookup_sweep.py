"""Sweep of what dict_lookup emits, on a TPU: the numbers behind
LOOKUP_MAX_RUNS (oceanbase_tpu/expr/compile.py).

    python tools/dict_lookup_sweep.py [--rows 6000640] [--reps 20]

For each dictionary size, `dict_lookup(table, codes)` itself is timed over
tables of 1 to 32 runs with the limit raised to the most it measures (so the
engine's own code emits the range compares), and over the same scattered
table with the limit at 0 (so it emits the gather): alone (a pred[rows]
result written to memory) and fused as TPC-H Q14 uses it
(`sum(where(pred, x, 0))`); median ms per call over `--reps` calls, each
checked against numpy first. One line per reading in
chiprun_out/dict_lookup_sweep.jsonl, a table on stdout.

A measurement off the chip is no measurement: without a TPU it exits 2 and
writes nothing. tests/test_dict_lookup.py checks every lowering on the CPU."""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

import oceanbase_tpu  # noqa: F401  (x64 on, as the engine runs)
from oceanbase_tpu.expr import compile as C
from oceanbase_tpu.share.metrics import MetricsRegistry

SIZES = (2, 7, 150, 256, 1024, 4096, 16384, 200_000)
RUN_COUNTS = (1, 2, 4, 8, 16, 32)


def run_table(n: int, k: int) -> np.ndarray | None:
    """k evenly spaced runs of true entries, a third of the table."""
    if n < 3 * k:
        return None
    t = np.zeros(n, np.bool_)
    step, width = n // k, max(n // (3 * k), 1)
    for i in range(k):
        t[i * step + 1 : i * step + 1 + width] = True
    return t


@contextlib.contextmanager
def limit(max_runs: int):
    """Inside, LOOKUP_MAX_RUNS is max_runs and what dict_lookup chooses is
    counted in the registry this yields."""
    reg = MetricsRegistry()
    keep, prev = C.LOOKUP_MAX_RUNS, C.set_lookup_metrics(reg)
    C.LOOKUP_MAX_RUNS = max_runs
    try:
        yield reg
    finally:
        C.LOOKUP_MAX_RUNS = keep
        C.set_lookup_metrics(prev)


def time_ms(fn, args, reps: int) -> float:
    fn(*args).block_until_ready()
    out = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn(*args).block_until_ready()
        out.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(out)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=6_000_640)
    ap.add_argument("--reps", type=int, default=20)
    a = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"dict_lookup_sweep: JAX found no TPU ({dev.platform}); "
              "nothing timed, nothing written", file=sys.stderr)
        return 2
    rng = np.random.default_rng(27)
    x_host = rng.integers(0, 10_000_000, a.rows).astype(np.int64)
    x = jnp.asarray(x_host)
    os.makedirs("chiprun_out", exist_ok=True)
    out = open("chiprun_out/dict_lookup_sweep.jsonl", "w")
    print(f"device {dev.platform} {dev.device_kind}; rows {a.rows}; "
          f"reps {a.reps}; LOOKUP_MAX_RUNS {C.LOOKUP_MAX_RUNS}")
    print(f"{'entries':>8} {'table':<10} {'lowering':<9} {'alone_ms':>9} {'fused_ms':>9}")
    for n in SIZES:
        codes_host = rng.integers(0, n, a.rows).astype(np.int32)
        codes = jnp.asarray(codes_host)
        cases = [(f"runs{k}", t, max(RUN_COUNTS))
                 for k in RUN_COUNTS if (t := run_table(n, k)) is not None]
        scattered = rng.random(n) < 0.5
        scattered[0], scattered[-1] = True, False
        cases.append(("scattered", scattered, 0))
        for name, table, max_runs in cases:
            alone = jax.jit(lambda c, t=table: C.dict_lookup(t, c))
            fused = jax.jit(lambda c, v, t=table: jnp.sum(
                jnp.where(C.dict_lookup(t, c), v, 0)))
            want = table[codes_host]
            with limit(max_runs) as reg:  # jit traces on the first call
                got, total = np.asarray(alone(codes)), int(fused(codes, x))
            if not np.array_equal(got, want) or total != int(x_host[want].sum()):
                print(f"WRONG: {n} {name}")
                return 1
            (lowering,) = [k for k in ("constant", "runs", "gather")
                           if reg.counter(f"dict lookup {k}")]
            rec = {
                "entries": n, "table": name, "lowering": lowering,
                "rows": a.rows,
                "alone_ms": time_ms(alone, (codes,), a.reps),
                "fused_ms": time_ms(fused, (codes, x), a.reps),
                "platform": dev.platform, "device_kind": dev.device_kind,
            }
            out.write(json.dumps(rec) + "\n")
            out.flush()
            print(f"{n:>8} {name:<10} {lowering:<9} {rec['alone_ms']:>9.3f} "
                  f"{rec['fused_ms']:>9.3f}", flush=True)
    # what the consumer costs with no predicate at all
    base = jax.jit(lambda v: jnp.sum(v))
    print(f"{'-':>8} {'sum only':<20} {'':>9} {time_ms(base, (x,), a.reps):>9.3f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
