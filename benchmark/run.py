#!/usr/bin/env python3
"""One run of one cell: `--workload <name> --seed <n> --seconds <s> --trace <0|1>`.

Everything about a cell is data: `BENCHMARK.json` names its configuration
and traffic mix, `configs/` and `traffic/` hold them, `generators/` makes the
data, statements and plain references of one public benchmark, and
`end_to_end/` and `layer_metrics/` define the metrics. This file only strings
the phases together:

  set-up    data from the seed, boot the served system (three replicas, MySQL
            wire), DDL + direct_load, first touch, warm every statement the
            window will send through the load generator's own connections
  window    the load generator (a child process, plain sockets, no JAX)
            drives closed-loop clients for --seconds
  traced    with --trace 1, a few more seconds under the profiler, one
            statement kind at a time
  read-back where the generator has `readback(records)`: the admin connection
            sends the statements it returns (every row a transaction wrote),
            outside every timed window
  check     the program is stopped and freed, then every answer of the window
            is compared with the plain reference (harness/check.py); where
            the generator has `judge(records, data, config, traffic)` it
            judges the log of the whole run instead (warm-up, windows,
            read-back), and gives the same dictionary back

The last line of stdout is the result. Off the chip the run fails, unless
`--rehearse key=value,...` (a tiny scale on the CPU) is given: that prints
"platform": "cpu" and no device metric.
"""

from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pickle  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)


def log(obj) -> None:
    print(json.dumps(obj, default=float), file=sys.stderr, flush=True)


class LoadGen:
    """The child process and its one-command-at-a-time protocol."""

    def __init__(self, spec: dict):
        env = {k: v for k, v in os.environ.items()
               if not k.startswith(("JAX_", "XLA_", "TPU_"))}
        self.p = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "harness", "loadgen.py"),
             json.dumps(spec)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env)
        if self._read() != "ready":
            raise RuntimeError("load generator did not come up")

    def _read(self):
        head = self.p.stdout.read(8)
        if len(head) < 8:
            raise RuntimeError("load generator died")
        n = int.from_bytes(head, "little")
        return pickle.loads(self.p.stdout.read(n))

    def call(self, **cmd):
        self.p.stdin.write((json.dumps(cmd) + "\n").encode())
        self.p.stdin.flush()
        return self._read()

    def stop(self) -> None:
        if self.p.poll() is None:
            try:
                self.p.stdin.write(b'{"cmd": "quit"}\n')
                self.p.stdin.flush()
                self.p.wait(timeout=20)
            except Exception:  # noqa: BLE001 - it is stopped either way
                self.p.kill()
        self.p.wait()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", default=None,
                    help="CPU rehearsal: config overrides, key=value,...")
    args = ap.parse_args()

    from benchmark.harness import cells

    try:
        bench, cell, config, traffic = cells.load_cell(args.workload,
                                                       args.rehearse)
    except KeyError as e:
        print(e.args[0], file=sys.stderr)
        return 2

    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    on_chip = device["platform"] == "tpu"
    if not on_chip and not args.rehearse:
        print(f"benchmark: JAX found no accelerator ({device['platform']}); "
              "a CPU rehearsal needs --rehearse", file=sys.stderr)
        return 2
    if on_chip and len(devs) < int(cell["chips"]):
        print(f"benchmark: the cell needs {cell['chips']} chips, JAX reports "
              f"{len(devs)}", file=sys.stderr)
        return 2
    used = devs[:int(cell["chips"])]

    from benchmark.harness import check, end_to_end, layer, loadgen, peaks
    from benchmark.harness import trace as T
    from benchmark.harness.server import Served
    from benchmark.harness.wire import WireClient

    if on_chip:
        peak = peaks.peaks(device["kind"])  # an unknown kind is an error
    gen = importlib.import_module("benchmark.generators."
                                  + traffic["generator"])
    setup = {}
    t0 = time.perf_counter()
    data = gen.generate(config, args.seed)
    rows = gen.row_counts(data)
    setup["datagen_s"] = time.perf_counter() - t0

    served = Served(config)
    lg = None
    tracedir = None
    try:
        admin = WireClient(served.port)
        served.apply_settings(admin, config)
        setup.update(served.load(admin, gen, config, data))
        lg = LoadGen({"port": served.port, "traffic": traffic,
                      "config": config, "seed": args.seed})
        t0 = time.perf_counter()
        n0, s0 = served.compiles.read()
        warm = lg.call(cmd="warm", repeat=int(traffic.get("warm_repeat", 3)))
        bad = [r for r in warm if isinstance(r[4], str)]
        if bad:
            raise RuntimeError(f"warm-up statement failed: {bad[0][4]}")
        whole_run = hasattr(gen, "judge")  # the judge replays every write
        history = list(warm) if whole_run else []
        if traffic.get("warm_window_s"):
            ww = lg.call(cmd="run", seconds=float(traffic["warm_window_s"]))
            if whole_run:
                history += ww["records"]
        n1, s1 = served.compiles.read()
        setup["warm_s"] = time.perf_counter() - t0
        setup["warm_compiles"], setup["warm_compile_s"] = n1 - n0, s1 - s0
        log({"setup": setup, "rows": rows, "device": device,
             "compile_cache_dir": served.cache_dir})

        # ---- the measured window
        counters0 = served.counters()
        setup_s = time.time() - T_START
        win = lg.call(cmd="run", seconds=args.seconds)
        counters1 = served.counters()
        records = win["records"]
        window_s = win["t_end"] - win["t0"]
        peak_bytes = max(((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                          for d in used), default=0)

        # ---- the traced sub-windows, one statement kind at a time
        red = None
        traced_statements, traced_bytes = {}, {}
        if args.trace and on_chip:
            tracedir = tempfile.mkdtemp(prefix="benchtrace")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # device ops and annotations only
            jax.profiler.start_trace(tracedir, profiler_options=opts)
            try:
                for kind in traffic["kinds"]:
                    with jax.profiler.TraceAnnotation(T.WINDOW_PREFIX + kind):
                        sub = lg.call(cmd="run", only=kind, seconds=float(
                            traffic["trace_seconds_per_kind"]))
                    records += sub["records"]
                    traced_statements[kind] = len(sub["records"])
            finally:
                jax.profiler.stop_trace()
            events = T.read_events(tracedir)
            red = T.reduce_events(events, len(used))
            widths = (cells.load_json(HERE, "generators", traffic["widths"])
                      if traffic.get("widths") else None)
            for kind, n in traced_statements.items():
                ref_cols = gen.REFERENCED_COLUMNS.get(kind)
                if widths and ref_cols:
                    traced_bytes[kind] = n * peaks.necessary_bytes(
                        ref_cols, rows, widths) / peak["hbm_bytes_per_s"]
        # ---- the read-back: acknowledged writes, through the leader
        back = []
        if hasattr(gen, "readback"):
            back = [loadgen._timed(admin, *a, -1)
                    for a in gen.readback(history + records)]
        lg.stop()
        lg = None
        admin.close()
    finally:
        if lg is not None:
            lg.stop()
        if tracedir:
            shutil.rmtree(tracedir, ignore_errors=True)
        served.free()
    gc.collect()

    # ---- correct: every answer against the plain reference
    t0 = time.perf_counter()
    refs = {}

    def reference_of(kind, lit):
        key = (kind, tuple(sorted(lit.items())))
        if key not in refs:
            refs[key] = gen.reference(kind, lit, data)
        return refs[key]

    if whole_run:
        judged = history + records + back
        verdict = gen.judge(judged, data, config, traffic)
    else:
        judged = records
        verdict = check.judge(records, reference_of,
                              float(config["correct"]["rel_err_max"]))
    check_s = time.perf_counter() - t0

    # ---- metrics
    all_window = [r for r in records if win["t0"] <= r[2] < win["t_end"]]
    ctx = {"records": all_window, "t_end": win["t_end"],
           "window_s": window_s, "setup_s": setup_s,
           "counters0": counters0, "counters1": counters1,
           "statements": len(all_window), "trace": red,
           "traced_statements": traced_statements,
           "traced_necessary_s": traced_bytes,
           "memory_peak_bytes": peak_bytes if on_chip else None}
    section, reader = (("per_layer", layer) if args.trace
                       else ("end_to_end", end_to_end))
    wanted = cells.metrics_for(bench, section, args.workload)
    metrics = {name: {"value": v, "unit": wanted[name]["unit"]}
               for name, v in reader.read_all(ctx, wanted).items()}

    dev_out = dict(device, memory_peak_bytes=int(peak_bytes))
    result = {"correct": bool(verdict["correct"]),
              "attempted": len(judged),
              "failed": verdict["compared"]["missing_answers"]["value"]
              + verdict["compared"]["wrong_answers"]["value"],
              "metrics": metrics, "device": dev_out}
    if red is not None:
        dev_out["busy_s"], dev_out["window_s"] = red["busy_s"], red["window_s"]
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
        log({"trace_per_kind": red["per_kind"],
             "traced_statements": traced_statements})
    result["window"] = {"seconds": window_s, "statements": len(all_window),
                        "setup": setup, "check_s": check_s,
                        "compiles": counters1["xla.compiles"]
                        - counters0["xla.compiles"],
                        "by_kind": end_to_end.by_kind(all_window)}
    if back:
        result["readback"] = {"statements": len(back),
                              **verdict.get("transactions", {})}
    if verdict.get("errors"):
        result["errors"] = verdict["errors"]
    result["compared"] = verdict["compared"]
    if verdict["first_bad"]:
        log({"first_bad": verdict["first_bad"],
             "errors": verdict.get("errors")})
    for name, c in verdict["compared"].items():
        print(f"compared {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result, default=float), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
