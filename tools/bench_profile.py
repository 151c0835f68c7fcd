#!/usr/bin/env python3
"""One traced benchmark run, read in the program's own names.

    python tools/bench_profile.py [--keep DIR] -- --workload <cell> --seed <n> \
        --seconds <s> --trace 1

runs `benchmark/run.py` with the arguments after `--`, unchanged, and logs to
stderr beside its own lines:

  named_breakdown   the profile reduced by `benchmark/harness/spans.py`: device
                    seconds by `kind:Node#nid[/expr]/primitive`, idle-gap
                    seconds by `kind:ob:<phase>`, the six trace-derived
                    per-layer numbers of ISSUE 26 and
                    `exchange_device_ms_per_stmt` (device time whose
                    innermost scope is `Exchange:*`, ISSUE 28), the eager
                    modules
  named_counters    thread-CPU ms per statement and the front end's pool
                    hand-off wait per statement over the measured window;
                    the statement thread's off-CPU ms per statement (its
                    runnable wall less its CPU: the interpreter-lock wait)
                    and the `catalog refresh ...` / `h2d ...` counters'
                    deltas (PR 37);
                    the `dict lookup <lowering>`, `clustered agg bounds
                    <shared|gathered>`, `group keys dependent` and `merge
                    join scan-carried` counters since the start;
                    the `result frames prefetched` / `result frames lazy`
                    counters' deltas over the window;
                    on a PX deployment the `px ...` counters' deltas over
                    the window (`px exchange rows` over `px exchange slots`
                    is `exchange_occupancy_pct`), the mesh's devices and
                    each one's peak and row-sharded bytes
  slow_statements   the window's three longest statements by the audit ring,
                    with their phases and the garbage collections of 20 ms
                    and more that overlap them
  device_wait_split per kind, the mean `ob:device wait` leaf of the traced
                    sub-windows in its parts: ms before the program's first
                    op, ms with an op running, ms between ops, ms after its
                    last op (the tail: the result frame crossing the link),
                    and the kind's whole idle ms per statement beside them
  ledger_vs_leaves  per phase, the seconds of the `ob:` leaves inside the
                    traced sub-windows beside the host-tax registry's delta
                    from `start_trace` to `stop_trace`

`--keep DIR` also keeps the profile (`DIR/trace.xplane.pb.gz`).

Why a wrapper: a PR that is not a `benchmark` PR may not edit `run.py` or
`harness/server.py`, and `run.py` removes the profile before a metric file
could read it. The two hooks below are the two calls PERF.md section 7 asks
a `benchmark` PR to make in those files; until then this is how section 5
is written. Only the process that holds the chip can trace it, so it is one
process with the run.
"""

from __future__ import annotations

import bisect
import gzip
import json
import os
import runpy
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def log(obj) -> None:
    print(json.dumps(obj, default=float), file=sys.stderr, flush=True)


WAIT_PHASE = "device wait"
# host-tax phases in which the statement's thread does not run on a CPU:
# the device, the wire, and the parked phases (gap_ledger.BLOCKED_PHASES)
OFF_THREAD = ("device wait", "d2h", "wire read", "admission queue",
              "wire write", "tenant permit", "batch window",
              "governor reserve", "retry backoff")
SPAN_COUNTERS = ("catalog refreshes", "catalog refresh rows",
                 "h2d uploads", "h2d bytes")


def wait_split(ev: dict, statements: dict, idle_s: dict) -> dict:
    """Per window kind, the mean `ob:device wait` leaf in milliseconds:
    before the first device op that overlaps it, with an op running,
    between ops, after the last op (mean over the chips that ran ops), the
    leaves no op overlapped, and the kind's whole idle time per statement
    (`trace.py` gives a whole gap to the phase that overlaps it most, so
    that number also holds the host path between two statements).

    The host's leaves and the device's ops are on one clock only to within
    some 0.5 ms, another offset in every process, and the before and after
    parts carry it. `around_program_ms` does not: the start of the
    statement's `device dispatch` leaf to the end of its wait leaf (host
    clock) less `program_ms`, the statement programs' own span (device
    clock): launch and tail together."""
    from benchmark.harness import spans

    _merge = spans.T._merge
    by_dev, programs = {}, {}
    for op in ev["ops"]:
        by_dev.setdefault(op[0], []).append((op[1], op[1] + op[2]))
    for dev, name, s, d in ev["modules"]:
        if name.startswith(spans.PROGRAM_PREFIX):
            programs.setdefault(dev, []).append((s, s + d))
    for ivs in by_dev.values():
        ivs.sort()
    starts = {dev: [iv[0] for iv in ivs] for dev, ivs in by_dev.items()}
    longest = max((e - s for ivs in by_dev.values() for s, e in ivs),
                  default=0)
    dispatched = {}  # thread -> starts of its `device dispatch` leaves
    for t, ph, s, _d, _stmt in ev["phases"]:
        if ph == "device dispatch":
            dispatched.setdefault(t, []).append(s)
    for starts_t in dispatched.values():
        starts_t.sort()
    parts_of = ("before_first_op_ms", "busy_ms", "between_ops_ms",
                "after_last_op_ms")
    out = {}
    for kind, w0, w1 in ev["windows"]:
        k = out.setdefault(kind, dict.fromkeys(
            ("leaves", "no_op_leaves", "programs_seen"), 0) | dict.fromkeys(
            parts_of + ("leaf_ms", "program_ms", "dispatch_to_wait_end_ms"),
            0.0))
        for t, ph, s, d, _stmt in ev["phases"]:
            if ph != WAIT_PHASE or s < w0 or s + d > w1:
                continue
            e = s + d
            k["leaves"] += 1
            k["leaf_ms"] += d / 1e6
            parts = []
            for dev, ivs in by_dev.items():
                i = bisect.bisect_left(starts[dev], s - longest)
                hit = []
                while i < len(ivs) and ivs[i][0] < e:
                    if ivs[i][1] > s:
                        hit.append((max(ivs[i][0], s), min(ivs[i][1], e)))
                    i += 1
                if hit:
                    merged = _merge(hit)
                    busy = sum(b - a for a, b in merged)
                    before, after = merged[0][0] - s, e - merged[-1][1]
                    parts.append((before, busy, d - before - busy - after,
                                  after))
            if not parts:
                k["no_op_leaves"] += 1
                continue
            for name, col in zip(parts_of, zip(*parts)):
                k[name] += sum(col) / len(col) / 1e6
            # the statement programs that overlap the leaf, first start to
            # last end on each chip, and the host's span around them
            spans_ = [(min(a for a, _b in mine), max(b for _a, b in mine))
                      for mine in ([p for p in ps if p[0] < e and p[1] > s]
                                   for ps in programs.values()) if mine]
            i = bisect.bisect_right(dispatched.get(t, []), s)
            if spans_ and i:
                k["programs_seen"] += 1
                k["program_ms"] += sum(
                    b - a for a, b in spans_) / len(spans_) / 1e6
                k["dispatch_to_wait_end_ms"] += (
                    e - dispatched[t][i - 1]) / 1e6
    for kind, k in out.items():
        with_ops = k["leaves"] - k["no_op_leaves"]
        for name, n in ([("leaf_ms", k["leaves"])]
                        + [(name, with_ops) for name in parts_of]
                        + [(name, k["programs_seen"]) for name in
                           ("program_ms", "dispatch_to_wait_end_ms")]):
            if n:
                k[name] /= n
        k["around_program_ms"] = (
            k["dispatch_to_wait_end_ms"] - k["program_ms"]
            if k["programs_seen"] else None)
        k["idle_ms_per_stmt"] = (
            sum(idle_s.get(kind, {}).values()) / statements[kind] * 1e3
            if statements.get(kind) else None)
    return out


def main(argv) -> int:
    if "--" not in argv:
        print(__doc__, file=sys.stderr)
        return 2
    cut = argv.index("--")
    mine, theirs = argv[:cut], argv[cut + 1:]
    keep = mine[mine.index("--keep") + 1] if "--keep" in mine else None

    from benchmark.harness import server, spans
    from chip_smoke import row_sharded_bytes_per_device
    from benchmark.harness import trace as T

    read_events = T.read_events

    def read_and_name(logdir):
        path = spans.profile_path(logdir)
        ev = spans.read_spans(path)
        # seconds are the mean over the chips that ran ops, as trace.py's
        red = spans.reduce_spans(ev, len({op[0] for op in ev["ops"]}) or 1)
        # statements completed in the sub-windows: by their last leaf,
        # or (a program that writes none) one program launch each
        statements = {k: v["completed"] or v["programs"]
                      for k, v in red["per_kind"].items()}
        q = spans.quantities(red)
        leaves = q["phase_s"]
        total = sum(statements.values())
        exchange_s = {k: v for k, v in q["scope_s"].items()
                      if k.startswith("Exchange:")}
        metrics = spans.metrics(red, total)
        metrics["exchange_device_ms_per_stmt"] = (
            sum(exchange_s.values()) / total * 1000.0
            if exchange_s and total else None)
        if len(traced) == 2:
            log({"ledger_vs_leaves": {
                ph: [leaves.get(ph), traced[1].get(ph, 0.0)
                     - traced[0].get(ph, 0.0)]
                for ph in sorted(set(leaves) | set(traced[1]))}})
        log({"device_wait_split": wait_split(
            ev, statements,
            {k: v["idle_phase_s"] for k, v in red["per_kind"].items()})})
        log({"named_breakdown": red["named_breakdown"],
             "scope_s": {k: v["scope_s"] for k, v in red["per_kind"].items()},
             "idle_phase_s": {k: v["idle_phase_s"]
                              for k, v in red["per_kind"].items()},
             "launches": {k: {"programs": v["programs"], "eager": v["eager"]}
                          for k, v in red["per_kind"].items()},
             "eager_modules": red["eager_modules"],
             "phase_events": red["phase_events"],
             "phase_overlaps": red["phase_overlaps"],
             "statements": statements,
             "metrics": metrics,
             "xplane_bytes": os.path.getsize(path)})
        if keep:
            os.makedirs(keep, exist_ok=True)
            with open(path, "rb") as src, gzip.open(os.path.join(
                    keep, "trace.xplane.pb.gz"), "wb") as dst:
                shutil.copyfileobj(src, dst)
        return read_events(logdir)

    T.read_events = read_and_name

    counters = server.Served.counters
    seen = []
    served = []   # the one Served of the run, once it has counted
    traced = []   # the registry's phase seconds at start_trace, stop_trace

    def phase_seconds():
        out = {}
        for a in served[0].db.host_tax.snapshot()["digests"].values():
            for ph, v in a["phases"].items():
                out[ph] = out.get(ph, 0.0) + v
        return out

    import jax

    start_trace, stop_trace = jax.profiler.start_trace, jax.profiler.stop_trace

    def start_and_note(*a, **kw):
        traced.append(phase_seconds())
        return start_trace(*a, **kw)

    def stop_and_note(*a, **kw):
        stop_trace(*a, **kw)
        traced.append(phase_seconds())

    jax.profiler.start_trace = start_and_note
    jax.profiler.stop_trace = stop_and_note

    def px_state(db):
        """The PX route's counters, and where its rows live (None on a
        deployment whose statements run on one chip)."""
        px = db._px_executor_obj
        if px is None:
            return None
        return {"counters": {k: v for k, v in
                             db.metrics.counters_snapshot().items()
                             if k.startswith("px ")},
                "mesh_devices": [str(d) for d in px.mesh.devices.flat],
                "row_sharded_bytes": row_sharded_bytes_per_device(),
                "peak_bytes_in_use": {
                    str(d): (d.memory_stats() or {}).get("peak_bytes_in_use")
                    for d in px.mesh.devices.flat}}

    # garbage collections of 20 ms and more, on the wall clock the audit
    # ring stamps statements with: (generation, start, seconds)
    import gc
    import time

    gc_pauses, gc_open = [], {}

    def note_gc(phase, info):
        if phase == "start":
            gc_open[info["generation"]] = time.time()
        else:
            t0 = gc_open.pop(info["generation"], None)
            if t0 is not None and time.time() - t0 >= 0.02:
                gc_pauses.append((info["generation"], t0, time.time() - t0))

    gc.callbacks.append(note_gc)

    def slow_statements(db, t0, t1):
        """The window's three longest statements by the server's own clock
        (the audit ring), each with the phases the record carries and the
        collections that overlap it: what a statement that runs 200 ms over
        its kind was doing (PERF.md section 7)."""
        recs = [r for r in list(db.audit._ring)
                if t0 <= r.ts <= t1 and r.stmt_type == "Select"]
        out = []
        for r in sorted(recs, key=lambda r: r.elapsed_s)[-3:]:
            begin = r.ts - r.elapsed_s
            out.append({
                "sql": r.sql[:40], "begin": begin - t0,
                "elapsed_ms": r.elapsed_s * 1e3,
                "compile_ms": r.compile_s * 1e3,
                "dispatch_ms": r.dispatch_us / 1e3,
                "fetch_ms": r.fetch_us / 1e3,
                "chip_idle_ms": r.chip_idle_us / 1e3,
                "unattributed_ms": r.unattributed_us / 1e3,
                "retry_cnt": r.retry_cnt,
                "gc": [{"generation": g, "at": at - t0, "ms": d * 1e3}
                       for g, at, d in gc_pauses
                       if at < r.ts and at + d > begin]})
        return {"statements": len(recs), "slowest": out,
                "gc_pauses_in_window": [
                    {"generation": g, "at": at - t0, "ms": d * 1e3}
                    for g, at, d in gc_pauses if t0 <= at <= t1]}

    def counters_and_mine(self):
        db = self.db
        served[:] = [self]
        tax = db.host_tax.snapshot()["digests"]
        w = db.metrics.wait_event("front pool queue")
        seen.append({
            "at": time.time(),
            "px": px_state(db),
            "statements": sum(a["count"] for a in tax.values()),
            "cpu_s": sum(a.get("cpu_s", 0.0) for a in tax.values()),
            "has_cpu": any("cpu_s" in a for a in tax.values()),
            # wall the statement thread could have run on a CPU: e2e less
            # the device, the wire and the phases it is parked in by design
            "runnable_s": sum(a["e2e_s"] - sum(
                v for ph, v in a["phases"].items() if ph in OFF_THREAD)
                for a in tax.values()),
            "spans": {k: db.metrics.counter(k) for k in SPAN_COUNTERS},
            "pool_wait_s": w.total_s if w else None,
            "pool_waits": w.count if w else 0,
            "depth_sum": db.metrics.counter("front pool depth"),
            "frames": {k: db.metrics.counter(f"result frames {k}")
                       for k in ("prefetched", "lazy")}})
        if len(seen) == 2:  # the measured window: counters0, counters1
            a, b = seen
            n = b["statements"] - a["statements"]
            waits = b["pool_waits"] - a["pool_waits"]
            log({"named_counters": {
                "host_cpu_ms_per_stmt":
                    (b["cpu_s"] - a["cpu_s"]) / n * 1000.0
                    if b["has_cpu"] and n else None,
                # the interpreter-lock wait (PR 37, a reading): runnable
                # wall less thread CPU
                "host_offcpu_ms_per_stmt":
                    ((b["runnable_s"] - a["runnable_s"])
                     - (b["cpu_s"] - a["cpu_s"])) / n * 1000.0
                    if b["has_cpu"] and n else None,
                "spans": {k: b["spans"][k] - a["spans"][k]
                          for k in SPAN_COUNTERS},
                "pool_wait_ms_per_stmt":
                    (b["pool_wait_s"] - (a["pool_wait_s"] or 0.0))
                    / waits * 1000.0 if waits else None,
                "pool_depth_mean":
                    (b["depth_sum"] - a["depth_sum"]) / waits
                    if waits else None,
                "statements": n,
                "result_frames": {k: b["frames"][k] - a["frames"][k]
                                  for k in b["frames"]},
                # since process start: the lowerings are chosen when a
                # program is traced, which the warm-up does
                "dict_lookup": {
                    k: db.metrics.counter(f"dict lookup {k}")
                    for k in ("constant", "runs", "gather")},
                "clustered_agg_bounds": {
                    k: db.metrics.counter(f"clustered agg bounds {k}")
                    for k in ("shared", "gathered")},
                "group_keys_dependent":
                    db.metrics.counter("group keys dependent"),
                "merge_join_scan_carried":
                    db.metrics.counter("merge join scan-carried"),
                "result_frame_positions_searched":
                    db.metrics.counter("result frame positions searched")}})
            log({"slow_statements": slow_statements(db, a["at"], b["at"])})
            if b["px"] is not None:
                before = (a["px"] or {}).get("counters", {})
                moved = {k: v - before.get(k, 0)
                         for k, v in b["px"]["counters"].items()}
                slots = moved.get("px exchange slots")
                log({"named_px": dict(
                    b["px"], counters=moved,
                    # live rows the exchanges delivered over the rows
                    # they hold room for, all chips, the whole window
                    exchange_occupancy_pct=100.0 * moved.get(
                        "px exchange rows", 0) / slots if slots else None)})
        return counters(self)

    server.Served.counters = counters_and_mine

    sys.argv = [os.path.join(ROOT, "benchmark", "run.py")] + theirs
    runpy.run_path(sys.argv[0], run_name="__main__")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
