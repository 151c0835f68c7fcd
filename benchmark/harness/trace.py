"""From a profiler trace to numbers: the benchmark's own reduction.

Two steps. `read_events` flattens the `.xplane.pb` the JAX profiler wrote
into plain tuples; `reduce_events` turns those into busy/idle seconds,
launches and a breakdown. The second step is checked against a small trace
recorded on the chip (`recorded_trace.json`, `selfcheck.py`).

The program names nothing in its traces (every statement's programs are
`jit_run` / `jit_run_narrow`), so the names come from outside: the harness
wraps each sub-window of the traced run, in which the stream sends one
statement kind only, in a host annotation `benchwin:<kind>`, and the
reduction cuts the device timeline at those spans.
"""

from __future__ import annotations

import glob
import os
import re

WINDOW_PREFIX = "benchwin:"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


def read_events(logdir: str) -> dict:
    """{"windows": [(kind, start_ns, end_ns)], "ops": [(dev, name, start_ns,
    dur_ns)], "modules": [...], "host": [(name, start_ns, dur_ns)]}."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    data = ProfileData.from_file(paths[-1])
    out = {"windows": [], "ops": [], "modules": [], "host": []}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key is None:
                    continue
                dev = int(m.group(1))
                for ev in line.events:
                    out[key].append((dev, ev.name, int(ev.start_ns),
                                     int(ev.duration_ns)))
            elif plane.name.startswith("/host:"):
                for ev in line.events:
                    name = ev.name
                    if name.startswith(WINDOW_PREFIX):
                        out["windows"].append(
                            (name[len(WINDOW_PREFIX):], int(ev.start_ns),
                             int(ev.start_ns + ev.duration_ns)))
                    elif ev.duration_ns >= 20_000:
                        out["host"].append((name, int(ev.start_ns),
                                            int(ev.duration_ns)))
    return out


def category(op_name: str) -> str:
    """HLO category of a device op: `%fusion.123` -> `fusion`."""
    name = op_name.lstrip("%").split(" ")[0].split("(")[0]
    return re.sub(r"([._]\d+)+$", "", name) or "op"


def label(op_name: str) -> str:
    """A device op for the breakdown: `%fusion.7 = s32[4500480,4]{...}
    fusion(...), kind=kCustom` -> `fusion.7 s32[4500480,4] kCustom`."""
    m = re.match(r"%?(\S+) = \(?([a-z0-9]+\[[0-9,]*\])", op_name)
    if not m:
        return op_name.lstrip("%")[:60]
    kind = re.search(r"kind=(\w+)", op_name)
    return " ".join(filter(None, [m.group(1), m.group(2),
                                  kind.group(1) if kind else ""]))


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce_events(ev: dict, devices: int = 1) -> dict:
    """Busy and idle seconds per window kind, averaged over `devices`."""
    per_kind = {}
    op_s, gaps = {}, []
    total_window = total_busy = 0.0
    launches = 0
    host = sorted(ev["host"], key=lambda h: h[1])
    for kind, w0, w1 in ev["windows"]:
        k = per_kind.setdefault(kind, {"window_s": 0.0, "busy_s": 0.0,
                                       "launches": 0, "ops": {}})
        k["window_s"] += (w1 - w0) / 1e9
        total_window += (w1 - w0) / 1e9
        by_dev = {}
        for dev, name, s, d in ev["ops"]:
            s2, e2 = max(s, w0), min(s + d, w1)
            if e2 <= s2:
                continue
            by_dev.setdefault(dev, []).append((s2, e2))
            cat = category(name)
            sec = (e2 - s2) / 1e9 / devices
            k["ops"][cat] = k["ops"].get(cat, 0.0) + sec
            key = f"{kind}:{label(name)}"
            op_s[key] = op_s.get(key, 0.0) + sec
        for dev, ivs in by_dev.items():
            merged = _merge(ivs)
            busy = sum(e - s for s, e in merged) / 1e9 / devices
            k["busy_s"] += busy
            total_busy += busy
            edges = [w0] + [x for iv in merged for x in iv] + [w1]
            for g0, g1 in zip(edges[0::2], edges[1::2]):
                if g1 - g0 >= 100_000:
                    gaps.append((g0, g1, kind))
        n = sum(1 for dev, _n, s, d in ev["modules"] if w0 <= s < w1)
        k["launches"] += n / devices
        launches += n / devices
    # name the longest idle gaps by what the host was doing in them
    gaps.sort(key=lambda g: g[0] - g[1])
    gap_s = {}
    for g0, g1, kind in gaps[:200]:
        best, best_ov = "host:unnamed", 0
        for name, s, d in host:
            if s >= g1:
                break
            ov = min(s + d, g1) - max(s, g0)
            if ov > best_ov:
                best, best_ov = name, ov
        key = f"{kind}:{best}"[:80]
        gap_s[key] = gap_s.get(key, 0.0) + (g1 - g0) / 1e9

    def top(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:10]]

    return {"window_s": total_window, "busy_s": total_busy,
            "idle_s": total_window - total_busy, "launches": launches,
            "per_kind": per_kind, "device_ops": top(op_s),
            "idle_gaps": top(gap_s)}


def quantity(red: dict, name: str, kinds=None) -> float:
    """One of window_s, busy_s, idle_s, launches, over the given kinds."""
    if kinds is None:
        return red[name]
    ks = [red["per_kind"][k] for k in kinds if k in red["per_kind"]]
    if name == "idle_s":
        return sum(k["window_s"] - k["busy_s"] for k in ks)
    return sum(k[name] for k in ks)
