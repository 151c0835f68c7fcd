"""Per-layer metrics, found by listing `benchmark/layer_metrics/`.

A metric is `<name>.json` (declarative) or `<name>.py` with
`read(ctx) -> float | None`. The declarative sources:

  counters        sum of the deltas of `num` (less `minus`) over the counted
                  window, divided by the deltas of `den` (a list of counters,
                  "statements": those the clients completed, or "window_s":
                  the window's seconds), times `scale`
  trace           `num` / `den` over the traced sub-windows, of: window_s,
                  busy_s, idle_s, launches, statements, necessary_s (the
                  statements' necessary bytes over the chip's peak bytes/s);
                  `kinds` restricts all of them to those statement kinds
  device_memory   peak bytes in use on the fullest chip, times `scale`

A reader that finds nothing to read (no trace, a zero denominator, a counter
that does not exist) returns None and the metric is left out of the line.
"""

from __future__ import annotations

import importlib.util
import json
import os

from . import trace as T

DIR = os.path.join(os.path.dirname(os.path.dirname(__file__)), "layer_metrics")


def _delta(ctx, names):
    c0, c1 = ctx["counters0"], ctx["counters1"]
    if not any(n in c1 for n in names):
        return None
    return sum(c1.get(n, 0.0) - c0.get(n, 0.0) for n in names)


def _trace_quantity(ctx, name, kinds):
    red = ctx.get("trace")
    if red is None:
        return None
    if name in ("statements", "necessary_s"):
        table = ctx["traced_" + name]
        return sum(v for k, v in table.items() if kinds is None or k in kinds)
    return T.quantity(red, name, kinds)


def evaluate(spec: dict, ctx: dict):
    src = spec["source"]
    scale = float(spec.get("scale", 1.0))
    if src == "counters":
        num = _delta(ctx, spec["num"])
        if num is None:
            return None
        if spec.get("minus"):
            num -= _delta(ctx, spec["minus"]) or 0.0
        den = spec.get("den")
        if den is None:
            return num * scale
        d = (ctx.get(den) if den in ("statements", "window_s")
             else _delta(ctx, den))
        return num / d * scale if d else None
    if src == "trace":
        kinds = spec.get("kinds")
        num = _trace_quantity(ctx, spec["num"], kinds)
        den = _trace_quantity(ctx, spec["den"], kinds)
        return num / den * scale if num is not None and den else None
    if src == "device_memory":
        peak = ctx.get("memory_peak_bytes")
        return peak * scale if peak else None
    raise ValueError(f"unknown layer-metric source {src!r}")


def read_all(ctx: dict, wanted) -> dict:
    """{name: value} for every metric file whose name is in `wanted`."""
    out = {}
    for fn in sorted(os.listdir(DIR)):
        name, ext = os.path.splitext(fn)
        if name not in wanted or ext not in (".json", ".py"):
            continue
        path = os.path.join(DIR, fn)
        if ext == ".json":
            with open(path) as f:
                v = evaluate(json.load(f), ctx)
        else:
            spec = importlib.util.spec_from_file_location(name, path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            v = mod.read(ctx)
        if v is not None:
            out[name] = float(v)
    return out
