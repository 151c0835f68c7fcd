"""A cell by its name: the `BENCHMARK.json` entry, its configuration and its
traffic mix, with `key=value,...` overrides (rehearsals and tests only)."""

from __future__ import annotations

import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name: str, overrides: str | None = None):
    """(bench, cell, config, traffic); KeyError for an unknown cell."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    config = load_json(ROOT, next(c["file"] for c in bench["configs"]
                                  if c["name"] == cell["config"]))
    traffic = load_json(ROOT, "benchmark", "traffic",
                        cell["traffic"] + ".json")
    for kv in filter(None, (overrides or "").split(",")):
        k, v = kv.split("=")
        (traffic if k in traffic else config)[k] = json.loads(v)
    return bench, cell, config, traffic


def metrics_for(bench: dict, section: str, cell_name: str) -> dict:
    """{name: entry} of the section's metrics that this cell reports."""
    return {m["name"]: m for m in bench[section]
            if cell_name in m.get("workloads", [cell_name])}
