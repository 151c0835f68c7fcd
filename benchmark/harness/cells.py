"""A cell by its name: the `BENCHMARK.json` entry, its configuration and its
traffic mix, with `key=value,...` overrides (rehearsals and tests only).
`benchmark/waiting_cells.json` is laid over `BENCHMARK.json`: the cells that
are built and wait for a mend of the program, run by name and by the tests,
never by a check. It holds only what `BENCHMARK.json` does not, and
`BENCHMARK.json` wins on every name that both have."""

from __future__ import annotations

import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_bench() -> dict:
    """`BENCHMARK.json` with the waiting cells laid over it: their
    configurations, workloads and metrics where `BENCHMARK.json` has none of
    that name, and their names in the `workloads` of the accepted metrics
    they join. An accepted cell reads what it read: every waiting metric
    lists its cells."""
    bench = load_json(ROOT, "BENCHMARK.json")
    waiting = load_json(ROOT, "benchmark", "waiting_cells.json")
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        have = {e["name"] for e in bench[section]}
        bench[section] += [e for e in waiting.get(section, [])
                           if e["name"] not in have]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] += [c for c in waiting["joins"].get(m["name"], [])
                               if c not in m["workloads"]]
    return bench


def load_cell(name: str, overrides: str | None = None):
    """(bench, cell, config, traffic); KeyError for an unknown cell."""
    bench = load_bench()
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


def struck_by_its_fault(cell: dict, line: dict) -> bool:
    """A result line of a waiting cell in which nothing is wrong but the
    program's fault that the cell waits for (`waits_for.fault`, a regular
    expression): every failed statement failed with such a text, no answer is
    wrong and every other number is inside its limit. A cell of
    `BENCHMARK.json` waits for nothing."""
    fault = cell.get("waits_for", {}).get("fault")
    c, errors = line["compared"], line.get("errors", {})
    return bool(fault and errors
                and c["missing_answers"]["value"] == sum(errors.values())
                and all(re.search(fault, text) for text in errors)
                and all(v["value"] <= v["limit"] for k, v in c.items()
                        if k != "missing_answers"))
