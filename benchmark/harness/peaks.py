"""The table of peaks, keyed by `device_kind`. An unknown kind is an error."""

from __future__ import annotations

import json
import os


def peaks(device_kind: str) -> dict:
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"no peaks for device kind {device_kind!r}: add it to "
                       "benchmark/harness/peaks.json with its source")
    return table[device_kind]


def necessary_bytes(referenced: dict, rows: dict, widths: dict) -> int:
    """The bytes a statement cannot avoid reading: each referenced column
    once, at the narrowest fixed width that holds its specified domain
    (`widths`: column -> bytes). A function of row counts and referenced
    columns only, never of the program's layout."""
    return sum(int(rows[table]) * int(widths[col]["bytes"])
               for table, cols in referenced.items() for col in cols)
