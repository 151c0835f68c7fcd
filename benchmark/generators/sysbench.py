"""sysbench 1.0 OLTP tables and statements for the benchmark (numpy only).

Schema and value shapes of `oltp_common.lua`: `sbtest<i>(id PK, k indexed,
c CHAR(120), pad CHAR(60))`, `c` ten groups of eleven digits joined by '-',
`pad` five such groups, `k` uniform in [1, table_size]. Rows are made from
the seed; the reference answers a statement by looking the row up in the
generated arrays.
"""

from __future__ import annotations

import numpy as np


def _digit_groups(rng, n: int, groups: int) -> np.ndarray:
    """n strings of `groups` 11-digit groups joined by '-', as bytes."""
    width = groups * 12 - 1
    a = np.full((n, width), ord("-"), dtype=np.uint8)
    digits = rng.integers(ord("0"), ord("9") + 1, (n, groups * 11),
                          dtype=np.uint8)
    for g in range(groups):
        a[:, g * 12:g * 12 + 11] = digits[:, g * 11:(g + 1) * 11]
    return a.view(f"S{width}").ravel()


def table_names(config: dict) -> list[str]:
    return [f"sbtest{i}" for i in range(1, int(config["tables"]) + 1)]


def generate(config: dict, seed: int) -> dict:
    n = int(config["table_size"])
    out = {}
    for i, name in enumerate(table_names(config)):
        rng = np.random.default_rng([seed, 0x5B, i])
        out[name] = {"id": np.arange(1, n + 1, dtype=np.int64),
                     "k": rng.integers(1, n + 1, n),
                     "c": _digit_groups(rng, n, 10),
                     "pad": _digit_groups(rng, n, 5)}
    return out


def ddl(config: dict) -> list[tuple[str, list[str]]]:
    out = []
    for name in table_names(config):
        i = name[len("sbtest"):]
        out.append((name, [
            f"create table {name} (id int not null, k int not null, "
            f"c char(120) not null, pad char(60) not null, primary key (id))",
            f"create index k_{i} on {name} (k)"]))
    return out


def as_strings(col):
    """A column as the loader takes it: bytes as text."""
    if getattr(col, "dtype", None) is not None and col.dtype.kind == "S":
        return col.astype(f"U{col.dtype.itemsize}")
    return col


def row_counts(data: dict) -> dict:
    return {name: len(cols["id"]) for name, cols in data.items()}


def reference_columns(config: dict) -> dict:
    return {name: ("c",) for name in table_names(config)}


TEXT = {"point_select": "SELECT c FROM {table} WHERE id={id}"}


def render(kind: str, lit: dict) -> str:
    return TEXT[kind].format(**lit)


def pools(traffic: dict, config: dict, seed: int) -> dict:
    """No fixed pool: keys are uniform over every table and id."""
    return {}


class Stream:
    """One sysbench thread: table and id uniform (rand-type=uniform)."""

    def __init__(self, traffic, config, seed, client, pools_):
        self.kinds = list(traffic["kinds"])
        self.tables = table_names(config)
        self.n = int(config["table_size"])
        self.rng = np.random.default_rng([seed, 0x51, client])
        self.i = 0

    def next(self, only: str | None = None):
        kind = only or self.kinds[self.i % len(self.kinds)]
        self.i += 1
        lit = {"table": self.tables[int(self.rng.integers(len(self.tables)))],
               "id": int(self.rng.integers(1, self.n + 1))}
        return kind, lit, render(kind, lit)


def warmup(traffic, config, pools_) -> list:
    """Every table once per kind: each table's statement is its own digest."""
    n = int(config["table_size"])
    lits = [{"table": t, "id": 1 + (7919 * j) % n}
            for j, t in enumerate(table_names(config))]
    return [(k, lit, render(k, lit)) for k in traffic["kinds"] for lit in lits]


def reference(kind: str, lit: dict, data: dict, acc=None, stale: int = 0):
    """The row the statement must return. `stale` is the control's fault: the
    row of another id, as a stale or misrouted read would answer."""
    if kind != "point_select":
        raise KeyError(kind)
    col = data[lit["table"]]["c"]
    return [(col[(int(lit["id"]) - 1 + stale) % len(col)].decode(),)]


REFERENCED_COLUMNS = {"point_select": {}}
