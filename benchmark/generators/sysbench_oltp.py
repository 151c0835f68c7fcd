"""sysbench 1.0 `oltp_read_only.lua` for the benchmark (numpy and the standard
library only).

Tables, value shapes and the data for a seed are `sysbench.py`'s
(`oltp_common.lua`). The statements are `oltp_common.lua`'s, letter for
letter, and one `Stream` is one sysbench thread running the script's
`event()` with `skip_trx` off:

  BEGIN
  point_selects (10) x  SELECT c FROM sbtest<t> WHERE id=<id>
  SELECT c FROM sbtest<t> WHERE id BETWEEN <a> AND <a+99>
  SELECT SUM(k) FROM sbtest<t> WHERE id BETWEEN <a> AND <a+99>
  SELECT c FROM sbtest<t> WHERE id BETWEEN <a> AND <a+99> ORDER BY c
  SELECT DISTINCT c FROM sbtest<t> WHERE id BETWEEN <a> AND <a+99> ORDER BY c
  COMMIT

with one table drawn for the point selects and one for each range group
(`get_table_num()` once a group), every id uniform in [1, table_size]
(`rand-type=uniform`); a range past `table_size` is shorter. Every wire
statement is a record, `BEGIN` and `COMMIT` too (sysbench's `queries`).

The plain reference answers from the generated arrays alone; an OK packet's
reference is its affected-row count, 0.
"""

from __future__ import annotations

import numpy as np

from .sysbench import (as_strings, ddl, generate, row_counts,  # noqa: F401
                       table_names)

RANGES = ("simple_range", "sum_range", "order_range", "distinct_range")
SELECTS = ("point_select",) + RANGES

_BETWEEN = " FROM {table} WHERE id BETWEEN {id} AND {id_end}"
TEXT = {
    "begin": "BEGIN",
    "commit": "COMMIT",
    "point_select": "SELECT c FROM {table} WHERE id={id}",
    "simple_range": "SELECT c" + _BETWEEN,
    "sum_range": "SELECT SUM(k)" + _BETWEEN,
    "order_range": "SELECT c" + _BETWEEN + " ORDER BY c",
    "distinct_range": "SELECT DISTINCT c" + _BETWEEN + " ORDER BY c",
}


def render(kind: str, lit: dict) -> str:
    return TEXT[kind].format(**lit)


def reference_columns(config: dict) -> dict:
    return {name: ("k", "c") for name in table_names(config)}


def pools(traffic: dict, config: dict, seed: int) -> dict:
    """No fixed pool: tables and ids are uniform over all of them."""
    return {}


class Stream:
    """One sysbench thread. `next(only)` gives the thread's next wire
    statement; under `only=<kind>` (a traced sub-window) its transactions
    hold that kind's group alone, still between BEGIN and COMMIT. A
    transaction left open by a window of another mix is finished first: its
    COMMIT is the next statement."""

    def __init__(self, traffic, config, seed, client, pools_):
        self.traffic = traffic
        self.tables = table_names(config)
        self.n = int(config["table_size"])
        self.width = int(traffic["range_size"])
        self.rng = np.random.default_rng([seed, 0x52, client])
        self.todo: list = []    # what is left of the open transaction
        self.open = False       # a BEGIN was sent and its COMMIT was not
        self.mix = None         # the `only` the open transaction was made for

    def _transaction(self, only):
        out = [("begin", {})]
        for kind in ((only,) if only else SELECTS):
            table = self.tables[int(self.rng.integers(len(self.tables)))]
            for _ in range(int(self.traffic[kind + "s"])):  # the script's
                i = int(self.rng.integers(1, self.n + 1))
                lit = {"table": table, "id": i}
                if kind != "point_select":
                    lit["id_end"] = i + self.width - 1
                out.append((kind, lit))
        out.append(("commit", {}))
        return out

    def next(self, only: str | None = None):
        if self.open and only != self.mix:
            self.todo = [("commit", {})]
        if not self.todo:
            self.todo = self._transaction(only)
            self.mix = only
        kind, lit = self.todo.pop(0)
        self.open = kind != "commit"
        return kind, lit, render(kind, lit)


def warmup(traffic, config, pools_) -> list:
    """Every kind on every table inside a transaction, `warm_passes` times
    over: each table's statement is its own digest, and inside a transaction
    its own program. (The load generator's `warm_repeat` sends a statement
    again at once, and a BEGIN inside a transaction is an error: the passes
    are whole transactions.) The range starts low enough to hold all
    `range_size` ids."""
    n = int(config["table_size"])
    width = int(traffic["range_size"])
    out = []
    for j, table in enumerate(table_names(config)):
        i = 1 + (7919 * j) % max(1, n - width)
        lit = {"table": table, "id": i, "id_end": i + width - 1}
        out.append(("begin", {}))
        out += [(k, dict(lit) if k != "point_select"
                 else {"table": table, "id": i}) for k in traffic["kinds"]]
        out.append(("commit", {}))
    return [(k, lit, render(k, lit)) for k, lit in out
            ] * int(traffic.get("warm_passes", 1))


def reference(kind: str, lit: dict, data: dict, stale: int = 0):
    """What the statement must answer. `stale` is the control's fault: the
    neighbouring id's row, a range shifted by one, as a read at another
    snapshot or a misrouted one would answer."""
    if kind in ("begin", "commit"):
        return 0
    cols = data[lit["table"]]
    n = len(cols["c"])
    if kind == "point_select":
        return [(cols["c"][(int(lit["id"]) - 1 + stale) % n].decode(),)]
    lo = min(n, max(0, int(lit["id"]) - 1 + stale))
    hi = min(n, max(0, int(lit["id_end"]) + stale))
    if kind == "sum_range":
        if hi <= lo:
            return [(None,)]
        return [(sum(cols["k"][lo:hi].tolist()),)]  # Python's integers
    c = cols["c"][lo:hi].tolist()  # bytes, in id order
    if kind == "order_range":
        c = sorted(c)
    elif kind == "distinct_range":
        c = sorted(set(c))
    elif kind != "simple_range":
        raise KeyError(kind)
    return [(v.decode(),) for v in c]


REFERENCED_COLUMNS = {k: {} for k in SELECTS}
