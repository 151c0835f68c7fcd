"""sysbench 1.0 `oltp_read_write.lua` for the benchmark (numpy and the
standard library only).

Tables, value shapes and the data for a seed are `sysbench.py`'s
(`oltp_common.lua`); the SELECT statements and their references are
`sysbench_oltp.py`'s; both imported, nothing copied. One `Stream` is one
sysbench thread running the script's `event()` with its defaults, `skip_trx`
off, text protocol:

  BEGIN
  the 14 selects of oltp_read_only.lua (sysbench_oltp.py)
  UPDATE sbtest<t> SET k=k+1 WHERE id=<id>                  index_updates 1
  UPDATE sbtest<t> SET c='<c>' WHERE id=<id>                non_index_updates 1
  DELETE FROM sbtest<t> WHERE id=<id>                       delete_inserts 1
  INSERT INTO sbtest<t> (id, k, c, pad) VALUES (<id>, ...)  the same id
  COMMIT

20 wire statements a transaction, each a record; every literal set carries
the stream's transaction number (`trx`). The load generator tells the stream
how each statement ended (`done`): on an error the stream sends ROLLBACK and
starts a new transaction with newly drawn values, as sysbench restarts the
event on the errors it ignores.

The plain reference is a replay of the log of the whole run (`judge`): what a
SELECT must answer depends on which transactions committed before its
reader's BEGIN, and no seed fixes the order of 32 connections, so the
reference takes the order from the client's clock and allows either answer
where the clock cannot tell. After the windows every row a transaction wrote
is read back (`readback`).
"""

from __future__ import annotations

import itertools
import re

import numpy as np

from ..harness.check import compare_rows
from . import sysbench_oltp as ro
from .sysbench import (_digit_groups, as_strings, ddl, generate,  # noqa: F401
                       row_counts, table_names)

SELECTS = ro.SELECTS
WRITE_GROUPS = ("index_update", "non_index_update", "delete_insert")
GROUPS = SELECTS + WRITE_GROUPS
WRITES = ("index_update", "non_index_update", "delete", "insert")
CONTROL = ("begin", "commit", "rollback")

TEXT = dict(ro.TEXT, **{
    "rollback": "ROLLBACK",
    "index_update": "UPDATE {table} SET k=k+1 WHERE id={id}",
    "non_index_update": "UPDATE {table} SET c='{c}' WHERE id={id}",
    "delete": "DELETE FROM {table} WHERE id={id}",
    "insert": "INSERT INTO {table} (id, k, c, pad) VALUES "
              "({id}, {k}, '{c}', '{pad}')",
    "readback_row": "SELECT id, k, c, pad FROM {table} WHERE id={id}",
    "readback_table": "SELECT COUNT(*), SUM(k) FROM {table}",
})

# what sysbench restarts an event on (--mysql-ignore-errors=1213,1020,1205),
# and what this tree answers a write conflict with today
CONFLICT = re.compile(r"ERR (?:1213|1205|1020):|ERR 1064: WriteConflict")
MAX_CHOICES = 64            # snapshots tried for one SELECT; over: unjudged
RESTART_SHARE = 0.01        # of the run's transactions


def render(kind: str, lit: dict) -> str:
    return TEXT[kind].format(**lit)


def reference_columns(config: dict) -> dict:
    return {name: ("k", "c", "pad") for name in table_names(config)}


def pools(traffic: dict, config: dict, seed: int) -> dict:
    """No fixed pool; the warm-up draws its values from the seed."""
    return {"seed": seed}


def _row_values(rng, n: int) -> dict:
    """k, c and pad of a new row, in oltp_common.lua's shapes."""
    return {"k": int(rng.integers(1, n + 1)),
            "c": _digit_groups(rng, 1, 10)[0].decode(),
            "pad": _digit_groups(rng, 1, 5)[0].decode()}


def _group(kind: str, lit: dict, rng, n: int, width: int) -> list:
    """The wire statements of one execution of a group on `lit`'s id."""
    if kind in ro.RANGES:
        return [(kind, dict(lit, id_end=lit["id"] + width - 1))]
    if kind == "non_index_update":
        return [(kind, dict(lit, c=_row_values(rng, n)["c"]))]
    if kind == "delete_insert":
        return [("delete", lit), ("insert", dict(lit, **_row_values(rng, n)))]
    return [(kind, lit)]


class Stream(ro.Stream):
    """One sysbench thread. As `sysbench_oltp.Stream`, and: under
    `only=<group>` a transaction holds that group alone (a write group too);
    after `done()` saw an error the next statement is ROLLBACK and the one
    after it the BEGIN of a new transaction."""

    def __init__(self, traffic, config, seed, client, pools_):
        super().__init__(traffic, config, seed, client, pools_)
        self.rng = np.random.default_rng([seed, 0x54, client])
        self.trx = 0
        self.failed = False

    def _transaction(self, only):
        self.trx += 1
        tag = {"trx": self.trx}
        out = [("begin", dict(tag))]
        for kind in ((only,) if only else GROUPS):
            table = self.tables[int(self.rng.integers(len(self.tables)))]
            for _ in range(int(self.traffic[kind + "s"])):  # the script's
                lit = dict(tag, table=table,
                           id=int(self.rng.integers(1, self.n + 1)))
                out += _group(kind, lit, self.rng, self.n, self.width)
        out.append(("commit", dict(tag)))
        return out

    def next(self, only: str | None = None):
        if self.failed:
            self.failed, self.todo, self.open = False, [], False
            return "rollback", {"trx": self.trx}, TEXT["rollback"]
        if self.open and only != self.mix:
            self.todo = [("commit", {"trx": self.trx})]
        if not self.todo:
            self.todo = self._transaction(only)
            self.mix = only
        kind, lit = self.todo.pop(0)
        self.open = kind != "commit"
        return kind, lit, render(kind, lit)

    def done(self, kind: str, lit: dict, rows) -> None:
        """How the statement ended: rows, an OK packet's int, or the error
        text."""
        if isinstance(rows, str) and kind != "rollback":
            self.failed = True


def warmup(traffic, config, pools_) -> list:
    """`warm_passes` whole transactions over every group on every table, on
    one connection: each table's statement is its own digest, inside a
    transaction its own program, and after a committed write each is built
    again. Their transaction numbers are negative: the judge replays them
    with the rest."""
    n = int(config["table_size"])
    width = int(traffic["range_size"])
    out, trx = [], 0
    for p in range(int(traffic.get("warm_passes", 1))):
        for j, table in enumerate(table_names(config)):
            trx -= 1
            rng = np.random.default_rng([int(pools_["seed"]), 0x55, j, p])
            i = 1 + (7919 * j + 104729 * p) % max(1, n - width)
            out.append(("begin", {"trx": trx}))
            for m, kind in enumerate(traffic["kinds"]):
                lit = {"trx": trx, "table": table,
                       "id": i + (m if kind in WRITE_GROUPS else 0)}
                out += _group(kind, lit, rng, n, width)
            out.append(("commit", {"trx": trx}))
    return [(k, lit, render(k, lit)) for k, lit in out]


# ---- the plain reference: a replay of the log

class _Trx:
    """One transaction of the log: its records in the order sent."""

    def __init__(self):
        self.recs = []
        self.end = None     # its COMMIT or ROLLBACK record
        # Its snapshot, as far as its reads have shown it: the writers whose
        # COMMIT overlaps its BEGIN on the client's clock and that a read of
        # it has met (`met`), and the sets of them that the snapshot may
        # hold, each consistent with every answer so far (`holds`).
        self.met = []
        self.holds = [frozenset()]

    @property
    def committed(self) -> bool:
        return (self.end is not None and self.end[0] == "commit"
                and not isinstance(self.end[4], str))

    def close(self) -> None:
        if self.recs[-1][0] in ("commit", "rollback"):
            self.end = self.recs[-1]
        # BEGIN on the client's clock: the snapshot lies between these two
        self.bs, self.bd = self.recs[0][2:4]
        # when it had ended for certain (never, if it is still open)
        self.ended = self.end[3] if self.end is not None else float("inf")


def _apply(row, kind: str, lit: dict):
    """A row (k, c, pad) or None after one write statement."""
    if kind == "delete":
        return None
    if kind == "insert":
        return (int(lit["k"]), lit["c"], lit["pad"])
    if row is None:
        return None
    if kind == "index_update":
        return (row[0] + 1, row[1], row[2])
    return (row[0], lit["c"], row[2])


def _answer(kind: str, table: str, rows: list):
    """What a SELECT of `kind` answers over `rows` (those present, in id
    order), by `sysbench_oltp.reference` on just these rows."""
    rows = [r for r in rows if r is not None]
    if not rows:
        return [(None,)] if kind == "sum_range" else []
    cols = {"k": np.array([r[0] for r in rows], dtype=np.int64),
            "c": np.array([r[1].encode() for r in rows])}
    return ro.reference(kind, {"table": table, "id": 1, "id_end": len(rows)},
                        {table: cols})


class _Replay:
    def __init__(self, records, data, config):
        self.data = data
        self.n = int(config["table_size"])
        self.trxs = {}
        for r in sorted(records, key=lambda r: r[2]):
            if "trx" in r[1]:
                self.trxs.setdefault((r[5], r[1]["trx"]),
                                     _Trx()).recs.append(r)
        for t in self.trxs.values():
            t.close()
        self.numbers = dict.fromkeys(
            ("missing_answers", "wrong_answers", "unjudged_answers",
             "conflict_restarts"), 0)
        self.first_bad = None
        self.errors = {}    # error text -> how many statements
        self.writers = {}   # (table, id) -> [(trx, record)] that wrote it
        for t in self.trxs.values():
            for r in t.recs:
                if r[0] in WRITES and not isinstance(r[4], str):
                    self.writers.setdefault(
                        (r[1]["table"], r[1]["id"]), []).append((t, r))
        self._chains()

    def bad(self, number: str, r, why: str) -> None:
        self.numbers[number] += 1
        if isinstance(r[4], str):  # what failed, by its text less the SQL
            text = r[4].split(" <- ")[0][:160]
            self.errors[text] = self.errors.get(text, 0) + 1
        if self.first_bad is None:
            self.first_bad = (f"{number}: {why}: {r[0]} {r[1]} by client "
                              f"{r[5]}: {str(r[4])[:300]}")

    def base(self, key):
        cols = self.data[key[0]]
        i = key[1] - 1
        return (int(cols["k"][i]), cols["c"][i].decode(),
                cols["pad"][i].decode())

    def _chains(self) -> None:
        """Per (table, id) the versions in the order of their writers'
        COMMITs: [(row, writer)], the generated row first. Two committed
        writers of a key of which the later took its snapshot for certain
        before the earlier committed are a lost update."""
        self.chains = {}
        done = sorted((t for t in self.trxs.values() if t.committed),
                      key=lambda t: t.end[2])
        for t in done:
            touched = {}
            for r in t.recs:
                if r[0] in WRITES and not isinstance(r[4], str):
                    key = (r[1]["table"], r[1]["id"])
                    chain = self.chains.setdefault(key,
                                                   [(self.base(key), None)])
                    row = touched.get(key, chain[-1][0])
                    touched[key] = _apply(row, r[0], r[1])
            for key, row in touched.items():
                chain = self.chains[key]
                prev = chain[-1][1]
                if prev is not None and prev.end[2] > t.bd:
                    self.bad("wrong_answers", t.end,
                             f"lost update on {key}: both this transaction "
                             f"and client {prev.end[5]}'s {prev.end[1]} "
                             f"committed a write, overlapped")
                chain.append((row, t))

    def unsure(self, keys, bs: float, bd: float) -> list:
        """The committed writers of `keys` that a snapshot taken between
        `bs` and `bd` on the client's clock may hold or not: a writer whose
        COMMIT was acknowledged before `bs` is in it for certain, one whose
        COMMIT was sent after `bd` is not, for certain."""
        out = []
        for key in keys:
            for _row, w in self.chains[key][1:]:
                if not w.end[3] < bs and w.end[2] <= bd and w not in out:
                    out.append(w)
        return sorted(out, key=lambda w: w.end[2])

    def version(self, key, held, bs: float, bd: float):
        """The chain index of `key` in a snapshot that holds the writers
        acknowledged before `bs` and those of `held`; None where that is no
        snapshot: it would hold a later writer of the key without an
        earlier one."""
        shown, gap = 0, False
        for i, (_row, w) in enumerate(self.chains[key][1:], 1):
            if w.end[3] < bs or (w.end[2] <= bd and w in held):
                if gap:
                    return None
                shown = i
            else:
                gap = True
        return shown

    def select(self, t, at: int, r, bs: float, bd: float) -> None:
        """One SELECT against the snapshots its transaction may have taken;
        `t` is its transaction (None: a statement of its own), `at` its place
        there. A snapshot is a set of writer TRANSACTIONS, not a version per
        key: a commit that wrote two ids is seen in both or in neither, in
        one range read and across the reads of one transaction."""
        kind, lit, rows = r[0], r[1], r[4]
        table = lit["table"]
        if kind in ("point_select", "readback_row"):
            ids = [lit["id"]] if 1 <= lit["id"] <= self.n else []
        else:
            ids = list(range(max(1, lit["id"]),
                             min(self.n, lit["id_end"]) + 1))
        own = {}
        for q in (t.recs[:at] if t is not None else ()):
            if q[0] in WRITES and not isinstance(q[4], str):
                own.setdefault((q[1]["table"], q[1]["id"]), []).append(q)

        def seen(key, o=None):
            """The row of `key` at chain index `o` (None: as generated),
            with the transaction's own earlier writes on top."""
            row = self.base(key) if o is None else self.chains[key][o][0]
            for q in own.get(key, ()):
                row = _apply(row, q[0], q[1])
            return row

        chained = [(table, i) for i in ids if (table, i) in self.chains]
        met, holds = (t.met, t.holds) if t is not None else ([], [frozenset()])
        new = [w for w in self.unsure(chained, bs, bd) if w not in met]
        choices = len(holds) << len(new)
        if choices > MAX_CHOICES:
            self.numbers["unjudged_answers"] += 1
            return
        known = met + new
        fit = []
        for held in holds:
            for pick in itertools.product((False, True), repeat=len(new)):
                held_ = held | {w for w, p in zip(new, pick) if p}
                # a snapshot is a point in the order of the commits: with a
                # writer it holds every writer acknowledged before that
                # one's COMMIT was sent
                if any(a.end[3] < b.end[2] and a not in held_
                       for b in held_ for a in known):
                    continue
                at_ = {key: self.version(key, held_, bs, bd)
                       for key in chained}
                if None in at_.values():
                    continue
                ordered = [seen((table, i), at_.get((table, i)))
                           for i in ids]
                if kind == "readback_row":
                    ref = [(i, *row) for i, row in zip(ids, ordered)
                           if row is not None]
                else:
                    ref = _answer(kind, table, ordered)
                if not compare_rows(rows, ref)[0]:
                    fit.append(held_)
        if not fit:
            self.bad("wrong_answers", r,
                     f"no permitted snapshot gives this answer "
                     f"({choices} tried)")
        elif t is not None:
            # every later read of this transaction shows one of these
            t.met, t.holds = known, fit

    def conflict_has_cause(self, t, r) -> bool:
        """The log holds the cause of a write-conflict error: another
        transaction wrote the key, had done so before this statement was
        answered, and either had not ended when the statement was sent or
        committed after this transaction's BEGIN was sent."""
        lit = r[1]
        if "id" not in lit:
            keys = [k for k, ws in self.writers.items()
                    if any(w is t for w, _ in ws)]
        else:
            keys = [(lit["table"], lit["id"])]
        for key in keys:
            for w, q in self.writers.get(key, ()):
                if w is t or q[2] >= r[3]:
                    continue
                if w.ended > r[2] or (w.committed and w.end[3] > t.bs):
                    return True
        return False

    def run(self) -> None:
        for t in self.trxs.values():
            for at, r in enumerate(t.recs):
                kind, rows = r[0], r[4]
                if isinstance(rows, str):
                    if (kind != "rollback" and CONFLICT.search(rows)
                            and self.conflict_has_cause(t, r)):
                        self.numbers["conflict_restarts"] += 1
                    else:
                        self.bad("missing_answers", r, "failed")
                elif kind in CONTROL or kind in WRITES:
                    # the row exists at every committed state: an id's
                    # delete and insert share a transaction
                    if compare_rows(rows, 0 if kind in CONTROL else 1)[0]:
                        self.bad("wrong_answers", r, "affected rows")
                else:
                    self.select(t, at, r, t.bs, t.bd)

    def readback(self, records) -> None:
        """Every written id's row is its chain's last version, letter for
        letter; a table's COUNT(*) and SUM(k) are the generated ones plus
        the committed changes."""
        for r in records:
            kind, lit, rows = r[0], r[1], r[4]
            if "trx" in lit or kind not in ("readback_row", "readback_table"):
                continue
            if isinstance(rows, str):
                self.bad("missing_answers", r, "failed")
            elif kind == "readback_row":
                self.select(None, 0, r, r[2], r[3])
            else:
                table = lit["table"]
                count = self.n
                total = int(self.data[table]["k"].sum())
                for key, chain in self.chains.items():
                    if key[0] == table:
                        last = chain[-1][0]
                        count -= last is None
                        total += (last[0] if last else 0) - chain[0][0][0]
                if compare_rows(rows, [(count, total)])[0]:
                    self.bad("wrong_answers", r,
                             f"the table holds {(count, total)}")


def judge(records, data: dict, config: dict, traffic: dict) -> dict:
    """`check.judge`'s dictionary, from the log of the whole run: the
    warm-up, every window and the read-back."""
    rp = _Replay(records, data, config)
    rp.run()
    rp.readback(records)
    limits = {"missing_answers": 0, "wrong_answers": 0,
              "rel_err_max": float(config["correct"]["rel_err_max"]),
              "unjudged_answers": 0,
              # a count: under 100 transactions one restart is inside it
              "conflict_restarts": max(1.0, RESTART_SHARE * len(rp.trxs))}
    # rel_err_max stays 0.0: there are no decimals in these tables
    numbers = {k: {"value": rp.numbers.get(k, 0.0), "limit": limit}
               for k, limit in limits.items()}
    correct = len(records) > 0 and all(
        c["value"] <= c["limit"] for c in numbers.values())
    return {"correct": correct, "compared": numbers,
            "first_bad": rp.first_bad, "errors": rp.errors,
            "transactions": {
                "all": len(rp.trxs),
                "committed": sum(t.committed for t in rp.trxs.values()),
                "written_ids": len(rp.writers)}}


def readback(records) -> list:
    """The statements the admin connection sends once the windows are over:
    every (table, id) a write statement of the run named, whether its
    transaction committed or not, and each table's count and sum."""
    keys = sorted({(r[1]["table"], r[1]["id"]) for r in records
                   if r[0] in WRITES})
    out = [("readback_row", {"table": t, "id": i}) for t, i in keys]
    out += [("readback_table", {"table": t})
            for t in sorted({t for t, _ in keys})]
    return [(k, lit, render(k, lit)) for k, lit in out]


REFERENCED_COLUMNS = {k: {} for k in GROUPS}
