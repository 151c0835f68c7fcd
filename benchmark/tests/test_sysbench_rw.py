"""`sysbench-1m-rw.read_write`: the stream that is told how its statement
ended, the judge that replays the log of the whole run, the read-back, the
control and the planted faults.

Run with `python -m pytest benchmark/tests -q` (not part of the repo's
tier-1 suite) or through `python -m benchmark.selfcheck --rehearse`. The
judge on logs made by hand; whole runs of `run.py` on the CPU at `tables=1,
table_size=2000`, the served system over the wire.

  clean     a log in which a transaction commits three writes, one aborts on
            a conflict the log explains, readers begin before, during and
            after the commit, and the read-back finds what was committed:
            `correct`.
  faults    the same log with one thing wrong, each caught by the number
            named beside it (`LOG_FAULTS`).
  control   the read-back answered from the generated data, as if every
            write were lost: `wrong_answers` > 0.
  planted   a whole run with every second acknowledged COMMIT rolled back
            underneath, and one with every 23rd staged write refused as a
            write conflict that nothing caused: `correct` false.
  conflicts 8 streams on 50 rows: real conflicts, every stream goes on.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.generators import sysbench, sysbench_oltp  # noqa: E402
from benchmark.generators import sysbench_rw as gen  # noqa: E402
from benchmark.harness import cells  # noqa: E402

SEED = 2_147_483_659  # past 31 bits, as the driver's seeds are
CELL = "sysbench-1m-rw.read_write"
TINY = "tables=1,table_size=2000,warm_window_s=1"
_, WORKLOAD, CONFIG, TRAFFIC = cells.load_cell(CELL, TINY)
SHAPE = (["begin"] + ["point_select"] * 10 + list(sysbench_oltp.RANGES)
         + ["index_update", "non_index_update", "delete", "insert", "commit"])
T = "sbtest1"
NEW_C = "-".join(["12345678901"] * 10)
NEW_PAD = "-".join(["10987654321"] * 5)
ERR = ("WireError: ERR 1064: WriteConflict: key (5,) modified at 540000 > "
       "snapshot 490000 <- 'UPDATE'")


@pytest.fixture(scope="module")
def data():
    d = gen.generate(CONFIG, SEED)
    return {t: {c: cols[c] for c in gen.reference_columns(CONFIG)[t]}
            for t, cols in d.items()}


# ---- the stream

def test_stream_has_the_scripts_shape_and_numbers_its_transactions():
    a = gen.Stream(TRAFFIC, CONFIG, SEED, 3, {})
    b = gen.Stream(TRAFFIC, CONFIG, SEED, 3, {})
    sent = [a.next() for _ in range(20 * 30)]
    assert sent == [b.next() for _ in range(20 * 30)]
    assert sent != [gen.Stream(TRAFFIC, CONFIG, SEED, 4, {}).next()
                    for _ in range(20 * 30)]
    n = int(CONFIG["table_size"])
    for t in range(30):
        trx = sent[20 * t:20 * t + 20]
        assert [s[0] for s in trx] == SHAPE
        assert all(s[1]["trx"] == t + 1 for s in trx)
        for kind, lit, text in trx:
            assert text == gen.TEXT[kind].format(**lit)
        dele, ins = trx[17][1], trx[18][1]
        assert dele["id"] == ins["id"] and 1 <= ins["k"] <= n
        assert [len(g) for g in ins["c"].split("-")] == [11] * 10
        assert [len(g) for g in ins["pad"].split("-")] == [11] * 5
        assert [len(g) for g in trx[16][1]["c"].split("-")] == [11] * 10
    assert gen.TEXT["index_update"] == "UPDATE {table} SET k=k+1 WHERE id={id}"
    assert gen.TEXT["non_index_update"] == (
        "UPDATE {table} SET c='{c}' WHERE id={id}")
    assert gen.TEXT["delete"] == "DELETE FROM {table} WHERE id={id}"
    assert gen.TEXT["insert"] == ("INSERT INTO {table} (id, k, c, pad) VALUES "
                                  "({id}, {k}, '{c}', '{pad}')")
    assert {k: gen.TEXT[k] for k in sysbench_oltp.TEXT} == sysbench_oltp.TEXT


@pytest.mark.parametrize("group", gen.WRITE_GROUPS)
def test_only_a_write_group_stays_inside_begin_and_commit(group):
    s = gen.Stream(TRAFFIC, CONFIG, SEED, 0, {})
    body = ["delete", "insert"] if group == "delete_insert" else [group]
    kinds = [s.next(group)[0] for _ in range(3 * (len(body) + 2))]
    assert kinds == (["begin"] + body + ["commit"]) * 3


def test_stream_told_of_an_error_rolls_back_and_starts_anew():
    s = gen.Stream(TRAFFIC, CONFIG, SEED, 0, {})
    sent = []
    for _ in range(15):
        sent.append(s.next())
        s.done(sent[-1][0], sent[-1][1], [("x",)])
    kind, lit, _ = s.next()
    assert kind == "index_update" and lit["trx"] == 1
    s.done(kind, lit, ERR)
    assert s.next() == ("rollback", {"trx": 1}, "ROLLBACK")
    s.done("rollback", {"trx": 1}, 0)
    again = [s.next() for _ in range(20)]
    assert [a[0] for a in again] == SHAPE
    assert all(a[1]["trx"] == 2 for a in again)
    assert again[15][1]["id"] != lit["id"]  # newly drawn values
    # a window of another mix ends the open transaction first
    assert s.next()[0] == "begin"
    assert [s.next("index_update")[0] for _ in range(4)] == [
        "commit", "begin", "index_update", "commit"]
    # the accepted generators have no such hooks: the harness takes the old
    # lines for them
    for old in (sysbench, sysbench_oltp):
        assert not hasattr(old.Stream, "done")
        assert not hasattr(old, "judge") and not hasattr(old, "readback")


def test_warmup_is_whole_transactions_numbered_below_zero():
    warm = gen.warmup(TRAFFIC, CONFIG, gen.pools(TRAFFIC, CONFIG, SEED))
    one = (["begin"] + list(gen.SELECTS) + ["index_update", "non_index_update",
                                            "delete", "insert", "commit"])
    assert [w[0] for w in warm] == one * int(TRAFFIC["warm_passes"])
    assert [w[1]["trx"] for w in warm] == [-1] * len(one) + [-2] * len(one)
    written = [w[1]["id"] for w in warm if w[0] in gen.WRITES]
    assert len(set(written)) == 6  # delete and insert share an id


# ---- the judge on logs made by hand

class Log:
    def __init__(self, data):
        self.data = data
        self.recs = []

    def add(self, who, trx, kind, t0, rows, dt=0.1, **lit):
        if trx is not None:
            lit["trx"] = trx
        if "id" in lit or kind == "readback_table":
            lit.setdefault("table", T)
        self.recs.append((kind, lit, t0, t0 + dt, rows, who))
        return self

    def base(self, i):
        cols = self.data[T]
        return (int(cols["k"][i - 1]), cols["c"][i - 1].decode(),
                cols["pad"][i - 1].decode())

    def sum_k(self, lo, hi):
        return sum(self.base(i)[0] for i in range(lo, hi + 1))

    def judge(self):
        return gen.judge(self.recs, self.data, CONFIG, TRAFFIC)


def clean(data, **over):
    """The log of the module's docstring. `over` alters one answer or time:
    the faults."""
    g = Log(data)
    b = g.base
    k7 = 1234
    o = lambda name, default: over.get(name, default)  # noqa: E731
    # W commits three writes between 12.0 (sent) and 12.1 (acknowledged)
    g.add(1, 1, "begin", 10.0, 0)
    g.add(1, 1, "index_update", 10.2, o("update_answers", 1), id=5)
    g.add(1, 1, "non_index_update", 10.4, 1, id=6, c=NEW_C)
    g.add(1, 1, "delete", 10.6, 1, id=7)
    g.add(1, 1, "insert", 10.8, 1, id=7, k=k7, c=NEW_C, pad=NEW_PAD)
    g.add(1, 1, "commit", 12.0, 0)
    # R1 began before W's COMMIT was sent: the old rows
    g.add(2, 1, "begin", 9.0, 0)
    g.add(2, 1, "point_select", 12.5, [(o("r1_sees", b(6)[1]),)], id=6)
    g.add(2, 1, "sum_range", 12.7, [(str(g.sum_k(1, 100)),)], id=1,
          id_end=100)
    g.add(2, 1, "commit", 12.9, 0)
    # R2 began after W's COMMIT was acknowledged: the new rows
    g.add(3, 1, "begin", 13.0, 0)
    g.add(3, 1, "point_select", 13.2, [(o("r2_sees", NEW_C),)], id=6)
    g.add(3, 1, "sum_range", 13.4, [(str(
        g.sum_k(1, 100) + 1 + k7 - b(7)[0]),)], id=1, id_end=100)
    g.add(3, 1, "simple_range", 13.6, [(b(5)[1],), (NEW_C,), (NEW_C,),
                                       (b(8)[1],)], id=5, id_end=8)
    g.add(3, 1, "commit", 13.8, 0)
    # R3's BEGIN overlaps W's COMMIT: either, but one version all through
    g.add(4, 1, "begin", 11.95, 0)
    g.add(4, 1, "point_select", 12.2, [(NEW_C,)], id=6)
    g.add(4, 1, "point_select", 12.4, [(o("r3_again", NEW_C),)], id=6)
    # W's commit is one: R3 saw its id 6, so it sees its ids 5 and 7 too
    g.add(4, 1, "sum_range", 12.45, [(str(
        g.sum_k(1, 5) + o("r3_sum_has", 1)),)], id=1, id_end=5)
    g.add(4, 1, "simple_range", 12.5, [(NEW_C,), (o("r3_sees_7", NEW_C),)],
          id=6, id_end=7)
    g.add(4, 1, "commit", 12.6, 0)
    # A began before W committed, writes id 9, then runs into W's id 5
    g.add(5, 1, "begin", 11.5, 0)
    g.add(5, 1, "non_index_update", 11.7, 1, id=9, c=NEW_C)
    g.add(5, 1, "index_update", 12.5, ERR, id=o("conflict_on", 5))
    g.add(5, 1, "rollback", 12.7, 0)
    # R4 began after A's rollback: A's write is nowhere
    g.add(6, 1, "begin", 14.0, 0)
    g.add(6, 1, "point_select", 14.2, [(o("r4_sees", b(9)[1]),)], id=9)
    g.add(6, 1, "commit", 14.4, 0)
    if over.get("second_writer"):  # overlapped W, wrote W's id 5, committed
        g.add(7, 1, "begin", 10.5, 0)
        g.add(7, 1, "index_update", 12.3, 1, id=5)
        g.add(7, 1, "commit", 12.5, 0)
    k5 = b(5)[0] + 1 + bool(over.get("second_writer"))
    back = {5: (k5, b(5)[1], b(5)[2]), 6: (b(6)[0], NEW_C, b(6)[2]),
            7: (k7, NEW_C, NEW_PAD), 9: b(9)}
    back.update(over.get("readback", {}))
    for j, (i, row) in enumerate(sorted(back.items())):
        g.add(-1, None, "readback_row", 20.0 + j,
              [tuple(str(v) for v in (i, *row))], id=i)
    total = (g.sum_k(1, 2000) + 1 + bool(over.get("second_writer"))
             + k7 - b(7)[0])
    g.add(-1, None, "readback_table", 30.0, [("2000", str(total))])
    return g


def test_clean_log_reads_correct(data):
    out = clean(data).judge()
    assert out["correct"], (out["compared"], out["first_bad"])
    c = out["compared"]
    assert list(c) == ["missing_answers", "wrong_answers", "rel_err_max",
                       "unjudged_answers", "conflict_restarts"]
    assert c["conflict_restarts"] == {"value": 1, "limit": 1.0}
    assert c["unjudged_answers"] == {"value": 0, "limit": 0}
    assert out["transactions"] == {"all": 6, "committed": 5, "written_ids": 4}
    # the other permitted answer of the overlapped reader is right too
    b = Log(data).base
    other = clean(data, r3_again=None, r3_sum_has=0, r3_sees_7=b(7)[1])
    other.recs = [r for r in other.recs if not (
        r[5] == 4 and r[0] in ("point_select", "simple_range"))]
    other.add(4, 1, "point_select", 12.2, [(b(6)[1],)], id=6)
    other.add(4, 1, "simple_range", 12.5, [(b(6)[1],), (b(7)[1],)], id=6,
              id_end=7)
    assert other.judge()["correct"]


# fault -> (what is altered, the number that has to say so)
LOG_FAULTS = {
    "commit_acknowledged_write_not_read_back": (
        lambda d: {"readback": {5: Log(d).base(5)}}, "wrong_answers"),
    "read_shows_an_aborted_write": (
        lambda d: {"r4_sees": NEW_C}, "wrong_answers"),
    "read_shows_a_commit_sent_after_its_begin": (
        lambda d: {"r1_sees": NEW_C}, "wrong_answers"),
    "read_misses_a_commit_acknowledged_before_its_begin": (
        lambda d: {"r2_sees": Log(d).base(6)[1]}, "wrong_answers"),
    "two_overlapped_writers_of_one_key_both_committed": (
        lambda d: {"second_writer": True}, "wrong_answers"),
    "conflict_error_with_no_other_writer": (
        lambda d: {"conflict_on": 8}, "missing_answers"),
    "update_answers_0": (lambda d: {"update_answers": 0}, "wrong_answers"),
    "one_id_read_twice_shows_two_versions": (
        lambda d: {"r3_again": Log(d).base(6)[1]}, "wrong_answers"),
    # a fractured read: one commit wrote ids 5, 6 and 7; a reader whose BEGIN
    # overlaps it is shown the new 6 and the old 5, or the new 6 and the old
    # 7 in one range read
    "one_commit_seen_in_one_id_and_not_in_another": (
        lambda d: {"r3_sum_has": 0}, "wrong_answers"),
    "one_range_read_shows_half_of_a_commit": (
        lambda d: {"r3_sees_7": Log(d).base(7)[1]}, "wrong_answers"),
    "an_aborted_write_is_read_back": (
        lambda d: {"readback": {9: (Log(d).base(9)[0], NEW_C,
                                    Log(d).base(9)[2])}}, "wrong_answers"),
}


@pytest.mark.parametrize("fault", sorted(LOG_FAULTS))
def test_log_fault_reads_not_correct_by_its_number(data, fault):
    alter, number = LOG_FAULTS[fault]
    out = clean(data, **alter(data)).judge()
    assert not out["correct"]
    c = out["compared"]
    assert c[number]["value"] > 0, (c, out["first_bad"])
    other = "missing_answers" if number == "wrong_answers" else "wrong_answers"
    assert c[other]["value"] == 0, (c, out["first_bad"])
    assert out["first_bad"].startswith(number)


def test_table_sums_and_counts_are_held(data):
    g = clean(data)
    kind, lit, t0, t1, rows, who = g.recs.pop()
    g.recs.append((kind, lit, t0, t1, [("2000", str(int(rows[0][1]) - 1))],
                   who))
    assert g.judge()["compared"]["wrong_answers"]["value"] == 1
    g.recs[-1] = (kind, lit, t0, t1, [("1999", rows[0][1])], who)
    assert g.judge()["compared"]["wrong_answers"]["value"] == 1


def test_own_writes_are_seen_and_other_errors_are_missing(data):
    g = Log(data)
    b = g.base
    g.add(1, 1, "begin", 1.0, 0)
    g.add(1, 1, "non_index_update", 1.2, 1, id=3, c=NEW_C)
    g.add(1, 1, "point_select", 1.4, [(NEW_C,)], id=3)
    g.add(1, 1, "index_update", 1.6, 1, id=4)
    g.add(1, 1, "sum_range", 1.8, [(str(g.sum_k(1, 10) + 1),)], id=1,
          id_end=10)
    g.add(1, 1, "commit", 2.0, 0)
    g.add(-1, None, "readback_row", 3.0, [("3", str(b(3)[0]), NEW_C, b(3)[2])],
          id=3)
    assert g.judge()["correct"]
    g.add(2, 1, "begin", 4.0, 0)
    g.add(2, 1, "point_select", 4.2, "WireError: ERR 1064: RuntimeError: tx 4 "
          "is aborted", id=3)
    g.add(2, 1, "rollback", 4.4, 0)
    out = g.judge()
    assert not out["correct"]
    assert out["compared"]["missing_answers"]["value"] == 1
    assert out["compared"]["conflict_restarts"]["value"] == 0


def test_more_choices_than_64_are_unjudged_and_restarts_have_a_limit(data):
    g = Log(data)
    for w in range(7):  # seven ids, each with a commit the reader overlaps
        g.add(w, 1, "begin", 1.0, 0)
        g.add(w, 1, "index_update", 1.2, 1, id=10 + w)
        g.add(w, 1, "commit", 2.0, 0, dt=1.0)
    g.add(9, 1, "begin", 2.5, 0)
    g.add(9, 1, "sum_range", 3.5, [("1",)], id=10, id_end=16)
    g.add(9, 1, "commit", 3.7, 0)
    out = g.judge()
    assert out["compared"]["unjudged_answers"]["value"] == 1
    assert out["compared"]["wrong_answers"]["value"] == 0
    assert not out["correct"]  # an answer nobody checked: the limit is 0
    # 2 restarts among 8 transactions: over max(1, 1 %)
    h = clean(data)
    h.add(8, 1, "begin", 11.6, 0)
    h.add(8, 1, "index_update", 12.55, ERR, id=5)
    h.add(8, 1, "rollback", 12.8, 0)
    out = h.judge()
    assert out["compared"]["conflict_restarts"] == {"value": 2, "limit": 1.0}
    assert out["compared"]["missing_answers"]["value"] == 0
    assert not out["correct"]


def test_a_snapshot_is_a_point_in_the_order_of_the_commits(data):
    """Two commits on different ids, the first acknowledged before the
    second was sent, both inside the reader's BEGIN: the reader may hold
    neither, the first, or both, never the second alone."""
    def log(sees_10, sees_11):
        g = Log(data)
        for who, i, t0 in ((1, 10, 1.2), (2, 11, 1.6)):
            g.add(who, 1, "begin", 0.5, 0)
            g.add(who, 1, "non_index_update", 0.7, 1, id=i, c=NEW_C)
            g.add(who, 1, "commit", t0, 0, dt=0.2)
        g.add(3, 1, "begin", 1.0, 0, dt=2.0)
        for i, new in ((10, sees_10), (11, sees_11)):
            g.add(3, 1, "point_select", 3.1 + i / 100,
                  [(NEW_C if new else g.base(i)[1],)], id=i)
        g.add(3, 1, "commit", 3.5, 0)
        return g.judge()
    for sees in ((False, False), (True, False), (True, True)):
        assert log(*sees)["correct"], sees
    out = log(False, True)
    assert out["compared"]["wrong_answers"]["value"] == 1
    assert out["compared"]["unjudged_answers"]["value"] == 0


def test_waiting_entries_lie_over_the_accepted_file(monkeypatch):
    """`waiting_cells.json` holds only what `BENCHMARK.json` does not, and
    `BENCHMARK.json` wins on a name that both have: bringing the cell in is
    an addition there and no edit here."""
    accepted = cells.load_json(ROOT, "BENCHMARK.json")
    waiting = cells.load_json(ROOT, "benchmark", "waiting_cells.json")
    assert set(waiting) == {"doc", "configs", "workloads", "per_layer",
                            "joins"}
    names = lambda b, sec: [e["name"] for e in b[sec]]  # noqa: E731
    bench = cells.load_bench()
    for sec in ("configs", "workloads", "per_layer"):
        assert not set(names(waiting, sec)) & set(names(accepted, sec))
        assert names(bench, sec) == names(accepted, sec) + names(waiting, sec)
    assert bench["end_to_end"] == accepted["end_to_end"]
    less = lambda ms: {n: {k: v for k, v in m.items()  # noqa: E731
                           if k != "workloads"} for n, m in ms.items()}
    for w in accepted["workloads"]:  # an accepted cell reads what it read
        for sec in ("end_to_end", "per_layer"):
            assert (less(cells.metrics_for(bench, sec, w["name"]))
                    == less(cells.metrics_for(accepted, sec, w["name"])))
    mine = cells.metrics_for(bench, "per_layer", CELL)
    assert {"commits_per_s", "log_replicated_per_entry",
            "batched_stmt_pct", "compiles_in_window"} <= set(mine)
    assert "join_roofline" not in mine
    # the PR that brings the cell in: the same entries in BENCHMARK.json,
    # `commits_per_s` with another unit to tell the two apart
    entered = json.loads(json.dumps(accepted))
    entered["configs"] += waiting["configs"]
    entered["workloads"].append({k: v for k, v in WORKLOAD.items()
                                 if k != "waits_for"})
    entered["per_layer"].append(dict(waiting["per_layer"][0], unit="1/s"))
    next(m for m in entered["per_layer"]
         if m["name"] == "batched_stmt_pct")["workloads"].append(CELL)
    real = cells.load_json
    monkeypatch.setattr(cells, "load_json", lambda *parts: (
        json.loads(json.dumps(entered)) if parts[-1] == "BENCHMARK.json"
        else real(*parts)))
    bench, cell, _, _ = cells.load_cell(CELL, TINY)
    assert "waits_for" not in cell and names(bench, "workloads").count(
        CELL) == 1
    mine = cells.metrics_for(bench, "per_layer", CELL)
    assert mine["commits_per_s"]["unit"] == "1/s"
    assert "log_replicated_per_entry" in mine
    assert mine["batched_stmt_pct"]["workloads"].count(CELL) == 1
    assert not cells.struck_by_its_fault(cell, {
        "compared": {"missing_answers": {"value": 1, "limit": 0}},
        "errors": {"IndexError: index 7 is out of bounds for axis 0 with "
                   "size 7": 1}})


def test_readback_names_every_written_id_once(data):
    g = clean(data)
    asked = gen.readback(g.recs)
    assert [a[1].get("id") for a in asked] == [5, 6, 7, 9, None]
    assert asked[0][2] == "SELECT id, k, c, pad FROM sbtest1 WHERE id=5"
    assert asked[-1][2] == "SELECT COUNT(*), SUM(k) FROM sbtest1"


# ---- whole runs of run.py on the CPU

PLANT = """
import sys
sys.argv = ["run.py"] + {argv!r}
sys.path.insert(0, {root!r})
fault = {fault!r}
if fault == "lost_commit":
    import oceanbase_tpu.server.database as D
    real = D.DbSession._end_tx
    n = [0]
    def broken(self, commit):
        # every second COMMIT that wrote something is acknowledged and
        # rolled back underneath
        wrote = commit and self._tx is not None and self._tx.touched_tables
        n[0] += bool(wrote)
        return real(self, commit and not (wrote and n[0] % 2 == 0))
    D.DbSession._end_tx = broken
if fault == "spurious_conflict":
    # past the warm-up, every 23rd staged write is refused as a write
    # conflict that nothing caused
    import oceanbase_tpu.storage.memtable as M
    real_stage = M.Memtable.stage
    staged = [0]
    def refusing(self, tx_id, read_snapshot, key, op, values):
        staged[0] += 1
        if staged[0] > {after!r} and staged[0] % 23 == 0:
            raise M.WriteConflict(f"key {{key}} locked by tx 0")
        return real_stage(self, tx_id, read_snapshot, key, op, values)
    M.Memtable.stage = refusing
if fault == "follower":
    # no fault: each row of the read-back is asked again on a connection at
    # ob_read_consistency = weak, and counted on the last line of stderr
    import atexit, json
    from benchmark.harness import loadgen
    from benchmark.harness.wire import WireClient
    real_timed = loadgen._timed
    weak, seen = [], {{"reads": 0, "equal": 0, "empty": 0, "failed": 0}}
    def and_the_follower(client, kind, lit, text, who):
        r = real_timed(client, kind, lit, text, who)
        if who == -1 and kind == "readback_row":
            if not weak:
                weak.append(WireClient(client.sock.getpeername()[1]))
                weak[0].query("SET ob_read_consistency = weak")
                atexit.register(lambda: print(
                    "follower_readback " + json.dumps(seen), file=sys.stderr))
            got = real_timed(weak[0], kind, lit, text, -2)[4]
            seen["reads"] += 1
            seen["equal"] += got == r[4]
            seen["empty"] += got == []
            seen["failed"] += isinstance(got, str)
            if got != r[4]:
                seen.setdefault("first_unequal", f"{{text}}: {{got}}"[:300])
        return r
    loadgen._timed = and_the_follower
if fault == "control":
    # the control: the read-back answered from the generated data, as if
    # every write were lost
    from benchmark.generators import sysbench
    from benchmark.harness import cells, loadgen
    over = sys.argv[sys.argv.index("--rehearse") + 1] if (
        "--rehearse" in sys.argv) else None
    _, _, config, _ = cells.load_cell(
        sys.argv[sys.argv.index("--workload") + 1], over)
    data = sysbench.generate(
        config, int(sys.argv[sys.argv.index("--seed") + 1]))
    real_timed = loadgen._timed
    def generated(client, kind, lit, text, who):
        r = real_timed(client, kind, lit, text, who)
        if who == -1 and kind == "readback_row":
            cols, i = data[lit["table"]], lit["id"] - 1
            r = r[:4] + ([(str(lit["id"]), str(int(cols["k"][i])),
                           cols["c"][i].decode(), cols["pad"][i].decode())],
                         who)
        return r
    loadgen._timed = generated
import runpy
runpy.run_path({run!r}, run_name="__main__")
"""


def planted_run(fault: str | None, argv: list, env=None,
                after: int = 40) -> dict:
    """One whole run of `run.py`: sound (None), with "lost_commit" or
    "spurious_conflict" (from the `after`-th staged write on) planted
    underneath, with the "control" in the read-back's place, or sound with
    the "follower" asked beside the read-back (its counts under `follower`).
    """
    code = PLANT.format(argv=argv, root=ROOT, fault=fault, after=after,
                        run=os.path.join(ROOT, "benchmark", "run.py"))
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=1500)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    last = p.stderr.strip().splitlines()[-1]
    if last.startswith("follower_readback "):
        line["follower"] = json.loads(last.split(" ", 1)[1])
    return line


def rehearse(fault, over=TINY + ",clients=8,point_selects=1", seconds="6"):
    """A whole run on the CPU; where a sound run was asked for and nothing
    struck it but the program's fault that the cell waits for (one run in
    four at this size, `waits_for` in waiting_cells.json), run again."""
    argv = ["--workload", CELL, "--seed", "3100000001", "--seconds", seconds,
            "--trace", "0", "--rehearse", over]
    for _ in range(5):
        line = planted_run(fault, argv, dict(os.environ, JAX_PLATFORMS="cpu"))
        assert line["device"]["platform"] == "cpu"
        if fault not in (None, "follower") or not (
                cells.struck_by_its_fault(WORKLOAD, line)):
            break
    return line


# what is planted -> the number of `correct` that has to catch it
RUNS = {None: None, "lost_commit": "wrong_answers", "control": "wrong_answers",
        "spurious_conflict": "missing_answers"}


@pytest.mark.parametrize("fault", sorted(RUNS, key=str))
def test_whole_run_sound_planted_and_control(fault):
    line = rehearse(fault)
    assert line["correct"] == (fault is None), line["compared"]
    back = line["readback"]
    assert back["committed"] >= 4 and back["statements"] > back["written_ids"]
    if fault:
        c = line["compared"]
        assert c[RUNS[fault]]["value"] > 0
        other = ({"missing_answers", "wrong_answers"} - {RUNS[fault]}).pop()
        assert c[other]["value"] == 0
        assert line["failed"] > 0
        # a conflict that nothing caused is no restart: it is not excused
        assert c["conflict_restarts"]["value"] == 0
    else:
        assert list(line["compared"])[-2:] == ["unjudged_answers",
                                               "conflict_restarts"]
        assert list(line)[-1] == "compared"
        assert set(line["window"]["by_kind"]) <= set(SHAPE) | {"rollback"}


def test_the_follower_is_asked_beside_the_read_back_and_counted():
    """Read and written down (PERF.md section 7), no gate: what
    `rw_at_size.py --follower` runs at the cell's size."""
    line = rehearse("follower")
    f = line["follower"]
    assert f["reads"] == line["readback"]["written_ids"] > 0
    assert 0 <= f["equal"] <= f["reads"] and f["failed"] == 0
    assert line["correct"], (line["compared"], line.get("errors"))


def test_real_conflicts_restart_the_stream_and_every_stream_goes_on():
    """8 streams on 50 rows: write conflicts for certain. Each is a restart
    the log explains; with the limit of `conflict_restarts` lifted the run
    is correct."""
    line = rehearse(None, "tables=1,table_size=50,warm_window_s=1,clients=8,"
                    "point_selects=1,range_size=3", seconds="8")
    c = line["compared"]
    assert c["conflict_restarts"]["value"] > c["conflict_restarts"]["limit"]
    assert not line["correct"]
    assert all(c[k]["value"] <= c[k]["limit"] for k in c
               if k != "conflict_restarts"), c
    kinds = line["window"]["by_kind"]
    # each restart is a ROLLBACK (the window's are some of the run's), and
    # every stream went on: more transactions begun than connections and
    # restarts together, and commits after them
    assert 0 < kinds["rollback"]["n"] <= c["conflict_restarts"]["value"]
    assert kinds["begin"]["n"] > 8 + kinds["rollback"]["n"]
    assert line["readback"]["committed"] > 8
