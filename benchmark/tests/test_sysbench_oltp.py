"""`sysbench-1m-trx.read_only`: the generator, the plain reference, the OK
packet in `check.py`, `counters()`, the control and the planted faults.

Run with `python -m pytest benchmark/tests -q` (not part of the repo's
tier-1 suite: a `benchmark` PR adds no file outside the benchmark's
directory) or through `python -m benchmark.selfcheck --rehearse`. On the CPU
at `tables=2, table_size=2000`, the served system over the wire.

  control   the `stale` reference in the program's place (the neighbouring
            id's row, a range shifted by one: a read at another snapshot):
            `wrong_answers` > 0 for every SELECT kind.
  faults    a whole run of `run.py` at rehearsal scale with the timed path
            broken underneath: an answer altered where it is produced (the
            server's cell encoder: `wrong_answers` > 0), every second
            COMMIT answered by an error (`missing_answers` > 0). A step that returns its
            state unchanged, half a batch left out and an exchange between
            chips left out are faults this one-chip, read-only cell cannot
            have.
"""

from __future__ import annotations

import json
import os
import sqlite3
import subprocess
import sys
from decimal import Decimal

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

import test_correct as tc  # noqa: E402
from benchmark.generators import sysbench, sysbench_oltp as gen  # noqa: E402
from benchmark.harness import cells, check, layer  # noqa: E402
from benchmark.harness.server import (CompileMeter, Served,  # noqa: E402
                                      named_sysstat)
from benchmark.harness.wire import WireClient, WireError  # noqa: E402

SEED = 2_147_483_659  # past 31 bits, as the driver's seeds are
CELL = "sysbench-1m-trx.read_only"
TINY = "tables=2,table_size=2000,warm_window_s=1"
_, _, CONFIG, TRAFFIC = cells.load_cell(CELL, TINY)
SHAPE = ["begin"] + ["point_select"] * 10 + list(gen.RANGES) + ["commit"]


@pytest.fixture(scope="module")
def data():
    return gen.generate(CONFIG, SEED)


# ---- check.py: an OK packet is an answer

ROWS = [("a", "1", "2.50")]
CASES = {
    # name: (answer, reference, missing, wrong)
    "ok_against_0": (0, 0, 0, 0),
    "ok_with_another_count": (3, 0, 0, 1),
    "ok_where_rows_were_due": (0, [("a",)], 0, 1),
    "rows_where_ok_was_due": ([("a",)], 0, 0, 1),
    "no_rows_where_ok_was_due": ([], 0, 0, 1),
    "err_where_ok_was_due": ("WireError: ERR 1064", 0, 1, 0),
    # the four verdict shapes the accepted cells produce, unchanged
    "rows_right": (ROWS, [("a", 1, Decimal("2.5"))], 0, 0),
    "rows_text_differs": (ROWS, [("b", 1, Decimal("2.5"))], 0, 1),
    "rows_count_differs": (ROWS + ROWS, [("a", 1, Decimal("2.5"))], 0, 1),
    "err_where_rows_were_due": ("WireError: boom", [("a",)], 1, 0),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_check_judges_ok_packets_and_rows(case):
    got, ref, missing, wrong = CASES[case]
    out = check.judge([("k", {}, 0.0, 0.0, got, 0)], lambda k, lit: ref, 0.0)
    assert out["compared"]["missing_answers"]["value"] == missing
    assert out["compared"]["wrong_answers"]["value"] == wrong
    assert out["correct"] == (missing == 0 and wrong == 0)


def test_check_decimal_gap_is_still_measured():
    out = check.judge([("k", {}, 0.0, 0.0, [("2.5000001",)], 0)],
                      lambda k, lit: [(Decimal("2.5"),)], 1e-9)
    assert not out["correct"]
    assert out["compared"]["wrong_answers"]["value"] == 0
    assert 3e-8 < out["compared"]["rel_err_max"]["value"] < 5e-8


# ---- the generator

def test_stream_is_reproducible_and_has_the_scripts_shape():
    a = gen.Stream(TRAFFIC, CONFIG, SEED, 3, {})
    b = gen.Stream(TRAFFIC, CONFIG, SEED, 3, {})
    sent = [a.next() for _ in range(16 * 40)]
    assert sent == [b.next() for _ in range(16 * 40)]
    assert sent != [gen.Stream(TRAFFIC, CONFIG, SEED, 4, {}).next()
                    for _ in range(16 * 40)]
    n = int(CONFIG["table_size"])
    clipped = 0
    for t in range(40):
        trx = sent[16 * t:16 * t + 16]
        assert [s[0] for s in trx] == SHAPE
        assert (trx[0][2], trx[-1][2]) == ("BEGIN", "COMMIT")
        assert len({s[1]["table"] for s in trx[1:11]}) == 1  # one a group
        for kind, lit, text in trx[1:-1]:
            assert 1 <= lit["id"] <= n and lit["table"] in sysbench.table_names(
                CONFIG)
            assert text == gen.TEXT[kind].format(**lit)
            if kind != "point_select":
                assert lit["id_end"] - lit["id"] == 99
                clipped += lit["id_end"] > n
    assert clipped, "2,000 rows and 160 ranges: some run past table_size"
    assert gen.TEXT == {
        "begin": "BEGIN", "commit": "COMMIT",
        "point_select": "SELECT c FROM {table} WHERE id={id}",
        "simple_range": "SELECT c FROM {table} WHERE id BETWEEN {id} AND "
                        "{id_end}",
        "sum_range": "SELECT SUM(k) FROM {table} WHERE id BETWEEN {id} AND "
                     "{id_end}",
        "order_range": "SELECT c FROM {table} WHERE id BETWEEN {id} AND "
                       "{id_end} ORDER BY c",
        "distinct_range": "SELECT DISTINCT c FROM {table} WHERE id BETWEEN "
                          "{id} AND {id_end} ORDER BY c"}


@pytest.mark.parametrize("kind", gen.SELECTS)
def test_only_a_kind_stays_inside_begin_and_commit(kind):
    s = gen.Stream(TRAFFIC, CONFIG, SEED, 0, {})
    group = 10 if kind == "point_select" else 1
    kinds = [s.next(kind)[0] for _ in range(3 * (group + 2))]
    assert kinds == (["begin"] + [kind] * group + ["commit"]) * 3


def test_window_cut_mid_transaction_resumes_with_commit():
    s = gen.Stream(TRAFFIC, CONFIG, SEED, 0, {})
    assert [s.next()[0] for _ in range(5)] == ["begin"] + ["point_select"] * 4
    # the next window is of another mix: the open transaction ends first
    assert [s.next("sum_range")[0] for _ in range(4)] == [
        "commit", "begin", "sum_range", "commit"]
    assert s.next("sum_range")[0] == "begin"
    assert [s.next()[0] for _ in range(3)] == ["commit", "begin",
                                               "point_select"]
    # a window of the same mix goes on where the last one stopped
    assert [s.next()[0] for _ in range(15)] == SHAPE[2:] + ["begin"]
    # a window that closed on a COMMIT leaves nothing to finish
    t = gen.Stream(TRAFFIC, CONFIG, SEED, 1, {})
    assert [t.next()[0] for _ in range(16)] == SHAPE
    assert t.next("order_range")[0] == "begin"


def test_warmup_sends_every_kind_on_every_table_inside_a_transaction():
    warm = gen.warmup(TRAFFIC, CONFIG, {})
    per_table = ["begin"] + list(gen.SELECTS) + ["commit"]
    assert [w[0] for w in warm] == per_table * 2 * int(TRAFFIC["warm_passes"])
    assert {w[1]["table"] for w in warm if w[1]} == set(
        sysbench.table_names(CONFIG))
    assert all(w[1]["id_end"] <= CONFIG["table_size"]
               for w in warm if "id_end" in w[1])


@pytest.mark.parametrize("kind", gen.SELECTS)
def test_reference_equals_sqlite(data, kind):
    """Every reference kind against the standard library's sqlite over the
    same rows, ranges past `table_size` among them."""
    db = sqlite3.connect(":memory:")
    for name, cols in data.items():
        db.execute(f"create table {name} (id integer primary key, k integer,"
                   " c text)")
        db.executemany(f"insert into {name} values (?, ?, ?)", zip(
            cols["id"].tolist(), cols["k"].tolist(),
            (v.decode() for v in cols["c"])))
    n = int(CONFIG["table_size"])
    for j, i in enumerate([1, 2, 777, n - 150, n - 99, n - 98, n - 1, n]):
        lit = {"table": f"sbtest{1 + j % 2}", "id": i, "id_end": i + 99}
        got = db.execute(gen.render(kind, lit)).fetchall()
        assert got == gen.reference(kind, lit, data), (kind, lit)
        assert len(got) == (1 if kind in ("point_select", "sum_range")
                            else min(100, n - i + 1))
    assert gen.reference("begin", {}, data) == 0
    assert gen.reference("commit", {}, data) == 0
    assert set(gen.reference_columns(CONFIG)["sbtest1"]) == {"k", "c"}


# ---- the control: a stale read in the program's place

def as_wire(answer):
    return answer if isinstance(answer, int) else tc.as_wire(answer)


def test_control_stale_read_is_wrong_for_every_kind(data):
    s = gen.Stream(TRAFFIC, CONFIG, SEED, 0, {})
    sent = [s.next()[:2] for _ in range(16 * 30)]
    ref = lambda k, lit: gen.reference(k, lit, data)  # noqa: E731
    good = [(k, lit, 0.0, 0.0, as_wire(ref(k, lit)), 0) for k, lit in sent]
    assert check.judge(good, ref, 0.0)["correct"]
    for kind in gen.SELECTS:
        stale = [(k, lit, 0.0, 0.0, as_wire(gen.reference(
            k, lit, data, stale=1 if k == kind else 0)), 0)
            for k, lit in sent]
        out = check.judge(stale, ref, 0.0)
        n = sum(k == kind for k, _ in sent)
        assert not out["correct"]
        # a SUM shifted by one id can agree by chance, the rows cannot
        assert out["compared"]["wrong_answers"]["value"] >= 0.9 * n, kind
        assert out["compared"]["missing_answers"]["value"] == 0


# ---- the served system over the wire

class Deployment:
    """`harness/server.py`'s `Served` without its process-wide compile
    cache: the same boot, DDL over the wire, `direct_load`, `counters()`."""

    def __init__(self, data):
        from oceanbase_tpu.server.async_front import AsyncMySqlFrontend
        from oceanbase_tpu.server.database import Database

        self.db = Database(n_nodes=int(CONFIG["cluster"]["replicas"]),
                           n_ls=int(CONFIG["cluster"]["log_streams"]))
        self.front = AsyncMySqlFrontend(self.db).start()
        self.port = self.front.port
        self.compiles = CompileMeter()
        self.named = named_sysstat()
        self.data = {t: dict(c) for t, c in data.items()}
        self.admin = WireClient(self.port)
        Served.load(self, self.admin, gen, CONFIG, self.data)

    def counters(self):
        return Served.counters(self)

    def close(self):
        self.admin.close()
        Served.free(self)


@pytest.fixture(scope="module")
def deployment(data):
    d = Deployment(data)
    yield d
    d.close()


def drive(d, clients: int, transactions: int):
    """`clients` connections, each a stream, their statements interleaved
    one at a time, so that transactions are open side by side."""
    conns = [WireClient(d.port) for _ in range(clients)]
    streams = [gen.Stream(TRAFFIC, CONFIG, SEED, i, {})
               for i in range(clients)]
    recs = []
    try:
        for _ in range(16 * transactions):
            for i, (c, s) in enumerate(zip(conns, streams)):
                kind, lit, text = s.next()
                try:
                    rows = c.query(text)
                except WireError as e:
                    rows = f"WireError: {e}"
                recs.append((kind, lit, 0.0, 0.0, rows, i))
    finally:
        for c in conns:
            c.close()
    return recs


def test_served_transactions_judge_correct_and_counters_are_named(deployment):
    d = deployment
    before = d.counters()
    recs = drive(d, clients=4, transactions=25)
    r0 = d.db.metrics.counters_snapshot()
    after = d.counters()
    registry = d.db.metrics.counters_snapshot()
    assert len(recs) == 1600 and sum(r[0] == "commit" for r in recs) == 100
    assert all(r[4] == 0 for r in recs if r[0] in ("begin", "commit"))
    out = check.judge(recs, lambda k, lit: gen.reference(k, lit, d.data), 0.0)
    assert out["correct"], (out["compared"], out["first_bad"])
    # every key the accepted metrics name, with the value it had
    tax = d.db.host_tax.snapshot()["digests"]
    assert after["plan_cache.fast_hits"] == d.db.plan_cache.stats.fast_hits
    assert after["xla.compiles"] == d.compiles.read()[0]
    assert after["host_tax.statements"] == sum(
        a["count"] for a in tax.values())
    assert after["host_tax.e2e_s"] == pytest.approx(
        sum(a["e2e_s"] for a in tax.values()))
    assert after["host_tax.cpu_s"] == pytest.approx(
        sum(a["cpu_s"] for a in tax.values())) and after["host_tax.cpu_s"] > 0
    assert after["host_tax.phase.device wait"] > 0
    ctx = {"counters0": before, "counters1": after, "statements": 1600,
           "window_s": 40.0}
    for fn in sorted(os.listdir(layer.DIR)):  # each finds what it names
        if fn.endswith(".json"):
            spec = cells.load_json(layer.DIR, fn)
            if spec["source"] == "counters":
                # nothing to read only where the denominator's counters
                # did not move (no log entry in a read-only drive)
                den = spec.get("den")
                still = isinstance(den, list) and not layer._delta(ctx, den)
                assert (layer.evaluate(spec, ctx) is None) == still, fn
                assert still == (fn == "log_replicated_per_entry.json")
    # the program's named counters, by name
    moved = lambda n: after[n] - before.get(n, 0.0)  # noqa: E731
    assert moved("sysstat.tx commits") == 100
    assert moved("sysstat.sql statements") == 1600
    assert moved("host_tax.statements") == 1600
    assert all(v <= after["sysstat." + n] <= registry[n]
               for n, v in r0.items())
    # one the program has not bumped and a metric names reads 0.0, and the
    # metric that reads it is on the line from the first run
    assert "stmt batched statements" not in registry
    assert after["sysstat.stmt batched statements"] == 0.0
    spec = cells.load_json(layer.DIR, "batched_stmt_pct.json")
    assert layer.evaluate(spec, ctx) == 0.0
    assert layer.evaluate(dict(spec, num=["sysstat.no such counter"]),
                          ctx) is None


def test_snapshot_of_begin_holds_against_a_later_commit(deployment):
    """The guarantee the configuration states: a statement of a transaction
    reads the snapshot of its BEGIN, whatever commits meanwhile."""
    d = deployment
    reader, writer = WireClient(d.port), WireClient(d.port)
    sql = "SELECT SUM(k) FROM sbtest1 WHERE id BETWEEN 10 AND 109"
    try:
        assert reader.query("BEGIN") == 0
        first = reader.query(sql)
        assert writer.query("UPDATE sbtest1 SET k=k+1 WHERE id=50") == 1
        assert reader.query(sql) == first
        assert reader.query("COMMIT") == 0
        assert int(reader.query(sql)[0][0]) == int(first[0][0]) + 1
        assert writer.query("UPDATE sbtest1 SET k=k-1 WHERE id=50") == 1
    finally:
        reader.close()
        writer.close()


# ---- planted faults: a whole run with the timed path broken underneath

COMMIT_ERROR = """
import sys
sys.argv = ["run.py"] + {argv!r}
sys.path.insert(0, {root!r})
import oceanbase_tpu.server.database as D
real = D.DbSession._end_tx
n = [0]
def broken(self, commit):
    # a COMMIT answered by an error, its transaction rolled back: every
    # second one once the warm-up's {skip} (one connection, in turn) are by
    n[0] += commit
    if commit and n[0] > {skip} and n[0] % 2 == 0:
        real(self, False)
        raise D.SqlError("planted: the commit was lost")
    return real(self, commit)
D.DbSession._end_tx = broken
import runpy
runpy.run_path({run!r}, run_name="__main__")
"""


def planted_run(fault: str | None, argv: list, env=None) -> dict:
    """One whole run of `run.py` with `fault` planted underneath:
    "cell" (an answer altered where it is produced, `test_correct.py`'s),
    "commit_error", or None."""
    if fault != "commit_error":
        return tc.planted_run(argv, fault == "cell", env)
    over = argv[argv.index("--rehearse") + 1] if "--rehearse" in argv else None
    _, _, config, traffic = cells.load_cell(CELL, over)
    code = COMMIT_ERROR.format(
        argv=argv, root=ROOT, run=os.path.join(ROOT, "benchmark", "run.py"),
        skip=int(config["tables"]) * int(traffic["warm_passes"]))
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=1200)
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


# fault -> the number of `correct` that has to catch it
FAULTS = {None: None, "cell": "wrong_answers",
          "commit_error": "missing_answers"}


@pytest.mark.parametrize("fault", sorted(FAULTS, key=str))
def test_planted_fault_reads_not_correct(fault):
    # one point select a transaction: however slow the machine, a window
    # of a few seconds closes some dozens of transactions
    argv = ["--workload", CELL, "--seed", "3100000001", "--seconds", "4",
            "--trace", "0", "--rehearse", TINY + ",point_selects=1"]
    line = planted_run(fault, argv, dict(os.environ, JAX_PLATFORMS="cpu"))
    assert line["device"]["platform"] == "cpu"
    assert line["correct"] == (fault is None), line["compared"]
    if fault:
        assert line["compared"][FAULTS[fault]]["value"] > 0
        assert line["failed"] > 0
    else:
        assert set(line["window"]["by_kind"]) <= set(SHAPE)
        assert line["window"]["compiles"] == 0
