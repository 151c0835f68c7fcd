"""A string dictionary that sessions grow while others read it.

  snapshot    two threads append strings to one column while a third
              remaps codes through the column's sorted view: no error, no
              view shorter than the codes, one code per string
  views       the sorted view kept by bisection equals a sort from scratch
              after every growth, strings landing first, last, in between
  programs    after growth a program that lowered a string literal answers
              like the reference; a range program with no literal is not
              compiled again
  stream      the sysbench read-write stream over the wire, 8 connections,
              judged by its own judge: correct on three seeds; with every
              second acknowledged COMMIT rolled back underneath: not
"""

from __future__ import annotations

import os
import random
import subprocess
import sys
import threading

import jax
import numpy as np
import pytest

from oceanbase_tpu.core import dictionary
from oceanbase_tpu.core.dictionary import Dictionary, SortedViews
from oceanbase_tpu.core.dtypes import DataType, Field, Schema
from oceanbase_tpu.server.database import Database, TableInfo

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _word(rng: random.Random) -> str:
    return "".join(rng.choice("acmxz") for _ in range(rng.randint(0, 5)))


# ---- snapshot

def test_appends_beside_a_reader_leave_no_short_view():
    schema = Schema((Field("id", DataType.int32()),
                     Field("c", DataType.varchar())))
    words = [f"w{i:04d}" for i in range(2000)]
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)  # interleave as often as the lock allows
    try:
        for rep in range(20):
            ti = TableInfo("t", schema, ["id"], 1, 1,
                           dicts={"c": Dictionary(["m"])})
            d = ti.dicts["c"]
            done = threading.Event()
            go = threading.Barrier(3)
            faults: list[BaseException] = []

            def writer(order):
                try:
                    go.wait()
                    for w in order:
                        d.encode_one(w)
                except BaseException as e:  # noqa: BLE001 - reported below
                    faults.append(e)

            def reader():
                try:
                    go.wait()
                    while not done.is_set():
                        vals, n = d.snapshot()
                        data = {"c": np.arange(n, dtype=np.int32)[::-1]}
                        want = [vals[c] for c in data["c"]]
                        sd = ti.remap_sorted(data)["c"]
                        assert sd.decode(data["c"]) == want
                except BaseException as e:  # noqa: BLE001
                    faults.append(e)

            rng = random.Random(rep)
            a, b = words[:], words[:]
            rng.shuffle(a)
            rng.shuffle(b)
            threads = [threading.Thread(target=writer, args=(o,))
                       for o in (a, b)]
            r = threading.Thread(target=reader)
            r.start()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            done.set()
            r.join()
            assert not faults, faults[0]
            vals = d.values()
            assert len(vals) == len(set(vals)) == len(words) + 1
            assert all(d.encode_one(v, add=False) == i
                       for i, v in enumerate(vals))
    finally:
        sys.setswitchinterval(old)


# ---- views

@pytest.mark.parametrize("chunk", [2, dictionary.CHUNK])
def test_view_kept_by_bisection_equals_a_sort_from_scratch(monkeypatch,
                                                           chunk):
    monkeypatch.setattr(dictionary, "CHUNK", chunk)  # 2: splits at once
    rng = random.Random(7)
    d = Dictionary(["m"])
    sv = SortedViews(d)
    landed = set()
    for step in range(300):
        for _ in range(rng.choice((0, 1, 1, 2, 3, 17))):
            s = rng.choice(("", "a", "zzzzzz", _word(rng), "m" + _word(rng)))
            d.encode_one(s)
        (sd, remap), how, placed = sv.extend_to(len(d))
        assert how in ("sort", "insert", "")
        ref, codes = d.finalize_sorted(np.arange(len(d), dtype=np.int32))
        assert sd.values() == ref.values(), step
        np.testing.assert_array_equal(remap, codes)
        assert [sd.decode_one(i) for i in range(len(sd))] == ref.values()
        assert all(sd.encode_one(v, add=False) == i
                   for i, v in enumerate(ref.values()))
        assert sd.encode_one("b" * 9, add=False) == -1
        assert sd.lineage is sv.lineage
        for code in (len(d) - 1,):
            landed.add("first" if remap[code] == 0 else
                       "last" if remap[code] == len(d) - 1 else "middle")
    assert landed == {"first", "middle", "last"}


# ---- programs

def _compile_count():
    seen = [0]

    def on(event, _seconds, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            seen[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on)
    return seen


def test_programs_after_growth_literal_answers_and_range_reuse():
    compiles = _compile_count()
    db = Database(n_nodes=3, n_ls=2)
    s = db.session()
    s.sql("CREATE TABLE g (id INT PRIMARY KEY, c VARCHAR(20))")
    rows = {i: f"c{i * 10:04d}" for i in range(1, 65)}
    s.sql("INSERT INTO g VALUES " + ",".join(
        f"({i}, '{c}')" for i, c in rows.items()))
    ranges = ["SELECT c FROM g WHERE id BETWEEN 3 AND 40 ORDER BY c",
              "SELECT DISTINCT c FROM g WHERE id BETWEEN 5 AND 60 ORDER BY c"]
    mid = "c0305"  # between c0300 and c0310

    def literal_answers():
        return (sorted(r[0] for r in s.sql(
                    f"SELECT id FROM g WHERE c = '{mid}'").rows()),
                s.sql(f"SELECT COUNT(*) FROM g WHERE c > '{mid}'").rows())

    def ref_literal():
        return ([i for i, c in sorted(rows.items()) if c == mid],
                [(sum(c > mid for c in rows.values()),)])

    def ref_range(lo, hi, distinct):
        got = [rows[i] for i in range(lo, hi + 1) if i in rows]
        return sorted(set(got)) if distinct else sorted(got)

    def range_answers():
        return [[r[0] for r in s.sql(q).rows()] for q in ranges]

    want_ranges = lambda: [ref_range(3, 40, False),  # noqa: E731
                           ref_range(5, 60, True)]
    for _ in range(3):  # past the sampled profile's first run as well
        assert range_answers() == want_ranges()
        assert literal_answers() == ref_literal()
    # a string landing first, one in the middle (the literal), one last;
    # and rows of the ranges moved onto new strings
    for i, c in ((65, "a"), (66, mid), (67, "zz")):
        s.sql(f"INSERT INTO g VALUES ({i}, '{c}')")
        rows[i] = c
    for i, c in ((7, "b7"), (20, "c0155"), (33, "y")):
        s.sql(f"UPDATE g SET c = '{c}' WHERE id = {i}")
        rows[i] = c
    before = compiles[0]
    assert range_answers() == want_ranges()
    assert compiles[0] == before, "a range program with no literal compiled"
    # inside a transaction, on its private view, the same programs
    s.sql("BEGIN")
    s.sql("UPDATE g SET c = 'c0306' WHERE id = 9")
    rows[9] = "c0306"
    assert range_answers() == want_ranges()
    s.sql("COMMIT")
    assert compiles[0] == before, "a program compiled for a private view"
    # the literal's code moved: its programs trace again and answer right
    assert literal_answers() == ref_literal()


# ---- stream

sys.path.insert(0, ROOT)
from benchmark.tests import test_sysbench_rw as rw  # noqa: E402

# 8 connections on 200,000 rows: a write conflict between two streams is
# one run in some thousands (on 2,000 rows several a run, each a restart
# the limit of `conflict_restarts` counts against the traffic)
OVER = "tables=1,table_size=200000,warm_window_s=1,clients=8,point_selects=1"
SEEDS = (3_100_000_011, 3_100_000_012, 3_100_000_013)


def _argv(seed):
    return ["--workload", rw.CELL, "--seed", str(seed), "--seconds", "6",
            "--trace", "0", "--rehearse", OVER]


def _start(fault, seed):
    code = rw.PLANT.format(argv=_argv(seed), root=ROOT, fault=fault,
                           after=40, run=os.path.join(
                               ROOT, "benchmark", "run.py"))
    return subprocess.Popen([sys.executable, "-c", code],
                            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                            text=True, env=dict(os.environ,
                                                JAX_PLATFORMS="cpu"))


def _line(p):
    import json

    out, err = p.communicate(timeout=600)
    assert p.returncode == 0, err[-2000:]
    return json.loads(out.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def stream_runs():
    """The three sound runs and the planted one, side by side."""
    procs = {s: _start(None, s) for s in SEEDS}
    procs["lost_commit"] = _start("lost_commit", SEEDS[0])
    return {k: _line(p) for k, p in procs.items()}


@pytest.mark.parametrize("seed", SEEDS)
def test_stream_reads_correct_on_every_seed(stream_runs, seed):
    line = stream_runs[seed]
    c = line["compared"]
    assert line["correct"], c
    assert c["missing_answers"]["value"] == 0 and line["failed"] == 0
    assert line["readback"]["committed"] >= 4
    assert line["window"]["compiles"] == 0


def test_lost_commit_reads_not_correct(stream_runs):
    line = stream_runs["lost_commit"]
    assert not line["correct"]
    assert line["compared"]["wrong_answers"]["value"] > 0
