#!/usr/bin/env python3
"""The harness checks itself, on the CPU: `python -m benchmark.selfcheck`.

  1. the trace reduction on a hand-built timeline and on the trace recorded
     on the chip (`harness/recorded_trace.json`)
  2. the necessary-bytes function against hand-worked row counts
  3. parameter pools reproducible from --seed (large seeds too)
  4. the parameterised numpy references against the program's
     fixed-parameter ones at the validation literals
  5. (with --rehearse) a tiny-scale end-to-end rehearsal of every cell in
     BENCHMARK.json and of those that wait in benchmark/waiting_cells.json,
     and the control and fault tests of benchmark/tests

No number printed here is a device number.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.generators import (sysbench, sysbench_oltp,  # noqa: E402
                                  sysbench_rw, tpch, tpch_regroup)
from benchmark.harness import cells, peaks  # noqa: E402
from benchmark.harness import trace as T  # noqa: E402

REHEARSE = {"tpch": "scale_factor=0.01",
            "tpch_regroup": "scale_factor=0.01",
            "sysbench": "tables=2,table_size=2000,warm_window_s=1",
            "sysbench_oltp": "tables=2,table_size=2000,warm_window_s=1",
            "sysbench_rw": "tables=1,table_size=2000,warm_window_s=1"}


def check_trace() -> None:
    ms = 1_000_000
    ev = {"windows": [("a", 0, 100 * ms), ("b", 100 * ms, 150 * ms)],
          "ops": [(0, "%fusion.12", 10 * ms, 20 * ms),
                  (0, "%sort.3", 25 * ms, 15 * ms),   # overlaps the fusion
                  (0, "%copy.1", 90 * ms, 20 * ms),   # straddles a -> b
                  (0, "%fusion.4", 120 * ms, 10 * ms)],
          "modules": [(0, "jit_run(1)", 10 * ms, 30 * ms),
                      (0, "jit_run(1)", 90 * ms, 20 * ms),
                      (0, "jit_run(2)", 120 * ms, 10 * ms)],
          "host": [("wait", 40 * ms, 50 * ms)]}
    r = T.reduce_events(ev)
    a, b = r["per_kind"]["a"], r["per_kind"]["b"]
    assert abs(a["busy_s"] - 0.040) < 1e-12, a   # 10..40 and 90..100
    assert abs(b["busy_s"] - 0.020) < 1e-12, b   # 100..110 and 120..130
    assert a["launches"] == 2 and b["launches"] == 1
    assert abs(r["idle_s"] - (0.150 - 0.060)) < 1e-12
    assert abs(a["ops"]["fusion"] - 0.020) < 1e-12
    assert r["idle_gaps"][0][0] == "a:wait", r["idle_gaps"]
    assert T.category("%fusion.123") == "fusion"
    assert T.category("sort.4") == "sort"
    rec = os.path.join(ROOT, "benchmark", "harness", "recorded_trace.json")
    with open(rec) as f:
        doc = json.load(f)
    got = T.reduce_events(doc["events"])
    for k, want in doc["expect"].items():
        assert abs(got[k] - want) <= 1e-9 * max(1.0, abs(want)), (k, got[k], want)
    print("trace reduction: hand-built timeline and recorded chip trace ok",
          {k: got[k] for k in doc["expect"]})


def check_bytes() -> None:
    widths = json.load(open(os.path.join(
        ROOT, "benchmark", "generators", "tpch_widths.json")))
    rows = {"lineitem": 1000, "orders": 250, "customer": 25, "part": 40}
    nb = lambda k: peaks.necessary_bytes(  # noqa: E731
        tpch.REFERENCED_COLUMNS[k], rows, widths)
    assert nb("q6") == 1000 * (2 + 1 + 1 + 4)
    assert nb("q1") == 1000 * (1 + 1 + 1 + 4 + 1 + 1 + 2)
    assert nb("q3") == 25 * (4 + 1) + 250 * (4 + 4 + 2 + 1) + 1000 * (4 + 4 + 1 + 2)
    assert nb("q14") == 40 * (4 + 1) + 1000 * (4 + 4 + 1 + 2)
    try:
        peaks.peaks("TPU v9 imaginary")
    except KeyError:
        pass
    else:
        raise AssertionError("an unknown device kind must be an error")
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    print("necessary bytes and peaks ok")


def check_pools() -> None:
    tr = {"kinds": ["q3", "q14", "q6", "q1"], "pool": 8}
    big = 2**31 + 12345
    a, b = tpch.pools(tr, {}, big), tpch.pools(tr, {}, big)
    assert a == b and a != tpch.pools(tr, {}, big + 1)
    assert all(len(v) == 8 for v in a.values())
    s1 = tpch.Stream(tr, {}, big, 0, a)
    s2 = tpch.Stream(tr, {}, big, 0, a)
    assert [s1.next() for _ in range(40)] == [s2.next() for _ in range(40)]
    cfg = {"tables": 3, "table_size": 1000}
    k1 = sysbench.Stream({"kinds": ["point_select"]}, cfg, big, 5, {})
    k2 = sysbench.Stream({"kinds": ["point_select"]}, cfg, big, 5, {})
    assert [k1.next() for _ in range(50)] == [k2.next() for _ in range(50)]
    d1, d2 = sysbench.generate(cfg, big), sysbench.generate(cfg, big)
    assert (d1["sbtest2"]["c"] == d2["sbtest2"]["c"]).all()
    assert len(d1["sbtest1"]["c"][0]) == 119 and len(d1["sbtest1"]["pad"][0]) == 59
    ro = json.load(open(os.path.join(ROOT, "benchmark", "traffic",
                                     "read_only.json")))
    t1 = sysbench_oltp.Stream(ro, cfg, big, 5, {})
    t2 = sysbench_oltp.Stream(ro, cfg, big, 5, {})
    sent = [t1.next() for _ in range(64)]
    assert sent == [t2.next() for _ in range(64)]
    assert [s[0] for s in sent[:16]] == (
        ["begin"] + ["point_select"] * 10 + list(sysbench_oltp.RANGES)
        + ["commit"]) and sent[16][0] == "begin"
    assert sysbench_oltp.generate is sysbench.generate
    rw = json.load(open(os.path.join(ROOT, "benchmark", "traffic",
                                     "read_write.json")))
    w1 = sysbench_rw.Stream(rw, cfg, big, 5, {})
    w2 = sysbench_rw.Stream(rw, cfg, big, 5, {})
    sent = [w1.next() for _ in range(80)]
    assert sent == [w2.next() for _ in range(80)]
    assert [s[0] for s in sent[:20]] == (
        ["begin"] + ["point_select"] * 10 + list(sysbench_oltp.RANGES)
        + ["index_update", "non_index_update", "delete", "insert", "commit"])
    assert sysbench_rw.generate is sysbench.generate
    print("pools, streams and data reproducible from the seed ok")


def check_hooks() -> None:
    """A generator without `done` / `judge` / `readback` goes through the
    lines it went through before PR 35: `loadgen.py`'s lane asks such a
    stream nothing, `run.py` calls `check.judge` and sends no read-back. The
    four accepted generators have none of them; `sysbench_rw` has all."""
    for gen in (sysbench, sysbench_oltp, tpch, tpch_regroup):
        assert not hasattr(gen.Stream, "done"), gen.__name__
        assert not hasattr(gen, "judge"), gen.__name__
        assert not hasattr(gen, "readback"), gen.__name__
    assert hasattr(sysbench_rw.Stream, "done")
    assert hasattr(sysbench_rw, "judge") and hasattr(sysbench_rw, "readback")
    print("the accepted generators have no done / judge / readback ok")


def check_references() -> None:
    """Against the program's fixed-parameter references, on the program's
    own generated tables (the only place the benchmark reads them)."""
    from oceanbase_tpu.models.tpch import datagen
    from oceanbase_tpu.models.tpch.queries import (
        q1_numpy_fast, q3_cpu, q6_numpy, q14_cpu)

    tables = datagen.generate(0.02, 20250930)
    data = {}
    for name in ("lineitem", "orders", "customer", "part"):
        tb = tables[name]
        data[name] = {
            c: ((np.asarray(tb.data[c]), np.asarray(tb.dicts[c].values()))
                if c in tb.dicts else np.asarray(tb.data[c]).astype(np.int64))
            for c in tpch.reference_columns({})[name]}
    V = tpch.VALIDATION
    li = tables["lineitem"]
    assert abs(float(tpch.reference("q6", V["q6"], data)[0][0])
               - q6_numpy(li)) < 1e-6
    assert abs(float(tpch.reference("q14", V["q14"], data)[0][0])
               - q14_cpu(tables["part"], li)) < 1e-9
    mine = tpch.reference("q3", V["q3"], data)
    theirs = q3_cpu(tables["customer"], tables["orders"], li)
    assert len(mine) == len(theirs) == 10
    for m, t in zip(mine, theirs):
        assert (m[0], m[2], m[3]) == (t[0], t[2], t[3]), (m, t)
        assert abs(float(m[1]) - t[1]) < 1e-6
    q1 = q1_numpy_fast(li)
    rows = tpch.reference("q1", V["q1"], data)
    assert len(rows) == int((q1["count"] > 0).sum())
    ls = tables["lineitem"].dicts["l_linestatus"]
    rf = tables["lineitem"].dicts["l_returnflag"]
    for r in rows:
        key = rf.encode_one(r[0], add=False) * len(ls) + ls.encode_one(
            r[1], add=False)
        assert int(q1["count"][key]) == r[9]
        assert abs(float(r[2]) - q1["sum_qty"][key] / 100) < 1e-6
        assert abs(float(r[5]) - q1["sum_ch"][key] / 1e6) < 1e-3
    cfg = {"tables": 1, "table_size": 300}
    rows = sysbench_oltp.generate(cfg, 77)
    c = [v.decode() for v in rows["sbtest1"]["c"]]
    lit = {"table": "sbtest1", "id": 250, "id_end": 349}  # past table_size
    ref = lambda k: sysbench_oltp.reference(k, lit, rows)  # noqa: E731
    assert ref("begin") == 0 and ref("commit") == 0
    assert ref("point_select") == [(c[249],)]
    assert ref("simple_range") == [(v,) for v in c[249:]]
    assert ref("sum_range") == [(sum(map(int, rows["sbtest1"]["k"][249:])),)]
    assert ref("order_range") == [(v,) for v in sorted(c[249:])]
    assert ref("distinct_range") == [(v,) for v in sorted(set(c[249:]))]
    print("parameterised references equal the program's at the validation "
          "literals, and sysbench_oltp's the rows by hand ok")


def rehearse_cells() -> None:
    bench = cells.load_bench()  # the accepted cells, then those that wait
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    three = ["missing_answers", "wrong_answers", "rel_err_max"]
    for w in bench["workloads"]:
        tr = json.load(open(os.path.join(
            ROOT, "benchmark", "traffic", w["traffic"] + ".json")))
        for trace in ("0", "1"):
            p = subprocess.run(
                bench["command"] + ["--workload", w["name"], "--seed",
                                    "2147483659", "--seconds", "2",
                                    "--trace", trace, "--rehearse",
                                    REHEARSE[tr["generator"]]],
                cwd=ROOT, env=env, capture_output=True, text=True,
                timeout=900)
            assert p.returncode == 0, p.stderr[-1500:]
            line = json.loads(p.stdout.strip().splitlines()[-1])
            assert line["device"]["platform"] == "cpu"
            # A waiting cell may be struck by the fault it waits for (one
            # rehearsal in four, PERF.md section 7): then nothing else may
            # be wrong. An accepted cell waits for nothing.
            struck = (not line["correct"]
                      and cells.struck_by_its_fault(w, line))
            assert line["correct"] or struck, (line["compared"],
                                               line.get("errors"))
            assert not any(k in line["metrics"] for k in (
                "device_idle_pct", "device_ms_per_stmt", "hbm_peak_gb"))
            assert list(line["compared"])[:3] == three, line["compared"]
            assert struck or all(c["value"] <= c["limit"]
                                 for c in line["compared"].values())
            if "readback" in line:  # the whole run judged, its writes read
                assert line["readback"]["committed"] > 0
                assert line["attempted"] > line["readback"]["statements"]
            else:  # the old lines: three numbers, every one of them 0
                assert list(line["compared"]) == three, line["compared"]
                assert line["attempted"] >= line["window"]["statements"]
            print(f"rehearsal {w['name']} --trace {trace}",
                  "struck by the fault it waits for: "
                  f"{line['errors']}" if struck else "ok:",
                  sorted(line["metrics"]))
    p = subprocess.run(bench["command"] + [
        "--workload", bench["workloads"][0]["name"], "--seed", "1",
        "--seconds", "1", "--trace", "0"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and not p.stdout.strip(), "off the chip it fails"
    print("off the chip, without --rehearse, the command fails ok")
    subprocess.run([sys.executable, "-m", "pytest", "-q", "-p",
                    "no:cacheprovider", os.path.join("benchmark", "tests")],
                   cwd=ROOT, env=env, check=True, timeout=1800)


def main() -> int:
    check_trace()
    check_bytes()
    check_pools()
    check_hooks()
    check_references()
    if "--rehearse" in sys.argv:
        rehearse_cells()
    print("selfcheck ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
