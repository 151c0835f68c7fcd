"""Operator-level plan telemetry (engine/plan_profile.py).

The profiled execution mode runs a compiled plan as segmented
per-operator jitted stages with fences — the result it serves must be
BIT-IDENTICAL to the fused program on the full warm query mix, every
plan node must surface as a per-operator row in
__all_virtual_sql_plan_monitor, the per-digest sampling cadence must be
deterministic, the calibration store must stay bounded, and the
cardinality_misestimate sentinel rule must edge-trigger exactly once
per divergence.
"""

import pytest

from oceanbase_tpu.engine.plan_profile import (
    OperatorProfileStore,
    OpSample,
    PlanProfiler,
    miss_factor,
)
from oceanbase_tpu.models.tpch import datagen
from oceanbase_tpu.models.tpch.sql_suite import QUERIES, UNIQUE_KEYS
from oceanbase_tpu.server.database import Database
from oceanbase_tpu.sql import parser as P

JOIN_Q = ("select c_mktsegment, count(*) as n from customer, orders "
          "where c_custkey = o_custkey "
          "group by c_mktsegment order by c_mktsegment")

MIX = {"q1": QUERIES[1], "q6": QUERIES[6], "q3": QUERIES[3],
       "join": JOIN_Q}


@pytest.fixture(scope="module")
def db():
    d = Database(n_nodes=1, n_ls=1,
                 extra_catalog=datagen.generate(sf=0.003))
    # preloaded benchmark tables carry no DDL primary keys; register
    # their unique keys so the physical fast paths are eligible
    d._unique_keys.update(UNIQUE_KEYS)
    d.engine.executor.unique_keys = d._unique_keys
    d.engine.planner.unique_keys = d._unique_keys
    # the slow-query watermark force-arms profiling (mark_slow); park it
    # out of reach so cadence in these tests is purely deterministic
    d.config.set("trace_log_slow_query_watermark", "3600")
    yield d
    d.close()


@pytest.fixture(scope="module")
def fused(db):
    """Fused-program baseline rows for the mix, profiling off."""
    db.config.set("enable_plan_profile", "false")
    s = db.session()
    out = {name: s.sql(q).rows() for name, q in MIX.items()}
    db.config.set("enable_plan_profile", "true")
    assert all(out.values())
    return out


# ---- bit-identity + VT coverage ---------------------------------------------


@pytest.mark.parametrize("name", list(MIX))
def test_profiled_run_bit_identical_to_fused(db, fused, name):
    """A profiled (segmented, fenced) execution serves EXACTLY the rows
    the fused program serves — on the warm plan-cache entry."""
    s = db.session()
    q = MIX[name]
    db.plan_profiler.force_next(P.digest_text(q))
    rs = s.sql(q)
    got = rs.rows()
    opp = rs.op_profile
    assert opp is not None and opp["reason"] == "forced"
    assert got == fused[name]
    assert opp["samples"], "profiled run yielded no operator samples"
    assert all(smp.device_us >= 0 for smp in opp["samples"])


@pytest.mark.parametrize("name", list(MIX))
def test_every_plan_node_lands_in_plan_monitor_vt(db, fused, name):
    """After a profile, __all_virtual_sql_plan_monitor carries one
    per-operator row for EVERY executed node of the plan (EXPLAIN emits
    exactly one line per node, so it supplies the expected count; nodes
    the executor absorbs into a parent — the Join under a clustered-FK
    aggregate — never execute standalone and carry no row)."""
    s = db.session()
    q = MIX[name]
    digest = P.digest_text(q)
    db.plan_profiler.force_next(digest)
    rs = s.sql(q)
    rs.rows()
    opp = rs.op_profile
    assert opp is not None
    absorbed = set(opp["absorbed"])
    if name == "q3":  # Q3's inner join is absorbed by the clustered agg
        assert absorbed
    n_nodes = len(s.sql("explain " + q).rows())
    vt = s.sql(
        "select query_sql, node_id, op_kind, est_rows, actual_rows, "
        "device_us, executions from __all_virtual_sql_plan_monitor"
    ).rows()
    mine = {int(r[1]): r for r in vt if r[0] == digest and r[1] >= 0}
    assert sorted(mine) == [n for n in range(n_nodes)
                            if n not in absorbed]
    assert all(r[2] for r in mine.values())          # op_kind named
    assert sum(r[5] for r in mine.values()) > 0      # fenced device time
    assert all(r[6] >= 1 for r in mine.values())     # executions


def test_vt_keeps_statement_level_rows(db, fused):
    """Back-compat: the plan-level monitor rows survive the per-operator
    rework (node_id -1, executions = plan runs)."""
    vt = db.session().sql(
        "select node_id, op_kind, executions "
        "from __all_virtual_sql_plan_monitor"
    ).rows()
    plan_rows = [r for r in vt if r[0] == -1]
    assert plan_rows and all(r[1] == "" for r in plan_rows)
    assert any(r[2] >= 1 for r in plan_rows)


def test_operator_device_time_reconciles_with_gap_ledger(db, fused):
    """Sum of fenced per-operator device time stays inside the
    statement's e2e wall from the PR 16 gap ledger — the fences measure
    a strict subset of the execute window, so the operator rows can
    never claim more chip time than the statement spent end-to-end."""
    s = db.session()
    q = MIX["q6"]
    db.plan_profiler.force_next(P.digest_text(q))
    rs = s.sql(q)
    rs.rows()
    opp = rs.op_profile
    assert opp is not None
    led = s._gap
    assert led is not None and led.closed
    op_us = sum(smp.device_us for smp in opp["samples"])
    assert op_us <= led.e2e_s * 1e6 * 1.05 + 500.0


# ---- sampling cadence -------------------------------------------------------


def test_sampling_cadence_deterministic():
    """first RE-execution + every sample_every-th after; forcing jumps
    the queue exactly once. Execution-count based — no clock involved.
    The very first execution of a digest is never profiled: one-shot
    statements must not pay the segmented-trace compile cost."""
    pp = PlanProfiler(store=OperatorProfileStore(), sample_every=4)
    got = [pp.decide("d") for _ in range(10)]
    assert got == [None, "first", None, None, "sample",
                   None, None, None, "sample", None]
    pp.force_next("d")
    assert pp.decide("d") == "forced"
    assert pp.decide("d") is None  # force consumed, cadence resumes
    # per-digest independence: a fresh digest waits for its recurrence
    assert pp.decide("other") is None
    assert pp.decide("other") == "first"
    # disabled profiler never samples (and never counts)
    pp.enabled = False
    assert pp.decide("d") is None
    pp.enabled = True
    pp.sample_every = 0  # 0 = first-re-execution-only
    assert all(pp.decide("d") is None for _ in range(5))


def test_config_params_wire_to_profiler(db):
    pp = db.plan_profiler
    try:
        db.config.set("ob_plan_profile_sample", "16")
        assert pp.sample_every == 16
        db.config.set("ob_plan_profile_max_digests", "8")
        assert pp.store.max_digests == 8
        db.config.set("enable_plan_profile", "false")
        assert pp.enabled is False
        assert pp.decide("whatever") is None
    finally:
        db.config.set("ob_plan_profile_sample", "64")
        db.config.set("ob_plan_profile_max_digests", "128")
        db.config.set("enable_plan_profile", "true")
    assert pp.enabled and pp.sample_every == 64


# ---- EXPLAIN ANALYZE --------------------------------------------------------


def test_explain_analyze_forces_exactly_one_profile(db, fused):
    s = db.session()
    q = MIX["q6"]
    store = db.plan_profiler.store
    before = store.profiles
    lines = [r[0] for r in s.sql("explain analyze " + q).rows()]
    assert store.profiles == before + 1
    # annotated plan tree: est/actual/miss/device on operator lines
    ann = [ln for ln in lines if "actual_rows=" in ln]
    assert ann and all("device=" in ln and "miss=" in ln for ln in ann)
    # the analyzed statement's chip-idle line (PR 16 ledger view)
    assert any("chip_idle_pct:" in ln for ln in lines)
    # plain EXPLAIN never executes, never profiles
    plain = [r[0] for r in s.sql("explain " + q).rows()]
    assert store.profiles == before + 1
    assert not any("actual_rows=" in ln for ln in plain)


def test_explain_analyze_marks_misestimates(db):
    """Operators whose window miss factor reaches 8x carry the `>>`
    marker (synthetic, through the annotator — the planner is too good
    on TPC-H scans to misestimate on demand)."""
    from oceanbase_tpu.sql.explain import annotate_plan_lines

    lines = ["SCAN t as t", "  FILTER pred"]
    prof = {
        "samples": [
            OpSample(node_id=0, op_kind="Scan", device_us=10.0,
                     rows=800, out_bytes=64),
            OpSample(node_id=1, op_kind="Filter", device_us=5.0,
                     rows=100, out_bytes=8),
        ],
        "estimates": {0: 100, 1: 50},
    }
    out = annotate_plan_lines(lines, prof)
    assert out[0].startswith(">> ")       # 8x miss marked
    assert not out[1].startswith(">> ")   # 2x miss not marked
    assert "est_rows=100" in out[0] and "actual_rows=800" in out[0]


def test_explain_analyze_annotates_absorbed_nodes(db, fused):
    """Q3's inner join is absorbed by the clustered-FK aggregate: it
    never executes standalone, so its EXPLAIN ANALYZE line says so
    instead of carrying (meaningless) actuals."""
    s = db.session()
    lines = [r[0] for r in s.sql("explain analyze " + MIX["q3"]).rows()]
    ab = [ln for ln in lines if "(absorbed into node" in ln]
    assert len(ab) == 1 and "JOIN" in ab[0]
    assert "actual_rows=" not in ab[0]


# ---- store bound + eviction -------------------------------------------------


def _sample(nid=0, kind="Scan", rows=10, us=5.0):
    return OpSample(node_id=nid, op_kind=kind, device_us=us, rows=rows,
                    out_bytes=rows * 8)


def test_store_bounded_evicts_coldest_digest():
    st = OperatorProfileStore(max_digests=2)
    for i in range(4):
        st.fold(f"d{i}", [_sample()], {0: 10})
    assert len(st.snapshot()["digests"]) == 2
    assert st.evictions == 2
    # coldest-first: the two most recently folded digests survive
    assert sorted(st.snapshot()["digests"]) == ["d2", "d3"]
    # re-folding an old digest re-warms it
    st.fold("d2", [_sample()], {0: 10})
    st.fold("d4", [_sample()], {0: 10})
    assert sorted(st.snapshot()["digests"]) == ["d2", "d4"]
    # shrinking the bound evicts immediately
    st.set_max_digests(1)
    assert list(st.snapshot()["digests"]) == ["d4"]


def test_store_records_calibration_pairs():
    st = OperatorProfileStore()
    st.fold("q", [_sample(rows=100), _sample(nid=1, kind="Join:inner",
                                             rows=7, us=2.0)],
            {0: 10, 1: 7}, plan_id=3)
    st.fold("q", [_sample(rows=300), _sample(nid=1, kind="Join:inner",
                                             rows=7, us=2.0)],
            {0: 10, 1: 7})
    recs = {r["node_id"]: r for r in st.digest_profile("q")}
    assert recs[0]["executions"] == 2
    assert recs[0]["est_rows"] == 10 and recs[0]["avg_rows"] == 200.0
    assert recs[0]["miss_factor"] == miss_factor(10, 200.0) == 20.0
    assert recs[0]["max_miss"] == 30.0
    assert recs[1]["miss_factor"] == 1.0
    assert recs[1]["plan_id"] == 3
    # the JSON-round-trip snapshot stringifies node ids
    snap = st.snapshot()
    import json

    assert json.loads(json.dumps(snap)) == snap


# ---- workload snapshots + sentinel ------------------------------------------


def _rec(execs, rows, us, est, kind="Join:inner"):
    return {"executions": execs, "rows": rows, "device_us": us,
            "est_rows": est, "avg_rows": rows / execs if execs else 0.0,
            "op_kind": kind}


def _snap(snap_id, digests):
    return {"snap_id": snap_id, "ts": float(snap_id), "summary": [],
            "sysstat": {}, "plan_profile": {"digests": digests}}


def test_snapshot_embeds_plan_profile(db, fused):
    snap = db.workload.take(db)
    assert "plan_profile" in snap
    assert snap["plan_profile"]["digests"]


def test_misestimate_rule_fires_once_and_grades_severity():
    from oceanbase_tpu.server.sentinel import evaluate_window

    first = _snap(1, {})
    last = _snap(2, {"q": {
        # node 2: 20x miss AND tops window device time -> critical
        "2": _rec(6, 1200, 9000.0, est=10),
        # node 3: well-estimated, quieter
        "3": _rec(6, 60, 100.0, est=10, kind="Scan"),
    }})
    alerts = [a for a in evaluate_window(first, last)
              if a["rule"] == "cardinality_misestimate"]
    assert len(alerts) == 1
    a = alerts[0]
    assert a["severity"] == "critical"
    assert a["key"] == "q#2"
    assert a["evidence"]["tops_window_device_time"]
    assert a["evidence"]["miss_factor"] == 20.0

    # same miss but another operator dominates device time -> warn
    last_w = _snap(2, {"q": {
        "2": _rec(6, 1200, 900.0, est=10),
        "3": _rec(6, 60, 99000.0, est=10, kind="Scan"),
    }})
    alerts = [a for a in evaluate_window(first, last_w)
              if a["rule"] == "cardinality_misestimate"]
    assert [a["severity"] for a in alerts] == ["warn"]


def test_misestimate_rule_thresholds_and_edge_trigger():
    from oceanbase_tpu.server.sentinel import evaluate_window

    def fires(first, last):
        return [a for a in evaluate_window(first, last)
                if a["rule"] == "cardinality_misestimate"]

    # under the executions floor: silent
    few = _snap(2, {"q": {"2": _rec(4, 800, 100.0, est=10)}})
    assert not fires(_snap(1, {}), few)
    # under the miss ratio: silent
    ok = _snap(2, {"q": {"2": _rec(6, 420, 100.0, est=10)}})  # 7x
    assert not fires(_snap(1, {}), ok)
    # edge trigger: a window that STARTS misestimated does not re-fire
    bad0 = _snap(1, {"q": {"2": _rec(6, 1200, 100.0, est=10)}})
    bad1 = _snap(2, {"q": {"2": _rec(12, 2400, 200.0, est=10)}})
    assert not fires(bad0, bad1)
    # ... but a fresh divergence (clean start) does
    clean0 = _snap(1, {"q": {"2": _rec(2, 20, 10.0, est=10)}})
    assert fires(clean0, bad1)


def test_misestimate_alert_dedup_in_sentinel_ring():
    from oceanbase_tpu.server.sentinel import HealthSentinel

    first = _snap(1, {})
    last = _snap(2, {"q": {"2": _rec(6, 1200, 9000.0, est=10)}})
    hs = HealthSentinel()
    fresh = hs.observe(first, last)
    assert [a.rule for a in fresh] == ["cardinality_misestimate"]
    assert hs.observe(first, last) == []  # re-evaluation is idempotent
    # a NEW window ending later with a fresh divergence fires again
    last2 = _snap(3, {"q": {"2": _rec(12, 2400, 18000.0, est=10)}})
    last2["plan_profile"]["digests"]["q"]["2"]["avg_rows"] = 200.0
    assert hs.observe(last, last2) == []  # still bad at window start


# ---- estimates through the plan-artifact path -------------------------------


ART_Q = ("select g, count(*) as c, sum(v) as s from prof_t "
         "group by g order by g")


def _boot(tmp_path):
    return Database(n_nodes=1, n_ls=1, data_dir=str(tmp_path / "node"),
                    fsync=False)


def test_warm_artifact_hit_profiles_identically(tmp_path):
    """A warm plan-artifact hit (zero compiles) must profile exactly
    like the fresh compile: same node estimates (persisted through
    ArtifactMeta), same per-node cardinalities, same rows."""
    db = _boot(tmp_path)
    db.config.set("trace_log_slow_query_watermark", "3600")
    s = db.session()
    s.sql("alter system set ob_plan_artifact_mode = 'rw'")
    s.sql("create table prof_t (id bigint primary key, "
          "g bigint not null, v bigint not null)")
    s.sql("insert into prof_t values " + ", ".join(
        f"({i}, {i % 5}, {i})" for i in range(64)))
    digest = P.digest_text(ART_Q)
    db.plan_profiler.force_next(digest)
    rs = s.sql(ART_Q)
    rows0 = rs.rows()
    opp0 = rs.op_profile
    assert opp0 is not None and opp0["estimates"]
    db._save_node_meta()
    db.close()

    db = _boot(tmp_path)
    db.config.set("trace_log_slow_query_watermark", "3600")
    assert db.metrics.counters_snapshot().get(
        "plan artifact warm load", 0) >= 1
    ex = db.engine.executor
    c0 = ex.compiles + ex.batched_compiles
    s = db.session()
    db.plan_profiler.force_next(digest)
    rs = s.sql(ART_Q)
    rows1 = rs.rows()
    assert ex.compiles + ex.batched_compiles == c0  # warm artifact hit
    opp1 = rs.op_profile
    assert opp1 is not None
    assert rows1 == rows0
    assert opp1["estimates"] == opp0["estimates"]
    assert ([(smp.node_id, smp.op_kind, smp.rows)
             for smp in opp1["samples"]]
            == [(smp.node_id, smp.op_kind, smp.rows)
                for smp in opp0["samples"]])
    db.close()


# ---- slow-query watermark arms the profiler ---------------------------------


def test_slow_statement_forces_next_profile(db, fused):
    """Crossing the flight-recorder watermark marks the digest so its
    NEXT occurrence carries an operator profile into the bundle."""
    s = db.session()
    q = MIX["q1"]
    digest = P.digest_text(q)
    try:
        db.config.set("trace_log_slow_query_watermark", "0")
        marks0 = db.plan_profiler.slow_marks
        s.sql(q).rows()           # recorded slow -> mark_slow(digest)
        assert db.plan_profiler.slow_marks > marks0
    finally:
        db.config.set("trace_log_slow_query_watermark", "3600")
    execs0 = {r["node_id"]: r["executions"]
              for r in db.plan_profiler.store.digest_profile(digest)}
    rs = s.sql(q)               # forced by the slow mark
    rs.rows()
    opp = rs.op_profile
    assert opp is not None and opp["reason"] == "forced"
    execs1 = {r["node_id"]: r["executions"]
              for r in db.plan_profiler.store.digest_profile(digest)}
    assert all(execs1[n] == execs0.get(n, 0) + 1 for n in execs1)
    # the flight-recorder bundle for the slow run carries the profile
    recs = [b for b in db.flight.records() if b.get("digest") == digest]
    assert recs and "op_profile" in recs[-1]


def test_profiled_slow_run_does_not_rearm(db, fused):
    """A profiled run is slower (fences); if its own slowness re-armed
    the profiler, a watermark-straddling digest would profile EVERY
    execution. The slow mark must skip runs that already profiled."""
    s = db.session()
    q = MIX["q6"]
    digest = P.digest_text(q)
    try:
        db.config.set("trace_log_slow_query_watermark", "0")
        db.plan_profiler.force_next(digest)
        marks0 = db.plan_profiler.slow_marks
        rs = s.sql(q)       # profiled AND recorded slow
        rs.rows()
        assert rs.op_profile is not None
        assert db.plan_profiler.slow_marks == marks0
        # the next run is not dragged into another forced profile
        rs = s.sql(q)
        rs.rows()
        opp = rs.op_profile
        assert opp is None or opp["reason"] != "forced"
    finally:
        db.config.set("trace_log_slow_query_watermark", "3600")
