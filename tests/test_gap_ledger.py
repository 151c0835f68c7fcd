"""Host-tax gap ledger: conservation-complete e2e wall attribution.

Unit layer: GapLedger on a fake clock — the conservation invariant
(sum(phases) + unattributed == e2e, exactly) across the serial cut()
timeline, measured windows with clamped hints, the engine-phase carve,
and batched leader/follower attribution (cohort device busy counted
ONCE).  Integration layer: the same invariant read off live statement
ledgers through the real serving stack — solo fast path, batched
cohorts under an 8-thread hammer, the errsim retry/degradation ladder,
follower reads, streamed out-of-core plans — plus liveness of the
__all_virtual_host_tax / sysstat / workload-snapshot surfaces.

Reference: share/gap_ledger.py (PR-16), server/database.py wiring.
"""

import json
import threading

import pytest

from oceanbase_tpu.share import gap_ledger as GL
from oceanbase_tpu.share.gap_ledger import (GapLedger, HostTaxRegistry,
                                            carve_engine_phases)


class FakeClock:
    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def tick(self, s: float) -> None:
        self.t += s


def conserved(led: GapLedger) -> None:
    """The module's central claim, asserted exactly (fake clock: no
    float noise beyond one sum)."""
    attributed = sum(led.phases.values())
    assert led.closed
    assert attributed <= led.e2e_s + 1e-12
    assert abs(attributed + led.unattributed_s - led.e2e_s) < 1e-12


# ---- serial timeline: cut() / add() -----------------------------------------


def test_cut_timeline_is_gapless():
    """Contiguous cuts cover every nanosecond from begin to close: the
    inter-span glue lands in the adjacent named phase, so a fully-cut
    statement has ZERO unattributed residual."""
    c = FakeClock()
    led = GapLedger(clock=c).begin()
    c.tick(0.010)
    led.cut("setup")
    c.tick(0.002)
    led.cut("fast lookup")
    c.tick(0.050)
    led.cut("device dispatch")
    c.tick(0.005)
    led.cut("completion fold")
    led.close()
    assert led.e2e_s == pytest.approx(0.067)
    assert led.phases == pytest.approx({
        "setup": 0.010, "fast lookup": 0.002,
        "device dispatch": 0.050, "completion fold": 0.005})
    assert led.unattributed_s == 0.0
    conserved(led)


def test_uncut_wall_stays_unattributed():
    """The residual is the whole point: wall nobody claimed is surfaced
    as `unattributed`, never folded into a neighbouring phase."""
    c = FakeClock()
    led = GapLedger(clock=c).begin()
    c.tick(0.004)
    led.cut("setup")
    c.tick(0.006)  # nobody cuts this
    led.close()
    assert led.unattributed_s == pytest.approx(0.006)
    conserved(led)


def test_add_advances_cursor_so_cut_does_not_recover_it():
    """add() outside a window is a caller-measured span that just
    ended; the following cut() must not attribute that wall again."""
    c = FakeClock()
    led = GapLedger(clock=c).begin()
    c.tick(0.020)
    led.add("retry backoff", 0.020)  # caller timed the sleep itself
    c.tick(0.003)
    led.cut("setup")  # only the 3ms since the add
    led.close()
    assert led.phases["retry backoff"] == pytest.approx(0.020)
    assert led.phases["setup"] == pytest.approx(0.003)
    assert led.unattributed_s == 0.0
    conserved(led)


def test_begin_fully_resets_for_session_reuse():
    """Sessions reuse ONE ledger object; begin() must erase every trace
    of the previous statement."""
    c = FakeClock()
    led = GapLedger(clock=c).begin()
    c.tick(0.01)
    led.cut("setup")
    led.device(0.5)
    led.close()
    c.tick(1.0)
    led.begin()
    c.tick(0.002)
    led.close()
    assert led.phases == {}
    assert led.device_s == 0.0
    assert led.e2e_s == pytest.approx(0.002)
    assert led.unattributed_s == pytest.approx(0.002)
    conserved(led)


# ---- measured windows: hint clamp -------------------------------------------


def test_window_hints_clamped_to_wall():
    """Overlapping inner spans can hint MORE than the window's measured
    wall; the proportional clamp keeps sum(phases) <= e2e no matter
    what inner layers report."""
    c = FakeClock()
    led = GapLedger(clock=c).begin()
    led.window_start()
    c.tick(0.010)  # window wall: 10ms
    led.add("batch window", 0.008)
    led.add("governor reserve", 0.008)  # hints total 16ms > 10ms wall
    led.window_end()
    led.close()
    assert sum(led.phases.values()) == pytest.approx(0.010)
    # clamp is proportional: both hints scaled by 10/16
    assert led.phases["batch window"] == pytest.approx(0.005)
    assert led.phases["governor reserve"] == pytest.approx(0.005)
    conserved(led)


def test_window_leftover_goes_to_default_phase():
    c = FakeClock()
    led = GapLedger(clock=c).begin()
    led.window_start()
    c.tick(0.010)
    led.add("device dispatch", 0.004)
    led.window_end("engine host")
    led.close()
    assert led.phases["device dispatch"] == pytest.approx(0.004)
    assert led.phases["engine host"] == pytest.approx(0.006)
    assert led.unattributed_s == 0.0
    conserved(led)


def test_cut_is_noop_inside_window_and_resumes_after():
    """Hints inside a window are clamped spans, not a serial timeline:
    cut() must not fire there.  window_end resumes the cursor, so the
    next cut covers only post-window wall."""
    c = FakeClock()
    led = GapLedger(clock=c).begin()
    c.tick(0.002)
    led.cut("setup")
    led.window_start()
    c.tick(0.010)
    led.cut("setup")  # ignored: window open
    led.window_end("engine host")
    c.tick(0.003)
    led.cut("completion fold")
    led.close()
    assert led.phases["setup"] == pytest.approx(0.002)
    assert led.phases["engine host"] == pytest.approx(0.010)
    assert led.phases["completion fold"] == pytest.approx(0.003)
    conserved(led)


def test_unbalanced_window_flushed_on_close():
    c = FakeClock()
    led = GapLedger(clock=c).begin()
    led.window_start()
    c.tick(0.004)
    led.add("batch window", 0.004)
    led.close()  # caller died before window_end: close() flushes it
    assert led.phases["batch window"] == pytest.approx(0.004)
    conserved(led)


# ---- engine-phase carve -----------------------------------------------------


def test_carve_d2h_never_overlaps_device_wait():
    hints, dev = carve_engine_phases({
        "dispatch_s": 0.010, "fetch_s": 0.006, "d2h_s": 0.002,
        "bind_s": 0.001})
    assert hints["device dispatch"] == pytest.approx(0.010)
    assert hints["d2h"] == pytest.approx(0.002)
    assert hints["device wait"] == pytest.approx(0.004)  # fetch - d2h
    assert hints["param pack"] == pytest.approx(0.001)
    assert dev == pytest.approx(0.014)  # dispatch + (fetch - d2h)


def test_carve_streamed_h2d_carved_out_of_dispatch():
    """A streamed plan's per-chunk H2D wall sits INSIDE dispatch_s; the
    carve subtracts its non-overlapped part so it is never counted
    twice.  On the serving path the pipeline already hinted it live
    (served_stream_hints=True): the carve must then NOT emit its own
    h2d, only shrink dispatch."""
    phases = {"dispatch_s": 0.020, "fetch_s": 0.001,
              "stream_h2d_s": 0.008, "stream_overlap_s": 0.002,
              "stream_compute_s": 0.010}
    served, dev_served = carve_engine_phases(
        phases, served_stream_hints=True)
    assert "h2d" not in served
    assert served["device dispatch"] == pytest.approx(0.014)  # 20-(8-2)
    solo, dev_solo = carve_engine_phases(
        phases, served_stream_hints=False)
    assert solo["h2d"] == pytest.approx(0.006)
    assert solo["device dispatch"] == pytest.approx(0.014)
    # solo carve owns the chunk compute as device busy; served path got
    # it hinted live by the pipeline instead
    assert dev_solo - dev_served == pytest.approx(0.010)


def test_window_end_carved_fuses_and_conserves():
    c = FakeClock()
    led = GapLedger(clock=c).begin()
    led.window_start()
    c.tick(0.020)
    led.window_end_carved(
        {"dispatch_s": 0.010, "fetch_s": 0.004, "d2h_s": 0.001},
        "engine host")
    led.close()
    assert led.phases["device dispatch"] == pytest.approx(0.010)
    assert led.phases["d2h"] == pytest.approx(0.001)
    assert led.phases["device wait"] == pytest.approx(0.003)
    assert led.phases["engine host"] == pytest.approx(0.006)
    assert led.device_s == pytest.approx(0.013)
    assert led.unattributed_s == 0.0
    conserved(led)


def test_from_phases_builds_conservation_complete_ledger():
    led = GapLedger.from_phases(
        0.010, {"dispatch_s": 0.004, "fetch_s": 0.002, "bind_s": 0.001},
        device_s=0.005)
    conserved(led)
    assert led.e2e_s == pytest.approx(0.010)
    assert led.device_s == pytest.approx(0.005)
    d = led.to_dict()
    assert abs(sum(d["phases"].values())
               + d["unattributed_s"] - d["e2e_s"]) < 1e-6


# ---- spans at the site (PR 37): catalog refresh, h2d ------------------------


def test_carve_upload_out_of_the_phase_that_held_it():
    """An upload inside the dispatch (or an overflow's redrive inside the
    sync) leaves that phase; served, the span already hinted "h2d"."""
    phases = {"dispatch_s": 0.010, "fetch_s": 0.006, "d2h_s": 0.001,
              "h2d_s": 0.004, "h2d_fetch_s": 0.002}
    served, _ = carve_engine_phases(phases, served_stream_hints=True)
    assert "h2d" not in served
    assert served["device dispatch"] == pytest.approx(0.006)
    assert served["device wait"] == pytest.approx(0.003)  # 6 - 2 - 1
    solo, _ = carve_engine_phases(phases)
    assert solo["h2d"] == pytest.approx(0.006)
    assert sum(solo.values()) == pytest.approx(0.016)  # dispatch + fetch


def test_span_in_window_is_a_hint_the_carve_does_not_repeat():
    """The span hints the upload; the engine's record says it sat inside
    dispatch_s; together they fill the window without the clamp."""
    c = FakeClock()
    led = GapLedger(clock=c).begin()
    GL.set_current(led)
    try:
        led.window_start("engine host")
        with GL.span("catalog refresh") as sp:
            c.tick(0.003)
            sp.count("catalog refresh rows", 64)
        c.tick(0.001)  # dispatch starts
        with GL.span("h2d") as sp:
            c.tick(0.004)
            sp.moved(1024)
        c.tick(0.002)
        led.window_end_carved({"dispatch_s": 0.006, "h2d_s": 0.004},
                              "engine host")
    finally:
        GL.set_current(None)
    led.close()
    assert led.phases == pytest.approx({
        "catalog refresh": 0.003, "h2d": 0.004, "device dispatch": 0.002,
        "engine host": 0.001})
    assert led.counts == {"catalog refresh rows": 64, "h2d uploads": 1,
                          "h2d bytes": 1024}
    conserved(led)


def test_span_on_the_serial_path_comes_out_of_the_next_cut():
    """Outside a window (the fast tier's refresh after a commit) the span
    is taken out of the cut that holds it: still no residual."""
    c = FakeClock()
    led = GapLedger(clock=c).begin()
    GL.set_current(led)
    try:
        c.tick(0.001)
        with GL.span("catalog refresh"):
            c.tick(0.005)
        c.tick(0.002)
        led.cut("fast lookup")
    finally:
        GL.set_current(None)
    led.close()
    assert led.phases == pytest.approx({"catalog refresh": 0.005,
                                        "fast lookup": 0.003})
    assert led.unattributed_s == 0.0
    conserved(led)


def test_span_outside_a_statement_counts_only_upload_seconds():
    """No ledger (a background rebuild) or a closed one: nothing is
    hinted or counted, but the thread's upload clock still runs."""
    c = FakeClock()
    led = GapLedger(clock=c).begin()
    led.close()
    GL.set_current(led)
    try:
        t0 = GL.h2d_seconds()
        with GL.span("h2d") as sp:
            sp.moved(8)
    finally:
        GL.set_current(None)
    with GL.span("h2d") as sp:
        sp.moved(8)
    assert GL.h2d_seconds() > t0  # the two spans ran on the real clock
    assert led.phases == {} and led.counts == {}
    led.begin()
    assert led.counts == {}


# ---- batched cohorts: busy counted once -------------------------------------


def test_batched_cohort_device_busy_counted_once():
    """Double-count regression: in a cohort of 4, the leader attributes
    the shared dispatch (and its device busy) exactly once; followers
    hint only their window wait.  Registry device_s must equal the
    leader's dispatch, not 4x it."""
    c = FakeClock()
    reg = HostTaxRegistry(clock=c)
    leds = [GapLedger(clock=c) for _ in range(4)]
    for led in leds:
        led.begin()
        led.window_start()
    c.tick(0.002)  # window fill
    # leader (index 0) dispatches for everyone: 3ms busy, once
    c.tick(0.003)
    leds[0].add("device dispatch", 0.003)
    leds[0].device(0.003)
    for led in leds[1:]:
        led.add("batch window", 0.005)  # followers waited the window
    for led in leds:
        led.window_end()
        led.close()
        conserved(led)
        reg.fold(7, led)
    snap = reg.snapshot()["digests"][7]
    assert snap["count"] == 4
    assert snap["device_s"] == pytest.approx(0.003)  # once, not 4x
    assert snap["phases"]["device dispatch"] == pytest.approx(0.003)
    assert snap["phases"]["batch window"] == pytest.approx(0.015)
    assert snap["e2e_s"] == pytest.approx(0.020)


def test_registry_windows_and_fold_extra():
    c = FakeClock()
    reg = HostTaxRegistry(clock=c, window_s=1.0)
    led = GapLedger(clock=c).begin()
    c.tick(0.4)
    led.cut("device dispatch")
    led.device(0.3)
    led.close()
    reg.fold(1, led)
    c.tick(1.0)  # next window bucket
    led2 = GapLedger(clock=c).begin()
    c.tick(0.2)
    led2.cut("setup")
    led2.close()
    reg.fold(1, led2)
    # post-close wall (wire write) lands on phase AND e2e: digest-level
    # conservation survives the annotation
    reg.fold_extra(1, "wire write", 0.1)
    a = reg.snapshot()["digests"][1]
    assert a["e2e_s"] == pytest.approx(0.7)
    assert sum(a["phases"].values()) + a["unattributed_s"] == (
        pytest.approx(a["e2e_s"]))
    wins = reg.snapshot()["windows"]
    assert len(wins) == 2 and wins[0]["stmts"] == 1
    # chip idle over the most recent window: no device time folded there
    assert reg.window_chip_idle_pct() == pytest.approx(100.0)


# ---- integration: live serving stack ----------------------------------------


@pytest.fixture(scope="module")
def db():
    from oceanbase_tpu.server import Database

    d = Database(n_nodes=3, n_ls=2)
    s = d.session()
    s.sql("create table gt (k bigint primary key, v bigint not null)")
    s.sql("insert into gt values " + ", ".join(
        f"({i}, {i * 3})" for i in range(64)))
    return d


def _assert_live_conserved(led):
    assert led is not None and led.closed
    attributed = sum(led.phases.values())
    assert attributed <= led.e2e_s + 1e-9
    assert abs(attributed + led.unattributed_s - led.e2e_s) < 1e-9


def test_solo_statement_conserves(db):
    s = db.session()
    for i in range(6):  # varying literals: registers + warms the fast tier
        s.sql(f"select v from gt where k = {i}").rows()
    _assert_live_conserved(s._gap)
    assert s._gap.phases  # named phases, not one unattributed blob


def test_hammer_8_threads_batched_conserves(db):
    """8 closed-loop threads through the micro-batcher: every final
    ledger conserves, and nothing attributed exceeds its own e2e (the
    window clamp holds under cohort overlap)."""
    sessions = [db.session() for _ in range(8)]
    for s in sessions:
        s.sql("set ob_batch_max_wait_us = 300")
    errs: list = []

    def worker(s, i):
        try:
            for j in range(30):
                s.sql(f"select v from gt where k = {(i * 7 + j) % 64}"
                      ).rows()
                _assert_live_conserved(s._gap)
        except Exception as e:  # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=worker, args=(s, i))
               for i, s in enumerate(sessions)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert errs == []
    for s in sessions:
        _assert_live_conserved(s._gap)
    # registry-level sanity after the hammer: the window ring never
    # reports more device busy than wall
    for w in db.host_tax.snapshot()["windows"]:
        assert w["device_s"] <= w["e2e_s"] + 1e-9


def test_retry_degradation_conserves_and_names_backoff():
    """The errsim OOM ladder (evict -> chunked -> host) retries inside
    one statement: its ledger must still conserve and must name the
    retry backoff instead of leaking it into the residual."""
    from oceanbase_tpu.server import Database
    from oceanbase_tpu.share import retry as R
    from oceanbase_tpu.share.errsim import ERRSIM

    d = Database(n_nodes=1, n_ls=1)
    try:
        s = d.session()
        s.sql("create table rt (id bigint primary key, v bigint)")
        for i in range(0, 2000, 500):
            s.sql("insert into rt values " + ", ".join(
                f"({j}, {j * 37 % 100})" for j in range(i, i + 500)))
        q = "select v, count(*) as n from rt group by v order by v"
        baseline = s.sql(q).rows()
        ERRSIM.arm("EN_DEVICE_OOM", error=R.DeviceOOM("EN_DEVICE_OOM"),
                   prob=1.0, count=3)
        assert s.sql(q).rows() == baseline
        led = s._gap
        _assert_live_conserved(led)
        assert led.phases.get("retry backoff", 0.0) > 0.0
    finally:
        ERRSIM.clear("EN_DEVICE_OOM")
        d.close()


def test_follower_read_conserves(db):
    db.cluster.settle(1.0)  # followers apply the seed before weak reads
    s = db.session()
    s.sql("set ob_read_consistency = 'weak'")
    try:
        rows = s.sql("select count(*) as n from gt").rows()
        assert rows == [(64,)]
        assert s.last_follower_read is not None
        _assert_live_conserved(s._gap)
    finally:
        s.sql("set ob_read_consistency = 'strong'")


def test_streamed_plan_conserves_with_pipeline_hints():
    """A tiny device budget forces the out-of-core streaming pipeline;
    its live H2D/compute hints must land on the statement ledger
    without double-counting against the engine carve."""
    from oceanbase_tpu.server import Database

    d = Database(n_nodes=1, n_ls=1)
    try:
        d.config.set("ob_device_memory_limit", "65536")
        s = d.session()
        s.sql("create table st (id bigint primary key, v bigint not null)")
        for i in range(0, 30000, 1000):
            s.sql("insert into st values " + ", ".join(
                f"({j}, {j % 97})" for j in range(i, i + 1000)))
        q = "select sum(v) as s, count(*) as n from st where v < 50"
        s.sql(q).rows()
        chunks0 = d.metrics.counter("stream chunks")
        s.sql(q).rows()
        assert d.metrics.counter("stream chunks") > chunks0
        led = s._gap
        _assert_live_conserved(led)
        assert led.phases.get("h2d", 0.0) > 0.0  # pipeline hinted live
        assert led.device_s > 0.0
    finally:
        d.close()


def test_q1_wide_groupby_serves_from_narrowed_frame():
    """Q1-tail regression pin: the wide group-by whose answer is FOUR
    groups must serve its warm reps through the FUSED narrowed frame —
    one dispatch, one completion roundtrip moving the frame's bytes
    instead of the plan's full pow2 output capacity — bit-identical to
    the unfused path, and the phase timings it leaves behind still
    build a conservation-complete ledger."""
    import time as _time

    from oceanbase_tpu.engine import Session
    from oceanbase_tpu.models.tpch import datagen
    from oceanbase_tpu.models.tpch.sql_suite import QUERIES, UNIQUE_KEYS

    tables = datagen.generate(sf=0.01)
    sess = Session(tables, unique_keys=UNIQUE_KEYS)
    nc0 = sess.executor.narrow_compiles
    sess.sql(QUERIES[1]).rows()  # compile + first run builds the frame
    t0 = _time.perf_counter()
    rs = sess.sql(QUERIES[1])  # warm rep: fused narrowed dispatch
    cur = rs._cursor
    assert cur.narrowed
    warm_rows = rs.rows()
    e2e = _time.perf_counter() - t0
    phases = dict(rs.phases)
    # built ONCE, reused warm — a retrace per rep would be its own tail
    assert sess.executor.narrow_compiles == nc0 + 1
    # Q1's root is an order-by, so the frame seeds at the 256-row
    # default — a 4-group answer never grows it, and the committed
    # host frame IS that pow2 width (the completion sync moved ncap
    # rows per column, not the group table's capacity)
    assert cur._ncap <= type(cur).NARROW_SEED_ROWS
    assert int(cur._hsel.shape[-1]) == cur._ncap
    frame_bytes = sum(
        int(getattr(a, "nbytes", 0))
        for d in (cur._hcols, cur._hvalid) for a in d.values()
    ) + int(cur._hsel.nbytes)
    # unfused A/B off the SAME cached plan, through the plan's own
    # opt-out: full-capacity result frame
    cur.prepared._narrow_off = True
    try:
        rs_off = sess.sql(QUERIES[1])
        off_rows = rs_off.rows()
        cur_off = rs_off._cursor
    finally:
        cur.prepared._narrow_off = False
    assert cur_off.prepared is cur.prepared and not cur_off.narrowed
    assert warm_rows == off_rows  # bit-identical through the fusion
    # the D2H diet, pinned scale-independently: every committed leaf is
    # exactly frame-width, so the completion roundtrip moves O(ncap)
    # bytes no matter how wide the plan's INTERNAL capacities grow (the
    # Q1 tail was an O(capacity) fetch hiding behind the group table)
    assert all(int(a.shape[-1]) == cur._ncap
               for a in cur._hcols.values())
    assert frame_bytes <= cur._ncap * (
        len(cur._hcols) + len(cur._hvalid) + 1) * 8
    # the narrowed rep's phase dict builds a conservation-complete
    # ledger with the dispatch named (the regression mode was the tail
    # hiding in an unattributed fetch blob)
    led = GapLedger.from_phases(e2e, phases)
    conserved(led)
    assert led.phases.get("device dispatch", 0.0) > 0.0


def test_vt_sysstat_and_snapshot_surfaces_live(db):
    s = db.session()
    for i in range(4):
        s.sql(f"select v from gt where k = {i}").rows()
    rs = s.sql(
        "select digest, executions, unattributed_pct, phases_json "
        "from __all_virtual_host_tax")
    rows = rs.rows()
    assert rows
    dig, execs, unattr_pct, pj = max(rows, key=lambda r: r[1])
    assert execs >= 4 and 0.0 <= unattr_pct <= 100.0
    phases = json.loads(pj)
    assert phases and all(v >= 0.0 for v in phases.values())
    assert db.metrics.counter("host tax statements") >= execs
    # audit ring carries the per-statement columns
    rec = db.audit.records()[-1]
    assert rec.chip_idle_us >= 0 and rec.unattributed_us >= 0
    # workload snapshots embed the registry for awr_report's window diff
    snap = db.workload.take(db)
    assert snap["host_tax"]["digests"]
    assert "window_s" in snap["host_tax"]


_SPAN_COUNTERS = ("catalog refreshes", "catalog refresh rows",
                  "h2d uploads", "h2d bytes")


def _span_counters(db):
    c = db.metrics.counters_snapshot()
    return {k: c.get(k, 0) for k in _SPAN_COUNTERS}


def test_tx_select_names_the_rescan_and_the_upload(db):
    """A SELECT inside BEGIN of a table the transaction wrote rescans its
    tablet and uploads a private table: both are named phases, carved (the
    dispatch keeps only what is not the upload, no clamp), and counted
    once in sysstat."""
    s = db.session()
    q = "select sum(v) as s from gt where k < 40"
    s.sql("begin")
    try:
        s.sql("update gt set v = v where k = 0")
        s.sql(q).rows()  # the plan's first run measures its footprint
        ex = db.engine.executor
        c0, b0 = _span_counters(db), ex.h2d_bytes
        rs = s.sql(q)
        assert rs.rows() == [(sum(3 * i for i in range(40)),)]
        _assert_live_conserved(s._gap)
        phases = dict(s._gap.phases)  # the session's ledger is reused
        c1 = _span_counters(db)
    finally:
        s.sql("commit")
    assert phases["catalog refresh"] > 0.0
    assert phases["h2d"] == pytest.approx(rs.phases["h2d_s"])
    assert phases["device dispatch"] == pytest.approx(
        rs.phases["dispatch_s"] - rs.phases["h2d_s"])
    moved = {k: c1[k] - c0[k] for k in c1}
    assert moved["catalog refreshes"] == 1
    assert moved["catalog refresh rows"] == 64
    assert moved["h2d uploads"] == 1
    assert moved["h2d bytes"] == ex.h2d_bytes - b0 > 0


def test_autocommit_select_pays_neither(db):
    s = db.session()
    q = "select sum(v) as s from gt where k < 30"
    s.sql(q).rows()
    c0 = _span_counters(db)
    for _ in range(3):
        s.sql(q).rows()
        assert not {"catalog refresh", "h2d"} & set(s._gap.phases)
    assert _span_counters(db) == c0


def test_private_upload_reaches_the_transfer_bytes(db):
    """Repair (PR 37): `_build_batch` counts into `h2d_bytes`, so a
    SELECT inside a transaction reports its upload in its profile and in
    the plan monitor's transfer bytes, and the footprint memo still
    hits (one upload a statement, not two)."""
    s = db.session()
    q = "select count(*) as n from gt where v > 30"
    s.sql("begin")
    try:
        s.sql("update gt set v = v where k = 0")  # its reads rescan
        s.sql(q).rows()
        c0 = _span_counters(db)
        rs = s.sql(q)
        rs.rows()
        c1 = _span_counters(db)
    finally:
        s.sql("commit")
    assert c1["h2d uploads"] - c0["h2d uploads"] == 1
    assert rs.profile.h2d_bytes == c1["h2d bytes"] - c0["h2d bytes"] > 0
    assert rs.profile.transfer_bytes >= rs.profile.h2d_bytes


def test_host_tax_surfaces_name_the_new_phases(db):
    """What tools/hosttax_smoke.py held and no test did: the statement
    sits in __all_virtual_host_tax with its phases, sysstat carries the
    counters, the audit row carries chip idle."""
    s = db.session()
    q = "select max(v) as m from gt where k < 50"
    s.sql("begin")
    try:
        s.sql(q).rows()
    finally:
        s.sql("commit")
    rows = s.sql("select digest, executions, phases_json "
                 "from __all_virtual_host_tax").rows()
    named = [json.loads(pj) for _d, _n, pj in rows]
    assert any({"catalog refresh", "h2d"} <= set(p) for p in named)
    stat = dict(s.sql("select name, value from __all_virtual_sysstat "
                      "where name like 'catalog refresh%'").rows())
    assert stat.get("catalog refresh rows", 0) >= 64
    assert any(r.chip_idle_us > 0 for r in db.audit.records()
               if r.stmt_type == "Select")


def test_phase_order_covers_wired_phases():
    """Every phase name the serving stack emits renders in canonical
    order — a new phase added to the wiring must join PHASE_ORDER."""
    for name in ("setup", "fast lookup", "batch window", "retry backoff",
                 "governor reserve", "h2d", "completion fold",
                 "catalog refresh"):
        assert name in GL.PHASE_ORDER
