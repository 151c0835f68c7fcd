"""Device-memory governor: HBM ledger, OOM-safe admission, and the
three-rung degradation ladder (engine/memory_governor.py + the retry
taxonomy wiring in server/database.py).

Covers the PR's acceptance surface directly:
  - the ledger balances to ZERO bytes under an 8-thread reservation
    hammer that forces mid-reservation errors through the Reservation
    context manager;
  - EN_DEVICE_OOM (the errsim twin of XlaRuntimeError
    RESOURCE_EXHAUSTED) walks the ladder exactly once per rung, in
    order — evict, chunked re-plan, host fallback — with bit-identical
    rows and every rung visible in sysstat;
  - a tenant at its TenantUnit.memory_limit QUEUES (and surfaces the
    deadline as DeviceMemoryTimeout) instead of evicting another
    tenant's residency;
  - the device_memory_pressure sentinel rule is edge-triggered and
    deduplicated like replica_unreachable;
  - __all_virtual_memory_governor exposes the live ledger over SQL.
"""

import random
import threading

import pytest

from oceanbase_tpu.engine.memory_governor import (
    AUTO_HBM_FRACTION, SYNTHETIC_CPU_BUDGET, MemoryGovernor, Reservation,
    derive_chunk_rows, detect_device_budget)
from oceanbase_tpu.server import Database
from oceanbase_tpu.server.database import TenantUnit
from oceanbase_tpu.server.sentinel import HealthSentinel, evaluate_window
from oceanbase_tpu.server.tenant import TenantManager
from oceanbase_tpu.share import retry as R
from oceanbase_tpu.share.errsim import DEFAULT_SEED, ERRSIM


@pytest.fixture(autouse=True)
def _clean():
    yield
    ERRSIM.clear()
    ERRSIM.reseed(DEFAULT_SEED)


# ------------------------------------------------------------ pure ledger


def test_grant_charges_and_release_refunds():
    gov = MemoryGovernor(budget=1 << 20)
    r = gov.reserve("sys", 1000, timeout_s=0.1)
    assert r is not None and r.nbytes == 1000
    assert gov.reserved == 1000 and gov.grants == 1
    r.release()
    r.release()  # idempotent — double release must not go negative
    assert gov.reserved == 0 and gov.ledger_balanced()


def test_zero_byte_reservation_is_free():
    gov = MemoryGovernor(budget=1 << 20)
    with gov.reserve("sys", 0) as r:
        assert isinstance(r, Reservation) and r.nbytes == 0
        assert gov.reserved == 0
    assert gov.ledger_balanced()


def test_oversized_request_clamped_runs_strictly_alone():
    # a single statement larger than the whole budget must still run
    # (clamped, degrading via the ladder) — just with nothing beside it
    gov = MemoryGovernor(budget=10_000)
    big = gov.reserve("sys", 1 << 30, timeout_s=0.1)
    assert big is not None and big.nbytes == gov.effective_budget()
    assert gov.reserve("sys", 1, timeout_s=0.05) is None  # pool is full
    assert gov.rejects == 1
    big.release()
    assert gov.ledger_balanced()


def test_note_oom_shrinks_multiplicatively_with_floor():
    gov = MemoryGovernor(budget=1000)
    for _ in range(20):
        gov.note_oom()
    assert gov.effective_budget() == 250  # OOM_SHRINK_FLOOR
    assert gov.oom_notes == 20
    gov.reset_shrink()
    assert gov.effective_budget() == 1000


def test_waiter_clamps_against_the_shrunk_pool():
    # note_oom() while a request waits: the waiter must re-clamp to the
    # NEW effective budget, not deadlock against its stale first clamp
    gov = MemoryGovernor(budget=1000)
    hold = gov.reserve("sys", 1000, timeout_s=0.1)
    got = []

    def waiter():
        got.append(gov.reserve("sys", 900, timeout_s=5.0))

    th = threading.Thread(target=waiter)
    th.start()
    gov.note_oom()  # effective budget now 750 < the waiter's 900
    hold.release()
    th.join(timeout=10)
    assert got and got[0] is not None
    assert got[0].nbytes == 750  # granted the re-clamped size
    got[0].release()
    assert gov.ledger_balanced()


def test_queue_depth_backpressure_rejects_without_waiting():
    gov = MemoryGovernor(budget=1000, max_queue=1)
    hold = gov.reserve("sys", 1000, timeout_s=0.1)
    stop = threading.Event()

    def parked():
        r = gov.reserve("sys", 500, timeout_s=30.0)
        stop.wait()
        if r is not None:
            r.release()

    th = threading.Thread(target=parked, daemon=True)
    th.start()
    for _ in range(100):  # wait for the parked thread to enter the queue
        with gov._cond:
            if gov._waiters >= 1:
                break
        threading.Event().wait(0.01)
    # queue is at max depth: the next request bounces immediately
    assert gov.reserve("sys", 1, timeout_s=30.0) is None
    assert gov.rejects == 1
    hold.release()
    stop.set()
    th.join(timeout=10)
    assert gov.ledger_balanced()


def test_tenant_lone_statement_always_admissible():
    # an over-resident tenant degrades its OWN working set (server-side
    # eviction) instead of deadlocking at admission: with no outstanding
    # reservations its statement is granted, clamped to its share
    gov = MemoryGovernor(budget=1 << 20)
    gov.register_tenant("tiny", 30 * 1024, resident_fn=lambda: 48 * 1024)
    r = gov.reserve("tiny", 16 << 20, timeout_s=0.1)
    assert r is not None and r.nbytes == 30 * 1024
    # but a SECOND concurrent reservation is gated by the shared quota
    assert gov.reserve("tiny", 1024, timeout_s=0.05) is None
    r.release()
    assert gov.ledger_balanced()


def test_derive_chunk_rows_bounds():
    assert derive_chunk_rows(0, 1 << 20) == 4096  # floor: forward progress
    assert derive_chunk_rows(1 << 40, 65536) == 65536  # cap: the default
    assert derive_chunk_rows(128 * 10_000, 1 << 20) == 10_000


class _FakeDevice:
    def __init__(self, platform, stats):
        self.platform = platform
        self._stats = stats

    def memory_stats(self):
        return self._stats


@pytest.mark.parametrize("platform, stats, want", [
    ("cpu", None, SYNTHETIC_CPU_BUDGET),
    ("tpu", {"bytes_limit": 16 << 30}, int((16 << 30) * AUTO_HBM_FRACTION)),
    ("tpu", {}, RuntimeError),
    ("tpu", None, RuntimeError),
])
def test_detect_device_budget_is_synthetic_only_on_cpu(
        monkeypatch, platform, stats, want):
    """A chip whose limit cannot be read must not get the CPU's made-up
    2 GiB: that would route resident-sized scans into chunk streaming."""
    import jax

    monkeypatch.delenv("OB_TPU_SYNTHETIC_HBM", raising=False)
    monkeypatch.setattr(
        jax, "devices", lambda *a: [_FakeDevice(platform, stats)])
    if want is RuntimeError:
        with pytest.raises(RuntimeError, match="bytes_limit"):
            detect_device_budget()
    else:
        assert detect_device_budget() == want


class _Boom(Exception):
    pass


def test_reservation_hammer_8_threads_exact_balance():
    """8 threads hammer reserve/release with forced mid-reservation
    errors: afterwards the ledger must balance to exactly zero bytes —
    no leak from any error path — and every request must have been
    granted (nothing timed out or bounced)."""
    gov = MemoryGovernor(budget=1 << 20, max_queue=64)
    gov.register_tenant("even", None)
    gov.register_tenant("odd", 600_000)
    iters, nthreads = 150, 8
    granted = [0] * nthreads
    failed: list[Exception] = []

    def worker(tid: int):
        rng = random.Random(0xA11CE + tid)
        tenant = "even" if tid % 2 == 0 else "odd"
        for _ in range(iters):
            nbytes = rng.randrange(1, 300_000)
            r = gov.reserve(tenant, nbytes, timeout_s=30.0)
            if r is None:
                failed.append(TimeoutError(f"t{tid} starved"))
                return
            granted[tid] += 1
            try:
                with r:
                    if rng.random() < 0.3:
                        raise _Boom()  # error path: __exit__ must refund
            except _Boom:
                pass

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(nthreads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not failed
    assert sum(granted) == iters * nthreads == gov.grants
    assert gov.rejects == 0
    assert gov.reserved == 0 and gov.ledger_balanced()
    assert gov.peak_reserved <= gov.budget  # never over-committed
    st = gov.stats()
    assert all(t["reserved"] == 0 for t in st["tenants"].values())


# --------------------------------------------------- taxonomy + ladder


def test_real_xla_oom_classified_as_device_oom():
    # a genuine jaxlib XlaRuntimeError is matched structurally (type
    # name + RESOURCE_EXHAUSTED status) so no jaxlib import is needed
    class XlaRuntimeError(Exception):
        pass

    err = XlaRuntimeError("RESOURCE_EXHAUSTED: Out of memory allocating "
                          "1073741824 bytes")
    assert R.classify(err) is R.DEVICE_OOM
    assert R.classify(R.DeviceOOM("EN_DEVICE_OOM")) is R.DEVICE_OOM
    assert R.classify(XlaRuntimeError("INTERNAL: no oom")) is R.NOT_RETRYABLE
    assert R.classify(R.DeviceMemoryTimeout("q")) is R.DEVICE_MEMORY
    assert R.DEVICE_OOM.max_retries == 3  # exactly one retry per rung


def test_errsim_ladder_walks_every_rung_once_in_order():
    """EN_DEVICE_OOM armed to fire 3 times against one SELECT: the
    statement must absorb all three — rung 1 evicts cold residency and
    shrinks the pool, rung 2 re-plans chunked, rung 3 falls back to
    host — and still return rows bit-identical to the unfaulted run."""
    db = Database(n_nodes=1, n_ls=1)
    try:
        s = db.session()
        s.sql("create table lt (id bigint primary key, v bigint)")
        for i in range(0, 3000, 500):
            vals = ", ".join(f"({j}, {j * 37 % 100})"
                             for j in range(i, i + 500))
            s.sql(f"insert into lt values {vals}")
        q = ("select v, count(*) as n, sum(id) as s from lt "
             "group by v order by v")
        baseline = s.sql(q).rows()
        assert len(baseline) == 100
        m0 = {k: db.metrics.counter(k) for k in (
            "device OOM retries", "stmt degraded chunked",
            "stmt degraded host")}

        ERRSIM.arm("EN_DEVICE_OOM", error=R.DeviceOOM("EN_DEVICE_OOM"),
                   prob=1.0, count=3)
        rows = s.sql(q).rows()

        assert rows == baseline  # bit-identical through all three rungs
        assert s._ladder == ["evict", "chunked", "host"]
        assert ERRSIM.fired("EN_DEVICE_OOM") == 3
        assert db.metrics.counter("device OOM retries") - m0[
            "device OOM retries"] == 3
        assert db.metrics.counter("stmt degraded chunked") - m0[
            "stmt degraded chunked"] == 1
        assert db.metrics.counter("stmt degraded host") - m0[
            "stmt degraded host"] == 1
        assert db.governor.oom_notes >= 1  # rung 1 shrank the pool
        assert db.governor.ledger_balanced()

        # the ladder is per-statement state: the NEXT statement starts
        # clean on the normal path
        assert s.sql(q).rows() == baseline
        assert s._ladder == []
    finally:
        db.close()


def test_ladder_state_resets_after_degraded_statement():
    db = Database(n_nodes=1, n_ls=1)
    try:
        s = db.session()
        s.sql("create table r1 (id bigint primary key, v bigint)")
        s.sql("insert into r1 values (1, 10), (2, 20)")
        ERRSIM.arm("EN_DEVICE_OOM", error=R.DeviceOOM("EN_DEVICE_OOM"),
                   prob=1.0, count=2)
        rows = s.sql("select v from r1 order by id").rows()
        assert rows == [(10,), (20,)]
        assert s._ladder == ["evict", "chunked"]  # stopped at rung 2
        assert s._degrade_mode == "chunk"
        ERRSIM.clear("EN_DEVICE_OOM")
        s.sql("select v from r1 order by id")
        assert s._degrade_mode is None and s._ladder == []
    finally:
        db.close()


# -------------------------------------------------- tenant accounting


def test_tenant_at_limit_queues_rather_than_evicting_neighbour():
    """Satellite regression for TenantUnit.memory_limit's extended
    semantics: governor reservations and resident snapshot bytes charge
    the SAME per-tenant quota. A tenant whose share is fully reserved
    queues on the 'device memory reservation' wait event and surfaces
    DeviceMemoryTimeout — it never evicts another tenant's residency."""
    mgr = TenantManager(n_nodes=1, n_ls=1)
    hot = mgr.create_tenant("hot", unit=TenantUnit(memory_limit=48 * 1024))
    cold = mgr.create_tenant("cold")
    sh, sc = hot.session(), cold.session()
    sh.sql("create table h (id bigint primary key, v bigint)")
    sh.sql("insert into h values (1, 1), (2, 2)")
    sc.sql("create table c (id bigint primary key, v bigint)")
    sc.sql("insert into c values (1, 1)")
    sc.sql("select count(*) as n from c")  # materialize cold's residency
    cold_v = cold.db.tables["c"].cached_data_version
    assert cold_v != -1

    gov = hot.db.governor
    assert gov is cold.db.governor  # one cluster-shared ledger
    sh.sql("alter system set ob_governor_queue_timeout = 0.05")
    # saturate hot's share with a live reservation (a long statement's
    # grant), then drive another statement through admission
    held = gov.reserve("hot", 48 * 1024, timeout_s=1.0)
    assert held is not None and held.nbytes == 48 * 1024
    rejects0 = hot.db.metrics.counter("device memory rejects")
    with pytest.raises(R.DeviceMemoryTimeout):
        sh.sql("select count(*) as n from h")
    assert hot.db.metrics.counter("device memory rejects") > rejects0
    # the neighbour's residency was never touched to make room
    assert cold.db.tables["c"].cached_data_version == cold_v
    assert gov.stats()["tenants"]["cold"]["reserved"] == 0

    held.release()
    assert sh.sql("select count(*) as n from h").columns["n"][0] == 2
    assert gov.ledger_balanced()


# ------------------------------------------------------------ sentinel


def _snap(snap_id, ts, **kw):
    base = {"snap_id": snap_id, "ts": ts, "summary": [], "access": [],
            "census": [], "sysstat": {}, "timeline": [],
            "timeline_meta": {}, "qos": {}, "governor": {}}
    base.update(kw)
    return base


def _pressure_pair(first_p99=0.0, host=1):
    first = _snap(1, 100.0, governor={"wait_p99_s": first_p99},
                  sysstat={"device OOM retries": 0})
    last = _snap(2, 160.0, governor={"wait_p99_s": 0.2, "reserved": 4096,
                                     "effective_budget": 8192,
                                     "shrink": 0.75},
                 sysstat={"device OOM retries": 3,
                          "stmt degraded chunked": 1,
                          "stmt degraded host": host})
    return first, last


def test_sentinel_pressure_fires_critical_on_host_fallback():
    alerts = evaluate_window(*_pressure_pair(host=1))
    got = [a for a in alerts if a["rule"] == "device_memory_pressure"]
    assert len(got) == 1
    a = got[0]
    assert a["severity"] == "critical"  # host fallback = data-path impact
    assert a["evidence"]["degraded"] == 5
    assert a["evidence"]["host"] == 1


def test_sentinel_pressure_warns_without_host_fallback():
    alerts = evaluate_window(*_pressure_pair(host=0))
    got = [a for a in alerts if a["rule"] == "device_memory_pressure"]
    assert got and got[0]["severity"] == "warn"


def test_sentinel_pressure_is_edge_triggered():
    # a window that STARTS pressured must not re-fire: pressure has to
    # clear before the next alert (replica_unreachable's discipline)
    alerts = evaluate_window(*_pressure_pair(first_p99=0.2))
    assert not [a for a in alerts if a["rule"] == "device_memory_pressure"]


def test_sentinel_pressure_needs_degraded_executions():
    first = _snap(1, 100.0)
    last = _snap(2, 160.0, governor={"wait_p99_s": 0.2})  # waits, no harm
    alerts = evaluate_window(first, last)
    assert not [a for a in alerts if a["rule"] == "device_memory_pressure"]


def test_sentinel_pressure_dedups_on_reobservation():
    sent = HealthSentinel(clock=lambda: 0.0)
    first, last = _pressure_pair()
    fresh = sent.observe(first, last)
    assert any(a.rule == "device_memory_pressure" for a in fresh)
    assert sent.observe(first, last) == []  # same window: no duplicate


# ------------------------------------------------------- virtual table


def test_virtual_memory_governor_readable_over_sql():
    db = Database(n_nodes=1, n_ls=1)
    try:
        s = db.session()
        s.sql("create table vt (id bigint primary key, v bigint)")
        s.sql("insert into vt values (1, 1)")
        s.sql("select count(*) as n from vt")  # drives >= 1 reservation
        rs = s.sql("select metric, value from __all_virtual_memory_governor")
        led = dict(zip(rs.columns["metric"], rs.columns["value"]))
        assert led["budget"] > 0
        assert 0 < led["effective_budget"] <= led["budget"]
        assert led["grants"] >= 1
        # the reading SELECT holds its own admission grant while the VT
        # row is snapped — the ledger reports it, charged to sys
        assert led["reserved"] == led["reserved:sys"] > 0
        assert led["limit:sys"] == -1  # sys tenant: unlimited share
        assert db.governor.ledger_balanced()  # released at statement end
    finally:
        db.close()
