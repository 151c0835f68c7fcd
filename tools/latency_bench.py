#!/usr/bin/env python
"""Serving-latency benchmark: warm statement throughput through the server.

The headline bench (bench.py) measures device throughput on analytic scans;
this one measures the OTHER limiter BENCH_r05 surfaced — per-statement host
overhead (Q6: 720x CPU on-device, 31x end-to-end). It drives repeated
parameterized statements through a real DbSession and reports:

  - warm statements/sec and p50/p99 latency per workload;
  - the serving-phase breakdown (fastparse / bind / dispatch / fetch) from
    the sql_audit ring, i.e. exactly what `select ... from
    __all_virtual_sql_audit` shows a DBA;
  - the full-statement host-tax waterfall (per-phase mean us, chip-idle %,
    unattributed residual) from the conservation ledger behind
    __all_virtual_host_tax, per workload and per serve leg;
  - the fast-path hit rate over the timed (warm) window;
  - an A/B against the same statements with the text tier disabled
    (plan_cache.fast_enabled = False): the full tokenize/parse/plan path
    with a warm LOGICAL plan cache, isolating the fast tier's contribution.

Workloads:
  point  - `select v from kv where k = ?` cycling K values: a parameterized
           point read on a non-indexed column (an indexed predicate takes
           the DAS route, which serves cold statements host-side);
  agg    - `select sum(v), count(*) from kv where k < ?` cycling bounds:
           parameterized cached aggregate;
  repeat - one identical group-by repeated verbatim: the pure text-hit case.

One-line JSON contract (last stdout line is always complete, exit 0):
  {"metric": "serving_stmts_per_sec", "value": <point warm stmts/s>,
   "vs_baseline": <speedup vs no-fastpath>, "detail": {...}}

Multi-session serving mode (--sessions N): N closed-loop threads, each
with its own DbSession, hammer the SAME parameterized point read through
the server concurrently — the cross-session micro-batcher's target
shape. Reports aggregate stmts/s + p50/p99 per statement + mean batch
size + batched-executable compile count, as an in-process A/B (batching
on vs off over identical workloads). --serve-strict gates CI: batches
must actually form (mean batch size > 1) and the compile count must stay
within the pow2 bucket bound.

Wire serving mode (--wire-sessions N): the front-end A/B. N REAL MySQL
protocol connections (raw sockets, selector-multiplexed closed-loop
clients) hammer the same point read through the THREADED MySqlFrontend
(one server thread per connection) and then through the async
front end (AsyncMySqlFrontend: one event loop + a bounded worker pool),
same database and batcher settings for both legs. Reports aggregate
stmts/s and per-statement p50/p99 per leg plus the async-vs-threaded
speedup. --wire-strict gates CI: speedup >= --wire-min-speedup and the
async leg's p99 <= 3x its p50.

Fairness mode (--fairness): two tenants on one shared cluster — quiet
(TenantUnit.weight 4, few sessions) vs noisy (weight 1, flooding) —
through the shared continuous-batching dispatch gate. Measures the
quiet tenant's p99 alone and under the flood; --fairness-strict gates
the ratio at --fairness-limit (default 2.0) and reports the gate's
per-tenant admission split.

Env/flags: --rows (table size, default 20000), --stmts (timed statements
per workload, default 300), --warmup (default 20), --strict (exit 1 unless
the warm window's fast-path hit rate is 100%), --sessions (enable serving
mode), --serve-seconds (per A/B leg, default 2.5), --batch-wait-us /
--batch-max-size (batcher knobs for the ON leg), --serve-strict,
--wire-sessions / --wire-seconds / --wire-strict / --wire-min-speedup /
--async-workers, --fairness / --fairness-seconds / --fairness-strict /
--fairness-limit, LATENCY_BUDGET_S (default 300; stops starting new
workloads near the budget, partial results still emit).
"""

import argparse
import json
import os
import selectors
import socket
import struct
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

START = time.monotonic()


def elapsed() -> float:
    return time.monotonic() - START


# BENCH_OUT=<path>: also write each emitted summary as a JSON line to a
# stable artifact path (truncated on the first emit of a run) so CI can
# collect results without scraping stdout.
_BENCH_OUT = os.environ.get("BENCH_OUT")
_bench_out_started = False


def emit(obj) -> None:
    print(json.dumps(obj), flush=True)
    global _bench_out_started
    if _BENCH_OUT:
        with open(_BENCH_OUT, "a" if _bench_out_started else "w") as f:
            f.write(json.dumps(obj) + "\n")
        _bench_out_started = True


def build_db(rows: int):
    from oceanbase_tpu.server.database import Database

    db = Database(n_nodes=1, n_ls=1)
    s = db.session()
    s.sql("create table kv (id int primary key, k int, v int, grp int)")
    rng = np.random.default_rng(7)
    vals = rng.integers(0, 1000, size=rows)
    chunk = 500
    for lo in range(0, rows, chunk):
        hi = min(lo + chunk, rows)
        tuples = ", ".join(
            f"({i + 1}, {i}, {int(vals[i])}, {i % 16})" for i in range(lo, hi)
        )
        s.sql(f"insert into kv values {tuples}")
    return db, s


def percentiles(lat_s: np.ndarray) -> dict:
    return {
        "p50_us": round(float(np.percentile(lat_s, 50)) * 1e6, 1),
        "p99_us": round(float(np.percentile(lat_s, 99)) * 1e6, 1),
        "mean_us": round(float(lat_s.mean()) * 1e6, 1),
    }


def run_stmts(sess, stmts) -> np.ndarray:
    lat = np.empty(len(stmts))
    for i, q in enumerate(stmts):
        t0 = time.perf_counter()
        rs = sess.sql(q)
        rs.rows()  # client consumes the result: lazy fetch cost included
        lat[i] = time.perf_counter() - t0
    return lat


def phase_breakdown(db, n: int) -> dict:
    """Mean serving-phase times over the last n fast-path audit records —
    read directly from the ring (a SELECT on the virtual table would
    itself audit)."""
    recs = [r for r in db.audit.records() if r.is_fast_path][-n:]
    if not recs:
        return {}
    m = len(recs)
    return {
        "fastparse_us": round(sum(r.fastparse_us for r in recs) / m, 1),
        "bind_us": round(sum(r.bind_us for r in recs) / m, 1),
        "dispatch_us": round(sum(r.dispatch_us for r in recs) / m, 1),
        "fetch_us": round(sum(r.fetch_us for r in recs) / m, 1),
    }


def ledger_waterfall(db, before: dict) -> dict:
    """Mean per-statement host-tax waterfall since `before` (a
    db.host_tax.snapshot()): every e2e nanosecond in a named phase or
    the explicit unattributed residual — the full-statement complement
    to the audit-ring engine spans, straight from the conservation
    ledger behind __all_virtual_host_tax."""
    reg = getattr(db, "host_tax", None)
    if reg is None or not reg.enabled:
        return {}
    b = before.get("digests", {})
    n = 0
    e2e = dev = una = 0.0
    phases: dict = {}
    for dig, a in reg.snapshot()["digests"].items():
        z = b.get(dig, {})
        dn = a["count"] - z.get("count", 0)
        if dn <= 0:
            continue
        n += dn
        e2e += a["e2e_s"] - z.get("e2e_s", 0.0)
        dev += a["device_s"] - z.get("device_s", 0.0)
        una += a["unattributed_s"] - z.get("unattributed_s", 0.0)
        zp = z.get("phases", {})
        for k, v in a["phases"].items():
            d = v - zp.get(k, 0.0)
            if d > 0.0:
                phases[k] = phases.get(k, 0.0) + d
    if not n or e2e <= 0.0:
        return {}
    return {
        "stmts": n,
        "e2e_us": round(e2e / n * 1e6, 1),
        "chip_idle_pct": round(
            max(0.0, min(1.0, 1.0 - dev / e2e)) * 100.0, 2),
        "unattributed_pct": round(100.0 * una / e2e, 3),
        "phases_us": {k: round(v / n * 1e6, 1) for k, v in
                      sorted(phases.items(), key=lambda kv: -kv[1])},
    }


def pretrace_buckets(db, max_size: int) -> None:
    """Pre-trace every pow2 bucket executable a leg can touch: a
    straggler lane forms a partial batch whose bucket would otherwise
    compile (~100ms) inside the measured window, denting both
    throughput and p99 for one arbitrary cohort."""
    from oceanbase_tpu.ops.hashing import next_pow2
    from oceanbase_tpu.sql import parser as P

    fkey, params, _kinds = P.fast_normalize("select v from kv where k = 0")
    hit = db.engine.fast_lookup(fkey, params)
    if hit is None or not getattr(hit.entry.prepared, "batchable", False):
        return
    prepared = hit.entry.prepared
    qrow = prepared.bind(hit.values, hit.entry.dtypes)
    bucket = 2
    while bucket <= next_pow2(max_size):
        prepared.run_batched_host(np.stack([qrow] * bucket))
        bucket *= 2


class _serving_tunes:
    """Serving tunes applied identically to every A/B leg, the standard
    CPython threaded-server pair: a 20ms GIL switch interval (with tens
    of session threads trading sub-ms statements, the default 5ms
    forces pointless preemptions mid-statement) and gc.freeze + 10x
    gen0 threshold (default thresholds run a gen0 sweep over the whole
    warm engine every ~20 statements, all of it on the GIL)."""

    def __enter__(self):
        import gc

        self._gc = gc
        self._swi = sys.getswitchinterval()
        self._thr = gc.get_threshold()
        sys.setswitchinterval(0.02)
        gc.collect()
        gc.freeze()
        gc.set_threshold(7000, 100, 100)
        return self

    def __exit__(self, *exc):
        self._gc.set_threshold(*self._thr)
        sys.setswitchinterval(self._swi)
        self._gc.unfreeze()
        return False


def run_serve_leg(db, nsessions: int, seconds: float, wait_us: int,
                  max_size: int, batching: bool) -> dict:
    """One closed-loop leg: N session threads hammer the same warm
    parameterized point read for `seconds`. Batcher state and metric
    deltas are scoped to the leg."""
    db.batcher.enabled = batching
    sessions = [db.session() for _ in range(nsessions)]
    for s in sessions:
        s.sql(f"set ob_batch_max_wait_us = {wait_us}")
        s.sql(f"set ob_batch_max_size = {max_size}")
    # warm: entry registered + solo executable traced OUTSIDE the
    # timed window (the solo leg measures serving, not compiles)
    for s in sessions[:2]:
        for k in range(4):
            s.sql(f"select v from kv where k = {k}").rows()
    if batching:
        pretrace_buckets(db, max_size)
    lats: list[list[float]] = [[] for _ in range(nsessions)]
    warm_stop = threading.Event()
    stop = threading.Event()
    b_start = threading.Barrier(nsessions + 1)
    b_warm_done = threading.Barrier(nsessions + 1)
    b_measure = threading.Barrier(nsessions + 1)

    # statement texts precomputed per session: the timed loop measures
    # the serving path, not f-string formatting
    texts = [[f"select v from kv where k = {(i * 17 + j) % 50}"
              for j in range(50)] for i in range(nsessions)]

    def worker(i: int) -> None:
        s = sessions[i]
        lat = lats[i]
        tx = texts[i]
        j = 0
        b_start.wait()
        # untimed concurrent warm: ramp-up forms partial batches, so the
        # pow2 bucket executables (and, batching off, the contended solo
        # path) compile HERE, not inside the measured window
        while not warm_stop.is_set():
            s.sql(tx[j % 50]).rows()
            j += 1
        b_warm_done.wait()
        b_measure.wait()
        while not stop.is_set():
            t0 = time.perf_counter()
            s.sql(tx[j % 50]).rows()
            lat.append(time.perf_counter() - t0)
            j += 1

    threads = [threading.Thread(target=worker, args=(i,), daemon=True)
               for i in range(nsessions)]
    for t in threads:
        t.start()
    b_start.wait()
    warm_stop.wait(0.75)
    warm_stop.set()
    b_warm_done.wait()
    # every worker is idle between the barriers: snapshot cleanly
    c0 = db.metrics.counters_snapshot()
    compiles0 = db.engine.executor.batched_compiles
    ht0 = db.host_tax.snapshot() if getattr(db, "host_tax", None) else {}
    b_measure.wait()
    t_start = time.perf_counter()
    cpu_start = time.process_time()
    stop.wait(seconds)
    stop.set()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t_start
    # process CPU over the measured window only (all threads): a
    # lower-noise numerator than the 1-2 s throughput readings
    cpu_s = time.process_time() - cpu_start
    c1 = db.metrics.counters_snapshot()

    def delta(name: str) -> int:
        return int(c1.get(name, 0) - c0.get(name, 0))

    lat = np.array([x for ls in lats for x in ls])
    total = len(lat)
    batched = delta("stmt batched statements")
    dispatches = delta("stmt batched dispatches")
    solos = delta("stmt batch solo")
    # mean device-dispatch amortization over the whole leg: every
    # statement counts, batched ones share a launch, everything else
    # (solo leaders, bypasses, the OFF leg) launches alone
    launches = dispatches + (total - batched)
    out = {
        "batching": batching,
        "stmts": total,
        "stmts_per_sec": round(total / wall, 1),
        "cpu_us_per_stmt": round(cpu_s / total * 1e6, 3) if total
        else 0.0,
        **(percentiles(lat) if total else {}),
        "batched_stmts": batched,
        "batched_dispatches": dispatches,
        "solo_leaders": solos,
        "batch_bypass": delta("stmt batch bypass"),
        "mean_batch_size": round(batched / dispatches, 2) if dispatches
        else 0.0,
        "mean_stmts_per_launch": round(total / launches, 2) if launches
        else 0.0,
        "batched_compiles": (db.engine.executor.batched_compiles
                             - compiles0),
        # where the leg's milliseconds went, from the conservation
        # ledger: mean per-statement phase waterfall + chip idle over
        # the measured window (includes window-wait for batch followers)
        "host_tax": ledger_waterfall(db, ht0),
    }
    return out


def run_serve(db, args, detail: dict) -> tuple[bool, dict, dict]:
    """In-process A/B: batching OFF then ON over identical closed-loop
    workloads. Returns (strict_ok, off_leg, on_leg)."""
    from oceanbase_tpu.ops.hashing import next_pow2

    with _serving_tunes():
        off = run_serve_leg(db, args.sessions, args.serve_seconds,
                            args.batch_wait_us, args.batch_max_size,
                            batching=False)
        on = run_serve_leg(db, args.sessions, args.serve_seconds,
                           args.batch_wait_us, args.batch_max_size,
                           batching=True)
    db.batcher.enabled = True
    # XLA compile bound: one batched executable per pow2 bucket in
    # [2, next_pow2(max_size)], regardless of traffic shape
    bound = max(int(np.log2(next_pow2(args.batch_max_size))), 1)
    speedup = (on["stmts_per_sec"] / off["stmts_per_sec"]
               if off["stmts_per_sec"] else 0.0)
    serve = {
        "sessions": args.sessions,
        "leg_seconds": args.serve_seconds,
        "batch_wait_us": args.batch_wait_us,
        "batch_max_size": args.batch_max_size,
        "off": off,
        "on": on,
        "batching_speedup": round(speedup, 3),
        "p99_on_vs_p50_off": (
            round(on["p99_us"] / off["p50_us"], 3)
            if on.get("p99_us") and off.get("p50_us") else 0.0),
        "compile_bound_pow2": bound,
        "compiles_within_bound": on["batched_compiles"] <= bound,
    }
    detail["serve"] = serve
    ok = (on.get("mean_batch_size", 0) > 1.0
          and serve["compiles_within_bound"])
    return ok, off, on


# ---------------------------------------------------------------- wire mode


def _wire_handshake(port: int, setup: list) -> socket.socket:
    """One blocking MySQL handshake as root/"" + setup statements;
    returns the socket ready for the non-blocking closed loop."""
    sock = socket.create_connection(("127.0.0.1", port), timeout=30)
    sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)

    def read_n(n: int) -> bytes:
        buf = b""
        while len(buf) < n:
            c = sock.recv(n - len(buf))
            if not c:
                raise ConnectionError("closed during handshake")
            buf += c
        return buf

    def read_pkt() -> bytes:
        head = read_n(4)
        return read_n(int.from_bytes(head[:3], "little"))

    greeting = read_pkt()
    assert greeting[0] == 10, "not a protocol-10 greeting"
    caps = 0x0200 | 0x8000  # PROTOCOL_41 | SECURE_CONNECTION
    login = (struct.pack("<IIB23x", caps, 1 << 24, 33)
             + b"root\x00" + b"\x00")  # empty-password scramble
    sock.sendall(len(login).to_bytes(3, "little") + b"\x01" + login)
    ok = read_pkt()
    if ok[0] != 0x00:
        raise PermissionError(ok[9:].decode(errors="replace"))

    def read_response() -> None:
        first, eofs = True, 0
        while True:
            pkt = read_pkt()
            if first:
                if pkt[0] in (0x00, 0xFF):
                    return
                first = False
            elif pkt[0] == 0xFE and len(pkt) < 9:
                eofs += 1
                if eofs == 2:
                    return

    for q in setup:
        p = b"\x03" + q.encode()
        sock.sendall(len(p).to_bytes(3, "little") + b"\x00" + p)
        read_response()
    return sock


class _WireConn:
    """One closed-loop wire session: a tiny non-blocking state machine
    (send COM_QUERY, parse frames until the response completes, repeat)
    driven by a shared selector — the client side stays O(drivers)
    threads no matter how many sessions it simulates."""

    __slots__ = ("sock", "buf", "out", "first", "eofs", "t0", "lat",
                 "texts", "j")

    def __init__(self, sock: socket.socket, texts: list):
        self.sock = sock
        self.buf = b""
        self.out = b""
        self.first = True
        self.eofs = 0
        self.t0 = 0.0
        self.lat: list[float] = []
        self.texts = texts
        self.j = 0

    def start_next(self) -> None:
        q = self.texts[self.j % len(self.texts)]
        self.j += 1
        p = b"\x03" + q.encode()
        self.out = len(p).to_bytes(3, "little") + b"\x00" + p
        self.first = True
        self.eofs = 0
        self.t0 = time.perf_counter()
        self.flush()

    def flush(self) -> None:
        while self.out:
            try:
                n = self.sock.send(self.out)
            except (BlockingIOError, InterruptedError):
                return
            self.out = self.out[n:]

    def parse(self) -> bool:
        """Consume complete packets from buf; True when one full
        response (OK/ERR, or coldefs+rows closed by the 2nd EOF) ends."""
        buf, pos = self.buf, 0
        done = False
        while len(buf) - pos >= 4:
            n = int.from_bytes(buf[pos:pos + 3], "little")
            if len(buf) - pos < 4 + n:
                break
            b0 = buf[pos + 4]
            pos += 4 + n
            if self.first:
                if b0 in (0x00, 0xFF):
                    done = True
                    break
                self.first = False
            elif b0 == 0xFE and n < 9:
                self.eofs += 1
                if self.eofs == 2:
                    done = True
                    break
        self.buf = buf[pos:]
        return done


def _wire_drive(conns: list, stop: threading.Event, record: list) -> None:
    """One driver thread multiplexing its share of the connections."""
    sel = selectors.DefaultSelector()
    for c in conns:
        c.sock.setblocking(False)
        c.start_next()
        ev = selectors.EVENT_READ
        if c.out:
            ev |= selectors.EVENT_WRITE
        sel.register(c.sock, ev, c)
    active = len(conns)
    while active:
        for key, ev in sel.select(0.05):
            c = key.data
            if ev & selectors.EVENT_WRITE:
                c.flush()
                if not c.out:
                    sel.modify(c.sock, selectors.EVENT_READ, c)
            if ev & selectors.EVENT_READ:
                try:
                    data = c.sock.recv(1 << 16)
                except (BlockingIOError, InterruptedError):
                    continue
                if not data:
                    sel.unregister(c.sock)
                    active -= 1
                    continue
                c.buf += data
                if c.parse():
                    if record[0]:
                        c.lat.append(time.perf_counter() - c.t0)
                    if stop.is_set():
                        sel.unregister(c.sock)
                        active -= 1
                    else:
                        c.start_next()
                        if c.out:
                            sel.modify(c.sock, selectors.EVENT_READ
                                       | selectors.EVENT_WRITE, c)
    sel.close()


def run_wire_leg(db, port: int, nsessions: int, seconds: float,
                 wait_us: int, max_size: int, drivers: int = 4,
                 warm_s: float = 0.75) -> dict:
    """One closed-loop wire leg against whichever server owns `port`."""
    from concurrent.futures import ThreadPoolExecutor

    setup = [f"set ob_batch_max_wait_us = {wait_us}",
             f"set ob_batch_max_size = {max_size}"]
    with ThreadPoolExecutor(max_workers=16) as pool:
        socks = list(pool.map(
            lambda _i: _wire_handshake(port, setup), range(nsessions)))
    texts = [[f"select v from kv where k = {(i * 17 + j) % 50}"
              for j in range(50)] for i in range(nsessions)]
    conns = [_WireConn(s, t) for s, t in zip(socks, texts)]
    stop = threading.Event()
    record = [False]
    drivers = max(1, min(drivers, nsessions))
    shards = [conns[i::drivers] for i in range(drivers)]
    threads = [threading.Thread(target=_wire_drive,
                                args=(shard, stop, record), daemon=True)
               for shard in shards]
    c0 = db.metrics.counters_snapshot()
    for t in threads:
        t.start()
    time.sleep(warm_s)
    record[0] = True
    t_start = time.perf_counter()
    time.sleep(seconds)
    record[0] = False
    wall = time.perf_counter() - t_start
    stop.set()
    for t in threads:
        t.join(timeout=30)
    for s in socks:
        try:
            s.close()
        except OSError:
            pass
    c1 = db.metrics.counters_snapshot()

    def delta(name: str) -> int:
        return int(c1.get(name, 0) - c0.get(name, 0))

    lat = np.array([x for c in conns for x in c.lat])
    total = len(lat)
    batched = delta("stmt batched statements")
    dispatches = delta("stmt batched dispatches")
    return {
        "sessions": nsessions,
        "stmts": total,
        "stmts_per_sec": round(total / wall, 1) if wall else 0.0,
        **(percentiles(lat) if total else {}),
        "batched_stmts": batched,
        "batched_dispatches": dispatches,
        "solo_leaders": delta("stmt batch solo"),
        "mean_batch_size": round(batched / dispatches, 2) if dispatches
        else 0.0,
    }


def run_wire(db, args, detail: dict) -> tuple[bool, dict]:
    """Serving-stack A/B over REAL wire sessions. Baseline leg: the
    threaded thread-per-connection MySqlFrontend on the solo fast path
    (the pre-async serving stack; the old group-commit batcher no
    longer exists, and giving the baseline the NEW continuous scheduler
    would measure front-end framing overhead, not the stack this PR
    replaces). Measured leg: AsyncMySqlFrontend + continuous batching
    on the same db. The worker pool auto-scales with the session count
    (unless --async-workers pins it) — pool width bounds how many
    statements can coalesce per dispatch."""
    from oceanbase_tpu.server.async_front import AsyncMySqlFrontend
    from oceanbase_tpu.server.mysql_front import MySqlFrontend

    workers = args.async_workers or max(8, min(64,
                                               args.wire_sessions // 8))
    s = db.session()
    for k in range(4):
        s.sql(f"select v from kv where k = {k}").rows()
    pretrace_buckets(db, args.batch_max_size)
    with _serving_tunes():
        db.batcher.enabled = False
        fe = MySqlFrontend(db).start()
        try:
            threaded = run_wire_leg(
                db, fe.port, args.wire_sessions, args.wire_seconds,
                args.batch_wait_us, args.batch_max_size,
                drivers=args.wire_drivers)
        finally:
            fe.stop()
        db.batcher.enabled = True
        afe = AsyncMySqlFrontend(db, workers=workers).start()
        try:
            asynced = run_wire_leg(
                db, afe.port, args.wire_sessions, args.wire_seconds,
                args.batch_wait_us, args.batch_max_size,
                drivers=args.wire_drivers)
        finally:
            afe.stop()
    speedup = (asynced["stmts_per_sec"] / threaded["stmts_per_sec"]
               if threaded["stmts_per_sec"] else 0.0)
    p99_vs_p50 = (asynced["p99_us"] / asynced["p50_us"]
                  if asynced.get("p50_us") else 0.0)
    # the tail is where thread-per-connection actually collapses at
    # high session counts (p99 blows out 10x+ while p50 holds); the
    # async stack's flat p99/p50 is the headline serving win
    tail_win = (threaded["p99_us"] / asynced["p99_us"]
                if asynced.get("p99_us") else 0.0)
    wire = {
        "sessions": args.wire_sessions,
        "leg_seconds": args.wire_seconds,
        "async_workers": workers,
        "threaded": threaded,
        "async": asynced,
        "async_speedup": round(speedup, 3),
        "async_p99_vs_p50": round(p99_vs_p50, 3),
        "async_p99_win": round(tail_win, 3),
    }
    detail["wire"] = wire
    ok = (speedup >= args.wire_min_speedup and p99_vs_p50 <= 3.0
          and tail_win >= args.wire_min_tail_win
          and asynced["stmts"] > 0)
    return ok, wire


# ------------------------------------------------------------ fairness mode


def _closed_loop_leg(groups: dict, seconds: float,
                     warm_s: float = 0.5) -> dict:
    """groups: name -> list of (session, texts). Runs every group's
    threads closed-loop for warm+measure; returns name -> lat array."""
    stop = threading.Event()
    rec = threading.Event()
    buckets = {name: [[] for _ in specs] for name, specs in groups.items()}

    def worker(s, texts, bucket) -> None:
        j = 0
        while not stop.is_set():
            t0 = time.perf_counter()
            s.sql(texts[j % len(texts)]).rows()
            dt = time.perf_counter() - t0
            if rec.is_set():
                bucket.append(dt)
            j += 1

    threads = []
    for name, specs in groups.items():
        for i, (s, texts) in enumerate(specs):
            threads.append(threading.Thread(
                target=worker, args=(s, texts, buckets[name][i]),
                daemon=True))
    for t in threads:
        t.start()
    time.sleep(warm_s)
    rec.set()
    time.sleep(seconds)
    rec.clear()
    stop.set()
    for t in threads:
        t.join(timeout=30)
    return {name: np.array([x for b in bs for x in b])
            for name, bs in buckets.items()}


def run_fairness(args, detail: dict) -> tuple[bool, dict]:
    """Two tenants, one shared dispatch gate: quiet (weight 4, 4
    sessions) vs noisy (weight 1, 12 flooding sessions). The quiet
    tenant's p99 under the flood must stay within --fairness-limit of
    its solo run."""
    from oceanbase_tpu.server.database import TenantUnit
    from oceanbase_tpu.server.tenant import TenantManager

    tm = TenantManager(n_nodes=1, n_ls=1)
    quiet = tm.create_tenant("quiet", unit=TenantUnit(weight=4))
    noisy = tm.create_tenant("noisy", unit=TenantUnit(weight=1))
    try:
        for t in (quiet, noisy):
            s = t.db.session()
            s.sql("create table kv (id int primary key, k int, v int)")
            rows = ", ".join(f"({i + 1}, {i}, {i * 7 + 3})"
                             for i in range(50))
            s.sql(f"insert into kv values {rows}")
            for k in range(4):
                s.sql(f"select v from kv where k = {k}").rows()
            pretrace_buckets(t.db, args.batch_max_size)

        def specs(tenant, n):
            out = []
            for i in range(n):
                s = tenant.db.session()
                s.sql(f"set ob_batch_max_wait_us = {args.batch_wait_us}")
                s.sql(f"set ob_batch_max_size = {args.batch_max_size}")
                out.append((s, [f"select v from kv where k = "
                                f"{(i * 17 + j) % 50}" for j in range(50)]))
            return out

        nq, nn = 4, 12
        gate = quiet.db.batcher.gate
        with _serving_tunes():
            solo = _closed_loop_leg({"quiet": specs(quiet, nq)},
                                    args.fairness_seconds)
            gate.admit_log = []
            loaded = _closed_loop_leg(
                {"quiet": specs(quiet, nq), "noisy": specs(noisy, nn)},
                args.fairness_seconds)
        admits = list(gate.admit_log)
        gate.admit_log = None
    finally:
        quiet.db.close()
        noisy.db.close()
    p99_solo = float(np.percentile(solo["quiet"], 99))
    p99_loaded = float(np.percentile(loaded["quiet"], 99))
    ratio = p99_loaded / p99_solo if p99_solo else 0.0
    fair = {
        "quiet_sessions": nq,
        "noisy_sessions": nn,
        "quiet_weight": 4,
        "noisy_weight": 1,
        "leg_seconds": args.fairness_seconds,
        "quiet_solo": {"stmts": len(solo["quiet"]),
                       **percentiles(solo["quiet"])},
        "quiet_loaded": {"stmts": len(loaded["quiet"]),
                         **percentiles(loaded["quiet"])},
        "noisy_loaded": {"stmts": len(loaded["noisy"]),
                         **percentiles(loaded["noisy"])},
        "quiet_p99_ratio": round(ratio, 3),
        "gate_admissions": {"quiet": admits.count("quiet"),
                            "noisy": admits.count("noisy")},
    }
    detail["fairness"] = fair
    ok = (ratio <= args.fairness_limit
          and len(loaded["quiet"]) > 0 and len(loaded["noisy"]) > 0)
    return ok, fair


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=20000)
    ap.add_argument("--stmts", type=int, default=300)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--strict", action="store_true",
                    help="exit 1 unless warm fast-path hit rate is 100%")
    ap.add_argument("--sessions", type=int, default=0,
                    help="closed-loop serving mode: N concurrent sessions")
    ap.add_argument("--serve-seconds", type=float, default=2.5,
                    help="seconds per A/B leg in serving mode")
    ap.add_argument("--batch-wait-us", type=int, default=1000,
                    help="batcher window for the ON leg")
    ap.add_argument("--batch-max-size", type=int, default=16,
                    help="batcher max lanes for the ON leg")
    ap.add_argument("--serve-strict", action="store_true",
                    help="exit 1 unless batches form (mean size > 1) and "
                         "batched compiles stay within the pow2 bound")
    ap.add_argument("--wire-sessions", type=int, default=0,
                    help="wire A/B mode: N real MySQL connections against "
                         "the threaded solo-path baseline then the async "
                         "front end with continuous batching")
    ap.add_argument("--wire-seconds", type=float, default=3.0,
                    help="seconds per wire A/B leg")
    ap.add_argument("--wire-drivers", type=int, default=4,
                    help="client-side selector driver threads")
    ap.add_argument("--wire-strict", action="store_true",
                    help="exit 1 unless async speedup >= --wire-min-speedup "
                         "and async p99 <= 3x async p50")
    ap.add_argument("--wire-min-speedup", type=float, default=1.0,
                    help="CI floor for the async-vs-threaded aggregate "
                         "throughput ratio (both stacks share one GIL "
                         "with the in-process clients, so the aggregate "
                         "is near parity by construction; the tail is "
                         "where the stacks separate)")
    ap.add_argument("--wire-min-tail-win", type=float, default=0.0,
                    help="CI floor for threaded-p99 / async-p99 (0 = "
                         "don't assert; at 128+ sessions the async "
                         "stack measures 8-10x)")
    ap.add_argument("--async-workers", type=int, default=0,
                    help="async front end worker pool size (0 = scale "
                         "with --wire-sessions, 8..64)")
    ap.add_argument("--fairness", action="store_true",
                    help="two-tenant fairness mode through the shared "
                         "dispatch gate")
    ap.add_argument("--fairness-seconds", type=float, default=1.5,
                    help="seconds per fairness leg")
    ap.add_argument("--fairness-strict", action="store_true",
                    help="exit 1 unless the quiet tenant's loaded p99 stays "
                         "within --fairness-limit of its solo p99")
    ap.add_argument("--fairness-limit", type=float, default=2.0,
                    help="max quiet-tenant p99 degradation ratio")
    args = ap.parse_args()
    budget = float(os.environ.get("LATENCY_BUDGET_S", "300"))

    from bench_meta import collect as bench_meta

    rc = 0
    if args.fairness:
        # fairness runs on its own two-tenant cluster (no shared kv db)
        fdetail = {"total_s": None}
        fair_ok, fair = run_fairness(args, fdetail)
        fdetail["total_s"] = round(elapsed(), 1)
        emit({
            "metric": "serving_fairness_quiet_p99_ratio",
            "value": fair["quiet_p99_ratio"],
            "unit": "x",
            "detail": {"fairness": fair, "meta": bench_meta(None),
                       "total_s": fdetail["total_s"]},
        })
        if args.fairness_strict and not fair_ok:
            print("FAIRNESS-STRICT: quiet tenant p99 degraded "
                  f"{fair['quiet_p99_ratio']}x under the noisy flood "
                  f"(limit {args.fairness_limit}x)", file=sys.stderr)
            rc = 1
        if args.wire_sessions <= 0 and args.sessions <= 0:
            return rc

    t0 = time.perf_counter()
    db, sess = build_db(args.rows)

    detail = {
        "rows": args.rows,
        "stmts": args.stmts,
        "setup_s": round(time.perf_counter() - t0, 2),
        # provenance: rev + config fingerprint + active overrides — two
        # artifacts compare cleanly only when these match
        "meta": bench_meta(db),
    }

    if args.wire_sessions > 0:
        wire_ok, wire = run_wire(db, args, detail)
        detail["total_s"] = round(elapsed(), 1)
        emit({
            "metric": "serving_wire_stmts_per_sec",
            "value": wire["async"]["stmts_per_sec"],
            "unit": "stmts/s",
            "vs_baseline": wire["async_speedup"],
            "detail": detail,
        })
        if args.wire_strict and not wire_ok:
            print("WIRE-STRICT: async speedup "
                  f"{wire['async_speedup']}x < {args.wire_min_speedup}x, "
                  f"async p99/p50 {wire['async_p99_vs_p50']}x > 3x, or "
                  f"p99 win {wire['async_p99_win']}x < "
                  f"{args.wire_min_tail_win}x", file=sys.stderr)
            rc = 1
        return rc

    if args.sessions > 0:
        serve_ok, off, on = run_serve(db, args, detail)
        detail["total_s"] = round(elapsed(), 1)
        emit({
            "metric": "serving_concurrent_stmts_per_sec",
            "value": on["stmts_per_sec"],
            "unit": "stmts/s",
            "vs_baseline": detail["serve"]["batching_speedup"],
            "detail": detail,
        })
        if args.serve_strict and not serve_ok:
            print("SERVE-STRICT: batches did not form (mean batch size "
                  f"{on.get('mean_batch_size')}) or compiles exceeded the "
                  f"pow2 bound ({on.get('batched_compiles')})",
                  file=sys.stderr)
            return 1
        return 0

    k_cycle = list(range(0, min(args.rows, 50)))
    workloads = {
        "point": [f"select v from kv where k = {k_cycle[i % len(k_cycle)]}"
                  for i in range(args.stmts)],
        "agg": [f"select sum(v), count(*) from kv where k < {100 + i % 50}"
                for i in range(args.stmts)],
        "repeat": ["select grp, sum(v), count(*) from kv group by grp"]
                  * args.stmts,
    }

    strict_ok = True
    point_fast = point_slow = None
    for name, stmts in workloads.items():
        if elapsed() > budget - 20:
            detail[f"{name}_skipped"] = "budget"
            continue
        # fast path ON: warm, then measure with hit-rate accounting
        db.plan_cache.fast_enabled = True
        run_stmts(sess, stmts[:args.warmup])
        st = db.plan_cache.stats
        h0, m0 = st.fast_hits, st.fast_misses
        ht0 = (db.host_tax.snapshot()
               if getattr(db, "host_tax", None) else {})
        lat = run_stmts(sess, stmts)
        hits, misses = st.fast_hits - h0, st.fast_misses - m0
        rate = hits / max(hits + misses, 1)
        sps = len(stmts) / lat.sum()
        detail[name] = {
            "stmts_per_sec": round(sps, 1),
            **percentiles(lat),
            "warm_fast_hit_rate": round(rate, 4),
            "phases": phase_breakdown(db, len(stmts)),
            "host_tax": ledger_waterfall(db, ht0),
        }
        if rate < 1.0:
            strict_ok = False
        # fast path OFF: same statements, warm logical cache (A/B)
        db.plan_cache.fast_enabled = False
        run_stmts(sess, stmts[:args.warmup])
        lat_off = run_stmts(sess, stmts)
        db.plan_cache.fast_enabled = True
        sps_off = len(stmts) / lat_off.sum()
        detail[name]["no_fastpath_stmts_per_sec"] = round(sps_off, 1)
        detail[name]["no_fastpath_p50_us"] = round(
            float(np.percentile(lat_off, 50)) * 1e6, 1)
        detail[name]["fastpath_speedup"] = round(sps / sps_off, 3)
        if name == "point":
            point_fast, point_slow = sps, sps_off

    detail["total_s"] = round(elapsed(), 1)
    emit({
        "metric": "serving_stmts_per_sec",
        "value": round(point_fast, 1) if point_fast else 0.0,
        "unit": "stmts/s",
        "vs_baseline": (round(point_fast / point_slow, 3)
                        if point_fast and point_slow else 0.0),
        "detail": detail,
    })
    if args.strict and not strict_ok:
        print("STRICT: warm fast-path hit rate below 100%", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    try:
        rc = main()
    except BaseException as e:
        emit({
            "metric": "serving_stmts_per_sec", "value": 0.0,
            "unit": "stmts/s",
            "detail": {"error": f"{type(e).__name__}: {e}",
                       "total_s": round(elapsed(), 1)},
        })
        rc = 0
    sys.exit(rc)
