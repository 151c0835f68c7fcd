"""Async MySQL front door: every connection multiplexed on one event
loop, statement execution on a small bounded worker pool.

The threaded front end (mysql_front.MySqlFrontend) spends one OS thread
per connection — at hundreds of sessions the thread stacks, scheduler
churn and GIL handoffs become the serving ceiling long before the
device does. This server keeps the SAME protocol surface (it reuses
mysql_front's response builders payload-for-payload, so result sets are
byte-identical) but splits the work the way the reference's libeasy
network frontend splits it from the tenant worker pools:

  * protocol work — packet framing, greeting/login, TLS upgrade,
    COM_STMT_PREPARE/CLOSE/RESET bookkeeping, PING — runs on the
    asyncio event loop: O(connections) costs only file descriptors.
  * statement execution — COM_QUERY / COM_STMT_EXECUTE, the parts that
    parse, take locks, and dispatch to the device — runs on a bounded
    ThreadPoolExecutor (`mysql_async_workers` config), which is ALSO
    the statement concurrency the continuous-batching scheduler
    (server/batcher.py) sees: the pool pushes concurrent statements
    into the dispatch gate where they coalesce into batched device
    dispatches instead of 256 threads trampling each other.

Backpressure is end-to-end: a slow client parks its connection
coroutine in `await writer.drain()` (no worker held), and statements
beyond the pool width queue in the executor — surfaced by the batcher
queue-depth / gate-wait telemetry, not by thread explosion.

One detail is version-sensitive: Python 3.10 has no
StreamWriter.start_tls, so the mid-handshake SSLRequest upgrade uses
loop.start_tls on the raw transport and rewires the stream pair by
hand, mirroring what 3.11's start_tls does.
"""

from __future__ import annotations

import asyncio
import threading
from concurrent.futures import ThreadPoolExecutor

from .database import Database
from .mysql_front import (
    _err_packet,
    _ok_packet,
    build_greeting,
    check_login,
    is_ssl_request,
    make_salt,
    query_payloads,
    stmt_execute_payloads,
    stmt_prepare_payloads,
    stmt_reset_payload,
)


class AsyncMySqlFrontend:
    """Selector-loop MySQL listener: same wire surface as
    MySqlFrontend, connections no longer cost a thread each.

    The loop runs on one daemon thread (start() returns once the port
    is bound); `users` follows MySqlFrontend's contract (None = open
    door via the privilege manager, plaintext map reduced to stage-2
    hashes immediately)."""

    def __init__(self, db: Database, host: str = "127.0.0.1",
                 port: int = 0, users: dict[str, str] | None = None,
                 ssl_context=None, workers: int | None = None):
        self.db = db
        if users is not None:
            from ..share.privilege import stage2_hash

            users = {u: stage2_hash(p) for u, p in users.items()}
        self.users = users
        self.ssl_context = ssl_context
        self.host = host
        self._port_req = port
        self.port: int | None = None
        if workers is None:
            try:
                workers = int(db.config["mysql_async_workers"])
            except Exception:  # noqa: BLE001 — config-less Database stub
                workers = 8
        self.workers = max(int(workers), 1)
        self._loop: asyncio.AbstractEventLoop | None = None
        self._server = None
        self._pool: ThreadPoolExecutor | None = None
        self._thread: threading.Thread | None = None
        self._startup_err: BaseException | None = None
        # rolling-restart drain state: while _draining is set the
        # listener is closed and statements on surviving connections are
        # shed with a retryable ER_SERVER_SHUTDOWN instead of entering
        # the worker pool; _inflight counts statements already submitted
        # (those are allowed to finish — drain() waits on them)
        self._draining = threading.Event()
        self._flight_lock = threading.Lock()
        self._inflight = 0
        self.shed = 0

    # ----------------------------------------------------------- lifecycle
    def start(self) -> "AsyncMySqlFrontend":
        ready = threading.Event()
        self._thread = threading.Thread(
            target=self._run, args=(ready,), daemon=True,
            name="mysql-async-loop")
        self._thread.start()
        ready.wait()
        if self._startup_err is not None:
            raise self._startup_err
        return self

    def drain(self, timeout: float = 30.0) -> dict:
        """Graceful drain for a zero-cold-start rolling restart: stop
        accepting connections (listener closed), let statements already
        in the worker pool finish, and shed anything newly queued with a
        retryable ER_SERVER_SHUTDOWN (1053) so the client's router
        redrives it on a peer. Returns {"inflight", "shed"}; resume()
        reopens the same port once the node is back."""
        import time

        self._draining.set()
        loop, srv = self._loop, self._server
        if loop is not None and srv is not None:
            try:
                loop.call_soon_threadsafe(srv.close)
            except RuntimeError:
                pass  # loop already closed
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            with self._flight_lock:
                n = self._inflight
            if n == 0:
                break
            time.sleep(0.005)
        with self._flight_lock:
            n = self._inflight
        return {"inflight": n, "shed": self.shed}

    def resume(self) -> None:
        """Reopen the listener on the SAME port after a drain (the
        restarted node rejoins the serving set at its old address) and
        lift the statement gate."""
        loop = self._loop
        if loop is None or self.port is None:
            raise RuntimeError("resume() before start()")

        async def _reopen():
            self._server = await asyncio.start_server(
                self._serve, self.host, self.port, backlog=512)

        asyncio.run_coroutine_threadsafe(_reopen(), loop).result(timeout=10)
        self._draining.clear()

    def stop(self) -> None:
        loop, thread = self._loop, self._thread
        if loop is None or thread is None:
            return
        try:
            loop.call_soon_threadsafe(loop.stop)
        except RuntimeError:
            pass  # loop already closed
        thread.join(timeout=10)
        if self._pool is not None:
            self._pool.shutdown(wait=False)

    def _run(self, ready: threading.Event) -> None:
        loop = self._loop = asyncio.new_event_loop()
        asyncio.set_event_loop(loop)
        self._pool = ThreadPoolExecutor(
            max_workers=self.workers, thread_name_prefix="mysql-async")
        try:
            self._server = loop.run_until_complete(
                asyncio.start_server(self._serve, self.host,
                                     self._port_req, backlog=512))
            self.port = self._server.sockets[0].getsockname()[1]
        except BaseException as e:  # noqa: BLE001 — surfaced by start()
            self._startup_err = e
            ready.set()
            loop.close()
            return
        ready.set()
        try:
            loop.run_forever()
        finally:
            try:
                self._server.close()
                loop.run_until_complete(self._server.wait_closed())
                tasks = asyncio.all_tasks(loop)
                for t in tasks:
                    t.cancel()
                if tasks:
                    loop.run_until_complete(
                        asyncio.gather(*tasks, return_exceptions=True))
            finally:
                loop.close()

    # ------------------------------------------------------------ protocol
    async def _execute(self, fn, *args):
        """Worker-pool dispatch behind the drain gate: a draining node
        sheds the statement (retryable 1053, no worker touched) instead
        of queueing work it has promised to finish."""
        if self._draining.is_set():
            self.shed += 1
            return [_err_packet(
                1053, "server shutting down: retry on a peer")]
        with self._flight_lock:
            depth = self._inflight  # statements ahead of this one
            self._inflight += 1
        import time as _t

        t0 = _t.perf_counter()

        def timed():
            # worker-pool handoff wait: wall between the event loop
            # posting the statement and a pool thread picking it up —
            # host tax the statement ledger (which opens inside fn)
            # cannot see. Folded post-hoc against the statement's digest
            # as frontend ingress ("wire read"), and recorded under its
            # own name beside the tenant's other queue wait.
            queued_s = _t.perf_counter() - t0
            self.db.metrics.bulk(adds=(("front pool depth", depth),),
                                 waits=(("front pool queue", queued_s),))
            out = fn(*args)
            sess_obj = args[0] if args else None
            ht = getattr(self.db, "host_tax", None)
            dg = getattr(sess_obj, "_last_digest", "")
            if ht is not None and ht.enabled and dg and queued_s > 0.0:
                ht.fold_extra(dg, "wire read", queued_s)
            return out

        try:
            return await self._loop.run_in_executor(self._pool, timed)
        finally:
            with self._flight_lock:
                self._inflight -= 1

    async def _serve(self, reader: asyncio.StreamReader,
                     writer: asyncio.StreamWriter) -> None:
        db, loop = self.db, self._loop
        sess = None
        seq = 0
        # id -> [pieces, nparams, last-bound param types]; the command
        # loop is sequential per connection, so loop-side PREPARE/RESET
        # and pool-side EXECUTE never race on this dict
        stmts: dict[int, list] = {}
        next_stmt = [1]

        async def read_packet() -> bytes:
            nonlocal seq
            head = await reader.readexactly(4)
            n = int.from_bytes(head[:3], "little")
            seq = (head[3] + 1) & 0xFF
            return await reader.readexactly(n) if n else b""

        def send(payloads) -> None:
            nonlocal seq
            buf = bytearray()
            for p in payloads:
                buf += len(p).to_bytes(3, "little")
                buf.append(seq)
                buf += p
                seq = (seq + 1) & 0xFF
            writer.write(bytes(buf))

        try:
            salt = make_salt()
            send([build_greeting(salt, self.ssl_context is not None)])
            await writer.drain()
            login = await read_packet()
            if self.ssl_context is not None and is_ssl_request(login):
                # mid-handshake TLS upgrade; 3.10 has no
                # StreamWriter.start_tls, so rewire like 3.11's does.
                # The packet sequence continues across the upgrade.
                await writer.drain()
                transport = writer.transport
                protocol = transport.get_protocol()
                new_tr = await loop.start_tls(
                    transport, protocol, self.ssl_context,
                    server_side=True)
                writer._transport = new_tr
                protocol._transport = new_tr
                login = await read_packet()
            user = check_login(db, self.users, login, salt)
            if user is None:
                send([_err_packet(1045,
                                  "Access denied (bad credentials)")])
                await writer.drain()
                return
            sess = db.session(user=user)
            send([_ok_packet()])
            await writer.drain()
            while True:
                seq = 0
                pkt = await read_packet()
                if not pkt:
                    return
                cmd = pkt[0]
                if cmd == 0x01:  # COM_QUIT
                    return
                if cmd in (0x0E, 0x02):  # COM_PING / COM_INIT_DB
                    send([_ok_packet()])
                elif cmd == 0x03:  # COM_QUERY -> worker pool
                    send(await self._execute(
                        query_payloads, sess, pkt[1:].decode()))
                elif cmd == 0x16:  # COM_STMT_PREPARE (protocol-only)
                    send(stmt_prepare_payloads(pkt[1:].decode(), stmts,
                                               next_stmt))
                elif cmd == 0x17:  # COM_STMT_EXECUTE -> worker pool
                    send(await self._execute(
                        stmt_execute_payloads, sess, pkt, stmts))
                elif cmd == 0x19:  # COM_STMT_CLOSE (no response)
                    if len(pkt) >= 5:
                        stmts.pop(int.from_bytes(pkt[1:5], "little"),
                                  None)
                    continue
                elif cmd == 0x1A:  # COM_STMT_RESET
                    send([stmt_reset_payload(pkt, stmts)])
                else:
                    send([_err_packet(1047, "unsupported command")])
                # write backpressure: a slow client parks THIS coroutine
                # here — no worker thread, no unbounded send buffer
                await writer.drain()
        except (asyncio.IncompleteReadError, ConnectionError, OSError):
            pass
        finally:
            # drop the engine session FIRST (rollback + workload-repo
            # flush on disconnect) — same contract as the threaded serve
            if sess is not None:
                try:
                    sess.close()
                except Exception:  # noqa: BLE001 — disconnect best-effort
                    pass
            try:
                writer.close()
            except Exception:  # noqa: BLE001
                pass
