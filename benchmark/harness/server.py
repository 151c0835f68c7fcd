"""The system under test, booted as a deployment boots it.

The only file of the benchmark that imports the program. It takes from it
the served system (`Database`, `AsyncMySqlFrontend`, `direct_load`), the one
compile-cache rule, and its counters (`counters`): nothing that decides a
result. Tables are created by DDL over the wire and filled through
`direct_load` from the arrays the benchmark's own generator made.
"""

from __future__ import annotations

import glob
import json
import os
import threading
import time

from . import layer


def named_sysstat() -> set:
    """The `sysstat.<name>` counters that the declarative layer metrics
    name: the program's registry holds a counter from its first bump, so
    one of these that it has not bumped yet reads 0.0, not absent."""
    names = set()
    for path in glob.glob(os.path.join(layer.DIR, "*.json")):
        with open(path) as f:
            spec = json.load(f)
        for key in ("num", "minus", "den"):
            if isinstance(spec.get(key), list):
                names.update(n for n in spec[key] if n.startswith("sysstat."))
    return names


class CompileMeter:
    """Backend (XLA) compiles of this process, as JAX's monitoring reports
    them (copied from chip_smoke.py, sound: PR 22)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self._lock = threading.Lock()
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, seconds: float, **_kw) -> None:
        if event == self.EVENT:
            with self._lock:
                self.count += 1
                self.seconds += seconds

    def read(self) -> tuple[int, float]:
        with self._lock:
            return self.count, self.seconds


class Served:
    def __init__(self, config: dict):
        from oceanbase_tpu.server.async_front import AsyncMySqlFrontend
        from oceanbase_tpu.server.database import Database
        from oceanbase_tpu.share.compile_cache import enable_compile_cache

        self.cache_dir = enable_compile_cache()
        self.compiles = CompileMeter()
        cl = config["cluster"]
        self.db = Database(n_nodes=int(cl["replicas"]),
                           n_ls=int(cl["log_streams"]))
        self.front = AsyncMySqlFrontend(self.db).start()
        self.port = self.front.port
        self.named = named_sysstat()

    def apply_settings(self, client, config: dict) -> None:
        """System parameters as an operator sets them: over the wire."""
        for name, value in config.get("settings", {}).items():
            client.query(f"alter system set {name} = {value}")

    def load(self, client, gen, config: dict, data: dict) -> dict:
        """DDL over the wire, direct_load per table, first touch (the
        catalog snapshot scan) per table of `data`. Drops what the references
        do not read. Returns seconds per step."""
        from oceanbase_tpu.server.direct_load import direct_load

        keep = gen.reference_columns(config)
        took = {"direct_load_s": 0.0, "first_touch_s": 0.0}
        rows = gen.row_counts(data)
        tables = [(n, stmts) for n, stmts in gen.ddl(config) if n in data]
        for name, stmts in tables:
            for s in stmts:
                client.query(s)
            t0 = time.perf_counter()
            cols = data[name]
            n = direct_load(self.db, name,
                            {c: gen.as_strings(v) for c, v in cols.items()})
            if n != rows[name]:
                raise RuntimeError(f"direct_load {name}: {n} != {rows[name]}")
            took["direct_load_s"] += time.perf_counter() - t0
            data[name] = {c: cols[c] for c in keep.get(name, ())}
        for name, _ in tables:
            t0 = time.perf_counter()
            got = int(client.query(f"select count(*) from {name}")[0][0])
            if got != rows[name]:
                raise RuntimeError(f"count(*) {name}: {got} != {rows[name]}")
            took["first_touch_s"] += time.perf_counter() - t0
        return took

    def counters(self) -> dict:
        """The counters the layer metrics name, flat, cumulative: the plan
        cache's fast hits, XLA compiles, the host-tax registry, and every
        named counter of the program (`__all_virtual_sysstat`) as
        `sysstat.<name>`."""
        db = self.db
        out = dict.fromkeys(self.named, 0.0)
        out.update((f"sysstat.{name}", float(v))
                   for name, v in db.metrics.counters_snapshot().items())
        out["plan_cache.fast_hits"] = float(db.plan_cache.stats.fast_hits)
        out["xla.compiles"] = float(self.compiles.read()[0])
        tax = db.host_tax.snapshot()["digests"]
        out["host_tax.statements"] = float(sum(a["count"] for a in tax.values()))
        out["host_tax.e2e_s"] = float(sum(a["e2e_s"] for a in tax.values()))
        out["host_tax.unattributed_s"] = float(
            sum(a["unattributed_s"] for a in tax.values()))
        out["host_tax.cpu_s"] = float(sum(a["cpu_s"] for a in tax.values()))
        for a in tax.values():
            for ph, v in a["phases"].items():
                key = f"host_tax.phase.{ph}"
                out[key] = out.get(key, 0.0) + float(v)
        return out

    def free(self) -> None:
        """Stop serving and drop the program's state (device arrays too)."""
        self.front.stop()
        self.db.close()
        self.db = None
        self.front = None
