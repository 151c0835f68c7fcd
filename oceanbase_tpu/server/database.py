"""Database: full-statement SQL over a replicated cluster (observer analog).

Reference surface:
  * statement dispatch: ObMPQuery::process -> ObSql::stmt_query
    (observer/mysql/obmp_query.cpp:53, sql/ob_sql.cpp:153) — here
    DbSession.sql() parsing any statement and dispatching DDL / DML / query;
  * DML operators + DAS write path: ObTableModifyOp -> ObDMLService ->
    ObAccessService -> ObMemtable::set (sql/engine/dml/ob_table_modify_op.h:190,
    storage/memtable/ob_memtable.cpp:540) — here UPDATE/DELETE qualify rows
    by running a generated SELECT through the TPU engine, then stage
    mutations through TransService into leader memtables;
  * tx control: ObSqlTransControl (sql/ob_sql_trans_control.cpp:229) —
    BEGIN/COMMIT/ROLLBACK with snapshot-isolation reads.

HTAP loop: writes go through MVCC memtables + the replicated log; reads
materialize a snapshot via scan_merge into a core Table and ship it to the
device once per data version (the marshalling point the north star names).
VARCHAR columns store APPEND-ORDER dictionary codes (stable under inserts,
so logged rows never need re-encoding); at snapshot materialization the
codes are remapped through a cached sorted dictionary so the engine's
code-order == string-order invariant holds on device.
"""

from __future__ import annotations

import contextlib
import itertools
import threading
import time as _time
from dataclasses import dataclass, field

import numpy as np

from ..core.dictionary import Dictionary, SortedViews
from ..core.dtypes import DataType, Field, Schema, TypeKind
from ..core.table import Table
from ..log.palf import leader_of as _leader_of
from ..engine.session import ResultSet, Session
from ..expr import compile as _EC
from ..rootserver import RootService
from ..share import Config, LocationService
from ..share import gap_ledger as _GL
from ..share import interrupt as _I
from ..share import retry as _R
from ..share.schema_service import SchemaError
from .diag import QueryProfile
from ..sql import ast as A
from ..sql import parser as P
from ..sql.logical import _parse_type
from ..sql.plan_cache import PlanCache
from ..storage import OP_DELETE, OP_PUT


class SqlError(Exception):
    """Statement-level error; `code` is the MySQL-compatible error code
    the wire front door puts in the ERR packet (1064 generic syntax,
    1142 table access denied, 1227 privilege required, 1396 user-admin)."""

    def __init__(self, msg: str, code: int = 1064):
        super().__init__(msg)
        self.code = code


class WorkerQueueTimeout(SqlError):
    """The statement never got a tenant worker inside its wait bound
    (ObThWorker queue overflow analog). A distinct class so the retry
    taxonomy and chaos harness can tell admission pressure from SQL
    errors; still a SqlError for wire/compat purposes."""


@dataclass
class IndexInfo:
    """One secondary index: an index tablet co-located on the base table's
    log stream, keyed by (index cols..., pk cols...) — pk suffix makes
    non-unique entries unique; a UNIQUE index keys on the index cols alone
    so duplicate values collide in the memtable (first-committer-wins).
    Reference surface: index schemas + direct-insert build
    (src/storage/ddl) and DAS index lookup iterators (src/sql/das/iter)."""

    name: str
    table: str
    cols: tuple[str, ...]
    tablet_id: int
    schema: Schema  # index cols + pk cols (deduped, in that order)
    key_cols: list[str]
    unique: bool = False
    status: str = "building"  # building -> ready
    build_version: int = 0
    reads: int = 0  # statements served through this index (diag surface)


def _part_of(value: int, n_parts: int) -> int:
    """Hash-partition routing: stable over the 64-bit mix of the partition
    column's storage value (dict codes are append-ordered and global per
    table, so string partition columns route consistently too)."""
    v = (int(value) * 0x9E3779B97F4A7C15) & 0xFFFFFFFFFFFFFFFF
    return (v >> 32) % n_parts


@dataclass
class TableInfo:
    """Schema-service record of one user table.

    `partitions` lists the table's (ls_id, tablet_id) shards — one entry
    for an unpartitioned table; PARTITION BY HASH(part_col) PARTITIONS n
    spreads n tablets across log streams (the reference's hash-partitioned
    tables; a multi-partition statement stages on several LS leaders and
    commits with 2PC — the parallel-DML shape). ls_id/tablet_id remain the
    first partition (index tablets and the table lock anchor there)."""

    name: str
    schema: Schema
    key_cols: list[str]
    ls_id: int
    tablet_id: int
    indexes: dict[str, IndexInfo] = field(default_factory=dict)
    partitions: list[tuple[int, int]] | None = None
    part_col: str | None = None
    # append-order dictionaries: code assignment is insertion order, so
    # logged/stored codes stay valid as strings arrive (the sorted view is
    # derived at read time)
    dicts: dict[str, Dictionary] = field(default_factory=dict)
    data_version: int = 0  # bumped on every committed DML batch
    schema_version: int = 0  # set at create time (schema service analog)
    # last data version materialized into the analytic catalog (-1 = stale)
    cached_data_version: int = -1
    # what Database.tx_shared_entry reads, under Database._snapshot_lock:
    # the highest commit version a finished writer left in the table, and
    # the writers still open on it (a transaction from its first write to
    # its cleanup, a bulk writer from its version to its bump)
    last_commit_version: int = 0
    writers: int = 0
    # per-column sorted views of `dicts`, kept as they grow
    _sorted_views: dict[str, SortedViews] = field(default_factory=dict)

    # per-column dictionary length already carried by COMMITTED records:
    # codes beyond this must ride the next commit's dict_appends (codes
    # created by aborted txs stay unlogged and are re-logged by the next
    # committer that references them)
    logged_dict_len: dict[str, int] = field(default_factory=dict)

    def all_partitions(self) -> list[tuple[int, int]]:
        return self.partitions or [(self.ls_id, self.tablet_id)]

    def partition_for_key(self, key: tuple) -> tuple[int, int]:
        """(ls_id, tablet_id) owning a primary-key tuple (the partition
        column is enforced to be part of the primary key)."""
        parts = self.all_partitions()
        if len(parts) == 1 or self.part_col is None:
            return parts[0]
        v = key[self.key_cols.index(self.part_col)]
        return parts[_part_of(int(v), len(parts))]

    @property
    def dict_sig(self) -> tuple:
        """Dictionary-state signature. Append-order dictionaries only grow,
        so length IS the version — derived, not book-kept, which makes it
        immune to failed statements that encoded strings before erroring."""
        return tuple(sorted((c, len(d)) for c, d in self.dicts.items()))

    def sorted_dict(self, col: str) -> tuple[Dictionary, np.ndarray]:
        """Sorted view + old-code -> sorted-code remap of one snapshot of
        the column's dictionary, taken once: a view as long as the
        dictionary, or longer (another session extended it meanwhile).

        Returning the SAME Dictionary object while nothing was appended
        keeps the device-cache entries and the plans built on it; a grown
        dictionary's view is the last one with the new strings placed by
        bisection (span `dict view`, counters `dict view inserts` and
        `dict view sorts`, the latter only for a column's first view)."""
        d = self.dicts[col]
        sv = self._sorted_views.get(col)
        if sv is None or sv.source is not d:
            sv = self._sorted_views.setdefault(col, SortedViews(d))
            if sv.source is not d:  # the column's dictionary was replaced
                sv = self._sorted_views[col] = SortedViews(d)
        n = len(d)
        view = sv.current(n)
        if view is not None:
            return view
        with _GL.span("dict view") as sp:
            view, how, placed = sv.extend_to(n)
            if how == "sort":
                sp.count("dict view sorts")
            elif how == "insert":
                sp.count("dict view inserts", placed)
        return view

    def remap_sorted(self, data: dict) -> dict[str, Dictionary]:
        """Rewrite the append-order codes of a scan's dict columns in
        `data` to sorted codes, in place; the sorted dictionaries. A view
        shorter than the codes it is applied to is a fault, never masked."""
        dicts = {}
        for col in self.dicts:
            sd, remap = self.sorted_dict(col)
            codes = data[col]
            if len(codes):
                assert int(codes.max()) < len(remap), (
                    f"{self.name}.{col}: code {int(codes.max())} past a "
                    f"sorted view of {len(remap)}")
                data[col] = remap[codes]
            dicts[col] = sd
        return dicts


class TxCatalog(dict):
    """Catalog mapping with statement-scoped transaction overlays.

    The shared dict holds COMMITTED snapshot Tables that every session
    reads. A session with an open tx needs private views (BEGIN-time
    snapshot plus its own staged rows); installing those into the shared
    dict would let a concurrent session read uncommitted rows between that
    tx's refresh and its own (advisor finding r1). Private views therefore
    live on the _OpenTx and are ACTIVATED only for the duration of one of
    that tx's statements via `tx_scope` — a thread-local pointer, so a
    different session's statement on the same OR another thread never
    resolves through them."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self._tls = threading.local()

    @contextlib.contextmanager
    def tx_scope(self, views: dict | None):
        prev = getattr(self._tls, "ov", None)
        self._tls.ov = views
        try:
            yield
        finally:
            self._tls.ov = prev

    def _overlay(self) -> dict | None:
        return getattr(self._tls, "ov", None)

    def is_private(self, name: str) -> bool:
        ov = self._overlay()
        return ov is not None and name in ov

    def __getitem__(self, name):
        ov = self._overlay()
        if ov is not None and name in ov:
            return ov[name]
        return super().__getitem__(name)

    def get(self, name, default=None):
        ov = self._overlay()
        if ov is not None and name in ov:
            return ov[name]
        return super().get(name, default)

    def __contains__(self, name) -> bool:
        ov = self._overlay()
        return (ov is not None and name in ov) or super().__contains__(name)


@dataclass
class TenantUnit:
    """Resource unit of one tenant (the OMT unit-config analog: worker
    pool size, memory quota, PX quota — observer/omt ObTenant +
    ob_unit_config). None limits = unbounded (the sys tenant default)."""

    max_workers: int | None = None  # concurrent statements
    queue_timeout_s: float = 5.0  # wait for a worker slot
    #: unified tenant memory quota, charged by TWO consumers sharing one
    #: accounting surface: (1) resident catalog snapshot bytes, enforced
    #: by Database._enforce_memory (evicts the tenant's OWN coldest
    #: tables, never a neighbour's); (2) live device-memory reservations,
    #: charged by the memory governor at statement admission
    #: (engine/memory_governor.py) — a tenant at its limit QUEUES on the
    #: "device memory reservation" wait event rather than evicting
    #: another tenant's residency. None = unbounded (sys tenant default).
    memory_limit: int | None = None
    px_target: int | None = None  # cluster-parallelism quota
    # continuous-batching admission share: the dispatch gate's weighted
    # round-robin picks this tenant's queued cohorts `weight` times per
    # unit-weight tenant when both have backlog (server/batcher.py)
    weight: int = 1


class Database:
    """An in-process replicated database: schema + cluster + analytic engine.

    One Database ~ one TENANT of the reference: a catalog, a plan cache,
    schemas, diagnostics, resource unit — plus, in standalone mode, the
    cluster itself. Pass `cluster`/`rootservice` to share one cluster
    among several tenants (observer/omt: tenants are resource-isolated
    units over shared nodes; see server/tenant.TenantManager)."""

    def __init__(self, n_nodes: int = 3, n_ls: int = 2,
                 extra_catalog: dict[str, Table] | None = None,
                 data_dir: str | None = None, fsync: bool = True,
                 cluster=None, rootservice=None, tenant_name: str = "sys",
                 unit: TenantUnit | None = None):
        # durable mode: palf logs + storage checkpoints + schema meta live
        # under data_dir; a Database pointed at an existing dir restarts
        # from disk (ckpt replay + palf replay — ob_server.cpp:923 analog)
        self.data_dir = data_dir
        self._fsync = fsync
        self.tenant_name = tenant_name
        # (schema version, name->TableInfo map) for the .tables property
        self._tables_cache: tuple | None = None
        # XA branch registry rebuilt from the LOG (ob_trans_part_ctx.h:154
        # logs prepare state): XA_PREPARE records add entries, the
        # decision records remove them — populated during boot replay and
        # kept current by normal apply. xid -> {tx_id, owner, parts,
        # tablets}; must exist before any record observer can fire.
        self._xa_registry: dict[str, dict] = {}
        self._xa_txids: dict[int, str] = {}
        # XA: externally-coordinated branches parked between PREPARE and
        # the decision; value = (live _OpenTx | None-if-recovered | the
        # _XA_PREPARING reservation, owner, registry-snapshot-or-None).
        # The snapshot lets a RETRY of a failed decide finish cleanup even
        # after the live registry entry popped.
        self._xa_prepared: dict[str, tuple] = {}
        self.unit = unit or TenantUnit()
        self._shared_cluster = cluster is not None
        # integrity counters accrued BEFORE the metrics registry exists
        # (meta/checkpoint verification runs first thing in boot); folded
        # into sysstat once the registry is built below
        self._boot_integrity: dict[str, float] = {}
        self._unique_keys: dict[str, tuple[str, ...]] = {}
        # tablet_id -> TableInfo, rebuilt lazily after DDL (apply-path hot)
        self._ti_by_tablet: dict[int, TableInfo] | None = None
        if self._shared_cluster:
            if data_dir is not None:
                raise ValueError(
                    "durable mode is per-cluster; pass data_dir to the "
                    "TenantManager, not a shared-cluster tenant"
                )
            self.cluster, self.rootservice = cluster, rootservice
            self.schema_service = self.rootservice.schema
            # record observation is multiplexed across tenants (each
            # ignores tablets it does not own)
            self.cluster.record_observers.append(self._on_applied_record)
            restored_meta = None
        else:
            node_meta = self._load_node_meta() if data_dir is not None else None
            if node_meta is not None:
                n_nodes, n_ls = node_meta["n_nodes"], node_meta["n_ls"]
                # seed the XA registry from meta (covers branches whose
                # XA_PREPARE predates the checkpoint the log recycled to);
                # replayed decision records then prune entries decided
                # after the meta snapshot
                for _xid, _e in (node_meta.get("xa_registry") or {}).items():
                    self._xa_registry[_xid] = {
                        "tx_id": _e["tx_id"], "owner": _e["owner"],
                        "parts": tuple(_e["parts"]),
                        "tablets": set(_e["tablets"]),
                    }
                    self._xa_txids[_e["tx_id"]] = _xid
            self.cluster, self.rootservice = RootService.bootstrap(
                n_nodes, n_ls, data_dir=data_dir, fsync=fsync, finalize=False
            )
            self.schema_service = self.rootservice.schema
            if node_meta is not None:
                self._restore_from_disk(node_meta)
            # every applied record re-applies logged dictionary appends and
            # advances GTS past restored commit versions (idempotent in
            # normal operation; essential during boot-time replay)
            for group in self.cluster.ls_groups.values():
                for rep in group.values():
                    rep.on_record = self._on_applied_record
            self.cluster.finalize()
            restored_meta = node_meta
        # user accounts + grants (src/sql/privilege_check analog); restored
        # from node meta alongside the schema so grants survive restart
        from ..share.privilege import PrivilegeManager

        self.privileges = PrivilegeManager.from_meta(
            restored_meta.get("privileges") if restored_meta else None
        )
        # vector index registrations: table -> col -> (lists, nprobe);
        # re-applied to every fresh snapshot Table (the built artifact
        # version-caches in the executor — DML = invalidate + lazy rebuild)
        self._vector_specs: dict[str, dict[str, tuple[int, int]]] = (
            restored_meta.get("vector_specs", {}) if restored_meta else {}
        )
        # external tables (plugin loaders): name -> (format, location);
        # re-materialized from their files once the catalog exists below
        self._external_specs: dict[str, tuple[str, str]] = (
            restored_meta.get("external_specs", {}) if restored_meta else {}
        )
        # materialized views: name -> defining SELECT text; re-run at
        # boot once base-table snapshots restore
        self._mview_specs: dict[str, str] = (
            restored_meta.get("mview_specs", {}) if restored_meta else {}
        )
        # PLAIN views: name -> defining SELECT text; nothing materializes
        # — the planner expands (and where possible MERGES) the body at
        # plan time (sql/planner.py _merge_view). The dict is shared with
        # the planner by reference, so DDL changes apply immediately.
        self._view_specs: dict[str, str] = (
            restored_meta.get("view_specs", {}) if restored_meta else {}
        )
        # row triggers: name -> {timing, event, table, body}; parsed form
        # cached lazily per process (sql/trigger.py)
        self._trigger_specs: dict[str, dict] = (
            restored_meta.get("trigger_specs", {}) if restored_meta else {}
        )
        self._trigger_parsed: dict[str, tuple] = {}
        # stored procedures: name -> definition text (sql/pl.py); parsed
        # lazily per process, persisted in node meta like schema
        self._procedure_texts: dict[str, str] = (
            restored_meta.get("procedures", {}) if restored_meta else {}
        )
        self._procedures_parsed: dict = {}
        # sequences: name -> {"next": int, "inc": int, "reserved": int}.
        # Durability via BLOCK RESERVATION (the reference's sequence
        # cache): meta persists the end of the reserved block, so a
        # crash skips at most one block and never repeats a value
        self._sequences: dict[str, dict] = (
            restored_meta.get("sequences", {}) if restored_meta else {}
        )
        for _sq in self._sequences.values():
            _sq["next"] = _sq["reserved"]  # post-restart: start past block
            _sq.pop("last", None)  # currval invalid until a nextval
        # worker pool quota (ObTenant worker queues): bounds concurrent
        # statements of this tenant
        self._worker_sem = (
            threading.BoundedSemaphore(self.unit.max_workers)
            if self.unit.max_workers else None
        )
        # global query interrupt (share/interrupt analog): one manager per
        # node, shared by every tenant on the cluster
        from ..share.interrupt import attach_cluster_interrupts

        if not hasattr(self.cluster, "_interrupt_mgrs"):
            self.cluster._interrupt_mgrs = attach_cluster_interrupts(self.cluster)
        self.interrupts = self.cluster._interrupt_mgrs
        # session_id -> interrupt id of its running statement
        self._active_stmts: dict[int, tuple] = {}
        self._stmt_seq = itertools.count(1)
        self.config = Config()
        # re-apply persisted parameter values (see _save_node_meta): a
        # restarted node keeps its ALTER SYSTEM SET state
        for _cn, _cv in ((restored_meta or {}).get("config") or {}).items():
            try:
                self.config.set(_cn, _cv)
            except Exception:
                pass
        self.location = LocationService(
            self.cluster.leader_node,
            ttl=10.0,
            clock=lambda: self.cluster.bus.now,
        )
        # analytic catalog: table name -> snapshot Table (plus any read-only
        # preloaded tables, e.g. benchmark data)
        self.catalog: dict[str, Table] = TxCatalog(extra_catalog or {})
        # held briefly, never across a scan: a shared entry's publish and
        # label, a writer's start and end (TableInfo.writers,
        # last_commit_version, data_version), and tx_shared_entry's
        # reading of them, so each sees the others whole
        self._snapshot_lock = threading.Lock()
        # (tx context, its TableInfos) of writers whose decision was in
        # flight at their end (writers_end): open until settle_writers
        # sees it land
        self._undecided: list = []
        # DbSession._prepare_range_shapes, per statement text: True once
        # its plan is compiled over a range-route-sized table (or no key
        # range can answer it, or no plan was cached at a second try),
        # False after a first try found no plan cached
        self.range_shapes: dict[str, bool] = {}
        # placeholder entries for restored tables (create_table provides
        # one on the DDL path): the resolver requires every table in the
        # shared catalog even when the first statement reads it through a
        # statement-scoped view (index route) or a tx overlay
        for ti in self.tables.values():
            if ti.name not in self.catalog:
                self.catalog[ti.name] = Table(ti.name, ti.schema, {
                    f.name: np.zeros(0, f.dtype.storage_np)
                    for f in ti.schema.fields
                })
        # re-materialize registered external tables from their files.
        # A load failure (missing mount, transient IO) keeps the
        # REGISTRATION — queries error "unknown table" until the file is
        # back and the next boot (or re-create) materializes it; silently
        # dropping the spec would persist the loss at the next meta save
        for _ename, (_efmt, _eloc) in list(self._external_specs.items()):
            try:
                from ..plugin import load_external

                self.catalog[_ename] = load_external(_ename, _efmt, _eloc)
            except Exception:
                pass
        self.plan_cache = PlanCache(capacity=self.config["plan_cache_capacity"])
        self.config.on_change(
            "plan_cache_capacity",
            lambda _n, _o, v: setattr(self.plan_cache, "capacity", v),
        )
        # tenant-wide metrics fabric (GV$SYSSTAT / GV$SYSTEM_EVENT /
        # QUERY_RESPONSE_TIME analog): one registry threaded through the
        # statement pipeline, plan cache, replication bus and tx commit
        from ..share.metrics import MetricsRegistry

        self.metrics = MetricsRegistry()
        # fold integrity counters accrued during boot-time verification
        # (before this registry existed) into sysstat
        for _n, _v in self._boot_integrity.items():
            self.metrics.add(_n, _v)
        self.plan_cache.metrics = self.metrics
        if getattr(self.cluster.bus, "metrics", None) is None:
            # shared-cluster mode: the first tenant (sys) owns the bus
            # stats — rpc traffic is cluster-wide, not per-tenant
            self.cluster.bus.metrics = self.metrics
        # diagnostics (observer/virtual_table surface)
        from .diag import (
            AshSampler,
            FlightRecorder,
            LongOps,
            PlanMonitor,
            SqlAudit,
            Tracer,
        )

        self.tracer = Tracer()
        if getattr(self.cluster.bus, "tracer", None) is None:
            # full-link propagation: replication messages stamped with the
            # sending statement's trace context land replica-side spans in
            # the same tree (first tenant owns, like bus.metrics)
            self.cluster.bus.tracer = self.tracer
        self.audit = SqlAudit(
            capacity=max(64, self.config["sql_audit_memory_limit"] // 4096)
        )
        self.plan_monitor = PlanMonitor()
        self.ash = AshSampler()
        self.long_ops = LongOps()
        self.flight = FlightRecorder(
            watermark_s=self.config["trace_log_slow_query_watermark"]
        )
        self.audit.enabled = self.config["enable_sql_audit"]
        self.plan_monitor.enabled = self.config["enable_perf_event"]
        self.config.on_change(
            "enable_sql_audit",
            lambda _n, _o, v: setattr(self.audit, "enabled", v))
        self.config.on_change(
            "enable_perf_event",
            lambda _n, _o, v: setattr(self.plan_monitor, "enabled", v))
        self.config.on_change(
            "sql_audit_memory_limit",
            lambda _n, _o, v: self.audit.set_capacity(max(64, v // 4096)))
        self.config.on_change(
            "trace_log_slow_query_watermark",
            lambda _n, _o, v: setattr(self.flight, "watermark_s", v))
        # host-tax gap ledger (share/gap_ledger.py): conservation-account
        # every statement's e2e wall into named phases + an explicit
        # unattributed residual, aggregated per digest behind
        # __all_virtual_host_tax; the stack sampler rides the slow-query
        # watermark so a recurring slow statement gets caught with
        # collapsed stacks in its flight-recorder bundle
        self.host_tax = _GL.HostTaxRegistry(
            max_digests=self.config["host_tax_max_digests"],
            window_s=self.config["host_tax_window"])
        self.host_tax.enabled = self.config["enable_host_tax"]
        self.stack_sampler = _GL.StackSampler(
            interval_s=self.config["stack_sampler_interval"])
        if self.config["enable_stack_sampler"]:
            self.stack_sampler.set_continuous(True)
        self.config.on_change(
            "enable_host_tax",
            lambda _n, _o, v: setattr(self.host_tax, "enabled", v))
        self.config.on_change(
            "host_tax_max_digests",
            lambda _n, _o, v: setattr(self.host_tax, "max_digests",
                                      max(8, v)))
        self.config.on_change(
            "enable_stack_sampler",
            lambda _n, _o, v: self.stack_sampler.set_continuous(v))
        self.config.on_change(
            "stack_sampler_interval",
            lambda _n, _o, v: setattr(self.stack_sampler, "interval_s",
                                      max(1e-4, v)))
        # operator-level plan telemetry (engine/plan_profile.py): sampled
        # per-operator profiled execution folding (estimate, actual)
        # calibration pairs into the bounded store — per-operator rows in
        # __all_virtual_sql_plan_monitor, EXPLAIN ANALYZE annotations,
        # awr_report hot operators, and the misestimate sentinel rule
        from ..engine.plan_profile import OperatorProfileStore, PlanProfiler

        self.plan_profiler = PlanProfiler(
            store=OperatorProfileStore(
                max_digests=self.config["ob_plan_profile_max_digests"]),
            sample_every=self.config["ob_plan_profile_sample"])
        self.plan_profiler.enabled = self.config["enable_plan_profile"]
        self.config.on_change(
            "enable_plan_profile",
            lambda _n, _o, v: setattr(self.plan_profiler, "enabled", v))
        self.config.on_change(
            "ob_plan_profile_sample",
            lambda _n, _o, v: setattr(self.plan_profiler, "sample_every",
                                      int(v)))
        self.config.on_change(
            "ob_plan_profile_max_digests",
            lambda _n, _o, v: self.plan_profiler.store.set_max_digests(v))
        # workload repository (server/workload.py): digest-keyed statement
        # summaries + table/column access heat folded at statement
        # completion, bounded AWR-style snapshots on demand or periodic
        from .workload import (
            StatementSummaryRegistry,
            TableAccessStats,
            WorkloadRepository,
        )

        self.stmt_summary = StatementSummaryRegistry(
            max_digests=self.config["ob_sql_stat_max_digests"],
            metrics=self.metrics)
        self.access = TableAccessStats()
        self.stmt_summary.enabled = self.config["enable_sql_stat"]
        self.access.enabled = self.config["enable_sql_stat"]
        self.workload = WorkloadRepository(
            capacity=self.config["workload_snapshot_capacity"])
        self.workload.interval_s = self.config["workload_snapshot_interval"]

        def _sql_stat_toggle(_n, _o, v):
            self.stmt_summary.enabled = v
            self.access.enabled = v

        self.config.on_change("enable_sql_stat", _sql_stat_toggle)
        self.config.on_change(
            "ob_sql_stat_max_digests",
            lambda _n, _o, v: self.stmt_summary.set_max_digests(v))
        self.config.on_change(
            "workload_snapshot_capacity",
            lambda _n, _o, v: self.workload.set_capacity(v))
        self.config.on_change(
            "workload_snapshot_interval",
            lambda _n, _o, v: setattr(self.workload, "interval_s", v))
        # serving saturation timeline (share/timeline.py): ONE ring per
        # cluster, shared like bus.metrics — tenant starvation is only
        # visible when every tenant's QoS lands in the same ledger. The
        # first tenant's config sizes it; any tenant's toggle gates it.
        from ..share.timeline import ServingTimeline

        tl = getattr(self.cluster, "_timeline", None)
        if tl is None:
            tl = ServingTimeline(
                bucket_s=self.config["serving_timeline_bucket"],
                capacity=self.config["serving_timeline_capacity"])
            self.cluster._timeline = tl
        self.timeline = tl
        tl.enabled = self.config["enable_serving_timeline"]
        tl.register_tenant(self.tenant_name, self.unit.max_workers,
                           self.unit.queue_timeout_s)
        self.config.on_change(
            "enable_serving_timeline",
            lambda _n, _o, v: setattr(self.timeline, "enabled", v))
        self.config.on_change(
            "serving_timeline_bucket",
            lambda _n, _o, v: self.timeline.set_bucket_s(v))
        self.config.on_change(
            "serving_timeline_capacity",
            lambda _n, _o, v: self.timeline.set_capacity(v))
        # health sentinel (server/sentinel.py): typed rules over each
        # snapshot interval, alert ring behind __all_virtual_alert_history
        from .sentinel import HealthSentinel

        self.sentinel = HealthSentinel(
            capacity=self.config["health_alert_capacity"])
        self.sentinel.enabled = self.config["enable_health_sentinel"]
        # closed-loop layout advisor (server/layout_advisor.py): folds the
        # workload repository's evidence into costed layout actions, and
        # (auto mode) applies them as background rebuild dags. Chained on
        # the snapshot hook next to the sentinel; either observer failing
        # must not starve the other.
        from .layout_advisor import LayoutAdvisor

        self.layout_advisor = LayoutAdvisor(self)
        # re-install persisted encoding picks (advisor view + live
        # tablets): dump-time FOR/RLE/const choices survive a restart
        for (_ht, _hc), _hv in (
                (restored_meta or {}).get("enc_hints") or {}).items():
            self.layout_advisor.encoding_hints[(_ht, _hc)] = _hv
            self.layout_advisor._push_encoding(_ht, _hc, _hv)
        # table -> advisor-set residency priority (higher = evict later);
        # _enforce_memory and the block cache's eviction consult it
        self.residency_priority: dict[str, float] = {}
        self._uid_tables: dict = {}

        def _observe_snapshot(first, last):
            for cb in (self.sentinel.observe,
                       self.layout_advisor.on_snapshot):
                try:
                    cb(first, last)
                except Exception:  # noqa: BLE001 - observer boundary
                    pass

        self.workload.on_snapshot = _observe_snapshot
        self.config.on_change(
            "enable_health_sentinel",
            lambda _n, _o, v: setattr(self.sentinel, "enabled", v))
        self.config.on_change(
            "health_alert_capacity",
            lambda _n, _o, v: self.sentinel.set_capacity(v))
        self._session_ids = itertools.count(1)
        # statement-scoped follower-read Tables, keyed on (table, chosen
        # replica applied positions, dict signature) — identical replica
        # state ⇒ identical rows, so read floods over static data reuse
        # one materialization (see _follower_table)
        self._follower_views: dict[tuple, Table] = {}
        # last rootserver rebalance pass (monotonic stamp) + the QoS
        # rejected-counts already consumed as pressure evidence
        self._last_rebalance_at: float | None = None
        self._rebalance_qos_seen: dict[str, int] = {}

        # storage maintenance: block cache, dag scheduler, freeze loop
        from ..share.cache import KVCache
        from ..share.dag_scheduler import TenantDagScheduler
        from ..storage.freezer import MaintenanceService

        self.block_cache = KVCache(self.config["block_cache_size"])
        # under memory pressure the block cache evicts the coldest entry
        # of the LOWEST advisor residency priority first (keys are
        # (sstable uid, block, column); uid -> table resolved lazily)
        self.block_cache.priority_of = self._block_priority
        self.config.on_change(
            "block_cache_size",
            lambda _n, _o, v: self.block_cache.set_capacity(v))
        # restored tablets (and their sstables) come off disk without a
        # cache: reattach
        for t in self._all_tablets():
            t.cache = self.block_cache
            for ss in t.deltas:
                ss.cache = self.block_cache
            if t.base is not None:
                t.base.cache = self.block_cache
        self.dag_scheduler = TenantDagScheduler(
            tracer=self.tracer, long_ops=self.long_ops
        )
        self.maintenance = MaintenanceService(
            self.dag_scheduler,
            config=self.config,
            tablets_fn=self._all_tablets,
            snapshot_fn=lambda: self.cluster.gts.current(),
        )
        # background storage scrubber (storage/scrub.py): queued as a
        # BACKGROUND dag from run_maintenance every ob_scrub_interval
        from ..storage.scrub import StorageScrubber

        self.scrubber = StorageScrubber(self)
        # ALTER SYSTEM SET ob_errsim_disk_* arms the shared disk-fault
        # injector live (chaos harness entry point; 0 disarms)
        from ..share.errsim import ERRSIM as _ERRSIM

        _disk_arms = {
            "ob_errsim_disk_bitflip": "EN_DISK_BITFLIP",
            "ob_errsim_disk_torn_write": "EN_DISK_TORN_WRITE",
            "ob_errsim_disk_truncate": "EN_DISK_TRUNCATE",
            "ob_errsim_disk_io_error": "EN_IO_ERROR",
        }

        def _arm_disk(name, _old, v):
            point = _disk_arms[name]
            if float(v) > 0.0:
                _ERRSIM.arm(point, prob=float(v), count=-1)
            else:
                _ERRSIM.clear(point)

        for _k in _disk_arms:
            self.config.on_change(_k, _arm_disk)

        from ..tx.tablelock import LockManager

        self.lock_mgr = LockManager()

        # XA recovery: every undecided branch in the log-rebuilt registry
        # parks again — locks re-held, and the leader replica RE-STAGES the
        # pending redo into its memtables so write-write conflict detection
        # guards the prepared rows exactly as before the restart (the
        # reference re-inserts prepared redo through the tx ctx on
        # recovery, ob_trans_part_ctx.h:154).
        from ..tx.tablelock import LockMode as _LockMode

        for _xid, _e in self._xa_registry.items():
            self._xa_prepared.setdefault(_xid, (None, _e["owner"], _e))
            # the recovered branch keeps its pre-crash tx_id: the owning
            # node's counter must never re-issue it (a collision would
            # hand the branch's locks + re-staged rows to a stranger)
            _svc = self.cluster.services.get(
                _e["tx_id"] // 1_000_000_000)
            if _svc is not None:
                _svc.ensure_tx_id_above(_e["tx_id"])
            for _tab in _e["tablets"]:
                try:
                    self.lock_mgr.lock(_e["tx_id"], _tab, _LockMode.ROW_X)
                except Exception:
                    pass
            for _ls in _e["parts"]:
                for _rep in (self.cluster.ls_groups.get(_ls) or {}).values():
                    if _rep.is_leader and _e["tx_id"] in _rep._pending_redo:
                        _ms = _rep._pending_redo.pop(_e["tx_id"])
                        _snap = self.cluster.gts.current()
                        for _m in _ms:
                            _t = _rep.tablets.get(_m.tablet_id)
                            if _t is not None:
                                _t.stage(_e["tx_id"], _snap, _m.key,
                                         _m.op, _m.values)
                        _rep._locally_staged.add(_e["tx_id"])
                        _rep.tx_table[_e["tx_id"]] = "prepared"

        # indexes built since the last checkpoint lost their (unlogged)
        # backfill sstables in a crash: re-backfill now that leaders exist
        for ti, idx in getattr(self, "_index_rebuild_pending", []):
            self._backfill_index(ti, idx)
        self._index_rebuild_pending = []

        self.engine = Session(
            self.catalog,
            unique_keys=self._unique_keys,
            plan_cache=self.plan_cache,
            key_extra_fn=self._key_extra,
            cache_enabled_fn=lambda: self.config["ob_enable_plan_cache"],
            plan_monitor=self.plan_monitor,
            views=self._view_specs,
            metrics=self.metrics,
            tracer=self.tracer,
            profile_enabled_fn=lambda: self.config["enable_query_profile"],
        )
        self.engine.artifact_extra_fn = self._artifact_extra
        # workload access heat folds per execution inside the engine
        self.engine.access = self.access
        # sampled per-operator profiling decisions + calibration folds
        # happen inside the engine's dispatch (engine/plan_profile.py)
        self.engine.plan_profiler = self.plan_profiler
        # the measured ANN route rates (IVF vs brute us/row) come out of
        # the same calibration store — the optimizer's _vector_topn_spec
        # reads them through this hook when costing the index route
        self.engine.executor.profile_store = self.plan_profiler.store
        # serving timeline feeds: engine dispatches (device busy +
        # compile interference), executor uploads (transfer
        # interference), batcher dispatches (occupancy) — server-side
        # feeds (admission, completion) go through db.timeline directly
        self.engine.timeline = self.timeline
        self.engine.executor.timeline = self.timeline
        # spill-segment corruption counting (storage/tmp_file.py) reaches
        # sysstat through the executor the grace-hash pipeline holds
        self.engine.executor.metrics = self.metrics
        # cross-session continuous-batching scheduler: concurrent
        # fast-path hits fold into batched device dispatches behind ONE
        # cluster-shared DispatchGate (like cluster._timeline) — the
        # weighted per-tenant admission only means anything when every
        # tenant queues at the same gate. Knobs: ob_batch_max_size,
        # ob_batch_max_wait_us, ob_batch_follower_timeout,
        # ob_batch_queue_depth, ob_tenant_admission_slots; admission
        # share: TenantUnit.weight
        from .batcher import DispatchGate, StatementBatcher

        gate = getattr(self.cluster, "_dispatch_gate", None)
        if gate is None:
            gate = DispatchGate()
            self.cluster._dispatch_gate = gate
        self.batcher = StatementBatcher(
            metrics=self.metrics, gate=gate, tenant=self.tenant_name)
        gate.register(self.tenant_name, self.unit.weight)
        self.batcher.timeline = self.timeline
        self.batcher.follower_timeout_s = (
            self.config["ob_batch_follower_timeout"])
        self.batcher.queue_depth = self.config["ob_batch_queue_depth"]
        gate.slots = self.config["ob_tenant_admission_slots"]
        self.config.on_change(
            "ob_batch_follower_timeout",
            lambda _n, _o, v: setattr(self.batcher, "follower_timeout_s", v))
        self.config.on_change(
            "ob_batch_queue_depth",
            lambda _n, _o, v: setattr(self.batcher, "queue_depth", v))
        self.config.on_change(
            "ob_tenant_admission_slots",
            lambda _n, _o, v: setattr(gate, "slots", v))
        # device-memory governor: ONE per-device HBM ledger shared by
        # every tenant on the cluster (like the dispatch gate) — per-
        # tenant shares seeded from TenantUnit.memory_limit, statement
        # admission reserves its estimated peak working set before any
        # upload. Knobs: ob_device_memory_limit (0 = auto/synthetic),
        # ob_governor_queue_timeout, ob_governor_max_queue,
        # ob_governor_cold_reserve
        from ..engine.memory_governor import (MemoryGovernor,
                                              detect_device_budget)

        gov = getattr(self.cluster, "_memory_governor", None)
        if gov is None:
            limit = int(self.config["ob_device_memory_limit"])
            gov = MemoryGovernor(
                limit if limit > 0 else detect_device_budget(),
                max_queue=self.config["ob_governor_max_queue"])
            self.cluster._memory_governor = gov
        self.governor = gov
        gov.register_tenant(self.tenant_name, self.unit.memory_limit,
                            self._resident_bytes)
        self.engine.executor.governor = gov
        self.batcher.governor = gov
        self.config.on_change(
            "ob_device_memory_limit",
            lambda _n, _o, v: gov.set_budget(
                int(v) if int(v) > 0 else detect_device_budget()))
        self.config.on_change(
            "ob_governor_max_queue",
            lambda _n, _o, v: setattr(gov, "max_queue", int(v)))
        # device-resident result cache: repeated dashboard statements
        # serve their narrowed frame with ZERO dispatches. Keyed on the
        # logical entry key + bound literals + committed-data watermark;
        # eagerly dropped by DML (_invalidate), schema bumps, plan-cache
        # flush (the hook below) and the OOM ladder. Its frames are
        # charged against the tenant unit through _resident_bytes.
        from ..engine.result_cache import ResultCache

        self.result_cache = ResultCache(
            capacity_bytes=int(self.config["ob_result_cache_size"]),
            entry_limit=int(self.config["ob_result_cache_entry_limit"]),
            enabled_fn=lambda: self.config["ob_enable_result_cache"],
            pressure_fn=gov.under_pressure,
            metrics=self.metrics,
        )
        self.engine.result_cache = self.result_cache
        self.engine.result_watermark_fn = self._result_watermark
        self.plan_cache.result_cache = self.result_cache
        self.config.on_change(
            "ob_result_cache_size",
            lambda _n, _o, v: setattr(
                self.result_cache, "capacity_bytes", int(v)))
        self.config.on_change(
            "ob_result_cache_entry_limit",
            lambda _n, _o, v: setattr(
                self.result_cache, "entry_limit", int(v)))
        # micro-batch coalescing: two heterogeneous-plan cohorts sharing
        # a pow2 bucket shape fuse into one device dispatch at the gate
        self.batcher.coalesce_enabled = bool(
            self.config["ob_enable_batch_coalesce"])
        self.config.on_change(
            "ob_enable_batch_coalesce",
            lambda _n, _o, v: setattr(
                self.batcher, "coalesce_enabled", bool(v)))
        # one shared virtual-clock closure: sql() builds a statement
        # Deadline from it on every call — no per-statement lambda
        self._bus_clock = lambda: self.cluster.bus.now
        # distributed (PX) executor, built lazily on the first statement a
        # session routes with ob_px_dop — mesh construction touches every
        # device, so tenants that never use PX never pay for it
        self._px_executor_obj = None
        # PX admission quota (built lazily with the executor): bounds the
        # cluster-wide worker grant before a PX statement may run
        self._px_admission_obj = None
        self._ddl_lock = threading.RLock()
        # persistent compiled-plan artifacts (engine/plan_artifact.py):
        # when ob_plan_artifact_mode != off, exported executables live
        # under plan_artifact_dir (default <data_dir>/plan_artifacts) and
        # boot warm-loads the hottest digests — ranked by the workload
        # repository's statement summaries, bounded by
        # plan_artifact_max_bytes — so a rebooted node serves cached
        # statements with ZERO engine traces
        self.plan_artifact = None
        self.config.on_change(
            "ob_plan_artifact_mode",
            lambda _n, _o, _v: self._reconfigure_plan_artifacts())
        self.config.on_change(
            "plan_artifact_dir",
            lambda _n, _o, _v: self._reconfigure_plan_artifacts())
        self.config.on_change(
            "plan_artifact_max_bytes",
            lambda _n, _o, v: setattr(self.plan_artifact, "max_bytes",
                                      int(v))
            if self.plan_artifact is not None else None)
        self._reconfigure_plan_artifacts()
        # re-materialize restored mviews against the recovered base data
        # (failures keep the registration: REFRESH can retry once the
        # base objects are available again)
        for _mname, _msql in list(self._mview_specs.items()):
            try:
                self._materialize_mview(_mname, _msql)
            except Exception:
                pass

    @property
    def tables(self):
        """Current-version schema view (name -> TableInfo). Cached per
        schema version: the serving path reads this 2x per statement and
        the guard only changes on DDL. The (version, map) tuple swaps
        atomically under the GIL; a stale version check just re-guards."""
        ss = self.schema_service
        v = ss.version
        c = self._tables_cache
        if c is not None and c[0] == v:
            return c[1]
        t = ss.guard(v).tables
        self._tables_cache = (v, t)
        return t

    def _own_tablet_ids(self) -> set[int]:
        ids = set()
        for ti in self.tables.values():
            for _ls, tab in ti.all_partitions():
                ids.add(tab)
            for idx in getattr(ti, "indexes", {}).values():
                ids.add(idx.tablet_id)
        return ids

    def _all_tablets(self):
        """This tenant's tablets on every replica (each replica maintains
        its own LSM). In standalone mode that is every tablet; on a shared
        cluster, only the tenant's own (maintenance/freeze isolation)."""
        own = self._own_tablet_ids() if self._shared_cluster else None
        out = []
        for group in self.cluster.ls_groups.values():
            for rep in group.values():
                for tid, t in rep.tablets.items():
                    if own is None or tid in own:
                        out.append(t)
        return out

    def run_maintenance(self) -> dict:
        """One deterministic freeze/compaction pass (tests and the
        post-commit hook); live servers call maintenance.start()."""
        out = self.maintenance.tick()
        self.maybe_rebalance_leaders()
        self.scrubber.maybe_queue()
        self.dag_scheduler.run_until_idle()
        return out

    # -------------------------------------------- leader rebalance driver
    def maybe_rebalance_leaders(self, force: bool = False) -> list:
        """Rootserver-driven leader rebalancing: feed FailureDetector
        evidence (the keepalive majority vote) and the tenant QoS ledger
        into RootService.balance_leaders, and queue each decided move as
        a background dag that runs cluster.transfer_leader off the
        statement path. A healthy, unpressured cluster plans no moves, so
        this is a cheap no-op on every maintenance tick; throttled by
        leader_rebalance_min_interval regardless."""
        import time as _time

        try:
            if not bool(self.config["enable_leader_rebalance"]):
                return []
        except Exception:  # noqa: BLE001 — config-less Database stub
            return []
        cluster = self.cluster
        if not getattr(cluster, "keepalives", None) or cluster.n_nodes < 2:
            return []
        now = _time.monotonic()
        min_iv = float(self.config["leader_rebalance_min_interval"])
        if not force and self._last_rebalance_at is not None \
                and now - self._last_rebalance_at < min_iv:
            return []
        self._last_rebalance_at = now
        unreachable = cluster.unreachable_nodes()
        moves = self.rootservice.balance_leaders(
            unreachable, spread=self._qos_pressure())
        if not moves:
            return []
        from ..share.dag_scheduler import Dag, DagPriority

        for ls_id, frm, to in moves:
            dag = Dag("leader rebalance", DagPriority.URGENT,
                      key=("leader rebalance", ls_id))

            def move(ls_id=ls_id, frm=frm, to=to):
                cluster.transfer_leader(ls_id, to)
                # the moved LS's cached leader is now wrong everywhere;
                # targeted invalidation, same as the NotMaster path
                self.location.invalidate(ls_id)
                self.metrics.add("leader moved")

            dag.add_task(move, name=f"move ls {ls_id}: {frm} -> {to}")
            self.dag_scheduler.add_dag(dag)
        return moves

    def simulate_node_restart(self, node: int, settle: float = 1.0) -> None:
        """One observer's rolling restart, in-process: take the node's
        bus endpoints down past the lease window (survivors re-elect and
        keep serving), drop the host-side memory state a real restart
        loses — plan-cache memory tiers (NOT the disk artifact store)
        and follower-read views — then rejoin and warm-boot compiled
        plans from the artifact store, so the restarted node's first
        statement is a warm artifact hit, not a cold trace+compile."""
        self.cluster.kill_node(node, settle=settle)
        self.plan_cache.flush(memory_only=True)
        self._follower_views.clear()
        self.cluster.revive_node(node, settle=settle)
        if self.plan_artifact is not None:
            self._warm_boot_plan_artifacts()

    def _qos_pressure(self) -> bool:
        """Serving-pressure bit from the tenant QoS ledger: True when any
        tenant accumulated NEW admission rejections since the last check
        (cumulative totals are diffed against what this driver already
        consumed, so one historic overload doesn't spread leaders
        forever)."""
        tl = getattr(self, "timeline", None)
        if tl is None:
            return False
        try:
            totals = tl.qos_totals()
        except Exception:  # noqa: BLE001 — ledger shape is advisory here
            return False
        pressure = False
        for tenant, row in totals.items():
            rej = int(row.get("rejected", 0))
            if rej > self._rebalance_qos_seen.get(tenant, 0):
                pressure = True
            self._rebalance_qos_seen[tenant] = rej
        return pressure

    # -------------------------------------------------- node durability
    def _meta_path(self) -> str:
        import os

        return os.path.join(self.data_dir, "node_meta.pkl")

    def _ckpt_path(self, node: int, ls_id: int) -> str:
        import os

        return os.path.join(self.data_dir, f"n{node}", f"ls_{ls_id}", "ckpt.pkl")

    def _load_node_meta(self) -> dict | None:
        """Read the newest verifiable node-meta snapshot. Missing means a
        fresh boot (None); a corrupt latest copy is counted, quarantined,
        and boot falls back to the retained .prev (schema changes since
        that snapshot replay from the log). All copies corrupt raises —
        booting with guessed schema would be silent data loss."""
        import os
        import pickle

        from ..storage.integrity import (META, CorruptBlock, CounterSink,
                                         quarantine_file, read_verified)

        sink = CounterSink(self._boot_integrity)
        path = self._meta_path()
        last_err: CorruptBlock | None = None
        for p in (path, path + ".prev"):
            if not os.path.exists(p):
                continue
            try:
                return pickle.loads(read_verified(p, path_class=META))
            except CorruptBlock as e:
                last_err = e
            except Exception as e:  # unpicklable despite a valid crc
                last_err = CorruptBlock(p, f"{type(e).__name__}: {e}")
            sink.add("node meta corruption")
            sink.add("checksum failures")
            quarantine_file(p, last_err.reason)
        if last_err is not None:
            raise last_err
        return None

    def _save_node_meta(self) -> None:
        """Persist schema + TableInfo state (the slog meta-redo analog,
        collapsed to an atomic whole-snapshot at DDL/checkpoint time).
        MUST be written after LS checkpoints within checkpoint(): the meta's
        dictionaries have to cover every code referenced by checkpointed
        tablet rows (later codes are recovered from logged dict_appends)."""
        import pickle

        if self.data_dir is None:
            return
        meta = {
            "n_nodes": self.cluster.n_nodes,
            "n_ls": len(self.cluster.ls_groups),
            "tables": dict(self.tables),
            "next_tablet_id": self.rootservice.next_tablet_id,
            "privileges": self.privileges.to_meta(),
            "vector_specs": dict(self._vector_specs),
            "external_specs": dict(self._external_specs),
            "mview_specs": dict(self._mview_specs),
            "view_specs": dict(self._view_specs),
            "trigger_specs": dict(self._trigger_specs),
            "procedures": dict(self._procedure_texts),
            "sequences": {k: dict(v) for k, v in self._sequences.items()},
            # non-default parameter values: ObConfigManager persists its
            # config file (etc/observer.config.bin), so ALTER SYSTEM SET
            # survives a restart — the plan-artifact warm boot depends on
            # its mode parameter still being rw after the reboot
            "config": (
                {n: v for n, v, p in self.config.snapshot()
                 if v != p.default}
                if getattr(self, "config", None) is not None else {}
            ),
            # advisor encoding picks: the dump path re-applies them on the
            # restarted node even before the advisor re-learns the workload
            "enc_hints": (
                dict(self.layout_advisor.encoding_hints)
                if getattr(self, "layout_advisor", None) is not None else {}
            ),
            # undecided XA branches: belt-and-braces alongside log replay
            # (covers an XA_PREPARE recycled below a later checkpoint)
            "xa_registry": {
                x: {"tx_id": e["tx_id"], "owner": e["owner"],
                    "parts": tuple(e["parts"]),
                    "tablets": sorted(e["tablets"])}
                for x, e in self._xa_registry.items()
            },
        }
        import os

        from ..storage.integrity import META, write_atomic

        path = self._meta_path()
        if os.path.exists(path):
            # keep the previous snapshot: a damaged latest copy still has
            # a fallback (same rotation as LS checkpoints)
            try:
                os.replace(path, path + ".prev")
            except OSError:
                pass
        write_atomic(
            path,
            pickle.dumps(meta, protocol=pickle.HIGHEST_PROTOCOL),
            fsync=self._fsync,
            path_class=META,
        )

    def _restore_from_disk(self, meta: dict) -> None:
        """Boot-time recovery, BEFORE the first election: install LS storage
        checkpoints, reinstall the schema, recreate tablets that postdate
        the last checkpoint. Replay of entries (applied_lsn, commit] then
        happens through the normal apply path once leaders elect."""
        from ..storage.ckpt import read_ls_checkpoint, restore_ls_replica
        from ..storage.integrity import CorruptBlock, CounterSink

        sink = CounterSink(self._boot_integrity)
        for ls_id, group in self.cluster.ls_groups.items():
            for node, rep in group.items():
                try:
                    st = read_ls_checkpoint(
                        self._ckpt_path(node, ls_id), metrics=sink)
                except CorruptBlock:
                    # EVERY retained copy failed verification (each one
                    # counted + quarantined by the reader). Recovery is
                    # full log replay — only safe while nothing below the
                    # checkpoint was recycled, checked just like the
                    # missing-checkpoint case below.
                    st = None
                if st is not None:
                    restore_ls_replica(rep, st)
                    # GTS must clear every restored commit version even if
                    # no log records remain to replay (fully-applied ckpt)
                    self.cluster.gts.advance_to(st.get("max_version", 0))
                elif rep.palf.log.base > 0:
                    raise RuntimeError(
                        f"ls {ls_id} node {node}: log recycled to "
                        f"{rep.palf.log.base} but no readable checkpoint; "
                        "replica needs a snapshot rebuild"
                    )
        tables = meta["tables"]

        def mutate(t: dict) -> None:
            t.update(tables)

        self.schema_service.apply_ddl(mutate)
        for ti in tables.values():
            ti.cached_data_version = -1
            ti.writers = 0  # the snapshot may have caught a writer open
            if not hasattr(ti, "indexes"):  # pre-index node_meta snapshots
                ti.indexes = {}
            if not hasattr(ti, "partitions") or ti.partitions is None:
                ti.partitions = [(ti.ls_id, ti.tablet_id)]
                ti.part_col = getattr(ti, "part_col", None)
            for pls, ptab in ti.all_partitions():
                for rep in self.cluster.ls_groups[pls].values():
                    if ptab not in rep.tablets:
                        rep.create_tablet(ptab, ti.schema, ti.key_cols)
            for rep in self.cluster.ls_groups[ti.ls_id].values():
                for idx in ti.indexes.values():
                    if idx.tablet_id not in rep.tablets:
                        rep.create_tablet(idx.tablet_id, idx.schema, idx.key_cols)
            self._unique_keys[ti.name] = tuple(ti.key_cols)
        self.rootservice.next_tablet_id = meta["next_tablet_id"]
        self._ti_by_tablet = None
        # index entries live in sstables installed outside the log (the
        # direct-load analog); a checkpoint covers them, a crash since the
        # last checkpoint may not — re-backfill is idempotent (same-content
        # rows at a newer version) and restores completeness
        self._index_rebuild_pending = [
            (ti, idx)
            for ti in tables.values()
            for idx in ti.indexes.values()
            if idx.status == "ready"
        ]

    def _on_applied_record(self, rec) -> None:
        """Observer of every applied tx record. Normal operation: keeps GTS
        ahead of replicated commit versions. Boot replay: re-applies logged
        dictionary appends (codes past the checkpointed dictionaries) —
        idempotent because codes are dense and append-ordered."""
        from ..tx.records import RecordType as _RT

        if rec.commit_version:
            self.cluster.gts.advance_to(rec.commit_version)
        # XA registry maintenance (idempotent: records apply once per
        # replica; keyed by xid / pruned by tx_id)
        if rec.rtype is _RT.XA_PREPARE and rec.xid and \
                rec.tenant == self.tenant_name:
            e = self._xa_registry.setdefault(rec.xid, {
                "tx_id": rec.tx_id, "owner": rec.owner,
                "parts": tuple(rec.participants), "tablets": set(),
            })
            e["tablets"].update(m.tablet_id for m in rec.mutations)
            self._xa_txids[rec.tx_id] = rec.xid
        elif rec.rtype in (_RT.COMMIT, _RT.ABORT, _RT.REDO_COMMIT):
            _xid = self._xa_txids.pop(rec.tx_id, None)
            if _xid is not None:
                self._xa_registry.pop(_xid, None)
                self._xa_prepared.pop(_xid, None)
        if not rec.dict_appends:
            return
        by_tab = self._ti_by_tablet
        if by_tab is None:
            by_tab = self._ti_by_tablet = {
                ti.tablet_id: ti for ti in self.tables.values()
            }
        apply_dict_appends(by_tab, rec.dict_appends)

    def checkpoint(self, recycle: bool = True) -> bool:
        """slog-ckpt analog: snapshot every replica's storage state, then
        persist schema meta; optionally recycle palf logs below each
        checkpoint. Returns False if any replica skipped (uncommitted
        leader-staged rows) — its log is kept whole and boot replays it."""
        if self.data_dir is None:
            return False
        ok_all = True
        from ..storage.ckpt import write_ls_checkpoint

        done: list[tuple] = []
        for ls_id, group in self.cluster.ls_groups.items():
            for node, rep in group.items():
                covered = write_ls_checkpoint(
                    self._ckpt_path(node, ls_id), rep, fsync=self._fsync
                )
                if covered is not None:
                    done.append((rep, covered))
                else:
                    ok_all = False
        # meta BEFORE recycling: the checkpointed rows' dictionary codes
        # must be durable in meta (or still recoverable from log records)
        # at every instant — recycling first would open a crash window
        # where neither survives
        self._save_node_meta()
        if recycle:
            for rep, covered in done:
                # recycle only what the WRITTEN snapshot covers — the live
                # applied_lsn may have advanced past it since the pickle
                rep.palf.recycle(covered + 1)
        return ok_all

    def close(self) -> None:
        """Flush and release durable resources (log stores), failing
        any forming statement batches to the solo path first."""
        b = getattr(self, "batcher", None)
        if b is not None:
            b.shutdown()
        pa = getattr(self, "plan_artifact", None)
        if pa is not None:
            # fold this boot's statement-summary exec counts into the
            # artifact ranking index so the NEXT boot warm-loads the
            # hottest digests first
            try:
                pa.sync_exec_counts(self.stmt_summary.snapshot())
            except Exception:
                pass
            # queued XLA-cache primes must land before the next boot
            # reads them, or the first warm boot re-pays the compile
            try:
                pa.drain()
            except Exception:
                pass
        for group in self.cluster.ls_groups.values():
            for rep in group.values():
                if rep.palf.store is not None:
                    rep.palf.store.close()

    # ------------------------------------------------------------ schema
    def _invalidate(self, name: str) -> None:
        """Drop one table's cached device batches on EVERY executor that
        may hold them — the single-chip engine executor and (when built)
        the PX executor, whose sharded upload cache is separate."""
        self.engine.executor.invalidate_table(name)
        if self._px_executor_obj is not None:
            self._px_executor_obj.invalidate_table(name)
        # cached result frames over this table died with the snapshot
        # (the watermark key already misses; the eager drop frees bytes)
        rc = getattr(self, "result_cache", None)
        if rc is not None:
            rc.invalidate_tables((name,))

    def _px_executor(self):
        """Lazily-built distributed executor over the full device mesh
        (sessions route statements here via SET ob_px_dop)."""
        if self._px_executor_obj is None:
            from ..parallel.mesh import make_mesh
            from ..parallel.px import PxExecutor

            px = PxExecutor(
                self.catalog,
                make_mesh(),
                unique_keys=self._unique_keys,
                stats=self.engine.stats,
                tracer=self.tracer,
                metrics=self.metrics,
                access=self.access,
            )
            # serving-plane wiring: sharded uploads land in the transfer
            # timeline, the partitioned residency charges the memory
            # governor bytes/n_shards per device, and PX prepare()
            # consults the governed upload budget like single-chip
            px.timeline = self.timeline
            gov = getattr(self, "governor", None)
            if gov is not None:
                px.governor = gov
                gov.register_sharded_residency(
                    px.residency.per_device_bytes)
            self._px_executor_obj = px
        return self._px_executor_obj

    def _px_admission(self):
        """Cluster-wide DOP quota (ObPxAdmission / ObPxTargetMgr): every PX
        statement acquires its worker grant here before executing, so a
        burst queues instead of oversubscribing the mesh. Sized from the
        parallel_servers_target config parameter (live-updatable)."""
        if self._px_admission_obj is None:
            from ..parallel.px import PxAdmission

            self._px_admission_obj = PxAdmission(
                target=self.config["parallel_servers_target"]
            )
            self.config.on_change(
                "parallel_servers_target",
                lambda _n, _o, v: setattr(self._px_admission_obj, "target", v),
            )
        return self._px_admission_obj

    def _key_extra(self, table_names: tuple[str, ...]) -> tuple:
        """Plan-cache key material: schema versions of the referenced
        DML-backed tables. A grown dictionary keeps the entry: its
        programs trace again only where they read the strings (string
        literals bake dictionary lookups at trace time; `DictPin`)."""
        out = []
        tables = self.tables
        for t in table_names:
            ti = tables.get(t)
            if ti is not None:
                out.append((t, ti.schema_version))
        return tuple(out)

    def _artifact_extra(self, table_names: tuple[str, ...]) -> tuple:
        """Plan-artifact key material: schema and dictionary versions. An
        exported executable hands back the dictionaries it was exported
        with, so it serves those versions only."""
        tables = self.tables
        return tuple(
            (t, ti.schema_version, ti.dict_sig)
            for t in table_names
            if (ti := tables.get(t)) is not None)

    def _result_watermark(self, table_names) -> tuple:
        """Result-cache key material: the referenced tables' committed
        data versions (the snapshot watermark). Any committed DML bumps a
        version, so a cached frame can never serve across it — the
        key_extra half (schema/dict versions) rides the logical entry key
        already."""
        out = []
        tables = self.tables
        for t in table_names:
            ti = tables.get(t)
            if ti is not None:
                out.append((t, ti.data_version))
        return tuple(out)

    # ---------------------------------------------- plan artifact store
    def _reconfigure_plan_artifacts(self) -> None:
        """(Re)wire the on-disk plan-artifact tier from config. Called at
        boot and on ob_plan_artifact_mode / plan_artifact_dir changes."""
        import os

        mode = self.config["ob_plan_artifact_mode"]
        adir = str(self.config["plan_artifact_dir"] or "")
        if not adir and self.data_dir is not None:
            adir = os.path.join(self.data_dir, "plan_artifacts")
        if mode == "off" or not adir:
            self.plan_artifact = None
            self.plan_cache.artifact_store = None
            return
        store = self.plan_artifact
        if store is not None and store.root == adir:
            store.mode = mode
            self.plan_cache.artifact_store = store
            return
        from ..engine.plan_artifact import PlanArtifactStore

        store = PlanArtifactStore(
            adir, mode=mode,
            max_bytes=self.config["plan_artifact_max_bytes"],
            metrics=self.metrics)
        self.plan_artifact = store
        self.plan_cache.artifact_store = store
        self._warm_boot_plan_artifacts()

    def _warm_boot_plan_artifacts(self) -> None:
        """Boot-time warm load: hydrate the hottest exported executables
        — ranked by the statement-summary exec counts persisted in the
        store index — until the byte budget is spent. Each hydrated entry
        lands in the plan cache under the same logical key the session
        computes, so the first execution of that statement is a plain
        cache hit: zero engine traces, and the backend compile of the
        deserialized program comes out of the XLA persistent cache."""
        from ..sql.plan_cache import CacheEntry, FastEntry

        store = self.plan_artifact
        if store is None or not store.readable:
            return
        budget = int(store.max_bytes)
        spent = loaded = 0
        for aid, info in store.ranked():
            nbytes = int(info.get("bytes", 0))
            if spent + nbytes > budget:
                continue
            meta = store.read_meta(aid)
            if meta is None:
                continue
            ex = self.engine.executor
            if meta.px_nsh:
                try:
                    ex = self._px_executor()
                except Exception:
                    continue
                if getattr(ex, "nsh", 0) != meta.px_nsh:
                    continue  # mesh shape moved; entry stays for ro tools
            got = store.hydrate(aid, ex, key_extra_fn=self._artifact_extra,
                                meta=meta)
            if got is None:
                continue
            meta, prepared = got
            extra = self._key_extra(meta.tables)
            if meta.px_nsh:
                extra = (*extra, "#exec", id(ex))
            key = (id(self.catalog), meta.art_key[0], meta.art_key[1],
                   meta.art_key[2], meta.art_key[3], extra)
            if self.plan_cache.get(key, count_miss=False) is None:
                entry = CacheEntry(prepared, tuple(meta.output_names),
                                   list(meta.dtypes))
                self.plan_cache.put(key, entry)
            if meta.fast and meta.text_key:
                try:
                    self.plan_cache.fast_put(
                        meta.text_key, FastEntry(**meta.fast))
                except Exception:
                    pass
            spent += nbytes
            loaded += 1
        if loaded:
            self.metrics.add("plan artifact warm load", loaded)
            self.metrics.add("plan artifact warm bytes", spent)

    def refresh_virtual(self, names) -> bool:
        """Materialize referenced __all_virtual_* tables for this statement.
        Returns True if any were referenced (such statements bypass the plan
        cache: per-materialization dictionaries make entries unreusable)."""
        from .virtual_tables import PROVIDERS

        any_vt = False
        for name in names:
            p = PROVIDERS.get(name)
            if p is None:
                continue
            self.catalog[name] = p(self)
            self._invalidate(name)
            any_vt = True
        return any_vt

    def create_table(self, stmt: A.CreateTable) -> None:
        with self._ddl_lock:
            if stmt.name in self.tables or stmt.name in self.catalog:
                if stmt.if_not_exists:
                    return
                raise SqlError(f"table {stmt.name} already exists")
            fields = []
            for c in stmt.columns:
                dt = _parse_type(c.type_name)
                if not c.not_null:
                    dt = dt.with_nullable(True)
                fields.append(Field(c.name, dt))
            schema = Schema(tuple(fields))
            pk = list(stmt.primary_key) or [stmt.columns[0].name]
            for k in pk:
                if k not in schema:
                    raise SqlError(f"primary key column {k} not in table")
                # key columns are implicitly NOT NULL (MySQL semantics)
                i = schema.index(k)
                fields[i] = Field(k, fields[i].dtype.with_nullable(False))
            schema = Schema(tuple(fields))
            if stmt.partition_by is not None:
                if stmt.partition_by not in schema:
                    raise SqlError(
                        f"partition column {stmt.partition_by} not in table"
                    )
                if stmt.partition_by not in pk:
                    # MySQL rule: the partition key must be part of every
                    # unique key, or cross-partition duplicates could hide
                    raise SqlError(
                        "partition column must be part of the primary key"
                    )

            def factory(partitions: list[tuple[int, int]]) -> TableInfo:
                ls_id, tablet_id = partitions[0]
                ti = TableInfo(
                    stmt.name, schema, pk, ls_id, tablet_id,
                    partitions=list(partitions),
                    part_col=stmt.partition_by,
                )
                for f in schema.fields:
                    if f.dtype.kind is TypeKind.VARCHAR:
                        ti.dicts[f.name] = Dictionary()
                return ti

            try:
                ti = self.rootservice.create_table(
                    factory, n_partitions=stmt.n_partitions
                )
            except SchemaError as e:
                raise SqlError(str(e)) from None
            for ls_id, tablet_id in ti.all_partitions():
                for rep in self.cluster.ls_groups[ls_id].values():
                    rep.tablets[tablet_id].cache = self.block_cache
            self._unique_keys[stmt.name] = tuple(pk)
            self._ti_by_tablet = None
            self.catalog[stmt.name] = Table(stmt.name, schema, {
                f.name: np.zeros(0, f.dtype.storage_np) for f in schema.fields
            })
            self._save_node_meta()

    def drop_table(self, stmt: A.DropTable) -> None:
        with self._ddl_lock:
            try:
                ti = self.rootservice.drop_table(stmt.name)
            except SchemaError:
                if stmt.if_exists:
                    return
                raise SqlError(f"no such table {stmt.name}") from None
            for idx in getattr(ti, "indexes", {}).values():
                for rep in self.cluster.ls_groups[ti.ls_id].values():
                    rep.tablets.pop(idx.tablet_id, None)
            self.catalog.pop(stmt.name, None)
            self._unique_keys.pop(stmt.name, None)
            self._ti_by_tablet = None
            self._invalidate(stmt.name)
            self._save_node_meta()

    # ---------------------------------------------------------- sequences
    SEQ_CACHE = 100  # values reserved per meta write

    def create_sequence(self, name: str, start: int = 1,
                        inc: int = 1) -> None:
        with self._ddl_lock:
            if name in self._sequences:
                raise SqlError(f"sequence {name} already exists")
            self._sequences[name] = {
                "next": start, "inc": inc, "reserved": start,
            }
            self._save_node_meta()

    def drop_sequence(self, name: str) -> None:
        with self._ddl_lock:
            if self._sequences.pop(name, None) is None:
                raise SqlError(f"no sequence {name}")
            self._save_node_meta()

    def sequence_next(self, name: str) -> int:
        with self._ddl_lock:
            sq = self._sequences.get(name)
            if sq is None:
                raise SqlError(f"no sequence {name}")
            v = sq["next"]
            inc = sq["inc"]
            sq["next"] = v + inc
            sq["last"] = v  # in-process only: currval before any
            # nextval (or right after restart) is an error, never a
            # value that was skipped or never issued
            past = (
                sq["next"] > sq["reserved"] if inc > 0
                else sq["next"] < sq["reserved"]
            )
            if past or v == sq["reserved"]:
                # crossed into unreserved territory: reserve a new block
                sq["reserved"] = sq["next"] + inc * self.SEQ_CACHE
                self._save_node_meta()
            return v

    # --------------------------------------------------------- plain views
    def create_view(self, st: "A.CreateView") -> None:
        """CREATE [OR REPLACE] VIEW (ob_create_view_resolver.h analog):
        only the definition text persists; expansion/merge happens at plan
        time through the planner's shared view dict."""
        from ..sql import parser as P2

        with self._ddl_lock:
            if st.name in self.tables or st.name in self._mview_specs or \
                    st.name in self._external_specs:
                raise SqlError(f"object {st.name} already exists")
            if st.name in self._view_specs and not st.or_replace:
                raise SqlError(f"view {st.name} already exists")
            body = P2.parse(st.query_sql)
            if not isinstance(body, (A.Select, A.SetSelect)):
                raise SqlError("CREATE VIEW body must be a SELECT")
            # validate references NOW (MySQL checks at create): every
            # referenced name must be a table, view, or mview
            for n in self.expand_views(_tables_in_ast(body)):
                if n not in self.tables and n not in self._mview_specs \
                        and n not in self.catalog:
                    raise SqlError(f"view references unknown table {n}")
            self._view_specs[st.name] = st.query_sql
            self._save_node_meta()

    def drop_view(self, name: str) -> None:
        with self._ddl_lock:
            if self._view_specs.pop(name, None) is None:
                raise SqlError(f"no view {name}")
            self._save_node_meta()

    def expand_views(self, names: set) -> set:
        """Map a statement's referenced names through view definitions to
        the BASE tables that must be fresh in the analytic catalog."""
        from ..sql import parser as P2

        out: set = set()
        stack, seen = list(names), set()
        while stack:
            n = stack.pop()
            if n in seen:
                continue
            seen.add(n)
            spec = self._view_specs.get(n)
            if spec is None:
                out.add(n)
                continue
            try:
                stack.extend(_tables_in_ast(P2.parse(spec)))
            except SyntaxError:
                pass
        return out

    # ------------------------------------------------------------ triggers
    def create_trigger(self, st: "A.CreateTrigger") -> None:
        from ..sql.trigger import TriggerError, parse_body

        with self._ddl_lock:
            if st.name in self._trigger_specs:
                raise SqlError(f"trigger {st.name} already exists")
            if st.table not in self.tables:
                raise SqlError(f"no such table {st.table}")
            try:
                acts = parse_body(st.body_sql)
            except (TriggerError, SyntaxError) as e:
                raise SqlError(f"bad trigger body: {e}") from None
            if st.timing == "after" and any(a[0] == "setnew" for a in acts):
                raise SqlError("SET NEW.x is only valid in BEFORE triggers")
            if st.event == "delete" and any(a[0] == "setnew" for a in acts):
                raise SqlError("DELETE triggers have no NEW row")
            self._trigger_specs[st.name] = {
                "timing": st.timing, "event": st.event,
                "table": st.table, "body": st.body_sql,
            }
            self._trigger_parsed[st.name] = acts
            self._save_node_meta()

    def drop_trigger(self, name: str) -> None:
        with self._ddl_lock:
            if self._trigger_specs.pop(name, None) is None:
                raise SqlError(f"no trigger {name}")
            self._trigger_parsed.pop(name, None)
            self._save_node_meta()

    def triggers_for(self, table: str, event: str, timing: str) -> list:
        """Parsed bodies of matching triggers, in name order (the firing
        order contract)."""
        from ..sql.trigger import parse_body

        out = []
        for name in sorted(self._trigger_specs):
            spec = self._trigger_specs[name]
            if spec["table"] != table or spec["event"] != event or \
                    spec["timing"] != timing:
                continue
            acts = self._trigger_parsed.get(name)
            if acts is None:
                acts = self._trigger_parsed[name] = parse_body(spec["body"])
            out.append((name, acts))
        return out

    # -------------------------------------------------- materialized views
    def create_mview(self, st: A.CreateMaterializedView) -> None:
        """Full-refresh materialized view (src/storage/mview analog at
        this engine's scale: definition text in meta like the reference's
        schema-service mview definitions; REFRESH re-plans and
        re-materializes against current data)."""
        with self._ddl_lock:
            if st.name in self.tables or st.name in self.catalog:
                raise SqlError(f"table {st.name} already exists")
        # materialization (plan + XLA compile + run) happens OUTSIDE the
        # DDL lock — it can take seconds and must not stall other DDL;
        # the lock re-checks before the catalog swap
        from ..sql import parser as P2

        self.refresh_catalog(_tables_in_ast(P2.parse(st.query_sql)), tx=None)
        t = self.engine.materialize(st.query_sql, st.name)
        with self._ddl_lock:
            if st.name in self.tables or st.name in self.catalog:
                raise SqlError(f"table {st.name} already exists")
            self.catalog[st.name] = t
            self._invalidate(st.name)
            self._mview_specs[st.name] = st.query_sql
            self._save_node_meta()

    def _materialize_mview(self, name: str, sql_text: str) -> None:
        from ..sql import parser as P2

        # base-table snapshots must be current before the defining query
        # runs (the same refresh every SELECT path does)
        self.refresh_catalog(
            _tables_in_ast(P2.parse(sql_text)), tx=None)
        self.catalog[name] = self.engine.materialize(sql_text, name)
        self._invalidate(name)

    def refresh_mview(self, name: str) -> None:
        with self._ddl_lock:
            sql_text = self._mview_specs.get(name)
        if sql_text is None:
            raise SqlError(f"no materialized view {name}")
        from ..sql import parser as P2

        self.refresh_catalog(
            _tables_in_ast(P2.parse(sql_text)), tx=None)
        t = self.engine.materialize(sql_text, name)
        with self._ddl_lock:
            if name not in self._mview_specs:
                return  # dropped concurrently: discard, don't resurrect
            self.catalog[name] = t
            self._invalidate(name)

    def drop_mview(self, name: str) -> None:
        with self._ddl_lock:
            if self._mview_specs.pop(name, None) is None:
                raise SqlError(f"no materialized view {name}")
            self.catalog.pop(name, None)
            self._invalidate(name)
            self._save_node_meta()

    def create_external_table(self, st: A.CreateExternalTable) -> None:
        """External table via the plugin loader registry (src/plugin's
        ob_external_arrow_data_loader analog): the file materializes as
        a columnar catalog Table readable by every query path; DML is
        rejected (the table is not LSM-backed), matching the reference's
        read-only external tables."""
        from ..plugin import ExternalFormatError, load_external

        with self._ddl_lock:
            if st.name in self.tables or st.name in self.catalog:
                raise SqlError(f"table {st.name} already exists")
            try:
                t = load_external(st.name, st.format, st.location)
            except ExternalFormatError as e:
                raise SqlError(str(e)) from None
            except OSError as e:
                raise SqlError(f"cannot read {st.location}: {e}") from None
            self.catalog[st.name] = t
            self._external_specs[st.name] = (st.format, st.location)
            self._save_node_meta()

    # ----------------------------------------------------------- indexes
    def create_vector_index(self, st: A.CreateVectorIndex) -> None:
        """IVF-flat ANN index registration (storage/vector_index.py);
        the artifact builds lazily per table version, so DML maintenance
        is the usual invalidate + rebuild contract."""
        from ..core.dtypes import TypeKind
        from ..storage.vector_index import register_vector_index

        ti = self.tables.get(st.table)
        if ti is None:
            raise SqlError(f"no such table {st.table}")
        try:
            ct = ti.schema[st.column]
        except Exception:
            raise SqlError(f"no such column {st.column}") from None
        if ct.kind is not TypeKind.VECTOR:
            raise SqlError(f"{st.column} is not a VECTOR column")
        self._vector_specs.setdefault(st.table, {})[st.column] = (
            st.lists, st.nprobe)
        t = self.catalog.get(st.table)
        if t is not None:
            register_vector_index(
                self.catalog, st.table, st.column, st.lists, st.nprobe)
        self._save_node_meta()

    def drop_vector_index(self, st: A.DropVectorIndex) -> None:
        from ..storage.vector_index import drop_vector_index

        specs = self._vector_specs.get(st.table, {})
        specs.pop(st.column, None)
        t = self.catalog.get(st.table)
        if t is not None:
            drop_vector_index(self.catalog, st.table, st.column)
        self._save_node_meta()

    def create_index(self, st: A.CreateIndex) -> None:
        """Online-ish index build (src/storage/ddl direct-insert analog):

        1. register the index under a momentary SHARE table lock — from
           that instant every DML statement maintains it, and the SHARE
           grant guarantees no tx holding ROW_X (staged base writes that
           would miss maintenance) spans the registration;
        2. backfill from a base-table snapshot taken after registration via
           the direct-load path (an sstable at the snapshot version on all
           replicas) — concurrent post-registration DML lands at HIGHER
           commit versions, so MVCC ordering resolves every interleaving;
        3. flip to ready."""
        from ..tx.tablelock import LockMode, WouldBlock

        with self._ddl_lock:
            ti = self.tables.get(st.table)
            if ti is None:
                raise SqlError(f"no such table {st.table}")
            if st.name in ti.indexes:
                if st.if_not_exists:
                    return
                raise SqlError(f"index {st.name} already exists on {st.table}")
            for c in st.columns:
                if c not in ti.schema:
                    raise SqlError(f"unknown column {c}")
            icols = list(st.columns)
            kcols = icols + [k for k in ti.key_cols if k not in icols]
            ischema = Schema(tuple(Field(c, ti.schema[c]) for c in kcols))
            ikey = icols if st.unique else kcols

            lock_tx = -next(self._session_ids)  # DDL-private lock owner
            deadline = _time.monotonic() + 10.0
            while True:
                try:
                    self.lock_mgr.lock(lock_tx, ti.tablet_id, LockMode.SHARE)
                    break
                except WouldBlock:
                    if _time.monotonic() > deadline:
                        raise SqlError(
                            f"create index on {st.table}: writers did not drain"
                        ) from None
                    _time.sleep(0.005)
            try:
                tablet_id = self.rootservice.create_index_tablet(
                    ti.ls_id, ischema, ikey
                )
                idx = IndexInfo(
                    st.name, st.table, tuple(icols), tablet_id, ischema,
                    ikey, unique=st.unique,
                )

                def mutate(tables: dict) -> None:
                    tables[st.table].indexes[st.name] = idx

                ti.schema_version = self.schema_service.apply_ddl(mutate)
            finally:
                self.lock_mgr.release_all(lock_tx)
            for rep in self.cluster.ls_groups[ti.ls_id].values():
                rep.tablets[tablet_id].cache = self.block_cache
            try:
                self._backfill_index(ti, idx)
            except Exception:
                def unmutate(tables: dict) -> None:
                    tables[st.table].indexes.pop(st.name, None)

                self.schema_service.apply_ddl(unmutate)
                for rep in self.cluster.ls_groups[ti.ls_id].values():
                    rep.tablets.pop(tablet_id, None)
                raise
            self._save_node_meta()

    def _backfill_index(self, ti: TableInfo, idx: IndexInfo) -> None:
        """Fill the index tablet from a base snapshot (direct-load style:
        one sorted sstable installed on every replica at the snapshot
        version). Idempotent — re-running adds same-content rows at a newer
        version, which is how crash recovery re-completes an index."""
        from ..storage.sstable import SSTable, write_sstable

        s0 = self.cluster.gts.next_ts()
        parts = []
        for pls, ptab in ti.all_partitions():
            rep = self._leader_replica_ls(pls)
            parts.append(rep.tablets[ptab].scan(
                s0, columns=list(idx.schema.names())
            ))
        data = (
            parts[0] if len(parts) == 1
            else {c: np.concatenate([p[c] for p in parts]) for c in parts[0]}
        )
        n = len(data[idx.schema.names()[0]]) if idx.schema.names() else 0
        if n:
            keys = [data[k].astype(np.int64) for k in idx.key_cols]
            order = np.lexsort(tuple(reversed(keys)))
            cols = {c: data[c][order] for c in idx.schema.names()}
            if idx.unique:
                k2d = np.stack([cols[k].astype(np.int64) for k in idx.key_cols], axis=1)
                dup = (k2d[1:] == k2d[:-1]).all(axis=1)
                if dup.any():
                    raise SqlError(
                        f"unique index {idx.name}: duplicate value "
                        f"{tuple(k2d[1:][dup][0])}"
                    )
            blob = write_sstable(
                idx.schema, idx.key_cols, cols,
                versions=np.full(n, s0, np.int64),
                ops=np.zeros(n, np.int8),
                base_version=0, end_version=s0,
            )
            for r in self.cluster.ls_groups[ti.ls_id].values():
                t = r.tablets[idx.tablet_id]
                with t._meta_lock:
                    t.deltas.append(
                        SSTable(blob, idx.schema, idx.key_cols,
                                cache=self.block_cache)
                    )
        idx.build_version = s0
        idx.status = "ready"

    def drop_index(self, st: A.DropIndex) -> None:
        with self._ddl_lock:
            ti = self.tables.get(st.table)
            idx = ti.indexes.get(st.name) if ti is not None else None
            if idx is None:
                if st.if_exists:
                    return
                raise SqlError(f"no such index {st.name} on {st.table}")

            def mutate(tables: dict) -> None:
                tables[st.table].indexes.pop(st.name, None)

            ti.schema_version = self.schema_service.apply_ddl(mutate)
            for rep in self.cluster.ls_groups[ti.ls_id].values():
                rep.tablets.pop(idx.tablet_id, None)
            self._save_node_meta()

    # ---------------------------------------------------------- snapshots
    #: bound on per-call location refreshes before the stale entry is
    #: surfaced to the statement retry layer as a classified error
    _LOCATION_RETRY_LIMIT = 8

    def _leader_replica_ls(self, ls_id: int):
        """Route through the location cache; stale entries retry under the
        STALE_LOCATION policy — bounded, backed off on the virtual clock so
        an in-flight election can settle between probes (the NOT_MASTER
        feedback loop of the reference's DAS routing). Exhausting the bound
        raises StaleLocation, which the statement retry controller treats
        as retryable-after-refresh."""
        from ..share.interrupt import checkpoint

        policy = _R.STALE_LOCATION
        attempt = 0
        while True:
            try:
                node = self.location.leader(ls_id)
            except RuntimeError:
                # the resolver itself found no ready leader (election still
                # in flight): same retry treatment as a stale cache entry
                rep = None
            else:
                rep = self.cluster.ls_groups[ls_id][node]
            if rep is not None and rep.is_ready:
                return rep
            attempt += 1
            if attempt > self._LOCATION_RETRY_LIMIT:
                self.metrics.add("location retries exhausted")
                raise _R.StaleLocation(
                    f"ls {ls_id}: no ready leader after "
                    f"{self._LOCATION_RETRY_LIMIT} location refreshes"
                )
            self.metrics.add("location cache refreshes")
            self.location.invalidate(ls_id)
            checkpoint()  # deadline / KILL QUERY unwind between probes
            wait = min(policy.base_wait * attempt, policy.max_wait)
            with self.metrics.waiting("location cache refresh"):
                self.cluster.settle(wait)

    def _leader_replica(self, ti: TableInfo):
        return self._leader_replica_ls(ti.ls_id)

    def snapshot_table(self, name: str, snapshot: int) -> Table:
        """FLASHBACK read: materialize `name` AS OF an older MVCC
        snapshot (reference: ob_log_flashback_service / Oracle-mode
        SELECT ... AS OF SNAPSHOT). Versions survive until major
        compaction discards them — reads below the discarded snapshot
        raise SnapshotDiscarded, the same undo-retention contract."""
        ti = self.tables.get(name)
        if ti is None:
            raise SqlError(f"no such table {name}")
        parts = []
        for ls_id, tablet_id in ti.all_partitions():
            rep = self._leader_replica_ls(ls_id)
            parts.append(rep.tablets[tablet_id].scan(snapshot, tx_id=0))
        if len(parts) == 1:
            data = parts[0]
        else:
            data = {
                c: np.concatenate([p[c] for p in parts])
                for c in parts[0]
            }
        dicts = ti.remap_sorted(data)
        return Table(name, ti.schema, data, dicts)

    def refresh_catalog(self, names, tx=None) -> None:
        """Bring catalog snapshot Tables of the given tables up to date.

        Inside an open tx every tablet table reads at the tx's BEGIN-time
        snapshot (repeatable reads across the whole statement set); tables
        the tx wrote additionally see their own staged rows via tx_id. Tx
        views are never left in the committed cache."""
        self.settle_writers()
        for name in names:
            ti = self.tables.get(name)
            if ti is None:
                continue  # preloaded read-only table
            in_tx = tx is not None and tx.ctx is not None
            if not in_tx and ti.cached_data_version == ti.data_version:
                continue
            # the snapshot rescan: a statement of a transaction that
            # tx_shared_entry did not serve, an autocommit one only after
            # a commit moved the table's data version. The version is read
            # BEFORE the scan: a commit that bumps it meanwhile leaves the
            # entry labelled stale, never current without its rows
            version = ti.data_version
            with _GL.span("catalog refresh") as sp:
                touched = in_tx and name in tx.touched_tables
                snap = (tx.ctx.read_snapshot if in_tx
                        else self.cluster.gts.current())
                parts = []
                for ls_id, tablet_id in ti.all_partitions():
                    if touched:
                        rep = tx.svc.replicas[ls_id]
                    else:
                        rep = self._leader_replica_ls(ls_id)
                    parts.append(rep.tablets[tablet_id].scan(
                        snap, tx_id=tx.ctx.tx_id if touched else 0,
                    ))
                if len(parts) == 1:
                    data = parts[0]
                else:
                    data = {
                        c: np.concatenate([p[c] for p in parts])
                        for c in parts[0]
                    }
                dicts = ti.remap_sorted(data)
                _dense_vectors(ti.schema, data)
                t = Table(name, ti.schema, data, dicts)
                sp.count("catalog refreshes")
                sp.count("catalog refresh rows", t.nrows)
            if in_tx:
                # tx-private view (BEGIN snapshot + own staged rows): lives
                # on the tx, activated per-statement via catalog.tx_scope —
                # never the shared committed entry other sessions read
                tx.views[name] = t
            else:
                # the replaced Table object carries no sorted_projections
                # registration, so routing stops by construction; delete
                # the orphaned projection tables, their device batches,
                # and every cached plan (a cached plan routed to the
                # dropped projection would KeyError — or worse, a
                # re-materialized namesake would serve stale device
                # columns)
                old = self.catalog.get(name)
                projs = getattr(old, "sorted_projections", None)
                requeue = None
                if projs:
                    from ..storage.sorted_projection import drop_projections

                    # DML invalidation is not silent: it counts in sysstat
                    # and the advisor re-queues a background rebuild (auto
                    # mode / advisor-managed layouts) instead of losing
                    # the projection until someone hand-rebuilds it
                    self.metrics.add(
                        "sorted projection invalidations", len(projs))
                    try:
                        requeue = self.layout_advisor.note_invalidated(
                            name, projs)
                    except Exception:  # noqa: BLE001 - advisory path
                        pass
                    for pname in projs.values():
                        self._invalidate(pname)
                    drop_projections(self.catalog, name)
                    self.plan_cache.flush()
                vspecs = self._vector_specs.get(name)
                if vspecs:
                    from ..storage.vector_index import register_vector_index

                    for col, (lists, nprobe) in vspecs.items():
                        register_vector_index(
                            {name: t}, name, col, lists, nprobe)
                # the publish, whole to tx_shared_entry: the entry, its
                # device batches dropped, its label
                with self._snapshot_lock:
                    self.catalog[name] = t
                    self._invalidate(name)
                    ti.cached_data_version = version
                if vspecs:
                    # DML invalidated the built IVF artifacts (the
                    # _invalidate above dropped the executor's #ivfh/#ivfd
                    # caches): re-queue background rebuilds so the next
                    # ANN query probes warm instead of k-means inline
                    self.metrics.add(
                        "vector index invalidations", len(vspecs))
                    try:
                        self.layout_advisor.note_vector_invalidated(
                            name, list(vspecs))
                    except Exception:  # noqa: BLE001 - advisory path
                        pass
                if requeue is not None:
                    try:
                        # only now that the refreshed snapshot landed: a
                        # dag worker starting the rebuild must see the
                        # current version, not re-enter this refresh
                        requeue()
                    except Exception:  # noqa: BLE001 - advisory path
                        pass
                self._enforce_memory(keep=name)

    def tx_shared_entry(self, name: str, tx) -> "tuple[Table, int] | None":
        """(the shared committed entry of `name`, the data version it was
        found at) where that entry answers exactly as a rescan at `tx`'s
        BEGIN snapshot would; None where the statement must rescan.

        Checked under `_snapshot_lock`: `tx` staged nothing in the table;
        no writer is open on it, so no commit of it is in flight; the
        entry's label is the data version read before its scan, and still
        current, so every writer finished before that read, and the
        entry's snapshot, drawn after it, holds all their commits; and the
        highest version they committed at is at or below `tx`'s snapshot.
        Both snapshots then hold every commit of the table and nothing
        else, and a commit after the check draws a version above both
        (they were drawn before it). A stale entry of a quiet table is
        published anew, as an autocommit read would; what changes while
        the statement runs is `tx_shared_holds`'."""
        ti = self.tables.get(name)
        if ti is None or name in tx.touched_tables:
            return None
        snap = tx.ctx.read_snapshot
        self.settle_writers()
        for refreshed in (False, True):
            with self._snapshot_lock:
                if ti.writers or ti.last_commit_version > snap:
                    return None
                if ti.cached_data_version == ti.data_version:
                    t = dict.get(self.catalog, name)
                    return None if t is None else (t, ti.data_version)
            if refreshed:
                return None
            self.refresh_catalog([name], tx=None)

    def tx_shared_holds(self, shared: dict) -> bool:
        """True while every entry `tx_shared_entry` gave is still the
        catalog's and its table's data version has not moved: a statement
        that read them stands, else it runs again on its rescan."""
        with self._snapshot_lock:
            return all(
                dict.get(self.catalog, n) is t
                and self.tables[n].data_version == v
                for n, (t, v) in shared.items())

    def writer_open(self, tx, ti: TableInfo) -> None:
        """`tx` writes `ti` for the first time: open on it until
        `writers_close`, so no transaction reads its shared entry."""
        with self._snapshot_lock:
            if ti.name in tx.touched_tables:
                return
            ti.writers += 1
            tx.touched_tables.add(ti.name)
            tx.writing.append(ti)

    def writers_close(self, tis, commit_version: int | None) -> None:
        """Writers of `tis` end; `commit_version` is what they committed
        at (None: nothing committed). The version is noted before the
        bump and the writer counted out after it, in one step."""
        with self._snapshot_lock:
            for ti in tis:
                if commit_version is not None:
                    ti.last_commit_version = max(
                        ti.last_commit_version, commit_version)
                    ti.data_version += 1
                ti.cached_data_version = -1
                ti.writers -= 1

    def writers_end(self, ctx, tis) -> None:
        """The transaction `ctx` stops writing `tis`: closed at its
        commit version once decided; else (a commit wait that timed out,
        its decision in flight) left open until `settle_writers` sees the
        decision land, so no reader takes a shared entry that the late
        commit would leave without its rows."""
        from ..tx.txn import TxState

        tis = list(tis)
        if not tis:
            return
        if ctx.is_done:
            self.writers_close(tis, ctx.commit_version
                               if ctx.state is TxState.COMMITTED else None)
            return
        with self._snapshot_lock:
            for ti in tis:
                ti.cached_data_version = -1
            self._undecided.append((ctx, tis))

    def settle_writers(self) -> None:
        """Close the writers `writers_end` left open whose decision has
        landed since."""
        if not self._undecided:
            return
        keep, done = [], []
        with self._snapshot_lock:
            for w in self._undecided:
                (done if w[0].is_done else keep).append(w)
            self._undecided = keep
        for ctx, tis in done:
            self.writers_end(ctx, tis)

    @contextlib.contextmanager
    def bulk_write(self, tis):
        """A writer outside a transaction is open on `tis` from before it
        draws its version until after it bumps: the bump counts it as
        committed at the GTS's current value, at or above any version it
        drew. Every path that changes a table's rows outside a
        transaction comes through here (direct load, standby apply, a
        recovered XA branch's decision, restore and PITR replay); a
        transaction's writes go through `writer_open` and `writers_end`.
        A path that bumps `data_version` itself would let
        `tx_shared_entry` serve an entry without its rows."""
        tis = list(tis)
        with self._snapshot_lock:
            for ti in tis:
                ti.writers += 1
        try:
            yield
        finally:
            self.writers_close(tis, self.cluster.gts.current())

    # ------------------------------------------------------ follower reads
    #: bound on replica-snapshot catch-up waits before a bounded-staleness
    #: read rejects back to the leader path
    _FOLLOWER_WAIT_LIMIT = 3
    _FOLLOWER_VIEW_CACHE_MAX = 128

    def _follower_replica(self, ls_id: int, dead: set[int]):
        """Serving replica for a follower read of ls_id: the highest-
        watermark non-leader replica on a reachable node, falling back to
        the leader itself (a one-survivor cluster keeps serving). None
        when every replica is unreachable."""
        group = self.cluster.ls_groups[ls_id]
        best = None
        for node, rep in sorted(group.items()):
            if node in dead or rep.is_leader:
                continue
            if best is None or rep.apply_watermark > best.apply_watermark:
                best = rep
        if best is not None:
            return best
        for node, rep in sorted(group.items()):
            if node not in dead and rep.is_leader:
                return rep
        return None

    def _follower_snapshot(self, reps) -> int:
        """Largest provably-complete snapshot across the chosen replicas.

        Caught-up fast path: under gts.submit_lock a fresh GTS read is
        safe when every replica has applied its live leader's last
        appended entry — the lock excludes any committer between version
        fetch and log append, so no commit version <= ts can be missing.
        Otherwise the min apply watermark: submit-lock ordering makes an
        applied scn dominate every earlier commit version in that log."""
        gts = self.cluster.gts
        with gts.submit_lock:
            ts = gts.current()
            for rep in reps:
                group = self.cluster.ls_groups[rep.ls_id]
                lead = _leader_of([r.palf for r in group.values()])
                if lead is None or rep.palf.applied_lsn != len(lead.log) - 1:
                    break
            else:
                return ts
        return min((rep.apply_watermark for rep in reps), default=ts)

    def follower_read_views(self, names, max_stale_us: int,
                            weak: bool = False):
        """Statement-scoped follower Tables for the replicated tables
        among `names`, read at a bounded-staleness snapshot.

        Returns (views, snapshot, stale_us), or None when the bound
        cannot be met (counted as a staleness reject — the caller falls
        back to the leader path), when no replicated table is involved,
        or when an LS has an undecided prepared (2PC/XA) transaction on
        its chosen replica — the prepare carries no version floor in
        this rebuild, so a non-weak read cannot prove completeness."""
        dead = self.cluster.unreachable_nodes()
        involved: dict[str, TableInfo] = {}
        chosen: dict[int, "LSReplica"] = {}
        for name in names:
            ti = self.tables.get(name)
            if ti is None:
                continue
            involved[name] = ti
            for ls_id, _tab in ti.all_partitions():
                if ls_id in chosen:
                    continue
                rep = self._follower_replica(ls_id, dead)
                if rep is None:
                    return None
                chosen[ls_id] = rep
        if not involved:
            return None
        reps = list(chosen.values())
        if not weak and any(rep._pending_redo for rep in reps):
            self.metrics.add("follower read staleness rejects")
            return None
        attempt = 0
        while True:
            snap = self._follower_snapshot(reps)
            stale_us = max(0, self.cluster.gts.current() - snap)
            if weak or stale_us <= max_stale_us:
                break
            attempt += 1
            if attempt > self._FOLLOWER_WAIT_LIMIT:
                self.metrics.add("follower read staleness rejects")
                return None
            # lagging replication may catch up within the bound: drive
            # the cluster briefly before rejecting back to the leader
            with self.metrics.waiting("replica snapshot wait"):
                self.cluster.settle(0.05 * attempt)
        views = {
            name: self._follower_table(name, ti, chosen, snap)
            for name, ti in involved.items()
        }
        return views, snap, stale_us

    def _follower_table(self, name: str, ti: "TableInfo",
                        chosen: dict, snap: int) -> Table:
        """Materialize one table from its chosen replicas at `snap`,
        cached by (replica apply positions, dict signature): an unchanged
        applied_lsn means no new rows applied, so any snapshot >= the
        cached one scans to identical rows."""
        pkey = tuple(
            (ls_id, chosen[ls_id].node_id, chosen[ls_id].palf.applied_lsn)
            for ls_id, _tab in ti.all_partitions()
        )
        key = (name, pkey, ti.dict_sig)
        hit = self._follower_views.get(key)
        if hit is not None:
            return hit
        parts = []
        for ls_id, tablet_id in ti.all_partitions():
            parts.append(chosen[ls_id].tablets[tablet_id].scan(snap, tx_id=0))
        if len(parts) == 1:
            data = parts[0]
        else:
            data = {
                c: np.concatenate([p[c] for p in parts]) for c in parts[0]
            }
        dicts = ti.remap_sorted(data)
        t = Table(name, ti.schema, data, dicts)
        while len(self._follower_views) >= self._FOLLOWER_VIEW_CACHE_MAX:
            self._follower_views.pop(next(iter(self._follower_views)))
        self._follower_views[key] = t
        return t

    def _resident_bytes(self) -> int:
        """Approximate bytes of DML-backed catalog snapshots (the tenant's
        resident analytic memory — the unit's accounting surface)."""
        total = 0
        for name, ti in self.tables.items():
            t = self.catalog.get(name)
            if t is None:
                continue
            for a in t.data.values():
                total += getattr(a, "nbytes", 0)
        # device-pinned result-cache frames are tenant residency too —
        # the governor must see them or cache growth would hide from
        # admission control
        rc = getattr(self, "result_cache", None)
        if rc is not None:
            total += rc.device_bytes()
        # device-resident IVF artifacts: an index the advisor keeps hot
        # is tenant memory too (eviction via the same priority order —
        # dropping a table's snapshot invalidates its index caches)
        try:
            total += self.engine.executor.ann_device_bytes()
        except Exception:  # noqa: BLE001 - accounting must not fail DML
            pass
        return total

    def _enforce_memory(self, keep: str) -> None:
        """Tenant memory unit: evict other tables' snapshots (they re-
        materialize on next use) until under the quota; raise if the kept
        table alone exceeds it (the unit is simply too small)."""
        limit = self.unit.memory_limit
        if limit is None:
            return
        if self._resident_bytes() <= limit:
            return
        # advisor residency priorities order the eviction: the lowest-
        # priority tables lose their snapshots (and, via _invalidate,
        # their device batches) first; ties keep insertion order
        order = sorted(
            self.tables.items(),
            key=lambda kv: self.residency_priority.get(kv[0], 0.0),
        )
        for name, ti in order:
            if name == keep:
                continue
            t = self.catalog.get(name)
            if t is None or not t.data or ti.cached_data_version < 0:
                continue
            empty = Table(name, ti.schema, {
                f.name: np.zeros(0, f.dtype.storage_np)
                for f in ti.schema.fields
            })
            with self._snapshot_lock:
                self.catalog[name] = empty
                ti.cached_data_version = -1
                self._invalidate(name)
            if self._resident_bytes() <= limit:
                return
        if self._resident_bytes() > limit:
            raise SqlError(
                f"tenant {self.tenant_name}: memory unit exceeded "
                f"({self._resident_bytes()} > {limit} bytes)"
            )

    def _evict_cold_residency(self) -> None:
        """Degradation ladder rung 1 (after a device OOM): free the
        coldest device-resident state without touching durable data —
        cached device batches of low-priority tables (advisor residency
        priorities order the walk, like _enforce_memory) and half the
        decoded block cache. Everything re-materializes on next use."""
        # cached result frames first: the most re-creatable bytes on the
        # chip (one warm dispatch rebuilds any of them)
        rc = getattr(self, "result_cache", None)
        if rc is not None and rc.flush():
            self.metrics.add("result cache evictions: device oom")
        ex = self.engine.executor
        order = sorted(
            {k[0] for k in ex._batch_cache} | {k[0] for k in ex._assembled},
            key=lambda n: self.residency_priority.get(n, 0.0),
        )
        for name in order:
            ex.invalidate_table(name)
        bc = self.block_cache
        if bc.bytes_used > 0:
            cap = bc.capacity_bytes
            bc.set_capacity(bc.bytes_used // 2)
            bc.capacity_bytes = cap  # one-shot trim, budget unchanged
        self.metrics.add("residency evictions: device oom")

    _UID_MISS = object()

    def _block_priority(self, key) -> float:
        """Residency priority of a block-cache key ((sstable uid, block,
        column)); unknown uids rebuild the uid map once and then cache a
        negative answer so eviction stays O(1)."""
        try:
            uid = key[0]
        except Exception:
            return 0.0
        name = self._uid_tables.get(uid, self._UID_MISS)
        if name is self._UID_MISS:
            m = {}
            for tname, ti in self.tables.items():
                for ls_id, tablet_id in ti.all_partitions():
                    for rep in (
                            self.cluster.ls_groups.get(ls_id) or {}
                    ).values():
                        tab = rep.tablets.get(tablet_id)
                        if tab is None:
                            continue
                        for ss in getattr(tab, "deltas", ()):
                            m[ss.uid] = tname
                        if getattr(tab, "base", None) is not None:
                            m[tab.base.uid] = tname
            m.setdefault(uid, None)
            self._uid_tables = m
            name = m[uid]
        if name is None:
            return 0.0
        return float(self.residency_priority.get(name, 0.0))

    def kill_query(self, session_id: int, reason: str = "killed by user") -> None:
        """Interrupt a session's running statement cluster-wide (the
        ObGlobalInterruptManager call analog; KILL QUERY <session>)."""
        iid = self._active_stmts.get(session_id)
        if iid is None:
            raise SqlError(f"session {session_id} has no running statement")
        self.interrupts[0].interrupt(iid, reason)

    # ------------------------------------------------------------ session
    def metrics_text(self) -> str:
        """Prometheus text exposition of the whole engine (one scrape):
        every counter/gauge/wait-event/histogram in the tenant registry,
        plus the cache and audit-ring stats kept outside it."""
        m = self.metrics
        m.gauge_set("plan cache entries", len(self.plan_cache))
        m.gauge_set("sql audit records", len(self.audit.records()))
        m.gauge_set("active statements", len(self._active_stmts))
        # serving-timeline self-metering: ring occupancy/bytes/records +
        # the retained window's device-busy fraction
        self.timeline.meter(m)
        m.gauge_set("health alerts", len(self.sentinel.alerts()))
        return m.prometheus_text()

    def session(self, user: str = "root") -> "DbSession":
        return DbSession(self, user=user)


# reservation marker in _xa_prepared while a PREPARE is still logging:
# blocks duplicate xids atomically without presenting as decidable
_XA_PREPARING = object()


class _OpenTx:
    """Client-side state of an open transaction."""

    def __init__(self, db: Database, deadline: "_R.Deadline | None" = None):
        self.db = db
        # ob_trx_timeout deadline, fixed at BEGIN on the virtual clock:
        # every statement of the tx runs under min(its own query deadline,
        # this) — an expired tx surfaces TrxTimeout at the next checkpoint
        self.deadline = deadline
        # home the tx where leadership currently lives (location cache):
        # after a failover/demotion new txs follow the leaders instead of
        # dragging leadership back to a fixed node
        try:
            home = db.location.leader(min(db.cluster.ls_groups))
        except Exception:
            home = 0
        self.svc = db.cluster.services[home]
        self.ctx = self.svc.begin()
        self.touched_tables: set[str] = set()
        # the TableInfos this tx is counted open on (Database.writer_open)
        self.writing: list[TableInfo] = []
        # tx-private catalog views (BEGIN snapshot + own staged rows),
        # activated per-statement through TxCatalog.tx_scope
        self.views: dict[str, Table] = {}

    def ensure_leader(self, ls_id: int) -> None:
        """Co-locate the LS leader with this tx's coordinating node (the
        analog of routing the statement to a server leading the
        participants), and wait until it is READY (replay caught up) —
        role transfer alone is not enough to serve writes."""
        from ..tx.txn import NotMaster

        rep = self.svc.replicas[ls_id]
        if rep.is_ready:
            return
        try:
            self.db.cluster.transfer_leader(ls_id, self.svc.node_id)
        except TimeoutError as e:
            # the drag failed (home node dead/partitioned, or no leader to
            # hand off yet): OB_NOT_MASTER — the statement retry layer
            # re-homes the tx after a location refresh
            raise NotMaster(f"ls {ls_id}: {e}", ls_id=ls_id) from e
        if not self.db.cluster.drive_until(lambda: rep.is_ready):
            raise NotMaster(f"ls {ls_id} leadership did not settle",
                            ls_id=ls_id)
        self.db.location.invalidate(ls_id)


def _carve_engine_window(led, rs) -> None:
    """Close a statement's "engine host" window, carved with the phases
    its OWN result carries; none where it failed or never reached the
    engine (a result of another worker's statement cannot get here)."""
    led.window_end_carved(
        (rs.phases if rs is not None else None) or {}, "engine host")


class DbSession:
    """One client session: statement dispatch + transaction state."""

    def __init__(self, db: Database, user: str = "root"):
        self.db = db
        self.user = user
        self._tx: _OpenTx | None = None
        self.session_id = next(db._session_ids)
        self._last_stmt_type = ""
        # the engine result of the statement's inner SELECT (a DML
        # statement's qualification scan): the DML result carries its
        # record. Per connection, so never another worker's.
        self._scan_rs = None
        self._retry_ctrl = None
        self._stmt_adds: list = []
        # (fkey, params, kinds) from the statement fast path — also the
        # statement-summary digest source. Reset per statement in
        # _sql_inner: prefix-dispatched statements (SET/XA/CALL/...)
        # return before _dispatch clears it, and a stale value would
        # mis-digest them under the previous SELECT
        self._fast_reg = None
        # lazily-created statement-summary accumulator (workload.py)
        self._ws_acc = None
        # per-statement host-tax gap ledger (share/gap_ledger.py); also
        # published thread-locally so batcher/governor waits self-report
        self._gap = None
        self._stmt_id = 0  # db-wide statement sequence number
        self._last_digest = ""
        # device-OOM degradation ladder state (reset per statement in
        # _sql_inner): None | "chunk" | "host", plus the fired rungs
        self._degrade_mode = None
        self._ladder = []
        # text -> digest memo for the governor's admission estimate (a
        # serving session repeats few texts; re-tokenizing each repeat
        # just to look up its measured peak would tax the fast path)
        self._digest_memo: dict[str, str] = {}
        # session variables (SET <name> = <value>): full-link trace
        # collection flag, PX degree-of-parallelism routing, and the
        # statement/transaction deadlines in MICROSECONDS of virtual time
        # (the reference's ob_query_timeout / ob_trx_timeout units).
        # Defaults are wider than the reference's 10s/100s because test
        # drives legitimately burn tens of virtual seconds (commit waits
        # and elections cap at 30s each)
        self._vars: dict[str, int] = {
            "ob_enable_show_trace": 0,
            "ob_query_timeout": 100_000_000,
            "ob_trx_timeout": 500_000_000,
            # PX routing and cross-session micro-batching
            # (server/batcher.py), seeded from the tenant config so ALTER
            # SYSTEM moves the default for new sessions while SET
            # overrides per session
            "ob_px_dop": int(db.config["ob_px_dop"]),
            "ob_batch_max_size": int(db.config["ob_batch_max_size"]),
            "ob_batch_max_wait_us": int(db.config["ob_batch_max_wait_us"]),
            # read-consistency routing (0 strong / 1 bounded_staleness /
            # 2 weak): non-strong SELECTs serve from follower replicas at
            # a GTS-checked snapshot within ob_max_read_stale_us
            "ob_read_consistency": self._CONSISTENCY_WORDS.get(
                str(db.config["ob_read_consistency"]), 0),
            "ob_max_read_stale_us": int(db.config["ob_max_read_stale_us"]),
            # device-resident result cache: per-session opt-out (a bench
            # A/B or a test that must observe real dispatches turns it
            # off without flipping the tenant-wide config)
            "ob_enable_result_cache": int(
                bool(db.config["ob_enable_result_cache"])),
        }
        # (snapshot, stale_us) of the last follower-served SELECT — the
        # staleness-contract tests and chaos bench read it to re-run the
        # same statement on the leader AS OF the identical snapshot
        self.last_follower_read: tuple[int, int] | None = None
        # trace_id of the last traced NON-meta statement — what SHOW TRACE
        # renders (meta statements: SHOW/SET themselves, so the flag and
        # the inspection don't overwrite the statement under diagnosis)
        self._last_trace_id = 0

    def close(self) -> None:
        """Session drop: roll back an open transaction and flush the
        statement-summary accumulator NOW instead of waiting for GC —
        the wire front ends call this on client disconnect so workload-
        repository digest counts reconcile promptly."""
        if self._tx is not None:
            try:
                self.sql("rollback")
            except Exception:
                self._tx = None
        acc = self._ws_acc
        if acc is not None:
            self._ws_acc = None
            try:
                acc.flush()
            except Exception:
                pass

    # ------------------------------------------------------------ public
    def sql(self, text: str) -> ResultSet:
        """Execute one statement, instrumented: trace span + ASH activity
        around execution, one sql_audit record at completion."""
        db = self.db
        t0 = _time.perf_counter()
        cpu0 = _time.thread_time()
        err, rs = "", None
        self._last_stmt_type = ""  # "": did not parse
        self._scan_rs = None  # set by any inner _select
        # the statement's id: the interrupt registration's, the `stmt`
        # tag of the `sql` span, and the `stmt` stat of every ob:<phase>
        # annotation in a profiler trace
        stmt_id = self._stmt_id = next(db._stmt_seq)
        # host-tax gap ledger: one per statement, spanning the SAME t0 as
        # the audit elapsed_s. Published thread-locally so the batcher and
        # governor (which run their waits on this thread) self-report
        # hints without any API plumbing.
        led = None
        if db.host_tax.enabled:
            # one ledger object per session, re-armed per statement
            # (begin() fully resets) — no per-statement allocation
            led = self._gap
            if led is None:
                led = _GL.GapLedger()
            led.begin(t0, stmt_id)
            _GL.set_current(led)
        self._gap = led
        # statement deadline: min(ob_query_timeout from now, the open tx's
        # ob_trx_timeout deadline) on the bus virtual clock — one Deadline
        # object bounds the worker wait, PX admission, DAS routing retries,
        # commit waits and every engine checkpoint below
        clock = db._bus_clock
        deadline = _R.Deadline(
            clock=clock,
            at=clock() + self._vars["ob_query_timeout"] / 1e6,
            label="ob_query_timeout",
        )
        if self._tx is not None and self._tx.deadline is not None:
            deadline = _R.Deadline.earliest(deadline, self._tx.deadline)
        # tenant worker quota (ObThWorker queue analog): bound concurrent
        # statements; waiting beyond the queue timeout (or the statement
        # deadline, when that is nearer) fails the statement
        sem = db._worker_sem
        if sem is not None:
            wait_s = db.unit.queue_timeout_s
            bounded = deadline is not None and deadline.tighter_than(wait_s)
            if bounded:
                wait_s = max(deadline.remaining(), 0.0)
            if led is not None:
                # deadline/quota bookkeeping since t0
                led.cut("setup", "admission queue")
            tq = _time.perf_counter()
            ok = sem.acquire(timeout=wait_s)
            waited = _time.perf_counter() - tq
            if led is not None:
                led.cut("admission queue", "setup")
            db.metrics.wait("tenant worker queue", waited)
            tl = db.timeline
            if tl.enabled:
                # per-tenant QoS ledger: admission wait (and, on a
                # timeout, the rejection) against the TenantUnit quota
                tl.record_admission(db.tenant_name, waited, ok)
            if not ok:
                db.metrics.add("worker queue timeouts")
                if bounded:
                    db.metrics.add("statement timeouts")
                    raise deadline._error()
                raise WorkerQueueTimeout(
                    f"tenant {db.tenant_name}: worker queue timeout "
                    f"({db.unit.max_workers} workers busy)"
                )
        # per-statement interrupt registration (KILL QUERY target)
        iid = ("stmt", db.tenant_name, self.session_id, stmt_id)
        checker = db.interrupts[0].register(iid)
        db._active_stmts[self.session_id] = iid
        prev = _I.set_current(checker)
        # inlined _R.deadline_scope: this frame already owns a finally,
        # and the generator contextmanager is measurable per-statement
        prev_dl = _R.current_deadline()
        _R.set_current_deadline(deadline)
        # programs traced on this thread count their dict_lookup lowerings
        # (`dict lookup runs`, ...) into this tenant's registry
        prev_lm = _EC.set_lookup_metrics(db.metrics)
        if led is not None:
            # interrupt + deadline registration (and admission metrics/
            # timeline above): small but real, and the residual gate is
            # strict — name it instead of leaking it
            led.cut("setup", "setup")
        try:
            return self._sql_inner(text, t0, cpu0)
        finally:
            if led is not None:
                _GL.set_current(None)
            _R.set_current_deadline(prev_dl)
            _EC.set_lookup_metrics(prev_lm)
            _I.set_current(prev)
            db._active_stmts.pop(self.session_id, None)
            db.interrupts[0].unregister(iid)
            if sem is not None:
                sem.release()

    def _sql_inner(self, text: str, t0, cpu0) -> ResultSet:
        db = self.db
        err, rs = "", None
        # retry bookkeeping spans attempts but the statement keeps ONE
        # span tree, ASH activity and audit record — retries are an
        # internal redrive, not new statements. The controller is built
        # lazily by _run_with_retries on the FIRST failure: the serving
        # hot path never pays for bookkeeping it doesn't use.
        self._retry_ctrl = None
        # per-statement counter batch: the fast path appends its plan
        # cache hit bumps here so the whole statement flushes through
        # ONE metrics.bulk() below
        self._stmt_adds = []
        self._fast_reg = None
        # degradation-ladder state (device OOM): None -> "chunk" -> "host";
        # _ladder records the rungs fired, in order, for tests/diagnosis
        self._degrade_mode = None
        self._ladder = []
        with db.tracer.span("sql", session=self.session_id,
                            stmt=self._stmt_id) as sp:
            with db.ash.activity(self.session_id, "EXECUTING", text,
                                 sp.trace_id):
                led = self._gap
                if led is not None:
                    # tracer span + ASH activity registration glue; a
                    # SELECT's next cut is the fast tier's, any other
                    # statement's the parser's
                    then = None
                    if led.stmt:
                        then = ("fast lookup"
                                if text[:16].lstrip()[:6].lower() == "select"
                                else "parse bind")
                    led.cut("setup", then)
                pp = db.plan_profiler
                if pp is not None and pp.enabled:
                    # hand the statement digest to the engine's operator
                    # profiler (memoized text->digest: one dict lookup on
                    # warm statements) so sampling, EXPLAIN ANALYZE
                    # forcing and slow-query marks all key identically
                    pp.set_pending(self._digest_of(text))
                try:
                    rs = self._run_with_retries(text)
                except Exception as e:
                    err = f"{type(e).__name__}: {e}"
                    if isinstance(e, _R.StatementTimeout):
                        db.metrics.add("statement timeouts")
                    raise
                finally:
                    if pp is not None:
                        pp.clear_pending()
                    elapsed_s = _time.perf_counter() - t0
                    stype = self._last_stmt_type or "Unknown"
                    m = db.metrics
                    # the execution's record rides the result: a
                    # statement that failed or never reached the engine
                    # (pure DDL, SHOW) has none, and no other's
                    prof = rs.profile if rs is not None else None
                    bi = (getattr(rs, "batch_info", None)
                          if rs is not None else None)
                    led = self._gap
                    fr = self._fast_reg
                    digest = ""
                    ws = db.stmt_summary
                    if ws.enabled or led is not None:
                        digest = (fr[0] if fr is not None
                                  else P.digest_text(text))
                        # fronts annotate post-close wall (wire write)
                        # against this digest via host_tax.fold_extra
                        self._last_digest = digest
                    if ws.enabled:
                        # exactly-once digest fold per statement — here in
                        # the completion finally, never in the except arm
                        # or the flight recorder, so a statement that both
                        # fails AND trips the slow-query watermark counts
                        # its error once. Fast-path statements reuse the
                        # already-tokenized key in _fast_reg for free, and
                        # the fold buffers into this session's own
                        # accumulator (readers flush before reading) so
                        # a completing batch cohort takes no shared lock.
                        acc = self._ws_acc
                        if acc is None:
                            acc = self._ws_acc = ws.session_acc()
                        acc.fold(
                            digest,
                            stype, elapsed_s, err,
                            self._retry_ctrl.retry_cnt
                            if self._retry_ctrl else 0,
                            rs, bi is not None, prof,
                        )
                    if led is not None:
                        # the return path + digest + summary fold are host
                        # wall too: cut everything since the engine window
                        # closed, then freeze e2e/residual/chip-idle
                        if led.stmt:
                            led.tag(digest=str(digest))
                        led.cut("completion fold")
                        led.close()
                        led.cpu_s = _time.thread_time() - cpu0
                    retry_cnt = (self._retry_ctrl.retry_cnt
                                 if self._retry_ctrl else 0)
                    retry_info = (self._retry_ctrl.retry_info
                                  if self._retry_ctrl else "")
                    sid = self.session_id
                    trace_id = sp.trace_id
                    stype2 = self._last_stmt_type
                    depth = len(db._active_stmts)
                    stmt_adds = self._stmt_adds

                    def _complete():
                        # statement accounting, exactly once, inline on
                        # the serving thread
                        if led is not None:
                            db.host_tax.fold(digest, led)
                        # hot-path diet: when metrics/audit are disabled,
                        # skip even the counter lookups and kwargs
                        # construction — the serving path pays zero for
                        # observability it isn't using
                        if m.enabled:
                            adds = stmt_adds
                            adds.append(("sql statements", 1))
                            if stype in ("Select", "SetSelect"):
                                adds.append(("sql select count", 1))
                            elif stype in ("Insert", "Update", "Delete"):
                                adds.append(("sql dml count", 1))
                            if err:
                                adds.append(("sql fail count", 1))
                            observes = [("sql response time", elapsed_s)]
                            waits = ()
                            if led is not None:
                                # per-phase wait events: sysstat/
                                # system_event rows AND prometheus
                                # summaries for free
                                adds.append(("host tax statements", 1))
                                # the spans' counters (catalog refresh,
                                # h2d), folded here and nowhere else
                                adds.extend(led.counts.items())
                                observes.append(("host chip idle pct",
                                                 led.chip_idle_pct))
                                waits = [("host tax: " + k, v)
                                         for k, v in led.phases.items()]
                                if led.unattributed_s > 0.0:
                                    waits.append(
                                        ("host tax: unattributed",
                                         led.unattributed_s))
                            m.bulk(adds=adds, observes=tuple(observes),
                                   waits=tuple(waits))
                        tl = db.timeline
                        if tl.enabled:
                            # timeline completion feed (exactly once per
                            # statement, beside the summary fold): host
                            # wall seconds + tenant admitted count +
                            # in-flight depth sample for the queue
                            # histograms
                            tl.record_stmt(db.tenant_name, elapsed_s,
                                           bool(err), depth)
                        if db.audit.enabled:
                            p = prof
                            db.audit.record(
                                session_id=sid,
                                trace_id=trace_id,
                                sql=text,
                                stmt_type=stype2,
                                elapsed_s=elapsed_s,
                                rows=rs.nrows if rs is not None else 0,
                                affected=(rs.affected
                                          if rs is not None else 0),
                                plan_cache_hit=(rs.plan_cache_hit
                                                if rs is not None
                                                else False),
                                error=err,
                                compile_s=p.compile_s if p else 0.0,
                                device_bytes=p.device_bytes if p else 0,
                                transfer_bytes=(p.transfer_bytes
                                                if p else 0),
                                peak_bytes=p.peak_bytes if p else 0,
                                retry_cnt=retry_cnt,
                                retry_info=retry_info,
                                fastparse_us=(int(p.fastparse_s * 1e6)
                                              if p else 0),
                                bind_us=int(p.bind_s * 1e6) if p else 0,
                                dispatch_us=(int(p.dispatch_s * 1e6)
                                             if p else 0),
                                fetch_us=int(p.fetch_s * 1e6) if p else 0,
                                is_fast_path=(bool(p.fast_path_hit)
                                              if p else False),
                                is_batched=bi is not None,
                                batch_id=bi[0] if bi is not None else 0,
                                batch_wait_us=(bi[2]
                                               if bi is not None else 0),
                                chip_idle_us=int(
                                    max(0.0, led.e2e_s - led.device_s)
                                    * 1e6) if led is not None else 0,
                                unattributed_us=int(
                                    led.unattributed_s * 1e6)
                                if led is not None else 0,
                            )

                    _complete()
                    # the scan's cursor pins its device frame: let it go
                    # with the statement
                    self._scan_rs = None
                    if stype not in ("Show", "SetVar", ""):
                        if self._vars.get("ob_enable_show_trace"):
                            self._last_trace_id = sp.trace_id
                        self._maybe_flight_record(
                            text, sp, elapsed_s, rs, err, prof)
                    wr = db.workload
                    if wr.interval_s > 0:
                        wr.maybe_auto(db)
        return rs

    def _stmt_retryable(self) -> bool:
        """Whole-statement redrive is safe only when nothing of the failed
        attempt outlives it: reads always (the snapshot re-resolves);
        DML only in autocommit, where _dml aborted the auto-tx with the
        failure — a DML inside an explicit transaction keeps its partial
        stages and must surface the error to the client instead."""
        st = self._last_stmt_type
        if st in ("Select", "SetSelect"):
            return True
        if st in ("Insert", "Update", "Delete"):
            return self._tx is None
        return False

    def _run_with_retries(self, text: str):
        """ObQueryRetryCtrl's loop: classify each failure, re-resolve
        locations/routing, back off on the bus virtual clock (driving the
        cluster so elections settle during the wait), and redrive until
        success, a non-retryable error, or the statement deadline — which
        surfaces as a timeout chaining the last transient, never as a raw
        NotMaster/InjectedError.

        The RetryController is built on the first failure only (stored on
        ``self._retry_ctrl`` so the audit record can read retry_cnt /
        retry_info after the loop returns)."""
        db = self.db
        schema_v = db.schema_service.version
        ctrl = None
        reserve_bytes = self._reserve_estimate(text)
        while True:
            res = None
            try:
                if reserve_bytes > 0:
                    # admission-time device-memory reservation, held for
                    # the whole attempt (re-taken per attempt so post-OOM
                    # attempts charge the SHRUNK pool)
                    res = self._reserve_device_memory(reserve_bytes)
                return self._dispatch(text)
            except Exception as e:
                if ctrl is None:
                    ctrl = _R.RetryController(deadline=_R.current_deadline())
                    self._retry_ctrl = ctrl
                policy = ctrl.decide(e, stmt_retryable=self._stmt_retryable())
                if policy is None:
                    # a DDL racing this statement invalidated any cached
                    # plan it compiled against: reclassify once per version
                    # move as OB_SCHEMA_EAGAIN and redrive fresh
                    cur_v = db.schema_service.version
                    if (cur_v != schema_v and self._stmt_retryable()
                            and not isinstance(e, _R.StatementTimeout)):
                        schema_v = cur_v
                        policy = ctrl.decide(
                            _R.SchemaVersionMismatch(
                                f"schema version moved under the statement "
                                f"({type(e).__name__}: {e})"),
                            stmt_retryable=True,
                        )
                    if policy is None:
                        raise
                d = ctrl.deadline
                if d is not None and d.expired:
                    raise ctrl.timeout_error(e) from e
                wait = ctrl.record(policy, e)
                m = db.metrics
                m.add("statement retries")
                m.add(f"statement retries: {policy.reason}")
                if policy.reason == "device oom":
                    # the three-rung degradation ladder: rung N is chosen
                    # by how many device OOMs THIS statement has already
                    # absorbed. Each rung strictly weakens the memory
                    # demand, so the sequence terminates: host execution
                    # (rung 3) cannot device-OOM at all.
                    rung = ctrl._per_policy.get("device oom", 0)
                    m.add("device OOM retries")
                    if rung <= 1:
                        # rung 1: evict cold residency + shrink the
                        # reservation pool, retry the same plan
                        db._evict_cold_residency()
                        db.governor.note_oom()
                        self._ladder.append("evict")
                    elif rung == 2:
                        # rung 2: re-plan through the chunked executor,
                        # chunk size derived from the remaining budget
                        self._degrade_mode = "chunk"
                        m.add("stmt degraded chunked")
                        self._ladder.append("chunked")
                    else:
                        # rung 3: host fallback, bit-identical
                        self._degrade_mode = "host"
                        m.add("stmt degraded host")
                        self._ladder.append("host")
                if policy.flush_plan_cache:
                    db.plan_cache.flush()
                if policy.refresh_location:
                    ls_id = getattr(e, "ls_id", None)
                    if ls_id is not None:
                        # NotMaster names the LS whose cached leader went
                        # stale: invalidate exactly that entry — dropping
                        # the whole cache forces every OTHER ls through a
                        # resolver round trip for one node's election
                        m.add("location targeted invalidations")
                        db.location.invalidate(ls_id)
                    else:
                        db.location.clear()
                if wait > 0:
                    led = _GL.current()
                    if led is not None:
                        led.leaf(None)  # parked in the backoff
                    tb = _time.perf_counter()
                    with m.waiting("statement retry backoff"):
                        db.cluster.settle(wait)
                    if led is not None:
                        led.add("retry backoff",
                                _time.perf_counter() - tb)
                        led.leaf_end()
                if d is not None and d.expired:
                    raise ctrl.timeout_error(e) from e
            finally:
                # the ledger must balance: release THIS attempt's grant on
                # every exit — success, retry, or surfaced error — so the
                # next attempt/rung charges an honest pool.
                if res is not None:
                    res.release()

    def _maybe_flight_record(self, text, sp, elapsed_s, rs, err,
                             prof) -> None:
        """Slow-query flight recorder: when a statement crosses the
        trace_log_slow_query_watermark, freeze the evidence (span tree,
        plan text, audit-shaped record, metrics delta, active config)
        into the bounded bundle ring — tools/obdiag_dump.py exports it."""
        db = self.db
        if not db.flight.should_record(elapsed_s):
            return
        # arm the stack sampler: THIS statement is already over, but slow
        # statements recur — the next occurrence gets sampled stacks into
        # its bundle. Config-armed mode (enable_stack_sampler) keeps it
        # running regardless.
        auto = db.config["stack_sampler_auto_arm"]
        if auto > 0:
            db.stack_sampler.arm(auto)
        spans = [
            {
                "depth": depth,
                "name": s.name,
                "node": s.tags.get("node", ""),
                "elapsed_us": int(s.elapsed * 1e6),
                "tags": {k: repr(v) for k, v in s.tags.items()},
            }
            for depth, s in db.tracer.trace_tree(sp.trace_id)
        ]
        digest = (self._fast_reg[0] if self._fast_reg is not None
                  else P.digest_text(text))
        pp = db.plan_profiler
        op_profile: list = []
        if pp is not None and pp.enabled:
            # arm the operator profiler: the NEXT occurrence of this slow
            # digest runs profiled, so a recurring slow statement's later
            # bundles carry per-operator evidence — and whatever profile
            # the store already holds rides THIS bundle now. UNLESS this
            # very run already carried a profile: a profiled run is
            # slower (fences), so re-arming on its own slowness would
            # lock a watermark-straddling digest into profiling forever
            opp = rs.op_profile if rs is not None else None
            if opp is None or opp.get("digest") != digest:
                pp.mark_slow(digest)
            op_profile = pp.store.digest_profile(digest)
        bundle = {
            "trace_id": sp.trace_id,
            "session_id": self.session_id,
            "sql": text,
            # same digest the statement summary folded under — a bundle
            # joins its aggregate without re-normalizing
            "digest": digest,
            # per-operator calibration records for this digest (est vs
            # actual rows, device_us) from engine/plan_profile.py
            "op_profile": op_profile,
            "stmt_type": self._last_stmt_type,
            "elapsed_s": elapsed_s,
            "rows": rs.nrows if rs is not None else 0,
            "error": err,
            "profile": prof.as_dict() if prof is not None else {},
            "plan": repr(rs.plan if rs is not None else None),
            "spans": spans,
            "config": {
                n: v for n, v, _p in db.config.snapshot()
            },
            # host-tax ledger: where THIS statement's wall went, phase by
            # phase, residual named — plus whatever collapsed stacks the
            # sampler holds (armed by a previous slow statement or config)
            "host_tax": (self._gap.to_dict()
                         if self._gap is not None and self._gap.closed
                         else {}),
            "stacks": db.stack_sampler.snapshot(),
        }
        db.flight.record(bundle, counters=db.metrics.counters_snapshot())
        db.metrics.add("flight recorder bundles")

    @staticmethod
    def _referenced_tables(node) -> set:
        """Every base-table name the statement reads: TableRef names
        anywhere in the AST (FROM lists, joins, subqueries inside
        predicates, INSERT..SELECT sources) MINUS names declared as CTEs
        — a CTE reference is statement-local, not a catalog object."""
        import dataclasses

        from ..engine.recursive import _table_refs

        refs = _table_refs(node)

        def cte_names(n, out):
            for name, _b in getattr(n, "ctes", ()) or ():
                out.add(name)
            if dataclasses.is_dataclass(n) and not isinstance(n, type):
                for f in dataclasses.fields(n):
                    cte_names(getattr(n, f.name), out)
            elif isinstance(n, (tuple, list)):
                for x in n:
                    cte_names(x, out)
            return out

        return refs - cte_names(node, set())

    def _check_privs(self, stmt) -> None:
        """Resolve-time privilege enforcement (the reference checks in
        sql/privilege_check before optimization; same point here: after
        parse, before any plan executes)."""
        from ..share.privilege import AccessDenied

        if self.user == "root":
            return  # superuser: skip the AST walk on the hot path
        pm = self.db.privileges
        try:
            if isinstance(stmt, (A.Select, A.SetSelect)):
                pm.check(self.user, "select", self._referenced_tables(stmt))
            elif isinstance(stmt, (A.Insert, A.Update, A.Delete)):
                priv = type(stmt).__name__.lower()
                target = stmt.table
                pm.check(self.user, priv, {target})
                others = self._referenced_tables(stmt) - {target}
                if others:
                    pm.check(self.user, "select", others)
            elif isinstance(stmt, (A.CreateTable, A.CreateExternalTable)):
                pm.check(self.user, "create", {stmt.name})
                if isinstance(stmt, A.CreateExternalTable):
                    # secure_file_priv gate: a bare 'create' grant must
                    # not turn SELECT into arbitrary-host-file read (a
                    # CSV loader would happily ingest /etc/passwd).
                    self._check_external_location(stmt.location)
            elif isinstance(stmt, A.LockTable):
                # shared holds need read rights, exclusive holds write
                # rights — otherwise a zero-grant user can block writers.
                pm.check(self.user,
                         "update" if stmt.exclusive else "select",
                         {stmt.name})
            elif isinstance(stmt, (A.CreateMaterializedView, A.CreateView)):
                pm.check(self.user, "create", {stmt.name})
                pm.check(self.user, "select", self._referenced_tables(
                    P.parse(stmt.query_sql)))
            elif isinstance(stmt, A.DropView):
                pm.check(self.user, "drop", {stmt.name})
            elif isinstance(stmt, A.CreateTrigger):
                # trigger bodies run with the firing statement's rights;
                # creating one therefore needs write-shaping power over
                # the subject table
                pm.check(self.user, "create", {stmt.table})
            elif isinstance(stmt, A.DropTrigger):
                pm.check(self.user, "drop", {stmt.name})
            elif isinstance(stmt, A.RefreshMaterializedView):
                pm.check(self.user, "create", {stmt.name})
                spec = self.db._mview_specs.get(stmt.name)
                if spec is not None:
                    pm.check(self.user, "select",
                             self._referenced_tables(P.parse(spec)))
            elif isinstance(stmt, A.DropMaterializedView):
                pm.check(self.user, "drop", {stmt.name})
            elif isinstance(stmt, A.DropTable):
                pm.check(self.user, "drop", {stmt.name})
            elif isinstance(stmt, (A.CreateIndex, A.DropIndex,
                                   A.CreateVectorIndex, A.DropVectorIndex)):
                pm.check(self.user, "index", {stmt.table})
            elif isinstance(stmt, (A.AlterSystemSet, A.RunLayoutAdvisor,
                                   A.KillQuery)):
                if self.user != "root":
                    raise AccessDenied(
                        f"'{self.user}' lacks SUPER", 1227)
        except AccessDenied as e:
            raise SqlError(str(e), code=e.code) from None

    def _check_external_location(self, location: str) -> None:
        """Non-root external-table locations must resolve inside the
        secure_file_priv directory (empty = root-only), checked on the
        os.path.realpath so ../ and symlink escapes don't bypass it."""
        import os

        allowed = str(self.db.config.get("secure_file_priv") or "")
        if not allowed:
            raise SqlError(
                "external tables are restricted to root "
                "(secure_file_priv is unset)", code=1227)
        real = os.path.realpath(location)
        base = os.path.realpath(allowed)
        if os.path.commonpath([real, base]) != base:
            raise SqlError(
                f"location {location!r} is outside secure_file_priv",
                code=1227)

    def _dcl(self, stmt) -> ResultSet:
        from ..share.privilege import AccessDenied

        if self.user != "root":
            raise SqlError(
                f"'{self.user}' may not administer users/grants", code=1227
            )
        pm = self.db.privileges
        try:
            if isinstance(stmt, A.CreateUser):
                pm.create_user(stmt.name, stmt.password)
            elif isinstance(stmt, A.DropUser):
                pm.drop_user(stmt.name)
            elif isinstance(stmt, A.Grant):
                pm.grant(stmt.user, stmt.obj, stmt.privs)
            elif isinstance(stmt, A.Revoke):
                pm.revoke(stmt.user, stmt.obj, stmt.privs)
        except AccessDenied as e:
            raise SqlError(str(e), code=e.code) from None
        self.db._save_node_meta()  # grants survive restart like schema
        return ResultSet((), {})

    def _dispatch(self, text: str) -> ResultSet:
        low = text.lstrip().lower()
        if low.startswith("create procedure"):
            self._last_stmt_type = "CreateProcedure"
            return self._create_procedure(text)
        if low.startswith("drop procedure"):
            self._last_stmt_type = "DropProcedure"
            return self._drop_procedure(text)
        if low.startswith("call ") or low.startswith("call("):
            self._last_stmt_type = "Call"
            return self._call_procedure(text)
        if low.startswith("xa "):
            self._last_stmt_type = "Xa"
            return self._xa(text)
        if low.startswith("set ") and not low.startswith("set transaction"):
            self._last_stmt_type = "SetVar"
            return self._set_session_var(text)
        if low.startswith("create sequence") or low.startswith("drop sequence"):
            self._last_stmt_type = "Sequence"
            return self._sequence_ddl(text)
        if low.startswith("snapshot workload"):
            # workload repository capture (server/workload.py): freeze the
            # current summary/access/census/sysstat state into the bounded
            # snapshot ring; tools/awr_report.py diffs two of them
            self._last_stmt_type = "SnapshotWorkload"
            snap = self.db.workload.take(self.db)
            return ResultSet(
                ("snap_id", "ts"),
                {"snap_id": [snap["snap_id"]], "ts": [float(snap["ts"])]},
            )
        if low.split(None, 1)[:1] == ["explain"]:
            self._last_stmt_type = "Explain"
            return self._explain(text.lstrip()[len("explain"):].lstrip())
        # statement fast path: a warm SELECT whose kind-marked text key is
        # registered skips parse/resolve/rewrite/plan entirely — one
        # tokenize pass, re-bind the literals, dispatch the cached
        # executable. Any rejection falls through to the full path (and
        # leaves self._fast_reg set so the full path registers the text).
        self._fast_reg = None
        if low.startswith("select"):
            rs = self._fast_select(text)
            if rs is not None:
                return rs
        tp = _time.perf_counter()
        stmt = P.parse_statement(text)
        self.db.metrics.observe("sql parse", _time.perf_counter() - tp)
        led = self._gap
        if led is not None:
            # cut, not a tp-anchored add: covers the fast-tier fallthrough
            # glue since the miss cut (or dispatch entry) too
            led.cut("parse bind")
        self._last_stmt_type = type(stmt).__name__
        # privileges first: a DENIED statement must not burn sequence
        # values or write node meta
        self._check_privs(stmt)
        stmt = self._bind_sequences(stmt)
        if self._fast_reg is not None:
            # the plain plan-cache key is the fast key with kind markers
            # collapsed (the tokenizer never emits a bare '?')
            norm_key = self._fast_reg[0].replace("?n", "?").replace("?s", "?")
        else:
            norm_key = P.normalize_for_cache(text)[0]
        if led is None:
            return self._dispatch_stmt(stmt, norm_key,
                                       fast_reg=self._fast_reg)
        # full-path engine window: whatever the engine measured
        # (plan/compile/bind/dispatch/fetch) carves the window wall; the
        # rest is the named measured remainder "engine host"
        led.window_start("engine host")
        rs = None
        try:
            rs = self._dispatch_stmt(stmt, norm_key,
                                     fast_reg=self._fast_reg)
            return rs
        finally:
            _carve_engine_window(led, rs)

    def _fast_select(self, text: str) -> "ResultSet | None":
        """Server half of the statement fast path. Eligibility mirrors the
        plain single-chip _select route: autocommit (no open tx), no PX
        DOP, and — via registration-side guards — no virtual tables, views
        or index routing. Privileges re-check against the registered scan
        tables on EVERY hit (a REVOKE between repeats must bite), and the
        per-table catalog refresh runs as usual (it no-ops per table while
        data_version is unchanged, which is what makes the path cheap).
        Returns None to fall through to the full parse path."""
        db = self.db
        if self._tx is not None or self._vars.get("ob_px_dop", 0) > 0:
            return None
        if self._degrade_mode is not None:
            # a device OOM put this statement on the degradation ladder:
            # the cached fast plan is exactly what just OOMed — force the
            # full parse path so _select can re-plan chunked/host
            return None
        if self._vars.get("ob_read_consistency", 0) != 0:
            # the fast tier replays against the shared committed catalog
            # (leader state); non-strong sessions route through the
            # follower view path in _select instead
            return None
        t0 = _time.perf_counter()
        led = self._gap

        def miss():
            # the fast tier's wall is host tax even when it MISSES — the
            # tokenize/peek attempt preceded the full parse path
            if led is not None:
                led.cut("fast lookup", "parse bind")
            return None

        try:
            fkey, params, kinds = P.fast_normalize(text)
        except Exception:
            return miss()  # tokenizer rejects: the full parser owns the error
        if "nextval" in fkey or "currval" in fkey:
            # sequence draws are side-effecting: _bind_sequences rewrites
            # them into fresh literals pre-resolution, which a text-keyed
            # replay would freeze. Never serve OR register these.
            return miss()
        self._fast_reg = (fkey, params, kinds)
        fe = db.plan_cache.fast_peek(fkey)
        if fe is None:
            db.plan_cache.note_fast_miss()
            return miss()
        if self.user != "root":
            from ..share.privilege import AccessDenied

            try:
                db.privileges.check(self.user, "select", set(fe.tables))
            except AccessDenied as e:
                raise SqlError(str(e), code=e.code) from None
        db.refresh_catalog(fe.tables, tx=None)
        hit = db.engine.fast_lookup(fkey, params, fe=fe,
                                    defer_adds=self._stmt_adds)
        if hit is None:
            return miss()
        # set BEFORE execute: the audit record and the retry controller's
        # retryability decision both read it if dispatch raises
        self._last_stmt_type = fe.stmt_type
        fastparse_s = _time.perf_counter() - t0
        if led is not None:
            # tokenize + peek + priv + catalog refresh + lookup: the fast
            # tier's whole host cost, as one contiguous cut from the
            # dispatch-entry cursor
            led.cut("fast lookup", "result cache" if self._vars.get(
                "ob_enable_result_cache", 1) else None)
        # device-resident result cache: probed AFTER the privilege check
        # (a REVOKE between repeats must bite a cached hit) and the
        # catalog refresh (the watermark key must see fresh committed
        # data versions). A hit serves the statement with ZERO device
        # dispatches; a miss threads the key down so the solo execute
        # admits the fresh narrowed frame.
        rc_key = (db.engine.result_cache_key(hit)
                  if self._vars.get("ob_enable_result_cache", 1) else None)
        if rc_key is not None and db.plan_profiler is not None \
                and db.plan_profiler.enabled \
                and db.plan_profiler.wants_force(fkey):
            # a pending forced operator profile (EXPLAIN ANALYZE, slow
            # mark) needs a real execution — neither serve nor admit
            rc_key = None
        if rc_key is not None:
            rs = db.engine.result_cache_probe(hit, rc_key, fastparse_s)
            if rs is not None:
                if led is not None:
                    led.cut("result cache")
                return rs
        # cross-session micro-batching: concurrent hits on the SAME entry
        # fold into one batched device dispatch. Admission honors the
        # tenant unit — a batch wider than max_workers could never form
        # (each lane holds a worker permit while it waits). None from the
        # batcher = graceful degradation to the solo fast path below.
        bmax = self._vars.get("ob_batch_max_size", 1)
        if db.unit.max_workers is not None:
            bmax = min(bmax, db.unit.max_workers)
        if bmax > 1 and db.batcher.enabled:
            # weighted tenant admission: hold one running permit for the
            # whole gated execution — dispatch order alone cannot shield
            # a quiet tenant from a flooding one when the contention is
            # CPU time across session threads
            db.batcher.admit()
            try:
                # host-tax window over the gated execution: the batcher
                # self-reports hints (window wait; dispatch on the leader
                # only — the cohort's device busy is counted ONCE) from
                # this thread via gap_ledger.current()
                if led is not None:
                    led.window_start("engine host")
                rs = db.batcher.execute(
                    hit, bmax, self._vars.get("ob_batch_max_wait_us", 0))
                if rs is not None:
                    if led is not None:
                        # batched lane: hints only; batcher glue stays in
                        # the unattributed residual (no engine ran here)
                        led.window_end()
                    if db.config["enable_query_profile"]:
                        rs.profile = QueryProfile(
                            compile_hit=True,
                            d2h_bytes=rs.batch_info[4],
                            fastparse_s=fastparse_s,
                            dispatch_s=rs.batch_info[3],
                            fast_path_hit=True,
                        )
                    return rs
                # None = degrade to the solo fast path (idle gate,
                # bypass, follower timeout, dispatch error, shutdown).
                # The batcher left ONE dispatch-gate busy token held for
                # this solo run; solo_done hands it to the next queued
                # cohort — the release is what keeps the
                # continuous-batching queue draining.
                rs = None
                try:
                    rs = db.engine.fast_execute(
                        hit, fastparse_s=fastparse_s, rc_key=rc_key)
                    return rs
                finally:
                    db.batcher.solo_done()
                    if led is not None:
                        _carve_engine_window(led, rs)
            finally:
                db.batcher.admit_done()
        if led is not None:
            led.window_start("engine host")
        rs = None
        try:
            rs = db.engine.fast_execute(hit, fastparse_s=fastparse_s,
                                        rc_key=rc_key)
            return rs
        finally:
            if led is not None:
                _carve_engine_window(led, rs)

    def _sequence_ddl(self, text: str) -> ResultSet:
        from ..share.privilege import AccessDenied

        if self.user != "root":
            try:
                self.db.privileges.check(self.user, "create", {"*"})
            except AccessDenied as e:
                raise SqlError(str(e), code=e.code) from None
        toks = text.replace(";", " ").split()
        if len(toks) < 3:
            raise SqlError("sequence DDL needs a name")
        name = toks[2].lower()
        if toks[0].lower() == "drop":
            self.db.drop_sequence(name)
            return ResultSet((), {})
        start, inc = 1, 1
        low = [t.lower() for t in toks]

        def clause_value(kw, filler):
            # scan AFTER the name token so a sequence named 'start'
            # cannot shadow its own clause; malformed values surface as
            # SqlError, not IndexError
            try:
                i = low.index(kw, 3)
            except ValueError:
                return None
            j = i + 2 if i + 1 < len(low) and low[i + 1] == filler else i + 1
            if j >= len(toks):
                raise SqlError(f"{kw.upper()} needs a value")
            try:
                return int(toks[j])
            except ValueError:
                raise SqlError(
                    f"bad {kw.upper()} value {toks[j]!r}") from None

        v = clause_value("start", "with")
        if v is not None:
            start = v
        v = clause_value("increment", "by")
        if v is not None:
            inc = v
        if inc == 0:
            raise SqlError("INCREMENT BY must be nonzero")
        self.db.create_sequence(name, start, inc)
        return ResultSet((), {})

    def _bind_sequences(self, stmt):
        """Replace nextval('s')/currval('s') calls with literal values
        BEFORE resolution (side-effecting functions cannot live in a
        traced program; each textual occurrence draws once per
        statement, the reference's per-statement sequence semantics)."""
        import dataclasses

        if not self.db._sequences:
            return stmt

        def rw(node):
            if isinstance(node, A.FuncCall) and node.name in (
                "nextval", "currval"
            ):
                if len(node.args) != 1 or not isinstance(
                    node.args[0], A.StringLit
                ):
                    raise SqlError(f"{node.name}('sequence_name')")
                sname = node.args[0].value.lower()
                if node.name == "nextval":
                    v = self.db.sequence_next(sname)
                else:
                    sq = self.db._sequences.get(sname)
                    if sq is None:
                        raise SqlError(f"no sequence {sname}")
                    if "last" not in sq:
                        raise SqlError(
                            f"currval of {sname} before nextval in this "
                            "server lifetime"
                        )
                    v = sq["last"]
                return A.NumberLit(str(v))
            if dataclasses.is_dataclass(node) and not isinstance(node, type):
                ch = {}
                for f in dataclasses.fields(node):
                    cur = getattr(node, f.name)
                    new = rw(cur)
                    if new is not cur:
                        ch[f.name] = new
                return dataclasses.replace(node, **ch) if ch else node
            if isinstance(node, tuple):
                items = tuple(rw(x) for x in node)
                if any(a is not b for a, b in zip(items, node)):
                    return items
                return node
            return node

        return rw(stmt)

    def _dispatch_stmt(self, stmt, norm_key: str, fast_reg=None) -> ResultSet:
        if isinstance(stmt, (A.CreateUser, A.DropUser, A.Grant, A.Revoke)):
            return self._dcl(stmt)
        if isinstance(stmt, (A.Select, A.SetSelect)):
            return self._select(stmt, norm_key, fast_reg=fast_reg)
        if isinstance(stmt, A.CreateTable):
            self.db.create_table(stmt)
            return ResultSet((), {})
        if isinstance(stmt, A.DropTable):
            self.db.drop_table(stmt)
            return ResultSet((), {})
        if isinstance(stmt, A.CreateIndex):
            self.db.create_index(stmt)
            return ResultSet((), {})
        if isinstance(stmt, A.DropIndex):
            self.db.drop_index(stmt)
            return ResultSet((), {})
        if isinstance(stmt, A.CreateExternalTable):
            self.db.create_external_table(stmt)
            return ResultSet((), {})
        if isinstance(stmt, A.CreateView):
            self.db.create_view(stmt)
            return ResultSet((), {})
        if isinstance(stmt, A.CreateTrigger):
            self.db.create_trigger(stmt)
            return ResultSet((), {})
        if isinstance(stmt, A.DropTrigger):
            self.db.drop_trigger(stmt.name)
            return ResultSet((), {})
        if isinstance(stmt, A.DropView):
            self.db.drop_view(stmt.name)
            return ResultSet((), {})
        if isinstance(stmt, A.CreateMaterializedView):
            self.db.create_mview(stmt)
            return ResultSet((), {})
        if isinstance(stmt, A.DropMaterializedView):
            self.db.drop_mview(stmt.name)
            return ResultSet((), {})
        if isinstance(stmt, A.RefreshMaterializedView):
            self.db.refresh_mview(stmt.name)
            return ResultSet((), {})
        if isinstance(stmt, A.CreateVectorIndex):
            self.db.create_vector_index(stmt)
            return ResultSet((), {})
        if isinstance(stmt, A.DropVectorIndex):
            self.db.drop_vector_index(stmt)
            return ResultSet((), {})
        if isinstance(stmt, A.Begin):
            if self._tx is not None:
                raise SqlError("transaction already open")
            self._tx = _OpenTx(self.db, deadline=self._new_trx_deadline())
            return ResultSet((), {})
        if isinstance(stmt, A.Commit):
            self._end_tx(commit=True)
            return ResultSet((), {})
        if isinstance(stmt, A.Rollback):
            self._end_tx(commit=False)
            return ResultSet((), {})
        if isinstance(stmt, A.AlterSystemSet):
            from ..share.config import ConfigError

            try:
                self.db.config.set(stmt.name, stmt.value)
            except ConfigError as e:
                raise SqlError(str(e)) from None
            if self.db.data_dir is not None:
                self.db._save_node_meta()  # config survives restart
            return ResultSet((), {})
        if isinstance(stmt, A.RunLayoutAdvisor):
            recs = self.db.layout_advisor.run()
            return ResultSet(
                ("action", "table_name", "column_name", "detail",
                 "benefit", "cost_bytes", "status"),
                {
                    "action": [r.action for r in recs],
                    "table_name": [r.table for r in recs],
                    "column_name": [r.column for r in recs],
                    "detail": [r.detail for r in recs],
                    "benefit": [float(r.benefit) for r in recs],
                    "cost_bytes": [int(r.cost_bytes) for r in recs],
                    "status": [r.status for r in recs],
                },
            )
        if isinstance(stmt, A.Show):
            return self._show(stmt)
        if isinstance(stmt, A.LockTable):
            return self._lock_table(stmt)
        if isinstance(stmt, A.KillQuery):
            self.db.kill_query(stmt.session_id)
            return ResultSet((), {})
        if isinstance(stmt, A.Insert):
            return self._dml(lambda tx: self._insert(stmt, tx))
        if isinstance(stmt, A.Update):
            return self._dml(lambda tx: self._update(stmt, tx))
        if isinstance(stmt, A.Delete):
            return self._dml(lambda tx: self._delete(stmt, tx))
        raise SqlError(f"unsupported statement {type(stmt).__name__}")

    # ------------------------------------------------------------- explain
    def _explain(self, text: str) -> ResultSet:
        """EXPLAIN <select>: the routed plan with physical annotations
        (never compiles — all host-side planning state). Privileges
        apply exactly like the SELECT itself (a plan leaks table/column
        names and estimates); inside an open tx the plan reflects the
        tx's OWN view of the data, like the statement would.

        EXPLAIN ANALYZE <select> additionally EXECUTES the statement
        through the normal dispatch path and appends the measured phase
        breakdown (parse/plan/compile/execute) and actual row count —
        the per-plan analog of GV$SQL_PLAN_MONITOR's timing columns."""
        from ..sql.explain import explain_plan

        head = text.split(None, 1)
        analyze = bool(head) and head[0].lower() == "analyze"
        if analyze:
            text = text[len(head[0]):].lstrip()
            if not text:
                raise SqlError("EXPLAIN ANALYZE needs a statement")
        tp = _time.perf_counter()
        ast = P.parse(text)
        parse_s = _time.perf_counter() - tp
        self._check_privs(ast)
        names = self.db.expand_views(_tables_in_ast(ast))
        any_vt = self.db.refresh_virtual(names)
        self.db.refresh_catalog(names, tx=self._tx)
        in_tx = self._tx is not None and self._tx.ctx is not None
        views = self._tx.views if in_tx else None
        engine = self.db.engine
        try:
            with self.db.catalog.tx_scope(views):
                planned = engine.planner.plan(ast)
                ex = engine.executor
                plan = ex._route_projections(planned.plan)
                params = ex.seed_params(plan)
                # host-only detection passes (same as compile())
                from ..engine.executor import _number_nodes
                from ..sql.logical import Aggregate as _Agg, TopN as _TopN

                for nid, op in _number_nodes(plan).items():
                    if isinstance(op, _Agg) and ex.clustered_agg_enabled:
                        spec = ex._clustered_agg_spec(op)
                        if spec is not None:
                            params.clustered_aggs[nid] = spec
                    if isinstance(op, _TopN) and ex.clustered_agg_enabled:
                        vspec = ex._vector_topn_spec(op)
                        if vspec is not None:
                            params.vector_topns[nid] = vspec
                lines = explain_plan(ex, plan, params)
        finally:
            if any_vt:
                from .virtual_tables import PROVIDERS

                for n in names:
                    if n in PROVIDERS:
                        self.db.catalog.pop(n, None)
                        self.db._invalidate(n)
        record = {}
        if analyze:
            pp = self.db.plan_profiler
            if pp is not None and pp.enabled:
                # EXPLAIN ANALYZE always profiles: force exactly one
                # profiled (segmented, fenced) run of the ANALYZED
                # statement's digest — re-point the pending digest too
                # (the one set at statement start named the outer
                # EXPLAIN text, not the inner select)
                d_inner = self._digest_of(text)
                pp.force_next(d_inner)
                pp.set_pending(d_inner)
            ta = _time.perf_counter()
            rs = self._select(ast, P.normalize_for_cache(text)[0])
            wall_s = _time.perf_counter() - ta
            # the analyzed statement's own record (none of it where the
            # statement took a route that keeps none: a recursive CTE)
            record = rs.record()
            ph = rs.phases or {}

            def us(s: float) -> int:
                return int(s * 1e6)

            opp = rs.op_profile
            lines = list(lines)
            if opp is not None:
                from ..sql.explain import annotate_plan_lines

                lines = annotate_plan_lines(lines, opp)
            lines.append("")
            hit = "hit" if ph.get("cache_hit") else "miss"
            lines.append(
                f"ANALYZE rows={rs.nrows} plan_cache={hit}"
            )
            lines.append(f"  phase parse:   {us(parse_s)} us")
            if ph:
                lines.append(f"  phase plan:    {us(ph['plan_s'])} us")
                lines.append(f"  phase compile: {us(ph['compile_s'])} us")
                lines.append(f"  phase execute: {us(ph['exec_s'])} us")
            if opp is not None and wall_s > 0:
                # the host-tax view on the same report: how much of the
                # analyzed statement's e2e wall the chip actually worked
                # (device time = the profile's fenced per-operator sum)
                dev_s = sum(
                    s.device_us for s in opp["samples"]) / 1e6
                idle = max(0.0, wall_s - dev_s) / wall_s * 100.0
                lines.append(
                    f"  chip_idle_pct: {idle:.1f} "
                    f"(device {us(dev_s)} us of {us(wall_s)} us e2e)"
                )
        return ResultSet(("plan",), {"plan": lines}, **record)

    # ------------------------------------------------------------------ XA
    def _xa(self, text: str) -> ResultSet:
        """XA surface (src/storage/tx/ob_xa_ctx analog at this engine's
        scale): START/END tag a session tx with an external xid, PREPARE
        logs the branch's redo DURABLY through palf (XA_PREPARE records on
        every participant LS, ob_trans_part_ctx.h:154) and parks it with
        locks + staged rows held, and COMMIT/ROLLBACK finish it from ANY
        session — the external-coordinator contract. A restart rebuilds
        the parked set from log replay (+ the node-meta registry
        snapshot), re-stages the pending redo on the leader, and re-holds
        the locks: prepared branches survive kill-9 and remain decidable,
        which is the window XA exists for."""
        import re as _re

        m = _re.match(
            r"\s*xa\s+(\w+)\s*(?:'([^']*)'|\"([^\"]*)\"|([^\s;]+))?",
            text, _re.IGNORECASE,
        )
        if not m:
            raise SqlError("bad XA syntax")
        verb = m.group(1).lower()
        if verb == "recover":
            # owners see their branches; root sees everything; branches
            # still mid-PREPARE are not yet recoverable
            xids = sorted(
                x for x, entry in self.db._xa_prepared.items()
                if entry[0] is not _XA_PREPARING
                and (self.user == "root" or entry[1] == self.user)
            )
            return ResultSet(("xid",), {"xid": xids})
        xid = next((g for g in m.groups()[1:] if g is not None), None)
        if xid is None:
            raise SqlError("XA needs an xid", code=1398)  # XAER_INVAL
        if verb in ("start", "begin"):
            if self._tx is not None:
                raise SqlError("transaction already open", code=1399)
            self._tx = _OpenTx(self.db, deadline=self._new_trx_deadline())
            self._xa_id = xid
            return ResultSet((), {})
        if verb == "end":
            if self._tx is None or getattr(self, "_xa_id", None) != xid:
                raise SqlError(f"unknown xid {xid!r}", code=1397)
            return ResultSet((), {})  # idle marker; state kept implicit
        if verb == "prepare":
            from ..tx.txn import NotMaster, TxState

            if self._tx is None or getattr(self, "_xa_id", None) != xid:
                raise SqlError(f"unknown xid {xid!r}", code=1397)
            # RESERVE the xid before logging (one atomic check+insert): two
            # concurrent prepares under the same xid must not both log —
            # the loser's branch would park forever without a handle
            with self.db._ddl_lock:
                if xid in self.db._xa_prepared:
                    raise SqlError(f"xid {xid!r} already prepared",
                                   code=1399)
                self.db._xa_prepared[xid] = (_XA_PREPARING, self.user, None)
            tx = self._tx
            self._tx = None
            self._xa_id = None
            try:
                try:
                    tx.svc.xa_prepare(tx.ctx, xid, self.user,
                                      self.db.tenant_name)
                except NotMaster as e:
                    # xa_prepare already rolled the tx back locally (and
                    # logged ABORT where a PREPARE reached the log): only
                    # the server-side locks remain to release
                    self._post_tx_cleanup(tx, committed_ok=False)
                    raise SqlError(f"XA PREPARE failed: {e}", code=1399)
                self.db.cluster.drive_until(
                    lambda: tx.ctx.state is not TxState.PREPARING)
                if tx.ctx.state is not TxState.XA_PREPARED:
                    try:
                        if not tx.ctx.is_done:
                            tx.svc.abort(tx.ctx)
                    except Exception:
                        pass
                    self._post_tx_cleanup(tx, committed_ok=False)
                    raise SqlError(
                        f"XA PREPARE did not reach the log for {xid!r}",
                        code=1399)
            except BaseException:
                with self.db._ddl_lock:
                    self.db._xa_prepared.pop(xid, None)
                raise
            with self.db._ddl_lock:
                self.db._xa_prepared[xid] = (tx, self.user, None)
            return ResultSet((), {})
        if verb in ("commit", "rollback"):
            with self.db._ddl_lock:
                hit = self.db._xa_prepared.get(xid)
                if hit is not None:
                    _tx, owner = hit[0], hit[1]
                    if _tx is _XA_PREPARING:
                        raise SqlError(
                            f"xid {xid!r} is being prepared", code=1399)
                    # the decide step is guarded: only the preparing
                    # user or root may finish a parked branch
                    if self.user != "root" and owner != self.user:
                        raise SqlError(
                            f"xid {xid!r} belongs to {owner!r}",
                            code=1227,
                        )
                    del self.db._xa_prepared[xid]
            if hit is not None:
                parked_tx = hit[0]
                try:
                    if parked_tx is not None:
                        self._xa_finish_parked(parked_tx,
                                               commit=(verb == "commit"))
                    else:
                        self._xa_finish_recovered(
                            xid, hit[2], commit=(verb == "commit"))
                except BaseException:
                    # a FAILED decide must stay decidable: restore the
                    # handle so a retry can re-drive the same decision
                    # (locks stay held until it lands — see the gated
                    # cleanup in the finish helpers)
                    with self.db._ddl_lock:
                        self.db._xa_prepared.setdefault(xid, hit)
                    raise
                return ResultSet((), {})
            # one-phase: this session's own un-prepared xid
            if self._tx is not None and \
                    getattr(self, "_xa_id", None) == xid:
                tx = self._tx
                self._tx = None
                self._xa_id = None
            else:
                raise SqlError(f"unknown xid {xid!r}", code=1397)
            self._finish_tx(tx, commit=(verb == "commit"))
            return ResultSet((), {})
        raise SqlError(f"bad XA verb {verb!r}", code=1398)

    def _xa_finish_parked(self, tx: "_OpenTx", commit: bool) -> None:
        """Decide a live parked (XA_PREPARED) branch: redo is already in
        the log, so commit only logs the decision records. Locks release
        ONLY once the decision lands (ctx.is_done) — releasing on a
        timeout while COMMIT records sit undelivered would let a new
        writer slip under the prepared rows (lost update). A timed-out
        decide leaves the branch parked for retry (same decision)."""
        from ..tx.txn import TxState

        ctx = tx.ctx
        try:
            tx.svc.xa_decide(ctx, commit)
        except RuntimeError as e:
            raise SqlError(str(e), code=1399) from None

        def done() -> bool:
            tx.svc.retry_decisions(ctx)
            return ctx.is_done

        ok = self.db.cluster.drive_until(done)
        if ctx.is_done:
            committed_ok = commit and ctx.state is TxState.COMMITTED
            self._post_tx_cleanup(tx, committed_ok)
        if not ok:
            raise SqlError(f"XA decision for tx {ctx.tx_id} timed out")

    def _xa_finish_recovered(self, xid: str, snapshot: dict | None,
                             commit: bool) -> None:
        """Decide a branch recovered from log replay after a restart: no
        live ctx exists — submit the decision records straight to the
        participant leader replicas and wait for apply (which commits the
        re-staged rows / replays pending redo). `snapshot` is the handle's
        registry snapshot: a retry after a failed decide can finish
        cleanup from it even once the live registry entry has popped."""
        e = self.db._xa_registry.get(xid) or snapshot
        if e is None:
            return  # decision already applied (e.g. raced another session)
        want = "commit" if commit else "rollback"
        prior = e.get("decision")
        if prior is not None and prior != want:
            # records of the FIRST decision may already sit in participant
            # logs; reversing would split the branch across directions —
            # this guard holds on RETRIES too (the registry entry may have
            # popped, but the handle snapshot remembers the direction)
            raise SqlError(
                f"xid {xid!r} already deciding {prior}; retry that",
                code=1399)
        e["decision"] = want
        if not commit:
            self._xa_decide_recovered(xid, e, commit)
            return
        by_tab = {ti.tablet_id: ti for ti in self.db.tables.values()}
        tis = {by_tab[t].name: by_tab[t] for t in e["tablets"]
               if t in by_tab}
        # open on the branch's tables from before its version is drawn
        # until the bump (Database.tx_shared_entry)
        with self.db.bulk_write(tis.values()):
            self._xa_decide_recovered(xid, e, commit)
        self.db.run_maintenance()

    def _xa_decide_recovered(self, xid: str, e: dict, commit: bool) -> None:
        from ..tx.records import RecordType, TxRecord

        tx_id, parts = e["tx_id"], tuple(e["parts"])
        if xid in self.db._xa_registry:
            # first attempt (or retry whose records never reached a log):
            # submit the decision to every participant leader
            version = self.db.cluster.gts.next_ts() if commit else 0
            rtype = RecordType.COMMIT if commit else RecordType.ABORT
            for ls in parts:
                group = self.db.cluster.ls_groups.get(ls) or {}

                def try_submit(ls=ls, group=group) -> bool:
                    for rep in group.values():
                        if rep.is_ready and rep.submit_record(
                                TxRecord(rtype, tx_id, (), version)
                        ) is not None:
                            return True
                    return False

                if not self.db.cluster.drive_until(try_submit):
                    raise SqlError(
                        f"no ready leader for ls {ls} to decide xid {xid!r}")

        def all_applied() -> bool:
            # the branch is decided only when the decision has applied on
            # EVERY participant replica (registry pop happens at the FIRST
            # apply — releasing locks then would expose a torn multi-LS
            # branch / lost-update window)
            for ls in parts:
                for rep in (self.db.cluster.ls_groups.get(ls) or {}).values():
                    if tx_id in rep.tx_table:
                        return False
            return xid not in self.db._xa_registry

        if not self.db.cluster.drive_until(all_applied):
            raise SqlError(f"XA decision for xid {xid!r} did not apply")
        self.db.lock_mgr.release_all(tx_id)

    # -------------------------------------------------- stored procedures
    def _create_procedure(self, text: str) -> ResultSet:
        from ..sql.pl import parse_procedure

        if self.user != "root":
            from ..share.privilege import AccessDenied

            try:
                self.db.privileges.check(self.user, "create", {"*"})
            except AccessDenied as e:
                raise SqlError(str(e), code=e.code) from None
        try:
            proc = parse_procedure(text)
        except SyntaxError as e:
            raise SqlError(f"PL syntax: {e}") from None
        with self.db._ddl_lock:
            if proc.name in self.db._procedure_texts:
                raise SqlError(f"procedure {proc.name} already exists")
            self.db._procedure_texts[proc.name] = text
            self.db._procedures_parsed[proc.name] = proc
            self.db._save_node_meta()
        return ResultSet((), {})

    def _drop_procedure(self, text: str) -> ResultSet:
        if self.user != "root":
            from ..share.privilege import AccessDenied

            try:
                self.db.privileges.check(self.user, "drop", {"*"})
            except AccessDenied as e:
                raise SqlError(str(e), code=e.code) from None
        parts = text.split()
        if len(parts) < 3:
            raise SqlError("DROP PROCEDURE needs a name")
        # the lexer lowercases identifiers at CREATE: match it
        name = parts[2].rstrip(";").lower()
        with self.db._ddl_lock:
            if self.db._procedure_texts.pop(name, None) is None:
                raise SqlError(f"no procedure {name}")
            self.db._procedures_parsed.pop(name, None)
            self.db._save_node_meta()
        return ResultSet((), {})

    def lookup_procedure(self, name: str):
        proc = self.db._procedures_parsed.get(name)
        if proc is None:
            text = self.db._procedure_texts.get(name)
            if text is None:
                return None
            from ..sql.pl import parse_procedure

            proc = parse_procedure(text)
            self.db._procedures_parsed[name] = proc
        return proc

    def run_statement(self, stmt, cache_key: str | None = None) -> ResultSet:
        """Execute one already-parsed statement (PL interpreter's SQL
        hook). Privileges enforce under the CALLING user (invoker
        rights); `cache_key` must identify the STORED statement node
        (not the per-call substituted copy) so plans stay cached across
        invocations — literal substitutions parameterize away inside
        the plan cache exactly like client literals."""
        self._check_privs(stmt)
        return self._dispatch_stmt(
            stmt, cache_key or f"#pl:{id(stmt)}")

    def _call_procedure(self, text: str) -> ResultSet:
        from ..sql.pl import PlError, PlInterpreter, PlParser

        p = PlParser(text.rstrip().rstrip(";") + ";")
        try:
            call = p._pl_statement()
        except SyntaxError as e:
            raise SqlError(f"bad CALL: {e}") from None
        from ..sql.pl import PlCall

        if not isinstance(call, PlCall):
            raise SqlError("expected CALL name(args)")
        proc = self.lookup_procedure(call.name)
        if proc is None:
            raise SqlError(f"no procedure {call.name}")
        interp = PlInterpreter(self)
        try:
            args = [interp._eval(a, {}) for a in call.args]
            ret, _env = interp.call(proc, args)
        except PlError as e:
            raise SqlError(f"PL: {e}") from None
        if ret is None:
            return ResultSet((), {})
        return ResultSet(("result",), {"result": [ret]})

    def _select_flashback(self, ast, fb) -> ResultSet:
        """FLASHBACK query: every `t AS OF SNAPSHOT s` reference reads a
        statement-scoped materialization of the OLDER MVCC snapshot;
        plain references in the same statement read current data (so
        `t` can join `t AS OF SNAPSHOT s` to diff history). Plans do
        not cache: the snapshot tables are per-statement."""
        import dataclasses as _dc

        tmp_names = []
        # session-scoped keys: two sessions flashing back to the SAME
        # (table, snapshot) must not share one catalog entry — the first
        # finisher would pop it under the other statement
        sid = self.session_id
        try:
            for name, snap in fb:
                tmp = f"#fb:{name}@{snap}#{sid}"
                self.db.catalog[tmp] = self.db.snapshot_table(name, snap)
                self.db._invalidate(tmp)
                tmp_names.append(tmp)

            def rw(node):
                if isinstance(node, A.TableRef) and node.snapshot is not None:
                    return A.TableRef(
                        f"#fb:{node.name}@{node.snapshot}#{sid}",
                        node.alias or node.name,
                    )
                if _dc.is_dataclass(node) and not isinstance(node, type):
                    ch = {}
                    for f in _dc.fields(node):
                        cur = getattr(node, f.name)
                        new = rw(cur)
                        if new is not cur:
                            ch[f.name] = new
                    return _dc.replace(node, **ch) if ch else node
                if isinstance(node, tuple):
                    items = tuple(rw(x) for x in node)
                    if any(a is not b for a, b in zip(items, node)):
                        return items
                    return node
                return node

            ast2 = rw(ast)
            plain = _tables_in_ast(ast2) - set(tmp_names)
            self.db.refresh_virtual(plain)
            self.db.refresh_catalog(plain, tx=self._tx)
            rs = self.db.engine.run_ast(ast2, "#flashback", use_cache=False)
            return rs
        finally:
            for tmp in tmp_names:
                self.db.catalog.pop(tmp, None)
                self.db._invalidate(tmp)

    # -------------------------------------------------------------- lock
    def _lock_table(self, st: A.LockTable) -> ResultSet:
        from ..tx.tablelock import DeadlockDetected, LockMode

        ti = self.db.tables.get(st.name)
        if ti is None:
            raise SqlError(f"no such table {st.name}")
        if self._tx is None:
            raise SqlError("LOCK TABLE requires an open transaction")
        mode = LockMode.EXCLUSIVE if st.exclusive else LockMode.SHARE
        try:
            self.db.lock_mgr.lock(self._tx.ctx.tx_id, ti.tablet_id, mode)
        except DeadlockDetected:
            # victim policy: the cycle-closing tx aborts (share/deadlock)
            self._end_tx(commit=False)
            raise
        return ResultSet((), {})

    # -------------------------------------------------------------- show
    _BOOL_WORDS = {"true": 1, "on": 1, "false": 0, "off": 0}
    _CONSISTENCY_WORDS = {"strong": 0, "bounded_staleness": 1, "weak": 2}
    # enum-valued session variables: accepted words -> stored int
    _ENUM_VARS = {"ob_read_consistency": _CONSISTENCY_WORDS}

    def _set_session_var(self, text: str) -> ResultSet:
        """SET <name> = <value> — session-scoped variables (the reference's
        sys-var surface, narrowed to the diagnosability knobs):
        ob_enable_show_trace gates full-link collection for THIS session,
        ob_px_dop routes SELECTs through the distributed (PX) executor."""
        body = text.strip().rstrip(";")
        body = body[3:].strip()  # after SET
        name, eq, val = body.partition("=")
        if not eq:
            raise SqlError("SET needs <variable> = <value>")
        name = name.strip().lower().lstrip("@").strip()
        if name not in self._vars:
            raise SqlError(f"unknown session variable {name!r}")
        sval = val.strip().strip("'\"").lower()
        try:
            iv = int(sval)
        except ValueError:
            iv = self._ENUM_VARS.get(name, {}).get(sval)
            if iv is None:
                iv = self._BOOL_WORDS.get(sval)
            if iv is None:
                raise SqlError(
                    f"bad value {val.strip()!r} for {name}") from None
        if name in self._ENUM_VARS and iv not in set(
                self._ENUM_VARS[name].values()):
            raise SqlError(f"bad value {val.strip()!r} for {name}")
        self._vars[name] = iv
        if name == "ob_enable_show_trace" and iv:
            # collection implies recording: a session asking for SHOW
            # TRACE needs spans in the ring regardless of the global flag
            self.db.tracer.enabled = True
        return ResultSet((), {})

    def _show_trace(self) -> ResultSet:
        if not self._vars.get("ob_enable_show_trace"):
            raise SqlError(
                "SHOW TRACE needs SET ob_enable_show_trace = 1 before the "
                "statement under diagnosis")
        tree = self.db.tracer.trace_tree(self._last_trace_id)
        names, nodes, elapsed, tags = [], [], [], []
        for depth, s in tree:
            names.append("  " * depth + s.name)
            nodes.append(str(s.tags.get("node", "")))
            elapsed.append(int(s.elapsed * 1e6))
            tags.append(", ".join(
                f"{k}={v}" for k, v in sorted(s.tags.items())
                if k != "node"
            ))
        return ResultSet(
            ("span_name", "node", "elapsed_us", "tags"),
            {"span_name": names, "node": nodes, "elapsed_us": elapsed,
             "tags": tags},
        )

    def _show(self, st: A.Show) -> ResultSet:
        if st.what == "trace":
            return self._show_trace()
        if st.what == "parameters":
            import fnmatch

            pat = st.like.replace("%", "*").replace("_", "?") if st.like else None
            names, values, types, scopes, infos = [], [], [], [], []
            for n, v, p in self.db.config.snapshot():
                if pat is not None and not fnmatch.fnmatch(n, pat):
                    continue
                names.append(n)
                values.append(str(v))
                types.append(p.type)
                scopes.append(p.scope)
                infos.append(p.info)
            return ResultSet(
                ("name", "value", "type", "scope", "info"),
                {"name": names, "value": values, "type": types,
                 "scope": scopes, "info": infos},
            )
        if st.what == "tables":
            names = sorted(set(self.db.tables) | set(self.db.catalog))
            return ResultSet(("table_name",), {"table_name": names})
        raise SqlError(f"unsupported SHOW {st.what}")

    # ------------------------------------------------------------ select
    _INDEX_ROUTE_MAX_ROWS = 4096

    def _route_table(self, ast) -> "tuple[A.TableRef, TableInfo] | None":
        """(FROM's table reference, its table) of a single-table SELECT
        over a served table with a WHERE and no subquery or CTE: the
        statements the index and range routes may read a few rows for."""
        if not isinstance(ast, A.Select) or len(ast.from_) != 1:
            return None
        tref = ast.from_[0]
        if not isinstance(tref, A.TableRef) or ast.ctes:
            return None
        from ..sql.planner import _contains_subquery

        if _contains_subquery(ast):
            return None
        ti = self.db.tables.get(tref.name)
        if ti is None or ast.where is None:
            return None
        return tref, ti

    def _index_route(self, ast: A.Select) -> dict[str, Table] | None:
        """DAS index/PK lookup analog (src/sql/das/iter): a single-table
        statement whose WHERE pins an index prefix (or the full primary
        key) with equality literals reads the few matching rows through the
        host index path instead of materializing the whole table to the
        device. Returns a statement-scoped {table: pruned Table} view, or
        None to fall back to the full-scan path. Inside a transaction it
        reads at the BEGIN snapshot; of a table the transaction has written
        only by the full primary key, the row its own staged writes leave
        (the replica they went to, read with its tx id), as the rescan's
        private view would show it."""
        got = self._route_table(ast)
        if got is None:
            return None
        tref, ti = got
        tx = self._tx
        touched = tx is not None and tref.name in tx.touched_tables
        alias = tref.alias or tref.name
        from ..sql.planner import split_ast_conjuncts

        eqs: dict[str, object] = {}
        for c in split_ast_conjuncts(ast.where):
            if not (isinstance(c, A.BinOp) and c.op == "="):
                continue
            lhs, rhs = c.left, c.right
            if not isinstance(lhs, A.Name):
                lhs, rhs = rhs, lhs
            if not isinstance(lhs, A.Name):
                continue
            parts = lhs.parts
            if len(parts) == 2 and parts[0] != alias:
                continue
            col = parts[-1]
            if col not in ti.schema:
                continue
            try:
                v = _eval_const(rhs)
            except SqlError:
                continue
            # encode without growing the dictionary: an unknown string
            # matches nothing (code -1 < every stored code)
            dt = ti.schema[col]
            if dt.kind is TypeKind.VARCHAR:
                d = ti.dicts.get(col)
                eqs[col] = d.encode_one(str(v), add=False) if d else -1
            else:
                try:
                    eqs[col] = _coerce(v, dt, None, col)
                except SqlError:
                    continue  # untypable literal: leave it to the engine

        if not eqs:
            return None
        snap = (tx.ctx.read_snapshot if tx is not None
                else self.db.cluster.gts.current())
        rep = self.db._leader_replica(ti)
        rows: list[tuple] | None = None
        used_idx = None
        if set(ti.key_cols) <= set(eqs):
            pk = tuple(int(eqs[k]) for k in ti.key_cols)
            pls, ptab = ti.partition_for_key(pk)
            if touched:
                hit = tx.svc.replicas[pls].tablets[ptab].get(
                    pk, snap, tx.ctx.tx_id)
            else:
                hit = self.db._leader_replica_ls(pls).tablets[ptab].get(
                    pk, snap)
            rows = [hit[1]] if hit is not None else []
        elif touched:
            return None
        else:
            best = None
            for idx in ti.indexes.values():
                # an index built after the snapshot holds none of the
                # rows it backfilled at that snapshot
                if idx.status != "ready" or idx.build_version > snap:
                    continue
                m = 0
                for c in idx.cols:
                    if c in eqs:
                        m += 1
                    else:
                        break
                if m and (best is None or m > best[1]):
                    best = (idx, m)
            if best is None:
                return None
            idx, m = best
            ranges = {
                c: (float(eqs[c]), float(eqs[c])) for c in idx.cols[:m]
            }
            idata = rep.tablets[idx.tablet_id].scan(snap, ranges=ranges)
            # ranges only PRUNE (zone maps; memtable rows come back whole):
            # apply the exact equality filter before fetching base rows
            if len(idata[idx.key_cols[0]]):
                m_ok = np.ones(len(idata[idx.key_cols[0]]), dtype=bool)
                for c in idx.cols[:m]:
                    m_ok &= idata[c] == eqs[c]
                idata = {c: a[m_ok] for c, a in idata.items()}
            pk_arrays = [idata[k] for k in ti.key_cols]
            npk = len(pk_arrays[0]) if pk_arrays else 0
            if npk > self._INDEX_ROUTE_MAX_ROWS:
                return None  # not selective enough: full scan wins
            rows = []
            for i in range(npk):
                pk = tuple(int(a[i]) for a in pk_arrays)
                pls, ptab = ti.partition_for_key(pk)
                hit = self.db._leader_replica_ls(pls).tablets[ptab].get(pk, snap)
                if hit is not None:
                    rows.append(hit[1])
            used_idx = idx
        names = ti.schema.names()
        data = {
            c: np.array([r[j] for r in rows], dtype=ti.schema[c].storage_np)
            for j, c in enumerate(names)
        }
        dicts = ti.remap_sorted(data)
        if used_idx is not None:
            used_idx.reads += 1
        if self.db.access.enabled:
            # workload heat: host-side DAS lookups are reads the device
            # scan path never sees
            self.db.access.record_das(tref.name, len(rows))
        return {tref.name: Table(tref.name, ti.schema, data, dicts)}

    def _range_bounds(self, ast) -> "tuple[str, TableInfo, int, int] | None":
        """(table, its info, lo, hi) where a single-table statement's WHERE
        bounds a single-column integer primary key on both sides with
        literals (BETWEEN, or a pair of >= > <= <): the statements the
        range route reads; bounds inclusive, lo > hi where none lies
        between them."""
        got = self._route_table(ast)
        if got is None:
            return None
        tref, ti = got
        if len(ti.key_cols) != 1 or not ti.schema[ti.key_cols[0]].is_integer:
            return None
        from ..sql.planner import split_ast_conjuncts

        pk = ti.key_cols[0]
        alias = tref.alias or tref.name
        lo = hi = None
        for c in split_ast_conjuncts(ast.where):
            b = _pk_bounds(c, pk, alias)
            if b is None:
                continue
            if b[0] is not None:
                lo = b[0] if lo is None else max(lo, b[0])
            if b[1] is not None:
                hi = b[1] if hi is None else min(hi, b[1])
        if lo is None or hi is None:
            return None
        # past the column's int64: none of its keys (lo > hi reads none)
        return tref.name, ti, max(lo, _INT64.min), min(hi, _INT64.max)

    def _range_route(self, ast: A.Select) -> dict[str, Table] | None:
        """A primary-key range read at a transaction's BEGIN snapshot: a
        statement `_range_bounds` accepts reads only the tablet rows of
        its key range, with the transaction's own staged rows over them
        exactly as the rescan's private view shows them. The whole WHERE
        still runs on the device over the few rows. Returns a
        statement-scoped {table: Table} view, or None where the rescan
        answers: no such range, or more than _INDEX_ROUTE_MAX_ROWS rows
        in it."""
        got = self._range_bounds(ast)
        if got is None:
            return None
        name, ti, lo, hi = got
        pk = ti.key_cols[0]
        tx = self._tx
        db = self.db
        with _GL.span("range route"):
            db.settle_writers()
            # the replica and tx id the rescan reads (refresh_catalog)
            touched = name in tx.touched_tables
            tx_id = tx.ctx.tx_id if touched else 0
            snap = tx.ctx.read_snapshot
            ranges = {pk: (float(lo), float(hi))}
            parts = []
            for ls_id, tablet_id in ti.all_partitions():
                rep = (tx.svc.replicas[ls_id] if touched
                       else db._leader_replica_ls(ls_id))
                parts.append(rep.tablets[tablet_id].scan(
                    snap, ranges=ranges, tx_id=tx_id))
            data = {c: np.concatenate([p[c] for p in parts])
                    for c in parts[0]}
            # the exact bound in integers: the scan's float bounds round
            # past 2**53
            k = data[pk]
            inside = (k >= lo) & (k <= hi)
            if not inside.all():
                data = {c: a[inside] for c, a in data.items()}
            if len(data[pk]) > self._INDEX_ROUTE_MAX_ROWS:
                return None
            _dense_vectors(ti.schema, data)
            t = Table(name, ti.schema, data, ti.remap_sorted(data))
        return {name: t}

    def _prepare_range_shapes(self, ast: A.Select, norm_key: str) -> None:
        """Once per statement text the range route could answer, from its
        second execution on (its plan cached by the first): compile that
        plan's programs over a table of the route's size too, whichever
        route answers now, so whether a table has open writers never
        decides a compile."""
        done = self.db.range_shapes
        tried = done.get(norm_key)
        if tried:
            return
        got = self._range_bounds(ast)
        if got is None:
            done[norm_key] = True
            return
        name, ti = got[:2]
        # no rows: the program of every route read up to 1,024 rows
        data = {f.name: np.zeros(0, f.dtype.storage_np)
                for f in ti.schema.fields}
        _dense_vectors(ti.schema, data)
        t = Table(name, ti.schema, data, ti.remap_sorted(data))
        try:
            with self.db.catalog.tx_scope({name: t}):
                ok = self.db.engine.prepare_shapes(ast, norm_key)
        except Exception:  # noqa: BLE001 - a compile ahead of need
            ok = True  # never fails the statement; not tried again
        done[norm_key] = ok or tried is False

    def _follower_select(self, ast: A.Select, norm_key: str,
                         names) -> "ResultSet | None":
        """Serve a non-strong SELECT from follower replicas: statement-
        scoped views (TxCatalog.tx_scope, so the shared device-batch and
        fast-path caches never see replica state) at a snapshot provably
        within the session's staleness bound. None falls back to the
        leader path — which is also the `strong`-on-follower contract:
        identical routing, bit-identical rows."""
        db = self.db
        weak = self._vars["ob_read_consistency"] == 2
        fv = db.follower_read_views(
            names, self._vars.get("ob_max_read_stale_us", 0), weak=weak)
        if fv is None:
            return None
        views, snap, stale_us = fv
        # non-replicated tables in the statement (preloaded/external)
        # refresh through the normal shared-catalog path
        db.refresh_catalog([n for n in names if n not in views], tx=None)
        with db.catalog.tx_scope(views):
            rs = db.engine.run_ast(ast, norm_key)
        self._scan_rs = rs
        self.last_follower_read = (snap, stale_us)
        db.metrics.add("follower read hits")
        return rs

    def _select_degraded(self, ast: A.Select, norm_key: str) -> ResultSet:
        """Device-OOM ladder rungs 2/3: re-drive the statement on a
        private degraded executor. "chunk" re-plans through the chunked
        path with a chunk size derived from the budget the governor has
        left; "host" compiles a fresh plan pinned to the host device
        (which cannot device-OOM). Both bypass the plan cache — the
        cached executable is exactly what just OOMed — and both return
        bit-identical rows to the undegraded plan."""
        import contextlib

        from ..engine.executor import Executor
        from ..engine.memory_governor import derive_chunk_rows

        db = self.db
        base = db.engine.executor
        if self._degrade_mode == "chunk":
            remaining = max(db.governor.remaining(), 1)
            ex = Executor(
                db.catalog, unique_keys=base.unique_keys, stats=base.stats,
                device_budget=remaining,
                chunk_rows=derive_chunk_rows(remaining, base.chunk_rows),
            )
            ctx = contextlib.nullcontext()
        else:  # host fallback
            ex = Executor(db.catalog, unique_keys=base.unique_keys,
                          stats=base.stats)
            ex.chunking_enabled = False
            ex.host_fallback = True
            try:
                import jax

                ctx = jax.default_device(jax.devices("cpu")[0])
            except Exception:  # no CPU device handle: backend IS the host
                ctx = contextlib.nullcontext()
        # a stand-in for the session's executor: the plain frame
        ex.fuses_frame = False
        ex.timeline = base.timeline
        in_tx = self._tx is not None and self._tx.ctx is not None
        views = self._tx.views if in_tx else None
        with ctx, db.catalog.tx_scope(views):
            rs = db.engine.run_ast(ast, norm_key, use_cache=False,
                                   executor=ex)
        self._scan_rs = rs
        return rs

    def _select(self, ast: A.Select, norm_key: str, fast_reg=None
                ) -> ResultSet:
        fb = _flashback_refs(ast)
        if fb:
            return self._select_flashback(ast, fb)
        raw_names = _tables_in_ast(ast)
        names = self.db.expand_views(set(raw_names))
        any_vt = self.db.refresh_virtual(names)
        self.last_follower_read = None
        if self._degrade_mode is not None and not any_vt:
            # device-OOM ladder rungs 2/3: re-plan on a private degraded
            # executor (chunked or host), bypassing PX and index routing
            self.db.refresh_catalog(names, tx=self._tx)
            return self._select_degraded(ast, norm_key)
        if (self._vars.get("ob_read_consistency", 0) != 0
                and self._tx is None and not any_vt
                and self._vars.get("ob_px_dop", 0) == 0
                and isinstance(ast, A.Select)):
            rs = self._follower_select(ast, norm_key, names)
            if rs is not None:
                return rs
            # bound unmet / no reachable follower: strong leader path below
        tx = self._tx
        in_tx = tx is not None and tx.ctx is not None
        # a single-chip SELECT of a transaction may take the autocommit
        # route, read at the transaction's snapshot: the index route for a
        # table it has not written, else the shared committed entry of
        # each table tx_shared_entry finds clean for that snapshot
        shared_ok = (in_tx and not any_vt
                     and self._vars.get("ob_px_dop", 0) == 0
                     and isinstance(ast, A.Select))
        if shared_ok:
            self._prepare_range_shapes(ast, norm_key)
        route = None
        if shared_ok or (tx is None and not any_vt
                         and isinstance(ast, A.Select)):
            route = self._index_route(ast)
        if route is not None and not (in_tx and set(names) - set(route)):
            self.db.refresh_catalog(
                [n for n in names if n not in route], tx=None
            )
            with self.db.catalog.tx_scope(route):
                rs = self.db.engine.run_ast(ast, norm_key)
            self._scan_rs = rs
            if in_tx:
                # a written table's row carries the transaction's own writes
                self.db.metrics.add(
                    "tx snapshot private reads"
                    if any(n in tx.touched_tables for n in route)
                    else "tx snapshot shared reads")
            return rs
        shared = {}
        if shared_ok:
            for n in names:
                e = self.db.tx_shared_entry(n, tx)
                if e is not None:
                    shared[n] = e
        if shared:
            self.db.refresh_catalog(
                [n for n in names if n not in shared], tx=tx)
            views = {n: v for n, v in tx.views.items() if n not in shared}
            with self.db.catalog.tx_scope(views):
                rs = self.db.engine.run_ast(ast, norm_key)
            if self.db.tx_shared_holds(shared):
                self._scan_rs = rs
                self.db.metrics.add(
                    "tx snapshot shared reads"
                    if all(n in shared for n in names
                           if n in self.db.tables)
                    else "tx snapshot private reads")
                return rs
            # an entry was replaced, or the table written, while the
            # statement ran: its answer is thrown away, the rescan below
            # answers instead
        if shared_ok:
            # the alternative below is a rescan of the whole table at the
            # snapshot: a key range reads its own rows instead
            route = self._range_route(ast)
            if route is not None:
                with self.db.catalog.tx_scope(route):
                    rs = self.db.engine.run_ast(ast, norm_key)
                self._scan_rs = rs
                self.db.metrics.add("tx range route reads")
                return rs
        self.db.refresh_catalog(names, tx=tx)
        views = tx.views if in_tx else None
        if in_tx and any(n in self.db.tables for n in names):
            self.db.metrics.add("tx snapshot private reads")
        # PX routing: non-virtual statements of a session with a DOP
        # variable run on the distributed executor. In-tx reads are safe:
        # the PX executor bypasses its shared input cache for tx-private
        # views (is_private), mirroring the single-chip isolation contract.
        px = None
        px_granted = 0
        if self._vars.get("ob_px_dop", 0) > 0 and not any_vt:
            # admission first (ObPxAdmission): hold a worker grant for the
            # whole distributed execution, released in the finally below
            px_granted = self._px_admit(self._vars["ob_px_dop"])
            px = self.db._px_executor()
        # fast-tier registration only from the plain route: no virtual
        # tables (use_cache is off anyway), no open tx (tx-private views
        # would leak across sessions), no PX (the compiled plan differs),
        # and no view expansion (the scan tables a fast hit privilege-
        # checks would diverge from what the user was granted)
        reg = (fast_reg if px is None and not any_vt and not in_tx
               and names == raw_names else None)
        try:
            with self.db.catalog.tx_scope(views):
                # a PX compile or execution failure is the statement's
                # error (the retry controller sorts retryable from not):
                # no re-run on one chip behind the operator's back
                rs = self.db.engine.run_ast(
                    ast, norm_key,
                    use_cache=False if any_vt else None,
                    executor=px,
                    fast_reg=reg,
                )
            # for DML the qualification scan's plan reuse IS the
            # statement's plan-cache behavior, and its record the
            # statement's (_dml)
            self._scan_rs = rs
            return rs
        finally:
            if px_granted:
                self.db._px_admission().release(px_granted)
            if any_vt:
                # virtual snapshots are per-statement: release them so they
                # neither pin memory nor appear as tables afterwards
                from .virtual_tables import PROVIDERS

                for n in names:
                    if n in PROVIDERS:
                        self.db.catalog.pop(n, None)
                        self.db._invalidate(n)

    def _new_trx_deadline(self) -> "_R.Deadline":
        """ob_trx_timeout deadline for a transaction opened now (BEGIN,
        XA START, or an autocommit DML's implicit tx)."""
        db = self.db
        return _R.Deadline.after(
            lambda: db.cluster.bus.now,
            self._vars["ob_trx_timeout"] / 1e6,
            label="ob_trx_timeout",
        )

    def _px_admit(self, dop: int) -> int:
        """Deadline-bounded PX admission: queue for a worker grant no
        longer than the statement deadline allows. An admission timeout is
        retryable (quota frees as peers finish) unless the deadline was
        the tighter bound, which surfaces as the statement's timeout."""
        adm = self.db._px_admission()
        wait_s = adm.queue_timeout_s
        d = _R.current_deadline()
        bounded = d is not None and d.tighter_than(wait_s)
        if bounded:
            wait_s = max(d.remaining(), 0.0)
        try:
            with self.db.metrics.waiting("px admission queue"):
                return adm.acquire(dop, timeout=wait_s)
        except RuntimeError as e:
            self.db.metrics.add("px admission timeouts")
            if bounded:
                self.db.metrics.add("statement timeouts")
                raise d._error() from e
            raise _R.PxAdmissionTimeout(str(e)) from e

    def _digest_of(self, text: str) -> str:
        """Memoized statement digest (same key the workload summary,
        host-tax ledger and flight recorder fold under)."""
        digest = self._digest_memo.get(text)
        if digest is None:
            if len(self._digest_memo) >= 256:
                self._digest_memo.clear()
            digest = self._digest_memo[text] = P.digest_text(text)
        return digest

    def _reserve_estimate(self, text: str) -> int:
        """Peak-device-bytes estimate for the admission reservation:
        the workload repository's measured per-digest peak when this
        statement has run before, else a conservative cold default for
        reads (ob_governor_cold_reserve). Non-reads reserve nothing —
        DML device work rides the read paths it triggers."""
        db = self.db
        low = text.lstrip().lower()
        if not low.startswith(("select", "with", "(")):
            return 0
        measured = db.stmt_summary.peak_estimate(self._digest_of(text))
        if measured > 0:
            return measured
        return int(db.config["ob_governor_cold_reserve"])

    def _reserve_device_memory(self, nbytes: int):
        """Deadline-bounded device-memory admission (mirrors _px_admit):
        wait on the governor's ledger no longer than the statement
        deadline allows. A reservation timeout is retryable (peers
        release as they finish) unless the deadline was the tighter
        bound, which surfaces as the statement's timeout."""
        db = self.db
        gov = db.governor
        wait_s = float(db.config["ob_governor_queue_timeout"])
        d = _R.current_deadline()
        bounded = d is not None and d.tighter_than(wait_s)
        if bounded:
            wait_s = max(d.remaining(), 0.0)
        with db.metrics.waiting("device memory reservation"):
            res = gov.reserve(db.tenant_name, nbytes, timeout_s=wait_s)
        if res is None:
            db.metrics.add("device memory rejects")
            if bounded:
                db.metrics.add("statement timeouts")
                raise d._error()
            raise _R.DeviceMemoryTimeout(
                f"device memory reservation of {nbytes} bytes timed out "
                f"after {wait_s:.3f}s (reserved {gov.reserved} of "
                f"{gov.effective_budget()} bytes)")
        return res

    # --------------------------------------------------------------- tx
    def _dml(self, body) -> ResultSet:
        # an expired deadline (ob_trx_timeout on an idle explicit tx) must
        # refuse new work up front — the session can still ROLLBACK, which
        # doesn't come through here
        _R.checkpoint_deadline()
        auto = self._tx is None
        if auto:
            self._tx = _OpenTx(self.db, deadline=self._new_trx_deadline())
        try:
            affected = body(self._tx)
        except Exception:
            if auto:
                self._end_tx(commit=False)
            raise
        if auto:
            self._end_tx(commit=True)
        scan = self._scan_rs
        if scan is None:  # e.g. INSERT ... VALUES: no scan, no record
            return ResultSet((), {}, affected=affected)
        return ResultSet((), {}, affected=affected,
                         plan_cache_hit=scan.plan_cache_hit,
                         **scan.record())

    def _end_tx(self, commit: bool) -> None:
        tx = self._tx
        self._tx = None
        self._xa_id = None  # a finished tx sheds any XA association
        self._finish_tx(tx, commit)

    def _finish_tx(self, tx: "_OpenTx | None", commit: bool) -> None:
        """Drive a transaction to its decision and clean up — shared by
        COMMIT/ROLLBACK and the XA paths (where the tx may have been
        PREPARED by a different session)."""
        if tx is None or tx.ctx is None:
            return
        touched = tx.touched_tables
        committed_ok = False
        m = self.db.metrics
        tc0 = _time.perf_counter()
        try:
            if commit:
                try:
                    if touched:
                        # bound the palf commit wait by the statement
                        # deadline; an expired wait means the decision is
                        # in flight but unobserved -> CommitUnknown (the
                        # reference's OB_TRANS_UNKNOWN), never retried
                        max_wait = 30.0
                        d = _R.current_deadline()
                        if d is not None:
                            d.check()  # unwind before staging the decision
                            max_wait = min(max_wait, d.remaining())
                        with m.waiting("tx commit log sync"), \
                                _GL.span("commit wait"):
                            try:
                                self.db.cluster.commit_sync(
                                    tx.svc, tx.ctx, max_time=max_wait)
                            except TimeoutError as te:
                                raise _R.CommitUnknown(
                                    f"commit wait timed out: {te}"
                                ) from te
                    else:
                        # an empty tx finishes at once: its wait is ~0
                        with _GL.span("commit wait"):
                            tx.svc.commit(tx.ctx)
                except Exception:
                    # commit failed before a decision was logged: abort so the
                    # staged rows don't stay undecided forever (which would
                    # block later writers and pin frozen memtables). A tx in
                    # COMMITTING has its decision in flight and must converge
                    # on its own; abort() refuses that case.
                    from ..tx.txn import TxState

                    if not tx.ctx.is_done and tx.ctx.state is not TxState.COMMITTING:
                        tx.svc.abort(tx.ctx)
                    raise
                committed_ok = True
            else:
                tx.svc.abort(tx.ctx)
        finally:
            if commit and committed_ok:
                m.add("tx commits")
                m.observe("tx commit", _time.perf_counter() - tc0)
            elif commit:
                m.add("tx commit failures")
            else:
                m.add("tx rollbacks")
            self._post_tx_cleanup(tx, committed_ok)

    def _post_tx_cleanup(self, tx: "_OpenTx", committed_ok: bool) -> None:
        """Shared decision epilogue: release locks, refresh table versions,
        note durably-logged dictionary growth, trigger maintenance."""
        touched = tx.touched_tables
        # locks hold through the commit decision, then release
        self.db.lock_mgr.release_all(tx.ctx.tx_id)
        by_tablet = {ti.tablet_id: ti for ti in tx.writing}
        self.db.writers_end(tx.ctx, tx.writing)
        tx.writing = []
        if committed_ok:
            # the appends are durable now (committed_ok, NOT the commit
            # intent: a failed commit logged nothing): later commits
            # need not re-log them
            for tab_id, col, code, _s in tx.ctx.dict_appends:
                ti = by_tablet.get(tab_id)
                if ti is not None:
                    ti.logged_dict_len[col] = max(
                        ti.logged_dict_len.get(col, 0), code + 1
                    )
        if committed_ok and touched:
            # post-commit freeze/compaction check (the tenant freezer's
            # write-path trigger; cheap when under the memstore limit)
            self.db.run_maintenance()

    # --------------------------------------------------------------- DML
    @staticmethod
    def _note_dict_appends(tx: _OpenTx, ti: TableInfo) -> None:
        """Attach every not-yet-durably-logged dictionary entry to this tx
        (log self-description for CDC/PITR). Based on logged_dict_len, not
        statement-local growth: entries created by an earlier aborted tx or
        a concurrent open tx get (re-)logged by the next committer, so the
        committed log always covers every code it references."""
        for col, d in ti.dicts.items():
            n0 = ti.logged_dict_len.get(col, 0)
            if len(d) > n0:
                tx.ctx.dict_appends.extend(
                    (ti.tablet_id, col, code, d.decode_one(code))
                    for code in range(n0, len(d))
                )

    def _stage_all(self, tx: _OpenTx, ti: TableInfo,
                   muts: list[tuple[tuple, int, tuple | None]],
                   index_muts: list[tuple[int, tuple, int, tuple | None]] = (),
                   ) -> int:
        """Stage a fully-validated mutation batch (statement atomicity: no
        row reaches the memtable until the whole statement has resolved, so
        a failed statement inside an explicit tx leaves no partial writes).
        A WriteConflict during staging still aborts the whole tx — that is
        transaction, not statement, semantics (first-committer-wins).

        Rows route to their hash partition's tablet; a multi-partition
        statement stages on several LS leaders in one tx and commits with
        2PC — the parallel-DML shape (reference sql/engine/pdml). Index
        mutations ride the same tx on the first partition's log stream."""
        if muts or index_muts:
            from ..tx.tablelock import LockMode

            # implicit intention lock: DML conflicts with explicit
            # SHARE/EXCLUSIVE table locks held by other txs (tablelock)
            self.db.lock_mgr.lock(tx.ctx.tx_id, ti.tablet_id, LockMode.ROW_X)
            needed_ls = {ls for ls, _t, _k, _o, _v in muts}
            if index_muts:
                needed_ls.add(ti.ls_id)
            for ls in sorted(needed_ls):
                tx.ensure_leader(ls)
            for ls_id, tab_id, key, op, vals in muts:
                tx.svc.write(tx.ctx, ls_id, tab_id, key, op, vals)
            for tab_id, key, op, vals in index_muts:
                tx.svc.write(tx.ctx, ti.ls_id, tab_id, key, op, vals)
            self.db.writer_open(tx, ti)
        return len(muts)

    @staticmethod
    def _index_entry(ti: TableInfo, idx: IndexInfo, vals: tuple):
        """(index key, index row values) of a base row's index entry."""
        vmap = {f.name: vals[i] for i, f in enumerate(ti.schema.fields)}
        ivals = tuple(vmap[c] for c in idx.schema.names())
        ikey = tuple(int(vmap[c]) for c in idx.key_cols)
        return ikey, ivals

    def _check_unique(self, tx: _OpenTx, ti: TableInfo, idx: IndexInfo,
                      ikey: tuple, own_pk: tuple | None = None) -> None:
        """Reject a committed conflicting entry for a UNIQUE index key.
        Concurrent in-flight writers of the same key are handled by the
        memtable's first-committer-wins staging conflict."""
        rep = tx.svc.replicas[ti.ls_id]
        hit = rep.tablets[idx.tablet_id].get(
            ikey, tx.ctx.read_snapshot, tx_id=tx.ctx.tx_id
        )
        if hit is None:
            return
        if own_pk is not None:
            names = idx.schema.names()
            hit_pk = tuple(
                int(hit[1][names.index(k)]) for k in ti.key_cols
            )
            if hit_pk == own_pk:
                return
        raise SqlError(
            f"unique index {idx.name} violation on {ikey} in {ti.name}"
        )

    # ------------------------------------------------------- trigger firing
    _MAX_TRIGGER_DEPTH = 8

    def _fire_triggers(self, table: str, event: str, timing: str,
                       rows: list, tx: _OpenTx) -> None:
        """Fire matching row triggers for each (new_map, old_map) in
        `rows`. SET NEW.x mutates new_map in place (BEFORE); DML actions
        substitute NEW/OLD as literals and run through the normal handlers
        INSIDE the same transaction."""
        trigs = self.db.triggers_for(table, event, timing)
        if not trigs:
            return
        from ..sql.trigger import TriggerError, substitute

        depth = getattr(self, "_trigger_depth", 0)
        if depth >= self._MAX_TRIGGER_DEPTH:
            raise SqlError(
                f"trigger recursion deeper than {self._MAX_TRIGGER_DEPTH}")
        self._trigger_depth = depth + 1
        try:
            for new_map, old_map in rows:
                for _name, acts in trigs:
                    for act in acts:
                        if act[0] == "setnew":
                            _k, col, expr = act
                            if new_map is None or col not in new_map:
                                raise SqlError(
                                    f"trigger SET NEW.{col}: no such column")
                            new_map[col] = _eval_const(
                                substitute(expr, new_map, old_map))
                        else:
                            st2 = substitute(act[1], new_map, old_map)
                            if isinstance(st2, A.Insert):
                                self._insert(st2, tx)
                            elif isinstance(st2, A.Update):
                                self._update(st2, tx)
                            else:
                                self._delete(st2, tx)
        except TriggerError as e:
            raise SqlError(str(e)) from None
        finally:
            self._trigger_depth = depth

    def _has_triggers(self, table: str, event: str) -> bool:
        return any(
            s["table"] == table and s["event"] == event
            for s in self.db._trigger_specs.values()
        )

    def _insert(self, st: A.Insert, tx: _OpenTx) -> int:
        ti = self.db.tables.get(st.table)
        if ti is None:
            raise SqlError(f"no such table {st.table}")
        names = list(st.columns) if st.columns else ti.schema.names()
        for n in names:
            if n not in ti.schema:
                raise SqlError(f"unknown column {n}")
        missing = [n for n in ti.schema.names() if n not in names]
        if missing:
            raise SqlError(f"insert must provide all columns (missing {missing})")

        if st.select is not None:
            rs = self._select(st.select, _norm_stmt(f"$ins:{st.table}", st.select))
            src = [rs.columns[c] for c in rs.names]
            py_rows = list(zip(*src)) if src else []
        else:
            py_rows = [tuple(_eval_const(e) for e in row) for row in st.rows]

        fire = self._has_triggers(st.table, "insert")
        new_maps: list[dict] = []
        if fire:
            for row in py_rows:  # arity must hold BEFORE dict(zip) truncates
                if len(row) != len(names):
                    raise SqlError("value count does not match column count")
            new_maps = [dict(zip(names, row)) for row in py_rows]
            self._fire_triggers(
                st.table, "insert", "before",
                [(m, None) for m in new_maps], tx)
            py_rows = [tuple(m[n] for n in names) for m in new_maps]

        order = [names.index(n) for n in ti.schema.names()]
        staged: list[tuple[int, int, tuple, tuple]] = []
        seen: set[tuple] = set()
        for row in py_rows:
            if len(row) != len(names):
                raise SqlError("value count does not match column count")
            vals = tuple(
                _coerce(row[order[i]], f.dtype, ti.dicts.get(f.name), f.name)
                for i, f in enumerate(ti.schema.fields)
            )
            key = tuple(int(vals[ti.schema.index(k)]) for k in ti.key_cols)
            if key in seen:
                raise SqlError(f"duplicate primary key {key} in {st.table}")
            seen.add(key)
            ls_id, tab_id = ti.partition_for_key(key)
            staged.append((ls_id, tab_id, key, vals))
        needed_ls = sorted({ls for ls, _t, _k, _v in staged})
        if ti.indexes:
            needed_ls = sorted(set(needed_ls) | {ti.ls_id})
        for ls in needed_ls:
            tx.ensure_leader(ls)
        muts: list[tuple[int, int, tuple, int, tuple | None]] = []
        for ls_id, tab_id, key, vals in staged:
            rep = tx.svc.replicas[ls_id]
            if rep.tablets[tab_id].get(
                key, tx.ctx.read_snapshot, tx_id=tx.ctx.tx_id
            ) is not None:
                raise SqlError(f"duplicate primary key {key} in {st.table}")
            muts.append((ls_id, tab_id, key, OP_PUT, vals))
        index_muts: list[tuple[int, tuple, int, tuple | None]] = []
        for idx in ti.indexes.values():
            seen_i: set[tuple] = set()
            for _ls, _t, key, _op, vals in muts:
                ikey, ivals = self._index_entry(ti, idx, vals)
                if idx.unique:
                    if ikey in seen_i:
                        raise SqlError(
                            f"unique index {idx.name} violation on {ikey}"
                        )
                    seen_i.add(ikey)
                    self._check_unique(tx, ti, idx, ikey)
                index_muts.append((idx.tablet_id, ikey, OP_PUT, ivals))
        self._note_dict_appends(tx, ti)
        n = self._stage_all(tx, ti, muts, index_muts)
        if fire:
            self._fire_triggers(
                st.table, "insert", "after",
                [(m, None) for m in new_maps], tx)
        return n

    def _qualify(self, st, ti: TableInfo, cols: list[str],
                 set_exprs: tuple[tuple[str, A.Node], ...] = ()) -> ResultSet:
        """Run the qualification scan for UPDATE/DELETE through the engine:
        SELECT <cols> [, set-exprs] FROM t WHERE <pred> — the rebuild
        analog of the DML operator's child scan."""
        items = [A.SelectItem(A.Name((ti.name, c)), c) for c in cols]
        for i, (_col, e) in enumerate(set_exprs):
            items.append(A.SelectItem(e, f"$set{i}"))
        sel = A.Select(
            items=tuple(items),
            from_=(A.TableRef(ti.name),),
            where=st.where,
        )
        # keyed by the scan it runs: a constant assignment (`SET c='...'`,
        # evaluated on the host) is no part of it
        return self._select(sel, _norm_stmt(f"$dml:{ti.name}", sel))

    def _update(self, st: A.Update, tx: _OpenTx) -> int:
        ti = self.db.tables.get(st.table)
        if ti is None:
            raise SqlError(f"no such table {st.table}")
        for col, _ in st.assignments:
            if col not in ti.schema:
                raise SqlError(f"unknown column {col}")
            if col in ti.key_cols:
                raise SqlError(f"updating key column {col} not supported")
        # constant assignments evaluate on host (a bare string literal has
        # no device representation); computed ones ride the qualification
        # scan as extra projections
        const_sets: dict[str, object] = {}
        computed: list[tuple[str, A.Node]] = []
        for col, e in st.assignments:
            try:
                const_sets[col] = _eval_const(e)
            except SqlError:
                computed.append((col, e))
        rs = self._qualify(st, ti, ti.schema.names(), tuple(computed))
        set_cols = {col: rs.columns[f"$set{i}"]
                    for i, (col, _) in enumerate(computed)}
        if any(idx.unique for idx in ti.indexes.values()):
            # _check_unique below reads the local replica of the index LS;
            # become (or sync with) its leader first or a lagging follower
            # can miss committed entries and admit a UNIQUE violation
            # (mirrors _insert's ensure_leader-before-check ordering)
            tx.ensure_leader(ti.ls_id)
        muts: list[tuple[tuple, int, tuple | None]] = []
        index_muts: list[tuple[int, tuple, int, tuple | None]] = []
        # intra-statement duplicate guard (mirrors _insert's seen_i): two
        # rows updated to the same unique key both pass the committed-state
        # check, so the statement itself must catch the collision
        seen_i: dict[str, set[tuple]] = {
            idx.name: set() for idx in ti.indexes.values() if idx.unique
        }
        fire = self._has_triggers(st.table, "update")
        fired_rows: list[tuple] = []
        for r in range(rs.nrows):
            new_map = old_map = None
            if fire:
                old_map = {
                    f.name: rs.columns[f.name][r] for f in ti.schema.fields
                }
                new_map = {}
                for f in ti.schema.fields:
                    if f.name in const_sets:
                        new_map[f.name] = const_sets[f.name]
                    else:
                        src = set_cols.get(f.name)
                        new_map[f.name] = (
                            src[r] if src is not None else old_map[f.name]
                        )
                self._fire_triggers(
                    st.table, "update", "before", [(new_map, old_map)], tx)
                for k in ti.key_cols:
                    if new_map[k] != old_map[k]:
                        raise SqlError(
                            f"trigger changed key column {k}")
                fired_rows.append((new_map, old_map))
            vals = []
            old_vals = []
            for f in ti.schema.fields:
                ov = rs.columns[f.name][r]
                old_vals.append(_coerce(ov, f.dtype, ti.dicts.get(f.name), f.name))
                if new_map is not None:
                    v = new_map[f.name]
                elif f.name in const_sets:
                    v = const_sets[f.name]
                else:
                    src = set_cols.get(f.name)
                    v = src[r] if src is not None else ov
                vals.append(_coerce(v, f.dtype, ti.dicts.get(f.name), f.name))
            vals = tuple(vals)
            old_vals = tuple(old_vals)
            key = tuple(int(vals[ti.schema.index(k)]) for k in ti.key_cols)
            ls_id, tab_id = ti.partition_for_key(key)
            muts.append((ls_id, tab_id, key, OP_PUT, vals))
            for idx in ti.indexes.values():
                old_ik, _ = self._index_entry(ti, idx, old_vals)
                new_ik, new_iv = self._index_entry(ti, idx, vals)
                if idx.unique:
                    # an unchanged entry still occupies its key within this
                    # statement; record it so another row can't move onto it
                    if new_ik in seen_i[idx.name]:
                        raise SqlError(
                            f"unique index {idx.name} violation on {new_ik}"
                        )
                    seen_i[idx.name].add(new_ik)
                if old_ik == new_ik:
                    continue  # entry content (key cols + pk) unchanged
                if idx.unique:
                    self._check_unique(tx, ti, idx, new_ik, own_pk=key)
                index_muts.append((idx.tablet_id, old_ik, OP_DELETE, None))
                index_muts.append((idx.tablet_id, new_ik, OP_PUT, new_iv))
        self._note_dict_appends(tx, ti)
        n = self._stage_all(tx, ti, muts, index_muts)
        if fire:
            self._fire_triggers(st.table, "update", "after", fired_rows, tx)
        return n

    def _delete(self, st: A.Delete, tx: _OpenTx) -> int:
        ti = self.db.tables.get(st.table)
        if ti is None:
            raise SqlError(f"no such table {st.table}")
        # the qualification scan must surface every indexed column so the
        # old index entries can be tombstoned alongside the base rows
        # (plus the whole row when delete triggers need OLD.*)
        fire = self._has_triggers(st.table, "delete")
        cols = list(dict.fromkeys(
            list(ti.key_cols)
            + [c for idx in ti.indexes.values() for c in idx.key_cols]
            + (list(ti.schema.names()) if fire else [])
        ))
        rs = self._qualify(st, ti, cols)
        fired_rows: list[tuple] = []
        muts: list[tuple[tuple, int, tuple | None]] = []
        index_muts: list[tuple[int, tuple, int, tuple | None]] = []
        for r in range(rs.nrows):
            if fire:
                old_map = {c: rs.columns[c][r] for c in cols}
                self._fire_triggers(
                    st.table, "delete", "before", [(None, old_map)], tx)
                fired_rows.append((None, old_map))
            row = {
                c: _coerce(rs.columns[c][r], ti.schema[c], ti.dicts.get(c), c)
                for c in cols
            }
            key = tuple(int(row[k]) for k in ti.key_cols)
            ls_id, tab_id = ti.partition_for_key(key)
            muts.append((ls_id, tab_id, key, OP_DELETE, None))
            for idx in ti.indexes.values():
                ikey = tuple(int(row[c]) for c in idx.key_cols)
                index_muts.append((idx.tablet_id, ikey, OP_DELETE, None))
        n = self._stage_all(tx, ti, muts, index_muts)
        if fire:
            self._fire_triggers(st.table, "delete", "after", fired_rows, tx)
        return n


# ---- helpers ---------------------------------------------------------------

_LIT_MASK_RE = None


def _norm_stmt(tag: str, st) -> str:
    """Literal-normalized cache key for a generated DML qualification scan
    (`st` the SELECT it runs).

    Numeric/date literals become runtime parameters during parameterize(),
    so masking them here lets point UPDATE/DELETE loops share one compiled
    plan (string literals stay: they are baked and already key material)."""
    global _LIT_MASK_RE
    if _LIT_MASK_RE is None:
        import re

        _LIT_MASK_RE = re.compile(r"(NumberLit|DateLit)\(value='[^']*'\)")
    return tag + ":" + _LIT_MASK_RE.sub(r"\1(value='?')", repr(st))


def apply_dict_appends(by_tab: dict, dict_appends) -> None:
    """Re-apply logged dictionary growth onto TableInfos (idempotent:
    codes are dense and append-ordered). Shared by live record
    observation (_on_applied_record) and the standby tail (ha/standby)."""
    for tab_id, col, code, s in dict_appends:
        ti = by_tab.get(tab_id)
        if ti is None:
            continue
        d = ti.dicts.get(col)
        if d is None:
            continue
        if code == len(d):
            d.encode_one(s)
        ti.logged_dict_len[col] = max(
            ti.logged_dict_len.get(col, 0), code + 1
        )


def _dense_vectors(schema: Schema, data: dict) -> None:
    """Every consumer of a VECTOR column (IVF build, route costing, H2D
    upload, mesh sharding) wants the dense (n, d) float32 form, which a
    tablet scan gives (sstable blocks and memtable rows alike); a column
    of cells in any other form is normalized in place, once."""
    for f in schema.fields:
        if f.dtype.kind is TypeKind.VECTOR:
            a = data[f.name]
            dim = int(f.dtype.precision)
            if a.ndim == 2 and a.dtype == np.float32:
                continue
            data[f.name] = (
                np.asarray(a.tolist(), dtype=np.float32).reshape(len(a), dim)
                if len(a) else np.zeros((0, dim), np.float32))


_INT64 = np.iinfo(np.int64)
# a comparison as `<column> op <literal>`: the op with its sides swapped
_FLIPPED = {"<": ">", "<=": ">=", ">": "<", ">=": "<="}


def _pk_bounds(c: A.Node, pk: str, alias: str):
    """(lo, hi) in integers, either None where open, that conjunct `c`
    puts on integer column `pk` when it is a BETWEEN or a comparison with
    literal numbers; None where it bounds nothing. A bound that is not a
    whole number widens to the next one outward: the statement's own WHERE
    still decides each row."""
    import math

    def col(n):
        return (isinstance(n, A.Name) and n.parts[-1] == pk
                and (len(n.parts) == 1 or n.parts == (alias, pk)))

    def num(n):
        try:
            v = _eval_const(n)
        except (SqlError, TypeError, ArithmeticError):
            return None
        if isinstance(v, bool) or not isinstance(v, (int, float)):
            return None
        return v if math.isfinite(v) else None

    if isinstance(c, A.BetweenOp) and not c.negated and col(c.expr):
        a, b = num(c.low), num(c.high)
        if a is None or b is None:
            return None
        return math.floor(a), math.ceil(b)
    if not (isinstance(c, A.BinOp) and c.op in _FLIPPED):
        return None
    op, lhs, rhs = c.op, c.left, c.right
    if not col(lhs):
        op, lhs, rhs = _FLIPPED[op], rhs, lhs
    if not col(lhs):
        return None
    v = num(rhs)
    if v is None:
        return None
    if isinstance(v, int):
        bound = v + 1 if op == ">" else v - 1 if op == "<" else v
    else:
        bound = math.floor(v) if op[0] == ">" else math.ceil(v)
    return (bound, None) if op[0] == ">" else (None, bound)


def _eval_const(node: A.Node):
    """Evaluate a literal/constant VALUES expression on the host."""
    if isinstance(node, A.NumberLit):
        t = node.value
        return float(t) if ("." in t or "e" in t or "E" in t) else int(t)
    if isinstance(node, A.StringLit):
        return node.value
    if isinstance(node, A.DateLit):
        return node.value
    if isinstance(node, A.Name) and node.parts == ("null",):
        raise SqlError("NULL values not supported in DML yet")
    if isinstance(node, A.UnaryOp) and node.op == "-":
        return -_eval_const(node.operand)
    if isinstance(node, A.BinOp):
        l, r = _eval_const(node.left), _eval_const(node.right)
        if node.op == "+":
            return l + r
        if node.op == "-":
            return l - r
        if node.op == "*":
            return l * r
        if node.op == "/":
            if r == 0:
                raise SqlError("division by zero in VALUES expression")
            return l / r
    raise SqlError(f"unsupported VALUES expression {node!r}")


def _coerce(v, dt: DataType, d: Dictionary | None, col: str):
    """Host value -> storage representation for one column."""
    if v is None:
        raise SqlError(f"NULL for column {col} not supported in DML yet")
    if dt.kind is TypeKind.VARCHAR:
        assert d is not None
        return d.encode_one(str(v))
    if dt.kind is TypeKind.DATE:
        if isinstance(v, str):
            return int(np.datetime64(v, "D").astype(np.int64))
        return int(v)
    if dt.is_decimal:
        return int(round(float(v) * dt.decimal_factor))
    if dt.is_integer:
        iv = int(v)
        if iv != v:
            raise SqlError(f"non-integer value {v!r} for column {col}")
        return iv
    if dt.is_float:
        return float(v)
    if dt.kind is TypeKind.VECTOR:
        # '[f, f, ...]' literal -> (d,) float32 tuple (hashable so the
        # MVCC row path treats it like any other cell value)
        from ..expr.compile import bind_value

        return tuple(float(x) for x in bind_value(v, dt))
    raise SqlError(f"unsupported column type {dt} for DML")


def _flashback_refs(node, out=None) -> list:
    """(name, snapshot) pairs of AS OF SNAPSHOT references in the AST."""
    import dataclasses

    if out is None:
        out = []
    if isinstance(node, A.TableRef) and node.snapshot is not None:
        if (node.name, node.snapshot) not in out:
            out.append((node.name, node.snapshot))
    if dataclasses.is_dataclass(node) and not isinstance(node, type):
        for f in dataclasses.fields(node):
            _flashback_refs(getattr(node, f.name), out)
    elif isinstance(node, (tuple, list)):
        for x in node:
            _flashback_refs(x, out)
    return out


def _tables_in_ast(node) -> set[str]:
    """All table names referenced anywhere in a statement AST."""
    import dataclasses

    out: set[str] = set()

    def walk(n):
        if isinstance(n, A.TableRef):
            out.add(n.name)
        if dataclasses.is_dataclass(n) and not isinstance(n, type):
            for f in dataclasses.fields(n):
                walk(getattr(n, f.name))
        elif isinstance(n, (tuple, list)):
            for x in n:
                walk(x)

    walk(node)
    return out
