"""Session facade: SQL text in, result rows out, with a plan cache.

Reference surface: ObSql::stmt_query + ObPlanCache
(src/sql/ob_sql.cpp:153, src/sql/plan_cache/ob_plan_cache.h:227). The cache
key is the literal-normalized SQL text (fast-parser analog,
sql/parser.py normalize_for_cache); a hit reuses the compiled jitted
program — the expensive artifact on TPU is the XLA executable, so the plan
cache IS the compile cache.
"""

from __future__ import annotations

import hashlib
import time
from dataclasses import dataclass

import numpy as np

from ..core.column import batch_to_host
from ..core.table import Table
from ..share import gap_ledger as _gap
from ..sql import parser as P
from ..sql.plan_cache import (
    CacheEntry,
    FastEntry,
    PlanCache,
    build_slot_map,
    parameterize,
    plan_fingerprint,
)
from ..sql.planner import Planner
from .executor import Executor, ann_adds


@dataclass
class ResultSet:
    names: tuple[str, ...]
    columns: dict[str, object]  # name -> np.ndarray | list
    affected: int = 0  # DML-affected row count (0 for queries)
    plan_cache_hit: bool = False  # this statement reused a compiled plan
    fast_path_hit: bool = False  # served by the text-keyed fast tier
    # the record of the execution this is the result of; all None where
    # the statement never reached the engine (pure DDL, SHOW). A result
    # built around another (a DML statement's over its qualification
    # scan's, EXPLAIN ANALYZE's over the analyzed statement's) takes the
    # inner one's `record()`.
    profile: object = None  # server/diag.QueryProfile (None: profiling off)
    phases: dict | None = None  # the phase walls the host-tax carve reads
    plan: object = None  # logical plan (flight-recorder bundles)
    op_profile: dict | None = None  # per-operator, of a profiled run

    @property
    def nrows(self) -> int:
        if not self.names:
            return 0
        c = self.columns[self.names[0]]
        return len(c)

    def rows(self, limit: int | None = None) -> list[tuple]:
        cols = [self.columns[n] for n in self.names]
        out = list(zip(*cols)) if cols else []
        return out[:limit] if limit is not None else out

    def record(self) -> dict:
        return {"profile": self.profile, "phases": self.phases,
                "plan": self.plan, "op_profile": self.op_profile}


class LazyResultSet:
    """Device-resident ResultSet: same read surface as ResultSet, but
    column data stays on the TPU behind a DeviceResult cursor until a
    host access touches it. `nrows` is the async-dispatch sync point
    (overflow redrive happens there): it reads what the cursor started
    copying at dispatch, the whole frame when it is small, two scalars
    when it is not. Over a large frame `.columns` fetches everything
    once; `column(name)` transfers only that column; `rows(limit=k)`
    transfers only k compacted rows per column. The execution's record
    is the cursor's (later fetches keep adding to it in place)."""

    record = ResultSet.record

    def __init__(self, names: tuple[str, ...], cursor, affected: int = 0,
                 plan_cache_hit: bool = False, fast_path_hit: bool = False,
                 plan=None):
        self.names = names
        self.affected = affected
        self.plan_cache_hit = plan_cache_hit
        self.fast_path_hit = fast_path_hit
        self.plan = plan
        self._cursor = cursor
        self._columns_cache: dict | None = None
        self._nrows: int | None = None

    @property
    def profile(self):
        return self._cursor.profile

    @property
    def phases(self):
        return self._cursor.phases

    @property
    def op_profile(self):
        return self._cursor.op_profile

    @property
    def nrows(self) -> int:
        # memoized: the completion path reads nrows several times per
        # statement (engine sync force, audit record, summary fold) and
        # each uncached read walks two property hops into the cursor
        n = self._nrows
        if n is None:
            n = self._nrows = self._cursor.nrows if self.names else 0
        return n

    @property
    def columns(self) -> dict[str, object]:
        # memoized: callers index rs.columns[...] in per-row loops, and
        # host_rows decode must not re-run per access
        if self._columns_cache is None:
            host = self._cursor.fetch_columns()
            self._columns_cache = {n: host[n] for n in self.names}
        return self._columns_cache

    def column(self, name: str):
        """One column's host values — transfers only this column (plus
        the shared sel mask once)."""
        return self._cursor.fetch_columns((name,))[name]

    def rows(self, limit: int | None = None) -> list[tuple]:
        if limit is not None:
            host = self._cursor.fetch_head(limit)
        else:
            host = self._cursor.fetch_columns()
        cols = [host[n] for n in self.names]
        return list(zip(*cols)) if cols else []


# fast_execute's "caller did not probe the result cache" marker (None is
# a real probe outcome: probed, uncacheable)
_RC_UNSET = object()


@dataclass
class _FastHit:
    """A resolved fast-tier lookup: the text entry, the re-bound slot
    values for THIS statement's literals, and the logical entry holding
    the compiled executable."""

    text_key: str
    fe: FastEntry
    values: list
    entry: CacheEntry
    # logical cache key of the entry (embeds schema versions via
    # key_extra) — the result cache reuses it as its identity base
    key: tuple | None = None


class Session:
    def __init__(self, catalog: dict[str, Table], unique_keys=None,
                 plan_cache: PlanCache | None = None, key_extra_fn=None,
                 cache_enabled_fn=None, plan_monitor=None, views=None,
                 metrics=None, tracer=None, profile_enabled_fn=None):
        self.catalog = catalog
        from ..share.stats import StatsManager

        self.stats = StatsManager(catalog)
        self.planner = Planner(
            catalog, stats=self.stats, unique_keys=unique_keys, views=views
        )
        self.executor = Executor(
            catalog, unique_keys=unique_keys, stats=self.stats
        )
        # shareable across sessions (the reference's cache is per-tenant,
        # not per-session: ob_plan_cache.h:227)
        self.plan_cache = plan_cache if plan_cache is not None else PlanCache()
        # hook: extra cache-key material per referenced table set (the
        # DML-backed catalog keys entries on table schema versions)
        self.key_extra_fn = key_extra_fn
        # hook: ob_enable_plan_cache (a disabled cache compiles every time)
        self.cache_enabled_fn = cache_enabled_fn
        # hook: server/diag.PlanMonitor (per-plan compile/exec stats)
        self.plan_monitor = plan_monitor
        # hook: share/metrics.MetricsRegistry (phase histograms + counters)
        self.metrics = metrics
        # hook: server/diag.Tracer — PX executions stitch per-DFO worker
        # spans into the active statement's trace through it
        self.tracer = tracer
        # hook: config enable_query_profile (None = always profile)
        self.profile_enabled_fn = profile_enabled_fn
        # hook: server/workload.TableAccessStats — per-execution fold of
        # the prepared plan's precomputed table/column access profile
        self.access = None
        # hook: share/timeline.ServingTimeline — per-dispatch device-busy
        # and compile-interference feed (the server wires it)
        self.timeline = None
        # hook: engine/plan_profile.PlanProfiler — sampled per-operator
        # profiled execution (the server wires it and sets the pending
        # statement digest before dispatch)
        self.plan_profiler = None
        # hook: engine/result_cache.ResultCache — device-resident narrowed
        # results keyed (logical key, bound literals, snapshot watermark);
        # a hit skips dispatch entirely
        self.result_cache = None
        # hook: tables -> snapshot watermark tuple (the server supplies
        # per-table committed data versions; staleness = key mismatch)
        self.result_watermark_fn = None
        # hook: key material of an exported artifact (key_extra_fn's
        # when unset; the server adds the dictionary versions)
        self.artifact_extra_fn = None

    def materialize(self, text: str, name: str) -> Table:
        """Run a SELECT and materialize its result as a storage-domain
        Table (exact round-trip: decimals stay scaled ints, dates stay
        day numbers, NULLs keep their validity masks) — the engine half
        of materialized views."""
        from ..core.column import (
            batch_rows_storage,
            batch_valid_storage,
            renamed_storage_schema,
        )
        from ..sql.logical import output_schema
        from .recursive import recursive_cte_of, run_recursive

        ast = P.parse(text)
        if getattr(ast, "ctes", None) and recursive_cte_of(ast) is not None:
            batch, out_names = run_recursive(self, ast)
            names = list(out_names)
            schema_src = batch.schema
        else:
            planned = self.planner.plan(ast)
            schema_src = output_schema(planned.plan)
            batch = self.executor.execute(planned.plan)
            names = list(planned.output_names)
        valid = batch_valid_storage(batch, names)
        schema = renamed_storage_schema(schema_src, names)
        if valid:
            # a validity mask forces the field nullable, or make_batch
            # would drop the mask on the next read
            from dataclasses import replace as _rp

            from ..core.dtypes import Field as _F, Schema as _S

            schema = _S(tuple(
                _F(f.name, _rp(f.dtype, nullable=True))
                if f.name in valid else f
                for f in schema.fields
            ))
        return Table(
            name,
            schema,
            batch_rows_storage(batch, names),
            {n: batch.dicts[n] for n in names if n in batch.dicts},
            valid,
        )

    def sql(self, text: str) -> ResultSet:
        # fast-parser front end: one tokenize pass both normalizes the
        # text-tier key and extracts the literal tokens. A warm repeat
        # skips parse + resolve + rewrite + plan + parameterize entirely
        # and goes straight to binding the cached executable.
        t0 = time.perf_counter()
        fkey, params, kinds = P.fast_normalize(text)
        use_cache = self.cache_enabled_fn() if self.cache_enabled_fn else True
        if use_cache:
            hit = self.fast_lookup(fkey, params)
            if hit is not None:
                return self.fast_execute(
                    hit, fastparse_s=time.perf_counter() - t0)
        fastparse_s = time.perf_counter() - t0
        # the plain plan-cache key is the fast key with kind markers
        # collapsed (the tokenizer never emits a bare '?')
        norm_key = fkey.replace("?n", "?").replace("?s", "?")
        ast = P.parse(text)
        return self.run_ast(
            ast, norm_key,
            fast_reg=(fkey, params, kinds) if use_cache else None,
            fastparse_s=fastparse_s,
        )

    def fast_lookup(self, text_key: str, params: tuple, fe=None,
                    defer_adds=None):
        """Text-tier lookup + literal re-bind + logical-tier fetch.
        Returns a _FastHit ready for fast_execute, or None (counted as a
        fast miss) when any stage rejects: unknown text, a baked token
        changed, a converter refused the new literal (dtype widening), or
        the logical entry is gone (evicted / flushed / schema version
        moved the key_extra) — that last case also drops the text entry.
        Callers that already peeked the text tier (the server fast path
        peeks to run privilege checks first) pass the FastEntry via `fe`
        so the lookup isn't paid twice per statement; `defer_adds` is
        forwarded to fast_hit_get (statement-end counter batching)."""
        pc = self.plan_cache
        if fe is None:
            fe = pc.fast_peek(text_key)
            if fe is None:
                pc.note_fast_miss()
                return None
        vals = fe.bind_tokens(params)
        if vals is None:
            pc.note_fast_miss()
            return None
        extra = (self.key_extra_fn(fe.tables)
                 if self.key_extra_fn is not None else ())
        key = (id(self.catalog), fe.norm_key, fe.sig, fe.baked,
               fe.fingerprint, extra)
        entry = pc.fast_hit_get(key, defer_adds=defer_adds)
        if entry is None:
            pc.fast_invalidate(text_key)
            pc.note_fast_miss()
            return None
        return _FastHit(text_key, fe, vals, entry, key)

    def result_cache_key(self, hit: "_FastHit"):
        """Result-cache identity for a fast hit, or None when the
        statement is uncacheable (not a SELECT, cache off, unhashable
        literal values). The key embeds the logical entry key (schema
        versions ride key_extra) plus the bound literals and the
        referenced tables' snapshot watermark — any committed DML or
        schema bump changes the key instead of serving a stale frame."""
        rc = self.result_cache
        if rc is None or not rc.enabled() or hit.key is None:
            return None
        if getattr(hit.fe, "stmt_type", None) != "Select":
            return None
        wm = (self.result_watermark_fn(hit.fe.tables)
              if self.result_watermark_fn is not None else ())
        # long string literals (query embeddings — a 128-d vector is a
        # ~1.4KB bracket text) key by digest: an exact-text collision is
        # a SHA-256 collision, and the key stays a few dozen bytes
        vals = tuple(
            hashlib.sha256(v.encode()).digest()
            if type(v) is str and len(v) > 256 else v
            for v in hit.values
        )
        return (hit.key, vals, wm)

    def result_cache_probe(self, hit: "_FastHit", rc_key,
                           fastparse_s: float = 0.0):
        """Serve a fast hit from the device-resident result cache, or
        None on miss. A hit skips bind + dispatch + sync entirely and
        still carries phases and a profile so completion accounting
        (audit, summary, host-tax ledger) sees a normal statement."""
        rc = self.result_cache
        if rc is None or rc_key is None:
            return None
        ce = rc.get(rc_key)
        if ce is None:
            return None
        prepared = hit.entry.prepared
        rs = ResultSet(ce.names, ce.copy_columns(), plan_cache_hit=True,
                       fast_path_hit=True, plan=prepared.plan)
        rs.phases = {
            "plan_s": 0.0, "compile_s": 0.0, "fastparse_s": fastparse_s,
            "bind_s": 0.0, "dispatch_s": 0.0, "fetch_s": 0.0,
            "exec_s": 0.0, "rows": rs.nrows, "cache_hit": True,
            "fast_hit": True, "result_cache": True,
        }
        if self.profile_enabled_fn is None or self.profile_enabled_fn():
            from ..server.diag import QueryProfile

            rs.profile = QueryProfile(
                compile_hit=True, fastparse_s=fastparse_s,
                fast_path_hit=True)
        m = self.metrics
        if m is not None and m.enabled:
            m.add("result rows returned", rs.nrows)
            vts = getattr(prepared.params, "vector_topns", None)
            if vts:
                # an ANN statement served straight from the device-
                # resident cache: the whole probe+re-rank was skipped
                m.add("ann cache hits")
        # a cached serve is still logically a read of its tables: fold
        # the plan's access profile so advisor heat (projection
        # keep/drop, index recommendations) doesn't see a dashboard
        # table go cold the moment its statements start hitting
        self._fold_access(prepared)
        return rs

    def _fold_access(self, prepared) -> None:
        """Access heat of one execution: the plan's profile resolves to
        live stat objects once per (prepared, epoch); every execution
        after that folds through direct references (no dict lookups)."""
        acc = self.access
        if acc is None or not acc.enabled:
            return
        memo = prepared._access_memo
        if memo is None or memo[0] != acc.epoch:
            memo = prepared._access_memo = (
                acc.epoch, acc.resolve(prepared.access_profile))
        if memo[1]:
            acc.fold_resolved(memo[1])

    def _result_cache_put(self, rc_key, hit: "_FastHit", rs) -> None:
        """Admit a freshly executed fused result: only clean narrowed
        frames small enough for the entry cap — the cursor reference
        pins the device-resident frame (that is the 'device cache' half;
        the decoded host columns make hits free of fold work too)."""
        rc = self.result_cache
        cur = getattr(rs, "_cursor", None)
        if rc is None or cur is None or not cur.narrowed:
            return
        nbytes = sum(
            int(getattr(a, "nbytes", 0))
            for d in (cur._hcols, cur._hvalid) for a in d.values()
        ) + int(getattr(cur._hsel, "nbytes", 0))
        if nbytes > rc.entry_limit:
            return
        try:
            cols = rs.columns
        except Exception:
            return
        rc.put(rc_key, rs.names, {n: cols[n] for n in rs.names}, nbytes,
               getattr(hit.fe, "tables", ()), cursor=cur)

    def fast_execute(self, hit: "_FastHit", fastparse_s: float = 0.0,
                     rc_key=_RC_UNSET) -> ResultSet:
        """Execute a fast-tier hit: bind + dispatch the cached executable.
        Any failure drops the text entry (the next occurrence re-registers
        through the full path) and re-raises for the retry controller.
        `rc_key` carries a result-cache identity the caller already
        probed (the server fast path probes before the batcher bracket);
        left unset, this probes/admits the cache itself."""
        profiling = (self.profile_enabled_fn() if self.profile_enabled_fn
                     else True)
        if rc_key is _RC_UNSET:
            rc_key = self.result_cache_key(hit)
            rs = self.result_cache_probe(hit, rc_key, fastparse_s)
            if rs is not None:
                return rs
        h2d0 = self.executor.h2d_bytes if profiling else 0
        try:
            rs = self._execute_entry(
                hit.entry, hit.values, ex=self.executor, was_hit=True,
                fast=True, plan_s=0.0, compile_s=0.0,
                fastparse_s=fastparse_s, profiling=profiling, h2d0=h2d0,
            )
        except Exception:
            self.plan_cache.fast_invalidate(hit.text_key)
            raise
        if rc_key is not None:
            try:
                self._result_cache_put(rc_key, hit, rs)
            except Exception:
                pass  # cache admission must never fail the statement
        return rs

    def cached_entry(self, text: str):
        """(CacheEntry, bound qparams) for a statement already run through
        sql() — the compiled-executable surface consumers (bench timing
        loops) use to re-run the exact cached artifact without a second
        trace/compile. Returns (None, None) on a cache miss."""
        norm_key, _ = P.normalize_for_cache(text)
        planned = self.planner.plan(P.parse(text))
        pz = parameterize(planned.plan)
        key = self._cache_key(norm_key, pz)
        entry = self.plan_cache.get(key)
        if entry is None:
            return None, None
        # the SAME dispatch form sql() used (packed int64 vector): a
        # tuple here would change the jit signature and silently
        # re-trace + re-compile the plan (review finding)
        return entry, entry.prepared.bind(pz.values, entry.dtypes)

    def prepare_shapes(self, ast, norm_key: str) -> bool:
        """Trace and compile the programs the statement's cached plan runs
        over the inputs the catalog resolves to now (a transaction's
        scoped view): its dispatch and, where the plan profiles, the
        stages of a sampled run. What they return is dropped. False where
        no plan is cached for the statement yet."""
        from . import plan_profile as _PP

        planned = self.planner.plan(ast)
        pz = parameterize(planned.plan)
        entry = self.plan_cache.get(self._cache_key(norm_key, pz))
        if entry is None:
            return False
        prepared = entry.prepared
        qparams = prepared.bind(pz.values, entry.dtypes)
        _ = prepared.dispatch(qparams).nrows
        pp = self.plan_profiler
        if pp is not None and pp.enabled and _PP.profile_eligible(prepared):
            import jax

            jax.block_until_ready(_PP.run_profiled(prepared, qparams)[0])
        return True

    def _cache_key(self, norm_key: str, pz, executor=None) -> tuple:
        return self._key_parts(norm_key, pz, executor)[0]

    def _key_parts(self, norm_key: str, pz, executor=None
                   ) -> tuple[tuple, tuple, str]:
        """(logical cache key, referenced table names, plan fingerprint).
        The tables and fingerprint also seed fast-tier registration — a
        fast hit rebuilds this key from them without planning."""
        tables = tuple(sorted(
            {s.table for s in self.executor._collect_scans(pz.plan)}
        ))
        extra = self.key_extra_fn(tables) if self.key_extra_fn is not None \
            else ()
        # an executor override (PX routing) compiles a DIFFERENT program
        # for the same text: the entry must not collide with single-chip
        if executor is not None and executor is not self.executor:
            extra = (*extra, "#exec", id(executor))
        fp = plan_fingerprint(pz.plan)
        # id(catalog) scopes entries to one table set (cache sharing is per
        # tenant = per catalog; entries pin their executor -> catalog, so the
        # id cannot be recycled while the entry lives); the plan fingerprint
        # catches literals consumed at plan time (ORDER BY ordinals etc.)
        key = (id(self.catalog), norm_key, pz.sig, pz.baked, fp, extra)
        return key, tables, fp

    def _artifact_key(self, norm_key: str, pz, fp: str, tables,
                      executor=None) -> tuple | None:
        """Restart-stable identity of a compiled artifact: the logical
        cache key minus process-local ids — id(catalog) drops (the store
        is scoped per database), and a PX override contributes its shard
        count instead of its executor's object id. `extra` is
        `artifact_extra_fn`'s where set: the schema and dictionary
        versions an exported executable was made under."""
        fn = self.artifact_extra_fn or self.key_extra_fn
        extra = fn(tables) if fn is not None else ()
        tag: tuple = ()
        if executor is not None and executor is not self.executor:
            nsh = getattr(executor, "nsh", 0)
            if not nsh:
                return None  # unknown override: don't risk a collision
            # full mesh signature, not just the device count: an SPMD
            # program's shardings are lowered against axis sizes + names,
            # and 8x1 vs 4x2 (or renamed axes) must never share artifacts
            sig = getattr(executor, "mesh_sig", ()) or ()
            tag = ("#px", int(nsh), *sig)
        return (norm_key, pz.sig, pz.baked, fp, extra, tag)

    def _emit_px_spans(self, prepared, start: float, end: float) -> None:
        """Per-DFO / per-shard worker spans for a PX execution, stitched
        under the active statement span. Works for CACHED plans too: the
        exchange layout rides the prepared plan from compile time."""
        tr = self.tracer
        exchanges = prepared.px_exchanges
        if tr is None or not tr.enabled or exchanges is None:
            return
        ctx = tr.current_ctx()
        nsh = prepared.px_nsh
        coord = tr.record_span("px coordinator", ctx, start, end, dop=nsh)
        cctx = (coord.trace_id, coord.span_id) if coord is not None else ctx
        if exchanges:
            for i, (kind, ncols, cap) in enumerate(exchanges):
                for node in range(nsh):
                    tr.record_span(
                        "px worker", cctx, start, end, node=node, dfo=i,
                        exchange=kind, lane_cap=cap, cols=ncols,
                    )
        else:
            # exchange-free plan (fully local per shard): one worker span
            # per mesh device so the trace still shows the fan-out
            for node in range(nsh):
                tr.record_span("px worker", cctx, start, end, node=node,
                               dfo=0)

    def run_ast(self, ast, norm_key: str, use_cache: bool | None = None,
                executor=None, fast_reg=None,
                fastparse_s: float = 0.0) -> ResultSet:
        """Plan + execute an already-parsed SELECT under the plan cache.

        Shared by text queries and internal consumers (the DML layer's
        UPDATE/DELETE qualification scans, virtual-table queries).
        use_cache=False bypasses the plan cache entirely (virtual-table
        statements: their per-materialization dictionaries make entries
        never reusable, and caching them would evict user plans).
        `executor` overrides the compiling/executing backend for this
        statement (PX routing: the server layer passes its PxExecutor when
        the session's DOP variable asks for distributed execution).
        `fast_reg` = (text_key, raw_params, kinds) from fast_normalize
        registers this statement in the text-keyed fast tier on success —
        callers pass it only for plain cacheable single-chip statements."""
        if getattr(ast, "ctes", None):
            from .recursive import recursive_cte_of, run_recursive

            if recursive_cte_of(ast) is not None:
                out_batch, names = run_recursive(self, ast)
                host = batch_to_host(out_batch)
                return ResultSet(tuple(names), {n: host[n] for n in names})
        # JSON_OBJECT/JSON_ARRAY select items: device executes the argument
        # columns, host formats the JSON text at result assembly
        # (sql/json_host.py); the spec joins the cache key — same
        # normalized text with different constructor literals must not
        # share an entry
        from ..sql.json_host import split_host_json

        try:
            ast, jspecs, jhidden = split_host_json(ast)
        except ValueError as err:
            from ..sql.logical import ResolveError

            raise ResolveError(str(err)) from None
        if jspecs:
            norm_key = f"{norm_key}|jh:{jspecs!r}"
        ex = executor if executor is not None else self.executor
        # the statement's ledger while a profiler session annotates it
        # (share/gap_ledger.py): each span timed below is also a leaf
        # ob:<phase> in the profiler's trace
        tl = _gap.tracing()
        if tl is not None:
            tl.leaf("plan compile")
        t0 = time.perf_counter()
        planned = self.planner.plan(ast)
        pz = parameterize(planned.plan)
        key, tables, fp = self._key_parts(norm_key, pz, executor)
        plan_s = time.perf_counter() - t0
        if tl is not None:
            tl.leaf_end()
        if use_cache is None:
            use_cache = self.cache_enabled_fn() if self.cache_enabled_fn else True
        entry = self.plan_cache.get(key) if use_cache else None
        was_hit = entry is not None
        profiling = (self.profile_enabled_fn() if self.profile_enabled_fn
                     else True)
        h2d0 = ex.h2d_bytes if profiling else 0
        compile_s = 0.0
        # on-disk artifact tier: a logical miss tries to hydrate the
        # exported executable before paying a compile. JSON-split
        # statements stay memory-only (their host formatting spec rides
        # the entry, not the executable).
        art_store = getattr(self.plan_cache, "artifact_store", None)
        art_key = None
        if art_store is not None and use_cache and not jspecs:
            art_key = self._artifact_key(norm_key, pz, fp, tables, executor)
        hydrated = False
        if entry is None and art_key is not None and art_store.readable:
            if tl is not None:
                tl.leaf("plan compile")
            t0 = time.perf_counter()
            got = art_store.hydrate(
                art_store.key_id(art_key), ex,
                key_extra_fn=self.artifact_extra_fn or self.key_extra_fn)
            if tl is not None:
                tl.leaf_end()
            if got is not None:
                _meta, prepared = got
                compile_s = time.perf_counter() - t0
                entry = CacheEntry(prepared, planned.output_names, pz.dtypes,
                                   json_specs=jspecs, json_hidden=jhidden)
                if self.plan_monitor is not None and self.plan_monitor.enabled:
                    entry.monitor = self.plan_monitor.register(
                        norm_key, compile_s)
                self.plan_cache.put(key, entry)
                hydrated = True
        if entry is None:
            if tl is not None:
                tl.leaf("plan compile")
            t0 = time.perf_counter()
            prepared = ex.prepare(pz.plan)
            compile_s = time.perf_counter() - t0
            if tl is not None:
                tl.leaf_end()
            entry = CacheEntry(prepared, planned.output_names, pz.dtypes,
                               json_specs=jspecs, json_hidden=jhidden)
            if self.plan_monitor is not None and self.plan_monitor.enabled:
                entry.monitor = self.plan_monitor.register(norm_key, compile_s)
            if use_cache:
                self.plan_cache.put(key, entry)
        rs = self._execute_entry(
            entry, pz.values, ex=ex, was_hit=was_hit, fast=False,
            plan_s=plan_s, compile_s=compile_s, fastparse_s=fastparse_s,
            profiling=profiling, h2d0=h2d0,
        )
        # text-tier registration AFTER a successful execution: one entry
        # per kind-marked normalized text, carrying the logical key parts
        # + token->slot accounting. PX overrides, JSON-split statements
        # and cache-bypassed (virtual-table) statements never register.
        if fast_reg is not None and use_cache and executor is None \
                and not jspecs:
            fkey, params, kinds = fast_reg
            self.plan_cache.fast_put(fkey, FastEntry(
                norm_key=norm_key, sig=pz.sig, baked=pz.baked,
                fingerprint=fp, tables=tables,
                slot_map=build_slot_map(params, kinds, pz.values),
                base_values=tuple(pz.values),
                stmt_type=type(ast).__name__,
            ))
        # artifact export AFTER a successful execution of a FRESH compile
        # (a hit/hydrate already has its executable on disk). The fast-
        # tier registration material rides the artifact so a warm boot
        # restores the text tier too.
        if art_key is not None and not was_hit and not hydrated \
                and art_store.writable:
            art_fast = art_text = None
            if fast_reg is not None and executor is None:
                fkey, params, kinds = fast_reg
                art_text = fkey
                art_fast = dict(
                    norm_key=norm_key, sig=pz.sig, baked=pz.baked,
                    fingerprint=fp, tables=tables,
                    slot_map=build_slot_map(params, kinds, pz.values),
                    base_values=tuple(pz.values),
                    stmt_type=type(ast).__name__,
                )
            try:
                art_store.save(
                    art_key, entry.prepared,
                    output_names=planned.output_names, dtypes=pz.dtypes,
                    tables=tables, fast=art_fast, text_key=art_text)
            except Exception:
                pass
        return rs

    def _execute_entry(self, entry, values, *, ex, was_hit, fast, plan_s,
                       compile_s, fastparse_s, profiling, h2d0) -> ResultSet:
        """Bind + dispatch a cached/compiled entry and assemble the
        ResultSet with the execution's record on it (profile, phase
        breakdown), the monitor row and the metrics. Shared by the full
        path (run_ast) and the fast path (fast_execute) — the fast path
        arrives with plan_s=compile_s=0.

        One route for every plan: `prepared.dispatch` is async (it
        returns the cursor as soon as ONE program is enqueued, a streamed
        plan's after its chunk loop), the cursor has started the
        device-to-host copies its sync will read right behind the
        program, sql_audit/metrics/trace host work overlaps both, and the
        only in-statement wait is that sync. A frame over
        DeviceResult.FRAME_PREFETCH_BYTES stays device-resident behind
        the cursor until the caller touches it."""
        from ..share.errsim import errsim_point
        from .executor import DeviceResult

        if not ex.host_fallback:
            # device OOM injection point (EN_DEVICE_OOM): covers the fast
            # path, the full path and chunked dispatch alike. A host-
            # fallback executor never device-OOMs, which is what lets the
            # degradation ladder's final rung terminate.
            errsim_point("EN_DEVICE_OOM")
        prepared = entry.prepared
        retries0 = prepared.retries
        ann0 = getattr(prepared.params, "ann_escalations", 0)
        # streaming pipeline counters are cumulative on the prepared plan
        # (plan-cache shared): fold per-run deltas, like overflow retries
        sstats = prepared.stream_stats
        stream0 = sstats.snapshot() if sstats is not None else None
        tl = _gap.tracing()
        if tl is not None:
            tl.leaf("param pack")
        t0 = time.perf_counter()
        # packed parameter upload where the plan allows it: ONE
        # host->device transfer for the whole parameter set
        qparams = prepared.bind(values, entry.dtypes)
        bind_s = time.perf_counter() - t0
        if tl is not None:
            tl.leaf("device dispatch")
        # table uploads (span "h2d") inside the dispatch and the sync: the
        # record carries them so the ledger carves them out of those two
        up0 = _gap.h2d_seconds()
        exec_t0 = time.perf_counter()
        op_samples = prof_digest = prof_reason = None
        pp = self.plan_profiler
        if pp is not None and pp.enabled:
            from . import plan_profile as _PP

            if _PP.profile_eligible(prepared):
                # the server layer hands the statement digest down
                # thread-locally; direct engine use falls back to the
                # monitor's normalized text as the sampling key
                mon0 = entry.monitor
                prof_digest = pp.take_pending() or (
                    mon0.sql if mon0 is not None else None)
                if prof_digest is not None:
                    prof_reason = pp.decide(prof_digest)
        cursor = None
        if prof_reason is not None:
            try:
                # profiled segmented run: fenced per-operator stages,
                # bit-identical (out, ovf_vec) — the statement is
                # served FROM this run, nothing executes twice
                out, ovf_vec, op_samples = _PP.run_profiled(
                    prepared, qparams)
            except Exception:
                # a broken profile never fails the statement — fall
                # back to the plan's own dispatch below
                pass
            else:
                cursor = DeviceResult(prepared, qparams, out, ovf_vec)
                cursor.start_copies()
        if cursor is None:
            # every leaf the completion sync will read starts crossing
            # the link in here, behind the program: the bookkeeping
            # below overlaps the program AND the transfers
            cursor = prepared.dispatch(qparams)
        dispatch_s = time.perf_counter() - exec_t0
        up1 = _gap.h2d_seconds()
        if tl is not None:
            tl.leaf_end()
        self._emit_px_spans(prepared, exec_t0, time.perf_counter())
        profile = None
        if profiling:
            from ..server.diag import QueryProfile

            device_bytes = 0
            input_spec = prepared.input_spec
            if input_spec is not None:
                # warm statements reuse the footprint walk: device inputs
                # only change via an upload, and every upload into the
                # device cache moves this counter (serving-path diet)
                memo = prepared._dev_bytes_memo
                if (memo is not None and memo[0] == ex.cache_h2d_bytes
                        and memo[1] is ex):
                    device_bytes = memo[2]
                else:
                    device_bytes = ex.input_device_bytes(input_spec)
                    prepared._dev_bytes_memo = (
                        ex.cache_h2d_bytes, ex, device_bytes)
            # peak working set: device-resident inputs + the result's
            # footprint (the frame's static shapes, no transfer: the
            # cursor adds actual d2h bytes as fetches happen) + PX
            # exchange lane capacity (the collective's buffers are live
            # simultaneously with both)
            peak = device_bytes + cursor.frame_bytes
            nsh = prepared.px_nsh
            for _kind, ncols, cap in prepared.px_exchanges or ():
                lanes = nsh if _kind == "broadcast" else nsh * nsh
                peak += ncols * cap * lanes * 8
            profile = QueryProfile(
                compile_hit=was_hit,
                compile_s=compile_s,
                h2d_bytes=ex.h2d_bytes - h2d0,
                device_bytes=device_bytes,
                peak_bytes=peak,
                fastparse_s=fastparse_s,
                bind_s=bind_s,
                dispatch_s=dispatch_s,
                fast_path_hit=fast,
            )
        phases = {
            "plan_s": plan_s, "compile_s": compile_s,
            "fastparse_s": fastparse_s, "bind_s": bind_s,
            "dispatch_s": dispatch_s, "fetch_s": 0.0,
            "cache_hit": was_hit, "fast_hit": fast,
        }
        if up1 > up0:
            phases["h2d_s"] = up1 - up0
        # the record rides the cursor, THEN the sync point is forced: the
        # overflow check + what the dispatch put in flight. All the host
        # work above overlapped device compute. The sync wall IS the
        # statement's device wait — time it (host-tax ledger's "device
        # wait" phase reads fetch_s; leaving it 0.0 hid the chip time
        # inside exec_s).
        cursor.profile = profile
        cursor.phases = phases
        if tl is not None:
            tl.leaf("device wait")
        up0 = _gap.h2d_seconds()
        tf = time.perf_counter()
        nrows = cursor.nrows
        fetch_s = time.perf_counter() - tf
        if tl is not None:
            tl.leaf_end()
        phases["fetch_s"] = fetch_s
        up1 = _gap.h2d_seconds()
        if up1 > up0:  # an overflow's redrive uploaded again
            phases["h2d_fetch_s"] = up1 - up0
        if profile is not None:
            profile.fetch_s = fetch_s
        json_cols = None
        if entry.json_specs:
            # JSON-split statement: the host formats the JSON text from
            # every argument column, so the result is eager
            from ..sql.json_host import apply_host_json

            host = cursor.fetch_columns()
            json_cols = apply_host_json(
                entry.json_specs, entry.json_hidden, entry.output_names,
                {n: host[n] for n in entry.output_names})
        exec_s = time.perf_counter() - exec_t0
        phases["exec_s"] = exec_s
        phases["rows"] = nrows
        self._fold_access(prepared)
        # mesh-SPMD collective accounting: the MeshPlan rides the prepared
        # plan (filled at first-dispatch trace, restored warm from the
        # artifact store), so cached and warm-booted plans fold identically
        mesh_plan = prepared.mesh_plan
        if mesh_plan is not None and not mesh_plan.total_ops:
            mesh_plan = None
        stream_d = None
        if sstats is not None:
            s1 = sstats.snapshot()
            d = tuple(b - a for a, b in zip(stream0, s1))
            if d[0] or d[6]:  # chunks streamed or partitions spilled
                stream_d = d
                # streamed plans execute inside dispatch_s; expose the
                # per-chunk H2D/compute/overlap split so the host-tax
                # ledger can carve the dispatch wall into real phases
                phases["stream_h2d_s"] = d[3]
                phases["stream_compute_s"] = d[4]
                phases["stream_overlap_s"] = d[5]
        retries = prepared.retries - retries0
        mon = entry.monitor
        if mon is not None:
            mon.runs += 1
            mon.total_exec_s += exec_s
            mon.last_rows = nrows
            mon.overflow_retries = prepared.retries
            if profile is not None:
                mon.total_transfer_bytes += profile.transfer_bytes
                mon.last_device_bytes = profile.device_bytes
                mon.peak_bytes = max(mon.peak_bytes, profile.peak_bytes)
            if mesh_plan is not None:
                mon.px_collective_ops += mesh_plan.total_ops
                mon.px_collective_bytes += mesh_plan.total_bytes
                mon.px_exchanges = mesh_plan.describe()
                mon.px_exchange_rows += cursor.exchange_rows
                mon.px_exchange_slots += prepared.exchange_slots
            if stream_d is not None:
                mon.stream_chunks += stream_d[0]
                mon.spill_partitions += stream_d[6]
                h2d_d, overlap_d = stream_d[3], stream_d[5]
                mon.h2d_overlap_pct = (
                    100.0 * overlap_d / h2d_d if h2d_d else 0.0)
        if op_samples is not None:
            # fold the (estimate, actual) calibration pairs into the
            # bounded store + per-op-kind sysstat counters; EXPLAIN
            # ANALYZE reads the result's op_profile right after this run
            est = prepared.node_estimates
            pp.store.fold(
                prof_digest, op_samples, est,
                plan_id=mon.plan_id if mon is not None else 0,
            )
            seg = getattr(prepared, "_segmented", None)
            cursor.op_profile = {
                "digest": prof_digest,
                "reason": prof_reason,
                "estimates": dict(est or {}),
                "samples": op_samples,
                # plan nodes the executor never emits standalone (e.g.
                # a Join absorbed by a clustered-FK aggregate): no
                # sample, charged to the absorbing parent
                "absorbed": dict(getattr(seg, "absorbed", None) or {}),
            }
            pm = self.metrics
            if pm is not None and pm.enabled:
                pm.add("plan profiles")
                pm.add(f"plan profiles: {prof_reason}")
                for s in op_samples:
                    pm.add(f"plan profile ops: {s.op_kind}")
        m = self.metrics
        if m is not None and m.enabled:
            m.observe("sql plan", plan_s)
            if not was_hit:
                m.observe("sql compile", compile_s)
            m.observe("sql execute", exec_s)
            m.add("result rows returned", nrows)
            if cursor.narrowed:
                m.add("stmt fused dispatches")
            # by what the sync read: a narrow frame that gave up fusion
            # over the ceiling finished lazy
            m.add("result frames prefetched" if cursor.prefetched
                  else "result frames lazy")
            if retries > 0:
                m.add("overflow recompiles", retries)
            if prepared.px_nsh:
                # the served PX route prepares and dispatches here, not
                # through PxExecutor.execute: count it where it runs
                m.add("px executions")
                # what its exchanges delivered and what they hold room
                # for: the quotient is lane occupancy
                m.add("px exchange rows", cursor.exchange_rows)
                m.add("px exchange slots", prepared.exchange_slots)
                if retries > 0:
                    # an exchange lane or a join capacity of a mesh
                    # program overflowed: each is one more PX compile
                    m.add("px overflow recompiles", retries)
            params = prepared.params
            vts = getattr(params, "vector_topns", None)
            if vts:
                m.bulk(adds=ann_adds(vts, 1))
                esc = getattr(params, "ann_escalations", 0) - ann0
                if esc > 0:
                    m.add("ann over-probe escalations", esc)
                stats = getattr(ex, "ann_stats", None)
                if stats is not None:
                    for v in vts.values():
                        st = stats.setdefault(
                            (v.table, v.column), [0, 0, 0])
                        st[0] += 1
                        st[1] += v.nprobe
                        st[2] += max(esc, 0)
            if mesh_plan is not None:
                for coll, cnt in mesh_plan.ops_by_collective().items():
                    m.add(f"px collective {coll}", cnt)
                m.add("px collective bytes", mesh_plan.total_bytes)
            if stream_d is not None:
                m.add("stream chunks", stream_d[0])
                m.add("stream h2d overlap", int(stream_d[5] * 1e6))
                if stream_d[6]:
                    m.add("stream spill partitions", stream_d[6])
        tl = self.timeline
        if tl is not None and tl.enabled:
            # serving timeline: this dispatch's device-busy seconds plus
            # compile/result-transfer interference. Batched cohorts skip
            # this path — their ONE shared dispatch is fed by the batcher
            tl.record_exec(dispatch_s, 0.0 if was_hit else compile_s,
                           profile.d2h_bytes if profile is not None else 0)
            if mesh_plan is not None:
                tl.record_collective(
                    mesh_plan.total_ops, mesh_plan.total_bytes)
            if stream_d is not None:
                tl.record_stream(stream_d[0], stream_d[3], stream_d[4],
                                 stream_d[5], stream_d[6])
        if json_cols is not None:
            return ResultSet(*json_cols, plan_cache_hit=was_hit,
                             fast_path_hit=fast, profile=profile,
                             phases=phases, plan=prepared.plan,
                             op_profile=cursor.op_profile)
        return LazyResultSet(entry.output_names, cursor,
                             plan_cache_hit=was_hit, fast_path_hit=fast,
                             plan=prepared.plan)
