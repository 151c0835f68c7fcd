"""Persistent compiled-plan artifacts: AOT export + warm-boot serving.

Reference surface: ObPlanCache keeps compiled plans only in memory —
a restarted observer re-optimizes every statement. On TPU the cached
artifact is an XLA executable whose trace + compile costs seconds, so a
rebooted node spends its first minutes compiling instead of serving
(exactly the host-side stall that kills accelerator utilization). This
module persists each compiled executable with `jax.export` (StableHLO
serialization), keyed by the plan-cache identity — normalized text,
parameter signature, baked literals, plan fingerprint, schema +
dictionary versions — plus the jax/jaxlib/backend version and device
topology. A warm boot rebuilds the plan cache from disk: ZERO engine
traces (Executor.compile never runs) for cached statements, and the
backend compile of the deserialized StableHLO hits the XLA persistent
compilation cache that lives next to the artifacts.

Layout under the store directory:

    index.json      ranking + byte accounting; exec counts are synced
                    from the workload repository's statement summaries
                    so the boot warm-load hydrates the HOTTEST digests
                    first under the byte budget
    <aid>.meta      pickled ArtifactMeta: logical plan, physical
                    capacities, cache-key parts, fast-tier registration
                    material, output prototype
    <aid>.x         serialized base executable (jax.export blob)
    <aid>.b<K>.x    pow2 batched-bucket variants (vmapped executables)
    xla/            XLA persistent compilation cache (backend compiles
                    of deserialized programs land here)

ColumnBatch is a custom pytree whose static aux (Schema, Dictionary)
jax.export cannot serialize, so artifacts ride a FLAT calling
convention: the export wrapper flattens (inputs, qparams) to positional
array leaves, and the loader rebuilds the output ColumnBatch from a
pickled prototype (column names + schema + dictionaries captured at
trace time). vmap over a deserialized call is unsupported, so each
batched bucket exports as its own program.

Every load path is load-or-compile: deserialization failure, version or
topology mismatch, schema bump (key mismatch) and input-shape drift
each bump a dedicated sysstat counter and fall back to a clean
recompile — a stale executable never runs. Loads time into the
"plan artifact load" wait event.
"""

from __future__ import annotations

import copy
import hashlib
import json
import os
import pickle
import threading
import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from ..core.column import ColumnBatch


class ArtifactStale(Exception):
    """A warm executable's input signature no longer matches the live
    catalog (DML changed a table's device capacity, a leaf count moved).
    PreparedPlan.jit_call catches this and recompiles from the pickled
    logical plan — never a wrong answer, at worst one honest compile."""


def env_signature() -> dict:
    """The portability key of a compiled artifact: an executable is only
    as reusable as the stack that built it."""
    import jaxlib

    return {
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "backend": jax.default_backend(),
        "devices": jax.device_count(),
        "x64": bool(jax.config.jax_enable_x64),
    }


@dataclass
class ArtifactMeta:
    """Everything needed to rebuild a live plan-cache entry from disk
    with zero parsing/planning/tracing."""

    aid: str
    art_key: tuple  # (norm_key, sig, baked, fingerprint, extra, tag)
    tables: tuple
    env: dict
    plan: object  # pickled logical plan (recompile fallback retraces it)
    params: object  # PhysicalParams (derived specs cleared; re-detected)
    input_spec: list
    overflow_nodes: list
    in_avals: tuple  # ((shape, dtype), ...) per flat input leaf
    nslots: int  # packed qparam width (int64 lanes; vectors span several)
    out_proto: tuple  # (col_names, valid_names, schema, dicts)
    output_names: tuple
    dtypes: list
    fast: dict | None = None  # FastEntry kwargs (text-tier re-install)
    text_key: str | None = None
    px_nsh: int = 0
    # SPMD programs: the mesh geometry the shardings were lowered against
    # (mesh_signature), the compiled exchange layout (worker spans come
    # back warm), and the MeshPlan (collective counters come back warm).
    # A hydrating executor whose live mesh signature differs is REJECTED
    # — an AOT program must never run with another mesh's shardings.
    mesh_sig: tuple = ()
    px_exchanges: list | None = None
    mesh_plan: object = None
    # compile-time optimizer row estimates per node id: a warm-booted
    # plan must profile against the estimates it was COMPILED with, or
    # its (estimate, actual) calibration pairs drift with later stats
    node_estimates: dict | None = None


class _WarmExecutable:
    """A deserialized AOT executable standing in for PreparedPlan.jitted.
    Calls validate the flat input signature first; any drift raises
    ArtifactStale so the owner recompiles from its logical plan instead
    of feeding wrong-shaped buffers to a stale program."""

    __slots__ = ("_compiled", "_avals", "_proto")

    def __init__(self, compiled, avals, proto):
        self._compiled = compiled
        self._avals = avals
        self._proto = proto

    def validate(self, inputs, qparams):
        """Raise ArtifactStale on any input-signature drift. Exposed so
        paths that DON'T dispatch through __call__ — the operator
        profiler's segmented run traces fresh stages over whatever
        shapes arrive — can still detect a stale artifact and refresh
        it instead of silently serving past it forever."""
        leaves = jax.tree_util.tree_leaves((inputs, qparams))
        if len(leaves) != len(self._avals):
            raise ArtifactStale("input leaf count drift")
        for a, (shp, dt) in zip(leaves, self._avals):
            if tuple(jnp.shape(a)) != tuple(shp) \
                    or str(jnp.result_type(a)) != dt:
                raise ArtifactStale("input aval drift")
        return leaves

    def __call__(self, inputs, qparams):
        leaves = self.validate(inputs, qparams)
        out_leaves = self._compiled(*leaves)
        return rebuild_output(self._proto, out_leaves)


def rebuild_output(proto, out_leaves):
    """(ColumnBatch, ovf_vec) from the flat output leaves: unflatten
    against a prototype rebuilt from the pickled static parts (names,
    schema, dicts) — structurally identical to the treedef the export
    trace saw, since dict leaves flatten in sorted-key order."""
    col_names, valid_names, schema, dicts = proto
    shape = (
        ColumnBatch(
            cols=dict.fromkeys(col_names, 0),
            valid=dict.fromkeys(valid_names, 0),
            sel=0, nrows=0, schema=schema, dicts=dicts,
        ),
        0,
    )
    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(shape), list(out_leaves))


def export_flat(fn, example):
    """Serialize `fn(inputs, qparams)` through jax.export over FLAT
    positional leaves (custom-pytree aux never reaches the serializer).
    Returns (blob, out_proto, in_avals); the output prototype is
    captured from the traced output's static attributes."""
    leaves, in_tree = jax.tree_util.tree_flatten(example)
    cell: dict = {}

    def _flat(*flat):
        inputs, qp = jax.tree_util.tree_unflatten(in_tree, list(flat))
        out, ovf = fn(inputs, qp)
        cell["proto"] = (
            tuple(sorted(out.cols)), tuple(sorted(out.valid)),
            out.schema, dict(out.dicts),
        )
        fl, _ = jax.tree_util.tree_flatten((out, ovf))
        return tuple(fl)

    from jax import export as jax_export

    specs = [jax.ShapeDtypeStruct(jnp.shape(a), jnp.result_type(a))
             for a in leaves]
    blob = jax_export.export(jax.jit(_flat))(*specs).serialize()
    avals = tuple(
        (tuple(jnp.shape(a)), str(jnp.result_type(a))) for a in leaves
    )
    return blob, cell["proto"], avals


def load_flat(blob, in_avals, proto, example_leaves=None):
    """Deserialize + AOT-compile an exported blob into a callable with
    the PreparedPlan.jitted signature. The backend compile of the
    StableHLO goes through jax's persistent compilation cache (pointed
    into the store directory), so a warm boot pays a disk read, not a
    compile. A multi-device (PX shard_map) program must lower against
    the live mesh shardings — carried by the freshly assembled input
    leaves — or jax rejects the single-device calling context."""
    from jax import export as jax_export

    exp = jax_export.deserialize(blob)
    multi = getattr(exp, "nr_devices", 1) > 1
    specs = []
    for i, (shp, dt) in enumerate(in_avals):
        sharding = None
        if multi and example_leaves is not None and i < len(example_leaves):
            sharding = getattr(example_leaves[i], "sharding", None)
        specs.append(
            jax.ShapeDtypeStruct(tuple(shp), jnp.dtype(dt),
                                 sharding=sharding))
    compiled = jax.jit(exp.call).lower(*specs).compile()
    return _WarmExecutable(compiled, in_avals, proto)


def _atomic_write(path: str, data: bytes) -> None:
    # artifacts are recomputable (worst case: one honest compile), so no
    # fsync — but they still ride the integrity envelope: a corrupt
    # artifact must be DETECTED and quarantined, never half-unpickled
    from ..storage.integrity import ARTIFACT, write_atomic

    write_atomic(path, data, fsync=False, path_class=ARTIFACT)


def _read_verified(path: str) -> bytes:
    """Verified read for every artifact file; raises FileNotFoundError
    (missing) or CorruptBlock (damaged) — never returns bad bytes."""
    from ..storage.integrity import ARTIFACT, read_verified

    return read_verified(path, path_class=ARTIFACT)


class PlanArtifactStore:
    """On-disk tier of the plan cache. Modes mirror the config parameter
    ob_plan_artifact_mode: "ro" hydrates but never writes, "rw" also
    exports on compile and re-exports on overflow recompile."""

    def __init__(self, root: str, mode: str = "rw",
                 max_bytes: int = 256 << 20, metrics=None):
        self.root = root
        self.mode = mode
        self.max_bytes = int(max_bytes)
        self.metrics = metrics
        self._lock = threading.RLock()
        os.makedirs(root, exist_ok=True)
        self._index: dict = {"env": env_signature(), "entries": {}}
        self._load_index()
        # per-entry runtime stats for __all_virtual_plan_artifact
        self.runtime: dict[str, dict] = {}
        self.miss_count = 0
        self._prime_pool = None
        self._enable_xla_cache()

    # ------------------------------------------------------------- state
    @property
    def readable(self) -> bool:
        return self.mode in ("ro", "rw")

    @property
    def writable(self) -> bool:
        return self.mode == "rw"

    def _note(self, name: str, n: float = 1) -> None:
        if self.metrics is not None:
            self.metrics.add(name, n)

    def _rt(self, aid: str) -> dict:
        st = self.runtime.get(aid)
        if st is None:
            st = self.runtime[aid] = {
                "hits": 0, "misses": 0, "load_us": 0, "warm": 0,
            }
        return st

    def _enable_xla_cache(self) -> None:
        """Backend compiles of deserialized programs (and of fresh
        compiles on this node) persist in the process-global XLA cache,
        so a warm boot's round-trip compile is a disk read."""
        from ..share.compile_cache import enable_compile_cache

        enable_compile_cache()

    # ----------------------------------------------------------- priming
    def _prime_async(self, blob, in_avals, proto, leaves) -> None:
        """Backend-compile the round-tripped export off the serving path.
        The deserialized program hashes differently from the original
        trace, so without this the FIRST warm boot still pays the XLA
        compile; priming writes the exact cache entry load_flat will
        look up, making every warm boot a disk read."""
        import concurrent.futures

        with self._lock:
            if self._prime_pool is None:
                self._prime_pool = concurrent.futures.ThreadPoolExecutor(
                    max_workers=1,
                    thread_name_prefix="plan-artifact-prime")
            pool = self._prime_pool

        def _job():
            try:
                load_flat(blob, in_avals, proto, example_leaves=leaves)
                self._note("plan artifact prime")
            except Exception:
                self._note("plan artifact prime error")
        try:
            pool.submit(_job)
        except RuntimeError:
            pass  # pool already shut down mid-close

    def drain(self) -> None:
        """Block until queued primes have hit the XLA cache (close path:
        the entry must be on disk before the next boot)."""
        with self._lock:
            pool, self._prime_pool = self._prime_pool, None
        if pool is not None:
            pool.shutdown(wait=True)

    # ------------------------------------------------------------- index
    def _index_path(self) -> str:
        return os.path.join(self.root, "index.json")

    def _load_index(self) -> None:
        from ..storage.integrity import CorruptBlock, quarantine_file

        try:
            idx = json.loads(_read_verified(self._index_path()))
            if isinstance(idx, dict) and "entries" in idx:
                self._index = idx
        except FileNotFoundError:
            pass  # fresh store
        except CorruptBlock as e:
            # a corrupt index is quarantined and the store starts empty:
            # orphaned artifact files are unreachable (never hydrated)
            # and get re-exported/overwritten on the next compile
            quarantine_file(self._index_path(), e.reason)
            self._note("plan artifact quarantined")
            self._note("checksum failures")
        except (OSError, ValueError):
            pass

    def _save_index(self) -> None:
        if not self.writable:
            return
        try:
            _atomic_write(
                self._index_path(),
                json.dumps(self._index, sort_keys=True).encode())
        except OSError:
            pass

    def quarantine(self, aid: str, path: str, reason: str) -> None:
        """First load error on a corrupt artifact file: move it into
        quarantine/ (kept for forensics, never re-read), drop the whole
        entry from the index so later boots don't retry it, and count."""
        from ..storage.integrity import quarantine_file

        quarantine_file(path, reason)
        with self._lock:
            if aid in self._index["entries"]:
                if self.writable:
                    self._drop_files(aid)
                self._index["entries"].pop(aid, None)
                self._save_index()
        self._note("plan artifact quarantined")
        self._note("checksum failures")

    def key_id(self, art_key: tuple) -> str:
        return hashlib.md5(repr(art_key).encode()).hexdigest()

    def _paths(self, aid: str) -> tuple[str, str]:
        return (os.path.join(self.root, f"{aid}.meta"),
                os.path.join(self.root, f"{aid}.x"))

    def _bucket_path(self, aid: str, bucket: int) -> str:
        return os.path.join(self.root, f"{aid}.b{bucket}.x")

    def total_bytes(self) -> int:
        with self._lock:
            return sum(int(e.get("bytes", 0))
                       for e in self._index["entries"].values())

    def entries(self) -> dict:
        with self._lock:
            return {k: dict(v) for k, v in self._index["entries"].items()}

    def ranked(self) -> list[tuple[str, dict]]:
        """(aid, index entry) hottest-first — the boot warm-load order.
        Exec counts come from the statement summaries synced at save /
        close time; ties break on save recency."""
        with self._lock:
            ents = list(self._index["entries"].items())
        ents.sort(key=lambda kv: (-int(kv[1].get("execs", 0)),
                                  -int(kv[1].get("seq", 0))))
        return ents

    def sync_exec_counts(self, summaries) -> None:
        """Fold the workload repository's per-digest exec counts into the
        ranking index (digest == the fast-tier text key)."""
        if not self.writable:
            return
        by_digest = {}
        try:
            for s in summaries:
                d = s.get("digest") if isinstance(s, dict) \
                    else getattr(s, "digest", None)
                n = s.get("exec_count") if isinstance(s, dict) \
                    else getattr(s, "exec_count", 0)
                if d:
                    by_digest[d] = int(n)
        except Exception:
            return
        with self._lock:
            for aid, ent in self._index["entries"].items():
                tk = ent.get("text")
                if tk in by_digest:
                    ent["execs"] = max(int(ent.get("execs", 0)),
                                       by_digest[tk])
            self._save_index()

    # -------------------------------------------------------------- save
    def _evict_to_budget(self, incoming: int) -> bool:
        """LRU-by-heat eviction so the store honors plan_artifact_max_bytes.
        Returns False when the incoming artifact alone exceeds the budget."""
        if incoming > self.max_bytes:
            self._note("plan artifact budget skip")
            return False
        ents = self._index["entries"]
        while ents and self.total_bytes() + incoming > self.max_bytes:
            coldest = min(
                ents, key=lambda k: (int(ents[k].get("execs", 0)),
                                     int(ents[k].get("seq", 0))))
            self._drop_files(coldest)
            ents.pop(coldest, None)
            self._note("plan artifact evict")
        return True

    def _drop_files(self, aid: str) -> None:
        meta_p, blob_p = self._paths(aid)
        ent = self._index["entries"].get(aid, {})
        for b in ent.get("buckets", ()):
            try:
                os.remove(self._bucket_path(aid, int(b)))
            except OSError:
                pass
        for p in (meta_p, blob_p):
            try:
                os.remove(p)
            except OSError:
                pass

    def save(self, art_key: tuple, prepared, *, output_names, dtypes,
             tables, fast: dict | None = None, text_key: str | None = None,
             execs: int = 1) -> str | None:
        """Export one freshly compiled plan. Returns the artifact id, or
        None when the plan is not exportable (legacy-tuple qparams,
        export/pickle failure) — the live entry is unaffected either way."""
        if not self.writable:
            return None
        spec = getattr(prepared, "_qparam_spec", None)
        if spec is None or not getattr(prepared, "_traceable", True):
            self._note("plan artifact export skip")
            return None
        aid = self.key_id(art_key)
        from .executor import packed_width

        try:
            inputs = prepared._inputs()
            qex = np.zeros(packed_width(spec), np.int64)
            blob, proto, avals = export_flat(prepared.jitted, (inputs, qex))
            params = copy.copy(prepared.params)
            params.clustered_aggs = {}
            params.vector_topns = {}
            meta = ArtifactMeta(
                aid=aid, art_key=art_key, tables=tuple(tables),
                env=env_signature(), plan=prepared.plan, params=params,
                input_spec=list(prepared.input_spec),
                overflow_nodes=list(prepared.overflow_nodes),
                in_avals=avals, nslots=packed_width(spec), out_proto=proto,
                output_names=tuple(output_names), dtypes=list(dtypes),
                fast=fast, text_key=text_key,
                px_nsh=int(getattr(prepared, "px_nsh", 0)),
                # save runs after the first successful execution, so the
                # lazily-traced exchange layout is populated by now
                mesh_sig=tuple(getattr(prepared, "mesh_sig", ()) or ()),
                px_exchanges=list(
                    getattr(prepared, "px_exchanges", None) or []),
                mesh_plan=getattr(prepared, "mesh_plan", None),
                node_estimates=dict(
                    getattr(prepared, "node_estimates", None) or {}),
            )
            meta_blob = pickle.dumps(meta, protocol=pickle.HIGHEST_PROTOCOL)
        except Exception:
            self._note("plan artifact export error")
            return None
        nbytes = len(blob) + len(meta_blob)
        with self._lock:
            if not self._evict_to_budget(nbytes):
                return None
            meta_p, blob_p = self._paths(aid)
            try:
                _atomic_write(meta_p, meta_blob)
                _atomic_write(blob_p, blob)
            except OSError:
                self._note("plan artifact export error")
                return None
            ents = self._index["entries"]
            old = ents.get(aid, {})
            ents[aid] = {
                "bytes": nbytes,
                "execs": max(int(old.get("execs", 0)), int(execs)),
                "seq": int(time.time() * 1e6),
                "text": text_key or (art_key[0] if art_key else ""),
                "buckets": [],
            }
            self._save_index()
        self._note("plan artifact save")
        self._note("plan artifact bytes saved", nbytes)
        prepared.artifact_ref = (self, aid)
        try:
            leaves = jax.tree_util.tree_flatten((inputs, qex))[0]
            self._prime_async(blob, avals, proto, leaves)
        except Exception:
            pass
        return aid

    def export_bucket(self, prepared, bucket: int, fn) -> None:
        """Persist one pow2 batched-bucket variant (vmap over a
        deserialized call is unsupported, so each bucket is its own
        exported program)."""
        if not self.writable:
            return
        ref = getattr(prepared, "artifact_ref", None)
        spec = getattr(prepared, "_qparam_spec", None)
        if ref is None or not spec:
            return
        aid = ref[1]
        from .executor import packed_width

        try:
            inputs = prepared._inputs()
            qb = np.zeros((bucket, packed_width(spec)), np.int64)
            blob, _proto, _avals = export_flat(fn, (inputs, qb))
        except Exception:
            self._note("plan artifact export error")
            return
        try:
            leaves = jax.tree_util.tree_flatten((inputs, qb))[0]
            self._prime_async(blob, _avals, _proto, leaves)
        except Exception:
            pass
        with self._lock:
            ent = self._index["entries"].get(aid)
            if ent is None:
                return
            try:
                _atomic_write(self._bucket_path(aid, bucket), blob)
            except OSError:
                return
            if bucket not in ent["buckets"]:
                ent["buckets"].append(int(bucket))
            ent["bytes"] = int(ent.get("bytes", 0)) + len(blob)
            self._save_index()
        self._note("plan artifact bucket save")

    def on_recompile(self, prepared) -> None:
        """Overflow recompile hook: the executable just changed capacity,
        so the on-disk artifact would replay the overflow on every boot.
        Re-export at the new capacity and drop the (stale) bucket
        variants."""
        ref = getattr(prepared, "artifact_ref", None)
        if ref is None or not self.writable:
            return
        aid = ref[1]
        with self._lock:
            ent = self._index["entries"].get(aid)
            if ent is None:
                prepared.artifact_ref = None
                return
            meta_p, _ = self._paths(aid)
            try:
                meta = pickle.loads(_read_verified(meta_p))
            except Exception:
                self._drop_files(aid)
                self._index["entries"].pop(aid, None)
                prepared.artifact_ref = None
                return
            for b in ent.get("buckets", ()):
                try:
                    os.remove(self._bucket_path(aid, int(b)))
                except OSError:
                    pass
            ent["buckets"] = []
        spec = getattr(prepared, "_qparam_spec", None) or ()
        from .executor import packed_width

        try:
            inputs = prepared._inputs()
            qex = np.zeros(packed_width(spec), np.int64)
            blob, proto, avals = export_flat(prepared.jitted, (inputs, qex))
            params = copy.copy(prepared.params)
            params.clustered_aggs = {}
            params.vector_topns = {}
            meta.params = params
            meta.input_spec = list(prepared.input_spec)
            meta.overflow_nodes = list(prepared.overflow_nodes)
            meta.in_avals = avals
            meta.out_proto = proto
            meta.node_estimates = dict(
                getattr(prepared, "node_estimates", None) or {})
            meta_blob = pickle.dumps(meta, protocol=pickle.HIGHEST_PROTOCOL)
        except Exception:
            self._note("plan artifact export error")
            return
        with self._lock:
            meta_p, blob_p = self._paths(aid)
            try:
                _atomic_write(meta_p, meta_blob)
                _atomic_write(blob_p, blob)
            except OSError:
                return
            ent = self._index["entries"].get(aid)
            if ent is not None:
                ent["bytes"] = len(blob) + len(meta_blob)
            self._save_index()
        self._note("plan artifact reexport")

    def load_bucket(self, prepared, bucket: int):
        """Hydrate one batched-bucket executable for a warm plan, or None
        (the caller recompiles — honestly counted — and rebuilds it)."""
        ref = getattr(prepared, "artifact_ref", None)
        proto = getattr(prepared, "_art_proto", None)
        spec = getattr(prepared, "_qparam_spec", None)
        if ref is None or proto is None or not self.readable or not spec:
            return None
        aid = ref[1]
        path = self._bucket_path(aid, bucket)
        t0 = time.perf_counter()
        try:
            from ..storage.integrity import CorruptBlock

            try:
                blob = _read_verified(path)
            except CorruptBlock as e:
                # quarantine just the bucket file; the base program and
                # the index entry stay (the caller recompiles the bucket)
                from ..storage.integrity import quarantine_file
                quarantine_file(path, e.reason)
                with self._lock:
                    ent = self._index["entries"].get(aid)
                    if ent is not None and bucket in ent.get("buckets", ()):
                        ent["buckets"].remove(bucket)
                        self._save_index()
                self._note("plan artifact quarantined")
                self._note("checksum failures")
                raise
            inputs = prepared._inputs()
            qb = np.zeros((bucket, len(spec)), np.int64)
            leaves = jax.tree_util.tree_leaves((inputs, qb))
            avals = tuple((tuple(jnp.shape(a)), str(jnp.result_type(a)))
                          for a in leaves)
            warm = load_flat(blob, avals, proto, example_leaves=leaves)
        except FileNotFoundError:
            return None
        except Exception:
            self._note("plan artifact load error")
            return None
        dt = time.perf_counter() - t0
        if self.metrics is not None:
            self.metrics.wait("plan artifact load", dt)
        st = self._rt(aid)
        st["hits"] += 1
        st["load_us"] += int(dt * 1e6)
        self._note("plan artifact bucket hit")
        return warm

    # ----------------------------------------------------------- hydrate
    def read_meta(self, aid: str):
        """Pickled ArtifactMeta for one entry, or None (a corrupt file is
        quarantined on first load error; an unpicklable-but-valid-crc
        payload is counted as a load error)."""
        from ..storage.integrity import CorruptBlock

        if not self.readable:
            return None
        meta_p, _ = self._paths(aid)
        try:
            return pickle.loads(_read_verified(meta_p))
        except FileNotFoundError:
            return None
        except CorruptBlock as e:
            self.quarantine(aid, meta_p, e.reason)
            self._note("plan artifact load error")
            return None
        except Exception:
            self._note("plan artifact load error")
            return None

    def hydrate(self, aid: str, executor, key_extra_fn=None,
                preload_buckets: bool = True, meta=None):
        """Rebuild a live PreparedPlan from one artifact. Returns
        (meta, prepared) or None; every rejection bumps its own counter
        and the caller falls back to a clean compile. `key_extra_fn`
        re-derives the schema/dict-version key material and rejects on
        mismatch — schema-bump invalidation semantics are identical to the
        in-memory tiers — and the plan recompiles once it reads otherwise
        (a dictionary grown under the exported executable)."""
        if not self.readable:
            return None
        with self._lock:
            known = aid in self._index["entries"]
        if not known:
            self.miss_count += 1
            self._note("plan artifact miss")
            return None
        t0 = time.perf_counter()
        st = self._rt(aid)
        _, blob_p = self._paths(aid)
        if meta is None:
            meta = self.read_meta(aid)
        if meta is None:
            st["misses"] += 1
            self._note("plan artifact load error")
            return None
        if meta.env != env_signature():
            st["misses"] += 1
            self._note("plan artifact version mismatch")
            return None
        if meta.px_nsh:
            # SPMD program: its shardings were lowered against one mesh
            # geometry. A different live mesh must key-mismatch cleanly
            # (counted; caller recompiles) — never run wrong shardings.
            saved_sig = tuple(getattr(meta, "mesh_sig", ()) or ())
            live_sig = tuple(getattr(executor, "mesh_sig", ()) or ())
            if saved_sig and saved_sig != live_sig:
                st["misses"] += 1
                self._note("plan artifact mesh mismatch")
                return None
        if key_extra_fn is not None:
            try:
                extra = key_extra_fn(meta.tables)
            except Exception:
                extra = None
            if extra != meta.art_key[4]:
                st["misses"] += 1
                self._note("plan artifact key mismatch")
                return None
        try:
            from ..storage.integrity import CorruptBlock

            try:
                blob = _read_verified(blob_p)
            except CorruptBlock as e:
                self.quarantine(aid, blob_p, e.reason)
                raise
            from .executor import PreparedPlan

            prepared = PreparedPlan(
                executor, meta.plan, meta.params, None,
                meta.input_spec, meta.overflow_nodes)
            # assemble + validate inputs BEFORE trusting the executable:
            # a table whose device capacity moved since export must fall
            # back to a compile, not feed a stale program
            inputs = prepared._inputs()
            leaves = jax.tree_util.tree_leaves(
                (inputs, np.zeros(meta.nslots, np.int64)))
            if len(leaves) != len(meta.in_avals) or any(
                tuple(jnp.shape(a)) != tuple(shp)
                or str(jnp.result_type(a)) != dt
                for a, (shp, dt) in zip(leaves, meta.in_avals)
            ):
                st["misses"] += 1
                self._note("plan artifact input mismatch")
                return None
            warm = load_flat(blob, meta.in_avals, meta.out_proto,
                             example_leaves=leaves)
        except Exception:
            st["misses"] += 1
            self._note("plan artifact load error")
            return None
        prepared.jitted = warm
        prepared._traceable = False
        if key_extra_fn is not None:
            prepared._warm_key = (key_extra_fn, meta.tables,
                                  meta.art_key[4])
        prepared.artifact_ref = (self, aid)
        prepared._art_proto = meta.out_proto
        prepared.node_estimates = dict(
            getattr(meta, "node_estimates", None) or {})
        if meta.px_nsh:
            prepared.px_nsh = meta.px_nsh
            # the exchange layout and mesh plan were captured at save
            # time (post-trace): warm boots get their worker spans and
            # collective counters without ever re-tracing
            prepared.px_exchanges = list(
                getattr(meta, "px_exchanges", None) or [])
            prepared.mesh_sig = tuple(getattr(meta, "mesh_sig", ()) or ())
            mp = getattr(meta, "mesh_plan", None)
            if mp is not None:
                prepared.mesh_plan = mp
        dt = time.perf_counter() - t0
        if self.metrics is not None:
            self.metrics.wait("plan artifact load", dt)
        st["hits"] += 1
        st["load_us"] += int(dt * 1e6)
        st["warm"] = 1
        self._note("plan artifact hit")
        if preload_buckets:
            with self._lock:
                buckets = list(self._index["entries"]
                               .get(aid, {}).get("buckets", ()))
            for b in buckets:
                fn = self.load_bucket(prepared, int(b))
                if fn is not None:
                    prepared._batched[int(b)] = fn
        return meta, prepared

    # ------------------------------------------------------------- flush
    def flush(self) -> None:
        """The plan cache's flush covers this tier too: schema/privilege
        driven invalidation must not leave executables that hydrate
        back. rw deletes the files; ro (can't write) just forgets the
        index so every hydration misses."""
        with self._lock:
            if self.writable:
                for aid in list(self._index["entries"]):
                    self._drop_files(aid)
            self._index["entries"] = {}
            self.runtime.clear()
            self._save_index()
        self._note("plan artifact flush")

    def census(self) -> list[dict]:
        """Per-entry rows for __all_virtual_plan_artifact: identity,
        bytes, ranking execs, bucket variants, and this boot's
        hit/miss/load-time tallies."""
        with self._lock:
            ents = {k: dict(v) for k, v in self._index["entries"].items()}
            rts = {k: dict(v) for k, v in self.runtime.items()}
        out = []
        for aid, ent in ents.items():
            st = rts.get(aid, {})
            out.append({
                "artifact_id": aid,
                "statement": str(ent.get("text", ""))[:128],
                "bytes": int(ent.get("bytes", 0)),
                "execs": int(ent.get("execs", 0)),
                "buckets": tuple(int(b) for b in ent.get("buckets", ())),
                "hits": int(st.get("hits", 0)),
                "misses": int(st.get("misses", 0)),
                "load_us": int(st.get("load_us", 0)),
                "warm": int(st.get("warm", 0)),
            })
        out.sort(key=lambda r: (-r["execs"], r["artifact_id"]))
        return out
