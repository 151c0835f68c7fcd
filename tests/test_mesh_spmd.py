"""Mesh-SPMD subsystem: one jitted program over an N-device mesh.

The contract under test (ISSUE 13 tentpole):

  * bit-identity — every plan shape returns EXACTLY the same rows on the
    8-device mesh, the degenerate 1-device mesh and the single chip,
    including a zipfian join leg whose hot keys ride the collective
    hot-key broadcast of the hybrid exchange;
  * a first-class mesh-plan representation — PX exchanges lower to named
    XLA collectives (all_gather / all_to_all / psum / ppermute) recorded
    per-program in PreparedPlan.mesh_plan, with bytes and lane capacity;
  * the shard_map compat shim tracks the PINNED jax (version-drift test:
    the resolved entry point and its replication-check kwarg must exist
    in this jax, so an upgrade that renames either fails loudly here);
  * SPMD plan artifacts are mesh-shape-keyed — an 8-device export must
    key-mismatch (counted, clean recompile) against a different mesh;
  * sharded residency charges the governor bytes/n_shards per device and
    the streamed out-of-core path is the ONLY one that pays
    host-mediated DTL hops.
"""

import numpy as np
import pytest

import jax

from oceanbase_tpu.core.column import batch_rows_normalized, batch_to_host
from oceanbase_tpu.core.dtypes import DataType, Schema
from oceanbase_tpu.core.table import Table
from oceanbase_tpu.engine.executor import Executor
from oceanbase_tpu.engine.memory_governor import MemoryGovernor
from oceanbase_tpu.engine.plan_artifact import PlanArtifactStore
from oceanbase_tpu.models.tpch import datagen
from oceanbase_tpu.models.tpch.sql_suite import QUERIES, UNIQUE_KEYS
from oceanbase_tpu.parallel import mesh as mesh_mod
from oceanbase_tpu.parallel.mesh import make_mesh, mesh_signature
from oceanbase_tpu.parallel.px import PxExecutor
from oceanbase_tpu.parallel.spmd import KIND_COLLECTIVE, SpmdLowering
from oceanbase_tpu.share.metrics import MetricsRegistry
from oceanbase_tpu.sql.parser import parse
from oceanbase_tpu.sql.planner import Planner

JOIN_SQL = ("select l.l_returnflag as rf, count(*) as c, "
            "sum(l.l_extendedprice) as s "
            "from lineitem l, orders o where l.l_orderkey = o.o_orderkey "
            "and o.o_totalprice > 1000 group by rf order by rf")


@pytest.fixture(scope="module")
def env():
    tables = datagen.generate(sf=0.005)
    n = len(jax.devices())
    return {
        "tables": tables,
        "planner": Planner(tables),
        "single": Executor(tables, unique_keys=UNIQUE_KEYS),
        "px": PxExecutor(tables, make_mesh(n), unique_keys=UNIQUE_KEYS),
        "px1": PxExecutor(tables, make_mesh(1, devices=jax.devices()[:1]),
                          unique_keys=UNIQUE_KEYS),
        "n": n,
    }


def _rows(ex, planned):
    return batch_rows_normalized(ex.execute(planned.plan),
                                 planned.output_names)


# --------------------------------------------------------- bit-identity

@pytest.mark.multidevice
@pytest.mark.parametrize("qid", [1, 6, 3])
def test_mesh_bit_identity_tpch(env, qid):
    """N-device mesh == 1-device mesh == single chip, bit for bit."""
    planned = env["planner"].plan(parse(QUERIES[qid]))
    want = _rows(env["single"], planned)
    assert _rows(env["px"], planned) == want
    assert _rows(env["px1"], planned) == want
    assert len(want) > 0


@pytest.mark.multidevice
def test_mesh_bit_identity_join(env):
    """lineitem ⋈ orders group-by: repartition + broadcast exchanges."""
    planned = env["planner"].plan(parse(JOIN_SQL))
    want = _rows(env["single"], planned)
    assert _rows(env["px"], planned) == want
    assert _rows(env["px1"], planned) == want
    assert len(want) > 0


@pytest.mark.multidevice
def test_zipf_join_hot_key_broadcast_bit_identity():
    """Zipfian probe side: the hybrid exchange broadcasts the hot keys as
    a collective (and psum-merges the skew histogram) yet stays
    bit-identical to the single chip."""
    rng = np.random.default_rng(23)
    nsh = len(jax.devices())
    n_fact = nsh * 4096
    zipf = np.minimum(rng.zipf(1.3, n_fact) - 1, 20_000).astype(np.int64)
    fact = Table.from_pydict(
        "fact", Schema.of(fk=DataType.int64(), v=DataType.int64()),
        {"fk": zipf, "v": rng.integers(0, 100, n_fact)})
    dim = Table.from_pydict(
        "dim", Schema.of(dk=DataType.int64(), w=DataType.int64()),
        {"dk": np.arange(20_001), "w": np.arange(20_001) * 3})
    catalog = {"fact": fact, "dim": dim}
    planned = Planner(catalog).plan(parse(
        "select sum(f.v + d.w) as s, count(*) as c "
        "from fact f, dim d where f.fk = d.dk"))
    want = batch_to_host(Executor(
        catalog, unique_keys={"dim": ("dk",)}).execute(planned.plan))
    px = PxExecutor(catalog, make_mesh(nsh), unique_keys={"dim": ("dk",)},
                    broadcast_threshold=1, hybrid_hash=True)
    prepared = px.prepare(planned.plan)
    got = batch_to_host(prepared.run())
    assert int(got["c"][0]) == int(want["c"][0])
    assert int(got["s"][0]) == int(want["s"][0])
    kinds = {e.kind for e in prepared.mesh_plan.exchanges}
    assert "skew_histogram" in kinds  # psum-merged skew detection ran
    assert "broadcast" in kinds       # hot keys rode the collective bcast
    assert "repartition" in kinds     # cold keys hash-exchanged


@pytest.mark.multidevice
def test_ring_broadcast_impl_bit_identity(env):
    """ppermute ring broadcast is a drop-in for all_gather: same rows,
    different collective in the mesh plan."""
    px_ring = PxExecutor(env["tables"], make_mesh(env["n"]),
                         unique_keys=UNIQUE_KEYS, broadcast_impl="ring")
    planned = env["planner"].plan(parse(QUERIES[3]))
    prepared = px_ring.prepare(planned.plan)
    got = batch_rows_normalized(prepared.run(), planned.output_names)
    assert got == _rows(env["single"], planned)
    colls = {e.collective for e in prepared.mesh_plan.exchanges
             if e.kind == "broadcast"}
    assert colls == {"ppermute"}


# ------------------------------------------------- mesh-plan representation

@pytest.mark.multidevice
def test_mesh_plan_records_collectives(env):
    """The traced program's exchanges land in PreparedPlan.mesh_plan with
    collective names, bytes and lane capacities; the legacy triple log
    stays consistent with it (worker-span + peak-bytes consumers)."""
    planned = env["planner"].plan(parse(QUERIES[3]))
    prepared = env["px"].prepare(planned.plan)
    assert prepared.mesh_plan.total_ops == 0  # jit is lazy: not traced yet
    prepared.run()
    mp = prepared.mesh_plan
    assert mp.mesh_sig == mesh_signature(env["px"].mesh)
    assert mp.n_shards == env["n"]
    assert mp.total_ops == len(mp.exchanges) > 0
    assert mp.total_bytes > 0
    assert mp.host_hops == 0
    for e in mp.exchanges:
        assert e.collective == KIND_COLLECTIVE.get(e.kind, e.collective)
        assert e.lanes > 0 and e.lane_cap > 0 and e.nbytes > 0
    # describe() is the compact form the plan monitor shows
    parts = dict(p.split(":") for p in mp.describe().split(","))
    assert sum(int(v) for v in parts.values()) == mp.total_ops
    assert mp.ops_by_collective() == {k: int(v) for k, v in parts.items()}
    # legacy triples = exactly the data-moving exchanges (psum merge
    # bookkeeping is mesh-plan-only)
    want_legacy = [(e.kind, e.ncols, e.lane_cap) for e in mp.exchanges
                   if e.kind in ("broadcast", "repartition")]
    assert list(prepared.px_exchanges) == want_legacy
    # a re-run must NOT retrace/grow the plan
    n_ops = mp.total_ops
    prepared.run()
    assert mp.total_ops == n_ops


@pytest.mark.multidevice
def test_collective_counters_fold_into_metrics(env):
    m = MetricsRegistry()
    px = PxExecutor(env["tables"], make_mesh(env["n"]),
                    unique_keys=UNIQUE_KEYS, metrics=m)
    planned = env["planner"].plan(parse(QUERIES[6]))
    px.execute(planned.plan)
    snap = m.counters_snapshot()
    assert snap.get("px collective psum", 0) >= 1
    assert snap.get("px collective bytes", 0) > 0
    assert snap.get("px sharded upload bytes", 0) > 0
    assert snap.get("px dtl host hops", 0) == 0


# --------------------------------------------------------- compat shim

def test_shard_map_shim_tracks_pinned_jax():
    """Drift canary: the shim spells the installed jax's API —
    `jax.shard_map` with the `check_vma` replication check. A jax upgrade
    that renames either fails HERE, not deep inside a lowering."""
    import inspect

    assert "check_vma" in inspect.signature(jax.shard_map).parameters
    mesh = make_mesh(2)
    f = mesh_mod.shard_map_compat(
        lambda x: jax.lax.psum(x, mesh_mod.SHARD_AXIS), mesh=mesh,
        in_specs=mesh_mod.P(mesh_mod.SHARD_AXIS), out_specs=mesh_mod.P())
    assert int(jax.jit(f)(np.arange(2))[0]) == 1


def test_mesh_signature_identifies_geometry():
    devs = jax.devices()
    sig8 = mesh_signature(make_mesh(len(devs)))
    sig1 = mesh_signature(make_mesh(1, devices=devs[:1]))
    assert sig8 == ((len(devs),), ("shard",))
    assert sig1 == ((1,), ("shard",))
    assert sig8 != sig1


# ------------------------------------------------------- plan artifacts

@pytest.mark.multidevice
def test_artifact_mesh_shape_mismatch_recompiles(env, tmp_path):
    """An SPMD program exported on the 8-device mesh must key-mismatch
    (counted) when hydrated against a different mesh shape, and the
    caller's clean recompile must serve identical rows; the SAME shape
    hydrates warm with the saved exchange layout attached."""
    m = MetricsRegistry()
    store = PlanArtifactStore(str(tmp_path / "art"), mode="rw", metrics=m)
    planned = env["planner"].plan(parse(QUERIES[6]))
    want = _rows(env["px"], planned)

    prepared = env["px"].prepare(planned.plan)
    prepared.run()  # trace: populates the mesh plan the export captures
    aid = store.save(("q6", env["n"]), prepared,
                     output_names=planned.output_names, dtypes=[],
                     tables=("lineitem",))
    assert aid is not None

    half = max(1, env["n"] // 2)
    px_half = PxExecutor(env["tables"],
                         make_mesh(half, devices=jax.devices()[:half]),
                         unique_keys=UNIQUE_KEYS)
    assert store.hydrate(aid, px_half) is None
    assert m.counters_snapshot().get("plan artifact mesh mismatch", 0) == 1
    # the rejection path's contract: a clean recompile, identical rows
    assert _rows(px_half, planned) == want

    px_same = PxExecutor(env["tables"], make_mesh(env["n"]),
                         unique_keys=UNIQUE_KEYS)
    got = store.hydrate(aid, px_same)
    assert got is not None
    meta, warm = got
    assert tuple(meta.mesh_sig) == mesh_signature(env["px"].mesh)
    assert warm.mesh_plan.total_ops > 0      # layout restored, no retrace
    assert list(warm.px_exchanges) == list(prepared.px_exchanges)
    assert batch_rows_normalized(warm.run(),
                                 planned.output_names) == want


# --------------------------------------------- residency + governor + DTL

@pytest.mark.multidevice
def test_sharded_residency_charges_governor_per_device(env):
    px = PxExecutor(env["tables"], make_mesh(env["n"]),
                    unique_keys=UNIQUE_KEYS)
    planned = env["planner"].plan(parse(QUERIES[6]))
    px.execute(planned.plan)
    total = px.residency.total_bytes()
    assert total > 0
    assert px.residency.per_device_bytes() == total // env["n"]
    assert "lineitem" in px.residency.tables()

    gov = MemoryGovernor(budget=64 << 20)
    gov.register_sharded_residency(px.residency.per_device_bytes)
    gov.register_sharded_residency(px.residency.per_device_bytes)  # idempotent
    assert gov.sharded_resident_bytes() == px.residency.per_device_bytes()
    assert gov.remaining() == gov.budget - px.residency.per_device_bytes()
    assert gov.stats()["sharded_resident"] == px.residency.per_device_bytes()
    # lone-statement clause: a want that only fits by ignoring residency
    # must still be granted (it runs strictly alone, degrading if needed)
    r = gov.reserve("t", gov.budget - (1 << 10), timeout_s=0.1)
    assert r is not None
    r.release()

    px.invalidate_table("lineitem")
    assert "lineitem" not in px.residency.tables()
    assert px.residency.total_bytes() < total


@pytest.mark.multidevice
def test_streamed_chunks_are_the_only_host_hops(env):
    """Out-of-core PX (tiny device budget → chunk-streamed lineitem) pays
    one host-mediated DTL hop per chunk dispatch — and the counter
    proves the resident path above paid none."""
    m = MetricsRegistry()
    px = PxExecutor(env["tables"], make_mesh(env["n"]),
                    unique_keys=UNIQUE_KEYS, metrics=m,
                    # budget_scale multiplies this by the mesh size (8),
                    # so 32 KiB still lands well under Q6's ~688 KiB input
                    device_budget=32 << 10, chunk_rows=1 << 13)
    planned = env["planner"].plan(parse(QUERIES[6]))
    got = batch_rows_normalized(px.execute(planned.plan),
                                planned.output_names)
    assert got == _rows(env["single"], planned)
    n_chunks = -(-env["tables"]["lineitem"].nrows // (1 << 13))
    assert n_chunks >= 2
    assert m.counters_snapshot().get("px dtl host hops", 0) >= n_chunks


# ----------------------------------------------------------- spmd units

def test_spmd_lowering_reset_guards_retrace():
    low = SpmdLowering(((8,), ("shard",)), 8)
    low.note("broadcast", 3, 1024, 8)
    low.note("merge", 2, 64, 8, collective="psum", legacy=False)
    assert low.plan.total_ops == 2
    assert low.legacy_log == [("broadcast", 3, 1024)]
    low.reset()  # a retrace replays every note; reset keeps counts exact
    assert low.plan.total_ops == 0 and low.legacy_log == []
    low.note("repartition", 2, 512, 64)
    assert low.plan.describe() == "all_to_all:1"
    assert low.plan.total_bytes == 2 * 512 * 64 * 8
