"""The one file that compiles for the chip: the served path's main device
programs, lowered against a described (not attached) `v5e:2x2` and handed
to the installed TPU compiler. No chip is involved and nothing runs there;
what this guards is that the compiler still ACCEPTS these programs — a
refusal is an exception here, a crash (TPC-H Q15 before PR 22) kills the
worker, and either fails the file.

Rules (on-chip-measurement guide, section 2): the topology is described
inside a module-scoped fixture of this file, never at import, in a skipif
or in a parametrize; every compile happens in the test's own process (the
worker that described the topology holds the TPU library); the persistent
compilation cache is off around the compiles (an entry compiled for a
described chip cannot be read back without one). The CPU runs first, so
each program is compiled at its settled capacities.
"""

import re

import jax
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache as cc
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from oceanbase_tpu.core.dtypes import DataType, Field, Schema, TypeKind
from oceanbase_tpu.core.table import Table
from oceanbase_tpu.engine import Session
from oceanbase_tpu.engine.executor import packed_width
from oceanbase_tpu.models.tpch import datagen
from oceanbase_tpu.models.tpch.sql_suite import QUERIES, UNIQUE_KEYS
from oceanbase_tpu.parallel.mesh import SHARD_AXIS, make_mesh
from oceanbase_tpu.parallel.px import PxExecutor
from oceanbase_tpu.share.metrics import MetricsRegistry
from oceanbase_tpu.sql.parser import parse
from oceanbase_tpu.sql.plan_cache import bind, parameterize
from oceanbase_tpu.storage.vector_index import register_vector_index


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip(topo):
    """shapes(tree): every array leaf as a ShapeDtypeStruct placed on the
    described chip (row-sharded leaves keep their spec on its mesh)."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    mesh = Mesh(np.array(topo.devices), (SHARD_AXIS,))

    def on_chip(a):
        sh = getattr(a, "sharding", None)
        if isinstance(sh, NamedSharding):
            sh = NamedSharding(mesh, sh.spec)
        else:
            sh = one_chip
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh)

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield lambda tree: jax.tree_util.tree_map(on_chip, tree), mesh
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def tpch():
    return datagen.generate(0.01)


def _compile(fn, *shapes):
    compiled = fn.lower(*shapes).compile()
    assert compiled.memory_analysis().generated_code_size_in_bytes > 0
    return compiled


def _entry(sess, text):
    """Run once on the CPU (capacities settle), hand back the cached
    plan with its bound parameter block."""
    sess.sql(text).rows()
    entry, qp = sess.cached_entry(text)
    assert entry is not None
    return entry.prepared, qp


@pytest.fixture(scope="module")
def q3_v5e(chip, tpch):
    """(prepared, v5e text) of Q3's plan, compiled once for the tests
    that read it."""
    shapes, _ = chip
    sess = Session(tpch, unique_keys=UNIQUE_KEYS)
    prepared, qp = _entry(sess, QUERIES[3])
    compiled = _compile(
        prepared.jitted, shapes(prepared._inputs()), shapes(qp))
    return prepared, compiled.as_text()


@pytest.mark.parametrize("q", [6, 1, 14, 3, 15])
def test_tpch_plan_compiles_for_v5e(chip, tpch, q, request):
    if q == 3:
        request.getfixturevalue("q3_v5e")
        return
    shapes, _ = chip
    sess = Session(tpch, unique_keys=UNIQUE_KEYS)
    prepared, qp = _entry(sess, QUERIES[q])
    _compile(prepared.jitted, shapes(prepared._inputs()), shapes(qp))


def test_q3_aggregate_gathers_one_bound_on_v5e(q3_v5e):
    """datagen's orders lie in key order and every l_orderkey has its
    order, so Q3's clustered-FK ranges tile and the aggregate's lower
    bounds are its upper bounds shifted: the v5e text has ONE gather under
    the Aggregate node (two where the ranges do not tile)."""
    prepared, text = q3_v5e
    (spec,) = prepared.params.clustered_aggs.values()
    assert spec.tiled
    gathers = re.findall(r' gather\(.*op_name="([^"]*)"', text)
    # the innermost plan node of the op's scope (the aggregate emits the
    # joins below it, whose own gathers are theirs)
    own = [n for n in gathers
           if re.findall(r"/([A-Za-z:]+)#\d+", n)[-1:] == ["Aggregate"]]
    assert len(own) == 1, gathers


@pytest.fixture(scope="module")
def point_read():
    n = 20_000
    ids = np.arange(1, n + 1)
    k = np.random.default_rng(7).permutation(n)
    I32 = DataType.int32()
    kv = Table.from_pydict("kv", Schema((
        Field("id", I32), Field("k", I32), Field("v", I32),
        Field("grp", I32))), {"id": ids, "k": k, "v": k % 977, "grp": ids % 16})
    sess = Session({"kv": kv}, unique_keys={"kv": (("id",),)})
    prepared, qp = _entry(sess, "select v from kv where k = 17")
    return prepared, qp


def test_point_read_compiles_for_v5e(chip, point_read):
    shapes, _ = chip
    prepared, qp = point_read
    _compile(prepared.jitted, shapes(prepared._inputs()), shapes(qp))


def test_batched_bucket_compiles_for_v5e(chip, point_read):
    """The batcher's pow2 bucket: the plan vmapped over the packed
    parameter block (PreparedPlan.run_batched_host)."""
    shapes, _ = chip
    prepared, qp = point_read
    prepared.run_batched_host(np.stack([np.asarray(qp)] * 4))
    qblock = np.zeros((4, packed_width(prepared._qparam_spec)), np.int64)
    _compile(prepared._batched[4], shapes(prepared._inputs()), shapes(qblock))


def test_narrow_frame_compiles_for_v5e(chip, point_read):
    """Whole-statement fusion: plan program + result-frame gather in one
    executable (PreparedPlan._build_narrow), the warm served dispatch.
    The frame finds its rows by `ops/compact.py` `live_positions`: no
    scatter is left under the `frame` scope of the v5e text (PR 36)."""
    shapes, _ = chip
    prepared, qp = point_read
    assert prepared._narrow, "the CPU run did not take the fused frame"
    for fn in prepared._narrow.values():
        text = _compile(fn, shapes(prepared._inputs()), shapes(qp)).as_text()
        frame = [ln for ln in text.splitlines()
                 if re.search(r'op_name="[^"]*/frame/', ln)]
        assert frame, "no op of the v5e text names the frame scope"
        assert not [ln for ln in frame if re.search(r"\bscatter\(", ln)]


def test_q14_scopes_survive_the_v5e_compiler(chip, tpch):
    """The names are HLO metadata: the v5e text of Q14's served program
    (plan + result frame) keeps the plan-node, `expr` and `frame` scopes
    as `op_name`s, and is called `jit_ob_select_<fingerprint>_narrow`.
    What the device's trace shows of them only a chip run can say."""
    shapes, _ = chip
    sess = Session(tpch, unique_keys=UNIQUE_KEYS)
    prepared, qp = _entry(sess, QUERIES[14])
    assert prepared._narrow, "the CPU run did not take the fused frame"
    fn = next(iter(prepared._narrow.values()))
    text = _compile(fn, shapes(prepared._inputs()), shapes(qp)).as_text()
    assert re.match(r"HloModule jit_ob_select_[0-9a-f]{8}_narrow\b", text)
    ops = re.findall(r'op_name="([^"]*)"', text)
    for want in (r"/Join:inner#\d+/", r"/Aggregate#\d+/",
                 r"/Aggregate#\d+/expr/", r"/frame/"):
        assert any(re.search(want, n) for n in ops), want


def test_dict_lookup_limit_compiles_for_v5e_without_a_gather(chip):
    """dict_lookup at its limit, LOOKUP_MAX_RUNS runs, over lineitem's
    rows at SF 1 and fused as Q14 uses it: the v5e compiler takes it and
    emits no gather."""
    from oceanbase_tpu.expr import compile as C

    shapes, _ = chip
    codes = jax.ShapeDtypeStruct((6_000_640,), np.int32)
    price = jax.ShapeDtypeStruct((6_000_640,), np.int64)
    table = np.arange(4 * C.LOOKUP_MAX_RUNS) % 4 == 1
    reg = MetricsRegistry()
    prev = C.set_lookup_metrics(reg)
    try:
        fn = jax.jit(lambda c, v: jax.numpy.sum(
            jax.numpy.where(C.dict_lookup(table, c), v, 0)))
        text = _compile(fn, *shapes((codes, price))).as_text()
    finally:
        C.set_lookup_metrics(prev)
    assert reg.counter("dict lookup runs") == 1
    assert " gather(" not in text


def test_two_sort_merge_joins_compile_for_v5e_without_a_gather(chip):
    """Two `merge_join_unique` in one program, as Q10's PX plan holds them:
    the v5e compiler takes both (two 64-bit `lax.cummax` in one program
    are a SIGSEGV in it, so each run head's row rides a two-plane int32
    scan) and the program is two sorts and one scan a join, no gather."""
    from oceanbase_tpu.ops.join import merge_join_unique

    shapes, _ = chip

    def side(n):
        return (jax.ShapeDtypeStruct((n,), np.int64),
                jax.ShapeDtypeStruct((n,), np.bool_))

    fn = jax.jit(lambda b1, p1, b2, p2: (
        merge_join_unique(*b1, *p1), merge_join_unique(*b2, *p2)))
    text = _compile(fn, *shapes(
        (side(4096), side(8192), side(2048), side(6144)))).as_text()
    assert " gather(" not in text
    assert len(re.findall(r" sort\(", text)) == 4
    assert " reduce-window(" in text


def test_shared_bounds_aggregate_compiles_for_v5e_with_one_gather(chip):
    """The clustered-FK aggregate's bounds at the join cell's shapes
    (lineitem's 6,000,640 rows, orders' 1,500,160 groups, a row count and
    one 64-bit sum as Q3 has them) with the ranges proved tiling: the v5e
    compiler takes the shift and the program gathers once."""
    from oceanbase_tpu.engine.executor import segment_bounds
    from oceanbase_tpu.expr import compile as C

    shapes, _ = chip
    jnp = jax.numpy
    rows = jax.ShapeDtypeStruct((6_000_640,), np.bool_)
    price = jax.ShapeDtypeStruct((6_000_640,), np.int64)
    bound = jax.ShapeDtypeStruct((1_500_160,), np.int32)

    def agg(live, v, starts, ends):
        running = {"#cnt": jnp.cumsum(live.astype(jnp.int64)),
                   0: jnp.cumsum(jnp.where(live, v, 0))}
        at_hi, at_lo = segment_bounds(running, starts, ends, tiled=True)
        return [jnp.where(ends > 0, at_hi[k], 0)
                - jnp.where(starts > 0, at_lo[k], 0) for k in running]

    reg = MetricsRegistry()
    prev = C.set_lookup_metrics(reg)
    try:
        text = _compile(
            jax.jit(agg), *shapes((rows, price, bound, bound))).as_text()
    finally:
        C.set_lookup_metrics(prev)
    assert reg.counter("clustered agg bounds shared") == 1
    assert reg.counter("clustered agg bounds gathered") == 0
    assert text.count(" gather(") == 1


def test_filtered_knn_compiles_for_v5e(chip):
    """Predicate + IVF probe + re-rank + top-k in one program."""
    shapes, _ = chip
    n, d = 20_000, 128
    rng = np.random.default_rng(4)
    centers = rng.normal(size=(256, d)).astype(np.float32) * 4
    x = centers[rng.integers(0, 256, n)] + rng.normal(
        size=(n, d)).astype(np.float32)
    ids = np.arange(n, dtype=np.int64)
    cat = {"docs": Table("docs", Schema((
        Field("id", DataType(TypeKind.INT64)),
        Field("grp", DataType(TypeKind.INT64)),
        Field("emb", DataType.vector(d)),
    )), {"id": ids, "grp": ids % 10, "emb": x})}
    register_vector_index(cat, "docs", "emb", lists=64, nprobe=2)
    sess = Session(cat)
    lit = "[" + ",".join(f"{v:.5f}" for v in x[3]) + "]"
    prepared, qp = _entry(
        sess, f"select id from docs where grp < 5 "
              f"order by vec_l2(emb, '{lit}') limit 10")
    assert prepared.params.vector_topns, "not routed through the IVF probe"
    _compile(prepared.jitted, shapes(prepared._inputs()), shapes(qp))


def test_px_q1_compiles_for_v5e_mesh(chip, tpch):
    """PX Q1 over the described four-chip mesh: inputs and capacities
    from a run on four virtual CPU devices, the program lowered by a
    second PxExecutor whose mesh is the chip's."""
    shapes, mesh = chip
    cpu_px = PxExecutor(tpch, make_mesh(4), unique_keys=UNIQUE_KEYS)
    sess = Session(tpch, unique_keys=UNIQUE_KEYS)
    pz = parameterize(sess.planner.plan(parse(QUERIES[1])).plan)
    prepared = cpu_px.prepare(pz.plan)
    qp = bind(pz.values, pz.dtypes)
    prepared.run(qparams=qp)
    chip_px = PxExecutor(tpch, mesh, unique_keys=UNIQUE_KEYS)
    jitted, _spec, _ovf = chip_px.compile(prepared.plan, prepared.params)
    rep = NamedSharding(mesh, PartitionSpec())
    qshapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype, sharding=rep),
        qp)
    compiled = _compile(jitted, shapes(prepared._inputs()), qshapes)
    text = compiled.as_text()
    assert "all-reduce" in text or "all-gather" in text, \
        "no collective in the four-chip program"
