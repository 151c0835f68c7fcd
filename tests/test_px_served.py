"""The parallel deployment as it is served: `ALTER SYSTEM SET ob_px_dop`
routes every new session's SELECTs through `PxExecutor` on the mesh of the
host's devices, over the wire, and the answers are held to the benchmark's
own plain reference (`benchmark/generators/tpch.py`, numpy, nothing of the
program) by the comparison that decides `correct` (`harness/check.py`), not
to the same code at `ob_px_dop = 0`. The cell `tpch-sf1-px4.join` runs this
path on four chips; here it runs on the CPU's host devices at SF 0.01.
"""

from __future__ import annotations

import json
import os
import re

import jax
import pytest

from benchmark.generators import tpch
from benchmark.harness import check
from benchmark.harness.server import CompileMeter, Served
from benchmark.harness.wire import WireClient, WireError
from benchmark.tests import test_px_fault as px_fault
from oceanbase_tpu.parallel import px as PX
from oceanbase_tpu.server.async_front import AsyncMySqlFrontend
from oceanbase_tpu.server.database import Database, SqlError

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2_147_483_659  # past 31 bits, as the driver's seeds are
KINDS = ("q3", "q14")


def load_json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


CONFIG = dict(load_json("benchmark", "configs", "tpch-sf1-px4.json"),
              scale_factor=0.01)
LIMIT = float(CONFIG["correct"]["rel_err_max"])
POOLS = tpch.pools(load_json("benchmark", "traffic", "join.json"), CONFIG,
                   SEED)


class Deployment:
    """`harness/server.py`'s `Served` without its process-wide compile
    cache: the same boot, settings over the wire, DDL + direct_load."""

    def __init__(self, witness: bool = False):
        self.db = Database(n_nodes=int(CONFIG["cluster"]["replicas"]),
                           n_ls=int(CONFIG["cluster"]["log_streams"]))
        self.front = AsyncMySqlFrontend(self.db).start()
        self.port = self.front.port
        self.data = tpch.generate(CONFIG, SEED)
        if witness:
            px_fault.plant_witness(self.data, len(jax.devices()))
        self.admin = WireClient(self.port)
        Served.apply_settings(self, self.admin, CONFIG)
        Served.load(self, self.admin, tpch, CONFIG, self.data)

    def counter(self, name: str) -> int:
        rows = self.admin.query("select value from __all_virtual_sysstat "
                                f"where name = '{name}'")
        return int(rows[0][0]) if rows else 0

    def reference_of(self, kind, lit):
        return tpch.reference(kind, lit, self.data)

    def send_pool(self, client):
        """Every pool member once, as the load generator records them."""
        return [(k, lit, 0.0, 0.0, client.query(tpch.render(k, lit)), 0)
                for k in KINDS for lit in POOLS[k]]

    def close(self):
        self.admin.close()
        Served.free(self)


@pytest.fixture(scope="module")
def deployment():
    d = Deployment()
    yield d
    d.close()


# ---- (a) the tenant parameter seeds the session variable --------------------


def test_alter_system_seeds_new_sessions_only():
    db = Database(n_nodes=1, n_ls=1)
    try:
        open_before = db.session()
        assert open_before._vars["ob_px_dop"] == 0  # the product's default
        open_before.sql("alter system set ob_px_dop = 4")
        assert open_before._vars["ob_px_dop"] == 0, "an open session moved"
        fresh = db.session()
        assert fresh._vars["ob_px_dop"] == 4
        fresh.sql("set ob_px_dop = 0")  # SET still overrides per session
        assert fresh._vars["ob_px_dop"] == 0
        assert db.session()._vars["ob_px_dop"] == 4
        # the dead knob in whose place this one came
        with pytest.raises(SqlError):
            fresh.sql("alter system set ob_sql_parallel_degree = 8")
    finally:
        db.close()


# ---- (b) served over the wire, against the plain reference -------------------


def test_served_px_equals_plain_reference(deployment):
    d = deployment
    meter = CompileMeter()
    client = WireClient(d.port)  # opened after the ALTER SYSTEM: dop 4
    try:
        runs0, fb0 = d.counter("px executions"), d.counter("px fallbacks")
        first = d.send_pool(client)
        compiled = meter.read()[0]
        second = d.send_pool(client)
        assert meter.read()[0] == compiled, (
            "the second pass over the pool compiled")
        verdict = check.judge(first + second, d.reference_of, LIMIT)
        assert verdict["correct"], (verdict["compared"], verdict["first_bad"])
        assert verdict["compared"]["rel_err_max"]["value"] <= LIMIT
        sent = len(first) + len(second)
        assert sent == 2 * sum(len(POOLS[k]) for k in KINDS) == 32
        assert d.counter("px executions") - runs0 == sent
        assert d.counter("px fallbacks") - fb0 == 0
        assert d.counter("px overflow recompiles") == 0
        assert d.counter("px collective all_to_all") > 0  # Q3's hash lanes
        assert d.counter("px collective psum") > 0        # Q14's merge
        # every build is a table in storage order: direct-address joins
        assert d.counter("merge join scan-carried") == 0
    finally:
        client.close()
    # the admin connection was open before the ALTER SYSTEM: one chip
    runs = d.counter("px executions")
    d.admin.query("select count(*) from nation")
    assert d.counter("px executions") == runs


def test_px_failure_is_the_statements_error(deployment, monkeypatch):
    """No re-run on one chip behind the operator's back: what breaks in the
    PX compile reaches the client."""
    def broken(*_a, **_kw):
        raise RuntimeError("planted: the exchange cannot be built")

    monkeypatch.setattr(PX, "broadcast_rows", broken)
    client = WireClient(deployment.port)
    try:
        # a statement shape no earlier test compiled
        with pytest.raises(WireError, match="planted"):
            client.query("select o_orderpriority, count(*) from orders, "
                         "customer where o_custkey = c_custkey and "
                         "c_acctbal > 0 group by o_orderpriority")
    finally:
        client.close()


# ---- (c) the planted fault: an exchange between chips left out ---------------


@pytest.mark.parametrize("left_out,witness", sorted(
    px_fault.CASES, key=lambda c: (c[1], c[0] or "")))
def test_fault_exchange_left_out(monkeypatch, left_out, witness):
    """The fault a one-chip cell cannot have, and what the cell's comparison
    holds of it (the twin of `benchmark/tests/test_px_fault.py`, which says
    why): the broadcast left out reads `correct` false on the cell's own
    data; Q3's hash lanes left out do not, and do once the witness order
    straddles a chip boundary. Each case is a deployment of its own: the
    plans compile with the fault underneath."""
    if left_out:
        monkeypatch.setattr(PX, left_out, px_fault.LEFT_OUT[left_out])
    d = Deployment(witness=witness)
    client = WireClient(d.port)
    try:
        verdict = check.judge(d.send_pool(client), d.reference_of, LIMIT)
    finally:
        client.close()
        d.close()
    assert verdict["correct"] == px_fault.CASES[left_out, witness], (
        verdict["compared"])


# ---- (d) an exchange is named in the program ---------------------------------


def lowered_px_text(db, sql: str) -> str:
    """Lowered text (with its `loc` names) of the statement's PX program."""
    from oceanbase_tpu.sql import parser as P
    from oceanbase_tpu.sql.plan_cache import bind, parameterize

    pz = parameterize(db.engine.planner.plan(P.parse(sql)).plan)
    prepared = db._px_executor().prepare(pz.plan)
    lowered = prepared.jitted.lower(prepared._inputs(),
                                    bind(pz.values, pz.dtypes))
    return lowered.as_text(debug_info=True)


def test_exchange_scopes_in_lowered_text(deployment):
    db = deployment.db
    q3 = lowered_px_text(db, tpch.render("q3", POOLS["q3"][0]))
    # the hash lane's scope holds its collective and the lane packing
    assert re.search(r"Exchange:hash#\d+/[^\"]*all_to_all", q3)
    assert re.search(r"Exchange:hash#\d+/[^\"]*sort", q3)
    assert re.search(r"Exchange:broadcast#\d+/[^\"]*all_gather", q3)
    # Q3's root is its top-n, replicated already: the last gather is the
    # top-n's, of ten rows a chip, inside the node that asked for it
    assert re.search(r"TopN#\d+/Exchange:gather#\d+/[^\"]*all_gather", q3)
    assert "Exchange:gather#0/" not in q3
    q14 = lowered_px_text(db, tpch.render("q14", POOLS["q14"][0]))
    assert re.search(r"Exchange:merge#\d+/[^\"]*psum", q14)
    assert "Exchange:hash#" not in q14  # Q14 repartitions nothing
    # a statement whose root is still sharded gathers it at the root, on
    # the lane of the root node's id (0): digits, as the accepted reader
    # of scopes wants after `#` (`benchmark/harness/spans.py` NODE)
    rows = lowered_px_text(db, "select o_orderkey from orders "
                               "where o_totalprice > 400000")
    assert re.search(r"Exchange:gather#0/[^\"]*all_gather", rows)
    from benchmark.harness import spans

    assert all(spans.NODE.match(m) for m in set(re.findall(
        r"Exchange:[a-z]+#\w+", q3 + q14 + rows)))


# ---- (e) collective_ms_per_stmt's reader -------------------------------------


def read_collective_ms(ctx):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "collective_ms_per_stmt", os.path.join(
            ROOT, "benchmark", "layer_metrics", "collective_ms_per_stmt.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def test_collective_ms_per_stmt_reader():
    from benchmark.harness import trace as T

    ms = 1_000_000
    ops = [(dev, name, s * ms, d * ms) for dev in range(4) for name, s, d in [
        ("%fusion.7 = s32[8]{0} fusion(...), kind=kLoop", 0, 10),
        ("%all_to_all.3 = s32[4,128]{1,0} all-to-all(...)", 10, 3),
        ("%all-to-all.4 = s32[4,128]{1,0} all-to-all(...)", 13, 1),
        ("%all-gather-start.1 = (s32[8], s32[32]) all-gather-start(...)", 14, 1),
        ("%all-gather-done.1 = s32[32]{0} all-gather-done(...)", 20, 2),
        ("%all-reduce.9 = s64[3]{0} all-reduce(...)", 110, 1),
        ("%collective-permute.2 = s32[8]{0} collective-permute(...)", 112, 2),
        ("%sort.4 = (s32[8], s32[8]) sort(...)", 120, 5)]]
    ev = {"windows": [("q3", 0, 100 * ms), ("q14", 100 * ms, 200 * ms)],
          "ops": ops, "modules": [], "host": []}
    red = T.reduce_events(ev, 4)
    ctx = {"trace": red, "traced_statements": {"q3": 4, "q14": 6}}
    # mean over the chips: 4 + 1 + 2 in q3's window, 1 + 2 in q14's, of 10
    assert read_collective_ms(ctx) == pytest.approx((7 + 3) / 10)
    quiet = T.reduce_events(dict(ev, ops=[o for o in ops if "fusion" in o[1]
                                          or "sort" in o[1]]), 4)
    assert read_collective_ms(dict(ctx, trace=quiet)) == 0.0
    assert read_collective_ms(dict(ctx, trace=None)) is None
    assert read_collective_ms({"traced_statements": {}}) is None


def test_px_roofline_is_join_roofline_over_four_chips():
    from benchmark.harness import layer

    spec = load_json("benchmark", "layer_metrics", "px_roofline.json")
    one = load_json("benchmark", "layer_metrics", "join_roofline.json")
    red = {"per_kind": {"q3": {"window_s": 3.0, "busy_s": 2.0},
                        "q14": {"window_s": 3.0, "busy_s": 1.0}}}
    ctx = {"trace": red, "traced_necessary_s": {"q3": 0.006, "q14": 0.003},
           "traced_statements": {"q3": 10, "q14": 30}}
    assert layer.evaluate(spec, ctx) == pytest.approx(
        layer.evaluate(one, ctx) / 4)
    assert layer.evaluate(spec, dict(ctx, trace=None)) is None
