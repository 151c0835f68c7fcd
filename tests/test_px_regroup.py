"""TPC-H Q10 on the parallel deployment as it is served, held to the
benchmark's own plain reference (`benchmark/generators/tpch_regroup.py`:
numpy, nothing of the program) by the comparison that decides `correct`
(`harness/check.py`). Q10 groups `lineitem ⋈ orders` by the customer, whose
orders lie on every chip: the hash exchange before the group-by moves every
group, so with it left out the answers are wrong on the cell's own data.
The cell `tpch-sf1-px4-regroup.q10` runs this path on four chips; here it
runs on the CPU's host devices at SF 0.01.
"""

from __future__ import annotations

import json
import os
import re
import sqlite3

import numpy as np
import pytest

from benchmark.generators import tpch_regroup as gen
from benchmark.harness import check
from benchmark.harness.server import CompileMeter, Served
from benchmark.harness.wire import WireClient
from benchmark.tests import test_regroup_fault as fault
from oceanbase_tpu.parallel import px as PX
from oceanbase_tpu.server.async_front import AsyncMySqlFrontend
from oceanbase_tpu.server.database import Database
from test_px_served import lowered_px_text

pytestmark = pytest.mark.multidevice

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2_147_483_659  # past 31 bits, as the driver's seeds are


def load_json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


CONFIG = dict(load_json("benchmark", "configs", "tpch-sf1-px4-regroup.json"),
              scale_factor=0.01)
TRAFFIC = load_json("benchmark", "traffic", "q10.json")
LIMIT = float(CONFIG["correct"]["rel_err_max"])


class Deployment:
    """`tests/test_px_served.py`'s: `harness/server.py`'s `Served` without
    its process-wide compile cache, the same boot, settings over the wire,
    DDL + direct_load; the data and the pool are the cell's for the seed."""

    def __init__(self):
        self.db = Database(n_nodes=int(CONFIG["cluster"]["replicas"]),
                           n_ls=int(CONFIG["cluster"]["log_streams"]))
        self.front = AsyncMySqlFrontend(self.db).start()
        self.port = self.front.port
        self.data = gen.generate(CONFIG, SEED)
        self.pool, self.rejected = gen.draw_pools(
            TRAFFIC, CONFIG, SEED, self.data)
        self.admin = WireClient(self.port)  # before the ALTER SYSTEM: dop 0
        Served.apply_settings(self, self.admin, CONFIG)
        Served.load(self, self.admin, gen, CONFIG, self.data)

    def counter(self, name: str) -> int:
        rows = self.admin.query("select value from __all_virtual_sysstat "
                                f"where name = '{name}'")
        return int(rows[0][0]) if rows else 0

    def reference_of(self, kind, lit):
        return gen.reference(kind, lit, self.data)

    def send_pool(self, client):
        """The warm-up's statements once (the validation literal, then
        every pool member), as the load generator records them."""
        return [(k, lit, 0.0, 0.0, client.query(text), 0)
                for k, lit, text in gen.warmup(TRAFFIC, CONFIG, self.pool)]

    def close(self):
        self.admin.close()
        Served.free(self)


@pytest.fixture(scope="module")
def deployment():
    d = Deployment()
    yield d
    d.close()


def test_pool_is_the_load_generators(deployment):
    """The load generator's process has no data: `pools` makes it from the
    seed, and gets the pool the deployment's own data gives."""
    assert gen.pools(TRAFFIC, CONFIG, SEED) == deployment.pool
    assert len(deployment.pool["q10"]) == 8 and not deployment.rejected
    first = {lit["date"] for lit in deployment.pool["q10"]}
    assert len(first) == 8 and all(
        d.endswith("-01") and "1993-02" <= d[:7] <= "1995-01" for d in first)


def test_served_px_equals_plain_reference(deployment):
    d = deployment
    meter = CompileMeter()
    client = WireClient(d.port)  # opened after the ALTER SYSTEM: dop 4
    try:
        before = {n: d.counter(n) for n in (
            "px executions", "px fallbacks", "px overflow recompiles",
            "px exchange rows", "px exchange slots")}
        first = d.send_pool(client)
        compiled = meter.read()[0]
        second = d.send_pool(client)
        assert meter.read()[0] == compiled, (
            "the second pass over the pool compiled")
        verdict = check.judge(first + second, d.reference_of, LIMIT)
        assert verdict["correct"], (verdict["compared"], verdict["first_bad"])
        assert verdict["compared"]["rel_err_max"]["value"] <= LIMIT
        sent = len(first) + len(second)
        assert sent == 18 and first[0][1] == gen.VALIDATION["q10"]
        moved = {n: d.counter(n) - v for n, v in before.items()}
        assert moved["px executions"] == sent
        assert moved["px fallbacks"] == 0
        assert moved["px overflow recompiles"] == 0
        assert d.counter("px collective all_to_all") > 0  # the hash lanes
        # lane occupancy: what the exchanges delivered over what they hold
        assert 0 < moved["px exchange rows"] <= moved["px exchange slots"]
        # one compiled plan dropped five of Q10's seven group keys
        assert d.counter("group keys dependent") == 5
        # the same occupancy per plan, where the cell's probe asks for it
        # (`tpch_regroup.PROBE`: the first statement of the load)
        assert gen.ddl(CONFIG)[0][1][0] == gen.PROBE
        per_plan = [(int(r), int(s)) for r, s in d.admin.query(
            gen.PROBE.replace(" limit 1", " where px_exchange_slots > 0"))]
        assert sum(r for r, _s in per_plan) == moved["px exchange rows"]
        assert sum(s for _r, s in per_plan) == moved["px exchange slots"]
    finally:
        client.close()


def test_one_chip_equals_plain_reference(deployment):
    """The same pool at `ob_px_dop = 0` (the admin connection, open before
    the ALTER SYSTEM) against the same reference."""
    d = deployment
    runs = d.counter("px executions")
    verdict = check.judge(d.send_pool(d.admin), d.reference_of, LIMIT)
    assert verdict["correct"], (verdict["compared"], verdict["first_bad"])
    assert d.counter("px executions") == runs


def test_float32_reference_is_not_correct(deployment):
    """The control: the reference summed in the precision below is seen by
    the cell's limit (PERF.md section 2 has the reading at the cell's size)."""
    d = deployment
    records = [("q10", lit, 0.0, 0.0,
                [tuple(str(v) for v in row) for row in gen.reference(
                    "q10", lit, d.data, acc=np.float32)], 0)
               for lit in d.pool["q10"]]
    verdict = check.judge(records, d.reference_of, LIMIT)
    assert not verdict["correct"]
    assert verdict["compared"]["wrong_answers"]["value"] == 0
    assert verdict["compared"]["rel_err_max"]["value"] > 100 * LIMIT


def test_reference_equals_sqlite_at_the_validation_literal(deployment):
    """The plain reference against an engine that shares nothing with it or
    with the program: sqlite over the same generated rows."""
    data = deployment.data
    conn = sqlite3.connect(":memory:")
    day0 = np.datetime64("1970-01-01", "D")
    money = {"l_extendedprice", "c_acctbal"}
    for table, cols in gen.reference_columns(CONFIG).items():
        decoded = []
        for c in cols:
            v = gen.as_strings(data[table][c])
            if c == "o_orderdate":
                v = [str(day0 + int(x)) for x in v]
            elif c in money or c == "l_discount":
                v = [int(x) for x in v]  # cents, hundredths: exact in sqlite
            else:
                v = v.tolist()
            decoded.append(v)
        conn.execute(f"create table {table} ({', '.join(cols)})")
        conn.executemany(
            f"insert into {table} values ({','.join('?' * len(cols))})",
            list(zip(*decoded)))
    lit = gen.VALIDATION["q10"]
    text = gen.render("q10", lit).replace("date '", "'").replace(
        "(1 - l_discount)", "(100 - l_discount)").replace(
        "order by revenue desc", "order by revenue desc, c_custkey")
    want = conn.execute(text).fetchall()
    got = gen.reference("q10", lit, data)
    assert len(got) == len(want) == 20
    for g, w in zip(got, want):
        assert g[0] == w[0] and g[1] == w[1] and g[4:] == tuple(w[4:])
        assert g[2].scaleb(4) == w[2]  # revenue in units of 10^-4
        assert g[3].scaleb(2) == w[3]  # c_acctbal in cents


def test_sort_merge_joins_gather_nothing():
    """At SF 1 `orders` meets `customer` through hash lanes and `lineitem`
    probes that join's result, so neither build is in storage order and
    both joins sort-merge (`merge_join_unique`); at SF 0.01 every build is
    under the broadcast threshold and joins by direct address. Lowering
    the threshold steers this deployment into SF 1's plan: the answers
    still equal the plain reference, the counter reads two such joins in
    the one compiled program, and the program has no 1-D element gather
    over a join's combined build ++ probe entries (there were three a
    join: the run head's side, its row, and the way back to probe order)."""
    d = Deployment()
    d.db._px_executor().broadcast_threshold = 1 << 10
    client = WireClient(d.port)
    try:
        verdict = check.judge(d.send_pool(client), d.reference_of, LIMIT)
        assert verdict["correct"], (verdict["compared"], verdict["first_bad"])
        programs = 1 + d.counter("px overflow recompiles")
        assert d.counter("merge join scan-carried") == 2 * programs
        text = lowered_px_text(
            d.db, gen.render("q10", gen.VALIDATION["q10"]))
    finally:
        client.close()
        d.close()
    scope = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))

    def node(loc):  # the plan node an op was emitted under
        return scope[loc].rsplit("/", 1)[0]

    # each join's combined sort: (dead, key, side, row) over build ++ probe
    combined = {node(loc): int(n) for n, loc in re.findall(
        r"^ *\}\) : \(tensor<(\d+)xi32>, tensor<\1xi64>, tensor<\1xi32>, "
        r"tensor<\1xi32>\) -> [^\n]* loc\((#loc\d+)\)$", text, re.M)}
    assert len(combined) == 2, combined
    assert all(re.search(r"Join:inner#\d+$", at) for at in combined)
    flat_gathers = [(node(loc), int(n)) for n, loc in re.findall(
        r'"stablehlo\.gather"[^\n]*: \(tensor<(\d+)xi\d+>, '
        r"tensor<\d+x1xi32>\) -> tensor<\d+xi\d+> loc\((#loc\d+)\)", text)]
    assert flat_gathers  # the reader finds 1-D gathers where there are some
    assert not [g for g in flat_gathers if combined.get(g[0]) == g[1]]


@pytest.mark.parametrize("left_out", sorted(fault.LEFT_OUT))
def test_fault_exchange_left_out(monkeypatch, left_out):
    """The planted fault, on the cell's own data (the twin of
    `benchmark/tests/test_regroup_fault.py`, which runs the whole cell):
    with the hash lanes returning their input a customer's revenue comes
    back as one partial sum per chip; with the broadcast returning its input
    the joins lose the build rows of the other chips. Both read `correct`
    false. Each case is a deployment of its own: the plans compile with the
    fault underneath."""
    monkeypatch.setattr(PX, left_out, fault.LEFT_OUT[left_out])
    d = Deployment()
    client = WireClient(d.port)
    try:
        verdict = check.judge(d.send_pool(client), d.reference_of, LIMIT)
    finally:
        client.close()
        d.close()
    assert not verdict["correct"], verdict["compared"]
    assert verdict["compared"]["wrong_answers"]["value"] > 0
