"""Vector search on the served path, held to the benchmark's plain
reference (`benchmark/generators/ann_sift.py`: numpy, nothing of the
program) by its own judge: the deployment of the cell `ann-sift1m.knn` at
20,000 x 16 with IVF 64/8 on the CPU.

The table comes as a deployment makes it: DDL over the wire, the rows
through `direct_load` into the tablets of three replicas, CREATE VECTOR
INDEX, the index built from the tablets' rows by the first kNN statement.
Then the three kinds of the cell over the MySQL wire, one connection and
eight at once (the batcher's lanes), each answer judged: no wrong or
missing answer, recall@10 at or above the cell's limit, every batched lane
equal to its solo replay; the counters and the span the cell reads move.
"""

from __future__ import annotations

import json
import os
import threading

import numpy as np
import pytest

from benchmark.generators import ann_sift as gen
from benchmark.harness.server import Served
from benchmark.harness.wire import WireClient
from oceanbase_tpu.server.async_front import AsyncMySqlFrontend
from oceanbase_tpu.server.database import Database

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2_147_483_659  # past 31 bits, as a benchmark run's seed may be


def load_json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


CELL_CONFIG = load_json("benchmark", "configs", "ann-sift1m.json")
# the cell's configuration at tests/test_vector_serving.py's sizes
CONFIG = dict(CELL_CONFIG, rows=20000, dim=16, lists=64, nprobe=8,
              queries=400)
TRAFFIC = load_json("benchmark", "traffic", "knn.json")
LIMIT = float(CELL_CONFIG["correct"]["recall_at_10"])


class Deployment:
    """`harness/server.py`'s `Served` without its process-wide compile
    cache: the same boot, DDL + direct_load; the data and the query pool
    are the cell's for the seed."""

    def __init__(self):
        self.db = Database(n_nodes=int(CONFIG["cluster"]["replicas"]),
                           n_ls=int(CONFIG["cluster"]["log_streams"]))
        self.front = AsyncMySqlFrontend(self.db).start()
        self.port = self.front.port
        self.data = gen.generate(CONFIG, SEED)
        self.pool = gen.pools(TRAFFIC, CONFIG, SEED)
        self.admin = WireClient(self.port)
        self.took = Served.load(self, self.admin, gen, CONFIG, self.data)

    def counters(self) -> dict:
        return dict(self.db.metrics.counters_snapshot())

    def send(self, client, kind, lit):
        return (kind, lit, 0.0, 0.0,
                client.query(gen.render(kind, lit, self.pool["text"])), 0)

    def close(self):
        self.admin.close()
        Served.free(self)


@pytest.fixture(scope="module")
def deployment():
    d = Deployment()
    yield d
    d.close()


def _lits(kind, n, seed):
    rng = np.random.default_rng([seed, 17])
    return [gen._draw_lit(kind, rng, CONFIG["queries"]) for _ in range(n)]


def test_loaded_through_ddl_and_direct_load(deployment):
    d = deployment
    ti = d.db.tables[gen.TABLE]
    assert d.db._vector_specs[gen.TABLE] == {"emb": (64, 8)}
    (ls_id, tablet_id), = ti.all_partitions()
    reps = d.db.cluster.ls_groups[ls_id]
    assert len(reps) == 3
    for rep in reps.values():
        tab = rep.tablets[tablet_id]
        assert len(tab.deltas) == 1 and tab.deltas[0].nrows == 20000
        assert tab.active.nkeys == 0
    # the reference keeps what it reads, nothing of the program
    assert set(d.data[gen.TABLE]) == {"cat", "emb"}
    assert d.data["queries"].shape == (400, 16)
    assert gen.pools(TRAFFIC, CONFIG, SEED) == d.pool


def test_served_knn_equals_plain_reference(deployment):
    d = deployment
    c0 = d.counters()
    led0 = d.db.host_tax.snapshot()["digests"]
    client = WireClient(d.port)
    try:
        recs = [d.send(client, k, lit) for i, k in enumerate(gen.KINDS)
                for lit in _lits(k, 40, i)]
    finally:
        client.close()
    verdict = gen.judge(recs, d.data, CONFIG, TRAFFIC)
    c = verdict["compared"]
    assert verdict["correct"], (c, verdict["first_bad"])
    assert c["missing_answers"]["value"] == 0
    assert c["wrong_answers"]["value"] == 0
    assert c["recall_at_10"]["value"] >= LIMIT
    assert set(c["recall_at_10"]["by_kind"]) == set(gen.KINDS)
    c1 = d.counters()
    moved = {n: c1.get(n, 0) - c0.get(n, 0) for n in (
        "ivf builds", "ivf build rows", "ann probes", "ann candidate rows")}
    # the first kNN statement built the index from the tablets' rows
    assert moved["ivf builds"] == 1 and moved["ivf build rows"] == 20000
    assert moved["ann probes"] > 0
    # a probe re-ranks its windows: max_list rows a list, padding in
    ex = d.db.engine.executor
    idx = ex.ivf_host(gen.TABLE, "emb")
    assert moved["ann candidate rows"] % idx.max_list == 0
    assert moved["ann candidate rows"] // idx.max_list == moved["ann probes"]
    # the build is a named phase of the host tax
    built = sum(a["phases"].get("ivf build", 0.0)
                for a in d.db.host_tax.snapshot()["digests"].values())
    before = sum(a["phases"].get("ivf build", 0.0) for a in led0.values())
    assert built > before


def test_batched_lanes_equal_solo_replays(deployment):
    d = deployment
    lanes = 8
    clients = [WireClient(d.port) for _ in range(lanes)]
    rng = np.random.default_rng([SEED, 99])
    lits = [(k, gen._draw_lit(k, rng, CONFIG["queries"]))
            for k in gen.KINDS * lanes]
    got: list = [None] * len(lits)
    try:
        # the warm text tier first: a plan registers after its first runs
        for k in gen.KINDS:
            for lit in _lits(k, 3, 5):
                d.send(clients[0], k, lit)
        batched = 0
        for _attempt in range(4):
            c0 = d.counters()
            barrier = threading.Barrier(lanes)

            def run(i):
                barrier.wait()
                for j in range(i, len(lits), lanes):
                    got[j] = d.send(clients[i], *lits[j])

            threads = [threading.Thread(target=run, args=(i,))
                       for i in range(lanes)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            c1 = d.counters()
            batched = (c1.get("stmt batched statements", 0)
                       - c0.get("stmt batched statements", 0))
            d.db.result_cache.flush()  # a retry must dispatch again
            if batched >= 2:
                break
        assert batched >= 2, "the batcher never coalesced kNN lanes"
        d.db.result_cache.flush()
        solo = [d.send(clients[0], *lit) for lit in lits]
    finally:
        for cl in clients:
            cl.close()
    for g, s in zip(got, solo):
        assert g[4] == s[4], (g[0], g[1])
    verdict = gen.judge(got + solo, d.data, CONFIG, TRAFFIC)
    assert verdict["correct"], (verdict["compared"], verdict["first_bad"])
