"""Multi-process PX: the same shard_map programs over a GLOBAL mesh
spanning two OS processes (jax.distributed + gloo CPU collectives).

The DCN half of SURVEY §2.7: the reference runs PX across observers via
SQC RPC dispatch + DTL channels (sql/engine/px/ob_px_rpc_processor.h:28,
sql/dtl/ob_dtl_rpc_channel.h:44); here two processes each own 4 virtual
devices of one 8-device mesh, XLA routes the exchange collectives across
the process boundary, and results must match the single-process engine
bit for bit."""

import multiprocessing as mp
import os
import socket

import pytest

pytestmark = pytest.mark.multidevice

QIDS = (1, 3, 6)


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def _worker(pid: int, nprocs: int, port: int, q):
    try:
        # must run BEFORE any oceanbase_tpu import: package imports build
        # jnp constants, which initialise (and lock) the XLA backend
        import jax

        jax.distributed.initialize(
            coordinator_address=f"127.0.0.1:{port}",
            num_processes=nprocs, process_id=pid,
        )

        assert len(jax.devices()) == 8, jax.devices()
        assert len(jax.local_devices()) == 4

        from oceanbase_tpu.core.column import batch_rows_normalized
        from oceanbase_tpu.models.tpch import datagen
        from oceanbase_tpu.models.tpch.sql_suite import QUERIES, UNIQUE_KEYS
        from oceanbase_tpu.parallel.mesh import make_mesh
        from oceanbase_tpu.parallel.px import PxExecutor
        from oceanbase_tpu.sql.parser import parse
        from oceanbase_tpu.sql.planner import Planner

        tables = datagen.generate(sf=0.01)  # deterministic: same everywhere
        mesh = make_mesh(8)
        planner = Planner(tables)
        px = PxExecutor(tables, mesh, unique_keys=UNIQUE_KEYS)
        out = {}
        for qid in QIDS:
            planned = planner.plan(parse(QUERIES[qid]))
            b = px.execute(planned.plan)
            out[qid] = batch_rows_normalized(b, planned.output_names)
        q.put(("ok", pid, out))
    except Exception as e:  # pragma: no cover - surfaced by the parent
        import traceback

        q.put(("err", pid, f"{e}\n{traceback.format_exc()}"))


def test_px_two_process_global_mesh():
    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    port = _free_port()
    # children must see this env at INTERPRETER start (jax platform
    # selection runs before any user code), so mutate the parent env
    # around the spawn
    saved = {
        k: os.environ.get(k) for k in ("JAX_PLATFORMS", "XLA_FLAGS")
    }
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    procs = [
        ctx.Process(target=_worker, args=(i, 2, port, q), daemon=True)
        for i in range(2)
    ]
    try:
        for p in procs:
            p.start()
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    results = {}
    try:
        for _ in range(2):
            kind, pid, payload = q.get(timeout=600)
            assert kind == "ok", f"process {pid} failed:\n{payload}"
            results[pid] = payload
    finally:
        for p in procs:
            p.join(timeout=30)
            if p.is_alive():
                p.terminate()

    # both processes executed the same SPMD program: identical results
    assert results[0] == results[1]

    # and they match the single-process engine (this test process)
    from oceanbase_tpu.core.column import batch_rows_normalized
    from oceanbase_tpu.engine.executor import Executor
    from oceanbase_tpu.models.tpch import datagen
    from oceanbase_tpu.models.tpch.sql_suite import QUERIES, UNIQUE_KEYS
    from oceanbase_tpu.sql.parser import parse
    from oceanbase_tpu.sql.planner import Planner

    tables = datagen.generate(sf=0.01)
    planner = Planner(tables)
    single = Executor(tables, unique_keys=UNIQUE_KEYS)
    for qid in QIDS:
        planned = planner.plan(parse(QUERIES[qid]))
        b = single.execute(planned.plan)
        srows = batch_rows_normalized(b, planned.output_names)
        assert results[0][qid] == srows, f"q{qid} distributed mismatch"
        assert len(srows) > 0
