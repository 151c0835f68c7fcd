#!/usr/bin/env python3
"""No-chip compile sweep: AOT-compile for a described v5e every device
program chip_smoke.py dispatches (on-chip-measurement guide, section 2,
rehearsal 3). Run by hand; nothing runs on a chip and no time printed here
is a device time.

    python tools/compile_sweep.py                  # every phase at SF 0.01
    python tools/compile_sweep.py --sf 10 --phases tpch --only q1,q6,q3,q14
    python tools/compile_sweep.py --phases px      # four-device mesh
    python tools/compile_sweep.py --sf 1 --phases px --only q10

The parent never imports JAX. Each phase runs in a child process that
drives chip_smoke's own phase on the CPU (so the plan cache holds exactly
the programs the smoke dispatches, at their settled capacities), then
lowers every cached program against `v5e:2x2` shapes and compiles it. A
compiler crash is a signal, not an exception: the child announces each
program before compiling it, and the parent charges a dead child to the
program in flight and restarts the phase without it (and without the
programs already done). Children run one
after another (one process at a time may hold the TPU library).
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PHASES = ("tpch", "kv", "vector", "px")


# ------------------------------------------------------------------ parent

def run_phase(phase: str, sf: float, only: str, out) -> list[dict]:
    rows: list[dict] = []
    skip: list[str] = []
    while True:
        env = dict(os.environ, JAX_PLATFORMS="cpu", TPU_LOG_DIR="disabled")
        if phase == "px":
            env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        cmd = [sys.executable, os.path.abspath(__file__), "--child", phase,
               "--sf", str(sf), "--only", only, "--skip", json.dumps(skip)]
        proc = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                                stderr=subprocess.DEVNULL, text=True, env=env)
        in_flight = None
        for line in proc.stdout:
            if not line.startswith("{"):
                continue
            rec = json.loads(line)
            if "start" in rec:
                in_flight = rec["start"]
                continue
            if "program" not in rec:
                if rec.get("stmt", "").endswith(" px"):
                    print(line, end="", flush=True)  # drive_px's comparison
                continue  # one of the smoke's own lines from the CPU drive
            in_flight = None
            skip.append(rec["program"])  # a restart does not redo it
            rec.update(phase=phase, sf=sf)
            rows.append(rec)
            out.write(json.dumps(rec) + "\n")
            out.flush()
            print(json.dumps(rec), flush=True)
        rc = proc.wait()
        if rc == 0:
            return rows
        name = in_flight or f"{phase} (before any compile)"
        why = (f"signal {signal.Signals(-rc).name}" if rc < 0
               else f"exit code {rc}")
        rec = {"program": name, "ok": False, "error": why,
               "phase": phase, "sf": sf}
        rows.append(rec)
        out.write(json.dumps(rec) + "\n")
        print(json.dumps(rec), flush=True)
        if in_flight is None:
            return rows  # the phase itself failed: nothing to restart past
        skip.append(in_flight)


def table(rows: list[dict]) -> str:
    lines = ["| phase | sf | program | result | compile s | args MB | "
             "temp MB | out MB | sorts (operands each) |",
             "|---|---|---|---|---|---|---|---|---|"]
    for r in rows:
        mb = lambda k: (f"{r[k] / 2**20:.1f}" if k in r else "")  # noqa: E731
        lines.append(
            f"| {r['phase']} | {r['sf']:g} | {r['program']} | "
            f"{'ok' if r['ok'] else r['error'][:80]} | "
            f"{r.get('compile_s', 0):.1f} | {mb('argument_bytes')} | "
            f"{mb('temp_bytes')} | {mb('output_bytes')} | "
            f"{r.get('sort_operands', '')} |")
    return "\n".join(lines)


def sort_operands(lowered_text: str) -> list[int]:
    """Operand count of every `sort` in a program's lowered text, in
    program order: compile seconds on the TPU follow the widest one (ISSUE
    32: Q10's 10-operand group-by sort against the same plan at 4)."""
    import re

    return [len(m.split(",")) for m in re.findall(
        r'"stablehlo\.sort"\(([^)]*)\)', lowered_text)]


# ------------------------------------------------------------------- child

def drive_px(ctx, smoke, queries: list[int]) -> None:
    """`--only` in the px phase: the named statements of the TPC-H suite at
    `ob_px_dop = 4` over the CPU's four host devices, as `phase_px` drives
    its three (the tenant parameter, a connection opened after it, each
    statement twice so the capacities settle), every answer held to the
    one-chip executor's."""
    from oceanbase_tpu.models.tpch import schema as S
    from oceanbase_tpu.models.tpch.sql_suite import QUERIES

    one_chip = ctx.connect()
    smoke.load_tpch(ctx, one_chip, list(S.TABLES))
    ctx.connect().query("alter system set ob_px_dop = 4")
    c = ctx.connect()
    one_chip.query("set ob_px_dop = 0")
    moved = ("px overflow recompiles", "px exchange rows",
             "px exchange slots")
    for q in queries:
        before = {n: ctx.db.metrics.counter(n) for n in moved}
        px_rows = c.query(QUERIES[q])
        c.query(QUERIES[q])
        equal = smoke.same_rows(px_rows, one_chip.query(QUERIES[q]))
        # a lane that overflowed was one more compile of the program, and
        # rows over slots is what its exchanges carry of what they hold
        print(json.dumps({"stmt": f"tpch q{q} px", "rows": len(px_rows),
                          "equal": equal, **{
                              n: ctx.db.metrics.counter(n) - v
                              for n, v in before.items()}}), flush=True)
        if not equal:
            raise AssertionError(f"Q{q}: dop 4 and dop 0 disagree")


def child(phase: str, sf: float, only: set[str], skip: set[str]) -> int:
    sys.path.insert(0, REPO)
    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import Mesh, NamedSharding, PartitionSpec, \
        SingleDeviceSharding

    import chip_smoke as smoke
    from oceanbase_tpu.models.tpch.sql_suite import QUERIES
    from oceanbase_tpu.server.async_front import AsyncMySqlFrontend
    from oceanbase_tpu.server.database import Database
    from oceanbase_tpu.share.compile_cache import enable_compile_cache
    from oceanbase_tpu.sql import parser as P
    from oceanbase_tpu.sql.plan_cache import bind, parameterize

    # first of all: a second process holding the TPU library fails here,
    # before the expensive CPU drive and not after it
    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2")
    enable_compile_cache()  # the CPU drive below recompiles on every restart
    db = Database(n_nodes=3, n_ls=2)
    front = AsyncMySqlFrontend(db).start()
    ctx = smoke.Ctx(db, front.port, sf, 19920101)
    names: dict[str, str] = {}  # normalized statement text -> program name

    def name_stmt(name: str, text: str) -> None:
        names[P.normalize_for_cache(text)[0]] = name

    try:
        if phase == "tpch":
            from oceanbase_tpu.models.tpch import schema as S

            c = ctx.connect()
            smoke.load_tpch(ctx, c, list(S.TABLES))
            for q, text in QUERIES.items():
                if only and f"q{q}" not in only:
                    continue
                name_stmt(f"q{q}", text)
                try:
                    # three runs of a headline query leave its narrow
                    # frame and its profiled per-operator stages cached
                    for _ in range(3 if q in smoke.HEADLINE else 1):
                        c.query(text)
                except smoke.WireError as e:
                    print(json.dumps({"program": f"q{q}", "ok": False,
                                      "error": f"CPU drive: {e}"[:300]}),
                          flush=True)
        elif phase == "kv":
            smoke.phase_transactional(ctx)
            name_stmt("point read", "select v from kv where k = 0")
        elif phase == "vector":
            smoke.phase_vector(ctx)
            q0 = np.zeros(smoke.VEC_DIM, np.float32)
            name_stmt("knn unfiltered", smoke.knn_text("docs", q0))
            name_stmt("knn filtered",
                      smoke.knn_text("docs", q0, "where grp < 5 "))
            name_stmt("knn docs_ddl", smoke.knn_text("docs_ddl", q0))
        elif phase == "px":
            queries = sorted(int(q[1:]) for q in only)
            if queries:
                drive_px(ctx, smoke, queries)
            else:
                smoke.phase_px(ctx)
            for q in queries or (6, 1, 3):
                name_stmt(f"q{q}", QUERIES[q])

        # the CPU drive is done; what follows compiles for a chip that is
        # described, not attached, and such entries cannot be read back
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        one_chip = SingleDeviceSharding(topo.devices[0])
        mesh = Mesh(np.array(topo.devices), ("shard",))

        def on_chip(a):
            if not hasattr(a, "shape"):
                return a
            sh = getattr(a, "sharding", None)
            if isinstance(sh, NamedSharding):  # PX input: same spec, chip mesh
                sh = NamedSharding(mesh, sh.spec)
            else:
                sh = one_chip
            return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh)

        def shapes(tree):
            return jax.tree_util.tree_map(on_chip, tree)

        def compile_one(name: str, fn, *args) -> None:
            if name in skip:
                return
            print(json.dumps({"start": name}), flush=True)
            rec = {"program": name}
            t0 = time.perf_counter()
            try:
                lowered = fn.lower(*args)
                rec["sort_operands"] = sort_operands(lowered.as_text())
                compiled = lowered.compile()
                m = compiled.memory_analysis()
                rec.update(ok=True, compile_s=time.perf_counter() - t0,
                           argument_bytes=m.argument_size_in_bytes,
                           temp_bytes=m.temp_size_in_bytes,
                           output_bytes=m.output_size_in_bytes,
                           code_bytes=m.generated_code_size_in_bytes)
            except Exception as e:  # noqa: BLE001 - the compiler's refusal IS the result
                rec.update(ok=False, compile_s=time.perf_counter() - t0,
                           error=f"{type(e).__name__}: {e}"[:300])
            print(json.dumps(rec), flush=True)

        for key, entry in list(db.plan_cache._entries.items()):
            p = entry.prepared
            name = names.get(key[1], key[1][:60])
            if phase == "px" and only and not getattr(p, "px_nsh", 0):
                continue  # the one-chip side of drive_px's comparison
            if not hasattr(p, "jitted") or not hasattr(p, "_inputs"):
                print(json.dumps({
                    "program": name, "ok": False,
                    "error": f"{type(p).__name__}: not one resident "
                             "device program"}), flush=True)
                continue
            ins = shapes(p._inputs())
            if getattr(p, "px_nsh", 0):
                # same plan and settled capacities, lowered over the
                # described four-chip mesh instead of the CPU's
                from oceanbase_tpu.parallel.px import PxExecutor

                text = next(t for t in QUERIES.values()
                            if P.normalize_for_cache(t)[0] == key[1])
                pz = parameterize(db.engine.planner.plan(P.parse(text)).plan)
                px = PxExecutor(db.catalog, mesh,
                                unique_keys=db._unique_keys,
                                stats=db.engine.stats)
                jitted, _, _ = px.compile(p.plan, p.params)
                rep = NamedSharding(mesh, PartitionSpec())
                qp = jax.tree_util.tree_map(
                    lambda a: jax.ShapeDtypeStruct(
                        np.shape(a), np.asarray(a).dtype, sharding=rep),
                    bind(pz.values, entry.dtypes))
                compile_one(f"{name} px mesh(4)", jitted, ins, qp)
                continue
            from oceanbase_tpu.engine.executor import packed_width

            spec = p._qparam_spec
            if spec is None:
                print(json.dumps({"program": name, "ok": False,
                                  "error": "unpacked qparams: not swept"}),
                      flush=True)
                continue
            width = packed_width(spec)
            qp = jax.ShapeDtypeStruct((width,), np.int64, sharding=one_chip)
            compile_one(name, p.jitted, ins, qp)
            for ncap, fn in p._narrow.items():
                compile_one(f"{name} narrow[{ncap}]", fn, ins, qp)
            for b, fn in p._batched.items():
                compile_one(f"{name} batched[{b}]", fn, ins,
                            jax.ShapeDtypeStruct((b, width), np.int64,
                                                 sharding=one_chip))
            seg = getattr(p, "_segmented", None)
            if seg is not None:
                outs = {}
                for nid in seg.order:
                    child_ids, fn = seg.stages[nid]
                    kids = tuple(outs[c] for c in child_ids)
                    compile_one(f"{name} stage[{nid}]", fn, ins, kids, qp)
                    bf = seg.builders.get(nid)
                    if bf is not None:
                        compile_one(f"{name} build[{nid}]", bf, ins, kids, qp)
                    outs[nid] = shapes(jax.eval_shape(fn, ins, kids, qp)[0])
                compile_one(f"{name} stage[compact]", seg._compact,
                            outs[seg.root])
        if phase == "vector":
            from oceanbase_tpu.storage import vector_index as V

            t = db.catalog["docs"]
            lists, _ = db._vector_specs["docs"]["emb"]
            x = jax.ShapeDtypeStruct(t.data["emb"].shape, np.float32,
                                     sharding=one_chip)
            cent = jax.ShapeDtypeStruct((lists, smoke.VEC_DIM), np.float32,
                                        sharding=one_chip)
            assign = jax.ShapeDtypeStruct((t.nrows,), np.int64,
                                          sharding=one_chip)
            compile_one("ivf build: kmeans assign", V._kmeans_assign, x, cent)
            compile_one("ivf build: kmeans update", V._kmeans_update,
                        x, assign, lists)
    finally:
        ctx.close_clients()
        front.stop()
        db.close()
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sf", type=float, default=0.01)
    ap.add_argument("--phases", default=",".join(PHASES))
    ap.add_argument("--only", default="",
                    help="tpch and px phases: comma list of queries "
                         "(q1,q6,...); the px phase drives q6,q1,q3 "
                         "without it")
    ap.add_argument("--out", default=os.path.join(
        REPO, "chiprun_out", "compile_sweep.jsonl"))
    ap.add_argument("--child", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--skip", default="[]", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child:
        return child(args.child, args.sf,
                     set(filter(None, args.only.split(","))),
                     set(json.loads(args.skip)))
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    rows = []
    with open(args.out, "a") as out:
        for phase in args.phases.split(","):
            rows += run_phase(phase, args.sf, args.only, out)
    print(table(rows))
    return 0 if all(r["ok"] for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
