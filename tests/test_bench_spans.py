"""The benchmark's reader of the program's names (`benchmark/harness/
spans.py`, PR 26) checks itself on a hand-built timeline and on the sample
recorded on the chip; the raw `.xplane.pb` decoder is checked here against
`jax.profiler.ProfileData` on a CPU profile of this process."""

import glob
import os

import jax
import jax.numpy as jnp
import pytest

from benchmark.harness import spans


def test_spans_selfcheck():
    spans.selfcheck()
    assert os.path.exists(spans.RECORDED), "the chip sample is part of it"


def test_decoder_agrees_with_profile_data(tmp_path):
    from jax.profiler import ProfileData, TraceAnnotation

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with TraceAnnotation("benchwin:k"):
            for i in range(3):
                with TraceAnnotation("ob:device wait", stmt=40 + i):
                    (jnp.arange(1024) * 2).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    got = spans.read_spans(str(tmp_path))
    path = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    want = []
    window = None
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name == "ob:device wait":
                    want.append((int(ev.start_ns), int(ev.duration_ns),
                                 {k: v for k, v in ev.stats}["stmt"]))
                elif ev.name == "benchwin:k":
                    window = (int(ev.start_ns), int(ev.duration_ns))
    assert len(want) == 3 and window is not None
    assert [(k, e - s) for k, s, e in got["windows"]] == [("k", window[1])]
    assert sorted((d, st) for _t, ph, _s, d, st in got["phases"]
                  if ph == "device wait") == sorted(
                      (d, st) for _s, d, st in want)
    # one clock: the leaves lie inside the window in both readings
    (_k, w0, w1), = got["windows"]
    assert all(w0 <= s and s + d <= w1 for _t, _p, s, d, _st in got["phases"])
    assert len({t for t, *_ in got["phases"]}) == 1


def test_tx_select_leaves_carry_the_statement(tmp_path):
    """PR 37: a SELECT inside BEGIN of a table the transaction wrote
    writes `ob:catalog refresh` and `ob:h2d` leaves with its `stmt`
    stat; one leaf at a time on the
    thread, and the `device dispatch` leaf the upload interrupted opens
    again when the upload ends."""
    from jax.profiler import TraceAnnotation

    from oceanbase_tpu.server import Database

    db = Database(n_nodes=1, n_ls=1)
    try:
        s = db.session()
        s.sql("create table lt (k bigint primary key, v bigint not null)")
        s.sql("insert into lt values " + ", ".join(
            f"({i}, {i})" for i in range(100)))
        q = "select sum(v) as s from lt where k < 60"
        s.sql("begin")
        s.sql("update lt set v = v where k = 0")  # its reads rescan
        s.sql(q).rows()  # compiles outside the trace
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            with TraceAnnotation("benchwin:tx"):
                assert s.sql(q).rows() == [(sum(range(60)),)]
        finally:
            jax.profiler.stop_trace()
        s.sql("commit")
    finally:
        db.close()
    leaves = sorted((st, d, ph, stmt)
                    for _t, ph, st, d, stmt in spans.read_spans(
                        str(tmp_path))["phases"])
    names = [ph for _s, _d, ph, _st in leaves]
    assert "catalog refresh" in names and "h2d" in names
    assert len({stmt for *_x, stmt in leaves}) == 1 and leaves[0][3] > 0
    for (s0, d0, *_a), (s1, *_b) in zip(leaves, leaves[1:]):
        assert s0 + d0 <= s1  # never nested, never overlapping
    i = names.index("h2d")
    assert names[i - 1] == names[i + 1] == "device dispatch"


def test_layer_reads_the_four_new_metrics():
    """The four files of PR 37 on a synthetic counters dict; the two
    counts read 0.0 where the program never bumped their counters."""
    import json

    from benchmark.harness import layer
    from benchmark.harness.server import named_sysstat

    names = ("catalog_refresh_ms_per_stmt", "catalog_refresh_rows_per_stmt",
             "h2d_ms_per_stmt", "h2d_mb_per_stmt")
    seeded = dict.fromkeys(named_sysstat(), 0.0)
    c0 = dict(seeded, **{"host_tax.statements": 10.0,
                         "host_tax.phase.catalog refresh": 1.0,
                         "host_tax.phase.h2d": 2.0,
                         "sysstat.catalog refresh rows": 5.0,
                         "sysstat.h2d bytes": 1e6})
    c1 = dict(seeded, **{"host_tax.statements": 26.0,
                         "host_tax.phase.catalog refresh": 1.16,
                         "host_tax.phase.h2d": 2.08,
                         "sysstat.catalog refresh rows": 14e6 + 5.0,
                         "sysstat.h2d bytes": 1e6 + 16 * 7e6})
    ctx = {"counters0": c0, "counters1": c1, "statements": 16}
    got = layer.read_all(ctx, names)
    assert got == pytest.approx({
        "catalog_refresh_ms_per_stmt": 10.0, "h2d_ms_per_stmt": 5.0,
        "catalog_refresh_rows_per_stmt": 875000.0, "h2d_mb_per_stmt": 7.0})
    # a cell where the work never happens: the sysstat counts read 0.0,
    # the phases (absent from the registry) leave the line
    quiet = {"counters0": dict(seeded, **{"host_tax.statements": 1.0}),
             "counters1": dict(seeded, **{"host_tax.statements": 9.0}),
             "statements": 8}
    assert {"sysstat.catalog refresh rows", "sysstat.h2d bytes"} <= set(
        seeded)
    assert layer.read_all(quiet, names) == {
        "catalog_refresh_rows_per_stmt": 0.0, "h2d_mb_per_stmt": 0.0}
    for n in names:
        with open(os.path.join(layer.DIR, n + ".json")) as f:
            assert json.load(f)["source"] == "counters"
