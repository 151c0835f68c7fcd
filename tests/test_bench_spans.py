"""The benchmark's reader of the program's names (`benchmark/harness/
spans.py`, PR 26) checks itself on a hand-built timeline and on the sample
recorded on the chip; the raw `.xplane.pb` decoder is checked here against
`jax.profiler.ProfileData` on a CPU profile of this process."""

import glob
import os

import jax
import jax.numpy as jnp

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
