"""From the same profile to names: what the program itself wrote into it.

`trace.py` reduces a profile to busy and idle seconds and has to name things
from outside. Since PR 26 the program names them: every device op carries the
plan node that emitted it (`jax.named_scope("<kind>#<nid>")`, with `expr` for
expression work and `frame` for the result-frame gather), a statement program
is called `jit_ob_select_<fingerprint>[_narrow]`, and while a profiler session
is active the gap ledger's phases are leaf host events `ob:<phase>` with a
`stmt` stat on the statement's own thread. This file reads those names out of
the same `.xplane.pb` and reduces them, per `benchwin:<kind>` window, to

  device seconds by innermost scope kind      (`expr`, `Join:inner`, `frame`, ...)
  module launches: statement programs (`jit_ob_*`) against everything else
  idle-gap seconds by the `ob:` phase with the largest overlap over all threads
  a named breakdown: the ten largest `kind:Node#nid[/expr]/primitive` and
  `kind:ob:<phase>`

On a TPU the scope of a device op is not a stat of its event: it is the
`tf_op` stat of the event's *metadata*, which `jax.profiler.ProfileData` does
not show. So the file is decoded here, from the protobuf wire format, for the
few messages of `xplane.proto` that are needed (nothing but the standard
library; the chip's machine need not have TensorFlow).

A device op that XLA made itself (the TPU's reduce-window rewrite, copies,
async slices) has no `tf_op`: its seconds count as `unnamed`, and
`op_named_pct` says how much that is. A fusion carries its root's scope.

`python -m benchmark.harness.spans <logdir-or-.xplane.pb[.gz]>` prints the
reduction of one profile; `--record out.json` keeps a thinned copy of its
events; `--selfcheck` checks the reduction on a hand-built timeline and on
`recorded_spans.json` (recorded on the chip, PR 26).
"""

from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
import re
import sys

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    from benchmark.harness import trace as T
else:
    from . import trace as T

PHASE_PREFIX = "ob:"
PROGRAM_PREFIX = "jit_ob_"
UNNAMED = "unnamed"
LAST_PHASE = "completion fold"
NODE = re.compile(r"^[A-Za-z][A-Za-z:]*#\d+$")
RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "recorded_spans.json")

# ---- xplane.proto, as far as it is needed ----------------------------------------
# XSpace.planes=1 | XPlane: name=2 lines=3 event_metadata=4 stat_metadata=5
# XLine: name=2 timestamp_ns=3 events=4 | XEvent: metadata_id=1 offset_ps=2
# duration_ps=3 stats=4 | XStat: metadata_id=1 uint64=3 int64=4 str=5 ref=7
# XEventMetadata: id=1 name=2 display_name=4 stats=5 | XStatMetadata: id=1 name=2
# (a map entry is a message with key=1 value=2)


def _fields(buf):
    """(field number, value) of one message: an int for a varint, a
    memoryview for a length-delimited field; fixed-width fields skipped."""
    i, n = 0, len(buf)
    while i < n:
        key = shift = 0
        while True:
            b = buf[i]
            i += 1
            key |= (b & 0x7F) << shift
            if b < 0x80:
                break
            shift += 7
        wire = key & 7
        if wire == 0:
            v = shift = 0
            while True:
                b = buf[i]
                i += 1
                v |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
            yield key >> 3, v
        elif wire == 2:
            ln = shift = 0
            while True:
                b = buf[i]
                i += 1
                ln |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
            yield key >> 3, buf[i:i + ln]
            i += ln
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= (1 << 63) else v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _stats(buf, stat_names) -> dict:
    """{stat name: value} of the repeated XStat fields `buf` yields."""
    out = {}
    for raw in buf:
        key = val = None
        for no, v in _fields(raw):
            if no == 1:
                key = stat_names.get(_signed(v))
            elif no in (3, 4):
                val = _signed(v)
            elif no == 5:
                val = _text(v)
            elif no == 7:
                val = stat_names.get(v)
        if key is not None:
            out[key] = val
    return out


def _plane(buf):
    """(name, [line bufs], {metadata id: (name, display name, [stat bufs])},
    {stat metadata id: name})."""
    name, lines, metas, stat_names = "", [], {}, {}
    for no, v in _fields(buf):
        if no == 2:
            name = _text(v)
        elif no == 3:
            lines.append(v)
        elif no == 4:
            for eno, ev in _fields(v):
                if eno != 2:
                    continue
                mid, mname, disp, st = 0, "", "", []
                for mno, mv in _fields(ev):
                    if mno == 1:
                        mid = _signed(mv)
                    elif mno == 2:
                        mname = _text(mv)
                    elif mno == 4:
                        disp = _text(mv)
                    elif mno == 5:
                        st.append(mv)
                metas[mid] = (mname, disp, st)
        elif no == 5:
            for eno, ev in _fields(v):
                if eno != 2:
                    continue
                sid, sname = 0, ""
                for mno, mv in _fields(ev):
                    if mno == 1:
                        sid = _signed(mv)
                    elif mno == 2:
                        sname = _text(mv)
                stat_names[sid] = sname
    return name, lines, metas, stat_names


def _line(buf):
    """(name, [(metadata id, start_ns, dur_ns, [stat bufs])])."""
    name, t0, raw = "", 0, []
    for no, v in _fields(buf):
        if no == 2:
            name = _text(v)
        elif no == 3:
            t0 = _signed(v)
        elif no == 4:
            raw.append(v)
    events = []
    for ev in raw:
        mid = off = dur = 0
        st = []
        for no, v in _fields(ev):
            if no == 1:
                mid = _signed(v)
            elif no == 2:
                off = _signed(v)
            elif no == 3:
                dur = _signed(v)
            elif no == 4:
                st.append(v)
        events.append((mid, t0 + off // 1000, dur // 1000, st))
    return name, events


def scope_of(tf_op: str):
    """`jit(ob_..)/Project#0/Aggregate#1/expr/gather:` ->
    ("Aggregate#1", "expr", "gather"): the innermost plan node, the kind of
    the innermost scope (`expr`, `frame`, or the node's kind without its
    number; None if the program wrote no scope) and the primitive."""
    cut = tf_op.rfind(":")
    path = tf_op[:cut] if cut > tf_op.rfind("/") else tf_op
    parts = path.split("/")
    node = kind = None
    for p in reversed(parts[:-1]):
        if NODE.match(p):
            node = p
            if kind is None:
                kind = p.split("#")[0]
            break
        if kind is None and p in ("expr", "frame"):
            kind = p
    return node, kind, parts[-1]


def profile_path(where: str) -> str:
    if os.path.isdir(where):
        paths = sorted(glob.glob(os.path.join(
            where, "plugins", "profile", "*", "*.xplane.pb")))
        if not paths:
            raise FileNotFoundError(f"no .xplane.pb under {where}")
        return paths[-1]
    return where


def read_spans(where: str) -> dict:
    """{"windows": [(kind, start_ns, end_ns)],
        "ops": [(dev, start_ns, dur_ns, node, scope kind, label)],
        "modules": [(dev, name, start_ns, dur_ns)],
        "phases": [(thread, phase, start_ns, dur_ns, stmt)]}."""
    path = profile_path(where)
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        space = memoryview(f.read())
    out = {"windows": [], "ops": [], "modules": [], "phases": []}
    thread = 0
    for no, pbuf in _fields(space):
        if no != 1:
            continue
        name, lines, metas, stat_names = _plane(pbuf)
        dev = T.DEVICE_PLANE.match(name)
        if dev:
            dev = int(dev.group(1))
            scopes = {}
            for lbuf in lines:
                lname, events = _line(lbuf)
                if lname == T.MODULES_LINE:
                    out["modules"] += [(dev, metas[m][0], s, d)
                                       for m, s, d, _st in events]
                elif lname == T.OPS_LINE:
                    for m, s, d, _st in events:
                        if m not in scopes:
                            mname, disp, st = metas[m]
                            tf_op = _stats(st, stat_names).get("tf_op")
                            node, kind, prim = (scope_of(tf_op) if tf_op
                                                else (None, None, None))
                            if kind is None:
                                label = f"{UNNAMED}/{disp or T.label(mname)}"
                            else:
                                label = "/".join(filter(None, [
                                    node, kind if kind in ("expr", "frame")
                                    else None, prim]))
                            scopes[m] = (node, kind, label)
                        out["ops"].append((dev, s, d) + scopes[m])
        elif name.startswith("/host:"):
            for lbuf in lines:
                _lname, events = _line(lbuf)
                thread += 1
                for m, s, d, st in events:
                    ename = metas[m][0] if m in metas else ""
                    if ename.startswith(PHASE_PREFIX):
                        out["phases"].append((
                            thread, ename[len(PHASE_PREFIX):], s, d,
                            _stats(st, stat_names).get("stmt")))
                    elif ename.startswith(T.WINDOW_PREFIX):
                        out["windows"].append(
                            (ename[len(T.WINDOW_PREFIX):], s, s + d))
    return out


# ---- the reduction ----------------------------------------------------------------


def _top(d: dict, n: int = 10):
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def reduce_spans(ev: dict, devices: int = 1) -> dict:
    """Per window kind: device seconds by innermost scope kind (ops
    clipped to the window; `unnamed` where the program wrote none),
    launches of statement programs and of anything else, idle-gap seconds
    (gaps of at least 100 us between the merged device ops, as in
    `trace.py`) by the `ob:` phase with the largest overlap over all
    threads, and the seconds of every leaf by phase (what the host-tax
    registry's deltas over the same window should read)."""
    phases = sorted(ev["phases"], key=lambda p: p[2])
    starts = [p[2] for p in phases]
    longest = max((p[3] for p in phases), default=0)
    per_kind, op_s, gap_s = {}, {}, {}
    eager_names = {}
    for kind, w0, w1 in ev["windows"]:
        k = per_kind.setdefault(kind, {
            "window_s": 0.0, "busy_s": 0.0, "scope_s": {},
            "programs": 0.0, "eager": 0.0, "idle_phase_s": {},
            "phase_s": {}, "completed": 0})
        k["window_s"] += (w1 - w0) / 1e9
        for _t, ph, s, d, _stmt in phases:  # every leaf, clipped
            sec = (min(s + d, w1) - max(s, w0)) / 1e9
            if sec > 0:
                k["phase_s"][ph] = k["phase_s"].get(ph, 0.0) + sec
            # a statement's last leaf closes inside the window: it
            # completed there (what the clients count, within a few)
            if ph == LAST_PHASE and w0 <= s + d < w1:
                k["completed"] += 1
        by_dev = {}
        for dev, s, d, _node, skind, label in ev["ops"]:
            s2, e2 = max(s, w0), min(s + d, w1)
            if e2 <= s2:
                continue
            by_dev.setdefault(dev, []).append((s2, e2))
            sec = (e2 - s2) / 1e9 / devices
            sk = skind or UNNAMED
            k["scope_s"][sk] = k["scope_s"].get(sk, 0.0) + sec
            key = f"{kind}:{label}"
            op_s[key] = op_s.get(key, 0.0) + sec
        for _dev, name, s, _d in ev["modules"]:
            if not w0 <= s < w1:
                continue
            if name.startswith(PROGRAM_PREFIX):
                k["programs"] += 1 / devices
            else:
                k["eager"] += 1 / devices
                short = name.split("(")[0]
                eager_names[short] = eager_names.get(short, 0) + 1
        for ivs in by_dev.values():
            merged = T._merge(ivs)
            k["busy_s"] += sum(e - s for s, e in merged) / 1e9 / devices
            edges = [w0] + [x for iv in merged for x in iv] + [w1]
            for g0, g1 in zip(edges[0::2], edges[1::2]):
                if g1 - g0 < 100_000:
                    continue
                best, best_ov = UNNAMED, 0
                i = bisect.bisect_left(starts, g0 - longest)
                while i < len(phases) and starts[i] < g1:
                    _t, ph, s, d, _stmt = phases[i]
                    ov = min(s + d, g1) - max(s, g0)
                    if ov > best_ov:
                        best, best_ov = ph, ov
                    i += 1
                sec = (g1 - g0) / 1e9 / devices
                k["idle_phase_s"][best] = k["idle_phase_s"].get(best, 0.0) + sec
                key = (f"{kind}:{UNNAMED}" if best == UNNAMED
                       else f"{kind}:{PHASE_PREFIX}{best}")
                gap_s[key] = gap_s.get(key, 0.0) + sec
    # leaves of one thread must not overlap: one annotation is open at a time
    overlaps = 0
    last_end = {}
    for t, _ph, s, d, _stmt in phases:
        if t in last_end and s < last_end[t] - 1:
            overlaps += 1
        last_end[t] = max(last_end.get(t, s), s + d)
    return {"per_kind": per_kind, "phase_overlaps": overlaps,
            "phase_events": len(phases), "eager_modules": _top(eager_names),
            "named_breakdown": {"device_ops": _top(op_s),
                                "idle_gaps": _top(gap_s)}}


def quantities(red: dict, kinds=None) -> dict:
    """Sums over the given window kinds (all when None)."""
    ks = [v for n, v in red["per_kind"].items() if kinds is None or n in kinds]
    out = {n: sum(k[n] for k in ks)
           for n in ("window_s", "busy_s", "programs", "eager", "completed")}
    for table in ("scope_s", "idle_phase_s", "phase_s"):
        out[table] = {}
        for k in ks:
            for n, v in k[table].items():
                out[table][n] = out[table].get(n, 0.0) + v
    return out


FETCH_PHASES = ("d2h", "device wait", "result fold")


def metrics(red: dict, statements: float, kinds=None) -> dict:
    """The six trace-derived per-layer numbers ISSUE 26 defines, each None
    where there is nothing to read (never 0 for "not there")."""
    q = quantities(red, kinds)
    named = sum(v for n, v in q["scope_s"].items() if n != UNNAMED)
    has_scopes = named > 0
    has_phases = red["phase_events"] > 0
    idle_all = sum(q["idle_phase_s"].values())
    idle_named = sum(v for n, v in q["idle_phase_s"].items() if n != UNNAMED)
    per = (lambda x: x / statements * 1000.0) if statements else (lambda x: None)
    programs = q["programs"]
    return {
        "op_named_pct": 100.0 * named / q["busy_s"]
        if has_scopes and q["busy_s"] else None,
        "expr_device_ms_per_stmt": per(q["scope_s"].get("expr", 0.0))
        if has_scopes else None,
        "join_op_device_ms_per_stmt": per(sum(
            v for n, v in q["scope_s"].items() if n.startswith("Join:")))
        if has_scopes else None,
        "eager_launches_per_stmt": q["eager"] / statements
        if programs and statements else None,
        "fetch_idle_ms_per_stmt": per(sum(
            q["idle_phase_s"].get(p, 0.0) for p in FETCH_PHASES))
        if has_phases else None,
        "idle_named_pct": 100.0 * idle_named / idle_all
        if has_phases and idle_all else None,
    }


# ---- checks, and the command line ------------------------------------------------


def selfcheck() -> None:
    ms = 1_000_000
    op = lambda s, d, node, kind, label: (0, s * ms, d * ms, node, kind, label)  # noqa: E731
    ev = {
        "windows": [("a", 0, 100 * ms)],
        "ops": [op(10, 10, "Join:inner#3", "Join:inner", "Join:inner#3/gather"),
                # a scan's expression nested in the join: its self time is expr
                op(20, 5, "Scan#4", "expr", "Scan#4/expr/lt"),
                op(25, 5, None, "frame", "frame/gather"),
                op(30, 2, None, None, "unnamed/reduce-window.6"),
                op(60, 10, "Aggregate#1", "Aggregate", "Aggregate#1/reduce_sum")],
        "modules": [(0, "jit_ob_select_1a2b3c4d_narrow(7)", 10 * ms, 22 * ms),
                    (0, "jit_add(9)", 45 * ms, 1 * ms),
                    (0, "jit_ob_select_1a2b3c4d_narrow(7)", 60 * ms, 10 * ms)],
        # gap 32..60: thread 1 covers 6 ms of it, thread 2 covers 20: 2 wins
        # gap 70..100: no leaf at all
        # gap 0..10: one leaf, partly outside the window
        "phases": [(1, "device wait", 30 * ms, 8 * ms, 41),
                   (2, "result fold", 38 * ms, 20 * ms, 42),
                   (1, "setup", -5 * ms, 9 * ms, 43)],
    }
    red = reduce_spans(ev)
    a = red["per_kind"]["a"]
    assert abs(a["busy_s"] - 0.032) < 1e-12, a
    assert a["scope_s"] == {"Join:inner": 0.010, "expr": 0.005,
                            "frame": 0.005, UNNAMED: 0.002,
                            "Aggregate": 0.010}, a["scope_s"]
    assert a["programs"] == 2 and a["eager"] == 1 and a["completed"] == 0
    assert red["eager_modules"] == [["jit_add", 1]]
    got = {k: round(v, 9) for k, v in a["idle_phase_s"].items()}
    assert got == {"result fold": 0.028, UNNAMED: 0.030, "setup": 0.010}, got
    assert red["named_breakdown"]["idle_gaps"][0] == ["a:unnamed", 0.030]
    assert red["named_breakdown"]["device_ops"][0][0] in (
        "a:Join:inner#3/gather", "a:Aggregate#1/reduce_sum")
    assert red["phase_overlaps"] == 0
    m = metrics(red, 2)
    assert abs(m["op_named_pct"] - 100 * 30 / 32) < 1e-9
    assert abs(m["expr_device_ms_per_stmt"] - 2.5) < 1e-9
    assert abs(m["join_op_device_ms_per_stmt"] - 5.0) < 1e-9
    assert m["eager_launches_per_stmt"] == 0.5
    assert abs(m["fetch_idle_ms_per_stmt"] - 14.0) < 1e-9
    assert abs(m["idle_named_pct"] - 100 * 38 / 68) < 1e-9
    # a profile of a program that names nothing reads nothing, never 0
    bare = dict(ev, phases=[], ops=[op(10, 10, None, None, "unnamed/fusion.1")],
                modules=[(0, "jit_run_narrow(7)", 10 * ms, 10 * ms)])
    assert set(metrics(reduce_spans(bare), 2).values()) == {None}
    assert scope_of("jit(ob_select_ab_narrow)/jit(ob_select_ab)/Project#0/"
                    "Aggregate#1/expr/gather:") == (
                        "Aggregate#1", "expr", "gather")
    assert scope_of("jit(a)/TopN#0/Join:inner#3/jit(_where)/select_n:") == (
        "Join:inner#3", "Join:inner", "select_n")
    assert scope_of("jit(a)/frame/jit(_take)/gather:") == (
        None, "frame", "gather")
    assert scope_of("qparams:") == (None, None, "qparams")
    print("spans: hand-built timeline ok")
    if os.path.exists(RECORDED):
        with open(RECORDED) as f:
            samples = json.load(f)["samples"]
        for doc in samples:
            got = metrics(reduce_spans(doc["events"]), doc["statements"])
            for name, want in doc["expect"].items():
                assert got[name] is not None and abs(got[name] - want) <= \
                    1e-9 * max(1.0, abs(want)), (name, got[name], want)
            print("spans: recorded chip trace ok:", doc["recorded"],
                  doc["expect"])


def record(ev: dict, out: str, per_window_ns: int, note: str) -> None:
    """A thinned copy for the selfcheck, added to the samples of `out`:
    the first `per_window_ns` of each window, with what the reduction
    reads there."""
    keep = {"windows": [], "ops": [], "modules": [], "phases": []}
    for kind, w0, w1 in ev["windows"]:
        w1 = min(w1, w0 + per_window_ns)
        keep["windows"].append([kind, w0, w1])
        keep["ops"] += [list(o) for o in ev["ops"] if w0 <= o[1] < w1]
        keep["modules"] += [list(m) for m in ev["modules"] if w0 <= m[2] < w1]
        keep["phases"] += [list(p) for p in ev["phases"]
                           if p[2] < w1 and p[2] + p[3] > w0]
    statements = len({p[4] for p in keep["phases"]})
    expect = {k: v for k, v in metrics(
        reduce_spans(keep), statements).items() if v is not None}
    samples = []
    if os.path.exists(out):
        with open(out) as f:
            samples = json.load(f)["samples"]
    samples.append({"recorded": note, "statements": statements,
                    "expect": expect, "events": keep})
    with open(out, "w") as f:
        json.dump({"samples": samples}, f, separators=(",", ":"))


def main(argv) -> int:
    if "--selfcheck" in argv:
        selfcheck()
        return 0
    if not argv:
        print(__doc__.split("\n\n")[-1], file=sys.stderr)
        return 2
    ev = read_spans(argv[0])
    if "--record" in argv:
        i = argv.index("--record")
        record(ev, argv[i + 1], int(float(argv[i + 2]) * 1e6), argv[i + 3])
        return 0
    red = reduce_spans(ev)
    print(json.dumps({"named_breakdown": red["named_breakdown"],
                      "per_kind": red["per_kind"],
                      "eager_modules": red["eager_modules"],
                      "phase_events": red["phase_events"],
                      "phase_overlaps": red["phase_overlaps"]}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
