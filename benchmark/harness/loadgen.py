"""The load generator: a child process that never imports JAX.

Closed-loop clients over plain sockets, one thread per connection, so that
client work does not share the server's interpreter lock. The parent sends
one JSON command per line on stdin; each reply is a pickle with an 8-byte
length before it on stdout:

  {"cmd": "warm", "repeat": n}            every warm-up statement n times
  {"cmd": "run", "seconds": s, "only": k} a window (k: one kind only)
  {"cmd": "quit"}

A reply is a list of records `(kind, literals, t_send, t_done, rows, client)`
with times on this process's `time.time()`; `rows` is the exception text
where the statement failed. Statements in flight when a window closes are
waited for and recorded: their latency counts the wait. A stream that has a
method `done(kind, literals, rows)` is told how each of its statements ended
before it is asked for the next.
"""

from __future__ import annotations

import importlib
import json
import os
import pickle
import sys
import threading
import time


def _root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def _reply(out, obj) -> None:
    blob = pickle.dumps(obj, protocol=pickle.HIGHEST_PROTOCOL)
    out.write(len(blob).to_bytes(8, "little"))
    out.write(blob)
    out.flush()


def _timed(client, kind, lit, text, who):
    t0 = time.time()
    try:
        rows = client.query(text)
    except Exception as e:  # noqa: BLE001 - recorded; the parent decides
        rows = f"{type(e).__name__}: {e}"
    return (kind, lit, t0, time.time(), rows, who)


def main() -> int:
    sys.path.insert(0, _root())
    from benchmark.harness.wire import WireClient

    spec = json.loads(sys.argv[1])
    gen = importlib.import_module("benchmark.generators."
                                  + spec["traffic"]["generator"])
    traffic, config, seed = spec["traffic"], spec["config"], spec["seed"]
    pools = gen.pools(traffic, config, seed)
    n = int(traffic["clients"])
    clients = [WireClient(spec["port"]) for _ in range(n)]
    streams = [gen.Stream(traffic, config, seed, i, pools) for i in range(n)]
    out = sys.stdout.buffer
    _reply(out, "ready")
    for line in sys.stdin:
        cmd = json.loads(line)
        if cmd["cmd"] == "quit":
            break
        if cmd["cmd"] == "warm":
            recs = []
            for kind, lit, text in gen.warmup(traffic, config, pools):
                for _ in range(int(cmd.get("repeat", 1))):
                    recs.append(_timed(clients[0], kind, lit, text, 0))
            _reply(out, recs)
            continue
        t0 = time.time()
        t_end = t0 + float(cmd["seconds"])
        only = cmd.get("only")
        per = [[] for _ in range(n)]

        def lane(i: int) -> None:
            done = getattr(streams[i], "done", None)
            while time.time() < t_end:
                kind, lit, text = streams[i].next(only)
                rec = _timed(clients[i], kind, lit, text, i)
                per[i].append(rec)
                if done is not None:
                    done(kind, lit, rec[4])

        threads = [threading.Thread(target=lane, args=(i,)) for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        _reply(out, {"t0": t0, "t_end": t_end,
                     "records": [r for lane_ in per for r in lane_]})
    for c in clients:
        c.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
