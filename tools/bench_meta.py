#!/usr/bin/env python
"""Provenance stamp for bench JSON artifacts.

A bench number without its provenance is unreproducible: two artifacts
with the same metric can come from different engine revisions or from a
run that flipped a server knob mid-experiment. Every emitted bench
summary (the smokes, tools/chaos_bench.py) carries a `meta` block:

  git_rev             HEAD short rev, "-dirty<hash>" when the working
                      tree diff touches the engine or the bench drivers
  config_fingerprint  sha256 over every (name, value) config parameter —
                      two runs compare cleanly only when it matches
  overrides           the parameters whose ACTIVE value differs from the
                      registry default (the knobs this run turned)

Stdlib + repo only; collect() never raises — a bench must not die on a
missing git binary.
"""

from __future__ import annotations

import hashlib
import os
import subprocess

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_BENCH_SOURCES = ("oceanbase_tpu", "tools")


def git_rev(repo: str = _REPO) -> str:
    """HEAD short rev + working-tree diff hash: uncommitted engine
    changes must invalidate cross-run comparisons too."""
    try:
        rev = subprocess.run(
            ["git", "-C", repo, "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
        ).stdout.strip() or "unknown"
        diff = subprocess.run(
            ["git", "-C", repo, "diff", "HEAD", "--", *_BENCH_SOURCES],
            capture_output=True, text=True, timeout=20,
        ).stdout
        if diff:
            rev += "-dirty" + hashlib.md5(diff.encode()).hexdigest()[:8]
        return rev
    except Exception:
        return "unknown"


def config_fingerprint(config=None) -> str:
    """sha256 over the sorted (name, value) pairs of the ACTIVE config
    (the benched Database's when given, the registry defaults else)."""
    try:
        if config is None:
            from oceanbase_tpu.share.config import Config

            config = Config()
        pairs = [(n, repr(v)) for n, v, _p in config.snapshot()]
        h = hashlib.sha256(repr(sorted(pairs)).encode())
        return h.hexdigest()[:16]
    except Exception:
        return "unknown"


def config_overrides(config=None) -> dict:
    """Parameters whose active value differs from the registry default —
    the session/system variables this run actually turned."""
    try:
        if config is None:
            return {}
        return {
            n: v for n, v, p in config.snapshot() if v != p.default
        }
    except Exception:
        return {}


def collect(db=None) -> dict:
    """The `meta` block benches stamp into every emitted artifact."""
    config = getattr(db, "config", None) if db is not None else None
    return {
        "git_rev": git_rev(),
        "config_fingerprint": config_fingerprint(config),
        "overrides": {
            k: (v if isinstance(v, (int, float, bool, str)) else repr(v))
            for k, v in config_overrides(config).items()
        },
    }


if __name__ == "__main__":
    import json

    print(json.dumps(collect(), indent=2))
