"""Native (C++) runtime components, loaded via ctypes.

The reference implements its storage codecs, log engine and allocators in
C++ (storage/blocksstable/encoding, logservice/palf). Here the native hot
paths live in small C++ translation units compiled on first use with the
baked-in toolchain (g++) into shared objects cached next to the sources;
every native entry point has a numpy fallback so the framework still works
where no compiler is available (pure wheel installs, sandboxes).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

_DIR = os.path.dirname(os.path.abspath(__file__))
_LOCK = threading.Lock()
_LIBS: dict[str, ctypes.CDLL | None] = {}


_FLAGS = ["-O3", "-shared", "-fPIC", "-std=c++17"]


def _build(name: str) -> str | None:
    src = os.path.join(_DIR, f"{name}.cpp")
    # the object's name carries what it was built from (source + flags),
    # and the flags name no CPU: a tree copied to another machine either
    # finds an object that is valid there or builds its own
    with open(src, "rb") as f:
        digest = hashlib.sha256(
            f.read() + " ".join(_FLAGS).encode()).hexdigest()[:12]
    so = os.path.join(_DIR, f"_{name}.{digest}.so")
    if os.path.exists(so):
        return so
    tmp = so + f".tmp.{os.getpid()}"
    cmd = ["g++", *_FLAGS, "-o", tmp, src]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, so)  # atomic: concurrent builders race benignly
        return so
    except (subprocess.SubprocessError, OSError):
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return None


def load(name: str) -> ctypes.CDLL | None:
    """Load (building if needed) the shared object for native/<name>.cpp.

    Returns None when no toolchain is available; callers fall back to numpy.
    Set OCEANBASE_TPU_NO_NATIVE=1 to force fallbacks (used by tests to cover
    both paths).
    """
    if os.environ.get("OCEANBASE_TPU_NO_NATIVE"):
        return None
    with _LOCK:
        if name not in _LIBS:
            so = _build(name)
            _LIBS[name] = ctypes.CDLL(so) if so else None
        return _LIBS[name]
