"""The one rule for JAX's persistent compilation cache.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its cache
there and no code names another directory. Otherwise the cache lives at
one fixed path inside the checkout: the directory is part of the cache
key, so a directory that moves between runs (a ``mkdtemp`` data dir, a
per-tool folder) never hits.

Nothing turns the cache on at import, in ``Database.__init__`` or in the
tests' ``conftest.py``: the entry points that want compiles to persist
call ``enable_compile_cache()`` themselves (``chip_smoke.py``, the
benchmark, the plan-artifact store when its mode is on).
"""

from __future__ import annotations

import os

import jax
from jax.experimental.compilation_cache import compilation_cache as _cc

#: unset-variable default, listed in .gitignore
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Persist every compile of this process; returns the directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # jax latches "no cache" on the first compile of the process; without
    # a reset a directory configured after that compile is ignored
    _cc.reset_cache()
    return path
