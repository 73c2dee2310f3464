"""JAX's persistent compilation cache, as the entry points configure it.

A cold call on a fresh chip machine compiles every program again; a cache
at a path that stays put lets the processes of one run, and later runs in
the same checkout, reuse what was compiled. The path is part of the cache
key, so it is fixed: never a temporary, per-process or time-stamped one.

Entry points call `enable_compile_cache()` once, before their first
compilation. Library modules never call it: importing the package must not
change JAX's configuration.
"""
from __future__ import annotations

import os
from pathlib import Path

# <checkout>/src/repro/launch/compile_cache.py -> <checkout>/.jax_cache,
# which .gitignore lists
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    Where JAX_COMPILATION_CACHE_DIR is set, JAX reads it itself and this
    sets nothing; otherwise the cache goes to the checkout's `.jax_cache`."""
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
