"""Where JAX keeps its persistent compilation cache.

Every entry point (``chip_smoke.py``, ``repro.launch.dist``,
``repro.launch.train``, ``repro.bench.run``) calls
:func:`enable_compilation_cache` once, before it compiles anything.
The cache key includes the directory, so the directory is fixed: it is
never built from a temporary name, a process id or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

# <checkout>/.jax_cache (git-ignored): src/repro/utils/cache.py -> root
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory: the one ``JAX_COMPILATION_CACHE_DIR`` names when it is
    set (JAX reads the variable itself, and no other directory is set
    here), else ``.jax_cache`` at the root of the checkout."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
