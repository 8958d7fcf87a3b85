"""JAX's persistent compilation cache for the repo's entry points.

A cache only pays when a later process finds it, and its directory is part
of what that process looks up. So the directory is either the one the
environment names or one fixed path inside the checkout, never built from
a temp name, a pid or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# <repo>/.jax_cache (listed in .gitignore)
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    other directory is set here; otherwise the cache is ``REPO_CACHE_DIR``.
    Every compile is cached, not only slow ones: a cold run on the chip
    compiles dozens of kernels that each take under a second. Call before
    the first compile of the process.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
