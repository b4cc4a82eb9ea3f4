"""JAX's persistent compilation cache, placed where a caller can find it again.

The cache's key includes its path, so a directory that moves between runs
never hits. ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it on its
own and nothing here overrides it. Otherwise the cache lives at the fixed
``<checkout>/.jax_cache`` (listed in ``.gitignore``).

Entry points call :func:`enable_compile_cache` before their first compile;
importing ``repro`` never does.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return the directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    # keep every program, not only those over JAX's default 1 s threshold:
    # the serve path compiles one decode step per cache shape
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
