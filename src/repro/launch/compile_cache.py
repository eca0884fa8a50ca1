"""JAX's persistent compilation cache for the entry points that compile.

``enable_compile_cache()`` is called by an entry point (``chip_smoke.py``,
``benchmarks/run.py``) before its first compile; the library never calls it
on import. The cache lives where ``JAX_COMPILATION_CACHE_DIR`` says when that
is set, and otherwise at one fixed directory of the checkout, ``.jax_cache``
(git ignores it): the path is part of what JAX keys entries on, so a
directory that moved would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["enable_compile_cache", "DEFAULT_CACHE_DIR"]

DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it writes to."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    # the assignment kernel compiles in about a second: cache every program
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
