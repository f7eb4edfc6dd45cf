"""JAX's persistent compile cache: placed from outside, or at one fixed path.

Call :func:`enable_compile_cache` before the first compile of a process
(``launch/serve.py``'s ``main`` and ``chip_smoke.py`` do). Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing here
changes it. Otherwise the cache goes to ``.jax_cache`` at the root of the
checkout (gitignored): a fixed path, never built from a temporary name, a
process id or the time, so the next run of the same checkout finds it.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CACHE_DIR", "enable_compile_cache"]

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; return the directory in use."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
