"""JAX persistent compilation cache for the entry points.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets nothing.  Otherwise the cache goes to ``.jax_cache/`` at the
checkout root: a fixed path, because the path is part of what a later
process must match to find an entry again.  Entry points (``serve.py``,
``chip_smoke.py``, the benchmarks) call :func:`enable_compile_cache` from
their ``__main__`` code; library imports and tests never do.
"""

from __future__ import annotations

import os
import pathlib

import jax

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
