"""JAX's persistent compilation cache, kept at one fixed path.

JAX keys cache entries by the directory they live in, so a cache that
moves never hits.  Entry points that compile for the chip
(``chip_smoke.py``, ``benchmarks/run.py``) call :func:`enable` before
any other JAX work.
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["CACHE_DIR", "enable"]

# <checkout>/.jax_cache (git-ignored), resolved from this file's location
CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here.  Otherwise the cache goes to :data:`CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
