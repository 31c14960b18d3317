"""JAX's persistent compilation cache for the entry points.

The drivers (``repro.launch.train`` / ``serve`` / ``fleet``,
``benchmarks.run``, ``chip_smoke.py``) call :func:`enable_compile_cache`
once at start-up; importing the package never does.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing is
changed.  Otherwise the cache goes to ``.jax_cache`` at the root of the
checkout: a fixed path, because the path is part of the cache key and a
directory that moves never hits.
"""

from __future__ import annotations

import os
import pathlib

import jax

#: the checkout root (``src/repro/launch/`` is three levels below it)
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; return it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
