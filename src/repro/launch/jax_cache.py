"""JAX's persistent compilation cache, placed for the entry points.

Entry points (``cz-compress``, ``launch/train.py``, ``benchmarks/run.py``,
``chip_smoke.py``) call :func:`enable_compile_cache` once at start-up;
nothing calls it at import, and the tests leave the cache off.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets no path.  Otherwise the cache goes to :data:`CHECKOUT_CACHE`, a
fixed ``.jax_cache/`` at the root of the checkout (listed in
``.gitignore``): the directory is part of the cache's key, so it is never
built from a temporary name, a process id or the time.
"""
from __future__ import annotations

import os

import jax

__all__ = ["CHECKOUT_CACHE", "enable_compile_cache"]

CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's compilation cache at its directory; returns the path."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE)
    return CHECKOUT_CACHE
