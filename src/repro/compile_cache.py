"""JAX's persistent compilation cache, for the program's entry points.

Entry points (``chip_smoke.py``, ``launch/serve.py``'s ``main``,
``benchmarks/common.py``) call :func:`enable_compile_cache` before their
first compile; importing the library never does, so tests compile as they
always have.
"""
from __future__ import annotations

import os

__all__ = ["CACHE_DIR", "enable_compile_cache"]

# one fixed path inside the checkout: the cache key includes nothing about
# the directory, but a directory that moved between runs is never found again
CACHE_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no
    other directory is set; otherwise the cache lives at :data:`CACHE_DIR`.
    Every compile is cached, however short: a launch compiles one program
    per row bucket, family and mode, each in about a second.
    """
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
