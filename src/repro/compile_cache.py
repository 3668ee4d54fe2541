"""Persistent XLA compile cache for the program's entry points.

Entry points (`chip_smoke.py`, `benchmarks/run.py`, `bench_sweep`,
`examples/*.py`) call `use_compile_cache()` before their first compile.
Importing `repro` sets nothing, so the tests, and the compiles they make
for a described chip, stay cache-free.
"""
from __future__ import annotations

import os

import jax

# A fixed path: the directory is part of the cache key, so a temporary or
# per-process directory would never be hit again.
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def use_compile_cache() -> str:
    """Point JAX's persistent compile cache at a fixed directory.

    Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and
    nothing is changed; otherwise the cache lives in `<checkout>/.jax_cache`.
    Returns the directory in use.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
