"""Persistent XLA compilation cache for the program's entry points.

Every entry point (``chip_smoke.py``, the examples, ``python -m repro.sql``,
``benchmarks/run.py``, ``scripts/run_parties.py``) calls
:func:`enable_compile_cache` first thing under its ``__main__`` guard.
Importing the package never turns the cache on, and the tests never do.

Where the cache lives: ``JAX_COMPILATION_CACHE_DIR`` when it is set (and no
other directory), else the fixed ``<checkout>/.jax_cache`` (gitignored). The
path is part of what a cache hit depends on, so it is never built from a temp
name, a PID or the time.
"""
from __future__ import annotations

from pathlib import Path

import jax

__all__ = ["DEFAULT_CACHE_DIR", "enable_compile_cache"]

DEFAULT_CACHE_DIR = str(Path(__file__).resolve().parents[2] / ".jax_cache")

# The engine dispatches many small programs; cache every one that took at
# least this long to compile (JAX's own default, 1 s, would skip most).
MIN_COMPILE_SECONDS = 0.1


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on and return its directory:
    the one ``JAX_COMPILATION_CACHE_DIR`` names (JAX reads the variable
    itself), else :data:`DEFAULT_CACHE_DIR`."""
    if not jax.config.jax_compilation_cache_dir:
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", MIN_COMPILE_SECONDS
    )
    return jax.config.jax_compilation_cache_dir
