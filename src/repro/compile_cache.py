"""Persistent XLA compilation cache for the command-line entry points.

Entry points (`chip_smoke.py`, `benchmarks/run.py`, `repro.service.run`,
`examples/*`) call `enable_compile_cache()` once at start-up; importing
the library never touches the cache.  Where `JAX_COMPILATION_CACHE_DIR`
is set, JAX already reads it and nothing else is set here.  Otherwise
the cache sits at a fixed `<checkout>/.jax_cache`: the directory is part
of the cache key, so a path built from a temporary name, a pid or the
time would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["enable_compile_cache", "DEFAULT_CACHE_DIR"]

DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
