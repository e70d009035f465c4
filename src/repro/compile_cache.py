"""Persistent XLA compilation cache for the runnable entry points.

Called once by each script a user runs (``chip_smoke.py``,
``benchmarks/run.py``, the examples) before its first compile — never
on import, so library users and the tests keep JAX's own defaults.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[2]


def use_compile_cache() -> str:
    """Turn on the persistent compile cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is honoured as JAX reads
    it and nothing is set here.  Otherwise the cache lives at the fixed
    ``<checkout>/.jax_cache``: a fixed path, because a later process
    only finds entries written under the same directory.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
