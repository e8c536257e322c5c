"""Persistent XLA compilation cache for the repository's entry points.

Entry points (``chip_smoke.py``, the benchmarks, the examples) call
:func:`init_compile_cache` once before their first compile.  Library code
never sets a cache: importing ``repro`` has no side effects.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: Where the cache lives when ``JAX_COMPILATION_CACHE_DIR`` is unset.  A
#: fixed path: the directory is part of the cache's key, so one that moved
#: between runs would never hit.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def init_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
    that setting stands.  Otherwise the cache goes to
    :data:`DEFAULT_CACHE_DIR` (``<repo root>/.jax_cache``).
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
