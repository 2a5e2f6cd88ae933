"""Where the persistent XLA compilation cache lives.

Entry points call :func:`enable_compile_cache` from ``main`` (never at
import). ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself
and nothing here overrides it. Otherwise the cache goes to one fixed,
git-ignored path in the checkout, ``.jax_cache/`` — the path is part of
the cache key, so it never carries a temp name, a pid or a time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_ROOT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
