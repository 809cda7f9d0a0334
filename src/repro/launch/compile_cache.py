"""JAX's persistent compilation cache, at one fixed place per checkout.

A cached program is keyed, among other things, by the cache directory's
path, so the directory never moves: ``JAX_COMPILATION_CACHE_DIR`` when the
environment sets it (JAX reads it itself, and nothing here overrides it),
otherwise ``<checkout>/.jax_cache`` (git-ignored).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    the path. Call before the first compile; touches no device."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
