"""Where JAX keeps its persistent compilation cache.

Programs that compile for the chip call :func:`setup_compile_cache` before
their first compile.  ``JAX_COMPILATION_CACHE_DIR``, when set, is read by
JAX itself and wins; otherwise the cache lives at one fixed path inside
the checkout (``<repo>/.jax_cache``, git-ignored).  The path is part of
the cache key, so it never depends on a temp directory, a pid or a time.
"""
from __future__ import annotations

import os
import pathlib

import jax

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; return it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
