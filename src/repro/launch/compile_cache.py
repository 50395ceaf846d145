"""Where JAX keeps its persistent compilation cache.

Entry points call :func:`use_compile_cache` at start-up (never at import),
so a second run in the same checkout reads its compiled programs back
instead of compiling them again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: ``.jax_cache/`` at the checkout root (gitignored).  The path is fixed:
#: a cache entry is only found again under the directory it was written to.
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Place the cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here; otherwise the cache goes to the checkout's
    ``.jax_cache/``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)


__all__ = ["use_compile_cache", "CHECKOUT_CACHE_DIR"]
