"""Where the entry points place JAX's persistent compilation cache."""
from __future__ import annotations

import jax
import pytest

from repro.launch.compile_cache import CHECKOUT_CACHE_DIR, use_compile_cache


@pytest.mark.parametrize("env_dir", [None, "/elsewhere/jax-cache"])
def test_use_compile_cache(monkeypatch, env_dir):
    prev = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    try:
        placed = use_compile_cache()
        if env_dir is None:
            # a fixed path at the checkout root, never a temp or per-run name
            assert placed == str(CHECKOUT_CACHE_DIR)
            assert CHECKOUT_CACHE_DIR.name == ".jax_cache"
            assert (CHECKOUT_CACHE_DIR.parent / "chip_smoke.py").is_file()
            assert jax.config.jax_compilation_cache_dir == placed
        else:
            # JAX reads the variable itself; nothing is set in code
            assert placed == env_dir
            assert jax.config.jax_compilation_cache_dir == prev
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
