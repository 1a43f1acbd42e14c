"""JAX persistent compilation cache, set up in one place for every launcher
and `chip_smoke.py`.

The cache directory is part of what a lookup matches, so it must not move
between runs: `JAX_COMPILATION_CACHE_DIR` when the environment sets it (JAX
reads the variable itself, and nothing here overrides it), otherwise the
fixed `.jax_cache/` at the root of the checkout (listed in `.gitignore`).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return that directory. Call before the first compilation."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
