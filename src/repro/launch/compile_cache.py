"""JAX's persistent compilation cache, shared by every entry point.

A compiled program is keyed, among other things, by the cache directory,
so the directory never moves: ``JAX_COMPILATION_CACHE_DIR`` when the
environment sets it (JAX reads it itself, and nothing here overrides it),
otherwise ``.jax_cache`` at the root of this checkout.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: the cache's home when the environment names none (git-ignored)
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
