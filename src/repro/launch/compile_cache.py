"""Where JAX keeps its persistent compilation cache.

A full-width serving program takes tens of seconds to compile, and a
cache only hits when its directory stays put, so entry points call
:func:`enable_compile_cache` before their first compile (never at
import)."""
from __future__ import annotations

import os

import jax

#: the checkout's own cache directory (listed in .gitignore)
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Use ``$JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it
    itself, and nothing here overrides it); otherwise point the cache at
    :data:`CACHE_DIR`.  Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
