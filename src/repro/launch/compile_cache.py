"""Where the entry points keep JAX's persistent compilation cache.

A cache entry is keyed by its directory among other things, so the
directory must not move between runs: it is either the one named by
``JAX_COMPILATION_CACHE_DIR`` (which JAX reads itself) or a fixed
``.jax_cache`` at the root of the checkout. Only entry points call
``enable_compile_cache``; library code and tests leave the cache alone.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
#: <checkout>/.jax_cache (this file is <checkout>/src/repro/launch/...)
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def cache_dir_to_set() -> Optional[str]:
    """The directory the entry point must configure, or None when
    ``JAX_COMPILATION_CACHE_DIR`` is set and JAX already uses it."""
    if os.environ.get(ENV_VAR):
        return None
    return str(CHECKOUT_CACHE)


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = cache_dir_to_set()
    if path is None:
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", path)
    return path
