"""JAX's persistent compilation cache, at one fixed place.

``JAX_COMPILATION_CACHE_DIR`` wins when it is set; otherwise the cache is
``<checkout>/.jax_cache`` (listed in .gitignore).  The path is part of the
cache key, so it must not move between runs."""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return that directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
