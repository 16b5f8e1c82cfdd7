"""JAX's persistent compilation cache, kept at a fixed place.

A cold process compiles every program again; with the cache on, a later
process that builds the same program reads the executable back instead.
JAX finds an entry only in the directory it was written to, so the
directory must not move between runs: no temp dir, pid or time in it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache (listed in .gitignore)
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and is
    left to JAX; otherwise the cache goes to ``DEFAULT_DIR``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
