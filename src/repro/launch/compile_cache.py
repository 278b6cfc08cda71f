"""JAX's persistent compilation cache, kept at one fixed place.

A cached program is found again only under the same directory, so the
path never depends on a temp name, a pid or the time.
"""

from __future__ import annotations

import os
import pathlib

import jax

# src/repro/launch/compile_cache.py -> the checkout root
CHECKOUT_ROOT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the cache: JAX reads it
    itself and no other directory is set here.  Otherwise the cache is
    ``.jax_cache/`` at the checkout root (listed in ``.gitignore``).
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    path = str(CHECKOUT_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
