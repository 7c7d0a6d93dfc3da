"""JAX's persistent compilation cache, for the entry-point scripts.

JAX finds a cached program again only in the directory it was written to,
so that directory must not move between runs: ``$JAX_COMPILATION_CACHE_DIR``
when it is set, otherwise ``.jax_cache/`` at the root of the checkout
(git-ignored). Entry points call :func:`enable_compile_cache` once, before
their first compile; importing the library never touches the cache.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at its fixed directory; returns it."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CHECKOUT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
