"""JAX's persistent compilation cache for the entry points.

Entry points (``launch.serve``, ``launch.train``, ``chip_smoke.py``) call
:func:`enable_compile_cache` at the start of ``main``, so a second process
on the same machine reuses the first one's compiled programs instead of
compiling the full-width model again.  Library import and tests never
turn it on: an ahead-of-time compile for a described (not attached) TPU
would land in the cache and could not be read back.
"""
from __future__ import annotations

import os

import jax

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))

#: Used when ``JAX_COMPILATION_CACHE_DIR`` is unset.  The path is part of
#: the cache key, so it is fixed: a directory that moved would never hit.
DEFAULT_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as it is (JAX reads
    the variable itself) and no other path is set; otherwise the cache
    lives in ``.jax_cache/`` at the root of the checkout."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
