"""JAX's persistent compilation cache for the repository's entry points.

A cold start on a TPU compiles every search program from scratch; the
persistent cache lets a second process load them instead.  The entry
points (``launch/search.py``, ``launch/serve_search.py``,
``launch/serve_http.py`` and ``chip_smoke.py``) call
:func:`enable_compile_cache` once, before their first compile.  Tests
never call it.
"""
from __future__ import annotations

import os
import pathlib

import jax

# <checkout>/.jax_cache — src/repro/launch/compile_cache.py is three levels
# below the checkout root.  A fixed path, never one built from a temp name,
# a pid or the time, so the next run finds what this one wrote.
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    this sets nothing.  Otherwise the cache goes to ``.jax_cache/`` at the
    root of the checkout."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
