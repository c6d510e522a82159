"""Where the launchers keep JAX's persistent compilation cache.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself; when it is set, nothing here
overrides it. When it is not, the cache goes to a fixed directory inside
the checkout (``.jax_cache/``, git-ignored): the cache key includes the
path, so a directory named after a PID, a temp dir or the time never hits.
"""

from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it writes to."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
