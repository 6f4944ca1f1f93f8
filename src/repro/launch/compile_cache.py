"""Where JAX's persistent compilation cache lives.

A chip run compiles every step shape afresh unless the compiled programs
are on disk, and the cache key includes the directory, so the directory
must not move between runs.  ``JAX_COMPILATION_CACHE_DIR``, when set,
places it (JAX reads the variable itself); otherwise it is the fixed,
git-ignored ``.jax_cache`` at the root of the checkout.

Entry points call ``enable_compile_cache()`` from their ``main``; nothing
calls it at import time, and the test suite never turns the cache on.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
