"""JAX's persistent compilation cache, set up in one place.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
is set here. Otherwise the cache goes to ``.jax_cache/`` at the root of the
checkout (git-ignored): one fixed path, so that a later run of the same
checkout finds what an earlier one compiled.
"""
from __future__ import annotations

import os

import jax

CHECKOUT_CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), os.pardir, os.pardir, os.pardir, ".jax_cache"))


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at its directory; returns the path."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
