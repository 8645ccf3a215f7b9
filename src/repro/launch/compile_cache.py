"""JAX's persistent compilation cache, kept at one fixed path.

The cache key includes the directory, so a path made from a temporary
name, a pid or the time never hits.  Where ``JAX_COMPILATION_CACHE_DIR``
is set, JAX reads it itself and nothing is set here; otherwise the cache
goes to ``.jax_cache/`` at the root of the checkout (listed in
``.gitignore``).  Call :func:`enable_compile_cache` before the first
compile.
"""
from __future__ import annotations

import os

import jax

__all__ = ["CACHE_DIR", "enable_compile_cache"]

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Point the persistent compilation cache at its fixed directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
