"""The persistent compilation cache, at one fixed path.

Compiling the frontier pipeline's executables for a TPU takes minutes (the
hash-reorder engine's sorts dominate), so every entry point keeps compiled
programs on disk.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and nothing here overrides it; otherwise the cache lives in the
checkout (``.jax_cache``, ignored by git).  The path never depends on a
temporary name, a pid or the time: a later run must find the same cache.
"""
from __future__ import annotations

import os

import jax

CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns its directory.  Call before
    the first compile."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE)
    return jax.config.jax_compilation_cache_dir
