"""Where the entry points keep JAX's persistent compilation cache.

``$JAX_COMPILATION_CACHE_DIR``, where it is set, is JAX's own setting
and this module leaves it alone.  Otherwise the cache is ``.jax_cache/``
at the root of the checkout: a fixed path, because the path is part of
what a later run must find again (ignored by git).
"""

from __future__ import annotations

import os

import jax

CHECKOUT_CACHE = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", ".jax_cache"))


def place_compile_cache() -> str:
    """Point JAX's persistent compilation cache at the checkout unless
    ``$JAX_COMPILATION_CACHE_DIR`` already places it; returns the
    directory in use.  Call once from an entry point, before the first
    compile."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE)
    return CHECKOUT_CACHE
