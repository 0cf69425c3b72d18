"""Where the entry scripts keep JAX's persistent compilation cache.

Called by ``chip_smoke.py`` and ``bench.py`` before their first compile,
never at package import. The cache's path is part of its key, so it has
to be the same on every run: ``JAX_COMPILATION_CACHE_DIR`` when the
caller set it (JAX reads that itself, and nothing here overrides it),
otherwise ``.jax_cache/`` at the root of this checkout — derived from
this file's own location, never from a temporary name, a pid or the
time. The directory is git-ignored.
"""
from __future__ import annotations

import os

__all__ = ["enable_compile_cache"]

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its
    directory."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
