"""JAX's persistent compilation cache for the entry points.

A full-width train step takes tens of seconds to compile; with the cache on,
a second process (or a second ``jit`` of the same step in one process) reads
it back.  The cache key includes the directory, so it is a fixed path:

* where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses it and
  nothing else is set here;
* otherwise ``<checkout>/.jax_cache`` (listed in ``.gitignore``).
"""

from __future__ import annotations

import os

import jax

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


__all__ = ["enable_compile_cache"]
