"""JAX's persistent compilation cache at a fixed place.

The cache directory is part of the cache key's lookup, so a directory that
moves (tempfile, pid, time) never hits. `enable_compile_cache()` is the one
place the repo's entry points (`chip_smoke.py`, `bench.py`'s device child)
turn the cache on.
"""

from __future__ import annotations

import os

__all__ = ["enable_compile_cache"]

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def enable_compile_cache() -> str:
    """Return the directory jax caches compiled programs in.

    Where `JAX_COMPILATION_CACHE_DIR` is set jax already reads it and this
    sets nothing; otherwise the cache lives in `<checkout>/.jax_cache`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
