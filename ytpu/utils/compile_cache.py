"""JAX's persistent compilation cache at a fixed place.

The cache directory is part of the cache key's lookup, so a directory that
moves (tempfile, pid, time) never hits. `enable_compile_cache()` is the one
place the repo's entry points (`chip_smoke.py`, `bench.py`'s device child,
`benchmark/run.py`) turn the cache on.

It also puts the programs' metadata into the cache key. jax leaves it out
by default, so a cache warmed by a tree without a `jax.named_scope` hands
the same executable, with its old op names, to a tree that has one, and
the profiler then shows `while.663` where the source says
`conflict_scan/cheap`. The price is that an edit which moves a traced
line recompiles the programs traced from that file once.
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
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
