"""Seconds jax spent tracing the programs built before the window (Python to jaxpr; a nested trace counted once, in its outermost): the process's build totals (`ytpu/utils/compile_cache.py`, `jax.monitoring`'s own timings) as they stood at the window's opening (`benchmark/setup_parts.py`). A program without the totals has nothing to read."""

from benchmark import setup_parts


def read(w):
    return setup_parts.part(w, "trace_s")
