"""Seconds jax spent lowering the programs built before the window (jaxpr to an MLIR module): the process's build totals (`ytpu/utils/compile_cache.py`, `jax.monitoring`'s own timings) as they stood at the window's opening (`benchmark/setup_parts.py`). A program without the totals has nothing to read."""

from benchmark import setup_parts


def read(w):
    return setup_parts.part(w, "lower_s")
