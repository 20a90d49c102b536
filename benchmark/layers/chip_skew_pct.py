"""(Busiest chip's busy time - idlest chip's) / busiest chip's, from the profiler trace of the slice."""

from benchmark import chip_trace


def read(w):
    ev = chip_trace.planes() if w.trace else None
    share = chip_trace.skew(chip_trace.busy_by_chip(ev)) if ev else None
    return None if share is None else 100.0 * share
