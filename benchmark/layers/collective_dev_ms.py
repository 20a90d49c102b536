"""Device time per step of the collective ops (all-reduce, all-gather, reduce-scatter, all-to-all, collective-permute; union) on the chip where the decode + integrate programs take longest, from the profiler trace of the slice."""

from benchmark import chip_trace


def read(w):
    ev = chip_trace.planes() if w.trace else None
    if not ev:
        return None
    chip = chip_trace.fullest(chip_trace.program_seconds_by_chip(ev, w.programs.get("integrate", [])))
    steps = w.trace_span_count("bench.dispatch")
    if chip is None or not steps:
        return None
    return chip_trace.collective_seconds_by_chip(ev)[chip] / steps * 1e3
