"""Device time of the decode + integrate programs per step on the chip where it is largest, from the profiler trace of the slice (`integrate_dev_ms` sums the chips)."""

from benchmark import chip_trace


def read(w):
    ev = chip_trace.planes() if w.trace else None
    per_chip = chip_trace.program_seconds_by_chip(ev, w.programs.get("integrate", [])) if ev else None
    chip, steps = chip_trace.fullest(per_chip), w.trace_span_count("bench.dispatch")
    return per_chip[chip] / steps * 1e3 if chip is not None and steps else None
