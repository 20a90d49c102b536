"""Device time of the ops traced under the `conflict_scan` scope (both tiers) per step, from the profiler trace of the slice."""

from benchmark import program_trace


def read(w):
    ev = program_trace.events() if w.trace else None
    secs = program_trace.scoped_device_seconds(ev, "conflict_scan") if ev else None
    steps = w.trace_span_count("bench.dispatch")
    return secs / steps * 1e3 if secs is not None and steps else None
