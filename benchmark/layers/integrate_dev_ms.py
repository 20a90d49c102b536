"""Device time of the decode + integrate programs per step, from the profiler trace of the slice."""


def read(w):
    secs, steps = w.trace_program_s("integrate"), w.trace_span_count("bench.dispatch")
    return secs / steps * 1e3 if steps and secs == secs else None
