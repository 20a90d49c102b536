"""Device time of the selection + pack programs per handshake, from the profiler trace of the slice."""


def read(w):
    secs, n = w.trace_program_s("select"), w.trace_span_count("bench.reconnect")
    return secs / n * 1e3 if n and secs == secs else None
