"""The integrate step's share of its HBM roofline: (state read once + written once) / 819 GB/s, over the device time of the integrate program per step. Bandwidth-bound."""

from benchmark.peaks import integrate_min_seconds


def read(w):
    secs, steps = w.trace_program_s("integrate_step"), w.trace_span_count("bench.dispatch")
    if not steps or secs != secs or secs <= 0:
        return None
    return 100.0 * integrate_min_seconds(w.state_bytes, w.device_kind) / (secs / steps)
