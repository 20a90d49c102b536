"""1 - union of device-op intervals / slice, from the profiler trace."""


def read(w):
    t = w.trace
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"]) if t.get("window_s") else None
