"""95th percentile, over every update frame of the window, of (end of the
dispatch that integrated it - the instant it was due)."""

from benchmark.stats import percentile


def read(w):
    r = w.rec
    lat = [(r.done[i] - r.due[i]) * 1e3 for i in w.indices("update")]
    return percentile(lat, 95) if lat else None
