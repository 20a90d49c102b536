"""95th percentile of (instant the generator thread handed a frame to the loop's inbox - the instant it was due)."""

from benchmark.stats import percentile


def read(w):
    r = w.rec
    late = [(r.handed[i] - r.due[i]) * 1e3 for i in range(len(r.kind))]
    return percentile(late, 95) if late else None
