"""Median of (start of the dispatch that carried an update - the instant it was due)."""

from benchmark.stats import median


def read(w):
    r = w.rec
    waits = [(r.disp0[i] - r.due[i]) * 1e3 for i in w.indices("update")]
    return median(waits) if waits else None
