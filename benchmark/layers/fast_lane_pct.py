"""Share of updates that took the raw-bytes lane: `ingest.fast_docs` / (`fast_docs` + `slow_docs`)."""


def read(w):
    fast, slow = w.counters.get("ingest.fast_docs", 0), w.counters.get("ingest.slow_docs", 0)
    return 100.0 * fast / (fast + slow) if fast + slow else None
