"""Seconds the backend really compiled before the window: the `backend_compile_duration` events' seconds, which hold a read of the persistent cache where it hit, less `cache_retrieval_time_sec` (`benchmark/setup_parts.py`). Near 0 on a warm cache. A program without the totals has nothing to read."""

from benchmark import setup_parts


def read(w):
    totals = setup_parts.at_opening(w)
    return None if totals is None else totals["backend_s"] - totals["cache_load_s"]
