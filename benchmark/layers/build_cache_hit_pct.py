"""Share of the builds that asked the persistent cache, before the window, that it answered: `cache_hits` / `compile_requests_use_cache` (`benchmark/setup_parts.py`). Near 100 on a warm cache, 0 on a tree's first run. A program without the totals, or one that never asked, has nothing to read."""

from benchmark import setup_parts


def read(w):
    totals = setup_parts.at_opening(w)
    if totals is None or not totals["cache_requests"]:
        return None
    return 100.0 * totals["cache_hits"] / totals["cache_requests"]
