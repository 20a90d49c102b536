"""Share of the window's updates that took the host lane for the stash's sake: `ingest.slow.dependency` (the update waits for another) + `ingest.slow.pending` (its room holds a stash) / the window's update frames x 100 (the phase recorder's copies of the two counts, by the first reason `_slow_reason` found; `pending` also counts a payload the prescan could not read, of which a run has none). Nothing to read where neither was counted."""


def read(w):
    stages = [w.phases.get("ingest.slow.dependency"), w.phases.get("ingest.slow.pending")]
    n = len(w.indices("update"))
    if not n or all(st is None for st in stages):
        return None
    return 100.0 * sum((st or {}).get("value") or 0.0 for st in stages) / n
