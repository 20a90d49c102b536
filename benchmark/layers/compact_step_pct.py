"""Share of the window's `apply_bytes` calls whose integrate step was the compact one: `ingest.compact_steps` / (`compact_steps` + `dense_steps`). The window's counter deltas where they carry the two names, else the phase recorder's copy of the same counts (stage value). A program without the counters has nothing to read."""


def read(w):
    def delta(name):
        return w.counters.get(name) or (w.phases.get(name) or {}).get("value") or 0

    compact, dense = delta("ingest.compact_steps"), delta("ingest.dense_steps")
    return 100.0 * compact / (compact + dense) if compact + dense else None
