"""Rows a compaction gave back to its room, mean over the window: `ingest.rows_reclaimed` / `ingest.room_compactions` (the phase recorder's copies, the window's deltas). Nothing to read in a window without a compaction, or from a program without the counters."""


def read(w):
    def delta(name):
        return (w.phases.get(name) or {}).get("value") or 0.0

    n = delta("ingest.room_compactions")
    return delta("ingest.rows_reclaimed") / n if n else None
