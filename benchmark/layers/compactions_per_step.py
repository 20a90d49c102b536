"""Rooms the served path compacted per step of the window: `ingest.room_compactions` (the phase recorder's copy of the count, the window's delta) / `flush_device` steps. About 0.005-0.02 in the typed cell (a compaction a second or so), or the traffic is not this cell's. A program without the counter has nothing to read."""


def read(w):
    st = w.phases.get("ingest.room_compactions")
    steps = len(w.dispatch_spans)
    return (st.get("value") or 0.0) / steps if st is not None and steps else None
