"""Host time of one compaction, enqueue to re-homed strings: the program's `ingest.compact` phase, `execute_s / calls` over the window (phases recorder; the wait for the device program is inside it, in `.d2h`). The foreground stall a compaction costs the step that carries it."""


def read(w):
    st = w.phases.get("ingest.compact")
    return st["execute_s"] / st["calls"] * 1e3 if st and st.get("calls") else None
