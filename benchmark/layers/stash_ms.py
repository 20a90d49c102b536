"""Host time of planning one room that holds or gains a stash: the program's stage `ingest.plan.stash` (the re-merge of the stash with the arrival, the partition against the room's mirror and the deferred-delete split; a leaf of `ingest.plan.host_rows`), `execute_s / calls` over the window (phases recorder). A program without the span, or a window without a stash, has nothing to read."""


def read(w):
    st = w.phases.get("ingest.plan.stash")
    return st["execute_s"] / st["calls"] * 1e3 if st and st.get("calls") else None
