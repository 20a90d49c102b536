"""Mean time an update waited in its room's queue: the program's `sync.queue_wait`, from `_enqueue` to the start of the `flush_device` step that carried it, `execute_s / calls` (one call an update carried; phases recorder). `queue_wait_ms` is the outside reading of the open-loop cells, from the instant an update was due."""


def read(w):
    st = w.phases.get("sync.queue_wait")
    return st["execute_s"] / st["calls"] * 1e3 if st and st.get("calls") else None
