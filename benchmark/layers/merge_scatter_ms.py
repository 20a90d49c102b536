"""Host time of the program's `ingest.merge.scatter` stage (the one enqueue of `merge_stream`: rebase + `full.at[idx].set(fast)` over every plane of the batch, with its three small uploads) per step (phases recorder; a host stage)."""


def read(w):
    st = w.phases.get("ingest.merge.scatter")
    steps = len(w.dispatch_spans)
    return st["execute_s"] / steps * 1e3 if st and steps else None
