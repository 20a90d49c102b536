"""Host time of the program's `ingest.merge` stage (`_merge_fast_lane`: pack, uploads, the table look-ups and the three enqueues: gather, `decode.v1`, `merge_stream`) per step (phases recorder; a host stage)."""


def read(w):
    st = w.phases.get("ingest.merge")
    steps = len(w.dispatch_spans)
    return st["execute_s"] / steps * 1e3 if st and steps else None
