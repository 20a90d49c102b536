"""Kilobytes a step sends to the device(s) for the host lane's batch: the phase recorder's `h2d_bytes` of the program's stage `ingest.plan.h2d` (counted where the batch is uploaded: every copy of every array sent, nothing in a step handed a kept batch) / steps / 1,024. Says whether the batch is as wide as the step or as the slots: 16 rooms at the 4-row bucket are under 8 KB, 1,024 rooms about 400. A program without the stage has nothing to read."""


def read(w):
    st = w.phases.get("ingest.plan.h2d")
    steps = len(w.dispatch_spans)
    return st["h2d_bytes"] / steps / 1024.0 if st and steps else None
