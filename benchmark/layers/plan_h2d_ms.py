"""Host time a step spends uploading the host lane's planes: the program's stage `ingest.plan.h2d` (27 planes over every slot, leaf by leaf, in a step that built them; nothing in a step handed a kept batch) per step (phases recorder; a host stage)."""


def read(w):
    st = w.phases.get("ingest.plan.h2d")
    steps = len(w.dispatch_spans)
    return st["execute_s"] / steps * 1e3 if st and steps else None
