"""Host time of the program's `ingest.plan` phase per step (phases recorder; a host stage, so the host clock is the right clock)."""


def read(w):
    st = w.phases.get("ingest.plan")
    steps = len(w.dispatch_spans)
    return st["execute_s"] / steps * 1e3 if st and steps else None
