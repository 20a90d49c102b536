"""Host time a step spends planning the host lane's rows: the program's stage `ingest.plan.host_rows` (in a step with a host-lane room `_plan_doc` over every slot and `batch_planes`, the 27 padded planes; else a look-up of the bucket's kept batch) per step (phases recorder; a host stage)."""


def read(w):
    st = w.phases.get("ingest.plan.host_rows")
    steps = len(w.dispatch_spans)
    return st["execute_s"] / steps * 1e3 if st and steps else None
