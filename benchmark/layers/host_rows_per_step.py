"""Rows the host lane planned, per `flush_device` step: `ingest.host_rows` (the rows `_plan_doc` handed `batch_planes` in the step, not its padding) / steps; about one a carried update that takes the host lane. The window's counter delta where it carries the name, else the phase recorder's copy of the same count (stage value). A program without the counter has nothing to read."""


def read(w):
    rows = w.counters.get("ingest.host_rows") or (w.phases.get("ingest.host_rows") or {}).get("value")
    steps = len(w.dispatch_spans)
    return rows / steps if rows and steps else None
