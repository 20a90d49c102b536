"""Share of the slice's device-idle time whose gaps (by their middle) lie inside a `ytpu.ingest.merge` span, from the profiler trace."""

from benchmark import program_trace


def read(w):
    ev = program_trace.events() if w.trace else None
    share = program_trace.idle_share_inside(ev, program_trace.MERGE) if ev else None
    return None if share is None else 100.0 * share
