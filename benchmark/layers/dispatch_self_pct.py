"""Share of the traced slice's `ytpu.sync.dispatch` time that no leaf `ytpu.*` span nested in it covers: host time in a step that no span names (profiler trace, the program's own annotations)."""

from benchmark import program_trace


def read(w):
    ev = program_trace.events() if w.trace else None
    share = program_trace.dispatch_self_share(ev) if ev else None
    return None if share is None else 100.0 * share
