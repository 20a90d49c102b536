"""Writers the server first heard of in the window, per `flush_device` step: `ingest.clients_first_seen` (one count a writer, in the step whose update brought its id) / steps. The window's counter delta where it carries the name, else the phase recorder's copy of the same count (stage value). A program without the counter has nothing to read."""


def read(w):
    seen = w.counters.get("ingest.clients_first_seen") or (w.phases.get("ingest.clients_first_seen") or {}).get("value")
    steps = len(w.dispatch_spans)
    return seen / steps if seen and steps else None
