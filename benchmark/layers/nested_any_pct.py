"""Share of the window's host-lane payloads that the prescan sent there for a nested Any value (an object or array inside an object): `ingest.slow.complex_any` (one count a payload, by the first reason `_fast_eligible` found) / `ingest.slow_docs`; 100 where every host-lane update carries a JSON record. The window's counter deltas where they carry the names, else the phase recorder's copy of the reason's count (stage value). A program without the reason counters has nothing to read."""


def read(w):
    nested = w.counters.get("ingest.slow.complex_any") or (w.phases.get("ingest.slow.complex_any") or {}).get("value")
    slow = w.counters.get("ingest.slow_docs")
    return 100.0 * nested / slow if nested is not None and slow else None
