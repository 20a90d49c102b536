"""Share of the window's first-seen writers whose id is past int32 and resolves through the device's client-hash table: `ingest.clients_first_seen_big` / `ingest.clients_first_seen`. The window's counter deltas where they carry the names, else the phase recorder's copy of the same counts (stage value). A program without the counters, or a window in which no writer walked in, has nothing to read."""


def read(w):
    def delta(name):
        return w.counters.get(name) or (w.phases.get(name) or {}).get("value") or 0

    seen = delta("ingest.clients_first_seen")
    return 100.0 * delta("ingest.clients_first_seen_big") / seen if seen else None
