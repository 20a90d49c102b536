"""Share of the window's lookup-table reads (client, key, client-hash and rank table, one read a table a step) that were handed the device arrays of an earlier build: `ingest.table_reuses` / (`table_reuses` + `table_builds`). The window's counter deltas where they carry the two names, else the phase recorder's copy of the same counts (stage value). A program without the counters has nothing to read."""


def read(w):
    def delta(name):
        return w.counters.get(name) or (w.phases.get(name) or {}).get("value") or 0

    reuses, builds = delta("ingest.table_reuses"), delta("ingest.table_builds")
    return 100.0 * reuses / (reuses + builds) if reuses + builds else None
