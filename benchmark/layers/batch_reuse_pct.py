"""Share of the window's steps that were handed the host lane's empty batch as an earlier step of the same `(n_rows, n_dels)` bucket left it on the device(s), and so built and uploaded no host planes: `ingest.batch_reuses` / (`batch_reuses` + `batch_builds`), one count a call of `apply_bytes`. The window's counter deltas where they carry the two names, else the phase recorder's copy of the same counts (stage value). A program without the counters has nothing to read."""


def read(w):
    def delta(name):
        return w.counters.get(name) or (w.phases.get(name) or {}).get("value") or 0

    reuses, builds = delta("ingest.batch_reuses"), delta("ingest.batch_builds")
    return 100.0 * reuses / (reuses + builds) if reuses + builds else None
