"""Share of the window's payloads whose native columns came to the step's planning already decoded: `ingest.prescan_carried` / `ingest.prescan_payloads` x 100 (`BatchIngestor.apply_bytes`' prescan: an update is decoded once, where it arrives, inside `sync.receive.roots`, and its columns ride the server's queue to the step that integrates it; a payload without them is decoded by the prescan, as every payload was before). 100 where every update of the window came in through `receive_frames` and left through `flush_device`; 0 where none carried its columns and each was decoded twice. The window's counter deltas where they carry the names, else the phase recorder's copies of the same counts (stage values). A program without the counters has nothing to read."""


def read(w):
    def count(name):
        return w.counters.get(name) or (w.phases.get(name) or {}).get("value")

    payloads = count("ingest.prescan_payloads")
    return 100.0 * (count("ingest.prescan_carried") or 0.0) / payloads if payloads else None
