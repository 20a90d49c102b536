"""Host arrays a step sends to the device(s), per `flush_device` step: `ingest.step_uploads` (one count a leaf `BatchIngestor._upload` sent for the step: the merge's manifest, the host lane's two packed arrays, `active` where no manifest carries it, a rebuilt lookup table's leaves, a compaction's operands) / steps. A transfer costs the host about what six output buffers do, whatever its bytes: 8 a step that merges while the wire bytes, the lanes' columns and `active` go up one by one, 1 as one manifest. The window's counter delta where it carries the name, else the phase recorder's copy of the same count (stage value). A program without the counter has nothing to read."""


def read(w):
    uploads = w.counters.get("ingest.step_uploads") or (w.phases.get("ingest.step_uploads") or {}).get("value")
    steps = len(w.dispatch_spans)
    return uploads / steps if uploads and steps else None
