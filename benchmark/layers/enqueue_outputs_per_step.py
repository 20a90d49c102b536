"""Output buffers a step's device programs hand back, per `flush_device` step: `ingest.enqueue_outputs` (one count a leaf of what each program `apply_bytes` enqueued returned: the lanes' gather, the decoder, the merge, the integrate call, a recovery's) / steps. What an enqueue costs the host goes with them: 85 a step that merges while the update batch crosses its three program boundaries as 27 planes, 35 as a pair. The window's counter delta where it carries the name, else the phase recorder's copy of the same count (stage value). A program without the counter has nothing to read."""


def read(w):
    outputs = w.counters.get("ingest.enqueue_outputs") or (w.phases.get("ingest.enqueue_outputs") or {}).get("value")
    steps = len(w.dispatch_spans)
    return outputs / steps if outputs and steps else None
