"""Updates integrated per `flush_device` step (counts)."""


def read(w):
    steps = len(w.dispatch_spans)
    return sum(c for _, _, c in w.dispatch_spans) / steps if steps else None
