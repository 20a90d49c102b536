"""Mean host time of one `flush_device` step to `block_until_ready` (benchmark span)."""


def read(w):
    spans = w.dispatch_spans
    return sum(t1 - t0 for t0, t1, _ in spans) / len(spans) * 1e3 if spans else None
