"""Devices the server's state planes span: the program's gauge `ingest.state_shards`, worked out from the arrays' shardings when it is read. A program without the gauge has nothing to read."""


def read(w):
    from ytpu.utils import metrics

    value = metrics.snapshot().get("ingest.state_shards")
    return float(value) if value else None
