"""The compaction program's share of its HBM roofline: (the K rooms' planes read once + written once) / 819 GB/s, over its device time per call in the slice. Bandwidth-bound (`benchmark/compact_bytes.py`). Nothing to read where the slice holds no compaction."""

from benchmark.compact_bytes import compact_min_seconds


def read(w):
    per = w.trace.get("program_calls") or {}
    secs, n = w.trace_program_s("compact"), sum(per.get(p, 0) for p in w.programs.get("compact", []))
    if not n or secs != secs or secs <= 0:
        return None
    return 100.0 * compact_min_seconds(w.state_bytes, w.device_kind) / (secs / n)
