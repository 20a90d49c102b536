"""Device time of the compaction program per call, from the profiler trace of the slice (`layers/programs/compact.txt`). Nothing to read where the slice holds no compaction."""


def calls(w) -> int:
    per = w.trace.get("program_calls") or {}
    return sum(per.get(n, 0) for n in w.programs.get("compact", []))


def read(w):
    secs, n = w.trace_program_s("compact"), calls(w)
    return secs / n * 1e3 if n and secs == secs and secs > 0 else None
