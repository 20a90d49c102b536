"""From a profiler trace to device metrics (yardstick code; no PR but a
`benchmark` PR may change it).

Two steps, so the second can be checked against a small recorded trace
(`benchmark/tests/data/`):

1. `load_xplane(path)` reads jax's `.xplane.pb` with `jax.profiler.
   ProfileData` and keeps, as plain lists, the device planes' lines and the
   host's `bench.*` annotations: `{"device": {plane: {line: [[name, start_ns,
   dur_ns], ...]}}, "host": [[name, start_ns, dur_ns], ...]}`;
2. `reduce(events)` works out, inside the traced slice (first `bench.tick`
   start to last `bench.tick` end): busy seconds as the union of the
   intervals in which an op ran on the device (averaged over the chips
   used), seconds per program (HLO module) and per op, and the idle gaps
   attributed to the innermost `bench.*` span open at the time.
"""

from __future__ import annotations

import bisect
import re
from typing import Dict, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SLICE_SPAN = "bench.tick"
_SUFFIX = re.compile(r"\(\d+\)$")


def load_xplane(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = {"device": {}, "host": []}
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = out["device"].setdefault(plane.name, {})
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    lines[line.name] = [
                        [e.name, float(e.start_ns), float(e.duration_ns)] for e in line.events
                    ]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        out["host"].append([e.name, float(e.start_ns), float(e.duration_ns)])
    return out


def plane_summary(path: str) -> List[str]:
    """Plane and line names with event counts: for looking at a trace by hand."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            n = 0
            first = None
            for e in line.events:
                n += 1
                first = first or e.name
            out.append(f"{plane.name} | {line.name} | {n} events | first {first}")
    return out


def union_seconds(intervals: List[Tuple[float, float]], lo: float, hi: float):
    """(covered ns, gaps) of the intervals clipped to [lo, hi]; gaps are the
    uncovered (start, end) pieces."""
    clipped = sorted((max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi)
    covered, gaps, cur = 0.0, [], lo
    for a, b in clipped:
        if a > cur:
            gaps.append((cur, a))
        if b > cur:
            covered += b - max(a, cur)
            cur = b
    if cur < hi:
        gaps.append((cur, hi))
    return covered, gaps


def program_name(event_name: str) -> str:
    """`jit__apply_update_batch_jit(1234)` -> `jit__apply_update_batch_jit`."""
    return _SUFFIX.sub("", event_name.strip())


def reduce(events: dict) -> dict:
    host = events["host"]
    ticks = [(s, s + d) for n, s, d in host if n == SLICE_SPAN]
    if not ticks:
        return {}
    lo, hi = min(a for a, _ in ticks), max(b for _, b in ticks)
    if hi <= lo or not events["device"]:
        return {}
    window_ns = hi - lo
    busy_ns_per_chip = []
    all_gaps: List[Tuple[float, float]] = []
    programs: Dict[str, float] = {}
    program_calls: Dict[str, int] = {}
    ops: Dict[str, float] = {}
    for plane, lines in sorted(events["device"].items()):
        op_events = lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []
        covered, gaps = union_seconds([(s, s + d) for _, s, d in op_events], lo, hi)
        busy_ns_per_chip.append(covered)
        if not all_gaps:
            all_gaps = gaps  # gaps are attributed on the first chip
        for n, s, d in lines.get(MODULES_LINE, []):
            if s + d > lo and s < hi:
                name = program_name(n)
                programs[name] = programs.get(name, 0.0) + (min(s + d, hi) - max(s, lo))
                program_calls[name] = program_calls.get(name, 0) + 1
        for n, s, d in op_events:
            if s + d > lo and s < hi:
                ops[n] = ops.get(n, 0.0) + (min(s + d, hi) - max(s, lo))
    # idle gaps -> the innermost bench.* span open at the gap's middle
    spans = sorted((s, s + d, n) for n, s, d in host if s + d > lo and s < hi)
    starts = [s for s, _, _ in spans]
    idle: Dict[str, float] = {}
    for a, b in all_gaps:
        mid = (a + b) / 2
        name = "outside bench spans"
        for i in range(bisect.bisect_right(starts, mid) - 1, -1, -1):  # latest start first
            if spans[i][1] >= mid:
                name = spans[i][2]
                break
        idle[name] = idle.get(name, 0.0) + (b - a)
    n_spans: Dict[str, int] = {}
    for s, e, n in spans:
        if s >= lo and e <= hi:
            n_spans[n] = n_spans.get(n, 0) + 1
    # an op's name can be its whole HLO text: keep what identifies it
    top = lambda d: [[k[:96], v / 1e9] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {
        "window_s": window_ns / 1e9,
        "busy_s": sum(busy_ns_per_chip) / len(busy_ns_per_chip) / 1e9,
        "chips": len(busy_ns_per_chip),
        "program_s": {k: v / 1e9 for k, v in programs.items()},
        "program_calls": program_calls,
        "device_ops": top(ops),
        "idle_gaps": top(idle),
        "span_counts": n_spans,
    }
