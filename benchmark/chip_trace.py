"""What a trace says chip by chip: for the cells whose state is laid over
several chips (`yws-rooms-4k-x4`).

`trace_reduce.reduce` averages the busy time over the chips, sums a
program's seconds over them and attributes gaps on the first; on one chip
those are the same thing. On four they are not, and what only exists across
chips has its reductions here, over the plain lists `trace_reduce.
load_xplane` returns (`{"device": {plane: {line: [[name, start_ns,
dur_ns], ...]}}, "host": [...]}`), so they can be checked on a hand-made
event list (`benchmark/tests/test_chip_trace.py`). The slice is
`trace_reduce`'s: first `bench.tick` start to last `bench.tick` end.

`planes()` parses the run's newest `*.xplane.pb` once a process. A trace
with one device plane, or none, still reduces: the skew of one chip is 0.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

from benchmark import program_trace, trace_reduce

#: HLO opcodes that move data between chips, as an op event's instruction
#: is named after them (`%all-reduce.12 = ...`; the asynchronous pairs are
#: `all-reduce-start.3` and `all-reduce-done.3`)
COLLECTIVE = re.compile(r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)(-start|-done)?(\.\d+)*$")

_PARSED: Dict[str, dict] = {}


def planes(trace_dir: str = program_trace.TRACE_DIR) -> Optional[dict]:
    path = program_trace.newest_xplane(trace_dir)
    if path is None:
        return None
    if path not in _PARSED:
        _PARSED[path] = trace_reduce.load_xplane(path)
    return _PARSED[path]


def slice_bounds(events: dict) -> Optional[Tuple[float, float]]:
    ticks = [(s, s + d) for n, s, d in events["host"] if n == trace_reduce.SLICE_SPAN]
    if not ticks:
        return None
    lo, hi = min(a for a, _ in ticks), max(b for _, b in ticks)
    return (lo, hi) if hi > lo else None


def _by_chip(events: dict, line: str, keep) -> Optional[List[float]]:
    """Seconds inside the slice, chip by chip in plane order, covered by the
    events of `line` that `keep(name)` holds to (a union: nested or
    overlapping events count once)."""
    bounds = slice_bounds(events)
    if bounds is None or not events["device"]:
        return None
    return [
        trace_reduce.union_seconds(
            [(s, s + d) for n, s, d in lines.get(line, []) if keep(n)], *bounds
        )[0] / 1e9
        for _, lines in sorted(events["device"].items())
    ]


def busy_by_chip(events: dict) -> Optional[List[float]]:
    return _by_chip(events, trace_reduce.OPS_LINE, lambda n: True)


def program_seconds_by_chip(events: dict, programs: List[str]) -> Optional[List[float]]:
    """Device seconds of the HLO modules named in `programs`."""
    names = set(programs)
    return _by_chip(events, trace_reduce.MODULES_LINE, lambda n: trace_reduce.program_name(n) in names)


def collective_seconds_by_chip(events: dict) -> Optional[List[float]]:
    """Device seconds in which a collective op ran."""
    return _by_chip(
        events, trace_reduce.OPS_LINE,
        lambda n: bool(COLLECTIVE.match(program_trace.instruction_name(n))),
    )


def fullest(per_chip: Optional[List[float]]) -> Optional[int]:
    """The chip on which the reading is largest; None with nothing read."""
    if not per_chip or max(per_chip) <= 0:
        return None
    return max(range(len(per_chip)), key=lambda i: per_chip[i])


def skew(per_chip: Optional[List[float]]) -> Optional[float]:
    """(largest - smallest) / largest."""
    if not per_chip or max(per_chip) <= 0:
        return None
    return (max(per_chip) - min(per_chip)) / max(per_chip)
