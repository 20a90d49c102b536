"""What a metric reader is given: the closed window's raw samples, counter
and phase deltas, and (traced runs) the reduced profiler trace. And the two
rules on the window's length, as pure functions (no jax here): when the
traced slice opens, and when a window is too short to report."""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List

TRACE_SLICE_S = 4.0  # the traced slice: the window's last seconds
PROJECT_FROM_SHARE = 0.1  # of the pool handed over before its rate projects the window's end
MIN_POOL_WINDOW_S = 2.0 * TRACE_SLICE_S  # a pool that drains sooner leaves too short a window to read
EXIT_TOO_SHORT = 5  # run.py's exit code for a window the pool closed too early to read


def projected_end(elapsed: float, handed_share: float, seconds: float) -> float:
    """When the window will close: at `seconds`, or sooner where a pool that
    does not repeat drains first at the rate it has been taken at so far.
    `handed_share` is the share of the plan's ops already handed to the
    server, 0 for a plan that repeats or is open loop (the clock closes
    those). The first tenth of a pool projects nothing: too few ticks."""
    if handed_share < PROJECT_FROM_SHARE:
        return seconds
    return min(seconds, elapsed / handed_share)


def slice_opens(elapsed: float, handed_share: float, seconds: float, slice_s: float) -> bool:
    """Does the profiler start at this tick? At the first one inside the
    last `slice_s` seconds of the window as `projected_end` sees it."""
    return elapsed >= projected_end(elapsed, handed_share, seconds) - slice_s


def closed_by_pool(handed_share: float, window_s: float, seconds: float) -> bool:
    """Did the window close because every op of the pool was handed over,
    and not because `seconds` had passed?"""
    return handed_share >= 1.0 and window_s < seconds


def too_short_to_read(by_pool: bool, window_s: float) -> bool:
    """A window its pool closed in under two traced slices: a rate read from
    a handful of ticks, any of which can stall for seconds, is no measurement."""
    return by_pool and window_s < MIN_POOL_WINDOW_S


@dataclass
class Window:
    rec: object  # benchmark.serve.Records of the window's ops
    t_open: float
    t_close: float
    setup_s: float
    dispatch_spans: List[tuple]  # (t0, t1, updates carried) of the window's dispatches
    counters: Dict[str, float] = field(default_factory=dict)  # program counters, window delta
    phases: Dict[str, Dict[str, float]] = field(default_factory=dict)  # phases stages, window delta
    trace: dict = field(default_factory=dict)  # benchmark.trace_reduce.reduce(...) of the slice
    programs: Dict[str, List[str]] = field(default_factory=dict)  # layer -> HLO module names
    compiles: int = 0  # program builds (compiled or loaded) inside the window
    state_bytes: int = 0  # resident bytes of the server's state, from its shapes
    device_kind: str = ""

    @property
    def elapsed(self) -> float:
        return self.t_close - self.t_open

    def indices(self, kind: str) -> List[int]:
        r = self.rec
        return [i for i, k in enumerate(r.kind) if k == kind and not r.failed[i] and r.done[i] > 0.0]

    def trace_program_s(self, layer: str) -> float:
        """Device seconds, inside the traced slice, of the layer's programs."""
        per = self.trace.get("program_s") or {}
        names = self.programs.get(layer, [])
        found = [per[n] for n in names if n in per]
        return sum(found) if found else float("nan")

    def trace_span_count(self, name: str) -> int:
        return (self.trace.get("span_counts") or {}).get(name, 0)
