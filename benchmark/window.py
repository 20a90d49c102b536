"""What a metric reader is given: the closed window's raw samples, counter
and phase deltas, and (traced runs) the reduced profiler trace."""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List


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
