#!/usr/bin/env python3
"""A traced run's slice chip by chip, for a cell whose state spans several.

    python3 benchmark/tools/by_chip.py <trace dir or .xplane.pb>

`tools/idle_by_span.py` reads the first chip, as the accepted readers do.
This prints, for every device plane of the trace: busy seconds and the idle
share, the seconds of each program (HLO module), the seconds in collective
ops by opcode, the device time under each `jax.named_scope`, and the idle
time by the innermost `ytpu.*` span; then the skew between the chips. The
tables of `PERF.md` section 5 for `yws-rooms-4k-x4.edit-flood`.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmark import chip_trace as ct  # noqa: E402
from benchmark import program_trace as pt  # noqa: E402
from benchmark import trace_reduce  # noqa: E402

SCOPES = ("integrate_rows", "delete_pass", "split", "conflict_scan", "move_recompute", "decode_v1", "merge_stream",
          "compact_gather", "compact_scatter")


def main(argv) -> int:
    if not argv:
        print(__doc__)
        return 2
    path = argv[0]
    if os.path.isdir(path):
        path = pt.newest_xplane(path)
        if path is None:
            print(f"no .xplane.pb under {argv[0]}")
            return 1
    lines, scoped = trace_reduce.load_xplane(path), pt.load(path)
    bounds = ct.slice_bounds(lines)
    if bounds is None:
        print("no bench.tick span: nothing to slice")
        return 1
    lo, hi = bounds
    steps = sum(1 for n, s, d in lines["host"] if n == "bench.dispatch" and s >= lo and s + d <= hi)
    busy, coll = ct.busy_by_chip(lines), ct.collective_seconds_by_chip(lines)
    print(f"{path}\nslice {(hi - lo) / 1e9:.3f} s, {steps} steps, {len(busy)} chips")
    for i, (plane, by_line) in enumerate(sorted(lines["device"].items())):
        print(f"\n{plane}: busy {busy[i]:.4f} s ({100 * (1 - busy[i] / ((hi - lo) / 1e9)):.1f}% idle), "
              f"in collectives {coll[i]:.4f} s ({1e3 * coll[i] / max(steps, 1):.3f} ms a step)")
        programs: dict = {}
        for n, s, d in by_line.get(trace_reduce.MODULES_LINE, []):
            if s + d > lo and s < hi:
                row = programs.setdefault(trace_reduce.program_name(n), [0.0, 0])
                row[0] += (min(s + d, hi) - max(s, lo)) / 1e9
                row[1] += 1
        for name, (secs, calls) in sorted(programs.items(), key=lambda kv: -kv[1][0])[:8]:
            print(f"  program {name:40} {secs:9.4f} s {calls:5d} calls {1e3 * secs / max(steps, 1):9.3f} ms a step")
        by_op: dict = {}
        for n, s, d in by_line.get(trace_reduce.OPS_LINE, []):
            m = ct.COLLECTIVE.match(pt.instruction_name(n))
            if m and s + d > lo and s < hi:
                row = by_op.setdefault(m.group(1) + (m.group(2) or ""), [0.0, 0])
                row[0] += d / 1e9
                row[1] += 1
        for op, (secs, n) in sorted(by_op.items()):
            print(f"  collective {op:24} {secs:9.4f} s in {n:6d} ops, {1e6 * secs / n:8.1f} us each, {n / max(steps, 1):6.1f} a step")
        view = {"host": scoped["host"], "device": {plane: scoped["device"].get(plane, [])}}
        for scope in SCOPES:
            secs = pt.scoped_device_seconds(view, scope)
            if secs is not None:
                print(f"  scope {scope:18} {secs:9.4f} s {1e3 * secs / max(steps, 1):9.3f} ms a step")
        idle = pt.idle_by_span(view)
        total = sum(row[0] for row in idle.values())
        for name, (secs, n, longest) in sorted(idle.items(), key=lambda kv: -kv[1][0])[:6]:
            print(f"  idle in {name:40} {secs:9.4f} s {100 * secs / total if total else 0:5.1f}% {n:6d} gaps, longest {1e3 * longest:8.3f} ms")
    skew = ct.skew(busy)
    print(f"\nskew (busiest - idlest) / busiest: {100 * skew:.2f}%" if skew is not None else "\nno device op in the slice")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
