#!/usr/bin/env python3
"""Where the device idles, by the program's own spans.

    python3 benchmark/tools/idle_by_span.py <trace dir or .xplane.pb> [--ops 12]

Reads a traced run's profiler trace (`benchmark/program_trace.py`) and
prints, for the traced slice: every idle gap of the device put down to the
innermost `ytpu.*` span open at its middle (seconds, share of the idle time,
gaps, longest gap) with the gaps over 1 ms that no program span names; the
host time of a step by span (mean per `ytpu.sync.dispatch`, leaves and
containers); and the device time by the `jax.named_scope` its ops were
traced under. The tables of `PERF.md` section 5.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmark import program_trace as pt  # noqa: E402


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
    n_ops = int(argv[argv.index("--ops") + 1]) if "--ops" in argv else 12
    ev = pt.load(path)
    bounds = pt.slice_bounds(ev)
    if bounds is None:
        print("no bench.tick span: nothing to slice")
        return 1
    lo, hi = bounds
    gaps = pt.idle_gaps(ev)
    idle_s = sum(b - a for a, b in gaps) / 1e9
    print(f"{path}\nslice {(hi - lo) / 1e9:.3f} s, device idle {idle_s:.3f} s in {len(gaps)} gaps")

    print("\nidle gaps by the innermost ytpu.* span open at their middle")
    print(f"{'span':42} {'idle s':>9} {'share':>7} {'gaps':>6} {'longest ms':>11}")
    table = pt.idle_by_span(ev)
    for name, (secs, n, longest) in sorted(table.items(), key=lambda kv: -kv[1][0]):
        print(f"{name:42} {secs:9.4f} {100 * secs / idle_s if idle_s else 0:6.1f}% {n:6d} {longest * 1e3:11.3f}")
    spans = sorted((s, s + d, n) for n, s, d, _ in ev["host"] if n.startswith(pt.PREFIX))
    unnamed = [(a, b) for a, b in gaps if b - a > 1e6 and not any(s <= (a + b) / 2 <= e for s, e, _ in spans)]
    print(f"gaps over 1 ms that no ytpu.* span names: {len(unnamed)}"
          + "".join(f"\n  {(b - a) / 1e6:.3f} ms at +{(a - lo) / 1e6:.1f} ms" for a, b in unnamed[:10]))

    inside = pt.program_spans(ev, lo, hi)
    steps = sum(1 for sp in inside if sp[2] == pt.DISPATCH)
    if steps:
        leaves = {sp for sp in pt.leaf_spans(inside)}
        per: dict = {}
        for sp in inside:
            row = per.setdefault(sp[2], [0.0, 0, sp in leaves])
            row[0] += (sp[1] - sp[0]) / 1e6
            row[1] += 1
        share = pt.dispatch_self_share(ev)
        print(f"\nhost time by span, {steps} steps in the slice; of a ytpu.sync.dispatch no leaf names "
              f"{100 * share:.2f}%")
        print(f"{'span':42} {'ms/step':>9} {'calls/step':>11} {'':>5}")
        for name, (ms, n, leaf) in sorted(per.items(), key=lambda kv: -kv[1][0]):
            print(f"{name:42} {ms / steps:9.3f} {n / steps:11.2f} {'leaf' if leaf else '':>5}")

    own = pt.device_self_seconds(ev)
    names = sorted({part for path in own for part in path.split("/")[1:] if part != pt.UNNAMED})  # [0]: the program
    print(f"\ndevice time under each named scope (union of its ops; scopes nest, so rows overlap), {steps} steps")
    print(f"{'scope':42} {'s':>9} {'ms/step':>9}")
    for secs, name in sorted(((pt.scoped_device_seconds(ev, name) or 0.0, name) for name in names), reverse=True):
        print(f"{name:42} {secs:9.4f} {secs / steps * 1e3 if steps else 0:9.3f}")
    print(f"\ndevice time by the scope of the op running, each op's own time (rows add up to the busy "
          f"{sum(own.values()):.4f} s), top {n_ops}")
    for path, secs in sorted(own.items(), key=lambda kv: -kv[1])[:n_ops]:
        print(f"{secs:9.4f}  {path}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
