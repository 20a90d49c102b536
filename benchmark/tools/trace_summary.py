#!/usr/bin/env python3
"""Look at a profiler trace by hand: planes, lines, event counts, and what
`benchmark/trace_reduce.py` makes of it.

    python3 benchmark/tools/trace_summary.py <trace dir or .xplane.pb> [--json out.json]

`--json` writes the reducer's input (device ops/modules lines and `bench.*`
host spans) as JSON: the form the recorded trace in `benchmark/tests/data/`
is kept in.
"""

from __future__ import annotations

import glob
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmark import trace_reduce  # noqa: E402


def main(argv) -> int:
    if not argv:
        print(__doc__)
        return 2
    path = argv[0]
    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True))
        if not found:
            print(f"no .xplane.pb under {path}")
            return 1
        path = found[-1]
    print(path, os.path.getsize(path), "bytes")
    for line in trace_reduce.plane_summary(path):
        print(line)
    events = trace_reduce.load_xplane(path)
    print(json.dumps(trace_reduce.reduce(events), indent=1)[:3000])
    if "--json" in argv:
        # the first `--ticks` ticks only (default 2): small enough to keep
        n_ticks = int(argv[argv.index("--ticks") + 1]) if "--ticks" in argv else 2
        ticks = sorted((s, s + d) for n, s, d in events["host"] if n == trace_reduce.SLICE_SPAN)[:n_ticks]
        lo, hi = ticks[0][0], ticks[-1][1]
        inside = lambda ev: [e for e in ev if e[1] >= lo and e[1] + e[2] <= hi]
        cut = {"host": inside(events["host"]),
               "device": {p: {ln: inside(ev) for ln, ev in lines.items()} for p, lines in events["device"].items()}}
        out = argv[argv.index("--json") + 1]
        import gzip
        with gzip.open(out, "wt") as f:
            json.dump(cut, f)
        with open(out.replace(".events.json.gz", ".expected.json"), "w") as f:
            r = trace_reduce.reduce(cut)
            json.dump({k: r[k] for k in ("busy_s", "window_s", "program_s")}, f, indent=1)
        print("fixture:", out, os.path.getsize(out), "bytes;", sum(len(ev) for l in cut["device"].values() for ev in l.values()), "device events")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
