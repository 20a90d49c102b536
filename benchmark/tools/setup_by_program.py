#!/usr/bin/env python3
"""Where set-up goes, by part and by program.

    python3 benchmark/tools/setup_by_program.py <build_journal.json or its directory> [--top 10] [--all]

Reads the build journal a traced run leaves beside its xplane
(`.bench_trace/build_journal.json`, written by `layers/build_programs.py` from
the program's own `build_totals()` and `build_log()`) and prints

1. **set-up by part**: the native library's start, then what `jax.monitoring`
   timed of the programs built before the window (trace, lower, compile,
   cache load), the served dispatches less the builds that lie inside them,
   and the rest of `setup_s` (start-up, inputs, connects, the waits in
   `block_until_ready`, the builds' own Python). The rows add up to `setup_s`.
   The header says what the persistent cache saved (`saved_s`: jax's record
   of what each hit had cost to compile, less its read), which is what a
   cold cache would add to this `setup_s`;
2. the programs built before the window **by the span they were built under**;
3. **the dearest programs**, by their seconds in all and by each part: name
   (`fun_name` of the backend event), innermost span, seconds traced, lowered,
   in the backend (`compile` = backend less cache load), read from the cache,
   whether the cache hit, and the span's key (the program's shape
   signature). `--all` adds the programs built after the window's opening
   (the window's own, expected none, and the check's).
"""

from __future__ import annotations

import json
import os
import sys
from typing import Dict, List

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmark.setup_parts import DISPATCH, JOURNAL  # noqa: E402

NAME = os.path.basename(JOURNAL)
UNSPANNED = "(no span open)"


def seconds(row: dict) -> float:
    return row["trace_s"] + row["lower_s"] + row["backend_s"]


def compile_s(row: dict) -> float:
    return row["backend_s"] - row["cache_load_s"]


def by_part(journal: dict) -> List[tuple]:
    """(part, seconds) rows that add up to `setup_s`."""
    at, native = journal["at_opening"], journal.get("native") or {}
    before = [r for r in journal["programs"] if r["before_window"]]
    in_dispatch = sum(seconds(r) for r in before if DISPATCH in r["spans"])
    rows = [
        ("native library: g++ build", native.get("build_s", 0.0)),
        ("native library: dlopen", native.get("load_s", 0.0)),
        ("trace (Python to jaxpr)", at["trace_s"]),
        ("lower (jaxpr to MLIR)", at["lower_s"]),
        ("compile (backend less cache load)", at["backend_s"] - at["cache_load_s"]),
        ("cache load (read + deserialise)", at["cache_load_s"]),
        ("served dispatches less their builds", (journal.get("setup_dispatch_s") or 0.0) - in_dispatch),
    ]
    rows.append(("the rest (start-up, inputs, connects, waits)", journal["setup_s"] - sum(s for _, s in rows)))
    return rows


def by_span(programs: List[dict]) -> Dict[str, List[float]]:
    """innermost span -> [programs, trace, lower, compile, cache load]."""
    out: Dict[str, List[float]] = {}
    for r in programs:
        row = out.setdefault(r["stage"] or UNSPANNED, [0, 0.0, 0.0, 0.0, 0.0])
        row[0] += 1
        for i, v in enumerate((r["trace_s"], r["lower_s"], compile_s(r), r["cache_load_s"]), 1):
            row[i] += v
    return out


def table(programs: List[dict], key, top: int) -> List[str]:
    lines = [f"{'program':44} {'span':28} {'trace':>8} {'lower':>8} {'compile':>8} {'load':>8} cache signature"]
    for r in sorted(programs, key=key, reverse=True)[:top]:
        lines.append(f"{r['fun_name'][:44]:44} {(r['stage'] or UNSPANNED)[:28]:28} {r['trace_s']:8.3f} "
                     f"{r['lower_s']:8.3f} {compile_s(r):8.3f} {r['cache_load_s']:8.3f} {r['cache'] or '-':5}"
                     f"{(' ' + r['signature'][:72]) if r.get('signature') else ''}")
    return lines


def report(journal: dict, top: int = 10, everything: bool = False) -> str:
    at = journal["at_opening"]
    out = [f"setup_s {journal['setup_s']:.3f}; {at['builds']} programs built before the window, "
           f"{at['cache_hits']} of {at['cache_requests']} cache requests hit; "
           f"a cold cache would have cost +{at['saved_s']:.1f} s of compiling"]
    out.append("\nset-up by part")
    for name, secs in by_part(journal):
        out.append(f"  {name:46} {secs:9.3f} s {100 * secs / journal['setup_s']:6.1f}%")
    programs = [r for r in journal["programs"] if everything or r["before_window"]]
    out.append("\nby the span the programs were built under")
    out.append(f"  {'span':40} {'n':>4} {'trace':>8} {'lower':>8} {'compile':>8} {'load':>8}")
    for span, (n, *parts) in sorted(by_span(programs).items(), key=lambda kv: -sum(kv[1][1:])):
        out.append(f"  {span[:40]:40} {n:4d} " + " ".join(f"{v:8.3f}" for v in parts))
    for title, key in (("seconds in all", seconds), ("trace", lambda r: r["trace_s"]), ("lower", lambda r: r["lower_s"]),
                       ("compile", compile_s), ("cache load", lambda r: r["cache_load_s"])):
        out.append(f"\nthe {top} dearest programs by {title}")
        out.extend(table(programs, key, top))
    return "\n".join(out)


def main(argv) -> int:
    if not argv:
        print(__doc__)
        return 2
    path = argv[0]
    if os.path.isdir(path):
        path = os.path.join(path, NAME)
    if not os.path.exists(path):
        print(f"no {NAME} at {path}: a traced run of a program that keeps build totals writes one")
        return 1
    with open(path) as f:
        journal = json.load(f)
    top = int(argv[argv.index("--top") + 1]) if "--top" in argv else 10
    print(report(journal, top, "--all" in argv))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
