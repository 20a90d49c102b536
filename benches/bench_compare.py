#!/usr/bin/env python
"""Diff two bench one-line JSON captures field by field (ISSUE-11).

Every bench round emits one JSON line (`bench.py`, committed as
`BENCH_r*.json`), but comparing rounds has been eyeball work — and the
ROADMAP's "no worse than" criteria have no mechanical check. This tool
is that check::

    python benches/bench_compare.py BENCH_r04.json BENCH_r05.json
    python benches/bench_compare.py old.json new.json --tol value=0.25
    python benches/bench_compare.py a.json b.json --default-tol 0.15
    python benches/bench_compare.py --trend candidate.json

``--trend`` (ISSUE-17) drops the explicit baseline: the candidate is
diffed against a synthetic **best-ever** capture folded from every
committed ``BENCH_r*.json`` with the candidate's platform tag (max over
history for higher-is-better keys, min for lower-is-better) — a round
that merely beats LAST round but falls short of the repo's best is
still called out.

Semantics:

- both files hold one JSON object (a bench one-line capture; a file
  with multiple lines uses its LAST non-empty line, matching how bench
  output is teed into logs);
- nested dicts flatten to dotted keys (``soak.rounds``,
  ``phases.host.replay.execute_s``); only numeric leaves compare —
  strings/bools are checked for equality and reported (never a
  regression: units and notes legitimately drift);
- a numeric change beyond tolerance is a **regression** only when the
  key's direction is known: higher-is-better keys (throughput, speedups,
  ``vs_*`` ratios) regress when B < A, lower-is-better keys (latency
  ``*_ms`` / ``*_s`` quantiles) regress when B > A. Unknown-direction
  numeric drift is reported as NEUTRAL and never fails the run —
  exactly like a human reviewer treats `chunks` changing.
- exit code: 0 = no regression, 1 = ≥1 regression, 2 = usage/load error.

`--json` emits the full diff as one JSON line (for tooling); default
output is a human-readable table of changed fields.

The tool itself is gated: `tests/test_bench_compare.py` pins direction/
tolerance semantics on synthetic captures, and a slow-marked test runs a
real `bench.py --dry-run` and asserts self-comparison is a zero diff
with exit 0.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, List, Optional, Tuple

__all__ = [
    "flatten",
    "classify",
    "compare",
    "load_capture",
    "capture_surface",
    "capture_platform",
    "repo_captures",
    "trend_baseline",
    "main",
]

#: default relative tolerance for numeric fields (|b-a| / max(|a|,eps))
DEFAULT_REL_TOL = 0.10

#: key-substring → direction. First match wins (checked in order), so
#: more specific fragments come first. "up" = higher is better, "down" =
#: lower is better. Everything else is neutral: reported, never failing.
_DIRECTION_RULES: Tuple[Tuple[str, str], ...] = (
    # unified wall-time attribution (ISSUE-17): the profile_* fractions
    # are a COMPOSITION of the wall budget, not better/worse — device
    # fraction legitimately falls when staging gets faster. Pinned
    # neutral FIRST so `profile_stall_fraction` never hits the
    # directional stall_fraction rule below.
    ("profile_", "neutral"),
    ("fractions_sum", "neutral"),
    ("stall_fraction", "down"),
    # compile/retrace sentinel (ISSUE-17): on the same warmed workload,
    # more retraces or more cumulative trace seconds is a regression —
    # a shape/static-plan leak re-entered the jit boundary. (Leaf
    # "retraces" also catches the compile_retraces headline.)
    ("retraces", "down"),
    # scan_iterations_total is workload shape (its leaf would otherwise
    # substring-match "s_total"); cumulative TRACE seconds regress on rise
    ("scan_iterations_total", "neutral"),
    ("s_total", "down"),
    ("_per_s", "up"),
    ("_per_sec", "up"),
    ("updates_per_s", "up"),
    ("speedup", "up"),
    ("overlap_ratio", "up"),
    ("vs_baseline", "up"),
    ("vs_native", "up"),
    ("vs_py_oracle", "up"),
    ("scan_trip_reduction", "up"),  # two-tier dispatch compression factor
    ("scan_width", "down"),  # conflict-scan tail: narrower is better
    # two-tier scan dispatch-trip counts (ISSUE-12): like latency, a
    # rise on the same workload is a regression — more serial while
    # trips per integrate. (Tier OCCUPANCY `scan_tier_*` stays neutral:
    # the cheap/wide split is workload shape, not better/worse.)
    ("scan_trips", "down"),
    # federation (ISSUE-13): rounds-to-byte-agreement and anti-entropy
    # traffic are costs — a rise on the same scenario is a regression
    # (more rounds / more bytes to reach the same converged state).
    # Occupancy-style counts (partitions, heals, mismatches) stay
    # neutral: they are the scripted chaos schedule, not better/worse.
    ("converge_rounds", "down"),
    ("anti_entropy_bytes", "down"),
    # autopilot on-vs-off deltas (ISSUE-16): availability_delta = on −
    # off (shrinking toward 0 means the controller stopped winning →
    # regresses on DROP); p99_adj_delta = on − off ms (negative is the
    # win; a RISE toward 0 is a regression). Raw action counts stay
    # neutral: more actions is a policy choice, not better/worse.
    ("availability_delta", "up"),
    ("p99_adj_delta", "down"),
    # capacity observatory (ISSUE-18): device-memory use regresses on
    # RISE (memory_peak_bytes, memory.program_bytes leaves), while the
    # forecaster's headroom and the doc-axis ceiling regress on DROP —
    # a smaller survivable doc axis or thinner headroom is the ceiling
    # closing in. The configured budget is an input, not a measurement,
    # so it pins neutral BEFORE the broad memory_ rule; occupancy /
    # fragmentation gauges (dead_rows, live_rows, dead_fraction,
    # reclaimed_rows, compact_gap_chunks) stay neutral by default —
    # they are workload shape, like the scan-tier occupancy split.
    ("headroom_fraction", "up"),
    ("doc_ceiling", "up"),
    # doc-axis sub-batching (ISSUE-20): a narrowed sub-batch width is
    # the memory budget closing in mid-replay — `subbatch_narrowed`
    # regresses on RISE. The width itself and the scaling ratio are
    # configuration/workload shape, not better/worse (the single-device
    # CPU ratio is an overhead floor, the mesh path the speedup axis):
    # both pin neutral, with the narrowed rule FIRST so its leaf never
    # falls through to the neutral `subbatch_` catch-all.
    ("subbatch_narrowed", "down"),
    ("sub_batch_scaling", "neutral"),
    ("subbatch_", "neutral"),
    ("memory_budget", "neutral"),
    ("memory_", "down"),
    ("peak_bytes", "down"),
    ("p50_ms", "down"),
    ("p99_ms", "down"),
    ("p999_ms", "down"),
    ("max_ms", "down"),
    ("rtt_floor_ms", "down"),
    ("_dt", "down"),
    ("p99_chunk_ms", "down"),
    ("p50_apply_ms", "down"),
    ("p99_apply_ms", "down"),
)

#: keys whose drift is pure noise at small scales — compared with a wider
#: default tolerance unless the caller overrides per key
_NOISY_DEFAULTS = {
    "rtt_floor_ms": 1.0,  # scheduler noise floor on loopback
    "wall_s": 1.0,
}


#: exact flattened keys with a known direction (the bench headline is
#: literally called "value"; a substring rule would misfire on the
#: phases gauges that also flatten to `.value` leaves)
_FULL_KEY_DIRECTION = {"value": "up", "parsed.value": "up"}


def classify(key: str) -> str:
    """'up' | 'down' | 'neutral' for a flattened key."""
    d = _FULL_KEY_DIRECTION.get(key)
    if d is not None:
        return d
    leaf = key.rsplit(".", 1)[-1]
    for frag, direction in _DIRECTION_RULES:
        if frag in leaf:
            return direction
    return "neutral"


def flatten(obj, prefix: str = "") -> Dict[str, object]:
    """Nested dicts → dotted scalar leaves. Lists compare as JSON text
    (order is meaningful in bench captures)."""
    out: Dict[str, object] = {}
    if isinstance(obj, dict):
        for k, v in obj.items():
            out.update(flatten(v, f"{prefix}{k}."))
    elif isinstance(obj, list):
        out[prefix[:-1]] = json.dumps(obj)
    else:
        out[prefix[:-1]] = obj
    return out


def compare(
    a: Dict,
    b: Dict,
    tolerances: Optional[Dict[str, float]] = None,
    default_rel: float = DEFAULT_REL_TOL,
) -> Dict:
    """Field-by-field diff of two captures. Returns
    ``{"regressions": [...], "improvements": [...], "changes": [...],
    "added": [...], "removed": [...]}`` where each entry is a dict with
    key / a / b / rel_change / direction."""
    tolerances = dict(tolerances or {})
    fa, fb = flatten(a), flatten(b)
    regressions: List[Dict] = []
    improvements: List[Dict] = []
    changes: List[Dict] = []
    added = sorted(set(fb) - set(fa))
    removed = sorted(set(fa) - set(fb))
    for key in sorted(set(fa) & set(fb)):
        va, vb = fa[key], fb[key]
        if isinstance(va, bool) or isinstance(vb, bool) or not (
            isinstance(va, (int, float)) and isinstance(vb, (int, float))
        ):
            if va != vb:
                changes.append(
                    {"key": key, "a": va, "b": vb, "direction": "neutral"}
                )
            continue
        if va == vb:
            continue
        leaf = key.rsplit(".", 1)[-1]
        tol = tolerances.get(
            key, tolerances.get(leaf, _NOISY_DEFAULTS.get(leaf, default_rel))
        )
        rel = (vb - va) / max(abs(va), 1e-12)
        entry = {
            "key": key,
            "a": va,
            "b": vb,
            "rel_change": round(rel, 4),
            "direction": classify(key),
            "tol": tol,
        }
        if abs(rel) <= tol:
            continue  # within tolerance: not even a change worth listing
        if entry["direction"] == "up":
            (regressions if rel < 0 else improvements).append(entry)
        elif entry["direction"] == "down":
            (regressions if rel > 0 else improvements).append(entry)
        else:
            changes.append(entry)
    return {
        "regressions": regressions,
        "improvements": improvements,
        "changes": changes,
        "added": added,
        "removed": removed,
    }


def capture_surface(d: Dict) -> Dict:
    """The measurement surface of a committed artifact: end-of-round
    ``BENCH_r*.json`` wrap the bench one-line JSON under ``parsed``;
    midsession captures ARE the surface. The bulky phases/metrics blobs
    are stripped — trend verdicts regress headlines, not trace dumps."""
    cap = d.get("parsed") if isinstance(d.get("parsed"), dict) else d
    return {k: v for k, v in cap.items() if k not in ("phases", "metrics")}


def capture_platform(d: Dict) -> str:
    """First word of the capture's platform tag (``"cpu (1 vCPU)"`` →
    ``"cpu"``), defaulting to ``host`` — the series key the trajectory
    ledger uses, so trend baselines never mix hardware with host runs."""
    return str(capture_surface(d).get("platform") or "host").split()[0]


def repo_captures(directory: Optional[str] = None) -> List[Tuple[Tuple, Dict]]:
    """Every loadable committed ``BENCH_r*.json`` as (rank, raw dict),
    oldest round first. Rank mirrors `bench._capture_rank`: round number
    from the filename, then the in-capture timestamp (mtime is useless —
    a git checkout stamps every artifact at once)."""
    import glob
    import os
    import re

    if directory is None:
        directory = os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))
        )
    out = []
    for path in glob.glob(os.path.join(directory, "BENCH_r*.json")):
        try:
            with open(path) as f:
                d = json.load(f)
        except (OSError, ValueError):
            continue
        m = re.search(r"BENCH_r(\d+)", os.path.basename(path))
        rank = (
            int(m.group(1)) if m else -1,
            str(d.get("captured_at") or ""),
        )
        out.append((rank, d))
    return sorted(out, key=lambda t: t[0])


def trend_baseline(captures: List[Dict]) -> Dict[str, object]:
    """Synthetic FLATTENED baseline for ``--trend`` (ISSUE-17): for every
    directional numeric leaf across the captures, the BEST value ever
    recorded (max for "up" keys, min for "down"); neutral and
    non-numeric keys keep the newest capture's value. Comparing a
    candidate against this regresses it against the repo's best-ever
    trajectory point, not just whatever round happened to land last."""
    base: Dict[str, object] = {}
    for cap in captures:  # oldest → newest, so "newest wins" is last-write
        for k, v in flatten(cap).items():
            numeric = isinstance(v, (int, float)) and not isinstance(v, bool)
            prior = base.get(k)
            prior_numeric = isinstance(prior, (int, float)) and not isinstance(
                prior, bool
            )
            if not (numeric and prior_numeric):
                base[k] = v
                continue
            d = classify(k)
            if d == "up":
                base[k] = max(prior, v)
            elif d == "down":
                base[k] = min(prior, v)
            else:
                base[k] = v
    return base


def load_capture(path: str) -> Dict:
    """One JSON object from `path` — a `BENCH_*.json` capture or any log
    whose LAST non-empty line is the bench one-line JSON."""
    with open(path) as f:
        text = f.read().strip()
    try:
        return json.loads(text)
    except json.JSONDecodeError:
        lines = [ln for ln in text.splitlines() if ln.strip()]
        if not lines:
            raise
        return json.loads(lines[-1])


def _render(diff: Dict, a_name: str, b_name: str) -> str:
    rows = []
    for kind, entries in (
        ("REGRESSION", diff["regressions"]),
        ("improvement", diff["improvements"]),
        ("change", diff["changes"]),
    ):
        for e in entries:
            rel = e.get("rel_change")
            rel_s = f"{rel * 100:+.1f}%" if isinstance(rel, float) else ""
            rows.append(
                f"{kind:<12} {e['key']:<48} {e['a']!r:>16} -> "
                f"{e['b']!r:<16} {rel_s}"
            )
    for k in diff["added"]:
        rows.append(f"{'added':<12} {k}")
    for k in diff["removed"]:
        rows.append(f"{'removed':<12} {k}")
    head = (
        f"bench_compare: A={a_name} B={b_name} — "
        f"{len(diff['regressions'])} regression(s), "
        f"{len(diff['improvements'])} improvement(s), "
        f"{len(diff['changes'])} neutral change(s)"
    )
    return "\n".join([head] + rows)


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument(
        "a",
        help="baseline capture (JSON file); with --trend, the CANDIDATE",
    )
    p.add_argument(
        "b",
        nargs="?",
        default=None,
        help="candidate capture (JSON file); omitted with --trend",
    )
    p.add_argument(
        "--trend",
        action="store_true",
        help="regress the candidate against the best-ever committed "
        "BENCH_r*.json values for its platform tag instead of one "
        "explicit baseline",
    )
    p.add_argument(
        "--captures-dir",
        default=None,
        metavar="DIR",
        help="where --trend looks for BENCH_r*.json (default: repo root)",
    )
    p.add_argument(
        "--tol",
        action="append",
        default=[],
        metavar="KEY=FRAC",
        help="per-key relative tolerance (key may be a flattened key or "
        "a leaf name); repeatable",
    )
    p.add_argument(
        "--default-tol",
        type=float,
        default=DEFAULT_REL_TOL,
        help=f"relative tolerance for keys without a --tol "
        f"(default {DEFAULT_REL_TOL})",
    )
    p.add_argument(
        "--json", action="store_true", help="emit the diff as one JSON line"
    )
    args = p.parse_args(argv)
    tolerances: Dict[str, float] = {}
    for spec in args.tol:
        if "=" not in spec:
            print(f"bad --tol {spec!r} (want KEY=FRAC)", file=sys.stderr)
            return 2
        k, v = spec.split("=", 1)
        try:
            tolerances[k] = float(v)
        except ValueError:
            print(f"bad --tol fraction {v!r}", file=sys.stderr)
            return 2
    if args.trend:
        cand_path = args.b or args.a
        try:
            cand_raw = load_capture(cand_path)
        except (OSError, json.JSONDecodeError) as e:
            print(f"load error: {e}", file=sys.stderr)
            return 2
        cand = capture_surface(cand_raw)
        platform = capture_platform(cand_raw)
        history = [
            capture_surface(d)
            for _, d in repo_captures(args.captures_dir)
            if capture_platform(d) == platform
        ]
        history = [h for h in history if h]
        if not history:
            print(
                f"--trend: no committed BENCH_r*.json with platform "
                f"{platform!r} to fold a baseline from",
                file=sys.stderr,
            )
            return 2
        a, b = trend_baseline(history), cand
        a_name = f"<best-ever:{platform}:{len(history)} captures>"
    elif args.b is None:
        print("candidate capture missing (or use --trend)", file=sys.stderr)
        return 2
    else:
        try:
            a = load_capture(args.a)
            b = load_capture(args.b)
        except (OSError, json.JSONDecodeError) as e:
            print(f"load error: {e}", file=sys.stderr)
            return 2
        a_name = args.a
    diff = compare(a, b, tolerances, args.default_tol)
    if args.json:
        print(json.dumps(diff))
    else:
        print(_render(diff, a_name, args.b or args.a))
    return 1 if diff["regressions"] else 0


if __name__ == "__main__":
    sys.exit(main())
