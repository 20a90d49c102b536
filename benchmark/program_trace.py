"""The program's own spans and the device ops' scopes, out of the traced
run's profiler trace.

`benchmark/trace_reduce.py` (yardstick code) keeps the `bench.*` host spans
and bare op names, and `Window.trace` holds only what it reduced. The
per-layer metrics that read *inside* the program need two more things from
the same `.xplane.pb`:

- the host spans the program writes itself: `ytpu/utils/phases.py`'s span
  seam opens `jax.profiler.TraceAnnotation("ytpu." + name)` for every live
  span (`ytpu.sync.dispatch`, `ytpu.ingest.merge.scatter`, ...), nested by
  containment on the thread that made them;
- for every device op, the `jax.named_scope` it was traced under
  (`conflict_scan/cheap`, `integrate_rows`, ...). On a v5e the "XLA Ops"
  events carry no scope that `jax.profiler.ProfileData` shows (their
  `tf_op` is a stat of the event *metadata*, which that reader does not
  surface, and the `while` ops have none at all). The profiler does store
  each program's HLO proto, in the `/host:metadata` plane, and there every
  instruction has its `metadata.op_name`: `hlo_op_names` walks the raw
  protobuf for just that, and an op event gets the `op_name` of the
  instruction it names (`%while.663 = ...`) in the module it ran inside
  ("XLA Modules" line, by time).

`events()` finds the run's newest `*.xplane.pb` under
`<checkout>/.bench_trace` (where `benchmark/README.md` says traces go),
parses it once for the process, and every reader below works on the plain
lists it returns, so they can be checked against a hand-made event list
(`benchmark/tests/test_program_trace.py`). The slice (first `bench.tick`
start to last `bench.tick` end), the busy/idle union and the by-the-middle
attribution of a gap are `trace_reduce`'s own.

A program without the seam (the parent of PR 26) writes no `ytpu.*` span and
no scope: every reader then returns None and its metric is left out.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

from benchmark import trace_reduce

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
PREFIX = "ytpu."
DISPATCH = PREFIX + "sync.dispatch"
MERGE = PREFIX + "ingest.merge"
OUTSIDE = "outside ytpu spans"
UNNAMED = "(no op_name)"

_PARSED: Dict[str, dict] = {}  # xplane path -> load(path), for the process


def newest_xplane(trace_dir: str = TRACE_DIR) -> Optional[str]:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


# --- the HLO protos the profiler keeps, by hand --------------------------------
# Field numbers of tsl's xplane.proto and xla's hlo.proto / xla_data.proto. The
# jax reader shows neither the metadata plane's entries nor an event
# metadata's stats, and the packages that hold the generated classes cost
# half a minute to import: a protobuf message is walked here field by field,
# skipping whatever is not on the way to an instruction's `op_name`.
_XSPACE_PLANES = 1
_XPLANE_NAME, _XPLANE_EVENT_METADATA = 2, 4
_MAP_VALUE = 2
_XEVENT_METADATA_NAME, _XEVENT_METADATA_STATS = 2, 5
_XSTAT_BYTES = 6
_HLO_PROTO_MODULE = 1
_HLO_MODULE_COMPUTATIONS = 3
_HLO_COMPUTATION_INSTRUCTIONS = 2
_HLO_INSTRUCTION_NAME, _HLO_INSTRUCTION_METADATA = 1, 7
_OP_METADATA_OP_NAME = 2
METADATA_PLANE = "/host:metadata"


def _varint(buf, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf, start: int, end: int):
    """(field number, (start, end)) of a message's length-delimited fields;
    the other wire types are stepped over."""
    i = start
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 2:
            n, i = _varint(buf, i)
            yield key >> 3, (i, i + n)
            i += n
        elif wire == 0:
            _, i = _varint(buf, i)
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}: not a protobuf this reader knows")


def _sub(buf, span: Tuple[int, int], number: int):
    return [sp for num, sp in _fields(buf, *span) if num == number]


def _text(buf, span: Tuple[int, int]) -> str:
    return bytes(buf[span[0] : span[1]]).decode("utf-8", "replace")


def hlo_op_names(xspace: bytes) -> Dict[str, Dict[str, str]]:
    """`{program: {instruction name: op_name}}` out of a serialized XSpace:
    `program` is the module's name as the "XLA Modules" line has it
    (`jit_apply_update_batch(3425012894235273035)`). Programs the profiler
    kept no HLO for, and instructions without an `op_name`, are absent."""
    buf = memoryview(xspace)
    out: Dict[str, Dict[str, str]] = {}
    for plane in _sub(buf, (0, len(buf)), _XSPACE_PLANES):
        if [_text(buf, sp) for sp in _sub(buf, plane, _XPLANE_NAME)] != [METADATA_PLANE]:
            continue
        for entry in _sub(buf, plane, _XPLANE_EVENT_METADATA):
            for meta in _sub(buf, entry, _MAP_VALUE):
                names = _sub(buf, meta, _XEVENT_METADATA_NAME)
                protos = [b for st in _sub(buf, meta, _XEVENT_METADATA_STATS) for b in _sub(buf, st, _XSTAT_BYTES)]
                if not names or not protos:
                    continue
                ops = out.setdefault(_text(buf, names[0]), {})
                for module in _sub(buf, protos[0], _HLO_PROTO_MODULE):
                    for comp in _sub(buf, module, _HLO_MODULE_COMPUTATIONS):
                        for ins in _sub(buf, comp, _HLO_COMPUTATION_INSTRUCTIONS):
                            name = op_name = None
                            for num, sp in _fields(buf, *ins):
                                if num == _HLO_INSTRUCTION_NAME:
                                    name = sp
                                elif num == _HLO_INSTRUCTION_METADATA:
                                    op_name = next(iter(_sub(buf, sp, _OP_METADATA_OP_NAME)), None)
                            if name and op_name:
                                ops[_text(buf, name)] = _text(buf, op_name)
    return out


_INSTRUCTION = re.compile(r"^%?([^\s=]+)")


def instruction_name(event_name: str) -> str:
    """`%while.663 = (s32[], ...) while(...)` -> `while.663`."""
    return _INSTRUCTION.match(event_name).group(1)


def load(path: str) -> dict:
    """`{"host": [[name, start_ns, dur_ns, line], ...]` (the `ytpu.*` and
    `bench.*` annotations; `line` numbers the host thread) and `"device":
    {plane: [[name, scope, start_ns, dur_ns], ...]}}` (the ops line; `scope`
    is the instruction's `op_name`, "" where the trace does not tell)."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        raw = f.read()
    op_names = hlo_op_names(raw)
    out = {"host": [], "device": {}}
    n_line = 0
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            if trace_reduce.OPS_LINE not in lines:
                continue
            modules = sorted(
                (float(e.start_ns), float(e.start_ns + e.duration_ns), op_names.get(e.name.strip()))
                for e in (lines[trace_reduce.MODULES_LINE].events if trace_reduce.MODULES_LINE in lines else ())
            )
            starts = [m[0] for m in modules]
            ops = out["device"][plane.name] = []
            for e in lines[trace_reduce.OPS_LINE].events:
                s = float(e.start_ns)
                i = bisect.bisect_right(starts, s) - 1
                inside = modules[i][2] if i >= 0 and s < modules[i][1] else None
                scope = inside.get(instruction_name(e.name), "") if inside else ""
                ops.append([e.name, scope, s, float(e.duration_ns)])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                n_line += 1
                for e in line.events:
                    if e.name.startswith((PREFIX, "bench.")):
                        out["host"].append([e.name, float(e.start_ns), float(e.duration_ns), n_line])
    return out


def events(trace_dir: str = TRACE_DIR) -> Optional[dict]:
    """The newest trace under `trace_dir`, parsed once; None without one."""
    path = newest_xplane(trace_dir)
    if path is None:
        return None
    if path not in _PARSED:
        _PARSED[path] = load(path)
    return _PARSED[path]


# --- reductions over the plain lists ------------------------------------------


def slice_bounds(ev: dict) -> Optional[Tuple[float, float]]:
    ticks = [(s, s + d) for n, s, d, _ in ev["host"] if n == trace_reduce.SLICE_SPAN]
    if not ticks:
        return None
    lo, hi = min(a for a, _ in ticks), max(b for _, b in ticks)
    return (lo, hi) if hi > lo else None


def program_spans(ev: dict, lo: float, hi: float) -> List[Tuple[float, float, str, int]]:
    """(start, end, name, line) of the `ytpu.*` spans wholly inside the
    slice, outermost first where two start together."""
    spans = [(s, s + d, n, ln) for n, s, d, ln in ev["host"] if n.startswith(PREFIX) and s >= lo and s + d <= hi]
    return sorted(spans, key=lambda sp: (sp[0], -sp[1]))


def leaf_spans(spans: List[tuple]) -> List[tuple]:
    """The spans with no other span of their thread nested in them."""
    leaves = []
    open_by_line: Dict[int, list] = {}
    for sp in spans:  # sorted by start, outermost first
        stack = open_by_line.setdefault(sp[3], [])
        while stack and stack[-1][0][1] <= sp[0]:  # ended before this one starts
            top, has_child = stack.pop()
            if not has_child:
                leaves.append(top)
        if stack:
            stack[-1][1] = True
        stack.append([sp, False])
    for stack in open_by_line.values():
        leaves.extend(top for top, has_child in stack if not has_child)
    return sorted(leaves)


def dispatch_self_share(ev: dict) -> Optional[float]:
    """Of the time of the slice's `ytpu.sync.dispatch` spans, the share that
    no leaf `ytpu.*` span nested in them covers: host time in a step that
    no span names."""
    bounds = slice_bounds(ev)
    if bounds is None:
        return None
    spans = program_spans(ev, *bounds)
    dispatches = [sp for sp in spans if sp[2] == DISPATCH]
    total = sum(e - s for s, e, _, _ in dispatches)
    if not total:
        return None
    leaves = [sp for sp in leaf_spans(spans) if sp[2] != DISPATCH]
    named = 0.0
    for s, e, _, ln in dispatches:
        inside = [(a, b) for a, b, _, l in leaves if l == ln and a >= s and b <= e]
        named += trace_reduce.union_seconds(inside, s, e)[0]
    return 1.0 - named / total


def device_ops(ev: dict) -> List[list]:
    """The first chip's op events (gaps are attributed on the first chip,
    as `trace_reduce.reduce` does)."""
    planes = sorted(ev["device"].items())
    return planes[0][1] if planes else []


def scoped_device_seconds(ev: dict, scope: str) -> Optional[float]:
    """Device seconds, inside the slice, in which an op traced under
    `scope` ran (union, so an op and the ops nested in it count once);
    None where no op of the trace was traced under it (a program without
    that scope has nothing to read, which is not a reading of zero)."""
    bounds = slice_bounds(ev)
    under = [(s, s + d) for _, sc, s, d in device_ops(ev) if scope in sc]
    if bounds is None or not under:
        return None
    return trace_reduce.union_seconds(under, *bounds)[0] / 1e9


_STRUCTURE = re.compile(r"^(jit\(.*\)|pjit|while|body|cond|body_pred|closed_call|branch_\d+_fun|checkpoint|remat)$")


def scope_path(op_name: str) -> str:
    """The program and the named scopes of an `op_name`, outermost first:
    `jit(f)/vmap(integrate_rows)/while/body/closed_call/conflict_scan/cheap/while/body/add`
    -> `f/integrate_rows/conflict_scan/cheap`. jax's own wrappers (`while`,
    `body`, ...) and the primitive at the end are dropped; a loop op, whose
    `op_name` ends in `while`, keeps the whole of its path."""
    parts = [p for p in op_name.split("/") if p]
    if len(parts) > 1 and not _STRUCTURE.match(parts[-1]):
        parts = parts[:-1]  # the primitive
    named: List[str] = []
    for i, p in enumerate(parts):
        if i and _STRUCTURE.match(p):
            continue
        p = re.sub(r"^\w+\((.*)\)$", r"\1", p)  # jit(f) -> f, vmap(integrate_rows) -> integrate_rows
        if p and named[-1:] != [p]:  # a scope re-entered by a nested trace: once
            named.append(p)
    return "/".join(named)


def device_self_seconds(ev: dict) -> Dict[str, float]:
    """Device seconds inside the slice by the scope path of the op that was
    running, each op counted for its own time alone (less the ops nested in
    it, as a loop's body is): the rows add up to the busy time. An op whose
    instruction has no `op_name` (a fusion or copy the compiler made) is
    under `UNNAMED` of the op it is nested in."""
    bounds = slice_bounds(ev)
    if bounds is None:
        return {}
    lo, hi = bounds
    clipped = sorted(
        ((max(s, lo), min(s + d, hi), sc) for _, sc, s, d in device_ops(ev) if s + d > lo and s < hi),
        key=lambda op: (op[0], -op[1]),
    )
    out: Dict[str, float] = {}
    stack: List[list] = []  # [end, scope path, own ns]

    def close(upto: float) -> None:
        while stack and stack[-1][0] <= upto:
            _, path, own = stack.pop()
            out[path] = out.get(path, 0.0) + own / 1e9

    for a, b, sc in clipped:
        close(a)
        if stack:
            stack[-1][2] -= min(b, stack[-1][0]) - a
        path = scope_path(sc)
        if not sc:
            path = (stack[-1][1].removesuffix("/" + UNNAMED) + "/" if stack else "") + UNNAMED
        stack.append([b, path, b - a])
    close(float("inf"))
    return out


def idle_gaps(ev: dict) -> List[Tuple[float, float]]:
    bounds = slice_bounds(ev)
    if bounds is None:
        return []
    return trace_reduce.union_seconds([(s, s + d) for _, _, s, d in device_ops(ev)], *bounds)[1]


def idle_by_span(ev: dict) -> Dict[str, List[float]]:
    """Every idle gap of the slice put down to the innermost `ytpu.*` span
    open at its middle: `{span: [seconds, gaps, longest gap in seconds]}`;
    `OUTSIDE` holds the gaps no program span was open at."""
    bounds = slice_bounds(ev)
    if bounds is None:
        return {}
    lo, hi = bounds
    spans = sorted(
        ((s, s + d, n) for n, s, d, _ in ev["host"] if n.startswith(PREFIX) and s + d > lo and s < hi),
        key=lambda sp: (sp[0], -sp[1]),  # of two that start together the inner one is the later
    )
    starts = [s for s, _, _ in spans]
    out: Dict[str, List[float]] = {}
    for a, b in idle_gaps(ev):
        mid = (a + b) / 2
        name = OUTSIDE
        for i in range(bisect.bisect_right(starts, mid) - 1, -1, -1):  # latest start first
            if spans[i][1] >= mid:
                name = spans[i][2]
                break
        row = out.setdefault(name, [0.0, 0, 0.0])
        row[0] += (b - a) / 1e9
        row[1] += 1
        row[2] = max(row[2], (b - a) / 1e9)
    return out


def idle_share_inside(ev: dict, span_name: str) -> Optional[float]:
    """Share of the slice's device-idle time whose gaps (by their middle)
    lie inside a span called `span_name`; None where the program wrote no
    such span."""
    bounds = slice_bounds(ev)
    if bounds is None:
        return None
    lo, hi = bounds
    inside = sorted((s, s + d) for n, s, d, _ in ev["host"] if n == span_name and s + d > lo and s < hi)
    gaps = idle_gaps(ev)
    idle = sum(b - a for a, b in gaps)
    if not inside or not idle:
        return None
    starts = [s for s, _ in inside]
    hit = 0.0
    for a, b in gaps:
        mid = (a + b) / 2
        i = bisect.bisect_right(starts, mid) - 1
        if i >= 0 and inside[i][1] >= mid:  # spans of one name do not nest
            hit += b - a
    return hit / idle
