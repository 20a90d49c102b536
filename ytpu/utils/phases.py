"""Device-phase timers: compile-vs-execute attribution + transfer bytes.

The host→device pipeline's wall time hides three very different costs:
first-call XLA/Mosaic compilation, steady-state dispatch/execute, and
host↔device transfers. Kernel-optimization rounds kept bisecting them
from ad-hoc logs; this recorder separates them at the jit boundaries
(`ops/decode_kernel`, `ops/compaction`, `models/batch_doc`,
`models/ingest`) so the benchmark's readers and `/snapshot` can read a
per-stage breakdown.

Attribution model: every instrumented call passes a hashable ``key``
describing the compiled-program identity (static args + operand shapes).
The FIRST call with an unseen (stage, key) is charged to ``compile_s``
(that wall time includes trace + compile + the first execute); later
calls with the same key charge ``execute_s``. What of a stage's time
went into building programs is beside it, by part, as jax itself times
it (``trace_s``, ``lower_s``, ``backend_s``, ``cache_load_s``, ``builds``,
``cache_hits``: "Build parts" below): ``compile_s`` less those is the
first execution and the Python around the call, and a stage with no key
(or a warm key) that still built something shows it there. ``key=None`` marks a
host-only stage with no compile phase. Because JAX dispatch is async,
``execute_s`` measures dispatch (plus any blocking the callee already
does) — the recorder itself NEVER adds a device sync, so it is safe on
the hot path.

Compile/retrace sentinel (ISSUE-17): beyond charging the time, every
first sighting is journaled as a *compile event* carrying the full
shape signature. A program's SECOND-or-later distinct signature is a
**retrace** — real recompilation on a warmed program, the silent tax
the PR-9 first-seen-client bug paid. Call sites may name the key's
positions via ``axes=("state", "rows", ..., "scan_plan")`` so the
journal's signature DELTA says *which axis changed* (an unnamed
position reports as ``argN``). Events surface three registry families
(looked up fresh on the rare compile path, so a test-time
``metrics.reset()`` can't orphan them): ``compile.events{program=}``,
``compile.retraces`` and ``compile.s_total``. Runs score retraces
against a budget via ``compile_marker()`` / ``compile_report(since=)``,
and ``compile_storm_provider`` turns a blown budget into a degraded
``/healthz`` (the ``compile.storm`` signal). A ``compile.retrace``
fault site perturbs the signature on demand so chaos can prove the
detector fires end to end.

Device-memory attribution (ISSUE-18): the same first-sighting path
that journals a compile event can also capture XLA's compiled memory
analysis. A call site passes ``memory=`` a zero-arg thunk (built with
``program_memory(fn, *args, **kwargs)``) that AOT-lowers the jitted
program against ShapeDtypeStruct snapshots and reads
``compiled.memory_analysis()`` — a compile-cache HIT on the
first-sighting path (the traced call just compiled the same program:
the snapshots keep the sharding of an array laid over several devices,
without which a doc-sharded state lowered to another program and every
first sighting was traced, lowered and compiled twice, PR 41),
so the capture costs ~1ms, never a second compile. The kind split
(temp / argument / output / generated_code / alias bytes) is journaled
INTO the compile event (``event["memory"]``), surfaced as
``memory.program_bytes{program=,kind=}`` gauges plus a
``memory.program_peak_bytes{program=}`` per-program ratchet, and
rolled up by ``memory_report()`` (per-program peaks + the peak
program — the resident-bytes axis the PR-4 roofline lacked).
The thunk reads only shapes and dtypes, which a donated
(`donate_argnums`) and by then deleted argument still answers for, so
building it costs a closure and a warm call builds no spec tree.

Build parts (ISSUE-41): `utils/compile_cache.py::listen_to_builds`
(registered by ``enable()``; this module still imports no jax) hears
``jax.monitoring`` time every program's trace, lowering and backend
build, on the calling thread and inside the call that needed it, and
keeps one row a program with the innermost open span and its recorder
(``build_log()``). That log is the one record: ``snapshot()`` sums a
stage's parts from the rows built under its spans since ``reset()`` (no
roll-up into the spans around it, as ``self_s``), and a first
sighting's compile event takes the rows of its own span.
``backend_s`` is a read of the persistent cache where that hit
(``cache_load_s`` of it), a compile where it missed. Rows built with no
span open while the process's recorder is on show as the stage
``build.unspanned``. A compile event carries the ``parts`` and the
``fun_names`` built under its span, and ``compile_report()`` their
totals, so a retrace says what it cost and of what. The process's
totals and the log are kept whether or not a recorder is on
(``compile_cache.build_totals()``, ``build_log()``).

Disabled-path contract (the default): attribute checks alone (this
recorder's flag and, for the process-wide pair, the tracer's), zero
allocation — call sites guard with ``if phases.enabled:`` before
building keys, and ``span()`` hands back a shared no-op context
manager. Enable via ``YTPU_PHASES=1`` or ``phases.enable()``.

Host spans (keyless stages) are on the same seam as the tracer's
(`_Span` below, utils/trace.py): a span made here also lands in the
tracer's ring when that is on, one made through ``tracer.span`` is also
summed into the stage of its name here, and either way it is written
into the profiler's trace as ``ytpu.<name>``. Stages nest by
containment; ``self_s`` is a stage's time less its nested spans'.

Stage namespaces: ``encode.*`` is the pipelined diff finisher (select /
stage / drain / finish / stall / overlap_ratio / d2h_bytes — ISSUE-10,
docs/observability.md §Encode pipeline); ``ingest.*`` and ``sync.*``
the served step's stages.
"""

from __future__ import annotations

import contextvars
import os
import threading
import time
from typing import Dict, List, Optional, Tuple

__all__ = [
    "PhaseRecorder",
    "phases",
    "NULL_SPAN",
    "compile_storm_provider",
    "program_memory",
]

#: what a stage, a compile event and `compile_report()` hold of the
#: programs built: seconds as `jax.monitoring` timed them, and counts
BUILD_PARTS = (
    "trace_s", "lower_s", "backend_s", "cache_load_s", "builds", "cache_hits",
)

_NO_PARTS = dict.fromkeys(BUILD_PARTS, 0)
#: the stage `snapshot()` shows the programs under that were built with no
#: span open while the process's recorder was on
UNSPANNED = "build.unspanned"

#: journal ring bound — a run that compiles more programs than this is
#: itself a compile storm; the TAIL is what the sentinel reports on
_MAX_COMPILE_EVENTS = 4096


class _NullSpan:
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


NULL_SPAN = _NullSpan()


class _Stage:
    __slots__ = (
        "calls",
        "compile_calls",
        "compile_s",
        "execute_s",
        "self_s",
        "h2d_bytes",
        "d2h_bytes",
        "value",
    )

    def __init__(self):
        self.calls = 0
        self.compile_calls = 0
        self.compile_s = 0.0
        self.execute_s = 0.0
        self.self_s = 0.0  # compile_s + execute_s less the time in nested spans
        self.h2d_bytes = 0
        self.d2h_bytes = 0
        self.value = None  # scalar gauge (overlap_ratio, in-flight depth)


def _built_under(rec, since: float, stage=None) -> List[Dict]:
    """The build log's rows of `rec` (`compile_cache.built_under`; that
    module imports no jax until something listens)."""
    from ytpu.utils.compile_cache import built_under

    return built_under(rec, since, stage)


def _sum_parts(into: Dict, row: Dict) -> None:
    """One build-log row into a dict of BUILD_PARTS."""
    for k in ("trace_s", "lower_s", "backend_s", "cache_load_s"):
        into[k] += row[k]
    into["builds"] += 1
    into["cache_hits"] += row["cache"] == "hit"


def _sig_delta(prev, new, axes) -> List[Dict[str, str]]:
    """Element-wise diff of two signatures with axis-name attribution.
    Non-tuple keys compare as one-element tuples; a length change shows
    as an axis appearing/disappearing against ``<absent>``."""
    prev_t = prev if isinstance(prev, tuple) else (prev,)
    new_t = new if isinstance(new, tuple) else (new,)
    axes = tuple(axes or ())
    delta: List[Dict[str, str]] = []
    for i in range(max(len(prev_t), len(new_t))):
        a = prev_t[i] if i < len(prev_t) else "<absent>"
        b = new_t[i] if i < len(new_t) else "<absent>"
        if a != b:
            delta.append(
                {
                    "axis": axes[i] if i < len(axes) else f"arg{i}",
                    "prev": repr(a),
                    "new": repr(b),
                }
            )
    return delta


def _sharded_over(a):
    """`a`'s sharding where it spans more than one device, else None (a
    single-device array lowers as the call did with none stated). A
    deleted donated array still answers."""
    sharding = getattr(a, "sharding", None)
    if sharding is None or len(sharding.device_set) < 2:
        return None
    return sharding


def program_memory(fn, *args, **kwargs):
    """Build a zero-arg memory-capture thunk for ``span(memory=...)``.

    Building it costs one closure: the thunk runs on the first-sighting
    path only, and it is there that every array-like argument (has
    ``.shape`` and ``.dtype``) becomes a ``jax.ShapeDtypeStruct``. The
    instrumented programs donate their state operands
    (`donate_argnums`), so by then the real buffers are deleted — a
    deleted array still answers for its shape and dtype, which is all
    that is read. Non-array arguments pass through verbatim (they are
    the program's static args). ``fn`` is the jitted callable, or a
    zero-arg resolver returning one (for lazily-built module globals the
    span body itself constructs).

    The thunk AOT-lowers and compiles against the specs — a
    compile-cache hit when invoked on the first-sighting path, since
    the traced call that just ran compiled the identical program — and
    returns the ``memory_analysis()`` kind split in bytes, or raises
    (the recorder treats any raise as "no capture")."""

    def _spec(a):
        import jax

        if hasattr(a, "shape") and hasattr(a, "dtype"):
            # an array laid over several devices keeps its sharding: left
            # out, the lowering is of another program than the call built,
            # and a second trace, lowering and compile (`_sharded_over`)
            return jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=_sharded_over(a)
            )
        if isinstance(a, tuple) and hasattr(a, "_fields"):  # NamedTuple
            return type(a)(*(_spec(x) for x in a))
        if isinstance(a, (tuple, list)):
            return type(a)(_spec(x) for x in a)
        return a

    def thunk():
        specs = tuple(_spec(a) for a in args)
        kwspecs = {k: _spec(v) for k, v in kwargs.items()}
        f = fn if hasattr(fn, "lower") else fn()
        stats = f.lower(*specs, **kwspecs).compile().memory_analysis()
        return {
            "temp_bytes": int(getattr(stats, "temp_size_in_bytes", 0)),
            "argument_bytes": int(
                getattr(stats, "argument_size_in_bytes", 0)
            ),
            "output_bytes": int(getattr(stats, "output_size_in_bytes", 0)),
            "alias_bytes": int(getattr(stats, "alias_size_in_bytes", 0)),
            "generated_code_bytes": int(
                getattr(stats, "generated_code_size_in_bytes", 0)
            ),
        }

    return thunk


# --- the span seam -----------------------------------------------------------
# One host span, whoever makes it: `tracer.span(name)` (utils/trace.py)
# and `phases.span(stage)` both build a `_Span`, and its one enter/exit
# feeds every recorder that is on — the tracer's Chrome-event ring, this
# module's per-stage sums — and opens `jax.profiler.TraceAnnotation(
# "ytpu." + name)`, so that while a profiler trace is being taken the
# span sits on the profiler's host plane, on the clock the device ops
# are on. The `ytpu.` prefix exists only there: a reducer picks the
# program's spans out of the host plane by it.

#: the innermost open span of this thread/task: a span's exit gives its
#: time to the span around it, which is how a stage's `self_s` (time in
#: no nested span) is known without naming the nesting anywhere
_OPEN: "contextvars.ContextVar[Optional[_Span]]" = contextvars.ContextVar(
    "ytpu_open_span", default=None
)

#: jax.profiler.TraceAnnotation; resolved at the first live span (False:
#: no jax here), so importing this module never imports jax
_ANNOTATION = None


def _listen_to_builds() -> None:
    try:
        from ytpu.utils.compile_cache import listen_to_builds

        listen_to_builds()
    except ImportError:  # host-only install: no jax, no program to build
        pass


def _annotate(name: str):
    global _ANNOTATION
    cls = _ANNOTATION
    if cls is None:
        try:
            from jax.profiler import TraceAnnotation as cls
        except ImportError:  # host-only install: the recorders still work
            cls = False
        _ANNOTATION = cls
        _listen_to_builds()  # a recorder turned on by `YTPU_PHASES` or `YTPU_TRACE`
    if cls is False:
        return None
    ann = cls("ytpu." + name)
    ann.__enter__()
    return ann


class _Span:
    __slots__ = (
        "_name", "_rec", "_key", "_axes", "_memory", "_ring", "_args",
        "_ann", "_token", "_outer", "_nested_s", "_start",
    )

    def __init__(
        self, name: str, rec=None, key=None, axes=None, memory=None,
        ring=None, args=None,
    ):
        self._name = name
        self._rec = rec  # PhaseRecorder to sum into, or None
        self._key = key
        self._axes = axes
        self._memory = memory
        self._ring = ring  # Tracer to append a Chrome event to, or None
        self._args = args

    def __enter__(self):
        self._outer = _OPEN.get()
        self._token = _OPEN.set(self)
        self._nested_s = 0.0
        self._ann = _annotate(self._name)
        self._start = time.perf_counter()
        return self

    def __exit__(self, *exc):
        end = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        _OPEN.reset(self._token)
        dt = end - self._start
        if self._outer is not None:
            self._outer._nested_s += dt
        if self._ring is not None:
            self._ring._complete(self._name, self._start, dt, self._args)
        if self._rec is not None:
            self._rec._record(
                self._name, self._key, self._axes, self._memory,
                dt, dt - self._nested_s,
            )
        return False


class PhaseRecorder:
    def __init__(self, enabled: bool = False):
        self.enabled = enabled
        #: the Tracer whose ring this recorder's spans also feed, and
        #: whose being on makes them live: set for the process-wide pair
        #: alone (utils/trace.py links `phases` and `tracer`)
        self._peer = None
        self._stages: Dict[str, _Stage] = {}
        self._seen: set = set()
        self._lock = threading.Lock()
        # ---- compile/retrace sentinel state (ISSUE-17) ----
        #: per program (stage): signatures in first-sighting order
        self._signatures: Dict[str, List] = {}
        #: per program: last axes names supplied by its call site
        self._axes: Dict[str, Tuple[str, ...]] = {}
        #: compile-event journal (bounded ring; see compile_events)
        self._events: List[Dict] = []
        self._event_seq = 0
        #: `reset()`'s moment: the build log's rows from here are this recorder's
        self._since = time.perf_counter()
        # ---- device-memory attribution (ISSUE-18) ----
        #: per program: peak resident bytes + the signature that set it
        self._memory_peaks: Dict[str, Dict] = {}

    def enable(self) -> None:
        self.enabled = True
        _listen_to_builds()

    def disable(self) -> None:
        self.enabled = False

    def reset(self) -> None:
        with self._lock:
            self._stages.clear()
            self._seen.clear()
            self._signatures.clear()
            self._axes.clear()
            self._events.clear()
            self._event_seq = 0
            self._memory_peaks.clear()
            self._since = time.perf_counter()

    # --- compile/retrace sentinel (ISSUE-17) ---------------------------------

    def _record_compile_locked(self, stage: str, key, axes, dt: float):
        """Journal one first-sighting (caller holds the lock, at the
        span's exit). The SECOND-or-later signature for a program is a
        retrace; its delta names the axis that changed vs the previous
        signature. Its ``parts`` are what jax timed of the programs built
        under the span: the build log's rows of this stage from the last
        ``dt`` seconds."""
        built = _built_under(self, time.perf_counter() - dt, stage)
        parts = dict.fromkeys(BUILD_PARTS, 0)
        for row in built:
            _sum_parts(parts, row)
        sigs = self._signatures.setdefault(stage, [])
        if axes:
            self._axes[stage] = tuple(axes)
        retrace = bool(sigs)
        delta = (
            _sig_delta(sigs[-1], key, self._axes.get(stage))
            if retrace
            else []
        )
        sigs.append(key)
        self._event_seq += 1
        event = {
            "seq": self._event_seq,
            "program": stage,
            "compile_s": round(dt, 6),
            "signature": repr(key),
            "retrace": retrace,
            "delta": delta,
            "parts": {k: round(v, 6) for k, v in parts.items()},
            "fun_names": [row["fun_name"] for row in built],
        }
        self._events.append(event)
        if len(self._events) > _MAX_COMPILE_EVENTS:
            del self._events[: len(self._events) - _MAX_COMPILE_EVENTS]
        return event

    @staticmethod
    def _emit_compile_metrics(event: Dict) -> None:
        """Registry families for the sentinel — looked up fresh (the
        compile path is rare, and cached family objects would be
        orphaned by a test-time ``metrics.reset()``)."""
        try:
            from ytpu.utils.metrics import metrics
        except Exception:  # pragma: no cover - import cycles in teardown
            return
        metrics.counter("compile.events", labelnames=("program",)).labels(
            event["program"]
        ).inc()
        metrics.gauge("compile.s_total").inc(event["compile_s"])
        if event["retrace"]:
            metrics.counter("compile.retraces").inc()

    def _fault_key(self, stage: str, key):
        """``compile.retrace`` fault site: a firing spec perturbs the
        signature with a nonce, forcing an attributable retrace — how
        chaos proves the sentinel catches real recompiles."""
        try:
            from ytpu.utils.faults import faults
        except Exception:  # pragma: no cover
            return key
        if not faults.active:
            return key
        spec = faults.fire("compile.retrace", program=stage)
        if spec is None:
            return key
        nonce = ("__fault__", spec.fired)
        return key + (nonce,) if isinstance(key, tuple) else (key, nonce)

    # --- device-memory attribution (ISSUE-18) --------------------------------

    def _record_memory(self, stage: str, event: Dict, thunk) -> None:
        """Capture one program's memory analysis on its first-sighting
        path (caller just emitted the compile event — the lock is NOT
        held). A thunk that raises means the backend can't report
        (interpret mode, host fallbacks): skip silently, the time
        attribution already happened.

        ``resident_bytes`` is the device footprint while the program
        runs: arguments + outputs − aliased (donated buffers overlap
        both) + temps. Generated code is charged separately — it is
        real device memory on TPU but not per-invocation."""
        try:
            kinds = thunk()
        except Exception:
            return
        if not kinds:
            return
        kinds = dict(kinds)
        resident = (
            kinds.get("argument_bytes", 0)
            + kinds.get("output_bytes", 0)
            - kinds.get("alias_bytes", 0)
            + kinds.get("temp_bytes", 0)
        )
        kinds["resident_bytes"] = int(resident)
        peak = 0
        with self._lock:
            event["memory"] = kinds
            rec = self._memory_peaks.get(stage)
            if rec is None or resident > rec["peak_bytes"]:
                rec = self._memory_peaks[stage] = {
                    "peak_bytes": int(resident),
                    "signature": event["signature"],
                    "kinds": kinds,
                }
            peak = rec["peak_bytes"]
        self._emit_memory_metrics(stage, kinds, peak)

    @staticmethod
    def _emit_memory_metrics(stage: str, kinds: Dict, peak: int) -> None:
        """Registry families for memory attribution — fresh lookups for
        the same reset-safety reason as ``_emit_compile_metrics``."""
        try:
            from ytpu.utils.metrics import metrics
        except Exception:  # pragma: no cover - import cycles in teardown
            return
        fam = metrics.gauge(
            "memory.program_bytes", labelnames=("program", "kind")
        )
        for kind, v in kinds.items():
            fam.labels(stage, kind).set(float(v))
        metrics.gauge(
            "memory.program_peak_bytes", labelnames=("program",)
        ).labels(stage).set(float(peak))

    def memory_report(self) -> Dict:
        """Per-program peak-resident ledger + the overall peak program:
        ``{"programs": {stage: {peak_bytes, signature, kinds}},
        "peak_bytes": int, "peak_program": str|None}``. Peaks are
        keyed by shape family — the signature names which shape set
        the high-water mark."""
        with self._lock:
            programs = {
                k: {
                    "peak_bytes": v["peak_bytes"],
                    "signature": v["signature"],
                    "kinds": dict(v["kinds"]),
                }
                for k, v in self._memory_peaks.items()
            }
        peak_program = None
        peak_bytes = 0
        for name, rec in programs.items():
            if rec["peak_bytes"] > peak_bytes:
                peak_bytes = rec["peak_bytes"]
                peak_program = name
        return {
            "programs": programs,
            "peak_bytes": peak_bytes,
            "peak_program": peak_program,
        }

    def compile_marker(self) -> int:
        """Opaque high-water mark for ``compile_report(since=...)`` —
        take one after warmup; events at or before it are 'expected
        cold compiles', anything after is scored."""
        with self._lock:
            return self._event_seq

    def compile_events(self, since: int = 0) -> List[Dict]:
        """Journal entries with seq > ``since`` (copies)."""
        with self._lock:
            return [dict(e) for e in self._events if e["seq"] > since]

    def compile_report(self, since: int = 0) -> Dict:
        """Sentinel rollup since a marker: total events, retrace count,
        compile seconds and, of them, the seconds by build part
        (``parts``: trace, lower, backend, cache load, with the counts of
        programs built and of cache hits), per-program event counts, and
        the retrace journal (each entry's ``delta`` names the changed
        axes, its ``parts`` what the retrace cost and of what)."""
        evs = self.compile_events(since)
        programs: Dict[str, int] = {}
        retraces = 0
        s_total = 0.0
        parts = dict.fromkeys(BUILD_PARTS, 0)
        for e in evs:
            programs[e["program"]] = programs.get(e["program"], 0) + 1
            s_total += e["compile_s"]
            for k in BUILD_PARTS:
                parts[k] += e["parts"][k]
            if e["retrace"]:
                retraces += 1
        return {
            "events": len(evs),
            "retraces": retraces,
            "s_total": round(s_total, 6),
            "parts": {k: round(v, 6) for k, v in parts.items()},
            "programs": programs,
            "journal": [e for e in evs if e["retrace"]],
        }

    # --- timers --------------------------------------------------------------

    def span(self, stage: str, key=None, axes=None, memory=None):
        """Time one call of `stage`. `key` identifies the compiled
        program (first sighting = compile); None = host-only stage.
        ``axes`` optionally names the key's positions for retrace
        attribution (e.g. ``("state", "rows", "scan_plan")``).
        ``memory`` optionally passes a ``program_memory(...)`` thunk,
        invoked ONLY on the first-sighting path (compile-cache hit) to
        journal the program's device-memory kind split.

        The span is the one `_Span` (the seam above): it also lands in
        the process-wide tracer's ring when that is on, and in the
        profiler's trace as ``ytpu.<stage>``."""
        rec = self if self.enabled else None
        ring = self._peer
        if ring is not None and not ring.enabled:
            ring = None
        if rec is None:
            if ring is None:
                return NULL_SPAN
            key = axes = memory = None
        elif key is not None:
            key = self._fault_key(stage, key)
        return _Span(
            stage, rec, key, axes, memory, ring,
            None if ring is None else ring._context_args(None),
        )

    def _record(
        self, stage: str, key, axes, memory, dt: float, self_dt: float
    ) -> None:
        """A `_Span`'s exit: sum `dt` into `stage` (compile_s on the
        first sighting of a key, else execute_s) and `self_dt`, the part
        of it spent in no nested span, into its self_s."""
        event = None
        with self._lock:
            st = self._stages.get(stage)
            if st is None:
                st = self._stages[stage] = _Stage()
            st.calls += 1
            st.self_s += self_dt
            if key is not None and (stage, key) not in self._seen:
                self._seen.add((stage, key))
                st.compile_calls += 1
                st.compile_s += dt
                event = self._record_compile_locked(stage, key, axes, dt)
            else:
                st.execute_s += dt
        if event is not None:
            self._emit_compile_metrics(event)
            if memory is not None:
                self._record_memory(stage, event, memory)

    def transfer(
        self, stage: str, nbytes: int, direction: str = "h2d"
    ) -> None:
        """Count host↔device bytes against `stage` (`direction` is
        "h2d" or "d2h"). No-op (one attribute check) when disabled."""
        if not self.enabled:
            return
        with self._lock:
            st = self._stages.get(stage)
            if st is None:
                st = self._stages[stage] = _Stage()
            if direction == "h2d":
                st.h2d_bytes += int(nbytes)
            else:
                st.d2h_bytes += int(nbytes)

    def add_time(self, stage: str, seconds: float, calls: int = 1) -> None:
        """Accumulate already-measured wall time against a host-only
        stage. The overlap engine times its staging/stall work with bare
        perf_counter reads on the worker/main threads (a span object per
        chunk would allocate on the hot path) and folds the totals in
        here at loop exit."""
        if not self.enabled:
            return
        with self._lock:
            st = self._stages.get(stage)
            if st is None:
                st = self._stages[stage] = _Stage()
            st.calls += int(calls)
            st.execute_s += float(seconds)
            st.self_s += float(seconds)

    def set_value(self, stage: str, value: float) -> None:
        """Record a scalar gauge under `stage` (snapshot key "value") —
        e.g. ``encode.overlap_ratio``, ``encode.inflight_depth``."""
        if not self.enabled:
            return
        with self._lock:
            st = self._stages.get(stage)
            if st is None:
                st = self._stages[stage] = _Stage()
            st.value = float(value)

    def add_value(self, stage: str, delta: float) -> None:
        """Accumulate a scalar gauge (snapshot key "value") — e.g.
        ``encode.d2h_bytes``, or the recorder's copy of an ingest
        counter, whose delta over a window the benchmark's readers
        take. Unlike `set_value` it survives multi-run accumulation."""
        if not self.enabled:
            return
        with self._lock:
            st = self._stages.get(stage)
            if st is None:
                st = self._stages[stage] = _Stage()
            st.value = (st.value or 0.0) + float(delta)

    def set_max(self, stage: str, value: float) -> None:
        """Ratchet a scalar gauge upward (high-water depth tracking)."""
        if not self.enabled:
            return
        with self._lock:
            st = self._stages.get(stage)
            if st is None:
                st = self._stages[stage] = _Stage()
            st.value = value if st.value is None else max(st.value, value)

    def snapshot(self) -> Dict[str, Dict[str, float]]:
        """Per-stage breakdown: calls / compile_calls / compile_s /
        execute_s / self_s (compile_s + execute_s less the time spent in
        spans nested in this stage's) / h2d_bytes / d2h_bytes /
        transfer_bytes (sum) / the build parts (trace_s, lower_s,
        backend_s, cache_load_s, builds, cache_hits: what jax timed of
        the programs built under the stage's spans)."""
        built: Dict[str, Dict[str, float]] = {}
        for row in _built_under(self, self._since):
            _sum_parts(
                built.setdefault(
                    row["stage"] or UNSPANNED, dict.fromkeys(BUILD_PARTS, 0)
                ),
                row,
            )
        out: Dict[str, Dict[str, float]] = {}
        with self._lock:
            stages = dict(self._stages)
            for name in built:  # built with no span open, or under one still open
                stages.setdefault(name, _Stage())
            for name, st in stages.items():
                out[name] = {
                    "calls": st.calls,
                    "compile_calls": st.compile_calls,
                    "compile_s": round(st.compile_s, 6),
                    "execute_s": round(st.execute_s, 6),
                    "self_s": round(st.self_s, 6),
                    "h2d_bytes": st.h2d_bytes,
                    "d2h_bytes": st.d2h_bytes,
                    "transfer_bytes": st.h2d_bytes + st.d2h_bytes,
                }
                for k, v in built.get(name, _NO_PARTS).items():
                    out[name][k] = round(v, 6)
                if st.value is not None:
                    out[name]["value"] = round(st.value, 6)
        if UNSPANNED in built:  # no span to count: a build there is its call
            out[UNSPANNED]["calls"] = built[UNSPANNED]["builds"]
        return out


def compile_storm_provider(
    budget: Optional[int] = 0,
    marker: int = 0,
    recorder: Optional[PhaseRecorder] = None,
):
    """Health-provider factory for ``TelemetryServer.add_health_provider``
    (register under the name ``"compile"``): reports retraces since
    ``marker`` and flips ``degraded``/``storm`` once they exceed
    ``budget`` (None = report-only, never degrades). The section also
    carries the LAST retrace's signature delta so a probe sees *which
    axis changed* without walking the journal, with the ``parts`` of its
    build (what it cost, and whether that was tracing, lowering or the
    backend) and the seconds by part of everything since the marker."""

    def provider() -> Dict:
        rec = recorder if recorder is not None else phases
        rep = rec.compile_report(since=marker)
        storm = budget is not None and rep["retraces"] > budget
        last = rep["journal"][-1] if rep["journal"] else None
        return {
            "retraces": rep["retraces"],
            "budget": budget,
            "compile_s": rep["s_total"],
            "parts": rep["parts"],
            "storm": storm,
            "degraded": storm,
            "last_retrace": (
                {
                    "program": last["program"],
                    "delta": last["delta"],
                    "parts": last["parts"],
                    "fun_names": last["fun_names"],
                }
                if last
                else None
            ),
        }

    return provider


phases = PhaseRecorder(enabled=bool(os.environ.get("YTPU_PHASES")))
