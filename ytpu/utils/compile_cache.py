"""JAX's persistent compilation cache at a fixed place, and what building
programs costs.

The cache directory is part of the cache key's lookup, so a directory that
moves (tempfile, pid, time) never hits. `enable_compile_cache()` is the one
place the repo's entry points (`chip_smoke.py`, `benchmark/run.py`) turn
the cache on.

It also puts the programs' metadata into the cache key. jax leaves it out
by default, so a cache warmed by a tree without a `jax.named_scope` hands
the same executable, with its old op names, to a tree that has one, and
the profiler then shows `while.663` where the source says
`conflict_scan/cheap`. The price is that an edit which moves a traced
line recompiles the programs traced from that file once.

**Build parts.** jax reports, through `jax.monitoring`, on the calling
thread and inside the call that needed the program, how long a program
was traced to a jaxpr, lowered to an MLIR module, and built by the
backend (which is a read of the persistent cache where that hit), and
how long the cache read took. `listen_to_builds()` (called by
`enable_compile_cache()` and `phases.enable()`, whichever comes first)
registers the process's one duration listener and one event listener.
They keep

- `build_totals()`: the process's seconds by part and its counts, always
  (three callbacks a program built, none once a server is warm);
- `build_log()`: one row a program built, with the span it was built
  under (`phases._OPEN`'s innermost) and the recorder that span was
  recording for.

The log is the one record by span: a recorder's `snapshot()` sums its
stages' build parts from these rows (`built_under`), a row with no span
open under the stage `build.unspanned`, and a compile event's `parts` are
the rows of its span.

`backend_s` holds a cache read where the cache hit, so the seconds the
backend really compiled are `backend_s - cache_load_s`. A traced function
that calls jitted ones (`jnp.where`, a nested `jax.jit`) reports every
inner trace and then its own, which contains them: only the outermost is
summed. `saved_s` is the cache's own estimate of what its hits would
have cost to compile, less their reads: what a cold cache would add to
`setup_s` (`chip_smoke.py` and `benchmark/tools/setup_by_program.py`
print it).
"""

from __future__ import annotations

import os
import threading
import time
from typing import Dict, List

from ytpu.utils.phases import _OPEN, phases

__all__ = [
    "enable_compile_cache",
    "listen_to_builds",
    "build_totals",
    "build_log",
    "built_under",
    "monitoring_names",
]

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

#: build-log ring bound, as the compile journal's
_MAX_ROWS = 4096
#: a thread's traces not yet claimed by an outer one: bounded, because a
#: trace that never lowers (`jax.eval_shape`) leaves its entry behind
_MAX_OPEN_TRACES = 256


def enable_compile_cache() -> str:
    """Return the directory jax caches compiled programs in.

    Where `JAX_COMPILATION_CACHE_DIR` is set jax already reads it and this
    sets nothing; otherwise the cache lives in `<checkout>/.jax_cache`."""
    import jax

    listen_to_builds()
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def monitoring_names() -> Dict[str, str]:
    """The `jax.monitoring` names the listeners key on, from the installed
    jax where it exports them (a jax that has renamed a constant falls
    back to the name this was written against, so a server still starts;
    `tests/test_build_parts.py` is what fails). The cache's are string
    literals in `jax._src.compiler.compile_or_get_cached` (the same test
    holds them to that source)."""
    from jax._src import dispatch

    core = "/jax/core/compile/"
    return {
        "trace": getattr(dispatch, "JAXPR_TRACE_EVENT", core + "jaxpr_trace_duration"),
        "lower": getattr(
            dispatch, "JAXPR_TO_MLIR_MODULE_EVENT",
            core + "jaxpr_to_mlir_module_duration",
        ),
        "backend": getattr(
            dispatch, "BACKEND_COMPILE_EVENT", core + "backend_compile_duration"
        ),
        "cache_load": "/jax/compilation_cache/cache_retrieval_time_sec",
        "saved": "/jax/compilation_cache/compile_time_saved_sec",
        "cache_hits": "/jax/compilation_cache/cache_hits",
        "cache_requests": "/jax/compilation_cache/compile_requests_use_cache",
    }


class _Builds:
    """The process's build accounting: what the two listeners write."""

    def __init__(self):
        self.lock = threading.Lock()
        self.listening = False
        self.totals = {
            "trace_s": 0.0, "lower_s": 0.0, "backend_s": 0.0,
            "cache_load_s": 0.0, "saved_s": 0.0,
            "builds": 0, "cache_hits": 0, "cache_requests": 0,
        }
        self.rows: List[Dict] = []
        #: per thread: `traces`, the (arrival, seconds) of traces no outer
        #: trace has claimed yet, and `row`, the parts of the program being
        #: built, closed by its backend event
        self.local = threading.local()

    def pending(self) -> Dict:
        row = getattr(self.local, "row", None)
        if row is None:
            row = self.local.row = {
                "trace_s": 0.0, "lower_s": 0.0, "cache_load_s": 0.0,
                "saved_s": 0.0, "cache": None,
            }
        return row

    def own_trace_s(self, secs: float) -> float:
        """`secs` less the traces nested in this one, which arrived first
        and were summed already: each arrived after this one started."""
        traces = getattr(self.local, "traces", None)
        if traces is None:
            traces = self.local.traces = []
        arrived = time.time()  # the clock jax timed `secs` on
        started = arrived - secs
        nested = 0.0
        while traces and traces[-1][0] >= started:
            nested += traces.pop()[1]
        traces.append((arrived, secs))
        if len(traces) > _MAX_OPEN_TRACES:
            del traces[: len(traces) - _MAX_OPEN_TRACES]
        return secs - nested

    def add(self, part: str, amount) -> None:
        """One part of one program: into the totals and the thread's open
        row (the seconds it keeps)."""
        with self.lock:
            self.totals[part] += amount
        row = self.pending()
        if part in row:
            row[part] += amount

    def close_row(self, name: str, backend_s: float) -> None:
        """The backend event, a program's last: its row is whole. It is
        put down to the innermost open span and to the recorder that span
        records for; with no span open, to the process's recorder if that
        is on (`build.unspanned` in its snapshot)."""
        row = self.pending()
        self.local.row = None
        spans = []
        span = _OPEN.get()
        if span is None:
            key, rec = None, phases if phases.enabled else None
        else:
            key, rec = span._key, span._rec
        while span is not None:
            spans.append(span._name)
            span = span._outer
        spans.reverse()
        row.update(
            fun_name=name,
            backend_s=backend_s,
            spans=spans,
            stage=spans[-1] if spans else None,
            signature=None if key is None else repr(key),
            t=time.perf_counter(),
            rec=rec,
        )
        with self.lock:
            self.totals["builds"] += 1
            self.rows.append(row)
            if len(self.rows) > _MAX_ROWS:
                del self.rows[: len(self.rows) - _MAX_ROWS]


_BUILDS = _Builds()


def listen_to_builds() -> None:
    """Register the process's listeners with `jax.monitoring`, once."""
    import jax

    b = _BUILDS
    with b.lock:
        if b.listening:
            return
        b.listening = True
    names = monitoring_names()
    seconds_of = {
        names[k]: k + "_s"
        for k in ("trace", "lower", "backend", "cache_load", "saved")
    }
    cache_hits, cache_requests = names["cache_hits"], names["cache_requests"]

    def on_duration(name, secs, fun_name=None, **_):
        part = seconds_of.get(name)
        if part is None:
            return
        if part == "trace_s":
            secs = b.own_trace_s(secs)
        b.add(part, secs)
        if part == "backend_s":
            b.close_row(fun_name or "?", secs)

    def on_event(name, **_):
        if name == cache_hits:
            b.pending()["cache"] = "hit"
            b.add("cache_hits", 1)
        elif name == cache_requests:
            b.pending()["cache"] = "miss"  # until its hit event, if one comes
            b.add("cache_requests", 1)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)


def build_totals() -> Dict[str, float]:
    """The process's seconds of `trace_s`, `lower_s`, `backend_s` (a cache
    read where the cache hit), `cache_load_s`, `saved_s` (what the cache's
    hits would have cost to compile, less their reads) and its counts
    `builds` (backend events), `cache_hits`, `cache_requests`, since
    `listen_to_builds()`. `{}` before it."""
    with _BUILDS.lock:
        return dict(_BUILDS.totals) if _BUILDS.listening else {}


def build_log() -> List[Dict]:
    """One row a program built, oldest first: `fun_name`, `trace_s`,
    `lower_s`, `backend_s`, `cache_load_s`, `saved_s`, `cache` ("hit",
    "miss", or None where the persistent cache was not asked), the open
    spans it was built under from the outermost in (`spans`; `stage` is
    the innermost, None with no span open; `signature` its key, where it
    has one) and `t`, `time.perf_counter()` at its end."""
    with _BUILDS.lock:
        return [{k: v for k, v in r.items() if k != "rec"} for r in _BUILDS.rows]


def built_under(rec, since: float, stage=None) -> List[Dict]:
    """The log's rows since `since` (`time.perf_counter()`) of the programs
    built under recorder `rec`'s spans (or with no span open while `rec`,
    the process's, was on); under its `stage` alone where one is given.
    What `PhaseRecorder.snapshot()` and a compile event's `parts` sum."""
    with _BUILDS.lock:
        return [
            r for r in _BUILDS.rows
            if r["rec"] is rec and r["t"] >= since
            and (stage is None or r["stage"] == stage)
        ]
