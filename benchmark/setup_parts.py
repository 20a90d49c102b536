"""Set-up from inside: what the program says its start cost, for the readers
of the metrics that move `setup_s`.

The program (`ytpu/utils/compile_cache.py`, since PR 41) hears `jax.monitoring`
time every program's trace, lowering and backend build, and keeps the process's
totals by part and one row a program built (`build_totals()`, `build_log()`).
A reader runs after the window and the check, both of which may build programs
too, so `at_opening` takes the totals now less the rows the log holds from the
window's opening on: each row carries `time.perf_counter()` at its end, the
clock `Window.t_open` is on.

A program without the totals (the parent of PR 41) gives `None` everywhere, and
the metric is left out.
"""

from __future__ import annotations

import os
from typing import Dict, List, Optional, Tuple

from benchmark.program_trace import TRACE_DIR

JOURNAL = os.path.join(TRACE_DIR, "build_journal.json")
SECONDS = ("trace_s", "lower_s", "backend_s", "cache_load_s", "saved_s")
DISPATCH = "sync.dispatch"


def program() -> Optional[Tuple[Dict[str, float], List[dict]]]:
    """(the process's build totals, its build log), or None where the
    program keeps none or has not been listening."""
    try:
        from ytpu.utils.compile_cache import build_log, build_totals
    except ImportError:
        return None
    totals = build_totals()
    return (totals, build_log()) if totals else None


def at_opening(w) -> Optional[Dict[str, float]]:
    """The build totals as they stood when the window opened."""
    got = program()
    if got is None:
        return None
    totals, log = got
    since = [r for r in log if r["t"] >= w.t_open]
    out = dict(totals)
    for k in SECONDS:
        out[k] -= sum(r[k] for r in since)
    out["builds"] -= len(since)
    out["cache_hits"] -= sum(1 for r in since if r["cache"] == "hit")
    out["cache_requests"] -= sum(1 for r in since if r["cache"] is not None)
    return out


def part(w, name: str) -> Optional[float]:
    totals = at_opening(w)
    return None if totals is None else float(totals[name])


def setup_dispatch_s(w) -> Optional[float]:
    """Set-up's seconds inside the program's `sync.dispatch` spans: the
    recorder's total less the window's delta. The builds that lie inside
    those spans are in it (the journal says which)."""
    from ytpu.utils.phases import phases

    st = phases.snapshot().get(DISPATCH)
    if not st or "trace_s" not in st:  # no stage, or a program without the parts
        return None
    delta = w.phases.get(DISPATCH, {})
    return (st["compile_s"] + st["execute_s"]) - (delta.get("compile_s", 0.0) + delta.get("execute_s", 0.0))


def native_startup() -> Optional[Dict[str, float]]:
    from ytpu import native

    return getattr(native, "startup", None)


def journal(w) -> Optional[dict]:
    """Everything `tools/setup_by_program.py` prints: the totals now and at
    the window's opening, `setup_s`, the native library's start, set-up's
    dispatch seconds, and one row a program (`before_window` says on which
    side of the opening it was built)."""
    got = program()
    if got is None:
        return None
    totals, log = got
    return {
        "setup_s": w.setup_s,
        "totals": totals,
        "at_opening": at_opening(w),
        "native": native_startup(),
        "setup_dispatch_s": setup_dispatch_s(w),
        "programs": [dict(r, before_window=r["t"] < w.t_open) for r in log],
    }
