"""Bounded resident-program set (VERDICT r4 #7 — the clear_caches fix).

Root cause being addressed: XLA:CPU executables are JIT-compiled into
one LLVM memory arena per process; after many LARGE programs accumulate
(each distinct shape of the decode/apply entry points is one), the
arena's allocator fails ("LLVM compilation error: Cannot allocate
memory", execution_engine.cc) and the failure is mishandled into a
SIGSEGV. The reference embeds in long-lived processes trivially; a
long-lived ytpu server (or a test suite compiling hundreds of shapes)
must therefore BOUND its live program set instead of growing it forever.

The old workaround wiped every cache wholesale from a test fixture
(`jax.clear_caches()` every other module — doubling suite wall time and
fixing nothing for real servers). This registry replaces it:

- the big jitted entry points register here (decode lanes, batched
  apply, diff encode, finisher pack, sharded step);
- `tick()` — called from the host-side entry wrappers — periodically
  sums the registered functions' per-function executable caches
  (`fn._cache_size()`); when the total exceeds the budget, the largest
  holders are evicted via their OWN `fn.clear_cache()` until back under.

Eviction is per-function and proportional: a steady server dispatching
a handful of shapes never crosses the budget and never pays a
recompile; only shape-churning workloads (the test suite, multi-tenant
servers with unbounded shape diversity) trade occasional recompiles for
a bounded LLVM arena. Upstream repro notes live in tests/conftest.py.
"""

from __future__ import annotations

import os
from typing import Callable, Dict

__all__ = ["register", "tick", "enforce", "resident_programs"]

_REGISTRY: Dict[str, Callable] = {}
# Budget on RESIDENT EXECUTABLES across the registered (large) programs.
# ~64 large CPU programs sit well under the observed exhaustion point
# (the r4 repro needed hundreds of large compiles to die); TPU
# executables don't ride the LLVM arena, so `enforce` leaves them alone.
_MAX = int(os.environ.get("YTPU_MAX_RESIDENT_PROGRAMS", "64"))
_EVERY = int(os.environ.get("YTPU_PROGBUDGET_EVERY", "16"))
_calls = 0


def register(name: str, fn: Callable) -> Callable:
    """Track a jitted function's executable cache under the budget."""
    _REGISTRY[name] = fn
    return fn


def _entries(fn: Callable) -> int:
    try:
        return int(fn._cache_size())
    except Exception:
        return 0


def resident_programs() -> Dict[str, int]:
    """Per-function resident executable counts (diagnostics)."""
    return {name: _entries(fn) for name, fn in _REGISTRY.items()}


def enforce() -> int:
    """Evict largest holders until the resident total is under budget.

    Returns the number of functions whose caches were cleared. On the CPU
    backend only: it is XLA:CPU's LLVM arena the budget guards. On a TPU a
    served tick of at most 16 rooms is 16 lane counts x 2 wire buckets x 3
    programs (gather, decode, merge), over the budget before the first
    handshake: an eviction there is a rebuild in the middle of traffic,
    11 of them in a 30 s window (PERF.md §6, PR 35), for nothing."""
    import jax

    if jax.default_backend() != "cpu":
        return 0
    sizes = [(name, fn, _entries(fn)) for name, fn in _REGISTRY.items()]
    total = sum(s for _, _, s in sizes)
    if total <= _MAX:
        return 0
    cleared = 0
    for _name, fn, s in sorted(sizes, key=lambda t: -t[2]):
        if total <= _MAX or s == 0:
            break
        try:
            fn.clear_cache()
        except Exception:
            continue
        total -= s
        cleared += 1
    return cleared


def tick() -> None:
    """Cheap per-dispatch hook: every `_EVERY` calls, enforce the budget."""
    global _calls
    _calls += 1
    if _calls % _EVERY == 0:
        enforce()
