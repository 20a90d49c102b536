"""Deterministic fault injection for the host→device pipeline (ISSUE-6).

The resilience machinery (the encode pipeline's demotions, the hardened
sync transport, the mesh's failover) is only trustworthy if its failure
paths run under test — and real dispatch crashes, staging exceptions, or
stalled peers cannot be produced on demand.  This module plants *named
injection sites* at the hot path's failure points; each site is a single
`faults.active` attribute check when nothing is armed, so the healthy
path pays one dict-is-empty test per site visit and allocates nothing.

Arming is deterministic and replayable: a site decision depends only on
the armed spec (its seed) and the site's *eligible pass counter*, never
on wall clock or object identity — the same `YTPU_FAULTS` string against
the same workload injects the same faults in the same places every run.

Grammar (`YTPU_FAULTS` env var, or `faults.configure(text)`):

    YTPU_FAULTS="site[:k=v[,k=v...]][;site2[:...]...]"

Reserved keys (all optional):

- ``n``     — how many times the spec fires (default 1; ``n=0`` = every
  eligible pass, unbounded);
- ``after`` — eligible passes skipped before the spec may fire
  (default 0: the first eligible pass fires);
- ``p``     — per-pass fire probability in [0, 1] (default: fire
  deterministically once ``after`` is exhausted);
- ``seed``  — RNG seed for ``p`` draws
  (default 0; the site name is folded in, so two sites armed with the
  same seed draw independent sequences).

Any other key is a free-form *site argument* (string or number) — e.g.
``prefix=encode`` restricts ``stage.raise`` to the encode pipeline's
staging thread, ``ms=50`` sets the ``net.delay`` stall.  A site argument
that names a *context* key the call site passes (e.g. ``prefix``) must
match for the pass to be eligible.

Standard sites (see docs/robustness.md for the full catalogue):

====================  =======================================================
``stage.raise``       raise inside the overlap staging thread (args:
                      ``prefix`` = OverlapPipeline stage_prefix, "encode"
                      for `DiffPipeline`'s — the site lives in the
                      engine's worker)
``net.drop``          swallow one outbound frame
``net.truncate``      write a frame header + half the payload (stalls the
                      reader mid-frame)
``net.delay``         stall a frame read (args: ``ms``, default 50)
``session.kill``      soak-time (ISSUE-9): force-drop the current event's
                      serving session mid-soak — the driver reconnects it
                      and the state-vector handshake resyncs
``admission.reject``  soak-time (ISSUE-9): force the next admission
                      decision to refuse (typed `QueueFull` → protocol
                      Busy reply / drop / shed per the armed policy;
                      args: ``tenant`` restricts to one tenant)
``diff.d2h_fail``     encode pipeline (ISSUE-10): fail one sub-batch's
                      device→host drain of the compacted finisher rows —
                      the sub-batch demotes to the serial per-doc
                      finisher path (``encode.demotions``) instead of
                      dropping the diff
``finisher.raise``    encode pipeline (ISSUE-10): raise in place of the
                      batched native finisher call for one sub-batch —
                      same serial per-doc demotion, byte output intact
``replica.partition`` federation (ISSUE-13): partition one mesh link
                      pair at the next sync round (args: ``a``/``b``
                      replica ids, default the first alive pair) —
                      frames DROP until a heal; anti-entropy skips the
                      cut links
``replica.heal``      federation (ISSUE-13): heal every partitioned
                      link, queueing an SV-resync gossip both ways
``replica.lag``       federation (ISSUE-13): defer one link pair's
                      delivery (args: ``a``/``b``, ``rounds`` default
                      2) — transit latency, nothing lost
``replica.kill``      federation (ISSUE-13): kill a replica at the next
                      sync round (args: ``replica`` id, default the
                      last alive; ``drain=0`` skips the pre-kill drain
                      so its unreplicated tail is LOST) — sessions drop
                      with ``net.sessions_dropped{reason="failover"}``,
                      ownership hands off to a survivor
``commit.corrupt``    federation (ISSUE-13): XOR one tenant-commitment
                      incremental fold (args: ``tenant`` restricts,
                      ``xor`` overrides the mask) — simulated silent
                      state divergence; the anti-entropy commitment
                      check must catch it as a typed `DivergenceFault`
``autopilot.stall``   autopilot (ISSUE-16): skip the controller's next
                      ``n`` ticks entirely (the control loop wedged) —
                      the mesh must keep serving and converging without
                      remediation, merely degraded; each skipped tick
                      journals a ``fault/stall`` entry and increments
                      ``autopilot.stalls``
``autopilot.misfire`` autopilot (ISSUE-16): after the policy pass, take
                      one WRONG but legal action (a seeded-RNG tenant
                      migration to a seeded-RNG live replica) — byte
                      parity must survive a misdirected controller,
                      since migration only moves ownership, never state
``compile.retrace``   observability (ISSUE-17): perturb the next
                      instrumented jit boundary's shape signature with
                      a nonce (args: ``program`` restricts to one
                      phases stage) — forces an attributable retrace
                      event so chaos can prove the compile sentinel and
                      its budget scoring fire end to end
====================  =======================================================

Every fired injection increments the ``faults.injected`` counter (plus a
per-site ``faults.injected_by_site{site=...}`` child) so recovery tests
can assert the fault actually happened, not just that nothing crashed.
"""

from __future__ import annotations

import os
import random
import threading
import zlib
from contextlib import contextmanager
from typing import Dict, List, Optional

from ytpu.utils.metrics import metrics

__all__ = ["FaultError", "FaultSpec", "FaultInjector", "faults"]

_INJECTED = metrics.counter("faults.injected")
_INJECTED_BY_SITE = metrics.counter(
    "faults.injected_by_site", labelnames=("site",)
)

class FaultError(RuntimeError):
    """An injected fault (never raised by real failures).  Recovery code
    treats it like the device/transport error its site simulates; code
    that must NOT mask injection (tests, the chaos smoke) can still
    `isinstance` it."""

    def __init__(self, site: str, spec: "FaultSpec"):
        super().__init__(f"injected fault at site {site!r}")
        self.site = site
        self.spec = spec


def _coerce(v: str):
    try:
        return int(v)
    except ValueError:
        try:
            return float(v)
        except ValueError:
            return v


class FaultSpec:
    """One armed fault: site + firing schedule + free-form site args."""

    __slots__ = ("site", "n", "after", "p", "seed", "args", "fired",
                 "passes", "_rng")

    def __init__(
        self,
        site: str,
        n: int = 1,
        after: int = 0,
        p: Optional[float] = None,
        seed: int = 0,
        **args,
    ):
        self.site = site
        self.n = int(n)
        self.after = int(after)
        self.p = None if p is None else float(p)
        self.seed = int(seed)
        self.args = args
        self.fired = 0
        self.passes = 0  # eligible passes seen (context-matched)
        # site name folded into the seed: two sites armed with one seed
        # draw independent, still fully deterministic sequences
        self._rng = random.Random(
            zlib.crc32(f"{self.seed}:{site}".encode()) & 0xFFFFFFFF
        )

    def _matches(self, ctx: Dict) -> bool:
        for k, v in ctx.items():
            want = self.args.get(k)
            if want is not None and str(want) != str(v):
                return False
        return True

    def _decide(self) -> bool:
        """Advance this spec's pass counter; True when it fires now."""
        self.passes += 1
        if self.n and self.fired >= self.n:
            return False
        if self.passes <= self.after:
            return False
        if self.p is not None and self._rng.random() >= self.p:
            return False
        self.fired += 1
        return True

    def __repr__(self):  # debugging / chaos-report aid
        return (
            f"FaultSpec({self.site!r}, n={self.n}, after={self.after}, "
            f"p={self.p}, fired={self.fired}, args={self.args})"
        )


class FaultInjector:
    """Process-wide registry of armed fault specs (thread-safe: staging
    threads and asyncio callbacks hit sites concurrently)."""

    def __init__(self):
        self._specs: Dict[str, List[FaultSpec]] = {}
        self._lock = threading.Lock()
        self._suspended = 0
        self.active = False  # cheap hot-path gate, kept in sync below

    # ------------------------------------------------------------- arming

    def arm(self, site: str, **kw) -> FaultSpec:
        """Programmatically arm one spec; returns it (its `fired` counter
        is the per-spec assertion surface)."""
        spec = FaultSpec(site, **kw)
        with self._lock:
            self._specs.setdefault(site, []).append(spec)
            self.active = self._suspended == 0
        return spec

    def configure(self, text: Optional[str]) -> None:
        """Arm every spec in a `YTPU_FAULTS` grammar string (appends to
        whatever is already armed; empty/None is a no-op)."""
        if not text:
            return
        for part in text.split(";"):
            part = part.strip()
            if not part:
                continue
            site, _, argstr = part.partition(":")
            kw = {}
            for kv in filter(None, (s.strip() for s in argstr.split(","))):
                k, _, v = kv.partition("=")
                kw[k.strip()] = _coerce(v.strip()) if v else 1
            self.arm(site.strip(), **kw)

    def clear(self) -> None:
        with self._lock:
            self._specs.clear()
            self.active = False

    @contextmanager
    def suspended(self):
        """No site fires inside this block (the chaos smoke's clean-run
        baseline; armed specs keep their counters)."""
        with self._lock:
            self._suspended += 1
            self.active = False
        try:
            yield
        finally:
            with self._lock:
                self._suspended -= 1
                self.active = self._suspended == 0 and bool(self._specs)

    # -------------------------------------------------------------- sites

    def fire(self, site: str, **ctx) -> Optional[FaultSpec]:
        """One pass over `site`: returns the firing spec or None.  All
        context-matching specs advance their pass counters; the first
        that decides to fire wins the pass."""
        if not self.active:
            return None
        if site not in self._specs:
            # GIL-atomic dict read: sites with nothing armed stay
            # lock-free even while OTHER sites are
            return None
        with self._lock:
            specs = self._specs.get(site)
            if not specs:
                return None
            hit = None
            for spec in specs:
                if not spec._matches(ctx):
                    continue
                if hit is None:
                    if spec._decide():
                        hit = spec
                else:
                    # the pass happened, but an earlier spec won it:
                    # advance the pass counter WITHOUT spending this
                    # spec's fire budget (`n`) — two specs armed on one
                    # site must inject on two separate passes
                    spec.passes += 1
        if hit is not None:
            _INJECTED.inc()
            _INJECTED_BY_SITE.labels(site).inc()
        return hit

    def maybe_raise(self, site: str, **ctx) -> None:
        spec = self.fire(site, **ctx)
        if spec is not None:
            raise FaultError(site, spec)

    def delay_s(self, site: str, **ctx) -> float:
        """Seconds the caller should stall (0.0 = not firing)."""
        spec = self.fire(site, **ctx)
        if spec is None:
            return 0.0
        return float(spec.args.get("ms", 50)) / 1e3


faults = FaultInjector()
faults.configure(os.environ.get("YTPU_FAULTS"))
