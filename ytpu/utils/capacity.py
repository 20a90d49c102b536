"""Capacity observatory: resident-bytes model + headroom forecaster.

The third leg of the flight recorder (ISSUE-18). PR-17 attributed
*time* (compile vs execute vs transfer); how many rooms of what capacity
a chip holds is a *memory* question. This module owns the host-side
math:

- ``packed_resident_bytes(n_docs, capacity)``: the analytic resident
  size of one state: the 26 column planes of ``DocStateBatch.blocks``
  at 4 bytes a row (PERF.md's "26 planes of 16 MB" at 1,024 rooms x
  4,096 rows) plus 32 words a room. It is what a grow to
  ``capacity`` would have to allocate (capacity doubles per grow).
- ``memory_budget_bytes()``: the device budget the forecaster scores
  against (``YTPU_MEMORY_BUDGET_BYTES``, default 16 GiB of HBM).
- ``HeadroomForecaster``: fed ledger readouts
  (`batch_doc.state_capacity_ledger`'s counts) by whoever owns a state
  — tests alone today (ROADMAP, named debts) — it linearly models
  resident bytes as a function of (docs·capacity, docs, clients) over
  the observed samples (analytic targets by default; a caller with
  measured ``memory_analysis()`` numbers feeds those instead, so the
  model tracks reality, not just the formula) and projects the
  occupancy trend to answer: *will the next grow exceed the budget, and
  in about how many chunks will the watermark force it?* The answer
  flips a degraded ``/capacity`` + ``/healthz`` section before the grow
  is attempted.

Pure host-side arithmetic: no jax imports at module level, no device
syncs, safe to call from the telemetry thread.
"""

from __future__ import annotations

import os
from typing import Dict, List, Optional, Tuple

__all__ = [
    "memory_budget_bytes",
    "packed_resident_bytes",
    "HeadroomForecaster",
    "capacity_report",
]

#: default device budget when the env doesn't pin one: 16 GiB, the
#: per-chip HBM of the TPU generation the flagship shapes target
_DEFAULT_BUDGET_BYTES = 16 << 30


def memory_budget_bytes() -> int:
    """Device memory budget the observatory scores against.
    ``YTPU_MEMORY_BUDGET_BYTES`` overrides (tests pin small budgets to
    make the ceiling reachable on CPU);
    unset/invalid falls back to 16 GiB of HBM."""
    try:
        return int(
            os.environ.get(
                "YTPU_MEMORY_BUDGET_BYTES", str(_DEFAULT_BUDGET_BYTES)
            )
        )
    except ValueError:
        return _DEFAULT_BUDGET_BYTES


def packed_resident_bytes(n_docs: int, capacity: int) -> int:
    """Analytic resident bytes of one state at a given capacity: the 26
    column planes of ``DocStateBatch.blocks`` (``BlockCols``' 26 fields,
    ``[n_docs, capacity]`` each) counted at 4 bytes a row, plus 32 words
    a room for the per-room vectors. Never under what a state holds: two
    of the planes (``deleted``, ``countable``) are stored as bool."""
    return 4 * (26 * n_docs * capacity + 32 * n_docs)


class HeadroomForecaster:
    """Linear resident-bytes model + occupancy-trend headroom forecast.

    ``observe()`` is called from readout drains with the ledger words
    (and optionally a MEASURED resident-bytes sample); ``report()`` is
    called from scrape threads. Both are cheap and lock-free by
    design: observe appends to bounded lists under the GIL, report
    reads a consistent-enough snapshot (a torn read across two appends
    costs one scrape a slightly stale forecast, never an exception).
    """

    #: model features per sample: (docs*capacity, docs, clients, 1)
    N_FEATURES = 4

    def __init__(
        self,
        budget_bytes: Optional[int] = None,
        window: int = 256,
        watermark: float = 0.85,
    ):
        self.budget_bytes = (
            int(budget_bytes)
            if budget_bytes is not None
            else memory_budget_bytes()
        )
        self.window = int(window)
        #: occupancy fraction past which the driver's policy compacts
        #: and, failing that, grows — the horizon the trend projects to
        self.watermark = float(watermark)
        #: (docs, capacity, clients, resident_bytes) model samples
        self._samples: List[Tuple[int, int, int, int]] = []
        #: (chunks, occupied_rows) occupancy trajectory
        self._occ: List[Tuple[int, int]] = []
        self._latest: Optional[Dict] = None
        self._coeffs: Optional[Tuple[float, ...]] = None

    # ------------------------------------------------------------ feeding

    def observe(
        self,
        *,
        n_docs: int,
        capacity: int,
        occupied_rows: int,
        dead_rows: int = 0,
        chunks: int = 0,
        max_capacity: Optional[int] = None,
        clients: int = 0,
        resident_bytes: Optional[int] = None,
    ) -> None:
        """Fold one ledger readout (or one measured sweep point) in.
        ``resident_bytes=None`` targets the analytic model — the fit
        then reproduces the formula; a caller that has them passes the
        MEASURED ``memory_analysis()`` bytes so forecaster-vs-measured
        stays an assertable delta."""
        if resident_bytes is None:
            resident_bytes = packed_resident_bytes(n_docs, capacity)
        self._samples.append(
            (int(n_docs), int(capacity), int(clients), int(resident_bytes))
        )
        if len(self._samples) > self.window:
            del self._samples[: len(self._samples) - self.window]
        self._occ.append((int(chunks), int(occupied_rows)))
        if len(self._occ) > self.window:
            del self._occ[: len(self._occ) - self.window]
        self._coeffs = None  # refit lazily on next model query
        self._latest = {
            "n_docs": int(n_docs),
            "capacity": int(capacity),
            "max_capacity": int(max_capacity or capacity),
            "clients": int(clients),
            "occupied_rows": int(occupied_rows),
            "dead_rows": int(dead_rows),
            "chunks": int(chunks),
            "resident_bytes": int(resident_bytes),
        }

    # ------------------------------------------------------------- model

    def _fit(self) -> Optional[Tuple[float, ...]]:
        """Least-squares coefficients over (docs·capacity, docs,
        clients, 1) → resident bytes; None below 2 samples (the
        analytic formula serves until the model has data)."""
        if self._coeffs is not None:
            return self._coeffs
        samples = list(self._samples)
        if len(samples) < 2:
            return None
        import numpy as np

        A = np.array(
            [[d * c, d, cl, 1.0] for d, c, cl, _ in samples],
            dtype=np.float64,
        )
        y = np.array([b for _, _, _, b in samples], dtype=np.float64)
        try:
            coeffs, *_ = np.linalg.lstsq(A, y, rcond=None)
        except Exception:
            return None
        self._coeffs = tuple(float(x) for x in coeffs)
        return self._coeffs

    def model_bytes(
        self, n_docs: int, capacity: int, clients: int = 0
    ) -> int:
        """Modeled resident bytes for a (docs, capacity, clients)
        point: the fitted linear model when it has data, the analytic
        formula otherwise (and whenever the fit degenerates below
        zero — a rank-deficient sample set can extrapolate wildly)."""
        coeffs = self._fit()
        if coeffs is not None:
            a, b, c, d = coeffs
            est = a * n_docs * capacity + b * n_docs + c * clients + d
            if est > 0:
                return int(est)
        return packed_resident_bytes(n_docs, capacity)

    def growth_rows_per_chunk(self) -> float:
        """Occupancy slope over the observed window (rows/chunk);
        0.0 until two distinct chunk indices exist."""
        occ = list(self._occ)
        if len(occ) < 2:
            return 0.0
        (c0, r0), (c1, r1) = occ[0], occ[-1]
        if c1 <= c0:
            return 0.0
        return (r1 - r0) / float(c1 - c0)

    # ------------------------------------------------------------ report

    def report(self) -> Dict:
        """The `/capacity` section: current + next-grow resident bytes
        vs budget, headroom fraction, occupancy trend, and the
        ``degraded`` flag — True when the NEXT grow would bust the
        budget and the occupancy trend says the watermark (which
        forces that grow) is being approached. ``chunks_to_watermark``
        is the "~N chunks" of the forecast (0 = already past it)."""
        latest = self._latest
        if latest is None:
            return {
                "observed": 0,
                "budget_bytes": self.budget_bytes,
                "degraded": False,
            }
        D = latest["n_docs"]
        cap = latest["capacity"]
        clients = latest["clients"]
        resident = self.model_bytes(D, cap, clients)
        next_cap = min(cap * 2, max(latest["max_capacity"], cap))
        grow_possible = next_cap > cap
        next_grow = (
            self.model_bytes(D, next_cap, clients)
            if grow_possible
            else resident
        )
        headroom = 1.0 - (next_grow / float(self.budget_bytes))
        total_rows = D * cap
        occupied = latest["occupied_rows"]
        rate = self.growth_rows_per_chunk()
        watermark_rows = self.watermark * total_rows
        chunks_to_watermark: Optional[float]
        if occupied >= watermark_rows:
            chunks_to_watermark = 0.0
        elif rate > 0:
            chunks_to_watermark = (watermark_rows - occupied) / rate
        else:
            chunks_to_watermark = None
        grow_exceeds = grow_possible and next_grow > self.budget_bytes
        degraded = bool(grow_exceeds and chunks_to_watermark is not None)
        return {
            "observed": len(self._samples),
            "budget_bytes": self.budget_bytes,
            "resident_bytes": int(resident),
            "next_grow_bytes": int(next_grow),
            "next_grow_capacity": int(next_cap),
            "headroom_fraction": round(headroom, 6),
            "occupancy_fraction": round(
                occupied / float(max(total_rows, 1)), 6
            ),
            "dead_rows": latest["dead_rows"],
            "growth_rows_per_chunk": round(rate, 4),
            "chunks_to_watermark": (
                None
                if chunks_to_watermark is None
                else round(chunks_to_watermark, 2)
            ),
            "grow_exceeds_budget": bool(grow_exceeds),
            "degraded": degraded,
        }

    def provider(self):
        """Closure for ``TelemetryServer.add_health_provider`` /
        ``add_capacity_provider`` (register under ``"capacity"``) —
        the report's ``degraded`` key flips `/healthz` the same way
        the compile-storm provider does."""
        return self.report


def capacity_report(
    forecasters: Optional[Dict[str, HeadroomForecaster]] = None,
) -> Dict:
    """One-call `/capacity` body: per-forecaster sections plus the
    phase recorder's per-program device-memory peak ledger (empty when
    ``YTPU_PHASES`` is off — memory attribution rides the compile
    sentinel's first-sighting path)."""
    from ytpu.utils.phases import phases

    out: Dict = {"memory": phases.memory_report()}
    for name, fc in (forecasters or {}).items():
        out[name] = fc.report()
    return out
