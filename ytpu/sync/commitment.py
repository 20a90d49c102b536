"""Incrementally-updatable per-tenant state commitments (ISSUE-13).

Anti-entropy at federation scale needs cheaper convergence checks than
flushing and comparing full state: with N replicas and T tenants every
round would otherwise render T texts per replica.  Following the Vector
Commitments with Efficient Updates direction (PAPERS.md), each replica
maintains a per-tenant **homomorphic digest of the op lattice** — one
integer a peer can compare in O(1) per tenant per round, updated in
O(delta) as ops integrate, never by walking state.

The commitment is an additive (mod 2^64) fold over clock units: client
``c``'s lattice ``[0, n_c)`` contributes ``A(c)·T(n_c) + B(c)·n_c``
where ``A``/``B`` are per-client mixed constants and ``T(n) = n(n-1)/2``
(the closed form of ``Σ_{j<n} (A(c)·j + B(c))``).  Additivity over
disjoint clock ranges is what makes it *incrementally updatable*: a
delta ``[old, new)`` folds in as ``A·(T(new)−T(old)) + B·(new−old)``
without revisiting history, and the same value is reached regardless of
how the ops were chunked, split, or merged on the way in.

The device twin (``batch_doc.commit_fold_blocks``) computes the
identical fold, 32-bit over the block columns, as a vectorized
reduction — per-block ``A(c)·(s·l + T(l)) + B(c)·l``
sums to the per-client closed form exactly because block rows tile the
lattice (splits/merges/GC conversions preserve ``(client, clock, len)``
coverage).  ``device_commit_of_clocks`` is its pure-Python oracle.

What the commitment can and cannot detect (docs/serving.md §Federation):
it covers the **op lattice** — any replica that missed, dropped, or
fabricated ops disagrees — but NOT content bytes behind an intact
lattice, and NOT tombstone-set divergence between replicas whose SVs
already agree (y-sync step2 ships the full delete set, so that requires
a lost partial delivery).  A mismatch that survives a converged sync is
therefore a *state-tracking* fault — `replica.DivergenceFault` — not a
sync gap.

The ``commit.corrupt`` fault site (docs/robustness.md) fires inside the
incremental fold, XORing one delta: the poisoned tracker disagrees with
every peer forever after (incremental state, nothing re-derives it),
which is exactly the silent-divergence shape the anti-entropy check
exists to catch.  ``recompute`` is the recovery: an authoritative
rebuild from the current state vector.
"""

from __future__ import annotations

from typing import Dict, Iterable, Mapping, Tuple

from ytpu.utils.faults import faults

__all__ = [
    "MASK32",
    "MASK64",
    "TenantCommitments",
    "commitment_of_clocks",
    "device_commit_of_clocks",
    "lattice_term",
    "mix32",
    "mix64",
    "tri",
]

MASK32 = 0xFFFFFFFF
MASK64 = 0xFFFFFFFFFFFFFFFF

#: XOR mask an armed ``commit.corrupt`` spec applies to one incremental
#: delta (overridable per spec via ``xor=``) — any nonzero value works;
#: this one is visible in hex dumps
CORRUPT_XOR = 0x9E3779B97F4A7C15


def tri(n: int) -> int:
    """T(n) = n(n-1)/2 — the sum of clocks below ``n`` (exact int)."""
    return n * (n - 1) // 2


def mix64(x: int) -> int:
    """splitmix64 finalizer: the per-client parameter generator for the
    64-bit host commitment."""
    x = (x + 0x9E3779B97F4A7C15) & MASK64
    x = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) & MASK64
    x = ((x ^ (x >> 27)) * 0x94D049BB133111EB) & MASK64
    return (x ^ (x >> 31)) & MASK64


def mix32(x: int) -> int:
    """32-bit finalizer — MUST stay bit-identical to the jnp/uint32 mix
    in ``batch_doc.commit_fold_blocks`` (the device readout word); this
    is its host-side oracle."""
    x &= MASK32
    x = ((x ^ (x >> 16)) * 0x7FEB352D) & MASK32
    x = ((x ^ (x >> 15)) * 0x846CA68B) & MASK32
    return (x ^ (x >> 16)) & MASK32


def _params64(client: int) -> Tuple[int, int]:
    return mix64(2 * client + 1), mix64(2 * client + 2)


def lattice_term(client: int, lo: int, hi: int) -> int:
    """Contribution of client ``client``'s clock range ``[lo, hi)`` to
    the 64-bit commitment — additive over disjoint ranges."""
    a, b = _params64(client)
    return (a * (tri(hi) - tri(lo)) + b * (hi - lo)) & MASK64


def commitment_of_clocks(clocks: Mapping[int, int]) -> int:
    """Full (non-incremental) 64-bit commitment of a state vector,
    given as ``{client_id: clock}`` — the authoritative rebuild the
    incremental tracker must always agree with."""
    total = 0
    for client, clock in clocks.items():
        total = (total + lattice_term(client, 0, clock)) & MASK64
    return total


def device_commit_of_clocks(clocks: Mapping[int, int]) -> int:
    """Pure-Python oracle of the DEVICE commitment word
    (`batch_doc.commit_fold_blocks`): the 32-bit fold
    ``Σ_c mix32(2c+1)·T(n_c) + mix32(2c+2)·n_c`` over the state's
    client id space (raw ids under an identity rank, interned indices
    on the ingest path)."""
    total = 0
    for client, clock in clocks.items():
        a = mix32(2 * client + 1)
        b = mix32(2 * client + 2)
        total = (total + a * tri(clock) + b * clock) & MASK32
    return total


class TenantCommitments:
    """One replica's per-tenant incremental commitment trackers.

    ``refresh(tenant, sv)`` folds the state-vector delta since the last
    call in O(changed clients) and returns the current commitment — the
    value a `ReplicaMesh` anti-entropy round exchanges.  The fold is the
    ``commit.corrupt`` injection site: a fired spec XORs the delta, so
    the tracker silently diverges from its own state (the fault the
    commitment check must catch; a recompute would mask it).
    """

    def __init__(self) -> None:
        self._clocks: Dict[str, Dict[int, int]] = {}
        self._commit: Dict[str, int] = {}

    def get(self, tenant: str) -> int:
        return self._commit.get(tenant, 0)

    def refresh(self, tenant: str, sv: Iterable[Tuple[int, int]]) -> int:
        """Fold ``sv`` (iterable of ``(client, clock)`` — a
        `StateVector` iterates that way) into the tracker; returns the
        commitment.  Clocks only grow under CRDT sync; a clock that
        went BACKWARD (restored-from-checkpoint server) forces an
        authoritative recompute instead of folding garbage."""
        clocks = self._clocks.setdefault(tenant, {})
        items = list(sv)
        if any(clock < clocks.get(client, 0) for client, clock in items):
            return self.recompute(tenant, items)
        delta = 0
        for client, clock in items:
            old = clocks.get(client, 0)
            if clock > old:
                delta = (delta + lattice_term(client, old, clock)) & MASK64
                clocks[client] = clock
        if delta:
            if faults.active:
                spec = faults.fire("commit.corrupt", tenant=tenant)
                if spec is not None:
                    delta ^= int(spec.args.get("xor", CORRUPT_XOR)) & MASK64
            self._commit[tenant] = (
                self._commit.get(tenant, 0) + delta
            ) & MASK64
        return self._commit.get(tenant, 0)

    def recompute(self, tenant: str, sv: Iterable[Tuple[int, int]]) -> int:
        """Authoritative rebuild from scratch — the recovery path for a
        quarantined (divergent) tenant: discards any poisoned
        incremental state."""
        clocks = {client: clock for client, clock in sv}
        self._clocks[tenant] = dict(clocks)
        self._commit[tenant] = commitment_of_clocks(clocks)
        return self._commit[tenant]

    def forget(self, tenant: str) -> None:
        self._clocks.pop(tenant, None)
        self._commit.pop(tenant, None)
