"""Seeded, replayable serving-traffic scenarios (ISSUE-9 tentpole).

A replay pushes one big trace through an engine.  A serving system is
driven by *sessions*:
many concurrent clients fanning mixed apply / diff / awareness traffic at
a multi-tenant server, with hot documents, a long tail, churn and
reconnects.  `Scenario` generates that traffic as a deterministic event
schedule:

- **Replayable grammar.**  Every random draw derives from the config's
  ``seed`` (plus the ``round`` index for multi-round soaks): per-session
  streams come from per-session RNGs keyed ``(seed, round, session)``,
  the interleave from its own RNG — so the same config generates the
  byte-identical schedule every time, on every host (`digest()` is the
  assertion surface).  Determinism is what makes soak parity checkable:
  a clean run and a checkpoint/restore + rebalance run of the same
  scenario must land byte-equal tenant states.
- **Zipf tenant skew.**  Sessions pick their tenant from a Zipf(s)
  distribution over the tenant index: tenant 0 is the hot doc, the tail
  is cold — the shape that makes per-tenant admission control and the
  slot rebalance non-trivial.
- **CRDT-honest updates.**  Each session owns a real client `Doc` (a
  stable ``client_id``) and edits a shared text root; apply events carry
  the genuine wire update bytes those edits produce.  Sessions never see
  each other at generation time, so each session's byte stream depends
  only on its own ops — and CRDT convergence makes the server's final
  tenant state a pure function of the delivered update SET, independent
  of interleaving, flush timing, retries, or mid-soak failover.

Event kinds (the ``payload`` is raw domain bytes; the driver wraps them
in protocol frames):

====================  ========================================================
``apply``             one V1 wire update (this session's next edit)
``diff``              a SyncStep1 read: payload = the session's state vector
                      (as of this point in its own stream), encoded
``awareness``         an encoded `AwarenessUpdate` for this session's client
``reconnect``         churn: drop the session and reconnect (PR-6's
                      resync-on-reconnect path); no payload
====================  ========================================================
"""

from __future__ import annotations

import hashlib
import random
import zlib
from dataclasses import dataclass, replace
from typing import Dict, Iterator, List, NamedTuple, Optional

from ytpu.core import Doc

__all__ = ["Event", "ScenarioConfig", "Scenario"]


class Event(NamedTuple):
    seq: int
    session: int
    tenant: str
    kind: str  # "apply" | "diff" | "awareness" | "reconnect"
    payload: Optional[bytes]


@dataclass(frozen=True)
class ScenarioConfig:
    n_tenants: int = 3
    n_sessions: int = 12
    events_per_session: int = 10
    seed: int = 0
    round: int = 0  # multi-round soaks bump this for fresh deterministic traffic
    zipf_s: float = 1.2  # tenant skew (higher = hotter hot doc)
    p_diff: float = 0.12
    p_awareness: float = 0.12
    p_reconnect: float = 0.06
    p_delete: float = 0.25
    client_base: int = 7000  # session i -> client_id base + round*n_sessions + i
    root: str = "text"


class _SessionScript(NamedTuple):
    sid: int
    tenant: str
    client_id: int
    events: List  # [(kind, payload)]


def _rng(*key) -> random.Random:
    """Deterministic RNG keyed by a tuple (stable across processes —
    `random.Random(str)` hashing is salted per process, crc32 is not)."""
    return random.Random(zlib.crc32(":".join(map(str, key)).encode()))


class Scenario:
    """One deterministic traffic schedule for a multi-tenant server."""

    def __init__(self, config: ScenarioConfig):
        self.config = config
        self._scripts = [
            self._build_session(i) for i in range(config.n_sessions)
        ]
        self._schedule = self._interleave()

    # --- generation -----------------------------------------------------------

    def _zipf_tenant(self, rng: random.Random) -> str:
        cfg = self.config
        weights = [1.0 / (k + 1) ** cfg.zipf_s for k in range(cfg.n_tenants)]
        total = sum(weights)
        r = rng.random() * total
        for k, w in enumerate(weights):
            r -= w
            if r <= 0:
                return f"tenant{k}"
        return f"tenant{cfg.n_tenants - 1}"

    def _build_session(self, i: int) -> _SessionScript:
        cfg = self.config
        rng = _rng(cfg.seed, cfg.round, "session", i)
        tenant = self._zipf_tenant(rng)
        client_id = cfg.client_base + cfg.round * cfg.n_sessions + i
        doc = Doc(client_id=client_id)
        captured: List[bytes] = []
        doc.observe_update_v1(lambda p, o, t: captured.append(p))
        txt = doc.get_text(cfg.root)
        length = 0
        events: List = []
        aw_clock = 0
        for k in range(cfg.events_per_session):
            r = rng.random()
            # the first event is always an apply so every session
            # contributes state (and the parity oracle is never vacuous)
            if k > 0 and r < cfg.p_diff:
                events.append(("diff", doc.state_vector().encode_v1()))
                continue
            if k > 0 and r < cfg.p_diff + cfg.p_awareness:
                from ytpu.sync.awareness import (
                    AwarenessUpdate,
                    AwarenessUpdateEntry,
                )

                aw_clock += 1
                json = '{"s":%d,"k":%d}' % (i, k)
                up = AwarenessUpdate(
                    {client_id: AwarenessUpdateEntry(aw_clock, json)}
                )
                events.append(("awareness", up.encode_v1()))
                continue
            if k > 0 and r < cfg.p_diff + cfg.p_awareness + cfg.p_reconnect:
                events.append(("reconnect", None))
                continue
            # apply: one deterministic text edit on the session's own doc
            with doc.transact() as txn:
                if length > 8 and rng.random() < cfg.p_delete:
                    pos = rng.randint(0, length - 4)
                    n = rng.randint(1, 3)
                    txt.remove_range(txn, pos, n)
                    length -= n
                else:
                    word = "".join(
                        rng.choice("abcdefghij")
                        for _ in range(rng.randint(3, 8))
                    )
                    txt.insert(txn, rng.randint(0, length), word)
                    length += len(word)
            events.append(("apply", captured[-1]))
        return _SessionScript(i, tenant, client_id, events)

    def _interleave(self) -> List[Event]:
        """Merge the per-session streams into one deterministic schedule
        (weighted-random pick among sessions with events remaining —
        order within a session is preserved, which CRDT causality needs:
        a session's update k+1 depends on its update k)."""
        rng = _rng(self.config.seed, self.config.round, "interleave")
        cursors = [0] * len(self._scripts)
        live = [s.sid for s in self._scripts if s.events]
        out: List[Event] = []
        seq = 0
        while live:
            sid = live[rng.randrange(len(live))]
            script = self._scripts[sid]
            kind, payload = script.events[cursors[sid]]
            cursors[sid] += 1
            out.append(Event(seq, sid, script.tenant, kind, payload))
            seq += 1
            if cursors[sid] >= len(script.events):
                live.remove(sid)
        return out

    # --- consumption ----------------------------------------------------------

    @property
    def sessions(self) -> List[_SessionScript]:
        return self._scripts

    @property
    def tenants(self) -> List[str]:
        return sorted({s.tenant for s in self._scripts})

    def events(self) -> Iterator[Event]:
        return iter(self._schedule)

    def __len__(self) -> int:
        return len(self._schedule)

    def owner_shards(self, n_replicas: int) -> Dict[str, int]:
        """Deterministic tenant → replica-shard assignment (ISSUE-13):
        round-robin over the sorted tenant list, so the Zipf-hot
        `tenant0` and its tail spread across the mesh the same way on
        every host.  The federated soak maps shard ``k`` to its k-th
        alive replica (hot-doc ownership sharding)."""
        if n_replicas < 1:
            raise ValueError("need at least one replica")
        return {t: i % n_replicas for i, t in enumerate(self.tenants)}

    def with_round(self, round_: int) -> "Scenario":
        """The same grammar, fresh deterministic traffic (new client ids,
        new edits) — multi-round soaks call this per round."""
        return Scenario(replace(self.config, round=round_))

    def digest(self) -> str:
        """SHA-256 over the full event schedule (the byte-determinism
        assertion surface: same config ⇒ same digest, everywhere)."""
        h = hashlib.sha256()
        for ev in self._schedule:
            h.update(
                f"{ev.seq}|{ev.session}|{ev.tenant}|{ev.kind}|".encode()
            )
            h.update(ev.payload or b"-")
        return h.hexdigest()

    def expected_texts(self) -> Dict[str, str]:
        """The parity oracle: per tenant, the text a host doc reaches
        after applying every session's apply payloads (any order — CRDT
        convergence makes the merge order irrelevant)."""
        out: Dict[str, str] = {}
        for tenant in self.tenants:
            doc = Doc(client_id=1)
            for script in self._scripts:
                if script.tenant != tenant:
                    continue
                for kind, payload in script.events:
                    if kind == "apply":
                        doc.apply_update_v1(payload)
            out[tenant] = doc.get_text(self.config.root).get_string()
        return out
