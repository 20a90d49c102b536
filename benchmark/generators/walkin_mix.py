"""Writers the server was not told of: every session walks in with the id
Yjs drew for it, and half of what it types goes at the end of the room.

A `Y.Doc` draws its `clientID` with `random.uint32()` when it is made, and
y-sync's handshake does not announce it: the first a server hears of a
writer is the writer's first update. So this generator hands the harness no
traffic client to intern (`Plan.clients` holds the prefill templates' ids
and nothing else; the warm-up's typists keep the ids `warmup.py` and
`oracle.py` fix for them, and walk in during the warm-up like everyone).

A traffic file is this generator's parameters. `sessions`,
`edits_per_session`, `zipf_s`, `tick_max_frames`, `warm_sessions`, `arrival`
(`saturated` only) and `repeat` are `session_mix`'s, and so are the rooms'
quotas, the edit grammar and the fixed trace (`grammar.LAYOUT` draws every
shape, the ids among them; the seed types the characters). Its own:

    client_ids    "yjs-uint32": one id a session, uniform over [1, 2**32),
                  distinct, redrawn where it falls among the ids the
                  oracle's filter and the templates keep (600,000-900,063).
                  About half lie past 2**31 - 1
    tail_share    share of a session's inserts that go at the end of the
                  document as the session holds it (its room's prefill and
                  its own edits); the rest at a random position. A room's
                  tail writers share an origin, so the server orders them
                  by client id: the YATA rule
    session_span  a session's edits lie within this share of the pool, and
                  the sessions' first edits are evenly spaced over the rest
                  of it, in a `LAYOUT`-drawn order: writers walk in all
                  through the window (`walk_in`)

Sessions do not apply the room's broadcasts, as in `session_mix`: B3.4's
shape (yrs `benches.rs`), every writer concurrent with every other.

A program whose lookup tables take their shape from the writers it knows
cannot serve this deployment: `plan` refuses it (`tables_hold_their_shape`).
"""

from __future__ import annotations

import math
import os
from typing import Dict, List, Optional, Sequence

import numpy as np

from benchmark import grammar as g
from benchmark.generators.session_mix import _update_ops
from benchmark.ops import Plan

# ids no traffic session may draw: the oracle's per-room comparison drops
# the warm-up typists' range, and the templates' ids follow it
RESERVED = range(g.WARM_CLIENT_BASE, g.TEMPLATE_CLIENT_BASE + 64)
ANYWAY = "BENCH_WALKIN_ANYWAY"  # set: `plan` serves a program that `tables_hold_their_shape` refuses
ROOM_GAP = 0.9  # of a room's mean distance between frames: the least distance `walk_in` leaves


def tables_hold_their_shape() -> bool:
    """Does a first-seen writer leave the shape of the decoder's lookup
    tables as it was? Asked of a one-room ingestor, before and after a
    second writer. Where it does not, the decode program specializes on the
    number of writers, so every dispatch that meets one builds a program
    inside the window: 29 builds in 30 dispatches and 1.7 updates/s on the
    tree before PR 35 (PERF.md section 6), which measures the compiler."""
    from ytpu.models.ingest import BatchIngestor

    ing = BatchIngestor(1, 8)
    shapes = []
    for client in (1, 2):
        ing.enc.interner.intern(client)
        shapes.append([np.shape(a) for a in ing._decode_tables()["client_table"]])
    return shapes[0] == shapes[1]


def draw_client_ids(n: int, stream: str) -> List[int]:
    """`n` distinct ids as `Doc.clientID = random.uint32()` draws them, from
    the layout: the same for every seed."""
    r = g.rng(g.LAYOUT, stream, "client_ids")
    out: List[int] = []
    taken = set()
    while len(out) < n:
        c = r.randrange(1, 2**32)
        if c in RESERVED or c in taken:
            continue
        taken.add(c)
        out.append(c)
    return out


class TailTypist(g.Typist):
    """`grammar.Typist` that puts `tail_share` of its inserts at the end of
    the document as it holds it; every other draw is the base's."""

    def __init__(self, *args, tail_share: float = 0.0, **kwargs):
        super().__init__(*args, **kwargs)
        self.tail_share = tail_share

    def next_edit(self, word_len: Optional[int] = None, delete: Optional[bool] = None) -> g.Edit:
        r, ids = self.r, self.ids
        if delete is None:
            delete = word_len is None and self.can_delete() and r.random() < self.p_delete
        if delete:
            return super().next_edit(delete=True)
        n = word_len if word_len is not None else r.randint(3, 8)
        word = "".join(self.text.choice(g.ALPHABET) for _ in range(n))
        pos = self.length if r.random() < self.tail_share else r.randint(0, self.length)
        at = self._at(pos - 1) + 1 if pos else 0  # right after the visible character left of the cursor
        block = g.Block(self.clock, ids[at - 1] if at else None, ids[at] if at < len(ids) else None, word)
        update = g.encode_update(self.client_id, [block], {})
        ids[at:at] = [(self.client_id, self.clock + i) for i in range(n)]
        self.dead = [d + n if d >= at else d for d in self.dead]
        self.clock += n
        self.clocks[self.client_id] = self.clock
        return g.Edit(update, g.encode_sv(self.clocks), n)


def build_sessions(n_rooms: int, client_ids: Sequence[int], edits_per_session: int, zipf_s: float,
                   seed: int, prefill, tail_share: float, stream: str = "traffic") -> List[g.Session]:
    """`grammar.build_sessions` (the same rooms for the same stream) with the
    given client ids and tail typists."""
    quotas = g.zipf_quotas(n_rooms, len(client_ids), zipf_s)
    rooms = [k for k, q in enumerate(quotas) for _ in range(q)]
    g.rng(g.LAYOUT, stream, "rooms").shuffle(rooms)
    out = []
    for i, client in enumerate(client_ids):
        tpl = prefill.for_room(rooms[i])
        t = TailTypist(client, g.rng(g.LAYOUT, stream, "session", i), tpl.ids, {tpl.client_id: tpl.chars},
                       text=g.rng(seed, stream, "text", i), tail_share=tail_share)
        out.append(g.Session(i, rooms[i], client, [t.next_edit() for _ in range(edits_per_session)]))
    return out


def walk_in(sessions: Sequence[g.Session], span_share: float, tick: int, stream: str = "traffic") -> List[tuple]:
    """(session index, edit index) in pool order: every session's own order
    kept, its edits within `span_share` of the pool, and the sessions' first
    edits evenly spaced over the rest of the pool in a layout-drawn order.

    The pool is filled slot by slot. A session's first edit sits at its
    start slot. Any other slot goes to the open session that can wait
    least: whose last slot (its start plus the span), less one `tick` for
    each edit it still owes, comes first. Passed over are a session that
    sent within the last `tick` slots (two frames of one session in a tick
    are two dispatches) and, unless it can no longer wait, one whose room
    had a frame within `ROOM_GAP` of the room's mean distance between
    frames (how many frames of the hottest room meet in a tick sets how
    many dispatches the tick costs: `grammar.interleave`). A session whose
    edits left equal its slots left takes the slot whatever was passed
    over. Starts outrun the slots they leave free until the last start, so
    a session waits the longer the later it walks in, and the last tenth of
    the pool is handed out by deadline alone."""
    n = len(sessions)
    per = len(sessions[0].edits)
    if n < 2 or per < 2 or any(len(s.edits) != per for s in sessions):
        raise ValueError("walk_in needs two sessions or more, each with the same two edits or more")
    size = n * per
    span = math.ceil(span_share * size)
    last_start = math.ceil((1.0 - span_share) * size) - 1
    order = list(range(n))
    g.rng(g.LAYOUT, stream, "walk_in").shuffle(order)
    start = np.zeros(n, dtype=np.int64)
    for k, i in enumerate(order):
        start[i] = round(k * last_start / (n - 1))
    starts_at = {int(slot): i for i, slot in enumerate(start)}
    if len(starts_at) != n:
        raise ValueError(f"{n} sessions cannot each start in a slot of their own among {last_start + 1}")
    rooms = np.asarray([s.room for s in sessions])
    n_rooms = int(rooms.max()) + 1
    frames = np.bincount(rooms, minlength=n_rooms) * per
    room_gap = np.minimum(tick, np.floor(ROOM_GAP * size / np.maximum(frames, 1))).astype(np.int64)
    deadline = np.minimum(start + span - 2, size - 1)  # two slots to spare: two sessions can run out at once
    owed = np.zeros(n, dtype=np.int64)
    sent_at = np.full(n, -size)
    room_at = np.full(n_rooms, -size)
    pool: List[tuple] = []
    for slot in range(size):
        i = starts_at.get(slot)
        if i is None:
            open_ = np.nonzero(owed)[0]
            if not len(open_):
                raise ValueError(f"slot {slot} of the pool has no open session to fill it")
            out_of_slots = open_[deadline[open_] - slot + 1 <= owed[open_]]
            if len(out_of_slots):
                i = int(out_of_slots[np.argmin(deadline[out_of_slots])])
            else:
                can_wait = deadline[open_] - owed[open_] * tick
                rested = slot - sent_at[open_] >= tick
                room_rested = slot - room_at[rooms[open_]] >= room_gap[rooms[open_]]
                ok = rested & (room_rested | (can_wait <= slot))
                if not ok.any():
                    ok = room_rested if room_rested.any() else np.ones(len(open_), dtype=bool)
                i = int(open_[np.argmin(np.where(ok, can_wait, np.iinfo(np.int64).max))])
            owed[i] -= 1
        else:
            owed[i] = per - 1
        pool.append((i, per - 1 - int(owed[i])))
        sent_at[i] = room_at[rooms[i]] = slot
    if owed.any():
        raise ValueError("walk_in left edits unplaced")
    return pool


def plan(deploy: dict, mix: dict, prefill, seed: int, seconds: float) -> Plan:
    if mix["arrival"] != "saturated" or mix.get("client_ids") != "yjs-uint32":
        raise ValueError("walkin_mix makes a saturated pool of updates from yjs-uint32 writers")
    if not tables_hold_their_shape() and not os.environ.get(ANYWAY):
        raise SystemExit(
            "bench: this program sizes its lookup tables by the writers it knows, so each unregistered writer "
            f"rebuilds the decode program inside the window; it cannot serve this deployment ({ANYWAY}=1 runs it all the same)")
    n_rooms = deploy["n_docs"]
    n_sessions = mix["sessions"]
    tick = mix["tick_max_frames"]
    tail = mix["tail_share"]
    sessions = build_sessions(n_rooms, draw_client_ids(n_sessions, "traffic"), mix["edits_per_session"],
                              mix["zipf_s"], seed, prefill, tail)
    ops = _update_ops(sessions, walk_in(sessions, mix["session_span"], tick))

    # warm-up, as `session_mix` lays it out: the first `tick` warm sessions
    # sit in distinct rooms (the harness drives its S-sweep through them);
    # the rest send this mix, two edits each
    n_own = mix.get("warm_sessions", 0)
    sweep_rooms = [(n_rooms // 2 + w) % n_rooms for w in range(min(tick, n_rooms))]
    own_base = g.WARM_CLIENT_BASE + len(sweep_rooms)
    own = build_sessions(n_rooms, range(own_base, own_base + n_own), 2, mix["zipf_s"], seed, prefill, tail,
                         stream="warm") if n_own else []
    own_ops = _update_ops(own, g.interleave(own, g.LAYOUT, "warm"))
    for op in own_ops:  # warm sessions are numbered after the sweep's
        op.session += len(sweep_rooms)
    warm = [own_ops[i : i + tick] for i in range(0, len(own_ops), tick)]

    lens = [len(op.update) for op in ops[:4096]]
    # the pool is taken in ticks of exactly `tick` frames, whatever the
    # server's speed, so the lane counts of its dispatches are known
    lane_counts = set()
    for i in range(0, len(ops), tick):
        per_room: Dict[int, int] = {}
        for op in ops[i : i + tick]:
            per_room[op.room] = per_room.get(op.room, 0) + 1
        for depth in range(1, max(per_room.values()) + 1):
            lane_counts.add(sum(1 for n in per_room.values() if n >= depth))
    return Plan(
        clients=[t.client_id for t in prefill.templates],  # and no writer: they walk in
        session_rooms=[s.room for s in sessions],
        preload=[],
        warm=warm,
        warm_session_rooms=sweep_rooms + [s.room for s in own],
        ops=ops,
        saturated=True,
        repeat=bool(mix.get("repeat", False)),
        tick_max_frames=tick,
        sessions=sessions,
        notes={
            "update_len_min": min(lens), "update_len_max": max(lens),
            "update_len_mean": sum(lens) / len(lens),
            "hot_room_sessions": max(g.zipf_quotas(n_rooms, n_sessions, mix["zipf_s"])),
            "needs_sync_warm": False,
            "needs_update_warm": True,
            "lane_counts": sorted(lane_counts),
        },
    )
