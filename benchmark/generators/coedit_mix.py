"""Rooms whose sessions apply the room's broadcasts and edit what the others
typed: co-editing, the shape of yrs `benches.rs` B2 widened from two peers to
a room's sessions.

In every other mix a session is synced with its room's prefilled document and
never sees another's edit. Here a session holds a **replica**: the room's
prefill, its own edits, and the room's updates up to a horizon a few frames
behind its own place in the pool (`see_lag`: what a peer has not yet been
sent, or has not yet applied, while the backlog drains; a replica never goes
back). An edit is `Scenario`'s grammar, as in `edit-flood` (an insert of 3-8
characters or, one in four, a delete of 1-3; one transaction, one wire
update, byte for byte what a Yjs client holding that replica sends:
`benchmark/tests/test_coedit_mix.py`), so its origin, right origin and delete
ranges name whatever stands beside the cursor in the replica, another
session's characters and tombstones included. Edits of a room within each
other's lag are concurrent.

    follow_share  of the inserts go right after the last character of the
                  newest insert *by another session* that the replica holds
                  (the line the room is working on), and of the deletes start
                  inside that insert's characters; the rest fall at a
                  position uniform over the document as the replica holds
                  it. A session alone in its room follows nobody.
    twin_share    of the sessions of rooms with two or more are two tabs of
                  one browser (pairs, rank order, fixed): a tab holds its
                  twin's edits at once (the provider's BroadcastChannel),
                  and the two share one horizon.
    early_share   of a twin's edits are made on top of the twin's newest
                  edit, where that is an insert (right after it, or a delete
                  inside it, as `p_delete` has it), and handed to the server
                  `early_by` of the room's frames **before** that edit of
                  the twin's: the faster of two sockets. The server must
                  stash it and integrate it when the twin's update comes.
                  Paced, as a room's frames are: a twin edit is early when
                  its room is behind its share and the move is possible.
                  Every other update is causally ready when handed over.

**One shared sequence a room, a horizon a session.** A replica is not a copy
of a document: the room keeps one `yata_plain.Text` that integrates every
update as it is made, and beside it, in document order, for every character
the frame after which a replica that takes the room's frames in order holds
it (`born`), who typed it (`by`) and the first frame that deleted it
(`gone`). A session's replica is the characters born before its horizon, its
own and its twin's, less those gone before its horizon or deleted by itself
or its twin: a sequence CRDT keeps every replica's order a subsequence of the
room's. The frame a replica holds an update after is the later of its own
place and those of what it names (an early update is held when its twin's
has come), so every replica is causally closed and an update that is not
early is ready at its place.

The shapes (who sits where, twins, every edit's kind, place, lag, position
and length) come from `grammar.LAYOUT`; `--seed` types the characters.

A traffic file (`benchmark/traffic/<mix>.json`) gives `sessions`,
`edits_per_session`, `zipf_s`, `tick_max_frames`, `p_delete`, `see_lag`
("lo-hi", frames), `follow_share`, `twin_share`, `early_share`, `early_by`
("lo-hi", frames), `warm_sessions` (disjoint sessions that co-edit the same
way before the window, a universe of their own: the window's sessions synced
before it) and `warm_edits_per_session`.
"""

from __future__ import annotations

from types import SimpleNamespace
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from benchmark import grammar as g
from benchmark import warmup
from benchmark import yata_plain as yp
from benchmark.generators.keystroke_mix import family  # what keys the fast lane's programs for one dispatch
from benchmark.ops import Op, Plan

NEVER = 1 << 30  # a frame that does not come
CLOCK_BITS = 40
LANE_MAX = warmup.LANE_MAX
OVERHEAD = 24  # bytes of a warm typist's insert beyond its word, about


def _span(text: str) -> Tuple[int, int]:
    lo, hi = text.split("-")
    return int(lo), int(hi)


def _keys(ids: Sequence[g.Id]) -> np.ndarray:
    return np.fromiter(((c << CLOCK_BITS) | k for c, k in ids), np.int64, len(ids))


def _id(key: int) -> g.Id:
    return (int(key) >> CLOCK_BITS, int(key) & ((1 << CLOCK_BITS) - 1))


class Room:
    """One room's shared sequence and what each replica holds of it."""

    def __init__(self, doc: yp.Text, keys: np.ndarray, n_frames: int):
        self.doc = doc.copy()
        self.key = keys.copy()  # the characters' ids, in document order
        n = len(keys)
        self.born = np.full(n, -1, np.int32)  # frame after which a replica in step with the room holds it
        self.by = np.full(n, -1, np.int32)  # session that typed it (-1: the prefill)
        self.gone = np.full(n, NEVER, np.int32)  # first frame after which it is deleted
        self.inserts: List[tuple] = []  # (session, born, first id's key, length), as made
        self.horizon: Dict[int, int] = {}  # session (a pair: its lower index) -> frames it has applied
        self.deleted_by: Dict[int, List[int]] = {}  # session -> keys it deleted itself
        self.ready = [NEVER] * n_frames  # by arrival place: the frame after which the update is integrated

    def replica(self, i: int, twin: Optional[int], h: int) -> Tuple[np.ndarray, np.ndarray]:
        """(held, shown) of session `i` with horizon `h`: the characters its
        replica holds, tombstones included, and those it shows."""
        held = self.born < h
        held |= self.by == i
        dead = self.gone < h
        own = self.deleted_by.get(i, [])
        if twin is not None:
            held |= self.by == twin
            own = own + self.deleted_by.get(twin, [])
        if own:
            dead |= np.isin(self.key, own)
        return held, held & ~dead

    def newest_insert(self, i: int, twin: Optional[int], h: int, of: Optional[int] = None) -> Optional[tuple]:
        """The newest insert of session `of`, or of any session but `i` that
        the replica holds."""
        if of is not None:
            return next(x for x in reversed(self.inserts) if x[0] == of)
        return next((x for x in reversed(self.inserts) if x[0] != i and (x[1] < h or x[0] == twin)), None)

    def foreign(self, i: int, named: Sequence[int]) -> bool:
        return any(self.by[x] >= 0 and self.by[x] != i for x in named)

    def typed(self, i: int, client: int, clock: int, origin, right_origin, word: str, ready: int) -> None:
        at = self.doc.insert_block(client, clock, origin, right_origin, word)
        n, first = len(word), (client << CLOCK_BITS) | clock
        self.key = np.insert(self.key, at, np.arange(first, first + n, dtype=np.int64))
        self.born = np.insert(self.born, at, np.full(n, ready, np.int32))
        self.by = np.insert(self.by, at, np.full(n, i, np.int32))
        self.gone = np.insert(self.gone, at, np.full(n, NEVER, np.int32))
        self.inserts.append((i, ready, first, n))

    def deleted(self, i: int, named: Sequence[int], ranges: Dict[int, List[Tuple[int, int]]], ready: int) -> None:
        for c, runs in ranges.items():
            for start, length in runs:
                self.doc.delete_range(c, start, length)
        self.gone[named] = np.minimum(self.gone[named], ready)
        self.deleted_by.setdefault(i, []).extend(int(self.key[x]) for x in named)


class Made:
    """What `build` hands back for one stream of sessions."""

    def __init__(self):
        self.sessions: List[g.Session] = []
        self.order: List[Tuple[int, int]] = []  # (session, edit) as handed to the server
        self.room_of: List[int] = []
        self.place: Dict[Tuple[int, int], int] = {}  # (session, edit) -> its place among its room's frames
        self.ready: Dict[Tuple[int, int], int] = {}  # -> the frame of its room after which it is integrated
        self.twin: Dict[int, int] = {}
        self.log: Dict[Tuple[int, int], tuple] = {}  # (session, edit) -> (op, replica): for the tests
        self.rooms: Dict[int, Room] = {}
        self.counts: Dict[str, int] = {}
        self.early_by_room: Dict[int, int] = {}

    def host_lane(self) -> List[bool]:
        """For every update of `order`: will the server plan it on the host?
        It does while the room holds a stash: the update that is early, and
        every update of its room up to the one it waits for."""
        held_until = {}  # room -> by place: the last frame any older update of the room waits for
        for k, room in self.rooms.items():
            held_until[k] = np.maximum.accumulate(np.r_[-1, room.ready[:-1]])
        return [
            bool(self.ready[e] > self.place[e] or held_until[self.room_of[e[0]]][self.place[e]] >= self.place[e])
            for e in self.order
        ]


def _pairs(quotas: Sequence[int], members: Dict[int, List[int]], share: float) -> Dict[int, int]:
    """Twins: of the sessions of rooms with two or more, `share` are tabs of
    one browser, two and two, rooms in rank order, a room's sessions in
    theirs."""
    twin: Dict[int, int] = {}
    seen = have = 0
    for room, q in enumerate(quotas):
        if q < 2:
            continue
        seen += q
        n = min(q // 2, int(share * seen / 2.0 + 0.5) - have)
        who = members[room]
        for k in range(max(0, n)):
            a, b = who[2 * k], who[2 * k + 1]
            twin[a], twin[b] = b, a
        have += max(0, n)
    return twin


def _arrival(seq: List[tuple], kinds, twin: Dict[int, int], share: float, by: Tuple[int, int], r) -> Tuple[List[tuple], Dict[tuple, tuple]]:
    """A room's frames as they reach the server, from the order they are
    made in (`seq`): an early edit moves up to `by` frames before the edit of
    its twin's that it is made on. Returns the order and {early edit: the
    twin's edit}. No early edit lands between another and what it waits for,
    nor before its own session's or its twin's older edits: it waits for the
    one update alone, at most `by[1]` frames."""
    arr = list(seq)
    target: Dict[tuple, tuple] = {}
    newest: Dict[int, tuple] = {}
    seen = 0
    for e in seq:
        i, j = e
        t = twin.get(i)
        if t is not None:
            seen += 1
            a = newest.get(t)
            d = r.randint(*by)
            if a is not None and kinds[a[0]][a[1]] and len(target) < int(share * seen + 0.5):
                pa, pb = arr.index(a), arr.index(e)
                older = [x for x in ((i, j - 1), (a[0], a[1] - 1)) if x[1] >= 0]
                lower = 1 + max([arr.index(x) for x in older], default=-1)
                spans = [(arr.index(b2), arr.index(a2)) for b2, a2 in target.items()]
                for dd in range(d, 0, -1):
                    new = pa - (dd - 1)
                    if new >= lower and not any(lo < new <= hi for lo, hi in spans):
                        arr.insert(new, arr.pop(pb))
                        target[e] = a
                        break
        newest[i] = e
    for b, a in target.items():
        assert 1 <= arr.index(a) - arr.index(b) <= by[1], (b, a)
    return arr, target


def build(n_rooms: int, n_sessions: int, n_edits: int, mix: dict, seed: int, prefill, client_base: int = g.CLIENT_BASE,
          stream: str = "traffic", keep_log: bool = False) -> Made:
    zipf_s, p_delete = mix["zipf_s"], mix.get("p_delete", 0.25)
    lag, early_by = _span(mix["see_lag"]), _span(mix["early_by"])
    quotas = g.zipf_quotas(n_rooms, n_sessions, zipf_s)
    rooms = [k for k, q in enumerate(quotas) for _ in range(q)]
    g.rng(g.LAYOUT, stream, "rooms").shuffle(rooms)  # `grammar.build_sessions`' seats
    members: Dict[int, List[int]] = {}
    for i, k in enumerate(rooms):
        members.setdefault(k, []).append(i)
    out = Made()
    out.room_of = rooms
    out.twin = twin = _pairs(quotas, members, mix["twin_share"])
    # an edit's kind (True: an insert), drawn before anything: an early edit is made on an insert
    kinds = [[g.rng(g.LAYOUT, stream, "kind", i, j).random() >= p_delete for j in range(n_edits)] for i in range(n_sessions)]

    # what `grammar.interleave` reads of a session: its room and how many edits it has
    made_order = g.interleave([SimpleNamespace(room=k, edits=[None] * n_edits) for k in rooms], g.LAYOUT, stream)
    seqs: Dict[int, List[tuple]] = {}
    slots: Dict[int, List[int]] = {}
    for pos, e in enumerate(made_order):
        seqs.setdefault(rooms[e[0]], []).append(e)
        slots.setdefault(rooms[e[0]], []).append(pos)
    order: List[Optional[tuple]] = [None] * len(made_order)
    early: Dict[tuple, tuple] = {}
    for room in sorted(seqs):
        arr, target = _arrival(seqs[room], kinds, twin, mix["early_share"], early_by, g.rng(g.LAYOUT, stream, "early", room))
        early.update(target)
        for k, e in enumerate(arr):
            out.place[e] = k
            order[slots[room][k]] = e
    out.order = order

    # the prefill's documents, through the plain reference: one a template
    templates = []
    for tpl in prefill.templates:
        doc = yp.Text()
        for stage in tpl.stages:
            doc.apply_update(stage)
        ids = [(it.client, it.clock) for it in doc.items]
        if ids != list(tpl.ids) or doc.waiting:
            raise ValueError("the plain reference and the grammar disagree on a prefilled document")
        templates.append((doc, _keys(ids)))

    shape = [g.rng(g.LAYOUT, stream, "session", i) for i in range(n_sessions)]
    text = [g.rng(seed, stream, "text", i) for i in range(n_sessions)]
    clock = [0] * n_sessions
    edits: List[List[Optional[g.Edit]]] = [[None] * n_edits for _ in range(n_sessions)]
    counts = dict.fromkeys(("inserts", "deletes", "followed", "siblings", "case2", "foreign_origin", "foreign_delete",
                            "double_delete", "early", "early_deletes"), 0)
    for i, j in made_order:
        k = rooms[i]
        room = out.rooms.get(k)
        if room is None:
            room = out.rooms[k] = Room(*templates[prefill.of_room[k]], len(seqs[k]))
        r, t, place = shape[i], twin.get(i), out.place[i, j]
        client = client_base + i
        pair = i if t is None else min(i, t)
        h = room.horizon[pair] = max(room.horizon.get(pair, 0), place - r.randint(*lag))
        follow = r.random() < mix["follow_share"]
        on = early.get((i, j))
        held, shown = room.replica(i, t, h)
        shown_at = np.flatnonzero(shown)
        n_shown = len(shown_at)
        # the edit it is made on, if any: the twin's, or the newest insert of another that the replica holds
        base = room.newest_insert(i, t, h, on and on[0]) if on or follow else None
        insert = kinds[i][j] or n_shown <= 8
        if insert:
            n = r.randint(3, 8)
            word = "".join(text[i].choice(g.ALPHABET) for _ in range(n))
            pos = r.randint(0, n_shown)
            if base is not None:  # right after its last character, as the replica shows it
                last = int(np.flatnonzero(room.key == base[2] + base[3] - 1)[0])
                pos = int(np.count_nonzero(shown[: last + 1]))
            # the character left of the cursor, and whatever the replica holds right of it, a tombstone too
            left = int(shown_at[pos - 1]) if pos else -1
            held_at = np.flatnonzero(held)
            nxt = int(np.searchsorted(held_at, left, side="right"))
            right = int(held_at[nxt]) if nxt < len(held_at) else None
            named = [x for x in (left, right) if x is not None and x >= 0]
            origin = _id(room.key[left]) if left >= 0 else None
            right_origin = _id(room.key[right]) if right is not None else None
            op = ("i", pos, word)
            update = g.encode_update(client, [g.Block(clock[i], origin, right_origin, word)], {})
        else:
            n = r.randint(1, 3)
            pos = r.randint(0, n_shown - 4)
            if base is not None:  # from one of its characters that the replica still shows
                mine = np.flatnonzero(shown & (room.key >= base[2]) & (room.key < base[2] + base[3]))
                if len(mine):
                    pos = int(np.count_nonzero(shown[: int(mine[r.randrange(len(mine))])]))
                else:
                    base = None
            named = [int(x) for x in shown_at[pos : pos + n]]
            ranges = g._ranges([_id(room.key[x]) for x in named])
            op = ("d", pos, len(named))
            update = g.encode_update(client, [], ranges)

        # when a replica in step with the room holds it: its place, or that of what it names
        ready = max([place] + [int(room.born[x]) for x in named])
        room.ready[place] = out.ready[i, j] = ready
        if (ready > place) != (on is not None):
            raise ValueError(f"session {i} edit {j}: ready after frame {ready}, placed at {place}, early {on}")
        if keep_log:
            out.log[i, j] = (op, [e for e in seqs[k] if e != (i, j) and e in out.ready and (
                out.ready[e] < h or e[0] in (i, t))])
        counts["followed"] += base is not None
        if on is not None:
            counts["early"] += 1
            counts["early_deletes"] += not insert
            out.early_by_room[k] = out.early_by_room.get(k, 0) + 1
        if insert:
            counts["inserts"] += 1
            counts["foreign_origin"] += room.foreign(i, named)
            room.typed(i, client, clock[i], origin, right_origin, word, ready)
            counts["siblings"] += room.doc.scanned > 0
            counts["case2"] += room.doc.case2 > 0
            clock[i] += n
        else:
            counts["deletes"] += 1
            counts["foreign_delete"] += room.foreign(i, named)
            counts["double_delete"] += any(room.gone[x] < NEVER for x in named)
            room.deleted(i, named, ranges, ready)
        edits[i][j] = g.Edit(update, b"", n if insert else -len(named))
    out.sessions = [g.Session(i, rooms[i], client_base + i, edits[i]) for i in range(n_sessions)]
    out.counts = counts
    return out


def dispatches(ops: Sequence[Op], host: Sequence[bool], tick: int):
    """The fast lane's payloads of every dispatch the server loop makes of
    `ops` taken in ticks of `tick` frames (one update a room a dispatch,
    oldest first), and how many of its updates the host lane plans."""
    for i in range(0, len(ops), tick):
        fifo: Dict[int, List[tuple]] = {}
        for op, slow in zip(ops[i : i + tick], host[i : i + tick]):
            fifo.setdefault(op.room, []).append((op.update, slow))
        for depth in range(max(map(len, fifo.values()))):
            step = [q[depth] for q in fifo.values() if len(q) > depth]
            yield [u for u, slow in step if not slow], sum(1 for _, slow in step if slow)


def family_tick(typists: Sequence[g.Typist], fam: tuple) -> List[g.Edit]:
    """One update from each of the first S typists (distinct rooms) whose
    dispatch is of the family `fam`: word lengths chosen so that the payloads
    sum into the wire bucket, deletes where an insert would be too long."""
    lanes, bucket, _width, all_delete = fam
    low = bucket // 2 + 1 if bucket > warmup.WIRE_BUCKET_LO else 0
    edits, total = [], 0
    for w, t in enumerate(typists[:lanes]):
        rest = lanes - w
        want = ((low + bucket) // 2 - total) / rest
        if all_delete or (w and want < OVERHEAD):
            edits.append(t.next_edit(delete=True))
        else:
            edits.append(t.next_edit(word_len=max(1, min(LANE_MAX - OVERHEAD - 2, round(want - OVERHEAD)))))
        total += len(edits[-1].update)
    return edits


def _ops(made: Made, session_offset: int = 0) -> List[Op]:
    return [
        Op("update", i + session_offset, made.room_of[i], g.update_frame(made.sessions[i].edits[j].update),
           update=made.sessions[i].edits[j].update)
        for i, j in made.order
    ]


def plan(deploy: dict, mix: dict, prefill, seed: int, seconds: float) -> Plan:
    n_rooms, tick = deploy["n_docs"], mix["tick_max_frames"]
    made = build(n_rooms, mix["sessions"], mix["edits_per_session"], mix, seed, prefill)
    ops = _ops(made)
    host = made.host_lane()

    # warm-up: the first `tick` warm sessions sit in distinct rooms (the
    # harness drives its S-sweep through them); then sessions that co-edit
    # the same way, early arrivals included, in a universe of their own
    sweep_rooms = [(n_rooms // 2 + w) % n_rooms for w in range(min(tick, n_rooms))]
    n_own = mix.get("warm_sessions", 0)
    warm: List[List[Op]] = []
    warm_rooms = list(sweep_rooms)
    own_host: List[bool] = []
    if n_own:
        own = build(n_rooms, n_own, mix.get("warm_edits_per_session", 2), mix, seed, prefill,
                    client_base=g.WARM_CLIENT_BASE + len(sweep_rooms), stream="warm")
        own_ops = _ops(own, session_offset=len(sweep_rooms))
        own_host = own.host_lane()
        if not own.counts["early"]:
            raise ValueError("no warm-up session's update is early: the stash would first be met inside the window")
        warm = [own_ops[i : i + tick] for i in range(0, len(own_ops), tick)]
        warm_rooms += own.room_of
    # one tick of every family the window's fast lanes have, from typists of
    # their own in distinct rooms: the harness's sweep aims at the buckets
    # from the mix's mean length and can land beside one
    fam_rooms = [(n_rooms // 2 + tick + w) % n_rooms for w in range(min(tick, n_rooms))]
    fam_typists = []
    for w, k in enumerate(fam_rooms):
        tpl = prefill.for_room(k)
        fam_typists.append(g.Typist(g.WARM_CLIENT_BASE + len(warm_rooms) + w, g.rng(g.LAYOUT, "warm", "family", w),
                                    tpl.ids, {tpl.client_id: tpl.chars}, text=g.rng(seed, "warm", "family", w)))
    steps = list(dispatches(ops, host, tick))
    families = sorted({family(p) for p, _ in steps if p})
    for fam in families:
        made_tick = family_tick(fam_typists, fam)
        got = family([e.update for e in made_tick])
        if got != fam:
            raise ValueError(f"the warm-up cannot make a dispatch of the family {fam}: it made {got}")
        warm.append([Op("update", len(warm_rooms) + w, fam_rooms[w], g.update_frame(e.update), update=e.update)
                     for w, e in enumerate(made_tick)])
    warm_rooms += fam_rooms

    c = made.counts
    n_host = sum(host)
    hot = sorted(made.early_by_room.items(), key=lambda kv: (-kv[1], kv[0]))[:6]
    print(f"bench: coedit: {len(ops)} updates ({c['inserts']} inserts, {c['deletes']} deletes), {len(made.twin)} sessions are "
          f"twins; {c['followed']} made on another session's newest insert, {c['foreign_origin']} inserts name another "
          f"session's character, {c['siblings']} inserts meet concurrent items between their neighbours "
          f"({c['case2']} judged by the rule's second case), {c['foreign_delete']} deletes take another session's "
          f"characters ({c['double_delete']} a character already deleted); {c['early']} updates are early "
          f"({c['early_deletes']} of them deletes; by room {hot}), so the host lane plans {n_host} "
          f"({100.0 * n_host / len(ops):.1f}%); warm-up: {sum(own_host)} of {len(own_host)} on the host lane", flush=True)

    lens = [len(op.update) for op in ops[:4096]]
    return Plan(
        clients=[s.client_id for s in made.sessions]
        + [g.WARM_CLIENT_BASE + w for w in range(len(warm_rooms))]
        + [t.client_id for t in prefill.templates],
        session_rooms=list(made.room_of),
        preload=[],
        warm=warm,
        warm_session_rooms=warm_rooms,
        ops=ops,
        saturated=True,
        repeat=False,
        tick_max_frames=tick,
        sessions=made.sessions,
        notes={
            "update_len_min": min(lens), "update_len_max": max(lens),
            "update_len_mean": sum(lens) / len(lens),
            "hot_room_sessions": max(g.zipf_quotas(n_rooms, mix["sessions"], mix["zipf_s"])),
            "needs_sync_warm": False,
            "needs_update_warm": True,
            "lane_counts": sorted({fam[0] for fam in families}),
            "families": families,
            "host_lane_updates": n_host,
            "host_lane_steps": sum(1 for _, slow in steps if slow),
            "counts": dict(c),
            "early_by_room": dict(made.early_by_room),
        },
    )
