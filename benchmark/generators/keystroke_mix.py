"""Rooms that are typed into: a keystroke an update, the shape of the B4
editing trace.

A session is a typist. Session *i* plays ops `[k*i, k*i + k)` of
crdt-benchmarks B4 (`benchmark/data/b4_flags.txt`, made from the repo's copy
of the trace by `benchmark/tools/b4_flags.py`: one letter an op), `k` =
`keystrokes_per_session`:

    c  type a character where the cursor is: the run goes on (its origin
       is the character the session typed last)
    j  move the cursor somewhere else in the room's document, then type
    b  backspace: delete the character left of the cursor
    d  delete a character somewhere else in the document

each one transaction and one wire update, byte for byte what
`ytpu.core.Doc` sends for it (`benchmark/tests/test_keystroke_mix.py`),
under the session's own client id. Where a run starts and which character
a `d` takes come from `grammar.LAYOUT` (one fixed trace); `--seed` types the
characters. Sessions sit in rooms by `session_mix`'s rule (fixed Zipf
quotas, largest remainder), are synced with their room's prefilled document
and do not apply its broadcasts, and the pool is paced per room
(`grammar.interleave`), as in every cell.

A traffic file (`benchmark/traffic/<mix>.json`) gives

    sessions                 typists, each bound to one room
    keystrokes_per_session   ops a typist plays (one update each)
    zipf_s                   skew of sessions over rooms
    tick_max_frames          ops the server loop takes per tick
    warm_sessions            disjoint typists that play the trace's next
                             ops during warm-up
    (then one tick of every program family the window's dispatches have,
    `family_tick`, from typists of their own)
    warm_fill_keystrokes     keystrokes one more warm-up typist types into
                             one room, so that the room crosses the
                             server's reserve and is compacted before the
                             window: the compaction program is built in
                             set-up, as every other

A device row an insert is what this traffic is about: a room's slot fills
some 5,000 keystrokes into a session unless the server squashes typed runs
and collects deleted content, as Yjs does at every commit.
"""

from __future__ import annotations

import os
from typing import Dict, List, Optional, Sequence

from benchmark import grammar as g
from benchmark import warmup
from benchmark.ops import Op, Plan

FLAGS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "data", "b4_flags.txt")


def load_flags() -> str:
    with open(FLAGS) as f:
        return f.read().strip()


class KeyTypist(g.Typist):
    """A `grammar.Typist` with a cursor: one character an edit."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.cursor: Optional[int] = None  # visible characters left of it; None: nowhere yet
        self.log: List[tuple] = []  # ("i", visible position, character) / ("d", visible position): for the tests

    def _insert(self, pos: int) -> g.Edit:
        ids = self.ids
        at = self._at(pos - 1) + 1 if pos else 0  # right after the visible character left of the cursor
        ch = self.text.choice(g.ALPHABET)
        self.log.append(("i", pos, ch))
        block = g.Block(self.clock, ids[at - 1] if at else None, ids[at] if at < len(ids) else None, ch)
        update = g.encode_update(self.client_id, [block], {})
        ids.insert(at, (self.client_id, self.clock))
        self.dead = [d + 1 if d >= at else d for d in self.dead]
        self.clock += 1
        self.clocks[self.client_id] = self.clock
        self.cursor = pos + 1
        return g.Edit(update, g.encode_sv(self.clocks), 1)

    def _delete(self, pos: int) -> g.Edit:
        gone = self._at(pos)
        self.log.append(("d", pos))
        update = g.encode_update(self.client_id, [], g._ranges([self.ids[gone]]))
        self.dead = sorted(self.dead + [gone])
        if self.cursor is not None and pos < self.cursor:
            self.cursor -= 1
        return g.Edit(update, g.encode_sv(self.clocks), -1)

    def play(self, flag: str) -> g.Edit:
        """One op of the trace. An op that cannot be played as it stands
        (a backspace at the document's start, a run to go on before any
        began) is played as its nearest kind: same kind of update."""
        if flag in "cj":
            if flag == "j" or self.cursor is None:
                return self._insert(self.r.randint(0, self.length))
            return self._insert(self.cursor)
        if flag == "b" and self.cursor:
            return self._delete(self.cursor - 1)
        return self._delete(self.r.randint(0, self.length - 1))


def build_typists(n_rooms: int, n_sessions: int, keystrokes: int, zipf_s: float, seed: int, prefill,
                  flags: str, first_op: int = 0, client_base: int = g.CLIENT_BASE,
                  stream: str = "traffic") -> List[g.Session]:
    """Sessions in their rooms (`grammar.build_sessions`' rule) with their
    scripts: session i plays `flags[first_op + keystrokes * i :][:keystrokes]`."""
    quotas = g.zipf_quotas(n_rooms, n_sessions, zipf_s)
    rooms = [k for k, q in enumerate(quotas) for _ in range(q)]
    g.rng(g.LAYOUT, stream, "rooms").shuffle(rooms)
    out = []
    for i in range(n_sessions):
        tpl = prefill.for_room(rooms[i])
        t = KeyTypist(client_base + i, g.rng(g.LAYOUT, stream, "session", i), tpl.ids, {tpl.client_id: tpl.chars},
                      text=g.rng(seed, stream, "text", i))
        ops = flags[first_op + keystrokes * i : first_op + keystrokes * (i + 1)]
        if len(ops) != keystrokes:
            raise ValueError(f"the flags file holds {len(flags)} ops: session {i} needs more")
        out.append(g.Session(i, rooms[i], t.client_id, [t.play(f) for f in ops]))
    return out


def dispatches(ops: Sequence[Op], tick: int):
    """The payloads of every dispatch the server loop makes of `ops` taken
    in ticks of `tick` frames: one update a room a dispatch, oldest first."""
    for i in range(0, len(ops), tick):
        fifo: Dict[int, List[bytes]] = {}
        for op in ops[i : i + tick]:
            fifo.setdefault(op.room, []).append(op.update)
        for depth in range(max(map(len, fifo.values()))):
            yield [q[depth] for q in fifo.values() if len(q) > depth]


def family(payloads: Sequence[bytes]) -> tuple:
    """What keys the fast lane's programs for one dispatch, as
    `ytpu/models/ingest.py` works it out: lanes, the wire bytes' bucket,
    the lane matrix's width, and whether no lane has a client section."""
    return (
        len(payloads),
        warmup._bucket(sum(map(len, payloads)), warmup.WIRE_BUCKET_LO),
        warmup._bucket(max(map(len, payloads)) + 16, 64),
        all(p[0] == 0 for p in payloads),
    )


INSERT_MAX, DELETE_LEN = 22, 9  # bytes of a warm typist's longest insert; of its delete


def family_tick(typists: Sequence[KeyTypist], fam: tuple) -> List[g.Edit]:
    """One update from each of the first S typists (distinct rooms) whose
    dispatch is of the family `fam`: inserts after a jump (the longest
    payload a keystroke has) while the rest can still finish inside the
    wire bucket with deletes (the shortest), deletes from then on."""
    lanes, bucket, _width, all_delete = fam
    edits, total = [], 0
    for w in range(lanes):
        rest = lanes - w - 1
        insert = not all_delete and total + INSERT_MAX + rest * DELETE_LEN <= bucket
        edits.append(typists[w].play("j" if insert else "d"))
        total += len(edits[-1].update)
    return edits


def _update_ops(sessions: Sequence[g.Session], order, session_offset: int = 0) -> List[Op]:
    return [
        Op("update", i + session_offset, sessions[i].room, g.update_frame(sessions[i].edits[j].update),
           update=sessions[i].edits[j].update)
        for i, j in order
    ]


def plan(deploy: dict, mix: dict, prefill, seed: int, seconds: float) -> Plan:
    n_rooms = deploy["n_docs"]
    n_sessions, keys, tick = mix["sessions"], mix["keystrokes_per_session"], mix["tick_max_frames"]
    flags = load_flags()
    sessions = build_typists(n_rooms, n_sessions, keys, mix["zipf_s"], seed, prefill, flags)
    ops = _update_ops(sessions, g.interleave(sessions, g.LAYOUT))

    # warm-up: the first `tick` warm sessions sit in distinct rooms (the
    # harness drives its S-sweep through them); then typists that play the
    # trace's next ops; then one that fills a room up to a compaction
    sweep_rooms = [(n_rooms // 2 + w) % n_rooms for w in range(min(tick, n_rooms))]
    n_own = mix.get("warm_sessions", 0)
    own = build_typists(n_rooms, n_own, keys, mix["zipf_s"], seed, prefill, flags, first_op=n_sessions * keys,
                        client_base=g.WARM_CLIENT_BASE + len(sweep_rooms), stream="warm") if n_own else []
    own_ops = _update_ops(own, g.interleave(own, g.LAYOUT, "warm"), session_offset=len(sweep_rooms))
    warm = [own_ops[i : i + tick] for i in range(0, len(own_ops), tick)]
    warm_rooms = sweep_rooms + [s.room for s in own]
    # one tick of every family the window's dispatches have, from typists of
    # their own in distinct rooms: the harness's sweep aims at the buckets
    # from the mix's mean length and can land beside one
    fam_rooms = [(n_rooms // 2 + tick + w) % n_rooms for w in range(min(tick, n_rooms))]
    fam_typists = []
    for w, k in enumerate(fam_rooms):
        tpl = prefill.for_room(k)
        fam_typists.append(KeyTypist(g.WARM_CLIENT_BASE + len(warm_rooms) + w, g.rng(g.LAYOUT, "warm", "family", w),
                                     tpl.ids, {tpl.client_id: tpl.chars}, text=g.rng(seed, "warm", "family", w)))
    families = sorted({family(p) for p in dispatches(ops, tick)})
    for fam in families:
        edits = family_tick(fam_typists, fam)
        got = family([e.update for e in edits])
        if got != fam:
            raise ValueError(f"the warm-up cannot make a dispatch of the family {fam}: it made {got}")
        warm.append([Op("update", len(warm_rooms) + w, fam_rooms[w], g.update_frame(e.update), update=e.update)
                     for w, e in enumerate(edits)])
    warm_rooms += fam_rooms
    n_fill = mix.get("warm_fill_keystrokes", 0)
    if n_fill:
        # a room no traffic session sits in where there is one (the coldest)
        fill_room = n_rooms - 1
        tpl = prefill.for_room(fill_room)
        filler = KeyTypist(g.WARM_CLIENT_BASE + len(warm_rooms), g.rng(g.LAYOUT, "warm", "fill"), tpl.ids,
                           {tpl.client_id: tpl.chars}, text=g.rng(seed, "warm", "fill"))
        fill = [filler.play("c") for _ in range(n_fill)]
        fill_ops = [Op("update", len(warm_rooms), fill_room, g.update_frame(e.update), update=e.update) for e in fill]
        warm += [fill_ops[i : i + tick] for i in range(0, len(fill_ops), tick)]
        warm_rooms.append(fill_room)

    lens = [len(op.update) for op in ops[:4096]]
    # the pool is taken in ticks of exactly `tick` frames, whatever the
    # server's speed, so the lane counts of its dispatches are known
    lane_counts = {fam[0] for fam in families}
    return Plan(
        clients=[s.client_id for s in sessions]
        + [g.WARM_CLIENT_BASE + w for w in range(len(warm_rooms))]
        + [t.client_id for t in prefill.templates],
        session_rooms=[s.room for s in sessions],
        preload=[],
        warm=warm,
        warm_session_rooms=warm_rooms,
        ops=ops,
        saturated=True,
        repeat=False,
        tick_max_frames=tick,
        sessions=sessions,
        notes={
            "update_len_min": min(lens), "update_len_max": max(lens),
            "update_len_mean": sum(lens) / len(lens),
            "hot_room_sessions": max(g.zipf_quotas(n_rooms, n_sessions, mix["zipf_s"])),
            "needs_sync_warm": False,
            "needs_update_warm": True,
            "lane_counts": sorted(lane_counts),
            "families": families,
            "flags": {k: flags[: n_sessions * keys].count(k) for k in "cjbd"},
        },
    )
