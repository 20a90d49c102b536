"""The benchmark's own traffic grammar (yardstick code).

Rooms hold documents, and the people in a room are **synced** with it: a
session starts from its room's prefilled document, as a y-websocket
provider does after its handshake, and every edit is the wire update (v1)
a Yjs client would send for it — an insert whose origin and right origin
are the characters beside the cursor, a delete set naming the characters
removed. The documents and the clients are modelled here (a list of the
visible characters' ids) and the updates are encoded here, byte for byte
what `ytpu.core.Doc` emits for the same edit (`benchmark/tests/
test_generator.py`); building 2,048 real client documents of 3,000 rows
would take two minutes of every run's set-up.

Taken from `ytpu/serving/scenario.py`: the edit grammar (3-8 character
inserts at a random position, 25% deletes of 1-3), a session stays in its
room. Changed, because a benchmark needs it:

- rooms are given to sessions by **fixed Zipf quotas** (largest remainder),
  the Zipf exponent is YCSB's 0.99, not 1.2;
- frames carry due times; Poisson gaps are the exponential's quantiles,
  shuffled, so every run has the same set of gaps;
- Scenario's sessions type into an empty document of their own, so their
  inserts carry no origin and the server's conflict scan walks the room;
  no provider does that.

From the program this module takes the wire framing of
`ytpu.sync.protocol` and `ytpu.core.state_vector.StateVector`.
"""

from __future__ import annotations

import math
import random
import zlib
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

ROOT = "text"
CLIENT_BASE = 7000  # session i -> client id CLIENT_BASE + i
WARM_CLIENT_BASE = 600_000  # warm-up sessions: ids disjoint from traffic
TEMPLATE_CLIENT_BASE = 900_000  # one writer per prefill template
ALPHABET = "abcdefghij"
# One fixed trace: the shape of the traffic (who sits where, every edit's
# kind, position and length, the order of the pool, the gaps) is drawn once,
# from this constant, and the run's seed types the characters. Six seeds
# that also drew the shapes read 35.0-44.1 updates/s where two runs of one
# seed agreed to 1% (my chip runs, PR 25, unsynced sessions on sparse
# rooms): the seed was changing the work.
LAYOUT = 20250927


def rng(*key) -> random.Random:
    """Seeded RNG keyed by a tuple; crc32 is stable across processes."""
    return random.Random(zlib.crc32(":".join(map(str, key)).encode()))


def room_name(k: int) -> str:
    return f"room{k}"


def zipf_weights(n: int, s: float) -> List[float]:
    w = [1.0 / (k + 1) ** s for k in range(n)]
    total = sum(w)
    return [x / total for x in w]


def zipf_quotas(n_rooms: int, n_sessions: int, s: float) -> List[int]:
    """Sessions per room (rank order): largest-remainder rounding of the
    Zipf shares, so the counts sum to `n_sessions` exactly."""
    shares = [p * n_sessions for p in zipf_weights(n_rooms, s)]
    counts = [int(x) for x in shares]
    rest = n_sessions - sum(counts)
    order = sorted(range(n_rooms), key=lambda k: (counts[k] - shares[k], k))
    for k in order[:rest]:
        counts[k] += 1
    return counts


class Edit(NamedTuple):
    update: bytes  # the wire update (v1) this edit produced
    sv_after: bytes  # the client's encoded state vector after the edit
    chars: int  # inserted (+) or deleted (-) characters


class Session(NamedTuple):
    sid: int
    room: int
    client_id: int
    edits: List[Edit]


def update_frame(update: bytes) -> bytes:
    from ytpu.sync.protocol import Message, SyncMessage

    return Message.sync(SyncMessage.update(update)).encode_v1()


def step1_frame(sv) -> bytes:
    from ytpu.sync.protocol import Message, SyncMessage

    return Message.sync(SyncMessage.step1(sv)).encode_v1()


def awareness_frame(client_id: int, clock: int, sid: int) -> bytes:
    from ytpu.sync.awareness import AwarenessUpdate, AwarenessUpdateEntry
    from ytpu.sync.protocol import Message

    up = AwarenessUpdate(
        {client_id: AwarenessUpdateEntry(clock, '{"s":%d,"k":%d}' % (sid, clock))}
    )
    return Message.awareness(up).encode_v1()


# --- Yjs update encoding (v1), by hand ---------------------------------------

Id = Tuple[int, int]  # (client, clock) of one character


def _varuint(n: int) -> bytes:
    out = bytearray()
    while n > 0x7F:
        out.append(0x80 | (n & 0x7F))
        n >>= 7
    out.append(n)
    return bytes(out)


class Block(NamedTuple):
    """One inserted string: `length` characters from `clock` on."""

    clock: int
    origin: Optional[Id]  # the character left of the cursor
    right: Optional[Id]  # the character right of the cursor
    text: str


def encode_update(client: int, blocks: Sequence[Block], deletes: Dict[int, List[Tuple[int, int]]]) -> bytes:
    """The v1 update holding `blocks` of one client (consecutive clocks) and
    a delete set {client: [(clock, length)]}: what a Yjs client sends."""
    out = bytearray()
    if blocks:
        out += _varuint(1) + _varuint(len(blocks)) + _varuint(client) + _varuint(blocks[0].clock)
        for b in blocks:
            out.append(4 | (0x80 if b.origin else 0) | (0x40 if b.right else 0))  # 4: a string
            for ref in (b.origin, b.right):
                if ref:
                    out += _varuint(ref[0]) + _varuint(ref[1])
            if not b.origin and not b.right:  # no neighbour names the parent: the root type
                out += _varuint(1) + _varuint(len(ROOT)) + ROOT.encode()
            raw = b.text.encode()
            out += _varuint(len(raw)) + raw
    else:
        out += _varuint(0)
    out += _varuint(len(deletes))
    for c in sorted(deletes, reverse=True):
        out += _varuint(c) + _varuint(len(deletes[c]))
        for clock, length in deletes[c]:
            out += _varuint(clock) + _varuint(length)
    return bytes(out)


def _ranges(ids: Sequence[Id]) -> Dict[int, List[Tuple[int, int]]]:
    """Delete-set ranges of the given character ids: per client, sorted, merged."""
    out: Dict[int, List[Tuple[int, int]]] = {}
    for c, k in sorted(ids):
        q = out.setdefault(c, [])
        if q and q[-1][0] + q[-1][1] == k:
            q[-1] = (q[-1][0], q[-1][1] + 1)
        else:
            q.append((k, 1))
    return out


def encode_sv(clocks: Dict[int, int]) -> bytes:
    from ytpu.core.state_vector import StateVector

    return StateVector({c: k for c, k in clocks.items() if k}).encode_v1()


class Typist:
    """One synced client typing Scenario's grammar into its room's document:
    inserts of 3-8 characters at a random position, 25% deletes of 1-3.
    `ids` is the document as the client holds it: the id of every character
    in order, its own tombstones (`dead`, their indices) included, because
    a Yjs client names the item right of the cursor as right origin even
    when that item is deleted. It holds the room's prefill and its own
    edits, not the others' (a client typing ahead of the room's fan-out).
    `next_edit(word_len=, delete=)` lets the warm-up force a payload size
    or a delete-only update. `r` draws the shape of every edit (kind,
    position, length), `text` the characters typed."""

    def __init__(self, client_id: int, r: random.Random, ids: Sequence[Id], base_clocks: Dict[int, int],
                 p_delete: float = 0.25, text: Optional[random.Random] = None):
        self.client_id = client_id
        self.r = r
        self.text = text or r
        self.ids = list(ids)
        self.dead: List[int] = []  # ascending indices into `ids`
        self.clocks = dict(base_clocks)  # the client's state vector
        self.clock = 0
        self.p_delete = p_delete

    @property
    def length(self) -> int:
        return len(self.ids) - len(self.dead)

    def can_delete(self) -> bool:
        return self.length > 8

    def _at(self, visible: int) -> int:
        """Index into `ids` of the visible character number `visible`."""
        i = visible
        for d in self.dead:
            if d > i:
                break
            i += 1
        return i

    def next_edit(self, word_len: Optional[int] = None, delete: Optional[bool] = None) -> Edit:
        r, ids = self.r, self.ids
        if delete is None:
            delete = word_len is None and self.can_delete() and r.random() < self.p_delete
        if delete:
            pos = r.randint(0, self.length - 4)
            n = r.randint(1, 3)
            gone = [self._at(pos + i) for i in range(n)]
            update = encode_update(self.client_id, [], _ranges([ids[i] for i in gone]))
            self.dead = sorted(self.dead + gone)
            chars = -n
        else:
            n = word_len if word_len is not None else r.randint(3, 8)
            word = "".join(self.text.choice(ALPHABET) for _ in range(n))
            pos = r.randint(0, self.length)
            at = self._at(pos - 1) + 1 if pos else 0  # right after the visible character left of the cursor
            block = Block(self.clock, ids[at - 1] if at else None, ids[at] if at < len(ids) else None, word)
            update = encode_update(self.client_id, [block], {})
            ids[at:at] = [(self.client_id, self.clock + i) for i in range(n)]
            self.dead = [d + n if d >= at else d for d in self.dead]
            self.clock += n
            self.clocks[self.client_id] = self.clock
            chars = n
        return Edit(update, encode_sv(self.clocks), chars)


def build_sessions(
    n_rooms: int,
    n_sessions: int,
    edits_per_session: int,
    zipf_s: float,
    seed: int,
    prefill: "Prefill",
    client_base: int = CLIENT_BASE,
    stream: str = "traffic",
) -> List[Session]:
    """Sessions with their rooms (fixed quotas) and their edit scripts,
    each starting from its room's prefilled document. The shape of
    everything (who sits where, every edit's kind, position and length)
    comes from `LAYOUT`; the seed types the characters."""
    quotas = zipf_quotas(n_rooms, n_sessions, zipf_s)
    rooms = [k for k, q in enumerate(quotas) for _ in range(q)]
    rng(LAYOUT, stream, "rooms").shuffle(rooms)
    out = []
    for i in range(n_sessions):
        tpl = prefill.for_room(rooms[i])
        t = Typist(client_base + i, rng(LAYOUT, stream, "session", i), tpl.ids, {tpl.client_id: tpl.chars},
                   text=rng(seed, stream, "text", i))
        out.append(
            Session(i, rooms[i], t.client_id, [t.next_edit() for _ in range(edits_per_session)])
        )
    return out


def interleave(sessions: Sequence[Session], seed: int, stream: str = "traffic") -> List[tuple]:
    """(session index, edit index) in an order that keeps every session's
    own order (an update depends on the one before it) and is **evenly
    paced per room**: a room's frames sit at equal distances through the
    pool (seeded phase), its sessions taking turns in a seeded order. Any
    run of W frames then holds a room's share of W, give or take one, for
    every seed: how many frames of the hottest room meet in one tick sets
    how many dispatches the tick costs, and a random interleave made that,
    and with it the rate, swing by 7% from seed to seed (my chip runs, PR 25)."""
    r = rng(seed, stream, "interleave")
    by_room: Dict[int, List[int]] = {}
    for i, s in enumerate(sessions):
        if s.edits:
            by_room.setdefault(s.room, []).append(i)
    placed = []
    for room in sorted(by_room):
        members = by_room[room]
        r.shuffle(members)
        n = sum(len(sessions[i].edits) for i in members)
        phase = r.random()
        cursors = {i: 0 for i in members}
        k = turn = 0
        while k < n:
            i = members[turn % len(members)]
            turn += 1
            if cursors[i] >= len(sessions[i].edits):
                continue
            placed.append(((k + phase) / n, room, i, cursors[i]))
            cursors[i] += 1
            k += 1
    placed.sort()
    return [(i, j) for _, _, i, j in placed]


def paced_order(rooms_of: Sequence[int], seed: int, stream: str) -> List[int]:
    """Indices 0..n-1 ordered so that every room's members are evenly
    spread (the order of a reconnect wave)."""
    r = rng(seed, stream, "paced")
    by_room: Dict[int, List[int]] = {}
    for i, room in enumerate(rooms_of):
        by_room.setdefault(room, []).append(i)
    placed = []
    for room in sorted(by_room):
        members = by_room[room]
        r.shuffle(members)
        phase = r.random()
        placed += [((k + phase) / len(members), room, i) for k, i in enumerate(members)]
    placed.sort()
    return [i for _, _, i in placed]


def exponential_gaps(n: int, rate: float, seed: int, stream: str) -> List[float]:
    """`n` Poisson inter-arrival gaps: the exponential's n mid-quantiles,
    shuffled by the seed. Every seed has the same set of gaps."""
    gaps = [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]
    rng(seed, stream, "gaps").shuffle(gaps)
    return gaps


# --- prefill ------------------------------------------------------------------


class Template(NamedTuple):
    """A prefilled document, one writer, loaded in stages."""

    client_id: int
    stages: List[bytes]  # one update per stage, each at most a row bucket of blocks
    ids: List[Id]  # the visible characters' ids once every stage is in
    chars: int  # the writer's clock then
    rows: int  # rows the device holds then: one per block, one more per block split
    row_clocks: List[int]  # the clock each of those rows starts at, ascending


def build_template(client_id: int, stage_rows: Sequence[int], r: random.Random, text: random.Random) -> Template:
    """A document written by 1-4 character inserts at random positions (an
    insert inside an older block splits it), `stage_rows[s]` of them in
    stage s. The counts are exact for every seed: they fix the decode
    program's step budget and the row bucket."""
    ids: List[Id] = []
    starts = set()
    clock = 0
    stages = []
    for n_blocks in stage_rows:
        blocks = []
        for _ in range(n_blocks):
            n = r.randint(1, 4)
            word = "".join(text.choice(ALPHABET) for _ in range(n))
            pos = r.randint(0, len(ids))
            left = ids[pos - 1] if pos else None
            right = ids[pos] if pos < len(ids) else None
            blocks.append(Block(clock, left, right, word))
            starts.add(clock)
            if left and right and right[1] == left[1] + 1:
                starts.add(right[1])  # typed inside a block: it splits there
            ids[pos:pos] = [(client_id, clock + i) for i in range(n)]
            clock += n
        stages.append(encode_update(client_id, blocks, {}))
    return Template(client_id, stages, ids, clock, len(starts), sorted(starts))


class Prefill:
    """What every room holds before traffic: the configuration's `prefill`
    gives classes of rooms by rank (the hottest first), each with the rows
    it loads per stage; a class is one template document. Every class has
    the same number of stages, so every stage is one dispatch over all
    rooms (the served path compiles per number of rooms in a dispatch)."""

    def __init__(self, cfg: dict, n_rooms: int, seed: int):
        classes = cfg["classes"]
        if len({len(c["stage_rows"]) for c in classes}) != 1:
            raise ValueError("every prefill class needs the same number of stages")
        self.n_stages = len(classes[0]["stage_rows"])
        self.templates = [
            build_template(TEMPLATE_CLIENT_BASE + t, c["stage_rows"], rng(LAYOUT, "template", t),
                           rng(seed, "template", "text", t))
            for t, c in enumerate(classes)
        ]
        self.of_room: List[int] = []
        for t, c in enumerate(classes):
            n = n_rooms - len(self.of_room) if c.get("rooms") is None else min(c["rooms"], n_rooms - len(self.of_room))
            self.of_room += [t] * n
        if len(self.of_room) != n_rooms:
            raise ValueError("the prefill classes do not cover every room: give the last one no `rooms`")

    def for_room(self, room: int) -> Template:
        return self.templates[self.of_room[room]]


def expected_clocks(sessions: Sequence[Session], taken: Dict[int, int]) -> Dict[int, Dict[int, int]]:
    """Per room, the state vector the grammar alone predicts: a client's
    clock is the number of characters it has inserted in the edits the
    server took (`taken[sid]` = how many of the session's edits)."""
    out: Dict[int, Dict[int, int]] = {}
    for s in sessions:
        n = taken.get(s.sid, 0)
        clock = sum(e.chars for e in s.edits[:n] if e.chars > 0)
        if clock:
            out.setdefault(s.room, {})[s.client_id] = clock
    return out

