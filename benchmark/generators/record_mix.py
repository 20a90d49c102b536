"""Rooms that are stores of JSON records: the tldraw-over-Yjs shape.

The tldraw-yjs-example (`src/useYjsStore.ts`) keeps a whiteboard in a
y-websocket room as `yDoc.getArray('tl_' + roomId)` of `{key, val}` entries
under y-utility's `YKeyValue`: every store change runs `yStore.set(id,
record)` or `yStore.delete(id)` in one transaction. `set` deletes the array
entry that held the key and pushes `{key, val}` at the array's end; `delete`
deletes the entry. A record is a `TLShape` (`TLBaseShape`'s fields and a geo
shape's `props`, `meta: {}`): an object with objects in it, so every update
that carries one is a nested lib0 Any, which the server's prescan sends to
the host lane (`ytpu/models/ingest.py _slow_reason`: `complex_any`). A
`delete` carries no record: its update is a delete range alone and rides the
fast lane, one update in ten.

A traffic file is this generator's parameters. `sessions`,
`edits_per_session`, `zipf_s`, `tick_max_frames`, `arrival` (`saturated`
only) and `repeat` are `session_mix`'s, and so are the rooms' quotas, the
pacing of the pool (`grammar.interleave`) and the fixed trace
(`grammar.LAYOUT` draws every shape: who sits where, every change's kind,
key and field, every string's length; the seed types the characters and the
coordinates, and moves no length). Its own:

    store_changes  shares of `set_existing` (a drag, a restyle or a new
                   label of a record the session holds: the loader's, or
                   its own earlier push), `set_new` (push only) and
                   `delete` (delete range only); exact counts over the pool

The configuration's `records` block says what a room holds before the
window: `classes` of rooms by rank, each with its count of records, loaded
in stages of at most `stage_blocks` one-record blocks. The harness's prefill
is text-only, so the records are loaded as `Plan.preload` by one loader
session a room (entries of `Plan.sessions` after the traffic's; a stage of n
records is an edit with `chars = n`), from one template a class: every room
of a class holds the same records under its own array, `tl_<room name>`.

Sessions are synced with their room's loaded store and do not apply the
room's broadcasts (yrs `benches.rs` B3.4's shape). Every update is byte for
byte what `ytpu.core.Doc` encodes for the same transaction
(`array.remove` + `array.push_back`; `benchmark/tests/test_record_mix.py`).

The warm-up (`Plan.warm`, played by `warmup.own_traffic`) mirrors the window:
one tick for every distinct make-up of a window dispatch (lanes, delete-only
lanes, wire-byte bucket), sent by disjoint writers in rooms of their own
with payloads of the same lengths.
"""

from __future__ import annotations

import struct
from functools import lru_cache
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

from benchmark import grammar as g
from benchmark.ops import Op, Plan
from benchmark.warmup import _bucket

KINDS = ("set_existing", "set_new", "delete")
LOADER_CLIENT_BASE = g.TEMPLATE_CLIENT_BASE + 32  # one loader a class, past the prefill templates' ids
ID_ALPHABET = "0123456789ABCDEFGHIJKLMNOPQRSTUVWXYZ_abcdefghijklmnopqrstuvwxyz-"  # nanoid's
ID_CHARS = 21  # tldraw's createShapeId: "shape:" + nanoid()
LABEL_ALPHABET = "abcdefghijklmnopqrstuvwxyz "
LABEL_MAX = 36
INDEX_DIGITS = "0123456789ABCDEFGHIJKLMNOPQRSTUVWXYZabcdefghijklmnopqrstuvwxyz"
# a geo shape's style props as tldraw's default styles enumerate them
STYLES = {
    "geo": ("rectangle", "ellipse", "triangle", "diamond", "star", "cloud", "hexagon", "arrow-right"),
    "color": ("black", "grey", "light-violet", "violet", "blue", "light-blue", "yellow", "orange", "green",
              "light-green", "light-red", "red"),
    "fill": ("none", "semi", "solid", "pattern"),
    "dash": ("draw", "solid", "dashed", "dotted"),
    "size": ("s", "m", "l", "xl"),
    "font": ("draw", "sans", "serif", "mono"),
    "align": ("start", "middle", "end"),
    "verticalAlign": ("start", "middle", "end"),
}
RESTYLED = ("color", "fill", "dash", "size")


def records_root(room: int) -> str:
    return "tl_" + g.room_name(room)


# --- lib0 Any and the update (v1), by hand -----------------------------------


@lru_cache(maxsize=4096)  # the records' field names and style values, over and over
def _string(s: str) -> bytes:
    raw = s.encode()
    return g._varuint(len(raw)) + raw


def _varint(n: int) -> bytes:
    """lib0 writeVarInt: the sign in bit 6 of the first byte."""
    neg, n = n < 0, abs(n)
    out = bytearray([(0x40 if neg else 0) | (n & 0x3F) | (0x80 if n > 0x3F else 0)])
    n >>= 6
    while n:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
    return bytes(out)


def any_bytes(v) -> bytes:
    """lib0 writeAny of JSON data, as a Yjs client writes it: a number
    without a fraction is a varint, one a float32 holds exactly takes four
    bytes, any other eight."""
    if v is None:
        return b"\x7e"
    if v is True:
        return b"\x78"
    if v is False:
        return b"\x79"
    if isinstance(v, str):
        return b"\x77" + _string(v)
    if isinstance(v, (int, float)):
        if float(v).is_integer():
            return b"\x7d" + _varint(int(v))
        if struct.unpack(">f", struct.pack(">f", v))[0] == v:
            return b"\x7c" + struct.pack(">f", v)
        return b"\x7b" + struct.pack(">d", v)
    if isinstance(v, dict):
        return b"\x76" + g._varuint(len(v)) + b"".join(_string(k) + any_bytes(x) for k, x in v.items())
    if isinstance(v, (list, tuple)):
        return b"\x75" + g._varuint(len(v)) + b"".join(any_bytes(x) for x in v)
    raise TypeError(f"no lib0 Any for {type(v)!r}")


def _id(ref: g.Id) -> bytes:
    return g._varuint(ref[0]) + g._varuint(ref[1])


def block_bytes(origin: Optional[g.Id], right: Optional[g.Id], root: str, entry: dict) -> bytes:
    """One array item holding one `{key, val}` entry (ContentAny, length 1);
    an item with no neighbour names its parent, the root array."""
    out = bytearray([8 | (0x80 if origin else 0) | (0x40 if right else 0)])
    for ref in (origin, right):
        if ref:
            out += _id(ref)
    if not origin and not right:
        out += g._varuint(1) + _string(root)
    return bytes(out) + g._varuint(1) + any_bytes(entry)


def section_head(client: int, clock: int, n_blocks: int) -> bytes:
    return g._varuint(1) + g._varuint(n_blocks) + g._varuint(client) + g._varuint(clock)


def delete_set_bytes(ids: Sequence[g.Id]) -> bytes:
    ranges = g._ranges(ids)
    out = bytearray(g._varuint(len(ranges)))
    for c in sorted(ranges, reverse=True):
        out += g._varuint(c) + g._varuint(len(ranges[c]))
        for clock, length in ranges[c]:
            out += g._varuint(clock) + g._varuint(length)
    return bytes(out)


# --- records -----------------------------------------------------------------


def _coordinate(r) -> float:
    """A canvas coordinate with two decimals that no float32 holds exactly,
    so that it takes eight bytes on the wire whatever the seed draws."""
    cents = r.choice([c for c in range(1, 100) if c % 25])
    return r.randint(-4000, 4000) + cents / 100.0


def _label(n: int, text) -> str:
    return "".join(text.choice(LABEL_ALPHABET) for _ in range(n))


def new_record(key: str, n: int, shape, text) -> dict:
    """`{key, val}` as `YKeyValue` stores a `TLShape`: every field of
    `TLBaseShape` and a geo shape's props. `shape` draws what decides a
    length, `text` the coordinates and the label's characters."""
    label_len = shape.randint(0, LABEL_MAX) if shape.random() < 0.5 else 0
    style = {k: shape.choice(v) for k, v in STYLES.items()}
    index = "a" + INDEX_DIGITS[n // 62 % 62] + INDEX_DIGITS[n % 62]
    return {
        "key": key,
        "val": {
            "id": key, "typeName": "shape", "type": "geo",
            "x": _coordinate(text), "y": _coordinate(text), "rotation": 0,
            "index": index, "parentId": "page:page", "isLocked": False, "opacity": 1,
            "props": {
                "geo": style["geo"], "w": shape.randint(20, 900), "h": shape.randint(20, 900),
                "color": style["color"], "fill": style["fill"], "dash": style["dash"], "size": style["size"],
                "font": style["font"], "align": style["align"], "verticalAlign": style["verticalAlign"],
                "labelColor": "black", "growY": 0, "url": "", "text": _label(label_len, text),
            },
            "meta": {},
        },
    }


def changed_record(entry: dict, shape, text) -> dict:
    """The entry after a drag (new `x`, `y`), a restyle (one style prop) or a
    new label."""
    val = dict(entry["val"])
    props = dict(val["props"])
    how = shape.random()
    if how >= 0.85:
        props["text"] = _label(shape.randint(0, LABEL_MAX), text)
    elif how < 0.6:
        val["x"], val["y"] = _coordinate(text), _coordinate(text)
    else:
        prop = shape.choice(RESTYLED)
        props[prop] = shape.choice([v for v in STYLES[prop] if v != props[prop]])
    val["props"] = props
    return {"key": entry["key"], "val": val}


def shortest(entry: dict) -> dict:
    """The entry with every prop of a variable length at its shortest and no
    label: the least a payload of this key can weigh, from which the
    warm-up's mirror pads the label up to its model's length."""
    val = dict(entry["val"])
    val["props"] = dict(val["props"], w=20, h=20, text="", **{k: min(v, key=len) for k, v in STYLES.items()})
    return {"key": entry["key"], "val": val}


def shape_id(shape) -> str:
    return "shape:" + "".join(shape.choice(ID_ALPHABET) for _ in range(ID_CHARS))


# --- a room's loaded store ----------------------------------------------------


class Store(NamedTuple):
    """What the rooms of one class hold before traffic: one writer's records,
    a block each, loaded in stages."""

    client_id: int
    entries: List[dict]  # {key, val}, in array order; entry j is item (client_id, j)
    stage_sizes: List[int]
    first: bytes  # stage 0 after its first block's parent name: that block's content, then the other blocks
    later: List[bytes]  # stages 1.., whole: no block of theirs names the parent

    def stage(self, s: int, room: int) -> bytes:
        """Stage `s` as room `room`'s loader sends it: only the array's very
        first item names the array."""
        if s:
            return self.later[s - 1]
        return (section_head(self.client_id, 0, self.stage_sizes[0]) + b"\x08" + g._varuint(1)
                + _string(records_root(room)) + self.first)


def build_store(client_id: int, n_records: int, stage_blocks: int, shape, text) -> Store:
    n_stages = max(1, -(-n_records // stage_blocks))
    sizes = [n_records // n_stages + (s < n_records % n_stages) for s in range(n_stages)]
    entries = [new_record(shape_id(shape), j, shape, text) for j in range(n_records)]
    contents = [g._varuint(1) + any_bytes(e) for e in entries]
    # every block after the first: origin the item before it, no right origin
    blocks = [b"\x88" + _id((client_id, j - 1)) + contents[j] for j in range(1, n_records)]
    first = contents[0] + b"".join(blocks[: sizes[0] - 1]) + b"\x00"
    later, at = [], sizes[0]
    for n in sizes[1:]:
        later.append(section_head(client_id, at, n) + b"".join(blocks[at - 1 : at - 1 + n]) + b"\x00")
        at += n
    return Store(client_id, entries, sizes, first, later)


class Stores:
    """The configuration's `records` block: classes of rooms by rank, one
    `Store` each (as `grammar.Prefill` holds one template a class)."""

    def __init__(self, cfg: dict, n_rooms: int, seed: int):
        self.stores: List[Store] = []
        self.of_room: List[int] = []
        for t, c in enumerate(cfg["classes"]):
            self.stores.append(build_store(LOADER_CLIENT_BASE + t, c["records"], cfg["stage_blocks"],
                                           g.rng(g.LAYOUT, "store", t), g.rng(seed, "store", "text", t)))
            n = n_rooms - len(self.of_room) if c.get("rooms") is None else min(c["rooms"], n_rooms - len(self.of_room))
            self.of_room += [t] * n
        if len(self.of_room) != n_rooms:
            raise ValueError("the record classes do not cover every room: give the last one no `rooms`")

    def for_room(self, room: int) -> Store:
        return self.stores[self.of_room[room]]


# --- a synced session -----------------------------------------------------------


class RecordClient:
    """One client synced with its room's loaded store, making store changes
    as `YKeyValue` makes them. `ids` is the array as the client holds it:
    every item in order, the deleted ones (`dead`) included, because a push
    goes right after the last item that is not deleted and names the deleted
    item after it, if there is one, as its right origin. It holds the
    loader's records and its own pushes, not the others' (a client ahead of
    the room's fan-out). `shape` draws what decides a length, `text` the
    coordinates and the labels' characters."""

    def __init__(self, client_id: int, room: int, store: Store, base_clocks: Dict[int, int], shape, text):
        self.client_id = client_id
        self.root = records_root(room)
        self.store = store
        self.shape, self.text = shape, text
        self.ids: List[g.Id] = [(store.client_id, j) for j in range(len(store.entries))]
        self.dead: set = set()
        self.own: Dict[str, Tuple[g.Id, dict]] = {}  # key -> (item, entry) of this client's live pushes
        self.gone: set = set()  # loader entries this client has deleted or overwritten
        self.last_key: Optional[str] = None
        self.clocks = dict(base_clocks)
        self.clocks[store.client_id] = len(store.entries)
        self.clock = 0
        self.made = 0
        self.changes: List[Tuple[str, str]] = []  # (kind, key) of every change made, for the tests

    def _pick(self) -> Tuple[str, g.Id, dict]:
        """A record this client holds: the one it set last (a drag is a run
        of sets of one key) half of the time, else one of the loader's."""
        if self.last_key in self.own and self.shape.random() < 0.5:
            item, entry = self.own[self.last_key]
            return self.last_key, item, entry
        while True:
            j = self.shape.randrange(len(self.store.entries))
            if j not in self.gone:
                entry = self.store.entries[j]
                return entry["key"], (self.store.client_id, j), entry

    def _drop(self, key: str, item: g.Id) -> None:
        self.dead.add(item)
        if item[0] == self.store.client_id:
            self.gone.add(item[1])
        else:
            del self.own[key]

    def _push(self, entry: dict, dropped: Sequence[g.Id], target_len: Optional[int]) -> bytes:
        """The update that pushes `entry` and deletes `dropped` (already
        dead here: `remove` comes before `push_back`), and the push done on
        this client's copy. `target_len`: the label is cut to the length
        that brings the update to that many bytes, or as near as the entry at
        its shortest allows (the warm-up's mirror of a window payload)."""
        ids = self.ids
        i = len(ids) - 1
        while i >= 0 and ids[i] in self.dead:
            i -= 1
        origin, right = (ids[i] if i >= 0 else None), (ids[i + 1] if i + 1 < len(ids) else None)
        head, tail = section_head(self.client_id, self.clock, 1), delete_set_bytes(dropped)
        update = head + block_bytes(origin, right, self.root, entry) + tail
        if target_len is not None and len(update) != target_len:
            n = max(0, target_len - len(update))  # the entry came at its shortest, without a label
            n -= n > 127  # a label past 127 bytes takes a second length byte
            entry["val"]["props"]["text"] = _label(n, self.text)
            update = head + block_bytes(origin, right, self.root, entry) + tail
        item = (self.client_id, self.clock)
        ids.insert(i + 1, item)
        self.own[entry["key"]] = (item, entry)
        self.last_key = entry["key"]
        self.clock += 1
        self.clocks[self.client_id] = self.clock
        return update

    def next_change(self, kind: str, target_len: Optional[int] = None) -> g.Edit:
        """One store change, one wire update. `target_len` (the warm-up's)
        fixes a set's payload length through its label."""
        sized = (lambda e: e) if target_len is None else shortest
        if kind == "set_new":
            self.made += 1
            entry = new_record(shape_id(self.shape), len(self.store.entries) + self.made, self.shape, self.text)
            key = entry["key"]
            update = self._push(sized(entry), [], target_len)
        else:
            key, item, entry = self._pick()
            self._drop(key, item)
            if kind == "delete":
                update = b"\x00" + delete_set_bytes([item])
            else:
                update = self._push(sized(changed_record(entry, self.shape, self.text)), [item], target_len)
        self.changes.append((kind, key))
        return g.Edit(update, g.encode_sv(self.clocks), -1 if kind == "delete" else 1)


# --- the plan -------------------------------------------------------------------


def _kinds(shares: Dict[str, float], n: int) -> List[str]:
    """`n` store changes in the shares' exact counts (largest remainder),
    shuffled by the layout."""
    total = sum(shares[k] for k in KINDS)
    exact = {k: n * shares[k] / total for k in KINDS}
    counts = {k: int(v) for k, v in exact.items()}
    for k in sorted(KINDS, key=lambda k: counts[k] - exact[k])[: n - sum(counts.values())]:
        counts[k] += 1
    kinds = [k for k in KINDS for _ in range(counts[k])]
    g.rng(g.LAYOUT, "records", "kinds").shuffle(kinds)
    return kinds


def _client(client_id: int, room: int, stores: Stores, prefill, seed: int, stream: str, i: int) -> RecordClient:
    tpl = prefill.for_room(room)
    return RecordClient(client_id, room, stores.for_room(room), {tpl.client_id: tpl.chars},
                        g.rng(g.LAYOUT, stream, "session", i), g.rng(seed, stream, "text", i))


def _op(session: int, room: int, edit: g.Edit, frames: Optional[dict] = None) -> Op:
    """`frames` keeps the frame of an update object: a stage every room of a
    class loads is framed once, not once a room."""
    frame = frames.get(id(edit.update)) if frames is not None else None
    if frame is None:
        frame = g.update_frame(edit.update)
        if frames is not None:
            frames[id(edit.update)] = frame
    return Op("update", session, room, frame, update=edit.update)


def dispatches(ops: Sequence[Op], tick: int) -> List[List[Op]]:
    """The dispatches a saturated pool is taken in: ticks of exactly `tick`
    frames, and within a tick one dispatch a depth, carrying every room's
    next queued update."""
    out = []
    for i in range(0, len(ops), tick):
        by_room: Dict[int, List[Op]] = {}
        for op in ops[i : i + tick]:
            by_room.setdefault(op.room, []).append(op)
        for depth in range(max(map(len, by_room.values()))):
            out.append([q[depth] for q in by_room.values() if len(q) > depth])
    return out


def make_up(lanes: Sequence[Op]) -> tuple:
    """What a dispatch's programs can be keyed by: its lanes, those of them
    that are delete-only updates (the fast lane's), and the power-of-two
    buckets of its wire bytes and of its longest payload."""
    lens = [len(op.update) for op in lanes]
    return (len(lanes), sum(1 for op in lanes if op.update[0] == 0), _bucket(sum(lens), 256),
            _bucket(max(lens) + 16, 64))


def _kind_of(update: bytes) -> str:
    if update[0] == 0:
        return "delete"
    return "set_new" if update[-1] == 0 else "set_existing"


def mirror_ticks(window: Sequence[List[Op]], writers: Sequence[RecordClient], rooms: Sequence[int]) -> List[List[Op]]:
    """One warm-up tick for every distinct make-up among the window's
    dispatches: the first such dispatch again, lane for lane the same kind
    of change with a payload of the same length, from `writers` (one a
    room of `rooms`, all distinct)."""
    seen, ticks = set(), []
    for lanes in window:
        key = make_up(lanes)
        if key in seen:
            continue
        seen.add(key)
        owed = 0  # bytes a delete-only lane fell short of its model by: made up in the next set
        tick = []
        for w, model in enumerate(sorted(lanes, key=lambda op: op.update[0] != 0)):  # the deletes first
            kind = _kind_of(model.update)
            edit = writers[w].next_change(kind, None if kind == "delete" else len(model.update) + owed)
            owed += len(model.update) - len(edit.update)
            tick.append(_op(w, rooms[w], edit))
        if make_up(tick) != key:
            raise ValueError(f"the warm-up cannot mirror a window dispatch of make-up {key}: got {make_up(tick)}")
        ticks.append(tick)
    return ticks


def plan(deploy: dict, mix: dict, prefill, seed: int, seconds: float) -> Plan:
    if mix["arrival"] != "saturated" or mix.get("client_ids") != "preregistered":
        raise ValueError("record_mix makes a saturated pool of store changes from preregistered writers")
    n_rooms = deploy["n_docs"]
    n_sessions = mix["sessions"]
    per = mix["edits_per_session"]
    tick = mix["tick_max_frames"]
    stores = Stores(deploy["records"], n_rooms, seed)

    quotas = g.zipf_quotas(n_rooms, n_sessions, mix["zipf_s"])
    rooms = [k for k, q in enumerate(quotas) for _ in range(q)]
    g.rng(g.LAYOUT, "traffic", "rooms").shuffle(rooms)  # `grammar.build_sessions`' rooms
    kinds = _kinds(mix["store_changes"], n_sessions * per)
    sessions: List[g.Session] = []
    for i in range(n_sessions):
        c = _client(g.CLIENT_BASE + i, rooms[i], stores, prefill, seed, "traffic", i)
        sessions.append(g.Session(i, rooms[i], c.client_id, [c.next_change(k) for k in kinds[i * per : (i + 1) * per]]))
    ops = [_op(i, sessions[i].room, sessions[i].edits[j]) for i, j in g.interleave(sessions, g.LAYOUT)]

    # one loader session a room, numbered after the traffic's; stage-major, so
    # that a tick of the preload holds `tick` rooms and is one dispatch
    loaders = []
    for k in range(n_rooms):
        store = stores.for_room(k)
        clocks, edits, at = {prefill.for_room(k).client_id: prefill.for_room(k).chars}, [], 0
        for s, n in enumerate(store.stage_sizes):
            at += n
            clocks[store.client_id] = at
            edits.append(g.Edit(store.stage(s, k), g.encode_sv(clocks), n))
        loaders.append(g.Session(n_sessions + k, k, store.client_id, edits))
    frames: dict = {}
    preload = [_op(ld.sid, ld.room, ld.edits[s], frames)
               for s in range(max(len(ld.edits) for ld in loaders)) for ld in loaders if s < len(ld.edits)]

    # warm-up: `tick` writers in rooms of their own (where `session_mix` puts
    # its sweep), mirroring every make-up of a window dispatch
    warm_rooms = [(n_rooms // 2 + w) % n_rooms for w in range(min(tick, n_rooms))]
    writers = [_client(g.WARM_CLIENT_BASE + w, k, stores, prefill, seed, "warm", w) for w, k in enumerate(warm_rooms)]
    window = dispatches(ops, tick)
    warm = mirror_ticks(window, writers, warm_rooms)

    loaded = sum(len(stores.for_room(k).entries) for k in range(n_rooms))
    lens = [len(op.update) for op in ops if op.update[0]]
    print(f"bench: record stores: {loaded} records to load as {len(preload)} preload updates of at most "
          f"{deploy['records']['stage_blocks']} blocks = {100.0 * loaded / (n_rooms * deploy['capacity']):.1f}% of the slots "
          f"({[(stores.of_room.count(t), len(s.entries)) for t, s in enumerate(stores.stores)]} rooms x records); "
          f"a set is {min(lens)}-{max(lens)} B on the wire; {len(window)} window dispatches of "
          f"{len({make_up(d) for d in window})} make-ups, {len(warm)} warm-up ticks", flush=True)
    return Plan(
        clients=[s.client_id for s in sessions]
        + [c.client_id for c in writers]
        + [s.client_id for s in stores.stores]
        + [t.client_id for t in prefill.templates],
        session_rooms=[s.room for s in sessions] + [ld.room for ld in loaders],
        preload=preload,
        warm=warm,
        warm_session_rooms=warm_rooms,
        ops=ops,
        saturated=True,
        repeat=bool(mix.get("repeat", False)),
        tick_max_frames=tick,
        sessions=sessions + loaders,
        notes={
            "update_len_min": min(lens), "update_len_max": max(lens), "update_len_mean": sum(lens) / len(lens),
            "hot_room_sessions": max(quotas),
            "needs_sync_warm": False,
            "needs_update_warm": False,  # the sweep's typists send text; this window's programs are the mirror's
            "lane_counts": sorted({len(d) for d in window}),
            "fast_lane_counts": sorted({make_up(d)[1] for d in window}),
            "host_lane_dispatches": sum(1 for d in window if make_up(d)[1] < len(d)),
            "records_loaded": loaded,
        },
    )
