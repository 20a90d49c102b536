"""A text CRDT in straightforward Python: the co-edit cell's plain reference.

The benchmark's own copy: it imports nothing from `ytpu`, neither the host
CRDT (`ytpu.core`) nor the device path, so the three can be held to each
other (`tests/test_coedit.py`, `benchmark/tests/test_coedit_mix.py`). The
same operations on the same data give the same answers: text and state
vector.

A document is a list of items in document order, one **character** an item,

    (client, clock, origin, right_origin, char, deleted)

`origin` and `right_origin` the ids `(client, clock)` of the characters that
stood left and right of the cursor when the character was typed (None at
the document's ends), tombstones included. An item is integrated by the
published YATA rule, as Yjs `Item.integrate` and yrs `block.rs:537-602`
state it: start right of the origin and walk the items up to the right
origin; an item `o` met on the way either

- names the same origin (case 1): it stays left of the new item if its
  client id is smaller (ids compare as unsigned integers); if it is larger
  and names the same right origin too, the walk ends here;
- or names an origin that the walk has passed (case 2,
  `itemsBeforeOrigin`): it stays left of the new item unless that origin is
  one of the items the new item still conflicts with;
- or names an origin left of the new item's: the walk ends.

Deletes are id ranges (a delete set): the characters in a range become
tombstones and keep their place. What is not yet causally ready waits in a
queue (`waiting`) and is tried again after everything that is integrated:
a block whose clock does not continue its client's, or whose origin or
right origin the document does not hold yet; the part of a delete range
past what it holds.

Departures from the published rule, each because this is a text of single
characters under one root:

- an item is one character, not a run: a block of n characters is n items,
  the k-th naming the (k-1)-th as its origin and the block's right origin
  as its own, which is what Yjs's `splitItem` gives a run cut there. The
  rule then puts it right behind the (k-1)-th (no item the document holds
  can name a character it had not got), so only a block's first character
  runs the walk and the others are placed behind it;
- `getItem(store, o.origin)` is the id itself: no run to look the id up in;
- one root, a text: a block's parent (the root's name on the wire where it
  has neither origin nor right origin) is read and not kept; `parentSub`,
  every content kind but a string (4), GC and Skip runs are refused;
- a character is one UTF-16 unit (the benchmark types ASCII), so a clock
  counts characters;
- a block the document holds already (a redelivery) is dropped whole, one
  it holds in part is cut to what is new.

`keys` holds the items' ids as integers in the same order, so that an id's
place is `list.index` and not a loop in Python; nothing else is kept.
"""

from __future__ import annotations

from typing import Dict, List, NamedTuple, Optional, Tuple

Id = Tuple[int, int]


class Item(NamedTuple):
    client: int
    clock: int
    origin: Optional[Id]
    right_origin: Optional[Id]
    char: str
    deleted: bool


def _key(i: Id) -> int:
    return (i[0] << 40) | i[1]


# --- the wire (v1), as far as a text update goes --------------------------------


class _Reader:
    def __init__(self, data: bytes):
        self.data, self.at = data, 0

    def varuint(self) -> int:
        n = shift = 0
        while True:
            b = self.data[self.at]
            self.at += 1
            n |= (b & 0x7F) << shift
            if b < 0x80:
                return n
            shift += 7

    def string(self) -> str:
        n = self.varuint()
        s = self.data[self.at : self.at + n].decode()
        self.at += n
        return s

    def id(self) -> Id:
        return (self.varuint(), self.varuint())


def decode_update(update: bytes):
    """(blocks, deletes) of a v1 update that holds strings and a delete set:
    blocks as `(client, clock, origin, right_origin, text)` in wire order,
    deletes as `(client, clock, length)`."""
    r = _Reader(update)
    blocks, deletes = [], []
    for _ in range(r.varuint()):
        n_blocks, client, clock = r.varuint(), r.varuint(), r.varuint()
        for _ in range(n_blocks):
            info = r.data[r.at]
            r.at += 1
            if info & 0x1F != 4 or info & 0x20:
                raise ValueError(f"block info {info:#x}: this reference reads string content under the root only")
            origin = r.id() if info & 0x80 else None
            right = r.id() if info & 0x40 else None
            if origin is None and right is None:
                if r.varuint() != 1:
                    raise ValueError("a parent given by id: this reference has one root")
                r.string()  # the root's name
            text = r.string()
            blocks.append((client, clock, origin, right, text))
            clock += len(text)
    for _ in range(r.varuint()):
        client = r.varuint()
        for _ in range(r.varuint()):
            deletes.append((client, r.varuint(), r.varuint()))
    if r.at != len(update):
        raise ValueError("bytes left over after the delete set")
    return blocks, deletes


# --- the document ---------------------------------------------------------------


class Text:
    def __init__(self):
        self.items: List[Item] = []  # document order, tombstones included
        self.keys: List[int] = []  # `_key` of items[i]'s id: an id's place is `keys.index`
        self.state: Dict[int, int] = {}  # client -> the next clock the document lacks
        self.waiting: List[tuple] = []  # ("block", ...) and ("delete", ...) not yet causally ready
        self.scanned = 0  # items the last integrated block's walk passed
        self.case2 = 0  # of them, those it judged by the second case

    def copy(self) -> "Text":
        """A document equal to this one (items are immutable: the lists are copied)."""
        t = Text()
        t.items, t.keys, t.state, t.waiting = self.items.copy(), self.keys.copy(), dict(self.state), list(self.waiting)
        return t

    # --- reads ---------------------------------------------------------------

    def text(self) -> str:
        return "".join(it.char for it in self.items if not it.deleted)

    def state_vector(self) -> Dict[int, int]:
        return {c: k for c, k in self.state.items() if k}

    def has(self, i: Optional[Id]) -> bool:
        return i is None or i[1] < self.state.get(i[0], 0)

    def index(self, i: Id) -> int:
        return self.keys.index(_key(i))

    # --- writes --------------------------------------------------------------

    def insert_block(self, client: int, clock: int, origin: Optional[Id], right: Optional[Id], text: str) -> Optional[int]:
        """Integrate a block, or queue it if it is not ready; the place of its
        first character in `items`, None where it waits or was held already."""
        have = self.state.get(client, 0)
        if clock + len(text) <= have:
            return None  # a redelivery
        if clock < have:  # held in part: what is new starts behind the last character held
            origin, text, clock = (client, have - 1), text[have - clock :], have
        if clock > have or not self.has(origin) or not self.has(right):
            self.waiting.append(("block", client, clock, origin, right, text))
            return None
        at = self._place(client, origin, right)
        self.items[at:at] = [
            Item(client, clock + k, origin if k == 0 else (client, clock + k - 1), right, ch, False)
            for k, ch in enumerate(text)
        ]
        self.keys[at:at] = [_key((client, clock + k)) for k in range(len(text))]
        self.state[client] = clock + len(text)
        return at

    def _place(self, client: int, origin: Optional[Id], right: Optional[Id]) -> int:
        """Where an item of `client` with these neighbours goes: the YATA walk."""
        left = self.index(origin) if origin is not None else -1
        # the right origin stands right of the origin in every replica: look from there
        end = self.keys.index(_key(right), left + 1) if right is not None else len(self.items)
        before_origin = set()  # Yjs: itemsBeforeOrigin
        conflicting = set()  # Yjs: conflictingItems
        self.scanned = self.case2 = 0
        o = left + 1
        while o < end:
            it = self.items[o]
            oid = (it.client, it.clock)
            before_origin.add(oid)
            conflicting.add(oid)
            self.scanned += 1
            if it.origin == origin:  # case 1: the same origin, the client id decides
                if it.client < client:
                    left = o
                    conflicting.clear()
                elif it.right_origin == right:
                    break
            elif it.origin is not None and it.origin in before_origin:  # case 2
                self.case2 += 1
                if it.origin not in conflicting:
                    left = o
                    conflicting.clear()
            else:
                break
            o += 1
        return left + 1

    def delete_range(self, client: int, clock: int, length: int) -> int:
        """Tombstone the characters of an id range; the part past what the
        document holds waits. How many it held (deleted before or not)."""
        have = self.state.get(client, 0)
        end = min(clock + length, have)
        if clock + length > have:
            self.waiting.append(("delete", client, max(clock, have), clock + length - max(clock, have)))
        for k in range(clock, end):
            at = self.index((client, k))
            it = self.items[at]
            if not it.deleted:
                self.items[at] = it._replace(deleted=True)
        return max(0, end - clock)

    def retry(self) -> None:
        """Try what waits again, until a pass integrates nothing."""
        while self.waiting:
            queue, self.waiting = self.waiting, []
            for kind, *args in queue:
                if kind == "block":
                    self.insert_block(*args)
                else:
                    self.delete_range(*args)
            if self.waiting == queue:
                return

    def apply_update(self, update: bytes) -> None:
        blocks, deletes = decode_update(update)
        for b in blocks:
            self.insert_block(*b)
        self.retry()
        for d in deletes:
            self.delete_range(*d)
        self.retry()
