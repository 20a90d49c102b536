"""Compaction on the served path (PR 43): a room typed past its capacity
through `receive_frames` / `flush_device` keeps taking updates, and after
every compaction its rows are what `ytpu.core.Doc` holds for the same
updates: text, state vector, canonical full-state encoding and row count.

The host squashes at commit (`Item.try_squash`) and collects deleted
content (`doc.gc`); the device appends a row an insert and does both when
the room's rows near its capacity (`BatchIngestor._make_room`). Row counts
differ by what PERF.md section 4 names: one anchor row a root after the
first.
"""

import random

import numpy as np
import pytest

from ytpu.core import Doc
from ytpu.core.state_vector import StateVector
from ytpu.models import ingest as ingest_mod
from ytpu.sync.device_server import DeviceSyncServer
from ytpu.sync.protocol import Message, SyncMessage, message_reader
from ytpu.utils import metrics

CAPACITY = 256
ROOM = "room"


def _frame(update: bytes) -> bytes:
    return Message.sync(SyncMessage.update(update)).encode_v1()


class Client:
    """A Yjs client: every transaction is one update, as it is sent."""

    def __init__(self, client_id: int):
        self.doc = Doc(client_id=client_id)
        self.sent = []
        self.doc.observe_update_v1(lambda payload, *_: self.sent.append(payload))
        self.cursor = 0

    @property
    def text(self):
        return self.doc.get_text("text")

    def type(self, ch: str) -> None:
        with self.doc.transact() as txn:
            self.text.insert(txn, self.cursor, ch)
        self.cursor += len(ch.encode("utf-16-le")) // 2  # the index counts UTF-16 units

    def backspace(self) -> None:
        if self.cursor:
            self.cursor -= 1
            with self.doc.transact() as txn:
                self.text.remove_range(txn, self.cursor, 1)

    def jump(self, r: random.Random) -> None:
        self.cursor = r.randint(0, len(self.text.get_string()))

    def take(self) -> list:
        out, self.sent = self.sent, []
        return out


def _host_rows(doc: Doc) -> int:
    return sum(len(lst.blocks) for lst in doc.store.blocks.clients.values())


def _canonical(update: bytes):
    fresh = Doc(client_id=2)
    fresh.apply_update_v1(update)
    return (
        fresh.get_text("text").get_string(),
        dict(fresh.state_vector().clocks),
        fresh.encode_state_as_update_v1(),
    )


class Served:
    """One room of a served batch beside the host oracle fed the same."""

    def __init__(self, **server_args):
        self.server = DeviceSyncServer(
            n_docs=server_args.pop("n_docs", 4), capacity=CAPACITY,
            device_authoritative=True, **server_args,
        )
        self.session, _ = self.server.connect_frames(ROOM)
        self.oracle = Doc(client_id=1)
        self.compactions = metrics.counter("ingest.room_compactions")
        self.seen = self.compactions.value
        self.checked = 0
        self.extra_rows = 0  # rows the device holds beside the host's blocks

    def send(self, updates) -> None:
        for u in updates:
            assert self.server.receive_frames(self.session, _frame(u)) == []
            self.server.flush_device()
            self.oracle.apply_update_v1(u)
            if self.compactions.value != self.seen:
                self.seen = self.compactions.value
                self.check()

    def rows(self) -> int:
        slot = self.server.slot_of(ROOM)
        return int(np.asarray(self.server.ingestor.state.n_blocks)[slot])

    def check(self) -> None:
        """The room against the oracle, as it stands (the update that
        followed the compaction is in both)."""
        server, want = self.server, self.oracle
        slot = server.slot_of(ROOM)
        assert int(np.asarray(server.ingestor.state.error)[slot]) == 0
        text = want.get_text("text").get_string()
        assert server.device_text(ROOM) == text
        sv = dict(want.state_vector().clocks)
        assert dict(server.device_state_vector(ROOM).clocks) == sv
        diff = server.device_encode_diff(ROOM, StateVector())
        assert server._diff_pipeline.stats.fallback_docs == 0
        assert _canonical(diff) == (
            text, sv, _canonical(want.encode_state_as_update_v1())[2]
        )
        self.checked += 1

    def check_rows_after_compaction(self) -> None:
        """Compact now (whatever the policy says) and count."""
        ing = self.server.ingestor
        ing._compact([self.server.slot_of(ROOM)])
        self.check()
        assert self.rows() == _host_rows(self.oracle) + self.extra_rows


def _plain(served: Served, r: random.Random, n: int) -> None:
    c = Client(11)
    for i in range(n):
        if i % 97 == 0:
            c.jump(r)
        c.type(r.choice("abcdefghij"))
        served.send(c.take())


def _backspaces(served: Served, r: random.Random, n: int) -> None:
    c = Client(11)
    for i in range(n):
        if i % 150 == 0:
            c.jump(r)
        if r.random() < 0.03:
            c.backspace()
        else:
            c.type(r.choice("abcdefghijé\U0001f600"))
        served.send(c.take())


def _two_typists(served: Served, r: random.Random, n: int) -> None:
    a, b = Client(11), Client(12)
    for i in range(n):
        who = a if r.random() < 0.5 else b
        if i % 53 == 0:
            # the two see each other's text now and then
            who.doc.apply_update_v1(
                served.server.device_encode_diff(ROOM, who.doc.state_vector())
            )
            who.take()
            who.jump(r)
        if r.random() < 0.05:
            who.backspace()
        else:
            who.type(r.choice("abcdefghij"))
        served.send(who.take())


def _map_and_nested(served: Served, r: random.Random, n: int) -> None:
    c = Client(11)
    doc = c.doc
    from ytpu.types.shared import MapPrelim

    with doc.transact() as txn:
        c.text.insert(txn, 0, "seed")
    with doc.transact() as txn:
        doc.get_map("meta").insert(txn, "title", "a room")
    with doc.transact() as txn:
        doc.get_map("meta").insert(txn, "nested", MapPrelim({"k": 1}))
    served.send(c.take())
    served.extra_rows = 1  # the second root's anchor row
    c.cursor = 4
    for i in range(n):
        if i % 40 == 0:
            with doc.transact() as txn:
                doc.get_map("meta").insert(txn, "title", f"v{i}")
            c.jump(r)
        c.type(r.choice("abcdefghij"))
        served.send(c.take())


SCRIPTS = {
    "plain-typing": (_plain, {}),
    "backspaces": (_backspaces, {}),
    "two-typists": (_two_typists, {}),
    "map-key-and-nested-type": (_map_and_nested, {}),
    "shard-docs": (_plain, {"shard_docs": True, "n_docs": 8}),
}


@pytest.mark.parametrize("name", list(SCRIPTS))
def test_room_typed_past_four_times_its_capacity(name):
    script, server_args = SCRIPTS[name]
    served = Served(**dict(server_args))
    refusals = metrics.counter("ingest.capacity_refusals").value
    script(served, random.Random(43), 4 * CAPACITY + 40)
    assert served.checked >= 3, "the policy compacted the room on the way"
    assert metrics.counter("ingest.capacity_refusals").value == refusals
    served.check_rows_after_compaction()
    assert served.rows() < CAPACITY


def _typed_room(n: int = 300):
    served = Served()
    c = Client(11)
    for _ in range(n):
        c.type("x")
        served.send(c.take())
    return served, c


@pytest.mark.parametrize("cut", ["inside-a-squashed-row", "empty"])
def test_sync_step_1_after_a_compaction(cut):
    """A SyncStep1 whose state vector cuts a squashed row, and an empty
    one, answered through the native finisher."""
    served, c = _typed_room()
    assert served.checked >= 1
    served.check_rows_after_compaction()
    assert served.rows() == 1  # one run, one row
    sv = StateVector({11: 137} if cut == "inside-a-squashed-row" else {})
    reader = Doc(client_id=5)
    if sv.clocks:  # a client that has the first 137 characters and no more
        reader.apply_update_v1(_prefix_update(137))
    step1 = Message.sync(SyncMessage.step1(sv)).encode_v1()
    replies = served.server.receive_frames(served.session, step1)
    assert served.server._diff_pipeline.stats.fallback_docs == 0
    assert len(replies) == 1
    reader.apply_update_v1(next(iter(message_reader(replies[0]))).body.payload)
    assert reader.get_text("text").get_string() == "x" * 300
    assert dict(reader.state_vector().clocks) == {11: 300}


def _prefix_update(n: int) -> bytes:
    d = Doc(client_id=11)
    with d.transact() as txn:
        d.get_text("text").insert(txn, 0, "x" * n)
    return d.encode_state_as_update_v1()


def test_policy_does_not_fire_twice_on_a_room_inside_the_reserve():
    """A room whose squashed form sits inside the reserve is compacted
    again only once it has grown by the regrow share."""
    served = Served()
    c = Client(11)
    r = random.Random(7)
    reserve = CAPACITY // ingest_mod.COMPACT_RESERVE_SHARE
    regrow = CAPACITY // ingest_mod.COMPACT_REGROW_SHARE
    # rows that nothing squashes: single characters at the front, each
    # typed after a jump, until the room sits inside its reserve
    while served.rows() < CAPACITY - reserve + 2:
        c.cursor = 0
        c.type(r.choice("abcdefghij"))
        served.send(c.take())
    fired = served.compactions.value
    rows = served.rows()
    assert rows > CAPACITY - reserve, "inside the reserve, squashed as it is"
    steps = 0
    while served.compactions.value == fired:
        c.cursor = 0
        c.type("z")
        served.send(c.take())
        steps += 1
        assert steps <= regrow + 2
    # it waited until the room had grown by the regrow share (a step can
    # add three rows at most by the host's count), and did not fire a step
    assert steps >= regrow // ingest_mod.ROWS_PER_ROW - 1
    assert served.compactions.value == fired + 1



def test_a_load_whose_worst_case_passes_the_reserve_is_not_compacted():
    """A loader's update of several blocks can add three rows a block at
    most and adds about one: the host's count runs ahead of the room, trips,
    is made exact, and nothing is due."""
    served = Served()
    loader = Doc(client_id=21)
    r = random.Random(3)
    recounts = metrics.counter("ingest.row_recounts").value
    fired = served.compactions.value
    for _ in range(30):
        sv = loader.state_vector()
        with loader.transact() as txn:
            text = loader.get_text("text")
            for _ in range(3):  # 3 blocks an update: 9 rows by the host's count
                text.insert(txn, r.randint(0, len(text.get_string())), r.choice("abcdefghij"))
        served.send([loader.encode_state_as_update_v1(sv)])
    assert served.rows() < CAPACITY - CAPACITY // ingest_mod.COMPACT_RESERVE_SHARE
    assert metrics.counter("ingest.row_recounts").value > recounts
    assert served.compactions.value == fired
    served.check()
