"""The doc-sharded server (`DeviceSyncServer(shard_docs=True)`) against the
host CRDT `ytpu.core.Doc`, and against the unsharded server, byte for byte.

The deployment the benchmark's `yws-rooms-4k-x4` configuration runs on four
chips, here on the suite's 8 forced host devices at 16 rooms x capacity 512
(2 rooms a device). Traffic in the benchmark's shape: rooms prefilled
through the served path, sessions synced with their room's document that
type inserts and deletes, one wire update an edit, several updates to one
room in a tick, `flush_device(max_steps=1)` until the queues are empty. The
checks are `benchmark/oracle.py`'s, each with the limit 0. At 16 rooms every
step is the dense one; two cases at 128 rooms x capacity 256 take the compact
step (`BatchIngestor._active_slots`) on the sharded state.
"""

import random

import jax
import numpy as np
import pytest

from ytpu.core import Doc
from ytpu.core.state_vector import StateVector
from ytpu.sync.device_server import DeviceSyncServer
from ytpu.sync.protocol import Message, SyncMessage
from ytpu.utils import metrics

N_ROOMS, CAPACITY = 16, 512
ROOT = "text"
TICK = 6  # frames a tick
# where the compact integrate step engages (16 rooms stay dense: 16 > 16 // 4):
# 16 rooms a device, and a tick's rooms are under a quarter of them
MANY_ROOMS, SMALL_CAPACITY = 128, 256
WATCHED = ("ingest.fast_recoveries", "encode.demotions", "lane.demotions", "net.bad_frames")
STEPS = ("ingest.compact_steps", "ingest.dense_steps")


def _frame(update: bytes) -> bytes:
    return Message.sync(SyncMessage.update(update)).encode_v1()


def _word(r: random.Random) -> str:
    return "".join(r.choice("abcdefghij") for _ in range(r.randint(3, 8)))


class _Typist:
    """A real client `Doc`; every edit is the wire update it emits."""

    def __init__(self, client_id: int, base=()):
        self.doc = Doc(client_id=client_id)
        for u in base:
            self.doc.apply_update_v1(u)
        self.sent = []
        self.doc.observe_update_v1(lambda p, o, t: self.sent.append(p))
        self.text = self.doc.get_text(ROOT)

    def insert(self, r: random.Random) -> bytes:
        with self.doc.transact() as txn:
            self.text.insert(txn, r.randint(0, len(self.text.get_string())), _word(r))
        return self.sent[-1]

    def delete(self, r: random.Random) -> bytes:
        length = len(self.text.get_string())
        with self.doc.transact() as txn:
            self.text.remove_range(txn, r.randint(0, length - 4), r.randint(1, 3))
        return self.sent[-1]

    def edit(self, r: random.Random) -> bytes:
        long_enough = len(self.text.get_string()) > 8
        return self.delete(r) if long_enough and r.random() < 0.25 else self.insert(r)


def _traffic(seed: int, rooms, n_sessions: int = 12, edits: int = 4):
    """(prefill stages, ticks): per stage one update a room; per tick a list
    of (room, update). Sessions are dealt to `rooms` most to the first, so a
    tick carries several updates of one room; a session does not see the
    others' edits, as a provider between two handshakes does not."""
    r = random.Random(seed)
    stages = [{}, {}]
    for k in rooms:
        loader = _Typist(900_000 + k)
        for stage in stages:  # one update a stage, as the benchmark's loader sends it
            before = loader.doc.state_vector()
            for _ in range(3):  # few enough for the edits' own row bucket: one integrate program
                loader.insert(r)
            stage[k] = loader.doc.encode_state_as_update_v1(before)
    base = {k: [stage[k] for stage in stages] for k in rooms}
    weights = [1.0 / (i + 1) for i in range(len(rooms))]
    sessions = [
        (k, _Typist(7000 + i, base[k]))
        for i, k in enumerate(r.choices(rooms, weights=weights, k=n_sessions))
    ]
    pool = [(k, t.edit(r)) for _ in range(edits) for k, t in sessions]
    # a session's own updates stay in order (the pool is edit-major)
    ticks = [pool[i : i + TICK] for i in range(0, len(pool), TICK)]
    return stages, ticks


def _serve(shard_docs: bool, connect, stages, ticks, n_rooms=N_ROOMS, capacity=CAPACITY):
    server = DeviceSyncServer(
        n_docs=n_rooms, capacity=capacity, device_authoritative=True, shard_docs=shard_docs
    )
    # client ids preregistered, as the benchmark does: a first-seen client
    # grows the lookup tables, and every size is a program family of its own
    loaders, typists = range(900_000, 900_000 + n_rooms), range(7000, 7016)
    for client in [*loaders, *typists]:
        server.ingestor.enc.interner.intern(client)
    sess = {k: server.connect_frames(f"room{k}")[0] for k in connect}
    steps_mixed = 0

    def tick(frames):
        nonlocal steps_mixed
        for k, u in frames:
            assert server.receive_frames(sess[k], _frame(u)) == []
        while server.pending_device_updates():
            fast, slow = server.ingestor.fast_docs, server.ingestor.slow_docs
            # a step's uploads go onto the mesh (`BatchIngestor._upload`): nothing
            # is left on one device for jax to carry across inside a jitted call
            with jax.transfer_guard_device_to_device("disallow"):
                assert server.flush_device(max_steps=1) == 1
            jax.block_until_ready(server.ingestor.state)
            if server.ingestor.fast_docs > fast and server.ingestor.slow_docs > slow:
                steps_mixed += 1

    for stage in stages:
        tick(sorted(stage.items()))
    for frames in ticks:
        tick(frames)
    return server, steps_mixed


def _canonical(update: bytes):
    fresh = Doc(client_id=2)
    fresh.apply_update_v1(update)
    return (
        fresh.get_text(ROOT).get_string(),
        dict(fresh.state_vector().clocks),
        fresh.encode_state_as_update_v1(),
    )


def _check_against_oracle(server, connect, stages, ticks):
    """`benchmark/oracle.py`'s comparisons, every one exact."""
    taken = {k: [] for k in connect}
    for stage in stages:
        for k, u in sorted(stage.items()):
            taken[k].append(u)
    for frames in ticks:
        for k, u in frames:
            taken[k].append(u)
    rooms = [k for k in connect if taken[k]]
    diffs = server.device_encode_diff_many([(f"room{k}", StateVector()) for k in rooms])
    for k, diff in zip(rooms, diffs):
        want = Doc(client_id=1)
        for u in taken[k]:
            want.apply_update_v1(u)
        want_text = want.get_text(ROOT).get_string()
        want_sv = dict(want.state_vector().clocks)
        assert server.device_text(f"room{k}") == want_text, k
        assert dict(server.device_state_vector(f"room{k}").clocks) == want_sv, k
        assert _canonical(diff) == (
            want_text, want_sv, _canonical(want.encode_state_as_update_v1())[2]
        ), k
    ing = server.ingestor
    written = {server.slot_of(f"room{k}") for k in rooms}
    n_blocks, start = np.asarray(ing.state.n_blocks), np.asarray(ing.state.start)
    for slot in set(range(ing.n_docs)) - written:  # never assigned, or connected and silent
        assert n_blocks[slot] == 0 and start[slot] == -1, slot
    assert not np.asarray(ing.state.error).any()
    assert not [d for d in range(ing.n_docs) if ing.pending_update(d) or ing.pending_ds(d)]
    assert ing.fast_recoveries == 0 and not server._host_tenants
    assert server._diff_pipeline.stats.fallback_docs == 0
    return dict(zip(rooms, diffs))


def _spans_every_device(server) -> None:
    """What `chip_smoke.py --chips 4` checks after its last flush."""
    n_dev = len(jax.devices())
    assert n_dev == 8  # tests/conftest.py
    local = [
        i for i, a in enumerate(jax.tree.leaves(server.ingestor.state))
        if len(a.sharding.device_set) != n_dev
    ]
    assert not local, f"state planes {local} no longer span {n_dev} devices"
    assert metrics.gauge("ingest.state_shards").value == n_dev
    assert server._telemetry_provider()["state_shards"] == n_dev


# rooms with sessions: one on every shard (2 rooms a device) and the last
# slots left untouched; or the first shard's two rooms only, where the
# benchmark's Zipf ranks put every hot room
EVERY_SHARD = [0, 1, 2, 4, 6, 8, 10, 12, 14]
FIRST_SHARD = [0, 1]


@pytest.mark.parametrize("rooms", [EVERY_SHARD, FIRST_SHARD], ids=["every_shard", "first_shard"])
def test_sharded_server_equals_the_oracle_and_the_unsharded_server(rooms):
    stages, ticks = _traffic(28_000_001 + len(rooms), rooms)
    connect = rooms + [15]  # a room that connects and never sends
    before = {n: metrics.counter(n).value for n in WATCHED}
    sharded, _ = _serve(True, connect, stages, ticks)
    _spans_every_device(sharded)
    got = _check_against_oracle(sharded, connect, stages, ticks)
    _spans_every_device(sharded)  # the diffs' fan-out left the planes where they were
    assert sharded.ingestor.fast_docs > 0
    plain, _ = _serve(False, connect, stages, ticks)
    assert metrics.gauge("ingest.state_shards").value == 1  # the newest ingestor's
    assert got == _check_against_oracle(plain, connect, stages, ticks)  # the same bytes
    assert sharded.ingestor.fast_docs == plain.ingestor.fast_docs
    assert sharded.ingestor.slow_docs == plain.ingestor.slow_docs
    assert {n: metrics.counter(n).value for n in WATCHED} == before
    assert any(len({k for k, _ in frames}) < len(frames) for frames in ticks)  # a room twice in a tick


# at 128 rooms, 16 a device: a room on every device and the last slot, or
# three rooms of the first device
MANY_EVERY_SHARD = [0, 17, 34, 51, 68, 85, 102, 119, 127]
MANY_FIRST_SHARD = [0, 1, 5]


@pytest.mark.parametrize(
    "rooms", [MANY_EVERY_SHARD, MANY_FIRST_SHARD], ids=["every_shard", "first_shard"]
)
def test_compact_steps_on_a_sharded_state(rooms):
    """Every step carries under a quarter of the 128 rooms, so every step is
    the compact one: the rooms gathered off the devices that hold them,
    integrated, scattered back, and the planes left laid by room."""
    stages, ticks = _traffic(29_000_001 + len(rooms), rooms, n_sessions=8, edits=3)
    connect = rooms + [100]  # a room that connects and never sends
    before = {n: metrics.counter(n).value for n in WATCHED + STEPS}
    sharded, _ = _serve(True, connect, stages, ticks, MANY_ROOMS, SMALL_CAPACITY)
    steps = {n: metrics.counter(n).value - before[n] for n in STEPS}
    assert steps["ingest.compact_steps"] >= len(stages) + len(ticks)
    assert steps["ingest.dense_steps"] == 0
    _spans_every_device(sharded)
    got = _check_against_oracle(sharded, connect, stages, ticks)
    _spans_every_device(sharded)
    assert sharded.ingestor.fast_docs > 0
    plain, _ = _serve(False, connect, stages, ticks, MANY_ROOMS, SMALL_CAPACITY)
    assert got == _check_against_oracle(plain, connect, stages, ticks)  # the same bytes
    assert sharded.ingestor.fast_docs == plain.ingestor.fast_docs
    assert {n: metrics.counter(n).value for n in WATCHED} == {n: before[n] for n in WATCHED}


def test_a_mixed_step_one_room_on_each_lane():
    """One dispatch carries a fast-lane room and a host-lane room: the
    second room's updates arrive out of order, so the first to come waits
    in the stash and its room plans on the host until the gap closes."""
    rooms = [0, 9]
    stages, _ = _traffic(28_000_011, rooms, n_sessions=0)
    r = random.Random(28_000_012)
    a = _Typist(7000, [stages[0][0], stages[1][0]])
    b = _Typist(7001, [stages[0][9], stages[1][9]])
    a_edits = [a.edit(r) for _ in range(3)]
    b1, b2, b3 = (b.insert(r) for _ in range(3))
    ticks = [[(0, a_edits[0]), (9, b2)], [(0, a_edits[1]), (9, b1)], [(0, a_edits[2]), (9, b3)]]
    server, mixed = _serve(True, rooms, stages, ticks)
    assert mixed >= 2 and server.ingestor.slow_docs >= 2
    _spans_every_device(server)
    got = _check_against_oracle(server, rooms, stages, ticks)
    plain, _ = _serve(False, rooms, stages, ticks)
    assert got == _check_against_oracle(plain, rooms, stages, ticks)


def test_an_all_delete_step():
    """A tick in which every update is a delete set and nothing else."""
    rooms = [1, 6, 15]
    stages, _ = _traffic(28_000_021, rooms, n_sessions=0)
    r = random.Random(28_000_022)
    typists = {k: _Typist(7000 + k, [stages[0][k], stages[1][k]]) for k in rooms}
    ticks = [
        [(k, t.insert(r)) for k, t in typists.items()],
        [(k, t.delete(r)) for k, t in typists.items()] + [(1, typists[1].delete(r))],
        [(k, t.edit(r)) for k, t in typists.items()],
    ]
    server, _ = _serve(True, rooms, stages, ticks)
    _spans_every_device(server)
    got = _check_against_oracle(server, rooms, stages, ticks)
    plain, _ = _serve(False, rooms, stages, ticks)
    assert got == _check_against_oracle(plain, rooms, stages, ticks)


def test_a_room_count_the_mesh_does_not_divide_is_refused():
    with pytest.raises(ValueError, match="refusing to serve unsharded"):
        DeviceSyncServer(n_docs=12, capacity=64, device_authoritative=True, shard_docs=True)


def test_one_chip_asked_to_shard_is_refused_and_one_cpu_device_is_the_no_op(monkeypatch):
    one = jax.devices()[:1]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: one)
    server = DeviceSyncServer(n_docs=4, capacity=64, device_authoritative=True, shard_docs=True)
    assert server.shard_docs and metrics.gauge("ingest.state_shards").value == 1
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="refusing to serve unsharded"):
        DeviceSyncServer(n_docs=4, capacity=64, device_authoritative=True, shard_docs=True)
