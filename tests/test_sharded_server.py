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

Below them, the wire logs the sequence-parallel engine's tests were worth
keeping for (that engine left in PR 31), each served to one room of the same
family (24 x 512: three rooms a device) on one device and doc-sharded: maps,
XML, several roots, GC carriers, the stash and a store of nested JSON records
on a sharded state, which text rooms never reach.
Then `ytpu/parallel/mesh.py` itself on the 8 devices, and what the server's
import loads. `tests/test_served_wire_logs.py` serves more logs through the
same harness (array moves, deep conflict scans, rooms that compact).
"""

import functools
import os
import random
import subprocess
import sys
from collections import deque

import jax
import numpy as np
import pytest

from ytpu.core import Doc
from ytpu.core.state_vector import StateVector
from ytpu.core.update import Update
from ytpu.parallel import mesh as doc_mesh
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
    """What `chip_smoke.py --chips 4` checks after its last flush; the
    gauge is the newest ingestor's, so only where `server` is the newest."""
    _assert_spans_every_device(server)
    assert metrics.gauge("ingest.state_shards").value == len(jax.devices())


def _assert_spans_every_device(server) -> None:
    n_dev = len(jax.devices())
    assert n_dev == 8  # tests/conftest.py
    local = [
        i for i, a in enumerate(jax.tree.leaves(server.ingestor.state))
        if len(a.sharding.device_set) != n_dev
    ]
    assert not local, f"state planes {local} no longer span {n_dev} devices"
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


def test_the_lookup_tables_stay_on_every_device():
    """The client, key, client-hash and rank tables go up at the first step
    (the clients are preregistered) and every later step is handed those
    arrays again: whole on every device, where `_upload` put them, so a
    reuse crosses no device inside the jitted calls (`_serve` runs every
    step under the device-to-device guard)."""
    tables = ("ingest.table_builds", "ingest.table_reuses")
    stages, ticks = _traffic(28_000_001 + len(FIRST_SHARD), FIRST_SHARD)
    before = {n: metrics.counter(n).value for n in tables + STEPS}
    server, _ = _serve(True, FIRST_SHARD + [15], stages, ticks)
    took = {n: metrics.counter(n).value - before[n] for n in tables + STEPS}
    steps = sum(took[n] for n in STEPS)
    assert steps >= len(stages) + len(ticks)
    assert took["ingest.table_builds"] == 4
    assert took["ingest.table_reuses"] == 4 * (steps - 1)
    cache = server.ingestor._table_cache
    assert sorted(cache) == ["client_hash_table", "client_rank", "client_table", "key_table"]
    for name, (_, arrays) in cache.items():
        for a in jax.tree.leaves(arrays):
            assert len(a.sharding.device_set) == len(jax.devices()) == 8, name
            assert a.sharding.is_fully_replicated, name
    _check_against_oracle(server, FIRST_SHARD + [15], stages, ticks)


def test_the_empty_host_lane_batch_stays_laid_out_by_room():
    """A step without a host-lane room is handed the all-padding batch
    an earlier step of its `(width, n_rows, n_dels)` bucket put on the mesh
    (the two arrays of a `PackedBatch`, laid out by room): every kept
    leaf split by room as the state's planes are, so a reuse crosses no
    device inside the jitted calls (`_serve` runs every step under the
    device-to-device guard)."""
    batches = ("ingest.batch_builds", "ingest.batch_reuses")
    stages, ticks = _traffic(28_000_001 + len(EVERY_SHARD), EVERY_SHARD)
    before = {n: metrics.counter(n).value for n in batches + STEPS}
    server, mixed = _serve(True, EVERY_SHARD + [15], stages, ticks)
    took = {n: metrics.counter(n).value - before[n] for n in batches + STEPS}
    steps = sum(took[n] for n in STEPS)
    assert steps >= len(stages) + len(ticks) and mixed == 0
    ing = server.ingestor
    assert ing.slow_docs == 0
    # one build a bucket, every other step a reuse
    assert took["ingest.batch_builds"] == len(ing._batch_cache) >= 1
    assert took["ingest.batch_reuses"] == steps - took["ingest.batch_builds"] > 0
    by_room = ing.state.blocks.client.sharding
    kept_bytes = 0
    for bucket, batch in ing._batch_cache.items():
        for a, (entries, columns) in zip(batch, ((bucket[1], 23), (bucket[2], 4))):  # rows, then deletes
            assert bucket[0] == N_ROOMS  # 16 rooms: every step is dense, as wide as the slots
            assert a.shape == (N_ROOMS, entries, columns)
            assert len(a.sharding.device_set) == len(jax.devices()) == 8, bucket
            assert a.sharding.is_equivalent_to(by_room, a.ndim), bucket
            assert not a.sharding.is_fully_replicated, bucket
            # 2 rooms a device, in the state's order
            assert [s.index[0] for s in a.addressable_shards] == [
                s.index[0] for s in ing.state.blocks.client.addressable_shards
            ], bucket
            kept_bytes += a.nbytes
    state_bytes = sum(a.nbytes for a in jax.tree.leaves(ing.state))
    assert 0 < kept_bytes <= state_bytes // 16
    _check_against_oracle(server, EVERY_SHARD + [15], stages, ticks)


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


# --------------------------------------------------------------------------
# Wire logs of real `Doc`s, a room a log. Each builder returns the updates in
# the order the server receives them.


def _capture(doc):
    log = []
    doc.observe_update_v1(lambda p, o, t: log.append(p))
    return log


def _random_edit(txn, txt, r, length):
    if length > 10 and r.random() < 0.3:
        n = r.randint(1, 3)
        txt.remove_range(txn, r.randint(0, length - 3), n)
        return length - n
    w = "".join(r.choice("abcdefgh ") for _ in range(r.randint(1, 5)))
    txt.insert(txn, r.randint(0, length), w)
    return length + len(w)


def _sequential_log(n_ops, seed):
    src = Doc(client_id=1)
    log = _capture(src)
    t = src.get_text(ROOT)
    r = random.Random(seed)
    length = 0
    for _ in range(n_ops):
        with src.transact() as txn:
            length = _random_edit(txn, t, r, length)
    return log


def _gcify(payload: bytes) -> bytes:
    """A full-state update as a gc-enabled yrs peer encodes it: deleted
    items become position-free GC carriers (BlockCell::GC)."""
    from ytpu.core.block import GCRange
    from ytpu.core.content import CONTENT_DELETED

    u = Update.decode_v1(payload)
    blocks = {}
    for client, carriers in u.blocks.items():
        blocks[client] = deque(
            GCRange(c.id, c.len)
            if getattr(c, "is_item", False) and c.content.kind == CONTENT_DELETED
            else c
            for c in carriers
        )
    return Update(blocks=blocks, delete_set=u.delete_set).encode_v1()


def sequential_replay():
    return _sequential_log(60, seed=3)


def _concurrent_at_one_position():
    base = Doc(client_id=1)
    with base.transact() as txn:
        base.get_text(ROOT).insert(txn, 0, "abcdefghijklmnop")
    state0 = base.encode_state_as_update_v1()
    peer_a, peer_b = Doc(client_id=2), Doc(client_id=3)
    peer_a.apply_update_v1(state0)
    peer_b.apply_update_v1(state0)
    ta, tb = peer_a.get_text(ROOT), peer_b.get_text(ROOT)
    with peer_a.transact() as txn:
        ta.insert(txn, 4, "AAA")  # the same spot as peer_b: the conflict scan
        ta.insert(txn, 19, "XX")  # an append at the tail
    with peer_b.transact() as txn:
        tb.insert(txn, 4, "BBB")
        tb.remove_range(txn, 8, 4)
    sv = base.state_vector()
    return state0, peer_a.encode_state_as_update_v1(sv), peer_b.encode_state_as_update_v1(sv)


def concurrent_edits_a_then_b():
    state0, upd_a, upd_b = _concurrent_at_one_position()
    return [state0, upd_a, upd_b]


def concurrent_edits_b_then_a():
    state0, upd_a, upd_b = _concurrent_at_one_position()
    return [state0, upd_b, upd_a]


def multi_peer_fuzz():
    """4 peers editing concurrently in rounds, a full exchange after each."""
    r = random.Random(7)
    peers = [Doc(client_id=i + 1) for i in range(4)]
    texts = [p.get_text(ROOT) for p in peers]
    made = []
    for _ in range(6):
        for p, t in zip(peers, texts):
            edit = _capture(p)
            with p.transact() as txn:
                _random_edit(txn, t, r, len(t.get_string()))
            made.extend(edit[:1])
        for _ in range(2):
            for a in peers:
                for b in peers:
                    if a is not b:
                        b.apply_update_v1(a.encode_state_as_update_v1(b.state_vector()))
    assert len({t.get_string() for t in texts}) == 1
    return made


def stash_of_text_and_map():
    """Updates delivered out of order: the later one waits in the stash
    (transaction.rs:675-727) and its room plans on the host until the gap
    closes. Text and map of one root, so the host lane's planes carry map
    rows onto the sharded state too."""
    src = Doc(client_id=1)
    log = _capture(src)
    t, m = src.get_text(ROOT), src.get_map(ROOT)
    for i, ch in enumerate("abcdef"):
        with src.transact() as txn:
            t.insert(txn, len(t.get_string()), ch)
            m.insert(txn, f"k{i % 2}", i)
    return [log[0], log[2], log[1], log[4], log[5], log[3]]


def delete_spanning_many_blocks():
    log = _sequential_log(8, seed=23)
    peer = Doc(client_id=50)
    for u in log:
        peer.apply_update_v1(u)
    tp = peer.get_text(ROOT)
    plog = _capture(peer)
    with peer.transact() as txn:
        tp.remove_range(txn, 2, len(tp.get_string()) - 4)
    return log + plog


def origin_in_the_middle_of_a_block():
    """A peer that synced only a prefix appends with an origin in the middle
    of what is by now one block."""
    src = Doc(client_id=1)
    log = _capture(src)
    t = src.get_text(ROOT)
    with src.transact() as txn:
        t.insert(txn, 0, "abcde")
    with src.transact() as txn:
        t.insert(txn, 5, "fghijklmnop")
    peer = Doc(client_id=2)
    peer.apply_update_v1(log[0])
    plog = _capture(peer)
    with peer.transact() as txn:
        peer.get_text(ROOT).insert(txn, 5, "ZZ")
    return log + plog


def text_plus_map():
    """The text and the map component of ONE root."""
    src = Doc(client_id=1)
    log = _capture(src)
    t, m = src.get_text(ROOT), src.get_map(ROOT)
    r = random.Random(7)
    length = 0
    for i in range(90):
        with src.transact() as txn:
            if i % 3 == 0:
                m.insert(txn, f"k{r.randint(0, 9)}", r.randint(0, 999))
            else:
                length = _random_edit(txn, t, r, length)
    return log


def concurrent_map_writers():
    """Two writers on the same keys: last writer wins, as the oracle picks."""
    a, b = Doc(client_id=5), Doc(client_id=9)
    log_a, log_b = _capture(a), _capture(b)
    ma, mb = a.get_map("m"), b.get_map("m")
    with a.transact() as txn:
        ma.insert(txn, "color", "red")
        a.get_text("m").insert(txn, 0, "alpha")
    with b.transact() as txn:
        mb.insert(txn, "color", "blue")
        mb.insert(txn, "size", 4)
        b.get_text("m").insert(txn, 0, "beta")
    for p in list(log_b):  # a sees b's writes; b stays behind
        a.apply_update_v1(p)
    with a.transact() as txn:
        ma.insert(txn, "color", "green")  # a new winner over the merged chain
        ma.remove(txn, "size")
    return log_a + log_b


def map_chain_fuzz():
    """3 writers on one root's map and text, syncing at random points; the
    log is their edits in the order they were made (what a peer re-emits
    when it takes a diff in only repeats them)."""
    r = random.Random(23)
    peers = [Doc(client_id=10 + i) for i in range(3)]
    made = []
    length = [0, 0, 0]
    for _ in range(60):
        i = r.randrange(3)
        d = peers[i]
        edit = _capture(d)
        with d.transact() as txn:
            x = r.random()
            if x < 0.4:
                d.get_map("doc").insert(txn, f"k{r.randint(0, 4)}", r.randint(0, 99))
            elif x < 0.5 and len(list(d.get_map("doc").keys())):
                d.get_map("doc").remove(txn, next(iter(d.get_map("doc").keys())))
            else:
                length[i] = _random_edit(txn, d.get_text("doc"), r, length[i])
        made.extend(edit[:1])
        if r.random() < 0.3:
            j = r.randrange(3)
            if j != i:
                peers[j].apply_update_v1(d.encode_state_as_update_v1(peers[j].state_vector()))
                length[j] = len(peers[j].get_text("doc").get_string())
    return made


def nested_xml_tree():
    """Elements, attributes (nested LWW chains), nested text edits, two
    concurrent clients, as a relay hands their updates on."""
    from ytpu.types import XmlElementPrelim, XmlTextPrelim

    r = random.Random(11)
    a, b = Doc(client_id=1, skip_gc=True), Doc(client_id=2, skip_gc=True)
    relay = Doc(client_id=0xFFFF, skip_gc=True)
    log = _capture(relay)
    fa, fb = a.get_xml_fragment("x"), b.get_xml_fragment("x")
    with a.transact() as txn:
        fa.insert(txn, 0, XmlElementPrelim("doc"))
        fa.insert(txn, 1, XmlTextPrelim("seed"))
    relay.apply_update_v1(a.encode_state_as_update_v1(relay.state_vector()))
    b.apply_update_v1(a.encode_state_as_update_v1(b.state_vector()))
    for step in range(50):
        doc, frag = (a, fa) if r.random() < 0.5 else (b, fb)
        with doc.transact() as txn:
            x = r.random()
            kids = list(frag.children())
            if x < 0.3:
                frag.insert(
                    txn,
                    r.randrange(len(kids) + 1),
                    XmlElementPrelim(f"e{step}", attributes={"n": str(step)}),
                )
            elif x < 0.6 and kids:
                el = kids[r.randrange(len(kids))]
                if hasattr(el, "insert_attribute"):
                    el.insert_attribute(txn, f"k{step % 5}", str(step))
            else:
                tx = [k for k in kids if type(k).__name__ == "XmlText"]
                if tx:
                    t = tx[r.randrange(len(tx))]
                    n = len(t)
                    if n > 3 and r.random() < 0.3:
                        t.remove_range(txn, r.randrange(n - 2), 2)
                    else:
                        t.insert(txn, r.randrange(n + 1), f"w{step} ")
        relay.apply_update_v1(doc.encode_state_as_update_v1(relay.state_vector()))
        other = b if doc is a else a
        other.apply_update_v1(doc.encode_state_as_update_v1(other.state_vector()))
    return log


def multi_root():
    """A text root and a map root beside the primary XML fragment."""
    from ytpu.types import XmlElementPrelim

    d = Doc(client_id=1, skip_gc=True)
    log = _capture(d)
    frag, m, t = d.get_xml_fragment("x"), d.get_map("meta"), d.get_text("title")
    with d.transact() as txn:
        frag.insert(txn, 0, XmlElementPrelim("div", attributes={"id": "a"}))
    with d.transact() as txn:
        m.insert(txn, "version", 3)
        t.insert(txn, 0, "hello")
    with d.transact() as txn:
        t.insert(txn, 5, " world")
        m.insert(txn, "version", 4)
    with d.transact() as txn:
        t.remove_range(txn, 0, 3)
    return log


def gc_carriers():
    """The full state of a gc-enabled peer: deleted items arrive as GC
    carriers, and " world", whose only anchor is GC'd, degrades to one
    (update.rs, the unresolvable-parent rule)."""
    a = Doc(client_id=1)
    t = a.get_text("t")
    with a.transact() as txn:
        t.insert(txn, 0, "hello cruel world")
    with a.transact() as txn:
        t.remove_range(txn, 5, 6)
    return [_gcify(a.encode_state_as_update_v1())]


def nested_json_records():
    """A room that is a store of JSON records, the tldraw-over-Yjs shape
    (`benchmark/generators/record_mix.py`): a `Y.Array` of `{key, val}`
    entries under `YKeyValue`, `val` an object with objects in it. A loader's
    records arrive in two stages of one-record blocks (what a relay's
    `mergeUpdates` makes of a room's history); three writers synced with the
    loaded store, one with an id past int32 and none seeing another, then set
    (`remove` + `push_back` in one transaction), add (`push_back`) and delete
    (`remove`) records. Every update that carries a record is a nested Any
    and plans on the host; a delete carries none and rides the fast lane."""
    from ytpu.core.update import merge_updates_v1

    def record(i, **props):
        key = f"shape:{i:04d}"
        return {"key": key, "val": {
            "id": key, "typeName": "shape", "type": "geo", "x": i + 0.13, "y": -i - 0.37, "rotation": 0,
            "index": f"a{i}", "parentId": "page:page", "isLocked": False, "opacity": 1,
            "props": {"geo": "rectangle", "w": 100 + i, "h": 80, "color": "black", "text": "", **props},
            "meta": {},
        }}

    root = "tl_nested_json_records"
    loader = Doc(client_id=900_032)
    loaded = _capture(loader)
    for i in range(12):
        with loader.transact() as txn:
            loader.get_array(root).push_back(txn, record(i))
    state = loader.encode_state_as_update_v1()
    logs = []
    for w, client in enumerate((7, 9, 2**31 + 5)):
        d = Doc(client_id=client)
        d.apply_update_v1(state)
        log, arr = _capture(d), d.get_array(root)
        with d.transact() as txn:  # a restyle of a record all three set: the rightmost entry wins
            arr.remove(txn, 3)
            arr.push_back(txn, record(3, color=("red", "blue", "green")[w]))
        with d.transact() as txn:  # a new record
            arr.push_back(txn, record(100 + w))
        with d.transact() as txn:  # a drag of the writer's own first push, now second to last
            arr.remove(txn, 11)
            arr.push_back(txn, record(3, color="grey", text=f"moved by {w}"))
        with d.transact() as txn:  # a delete of one of the loader's
            arr.remove(txn, 5 + w)
        logs.append(log)
    assert all(len(log) == 4 for log in logs)
    return [merge_updates_v1(loaded[:7]), merge_updates_v1(loaded[7:])] + [u for step in zip(*logs) for u in step]


def _anchored_into_a_gcd_region(insert_at):
    """A stale peer's insert whose anchors were GC'd since."""
    a, b = Doc(client_id=1), Doc(client_id=2)
    ta = a.get_text("t")
    with a.transact() as txn:
        ta.insert(txn, 0, "abcdef")
    b.apply_update_v1(a.encode_state_as_update_v1())
    with b.transact() as txn:
        b.get_text("t").insert(txn, insert_at, "XY")
    b_update = b.encode_state_as_update_v1(a.state_vector())
    with a.transact() as txn:
        ta.remove_range(txn, 1, 3)  # "bcd"
    return [_gcify(a.encode_state_as_update_v1()), b_update]


def anchored_both_sides_gcd():
    return _anchored_into_a_gcd_region(3)  # origin 'c' and right origin 'd' GC'd: degrades


def anchored_left_gcd():
    return _anchored_into_a_gcd_region(4)  # origin 'd' GC'd, right origin 'e' live


def anchored_right_gcd():
    return _anchored_into_a_gcd_region(1)  # origin 'a' live, right origin 'b' GC'd


# a room each: 17 logs on a server of 24 rooms. Array moves are not among
# them: the rooms read right and `device_encode_diff` cannot write a move row
# the device decoded, on one device as on eight (ROADMAP, Reach A)
SCENARIOS = [
    sequential_replay,
    text_plus_map,
    concurrent_map_writers,
    map_chain_fuzz,
    nested_xml_tree,
    multi_root,
    gc_carriers,
    anchored_both_sides_gcd,
    anchored_left_gcd,
    anchored_right_gcd,
    stash_of_text_and_map,
    multi_peer_fuzz,
    delete_spanning_many_blocks,
    origin_in_the_middle_of_a_block,
    concurrent_edits_a_then_b,
    concurrent_edits_b_then_a,
    nested_json_records,
]
SCENARIO_ROOMS = 24  # a room a log, and a count the 8 devices divide
assert len(SCENARIOS) <= SCENARIO_ROOMS
# what the oracle is read by beside state vector and full-state diff: root -> kinds
READS = {
    text_plus_map: {ROOT: ("text", "map")},
    stash_of_text_and_map: {ROOT: ("text", "map")},
    concurrent_map_writers: {"m": ("text", "map")},
    map_chain_fuzz: {"doc": ("text", "map")},
    nested_xml_tree: {},
    multi_root: {"meta": ("map",), "title": ("text",)},  # beside the primary, "x"
    gc_carriers: {"t": ("text",)},
    anchored_both_sides_gcd: {"t": ("text",)},
    anchored_left_gcd: {"t": ("text",)},
    anchored_right_gcd: {"t": ("text",)},
    nested_json_records: {"tl_nested_json_records": ("array",)},
}
# updates the served path plans on the host (`BatchIngestor.slow_docs`): the
# stashed ones and those that close their gaps. Every other update of every
# log rides the fast lane, maps and XML too, and no room leaves the device.
# Of the record store's 14 updates the three deletes carry no record: the two
# stages and the nine sets and adds are nested Any values.
HOST_LANE = {stash_of_text_and_map: 5, nested_json_records: 11}
# a room's first mention of a secondary root builds the anchor row's mask on
# the first device (`batch_doc.ensure_root_anchor`) and jax carries it onto
# the mesh: the one step of these logs the guard would refuse (ROADMAP, Reach A)
UNGUARDED = {multi_root}

@functools.lru_cache(maxsize=None)
def _log(scenario):
    return scenario()


def _log_server(logs, shard_docs: bool, n_rooms: int, capacity: int) -> DeviceSyncServer:
    """A server for `logs`, a room each. Clients, map keys and root names
    of every log are registered before the first frame, as the benchmark
    registers its clients: a first-seen one grows a lookup table, and every
    table size is a program family of its own."""
    server = DeviceSyncServer(
        n_docs=n_rooms, capacity=capacity, device_authoritative=True, shard_docs=shard_docs
    )
    clients, names = set(), set()
    for log in logs:
        for payload in log:
            for client, carriers in Update.decode_v1(payload).blocks.items():
                clients.add(client)
                for c in carriers:
                    named = (getattr(c, "parent", None), getattr(c, "parent_sub", None))
                    names.update(n for n in named if isinstance(n, str))
    for client in sorted(clients):
        server.ingestor.enc.interner.intern(client)
    for name in sorted(names):
        assert server.ingestor._register_key(name)
    return server


@functools.lru_cache(maxsize=None)
def _scenario_server(shard_docs: bool) -> DeviceSyncServer:
    """The layout's one server."""
    return _log_server([_log(s) for s in SCENARIOS], shard_docs, SCENARIO_ROOMS, CAPACITY)


def _device_reads(server, room, reads, primary) -> dict:
    tree = server.device_tree(room)
    out = {}
    for root, kinds in reads.items():
        branch = tree if root == primary else tree["roots"][root]
        for kind in kinds:
            if kind == "text":
                out[root, kind] = "".join(v for v in branch["seq"] if isinstance(v, str))
            elif kind == "array":
                out[root, kind] = branch["seq"]
            else:
                out[root, kind] = branch["map"]
    return out


def _oracle_reads(oracle: Doc, reads) -> dict:
    get = {
        "text": lambda root: oracle.get_text(root).get_string(),
        "map": lambda root: oracle.get_map(root).to_json(),
        "array": lambda root: oracle.get_array(root).to_json(),
    }
    return {(root, kind): get[kind](root) for root, kinds in reads.items() for kind in kinds}


def _serve_one(server, room, log, reads, guarded=True, settles=True, diff=True) -> dict:
    """One log to one room of `server`, an update a step, and what the room
    then reads. `settles`: nothing of the log is left waiting in the room's
    stash; `diff`: the room's full state is encoded too."""
    ing = server.ingestor
    session, _ = server.connect_frames(room)
    slot = server.slot_of(room)
    fast, slow, recovered = ing.fast_docs, ing.slow_docs, ing.fast_recoveries
    stashed = 0
    for update in log:
        assert server.receive_frames(session, _frame(update)) == []
        if guarded:
            with jax.transfer_guard_device_to_device("disallow"):
                assert server.flush_device(max_steps=1) == 1
        else:
            assert server.flush_device(max_steps=1) == 1
        stashed += ing.pending_update(slot) is not None
    jax.block_until_ready(ing.state)
    server.disconnect(session)
    assert not server.pending_device_updates()
    assert int(ing.state.error[slot]) == 0
    waiting = ing.pending_update(slot) is not None or ing.pending_ds(slot) is not None
    assert waiting != settles
    assert room not in server._host_tenants and ing.fast_recoveries == recovered
    return {
        "lanes": (ing.fast_docs - fast, ing.slow_docs - slow),
        "stashed": stashed,
        **_read_room(server, room, reads, diff),
    }


def _read_room(server, room, reads, diff=True) -> dict:
    """What a served room reads: state vector, its roots, and (`diff`) its
    full state encoded, with the rooms the native finisher handed back to
    the Python one."""
    primary = server.ingestor.primary_roots[server.slot_of(room)]
    return {
        "sv": dict(server.device_state_vector(room).clocks),
        "reads": _device_reads(server, room, reads, primary),
        # `device_text` reads the primary root as a text, whatever it is
        "text": (primary, server.device_text(room)) if "text" in reads.get(primary, ()) else None,
        "diff": server.device_encode_diff(room, StateVector()) if diff else None,
        "finisher_fallbacks": server._diff_pipeline.stats.fallback_docs if diff else 0,
    }


@functools.lru_cache(maxsize=None)
def _serve_log(shard_docs: bool, scenario) -> dict:
    """Kept, so the doc-sharded case compares with the one-device case's
    bytes without serving the log again."""
    reads = READS.get(scenario, {ROOT: ("text",)})
    got = _serve_one(
        _scenario_server(shard_docs), scenario.__name__, _log(scenario), reads,
        guarded=scenario not in UNGUARDED,
    )
    assert got["finisher_fallbacks"] == 0
    return got


def _canonical_bytes(update: bytes) -> bytes:
    """What a fresh replica holds after `update`, adjacent GC ranges as one
    (the device cuts a GC range where a later insert anchored into it)."""
    from ytpu.core.block import GCRange

    u = Update.decode_v1(_canonical(update)[2])
    blocks = {}
    for client, carriers in u.blocks.items():
        out = deque()
        for c in carriers:
            last = out[-1] if out else None
            if (
                isinstance(c, GCRange)
                and isinstance(last, GCRange)
                and last.id.clock + last.len == c.id.clock
            ):
                out[-1] = GCRange(last.id, last.len + c.len)
            else:
                out.append(c)
        blocks[client] = out
    return Update(blocks=blocks, delete_set=u.delete_set).encode_v1()


SERVED_CASES = [(s, sharded) for sharded in (False, True) for s in SCENARIOS]


def _assert_equals_the_oracle(got, log, reads) -> None:
    """What `_read_room` read of a room against the host CRDT fed the same
    log: state vector, reads, canonical full-state bytes."""
    oracle = Doc(client_id=99)
    for update in log:
        oracle.apply_update_v1(update)
    assert got["sv"] == dict(oracle.state_vector().clocks)
    assert got["reads"] == _oracle_reads(oracle, reads)
    if got["text"] is not None:
        primary, text = got["text"]
        assert text == oracle.get_text(primary).get_string()
    if got["diff"] is not None:
        assert _canonical_bytes(got["diff"]) == _canonical_bytes(oracle.encode_state_as_update_v1())


@pytest.mark.parametrize(
    "scenario,shard_docs",
    SERVED_CASES,
    ids=[f"{s.__name__}-{'doc_sharded' if sh else 'one_device'}" for s, sh in SERVED_CASES],
)
def test_a_served_wire_log_equals_the_oracle(scenario, shard_docs):
    got = _serve_log(shard_docs, scenario)
    log = _log(scenario)
    host_lane = HOST_LANE.get(scenario, 0)
    assert got["lanes"] == (len(log) - host_lane, host_lane)
    assert (got["stashed"] > 0) == (scenario is stash_of_text_and_map)
    _assert_equals_the_oracle(got, log, READS.get(scenario, {ROOT: ("text",)}))
    if shard_docs:
        assert got["diff"] == _serve_log(False, scenario)["diff"]  # the same bytes
        _assert_spans_every_device(_scenario_server(True))


# --------------------------------------------------------------------------
# `ytpu/parallel/mesh.py` on the suite's 8 devices.


def test_shard_docs_put_splits_an_even_doc_axis_in_contiguous_blocks():
    mesh = doc_mesh.batch_mesh()
    assert mesh is not None and dict(mesh.shape) == {doc_mesh.AXIS_BATCH: 8}
    arr = np.arange(16 * 3, dtype=np.int32).reshape(16, 3)
    put = doc_mesh.shard_docs_put(arr, mesh)
    assert len(put.sharding.device_set) == 8
    by_device = {s.device: np.asarray(s.data) for s in put.addressable_shards}
    for i, dev in enumerate(mesh.devices.flat):  # rooms 2i, 2i+1 on device i
        assert np.array_equal(by_device[dev], arr[2 * i : 2 * i + 2])


@pytest.mark.parametrize("shape", [(12, 4), (7,), ()], ids=["uneven", "odd_vector", "scalar"])
def test_shard_docs_put_hands_back_what_the_mesh_does_not_divide(shape):
    arr = np.zeros(shape, np.int32)
    assert doc_mesh.shard_docs_put(arr) is arr


def test_shard_docs_put_on_axis_one_for_packed_columns():
    arr = np.arange(5 * 16 * 4, dtype=np.int32).reshape(5, 16, 4)  # [NC, D, C]
    put = doc_mesh.shard_docs_put(arr, doc_axis=1)
    assert put.sharding.spec == jax.sharding.PartitionSpec(None, doc_mesh.AXIS_BATCH, None)
    assert {s.data.shape for s in put.addressable_shards} == {(5, 2, 4)}
    assert np.array_equal(np.asarray(put), arr)
    uneven = np.zeros((16, 12), np.int32)  # axis 0 would divide; axis 1 does not
    assert doc_mesh.shard_docs_put(uneven, doc_axis=1) is uneven


@pytest.mark.parametrize(
    "doc_axis,ndim,spec",
    [(0, 1, ("batch",)), (0, 3, ("batch", None, None)), (1, 3, (None, "batch", None)),
     (1, 1, (None, "batch"))],
    ids=["vector", "leading", "packed", "rank_grows_to_hold_the_axis"],
)
def test_batch_sharding_spec(doc_axis, ndim, spec):
    mesh = doc_mesh.batch_mesh()
    sharding = doc_mesh.batch_sharding(mesh, doc_axis, ndim)
    assert sharding.mesh == mesh
    assert sharding.spec == jax.sharding.PartitionSpec(*spec)


def test_batch_mesh_takes_the_first_n_devices_and_none_for_one():
    assert list(doc_mesh.batch_mesh(4).devices.flat) == jax.devices()[:4]
    assert doc_mesh.batch_mesh(1) is None


def test_state_shards_reads_the_fewest_over_the_planes():
    mesh = doc_mesh.batch_mesh()
    by_room = doc_mesh.shard_docs_put(np.zeros((16, 4), np.int32), mesh)
    everywhere = jax.device_put(np.zeros(3, np.int32), doc_mesh.replicated(mesh))
    assert len(everywhere.sharding.device_set) == 8 and everywhere.sharding.is_fully_replicated
    assert doc_mesh.state_shards({"a": by_room, "b": everywhere}) == 8
    on_one = jax.device_put(np.zeros((16, 4), np.int32), jax.devices()[3])
    assert doc_mesh.state_shards({"a": by_room, "b": on_one}) == 1
    half = doc_mesh.shard_docs_put(np.zeros((16, 4), np.int32), doc_mesh.batch_mesh(4))
    assert doc_mesh.state_shards([by_room, half, everywhere]) == 4


def test_require_doc_mesh_is_the_mesh_of_every_device():
    mesh = doc_mesh.require_doc_mesh(16)
    assert list(mesh.devices.flat) == jax.devices() and mesh.axis_names == (doc_mesh.AXIS_BATCH,)


def test_the_server_imports_the_doc_mesh_and_no_other_parallel_module():
    """`ytpu.parallel` is `mesh.py`: what the served server loads of it is
    what the four-chip cell runs."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = (
        "import sys, ytpu.sync.device_server\n"
        "print(sorted(m for m in sys.modules if m.startswith('ytpu.parallel')))"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=root)
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=root, env=env, capture_output=True, text=True, timeout=300
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == "['ytpu.parallel', 'ytpu.parallel.mesh']"
    files = set(os.listdir(os.path.join(root, "ytpu", "parallel"))) - {"__pycache__"}
    assert files == {"__init__.py", "mesh.py"}
