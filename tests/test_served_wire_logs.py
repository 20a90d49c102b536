"""Wire logs the served path (`DeviceSyncServer.receive_frames` ->
`flush_device` -> `BatchIngestor.apply_bytes` -> `apply_update_batch`) was
held to nowhere, through `tests/test_sharded_server.py`'s harness: a
module-scoped server a layout, a room a log, an update a step under the
device-to-device transfer guard, on one device and doc-sharded over the
suite's 8 host devices, each room against `ytpu.core.Doc` fed the same log.

They are the traffic the replay drivers' tests built before those drivers
left (PR 48): array moves, text inside a deleted parent with formats, an
update whose anchor never arrives, same-origin storms under, at and over the
conflict scan's cheap tier, the B4 editing trace's first updates, and rooms
small enough that the served compaction (`compact_rooms`, from
`BatchIngestor._make_room`) fires in the middle of the log.

`device_encode_diff` cannot write a move row the device decoded (ROADMAP,
Reach A; PR 31's finding): rooms whose log holds an array move are compared
by what they read and by state vector, and their full state is not encoded.
"""

import functools
import random
import string

import jax
import numpy as np
import pytest

from _traces import build_conflict_stream, build_move_storm, load_b4_log
from test_sharded_server import (
    _assert_equals_the_oracle,
    _assert_spans_every_device,
    _capture,
    _frame,
    _log_server,
    _read_room,
    _serve_one,
)
from ytpu.core import Doc
from ytpu.models.batch_doc import SCAN_TIER_CHEAP_DEFAULT, scan_tier_plan
from ytpu.utils import metrics

ROOMS, CAPACITY = 24, 512  # test_sharded_server's family of programs
SMALL_ROOMS, SMALL_CAPACITY = 8, 256  # rooms that fill: a room a device
TEXT = {"text": ("text",)}
ARRAY = {"a": ("array",)}


# --------------------------------------------------------------------------
# text


def random_edit_trace():
    doc = Doc(client_id=1)
    log = _capture(doc)
    rng = random.Random(9)
    t = doc.get_text("text")
    for _ in range(30):
        with doc.transact() as txn:
            n = len(t)
            if n > 5 and rng.random() < 0.35:
                pos = rng.randint(0, n - 2)
                t.remove_range(txn, pos, min(rng.randint(1, 3), n - pos))
            else:
                word = "".join(rng.choice(string.ascii_lowercase) for _ in range(3))
                t.insert(txn, rng.randint(0, n), word)
    return log


def text_in_a_deleted_parent_with_formats():
    """Formats (uncountable rows) and a write under a nested text that is
    then removed from its map: its rows are dead on arrival
    (block.rs:751-765)."""
    from ytpu.types.shared import TextPrelim

    doc = Doc(client_id=1)
    log = _capture(doc)
    m = doc.get_map("m")
    with doc.transact() as txn:
        m.insert(txn, "t", TextPrelim("ab"))
    with doc.transact() as txn:
        m.get("t").insert_with_attributes(txn, 1, "B", {"bold": True})
    with doc.transact() as txn:
        m.insert(txn, "kept", 1)
    with doc.transact() as txn:
        m.remove(txn, "t")  # tombstone the nested text
    return log


def an_anchor_that_never_arrives():
    """The second of three updates is lost: the third's origin is a row the
    room never holds, so it waits in the stash and the state vector stays
    where the first left it, on the device as in the host CRDT."""
    doc = Doc(client_id=1)
    log = _capture(doc)
    t = doc.get_text("text")
    for word in ("head", " lost", " tail"):
        with doc.transact() as txn:
            t.insert(txn, len(t), word)
    return [log[0], log[2]]


def b4_trace_prefix():
    return load_b4_log(200)[0]


# --------------------------------------------------------------------------
# array moves


def _seeded_array(values, client_id=1):
    doc = Doc(client_id=client_id)
    log = _capture(doc)
    arr = doc.get_array("a")
    with doc.transact() as txn:
        for v in values:
            arr.push_back(txn, v)
    return doc, arr, log


def collapsed_move():
    doc, arr, log = _seeded_array([0, 1, 2, 3, 4])
    with doc.transact() as txn:
        arr.move_to(txn, 1, 4)
    assert arr.to_json() == [0, 2, 3, 1, 4]
    return log


def range_move_backward():
    doc, arr, log = _seeded_array(list(range(6)))
    with doc.transact() as txn:
        arr.move_range_to(txn, 3, 4, 1)
    assert arr.to_json() == [0, 3, 4, 1, 2, 5]
    return log


def insert_into_a_moved_range():
    doc, arr, log = _seeded_array(list(range(5)))
    with doc.transact() as txn:
        arr.move_range_to(txn, 2, 3, 0)
    with doc.transact() as txn:
        arr.insert(txn, 2, ["x"])
    return log


def _two_concurrent_moves():
    a, arr_a, log_a = _seeded_array([0, 1, 2, 3, 4], client_id=1)
    seed = list(log_a)
    b = Doc(client_id=2)
    log_b = _capture(b)
    for p in seed:
        b.apply_update_v1(p)
    with a.transact() as txn:
        arr_a.move_to(txn, 1, 4)
    with b.transact() as txn:
        b.get_array("a").move_to(txn, 1, 3)
    return seed, log_a[-1], log_b[-1]


def concurrent_moves_a_then_b():
    seed, mv_a, mv_b = _two_concurrent_moves()
    return seed + [mv_a, mv_b]


def concurrent_moves_b_then_a():
    seed, mv_a, mv_b = _two_concurrent_moves()
    return seed + [mv_b, mv_a]


def move_delete_releases_its_range():
    doc, arr, log = _seeded_array(list(range(5)))
    with doc.transact() as txn:
        arr.move_to(txn, 0, 4)
    with doc.transact() as txn:
        arr.remove_range(txn, 3, 1)  # the moved element: the move row goes with it
    return log


def branch_scoped_move():
    """A move from index 0: its start bound is the branch, no id."""
    doc, arr, log = _seeded_array([0, 1, 2, 3])
    with doc.transact() as txn:
        arr.move_to(txn, 0, 3)
    return log


def random_move_fuzz():
    rng = random.Random(5)
    doc, arr, log = _seeded_array(list(range(8)))
    for _ in range(12):
        n = len(arr)
        with doc.transact() as txn:
            r = rng.random()
            if r < 0.5 and n >= 2:
                arr.move_to(txn, rng.randrange(n), rng.randrange(n + 1))
            elif r < 0.75:
                arr.insert(txn, rng.randrange(n + 1), [rng.randrange(100)])
            elif n > 2:
                arr.remove_range(txn, rng.randrange(n - 1), 1)
    return log


# --------------------------------------------------------------------------
# deep conflict scans: same-origin siblings whose widest scan is under, at,
# one over and far over the cheap tier's bound of 32 candidates
# (`batch_doc.scan_tier_plan`); `test_the_storms_straddle_the_cheap_tier`
# holds the widths


def conflicts_under_the_cheap_tier():
    return build_conflict_stream(4, 4, erase_every=2)[0]


def conflicts_at_the_cheap_tier():
    return build_conflict_stream(5, 8, erase_every=2)[0]


def conflicts_one_over_the_cheap_tier():
    return build_conflict_stream(4, 11, erase_every=2)[0]


def conflicts_far_over_the_cheap_tier():
    return build_conflict_stream(10, 12, erase_every=5, erase_len=11)[0]


def conflicts_with_live_moves():
    return build_move_storm()[0]


SCENARIOS = [
    random_edit_trace,
    text_in_a_deleted_parent_with_formats,
    an_anchor_that_never_arrives,
    b4_trace_prefix,
    collapsed_move,
    range_move_backward,
    insert_into_a_moved_range,
    concurrent_moves_a_then_b,
    concurrent_moves_b_then_a,
    move_delete_releases_its_range,
    branch_scoped_move,
    random_move_fuzz,
    conflicts_under_the_cheap_tier,
    conflicts_at_the_cheap_tier,
    conflicts_one_over_the_cheap_tier,
    conflicts_far_over_the_cheap_tier,
    conflicts_with_live_moves,
]
MOVES = {
    collapsed_move, range_move_backward, insert_into_a_moved_range, concurrent_moves_a_then_b,
    concurrent_moves_b_then_a, move_delete_releases_its_range, branch_scoped_move,
    random_move_fuzz, conflicts_with_live_moves,
}
READS = {text_in_a_deleted_parent_with_formats: {"m": ("map",)}, **{s: ARRAY for s in MOVES}}
# the lost update's successor: it plans on the host and waits
HOST_LANE = {an_anchor_that_never_arrives: 1}
WAITS = {an_anchor_that_never_arrives}
# the native diff finisher hands a room with a nested text back to the Python one
FINISHER_FALLS_BACK = {text_in_a_deleted_parent_with_formats}


# --------------------------------------------------------------------------
# rooms of 256 rows that fill: the served compaction fires inside a step


def typing_that_squashes():
    """A client types 400 characters rightward, a keystroke an update: the
    room compacts more than once and its rows squash to a few."""
    doc = Doc(client_id=1)
    log = _capture(doc)
    t = doc.get_text("text")
    for i in range(400):
        with doc.transact() as txn:
            t.insert(txn, i, "abcdefghij"[i % 10])
    return log


def compaction_with_moves_live():
    """An array whose elements were moved keeps taking pushes until the
    room compacts: the move rows and what they claim outlive it."""
    doc, arr, log = _seeded_array(list(range(6)))
    with doc.transact() as txn:
        arr.move_range_to(txn, 1, 2, 5)
    with doc.transact() as txn:
        arr.move_to(txn, 0, 3)
    for i in range(260):
        with doc.transact() as txn:
            arr.push_back(txn, 100 + i)
        if i % 20 == 19:
            with doc.transact() as txn:
                arr.remove_range(txn, len(arr) - 12, 10)
    return log


def an_update_split_across_a_compaction():
    """A peer that synced the first 40 characters inserts in their middle
    after the room compacted: its origin lies inside what is by then one
    squashed row, which the step splits."""
    doc = Doc(client_id=1)
    log = _capture(doc)
    t = doc.get_text("text")
    for i in range(40):
        with doc.transact() as txn:
            t.insert(txn, i, "abcdefghij"[i % 10])
    peer = Doc(client_id=2)
    for u in log:
        peer.apply_update_v1(u)
    plog = _capture(peer)
    with peer.transact() as txn:
        peer.get_text("text").insert(txn, 20, "PEER")
    for i in range(40, 300):
        with doc.transact() as txn:
            t.insert(txn, i, "abcdefghij"[i % 10])
    return log + plog


FILLING = [typing_that_squashes, compaction_with_moves_live, an_update_split_across_a_compaction]
MOVES.add(compaction_with_moves_live)
READS[compaction_with_moves_live] = ARRAY


@functools.lru_cache(maxsize=None)
def _log(scenario):
    return scenario()


def _new_server(shard_docs: bool, filling: bool):
    if filling:
        return _log_server([_log(s) for s in FILLING], shard_docs, SMALL_ROOMS, SMALL_CAPACITY)
    logs = [_log(s) for s in SCENARIOS + [_array_beside_texts]]
    return _log_server(logs, shard_docs, ROOMS, CAPACITY)


@functools.lru_cache(maxsize=None)
def _server(shard_docs: bool, filling: bool):
    """The layout's server of rooms served one after another."""
    return _new_server(shard_docs, filling)


@functools.lru_cache(maxsize=None)
def _served(shard_docs: bool, scenario) -> dict:
    """Kept: the doc-sharded case compares with the one-device case's bytes."""
    compactions = metrics.counter("ingest.room_compactions")
    before = compactions.value
    got = _serve_one(
        _server(shard_docs, scenario in FILLING), scenario.__name__, _log(scenario),
        READS.get(scenario, TEXT), settles=scenario not in WAITS, diff=scenario not in MOVES,
    )
    got["compactions"] = compactions.value - before
    return got


CASES = [(s, sharded) for sharded in (False, True) for s in SCENARIOS + FILLING]


@pytest.mark.parametrize(
    "scenario,shard_docs",
    CASES,
    ids=[f"{s.__name__}-{'doc_sharded' if sh else 'one_device'}" for s, sh in CASES],
)
def test_a_served_wire_log_equals_the_oracle(scenario, shard_docs):
    got = _served(shard_docs, scenario)
    log = _log(scenario)
    host_lane = HOST_LANE.get(scenario, 0)
    assert got["lanes"] == (len(log) - host_lane, host_lane)
    assert (got["stashed"] > 0) == (scenario in WAITS)
    _assert_equals_the_oracle(got, log, READS.get(scenario, TEXT))
    assert (got["compactions"] > 0) == (scenario in FILLING)
    assert got["finisher_fallbacks"] == (scenario in FINISHER_FALLS_BACK)
    if shard_docs:
        one = _served(False, scenario)
        assert got == one  # the same bytes, reads, lanes and compactions
        _assert_spans_every_device(_server(True, scenario in FILLING))


# --------------------------------------------------------------------------
# the same logs with every room's whole log queued before the first flush:
# queues many updates deep, and every dispatch carries all the rooms that
# still have one — moves, storms, a waiting stash and plain text side by side


@functools.lru_cache(maxsize=None)
def _served_together(shard_docs: bool, filling: bool) -> dict:
    scenarios = FILLING if filling else SCENARIOS
    server = _new_server(shard_docs, filling)
    ing = server.ingestor
    compactions = metrics.counter("ingest.room_compactions")
    before = (ing.fast_docs, ing.slow_docs, compactions.value)
    sessions = {s: server.connect_frames(s.__name__)[0] for s in scenarios}
    for s, session in sessions.items():
        for update in _log(s):
            assert server.receive_frames(session, _frame(update)) == []
    deepest = max(len(_log(s)) for s in scenarios)
    assert server.pending_device_updates() == sum(len(_log(s)) for s in scenarios)
    steps = rooms_in_widest_step = 0
    while server.pending_device_updates():
        rooms_in_widest_step = max(rooms_in_widest_step, sum(bool(q) for q in server._queues))
        with jax.transfer_guard_device_to_device("disallow"):
            assert server.flush_device(max_steps=1) == 1
        steps += 1
    jax.block_until_ready(ing.state)
    assert steps == deepest and rooms_in_widest_step == len(scenarios)
    assert ing.fast_recoveries == 0 and not server._host_tenants
    assert not np.asarray(ing.state.error).any()
    got = {
        s: _read_room(server, s.__name__, READS.get(s, TEXT), diff=s not in MOVES)
        for s in scenarios
    }
    got["lanes"] = (ing.fast_docs - before[0], ing.slow_docs - before[1])
    got["compactions"] = compactions.value - before[2]
    got["waiting"] = {s for s in scenarios if ing.pending_update(server.slot_of(s.__name__))}
    return got


@pytest.mark.parametrize(
    "scenario,shard_docs",
    CASES,
    ids=[f"{s.__name__}-{'doc_sharded' if sh else 'one_device'}" for s, sh in CASES],
)
def test_logs_queued_whole_and_served_together_equal_the_oracle(scenario, shard_docs):
    filling = scenario in FILLING
    together = _served_together(shard_docs, filling)
    _assert_equals_the_oracle(together[scenario], _log(scenario), READS.get(scenario, TEXT))
    alone = _served(shard_docs, scenario)
    for read in ("sv", "reads", "text", "diff", "finisher_fallbacks"):
        assert together[scenario][read] == alone[read], read  # the same bytes as served alone
    scenarios = FILLING if filling else SCENARIOS
    host_lane = sum(HOST_LANE.get(s, 0) for s in scenarios)
    assert together["lanes"] == (sum(len(_log(s)) for s in scenarios) - host_lane, host_lane)
    assert together["waiting"] == WAITS & set(scenarios)
    assert (together["compactions"] > 0) == filling


def test_the_storms_straddle_the_cheap_tier():
    """What the four storms are for: the widest scan of each, counted by
    the host CRDT's own probe, lies under, at, one over and far over the
    bound."""
    from ytpu.core import store

    cheap, _ = scan_tier_plan()
    assert cheap == SCAN_TIER_CHEAP_DEFAULT == 32
    widest = {}
    for scenario in (
        conflicts_under_the_cheap_tier, conflicts_at_the_cheap_tier,
        conflicts_one_over_the_cheap_tier, conflicts_far_over_the_cheap_tier,
    ):
        store.SCAN_WIDTH_PROBE = []
        try:
            oracle = Doc(client_id=99)
            for update in _log(scenario):
                oracle.apply_update_v1(update)
            widest[scenario] = max(store.SCAN_WIDTH_PROBE)
        finally:
            store.SCAN_WIDTH_PROBE = None
    assert [widest[s] for s in widest] == [12, cheap, cheap + 1, 108]


# --------------------------------------------------------------------------
# one dispatch that carries an array room with moves and two text rooms


def _array_beside_texts():
    doc, arr, log = _seeded_array(list(range(4)))
    with doc.transact() as txn:
        arr.move_to(txn, 3, 0)
    with doc.transact() as txn:
        arr.push_back(txn, 99)
    return log


@pytest.mark.parametrize("shard_docs", [False, True], ids=["one_device", "doc_sharded"])
def test_a_step_carries_a_move_room_beside_text_rooms(shard_docs):
    server = _server(shard_docs, False)
    ing = server.ingestor
    moves, typed = _log(_array_beside_texts), _log(random_edit_trace)
    logs = {"beside-array": moves, "beside-text-1": typed[:3], "beside-text-2": typed[:3]}
    sessions = {room: server.connect_frames(room)[0] for room in logs}
    fast = ing.fast_docs
    for i in range(3):
        for room, log in logs.items():
            assert server.receive_frames(sessions[room], _frame(log[i])) == []
        with jax.transfer_guard_device_to_device("disallow"):
            assert server.flush_device(max_steps=1) == 1  # the three rooms, one dispatch
        assert not server.pending_device_updates()
    assert ing.fast_docs - fast == 9 and ing.fast_recoveries == 0
    oracle = {room: Doc(client_id=99) for room in logs}
    for room, log in logs.items():
        for update in log[:3]:
            oracle[room].apply_update_v1(update)
        slot = server.slot_of(room)
        assert int(np.asarray(ing.state.error)[slot]) == 0
        assert dict(server.device_state_vector(room).clocks) == dict(oracle[room].state_vector().clocks)
    assert server.device_tree("beside-array")["seq"] == oracle["beside-array"].get_array("a").to_json()
    for room in ("beside-text-1", "beside-text-2"):
        assert server.device_text(room) == oracle[room].get_text("text").get_string()
    if shard_docs:
        _assert_spans_every_device(server)
