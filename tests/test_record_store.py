"""Rooms that are stores of JSON records (ISSUE-39): the tldraw-over-Yjs shape.

A y-websocket room of the tldraw-yjs-example is a `Y.Array` of `{key, val}`
entries under y-utility's `YKeyValue`: `set` deletes the entry that held the
key and pushes the new one at the array's end, `delete` deletes it, and a
read walks the array and lets the rightmost live entry of a key win. `val`
is a `TLShape`, an object with objects in it, so every update that carries
one is a nested lib0 Any and the served path plans it on the host
(`ingest.slow.complex_any`); a `delete` carries none and rides the fast lane.
Each served case runs on one device and doc-sharded over the suite's 8 host
devices, as `tests/test_sharded_server.py` does:

(a) `YKeyValue`'s read stated in plain Python, not through `Doc`, and the
    served path held to it and to `ytpu.core.Doc` on seeded records: three
    writers, one past int32, none seeing another;
(b) a small `record-flood` trace (the benchmark's own generator,
    `benchmark/generators/record_mix.py`: a text prefill, the loaders'
    staged records under a second root, the pool) equals `ytpu.core.Doc` fed
    the same updates: array, state vector, canonical re-encoding; and the
    counters the benchmark's readers read count what was sent;
(c) `ingest.slow.<reason>`: a case a reason, each counted once, and a
    fast-lane payload counts none.
"""

import json
import os

import jax
import numpy as np
import pytest

from benchmark import grammar as g
from benchmark.generators import record_mix
from ytpu.core import Doc
from ytpu.core.state_vector import StateVector
from ytpu.core.update import Update, merge_updates_v1
from ytpu.encoding.lib0 import Writer
from ytpu.models import ingest as ingest_mod
from ytpu.models.ingest import BatchIngestor
from ytpu.native import decode_update_columns
from ytpu.sync.device_server import DeviceSyncServer
from ytpu.sync.protocol import Message, SyncMessage
from ytpu.utils import metrics
from ytpu.utils.phases import phases

pytestmark = pytest.mark.usefixtures("native_lib")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N_ROOMS, CAPACITY = 16, 512  # 2 rooms a device when doc-sharded
EITHER = pytest.mark.parametrize("shard_docs", [False, True], ids=["one_device", "doc_sharded"])
WATCHED = ("ingest.fast_recoveries", "encode.demotions", "lane.demotions", "net.bad_frames")
REASONS = tuple("ingest.slow." + r for r in ingest_mod._SLOW_REASONS)
LANES = ("ingest.fast_docs", "ingest.slow_docs", "ingest.host_rows")


def _counts(names) -> dict:
    return {n: metrics.counter(n).value for n in names}


def _counted(before: dict) -> dict:
    return {n: metrics.counter(n).value - v for n, v in before.items()}


def _server(shard_docs: bool) -> DeviceSyncServer:
    return DeviceSyncServer(n_docs=N_ROOMS, capacity=CAPACITY, device_authoritative=True, shard_docs=shard_docs)


def _serve(server, sessions, ticks) -> None:
    """Every tick's frames handed over, then one `flush_device` step at a
    time until the queues are empty, as the benchmark's loop does."""
    for frames in ticks:
        for k, u in frames:
            frame = Message.sync(SyncMessage.update(u)).encode_v1()
            assert server.receive_frames(sessions[k], frame) == []
        while server.pending_device_updates():
            assert server.flush_device(max_steps=1) == 1
            jax.block_until_ready(server.ingestor.state)


def _clean(server) -> None:
    ing = server.ingestor
    assert not np.asarray(ing.state.error).any()
    assert not [d for d in range(ing.n_docs) if ing.pending_update(d) or ing.pending_ds(d)]
    assert ing.fast_recoveries == 0 and not server._host_tenants
    assert server._diff_pipeline.stats.fallback_docs == 0


def _device_array(server, room: str, root: str) -> list:
    tree = server.device_tree(room)
    branch = tree if server.ingestor.primary_roots[server.slot_of(room)] == root else tree["roots"][root]
    return branch["seq"]


def _canonical(update: bytes, root: str):
    fresh = Doc(client_id=2)
    fresh.apply_update_v1(update)
    return fresh.get_array(root).to_json(), dict(fresh.state_vector().clocks), fresh.encode_state_as_update_v1()


# --- (a) YKeyValue's read, in plain Python ------------------------------------------


def ykeyvalue_read(array) -> dict:
    """What `YKeyValue` holds of an array of `{key, val}` entries: it walks
    the array left to right and the rightmost entry of a key wins."""
    return {entry["key"]: entry["val"] for entry in array}


def plain_store(loaded, writers) -> dict:
    """The store after `writers` ({client id: [(op, key, val)]}; `set` or
    `delete`), each synced with the `loaded` entries and none seeing another.
    A writer's `set` and `delete` remove the entry that held the key as the
    writer holds the store: the loader's, or its own earlier push. A `set`
    pushes at the array's end; pushes that name the same left neighbour stand
    in ascending client id, as unsigned integers (Yjs `Item.integrate`:
    `tests/test_walkin_clients.py` states the rule on its own), so behind the
    loader's entries the writers' runs follow one another by client id. Holds
    while no writer removes the loader's last entry, which no case here does."""
    live = {("loader", j): entry for j, entry in enumerate(loaded)}  # in array order, as a dict keeps it
    for client in sorted(writers):
        held = {entry["key"]: ("loader", j) for j, entry in enumerate(loaded)}
        for n, (op, key, val) in enumerate(writers[client]):
            live.pop(held.pop(key, None), None)
            if op == "set":
                held[key] = (client, n)
                live[client, n] = {"key": key, "val": val}
    return ykeyvalue_read(live.values())


def _three_writers(seed: int):
    """Seeded `TLShape` records: 30 loaded in two stages of one-record blocks,
    then three writers (one past int32) that restyle the same record, add one,
    drag their own and delete one of the loader's, as `YKeyValue` does it:
    `remove` + `push_back` in one transaction."""
    shape, text = g.rng(seed, "shape"), g.rng(seed, "text")
    root = "tl_" + g.room_name(0)
    loaded = [record_mix.new_record(record_mix.shape_id(shape), j, shape, text) for j in range(30)]
    loader = Doc(client_id=900_032)
    sent = []
    loader.observe_update_v1(lambda p, o, t: sent.append(p))
    for entry in loaded:
        with loader.transact() as txn:
            loader.get_array(root).push_back(txn, entry)
    stages = [merge_updates_v1(sent[:16]), merge_updates_v1(sent[16:])]
    state = loader.encode_state_as_update_v1()
    writers, logs = {}, []
    for w, client in enumerate((7001, 2**31 + 77, 7002)):
        d = Doc(client_id=client)
        d.apply_update_v1(state)
        log, arr = [], d.get_array(root)
        d.observe_update_v1(lambda p, o, t, log=log: log.append(p))
        ops = writers[client] = []

        def index_of(key):
            return next(i for i, e in enumerate(arr.to_json()) if e["key"] == key)

        def change(op, key, val=None):
            with d.transact() as txn:
                if key in ykeyvalue_read(arr.to_json()):
                    arr.remove(txn, index_of(key))
                if op == "set":
                    arr.push_back(txn, {"key": key, "val": val})
            ops.append((op, key, val))

        shared, own, dropped = loaded[4], loaded[10 + w], loaded[20 + w]
        change("set", shared["key"], record_mix.changed_record(shared, shape, text)["val"])
        fresh = record_mix.new_record(record_mix.shape_id(shape), 100 + w, shape, text)
        change("set", fresh["key"], fresh["val"])
        change("set", own["key"], record_mix.changed_record(own, shape, text)["val"])
        change("set", own["key"], record_mix.changed_record(own, shape, text)["val"])  # the drag goes on
        change("delete", dropped["key"])
        assert len(log) == 5
        logs.append(log)
    return root, loaded, writers, stages + [u for step in zip(*logs) for u in step]


@EITHER
def test_a_served_record_store_reads_as_ykeyvalue_reads_it(shard_docs):
    root, loaded, writers, log = _three_writers(38_000_003)
    want = plain_store(loaded, writers)
    assert len(want) == 30 + 3 - 3  # three records added, three of the loader's deleted
    shared = loaded[4]["key"]  # all three set it: the rightmost entry is the largest client id's
    assert want[shared] == writers[2**31 + 77][0][2] != loaded[4]["val"]
    server = _server(shard_docs)
    room = g.room_name(0)
    session, _ = server.connect_frames(room)
    lanes, reasons, watched = _counts(LANES), _counts(REASONS), _counts(WATCHED)
    _serve(server, {0: session}, [[(0, u)] for u in log])
    _clean(server)
    # the two stages and the twelve sets are nested Any values; a delete carries no record
    assert _counted(lanes) == {"ingest.fast_docs": 3, "ingest.slow_docs": 14, "ingest.host_rows": 30 + 12}
    assert _counted(reasons) == dict(dict.fromkeys(REASONS, 0), **{"ingest.slow.complex_any": 14})
    assert _counted(watched) == dict.fromkeys(WATCHED, 0)
    assert ykeyvalue_read(_device_array(server, room, root)) == want
    oracle = Doc(client_id=1)
    for u in log:
        oracle.apply_update_v1(u)
    array = oracle.get_array(root).to_json()
    assert ykeyvalue_read(array) == want
    assert _device_array(server, room, root) == array
    assert dict(server.device_state_vector(room).clocks) == dict(oracle.state_vector().clocks)
    diff = server.device_encode_diff(room, StateVector())
    assert _canonical(diff, root) == (array, dict(oracle.state_vector().clocks),
                                      _canonical(oracle.encode_state_as_update_v1(), root)[2])


# --- (b) a small record-flood trace against ytpu.core.Doc ---------------------------

SMALL = {
    "n_docs": N_ROOMS, "capacity": CAPACITY,
    "prefill": {"classes": [{"rooms": 2, "stage_rows": [1]}, {"rooms": None, "stage_rows": [1]}]},
    "records": {"stage_blocks": 24, "classes": [{"rooms": 2, "records": 24}, {"rooms": None, "records": 40}]},
}


def _record_flood(seed: int):
    with open(os.path.join(ROOT, "benchmark", "traffic", "record-flood.json")) as f:
        mix = dict(json.load(f), sessions=40, edits_per_session=4, tick_max_frames=6)
    prefill = g.Prefill(SMALL["prefill"], N_ROOMS, seed)
    plan = record_mix.plan(SMALL, mix, prefill, seed, 1.0)
    ticks = [[(k, prefill.for_room(k).stages[0]) for k in range(N_ROOMS)]]
    for part in (plan.preload, plan.ops):
        ticks += [[(op.room, op.update) for op in part[i : i + 6]] for i in range(0, len(part), 6)]
    return plan, prefill, ticks


@EITHER
def test_a_served_record_flood_trace_equals_the_oracle(shard_docs):
    plan, prefill, ticks = _record_flood(38_000_001)
    server = _server(shard_docs)
    for c in plan.clients:  # preregistered, as the cell's are
        server.ingestor.enc.interner.intern(c)
    sessions = {k: server.connect_frames(g.room_name(k))[0] for k in range(N_ROOMS)}
    lanes, reasons, watched = _counts(LANES), _counts(REASONS), _counts(WATCHED)
    phases.reset()
    phases.enable()
    try:
        _serve(server, sessions, ticks)
        recorded = phases.snapshot()
    finally:
        phases.disable()
    _clean(server)
    sent = [u for frames in ticks for _, u in frames]
    deletes = sum(1 for u in sent if u[0] == 0)  # no client section: a delete range alone
    stages = sum(len(s.edits) for s in plan.sessions[40:])
    records = sum(e.chars for s in plan.sessions for e in s.edits if e.chars > 0)
    assert deletes == 16 and stages == 30  # a tenth of the pool; 2 rooms x 1 stage + 14 x 2
    # the text prefill and the deletes ride the fast lane; every update that carries a record plans on the host
    took = _counted(lanes)
    assert took == {"ingest.fast_docs": N_ROOMS + deletes, "ingest.slow_docs": len(sent) - N_ROOMS - deletes,
                    "ingest.host_rows": records}
    assert _counted(reasons) == dict(dict.fromkeys(REASONS, 0),
                                     **{"ingest.slow.complex_any": took["ingest.slow_docs"]})
    assert _counted(watched) == dict.fromkeys(WATCHED, 0)
    # the phase recorder's copy of the two counts, and the span around the host decode: what the readers read
    assert recorded["ingest.slow.complex_any"]["value"] == took["ingest.slow_docs"]
    assert recorded["ingest.host_rows"]["value"] == records
    assert recorded["ingest.plan.decode_host"]["calls"] == took["ingest.slow_docs"]
    diffs = server.device_encode_diff_many([(g.room_name(k), StateVector()) for k in range(N_ROOMS)])
    _clean(server)
    for k, diff in enumerate(diffs):
        want = Doc(client_id=1)
        for frames in ticks:
            for room, u in frames:
                if room == k:
                    want.apply_update_v1(u)
        root, name = record_mix.records_root(k), g.room_name(k)
        array, sv = want.get_array(root).to_json(), dict(want.state_vector().clocks)
        assert server.ingestor.primary_roots[k] == g.ROOT  # the array is the room's second root
        assert server.device_text(name) == want.get_text(g.ROOT).get_string(), k
        assert _device_array(server, name, root) == array, k
        assert dict(server.device_state_vector(name).clocks) == sv, k
        assert _canonical(diff, root) == (array, sv, _canonical(want.encode_state_as_update_v1(), root)[2]), k
    # the grammar's own count of every room's state vector, as `benchmark/oracle.py` reads `Edit.chars`
    expect = g.expected_clocks(plan.sessions, {s.sid: len(s.edits) for s in plan.sessions})
    for k in range(N_ROOMS):
        tpl = prefill.for_room(k)
        want = dict(expect[k])
        want[tpl.client_id] = tpl.chars
        assert dict(server.device_state_vector(g.room_name(k)).clocks) == want, k


# --- (c) a case a reason ------------------------------------------------------------


def _text_update(client: int, clock: int, text: str, origin=None) -> bytes:
    return g.encode_update(client, [g.Block(clock, origin, None, text)], {})


def _named(client: int, clock: int, text: str, root: str) -> bytes:
    w = Writer()
    for n in (1, 1, client, clock):
        w.write_var_uint(n)
    w.write_u8(4)  # a string, no neighbour: the parent is named
    w.write_var_uint(1)
    w.write_string(root)
    w.write_string(text)
    w.write_var_uint(0)
    return w.to_bytes()


def _of_a_doc(make) -> bytes:
    d = Doc(client_id=5)
    with d.transact() as txn:
        make(d, txn)
    return d.encode_state_as_update_v1()


def _empty_ds_sections(n: int) -> bytes:
    w = Writer()
    w.write_var_uint(0)
    w.write_var_uint(n)
    for c in range(n):
        w.write_var_uint(c + 1)
        w.write_var_uint(0)
    return w.to_bytes()


def _stash(ing: BatchIngestor) -> None:
    """Leave an out-of-order update in room 0's stash, as a step would."""
    ing._plan_doc(0, Update.decode_v1(_text_update(5, 3, "later")))
    assert ing.pending_update(0) is not None


CASES = {
    "complex_any": lambda: _of_a_doc(lambda d, txn: d.get_array("a").push_back(txn, {"key": "k", "val": {"props": {}}})),
    "pending": lambda: _text_update(6, 0, "abc"),  # the room holds a stash (`_stash`)
    "root": lambda: _named(5, 0, "abc", "r" * 40),  # a root name past the device's hash window
    "sections": lambda: _empty_ds_sections(40),
    "kind": lambda: _of_a_doc(lambda d, txn: d.get_array("a").push_back(txn, Doc(client_id=9, guid="sub"))),
    "key": lambda: _of_a_doc(lambda d, txn: d.get_map("m").insert(txn, "k" * 40, 1)),
    "dependency": lambda: _text_update(5, 3, "later"),  # a clock gap
    "client": lambda: _text_update(5, 2**31 - 2, "abc"),  # the block ends past int32
}
assert set(CASES) == set(ingest_mod._SLOW_REASONS)


@pytest.mark.parametrize("reason", ingest_mod._SLOW_REASONS)
def test_a_host_lane_payload_is_counted_once_by_its_first_reason(reason):
    ing = BatchIngestor(2, 64)
    if reason == "pending":
        _stash(ing)
    cols = decode_update_columns(CASES[reason]())
    assert cols is not None
    before = _counts(REASONS)
    phases.reset()
    phases.enable()
    try:
        assert ing._fast_eligible(0, cols) is False
        recorded = phases.snapshot()
    finally:
        phases.disable()
    assert _counted(before) == dict(dict.fromkeys(REASONS, 0), **{"ingest.slow." + reason: 1})
    assert {n: st["value"] for n, st in recorded.items() if n.startswith("ingest.slow.")} == {"ingest.slow." + reason: 1}


def test_a_fast_lane_payload_counts_no_reason():
    ing = BatchIngestor(2, 64)
    before = _counts(REASONS)
    phases.reset()
    phases.enable()
    try:
        first = decode_update_columns(_text_update(5, 0, "abc"))
        assert ing._slow_reason(0, first) is None and ing._fast_eligible(0, first) is True
        ing.svs[0].set_max(5, 3)  # as the prescan's walk leaves the mirror
        for payload in (
            _text_update(5, 3, "def", origin=(5, 2)),
            g.encode_update(5, [], {5: [(1, 2)]}),  # a delete range alone
            _of_a_doc(lambda d, txn: d.get_map(g.ROOT).insert(txn, "k", {"flat": 1})),  # an object of scalars
        ):
            assert ing._fast_eligible(0, decode_update_columns(payload)) is True
        recorded = phases.snapshot()
    finally:
        phases.disable()
    assert _counted(before) == dict.fromkeys(REASONS, 0)
    assert not [n for n in recorded if n.startswith("ingest.slow.")]
