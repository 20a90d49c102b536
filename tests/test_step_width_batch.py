"""The host lane's batch at the step's width, in two packed uploads (ISSUE-40).

A step is as wide as the rooms that carry a payload (`_active_slots`: 16, 32,
... or every slot), and so is the batch its two lanes meet in: the host lane
plans the rooms with a payload alone, `BatchEncoder.batch_packed` pads two
int32 arrays to `[W, U, 23]` and `[W, R, 4]` (a `PackedBatch`), two uploads
carry them, and since PR 42 the pair is what crosses every program boundary:
`merge_stream` lays the decoder's pair over it and hands on a pair, a step
without a fast lane hands the upload (or the kept batch) straight to the
integrate program, and `unpack_batch` takes the 27 planes apart inside that
program, which so has one form a bucket. What a program works on is, row for
row, what the parent built over every slot, gathered at `active`:

(a) the packed form, unpacked by the device program, is bit-equal to the 27
    planes the parent padded (`test_batch_cache._parent_planes` is that code);
(b) a served sequence (128 rooms, on one device and doc-sharded over the
    suite's 8: a dense text prefill, record loads and `set`s on the host lane,
    delete-only and text lanes on the fast one, both in one step, an update
    that waits in the stash, a 17-room step, an idle step) hands `merge_stream`
    and the integrate step the parent's dense batch gathered at `active`, and
    leaves every room's text, array, state vector and full-state diff equal
    to `ytpu.core.Doc`'s;
(c) `_plan_doc` runs for the host-lane rooms of the step and for no other slot.
"""

import json
import os
import random

import jax
import numpy as np
import pytest

from benchmark import grammar as g
from benchmark.generators import record_mix
from test_batch_cache import _Spy, _parent_merged, _parent_planes, _wrong_leaves
from test_record_store import _canonical, _clean, _device_array
from ytpu.core import Doc
from ytpu.core.state_vector import StateVector
from ytpu.models import ingest as ingest_mod
from ytpu.models.batch_doc import BatchEncoder, PackedBatch, UpdateBatch, unpack_batch_jit
from ytpu.sync.device_server import DeviceSyncServer
from ytpu.sync.protocol import Message, SyncMessage
from ytpu.utils import metrics
from ytpu.utils.phases import phases

pytestmark = pytest.mark.usefixtures("native_lib")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N_ROOMS, CAPACITY = 128, 256  # 16 rooms a device when doc-sharded; 32 <= 128 // 4: the 17-room step is compact too
EITHER = pytest.mark.parametrize("served", [False, True], ids=["one_device", "doc_sharded"], indirect=True)


# --- (a) the packed form against the parent's planes -----------------------------


def _some_rows(r: random.Random, width: int, n_rows: int, n_dels: int):
    """Row and delete tuples in a few rooms (the first and the last among
    them), one room filled to the bucket: the values are arbitrary int32s,
    the negative ones and the extremes included."""
    value = lambda: r.choice((0, 1, -1, 2**31 - 1, -(2**31), r.randrange(-(2**31), 2**31)))
    all_rows, all_dels = [[] for _ in range(width)], [[] for _ in range(width)]
    rooms = sorted({0, width - 1, *r.sample(range(width), 5)})
    for k, d in enumerate(rooms):
        all_rows[d] = [tuple(value() for _ in range(22)) for _ in range(n_rows if k == 1 else r.randint(0, min(n_rows, 6)))]
        all_dels[d] = [tuple(value() for _ in range(3)) for _ in range(n_dels if k == 2 else r.randint(0, min(n_dels, 3)))]
    return all_rows, all_dels


@pytest.mark.parametrize("n_dels", [4, 16])
@pytest.mark.parametrize("n_rows", [4, 512])
@pytest.mark.parametrize("width", [16, 64, 1024], ids=["16", "64", "n_docs"])
def test_the_packed_batch_unpacks_to_the_parents_planes(width, n_rows, n_dels):
    all_rows, all_dels = _some_rows(random.Random(40_000 + width + n_rows + n_dels), width, n_rows, n_dels)
    want = _parent_planes(all_rows, all_dels, n_rows, n_dels)
    enc = BatchEncoder()
    packed = enc.batch_packed(all_rows, all_dels, n_rows, n_dels)
    assert isinstance(packed, PackedBatch) and type(packed.rows) is type(packed.dels) is np.ndarray
    assert packed.rows.shape == (width, n_rows, 23) and packed.dels.shape == (width, n_dels, 4)
    for array in packed:  # two uploads, each one copy of one buffer
        assert array.dtype == np.int32 and array.flags["C_CONTIGUOUS"]
    assert sum(a.nbytes for a in packed) <= 1.25 * sum(p.nbytes for p in want)  # a valid column is an i32, not a bool
    on_device = unpack_batch_jit(jax.tree.map(jax.numpy.asarray, packed))
    assert isinstance(on_device, UpdateBatch) and _wrong_leaves(on_device, want) == []
    assert _wrong_leaves(unpack_batch_jit(on_device), want) == []  # planes pass through
    # the one-call form, on the default device
    assert _wrong_leaves(enc.batch_from_rows(all_rows, all_dels, n_rows, n_dels), want) == []


def test_an_unbucketed_batch_is_as_wide_as_its_longest_room():
    packed = BatchEncoder().batch_packed([[], [(1,) * 22] * 3], [[(2,) * 3], []])
    assert packed.rows.shape == (2, 3, 23) and packed.dels.shape == (2, 1, 4)
    assert _wrong_leaves(packed, _parent_planes([[], [(1,) * 22] * 3], [[(2,) * 3], []], 3, 1)) == []


# --- (b) a served sequence ------------------------------------------------------

SMALL = {
    "n_docs": N_ROOMS, "capacity": CAPACITY,
    "prefill": {"classes": [{"rooms": 2, "stage_rows": [3]}, {"rooms": None, "stage_rows": [2]}]},
    "records": {"stage_blocks": 12, "classes": [{"rooms": 2, "records": 6}, {"rooms": None, "records": 12}]},
}
# rooms the 24 sessions' Zipf leaves alone: 17 of them, one more than the 16-wide step holds
TYPISTS = list(range(100, 117))
LATE_ROOM = 120


class _Typist:
    """A client synced with its room's text prefill, typing into it: every
    edit is the wire update a real `Doc` sends (plain strings and deletes:
    the fast lane)."""

    def __init__(self, room: int, prefill):
        self.doc = Doc(client_id=800_000 + room)
        for u in prefill.for_room(room).stages:
            self.doc.apply_update_v1(u)
        self.sent = []
        self.doc.observe_update_v1(lambda p, o, t: self.sent.append(p))

    def edit(self, word: str, cut: bool = False) -> bytes:
        text = self.doc.get_text(g.ROOT)
        with self.doc.transact() as txn:
            if cut:
                text.remove_range(txn, 0, 1)
            else:
                text.insert(txn, len(text.get_string()) // 2, word)
        return self.sent[-1]


def _ticks(seed: int):
    """(what the step is for, [(room, update)]) in the order served."""
    with open(os.path.join(ROOT, "benchmark", "traffic", "record-flood.json")) as f:
        mix = dict(json.load(f), sessions=24, edits_per_session=3, tick_max_frames=6)
    prefill = g.Prefill(SMALL["prefill"], N_ROOMS, seed)
    plan = record_mix.plan(SMALL, mix, prefill, seed, 1.0)
    typists = {k: _Typist(k, prefill) for k in TYPISTS + [LATE_ROOM]}
    ticks = [("prefill", [(k, prefill.for_room(k).stages[0]) for k in range(N_ROOMS)])]
    ticks += [("load", [(op.room, op.update) for op in plan.preload[i : i + 16]]) for i in range(0, len(plan.preload), 16)]
    # the pool as the benchmark's loop takes it: ticks of 6 frames, a dispatch a depth, a room once in each
    pool = [[(op.room, op.update) for op in lanes] for lanes in record_mix.dispatches(plan.ops, 6)]
    ticks += [("pool", tick) for tick in pool[:4]]
    assert not {room for tick in pool for room, _ in tick} & set(TYPISTS + [LATE_ROOM])
    # the fast lane's text rows beside the pool's sets and deletes
    ticks.append(("pool+text", pool[4] + [(k, typists[k].edit("mid")) for k in TYPISTS[:5]]))
    ticks.append(("text", [(k, typists[k].edit("abc")) for k in TYPISTS[:3]]))
    ticks.append(("idle", []))
    # the late room's second edit comes first: it waits in the stash, and the step after plans both
    first, second = typists[LATE_ROOM].edit("one"), typists[LATE_ROOM].edit("two")
    ticks.append(("stashed", pool[5] + [(LATE_ROOM, second)]))
    ticks.append(("unstashed", pool[6] + [(LATE_ROOM, first)]))
    # 17 text rooms beside the pool's next dispatch with a set in it: the 32-wide step, both lanes
    wide = next(i for i in range(7, len(pool)) if any(u[0] for _, u in pool[i]))
    ticks += [("pool", tick) for tick in pool[7:wide]]
    ticks.append(("wide", [(k, typists[k].edit("w", cut=k % 3 == 0)) for k in TYPISTS] + pool[wide]))
    ticks += [("pool", tick) for tick in pool[wide + 1 :]]
    return plan, prefill, ticks


@pytest.fixture(scope="module")
def served(request):
    """The sequence served once a mode, one `flush_device` step at a time;
    what every `apply_bytes` call handed its programs, beside what the
    parent would have built for it over every slot."""
    shard_docs = request.param
    plan, prefill, ticks = _ticks(40_000_001)
    server = DeviceSyncServer(n_docs=N_ROOMS, capacity=CAPACITY, device_authoritative=True, shard_docs=shard_docs)
    ing = server.ingestor
    for c in plan.clients:  # preregistered, as the cell's are
        ing.enc.interner.intern(c)
    sessions = {k: server.connect_frames(g.room_name(k))[0] for k in range(N_ROOMS)}
    monkeypatch = pytest.MonkeyPatch()
    real_apply_bytes = ing.apply_bytes
    steps = []
    try:
        spy = _Spy(monkeypatch, ing)
        tag = [None]

        def apply_bytes(payloads, columns=None):
            merged, applied = len(spy.merged), len(spy.applied)
            spy.plan_calls = []
            out = real_apply_bytes(payloads, columns)
            bucket, want, host_lane = spy.parent_planes(payloads)
            live = [d for d, p in enumerate(payloads) if p is not None]
            assert len(spy.applied) == applied + 1  # no lane was flagged: one integrate call
            active = spy.active[-1]
            step = dict(tag=tag[0], live=live, host_lane=host_lane, bucket=bucket, want=want,
                        active=None if active is None else np.asarray(active),
                        plan_calls=list(spy.plan_calls), applied=spy.applied[-1],
                        merged=spy.merged[merged:], merge_idx=None, parent_merged=None)
            if step["merged"]:
                (stream, idx, prefix, base), kw = spy.merge_args[-1]
                step["merge_idx"] = np.asarray(idx)
                # what the parent's merge made of its dense batch: the decoder's lanes at their rooms' slots
                fast = np.asarray([d for d in live if d not in host_lane], dtype=np.int32)
                step["parent_merged"] = _parent_merged(want, unpack_batch_jit(stream), fast, prefix, base, kw["width"])
            steps.append(step)
            return out

        monkeypatch.setattr(ing, "apply_bytes", apply_bytes)
        before = {n: metrics.counter(n).value for n in COUNTERS}
        phases.reset()
        phases.enable()
        try:
            for tag[0], frames in ticks:
                for k, u in frames:
                    assert server.receive_frames(sessions[k], Message.sync(SyncMessage.update(u)).encode_v1()) == []
                if not frames:  # a flush with nothing queued dispatches nothing: the idle step is the ingestor's
                    ing.apply_bytes([None] * N_ROOMS)
                while server.pending_device_updates():
                    assert server.flush_device(max_steps=1) == 1
                    jax.block_until_ready(ing.state)
            recorded = phases.snapshot()
        finally:
            phases.disable()
        counted = {n: metrics.counter(n).value - v for n, v in before.items()}
    finally:
        monkeypatch.undo()
    return server, ticks, steps, counted, recorded


COUNTERS = ("ingest.compact_steps", "ingest.dense_steps", "ingest.batch_builds", "ingest.batch_reuses",
            "ingest.fast_recoveries", "ingest.enqueue_outputs")


def _gathered(planes, active):
    return planes if active is None else [p[active] for p in planes]


@EITHER
def test_the_sequence_has_every_kind_of_step(served):
    _, ticks, steps, counted, _ = served
    assert [s["tag"] for s in steps] == [tag for tag, _ in ticks]  # a room an update a tick: one step each
    widths = [None if s["active"] is None else len(s["active"]) for s in steps]
    by_tag = {s["tag"]: (w, s) for w, s in zip(widths, steps)}
    assert by_tag["prefill"][0] is None and set(widths[1:]) == {16, 32} and by_tag["wide"][0] == 32
    assert counted["ingest.dense_steps"] == 1 and counted["ingest.compact_steps"] == len(steps) - 1
    assert counted["ingest.fast_recoveries"] == 0
    kinds = {(bool(s["host_lane"]), len(s["live"]) > len(s["host_lane"])) for s in steps}
    assert kinds == {(True, True), (True, False), (False, True), (False, False)}  # the lanes: both, either, neither
    assert by_tag["idle"][1]["live"] == [] and not by_tag["idle"][1]["merged"]
    assert all(len(s["host_lane"]) == len(s["live"]) for s in steps if s["tag"] == "load")
    # a delete-only lane (no client section) rode the fast lane beside host-lane sets
    lone = [u for _, tick in ticks for _, u in tick if u[0] == 0]
    assert lone and any(s["host_lane"] and s["merged"] for s in steps if s["tag"] == "pool")
    # the stashed edit planned nothing in its step and two rows in the next
    late = lambda s: s["want"][22][LATE_ROOM].sum()  # the parent's `valid` plane
    assert LATE_ROOM in by_tag["stashed"][1]["host_lane"] and late(by_tag["stashed"][1]) == 0
    assert LATE_ROOM in by_tag["unstashed"][1]["host_lane"] and late(by_tag["unstashed"][1]) == 2
    assert by_tag["wide"][1]["host_lane"] and len(by_tag["wide"][1]["live"]) > 17


@EITHER
def test_every_step_hands_merge_stream_the_parents_batch_gathered_at_active(served):
    _, _, steps, _, _ = served
    for n, s in enumerate(steps):
        active = s["active"]
        assert s["bucket"][0] == N_ROOMS
        if active is not None:
            assert set(s["live"]) <= set(active.tolist()), n
        fast = [d for d in s["live"] if d not in s["host_lane"]]
        assert len(s["merged"]) == bool(fast), n
        assert type(s["applied"]) is PackedBatch, n  # the integrate program has one form: it is handed the pair
        if not fast:  # no merge: the host lane's upload, or the kept batch, goes to the step as it is
            assert _wrong_leaves(s["applied"], _gathered(s["want"], active)) == [], n
            continue
        assert type(s["merged"][0]) is PackedBatch, n
        assert _wrong_leaves(s["merged"][0], _gathered(s["want"], active)) == [], n
        # the decoded lanes land at their rooms' rows of the step
        at = fast if active is None else [active.tolist().index(d) for d in fast]
        assert s["merge_idx"].tolist() == at, n


@EITHER
def test_every_step_integrates_the_parents_merged_batch_gathered_at_active(served):
    _, _, steps, _, _ = served
    for n, s in enumerate(steps):
        if s["parent_merged"] is None:
            continue
        assert {a.shape[0] for a in s["applied"]} == {N_ROOMS if s["active"] is None else len(s["active"])}, n
        assert _wrong_leaves(s["applied"], _gathered(s["parent_merged"], s["active"])) == [], n
    assert sum(1 for s in steps if s["parent_merged"] is not None and s["host_lane"]) >= 5
    assert sum(1 for s in steps if s["parent_merged"] is not None) == sum(1 for s in steps if s["merged"]) >= 8


@EITHER
def test_plan_doc_runs_for_the_host_lane_rooms_alone(served):
    _, _, steps, _, recorded = served
    for n, s in enumerate(steps):
        assert s["plan_calls"] == s["host_lane"], n  # ascending, once each
        assert set(s["plan_calls"]) <= set(s["live"]), n
    assert sum(len(s["plan_calls"]) for s in steps) < sum(len(s["live"]) for s in steps)
    assert recorded["ingest.plan.decode_host"]["calls"] == sum(len(s["host_lane"]) for s in steps)


@EITHER
def test_a_build_is_two_uploads_and_no_enqueue(served):
    """What the stage `ingest.plan.h2d` counts is the two packed arrays at
    the step's width (whole on every device of a doc-sharded server in a
    compact step, by room in a dense one), and a step that is handed a
    kept batch sends nothing. No served step enqueues `jit_unpack_batch`:
    the pair goes to the integrate program as it is, so a step's programs
    hand back 29 buffers (the state's) where no room rode the fast lane,
    and where one did 8 more in a compact step (the gather's 3: the lane
    matrix, the lane table, `active`; the decoder's 3, the merge's 2) and 7
    in a dense one, which has no `active`."""
    server, _, steps, counted, recorded = served
    copies = len(jax.devices()) if server.ingestor._on_every_chip is not None else 1
    sent, kept = 0, set()
    for s in steps:
        width = N_ROOMS if s["active"] is None else len(s["active"])
        bucket = (width,) + s["bucket"][1:]
        if s["host_lane"] or bucket not in kept:
            sent += 4 * width * (23 * bucket[1] + 4 * bucket[2]) * (1 if s["active"] is None else copies)
        if not s["host_lane"]:
            kept.add(bucket)
    assert kept == set(server.ingestor._batch_cache)  # small enough, all of them, to stay
    builds = counted["ingest.batch_builds"]
    assert builds + counted["ingest.batch_reuses"] == len(steps) and 0 < counted["ingest.batch_reuses"]
    assert recorded["ingest.plan.h2d"]["h2d_bytes"] == sent
    assert builds == sum(1 for s in steps if s["host_lane"]) + len(kept)
    no_fast_lane = sum(1 for s in steps if not s["merged"])
    assert "ingest.plan.unpack" not in recorded and not hasattr(ingest_mod, "unpack_batch_jit")
    assert all(type(s["applied"]) is PackedBatch for s in steps) and no_fast_lane >= 8
    assert recorded["ingest.merge.scatter"]["calls"] == len(steps) - no_fast_lane
    assert counted["ingest.enqueue_outputs"] == recorded["ingest.enqueue_outputs"]["value"]
    assert counted["ingest.enqueue_outputs"] == 29 * len(steps) + sum(
        7 + (s["active"] is not None) for s in steps if s["merged"]
    )
    assert recorded["ingest.plan.h2d"]["calls"] == recorded["ingest.plan.host_rows"]["calls"] == len(steps)


@EITHER
def test_the_kept_batches_are_as_wide_as_their_steps(served):
    server, _, steps, _, _ = served
    ing = server.ingestor
    kept = dict(ing._batch_cache)
    assert {k[0] for k in kept} <= {16, 32, N_ROOMS} and 16 in {k[0] for k in kept}
    empty = lambda w: [[]] * w
    for (width, n_rows, n_dels), batch in kept.items():
        assert _wrong_leaves(batch, _parent_planes(empty(width), empty(width), n_rows, n_dels)) == []
        assert isinstance(batch, PackedBatch)
        for a in batch:  # a compact batch whole on every device, a dense one by room
            assert a.sharding.is_fully_replicated == (width != N_ROOMS or len(a.sharding.device_set) == 1)


@EITHER
def test_the_served_rooms_equal_the_oracle(served):
    server, ticks, _, _, _ = served
    _clean(server)
    diffs = server.device_encode_diff_many([(g.room_name(k), StateVector()) for k in range(N_ROOMS)])
    _clean(server)
    for k, diff in enumerate(diffs):
        want = Doc(client_id=1)
        for _, frames in ticks:
            for room, u in frames:
                if room == k:
                    want.apply_update_v1(u)
        assert not want.store.pending
        root, name = record_mix.records_root(k), g.room_name(k)
        array, sv = want.get_array(root).to_json(), dict(want.state_vector().clocks)
        assert server.device_text(name) == want.get_text(g.ROOT).get_string(), k
        assert _device_array(server, name, root) == array, k
        assert dict(server.device_state_vector(name).clocks) == sv == dict(server.ingestor.svs[k].clocks), k
        assert _canonical(diff, root) == (array, sv, _canonical(want.encode_state_as_update_v1(), root)[2]), k


# --- the counter's reader ---------------------------------------------------------


def test_the_benchmark_reads_the_output_buffers_a_step():
    """`enqueue_outputs_per_step.flood`: the entry is in `per_layer` and
    names every cell; its reader divides the window's
    count by the window's steps, from the counter deltas or the phase
    recorder's copy, and has nothing to say of a program without the counter
    (the parent)."""
    from benchmark.run import applies, load_reader
    from benchmark.window import Window

    name = "enqueue_outputs_per_step.flood"
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = [w["name"] for w in bench["workloads"]]
    entry = next(m for m in bench["per_layer"] if m["name"] == name)  # later PRs append after it
    assert entry == {
        "name": name, "unit": "buffers/step", "better": "lower", "source": "program_counter",
        "layer": "ingest merge", "moves": "updates_per_s", "workloads": cells,
    }
    assert all(applies(entry, c, {"updates_per_s", "setup_s"}) for c in cells)
    read = load_reader("layers", name).read
    window = lambda **kw: Window(rec=None, t_open=0.0, t_close=30.0, setup_s=1.0,
                                 dispatch_spans=[(float(i), i + 0.5, 1) for i in range(10)], **kw)
    assert read(window(counters={"ingest.enqueue_outputs": 350})) == 35.0
    assert read(window(phases={"ingest.enqueue_outputs": {"value": 320.0}})) == 32.0
    assert read(window()) is None and read(window(phases={"ingest.merge": {"calls": 10}})) is None
