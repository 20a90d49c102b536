"""One upload a served step (ISSUE-44).

A step that merges makes its small host arrays once, as one contiguous
buffer (the step's manifest: the wire arena, the fast lanes' columns, the
step's `active` slots), sends it in one transfer, and the programs it
enqueues take their operands out of it on the device:

(a) the first program's lane matrix from a manifest is, byte for byte,
    `gather_raw_lanes` over the arena, and the lane table gives back every
    column to the element;
(b) `ingest.step_uploads` counts what a step sent: one leaf where it merges
    on a kept batch, the host lane's beside it where a room took that lane;
(c) a sweep over the lane counts of a tick builds one form of the integrate
    program and one of the decoder a lane count, as before: only the first
    program is keyed by the wire bucket;
(d) a state laid over the CPU mesh gets the manifest whole on every device
    and ends byte-equal to the one-device run.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_batch_cache import _four_and_four
from test_table_cache import _Room, _cut, _flag_lanes, _type
from ytpu.models import ingest as ingest_mod
from ytpu.models.batch_doc import _apply_update_batch_in_place_jit, get_string
from ytpu.models.ingest import BatchIngestor, _bucket, pack_lane_table, pack_manifest
from ytpu.ops import decode_kernel as dk
from ytpu.utils import metrics
from ytpu.utils.phases import phases

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
I32_MAX = 2**31 - 1


# --- (a) the manifest, packed on the host and taken apart on the device ---------


@pytest.mark.parametrize("longest", [20, 200], ids=["wire_256", "wire_4096"])
@pytest.mark.parametrize("lanes", [1, 2, 13, 16])
def test_the_manifest_opens_to_the_lane_matrix_and_the_lane_table(lanes, longest):
    r = np.random.default_rng(44_000 + 31 * lanes + longest)
    payloads = [r.integers(0, 256, size=int(n), dtype=np.uint8).tobytes() for n in r.integers(2, longest + 1, size=lanes)]
    payloads[0] = payloads[0][:2]  # a lane shorter than any window the decoder reads
    lens = np.asarray([len(p) for p in payloads], dtype=np.int32)
    # every column with values at both ends of int32: a byte out of place shows
    root_hash = r.integers(-1, I32_MAX, size=lanes, endpoint=True).astype(np.int32)
    root_hash[0] = -1
    at = np.sort(r.choice(16, size=lanes, replace=False)).astype(np.int32)
    prefix = r.integers(0, I32_MAX, size=lanes).astype(np.int32)
    base = int(r.integers(2**24, I32_MAX))
    active = np.sort(r.choice(1024, size=16, replace=False)).astype(np.int32)
    width = _bucket(int(lens.max()) + 16, 64)

    table = pack_lane_table(lens, root_hash, at, prefix, base)
    offsets = np.concatenate([[0], np.cumsum(lens[:-1])]).astype(np.int32)
    assert table.shape == (dk.LANE_FIELDS, lanes) and table[dk.LANE_OFFSET].tolist() == offsets.tolist()
    wire = np.zeros(_bucket(int(lens.sum()), 256), dtype=np.uint8)
    wire[: lens.sum()] = np.frombuffer(b"".join(payloads), dtype=np.uint8)
    want = np.asarray(dk.gather_raw_lanes(jnp.asarray(wire), jnp.asarray(offsets), jnp.asarray(lens), width))

    for step_active in (active, None):  # a tick's compact step, the dense step
        manifest = pack_manifest(payloads, table, step_active)
        words = dk.LANE_FIELDS * lanes + (0 if step_active is None else 16)
        assert manifest.dtype == np.uint8 and manifest.shape == (wire.size + 4 * words,)
        assert manifest[: wire.size].tobytes() == wire.tobytes()
        matrix, dev_table, dev_active = ingest_mod._gather_manifest_jit(
            jnp.asarray(manifest), lanes=lanes, width=width, step_width=0 if step_active is None else 16
        )
        assert matrix.dtype == jnp.uint8 and np.asarray(matrix).tobytes() == want.tobytes()
        assert dev_table.dtype == jnp.int32 and dev_table.shape == (dk.LANE_FIELDS, lanes)
        got = np.asarray(dev_table)
        for row, column in (
            (dk.LANE_OFFSET, offsets), (dk.LANE_LEN, lens), (dk.LANE_ROOT_HASH, root_hash), (dk.LANE_AT, at),
            (dk.LANE_PREFIX, prefix), (dk.LANE_BASE, np.full(lanes, base, np.int32)),
        ):
            assert got[row].tolist() == column.tolist(), row
        if step_active is None:
            assert dev_active is None
        else:
            assert dev_active.dtype == jnp.int32 and np.asarray(dev_active).tolist() == active.tolist()


# --- (b) what a step sends, counted ------------------------------------------------

N_DOCS, CAPACITY = 64, 128  # a tick of at most 16 rooms is the compact step
WRITERS = (1, 2, 50, 51, 60)  # known before the first frame, but `LATE_WRITER`
LATE_WRITER = 77
# the lookup tables' leaves, all four built by the step that first needs them:
# three pairs (client, key, client-hash) and the rank table
TABLE_LEAVES = 7


def _served_steps():
    """(what the step is, its payloads by slot, uploads, output buffers)."""
    rooms = [_Room() for _ in range(4)]
    first = rooms[0].edit(1, _type("abcdefgh "))
    early = rooms[0].edit(2, _type("two "))
    late = rooms[0].edit(2, _type("more ", 2))
    slots = lambda **at: [at.get(f"r{d}") for d in range(N_DOCS)]
    return rooms, [
        # the manifest, and the lookup tables and the bucket's empty batch built
        ("first", slots(r0=first, r1=rooms[1].edit(50, _type("abcdefghijklmnop "))), 1 + TABLE_LEAVES + 2, 37),
        # a kept batch and no payload change to the tables: the manifest alone
        ("merge on a kept batch", slots(r1=rooms[1].edit(50, _type("x1")), r2=rooms[2].edit(51, _type("y1"))), 1, 37),
        ("one lane", slots(r2=rooms[2].edit(51, _cut(0, 1))), 1, 37),
        # a stashed update: the host lane's pair, and `active` by itself
        ("host lane only", slots(r0=late), 2 + 1, 29),
        # both lanes: the host lane's pair and the manifest, `active` inside it
        ("both lanes", slots(r0=early, r1=rooms[1].edit(50, _type("x2"))), 2 + 1, 37),
        # a writer first seen: the raw client table's pair and the rank table again
        ("a first-seen writer", slots(r3=rooms[3].edit(LATE_WRITER, _type("who "))), 1 + 3, 37),
        ("nothing queued", slots(), 1, 29),  # `active` beside a kept batch
        # every lane flagged: the manifest, then the recovery's pair and `active`
        ("flagged", slots(r1=rooms[1].edit(50, _four_and_four)), 1 + 2 + 1, 37 + 29),
    ]


@pytest.fixture(scope="module")
def served(native_lib):
    rooms, steps = _served_steps()
    ing = BatchIngestor(n_docs=N_DOCS, capacity=CAPACITY)
    for c in WRITERS:
        ing.enc.interner.intern(c)
    monkeypatch = pytest.MonkeyPatch()
    real_decode = dk.decode_updates_v1
    flag = [False]

    def decode(*a, **kw):
        stream, flags = real_decode(*a, **kw)
        return _flag_lanes(stream, flags) if flag[0] else (stream, flags)

    monkeypatch.setattr(dk, "decode_updates_v1", decode)
    uploads, outputs = metrics.counter("ingest.step_uploads"), metrics.counter("ingest.enqueue_outputs")
    forms = _apply_update_batch_in_place_jit._cache_size()
    got = {}
    phases.reset()
    phases.enable()
    try:
        for what, payloads, _, _ in steps:
            flag[0] = what == "flagged"
            before = uploads.value, outputs.value
            ing.apply_bytes(payloads)
            got[what] = uploads.value - before[0], outputs.value - before[1]
        recorded = phases.snapshot()
    finally:
        phases.disable()
        monkeypatch.undo()
    want = [(what, uploads, outputs) for what, _, uploads, outputs in steps]
    return ing, rooms, got, want, recorded, _apply_update_batch_in_place_jit._cache_size() - forms


@pytest.mark.parametrize("step", range(8))
def test_a_step_counts_the_host_arrays_it_sent(served, step):
    _, _, got, want, _, _ = served
    assert len(want) == 8 and list(got) == [what for what, _, _ in want]
    what, uploads, outputs = want[step]
    assert got[what] == (uploads, outputs), what


def test_the_recorder_keeps_the_same_count_and_the_merge_sends_one_leaf(served):
    ing, rooms, got, _, recorded, forms = served
    assert recorded["ingest.step_uploads"]["value"] == sum(u for u, _ in got.values())
    merges = recorded["ingest.merge"]["calls"]
    assert merges == recorded["ingest.merge.h2d"]["calls"] == recorded["ingest.merge.scatter"]["calls"] == 6
    # every stage a reader names is still there, the retained chunk's among them
    assert {"ingest.merge.retain", "ingest.merge.pack", "ingest.merge.gather", "ingest.merge.tables", "decode.v1"} <= set(recorded)
    # compact steps all, whichever lane the rooms took and the recovery's too:
    # `active` comes as a device array from every one, one form of the program
    assert forms == 1 and ing.fast_recoveries == 1
    assert not np.asarray(ing.state.error).any()
    for d, room in enumerate(rooms):
        assert get_string(ing.state, d, ing.payloads) == room.oracle().get_text("text").get_string(), d


def test_no_host_array_rides_up_with_a_jitted_call(native_lib, monkeypatch):
    """Every operand of the step's four programs is on the device before
    the call: the manifest's gather takes the one upload, the decoder, the
    merge and the integrate call what the gather handed back."""
    seen = []

    def watch(name, real):
        def call(*a, **kw):
            seen.append((name, [type(x) for x in jax.tree.leaves((a, {k: v for k, v in kw.items() if k not in STATICS}))]))
            return real(*a, **kw)
        return call

    STATICS = {"lanes", "width", "step_width", "max_rows", "max_dels", "n_steps", "max_sections", "packed"}
    monkeypatch.setattr(ingest_mod, "_gather_manifest_jit", watch("gather", ingest_mod._gather_manifest_jit))
    monkeypatch.setattr(dk, "_decode_updates_v1_jit", watch("decode", dk._decode_updates_v1_jit))
    monkeypatch.setattr(ingest_mod, "_merge_stream_jit", watch("merge", ingest_mod._merge_stream_jit))
    import ytpu.models.batch_doc as bd

    monkeypatch.setattr(bd, "_apply_update_batch_in_place_jit", watch("integrate", bd._apply_update_batch_in_place_jit))
    rooms = [_Room() for _ in range(3)]
    ing = BatchIngestor(n_docs=N_DOCS, capacity=CAPACITY)
    ing.apply_bytes([rooms[d].edit(d + 1, _type("abc ")) if d < 3 else None for d in range(N_DOCS)])
    ing.apply_bytes([rooms[0].edit(9, _type("host ", 1))] + [None] * (N_DOCS - 1))
    assert [name for name, _ in seen] == ["gather", "decode", "merge", "integrate"] * 2
    for name, leaves in seen:
        scan_plan = [t for t in leaves if t in (int, bool, str)]  # the integrate call's static plan
        assert all(issubclass(t, jax.Array) for t in leaves if t not in scan_plan), (name, leaves)


# --- (c) the programs a tick's lane counts build ------------------------------------


def test_a_sweep_over_lane_counts_builds_the_forms_it_built_before(native_lib, monkeypatch):
    """Lane counts 1..16 at the (4, 4) bucket, then again with longer
    updates (another wire bucket, the same lane width): one form of the
    integrate program (a tick's step is 16 wide whatever rode it), one of
    the decoder and one of the merge a lane count, as the parent built; the
    second sweep adds forms to the first program alone, the one the wire
    bucket keys."""
    from ytpu.utils import progbudget

    monkeypatch.setattr(progbudget, "_MAX", 10**9)  # no eviction under our feet
    programs = {
        "integrate": _apply_update_batch_in_place_jit,
        "decode": dk._decode_updates_v1_jit,
        "merge": ingest_mod._merge_stream_jit,
        "gather": ingest_mod._gather_manifest_jit,
    }
    rooms = [_Room() for _ in range(16)]
    ing = BatchIngestor(n_docs=N_DOCS, capacity=CAPACITY // 2)  # a state shape of this test's own: new forms
    for c in range(100, 116):
        ing.enc.interner.intern(c)
    for name, jit in programs.items():  # earlier tests of this process may hold the same keys
        if name != "integrate":
            jit.clear_cache()
    sizes = lambda: {n: jit._cache_size() for n, jit in programs.items()}
    seen = []

    def sweep(word):
        for lanes in range(1, 17):
            ing.apply_bytes(
                [rooms[d].edit(100 + d, _type(word)) if d < lanes else None for d in range(N_DOCS)]
            )
            seen.append(ing._last_fast_flags.shape[0])

    before = sizes()
    sweep("ab ")
    first = sizes()
    assert {n: first[n] - before[n] for n in programs} == {"integrate": 1, "decode": 16, "merge": 16, "gather": 16}
    sweep("thirty characters typed at once")  # longer updates under the same 64-byte lane
    second = sizes()
    assert seen == list(range(1, 17)) * 2
    longer = len(rooms[0].sent[-1])
    assert longer + 16 <= 64 and _bucket(16 * longer, 256) == 1024
    grown = {n: second[n] - first[n] for n in programs}
    # the first sweep's arenas all fit 256 bytes; the second's pass it from some lane count on
    assert grown == {"integrate": 0, "decode": 0, "merge": 0, "gather": sum(_bucket(s * longer, 256) > 256 for s in range(1, 17))}
    assert 0 < grown["gather"] < 16
    assert not np.asarray(ing.state.error).any()
    for d, room in enumerate(rooms):
        assert get_string(ing.state, d, ing.payloads) == room.oracle().get_text("text").get_string(), d


# --- (d) a state laid over the mesh ---------------------------------------------------


def test_a_doc_sharded_state_gets_the_manifest_whole_on_every_device(native_lib, monkeypatch):
    """`shard_docs=True` on the suite's CPU mesh: the merge's upload is one
    leaf, whole on every device, every array its first program hands back
    lies whole on every device too, and the served rooms end byte-equal to
    the one-device run's."""
    devices = jax.devices()
    assert len(devices) == 8  # tests/conftest.py
    rooms = {d: _Room() for d in range(N_DOCS)}
    steps = [
        {d: rooms[d].edit(1 + d, _type(f"room{d} ")) for d in range(0, N_DOCS, 9)},  # a room on every device
        {0: rooms[0].edit(1, _type("again ", 2)), 9: rooms[9].edit(10, _cut(0, 2)), 63: rooms[63].edit(64, _type("last "))},
    ]
    skipped, late = rooms[0].edit(200, _type("never sent ")), rooms[0].edit(200, _type("late ", 1))
    rooms[0].sent.remove(skipped)
    steps.append({0: late})  # it waits in the stash: the host lane's step, `active` by itself
    sent = []  # (host tree, device tree) of every upload of the sharded run

    def run(shard_docs):
        ing = BatchIngestor(n_docs=N_DOCS, capacity=CAPACITY, shard_docs=shard_docs)
        if shard_docs:
            real = ing._upload

            def upload(host, by_doc=False):
                dev = real(host, by_doc)
                sent.append((host, dev, by_doc))
                return dev

            monkeypatch.setattr(ing, "_upload", upload)
        for step in steps:
            with jax.transfer_guard_device_to_device("disallow"):
                ing.apply_bytes([step.get(d) for d in range(N_DOCS)])
        jax.block_until_ready(ing.state)
        return ing

    sharded = run(True)
    assert metrics.gauge("ingest.state_shards").value == 8
    manifests = [(h, dev) for h, dev, by_doc in sent if isinstance(h, np.ndarray) and h.dtype == np.uint8]
    assert len(manifests) == 2  # the two steps with a fast lane: one leaf each
    for host, dev in manifests:
        assert dev.sharding.is_fully_replicated and dev.sharding.device_set == set(devices)
        assert all(np.asarray(s.data).tobytes() == host.tobytes() for s in dev.addressable_shards)
    assert sharded.pending_update(0) is not None
    plain = run(False)
    assert (sharded.fast_docs, sharded.slow_docs) == (plain.fast_docs, plain.slow_docs) == (11, 1)
    for a, b in zip(jax.tree.leaves(sharded.state), jax.tree.leaves(plain.state)):
        assert a.dtype == b.dtype and np.asarray(a).tobytes() == np.asarray(b).tobytes()
    assert not np.asarray(sharded.state.error).any()
    rooms[0].sent.remove(late)
    for d in (0, 9, 63):
        assert get_string(sharded.state, d, sharded.payloads) == rooms[d].oracle().get_text("text").get_string(), d


# --- the counter's reader --------------------------------------------------------------


def test_the_benchmark_reads_the_uploads_a_step():
    """`uploads_per_step.flood`: the last entry of `per_layer`, naming every
    cell; its reader divides the window's count by the window's steps, from
    the counter deltas or the phase recorder's copy, and has nothing to say
    of a program without the counter (the parent)."""
    from benchmark.run import applies, load_reader
    from benchmark.window import Window

    name = "uploads_per_step.flood"
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = [w["name"] for w in bench["workloads"]]
    entry = next(m for m in bench["per_layer"] if m["name"] == name)  # later PRs append after it
    assert entry == {
        "name": name, "unit": "uploads/step", "better": "lower", "source": "program_counter",
        "layer": "ingest merge", "moves": "updates_per_s", "workloads": cells,
    }
    assert all(applies(entry, c, {"updates_per_s", "setup_s"}) for c in cells)
    read = load_reader("layers", name).read
    window = lambda **kw: Window(rec=None, t_open=0.0, t_close=30.0, setup_s=1.0,
                                 dispatch_spans=[(float(i), i + 0.5, 1) for i in range(10)], **kw)
    assert read(window(counters={"ingest.step_uploads": 10})) == 1.0
    assert read(window(phases={"ingest.step_uploads": {"value": 25.0}})) == 2.5
    assert read(window()) is None and read(window(phases={"ingest.merge": {"calls": 10}})) is None
