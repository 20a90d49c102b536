"""One columnar decode a payload (PR 47): the server decodes an update into
its native columns where it arrives (`DeviceSyncServer._note_roots`, inside
`sync.receive.roots`), queues the columns beside the bytes, and
`flush_device` hands them to `BatchIngestor.apply_bytes`, whose prescan walks
what it was handed and decodes only a payload that came without. The columns
are a pure function of the bytes; everything the prescan decides from them
it decides when the step plans, so a step handed columns and the same step
without them leave the same state, bit for bit."""

import json
import os

import jax
import numpy as np
import pytest

import ytpu.native
from ytpu.core import Doc
from ytpu.encoding.lib0 import EncodingError
from ytpu.models.batch_doc import get_string
from ytpu.models.ingest import BatchIngestor
from ytpu.native import decode_update_columns
from ytpu.sync.device_server import DeviceSyncServer
from ytpu.sync.protocol import Message, SyncMessage
from ytpu.utils import metrics
from ytpu.utils.phases import phases

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
needs_native = pytest.mark.usefixtures("native_lib")
N_DOCS, CAPACITY = 4, 64
BIG = 3_000_000_011  # a uint32 client id past int32, as Yjs draws them
PRESCAN = ("ingest.prescan_payloads", "ingest.prescan_carried")
LANES = ("ingest.fast_docs", "ingest.slow_docs", "ingest.stash_updates", "ingest.stash_released",
         "ingest.clients_first_seen_big", "ingest.slow.complex_any", "ingest.slow.pending", "ingest.slow.dependency")


def _counts(names) -> dict:
    return {n: metrics.counter(n).value for n in names}


def _counted(before: dict) -> dict:
    return {n: metrics.counter(n).value - v for n, v in before.items()}


class _Client:
    """A client whose every transaction is one update, as it is sent."""

    def __init__(self, client_id: int):
        self.doc, self._sent = Doc(client_id=client_id), []
        self.doc.observe_update_v1(lambda payload, *_: self._sent.append(payload))

    def send(self, edit) -> bytes:
        with self.doc.transact() as txn:
            edit(self.doc, txn)
        (update,) = self._sent
        del self._sent[:]
        return update

    def type(self, chunk: str, at=None, root: str = "text") -> bytes:
        def edit(doc, txn):
            text = doc.get_text(root)
            text.insert(txn, len(text.get_string()) if at is None else at, chunk)

        return self.send(edit)


# --- the traffic: steps of {slot: payload}, one update a room a step -------------------


def _text_edits():
    a, b, c = _Client(7), _Client(8), _Client(9)
    return [
        {0: a.type("hello"), 1: b.type("wor"), 3: c.type("x")},
        {0: a.type(" there", at=5), 1: b.type("ld")},
        {0: a.type("--", at=2), 3: c.type("yz", at=0)},
    ]


def _deletes():
    a, b = _Client(7), _Client(8)
    cut = lambda at, n: (lambda doc, txn: doc.get_text("text").remove_range(txn, at, n))
    return [
        {0: a.type("abcdefgh"), 2: b.type("0123456789")},
        {0: a.send(cut(2, 3)), 2: b.send(cut(0, 1))},  # a delete range and nothing else
        {0: a.type("Z", at=1), 2: b.send(cut(4, 4))},
    ]


def _nested_records():
    """The record store's shape: an array of `{key, val}` whose `val` holds
    an object in an object (`ingest.slow.complex_any`: the host lane), a
    `set` being the old entry's removal and a push in one transaction."""
    a, b = _Client(7), _Client(8)
    record = lambda k, x: {"key": k, "val": {"id": k, "props": {"x": x, "tags": ["a", "b"]}}}
    push = lambda k, x: (lambda doc, txn: doc.get_array("tl_room").push_back(txn, record(k, x)))

    def reset(k, x):
        def edit(doc, txn):
            store = doc.get_array("tl_room")
            store.remove(txn, 0)
            store.push_back(txn, record(k, x))

        return edit

    return [
        {0: a.send(push("shape:1", 1)), 1: b.send(push("shape:9", 9))},
        {0: a.send(push("shape:2", 2)), 1: b.type("beside", root="notes")},
        {0: a.send(reset("shape:1", 3))},
    ]


def _early_update():
    """The co-edit shape: a writer's second update overtakes its first, waits
    in the room's stash (`dependency`), and is released by the step that
    brings the first (`pending`); the room is back on the fast lane after."""
    a, b = _Client(7), _Client(8)
    first, second, third = a.type("one "), a.type("two "), a.type("three")
    return [
        {0: second, 1: b.type("calm")},
        {0: first, 1: b.type("er")},
        {0: third},
    ]


def _walk_in():
    """A uint32 writer past int32 the server was told nothing of, and a
    small one appending where it does."""
    big, small = _Client(BIG), _Client(41)
    seed = big.type("big")
    small.doc.apply_update_v1(seed)
    del small._sent[:]
    return [
        {2: seed},
        {2: small.type(" small")},
        {2: big.type(" again")},
    ]


def _two_roots():
    a = _Client(7)

    def both(doc, txn):
        doc.get_text("text").insert(txn, 0, ">")  # beside a neighbour: its parent is not named
        doc.get_text("margin").insert(txn, 0, "#")

    return [
        {0: a.type("body")},
        {0: a.type("aside", root="notes")},
        {0: a.send(both)},
    ]


TRAFFIC = {
    "text_edit": _text_edits, "delete": _deletes, "nested_any": _nested_records,
    "stash_and_release": _early_update, "walk_in_uint32": _walk_in, "two_roots": _two_roots,
}
# what each shape must have exercised, or it is not the shape its name says
EXERCISED = {
    "text_edit": {"ingest.fast_docs": 7, "ingest.slow_docs": 0},
    "delete": {"ingest.fast_docs": 6, "ingest.slow_docs": 0},
    "nested_any": {"ingest.slow.complex_any": 4, "ingest.fast_docs": 1},
    "stash_and_release": {"ingest.slow.dependency": 1, "ingest.slow.pending": 1, "ingest.stash_updates": 1,
                          "ingest.stash_released": 1, "ingest.fast_docs": 3},
    "walk_in_uint32": {"ingest.clients_first_seen_big": 1, "ingest.fast_docs": 3},
    "two_roots": {"ingest.fast_docs": 3, "ingest.slow_docs": 0},
}


def _slots(step: dict) -> list:
    return [step.get(d) for d in range(N_DOCS)]


def _apply(steps, carry) -> tuple:
    """The steps through a fresh ingestor; `carry(step, slot)` says whether
    the slot's columns are handed in. Returns it and what every step left."""
    ing = BatchIngestor(N_DOCS, CAPACITY)
    left = []
    for n, step in enumerate(steps):
        payloads = _slots(step)
        columns = [decode_update_columns(p) if p is not None and carry(n, d) else None for d, p in enumerate(payloads)]
        if any(c is not None for c in columns):
            ing.apply_bytes(payloads, columns)
        else:
            ing.apply_bytes(payloads)  # as every direct caller calls it
        left.append(_snapshot(ing))
    return ing, left


def _snapshot(ing) -> dict:
    """Everything a step leaves behind: the state's planes as bytes, the
    flags read back, the mirror state vectors, the interner, the roots, the
    stash and the host's row bounds."""
    flags = ing._last_fast_flags
    return dict(
        state=[(str(a.dtype), a.shape, np.asarray(a).tobytes()) for a in jax.tree.leaves(ing.state)],
        flags=None if flags is None else np.asarray(flags).tolist(),
        svs=[dict(sv.clocks) for sv in ing.svs],
        interner=list(ing.enc.interner.from_idx),
        roots=(dict(ing.primary_roots), [sorted(r) for r in ing._anchored_roots]),
        stash=[(None if ing.pending_update(d) is None else ing.pending_update(d).encode_v1(),
                None if ing.pending_ds(d) is None else sorted(ing.pending_ds(d).clients.items()))
               for d in range(ing.n_docs)],
        rows=ing._rows_bound.tolist(),
        lanes=(ing.fast_docs, ing.slow_docs, ing.fast_recoveries),
    )


@pytest.fixture(scope="module")
def plain():
    """Every shape through `apply_bytes(payloads)`, once: what the parent
    leaves, step by step, and the counters each shape moved."""
    out = {}
    for name, make in TRAFFIC.items():
        steps = make()
        before = _counts(LANES + PRESCAN)
        _, left = _apply(steps, lambda n, d: False)
        out[name] = (steps, left, _counted(before))
    return out


# --- (b) the same step with the columns handed in ---------------------------------------


@needs_native
@pytest.mark.parametrize("shape", list(TRAFFIC))
def test_a_step_handed_its_columns_leaves_what_the_plain_step_leaves(plain, shape):
    steps, want, moved = plain[shape]
    n = sum(len(s) for s in steps)
    assert {k: moved[k] for k in EXERCISED[shape]} == EXERCISED[shape]
    assert (moved["ingest.prescan_payloads"], moved["ingest.prescan_carried"]) == (n, 0)
    before = _counts(LANES + PRESCAN)
    _, got = _apply(steps, lambda n, d: True)
    counted = _counted(before)
    assert got == want
    assert (counted.pop("ingest.prescan_payloads"), counted.pop("ingest.prescan_carried")) == (n, n)
    assert counted == {k: v for k, v in moved.items() if k not in PRESCAN}  # the same lanes, reasons, stash


# --- (c) a direct caller mixed with the queue -------------------------------------------


@needs_native
@pytest.mark.parametrize("shape", ["text_edit", "nested_any", "stash_and_release"])
def test_a_step_with_columns_in_some_slots_plans_the_same_batch(plain, shape):
    steps, want, _ = plain[shape]
    carry = lambda n, d: (n + d) % 2 == 0
    handed = sum(1 for n, s in enumerate(steps) for d in s if carry(n, d))
    assert 0 < handed < sum(len(s) for s in steps)
    before = _counts(PRESCAN)
    _, got = _apply(steps, carry)
    assert got == want
    assert _counted(before) == {"ingest.prescan_payloads": sum(len(s) for s in steps), "ingest.prescan_carried": handed}


def test_the_columns_are_one_a_slot_or_none():
    ing = BatchIngestor(N_DOCS, CAPACITY)
    with pytest.raises(ValueError, match="column slots"):
        ing.apply_bytes([None] * N_DOCS, [None] * (N_DOCS - 1))
    before = _counts(PRESCAN)
    ing.apply_bytes([None] * N_DOCS, [None] * N_DOCS)  # an idle step plans no payload
    assert _counted(before) == dict.fromkeys(PRESCAN, 0)


# --- (a) a served run decodes a payload once ---------------------------------------------


class _Decodes:
    """Every call of `ytpu.native.decode_update_columns`, which the server
    and the ingestor both look up when they call it."""

    def __init__(self, monkeypatch):
        self.payloads, self.returned = [], []
        real = ytpu.native.decode_update_columns

        def spy(payload):
            self.payloads.append(payload)
            self.returned.append(real(payload))
            return self.returned[-1]

        monkeypatch.setattr(ytpu.native, "decode_update_columns", spy)


def _serve(server, steps, each_step=None) -> dict:
    """The steps as the benchmark's loop serves them: a tick's frames
    through `receive_frames`, then `flush_device(max_steps=1)` until the
    queues are empty. Returns the rooms' sessions."""
    # rooms take their slots in order; a session connected later would
    # flush what is queued (`connect_frames`)
    sessions = {d: server.connect_frames(f"room-{d}")[0] for d in range(N_DOCS)}
    for step in steps:
        for d in sorted(step):
            frame = Message.sync(SyncMessage.update(step[d])).encode_v1()
            assert server.receive_frames(sessions[d], frame) == []
        while server.pending_device_updates():
            assert server.flush_device(max_steps=1) == 1
            if each_step is not None:
                each_step()
    return sessions


@needs_native
@pytest.mark.parametrize("shape", ["text_edit", "nested_any", "stash_and_release", "two_roots"])
def test_a_served_run_decodes_every_payload_once(plain, shape, monkeypatch):
    """`receive_frames` x N over several rooms, then `flush_device`: one
    call of the native decoder a payload (two at the parent), every planned
    payload's columns carried, the recorder's copies of the two counts the
    same, the queue handing `apply_bytes` the very columns it was given,
    and the server's state what `apply_bytes(payloads)` leaves."""
    steps, want, moved = plain[shape]
    sent = [step[d] for step in steps for d in sorted(step)]
    server = DeviceSyncServer(n_docs=N_DOCS, capacity=CAPACITY, device_authoritative=True)
    ing = server.ingestor
    handed = []
    real = ing.apply_bytes
    monkeypatch.setattr(ing, "apply_bytes", lambda payloads, columns=None: (handed.append((payloads, columns)), real(payloads, columns))[1])
    decodes = _Decodes(monkeypatch)
    before = _counts(LANES + PRESCAN + ("sync.multi_root_tenants",))
    multi_root = before.pop("sync.multi_root_tenants")
    left = []
    phases.reset()
    phases.enable()
    try:
        _serve(server, steps, lambda: left.append(_snapshot(ing)))
        recorded = phases.snapshot()
    finally:
        phases.disable()
    assert decodes.payloads == sent  # once each, in arrival order, before its step
    counted = _counted(before)
    assert (counted.pop("ingest.prescan_payloads"), counted.pop("ingest.prescan_carried")) == (len(sent), len(sent))
    assert counted == {k: v for k, v in moved.items() if k not in PRESCAN}
    assert [recorded[n]["value"] for n in PRESCAN] == [len(sent), len(sent)]
    assert recorded["sync.receive.roots"]["calls"] == len(sent)
    assert left == want and len(handed) == len(steps)
    by_bytes = dict(zip(sent, decodes.returned))
    for payloads, columns in handed:
        assert len(columns) == len(payloads) == N_DOCS
        for p, c in zip(payloads, columns):
            assert (p is None and c is None) or c is by_bytes[p]
    # the roots noted as the parent notes them: the first name the room's, a
    # count an update that names another (`_two_roots`: its second and third)
    roots = {"nested_any": {"room-0": "tl_room", "room-1": "tl_room"}, "two_roots": {"room-0": "text"}}
    assert server._root_names == roots.get(shape, {f"room-{d}": "text" for d in {d for s in steps for d in s}})
    extra = {"nested_any": 1, "two_roots": 2}.get(shape, 0)
    assert metrics.counter("sync.multi_root_tenants").value - multi_root == extra
    assert not any(server._queues) and not any(server._queue_columns) and not any(server._queue_traces)


# --- (d) what cannot be decoded ends as it ends at the parent ----------------------------


@needs_native
def test_an_update_the_native_decoder_cannot_read_ends_as_at_the_parent():
    """An update cut short: its columns carry `error`, the host decoder
    names no root, the update is queued (admitted, broadcast) and its step
    raises out of `flush_device` from the host lane's decoder
    (`ingest.slow.pending`), nothing popped: today's outcome, pinned, for a
    step handed the columns as for one that decodes them itself."""
    good = _Client(7).type("abc")
    cut = good[:-1]
    assert decode_update_columns(cut).error
    server = DeviceSyncServer(n_docs=N_DOCS, capacity=CAPACITY, device_authoritative=True)
    session = server.connect_frames("room-0")[0]
    peer = server.connect_frames("room-0")[0]
    before = _counts(PRESCAN + ("ingest.slow.pending", "net.bad_frames"))
    frame = Message.sync(SyncMessage.update(cut)).encode_v1()
    assert server.receive_frames(session, frame) == [] and not session.dead
    assert peer.outbox == [frame] and server._root_names == {}
    (queued,) = server._queue_columns[0]
    assert queued.error and server._queues[0] == [cut]
    for attempt in (1, 2):
        with pytest.raises(EncodingError):
            server.flush_device()
        assert server._queues[0] == [cut] and server._queue_columns[0] == [queued] and len(server._queue_traces[0]) == 1
        assert _counted(before) == {"ingest.prescan_payloads": 0, "ingest.prescan_carried": 0,
                                    "ingest.slow.pending": attempt, "net.bad_frames": 0}
    direct = BatchIngestor(N_DOCS, CAPACITY)
    with pytest.raises(EncodingError):
        direct.apply_bytes([cut] + [None] * (N_DOCS - 1))
    assert _counted(before)["ingest.slow.pending"] == 3


def test_a_server_without_the_native_library_serves_from_the_host_lane(monkeypatch):
    """The library masked: the server decodes nothing and queues no
    columns, the roots come from the host decoder, every payload is planned
    on the host lane with nothing carried, and the rooms read right."""
    monkeypatch.setattr(ytpu.native, "load", lambda: None)
    assert not ytpu.native.available() and decode_update_columns(b"\x00\x00") is None
    steps = _two_roots() + _text_edits()
    n = sum(len(s) for s in steps)
    server = DeviceSyncServer(n_docs=N_DOCS, capacity=CAPACITY, device_authoritative=True)
    before = _counts(PRESCAN + ("ingest.fast_docs", "ingest.slow_docs", "sync.multi_root_tenants"))
    seen = []
    real = server._enqueue
    monkeypatch.setattr(server, "_enqueue", lambda slot, payload, columns=None: (seen.append(columns), real(slot, payload, columns))[1])
    _serve(server, steps)
    assert seen == [None] * n
    assert _counted(before) == {"ingest.prescan_payloads": n, "ingest.prescan_carried": 0, "ingest.fast_docs": 0,
                                "ingest.slow_docs": n, "sync.multi_root_tenants": 2}
    assert server._root_names["room-0"] == "text"
    ing = server.ingestor
    assert not np.asarray(ing.state.error).any()
    assert get_string(ing.state, 1, ing.payloads) == "world" and get_string(ing.state, 3, ing.payloads) == "yzx"


# --- (e) peek, apply, THEN pop -----------------------------------------------------------


@needs_native
def test_the_queue_its_traces_and_its_columns_stay_in_lockstep_when_a_step_raises(monkeypatch):
    server = DeviceSyncServer(n_docs=N_DOCS, capacity=CAPACITY, device_authoritative=True)
    ing = server.ingestor
    a, b = _Client(7), _Client(8)
    steps = [{0: a.type("ab"), 1: b.type("x")}]
    sessions = {d: server.connect_frames(f"room-{d}")[0] for d in range(2)}
    for word in ("cd", "ef"):  # room 0 three deep, room 1 one
        steps.append({0: a.type(word)})
    for step in steps:
        for d, u in step.items():
            server.receive_frames(sessions[d], Message.sync(SyncMessage.update(u)).encode_v1())
    depth = lambda: [(len(q), len(c), len(t)) for q, c, t in zip(server._queues, server._queue_columns, server._queue_traces)]
    assert depth() == [(3, 3, 3), (1, 1, 1), (0, 0, 0), (0, 0, 0)]
    assert all(c.payload is p for q, cs in zip(server._queues, server._queue_columns) for p, c in zip(q, cs))
    assert server._tenant_queue_depth("room-0") == 3 and server.pending_device_updates() == 4
    real, calls = ing.apply_bytes, []

    def failing(payloads, columns=None):
        calls.append((payloads, columns))
        if len(calls) == 1:
            raise RuntimeError("the step failed")
        return real(payloads, columns)

    monkeypatch.setattr(ing, "apply_bytes", failing)
    with pytest.raises(RuntimeError, match="the step failed"):
        server.flush_device()
    assert depth() == [(3, 3, 3), (1, 1, 1), (0, 0, 0), (0, 0, 0)]  # nothing popped
    assert server.flush_device(max_steps=1) == 1
    assert depth() == [(2, 2, 2), (0, 0, 0), (0, 0, 0), (0, 0, 0)]
    # the failed step and its retry were handed the same heads of the queues
    (p1, c1), (p2, c2) = calls
    assert p1 == p2 and all(x is y for x, y in zip(c1, c2)) and c1[0].payload is p1[0] and c1[2] is None
    # an update queued by another path (a rebalance's re-ingest, a mirror) carries none
    server._enqueue(2, steps[0][1])
    assert depth()[2] == (1, 1, 1) and server._queue_columns[2] == [None]
    assert server.flush_device() == 2 and depth() == [(0, 0, 0)] * N_DOCS
    assert calls[-2][1][2] is None and calls[-2][1][0] is not None
    assert get_string(ing.state, 0, ing.payloads) == "abcdef" and get_string(ing.state, 2, ing.payloads) == "x"


# --- the counters' reader ----------------------------------------------------------------


def test_the_benchmark_reads_the_share_of_payloads_that_carried_their_columns():
    """`decode_carried_pct.flood`: the last entry of `per_layer`, naming
    every cell; its reader divides the window's two counts, from the
    counter deltas or the phase recorder's copies, and has nothing to say
    of a program without the counters (the parent)."""
    from benchmark.run import applies, load_reader
    from benchmark.window import Window

    name = "decode_carried_pct.flood"
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = [w["name"] for w in bench["workloads"]]
    entry = next(m for m in bench["per_layer"] if m["name"] == name)  # later PRs append after it
    assert entry == {
        "name": name, "unit": "%", "better": "higher", "source": "program_counter",
        "layer": "ingest planning", "moves": "updates_per_s", "workloads": cells,
    }
    assert all(applies(entry, c, {"updates_per_s", "setup_s"}) for c in cells)
    read = load_reader("layers", name).read
    window = lambda **kw: Window(rec=None, t_open=0.0, t_close=30.0, setup_s=1.0,
                                 dispatch_spans=[(float(i), i + 0.5, 1) for i in range(10)], **kw)
    assert read(window(counters={"ingest.prescan_payloads": 80, "ingest.prescan_carried": 80})) == 100.0
    assert read(window(phases={"ingest.prescan_payloads": {"value": 8.0}, "ingest.prescan_carried": {"value": 6.0}})) == 75.0
    assert read(window(phases={"ingest.prescan_payloads": {"value": 8.0}})) == 0.0  # every payload decoded twice
    assert read(window()) is None and read(window(phases={"ingest.plan": {"calls": 10}})) is None
