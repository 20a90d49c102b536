"""Writers the server was not told of (ISSUE-35).

A Yjs client draws its id (`random.uint32()`) when its document is made and
the handshake does not announce it: the first a server hears of a writer is
the writer's first update. Nothing here is interned before its first frame.
Each case runs on one device and doc-sharded over the suite's 8 host
devices (`tests/conftest.py`), as `tests/test_sharded_server.py` does:

(a) the served path on a small `author-flood` trace (the benchmark's own
    generator, `benchmark/generators/walkin_mix.py`) equals `ytpu.core.Doc`
    fed the same updates: text, state vector, canonical re-encoding;
(b) the YATA rule stated on its own, not through `Doc`: writers with ids on
    both sides of 2**31 insert concurrently after one character, and the
    room reads their words in ascending client id, as unsigned integers
    (Yjs `Item.integrate`: `o.id.client < this.id.client` moves left);
(c) writers walking in one or several a step compile nothing after the
    first step of a table shape, and a table that outgrows its shape
    compiles the programs that take it once;
(d) a writer keeps its interned index from step to step, through the raw
    table (an id up to int32) and through the hash table (an id past it),
    across rebuilds and a doubling.
"""

import json
import os
import random

import jax
import numpy as np
import pytest

from benchmark import grammar as g
from benchmark.generators import walkin_mix
from ytpu.core import Doc
from ytpu.core.state_vector import StateVector
from ytpu.models import batch_doc as bd
from ytpu.models import ingest as ingest_mod
from ytpu.models.batch_doc import ClientInterner, get_string
from ytpu.models.ingest import BatchIngestor
from ytpu.ops import decode_kernel as dk
from ytpu.sync.device_server import DeviceSyncServer
from ytpu.sync.protocol import Message, SyncMessage
from ytpu.utils import metrics
from ytpu.utils.phases import phases

pytestmark = pytest.mark.usefixtures("native_lib")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N_ROOMS, CAPACITY = 16, 512  # 2 rooms a device when doc-sharded
I32_MAX = 2**31 - 1
EITHER = pytest.mark.parametrize("shard_docs", [False, True], ids=["one_device", "doc_sharded"])
WATCHED = ("ingest.fast_recoveries", "encode.demotions", "lane.demotions", "net.bad_frames")
SEEN = ("ingest.clients_first_seen", "ingest.clients_first_seen_big", "ingest.table_grows")


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


# --- (a) a small author-flood trace against ytpu.core.Doc ------------------------

SMALL_PREFILL = {"classes": [{"rooms": 2, "stage_rows": [24, 1]}, {"rooms": None, "stage_rows": [24, 24]}]}


def _author_flood(seed: int):
    with open(os.path.join(ROOT, "benchmark", "traffic", "author-flood.json")) as f:
        mix = dict(json.load(f), sessions=40, edits_per_session=4, tick_max_frames=6, warm_sessions=0)
    prefill = g.Prefill(SMALL_PREFILL, N_ROOMS, seed)
    plan = walkin_mix.plan({"n_docs": N_ROOMS}, mix, prefill, seed, 1.0)
    stages = [[(k, prefill.for_room(k).stages[s]) for k in range(N_ROOMS)] for s in range(prefill.n_stages)]
    tick = plan.tick_max_frames
    ticks = [[(op.room, op.update) for op in plan.ops[i : i + tick]] for i in range(0, len(plan.ops), tick)]
    return plan, stages + ticks


def _canonical(update: bytes):
    fresh = Doc(client_id=2)
    fresh.apply_update_v1(update)
    return fresh.get_text(g.ROOT).get_string(), dict(fresh.state_vector().clocks), fresh.encode_state_as_update_v1()


@EITHER
def test_a_served_author_flood_trace_equals_the_oracle(shard_docs):
    plan, ticks = _author_flood(35_000_001)
    writers = [s.client_id for s in plan.sessions]
    big = [c for c in writers if c > I32_MAX]
    assert len(set(writers)) == 40 and 10 <= len(big) <= 30  # both tables have work
    assert plan.clients == [900_000, 900_001]  # the templates, and no writer
    server = _server(shard_docs)
    sessions = {k: server.connect_frames(g.room_name(k))[0] for k in range(N_ROOMS)}
    watched, seen = _counts(WATCHED), _counts(SEEN)
    _serve(server, sessions, ticks)
    _clean(server)
    ing = server.ingestor
    # every update took the fast lane, a first-seen writer's first too
    assert ing.slow_docs == 0 and ing.fast_docs == sum(len(t) for t in ticks)
    assert _counted(watched) == dict.fromkeys(WATCHED, 0)
    assert _counted(seen) == {
        "ingest.clients_first_seen": 42, "ingest.clients_first_seen_big": len(big), "ingest.table_grows": 0,
    }
    diffs = server.device_encode_diff_many([(g.room_name(k), StateVector()) for k in range(N_ROOMS)])
    tail_writers = 0
    for k, diff in enumerate(diffs):
        want = Doc(client_id=1)
        for frames in ticks:
            for room, u in frames:
                if room == k:
                    want.apply_update_v1(u)
        text, sv = want.get_text(g.ROOT).get_string(), dict(want.state_vector().clocks)
        assert server.device_text(g.room_name(k)) == text, k
        assert dict(server.device_state_vector(g.room_name(k)).clocks) == sv, k
        assert _canonical(diff) == (text, sv, _canonical(want.encode_state_as_update_v1())[2]), k
        tail_writers = max(tail_writers, len(sv) - 1)
    assert tail_writers >= 8  # the hottest room: writers enough for the tie-break to decide


# --- (b) the YATA rule, stated on its own ----------------------------------------

BASE = 900_000
# ids on both sides of 2**31; as int32 bit patterns the large ones are negative
WRITERS = [
    5, I32_MAX, I32_MAX + 1, I32_MAX + 8, 2**32 - 1, 70_000, 3_000_000_000, 1,
    I32_MAX - 1, 4_000_000_000, 123_456_789, 2_222_222_222,
]


def _as_int32(c: int) -> int:
    return c - 2**32 if c > I32_MAX else c


def _concurrent_inserts():
    """The room's first update ("base:" by one writer) and one update a
    writer in `WRITERS`, each an insert after the base's last character with
    nothing to its right: what every client appending to a synced document
    sends before it has seen the others. Encoded by hand (`grammar`)."""
    base = g.encode_update(BASE, [g.Block(0, None, None, "base:")], {})
    words = {c: f"<{c:x}>" for c in WRITERS}
    updates = {c: g.encode_update(c, [g.Block(0, (BASE, 4), None, words[c])], {}) for c in WRITERS}
    return base, words, updates


def _serve_concurrent(shard_docs: bool):
    base, words, updates = _concurrent_inserts()
    server = _server(shard_docs)
    # three rooms, three arrival orders; one update a room a step
    orders = [list(WRITERS), list(reversed(WRITERS)), random.Random(35).sample(WRITERS, len(WRITERS))]
    rooms = [0, 7, 15]
    sessions = {k: server.connect_frames(g.room_name(k))[0] for k in rooms}
    ticks = [[(k, base) for k in rooms]]
    ticks += [[(k, updates[order[i]]) for k, order in zip(rooms, orders)] for i in range(len(WRITERS))]
    _serve(server, sessions, ticks)
    _clean(server)
    return server, rooms, words


@EITHER
def test_concurrent_appends_read_in_ascending_unsigned_client_id(shard_docs):
    server, rooms, words = _serve_concurrent(shard_docs)
    want = "base:" + "".join(words[c] for c in sorted(WRITERS))
    # the rule separates the two orders: a rank by int32 bit patterns reads otherwise
    assert want != "base:" + "".join(words[c] for c in sorted(WRITERS, key=_as_int32))
    for k in rooms:
        assert server.device_text(g.room_name(k)) == want, k
    assert server.ingestor.slow_docs == 0  # the device decoded every id, the large ones through the hash table


def test_a_rank_table_of_int32_bit_patterns_gets_the_order_wrong(monkeypatch):
    """The control of (b): ranks that compare the ids' int32 bit patterns
    put every writer past 2**31 - 1 before the others."""

    def rank_table_host(self, pad_to=None):
        n = len(self.from_idx)
        ranks = np.zeros(pad_to or max(8, n), dtype=np.int32)
        ranks[np.argsort(np.asarray(self.from_idx, dtype=np.uint64).astype(np.uint32).view(np.int32))] = np.arange(
            n, dtype=np.int32
        )
        return ranks

    monkeypatch.setattr(ClientInterner, "rank_table_host", rank_table_host)
    server, rooms, words = _serve_concurrent(False)
    wrong = "base:" + "".join(words[c] for c in sorted(WRITERS, key=_as_int32))
    assert [server.device_text(g.room_name(k)) for k in rooms] == [wrong] * len(rooms)


# --- (c), (d): writers walking in, step by step ----------------------------------

SLOTS = 1024  # entries a table starts with (`BatchIngestor._table_floor` of a server this small)
FILLERS = range(100_000, 100_000 + SLOTS - 8)  # interned from outside, so that a doubling is a few writers away
PROGRAMS = {
    "decode": dk._decode_updates_v1_jit,
    "integrate": bd._apply_update_batch_in_place_jit,
    "gather": ingest_mod._gather_manifest_jit,
    "merge": ingest_mod._merge_stream_jit,
}
SMALL, LARGE = 41, 3_000_000_041  # the two writers followed from step to step
# step -> the writers of rooms 0 and 1, after the step that brings the two
# bases and `FILLERS`. First seen: one a step, several a step, none. The
# rank table (every writer) is full after step 4 and doubles at step 5, the
# raw table (ids up to int32) is full after step 7 and doubles at step 9;
# the hash table (5 ids past int32) never does
STEPS = [
    (SMALL, LARGE),  # 0: the first step of the shape: every program builds
    (SMALL, 42),  # 1: one writer walks in
    (43, 2**32 - 2),  # 2: two, one of them past int32
    (LARGE, SMALL),  # 3: nobody new
    (44, LARGE),  # 4: the last free entry of the rank table
    (SMALL, 3_999_999_999),  # 5: one more, past int32: the rank table doubles, the hash table is rebuilt
    (45, LARGE),  # 6: the doubled shape again
    (46, 2_500_000_000),  # 7: the last free entry of the raw table
    (LARGE, SMALL),  # 8
    (47, SMALL),  # 9: one more id up to int32: the raw table doubles
    (48, 2_600_000_000),  # 10
    (SMALL, LARGE),  # 11
]
GROWS = {5: {"integrate"}, 9: {"decode"}}  # the programs that take the doubled table


class _Room:
    """One room's writers: whoever edits has seen every earlier update."""

    def __init__(self, base: int):
        self.sent, self.docs = [], {}
        self.edit(base, "room ")

    def edit(self, client: int, word: str) -> bytes:
        doc, emitted, seen = self.docs.get(client) or (Doc(client_id=client), [], 0)
        if not emitted and not seen:
            doc.observe_update_v1(lambda p, o, t, out=emitted: out.append(p))
        for u in self.sent[seen:]:
            doc.apply_update_v1(u)
        with doc.transact() as txn:
            doc.get_text("text").insert(txn, len(doc.get_text("text").get_string()), word)
        self.sent.append(emitted[-1])
        self.docs[client] = (doc, emitted, len(self.sent))
        return self.sent[-1]

    def oracle(self) -> Doc:
        doc = Doc(client_id=999_999)
        for u in self.sent:
            doc.apply_update_v1(u)
        return doc


@pytest.fixture(scope="module", params=[False, True], ids=["one_device", "doc_sharded"])
def walked_in(request):
    """`STEPS` served once: after every step the programs' cache sizes, the
    counters' deltas and the two followed writers' interned indices."""
    from ytpu.utils import progbudget

    patch = pytest.MonkeyPatch()
    patch.setattr(progbudget, "_MAX", 10**9)  # no eviction under our feet
    for jit in PROGRAMS.values():
        jit.clear_cache()
    rooms = [_Room(900_000), _Room(900_001)]
    ing = BatchIngestor(n_docs=8, capacity=256, shard_docs=request.param)
    assert ing._table_floor == SLOTS
    for client in FILLERS:
        ing.enc.interner.intern(client)
    pad = [None] * 6
    ing.apply_bytes([room.sent[0] for room in rooms] + pad)  # the bases: a step of another family
    steps = []
    phases.reset()
    phases.enable()
    try:
        for a, b in STEPS:
            before = _counts(SEEN)
            ing.apply_bytes([rooms[0].edit(a, f"{a:x} "), rooms[1].edit(b, f"{b:x} ")] + pad)
            jax.block_until_ready(ing.state)
            flags = ing._last_fast_flags
            steps.append(dict(
                sizes={name: jit._cache_size() for name, jit in PROGRAMS.items()},
                counted=_counted(before),
                index={c: ing.enc.interner.to_idx.get(c) for c in (SMALL, LARGE)},
                flags=int(np.bitwise_or.reduce(flags)) if flags is not None else None,
            ))
        recorded = phases.snapshot()
    finally:
        phases.disable()
        phases.reset()
        patch.undo()
    return ing, rooms, steps, recorded


def test_walking_in_compiles_nothing_within_a_table_shape(walked_in):
    _, _, steps, _ = walked_in
    for i, (prev, now) in enumerate(zip(steps, steps[1:]), start=1):
        grown = {name for name in PROGRAMS if now["sizes"][name] != prev["sizes"][name]}
        assert grown == GROWS.get(i, set()), i
        assert all(now["sizes"][name] == prev["sizes"][name] + 1 for name in grown), i


def test_a_doubling_is_counted_and_spanned(walked_in):
    ing, _, steps, recorded = walked_in
    assert [i for i, s in enumerate(steps) if s["counted"]["ingest.table_grows"]] == sorted(GROWS)
    assert ing._table_width == {"client_rank": 2 * SLOTS, "client_table": 2 * SLOTS}
    assert recorded["ingest.table_grow"]["calls"] == len(GROWS)
    assert recorded["ingest.table_grows"]["value"] == len(GROWS)


def test_every_writer_is_counted_once_when_it_walks_in(walked_in):
    _, _, steps, recorded = walked_in
    seen = set()
    for (a, b), s in zip(STEPS, steps):
        new = {a, b} - seen
        seen |= new
        assert s["counted"]["ingest.clients_first_seen"] == len(new)
        assert s["counted"]["ingest.clients_first_seen_big"] == sum(c > I32_MAX for c in new)
    assert recorded["ingest.clients_first_seen"]["value"] == len(seen)
    assert recorded["ingest.clients_first_seen_big"]["value"] == sum(c > I32_MAX for c in seen)


def test_no_step_flags_a_writer_it_has_just_met(walked_in):
    ing, _, steps, _ = walked_in
    assert [s["flags"] for s in steps] == [0] * len(STEPS)  # FLAG_UNKNOWN_CLIENT among them
    assert ing.slow_docs == 0 and ing.fast_recoveries == 0
    assert not np.asarray(ing.state.error).any()


def test_a_writer_keeps_its_index_on_either_path(walked_in):
    """(d): the device's rows carry interned indices. Read back through the
    interner they give every writer the clock the oracle gives it, so each
    update of `SMALL` (the raw table) and of `LARGE` (the hash table)
    resolved to the index its first one was given, whatever was rebuilt,
    padded or doubled in between."""
    ing, rooms, steps, _ = walked_in
    assert len({s["index"][SMALL] for s in steps}) == len({s["index"][LARGE] for s in steps}) == 1
    blocks = ing.state.blocks
    client, clock, length = (np.asarray(a) for a in (blocks.client, blocks.clock, blocks.length))
    n_blocks = np.asarray(ing.state.n_blocks)
    for d, room in enumerate(rooms):
        have = {}
        for row in range(n_blocks[d]):
            real = ing.enc.interner.from_idx[client[d, row]]
            have[real] = max(have.get(real, 0), int(clock[d, row] + length[d, row]))
        want = room.oracle()
        assert have == dict(want.state_vector().clocks), d
        assert get_string(ing.state, d, ing.payloads) == want.get_text("text").get_string(), d
    writes = {c: sum(c in pair for pair in STEPS) for c in (SMALL, LARGE)}
    assert min(writes.values()) >= 5


# --- the program budget: the CPU's arena, not the chip's ------------------------


def test_the_program_budget_evicts_on_the_cpu_backend_only(monkeypatch):
    """A served tick of up to 16 rooms is 16 lane counts x 2 wire buckets x
    3 programs, over the budget of 64: evicting them on a TPU rebuilt 11
    inside the benchmark's window (PERF.md §6, PR 35). The budget guards
    XLA:CPU's LLVM arena, so it holds there and nowhere else."""
    import jax.numpy as jnp

    from ytpu.utils import progbudget

    fn = jax.jit(lambda x: x + 1)
    for n in range(1, 4):
        fn(jnp.zeros(n))
    monkeypatch.setattr(progbudget, "_REGISTRY", {"fn": fn})
    monkeypatch.setattr(progbudget, "_MAX", 2)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert progbudget.enforce() == 0 and fn._cache_size() == 3
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert progbudget.enforce() == 1 and fn._cache_size() == 0
