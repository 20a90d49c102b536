"""Rooms whose sessions apply the room's broadcasts (ISSUE-45): concurrent
edits that name other sessions' characters, and updates that reach the server
before one they depend on.

Three implementations are held to each other on every trace here: the served
path (`DeviceSyncServer.receive_frames` / `flush_device`, the device engine),
`ytpu.core.Doc` (the host CRDT, the benchmark's oracle) and
`benchmark/yata_plain.py` (the benchmark's plain reference, which imports
nothing from `ytpu`): text and state vector of every room equal in all
three, and the canonical re-encoding of a full-state diff equal to `Doc`'s.

(a) traces: the co-edit generator's own at rehearsal size (twins and early
    arrivals in it), on one device and doc-sharded; an early insert; an early
    delete (the pending delete set); an early update whose room is compacted
    while it waits; two sessions deleting the same characters; nine
    concurrent inserts around one character with mixed origins, in three
    arrival orders;
(b) the stash's counters step by step over a hand-made trace, and 0 over
    `edit-flood`'s rehearsal; the lane a room takes while it holds a stash;
(c) `Update.merge`: a pending update's Skip must not shadow the blocks a
    later arrival has for the same clocks (found by the plain reference);
    inputs with explicit Skip and GC carriers that no Skip shadows re-encode
    to the bytes PR 44's tree gave (the oracle's merge did not move for them).
"""

import json
import os
import random

import jax
import numpy as np
import pytest

from benchmark import grammar as g
from benchmark import yata_plain as yp
from benchmark.generators import coedit_mix, session_mix
from ytpu.core import Doc
from ytpu.core.block import GCRange
from ytpu.core.state_vector import StateVector
from ytpu.core.update import Update, merge_updates_v1
from ytpu.sync.device_server import DeviceSyncServer
from ytpu.sync.protocol import Message, SyncMessage
from ytpu.utils import metrics
from ytpu.utils.phases import phases

pytestmark = pytest.mark.usefixtures("native_lib")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N_ROOMS, CAPACITY = 16, 128  # 2 rooms a device when doc-sharded
TICK = 4  # frames a tick: the lane counts, and so the programs, every trace here shares
WATCHED = ("ingest.fast_recoveries", "encode.demotions", "lane.demotions", "net.bad_frames")
STASH = ("ingest.stash_updates", "ingest.stash_released", "ingest.stash_wait_steps", "ingest.stash_rooms")
LANES = ("ingest.fast_docs", "ingest.slow_docs", "ingest.slow.pending", "ingest.slow.dependency")
B = g.Block


def _counts(names) -> dict:
    return {n: metrics.counter(n).value for n in names}


def _counted(before: dict) -> dict:
    return {n: metrics.counter(n).value - v for n, v in before.items()}


def _server(shard_docs: bool = False, capacity: int = CAPACITY) -> DeviceSyncServer:
    return DeviceSyncServer(n_docs=N_ROOMS, capacity=capacity, device_authoritative=True, shard_docs=shard_docs)


def _serve(server, sessions, ticks, after_step=None) -> None:
    """Every tick's frames handed over, then one `flush_device` step at a
    time until the queues are empty, as the benchmark's loop does."""
    for frames in ticks:
        for k, u in frames:
            if k not in sessions:
                sessions[k] = server.connect_frames(g.room_name(k))[0]
            assert server.receive_frames(sessions[k], Message.sync(SyncMessage.update(u)).encode_v1()) == []
        while server.pending_device_updates():
            assert server.flush_device(max_steps=1) == 1
            jax.block_until_ready(server.ingestor.state)
            if after_step:
                after_step()


def _clean(server) -> None:
    ing = server.ingestor
    assert not np.asarray(ing.state.error).any()
    assert not [d for d in range(ing.n_docs) if ing.pending_update(d) or ing.pending_ds(d)]
    assert not ing._stash_tickets
    assert ing.fast_recoveries == 0 and not server._host_tenants
    assert server._diff_pipeline.stats.fallback_docs == 0


def _canonical(update: bytes):
    fresh = Doc(client_id=2)
    fresh.apply_update_v1(update)
    return fresh.get_text(g.ROOT).get_string(), dict(fresh.state_vector().clocks), fresh.encode_state_as_update_v1()


def _agree(server, ticks) -> dict:
    """Every room the ticks name: the served path, `Doc` and the plain
    reference, each fed the room's updates in the order handed over."""
    rooms = sorted({k for frames in ticks for k, _ in frames})
    _clean(server)
    diffs = server.device_encode_diff_many([(g.room_name(k), StateVector()) for k in rooms])
    _clean(server)
    texts = {}
    for k, diff in zip(rooms, diffs):
        doc, plain = Doc(client_id=1), yp.Text()
        for frames in ticks:
            for room, u in frames:
                if room == k:
                    doc.apply_update_v1(u)
                    plain.apply_update(u)
        assert not plain.waiting and doc.store.pending is None and doc.store.pending_ds is None, k
        text, sv = doc.get_text(g.ROOT).get_string(), dict(doc.state_vector().clocks)
        assert (plain.text(), plain.state_vector()) == (text, sv), k
        name = g.room_name(k)
        assert server.device_text(name) == text, k
        assert dict(server.device_state_vector(name).clocks) == sv, k
        assert _canonical(diff) == (text, sv, _canonical(doc.encode_state_as_update_v1())[2]), k
        texts[k] = text
    return texts


# --- (a) the traces ---------------------------------------------------------------

SMALL = {
    "n_docs": N_ROOMS, "capacity": CAPACITY,
    # stages of four blocks: the prefill's dispatches and the window's share one integrate form
    "prefill": {"classes": [{"rooms": 2, "stage_rows": [4] * 4 + [1] * 4}, {"rooms": None, "stage_rows": [4] * 8}]},
}


def _mix(name: str, **over) -> dict:
    with open(os.path.join(ROOT, "benchmark", "traffic", name + ".json")) as f:
        return dict(json.load(f), **over)


def _stages(prefill):
    return [[(k, prefill.for_room(k).stages[s]) for k in range(N_ROOMS)] for s in range(prefill.n_stages)]


def _coedit_trace(seed: int):
    mix = _mix("coedit-flood", sessions=24, edits_per_session=3, tick_max_frames=TICK, warm_sessions=0)
    prefill = g.Prefill(SMALL["prefill"], N_ROOMS, seed)
    plan = coedit_mix.plan(SMALL, mix, prefill, seed, 1.0)
    ticks = [[(op.room, op.update) for op in plan.ops[i : i + TICK]] for i in range(0, len(plan.ops), TICK)]
    return plan, prefill, _stages(prefill) + ticks


def _typed(client: int, n: int, first: str = "x"):
    """`n` keystrokes of one client at the end of its own run, an update each."""
    return [g.encode_update(client, [B(k, (client, k - 1) if k else None, None, first)], {}) for k in range(n)]


def _early_insert():
    """Tab B holds tab A's edit before the server does and types behind it;
    B's update comes first, then another session's, then A's."""
    a = g.encode_update(11, [B(0, (10, 4), None, "AAA")], {})
    b = g.encode_update(12, [B(0, (11, 2), None, "bb")], {})
    c = g.encode_update(13, [B(0, (10, 1), (10, 2), "c")], {})
    base = g.encode_update(10, [B(0, None, None, "base:")], {})
    return [[(0, base), (3, base)], [(0, b)], [(0, c), (3, a)], [(0, a), (3, c)], [(3, b)]]


def _early_delete():
    """B deletes two of A's characters and one of the base's before A's
    insert has come: the range on A's clocks waits in the pending delete set."""
    base = g.encode_update(10, [B(0, None, None, "base:")], {})
    a = g.encode_update(11, [B(0, (10, 2), (10, 3), "AAAA")], {})
    b = g.encode_update(12, [], {11: [(1, 2)], 10: [(3, 1)]})
    c = g.encode_update(13, [B(0, (10, 4), None, "cc")], {})
    return [[(0, base)], [(0, b)], [(0, c)], [(0, a)]]


def _double_delete():
    """Two sessions delete overlapping characters of a third's block, one
    of them before it has seen the other's insert inside that block."""
    base = g.encode_update(10, [B(0, None, None, "abcdefgh")], {})
    ins = g.encode_update(13, [B(0, (10, 3), (10, 4), "XY")], {})
    d1 = g.encode_update(11, [], {10: [(2, 3)]})
    d2 = g.encode_update(12, [], {10: [(3, 3)], 13: [(0, 1)]})
    return [[(0, base), (5, base)], [(0, ins), (5, d1)], [(0, d1), (5, ins)], [(0, d2), (5, d2)]]


NINE = [21, 22, 23, 2**31 + 24, 25, 26, 2**32 - 27, 28, 29]


def _nine_concurrent():
    """Nine inserts made at once around the base's last character: three
    behind it with nothing to their right, three between its last two
    characters, three behind the first writer's insert by writers that had
    seen it and not the others. Three rooms, three arrival orders; a writer
    that names the first writer's characters comes after it in each."""
    base = g.encode_update(10, [B(0, None, None, "base:")], {})
    ups = {}
    for n, c in enumerate(NINE):
        word = f"<{n}>"
        if n < 3:
            ups[c] = g.encode_update(c, [B(0, (10, 4), None, word)], {})
        elif n < 6:
            ups[c] = g.encode_update(c, [B(0, (10, 3), (10, 4), word)], {})
        else:
            ups[c] = g.encode_update(c, [B(0, (NINE[0], 2), None, word)], {})
    orders = [list(NINE), [NINE[0]] + list(reversed(NINE[1:])), [NINE[0]] + random.Random(45).sample(NINE[1:], 8)]
    rooms = [0, 7, 15]
    return [[(k, base) for k in rooms]] + [[(k, ups[o[i]]) for k, o in zip(rooms, orders)] for i in range(9)]


HAND_MADE = {
    "an_early_insert": _early_insert,
    "an_early_delete": _early_delete,
    "two_sessions_delete_the_same_characters": _double_delete,
    "nine_concurrent_inserts_with_mixed_origins": _nine_concurrent,
}


@pytest.mark.parametrize("case", ["generator_one_device", "generator_doc_sharded", *HAND_MADE,
                                  "an_early_update_in_a_room_compacted_while_it_waits"])
def test_served_path_host_doc_and_plain_reference_agree(case):
    watched, stash, lanes = _counts(WATCHED), _counts(STASH), _counts(LANES)
    if case.startswith("generator"):
        plan, _prefill, ticks = _coedit_trace(45_000_001)
        server = _server(shard_docs=case.endswith("sharded"))
        for c in plan.clients:  # preregistered, as the cell's are
            server.ingestor.enc.interner.intern(c)
        _serve(server, {}, ticks)
        _agree(server, ticks)
        took, early = _counted(lanes), plan.notes["counts"]["early"]
        assert early >= 3 and len({s.room for s in plan.sessions}) > 4
        # the generator's own reckoning of the lanes: an early update waits
        # (`dependency`), its room's updates up to the one it waits for follow it (`pending`)
        assert took["ingest.slow.dependency"] == early
        assert took["ingest.slow_docs"] == plan.notes["host_lane_updates"] == early + took["ingest.slow.pending"]
        assert took["ingest.fast_docs"] == len(plan.ops) - took["ingest.slow_docs"] + N_ROOMS * 8
        counted = _counted(stash)
        assert counted["ingest.stash_updates"] == counted["ingest.stash_released"] == early
        assert early <= counted["ingest.stash_wait_steps"] <= 3 * early
    elif case in HAND_MADE:
        ticks = HAND_MADE[case]()
        server = _server()
        _serve(server, {}, ticks)
        texts = _agree(server, ticks)
        if case == "nine_concurrent_inserts_with_mixed_origins":
            assert len(set(texts.values())) == 1  # whatever order they came in
            plain = yp.Text()
            for _, u in [f for frames in ticks for f in frames if f[0] == 0]:
                plain.apply_update(u)
            # the rule, spelled out: between the base's last two characters the
            # three in ascending client id; behind the last, in ascending client
            # id (as unsigned integers), the first writer's insert keeping the
            # three that name it behind it, in ascending client id too
            assert plain.text() == texts[0] == "base<4><5><3>:<0><7><8><6><1><2>"
        if case == "an_early_delete":
            assert _counted(stash)["ingest.stash_updates"] == 1
    else:
        # capacity 128: a room is due above 120 rows. A typist fills room 0 row
        # by row; an early update waits in its stash while the typing goes on
        # and crosses the line; then what it waits for comes
        server = _server()
        keys = _typed(10, 130)
        a = g.encode_update(11, [B(0, (10, 9), (10, 10), "AAA")], {})
        b = g.encode_update(12, [B(0, (11, 1), (11, 2), "bb")], {})
        d = g.encode_update(13, [], {11: [(0, 1)], 10: [(0, 2)]})
        ticks = [[(0, u)] for u in keys[:116]] + [[(0, b)], [(0, d)]] + [[(0, u)] for u in keys[116:124]]
        ticks += [[(0, a)]] + [[(0, u)] for u in keys[124:]]
        compactions = metrics.counter("ingest.room_compactions")
        seen = {"n": compactions.value, "while_stashed": 0}

        def after_step():
            if compactions.value > seen["n"]:
                seen["n"] = compactions.value
                seen["while_stashed"] += server.ingestor.pending_update(0) is not None

        _serve(server, {}, ticks, after_step)
        assert seen["while_stashed"] >= 1, "the room was compacted while an update waited in its stash"
        _agree(server, ticks)
        counted = _counted(stash)
        assert counted["ingest.stash_updates"] == 2 and counted["ingest.stash_released"] == 2
        assert counted["ingest.stash_wait_steps"] == 10 + 9  # b, then d, until a came
    assert _counted(watched) == dict.fromkeys(WATCHED, 0)


# --- (b) the stash's counters, and the lane a room takes -------------------------------


def test_stash_counters_step_by_step():
    """Stashed, held two steps, released: 1 / 2 / 1; a room with a stash
    takes the host lane, and is back on the fast lane the step after."""
    base = g.encode_update(10, [B(0, None, None, "base:")], {})
    a = g.encode_update(11, [B(0, (10, 4), None, "AAA")], {})
    b = g.encode_update(12, [B(0, (11, 2), None, "bb")], {})  # on top of a, before it
    other = [g.encode_update(13, [B(k, (10, 1) if k == 0 else (13, k - 1), (10, 2), "c")], {}) for k in range(3)]
    server = _server()
    sessions = {}
    _serve(server, sessions, [[(0, base), (1, base)]])
    phases.reset()
    phases.enable()
    try:
        stash, lanes = _counts(STASH), _counts(LANES)
        steps = []
        for u in (b, other[0], a, other[1], other[2]):
            # room 1 rides the fast lane beside it, whatever room 0 holds
            _serve(server, sessions, [[(0, u), (1, other[len(steps)] if len(steps) < 3 else _typed(14, 2)[len(steps) - 3])]])
            steps.append((_counted(stash), _counted(lanes), bool(server.ingestor._stash_tickets)))
        recorded = phases.snapshot()
    finally:
        phases.disable()
    by_step = [tuple(int(s[n]) for n in STASH) for s, _, _ in steps]
    #          updates, released, wait steps, rooms holding one as a step plans (summed)
    assert by_step == [(1, 0, 0, 0), (1, 0, 0, 1), (1, 1, 2, 2), (1, 1, 2, 2), (1, 1, 2, 2)]
    assert [held for _, _, held in steps] == [True, True, False, False, False]
    lane = [(int(l["ingest.slow.dependency"]), int(l["ingest.slow.pending"]), int(l["ingest.fast_docs"])) for _, l, _ in steps]
    # b waits (`dependency`); the update in between and a itself find a stash
    # (`pending`: exactly the updates in between); then room 0 is back on the
    # fast lane. Room 1 is on it in every step
    assert lane == [(1, 0, 1), (1, 1, 2), (1, 2, 3), (1, 2, 5), (1, 2, 7)]
    # the recorder's copies, which the benchmark's readers read, and the span: one a
    # step in which room 0 held or gained a stash
    for n in STASH:
        assert recorded[n]["value"] == steps[-1][0][n]
    assert recorded["ingest.plan.stash"]["calls"] == 3
    assert recorded["ingest.plan.host_rows"]["calls"] == 5
    ticks = [[(0, base), (1, base)]] + [[(0, u)] for u in (b, other[0], a, other[1], other[2])]
    ticks += [[(1, u)] for u in other] + [[(1, u)] for u in _typed(14, 2)]
    texts = _agree(server, ticks)
    assert texts[0] == "bacccse:AAAbb"


def test_edit_flood_stashes_nothing():
    """The mechanism idle: `edit-flood`'s rehearsal counts no stash."""
    seed = 45_000_003
    mix = _mix("edit-flood", sessions=24, edits_per_session=3, tick_max_frames=TICK, warm_sessions=0)
    prefill = g.Prefill(SMALL["prefill"], N_ROOMS, seed)
    plan = session_mix.plan(SMALL, mix, prefill, seed, 1.0)
    ticks = _stages(prefill) + [[(op.room, op.update) for op in plan.ops[i : i + TICK]] for i in range(0, len(plan.ops), TICK)]
    server = _server()
    for c in plan.clients:
        server.ingestor.enc.interner.intern(c)
    stash, lanes = _counts(STASH), _counts(LANES)
    phases.reset()
    phases.enable()
    try:
        _serve(server, {}, ticks)
        recorded = phases.snapshot()
    finally:
        phases.disable()
    _clean(server)
    assert _counted(stash) == dict.fromkeys(STASH, 0)
    assert _counted(lanes)["ingest.slow_docs"] == 0
    assert "ingest.plan.stash" not in recorded and not any(n in recorded for n in STASH)


# --- (c) a pending update's Skip must not shadow a later arrival's blocks ------------

SHADOWED = [
    g.encode_update(12, [B(0, (11, 1), None, "a")], {}),
    g.encode_update(11, [B(2, (11, 1), None, "b")], {}),
    g.encode_update(12, [B(7, (12, 6), (11, 1), "baa")], {}),
    g.encode_update(12, [B(4, (12, 2), (11, 1), "bbe")], {}),  # inside the gap the pending update skips
    g.encode_update(12, [B(3, (12, 1), (12, 2), "c")], {}),
    g.encode_update(12, [B(1, (11, 0), (11, 1), "af")], {}),
    g.encode_update(11, [B(0, None, None, "cd")], {}),
]


def _any_order(seed: int):
    """Three clients co-edit through real `Doc`s, taking each other's
    updates late and out of order; then a fourth takes all of it shuffled."""
    r = random.Random(seed)
    docs = [Doc(client_id=c) for c in (10, 2**31 + 11, 7012)]
    sent = [[] for _ in docs]
    for i, d in enumerate(docs):
        d.observe_update_v1(lambda p, o, t, i=i: sent[i].append(p))
    inbox, log = [[] for _ in docs], []
    for _ in range(40):
        i = r.randrange(3)
        d = docs[i]
        while inbox[i] and r.random() < 0.6:
            n = len(sent[i])
            d.apply_update_v1(inbox[i].pop(r.randrange(len(inbox[i]))))
            del sent[i][n:]
        txt = d.get_text(g.ROOT)
        s = txt.get_string()
        with d.transact() as txn:
            if len(s) > 4 and r.random() < 0.3:
                txt.remove_range(txn, r.randrange(len(s) - 2), r.randint(1, 3))
            else:
                txt.insert(txn, r.randint(0, len(s)), "".join(r.choice("abcdef") for _ in range(r.randint(1, 4))))
        u = sent[i].pop()
        log.append(u)
        for j in range(3):
            if j != i:
                inbox[j].append(u)
    r.shuffle(log)
    return log


@pytest.mark.parametrize("order", ["the_order_the_plain_reference_found", *range(6)])
def test_updates_delivered_in_any_order_converge(order):
    log = SHADOWED if isinstance(order, str) else _any_order(4500 + order)
    doc, plain, total = Doc(client_id=1), yp.Text(), yp.Text()
    for u in log:
        doc.apply_update_v1(u)
        plain.apply_update(u)
    assert not plain.waiting and doc.store.pending is None and doc.store.pending_ds is None
    assert doc.get_text(g.ROOT).get_string() == plain.text()
    assert dict(doc.state_vector().clocks) == plain.state_vector()
    if isinstance(order, str):
        for u in sorted(log, key=lambda u: yp.decode_update(u)[0][0][:2]):  # in causal order
            total.apply_update(u)
        assert plain.text() == total.text() == "cacfbbebaadba"


# `Update.merge` is the check's oracle's code too (`Doc`'s pending path, `store.py`, `device_server.merge_updates`):
# inputs that carry explicit Skip and GC carriers, and what PR 44's tree (66b8176) re-encoded for them, byte for byte.
# name -> (inputs, merged), hex
MERGED_AT_THE_PARENT = {
    "skip_carrier_alone": (
        ["01030b00040104746578740261620a03840b04016600"],
        "01030b00040104746578740261620a03840b04016600",
    ),
    "skip_with_other_client": (
        ["01030b00040104746578740261620a03840b04016600", "01010c000401047465787402787900", "01010c03840c020375767700"],
        "02030c00040104746578740278790a01840c0203757677030b00040104746578740261620a03840b04016600",
    ),
    "skip_then_later_run": (
        ["01030b00040104746578740261620a03840b04016600", "01010b0a840b09026b6c00"],
        "01050b00040104746578740261620a03840b0401660a04840b09026b6c00",
    ),
    "two_skips_same_gap": (
        ["01030b00040104746578740261620a03840b04016600", "01050b00040104746578740261620a03840b0401660a04840b09026b6c00"],
        "01050b00040104746578740261620a03840b0401660a04840b09026b6c00",
    ),
    "skip_filled_by_later_arrival": (
        ["01030b00040104746578740261620a03840b04016600", "01010b02840b010363646500"],
        "01030b0004010474657874026162840b0103636465840b04016600",
    ),
    "gc_full_state": (
        ["01030d00040104746578740268650007840d08046c642121010d010207"],
        "01030d00040104746578740268650007840d08046c642121010d010207",
    ),
    "gc_with_its_parts": (
        ["01010d00040104746578740568656c6c6f00", "01030d00040104746578740268650007840d08046c642121010d010207"],
        "01030d00040104746578740568656c6c6f0004840d08046c642121010d010207",
    ),
    "gc_after_its_parts": (
        ["01030d00040104746578740268650007840d08046c642121010d010207", "01010d00040104746578740568656c6c6f00", "01010d05840d040620776f726c6400"],
        "01050d0004010474657874026865840d01036c6c6f0004840d08026c64840d0a022121010d010207",
    ),
    "gc_and_skip": (
        ["01030d00040104746578740268650007840d08046c642121010d010207", "01030b00040104746578740261620a03840b04016600", "01010c02840c01017a00"],
        "03030d00040104746578740268650007840d08046c642121010c02840c01017a030b00040104746578740261620a03840b040166010d010207",
    ),
    "gc_diff_from_inside": (
        ["01020d030006840d08046c642121010d010207"],
        "01020d030006840d08046c642121010d010207",
    ),
    "gc_partial_overlap": (
        ["01010d00040104746578740568656c6c6f00", "01020d030006840d08046c642121010d010207"],
        "01030d00040104746578740568656c6c6f0004840d08046c642121010d010207",
    ),
}
# the two where an input's Skip lay over blocks another input held: the parent dropped them (its bytes second)
SHADOWED_AT_THE_PARENT = {
    "skip_wider_and_narrower": (
        ["01030b00040104746578740261620a04840b05046768696a00", "01030b00040104746578740261620a03840b04016600"],
        "01040b00040104746578740261620a03840b040166840b05046768696a00",
        "01030b00040104746578740261620a04840b05046768696a00",
    ),
    "gc_beside_a_skip": (
        ["01030d00040104746578740568656c6c6f0a06840d0a02212100", "01020d030006840d08046c642121010d010207"],
        "01030d00040104746578740568656c6c6f0004840d08046c642121010d010207",
        "01040d00040104746578740568656c6c6f00040a02840d0a022121010d010207",
    ),
}


@pytest.mark.parametrize("name", sorted(MERGED_AT_THE_PARENT))
def test_merge_re_encodes_skip_and_gc_carriers_to_the_parents_bytes(name):
    inputs, merged = MERGED_AT_THE_PARENT[name]
    carriers = [c for u in inputs for q in Update.decode_v1(bytes.fromhex(u)).blocks.values() for c in q]
    assert any(c.is_skip or isinstance(c, GCRange) for c in carriers)
    assert merge_updates_v1([bytes.fromhex(u) for u in inputs]).hex() == merged


@pytest.mark.parametrize("name", sorted(SHADOWED_AT_THE_PARENT))
def test_merge_keeps_the_blocks_an_inputs_skip_lay_over(name):
    inputs, merged, lost = SHADOWED_AT_THE_PARENT[name]
    out = merge_updates_v1([bytes.fromhex(u) for u in inputs])
    assert out.hex() == merged != lost
    kept, was = (sum(c.len for q in Update.decode_v1(u).blocks.values() for c in q if not c.is_skip) for u in (out, bytes.fromhex(lost)))
    assert kept > was
