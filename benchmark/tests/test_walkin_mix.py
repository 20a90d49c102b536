"""The walk-in generator (`author-flood`): same seed -> same frames and ids;
the ids are what Yjs draws; writers walk in as `session_span` says; every
room stays under capacity; the hand-encoded updates of a uint32 writer are
what a real synced client sends, at the tail and mid-document; and the
cell's command path on the CPU, traced, untraced and broken."""

import hashlib
import json
import math
import os
import subprocess
import sys

import pytest

from benchmark import grammar as g
from benchmark.generators import walkin_mix as wm

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL = "yws-rooms-1k-unregistered.author-flood"
I32_MAX = 2**31 - 1


def _load(kind, name):
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


def _digest(plan):
    h = hashlib.sha256()
    for op in plan.ops + [o for tick in plan.warm for o in tick]:
        h.update(f"{op.kind}|{op.session}|{op.room}|".encode())
        h.update(op.frame)
    return h.hexdigest()


@pytest.fixture(scope="module")
def cell():
    """The cell at its real size: (deploy, mix, plan of seed 1)."""
    deploy, mix = _load("configs", "yws-rooms-1k-unregistered"), _load("traffic", "author-flood")
    prefill = g.Prefill(deploy["prefill"], deploy["n_docs"], 1)
    return deploy, mix, prefill, wm.plan(deploy, mix, prefill, 1, 30.0)


def test_the_parameters_are_the_issues(cell):
    deploy, mix, _, plan = cell
    assert {k: mix[k] for k in ("generator", "arrival", "sessions", "edits_per_session", "zipf_s", "tick_max_frames",
                                "client_ids", "tail_share", "session_span", "warm_sessions", "repeat")} == {
        "generator": "walkin_mix", "arrival": "saturated", "sessions": 2048, "edits_per_session": 6, "zipf_s": 0.99,
        "tick_max_frames": 16, "client_ids": "yjs-uint32", "tail_share": 0.5, "session_span": 0.2,
        "warm_sessions": 64, "repeat": False}
    assert len(plan.ops) == 12288 and plan.saturated and not plan.repeat and not plan.preload
    assert g.zipf_quotas(1024, 2048, 0.99)[:4] == [264, 133, 89, 67] and plan.notes["hot_room_sessions"] == 264
    # every size and every guarantee is yws-rooms-1k's
    parent = _load("configs", "yws-rooms-1k")
    for key in ("n_docs", "capacity", "device_authoritative", "shard_docs", "chips", "replicas", "room_type",
                "zipf_s", "prefill", "guarantees", "reduced", "rehearsal", "server"):
        assert deploy[key] == parent[key], key


def test_no_traffic_client_is_handed_over_for_interning(cell):
    _, _, prefill, plan = cell
    assert plan.clients == [t.client_id for t in prefill.templates] == [900_000, 900_001]
    assert not set(plan.clients) & {s.client_id for s in plan.sessions}


@pytest.mark.parametrize("what", ["frames", "ids"])
def test_same_seed_same_frames_and_the_seed_leaves_the_ids_alone(what):
    deploy = dict(_load("configs", "yws-rooms-1k-unregistered"), n_docs=64)
    mix = dict(_load("traffic", "author-flood"), sessions=96, edits_per_session=4, tick_max_frames=8, warm_sessions=8)
    small = {"classes": [{"rooms": 2, "stage_rows": [24, 1]}, {"rooms": None, "stage_rows": [24, 24]}]}
    a, b, c = (wm.plan(deploy, mix, g.Prefill(small, 64, seed), seed, 5.0) for seed in (7, 7, 8))
    if what == "frames":
        assert _digest(a) == _digest(b) != _digest(c)
        shape = lambda p: [(o.session, o.room, len(o.frame)) for o in p.ops + [x for t in p.warm for x in t]]
        assert shape(a) == shape(c)  # the seed types other characters and moves nothing else
    else:
        ids = lambda p: [s.client_id for s in p.sessions]
        assert ids(a) == ids(b) == ids(c) == wm.draw_client_ids(96, "traffic")


def test_the_ids_are_what_yjs_draws(cell):
    from ytpu.ops.decode_kernel import client_hash_host

    ids = [s.client_id for s in cell[3].sessions]
    assert len(set(ids)) == len(ids) == 2048
    assert all(1 <= c < 2**32 for c in ids)
    assert not [c for c in ids if 600_000 <= c <= 900_063]
    big = [c for c in ids if c > I32_MAX]
    assert 0.40 <= len(big) / len(ids) <= 0.60
    # the device resolves an id past int32 by a hash of its varint bytes: two
    # writers of one hash would take the host lane, and the cell's fast lane is 100%
    assert len({client_hash_host(c) for c in big}) == len(big)
    # the warm-up's writers keep the ids warmup.py and oracle.py fix for them
    warm = {_insert_header(op.update)[0] for tick in cell[3].warm for op in tick if op.update[0] == 1}
    assert warm and all(g.WARM_CLIENT_BASE + 16 <= c < g.WARM_CLIENT_BASE + 16 + 64 for c in warm)


def test_writers_walk_in_all_through_the_first_four_fifths(cell):
    plan = cell[3]
    size = len(plan.ops)
    first, last = {}, {}
    seen = {}
    for at, op in enumerate(plan.ops):
        k = seen.get(op.session, 0)
        assert op.update == plan.sessions[op.session].edits[k].update  # a session's own order is kept
        seen[op.session] = k + 1
        first.setdefault(op.session, at)
        last[op.session] = at
    assert set(seen.values()) == {6} and len(seen) == 2048
    # a session's six edits lie within one fifth of the pool
    assert max(last[s] - first[s] for s in first) <= math.ceil(0.2 * size)
    # each eighth of the pool's first four fifths holds 10-15% of the first updates, its last fifth none
    eighth = 0.8 * size / 8
    shares = [sum(1 for at in first.values() if k * eighth <= at < (k + 1) * eighth) / 2048 for k in range(8)]
    assert all(0.10 <= x <= 0.15 for x in shares), shares
    assert max(first.values()) < 0.8 * size
    # so about one update in five is a writer's first, wherever a window ends
    for end in (0.5, 0.68, 0.8):
        n = int(end * size)
        assert 0.19 <= sum(1 for at in first.values() if at < n) / n <= 0.22, end


def test_a_tick_seldom_holds_a_session_twice_and_dispatches_stay_wide(cell):
    plan = cell[3]
    tick = plan.tick_max_frames
    reach = int(0.9 * len(plan.ops))  # the pool's last tenth is handed out by deadline alone
    twice = dispatches = 0
    for i in range(0, reach, tick):
        ops = plan.ops[i : i + tick]
        twice += len(ops) - len({op.session for op in ops})
        per_room = {}
        for op in ops:
            per_room[op.room] = per_room.get(op.room, 0) + 1
        dispatches += max(per_room.values())
    assert twice <= 0.01 * reach  # the pool's first few ticks, when few sessions are open
    assert reach / dispatches >= 6.5  # edit-flood: 7.76; a first-seen writer in most dispatches needs them wide
    # the warm-up covers every lane count the pool's ticks dispatch
    assert plan.notes["lane_counts"] == sorted(set(plan.notes["lane_counts"]))
    assert set(plan.notes["lane_counts"]) <= set(range(1, tick + 1)) and plan.notes["needs_update_warm"]


def _varuint_at(buf: bytes, at: int):
    value = shift = 0
    while True:
        byte = buf[at]
        value |= (byte & 0x7F) << shift
        at, shift = at + 1, shift + 7
        if not byte & 0x80:
            return value, at


def _insert_header(update: bytes):
    """(client, info byte, origin id or None) of a one-block insert update."""
    assert update[0] == 1 and update[1] == 1  # one client section, one block
    client, at = _varuint_at(update, 2)
    _clock, at = _varuint_at(update, at)
    info = update[at]
    origin = None
    if info & 0x80:
        oc, at = _varuint_at(update, at + 1)
        ok, at = _varuint_at(update, at)
        origin = (oc, ok)
    return client, info, origin


def test_half_of_the_inserts_go_at_the_end_of_the_room(cell):
    """A tail insert names the last character of the document as the
    session holds it as its origin and nothing to its right: a room's first
    tail inserts all name the prefill's last character, so the server has
    to order them, and by nothing but the writers' ids."""
    _, _, prefill, plan = cell
    tails = inserts = 0
    shared = {}  # room -> writers whose insert names the prefill's last character and nothing to its right
    for s in plan.sessions:
        end = prefill.for_room(s.room).ids[-1]
        for e in s.edits:
            if e.chars <= 0:
                continue
            client, info, origin = _insert_header(e.update)
            assert client == s.client_id
            inserts += 1
            if origin is not None and not info & 0x40:
                tails += 1
                if origin == end:
                    shared.setdefault(s.room, set()).add(client)
    assert 0.45 <= tails / inserts <= 0.56, tails / inserts
    assert len(shared[0]) >= 150  # of room 0's 264 writers
    assert sum(1 for c in shared[0] if c > I32_MAX) >= 50 and sum(1 for c in shared[0] if c <= I32_MAX) >= 50


@pytest.mark.parametrize("config", ["yws-rooms-1k-unregistered"])
def test_every_room_stays_under_capacity(config):
    """`test_generator.py`'s reckoning with this cell's files: with the
    whole pool drained a room holds its prefill, its share of the warm-up
    and its sessions' edits; an edit adds 2 rows at most (a tail insert 1)."""
    deploy, mix = _load("configs", config), _load("traffic", "author-flood")
    n = deploy["n_docs"]
    prefill = g.Prefill(deploy["prefill"], n, 1)
    quota = g.zipf_quotas(n, mix["sessions"], mix["zipf_s"])
    warm = g.zipf_quotas(n, mix["warm_sessions"], mix["zipf_s"])
    sweep = mix["tick_max_frames"] * 4
    for k in range(n):
        rows = prefill.for_room(k).rows + 2 * (quota[k] * mix["edits_per_session"] + warm[k] * 2 + sweep)
        assert rows < deploy["capacity"], (k, rows)
    assert min(deploy["capacity"] - prefill.for_room(k).rows for k in range(n)) >= 2 * mix["edits_per_session"]


@pytest.mark.parametrize("client_id", [3_405_691_582, 1_234_567], ids=["past_int32", "within_int32"])
def test_hand_encoded_updates_of_a_uint32_writer_are_what_a_synced_client_sends(client_id):
    """A real `ytpu.core.Doc` with this id that has applied the room's
    prefill and makes the same edits, tail and mid-document, emits the same
    bytes and state vector, edit for edit."""
    from ytpu.core import Doc

    small = {"classes": [{"rooms": None, "stage_rows": [24, 24]}]}
    tpl = g.Prefill(small, 4, 3).templates[0]
    real = Doc(client_id=client_id)
    for u in tpl.stages:
        real.apply_update_v1(u)
    txt = real.get_text(g.ROOT)
    sent = []
    real.observe_update_v1(lambda p, o, t: sent.append(p))
    typist = wm.TailTypist(client_id, g.rng(1, "shape"), tpl.ids, {tpl.client_id: tpl.chars},
                           text=g.rng(2, "text"), tail_share=0.5)
    r, letters, length = g.rng(1, "shape"), g.rng(2, "text"), tpl.chars
    tails = mids = 0
    for k in range(300):
        edit = typist.next_edit()
        delete = length > 8 and r.random() < 0.25  # the same draws, on the real client
        with real.transact() as txn:
            if delete:
                pos, n = r.randint(0, length - 4), r.randint(1, 3)
                txt.remove_range(txn, pos, n)
                length -= n
            else:
                n = r.randint(3, 8)
                word = "".join(letters.choice(g.ALPHABET) for _ in range(n))
                tail = r.random() < 0.5
                txt.insert(txn, length if tail else r.randint(0, length), word)
                length += n
                tails += tail
                mids += not tail
        assert sent[-1] == edit.update, (k, delete)
        assert real.state_vector().encode_v1() == edit.sv_after
    assert typist.length == length == len(txt.get_string())
    assert tails >= 80 and mids >= 80


def _run(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", CELL, "--seconds", "2",
         "--rehearse", *args],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=900,
    )


@pytest.mark.parametrize("trace", [0, 1], ids=["untraced", "traced"])
def test_the_cells_rehearsal(trace):
    p = _run("--seed", "4000000035", "--trace", str(trace))
    assert p.returncode == 0, p.stderr[-2000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True and last["correct"] is True and last["failed"] == 0
    assert " 0 programs built inside the window" in p.stdout
    if trace:  # the three readers this cell brings find their counters and stages
        assert {"first_seen_per_step.flood", "big_client_pct.flood", "tables_ms.flood",
                "fast_lane_pct.flood", "window_compiles.flood"} <= set(last["would_report"])
    else:
        assert last["would_report"] == ["setup_s", "updates_per_s"]


def test_a_lost_update_of_a_walk_in_writer_is_not_correct():
    p = _run("--seed", "12", "--trace", "0", "--break", "lose-update")
    assert p.returncode == 0, p.stderr[-2000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["correct"] is False
    assert "FAILED" in p.stdout


@pytest.mark.parametrize("anyway", [False, True], ids=["refused", "run_all_the_same"])
def test_a_program_whose_tables_follow_its_writers_is_refused(cell, monkeypatch, anyway):
    """The tree before PR 35 built each table at the length of what it held;
    the same is done here to this tree's `_sorted_table`."""
    import numpy as np

    from ytpu.models import ingest

    deploy, mix, prefill, _ = cell
    assert wm.tables_hold_their_shape()

    def exact(mapping, width):
        keys = np.fromiter(mapping, np.int32, len(mapping))
        order = np.argsort(keys)
        return keys[order], np.fromiter(mapping.values(), np.int32, len(mapping))[order]

    monkeypatch.setattr(ingest, "_sorted_table", exact)
    assert not wm.tables_hold_their_shape()
    if anyway:
        monkeypatch.setenv(wm.ANYWAY, "1")
        assert len(wm.plan(deploy, mix, prefill, 1, 30.0).ops) == 12288
    else:
        monkeypatch.delenv(wm.ANYWAY, raising=False)
        with pytest.raises(SystemExit, match="cannot serve this deployment"):
            wm.plan(deploy, mix, prefill, 1, 30.0)
