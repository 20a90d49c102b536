"""The generator: same seed -> same frames, session order kept, the seed
types other characters and moves nothing else, the hand-encoded updates
are what a real synced client sends, and every room stays under capacity."""

import hashlib
import json
import os

import pytest

from benchmark import grammar as g
from benchmark.generators import session_mix

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)


def _load(kind, name):
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


def _small(mix_name, **over):
    deploy = dict(_load("configs", "yws-rooms-1k"), n_docs=64)
    mix = dict(_load("traffic", mix_name), sessions=96, edits_per_session=4, tick_max_frames=8,
               warm_sessions=8, **over)
    return deploy, mix


def _digest(plan):
    h = hashlib.sha256()
    for op in plan.preload + plan.ops + [o for tick in plan.warm for o in tick]:
        h.update(f"{op.kind}|{op.session}|{op.room}|{op.due:.9f}|{op.stale}|".encode())
        h.update(op.frame)
    return h.hexdigest()


SMALL_PREFILL = {"classes": [{"rooms": 2, "stage_rows": [24, 1]}, {"rooms": None, "stage_rows": [24, 24]}]}


def _prefill(seed, n_rooms=64):
    return g.Prefill(SMALL_PREFILL, n_rooms, seed)


@pytest.fixture(scope="module")
def prefill():
    return _prefill(7)


@pytest.mark.parametrize("mix_name", ["edit-flood", "connect-storm", "edit-steady"])
def test_same_seed_same_frames(mix_name, prefill):
    over = {"preload_updates": 48} if mix_name == "connect-storm" else {}
    deploy, mix = _small(mix_name, **over)
    a = session_mix.plan(deploy, mix, prefill, 7, 5.0)
    b = session_mix.plan(deploy, mix, prefill, 7, 5.0)
    c = session_mix.plan(deploy, mix, prefill, 8, 5.0)
    assert _digest(a) == _digest(b)
    assert _digest(a) != _digest(c)


def test_each_sessions_order_is_kept(prefill):
    deploy, mix = _small("edit-flood")
    plan = session_mix.plan(deploy, mix, prefill, 3, 5.0)
    seen = {}
    for op in plan.ops:
        s = plan.sessions[op.session]
        k = seen.get(op.session, 0)
        assert op.update == s.edits[k].update
        seen[op.session] = k + 1
    assert all(n == mix["edits_per_session"] for n in seen.values())


def test_the_seed_types_other_characters_and_changes_nothing_else():
    """Same rooms, kinds, due times, staleness and payload lengths, in the
    same order, for every seed; other bytes."""
    for mix_name, over in (("edit-steady", {"rate_per_s": 20.0}), ("connect-storm", {"preload_updates": 48})):
        deploy, mix = _small(mix_name, **over)
        a = session_mix.plan(deploy, mix, _prefill(1), 1, 5.0)
        b = session_mix.plan(deploy, mix, _prefill(2), 2, 5.0)
        shape = lambda p: [(o.kind, o.session, o.room, round(o.due, 9), o.stale, len(o.frame))
                           for o in p.preload + p.ops + [x for t in p.warm for x in t]]
        assert shape(a) == shape(b)
        assert a.session_rooms == b.session_rooms
        assert [o.frame for o in a.preload + a.ops] != [o.frame for o in b.preload + b.ops]
    ta, tb = _prefill(1).templates, _prefill(2).templates
    shape = lambda t: (t.rows, t.chars, [len(u) for u in t.stages], t.row_clocks)
    assert [shape(t) for t in ta] == [shape(t) for t in tb]
    assert [t.stages for t in ta] != [t.stages for t in tb]


def test_hand_encoded_updates_are_what_a_synced_client_sends():
    """A real `ytpu.core.Doc` that has applied the room's prefill and makes
    the same edits emits the same bytes and state vector, edit for edit;
    and the prefill's stages give the document the model says they give."""
    from ytpu.core import Doc

    tpl = _prefill(3).templates[1]
    real = Doc(client_id=7001)
    for u in tpl.stages:
        real.apply_update_v1(u)
    txt = real.get_text(g.ROOT)
    assert len(txt.get_string()) == tpl.chars == len(tpl.ids)
    assert dict(real.state_vector().clocks) == {tpl.client_id: tpl.chars}
    # one device row per block the stages carry, one more per block a later insert split
    blocks = sum(SMALL_PREFILL["classes"][1]["stage_rows"])
    assert blocks <= tpl.rows == len(tpl.row_clocks) <= 2 * blocks
    sent = []
    real.observe_update_v1(lambda p, o, t: sent.append(p))
    typist = g.Typist(7001, g.rng(1, "shape"), tpl.ids, {tpl.client_id: tpl.chars}, text=g.rng(2, "text"))
    r, letters, length = g.rng(1, "shape"), g.rng(2, "text"), tpl.chars
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
                txt.insert(txn, r.randint(0, length), word)
                length += n
        assert sent[-1] == edit.update, (k, delete)
        assert real.state_vector().encode_v1() == edit.sv_after
    assert typist.length == length == len(txt.get_string())


def test_a_rooms_frames_are_evenly_paced():
    sessions = g.build_sessions(64, 96, 4, 0.99, 1, _prefill(1))
    order = g.interleave(sessions, g.LAYOUT)
    hot = max(set(s.room for s in sessions), key=lambda k: sum(s.room == k for s in sessions))
    share = sum(s.room == hot for s in sessions) / len(sessions)
    for i in range(0, len(order) - 16, 16):
        n = sum(sessions[j].room == hot for j, _ in order[i : i + 16])
        assert abs(n - 16 * share) <= 1.0 + 1e-9


@pytest.mark.parametrize("config", ["yws-rooms-1k"])
def test_every_room_stays_under_capacity(config):
    """The headroom reckoning: with the whole pool drained, a room holds
    its prefill, its share of the warm-up and its sessions' edits; an edit
    adds 2 rows at most (a block and one split, or a delete's two ends)."""
    deploy = _load("configs", config)
    mix = _load("traffic", "edit-flood")
    n = deploy["n_docs"]
    prefill = g.Prefill(deploy["prefill"], n, 1)
    quota = g.zipf_quotas(n, mix["sessions"], mix["zipf_s"])
    warm = g.zipf_quotas(n, mix["warm_sessions"], mix["zipf_s"])
    sweep = mix["tick_max_frames"] * 4  # a sweep room takes a few updates per lane count
    for k in range(n):
        rows = prefill.for_room(k).rows + 2 * (quota[k] * mix["edits_per_session"] + warm[k] * 2 + sweep)
        assert rows < deploy["capacity"], (k, rows)
    filled = sum(prefill.for_room(k).rows for k in range(n)) / (n * deploy["capacity"])
    assert filled > 0.75, filled  # the rooms are filled, not reserved


def test_quotas_sum_and_skew():
    q = g.zipf_quotas(1024, 2048, 0.99)
    assert sum(q) == 2048 and q == sorted(q, reverse=True)
    assert 0.12 < q[0] / 2048 < 0.14
