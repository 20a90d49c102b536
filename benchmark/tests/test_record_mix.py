"""The record-store generator (`generators/record_mix.py`): the traffic is the
one the cell names, every update is what a real synced `ytpu.core.Doc` sends
for the same `YKeyValue` transaction, every room stays under capacity, the
grammar's count of a room's state vector is the oracle's, the warm-up mirrors
every make-up of a window dispatch, and the cell's CPU rehearsal ends
`correct` with no program built inside its window (and not `correct` where
the loop loses an update)."""

import collections
import json
import os
import subprocess
import sys

import pytest

from benchmark import grammar as g
from benchmark.generators import record_mix as rm

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL = "yws-rooms-1k-records.record-flood"
SEED = 4000000123


def _load(kind, name):
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


def _plan(deploy, mix, seed):
    prefill = g.Prefill(deploy["prefill"], deploy["n_docs"], seed)
    return prefill, rm.plan(deploy, mix, prefill, seed, 30.0)


@pytest.fixture(scope="module")
def cell():
    """The cell at its real size: 1,024 rooms, 2,048 sessions x 6."""
    deploy, mix = _load("configs", "yws-rooms-1k-records"), _load("traffic", "record-flood")
    prefill, plan = _plan(deploy, mix, SEED)
    return deploy, mix, prefill, plan


def _small(seed):
    deploy = _load("configs", "yws-rooms-1k-records")
    deploy.update(deploy["rehearsal"])
    mix = dict(_load("traffic", "record-flood"), sessions=40, edits_per_session=4, tick_max_frames=6)
    return (deploy, mix) + _plan(deploy, mix, seed)


def test_the_traffic_is_the_cells(cell):
    deploy, mix, prefill, plan = cell
    assert len(plan.ops) == 12288 and len(plan.session_rooms) == 2048 + 1024
    per_session = collections.Counter(op.session for op in plan.ops)
    assert set(per_session) == set(range(2048)) and set(per_session.values()) == {6}
    quotas = collections.Counter(plan.session_rooms[:2048])
    assert [quotas[k] for k in range(4)] == [264, 133, 89, 67] and plan.notes["hot_room_sessions"] == 264
    kinds = collections.Counter(rm._kind_of(op.update) for op in plan.ops)
    assert kinds == {"set_existing": 8601, "set_new": 2458, "delete": 1229}  # 0.7 / 0.2 / 0.1 of 12,288, to the unit
    sets = [len(op.update) for op in plan.ops if op.update[0]]
    assert 300 <= min(sets) and max(sets) <= 420
    assert plan.saturated and not plan.repeat and plan.tick_max_frames == 16
    # every writer is known before the first frame: sessions 7000 + i, the loaders, the warm-up's, the templates
    assert set(range(g.CLIENT_BASE, g.CLIENT_BASE + 2048)) <= set(plan.clients)
    assert {s.client_id for s in plan.sessions} <= set(plan.clients)
    # the loaders come after the traffic's sessions, one a room, a stage an edit of `chars` records
    loaders = plan.sessions[2048:]
    assert [ld.room for ld in loaders] == list(range(1024)) == plan.session_rooms[2048:]
    # one loader update a room: the whole store, a block a record, inside the 512-row bucket
    (store,) = deploy["records"]["classes"]  # every room alike: `reduced.records_per_room`
    n = store["records"]
    assert store["rooms"] is None and 256 <= n <= 448 <= deploy["records"]["stage_blocks"] == 460
    assert [[e.chars for e in ld.edits] for ld in loaders] == [[n]] * 1024
    assert len(plan.preload) == 1024 and plan.notes["records_loaded"] == n * 1024
    for i in range(0, len(plan.preload), 16):  # a tick of the load is 16 rooms: one dispatch
        assert len({op.room for op in plan.preload[i : i + 16]}) == len(plan.preload[i : i + 16])


def test_same_seed_same_frames_and_the_seed_moves_no_length():
    a, b, c = (_small(seed)[3] for seed in (7, 7, 8))
    ops = lambda p: p.preload + p.ops + [o for t in p.warm for o in t]
    assert [o.frame for o in ops(a)] == [o.frame for o in ops(b)]
    shape = lambda p: [(o.session, o.room, len(o.frame)) for o in ops(p)]
    assert shape(a) == shape(c) and a.session_rooms == c.session_rooms
    assert [o.frame for o in a.ops] != [o.frame for o in c.ops]
    assert [o.frame for o in a.preload] != [o.frame for o in c.preload]


def _synced_doc(client_id, room, prefill, stores):
    """A real client after its handshake: the room's text prefill and its
    loaded store."""
    from ytpu.core import Doc

    real = Doc(client_id=client_id)
    for u in prefill.for_room(room).stages:
        real.apply_update_v1(u)
    store = stores.for_room(room)
    for s in range(len(store.stage_sizes)):
        real.apply_update_v1(store.stage(s, room))
    assert real.get_array(rm.records_root(room)).to_json() == store.entries
    return real


def _replay(real, room, client, kinds, targets=None):
    """The model's changes made on the real client as `YKeyValue` makes them
    (`remove` the entry that holds the key, `push_back` the new one, one
    transaction); yields (the model's edit, the real client's update)."""
    arr = real.get_array(rm.records_root(room))
    sent = []
    real.observe_update_v1(lambda p, o, t: sent.append(p))
    for n, kind in enumerate(kinds):
        edit = client.next_change(kind, targets[n] if targets else None)
        kind_made, key = client.changes[-1]
        assert kind_made == kind
        with real.transact() as txn:
            held = [i for i, e in enumerate(arr.to_json()) if e["key"] == key]
            assert len(held) == (kind != "set_new")
            for i in held:
                arr.remove(txn, i)
            if kind != "delete":
                arr.push_back(txn, client.own[key][1])
        yield edit, sent[-1], real


def test_every_update_is_what_a_synced_client_sends():
    """All 40 sessions of a small plan, and a warm-up writer cut to chosen
    payload lengths: byte for byte, and state vector for state vector."""
    deploy, mix, prefill, plan = _small(3)
    stores = rm.Stores(deploy["records"], deploy["n_docs"], 3)
    kinds = rm._kinds(mix["store_changes"], 40 * 4)
    for s in plan.sessions[:40]:
        model = rm._client(s.client_id, s.room, stores, prefill, 3, "traffic", s.sid)
        real = _synced_doc(s.client_id, s.room, prefill, stores)
        for n, (edit, sent, real) in enumerate(_replay(real, s.room, model, kinds[s.sid * 4 : s.sid * 4 + 4])):
            assert edit.update == s.edits[n].update == sent, (s.sid, n)
            assert edit.sv_after == real.state_vector().encode_v1() == s.edits[n].sv_after
    room = 9
    model = rm._client(g.WARM_CLIENT_BASE, room, stores, prefill, 3, "warm", 0)
    real = _synced_doc(g.WARM_CLIENT_BASE, room, prefill, stores)
    kinds = ["set_existing", "set_new", "delete", "set_existing", "set_existing", "set_new"] * 4
    targets = [360 + 3 * n for n in range(len(kinds))]
    for n, (edit, sent, real) in enumerate(_replay(real, room, model, kinds, targets)):
        assert edit.update == sent, n
        assert kinds[n] == "delete" or len(edit.update) == targets[n]


def test_a_session_of_a_full_room_is_what_a_synced_client_sends(cell):
    deploy, mix, prefill, plan = cell
    stores = rm.Stores(deploy["records"], deploy["n_docs"], SEED)
    kinds = rm._kinds(mix["store_changes"], 2048 * 6)
    for s in (plan.sessions[0], next(s for s in plan.sessions if s.room == 0)):
        model = rm._client(s.client_id, s.room, stores, prefill, SEED, "traffic", s.sid)
        real = _synced_doc(s.client_id, s.room, prefill, stores)
        for n, (edit, sent, _) in enumerate(_replay(real, s.room, model, kinds[s.sid * 6 : s.sid * 6 + 6])):
            assert edit.update == s.edits[n].update == sent, (s.sid, n)


def test_every_room_stays_under_capacity(cell):
    """The worst case of the whole pool and the warm-up: a row for the text
    prefill's block, one for the array's anchor, one a record loaded and one
    a record pushed; a delete takes none. And the rule the older cells fill
    their rooms by: free rows at least twice the edits a room can get."""
    deploy, mix, prefill, plan = cell
    rows = [prefill.for_room(k).rows + 1 for k in range(deploy["n_docs"])]
    edits = [0] * deploy["n_docs"]
    for op in plan.preload:
        rows[op.room] += plan.sessions[op.session].edits[0].chars
    loaded = list(rows)
    for op in plan.ops + [o for t in plan.warm for o in t]:
        rows[op.room] += op.update[0] != 0
        edits[op.room] += 1
    assert max(rows) <= deploy["capacity"]
    assert all(deploy["capacity"] - loaded[k] >= 2 * edits[k] for k in range(deploy["n_docs"]))
    filled = sum(loaded) / (deploy["n_docs"] * deploy["capacity"])
    assert 0.06 <= filled <= 0.12  # the loaded records, the text block and the anchor: the clock's cut (`reduced`)


def test_the_grammars_count_is_the_oracles_state_vector():
    """`Edit.chars` (a stage's records, 1 a set, none a delete) summed as
    `benchmark/oracle.py` sums it is the state vector a `Doc` fed the room's
    updates holds, for every room."""
    from ytpu.core import Doc

    deploy, mix, prefill, plan = _small(5)
    expect = g.expected_clocks(plan.sessions, {s.sid: len(s.edits) for s in plan.sessions})
    for k in range(deploy["n_docs"]):
        doc = Doc(client_id=1)
        for u in prefill.for_room(k).stages + [op.update for op in plan.preload + plan.ops if op.room == k]:
            doc.apply_update_v1(u)
        want = dict(expect[k])
        want[prefill.for_room(k).client_id] = prefill.for_room(k).chars
        assert dict(doc.state_vector().clocks) == want, k
        assert not doc.store.pending  # every update found what it names: sessions are synced with the load


def test_the_warm_up_mirrors_every_make_up_of_the_window(cell):
    """The window's dispatches are known (a saturated pool is taken in ticks
    of exactly 16, one dispatch a depth); each distinct make-up (lanes,
    delete-only lanes, wire-byte bucket, longest-payload bucket) is one
    warm-up tick, from writers and rooms the traffic does not use."""
    deploy, mix, prefill, plan = cell
    window = {rm.make_up(d) for d in rm.dispatches(plan.ops, 16)}
    warm = [rm.make_up(t) for t in plan.warm]
    assert set(warm) == window and len(warm) == len(window)
    assert plan.notes["lane_counts"] == sorted({m[0] for m in window})
    assert plan.notes["fast_lane_counts"] == sorted({m[1] for m in window})
    assert plan.notes["needs_update_warm"] is False
    for tick in plan.warm:
        assert len({op.room for op in tick}) == len(tick)  # a tick is one dispatch
        assert all(op.room == plan.warm_session_rooms[op.session] for op in tick)
    writers = {g.WARM_CLIENT_BASE + w for w in range(16)}
    assert writers <= set(plan.clients) and not writers & {s.client_id for s in plan.sessions}


def _run(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, os.path.join(BENCH, "run.py"), *args],
                          capture_output=True, text=True, env=env, cwd=ROOT, timeout=900)


def test_the_rehearsal_ends_correct_with_no_program_built_inside_its_window():
    """`--mix record-flood` over the cell's own configuration: the same
    command path as the cell's, traced, so the four new readers run."""
    p = _run("--workload", CELL, "--mix", "record-flood", "--seed", "4000000017", "--seconds", "2", "--trace", "1",
             "--rehearse")
    assert p.returncode == 0, p.stderr[-2000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True and last["correct"] is True and last["failed"] == 0
    assert all(value == 0 and limit == 0 for value, limit in last["compared"].values()) and len(last["compared"]) == 12
    assert " 0 programs built inside the window" in p.stdout
    assert {"host_rows_ms.flood", "plan_h2d_ms.flood", "host_rows_per_step.flood", "nested_any_pct.flood",
            "fast_lane_pct.flood", "batch_reuse_pct.flood", "compact_step_pct.flood"} <= set(last["would_report"])
    assert "merge_ms.flood" not in last["would_report"]


def test_a_lost_store_change_is_not_correct():
    p = _run("--workload", CELL, "--seed", "12", "--seconds", "2", "--trace", "0", "--rehearse", "--break", "lose-update")
    assert p.returncode == 0, p.stderr[-2000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["correct"] is False and "FAILED" in p.stdout
    assert any(value > limit for value, limit in last["compared"].values())
