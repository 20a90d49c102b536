"""`generators/keystroke_mix.py`: the same seed gives the same frames and the
seed types other characters and moves nothing else; every update is byte for
byte what `ytpu.core.Doc` sends for the same transaction; the B4 flags file
is what a recount from the log gives; the warm-up makes one dispatch of
every family the window has; and the cell's rehearsal crosses capacity: a
room is compacted in the warm-up and in the window, and the run is correct."""

import hashlib
import json
import os
import subprocess
import sys

import pytest

from benchmark import grammar as g
from benchmark.generators import keystroke_mix as km

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL = "yws-rooms-1k-typed.keystroke-flood"


def _load(kind, name):
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


def _small(seed):
    deploy = dict(_load("configs", "yws-rooms-1k-typed"))
    deploy.update(deploy["rehearsal"])
    mix = dict(_load("traffic", "keystroke-flood"))
    mix.update(mix["rehearsal"])
    prefill = g.Prefill(deploy["prefill"], deploy["n_docs"], seed)
    return deploy, mix, prefill


def _digest(plan, shape_only=False):
    h = hashlib.sha256()
    for op in plan.ops + [o for tick in plan.warm for o in tick]:
        h.update(f"{op.kind}|{op.session}|{op.room}|{len(op.frame)}|".encode())
        if not shape_only:
            h.update(op.frame)
    return h.hexdigest()


def test_same_seed_same_frames_and_the_seed_moves_no_shape():
    plans = {}
    for seed in (7, 7, 4200000011):
        deploy, mix, prefill = _small(seed)
        plans.setdefault(seed, []).append(km.plan(deploy, mix, prefill, seed, 2.0))
    a, b = plans[7]
    c = plans[4200000011][0]
    assert _digest(a) == _digest(b)
    assert _digest(a) != _digest(c), "the seed types the characters"
    assert _digest(a, shape_only=True) == _digest(c, shape_only=True)
    assert [op.room for op in a.ops] == [op.room for op in c.ops]


def test_the_pool_is_the_traces_ops_in_each_sessions_order():
    deploy, mix, prefill = _small(3)
    plan = km.plan(deploy, mix, prefill, 3, 2.0)
    flags = km.load_flags()
    keys = mix["keystrokes_per_session"]
    assert len(plan.ops) == mix["sessions"] * keys
    seen = {}
    for op in plan.ops:
        j = seen.get(op.session, 0)
        assert op.update == plan.sessions[op.session].edits[j].update
        seen[op.session] = j + 1
    for s in plan.sessions:
        want = flags[keys * s.sid : keys * (s.sid + 1)]
        # an insert is an insert and a delete a delete, op by op
        assert [e.chars > 0 for e in s.edits] == [f in "cj" for f in want]


def test_every_update_is_what_a_synced_client_sends():
    """A real `Doc` synced with the room's prefill, played the same ops at
    the same visible positions, sends the same bytes, transaction by
    transaction: runs that go on, jumps, backspaces, deletes elsewhere."""
    from ytpu.core import Doc

    seed = 11
    _deploy, _mix, prefill = _small(seed)
    tpl = prefill.for_room(0)
    flags = km.load_flags()
    script = flags[:60] + "jccbccbbcdcjcdbcc" + flags[4000:4060]
    assert set(script) == set("cjbd")
    typist = km.KeyTypist(7001, g.rng(1, "t"), tpl.ids, {tpl.client_id: tpl.chars}, text=g.rng(seed, "x"))
    real = Doc(client_id=7001)
    for stage in tpl.stages:
        real.apply_update_v1(stage)
    sent = []
    real.observe_update_v1(lambda payload, *_: sent.append(payload))
    txt = real.get_text(g.ROOT)
    for flag in script:
        edit = typist.play(flag)
        op = typist.log[-1]
        with real.transact() as txn:
            if op[0] == "i":
                txt.insert(txn, op[1], op[2])
            else:
                txt.remove_range(txn, op[1], 1)
        assert sent.pop() == edit.update, (flag, op)
        assert real.state_vector().encode_v1() == edit.sv_after
    assert typist.length == len(txt.get_string())


def test_the_flags_file_is_a_recount_of_the_log():
    log = os.path.join(ROOT, "benches", "data", "b4_log.pkl.gz")
    if not os.path.exists(log):
        pytest.skip("the repo's copy of the B4 trace is not in this checkout")
    sys.path.insert(0, os.path.join(BENCH, "tools"))
    try:
        import b4_flags
    finally:
        sys.path.pop(0)
    have = km.load_flags()
    assert len(have) >= 1024 * 24 + 64 * 24 and set(have) == set("cjbd")
    n = 6000  # the recount decodes every update: the first 6,000 and the file's tail
    assert b4_flags.recount(n) == have[:n]
    first = have[:5000]
    assert first.count("c") + first.count("j") == 4236  # ISSUE 43's count of the same stretch


def test_the_warm_up_makes_every_family_of_the_window():
    deploy = _load("configs", "yws-rooms-1k-typed")
    mix = _load("traffic", "keystroke-flood")
    seed = 5
    prefill = g.Prefill(deploy["prefill"], deploy["n_docs"], seed)
    plan = km.plan(deploy, mix, prefill, seed, 30.0)
    tick = plan.tick_max_frames
    window = {km.family(p) for p in km.dispatches(plan.ops, tick)}
    warm = {km.family(p) for ops in plan.warm for p in km.dispatches(ops, tick)}
    assert window <= warm, sorted(window - warm)
    assert len(plan.ops) == 24576 and plan.notes["hot_room_sessions"] == 132
    # the fill crosses the reserve: more rows than the room has above it
    fill_ops = sum(len(t) for t in plan.warm if {o.room for o in t} == {deploy["n_docs"] - 1})
    assert fill_ops >= deploy["capacity"] - deploy["capacity"] // 16 - prefill.for_room(0).rows


def test_the_rehearsal_crosses_capacity_in_the_warm_up_and_in_the_window():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    code = (
        "import sys, json; sys.argv = ['run.py'] + %r\n"
        "sys.path.insert(0, %r)\n"
        "import benchmark.run as run\n"
        "from ytpu.utils import metrics\n"
        "from benchmark.serve import ServerLoop\n"
        "marks = {}\n"
        "orig = ServerLoop.open_window\n"
        "def open_window(self):\n"
        "    marks['warm'] = metrics.counter('ingest.room_compactions').value\n"
        "    return orig(self)\n"
        "ServerLoop.open_window = open_window\n"
        "rc = run.main()\n"
        "marks['all'] = metrics.counter('ingest.room_compactions').value\n"
        "marks['refusals'] = metrics.counter('ingest.capacity_refusals').value\n"
        "print('MARKS ' + json.dumps(marks))\n"
        "sys.exit(rc)\n"
    ) % (["--workload", CELL, "--seed", "4300000017", "--seconds", "2", "--trace", "1", "--rehearse"], ROOT)
    p = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, env=env, cwd=ROOT, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    marks = json.loads(next(ln for ln in lines if ln.startswith("MARKS "))[6:])
    last = json.loads(next(ln for ln in reversed(lines) if ln.startswith("{")))
    assert last["correct"] is True and last["failed"] == 0
    assert marks["warm"] >= 1, "a room is compacted in the warm-up: the program is built before the window"
    assert marks["all"] > marks["warm"], "and a room is compacted in the window"
    assert marks["refusals"] == 0
    assert " 0 programs built inside the window" in p.stdout
    for name in ("compactions_per_step.flood", "rows_reclaimed_per_compaction.flood", "compact_ms.flood"):
        assert name in last["would_report"]
