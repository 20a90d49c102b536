"""`generators/coedit_mix.py` and `yata_plain.py`: the same seed gives the
same frames and the seed types other characters and moves nothing else; every
update is byte for byte what `ytpu.core.Doc` sends for the same transaction
on the same replica; every update but the early ones is ready given the
frames before it, and every early one within three of its room's frames;
every room stays under capacity, and under the line at which the server
would compact it, over the whole pool; at the real size `ytpu.core.Doc` fed
the pool equals the plain reference in the twelve hottest rooms; every step
the server plans of it stays in `edit-flood`'s `(16, 4, 4)` bucket and the
warm-up makes every family of the window; the plain reference reads the
stated tie-break cases; the four readers; the cell's rehearsal is correct and
stashes in the warm-up and in the window."""

import collections
import hashlib
import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

from benchmark import grammar as g
from benchmark import yata_plain as yp
from benchmark.generators import coedit_mix as cm

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL = "yws-rooms-1k-coedit.coedit-flood"
CONFIG, MIX = "yws-rooms-1k-coedit", "coedit-flood"


def _load(kind, name):
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


def _small(seed):
    deploy = dict(_load("configs", CONFIG))
    deploy.update(deploy["rehearsal"])
    mix = dict(_load("traffic", MIX))
    mix.update(mix["rehearsal"])
    return deploy, mix, g.Prefill(deploy["prefill"], deploy["n_docs"], seed)


@pytest.fixture(scope="module")
def real():
    """The cell as it is measured: 1,024 rooms, the pool of 12,288."""
    deploy, mix = _load("configs", CONFIG), _load("traffic", MIX)
    prefill = g.Prefill(deploy["prefill"], deploy["n_docs"], 5)
    return deploy, mix, prefill, cm.plan(deploy, mix, prefill, 5, 30.0)


def _digest(plan, shape_only=False):
    h = hashlib.sha256()
    for op in plan.ops + [o for tick in plan.warm for o in tick]:
        h.update(f"{op.kind}|{op.session}|{op.room}|{len(op.frame)}|".encode())
        if not shape_only:
            h.update(op.frame)
    return h.hexdigest()


def test_same_seed_same_frames_and_the_seed_moves_no_shape():
    plans = {}
    for seed in (7, 7, 4500000011):
        deploy, mix, prefill = _small(seed)
        plans.setdefault(seed, []).append(cm.plan(deploy, mix, prefill, seed, 2.0))
    a, b = plans[7]
    c = plans[4500000011][0]
    assert _digest(a) == _digest(b)
    assert _digest(a) != _digest(c), "the seed types the characters"
    assert _digest(a, shape_only=True) == _digest(c, shape_only=True)
    assert [op.room for op in a.ops] == [op.room for op in c.ops]
    assert a.notes["counts"] == c.notes["counts"] and a.notes["families"] == c.notes["families"]


def test_the_pool_keeps_every_sessions_own_order():
    deploy, mix, prefill = _small(3)
    plan = cm.plan(deploy, mix, prefill, 3, 2.0)
    assert len(plan.ops) == mix["sessions"] * mix["edits_per_session"]
    seen = {}
    for op in plan.ops:
        j = seen.get(op.session, 0)
        assert op.update == plan.sessions[op.session].edits[j].update
        seen[op.session] = j + 1


def test_every_update_is_what_a_client_holding_that_replica_sends():
    """A real `Doc` that has applied the prefill and exactly the updates the
    generator says the session's replica holds, given the same edit at the
    same visible position, sends the same bytes: inserts beside and inside
    other sessions' characters, deletes of them, the early ones on a twin's."""
    from ytpu.core import Doc

    seed = 45
    deploy, mix, prefill = _small(seed)
    made = cm.build(deploy["n_docs"], 32, 4, mix, seed, prefill, keep_log=True)
    assert made.counts["early"] >= 3 and made.counts["foreign_origin"] > 10 and made.counts["foreign_delete"] > 3
    checked = 0
    for (i, j), (op, held) in made.log.items():
        real = Doc(client_id=g.CLIENT_BASE + i)
        for stage in prefill.for_room(made.room_of[i]).stages:
            real.apply_update_v1(stage)
        for s, e in held:
            real.apply_update_v1(made.sessions[s].edits[e].update)
        assert real.store.pending is None and real.store.pending_ds is None, "a replica is causally closed"
        sent = []
        real.observe_update_v1(lambda payload, *_: sent.append(payload))
        txt = real.get_text(g.ROOT)
        with real.transact() as txn:
            if op[0] == "i":
                txt.insert(txn, op[1], op[2])
            else:
                txt.remove_range(txn, op[1], op[2])
        assert sent == [made.sessions[i].edits[j].update], ((i, j), op)
        checked += 1
    assert checked == 32 * 4


def _by_room(made):
    rooms = collections.defaultdict(list)
    for e in made.order:
        rooms[made.room_of[e[0]]].append(e)
    return rooms


def test_every_update_but_the_early_ones_is_ready_and_those_within_three_frames():
    seed = 9
    deploy, mix, prefill = _small(seed)
    made = cm.build(deploy["n_docs"], 40, 5, mix, seed, prefill)
    early = waited = 0
    for k, frames in _by_room(made).items():
        doc = yp.Text()
        for stage in prefill.for_room(k).stages:
            doc.apply_update(stage)
        held = {}  # frames that wait -> the place they came at
        for place, e in enumerate(frames):
            assert made.place[e] == place
            before = len(doc.waiting)
            doc.apply_update(made.sessions[e[0]].edits[e[1]].update)
            if len(doc.waiting) > before:  # it waits: an early one, by the generator's own word
                assert made.ready[e] > place and e[0] in made.twin
                held[e] = place
                early += 1
            else:
                assert made.ready[e] == place, e
            for b in [b for b in held if made.ready[b] == place]:
                assert 1 <= place - held.pop(b) <= 3
                waited += 1
            assert len(doc.waiting) == len(held)
        assert not doc.waiting and not held
        assert doc.text() == made.rooms[k].doc.text()  # as made and as handed over: one document
    assert early == waited == made.counts["early"] >= 4


def _rows(updates, tpl):
    """Rows the device holds once it has taken `updates` on top of the
    template: a row a block, one more wherever an origin, a right origin or
    a delete range cuts a row (the device squashes nothing by itself)."""
    starts = {(tpl.client_id, k) for k in tpl.row_clocks}
    state = {tpl.client_id: tpl.chars}
    for u in updates:
        blocks, deletes = yp.decode_update(u)
        for c, k, origin, right, text in blocks:
            starts.add((c, k))
            state[c] = max(state.get(c, 0), k + len(text))
            if origin:
                starts.add((origin[0], origin[1] + 1))
            if right:
                starts.add(right)
        for c, k, n in deletes:
            starts |= {(c, k), (c, k + n)}
    return sum(1 for c, k in starts if k < state.get(c, 0))


def test_every_room_stays_under_the_line_the_server_compacts_at(real):
    deploy, _mix, prefill, plan = real
    taken = collections.defaultdict(list)
    for op in [o for tick in plan.warm for o in tick] + plan.ops:
        taken[op.room].append(op.update)
    cap = deploy["capacity"]
    rows = {k: _rows(us, prefill.for_room(k)) for k, us in taken.items()}
    fullest = max(rows.values())
    # the harness's sweep adds a few dozen rows to sixteen cold rooms; the
    # server compacts a room above capacity - capacity / 16 rows
    assert fullest + 64 <= cap - cap // 16, fullest
    assert rows[0] < rows[4] == fullest  # the four hottest rooms' smaller class


def test_the_host_doc_and_the_plain_reference_agree_on_the_hottest_rooms(real):
    from ytpu.core import Doc

    _deploy, _mix, prefill, plan = real
    taken = collections.defaultdict(list)
    for op in plan.ops:
        taken[op.room].append(op.update)
    hottest = sorted(taken, key=lambda k: (-len(taken[k]), k))[:12]
    assert hottest == list(range(12)) and len(taken[0]) == 264 * 6
    for k in hottest:
        doc, plain = Doc(client_id=1), yp.Text()
        for u in list(prefill.for_room(k).stages) + taken[k]:
            doc.apply_update_v1(u)
            plain.apply_update(u)
        assert not plain.waiting and doc.store.pending is None and doc.store.pending_ds is None
        assert doc.get_text(g.ROOT).get_string() == plain.text(), k
        assert dict(doc.state_vector().clocks) == plain.state_vector(), k


def _planned(plan, prefill, n_rooms, ticks):
    """The `(width, n_rows, n_dels)` bucket of every step the server makes
    of `ticks`, and the updates it plans on the host by reason, from the
    ingestor's own planning (host side: no device program runs)."""
    from ytpu.core import Update
    from ytpu.models.ingest import BatchIngestor, _bucket
    from ytpu.native import decode_update_columns

    ing = BatchIngestor(n_rooms, 8)
    for c in plan.clients:
        ing.enc.interner.intern(c)
    for k in range(n_rooms):
        tpl = prefill.for_room(k)
        ing.svs[k].set_max(tpl.client_id, tpl.chars)
        ing.primary_roots[k] = g.ROOT
    buckets, slow = collections.Counter(), collections.Counter()
    for tick in ticks:
        fifo = collections.defaultdict(list)
        for op in tick:
            fifo[op.room].append(op.update)
        for depth in range(max(map(len, fifo.values()))):
            rows = dels = 1
            step = [(d, q[depth]) for d, q in fifo.items() if len(q) > depth]
            for d, p in step:
                cols = decode_update_columns(p)
                why = ing._slow_reason(d, cols)
                if why is None:
                    for i in range(cols.n_blocks):
                        ing.svs[d].set_max(int(cols.client[i]), int(cols.clock[i]) + int(cols.length[i]))
                    rows, dels = max(rows, cols.n_blocks), max(dels, cols.n_dels)
                else:
                    slow[why] += 1
                    r, dl = ing._plan_doc(d, Update.decode_v1(p))
                    rows, dels = max(rows, len(r)), max(dels, len(dl))
            buckets[_bucket(len(step), 16), _bucket(rows), _bucket(dels)] += 1
    assert not ing._stash_tickets and not any(ing._pending)
    return buckets, slow


def test_no_step_leaves_edit_floods_bucket_and_the_warm_up_makes_every_family(real):
    pytest.importorskip("jax")
    from ytpu import native

    if not native.available():
        pytest.skip("the native library did not build here")
    deploy, mix, prefill, plan = real
    tick = plan.tick_max_frames
    window = [plan.ops[i : i + tick] for i in range(0, len(plan.ops), tick)]
    buckets, slow = _planned(plan, prefill, deploy["n_docs"], window)
    # a step that releases a stash plans the waiting rows beside the arriving one's: still four at most
    assert dict(buckets) == {(16, 4, 4): 1584}
    notes = plan.notes
    assert sum(slow.values()) == notes["host_lane_updates"] == 1647 and set(slow) == {"dependency", "pending"}
    assert notes["counts"]["early"] == 598 and slow["dependency"] <= 598  # an early one in a room that holds a stash counts `pending`
    warm_buckets, warm_slow = _planned(plan, prefill, deploy["n_docs"], plan.warm)
    assert set(warm_buckets) == {(16, 4, 4)} and warm_slow["dependency"] >= 1 and warm_slow["pending"] >= 1
    # one tick of every family the window's fast lanes have
    made = cm.build(deploy["n_docs"], mix["sessions"], mix["edits_per_session"], mix, 5, prefill)
    host = made.host_lane()
    assert sum(host) == notes["host_lane_updates"]
    families = {cm.family(p) for p, _ in cm.dispatches(plan.ops, host, tick) if p}
    warm = {cm.family([op.update for op in ops]) for ops in plan.warm[-len(families):]}
    assert families == set(notes["families"]) == warm
    assert len(plan.ops) == 12288 and notes["hot_room_sessions"] == 264 and len(made.twin) == 400


# --- the plain reference against the stated tie-break cases ---------------------------

WRITERS = [41, 2**31 - 1, 2**31, 3_000_000_041, 2**32 - 1, 7, 123_456_789, 2_222_222_222]


@pytest.mark.parametrize("order", ["ascending", "descending", "shuffled"])
def test_the_plain_reference_reads_concurrent_appends_in_ascending_unsigned_client_id(order):
    """`tests/test_walkin_clients.py`'s case, stated on its own there: every
    writer appends behind the base's last character, none seeing another."""
    base = g.encode_update(900, [g.Block(0, None, None, "base:")], {})
    words = {c: f"<{c:x}>" for c in WRITERS}
    ups = {c: g.encode_update(c, [g.Block(0, (900, 4), None, words[c])], {}) for c in WRITERS}
    arrive = {"ascending": sorted(WRITERS), "descending": sorted(WRITERS, reverse=True),
              "shuffled": g.rng(45, "order").sample(WRITERS, len(WRITERS))}[order]
    doc = yp.Text()
    doc.apply_update(base)
    for c in arrive:
        doc.apply_update(ups[c])
    assert doc.text() == "base:" + "".join(words[c] for c in sorted(WRITERS))
    assert doc.state_vector() == {900: 5, **{c: len(words[c]) for c in WRITERS}}


def test_the_plain_reference_queues_what_is_not_ready_and_cuts_a_redelivery():
    doc = yp.Text()
    late = g.encode_update(11, [g.Block(3, (11, 2), None, "de")], {})
    first = g.encode_update(11, [g.Block(0, None, None, "abc")], {})
    gone = g.encode_update(12, [], {11: [(2, 3)]})
    doc.apply_update(late)
    doc.apply_update(gone)
    assert doc.text() == "" and len(doc.waiting) == 2
    doc.apply_update(first)
    assert doc.text() == "ab" and not doc.waiting and doc.state_vector() == {11: 5}
    doc.apply_update(first)  # a redelivery adds nothing
    doc.apply_update(g.encode_update(11, [g.Block(4, (11, 3), None, "eXY")], {}))  # held in part
    assert doc.text() == "abXY" and doc.state_vector() == {11: 7}
    with pytest.raises(ValueError):
        yp.decode_update(bytes([1, 1, 5, 0, 0x08, 1, 4]) + b"text" + bytes([1, 0, 0]))  # an Any value: not a text


# --- the readers ------------------------------------------------------------------


def _reader(name):
    sys.path.insert(0, BENCH)
    try:
        import run
    finally:
        sys.path.pop(0)
    return run.load_reader("layers", name)


def _window(phases, n_updates=200):
    return SimpleNamespace(phases=phases, indices=lambda kind: list(range(n_updates)) if kind == "update" else [])


def test_the_four_readers_read_the_recorders_copies_and_nothing_where_there_is_none():
    w = _window({
        "ingest.stash_updates": {"value": 10.0}, "ingest.stash_released": {"value": 8.0},
        "ingest.stash_wait_steps": {"value": 14.0}, "ingest.plan.stash": {"execute_s": 0.003, "calls": 30},
        "ingest.slow.dependency": {"value": 10.0}, "ingest.slow.pending": {"value": 18.0},
    })
    assert _reader("stash_pct.flood").read(w) == 5.0
    assert _reader("stash_wait_steps.flood").read(w) == 1.75
    assert _reader("stash_ms.flood").read(w) == pytest.approx(0.1)
    assert _reader("slow_dependency_pct.flood").read(w) == 14.0
    parent = _window({"ingest.slow.pending": {"value": 18.0}})  # a program without the stash's counters and span
    assert [_reader(n).read(parent) for n in ("stash_pct.flood", "stash_wait_steps.flood", "stash_ms.flood")] == [None] * 3
    assert _reader("slow_dependency_pct.flood").read(parent) == 9.0
    idle = _window({})
    assert all(_reader(n).read(idle) is None for n in
               ("stash_pct.flood", "stash_wait_steps.flood", "stash_ms.flood", "slow_dependency_pct.flood"))


def test_the_entries_name_the_cell_the_layer_and_what_the_cell_joins():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, MIX, 1)
    config = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert config["file"] == "benchmark/configs/yws-rooms-1k-coedit.json" and len(config["source"]) <= 200
    assert config["reduced"] == ["rows_per_room", "sessions"]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in ("stash_pct.flood", "stash_wait_steps.flood", "stash_ms.flood", "slow_dependency_pct.flood"):
        m = by_name[name]
        assert (m["layer"], m["moves"], m["workloads"]) == ("ingest planning", "updates_per_s", [CELL])
        assert os.path.exists(os.path.join(BENCH, "layers", name.rsplit(".", 1)[0] + ".py"))
    edit = "yws-rooms-1k.edit-flood"
    for m in bench["per_layer"] + bench["end_to_end"]:
        if edit in m.get("workloads", []):
            assert CELL in m["workloads"], m["name"]
    for name in ("host_rows_ms.flood", "plan_h2d_ms.flood", "host_rows_per_step.flood", "plan_h2d_kb.flood"):
        assert CELL in by_name[name]["workloads"]
    deploy, base = _load("configs", CONFIG), _load("configs", "yws-rooms-1k")
    for key in ("server", "n_docs", "capacity", "device_authoritative", "shard_docs", "chips", "replicas",
                "room_type", "zipf_s", "prefill"):
        assert deploy[key] == base[key], key
    assert {k: v for k, v in deploy["guarantees"].items() if k != "early_arrival"} == base["guarantees"]
    assert {"see_lag", "follow_share", "twin_share", "early_share", "early_by"} <= set(deploy["assumed"])


# --- the cell's rehearsal --------------------------------------------------------------


def test_the_rehearsal_stashes_in_the_warm_up_and_in_the_window():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    code = (
        "import sys, json; sys.argv = ['run.py'] + %r\n"
        "sys.path.insert(0, %r)\n"
        "import benchmark.run as run\n"
        "from ytpu.utils import metrics\n"
        "from benchmark.serve import ServerLoop\n"
        "names = ('ingest.stash_updates', 'ingest.stash_released', 'ingest.capacity_refusals', 'ingest.room_compactions')\n"
        "marks = {}\n"
        "orig = ServerLoop.open_window\n"
        "def open_window(self):\n"
        "    marks['warm'] = {n: metrics.counter(n).value for n in names}\n"
        "    return orig(self)\n"
        "ServerLoop.open_window = open_window\n"
        "rc = run.main()\n"
        "marks['all'] = {n: metrics.counter(n).value for n in names}\n"
        "print('MARKS ' + json.dumps(marks))\n"
        "sys.exit(rc)\n"
    ) % (["--workload", CELL, "--seed", "4500000017", "--seconds", "2", "--trace", "1", "--rehearse"], ROOT)
    p = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, env=env, cwd=ROOT, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    marks = json.loads(next(ln for ln in lines if ln.startswith("MARKS "))[6:])
    last = json.loads(next(ln for ln in reversed(lines) if ln.startswith("{")))
    assert last["correct"] is True and last["failed"] == 0 and last["compared"]["pending_slots"] == [0, 0]
    warm, end = marks["warm"], marks["all"]
    assert warm["ingest.stash_updates"] >= 1, "an update waits in the warm-up: the window's first is not the server's first"
    assert warm["ingest.stash_released"] == warm["ingest.stash_updates"]
    assert end["ingest.stash_updates"] - warm["ingest.stash_updates"] >= 2, "and in the window"
    assert end["ingest.stash_released"] == end["ingest.stash_updates"]
    assert end["ingest.capacity_refusals"] == end["ingest.room_compactions"] == 0
    assert " 0 programs built inside the window" in p.stdout
    for name in ("stash_pct.flood", "stash_wait_steps.flood", "stash_ms.flood", "slow_dependency_pct.flood",
                 "fast_lane_pct.flood", "host_rows_ms.flood"):
        assert name in last["would_report"]
