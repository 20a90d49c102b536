"""Who owns the served state (PR 46): `BatchIngestor` hands it to the
programs that replace it as a DONATED operand (the integrate step,
`apply_update_batch_in_place`, and `compact_rooms`), and counts them
(`_count_state_step`), so the tree handed in is consumed, on the CPU as on
the chip, and a step copies no plane it does not change. Every other operand of a step outlives it and is
handed to the next; the public `apply_update_batch` keeps value semantics
over the same traced body."""

import json
import os
import warnings

import jax
import numpy as np
import pytest

from test_ingest_fast_lane import _edit_log
from test_table_cache import _flag_lanes
from ytpu.core import Doc
from ytpu.models import ingest as ingest_mod
from ytpu.models.batch_doc import (
    BatchEncoder,
    apply_update_batch,
    apply_update_batch_in_place,
    get_string,
    init_state,
)
from ytpu.models.ingest import BatchIngestor
from ytpu.utils import metrics

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
needs_native = pytest.mark.usefixtures("native_lib")


def _consumed(tree) -> bool:
    return all(a.is_deleted() for a in jax.tree.leaves(tree))


def _readable(tree) -> bool:
    return not any(a.is_deleted() for a in jax.tree.leaves(tree))


class _Counts:
    """`ingest.state_steps` and `ingest.state_in_place` since it was made."""

    def __init__(self):
        self.steps = metrics.counter("ingest.state_steps")
        self.in_place = metrics.counter("ingest.state_in_place")
        self.was = (self.steps.value, self.in_place.value)

    def since(self):
        return self.steps.value - self.was[0], self.in_place.value - self.was[1]


def _typist(client_id: int):
    """A client whose every keystroke is one update, as it is sent."""
    doc, sent = Doc(client_id=client_id), []
    doc.observe_update_v1(lambda payload, *_: sent.append(payload))

    def key(ch: str) -> bytes:
        text = doc.get_text("text")
        with doc.transact() as txn:
            text.insert(txn, len(text.get_string()), ch)
        return sent.pop()

    return doc, key


@needs_native
def test_a_served_step_consumes_the_state_and_nothing_else(monkeypatch):
    """`apply_bytes`, a compact step (one room of 64) and a dense one (all
    four of 4): the tree handed in is deleted, the counts agree, jax says
    of no donated buffer that it was not usable, and the step's batch,
    the rank table, `active` and the kept batch are readable after it; a
    step that brings no new writer is handed the last step's table again."""
    handed = []
    real = ingest_mod.apply_update_batch_in_place

    def spy(state, batch, client_rank, active=None):
        out = real(state, batch, client_rank, active)
        handed.append((state, batch, client_rank, active))
        return out

    monkeypatch.setattr(ingest_mod, "apply_update_batch_in_place", spy)
    for n_docs, live in ((64, 1), (4, 4)):
        ing = BatchIngestor(n_docs=n_docs, capacity=64)
        typists = [_typist(7 + d) for d in range(live)]
        counts = _Counts()
        del handed[:]
        with warnings.catch_warnings():
            warnings.simplefilter("error")  # "Some donated buffers were not usable"
            for step in range(3):
                before = ing.state
                ing.apply_bytes([typists[d][1]("abc"[step]) if d < live else None for d in range(n_docs)])
                assert _consumed(before) and _readable(ing.state)
                assert handed[-1][0] is before
        assert counts.since() == (3, 3)
        assert (handed[0][3] is None) == (live == n_docs)  # dense where every room carries a payload
        for _, batch, client_rank, active in handed:
            assert _readable((batch, client_rank)) and (active is None or _readable(active))
        # steps 2 and 3 add no writer: the rank table of step 2 serves step 3
        assert handed[2][2] is handed[1][2]
        # the host lane's empty batch, kept where the state is large enough
        # to keep one (`_keep_batch`): the merge reads it, step after step
        kept = list(ing._batch_cache.values())
        assert _readable(kept) and (len(kept) == 1 or live == n_docs)
        for d in range(live):
            assert get_string(ing.state, d, ing.payloads) == "abc"


@needs_native
def test_a_served_compaction_consumes_the_state():
    """A room typed at its end until `_make_room` compacts it inside a
    served step: that step replaced the state twice (`compact_rooms`, then
    the integrate step), each time in place, and the room reads on."""
    ing = BatchIngestor(n_docs=4, capacity=64)
    doc, key = _typist(11)
    fired = metrics.counter("ingest.room_compactions")
    was = fired.value
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        for i in range(80):
            before, counts = ing.state, _Counts()
            ing.apply_bytes([key("x"), None, None, None])
            assert _consumed(before)
            if fired.value != was:
                assert counts.since() == (2, 2)
                break
            assert counts.since() == (1, 1)
        else:
            pytest.fail("the policy never compacted the room")
        # and called by hand, as the tests of the policy do
        before, counts = ing.state, _Counts()
        ing._compact([0])
    assert _consumed(before) and counts.since() == (1, 1)
    assert not np.asarray(ing.state.error).any()
    assert get_string(ing.state, 0, ing.payloads) == "x" * (i + 1)


@needs_native
def test_the_recovery_step_runs_on_the_new_state(monkeypatch):
    """A lane the device flags integrated nothing; `_recover_flagged`
    applies its follow-up step to the state the flagged step returned
    (the one before it is gone), and that step is in place too."""
    from ytpu.ops import decode_kernel as dk

    real = dk.decode_updates_v1
    hits = []

    def sabotage(*a, **kw):
        stream, flags = real(*a, **kw)
        if not hits:
            stream, flags = _flag_lanes(stream, flags)
        hits.append(1)
        return stream, flags

    monkeypatch.setattr(dk, "decode_updates_v1", sabotage)
    states = []
    real_step = ingest_mod.apply_update_batch_in_place

    def spy(state, *rest):
        states.append(state)
        out = real_step(state, *rest)
        states.append(out)
        return out

    monkeypatch.setattr(ingest_mod, "apply_update_batch_in_place", spy)
    log, expect = _edit_log([("i", 0, "hello"), ("i", 5, " world")])
    ing = BatchIngestor(n_docs=1, capacity=128)
    counts = _Counts()
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        ing.apply_bytes([log[0]])
    assert ing.fast_recoveries == 1 and counts.since() == (2, 2)
    first_in, first_out, second_in, second_out = states
    assert second_in is first_out and _consumed((first_in, first_out))
    assert ing.state is second_out and _readable(ing.state)
    ing.apply_bytes([log[1]])
    assert get_string(ing.state, 0, ing.payloads) == expect


def _one_update_a_room(n_docs: int, rooms):
    """A batch of `n_docs` rows in which the slots `rooms` insert a word."""
    enc = BatchEncoder()
    from ytpu.core.update import Update

    rows, dels = [[] for _ in range(n_docs)], [[] for _ in range(n_docs)]
    for d in rooms:
        doc = Doc(client_id=3 + d)
        with doc.transact() as txn:
            doc.get_text("text").insert(txn, 0, f"room {d}")
        r, ds = enc.rows_from_update(Update.decode_v1(doc.encode_state_as_update_v1()))
        rows[d], dels[d] = r, ds
    return enc, rows, dels


@pytest.mark.parametrize("form", ["dense", "compact"])
def test_the_public_entry_leaves_its_argument_readable(form):
    """`apply_update_batch(state, ...)`: `state` reads after the call as
    before it, and the result is byte for byte what the served form gives
    on the same inputs, which consumes the tree it is handed."""
    n_docs, rooms = 8, (1, 5)
    enc, rows, dels = _one_update_a_room(n_docs, rooms)
    if form == "dense":
        batch, active = enc.batch_packed(rows, dels), None
    else:
        batch = enc.batch_packed([rows[d] for d in rooms], [dels[d] for d in rooms])
        active = jax.numpy.asarray(rooms, dtype=jax.numpy.int32)
    batch = jax.tree.map(jax.numpy.asarray, batch)
    rank = enc.interner.rank_table()
    state = init_state(n_docs, 32)
    was = [np.asarray(a).copy() for a in jax.tree.leaves(state)]
    out = apply_update_batch(state, batch, rank, active)
    assert _readable(state)
    assert all(np.array_equal(a, b) for a, b in zip(was, jax.tree.leaves(state)))
    owned = init_state(n_docs, 32)
    served = apply_update_batch_in_place(owned, batch, rank, active)
    assert _consumed(owned) and _readable((batch, rank)) and (active is None or _readable(active))
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(served)):
        assert a.dtype == b.dtype and np.asarray(a).tobytes() == np.asarray(b).tobytes()
    for d in rooms:
        assert get_string(served, d, enc.payloads) == f"room {d}"


# --- the counter's reader --------------------------------------------------------------


def test_the_benchmark_reads_the_share_of_steps_in_place():
    """`state_in_place_pct.flood`: an entry of `per_layer` naming every
    cell; its reader divides the window's two counts, from the counter
    deltas or the phase recorder's copies, and has nothing to say of a
    program without the counters (the parent)."""
    from benchmark.run import applies, load_reader
    from benchmark.window import Window

    name = "state_in_place_pct.flood"
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = [w["name"] for w in bench["workloads"]]
    entry = next(m for m in bench["per_layer"] if m["name"] == name)  # later PRs append after it
    assert entry == {
        "name": name, "unit": "%", "better": "higher", "source": "program_counter",
        "layer": "device decode + integrate", "moves": "updates_per_s", "workloads": cells,
    }
    assert all(applies(entry, c, {"updates_per_s", "setup_s"}) for c in cells)
    read = load_reader("layers", name).read
    window = lambda **kw: Window(rec=None, t_open=0.0, t_close=30.0, setup_s=1.0,
                                 dispatch_spans=[(float(i), i + 0.5, 1) for i in range(10)], **kw)
    assert read(window(counters={"ingest.state_steps": 10, "ingest.state_in_place": 10})) == 100.0
    assert read(window(phases={"ingest.state_steps": {"value": 8.0}, "ingest.state_in_place": {"value": 6.0}})) == 75.0
    assert read(window(phases={"ingest.state_steps": {"value": 8.0}})) == 0.0  # the donation never engaged
    assert read(window()) is None and read(window(phases={"ingest.merge": {"calls": 10}})) is None
