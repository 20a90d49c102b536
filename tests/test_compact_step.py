"""The compact integrate step (`apply_update_batch(..., active=...)`) against
the dense step, plane for plane, and against the host CRDT `ytpu.core.Doc`.

128 rooms x capacity 256, every room prefilled through `apply_bytes` (one
all-room dispatch: dense, 128 > 128 // 4). Then two steps in which only the
rooms of one active set carry an update: inserts, deletes and, in the same
step, one room on the host lane (its updates arrive out of order, so the
first waits in the stash and the second plans on the host). Every integrate
call of those steps runs twice: with `active` and the `[K, ...]` batch the
step was handed, and dense, over that batch laid out over every slot (row i
at slot `active[i]`, padding elsewhere): every plane must come out the same
bit for bit, and a room outside `active` keeps the planes it came in with.
"""

import random

import jax
import numpy as np
import pytest

from test_table_cache import _flag_lanes
from ytpu.core import Doc
from ytpu.models import ingest
from ytpu.models.batch_doc import (
    BatchEncoder,
    unpack_batch_jit,
    apply_update_batch,
    encode_diff_batch,
    finish_encode_diff,
    get_string,
)
from ytpu.models.ingest import BatchIngestor
from ytpu.utils import metrics

N_DOCS, CAPACITY = 128, 256
ROOT = "text"
COUNTERS = ("ingest.compact_steps", "ingest.dense_steps")

pytestmark = pytest.mark.usefixtures("native_lib")


class _Room:
    """A room's one writer, a real client `Doc`; every edit is the wire
    update it emits."""

    def __init__(self, d: int, r: random.Random):
        self.doc = Doc(client_id=1000 + d)
        self.text = self.doc.get_text(ROOT)
        self.sent = []
        self.doc.observe_update_v1(lambda p, o, t: self.sent.append(p))
        self.edit(r, deletes=False)

    def edit(self, r: random.Random, deletes: bool = True) -> bytes:
        """One transaction, one update: an insert and, over a long enough
        text, a delete beside it."""
        n = len(self.text.get_string())
        with self.doc.transact() as txn:
            word = "".join(r.choice("abcdefghij") for _ in range(r.randint(3, 8)))
            self.text.insert(txn, r.randint(0, n), word)
            if deletes and n > 6:
                self.text.remove_range(txn, r.randint(0, n - 4), r.randint(1, 3))
        return self.sent[-1]


def _prefilled(seed: int):
    r = random.Random(seed)
    rooms = [_Room(d, r) for d in range(N_DOCS)]
    ing = BatchIngestor(N_DOCS, CAPACITY)
    ing.apply_bytes([room.sent[0] for room in rooms])
    assert ing.fast_docs == N_DOCS
    return r, rooms, ing


def _planes(state):
    return [np.asarray(a) for a in jax.tree.leaves(state)]


@pytest.fixture
def both_steps(monkeypatch):
    """Every integrate call of `apply_bytes` run compact and dense: the
    widths it took (None: the caller chose the dense step itself)."""
    widths = []

    def checked(state, batch, client_rank, active=None):
        widths.append(None if active is None else int(active.shape[0]))
        if active is None:
            return apply_update_batch(state, batch, client_rank)
        slots = np.asarray(active)
        assert len(set(slots.tolist())) == len(slots), slots
        assert slots.min() >= 0 and slots.max() < N_DOCS, slots
        idle = np.setdiff1d(np.arange(N_DOCS), slots)
        # the batch is as wide as the step (the host lane's packed pair, or
        # the merge's planes); over every slot it is padding outside `active`
        batch = unpack_batch_jit(batch)
        assert {a.shape[0] for a in batch} == {len(slots)}
        empty = [[]] * N_DOCS
        padding = BatchEncoder().batch_from_rows(
            empty, empty, batch.client.shape[1], batch.del_client.shape[1]
        )
        over_all = jax.tree.map(lambda pad, sub: pad.at[slots].set(sub), padding, batch)
        dense = apply_update_batch(state, over_all, client_rank)
        compact = apply_update_batch(state, batch, client_rank, active)
        for was, got, want in zip(_planes(state), _planes(compact), _planes(dense)):
            assert got.dtype == want.dtype and np.array_equal(got, want)
            assert np.array_equal(got[idle], was[idle])
        return compact

    monkeypatch.setattr(ingest, "apply_update_batch_in_place", checked)
    return widths


def _check_against_oracle(ing, rooms, which):
    """Text, state vector and the full-state diff of the rooms `which`,
    read back from the device state, against each room's own `Doc`."""
    n_clients = 1
    while n_clients < len(ing.enc.interner):
        n_clients *= 2
    ship, offsets, local_sv, deleted = jax.tree.map(
        np.asarray,
        encode_diff_batch(ing.state, np.zeros((N_DOCS, n_clients), np.int32), n_clients),
    )
    assert not np.asarray(ing.state.error).any()
    for d in which:
        want = rooms[d].doc
        assert get_string(ing.state, d, ing.payloads) == want.get_text(ROOT).get_string(), d
        sv = {
            ing.enc.interner.from_idx[c]: int(k)
            for c, k in enumerate(local_sv[d][: len(ing.enc.interner)])
            if k
        }
        assert sv == dict(want.state_vector().clocks) == dict(ing.svs[d].clocks), d
        diff = finish_encode_diff(
            ing.state, d, ship, offsets, deleted, ing.enc,
            payloads=ing.payloads, root_name=ROOT,
        )
        fresh, twin = Doc(client_id=2), Doc(client_id=3)
        fresh.apply_update_v1(diff)
        twin.apply_update_v1(want.encode_state_as_update_v1())
        assert fresh.get_text(ROOT).get_string() == want.get_text(ROOT).get_string(), d
        assert fresh.encode_state_as_update_v1() == twin.encode_state_as_update_v1(), d


# active sets: 1, 5 and 16 rooms take the 16-wide step, 17 the 32-wide one,
# 33 are over a quarter of the slots (dense); `last` fills the top slots, so
# the padding has to find its idle slots below them
SPREAD = [3, 127, 64, 31, 96]
ACTIVE_SETS = {
    "one": ([77], 16),
    "five": (SPREAD, 16),
    "sixteen": (list(range(5, 128, 8)), 16),
    "seventeen": (list(range(5, 128, 8)) + [0], 32),
    "thirty_three": (list(range(1, 128, 4)) + [2], None),
    "last": (list(range(118, 128)), 16),
}


@pytest.mark.parametrize("name", list(ACTIVE_SETS))
def test_compact_step_equals_the_dense_step_and_the_oracle(both_steps, name):
    active, width = ACTIVE_SETS[name]
    r, rooms, ing = _prefilled(29_000_000 + len(active))
    before = {n: metrics.counter(n).value for n in COUNTERS}
    fast, slow = ing.fast_docs, ing.slow_docs
    del both_steps[:]
    # the last room of the set sends its second update first
    late = rooms[active[-1]] if len(active) > 1 else None
    first = {d: rooms[d].edit(r) for d in active}
    second = {d: rooms[d].edit(r) for d in active}
    if late is not None:
        first[active[-1]], second[active[-1]] = second[active[-1]], first[active[-1]]
    for step in (first, second):
        ing.apply_bytes([step.get(d) for d in range(N_DOCS)])
    assert both_steps == [width, width]
    took = {n: metrics.counter(n).value - before[n] for n in COUNTERS}
    assert took == {
        "ingest.compact_steps": 0 if width is None else 2,
        "ingest.dense_steps": 2 if width is None else 0,
    }
    if late is not None:  # both lanes rode the same steps
        assert ing.slow_docs - slow == 2 and ing.fast_docs - fast == 2 * (len(active) - 1)
    assert ing.fast_recoveries == 0
    assert not [d for d in range(N_DOCS) if ing.pending_update(d) or ing.pending_ds(d)]
    idle = [d for d in (0, 1, 63, 126) if d not in active]
    _check_against_oracle(ing, rooms, active + idle)


def test_a_recovery_step_is_compact_too(both_steps, monkeypatch):
    """The device flags two lanes the host pre-scan had passed: their rooms
    replay through the host lane in a follow-up step, as wide as they are."""
    from ytpu.ops import decode_kernel as dk

    r, rooms, ing = _prefilled(29_000_101)
    real = dk.decode_updates_v1
    bad_lanes = np.zeros(len(SPREAD), bool)
    bad_lanes[[1, 3]] = True

    def sabotage(buf, lens, max_rows, max_dels, **kw):
        return _flag_lanes(*real(buf, lens, max_rows, max_dels, **kw), bad_lanes)

    monkeypatch.setattr(dk, "decode_updates_v1", sabotage)
    del both_steps[:]
    step = {d: rooms[d].edit(r) for d in SPREAD}
    ing.apply_bytes([step.get(d) for d in range(N_DOCS)])
    assert ing.fast_recoveries == 2
    assert both_steps == [16, 16]  # the step, then the recovery of two rooms
    _check_against_oracle(ing, rooms, SPREAD + [0, 126])


@pytest.mark.parametrize(
    "n_docs,live,width",
    [
        (128, [], 16),
        (128, [127], 16),
        (128, list(range(16)), 16),
        (128, list(range(111, 128)), 32),
        (128, list(range(32)), 32),
        (128, list(range(33)), None),
        (64, [5], 16),
        (63, [5], None),  # 16 > 63 // 4
        (16, [0], None),
        (1024, list(range(1000, 1016)), 16),
    ],
)
def test_active_slots_are_distinct_in_range_and_a_power_of_two_wide(n_docs, live, width):
    ing = BatchIngestor.__new__(BatchIngestor)  # `_active_slots` reads `n_docs` alone
    ing.n_docs = n_docs
    active = ing._active_slots(live)
    if width is None:
        assert active is None
        return
    assert active.dtype == np.int32 and active.shape == (width,)
    assert np.array_equal(active, np.unique(active))  # sorted, distinct
    assert 0 <= active[0] and active[-1] < n_docs
    assert set(live) <= set(active.tolist())
