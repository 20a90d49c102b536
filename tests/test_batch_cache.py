"""The host lane's empty batch stays on the device (ISSUE-37).

A step none of whose payloads takes the host lane plans no row: its 27
planes are `batch_packed`'s padding, a constant of `(width, n_rows, n_dels)`
(the width is `n_docs` here: four rooms make every step dense; the compact
width is `tests/test_step_width_batch.py`'s), so `apply_bytes` hands
`merge_stream` the device arrays an earlier step of the bucket uploaded:
since PR 40 the two arrays of a `PackedBatch`, and since PR 42 a
`PackedBatch` is what crosses every program boundary of the step: the served
decoder's output, the merge's, the integrate program's operand, which takes
the planes apart inside itself (`unpack_batch`; `_wrong_leaves` does the
same to compare). What changes is when the planes are built and uploaded
and how many buffers carry them, never what a program works on: every
step's batch is compared, leaf for leaf and bit for bit, with what the step
used to build (`_parent_planes` and `_parent_merged` below are that code),
over a served sequence in which a stashed out-of-order update, changes of
bucket, an eviction by the byte bound, a bucket too large to keep and a
flagged lane arrive at chosen steps.
"""

import jax
import numpy as np
import pytest

from test_table_cache import _Room, _cut, _flag_lanes, _replayed, _type
from ytpu.core import Doc
from ytpu.models import ingest as ingest_mod
from ytpu.models.batch_doc import PackedBatch, UpdateBatch, unpack_batch_jit, get_string
from ytpu.models.ingest import BatchIngestor
from ytpu.native import decode_update_columns
from ytpu.ops import decode_kernel as dk
from ytpu.utils import metrics

pytestmark = pytest.mark.usefixtures("native_lib")

COUNTERS = ("ingest.batch_builds", "ingest.batch_reuses")
N_DOCS, CAPACITY = 4, 256
N_PLANES = len(UpdateBatch._fields)


# --- what the parent built, every step -----------------------------------------


def _bucket(n, lo=4):
    b = lo
    while b < n:
        b *= 2
    return b


def _parent_planes(all_rows, all_dels, n_rows, n_dels):
    """`BatchEncoder.batch_planes` as the parent (of PR 40) had it: 27 host
    planes over every slot, the planes' one source of truth then; what
    `batch_packed` and `unpack_batch` must still hand the programs."""
    D = len(all_rows)
    rows = np.zeros((D, n_rows, 22), dtype=np.int32)
    for col in (10, 12, 14, 15, 18, 21):
        rows[:, :, col] = -1
    rows_valid = np.zeros((D, n_rows), dtype=bool)
    for d, doc_rows in enumerate(all_rows):
        for i, row in enumerate(doc_rows):
            rows[d, i] = row
            rows_valid[d, i] = True
    dels = np.zeros((D, n_dels, 3), dtype=np.int32)
    dels_valid = np.zeros((D, n_dels), dtype=bool)
    for d, doc_dels in enumerate(all_dels):
        for i, de in enumerate(doc_dels):
            dels[d, i] = de
            dels_valid[d, i] = True
    return (
        [rows[:, :, i] for i in range(22)] + [rows_valid]
        + [dels[:, :, i] for i in range(3)] + [dels_valid]
    )


def _parent_merged(want, stream, idx, prefix, base, width):
    """`merge_stream` as the parent had it, in numpy over the 27 planes:
    the string refs rebased onto the retained chunk, then `full[idx] = fast`
    plane by plane. `want` is the host lane's planes (`_parent_planes`),
    `stream` the decoder's, as planes."""
    ref, valid = UpdateBatch._fields.index("content_ref"), UpdateBatch._fields.index("valid")
    full, fast = [np.array(p) for p in want], [np.asarray(p) for p in stream]
    lane = np.arange(len(idx), dtype=np.int32)[:, None]
    compact = np.asarray(prefix, np.int32)[:, None] + (fast[ref] - lane * np.int32(width))
    fast[ref] = np.where(fast[valid] & (fast[ref] >= 0), np.int32(-2 - int(base)) - compact, fast[ref])
    for f, s in zip(full, fast):
        f[np.asarray(idx)] = s
    return full


def _entry_bytes(n_docs, n_rows, n_dels):
    """A kept batch: `[n_docs, n_rows, 23]` and `[n_docs, n_dels, 4]`, int32."""
    return 4 * n_docs * (23 * n_rows + 4 * n_dels)


def _bound(ing):
    return sum(a.nbytes for a in jax.tree.leaves(ing.state)) // 16


def _wrong_leaves(handed, want):
    """Indices of the planes of `handed` (device; a `PackedBatch` taken apart
    as the programs take it apart) that are not `want`'s (host), bit for bit."""
    handed = unpack_batch_jit(handed)
    assert len(handed) == len(want) == N_PLANES
    return [
        i for i, (h, w) in enumerate(zip(handed, want))
        if not (h.dtype == w.dtype and h.shape == w.shape and np.asarray(h).tobytes() == w.tobytes())
    ]


class _Spy:
    """The batch every program was handed, call by call, beside what the
    parent's `apply_bytes` would have built from the same step: its walk
    called `_plan_doc` for every slot (a slot without a host-lane update
    plans nothing), sized the planes by the widest of both lanes, padded
    and uploaded."""

    def __init__(self, monkeypatch, ing, flag_decode=None):
        self.ing = ing
        self.merged = []  # per `merge_stream` call: the batch it was handed
        self.merge_args = []  # and the rest: (stream, idx, prefix, base), its keywords
        self.applied = []  # per `apply_update_batch` call: the batch it was handed
        self.active = []  # and its `active` (None: the dense step)
        self.plan_calls = []  # this step: the slots `_plan_doc` was called for
        self.planned = {}  # this step: slot -> (rows, dels) its host lane planned
        self.replanned = {}  # a recovery's follow-up step: slot -> (rows, dels)
        self.decodes = 0
        self.recovering = False
        real_merge, real_apply = ingest_mod._merge_stream_jit, ingest_mod.apply_update_batch_in_place
        real_decode, real_plan, real_recover = dk.decode_updates_v1, ing._plan_doc, ing._recover_flagged

        def merge(batch, stream, table, **kw):
            self.merged.append(batch)
            # the lanes' operands are rows of the lane table, a device array
            # the step's first program made: nothing rides up with the call
            assert isinstance(table, jax.Array)
            t = np.asarray(table)
            self.merge_args.append(((stream, t[dk.LANE_AT], t[dk.LANE_PREFIX], t[dk.LANE_BASE, 0]), kw))
            return real_merge(batch, stream, table, **kw)

        def apply(state, batch, *rest):
            self.applied.append(batch)
            self.active.append(rest[1] if len(rest) > 1 else None)
            return real_apply(state, batch, *rest)

        def plan(doc, incoming):
            got = real_plan(doc, incoming)
            if not self.recovering:
                self.plan_calls.append(doc)
            if incoming is not None:
                (self.replanned if self.recovering else self.planned)[doc] = got
            return got

        def recover(*a):  # the follow-up step plans its own rows: not the step's
            self.recovering = True
            try:
                return real_recover(*a)
            finally:
                self.recovering = False

        def decode(*a, **kw):
            stream, flags = real_decode(*a, **kw)
            self.decodes += 1
            if self.decodes == flag_decode:  # the device flags every lane of this call
                stream, flags = _flag_lanes(stream, flags)
            return stream, flags

        monkeypatch.setattr(ingest_mod, "_merge_stream_jit", merge)
        monkeypatch.setattr(ingest_mod, "apply_update_batch_in_place", apply)
        monkeypatch.setattr(dk, "decode_updates_v1", decode)
        monkeypatch.setattr(ing, "_plan_doc", plan)
        monkeypatch.setattr(ing, "_recover_flagged", recover)

    def parent_planes(self, payloads):
        """What the parent built for the step that has just ended."""
        planned, self.planned = self.planned, {}
        fast_rows = fast_dels = 0
        for d, p in enumerate(payloads):
            if p is None or d in planned:
                continue
            cols = decode_update_columns(p)
            fast_rows = max(fast_rows, sum(
                1 for i in range(cols.n_blocks) if int(cols.kind[i]) != 10 and int(cols.length[i]) > 0
            ))
            fast_dels = max(fast_dels, cols.n_dels)
        all_rows = [planned.get(d, ([], []))[0] for d in range(len(payloads))]
        all_dels = [planned.get(d, ([], []))[1] for d in range(len(payloads))]
        n_rows = _bucket(max(fast_rows, 1, max(len(r) for r in all_rows)))
        n_dels = _bucket(max(fast_dels, 1, max(len(d) for d in all_dels)))
        bucket = (len(payloads), n_rows, n_dels)
        return bucket, _parent_planes(all_rows, all_dels, n_rows, n_dels), sorted(planned)


def _counts() -> dict:
    return {n: metrics.counter(n).value for n in COUNTERS}


def _counted(before: dict) -> dict:
    return {n: v - before[n] for n, v in _counts().items()}


def _many(n):
    """`n` inserts at the head of the text in one transaction: `n` blocks."""
    def fn(doc, txn):
        for i in range(n):
            doc.get_text("text").insert(txn, 0, "abcdefghijklmnopq"[i])

    return fn


def _cuts(n):
    """`n` deletes of one character, none next to another: `n` ranges."""
    def fn(doc, txn):
        for i in range(n):
            doc.get_text("text").remove_range(txn, 2 * i, 1)

    return fn


# step -> what room 0 receives; every other step client 1 types a word there.
# Room 1 takes one plain insert a step from client 50 (so every step has a
# fast lane, whichever lane room 0's update takes); rooms 2 and 3 are idle.
LATE, EARLY = "the second edit of client 2", "its first"
ROOM0 = {
    3: LATE,  # comes before the edit it follows: stashed, the host lane plans nothing yet
    4: EARLY,  # the gap closes: the host lane plans both
    6: (1, _many(5)),  # 5 rows: the (8, 4) bucket
    7: (1, _many(6)),
    8: (1, _type("abcdefghijklmnop ")),  # one block, for step 9 to cut into
    9: (1, _cuts(5)),  # 5 delete ranges: the (4, 8) bucket; (8, 4), least recently used, goes
    11: (1, _many(7)),  # (8, 4) again, built again; (4, 8) goes
    12: (1, _many(17)),  # the (32, 4) bucket: passes the bound alone
    13: (1, _many(17)),
    # step 15: the device flags both lanes (`FLAGGED_DECODE`); one follow-up step on the host lane
}
N_STEPS = 18
FLAGGED_STEP = 15
# step -> (the bucket, built or reused, the buckets kept after it, least recently used first)
A, B, C, BIG = ((N_DOCS,) + k for k in ((4, 4), (8, 4), (4, 8), (32, 4)))
WANT = {
    0: (A, "build", [A]),
    1: (A, "reuse", [A]),
    2: (A, "reuse", [A]),
    3: (A, "build", [A]),  # a host-lane room: today's path, and keeps nothing
    4: (A, "build", [A]),
    5: (A, "reuse", [A]),
    6: (B, "build", [A, B]),
    7: (B, "reuse", [A, B]),
    8: (A, "reuse", [B, A]),
    9: (C, "build", [A, C]),
    10: (A, "reuse", [C, A]),
    11: (B, "build", [A, B]),
    12: (BIG, "build", [A, B]),
    13: (BIG, "build", [A, B]),
    14: (A, "reuse", [B, A]),
    15: (A, "reuse", [B, A]),
    16: (A, "reuse", [B, A]),
    17: (A, "reuse", [B, A]),
}


def test_the_sequence_meets_the_bound_where_it_says():
    bound = _bound(BatchIngestor(n_docs=N_DOCS, capacity=CAPACITY))
    a, b, c, big = (_entry_bytes(*k) for k in (A, B, C, BIG))
    assert a + b <= bound < a + b + c  # step 9 evicts
    assert a + c <= bound < a + c + b  # step 11 evicts
    assert a + b <= bound < big  # steps 12 and 13 keep nothing


@pytest.fixture(scope="module")
def served():
    """The sequence served once; what every step handed its programs."""
    monkeypatch = pytest.MonkeyPatch()
    rooms = [_Room() for _ in range(N_DOCS)]
    ing = BatchIngestor(n_docs=N_DOCS, capacity=CAPACITY)
    steps = []
    try:
        # step 0's decode is call 1; the recovery's follow-up step decodes nothing
        spy = _Spy(monkeypatch, ing, flag_decode=FLAGGED_STEP + 1)
        late = early = None
        for step in range(N_STEPS):
            got = ROOM0.get(step, (1, _type(f"w{step} ")))
            if got is LATE:
                early = rooms[0].edit(2, _type("two "))
                late = rooms[0].edit(2, _type("more ", 2))
            to_room0 = late if got is LATE else early if got is EARLY else rooms[0].edit(*got)
            payloads = [to_room0, rooms[1].edit(50, _type(f"x{step}")), None, None]
            merged, applied, before = len(spy.merged), len(spy.applied), _counts()
            recoveries = ing.fast_recoveries
            ing.apply_bytes(payloads)
            bucket, want, host_lane = spy.parent_planes(payloads)
            steps.append(
                dict(
                    merged=spy.merged[merged:],
                    merge_args=spy.merge_args[merged:],
                    applied=spy.applied[applied:],
                    replanned=dict(spy.replanned),
                    counted=_counted(before),
                    bucket=bucket,
                    want=want,
                    host_lane=host_lane,
                    recovered=ing.fast_recoveries - recoveries,
                    kept=list(ing._batch_cache),
                    kept_arrays=dict(ing._batch_cache),
                )
            )
    finally:
        monkeypatch.undo()
    return ing, rooms, steps


@pytest.mark.parametrize("step", range(N_STEPS))
def test_every_step_hands_merge_stream_the_parents_batch(served, step):
    _, _, steps = served
    s = steps[step]
    assert s["bucket"] == WANT[step][0]
    assert len(s["merged"]) == 1  # one fast lane a step, at least room 1's
    assert _wrong_leaves(s["merged"][0], s["want"]) == []
    # the host lane planned where the sequence says, and had rows to carry once
    assert s["host_lane"] == ([0] if step in (3, 4) else [])
    assert bool(np.asarray(unpack_batch_jit(s["merged"][0]).valid).any()) == (step == 4)


@pytest.mark.parametrize("step", range(N_STEPS))
def test_every_step_integrates_the_parents_merged_planes(served, step):
    """What the merge hands the integrate call, two buffers, taken apart as
    the program takes it apart: the parent's 27 planes, bit for bit (the
    flagged step's too: its lanes' rows come invalid out of the decoder)."""
    _, _, steps = served
    s = steps[step]
    ((stream, idx, prefix, base), kw), = s["merge_args"]
    assert all(type(b) is PackedBatch for b in (s["merged"][0], stream, s["applied"][0]))
    want = _parent_merged(s["want"], unpack_batch_jit(stream), idx, prefix, base, kw["width"])
    assert _wrong_leaves(s["applied"][0], want) == []
    assert np.asarray(idx).tolist() == ([1] if step in (3, 4) else [0, 1])  # the fast lanes' rows of the step


def test_a_reuse_hands_over_the_arrays_of_the_last_build(served):
    _, _, steps = served
    built = {}  # bucket -> the batch of its last all-fast-lane build
    for step, s in enumerate(steps):
        bucket, what, _ = WANT[step]
        handed = s["merged"][0]
        if what == "reuse":
            assert all(h is b for h, b in zip(handed, built[bucket])), step
        else:
            earlier = {id(a) for batch in built.values() for a in batch}
            assert not earlier & {id(h) for h in handed}, step
            if not s["host_lane"]:
                built[bucket] = handed
    # step 5 was handed step 0's arrays: the two host-lane steps between kept nothing
    assert all(h is b for h, b in zip(steps[5]["merged"][0], steps[0]["merged"][0]))


def test_the_counters_count_what_happened(served):
    _, _, steps = served
    for step, s in enumerate(steps):
        built = WANT[step][1] == "build"
        assert s["counted"] == {
            "ingest.batch_builds": int(built), "ingest.batch_reuses": int(not built),
        }, step


def test_the_kept_bytes_never_pass_the_bound(served):
    ing, _, steps = served
    bound = _bound(ing)
    for step, s in enumerate(steps):
        assert s["kept"] == WANT[step][2], step
        kept_bytes = sum(a.nbytes for batch in s["kept_arrays"].values() for a in batch)
        assert kept_bytes == sum(_entry_bytes(*k) for k in s["kept"]) <= bound, step
        for bucket, batch in s["kept_arrays"].items():
            empty = [[]] * N_DOCS
            assert _wrong_leaves(batch, _parent_planes(empty, empty, *bucket[1:])) == [], (step, bucket)
    assert _entry_bytes(*BIG) > bound  # and the one that was never kept could not be


def test_the_flagged_lanes_recover_through_a_batch_of_their_own(served):
    """The follow-up step of a flagged lane carries host-lane rows by
    definition: built, uploaded and handed to the integrate program, it
    neither reads the kept batches nor is counted as a step's."""
    ing, _, steps = served
    s = steps[FLAGGED_STEP]
    assert s["recovered"] == 2 and ing.fast_recoveries == 2
    assert [st["recovered"] for st in steps].count(0) == N_STEPS - 1
    assert len(s["applied"]) == 2  # the step's own integrate call, then the recovery's
    recovery = s["applied"][1]
    assert type(recovery) is PackedBatch  # the pair goes straight in: the integrate program's one form
    rows, dels = ([s["replanned"].get(d, ([], []))[i] for d in range(N_DOCS)] for i in (0, 1))
    assert sorted(s["replanned"]) == [0, 1]
    widest = lambda per_doc: max(1, max(len(entries) for entries in per_doc))  # unbucketed, as `_batch` pads
    assert _wrong_leaves(recovery, _parent_planes(rows, dels, widest(rows), widest(dels))) == []
    assert np.asarray(unpack_batch_jit(recovery).valid).any(axis=1).tolist() == [True, True, False, False]
    kept = [a for batch in s["kept_arrays"].values() for a in batch]
    assert all(leaf is not k for leaf in recovery for k in kept)
    assert sum(s["counted"].values()) == 1
    for other in steps[:FLAGGED_STEP] + steps[FLAGGED_STEP + 1 :]:
        assert len(other["applied"]) == 1


def test_the_served_rooms_equal_the_oracle(served):
    import jax.numpy as jnp

    from ytpu.models.batch_doc import encode_diff_batch, finish_encode_diff_batch

    ing, rooms, _ = served
    assert not np.asarray(ing.state.error).any()
    assert (ing.slow_docs, ing.fast_docs) == (2, 2 * N_STEPS - 2)
    want = [room.oracle() for room in rooms]
    for d, doc in enumerate(want):
        assert get_string(ing.state, d, ing.payloads) == doc.get_text("text").get_string(), d
        assert dict(ing.svs[d].clocks) == dict(doc.state_vector().clocks), d
    assert len(want[0].get_text("text").get_string()) > 60
    # the full diff, off the device: a fresh replica ends where the oracle is
    C_ = max(8, len(ing.enc.interner))
    ship, offsets, _sv, deleted = encode_diff_batch(
        ing.state, jnp.zeros((N_DOCS, C_), dtype=jnp.int32), C_
    )
    diffs = finish_encode_diff_batch(
        ing.state, list(range(N_DOCS)), ship, offsets, deleted, ing.enc,
        payloads=ing.payloads, root_name="text",
    )
    for diff, doc in zip(diffs, want):
        fresh = Doc(client_id=77)
        fresh.apply_update_v1(diff)
        assert fresh.get_text("text").get_string() == doc.get_text("text").get_string()
        assert dict(fresh.state_vector().clocks) == dict(doc.state_vector().clocks)
        assert fresh.encode_state_as_update_v1() == _replayed(doc).encode_state_as_update_v1()


def test_apply_builds_its_batch_and_keeps_nothing(monkeypatch):
    """`apply()` plans every slot on the host: it goes through `_batch` as
    before, counts no step of `apply_bytes` and leaves the kept batches alone."""
    room = _Room()
    ing = BatchIngestor(n_docs=1, capacity=CAPACITY)
    A = (1, 4, 4)
    spy = _Spy(monkeypatch, ing)
    ing.apply_bytes([room.edit(1, _type("one "))])
    kept = dict(ing._batch_cache)
    before = _counts()
    ing.apply([room.edit(1, _type("two "))])
    assert _counted(before) == {"ingest.batch_builds": 0, "ingest.batch_reuses": 0}
    assert list(ing._batch_cache) == [A] and ing._batch_cache[A] is kept[A]
    assert all(leaf is not k for leaf in spy.applied[-1] for k in kept[A])
    assert np.asarray(unpack_batch_jit(spy.applied[-1]).valid).any()
    ing.apply_bytes([room.edit(1, _type("three "))])
    assert all(h is k for h, k in zip(spy.merged[-1], kept[A]))
    assert get_string(ing.state, 0, ing.payloads) == room.oracle().get_text("text").get_string()


def test_a_step_without_a_payload_takes_the_kept_batch(monkeypatch):
    """No payload at all is no host-lane room either: the integrate program
    is handed the bucket's kept batch, and the state stays as it was."""
    room = _Room()
    ing = BatchIngestor(n_docs=2, capacity=CAPACITY)
    spy = _Spy(monkeypatch, ing)
    ing.apply_bytes([room.edit(1, _type("one ")), None])
    before = _counts()
    ing.apply_bytes([None, None])
    assert _counted(before) == {"ingest.batch_builds": 0, "ingest.batch_reuses": 1}
    # the kept pair itself: no merge ran, and no program of its own takes it apart
    assert all(h is k for h, k in zip(spy.applied[-1], spy.merged[-1]))
    assert get_string(ing.state, 0, ing.payloads) == "one "


def test_a_restored_ingestor_builds_at_its_first_step(monkeypatch, tmp_path):
    from ytpu.models.checkpoint import load_ingestor, save_ingestor

    room = _Room()
    ing = BatchIngestor(n_docs=1, capacity=CAPACITY)
    for fn in (_type("saved "), _many(5), _cut(1, 2)):
        ing.apply_bytes([room.edit(1, fn)])
    A, B = (1, 4, 4), (1, 8, 4)
    assert list(ing._batch_cache) == [B, A]
    path = str(tmp_path / "ckpt")
    save_ingestor(path, ing)
    restored = load_ingestor(path)
    assert restored._batch_cache == {}
    assert _bound(restored) == _bound(ing)
    nxt = room.edit(1, _type("on "))
    handed = []
    for which in (restored, ing):
        spy = _Spy(monkeypatch, which)
        before = _counts()
        which.apply_bytes([nxt])
        built = which is restored
        assert _counted(before) == {
            "ingest.batch_builds": int(built), "ingest.batch_reuses": int(not built),
        }
        bucket, want, _ = spy.parent_planes([nxt])
        assert bucket == A and _wrong_leaves(spy.merged[-1], want) == []
        handed.append(spy.merged[-1])
        monkeypatch.undo()
    # what the restored one built is what the one that never stopped holds
    assert _wrong_leaves(handed[0], [np.asarray(a) for a in unpack_batch_jit(handed[1])]) == []
    assert list(restored._batch_cache) == [A]
    want = room.oracle().get_text("text").get_string()
    assert get_string(restored.state, 0, restored.payloads) == want
    assert get_string(ing.state, 0, ing.payloads) == want


def _four_and_four(doc, txn):
    """Four delete ranges inside client 1's first block, none next to
    another, then four one-character blocks at the head: an update as wide
    as the (4, 4) bucket, so that its recovery pads to the bucket's shape."""
    text = doc.get_text("text")
    at = text.get_string().index("abcdefgh")
    for i in range(4):
        text.remove_range(txn, at + 1 + 2 * i, 1)
    for ch in "wxyz":
        text.insert(txn, 0, ch)


def test_a_served_process_keeps_one_integrate_form_a_bucket(monkeypatch):
    """A fast-lane-only step, a host-lane-only step, a step with both and a
    flagged lane's recovery, all at the (4, 4) bucket: the integrate program
    is handed the pair by every one of them (the merge's output, the upload
    as it is, the recovery's `_batch`), so the process builds one form of
    it; and a step's programs hand back 36 buffers where it merges (the
    gather's 2 in these dense steps, the lane matrix and the lane table;
    the decoder's 3, the merge's 2, the state's 29), 29 where it does not."""
    from ytpu.models.batch_doc import _apply_update_batch_in_place_jit
    from ytpu.utils import progbudget

    monkeypatch.setattr(progbudget, "_MAX", 10**9)  # no eviction under our feet
    rooms = [_Room() for _ in range(N_DOCS)]
    ing = BatchIngestor(n_docs=N_DOCS, capacity=CAPACITY // 2)  # a state shape of this test's own: a new form
    spy = _Spy(monkeypatch, ing, flag_decode=3)  # steps 0, 2 and 3 decode
    first = rooms[0].edit(1, _type("abcdefghijklmnop "))
    early = rooms[0].edit(2, _type("two "))
    late = rooms[0].edit(2, _type("more ", 2))
    steps = [
        ("fast lane only", [first, rooms[1].edit(50, _type("x0")), None, None], 36),
        ("host lane only", [late, None, None, None], 29),  # stashed: the host lane's step, and nothing to carry
        ("both", [early, rooms[1].edit(50, _type("x2")), None, None], 36),
        ("flagged", [rooms[0].edit(1, _four_and_four), rooms[1].edit(50, _type("x3")), None, None], 36 + 29),
    ]
    forms = _apply_update_batch_in_place_jit._cache_size()
    counter = metrics.counter("ingest.enqueue_outputs")
    for what, payloads, outputs in steps:
        merged, applied, before = len(spy.merged), len(spy.applied), counter.value
        ing.apply_bytes(payloads)
        assert counter.value - before == outputs, what
        assert len(spy.merged) - merged == (what != "host lane only"), what
        handed = spy.applied[applied:]
        assert len(handed) == 1 + (what == "flagged"), what
        for batch in handed:  # the bucket's one shape, the recovery's too
            assert type(batch) is PackedBatch and (batch.rows.shape, batch.dels.shape) == ((N_DOCS, 4, 23), (N_DOCS, 4, 4)), what
    assert ing.fast_recoveries == 2 and (ing.slow_docs, ing.fast_docs) == (2, 5)
    assert _apply_update_batch_in_place_jit._cache_size() == forms + 1
    assert not np.asarray(ing.state.error).any()
    for d in (0, 1):
        assert get_string(ing.state, d, ing.payloads) == rooms[d].oracle().get_text("text").get_string(), d
