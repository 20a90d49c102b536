"""Device compaction (squash + GC collapse + defrag) parity tests.

The invariant under test: compaction is semantics-preserving — replaying a
stream, compacting at arbitrary points, and continuing the replay must
produce exactly the host oracle's document (reference guarantee of
try_squash/GC at block.rs:775-799 and gc.rs)."""

import random

import numpy as np
import pytest

from ytpu.core import Doc, Update
from ytpu.models.batch_doc import (
    BatchEncoder,
    apply_update_batch,
    get_string,
    get_tree,
    get_values,
    init_state,
)
from ytpu.ops.compaction import compact_state, grow_state


def capture(doc: Doc):
    log = []
    doc.observe_update_v1(lambda p, o, t: log.append(p))
    return log


def replay(enc, state, payloads):
    for p in payloads:
        u = Update.decode_v1(p)
        batch = enc.build_batch([u] * state.start.shape[0])
        state = apply_update_batch(state, batch, enc.interner.rank_table())
    return state


def text_workload(n_ops=80, seed=3):
    rng = random.Random(seed)
    doc = Doc(client_id=1)
    log = capture(doc)
    t = doc.get_text("text")
    length = 0
    for _ in range(n_ops):
        with doc.transact() as txn:
            if length > 10 and rng.random() < 0.3:
                k = rng.randint(1, 4)
                pos = rng.randint(0, length - k)
                t.remove_range(txn, pos, k)
                length -= k
            else:
                word = "".join(rng.choice("abcdef") for _ in range(rng.randint(1, 5)))
                t.insert(txn, rng.randint(0, length), word)
                length += len(word)
    return log, t.get_string()


def test_compact_preserves_text_and_shrinks():
    log, expect = text_workload()
    enc = BatchEncoder()
    state = replay(enc, init_state(2, 512), log)
    before = int(state.n_blocks[0])
    state2 = compact_state(state)
    after = int(state2.n_blocks[0])
    assert after < before, (before, after)
    assert int(state2.error.max()) == 0
    assert get_string(state2, 0, enc.payloads) == expect
    assert get_string(state2, 1, enc.payloads) == expect
    # idempotent
    state3 = compact_state(state2)
    assert int(state3.n_blocks[0]) == after
    assert get_string(state3, 0, enc.payloads) == expect


def test_compact_midstream_then_continue():
    log, expect = text_workload(n_ops=60, seed=9)
    enc = BatchEncoder()
    state = init_state(1, 512)
    cut = len(log) // 2
    state = replay(enc, state, log[:cut])
    state = compact_state(state)
    state = replay(enc, state, log[cut:])
    # compact again at the end for good measure
    state = compact_state(state)
    assert int(state.error[0]) == 0
    assert get_string(state, 0, enc.payloads) == expect


def test_compact_many_interleaved_points():
    log, expect = text_workload(n_ops=50, seed=21)
    enc = BatchEncoder()
    state = init_state(1, 512)
    for i, p in enumerate(log):
        u = Update.decode_v1(p)
        state = apply_update_batch(
            state, enc.build_batch([u]), enc.interner.rank_table()
        )
        if i % 7 == 3:
            state = compact_state(state)
    state = compact_state(state)
    assert int(state.error[0]) == 0
    assert get_string(state, 0, enc.payloads) == expect


def test_compacted_diff_applies_to_fresh_host_doc():
    from ytpu.models.batch_doc import encode_diff_batch, finish_encode_diff

    log, expect = text_workload(n_ops=40, seed=5)
    enc = BatchEncoder()
    state = compact_state(replay(enc, init_state(1, 512), log))
    C = max(8, len(enc.interner))
    remote = np.zeros((1, C), dtype=np.int32)
    import jax

    ship, offsets, local_sv, deleted = jax.tree_util.tree_map(
        np.asarray, encode_diff_batch(state, remote, C)
    )
    payload = finish_encode_diff(state, 0, ship, offsets, deleted, enc)
    replica = Doc(client_id=99)
    replica.apply_update_v1(payload)
    assert replica.get_text("text").get_string() == expect


def test_compact_with_moves():
    doc = Doc(client_id=1)
    log = capture(doc)
    arr = doc.get_array("a")
    with doc.transact() as txn:
        for v in range(8):
            arr.push_back(txn, v)
    with doc.transact() as txn:
        arr.move_range_to(txn, 2, 4, 7)
    with doc.transact() as txn:
        arr.remove_range(txn, 0, 1)
    expect = doc.get_array("a").to_json()
    enc = BatchEncoder(root_name="a")
    state = replay(enc, init_state(1, 128), log)
    state = compact_state(state)
    assert int(state.error[0]) == 0
    assert get_values(state, 0, enc.payloads) == expect


def test_compact_nested_tree():
    from ytpu.types import XmlElementPrelim

    doc = Doc(client_id=4)
    log = capture(doc)
    m = doc.get_map("m")
    with doc.transact() as txn:
        m.insert(txn, "x", 1)
        m.insert(txn, "y", "two")
    with doc.transact() as txn:
        m.insert(txn, "x", 3)  # overwrite -> tombstone
    enc = BatchEncoder(root_name="m")
    state = replay(enc, init_state(1, 128), log)
    state = compact_state(state)
    assert int(state.error[0]) == 0
    tree = get_tree(state, 0, enc.payloads, enc.keys)
    assert tree["map"] == doc.get_map("m").to_json()


def test_grow_state_continues_replay():
    log, expect = text_workload(n_ops=40, seed=13)
    enc = BatchEncoder()
    state = init_state(1, 64)
    cut = len(log) // 2
    state = replay(enc, state, log[:cut])
    state = grow_state(state, 512)
    state = replay(enc, state, log[cut:])
    assert int(state.error[0]) == 0
    assert get_string(state, 0, enc.payloads) == expect


def test_compact_plus_grow_sustains_small_capacity():
    """Periodic compaction keeps a long stream inside a small capacity."""
    log, expect = text_workload(n_ops=120, seed=17)
    enc = BatchEncoder()
    state = init_state(1, 256)
    for i, p in enumerate(log):
        u = Update.decode_v1(p)
        state = apply_update_batch(
            state, enc.build_batch([u]), enc.interner.rank_table()
        )
        if i % 16 == 15:
            state = compact_state(state)
    state = compact_state(state)
    assert int(state.error[0]) == 0
    assert get_string(state, 0, enc.payloads) == expect


def test_compact_then_grow_keeps_moves_and_pads_unowned():
    """`compact_state` remaps `moved` slot indices through the defragment
    permutation and `grow_state` pads every move column with "no owner":
    a padded slot must never read as owned by slot 0."""
    doc = Doc(client_id=1)
    log = []
    doc.observe_update_v1(lambda p, o, t: log.append(p))
    arr = doc.get_array("a")
    with doc.transact() as txn:
        for v in range(6):
            arr.push_back(txn, v)
    with doc.transact() as txn:
        arr.move_to(txn, 1, 5)
    with doc.transact() as txn:
        arr.remove_range(txn, 0, 1)  # a tombstone for compaction to chew

    enc = BatchEncoder(root_name="a")
    state = init_state(1, 64)
    for p in log:
        batch = enc.build_batch([Update.decode_v1(p)])
        state = apply_update_batch(state, batch, enc.interner.rank_table())
    expect = get_values(state, 0, enc.payloads)

    out = grow_state(compact_state(state), 128)
    moved = np.asarray(out.blocks.moved[0])
    assert moved.shape == (128,) and int(moved.max(initial=-1)) < 64
    for name in ("moved", "mv_sc", "mv_ec", "mv_prio"):
        assert int(np.asarray(getattr(out.blocks, name))[0, 64:].max(initial=-1)) == -1, name
    assert get_values(out, 0, enc.payloads) == expect
    assert (moved >= 0).any()  # a live move row still owns its range after defrag


def test_compaction_policy_watermark():
    from ytpu.models.batch_doc import DEFAULT_COMPACTION_POLICY as P

    assert P.should_compact(90, 20, 100)  # projected overflow
    assert P.should_compact(86, 1, 100)  # high-watermark tripped
    assert not P.should_compact(50, 20, 100)
    assert P.chunk_add_budget(32768) == int(0.15 * 32768)
