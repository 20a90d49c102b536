"""Chunked replay driver (ISSUE-4 tentpole): fixed-shape stream chunks
through the packed kernel state with BETWEEN-CHUNK device compaction under
the shared CompactionPolicy, vs the unchunked XLA lane and the host oracle.

The kernel-agnostic machinery (chunk slicing, occupancy bounds, policy,
compact/grow, sticky-error drain) is exercised on the CPU-testable
`lane="xla"` twin; the Pallas lane shares every line of the driver except
the kernel dispatch, which tests/test_chip_compile.py compiles for a v5e
(not run on the current machine).
Interpret-mode Pallas raises NotImplementedError in this container's jax
build (seed behavior) — the fused-lane smoke SKIPS on that, never fails.
"""

import numpy as np
import pytest

from ytpu.core import Doc, Update
from ytpu.models.batch_doc import (
    BatchEncoder,
    CompactionPolicy,
    get_string,
    get_values,
    init_state,
)
from ytpu.ops.integrate_kernel import replay_stream_fused

from _fused_interpret import run_or_skip


def _capture(doc):
    log = []
    doc.observe_update_v1(lambda p, o, t: log.append(p))
    return log


def _text_stream(rounds=8, typed=20, erased=18):
    """Append-typing + contiguous range deletes: the realistic editing
    shape whose tombstones are clock- AND sequence-contiguous, so
    compaction actually reclaims them (random-position churn would leave
    unmergeable fragments — also covered, in the move test below)."""
    doc = Doc(client_id=1)
    log = _capture(doc)
    txt = doc.get_text("text")
    length = 0
    for _ in range(rounds):
        for i in range(typed):
            with doc.transact() as txn:
                txt.insert(txn, length, "abcdef"[i % 6])
            length += 1
        with doc.transact() as txn:
            txt.remove_range(txn, length - erased, erased)
        length -= erased
    enc = BatchEncoder()
    steps = [enc.build_step(Update.decode_v1(p), 4, 4) for p in log]
    return (
        BatchEncoder.stack_steps(steps),
        enc,
        txt.get_string(),
    )


def test_chunked_xla_compaction_parity_text():
    """Multi-chunk stream whose total row growth exceeds the chunked
    capacity: ≥1 between-chunk compaction must fire and the final text
    must match the host oracle — which IS the unchunked XLA lane's
    output (their equality is asserted suite-wide by test_batch_doc;
    compaction permutes slots, so decoded output, not raw state, is the
    byte-exact surface)."""
    stream, enc, expect = _text_stream()
    rank = enc.interner.rank_table()

    # every valid stream row integrates to one resident block and
    # deletes only tombstone, so the encoded row count is a strict lower
    # bound on uncompacted residency — no device reference run needed
    raw_rows = int(np.asarray(stream.valid).sum())

    st, stats = replay_stream_fused(
        init_state(2, 96),
        stream,
        rank,
        chunk_steps=16,
        lane="xla",
        max_capacity=96,  # growth disabled: compaction must carry it
    )
    assert raw_rows > 96, "workload must not fit without compaction"
    assert stats.compactions >= 1, stats
    assert stats.growths == 0, stats
    assert int(np.asarray(st.error).max()) == 0
    assert get_string(st, 0, enc.payloads) == expect
    assert get_string(st, 1, enc.payloads) == expect


def test_chunk_boundary_splits_after_compaction():
    """A row arriving AFTER a compaction whose origin lands mid-block of a
    squashed run: the pending split must land inside the merged block."""
    doc = Doc(client_id=1)
    log = _capture(doc)
    txt = doc.get_text("text")
    # chunk 1 territory: one sequential 12-char run (squashes to 1 block)
    for i in range(12):
        with doc.transact() as txn:
            txt.insert(txn, i, "abcdefghijkl"[i])
    # churn to trip the watermark so a compaction lands mid-stream
    for _ in range(4):
        for i in range(8):
            with doc.transact() as txn:
                txt.insert(txn, 12, "xyzwvuts"[i])
        with doc.transact() as txn:
            txt.remove_range(txn, 12, 8)
    # chunk-boundary-crossing edits: origins point mid-run (splits) and a
    # delete straddles an earlier squashed block
    for k in (3, 7, 10):
        with doc.transact() as txn:
            txt.insert(txn, k, ".")
    with doc.transact() as txn:
        txt.remove_range(txn, 2, 6)
    expect = txt.get_string()
    enc = BatchEncoder()
    steps = [enc.build_step(Update.decode_v1(p), 4, 4) for p in log]
    stream = BatchEncoder.stack_steps(steps)
    rank = enc.interner.rank_table()

    st, stats = replay_stream_fused(
        init_state(2, 96),
        stream,
        rank,
        chunk_steps=16,
        lane="xla",
        max_capacity=96,
        policy=CompactionPolicy(high_watermark=0.3, chunk_budget=0.7),
    )
    assert stats.compactions >= 1, stats
    assert int(np.asarray(st.error).max()) == 0
    assert get_string(st, 0, enc.payloads) == expect


def test_chunk_boundary_compaction_with_live_moves():
    """Compaction landing mid-stream with LIVE move ranges spanning the
    chunk boundary: the packed pass must remap the MV plane and keep the
    move-range planes intact for later chunks' claim recomputes.

    Shapes deliberately reuse the (chunk=16, rows=4, dels=4, C=96)
    family the tests above already compiled — one program serves the
    whole file, and distinct big programs are the suite's scarce
    resource (conftest.py LLVM-arena note)."""
    doc = Doc(client_id=1)
    log = _capture(doc)
    arr = doc.get_array("a")
    with doc.transact() as txn:
        for v in range(24):
            arr.push_back(txn, v)
    for r in range(8):
        with doc.transact() as txn:
            arr.move_range_to(txn, 1, 3, len(arr) - 1)
        for v in range(4):  # one row per txn: fits the 4-row bucket
            with doc.transact() as txn:
                arr.insert(txn, 2, 100 * r + v)
        with doc.transact() as txn:
            arr.remove_range(txn, 3, 5)
    expect = arr.to_json()
    enc = BatchEncoder(root_name="a")
    steps = [enc.build_step(Update.decode_v1(p), 4, 4) for p in log]
    stream = BatchEncoder.stack_steps(steps)
    rank = enc.interner.rank_table()

    st, stats = replay_stream_fused(
        init_state(2, 96),
        stream,
        rank,
        chunk_steps=16,
        lane="xla",
        max_capacity=96,
        policy=CompactionPolicy(high_watermark=0.3, chunk_budget=0.5),
    )
    assert stats.compactions >= 1, stats
    assert stats.growths == 0, stats  # pins the shape-reuse property
    assert int(np.asarray(st.error).max()) == 0
    assert get_values(st, 0, enc.payloads) == expect
    assert get_values(st, 1, enc.payloads) == expect


def test_pipeline_packed_xla_lane():
    """UpdatePipeline routes chunks into the packed chunked driver when
    the opt-in lane is selected (same policy/compaction machinery as the
    fused lane, CPU-runnable)."""
    from ytpu.models.pipeline import UpdatePipeline

    doc = Doc(client_id=1)
    log = _capture(doc)
    txt = doc.get_text("text")
    for i in range(40):
        with doc.transact() as txn:
            txt.insert(txn, i, "abcd"[i % 4])
    expect = txt.get_string()
    enc = BatchEncoder()
    pipe = UpdatePipeline(enc, n_rows=4, n_dels=4, chunk_steps=16, lane="packed_xla")
    state, n_chunks = pipe.run(init_state(2, 96), log)
    assert n_chunks == (40 + 15) // 16
    assert int(np.asarray(state.error).max()) == 0
    assert get_string(state, 0, enc.payloads) == expect


def test_pipeline_rejects_unknown_lane():
    with pytest.raises(ValueError, match="lane"):
        from ytpu.models.pipeline import UpdatePipeline

        UpdatePipeline(BatchEncoder(), 4, 4, lane="hbm")


def test_replay_stream_fused_interpret_or_skip():
    """The fused lane end-to-end in interpret mode — or a SKIP when this
    container's jax cannot interpret Pallas TPU kernels (seed behavior:
    NotImplementedError from the interpreter, not a ytpu bug)."""
    doc = Doc(client_id=1)
    log = _capture(doc)
    txt = doc.get_text("text")
    for i in range(6):
        with doc.transact() as txn:
            txt.insert(txn, i, "abcdef"[i])
    expect = txt.get_string()
    enc = BatchEncoder()
    steps = [enc.build_step(Update.decode_v1(p), 4, 4) for p in log]
    stream = BatchEncoder.stack_steps(steps)
    rank = enc.interner.rank_table()
    st, stats = run_or_skip(lambda: replay_stream_fused(
        init_state(2, 96),
        stream,
        rank,
        chunk_steps=16,
        d_block=2,
        interpret=True,
        lane="fused",
        max_capacity=96,
    ))
    assert int(np.asarray(st.error).max()) == 0
    assert get_string(st, 0, enc.payloads) == expect


def test_plan_chunks_sizes_to_policy_budget():
    from ytpu.models.replay import plan_chunks

    # flagship-shaped accounting: ~3 worst-case adds per update
    adds = np.full(200_000, 3, dtype=np.int64)
    plan = plan_chunks(adds, capacity=32768, max_chunk=8192)
    assert plan.feasible, plan
    assert plan.chunk <= 8192 and plan.chunk & (plan.chunk - 1) == 0
    assert plan.max_chunk_adds <= plan.budget
    assert plan.needs_compaction  # 600k worst-case adds >> 32768
    assert plan.n_chunks == -(-200_000 // plan.chunk)
    # a stream that fits outright plans a single max-size chunk family
    small = plan_chunks(np.full(100, 3, dtype=np.int64), capacity=32768)
    assert not small.needs_compaction
    assert small.chunk == 8192


def test_compaction_policy_watermark():
    from ytpu.models.batch_doc import DEFAULT_COMPACTION_POLICY as P

    assert P.should_compact(90, 20, 100)  # projected overflow
    assert P.should_compact(86, 1, 100)  # high-watermark tripped
    assert not P.should_compact(50, 20, 100)
    assert P.chunk_add_budget(32768) == int(0.15 * 32768)
