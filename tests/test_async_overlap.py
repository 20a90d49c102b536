"""Async double-buffered replay (ISSUE-5 tentpole): the overlap lane
(`FusedReplay(overlap=True)` → `PackedReplayDriver.step_bytes` → the one
fused decode→rebase→integrate `replay_chunk_program`) vs the synchronous
chunked loop, on CPU-testable shapes.

Every test in this file shares ONE workload/plan and the (n_docs=2,
capacity=256, chunk=16) shape family, so each compiled program (decode,
xla_chunk_step, replay_chunk_program, compact_packed) is traced at most
once for the whole file — distinct big programs are the suite's scarce
resource (conftest.py LLVM-arena note). The fused-lane interpret test
routes through `tests/_fused_interpret.run_or_skip` (this container's
jax cannot interpret the Pallas kernel — seed behavior) and runs LAST so
the cheap assertions report first.
"""

from functools import lru_cache

import numpy as np
import pytest


from _fused_interpret import run_or_skip

needs_native = pytest.mark.usefixtures("native_lib")

# (n_docs, capacity, chunk, d_block) — the one shape family of this file
N_DOCS, CAPACITY, CHUNK, D_BLOCK = 2, 256, 16, 2


@lru_cache(maxsize=1)
def _workload():
    """Append-typing + tail erase: tombstones are clock- AND sequence-
    contiguous, so `compact_packed` actually reclaims them and a
    max_capacity == capacity replay is carried by compaction alone."""
    import bench as _bench

    ops = []
    length = 0
    for _ in range(14):
        for i in range(20):
            ops.append(("i", length, "abcdef"[i % 6]))
            length += 1
        ops.append(("d", length - 18, 18))
        length -= 18
    log, expect = _bench.build_updates(ops)
    from ytpu.models.replay import plan_replay

    return log, expect, plan_replay(log)


def _make(overlap: bool, lane: str = "xla", interpret: bool = False):
    from ytpu.models.replay import FusedReplay

    _, _, plan = _workload()
    return FusedReplay(
        n_docs=N_DOCS,
        plan=plan,
        capacity=CAPACITY,
        max_capacity=CAPACITY,  # growth disabled: compaction must carry it
        d_block=D_BLOCK,
        chunk=CHUNK,
        lane=lane,
        interpret=interpret,
        overlap=overlap,
    )


@needs_native
def test_async_parity_with_compaction_midstream():
    """The async lane must be byte-exact vs the synchronous loop on a
    multi-chunk stream that trips ≥1 between-chunk compaction — the
    decoded text (slot layout permutes under compaction) is the
    byte-exact surface, as in test_replay_chunked."""
    log, expect, _ = _workload()
    sync = _make(overlap=False)
    s_sync = sync.run(log)
    asyn = _make(overlap=True)
    s_async = asyn.run(log)
    assert s_sync.compactions >= 1 and s_async.compactions >= 1
    assert s_async.growths == 0, s_async  # pins the shape-reuse property
    assert s_async.chunks == s_sync.chunks == (len(log) + CHUNK - 1) // CHUNK
    for d in range(N_DOCS):
        assert asyn.get_string(d) == sync.get_string(d) == expect
    # double-buffer contract: depth capped at 2, every later chunk
    # re-packs a recycled slot, and the loop never synced once per chunk
    assert 1 <= s_async.max_inflight <= 2, s_async
    assert s_async.buffer_reuses == s_async.chunks - 2, s_async
    assert s_async.syncs < s_async.chunks, s_async
    assert s_async.overlap_ratio >= 0.0


@needs_native
def test_async_zero_sync_steady_state():
    """Acceptance: the steady-state async loop performs NO blocking
    device sync per chunk. On a prefix whose optimistic adds-bound never
    trips the watermark, the ONLY host materialization is the single
    drain at `finish()` — counted via the phases instrumentation
    (`replay.readout` d2h bytes = 12 per [3]-word readout, all of them
    landing in one drain) and the driver's `syncs` counter."""
    from ytpu.utils.phases import phases

    log, _, _ = _workload()
    prefix = log[: 3 * CHUNK]  # adds-bound stays far under the watermark
    sync = _make(overlap=False)
    sync.run(prefix)
    phases.reset()
    phases.enable()
    try:
        asyn = _make(overlap=True)
        stats = asyn.run(prefix)
        snap = phases.snapshot()
    finally:
        phases.disable()
        phases.reset()
    assert stats.chunks == 3 and stats.compactions == 0, stats
    assert stats.syncs == 1, f"steady state must drain once, got {stats}"
    # all 3 readouts materialized together in that one finish() drain
    assert snap["replay.readout"]["d2h_bytes"] == 12 * stats.chunks, snap
    # the overlap gauges landed in bench-visible phases
    assert "value" in snap["replay.overlap_ratio"]
    assert snap["replay.inflight_depth"]["value"] >= 1
    assert snap["replay.stage"]["calls"] == stats.chunks
    for d in range(N_DOCS):
        assert asyn.get_string(d) == sync.get_string(d)


@needs_native
def test_async_deferred_decode_error_same_message():
    """A decode error in the async lane surfaces DEFERRED (sticky device
    scalar, drained at a watermark trip or finish) but re-identifies the
    offending update host-side and raises the SAME message the serial
    loop produces at the offending chunk."""
    log, _, _ = _workload()
    bad = list(log)
    bad[37] = bad[37][: len(bad[37]) // 2]  # truncation → FLAG_MALFORMED
    with pytest.raises(RuntimeError, match="flagged updates") as sync_err:
        _make(overlap=False).run(bad)
    with pytest.raises(RuntimeError, match="flagged updates") as async_err:
        _make(overlap=True).run(bad)
    assert str(async_err.value) == str(sync_err.value)
    assert "[37]" in str(async_err.value)


@needs_native
def test_overlap_plan_and_dry_run():
    """The static staging plan (depth-2 double buffer, every later chunk
    a slot reuse) plus the host-only bench rehearsal that CI asserts
    before a device round trusts the overlap lane."""
    import bench as _bench
    from ytpu.models.replay import plan_overlap

    log, _, _ = _workload()
    op = plan_overlap(len(log), CHUNK)
    assert op.depth == 2 and op.buffers == 2
    assert op.n_chunks == (len(log) + CHUNK - 1) // CHUNK
    assert op.buffer_reuses == max(0, op.n_chunks - 2)
    assert _make(overlap=True).overlap_plan() == op
    # bench's rehearsal asserts depth/reuse internally and models the win
    out = _bench.overlap_dry_run(log[: 4 * CHUNK], chunk=CHUNK)
    assert out["depth"] == 2 and out["buffers"] == 2
    assert out["n_chunks"] == 4 and out["buffer_reuses"] == 2
    assert out["modeled_speedup"] >= 1.0
    # the non-vacuous engine signal (speedup >= 1 holds by algebra)
    assert out["overlap_ratio"] > 0.0


@needs_native
def test_pack_updates_into_reuse_is_clean():
    """Slot reuse can never alias stale bytes into a later decode: after
    re-packing a shorter payload over a longer one, the tail up to the
    previous occupant's length + guard is zeroed."""
    from ytpu.ops.decode_kernel import _PAD, pack_updates_into

    buf = np.zeros((4, 64), dtype=np.uint8)
    lens = np.zeros((4,), dtype=np.int32)
    pack_updates_into([b"\x01" * 40, b"\x02" * 8], buf, lens)
    assert lens.tolist() == [40, 8, 2, 2]  # short rows pad as EMPTY_UPDATE
    pack_updates_into([b"\x03" * 6], buf, lens)
    assert lens[0] == 6
    assert buf[0, :6].tolist() == [3] * 6
    assert not buf[0, 6 : 40 + _PAD].any(), "stale bytes survived reuse"
    with pytest.raises(ValueError, match="exceeds staging width"):
        pack_updates_into([b"\x04" * 60], buf, lens)


@needs_native
def test_capacity_exhausted_error_names_limit():
    """`max_capacity` BELOW the current capacity raises a proper
    capacity-exhausted error naming the limit — not grow_packed's
    misleading "cannot shrink" (PR-4 review). Driven through the
    driver's `ensure_room` directly: a chunk whose worst-case growth
    cannot fit must fail before the tile-corrupting ERR_CAPACITY."""
    from ytpu.models.batch_doc import init_state
    from ytpu.ops.decode_kernel import identity_rank
    from ytpu.ops.integrate_kernel import PackedReplayDriver, pack_state

    cols, meta = pack_state(init_state(N_DOCS, CAPACITY))
    drv = PackedReplayDriver(
        cols,
        meta,
        identity_rank(256),
        lane="xla",
        unit_refs=True,  # reuse this file's compiled compact family
        gc_ranges=True,
        max_capacity=CAPACITY // 4,  # below current capacity
    )
    with pytest.raises(RuntimeError, match=r"capacity-exhausted.*max_capacity"):
        drv.ensure_room(10 * CAPACITY)


@needs_native
def test_async_fused_interpret_or_skip():
    """The fused Pallas lane through the async pipeline — or a SKIP when
    this container's jax cannot interpret the kernel (memoized across
    files by tests/_fused_interpret)."""
    log, _, _ = _workload()
    prefix = log[: 2 * CHUNK]
    sync = _make(overlap=False)
    sync.run(prefix)
    asyn = _make(overlap=True, lane="fused", interpret=True)
    run_or_skip(lambda: asyn.run(prefix))
    for d in range(N_DOCS):
        assert asyn.get_string(d) == sync.get_string(d)
