"""Doc-axis sub-batched integrate dispatch (ISSUE-20 tentpole): the
`SubBatchPlan`-driven slice loop inside `PackedReplayDriver` must be
BYTE-invisible — monolithic vs sub-batched replay produce identical
packed cols/meta and the identical ISSUE-13 commitment word — while
keeping every prior invariant alive: the PR-5 zero-sync lazy readout
(one drain, 12 d2h bytes per chunk readout, the per-slice words folded
on device), the PR-17 compile sentinel bound (ONE compiled family per
`(sub_width, capacity)` pair — slices never retrace), and the PR-6
ladder semantics (an armed `grow.oom` narrows the width in place
instead of killing the chunk: zero recoveries).

Every replay reuses the suite-wide (n_docs=2, capacity=256, chunk=16)
shape family for the MONOLITHIC side (the programs test_async_overlap /
test_scan_tiers already compiled) and forces width 1 via the budget
trick, so the file adds exactly one new big program — the (1, 256)
slice family; the slice boundary then sits between docs 0 and 1, inside
the broadcast storm (distinct big programs are the suite's scarce
resource, conftest.py LLVM-arena note). The narrowing test necessarily
uses its own small-capacity family: that IS the grow trajectory under
test. The fused-interpret parity test routes through
`tests/_fused_interpret.run_or_skip` and runs LAST.
"""

from functools import lru_cache

import numpy as np
import pytest

from ytpu.core import Doc, Update
from ytpu.models.batch_doc import BatchEncoder, get_values, init_state
from ytpu.models.replay import FusedReplay, plan_replay, plan_subbatches
from ytpu.ops import integrate_kernel as ik
from ytpu.ops.integrate_kernel import packed_state_bytes
from ytpu.parallel import mesh as pmesh
from ytpu.utils import metrics
from ytpu.utils.capacity import HeadroomForecaster
from ytpu.utils.faults import faults
from ytpu.utils.phases import phases

from _fused_interpret import run_or_skip

# the ONE adversarial-stream generator shared with the bench (conftest
# puts the repo root on sys.path; benches/ is a namespace package)
from benches.scan_tiers import build_conflict_stream

needs_native = pytest.mark.usefixtures("native_lib")

# the one shape family of this file (shared suite-wide)
N_DOCS, CAPACITY, CHUNK, D_BLOCK = 2, 256, 16, 2

# admits exactly width 1: slice state + its 2x grow transient
W1_BUDGET = packed_state_bytes(1, CAPACITY) + packed_state_bytes(
    1, 2 * CAPACITY
)


@pytest.fixture(autouse=True)
def _clean_slate():
    """Armed faults and sticky lane demotions are process-global."""
    faults.clear()
    ik.reset_lane_health()
    yield
    faults.clear()
    ik.reset_lane_health()


def _capture(doc):
    log = []
    doc.observe_update_v1(lambda p, o, t: log.append(p))
    return log


@lru_cache(maxsize=1)
def _typing():
    """Append-typing + tail erase (the test_async_overlap workload):
    tombstones are clock- AND sequence-contiguous, so `compact_packed`
    reclaims them and a max_capacity == capacity replay is carried by
    compaction alone; the 3-chunk prefix is the zero-sync steady
    state."""
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
    return log, expect, plan_replay(log)


@lru_cache(maxsize=1)
def _storm():
    """Same-origin conflict storm (the test_scan_tiers `_deep` shape,
    sized down): ~64 concurrent siblings all anchored on one origin —
    every doc is hot, and under a width-1 plan the slice boundary cuts
    straight through the broadcast storm."""
    payloads, expect = build_conflict_stream(
        8, 8, erase_every=5, erase_len=11
    )
    return payloads, expect, plan_replay(payloads)


def _make(plan, shard: bool, max_capacity: int = 4 * CAPACITY, **kw):
    kw.setdefault("lane", "xla")
    if shard:
        kw.setdefault(
            "forecaster", HeadroomForecaster(budget_bytes=W1_BUDGET)
        )
    return FusedReplay(
        n_docs=N_DOCS,
        plan=plan,
        capacity=CAPACITY,
        max_capacity=max_capacity,
        d_block=D_BLOCK,
        chunk=CHUNK,
        overlap=True,
        ingest="raw",
        sync_per_chunk=False,
        shard_docs=shard,
        **kw,
    )


def _byte_parity(a: FusedReplay, b: FusedReplay) -> None:
    assert np.array_equal(np.asarray(a.cols), np.asarray(b.cols))
    assert np.array_equal(np.asarray(a.meta), np.asarray(b.meta))
    assert a.stats.commit_word == b.stats.commit_word


def test_plan_subbatches_pow2_divisibility_and_floor():
    """The plan is pure host arithmetic: width is always a pow2 that
    divides the doc axis (ONE shape family serves every slice), the
    budget trick admits exactly the intended width, and the floor is
    `d_block` even when infeasible."""
    budget = 3 * packed_state_bytes(768, 512)
    p = plan_subbatches(1024, 512, d_block=8, budget_bytes=budget)
    assert (p.width, p.n_sub) == (512, 2)
    assert p.feasible and not p.monolithic
    assert p.transient_bytes <= budget < p.monolithic_bytes
    wide = plan_subbatches(8192, 512, d_block=8, budget_bytes=budget)
    assert (wide.width, wide.n_sub) == (512, 16)
    # pow2 + divisibility hold on a non-pow2 doc axis too
    odd = plan_subbatches(6, 256, budget_bytes=1 << 40)
    assert (odd.width, odd.n_sub) == (2, 3)
    assert odd.n_docs % odd.width == 0
    # the budget trick used suite-wide: transient(w) admits exactly w
    forced = plan_subbatches(N_DOCS, CAPACITY, budget_bytes=W1_BUDGET)
    assert forced.width == 1 and forced.n_sub == 2
    assert forced.transient_bytes == W1_BUDGET
    # floor: the fused lane cannot tile below d_block — plan reports
    # the bust via `feasible` instead of returning an untileable width
    floored = plan_subbatches(1024, 512, d_block=8, budget_bytes=1)
    assert floored.width == 8 and not floored.feasible
    # a huge budget degenerates to the PR-5 monolithic dispatch
    mono = plan_subbatches(1024, 512, budget_bytes=1 << 50)
    assert mono.monolithic and mono.n_sub == 1
    # max_width caps the start even when the budget would allow more
    capped = plan_subbatches(1024, 512, budget_bytes=1 << 50, max_width=256)
    assert capped.width == 256 and capped.n_sub == 4


def test_single_device_mesh_fallback_is_identity():
    """CPU tier-1 runs on one device: every batch-dim sharding helper
    must degrade to a no-op so the sub-batch loop is placement-free and
    byte-identical to the unsharded path."""
    import jax

    if len(jax.devices()) != 1:
        pytest.skip("multi-device host: fallback path not reachable")
    assert pmesh.batch_mesh() is None
    assert pmesh.batch_mesh(n_devices=1) is None
    assert pmesh.subbatch_devices(4) is None
    probe = np.arange(8)
    assert pmesh.shard_docs_put(probe) is probe


@needs_native
def test_subbatch_parity_with_compaction_midstream():
    """Tentpole acceptance: a tight-capacity typing stream (growth
    disabled — BETWEEN-CHUNK compaction carries it, running per doc
    slice under the width-1 plan) must be BYTE-identical to the
    monolithic replay."""
    log, expect, plan = _typing()
    mono = _make(plan, shard=False, max_capacity=CAPACITY)
    mono.run(log)
    sub = _make(plan, shard=True, max_capacity=CAPACITY)
    sub.run(log)
    assert sub.stats.subbatch_width == 1, sub.stats
    assert mono.stats.compactions >= 1 and sub.stats.compactions >= 1
    assert sub.stats.growths == 0, sub.stats
    _byte_parity(mono, sub)
    for d in range(N_DOCS):
        assert sub.get_string(d) == mono.get_string(d) == expect


@needs_native
def test_subbatch_boundary_splits_conflict_storm():
    """A same-origin conflict storm broadcast to every doc, replayed
    with the slice boundary cutting the batch in half: each per-slice
    dispatch integrates the same ~64-sibling scan, and the result is
    byte-identical to the monolithic replay — the storm never sees the
    seam (docs 0 and 1 sit in different slices)."""
    payloads, expect, plan = _storm()
    mono = _make(plan, shard=False)
    mono.run(payloads)
    sub = _make(plan, shard=True)
    sub.run(payloads)
    assert sub.stats.subbatch_width == 1, sub.stats
    _byte_parity(mono, sub)
    for d in range(N_DOCS):
        assert sub.get_string(d) == mono.get_string(d) == expect
    assert sub.get_string(0) == sub.get_string(1)


def test_subbatch_parity_with_live_moves():
    """Array storm with live `move_range_to` ranges through the STREAM
    path (`replay_stream_fused(shard_docs=True)` — mixed content can't
    ride the text-only byte path): the between-chunk grow/compact run
    per doc slice under a budget that forces width 1, and the packed
    planes stay byte-identical to the monolithic replay."""
    from ytpu.ops.integrate_kernel import pack_state, replay_stream_fused

    base = Doc(client_id=1)
    base_log = _capture(base)
    arr = base.get_array("a")
    with base.transact() as txn:
        for v in range(12):
            arr.push_back(txn, v)
    base_update = base.encode_state_as_update_v1()

    per_client = []
    for k in range(8):
        doc = Doc(client_id=10 + k)
        doc.apply_update_v1(base_update)
        log = _capture(doc)
        a = doc.get_array("a")
        for i in range(8):
            with doc.transact() as txn:
                a.insert(txn, 3, 1000 * k + i)
        with doc.transact() as txn:
            a.move_range_to(txn, 1, 3, len(a) - 1)
        if k % 3 == 0:
            with doc.transact() as txn:
                a.remove_range(txn, 2, 3)
        per_client.append(log)

    payloads = list(base_log)
    for i in range(max(len(log) for log in per_client)):
        for log in per_client:
            if i < len(log):
                payloads.append(log[i])
    oracle = Doc(client_id=2)
    for p in payloads:
        oracle.apply_update_v1(p)
    expect = oracle.get_array("a").to_json()
    enc = BatchEncoder(root_name="a")
    steps = [enc.build_step(Update.decode_v1(p), 4, 4) for p in payloads]
    stream = BatchEncoder.stack_steps(steps)
    rank = enc.interner.rank_table()
    tight = 64  # raw rows exceed it: the grow path MUST fire per slice
    assert int(np.asarray(stream.valid).sum()) > tight

    def replay(shard: bool):
        kw = {}
        if shard:
            b = packed_state_bytes(1, tight) + packed_state_bytes(
                1, 2 * tight
            )
            kw = dict(
                shard_docs=True,
                forecaster=HeadroomForecaster(budget_bytes=b),
            )
        return replay_stream_fused(
            init_state(N_DOCS, tight),
            stream,
            rank,
            chunk_steps=CHUNK,
            d_block=D_BLOCK,
            lane="xla",
            max_capacity=4 * CAPACITY,
            **kw,
        )

    st_a, a = replay(shard=False)
    st_b, b = replay(shard=True)
    assert a.growths >= 1 and b.growths >= 1, (a, b)
    assert b.subbatch_width == 1, b
    for pa, pb in zip(pack_state(st_a), pack_state(st_b)):
        assert np.array_equal(np.asarray(pa), np.asarray(pb))
    assert get_values(st_b, 0, enc.payloads) == expect
    assert get_values(st_b, N_DOCS - 1, enc.payloads) == expect


@needs_native
def test_subbatch_zero_sync_and_compile_family_bound():
    """The two load-bearing invariants of the slice loop: (1) the PR-5
    zero-sync readout survives the fold — per-slice readout words merge
    ON DEVICE into one `[N_READOUT]` surface per chunk, so the steady
    state still drains ONCE with 12 d2h bytes per chunk readout; (2)
    the PR-17 sentinel sees exactly ONE `replay.subbatch` compile event
    for the whole run (one `(sub_width, capacity)` family, zero
    retraces) even though every chunk pays n_sub slice dispatches."""
    log, expect, plan = _typing()
    prefix = log[: 3 * CHUNK]
    mono = _make(plan, shard=False)
    mono.run(prefix)
    phases.reset()
    phases.enable()
    try:
        marker = phases.compile_marker()
        sub = _make(plan, shard=True)
        stats = sub.run(prefix)
        snap = phases.snapshot()
        events = [
            e
            for e in phases.compile_events(marker)
            if e["program"] == "replay.subbatch"
        ]
    finally:
        phases.disable()
        phases.reset()
    assert stats.chunks == 3 and stats.subbatch_width == 1, stats
    assert stats.syncs == 1, f"steady state must drain once, got {stats}"
    # one folded readout per chunk, all materialized in the one drain
    assert snap["replay.readout"]["d2h_bytes"] == 12 * stats.chunks, snap
    # 3 chunks x 2 slices = 6 dispatches, ONE compiled family, 0 retraces
    assert len(events) == 1, events
    assert not events[0]["retrace"], events
    assert snap["subbatch.width"]["value"] == 1.0, snap
    assert snap["subbatch.n_sub"]["value"] == 2.0, snap
    for d in range(N_DOCS):
        assert sub.get_string(d) == mono.get_string(d)


@needs_native
def test_grow_oom_narrows_instead_of_killing_chunk():
    """Satellite acceptance: an armed ``grow.oom`` under `shard_docs`
    demotes the width in place (journaled, counted
    `capacity.subbatch_narrowed`) and the grow RETRIES and succeeds —
    the chunk is never killed, so the PR-6 recovery ladder stays cold
    (zero recoveries), unlike the monolithic path where the same fault
    costs a ReplayFault recovery."""
    import bench as _bench

    grow_log, grow_expect = _bench.build_updates(
        [("i", 0, "abcdefgh") for _ in range(40)]
    )
    grow_plan = plan_replay(grow_log)

    def replay():
        r = FusedReplay(
            n_docs=N_DOCS,
            plan=grow_plan,
            capacity=32,
            max_capacity=1024,
            d_block=D_BLOCK,
            chunk=8,
            lane="xla",
            overlap=True,
            ingest="raw",
            sync_per_chunk=False,
            shard_docs=True,
            forecaster=HeadroomForecaster(budget_bytes=1 << 30),
        )
        r.run(grow_log)
        return r

    before = metrics.counter("capacity.subbatch_narrowed").value
    faults.arm("grow.oom")
    try:
        r = replay()
    finally:
        faults.clear()
    narrowed = metrics.counter("capacity.subbatch_narrowed").value - before
    assert narrowed >= 1, "armed grow.oom never narrowed the sub-batch"
    assert r.stats.subbatch_narrowed == narrowed, r.stats
    assert r.stats.growths >= 1, r.stats
    assert r.stats.recoveries == 0, (
        "narrowing must absorb the denial in place",
        r.stats,
    )
    assert r.get_string(0) == grow_expect == r.get_string(N_DOCS - 1)
    # an un-faulted run on the same family narrows nothing
    clean = replay()
    assert clean.stats.subbatch_narrowed == 0, clean.stats
    assert clean.get_string(0) == grow_expect


@needs_native
def test_subbatch_fused_interpret_or_skip():
    """The fused Pallas lane through the sliced loop — or a SKIP when
    this container's jax cannot interpret the kernel (memoized across
    files by tests/_fused_interpret). The fused floor is `d_block`, so
    this leg needs 4 docs for a real width-2 slice boundary (one
    `d_block` tile per slice); the extra family only compiles where
    fused-interpret actually runs. Runs LAST."""
    log, expect, plan = _typing()
    prefix = log[: 2 * CHUNK]
    budget = packed_state_bytes(2, CAPACITY) + packed_state_bytes(
        2, 2 * CAPACITY
    )

    def go():
        r = FusedReplay(
            n_docs=4,
            plan=plan,
            capacity=CAPACITY,
            max_capacity=4 * CAPACITY,
            d_block=D_BLOCK,
            chunk=CHUNK,
            lane="fused",
            interpret=True,
            overlap=True,
            ingest="raw",
            sync_per_chunk=False,
            shard_docs=True,
            forecaster=HeadroomForecaster(budget_bytes=budget),
        )
        r.run(prefix)
        return r

    sub = run_or_skip(go)
    assert sub.stats.subbatch_width == 2, sub.stats
    # the xla monolithic twin (compiled only where fused-interpret ran)
    mono = FusedReplay(
        n_docs=4,
        plan=plan,
        capacity=CAPACITY,
        max_capacity=4 * CAPACITY,
        d_block=D_BLOCK,
        chunk=CHUNK,
        lane="xla",
        overlap=True,
        ingest="raw",
        sync_per_chunk=False,
    )
    mono.run(prefix)
    for d in range(4):
        assert sub.get_string(d) == mono.get_string(d)
