"""Fault-injected resilience (ISSUE-6): the lane-demotion ladder,
checkpointed replay recovery, poison-update quarantine, and the hardened
sync transport, all exercised through `ytpu.utils.faults` so the failure
paths run deterministically on CPU.

Every replay in this file reuses test_async_overlap's workload and its
one (n_docs=2, capacity=256, chunk=16) shape family — the compiled
decode/chunk-step/compaction programs are shared with that file (which
sorts immediately before this one), so no test here pays a fresh
big-program trace.  The fused interpret test routes through
`tests/_fused_interpret.run_or_skip` (this container's jax cannot
interpret the Pallas kernel — seed behavior) and runs LAST.
"""

import asyncio
import socket
import time

import pytest

from ytpu.native import available as native_available
from ytpu.ops import integrate_kernel as ik
from ytpu.utils import metrics
from ytpu.utils.faults import FaultError, FaultSpec, faults

from _fused_interpret import run_or_skip
from test_async_overlap import CAPACITY, CHUNK, D_BLOCK, N_DOCS, _workload

needs_native = pytest.mark.usefixtures("native_lib")


@pytest.fixture(autouse=True)
def _clean_slate():
    """Armed faults and sticky lane demotions are process-global: every
    test starts and ends with both cleared so no state leaks into the
    rest of the suite."""
    faults.clear()
    ik.reset_lane_health()
    yield
    faults.clear()
    ik.reset_lane_health()


def _make(lane="xla", overlap=False, interpret=False, **kw):
    from ytpu.models.replay import FusedReplay

    _, _, plan = _workload()
    return FusedReplay(
        n_docs=N_DOCS,
        plan=plan,
        capacity=CAPACITY,
        max_capacity=CAPACITY,
        d_block=D_BLOCK,
        chunk=CHUNK,
        lane=lane,
        interpret=interpret,
        overlap=overlap,
        **kw,
    )


# --------------------------------------------------------- fault injector


def test_faults_grammar_and_determinism():
    faults.configure("dispatch.fail:lane=fused,after=2;net.delay:ms=7,n=3")
    specs = faults._specs
    assert [s.after for s in specs["dispatch.fail"]] == [2]
    assert specs["dispatch.fail"][0].args == {"lane": "fused"}
    assert specs["net.delay"][0].n == 3
    # context mismatch is not an eligible pass; match fires after `after`
    assert faults.fire("dispatch.fail", lane="xla") is None
    assert faults.fire("dispatch.fail", lane="fused") is None  # pass 1
    assert faults.fire("dispatch.fail", lane="fused") is None  # pass 2
    assert faults.fire("dispatch.fail", lane="fused") is not None  # fires
    assert faults.fire("dispatch.fail", lane="fused") is None  # n=1 spent
    # p-draws are seeded: same seed → same decision sequence
    a = FaultSpec("x", n=0, p=0.5, seed=7)
    b = FaultSpec("x", n=0, p=0.5, seed=7)
    assert [a._decide() for _ in range(32)] == [b._decide() for _ in range(32)]
    # suspended(): nothing fires inside the clean-run baseline
    faults.arm("grow.oom")
    with faults.suspended():
        assert faults.fire("grow.oom") is None
    assert faults.fire("grow.oom") is not None
    # two specs armed on one site: the pass's winner spends its fire
    # budget, the loser keeps its `n` for a later pass — so
    # "net.drop;net.drop" drops TWO frames, not one
    faults.clear()
    faults.configure("net.drop;net.drop")
    assert faults.fire("net.drop") is not None
    assert faults.fire("net.drop") is not None
    assert faults.fire("net.drop") is None


# ------------------------------------------------- lane-demotion ladder


@needs_native
def test_dispatch_fault_demotes_with_parity():
    """An injected fused-lane dispatch failure demotes the family one
    rung and retries the SAME chunk in place: the run completes on the
    packed-XLA lane with byte parity vs the serial host oracle, and the
    demotion is sticky — a later fused-lane replay of the same family
    skips the known-bad lane without any fault armed."""
    log, expect, _ = _workload()
    base = metrics.counter("lane.demotions").value
    faults.arm("dispatch.fail", lane="fused")
    r = _make(lane="fused")
    r.run(log)
    assert r.get_string(0) == expect
    assert r.stats.demotions >= 1 and r.stats.recoveries >= 1
    assert r.stats.final_lane == "xla"
    assert metrics.counter("lane.demotions").value >= base + 1
    # sticky floor: the family remembers without any armed fault
    fam = ik.lane_family(N_DOCS, D_BLOCK)
    assert ik.effective_lane(fam, "fused") == "xla"
    faults.clear()
    r2 = _make(lane="fused")
    r2.run(log)
    assert r2.get_string(0) == expect
    assert r2.stats.final_lane == "xla"
    assert r2.stats.demotions == 0  # no new failure: floor did the routing


@needs_native
def test_ladder_bottoms_out_on_host_oracle():
    """Demoting past the packed-XLA rung lands on the serial host
    oracle: slow, but the replay still completes with parity."""
    log, expect, _ = _workload()
    faults.arm("dispatch.fail", lane="xla")
    r = _make(lane="xla")
    r.run(log)
    assert r.stats.final_lane == "host"
    assert r.get_string(0) == expect
    assert r.get_string(1) == expect  # the stream is broadcast: all slots


# --------------------------------------------- checkpointed replay recovery


@needs_native
def test_kill_mid_replay_resumes_from_checkpoint():
    log, expect, _ = _workload()
    faults.arm("replay.kill", after=3)
    r = _make(checkpoint_every=2)
    r.run(log)
    assert r.get_string(0) == expect
    assert r.stats.checkpoints >= 1
    assert r.stats.resumes and r.stats.resumes[0] > 0, (
        "kill resumed from scratch, not from a chunk-boundary checkpoint"
    )


@needs_native
def test_kill_without_checkpoints_restarts_from_scratch():
    log, expect, _ = _workload()
    faults.arm("replay.kill", after=2)
    r = _make()  # checkpoint_every=0: healthy path stays zero-sync
    r.run(log)
    assert r.get_string(0) == expect
    assert r.stats.resumes == [0]


@needs_native
def test_kill_mid_overlap_resumes_with_parity():
    log, expect, _ = _workload()
    faults.arm("replay.kill", after=2)
    r = _make(overlap=True, checkpoint_every=2)
    r.run(log)
    assert r.get_string(0) == expect
    assert r.stats.resumes and r.stats.resumes[0] > 0


@needs_native
def test_continuation_fault_with_checkpoints_resumes_entry_state():
    """A second run() on a state that already carries content takes an
    entry snapshot (pos=0) when checkpointing is on: a fault before the
    first chunk-boundary checkpoint resumes from the carried state, not
    from empty (re-applying the same stream is idempotent, so parity
    proves the carried content survived)."""
    log, expect, _ = _workload()
    r = _make(checkpoint_every=4)
    r.run(log)
    assert r.get_string(0) == expect
    faults.arm("replay.kill")
    r.run(log)  # idempotent continuation: same updates re-applied
    assert r.get_string(0) == expect
    # resumed from THIS run's entry snapshot, not a stale ckpt of run 1
    assert r.stats.resumes == [0]


@needs_native
def test_continuation_fault_without_checkpoints_refuses_silent_reset():
    """With checkpointing off there is no entry snapshot: recovering a
    continuation run by rebuilding an EMPTY state would silently discard
    the content integrated before this run() — the fault must surface
    instead."""
    log, _, _ = _workload()
    r = _make()  # checkpoint_every=0
    r.run(log)
    faults.arm("replay.kill")
    with pytest.raises(ik.ReplayFault):
        r.run(log)


@needs_native
def test_recovery_budget_bounds_repeated_faults():
    """An unbounded fault (n=0) must not loop forever: after
    `max_recoveries` resume attempts the fault propagates."""
    log, _, _ = _workload()
    faults.arm("replay.kill", n=0)
    r = _make(max_recoveries=2)
    with pytest.raises(ik.ReplayFault):
        r.run(log)
    assert r.stats.recoveries == 2


# ------------------------------------------------ poison-update quarantine


@needs_native
def test_poison_update_quarantined_not_aborted():
    """A corrupted (truncated) update trips the decoder's error flags;
    with quarantine on, the update is recorded and skipped — the rest of
    the stream integrates.  The poison target is the LAST update so no
    healthy update depends on it (skipping a mid-chain update voids its
    causal dependents — that still aborts, by design)."""
    from ytpu.core import Doc

    log, _, _ = _workload()
    poison = len(log) - 1
    oracle = Doc()
    for p in log[:poison]:
        oracle.apply_update_v1(p)
    expect_m1 = oracle.get_text("text").get_string()
    base = metrics.counter("replay.quarantined").value
    faults.arm("update.corrupt", after=poison)
    r = _make(quarantine=True)
    r.run(log)
    assert r.stats.quarantined == [poison]
    assert r.get_string(0) == expect_m1
    assert metrics.counter("replay.quarantined").value == base + 1

    # same stream through the overlap lane's deferred sticky-error path
    # on the RAW ingest lane (ISSUE-7): the corruption lands in the wire
    # table, the ON-DEVICE varint decode flags the lane into the sticky
    # scalar, and deferred host re-identification quarantines the same
    # update index the serial loop names
    faults.clear()
    ik.reset_lane_health()
    faults.arm("update.corrupt", after=poison)
    r2 = _make(overlap=True, ingest="raw", quarantine=True)
    r2.run(log)
    assert r2.stats.ingest == "raw", r2.stats
    assert r2.stats.quarantined == [poison]
    assert r2.get_string(0) == expect_m1

    # and through the host-packed fallback rung (ingest="packed" — the
    # PR-5 staging the PR-6 ladder keeps): identical quarantine outcome
    faults.clear()
    ik.reset_lane_health()
    faults.arm("update.corrupt", after=poison)
    r3 = _make(overlap=True, ingest="packed", quarantine=True)
    r3.run(log)
    assert r3.stats.ingest == "packed", r3.stats
    assert r3.stats.quarantined == [poison]
    assert r3.get_string(0) == expect_m1


@needs_native
def test_poison_update_without_quarantine_still_aborts():
    log, _, _ = _workload()
    faults.arm("update.corrupt", after=len(log) - 1)
    r = _make()
    with pytest.raises(RuntimeError, match="flagged updates"):
        r.run(log)


# ------------------------------------------- overlap engine fault paths


def test_raising_producer_never_strands_consumer():
    """A staging generator that raises must shut the pipeline down
    cleanly: the error re-raises on the caller promptly (no deadlock on
    a full queue), the staged backlog is abandoned, and the engine is
    reusable afterwards."""
    from ytpu.models.replay import OverlapPipeline

    pipe = OverlapPipeline(depth=2, stage_prefix="chaos")
    consumed = []

    def produce():
        yield 1
        yield 2
        yield 3
        raise RuntimeError("staging boom")

    t0 = time.perf_counter()
    with pytest.raises(RuntimeError, match="staging boom"):
        # slow consumer: the queue is full when the producer dies — the
        # old hand-rolled worker deadlocked exactly here
        pipe.run(produce(), lambda x: (time.sleep(0.05), consumed.append(x)))
    assert time.perf_counter() - t0 < 5.0, "consumer was stranded"
    # the engine survives for the retry the recovery path performs
    stats = pipe.run(iter([10, 11]), consumed.append)
    assert stats.consumed == 2 and consumed[-2:] == [10, 11]


def test_injected_staging_fault_recovers_end_to_end():
    if not native_available():
        pytest.skip("native codec unavailable (plan pre-scan)")
    log, expect, _ = _workload()
    faults.arm("stage.raise", prefix="replay")
    r = _make(overlap=True)
    r.run(log)
    assert r.get_string(0) == expect
    assert r.stats.recoveries >= 1


# ------------------------------------------------- hardened transport


def _run(coro):
    return asyncio.run(coro)


def test_whole_frame_deadline_and_reconnect_resync():
    """A peer that stalls mid-frame trips the typed FrameTimeout (the
    old first-byte timeout hung forever), and reconnect() resyncs the
    client through the state-vector handshake."""
    from ytpu.core import Doc
    from ytpu.sync.net import FrameTimeout, SyncClient, serve
    from ytpu.sync.server import SyncServer

    async def main():
        server = SyncServer()
        seed = server.doc("room")
        with seed.transact() as txn:
            seed.get_text("text").insert(txn, 0, "state")
        srv, port = await serve(server, idle_flush=0.05)
        c = SyncClient(Doc(client_id=31))
        await c.connect("127.0.0.1", port, "room")
        await c.pump(max_frames=4, timeout=0.3)
        assert c.doc.get_text("text").get_string() == "state"
        base_t = metrics.counter("net.frame_timeouts").value
        base_r = metrics.counter("net.reconnects").value
        # the next server write (this edit's broadcast) is truncated:
        # header + half the payload, then silence — a mid-frame stall
        faults.arm("net.truncate")
        with seed.transact() as txn:
            seed.get_text("text").insert(txn, 5, "!")
        with pytest.raises(FrameTimeout):
            await c.pump(max_frames=2, timeout=1.0, frame_timeout=0.4)
        assert metrics.counter("net.frame_timeouts").value == base_t + 1
        faults.clear()
        await c.reconnect()
        await c.pump(max_frames=4, timeout=0.5)
        assert c.doc.get_text("text").get_string() == "state!"
        assert metrics.counter("net.reconnects").value == base_r + 1
        await c.close()
        srv.close()
        await srv.wait_closed()

    _run(main())


def test_connect_backoff_retries_then_raises():
    from ytpu.core import Doc
    from ytpu.sync.net import SyncClient

    # a port that was just released: connects are refused immediately
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()

    async def main():
        base = metrics.counter("net.connect_retries").value
        c = SyncClient(Doc(client_id=32))
        t0 = time.perf_counter()
        with pytest.raises(OSError):
            await c.connect(
                "127.0.0.1", port, "room", retries=2, backoff=0.01
            )
        assert metrics.counter("net.connect_retries").value == base + 2
        assert time.perf_counter() - t0 < 5.0

    _run(main())


def test_device_server_isolates_bad_frames():
    """A malformed frame marks ONLY the offending session dead
    (net.bad_frames) — the other tenant keeps being served and nothing
    propagates into the caller."""
    from ytpu.sync.device_server import DeviceSyncServer

    srv = DeviceSyncServer(n_docs=2, capacity=256, device_authoritative=True)
    s1, _ = srv.connect_frames("a")
    s2, _ = srv.connect_frames("b")
    base = metrics.counter("net.bad_frames").value
    out = srv.receive_frames(s1, b"\xff\xff\xff\xff garbage")
    assert out == []
    assert s1.dead
    assert metrics.counter("net.bad_frames").value == base + 1
    # the healthy session still answers its handshake
    from ytpu.core.state_vector import StateVector
    from ytpu.sync.protocol import Message, SyncMessage

    step1 = Message.sync(SyncMessage.step1(StateVector({}))).encode_v1()
    replies = srv.receive_frames(s2, step1)
    assert replies and not s2.dead


def test_serve_loop_survives_poisoned_session():
    """One session whose frames blow up server-side must not take down
    the accept loop: the bad session drops, a fresh client still syncs."""
    from ytpu.core import Doc
    from ytpu.sync.net import SyncClient, serve
    from ytpu.sync.server import SyncServer

    class Poisoned(SyncServer):
        poison_ids: set = set()

        def receive_frames(self, session, data):
            if session.id in self.poison_ids:
                raise RuntimeError("server-side bug for this session")
            return super().receive_frames(session, data)

    async def main():
        server = Poisoned()
        seed = server.doc("room")
        with seed.transact() as txn:
            seed.get_text("text").insert(txn, 0, "alive")
        srv, port = await serve(server, idle_flush=0.05)
        base = metrics.counter("net.bad_frames").value
        bad = SyncClient(Doc(client_id=41))
        await bad.connect("127.0.0.1", port, "room")
        # wait for the handler to register the session, then poison it
        for _ in range(50):
            if server.tenants["room"].sessions:
                break
            await asyncio.sleep(0.02)
        server.poison_ids = {server.tenants["room"].sessions[-1].id}
        with bad.doc.transact() as txn:
            bad.doc.get_text("text").insert(txn, 0, "x")
        await bad.flush()
        await asyncio.sleep(0.2)  # server hits the poisoned path
        assert metrics.counter("net.bad_frames").value == base + 1
        # accept loop and tenant still serve a fresh client
        good = SyncClient(Doc(client_id=42))
        await good.connect("127.0.0.1", port, "room")
        await good.pump(max_frames=4, timeout=0.5)
        assert good.doc.get_text("text").get_string() == "alive"
        await bad.close()
        await good.close()
        srv.close()
        await srv.wait_closed()

    _run(main())


# ----------------------------------------------- fused interpret (LAST)


@needs_native
def test_fused_interpret_dispatch_fault_demotes():
    """The ladder under interpret-mode Pallas: the injected fault fires
    BEFORE the kernel, so this exercises the same demote-and-retry path
    the TPU worker takes on a hostile shape family.  Skips (memoized)
    where this jax build cannot interpret the fused kernel."""
    log, expect, _ = _workload()

    def thunk():
        # after=1: chunk 0 really runs the interpreted fused kernel
        # (surfacing this build's NotImplementedError for the memoized
        # skip), chunk 1 faults and demotes
        faults.arm("dispatch.fail", lane="fused", after=1)
        r = _make(lane="fused", interpret=True)
        r.run(log)
        return r

    r = run_or_skip(thunk)
    assert r.get_string(0) == expect
    assert r.stats.demotions >= 1
