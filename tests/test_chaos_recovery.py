"""Fault-injected resilience (ISSUE-6): the fault injector's grammar, the
overlap engine's shutdown when its producer raises, and the hardened sync
transport, all exercised through `ytpu.utils.faults` so the failure paths
run deterministically on CPU. The encode pipeline's own fault sites
(`diff.d2h_fail`, `finisher.raise`, `stage.raise`) are in
`tests/test_diff_overlap.py`.
"""

import asyncio
import socket
import time

import pytest

from ytpu.utils import metrics
from ytpu.utils.faults import FaultSpec, faults

@pytest.fixture(autouse=True)
def _clean_slate():
    """Armed faults are process-global: every test starts and ends with
    them cleared so no state leaks into the rest of the suite."""
    faults.clear()
    yield
    faults.clear()


# --------------------------------------------------------- fault injector


def test_faults_grammar_and_determinism():
    faults.configure("stage.raise:prefix=encode,after=2;net.delay:ms=7,n=3")
    specs = faults._specs
    assert [s.after for s in specs["stage.raise"]] == [2]
    assert specs["stage.raise"][0].args == {"prefix": "encode"}
    assert specs["net.delay"][0].n == 3
    # context mismatch is not an eligible pass; match fires after `after`
    assert faults.fire("stage.raise", prefix="chaos") is None
    assert faults.fire("stage.raise", prefix="encode") is None  # pass 1
    assert faults.fire("stage.raise", prefix="encode") is None  # pass 2
    assert faults.fire("stage.raise", prefix="encode") is not None  # fires
    assert faults.fire("stage.raise", prefix="encode") is None  # n=1 spent
    # p-draws are seeded: same seed → same decision sequence
    a = FaultSpec("x", n=0, p=0.5, seed=7)
    b = FaultSpec("x", n=0, p=0.5, seed=7)
    assert [a._decide() for _ in range(32)] == [b._decide() for _ in range(32)]
    # suspended(): nothing fires inside the clean-run baseline
    faults.arm("diff.d2h_fail")
    with faults.suspended():
        assert faults.fire("diff.d2h_fail") is None
    assert faults.fire("diff.d2h_fail") is not None
    # two specs armed on one site: the pass's winner spends its fire
    # budget, the loser keeps its `n` for a later pass — so
    # "net.drop;net.drop" drops TWO frames, not one
    faults.clear()
    faults.configure("net.drop;net.drop")
    assert faults.fire("net.drop") is not None
    assert faults.fire("net.drop") is not None
    assert faults.fire("net.drop") is None


# ------------------------------------------- overlap engine fault paths


def test_raising_producer_never_strands_consumer():
    """A staging generator that raises must shut the pipeline down
    cleanly: the error re-raises on the caller promptly (no deadlock on
    a full queue), the staged backlog is abandoned, and the engine is
    reusable afterwards."""
    from ytpu.models.overlap import OverlapPipeline

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
