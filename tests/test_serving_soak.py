"""Multi-tenant serving soak subsystem (ISSUE-9): seeded scenario
replayability, soak byte parity across checkpoint/restore and live
rebalance, admission shed/overload-reply paths, the raw-ingest fast
lane, per-session net gauges, and a chaos variant arming transport
faults during a socket soak.

Suite-cost hygiene: every device-touching test here shares ONE
DeviceSyncServer shape family — (n_docs=4, capacity=256), the same
family tests/test_device_server.py compiles earlier in the run — and one
module-scoped clean soak whose report the parity tests compare against.
The CPU mini-soak is tens of sessions over a seconds-scale schedule.
"""

import asyncio

import numpy as np
import pytest

from ytpu.utils import metrics
from ytpu.utils.faults import faults

needs_native = pytest.mark.usefixtures("native_lib")

N_DOCS, CAPACITY = 4, 256
SEED = 5


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


def _cfg(**kw):
    from ytpu.serving import ScenarioConfig

    base = dict(
        n_tenants=3, n_sessions=8, events_per_session=8, seed=SEED
    )
    base.update(kw)
    return ScenarioConfig(**base)


def _fresh_server():
    from ytpu.sync.device_server import DeviceSyncServer

    return DeviceSyncServer(n_docs=N_DOCS, capacity=CAPACITY)


_CLEAN: dict = {}


def _clean_soak() -> dict:
    """One clean mini-soak per test process; the parity tests compare
    their digests against this run's (and pay no second compile)."""
    if not _CLEAN:
        from ytpu.serving import Scenario, SoakDriver

        driver = SoakDriver(_fresh_server(), Scenario(_cfg()), flush_every=4)
        _CLEAN["report"] = driver.run()
        _CLEAN["server"] = driver.server
    return _CLEAN


# ------------------------------------------------------------- scenario


def test_scenario_same_seed_is_byte_deterministic():
    from ytpu.serving import Scenario

    a, b = Scenario(_cfg()), Scenario(_cfg())
    assert a.digest() == b.digest()
    assert [e[1:] for e in a.events()] == [e[1:] for e in b.events()]
    # seed and round both perturb the stream
    assert a.digest() != Scenario(_cfg(seed=SEED + 1)).digest()
    assert a.digest() != a.with_round(1).digest()


def test_scenario_preserves_per_session_order_and_mixes_kinds():
    from ytpu.serving import Scenario

    sc = Scenario(_cfg(n_sessions=16, events_per_session=12))
    kinds = {e.kind for e in sc.events()}
    assert "apply" in kinds and len(kinds) >= 3, kinds
    # order within a session must match its script (CRDT causality)
    per = {}
    for ev in sc.events():
        per.setdefault(ev.session, []).append((ev.kind, ev.payload))
    for script in sc.sessions:
        assert per[script.sid] == script.events
    # Zipf skew: the hot tenant holds the plurality of sessions
    by_tenant = {}
    for s in sc.sessions:
        by_tenant[s.tenant] = by_tenant.get(s.tenant, 0) + 1
    assert by_tenant.get("tenant0", 0) == max(by_tenant.values())


# ------------------------------------------------------------ admission


def test_token_bucket_is_deterministic():
    from ytpu.serving import AdmissionController, QueueFull, RateLimited

    now = [0.0]

    def clock():
        return now[0]

    adm = AdmissionController(
        max_queue=2, rate=10.0, burst=2.0, policy="defer", clock=clock,
    )
    adm.admit("t", queue_depth=0)
    adm.admit("t", queue_depth=1)
    with pytest.raises(QueueFull):
        adm.admit("t", queue_depth=2)
    with pytest.raises(RateLimited) as ri:
        adm.admit("t", queue_depth=0)  # burst of 2 spent
    assert ri.value.retry_after_s == pytest.approx(0.1)
    now[0] += 0.1  # one token refills
    adm.admit("t", queue_depth=0)
    with pytest.raises(RateLimited):
        adm.admit("t", queue_depth=0)  # the refilled token is spent again


# ------------------------------------------------------------- the soak


@needs_native
def test_mini_soak_scores_and_matches_oracle():
    from ytpu.serving import Scenario

    bundle = _clean_soak()
    rep, server = bundle["report"], bundle["server"]
    assert rep["complete"] and rep["rounds"] == 1
    assert rep["applied"] > 0 and rep["updates_per_s"] > 0
    assert rep["mirror_parity"] is True
    # SLO fields: raw + floor-subtracted, adjusted never above raw
    assert rep["rtt_floor_ms"] >= 0
    for k in ("apply", "apply_e2e", "diff"):
        assert rep[f"{k}_p50_ms_adj"] <= rep[f"{k}_p50_ms"]
        assert rep[f"{k}_p99_ms_adj"] <= rep[f"{k}_p99_ms"]
    assert rep["apply_count"] > 0 and rep["diff_count"] > 0
    # final tenant states equal the scenario's CRDT merge oracle
    oracle = Scenario(_cfg()).expected_texts()
    for tenant, text in oracle.items():
        assert server.device_text(tenant) == text


@needs_native
def test_same_seed_soak_runs_land_byte_equal_states():
    from ytpu.serving import Scenario, SoakDriver

    clean = _clean_soak()["report"]
    again = SoakDriver(
        _fresh_server(), Scenario(_cfg()), flush_every=4
    ).run()
    assert again["scenario_digest"] == clean["scenario_digest"]
    assert again["state_digest"] == clean["state_digest"]


@needs_native
def test_checkpoint_restore_and_rebalance_keep_byte_parity(tmp_path):
    from ytpu.serving import Scenario, SoakDriver

    clean = _clean_soak()["report"]
    churn = SoakDriver(
        _fresh_server(),
        Scenario(_cfg()),
        flush_every=4,
        checkpoint_at=0.45,
        rebalance_at=0.7,
        ckpt_dir=str(tmp_path),
    ).run()
    assert churn["checkpoints"] == 1
    assert churn["rebalances"] == 1
    assert churn.get("rebalance_parity_failures", 0) == 0
    assert churn["state_digest"] == clean["state_digest"]
    assert metrics.counter("sync.rebalances").value >= 1


@needs_native
def test_live_rebalance_moves_slot_and_keeps_traffic_flowing():
    """Direct rebalance contract: the tenant's slot changes, its text
    survives byte-exact, and post-rebalance updates land in the NEW slot
    (the mirror observer resolves slots dynamically)."""
    from ytpu.core import Doc
    from ytpu.sync.protocol import Message, SyncMessage

    server = _fresh_server()
    sess, _ = server.connect_frames("mv")
    peer = Doc(client_id=77)
    txt = peer.get_text("text")
    with peer.transact() as txn:
        txt.insert(txn, 0, "before ")
    server.receive_frames(
        sess,
        Message.sync(
            SyncMessage.update(peer.encode_state_as_update_v1())
        ).encode_v1(),
    )
    server.flush_device()
    old = server.slot_of("mv")
    new = server.rebalance_tenant("mv")
    assert new != old and server.slot_of("mv") == new
    assert server.device_text("mv") == "before "
    with peer.transact() as txn:
        txt.insert(txn, len("before "), "after")
    sv = server.doc("mv").state_vector()
    server.receive_frames(
        sess,
        Message.sync(
            SyncMessage.update(peer.encode_state_as_update_v1(sv))
        ).encode_v1(),
    )
    server.flush_device()
    assert server.device_text("mv") == "before after"
    # explicit destination: the claimed slot must leave the free list,
    # or a later tenant's _assign_slot would share it (allocator hole)
    back = server.rebalance_tenant("mv", to_slot=old)
    assert back == old and server.slot_of("mv") == old
    assert server.device_text("mv") == "before after"
    server.connect_frames("other")
    assert server.slot_of("other") != old


# ----------------------------------------------- admission × the server


@needs_native
def test_admission_defer_replies_busy_and_converges():
    from ytpu.serving import AdmissionController, Scenario, SoakDriver

    clean = _clean_soak()["report"]
    busy = SoakDriver(
        _fresh_server(),
        Scenario(_cfg()),
        admission=AdmissionController(max_queue=2, policy="defer"),
        flush_every=64,  # queues pile up → the bound trips
    ).run()
    assert busy["busy_replies"] >= 1
    assert busy["admission"]["rejected_queue_full"] >= 1
    assert metrics.counter("sync.busy_replies").value >= 1
    # defer loses nothing: retries drain and parity holds
    assert busy["state_digest"] == clean["state_digest"]


@needs_native
def test_admission_shed_kills_session_with_attribution():
    from ytpu.serving import AdmissionController, Scenario, SoakDriver

    dropped = metrics.counter(
        "net.sessions_dropped", labelnames=("reason",)
    ).labels("shed")
    before = dropped.value
    rep = SoakDriver(
        _fresh_server(),
        Scenario(_cfg()),
        admission=AdmissionController(max_queue=1, policy="shed"),
        flush_every=64,
    ).run()
    assert dropped.value > before
    # shed is lossy by design: the server applied fewer updates than the
    # driver submitted (refusals kill the session instead of replying)
    assert rep["applied_server"] < rep["applied"]


@needs_native
def test_injected_admission_reject_exercises_busy_path():
    from ytpu.serving import AdmissionController, Scenario, SoakDriver

    clean = _clean_soak()["report"]
    faults.arm("admission.reject", n=2)
    rep = SoakDriver(
        _fresh_server(),
        Scenario(_cfg()),
        admission=AdmissionController(max_queue=None, policy="defer"),
        flush_every=4,
    ).run()
    assert rep["busy_replies"] >= 2
    assert rep["admission"]["rejected_injected"] >= 2
    assert rep["state_digest"] == clean["state_digest"]


@needs_native
def test_session_kill_fault_reconnects_with_parity():
    from ytpu.serving import Scenario, SoakDriver

    clean = _clean_soak()["report"]
    faults.arm("session.kill", after=5, n=3)
    rep = SoakDriver(
        _fresh_server(), Scenario(_cfg()), flush_every=4
    ).run()
    assert rep["session_kills"] == 3
    assert rep["state_digest"] == clean["state_digest"]


# --------------------------------------- diff path through the pipeline


@needs_native
def test_soak_diff_path_through_pipeline_matches_serial():
    """ISSUE-10: in the device-authoritative serving mode — the one
    where the device batch answers SyncStep1s — every soak diff routes
    through the encode `DiffPipeline`, the run lands the SAME state
    digest as the mirrored clean run (the pipeline produced the pinned
    digest), and re-answering each tenant's step1 is byte-equal to the
    serial `finish_encode_diff_batch` path.  (Mirrored-mode soaks keep
    answering diffs from the authoritative HOST doc by design — their
    `diff_pipeline_runs` reads 0.)"""
    import jax.numpy as jnp

    from ytpu.core import StateVector
    from ytpu.models import batch_doc as bd
    from ytpu.serving import Scenario, SoakDriver
    from ytpu.sync.device_server import DeviceSyncServer

    clean = _clean_soak()["report"]
    assert clean["diff_pipeline_runs"] == 0  # mirrored mode: host path
    driver = SoakDriver(
        DeviceSyncServer(
            n_docs=N_DOCS, capacity=CAPACITY, device_authoritative=True
        ),
        Scenario(_cfg()),
        flush_every=4,
    )
    rep = driver.run()
    server = driver.server
    assert rep["diffs"] > 0
    # each diff event (plus the RTT idle-echo probes) ran the pipeline,
    # and none of them had to demote off the native batched path
    assert rep["diff_pipeline_runs"] >= rep["diffs"]
    assert rep["encode_demotions"] == 0
    assert rep["state_digest"] == clean["state_digest"]
    for t in sorted(server.tenants):
        try:
            slot = server.slot_of(t)
        except KeyError:
            continue  # host-resident tenant: no device diff to compare
        piped = server.device_encode_diff(t, StateVector())
        remote, n_clients = server._remote_matrix([(slot, StateVector())])
        ship, offsets, _sv, deleted = bd.encode_diff_batch(
            server.ingestor.state, jnp.asarray(remote), n_clients
        )
        serial = bd.finish_encode_diff_batch(
            server.ingestor.state,
            [slot],
            ship,
            offsets,
            deleted,
            server.ingestor.enc,
            payloads=server.ingestor.payloads,
            root_name=server._root_names.get(t),
        )[0]
        assert piped == server._merge_pending(slot, serial), t


@needs_native
def test_device_encode_diff_many_fanout_parity():
    """The batched fan-out entry answers many tenants in one pipelined
    pass, byte-equal to the per-tenant path; duplicate tenants are
    rejected (they would collide on the slot's remote-clock row)."""
    from ytpu.core import StateVector

    server = _clean_soak()["server"]
    tenants = [t for t in sorted(server.tenants) if t in server._slot_of]
    assert len(tenants) >= 2
    many = server.device_encode_diff_many(
        [(t, StateVector()) for t in tenants]
    )
    for t, payload in zip(tenants, many):
        assert payload == server.device_encode_diff(t, StateVector()), t
    with pytest.raises(ValueError, match="one request per tenant"):
        server.device_encode_diff_many(
            [(tenants[0], StateVector()), (tenants[0], StateVector())]
        )


# -------------------------------------------------- chaos over sockets


@needs_native
def test_chaos_soak_survives_transport_faults():
    """The ISSUE-9 chaos variant: the scenario over real sockets with
    `net.drop`/`net.delay` armed mid-soak (the ISSUE-6 sites).  Scores
    survivability: every fault fires, the accept loop outlives them, and
    the mirrored device batch stays consistent with the host docs for
    whatever traffic did land."""
    from ytpu.serving import Scenario, run_soak_tcp

    server = _fresh_server()
    armed = []  # per-spec fired counters: reset-proof assertion surface

    def arm():
        armed.append(faults.arm("net.drop", after=3, n=2))
        armed.append(faults.arm("net.delay", ms=5, n=4))

    counts = run_soak_tcp(
        server,
        Scenario(_cfg(n_sessions=6, events_per_session=6)),
        arm=arm,
        budget_s=20.0,
        frame_deadline=1.0,
    )
    faults.clear()
    assert counts["survived"] and counts["sent"] > 0
    assert sum(s.fired for s in armed) >= 2, (counts, armed)
    server.flush_device()
    for t in sorted(server.tenants):
        host = server.doc(t).get_text("text").get_string()
        assert server.device_text(t) == host


def test_net_session_gauges_track_active_and_bad_frame_drops():
    from ytpu.core import Doc
    from ytpu.sync import net as net_mod
    from ytpu.sync.net import SyncClient, serve, write_frame
    from ytpu.sync.server import SyncServer

    # the live-session gauge is the transport's OWN cached series
    # (module-level in net.py): a fresh registry lookup would diverge
    # after any metrics.reset() earlier in the suite (test_metrics_trace
    # sorts before this file). The drop counter is looked up per drop,
    # so the registry's family is the one that moves.
    active = net_mod._SESSIONS_ACTIVE
    bad = metrics.counter(
        "net.sessions_dropped", labelnames=("reason",)
    ).labels("bad_frame")

    async def main():
        base_active = active.value
        base_bad = bad.value
        server = SyncServer()
        srv, port = await serve(server, idle_flush=0.05)
        a = SyncClient(Doc(client_id=61))
        await a.connect("127.0.0.1", port, "room")
        await a.pump(max_frames=2, timeout=0.3)
        assert active.value == base_active + 1
        # a second peer sends protocol garbage after its hello: its
        # session drops with reason=bad_frame, the first session lives
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        write_frame(writer, b"room")
        write_frame(writer, b"\xff\xff\xff\xff")
        await writer.drain()
        for _ in range(50):
            if bad.value > base_bad:
                break
            await asyncio.sleep(0.05)
        assert bad.value > base_bad
        writer.close()
        await a.close()
        for _ in range(50):
            if active.value == base_active:
                break
            await asyncio.sleep(0.05)
        assert active.value == base_active
        srv.close()
        await srv.wait_closed()

    asyncio.run(main())


# ------------------------------------------- raw-ingest fast lane (ROADMAP 2)


@needs_native
def test_ingest_fast_lane_raw_matches_packed_byte_exactly():
    """The ingest fast lane ships raw concatenated wire bytes + offsets
    and gathers the lane matrix ON DEVICE (`gather_raw_lanes`): final
    device state must be byte-identical to the host-packed path, with
    the fast lane proven to have actually run."""
    import jax

    from ytpu.core import Doc
    from ytpu.models.batch_doc import get_string
    from ytpu.models.ingest import BatchIngestor

    doc = Doc(client_id=1)
    log = []
    doc.observe_update_v1(lambda p, o, t: log.append(p))
    txt = doc.get_text("text")
    for i in range(8):
        with doc.transact() as txn:
            if i % 3 == 2:
                txt.remove_range(txn, 0, 1)
            else:
                txt.insert(txn, 0, f"w{i}")
    expect = txt.get_string()
    states = {}
    for mode in ("raw", "packed"):
        ing = BatchIngestor(2, CAPACITY, ingest=mode)
        for p in log:
            ing.apply_bytes([p, None])
        assert ing.fast_docs > 0, (mode, ing.slow_docs)
        assert get_string(ing.state, 0, ing.payloads) == expect
        states[mode] = ing.state
    for a, b in zip(
        jax.tree_util.tree_leaves(states["raw"]),
        jax.tree_util.tree_leaves(states["packed"]),
    ):
        assert (np.asarray(a) == np.asarray(b)).all()


def test_ingest_rejects_unknown_mode():
    from ytpu.models.ingest import BatchIngestor

    with pytest.raises(ValueError, match="ingest must be"):
        BatchIngestor(2, 64, ingest="zip")


@needs_native
def test_decode_v2_raw_stream_parity_end_to_end():
    """V2 raw ingestion end-to-end through the DEVICE decoder:
    `decode_updates_v2_raw` (flat arena + on-device gather) must produce
    the identical decoded stream and flags as `decode_updates_v2` over
    the host-packed matrix (ISSUE-9 satellite; the pack-level byte
    parity lives in test_async_raw_ingest)."""
    import jax

    from ytpu.core import Doc, Update
    from ytpu.ops.decode_v2 import (
        decode_updates_v2,
        decode_updates_v2_raw,
        pack_updates_v2,
    )
    from ytpu.ops.decode_v2 import pack_updates_v2_raw

    import jax.numpy as jnp

    doc = Doc(client_id=5)
    log = []
    doc.observe_update_v1(lambda p, o, t: log.append(p))
    txt = doc.get_text("text")
    for i in range(4):
        with doc.transact() as txn:
            txt.insert(txn, i, "abcd"[i])
    v2 = [Update.decode_v1(p).encode_v2() for p in log]
    buf, lens, spans, side = pack_updates_v2(v2)
    packed_stream, packed_flags = decode_updates_v2(
        jnp.asarray(buf), jnp.asarray(lens), spans,
        max_rows=4, max_dels=4, sidecar=side,
    )
    wire, offs, row_lens, rlens, rspans, rside, width = pack_updates_v2_raw(v2)
    raw_stream, raw_flags = decode_updates_v2_raw(
        wire, offs, row_lens, rlens, rspans, width,
        max_rows=4, max_dels=4, sidecar=rside,
    )
    assert (np.asarray(raw_flags) == np.asarray(packed_flags)).all()
    for a, b in zip(
        jax.tree_util.tree_leaves(raw_stream),
        jax.tree_util.tree_leaves(packed_stream),
    ):
        assert (np.asarray(a) == np.asarray(b)).all()
