"""Multi-replica federation (ISSUE-13): peer sync mesh, O(1) incremental
state commitments, partition/heal chaos, forced failover.

Layering: the protocol/commitment/mesh tests are HOST-ONLY (no jax —
`SyncServer` replicas; milliseconds), the device-backed mesh test reuses
the suite-wide (n_docs=4, capacity=256) `DeviceSyncServer` family
compiled by test_device_server/test_serving_soak, and the device
commitment test folds the block columns of a (2, 256) `BatchIngestor`.
"""

import urllib.request

import pytest

from ytpu.core import Doc
from ytpu.serving import (
    FederatedSoakDriver,
    Scenario,
    ScenarioConfig,
    SoakDriver,
)
from ytpu.serving.soak import server_state_digest
from ytpu.sync.commitment import (
    MASK32,
    TenantCommitments,
    commitment_of_clocks,
    device_commit_of_clocks,
)
from ytpu.sync.protocol import (
    Message,
    OwnershipHandoff,
    SyncMessage,
    commit_message,
    decode_commit,
    decode_ownership,
    message_reader,
    ownership_message,
)
from ytpu.sync.replica import DivergenceFault, ReplicaMesh
from ytpu.sync.server import SyncServer
from ytpu.utils import metrics
from ytpu.utils.faults import faults

CFG = ScenarioConfig(n_tenants=3, n_sessions=8, events_per_session=8, seed=5)


def _clean_digest() -> str:
    """The PR-9 oracle: the scenario's clean single-server digest."""
    return SoakDriver(SyncServer(), Scenario(CFG), flush_every=4).run()[
        "state_digest"
    ]


def _write(server, tenant: str, doc: Doc, text: str, at: int = 0) -> None:
    """One client edit delivered to `server` as a protocol update frame."""
    sess, _ = server.connect_frames(tenant)
    with doc.transact() as txn:
        doc.get_text("text").insert(txn, at, text)
    upd = doc.encode_state_as_update_v1()
    server.receive_frames(
        sess, Message.sync(SyncMessage.update(upd)).encode_v1()
    )
    server.disconnect(sess)


# --------------------------------------------------------------- protocol


def test_commit_and_ownership_frame_round_trip():
    big = 0xDEADBEEF_CAFEF00D  # exercises the 64-bit lo/hi split
    msg = commit_message("tenant0", big, round_=7)
    frame = msg.encode_v1()
    (decoded,) = list(message_reader(frame))
    assert decoded.kind == 5
    assert decode_commit(decoded.body) == ("tenant0", big, 7)

    h = OwnershipHandoff("tenant1", "replica-b", 42)
    frame = ownership_message(h).encode_v1()
    (decoded,) = list(message_reader(frame))
    assert decoded.kind == 6
    assert decode_ownership(decoded.body) == h


# ------------------------------------------------------------- commitment


def test_commitment_incremental_equals_full_and_is_order_free():
    tc = TenantCommitments()
    # fold in three deltas, out of client order, across calls
    tc.refresh("t", [(7, 3)])
    tc.refresh("t", [(7, 3), (123456, 10)])
    inc = tc.refresh("t", [(7, 9), (123456, 10), (2, 1)])
    assert inc == commitment_of_clocks({2: 1, 7: 9, 123456: 10})
    # order independence of the full fold (additive homomorphism)
    assert commitment_of_clocks({7: 9, 2: 1, 123456: 10}) == inc
    # a shrunk clock (checkpoint-restored server) forces a clean rebuild
    # from the sv as given — the tracker mirrors the server, not history
    assert tc.refresh("t", [(7, 4)]) == commitment_of_clocks({7: 4})


def test_commit_corrupt_poisons_the_incremental_fold_stickily():
    faults.clear()
    spec = faults.arm("commit.corrupt")
    try:
        tc = TenantCommitments()
        poisoned = tc.refresh("t", [(7, 5)])
    finally:
        faults.clear()
    assert spec.fired == 1
    truth = commitment_of_clocks({7: 5})
    assert poisoned != truth
    # sticky: later (un-injected) folds keep the divergence — nothing
    # re-derives the poisoned prefix...
    assert tc.refresh("t", [(7, 8)]) != commitment_of_clocks({7: 8})
    # ...except the authoritative recompute (the recovery path)
    assert tc.recompute("t", [(7, 8)]) == commitment_of_clocks({7: 8})


# ---------------------------------------------------- anti-entropy + mesh


def test_anti_entropy_compares_commitments_and_pulls_only_on_mismatch():
    a, b = SyncServer(), SyncServer()
    mesh = ReplicaMesh([("a", a), ("b", b)], tenants=["room"])
    mesh.sync_round()
    # agreement round: one O(1) probe each way, nothing pulled
    rep = mesh.anti_entropy_round()
    assert rep["compared"] >= 1 and rep["mismatches"] == 0, rep
    # diverge replica a only (no sync round in between)
    _write(a, "room", Doc(client_id=301), "only-on-a ")
    rep = mesh.anti_entropy_round()
    assert rep["mismatches"] >= 1 and rep["pulled"] >= 1, rep
    assert rep["divergences"] == 0, rep
    mismatch_bytes = rep["bytes"]
    assert b.doc("room").get_text("text").get_string() == "only-on-a "
    # repaired: back to the cheap path — an agreement round costs only
    # the two commit probes (the O(1) claim, in bytes), strictly less
    # than the round that had to pull the SV-diff
    rep = mesh.anti_entropy_round()
    assert rep["mismatches"] == 0, rep
    assert 0 < rep["bytes"] < min(mismatch_bytes, 64), (rep, mismatch_bytes)


def test_partition_heal_converges_to_scenario_oracle():
    clean = _clean_digest()
    mesh = ReplicaMesh([("r0", SyncServer()), ("r1", SyncServer())])
    rep = FederatedSoakDriver(
        mesh,
        Scenario(CFG),
        sync_every=6,
        anti_entropy_every=10,
        partition_at=0.25,
        heal_at=0.6,
    ).run()
    assert rep["partitions"] >= 1 and rep["heals"] >= 1, rep
    assert rep["converged"], rep
    assert rep["state_digest"] == clean, rep
    assert set(rep["replica_digests"]) == {"r0", "r1"}
    assert len(set(rep["replica_digests"].values())) == 1


def test_forced_failover_sessions_reconnect_and_ownership_migrates():
    clean = _clean_digest()
    dropped_before = metrics.counter(
        "net.sessions_dropped", labelnames=("reason",)
    ).labels("failover").value
    mesh = ReplicaMesh([(f"r{i}", SyncServer()) for i in range(3)])
    rep = FederatedSoakDriver(
        mesh,
        Scenario(CFG),
        sync_every=6,
        anti_entropy_every=12,
        failover_at=0.7,
        failover_replica="r2",
    ).run()
    assert rep["failovers"] == 1, rep
    assert not mesh.replicas["r2"].alive
    assert rep["failover_sessions_dropped"] >= 1, rep
    assert rep["failover_reconnects"] >= 1, rep
    # the metric carries the attribution (reason="failover")
    dropped = metrics.counter(
        "net.sessions_dropped", labelnames=("reason",)
    ).labels("failover").value - dropped_before
    assert dropped == rep["failover_sessions_dropped"], (dropped, rep)
    # every tenant's owner is a survivor, epoch bumped past the handoff
    for tenant, (owner, epoch) in mesh.owner.items():
        assert owner != "r2", (tenant, owner)
        assert mesh.replicas[owner].alive
    # survivors hold the oracle state — convergence re-established
    assert rep["converged"] and rep["state_digest"] == clean, rep


def test_migration_is_typed_epoch_guarded_handoff():
    mesh = ReplicaMesh(
        [("a", SyncServer()), ("b", SyncServer())], tenants=["room"]
    )
    doc = Doc(client_id=401)
    _write(mesh.replicas["a"].server, "room", doc, "pre-migration ")
    epoch = mesh.migrate_tenant("room", "b")
    assert mesh.owner["room"] == ("b", epoch)
    assert mesh.route("room").id == "b"
    # a stale handoff (≤ current epoch) must be ignored, not applied
    assert not mesh._apply_handoff(OwnershipHandoff("room", "a", epoch))
    assert mesh.owner["room"][0] == "b"
    # migration drained first: the new owner already holds the state
    assert (
        mesh.replicas["b"].server.doc("room").get_text("text").get_string()
        == "pre-migration "
    )


def test_replica_lag_defers_but_loses_nothing():
    a, b = SyncServer(), SyncServer()
    mesh = ReplicaMesh([("a", a), ("b", b)], tenants=["room"])
    mesh.sync_round()
    faults.clear()
    spec = faults.arm("replica.lag", rounds=2)
    try:
        _write(a, "room", Doc(client_id=501), "laggy ")
        mesh.sync_round()  # fires the site: delivery deferred
        assert spec.fired == 1
        assert b.doc("room").get_text("text").get_string() == ""
        for _ in range(3):
            mesh.sync_round()
        assert b.doc("room").get_text("text").get_string() == "laggy "
    finally:
        faults.clear()


def test_partition_and_heal_fault_sites_via_grammar():
    a, b = SyncServer(), SyncServer()
    mesh = ReplicaMesh([("a", a), ("b", b)], tenants=["room"])
    mesh.sync_round()
    faults.clear()
    faults.configure("replica.partition;replica.heal:after=1")
    try:
        _write(a, "room", Doc(client_id=601), "dropped? ")
        mesh.sync_round()  # partition fires: the frame is DROPPED
        assert b.doc("room").get_text("text").get_string() == ""
        assert (
            metrics.counter(
                "replica.frames_dropped", labelnames=("reason",)
            ).labels("partition").value
            >= 1
        )
        mesh.sync_round()  # heal fires: gossip queues the SV resync
        mesh.sync_round()
        assert b.doc("room").get_text("text").get_string() == "dropped? "
    finally:
        faults.clear()


def test_bare_mesh_sync_rounds_quiesce():
    """A ≥3-replica mesh with no client traffic must reach quiescence:
    awareness snapshots are rebroadcast unconditionally by servers, so
    without the per-replica payload dedup covering them one snapshot
    would circulate the triangle forever and every sync round would
    burn its full pass budget (review-caught liveness pin)."""
    mesh = ReplicaMesh(
        [(f"r{i}", SyncServer()) for i in range(3)], tenants=["room"]
    )
    mesh.sync_round()  # greetings + their fan-out settle here
    rep = mesh.sync_round()
    assert rep["frames"] == 0 and rep["passes"] == 1, rep


def _round_under_drop_policy(client_id: int, text: str, over_the_link: bool):
    """a and b meshed on "room", b under admission policy="drop": one write
    on a, `admission.reject` armed once, one sync round. With
    `over_the_link` False the session on b that a's frames arrive on is an
    ordinary client's for that round. Returns (mesh, b, the armed fault)."""
    from ytpu.serving import AdmissionController

    a, b = SyncServer(), SyncServer()
    mesh = ReplicaMesh([("a", a), ("b", b)], tenants=["room"])
    mesh.sync_round()
    b.admission = AdmissionController(policy="drop")
    _write(a, "room", Doc(client_id=client_id), text)
    (link,) = mesh._tenant_links("room")
    recv = link.sess_b if link.b.server is b else link.sess_a
    assert recv.mesh_link
    recv.mesh_link = over_the_link
    faults.clear()
    spec = faults.arm("admission.reject", n=1)
    try:
        mesh.sync_round()
    finally:
        faults.clear()
        recv.mesh_link = True
    assert not recv.dead
    return mesh, b, spec


def test_silently_dropped_update_is_not_blacklisted():
    """An update the receiving server REFUSED without any reply
    (admission policy="drop") must not enter the dedup set: the
    mark-on-success gate reads the applied counter, so the SV-resync
    retransmission — byte-identical payload — still lands (review-caught
    correctness pin).

    A mesh link is never refused by admission (the next test), so for the
    refused round the receiving session is made an ordinary client's: the
    armed `admission.reject` is consulted, and the gate in
    `replica._PeerLink._deliver` sees an update that did not apply."""
    mesh, b, spec = _round_under_drop_policy(801, "must-arrive ", over_the_link=False)
    assert spec.fired == 1  # the update crossed the link and was refused
    assert b.doc("room").get_text("text").get_string() == ""
    b.admission = None
    rep = mesh.anti_entropy_round()
    assert rep["mismatches"] >= 1 and rep["pulled"] >= 1, rep
    assert b.doc("room").get_text("text").get_string() == "must-arrive "


def test_admission_never_refuses_an_update_over_a_mesh_link():
    """Peer replication bypasses the client valve (`SyncServer._admit_update`:
    a refused peer update is not load shedding, it is data loss in flight):
    under policy="drop" with `admission.reject` armed, an update over an
    untouched link is applied and the fault is never consulted."""
    _, b, spec = _round_under_drop_policy(802, "over-the-link ", over_the_link=True)
    assert spec.fired == 0
    assert b.doc("room").get_text("text").get_string() == "over-the-link "


# ------------------------------------------- divergence + health surface


def test_commit_corrupt_divergence_quarantines_and_degrades_healthz():
    from ytpu.utils.telemetry import TelemetryServer

    a, b = SyncServer(), SyncServer()
    mesh = ReplicaMesh([("a", a), ("b", b)], tenants=["room"])
    mesh.sync_round()
    faults.clear()
    spec = faults.arm("commit.corrupt")
    try:
        _write(a, "room", Doc(client_id=701), "diverge-me ")
        mesh.sync_round()  # replicas converge; one tracker gets poisoned
        div_before = metrics.counter("replica.divergences").value
        with pytest.raises(DivergenceFault) as exc:
            mesh.anti_entropy_round(strict=True)
        assert spec.fired == 1
        assert exc.value.tenant == "room"
        assert "room" in mesh.quarantined
        assert metrics.counter("replica.divergences").value == div_before + 1
        # /healthz surfaces it: degraded + the tenant named
        with TelemetryServer(port=0) as t:
            mesh.attach_health(t)
            import json

            body = json.loads(
                urllib.request.urlopen(
                    f"http://127.0.0.1:{t.port}/healthz", timeout=5
                ).read()
            )
        assert body["status"] == "degraded", body
        assert body["replica"]["quarantined_tenants"] == ["room"], body
        # quarantined tenants are skipped by later rounds
        assert mesh.anti_entropy_round()["tenants"] == 0
        # recovery: authoritative rebuild clears the poison + quarantine
        rec_before = metrics.counter("replica.recoveries").value
        assert mesh.recover_tenant("room")
        assert not mesh.quarantined
        assert metrics.counter("replica.recoveries").value == rec_before + 1
        assert mesh.anti_entropy_round()["mismatches"] == 0
    finally:
        faults.clear()


# ---------------------------------------------------- device-backed mesh


def test_device_backed_mesh_federates_at_oracle_parity():
    pytest.importorskip("jax")
    from ytpu.sync.device_server import DeviceSyncServer

    cfg = ScenarioConfig(
        n_tenants=2, n_sessions=4, events_per_session=6, seed=29
    )
    clean = SoakDriver(
        DeviceSyncServer(n_docs=4, capacity=256), Scenario(cfg),
        flush_every=4,
    ).run()
    mesh = ReplicaMesh(
        [
            ("a", DeviceSyncServer(n_docs=4, capacity=256)),
            ("b", DeviceSyncServer(n_docs=4, capacity=256)),
        ]
    )
    rep = FederatedSoakDriver(
        mesh, Scenario(cfg), sync_every=4, anti_entropy_every=8,
        partition_at=0.3, heal_at=0.6,
    ).run()
    assert rep["converged"], rep
    assert rep["state_digest"] == clean["state_digest"], rep
    # the digest parity is DEVICE-rendered on both sides (slotted
    # tenants render via device_text inside server_state_digest)
    for rid in ("a", "b"):
        server = mesh.replicas[rid].server
        assert server_state_digest(server, cfg.root) == clean["state_digest"]
        for tenant in sorted(server.tenants):
            server.device_text(tenant)  # KeyError would mean host-demoted


# -------------------------------------------- device commitment readout


@pytest.fixture(scope="module")
def _multi_client_log():
    """A 3-writer shared-doc history (clients 3/5/9, inserts + deletes)
    in causal order — every lane must fold the same lattice."""
    pytest.importorskip("jax")
    docs = {c: Doc(client_id=c) for c in (3, 5, 9)}
    captured = []

    def capture(p, origin, txn):
        if origin != "relay":
            captured.append(p)

    for d in docs.values():
        d.observe_update_v1(capture)
    log = []
    for k in range(8):
        for c, d in docs.items():
            for p in log:
                d.apply_update_v1(p, origin="relay")
            txt = d.get_text("text")
            with d.transact() as txn:
                cur = txt.get_string()
                if len(cur) > 10 and (k + c) % 3 == 0:
                    txt.remove_range(txn, 2, 4)
                else:
                    txt.insert(txn, min(len(cur), c), f"c{c}k{k}")
            log.append(captured[-1])
    oracle = Doc(client_id=99)
    for p in log:
        oracle.apply_update_v1(p)
    return log, dict(oracle.state_vector()), oracle.get_text(
        "text"
    ).get_string()


@pytest.mark.parametrize("compacted", [False, True], ids=["as_integrated", "compacted"])
def test_device_commitment_fold_matches_sv_closed_form(_multi_client_log, compacted):
    """`commit_fold_blocks` over the block columns the served ingestor
    built equals the pure-Python closed form over the final state vector
    (in the ingestor's interned client ids): the block rows tile each
    client's lattice, so the row-wise fold collapses to
    `device_commit_of_clocks`, before and after the squash, GC conversion
    and defragmentation of `compact_state`."""
    import numpy as np

    from ytpu.models.batch_doc import commit_fold_blocks, get_string
    from ytpu.models.ingest import BatchIngestor
    from ytpu.ops.compaction import compact_state

    log, sv, expect_text = _multi_client_log
    ing = BatchIngestor(2, 256)
    for p in log:
        ing.apply_bytes([p, p])
    state = compact_state(ing.state) if compacted else ing.state
    assert int(np.asarray(state.error).max()) == 0
    assert get_string(state, 0, ing.payloads) == expect_text
    bl = state.blocks
    slots = np.arange(bl.client.shape[-1])[None, :]
    valid = (slots < np.asarray(state.n_blocks)[:, None]) & (np.asarray(bl.client) >= 0)
    words = np.asarray(commit_fold_blocks(bl.client, bl.clock, bl.length, valid))
    interned = {ing.enc.interner.to_idx[c]: n for c, n in sv.items()}
    per_doc = device_commit_of_clocks(interned)
    assert [int(w) for w in words] == [per_doc, per_doc], (words, per_doc)
    assert per_doc == per_doc & MASK32
    # the host federation mirror folds the SAME lattice (64-bit params,
    # same clock coverage): its incremental and full values agree on it
    tc = TenantCommitments()
    assert tc.refresh("t", sv.items()) == commitment_of_clocks(sv)
