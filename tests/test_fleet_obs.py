"""Fleet observability plane (ISSUE-15): the wire trace-context
extension's codec + backward compatibility, cross-replica trace
propagation through the in-proc mesh, the merged `/fleet` exposition
under concurrent live scrapes, and canary probing semantics.

Compatibility is the load-bearing surface here: trace frames are a
PROTOCOL_VERSION 2 extension, so an old (version-1) peer must (a) never
emit them and (b) silently ignore ones it receives — a mixed-version
mesh converges with tracing on, losing only the old replica's spans.
"""

import json
import threading
import urllib.request

from ytpu.serving import (
    CANARY_PREFIX,
    FederatedSoakDriver,
    Scenario,
    ScenarioConfig,
    SoakDriver,
    server_state_digest,
)
from ytpu.sync.protocol import (
    MSG_TRACE,
    PROTOCOL_VERSION,
    TRACE_WIRE_VERSION,
    Message,
    Protocol,
    SyncMessage,
    decode_trace,
    message_reader,
    trace_message,
)
from ytpu.sync.replica import ReplicaMesh
from ytpu.sync.server import SyncServer
from ytpu.utils import metrics
from ytpu.utils.telemetry import TelemetryServer
from ytpu.utils.trace import trace_context, tracer

CFG = ScenarioConfig(n_tenants=2, n_sessions=4, events_per_session=6, seed=29)


def _get(port: int, path: str, timeout: float = 10.0):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=timeout
    ) as r:
        return r.status, r.read().decode()


# --------------------------------------------------------------- wire codec


def test_trace_message_round_trips():
    frame = trace_message("t1234-ab", "r0").encode_v1()
    assert frame[0] == MSG_TRACE
    msgs = list(message_reader(frame))
    assert len(msgs) == 1 and msgs[0].kind == MSG_TRACE
    ver, trace, origin = decode_trace(msgs[0].body)
    assert (ver, trace, origin) == (1, "t1234-ab", "r0")
    # origin is optional on the wire (client-side emission has none)
    msg = next(iter(message_reader(trace_message("tX").encode_v1())))
    _, trace2, origin2 = decode_trace(msg.body)
    assert (trace2, origin2) == ("tX", "")


def test_protocol_version_gates_emission_not_tolerance():
    """Version-1 servers never EMIT trace frames; EVERY version ignores
    a received one (forward tolerance is unconditional — an old binary
    meeting a new peer must not drop the session as a bad frame)."""
    assert PROTOCOL_VERSION >= TRACE_WIRE_VERSION
    for version in (1, PROTOCOL_VERSION):
        server = SyncServer(protocol=Protocol(version=version))
        sess, _greet = server.connect_frames("t0")
        # a bare trace frame: no reply, no error, session stays alive
        replies = server.receive_frames(
            sess, trace_message("tZ", "rX").encode_v1()
        )
        assert replies == []
        assert not sess.dead
        # and the session still serves real traffic afterwards
        sv_frame = Message.sync(
            SyncMessage.step1(server.tenant_state_vector("t0"))
        ).encode_v1()
        server.receive_frames(sess, sv_frame)
        assert not sess.dead


def test_old_version_server_emits_no_trace_frames():
    """The broadcast path of a version-1 server must stay byte-clean of
    MSG_TRACE even while the tracer runs with an ambient context."""
    old = SyncServer(protocol=Protocol(version=1))
    new = SyncServer()
    import ytpu.core as _core

    doc = _core.Doc(client_id=77)
    captured = []
    unsub = doc.observe_update_v1(lambda p, o, t: captured.append(p))
    txt = doc.get_text("text")
    with doc.transact() as txn:
        txt.insert(txn, 0, "hello")
    unsub()
    update = Message.sync(SyncMessage.update(captured[0])).encode_v1()
    tracer.enabled = True
    try:
        for server, expect_trace in ((old, False), (new, True)):
            writer, _ = server.connect_frames("t0")
            watcher, _ = server.connect_frames("t0")
            server.drain(watcher)
            with trace_context(tenant="t0", replica="rme"):
                server.receive_frames(writer, update)
            frames = server.drain(watcher)
            kinds = {f[0] for f in frames if f}
            assert (MSG_TRACE in kinds) == expect_trace, (
                server.protocol.version, kinds,
            )
    finally:
        tracer.enabled = False


def test_mixed_version_mesh_converges_with_tracing_on():
    """A 3-replica mesh whose MIDDLE replica speaks protocol version 1
    must converge to the clean oracle digest with the tracer live: new
    replicas' trace frames cross the old one unharmed (swallowed), and
    the old one simply contributes no propagated spans."""
    clean = SoakDriver(SyncServer(), Scenario(CFG), flush_every=4).run()
    mesh = ReplicaMesh(
        [
            ("r0", SyncServer()),
            ("r1", SyncServer(protocol=Protocol(version=1))),
            ("r2", SyncServer()),
        ]
    )
    tracer.enabled = True
    try:
        tracer.clear()
        rep = FederatedSoakDriver(
            mesh, Scenario(CFG), sync_every=4, anti_entropy_every=8,
            canary_every=4,
        ).run()
    finally:
        tracer.enabled = False
        tracer.clear()
    assert rep["converged"], rep
    assert rep["state_digest"] == clean["state_digest"]
    assert rep["canary"]["availability_min"] == 1.0, rep["canary"]


# ------------------------------------------------- /fleet + concurrency


def _assert_untorn_exposition(text: str):
    """A merged exposition is torn iff a family's series appear outside
    its contiguous TYPE block: every TYPE header exactly once, every
    sample under the most recent header's family."""
    seen_types = []
    current = None
    for line in text.strip().splitlines():
        if not line:
            continue
        if line.startswith("# TYPE "):
            fam = line.split()[2]
            assert fam not in seen_types, f"family {fam} split: torn merge"
            seen_types.append(fam)
            current = fam
        else:
            name = line.split("{", 1)[0].split()[0]
            assert current is not None and name.startswith(current), (
                f"sample {name!r} outside its TYPE block {current!r}"
            )


def test_concurrent_fleet_and_snapshot_scrapes_mid_soak():
    """8 threads hammer `/fleet` + `/snapshot` + `/metrics` WHILE the
    federated soak mutates the mesh (the probe hook fires mid-schedule):
    every response parses, no torn exposition, no deadlock — the scrape
    plane reads live state without stopping the world."""
    mesh = ReplicaMesh([(f"r{i}", SyncServer()) for i in range(3)])
    telemetry = TelemetryServer(port=0)
    mesh.attach_telemetry(telemetry)
    telemetry.start()
    errors = []
    bodies = {"fleet": [], "snapshot": [], "metrics": []}

    def hammer():
        try:
            for _ in range(4):
                for path, key in (
                    ("/fleet", "fleet"),
                    ("/snapshot", "snapshot"),
                    ("/metrics", "metrics"),
                ):
                    status, body = _get(telemetry.port, path)
                    assert status == 200
                    bodies[key].append(body)
        except Exception as e:  # noqa: BLE001 - collected for the assert
            errors.append(f"{type(e).__name__}: {e}")

    def probe():
        threads = [threading.Thread(target=hammer) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
            assert not t.is_alive(), "scrape thread wedged: deadlock"

    try:
        rep = FederatedSoakDriver(
            mesh, Scenario(CFG), sync_every=4, anti_entropy_every=8,
            canary_every=4, probe_at=0.5, probe=probe,
        ).run()
    finally:
        telemetry.stop()
    assert not errors, errors
    assert rep["converged"]
    assert len(bodies["fleet"]) == 8 * 4
    for body in bodies["fleet"]:
        _assert_untorn_exposition(body)
        for rid in ("r0", "r1", "r2"):
            assert f'replica="{rid}"' in body
    for body in bodies["snapshot"]:
        snap = json.loads(body)  # valid JSON = not torn
        assert "fleet_timeline" in snap


def test_fleet_source_error_is_reported_not_fatal():
    t = TelemetryServer(port=0)
    t.add_fleet_source("good", lambda: {"replica.alive": 1.0})

    def bad():
        raise RuntimeError("boom")

    t.add_fleet_source("bad", bad)
    t.start()
    try:
        status, body = _get(t.port, "/fleet")
    finally:
        t.stop()
    assert status == 200
    assert 'replica_alive{replica="good"} 1' in body
    assert 'fleet_source_error{replica="bad"} 1' in body
    _assert_untorn_exposition(body)


# ------------------------------------------------------- canary + digest


def test_canary_tenants_stay_off_the_parity_surface():
    """Two servers with identical real-tenant state but different canary
    traffic must digest identically (CANARY_PREFIX exclusion)."""
    a, b = SyncServer(), SyncServer()
    for server in (a, b):
        server.connect_frames("t0")
    b.connect_frames(f"{CANARY_PREFIX}:r9")
    assert server_state_digest(a, "text") == server_state_digest(b, "text")


def test_timeline_records_ownership_and_migration():
    mesh = ReplicaMesh([("a", SyncServer()), ("b", SyncServer())])
    mesh.assign_owner("t0", "a")
    mesh.migrate_tenant("t0", "b")
    kinds = [ev["kind"] for ev in mesh.timeline_events()]
    assert "ownership" in kinds and "migration" in kinds, kinds
    seqs = [ev["seq"] for ev in mesh.timeline_events()]
    assert seqs == sorted(seqs)
