"""Live telemetry plane (ISSUE-11): the scrapeable HTTP endpoint
(`ytpu/utils/telemetry.py`), its serving attach points, end-to-end
request tracing across the transport/admission/dispatch/reply layers,
and the endpoint's behavior under injected faults.

Shares the (n_docs=4, capacity=256) DeviceSyncServer family with
test_device_server / test_serving_soak so no new device programs
compile for this file.
"""

import asyncio
import json
import os
import time
import urllib.request

import pytest

from ytpu.core import Doc
from ytpu.utils import metrics, tracer
from ytpu.utils.telemetry import TelemetryServer

N_DOCS, CAPACITY = 4, 256


def _get(port: int, path: str, timeout: float = 5.0):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=timeout
    ) as r:
        return r.status, r.read().decode()


# --- the bare endpoint -------------------------------------------------------


def test_endpoints_serve_metrics_snapshot_healthz():
    metrics.counter("telemetry_test.ops").inc(3)
    with TelemetryServer(port=0) as t:
        assert t.port and t.port > 0  # ephemeral bind resolved
        status, text = _get(t.port, "/metrics")
        assert status == 200
        assert "telemetry_test_ops_total 3" in text
        status, body = _get(t.port, "/snapshot")
        snap = json.loads(body)
        assert snap["metrics"]["telemetry_test.ops"] == 3
        assert "phases" in snap and "time_unix" in snap
        status, body = _get(t.port, "/healthz")
        h = json.loads(body)
        assert h["status"] == "ok" and h["uptime_s"] >= 0
        assert "lane_ladder" not in h  # the replay lanes' gauge left with them (PR 48)
        with pytest.raises(urllib.error.HTTPError) as err:
            _get(t.port, "/nope")
        assert err.value.code == 404
    # scrape self-accounting landed, in the registry's own family: the
    # plane looks it up per scrape, so an earlier module's
    # `metrics.reset()` on this worker cannot orphan it
    scrapes = metrics.counter("telemetry.scrapes", labelnames=("endpoint",))
    assert scrapes.labels("metrics").value >= 1


def test_provider_sections_and_provider_errors_degrade():
    t = TelemetryServer(port=0, providers={"pool": lambda: {"n": 7}})
    t.add_provider("bad", lambda: 1 / 0)
    t.start()
    try:
        _, body = _get(t.port, "/snapshot")
        snap = json.loads(body)
        assert snap["pool"] == {"n": 7}
        # a raising provider degrades to an error section — the scrape
        # itself (and every other section) survives
        assert "ZeroDivisionError" in snap["bad"]["error"]
        assert "metrics" in snap
    finally:
        t.stop()


def test_start_is_idempotent_and_stop_releases():
    t = TelemetryServer(port=0)
    p1 = t.start()
    assert t.start() == p1  # second start: same bound port, no rebind
    t.stop()
    t.stop()  # idempotent


# --- serving attach points ---------------------------------------------------


def test_device_server_telemetry_attach_and_healthz_dispatch_age():
    pytest.importorskip("jax")
    from ytpu.sync.device_server import DeviceSyncServer

    server = DeviceSyncServer(
        n_docs=N_DOCS, capacity=CAPACITY, telemetry_port=0
    )
    try:
        sess, _ = server.connect_frames("room")
        peer = Doc(client_id=31)
        with peer.transact() as txn:
            peer.get_text("text").insert(txn, 0, "hi")
        from ytpu.sync.protocol import Message, SyncMessage

        server.receive_frames(
            sess,
            Message.sync(
                SyncMessage.update(peer.encode_state_as_update_v1())
            ).encode_v1(),
        )
        server.flush_device()
        _, body = _get(server.telemetry.port, "/healthz")
        h = json.loads(body)
        assert h["status"] == "ok"
        # the flush just set sync.last_dispatch_unix: age is fresh
        assert 0 <= h["last_dispatch_age_s"] < 60
        _, body = _get(server.telemetry.port, "/snapshot")
        snap = json.loads(body)
        assert snap["server"]["tenants"] >= 1
        assert snap["server"]["slots_assigned"] >= 1
        assert snap["server"]["queued_updates"] == 0  # flushed
    finally:
        server.telemetry.stop()


def test_soak_driver_probe_scrapes_live_windows():
    pytest.importorskip("jax")
    from ytpu.serving import Scenario, ScenarioConfig, SoakDriver
    from ytpu.sync.device_server import DeviceSyncServer

    cfg = ScenarioConfig(
        n_tenants=2, n_sessions=4, events_per_session=6, seed=11
    )
    scraped = {}

    def probe():
        _, body = _get(drv.telemetry.port, "/snapshot")
        scraped["snapshot"] = json.loads(body)

    drv = SoakDriver(
        DeviceSyncServer(n_docs=N_DOCS, capacity=CAPACITY),
        Scenario(cfg),
        flush_every=4,
        telemetry_port=0,
        probe_at=0.5,
        probe=probe,
    )
    try:
        rep = drv.run()
    finally:
        drv.telemetry.stop()
    live = scraped["snapshot"]["soak"]
    assert live["running"] is True
    # the live window is a prefix of the final report's window
    assert 0 < live["apply_e2e_count"] <= rep["apply_e2e_count"]
    # p999/max ride the report (slo satellite)
    for k in ("apply_p999_ms", "apply_max_ms", "apply_e2e_p999_ms"):
        assert k in rep, sorted(rep)


# --- fault injection: the plane must outlive the data plane ------------------


def test_healthz_serveable_and_drop_reasons_labeled_under_faults():
    """Satellite: arm transport faults during a TCP mini-soak (plus one
    deliberate garbage frame) and assert `/healthz` keeps answering and
    `net.sessions_dropped{reason=...}` shows up in `/metrics` with a
    correct reason label."""
    pytest.importorskip("jax")
    from ytpu.serving import Scenario, ScenarioConfig
    from ytpu.serving.soak import run_soak_tcp
    from ytpu.sync.device_server import DeviceSyncServer
    from ytpu.utils.faults import faults

    dropped = metrics.counter("net.sessions_dropped", labelnames=("reason",))
    bad_before = dropped.labels("bad_frame").value
    probed = {}

    def probe(port):
        probed["port"] = port
        status, body = _get(port, "/healthz")
        probed["healthz_status"] = status
        probed["healthz"] = json.loads(body)
        # one hostile peer: connect, say hello, then send garbage bytes
        # framed as a valid-length frame — the session must die counted
        # as bad_frame while the accept loop and the plane keep serving

    faults.clear()
    try:
        counts = run_soak_tcp(
            DeviceSyncServer(n_docs=N_DOCS, capacity=CAPACITY),
            Scenario(
                ScenarioConfig(
                    n_tenants=2, n_sessions=4, events_per_session=5, seed=13
                )
            ),
            arm=lambda: faults.arm("net.drop", n=3),
            budget_s=20.0,
            telemetry_port=0,
            probe=probe,
            probe_at_events=2,
        )
    finally:
        faults.clear()
    assert counts["survived"], counts
    assert probed.get("healthz_status") == 200, probed
    assert probed["healthz"]["status"] == "ok"

    # session.kill leg (in-proc): sessions force-dropped mid-soak while
    # the driver's own endpoint keeps answering
    from ytpu.serving import SoakDriver

    killed = {}

    def kill_probe():
        status, body = _get(drv.telemetry.port, "/healthz")
        killed["status"] = status
        killed["healthz"] = json.loads(body)

    faults.arm("session.kill", n=2)
    drv = SoakDriver(
        DeviceSyncServer(n_docs=N_DOCS, capacity=CAPACITY),
        Scenario(
            ScenarioConfig(
                n_tenants=2, n_sessions=4, events_per_session=5, seed=17
            )
        ),
        flush_every=4,
        telemetry_port=0,
        probe_at=0.6,
        probe=kill_probe,
    )
    try:
        rep = drv.run()
    finally:
        faults.clear()
        drv.telemetry.stop()
    assert rep.get("session_kills", 0) >= 1, rep
    assert killed.get("status") == 200 and killed["healthz"]["status"] == "ok"


def test_metrics_exposition_carries_drop_reason_labels():
    """The per-reason drop series renders with correct labels in the
    Prometheus exposition a scraper reads (a garbage frame over a real
    socket drives reason="bad_frame")."""
    pytest.importorskip("jax")
    from ytpu.sync.net import serve, write_frame
    from ytpu.sync.server import SyncServer

    dropped = metrics.counter("net.sessions_dropped", labelnames=("reason",))
    before = dropped.labels("bad_frame").value

    async def main():
        server = SyncServer()
        srv, port = await serve(server, idle_flush=0.05)
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        write_frame(writer, b"room")
        write_frame(writer, b"\xff\xff\xff\xff\xff")  # protocol garbage
        await writer.drain()
        await asyncio.sleep(0.3)
        writer.close()
        srv.close()
        await srv.wait_closed()

    asyncio.run(main())
    assert dropped.labels("bad_frame").value == before + 1
    with TelemetryServer(port=0) as t:
        _, text = _get(t.port, "/metrics")
    line = [
        ln
        for ln in text.splitlines()
        if ln.startswith("net_sessions_dropped_total{")
        and 'reason="bad_frame"' in ln
    ]
    assert line, "bad_frame reason label missing from exposition"


# --- end-to-end request tracing (tentpole b acceptance) ----------------------


def test_trace_id_spans_four_layers_in_chrome_dump(tmp_path, monkeypatch):
    """Acceptance: one frame's trace id is observable across ≥4 span
    layers (net → admission → dispatch → reply) in a YTPU_TRACE
    Chrome-trace dump."""
    pytest.importorskip("jax")
    from ytpu.serving import AdmissionController
    from ytpu.sync.device_server import DeviceSyncServer
    from ytpu.sync.net import SyncClient, serve
    from ytpu.utils import trace as trace_mod

    path = str(tmp_path / "req-trace-%p.json")
    monkeypatch.setenv("YTPU_TRACE", path)
    tracer.clear()
    tracer.enable()

    async def main():
        server = DeviceSyncServer(n_docs=N_DOCS, capacity=CAPACITY)
        server.admission = AdmissionController(max_queue=4096)
        srv, port = await serve(server, flush_every=1)
        c = SyncClient(Doc(client_id=41))
        await c.connect("127.0.0.1", port, "traced")
        await c.pump(max_frames=4, timeout=0.5)
        with c.doc.transact() as txn:
            c.doc.get_text("text").insert(txn, 0, "traced edit")
        await c.flush()
        await asyncio.sleep(0.4)
        await c.close()
        srv.close()
        await srv.wait_closed()

    try:
        asyncio.run(main())
        # the YTPU_TRACE dump path (atexit shape, invoked directly so the
        # test reads the file the env contract would produce)
        trace_mod._atexit_dump()
    finally:
        tracer.disable()
        tracer.clear()
    dump = path.replace("%p", str(os.getpid()))
    events = json.loads(open(dump).read())["traceEvents"]
    by_trace = {}
    for e in events:
        t = (e.get("args") or {}).get("trace")
        if t:
            by_trace.setdefault(t, set()).add(e["name"])
    layers = {"net.frame", "admission.admit", "sync.dispatch", "net.reply"}
    best = max(by_trace.values(), key=lambda s: len(s & layers), default=set())
    assert len(best & layers) >= 4, by_trace
    # the spans also carry tenant/session correlation args
    traced = [
        e
        for e in events
        if e["name"] == "net.frame" and (e.get("args") or {}).get("trace")
    ]
    assert traced and traced[0]["args"]["tenant"] == "traced"
    assert "session" in traced[0]["args"]


def test_trace_context_nesting_and_disabled_cost():
    from ytpu.utils import (
        current_trace,
        current_trace_id,
        new_trace_id,
        trace_context,
    )

    assert current_trace() is None
    tracer.enable()
    try:
        with trace_context(tenant="a") as ctx:
            tid = ctx["trace"]
            assert current_trace_id() == tid
            # nested context merges, inner keys win, outer trace kept
            with trace_context(trace=tid, session=9):
                assert current_trace()["tenant"] == "a"
                assert current_trace()["session"] == 9
            assert "session" not in current_trace()  # inner ctx unwound
        assert current_trace() is None
        # spans auto-merge the ambient context into args
        with trace_context(trace="txyz", tenant="t"):
            with tracer.span("probe"):
                pass
        ev = json.loads(tracer.export_chrome_trace())["traceEvents"][-1]
        assert ev["args"]["trace"] == "txyz" and ev["args"]["tenant"] == "t"
    finally:
        tracer.disable()
        tracer.clear()
    # disabled tracer: the shared no-op context, no allocation per frame
    a = trace_context(tenant="x")
    b = trace_context(tenant="y")
    assert a is b
    assert new_trace_id() != new_trace_id()


def test_healthz_reports_never_before_first_dispatch():
    """ISSUE-15 satellite regression: with the last-dispatch gauge at
    its 0.0 default (no dispatch ever happened), `/healthz` must say
    ``last_dispatch: "never"`` and OMIT ``last_dispatch_age_s`` — an age
    computed from epoch 0 reads ~56 years of false alarm.  The gauge
    is saved/zeroed/restored in place (`metrics.reset()` would orphan
    every cached metric object in the process)."""
    sync_g = metrics.gauge("sync.last_dispatch_unix")
    saved = sync_g.value
    try:
        sync_g.set(0.0)
        with TelemetryServer(port=0) as t:
            status, body = _get(t.port, "/healthz")
        assert status == 200
        hz = json.loads(body)
        assert hz["last_dispatch"] == "never", hz
        assert "last_dispatch_age_s" not in hz, hz
        # and once the gauge moves, the age replaces the marker
        sync_g.set(time.time())
        with TelemetryServer(port=0) as t:
            _, body = _get(t.port, "/healthz")
        hz = json.loads(body)
        assert "last_dispatch" not in hz, hz
        assert 0.0 <= hz["last_dispatch_age_s"] < 60.0, hz
    finally:
        sync_g.set(saved)
