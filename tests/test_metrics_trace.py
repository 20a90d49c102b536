"""Metrics + tracing + phase-timer subsystem (SURVEY §5.1/§5.5)."""

import json
import os
import re

import pytest

from ytpu.utils import MetricsRegistry, Tracer


@pytest.fixture
def fresh_registry():
    """An empty process-wide registry for one test, the families put back
    after it: `metrics.reset()` alone orphans every family a module cached
    at import (`net.py`, `replica.py`, `admission.py`, ...) for the
    rest of the worker's life, and a later test that reads one through the
    registry then sees a namesake at 0 (ROADMAP Design 13)."""
    from ytpu.utils import metrics

    saved = dict(metrics._families)
    metrics.reset()
    yield metrics
    metrics._families.clear()
    metrics._families.update(saved)


def test_counter_and_histogram():
    reg = MetricsRegistry()
    c = reg.counter("ops")
    c.inc()
    c.inc(4)
    assert c.value == 5

    h = reg.histogram("lat")
    for ms in [1, 1, 2, 2, 3, 100]:
        h.observe(ms / 1000)
    assert h.count == 6
    assert 0.0005 < h.p50_s < 0.01
    assert h.p99_s >= 0.05  # dominated by the 100ms outlier
    snap = reg.snapshot()
    assert snap["ops"] == 5
    assert snap["lat.count"] == 6


def test_histogram_timer():
    reg = MetricsRegistry()
    h = reg.histogram("t")
    with h.time():
        pass
    assert h.count == 1
    assert h.p99_s < 0.1


def test_tracer_chrome_export(tmp_path):
    tr = Tracer(enabled=True)
    with tr.span("decode", n=3):
        with tr.span("inner"):
            pass
    payload = json.loads(tr.export_chrome_trace())
    names = [e["name"] for e in payload["traceEvents"]]
    assert names == ["inner", "decode"]  # completion order
    assert payload["traceEvents"][1]["args"] == {"n": 3}

    path = tmp_path / "trace.json"
    tr.export_chrome_trace(str(path))
    assert json.loads(path.read_text())["traceEvents"]


def test_tracer_disabled_is_noop():
    tr = Tracer(enabled=False)
    with tr.span("x"):
        pass
    assert json.loads(tr.export_chrome_trace())["traceEvents"] == []


def test_server_records_apply_metrics(fresh_registry):
    from ytpu.core import Doc
    from ytpu.sync.server import SyncServer
    from ytpu.sync.protocol import Message, SyncMessage

    metrics = fresh_registry
    server = SyncServer()
    s1, _hello = server.connect("room")
    peer = Doc(client_id=7)
    with peer.transact() as txn:
        peer.get_text("t").insert(txn, 0, "hi")
    update = peer.encode_state_as_update_v1()
    server.receive(s1, Message.sync(SyncMessage.update(update)).encode_v1())

    snap = metrics.snapshot()
    assert snap["sync.updates_applied"] == 1
    assert snap["sync.apply_update.count"] == 1
    assert snap["sync.apply_update.p99_s"] > 0
    assert snap['sync.tenant_updates_applied{tenant="room"}'] == 1
    assert snap["sync.sessions"] == 1
    assert server.doc("room").get_text("t").get_string() == "hi"


# --- labeled metrics + gauges + Prometheus exposition -----------------------


def test_labeled_counter_children():
    reg = MetricsRegistry()
    fam = reg.counter("req", labelnames=("tenant",))
    fam.labels("a").inc()
    fam.labels("a").inc(2)
    fam.labels(tenant="b").inc()
    assert fam.labels("a") is fam.labels("a")  # children are cached
    snap = reg.snapshot()
    assert snap['req{tenant="a"}'] == 3
    assert snap['req{tenant="b"}'] == 1
    # a labeled family refuses direct value ops
    with pytest.raises(ValueError):
        fam.inc()
    # re-registering under a different schema is a conflict
    with pytest.raises(ValueError):
        reg.gauge("req")
    with pytest.raises(ValueError):
        reg.counter("req", labelnames=("other",))


def test_gauge_set_inc_dec_and_max():
    reg = MetricsRegistry()
    g = reg.gauge("depth")
    g.set(4)
    g.inc()
    g.dec(2)
    assert g.value == 3
    g.set_max(10)
    g.set_max(7)  # ratchet: lower values don't regress the mark
    assert g.value == 10
    lg = reg.gauge("slots", labelnames=("pool",))
    lg.labels("x").set(5)
    assert reg.snapshot()['slots{pool="x"}'] == 5


_PROM_LINE = re.compile(
    r"^(?:"
    r"# (?:HELP|TYPE) [a-zA-Z_:][a-zA-Z0-9_:]* ?.*"
    r"|"
    r"[a-zA-Z_:][a-zA-Z0-9_:]*"
    r'(?:\{[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*"'
    r'(?:,[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*")*\})?'
    r" [0-9.eE+-]+(?:[0-9.eE+-]*)?"
    r")$"
)


def test_prometheus_text_round_trips_format_validity():
    reg = MetricsRegistry()
    reg.counter("ops.total").inc(7)
    reg.gauge("queue.depth").set(3)
    fam = reg.counter("tenant.ops", labelnames=("tenant",))
    fam.labels('we"ird\\name').inc()
    h = reg.histogram("lat")
    for ms in (1, 2, 5, 80):
        h.observe(ms / 1000)
    text = reg.prometheus_text()
    lines = text.strip().splitlines()
    assert lines, "empty exposition"
    for ln in lines:
        assert _PROM_LINE.match(ln), f"invalid exposition line: {ln!r}"
    # TYPE headers name the SAMPLE family (counters sample as _total,
    # so the header declares the _total name — prometheus_client parity)
    assert "# TYPE ops_total_total counter" in text
    assert "ops_total_total 7" in text
    assert "# TYPE tenant_ops_total counter" in text
    assert "# TYPE queue_depth gauge" in text
    assert "# TYPE lat histogram" in text
    # histogram contract: cumulative buckets, +Inf == _count, sum in s
    buckets = [
        float(ln.rsplit(" ", 1)[1])
        for ln in lines
        if ln.startswith("lat_bucket")
    ]
    assert buckets == sorted(buckets), "bucket series must be cumulative"
    inf_line = [ln for ln in lines if 'le="+Inf"' in ln]
    assert len(inf_line) == 1 and inf_line[0].endswith(" 4")
    count_line = [ln for ln in lines if ln.startswith("lat_count")][0]
    assert count_line.endswith(" 4")
    sum_line = [ln for ln in lines if ln.startswith("lat_sum")][0]
    assert abs(float(sum_line.rsplit(" ", 1)[1]) - 0.088) < 1e-6
    # escaped label values survive
    assert 'tenant="we\\"ird\\\\name"' in text


def test_histogram_labeled_children():
    reg = MetricsRegistry()
    fam = reg.histogram("apply", labelnames=("lane",))
    fam.labels("fast").observe(0.002)
    fam.labels("fast").observe(0.004)
    fam.labels("slow").observe(0.1)
    snap = reg.snapshot()
    assert snap['apply.count{lane="fast"}'] == 2
    assert snap['apply.count{lane="slow"}'] == 1
    assert snap['apply.p99_s{lane="slow"}'] >= 0.05


# --- label handling: escaping + name validation (ISSUE-11 satellite) --------


def test_label_value_escaping_survives_hostile_tenant_names():
    """Regression pin: label VALUES containing backslashes, quotes and
    real newlines must escape into single, spec-valid exposition lines —
    reachable now that tenant ids ride labels on the live `/metrics`
    endpoint."""
    reg = MetricsRegistry()
    fam = reg.counter("tenant.ops", labelnames=("tenant",))
    hostile = 'room"1\\end\nnext'
    fam.labels(hostile).inc(2)
    text = reg.prometheus_text()
    lines = text.strip().splitlines()
    # the newline did NOT split the sample line
    sample = [ln for ln in lines if ln.startswith("tenant_ops_total{")]
    assert len(sample) == 1, lines
    assert sample[0] == (
        'tenant_ops_total{tenant="room\\"1\\\\end\\nnext"} 2'
    )
    for ln in lines:
        assert _PROM_LINE.match(ln), f"invalid exposition line: {ln!r}"
    # the JSON snapshot escapes identically (one shared escaper)
    key = 'tenant.ops{tenant="room\\"1\\\\end\\nnext"}'
    assert reg.snapshot()[key] == 2


def test_label_name_with_trailing_newline_is_rejected():
    """`$` matches before a trailing newline, so "tenant\\n" used to
    validate as a label NAME and emit a torn exposition line; the
    validator now anchors with \\Z."""
    reg = MetricsRegistry()
    with pytest.raises(ValueError, match="invalid label name"):
        reg.counter("bad.family", labelnames=("tenant\n",))
    with pytest.raises(ValueError, match="invalid label name"):
        reg.gauge("bad.family2", labelnames=("with space",))


# --- SLO windows: max/p999 + window reset (ISSUE-11 satellite) ---------------


def test_slo_report_carries_p999_and_max():
    from ytpu.utils import HistogramWindow, slo_report

    reg = MetricsRegistry()
    h = reg.histogram("lat")
    w = HistogramWindow(h)
    for ms in [1.0] * 997 + [40.0, 40.0, 900.0]:
        h.observe(ms / 1000)
    rep = slo_report(w, prefix="apply_")
    assert rep["apply_count"] == 1000
    # p999 must NOT collapse into the p99 key (int(99.9) == 99 bug shape)
    assert "apply_p999_ms" in rep and "apply_p99_ms" in rep
    assert rep["apply_p99_ms"] < rep["apply_p999_ms"] <= rep["apply_max_ms"]
    # the 40/900ms outliers are invisible at p99 (the 990th sample is
    # still a 1ms one) but own p999/max — the tail surface the two-tier
    # scan work regresses against
    assert rep["apply_p99_ms"] < 10
    assert rep["apply_p999_ms"] >= 30
    assert rep["apply_max_ms"] >= 900
    assert rep["apply_max_ms_adj"] <= rep["apply_max_ms"]
    # windowed max is bucket-resolution and empty-safe
    assert HistogramWindow(h).max_s == 0.0


def test_histogram_window_reset_between_soak_rounds():
    """Pin the window-reset contract: a window opened AFTER round 1
    scores only round 2's samples — a stale window would silently blend
    both rounds' percentiles (the drift the soak driver guards against
    by re-opening windows per run)."""
    from ytpu.utils import HistogramWindow, slo_report

    reg = MetricsRegistry()
    h = reg.histogram("lat")
    # round 1: slow regime
    for _ in range(50):
        h.observe(0.200)
    stale = HistogramWindow(h)  # opened at the boundary
    r1 = slo_report(HistogramWindow(h), prefix="r1_")
    assert r1["r1_count"] == 0  # fresh window sees nothing yet
    # round 2: fast regime
    for _ in range(50):
        h.observe(0.001)
    fresh = slo_report(stale, prefix="r2_")
    assert fresh["r2_count"] == 50
    # only round 2's regime: p99 AND max stay ~1ms, nowhere near 200ms
    assert fresh["r2_p99_ms"] < 50
    assert fresh["r2_max_ms"] < 50
    # the cumulative histogram would have blended (its p50 spans rounds)
    assert h.count == 100


def test_soak_driver_windows_do_not_blend_across_runs():
    """The driver-level version of the reset pin: two back-to-back
    `SoakDriver.run()`s on one process share the process-global
    histograms, but each report windows ONLY its own run."""
    pytest.importorskip("jax")
    from ytpu.serving import Scenario, ScenarioConfig, SoakDriver
    from ytpu.sync.server import SyncServer

    cfg = ScenarioConfig(
        n_tenants=2, n_sessions=3, events_per_session=5, seed=23
    )
    r1 = SoakDriver(SyncServer(), Scenario(cfg), flush_every=4).run()
    r2 = SoakDriver(SyncServer(), Scenario(cfg), flush_every=4).run()
    # same deterministic scenario, fresh window: the second run's counts
    # equal the first's instead of doubling (a stale window would show
    # run1+run2 samples in run 2's report)
    assert r2["apply_e2e_count"] == r1["apply_e2e_count"] > 0


# --- flight recorder: bounded ring + error dump -----------------------------


def test_tracer_ring_buffer_evicts_oldest():
    tr = Tracer(enabled=True, max_events=4)
    for i in range(10):
        with tr.span(f"s{i}"):
            pass
    payload = json.loads(tr.export_chrome_trace())
    names = [e["name"] for e in payload["traceEvents"]]
    assert names == ["s6", "s7", "s8", "s9"]  # drop-oldest, bounded
    assert len(tr) == 4


def test_tracer_instant_events_ride_the_ring():
    tr = Tracer(enabled=True, max_events=8)
    tr.instant("marker", stage="probe")
    payload = json.loads(tr.export_chrome_trace())
    (ev,) = payload["traceEvents"]
    assert ev["ph"] == "i" and ev["args"] == {"stage": "probe"}


def test_dump_on_error_writes_loadable_chrome_trace(tmp_path):
    tr = Tracer(enabled=True, max_events=16)
    with tr.span("decode"):
        pass
    path = str(tmp_path / "crash.json")
    got = tr.dump_on_error(path, error=RuntimeError("kernel abort"))
    assert got == path
    data = json.loads(open(path).read())
    names = [e["name"] for e in data["traceEvents"]]
    assert names == ["decode", "error"]
    err = data["traceEvents"][-1]
    assert err["args"]["type"] == "RuntimeError"
    assert "kernel abort" in err["args"]["message"]


def test_dump_on_error_resolves_path_from_env(tmp_path, monkeypatch):
    tr = Tracer(enabled=False, max_events=16)  # never enabled: still dumps
    template = str(tmp_path / "t-%p.json")
    monkeypatch.setenv("YTPU_TRACE", template)
    got = tr.dump_on_error(error=ValueError("x"))
    assert got == template.replace("%p", str(os.getpid()))
    assert json.loads(open(got).read())["traceEvents"]
    assert tr.enabled is False  # the dump didn't leave tracing on
    monkeypatch.delenv("YTPU_TRACE")
    assert tr.dump_on_error(error=ValueError("x")) is None


def test_tracer_disabled_span_is_shared_noop():
    tr = Tracer(enabled=False)
    a = tr.span("x", big="arg")
    b = tr.span("y")
    assert a is b  # singleton: no per-call allocation when disabled


# --- device-phase timers ----------------------------------------------------


def test_phase_recorder_compile_vs_execute_attribution():
    from ytpu.utils import PhaseRecorder

    rec = PhaseRecorder(enabled=True)
    with rec.span("stage", key=("shape", 1)):
        pass
    with rec.span("stage", key=("shape", 1)):
        pass
    with rec.span("stage", key=("shape", 2)):  # new compiled key
        pass
    with rec.span("hostonly"):  # key=None: execute-only stage
        pass
    rec.transfer("stage", 100, "h2d")
    rec.transfer("stage", 40, "d2h")
    snap = rec.snapshot()
    st = snap["stage"]
    assert st["calls"] == 3 and st["compile_calls"] == 2
    assert st["h2d_bytes"] == 100 and st["d2h_bytes"] == 40
    assert st["transfer_bytes"] == 140
    assert snap["hostonly"]["compile_calls"] == 0
    # disabled: the shared no-op context, zero recording
    rec2 = PhaseRecorder(enabled=False)
    assert rec2.span("s") is rec2.span("t")
    rec2.transfer("s", 10)
    assert rec2.snapshot() == {}


def test_instrumented_ingest_integrate_records_phase_spans():
    """The ingest→integrate path must attribute first-call compile vs
    steady-state execute at the jit boundary, using the cheap
    (n_docs=2, capacity=256) device shapes tier-1 already compiles."""
    pytest.importorskip("jax")
    from ytpu.core import Doc
    from ytpu.models.ingest import BatchIngestor
    from ytpu.utils import phases

    doc = Doc(client_id=3)
    log = []
    doc.observe_update_v1(lambda p, o, t: log.append(p))
    t = doc.get_text("text")
    for i, word in enumerate(["hi ", "there ", "friend"]):
        with doc.transact() as txn:
            t.insert(txn, len(t.get_string()), word)

    phases.reset()
    phases.enable()
    try:
        ing = BatchIngestor(2, 256)
        for p in log:
            ing.apply_bytes([p, None])
    finally:
        phases.disable()
    snap = phases.snapshot()
    st = snap["integrate.xla_batch"]
    assert st["calls"] == len(log)
    # same (state, batch) shapes each step: exactly one first-call
    # compile charge, the rest land in the execute bucket
    assert st["compile_calls"] == 1
    assert st["calls"] - st["compile_calls"] == len(log) - 1
    assert st["compile_s"] > 0 and st["execute_s"] > 0
    assert "ingest.plan" in snap and snap["ingest.plan"]["calls"] == len(log)
    if ing.fast_docs:  # native lane present: wire bytes were counted,
        # once, where they are uploaded (decode.v1 is handed device arrays)
        assert snap["ingest.merge.h2d"]["h2d_bytes"] > 0
        assert snap["decode.v1"]["h2d_bytes"] == 0
    phases.reset()


def test_ingest_metrics_counters_mirror_lane_stats(fresh_registry):
    pytest.importorskip("jax")
    from ytpu.core import Doc
    from ytpu.models.ingest import BatchIngestor

    metrics = fresh_registry
    doc = Doc(client_id=9)
    log = []
    doc.observe_update_v1(lambda p, o, t: log.append(p))
    with doc.transact() as txn:
        doc.get_text("text").insert(txn, 0, "m")
    ing = BatchIngestor(2, 256)
    ing.apply_bytes([log[0], None])
    snap = metrics.snapshot()
    assert snap["ingest.fast_docs"] + snap["ingest.slow_docs"] == 1
    assert snap["ingest.fast_docs"] == ing.fast_docs
    assert snap["ingest.slow_docs"] == ing.slow_docs
