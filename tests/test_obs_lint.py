"""Metric-name lint (ISSUE-11 satellite): every counter/gauge/histogram
family and every phases stage a dry-run-style exercise emits must appear
in docs/observability.md — the doc PRs 7/9/10 each had to patch by hand
after the fact. The test fails naming exactly the missing entries, so
adding a metric without documenting it is a one-line fix at review time,
not doc drift discovered two PRs later.
"""

import os

import pytest

from ytpu.utils import metrics, phases

DOCS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "docs",
    "observability.md",
)

def _exercise():
    """A compact workout touching every subsystem that registers series:
    transport + device serving + soak + admission + telemetry. Reuses the
    suite's compiled (4, 256) device-server family."""
    pytest.importorskip("jax")
    from ytpu.serving import (
        AdmissionController,
        Scenario,
        ScenarioConfig,
        SoakDriver,
    )
    from ytpu.sync.device_server import DeviceSyncServer
    from ytpu.utils.telemetry import TelemetryServer

    phases.reset()
    phases.enable()
    try:
        # serving leg: device server + admission + soak series
        cfg = ScenarioConfig(
            n_tenants=2, n_sessions=4, events_per_session=6, seed=29
        )
        SoakDriver(
            DeviceSyncServer(n_docs=4, capacity=256),
            Scenario(cfg),
            admission=AdmissionController(max_queue=4096),
            flush_every=4,
        ).run()

        # telemetry leg: one scrape registers the plane's own series
        with TelemetryServer(port=0) as t:
            import urllib.request

            urllib.request.urlopen(
                f"http://127.0.0.1:{t.port}/metrics", timeout=5
            ).read()
        snap = phases.snapshot()
    finally:
        phases.disable()
        phases.reset()
    return snap


def test_every_emitted_metric_and_phase_name_is_documented():
    snap = _exercise()
    with open(DOCS) as f:
        doc = f.read()
    # metric families: every registered family name (the exercise above
    # touched every subsystem; module-level families register at import)
    missing = []
    for name in sorted(metrics._families):
        if name.startswith("telemetry_test."):
            continue  # tests/test_telemetry.py's own family, same worker
        if name not in doc:
            missing.append(f"metric: {name}")
    for key in sorted(snap):
        if key not in doc:
            missing.append(f"phase: {key}")
    assert not missing, (
        "undocumented observability names (add them to "
        "docs/observability.md §Metric name index):\n  "
        + "\n  ".join(missing)
    )


def _federated_exercise():
    """A dry-run-shaped federated workout with the TRACER live (ISSUE-15
    satellite f): host-only 3-replica chaos soak + canary probing, so
    every fleet span family — soak.event, canary.probe, replica.* —
    is emitted.  Returns the set of span names recorded."""
    from ytpu.serving import FederatedSoakDriver, Scenario, ScenarioConfig
    from ytpu.sync.replica import ReplicaMesh
    from ytpu.sync.server import SyncServer
    from ytpu.utils.trace import tracer

    import json as _json

    cfg = ScenarioConfig(
        n_tenants=2, n_sessions=4, events_per_session=6, seed=29
    )
    tracer.enabled = True
    try:
        tracer.clear()
        rep = FederatedSoakDriver(
            ReplicaMesh([(f"r{i}", SyncServer()) for i in range(3)]),
            Scenario(cfg),
            sync_every=4,
            anti_entropy_every=8,
            canary_every=4,
            partition_at=0.3,
            heal_at=0.5,
            failover_at=0.8,
            migrate_at=0.4,
        ).run()
        events = _json.loads(tracer.export_chrome_trace())["traceEvents"]
    finally:
        tracer.enabled = False
        tracer.clear()
    assert rep["converged"], rep
    return {e["name"] for e in events}


def test_every_emitted_span_name_is_documented():
    """Satellite (f): every span NAME a traced federated exercise emits
    must appear in docs/observability.md (the §Span name index), so a
    new span ships with its doc row or fails here by name."""
    names = _federated_exercise()
    # the chaos schedule must actually have exercised the fleet spans —
    # an empty/narrow set would vacuously pass the lint
    for expected in (
        "soak.event",
        "canary.probe",
        "replica.sync_round",
        "replica.deliver",
        "replica.anti_entropy",
        "replica.handoff",
        "replica.failover",
        "replica.migrate",
    ):
        assert expected in names, (expected, sorted(names))
    with open(DOCS) as f:
        doc = f.read()
    missing = sorted(n for n in names if n not in doc)
    assert not missing, (
        "undocumented span names (add them to docs/observability.md "
        "§Span name index):\n  " + "\n  ".join(missing)
    )


def test_window_prometheus_text_format_pin():
    """Satellite (b): `window_prometheus_text` emits a REAL Prometheus
    histogram exposition — TYPE header, cumulative `_bucket{le=...}`
    series ending in `+Inf` == `_count`, `_sum` in seconds — computed
    over the WINDOW's delta only, and an empty window still emits the
    +Inf/_sum/_count triplet."""
    import re as _re

    from ytpu.utils.metrics import Histogram
    from ytpu.utils.slo import HistogramWindow, window_prometheus_text

    # standalone Histogram (NOT registry-registered: this pin must not
    # add a family the documented-names lint would then demand)
    hist = Histogram("obs_lint.window_pin")
    hist.observe(0.5)  # pre-window sample: must NOT appear in the delta
    w = HistogramWindow(hist)
    empty = window_prometheus_text("obs_lint.window_pin", w)
    assert empty.splitlines() == [
        "# TYPE obs_lint_window_pin histogram",
        'obs_lint_window_pin_bucket{le="+Inf"} 0',
        "obs_lint_window_pin_sum 0",
        "obs_lint_window_pin_count 0",
    ]
    for s in (0.001, 0.002, 0.004, 1.0):
        hist.observe(s)
    text = window_prometheus_text("obs_lint.window_pin", w)
    lines = text.splitlines()
    assert lines[0] == "# TYPE obs_lint_window_pin histogram"
    bucket_re = _re.compile(
        r'^obs_lint_window_pin_bucket\{le="([^"]+)"\} (\d+)$'
    )
    counts = []
    uppers = []
    for ln in lines[1:-2]:
        m = bucket_re.match(ln)
        assert m, ln
        uppers.append(m.group(1))
        counts.append(int(m.group(2)))
    # cumulative, ending at +Inf == windowed count (4, not 5: the
    # pre-window sample stayed out)
    assert counts == sorted(counts)
    assert uppers[-1] == "+Inf" and counts[-1] == 4
    assert lines[-1] == "obs_lint_window_pin_count 4"
    m = _re.match(r"^obs_lint_window_pin_sum ([0-9.e+-]+)$", lines[-2])
    assert m, lines[-2]
    assert abs(float(m.group(1)) - (0.001 + 0.002 + 0.004 + 1.0)) < 0.01
    # le values are seconds, formatted like the registry's exposition
    for le in uppers[:-1]:
        float(le)
