"""Performance observatory (ISSUE-17): the compile/retrace sentinel,
the unified wall-time attribution, and the label-cardinality guard.

The sentinel turns "jax silently recompiled" into an attributed,
budgetable event: every instrumented jit boundary records a per-call
shape signature, distinct signatures per program count as retraces, and
the signature DELTA names the axis that changed — so a mid-run
``YTPU_SCAN_TIER_CHEAP`` flip is caught and attributed to ``scan_plan``,
not shrugged at. The profile fold's fractions must sum to 1 of the
measured wall by construction, and the metrics registry must survive a
10k-tenant label flood by folding overflow into the reserved ``other``
label instead of growing without bound."""

import json
import os
import time
import urllib.request

import pytest

from ytpu.utils.metrics import MetricsRegistry, metrics
from ytpu.utils.phases import (
    PhaseRecorder,
    compile_storm_provider,
    phases,
)
from ytpu.utils.profile import ProfileWindow


# ---------------------------------------------------------------------------
# sentinel unit semantics (private recorder: no global state touched)
# ---------------------------------------------------------------------------


def test_sentinel_counts_retraces_and_attributes_axis():
    rec = PhaseRecorder(enabled=True)
    axes = ("shape", "dtype")
    with rec.span("prog.x", key=((4, 3), "f32"), axes=axes):
        pass
    rep = rec.compile_report()
    assert rep["events"] == 1 and rep["retraces"] == 0, rep
    # same signature again: cache hit, no new event
    with rec.span("prog.x", key=((4, 3), "f32"), axes=axes):
        pass
    assert rec.compile_report()["events"] == 1
    # a changed leading axis is a RETRACE whose delta names that axis
    with rec.span("prog.x", key=((8, 3), "f32"), axes=axes):
        pass
    rep = rec.compile_report()
    assert rep["events"] == 2 and rep["retraces"] == 1, rep
    (entry,) = rep["journal"]
    assert entry["program"] == "prog.x"
    assert [d["axis"] for d in entry["delta"]] == ["shape"]
    assert entry["delta"][0]["prev"] == repr((4, 3))
    assert entry["delta"][0]["new"] == repr((8, 3))
    # per-program attribution in the report
    assert rep["programs"] == {"prog.x": 2}


def test_compile_marker_windows_the_report():
    rec = PhaseRecorder(enabled=True)
    with rec.span("prog.w", key=(1,), axes=("k",)):
        pass
    marker = rec.compile_marker()
    assert rec.compile_report(since=marker)["events"] == 0
    with rec.span("prog.w", key=(2,), axes=("k",)):
        pass
    windowed = rec.compile_report(since=marker)
    assert windowed["events"] == 1 and windowed["retraces"] == 1
    # the full-history view still sees both sightings
    assert rec.compile_report()["events"] == 2


def test_storm_provider_budget_semantics():
    rec = PhaseRecorder(enabled=True)
    with rec.span("prog.s", key=(1,), axes=("k",)):
        pass
    marker = rec.compile_marker()
    zero = compile_storm_provider(budget=0, marker=marker, recorder=rec)
    lax = compile_storm_provider(budget=None, marker=marker, recorder=rec)
    assert not zero()["degraded"] and not lax()["degraded"]
    with rec.span("prog.s", key=(2,), axes=("k",)):
        pass
    blown = zero()
    assert blown["degraded"] and blown["storm"], blown
    assert blown["last_retrace"]["program"] == "prog.s"
    # report-only mode journals but never degrades
    assert not lax()["degraded"] and lax()["retraces"] == 1


def test_compile_retrace_fault_site():
    """Chaos can PROVE the detector fires: arming ``compile.retrace``
    perturbs the next instrumented boundary's signature with a nonce, so
    a cache-hit call journals as a retrace."""
    from ytpu.utils.faults import faults

    rec = PhaseRecorder(enabled=True)
    with rec.span("prog.fault", key=(1,), axes=("k",)):
        pass
    faults.arm("compile.retrace", n=1)
    try:
        with rec.span("prog.fault", key=(1,), axes=("k",)):
            pass
    finally:
        faults.clear()
    rep = rec.compile_report()
    assert rep["retraces"] == 1, rep
    assert rep["journal"][0]["program"] == "prog.fault"
    # the one-shot spec is spent: the same call is a cache hit again
    with rec.span("prog.fault", key=(1,), axes=("k",)):
        pass
    assert rec.compile_report()["events"] == 2


# ---------------------------------------------------------------------------
# wall-time attribution: fractions sum to 1 by construction
# ---------------------------------------------------------------------------


def _synthetic_stages(rec):
    with rec.span("ingest.plan"):
        time.sleep(0.02)
    with rec.span("encode.finish"):
        time.sleep(0.01)


def _served_step(rec):
    """The served step's own nesting (ISSUE-26): `sync.dispatch` ⊃
    `ingest.apply` ⊃ `ingest.plan`/`ingest.merge` ⊃ leaves, with the
    time in the leaves, as on the chip."""
    with rec.span("sync.receive"):
        with rec.span("sync.receive.fanout"):
            time.sleep(0.002)
    with rec.span("sync.dispatch"):
        with rec.span("ingest.apply"):
            with rec.span("ingest.plan"):
                with rec.span("ingest.plan.prescan"):
                    time.sleep(0.02)
            with rec.span("ingest.merge"):
                with rec.span("ingest.merge.scatter"):
                    time.sleep(0.02)
                with rec.span("decode.v1", key=((2, 64),)):
                    time.sleep(0.005)  # first sighting: compile
            with rec.span("integrate.xla_batch", key=((2, 256),)):
                time.sleep(0.005)
    rec.add_time("sync.queue_wait", 5.0)  # waiting is nobody's work
    with rec.span("encode.finish"):
        time.sleep(0.01)


@pytest.mark.parametrize("stages", [_synthetic_stages, _served_step])
def test_profile_fractions_self_consistent(stages):
    rec = PhaseRecorder(enabled=True)
    w = ProfileWindow(recorder=rec)
    w.begin()
    stages(rec)
    time.sleep(0.02)  # unattributed wall → idle bucket
    rep = w.report()
    assert abs(rep["fractions_sum"] - 1.0) < 1e-6, rep
    fracs = {k: v for k, v in rep.items() if k.startswith("profile_")}
    assert all(v >= 0.0 for v in fracs.values()), fracs
    assert rep["profile_staging_fraction"] > 0.0
    assert rep["profile_finisher_fraction"] > 0.0
    assert rep["profile_idle_fraction"] > 0.0
    assert rep["seconds"]["staging"] == pytest.approx(0.02, abs=0.015)
    # a container's time is not counted on top of its children's, and a
    # queue wait is in no bucket: the buckets fit in the wall
    assert rep["overcommit_s"] == 0.0, rep
    if stages is _served_step:
        # the merge's eager glue is host time, not device time
        assert rep["seconds"]["host"] == pytest.approx(0.022, abs=0.015)
        assert rep["seconds"]["compile"] == pytest.approx(0.010, abs=0.008)
        assert rep["seconds"]["device"] < 0.004


def test_profile_window_is_deltas_not_cumulative():
    rec = PhaseRecorder(enabled=True)
    with rec.span("ingest.plan"):
        time.sleep(0.01)
    w = ProfileWindow(recorder=rec)
    w.begin()  # window opens AFTER the stage time above
    time.sleep(0.01)
    rep = w.report()
    assert rep["seconds"]["staging"] == pytest.approx(0.0, abs=1e-3), rep
    assert rep["profile_idle_fraction"] > 0.9, rep


def test_profile_endpoint_serves_fractions():
    from ytpu.utils.telemetry import TelemetryServer

    rec = PhaseRecorder(enabled=True)
    w = ProfileWindow(recorder=rec)
    w.begin()
    with rec.span("integrate.xla_batch"):
        time.sleep(0.01)
    srv = TelemetryServer(port=0)
    srv.set_profile_source(w.report)
    srv.start()
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/profile", timeout=10
        ) as r:
            assert r.status == 200
            rep = json.loads(r.read().decode())
    finally:
        srv.stop()
    assert abs(rep["fractions_sum"] - 1.0) <= 0.05, rep
    assert rep["profile_device_fraction"] > 0.0, rep


# ---------------------------------------------------------------------------
# soak integration: warmed runs score zero, a mid-run static-plan flip
# is caught and attributed
# ---------------------------------------------------------------------------


def _mini_cfg():
    from ytpu.serving import Scenario, ScenarioConfig

    return Scenario(
        ScenarioConfig(
            n_tenants=2, n_sessions=4, events_per_session=6, seed=5
        )
    )


def _fresh_server():
    from ytpu.sync.device_server import DeviceSyncServer

    return DeviceSyncServer(n_docs=4, capacity=256)


def test_warmed_soak_scores_zero_retraces():
    from ytpu.serving import SoakDriver

    prev_enabled = phases.enabled
    phases.enable()
    try:
        SoakDriver(_fresh_server(), _mini_cfg(), flush_every=4).run()
        rep = SoakDriver(
            _fresh_server(), _mini_cfg(), flush_every=4, retrace_budget=0
        ).run()
    finally:
        phases.enabled = prev_enabled
    comp = rep["compile"]
    assert comp["retraces"] == 0, comp
    assert comp["within_budget"] is True, comp
    prof = rep["profile"]
    assert abs(prof["fractions_sum"] - 1.0) <= 0.05, prof


@pytest.mark.slow
def test_midrun_scan_plan_flip_is_caught_and_attributed():
    """The acceptance scenario: flipping ``YTPU_SCAN_TIER_CHEAP`` mid-run
    forces a real retrace of the batch program; the journal must name
    the ``scan_plan`` axis (the changed knob), and a zero budget must
    score the run out of budget.

    Slow tier: the forced retrace pays a real ~15s XLA recompile of the
    flipped-plan batch program on CPU. The fast unit tests above pin the
    same counting/attribution mechanics, and `bench.py --dry-run`'s
    observatory storm leg exercises this exact end-to-end path."""
    from ytpu.models.batch_doc import scan_tier_plan
    from ytpu.serving import SoakDriver

    prev_enabled = phases.enabled
    prev_env = os.environ.get("YTPU_SCAN_TIER_CHEAP")
    phases.enable()

    def flip():
        cur = scan_tier_plan()[0]
        os.environ["YTPU_SCAN_TIER_CHEAP"] = str(4 if cur != 4 else 8)

    try:
        # warm every program this scenario dispatches
        SoakDriver(_fresh_server(), _mini_cfg(), flush_every=4).run()
        rep = SoakDriver(
            _fresh_server(),
            _mini_cfg(),
            flush_every=4,
            retrace_budget=0,
            probe_at=0.5,
            probe=flip,
        ).run()
    finally:
        phases.enabled = prev_enabled
        if prev_env is None:
            os.environ.pop("YTPU_SCAN_TIER_CHEAP", None)
        else:
            os.environ["YTPU_SCAN_TIER_CHEAP"] = prev_env
    comp = rep["compile"]
    assert comp["retraces"] >= 1, comp
    assert comp["within_budget"] is False, comp
    axes = {
        d["axis"] for ev in comp["journal"] for d in (ev.get("delta") or [])
    }
    assert "scan_plan" in axes, comp["journal"]


# ---------------------------------------------------------------------------
# label-cardinality guard: a tenant flood folds into `other`, bounded
# ---------------------------------------------------------------------------


def test_cardinality_guard_folds_tenant_flood(monkeypatch):
    monkeypatch.setenv("YTPU_METRICS_MAX_LABELSETS", "64")
    # a private registry keeps the synthetic family out of the global
    # exposition (the obs lint asserts every GLOBAL family is
    # documented); the drop counter is global by design — the guard
    # reports into the process registry whichever registry overflowed
    reg = MetricsRegistry()
    fam = reg.counter("obs_test.tenant_flood", labelnames=("tenant",))
    dropped = metrics.counter("metrics.cardinality_dropped")
    before = dropped.value
    for i in range(10_000):
        fam.labels(f"tenant{i}").inc()
    # 64 real children + the reserved overflow child, nothing more
    assert len(fam._children) <= 65, len(fam._children)
    other = fam.labels("other")
    assert other.value == 10_000 - 64, other.value
    assert dropped.value - before == 10_000 - 64
    # no counts were lost: the family total is exact
    total = sum(c.value for c in fam._children.values())
    assert total == 10_000
    # the fold is sticky and the guard re-reads the env per miss
    fam.labels("tenant_one_more").inc()
    assert fam.labels("other").value == 10_000 - 64 + 1


def test_cardinality_guard_exports_other_label():
    reg = MetricsRegistry()
    fam = reg.counter("obs_test.tiny_family", labelnames=("who",))
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("YTPU_METRICS_MAX_LABELSETS", "1")
        fam.labels("a").inc()
        fam.labels("b").inc()  # folds: family already at the cap
    text = reg.prometheus_text()
    assert 'obs_test_tiny_family_total{who="a"} 1' in text
    assert 'obs_test_tiny_family_total{who="other"} 1' in text
