"""Capacity observatory (ISSUE-18): occupancy/fragmentation ledger,
device-memory attribution, the headroom forecaster, and the typed
`grow.oom` denial.

Early-alphabet-named on purpose: these assertions pin the readout-word
layout (`LEDGER_WORDS` riding `N_READOUT`) and the zero-new-syncs
contract, so they should fail FIRST — before the heavier replay suites
whose drivers depend on the same words.
"""

import json
import urllib.request
from functools import lru_cache

import numpy as np
import pytest

from ytpu.core import Doc
from ytpu.sync.device_server import DeviceSyncServer
from ytpu.sync.protocol import Message, SyncMessage
from ytpu.utils import metrics
from ytpu.utils.capacity import (
    HeadroomForecaster,
    memory_budget_bytes,
    packed_resident_bytes,
)
from ytpu.utils.faults import FaultError, FaultSpec, faults
from ytpu.utils.phases import phases, program_memory


def _push(server, session, peer_doc):
    sv = server.doc(session.tenant).state_vector()
    diff = peer_doc.encode_state_as_update_v1(sv)
    server.receive(session, Message.sync(SyncMessage.update(diff)).encode_v1())


# --- tenant-facing occupancy/fragmentation ledger ---------------------------


def _served_state():
    """Two tenants, one with tombstones: the serving-side ledger's
    acceptance shape. The deletion spans a block boundary so the slot
    holds TWO clock-contiguous tombstoned rows — a shape compaction can
    actually merge (a lone mid-string tombstone is unmergeable)."""
    server = DeviceSyncServer(n_docs=4, capacity=256)
    s_pad, _ = server.connect("pad")
    s_doc, _ = server.connect("docs")
    alice = Doc(client_id=1)
    with alice.transact() as txn:
        alice.get_text("text").insert(txn, 0, "alice writes a lot of text")
    _push(server, s_pad, alice)
    with alice.transact() as txn:
        alice.get_text("text").insert(txn, 26, " and then appends more")
    _push(server, s_pad, alice)
    with alice.transact() as txn:
        alice.get_text("text").remove_range(txn, 20, 12)  # spans both blocks
    _push(server, s_pad, alice)
    bob = Doc(client_id=2)
    with bob.transact() as txn:
        bob.get_text("text").insert(txn, 0, "bob too")
    _push(server, s_doc, bob)
    server.flush_device()
    return server


def test_capacity_ledger_rows_sum_to_capacity():
    """Per tenant: live + dead + free == slot capacity, dead > 0 where
    tombstones exist, and the same numbers ride `/snapshot`'s capacity
    section and the per-tenant gauges."""
    server = _served_state()
    snap = server.capacity_snapshot()
    assert snap["slot_capacity"] == 256
    assert set(snap["tenants"]) == {"pad", "docs"}
    for name, row in snap["tenants"].items():
        assert (
            row["live_rows"] + row["dead_rows"] + row["free_rows"]
            == snap["slot_capacity"]
        ), (name, row)
        assert row["live_rows"] > 0, (name, row)
    assert snap["tenants"]["pad"]["dead_rows"] > 0  # the tombstoned tenant
    assert 0 < snap["tenants"]["pad"]["dead_fraction"] <= 1
    # batch totals are the tenant rows plus unassigned (all-free) slots
    assert snap["live_rows"] == sum(
        r["live_rows"] for r in snap["tenants"].values()
    )
    # the provider surfaces the same section (the /snapshot body)
    assert server._telemetry_provider()["capacity"]["tenants"]["pad"][
        "dead_rows"
    ] == snap["tenants"]["pad"]["dead_rows"]
    # per-tenant gauges landed in the registry
    g = metrics.gauge("capacity.tenant_dead_rows", labelnames=("tenant",))
    assert g.labels(tenant="pad").value == snap["tenants"]["pad"]["dead_rows"]


def test_ingestor_ledger_matches_state_and_compaction_reclaims():
    """`BatchIngestor.capacity_ledger` mirrors `state_capacity_ledger`,
    and compaction strictly reduces the dead fraction (tail tombstones
    are clock-contiguous, so GC actually reclaims them)."""
    from ytpu.models.batch_doc import state_capacity_ledger
    from ytpu.ops.compaction import compact_state

    server = _served_state()
    live, dead, free = server.ingestor.capacity_ledger()
    s_live, s_dead = state_capacity_ledger(server.ingestor.state)
    assert np.array_equal(live, np.asarray(s_live))
    assert np.array_equal(dead, np.asarray(s_dead))
    assert int(dead.sum()) > 0
    compacted = compact_state(server.ingestor.state)
    c_live, c_dead = state_capacity_ledger(compacted)
    assert int(np.asarray(c_dead).sum()) < int(dead.sum())
    dead_frac = dead.sum() / max(int((live + dead).sum()), 1)
    c_dead_frac = int(np.asarray(c_dead).sum()) / max(
        int((np.asarray(c_live) + np.asarray(c_dead)).sum()), 1
    )
    assert c_dead_frac < dead_frac


# --- packed replay: ledger words ride the existing lazy readout -------------


@lru_cache(maxsize=1)
def _replay_workload():
    import bench as _bench
    from ytpu.models.replay import plan_replay

    ops = []
    length = 0
    for _ in range(6):
        for i in range(20):
            ops.append(("i", length, "abcdef"[i % 6]))
            length += 1
        ops.append(("d", length - 18, 18))
        length -= 18
    log, expect = _bench.build_updates(ops)
    return log, expect, plan_replay(log)


def test_ledger_rides_readout_with_zero_new_syncs():
    """The 3 ledger words ride the SAME [N_READOUT] future the driver
    already drains: `replay.readout` d2h attribution stays pinned at 12
    bytes per readout (unchanged since ISSUE-5), the new words charge
    under their own `capacity.ledger` stage at 4*LEDGER_WORDS per
    readout, and the sync count of a plain chunked run is unchanged."""
    from ytpu.models.replay import FusedReplay
    from ytpu.ops.integrate_kernel import LEDGER_WORDS

    log, expect, plan = _replay_workload()
    phases.reset()
    phases.enable()
    try:
        r = FusedReplay(
            n_docs=2, plan=plan, capacity=256, max_capacity=256,
            d_block=2, chunk=16, lane="xla",
        )
        stats = r.run(log)
        snap = phases.snapshot()
    finally:
        phases.disable()
        phases.reset()
    assert r.get_string(0) == expect
    readouts = snap["replay.readout"]["d2h_bytes"] // 12
    assert readouts >= stats.chunks
    assert snap["replay.readout"]["d2h_bytes"] == 12 * readouts
    assert (
        snap["capacity.ledger"]["d2h_bytes"] == 4 * LEDGER_WORDS * readouts
    ), snap["capacity.ledger"]
    # the drained ledger landed in stats and the occupancy gauges
    assert stats.occupied_rows >= 0 and stats.dead_rows >= 0
    assert "capacity.occupied_rows" in snap
    assert snap["capacity.dead_fraction"]["value"] <= 1.0


def test_compact_efficacy_rides_driver_stats():
    """A tombstone-heavy replay that compacts must report reclaimed
    rows and the chunk gap since the previous compaction."""
    from ytpu.models.replay import FusedReplay

    log, expect, plan = _replay_workload()
    r = FusedReplay(
        n_docs=2, plan=plan, capacity=64, max_capacity=64,
        d_block=2, chunk=16, lane="xla",
    )
    stats = r.run(log)
    assert r.get_string(0) == expect
    assert stats.compactions >= 1
    assert stats.reclaimed_rows > 0, stats
    assert stats.occupied_rows + stats.dead_rows <= 2 * 64


# --- headroom forecaster + typed grow.oom denial ----------------------------


def test_forecaster_flags_degraded_before_grow_oom():
    """The acceptance ordering: on an incompressible head-insert log the
    forecaster must flip `degraded` from ledger observations BEFORE the
    armed `grow.oom` moves the `memory.grow_denied` counter."""
    import bench as _bench
    from ytpu.models.replay import FusedReplay, plan_replay
    from ytpu.ops import integrate_kernel as ik

    ops = [("i", 0, "abcdef"[i % 6]) for i in range(120)]
    log, expect = _bench.build_updates(ops)
    plan = plan_replay(log)
    ik.reset_lane_health()
    faults.clear()
    faults.arm("grow.oom")
    try:
        denied0 = metrics.counter("memory.grow_denied").value
        fc = HeadroomForecaster(
            budget_bytes=ik.packed_state_bytes(2, 48), watermark=0.5
        )
        flagged_pre_denial = []
        observe = fc.observe

        def scored(**kw):
            observe(**kw)
            if fc.report()["degraded"]:
                flagged_pre_denial.append(
                    metrics.counter("memory.grow_denied").value == denied0
                )

        fc.observe = scored
        r = FusedReplay(
            n_docs=2, plan=plan, capacity=32, max_capacity=1024,
            d_block=2, chunk=4, lane="xla", forecaster=fc,
        )
        stats = r.run(log)
    finally:
        faults.clear()
        ik.reset_lane_health()
    assert r.get_string(0) == expect
    assert stats.growths >= 1 and stats.recoveries >= 1, stats
    assert metrics.counter("memory.grow_denied").value > denied0
    assert flagged_pre_denial and flagged_pre_denial[0] is True, (
        flagged_pre_denial
    )
    rep = fc.report()
    assert rep["grow_exceeds_budget"] and rep["degraded"]
    assert rep["headroom_fraction"] < 0  # next grow overshoots the budget


def test_grow_oom_error_reports_attempted_vs_available_bytes():
    """The typed denial carries the numbers an operator needs, stays a
    FaultError (site catalogue), and stays on the checkpoint-resume
    recovery path (`is_device_fault`)."""
    from ytpu.ops.integrate_kernel import (
        GrowOomError,
        is_device_fault,
        packed_state_bytes,
    )

    spec = FaultSpec("grow.oom")
    e = GrowOomError(
        spec,
        capacity=32,
        new_capacity=64,
        n_docs=2,
        attempted_bytes=packed_state_bytes(2, 64),
        available_bytes=10_000,
    )
    assert isinstance(e, FaultError)
    assert is_device_fault(e)
    assert e.attempted_bytes == packed_state_bytes(2, 64)
    assert e.available_bytes == 10_000
    assert str(e.attempted_bytes) in str(e) and "budget" in str(e)
    assert "32 -> 64" in str(e)


def test_memory_budget_env_override(monkeypatch):
    monkeypatch.setenv("YTPU_MEMORY_BUDGET_BYTES", "12345")
    assert memory_budget_bytes() == 12345
    monkeypatch.setenv("YTPU_MEMORY_BUDGET_BYTES", "junk")
    assert memory_budget_bytes() == 16 << 30
    assert packed_resident_bytes(2, 64) > 0


def test_forecaster_report_math():
    """Analytic fallback below 2 samples; fitted model after; the
    degraded flag needs BOTH budget overshoot and an occupancy trend."""
    fc = HeadroomForecaster(budget_bytes=5_000, watermark=0.5)
    assert fc.report() == {
        "observed": 0, "budget_bytes": 5_000, "degraded": False,
    }
    fc.observe(
        n_docs=2, capacity=16, occupied_rows=2, chunks=1, max_capacity=64
    )
    rep = fc.report()
    assert rep["grow_exceeds_budget"]  # analytic: psb(2,32)=6912 > 5k
    assert not rep["degraded"]  # no trend yet (one sample, rate 0)
    fc.observe(
        n_docs=2, capacity=16, occupied_rows=10, chunks=3, max_capacity=64
    )
    rep = fc.report()
    assert rep["growth_rows_per_chunk"] > 0
    assert rep["chunks_to_watermark"] is not None
    assert rep["degraded"]
    # trend projects (watermark_rows - occupied) / rate chunks ahead
    assert rep["chunks_to_watermark"] == pytest.approx(
        (0.5 * 32 - 10) / rep["growth_rows_per_chunk"], rel=1e-3
    )


# --- device-memory attribution at the jit boundary --------------------------


def test_program_memory_attribution_journals_and_peaks():
    """A span carrying a `program_memory` thunk journals the program's
    XLA memory analysis on first sighting and ratchets the per-stage
    peak ledger + gauges."""
    import jax
    import jax.numpy as jnp

    fn = jax.jit(lambda x: (x @ x).sum())
    x = jnp.zeros((64, 64), jnp.float32)
    phases.reset()
    phases.enable()
    try:
        with phases.span(
            "integrate.fused",
            ((64, 64),),
            axes=("shape",),
            memory=program_memory(fn, x),
        ):
            fn(x)
        report = phases.memory_report()
    finally:
        phases.disable()
        phases.reset()
    prog = report["programs"]["integrate.fused"]
    assert prog["peak_bytes"] > 0
    kinds = prog["kinds"]
    assert kinds["argument_bytes"] == 64 * 64 * 4
    assert kinds["resident_bytes"] == (
        kinds["argument_bytes"]
        + kinds["output_bytes"]
        - kinds["alias_bytes"]
        + kinds["temp_bytes"]
    )
    assert report["peak_program"] == "integrate.fused"
    assert report["peak_bytes"] == prog["peak_bytes"]
    # the per-program gauges landed in the registry
    g = metrics.gauge(
        "memory.program_bytes", labelnames=("program", "kind")
    )
    assert g.labels(
        program="integrate.fused", kind="argument_bytes"
    ).value == 64 * 64 * 4
    assert metrics.gauge(
        "memory.program_peak_bytes", labelnames=("program",)
    ).labels(program="integrate.fused").value == prog["peak_bytes"]


def test_program_memory_snapshots_specs_before_donation():
    """The thunk must survive being invoked AFTER the donated arrays
    are consumed — specs are captured eagerly at span construction."""
    import jax
    import jax.numpy as jnp

    fn = jax.jit(lambda x: x + 1, donate_argnums=(0,))
    x = jnp.zeros((16,), jnp.float32)
    thunk = program_memory(fn, x)
    fn(x)  # donates x's buffer
    kinds = thunk()  # must not touch the deleted buffer
    assert kinds["argument_bytes"] == 16 * 4
    assert kinds["alias_bytes"] == 16 * 4  # donation aliased in-place


# --- /capacity endpoint + health provider -----------------------------------


def _get(port, path):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=10
    ) as r:
        return r.status, r.read().decode()


def test_capacity_endpoint_serves_forecast_and_degrades_health():
    from ytpu.utils.telemetry import TelemetryServer

    fc = HeadroomForecaster(budget_bytes=5_000, watermark=0.5)
    fc.observe(
        n_docs=2, capacity=16, occupied_rows=4, chunks=1, max_capacity=64
    )
    fc.observe(
        n_docs=2, capacity=16, occupied_rows=12, chunks=3, max_capacity=64
    )
    with TelemetryServer(port=0) as t:
        t.add_capacity_provider("replay", fc.provider())
        t.add_health_provider("capacity", fc.provider())
        status, body = _get(t.port, "/capacity")
        assert status == 200
        cap = json.loads(body)
        assert cap["replay"]["degraded"] is True
        assert cap["replay"]["budget_bytes"] == 5_000
        assert "memory" in cap  # the per-program peak ledger section
        _, hbody = _get(t.port, "/healthz")
        h = json.loads(hbody)
        assert h["status"] == "degraded"
        assert h["capacity"]["grow_exceeds_budget"] is True
    # the endpoint self-accounts its scrapes like its siblings
    assert metrics.counter(
        "telemetry.scrapes", labelnames=("endpoint",)
    ).labels("capacity").value >= 1
