"""Capacity observatory (ISSUE-18): occupancy/fragmentation ledger,
device-memory attribution and the headroom forecaster.
"""

import json
import urllib.request

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


# --- headroom forecaster + typed grow.oom denial ----------------------------


def test_memory_budget_env_override(monkeypatch):
    monkeypatch.setenv("YTPU_MEMORY_BUDGET_BYTES", "12345")
    assert memory_budget_bytes() == 12345
    monkeypatch.setenv("YTPU_MEMORY_BUDGET_BYTES", "junk")
    assert memory_budget_bytes() == 16 << 30


def test_resident_bytes_are_the_states_column_planes():
    """The formula counts `BlockCols`' 26 planes at 4 bytes a row and 32
    words a room: what PERF.md calls "26 planes of 16 MB" at the
    benchmark's size, and never under what a state really holds (two of
    the planes are stored as bool)."""
    import jax

    from ytpu.models.batch_doc import BlockCols, init_state

    assert len(BlockCols._fields) == 26
    assert packed_resident_bytes(1024, 4096) == 26 * (16 << 20) + 32 * 4 * 1024
    state = init_state(2, 64)
    held = sum(a.nbytes for a in jax.tree.leaves(state))
    assert held <= packed_resident_bytes(2, 64) <= held * 1.1


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
