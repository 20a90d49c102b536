"""Multi-tenant soak driver + SLO scorer (ISSUE-9 tentpole).

Drives a `SyncServer` / `DeviceSyncServer` with a `Scenario`'s session
traffic and scores the run against SLOs:

- **sustained updates/s** over the wall-clock budget (multi-round: the
  scenario regenerates deterministically per round until the budget is
  spent);
- **p50/p99 apply latency** from the existing `sync.apply_update`
  histogram (the BASELINE SLO series) *windowed to this run*
  (`ytpu.utils.slo.HistogramWindow`), reported **raw and with the
  measured RTT floor subtracted** (VERDICT Weak #7) — the floor is
  measured per run by idle-echo probes (a SyncStep1 carrying the
  server's own state vector: the reply encodes an empty diff, so the
  round-trip is pure protocol + transport);
- **p50/p99 diff latency** (`soak.diff_latency`) and end-to-end
  per-event apply latency (`soak.apply_e2e`);
- **admission behavior**: Busy replies, retries, drops and sheds, all
  attributable via `admission.*` and `net.sessions_dropped{reason=}`.

Mid-soak survivability is part of the score, not a separate test:
``checkpoint_at`` takes a full `save_device_server` → `load_device_server`
round-trip at that fraction of the schedule (sessions reconnect, traffic
continues), and ``rebalance_at`` moves the hottest tenant to a fresh
device slot live (`DeviceSyncServer.rebalance_tenant`).  Because the
scenario is deterministic and CRDT merge is order-independent, a clean
run and a checkpoint+rebalance run of the same scenario must land the
same `state_digest` — byte parity is the acceptance surface.

Fault sites (docs/robustness.md): ``session.kill`` force-drops the
current event's session (it reconnects and resyncs); the admission layer
owns ``admission.reject``.  The TCP variant (`run_soak_tcp`) composes
with the ISSUE-6 transport faults (``net.drop`` / ``net.delay`` /
``net.truncate``) since its frames cross real sockets.
"""

from __future__ import annotations

import hashlib
import os
import tempfile
import time
from typing import Dict, List, Optional

from ytpu.core.state_vector import StateVector
from ytpu.sync.awareness import AwarenessUpdate
from ytpu.sync.protocol import (
    MSG_BUSY,
    Message,
    SyncMessage,
    message_reader,
)
from ytpu.utils import metrics
from ytpu.utils.faults import faults
from ytpu.utils.phases import compile_storm_provider, phases
from ytpu.utils.profile import ProfileWindow
from ytpu.utils.slo import (
    HistogramWindow,
    slo_report,
    window_prometheus_text,
)
from ytpu.utils.trace import trace_context, tracer

from .scenario import Scenario

__all__ = [
    "CANARY_PREFIX",
    "FederatedSoakDriver",
    "SoakDriver",
    "run_soak_tcp",
    "server_state_digest",
]

#: synthetic canary tenants (`ytpu.serving.canary.CanaryProber`) live
#: under this prefix and are EXCLUDED from `server_state_digest` — probe
#: traffic must never move the soak byte-parity surface
CANARY_PREFIX = "__canary"


def server_state_digest(server, root: str) -> str:
    """Canonical per-tenant state digest — tenant name, the rendered
    root text (device-side when the tenant holds a slot), and the
    sorted state vector, hashed.  Two servers that land byte-equal
    digests hold byte-equal observable tenant states: the soak parity
    surface, shared by `SoakDriver` and the federated soak (every mesh
    replica must land the clean single-server run's digest).  Canary
    tenants (`CANARY_PREFIX`) are skipped: synthetic probe traffic is
    per-replica by design and must stay off the parity surface."""
    flush = getattr(server, "flush_device", None)
    if flush is not None:
        flush()
    h = hashlib.sha256()
    for t in sorted(server.tenants):
        if t.startswith(CANARY_PREFIX):
            continue
        h.update(t.encode())
        h.update(_server_tenant_text(server, t, root).encode())
        sv = server.tenant_state_vector(t)
        h.update(repr(sorted(sv)).encode())
    return h.hexdigest()


def _server_tenant_text(server, tenant: str, root: str) -> str:
    if hasattr(server, "device_text"):
        try:
            return server.device_text(tenant)
        except KeyError:
            pass  # host-resident tenant
    return server.doc(tenant).get_text(root).get_string()

def _admission_values() -> Dict[str, int]:
    """The admission module's OWN cached counter objects — the ones
    `admit()` increments — not fresh registry lookups: a test-time
    `metrics.reset()` orphans cached metrics, and reading re-registered
    namesakes would report zeros forever after."""
    from ytpu.serving import admission as _adm

    out = {"admitted": _adm._ADMITTED.value}
    for reason in ("queue_full", "rate_limited", "injected"):
        out[f"rejected_{reason}"] = _adm._REJECTED.labels(reason).value
    return out


class SoakDriver:
    """In-process soak: sessions are server `Session` objects, events are
    pumped straight through `receive_frames` (deterministic, tier-1-safe
    — the TCP transport variant is `run_soak_tcp`)."""

    def __init__(
        self,
        server,
        scenario: Scenario,
        admission=None,
        flush_every: int = 8,
        checkpoint_at: Optional[float] = None,
        rebalance_at: Optional[float] = None,
        budget_s: Optional[float] = None,
        rounds: int = 1,
        ckpt_dir: Optional[str] = None,
        rtt_probes: int = 16,
        max_busy_retries: int = 200,
        telemetry_port: Optional[int] = None,
        probe_at: Optional[float] = None,
        probe=None,
        retrace_budget: Optional[int] = None,
    ):
        self.server = server
        self.scenario = scenario
        self.admission = admission
        self.flush_every = max(1, flush_every)
        self.checkpoint_at = checkpoint_at
        self.rebalance_at = rebalance_at
        self.budget_s = budget_s
        self.rounds = max(1, rounds)
        self.ckpt_dir = ckpt_dir
        self.rtt_probes = rtt_probes
        self.max_busy_retries = max_busy_retries
        #: compile sentinel budget (ISSUE-17): max retraces this run may
        #: score before the report flags it and the `compile` health
        #: provider degrades `/healthz`; None = report-only (a cold run
        #: legitimately retraces as shapes appear — only a WARMED run
        #: should pin the budget)
        self.retrace_budget = retrace_budget
        #: mid-soak observation hook: at fraction ``probe_at`` of round
        #: 0's schedule, ``probe()`` is called — the telemetry rehearsal
        #: scrapes the live HTTP endpoints there, mid-run by construction
        self.probe_at = probe_at
        self.probe = probe
        self._sessions: Dict[int, object] = {}
        self._counts: Dict[str, int] = {}
        self._apply_hist = metrics.histogram("soak.apply_e2e")
        self._diff_hist = metrics.histogram("soak.diff_latency")
        # live telemetry plane (ISSUE-11): the DRIVER owns the endpoint
        # (not the server object — a mid-soak checkpoint/restore swaps
        # the server out; the driver survives), exposing the in-flight
        # SLO windows under `/snapshot`'s "soak" section
        self._live = None  # (apply_w, e2e_w, diff_w, floor_s) during run
        self._running = False
        self.telemetry = None
        if telemetry_port is not None:
            from ytpu.utils.telemetry import TelemetryServer

            self.telemetry = TelemetryServer(port=telemetry_port)
            self.telemetry.add_provider("soak", self._live_slo)
            # the run's SLO windows as REAL Prometheus histograms on
            # `/metrics` (ISSUE-15 satellite): an external scraper
            # computes its own windowed quantiles from the buckets
            # instead of trusting the p50/p99 gauges
            self.telemetry.add_exposition(
                "soak_windows", self._window_exposition
            )
            self.telemetry.start()

    def _live_slo(self) -> Dict:
        """`/snapshot`'s "soak" section: the CURRENT run's SLO windows
        (what the final report will score), readable mid-run."""
        if self._live is None:
            return {"running": False}
        apply_w, e2e_w, diff_w, floor_s = self._live
        try:
            # read from the scrape thread while run() mutates: a resize
            # mid-copy surfaces as RuntimeError — skip counts this scrape
            # rather than fail it (the SLO windows are lock-protected)
            counts = dict(self._counts)
        except RuntimeError:
            counts = {}
        return {
            "running": self._running,
            **{k: v for k, v in sorted(counts.items())},
            **slo_report(apply_w, floor_s, "apply_"),
            **slo_report(e2e_w, floor_s, "apply_e2e_"),
            **slo_report(diff_w, floor_s, "diff_"),
        }

    def _window_exposition(self) -> str:
        """The current run's SLO windows rendered as Prometheus
        histogram families (`window_prometheus_text`) for `/metrics`.
        Empty before/after a run — the families exist only while their
        windows do."""
        if self._live is None:
            return ""
        apply_w, e2e_w, diff_w, _floor = self._live
        return (
            window_prometheus_text("soak_window_apply", apply_w)
            + window_prometheus_text("soak_window_apply_e2e", e2e_w)
            + window_prometheus_text("soak_window_diff", diff_w)
        )

    # --- plumbing --------------------------------------------------------------

    def _flush(self) -> None:
        flush = getattr(self.server, "flush_device", None)
        if flush is not None:
            flush()

    def _drain_all(self) -> None:
        n = 0
        for sess in list(self._sessions.values()):
            n += len(self.server.drain(sess))
        self._counts["broadcast_frames"] = (
            self._counts.get("broadcast_frames", 0) + n
        )

    def _connect(self, sid: int, tenant: str):
        sess, _greeting = self.server.connect_frames(tenant)
        self._sessions[sid] = sess
        return sess

    def _preregister_clients(self, scenario: Scenario) -> None:
        """Intern the round's known client ids up front (device-backed
        servers only).  A choice, not a need: the lookup tables keep one
        padded shape whoever writes (`BatchIngestor._table_floor`), so a
        first-seen client mid-run retraces nothing and stays on the fast
        lane.  What this still saves is the rebuild and upload of the
        rank table and the client (or client-hash) table in the step that
        meets a new writer — 1-2 ms of host time on a v5e host, once a
        writer — and, for a scenario with more writers than the tables'
        floor, the doublings (one compile of the decode or integrate
        program each) happen here, before the measured rounds."""
        ing = getattr(self.server, "ingestor", None)
        if ing is None:
            return
        for script in scenario.sessions:
            ing.enc.interner.intern(script.client_id)

    def _session(self, ev):
        sess = self._sessions.get(ev.session)
        if sess is None or sess.dead:
            sess = self._connect(ev.session, ev.tenant)
        return sess

    def _bump(self, key: str, n: int = 1) -> None:
        self._counts[key] = self._counts.get(key, 0) + n

    # --- RTT floor -------------------------------------------------------------

    def _measure_rtt_floor(self, scenario: Scenario) -> float:
        """Idle-echo floor: SyncStep1 carrying the server's OWN state
        vector — the reply is an empty diff, so the round-trip measures
        protocol + encode overhead with zero integration work.  min over
        the probes is the least-contended estimate (same rationale as
        the bench's best-of-N native baseline)."""
        tenant = scenario.tenants[0]
        sess, _ = self.server.connect_frames(tenant)
        best = None
        for _ in range(max(1, self.rtt_probes)):
            sv = self.server.tenant_state_vector(tenant)
            frame = Message.sync(SyncMessage.step1(sv)).encode_v1()
            t0 = time.perf_counter()
            self.server.receive_frames(sess, frame)
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        self.server.drain(sess)
        self.server.disconnect(sess)
        return best or 0.0

    # --- mid-soak failover -----------------------------------------------------

    def _checkpoint_restore(self) -> None:
        """Full save → load round-trip, swapping the live server out from
        under the traffic (sessions are transient by design — they
        reconnect and resync exactly like clients of a restarted pod)."""
        from ytpu.models.checkpoint import (
            load_device_server,
            save_device_server,
        )

        ctx = (
            tempfile.TemporaryDirectory()
            if self.ckpt_dir is None
            else None
        )
        path = ctx.name if ctx is not None else self.ckpt_dir
        try:
            save_device_server(os.path.join(path, "soak_ckpt"), self.server)
            restored = load_device_server(os.path.join(path, "soak_ckpt"))
        finally:
            if ctx is not None:
                ctx.cleanup()
        restored.admission = self.admission
        self.server = restored
        # every live session reconnects against the restored server
        for sid, old in list(self._sessions.items()):
            self._connect(sid, old.tenant)
        self._bump("checkpoints")

    def _rebalance(self) -> None:
        """Move the hottest tenant (most applies so far) to a fresh slot,
        asserting text parity across the move."""
        if not hasattr(self.server, "rebalance_tenant"):
            return
        hot = max(
            self._applies_by_tenant,
            key=lambda t: self._applies_by_tenant[t],
            default=None,
        )
        if hot is None:
            return
        self._flush()
        before = self.server.device_text(hot)
        self.server.rebalance_tenant(hot)
        ok = self.server.device_text(hot) == before
        self._bump("rebalances")
        if not ok:
            self._counts["rebalance_parity_failures"] = (
                self._counts.get("rebalance_parity_failures", 0) + 1
            )

    # --- event handling --------------------------------------------------------

    def _handle(self, ev, retries: int, backlog: List) -> None:
        if faults.active and faults.fire("session.kill") is not None:
            # forced mid-soak session death: drop it now; `_session`
            # reconnects it for this very event (resync-on-reconnect)
            old = self._sessions.pop(ev.session, None)
            if old is not None:
                self.server.disconnect(old)
            self._bump("session_kills")
        sess = self._session(ev)
        if ev.kind == "apply":
            frame = Message.sync(SyncMessage.update(ev.payload)).encode_v1()
            t0 = time.perf_counter()
            replies = self.server.receive_frames(sess, frame)
            self._apply_hist.observe(time.perf_counter() - t0)
            if any(
                m.kind == MSG_BUSY
                for r in replies
                for m in message_reader(r)
            ):
                self._bump("busy_replies")
                if retries < self.max_busy_retries:
                    # the server asked us to back off: drain the device
                    # queue (the backpressure valve) and retry the SAME
                    # update later — defer policy loses nothing
                    self._flush()
                    backlog.append((ev, retries + 1))
                    self._bump("busy_retries")
                else:
                    self._bump("dropped_updates")
                return
            self._bump("applied")
            t = ev.tenant
            self._applies_by_tenant[t] = self._applies_by_tenant.get(t, 0) + 1
            if self._counts.get("applied", 0) % self.flush_every == 0:
                self._flush()
                self._drain_all()
        elif ev.kind == "diff":
            sv = StateVector.decode_v1(ev.payload)
            frame = Message.sync(SyncMessage.step1(sv)).encode_v1()
            t0 = time.perf_counter()
            replies = self.server.receive_frames(sess, frame)
            self._diff_hist.observe(time.perf_counter() - t0)
            self._bump("diffs")
            if replies:
                self._bump("diff_bytes", sum(len(r) for r in replies))
        elif ev.kind == "awareness":
            up = AwarenessUpdate.decode_v1(ev.payload)
            self.server.receive_frames(
                sess, Message.awareness(up).encode_v1()
            )
            self._bump("awareness")
        elif ev.kind == "reconnect":
            self.server.disconnect(sess)
            self._connect(ev.session, ev.tenant)
            self._bump("reconnects")

    # --- the run ---------------------------------------------------------------

    def run(self) -> Dict:
        if self.admission is not None:
            self.server.admission = self.admission
        adm_before = _admission_values()
        applied_server_before = metrics.counter("sync.updates_applied").value
        # the diff path routes through the encode pipeline (ISSUE-10):
        # score how many answers it served and whether any sub-batch had
        # to demote to the serial per-doc finisher
        diff_pipe_before = metrics.counter("encode.pipeline_runs").value
        enc_demotions_before = metrics.counter("encode.demotions").value
        scenario = self.scenario
        self._preregister_clients(scenario)
        rtt_floor_s = self._measure_rtt_floor(scenario)
        # compile/retrace sentinel window (ISSUE-17): everything above
        # (client preregistration, RTT pings) is warmup — compile events
        # past this marker belong to THIS run, and retraces among them
        # score against `retrace_budget`. The profile window baselines
        # the wall-time attribution over the same span.
        compile_marker = phases.compile_marker()
        profile_window = ProfileWindow()
        if self.telemetry is not None:
            self.telemetry.add_health_provider(
                "compile",
                compile_storm_provider(
                    budget=self.retrace_budget, marker=compile_marker
                ),
            )
            self.telemetry.set_profile_source(profile_window.report)
        # fresh delta windows per run(): back-to-back soak runs (or
        # rounds driven as separate runs) must never blend percentiles —
        # the windows below this line see ONLY this run's samples
        # (pinned by tests/test_metrics_trace.py window-reset test)
        apply_w = HistogramWindow(metrics.histogram("sync.apply_update"))
        e2e_w = HistogramWindow(self._apply_hist)
        diff_w = HistogramWindow(self._diff_hist)
        self._live = (apply_w, e2e_w, diff_w, rtt_floor_s)
        self._running = True
        self._counts = {}
        self._applies_by_tenant: Dict[str, int] = {}
        complete = True
        t_start = time.perf_counter()

        def over_budget() -> bool:
            return (
                self.budget_s is not None
                and time.perf_counter() - t_start > self.budget_s
            )

        rounds_done = 0
        for rnd in range(self.rounds):
            if rnd > 0:
                if over_budget():
                    break
                scenario = self.scenario.with_round(rnd)
                self._preregister_clients(scenario)
                # fresh deterministic traffic, fresh sessions
                for sess in self._sessions.values():
                    self.server.disconnect(sess)
                self._sessions = {}
            schedule = list(scenario.events())
            total = len(schedule)
            ckpt_idx = (
                int(total * self.checkpoint_at)
                if rnd == 0 and self.checkpoint_at is not None
                else None
            )
            reb_idx = (
                int(total * self.rebalance_at)
                if rnd == 0 and self.rebalance_at is not None
                else None
            )
            probe_idx = (
                int(total * self.probe_at)
                if rnd == 0
                and self.probe_at is not None
                and self.probe is not None
                else None
            )
            backlog: List = []  # Busy-deferred (event, retries)
            for i, ev in enumerate(schedule):
                if over_budget():
                    complete = False
                    break
                if ckpt_idx is not None and i == ckpt_idx:
                    self._checkpoint_restore()
                if reb_idx is not None and i == reb_idx:
                    self._rebalance()
                if probe_idx is not None and i == probe_idx:
                    self.probe()
                self._handle(ev, 0, backlog)
                self._bump("events")
            # drain the Busy backlog: defer policy converges because the
            # flush between retries frees queue budget and wall time
            # refills the rate bucket
            while backlog and not over_budget():
                ev, retries = backlog.pop(0)
                self._handle(ev, retries, backlog)
                self._bump("events")
            if backlog:
                complete = False
                self._bump("dropped_updates", len(backlog))
                break
            rounds_done += 1
        wall_s = time.perf_counter() - t_start
        self._running = False  # windows stay scrapeable, marked final
        self._flush()
        self._drain_all()
        for sess in self._sessions.values():
            self.server.disconnect(sess)
        self._sessions = {}

        applied = self._counts.get("applied", 0)
        # the server's own apply counter increments only past admission:
        # under drop/shed policies it reads BELOW the driver's submit
        # count — the lossy policies' accounting surface
        applied_server = (
            metrics.counter("sync.updates_applied").value
            - applied_server_before
        )
        report: Dict = {
            "applied_server": applied_server,
            "scenario_digest": self.scenario.digest(),
            "rounds": rounds_done,
            "complete": complete,
            "wall_s": round(wall_s, 4),
            "updates_per_s": round(applied / max(wall_s, 1e-9), 1),
            "rtt_floor_ms": round(rtt_floor_s * 1e3, 4),
            "state_digest": self.state_digest(),
            "sessions": len(self.scenario.sessions),
            **{k: v for k, v in sorted(self._counts.items())},
            **slo_report(apply_w, rtt_floor_s, "apply_"),
            **slo_report(e2e_w, rtt_floor_s, "apply_e2e_"),
            **slo_report(diff_w, rtt_floor_s, "diff_"),
        }
        adm_after = _admission_values()
        report["admission"] = {
            k: adm_after[k] - adm_before[k] for k in adm_after
        }
        report["diff_pipeline_runs"] = (
            metrics.counter("encode.pipeline_runs").value - diff_pipe_before
        )
        report["encode_demotions"] = (
            metrics.counter("encode.demotions").value - enc_demotions_before
        )
        # sentinel + attribution sections (ISSUE-17): retraces since the
        # post-warmup marker (journal names the changed axis) and the
        # top-down wall budget over the same window
        compile_rep = phases.compile_report(since=compile_marker)
        compile_rep["budget"] = self.retrace_budget
        compile_rep["within_budget"] = (
            self.retrace_budget is None
            or compile_rep["retraces"] <= self.retrace_budget
        )
        report["compile"] = compile_rep
        report["profile"] = profile_window.report(wall_s=wall_s)
        mirror = self._mirror_parity()
        if mirror is not None:
            report["mirror_parity"] = mirror
        return report

    # --- scoring surfaces ------------------------------------------------------

    def state_digest(self) -> str:
        """Canonical per-tenant state digest (`server_state_digest`) —
        the soak parity surface."""
        return server_state_digest(self.server, self.scenario.config.root)

    def _tenant_text(self, tenant: str) -> str:
        return _server_tenant_text(
            self.server, tenant, self.scenario.config.root
        )

    def _mirror_parity(self) -> Optional[bool]:
        """Mirrored-mode cross-check: device text == host text for every
        slotted tenant (None when not applicable)."""
        server = self.server
        if not hasattr(server, "device_text") or getattr(
            server, "device_authoritative", False
        ):
            return None
        root = self.scenario.config.root
        for t in sorted(server.tenants):
            if t in getattr(server, "_host_tenants", ()):
                continue
            host = server.doc(t).get_text(root).get_string()
            if server.device_text(t) != host:
                return False
        return True


class FederatedSoakDriver:
    """2–3 replica federated soak (ISSUE-13): the PR-9 scenario driven
    at a `ReplicaMesh` with tenant-sharded ownership, periodic sync +
    commitment-verified anti-entropy rounds, and a scripted chaos
    schedule — partition, heal, forced replica failover (sessions of
    the dead replica reconnect to a survivor) and optional live tenant
    migration — scored at BYTE PARITY against the same scenario's clean
    single-server run: every surviving replica must land the PR-9
    oracle `state_digest`.

    Fractions (``partition_at`` etc.) index round-0's event schedule
    like `SoakDriver.checkpoint_at`.  The driver routes each event to
    its tenant's current owner (`mesh.route`), so ownership handoffs
    re-route traffic live; a session whose replica died reconnects on
    its next event (``failover_reconnects``).  When a divergence is
    caught (e.g. an armed ``commit.corrupt``), the quarantined tenant
    recovers in the convergence epilogue (``divergence_recoveries``)
    unless ``recover_divergence=False``."""

    def __init__(
        self,
        mesh,
        scenario: Scenario,
        flush_every: int = 8,
        sync_every: int = 8,
        anti_entropy_every: int = 24,
        partition_at: Optional[float] = None,
        partition_pair: Optional[tuple] = None,
        heal_at: Optional[float] = None,
        failover_at: Optional[float] = None,
        failover_replica: Optional[str] = None,
        migrate_at: Optional[float] = None,
        migrate_to: Optional[str] = None,
        recover_divergence: bool = True,
        max_converge_rounds: int = 32,
        max_busy_retries: int = 8,
        canary_every: Optional[int] = None,
        probe_at: Optional[float] = None,
        probe=None,
        admission=None,
        autopilot=None,
        autopilot_every: Optional[int] = None,
        rtt_probes: int = 16,
        retrace_budget: Optional[int] = None,
    ):
        self.mesh = mesh
        self.scenario = scenario
        self.flush_every = max(1, flush_every)
        self.sync_every = max(1, sync_every)
        self.anti_entropy_every = max(1, anti_entropy_every)
        self.partition_at = partition_at
        self.partition_pair = partition_pair
        self.heal_at = heal_at
        self.failover_at = failover_at
        self.failover_replica = failover_replica
        self.migrate_at = migrate_at
        self.migrate_to = migrate_to
        self.recover_divergence = recover_divergence
        self.max_converge_rounds = max(1, max_converge_rounds)
        self.max_busy_retries = max(0, max_busy_retries)
        #: synthetic canary cadence (ISSUE-15): every ``canary_every``
        #: events the `CanaryProber` runs one probe pass against every
        #: replica; None disables probing entirely
        self.canary_every = canary_every
        #: mid-soak observation hook (the `SoakDriver.probe_at`
        #: discipline): at fraction ``probe_at`` of the event schedule,
        #: ``probe()`` is called — the fleet rehearsal scrapes the live
        #: `/fleet` endpoint there, mid-run by construction
        self.probe_at = probe_at
        self.probe = probe
        #: one `AdmissionController` shared by every replica server for
        #: the run (ISSUE-16): queue depths stay per-server/per-tenant,
        #: so a shared controller means shared *policy*, not a shared
        #: queue — and the autopilot retunes one object for the fleet
        self.admission = admission
        #: `FleetAutopilot` ticked every ``autopilot_every`` events
        #: (default: with every periodic sync round) — ISSUE-16: the
        #: scored on-vs-off experiment runs the same schedule either way
        self.autopilot = autopilot
        self.autopilot_every = max(1, autopilot_every or sync_every)
        self.rtt_probes = rtt_probes
        #: compile sentinel budget (ISSUE-17; `SoakDriver.retrace_budget`
        #: semantics: None = report-only)
        self.retrace_budget = retrace_budget
        self.canary = None  # CanaryProber while run() is live
        self._sessions: Dict[int, tuple] = {}  # sid -> (replica_id, Session)
        self._counts: Dict[str, int] = {}
        self._e2e_hist = metrics.histogram("soak.apply_e2e")

    def _bump(self, key: str, n: int = 1) -> None:
        self._counts[key] = self._counts.get(key, 0) + n

    def _drain_all(self) -> None:
        """Pull broadcast frames out of every soak client session's
        outbox (the SoakDriver discipline): left undrained, a long soak
        overflows the bounded outboxes and slow-consumer eviction sheds
        the sessions, polluting the failover session-drop attribution."""
        n = 0
        for rid, sess in list(self._sessions.values()):
            holder = self.mesh.replicas[rid]
            if holder.alive and not sess.dead:
                n += len(holder.server.drain(sess))
        if n:
            self._bump("broadcast_frames", n)

    def _session(self, ev):
        """The event's session on its tenant's CURRENT owner replica —
        reconnecting across failovers (dead replica) and re-routing
        across ownership handoffs (migration)."""
        target = self.mesh.route(ev.tenant)
        cur = self._sessions.get(ev.session)
        if cur is not None:
            rid, sess = cur
            holder = self.mesh.replicas[rid]
            if holder.alive and rid == target.id and not sess.dead:
                return holder.server, sess
            if not holder.alive:
                self._bump("failover_reconnects")
            elif rid != target.id:
                self._bump("rerouted_sessions")
                holder.server.disconnect(sess)
        sess, _greeting = target.server.connect_frames(ev.tenant)
        self._sessions[ev.session] = (target.id, sess)
        return target.server, sess

    def _handle(self, ev) -> None:
        """Route + serve one event, under a fresh trace when the tracer
        is live: the ambient trace id minted here rides the broadcast
        trace frames across every peer link the update crosses, so one
        client edit is followable replica-to-replica in the Chrome dump
        (the ISSUE-15 cross-replica propagation surface)."""
        server, sess = self._session(ev)
        if not tracer.enabled:
            self._handle_inner(ev, server, sess)
            return
        rid = self._sessions.get(ev.session, (None,))[0]
        with trace_context(tenant=ev.tenant, session=ev.session,
                           replica=rid):
            with tracer.span("soak.event", kind=ev.kind, tenant=ev.tenant,
                             replica=rid):
                self._handle_inner(ev, server, sess)

    def _handle_inner(self, ev, server, sess) -> None:
        if ev.kind == "apply":
            frame = Message.sync(SyncMessage.update(ev.payload)).encode_v1()
            # e2e timing covers the WHOLE retry loop (ISSUE-16): a Busy
            # deferral's flush+retry cost is latency the client saw, so
            # the federated p99 scores admission behavior, not just the
            # raw apply — the autopilot on-vs-off comparison surface
            with self._e2e_hist.time():
                for _ in range(self.max_busy_retries + 1):
                    replies = server.receive_frames(sess, frame)
                    if not any(
                        m.kind == MSG_BUSY
                        for r in replies
                        for m in message_reader(r)
                    ):
                        self._bump("applied")
                        break
                    # an admission-deferred update must not be lost:
                    # drain the backpressure valve and retry the SAME
                    # frame (the SoakDriver backlog discipline, inline)
                    self._bump("busy_replies")
                    flush = getattr(server, "flush_device", None)
                    if flush is not None:
                        flush()
                else:
                    self._bump("dropped_updates")
        elif ev.kind == "diff":
            sv = StateVector.decode_v1(ev.payload)
            frame = Message.sync(SyncMessage.step1(sv)).encode_v1()
            server.receive_frames(sess, frame)
            self._bump("diffs")
        elif ev.kind == "awareness":
            up = AwarenessUpdate.decode_v1(ev.payload)
            server.receive_frames(sess, Message.awareness(up).encode_v1())
            self._bump("awareness")
        elif ev.kind == "reconnect":
            server.disconnect(sess)
            self._sessions.pop(ev.session, None)
            self._bump("reconnects")

    def _counter_deltas(self):
        """The replica module's OWN cached counter objects — the ones
        the mesh increments — not fresh registry lookups (a test-time
        `metrics.reset()` orphans cached metrics; same rationale as
        `_admission_values`).  The failover-drop child comes from a
        mesh server's cached `_dropped` family for the same reason."""
        from ytpu.sync import replica as _rep

        vals = {
            "replica.partitions": _rep._PARTITIONS.value,
            "replica.heals": _rep._HEALS.value,
            "replica.failovers": _rep._FAILOVERS.value,
            "replica.migrations": _rep._MIGRATIONS.value,
            "replica.commit_mismatches": _rep._MISMATCHES.value,
            "replica.divergences": _rep._DIVERGENCES.value,
            "replica.recoveries": _rep._RECOVERIES.value,
            "replica.anti_entropy_bytes": _rep._AE_BYTES.value,
        }
        dropped = next(iter(self.mesh.replicas.values())).server._dropped
        vals["net.sessions_dropped.failover"] = dropped.labels(
            "failover"
        ).value
        return vals

    def _measure_rtt_floor(self, scenario: Scenario) -> float:
        """Idle-echo floor against the first tenant's owner (the
        `SoakDriver` discipline): SyncStep1 carrying the server's OWN
        state vector round-trips pure protocol + encode, so the
        ``_adj`` SLO twins report mesh-attributable latency."""
        tenant = scenario.tenants[0]
        rep = self.mesh.route(tenant)
        sess, _ = rep.server.connect_frames(tenant)
        best = None
        for _ in range(max(1, self.rtt_probes)):
            sv = rep.server.tenant_state_vector(tenant)
            frame = Message.sync(SyncMessage.step1(sv)).encode_v1()
            t0 = time.perf_counter()
            rep.server.receive_frames(sess, frame)
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        rep.server.drain(sess)
        rep.server.disconnect(sess)
        return best or 0.0

    def run(self) -> Dict:
        mesh = self.mesh
        scenario = self.scenario
        root = scenario.config.root
        before = self._counter_deltas()
        self._counts = {}
        if self.admission is not None:
            for rep in mesh.replicas.values():
                rep.server.admission = self.admission
        e2e_w = HistogramWindow(self._e2e_hist)
        # the canary's tenants are created (and host-demoted) BEFORE the
        # scenario tenants claim their device slots: create-then-release
        # keeps at most one slot in flight, so probing never steals a
        # slot a real tenant needs
        if self.canary_every is not None:
            from .canary import CanaryProber

            self.canary = CanaryProber(mesh, root=root)
        # tenant-sharded hot-doc ownership: deterministic round-robin
        # over the alive replicas (typed epoch-bumped handoffs)
        ids = [r.id for r in mesh.alive()]
        for tenant, shard in scenario.owner_shards(len(ids)).items():
            mesh.assign_owner(tenant, ids[shard])
        mesh.preregister_clients(s.client_id for s in scenario.sessions)
        floor_s = self._measure_rtt_floor(scenario)
        # sentinel + attribution windows (ISSUE-17): the SoakDriver
        # discipline — preregistration/RTT pings are warmup
        compile_marker = phases.compile_marker()
        profile_window = ProfileWindow()
        schedule = list(scenario.events())
        total = len(schedule)

        def idx(frac):
            return int(total * frac) if frac is not None else None

        partition_idx = idx(self.partition_at)
        heal_idx = idx(self.heal_at)
        failover_idx = idx(self.failover_at)
        migrate_idx = idx(self.migrate_at)
        probe_idx = idx(self.probe_at) if self.probe is not None else None
        t_start = time.perf_counter()
        for i, ev in enumerate(schedule):
            if partition_idx is not None and i == partition_idx:
                alive_ids = [r.id for r in mesh.alive()]
                if self.partition_pair or len(alive_ids) >= 2:
                    a, b = self.partition_pair or (
                        alive_ids[0], alive_ids[1],
                    )
                    mesh.partition(a, b)
            if heal_idx is not None and i == heal_idx:
                mesh.heal()
            if failover_idx is not None and i == failover_idx:
                victim = self.failover_replica or [
                    r.id for r in mesh.alive()
                ][-1]
                dropped = mesh.kill_replica(victim, drain=True)
                self._bump("failover_sessions_dropped", dropped)
            if migrate_idx is not None and i == migrate_idx:
                hot = scenario.tenants[0]
                cur_owner = mesh.owner[hot][0]
                others = [
                    r.id for r in mesh.alive() if r.id != cur_owner
                ]
                dst = self.migrate_to or (others[-1] if others else None)
                if dst is not None:
                    mesh.migrate_tenant(hot, dst)
            if probe_idx is not None and i == probe_idx:
                self.probe()
            self._handle(ev)
            self._bump("events")
            if (
                self.canary is not None
                and (i + 1) % self.canary_every == 0
            ):
                self.canary.tick()
                self._bump("canary_ticks")
            if (i + 1) % self.flush_every == 0:
                mesh.flush_devices()
                self._drain_all()
            if (i + 1) % self.sync_every == 0:
                mesh.sync_round()
                if self.canary is not None:
                    self.canary.observe_round()
            if (
                self.autopilot is not None
                and (i + 1) % self.autopilot_every == 0
            ):
                self.autopilot.tick()
            if (i + 1) % self.anti_entropy_every == 0:
                mesh.anti_entropy_round()
        # convergence epilogue: sync + anti-entropy (recovering any
        # quarantined tenant) until every surviving replica's digest
        # agrees — `converge_rounds` is the headline federation cost
        converged = False
        converge_rounds = 0
        digests: Dict[str, str] = {}
        while converge_rounds < self.max_converge_rounds:
            converge_rounds += 1
            mesh.sync_round(fire_faults=False)
            if self.canary is not None:
                # pending read-your-writes watches must resolve (or time
                # out, attributed) before the run is scored
                self.canary.observe_round()
            mesh.anti_entropy_round()
            if mesh.quarantined and self.recover_divergence:
                for tenant in sorted(mesh.quarantined):
                    if mesh.recover_tenant(tenant):
                        self._bump("divergence_recoveries")
            digests = {
                r.id: server_state_digest(r.server, root)
                for r in mesh.alive()
            }
            if len(set(digests.values())) == 1 and not mesh.quarantined:
                converged = True
                break
        wall_s = time.perf_counter() - t_start
        self._drain_all()
        for rid, sess in self._sessions.values():
            holder = self.mesh.replicas[rid]
            if holder.alive:
                holder.server.disconnect(sess)
        self._sessions = {}
        after = self._counter_deltas()
        delta = {k: after[k] - before[k] for k in after}
        applied = self._counts.get("applied", 0)
        canary_report = None
        if self.canary is not None:
            canary_report = self.canary.report()
            self.canary.close()
        out = {
            "replicas": len(mesh.replicas),
            "replicas_alive": len(mesh.alive()),
            "sessions": len(scenario.sessions),
            "scenario_digest": scenario.digest(),
            "wall_s": round(wall_s, 4),
            "updates_per_s": round(applied / max(wall_s, 1e-9), 1),
            "converged": converged,
            "converge_rounds": converge_rounds,
            "state_digest": next(iter(digests.values()), ""),
            "replica_digests": digests,
            "quarantined": sorted(mesh.quarantined),
            "partitions": delta["replica.partitions"],
            "heals": delta["replica.heals"],
            "failovers": delta["replica.failovers"],
            "migrations": delta["replica.migrations"],
            "commit_mismatches": delta["replica.commit_mismatches"],
            "divergences_caught": delta["replica.divergences"],
            "recoveries": delta["replica.recoveries"],
            "anti_entropy_bytes": delta["replica.anti_entropy_bytes"],
            "failover_sessions_dropped_metric": delta[
                "net.sessions_dropped.failover"
            ],
            "rtt_floor_ms": round(floor_s * 1e3, 3),
            **slo_report(e2e_w, floor_s, "apply_e2e_"),
            **{k: v for k, v in sorted(self._counts.items())},
        }
        if canary_report is not None:
            out["canary"] = canary_report
        if self.autopilot is not None:
            out["autopilot"] = self.autopilot.report()
        compile_rep = phases.compile_report(since=compile_marker)
        compile_rep["budget"] = self.retrace_budget
        compile_rep["within_budget"] = (
            self.retrace_budget is None
            or compile_rep["retraces"] <= self.retrace_budget
        )
        out["compile"] = compile_rep
        out["profile"] = profile_window.report(wall_s=wall_s)
        return out


def run_soak_tcp(
    server,
    scenario: Scenario,
    arm=None,
    budget_s: float = 30.0,
    idle_flush: float = 0.05,
    frame_deadline: float = 2.0,
    telemetry_port: Optional[int] = None,
    probe=None,
    probe_at_events: int = 0,
) -> Dict:
    """Transport-level soak: the same scenario over real localhost
    sockets (`sync.net.serve`), for chaos runs — ``arm`` is called after
    every session's handshake completes, so armed ``net.drop`` /
    ``net.delay`` / ``net.truncate`` specs hit steady-state traffic, not
    the hello.  Scores survivability, not parity (dropped frames are the
    point); the server must outlive every injected transport fault.

    ``telemetry_port`` starts a live `TelemetryServer` for the run (the
    returned counts carry the bound port); ``probe`` is called ONCE when
    ``probe_at_events`` events have shipped — the telemetry rehearsal
    scrapes `/metrics` mid-soak there, with real `net.*` traffic on the
    wire by construction."""
    import asyncio

    from ytpu.sync.net import FrameTimeout, read_frame, serve, write_frame

    telemetry = None
    if telemetry_port is not None:
        from ytpu.utils.telemetry import TelemetryServer

        telemetry = TelemetryServer(port=telemetry_port)
        telemetry.start()

    async def main():
        srv, port = await serve(
            server, idle_flush=idle_flush, frame_deadline=frame_deadline
        )
        conns: Dict[int, tuple] = {}
        counts = {"sent": 0, "reconnects": 0, "conn_errors": 0}

        async def open_sess(sid: int, tenant: str) -> None:
            reader, writer = await asyncio.open_connection("127.0.0.1", port)
            # the hello must not ride the fault sites: a swallowed hello
            # deadlocks the handshake, which is not the scenario under
            # test (faults arm AFTER connect, mirroring chaos_smoke)
            with faults.suspended():
                write_frame(writer, tenant.encode("utf-8"))
                await writer.drain()
                for _ in range(2):  # greeting: step1 + awareness
                    f = await read_frame(
                        reader, first_byte_timeout=0.25, frame_timeout=2.0
                    )
                    if f is None:
                        break
            conns[sid] = (reader, writer)

        for script in scenario.sessions:
            await open_sess(script.sid, script.tenant)
        if arm is not None:
            arm()
        t0 = time.perf_counter()
        for ev in scenario.events():
            if time.perf_counter() - t0 > budget_s:
                break
            pair = conns.get(ev.session)
            if pair is None or pair[1].is_closing():
                await open_sess(ev.session, ev.tenant)
                counts["reconnects"] += 1
                pair = conns[ev.session]
            reader, writer = pair
            try:
                if ev.kind == "reconnect":
                    writer.close()
                    await open_sess(ev.session, ev.tenant)
                    counts["reconnects"] += 1
                    continue
                if ev.kind == "apply":
                    msg = Message.sync(SyncMessage.update(ev.payload))
                elif ev.kind == "diff":
                    msg = Message.sync(
                        SyncMessage.step1(StateVector.decode_v1(ev.payload))
                    )
                else:
                    msg = Message.awareness(
                        AwarenessUpdate.decode_v1(ev.payload)
                    )
                write_frame(writer, msg.encode_v1())
                await writer.drain()
                counts["sent"] += 1
                if probe is not None and counts["sent"] == max(
                    1, probe_at_events
                ):
                    # mid-soak scrape: the telemetry thread answers while
                    # this loop blocks — exactly the liveness the plane
                    # exists to provide. The probe gets the bound port
                    # (None when the caller brought their own endpoint).
                    probe(
                        telemetry.port if telemetry is not None else None
                    )
                # opportunistic pump keeps both sockets' buffers drained
                try:
                    await read_frame(
                        reader, first_byte_timeout=0.005, frame_timeout=0.5
                    )
                except FrameTimeout:
                    writer.close()
                    conns.pop(ev.session, None)
            except (ConnectionError, OSError):
                counts["conn_errors"] += 1
                conns.pop(ev.session, None)
        for _reader, writer in conns.values():
            writer.close()
        srv.close()
        await srv.wait_closed()
        return counts

    try:
        counts = asyncio.run(main())
    finally:
        if telemetry is not None:
            counts_port = telemetry.port
            telemetry.stop()
    flush = getattr(server, "flush_device", None)
    if flush is not None:
        with faults.suspended():
            flush()
    if telemetry is not None:
        counts["telemetry_port"] = counts_port
    counts["survived"] = True
    return counts
