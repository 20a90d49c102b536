"""The span seam (ISSUE-26): one host span, whoever makes it.

`tracer.span(name)` and `phases.span(stage)` build the same object; its one
enter/exit feeds the tracer's ring and the phase recorder's stage sums,
whichever is on, and opens `jax.profiler.TraceAnnotation("ytpu." + name)`.
A served step on the CPU yields every stage of the issue's table, nested by
containment; the integrate and decode programs carry their named scopes.
Shapes are the cheap (n_docs=2, capacity=256) family tier-1 already builds.
"""

import glob
import os

import pytest

from ytpu.utils import metrics
from ytpu.utils.phases import NULL_SPAN, PhaseRecorder, phases, program_memory
from ytpu.utils.trace import Tracer, tracer

# parent stage -> the stages nested in it on the served step
NESTING = {
    "sync.receive": ("sync.receive.parse", "sync.receive.roots", "sync.receive.fanout"),
    "sync.dispatch": ("sync.dispatch.peek", "ingest.apply", "sync.dispatch.pop"),
    "ingest.apply": (
        "ingest.plan", "ingest.merge", "ingest.rank_table", "integrate.xla_batch", "ingest.flags",
    ),
    "ingest.plan": ("ingest.plan.prescan", "ingest.plan.host_rows", "ingest.plan.h2d"),
    "ingest.merge": (
        "ingest.merge.pack", "ingest.merge.h2d", "ingest.merge.gather", "ingest.merge.retain",
        "ingest.merge.tables", "decode.v1", "ingest.merge.scatter",
    ),
}
COUNTERS = ("sync.dispatch_updates", "sync.queue_wait")
# the span call sites this PR adds: all keyless, all through `phases.span`
# except `sync.dispatch`, which the tracer had
NEW_CALL_SITES = sorted(
    {s for kids in NESTING.values() for s in kids} - {"integrate.xla_batch", "decode.v1"}
    | {"sync.receive", "sync.dispatch"}
)


def _typed_updates(n=3):
    from ytpu.core import Doc

    doc = Doc(client_id=3)
    log = []
    doc.observe_update_v1(lambda p, o, t: log.append(p))
    text = doc.get_text("text")
    for word in ["hi ", "there ", "friend", "s"][:n]:
        with doc.transact() as txn:
            text.insert(txn, len(text.get_string()), word)
    return log, text.get_string()


def _serve(log):
    """One room, two sessions, every update its own dispatch."""
    from ytpu.sync.device_server import DeviceSyncServer
    from ytpu.sync.protocol import Message, SyncMessage

    server = DeviceSyncServer(n_docs=2, capacity=256, device_authoritative=True)
    a, _ = server.connect_frames("room")
    server.connect_frames("room")
    for p in log:
        server.receive_frames(a, Message.sync(SyncMessage.update(p)).encode_v1())
        assert server.flush_device() == 1
    return server


@pytest.fixture(scope="module")
def served(native_lib):
    """The phases snapshot of three served steps (after one unrecorded
    step, so that no first-sighting compile time is in the sums)."""
    pytest.importorskip("jax")
    log, want = _typed_updates()
    _serve(log[:1])
    phases.reset()
    phases.enable()
    try:
        server = _serve(log)
    finally:
        phases.disable()
    snap = phases.snapshot()
    phases.reset()
    assert server.device_text("room") == want
    return snap, len(log)


def test_served_step_yields_every_stage(served):
    snap, steps = served
    want = set(NESTING) | {s for kids in NESTING.values() for s in kids} | set(COUNTERS)
    assert want <= set(snap), sorted(want - set(snap))
    for stage in want - {"sync.dispatch_updates"}:
        assert snap[stage]["calls"] == steps, (stage, snap[stage])
    assert snap["sync.dispatch_updates"]["value"] == steps  # one update a step
    assert snap["sync.queue_wait"]["execute_s"] > 0.0
    assert "ingest.recover" not in snap  # the rare path stayed rare
    assert "ingest.merge.rebase" not in snap  # folded into `.scatter`'s one program


@pytest.mark.parametrize("parent", sorted(NESTING))
def test_children_fit_in_their_parent(served, parent):
    snap, _ = served
    whole = lambda st: st["compile_s"] + st["execute_s"]
    inside = sum(whole(snap[c]) for c in NESTING[parent])
    assert inside <= whole(snap[parent]) + 1e-6, (parent, inside, snap[parent])
    # self_s is the parent's time in none of them
    assert snap[parent]["self_s"] == pytest.approx(whole(snap[parent]) - inside, abs=1e-4)


def test_wire_bytes_are_counted_once(served):
    snap, _ = served
    counted = {stage: st["h2d_bytes"] for stage, st in snap.items() if st["h2d_bytes"]}
    # the wire bytes in `ingest.merge.h2d` and nowhere else (`decode.v1` is
    # handed device arrays); the host lane's planes are an upload of their
    # own stage, and the lookup tables of theirs at the step that built them
    assert sorted(counted) == [
        "ingest.merge.h2d", "ingest.merge.tables", "ingest.plan.h2d", "ingest.rank_table",
    ], counted
    assert "ingest.fast_lane" not in snap


def test_a_reuse_step_counts_no_bytes_under_plan_h2d(served):
    """The host lane's batch goes up in the step that builds it, as the two
    arrays of a `PackedBatch` (the programs take the planes apart): the two
    steps after it are handed those arrays, span `ingest.plan.h2d` around
    nothing and count no byte there."""
    snap, steps = served
    assert (snap["ingest.batch_builds"]["value"], snap["ingest.batch_reuses"]["value"]) == (1, steps - 1)
    # one build of 2 rooms x the (4, 4) bucket: [2, 4, 23] and [2, 4, 4], both i32
    assert snap["ingest.plan.h2d"]["h2d_bytes"] == 2 * 4 * (4 * 23 + 4 * 4)
    assert snap["ingest.plan.h2d"]["calls"] == snap["ingest.plan.host_rows"]["calls"] == steps


def test_disabled_path_hands_every_call_site_the_null_span(native_lib, monkeypatch):
    pytest.importorskip("jax")
    handed = {}

    def watch(obj):
        real = obj.span

        def span(name, *a, **kw):
            got = real(name, *a, **kw)
            handed.setdefault(name, []).append(got)
            return got

        monkeypatch.setattr(obj, "span", span)

    watch(phases)
    watch(tracer)
    assert not phases.enabled and not tracer.enabled
    _serve(_typed_updates(1)[0])
    assert sorted(handed) == NEW_CALL_SITES
    assert all(got is NULL_SPAN for spans in handed.values() for got in spans)


def test_tracer_span_is_summed_into_the_stage_of_its_name():
    rec, ring = PhaseRecorder(enabled=True), Tracer()
    ring._peer, rec._peer = rec, ring
    with ring.span("sync.dispatch", step=0):  # ring off, recorder on: live
        with rec.span("ingest.apply"):
            pass
    snap = rec.snapshot()
    assert snap["sync.dispatch"]["calls"] == 1 and snap["ingest.apply"]["calls"] == 1
    assert snap["sync.dispatch"]["execute_s"] >= snap["ingest.apply"]["execute_s"]
    assert len(ring) == 0
    ring.enable()
    rec.disable()
    with rec.span("ingest.plan"):  # recorder off, ring on: live, into the ring
        pass
    assert [e["name"] for e in ring._events] == ["ingest.plan"]
    assert rec.snapshot()["sync.dispatch"]["calls"] == 1  # and not into the sums
    ring.disable()
    assert rec.span("x") is NULL_SPAN and ring.span("x") is NULL_SPAN


def test_private_recorders_are_not_linked():
    rec, ring = PhaseRecorder(enabled=True), Tracer(enabled=True)
    with rec.span("only.phases"):
        pass
    with ring.span("only.ring"):
        pass
    assert list(rec.snapshot()) == ["only.phases"]
    assert [e["name"] for e in ring._events] == ["only.ring"]
    assert tracer._peer is phases and phases._peer is tracer


def test_spans_land_in_the_profiler_trace_under_the_ytpu_prefix(tmp_path):
    jax = pytest.importorskip("jax")
    from jax.profiler import ProfileData

    rec, ring = PhaseRecorder(enabled=True), Tracer()
    ring._peer = rec
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with ring.span("seam.outer"):
            with rec.span("seam.inner"):
                jax.block_until_ready(jax.numpy.zeros(8) + 1)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True)
    found = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("ytpu.seam."):
                        found[e.name] = (e.start_ns, e.start_ns + e.duration_ns)
    assert set(found) == {"ytpu.seam.outer", "ytpu.seam.inner"}
    (o0, o1), (i0, i1) = found["ytpu.seam.outer"], found["ytpu.seam.inner"]
    assert o0 <= i0 <= i1 <= o1
    assert "seam.outer" in rec.snapshot()  # the bare name everywhere else


def test_self_time_leaves_nested_spans_out():
    import time

    rec = PhaseRecorder(enabled=True)
    with rec.span("outer"):
        time.sleep(0.01)
        with rec.span("inner"):
            time.sleep(0.02)
    snap = rec.snapshot()
    assert snap["inner"]["self_s"] == pytest.approx(snap["inner"]["execute_s"])
    assert snap["outer"]["self_s"] == pytest.approx(
        snap["outer"]["execute_s"] - snap["inner"]["execute_s"], abs=1e-5
    )
    assert 0.005 < snap["outer"]["self_s"] < snap["outer"]["execute_s"] - 0.015


def test_program_memory_reads_nothing_until_the_first_sighting():
    reads = []

    class Arg:
        dtype = "float32"

        @property
        def shape(self):
            reads.append(1)
            return (4,)

    class Fn:
        lowered = 0

        def lower(self, *specs):
            Fn.lowered += 1
            raise RuntimeError("no backend here")  # "no capture", by contract

    rec = PhaseRecorder(enabled=True)
    for _ in range(3):
        with rec.span("prog", key=((4,),), memory=program_memory(Fn(), Arg())):
            pass
    assert Fn.lowered == 1 and len(reads) >= 1  # the first sighting alone
    n = len(reads)
    program_memory(Fn(), Arg())  # building one costs no spec tree
    assert len(reads) == n


SCOPES = ("conflict_scan/cheap", "conflict_scan/wide", "integrate_rows", "delete_pass",
          "move_recompute", "split")


@pytest.fixture(scope="module")
def integrate_hlo():
    pytest.importorskip("jax")
    from ytpu.models.batch_doc import _apply_update_batch_jit, scan_tier_plan
    from ytpu.models.ingest import BatchIngestor

    ing = BatchIngestor(2, 256)
    batch = ing.enc.batch_from_rows([[], []], [[], []])
    lowered = _apply_update_batch_jit.lower(
        ing.state, batch, ing.enc.interner.rank_table(), scan_tier_plan()
    )
    return lowered.as_text(debug_info=True)


@pytest.mark.parametrize("scope", SCOPES)
def test_integrate_program_carries_its_named_scopes(integrate_hlo, scope):
    assert scope in integrate_hlo


def test_decode_program_carries_its_named_scope():
    pytest.importorskip("jax")
    import jax.numpy as jnp

    from ytpu.ops.decode_kernel import _decode_updates_v1_jit

    lowered = _decode_updates_v1_jit.lower(
        jnp.zeros((2, 64), jnp.uint8), jnp.zeros((2,), jnp.int32), max_rows=4, max_dels=4
    )
    assert "decode_v1" in lowered.as_text(debug_info=True)


def test_net_drop_series_survives_a_registry_reset():
    """The `test_telemetry` order-dependence (ROADMAP Design 13): a family
    cached at import is orphaned by `metrics.reset()`, and the exposition
    then misses the drop an operator looks for. The drop path looks its
    families up per drop."""
    from ytpu.sync import net

    fam = lambda: metrics.counter("net.sessions_dropped", labelnames=("reason",))
    saved = dict(metrics._families)
    try:
        metrics.reset()
        net._session_dropped("bad_frame")
        assert fam().labels("bad_frame").value == 1
        assert metrics.counter("net.bad_frames").value == 1
        assert 'net_sessions_dropped_total{reason="bad_frame"} 1' in metrics.prometheus_text()
    finally:
        metrics._families.clear()
        metrics._families.update(saved)
