"""Build parts (ISSUE-41): what jax times of every program built, by span.

`ytpu/utils/compile_cache.py::listen_to_builds` registers the process's one
pair of `jax.monitoring` listeners; they keep the process's totals and one
row a program always, each row with the innermost open span and its
recorder, and a recorder's `snapshot()` sums its stages' parts from those
rows (`build.unspanned` where no span was open while `phases` was on).
Tiny jitted functions on the CPU; every test builds
functions of its own, because a program this process built before is in
jax's in-memory caches and reports nothing.
"""

import gc
import inspect
import os
import subprocess
import sys

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax._src import monitoring as jax_monitoring  # noqa: E402

from ytpu.utils.compile_cache import build_log, build_totals, listen_to_builds, monitoring_names  # noqa: E402
from ytpu.utils.phases import BUILD_PARTS, PhaseRecorder, compile_storm_provider, phases  # noqa: E402


def _fresh(tag: str):
    """A jitted function nothing has built yet, named `tag`, that calls a
    jitted function of its own (a nested trace) and a `jnp` one."""

    def inner(x):
        return jnp.cumsum(x) * 2

    inner.__name__ = tag + "_inner"
    jitted_inner = jax.jit(inner)

    def outer(x):
        return jnp.where(x > 0, jitted_inner(x), x).sum()

    outer.__name__ = tag
    return jax.jit(outer)


def _row(n: int):
    """A host array: handing it to a jitted function builds no program of
    its own (`jnp.arange` would, eagerly, under whatever span is open)."""
    return np.arange(n, dtype=np.float32)


@pytest.fixture
def recorder():
    """The process-wide recorder, on and empty; off and empty afterwards."""
    listen_to_builds()
    phases.reset()
    phases.enable()
    try:
        yield phases
    finally:
        phases.disable()
        phases.reset()


def _delta(before: dict) -> dict:
    return {k: v - before[k] for k, v in build_totals().items()}


@pytest.mark.parametrize("name", sorted(monitoring_names()))
def test_the_event_names_exist_in_the_installed_jax(name):
    """The three compile events are jax's own constants (an AttributeError in
    `monitoring_names` if one goes); the cache's are literals of
    `jax._src.compiler`: a jax that renames one must fail here, loudly, and
    not read 0 on the chip."""
    from jax._src import compiler, dispatch, pjit
    from jax._src.interpreters import pxla

    value = monitoring_names()[name]
    emitted_by = {
        "trace": (pjit, "dispatch.JAXPR_TRACE_EVENT"),
        "lower": (pxla, "dispatch.JAXPR_TO_MLIR_MODULE_EVENT"),
        "backend": (pxla, "dispatch.BACKEND_COMPILE_EVENT"),
    }
    if name in emitted_by:
        module, constant = emitted_by[name]
        assert value == getattr(dispatch, constant.split(".")[1])
        assert constant in inspect.getsource(module)
    else:
        assert value in inspect.getsource(compiler.compile_or_get_cached)


def test_each_stage_holds_its_own_parts_and_the_journal_names_the_program(recorder):
    f = _fresh("bp_stage_fn")
    x8, x9 = _row(8), _row(9)
    before = build_totals()
    with recorder.span("bp.a", key=(8,)):
        f(x8)
    snap_a = recorder.snapshot()["bp.a"]
    with recorder.span("bp.a", key=(8,)):
        f(x8)  # the program is there: nothing to build
    with recorder.span("bp.b", key=(9,)):
        f(x9)
    snap = recorder.snapshot()
    assert snap["bp.a"]["calls"] == 2 and snap["bp.a"]["compile_calls"] == 1
    for k in BUILD_PARTS:
        assert snap["bp.a"][k] == snap_a[k], k  # the cached call added nothing
    for stage in ("bp.a", "bp.b"):
        st = snap[stage]
        assert st["builds"] == 1 and st["cache_hits"] == 0
        assert st["trace_s"] > 0 and st["lower_s"] > 0 and st["backend_s"] > 0
    assert "build.unspanned" not in snap
    # the process totals moved by the two stages' builds: counts, and that
    # every part was heard; no seconds of one accumulator are held to
    # another's (under six workers the stage's sum has read 0.23 s where
    # the process total's delta read 0.009: builder's runs, PR 48)
    moved = _delta(before)
    assert moved["builds"] == 2
    for k in ("trace_s", "lower_s", "backend_s"):
        assert moved[k] > 0, k
    ev_a, ev_b = recorder.compile_events()
    assert ev_a["program"] == "bp.a" and ev_a["fun_names"] == ["jit(bp_stage_fn)"]
    assert ev_a["parts"]["builds"] == 1 and ev_a["parts"]["trace_s"] > 0
    assert ev_b["fun_names"] == ["jit(bp_stage_fn)"] and not ev_b["retrace"]
    report = recorder.compile_report()
    assert report["parts"]["builds"] == 2
    assert report["parts"]["lower_s"] > 0 and ev_b["parts"]["lower_s"] > 0
    rows = [r for r in build_log() if r["fun_name"] == "jit(bp_stage_fn)"]
    assert [r["stage"] for r in rows] == ["bp.a", "bp.b"]
    assert rows[0]["spans"] == ["bp.a"] and rows[0]["signature"] == "(8,)" and rows[0]["t"] <= rows[1]["t"]


def test_a_retrace_says_what_it_cost(recorder):
    f = _fresh("bp_retrace_fn")
    x4, x5 = _row(4), _row(5)
    marker = recorder.compile_marker()
    with recorder.span("bp.retrace", key=(4,), axes=("rows",)):
        f(x4)
    with recorder.span("bp.retrace", key=(5,), axes=("rows",)):
        f(x5)
    section = compile_storm_provider(budget=0, marker=marker)()
    assert section["retraces"] == 1 and section["storm"]
    last = section["last_retrace"]
    assert last["delta"] == [{"axis": "rows", "prev": "4", "new": "5"}]
    assert last["fun_names"] == ["jit(bp_retrace_fn)"]
    assert last["parts"]["builds"] == 1 and last["parts"]["trace_s"] > 0
    assert section["parts"]["builds"] == 2 and section["parts"]["backend_s"] > last["parts"]["backend_s"]


def test_a_nested_span_keeps_its_own_builds(recorder):
    f, g = _fresh("bp_outer_fn"), _fresh("bp_leaf_fn")
    x = _row(6)
    with recorder.span("bp.container"):
        f(x)
        with recorder.span("bp.container.leaf"):
            g(x)
    snap = recorder.snapshot()
    assert snap["bp.container"]["builds"] == 1 and snap["bp.container.leaf"]["builds"] == 1
    (row,) = [r for r in build_log() if r["fun_name"] == "jit(bp_leaf_fn)"]
    assert row["spans"] == ["bp.container", "bp.container.leaf"] and row["signature"] is None


def test_a_build_with_no_span_open_lands_in_build_unspanned(recorder):
    x = _row(7)
    before = build_totals()
    _fresh("bp_unspanned_fn")(x)
    jnp.asarray(x).astype(jnp.int32)  # a small eager program, put down by name
    st = recorder.snapshot()["build.unspanned"]
    moved = _delta(before)
    assert st["builds"] == st["calls"] == moved["builds"] >= 2
    assert st["trace_s"] == pytest.approx(moved["trace_s"], abs=1e-5) and st["compile_s"] == 0.0
    names = [r["fun_name"] for r in build_log()[-moved["builds"]:]]
    assert "jit(bp_unspanned_fn)" in names and "jit(convert_element_type)" in names
    assert all(r["stage"] is None for r in build_log()[-moved["builds"]:])


def test_with_the_recorder_off_the_totals_still_count_and_no_stage_is_made():
    listen_to_builds()
    phases.reset()
    assert not phases.enabled
    x = _row(5)
    before = build_totals()
    with phases.span("bp.off", key=(5,)):  # the shared no-op span
        _fresh("bp_off_fn")(x)
    moved = _delta(before)
    assert moved["builds"] == 1 and moved["trace_s"] > 0 and moved["lower_s"] > 0 and moved["backend_s"] > 0
    assert phases.snapshot() == {} and phases.compile_events() == []
    assert build_log()[-1]["fun_name"] == "jit(bp_off_fn)" and build_log()[-1]["stage"] is None


def test_a_private_recorders_span_takes_the_parts_and_the_process_pair_none(recorder):
    mine = PhaseRecorder(enabled=True)
    x = _row(3)
    with mine.span("bp.private", key=(3,)):
        _fresh("bp_private_fn")(x)
    assert mine.snapshot()["bp.private"]["builds"] == 1
    assert mine.compile_events()[0]["fun_names"] == ["jit(bp_private_fn)"]
    assert recorder.snapshot() == {}


def test_the_totals_agree_with_a_compile_watch_beside_them():
    """`benchmark/run.py::CompileWatch` counts `backend_compile_duration`
    events and `cache_hits`; the program's totals must read the same."""
    listen_to_builds()
    watch = {"builds": 0, "build_s": 0.0, "cache_hits": 0, "raw_trace_s": 0.0}

    def on_duration(name, secs, **_):
        if name.endswith("/backend_compile_duration"):
            watch["builds"] += 1
            watch["build_s"] += secs
        elif name.endswith("/jaxpr_trace_duration"):
            watch["raw_trace_s"] += secs

    def on_event(name, **_):
        if name.endswith("/cache_hits"):
            watch["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    try:
        before = build_totals()
        x = _row(11)
        for tag in ("bp_watch_a", "bp_watch_b", "bp_watch_c"):
            _fresh(tag)(x)
        moved = _delta(before)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
        jax.monitoring.unregister_event_listener(on_event)
    assert moved["builds"] == watch["builds"] == 3
    assert moved["cache_hits"] == watch["cache_hits"]
    assert moved["backend_s"] == pytest.approx(watch["build_s"], abs=1e-9)
    # a nested trace is inside its outermost, which alone is summed: the raw
    # events (`where`, `cumsum`, the inner jit, then the outer) count it twice
    assert 0 < moved["trace_s"] < watch["raw_trace_s"]


def test_a_second_enable_registers_no_second_listener():
    listen_to_builds()
    listeners = len(jax_monitoring.get_event_duration_listeners()), len(jax_monitoring.get_event_listeners())
    listen_to_builds()
    phases.enable()
    try:
        phases.enable()
        assert (len(jax_monitoring.get_event_duration_listeners()), len(jax_monitoring.get_event_listeners())) == listeners
        before = build_totals()
        _fresh("bp_twice_fn")(_row(13))
        assert _delta(before)["builds"] == 1  # not 2: one listener heard it
    finally:
        phases.disable()
        phases.reset()


def test_native_startup_has_its_three_keys():
    from ytpu import native

    assert set(native.startup) == {"built", "build_s", "load_s"}
    if native.available():
        assert native.startup["load_s"] > 0.0
        assert native.startup["build_s"] >= 0.0 and (native.startup["built"] or native.startup["build_s"] == 0.0)


def test_importing_phases_imports_no_jax_and_enable_listens():
    code = (
        "import sys\n"
        "from ytpu.utils.phases import phases\n"
        "assert 'jax' not in sys.modules, 'importing phases imported jax'\n"
        "from ytpu.utils.compile_cache import build_totals\n"
        "assert build_totals() == {} and 'jax' not in sys.modules\n"
        "phases.enable()\n"
        "assert build_totals()['builds'] == 0\n"
    )
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    res = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, timeout=120, cwd=root)
    assert res.returncode == 0, res.stderr[-800:]


def test_the_queue_depth_gauge_is_worked_out_when_read():
    """`flush_device` no longer walks every slot twice a call to keep
    `sync.device_queue_depth` current: the gauge asks the live servers, all
    of them, when it is read."""
    from ytpu.sync.device_server import DeviceSyncServer
    from ytpu.utils import metrics

    gc.collect()  # servers other tests of this process left to the collector
    gauge = metrics.gauge("sync.device_queue_depth")
    base = gauge.value
    server = DeviceSyncServer(n_docs=2, capacity=64, device_authoritative=True)
    newer = DeviceSyncServer(n_docs=2, capacity=64, device_authoritative=True)
    server._enqueue(0, b"\x00\x00")
    server._enqueue(0, b"\x00\x00")
    server._enqueue(1, b"\x00\x00")
    newer._enqueue(1, b"\x00\x00")
    assert server.pending_device_updates() == 3 and gauge.value == base + 4
    assert f"sync_device_queue_depth {base + 4:g}" in metrics.prometheus_text()
    del newer
    gc.collect()
    assert gauge.value == base + 3  # the older server still counts once the newest is gone
    del server
    gc.collect()
    assert gauge.value == base  # the registry kept no server alive


def test_the_memory_capture_builds_no_second_program_on_a_sharded_state(native_lib):
    """What the build log showed on four chips (PR 41): `program_memory`'s
    specs dropped the arrays' shardings, so on a doc-sharded state the
    capture lowered another program than the call had built, and every first
    sighting of the decode and integrate programs was traced, lowered and
    compiled twice, the second time under the span around it."""
    from ytpu.core import Doc
    from ytpu.sync.device_server import DeviceSyncServer
    from ytpu.sync.protocol import Message, SyncMessage

    doc = Doc(client_id=3)
    log = []
    doc.observe_update_v1(lambda p, o, t: log.append(p))
    with doc.transact() as txn:
        doc.get_text("text").insert(txn, 0, "hi")
    listen_to_builds()
    phases.reset()
    phases.enable()
    try:
        rows_before = len(build_log())
        server = DeviceSyncServer(n_docs=8, capacity=256, device_authoritative=True, shard_docs=True)
        session, _ = server.connect_frames("room")
        server.receive_frames(session, Message.sync(SyncMessage.update(log[0])).encode_v1())
        assert server.flush_device() == 1
        built = [(r["fun_name"], r["stage"]) for r in build_log()[rows_before:]]
        events = {e["program"]: e for e in phases.compile_events()}
    finally:
        phases.disable()
        phases.reset()
    assert server.device_text("room") == "hi"
    for program, fun_name in (("integrate.xla_batch", "jit(apply_update_batch)"), ("decode.v1", "jit(decode_updates_v1)")):
        assert [stage for name, stage in built if name == fun_name] == [program], built
        assert events[program]["fun_names"] == [fun_name]
        assert events[program]["memory"]["resident_bytes"] > 0  # and the capture still reads the program
