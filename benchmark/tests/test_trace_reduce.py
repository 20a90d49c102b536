"""The trace reduction: busy/idle union, per-program time and gap
attribution, on a hand-made trace, on the trace recorded on the chip
(`data/`), and the xplane loader on a trace recorded here on the CPU."""

import glob
import gzip
import json
import os

import pytest

from benchmark import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1e6  # ns


def _events():
    dev = {
        "XLA Ops": [
            ["fusion.1", 10 * MS, 10 * MS],  # 10..20
            ["fusion.2", 15 * MS, 10 * MS],  # 15..25 overlaps: union 10..25
            ["copy.3", 60 * MS, 5 * MS],  # 60..65
            ["late", 150 * MS, 5 * MS],  # outside the slice
        ],
        "XLA Modules": [
            ["jit_apply_update_batch(17)", 10 * MS, 15 * MS],
            ["jit_take(3)", 60 * MS, 5 * MS],
        ],
    }
    host = [
        ["bench.tick", 0.0, 100 * MS],
        ["bench.update", 1 * MS, 4 * MS],
        ["bench.dispatch", 5 * MS, 65 * MS],  # 5..70
    ]
    return {"device": {"/device:TPU:0": dev}, "host": host}


def test_union_busy_idle_and_programs():
    r = tr.reduce(_events())
    assert r["window_s"] == pytest.approx(0.100)
    assert r["busy_s"] == pytest.approx(0.020)  # 10..25 and 60..65
    assert r["program_s"]["jit_apply_update_batch"] == pytest.approx(0.015)
    assert r["program_s"]["jit_take"] == pytest.approx(0.005)
    assert r["span_counts"]["bench.dispatch"] == 1
    assert dict(map(tuple, r["device_ops"]))["fusion.1"] == pytest.approx(0.010)


def test_gaps_go_to_the_innermost_open_span():
    r = tr.reduce(_events())
    idle = dict(map(tuple, r["idle_gaps"]))
    # gaps: 0..10 (middle 5: tick and dispatch start at 5 -> dispatch),
    # 25..60 (dispatch), 65..100 (middle 82.5: only the tick)
    assert idle["bench.dispatch"] == pytest.approx(0.010 + 0.035)
    assert idle["bench.tick"] == pytest.approx(0.035)
    assert sum(idle.values()) == pytest.approx(r["window_s"] - r["busy_s"])


def test_no_slice_no_metrics():
    ev = _events()
    ev["host"] = []
    assert tr.reduce(ev) == {}


def test_program_name_strips_the_run_id():
    assert tr.program_name("jit_decode_updates_v1(4521)") == "jit_decode_updates_v1"
    assert tr.program_name("jit__finish_counts") == "jit__finish_counts"


def test_recorded_chip_trace():
    """A slice recorded on one v5e (my chip run, PR 25), reduced here: the
    numbers the run itself printed, kept beside it."""
    paths = glob.glob(os.path.join(HERE, "data", "*.events.json.gz"))
    if not paths:
        pytest.skip("no recorded trace in benchmark/tests/data")
    for path in paths:
        with gzip.open(path, "rt") as f:
            events = json.load(f)
        with open(path.replace(".events.json.gz", ".expected.json")) as f:
            want = json.load(f)
        got = tr.reduce(events)
        assert got["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
        assert got["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
        for name, secs in want["program_s"].items():
            assert got["program_s"][name] == pytest.approx(secs, rel=1e-9)
        assert 0.0 < got["busy_s"] <= got["window_s"]
        idle = sum(v for _, v in got["idle_gaps"])
        assert idle <= got["window_s"] - got["busy_s"] + 1e-9


def test_loader_reads_bench_spans_from_an_xplane(tmp_path):
    import jax
    import jax.numpy as jnp

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.tick"):
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            jnp.ones((64, 64)).sum().block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))[0]
    events = tr.load_xplane(path)
    names = [n for n, _, _ in events["host"]]
    assert "bench.tick" in names and "bench.dispatch" in names
    assert events["device"] == {}  # no TPU here: nothing is reported as device time
    assert tr.reduce(events) == {}
