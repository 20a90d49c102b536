"""benches/bench_compare.py (ISSUE-11 satellite): field-by-field bench
capture diffing with per-metric tolerance and directional regression
semantics — the tool that turns "no worse than" from eyeball work into
an exit code. The tool itself is gated here: synthetic captures pin the
direction/tolerance rules, and fixture captures pin self-comparison as
a zero diff through the CLI entry."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benches"))

import bench_compare as bc  # noqa: E402


def test_self_compare_is_zero_diff_and_rc0(tmp_path, capsys):
    cap = {
        "value": 1000.0,
        "soak": {"updates_per_s": 50.0, "apply_p99_ms": 3.0},
        "owed_captures": ["a", "b"],
    }
    p = tmp_path / "cap.json"
    p.write_text(json.dumps(cap))
    rc = bc.main([str(p), str(p)])
    assert rc == 0
    diff = bc.compare(cap, cap)
    assert diff["regressions"] == diff["improvements"] == diff["changes"] == []
    assert diff["added"] == diff["removed"] == []


def test_directional_regressions_and_tolerance():
    a = {
        "value": 1000.0,
        "overlap_speedup": 2.0,
        "soak": {"apply_p99_ms": 4.0},
        "chunks": 19,
    }
    # throughput -24% = regression; p99 +50% = regression; chunks drift
    # is neutral (reported, never failing)
    b = {
        "value": 760.0,
        "overlap_speedup": 2.0,
        "soak": {"apply_p99_ms": 6.0},
        "chunks": 24,
    }
    diff = bc.compare(a, b)
    keys = {e["key"] for e in diff["regressions"]}
    assert keys == {"value", "soak.apply_p99_ms"}
    assert {e["key"] for e in diff["changes"]} == {"chunks"}
    # a wide-enough per-key tolerance absorbs the latency regression
    diff = bc.compare(a, b, tolerances={"apply_p99_ms": 0.6})
    assert {e["key"] for e in diff["regressions"]} == {"value"}
    # within the default 10% band nothing fires at all
    diff = bc.compare({"value": 100.0}, {"value": 95.0})
    assert not diff["regressions"]


def test_scan_width_and_trip_rows_regress_like_latency():
    """ISSUE-12 satellite: a conflict-scan width-p99 rise or a dispatch-
    trip-count rise is a REGRESSION (like latency); a trip-reduction
    drop regresses like a speedup; tier occupancy only drifts neutral."""
    a = {
        "scan_width_p99": 120,
        "scan_trips_serial": 67000,
        "scan_trips_two_tier": 16000,
        "scan_trip_reduction": 4.2,
        "scan_tier_wide": 400,
    }
    b = {
        "scan_width_p99": 340,  # tail widened: regression
        "scan_trips_serial": 67000,
        "scan_trips_two_tier": 67000,  # compression lost: regression
        "scan_trip_reduction": 1.0,  # factor collapsed: regression
        "scan_tier_wide": 500,  # occupancy shift: neutral drift only
    }
    diff = bc.compare(a, b)
    keys = {e["key"] for e in diff["regressions"]}
    assert keys == {
        "scan_width_p99",
        "scan_trips_two_tier",
        "scan_trip_reduction",
    }, diff
    assert {e["key"] for e in diff["changes"]} == {"scan_tier_wide"}
    # and the inverse direction reports as improvements, never failures
    diff = bc.compare(b, a)
    assert not diff["regressions"], diff


def test_improvements_and_added_removed_fields():
    a = {"value": 100.0, "gone": 1}
    b = {"value": 200.0, "new_key": {"x": 1}}
    diff = bc.compare(a, b)
    assert [e["key"] for e in diff["improvements"]] == ["value"]
    assert diff["added"] == ["new_key.x"]
    assert diff["removed"] == ["gone"]


def test_direction_classification_rules():
    assert bc.classify("value") == "up"
    assert bc.classify("soak.updates_per_s") == "up"
    assert bc.classify("diff_pipeline_speedup") == "up"
    assert bc.classify("soak.apply_p999_ms") == "down"
    assert bc.classify("apply_max_ms") == "down"
    assert bc.classify("scan_width_p99") == "down"
    assert bc.classify("scan_width_p50") == "down"
    assert bc.classify("scan_width_max") == "down"
    # two-tier scan (ISSUE-12): dispatch-trip counts regress when they
    # RISE (like latency), the compression factor when it DROPS (like a
    # speedup), and tier occupancy is reported-neutral workload shape
    assert bc.classify("scan_trips_serial") == "down"
    assert bc.classify("scan_trips_two_tier") == "down"
    assert bc.classify("scan_tiers.p99.scan_trips_two_tier") == "down"
    assert bc.classify("scan_trip_reduction") == "up"
    assert bc.classify("scan_tier_cheap") == "neutral"
    assert bc.classify("scan_tier_wide") == "neutral"
    # federation (ISSUE-13): convergence cost and anti-entropy traffic
    # regress when they RISE; the scripted chaos schedule stays neutral
    assert bc.classify("federation_converge_rounds") == "down"
    assert bc.classify("federation_anti_entropy_bytes") == "down"
    assert bc.classify("federation.converge_rounds") == "down"
    assert bc.classify("federation.anti_entropy_bytes") == "down"
    assert bc.classify("federation.partitions") == "neutral"
    assert bc.classify("federation.commit_mismatches") == "neutral"
    assert bc.classify("federation.updates_per_s") == "up"
    # autopilot (ISSUE-16): on-vs-off deltas score the controller —
    # availability regresses on DROP, the p99 delta on RISE; raw action
    # counts are policy shape, reported-neutral
    assert bc.classify("autopilot_availability_delta") == "up"
    assert bc.classify("autopilot_p99_adj_delta") == "down"
    assert bc.classify("autopilot.p99_adj_delta_ms") == "down"
    assert bc.classify("autopilot_actions") == "neutral"
    assert bc.classify("autopilot.actions_by_policy.maintenance") == "neutral"
    assert bc.classify("phases.replay.stage.execute_s") == "neutral"
    assert bc.classify("chunks") == "neutral"
    # performance observatory (ISSUE-17): retrace counts and cumulative
    # trace seconds regress when they RISE on the same warmed workload —
    # a shape/static-plan leak re-entered the jit boundary
    assert bc.classify("compile_retraces") == "down"
    assert bc.classify("metrics.compile.retraces") == "down"
    assert bc.classify("observatory.clean.retraces") == "down"
    assert bc.classify("metrics.compile.s_total") == "down"
    # ...while the wall-time attribution fractions are a COMPOSITION of
    # the budget, not better/worse — pinned neutral, including the one
    # whose leaf would otherwise substring-match stall_fraction
    assert bc.classify("profile_device_fraction") == "neutral"
    assert bc.classify("profile_stall_fraction") == "neutral"
    assert bc.classify("profile_idle_fraction") == "neutral"
    assert bc.classify("observatory.profile.profile_net_fraction") == "neutral"
    assert bc.classify("profile.fractions_sum") == "neutral"
    # plain stall_fraction (ISSUE-7 staging gauge) keeps its direction
    assert bc.classify("stall_fraction") == "down"
    assert bc.classify("ingest_raw.stall_fraction") == "down"
    # workload-shape counter whose leaf contains "s_total" stays neutral
    assert bc.classify("metrics.integrate.scan_iterations_total") == "neutral"
    # capacity observatory (ISSUE-18): device-memory footprints regress
    # when they RISE; the forecaster's headroom and the doc-axis ceiling
    # regress when they DROP (the ceiling closing in); the configured
    # budget is an input, not an outcome, and the occupancy/fragmentation
    # gauges are workload shape — both reported-neutral
    assert bc.classify("memory_peak_bytes") == "down"
    assert bc.classify("observatory.memory.peak_bytes") == "down"
    assert bc.classify("memory_program_bytes") == "down"
    assert bc.classify("capacity_headroom_fraction") == "up"
    assert bc.classify("capacity.headroom_fraction") == "up"
    assert bc.classify("doc_ceiling") == "up"
    assert bc.classify("doc_ceiling_sweep.doc_ceiling") == "up"
    assert bc.classify("doc_ceiling_sweep.memory_budget_bytes") == "neutral"
    assert bc.classify("capacity.live_rows") == "neutral"
    assert bc.classify("capacity.dead_rows") == "neutral"
    assert bc.classify("capacity.dead_fraction") == "neutral"
    assert bc.classify("capacity.occupancy_fraction") == "neutral"
    # doc-axis sub-batching (ISSUE-20): a narrowed width is the budget
    # closing in mid-replay — regresses on RISE; the width and the
    # scaling ratio are configuration/workload shape, pinned neutral
    # (doc_ceiling keeps its ISSUE-18 up direction on the sub-batch leg)
    assert bc.classify("capacity.subbatch_narrowed") == "down"
    assert bc.classify("metrics.capacity.subbatch_narrowed") == "down"
    assert bc.classify("doc_shard.subbatch_narrowed") == "down"
    assert bc.classify("subbatch_width") == "neutral"
    assert bc.classify("doc_shard.subbatch_width") == "neutral"
    assert bc.classify("phases.subbatch.width.value") == "neutral"
    assert bc.classify("sub_batch_scaling") == "neutral"
    assert bc.classify("doc_shard.sub_batch_scaling.sub_batch_scaling") == "neutral"
    assert bc.classify("doc_ceiling_pr20.doc_ceiling") == "up"


def test_subbatch_families_regress_on_rise():
    """ISSUE-20 satellite: a `capacity.subbatch_narrowed` rise on the
    same workload is a REGRESSION (the budget forced a narrower width);
    subbatch_width / sub_batch_scaling drift is reported-neutral."""
    a = {
        "metrics": {"capacity.subbatch_narrowed": 0},
        "subbatch_width": 512,
        "sub_batch_scaling": 0.9,
    }
    b = {
        "metrics": {"capacity.subbatch_narrowed": 3},  # budget closing in
        "subbatch_width": 128,  # configuration shift: neutral
        "sub_batch_scaling": 0.5,  # overhead floor drift: neutral
    }
    diff = bc.compare(a, b)
    keys = {e["key"] for e in diff["regressions"]}
    assert keys == {"metrics.capacity.subbatch_narrowed"}, diff
    assert {e["key"] for e in diff["changes"]} == {
        "subbatch_width",
        "sub_batch_scaling",
    }, diff


def test_observatory_families_regress_on_rise():
    """ISSUE-17 satellite: a retrace-count or trace-seconds rise is a
    REGRESSION; profile fraction drift is reported-neutral."""
    a = {
        "compile_retraces": 0,
        "metrics": {"compile.s_total": 2.0},
        "profile_device_fraction": 0.4,
        "profile_stall_fraction": 0.05,
    }
    b = {
        "compile_retraces": 3,  # warmed run started retracing: regression
        "metrics": {"compile.s_total": 9.0},  # tracing cost blew up
        "profile_device_fraction": 0.2,  # composition shift: neutral
        "profile_stall_fraction": 0.2,  # neutral (NOT the staging gauge)
    }
    diff = bc.compare(a, b)
    keys = {e["key"] for e in diff["regressions"]}
    assert keys == {"compile_retraces", "metrics.compile.s_total"}, diff
    assert {e["key"] for e in diff["changes"]} == {
        "profile_device_fraction",
        "profile_stall_fraction",
    }
    # the inverse direction is an improvement, never a failure
    diff = bc.compare(b, a)
    assert not diff["regressions"], diff


def test_trend_baseline_folds_best_ever():
    """ISSUE-17: the --trend baseline takes the BEST value per
    directional key across history (max for up, min for down), newest
    value for neutral/non-numeric keys."""
    history = [
        {"value": 100.0, "soak": {"apply_p99_ms": 8.0}, "note": "old"},
        {"value": 300.0, "soak": {"apply_p99_ms": 2.0}, "note": "peak"},
        {"value": 200.0, "soak": {"apply_p99_ms": 5.0}, "note": "new"},
    ]
    base = bc.trend_baseline(history)
    assert base["value"] == 300.0  # best-ever, not last
    assert base["soak.apply_p99_ms"] == 2.0  # best-ever latency floor
    assert base["note"] == "new"  # neutral: newest wins
    # a candidate that beats LAST round but not the best still regresses
    cand = {"value": 250.0, "soak": {"apply_p99_ms": 2.1}, "note": "cand"}
    diff = bc.compare(base, bc.flatten(cand))
    assert {e["key"] for e in diff["regressions"]} == {"value"}, diff


def test_trend_cli_against_synthetic_captures(tmp_path):
    """--trend end to end: committed-round folding is platform-keyed,
    end-of-round artifacts unwrap their `parsed` surface, and the exit
    code carries the verdict."""
    (tmp_path / "BENCH_r01.json").write_text(
        json.dumps(
            {"rc": 0, "parsed": {"platform": "tpu", "value": 100.0}}
        )
    )
    (tmp_path / "BENCH_r02.json").write_text(
        json.dumps({"platform": "tpu", "value": 500.0})
    )
    # a different platform's round must NOT leak into the tpu baseline
    (tmp_path / "BENCH_r03.json").write_text(
        json.dumps({"platform": "cpu", "value": 9999.0})
    )
    tool = os.path.join(ROOT, "benches", "bench_compare.py")

    def run_trend(cand):
        p = tmp_path / "cand.json"
        p.write_text(json.dumps(cand))
        return subprocess.run(
            [
                sys.executable,
                tool,
                "--trend",
                str(p),
                "--captures-dir",
                str(tmp_path),
            ],
            capture_output=True,
            text=True,
        )

    res = run_trend({"platform": "tpu", "value": 200.0})
    assert res.returncode == 1, res.stdout + res.stderr  # < best-ever 500
    assert "REGRESSION" in res.stdout
    res = run_trend({"platform": "tpu", "value": 510.0})
    assert res.returncode == 0, res.stdout + res.stderr  # new best
    res = run_trend({"platform": "gpu", "value": 1.0})
    assert res.returncode == 2, res.stdout + res.stderr  # no history


def test_cli_exit_codes_and_last_line_loading(tmp_path):
    a = tmp_path / "a.json"
    b = tmp_path / "b.json"
    a.write_text("noise line\n" + json.dumps({"value": 100.0}) + "\n")
    b.write_text(json.dumps({"value": 50.0}))
    tool = os.path.join(ROOT, "benches", "bench_compare.py")
    res = subprocess.run(
        [sys.executable, tool, str(a), str(b)],
        capture_output=True,
        text=True,
    )
    assert res.returncode == 1, res.stdout + res.stderr
    assert "REGRESSION" in res.stdout
    res = subprocess.run(
        [sys.executable, tool, str(a), str(a), "--json"],
        capture_output=True,
        text=True,
    )
    assert res.returncode == 0
    assert json.loads(res.stdout)["regressions"] == []
    res = subprocess.run(
        [sys.executable, tool, str(a), "/nonexistent.json"],
        capture_output=True,
        text=True,
    )
    assert res.returncode == 2


def test_capture_shaped_file_self_compares_clean(tmp_path):
    """A file in the shape of a device capture (nested stats, per-config
    sub-dicts, lists, notes) is a valid input and a fixed point of the
    tool."""
    cap = tmp_path / "capture.json"
    cap.write_text(
        json.dumps(
            {
                "metric": "updates_integrated_per_sec_full_b4_trace",
                "platform": "tpu",
                "device_kind": "TPU v5 lite",
                "n_devices": 1,
                "value": 50352.0,
                "vs_native": 0.08,
                "p50_apply_ms": 88.9,
                "probe": {"probe_stage": "done", "devices_s": 1.5},
                "configs": {
                    "config3": {"updates_per_sec": 78747.0, "scan_p99": 337},
                    "config5": {"docs_per_sec": 2274.0, "pipeline": {"n_sub": 4}},
                },
                "xla_full_stats": {"xla_chunks": 32, "xla_compactions": 5},
                "ladder": {"failures": [3, 5, 9]},
                "note": "fixture in the shape of a device capture",
            }
        )
    )
    assert bc.main([str(cap), str(cap)]) == 0
