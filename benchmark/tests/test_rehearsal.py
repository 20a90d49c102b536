"""The whole command path of every cell, on the CPU at the tiny sizes the
data files give under "rehearsal": prefill, warm-up, window, check, the
result line. No metric is printed from a CPU run. And the control: a run
in which the loop loses one update the oracle is told about must end with
`correct` false."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CELLS = [w["name"] for w in BENCH["workloads"]]
# mixes whose cells are not in BENCHMARK.json yet (PERF.md, Open questions)
# are rehearsed all the same, over the first configuration: --mix names one
OTHER_MIXES = sorted(
    f[:-5] for f in os.listdir(os.path.join(ROOT, "benchmark", "traffic"))
    if f.endswith(".json") and f[:-5] not in {w["traffic"] for w in BENCH["workloads"]}
)


def _run(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), *args],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=900,
    )
    return p


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_runs_the_command_path(cell, trace):
    if trace and cell != CELLS[0]:
        pytest.skip("one traced rehearsal is enough")
    p = _run("--workload", cell, "--seed", "4000000011", "--seconds", "2", "--trace", str(trace), "--rehearse")
    assert p.returncode == 0, p.stderr[-2000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True and last["correct"] is True and last["failed"] == 0
    assert "metrics" not in last and "device" not in last
    e2e = {m["name"] for m in BENCH["end_to_end"] if cell in m.get("workloads", [cell])}
    if not trace:
        assert set(last["would_report"]) == e2e
    assert "limit 0" in p.stdout  # every number compared is printed beside its limit,
    # and again as the last key of the result line and the last lines on stderr
    assert list(last)[-1] == "compared" and len(last["compared"]) >= 12
    assert all(value <= limit for value, limit in last["compared"].values())
    assert p.stderr.strip().splitlines()[-len(last["compared"])].startswith("bench: compared text_rooms_wrong 0 (limit 0)")
    assert " 0 programs built inside the window" in p.stdout


@pytest.mark.parametrize("mix", OTHER_MIXES)
def test_rehearsal_of_a_mix_no_cell_uses_yet(mix):
    p = _run("--workload", CELLS[0], "--mix", mix, "--seed", "4000000013", "--seconds", "2", "--trace", "0", "--rehearse")
    assert p.returncode == 0, p.stderr[-2000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True and last["correct"] is True and last["failed"] == 0
    if "reconnect" in p.stdout or "sync1" in p.stdout:
        assert "SyncStep2 replies of" in p.stdout


def test_without_a_tpu_the_benchmark_refuses():
    p = _run("--workload", CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert not p.stdout.strip().startswith("{") and '"metrics"' not in p.stdout


@pytest.mark.parametrize("mix", [None, "connect-storm"])
def test_lost_update_is_not_correct(mix):
    """The timed path broken underneath: one update (of the window, or of the
    preload where the window sends none) never reaches the server, the oracle
    is told of it, and the rest of the run is driven as always."""
    p = _run("--workload", CELLS[0], *(("--mix", mix) if mix else ()), "--seed", "12", "--seconds", "2",
             "--trace", "0", "--rehearse", "--break", "lose-update")
    assert p.returncode == 0, p.stderr[-2000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["correct"] is False
    assert "FAILED" in p.stdout
    assert any(value > limit for value, limit in last["compared"].values())
