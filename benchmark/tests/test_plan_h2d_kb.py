"""`plan_h2d_kb.flood` (`layers/plan_h2d_kb.py`): the reader returns the bytes a
recorded step of the program sent for the host lane's batch, per step and in
KB, nothing where the recorder has no such stage or the window no step, and
the cell's CPU rehearsal reports it."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.run import applies, load_reader
from benchmark.window import Window

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
NAME = "plan_h2d_kb.flood"
CELL = "yws-rooms-1k-records.record-flood"


def _window(phases, steps):
    return Window(rec=None, t_open=0.0, t_close=30.0, setup_s=1.0,
                  dispatch_spans=[(float(i), i + 0.5, 1) for i in range(steps)], phases=phases)


def test_the_entry_names_the_cell_and_the_layer():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert entry == {"name": NAME, "unit": "KB/step", "better": "lower", "source": "program_counter",
                     "layer": "ingest planning", "moves": "updates_per_s", "workloads": [CELL]}
    assert bench["per_layer"][-1] is entry  # appended: nothing before it moved
    assert applies(entry, CELL, {"updates_per_s", "setup_s"})
    assert not applies(entry, "yws-rooms-1k.edit-flood", {"updates_per_s", "setup_s"})


def test_the_reader_divides_the_stages_bytes_by_the_steps():
    read = load_reader("layers", NAME).read
    stage = {"execute_s": 0.01, "calls": 20, "h2d_bytes": 19 * 417_792}  # the parent: 19 builds in 20 steps
    assert read(_window({"ingest.plan.h2d": stage}, 20)) == pytest.approx(19 * 417_792 / 20 / 1024)
    assert read(_window({"ingest.plan.h2d": dict(stage, h2d_bytes=0)}, 20)) == 0.0  # every step a kept batch
    assert read(_window({"ingest.plan": stage}, 20)) is None  # a program without the stage
    assert read(_window({"ingest.plan.h2d": stage}, 0)) is None  # a window without a step


def test_the_reader_returns_what_a_recorded_step_sent():
    """Two steps of the program itself with the recorder on, 64 rooms: one
    with a host-lane room (a nested Any), 16 wide, and one that builds the
    bucket's empty batch; then a third that is handed it and sends nothing."""
    from ytpu.core import Doc
    from ytpu.models.ingest import BatchIngestor
    from ytpu.native import available
    from ytpu.utils.phases import phases

    if not available():
        pytest.skip("the native prescan is not built here")
    doc, sent = Doc(client_id=5), []
    doc.observe_update_v1(lambda p, o, t: sent.append(p))
    with doc.transact() as txn:
        doc.get_array("a").push_back(txn, {"key": "k", "val": {"props": {"w": 1}}})
    for word in ("ab", "cd"):
        with doc.transact() as txn:
            doc.get_text("text").insert(txn, 0, word)
    ing = BatchIngestor(n_docs=64, capacity=64)
    phases.reset()
    phases.enable()
    try:
        for step, update in enumerate(sent):
            ing.apply_bytes([None] * 9 + [update] + [None] * 54)
            recorded = phases.snapshot()
            got = load_reader("layers", NAME).read(_window(recorded, step + 1))
            # [16, 4, 23] and [16, 4, 4] int32, once a build: the host-lane step, then the first fast-lane one
            assert got == pytest.approx(min(step + 1, 2) * 16 * 4 * (23 + 4) * 4 / (step + 1) / 1024), step
    finally:
        phases.disable()
        phases.reset()
    assert ing.slow_docs == 1 and ing.fast_docs == 2
    assert recorded["ingest.plan.h2d"]["h2d_bytes"] == 2 * 6912 and got < 8.0


def test_the_rehearsal_reports_it():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL, "--seed", "4000000041", "--seconds", "2",
         "--trace", "1", "--rehearse"], capture_output=True, text=True, env=env, cwd=ROOT, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True and last["correct"] is True and last["failed"] == 0
    assert " 0 programs built inside the window" in p.stdout
    assert {NAME, "plan_h2d_ms.flood", "host_rows_ms.flood", "batch_reuse_pct.flood"} <= set(last["would_report"])
