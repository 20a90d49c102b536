"""`benchmark/chip_trace.py` and the four readers of the four-chip cell, on a
hand-made four-plane event list; what they give for one chip and for a
program without the gauge (nothing, and never an exception); and the
headroom reckoning of `yws-rooms-4k-x4`'s worst case."""

import json
import os

import pytest

from benchmark import chip_trace as ct
from benchmark import grammar as g
from benchmark.run import load_reader
from benchmark.window import Window

MS = 1e6  # ns
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
INTEGRATE = ["jit_apply_update_batch", "jit_decode_updates_v1"]


def _plane(decode_at, step_at, step_ms, reduce_ms):
    """One chip's lines for one step: the decode program (1 ms) and the
    integrate program, inside it a row loop holding two all-reduces."""
    return {
        "XLA Modules": [
            ["jit_decode_updates_v1(11)", decode_at * MS, 1 * MS],
            ["jit_apply_update_batch(22)", step_at * MS, step_ms * MS],
            ["jit_merge_stream(33)", (decode_at + 2) * MS, 1 * MS],  # not of the layer
        ],
        "XLA Ops": [
            ["%fusion.1 = s32[] fusion()", decode_at * MS, 1 * MS],
            ["%fusion.2 = s32[] fusion()", (decode_at + 2) * MS, 1 * MS],
            ["%while.663 = (s32[]) while()", step_at * MS, step_ms * MS],
            # nested in the loop: counted once in the busy time
            ["%all-reduce.5 = pred[] all-reduce(pred[] %p)", (step_at + 1) * MS, reduce_ms * MS],
            ["%all-reduce-start.7 = s32[] all-reduce-start()", (step_at + 10) * MS, 1 * MS],
            ["%all-reduce-done.7 = s32[] all-reduce-done()", (step_at + 11) * MS, 1 * MS],
            ["%reduce.9 = s32[] reduce()", (step_at + 15) * MS, 1 * MS],  # no collective
        ],
    }


def _events(n_chips=4):
    """A slice of 100 ms and one step. Chip 0 decodes at 20 and integrates
    60..90; the others integrate for 26, 24 and 20 ms."""
    steps = [30, 26, 24, 20][:n_chips]
    reduces = [2, 4, 5, 8][:n_chips]  # the chips that finish early wait longer in the all-reduce
    return {
        "host": [["bench.tick", 0.0, 100 * MS], ["bench.dispatch", 5 * MS, 87 * MS]],
        "device": {f"/device:TPU:{i}": _plane(20, 60, steps[i], reduces[i]) for i in range(n_chips)},
    }


def _window(trace=True):
    return Window(rec=None, t_open=0.0, t_close=30.0, setup_s=1.0, dispatch_spans=[],
                  trace={"span_counts": {"bench.dispatch": 1}, "window_s": 0.1} if trace else {},
                  programs={"integrate": INTEGRATE})


def test_per_chip_reductions_are_unions_inside_the_slice():
    ev = _events()
    assert ct.slice_bounds(ev) == (0.0, 100 * MS)
    # decode 1 + merge 1 + the loop; the ops nested in the loop count once
    assert ct.busy_by_chip(ev) == pytest.approx([0.032, 0.028, 0.026, 0.022])
    assert ct.program_seconds_by_chip(ev, INTEGRATE) == pytest.approx([0.031, 0.027, 0.025, 0.021])
    # all-reduce, -start and -done; `reduce` is no collective
    assert ct.collective_seconds_by_chip(ev) == pytest.approx([0.004, 0.006, 0.007, 0.010])
    assert ct.fullest([0.031, 0.027, 0.025, 0.021]) == 0
    assert ct.skew(ct.busy_by_chip(ev)) == pytest.approx(10 / 32)
    clipped = dict(ev, host=[["bench.tick", 0.0, 75 * MS]])  # the slice ends inside the step
    assert ct.program_seconds_by_chip(clipped, INTEGRATE) == pytest.approx([0.016] * 4)


@pytest.mark.parametrize("name", ["all-reduce", "all-gather.3", "reduce-scatter.1", "all-to-all.12",
                                  "collective-permute-start.4", "all-reduce-done.7.clone"])
def test_collectives_by_their_opcode(name):
    assert bool(ct.COLLECTIVE.match(name)) == (not name.endswith("clone"))
    assert not ct.COLLECTIVE.match("reduce.9") and not ct.COLLECTIVE.match("fusion.all-reduce")


def test_readers_read_the_chip_where_the_step_is_longest(monkeypatch):
    monkeypatch.setattr(ct, "planes", _events)
    read = lambda name: load_reader("layers", name).read(_window())
    assert read("integrate_chip_ms.flood") == pytest.approx(31.0)  # chip 0, not the chips' sum (104)
    assert read("collective_dev_ms.flood") == pytest.approx(4.0)  # on that chip
    assert read("chip_skew_pct.flood") == pytest.approx(100 * 10 / 32)


def test_one_chip_has_no_skew_and_an_untraced_run_nothing_to_read(monkeypatch):
    monkeypatch.setattr(ct, "planes", lambda: _events(1))
    assert load_reader("layers", "chip_skew_pct.flood").read(_window()) == 0.0
    assert load_reader("layers", "integrate_chip_ms.flood").read(_window()) == pytest.approx(31.0)
    for name in ("integrate_chip_ms.flood", "collective_dev_ms.flood", "chip_skew_pct.flood"):
        assert load_reader("layers", name).read(_window(trace=False)) is None
    monkeypatch.setattr(ct, "planes", lambda: {"host": [], "device": {}})  # a trace without the slice
    for name in ("integrate_chip_ms.flood", "collective_dev_ms.flood", "chip_skew_pct.flood"):
        assert load_reader("layers", name).read(_window()) is None


def test_state_shards_reads_the_gauge_and_nothing_without_one():
    from ytpu.utils import metrics

    read = load_reader("layers", "state_shards.flood").read
    metrics.reset()  # a program without the gauge: the parent of PR 28
    assert read(_window()) is None
    metrics.gauge("ingest.state_shards").set_function(lambda: 4)
    assert read(_window()) == 4.0
    metrics.reset()


def test_every_room_of_the_four_chip_deployment_stays_under_capacity():
    """`test_generator.test_every_room_stays_under_capacity`'s reckoning for
    `yws-rooms-4k-x4`: with the whole pool drained, a room holds its
    prefill, its share of the warm-up and its sessions' edits, 2 rows an
    edit at most. And a chip holds what `yws-rooms-1k`'s chip holds."""
    load = lambda kind, name: json.load(open(os.path.join(ROOT, "benchmark", kind, name + ".json")))
    deploy, one_chip, mix = load("configs", "yws-rooms-4k-x4"), load("configs", "yws-rooms-1k"), load("traffic", "edit-flood")
    n = deploy["n_docs"]
    assert n == deploy["chips"] * deploy["rooms_per_chip"] == 4 * one_chip["n_docs"]
    for same in ("capacity", "prefill", "guarantees", "zipf_s", "room_type", "device_authoritative", "replicas"):
        assert deploy[same] == one_chip[same], same
    prefill = g.Prefill(deploy["prefill"], n, 1)
    quota = g.zipf_quotas(n, mix["sessions"], mix["zipf_s"])
    warm = g.zipf_quotas(n, mix["warm_sessions"], mix["zipf_s"])
    assert quota[:6] == [222, 112, 75, 56, 45, 38] and quota.count(0) == 3229
    sweep = mix["tick_max_frames"] * 4
    slack = min(
        deploy["capacity"] - prefill.for_room(k).rows - 2 * (quota[k] * mix["edits_per_session"] + warm[k] * 2 + sweep)
        for k in range(n)
    )
    assert slack == 167  # rank 4, the hottest room with the large document
    rows = sum(prefill.for_room(k).rows for k in range(n))
    assert rows == 13_313_944 and rows / (n * deploy["capacity"]) > 0.79
