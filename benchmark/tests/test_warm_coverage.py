"""Every program family the window's dispatches need is one the warm-up has
dispatched, at the cell's real size. The served path compiles a decode and
gather family per number of lanes in a dispatch (S), per power-of-two
bucket of the dispatch's wire bytes, per lane-matrix width (L), and one
more where every lane is a delete-only update; a family first met inside
the window would compile there. The keys are worked out from the payload
bytes as `ytpu/models/ingest.py` does, without a device."""

import collections
import importlib
import json
import os

import pytest

from benchmark import grammar as g
from benchmark import warmup

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)


def _bucket(n, lo):
    b = lo
    while b < n:
        b *= 2
    return b


def _family(payloads):
    """What the fast lane's programs are keyed by, for one dispatch."""
    return (
        len(payloads),
        _bucket(sum(map(len, payloads)), 256),
        _bucket(max(map(len, payloads)) + 16, 64),
        all(p[0] == 0 for p in payloads),  # no client section in any lane
    )


class KeyLoop:
    """Stands in for the server loop: one update per room per dispatch, in
    arrival order, and notes each dispatch's family."""

    def __init__(self):
        self.families = set()
        self.warm_sessions = self.sessions = None

    def tick(self, ops, table, dues=None, handeds=None, count=True):
        fifo = collections.OrderedDict()
        for op in ops:
            if op.kind == "update":
                fifo.setdefault(op.room, collections.deque()).append(op.update)
        while fifo:
            self.families.add(_family([q.popleft() for q in fifo.values()]))
            for room in [r for r, q in fifo.items() if not q]:
                del fifo[room]


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_the_window_needs_no_family_the_warm_up_skipped(cell):
    w = next(w for w in BENCH["workloads"] if w["name"] == cell)
    config = next(c for c in BENCH["configs"] if c["name"] == w["config"])
    with open(os.path.join(ROOT, config["file"])) as f:
        deploy = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "traffic", w["traffic"] + ".json")) as f:
        mix = json.load(f)
    seed = 4000000123
    prefill = g.Prefill(deploy["prefill"], deploy["n_docs"], seed)
    plan = importlib.import_module("benchmark.generators." + mix["generator"]).plan(
        deploy, mix, prefill, seed, BENCH["run_seconds"])
    warm = KeyLoop()
    if plan.notes.get("needs_update_warm"):
        warmup.update_sweep(warm, plan, warmup.Sweeper(plan, seed, prefill), lambda msg: None)
    warmup.own_traffic(warm, plan, lambda msg: None)
    window = KeyLoop()
    tick = plan.tick_max_frames
    for i in range(0, len(plan.ops), tick):
        window.tick(plan.ops[i : i + tick], None)
    missing = sorted(window.families - warm.families)
    assert not missing, f"{len(missing)} families of {len(window.families)} first met in the window: {missing[:8]}"
