"""The compaction policy is on in every server (PR 43): the four older
deployments, whose rooms are sized so that nothing fills, must not be
touched by it. A file of its own: the four cases take about as long as
`tests/test_served_compaction.py`'s, and the suite hands files to workers."""

import numpy as np
import pytest

from ytpu.sync.device_server import DeviceSyncServer
from ytpu.utils import metrics


OLDER = ["yws-rooms-1k.edit-flood", "yws-rooms-4k-x4.edit-flood",
         "yws-rooms-1k-unregistered.author-flood", "yws-rooms-1k-records.record-flood"]
WATCHED = ("ingest.room_compactions", "ingest.capacity_refusals")


def _serve_rehearsal(cell, on_update=None):
    """The cell's own generator at its rehearsal sizes (prefill, preload
    and pool) through the served path, a tick at a time; returns the
    server and what the watched counters counted. `on_update(room, update)`
    is told of every update handed over."""
    import importlib
    import json
    import os

    import jax

    from benchmark import grammar as g

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    w = next(w for w in bench["workloads"] if w["name"] == cell)
    config = next(c for c in bench["configs"] if c["name"] == w["config"])
    with open(os.path.join(root, config["file"])) as f:
        deploy = json.load(f)
    with open(os.path.join(root, "benchmark", "traffic", w["traffic"] + ".json")) as f:
        mix = json.load(f)
    deploy.update(deploy["rehearsal"])
    mix.update(mix["rehearsal"])
    seed, n_rooms = 43, deploy["n_docs"]
    prefill = g.Prefill(deploy["prefill"], n_rooms, seed)
    plan = importlib.import_module("benchmark.generators." + mix["generator"]).plan(deploy, mix, prefill, seed, 2.0)
    server = DeviceSyncServer(n_docs=n_rooms, capacity=deploy["capacity"], device_authoritative=True,
                              shard_docs=deploy["shard_docs"])
    for c in plan.clients:
        server.ingestor.enc.interner.intern(c)
    before = {n: metrics.counter(n).value for n in WATCHED}
    loaders = [server.connect_frames(g.room_name(k))[0] for k in range(n_rooms)]
    sessions = [server.connect_frames(g.room_name(k))[0] for k in plan.session_rooms]

    def serve(frames):
        for sess, room, update in frames:
            assert server.receive_frames(sess, g.update_frame(update)) == []
            if on_update:
                on_update(room, update)
        while server.pending_device_updates():
            assert server.flush_device(max_steps=1) == 1
            jax.block_until_ready(server.ingestor.state)

    for stage in range(prefill.n_stages):
        serve([(loaders[k], k, prefill.for_room(k).stages[stage]) for k in range(n_rooms)])
    tick = plan.tick_max_frames
    for ops in (plan.preload, [op for op in plan.ops if op.kind == "update"]):
        for i in range(0, len(ops), tick):
            serve([(sessions[op.session], op.room, op.update) for op in ops[i : i + tick]])
    assert not np.asarray(server.ingestor.state.error).any()
    return server, {n: metrics.counter(n).value - v for n, v in before.items()}


@pytest.mark.parametrize("cell", OLDER)
def test_the_older_deployments_rehearsals_count_no_compaction(cell):
    """Their rooms are sized so that nothing fills (PERF.md section 4): the
    policy that is on in every server must leave them alone: no room is
    compacted and none is refused."""
    _, counted = _serve_rehearsal(cell)
    assert counted == dict.fromkeys(WATCHED, 0)


def test_the_typed_cells_rooms_hold_the_hosts_blocks():
    """`yws-rooms-1k-typed.keystroke-flood` at its rehearsal sizes: many
    typists a room, runs, jumps, backspaces and deletes elsewhere in a
    prefilled document. Rooms are compacted on the way, none is refused,
    and once every room is compacted its rows are the blocks
    `ytpu.core.Doc` holds for the same updates, room by room."""
    from benchmark import grammar as g
    from ytpu.core import Doc

    oracle = {}
    server, counted = _serve_rehearsal(
        "yws-rooms-1k-typed.keystroke-flood",
        lambda room, update: oracle.setdefault(room, Doc(client_id=1)).apply_update_v1(update),
    )
    assert counted["ingest.room_compactions"] >= 1 and counted["ingest.capacity_refusals"] == 0
    ing = server.ingestor
    ing._compact(sorted(oracle))
    rows = np.asarray(ing.state.n_blocks)
    for room, doc in oracle.items():
        assert rows[room] == sum(len(q.blocks) for q in doc.store.blocks.clients.values()), room
        assert server.device_text(g.room_name(room)) == doc.get_text(g.ROOT).get_string()
