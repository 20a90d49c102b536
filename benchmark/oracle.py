"""How `correct` is decided: the server against a host oracle it never saw.

The oracle is `ytpu.core.Doc`, the repo's host CRDT, fed exactly the update
payloads the loop handed to the server (prefill, preload and warm-up
included), room by room, after the window has closed. Compared, each with
the limit 0 (exact comparisons):

- for the 12 hottest, 12 seeded-random touched and 4 untouched rooms: the
  device text, the state vector and the canonical re-encoding of a
  full-state SyncStep1 answer (device diff -> fresh Doc -> encode);
- for every room: the server's state vector against the one the grammar
  alone predicts (a client's clock = characters it inserted);
- for a seeded sample of the window's SyncStep2 replies, to reconnects and
  to a typing session's SyncStep1 alike (the longest among them): the
  reply applied to a Doc holding exactly the state vector the request
  carried gives the text and state vector of an oracle fed what the room
  had taken when the request came;
- no error flag, nothing pending, and no recovery path fired.

Block granularity depends on history (the device never squashes rows, the
host squashes as it goes), so raw diff bytes are compared canonically, as
`chip_smoke.py` does.
"""

from __future__ import annotations

from typing import Dict, List

from benchmark import grammar as g
from benchmark.serve import finisher_punts

N_HOT, N_RANDOM, N_UNTOUCHED = 12, 12, 4  # 3,250-row rooms: the host oracle takes 0.4 s a room
N_REPLIES = 48

#: recovery paths that stay in the library; a run in which one fired is a
#: different (silently slower) result, so it is not `correct`
WATCHED = ("ingest.fast_recoveries", "encode.demotions", "lane.demotions", "net.bad_frames")


def counter_values() -> Dict[str, float]:
    from ytpu.utils import metrics

    return {name: metrics.counter(name).value for name in WATCHED}


def _canonical(update: bytes):
    from ytpu.core import Doc

    fresh = Doc(client_id=2)
    fresh.apply_update_v1(update)
    return (
        fresh.get_text(g.ROOT).get_string(),
        dict(fresh.state_vector().clocks),
        fresh.encode_state_as_update_v1(),
    )


def _oracle(updates: List[bytes]):
    from ytpu.core import Doc

    doc = Doc(client_id=1)
    for u in updates:
        doc.apply_update_v1(u)
    return doc


def sample_rooms(taken: List[List[bytes]], window_counts: List[int], seed: int) -> dict:
    n = len(taken)
    by_heat = sorted(range(n), key=lambda k: (-window_counts[k], k))
    hot = by_heat[:N_HOT]
    touched = [k for k in by_heat[N_HOT:] if window_counts[k] > 0]
    g.rng(seed, "check", "rooms").shuffle(touched)
    untouched = [k for k in reversed(by_heat) if window_counts[k] == 0][:N_UNTOUCHED]
    return {"hot": hot, "random": touched[:N_RANDOM], "untouched": untouched}


def check(server, loop, plan, prefill, room_updates: List[List[bytes]], window_counts: List[int],
          counters_before: Dict[str, float], seed: int, say, compared: Dict[str, list]) -> bool:
    """Print every number compared beside its limit, and put it into
    `compared` under a short name as `[number, limit]`; True if all hold."""
    import numpy as np

    from ytpu.core.state_vector import StateVector

    n_rooms = len(room_updates)
    rows: List[tuple] = []  # (short name, what, value, limit)

    # --- sampled rooms: text, state vector, canonical full-state diff -------
    groups = sample_rooms(room_updates, window_counts, seed)
    rooms = list(dict.fromkeys(groups["hot"] + groups["random"] + groups["untouched"]))
    diffs = server.device_encode_diff_many([(g.room_name(k), StateVector()) for k in rooms])
    bad_text, bad_sv, bad_diff = [], [], []
    for k, diff in zip(rooms, diffs):
        want = _oracle(room_updates[k])
        want_text = want.get_text(g.ROOT).get_string()
        want_sv = dict(want.state_vector().clocks)
        if server.device_text(g.room_name(k)) != want_text:
            bad_text.append(k)
        if dict(server.device_state_vector(g.room_name(k)).clocks) != want_sv:
            bad_sv.append(k)
        if _canonical(diff) != (want_text, want_sv, _canonical(want.encode_state_as_update_v1())[2]):
            bad_diff.append(k)
    rows += [
        ("text_rooms_wrong", f"rooms of {len(rooms)} sampled whose device text differs from the oracle", len(bad_text), 0),
        ("sv_rooms_wrong", f"rooms of {len(rooms)} sampled whose state vector differs from the oracle", len(bad_sv), 0),
        ("diff_rooms_wrong", f"rooms of {len(rooms)} sampled whose full-state diff, re-encoded, differs", len(bad_diff), 0),
    ]

    # --- every room: state vector against the grammar's own prediction ------
    expect = g.expected_clocks(plan.sessions, loop.taken_per_session)
    wrong = 0
    for k in range(n_rooms):
        tpl = prefill.for_room(k)
        have = dict(server.device_state_vector(g.room_name(k)).clocks)
        want = dict(expect.get(k, {}))
        want[tpl.client_id] = tpl.chars
        for c in list(have):
            if c >= g.WARM_CLIENT_BASE and c < g.TEMPLATE_CLIENT_BASE:
                del have[c]  # warm-up typists: checked through the oracle above
        wrong += have != want
    rows.append(("sv_vs_grammar_wrong", f"rooms of {n_rooms} whose state vector differs from the grammar's count", wrong, 0))

    # --- SyncStep2 replies of the window ------------------------------------
    replies = list(loop.replies)
    if replies:
        from ytpu.sync.protocol import message_reader

        longest = max(replies, key=lambda r: len(r[1]))
        pick = list(replies)
        g.rng(seed, "check", "replies").shuffle(pick)
        pick = [longest] + [p for p in pick if p is not longest][: N_REPLIES - 1]
        then: Dict[tuple, object] = {}  # (room, updates taken) -> oracle
        bad = 0
        for i, frame, n_at in pick:
            op = loop.rec.op[i]
            msgs = list(message_reader(frame))
            want = then.get((op.room, n_at))
            if want is None:
                want = then[(op.room, n_at)] = _oracle(room_updates[op.room][:n_at])
            client = _client_before(loop.taken[op.room][:n_at], op)
            client.apply_update_v1(msgs[0].body.payload)
            ok = (
                client.get_text(g.ROOT).get_string() == want.get_text(g.ROOT).get_string()
                and dict(client.state_vector().clocks) == dict(want.state_vector().clocks)
            )
            bad += not ok
        rows.append(("replies_wrong", f"SyncStep2 replies of {len(pick)} sampled (longest {len(longest[1])} B) that leave a client short of the oracle", bad, 0))

    # --- flags, stashes, recovery paths --------------------------------------
    ing = server.ingestor
    flagged = int(np.count_nonzero(np.asarray(ing.state.error)))
    stuck = sum(1 for d in range(n_rooms) if ing.pending_update(d) or ing.pending_ds(d))
    after = counter_values()
    fired = sum(after[n] - counters_before[n] for n in WATCHED)
    punted = finisher_punts(server)
    rows += [
        ("flagged_slots", "room slots with an error flag (1 = capacity, 2 = missing dependency)", flagged, 0),
        ("pending_slots", "room slots with updates left pending", stuck, 0),
        ("recoveries_fired", "recovery paths fired (" + ", ".join(WATCHED) + ")", fired, 0),
        ("fast_recoveries", "ingestor fast-lane recoveries", int(ing.fast_recoveries), 0),
        ("check_punts", "rooms the native finisher punted to the Python finisher in the check's fan-out", punted, 0),
        # a private read (no public counter; listed in benchmark/README.md): a rename fails here, loudly
        ("host_tenants", "tenants demoted to the host path", len(server._host_tenants), 0),
    ]
    ok = True
    for name, what, value, limit in rows:
        verdict = "ok" if value <= limit else "FAILED"
        ok &= value <= limit
        compared[name] = [value.item() if hasattr(value, "item") else value, limit]
        say(f"check: {what}: {value} (limit {limit}) {verdict}")
    if bad_text or bad_sv or bad_diff:
        say(f"check: first wrong rooms: text {bad_text[:6]}, state vector {bad_sv[:6]}, diff {bad_diff[:6]}")
    return ok


def _client_before(tagged: List[tuple], op):
    """A client `Doc` holding what the request's state vector claims, out
    of what the room had taken by then. A reconnect: the room's prefill and
    preload, short of the last `op.stale` preload updates (-1: nothing at
    all). A typing session's SyncStep1: the prefill and its own updates."""
    from ytpu.core import Doc

    client = Doc(client_id=3)
    if op.kind == "sync1":
        for tag, u, session in tagged:
            if tag == "prefill" or (tag in ("preload", "window") and session == op.session):
                client.apply_update_v1(u)
        return client
    if op.stale < 0:
        return client
    base = [u for tag, u, _ in tagged if tag in ("prefill", "preload")]
    n_pre = sum(1 for tag, _, _ in tagged if tag == "preload")
    for u in base[: len(base) - min(op.stale, n_pre)]:
        client.apply_update_v1(u)
    return client
