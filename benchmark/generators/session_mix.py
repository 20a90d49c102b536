"""The general generator: sessions in Zipf-loaded rooms sending a mix of
update, awareness, SyncStep1 and reconnect ops, saturated or Poisson.

A traffic file (`benchmark/traffic/<mix>.json`) is this generator's
parameters; a new mix is a new data file:

    arrival            "saturated" (everything due at 0; the inbox is never
                       empty) or "poisson" (open loop at `rate_per_s`)
    rate_per_s         offered ops per second (poisson only)
    shares             {"update": .., "awareness": .., "sync1": .., "reconnect": ..}
    sessions           traffic sessions, each bound to one room
    edits_per_session  update ops a session can send
    zipf_s             skew of sessions over rooms (YCSB zipfian constant)
    preload_updates    update ops put through the served path in set-up
    stale_updates_max  a reconnecting client lacks 0..this many room updates
    empty_sv_share     share of reconnects that carry an empty state vector
    repeat             start over when the ops run out (state must not change)
    tick_max_frames    ops the server loop takes per tick
    warm_sessions      disjoint sessions that send this mix during warm-up

Every run sends one fixed trace: the shapes (who sits where, every edit's
kind, position and length, the order of the pool, the gaps, kinds and
staleness) come from `grammar.LAYOUT` and the seed types the characters,
because the served path's device time depends on the data and a seed that
drew the shapes changed the work by +-12% (see `benchmark/grammar.py`).
"""

from __future__ import annotations

from typing import Dict, List

from benchmark import grammar as g
from benchmark.ops import Op, Plan

KINDS = ("update", "awareness", "sync1", "reconnect")


def _counts(shares: Dict[str, float], n: int) -> Dict[str, int]:
    """Fixed counts per kind summing to n (largest remainder)."""
    total = sum(shares.get(k, 0.0) for k in KINDS)
    exact = {k: n * shares.get(k, 0.0) / total for k in KINDS}
    out = {k: int(v) for k, v in exact.items()}
    rest = n - sum(out.values())
    for k in sorted(KINDS, key=lambda k: out[k] - exact[k])[:rest]:
        out[k] += 1
    return out


def _update_ops(sessions, order) -> List[Op]:
    return [
        Op("update", i, sessions[i].room, g.update_frame(sessions[i].edits[j].update),
           update=sessions[i].edits[j].update)
        for i, j in order
    ]


def _stale_sv(room_log: List[tuple], template_clock: tuple, stale: int):
    """The room's state vector as the grammar predicts it `stale` updates
    ago (-1: empty). `room_log` is [(client, chars)] of the updates the
    room has taken, in order; `template_clock` its prefill (client, clock)."""
    from ytpu.core.state_vector import StateVector

    if stale < 0:
        return StateVector()
    clocks = {template_clock[0]: template_clock[1]}
    for client, chars in room_log[: max(0, len(room_log) - stale)]:
        if chars > 0:
            clocks[client] = clocks.get(client, 0) + chars
    return StateVector(clocks)


def _mixed_ops(sessions, pool, kinds, seed, stream, room_logs=None, template_clocks=None,
               stale_plan=None) -> List[Op]:
    """Ops of the given kinds, in order; update ops come off `pool` in its
    order (so every session's own order holds)."""
    from ytpu.core.state_vector import StateVector

    r = g.rng(seed, stream, "pick")
    cursor = [0] * len(sessions)  # edits of each session handed out so far
    aw_clock = [0] * len(sessions)
    pool_it = iter(pool)
    out = []
    n_reconnect = 0
    for kind in kinds:
        if kind == "update":
            op = next(pool_it)
            cursor[op.session] += 1
            out.append(op)
            continue
        i = r.randrange(len(sessions))
        s = sessions[i]
        if kind == "awareness":
            aw_clock[i] += 1
            out.append(Op(kind, i, s.room, g.awareness_frame(s.client_id, aw_clock[i], i)))
        elif kind == "sync1":
            sv = (
                StateVector.decode_v1(s.edits[cursor[i] - 1].sv_after)
                if cursor[i]
                else StateVector(dict([template_clocks[s.room]]))  # synced, nothing typed yet
            )
            out.append(Op(kind, i, s.room, g.step1_frame(sv)))
        else:  # reconnect
            stale = stale_plan[n_reconnect % len(stale_plan)]
            n_reconnect += 1
            sv = _stale_sv(room_logs.get(s.room, []), template_clocks[s.room], stale)
            out.append(Op(kind, i, s.room, g.step1_frame(sv), stale=stale))
    return out


STALE_PERIOD = 30


def _stale_plan(n: int, empty_share: float, stale_max: int, seed: int, stream: str) -> List[int]:
    """Staleness of n reconnects: one seeded pattern of 30 (its share of
    empty state vectors, the rest cycling 0..stale_max) repeated, so every
    30 handshakes in a row hold the same multiset."""
    n_empty = round(STALE_PERIOD * empty_share)
    vals = [-1] * n_empty + [k % (stale_max + 1) for k in range(STALE_PERIOD - n_empty)]
    g.rng(seed, stream, "stale").shuffle(vals)
    return [vals[i % STALE_PERIOD] for i in range(n)]


def plan(deploy: dict, mix: dict, prefill, seed: int, seconds: float) -> Plan:
    n_rooms = deploy["n_docs"]
    n_sessions = mix["sessions"]
    tick = mix["tick_max_frames"]
    layout = g.LAYOUT  # shapes and order; the seed types the characters
    sessions = g.build_sessions(n_rooms, n_sessions, mix["edits_per_session"], mix["zipf_s"], seed, prefill)
    pool = _update_ops(sessions, g.interleave(sessions, layout))
    shares = mix["shares"]
    saturated = mix["arrival"] == "saturated"

    n_pre = min(mix.get("preload_updates", 0), len(pool))
    preload, pool = pool[:n_pre], pool[n_pre:]
    # what every room holds once set-up has put the preload through
    room_logs: Dict[int, List[tuple]] = {}
    taken = [0] * n_sessions
    for op in preload:
        s = sessions[op.session]
        room_logs.setdefault(op.room, []).append((s.client_id, s.edits[taken[op.session]].chars))
        taken[op.session] += 1
    template_clocks = [(prefill.for_room(k).client_id, prefill.for_room(k).chars) for k in range(n_rooms)]

    if saturated:
        if shares.get("reconnect", 0) >= 1.0:
            order = g.paced_order([s.room for s in sessions], layout, "traffic")
            stale = _stale_plan(n_sessions, mix["empty_sv_share"], mix["stale_updates_max"], layout, "traffic")
            ops = []
            for n, i in enumerate(order):
                s = sessions[i]
                sv = _stale_sv(room_logs.get(s.room, []), template_clocks[s.room], stale[n])
                ops.append(Op("reconnect", i, s.room, g.step1_frame(sv), stale=stale[n]))
        elif shares.get("update", 0) >= 1.0:
            ops = pool
        else:
            raise ValueError("a saturated mix is all updates or all reconnects")
    else:
        n = max(1, round(mix["rate_per_s"] * seconds))
        counts = _counts(shares, n)
        if counts["update"] > len(pool):
            raise ValueError(f"{counts['update']} updates wanted, the pool holds {len(pool)}")
        kinds = [k for k in KINDS for _ in range(counts[k])]
        g.rng(layout, "traffic", "kinds").shuffle(kinds)
        stale = _stale_plan(max(1, counts["reconnect"]), mix.get("empty_sv_share", 0.3),
                            mix.get("stale_updates_max", 8), layout, "traffic")
        ops = _mixed_ops(sessions, pool, kinds, layout, "traffic", room_logs, template_clocks, stale)
        t = 0.0
        for op, gap in zip(ops, g.exponential_gaps(n, mix["rate_per_s"], layout, "traffic")):
            t += gap
            op.due = t

    # warm-up: the first `tick` warm sessions sit in distinct rooms (the
    # harness drives its S-sweep through them); the rest send this mix
    n_own = mix.get("warm_sessions", 0)
    sweep_rooms = [(n_rooms // 2 + w) % n_rooms for w in range(min(tick, n_rooms))]
    own = g.build_sessions(n_rooms, n_own, 2, mix["zipf_s"], seed, prefill,
                           client_base=g.WARM_CLIENT_BASE + len(sweep_rooms), stream="warm") if n_own else []
    warm: List[List[Op]] = []
    if own:
        own_pool = _update_ops(own, g.interleave(own, layout, "warm"))
        total = sum(shares.get(k, 0.0) for k in KINDS)
        n_upd = len(own_pool)
        n_all = max(1, round(n_upd / max(shares.get("update", 0.0) / total, 1e-9))) if shares.get("update") else n_own
        counts = _counts(shares, n_all)
        counts["update"] = min(counts["update"], n_upd)
        kinds = [k for k in KINDS for _ in range(counts[k])]
        g.rng(layout, "warm", "kinds").shuffle(kinds)
        stale = _stale_plan(max(1, counts["reconnect"]), mix.get("empty_sv_share", 0.3),
                            mix.get("stale_updates_max", 8), layout, "warm")
        ops_w = _mixed_ops(own, own_pool, kinds, layout, "warm", room_logs, template_clocks, stale)
        # warm sessions are numbered after the sweep's
        for op in ops_w:
            op.session += len(sweep_rooms)
        warm = [ops_w[i : i + tick] for i in range(0, len(ops_w), tick)]

    lens = [len(op.update) for op in (preload + pool)[:4096] if op.update]
    # a saturated pool is taken in ticks of exactly `tick` frames, whatever
    # the server's speed, so the lane counts of its dispatches are known
    lane_counts = None
    if saturated and shares.get("update", 0) >= 1.0:
        lane_counts = set()
        for i in range(0, len(ops), tick):
            per_room: Dict[int, int] = {}
            for op in ops[i : i + tick]:
                per_room[op.room] = per_room.get(op.room, 0) + 1
            for depth in range(1, max(per_room.values()) + 1):
                lane_counts.add(sum(1 for n in per_room.values() if n >= depth))
    return Plan(
        clients=[s.client_id for s in sessions]
        + [g.WARM_CLIENT_BASE + w for w in range(len(sweep_rooms) + n_own)]
        + [t.client_id for t in prefill.templates],
        session_rooms=[s.room for s in sessions],
        preload=preload,
        warm=warm,
        warm_session_rooms=sweep_rooms + [s.room for s in own],
        ops=ops,
        saturated=saturated,
        repeat=bool(mix.get("repeat", False)),
        tick_max_frames=tick,
        sessions=sessions,
        notes={
            "update_len_min": min(lens), "update_len_max": max(lens),
            "update_len_mean": sum(lens) / len(lens),
            "hot_room_sessions": max(g.zipf_quotas(n_rooms, n_sessions, mix["zipf_s"])),
            "needs_sync_warm": bool(shares.get("sync1") or shares.get("reconnect")),
            "needs_update_warm": bool(shares.get("update")),
            "lane_counts": sorted(lane_counts) if lane_counts else list(range(1, min(tick, n_rooms) + 1)),
        },
    )

