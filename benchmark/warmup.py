"""Warm-up: build every program the window will run, before it opens.

The served path compiles one decode/gather family per number of rooms that
carry a payload in a dispatch (S, unbucketed), per power-of-two bucket of
the dispatch's total wire bytes, and one more when every lane is a
delete-only update (no client section); the diff path compiles one pack
family per power of two of rows shipped (R). None of that can be changed
here (the program is not this PR's), so the warm-up dispatches each of
those shapes once, through the served path, from warm-up sessions whose
client ids are disjoint from the traffic's:

1. `update_sweep`: S lanes in distinct rooms for every lane count S the
   mix can dispatch (1..tick_max_frames; a saturated pool is taken in
   whole ticks, so the generator knows its lane counts and names fewer),
   once per wire-byte bucket the mix's payload lengths can reach at that S,
   and all-delete dispatches for small S;
2. `sync_sweep`: one handshake per power of two of rows shipped, against a
   room with the largest prefill plus a few warm-up rows, by lowering the
   prefill writer's clock in the state vector sent;
3. the cell's own mix from disjoint sessions (`plan.warm`), as ticks.

The same work from the seed every run, so `setup_s` is steady.
"""

from __future__ import annotations

import math
from typing import List

from benchmark import grammar as g
from benchmark.ops import Op

WIRE_BUCKET_LO = 256  # `_bucket(len(flat), 256)` in ytpu/models/ingest.py
LANE_MAX = 44  # the lane matrix is `_bucket(longest payload + 16, 64)` wide: the mix's payloads stay under 48
ALL_DELETE_MAX_S = 6  # 0.18**6 of dispatches at S=6 are all deletes: none in a window


def _bucket(n: int, lo: int) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


def reachable_buckets(s: int, lo: int, hi: int, mean: float) -> List[int]:
    """Wire-byte buckets a dispatch of `s` lanes can land in, for payload
    lengths in [lo, hi] around `mean`: the mean +- 6 standard deviations of
    a sum of s lengths, taking (hi - lo) / 3 as one length's deviation."""
    sd = (hi - lo) / 3.0 * math.sqrt(s)
    a = max(s * lo, int(s * mean - 6 * sd))
    b = min(s * hi, int(s * mean + 6 * sd) + 1)
    return sorted({_bucket(t, WIRE_BUCKET_LO) for t in (a, b)} | {_bucket((a + b) // 2, WIRE_BUCKET_LO)})


class Sweeper:
    """Warm-up typists, one per sweep room, making updates to order."""

    def __init__(self, plan, seed: int, prefill):
        self.rooms = plan.warm_session_rooms[: plan.tick_max_frames]
        self.typists = [
            g.Typist(g.WARM_CLIENT_BASE + w, g.rng(g.LAYOUT, "sweep", w), prefill.for_room(k).ids,
                     {prefill.for_room(k).client_id: prefill.for_room(k).chars}, text=g.rng(seed, "sweep", w))
            for w, k in enumerate(self.rooms)
        ]
        self.overhead = [24] * len(self.rooms)  # payload bytes beyond the word

    def _op(self, w: int, edit) -> Op:
        return Op("update", w, self.rooms[w], g.update_frame(edit.update), update=edit.update)

    def lanes(self, s: int, target_total: int) -> List[Op]:
        """One update from each of the first `s` typists, with word lengths
        chosen so the payloads sum to about `target_total` bytes. The first
        lane is always an insert (a dispatch of deletes alone is a program
        family of its own) and no payload passes `LANE_MAX`."""
        ops, total = [], 0
        for w in range(s):
            t = self.typists[w]
            want = (target_total - total) / (s - w)
            if w and want < self.overhead[w] and t.length >= 4:  # too short for an insert
                edit = t.next_edit(delete=True)
            else:
                n = max(1, min(LANE_MAX - self.overhead[w], round(want - self.overhead[w])))
                edit = t.next_edit(word_len=n)
                self.overhead[w] = len(edit.update) - n
            total += len(edit.update)
            ops.append(self._op(w, edit))
        return ops

    def one(self, w: int) -> Op:
        """One more 4-character insert from typist `w`."""
        return self._op(w, self.typists[w].next_edit(word_len=4))

    def deletes(self, s: int) -> List[Op]:
        ops = []
        for w in range(s):
            t = self.typists[w]
            if t.length < 4:
                raise RuntimeError("a warm-up typist has nothing to delete yet")
            ops.append(self._op(w, t.next_edit(delete=True)))
        return ops


def update_sweep(loop, plan, sw: Sweeper, say) -> dict:
    notes = plan.notes
    lo, hi, mean = notes["update_len_min"], notes["update_len_max"], notes["update_len_mean"]
    n_ticks = missed = 0
    lane_counts = [s for s in notes["lane_counts"] if s <= len(sw.rooms)]
    for s in lane_counts:
        for b in reachable_buckets(s, lo, hi, mean):
            # aim at the middle of the part of the bucket the mix can reach
            low = max(s * lo, b // 2 + 1 if b > WIRE_BUCKET_LO else 0)
            high = min(s * hi, b)
            ops = sw.lanes(s, (low + high) // 2)
            got = _bucket(sum(len(o.update) for o in ops), WIRE_BUCKET_LO)
            missed += got != b
            loop.tick(ops, loop.warm_sessions, count=False)
            n_ticks += 1
    for s in [s for s in lane_counts if s <= ALL_DELETE_MAX_S]:
        loop.tick(sw.deletes(s), loop.warm_sessions, count=False)
        n_ticks += 1
    say(f"warm-up: update sweep S={lane_counts}: {n_ticks} dispatches, "
        f"{missed} landed beside their wire bucket")
    return {"sweep_ticks": n_ticks, "sweep_missed": missed}


def sync_sweep(loop, plan, sw: Sweeper, prefill, say) -> dict:
    """Handshakes that ship 0, 1, 2, 4, ... rows of the largest prefill, and
    the whole of a room that has outgrown it (the next power of two)."""
    from ytpu.core.state_vector import StateVector

    # a sweep room that holds the largest prefill, so a warm-up typist can
    # push it past the prefill's row count
    w = max(range(len(sw.rooms)), key=lambda w: (prefill.for_room(sw.rooms[w]).rows, -w))
    room = sw.rooms[w]
    for _ in range(2):
        loop.tick([sw.one(w)], loop.warm_sessions, count=False)
    tpl = prefill.for_room(room)
    blocks = tpl.row_clocks
    table = {0: loop.loaders[room]}  # a session to sync through: the room's loader
    targets, n = [0], 1
    while n < len(blocks):
        targets.append(n)
        n *= 2
    targets.append(len(blocks))
    done = 0
    for kind in ("reconnect", "sync1"):
        for rows in targets:
            sv = StateVector(dict(loop.server.device_state_vector(g.room_name(room)).clocks))
            sv.clocks[tpl.client_id] = tpl.chars if rows == 0 else blocks[len(blocks) - rows]
            if rows == len(blocks):
                sv = StateVector()  # a new joiner: the whole room
            loop.tick([Op(kind, 0, room, g.step1_frame(sv))], table, count=False)
            done += 1
    loop.loaders[room] = table[0]
    say(f"warm-up: sync sweep on room{room} ({len(blocks)} prefill rows and a typist's): {done} handshakes")
    return {"sync_sweep": done}


def own_traffic(loop, plan, say) -> dict:
    n = 0
    for ops in plan.warm:
        loop.tick(ops, loop.warm_sessions, count=False)
        n += len(ops)
    if n:
        say(f"warm-up: {n} ops of the cell's own mix from disjoint sessions")
    return {"own_ops": n}
