"""The benchmark's server loop around `DeviceSyncServer`'s public entry points.

One tick: take every op that is due (at most `tick_max_frames` = 16, oldest
first; the rest wait and their latency keeps counting), hand each to
`receive_frames`, then `flush_device(max_steps=1)` + `block_until_ready`
until the queues are empty, so every update gets the end time of its own
dispatch, then drain the outboxes. An op that reads device state (SyncStep1,
reconnect) first flushes what is queued, one timed dispatch at a time.

All timing here is the benchmark's own (host clock around work that ends in
`block_until_ready` or in reply bytes); in traced runs the same spans are
also written into the profiler's trace as `bench.*` annotations.
"""

from __future__ import annotations

import collections
import contextlib
import threading
import time
from typing import Dict, List

from benchmark import grammar as g
from benchmark.ops import Op, Plan

now = time.perf_counter


def finisher_punts(server) -> int:
    """Rooms the native finisher handed to the Python finisher in the last
    diff. The program has no public counter for it: a private read, listed
    in `benchmark/README.md`; a rename fails here, loudly."""
    return server._diff_pipeline.stats.fallback_docs


class Records:
    """Raw samples, one entry per op handed to the server."""

    def __init__(self):
        self.kind: List[str] = []
        self.room: List[int] = []
        self.due: List[float] = []  # absolute host-clock instants
        self.handed: List[float] = []
        self.recv0: List[float] = []
        self.recv1: List[float] = []
        self.disp0: List[float] = []  # start of the dispatch that carried it
        self.done: List[float] = []  # update: its dispatch ended; else reply in hand
        self.failed: List[bool] = []
        self.op: List[Op] = []

    def add(self, op: Op, due: float, handed: float) -> int:
        for lst, v in (
            (self.kind, op.kind), (self.room, op.room), (self.due, due),
            (self.handed, handed), (self.recv0, 0.0), (self.recv1, 0.0),
            (self.disp0, 0.0), (self.done, 0.0), (self.failed, False), (self.op, op),
        ):
            lst.append(v)
        return len(self.kind) - 1


class ServerLoop:
    def __init__(self, server, plan: Plan, n_rooms: int, traced: bool, lose_update_at: int = -1):
        import jax

        self._jax = jax
        self.server = server
        self.plan = plan
        self.n_rooms = n_rooms
        self.traced = traced
        self.rec = Records()
        self.loaders: Dict[int, object] = {}
        self.sessions: Dict[int, object] = {}  # traffic session index -> server session
        self.warm_sessions: Dict[int, object] = {}
        self.room_sessions: List[list] = [[] for _ in range(n_rooms)]
        self.fifo: List[collections.deque] = [collections.deque() for _ in range(n_rooms)]
        self.pending_rooms: set = set()
        self.dispatches = 0
        self.dispatch_s = 0.0
        self.dispatch_spans: List[tuple] = []  # (t0, t1, updates carried)
        self.replies: List[tuple] = []  # (record index, SyncStep2 frame, room updates taken before it)
        self.broadcast_frames = 0
        self.punted = 0  # rooms the native finisher handed to the Python finisher
        # per room, in order: (tag, update, session) of everything handed to the
        # server; tag is "prefill", "preload", "warm" or "window"
        self.taken: List[List[tuple]] = [[] for _ in range(n_rooms)]
        self.tag = "prefill"
        self.taken_per_session: Dict[int, int] = {}  # traffic sessions only
        # the negative control: silently lose the n-th update the loop
        # hands over (counted as taken, never given to the server)
        self.lose_update_at = lose_update_at
        self._updates_seen = 0

    def open_window(self) -> None:
        """Forget set-up's samples: what follows is the measured window."""
        self.tag = "window"
        self.rec = Records()
        self.dispatch_spans = []
        self.replies = []
        self.punted = 0

    # --- spans ---------------------------------------------------------------

    def span(self, name: str):
        if self.traced:
            return self._jax.profiler.TraceAnnotation(name)
        return contextlib.nullcontext()

    # --- sessions ------------------------------------------------------------

    def connect_loaders(self) -> None:
        """One loader session per room, in rank order, so slot k is room k."""
        for k in range(self.n_rooms):
            sess, _ = self.server.connect_frames(g.room_name(k))
            self.loaders[k] = sess
            self.room_sessions[k].append(sess)

    def connect_sessions(self) -> None:
        for i, room in enumerate(self.plan.session_rooms):
            sess, _ = self.server.connect_frames(g.room_name(room))
            self.sessions[i] = sess
            self.room_sessions[room].append(sess)
        for w, room in enumerate(self.plan.warm_session_rooms):
            sess, _ = self.server.connect_frames(g.room_name(room))
            self.warm_sessions[w] = sess
            self.room_sessions[room].append(sess)

    def _swap(self, table: dict, key: int, room: int, new) -> None:
        old = table[key]
        lst = self.room_sessions[room]
        lst[lst.index(old)] = new
        table[key] = new

    # --- one op --------------------------------------------------------------

    def receive(self, op: Op, table: dict, due: float, handed: float, count: bool = True) -> int:
        rec = self.rec
        idx = rec.add(op, due, handed)
        if op.kind in ("sync1", "reconnect"):
            self.flush_all()
        sess = table[op.session]
        server = self.server
        if op.kind == "update" and count:
            self._updates_seen += 1
            if self._updates_seen - 1 == self.lose_update_at:
                self._note_taken(op)
                rec.recv0[idx] = rec.recv1[idx] = rec.disp0[idx] = rec.done[idx] = now()
                return idx
        with self.span("bench." + op.kind):
            rec.recv0[idx] = now()
            if op.kind == "reconnect":
                server.disconnect(sess)
                sess, _greeting = server.connect_frames(g.room_name(op.room))
                self._swap(table, op.session, op.room, sess)
            replies = server.receive_frames(sess, op.frame)
            rec.recv1[idx] = now()
        if sess.dead:
            rec.failed[idx] = True
            rec.done[idx] = rec.recv1[idx]
            new, _ = server.connect_frames(g.room_name(op.room))
            self._swap(table, op.session, op.room, new)
            return idx
        if op.kind == "update":
            if replies:  # a Busy reply: the update was refused
                rec.failed[idx] = True
                rec.done[idx] = rec.recv1[idx]
                return idx
            self._note_taken(op)
            self.fifo[op.room].append(idx)
            self.pending_rooms.add(op.room)
        else:
            rec.done[idx] = rec.recv1[idx]
            if op.kind in ("sync1", "reconnect"):
                self.punted += finisher_punts(server)
                if len(replies) != 1:
                    rec.failed[idx] = True
                else:
                    self.replies.append((idx, replies[0], len(self.taken[op.room])))
        return idx

    def _note_taken(self, op: Op) -> None:
        self.taken[op.room].append((self.tag, op.update, op.session))
        if self.tag in ("preload", "window"):
            self.taken_per_session[op.session] = self.taken_per_session.get(op.session, 0) + 1

    # --- dispatch ------------------------------------------------------------

    def dispatch(self) -> None:
        """One `flush_device` step to `block_until_ready`; every room's
        oldest queued update is carried by it."""
        with self.span("bench.dispatch"):
            t0 = now()
            steps = self.server.flush_device(max_steps=1)
            self._jax.block_until_ready(self.server.ingestor.state)
            t1 = now()
        rec = self.rec
        carried = 0
        for room in list(self.pending_rooms):
            q = self.fifo[room]
            idx = q.popleft()
            rec.disp0[idx] = t0
            rec.done[idx] = t1
            carried += 1
            if not q:
                self.pending_rooms.discard(room)
        if steps != 1 and carried:
            raise RuntimeError(f"flush_device(max_steps=1) made {steps} steps with updates queued")
        self.dispatches += steps
        self.dispatch_s += t1 - t0
        self.dispatch_spans.append((t0, t1, carried))

    def flush_all(self) -> None:
        while self.pending_rooms:
            self.dispatch()

    def drain(self, rooms) -> None:
        n = 0
        drain = self.server.drain
        for room in rooms:
            for sess in self.room_sessions[room]:
                if sess.outbox:
                    n += len(drain(sess))
        self.broadcast_frames += n

    def tick(self, ops: List[Op], table: dict, dues=None, handeds=None, count: bool = True) -> None:
        t = now()
        with self.span("bench.tick"):
            for j, op in enumerate(ops):
                self.receive(op, table, dues[j] if dues else t, handeds[j] if handeds else t, count)
            self.flush_all()
            self.drain({op.room for op in ops})

    # --- the window ----------------------------------------------------------

    def run_window(self, seconds: float, on_tick=None) -> tuple:
        """Drive the plan's ops for `seconds`; returns (t_open, t_close). After
        every tick `on_tick(elapsed, handed_share)`: the share of the plan's ops
        handed to the server so far where draining them closes the window (a
        saturated plan that does not repeat), else 0."""
        plan = self.plan
        tick_n = plan.tick_max_frames
        ops = plan.ops
        t_open = now()
        if plan.saturated:
            pos = 0
            while now() - t_open < seconds:
                if pos >= len(ops):
                    if not plan.repeat:
                        break
                    pos = 0
                batch = ops[pos : pos + tick_n]
                pos += len(batch)
                self.tick(batch, self.sessions, [t_open] * len(batch), [t_open] * len(batch))
                if on_tick:
                    on_tick(now() - t_open, 0.0 if plan.repeat else pos / len(ops))
            return t_open, now()

        inbox: collections.deque = collections.deque()
        finished = threading.Event()

        def feed():
            for op in ops:
                due = t_open + op.due
                while True:
                    wait = due - now()
                    if wait <= 0:
                        break
                    time.sleep(wait)
                inbox.append((op, due, now()))
            finished.set()

        feeder = threading.Thread(target=feed, name="bench-feeder", daemon=True)
        feeder.start()
        try:
            while True:
                batch = []
                while inbox and len(batch) < tick_n:
                    batch.append(inbox.popleft())
                if not batch:
                    if finished.is_set() and not inbox:
                        break
                    time.sleep(0.0005)
                    continue
                self.tick([b[0] for b in batch], self.sessions,
                          [b[1] for b in batch], [b[2] for b in batch])
                if on_tick:
                    on_tick(now() - t_open, 0.0)
        finally:
            feeder.join(timeout=seconds + 60)
        if feeder.is_alive():
            raise RuntimeError("the load generator thread did not end")
        return t_open, now()
