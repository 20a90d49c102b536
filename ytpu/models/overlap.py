"""The bounded producer/consumer loop the encode pipeline runs on.

`OverlapPipeline` overlaps host staging with device dispatch on worker
threads; its one user is `batch_doc.DiffPipeline`, which runs the blocking
D2H pull of a SyncStep2 sub-batch in the drain stage.
"""

from __future__ import annotations

import queue
import threading
import time
from dataclasses import dataclass
from typing import Callable, Iterable, List, Optional

__all__ = ["OverlapPipeline", "OverlapStats"]


@dataclass
class OverlapStats:
    """One overlap-loop run: staging/stall attribution + depth."""

    staged: int = 0
    consumed: int = 0
    stage_s: float = 0.0  # worker thread: pack/decode/build time
    stall_s: float = 0.0  # main thread: waited on staging (not hidden)
    max_depth: int = 0  # high-water staged-but-unconsumed chunks
    overlap_ratio: float = 0.0  # fraction of stage_s hidden behind dispatch
    # consumer-side drain stage (ISSUE-10): items that passed through the
    # optional `drain` callable and the wall time it spent — the encode
    # pipeline uses it for the async D2H pull, so device→host transfer
    # time attributes separately from both staging and the finisher
    drained: int = 0
    drain_s: float = 0.0


class OverlapPipeline:
    """Bounded producer/consumer overlap loop: a staging worker thread
    runs the host-side work for chunk k+1 while the caller thread
    dispatches chunk k to the device — wall-clock approaches
    max(stage, dispatch) instead of their sum.

    `run(produce, consume, drain=None)`: `produce` is an iterator driven
    on the worker thread (each `next()` is timed as staging);
    `consume(item)` runs on the calling thread. The queue holds at most
    `depth` staged items (backpressure). Exceptions from any side cancel
    the others and re-raise on the caller.

    `drain` (ISSUE-10) inserts a CONSUMER-SIDE middle stage on its own
    worker thread: staged items pass through `drain(item)` before the
    caller's `consume` sees the result, with the drain wall time
    attributed separately (`stats.drain_s`, `<prefix>.drain` phase). The
    encode pipeline runs the blocking D2H pull there, so sub-batch k's
    device→host transfer overlaps BOTH the device compaction of k+1
    (produce) and the native finisher of k−1 (consume) — a three-stage
    pipeline with per-stage attribution. Each stage boundary holds at
    most `depth` items.

    The end-of-stream sentinel is enqueued with the same blocking
    stop-checked loop as items: a `put_nowait` would drop it when the
    queue is full and the consumer slow (e.g. compiling chunk 1),
    stranding the consumer in `q.get()` forever.

    `overlap_ratio` = 1 − stall_s/stage_s (clamped to [0, 1]): 1 means
    every staged second was hidden behind device dispatch, 0 means the
    dispatch thread waited out all of it. Note stage_s includes any
    backpressure wait inside `produce` (free-slot acquisition); that
    wait only occurs when the device side is the bottleneck, where
    stall_s ≈ 0 keeps the ratio honest. With phases enabled the totals
    land under `<prefix>.stage` / `<prefix>.stall` plus
    `<prefix>.overlap_ratio` / `<prefix>.inflight_depth` value gauges.
    """

    def __init__(self, depth: int = 2, stage_prefix: str = "replay"):
        if depth < 1:
            raise ValueError(f"depth must be >= 1, got {depth}")
        self.depth = depth
        self.stage_prefix = stage_prefix
        self._stop = threading.Event()

    @property
    def stopping(self) -> bool:
        """True once the loop is tearing down — stop-aware producers
        (e.g. a staging generator blocked acquiring a buffer slot that a
        dead consumer will never free) must poll this and bail."""
        return self._stop.is_set()

    def run(
        self,
        produce: Iterable,
        consume: Callable,
        drain: Optional[Callable] = None,
    ) -> OverlapStats:
        from ytpu.utils.phases import phases

        # fresh per run(): teardown sets the event, and a stale set event
        # would skip the worker's sentinel-put on reuse — stranding the
        # caller in q.get() forever
        self._stop = threading.Event()
        q_in: "queue.Queue" = queue.Queue(maxsize=self.depth)
        # the drain stage gets its own boundary queue; without one the
        # consumer reads the staging queue directly (PR-5 shape)
        q_out: "queue.Queue" = (
            q_in if drain is None else queue.Queue(maxsize=self.depth)
        )
        SENTINEL = object()
        err: List[BaseException] = []
        stop = self._stop
        stats = OverlapStats()

        def _put(q, item) -> bool:
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        def worker():
            from ytpu.utils.faults import faults

            try:
                it = iter(produce)
                while not stop.is_set():
                    faults.maybe_raise("stage.raise", prefix=self.stage_prefix)
                    t0 = time.perf_counter()
                    try:
                        item = next(it)
                    except StopIteration:
                        return
                    stats.stage_s += time.perf_counter() - t0
                    stats.staged += 1
                    if not _put(q_in, item):
                        return
            except BaseException as e:  # surface staging errors on caller
                err.append(e)
            finally:
                _put(q_in, SENTINEL)

        def drainer():
            try:
                while not stop.is_set():
                    try:
                        item = q_in.get(timeout=0.1)
                    except queue.Empty:
                        continue
                    if item is SENTINEL:
                        return
                    t0 = time.perf_counter()
                    out = drain(item)
                    stats.drain_s += time.perf_counter() - t0
                    stats.drained += 1
                    if not _put(q_out, out):
                        return
            except BaseException as e:  # surface drain errors on caller
                err.append(e)
            finally:
                _put(q_out, SENTINEL)

        threads = [threading.Thread(target=worker, daemon=True)]
        if drain is not None:
            threads.append(threading.Thread(target=drainer, daemon=True))
        for t in threads:
            t.start()
        try:
            while True:
                t0 = time.perf_counter()
                item = q_out.get()
                stats.stall_s += time.perf_counter() - t0
                if item is SENTINEL:
                    break
                if err:
                    # an upstream stage died: abandon the staged backlog
                    # NOW rather than integrating ahead of an error that
                    # voids the run anyway — the finally below drains the
                    # queues and the stop event releases any producer-held
                    # buffers, so a raising stage never strands the caller
                    break
                # qsize()+1 races a worker put landing between the get
                # and this read; the queue cap bounds TRUE in-flight at
                # depth PER STAGE BOUNDARY, so clamp the gauge to what is
                # actually possible at the consumer-facing boundary
                stats.max_depth = max(
                    stats.max_depth, min(self.depth, q_out.qsize() + 1)
                )
                consume(item)
                stats.consumed += 1
        finally:
            stop.set()
            for q in (q_in, q_out):
                while True:  # unblock a worker mid-put
                    try:
                        q.get_nowait()
                    except queue.Empty:
                        break
            for t in threads:
                t.join()
        if err:
            raise err[0]
        hideable = stats.stage_s + stats.drain_s
        if hideable > 0:
            # with a drain stage, the hideable host work is staging PLUS
            # the D2H drain; stall still measures what the caller waited
            stats.overlap_ratio = max(
                0.0, min(1.0, 1.0 - stats.stall_s / hideable)
            )
        if phases.enabled:
            p = self.stage_prefix
            phases.add_time(f"{p}.stage", stats.stage_s, stats.staged)
            phases.add_time(f"{p}.stall", stats.stall_s, max(1, stats.consumed))
            if drain is not None:
                phases.add_time(f"{p}.drain", stats.drain_s, stats.drained)
            phases.set_value(f"{p}.overlap_ratio", stats.overlap_ratio)
            phases.set_max(f"{p}.inflight_depth", stats.max_depth)
        return stats
