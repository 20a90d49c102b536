"""Full-trace fused replay: long update streams on a doc batch, with
capacity growth and commit-style compaction in the loop.

This is the north-star B4 workload (BASELINE.md config #2) at full length:
the round-1 bench replayed a 600-op prefix into a fixed-capacity state;
this driver sustains the whole 259,778-op editing trace (or any V1 update
stream) by running the engine the way a long-lived server would:

- the stream is decoded on device in chunks (`decode_updates_v1`) and
  integrated by the fused Pallas kernel (`integrate_kernel._run`), with
  the state kept in the kernel's packed [NC, D, C] layout between chunks
  (no per-chunk pack/unpack);
- string content is addressed by **global UTF-16 unit offsets** (a host
  pre-scan over the native columns assigns them), so sequential typing
  runs from different updates are byte-adjacent in a virtual content
  arena and `compact_packed(unit_refs=True)` re-merges them the way the
  reference's `try_squash` concatenates strings (block.rs:775-799);
- tombstones collapse to origin-free GC ranges
  (`compact_packed(gc_ranges=True)`), the reference's default-GC behavior
  (gc.rs, block_store.rs:155-235);
- compaction fires at a high-water mark, and when even the compacted
  state approaches capacity the state grows in place (`grow_packed`) —
  host-driven, exactly like a server reacting to tenant growth.

Host work per update is bounded and small: the native columnar pre-scan
(the same control plane the ingest fast lane uses) plus — on the async
raw ingest lane (ISSUE-7, the default) — a slice copy of the stream's
concatenated wire bytes; the per-update padding/packing happens on
device (`gather_raw_lanes`). Decode, integrate, squash, and GC all run
on device.
"""

from __future__ import annotations

import queue
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from functools import partial
from typing import Callable, Iterable, List, Optional, Tuple

import numpy as np

__all__ = [
    "ReplayPlan",
    "UnitArenaView",
    "plan_replay",
    "FusedReplay",
    "ChunkPlan",
    "plan_chunks",
    "SubBatchPlan",
    "plan_subbatches",
    "OverlapPipeline",
    "OverlapStats",
    "OverlapPlan",
    "plan_overlap",
    "build_wire_table",
    "raw_chunk_cap",
]


@dataclass
class ReplayPlan:
    """Host pre-scan of an update stream (native columns, one pass)."""

    n_updates: int
    max_rows: int  # U bucket
    max_dels: int  # R bucket
    max_len: int  # longest update in bytes
    max_steps: int  # decode step budget
    max_sections: int
    max_client: int  # largest raw client id in the stream
    # per (update, row-slot): absolute UTF-16 unit offset of the row's
    # string content (-1 for non-string rows), assigned in wire order
    unit_refs: np.ndarray  # [S, U] i32
    # unit -> byte-start of its character within `arena` (both units of a
    # surrogate pair share the char start); sentinel entry = len(arena)
    unit_byte: np.ndarray  # [total_units + 1] i64
    arena: bytes  # concatenated string payload bytes (UTF-8)
    # worst-case state rows each update can add (rows x 3 for the row +
    # two splits, delete ranges x 2 splits) — drives the high-water check
    adds: np.ndarray = None  # [S] i32


def plan_replay(payloads: List[bytes]) -> ReplayPlan:
    from ytpu.native import decode_update_columns
    from ytpu.ops.decode_kernel import steps_for_columns

    S = len(payloads)
    max_rows = max_dels = max_len = max_steps = max_sections = 0
    max_client = 0
    adds = np.zeros(S, dtype=np.int32)
    rows_per: List[List[int]] = []
    arena_parts: List[bytes] = []
    unit_byte: List[int] = []
    total_bytes = 0
    for p in payloads:
        cols = decode_update_columns(p)
        if cols is None:
            raise RuntimeError("native codec unavailable (required for plan)")
        if cols.error:
            raise ValueError("malformed update in stream")
        max_len = max(max_len, len(p))
        max_sections = max(max_sections, cols.n_client_sections)
        refs_here: List[int] = []
        for i in range(cols.n_blocks):
            kind = int(cols.kind[i])
            if kind == 10:
                continue
            # the unit-ref arena covers text streams; other content kinds
            # would leave refs into the transient chunk buffer — reject
            # loudly rather than corrupt silently
            if kind not in (0, 1, 4):
                raise ValueError(
                    f"replay plan supports text streams only (GC/Deleted/"
                    f"String); update carries content kind {kind} — use "
                    "BatchIngestor.apply_bytes for mixed-content streams"
                )
            max_client = max(max_client, int(cols.client[i]))
            if int(cols.length[i]) <= 0:
                continue
            if kind == 4:
                # strip the varint length prefix from the content span
                span = cols.content_bytes(i)
                j, blen, shift = 0, 0, 0
                while True:
                    b = span[j]
                    blen |= (b & 0x7F) << shift
                    shift += 7
                    j += 1
                    if b < 0x80:
                        break
                sbytes = span[j : j + blen]
                refs_here.append(len(unit_byte))
                # per-unit char starts (surrogate pairs take two entries)
                k = 0
                while k < len(sbytes):
                    b0 = sbytes[k]
                    w = 1 if b0 < 0x80 else 2 if b0 < 0xE0 else 3 if b0 < 0xF0 else 4
                    unit_byte.append(total_bytes + k)
                    if w == 4:
                        unit_byte.append(total_bytes + k)
                    k += w
                arena_parts.append(sbytes)
                total_bytes += len(sbytes)
            else:
                refs_here.append(-1)
        rows_per.append(refs_here)
        adds[len(rows_per) - 1] = 3 * len(refs_here) + 2 * cols.n_dels
        max_rows = max(max_rows, len(refs_here))
        max_dels = max(max_dels, cols.n_dels)
        max_steps = max(max_steps, steps_for_columns(cols))
    U = max(1, max_rows)
    refs = np.full((S, U), -1, dtype=np.int32)
    for s, rr in enumerate(rows_per):
        for u, r in enumerate(rr):
            refs[s, u] = r
    unit_byte.append(total_bytes)
    return ReplayPlan(
        n_updates=S,
        max_rows=U,
        max_dels=max(1, max_dels),
        max_len=max_len,
        max_steps=max_steps,
        max_sections=max(1, max_sections),
        max_client=max_client,
        unit_refs=refs,
        unit_byte=np.asarray(unit_byte, dtype=np.int64),
        arena=b"".join(arena_parts),
        adds=adds,
    )


class UnitArenaView:
    """PayloadStore-shaped resolver over unit-addressed arena content.

    Rows carry ``ref`` = absolute UTF-16 unit offset of their content
    start and ``off``/``len`` in units; splits that land inside a
    surrogate pair render U+FFFD halves, matching the host's
    `split_str_utf16` (content.py)."""

    def __init__(self, unit_byte: np.ndarray, arena: bytes):
        self.unit_byte = unit_byte
        self.arena = arena

    def _is_second_half(self, u: int) -> bool:
        return u > 0 and self.unit_byte[u] == self.unit_byte[u - 1] and (
            u >= len(self.unit_byte) - 1 or self.unit_byte[u + 1] != self.unit_byte[u]
        )

    def slice_text(self, ref: int, off: int, length: int) -> str:
        p = int(ref) + int(off)
        q = p + int(length)
        if length <= 0:
            return ""
        prefix = suffix = ""
        if self._is_second_half(p):
            prefix = "�"
            p += 1
        end_mid = q < len(self.unit_byte) - 1 and self._is_second_half(q)
        b0 = int(self.unit_byte[p])
        b1 = int(self.unit_byte[q])
        if end_mid:
            suffix = "�"
        return prefix + self.arena[b0:b1].decode("utf-8") + suffix

    def slice_values(self, ref: int, off: int, length: int) -> list:
        return list(self.slice_text(ref, off, length))


@dataclass
class ReplayStats:
    chunks: int = 0
    compactions: int = 0
    growths: int = 0
    capacity: int = 0
    peak_blocks: int = 0
    final_blocks: int = 0
    chunk_seconds: List[float] = field(default_factory=list)
    # async (overlap) lane only — see OverlapStats / PackedReplayDriver
    syncs: int = 0  # readout drains actually materialized on host
    stage_s: float = 0.0
    stall_s: float = 0.0
    overlap_ratio: float = 0.0
    max_inflight: int = 0
    buffer_reuses: int = 0
    # raw ingest lane (ISSUE-7): which staging path ran ("raw" ships
    # concatenated bytes + an offsets table, "packed" the per-update
    # host-packed [S, L] matrix), how many payload bytes staging copied,
    # and the one-time wire-table build cost (NOT counted in stage_s —
    # it is not per-chunk work and cannot be hidden behind dispatch)
    ingest: str = ""
    stage_bytes: int = 0
    prescan_s: float = 0.0
    # resilience (ISSUE-6): caller-level resumes + driver-level in-place
    # retries, sticky lane demotions, chunk-boundary checkpoints taken,
    # update indices quarantined instead of aborting, and positions the
    # replay restarted from after a fault (empty = no fault)
    recoveries: int = 0
    demotions: int = 0
    checkpoints: int = 0
    quarantined: List[int] = field(default_factory=list)
    resumes: List[int] = field(default_factory=list)
    final_lane: str = ""
    # conflict-tail attribution (ISSUE-11): the scan-width record pulled
    # with the driver's final readout drain — pow2 bucket counts, the
    # observed max, and the bucket-quantile p50/p99 (docs/observability.md
    # §Conflict-tail attribution). Zero extra syncs: the words ride the
    # same lazy readout future the occupancy protocol already drains.
    scan_hist: tuple = ()
    scan_max: int = 0
    scan_p50: int = 0
    scan_p99: int = 0
    # two-tier scan occupancy (ISSUE-12), same readout origin: scans the
    # cheap tier resolved vs scans that escalated to the vectorized wide
    # tier, and the exact dispatch-trip accounting — serial-equivalent
    # trips (Σ width, what the single-tier loop would have dispatched)
    # vs the trips the two-tier dispatch actually paid
    scan_tier_cheap: int = 0
    scan_tier_wide: int = 0
    scan_trips_serial: int = 0
    scan_trips_two_tier: int = 0
    # incremental state commitment (ISSUE-13): the batch-aggregate
    # lattice-digest word from the driver's final readout drain (uint32;
    # docs/serving.md §Federation — the device twin of the host-side
    # per-tenant commitments the replica mesh exchanges)
    commit_word: int = 0
    # capacity observatory (ISSUE-18): occupancy/fragmentation ledger
    # from the driver's final readout drain plus cumulative compaction
    # efficacy — see integrate_kernel.ReplayChunkStats for the word
    # origins (all ride the lazy readout, zero new syncs)
    occupied_rows: int = 0
    dead_rows: int = 0
    dead_max: int = 0
    reclaimed_rows: int = 0
    compact_gap_chunks: int = 0
    # doc-axis sub-batching (ISSUE-20): the driver's active pow2 slice
    # width (0 = monolithic dispatch) and cumulative width demotions
    subbatch_width: int = 0
    subbatch_narrowed: int = 0


@dataclass
class _ReplayCheckpoint:
    """Chunk-boundary snapshot of the packed state (host numpy copies —
    survives donation, worker death, and lane demotion)."""

    cols: np.ndarray
    meta: np.ndarray
    pos: int  # first un-integrated update index
    hi: int  # actual occupancy at the snapshot (post-drain)
    lane: str  # lane the snapshot was produced under


@dataclass(frozen=True)
class ChunkPlan:
    """Host-side chunk/compaction plan for a fixed-capacity chunked replay.

    `chunk` is the fixed steps-per-dispatch (one compiled program serves
    every chunk); `max_chunk_adds` the worst-case block-slot growth any
    single chunk can cause; `budget` the policy's per-chunk growth
    allowance at this capacity; `needs_compaction` whether the stream's
    total worst-case growth exceeds one capacity (≥1 between-chunk
    compaction is then guaranteed in the plan)."""

    chunk: int
    n_chunks: int
    max_chunk_adds: int
    budget: int
    capacity: int
    needs_compaction: bool

    @property
    def feasible(self) -> bool:
        """Every chunk's worst-case growth fits the policy budget."""
        return self.max_chunk_adds <= self.budget


def plan_chunks(adds, capacity: int, max_chunk: int = 8192, policy=None) -> ChunkPlan:
    """Size the fixed replay chunk so between-chunk compaction suffices.

    The round-5 flagship failure mode was exactly a mis-sized chunk: at
    C=32768 an 8192-update B4 chunk carries ~26k worst-case adds, so even
    a perfect compaction can't make room and the replay dies with "state
    full at max capacity". This planner picks the largest power-of-two
    chunk ≤ `max_chunk` whose worst consecutive window of per-update adds
    (`adds`, the `ReplayPlan.adds` accounting) fits the shared
    `CompactionPolicy`'s chunk budget — compaction restores at least
    `1 - high_watermark` of the capacity whenever the policy fires, so a
    budget-sized chunk always has room. Both device lanes plan with this
    one function (shared-policy requirement of ISSUE-4)."""
    from ytpu.models.batch_doc import DEFAULT_COMPACTION_POLICY

    policy = policy or DEFAULT_COMPACTION_POLICY
    adds = np.asarray(adds, dtype=np.int64)
    S = int(adds.shape[0])
    budget = policy.chunk_add_budget(capacity)
    cum = np.concatenate([[0], np.cumsum(adds)])

    def worst_window(chunk: int) -> int:
        starts = np.arange(0, S, chunk)
        ends = np.minimum(starts + chunk, S)
        return int((cum[ends] - cum[starts]).max(initial=0))

    chunk = 1 << max(0, int(max_chunk).bit_length() - 1)  # pow2 round-down
    while chunk > 1 and worst_window(chunk) > budget:
        chunk //= 2
    return ChunkPlan(
        chunk=chunk,
        n_chunks=(S + chunk - 1) // chunk,
        max_chunk_adds=worst_window(chunk),
        budget=budget,
        capacity=capacity,
        needs_compaction=int(adds.sum()) > capacity,
    )


@dataclass(frozen=True)
class SubBatchPlan:
    """Host-side doc-axis sub-batch plan for one integrate dispatch
    (ISSUE-20, the doc-axis dual of `ChunkPlan`).

    `width` is the fixed pow2 doc count per sub-batch — one compiled
    chunk-program family per `(width, capacity)` pair serves every
    slice; `transient_bytes` the worst per-dispatch allocation the plan
    admits (`packed_state_bytes(width, C) + packed_state_bytes(width,
    2C)`: a slice plus the grow transient its `ensure_room` may ask
    for); `monolithic_bytes` the same transient at the full doc axis
    (what the plan avoids allocating)."""

    width: int
    n_sub: int
    n_docs: int
    capacity: int
    budget_bytes: int
    transient_bytes: int
    monolithic_bytes: int

    @property
    def monolithic(self) -> bool:
        """True when the whole doc axis fits one dispatch — the
        sub-batch loop then degenerates to the PR-5 single-dispatch
        path, byte-identically."""
        return self.width >= self.n_docs

    @property
    def feasible(self) -> bool:
        """The per-dispatch transient fits the budget at this width."""
        return self.transient_bytes <= self.budget_bytes


def plan_subbatches(
    n_docs: int,
    capacity: int,
    *,
    d_block: int = 1,
    budget_bytes: Optional[int] = None,
    forecaster=None,
    max_width: Optional[int] = None,
) -> SubBatchPlan:
    """Size the pow2 doc-width sub-batch so one dispatch's grow
    transient fits the memory budget — the `plan_chunks` pow2
    round-down, applied to the doc axis instead of the step axis.

    Starts at the largest pow2 ≤ `n_docs` that divides it (every slice
    then shares ONE shape family — the retrace bound the PR-17 sentinel
    pins) and halves while `packed_state_bytes(w, C) +
    packed_state_bytes(w, 2C)` busts the budget, flooring at `d_block`
    (the fused lane can't tile below its block) or 1. The budget comes
    from, in order: the explicit arg, the forecaster's pinned
    `budget_bytes`, the observatory's `memory_budget_bytes()`; when the
    forecaster has fitted samples its `model_bytes` replaces the
    analytic formula so the plan tracks measured reality."""
    from ytpu.ops.integrate_kernel import packed_state_bytes
    from ytpu.utils.capacity import memory_budget_bytes

    n_docs = int(n_docs)
    capacity = int(capacity)
    if budget_bytes is None:
        budget_bytes = (
            forecaster.budget_bytes
            if forecaster is not None
            else memory_budget_bytes()
        )
    budget_bytes = int(budget_bytes)
    floor = max(int(d_block), 1)

    model = (
        forecaster.model_bytes
        if forecaster is not None
        else packed_state_bytes
    )

    def transient(w: int) -> int:
        return int(model(w, capacity)) + int(model(w, 2 * capacity))

    # largest pow2 ≤ n_docs that divides it (pow2 halving preserves
    # divisibility, so the loop below never has to re-check)
    width = 1 << max(0, n_docs.bit_length() - 1)
    while width > 1 and n_docs % width:
        width //= 2
    if max_width is not None:
        while width > max(int(max_width), 1):
            width //= 2
    while width > floor and transient(width) > budget_bytes:
        width //= 2
    return SubBatchPlan(
        width=width,
        n_sub=(n_docs + width - 1) // width,
        n_docs=n_docs,
        capacity=capacity,
        budget_bytes=budget_bytes,
        transient_bytes=transient(width),
        monolithic_bytes=transient(n_docs),
    )


# --- host-staging ↔ device-dispatch overlap engine (ISSUE-5 tentpole) -------


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
    """Bounded producer/consumer overlap loop shared by the packed replay
    lanes: a staging worker thread runs the host-side work for chunk k+1
    (byte packing + unit-ref rebase in `FusedReplay`, payload decode +
    step building in `UpdatePipeline`) while the caller thread dispatches
    chunk k to the device — wall-clock approaches max(stage, dispatch)
    instead of their sum.

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
    stop-checked loop as items: the previous `UpdatePipeline` machinery
    `put_nowait`-dropped it when the queue was full and the consumer
    slow (e.g. compiling chunk 1), stranding the consumer in `q.get()`
    forever — a real deadlock beyond the tier-1 gate's alphabetical
    timeout horizon.

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


@dataclass(frozen=True)
class OverlapPlan:
    """Host-checkable staging plan of an async replay (dry-run surface:
    `bench.py --dry-run` asserts depth/buffer-reuse before a device
    round trusts the overlap lane)."""

    depth: int  # max in-flight chunks (= staging buffer pair)
    buffers: int  # preallocated staging slots
    n_chunks: int
    buffer_reuses: int  # times a slot is re-packed after its first use


def plan_overlap(n_updates: int, chunk: int, depth: int = 2) -> OverlapPlan:
    """The async lane's static staging plan: `depth` preallocated slots
    (double-buffered at the default 2), every chunk beyond the first
    `depth` re-packs a recycled slot — zero steady-state allocation."""
    n_chunks = max(0, -(-int(n_updates) // int(chunk)))
    return OverlapPlan(
        depth=depth,
        buffers=depth,
        n_chunks=n_chunks,
        buffer_reuses=max(0, n_chunks - depth),
    )


class _StagingSlot:
    """One reusable staging buffer: padded wire bytes + lens + the
    chunk's global unit-ref rows. A pair of these (the double buffer)
    serves the whole replay. ``trace`` carries the staging request's
    trace id (ISSUE-11) across the thread hand-off — ContextVars don't
    cross into the consumer thread, the slot does."""

    __slots__ = ("buf", "lens", "refs", "pos", "end", "trace")

    def __init__(self, chunk: int, width: int, u: int):
        self.buf = np.zeros((chunk, width), dtype=np.uint8)
        self.lens = np.zeros((chunk,), dtype=np.int32)
        self.refs = np.full((chunk, u), -1, dtype=np.int32)
        self.pos = 0
        self.end = 0
        self.trace = None


class _RawStagingSlot:
    """One reusable RAW-ingest staging buffer (ISSUE-7): a plain byte
    buffer holding the chunk's concatenated wire bytes, the tiny
    per-update offset/length tables, and the chunk's global unit-ref
    rows. Staging into it is a memcpy (`pack_raw_updates_into`) — the
    per-update padding/packing of `_StagingSlot` moved on device
    (`gather_raw_lanes`)."""

    __slots__ = ("raw", "offs", "lens", "refs", "pos", "end", "trace")

    def __init__(self, raw_cap: int, chunk: int, u: int):
        self.raw = np.zeros((raw_cap,), dtype=np.uint8)
        self.offs = np.zeros((chunk,), dtype=np.int32)
        self.lens = np.zeros((chunk,), dtype=np.int32)
        self.refs = np.full((chunk, u), -1, dtype=np.int32)
        self.pos = 0
        self.end = 0
        self.trace = None


def build_wire_table(payloads) -> Tuple[np.ndarray, np.ndarray]:
    """Flatten a payload sequence into the raw ingest lane's wire table:
    ``(wire, wire_offsets)`` with ``wire`` the concatenated u8 bytes and
    ``wire_offsets`` the ``[S+1]`` prefix table. One C-speed join + one
    cumsum — the only per-update host work left on the raw path is the
    ``len()`` reads of this prescan; per-CHUNK staging afterwards is
    pure slice copies (`pack_raw_updates_into`)."""
    n = len(payloads)
    lens = np.fromiter((len(p) for p in payloads), dtype=np.int64, count=n)
    offsets = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(lens, out=offsets[1:])
    wire = np.frombuffer(b"".join(payloads), dtype=np.uint8)
    return wire, offsets


def raw_chunk_cap(wire_offsets: np.ndarray, chunk: int) -> int:
    """Staging-buffer capacity for the raw lane: the worst byte span of
    ANY ``chunk``-update window (sliding, not just stride-aligned — a
    checkpoint resume shifts the window grid) plus the staged
    `EMPTY_UPDATE` tail, bucketed to 64 so near-identical streams share
    one compiled `replay_chunk_program_raw` family."""
    from ytpu.ops.decode_kernel import EMPTY_UPDATE

    S = len(wire_offsets) - 1
    if S <= 0:
        return 64
    ends = np.minimum(np.arange(S, dtype=np.int64) + chunk, S)
    worst = int((wire_offsets[ends] - wire_offsets[:S]).max())
    cap = worst + len(EMPTY_UPDATE)
    return -(-cap // 64) * 64


def _decoder(max_rows: int, max_dels: int, n_steps: int, max_sections: int):
    """Chunk decoder bound to its static shape params. `FusedReplay.run`
    used to build a FRESH `jax.jit(partial(...))` per call, so the warmup
    instance's compile never carried over to the timed instance — the
    timed pass's first chunk re-traced and re-compiled the decode
    machine, polluting p99_chunk_ms with compile time (code-review r5).
    `decode_updates_v1` is already routed through the module-level jit
    (`decode_kernel._decode_updates_v1_jit`, static-keyed and registered
    with the progbudget resident-program registry), so binding the
    statics with `partial` shares that cache across instances — an outer
    jit here would hold unevictable duplicate executables."""
    from ytpu.ops.decode_kernel import decode_updates_v1

    return partial(
        decode_updates_v1,
        max_rows=max_rows,
        max_dels=max_dels,
        n_steps=n_steps,
        max_sections=max_sections,
    )


def _xla_chunk_step(cols, meta, stream, rank):
    """Back-compat shim: the packed-XLA chunk step moved to
    `integrate_kernel.xla_chunk_step` so the chunked driver and this
    module share ONE compiled singleton (two copies would hold duplicate
    unevictable executables under the progbudget registry)."""
    from ytpu.ops.integrate_kernel import xla_chunk_step

    return xla_chunk_step(cols, meta, stream, rank)


class FusedReplay:
    """Chunked fused replay of one shared update stream over a doc batch.

    Capacity management now rides the shared chunked driver
    (`integrate_kernel.PackedReplayDriver`): before each chunk the driver
    checks the `CompactionPolicy` — projected worst-case growth (`margin`
    = rows·3 + delete ranges·2, `ReplayPlan.adds`) against capacity AND
    the high-watermark — compacting (`compact_packed`) and, only when
    compaction can't make room, growing (`grow_packed`). Both kernel
    lanes ("fused" Pallas / "xla" packed fallback) share the one policy;
    `sync_per_chunk=False` switches to the lazy occupancy readout (no
    device sync per chunk — chunk_seconds then measure dispatch, not
    execution).

    `overlap=True` selects the ASYNC pipelined lane (ISSUE-5): a staging
    thread preps chunk k+1 into a reusable slot while the device
    decodes+integrates chunk k as ONE fused dispatch (donated state),
    decode-error checking folds into the driver's sticky device scalar,
    and the steady-state loop performs ZERO blocking device syncs —
    errors surface at watermark drains or `finish()`, with the offending
    update re-identified host-side for the same message the serial loop
    raises. `sync_per_chunk` is ignored in overlap mode.

    Under the default `ingest="raw"` (ISSUE-7) staging is a MEMCPY: the
    host ships the chunk's raw concatenated wire bytes plus a tiny
    per-update offsets table, and the device gathers the update lanes
    and decodes the varints itself (`replay_chunk_program_raw`) — the
    per-update Python packing + its `[S, L]` padded h2d transfer are
    gone, so `depth` > 2 pipelining is essentially free.
    `ingest="packed"` keeps the PR-5 `pack_updates_into` staging
    (`replay_chunk_program`) as the host-packed fallback rung; the
    serial and checkpoint/host-oracle paths keep it unconditionally."""

    def __init__(
        self,
        n_docs: int,
        plan: ReplayPlan,
        capacity: int = 4096,
        max_capacity: int = 1 << 17,
        d_block: int = 8,
        chunk: int = 8192,
        interpret: bool = False,
        lane: str = "fused",
        policy=None,
        sync_per_chunk: bool = True,
        overlap: bool = False,
        ingest: str = "raw",
        depth: int = 2,
        checkpoint_every: int = 0,
        quarantine: bool = False,
        max_recoveries: int = 3,
        forecaster=None,
        shard_docs: bool = False,
    ):
        import jax.numpy as jnp

        from ytpu.models.batch_doc import init_state
        from ytpu.ops.integrate_kernel import pack_state

        if lane not in ("fused", "xla"):
            raise ValueError(f"lane must be 'fused' or 'xla', got {lane!r}")
        if ingest not in ("raw", "packed"):
            raise ValueError(
                f"ingest must be 'raw' or 'packed', got {ingest!r}"
            )
        if depth < 1:
            raise ValueError(f"depth must be >= 1, got {depth}")
        self.plan = plan
        self.n_docs = n_docs
        self.d_block = d_block
        self.chunk = chunk
        self.interpret = interpret
        self.lane = lane
        self.max_capacity = max_capacity
        self.policy = policy
        self.sync_per_chunk = sync_per_chunk
        self.overlap = overlap
        # raw ingest knobs (ISSUE-7): `ingest="raw"` (default) collapses
        # the async lane's host staging to a memcpy — concatenated wire
        # bytes + a per-update offsets table, lanes gathered on device by
        # `replay_chunk_program_raw`. `ingest="packed"` keeps the PR-5
        # per-update `pack_updates_into` staging (the fallback rung the
        # PR-6 ladder and the serial/checkpoint paths also keep).
        # `depth` sizes the overlap pipeline: >2 is essentially free
        # under raw staging (each extra slot is wire-bytes-sized, and
        # staging is no longer the critical path).
        self.ingest = ingest
        self.depth = depth
        # resilience knobs (ISSUE-6): `checkpoint_every` > 0 pulls a host
        # snapshot of the packed state every N chunks so a mid-replay
        # fault resumes there instead of from scratch (each snapshot is a
        # blocking d2h pull — the default 0 keeps the healthy steady
        # state zero-sync); `quarantine` records poison updates instead
        # of aborting; `max_recoveries` bounds fault-resume attempts.
        self.checkpoint_every = checkpoint_every
        self.quarantine = quarantine
        self.max_recoveries = max_recoveries
        # capacity observatory (ISSUE-18): an optional HeadroomForecaster
        # fed at every materialized ledger readout by the driver(s) this
        # replay creates — None keeps the hot path untouched
        self.forecaster = forecaster
        # doc-axis sub-batching (ISSUE-20): split each integrate dispatch
        # into pow2 doc-width slices sized by `plan_subbatches` against
        # the forecaster's budget, so the 1024-doc monolith never
        # allocates. False keeps the PR-5 single-dispatch path.
        self.shard_docs = shard_docs
        self.capacity0 = capacity
        self.cols, self.meta = pack_state(init_state(n_docs, capacity))
        self.stats = ReplayStats(capacity=capacity)
        self._hi = 0  # occupancy upper bound carried across run()/compact()
        self._jnp = jnp
        # chunk ranges dispatched through the async lane, for deferred
        # decode-error re-identification (sticky flags name no update)
        self._dispatched_ranges: List[Tuple[int, int]] = []
        self._ckpt: Optional[_ReplayCheckpoint] = None
        self._corrupted: dict = {}  # idx -> injected-corrupt wire bytes
        self._qset: set = set()  # quarantined update indices (dedup)
        self._host_text: Optional[str] = None
        self._host_doc = None  # host-oracle rung: survives across run()s
        self._host_name: Optional[str] = None
        self._recoveries_used = 0
        self._needs_restore = False
        self._resumed_ckpt: Optional[_ReplayCheckpoint] = None
        self._base_hi = 0  # occupancy carried into the CURRENT run()
        self._driver = None

    def _capacity(self) -> int:
        return self.cols.shape[2]

    def _make_driver(self, rank):
        from ytpu.ops.integrate_kernel import PackedReplayDriver

        driver = PackedReplayDriver(
            self.cols,
            self.meta,
            rank,
            d_block=self.d_block,
            interpret=self.interpret,
            lane=self.lane,
            policy=self.policy,
            unit_refs=True,
            gc_ranges=True,
            max_capacity=self.max_capacity,
            # overlap mode is the zero-sync pipeline by definition
            sync_every_chunk=self.sync_per_chunk and not self.overlap,
            initial_occupancy=self._hi,
            quarantine=self.quarantine,
            shard_docs=self.shard_docs,
        )
        driver.forecaster = self.forecaster
        return driver

    def _resolve_rank(self, client_rank):
        from ytpu.ops.decode_kernel import identity_rank

        if client_rank is None:
            # raw ids double as ranks only while they fit the identity
            # table; beyond that the YATA tie-break would silently read
            # rank 0 for every client
            if self.plan.max_client >= 256:
                raise ValueError(
                    f"stream contains client id {self.plan.max_client}; "
                    "pass an explicit client_rank table"
                )
            client_rank = identity_rank(256)
        return client_rank

    def run(self, payloads: List[bytes], client_rank=None) -> ReplayStats:
        """Replay `payloads`, surviving mid-replay faults: dispatch and
        compile failures demote the shape family down the lane-health
        ladder (fused → packed-XLA, sticky), unrecoverable faults resume
        from the last chunk-boundary checkpoint (or the initial state),
        and when even the packed-XLA rung is demoted the serial host
        oracle carries the stream to completion (docs/robustness.md)."""
        from ytpu.ops.integrate_kernel import (
            ReplayFault,
            effective_lane,
            lane_family,
        )
        from ytpu.utils.faults import FaultError

        client_rank = self._resolve_rank(client_rank)
        fam = lane_family(self.n_docs, self.d_block)
        self._recoveries_used = 0
        # per-run recovery bookkeeping: checkpoint positions and
        # corrupted-byte records index into THIS call's payload list — a
        # snapshot carried over from a previous run() would resume at
        # the wrong position in the new stream
        self._ckpt = None
        self._corrupted.clear()
        self._qset.clear()  # quarantine dedup is per-run too: index 5 of
        # THIS stream is not index 5 of the last one
        self._base_hi = self._hi
        if self._hi and self.checkpoint_every and self._host_text is None:
            # continuation replay (the state carries content from an
            # earlier run): snapshot the ENTRY state so a fault before
            # the first chunk-boundary checkpoint cannot reset to empty
            self._checkpoint_now(pos=0)
        while True:
            if (
                self._host_text is not None
                or effective_lane(fam, self.lane) == "host"
            ):
                return self._run_host(payloads)
            try:
                if self.overlap:
                    return self._run_overlap(payloads, client_rank)
                return self._run_serial(payloads, client_rank)
            except (ReplayFault, FaultError) as e:
                self._recover(e)

    def _run_serial(self, payloads: List[bytes], client_rank) -> ReplayStats:
        import jax.numpy as jnp

        from ytpu.ops.decode_kernel import FLAG_ERRORS, pack_updates

        plan = self.plan
        decode = _decoder(
            plan.max_rows, plan.max_dels, plan.max_steps, plan.max_sections
        )
        start = self._restore_state()
        driver = self._driver = self._make_driver(client_rank)
        self._post_restore(driver)
        S = len(payloads)
        pos = start
        while pos < S:
            t0 = time.perf_counter()
            end = min(pos + self.chunk, S)
            batch = self._stage_batch(payloads, pos, end)
            if len(batch) < self.chunk:
                batch = batch + [b"\x00\x00"] * (self.chunk - len(batch))
            buf, lens = pack_updates(batch, pad_to=plan.max_len + 16)
            stream, flags = decode(jnp.asarray(buf), jnp.asarray(lens))
            # rebase string refs onto global arena unit offsets
            refs_np = plan.unit_refs[pos:end]
            if refs_np.shape[0] < self.chunk:
                refs_np = np.pad(
                    refs_np,
                    ((0, self.chunk - refs_np.shape[0]), (0, 0)),
                    constant_values=-1,
                )
            refs_c = jnp.asarray(refs_np)
            stream = stream._replace(
                content_ref=jnp.where(refs_c >= 0, refs_c, stream.content_ref)
            )
            f = np.asarray(flags)[: end - pos] & FLAG_ERRORS
            if f.any():
                bad = np.nonzero(f)[0]
                if self.quarantine:
                    # the decoder zeroed the flagged lanes' valid masks,
                    # so the stream integrates them as no-ops — record
                    # and carry on (poison-update quarantine)
                    self._note_quarantined(
                        [int(pos + b) for b in bad], count_metric=True
                    )
                else:
                    raise RuntimeError(
                        f"device decode flagged updates "
                        f"{(pos + bad[:8]).tolist()}: "
                        f"flags {f[bad[:8]].tolist()}"
                    )
            # worst-case state rows this chunk can add: the driver
            # compacts/grows BEFORE integrating so ERR_CAPACITY (which
            # corrupts the tile) cannot fire mid-chunk; with
            # sync_every_chunk the post-step readout drain doubles as the
            # per-chunk latency fence
            driver.step(stream, margin=int(plan.adds[pos:end].sum()) + 8)
            self.cols, self.meta = driver.cols, driver.meta
            self.stats.chunk_seconds.append(time.perf_counter() - t0)
            pos = end
            self._maybe_checkpoint(driver, pos)
        self.cols, self.meta = driver.finish()
        self._merge_driver_stats(driver)
        self._driver = None
        return self.stats

    def _merge_driver_stats(self, driver) -> None:
        d = driver.stats
        self.stats.chunks += d.chunks
        self.stats.compactions += d.compactions
        self.stats.growths += d.growths
        self.stats.syncs += d.syncs
        self.stats.peak_blocks = max(self.stats.peak_blocks, d.peak_blocks)
        self.stats.capacity = self._capacity()
        self.stats.final_blocks = d.final_blocks
        self.stats.demotions += d.demotions
        self.stats.recoveries += d.recoveries
        self.stats.final_lane = driver.lane
        if d.scan_hist:
            self.stats.scan_hist = d.scan_hist
            self.stats.scan_max = d.scan_max
            self.stats.scan_p50 = d.scan_p50
            self.stats.scan_p99 = d.scan_p99
            self.stats.scan_tier_cheap = d.scan_tier_cheap
            self.stats.scan_tier_wide = d.scan_tier_wide
            self.stats.scan_trips_serial = d.scan_trips_serial
            self.stats.scan_trips_two_tier = d.scan_trips_two_tier
        self.stats.commit_word = d.commit_word
        # capacity ledger (ISSUE-18): freshest readout supersedes,
        # reclaimed rows accumulate across driver incarnations
        self.stats.occupied_rows = d.occupied_rows
        self.stats.dead_rows = d.dead_rows
        self.stats.dead_max = d.dead_max
        self.stats.reclaimed_rows += d.reclaimed_rows
        self.stats.compact_gap_chunks = d.compact_gap_chunks
        self.stats.subbatch_width = d.subbatch_width
        self.stats.subbatch_narrowed += d.subbatch_narrowed
        self._hi = d.final_blocks

    # ------------------------------------------- fault recovery (ISSUE-6)

    def _recover(self, e: BaseException) -> None:
        """Roll back to the last chunk-boundary checkpoint (or the
        initial state).  The sticky lane floor already records any
        demotion, so the next `run()` attempt enters with the demoted
        lane — including the host-oracle bottom rung."""
        from ytpu.utils import metrics

        if self._driver is not None:
            self._merge_driver_stats(self._driver)
            self._driver = None
        self._recoveries_used += 1
        if self._recoveries_used > self.max_recoveries:
            raise e
        if self._ckpt is None and self._base_hi:
            # continuation replay with no checkpoint (checkpoint_every=0
            # skips the entry snapshot): the scratch rebuild below would
            # silently discard everything integrated BEFORE this run() —
            # surfacing the fault is the only honest recovery
            raise e
        self.stats.recoveries += 1
        metrics.counter("replay.recoveries").inc()
        self._needs_restore = True
        self.stats.resumes.append(self._ckpt.pos if self._ckpt else 0)

    def _restore_state(self) -> int:
        """(Re)build the packed state for a fresh driver attempt; returns
        the update index to resume from (0 on the first attempt, or when
        no checkpoint was taken before the fault)."""
        self._resumed_ckpt = None
        if not self._needs_restore:
            return 0
        import jax.numpy as jnp

        from ytpu.models.batch_doc import init_state
        from ytpu.ops.integrate_kernel import pack_state

        self._needs_restore = False
        ck = self._ckpt
        if ck is None:
            self.cols, self.meta = pack_state(
                init_state(self.n_docs, self.capacity0)
            )
            self._hi = 0
            return 0
        # jnp.array COPIES: on a zero-copy backend jnp.asarray would
        # alias the checkpoint's numpy memory, and the next donation
        # would corrupt the checkpoint for any second resume
        self.cols = jnp.array(ck.cols)
        self.meta = jnp.array(ck.meta)
        self._hi = ck.hi
        self._resumed_ckpt = ck
        return ck.pos

    def _post_restore(self, driver) -> None:
        """A checkpoint taken under the fused kernel carries a stale
        origin_slot plane; rebuild it before the first packed-XLA chunk
        of a demoted resume (including a pos=0 entry-state resume)."""
        ck = self._resumed_ckpt
        if ck is not None and ck.lane == "fused" and driver.lane != "fused":
            driver._refresh_origin_slot_packed()

    def _checkpoint_now(self, pos: int, driver=None) -> None:
        """Snapshot the packed state as host numpy copies (they survive
        donation and simulated worker death).  With a driver, drain its
        readouts first so errors/quarantine surface before the snapshot
        can be trusted; without one, snapshot this object's carried
        state (the run()-entry snapshot of a continuation replay)."""
        from ytpu.utils.phases import phases

        if driver is not None:
            hi = driver._drain_readouts()
            cols, meta, lane = driver.cols, driver.meta, driver.lane
        else:
            hi, cols, meta = self._hi, self.cols, self.meta
            lane = self.stats.final_lane or self.lane
        cols_np = np.array(cols)
        meta_np = np.array(meta)
        self._ckpt = _ReplayCheckpoint(
            cols=cols_np, meta=meta_np, pos=pos, hi=hi, lane=lane
        )
        self.stats.checkpoints += 1
        if phases.enabled:
            phases.transfer(
                "replay.checkpoint", cols_np.nbytes + meta_np.nbytes, "d2h"
            )

    def _maybe_checkpoint(self, driver, pos: int) -> None:
        if (
            not self.checkpoint_every
            or driver.stats.chunks % self.checkpoint_every
        ):
            return
        self._checkpoint_now(pos, driver=driver)

    def _stage_batch(self, payloads: List[bytes], pos: int, end: int):
        """One chunk's wire payloads, through the `update.corrupt`
        injection site.  Injected corruption is remembered per index so
        deferred re-identification and checkpoint re-runs see the SAME
        bytes the device integrated."""
        from ytpu.utils.faults import faults

        if not faults.active and not self._corrupted:
            return payloads[pos:end]
        batch = list(payloads[pos:end])
        for i in range(len(batch)):
            idx = pos + i
            prev = self._corrupted.get(idx)
            if prev is not None:
                batch[i] = prev
                continue
            if faults.active:
                c = faults.corrupt("update.corrupt", batch[i])
                if c is not batch[i]:
                    self._corrupted[idx] = c
                    batch[i] = c
        return batch

    def _note_quarantined(self, idxs: List[int], count_metric: bool):
        newly = [i for i in idxs if i not in self._qset]
        self._qset.update(newly)
        self.stats.quarantined.extend(newly)
        if newly and count_metric:
            from ytpu.utils import metrics

            metrics.counter("replay.quarantined").inc(len(newly))
        return newly

    def _flagged_chunks(self, payloads: List[bytes]):
        """Re-decode the dispatched chunk ranges against the bytes the
        device actually saw (injected corruption included, not the
        caller's clean payloads); yields (pos, bad_offsets, flags) for
        every chunk carrying ≥1 FLAG_ERRORS lane.  Shared by the
        deferred error-message path and the quarantine path so the
        padding/substitution contract cannot silently diverge."""
        import jax.numpy as jnp

        from ytpu.ops.decode_kernel import FLAG_ERRORS, pack_updates

        plan = self.plan
        decode = _decoder(
            plan.max_rows, plan.max_dels, plan.max_steps, plan.max_sections
        )
        for pos, end in self._dispatched_ranges:
            batch = [
                self._corrupted.get(i, payloads[i]) for i in range(pos, end)
            ]
            if len(batch) < self.chunk:
                batch = batch + [b"\x00\x00"] * (self.chunk - len(batch))
            buf, lens = pack_updates(batch, pad_to=plan.max_len + 16)
            _, flags = decode(jnp.asarray(buf), jnp.asarray(lens))
            f = np.asarray(flags)[: end - pos] & FLAG_ERRORS
            if f.any():
                yield pos, np.nonzero(f)[0], f

    def _quarantine_collect(self, payloads: List[bytes], flags_or: int):
        """Driver quarantine hook (async lane): re-decode the dispatched
        ranges host-side and record every newly flagged update index —
        the device already integrated flagged lanes as no-ops, so
        recording IS the recovery.  The driver counts the metric."""
        idxs = [
            int(pos + b)
            for pos, bad, _ in self._flagged_chunks(payloads)
            for b in bad
        ]
        self._dispatched_ranges.clear()
        return self._note_quarantined(idxs, count_metric=False)

    @staticmethod
    def _root_name(payloads: List[bytes]) -> Optional[str]:
        """The stream's wire root name, or None when no named root
        appears (the host-oracle rung needs it to read the final text
        back).  Uses the native columnar prescan, falling back to the
        host decoder where the native library is absent — the degraded
        hosts most likely to reach the host rung must not silently
        default to the wrong root."""
        from ytpu.native import decode_update_columns

        for p in payloads:
            cols = decode_update_columns(p)
            if cols is not None and not cols.error:
                for i in range(cols.n_blocks):
                    n = cols.parent_name(i)
                    if n:
                        return n
                continue
            from ytpu.core.update import Update

            try:
                up = Update.decode_v1(p)
            except Exception:
                continue
            for blocks in up.blocks.values():
                for b in blocks:
                    n = getattr(b, "parent", None)
                    if isinstance(n, str) and n:
                        return n
        return None

    def _run_host(self, payloads: List[bytes]) -> ReplayStats:
        """The ladder's bottom rung: the serial host oracle replays the
        stream on ONE host doc (the stream is broadcast to every slot, so
        one doc IS every slot's content) and `get_string` serves its text
        afterwards.  Slow, but alive — the rung's contract is survival,
        not throughput.  The doc persists across run()s so continuation
        replays keep accumulating; a DEMOTION to this rung mid-way
        through a continuation sequence (packed content exists but no
        host doc does) is refused rather than silently dropped."""
        from ytpu.core import Doc

        if self._host_doc is None:
            if self._base_hi:
                raise RuntimeError(
                    "host-oracle rung cannot serve a continuation replay:"
                    " the packed state carries content integrated before"
                    " this run() and there is no host doc to continue"
                    " from — re-run the full stream on a fresh replay"
                )
            self._host_doc = Doc()
        doc = self._host_doc
        name = self._root_name(payloads) or self._host_name or "text"
        self._host_name = name
        bad: List[int] = []
        for i, p in enumerate(payloads):
            p = self._corrupted.get(i, p)
            try:
                doc.apply_update_v1(p)
            except Exception:
                if not self.quarantine:
                    raise
                bad.append(i)
        self._note_quarantined(bad, count_metric=True)
        self._host_text = doc.get_text(name).get_string()
        self.stats.final_lane = "host"
        return self.stats

    # ------------------------------------------------ async overlap lane

    def overlap_plan(self, n_updates: Optional[int] = None) -> OverlapPlan:
        """The static staging plan the async lane will execute (dry-run
        assertion surface) — `depth` slots, depth > 2 supported (and
        essentially free under raw ingest)."""
        return plan_overlap(
            self.plan.n_updates if n_updates is None else n_updates,
            self.chunk,
            depth=self.depth,
        )

    def _build_wire(self, payloads: List[bytes]):
        """The raw lane's per-run wire table — one C-speed join + cumsum
        over the CALLER'S payloads (never the plan's: run() may replay a
        mutated list, e.g. the deferred-error tests). When corruption
        faults are armed (or were injected on an earlier attempt) the
        table is built from the corrupted batch so the device integrates
        the SAME bytes the fault path re-identifies against — the
        `update.corrupt` site fires here once per update, in stream
        order, exactly like the per-chunk packed staging does."""
        from ytpu.utils.faults import faults

        t0 = time.perf_counter()
        if faults.active or self._corrupted:
            batch = self._stage_batch(payloads, 0, len(payloads))
        else:
            batch = payloads
        wire, offsets = build_wire_table(batch)
        self.stats.prescan_s += time.perf_counter() - t0
        return wire, offsets

    def _run_overlap(self, payloads: List[bytes], client_rank) -> ReplayStats:
        """ISSUE-5/7 tentpole loop: staging thread preps chunk k+1 into a
        reusable slot while the device runs chunk k through the fused
        decode→rebase→integrate program; ZERO blocking device syncs in
        steady state (readouts stay futures until a watermark drain or
        `finish()`). Under the default `ingest="raw"` the staging work
        is a memcpy — slice-copy the chunk's concatenated wire bytes +
        offset/length tables into a plain byte buffer — and the device
        gathers the update lanes itself (`replay_chunk_program_raw`);
        `ingest="packed"` keeps the PR-5 per-update `pack_updates_into`
        packing as the host-packed fallback rung."""
        import jax.numpy as jnp  # noqa: F401 — device runtime must be up

        from ytpu.ops.decode_kernel import pack_updates_into
        from ytpu.utils.phases import phases

        plan = self.plan
        S = len(payloads)
        chunk = self.chunk
        width = plan.max_len + 16  # == the serial loop's pad_to
        dims = (plan.max_rows, plan.max_dels, plan.max_steps,
                plan.max_sections)
        use_raw = self.ingest == "raw"
        start = self._restore_state()
        driver = self._driver = self._make_driver(client_rank)
        self._post_restore(driver)
        # fresh per run(): the error path re-decodes these ranges against
        # THIS run's payloads; carried-over ranges would index stale data
        # (and N-fold the rescan on continuation replays)
        self._dispatched_ranges = []
        driver.on_decode_error = partial(
            self._reidentify_decode_error, payloads
        )
        driver.on_quarantine = partial(self._quarantine_collect, payloads)
        oplan = self.overlap_plan(S)
        pipe = OverlapPipeline(depth=oplan.depth, stage_prefix="replay")
        if use_raw:
            wire, woffs = self._build_wire(payloads)
            cap = raw_chunk_cap(woffs, chunk)  # one O(S) scan, not per slot
            slots = [
                _RawStagingSlot(cap, chunk, plan.unit_refs.shape[1])
                for _ in range(oplan.buffers)
            ]
        else:
            slots = [
                _StagingSlot(chunk, width, plan.unit_refs.shape[1])
                for _ in range(oplan.buffers)
            ]
        free_q: "queue.Queue" = queue.Queue()
        for s in slots:
            free_q.put(s)
        inflight: deque = deque()
        acquisitions = 0
        staged_bytes = 0

        # request-tracing hand-off (ISSUE-11): the staging generator runs
        # on the engine's worker thread where the caller's ContextVar
        # context is invisible — capture the ambient trace id HERE and
        # let each staged slot carry it to the dispatch span
        from ytpu.utils.trace import current_trace_id, tracer

        ambient_trace = current_trace_id()

        def produce():
            nonlocal acquisitions, staged_bytes
            from ytpu.ops.decode_kernel import pack_raw_updates_into

            for pos in range(start, S, chunk):
                while True:
                    try:
                        slot = free_q.get(timeout=0.1)
                        break
                    except queue.Empty:
                        # a dead consumer never frees slots — bail so the
                        # engine's join() can't hang on this generator
                        if pipe.stopping:
                            return
                end = min(pos + chunk, S)
                with tracer.span(
                    "replay.stage_slot",
                    first=pos,
                    last=end - 1,
                    trace=ambient_trace,
                ):
                    if use_raw:
                        staged_bytes += pack_raw_updates_into(
                            wire, woffs, pos, end,
                            slot.raw, slot.offs, slot.lens, width=width,
                        )
                    else:
                        batch = self._stage_batch(payloads, pos, end)
                        pack_updates_into(batch, slot.buf, slot.lens)
                        staged_bytes += sum(len(p) for p in batch)
                    slot.refs[: end - pos] = plan.unit_refs[pos:end]
                    slot.refs[end - pos :] = -1
                    slot.pos, slot.end = pos, end
                    slot.trace = ambient_trace
                acquisitions += 1
                yield slot

        def consume(slot):
            t0 = time.perf_counter()
            margin = int(plan.adds[slot.pos : slot.end].sum()) + 8
            with tracer.span(
                "replay.dispatch_slot",
                first=slot.pos,
                last=slot.end - 1,
                trace=slot.trace,
            ):
                if use_raw:
                    inputs = driver.step_raw(
                        slot.raw, slot.offs, slot.lens, slot.refs, dims,
                        width, margin=margin,
                    )
                else:
                    inputs = driver.step_bytes(
                        slot.buf, slot.lens, slot.refs, dims, margin=margin
                    )
            self._dispatched_ranges.append((slot.pos, slot.end))
            self.cols, self.meta = driver.cols, driver.meta
            inflight.append((slot, inputs))
            if len(inflight) >= oplan.depth:
                # depth cap: before a slot is re-packed its previous h2d
                # transfer must have completed. Waiting on an INPUT array
                # is transfer-completion only, not a result sync.
                old_slot, old_inputs = inflight.popleft()
                for a in old_inputs:
                    a.block_until_ready()
                free_q.put(old_slot)
            self.stats.chunk_seconds.append(time.perf_counter() - t0)
            self._maybe_checkpoint(driver, slot.end)

        ostats = pipe.run(produce(), consume)
        while inflight:
            slot, inputs = inflight.popleft()
            for a in inputs:
                a.block_until_ready()
            free_q.put(slot)
        self.cols, self.meta = driver.finish()
        self._merge_driver_stats(driver)
        self._driver = None
        self.stats.stage_s += ostats.stage_s
        self.stats.stall_s += ostats.stall_s
        self.stats.overlap_ratio = ostats.overlap_ratio
        self.stats.max_inflight = max(self.stats.max_inflight, ostats.max_depth)
        self.stats.buffer_reuses += max(0, acquisitions - len(slots))
        self.stats.ingest = "raw" if use_raw else "packed"
        self.stats.stage_bytes += staged_bytes
        if phases.enabled:
            phases.add_value("replay.stage_bytes", staged_bytes)
            if ostats.stage_s > 0:
                phases.set_value(
                    "replay.stage_bytes_per_s",
                    staged_bytes / ostats.stage_s,
                )
        return self.stats

    def _reidentify_decode_error(self, payloads: List[bytes], flags_or: int):
        """Deferred decode-error trip: the sticky device scalar says SOME
        chunk since driver start carried FLAG_ERRORS lanes — re-decode
        the dispatched ranges synchronously (error path, perf
        irrelevant) and raise the SAME message the serial loop produces
        at the offending chunk."""
        for pos, bad, f in self._flagged_chunks(payloads):
            raise RuntimeError(
                f"device decode flagged updates "
                f"{(pos + bad[:8]).tolist()}: flags {f[bad[:8]].tolist()}"
            )
        raise RuntimeError(
            f"device decode flagged errors (sticky flags {flags_or}) but "
            "the host re-scan found none — payloads mutated mid-replay?"
        )

    def compact(self) -> int:
        """Force a commit-style compaction; returns the high-water block
        count afterwards."""
        from ytpu.ops.compaction import compact_packed
        from ytpu.ops.integrate_kernel import M_NBLOCKS

        self.cols, self.meta = compact_packed(
            self.cols, self.meta, unit_refs=True, gc_ranges=True
        )
        self.stats.compactions += 1
        self._hi = int(np.asarray(self.meta)[:, M_NBLOCKS].max())
        return self._hi

    def get_string(self, doc: int) -> str:
        """Final text of one doc slot (host walk over the readback rows;
        after a host-oracle demotion, the oracle's text serves every
        slot — the stream is broadcast, so all slots are identical)."""
        if self._host_text is not None:
            return self._host_text
        from ytpu.ops.integrate_kernel import (
            CN,
            DL,
            LN,
            M_NBLOCKS,
            M_START,
            OF,
            RF,
            RT,
        )

        cols = np.asarray(self.cols[:, doc, :])
        meta = np.asarray(self.meta[doc])
        view = UnitArenaView(self.plan.unit_byte, self.plan.arena)
        out: List[str] = []
        i = int(meta[M_START])
        hops = 0
        limit = int(meta[M_NBLOCKS]) + 2
        while i >= 0 and hops <= limit:
            if cols[DL, i] == 0 and cols[CN, i] == 1 and cols[RF, i] >= 0:
                out.append(
                    view.slice_text(
                        int(cols[RF, i]), int(cols[OF, i]), int(cols[LN, i])
                    )
                )
            i = int(cols[RT, i])
            hops += 1
        if hops > limit:
            raise RuntimeError("cycle in sequence links")
        return "".join(out)
