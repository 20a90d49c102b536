"""Batched ingestion with exact pending-update semantics.

The reference stashes an update whose dependencies are unmet and retries it
when the missing clocks arrive (transaction.rs:675-727, update.rs:289-299
PendingUpdate; pending delete-sets store.rs:42-50). `BatchIngestor` lifts
that contract to the batch engine — the SURVEY §7 hard-part "a doc whose
update goes pending must not stall its batch":

- per doc slot, a host-side `StateVector` mirror tracks exactly what the
  device holds (rows are planned host-side, so the mirror is exact);
- each incoming update is partitioned against the mirror
  (`BatchEncoder.partition_carriers`): the applicable prefix ships in this
  step's batch, the remainder is stashed per doc;
- delete ranges beyond the mirror stash into a per-doc pending delete set;
- a stash moves only when its own room gets an update: `_plan_doc` merges
  the room's stash with the arrival (`_merge_with_stash`), partitions the
  whole again and stashes what still waits, so blocks integrate in the
  step that brings their last dependency — a room without an arrival
  plans nothing, its stash stays as it is (its mirror only advances
  through its own updates), other doc slots in the batch are never
  stalled, and the device never sees a missing-dep row
  (`ERR_MISSING_DEP` stays 0 by construction). While a room holds a
  stash every update of it takes the host lane (`ingest.slow.pending`);
  the step after the stash empties it is back on the fast lane;
- what waits is counted (`_count_stash`): `ingest.stash_updates` (updates
  of which a block or a delete range went into a stash),
  `ingest.stash_released` (those wholly integrated since),
  `ingest.stash_wait_steps` (summed over the released: served steps of
  their room from the one that stashed them to the one that released
  them), `ingest.stash_rooms` (summed over steps: rooms holding a stash
  as the step plans), and the span `ingest.plan.stash` around
  `_plan_doc` for a host-lane room of a served step that holds a stash
  or whose update the prescan found waiting for another (the re-merge,
  the partition, the deferred-delete split, the counts, and the rows of
  what is released).
"""

from __future__ import annotations

import weakref
from collections import OrderedDict
from itertools import islice
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ytpu.core import Update
from ytpu.core.id_set import DeleteSet
from ytpu.core.state_vector import StateVector
from ytpu.models.batch_doc import (
    BatchEncoder,
    DocStateBatch,
    PackedBatch,
    UpdateBatch,
    apply_update_batch_in_place,
    ensure_origin_slot,
    init_state,
)
from ytpu.ops.decode_kernel import (
    LANE_AT,
    LANE_BASE,
    LANE_FIELDS,
    LANE_LEN,
    LANE_OFFSET,
    LANE_PREFIX,
    LANE_ROOT_HASH,
    ChunkedWirePayloads,
    gather_raw_lanes,
    steps_for_columns,
)
from ytpu.parallel.mesh import (
    batch_sharding,
    replicated,
    require_doc_mesh,
    shard_docs_put,
    state_shards,
)

__all__ = ["BatchIngestor"]


def _sorted_table(mapping: Dict[int, int], width: int):
    """(sorted keys, value perm) as host i32 arrays of `width` entries —
    the shape every device lookup table (clients, key hashes, client
    hashes) shares; `BatchIngestor._cached_table` uploads them when they
    change. The mapping's entries sit at the end, behind padding whose
    key is -1: no client id, key hash or client hash is negative, and the
    decoder's lookups (`decode_kernel._resolve_and_pack`) take a hit only
    for a value that is not, so the padding answers to nothing and the
    programs that take the table compile once a `width`, not once a
    writer."""
    n = len(mapping)
    ks = np.full(width, -1, np.int32)
    vs = np.zeros(width, np.int32)
    keys = np.fromiter(mapping, np.int32, n)
    order = np.argsort(keys)  # a dict's keys are distinct: one order
    ks[width - n :] = keys[order]
    vs[width - n :] = np.fromiter(mapping.values(), np.int32, n)[order]
    return ks, vs

# content kinds the device decoder handles: GC, Deleted, Json, Binary,
# String, Embed, Format, Type (non-weak), Any(scalar), Skip, Move
_FAST_KINDS = frozenset((0, 1, 2, 3, 4, 5, 6, 7, 8, 10, 11))
# kinds whose rows keep content refs into the retained wire bytes
_WIRE_REF_KINDS = frozenset((2, 3, 4, 5, 6, 7, 8))
_I32_MAX = 2**31 - 1
# why the prescan sent a payload to the host lane (`_slow_reason`), one
# counter each: `ingest.slow.<reason>`
_SLOW_REASONS = (
    "pending", "root", "sections", "complex_any", "kind", "key",
    "dependency", "client",
)


# --- when a room is compacted (`BatchIngestor._make_room`) -----------------
# The server's own constants, as `_table_floor` is: a deployment passes none.
#: free rows a room keeps, as a share of its capacity: a room that has
#: fewer when a step brings it an update is squashed, collected and
#: defragmented first (256 of 4,096: under the 440 the fullest room of the
#: text cells keeps after a whole pool, PERF.md section 4)
COMPACT_RESERVE_SHARE = 16
#: hysteresis: a room whose compacted form sits inside the reserve is
#: compacted again only once it has grown by this share of its capacity
#: (128 rows of 4,096), or when its update might not fit at all
COMPACT_REGROW_SHARE = 32
#: rooms a call of `compact_rooms` gathers: one program whatever is due.
#: The chip's time for a call goes with the rooms it gathers (65 ms at 16
#: rooms of which one was due: PERF.md section 6, PR 43); at one room XLA
#: makes the gather a dynamic slice, which a doc-sharded state answers by
#: gathering whole planes (`compact_rooms`' docstring)
COMPACT_ROOMS_PER_CALL = 2
#: rows a planned row or a delete range can add at most: itself and a
#: split at either anchor; a split at either edge
#: (`batch_doc.stream_worst_case_adds`, the one accounting)
ROWS_PER_ROW, ROWS_PER_DEL = 3, 2


# columns of `PackedBatch.rows` the merge reads: the planes' order
_REF = UpdateBatch._fields.index("content_ref")
_VALID = UpdateBatch._fields.index("valid")


def pack_lane_table(lens, root_hash, at, prefix, base: int) -> np.ndarray:
    """The fast lanes' columns of a step as one host array, ``[LANE_FIELDS,
    S]`` little-endian i32 (`decode_kernel.LANE_*` names the rows): each
    lane's length, its primary root's hash, its row of the step's batch
    (`_step_rows`), where its bytes start in the chunk the step retained,
    and that chunk's `base`, the same in every column. The lanes' bytes
    lie end to end in the wire arena, so `LANE_OFFSET` follows from the
    lengths."""
    table = np.empty((LANE_FIELDS, len(lens)), dtype="<i4")
    table[LANE_OFFSET, 0] = 0
    np.cumsum(lens[:-1], out=table[LANE_OFFSET, 1:])
    table[LANE_LEN] = lens
    table[LANE_ROOT_HASH] = root_hash
    table[LANE_AT] = at
    table[LANE_PREFIX] = prefix
    table[LANE_BASE] = base
    return table


def pack_manifest(payloads, table: np.ndarray, active=None) -> np.ndarray:
    """A merging step's one upload: the wire arena (the fast lanes' bytes
    end to end, zero-padded to `_bucket(len, 256)`: the programs that take
    it specialize on its length), then `table` (`pack_lane_table`), then the
    step's `active` slots ([K] i32; a dense step has none), the int32
    words as little-endian bytes. `gather_manifest_lanes` takes it apart
    on the device."""
    flat = b"".join(payloads)
    arena = _bucket(len(flat), 256)
    n_words = table.size + (0 if active is None else len(active))
    manifest = np.zeros(arena + 4 * n_words, dtype=np.uint8)
    manifest[: len(flat)] = np.frombuffer(flat, dtype=np.uint8)
    words = manifest[arena:].view("<i4")  # the arena is a whole number of words
    words[: table.size] = table.ravel()
    if active is not None:
        words[table.size :] = active
    return manifest


def gather_manifest_lanes(manifest, lanes: int, width: int, step_width: int):
    """The step's first program: the manifest (`pack_manifest`, u8) taken
    apart inside the traced body. Hands back the padded ``[lanes, width]``
    lane matrix (`gather_raw_lanes` over the arena), the ``[LANE_FIELDS,
    lanes]`` i32 lane table, and the step's `active` (``[step_width]``
    i32, None where `step_width` is 0: the dense step). The statics say
    where the arena ends; with the manifest's length they are what keyed
    the lanes' gather before (S, the wire bucket, L) and, in a compact
    step, the step's width. An int32 word is put together from its four
    bytes by shifts, so no byte order but the manifest's own is assumed."""
    n_words = LANE_FIELDS * lanes + step_width
    arena = manifest.shape[0] - 4 * n_words
    quads = manifest[arena:].reshape(n_words, 4).astype(jnp.int32)
    words = (
        quads[:, 0] | quads[:, 1] << 8 | quads[:, 2] << 16 | quads[:, 3] << 24
    )
    table = words[: LANE_FIELDS * lanes].reshape(LANE_FIELDS, lanes)
    matrix = gather_raw_lanes(
        manifest[:arena], table[LANE_OFFSET], table[LANE_LEN], width
    )
    active = words[LANE_FIELDS * lanes :] if step_width else None
    return matrix, table, active


@jax.named_scope("merge_stream")
def merge_stream(batch, stream, table, width: int) -> PackedBatch:
    """The fast lanes' decoded `stream` ([S, ...]) laid over the host
    lane's `batch` ([W, ...], the step's width) at the rows the lane
    `table` gives (its row `LANE_AT`, [S] i32: where each lane's slot sits
    in the step, `_step_rows`). Both arrive as a `PackedBatch` (the host's
    upload or the kept batch, the served decoder's output) and one leaves:
    two scatters on the room axis over the two arrays, two output buffers,
    and the planes are never made (`content_ref` and `valid` are two
    columns of `rows`).

    String rows leave the decoder with refs into the padded lane matrix
    (``s * width + start``); the step retained only the string-bearing
    lanes' bytes, trimmed and concatenated, so each ref is rebased onto
    that chunk: lane s's bytes start at the table's ``LANE_PREFIX`` of the
    chunk at its ``LANE_BASE``, and a wire ref is stored as ``-2 - ref``.
    The table differs every step and is a device array the step's first
    program made (`gather_manifest_lanes`): an operand, never a static,
    and nothing that rides up with the call."""
    rows, dels = stream
    idx, prefix, base = table[LANE_AT], table[LANE_PREFIX], table[LANE_BASE, 0]
    ref = rows[..., _REF]
    lane = jnp.arange(idx.shape[0], dtype=jnp.int32)[:, None]
    compact_ref = prefix[:, None] + (ref - lane * width)
    is_str_ref = (rows[..., _VALID] != 0) & (ref >= 0)
    rows = rows.at[..., _REF].set(
        jnp.where(is_str_ref, -2 - base - compact_ref, ref)
    )
    return PackedBatch(
        batch.rows.at[idx].set(rows), batch.dels.at[idx].set(dels)
    )


# The merge's device-side glue is two programs of its own, not eager ops:
# eagerly one plane's `at[idx].set` is about a dozen tiny programs, each
# ~180 us of host time on the chip's host (PERF.md §6, PR 27). The first
# is the only one the wire bucket keys: it is cheap to build (PERF.md §5),
# the decoder and the integrate program are not.
_merge_stream_jit = jax.jit(merge_stream, static_argnames=("width",))
_gather_manifest_jit = jax.jit(
    gather_manifest_lanes, static_argnames=("lanes", "width", "step_width")
)


def _register_programs():
    from ytpu.utils import progbudget

    progbudget.register("merge_stream", _merge_stream_jit)
    progbudget.register("gather_manifest_lanes", _gather_manifest_jit)


_register_programs()


def _bucket(n: int, lo: int = 4) -> int:
    """Round a jit-static dimension up to a power of two (floor `lo`).

    Serving streams vary per step (payload length, row/delete counts,
    decode budget); compiling the decode/integrate programs for the exact
    per-step shape retraces almost every step. Bucketing caps the set of
    compiled programs at a handful per dimension."""
    b = lo
    while b < n:
        b *= 2
    return b


class BatchIngestor:
    def __init__(
        self,
        n_docs: int,
        capacity: int,
        enc: Optional[BatchEncoder] = None,
        ingest: str = "raw",
        shard_docs: bool = False,
    ):
        if ingest not in ("raw", "packed"):
            raise ValueError(f"ingest must be 'raw' or 'packed', got {ingest!r}")
        self.enc = enc or BatchEncoder()
        self.n_docs = n_docs
        #: doc-axis sharding (ISSUE-20): place the batched state so its
        #: doc axis spans the batch mesh (`ytpu.parallel.mesh`). A no-op
        #: off the chip with one device visible, so CPU behavior is
        #: byte-identical; refused (`require_doc_mesh`) where it would be
        #: a silent no-op on the chip or on a room count the mesh does
        #: not divide. What the state does span is the gauge
        #: `ingest.state_shards`, worked out when it is read
        self.shard_docs = bool(shard_docs)
        #: fast-lane wire shipping (ISSUE-9 satellite, ROADMAP item 2):
        #: ``"raw"`` (default) ships the eligible docs' updates as ONE
        #: flat concatenated byte arena + a tiny offsets table and
        #: materializes the padded lane matrix ON DEVICE
        #: (`decode_kernel.gather_raw_lanes` — h2d shrinks from padded
        #: S·L to the actual wire bytes); ``"packed"`` keeps the
        #: host-padded `pack_updates` matrix.  The gather zero-masks
        #: past each lane's length, so the two paths feed the decoder
        #: BYTE-IDENTICAL matrices — parity is structural
        #: (tests/test_serving_soak.py asserts it end to end).
        self.ingest = ingest
        self.state: DocStateBatch = init_state(n_docs, capacity)
        # where a step's uploads go (`_upload`): None = the default device
        self._by_doc = self._on_every_chip = None
        if self.shard_docs:
            mesh = require_doc_mesh(n_docs)
            if mesh is not None:
                self.state = jax.tree.map(
                    lambda a: shard_docs_put(a, mesh), self.state
                )
                self._by_doc = batch_sharding(mesh)
                self._on_every_chip = replicated(mesh)
        self.svs: List[StateVector] = [StateVector() for _ in range(n_docs)]
        # per-doc stash: carriers waiting for dependencies + deferred deletes
        self._pending: List[Dict[int, list]] = [{} for _ in range(n_docs)]
        self._pending_ds: List[DeleteSet] = [DeleteSet() for _ in range(n_docs)]
        # fast-lane payload resolution: PayloadStore refs (>= 0) for host-
        # planned rows + retained wire chunks (<= -2) for device-decoded rows
        self.payloads = ChunkedWirePayloads(self.enc.payloads)
        # fast-lane stats (observability; tests assert the lane actually ran)
        self.fast_docs = 0
        self.slow_docs = 0
        self.fast_recoveries = 0  # flagged fast lanes replayed via host lane
        self._bind_counters()
        from ytpu.utils import metrics

        # of the newest ingestor; a weak reference, so the registry keeps
        # no state alive
        me = weakref.ref(self)

        def shards() -> int:
            ing = me()
            return 0 if ing is None else state_shards(ing.state)

        metrics.gauge("ingest.state_shards").set_function(shards)
        self._last_fast_flags: Optional[np.ndarray] = None
        self._reset_tables()
        self._reset_rows()
        # multi-root docs (doc.rs:156-228): the first named root seen per
        # doc maps onto the implicit device branch; others anchor through
        # BLOCK_ROOT_ANCHOR rows created before the apply
        self.primary_roots: Dict[int, str] = {}
        self._anchored_roots: List[set] = [set() for _ in range(n_docs)]

    def _bind_counters(self) -> None:
        """Process-wide mirrors of the lane stats (cached metric objects:
        O(1) increments, no per-step lookups — SURVEY §5.5); a restored
        ingestor (`checkpoint.load_ingestor`) binds the same."""
        from ytpu.utils import metrics

        self._m_fast = metrics.counter("ingest.fast_docs")
        self._m_slow = metrics.counter("ingest.slow_docs")
        self._m_recoveries = metrics.counter("ingest.fast_recoveries")
        # which integrate step an `apply_bytes` call took (`_active_slots`)
        self._m_compact = metrics.counter("ingest.compact_steps")
        self._m_dense = metrics.counter("ingest.dense_steps")
        # what a step's lookup tables cost it (`_cached_table`)
        self._m_table_reuses = metrics.counter("ingest.table_reuses")
        self._m_table_builds = metrics.counter("ingest.table_builds")
        self._m_table_grows = metrics.counter("ingest.table_grows")
        # whether a step built the host lane's planes (`_kept_batch`)
        self._m_batch_reuses = metrics.counter("ingest.batch_reuses")
        self._m_batch_builds = metrics.counter("ingest.batch_builds")
        # writers the tables had not held (`_note_clients`)
        self._m_first_seen = metrics.counter("ingest.clients_first_seen")
        self._m_first_seen_big = metrics.counter(
            "ingest.clients_first_seen_big"
        )
        # why a payload took the host lane (`_host_lane_reason`), and the
        # rows the host lane planned for the step (`_plan_doc`)
        self._m_slow_reason = {
            r: metrics.counter("ingest.slow." + r) for r in _SLOW_REASONS
        }
        self._m_host_rows = metrics.counter("ingest.host_rows")
        # output buffers of the programs a step enqueued (`_count_outputs`)
        self._m_enqueue_outputs = metrics.counter("ingest.enqueue_outputs")
        # host arrays a step sent to the device(s) (`_upload`)
        self._m_step_uploads = metrics.counter("ingest.step_uploads")
        # payloads a step's prescan planned, and those of them whose
        # native columns came with them, decoded where the update arrived
        # (`apply_bytes`' `columns`)
        self._m_prescan_payloads = metrics.counter("ingest.prescan_payloads")
        self._m_prescan_carried = metrics.counter("ingest.prescan_carried")
        # calls that replaced the state (integrate, compaction), and those
        # of them that consumed the buffers handed in (`_count_state_step`)
        self._m_state_steps = metrics.counter("ingest.state_steps")
        self._m_state_in_place = metrics.counter("ingest.state_in_place")
        # compaction on the served path (`_compact`): rooms compacted, the
        # rows they held before and what that freed; bounds made exact by
        # a read of `n_blocks` (`_recount`); rooms whose update might not
        # fit although they were compacted
        self._m_compactions = metrics.counter("ingest.room_compactions")
        self._m_rows_before = metrics.counter("ingest.rows_before_compaction")
        self._m_rows_reclaimed = metrics.counter("ingest.rows_reclaimed")
        self._m_recounts = metrics.counter("ingest.row_recounts")
        self._m_refusals = metrics.counter("ingest.capacity_refusals")
        # the stash (`_count_stash`): updates that went into one, those
        # wholly integrated since, the served steps of their room they
        # waited, and, a step, the rooms that hold one
        self._m_stash_updates = metrics.counter("ingest.stash_updates")
        self._m_stash_released = metrics.counter("ingest.stash_released")
        self._m_stash_wait = metrics.counter("ingest.stash_wait_steps")
        self._m_stash_rooms = metrics.counter("ingest.stash_rooms")
        # slot -> its stashed updates, one `[steps waited, needs]` each,
        # `needs` the (client, clock) ends the room's mirror must reach:
        # what the four counts are kept from, and nothing else reads it (a
        # restored stash has no tickets: its release is not counted)
        self._stash_tickets: Dict[int, list] = {}

    def _reset_tables(self) -> None:
        """The device lookup tables' sources, empty, and nothing built of
        them yet; a restored ingestor (`checkpoint.load_ingestor`) starts
        from the same and registers its interners' keys and clients."""
        #: entries every device lookup table starts with (a power of two).
        #: A server is not told who will write: a Yjs client draws its id
        #: when its document is made, and the first the server hears of it
        #: is its first update. So the tables' shape is the server's own
        #: choice, four writers a room and 1,024 at least, and a table
        #: that outgrows it doubles (`_cached_table`): the decode and
        #: integrate programs specialize on the shape, not on the writers
        self._table_floor = _bucket(4 * self.n_docs, 1024)
        # device key hashing (map rows on the fast lane): hash -> key idx;
        # keys whose hash collides with a different key take the host lane
        self._key_hashes: Dict[int, int] = {}
        self._key_collisions: set = set()
        # device big-client hashing (ids beyond i32): varint-byte hash ->
        # interned idx; colliding ids take the host lane
        self._client_hashes: Dict[int, int] = {}
        self._client_id_collisions: set = set()
        # one count a dict, bumped where an entry comes or goes
        self._key_gen = self._client_hash_gen = 0
        # table name -> (its source's stamp at the build, device arrays)
        self._table_cache: Dict[str, tuple] = {}
        # table name -> its entries, padding included, once past the floor
        self._table_width: Dict[str, int] = {}
        # (width, n_rows, n_dels) -> the host lane's empty batch on the
        # device(s), least recently used first (`_kept_batch`)
        self._batch_cache: "OrderedDict[Tuple[int, int, int], PackedBatch]" = (
            OrderedDict()
        )
        # the interner as `_note_clients` last saw it: how many clients,
        # and how many of them the raw table holds. What it holds already
        # (a restored checkpoint's writers) is known, not first seen
        known = self.enc.interner.from_idx
        self._clients_seen = len(known)
        self._raw_clients = sum(1 for c in known if c <= _I32_MAX)
        # a key's or root name's device hash, worked out once
        self._name_hashes: Dict[str, int] = {}

    def _reset_rows(self, n_blocks=None) -> None:
        """The host's count of every slot's rows: an upper bound that a
        step raises by what its update can add at most (`ROWS_PER_ROW`,
        `ROWS_PER_DEL`) and no step reads from the device. `n_blocks`
        ([n_docs], a restored state's) makes it exact; so does `_recount`
        when the bound says a room nears its capacity, and `_compact` for
        the rooms it compacted."""
        self._rows_bound = (
            np.zeros(self.n_docs, np.int64)
            if n_blocks is None
            else np.asarray(n_blocks, dtype=np.int64).copy()
        )
        # rows a room held after its last compaction (0: never compacted)
        self._rows_compacted_to = np.zeros(self.n_docs, np.int64)
        # rooms counted in `ingest.capacity_refusals`, once each
        self._refused: set = set()

    def _key_hash(self, key: str) -> int:
        h = self._name_hashes.get(key)
        if h is None:
            from ytpu.ops.decode_kernel import key_hash_host

            h = self._name_hashes[key] = key_hash_host(key.encode("utf-8"))
        return h

    def _cached_table(self, name: str, stage: str, stamp, size: int, build):
        """Lookup table `name` on the device(s) the state lives on: the
        arrays of its last build while `stamp`, which says what it was
        built from, is that build's; else `build(width)` (host arrays of
        `width` entries, `size` of them real), uploaded whole on every
        chip and counted against `stage`.

        The tables hold every interned client or key, not a step's, and a
        server interns when a client or a key is first seen, not per
        keystroke. The programs that take them only read them (the
        integrate step donates the state, operand 0, and nothing else), so
        one upload serves every step until its source changes. A first-seen
        writer changes what a table holds and not its shape: `width` is
        `_table_floor` until `size` passes it and doubles then, a counted
        and spanned event (`ingest.table_grows`, `ingest.table_grow`)
        after which the programs that take the table compile anew."""
        from ytpu.utils.phases import NULL_SPAN, phases

        hit = self._table_cache.get(name)
        if hit is not None and hit[0] == stamp:
            took = self._m_table_reuses
            dev = hit[1]
        else:
            took = self._m_table_builds
            width = self._table_width.get(name, self._table_floor)
            span = NULL_SPAN
            if size > width:
                width = self._table_width[name] = _bucket(size, width)
                self._m_table_grows.inc()
                phases.add_value(self._m_table_grows.name, 1)
                span = phases.span("ingest.table_grow")
            with span:
                host = build(width)
                dev = self._upload(host)
            self._table_cache[name] = (stamp, dev)
            if phases.enabled:
                phases.transfer(stage, self._uploaded_bytes(host), "h2d")
        took.inc()
        phases.add_value(took.name, 1)  # the recorder's: a window's delta
        return dev

    def _kept_batch(self, bucket: Tuple[int, int, int]) -> Optional[PackedBatch]:
        """The host lane's empty batch of this bucket, if a step left it
        on the device(s): what `batch_packed` pads to when no slot plans a
        row, a constant of `(width, n_rows, n_dels)`, the step's width
        among them (`_active_slots`). `merge_stream` donates no operand
        and the integrate step only the state (`_integrate`), so one upload
        serves every step without a host-lane room until it is evicted
        (`_keep_batch`)."""
        kept = self._batch_cache.get(bucket)
        if kept is not None:
            self._batch_cache.move_to_end(bucket)
        return kept

    def _keep_batch(self, bucket: Tuple[int, int, int], batch: PackedBatch) -> None:
        """Keep an empty batch a step has just uploaded. The entries hold
        at most 1/16 of the state's resident bytes between them, least
        recently used out; one that alone passes that (a dense step's
        wide buckets) is not kept, and its step builds it again."""
        def nbytes(tree) -> int:
            return sum(a.nbytes for a in jax.tree.leaves(tree))

        room = nbytes(self.state) // 16 - nbytes(batch)
        if room < 0:
            return
        kept = self._batch_cache
        while nbytes(list(kept.values())) > room:
            kept.popitem(last=False)
        kept[bucket] = batch

    def _note_clients(self) -> None:
        """Count the writers interned since the last look: one count a
        writer, in the step that brought it, before the step's tables are
        looked up (`ingest.clients_first_seen`, and `_big` for an id past
        int32, which resolves through the hash table). The interner only
        appends and others intern into it too (the encoder's host lane, a
        driver that preregisters its sessions): what it has grown by,
        read here, is every path's."""
        from ytpu.utils.phases import phases

        from_idx = self.enc.interner.from_idx
        new = from_idx[self._clients_seen :]
        if not new:
            return
        self._clients_seen = len(from_idx)
        big = sum(1 for c in new if c > _I32_MAX)
        self._raw_clients += len(new) - big
        self._m_first_seen.inc(len(new))
        phases.add_value(self._m_first_seen.name, len(new))
        if big:
            self._m_first_seen_big.inc(big)
            phases.add_value(self._m_first_seen_big.name, big)

    def _upload(self, host, by_doc: bool = False):
        """A tree of host arrays onto the device(s) the state lives on.

        One chip: `jnp.asarray`, leaf by leaf. A doc-sharded state: one
        `device_put` straight onto the mesh — by room where the leading
        axis is the room axis (`by_doc`), else whole on every chip — so
        the step's four programs all run where the state is and nothing
        of a step sits on the first chip alone. Left there, jax carries
        each plane across inside the integrate call, a slicing program
        and a copy a chip: 32 ms of a 127 ms step on four v5e chips
        (PERF.md §6, PR 28).

        Every host array a step sends goes through here, and none rides
        up with a jitted call as a numpy operand: `ingest.step_uploads`
        counts one a leaf. A transfer costs the host about what six
        output buffers do, whatever its bytes (PERF.md section 6, PR 44)."""
        self._tally(self._m_step_uploads, len(jax.tree.leaves(host)))
        if self._on_every_chip is None:
            return jax.tree.map(jnp.asarray, host)
        return jax.device_put(
            host, self._by_doc if by_doc else self._on_every_chip
        )

    def _uploaded_bytes(self, host, by_doc: bool = False) -> int:
        """Bytes `_upload(host, by_doc)` sends: a whole copy a chip unless
        the tree is laid out by room."""
        copies = (
            1 if by_doc or self._on_every_chip is None
            else len(self._on_every_chip.device_set)
        )
        return copies * sum(a.nbytes for a in jax.tree.leaves(host))

    def _batch(self, all_rows, all_dels, by_doc: bool = True) -> PackedBatch:
        """`BatchEncoder.batch_packed` where the state is: the two packed
        arrays uploaded (`_upload`), as the integrate program takes them."""
        return self._upload(self.enc.batch_packed(all_rows, all_dels), by_doc)

    def _count_outputs(self, *outs) -> None:
        """`ingest.enqueue_outputs`: one count an output buffer (a leaf of
        what the call returned) of the programs a step enqueued. What an
        enqueue costs the host goes with them (PERF.md section 6, PR 42):
        37 a compact step that merges (the gather's 3: the lane matrix,
        the lane table, `active`; the decoder's 3, the merge's 2, the
        state's 29), an array over several chips one."""
        self._tally(self._m_enqueue_outputs, len(jax.tree.leaves(outs)))

    def _decode_tables(self) -> dict:
        """`decode_updates_v1`'s tables of every interned client, key and
        big client, each on the device since the step that last changed
        its source: a writer past int32 leaves the raw table as it is, a
        small one the hash table. The two hash dicts lose an entry on a
        collision, so their stamp is a count of changes, not a length."""
        stage = "ingest.merge.tables"
        self._note_clients()
        return dict(
            client_table=self._cached_table(
                "client_table", stage, self._raw_clients, self._raw_clients,
                self._client_table,
            ),
            key_table=self._cached_table(
                "key_table", stage, self._key_gen, len(self._key_hashes),
                self._key_table,
            ),
            client_hash_table=self._cached_table(
                "client_hash_table", stage, self._client_hash_gen,
                len(self._client_hashes), self._client_hash_table,
            ),
        )

    def _client_rank(self):
        """The rank table of every interned client. The interner only
        appends, and others intern into it too (the encoder's host lane,
        a server preregistering its sessions): its length, read here, at
        the point of use, says whether the table still holds."""
        interner = self.enc.interner
        self._note_clients()
        return self._cached_table(
            "client_rank", "ingest.rank_table", len(interner), len(interner),
            interner.rank_table_host,
        )

    def _active_slots(self, live: List[int]) -> Optional[np.ndarray]:
        """`apply_update_batch`'s `active` for a step in which only the
        slots `live` carry rows: `live` padded with idle slots (distinct,
        in range: their batch rows are all invalid, so the step is the
        identity on them) up to a power of two, floor 16, and sorted; or
        None, the dense step, once that is over a quarter of the slots
        (the prefill's all-room dispatches, bulk loads, small batches).
        Decided by the count of rooms that carry an update, by nothing
        else; every tick of at most 16 rooms is one program family."""
        width = _bucket(len(live), 16)
        if width > self.n_docs // 4:
            return None
        taken = set(live)
        idle = (d for d in range(self.n_docs) if d not in taken)
        pad = list(islice(idle, width - len(live)))
        return np.sort(np.asarray(live + pad, dtype=np.int32))

    def _step_rows(self, active: Optional[np.ndarray], slots) -> np.ndarray:
        """The row of the step's batch each of `slots` has: the slot
        itself in a dense step, its place in `active` (sorted, and it
        holds every slot with a payload) in a compact one. The batch is
        as wide as the step in both, so one rule lays out both lanes."""
        slots = np.asarray(slots, dtype=np.int32)
        if active is None:
            return slots
        return np.searchsorted(active, slots).astype(np.int32)

    def _planned_batch(self, active: Optional[np.ndarray], planned: dict):
        """(all_rows, all_dels) of a batch as wide as the step, from
        `planned`: slot -> `_plan_doc`'s (rows, dels). Every other row of
        the batch is padding."""
        width = self.n_docs if active is None else len(active)
        all_rows, all_dels = [[]] * width, [[]] * width
        for at, (rows, dels) in zip(
            self._step_rows(active, list(planned)), planned.values()
        ):
            all_rows[at], all_dels[at] = rows, dels
        return all_rows, all_dels

    def reset_slot(self, doc: int) -> None:
        """Return a doc slot to its empty state (start/-1, zero blocks,
        clear error, empty SV and pending stashes). Block columns stay —
        they are masked by n_blocks — so the reset is O(1) metadata. Used
        when a tenant leaves its slot (e.g. multi-root demotion) so the
        slot can serve a new tenant without leaking capacity."""
        st = self.state
        self.state = st._replace(
            start=st.start.at[doc].set(-1),
            n_blocks=st.n_blocks.at[doc].set(0),
            error=st.error.at[doc].set(0),
        )
        self.svs[doc] = StateVector()
        self._pending[doc] = {}
        self._pending_ds[doc] = DeleteSet()
        self._stash_tickets.pop(doc, None)
        self.primary_roots.pop(doc, None)
        self._anchored_roots[doc] = set()
        self._rows_bound[doc] = self._rows_compacted_to[doc] = 0
        self._refused.discard(doc)

    # --- introspection (parity: ytransaction_pending_update/_ds shape) -------

    def pending_update(self, doc: int) -> Optional[Update]:
        blocks = self._pending[doc]
        if not blocks:
            return None
        return Update({c: list(q) for c, q in blocks.items()}, DeleteSet())

    def pending_ds(self, doc: int) -> Optional[DeleteSet]:
        ds = self._pending_ds[doc]
        return None if ds.is_empty() else ds

    def capacity_ledger(self):
        """Per-slot occupancy/fragmentation view (ISSUE-18): numpy
        ``(live, dead, free)`` row counts, each ``[n_docs]``, summing
        to the slot capacity per doc. One scrape-time device pull
        (`state_capacity_ledger`) — never called from the ingest hot
        path."""
        import numpy as np

        from ytpu.models.batch_doc import state_capacity_ledger

        live, dead = state_capacity_ledger(self.state)
        live = np.asarray(live)
        dead = np.asarray(dead)
        cap = int(self.state.blocks.client.shape[-1])
        return live, dead, cap - live - dead

    # --- ingestion -------------------------------------------------------------

    def _merge_with_stash(self, doc: int, incoming: Optional[Update]) -> Update:
        blocks: Dict[int, list] = {
            c: list(q) for c, q in self._pending[doc].items()
        }
        ds = DeleteSet({c: list(rs) for c, rs in self._pending_ds[doc].clients.items()})
        if incoming is not None:
            for c, q in incoming.blocks.items():
                blocks.setdefault(c, []).extend(q)
            for c, ranges in incoming.delete_set.clients.items():
                for s, e in ranges:
                    ds.insert_range(c, s, e)
        sv = self.svs[doc]
        for c in blocks:
            blocks[c].sort(key=lambda carrier: carrier.id.clock)
            # redelivery dedup: drop exact re-sends (same start clock; the
            # device's offset check handles partial overlaps) and carriers
            # already fully covered by the mirror
            seen = set()
            kept = []
            for carrier in blocks[c]:
                if carrier.id.clock in seen:
                    continue
                if carrier.id.clock + carrier.len <= sv.get(c):
                    continue
                seen.add(carrier.id.clock)
                kept.append(carrier)
            blocks[c] = kept
        blocks = {c: q for c, q in blocks.items() if q}
        self._pending[doc] = {}
        self._pending_ds[doc] = DeleteSet()
        return Update(blocks, ds)

    def _plan_doc(self, doc: int, incoming: Optional[Update]) -> Tuple[list, list]:
        """(rows, dels) applicable now; the rest returns to the stash,
        counted (`_count_stash`) where there was or is one."""
        if incoming is None:
            # a stuck stash cannot progress without new data for this doc:
            # its mirror SV only advances through its own incoming updates
            return [], []
        merged = self._merge_with_stash(doc, incoming)
        self._register_roots_from_update(doc, merged)
        sv = self.svs[doc]
        applicable, leftover = self.enc.partition_carriers(merged, sv)
        for carrier in applicable:
            sv.set_max(carrier.id.client, carrier.id.clock + carrier.len)
        for carrier in leftover:
            self._pending[doc].setdefault(carrier.id.client, []).append(carrier)

        dels: list = []
        for client, ranges in merged.delete_set.clients.items():
            covered = sv.get(client)
            c = self.enc.interner.intern(client)
            for start, end in ranges:
                if end <= covered:
                    dels.append((c, start, end))
                elif start >= covered:
                    self._pending_ds[doc].insert_range(client, start, end)
                else:  # split: tombstone what exists, defer the tail
                    dels.append((c, start, covered))
                    self._pending_ds[doc].insert_range(client, covered, end)
        if (
            doc in self._stash_tickets
            or self._pending[doc]
            or not self._pending_ds[doc].is_empty()
        ):
            self._count_stash(doc, incoming)
        return (
            self.enc.rows_from_carriers(
                applicable, primary_root=self.primary_roots.get(doc)
            ),
            dels,
        )

    def _count_stash(self, doc: int, incoming: Update) -> None:
        """Keep the stash's counters for a room that has just planned
        `incoming` (`_plan_doc`): every update that waited there has
        waited one more served step of its room, and is released if the
        mirror now covers all it brought; `incoming` waits from now on if
        the mirror does not cover a block or a delete range of it."""
        sv = self.svs[doc]
        waiting = []
        for ticket in self._stash_tickets.get(doc, ()):
            ticket[0] += 1
            if all(sv.get(c) >= end for c, end in ticket[1]):
                self._tally(self._m_stash_released)
                self._tally(self._m_stash_wait, ticket[0])
            else:
                waiting.append(ticket)
        needs = [
            (c.id.client, c.id.clock + c.len)
            for q in incoming.blocks.values()
            for c in q
            if not c.is_skip
        ]
        needs += [
            (client, end)
            for client, ranges in incoming.delete_set.clients.items()
            for _, end in ranges
        ]
        needs = [(c, end) for c, end in needs if sv.get(c) < end]
        if needs:
            waiting.append([0, needs])
            self._tally(self._m_stash_updates)
        if waiting:
            self._stash_tickets[doc] = waiting
        else:
            self._stash_tickets.pop(doc, None)

    def apply(
        self, payloads: List[Optional[bytes]], v2: bool = False
    ) -> DocStateBatch:
        """One batched step: per-doc update payloads (None = no-op slot)."""
        updates = [
            None
            if p is None
            else (Update.decode_v2(p) if v2 else Update.decode_v1(p))
            for p in payloads
        ]
        if len(updates) != self.n_docs:
            raise ValueError(f"expected {self.n_docs} payload slots")
        from ytpu.utils.progbudget import tick

        tick()
        all_rows, all_dels = [], []
        adds: Dict[int, int] = {}
        for d, u in enumerate(updates):
            rows, dels = self._plan_doc(d, u)
            all_rows.append(rows)
            all_dels.append(dels)
            if rows or dels:
                adds[d] = ROWS_PER_ROW * len(rows) + ROWS_PER_DEL * len(dels)
        self._make_room(adds)
        self._integrate(self._batch(all_rows, all_dels), self._client_rank())
        return self.state

    # --- raw-bytes fast lane ---------------------------------------------------

    def _fast_eligible(self, doc: int, cols) -> bool:
        """Can this update's wire bytes go straight to the device?"""
        return self._host_lane_reason(doc, cols) is None

    def _host_lane_reason(self, doc: int, cols) -> Optional[str]:
        """`_slow_reason`, counted: where the update's wire bytes cannot
        go straight to the device, the first reason found
        (`ingest.slow.<reason>`); a payload that can counts nothing."""
        reason = self._slow_reason(doc, cols)
        if reason is not None:
            from ytpu.utils.phases import phases

            took = self._m_slow_reason[reason]
            took.inc()
            phases.add_value(took.name, 1)  # the recorder's: a window's delta
        return reason

    def _slow_reason(self, doc: int, cols) -> Optional[str]:
        """Why this update's wire bytes cannot go straight to the device
        (one of `_SLOW_REASONS`), or None where they can.

        The native columns (C++ `lib0_codec`) are the control plane: they
        prove, before anything ships, that integrating the blocks in wire
        order needs no stash/retry and no host-only feature — so the device
        decode cannot flag and the device integrate cannot miss a
        dependency (the exactness the slow lane gets from
        `partition_carriers`). The reasons: `pending` (the prescan could
        not read the payload, or the room has a stash), `root` (a root
        name past the hash window or colliding), `sections` (more
        sections than the decode budget bounds), `complex_any` (an Any
        value with an object or array inside), `kind` (ContentDoc, a
        WeakRef branch, an unknown type, an unreadable move), `key` (a map
        key past the hash window or colliding), `client` (an id past int32
        whose hash collides, or a clock past int32), `dependency` (a clock
        gap, or an origin, parent, move bound or delete not yet covered)."""
        if cols.error or self._pending[doc] or not self._pending_ds[doc].is_empty():
            return "pending"
        # named roots: record primaries, create anchors for the rest; any
        # un-hashable/colliding root name routes the doc to the host lane
        # (anchors created here are needed either way — both lanes
        # integrate on device)
        if not self._register_roots_from_cols(doc, cols):
            return "root"
        # Degenerate-but-legal wire shapes (many client sections holding only
        # covered Skip runs, many empty ds-client sections) are correct on
        # the fast lane only if the decode budget covers them; bound the
        # blow-up so one doc can't balloon the whole step's T.
        if cols.n_client_sections > cols.n_blocks + 16:
            return "sections"
        if cols.n_ds_sections > cols.n_dels + 16:
            return "sections"
        n = cols.n_blocks
        sv = self.svs[doc]
        covered: Dict[int, int] = {}

        def cov(c: int) -> int:
            return covered.get(c, sv.get(c))

        def uncovered(client: int, clock: int) -> Optional[str]:
            """Why the id cannot be named on the device yet, if it cannot."""
            if not self._client_ok(client):
                return "client"
            return "dependency" if clock >= cov(client) else None

        if cols.n_complex_any > 0:
            return "complex_any"  # recursive Any values: host lane
        from ytpu.ops.decode_kernel import KEY_HASH_BYTES

        for i in range(n):
            kind = int(cols.kind[i])
            if kind not in _FAST_KINDS:
                return "kind"
            if kind == 7:
                # ContentType rides the wire lane except WeakRef branches
                # (host-resolved link sources) and unknown TypeRef tags
                span = cols.content_bytes(i)
                if not span or span[0] >= 7:
                    return "kind"
            if kind == 11:
                # ContentMove: the range-bound ids must already be covered
                # (the claim walk resolves them by id; an unresolved bound
                # sets ERR_MISSING_DEP and poisons the step)
                from ytpu.encoding.lib0 import Cursor, EncodingError

                cur = Cursor(bytes(cols.content_bytes(i)))
                try:
                    flags = cur.read_var_uint()
                    bounds = [(cur.read_var_uint(), cur.read_var_uint())]
                    if not flags & 1:
                        bounds.append(
                            (cur.read_var_uint(), cur.read_var_uint())
                        )
                except EncodingError:
                    return "kind"  # truncated span: host lane decides
                for bc, bk in bounds:
                    why = uncovered(bc, bk)
                    if why:
                        return why
            psl = int(cols.parent_sub_len[i])
            if psl > KEY_HASH_BYTES:
                return "key"  # key exceeds the device hash window
            if psl >= 0:
                key = cols.parent_sub(i)
                if not self._register_key(key):
                    return "key"  # hash collision: host lane
            if int(cols.parent_kind[i]) == 2:
                # nested-branch parent: the ContentType item must already
                # be covered (the device resolves it by id)
                why = uncovered(
                    int(cols.parent_id_client[i]), int(cols.parent_id_clock[i])
                )
                if why:
                    return why
            c = int(cols.client[i])
            ck = int(cols.clock[i])
            ln = int(cols.length[i])
            if not self._client_ok(c) or ck + ln > _I32_MAX:
                return "client"
            if ck > cov(c):
                return "dependency"  # clock gap → pending semantics needed
            if kind != 10:  # Skip advances no state
                ok = int(cols.origin_clock[i])
                if ok >= 0:
                    why = uncovered(int(cols.origin_client[i]), ok)
                    if why:
                        return why
                rk = int(cols.ror_clock[i])
                if rk >= 0:
                    why = uncovered(int(cols.ror_client[i]), rk)
                    if why:
                        return why
                covered[c] = max(cov(c), ck + ln)
        for i in range(cols.n_dels):
            c = int(cols.del_client[i])
            if not self._client_ok(c):
                return "client"
            if int(cols.del_end[i]) > cov(c):
                return "dependency"
        return None

    def _client_ok(self, client: int) -> bool:
        """Small ids ride raw; ids beyond i32 (real Yjs clients) must
        resolve through the device hash table — register, reject on
        collision (host lane)."""
        if client <= _I32_MAX:
            return True
        return self._register_big_client(client)

    def _register_big_client(self, client: int) -> bool:
        from ytpu.ops.decode_kernel import client_hash_host

        if client in self._client_id_collisions:
            return False
        idx = self.enc.interner.intern(client)
        h = client_hash_host(client)
        prev = self._client_hashes.get(h)
        if prev is not None and prev != idx:
            self._client_id_collisions.add(client)
            self._client_id_collisions.add(self.enc.interner.from_idx[prev])
            del self._client_hashes[h]
            self._client_hash_gen += 1
            return False
        if prev is None:
            self._client_hashes[h] = idx
            self._client_hash_gen += 1
        return True

    def _client_hash_table(self, width: int):
        """Device big-client table: (sorted varint-byte hashes, interned
        idx perm)."""
        return _sorted_table(self._client_hashes, width)

    def _register_key(self, key: str) -> bool:
        """Intern `key` and record its device hash; False on collision."""
        if key in self._key_collisions:
            return False
        kid = self.enc.keys.intern(key)
        h = self._key_hash(key)
        prev = self._key_hashes.get(h)
        if prev is not None and prev != kid:
            # two distinct keys share a hash: neither may use the device
            # table (the resolution would be ambiguous)
            self._key_collisions.add(key)
            self._key_collisions.add(self.enc.keys.names[prev])
            del self._key_hashes[h]
            self._key_gen += 1
            return False
        if prev is None:
            self._key_hashes[h] = kid
            self._key_gen += 1
        return True

    def _key_table(self, width: int):
        """Device key table: (sorted hashes, interned key idx perm)."""
        return _sorted_table(self._key_hashes, width)

    def _ensure_anchor(self, doc: int, name: str) -> None:
        """Create doc's BLOCK_ROOT_ANCHOR row for a non-primary named root
        (idempotent; the integrate path resolves anchors but never creates
        them). A doc at block capacity does NOT mark the root anchored —
        the next update retries after compaction frees slots, instead of
        wedging every future row of that root as a missing dep."""
        if name in self._anchored_roots[doc]:
            return
        from ytpu.models.batch_doc import ensure_root_anchor

        if int(np.asarray(self.state.n_blocks[doc])) >= int(
            self.state.blocks.client.shape[-1]
        ):
            return  # full: leave unanchored; rows stash + retry
        kid = self.enc.keys.intern(name)
        self.state = ensure_root_anchor(self.state, doc, kid)
        self._rows_bound[doc] += 1
        self._anchored_roots[doc].add(name)

    def _register_roots_from_cols(self, doc: int, cols) -> bool:
        """Record named roots from the wire prescan; False -> host lane.

        The first named root a doc ever mentions becomes its primary
        (mapped onto the implicit device branch); later names anchor
        through BLOCK_ROOT_ANCHOR rows. Names beyond the device hash
        window, or whose hash collides in the key table, are host-lane
        work."""
        from ytpu.ops.decode_kernel import KEY_HASH_BYTES

        ok = True
        for i in range(cols.n_blocks):
            if int(cols.parent_kind[i]) != 1:
                continue
            name = cols.parent_name(i)
            prim = self.primary_roots.setdefault(doc, name)
            if len(name.encode("utf-8")) > KEY_HASH_BYTES:
                ok = False  # device can't hash this name (compare/resolve)
                continue
            if name == prim:
                # register the PRIMARY's hash too: a later root whose hash
                # collides with it would otherwise silently alias onto the
                # primary branch on device (the unguarded collision
                # channel; key-vs-key and client-id collisions already
                # route to the host lane)
                if not self._register_key(name):
                    ok = False
                continue
            if not self._register_key(name):
                ok = False
                continue
            self._ensure_anchor(doc, name)
        return ok

    def _register_roots_from_update(self, doc: int, update) -> None:
        """Host-lane root registration: primaries + anchors from a decoded
        Update (no hash-window limits — the host encodes names directly).
        The primary's DEVICE hash registers here too: a later fast-lane
        root whose hash collides with it must hit the collision guard and
        route to the host, never silently alias onto the primary branch."""
        for blocks in update.blocks.values():
            for b in blocks:
                p = getattr(b, "parent", None)
                if isinstance(p, str):
                    prim = self.primary_roots.setdefault(doc, p)
                    if p == prim:
                        self._register_key(p)  # collision guard; result
                        # re-checked per fast update in _register_roots_from_cols
                    else:
                        self._ensure_anchor(doc, p)

    def _client_table(self, width: int):
        """Device intern table: (sorted raw ids, perm to interned idx).

        Ids above int32 (half of the uint32 ids Yjs draws, and the 53-bit
        ones of older clients) are excluded here — they resolve through
        the varint-byte hash table instead (`_client_hash_table`)."""
        return _sorted_table(
            {
                c: i
                for c, i in self.enc.interner.to_idx.items()
                if 0 <= c <= _I32_MAX
            },
            width,
        )

    # --- compaction on the served path ----------------------------------------

    def _make_room(self, adds: Dict[int, int]) -> None:
        """Before a step integrates: `adds` is slot -> the rows its update
        can add at most. A room that is due (below) is compacted first
        (`_compact`), decided from the host's bound alone:
        a step reads nothing from the device. Only where the bound says a
        room is due is it made exact (`_recount`: one read of `n_blocks`,
        4 B a room), because it rises by the worst case a row and a room
        is compacted for what it holds, not for what it might. Then every
        slot's bound rises by its `adds`.

        The policy, constants of this module: a room is due when it holds
        more than `capacity - capacity // COMPACT_RESERVE_SHARE` rows (it
        is inside its reserve), or when its update might not fit
        (`rows + adds > capacity`: a bulk load's worst case is far above
        what it adds, so `adds` does not count against the reserve); one
        whose compacted form already sits inside the reserve is due again
        only once it has grown by `capacity // COMPACT_REGROW_SHARE` rows
        since, or when the update might not fit; one that has not grown
        since it was compacted is left alone, and if its update might not
        fit it is counted (`ingest.capacity_refusals`) and the device sets
        `ERR_CAPACITY` should it not: a room whose squashed document does
        not fit needs a larger slot (ROADMAP Reach A2)."""
        if not adds:
            return
        from ytpu.utils.phases import phases

        bound, floor = self._rows_bound, self._rows_compacted_to
        cap = int(self.state.blocks.client.shape[-1])
        reserve = cap // COMPACT_RESERVE_SHARE
        regrow = cap // COMPACT_REGROW_SHARE

        def due(d: int) -> bool:
            fits = bound[d] + adds[d] <= cap
            if bound[d] <= cap - reserve and fits:
                return False
            if floor[d] and bound[d] <= floor[d]:
                return False  # it has not grown since it was compacted
            return not (floor[d] and bound[d] <= floor[d] + regrow and fits)

        rooms = [d for d in adds if due(d)]
        if rooms:
            with phases.span("ingest.recount"):
                self._recount()
            rooms = [d for d in rooms if due(d)]
        if rooms:
            with phases.span("ingest.compact"):
                self._compact(rooms)
        for d, a in adds.items():
            if bound[d] + a > cap and d not in self._refused:
                # compacted or not worth compacting, and it might not fit
                self._refused.add(d)
                self._tally(self._m_refusals)
            bound[d] += a

    @staticmethod
    def _tally(counter, n: int = 1) -> None:
        """Count `n` on a process-wide counter and on the phase recorder's
        copy of it (stage value: what a window's delta is read from)."""
        from ytpu.utils.phases import phases

        counter.inc(n)
        phases.add_value(counter.name, n)

    def _count_state_step(self, old) -> None:
        """Count a call that replaced the state, `old` the tree it was
        handed (the integrate step, `compact_rooms`: both donate it):
        `ingest.state_steps`, and `ingest.state_in_place` where every
        buffer handed in was consumed (a host attribute of the arrays, no
        sync). A donation jax finds no output for only warns and copies
        as before; the two counts then part.

        The ingestor is the state's one owner: it rebinds `self.state` to
        the call's result at once, and whoever took the tree before the
        call (`ingestor.state`, a leaf of it) holds deleted arrays after."""
        self._tally(self._m_state_steps)
        if all(a.is_deleted() for a in jax.tree.leaves(old)):
            self._tally(self._m_state_in_place)

    def _integrate(self, batch, client_rank, active=None) -> None:
        """The integrate step on the state where it is
        (`apply_update_batch_in_place`): `batch`, `client_rank` and
        `active` are read and may be handed to a later step again."""
        old = ensure_origin_slot(self.state)
        self.state = apply_update_batch_in_place(old, batch, client_rank, active)
        self._count_state_step(old)

    def _recount(self) -> None:
        """Make every slot's bound exact: one read of `n_blocks`."""
        from ytpu.utils.phases import phases

        n = np.asarray(self.state.n_blocks)
        self._rows_bound[:] = n
        self._tally(self._m_recounts)
        if phases.enabled:
            phases.transfer("ingest.recount", n.nbytes, "d2h")

    def _compact(self, rooms: List[int]) -> None:
        """Squash, collect and defragment the slots `rooms` on the
        device(s), `COMPACT_ROOMS_PER_CALL` a call
        (`ops.compaction.compact_rooms`: gather, compact, scatter back; a
        lone room brings an idle slot along behind a mask), give the
        squashed runs their content (`_rehome`) and make the rooms' bounds
        exact from the `n_blocks` the program returns.

        Host stages, leaves of `ingest.compact`, once a call: `.select`
        (the call's slots), `.h2d`, `.enqueue` (one program), `.d2h` (the
        counts and the report: the wait for the program is here),
        `.rehome`."""
        from ytpu.ops.compaction import compact_rooms
        from ytpu.utils.phases import phases

        rooms = sorted(rooms)
        taken = set(rooms)
        for i in range(0, len(rooms), COMPACT_ROOMS_PER_CALL):
            with phases.span("ingest.compact.select"):
                due = rooms[i : i + COMPACT_ROOMS_PER_CALL]
                # a call short of rooms brings idle slots along, masked out
                idle = (d for d in range(self.n_docs) if d not in taken)
                pad = list(islice(idle, COMPACT_ROOMS_PER_CALL - len(due)))
                called = np.sort(np.asarray(due + pad, dtype=np.int32))
                mask = np.isin(called, due)
                host = (called, mask, np.int32(len(self.enc.payloads.items)))
            with phases.span("ingest.compact.h2d"):
                operands = self._upload(host)
                if phases.enabled:
                    phases.transfer(
                        "ingest.compact.h2d", self._uploaded_bytes(host), "h2d"
                    )
            with phases.span("ingest.compact.enqueue"):
                old = ensure_origin_slot(self.state)
                out = compact_rooms(old, *operands)
                self.state = out[0]
                self._count_state_step(old)
                self._count_outputs(out)
            with phases.span("ingest.compact.d2h"):
                n_before, n_after, report, n_chains = jax.device_get(out[1:])
                if phases.enabled:
                    phases.transfer(
                        "ingest.compact.d2h",
                        n_before.nbytes + n_after.nbytes + report.nbytes
                        + n_chains.nbytes,
                        "d2h",
                    )
            with phases.span("ingest.compact.rehome"):
                self._rehome(report[mask], int(n_chains.sum()))
            slots = called[mask]
            n_before, n_after = n_before[mask], n_after[mask]
            self._rows_bound[slots] = self._rows_compacted_to[slots] = n_after
            self._tally(self._m_compactions, len(slots))
            self._tally(self._m_rows_before, int(n_before.sum()))
            self._tally(self._m_rows_reclaimed, int((n_before - n_after).sum()))

    def _rehome(self, report: np.ndarray, n_chains: int) -> None:
        """Append the `n_chains` payloads `compact_rooms` numbered: the
        content of every squashed run whose rows read different payloads,
        assembled from `report` (`ops.compaction.REHOME_FIELDS`, one row
        an old row of a compacted room). A chain's members come in clock
        order and each sits `chain_off` units into the run, so a string
        run is one UTF-16 buffer filled member by member; a keystroke's
        member (one ASCII unit straight off the wire) is filled without a
        Python loop. The head row already reads the new payload from
        offset 0: nothing goes back to the device."""
        store = self.enc.payloads
        if not n_chains:
            return
        from ytpu.core.content import CONTENT_STRING
        from ytpu.models.batch_doc import _wire_concat
        from ytpu.ops.decode_kernel import utf8_slice_u16

        rows = report.reshape(-1, report.shape[-1])
        rows = rows[rows[:, 0] >= 0]
        rows = rows[np.lexsort((rows[:, 1], rows[:, 0]))]
        chain, at, kind, ref, off, length = rows.T
        first = np.flatnonzero(np.r_[True, chain[1:] != chain[:-1]])
        if len(first) != n_chains:
            raise RuntimeError(
                f"compact_rooms numbered {n_chains} re-homed chains, its "
                f"report holds {len(first)}"
            )
        last = np.r_[first[1:], len(chain)] - 1
        units = at[last] + length[last]  # a chain's extent in clock units
        base = np.cumsum(units) - units  # where its units start in `text`
        text = np.zeros(int(units.sum()), dtype=np.uint16)
        where = base[np.cumsum(np.r_[True, chain[1:] != chain[:-1]]) - 1] + at
        wire = _wire_concat(self.payloads)
        is_str = kind == CONTENT_STRING
        # one ASCII unit off the wire: the byte is the unit
        start = np.where(ref <= -2, -(ref + 2), 0)
        key = is_str & (ref <= -2) & (length == 1) & (off == 0)
        key[key] = wire[start[key]] < 0x80
        text[where[key]] = wire[start[key]]
        for i in np.flatnonzero(is_str & ~key):
            s = (
                utf8_slice_u16(wire, start[i], int(off[i]), int(length[i]))
                if ref[i] <= -2
                else store.slice_text(int(ref[i]), int(off[i]), int(length[i]))
            )
            got = np.frombuffer(
                s.encode("utf-16-le", "surrogatepass"), dtype=np.uint16
            )
            text[where[i] : where[i] + len(got)] = got
        for c, (lo, hi) in enumerate(zip(first, last + 1)):
            if is_str[lo]:
                payload = text[base[c] : base[c] + units[c]].tobytes()
            else:  # Any values, member by member
                payload = []
                for i in range(lo, hi):
                    payload += self.payloads.slice_values(
                        int(ref[i]), int(off[i]), int(length[i])
                    )
            store.add(int(kind[lo]), payload)

    def apply_bytes(
        self, payloads: List[Optional[bytes]], columns: Optional[list] = None
    ) -> DocStateBatch:
        """One batched step straight from V1 wire bytes.

        `columns`, where the caller has decoded a payload already (the
        server does, where an update arrives: `_note_roots`), is a list
        as long as `payloads` of each payload's native columns
        (`ytpu.native.decode_update_columns(p)`, a pure function of the
        bytes), None where a slot has none: the prescan walks those and
        decodes the rest itself. What it decides from them it decides
        here, as the step plans.

        Eligible docs (no stash, in-order, device-decodable content) ship
        raw bytes to HBM and decode on device; the rest take the exact
        host lane (`_plan_doc`). Both lanes merge into one
        `apply_update_batch` dispatch, so mixed batches cost one step.

        The step is as wide as the rooms that carry a payload
        (`_active_slots`), and so is the batch both lanes meet in: a row
        a slot of `active`, or a row a slot in a dense step.

        Host stages (docs/observability.md, "Inside a dispatch"):
        `ingest.apply` ⊃ `ingest.plan` (⊃ `.prescan` (⊃ `.decode_host`, one
        a host-lane payload), `.host_rows`, `.h2d`), `ingest.merge` (the
        uploads, then one enqueue each under `.gather`, `decode.v1`,
        `.scatter`), `ingest.rank_table`, `integrate.xla_batch`,
        `ingest.flags`, `ingest.recover`. The batch crosses every program
        boundary as a `PackedBatch`: the upload or the kept batch, the
        decoder's output, the merge's, the integrate program's operand.
        """
        if len(payloads) != self.n_docs:
            raise ValueError(f"expected {self.n_docs} payload slots")
        if columns is not None and len(columns) != self.n_docs:
            raise ValueError(f"expected {self.n_docs} column slots")
        from ytpu.utils.phases import phases

        # keyless spans: phases.span() itself returns the shared no-op
        # when disabled — no extra guard needed without a key tuple. The
        # body stays in this frame: a helper frame between here and the
        # jitted calls made every trace of a new shape dearer (10 s of a
        # run's set-up on the chip, PERF.md §6, PR 26)
        with phases.span("ingest.apply"):
            self._last_fast_flags = None
            from ytpu.native import available, decode_update_columns

            with phases.span("ingest.plan"):
                native = available()
                live: List[int] = []  # the slots that carry a payload
                fast_idx: List[int] = []
                fast_payloads: List[bytes] = []
                # recovery support: per fast doc, first-touch (client ->
                # pre-step clock) deltas — cheaper than copying whole SVs on
                # the hot path
                fast_sv_deltas: Dict[int, Dict[int, int]] = {}
                fast_has_str: List[bool] = []
                slow_updates: Dict[int, Update] = {}  # slot -> its update
                # those of them whose room holds a stash (`pending`) or gains
                # one (`dependency`: the update waits for another)
                stash_rooms = set()
                # slot -> the rows its update can add at most (`_make_room`)
                adds: Dict[int, int] = {}
                max_fast_rows, max_fast_dels = 0, 0
                max_sections, max_steps = 0, 0
                if self._stash_tickets:
                    # rooms holding a stash as the step plans
                    self._tally(self._m_stash_rooms, len(self._stash_tickets))
                carried = 0  # payloads whose columns were handed in
                with phases.span("ingest.plan.prescan"):
                    for d, p in enumerate(payloads):
                        if p is None:
                            continue
                        live.append(d)
                        cols = None if columns is None else columns[d]
                        if cols is not None:
                            carried += 1
                        elif native:
                            cols = decode_update_columns(p)
                        why = None if cols is None else self._host_lane_reason(d, cols)
                        if cols is None or why is not None:
                            if why in ("pending", "dependency"):
                                stash_rooms.add(d)
                            with phases.span("ingest.plan.decode_host"):
                                slow_updates[d] = Update.decode_v1(p)
                            continue
                        fast_idx.append(d)
                        fast_payloads.append(p)
                        sv = self.svs[d]
                        deltas = fast_sv_deltas[d] = {}
                        rows_here = 0
                        str_here = 0
                        for i in range(cols.n_blocks):
                            kind = int(cols.kind[i])
                            if kind == 10:
                                continue
                            if (
                                kind in _WIRE_REF_KINDS
                                and int(cols.length[i]) > 0
                            ):
                                str_here += 1
                            c = int(cols.client[i])
                            self.enc.interner.intern(c)
                            for arr, clk in (
                                (cols.origin_client, cols.origin_clock),
                                (cols.ror_client, cols.ror_clock),
                            ):
                                if int(clk[i]) >= 0:
                                    self.enc.interner.intern(int(arr[i]))
                            deltas.setdefault(c, sv.get(c))
                            sv.set_max(
                                c, int(cols.clock[i]) + int(cols.length[i])
                            )
                            if int(cols.length[i]) > 0:
                                rows_here += 1
                        for i in range(cols.n_dels):
                            self.enc.interner.intern(int(cols.del_client[i]))
                        fast_has_str.append(str_here > 0)
                        adds[d] = (
                            ROWS_PER_ROW * rows_here + ROWS_PER_DEL * cols.n_dels
                        )
                        max_fast_rows = max(max_fast_rows, rows_here)
                        max_fast_dels = max(max_fast_dels, cols.n_dels)
                        max_sections = max(max_sections, cols.n_client_sections)
                        max_steps = max(max_steps, steps_for_columns(cols))
                self.fast_docs += len(fast_idx)
                self.slow_docs += len(slow_updates)
                if live:
                    self._tally(self._m_prescan_payloads, len(live))
                if carried:
                    self._tally(self._m_prescan_carried, carried)

                # a slot without a payload plans no row (`_plan_doc`): the
                # step, and the batch both lanes meet in, need be no wider
                # than the slots that carry one
                active = self._active_slots(live)
                width = self.n_docs if active is None else len(active)
                # a step none of whose payloads took the host lane plans no
                # row at all: its batch is `batch_packed`'s padding, kept
                # on the device by bucket
                with phases.span("ingest.plan.host_rows"):
                    planned = {}
                    for d, u in slow_updates.items():
                        if d in stash_rooms:
                            with phases.span("ingest.plan.stash"):
                                planned[d] = self._plan_doc(d, u)
                        else:
                            planned[d] = self._plan_doc(d, u)
                    n_rows = _bucket(max(
                        [max_fast_rows, 1] + [len(r) for r, _ in planned.values()]
                    ))
                    n_dels = _bucket(max(
                        [max_fast_dels, 1] + [len(d_) for _, d_ in planned.values()]
                    ))
                    bucket = (width, n_rows, n_dels)
                    for d, (rows, dels) in planned.items():
                        adds[d] = (
                            ROWS_PER_ROW * len(rows) + ROWS_PER_DEL * len(dels)
                        )
                    if planned:
                        host_rows = sum(len(r) for r, _ in planned.values())
                        self._m_host_rows.inc(host_rows)
                        phases.add_value(self._m_host_rows.name, host_rows)
                    batch = None if planned else self._kept_batch(bucket)
                    built = batch is None
                    if built:
                        packed = self.enc.batch_packed(
                            *self._planned_batch(active, planned), n_rows, n_dels
                        )
                with phases.span("ingest.plan.h2d"):
                    if built:
                        # the host lane's two packed arrays: by room over
                        # the chips in a dense step, whole on every chip
                        # in a compact one
                        by_doc = active is None
                        batch = self._upload(packed, by_doc)
                        if phases.enabled:
                            phases.transfer(
                                "ingest.plan.h2d",
                                self._uploaded_bytes(packed, by_doc),
                                "h2d",
                            )
                        if not planned:
                            self._keep_batch(bucket, batch)
                    if not fast_idx:
                        # no manifest to ride in (`_merge_fast_lane`): the
                        # step's `active` goes up beside the batch. Its 4 B
                        # a slot are not in the stage's bytes, which are
                        # the batch's (`plan_h2d_kb.flood`)
                        active = self._upload(active)
                took = self._m_batch_builds if built else self._m_batch_reuses
                took.inc()
                phases.add_value(took.name, 1)  # the recorder's: a window's delta
            self._m_fast.inc(len(fast_idx))
            self._m_slow.inc(len(slow_updates))
            took = self._m_dense if active is None else self._m_compact
            took.inc()
            phases.add_value(took.name, 1)  # the recorder's: a window's delta

            flags = None
            chunk_base = None
            if fast_idx:
                # retain wire bytes only for lanes that actually emitted string
                # rows (delete/GC-only payloads hold no device-referenced spans)
                batch, flags, chunk_base, active = self._merge_fast_lane(
                    batch, fast_idx, self._step_rows(active, fast_idx),
                    fast_payloads, n_rows, n_dels,
                    retain_lanes=fast_has_str,
                    n_steps=16 * ((max_steps + 15) // 16) or None,
                    max_sections=_bucket(max_sections, 2) if max_sections else None,
                    active=active,
                )
            with phases.span("ingest.rank_table"):
                # after the prescan has interned what this step brought
                client_rank = self._client_rank()
            # a room whose update might leave it short of rows is squashed,
            # collected and defragmented first (`ingest.compact`)
            self._make_room(adds)
            # `active` is on the device(s) already (the merge's first
            # program handed it back, out of the step's manifest), as the
            # batch is: `[len(active), ...]`, and a pair whether a merge
            # made it or the host lane's upload goes straight in. One form
            # of the program a bucket, and no host array crosses with it
            # (`_integrate`'s three lines, kept in this frame: see above)
            old = ensure_origin_slot(self.state)
            self.state = apply_update_batch_in_place(
                old, batch, client_rank, active
            )
            self._count_state_step(old)
            self._count_outputs(self.state)
            if flags is not None:
                # `_fast_eligible` proved these lanes decode clean, and flagged
                # lanes integrate nothing (their rows are marked invalid), so a
                # flag here means the device saw something the host pre-scan
                # did not. Recover exactly: rewind the mirror SV and re-route
                # the payload through the host lane in one follow-up step.
                # (The readback overlaps the already-dispatched integrate step.)
                from ytpu.ops.decode_kernel import FLAG_ERRORS

                with phases.span("ingest.flags"):
                    f = np.asarray(flags)
                if (f & FLAG_ERRORS).any():
                    with phases.span("ingest.recover"):
                        self._recover_flagged(
                            payloads, f, fast_idx, fast_has_str,
                            fast_sv_deltas, chunk_base,
                        )
                self._last_fast_flags = f
            return self.state

    def _recover_flagged(
        self, payloads, f, fast_idx, fast_has_str, fast_sv_deltas, chunk_base
    ) -> None:
        """Re-route the fast lanes the device flagged through the host
        lane, in one follow-up step (`apply_bytes`, `ingest.recover`)."""
        from ytpu.ops.decode_kernel import FLAG_ERRORS

        bad_lanes = set(np.nonzero(f & FLAG_ERRORS)[0].tolist())
        bad = sorted(fast_idx[i] for i in bad_lanes)
        self.fast_recoveries += len(bad)
        self._m_recoveries.inc(len(bad))
        # release the retained wire chunk if every string-bearing
        # lane in it was flagged (their refs never went live); a
        # partially-flagged chunk keeps the surviving lanes' bytes
        # (the flagged lanes' share is stranded — rare, bounded by
        # decoder-disagreement frequency)
        if chunk_base is not None and all(
            i in bad_lanes for i, has in enumerate(fast_has_str) if has
        ):
            self.payloads.drop_if_unreferenced(chunk_base)
        planned = {}
        for d in bad:
            clocks = self.svs[d].clocks
            for c, old in fast_sv_deltas[d].items():
                if old == 0:
                    clocks.pop(c, None)
                else:
                    clocks[c] = old
            planned[d] = self._plan_doc(d, Update.decode_v1(payloads[d]))
        # as wide as the flagged rooms, by the step's own rule (the flagged
        # lanes integrated nothing: the rows bound already holds their adds)
        active = self._active_slots(bad)
        self._integrate(
            self._batch(
                *self._planned_batch(active, planned), by_doc=active is None
            ),
            self._client_rank(),
            self._upload(active),
        )
        self._count_outputs(self.state)

    def _merge_fast_lane(
        self,
        batch,
        fast_idx,
        fast_at,
        fast_payloads,
        n_rows,
        n_dels,
        retain_lanes=None,
        n_steps=None,
        max_sections=None,
        active=None,
    ):
        """Decode the fast lanes (the slots `fast_idx`) on the device and
        lay them over `batch` at its rows `fast_at` (`_step_rows`).

        One upload, the step's manifest (`pack_manifest`: the wire bytes,
        the lanes' columns and the step's `active`), then at most three
        device programs: the manifest's gather, `decode_updates_v1`,
        `merge_stream`. The first is keyed by what keys the decode family
        (S, the wire bucket, L) and the step's width, the other two by S,
        L, `n_rows`, `n_dels` and by nothing else; each is handed device
        arrays alone. `batch` comes and goes as a `PackedBatch`, and so
        does the decoded stream between the two programs; `active` comes
        back as the device array the integrate call takes."""
        from ytpu.ops.decode_kernel import decode_updates_v1, pack_updates
        from ytpu.utils.phases import phases

        # the host stages of the merge, in order (docs/observability.md,
        # "Inside a dispatch"): retain, pack, h2d, gather, tables,
        # decode.v1, scatter — between them they are the host's share of
        # a served step that is neither planning nor integrate
        with phases.span("ingest.merge"):
            S = len(fast_payloads)
            raw = self.ingest == "raw"
            lens = np.asarray([len(p) for p in fast_payloads], dtype=np.int32)
            L = _bucket(int(lens.max()) + 16, 64)
            # Retain only the wire bytes of lanes that emitted string rows
            # (lens-trimmed, concatenated) — `merge_stream` rebases refs
            # from the padded s*L layout onto the compact one. Lanes
            # without string rows have no device-referenced spans, so
            # their bytes are never kept. Host work all of it, and what
            # it works out rides in the step's one upload
            with phases.span("ingest.merge.retain"):
                keep = (
                    np.ones(S, dtype=bool)
                    if retain_lanes is None
                    else np.asarray(retain_lanes, dtype=bool)
                )
                prefix = np.zeros(S, dtype=np.int32)
                prefix[1:] = np.cumsum(np.where(keep, lens, 0)[:-1])
                base = 0
                if keep.any():
                    compact = b"".join(
                        p for p, k in zip(fast_payloads, keep) if k
                    )
                    base = self.payloads.add_chunk(
                        np.frombuffer(compact, dtype=np.uint8)
                    )
            with phases.span("ingest.merge.pack"):
                # the lanes' primary roots: the one table a step makes
                prim_hash = np.full(S, -1, dtype=np.int32)
                for s_i, d in enumerate(fast_idx):
                    name = self.primary_roots.get(d)
                    if name is not None:
                        prim_hash[s_i] = self._key_hash(name)
                table = pack_lane_table(lens, prim_hash, fast_at, prefix, base)
                if raw:
                    # RAW lane: ship the actual wire bytes and the lanes'
                    # offsets, gather the padded [S, L] matrix on device
                    # (byte-identical to the packed matrix: the gather
                    # zero-masks past lens), all of it one host array
                    host = pack_manifest(fast_payloads, table, active)
                else:
                    # the host-padded matrix, and leaves of its own
                    host = (pack_updates(fast_payloads, pad_to=L)[0], table, active)
            with phases.span("ingest.merge.h2d"):
                # the step's one trip to HBM, the wire bytes in it counted
                # here and nowhere else (every program below is handed
                # device arrays)
                dev = self._upload(host)
                if phases.enabled:
                    phases.transfer(
                        "ingest.merge.h2d", self._uploaded_bytes(host), "h2d"
                    )
            with phases.span("ingest.merge.gather"):
                # one enqueue (`jit_gather_manifest_lanes`); the packed
                # mode uploaded the matrix itself
                gathered = ()
                if raw:
                    gathered = dev = _gather_manifest_jit(
                        dev,
                        lanes=S,
                        width=L,
                        step_width=0 if active is None else len(active),
                    )
                dev_buf, dev_table, dev_active = dev
            with phases.span("ingest.merge.tables"):
                tables = self._decode_tables()
            stream, flags = decode_updates_v1(
                dev_buf,
                None,
                n_rows,
                n_dels,
                n_steps=n_steps,
                max_sections=max_sections,
                packed=True,
                lane_table=dev_table,
                **tables,
            )
            with phases.span("ingest.merge.scatter"):
                # one enqueue (`jit_merge_stream`: rebase + the scatter
                # of both arrays) and nothing else: the lanes' rows, the
                # chunk's prefix and base are rows of the lane table
                merged = _merge_stream_jit(batch, stream, dev_table, width=L)
            self._count_outputs(gathered, stream, flags, merged)
        return merged, flags, (base if keep.any() else None), dev_active
