"""batch_doc — the flagship batched CRDT engine: N documents as one pytree.

This is the TPU-native re-design of the reference's per-doc mutable store
(/root/reference/yrs/src/block_store.rs, block.rs:482-769, update.rs:169-308):

- Document state is a struct-of-arrays block tensor per doc, vmapped over a
  doc axis (the DP axis of the mesh). Every Item field is a column
  (SURVEY.md §7's layout); splits append rows instead of mutating a pointer
  graph; the sequence is a pair of left/right i32 index columns.
- `apply_update_batch(state, batch)` integrates one decoded update per doc
  per step under `jit`: per doc a `lax.fori_loop` over incoming rows, each
  row resolving its origins with vectorized (client, clock) interval lookups,
  running the YATA conflict scan as a `lax.while_loop` (set membership = B-bit
  boolean masks), and linking in with O(1) scatters. Delete ranges apply as
  two guarded splits + a vectorized range mask.
- Clients are interned to dense i32 on host (SURVEY §2 #8); string/Any
  payloads stay in host side-buffers addressed by (content_ref, offset, len)
  columns — the device never touches variable-length data.

Device scope: full branch trees. Sequence components (YText/YArray), map
components (YMap / XML attributes; per-key chains with LWW tails keyed by an
interned `parent_sub` column), and nested shared types (a ContentType row
owns a child sequence through its `head` column; children point back through
the `parent` column). Semantic parity is enforced against `ytpu.core` in
tests/test_batch_device.py, test_batch_map.py and test_batch_tree.py.
"""

from __future__ import annotations

import os
import time
from dataclasses import dataclass
from functools import partial
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ytpu.core import Doc, Update
from ytpu.core.block import GCRange, Item, SkipRange
from ytpu.core.content import (
    BLOCK_GC,
    BLOCK_ROOT_ANCHOR,
    CONTENT_ANY,
    CONTENT_BINARY,
    CONTENT_DELETED,
    CONTENT_EMBED,
    CONTENT_FORMAT,
    CONTENT_JSON,
    CONTENT_MOVE,
    CONTENT_STRING,
    CONTENT_TYPE,
    ContentMove,
)
from ytpu.core.ids import ID

__all__ = [
    "BlockCols",
    "DocStateBatch",
    "UpdateBatch",
    "PackedBatch",
    "unpack_batch",
    "pack_batch",
    "unpack_batch_jit",
    "init_state",
    "CompactionPolicy",
    "DEFAULT_COMPACTION_POLICY",
    "stream_worst_case_adds",
    "apply_update_batch",
    "apply_update_batch_in_place",
    "apply_update_stream_raw",
    "ClientInterner",
    "KeyInterner",
    "PayloadStore",
    "BatchEncoder",
    "finish_encode_diff",
    "finish_encode_diff_batch",
    "compact_finisher_rows",
    "DiffPlan",
    "DiffStats",
    "DiffPipeline",
    "plan_diff_pipeline",
    "FINISHER_MT_MIN_ROWS",
    "ensure_root_anchor",
    "ensure_root_anchor_all",
    "recompute_origin_slot",
    "mark_origin_slot_stale",
    "origin_slot_is_stale",
    "ensure_origin_slot",
    "get_string",
    "get_map",
    "get_tree",
    "state_vectors",
    "scan_tier_plan",
    "merge_scan_records",
]

I32 = jnp.int32


class BlockCols(NamedTuple):
    """Columnar Item schema (reference fields: block.rs:1088-1133)."""

    client: jax.Array  # [*, B] i32 interned client (-1 = unused slot)
    clock: jax.Array  # [*, B] i32
    length: jax.Array  # [*, B] i32
    origin_client: jax.Array  # [*, B] i32 (-1 = none)
    origin_clock: jax.Array  # [*, B] i32
    ror_client: jax.Array  # [*, B] i32 right-origin (-1 = none)
    ror_clock: jax.Array  # [*, B] i32
    left: jax.Array  # [*, B] i32 sequence link (-1 = head)
    right: jax.Array  # [*, B] i32 sequence link (-1 = tail)
    deleted: jax.Array  # [*, B] bool
    countable: jax.Array  # [*, B] bool
    kind: jax.Array  # [*, B] i32 content kind
    content_ref: jax.Array  # [*, B] i32 host payload id
    content_off: jax.Array  # [*, B] i32 offset into payload (clock units)
    key: jax.Array  # [*, B] i32 interned parent_sub (-1 = sequence item)
    parent: jax.Array  # [*, B] i32 row of the parent ContentType (-1 = root)
    head: jax.Array  # [*, B] i32 child-sequence head for ContentType rows
    moved: jax.Array  # [*, B] i32 slot of the move item owning this row (-1)
    mv_sc: jax.Array  # [*, B] i32 move rows: range-start id client (-1 n/a)
    mv_sk: jax.Array  # [*, B] i32 move rows: range-start id clock
    mv_sa: jax.Array  # [*, B] i32 move rows: start assoc (0 after, -1 before)
    mv_ec: jax.Array  # [*, B] i32 move rows: range-end id client (-1 n/a)
    mv_ek: jax.Array  # [*, B] i32 move rows: range-end id clock
    mv_ea: jax.Array  # [*, B] i32 move rows: end assoc
    mv_prio: jax.Array  # [*, B] i32 move rows: conflict priority
    origin_slot: jax.Array  # [*, B] i32 cached slot of the block containing
    # this row's origin id (-1 = no origin / absent from the local store).
    # The conflict scan's case-2 resolution (block.rs:537-602) reads it as
    # one gather instead of an O(B) find per while-trip (VERDICT r4 #9).
    # Maintained at insert/split/squash/compact/grow; recomputed wholesale
    # at fused-lane unpack and pre-origin_slot checkpoint load. Contract
    # (asserted in tests/test_origin_slot.py): authoritative for every
    # sequence-LINKED row; unlinked rows (GC carriers, rows in
    # error-flagged docs) may conservatively hold -1.


class DocStateBatch(NamedTuple):
    blocks: BlockCols
    start: jax.Array  # [*] i32 head of the root sequence (-1 empty)
    n_blocks: jax.Array  # [*] i32
    error: jax.Array  # [*] i32 sticky error flags (0 = healthy)


class UpdateBatch(NamedTuple):
    """One decoded update per doc, padded to U rows / R delete ranges."""

    client: jax.Array  # [*, U] i32
    clock: jax.Array  # [*, U] i32
    length: jax.Array  # [*, U] i32
    origin_client: jax.Array  # [*, U] i32 (-1 none)
    origin_clock: jax.Array  # [*, U] i32
    ror_client: jax.Array  # [*, U] i32 (-1 none)
    ror_clock: jax.Array  # [*, U] i32
    kind: jax.Array  # [*, U] i32 (BLOCK_GC for GC carriers)
    content_ref: jax.Array  # [*, U] i32
    content_off: jax.Array  # [*, U] i32
    key: jax.Array  # [*, U] i32 interned parent_sub (-1 = sequence row)
    p_tag: jax.Array  # [*, U] i32 parent form: 0 inherit, 1 root, 2 branch id
    p_client: jax.Array  # [*, U] i32 branch-id parent (p_tag == 2)
    p_clock: jax.Array  # [*, U] i32
    p_root: jax.Array  # [*, U] i32 root-name key id (p_tag == 1; -1 = the
    # primary root branch, i.e. state.start — doc.rs:156-228 named roots)
    mv_sc: jax.Array  # [*, U] i32 move rows: range-start id client (-1 n/a)
    mv_sk: jax.Array  # [*, U] i32
    mv_sa: jax.Array  # [*, U] i32 start assoc (0 after, -1 before)
    mv_ec: jax.Array  # [*, U] i32 range-end id client (-1 n/a)
    mv_ek: jax.Array  # [*, U] i32
    mv_ea: jax.Array  # [*, U] i32 end assoc
    mv_prio: jax.Array  # [*, U] i32 conflict priority
    valid: jax.Array  # [*, U] bool
    del_client: jax.Array  # [*, R] i32
    del_start: jax.Array  # [*, R] i32
    del_end: jax.Array  # [*, R] i32
    del_valid: jax.Array  # [*, R] bool


# one padding entry of `BatchEncoder.batch_packed`'s `rows`: the 22 row
# fields in `UpdateBatch`'s order, then `valid`. A padded key must read as
# "sequence row", a padded p_root as the primary root
_PAD_ROW = np.zeros(23, dtype=np.int32)
_PAD_ROW[[10, 12, 14, 15, 18, 21]] = -1  # key, p_client, p_root, mv_sc, mv_ec, mv_prio


class PackedBatch(NamedTuple):
    """An `UpdateBatch` as it crosses a program boundary: two contiguous
    int32 arrays, so two buffers where the planes are 27, each field a
    column and each array's last column its `valid` plane (1 a real
    entry, 0 padding). The host builds and ships it
    (`BatchEncoder.batch_packed`), the served decoder and `merge_stream`
    hand it on, and the integrate program takes it apart
    (`unpack_batch`): the planes exist inside a program only."""

    rows: jax.Array  # [*, U, 23] client .. mv_prio, valid
    dels: jax.Array  # [*, R, 4] del_client, del_start, del_end, del_valid


def unpack_batch(batch) -> UpdateBatch:
    """The 27 planes of a `PackedBatch`, sliced out on the device(s) its
    arrays are on and laid out as they are: inside the program that is
    handed it (`apply_update_batch`: no enqueue of its own), or as the
    small program `unpack_batch_jit` for a caller that wants planes. An
    `UpdateBatch` passes through."""
    if isinstance(batch, UpdateBatch):
        return batch
    rows, dels = batch
    return UpdateBatch(
        *(rows[..., i] for i in range(22)),  # client .. mv_prio
        rows[..., 22] != 0,
        *(dels[..., i] for i in range(3)),  # del_client, _start, _end
        dels[..., 3] != 0,
    )


def pack_batch(batch: UpdateBatch) -> PackedBatch:
    """`unpack_batch`'s inverse: the planes of an `UpdateBatch` stacked
    into the two arrays, inside the program that made them (the served
    decoder)."""
    return PackedBatch(
        jnp.stack([*batch[:22], batch.valid.astype(I32)], axis=-1),
        jnp.stack([*batch[23:26], batch.del_valid.astype(I32)], axis=-1),
    )


# for a caller that wants planes and has no program to take them apart in
unpack_batch_jit = jax.jit(unpack_batch)


ERR_CAPACITY = 1
ERR_MISSING_DEP = 2

# empty-slot value per BlockCols field — the single source of truth for
# init_state, compaction's defrag fills, and grow_state's padding
COL_DEFAULTS: Dict[str, object] = {
    "client": -1,
    "clock": 0,
    "length": 0,
    "origin_client": -1,
    "origin_clock": 0,
    "ror_client": -1,
    "ror_clock": 0,
    "left": -1,
    "right": -1,
    "deleted": False,
    "countable": False,
    "kind": 0,
    "content_ref": -1,
    "content_off": 0,
    "key": -1,
    "parent": -1,
    "head": -1,
    "moved": -1,
    "mv_sc": -1,
    "mv_sk": 0,
    "mv_sa": 0,
    "mv_ec": -1,
    "mv_ek": 0,
    "mv_ea": 0,
    "mv_prio": -1,
    "origin_slot": -1,
}
assert tuple(COL_DEFAULTS) == BlockCols._fields


def init_state(n_docs: int, capacity: int) -> DocStateBatch:
    """Allocate an empty batch of docs with `capacity` block slots each."""
    shape = (n_docs, capacity)
    blocks = BlockCols(
        **{
            name: jnp.full(shape, fill, dtype=bool if isinstance(fill, bool) else I32)
            for name, fill in COL_DEFAULTS.items()
        }
    )

    def full(shape, v, dtype=I32):
        return jnp.full(shape, v, dtype=dtype)

    return DocStateBatch(
        blocks=blocks,
        start=full((n_docs,), -1),
        n_blocks=full((n_docs,), 0),
        error=full((n_docs,), 0),
    )


class CompactionPolicy(NamedTuple):
    """When does a chunked replay lane compact / grow its block state?

    One policy object serves BOTH device lanes (the fused Pallas driver
    and the packed-XLA chunk step): the round-5 flagship capture showed
    the XLA lane surviving full B4 only through mid-replay compactions
    while the fused lane had no compaction story at all — the policies
    must not diverge again. Mirrors the reference's commit-time squash
    cadence (block_store.rs:155-270): compaction is not an emergency
    valve, it runs whenever occupancy crosses the high-watermark so the
    NEXT chunk integrates into a mostly-empty tile.

    - ``high_watermark``: occupancy fraction above which a between-chunk
      compaction fires even when the next chunk would still fit.
    - ``chunk_budget``: fraction of capacity a single chunk's WORST-CASE
      adds may consume — a chunk planner sizes
      chunks so one compaction's headroom (1 - high_watermark is the
      floor it restores when content is mostly tombstones) always admits
      the next chunk.
    """

    high_watermark: float = 0.85
    chunk_budget: float = 0.15

    def occupancy_trips(self, occupancy: int, capacity: int) -> bool:
        """High-watermark check (ISSUE-4 policy: n_blocks/C > 0.85)."""
        return occupancy > self.high_watermark * capacity

    def should_compact(self, occupancy: int, margin: int, capacity: int) -> bool:
        """Compact before the next chunk? True when projected growth
        (`margin` = the chunk's worst-case adds) would overflow, or the
        high-watermark already tripped."""
        return occupancy + margin > capacity or self.occupancy_trips(
            occupancy, capacity
        )

    def chunk_add_budget(self, capacity: int) -> int:
        """Worst-case adds one chunk may carry under this policy."""
        return max(1, int(self.chunk_budget * capacity))


DEFAULT_COMPACTION_POLICY = CompactionPolicy()


def stream_worst_case_adds(stream: UpdateBatch) -> np.ndarray:
    """[S] worst-case block-slot growth per step of a stacked stream.

    Each valid row can cost 3 slots (itself + two anchor splits), each
    valid delete range 2 (edge splits): the one accounting, which the
    served ingestor's bound on a room's rows shares (`models/ingest.py`).
    For an occupancy projection; host-side (numpy) so the
    projection never touches the device."""
    rows = np.asarray(stream.valid).sum(axis=-1).astype(np.int64)
    dels = np.asarray(stream.del_valid).sum(axis=-1).astype(np.int64)
    return 3 * rows + 2 * dels


@jax.jit
def _append_root_anchor_masked(state: DocStateBatch, doc_mask, key_id) -> DocStateBatch:
    """Idempotently append the BLOCK_ROOT_ANCHOR row for root `key_id` in
    every doc selected by ``doc_mask`` ([D] bool) — the shared core of
    `ensure_root_anchor` (one-hot mask) and `ensure_root_anchor_all`.

    Anchors give non-primary named roots (doc.rs:156-228) a per-doc row
    the integrate path can parent through (its `head` column is the root's
    child-sequence head, exactly like a nested ContentType row). They have
    no wire identity: client == -1 keeps them out of state vectors, ship
    masks, and delete sets; compaction keeps and remaps them like any row.
    """
    bl = state.blocks
    D, B = bl.client.shape
    slots = jnp.arange(B, dtype=I32)[None, :]
    exists = jnp.any(
        (slots < state.n_blocks[:, None])
        & (bl.kind == BLOCK_ROOT_ANCHOR)
        & (bl.key == key_id),
        axis=1,
    )
    j = state.n_blocks
    do = doc_mask & ~exists & (j < B)
    overflow = doc_mask & ~exists & (j >= B)
    didx = jnp.arange(D, dtype=I32)
    wi = jnp.where(do, j, 0)

    def put(col, val):
        # in-bounds read-modify-write, as `_set` (and for its reason)
        return col.at[didx, wi].set(
            jnp.where(do, val, col[didx, wi]), mode="promise_in_bounds"
        )

    new_bl = bl._replace(
        kind=put(bl.kind, BLOCK_ROOT_ANCHOR),
        key=put(bl.key, key_id),
        client=put(bl.client, -1),
        length=put(bl.length, 0),
        head=put(bl.head, -1),
        left=put(bl.left, -1),
        right=put(bl.right, -1),
        deleted=put(bl.deleted, False),
        countable=put(bl.countable, False),
    )
    return DocStateBatch(
        blocks=new_bl,
        start=state.start,
        n_blocks=state.n_blocks + do.astype(I32),
        # error is a BITMASK — OR the flag in
        error=state.error | jnp.where(overflow, ERR_CAPACITY, 0),
    )


def ensure_root_anchor(state: DocStateBatch, doc: int, key_id: int) -> DocStateBatch:
    """Host entry: create doc's anchor row for a non-primary root (no-op
    when it already exists). Call BEFORE applying updates whose rows carry
    ``p_root == key_id`` — the integrate path resolves anchors, it never
    creates them (missing anchor -> pending stash, like any missing dep)."""
    D = state.blocks.client.shape[0]
    mask = jnp.arange(D, dtype=I32) == jnp.int32(doc)
    return _append_root_anchor_masked(state, mask, jnp.int32(key_id))


def ensure_root_anchor_all(state: DocStateBatch, key_id: int) -> DocStateBatch:
    """Create the anchor row for root `key_id` in EVERY doc slot (one
    vectorized dispatch — the batched-replay analogue of
    `ensure_root_anchor`, for streams that broadcast one multi-root doc
    to all slots)."""
    D = state.blocks.client.shape[0]
    return _append_root_anchor_masked(
        state, jnp.ones((D,), bool), jnp.int32(key_id)
    )


# --- per-doc primitives (vmapped over the doc axis) ---------------------------


def _capacity(bl: BlockCols) -> int:
    return bl.client.shape[-1]


def _find_slot(bl: BlockCols, n: jax.Array, client: jax.Array, clock: jax.Array):
    """Slot whose clock interval covers (client, clock); -1 if absent.

    Device analogue of `find_pivot` (block_store.rs:70-96): an O(B) vector
    compare instead of a binary search — lanes are cheaper than branches.
    """
    B = _capacity(bl)
    slots = jnp.arange(B, dtype=I32)
    match = (
        (slots < n)
        & (bl.client == client)
        & (bl.clock <= clock)
        & (clock < bl.clock + bl.length)
    )
    idx = jnp.argmax(match).astype(I32)
    return jnp.where(jnp.any(match), idx, -1)


def _client_clock(bl: BlockCols, n: jax.Array, client: jax.Array) -> jax.Array:
    """Next expected clock for `client` (state-vector entry), 0 if unseen."""
    B = _capacity(bl)
    slots = jnp.arange(B, dtype=I32)
    mask = (slots < n) & (bl.client == client)
    return jnp.max(jnp.where(mask, bl.clock + bl.length, 0))


def _set(arr: jax.Array, idx: jax.Array, val) -> jax.Array:
    """Guarded write of one element: `idx >= B` means "no write" (inactive
    writes pass idx = B).

    An always-in-bounds read-modify-write, NOT a scatter that drops the
    out-of-range index. Under `vmap` the dropping form becomes one batched
    scatter whose rows mix in-range and out-of-range indices, and on a TPU
    v5e (jax 0.9.0, libtpu 0.0.34) that scatter also loses in-range writes
    of other rows: bool planes lost every lone write at doc 256..~1000 of
    1,024, i32 planes a few (CHANGES.md PR 24; tests/test_guarded_scatter.py
    pins the semantics). The CPU drops only what it should."""
    ok = idx < arr.shape[0]
    i = jnp.where(ok, idx, 0)
    return arr.at[i].set(
        jnp.where(ok, val, arr[i]), mode="promise_in_bounds"
    )


def recompute_origin_slot(state: DocStateBatch) -> DocStateBatch:
    """Rebuild the `origin_slot` cache column wholesale (brute-force
    containment search per row; the incremental maintenance lives in
    `_split` / `_integrate_row` / compaction's remap).

    Used at boundaries where the cache cannot ride along: a state some
    producer marked stale (`mark_origin_slot_stale`: the packed replay
    lane did until it left in PR 48; nothing does today), pre-origin_slot
    checkpoint restore: those two and no other. Docs are processed
    sequentially (`lax.map`) so the [B, B] containment compare never
    materializes across the whole batch."""

    def one_doc(args):
        bl, n = args

        def q(c, k):
            return _find_slot(bl, n, c, k)

        found = jax.vmap(q)(bl.origin_client, bl.origin_clock)
        B = _capacity(bl)
        active = jnp.arange(B, dtype=I32) < n
        return jnp.where(active & (bl.origin_client >= 0), found, -1)

    os_col = jax.lax.map(one_doc, (state.blocks, state.n_blocks))
    return state._replace(blocks=state.blocks._replace(origin_slot=os_col))


# --- lazy origin_slot refresh (ADVICE r5 #1) --------------------------------
# The fused kernel passes the origin_slot plane through without
# maintaining it; the wholesale recompute above is O(D·B²), so fused
# applies no longer run it eagerly. Instead the fused unpack marks its
# output STALE here (host-side dirty flag keyed on the cache array's
# identity — jax arrays are immutable, so identity pins the exact value)
# and the cache's readers refresh on first touch via
# `ensure_origin_slot`. `weakref.finalize` retires ids when the array
# dies, so a recycled id can never alias a fresh array as stale.

_STALE_ORIGIN_SLOT: set = set()


def mark_origin_slot_stale(state: DocStateBatch) -> None:
    """Flag `state.blocks.origin_slot` as stale (fused-lane output)."""
    import weakref

    arr = state.blocks.origin_slot
    key = id(arr)
    if key not in _STALE_ORIGIN_SLOT:
        _STALE_ORIGIN_SLOT.add(key)
        weakref.finalize(arr, _STALE_ORIGIN_SLOT.discard, key)


def origin_slot_is_stale(state: DocStateBatch) -> bool:
    """One set lookup — the hot-path cost of the lazy refresh."""
    return id(state.blocks.origin_slot) in _STALE_ORIGIN_SLOT


def ensure_origin_slot(state: DocStateBatch) -> DocStateBatch:
    """Recompute the cache iff this state was marked stale; the readers'
    entry points (XLA-lane applies, checkpoint save) call this so chained
    fused applies pay the O(D·B²) rebuild at most once."""
    if origin_slot_is_stale(state):
        return recompute_origin_slot(state)
    return state


@jax.named_scope("split")
def _split(state: DocStateBatch, i: jax.Array, off: jax.Array):
    """Split block `i` at `off` clock units; returns (state, right_slot).

    Device analogue of `split_block` (block_store.rs:456) — the right half
    is appended as a fresh row; linkage is patched with three scatters.
    No-op (returning `i`) unless 0 < off < len(i) and i >= 0.
    """
    bl = state.blocks
    B = _capacity(bl)
    length_i = jnp.where(i >= 0, bl.length[jnp.maximum(i, 0)], 0)
    do = (i >= 0) & (off > 0) & (off < length_i)
    j = state.n_blocks
    overflow = do & (j >= B)
    do = do & (j < B)
    wj = jnp.where(do, j, B)  # write slot for the new row ("B" = dropped)
    wi = jnp.where(do, i, B)  # write slot for the left half
    safe_i = jnp.maximum(i, 0)
    right_i = bl.right[safe_i]
    w_right = jnp.where(do & (right_i >= 0), right_i, B)

    # origin_slot repair: rows whose cached origin slot is the split block
    # and whose origin clock landed in the new right half repoint to j;
    # the right half's own origin is the left half (block.rs:435-478 —
    # splice chains the right part to the left part's last id)
    repoint = do & (bl.origin_slot == i) & (
        bl.origin_clock >= bl.clock[safe_i] + off
    )
    os_col = jnp.where(repoint, j, bl.origin_slot)

    new_bl = BlockCols(
        client=_set(bl.client, wj, bl.client[safe_i]),
        clock=_set(bl.clock, wj, bl.clock[safe_i] + off),
        length=_set(_set(bl.length, wj, length_i - off), wi, off),
        origin_client=_set(bl.origin_client, wj, bl.client[safe_i]),
        origin_clock=_set(bl.origin_clock, wj, bl.clock[safe_i] + off - 1),
        ror_client=_set(bl.ror_client, wj, bl.ror_client[safe_i]),
        ror_clock=_set(bl.ror_clock, wj, bl.ror_clock[safe_i]),
        left=_set(_set(bl.left, wj, i), w_right, j),
        right=_set(_set(bl.right, wj, right_i), wi, j),
        deleted=_set(bl.deleted, wj, bl.deleted[safe_i]),
        countable=_set(bl.countable, wj, bl.countable[safe_i]),
        kind=_set(bl.kind, wj, bl.kind[safe_i]),
        content_ref=_set(bl.content_ref, wj, bl.content_ref[safe_i]),
        content_off=_set(bl.content_off, wj, bl.content_off[safe_i] + off),
        key=_set(bl.key, wj, bl.key[safe_i]),
        parent=_set(bl.parent, wj, bl.parent[safe_i]),
        head=_set(bl.head, wj, -1),  # type rows (len 1) never split
        moved=_set(bl.moved, wj, bl.moved[safe_i]),  # parity: block.rs splice
        mv_sc=_set(bl.mv_sc, wj, -1),  # move rows (len 1) never split
        mv_sk=_set(bl.mv_sk, wj, 0),
        mv_sa=_set(bl.mv_sa, wj, 0),
        mv_ec=_set(bl.mv_ec, wj, -1),
        mv_ek=_set(bl.mv_ek, wj, 0),
        mv_ea=_set(bl.mv_ea, wj, 0),
        mv_prio=_set(bl.mv_prio, wj, -1),
        origin_slot=_set(os_col, wj, safe_i),
    )
    state = DocStateBatch(
        blocks=new_bl,
        start=state.start,
        n_blocks=state.n_blocks + do.astype(I32),
        error=state.error | jnp.where(overflow, ERR_CAPACITY, 0),
    )
    return state, jnp.where(do, j, i)


def _clean_end(state: DocStateBatch, client: jax.Array, clock: jax.Array):
    """Slot of the block *ending exactly at* (client, clock), splitting if
    needed (parity: get_item_clean_end, block_store.rs:402-417)."""
    i = _find_slot(state.blocks, state.n_blocks, client, clock)
    off = clock - state.blocks.clock[jnp.maximum(i, 0)] + 1
    state, _ = _split(state, i, off)  # _split no-ops when off == length
    return state, i


def _clean_start(state: DocStateBatch, client: jax.Array, clock: jax.Array):
    """Slot of the block *starting exactly at* (client, clock)."""
    i = _find_slot(state.blocks, state.n_blocks, client, clock)
    off = clock - state.blocks.clock[jnp.maximum(i, 0)]
    state, j = _split(state, i, off)
    return state, jnp.where((i >= 0) & (off > 0), j, i)


def _origins_equal(ha, ca, ka, hb, cb, kb):
    both_none = ~ha & ~hb
    both_same = ha & hb & (ca == cb) & (ka == kb)
    return both_none | both_same


# --- conflict-scan-width attribution (ISSUE-11) ------------------------------
# Fixed pow2 histogram the integrate row body folds as it runs (the
# served step drops it in-jit; the stream body returns it): bucket 0 holds
# widths 0-1, bucket k holds [2^k, 2^{k+1}) for k < SCAN_WIDTH_BUCKETS-1,
# the last bucket is unbounded above (the p99=337 tail lands there; the
# separate max word records the true extreme). Counting is pure vector
# arithmetic folded into the integrate program — never a device sync
# (docs/observability.md, "Conflict-tail attribution").

SCAN_WIDTH_BUCKETS = 8
SCAN_WIDTH_THRESHOLDS = (2, 4, 8, 16, 32, 64, 128)
#: inclusive upper bound of each bucket (the quantile representative);
#: the last bucket has no bound — report the observed max there
SCAN_WIDTH_UPPER = (1, 3, 7, 15, 31, 63, 127)

# --- two-tier conflict scan (ISSUE-12) ---------------------------------------
# The serial `lax.while_loop` dispatch — not the scan's find itself —
# owned the p99 integrate tail (p50=32 / p99=337 trips). The scan now
# runs in two tiers shared by both integrate lanes: a CHEAP tier (the
# original one-candidate-per-trip loop, bounded at `cheap` trips — covers
# the p50 mass with zero extra work) and a vectorized WIDE tier whose
# while body unrolls `unroll` candidate steps per trip (the Stream-VByte
# move: fixed-unroll block processing replaces per-element dispatch), so
# a width-337 scan costs 32 + ceil(305/8) = 71 trips instead of 337.
#
# Knob + retrace implications: the (cheap, unroll) pair is a TRACE-TIME
# static — `apply_update_stream` takes it as a static argument, so a
# caller that re-reads the env and passes the pair on gets a retrace of
# its dispatch programs when the value changes (the replay drivers that
# did so left in PR 48); the bare `apply_update_batch`/`apply_update_stream` wrappers
# (a direct caller of `apply_update_batch`, which the served path is)
# read it once at first trace and keep the baked value for
# already-compiled shapes (set the env before first dispatch, or pass
# `scan_plan` yourself). Width SEMANTICS are tier-independent:
# `width` counts visited candidates exactly as the single-tier loop did,
# so the scan-width histogram and `scan_width_p50/p99/max` keep their
# meaning.

SCAN_TIER_CHEAP_DEFAULT = 32
SCAN_WIDE_UNROLL_DEFAULT = 8


def scan_tier_plan() -> tuple:
    """Resolve the (cheap_bound, wide_unroll) tier plan from the
    environment (``YTPU_SCAN_TIER_CHEAP`` / ``YTPU_SCAN_WIDE_UNROLL``).
    ``cheap=0`` disables the cheap tier (every scan goes wide — the
    bench's forcing knob); ``unroll=1`` degenerates the wide tier to the
    pre-ISSUE-12 serial loop."""
    cheap = int(
        os.environ.get("YTPU_SCAN_TIER_CHEAP", SCAN_TIER_CHEAP_DEFAULT)
    )
    unroll = int(
        os.environ.get("YTPU_SCAN_WIDE_UNROLL", SCAN_WIDE_UNROLL_DEFAULT)
    )
    return (max(0, cheap), max(1, unroll))


# per-doc scan-record word layout (`_apply_update_stream_hist_body`
# returns it beside the state, `[D, SCAN_REC_WORDS]`): pow2 bucket
# counts, the observed max width, then the ISSUE-12 tier-occupancy and
# trip-accounting words. All words ADD under merge except the max.
SCAN_REC_MAX = SCAN_WIDTH_BUCKETS  # observed max width
SCAN_REC_CHEAP = SCAN_WIDTH_BUCKETS + 1  # scans resolved in the cheap tier
SCAN_REC_WIDE = SCAN_WIDTH_BUCKETS + 2  # scans that escalated to the wide tier
SCAN_REC_CHEAP_TRIPS = SCAN_WIDTH_BUCKETS + 3  # Σ min(width, cheap_bound)
SCAN_REC_WIDE_TRIPS = SCAN_WIDTH_BUCKETS + 4  # Σ wide-tier block trips
SCAN_REC_WIDTH_SUM = SCAN_WIDTH_BUCKETS + 5  # Σ width = serial-equiv trips
SCAN_REC_WORDS = SCAN_WIDTH_BUCKETS + 6


def scan_width_bucket(w):
    """Bucket index of one width sample (traced jnp value)."""
    b = (w >= SCAN_WIDTH_THRESHOLDS[0]).astype(I32)
    for t in SCAN_WIDTH_THRESHOLDS[1:]:
        b = b + (w >= t).astype(I32)
    return b


def _fold_scan_width(hist, w, wide_trips, cheap_bound: int):
    """Fold one row's scan sample (``w = -1`` = no scan; ``wide_trips``
    the wide-tier block trips it took) into a ``[SCAN_REC_WORDS]``
    record: bucket counts, max width, tier occupancy (resolved-cheap vs
    escalated-wide), and the exact trip accounting — ``Σ min(w, cheap)``
    cheap trips + ``Σ wide_trips`` block trips is the two-tier dispatch
    cost, ``Σ w`` the serial-equivalent cost the pre-ISSUE-12 loop paid
    (one trip per visited candidate), so their ratio IS the measured
    dispatch-trip compression."""
    scanned = w >= 0
    wc = jnp.maximum(w, 0)
    b = scan_width_bucket(wc)
    hist = hist.at[b].add(scanned.astype(I32))
    hist = hist.at[SCAN_REC_MAX].max(jnp.where(scanned, wc, 0))
    wide = scanned & (wide_trips > 0)
    hist = hist.at[SCAN_REC_CHEAP].add((scanned & ~wide).astype(I32))
    hist = hist.at[SCAN_REC_WIDE].add(wide.astype(I32))
    hist = hist.at[SCAN_REC_CHEAP_TRIPS].add(
        jnp.where(scanned, jnp.minimum(wc, cheap_bound), 0)
    )
    hist = hist.at[SCAN_REC_WIDE_TRIPS].add(jnp.where(scanned, wide_trips, 0))
    return hist.at[SCAN_REC_WIDTH_SUM].add(jnp.where(scanned, wc, 0))


def merge_scan_records(a, b):
    """Combine two scan records (or ``[..., SCAN_REC_WORDS]`` stacks):
    every word adds except the observed-max word, which maxes. One
    definition shared by the stream body and the chunk programs'
    meta-fold so the merge rule can never drift."""
    out = a + b
    return out.at[..., SCAN_REC_MAX].set(
        jnp.maximum(a[..., SCAN_REC_MAX], b[..., SCAN_REC_MAX])
    )


# --- incremental state commitment (ISSUE-13) ---------------------------------
# A homomorphic per-doc digest of the op lattice the federation layer's
# anti-entropy compares in O(1) per tenant per round (ytpu/sync/
# commitment.py holds the 64-bit host mirror and the full rationale).
# The device word is a vectorized reduction over the block columns
# (`commit_fold_blocks`). No served path reads it yet: the replay lane
# whose lazy readout carried it left in PR 48 (tests fold it directly).


def _commit_mix_u32(x):
    """32-bit integer finalizer over uint32 arrays — bit-identical to
    ``ytpu.sync.commitment.mix32`` (its pure-Python oracle)."""
    x = (x ^ (x >> 16)) * jnp.uint32(0x7FEB352D)
    x = (x ^ (x >> 15)) * jnp.uint32(0x846CA68B)
    return x ^ (x >> 16)


def commit_fold_blocks(client, clock, length, valid):
    """Per-doc state-commitment fold over block rows: ``[..., B]`` i32
    (client, clock, length) columns + a ``valid`` mask → ``[...]``
    uint32 (last axis reduced, mod 2^32 wrapping throughout).

    Each row contributes ``A(c)·(Σ_{j∈[s,s+l)} j) + B(c)·l`` with
    ``A/B = mix32(2c+1/2c+2)`` — additive over disjoint clock ranges, so
    the fold is invariant under block splits, merges and GC conversion
    (they preserve ``(client, clock, len)`` lattice coverage), and a
    state whose rows tile each client's ``[0, n_c)`` folds to exactly
    ``Σ_c A(c)·T(n_c) + B(c)·n_c`` (`commitment.device_commit_of_clocks`).

    The triangular term ``l(l-1)/2`` is computed division-free —
    ``(l/2)·(l-1)`` or ``l·((l-1)/2)`` by parity — because halving a
    *wrapped* product is not well defined mod 2^32."""
    c = client.astype(jnp.uint32)
    a = _commit_mix_u32(jnp.uint32(2) * c + jnp.uint32(1))
    b = _commit_mix_u32(jnp.uint32(2) * c + jnp.uint32(2))
    s = clock.astype(jnp.uint32)
    l = length.astype(jnp.uint32)
    tri = jnp.where(
        l % 2 == 0, (l >> 1) * (l - jnp.uint32(1)),
        l * ((l - jnp.uint32(1)) >> 1),
    )
    contrib = a * (s * l + tri) + b * l
    return jnp.sum(
        jnp.where(valid, contrib, jnp.uint32(0)), axis=-1, dtype=jnp.uint32
    )


def scan_width_quantile(counts, q: float, observed_max: int) -> int:
    """Host-side quantile over materialized bucket counts: the inclusive
    upper bound of the bucket holding the q-th sample (the unbounded last
    bucket reports the observed max). 0 when no scans were recorded."""
    counts = [int(c) for c in counts]
    total = sum(counts)
    if total == 0:
        return 0
    target = q * total
    acc = 0
    for k, c in enumerate(counts):
        acc += c
        if acc >= target:
            if k < len(SCAN_WIDTH_UPPER):
                return min(SCAN_WIDTH_UPPER[k], int(observed_max))
            return int(observed_max)
    return int(observed_max)


def _conflict_scan(
    state: DocStateBatch,
    client_rank: jax.Array,
    r_client,
    has_origin,
    origin_client,
    origin_clock,
    has_ror,
    ror_client,
    ror_clock,
    right_idx,
    o0,
    left_idx,
    scan_plan: Optional[tuple] = None,
):
    """The YATA conflict scan (parity: block.rs:537-602) of the batched
    engine: `_integrate_row` below is its one caller.

    Walks candidates from `o0` toward `right_idx` (or the sequence tail),
    resolving the final left neighbor: same-origin candidates tie-break on
    real client rank (case 1); candidates anchored inside the scanned
    region fold per the before/conflicting set rules (case 2). Returns
    ``(left_scanned, width, wide_trips)``: the scanned left slot (callers
    apply it only where their `need_scan` predicate held), the number of
    candidates the walk visited — the conflict-tail attribution sample
    (ISSUE-11) the integrate lanes fold into the lazy scan-width
    histogram — and the number of WIDE-TIER block trips the walk took
    (0 = resolved entirely in the cheap tier; the ISSUE-12 tier-occupancy
    sample). Callers that don't track widths discard the extra values
    (XLA dead-code-eliminates the counters).

    Two-tier dispatch (ISSUE-12): `scan_plan = (cheap_bound, unroll)`
    (default: `scan_tier_plan()`, read at trace time). The CHEAP tier is
    the original loop — one candidate per `while_loop` trip — bounded at
    `cheap_bound` trips, which covers the p50=32 mass at zero extra cost.
    A scan still unresolved after the bound escalates to the WIDE tier,
    whose while body unrolls `unroll` candidate steps per trip: each
    sub-step is fully masked by its own `active` predicate, so a scan
    that resolves mid-block no-ops through the remaining sub-steps. Per-
    candidate work is IDENTICAL to the single-tier loop — what shrinks is
    the serial `while_loop` trip count (the measured owner of the p99
    integrate tail), from `w` to `min(w, cheap) + ceil((w-cheap)/unroll)`.

    Cost model (VERDICT r4 #9): each candidate step is ~8 capacity-wide
    vector ops; before round 5 it was dominated by the unconditional
    case-2 origin resolution (`_find_slot`, an O(B) compare per trip —
    measured width distribution on the 256-client concurrent-array
    workload: p50=32, p99=337, the tail rode this loop). Case 2 now reads
    the `origin_slot` cache column as ONE gather: the cache is set at
    insert (where the pre-scan `left_idx` IS the clean-end of the
    origin), repaired on splits with one vector op, and remapped by
    compaction's permutation (absorbed rows redirect to their chain head,
    whose widened range still contains the origin clock)."""
    cheap_bound, unroll = scan_plan if scan_plan is not None else scan_tier_plan()
    bl = state.blocks
    B = _capacity(bl)
    safe = lambda idx: jnp.maximum(idx, 0)

    def scan_step(carry):
        """One candidate step, fully masked by `active` so it composes
        both as a whole while trip (cheap tier) and as one sub-step of a
        fixed-unroll wide-tier block (an inactive step is a no-op)."""
        o, left, conflicting, before, brk, width = carry
        active = (o >= 0) & (o != right_idx) & ~brk
        so = safe(o)
        # guarded writes: an inactive step must not touch slot 0
        wslot = jnp.where(active, so, B)
        before = _set(before, wslot, True)
        conflicting = _set(conflicting, wslot, True)
        same_origin = _origins_equal(
            has_origin,
            origin_client,
            origin_clock,
            bl.origin_client[so] >= 0,
            bl.origin_client[so],
            bl.origin_clock[so],
        )
        same_ror = _origins_equal(
            has_ror,
            ror_client,
            ror_clock,
            bl.ror_client[so] >= 0,
            bl.ror_client[so],
            bl.ror_clock[so],
        )
        # case 1: same left anchor — (real) client id breaks the tie
        case1_take = same_origin & (
            client_rank[safe(bl.client[so])] < client_rank[safe(r_client)]
        )
        case1_break = same_origin & ~case1_take & same_ror
        # case 2: o anchors somewhere inside the scanned region. A slot
        # that fails to resolve (-1, e.g. a non-local origin on a shard)
        # reads as "origin precedes the scanned region" — the break case.
        # The cached origin_slot makes this one gather (see docstring).
        o_has_origin = bl.origin_client[so] >= 0
        o_origin_idx = bl.origin_slot[so]
        o_origin_known = o_has_origin & (o_origin_idx >= 0)
        in_before = o_origin_known & before[safe(o_origin_idx)]
        in_conflicting = o_origin_known & conflicting[safe(o_origin_idx)]
        case2_take = ~same_origin & in_before & ~in_conflicting
        case2_break = ~same_origin & ~in_before

        take = (case1_take | case2_take) & active
        left = jnp.where(take, o, left)
        conflicting = jnp.where(take, jnp.zeros_like(conflicting), conflicting)
        brk = brk | ((case1_break | case2_break) & active)
        o = jnp.where(active & ~brk, bl.right[so], o)
        return (o, left, conflicting, before, brk, width + active.astype(I32))

    def cheap_cond(carry):
        o, left, conflicting, before, brk, width = carry
        # `width` doubles as the cheap-tier trip counter: the tier admits
        # exactly one candidate per trip, so width == trips here
        return (o >= 0) & (o != right_idx) & ~brk & (width < cheap_bound)

    # named scopes (here and below) only label the HLO: the profiler's
    # device ops carry them, so a trace says "conflict_scan/cheap", not
    # "while.663"
    zeros = jnp.zeros((B,), bool)
    with jax.named_scope("conflict_scan/cheap"):
        carry = jax.lax.while_loop(
            cheap_cond,
            scan_step,
            (o0, left_idx, zeros, zeros, jnp.array(False), I32(0)),
        )

    def wide_cond(carry):
        inner, wtrips = carry
        o, left, conflicting, before, brk, width = inner
        return (o >= 0) & (o != right_idx) & ~brk

    def wide_body(carry):
        inner, wtrips = carry
        for _ in range(unroll):
            inner = scan_step(inner)
        return inner, wtrips + 1

    with jax.named_scope("conflict_scan/wide"):
        (_, left_scanned, _, _, _, width), wide_trips = jax.lax.while_loop(
            wide_cond, wide_body, (carry, I32(0))
        )
    return left_scanned, width, wide_trips


def _integrate_row(
    state: DocStateBatch,
    row,
    client_rank: jax.Array,
    scan_plan: Optional[tuple] = None,
):
    """Integrate one incoming block row (YATA; parity: block.rs:482-769).

    `client_rank[c]` is the rank of interned client c in *real client id*
    order — the YATA tie-break (block.rs:571-580) is defined on real ids,
    which interning does not preserve.

    Returns (state, moves_dirty, scan_width, scan_wide_trips): dirty is
    True when move ownership must be recomputed (a move row arrived, or
    an insert landed between rows owned by *different* moves — the
    reconciliation case of block.rs:677-702); scan_width is the
    conflict-scan width sample for this row (-1 when no scan was needed
    — the no-scan path), feeding the ISSUE-11 scan-width histogram;
    scan_wide_trips the ISSUE-12 wide-tier block-trip count (0 = the
    cheap tier resolved it). `scan_plan` is the two-tier (cheap, unroll)
    static — None reads `scan_tier_plan()` at trace time.
    """
    (
        r_client,
        r_clock,
        r_len,
        r_oc,
        r_ok,
        r_rc,
        r_rk,
        r_kind,
        r_ref,
        r_off,
        r_key,
        r_ptag,
        r_pclient,
        r_pclock,
        r_proot,
        r_mv_sc,
        r_mv_sk,
        r_mv_sa,
        r_mv_ec,
        r_mv_ek,
        r_mv_ea,
        r_mv_prio,
        r_valid,
    ) = row
    bl = state.blocks
    B = _capacity(bl)

    local = _client_clock(bl, state.n_blocks, r_client)
    applicable = r_valid & (local >= r_clock)
    missing = r_valid & ~applicable
    offset = local - r_clock
    dup = applicable & (offset >= r_len)
    do = applicable & ~dup

    # offset adjustment (partial dedup; parity: block.rs:487-501)
    clock = r_clock + offset
    length = r_len - offset
    c_off = r_off + offset
    has_origin = jnp.where(offset > 0, True, r_oc >= 0)
    origin_client = jnp.where(offset > 0, r_client, r_oc)
    origin_clock = jnp.where(offset > 0, clock - 1, r_ok)
    has_ror = r_rc >= 0

    is_gc = r_kind == BLOCK_GC
    linkable = do & ~is_gc

    # resolve left/right anchors (repair; parity: block.rs:1287-1300)
    probe_oc = jnp.where(linkable & has_origin, origin_client, -2)
    state, left_idx = _clean_end(state, probe_oc, origin_clock)
    probe_rc = jnp.where(linkable & has_ror, r_rc, -2)
    state, right_idx = _clean_start(state, probe_rc, r_rk)
    bl = state.blocks

    # device engine requires resolvable anchors (host stashes pending updates)
    anchor_missing = (linkable & has_origin & (left_idx < 0)) | (
        linkable & has_ror & (right_idx < 0)
    )
    missing = missing | anchor_missing
    linkable = linkable & ~anchor_missing

    # the pre-scan left_idx IS the clean-end slot of this row's origin —
    # cache it now, before the conflict scan overwrites left_idx with the
    # YATA-final left neighbor
    origin_slot_j = jnp.where(linkable & has_origin & (left_idx >= 0), left_idx, -1)

    safe = lambda idx: jnp.maximum(idx, 0)

    # resolve the parent branch (parity: block.rs:503-523 TypePtr handling):
    # p_tag 2 = a nested branch, addressed by its ContentType item's id;
    # p_tag 1 = a named root — the primary branch (p_root < 0, state.start)
    # or a non-primary root's anchor row (p_root = interned key id; the
    # anchor is created by `ensure_root_anchor` before the apply);
    # p_tag 0 = omitted on the wire (an origin is present) — inherit from
    # the resolved left (else right) anchor
    parent_probe = jnp.where(linkable & (r_ptag == 2), r_pclient, -2)
    parent_slot = _find_slot(bl, state.n_blocks, parent_probe, r_pclock)
    slots_b = jnp.arange(B, dtype=I32)
    anchor_mask = (
        (slots_b < state.n_blocks)
        & (bl.kind == BLOCK_ROOT_ANCHOR)
        & (bl.key == r_proot)
    )
    anchor_slot = jnp.where(
        jnp.any(anchor_mask), jnp.argmax(anchor_mask).astype(I32), -1
    )
    root_row = jnp.where(r_proot >= 0, anchor_slot, -1)
    left_parent = jnp.where(left_idx >= 0, bl.parent[safe(left_idx)], -1)
    right_parent = jnp.where(right_idx >= 0, bl.parent[safe(right_idx)], -1)
    inherited_parent = jnp.where(left_idx >= 0, left_parent, right_parent)
    parent_row = jnp.where(
        r_ptag == 2,
        parent_slot,
        jnp.where(r_ptag == 1, root_row, inherited_parent),
    )
    parent_missing = linkable & (
        ((r_ptag == 2) & (parent_slot < 0))
        | ((r_ptag == 1) & (r_proot >= 0) & (anchor_slot < 0))
    )
    missing = missing | parent_missing
    linkable = linkable & ~parent_missing

    # the wire omits parent_sub when an origin is present — inherit the key
    # from the resolved left (else right) anchor (parity: block.rs:604-612)
    left_key = jnp.where(left_idx >= 0, bl.key[safe(left_idx)], -1)
    right_key = jnp.where(right_idx >= 0, bl.key[safe(right_idx)], -1)
    r_key = jnp.where(r_key >= 0, r_key, jnp.where(left_key >= 0, left_key, right_key))

    # map rows (parent_sub set) anchor on their key chain, not the sequence:
    # the no-left entry point is the chain's leftmost item (parity:
    # block.rs:541-551 — walk parent.map[sub] to the leftmost sibling).
    # Chains are scoped per (parent branch, key).
    is_map = r_key >= 0
    slots = jnp.arange(_capacity(bl), dtype=I32)
    chain_mask = (
        (slots < state.n_blocks)
        & (bl.key == r_key)
        & (bl.parent == parent_row)
        & (bl.left == -1)
        & is_map
    )
    chain_head = jnp.where(jnp.any(chain_mask), jnp.argmax(chain_mask).astype(I32), -1)
    # the no-left sequence entry point is the parent branch's head
    seq_head = jnp.where(
        parent_row >= 0, bl.head[safe(parent_row)], state.start
    )
    anchor0 = jnp.where(is_map, chain_head, seq_head)

    # --- conflict scan (parity: block.rs:537-602) ---
    right_left = jnp.where(right_idx >= 0, bl.left[safe(right_idx)], -1)
    need_scan = linkable & (
        ((left_idx < 0) & ((right_idx < 0) | (right_left >= 0)))
        | ((left_idx >= 0) & (bl.right[safe(left_idx)] != right_idx))
    )
    o0 = jnp.where(
        left_idx >= 0,
        bl.right[safe(left_idx)],
        anchor0,
    )
    o0 = jnp.where(need_scan, o0, -1)
    left_scanned, scan_w, wide_w = _conflict_scan(
        state,
        client_rank,
        r_client,
        has_origin,
        origin_client,
        origin_clock,
        has_ror,
        r_rc,
        r_rk,
        right_idx,
        o0,
        left_idx,
        scan_plan=scan_plan,
    )
    left_idx = jnp.where(need_scan, left_scanned, left_idx)
    scan_width = jnp.where(need_scan, scan_w, I32(-1))
    scan_wide_trips = jnp.where(need_scan, wide_w, I32(0))

    # --- link in (parity: block.rs:614-659) ---
    j = state.n_blocks
    overflow = do & (j >= B)
    do = do & (j < B)
    linkable = linkable & (j < B)
    wj = jnp.where(do, j, B)

    has_left = linkable & (left_idx >= 0)
    right_final = jnp.where(
        has_left, bl.right[safe(left_idx)], jnp.where(linkable, anchor0, -1)
    )
    # left.right = j ; branch head = j when no left (sequence rows only —
    # map rows never touch the head, parity: block.rs:618-632)
    w_left = jnp.where(has_left, left_idx, B)
    new_right_col = _set(bl.right, w_left, j)
    new_head = linkable & ~has_left & ~is_map
    new_start = jnp.where(new_head & (parent_row < 0), j, state.start)
    w_head = jnp.where(new_head & (parent_row >= 0), parent_row, B)
    new_head_col = _set(bl.head, w_head, j)
    # right.left = j
    w_right = jnp.where(linkable & (right_final >= 0), right_final, B)
    new_left_col = _set(bl.left, w_right, j)

    # self-delete on arrival (parity: block.rs:751-765): a row whose parent
    # branch item is tombstoned, or a map row that lands with a right
    # neighbor (a losing concurrent write), integrates directly as deleted
    parent_deleted = (parent_row >= 0) & bl.deleted[safe(parent_row)]
    dead_on_arrival = linkable & (
        parent_deleted | (is_map & (right_final >= 0))
    )
    row_deleted = is_gc | (r_kind == CONTENT_DELETED) | dead_on_arrival
    row_countable = (
        ~row_deleted & (r_kind != CONTENT_FORMAT) & (r_kind != CONTENT_MOVE)
    )

    # moved-range inheritance (parity: block.rs:677-702 / store.py): an
    # insert between two rows owned by the same move inherits its owner; a
    # mismatch defers to the end-of-update recompute pass (moves_dirty)
    left_moved = jnp.where(
        has_left, bl.moved[safe(left_idx)], -1
    )
    right_moved = jnp.where(right_final >= 0, bl.moved[safe(right_final)], -1)
    inherit_moved = jnp.where(left_moved == right_moved, left_moved, -1)
    moved_conflict = linkable & (left_moved != right_moved)
    is_move_row = r_valid & (r_kind == CONTENT_MOVE)
    moves_dirty = moved_conflict | is_move_row

    new_bl = BlockCols(
        client=_set(bl.client, wj, r_client),
        clock=_set(bl.clock, wj, clock),
        length=_set(bl.length, wj, length),
        origin_client=_set(bl.origin_client, wj, jnp.where(has_origin, origin_client, -1)),
        origin_clock=_set(bl.origin_clock, wj, jnp.where(has_origin, origin_clock, 0)),
        ror_client=_set(bl.ror_client, wj, jnp.where(has_ror, r_rc, -1)),
        ror_clock=_set(bl.ror_clock, wj, jnp.where(has_ror, r_rk, 0)),
        left=_set(new_left_col, wj, jnp.where(linkable, left_idx, -1)),
        right=_set(new_right_col, wj, jnp.where(linkable, right_final, -1)),
        deleted=_set(bl.deleted, wj, row_deleted),
        countable=_set(bl.countable, wj, row_countable),
        kind=_set(bl.kind, wj, r_kind),
        content_ref=_set(bl.content_ref, wj, r_ref),
        content_off=_set(bl.content_off, wj, c_off),
        key=_set(bl.key, wj, r_key),
        parent=_set(bl.parent, wj, parent_row),
        head=_set(new_head_col, wj, -1),
        moved=_set(bl.moved, wj, jnp.where(linkable, inherit_moved, -1)),
        mv_sc=_set(bl.mv_sc, wj, jnp.where(is_move_row, r_mv_sc, -1)),
        mv_sk=_set(bl.mv_sk, wj, jnp.where(is_move_row, r_mv_sk, 0)),
        mv_sa=_set(bl.mv_sa, wj, jnp.where(is_move_row, r_mv_sa, 0)),
        mv_ec=_set(bl.mv_ec, wj, jnp.where(is_move_row, r_mv_ec, -1)),
        mv_ek=_set(bl.mv_ek, wj, jnp.where(is_move_row, r_mv_ek, 0)),
        mv_ea=_set(bl.mv_ea, wj, jnp.where(is_move_row, r_mv_ea, 0)),
        mv_prio=_set(bl.mv_prio, wj, jnp.where(is_move_row, r_mv_prio, -1)),
        origin_slot=_set(bl.origin_slot, wj, origin_slot_j),
    )
    # a map row that became its chain's tail is the key's new live value;
    # the previous winner — its immediate left — gets tombstoned (parity:
    # block.rs:637-659 "this is the current attribute value ... delete")
    new_tail = linkable & is_map & (right_final < 0)
    w_prev = jnp.where(new_tail & has_left, left_idx, B)
    new_bl = new_bl._replace(deleted=_set(new_bl.deleted, w_prev, True))
    error = (
        state.error
        | jnp.where(overflow, ERR_CAPACITY, 0)
        | jnp.where(missing, ERR_MISSING_DEP, 0)
    )
    out = DocStateBatch(
        blocks=new_bl,
        start=new_start,
        n_blocks=state.n_blocks + do.astype(I32),
        error=error,
    )
    return out, moves_dirty, scan_width, scan_wide_trips


def _apply_delete_range(state: DocStateBatch, client, start, end, valid):
    """Tombstone [start, end) of `client` (parity: transaction.rs:472-575).

    Returns (state, hit_move): hit_move is True when the range tombstoned a
    ContentMove row (its claims must then be released by the recompute)."""
    probe = jnp.where(valid, client, -2)
    # split the head block at `start` (only non-deleted blocks get split)
    i = _find_slot(state.blocks, state.n_blocks, probe, start)
    i_ok = (i >= 0) & ~state.blocks.deleted[jnp.maximum(i, 0)]
    off = start - state.blocks.clock[jnp.maximum(i, 0)]
    state, _ = _split(state, jnp.where(i_ok, i, -1), off)
    # split the tail block at `end`
    k = _find_slot(state.blocks, state.n_blocks, probe, end - 1)
    k_ok = (k >= 0) & ~state.blocks.deleted[jnp.maximum(k, 0)]
    off_k = end - state.blocks.clock[jnp.maximum(k, 0)]
    state, _ = _split(state, jnp.where(k_ok, k, -1), off_k)
    # mark fully covered blocks
    bl = state.blocks
    B = _capacity(bl)
    slots = jnp.arange(B, dtype=I32)
    mask = (
        valid
        & (slots < state.n_blocks)
        & (bl.client == client)
        & (bl.clock >= start)
        & (bl.clock + bl.length <= end)
    )
    hit_move = jnp.any(mask & (bl.kind == CONTENT_MOVE) & ~bl.deleted)
    state = state._replace(blocks=bl._replace(deleted=bl.deleted | mask))
    return state, hit_move


def _resolve_move_ptr(state: DocStateBatch, c, k, assoc, enable):
    """Sticky (client, clock, assoc) -> first in-range slot.

    assoc After (>= 0): the item starting at the id (split to a clean
    start); assoc Before: the right neighbor of the item *ending* at the id
    — the exclusive-bound convention of moving.rs:100-111.
    """
    after = assoc >= 0
    probe_a = jnp.where(enable & after, c, -2)
    state, i_a = _clean_start(state, probe_a, k)
    probe_b = jnp.where(enable & ~after, c, -2)
    state, i_b = _clean_end(state, probe_b, k)
    right_b = jnp.where(i_b >= 0, state.blocks.right[jnp.maximum(i_b, 0)], -1)
    found = jnp.where(after, i_a >= 0, i_b >= 0)
    return state, jnp.where(after, i_a, right_b), found


def _claim_move(state: DocStateBatch, s, enable, client_rank: jax.Array):
    """Walk move row `s`'s range, claiming rows it beats.

    Parity: Move::integrate_block (moving.rs:149-227). The 'takes'
    comparison is the total order (priority, real client id, clock) — ties
    on priority fall to the move item's id, so one claim pass per active
    move in any order converges to the reference fixpoint. find_move_loop
    cleanup (nested move cycles, moving.rs:113-141) is host-oracle-only.
    """
    bl = state.blocks
    safe_s = jnp.maximum(s, 0)
    state, start, s_found = _resolve_move_ptr(
        state, bl.mv_sc[safe_s], bl.mv_sk[safe_s], bl.mv_sa[safe_s], enable
    )
    state, endp, e_found = _resolve_move_ptr(
        state, bl.mv_ec[safe_s], bl.mv_ek[safe_s], bl.mv_ea[safe_s], enable
    )
    bl = state.blocks  # re-read: resolution may have split blocks
    # branch-scoped bounds (id client -1): sequence head / tail of the MOVE
    # ROW'S OWN branch (moving.rs get_coords' None-bound convention) — the
    # root start for root rows, the parent's head column for nested ones
    par = bl.parent[safe_s]
    seq_head = jnp.where(par < 0, state.start, bl.head[jnp.maximum(par, 0)])
    start = jnp.where(bl.mv_sc[safe_s] < 0, seq_head, start)
    endp = jnp.where(bl.mv_ec[safe_s] < 0, -1, endp)
    # a move whose range bounds aren't materialized yet must fail loudly —
    # the host stash (partition_carriers) defers such rows, so reaching
    # here with an unresolved id-scoped bound is a missing dependency
    unresolved = enable & (
        ((bl.mv_sc[safe_s] >= 0) & ~s_found)
        | ((bl.mv_ec[safe_s] >= 0) & ~e_found)
    )
    state = state._replace(
        error=state.error | jnp.where(unresolved, ERR_MISSING_DEP, 0)
    )
    enable = enable & ~unresolved  # an unresolved end would read as "tail"
    B = _capacity(bl)
    prio_s = bl.mv_prio[safe_s]
    rank_s = client_rank[jnp.maximum(bl.client[safe_s], 0)]
    clock_s = bl.clock[safe_s]

    def cond(carry):
        moved_col, deleted_col, cur, n = carry
        return enable & (cur >= 0) & (cur != endp) & (n <= B)

    def body(carry):
        moved_col, deleted_col, cur, n = carry
        sc = jnp.maximum(cur, 0)
        m = moved_col[sc]
        sm = jnp.maximum(m, 0)
        prev_prio = jnp.where(m >= 0, bl.mv_prio[sm], -1)
        prev_rank = client_rank[jnp.maximum(bl.client[sm], 0)]
        prev_clock = bl.clock[sm]
        takes = (prev_prio < prio_s) | (
            (prev_prio == prio_s)
            & (m >= 0)
            & (
                (prev_rank < rank_s)
                | ((prev_rank == rank_s) & (prev_clock < clock_s))
            )
        )
        # a beaten *collapsed* move is tombstoned on the spot (parity:
        # _delete_as_cleanup at moving.rs:190-196; the recompute pass
        # replays claims in slot = arrival order, so this side effect
        # matches the oracle's arrival-order behavior)
        m_collapsed = (
            (m >= 0)
            & (bl.mv_sc[sm] >= 0)
            & (bl.mv_sc[sm] == bl.mv_ec[sm])
            & (bl.mv_sk[sm] == bl.mv_ek[sm])
        )
        deleted_col = deleted_col.at[sm].set(
            (takes & m_collapsed) | deleted_col[sm]
        )
        moved_col = moved_col.at[sc].set(jnp.where(takes, s, m))
        return moved_col, deleted_col, bl.right[sc], n + 1

    moved_col, deleted_col, _, _ = jax.lax.while_loop(
        cond, body, (bl.moved, bl.deleted, start, jnp.zeros((), I32))
    )
    return state._replace(
        blocks=bl._replace(moved=moved_col, deleted=deleted_col)
    )


def _move_cycle(state: DocStateBatch, s) -> jax.Array:
    """Is move row `s` inside an ownership cycle after its claim pass?

    Device analogue of `find_move_loop` (moving.rs:113-141): ownership is
    single-parent (each row has one `moved` owner), so a cycle reachable
    from `s` must contain `s` — i.e. `s` appears among its own
    move-descendants. Computed as a monotone reachability fixpoint.
    """
    bl = state.blocks
    B = _capacity(bl)
    slots = jnp.arange(B, dtype=I32)
    live_move = (
        (slots < state.n_blocks) & (bl.kind == CONTENT_MOVE) & ~bl.deleted
    )
    owner = jnp.maximum(bl.moved, 0)
    has_owner = bl.moved >= 0
    d0 = live_move & (bl.moved == s)

    def cond(carry):
        _, changed = carry
        return changed

    def body(carry):
        d, _ = carry
        d2 = d | (live_move & has_owner & d[owner])
        return d2, jnp.any(d2 != d)

    d, _ = jax.lax.while_loop(cond, body, (d0, jnp.any(d0)))
    return d[jnp.maximum(s, 0)] & (s >= 0)


@jax.named_scope("move_recompute")
def _recompute_moves(
    state: DocStateBatch, dirty, client_rank: jax.Array
) -> DocStateBatch:
    """Recompute move ownership from scratch for a dirty doc.

    Releases every claim, then runs one claim pass per live move row. The
    result is the reference steady state (owner of a row = the maximal
    (priority, client, clock) non-deleted move whose resolved range covers
    it): Move::integrate_block's incremental claims and its delete-time
    override reintegration (moving.rs:229-280) both converge to that same
    argmax, because each pairwise 'takes' keeps the maximum. Clean docs
    (`dirty` False) exit the loop without iterating.

    A claim that closes an ownership cycle tombstones its move row and
    restarts the recompute without it (`_delete_as_cleanup` parity,
    moving.rs:190-196 via find_move_loop): each restart permanently
    removes one move, so the loop terminates.
    """
    bl = state.blocks
    B = _capacity(bl)
    slots = jnp.arange(B, dtype=I32)
    state = state._replace(
        blocks=bl._replace(moved=jnp.where(dirty, -1, bl.moved))
    )

    def active_moves(st, done):
        return (
            (slots < st.n_blocks)
            & (st.blocks.kind == CONTENT_MOVE)
            & ~st.blocks.deleted
            & ~done
        )

    def cond(carry):
        st, done = carry
        return dirty & jnp.any(active_moves(st, done))

    def body(carry):
        st, done = carry
        am = active_moves(st, done)
        exists = jnp.any(am)
        s = jnp.where(exists, jnp.argmax(am).astype(I32), -1)
        st = _claim_move(st, s, dirty & exists, client_rank)
        cyc = _move_cycle(st, s) & exists & dirty
        bl2 = st.blocks
        safe_s = jnp.maximum(s, 0)
        st = st._replace(
            blocks=bl2._replace(
                deleted=bl2.deleted.at[safe_s].set(
                    cyc | bl2.deleted[safe_s]
                ),
                # cycle: release EVERY claim and replay without s
                moved=jnp.where(cyc, -1, bl2.moved),
            )
        )
        done = jnp.where(
            cyc,
            jnp.zeros((B,), bool),
            done.at[safe_s].set(exists | done[safe_s]),
        )
        return st, done

    state, _ = jax.lax.while_loop(cond, body, (state, jnp.zeros((B,), bool)))
    return state


def _apply_update_one_doc(
    state: DocStateBatch,
    batch: UpdateBatch,
    client_rank: jax.Array,
    scan_plan: Optional[tuple] = None,
):
    """Returns ``(state, scan_hist)`` — scan_hist is the per-doc
    conflict-scan record ``[SCAN_REC_WORDS]`` i32 (pow2 bucket counts,
    max width, ISSUE-12 tier occupancy + trip accounting) accumulated
    over this batch's rows; callers that only want the state drop it
    (XLA DCEs the counter when the output is unused)."""
    if scan_plan is None:
        scan_plan = scan_tier_plan()
    U = batch.client.shape[-1]
    R = batch.del_client.shape[-1]

    def blk_body(i, carry):
        st, dirty, hist = carry
        row = (
            batch.client[i],
            batch.clock[i],
            batch.length[i],
            batch.origin_client[i],
            batch.origin_clock[i],
            batch.ror_client[i],
            batch.ror_clock[i],
            batch.kind[i],
            batch.content_ref[i],
            batch.content_off[i],
            batch.key[i],
            batch.p_tag[i],
            batch.p_client[i],
            batch.p_clock[i],
            batch.p_root[i],
            batch.mv_sc[i],
            batch.mv_sk[i],
            batch.mv_sa[i],
            batch.mv_ec[i],
            batch.mv_ek[i],
            batch.mv_ea[i],
            batch.mv_prio[i],
            batch.valid[i],
        )
        # padding rows skip all work; with a broadcast (unbatched) update the
        # predicate is scalar, so XLA executes only one branch
        st, d, w, wt = jax.lax.cond(
            batch.valid[i],
            lambda s: _integrate_row(s, row, client_rank, scan_plan),
            lambda s: (s, jnp.array(False), I32(-1), I32(0)),
            st,
        )
        return st, dirty | d, _fold_scan_width(hist, w, wt, scan_plan[0])

    hist0 = jnp.zeros((SCAN_REC_WORDS,), I32)
    with jax.named_scope("integrate_rows"):
        state, moves_dirty, scan_hist = jax.lax.fori_loop(
            0, U, blk_body, (state, jnp.array(False), hist0)
        )

    def del_body(r, carry):
        st, dirty = carry
        st, hit_move = jax.lax.cond(
            batch.del_valid[r],
            lambda s: _apply_delete_range(
                s,
                batch.del_client[r],
                batch.del_start[r],
                batch.del_end[r],
                batch.del_valid[r],
            ),
            lambda s: (s, jnp.array(False)),
            st,
        )
        return st, dirty | hit_move

    # a tombstoned move row must release its range (and let shadowed moves
    # win again — the override-reintegration of moving.rs:229-280)
    with jax.named_scope("delete_pass"):
        state, moves_dirty = jax.lax.fori_loop(
            0, R, del_body, (state, moves_dirty)
        )
    return _recompute_moves(state, moves_dirty, client_rank), scan_hist


def apply_update_batch(
    state: DocStateBatch,
    batch: UpdateBatch,
    client_rank: jax.Array,
    scan_plan: Optional[tuple] = None,
    active: Optional[jax.Array] = None,
) -> DocStateBatch:
    """Integrate one decoded update per doc — the north-star entry point.

    `client_rank` is the [C] interned-client rank table (shared by all docs).
    `scan_plan` is the two-tier static (None = `scan_tier_plan()` read at
    trace time — the public wrapper re-reads per call and threads it, so
    a changed knob retraces instead of silently reusing the old plan).

    `active` ([K] i32 of DISTINCT in-range slots, or None) makes the step
    compact: under `vmap` the per-row `lax.cond` runs both branches for
    every slot, so a step costs its width in rooms whether or not a room
    carries a row. With `active` the step gathers those K rooms' planes
    of the state, integrates `[K, ...]`, and scatters the rooms back;
    every other room's planes are carried over untouched, which is what
    the dense step's identity on an all-invalid slot gives. `batch` is
    then `[K, ...]` already, row i the update of slot `active[i]`: the
    caller builds it no wider than the step (`BatchIngestor.apply_bytes`).
    One traced body either way, in two programs that differ in who owns
    the state's buffers. This entry keeps value semantics: `state` is
    still readable after the call, at the price of every plane read once
    and written once. The state's owner calls `apply_update_batch_in_place`
    (`BatchIngestor`), which donates `state` and nothing else: XLA aliases
    each of its buffers to its output, so a compact step writes K rooms
    where they are (PERF.md section 6, PR 46).

    `batch` may be a `PackedBatch`, taken apart here: the served path
    hands the pair from every call site, so a process builds one form of
    this program a bucket and no step hands 27 buffers across.
    """
    batch = unpack_batch(batch)
    step = jax.vmap(
        lambda s, b, cr: _apply_update_one_doc(s, b, cr, scan_plan),
        in_axes=(0, 0, None),
    )
    if active is None:
        state, _hist = step(state, batch, client_rank)
        return state
    if batch.client.shape[0] != active.shape[0]:
        raise ValueError(
            f"a compact step over {active.shape[0]} slots takes a batch of "
            f"as many rows, not {batch.client.shape[0]}"
        )
    with jax.named_scope("compact_gather"):
        sub_state = jax.tree.map(lambda a: a[active], state)
    sub_state, _hist = step(sub_state, batch, client_rank)
    # a plain scatter on the room axis, outside any vmap: the form
    # `ingest.merge_stream` proved right on the chip
    with jax.named_scope("compact_scatter"):
        return jax.tree.map(
            lambda full, sub: full.at[active].set(sub), state, sub_state
        )


# One traced function, two programs: the profiler names both module
# `jit_apply_update_batch` and their ops `jit(apply_update_batch)/...`
# (what the benchmark's readers look for). `scan_plan` is static in both.
_apply_update_batch_jit = jax.jit(apply_update_batch, static_argnums=3)
# the owner's: operand 0 is consumed, every other operand (the kept batch,
# the rank table, `active`) outlives the call
_apply_update_batch_in_place_jit = jax.jit(
    apply_update_batch, static_argnums=3, donate_argnums=0
)


def _apply_update_stream_hist_body(
    state: DocStateBatch,
    stream: UpdateBatch,
    client_rank: jax.Array,
    scan_plan: Optional[tuple] = None,
):
    """Integrate a whole stream of updates per doc in one compiled program.

    `stream` leaves carry a leading step axis [S, ...] *without* a doc axis:
    each step's update is broadcast to every doc slot (the multi-tenant
    replay shape of BASELINE.md config #2). `lax.scan` amortizes dispatch —
    wall-clock per step is pure device time.

    Returns ``(state, scan_hist)``: scan_hist is the per-doc
    ``[D, SCAN_REC_WORDS]`` conflict-scan record (bucket counts, tier
    occupancy and trip words summed over the stream; per-doc max width —
    ISSUE-11/12). The public wrapper discards it; `apply_update_stream_raw`
    hands it back (tests/test_scan_tiers.py reads the tier words there).
    `scan_plan` is the two-tier static (None = `scan_tier_plan()` at
    trace time; a caller may thread its own static through).
    """
    D = state.start.shape[0]
    if scan_plan is None:
        scan_plan = scan_tier_plan()

    def step(carry, batch):
        st, hist = carry
        st, h = jax.vmap(
            _apply_update_one_doc, in_axes=(0, None, None, None)
        )(st, batch, client_rank, scan_plan)
        return (st, merge_scan_records(hist, h)), None

    hist0 = jnp.zeros((D, SCAN_REC_WORDS), I32)
    (state, scan_hist), _ = jax.lax.scan(step, (state, hist0), stream)
    return state, scan_hist


# the tuple-returning jit: tests call it for the scan record; the chunk
# programs that traced through it left with the replay drivers (PR 48),
# and the served path runs `apply_update_batch`. `scan_plan` is a
# STATIC argument (a changed tier plan must recompile: it shapes the
# traced loops).
apply_update_stream = partial(jax.jit, donate_argnums=0, static_argnums=3)(
    _apply_update_stream_hist_body
)
apply_update_stream.__doc__ = _apply_update_stream_hist_body.__doc__


@partial(jax.jit, static_argnums=2)
def encode_diff_batch(state: DocStateBatch, remote_sv: jax.Array, n_clients: int):
    """Device half of the batched sync step 2 (north-star encode_diff_batch).

    For every (doc, block): should it ship to a remote whose state vector is
    `remote_sv[d]` ([D, C] i32 over interned clients), and from which clock
    offset? Mirrors `Store::write_blocks_from` / `diff_state_vectors`
    (reference store.rs:204-248) as pure tensor ops:

    returns (ship_mask [D, B] bool, offsets [D, B] i32, local_sv [D, C] i32,
    deleted [D, B] bool). The host finisher gathers selected rows (sorted by
    client desc, clock asc per the wire contract) and emits bytes from the
    payload store.
    """
    from ytpu.ops.state_vector import sv_from_blocks

    bl = state.blocks
    B = bl.client.shape[-1]
    slots = jnp.arange(B, dtype=I32)
    valid = (slots[None, :] < state.n_blocks[:, None]) & (bl.client >= 0)
    # remote clock per block row (gather along the client axis)
    safe_client = jnp.clip(bl.client, 0, n_clients - 1)
    remote_clock = jnp.take_along_axis(remote_sv, safe_client, axis=1)
    end = bl.clock + bl.length
    ship = valid & (end > remote_clock)
    offsets = jnp.clip(remote_clock - bl.clock, 0, None) * ship
    local_sv = sv_from_blocks(bl.client, bl.clock, bl.length, n_clients)
    return ship, offsets, local_sv, bl.deleted & valid


_encode_diff_batch_jit = encode_diff_batch


def encode_diff_batch(
    state: DocStateBatch, remote_sv: jax.Array, n_clients: int
):
    from ytpu.utils.phases import NULL_SPAN, phases, program_memory

    span = (
        phases.span(
            "encode.diff_batch",
            (state.blocks.client.shape, remote_sv.shape, n_clients),
            axes=("state", "remote_sv", "n_clients"),
            memory=program_memory(
                _encode_diff_batch_jit, state, remote_sv, n_clients
            ),
        )
        if phases.enabled
        else NULL_SPAN
    )
    with span:
        return _encode_diff_batch_jit(state, remote_sv, n_clients)


encode_diff_batch.__doc__ = _encode_diff_batch_jit.__doc__


@jax.jit
def state_capacity_ledger(state: DocStateBatch):
    """Per-doc ``([D] live, [D] dead)`` block-row counts (ISSUE-18):
    live rows are allocations inside the ``n_blocks`` prefix that are
    not tombstoned; dead rows the tombstoned (GC-able) remainder —
    the same validity predicate `encode_diff_batch` ships by. Free
    rows per doc are ``capacity - live - dead``, so the per-tenant
    occupancy gauges always sum to the slot capacity. NOT a hot-path
    call: scrape-time `/snapshot` sections and tests materialize it on
    demand (one reduction over the block columns, one pull of
    two `[D]` vectors)."""
    bl = state.blocks
    B = bl.client.shape[-1]
    slots = jnp.arange(B, dtype=jnp.int32)
    valid = (slots[None, :] < state.n_blocks[:, None]) & (bl.client >= 0)
    dead = jnp.sum((valid & (bl.deleted != 0)).astype(jnp.int32), axis=1)
    return state.n_blocks.astype(jnp.int32) - dead, dead


def finish_encode_diff(
    state: DocStateBatch,
    doc: int,
    ship: np.ndarray,
    offsets: np.ndarray,
    deleted: np.ndarray,
    enc: "BatchEncoder",
    payloads=None,
    root_name: Optional[str] = None,
) -> bytes:
    """Host finisher: selected device rows -> a v1 update payload.

    Emits the same wire layout as the host oracle (clients descending,
    clock-contiguous runs, first block offset-trimmed) from the device block
    columns + payload side-buffers. Pass `payloads` (e.g. a BatchIngestor's
    `ChunkedWirePayloads`) when the state holds device-decoded rows whose
    refs live in the chunked (<= -2) space; defaults to `enc.payloads`.
    """
    if payloads is None:
        payloads = enc.payloads
    from ytpu.encoding.codec import EncoderV1
    from ytpu.core.id_set import DeleteSet

    bl = jax.tree.map(lambda a: np.asarray(a[doc]), state.blocks)
    rows = np.nonzero(ship[doc])[0]
    per_client: Dict[int, List[int]] = {}
    for r in rows:
        per_client.setdefault(int(bl.client[r]), []).append(int(r))
    out = EncoderV1()
    out.write_var(len(per_client))
    for cidx in sorted(per_client, key=lambda c: -enc.interner.from_idx[c]):
        slots = sorted(per_client[cidx], key=lambda r: int(bl.clock[r]))
        real_client = enc.interner.from_idx[cidx]
        out.write_var(len(slots))
        out.write_client(real_client)
        first_off = int(offsets[doc][slots[0]])
        out.write_var(int(bl.clock[slots[0]]) + first_off)
        for pos, r in enumerate(slots):
            off = first_off if pos == 0 else 0
            _encode_device_row(
                out, bl, r, off, real_client, enc, payloads, root_name
            )
    ds = DeleteSet()
    for r in np.nonzero(deleted[doc])[0]:
        real_client = enc.interner.from_idx[int(bl.client[r])]
        ds.insert_range(real_client, int(bl.clock[r]), int(bl.clock[r] + bl.length[r]))
    ds.encode(out)
    return out.to_bytes()


def _encode_device_row(
    out, bl, r, off, real_client, enc: "BatchEncoder", payloads=None,
    root_name: Optional[str] = None,
) -> None:
    if payloads is None:
        payloads = enc.payloads

    kind = int(bl.kind[r])
    if kind == BLOCK_GC:
        out.write_info(BLOCK_GC)
        out.write_len(int(bl.length[r]) - off)
        return
    oc, ok = int(bl.origin_client[r]), int(bl.origin_clock[r])
    rc, rk = int(bl.ror_client[r]), int(bl.ror_clock[r])
    clock = int(bl.clock[r])
    if off > 0:
        oc, ok = int(bl.client[r]), clock + off - 1
    has_o, has_r = oc >= 0, rc >= 0
    key = int(bl.key[r])
    has_sub = key >= 0
    info = (
        kind
        | (0x80 if has_o else 0)
        | (0x40 if has_r else 0)
        | (0x20 if has_sub else 0)  # HAS_PARENT_SUB (parity: block.rs:868-908)
    )
    out.write_info(info)
    if has_o:
        out.write_left_id(ID(enc.interner.from_idx[oc], ok))
    if has_r:
        out.write_right_id(ID(enc.interner.from_idx[rc], rk))
    if not has_o and not has_r:
        parent_row = int(bl.parent[r])
        if parent_row >= 0 and int(bl.kind[parent_row]) == BLOCK_ROOT_ANCHOR:
            # non-primary named root: the anchor row has no wire identity —
            # re-emit the root-name form with the anchor's interned name
            out.write_parent_info(True)
            out.write_string(enc.keys.names[int(bl.key[parent_row])])
        elif parent_row >= 0:
            # nested branch: parent is the ContentType item's id
            out.write_parent_info(False)
            out.write_left_id(
                ID(
                    enc.interner.from_idx[int(bl.client[parent_row])],
                    int(bl.clock[parent_row]),
                )
            )
        else:
            out.write_parent_info(True)
            # per-tenant root name (serving) falls back to the batch root
            out.write_string(root_name if root_name is not None else enc.root_name)
        if has_sub:
            out.write_string(enc.keys.names[key])
    ref = int(bl.content_ref[r])
    c_off = int(bl.content_off[r]) + off
    length = int(bl.length[r]) - off
    if kind == CONTENT_STRING:
        out.write_string(payloads.slice_text(ref, c_off, length))
    elif kind == CONTENT_ANY:
        out.write_len(length)
        for v in payloads.slice_values(ref, c_off, length):
            out.write_any(v)
    elif kind == CONTENT_DELETED:
        out.write_len(length)
    elif ref < 0 and kind == CONTENT_FORMAT:
        fkey, fval = payloads.format_kv(ref)
        out.write_key(fkey)
        out.write_json(fval)
    elif ref < 0 and kind == CONTENT_EMBED:
        out.write_json(payloads.embed_value(ref))
    elif ref < 0 and kind == CONTENT_BINARY:
        out.write_buf(payloads.binary_value(ref))
    elif ref < 0 and kind == CONTENT_JSON:
        raw = payloads.json_raw(ref, c_off, length)
        out.write_len(len(raw))
        for s in raw:
            out.write_string(s)
    elif ref < -1 and kind == CONTENT_TYPE:
        # device-retained wire span: re-emit the original bytes verbatim
        out.write_raw(payloads.type_raw(ref))
    else:
        # other payload kinds stash the host content object directly
        content = payloads.items[ref][1]
        content.encode(out)


def _payload_native_arenas(store) -> dict:
    """Per-item arenas for the native finisher, cached on the PayloadStore.

    The store is append-only, so the cache extends incrementally: UTF-16LE
    text bytes for string payloads, pre-encoded content blobs (the exact
    bytes `content.encode(EncoderV1())` emits — the Python finisher's
    else-branch), and per-element pre-encoded `write_any` bytes for
    ContentAny payloads.
    """
    from ytpu.encoding.codec import EncoderV1

    ar = getattr(store, "_nat_arena", None)
    if ar is None:
        ar = {
            "n": 0,
            "text": bytearray(),
            "text_off": [],
            "text_units": [],
            "blob": bytearray(),
            "blob_off": [],
            "blob_len": [],
            "elem_base": [],
            "elem_count": [],
            "elem_off": [0],
            "elem": bytearray(),
        }
        store._nat_arena = ar
    items = store.items
    for i in range(ar["n"], len(items)):
        kind, payload = items[i]
        text_off = blob_off = blob_len = elem_base = -1
        text_units = elem_count = 0
        if kind == CONTENT_STRING and isinstance(payload, (bytes, bytearray)):
            text_off = len(ar["text"])
            text_units = len(payload) // 2
            ar["text"] += payload
        elif kind == CONTENT_ANY and isinstance(payload, list):
            elem_base = len(ar["elem_off"]) - 1
            elem_count = len(payload)
            for v in payload:
                enc = EncoderV1()
                enc.write_any(v)
                ar["elem"] += enc.to_bytes()
                ar["elem_off"].append(len(ar["elem"]))
        else:
            try:
                enc = EncoderV1()
                payload.encode(enc)
                blob = enc.to_bytes()
                blob_off = len(ar["blob"])
                blob_len = len(blob)
                ar["blob"] += blob
            except Exception:
                pass  # row falls back to the Python finisher
        ar["text_off"].append(text_off)
        ar["text_units"].append(text_units)
        ar["blob_off"].append(blob_off)
        ar["blob_len"].append(blob_len)
        ar["elem_base"].append(elem_base)
        ar["elem_count"].append(elem_count)
    ar["n"] = len(items)

    # numpy mirrors, rebuilt only when the store grew — a long-lived server
    # answering single-doc syncs must not re-copy the whole store per reply
    key = (ar["n"], len(ar["text"]), len(ar["blob"]), len(ar["elem"]))
    if ar.get("np_key") != key:
        ar["np"] = {
            "text": np.frombuffer(bytes(ar["text"]) or b"\0", dtype=np.uint8),
            "blob": np.frombuffer(bytes(ar["blob"]) or b"\0", dtype=np.uint8),
            "elem": np.frombuffer(bytes(ar["elem"]) or b"\0", dtype=np.uint8),
            "text_off": np.asarray(ar["text_off"] or [0], dtype=np.int64),
            "text_units": np.asarray(ar["text_units"] or [0], dtype=np.int64),
            "blob_off": np.asarray(ar["blob_off"] or [0], dtype=np.int64),
            "blob_len": np.asarray(ar["blob_len"] or [0], dtype=np.int64),
            "elem_base": np.asarray(ar["elem_base"] or [0], dtype=np.int64),
            "elem_count": np.asarray(ar["elem_count"] or [0], dtype=np.int64),
            "elem_off": np.asarray(ar["elem_off"] or [0], dtype=np.int64),
        }
        ar["np_key"] = key
    return ar


def _wire_concat(payloads) -> np.ndarray:
    """One contiguous buffer over a ChunkedWirePayloads' retained chunks
    (refs <= -2 index into it directly). Grows incrementally — chunk lists
    are append-only across calls (drop_if_unreferenced only fires within
    an ingest step), so each call copies only the chunks added since the
    last one, not the whole history."""
    state = getattr(payloads, "_nat_wire", None)
    if state is None:
        state = {
            "arr": np.empty(4096, dtype=np.uint8),
            "len": 0,
            "n_chunks": 0,
            "gen": payloads.generation,
        }
        payloads._nat_wire = state
    chunks = payloads._chunks
    if state["gen"] != payloads.generation:
        # a retained chunk was dropped since we last looked (possibly then
        # replaced at the same base): resync from scratch
        state["len"] = 0
        state["n_chunks"] = 0
        state["gen"] = payloads.generation
    for _, flat in chunks[state["n_chunks"] :]:
        need = state["len"] + flat.size
        if need > state["arr"].size:
            grown = np.empty(max(need, state["arr"].size * 2), dtype=np.uint8)
            grown[: state["len"]] = state["arr"][: state["len"]]
            state["arr"] = grown
        state["arr"][state["len"] : need] = flat
        state["len"] = need
    state["n_chunks"] = len(chunks)
    return state["arr"][: state["len"]]


_FINISH_COLS = (
    "client",
    "clock",
    "length",
    "origin_client",
    "origin_clock",
    "ror_client",
    "ror_clock",
    "kind",
    "content_ref",
    "content_off",
    "key",
    "parent",
)


def _next_pow2(n: int, floor: int = 8) -> int:
    return max(floor, 1 << max(0, int(n - 1).bit_length()))


def _finish_include(parent, ship, deleted):
    """Rows the native finisher must see: shipped, deleted, or the parent
    of a shipped row (encode_row walks one parent hop for parentful items)."""
    B = ship.shape[1]
    pv = ship & (parent >= 0)
    spar = jnp.where(pv, parent, 0)
    incl = ship | deleted
    return jax.vmap(
        lambda inc, par, m: inc.at[jnp.where(m, par, B)].max(m, mode="drop")
    )(incl, spar, pv), pv, spar


@jax.jit
def _finish_counts(parent, ship, deleted, idx):
    g = lambda a: jnp.take(a, idx, axis=0)
    incl, _, _ = _finish_include(g(parent), g(ship), g(deleted))
    return jnp.sum(incl, axis=1, dtype=jnp.int32)


def _compact_finisher_rows_impl(bl, ship, offsets, deleted, idx, R):
    """Compact the finisher's row set to [Dsel, 15, R] i32 ON DEVICE.

    The cost of the old path was pulling every [D, B]
    block column to host (capacity-sized, ~all HBM-resident state); the
    finisher only reads shipped/deleted/parent rows, so this scatters just
    those into R slots per doc and ships ONE packed tensor. The parent
    column is remapped into the compacted index space (valid for every
    shipped row by construction; -1 elsewhere — never read by the C++
    side, which only dereferences parents of shipped rows).

    This is the per-sub-batch device stage of the `DiffPipeline`
    (ISSUE-10): one compiled program per (doc-width, R) shape family —
    both dims pow2-bucketed by the callers — serves every sub-batch, and
    the per-dispatch `idx` selection buffer is donated (it is never read
    again after the dispatch consumes it)."""
    g = lambda a: jnp.take(a, idx, axis=0)
    ship = g(ship)
    offsets = g(offsets).astype(jnp.int32)
    deleted = g(deleted)
    cols = {n: g(getattr(bl, n)).astype(jnp.int32) for n in _FINISH_COLS}
    Ds, B = ship.shape
    incl, pv, spar = _finish_include(cols["parent"], ship, deleted)
    incl_i = incl.astype(jnp.int32)
    new_idx = jnp.cumsum(incl_i, axis=1) - incl_i
    tgt = jnp.where(incl, new_idx, R)  # R is out of range -> dropped
    didx = jnp.broadcast_to(jnp.arange(Ds, dtype=jnp.int32)[:, None], (Ds, B))
    cols["parent"] = jnp.where(
        pv, jnp.take_along_axis(new_idx, spar, axis=1), -1
    )

    def compact(col):
        return jnp.zeros((Ds, R), jnp.int32).at[didx, tgt].set(col, mode="drop")

    packed = [compact(cols[n]) for n in _FINISH_COLS]
    packed.append(compact(ship.astype(jnp.int32)))
    packed.append(compact(offsets))
    packed.append(compact(deleted.astype(jnp.int32)))
    return jnp.stack(packed, axis=1)


# two compiled variants: donation of the per-dispatch idx buffer only
# where the backend can actually alias it (device). The CPU backend
# cannot, and XLA would warn "Some donated buffers were not usable"
# once per compiled (sub, R) family — a process-global filterwarnings
# would hide the (advisory, but useful) hint from the APPLICATION's own
# jax code too, so route around the warning instead of silencing it.
_compact_rows_donated = partial(
    jax.jit, static_argnums=(5,), donate_argnums=(4,)
)(_compact_finisher_rows_impl)
_compact_rows_plain = partial(jax.jit, static_argnums=(5,))(
    _compact_finisher_rows_impl
)


def _donation_usable() -> bool:
    return jax.default_backend() != "cpu"


def compact_finisher_rows(bl, ship, offsets, deleted, idx, R):
    """Dispatch `_compact_finisher_rows_impl`, donating `idx` on device
    backends (it is never read again after the dispatch consumes it).
    The `encode.pack` span keys the compiled pack family — `(sub, R)`
    via idx.shape/R plus the state width — so the retrace sentinel sees
    a family explosion the moment pow2 discipline slips (ISSUE-17)."""
    from ytpu.utils.phases import NULL_SPAN, phases, program_memory

    fn = _compact_rows_donated if _donation_usable() else _compact_rows_plain
    span = (
        phases.span(
            "encode.pack",
            (bl.client.shape, idx.shape, R),
            axes=("state", "idx", "R"),
            memory=program_memory(fn, bl, ship, offsets, deleted, idx, R),
        )
        if phases.enabled
        else NULL_SPAN
    )
    with span:
        return fn(bl, ship, offsets, deleted, idx, R)


def _compact_rows_cache_size() -> int:
    """Compiled-instance count across both variants (retrace-bound
    tests; only one variant is ever populated per process backend)."""
    return (
        _compact_rows_donated._cache_size() + _compact_rows_plain._cache_size()
    )


def _compact_rows_clear_cache() -> None:
    _compact_rows_donated.clear_cache()
    _compact_rows_plain.clear_cache()


# progbudget/test surface: the dispatch wrapper reports and evicts the
# union of both variants' executable caches
compact_finisher_rows._cache_size = _compact_rows_cache_size
compact_finisher_rows.clear_cache = _compact_rows_clear_cache
_finish_pack = compact_finisher_rows  # back-compat internal name


# Native finisher threading threshold (ISSUE-10 small fix): total
# selected rows below this run single-threaded (spawn overhead dominates);
# at/above it the C++ side fans docs across hardware threads.
FINISHER_MT_MIN_ROWS = 4096

# Test-introspection surface: per-active-doc status codes of the LAST
# native finisher call (0 = native core encoded it, 1 = fell back to the
# per-doc Python finisher).  Written by `_FinisherContext.finish` on the
# calling thread only.
LAST_FINISH_STATUSES: List[int] = []


def _finisher_threads(total_rows: int) -> int:
    """Native finisher threading decision: 0 = thread pool (hardware
    concurrency), 1 = single thread.  Keyed on the TOTAL selected rows of
    the call, not the doc count (ISSUE-10): the old ``len(docs) >= 128``
    rule let a handful of huge docs — one hot tenant shipping its whole
    history — run single-threaded, while a thousand near-empty docs paid
    pool overhead for nothing."""
    return 0 if int(total_rows) >= FINISHER_MT_MIN_ROWS else 1


def _check_doc_selection(sel_np: np.ndarray, n_docs: int) -> None:
    if sel_np.size and (sel_np.min() < 0 or sel_np.max() >= n_docs):
        # jnp.take clamps OOB indices — without this check a stale slot id
        # would silently encode the LAST doc's diff for the wrong tenant
        raise IndexError(
            f"doc selection out of range: {sel_np.min()}..{sel_np.max()} "
            f"for {n_docs} docs"
        )


def _interner_tables(enc: "BatchEncoder") -> dict:
    """Interner/key-name tables for the native finisher, cached on the
    encoder — both are append-only, so rebuild only when they grew (a
    long-lived server answering single-doc syncs must not re-copy them
    per reply)."""
    tables = getattr(enc, "_nat_tables", None)
    n_keys = len(enc.keys)
    if tables is None or tables["key"] != (len(enc.interner), n_keys):
        from_idx = np.ascontiguousarray(enc.interner.from_idx, dtype=np.int64)
        if from_idx.size == 0:
            from_idx = np.zeros(1, dtype=np.int64)
        key_names = [enc.keys.names[k].encode("utf-8") for k in range(n_keys)]
        key_blob = np.frombuffer(b"".join(key_names) or b"\0", dtype=np.uint8)
        key_off = np.zeros(n_keys + 1, dtype=np.int64)
        if key_names:
            key_off[1:] = np.cumsum([len(k) for k in key_names])
        tables = {
            "key": (len(enc.interner), n_keys),
            "from_idx": from_idx,
            "key_blob": key_blob,
            "key_off": key_off,
            "root": np.frombuffer(
                enc.root_name.encode("utf-8") or b"\0", dtype=np.uint8
            ),
        }
        enc._nat_tables = tables
    return tables


class _FinisherContext:
    """One finisher invocation family's host-side context, shared by the
    serial batched entry and the `DiffPipeline` consumer stage: the
    native library, the payload arenas + retained-wire buffer, and the
    interner/key tables, resolved ONCE per call family.  `finish()`
    turns a HOST copy of the packed [Dsel, 15, R] tensor into wire
    payloads in one native call, through the zero-copy strided arena
    entry (`ytpu_finish_batch_strided`)."""

    def __init__(self, enc: "BatchEncoder", payloads=None):
        from ytpu import native as _native
        from ytpu.ops.decode_kernel import ChunkedWirePayloads

        self.enc = enc
        self.payloads = enc.payloads if payloads is None else payloads
        self._native = _native
        lib = _native.load()
        self.lib = lib
        self.ok = lib is not None and getattr(lib, "finisher_ok", False)
        if not self.ok:
            return
        if isinstance(self.payloads, ChunkedWirePayloads):
            self.store = self.payloads.store
            wire = _wire_concat(self.payloads)
        else:
            self.store = self.payloads
            wire = np.empty(0, dtype=np.uint8)
        self.ar = _payload_native_arenas(self.store)
        wire = np.ascontiguousarray(wire, dtype=np.uint8)
        if wire.size == 0:
            wire = np.zeros(1, dtype=np.uint8)
        self.wire = wire
        self.tables = _interner_tables(enc)

    def finish(
        self,
        arr: np.ndarray,
        n_active: int,
        root_name: Optional[str],
        n_threads: int,
    ) -> List[Optional[bytes]]:
        """`arr`: [d_pad, 15, R] i32 host tensor (a drained
        `compact_finisher_rows` output), read in place through its own
        strides: only the rows need be contiguous.  The CPU hands the
        tensor back row-major; a TPU v5e hands it back PLANE-major
        (strides (4R, 4·d_pad·R, 4)), and pointer math that assumed
        row-major there read other docs' rows — most docs punted to the
        Python finisher, some encoded plausible garbage (PR 24).  Returns
        one entry per ACTIVE doc: wire bytes, or None where the native
        core punted (the caller peels those per doc through the Python
        finisher)."""
        import ctypes

        global LAST_FINISH_STATUSES
        if n_active == 0:
            LAST_FINISH_STATUSES = []  # never report a previous call's
            return []
        lib = self.lib
        enc, ar, tables = self.enc, self.ar, self.tables
        if (
            arr.dtype != np.int32
            or arr.strides[2] != 4
            or arr.strides[0] % 4
            or arr.strides[1] % 4
        ):
            arr = np.ascontiguousarray(arr, dtype=np.int32)
        d_pad, _planes, R = arr.shape
        doc_stride, plane_bytes = arr.strides[0] // 4, arr.strides[1]

        def p_i32(a):
            return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))

        def p_i64(a):
            return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))

        def p_u8(a):
            return a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))

        if root_name is not None:
            root_bytes = root_name.encode("utf-8")
            root = np.frombuffer(root_bytes or b"\0", dtype=np.uint8)
        else:
            root_bytes = enc.root_name.encode("utf-8")
            root = tables["root"]
        sel = np.arange(n_active, dtype=np.int32)
        # zero-copy column pointers straight into the packed arena:
        # plane k of doc 0 sits `plane_bytes`·k past the base, consecutive
        # docs `doc_stride` int32s apart (both read off the tensor's own
        # strides); the ship/offsets/deleted planes stay i32 — no u8
        # conversions
        base = arr.ctypes.data

        def plane(k, typ=ctypes.c_int32):
            return ctypes.cast(base + k * plane_bytes, ctypes.POINTER(typ))

        cols = {name: plane(k) for k, name in enumerate(_FINISH_COLS)}
        ship_p = plane(12, ctypes.c_uint8)
        off_p = plane(13)
        del_p = plane(14, ctypes.c_uint8)
        nparr = ar["np"]
        fin = self._native.FinishIn(
            n_docs_total=d_pad,
            n_blocks_cap=R,
            client=cols["client"],
            clock=cols["clock"],
            length=cols["length"],
            origin_client=cols["origin_client"],
            origin_clock=cols["origin_clock"],
            ror_client=cols["ror_client"],
            ror_clock=cols["ror_clock"],
            kind=cols["kind"],
            content_ref=cols["content_ref"],
            content_off=cols["content_off"],
            key=cols["key"],
            parent=cols["parent"],
            ship=ship_p,
            offsets=off_p,
            deleted=del_p,
            sel=p_i32(sel),
            n_sel=n_active,
            from_idx=p_i64(tables["from_idx"]),
            n_interned=len(enc.interner),
            key_blob=p_u8(tables["key_blob"]),
            key_off=p_i64(tables["key_off"]),
            n_keys=len(enc.keys),
            root_name=p_u8(root),
            root_name_len=len(root_bytes),
            text_arena=p_u8(nparr["text"]),
            text_arena_len=len(ar["text"]),
            item_text_off=p_i64(nparr["text_off"]),
            item_text_units=p_i64(nparr["text_units"]),
            blob_arena=p_u8(nparr["blob"]),
            blob_arena_len=len(ar["blob"]),
            item_blob_off=p_i64(nparr["blob_off"]),
            item_blob_len=p_i64(nparr["blob_len"]),
            item_elem_base=p_i64(nparr["elem_base"]),
            item_elem_count=p_i64(nparr["elem_count"]),
            elem_off=p_i64(nparr["elem_off"]),
            elem_arena=p_u8(nparr["elem"]),
            elem_arena_len=len(ar["elem"]),
            n_items=ar["n"],
            wire=p_u8(self.wire),
            wire_len=int(getattr(self.payloads, "total_bytes", 0)),
        )
        handle = lib.ytpu_finish_batch_strided(
            ctypes.byref(fin), doc_stride, n_threads
        )
        try:
            data_ptr = lib.ytpu_finish_data(handle)
            # vectorized offset/length-table handling: one native call
            # fills the span/status tables, one copy lifts the output
            # arena, and per-doc payloads are cheap bytes slices
            offs = np.empty(n_active, dtype=np.int64)
            lens = np.empty(n_active, dtype=np.int64)
            stat = np.empty(n_active, dtype=np.int32)
            lib.ytpu_finish_spans(handle, p_i64(offs), p_i64(lens), p_i32(stat))
            total = int(lib.ytpu_finish_total_len(handle))
            blob = ctypes.string_at(data_ptr, total) if total else b""
            LAST_FINISH_STATUSES = stat.tolist()
            return [
                blob[o : o + n] if s == 0 else None
                for o, n, s in zip(
                    offs.tolist(), lens.tolist(), LAST_FINISH_STATUSES
                )
            ]
        finally:
            lib.ytpu_finish_free(handle)


def finish_encode_diff_batch(
    state: DocStateBatch,
    docs,
    ship: np.ndarray,
    offsets: np.ndarray,
    deleted: np.ndarray,
    enc: "BatchEncoder",
    payloads=None,
    root_name: Optional[str] = None,
) -> List[bytes]:
    """Batched native finisher: selected device rows -> v1 payloads for
    many docs in one C++ call (VERDICT r2 #6; reference equivalent:
    store.rs:204-248 compiled). Byte-identical to `finish_encode_diff`;
    docs holding a row outside the native scope (wire-ref Format/Embed,
    unknown kinds) fall back to the Python finisher individually; wire
    ContentType spans re-emit natively (verbatim copy).
    `root_name` overrides the batch root branch name on the wire for this
    call (per-tenant serving; all selected docs share it).
    """
    docs = list(docs)
    ctx = _FinisherContext(enc, payloads)
    if not ctx.ok:
        return [
            finish_encode_diff(
                state, d, ship, offsets, deleted, enc, ctx.payloads, root_name
            )
            for d in docs
        ]

    bl = state.blocks
    D, B = bl.client.shape

    # Device-side row compaction (VERDICT r3 #3): only shipped/deleted/
    # parent rows cross the device->host boundary, as ONE [Dsel, 15, R]
    # tensor — R is the largest per-doc row set, bucketed to a power of
    # two to bound recompiles (as is the doc-selection length).
    ship_j = ship if isinstance(ship, jax.Array) else jnp.asarray(ship)
    off_j = offsets if isinstance(offsets, jax.Array) else jnp.asarray(offsets)
    del_j = deleted if isinstance(deleted, jax.Array) else jnp.asarray(deleted)
    n_sel = len(docs)
    sel_np = np.asarray(docs, dtype=np.int32)
    _check_doc_selection(sel_np, D)
    # no clamp to D: `docs` may legally repeat slots, so n_sel can exceed
    # the doc capacity; padding entries repeat the first SELECTED doc so R
    # (the packed width) is sized by the actual selection, not by doc 0
    d_pad = _next_pow2(n_sel)
    idx_np = np.full(d_pad, sel_np[0] if n_sel else 0, dtype=np.int32)
    idx_np[:n_sel] = sel_np
    idx = jnp.asarray(idx_np)
    counts = np.asarray(_finish_counts(bl.parent, ship_j, del_j, idx))
    R = min(_next_pow2(int(counts.max(initial=1))), B)
    arr = np.asarray(compact_finisher_rows(bl, ship_j, off_j, del_j, idx, R))
    # threading keys on TOTAL selected rows, not doc count (ISSUE-10)
    threads = _finisher_threads(int(counts[:n_sel].sum()))
    res = ctx.finish(arr, n_sel, root_name, threads)
    return [
        p
        if p is not None
        else finish_encode_diff(
            state, d, ship, offsets, deleted, enc, ctx.payloads, root_name
        )
        for p, d in zip(res, docs)
    ]


# --- pipelined encode/diff (ISSUE-10 tentpole) ------------------------------


@dataclass(frozen=True)
class DiffPlan:
    """Host-checkable sub-batch plan of a pipelined encode/diff run —
    what `plan_diff_pipeline` returns and tests assert on before a
    device run (sub-batch bounds, depth, buffer reuse)."""

    n_docs: int
    sub: int  # docs per sub-batch = the compiled doc width (pow2)
    n_sub: int
    depth: int  # max in-flight sub-batches per stage boundary
    idx_buffers: int  # preallocated host index slots (donated per dispatch)
    buffer_reuses: int  # times the index slot is re-filled after first use
    donate_idx: bool = True  # the device selection buffer is donated


def plan_diff_pipeline(
    n_docs: int, sub_batch: int = 512, depth: int = 2
) -> DiffPlan:
    """Size the encode pipeline's sub-batches: the sub-batch doc width is
    pow2 (ONE compiled `compact_finisher_rows` family per (sub, R) pair)
    and never exceeds the pow2 bucket of the selection itself.  One host
    index slot serves every sub-batch — `jnp.asarray` copies it at
    dispatch and the device-side copy is donated into the pack program."""
    n = max(0, int(n_docs))
    if n == 0:
        return DiffPlan(0, 0, 0, depth, 0, 0)
    sub = min(_next_pow2(int(sub_batch), 1), _next_pow2(n, 1))
    n_sub = -(-n // sub)
    return DiffPlan(
        n_docs=n,
        sub=sub,
        n_sub=n_sub,
        depth=depth,
        idx_buffers=1,
        buffer_reuses=max(0, n_sub - 1),
    )


@dataclass
class DiffStats:
    """One `DiffPipeline.run`: per-stage attribution + integrity counters."""

    n_docs: int = 0
    sub: int = 0
    n_sub: int = 0
    depth: int = 0
    R: int = 0  # compiled finisher row width (pow2)
    total_rows: int = 0  # selected rows across the whole call
    threads: int = 0  # native n_threads decision (0 = pool, 1 = single)
    select_s: float = 0.0  # device selection+compaction dispatch (staging)
    d2h_s: float = 0.0  # blocking D2H drains (the middle stage)
    finish_s: float = 0.0  # native finisher + per-doc peeling
    stall_s: float = 0.0  # consumer waited on upstream (not hidden)
    d2h_bytes: int = 0
    overlap_ratio: float = 0.0
    max_inflight: int = 0
    syncs: int = 0  # blocking host materializations (counts pull + drains)
    demotions: int = 0  # sub-batches degraded to the serial per-doc path
    fallback_docs: int = 0  # rows peeled per doc by the Python finisher
    buffer_reuses: int = 0


class DiffPipeline:
    """Staged encode/diff pipeline (ISSUE-10 tentpole): the device runs
    selection + `compact_finisher_rows` for doc sub-batch k+1 while an
    async D2H (the `OverlapPipeline` drain stage) pulls sub-batch k's
    compacted [sub, 15, R] rows and the native finisher consumes
    sub-batch k−1 — finisher calls batched per sub-batch instead of per
    doc, D2H overlapped with device encode, and the per-doc Python glue
    collapsed to vectorized offset/length tables (the encode-side replay
    of PR 5's apply overlap + PR 7's memcpy staging, in the D2H
    direction).

    Exactly ONE jitted selection→compaction program per (sub, R) shape
    family serves every sub-batch (both dims pow2-bucketed; the idx
    selection buffer is donated per dispatch), and ONE blocking counts
    pull sizes R for the whole call — so a run performs `n_sub + 1` host
    materializations total, nothing per doc.

    Degradation (fault sites `diff.d2h_fail` / `finisher.raise`, plus
    any real D2H/native failure): the failing SUB-BATCH demotes to the
    serial per-doc Python finisher path — counted by `encode.demotions`
    — instead of dropping the diff; byte output is identical either way.

    Gauges (docs/observability.md §Encode pipeline): `encode.select`,
    `encode.d2h_bytes`, `encode.finish`, plus the engine's
    `encode.stage`/`encode.drain`/`encode.stall`/`encode.overlap_ratio`/
    `encode.inflight_depth` when ≥2 sub-batches actually pipeline."""

    def __init__(self, sub_batch: int = 512, depth: int = 2):
        if depth < 1:
            raise ValueError(f"depth must be >= 1, got {depth}")
        if sub_batch < 1:
            raise ValueError(f"sub_batch must be >= 1, got {sub_batch}")
        self.sub_batch = sub_batch
        self.depth = depth
        self.stats = DiffStats()

    def plan(self, n_docs: int) -> DiffPlan:
        return plan_diff_pipeline(n_docs, self.sub_batch, self.depth)

    def run(
        self,
        state: DocStateBatch,
        docs,
        ship,
        offsets,
        deleted,
        enc: "BatchEncoder",
        payloads=None,
        root_name: Optional[str] = None,
    ) -> List[bytes]:
        """Drop-in replacement for `finish_encode_diff_batch` over the
        same selection outputs; byte-identical payloads, pipelined."""
        from ytpu.models.overlap import OverlapPipeline
        from ytpu.utils import metrics
        from ytpu.utils.faults import faults
        from ytpu.utils.phases import phases

        docs = list(docs)
        n_sel = len(docs)
        stats = self.stats = DiffStats(
            n_docs=n_sel, depth=self.depth
        )
        if n_sel == 0:
            return []
        metrics.counter("encode.pipeline_runs").inc()
        ctx = _FinisherContext(enc, payloads)
        if not ctx.ok:
            # no native finisher → nothing to batch against; the per-doc
            # Python path serves the whole selection (parity unchanged)
            stats.fallback_docs = n_sel
            return [
                finish_encode_diff(
                    state, d, ship, offsets, deleted, enc, ctx.payloads,
                    root_name,
                )
                for d in docs
            ]
        bl = state.blocks
        D, B = bl.client.shape
        ship_j = ship if isinstance(ship, jax.Array) else jnp.asarray(ship)
        off_j = (
            offsets if isinstance(offsets, jax.Array) else jnp.asarray(offsets)
        )
        del_j = (
            deleted if isinstance(deleted, jax.Array) else jnp.asarray(deleted)
        )
        sel_np = np.asarray(docs, dtype=np.int32)
        _check_doc_selection(sel_np, D)
        plan = self.plan(n_sel)
        sub, n_sub = plan.sub, plan.n_sub
        stats.sub, stats.n_sub = sub, n_sub

        # ONE counts pull for the whole selection (a single blocking
        # sync); R is shared by every sub-batch so one compiled pack
        # family serves the run
        d_pad = _next_pow2(n_sel)
        idx_full = np.full(d_pad, sel_np[0], dtype=np.int32)
        idx_full[:n_sel] = sel_np
        t0 = time.perf_counter()
        counts = np.asarray(
            _finish_counts(bl.parent, ship_j, del_j, jnp.asarray(idx_full))
        )[:n_sel]
        stats.select_s += time.perf_counter() - t0
        stats.syncs += 1
        R = min(_next_pow2(int(counts.max(initial=1))), B)
        stats.R = R
        stats.total_rows = int(counts.sum())
        stats.threads = _finisher_threads(stats.total_rows)
        stats.buffer_reuses = plan.buffer_reuses

        out: List[Optional[bytes]] = [None] * n_sel
        host_full: dict = {}

        def host_arrays() -> dict:
            # degraded-path only: the serial per-doc finisher reads the
            # full [D, B] selection arrays on host (one extra sync each,
            # cached for the rest of the run)
            if not host_full:
                host_full["ship"] = np.asarray(ship_j)
                host_full["offsets"] = np.asarray(off_j)
                host_full["deleted"] = np.asarray(del_j)
                stats.syncs += 3
            return host_full

        def py_doc(d: int) -> bytes:
            h = host_arrays()
            return finish_encode_diff(
                state, d, h["ship"], h["offsets"], h["deleted"], enc,
                ctx.payloads, root_name,
            )

        def finish_sub(lo: int, hi: int, host: Optional[np.ndarray]) -> None:
            if host is None:
                # demoted sub-batch: serial per-doc finisher — the diff
                # still ships, slower
                for j in range(lo, hi):
                    out[j] = py_doc(docs[j])
                return
            threads = _finisher_threads(int(counts[lo:hi].sum()))
            res = ctx.finish(host, hi - lo, root_name, threads)
            for j, payload in enumerate(res):
                if payload is None:
                    stats.fallback_docs += 1
                    out[lo + j] = py_doc(docs[lo + j])
                else:
                    out[lo + j] = payload

        def produce():
            for k in range(n_sub):
                lo = k * sub
                hi = min(lo + sub, n_sel)
                # fresh host buffer PER sub-batch, never written after the
                # jnp conversion: the numpy->device read can happen as late
                # as program execution (async dispatch; CPU zero-copy may
                # even alias the buffer outright), so a reused slot races
                # the in-flight dispatch — sub-batch k gathering k+1's docs
                # under load.  The ONE reusable slot in the plan is the
                # DEVICE-side donated idx buffer, not this staging array.
                idx_host = np.empty(sub, dtype=np.int32)
                idx_host[: hi - lo] = sel_np[lo:hi]
                idx_host[hi - lo :] = sel_np[lo]  # pad repeats a SELECTED doc
                arr = compact_finisher_rows(
                    bl, ship_j, off_j, del_j, jnp.asarray(idx_host), R
                )
                yield (lo, hi, arr)

        # stats-field ownership is per stage/thread (no locks needed):
        # drain (worker thread) only touches syncs/d2h_bytes, consume
        # (caller thread) owns demotions/fallback_docs/finish_s — a
        # failed drain hands a None marker down and the CONSUMER counts
        # the demotion, so the two threads never race one field
        def drain(item):
            lo, hi, arr = item
            try:
                faults.maybe_raise("diff.d2h_fail")
                host = np.asarray(arr)  # the pipelined D2H: blocks HERE,
                # overlapped with both neighbor stages
            except Exception:
                return (lo, hi, None)
            stats.syncs += 1
            stats.d2h_bytes += host.nbytes
            return (lo, hi, host)

        def consume(item):
            lo, hi, host = item
            t0 = time.perf_counter()
            try:
                if host is None:
                    raise RuntimeError("d2h drain failed")  # demote below
                faults.maybe_raise("finisher.raise")
                finish_sub(lo, hi, host)
            except Exception:
                stats.demotions += 1
                metrics.counter("encode.demotions").inc()
                finish_sub(lo, hi, None)
            stats.finish_s += time.perf_counter() - t0

        if n_sub == 1:
            # nothing to overlap (the serving server's single-tenant
            # SyncStep1 answer): run the three stages inline — no threads,
            # no queue hops, same gauges minus the overlap ratio
            gen = produce()
            t0 = time.perf_counter()
            item = next(gen)
            stats.select_s += time.perf_counter() - t0
            t0 = time.perf_counter()
            drained = drain(item)
            stats.d2h_s += time.perf_counter() - t0
            consume(drained)
        else:
            pipe = OverlapPipeline(depth=self.depth, stage_prefix="encode")
            ostats = pipe.run(produce(), consume, drain=drain)
            stats.select_s += ostats.stage_s
            stats.d2h_s += ostats.drain_s
            stats.stall_s += ostats.stall_s
            stats.overlap_ratio = ostats.overlap_ratio
            stats.max_inflight = ostats.max_depth
        if phases.enabled:
            phases.add_time("encode.select", stats.select_s, n_sub)
            phases.add_time("encode.finish", stats.finish_s, n_sub)
            phases.add_value("encode.d2h_bytes", stats.d2h_bytes)
            phases.transfer("encode.d2h", stats.d2h_bytes, "d2h")
        return out  # type: ignore[return-value]  — every slot is filled


@partial(jax.jit, static_argnums=1)
def state_vectors(state: DocStateBatch, n_clients: int) -> jax.Array:
    """[D, C] dense state vectors from the block columns."""
    from ytpu.ops.state_vector import sv_from_blocks

    return sv_from_blocks(
        state.blocks.client, state.blocks.clock, state.blocks.length, n_clients
    )


# --- host-side conversion layer -----------------------------------------------


class ClientInterner:
    """Dense i32 interning of 53-bit client ids (SURVEY §2 #8)."""

    def __init__(self):
        self.to_idx: Dict[int, int] = {}
        self.from_idx: List[int] = []

    def intern(self, client: int) -> int:
        idx = self.to_idx.get(client)
        if idx is None:
            idx = len(self.from_idx)
            self.to_idx[client] = idx
            self.from_idx.append(client)
        return idx

    def rank_table_host(self, pad_to: Optional[int] = None) -> np.ndarray:
        """[C] i32: rank of each interned client in real-id order.

        Padded to a power of two so the jitted kernel's shape stays stable
        as new clients appear.
        """
        n = len(self.from_idx)
        size = pad_to or max(8, 1 << (max(1, n - 1)).bit_length())
        ranks = np.zeros(size, dtype=np.int32)
        try:
            ids = np.asarray(self.from_idx, dtype=np.int64)
        except OverflowError:  # an id past 63 bits: compare as Python ints
            ids = np.asarray(self.from_idx, dtype=object)
        ranks[np.argsort(ids)] = np.arange(n, dtype=np.int32)
        return ranks

    def rank_table(self, pad_to: Optional[int] = None) -> jax.Array:
        """`rank_table_host` on the default device."""
        return jnp.asarray(self.rank_table_host(pad_to))

    def __len__(self) -> int:
        return len(self.from_idx)


class KeyInterner:
    """Dense interning of map keys (parent_sub strings) to i32 ids."""

    def __init__(self):
        self.ids: Dict[str, int] = {}
        self.names: Dict[int, str] = {}

    def intern(self, key: str) -> int:
        kid = self.ids.get(key)
        if kid is None:
            kid = len(self.ids)
            self.ids[key] = kid
            self.names[kid] = key
        return kid

    def __len__(self) -> int:
        return len(self.ids)


class PayloadStore:
    """Host side-buffers for variable-length content, addressed by i32 refs.

    Strings are stored as UTF-16LE bytes so (offset, len) columns measured in
    clock units slice exactly; other payloads store their element lists.
    """

    def __init__(self):
        self.items: List[Tuple[int, object]] = []  # (kind, payload)

    def add(self, kind: int, payload) -> int:
        self.items.append((kind, payload))
        return len(self.items) - 1

    def slice_text(self, ref: int, off: int, length: int) -> str:
        kind, payload = self.items[ref]
        # a slice boundary inside a surrogate pair renders the severed half
        # as U+FFFD — split_str_utf16 parity (block.rs:1852-1860)
        return payload[2 * off : 2 * (off + length)].decode(
            "utf-16-le", errors="replace"
        )

    def slice_values(self, ref: int, off: int, length: int) -> list:
        kind, payload = self.items[ref]
        return payload[off : off + length]

    # kind-specific accessors, shape-compatible with the wire-ref
    # resolvers (decode_kernel.RawPayloadView / ChunkedWirePayloads)

    def json_values(self, ref: int, off: int, length: int) -> list:
        kind, payload = self.items[ref]  # a ContentJSON object
        return payload.values()[off : off + length]

    def json_raw(self, ref: int, off: int, length: int) -> list:
        return self.items[ref][1].raw[off : off + length]

    def embed_value(self, ref: int):
        return self.items[ref][1].value  # ContentEmbed

    def binary_value(self, ref: int) -> bytes:
        return self.items[ref][1].data  # ContentBinary

    def format_kv(self, ref: int):
        fmt = self.items[ref][1]  # ContentFormat
        return fmt.key, fmt.value


class BatchEncoder:
    """Converts host `Update` objects into padded `UpdateBatch` tensors."""

    def __init__(self, root_name: str = "text"):
        self.interner = ClientInterner()
        self.keys = KeyInterner()
        self.payloads = PayloadStore()
        self.root_name = root_name  # root branch of the device sequence
        # Until a named root has been seen, the FIRST one encountered is
        # ADOPTED as the batch root (legacy single-root callers never name
        # their root at construction); later distinct names are true
        # multi-root and anchor through BLOCK_ROOT_ANCHOR rows.
        self._root_adopted = False
        # build_batch slot primaries: doc index -> its first named root,
        # sticky across calls (each slot keeps its own implicit branch)
        self.doc_primaries: Dict[int, str] = {}
        # True once any encoded row was a map row or had a branch-id parent
        # (streams with such rows cannot take the fused Pallas path)
        self.saw_map_or_nested = False
        # True once any encoded row was a ContentMove (also fused-path-unsafe)
        self.saw_move = False

    def partition_carriers(self, update: Update, local_sv=None):
        """(applicable, leftover) carriers — the host half of the reference's
        integration stack machine (update.rs:169-308 + missing() :310-385):
        clients descending, but a block whose origin/right-origin/parent
        points into a not-yet-emitted range defers until that range lands.

        With `local_sv` (a StateVector mirror of the target doc) the check
        is exact: dependencies must be covered by the mirror or by already
        emitted in-update rows, and each client's rows must be clock-
        contiguous with the mirror — anything else lands in `leftover` (the
        PendingUpdate stash semantics of transaction.rs:675-727). Without
        it, out-of-update dependencies are assumed present in device state
        (the device flags them otherwise)."""
        queues = {
            c: [x for x in update.blocks[c] if not isinstance(x, SkipRange)]
            for c in sorted(update.blocks.keys(), reverse=True)
        }
        queues = {c: q for c, q in queues.items() if q}
        if local_sv is None:
            emitted = {c: q[0].id.clock for c, q in queues.items()}
        else:
            emitted = {c: local_sv.get(c) for c in queues}
        heads = {c: 0 for c in queues}

        def satisfied(dep) -> bool:
            if dep is None:
                return True
            if dep.client not in emitted:
                if local_sv is None:
                    return True  # assumed in device state; device flags
                return dep.clock < local_sv.get(dep.client)
            return dep.clock < emitted[dep.client]

        out = []
        progress = True
        while progress:
            progress = False
            for c, q in queues.items():
                while heads[c] < len(q):
                    carrier = q[heads[c]]
                    if local_sv is not None and carrier.id.clock > emitted[c]:
                        break  # clock gap within this client → pending
                    if isinstance(carrier, Item):
                        deps = [
                            carrier.origin,
                            carrier.right_origin,
                            carrier.parent
                            if isinstance(carrier.parent, ID)
                            else None,
                        ]
                        # a move row depends on its range bounds too
                        # (parity: Update::missing, update.rs:310-385)
                        content = carrier.content
                        if isinstance(content, ContentMove):
                            deps.append(content.move.start.id)
                            deps.append(content.move.end.id)
                        if not all(satisfied(d) for d in deps):
                            break
                    out.append(carrier)
                    emitted[c] = max(emitted[c], carrier.id.clock + carrier.len)
                    heads[c] += 1
                    progress = True
        leftover = []
        for c, q in queues.items():
            leftover.extend(q[heads[c] :])
        if local_sv is None:
            # single-pass mode: emit everything; device flags true misses
            return out + leftover, []
        return out, leftover

    def _ordered_carriers(self, update: Update) -> list:
        ordered, _ = self.partition_carriers(update)
        return ordered

    def rows_from_update(self, update: Update, primary_root=None) -> Tuple[list, list]:
        rows = self.rows_from_carriers(
            self._ordered_carriers(update), primary_root=primary_root
        )
        dels = []
        for client, ranges in update.delete_set.clients.items():
            c = self.interner.intern(client)
            for s, e in ranges:
                dels.append((c, s, e))
        return rows, dels

    def rows_from_carriers(self, carriers: list, primary_root=None) -> list:
        """Row tuples for already-ordered carriers (see partition_carriers).

        ``primary_root`` is the root name mapped onto the implicit device
        branch (``state.start``); other named roots intern into the key
        table and anchor through per-doc BLOCK_ROOT_ANCHOR rows
        (doc.rs:156-228 multi-root shape). When omitted, the batch root is
        used — and the first named root ever seen is adopted as it."""
        explicit_primary = primary_root
        if primary_root is None:
            primary_root = self.root_name
        no_move = (-1, 0, 0, -1, 0, 0, -1)  # mv_sc..mv_prio padding
        rows = []
        for carrier in carriers:
            c = self.interner.intern(carrier.id.client)
            if isinstance(carrier, GCRange):
                rows.append(
                    (c, carrier.id.clock, carrier.len, -1, 0, -1, 0,
                     BLOCK_GC, -1, 0, -1, 0, -1, 0, -1) + no_move
                )
                continue
            item: Item = carrier
            kind = item.content.kind
            if kind == CONTENT_STRING:
                ref = self.payloads.add(
                    kind, item.content.text.encode("utf-16-le")
                )
            elif kind in (CONTENT_ANY,):
                ref = self.payloads.add(kind, list(item.content.items))
            elif kind == CONTENT_DELETED:
                ref = -1
            else:
                # embed/format/type/doc payloads: stash the content object
                ref = self.payloads.add(kind, item.content)
            oc = self.interner.intern(item.origin.client) if item.origin else -1
            ok = item.origin.clock if item.origin else 0
            rc = (
                self.interner.intern(item.right_origin.client)
                if item.right_origin
                else -1
            )
            rk = item.right_origin.clock if item.right_origin else 0
            key = (
                self.keys.intern(item.parent_sub)
                if item.parent_sub is not None
                else -1
            )
            parent = item.parent
            p_root = -1
            if isinstance(parent, ID):
                p_tag = 2
                pc, pk = self.interner.intern(parent.client), parent.clock
            elif parent is not None:  # named root (doc.rs root branches)
                p_tag, pc, pk = 1, -1, 0
                if explicit_primary is None and not self._root_adopted:
                    # first named root this encoder ever sees becomes the
                    # batch root (legacy single-root behavior)
                    self.root_name = primary_root = parent
                    self._root_adopted = True
                if parent != primary_root:
                    # non-primary root: anchored through a per-doc
                    # BLOCK_ROOT_ANCHOR row keyed by the interned name
                    p_root = self.keys.intern(parent)
            else:  # omitted on the wire: inherit from the resolved anchor
                p_tag, pc, pk = 0, -1, 0
            if key >= 0 or p_tag == 2:
                self.saw_map_or_nested = True
            mv = no_move
            if kind == CONTENT_MOVE:
                self.saw_move = True
                move = item.content.move
                # branch-scoped sticky bounds (no item id — e.g. a range
                # starting at index 0, IndexScope::Relative) encode as -1:
                # the claim walk reads -1 as "sequence head" / "sequence
                # tail" (moving.rs get_coords' None-bound convention)
                sc, sk, sa = -1, 0, move.start.assoc
                if move.start.id is not None:
                    sc = self.interner.intern(move.start.id.client)
                    sk = move.start.id.clock
                ec, ek, ea = -1, 0, move.end.assoc
                if move.end.id is not None:
                    ec = self.interner.intern(move.end.id.client)
                    ek = move.end.id.clock
                mv = (sc, sk, sa, ec, ek, ea, max(move.priority, 0))
            rows.append(
                (c, item.id.clock, item.len, oc, ok, rc, rk, kind, ref, 0,
                 key, p_tag, pc, pk, p_root) + mv
            )
        return rows

    def build_batch(
        self,
        updates: List[Optional[Update]],
        n_rows: Optional[int] = None,
        n_dels: Optional[int] = None,
    ) -> UpdateBatch:
        """Pad per-doc rows into one [D, U] / [D, R] batch.

        Each doc slot's primary root is the first named root it EVER used
        (sticky across build_batch calls on this encoder, recorded in
        `doc_primaries` — docs in one batch may use different root names;
        each maps onto its slot's implicit branch, matching the
        pre-multi-root behavior for single-root docs). Genuinely
        multi-root updates need per-doc anchor rows, which `BatchIngestor`
        manages; raw build_batch callers get the missing-dep flag for
        non-primary roots instead of silent aliasing.
        """

        def first_root(u: Update):
            # wire order: clients descending, then block order
            for c in sorted(u.blocks, reverse=True):
                for b in u.blocks[c]:
                    p = getattr(b, "parent", None)
                    if isinstance(p, str):
                        return p
            return None

        all_rows = []
        all_dels = []
        for d_i, u in enumerate(updates):
            if u is None:
                all_rows.append([])
                all_dels.append([])
            else:
                fr = first_root(u)
                prim = (
                    self.doc_primaries.setdefault(d_i, fr)
                    if fr is not None
                    else self.doc_primaries.get(d_i)
                )
                r, d = self.rows_from_update(u, primary_root=prim)
                all_rows.append(r)
                all_dels.append(d)
        return self.batch_from_rows(all_rows, all_dels, n_rows, n_dels)

    def batch_packed(
        self,
        all_rows: List[list],
        all_dels: List[list],
        n_rows: Optional[int] = None,
        n_dels: Optional[int] = None,
    ) -> PackedBatch:
        """Per-doc row/del tuple lists padded to a host `PackedBatch`:
        `rows` [D, U, 23] and `dels` [D, R, 4], both int32 and
        contiguous."""
        U = n_rows or max(1, max(len(r) for r in all_rows))
        R = n_dels or max(1, max(len(d) for d in all_dels))
        D = len(all_rows)

        rows = np.empty((D, U, len(_PAD_ROW)), dtype=np.int32)
        rows[:] = _PAD_ROW
        dels = np.zeros((D, R, 4), dtype=np.int32)
        for packed, per_doc in ((rows, all_rows), (dels, all_dels)):
            for d, entries in enumerate(per_doc):
                if entries:
                    packed[d, : len(entries), :-1] = entries
                    packed[d, : len(entries), -1] = 1
        return PackedBatch(rows, dels)

    def batch_from_rows(
        self,
        all_rows: List[list],
        all_dels: List[list],
        n_rows: Optional[int] = None,
        n_dels: Optional[int] = None,
    ) -> UpdateBatch:
        """`batch_packed` as the planes of one [D, U] / [D, R] batch on
        the default device."""
        return unpack_batch_jit(
            self.batch_packed(all_rows, all_dels, n_rows, n_dels)
        )

    def build_step(
        self, update: Update, n_rows: int, n_dels: int, primary_root=None
    ) -> UpdateBatch:
        """One update as a doc-axis-free batch (leaves [U]/[R]) for
        `apply_update_stream`."""
        rows, dels = self.rows_from_update(update, primary_root=primary_root)
        if len(rows) > n_rows or len(dels) > n_dels:
            raise ValueError(
                f"update needs {len(rows)} rows/{len(dels)} dels, "
                f"buckets are {n_rows}/{n_dels}"
            )
        packed = self.batch_packed([rows], [dels], n_rows, n_dels)
        return unpack_batch_jit(PackedBatch(packed.rows[0], packed.dels[0]))

    @staticmethod
    def stack_steps(steps: List[UpdateBatch]) -> UpdateBatch:
        """Stack per-step batches into [S, ...] leaves for lax.scan."""
        return jax.tree.map(lambda *xs: jnp.stack(xs), *steps)

def _move_bounds(bl, n: int, s: int, doc_start: int = -1):
    """Host resolution of move row s's (start, end) slots.

    Mirrors `_resolve_move_ptr`: assoc After -> the slot starting at the
    sticky id; assoc Before -> the right neighbor of the slot ending at it.
    Claim passes split at the bounds, so covering slots land exactly.
    Branch-scoped bounds (id client -1) read as sequence head / tail."""

    def covering(c: int, k: int) -> int:
        m = np.nonzero(
            (bl.client[:n] == c)
            & (bl.clock[:n] <= k)
            & (k < bl.clock[:n] + bl.length[:n])
        )[0]
        return int(m[0]) if len(m) else -1

    if int(bl.mv_sc[s]) < 0:
        i = doc_start
    else:
        i = covering(int(bl.mv_sc[s]), int(bl.mv_sk[s]))
        if int(bl.mv_sa[s]) < 0:  # assoc Before: exclusive left bound
            i = int(bl.right[i]) if i >= 0 else -1
    if int(bl.mv_ec[s]) < 0:
        j = -1  # walk to the sequence tail
    else:
        j = covering(int(bl.mv_ec[s]), int(bl.mv_ek[s]))
        if int(bl.mv_ea[s]) >= 0:
            pass  # assoc After: the end slot itself is the exclusive bound
        else:
            j = int(bl.right[j]) if j >= 0 else -1
    return i, j


def _visible_walk(bl, n: int, start: int):
    """Yield slots in *visible* order, honoring move ranges.

    Host mirror of `ytpu.types.shared.visible_items` (reference MoveIter,
    iter.rs:46-116) over device block columns: a row whose `moved` owner
    differs from the current scope is skipped (it renders at its
    destination); a live ContentMove row descends into its range. Callers
    apply their own deleted/countable filters."""
    stack: List[Tuple[int, int, int]] = []
    cur, scope, scope_end = start, -1, -1
    # every live move row re-scans its physical span, so the walk bound
    # must scale with the live-move count, not just the row count
    n_moves = int(
        np.sum((bl.kind[:n] == CONTENT_MOVE) & ~bl.deleted[:n])
    )
    steps, limit = 0, (n + 2) * (n_moves + 2)
    while True:
        if cur < 0 or (scope_end >= 0 and cur == scope_end):
            if stack:
                cur, scope, scope_end = stack.pop()
                continue
            break
        steps += 1
        if steps > limit:
            raise RuntimeError("cycle detected in move-aware walk")
        kind = int(bl.kind[cur])
        if (
            kind == CONTENT_MOVE
            and not bl.deleted[cur]
            and int(bl.moved[cur]) == scope
        ):
            s_ptr, e_ptr = _move_bounds(bl, n, cur, doc_start=start)
            stack.append((int(bl.right[cur]), scope, scope_end))
            scope, scope_end = cur, e_ptr
            cur = s_ptr
            continue
        if int(bl.moved[cur]) == scope and kind != CONTENT_MOVE:
            yield cur
        cur = int(bl.right[cur])


def get_string(state: DocStateBatch, doc: int, payloads: PayloadStore) -> str:
    """Host assembly of a doc's visible text (device gather + host concat)."""
    bl = jax.tree.map(lambda a: np.asarray(a[doc]), state.blocks)
    out: List[str] = []
    for idx in _visible_walk(bl, int(state.n_blocks[doc]), int(state.start[doc])):
        if not bl.deleted[idx] and bl.kind[idx] == CONTENT_STRING:
            out.append(
                payloads.slice_text(
                    int(bl.content_ref[idx]),
                    int(bl.content_off[idx]),
                    int(bl.length[idx]),
                )
            )
    return "".join(out)


def get_diff(state: DocStateBatch, doc: int, payloads) -> list:
    """Host assembly of a doc's visible text as *formatted runs* — the
    device-state analogue of `Text.diff()` (reference types/text.rs:534-:
    runs of string content annotated with the formatting attributes in
    force, ContentFormat toggles flushing runs, embeds/types as their own
    single-value runs). Returns `ytpu.types.text.Diff` objects so results
    compare directly against the host oracle's.
    """
    from ytpu.types.text import Diff

    bl = jax.tree.map(lambda a: np.asarray(a[doc]), state.blocks)
    n = int(state.n_blocks[doc])
    runs: list = []
    attrs: dict = {}
    buf: List[str] = []

    def flush():
        if buf:
            runs.append(Diff("".join(buf), dict(attrs) if attrs else None))
            buf.clear()

    for i in _visible_walk(bl, n, int(state.start[doc])):
        if bl.deleted[i]:
            continue
        kind = int(bl.kind[i])
        ref = int(bl.content_ref[i])
        if kind == CONTENT_STRING:
            buf.append(
                payloads.slice_text(ref, int(bl.content_off[i]), int(bl.length[i]))
            )
        elif kind == CONTENT_FORMAT:
            fkey, fval = payloads.format_kv(ref)
            if attrs.get(fkey) != fval:
                flush()
            if fval is None:
                attrs.pop(fkey, None)
            else:
                attrs[fkey] = fval
        elif kind in (CONTENT_EMBED, CONTENT_TYPE):
            flush()
            if kind == CONTENT_EMBED:
                value = payloads.embed_value(ref)
            else:
                # a user-facing SharedType view, like the host's
                # out_value -> wrap_branch (the branch is the decoded
                # wire object: a detached view, not the live host one);
                # device-decoded rows carry wire refs → type_branch
                tb = getattr(payloads, "type_branch", None)
                branch = (
                    tb(ref) if tb is not None else payloads.items[ref][1].branch
                )
                from ytpu.types import wrap_branch

                value = wrap_branch(branch)
            runs.append(Diff(value, dict(attrs) if attrs else None))
    flush()
    return runs


def get_map(
    state: DocStateBatch, doc: int, payloads: PayloadStore, keys: KeyInterner
) -> dict:
    """Host assembly of the root branch's visible map component.

    The live value of key k is the *tail* of k's item chain — the row with
    key==k and right==-1 (parity: map entry = parent.map[sub] maintained at
    block.rs:637-642; a deleted tail means the key is absent, map.rs:285).
    One rendering path with get_tree — this is its root "map" component.
    """
    return get_tree(state, doc, payloads, keys)["map"]


def get_tree(
    state: DocStateBatch,
    doc: int,
    payloads: PayloadStore,
    keys: KeyInterner,
    interner=None,
) -> dict:
    """Host assembly of a doc's full branch tree: the root's sequence and map
    components, with nested shared types rendered recursively by their
    TypeRef (text -> str, map -> dict, array/xml -> list).

    Nested branches live in the same block table: a ContentType row owns a
    child sequence via its `head` column, and child map chains reference it
    through the `parent` column (parity: the Branch projections of
    branch.rs:173-215 over the device columns). With the `ClientInterner`
    supplied, WeakRef branches render as their quoted values (the
    `unquote` projection, weak.rs:303-372) resolved over the device
    columns; without it they render as empty sequences.
    """
    from ytpu.core.branch import TYPE_MAP, TYPE_TEXT, TYPE_WEAK, TYPE_XML_TEXT

    bl = jax.tree.map(lambda a: np.asarray(a[doc]), state.blocks)
    n = int(state.n_blocks[doc])

    def render_type(i: int):
        ref = int(bl.content_ref[i])
        tb = getattr(payloads, "type_branch", None)
        if tb is not None:
            branch = tb(ref)
        else:
            branch = payloads.items[ref][1].branch
        tr = branch.type_ref
        if tr == TYPE_WEAK:
            # weak branches only come from the host store (the device
            # decoder flags WeakRef ContentType to the host lane)
            return render_weak(payloads.items[ref][1])
        seq, mp = render_branch(int(bl.head[i]), i)
        if tr in (TYPE_TEXT, TYPE_XML_TEXT):
            return "".join(v for v in seq if isinstance(v, str))
        if tr == TYPE_MAP:
            return mp
        return seq

    def render_weak(content):
        """Quoted-range values from device columns (unquote parity:
        weak.rs:303-372 — whole covering blocks, stop at the end id)."""
        src = getattr(content.branch, "link_source", None)
        if interner is None or src is None or src.quote_start.id is None:
            return []
        sc = interner.to_idx.get(src.quote_start.id.client)
        if sc is None:
            return []
        sk = src.quote_start.id.clock
        m = np.nonzero(
            (bl.client[:n] == sc)
            & (bl.clock[:n] <= sk)
            & (sk < bl.clock[:n] + bl.length[:n])
        )[0]
        if not len(m):
            return []
        i = int(m[0])
        eid = src.quote_end.id
        ec = interner.to_idx.get(eid.client) if eid is not None else None
        from ytpu.core.moving import ASSOC_BEFORE

        out: list = []
        steps = 0
        first = True
        while i >= 0 and steps <= n:
            steps += 1
            ck, ln = int(bl.clock[i]), int(bl.length[i])
            same_client = (
                eid is not None and ec is not None and int(bl.client[i]) == ec
            )
            # stop only at the block actually containing the end id — a
            # clock comparison fires early on out-of-order blocks
            # (weak.rs RangeIter parity)
            contains_end = same_client and ck <= eid.clock < ck + ln
            if not bl.deleted[i] and bl.countable[i]:
                vals = render_row_values(i)
                # trim to the quoted units only where a bound id falls
                # INSIDE the block: host blocks are split at the quote
                # bounds at creation time, device blocks are not
                a = 0
                if first and int(bl.client[i]) == sc and ck <= sk < ck + ln:
                    a = sk - ck
                    if src.quote_start.assoc == ASSOC_BEFORE:
                        a += 1
                b = len(vals)
                if contains_end:
                    b = eid.clock - ck
                    if src.quote_end.assoc != ASSOC_BEFORE:
                        b += 1
                out.extend(vals[a:b])
            first = False
            if contains_end:
                break
            i = int(bl.right[i])
        return out

    def render_row_values(i: int) -> list:
        kind = int(bl.kind[i])
        ref = int(bl.content_ref[i])
        off = int(bl.content_off[i])
        ln = int(bl.length[i])
        if kind == CONTENT_STRING:
            return list(payloads.slice_text(ref, off, ln))
        if kind == CONTENT_ANY:
            return payloads.slice_values(ref, off, ln)
        if kind == CONTENT_TYPE:
            return [render_type(i)]
        if kind == CONTENT_JSON:
            return payloads.json_values(ref, off, ln)
        if kind == CONTENT_EMBED:
            return [payloads.embed_value(ref)]
        if kind == CONTENT_BINARY:
            return [payloads.binary_value(ref)]
        if ref >= 0:
            payload = payloads.items[ref][1]
            if hasattr(payload, "values"):
                return list(payload.values())
        return []

    def render_branch(head: int, parent_row: int):
        seq: list = []
        for idx in _visible_walk(bl, n, head):
            if not bl.deleted[idx] and bl.countable[idx] and bl.key[idx] < 0:
                seq.extend(render_row_values(idx))
        mp: dict = {}
        for i in range(n):
            if (
                int(bl.key[i]) >= 0
                and int(bl.parent[i]) == parent_row
                and int(bl.right[i]) == -1
                and not bl.deleted[i]
            ):
                name = keys.names.get(int(bl.key[i]))
                vals = render_row_values(i)
                if name is not None and vals:
                    mp[name] = vals[-1]
        return seq, mp

    seq, mp = render_branch(int(state.start[doc]), -1)
    out = {"seq": seq, "map": mp}
    # non-primary named roots live behind per-doc anchor rows
    # (doc.rs:156-228 multi-root shape); render each under its name
    roots: dict = {}
    for i in range(n):
        if int(bl.kind[i]) == BLOCK_ROOT_ANCHOR:
            name = keys.names.get(int(bl.key[i]))
            r_seq, r_mp = render_branch(int(bl.head[i]), i)
            if name is not None:
                roots[name] = {"seq": r_seq, "map": r_mp}
    if roots:
        out["roots"] = roots
    return out


def get_values(state: DocStateBatch, doc: int, payloads: PayloadStore) -> list:
    """Host assembly of a doc's visible sequence values (Array flagship)."""
    bl = jax.tree.map(lambda a: np.asarray(a[doc]), state.blocks)
    out: list = []
    for idx in _visible_walk(bl, int(state.n_blocks[doc]), int(state.start[doc])):
        if not bl.deleted[idx] and bl.countable[idx]:
            kind = int(bl.kind[idx])
            ref = int(bl.content_ref[idx])
            off = int(bl.content_off[idx])
            ln = int(bl.length[idx])
            if kind == CONTENT_STRING:
                out.extend(payloads.slice_text(ref, off, ln))
            elif kind == CONTENT_ANY:
                out.extend(payloads.slice_values(ref, off, ln))
    return out


# --- bounded resident-program plumbing (VERDICT r4 #7) ----------------------
# The two batched-apply entry points get tick-ing host wrappers: nearly
# every test and serving path integrates through one of them, so the
# budget's periodic enforcement actually runs suite-wide (the library-
# internal hooks alone missed direct callers — the r5 no-crutch suite
# segfaulted at ~73% compiling an unregistered giant program).

_apply_update_stream_jit = apply_update_stream


# state-only twin for the PUBLIC stream entry: the scan-width record is
# dropped INSIDE the jit, so XLA dead-code-eliminates the whole counter
# carry on the classic stream lane — a standalone caller pays nothing
# for the attribution it isn't reading (the chunk programs, which DO
# read it, trace through the tuple body instead)
_apply_update_stream_state_jit = partial(
    jax.jit, donate_argnums=0, static_argnums=3
)(
    lambda state, stream, client_rank, scan_plan=None: (
        _apply_update_stream_hist_body(state, stream, client_rank, scan_plan)[0]
    )
)


def _xla_batch_span(program, state, batch, client_rank, scan_plan, active):
    """The `integrate.xla_batch` span around a call of `program`, one of
    the two jits of `apply_update_batch`. The span key carries the scan
    plan, so the sentinel attributes a retrace to a changed knob."""
    from ytpu.utils.phases import NULL_SPAN, phases, program_memory

    if not phases.enabled:
        return NULL_SPAN
    return phases.span(
        "integrate.xla_batch",
        (
            state.blocks.client.shape,
            batch[0].shape,  # `client` of planes, `rows` of a pair
            scan_plan,
            None if active is None else active.shape[0],
        ),
        axes=("state", "batch", "scan_plan", "active"),
        # reads shapes alone, on the first sighting and after the call:
        # a donated state still answers (`phases.program_memory`)
        memory=program_memory(
            program, state, batch, client_rank, scan_plan, active
        ),
    )


# The two entries below are one body twice, and not a helper that takes
# the program: a Python frame between a caller and a jitted call makes
# every trace of a new shape dearer (`BatchIngestor.apply_bytes`).


def apply_update_batch(
    state: DocStateBatch,
    batch: UpdateBatch,
    client_rank: jax.Array,
    active: Optional[jax.Array] = None,
) -> DocStateBatch:
    from ytpu.utils.progbudget import tick

    tick()
    # lazy origin_slot refresh: the conflict scan reads the cache, so a
    # fused-lane (stale-marked) state rebuilds it here, on first read.
    # Under jit tracing (tracer args) the id lookup misses — correct, the
    # traced program's operands are maintained by the XLA lane itself.
    state = ensure_origin_slot(state)
    # two-tier scan plan: env re-read per CALL and threaded as a static
    # (same discipline as the chunk programs) — a changed knob retraces
    # instead of silently reusing the old unroll
    scan_plan = scan_tier_plan()
    with _xla_batch_span(
        _apply_update_batch_jit, state, batch, client_rank, scan_plan, active
    ):
        return _apply_update_batch_jit(
            state, batch, client_rank, scan_plan, active
        )


def apply_update_batch_in_place(
    state: DocStateBatch,
    batch: UpdateBatch,
    client_rank: jax.Array,
    active: Optional[jax.Array] = None,
) -> DocStateBatch:
    """`apply_update_batch` for the state's one owner: `state` is DONATED,
    its buffers become the result's and the tree handed in is deleted, so
    the caller rebinds what it holds to the result and nothing else may
    keep the old tree (`BatchIngestor`). No plane the step does not change
    is copied. `batch`, `client_rank` and `active` are read, not consumed."""
    from ytpu.utils.progbudget import tick

    tick()
    state = ensure_origin_slot(state)  # as `apply_update_batch`
    scan_plan = scan_tier_plan()
    with _xla_batch_span(
        _apply_update_batch_in_place_jit, state, batch, client_rank,
        scan_plan, active,
    ):
        return _apply_update_batch_in_place_jit(
            state, batch, client_rank, scan_plan, active
        )


def apply_update_stream(
    state: DocStateBatch, stream: UpdateBatch, client_rank: jax.Array
) -> DocStateBatch:
    from ytpu.utils.phases import NULL_SPAN, phases
    from ytpu.utils.progbudget import tick

    tick()
    state = ensure_origin_slot(state)
    # two-tier scan plan as a per-call static (see apply_update_batch)
    scan_plan = scan_tier_plan()
    from ytpu.utils.phases import program_memory

    span = (
        phases.span(
            "integrate.xla_stream",
            (state.blocks.client.shape, stream.client.shape, scan_plan),
            axes=("state", "stream", "scan_plan"),
            memory=program_memory(
                _apply_update_stream_state_jit, state, stream,
                client_rank, scan_plan,
            ),
        )
        if phases.enabled
        else NULL_SPAN
    )
    with span:
        # state-only compiled variant: the scan-width record (ISSUE-11)
        # is dropped in-jit and DCE'd — the chunk programs are the
        # consumers that fold the histogram into the lazy readout
        return _apply_update_stream_state_jit(
            state, stream, client_rank, scan_plan
        )


apply_update_batch.__doc__ = _apply_update_batch_jit.__doc__
apply_update_stream.__doc__ = _apply_update_stream_jit.__doc__

# Raw, uninstrumented body for IN-JIT composition (a program that
# traces the stream step inside its own jit; none is left since PR 48).
# Tracing through the instrumented wrapper above records a phantom
# `integrate.xla_stream` compile_s entry keyed on tracer shapes — the
# bench-JSON double-count flagged by the PR-4 review — and its
# ensure_origin_slot identity lookup is a guaranteed miss on tracers
# anyway (the composing program maintains the cache itself).
apply_update_stream_raw = _apply_update_stream_jit


def _register_programs():
    """Track the big jitted entry points under the bounded resident-
    program registry (VERDICT r4 #7; see ytpu/utils/progbudget.py)."""
    from ytpu.utils import progbudget

    progbudget.register("apply_update_batch", _apply_update_batch_jit)
    progbudget.register(
        "apply_update_batch_in_place", _apply_update_batch_in_place_jit
    )
    progbudget.register("apply_update_stream", _apply_update_stream_jit)
    progbudget.register(
        "apply_update_stream_state", _apply_update_stream_state_jit
    )
    # the raw jit, not the instrumented wrapper — progbudget tracks
    # compiled-executable caches, and the wrapper has none of its own
    progbudget.register("encode_diff_batch", _encode_diff_batch_jit)
    progbudget.register("finish_pack", _finish_pack)
    progbudget.register("finish_counts", _finish_counts)
    progbudget.register("state_vectors", state_vectors)
    progbudget.register("unpack_batch", unpack_batch_jit)


_register_programs()
