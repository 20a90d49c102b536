"""Device compaction: commit-time block squash + GC collapse, vmapped.

The reference compacts continuously at commit: `Item::try_squash` merges a
block into its clock-contiguous right neighbor (block.rs:775-799,
squash_left at block_store.rs:243), and the GC collector replaces deleted
non-kept items with content-free GC ranges (gc.rs:11-65). The device engine
appends rows forever, so long-lived docs fill their capacity with 1-element
blocks; this pass is the batched equivalent, run as one jitted program:

1. **GC conversion** — tombstoned value rows (string/any/binary/json/
   embed/format) drop their payload reference and become CONTENT_DELETED
   rows, exactly like the host oracle's collector: the item (with its
   origin/right-origin anchors) stays in the graph so wire encodes remain
   integrable by fresh replicas; only the payload is discarded. Structural
   rows (type/move/doc) are preserved.
2. **Squash** — a row merges into its sequence-right neighbor under the
   exact try_squash conditions (same client, contiguous clocks, the
   neighbor's origin is the row's last id, equal right-origins, equal
   deleted/moved/key/parent, mergeable content: same payload ref with
   contiguous offsets for string/any, unconditionally for GC/deleted).
   Chains collapse in one pass via pointer doubling + segment sums.
3. **Defragmentation** — surviving rows are packed to the front (slot
   order preserved), every index column (left/right/parent/head/moved,
   sequence starts) remapped, and n_blocks shrinks accordingly.

Semantics parity is testable: replay -> compact -> keep replaying must
match the host oracle exactly (tests/test_compaction.py).

Two forms, and who calls each:

- `compact_rooms` — **the served form** (PR 43). `BatchIngestor` calls it
  from inside `apply_bytes` (so from `DeviceSyncServer.flush_device`) for
  the rooms whose rows near their capacity: gather `[K, ...]`, compact,
  scatter back, as the integrate step does, K = 2 a call (a lone room
  brings an idle slot along, behind a mask); on one chip and on a
  doc-sharded state alike. Its chain rule stands on structure alone, so
  typed runs squash although every keystroke's string is a wire ref of
  its own; the strings follow on the host (`BatchIngestor._rehome`).
  Nothing inside its `vmap` scatters: chain heads, tails and sums come
  from pointer doubling over gathers (`_set`'s docstring in `batch_doc.py`
  says what a batched dropping scatter does on a v5e).
- `compact_state` — every slot of a `DocStateBatch` at once, the state
  donated, content merged only where the payload ref is shared. **No
  server path and no replay lane calls it**: its callers are tests
  (`tests/test_compaction.py`, `tests/test_capacity.py`,
  `tests/test_origin_slot.py`, and `tests/test_chip_compile.py` compiles
  it). It has never run on the chip, and its scatters are of the kind
  `_set`'s docstring warns of.

`grow_state` widens every slot's capacity by a host-side repad; like
`compact_state` it has tests for callers and no server path: growing a
served slot starts from it (ROADMAP Reach A1). The packed `[NC, D, C]`
form of the rule left with the replay drivers it served (PR 48).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ytpu.core.content import (
    BLOCK_GC,
    CONTENT_ANY,
    CONTENT_BINARY,
    CONTENT_DELETED,
    CONTENT_EMBED,
    CONTENT_FORMAT,
    CONTENT_JSON,
    CONTENT_STRING,
)
from ytpu.models.batch_doc import COL_DEFAULTS, BlockCols, DocStateBatch

__all__ = [
    "compact_state",
    "compact_rooms",
    "grow_state",
    "REHOME_FIELDS",
]

I32 = jnp.int32

# kinds whose tombstones GC to content-free deleted rows (value content;
# the reference's ItemContent::gc drops these payloads outright)
_GCABLE = (
    CONTENT_JSON,
    CONTENT_BINARY,
    CONTENT_STRING,
    CONTENT_EMBED,
    CONTENT_FORMAT,
    CONTENT_ANY,
)
# content kinds mergeable under try_squash when payload refs are contiguous
_SPLICEABLE = (CONTENT_STRING, CONTENT_ANY)


def _compact_one(state: DocStateBatch) -> DocStateBatch:
    bl = state.blocks
    B = bl.client.shape[-1]
    slots = jnp.arange(B, dtype=I32)
    n = state.n_blocks
    active = slots < n

    # --- 1. GC conversion (gc.rs:11-65) ------------------------------------
    gcable = jnp.zeros((B,), bool)
    for k in _GCABLE:
        gcable = gcable | (bl.kind == k)
    convert = active & bl.deleted & gcable
    kind = jnp.where(convert, CONTENT_DELETED, bl.kind)
    content_ref = jnp.where(convert, -1, bl.content_ref)
    content_off = jnp.where(convert, 0, bl.content_off)
    bl = bl._replace(kind=kind, content_ref=content_ref, content_off=content_off)

    # --- 2. squash eligibility a -> b = right[a] (block.rs:775-799) --------
    b = bl.right
    sb = jnp.maximum(b, 0)

    def g(col):
        return col[sb]

    ror_eq = (bl.ror_client == g(bl.ror_client)) & (
        (bl.ror_client < 0) | (bl.ror_clock == g(bl.ror_clock))
    )
    origin_chain = (g(bl.origin_client) == bl.client) & (
        g(bl.origin_clock) == bl.clock + bl.length - 1
    )
    spliceable = jnp.zeros((B,), bool)
    for k in _SPLICEABLE:
        spliceable = spliceable | (bl.kind == k)
    content_ok = (bl.kind == g(bl.kind)) & (
        (bl.kind == BLOCK_GC)
        | (bl.kind == CONTENT_DELETED)
        | (
            spliceable
            & (bl.content_ref == g(bl.content_ref))
            & (g(bl.content_off) == bl.content_off + bl.length)
        )
    )
    elig = (
        active
        & (b >= 0)
        & (b < n)
        & (bl.client == g(bl.client))
        & (g(bl.clock) == bl.clock + bl.length)
        & origin_chain
        & ror_eq
        & (bl.deleted == g(bl.deleted))
        & (bl.moved == g(bl.moved))
        & (bl.key == g(bl.key))
        & (bl.parent == g(bl.parent))
        & (g(bl.left) == slots)  # well-formed adjacency both ways
        & content_ok
    )

    # a row is absorbed into its chain head iff its left neighbor merges
    # rightward into it
    sl = jnp.maximum(bl.left, 0)
    merged_away = active & (bl.left >= 0) & elig[sl]

    # chain representative via pointer doubling: parent = left when absorbed
    rep = jnp.where(merged_away, bl.left, slots)
    n_doubling = max(1, B.bit_length())
    for _ in range(n_doubling):
        rep = rep[jnp.maximum(rep, 0)]

    # per-chain aggregates (segment id = chain head slot)
    seg_len = jax.ops.segment_sum(
        jnp.where(active, bl.length, 0), jnp.maximum(rep, 0), num_segments=B
    )
    # the chain tail (the row that does NOT merge rightward) donates its
    # right pointer to the head
    tail = active & ~elig
    tail_w = jnp.where(tail, rep, B)
    chain_right = jnp.full((B,), -1, I32).at[tail_w].set(bl.right, mode="drop")

    keep = active & ~merged_away
    # heads take the aggregated length + the tail's right pointer
    length = jnp.where(keep, seg_len, bl.length)
    right = jnp.where(keep, chain_right, bl.right)
    bl = bl._replace(length=length, right=right)

    # --- 3. defragment: pack kept rows, remap index columns ----------------
    new_idx = jnp.cumsum(keep.astype(I32)) - 1
    # pointers into absorbed rows redirect to their chain head
    old2new = jnp.where(keep, new_idx, new_idx[jnp.maximum(rep, 0)])

    def remap(col):
        return jnp.where(col >= 0, old2new[jnp.maximum(col, 0)], -1)

    bl = bl._replace(
        left=remap(bl.left),
        right=remap(bl.right),
        parent=remap(bl.parent),
        head=remap(bl.head),
        moved=remap(bl.moved),
        # origin_slot: absorbed rows redirect to their chain head via
        # old2new; the head's widened clock range still contains the
        # origin id, so containment (the cache contract) is preserved
        origin_slot=remap(bl.origin_slot),
    )
    n_new = jnp.sum(keep.astype(I32))
    # kept rows first (slot order preserved), dropped rows after
    order = jnp.argsort(jnp.where(keep, slots, B + slots))
    blank = slots >= n_new

    packed = BlockCols(
        **{
            name: jnp.where(blank, fill, getattr(bl, name)[order])
            for name, fill in COL_DEFAULTS.items()
        }
    )
    start = jnp.where(
        state.start >= 0, old2new[jnp.maximum(state.start, 0)], -1
    )
    return DocStateBatch(
        blocks=packed, start=start, n_blocks=n_new, error=state.error
    )


#: columns of `compact_rooms`' per-row report (`[K, B, 6]` i32, one row an
#: OLD row of the room, before the defragmentation): the chain a row's
#: content is re-homed into (-1: none), the row's offset into that chain's
#: content in clock units, and where its content was (`kind`, `content_ref`,
#: `content_off`, `length`, after the GC conversion)
REHOME_FIELDS = ("chain", "chain_off", "kind", "ref", "off", "length")


def _compact_room(state: DocStateBatch):
    """One room of `compact_rooms`: `_compact_one`'s three passes with the
    served path's chain rule. Returns the compacted room, per NEW row the
    room-local number of the re-homed chain it heads (-1: none), the
    `[B, 6]` report (`REHOME_FIELDS`, room-local chain numbers) and the
    count of re-homed chains.

    A live string or Any row chains into its right neighbour under
    `try_squash`'s structural conditions whatever payload each reads:
    on the served path every update's string is its own wire ref, so the
    old content rule (same ref, contiguous offsets) never held between two
    keystrokes. A chain with a link the old rule refuses has its content
    re-homed: its head reads a new payload from offset 0, which the host
    assembles from the report.

    Written for the chip. A batched scatter that drops rows is not to be
    trusted there (`_set`'s docstring in `batch_doc.py`), so a chain's
    head, its tail and whether any of its links needs re-homing come from
    pointer doubling over gathers, and nothing scatters. A gather costs
    the chip some 10 ns an element whatever it fetches (PERF.md section 6,
    PR 43), so the right neighbour's columns and the packing are one
    gather of rows each, not one a plane. (Sorting the rows by client and
    clock finds the chains with no doubling at all, but a sort of several
    operands takes the TPU's compiler 30-40 s to build.)"""
    bl = state.blocks
    B = bl.client.shape[-1]
    slots = jnp.arange(B, dtype=I32)
    n = state.n_blocks
    active = slots < n

    # --- 1. GC conversion (gc.rs:11-65) ------------------------------------
    gcable = jnp.zeros((B,), bool)
    for k in _GCABLE:
        gcable = gcable | (bl.kind == k)
    convert = active & bl.deleted & gcable
    kind = jnp.where(convert, CONTENT_DELETED, bl.kind)
    content_ref = jnp.where(convert, -1, bl.content_ref)
    content_off = jnp.where(convert, 0, bl.content_off)

    # --- 2. squash eligibility a -> b = right[a] (block.rs:775-799) --------
    with jax.named_scope("room_squash"):
        b = bl.right
        mine = (
            bl.client, bl.clock, bl.origin_client, bl.origin_clock,
            bl.ror_client, bl.ror_clock, bl.deleted.astype(I32), bl.moved,
            bl.key, bl.parent, bl.left, kind, content_ref, content_off,
        )
        theirs = jnp.stack(mine, axis=-1)[jnp.maximum(b, 0)]  # [B, 14]
        (
            b_client, b_clock, b_origin_client, b_origin_clock, b_ror_client,
            b_ror_clock, b_deleted, b_moved, b_key, b_parent, b_left, b_kind,
            b_ref, b_off,
        ) = (theirs[:, i] for i in range(len(mine)))
        spliceable = jnp.zeros((B,), bool)
        for k in _SPLICEABLE:
            spliceable = spliceable | (kind == k)
        contentless = (kind == BLOCK_GC) | (kind == CONTENT_DELETED)
        elig = (
            active
            & (b >= 0)
            & (b < n)
            & (bl.client == b_client)
            & (b_clock == bl.clock + bl.length)
            & (b_origin_client == bl.client)
            & (b_origin_clock == bl.clock + bl.length - 1)
            & (bl.ror_client == b_ror_client)
            & ((bl.ror_client < 0) | (bl.ror_clock == b_ror_clock))
            & (bl.deleted.astype(I32) == b_deleted)
            & (bl.moved == b_moved)
            & (bl.key == b_key)
            & (bl.parent == b_parent)
            & (b_left == slots)  # well-formed adjacency both ways
            & (kind == b_kind)
            & (contentless | spliceable)
        )
        # the link's content does not follow by itself: re-home the chain
        same_run = (content_ref == b_ref) & (b_off == content_off + bl.length)
        loose = elig & spliceable & ~same_run

        sl = jnp.maximum(bl.left, 0)
        merged_away = active & (bl.left >= 0) & elig[sl]
        # leftwards: a row's chain head; rightwards: its chain tail, and
        # whether any link from the row to the tail is loose
        head = jnp.where(merged_away, bl.left, slots)
        tail = jnp.where(elig, b, slots)
        for _ in range(max(1, B.bit_length())):
            head = head[head]
            loose = loose | loose[tail]
            tail = tail[tail]
        keep = active & ~merged_away
        # clocks run on through a chain: its length is its extent
        length = jnp.where(
            keep, bl.clock[tail] + bl.length[tail] - bl.clock, bl.length
        )
        right = jnp.where(keep, bl.right[tail], bl.right)
        rehomed = keep & loose  # a head whose chain is re-homed
        chain = jnp.cumsum(rehomed.astype(I32)) - 1
        n_chains = jnp.sum(rehomed.astype(I32))
        member = active & rehomed[head]
        report = jnp.stack(
            [
                jnp.where(member, chain[head], -1),
                bl.clock - bl.clock[head],
                kind,
                content_ref,
                content_off,
                bl.length,
            ],
            axis=-1,
        )
        bl = bl._replace(
            kind=kind,
            content_ref=content_ref,
            content_off=jnp.where(rehomed, 0, content_off),
            length=length,
            right=right,
        )

    # --- 3. defragment: pack kept rows, remap index columns ----------------
    with jax.named_scope("room_defrag"):
        new_idx = jnp.cumsum(keep.astype(I32)) - 1
        # pointers into absorbed rows redirect to their chain head
        old2new = new_idx[head]
        # left, right, parent, head, moved, and origin_slot: an absorbed
        # origin row redirects to its chain head, whose widened clock
        # range still contains the origin id
        links = ("left", "right", "parent", "head", "moved", "origin_slot")
        old = jnp.stack([getattr(bl, name) for name in links])
        remapped = jnp.where(old >= 0, old2new[jnp.maximum(old, 0)], -1)
        bl = bl._replace(**{name: remapped[i] for i, name in enumerate(links)})
        n_new = jnp.sum(keep.astype(I32))
        # kept rows first (slot order preserved), dropped rows after
        order = jnp.argsort(jnp.where(keep, slots, B + slots))
        blank = slots >= n_new
        planes = jnp.stack(
            [getattr(bl, name).astype(I32) for name in COL_DEFAULTS]
            + [jnp.where(rehomed, chain, -1)],
            axis=-1,
        )[order]  # [B, 27]: one gather of rows
        packed = BlockCols(
            **{
                name: jnp.where(
                    blank, fill, planes[:, i].astype(getattr(bl, name).dtype)
                )
                for i, (name, fill) in enumerate(COL_DEFAULTS.items())
            }
        )
        heads = jnp.where(blank, -1, planes[:, -1])
        start = jnp.where(
            state.start >= 0, old2new[jnp.maximum(state.start, 0)], -1
        )
    out = DocStateBatch(
        blocks=packed, start=start, n_blocks=n_new, error=state.error
    )
    return out, heads, report, n_chains


@partial(jax.jit, donate_argnums=0)
def compact_rooms(state: DocStateBatch, rooms, mask, ref_base):
    """Squash + GC + defragment the rooms `rooms` ([K] i32, distinct and
    in range) of `state` where `mask` ([K] bool) is set; the rest of
    `rooms` is padding and every other room's planes are carried over. The
    integrate step's form: gather `[K, ...]`, compact under `vmap`, one
    plain scatter on the room axis outside it. `state` is DONATED, as
    the served integrate step's is (`apply_update_batch_in_place`): the
    scatter writes K rooms where they are, the tree handed in is deleted,
    and its one caller rebinds to the result (`BatchIngestor._compact`);
    `rooms`, `mask` and `ref_base` are read. The device's time goes with K (a
    gather's cost is per element), so the served path calls it with
    K = 2: one program, a third room due in the same step is a second
    call, and a lone room brings an idle slot along (at K = 1 XLA turns
    the room gather into a dynamic slice, which the partitioner answers
    on a doc-sharded state by gathering every plane whole onto every
    chip: `tests/test_chip_compile.py`).

    A re-homed chain's head (`_compact_room`) reads payload
    `ref_base + c`, `c` counting the chains of the call room by room:
    the caller appends exactly those payloads to its store, in that
    order, from the report. Returns `(state, n_before [K], n_after [K],
    report [K, B, 6], n_chains [K])`, the report's chain numbers
    call-wide and -1 in a room the mask leaves out."""
    with jax.named_scope("compact_gather"):
        sub = jax.tree.map(lambda a: a[rooms], state)
    done, heads, report, n_chains = jax.vmap(_compact_room)(sub)
    n_chains = jnp.where(mask, n_chains, 0)
    first = jnp.cumsum(n_chains) - n_chains  # a room's first chain number
    bl = done.blocks
    bl = bl._replace(
        content_ref=jnp.where(
            heads >= 0, ref_base + first[:, None] + heads, bl.content_ref
        )
    )
    done = done._replace(blocks=bl)
    report = report.at[..., 0].set(
        jnp.where(
            mask[:, None] & (report[..., 0] >= 0),
            first[:, None] + report[..., 0],
            -1,
        )
    )

    def pick(new, old):
        return jnp.where(mask.reshape(mask.shape + (1,) * (new.ndim - 1)), new, old)

    done = jax.tree.map(pick, done, sub)
    with jax.named_scope("compact_scatter"):
        out = jax.tree.map(
            lambda full, part: full.at[rooms].set(part), state, done
        )
    return out, sub.n_blocks, done.n_blocks, report, n_chains


@partial(jax.jit, donate_argnums=0)
def compact_state(state: DocStateBatch) -> DocStateBatch:
    """Squash + GC + defragment every doc in the batch (one compiled pass).

    The input state is donated: compaction runs exactly when the batch is
    near capacity, so holding two copies of the block columns would double
    HBM at the worst possible moment."""
    return jax.vmap(_compact_one)(state)


def grow_state(state: DocStateBatch, new_capacity: int) -> DocStateBatch:
    """Widen every doc's block capacity (host-side repad; index columns are
    slot-based so they survive unchanged). A stale origin_slot flag
    (identity-keyed) propagates to the repadded output."""
    B = state.blocks.client.shape[-1]
    if new_capacity < B:
        raise ValueError(f"cannot shrink capacity {B} -> {new_capacity}")
    if new_capacity == B:
        return state
    pad = new_capacity - B

    cols = {}
    for name, fill in COL_DEFAULTS.items():
        col = getattr(state.blocks, name)
        ext = jnp.full(col.shape[:-1] + (pad,), fill, dtype=col.dtype)
        cols[name] = jnp.concatenate([col, ext], axis=-1)
    out = state._replace(blocks=BlockCols(**cols))
    from ytpu.models.batch_doc import (
        mark_origin_slot_stale,
        origin_slot_is_stale,
    )

    if origin_slot_is_stale(state):
        mark_origin_slot_stale(out)
    return out


# --- phase-timer wrappers (observability layer) -----------------------------
# The jitted bodies stay module-level (progbudget needs the jit objects);
# the public names grow thin host wrappers that attribute first-call
# compile vs steady-state dispatch per compiled key. Disabled path: one
# attribute check, no allocation (SURVEY §5.5 hot-path rule).

_compact_state_jit = compact_state


def compact_state(state: DocStateBatch) -> DocStateBatch:
    from ytpu.models.batch_doc import (
        mark_origin_slot_stale,
        origin_slot_is_stale,
    )
    from ytpu.utils.phases import NULL_SPAN, phases, program_memory

    # staleness is identity-keyed on the cache array; the defragment
    # remap builds a NEW array, so a stale input must re-mark its output
    # or the unrefreshed cache would launder into a "clean" wrong one
    stale = origin_slot_is_stale(state)
    span = (
        phases.span(
            "compact.state", (state.blocks.client.shape,), axes=("state",),
            memory=program_memory(_compact_state_jit, state),
        )
        if phases.enabled
        else NULL_SPAN
    )
    with span:
        out = _compact_state_jit(state)
    if stale:
        mark_origin_slot_stale(out)
    return out


compact_state.__doc__ = _compact_state_jit.__doc__


def _register_programs():
    from ytpu.utils import progbudget

    progbudget.register("compact_state", _compact_state_jit)
    progbudget.register("compact_rooms", compact_rooms)


_register_programs()
