"""Fused Pallas integrate kernel — the whole update-stream replay in VMEM.

The XLA path (`ytpu.models.batch_doc.apply_update_stream`) streams the full
[docs, capacity] block state through HBM once per update step (every scatter
and select materializes columns). This kernel removes that bottleneck:

- the doc axis is tiled (D_BLK docs per grid program) and each tile's block
  columns are DMA'd into VMEM **once**;
- the *entire* S-step update stream is integrated in-core (YATA conflict
  scans, splits, delete ranges — all vectorized over the doc sublanes with
  one-hot selects over the capacity lanes);
- the tile is written back **once**. HBM traffic drops from
  O(S · docs · capacity) to O(docs · capacity + S).

Semantics mirror `_integrate_row` / `_apply_delete_range` in batch_doc.py
(reference: block.rs:482-769, transaction.rs:472-575); parity is enforced in
tests/test_pallas_kernel.py against both the XLA path and the host oracle.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import lru_cache, partial
from typing import Optional, Tuple

import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ytpu.core.content import (
    BLOCK_GC,
    BLOCK_ROOT_ANCHOR,
    CONTENT_DELETED,
    CONTENT_FORMAT,
    CONTENT_MOVE,
)
from ytpu.models.batch_doc import (
    SCAN_REC_CHEAP,
    SCAN_REC_CHEAP_TRIPS,
    SCAN_REC_MAX,
    SCAN_REC_WIDE,
    SCAN_REC_WIDE_TRIPS,
    SCAN_REC_WIDTH_SUM,
    SCAN_REC_WORDS,
    SCAN_WIDTH_BUCKETS,
    BlockCols,
    DocStateBatch,
    UpdateBatch,
    commit_fold_blocks,
    merge_scan_records,
    scan_tier_plan,
    scan_width_bucket,
    scan_width_quantile,
)

__all__ = [
    "pack_state",
    "unpack_state",
    "pack_stream",
    "apply_update_stream_fused",
    "xla_chunk_step",
    "replay_chunk_program",
    "replay_chunk_program_raw",
    "PackedReplayDriver",
    "ReplayChunkStats",
    "replay_stream_fused",
    "LANE_LADDER",
    "ReplayFault",
    "lane_family",
    "effective_lane",
    "demote_lane",
    "reset_lane_health",
    "lane_health",
    "is_device_fault",
    "N_READOUT",
    "packed_commitments",
]

I32 = jnp.int32

# column indices in the packed [NC, D, C] state
(
    CL,  # client
    CK,  # clock
    LN,  # length
    OC,  # origin client
    OK,  # origin clock
    RC,  # right-origin client
    RK,  # right-origin clock
    LT,  # left link
    RT,  # right link
    DL,  # deleted flag
    CN,  # countable flag
    KD,  # content kind
    RF,  # content ref
    OF,  # content offset
    KEY,  # interned parent_sub (-1 = sequence item)
    PA,  # parent ContentType row (-1 = root)
    HD,  # child-sequence head (ContentType rows)
    MV,  # slot of the move row owning this row (-1 = unowned)
    MSC,  # move rows: range-start id client (-1 = branch-scoped bound)
    MSK,  # move rows: range-start id clock
    MSA,  # move rows: start assoc (>= 0 after, < 0 before)
    MEC,  # move rows: range-end id client
    MEK,  # move rows: range-end id clock
    MEA,  # move rows: end assoc
    MPR,  # move rows: conflict priority
    OS,  # cached origin slot (batch_doc.BlockCols.origin_slot). The kernel
    # itself neither reads nor writes this plane — it rides the packed
    # state so the XLA chunk lane (replay._xla_chunk_step) carries the
    # live cache through pack/unpack at zero cost; kernel-created rows
    # leave it stale, so the fused lane recomputes it wholesale at
    # unpack (apply_update_stream_fused).
) = range(26)
NC = 26

# meta columns in the packed [D, 32] array (padded to a TPU-friendly lane dim)
# M_MDIRTY: move ownership must be recomputed for this doc at step end (a
# move row arrived, an insert straddled differently-owned neighbors, or a
# delete tombstoned a live move — the moves_dirty of batch_doc)
M_START, M_NBLOCKS, M_ERROR, M_MDIRTY = 0, 1, 2, 3
# conflict-scan attribution (ISSUE-11/12): per-doc pow2 bucket counts,
# max width, tier-occupancy and trip-accounting words ride the meta
# tile, accumulated INSIDE the integrate scan (both lanes) so the totals
# survive chunking/compaction/growth for free and surface only through
# the existing lazy readout — never a new sync. Layout mirrors the
# batch_doc.SCAN_REC_* record word-for-word at offset M_HIST0.
M_HIST0 = 4
M_SCANW_MAX = M_HIST0 + SCAN_REC_MAX  # 12: observed max scan width
M_TIER_CHEAP = M_HIST0 + SCAN_REC_CHEAP  # 13: scans resolved cheap-tier
M_TIER_WIDE = M_HIST0 + SCAN_REC_WIDE  # 14: scans escalated to wide tier
M_CHEAP_TRIPS = M_HIST0 + SCAN_REC_CHEAP_TRIPS  # 15: Σ min(width, cheap)
M_WIDE_TRIPS = M_HIST0 + SCAN_REC_WIDE_TRIPS  # 16: Σ wide block trips
M_WIDTH_SUM = M_HIST0 + SCAN_REC_WIDTH_SUM  # 17: Σ width (serial-equiv trips)
M_SCAN_END = M_HIST0 + SCAN_REC_WORDS  # 18 (exclusive)
M_PAD = 32  # the ISSUE-12 trip words outgrew the 16-wide tile (was 8 pre-PR-11)

#: words in the per-chunk lazy readout: the original [3] occupancy/error
#: words + the full scan record (buckets, max, tiers, trips) + the
#: ISSUE-13 state-commitment word (wrap-sum over docs of the per-doc
#: homomorphic lattice digest, `batch_doc.commit_fold_blocks`) + the
#: ISSUE-18 capacity-ledger words (see LEDGER_WORDS)
#: capacity-ledger words (ISSUE-18): Σ occupied rows over docs,
#: Σ dead (tombstoned, GC-able) rows, and the max per-doc dead count —
#: the occupancy/fragmentation gauges ride the SAME lazy readout
#: future, so the zero-sync invariant (`test_async_overlap`) holds
LEDGER_WORDS = 3
N_READOUT = 3 + SCAN_REC_WORDS + 1 + LEDGER_WORDS

ERR_CAPACITY = 1
ERR_MISSING_DEP = 2


def pack_state(state: DocStateBatch) -> Tuple[jax.Array, jax.Array]:
    bl = state.blocks
    cols = jnp.stack(
        [
            bl.client,
            bl.clock,
            bl.length,
            bl.origin_client,
            bl.origin_clock,
            bl.ror_client,
            bl.ror_clock,
            bl.left,
            bl.right,
            bl.deleted.astype(I32),
            bl.countable.astype(I32),
            bl.kind,
            bl.content_ref,
            bl.content_off,
            bl.key,
            bl.parent,
            bl.head,
            bl.moved,
            bl.mv_sc,
            bl.mv_sk,
            bl.mv_sa,
            bl.mv_ec,
            bl.mv_ek,
            bl.mv_ea,
            bl.mv_prio,
            bl.origin_slot,
        ]
    )  # [NC, D, C]
    D = state.start.shape[0]
    meta = jnp.zeros((D, M_PAD), I32)
    meta = meta.at[:, M_START].set(state.start)
    meta = meta.at[:, M_NBLOCKS].set(state.n_blocks)
    meta = meta.at[:, M_ERROR].set(state.error)
    return cols, meta


def unpack_state(
    cols: jax.Array, meta: jax.Array, state: DocStateBatch
) -> DocStateBatch:
    """Rebuild state from kernel outputs."""
    del state  # all columns now live in the packed buffers
    blocks = BlockCols(
        client=cols[CL],
        clock=cols[CK],
        length=cols[LN],
        origin_client=cols[OC],
        origin_clock=cols[OK],
        ror_client=cols[RC],
        ror_clock=cols[RK],
        left=cols[LT],
        right=cols[RT],
        deleted=cols[DL].astype(bool),
        countable=cols[CN].astype(bool),
        kind=cols[KD],
        content_ref=cols[RF],
        content_off=cols[OF],
        key=cols[KEY],
        parent=cols[PA],
        head=cols[HD],
        moved=cols[MV],
        mv_sc=cols[MSC],
        mv_sk=cols[MSK],
        mv_sa=cols[MSA],
        mv_ec=cols[MEC],
        mv_ek=cols[MEK],
        mv_ea=cols[MEA],
        mv_prio=cols[MPR],
        origin_slot=cols[OS],
    )
    return DocStateBatch(
        blocks=blocks,
        start=meta[:, M_START],
        n_blocks=meta[:, M_NBLOCKS],
        error=meta[:, M_ERROR],
    )


def pack_stream(stream: UpdateBatch) -> Tuple[jax.Array, jax.Array]:
    """Stacked doc-axis-free stream → rows [S, U, 23] / dels [S, R, 4] i32."""
    rows = jnp.stack(
        [
            stream.client,
            stream.clock,
            stream.length,
            stream.origin_client,
            stream.origin_clock,
            stream.ror_client,
            stream.ror_clock,
            stream.kind,
            stream.content_ref,
            stream.content_off,
            stream.key,
            stream.p_tag,
            stream.p_client,
            stream.p_clock,
            stream.valid.astype(I32),
            stream.mv_sc,
            stream.mv_sk,
            stream.mv_sa,
            stream.mv_ec,
            stream.mv_ek,
            stream.mv_ea,
            stream.mv_prio,
            stream.p_root,
        ],
        axis=-1,
    )  # [S, U, 23]
    dels = jnp.stack(
        [
            stream.del_client,
            stream.del_start,
            stream.del_end,
            stream.del_valid.astype(I32),
        ],
        axis=-1,
    )  # [S, R, 4]
    return rows, dels


def _kernel(
    rows_ref,
    dels_ref,
    rank_ref,
    _cols_in,
    _meta_in,
    cols_ref,
    meta_ref,
    *,
    phases: int = 3,
    row_phase: int = 4,
    scan_plan: Tuple[int, int] = (32, 8),
):
    """One doc tile: integrate the whole stream in VMEM.

    cols_ref: [NC, DB, C] out-ref aliased to the input (holds the state),
    meta_ref: [DB, M_PAD=32] aliased (cols 0-3 start/n_blocks/error/
    mdirty; cols M_HIST0..M_SCAN_END the scan record); rows_ref:
    [S, U, 23], dels_ref: [S, R, 4], rank_ref: [1, K].

    `phases` / `row_phase` are HARDWARE-BISECT hooks (trace-time static,
    threaded from `apply_update_stream_fused`): they truncate the kernel
    after the row loop / delete loop (phases) or mid-`integrate_row`
    (row_phase) so a Mosaic miscompile or device fault can be localized.
    Production callers leave the defaults (full kernel); partial values
    corrupt state by design and must never ship.

    `scan_plan = (cheap_bound, wide_unroll)` is the ISSUE-12 two-tier
    conflict-scan static: the cheap tier keeps the original one-
    candidate-per-trip loop up to `cheap_bound` trips, the wide tier
    unrolls `wide_unroll` masked candidate steps per while trip for the
    deep-conflict tail. A changed plan recompiles (the public entries
    re-read the env per call, like YTPU_FUSED_VMEM_MB).
    """
    S, U, _ = rows_ref.shape
    R = dels_ref.shape[1]
    DB = cols_ref.shape[1]
    C = cols_ref.shape[2]

    # Copy every aliased in-ref into its out-ref before anything else, so
    # each plane of the output tile is stored at least once and the rest
    # of the kernel reads and writes the out-refs only. Whether a
    # never-stored aliased output comes back intact on a directly
    # attached v5e is not known; this copy does not depend on it.
    for _i in range(cols_ref.shape[0]):
        cols_ref[_i] = _cols_in[_i]
    meta_ref[:, :] = _meta_in[:, :]

    iota_c = jax.lax.broadcasted_iota(I32, (DB, C), 1)

    def col(i):
        return cols_ref[i]

    def mrow(mask):
        """(DB,) bool -> (DB, 1) bool. Mosaic cannot insert a minor dim on
        an i1 vector ("only supported for 32-bit types"), so widen to i32,
        insert, and compare back down."""
        return mask.astype(I32)[:, None] > 0

    def gather(i, idx, fill):
        """Per-doc element col(i)[d, idx[d]] with idx < 0 -> fill."""
        onehot = iota_c == idx[:, None]
        v = jnp.sum(jnp.where(onehot, col(i), 0), axis=1)
        return jnp.where(idx >= 0, v, fill)

    def put(i, idx, val, active):
        """col(i)[d, idx[d]] = val[d] where active[d] & idx valid."""
        mask = (iota_c == idx[:, None]) & mrow(active) & (idx[:, None] >= 0)
        cols_ref[i] = jnp.where(mask, val[:, None], col(i))

    def put_many(idx, active, writes):
        """Write several columns at one slot, computing the mask once.

        `writes` is [(col_idx, val_vector), ...]; same semantics as `put`."""
        mask = (iota_c == idx[:, None]) & mrow(active) & (idx[:, None] >= 0)
        for i, val in writes:
            cols_ref[i] = jnp.where(mask, val[:, None], col(i))

    def n_blocks():
        return meta_ref[:, M_NBLOCKS]

    K = rank_ref.shape[1]
    iota_k = jax.lax.broadcasted_iota(I32, (DB, K), 1)

    def gather_rank(client_v):
        """rank_ref[0, client_v[d]] per doc (one-hot gather)."""
        onehot = iota_k == jnp.maximum(client_v, 0)[:, None]
        return jnp.sum(jnp.where(onehot, rank_ref[0][None, :], 0), axis=1)

    def find_slot(client_v, clock_v, enable):
        """(idx[DB], found[DB]) of the block covering (client, clock);
        `client_v`/`clock_v` are per-doc (DB,) vectors."""
        valid = iota_c < n_blocks()[:, None]
        m = (
            valid
            & (col(CL) == client_v[:, None])
            & (col(CK) <= clock_v[:, None])
            & (clock_v[:, None] < col(CK) + col(LN))
            & mrow(enable)
        )
        # integer argmax is unsupported in Mosaic: min-reduce the indices
        idx = jnp.min(jnp.where(m, iota_c, C), axis=1).astype(I32)
        found = idx < C
        return jnp.where(found, idx, -1), found

    def client_clock(client_s):
        valid = iota_c < n_blocks()[:, None]
        m = valid & (col(CL) == client_s)
        return jnp.max(jnp.where(m, col(CK) + col(LN), 0), axis=1)

    def split(i_idx, off, want):
        """Split block i at off (per doc); returns right-half slot (or i).

        The whole write phase sits behind `pl.when(any(do))`: the hot replay
        case (appends, whole-block deletes) needs no split in *any* doc of
        the tile, so the ~30 [DB, C] sweeps below are skipped entirely."""
        length_i = gather(LN, i_idx, 0)
        do = want & (i_idx >= 0) & (off > 0) & (off < length_i)
        j = n_blocks()
        overflow = do & (j >= C)
        do = do & (j < C)
        # the error record must not sit behind the lazy write phase: a tile
        # where every needed split overflows has all-False `do`
        meta_ref[:, M_ERROR] = meta_ref[:, M_ERROR] | jnp.where(
            overflow, ERR_CAPACITY, 0
        )

        @pl.when(jnp.any(do))
        def _():
            right_i = gather(RT, i_idx, -1)
            # new row j = right half (moved inherits — splice parity; the
            # mv_* range fields stay empty: length-1 move rows never split)
            put_many(
                j,
                do,
                [
                    (CL, gather(CL, i_idx, -1)),
                    (CK, gather(CK, i_idx, 0) + off),
                    (LN, length_i - off),
                    (OC, gather(CL, i_idx, -1)),
                    (OK, gather(CK, i_idx, 0) + off - 1),
                    (RC, gather(RC, i_idx, -1)),
                    (RK, gather(RK, i_idx, 0)),
                    (LT, i_idx),
                    (RT, right_i),
                    (DL, gather(DL, i_idx, 0)),
                    (CN, gather(CN, i_idx, 0)),
                    (KD, gather(KD, i_idx, 0)),
                    (RF, gather(RF, i_idx, -1)),
                    (OF, gather(OF, i_idx, 0) + off),
                    (KEY, gather(KEY, i_idx, -1)),
                    (PA, gather(PA, i_idx, -1)),
                    (HD, gather(HD, i_idx, -1)),
                    (MV, gather(MV, i_idx, -1)),
                    (MSC, jnp.full((DB,), -1, I32)),
                    (MSK, jnp.zeros((DB,), I32)),
                    (MSA, jnp.zeros((DB,), I32)),
                    (MEC, jnp.full((DB,), -1, I32)),
                    (MEK, jnp.zeros((DB,), I32)),
                    (MEA, jnp.zeros((DB,), I32)),
                    (MPR, jnp.full((DB,), -1, I32)),
                ],
            )
            # fix left half + old right neighbor
            put_many(i_idx, do, [(LN, off), (RT, j)])
            put(LT, right_i, j, do & (right_i >= 0))
            meta_ref[:, M_NBLOCKS] = n_blocks() + do.astype(I32)

        return jnp.where(do, j, i_idx)

    def clean_end(client_s, clock_v, enable):
        i, found = find_slot(client_s, clock_v, enable)
        off = clock_v - gather(CK, i, 0) + 1
        split(i, off, enable & found)
        return i, found

    def clean_start(client_s, clock_v, enable):
        i, found = find_slot(client_s, clock_v, enable)
        off = clock_v - gather(CK, i, 0)
        j = split(i, off, enable & found)
        return jnp.where((i >= 0) & (off > 0), j, i), found

    def integrate_row(s, u):
        r_client = rows_ref[s, u, 0]
        r_clock = rows_ref[s, u, 1]
        r_len = rows_ref[s, u, 2]
        r_oc = rows_ref[s, u, 3]
        r_ok = rows_ref[s, u, 4]
        r_rc = rows_ref[s, u, 5]
        r_rk = rows_ref[s, u, 6]
        r_kind = rows_ref[s, u, 7]
        r_ref = rows_ref[s, u, 8]
        r_off = rows_ref[s, u, 9]
        r_key = rows_ref[s, u, 10]
        r_ptag = rows_ref[s, u, 11]
        r_pclient = rows_ref[s, u, 12]
        r_pclock = rows_ref[s, u, 13]
        r_mv_sc = rows_ref[s, u, 15]
        r_mv_sk = rows_ref[s, u, 16]
        r_mv_sa = rows_ref[s, u, 17]
        r_mv_ec = rows_ref[s, u, 18]
        r_mv_ek = rows_ref[s, u, 19]
        r_mv_ea = rows_ref[s, u, 20]
        r_mv_prio = rows_ref[s, u, 21]
        r_proot = rows_ref[s, u, 22]
        is_move_row = r_kind == CONTENT_MOVE

        local = client_clock(r_client)  # (DB,)
        applicable = local >= r_clock
        missing = ~applicable
        offset = local - r_clock
        dup = applicable & (offset >= r_len)
        do = applicable & ~dup

        clock = r_clock + offset
        length = r_len - offset
        c_off = r_off + offset
        has_origin = (offset > 0) | (r_oc >= 0)
        origin_client = jnp.where(offset > 0, r_client, r_oc)
        origin_clock = jnp.where(offset > 0, clock - 1, r_ok)
        has_ror = r_rc >= 0

        is_gc = r_kind == BLOCK_GC
        linkable = do & ~is_gc

        if row_phase < 2:
            meta_ref[:, M_ERROR] = meta_ref[:, M_ERROR] | jnp.where(
                missing, ERR_MISSING_DEP, 0
            )
            return

        left_idx, lfound = clean_end(
            origin_client, origin_clock, linkable & has_origin
        )
        right_idx, rfound = clean_start(
            jnp.full((DB,), r_rc, I32), jnp.full((DB,), r_rk, I32),
            linkable & has_ror,
        )
        left_idx = jnp.where(linkable & has_origin, left_idx, -1)
        right_idx = jnp.where(linkable & has_ror, right_idx, -1)

        anchor_missing = (linkable & has_origin & (left_idx < 0)) | (
            linkable & has_ror & (right_idx < 0)
        )
        missing = missing | anchor_missing
        linkable = linkable & ~anchor_missing

        # parent branch (parity: block.rs:503-523): p_tag 2 = nested branch
        # by ContentType item id; 1 = root; 0 = inherit from the resolved
        # left (else right) anchor
        parent_slot, _pfound = find_slot(
            jnp.full((DB,), r_pclient, I32),
            jnp.full((DB,), r_pclock, I32),
            linkable & (r_ptag == 2),
        )
        left_parent = gather(PA, left_idx, -1)
        right_parent = gather(PA, right_idx, -1)
        inherited_parent = jnp.where(left_idx >= 0, left_parent, right_parent)
        # named-root parents: primary (p_root < 0) -> the doc sequence;
        # non-primary -> the BLOCK_ROOT_ANCHOR row keyed by the root id
        # (created host-side before the apply; absence = missing dep)
        anchor_m = (
            (iota_c < n_blocks()[:, None])
            & (col(KD) == BLOCK_ROOT_ANCHOR)
            & (col(KEY) == r_proot)
        )
        anchor_idx = jnp.min(jnp.where(anchor_m, iota_c, C), axis=1).astype(I32)
        anchor_found = anchor_idx < C
        root_row = jnp.where(
            (r_proot >= 0) & anchor_found, anchor_idx, -1
        )
        parent_row = jnp.where(
            r_ptag == 2,
            parent_slot,
            jnp.where(r_ptag == 1, root_row, inherited_parent),
        )
        parent_missing = linkable & (
            ((r_ptag == 2) & (parent_slot < 0))
            | ((r_ptag == 1) & (r_proot >= 0) & ~anchor_found)
        )
        missing = missing | parent_missing
        linkable = linkable & ~parent_missing
        if row_phase < 3:
            return

        # parent_sub: inherited from the anchors when omitted on the wire
        # (parity: block.rs:604-612)
        left_key = gather(KEY, left_idx, -1)
        right_key = gather(KEY, right_idx, -1)
        key_v = jnp.where(
            r_key >= 0,
            jnp.full((DB,), r_key, I32),
            jnp.where(left_key >= 0, left_key, right_key),
        )
        is_map = key_v >= 0

        # map rows anchor on their (parent, key) chain's leftmost item
        # (parity: block.rs:541-551); sequence rows on the parent's head
        valid_slots = iota_c < n_blocks()[:, None]
        chain_mask = (
            valid_slots
            & (col(KEY) == key_v[:, None])
            & (col(PA) == parent_row[:, None])
            & (col(LT) == -1)
            & mrow(is_map)
        )
        chain_idx = jnp.min(jnp.where(chain_mask, iota_c, C), axis=1).astype(I32)
        chain_head = jnp.where(chain_idx < C, chain_idx, -1)
        seq_head = jnp.where(
            parent_row >= 0, gather(HD, parent_row, -1), meta_ref[:, M_START]
        )
        anchor0_base = jnp.where(is_map, chain_head, seq_head)

        right_left = gather(LT, right_idx, -1)
        need_scan = linkable & (
            ((left_idx < 0) & ((right_idx < 0) | (right_left >= 0)))
            | ((left_idx >= 0) & (gather(RT, left_idx, -1) != right_idx))
        )
        o0 = jnp.where(left_idx >= 0, gather(RT, left_idx, -1), anchor0_base)
        o0 = jnp.where(need_scan, o0, -1)

        def origins_equal(ha, ca, ka, hb, cb, kb):
            return (~ha & ~hb) | (ha & hb & (ca == cb) & (ka == kb))

        cheap_bound, wide_unroll = scan_plan

        def scan_step(carry):
            """One candidate step, fully masked by `active` (a resolved
            doc no-ops through it) — composes both as a whole cheap-tier
            trip and as one sub-step of a wide-tier unrolled block.
            Every carry element is a (DB,)- or (DB, C)-shaped VECTOR,
            never a scalar accumulator carried over VMEM data (the
            mosaic ladder's rungs 3 and 5)."""
            o, left, conflicting, before, brk, width = carry
            active = (o >= 0) & (o != right_idx) & (brk == 0)
            width = width + active.astype(I32)
            onehot_o = ((iota_c == o[:, None]) & mrow(active)).astype(I32)
            before = before | onehot_o
            conflicting = conflicting | onehot_o
            o_oc = gather(OC, o, -1)
            o_ok = gather(OK, o, 0)
            same_origin = origins_equal(
                has_origin, origin_client, origin_clock, o_oc >= 0, o_oc, o_ok
            )
            o_rc = gather(RC, o, -1)
            o_rk = gather(RK, o, 0)
            same_ror = origins_equal(has_ror, r_rc, r_rk, o_rc >= 0, o_rc, o_rk)
            o_client = gather(CL, o, -1)
            rank_o = gather_rank(o_client)
            rank_r = gather_rank(jnp.full((DB,), r_client, I32))
            case1_take = same_origin & (rank_o < rank_r)
            case1_break = same_origin & ~case1_take & same_ror
            # case 2: does o's origin sit inside the scanned region?
            oo_idx, oo_found = find_slot(o_oc, o_ok, active & (o_oc >= 0))
            # per-doc membership of oo_idx in before/conflicting
            in_before = oo_found & (
                jnp.sum(jnp.where(iota_c == oo_idx[:, None], before, 0), axis=1) > 0
            )
            in_conflicting = oo_found & (
                jnp.sum(jnp.where(iota_c == oo_idx[:, None], conflicting, 0), axis=1)
                > 0
            )
            case2_take = ~same_origin & in_before & ~in_conflicting
            case2_break = ~same_origin & ~in_before

            take = (case1_take | case2_take) & active
            left = jnp.where(take, o, left)
            conflicting = jnp.where(mrow(take), 0, conflicting)
            brk = brk | ((case1_break | case2_break) & active).astype(I32)
            o_next = gather(RT, o, -1)
            o = jnp.where(active & (brk == 0), o_next, o)
            return (o, left, conflicting, before, brk, width)

        # --- two-tier dispatch (ISSUE-12) ---
        # CHEAP tier: the original one-candidate-per-trip loop, bounded.
        # All active docs advance in lockstep, so `width` doubles as the
        # tier's trip counter (uniform across active docs) — the bound
        # compare folds into the cond instead of a new carry element.
        def cheap_cond(carry):
            o, left, conflicting, before, brk, width = carry
            active = (o >= 0) & (o != right_idx) & (brk == 0)
            return jnp.any(active & (width < cheap_bound))

        zeros = jnp.zeros((DB, C), I32)
        carry = jax.lax.while_loop(
            cheap_cond,
            scan_step,
            (o0, left_idx, zeros, zeros, jnp.zeros((DB,), I32),
             jnp.zeros((DB,), I32)),
        )

        # WIDE tier: still-unresolved (deep-conflict) docs continue with
        # `wide_unroll` masked candidate steps per while trip — whole-
        # block membership/origin tests per dispatch instead of one
        # element per trip. `wtrips` counts per-doc block trips (the
        # tier-occupancy sample); a (DB,) vector like every other carry.
        def wide_cond(carry):
            inner, wtrips = carry
            o, left, conflicting, before, brk, width = inner
            return jnp.any((o >= 0) & (o != right_idx) & (brk == 0))

        def wide_body(carry):
            inner, wtrips = carry
            o, left, conflicting, before, brk, width = inner
            entered = (o >= 0) & (o != right_idx) & (brk == 0)
            wtrips = wtrips + entered.astype(I32)
            for _ in range(wide_unroll):
                inner = scan_step(inner)
            return inner, wtrips

        (_, left_scanned, _, _, _, scan_width), wide_trips = (
            jax.lax.while_loop(
                wide_cond, wide_body, (carry, jnp.zeros((DB,), I32))
            )
        )
        left_idx = jnp.where(need_scan, left_scanned, left_idx)
        # conflict-tail attribution (ISSUE-11): fold this row's per-doc
        # scan width into the pow2 histogram riding the meta tile — a
        # handful of (DB,)-wide compares per row, no extra HBM traffic,
        # materialized host-side only when the lazy readout is pulled
        wb = jnp.maximum(scan_width, 0)
        # the SAME bucket function as the packed-XLA lane (pure jnp ops,
        # vectorizes over the doc sublanes) — one definition, so the two
        # lanes' histograms can never drift apart
        bucket = scan_width_bucket(wb)
        for _k in range(SCAN_WIDTH_BUCKETS):
            meta_ref[:, M_HIST0 + _k] = meta_ref[:, M_HIST0 + _k] + (
                need_scan & (bucket == _k)
            ).astype(I32)
        meta_ref[:, M_SCANW_MAX] = jnp.maximum(
            meta_ref[:, M_SCANW_MAX], jnp.where(need_scan, wb, 0)
        )
        # tier occupancy + trip accounting (ISSUE-12): identical word
        # semantics to the packed-XLA lane's _fold_scan_width, so the
        # readout record is lane-agnostic (cheap trips use the SAME
        # min(width, bound) accounting — per-doc attribution of the
        # lockstep tile loop matches the vmapped XLA lane exactly)
        wide_used = need_scan & (wide_trips > 0)
        meta_ref[:, M_TIER_CHEAP] = meta_ref[:, M_TIER_CHEAP] + (
            need_scan & ~wide_used
        ).astype(I32)
        meta_ref[:, M_TIER_WIDE] = (
            meta_ref[:, M_TIER_WIDE] + wide_used.astype(I32)
        )
        meta_ref[:, M_CHEAP_TRIPS] = meta_ref[:, M_CHEAP_TRIPS] + jnp.where(
            need_scan, jnp.minimum(wb, cheap_bound), 0
        )
        meta_ref[:, M_WIDE_TRIPS] = meta_ref[:, M_WIDE_TRIPS] + jnp.where(
            need_scan, wide_trips, 0
        )
        meta_ref[:, M_WIDTH_SUM] = meta_ref[:, M_WIDTH_SUM] + jnp.where(
            need_scan, wb, 0
        )
        if row_phase < 4:
            return

        j = n_blocks()
        overflow = do & (j >= C)
        do = do & (j < C)
        linkable = linkable & (j < C)

        has_left = linkable & (left_idx >= 0)
        right_final = jnp.where(
            has_left, gather(RT, left_idx, -1), jnp.where(linkable, anchor0_base, -1)
        )
        put(RT, left_idx, j, has_left)
        # sequence rows with no left become the head: the root start, or
        # the parent branch's head column (map rows never touch the head)
        new_head = linkable & ~has_left & ~is_map
        meta_ref[:, M_START] = jnp.where(
            new_head & (parent_row < 0), j, meta_ref[:, M_START]
        )
        put(HD, parent_row, j, new_head & (parent_row >= 0))
        put(LT, right_final, j, linkable & (right_final >= 0))

        # self-delete on arrival (parity: block.rs:751-765): a row under a
        # tombstoned parent, or a map row landing with a right neighbor (a
        # losing concurrent write), integrates directly as deleted
        parent_deleted = (parent_row >= 0) & (gather(DL, parent_row, 0) == 1)
        dead_on_arrival = linkable & (
            parent_deleted | (is_map & (right_final >= 0))
        )
        row_deleted = is_gc | (r_kind == CONTENT_DELETED) | dead_on_arrival
        row_countable = (
            ~row_deleted & (r_kind != CONTENT_FORMAT) & (r_kind != CONTENT_MOVE)
        )

        # moved-range inheritance (parity: block.rs:677-702): an insert
        # between rows owned by the same move inherits the owner; a
        # mismatch marks the doc for the end-of-step recompute
        left_moved = jnp.where(has_left, gather(MV, left_idx, -1), -1)
        right_moved = jnp.where(
            right_final >= 0, gather(MV, right_final, -1), -1
        )
        inherit_moved = jnp.where(left_moved == right_moved, left_moved, -1)
        moved_conflict = linkable & (left_moved != right_moved)
        meta_ref[:, M_MDIRTY] = meta_ref[:, M_MDIRTY] | (
            (moved_conflict | (do & is_move_row)).astype(I32)
        )

        put_many(
            j,
            do,
            [
                (CL, jnp.full((DB,), r_client, I32)),
                (CK, clock),
                (LN, length),
                (OC, jnp.where(has_origin, origin_client, -1)),
                (OK, jnp.where(has_origin, origin_clock, 0)),
                (RC, jnp.full((DB,), jnp.where(has_ror, r_rc, -1), I32)),
                (RK, jnp.full((DB,), jnp.where(has_ror, r_rk, 0), I32)),
                (LT, jnp.where(linkable, left_idx, -1)),
                (RT, jnp.where(linkable, right_final, -1)),
                (DL, row_deleted.astype(I32)),
                (CN, row_countable.astype(I32)),
                (KD, jnp.full((DB,), r_kind, I32)),
                (RF, jnp.full((DB,), r_ref, I32)),
                (OF, c_off),
                (KEY, key_v),
                (PA, parent_row),
                (HD, jnp.full((DB,), -1, I32)),
                (MV, jnp.where(linkable, inherit_moved, -1)),
                (MSC, jnp.full((DB,), jnp.where(is_move_row, r_mv_sc, -1), I32)),
                (MSK, jnp.full((DB,), jnp.where(is_move_row, r_mv_sk, 0), I32)),
                (MSA, jnp.full((DB,), jnp.where(is_move_row, r_mv_sa, 0), I32)),
                (MEC, jnp.full((DB,), jnp.where(is_move_row, r_mv_ec, -1), I32)),
                (MEK, jnp.full((DB,), jnp.where(is_move_row, r_mv_ek, 0), I32)),
                (MEA, jnp.full((DB,), jnp.where(is_move_row, r_mv_ea, 0), I32)),
                (MPR, jnp.full((DB,), jnp.where(is_move_row, r_mv_prio, -1), I32)),
            ],
        )
        # a map row that became its chain's tail is the key's live value;
        # the previous winner — its immediate left — gets tombstoned
        # (parity: block.rs:637-659)
        new_tail = linkable & is_map & (right_final < 0)
        put(DL, left_idx, jnp.ones((DB,), I32), new_tail & has_left)
        meta_ref[:, M_NBLOCKS] = n_blocks() + do.astype(I32)
        meta_ref[:, M_ERROR] = (
            meta_ref[:, M_ERROR]
            | jnp.where(overflow, ERR_CAPACITY, 0)
            | jnp.where(missing, ERR_MISSING_DEP, 0)
        )

    def delete_range(s, r):
        client = dels_ref[s, r, 0]
        start = dels_ref[s, r, 1]
        end = dels_ref[s, r, 2]
        enable = jnp.ones((DB,), bool)
        client_v = jnp.full((DB,), client, I32)
        start_v = jnp.full((DB,), start, I32)
        end_v = jnp.full((DB,), end, I32)
        # split head
        i, found = find_slot(client_v, start_v, enable)
        i_ok = found & (gather(DL, i, 1) == 0)
        split(i, start_v - gather(CK, i, 0), i_ok)
        # split tail
        k, kfound = find_slot(client_v, end_v - 1, enable)
        k_ok = kfound & (gather(DL, k, 1) == 0)
        split(k, end_v - gather(CK, k, 0), k_ok)
        # mark covered blocks deleted; tombstoning a live move row dirties
        # the doc (its claims must be released — moving.rs:229-280)
        valid = iota_c < n_blocks()[:, None]
        m = (
            valid
            & (col(CL) == client)
            & (col(CK) >= start)
            & (col(CK) + col(LN) <= end)
        )
        hit_move = jnp.any(
            m & (col(KD) == CONTENT_MOVE) & (col(DL) == 0), axis=1
        )
        meta_ref[:, M_MDIRTY] = meta_ref[:, M_MDIRTY] | hit_move.astype(I32)
        cols_ref[DL] = jnp.where(m, 1, col(DL))

    # --- move ownership (parity: moving.rs:149-227 via batch_doc's
    # _claim_move/_move_cycle/_recompute_moves) -----------------------------

    def resolve_move_ptr(c_v, k_v, assoc_v, enable):
        """Sticky (client, clock, assoc) -> first in-range slot per doc."""
        after = assoc_v >= 0
        i_a, found_a = clean_start(c_v, k_v, enable & after & (c_v >= 0))
        i_b, found_b = clean_end(c_v, k_v, enable & ~after & (c_v >= 0))
        right_b = gather(RT, i_b, -1)
        ptr = jnp.where(after, i_a, right_b)
        # logical blend, not jnp.where: Mosaic cannot lower an i1-vector
        # select (trunci i8->i1) on real TPU
        found = (after & found_a) | (~after & found_b)
        return ptr, found

    def claim_move(s_v, enable):
        """One claim pass for per-doc move slot s_v (walk its range,
        claiming rows the move beats on (priority, client rank, clock))."""
        msc = gather(MSC, s_v, -1)
        msk = gather(MSK, s_v, 0)
        msa = gather(MSA, s_v, 0)
        mec = gather(MEC, s_v, -1)
        mek = gather(MEK, s_v, 0)
        mea = gather(MEA, s_v, 0)
        start, s_found = resolve_move_ptr(msc, msk, msa, enable)
        endp, e_found = resolve_move_ptr(mec, mek, mea, enable)
        par = gather(PA, s_v, -1)
        seq_head = jnp.where(
            par < 0, meta_ref[:, M_START], gather(HD, par, -1)
        )
        start = jnp.where(msc < 0, seq_head, start)
        endp = jnp.where(mec < 0, -1, endp)
        unresolved = enable & (
            ((msc >= 0) & ~s_found) | ((mec >= 0) & ~e_found)
        )
        meta_ref[:, M_ERROR] = meta_ref[:, M_ERROR] | jnp.where(
            unresolved, ERR_MISSING_DEP, 0
        )
        enable = enable & ~unresolved
        prio_s = gather(MPR, s_v, -1)
        rank_s = gather_rank(gather(CL, s_v, -1))
        clock_s = gather(CK, s_v, 0)

        def wcond(carry):
            cur, n = carry
            return jnp.any(enable & (cur >= 0) & (cur != endp) & (n <= C))

        def wbody(carry):
            cur, n = carry
            active = enable & (cur >= 0) & (cur != endp) & (n <= C)
            m = gather(MV, cur, -1)
            prev_prio = jnp.where(m >= 0, gather(MPR, m, -1), -1)
            prev_rank = gather_rank(gather(CL, m, -1))
            prev_clock = gather(CK, m, 0)
            takes = (prev_prio < prio_s) | (
                (prev_prio == prio_s)
                & (m >= 0)
                & (
                    (prev_rank < rank_s)
                    | ((prev_rank == rank_s) & (prev_clock < clock_s))
                )
            )
            # a beaten collapsed move tombstones on the spot (parity:
            # _delete_as_cleanup, moving.rs:190-196)
            m_msc = gather(MSC, m, -1)
            m_collapsed = (
                (m >= 0)
                & (m_msc >= 0)
                & (m_msc == gather(MEC, m, -2))
                & (gather(MSK, m, 0) == gather(MEK, m, -1))
            )
            put(DL, m, jnp.ones((DB,), I32), active & takes & m_collapsed)
            put(MV, cur, s_v, active & takes)
            cur = jnp.where(active, gather(RT, cur, -1), cur)
            return cur, n + 1

        jax.lax.while_loop(wcond, wbody, (start, jnp.zeros((DB,), I32)))
        return enable

    def move_cycle(s_v, enable):
        """Does s_v sit on an ownership cycle? Ownership is single-parent,
        so walking the `moved` chain upward from s_v either terminates or
        returns to s_v (find_move_loop parity, moving.rs:113-141). Like
        the XLA `_move_cycle`, the chain only counts LIVE MOVE nodes — a
        stale claim held by a tombstoned move must not close a cycle."""

        def live_move(idx):
            return (gather(KD, idx, -1) == CONTENT_MOVE) & (
                gather(DL, idx, 1) == 0
            )

        # `hit` rides the carry as i32 0/1: an i1-vector loop carry fails
        # Mosaic legalization (scf.yield) on real TPU
        def ccond(carry):
            cur, n, hit = carry
            return jnp.any(enable & (cur >= 0) & (hit == 0) & (n <= C))

        def cbody(carry):
            cur, n, hit = carry
            active = enable & (cur >= 0) & (hit == 0) & (n <= C)
            nxt = gather(MV, cur, -1)
            hit = hit | (active & (nxt == s_v) & (s_v >= 0)).astype(I32)
            # a dead or non-move node breaks the live ownership chain
            nxt = jnp.where(live_move(nxt), nxt, -1)
            cur = jnp.where(active, nxt, cur)
            return cur, n + 1, hit

        first = gather(MV, s_v, -1)
        first = jnp.where(live_move(first), first, -1)
        _, _, hit = jax.lax.while_loop(
            ccond,
            cbody,
            (first, jnp.zeros((DB,), I32), jnp.zeros((DB,), I32)),
        )
        return hit > 0

    def recompute_moves():
        """Per-doc from-scratch ownership recompute for dirty docs (the
        end-of-update pass of batch_doc._recompute_moves)."""
        dirty = meta_ref[:, M_MDIRTY] > 0

        @pl.when(jnp.any(dirty))
        def _():
            cols_ref[MV] = jnp.where(mrow(dirty), -1, col(MV))
            done0 = jnp.zeros((DB, C), I32)

            def active_moves(done):
                return (
                    (iota_c < n_blocks()[:, None])
                    & (col(KD) == CONTENT_MOVE)
                    & (col(DL) == 0)
                    & (done == 0)
                    & mrow(dirty)
                )

            def rcond(done):
                return jnp.any(active_moves(done))

            def rbody(done):
                am = active_moves(done)
                s_idx = jnp.min(jnp.where(am, iota_c, C), axis=1).astype(I32)
                exists = s_idx < C
                s_v = jnp.where(exists, s_idx, -1)
                enable = claim_move(s_v, dirty & exists)
                cyc = move_cycle(s_v, enable) & exists
                put(DL, s_v, jnp.ones((DB,), I32), cyc)
                # cycle: release every claim and replay without s
                cols_ref[MV] = jnp.where(mrow(cyc), -1, col(MV))
                onehot_s = (iota_c == s_v[:, None]) & mrow(exists)
                done = jnp.where(
                    mrow(cyc), 0, done | onehot_s.astype(I32)
                )
                return done

            jax.lax.while_loop(rcond, rbody, done0)

        meta_ref[:, M_MDIRTY] = jnp.zeros((DB,), I32)

    def step(s, _):
        if phases >= 1:
            def row_body(u, __):
                @pl.when(rows_ref[s, u, 14] == 1)
                def _():
                    integrate_row(s, u)

                return 0

            jax.lax.fori_loop(0, U, row_body, 0)

        if phases >= 2:
            def del_body(r, __):
                @pl.when(dels_ref[s, r, 3] == 1)
                def _():
                    delete_range(s, r)

                return 0

            jax.lax.fori_loop(0, R, del_body, 0)
        if phases >= 3:
            recompute_moves()
        return 0

    jax.lax.fori_loop(0, S, step, 0)


def fused_vmem_mb(d_block: int, capacity: int) -> int:
    """Scoped-VMEM limit for one fused tile, in MB; `YTPU_FUSED_VMEM_MB`
    overrides it. The grid pipeline holds two buffers each of the aliased
    input and output tile ([NC, d_block, C] i32 apiece) beside the scan's
    temporaries, so the limit follows the tile: the compiler measured
    115 MB for d_block=128 x C=2048 and 117 MB for d_block=8 x C=32768
    (4 x 26 MB + temporaries) and
    refuses that under a flat 64. Capped under a v5e's 128 MiB of VMEM.
    Callers thread the value through `_run` as a STATIC argument, so a
    changed override retraces."""
    env = os.environ.get("YTPU_FUSED_VMEM_MB")
    if env:
        return int(env)
    tile_mb = NC * d_block * capacity * 4 / 2**20
    return min(max(64, int(4 * tile_mb) + 16), 120)


def _run_body(
    cols, meta, packed, d_block: int, interpret: bool,
    phases: int = 3, row_phase: int = 4, vmem_limit_mb: int = 64,
    scan_plan: Optional[Tuple[int, int]] = None,
):
    if scan_plan is None:
        scan_plan = scan_tier_plan()
    rows, dels, rank = packed
    NC_, D, C = cols.shape
    grid = (D // d_block,)
    rank = rank.reshape(1, -1)
    out = pl.pallas_call(
        partial(
            _kernel, phases=phases, row_phase=row_phase, scan_plan=scan_plan
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec(rows.shape, lambda d: (0, 0, 0)),
            pl.BlockSpec(dels.shape, lambda d: (0, 0, 0)),
            pl.BlockSpec(rank.shape, lambda d: (0, 0)),
            pl.BlockSpec((NC, d_block, C), lambda d: (0, d, 0)),
            pl.BlockSpec((d_block, M_PAD), lambda d: (d, 0)),
        ],
        out_specs=[
            pl.BlockSpec((NC, d_block, C), lambda d: (0, d, 0)),
            pl.BlockSpec((d_block, M_PAD), lambda d: (d, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(cols.shape, I32),
            jax.ShapeDtypeStruct(meta.shape, I32),
        ],
        input_output_aliases={3: 0, 4: 1},
        interpret=interpret,
        # the doc tile ([NC, d_block, C] i32) plus the conflict-scan's
        # [d_block, C] temporaries are the VMEM tenants. With NC=26 (move
        # columns + the pass-through origin_slot plane) a d_block=128/
        # C=2048 tile is ~27MB + scan temporaries; the pre-move measured
        # sweet spot (d_block=128 at ~56MB total under NC=17) now lands
        # near the 64MB limit, so re-measure on hardware — d_block<=96 is
        # the safe default at C=2048 if allocation fails. The ISSUE-12
        # wide-tier unroll does NOT multiply the resident scan
        # temporaries (the before/conflicting sets and the per-step
        # gathers are reused across the unrolled sub-steps — program
        # text grows ~unroll×, live VMEM does not), but a raised
        # YTPU_SCAN_WIDE_UNROLL inflates compile time and instruction
        # footprint: re-bisect d_block if allocation regresses.
        compiler_params=None
        if interpret
        else pltpu.CompilerParams(
            # v5e VMEM is 128MB; the default guard stays conservative.
            # Big-capacity tiles (the fused full-B4 at C=65536 needs a
            # ~54MB state tile + scan temporaries) raise it via the
            # YTPU_FUSED_VMEM_MB env var, which the public entry points
            # re-read PER CALL and thread here as a STATIC argument — a
            # changed value forces a retrace instead of being silently
            # ignored for already-compiled (shape, d_block) keys
            # (ADVICE r5 #2: the old trace-time env read misled
            # VMEM-limit bisection).
            vmem_limit_bytes=vmem_limit_mb * 1024 * 1024
        ),
    )(rows, dels, rank, cols, meta)
    return out


# the standalone jitted entry (donated state); the async chunk program
# composes `_run_body` directly inside its own jit instead, so donation
# applies to the OUTER program's state operands. scan_plan rides as a
# STATIC (position 8) so a changed tier plan recompiles.
_run = partial(
    jax.jit, static_argnums=(3, 4, 5, 6, 7, 8), donate_argnums=(0, 1)
)(_run_body)


def apply_update_stream_fused(
    state: DocStateBatch,
    stream: UpdateBatch,
    client_rank: jax.Array,
    d_block: int = 32,
    interpret: bool = False,
    guard: bool = True,
    refresh_cache: bool = False,
    _debug_phases: int = 3,
    _debug_row_phase: int = 4,
) -> DocStateBatch:
    """Fused-replay drop-in for `apply_update_stream`: sequence rows, map
    rows (per-key LWW chains), nested-branch parents AND move ranges all
    integrate in-VMEM — move claims run as a fused end-of-step recompute
    pass (the claim walk / cycle check / ownership argmax of
    `batch_doc._recompute_moves`, parity: moving.rs:149-227).

    `guard` is kept for call-site compatibility; it no longer excludes
    anything.

    origin_slot cache (ADVICE r5 #1): the kernel passes the cache plane
    through without maintaining it, so a wholesale rebuild
    (`recompute_origin_slot`) is needed before anything READS it — and
    that rebuild is O(D·B²) compares with a multi-GB vmapped
    intermediate per doc at flagship capacities (C=65536, ~51k blocks:
    billions of compares). It therefore no longer runs eagerly on every
    fused apply. The default `refresh_cache=False` marks the returned
    state's cache STALE (`batch_doc.mark_origin_slot_stale`); the
    XLA-lane entry points (`apply_update_batch`/`apply_update_stream`)
    and checkpoint save — the cache's only readers — refresh lazily via
    `batch_doc.ensure_origin_slot`, so chained fused applies pay the
    rebuild at most once, at the boundary where the cache is actually
    consumed. Pass `refresh_cache=True` to opt back into the eager
    rebuild (callers that hand the state to out-of-tree cache readers).

    `_debug_phases` / `_debug_row_phase` truncate the kernel for
    hardware bisection only (see `_kernel`); never pass them in production
    — partial kernels corrupt state by design."""
    del guard
    # the fused program (especially interpret-mode on CPU) is the largest
    # in the process: evict under the resident-program budget BEFORE a
    # possible compile, not just on the periodic tick (the r5 no-crutch
    # suite segfaulted compiling exactly this program at ~73%)
    from ytpu.utils import progbudget
    from ytpu.utils.phases import (
        NULL_SPAN,
        phases as _phases,
        program_memory as _program_memory,
    )

    progbudget.enforce()
    cols, meta = pack_state(state)
    D = cols.shape[1]
    if D % d_block != 0:
        raise ValueError(f"n_docs {D} must be a multiple of d_block {d_block}")
    rows, dels = pack_stream(stream)
    vmem_mb = fused_vmem_mb(d_block, cols.shape[2])
    # two-tier scan plan: re-read per call and threaded as a static, so
    # a changed knob retraces instead of silently reusing the old unroll
    scan_plan = scan_tier_plan()
    if _phases.enabled:
        _phases.transfer(
            "integrate.fused",
            rows.size * rows.dtype.itemsize + dels.size * dels.dtype.itemsize,
            "h2d",
        )
        span = _phases.span(
            "integrate.fused",
            (cols.shape, rows.shape, dels.shape, d_block, interpret,
             _debug_phases, _debug_row_phase, vmem_mb, scan_plan),
            axes=("state", "rows", "dels", "d_block", "interpret",
                  "debug_phases", "debug_row_phase", "vmem_mb",
                  "scan_plan"),
            memory=_program_memory(
                _run, cols, meta, (rows, dels, client_rank), d_block,
                interpret, _debug_phases, _debug_row_phase, vmem_mb,
                scan_plan,
            ),
        )
    else:
        span = NULL_SPAN
    with span:
        cols, meta = _run(
            cols, meta, (rows, dels, client_rank), d_block, interpret,
            _debug_phases, _debug_row_phase, vmem_mb, scan_plan,
        )
    out = unpack_state(cols, meta, state)
    if not refresh_cache:
        # lazy dirty-flag: the XLA apply wrappers / checkpoint save run
        # recompute_origin_slot on first read of a stale cache
        from ytpu.models.batch_doc import mark_origin_slot_stale

        mark_origin_slot_stale(out)
        return out
    # eager opt-in: rebuild so even out-of-tree readers see a valid cache
    from ytpu.models.batch_doc import recompute_origin_slot

    return recompute_origin_slot(out)


# --- chunked replay driver (ISSUE-4 tentpole) --------------------------------
# The fused kernel is byte-exact on silicon but a full-B4 tile needs more
# resident blocks than any legal VMEM shape holds (peak 51,555 at C=65536,
# which violates Pallas block limits; C=32768 overflows). The driver below
# gives the fused lane the XLA lane's survival trick — mid-replay
# compaction — without ever unpacking to host: chunks of the update stream
# run through `_run`, and between chunks `compact_packed` squashes the
# packed [NC, D, C] state in place whenever the shared CompactionPolicy's
# high-watermark trips or the next chunk's worst-case growth would
# overflow the tile.


_XLA_CHUNK_STEP = None


def xla_chunk_step(cols, meta, stream, rank, scan_plan=None):
    """One chunk of stream steps through the un-fused XLA integrate path,
    on the packed kernel state (unpack → apply_update_stream → repack, all
    inside one jit so XLA fuses the repacks away). The jitted step is a
    module singleton shared by every chunked driver instance — a per-call
    closure would retrace every chunk, and two singletons (this one and
    replay.py's old private copy) would hold duplicate unevictable
    executables. `scan_plan` (the ISSUE-12 two-tier static; None = the
    env-resolved `scan_tier_plan()`) rides as a static argnum so a
    changed tier plan recompiles the step."""
    global _XLA_CHUNK_STEP
    if scan_plan is None:
        scan_plan = scan_tier_plan()
    if _XLA_CHUNK_STEP is None:
        # the RAW body, not the instrumented wrapper: tracing through the
        # wrapper recorded a phantom `integrate.xla_stream` compile_s
        # entry in bench JSON (PR-4 review) — the only real dispatch here
        # is this chunk step, already attributed to `replay.chunk_xla`
        from ytpu.models.batch_doc import apply_update_stream_raw

        def step(cols, meta, stream, rank, scan_plan):
            # pack_state zeroes the meta padding, so the carried
            # scan record (ISSUE-11/12) is read out first and folded
            # back in with this chunk's contribution
            carried = meta[:, M_HIST0:M_SCAN_END]
            state = unpack_state(cols, meta, None)
            state, dhist = apply_update_stream_raw(
                state, stream, rank, scan_plan
            )
            cols, meta = pack_state(state)
            meta = _fold_scan_meta(meta, carried, dhist)
            return cols, meta

        # donate like the fused _run: the packed state updates in place
        # instead of holding two full copies at grown capacity
        _XLA_CHUNK_STEP = jax.jit(
            step, donate_argnums=(0, 1), static_argnums=(4,)
        )
    return _XLA_CHUNK_STEP(cols, meta, stream, rank, scan_plan)


def _fold_scan_meta(meta, carried, dhist):
    """Fold an XLA-lane chunk's scan record (``dhist``
    ``[D, SCAN_REC_WORDS]``) plus the pre-chunk carried meta columns
    back into a freshly packed meta (whose padding pack_state zeroed):
    every word adds except the max, which maxes (`merge_scan_records`,
    the one shared combine rule)."""
    return meta.at[:, M_HIST0:M_SCAN_END].set(
        merge_scan_records(carried, dhist)
    )


def _packed_commit_fold(cols, meta):
    """``[D]`` uint32 per-doc state commitments from the packed columns
    (ISSUE-13): `commit_fold_blocks` over every live block row — the
    same validity predicate `encode_diff_batch` uses.  Recomputed from
    the CURRENT state at each readout (a ~D·C vectorized reduction, free
    next to the integrate it rides), so compaction/GC/growth can never
    leave a stale accumulator behind."""
    B = cols.shape[-1]
    slots = jnp.arange(B, dtype=I32)
    valid = (slots[None, :] < meta[:, M_NBLOCKS][:, None]) & (cols[CL] >= 0)
    return commit_fold_blocks(cols[CL], cols[CK], cols[LN], valid)


@jax.jit
def packed_commitments(cols, meta):
    """Public on-demand pull of the ``[D]`` per-doc commitment words
    (i32 bit pattern of the uint32 fold).  NOT a hot-path call — the
    batch-aggregate word already rides the lazy readout; this exists
    for per-doc verification (tests, a quarantine postmortem)."""
    return jax.lax.bitcast_convert_type(
        _packed_commit_fold(cols, meta), I32
    )


@jax.jit
def packed_capacity_ledger(cols, meta):
    """Per-doc ``([D] occupied, [D] dead)`` i32 rows from the packed
    columns (ISSUE-18). NOT a hot-path call — the batch aggregates
    already ride the lazy readout; this is the per-tenant pull serving
    scrapes (`DeviceSyncServer` `/snapshot`) and tests materialize on
    demand. Free rows per doc are ``capacity - occupied - dead`` under
    the ledger convention (occupied counts LIVE rows, dead the
    tombstoned ones), so the three per-tenant gauges always sum to the
    column capacity."""
    occ = meta[:, M_NBLOCKS].astype(I32)
    dead = _packed_dead_rows(cols, meta)
    return occ - dead, dead


def _packed_dead_rows(cols, meta):
    """``[D]`` i32 per-doc dead-row counts: rows inside the occupied
    prefix (`n_blocks`) that are live allocations (`client >= 0`) but
    tombstoned (`DL > 0`) — the GC-able fragmentation `compact_packed`
    reclaims. Same validity predicate as `_packed_commit_fold`."""
    B = cols.shape[-1]
    slots = jnp.arange(B, dtype=I32)
    valid = (slots[None, :] < meta[:, M_NBLOCKS][:, None]) & (cols[CL] >= 0)
    return jnp.sum((valid & (cols[DL] > 0)).astype(I32), axis=1)


def _readout_words(cols, meta, err):
    """``[N_READOUT]`` i32: (max n_blocks, max sticky integrate error,
    sticky decode flags, scan-width bucket totals summed over docs, max
    scan width, the ISSUE-12 tier/trip totals summed over docs, the
    ISSUE-13 commitment word — wrap-sum over docs of the per-doc lattice
    digest — then the ISSUE-18 capacity-ledger words: Σ occupied rows,
    Σ dead rows, max per-doc dead) — everything the host learns per
    drain, one future."""
    hist = jnp.sum(meta[:, M_HIST0:M_SCANW_MAX], axis=0)
    tiers = jnp.sum(meta[:, M_TIER_CHEAP:M_SCAN_END], axis=0)
    commit = jax.lax.bitcast_convert_type(
        jnp.sum(_packed_commit_fold(cols, meta), dtype=jnp.uint32), I32
    )
    dead = _packed_dead_rows(cols, meta)
    ledger = jnp.stack(
        [
            jnp.sum(meta[:, M_NBLOCKS]),
            jnp.sum(dead),
            jnp.max(dead),
        ]
    )
    return jnp.concatenate(
        [
            jnp.stack(
                [jnp.max(meta[:, M_NBLOCKS]), jnp.max(meta[:, M_ERROR]), err]
            ),
            hist,
            jnp.max(meta[:, M_SCANW_MAX])[None],
            tiers,
            commit[None],
            ledger,
        ]
    )


@jax.jit
def _chunk_readout(cols, meta, err):
    """[N_READOUT] i32 (max n_blocks, max sticky integrate error, sticky
    decode flags, + the scan-width histogram words) — the per-chunk
    occupancy/error readout. Dispatched after every chunk but NOT
    materialized: the host keeps the device future and only blocks on it
    when its own optimistic occupancy bound trips the watermark, so
    steady-state chunks never pay a sync (the round-5 FusedReplay synced
    every chunk). Decode FLAG_ERRORS ride the same word (`err`,
    OR-reduced on device by `replay_chunk_program`), so the async lane's
    per-chunk `np.asarray(flags)` block is gone too. The ISSUE-11
    scan-width words (bucket totals + max) ride the SAME future — zero
    additional materializations."""
    return _readout_words(cols, meta, err)


@jax.jit
def _fold_subbatch_readouts(stacked):
    """Fold ``[n_sub, N_READOUT]`` per-sub-batch readouts into the ONE
    ``[N_READOUT]`` surface the drain already parses (ISSUE-20): each
    word folds with the same reduction `_readout_words` used to produce
    it over docs — max for the occupancy/error/scan-max words, sum for
    the histogram/tier/ledger totals, bitwise-OR for the sticky decode
    flags (threaded slice→slice, so the fold is also just the last
    word), uint32 wrap-sum for the ISSUE-13 commitment, and max for the
    per-doc dead peak. The result is byte-identical to the monolithic
    readout, so `_drain_readouts` (and the zero-sync invariant) never
    learns sub-batching happened."""
    mx = jnp.max(stacked, axis=0)
    sm = jnp.sum(stacked, axis=0)
    err = jax.lax.associative_scan(jnp.bitwise_or, stacked[:, 2])[-1]
    commit = jax.lax.bitcast_convert_type(
        jnp.sum(
            jax.lax.bitcast_convert_type(
                stacked[:, 3 + SCAN_REC_WORDS], jnp.uint32
            )
        ),
        I32,
    )
    base = 4 + SCAN_REC_WORDS  # first capacity-ledger word
    return jnp.concatenate(
        [
            jnp.stack([mx[0], mx[1], err]),
            sm[3 : 3 + SCAN_REC_MAX],  # scan-width bucket totals
            mx[3 + SCAN_REC_MAX][None],  # observed max scan width
            sm[3 + SCAN_REC_CHEAP : 3 + SCAN_REC_WORDS],  # tier/trip sums
            commit[None],
            jnp.stack([sm[base], sm[base + 1], mx[base + 2]]),
        ]
    )


def _chunk_core(
    cols,
    meta,
    err,
    buf,
    lens,
    refs,
    rank,
    *,
    lane: str,
    max_rows: int,
    max_dels: int,
    n_steps: int,
    max_sections: int,
    d_block: int,
    interpret: bool,
    vmem_mb: int,
    scan_plan: Tuple[int, int],
):
    """Traceable body shared by `replay_chunk_program` (host-packed
    ``[S, L]`` lanes) and `replay_chunk_program_raw` (device-gathered
    lanes): device decode (`decode_updates_v1` body) → global unit-ref
    rebase (`refs`, -1 = keep the decoded in-chunk ref) → integrate
    (fused Pallas tile or the packed-XLA scan, both under the ISSUE-12
    two-tier `scan_plan` static) → `[N_READOUT]` readout."""
    from ytpu.ops.decode_kernel import FLAG_ERRORS, _decode_updates_v1_impl

    stream, flags = _decode_updates_v1_impl(
        buf,
        lens,
        max_rows=max_rows,
        max_dels=max_dels,
        n_steps=n_steps,
        max_sections=max_sections,
    )
    stream = stream._replace(
        content_ref=jnp.where(refs >= 0, refs, stream.content_ref)
    )
    err = err | jax.lax.reduce(
        flags & FLAG_ERRORS, np.int32(0), jax.lax.bitwise_or, (0,)
    )
    if lane == "fused":
        rows, dels = pack_stream(stream)
        cols, meta = _run_body(
            cols, meta, (rows, dels, rank), d_block, interpret, 3, 4,
            vmem_mb, scan_plan,
        )
    else:
        from ytpu.models.batch_doc import apply_update_stream_raw

        carried = meta[:, M_HIST0:M_SCAN_END]
        state = unpack_state(cols, meta, None)
        state, dhist = apply_update_stream_raw(state, stream, rank, scan_plan)
        cols, meta = pack_state(state)
        meta = _fold_scan_meta(meta, carried, dhist)
    readout = _readout_words(cols, meta, err)
    return cols, meta, err, readout


@partial(
    jax.jit,
    static_argnames=(
        "lane",
        "max_rows",
        "max_dels",
        "n_steps",
        "max_sections",
        "d_block",
        "interpret",
        "vmem_mb",
        "scan_plan",
    ),
    donate_argnums=(0, 1, 2),
)
def replay_chunk_program(
    cols,
    meta,
    err,
    buf,
    lens,
    refs,
    rank,
    *,
    lane: str,
    max_rows: int,
    max_dels: int,
    n_steps: int,
    max_sections: int,
    d_block: int,
    interpret: bool,
    vmem_mb: int,
    scan_plan: Tuple[int, int],
):
    """One replay chunk straight from padded wire bytes, as ONE compiled
    dispatch: device decode (`decode_updates_v1` body) → global unit-ref
    rebase (`refs`, -1 = keep the decoded in-chunk ref) → integrate
    (fused Pallas tile or the packed-XLA scan) → `[3]` readout.

    Fusing the stages kills the two host hops the serial loop paid per
    chunk — the decoded-stream round trip between the decode and
    integrate programs, and the blocking `np.asarray(flags)` error check
    (replay.py:419/420 pre-PR5): per-lane decode FLAG_ERRORS are
    OR-reduced into the sticky `err` scalar on device, and flagged lanes
    already integrate as no-ops (the decoder zeroes their valid masks),
    so the host materializes nothing in steady state. `donate_argnums`
    on cols/meta lets XLA update the ~NC·D·C state in place instead of
    copying it every chunk.

    This is the HOST-PACKED lane: staging built the `[S, L]` matrix with
    `pack_updates_into` (per-update Python packing). The raw ingest lane
    (`replay_chunk_program_raw`) moves that packing on device too; this
    program stays as the fallback/checkpoint rung of the PR-6 ladder."""
    return _chunk_core(
        cols,
        meta,
        err,
        buf,
        lens,
        refs,
        rank,
        lane=lane,
        max_rows=max_rows,
        max_dels=max_dels,
        n_steps=n_steps,
        max_sections=max_sections,
        d_block=d_block,
        interpret=interpret,
        vmem_mb=vmem_mb,
        scan_plan=scan_plan,
    )


@partial(
    jax.jit,
    static_argnames=(
        "width",
        "lane",
        "max_rows",
        "max_dels",
        "n_steps",
        "max_sections",
        "d_block",
        "interpret",
        "vmem_mb",
        "scan_plan",
    ),
    donate_argnums=(0, 1, 2),
)
def replay_chunk_program_raw(
    cols,
    meta,
    err,
    raw,
    offs,
    lens,
    refs,
    rank,
    *,
    width: int,
    lane: str,
    max_rows: int,
    max_dels: int,
    n_steps: int,
    max_sections: int,
    d_block: int,
    interpret: bool,
    vmem_mb: int,
    scan_plan: Tuple[int, int],
):
    """One replay chunk straight from RAW CONCATENATED wire bytes plus a
    tiny per-update offsets table (ISSUE-7 tentpole): the device gathers
    each update's byte lane out of the flat arena
    (`decode_kernel.gather_raw_lanes` — the Stream-VByte control/data
    split: offsets are the control stream, the byte arena the data
    stream), then runs the same lane-parallel varint decode → unit-ref
    rebase → integrate → readout as `replay_chunk_program`.

    What this buys over the host-packed program: staging collapses to a
    memcpy (one slice copy + two vectorized table writes, no per-update
    Python), and the h2d transfer shrinks from ``S·L`` padded bytes to
    the actual wire bytes + ``2·S`` table words — so pipeline depth > 2
    is essentially free and `replay.overlap_ratio` → 1.0. The gather's
    zero mask makes the on-device lane matrix byte-identical to a
    host-packed one, so raw-vs-packed byte parity is structural."""
    from ytpu.ops.decode_kernel import gather_raw_lanes

    buf = gather_raw_lanes(raw, offs, lens, width)
    return _chunk_core(
        cols,
        meta,
        err,
        buf,
        lens,
        refs,
        rank,
        lane=lane,
        max_rows=max_rows,
        max_dels=max_dels,
        n_steps=n_steps,
        max_sections=max_sections,
        d_block=d_block,
        interpret=interpret,
        vmem_mb=vmem_mb,
        scan_plan=scan_plan,
    )


@lru_cache(maxsize=1)
def _transfer_aliases_host() -> bool:
    """True when `jnp.asarray` of a numpy array shares its memory instead
    of copying (the CPU PJRT client's zero-copy path). The async replay's
    staging-slot reuse gate assumes the h2d transfer made the input
    private; on an aliasing backend the bytes must be copied host-side
    first or a re-packed slot races the chunk program still reading it."""
    # the probe buffer must be 64-byte aligned: the zero-copy path only
    # engages on aligned host memory, so a small unaligned allocation
    # here would report "copies" while the page-aligned staging buffers
    # still alias — carve an aligned window out of a larger block
    raw = np.zeros(128, dtype=np.uint8)
    off = (-raw.ctypes.data) % 64
    probe = raw[off : off + 64]
    dev = jnp.asarray(probe)
    dev.block_until_ready()
    probe[0] = 1
    return bool(np.asarray(dev)[0] == 1)


@dataclass
class ReplayChunkStats:
    """Counters of one chunked replay (shared by both kernel lanes)."""

    chunks: int = 0
    compactions: int = 0
    growths: int = 0
    syncs: int = 0  # occupancy readouts actually materialized
    capacity: int = 0
    peak_blocks: int = 0  # max occupancy OBSERVED at readouts (lazy: the
    # true peak between syncs may be higher but is bounded by the margin)
    final_blocks: int = 0
    # resilience counters (ISSUE-6): lane demotions this driver performed,
    # in-place chunk retries that succeeded on a demoted lane, and decode
    # errors quarantined (skip-and-record) instead of aborting the replay
    demotions: int = 0
    recoveries: int = 0
    quarantined: int = 0
    # conflict-tail attribution (ISSUE-11): the scan-width record as of
    # the freshest materialized readout — pow2 bucket counts, observed
    # max, and the bucket-quantile p50/p99 (0s until the first drain)
    scan_hist: tuple = ()
    scan_max: int = 0
    scan_p50: int = 0
    scan_p99: int = 0
    # two-tier scan occupancy (ISSUE-12), same freshest-readout origin:
    # scans resolved entirely in the cheap tier vs escalated to the
    # vectorized wide tier, plus the exact dispatch-trip accounting —
    # `scan_trips_serial` is what the pre-ISSUE-12 one-candidate-per-trip
    # loop would have paid (Σ width), `scan_trips_two_tier` what the
    # tiered dispatch actually paid (Σ min(width, cheap) + wide blocks)
    scan_tier_cheap: int = 0
    scan_tier_wide: int = 0
    scan_trips_serial: int = 0
    scan_trips_two_tier: int = 0
    # incremental state commitment (ISSUE-13): the batch-aggregate
    # lattice-digest word as of the freshest materialized readout
    # (uint32 value; per-doc words via `packed_commitments` on demand)
    commit_word: int = 0
    # capacity observatory (ISSUE-18): occupancy/fragmentation ledger as
    # of the freshest materialized readout — Σ occupied rows over docs
    # (the n_blocks prefix, live + dead), Σ dead (tombstoned, GC-able)
    # rows inside it, and the worst per-doc dead count; plus compaction
    # efficacy — total rows reclaimed by `compact_packed` calls and the
    # chunk gap between the last two compactions (time-to-watermark).
    # All ride the SAME lazy readout future — zero new device syncs.
    occupied_rows: int = 0
    dead_rows: int = 0
    dead_max: int = 0
    reclaimed_rows: int = 0
    compact_gap_chunks: int = 0
    # doc-axis sub-batching (ISSUE-20): the active pow2 sub-batch width
    # (0 = monolithic dispatch) and how many times the driver narrowed
    # it — forecaster-driven or on a typed GrowOomError
    subbatch_width: int = 0
    subbatch_narrowed: int = 0


# --- lane-health ladder + typed replay faults (ISSUE-6 tentpole) -------------
# A hostile shape family (e.g. the 1024-doc integrate programs that kill
# the TPU worker, ROADMAP item 1) must not take the process down on every
# retry: the first dispatch/compile failure demotes the family one rung —
# fused Pallas → packed-XLA chunk step → (caller-level) serial host
# oracle — and the demotion is STICKY per shape family, so later drivers
# for the same family skip the known-bad lane entirely.

from ytpu.utils import metrics as _metrics
from ytpu.utils.faults import FaultError, faults

LANE_LADDER = ("fused", "xla", "host")

_DEMOTIONS = _metrics.counter("lane.demotions")
_DEMOTIONS_BY = _metrics.counter(
    "lane.demotions_by_lane", labelnames=("from_lane", "to_lane")
)
_RECOVERIES = _metrics.counter("replay.recoveries")
_QUARANTINED = _metrics.counter("replay.quarantined")
#: `grow.oom` denials (ISSUE-18): every typed GrowOomError raised at the
#: fault site — the chaos-side truth the `/capacity` forecaster is
#: scored against (forecast flagged BEFORE this counter moved?)
_GROW_DENIED = _metrics.counter("memory.grow_denied")
#: sub-batch width demotions (ISSUE-20): every halving of the doc-axis
#: sub-batch width — forecaster-driven (BEFORE a grow attempt) or in
#: response to a typed GrowOomError (instead of killing the chunk).
#: bench_compare regresses this on RISE: a healthy budget never narrows.
_SUBBATCH_NARROWED = _metrics.counter("capacity.subbatch_narrowed")


def packed_state_bytes(n_docs: int, capacity: int) -> int:
    """Analytic resident bytes of ONE packed state at a given capacity:
    the ``[NC, D, C]`` i32 column planes plus the ``[D, M_PAD]`` meta
    tile. The capacity observatory's model term — `grow_packed` doubles
    `capacity`, so the next grow attempt costs exactly this much at
    ``capacity * 2`` (plus the transient old+new overlap)."""
    return 4 * (NC * n_docs * capacity + n_docs * M_PAD)

# shape family -> lowest healthy rung (absent = full health)
_lane_floor: dict = {}
_lane_floor_lock = threading.Lock()


def lane_family(n_docs: int, d_block: int) -> Tuple[int, int]:
    """The sticky-health key: capacity grows mid-replay, so only the doc
    axis and kernel tiling identify a compiled shape family."""
    return (int(n_docs), int(d_block))


def effective_lane(family, requested: str) -> str:
    """`requested` demoted to the family's sticky floor, if any."""
    floor = _lane_floor.get(family)
    if floor is None:
        return requested
    if LANE_LADDER.index(floor) > LANE_LADDER.index(requested):
        return floor
    return requested


def demote_lane(family, from_lane: str) -> Optional[str]:
    """Record a sticky demotion one rung below `from_lane`; returns the
    new rung (``None`` when already at the ladder's end)."""
    idx = LANE_LADDER.index(from_lane)
    if idx + 1 >= len(LANE_LADDER):
        return None
    nxt = LANE_LADDER[idx + 1]
    with _lane_floor_lock:
        cur = _lane_floor.get(family)
        if cur is None or LANE_LADDER.index(nxt) > LANE_LADDER.index(cur):
            _lane_floor[family] = nxt
    _DEMOTIONS.inc()
    _DEMOTIONS_BY.labels(from_lane, nxt).inc()
    return nxt


def reset_lane_health() -> None:
    """Test/ops hook: forget every sticky demotion."""
    with _lane_floor_lock:
        _lane_floor.clear()


def lane_health() -> dict:
    """JSON-safe view of the sticky lane-demotion ladder: shape-family
    key (``"{n_docs}x{d_block}"``) → lowest healthy rung. Empty = full
    health. The telemetry plane's `/healthz` endpoint serves this."""
    with _lane_floor_lock:
        return {f"{fam[0]}x{fam[1]}": floor for fam, floor in _lane_floor.items()}


#: wall-clock of the most recent successful chunk dispatch, for the
#: telemetry `/healthz` last-dispatch age — a wedged device shows up as a
#: growing age while the HTTP plane stays serveable (its own thread)
_LAST_DISPATCH = _metrics.gauge("integrate.last_dispatch_unix")


class ReplayFault(RuntimeError):
    """A mid-replay device fault the driver could NOT absorb in place
    (state buffers lost to donation, simulated worker death, or the
    ladder exhausted).  `recoverable` callers (FusedReplay) restore the
    last chunk-boundary checkpoint — or the initial state — and re-run;
    the sticky lane floor already records any demotion."""

    def __init__(self, msg: str, *, chunk: int, lane: str,
                 cause: Optional[BaseException] = None):
        super().__init__(msg)
        self.chunk = chunk
        self.lane = lane
        self.cause = cause


class GrowOomError(FaultError):
    """The ``grow.oom`` fault site, typed (ISSUE-18): a denied
    `grow_packed` now reports WHAT it attempted against WHAT was
    available — attempted resident bytes at the doubled capacity vs
    the device budget — so chaos runs can score the `/capacity`
    forecaster against reality. Still a `FaultError` subclass: the
    lane ladder's `is_device_fault` and FusedReplay's checkpoint-resume
    recovery treat it exactly like the bare fault it replaces."""

    def __init__(
        self,
        spec,
        *,
        capacity: int,
        new_capacity: int,
        n_docs: int,
        attempted_bytes: int,
        available_bytes: int,
    ):
        RuntimeError.__init__(
            self,
            f"injected fault at site 'grow.oom': grow {capacity} -> "
            f"{new_capacity} slots for {n_docs} docs needs "
            f"~{attempted_bytes} resident bytes, budget "
            f"{available_bytes}",
        )
        self.site = "grow.oom"
        self.spec = spec
        self.capacity = int(capacity)
        self.new_capacity = int(new_capacity)
        self.n_docs = int(n_docs)
        self.attempted_bytes = int(attempted_bytes)
        self.available_bytes = int(available_bytes)


def is_device_fault(e: BaseException) -> bool:
    """True for failures that indict the DEVICE LANE (injected faults,
    XLA runtime/compile errors, Mosaic failures) — never for host-side
    programming errors, and never for the interpret-mode
    NotImplementedError that `tests/_fused_interpret` must see raw."""
    if isinstance(e, FaultError):
        return True
    if isinstance(e, (NotImplementedError, MemoryError, KeyboardInterrupt)):
        return False
    mod = type(e).__module__ or ""
    return (
        "jaxlib" in mod
        or "mosaic" in mod.lower()
        or type(e).__name__ == "XlaRuntimeError"
    )


def _buffers_alive(*arrays) -> bool:
    """True when every jax array still owns its buffer (donation marks
    consumed inputs deleted — a failed dispatch that already consumed the
    state cannot be retried in place)."""
    for a in arrays:
        try:
            if a.is_deleted():
                return False
        except AttributeError:
            pass
    return True


class PackedReplayDriver:
    """Chunked replay over a packed [NC, D, C] state with between-chunk
    device compaction under one shared `CompactionPolicy`.

    The occupancy protocol (no per-chunk sync): the host maintains an
    optimistic UPPER BOUND on the max per-doc block count — each chunk
    adds its worst-case growth (3 slots/row + 2/delete range, the same
    accounting as `ReplayPlan.adds` and `stream_worst_case_adds`) — and each
    chunk dispatches a tiny `[2]` (occupancy, sticky-error) readout that
    stays an un-materialized device future. Only when the BOUND says the
    next chunk might not fit (or the high-watermark tripped) does the
    host block on the freshest readout; if the ACTUAL occupancy still
    trips the policy, `compact_packed` squashes in place and, when even
    that can't make room, `grow_packed` widens the tile (capacity change
    = one retrace, same as the round-5 XLA lane). Sticky error flags are
    checked at every materialized readout and once more at `finish()` —
    the device flags are sticky by design, so deferral never loses one.
    """

    def __init__(
        self,
        cols,
        meta,
        client_rank,
        *,
        d_block: int = 8,
        interpret: bool = False,
        lane: str = "fused",
        policy=None,
        unit_refs: bool = False,
        gc_ranges: bool = False,
        max_capacity: Optional[int] = None,
        sync_every_chunk: bool = False,
        initial_occupancy: int = 0,
        quarantine: bool = False,
        shard_docs: bool = False,
    ):
        from ytpu.models.batch_doc import DEFAULT_COMPACTION_POLICY

        if lane not in ("fused", "xla"):
            raise ValueError(f"lane must be 'fused' or 'xla', got {lane!r}")
        D = cols.shape[1]
        if lane == "fused" and D % d_block != 0:
            raise ValueError(
                f"n_docs {D} must be a multiple of d_block {d_block}"
            )
        # sticky lane health: a family demoted by an earlier driver (or an
        # earlier chunk of this replay) never re-tries the known-bad lane;
        # the "host" rung is the CALLER's (serial oracle) — the driver
        # itself bottoms out at the packed-XLA step
        self._family = lane_family(D, d_block)
        eff = effective_lane(self._family, lane)
        self.cols = cols
        self.meta = meta
        self.rank = client_rank
        lane = "xla" if eff == "host" else eff
        self.d_block = d_block
        self.interpret = interpret
        self.lane = lane
        self.policy = policy or DEFAULT_COMPACTION_POLICY
        self.unit_refs = unit_refs
        self.gc_ranges = gc_ranges
        self.max_capacity = max_capacity or cols.shape[2]
        self.sync_every_chunk = sync_every_chunk
        self.stats = ReplayChunkStats(capacity=cols.shape[2])
        self._hi_bound = int(initial_occupancy)
        self._pending = []  # un-materialized [3] readout futures
        # sticky decode-error scalar, kept ON DEVICE: replay_chunk_program
        # ORs each chunk's FLAG_ERRORS into it so the host never blocks on
        # per-chunk flags; materialized only at drains/finish
        self._err = jnp.zeros((), I32)
        # optional hook raised INSTEAD of the generic decode error: the
        # async replay loop re-identifies the offending chunk/update
        # indices host-side for the same message the sync lane raises
        self.on_decode_error = None
        # poison-update quarantine (opt-in): a tripped sticky decode
        # error is RECORDED and cleared instead of aborting the replay —
        # the decoder already integrates flagged lanes as no-ops, so the
        # stream's healthy updates are untouched. `on_quarantine(flags)`
        # (set by FusedReplay) re-identifies the offending update
        # indices host-side and returns the newly recorded ones.
        self.quarantine = quarantine
        self.on_quarantine = None
        # capacity observatory (ISSUE-18): optional HeadroomForecaster
        # fed at every materialized ledger readout (set by FusedReplay /
        # tests; None keeps the hot path untouched), plus the chunk
        # index of the latest compaction for the time-to-watermark gap
        self.forecaster = None
        self._last_compact_chunk = -1
        # doc-axis sub-batching (ISSUE-20): when enabled, every
        # one-dispatch chunk program (and compact/grow) runs per
        # pow2-width doc slice sized by `plan_subbatches` against the
        # forecaster's budget — the packed state never allocates (or
        # dispatches) as one monolith. `_sub_width` is the sticky active
        # width: planned lazily per capacity, only ever narrowed
        # (forecast or GrowOomError), never re-widened mid-replay.
        self.shard_docs = bool(shard_docs)
        self._sub_width: Optional[int] = None
        self._sub_cap = -1
        self.subbatch_journal: list = []

    @property
    def capacity(self) -> int:
        return self.cols.shape[2]

    # ------------------------------------------------------- sub-batching

    def _active_sub_width(self) -> Optional[int]:
        """The pow2 doc width each dispatch slices at, or None for the
        monolithic path (shard_docs off, or the whole doc axis fits one
        dispatch under the budget). Planned lazily per capacity via
        `plan_subbatches`; a sticky narrowing survives replanning (the
        min below) so a width demoted by `grow.oom` never re-widens."""
        if not self.shard_docs:
            return None
        D = self.cols.shape[1]
        if self._sub_cap != self.capacity:
            from ytpu.models.replay import plan_subbatches

            plan = plan_subbatches(
                D,
                self.capacity,
                d_block=self.d_block if self.lane == "fused" else 1,
                forecaster=self.forecaster,
            )
            width = plan.width
            if self._sub_width is not None:
                width = min(width, self._sub_width)
            self._sub_width = width
            self._sub_cap = self.capacity
            self.stats.subbatch_width = width if width < D else 0
        return self._sub_width if (self._sub_width or D) < D else None

    def _narrow_subbatch(self, reason: str) -> bool:
        """Demote the sub-batch width one pow2 rung (journaled + counted
        `capacity.subbatch_narrowed`); False at the floor (`d_block` on
        the fused lane, 1 otherwise) — the caller then surfaces the
        original failure instead of looping."""
        from ytpu.utils.phases import phases as _phases

        D = self.cols.shape[1]
        cur = self._sub_width if self._sub_width is not None else D
        floor = self.d_block if self.lane == "fused" else 1
        nxt = cur // 2
        if nxt < max(floor, 1):
            return False
        self._sub_width = nxt
        self._sub_cap = self.capacity
        self.stats.subbatch_width = nxt
        self.stats.subbatch_narrowed += 1
        _SUBBATCH_NARROWED.inc()
        self.subbatch_journal.append(
            {
                "chunk": self.stats.chunks,
                "capacity": self.capacity,
                "from_width": cur,
                "to_width": nxt,
                "reason": reason,
            }
        )
        if _phases.enabled:
            _phases.set_value("subbatch.width", nxt)
            _phases.add_value("capacity.subbatch_narrowed", 1)
        return True

    def _forecast_narrow(self, new_cap: int) -> None:
        """Satellite fix (ISSUE-20): consult the HeadroomForecaster
        BEFORE attempting `grow_packed` — while the MODELED grow
        transient at the active width busts the budget, narrow the
        width instead of letting the device (or the chaos site) deny
        the allocation."""
        if self.forecaster is None:
            return
        D = self.cols.shape[1]
        budget = self.forecaster.budget_bytes
        while True:
            w = self._active_sub_width() or D
            transient = self.forecaster.model_bytes(
                w, self.capacity
            ) + self.forecaster.model_bytes(w, new_cap)
            if transient <= budget or not self._narrow_subbatch("forecast"):
                return

    def _map_subbatches(self, fn, width: int):
        """Apply ``fn(cols_slice, meta_slice) -> (cols, meta)`` per
        doc-axis sub-batch and reassemble. Only one slice's transient
        (donated old + new buffers) is live at a time — the bounded
        working set that lets compact/grow clear shapes whose
        monolithic transient busts the budget."""
        D = self.cols.shape[1]
        outs_c, outs_m = [], []
        for lo in range(0, D, width):
            hi = min(lo + width, D)
            c = jax.lax.slice_in_dim(self.cols, lo, hi, axis=1)
            m = jax.lax.slice_in_dim(self.meta, lo, hi, axis=0)
            c, m = fn(c, m)
            outs_c.append(c)
            outs_m.append(m)
        if len(outs_c) == 1:
            return outs_c[0], outs_m[0]
        return (
            jnp.concatenate(outs_c, axis=1),
            jnp.concatenate(outs_m, axis=0),
        )

    def _dispatch_subbatched(
        self, lane, width, stage, span_tail, dev, vmem_mb, scan_plan,
        program, program_kw,
    ):
        """Run one chunk program per doc-axis sub-batch slice and
        reassemble (ISSUE-20 tentpole). Invariants:

        - every slice shares ONE `(width, capacity)` shape family, so
          the loop costs exactly one compile under the PR-17 sentinel
          (the per-slice span key carries no slice index);
        - slices are fresh `slice_in_dim` arrays, so the programs'
          donation frees only slice transients — `self.cols/meta/_err`
          stay alive and the PR-6 lane-ladder retry-in-place works
          unchanged;
        - the sticky decode-error scalar threads slice→slice (a copy of
          `self._err` seeds slice 0 — the original is never donated);
        - per-slice readouts fold on device into the ONE `[N_READOUT]`
          future the drain already parses: zero new syncs (PR-5);
        - on a multi-device host, slices round-robin across the batch
          mesh (`ytpu.parallel.mesh.subbatch_devices`); single-device
          placement is a no-op, keeping CPU dispatch byte-identical.
        """
        from ytpu.parallel.mesh import subbatch_devices
        from ytpu.utils.phases import (
            NULL_SPAN,
            phases as _phases,
            program_memory as _program_memory,
        )

        D = self.cols.shape[1]
        n_sub = (D + width - 1) // width
        placements = subbatch_devices(n_sub)
        err = jnp.bitwise_or(self._err, jnp.zeros((), I32))
        outs_c, outs_m, readouts = [], [], []
        for i, lo in enumerate(range(0, D, width)):
            hi = min(lo + width, D)
            sub_cols = jax.lax.slice_in_dim(self.cols, lo, hi, axis=1)
            sub_meta = jax.lax.slice_in_dim(self.meta, lo, hi, axis=0)
            dev_i = dev
            if placements is not None:
                tgt = placements[i]
                sub_cols = jax.device_put(sub_cols, tgt)
                sub_meta = jax.device_put(sub_meta, tgt)
                err = jax.device_put(err, tgt)
                dev_i = tuple(jax.device_put(a, tgt) for a in dev)
            span = (
                _phases.span(
                    "replay.subbatch",
                    (sub_cols.shape, stage, span_tail, lane,
                     self.d_block, vmem_mb, scan_plan),
                    axes=("state", "stage", "tail", "lane", "d_block",
                          "vmem_mb", "scan_plan"),
                    memory=_program_memory(
                        program, sub_cols, sub_meta, err, *dev_i,
                        self.rank, lane=lane, d_block=self.d_block,
                        interpret=self.interpret, vmem_mb=vmem_mb,
                        scan_plan=scan_plan, **program_kw,
                    ),
                )
                if _phases.enabled
                else NULL_SPAN
            )
            with span:
                sub_cols, sub_meta, err, ro = program(
                    sub_cols,
                    sub_meta,
                    err,
                    *dev_i,
                    self.rank,
                    lane=lane,
                    d_block=self.d_block,
                    interpret=self.interpret,
                    vmem_mb=vmem_mb,
                    scan_plan=scan_plan,
                    **program_kw,
                )
            outs_c.append(sub_cols)
            outs_m.append(sub_meta)
            readouts.append(ro)
        if placements is not None:
            # gather outputs onto one device before reassembly (the
            # follow-up NamedSharding-resident layout stays ROADMAP work)
            home = placements[0]
            outs_c = [jax.device_put(a, home) for a in outs_c]
            outs_m = [jax.device_put(a, home) for a in outs_m]
            readouts = [jax.device_put(a, home) for a in readouts]
            err = jax.device_put(err, home)
        cols = jnp.concatenate(outs_c, axis=1) if n_sub > 1 else outs_c[0]
        meta = jnp.concatenate(outs_m, axis=0) if n_sub > 1 else outs_m[0]
        readout = (
            _fold_subbatch_readouts(jnp.stack(readouts))
            if n_sub > 1
            else readouts[0]
        )
        self.stats.subbatch_width = width
        if _phases.enabled:
            _phases.set_value("subbatch.width", width)
            _phases.set_value("subbatch.n_sub", n_sub)
        return cols, meta, err, readout

    # ----------------------------------------------------------- readouts

    def _drain_readouts(self) -> int:
        """Materialize every pending readout; returns the freshest actual
        occupancy. Raises on a sticky device error flag."""
        from ytpu.utils.phases import phases as _phases

        hi = self._hi_bound
        if self._pending:
            if _phases.enabled:
                # the original [3]-word occupancy/error readout keeps its
                # historical 12-byte accounting (the zero-sync invariant
                # test pins it); the scan-width words riding the SAME
                # future attribute separately — one future, no new sync
                _phases.transfer(
                    "replay.readout", 12 * len(self._pending), "d2h"
                )
                _phases.transfer(
                    "integrate.scan_hist",
                    4 * SCAN_REC_WORDS * len(self._pending),
                    "d2h",
                )
                # the ISSUE-13 commitment word rides the same future:
                # its 4 bytes attribute separately, `replay.readout`
                # keeps its historical 12-byte accounting
                _phases.transfer(
                    "integrate.commit_word", 4 * len(self._pending), "d2h"
                )
                # the ISSUE-18 capacity-ledger words ride it too: their
                # bytes attribute under their own stage so every pinned
                # historical accounting above stays exact
                _phases.transfer(
                    "capacity.ledger",
                    4 * LEDGER_WORDS * len(self._pending),
                    "d2h",
                )
            sticky_derr = 0
            for fut in self._pending:
                try:
                    vals = np.asarray(fut)
                except Exception as e:
                    # an async dispatch whose EXECUTION died surfaces
                    # here, not at the dispatch call — the packed state
                    # downstream of it is unusable, so record the sticky
                    # demotion and hand the caller the resume path
                    if not is_device_fault(e):
                        raise
                    demote_lane(self._family, self.lane)
                    self.stats.demotions += 1
                    self._pending.clear()
                    raise ReplayFault(
                        f"deferred device fault at readout on lane "
                        f"{self.lane!r} ({type(e).__name__}: {e})",
                        chunk=self.stats.chunks,
                        lane=self.lane,
                        cause=e,
                    ) from e
                occ, kerr = int(vals[0]), int(vals[1])
                derr = int(vals[2]) if vals.shape[0] > 2 else 0
                if vals.shape[0] >= N_READOUT:
                    # meta carries the CUMULATIVE record, so the freshest
                    # readout supersedes earlier ones in the same drain
                    self._record_scan_width(
                        vals[3 : 3 + SCAN_WIDTH_BUCKETS],
                        int(vals[3 + SCAN_WIDTH_BUCKETS]),
                        vals[3 + SCAN_WIDTH_BUCKETS + 1 : 3 + SCAN_REC_WORDS],
                    )
                    # ISSUE-13 commitment word: recomputed from the
                    # state per readout, so the freshest one is THE
                    # current value (uint32 bit pattern of an i32 word)
                    self.stats.commit_word = (
                        int(vals[3 + SCAN_REC_WORDS]) & 0xFFFFFFFF
                    )
                    if _phases.enabled:
                        _phases.set_value(
                            "integrate.commit_word", self.stats.commit_word
                        )
                    # ISSUE-18 capacity ledger: same freshest-supersedes
                    # semantics — the words are recomputed from the
                    # CURRENT state at each readout
                    base = 4 + SCAN_REC_WORDS
                    self._record_capacity_ledger(
                        int(vals[base]),
                        int(vals[base + 1]),
                        int(vals[base + 2]),
                    )
                self.stats.peak_blocks = max(self.stats.peak_blocks, occ)
                if derr != 0:
                    if self.quarantine and self.on_quarantine is not None:
                        sticky_derr |= derr  # handled once after the loop
                    else:
                        self._raise_decode_error(derr)
                if kerr != 0:
                    self._raise_device_error()
                hi = occ
            self._pending.clear()
            self.stats.syncs += 1
            self._hi_bound = hi
            if sticky_derr:
                # skip-and-record: flagged lanes already integrated as
                # no-ops on device, so recording the offenders and
                # clearing the sticky scalar IS the recovery
                newly = self.on_quarantine(sticky_derr) or []
                self.stats.quarantined += len(newly)
                _QUARANTINED.inc(len(newly))
                self._err = jnp.zeros((), I32)
        return hi

    def _record_scan_width(self, buckets, observed_max: int, tiers=()) -> None:
        """Fold one materialized readout's scan words into the driver
        stats and the `integrate.scan_width_*` / `integrate.scan_tier_*`
        phase gauges (ISSUE-11/12). Called only from drains — the record
        arrives on the readout future the host was already blocking on,
        so this adds ZERO device syncs. Gauges land twice: the base key
        and a `.{lane}`-suffixed key, so fused- and packed-XLA-lane
        distributions stay separately regressable."""
        from ytpu.utils.phases import phases as _phases

        counts = [int(c) for c in buckets]
        mx = int(observed_max)
        st = self.stats
        st.scan_hist = tuple(counts)
        st.scan_max = mx
        st.scan_p50 = scan_width_quantile(counts, 0.50, mx)
        st.scan_p99 = scan_width_quantile(counts, 0.99, mx)
        tiers = [int(t) for t in tiers]
        if len(tiers) == SCAN_REC_WORDS - SCAN_WIDTH_BUCKETS - 1:
            cheap, wide, cheap_trips, wide_trips, width_sum = tiers
            st.scan_tier_cheap = cheap
            st.scan_tier_wide = wide
            st.scan_trips_serial = width_sum
            st.scan_trips_two_tier = cheap_trips + wide_trips
        if _phases.enabled and sum(counts):
            for name, v in (
                ("width_p50", st.scan_p50),
                ("width_p99", st.scan_p99),
                ("width_max", st.scan_max),
                ("tier_cheap", st.scan_tier_cheap),
                ("tier_wide", st.scan_tier_wide),
                ("trips_serial", st.scan_trips_serial),
                ("trips_two_tier", st.scan_trips_two_tier),
            ):
                _phases.set_value(f"integrate.scan_{name}", v)
                _phases.set_value(
                    f"integrate.scan_{name}.{self.lane}", v
                )

    def _record_capacity_ledger(
        self, occupied: int, dead: int, dead_max: int
    ) -> None:
        """Fold one materialized readout's capacity-ledger words into
        the driver stats, the `capacity.*` phase gauges, and (when set)
        the headroom forecaster (ISSUE-18). Called only from drains —
        the words arrive on the readout future the host was already
        blocking on, so this adds ZERO device syncs."""
        from ytpu.utils.phases import phases as _phases

        st = self.stats
        st.occupied_rows = int(occupied)
        st.dead_rows = int(dead)
        st.dead_max = int(dead_max)
        D = self.cols.shape[1]
        total = D * self.capacity
        if self.forecaster is not None:
            self.forecaster.observe(
                n_docs=D,
                capacity=self.capacity,
                occupied_rows=st.occupied_rows,
                dead_rows=st.dead_rows,
                chunks=st.chunks,
                max_capacity=self.max_capacity,
            )
        if _phases.enabled:
            for name, v in (
                ("occupied_rows", st.occupied_rows),
                ("dead_rows", st.dead_rows),
                ("dead_max", st.dead_max),
                ("free_rows", total - st.occupied_rows),
                (
                    "dead_fraction",
                    st.dead_rows / max(st.occupied_rows, 1),
                ),
                (
                    "occupancy_fraction",
                    st.occupied_rows / max(total, 1),
                ),
            ):
                _phases.set_value(f"capacity.{name}", v)

    def _raise_device_error(self):
        meta_np = np.asarray(self.meta)
        bad = meta_np[meta_np[:, M_ERROR] != 0][:4]
        raise RuntimeError(f"device error flags {bad}")

    def _raise_decode_error(self, flags_or: int):
        if self.on_decode_error is not None:
            self.on_decode_error(flags_or)  # expected to raise
        raise RuntimeError(
            f"device decode flagged errors in a deferred chunk (sticky "
            f"flags {flags_or}); replay with sync_every_chunk=True to "
            "localize the update"
        )

    # ------------------------------------------- lane ladder (ISSUE-6)

    def _refresh_origin_slot_packed(self) -> None:
        """Demotion repair: chunks run by the fused kernel leave the
        packed origin_slot cache plane stale, and the packed-XLA chunk
        step's conflict scan READS that plane — rebuild it before the
        first post-demotion XLA chunk (rare failure path; the O(D·B²)
        rebuild cost is irrelevant next to the fault it recovers from)."""
        from ytpu.models.batch_doc import recompute_origin_slot

        state = unpack_state(self.cols, self.meta, None)
        state = recompute_origin_slot(state)
        self.cols, self.meta = pack_state(state)

    def _absorb_lane_fault(self, e: BaseException) -> None:
        """Classify one dispatch failure: demote-and-return when the SAME
        chunk can retry in place on the next rung, else raise
        `ReplayFault` for the caller's checkpoint-resume path.  Host-side
        programming errors re-raise untouched."""
        if not is_device_fault(e):
            raise e
        kill = isinstance(e, FaultError) and bool(e.spec.args.get("kill"))
        alive = _buffers_alive(self.cols, self.meta, self._err)
        nxt = demote_lane(self._family, self.lane)
        if nxt is not None:
            self.stats.demotions += 1
        if kill or not alive or nxt is None or nxt == "host":
            raise ReplayFault(
                f"device dispatch failed on lane {self.lane!r} "
                f"({type(e).__name__}: {e})"
                + ("" if alive else " — state buffers lost to donation"),
                chunk=self.stats.chunks,
                lane=self.lane,
                cause=e,
            ) from e
        if self.lane == "fused":
            self._refresh_origin_slot_packed()
        self.lane = nxt
        self.stats.recoveries += 1
        _RECOVERIES.inc()

    def _dispatch(self, fn):
        """Run one chunk dispatch under the lane-health ladder: an
        injected or real dispatch/compile failure demotes the family one
        rung (sticky) and retries the SAME chunk in place while the state
        buffers survive; past the driver's rungs — or on simulated worker
        death (`replay.kill`) — it raises `ReplayFault` instead."""
        while True:
            try:
                faults.maybe_raise("dispatch.fail", lane=self.lane)
                out = fn(self.lane)
            except Exception as e:
                self._absorb_lane_fault(e)
                continue
            spec = faults.fire("replay.kill", lane=self.lane)
            if spec is not None:
                raise ReplayFault(
                    "injected mid-replay kill (state treated as lost)",
                    chunk=self.stats.chunks,
                    lane=self.lane,
                    cause=FaultError("replay.kill", spec),
                )
            _LAST_DISPATCH.set(time.time())
            return out

    # ------------------------------------------------------- compact/grow

    def compact(self) -> int:
        """Force a commit-style on-device compaction of the packed state;
        returns the actual high-water block count afterwards. Efficacy
        accounting (ISSUE-18): rows reclaimed vs the freshest
        pre-compaction ledger, and the chunk gap since the previous
        compaction (time-to-watermark) — both from readouts the call
        was already draining, zero new syncs."""
        from ytpu.ops.compaction import compact_packed
        from ytpu.utils.phases import phases as _phases

        occ_before = self.stats.occupied_rows
        sub_w = self._active_sub_width()
        if sub_w is None:
            self.cols, self.meta = compact_packed(
                self.cols, self.meta, self.unit_refs, self.gc_ranges
            )
        else:
            # compact_packed vmaps per doc, so per-slice compaction is
            # byte-identical — but its temp-heavy transient now peaks at
            # the sub width, not the monolith (ISSUE-20)
            self.cols, self.meta = self._map_subbatches(
                lambda c, m: compact_packed(
                    c, m, self.unit_refs, self.gc_ranges
                ),
                sub_w,
            )
        self.stats.compactions += 1
        if self._last_compact_chunk >= 0:
            self.stats.compact_gap_chunks = (
                self.stats.chunks - self._last_compact_chunk
            )
        self._last_compact_chunk = self.stats.chunks
        self._pending.append(_chunk_readout(self.cols, self.meta, self._err))
        hi = self._drain_readouts()
        reclaimed = max(0, occ_before - self.stats.occupied_rows)
        self.stats.reclaimed_rows += reclaimed
        if _phases.enabled:
            _phases.add_value("capacity.reclaimed_rows", reclaimed)
            _phases.set_value(
                "capacity.compact_gap_chunks", self.stats.compact_gap_chunks
            )
        return hi

    def ensure_room(self, margin: int) -> None:
        """Compact (and grow, when allowed) BEFORE a chunk whose worst-case
        growth is `margin`, so ERR_CAPACITY — which corrupts the tile —
        cannot fire mid-chunk."""
        if not self.policy.should_compact(self._hi_bound, margin, self.capacity):
            return
        hi = self._drain_readouts()
        if not self.policy.should_compact(hi, margin, self.capacity):
            return
        hi = self.compact()
        while hi + margin > self.capacity:
            new_cap = min(self.capacity * 2, self.max_capacity)
            if new_cap <= self.capacity:
                # `<=`, not `==`: a max_capacity BELOW the current
                # capacity used to fall through into grow_packed and
                # raise its misleading "cannot shrink" (PR-4 review) —
                # either way the real condition is capacity exhaustion
                raise RuntimeError(
                    f"state needs {hi + margin} block slots but replay "
                    f"is capacity-exhausted: max_capacity "
                    f"{self.max_capacity} (current capacity "
                    f"{self.capacity})"
                )
            from ytpu.ops.compaction import grow_packed

            # ISSUE-20 satellite: the forecaster is consulted BEFORE the
            # grow attempt — a modeled transient that busts the budget
            # narrows the sub-batch width instead of provoking the OOM
            if self.shard_docs:
                self._forecast_narrow(new_cap)
            try:
                spec = faults.fire("grow.oom")
                if spec is not None:
                    # typed denial (ISSUE-18): report attempted vs
                    # available bytes so chaos can score the /capacity
                    # forecaster against reality, and count it
                    from ytpu.utils.capacity import memory_budget_bytes

                    _GROW_DENIED.inc()
                    D = self.cols.shape[1]
                    raise GrowOomError(
                        spec,
                        capacity=self.capacity,
                        new_capacity=new_cap,
                        n_docs=D,
                        attempted_bytes=packed_state_bytes(D, new_cap),
                        available_bytes=int(
                            spec.args.get(
                                "budget", memory_budget_bytes()
                            )
                        ),
                    )
                sub_w = self._active_sub_width()
                if sub_w is None:
                    self.cols, self.meta = grow_packed(
                        self.cols, self.meta, new_cap
                    )
                else:
                    self.cols, self.meta = self._map_subbatches(
                        lambda c, m: grow_packed(c, m, new_cap), sub_w
                    )
            except Exception as e:
                if (
                    isinstance(e, GrowOomError)
                    and self.shard_docs
                    and self._narrow_subbatch("grow.oom")
                ):
                    # ISSUE-20: a denied grow demotes to a narrower
                    # sub-batch width and retries the SAME capacity step
                    # instead of killing the chunk (the armed fault was
                    # consumed firing, so the retry proceeds)
                    continue
                if not is_device_fault(e):
                    raise
                # a failed growth (device OOM) leaves the pre-grow state
                # valid but the next chunk unservable — checkpoint-resume
                # territory, not an in-place retry
                raise ReplayFault(
                    f"grow to capacity {new_cap} failed "
                    f"({type(e).__name__}: {e})",
                    chunk=self.stats.chunks,
                    lane=self.lane,
                    cause=e,
                ) from e
            self.stats.growths += 1
            self.stats.capacity = new_cap

    # --------------------------------------------------------------- step

    def step(self, stream, margin: Optional[int] = None) -> None:
        """Integrate one [S, ...] stream chunk (doc-free leading step axis,
        the `apply_update_stream` shape). `margin` is the chunk's worst-
        case slot growth; pass it when known host-side (e.g. from
        `ReplayPlan.adds`) to avoid touching the stream's valid masks."""
        from ytpu.models.batch_doc import stream_worst_case_adds
        from ytpu.utils.phases import (
            NULL_SPAN,
            phases as _phases,
            program_memory as _program_memory,
        )

        if margin is None:
            margin = int(stream_worst_case_adds(stream).sum()) + 8
        self.ensure_room(margin)

        # two-tier scan plan: env re-read per chunk, static through both
        # lanes' programs so a changed knob retraces (ADVICE r5 #2 shape)
        scan_plan = scan_tier_plan()

        def dispatch(lane):
            if lane == "fused":
                rows, dels = pack_stream(stream)
                # YTPU_FUSED_VMEM_MB rides `_run` as a STATIC arg (read
                # per chunk): a changed limit forces a retrace instead of
                # silently reusing the old compiled guard (ADVICE r5 #2)
                vmem_mb = fused_vmem_mb(self.d_block, self.cols.shape[2])
                if _phases.enabled:
                    _phases.transfer(
                        "replay.chunk_fused",
                        rows.size * rows.dtype.itemsize
                        + dels.size * dels.dtype.itemsize,
                        "h2d",
                    )
                    span = _phases.span(
                        "replay.chunk_fused",
                        (self.cols.shape, rows.shape, dels.shape,
                         self.d_block, scan_plan),
                        axes=("state", "rows", "dels", "d_block",
                              "scan_plan"),
                        memory=_program_memory(
                            _run, self.cols, self.meta,
                            (rows, dels, self.rank), self.d_block,
                            self.interpret, 3, 4, vmem_mb, scan_plan,
                        ),
                    )
                else:
                    span = NULL_SPAN
                with span:
                    return _run(
                        self.cols,
                        self.meta,
                        (rows, dels, self.rank),
                        self.d_block,
                        self.interpret,
                        3,
                        4,
                        vmem_mb,
                        scan_plan,
                    )
            span = (
                _phases.span(
                    "replay.chunk_xla",
                    (self.cols.shape, stream.client.shape, scan_plan),
                    axes=("state", "stream", "scan_plan"),
                    # the jitted step is a lazily-built module singleton:
                    # resolve it at thunk-invoke time (the span body
                    # constructs it on the very first call)
                    memory=_program_memory(
                        lambda: _XLA_CHUNK_STEP, self.cols, self.meta,
                        stream, self.rank, scan_plan,
                    ),
                )
                if _phases.enabled
                else NULL_SPAN
            )
            with span:
                return xla_chunk_step(
                    self.cols, self.meta, stream, self.rank, scan_plan
                )

        self.cols, self.meta = self._dispatch(dispatch)
        self._pending.append(_chunk_readout(self.cols, self.meta, self._err))
        self._hi_bound += margin
        self.stats.chunks += 1
        if self.sync_every_chunk:
            self._drain_readouts()

    def _step_one_dispatch(self, stage, host_arrays, margin, span_tail,
                           program, span_axes=(), **program_kw):
        """Shared mechanics of the one-dispatch byte lanes (`step_bytes`
        / `step_raw`): progbudget tick, pre-chunk room check, the
        zero-copy-backend host copy, h2d accounting, the lane-laddered
        dispatch, and the readout/occupancy-bound epilogue — one copy,
        so a fix to any of them (e.g. the `_transfer_aliases_host` race
        guard) can never reach one lane and miss the other. The program
        is called as ``program(cols, meta, err, *device_arrays, rank,
        lane=..., ...program_kw..., d_block/interpret/vmem_mb)``;
        `span_tail` extends the phases span key with the lane-specific
        shape statics. Returns the device input arrays (the caller's
        slot-reuse gate)."""
        from ytpu.utils import progbudget
        from ytpu.utils.phases import (
            NULL_SPAN,
            phases as _phases,
            program_memory as _program_memory,
        )

        progbudget.tick()
        self.ensure_room(margin)
        vmem_mb = fused_vmem_mb(self.d_block, self.cols.shape[2])
        # two-tier scan plan: env re-read per chunk, threaded as a static
        # of the one-dispatch programs — a changed knob retraces
        scan_plan = scan_tier_plan()
        if _transfer_aliases_host():
            host_arrays = tuple(a.copy() for a in host_arrays)
        dev = tuple(jnp.asarray(a) for a in host_arrays)
        if _phases.enabled:
            _phases.transfer(
                stage,
                sum(a.size * a.dtype.itemsize for a in dev),
                "h2d",
            )
        sub_w = self._active_sub_width()

        def dispatch(lane):
            if sub_w is not None:
                return self._dispatch_subbatched(
                    lane, sub_w, stage, span_tail, dev, vmem_mb,
                    scan_plan, program, program_kw,
                )
            span = (
                _phases.span(
                    stage,
                    (self.cols.shape, *span_tail, lane, self.d_block,
                     vmem_mb, scan_plan),
                    axes=("state", *span_axes, "lane", "d_block",
                          "vmem_mb", "scan_plan"),
                    memory=_program_memory(
                        program, self.cols, self.meta, self._err, *dev,
                        self.rank, lane=lane, d_block=self.d_block,
                        interpret=self.interpret, vmem_mb=vmem_mb,
                        scan_plan=scan_plan, **program_kw,
                    ),
                )
                if _phases.enabled
                else NULL_SPAN
            )
            with span:
                return program(
                    self.cols,
                    self.meta,
                    self._err,
                    *dev,
                    self.rank,
                    lane=lane,
                    d_block=self.d_block,
                    interpret=self.interpret,
                    vmem_mb=vmem_mb,
                    scan_plan=scan_plan,
                    **program_kw,
                )

        self.cols, self.meta, self._err, readout = self._dispatch(dispatch)
        self._pending.append(readout)
        self._hi_bound += margin
        self.stats.chunks += 1
        if self.sync_every_chunk:
            self._drain_readouts()
        return dev

    def step_bytes(self, buf, lens, refs, dims, margin: int):
        """Integrate one chunk straight from padded wire bytes: decode →
        unit-ref rebase → integrate → readout as ONE dispatch
        (`replay_chunk_program`, donated state) — the async replay
        loop's zero-sync steady state. `dims` is the decode-shape tuple
        ``(max_rows, max_dels, n_steps, max_sections)`` (from
        `ReplayPlan`); `refs` the chunk's ``[S, U]`` global unit-ref
        rows (-1 = keep the decoded ref); `margin` the chunk's
        worst-case slot growth. Decode errors fold into the sticky
        device scalar and surface at the next drain / `finish()`.

        Returns the device input arrays: the caller gates reuse of the
        numpy staging buffers on their transfer completing
        (`block_until_ready` on an INPUT waits for the h2d copy only —
        it is not a result materialization). On a backend whose
        "transfer" is zero-copy (CPU jax aliases the numpy buffer), the
        arrays are copied host-side first so a re-packed slot can never
        race the program still reading it."""
        max_rows, max_dels, n_steps, max_sections = dims
        return self._step_one_dispatch(
            "replay.chunk_async",
            (buf, lens, refs),
            margin,
            (buf.shape, refs.shape, tuple(dims)),
            replay_chunk_program,
            span_axes=("buf", "refs", "dims"),
            max_rows=max_rows,
            max_dels=max_dels,
            n_steps=n_steps,
            max_sections=max_sections,
        )

    def step_raw(self, raw, offs, lens, refs, dims, width: int, margin: int):
        """Integrate one chunk straight from RAW CONCATENATED wire bytes
        + a per-update offsets table: device lane-gather → decode →
        unit-ref rebase → integrate → readout as ONE dispatch
        (`replay_chunk_program_raw`, donated state) — the raw ingest
        lane whose host staging is a memcpy (ISSUE-7). ``width`` is the
        static per-lane window (the host-packed lane's ``pad_to``), the
        other arguments mirror `step_bytes`, including the returned
        device inputs for the caller's slot-reuse gate and the
        zero-copy-backend host copy."""
        max_rows, max_dels, n_steps, max_sections = dims
        return self._step_one_dispatch(
            "replay.chunk_raw",
            (raw, offs, lens, refs),
            margin,
            (raw.shape, refs.shape, tuple(dims), width),
            replay_chunk_program_raw,
            span_axes=("raw", "refs", "dims", "width"),
            width=width,
            max_rows=max_rows,
            max_dels=max_dels,
            n_steps=n_steps,
            max_sections=max_sections,
        )

    def finish(self):
        """Drain every pending readout (surfacing sticky errors) and
        return the packed (cols, meta)."""
        self._drain_readouts()
        self.stats.capacity = self.capacity
        self.stats.final_blocks = int(
            np.asarray(self.meta)[:, M_NBLOCKS].max()
        )
        return self.cols, self.meta


def replay_stream_fused(
    state: DocStateBatch,
    stream: UpdateBatch,
    client_rank: jax.Array,
    *,
    chunk_steps: int = 64,
    d_block: int = 8,
    interpret: bool = False,
    lane: str = "fused",
    policy=None,
    max_capacity: Optional[int] = None,
    refresh_cache: bool = False,
    shard_docs: bool = False,
    forecaster=None,
) -> Tuple[DocStateBatch, ReplayChunkStats]:
    """Chunked fused replay of a stacked [S, ...] update stream with
    between-chunk device compaction — `apply_update_stream_fused` for
    streams whose PEAK block count exceeds the tile capacity.

    The stream is cut into fixed `chunk_steps` windows (one compiled
    program serves every chunk; the tail pads with valid=False steps),
    each window runs through the fused kernel (`lane="fused"`) or the
    packed XLA chunk step (`lane="xla"`, the CPU-testable / Mosaic-
    fallback twin), and between windows the shared `CompactionPolicy`
    decides when the packed state squashes (`compact_packed`) or grows
    (`grow_packed`) — never unpacking to host mid-replay. Returns the
    final state plus `ReplayChunkStats`.

    origin_slot cache: the fused lane marks the returned state stale
    (same contract as `apply_update_stream_fused`; `refresh_cache=True`
    opts into the eager O(D·B²) rebuild); the XLA lane maintains the
    cache in-kernel, so the input is `ensure_origin_slot`'d up front and
    the output stays fresh — compaction's defrag remap preserves the
    containment contract either way.

    ``shard_docs=True`` (ISSUE-20) enables the driver's doc-axis
    sub-batch plan for this stream replay: the per-step integrate
    dispatch stays monolithic (the stacked-stream path carries no
    per-slice readout fold), but between-chunk `compact_packed` /
    `grow_packed` run per pow2-width doc slice under the budget
    (``forecaster`` optionally pins it) — the mixed-content twin of the
    byte-stream path's fully sliced dispatch."""
    from ytpu.models.batch_doc import stream_worst_case_adds

    if lane == "xla":
        from ytpu.models.batch_doc import ensure_origin_slot

        state = ensure_origin_slot(state)
    S = stream.valid.shape[0]
    if S == 0:
        return state, ReplayChunkStats(capacity=state.blocks.client.shape[-1])
    adds = stream_worst_case_adds(stream)
    initial = int(np.asarray(state.n_blocks).max())
    cols, meta = pack_state(state)
    driver = PackedReplayDriver(
        cols,
        meta,
        client_rank,
        d_block=d_block,
        interpret=interpret,
        lane=lane,
        policy=policy,
        max_capacity=max_capacity,
        initial_occupancy=initial,
        shard_docs=shard_docs,
    )
    driver.forecaster = forecaster
    for s in range(0, S, chunk_steps):
        e = min(S, s + chunk_steps)
        chunk = jax.tree_util.tree_map(lambda a: a[s:e], stream)
        if e - s < chunk_steps:
            # pad the tail to the compiled shape: replicate the last step,
            # then invalidate the padding rows/deletes
            pad = chunk_steps - (e - s)

            def _pad(a):
                tail = jnp.broadcast_to(a[-1:], (pad,) + a.shape[1:])
                return jnp.concatenate([a, tail], axis=0)

            chunk = jax.tree_util.tree_map(_pad, chunk)
            chunk = chunk._replace(
                valid=chunk.valid.at[e - s :].set(False),
                del_valid=chunk.del_valid.at[e - s :].set(False),
            )
        driver.step(chunk, margin=int(adds[s:e].sum()) + 8)
    cols, meta = driver.finish()
    out = unpack_state(cols, meta, state)
    if lane == "fused":
        if refresh_cache:
            from ytpu.models.batch_doc import recompute_origin_slot

            return recompute_origin_slot(out), driver.stats
        from ytpu.models.batch_doc import mark_origin_slot_stale

        mark_origin_slot_stale(out)
    return out, driver.stats


def _register_programs():
    from ytpu.utils import progbudget

    progbudget.register("fused_run", _run)
    # the chunk programs (fused decode+rebase+integrate, host-packed and
    # raw-gather variants) are the largest executables in the process —
    # one per (chunk, width, refs, state) shape family; they must ride
    # the same bounded-arena budget
    progbudget.register("replay_chunk_program", replay_chunk_program)
    progbudget.register("replay_chunk_program_raw", replay_chunk_program_raw)


_register_programs()
