"""Device-side lib0/V1 update decoding — raw wire bytes in HBM → block rows.

The north-star fusion (SURVEY §2 #1, §7 step 8): hosts ship raw Yjs V1
update payloads to the device as a padded ``[S, L]`` byte matrix; the
device turns them into the columnar ``UpdateBatch`` stream the integrate
kernels consume. No host-side parsing, interning, or payload copying —
string payloads stay inside the wire buffer and are addressed by linear
byte offsets (``content_ref = s * L + byte_start``).

Algorithm: a vectorized field-at-a-time state machine. Every iteration
decodes one lib0 varint (or one info byte / one string skip) *in every
update lane simultaneously* — the per-lane parse is sequential (the wire
grammar is), but all S updates advance in lockstep as [S]-wide vector
ops, and UTF-16 lengths of string payloads come from prefix sums over
byte-class masks (the Stream-VByte-style trick: continuation-bit masks +
cumulative sums instead of byte loops).

Grammar decoded here (reference: update.rs:714-749 + :433-488,
block.rs:1786-1835, id_set.rs decode):

    update   := n_clients:var ( n_blocks:var client:var clock:var block* )*
                delete_set
    block    := info:u8
                [ origin:id ]       if info & 0x80
                [ r_origin:id ]     if info & 0x40
                [ parent ]          if info & 0xC0 == 0
                [ parent_sub:str ]  if info & 0xC0 == 0 and info & 0x20
                content
    content  := GC len:var | Skip len:var | Deleted len:var | String str
                | Any n:var value{token}* | Json n:var str* | Embed str
                | Binary buf | Format key:str value:str
                | Type tag:u8 [name:str]
                | Move flags:var start:id [end:id]
                (WeakRef types / Doc → host fallback, flagged)
    delete_set := n_clients:var ( client:var n_ranges:var (clock:var len:var)* )*

Supported on-device: GC / Skip / Deleted / String / Any (scalars,
arrays, depth-1 objects) / Json / Embed / Binary / Format / Type
(nested shared types; WeakRef branches excluded) / Move blocks with
root, ID, or nested parents, including map rows — parent_sub keys
resolve through a host-verified hash table (`key_table`), and client
ids beyond i32 (real 53-bit Yjs ids) through a varint-byte hash table
(`client_hash_table`). The remaining host-lane shapes: non-scalar
values nested inside object Any values, oversized keys, WeakRef types,
Doc. Flagged updates lose nothing — they take the exact host path they
take today.

Without tables, client ids are kept *raw*: YATA's tie-break is monotone
in the client id itself, so the rank table for the fused kernel is the
identity (`identity_rank`).
"""

from __future__ import annotations

from functools import partial
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ytpu.core.content import (
    BLOCK_GC,
    BLOCK_SKIP,
    CONTENT_ANY,
    CONTENT_BINARY,
    CONTENT_DELETED,
    CONTENT_EMBED,
    CONTENT_FORMAT,
    CONTENT_JSON,
    CONTENT_MOVE,
    CONTENT_STRING,
    CONTENT_TYPE,
)
from ytpu.models.batch_doc import UpdateBatch, pack_batch

__all__ = [
    "pack_updates",
    "pack_updates_into",
    "pack_raw_updates_into",
    "gather_raw_lanes",
    "EMPTY_UPDATE",
    "decode_updates_v1",
    "default_steps",
    "exact_steps",
    "steps_for_columns",
    "identity_rank",
    "utf8_slice_u16",
    "RawPayloadView",
    "ChunkedWirePayloads",
    "FLAG_UNSUPPORTED",
    "FLAG_OVERFLOW",
    "FLAG_MALFORMED",
    "FLAG_BIG_CLIENT",
    "FLAG_MULTI_CLIENT",
    "FLAG_UNKNOWN_CLIENT",
]

I32 = jnp.int32
U32 = jnp.uint32

# --- per-update flag bits ----------------------------------------------------
FLAG_UNSUPPORTED = 1  # content kind / parent_sub the device cannot decode
FLAG_OVERFLOW = 2  # more blocks / delete ranges than the U/R buckets
FLAG_MALFORMED = 4  # ran past the buffer or did not reach DONE in T steps
FLAG_BIG_CLIENT = 8  # a client id >= 2^31 (needs host interning)
FLAG_MULTI_CLIENT = 16  # informational: >1 client section (wire order may
#                         not be a valid integration order for cross-client
#                         origins inside one update; single-client updates —
#                         the live-editing case — are always ordered)
FLAG_UNKNOWN_CLIENT = 32  # a client id absent from the supplied intern table
FLAG_UNKNOWN_KEY = 64  # a parent_sub hash absent from the supplied key table

FLAG_ERRORS = (
    FLAG_UNSUPPORTED
    | FLAG_OVERFLOW
    | FLAG_MALFORMED
    | FLAG_BIG_CLIENT
    | FLAG_UNKNOWN_CLIENT
    | FLAG_UNKNOWN_KEY
)

# --- parser states -----------------------------------------------------------
(
    ST_NCLIENTS,
    ST_NBLOCKS,
    ST_CLIENT,
    ST_CLOCK,
    ST_INFO,
    ST_ORIGIN_C,
    ST_ORIGIN_K,
    ST_ROR_C,
    ST_ROR_K,
    ST_PARENT_INFO,
    ST_PARENT_NAME,
    ST_PARENT_ID_C,
    ST_PARENT_ID_K,
    ST_PARENT_SUB,
    ST_DEL_LEN,
    ST_GC_LEN,
    ST_SKIP_LEN,
    ST_STR,
    ST_DS_NCLIENTS,
    ST_DS_CLIENT,
    ST_DS_NRANGES,
    ST_DS_CLOCK,
    ST_DS_LEN,
    ST_ANY_COUNT,  # ContentAny: value count
    ST_ANY_VAL,  # ContentAny: one scalar value per step
    ST_JSON_COUNT,  # ContentJson: string count
    ST_JSON_VAL,  # ContentJson: one length-prefixed string per step
    ST_SPAN1,  # ContentEmbed/Binary: one length-prefixed span, len 1
    ST_FMT_KEY,  # ContentFormat: key string
    ST_FMT_VAL,  # ContentFormat: one Any value
    ST_TYPE_TAG,  # ContentType: branch TypeRef tag byte
    ST_TYPE_NAME,  # ContentType: XmlElement/XmlHook name string
    ST_MV_FLAGS,  # ContentMove: collapsed/assoc/priority flags varint
    ST_MV_SC,  # ContentMove: range-start id client
    ST_MV_SK,  # ContentMove: range-start id clock
    ST_MV_EC,  # ContentMove: range-end id client (absent if collapsed)
    ST_MV_EK,  # ContentMove: range-end id clock
    ST_ANY_MKEY,  # ContentAny map value: one key string per step
    ST_ANY_MVAL,  # ContentAny map value: one scalar value per step
    ST_DONE,
    ST_ERR,
) = range(41)

# key-hash window: parent_sub keys longer than this take the host lane
KEY_HASH_BYTES = 32

# --- the served step's lane table ---------------------------------------------
# Rows of the ``[LANE_FIELDS, S]`` i32 table a served step's first program
# takes out of the step's one upload and hands back beside the lane matrix
# (`ingest.gather_manifest_lanes`), a column a fast lane: where the lane's
# bytes start in the wire arena, how many they are, its primary root's hash
# (-1: none), its row of the step's batch, where its bytes start in the
# retained chunk, and the chunk's base (the same in every column). The
# decoder reads `LANE_LEN` and `LANE_ROOT_HASH`; `ingest.merge_stream`
# `LANE_AT`, `LANE_PREFIX` and `LANE_BASE`.
(
    LANE_OFFSET,
    LANE_LEN,
    LANE_ROOT_HASH,
    LANE_AT,
    LANE_PREFIX,
    LANE_BASE,
) = range(6)
LANE_FIELDS = 6

_PAD = 16  # gather guard past the longest update


def pack_updates(
    payloads: List[bytes], pad_to: Optional[int] = None
) -> Tuple[np.ndarray, np.ndarray]:
    """Pad raw V1 update byte strings into an ``[S, L] uint8`` matrix.

    This is the *only* host work on the device-decode path — a memcpy.
    """
    lens = np.array([len(p) for p in payloads], dtype=np.int32)
    L = max(int(lens.max()) + _PAD if len(payloads) else _PAD, pad_to or 0)
    buf = np.zeros((len(payloads), L), dtype=np.uint8)
    for i, p in enumerate(payloads):
        buf[i, : len(p)] = np.frombuffer(p, dtype=np.uint8)
    return buf, lens


# the minimal well-formed V1 update (0 client sections, empty delete set):
# what staging pads short tail chunks with so every chunk keeps the one
# compiled [S, L] shape
EMPTY_UPDATE = b"\x00\x00"


def pack_updates_into(
    payloads: List[bytes], buf: np.ndarray, lens: np.ndarray
) -> None:
    """`pack_updates` into CALLER-PROVIDED staging buffers (in place).

    The async replay pipeline reuses a pair of preallocated ``[S, L]``
    u8 / ``[S]`` i32 staging buffers across chunks instead of allocating
    a fresh matrix per chunk; rows past ``len(payloads)`` are filled
    with `EMPTY_UPDATE` so a short tail chunk decodes as no-ops at the
    compiled shape. Each row's tail is zeroed only up to the previous
    occupant's length — the buffers never shrink, so stale bytes beyond
    `lens` can never alias into a later decode (the decoder's gather
    guard reads at most `_PAD` past `lens`, which stays zeroed)."""
    S, L = buf.shape
    if len(payloads) > S:
        raise ValueError(f"chunk of {len(payloads)} exceeds staging rows {S}")
    for i in range(S):
        p = payloads[i] if i < len(payloads) else EMPTY_UPDATE
        n = len(p)
        if n + _PAD > L:
            raise ValueError(f"payload of {n} bytes exceeds staging width {L}")
        prev = int(lens[i])
        buf[i, :n] = np.frombuffer(p, dtype=np.uint8)
        if prev + _PAD > n:
            buf[i, n : prev + _PAD] = 0
        lens[i] = n


_EMPTY_NP = np.frombuffer(EMPTY_UPDATE, dtype=np.uint8)


def pack_raw_updates_into(
    wire: np.ndarray,
    wire_offsets: np.ndarray,
    pos: int,
    end: int,
    raw: np.ndarray,
    offs: np.ndarray,
    lens: np.ndarray,
    width: Optional[int] = None,
) -> int:
    """Stage one chunk of the RAW ingest lane (ISSUE-7): a slice copy of
    the run's concatenated wire bytes plus vectorized offset/length
    tables — NO per-update Python work (the memcpy-staging invariant the
    bench dry-run asserts). ``wire`` is the whole stream's concatenated
    payload bytes, ``wire_offsets`` its ``[S+1]`` prefix table (update i
    occupies ``wire[wire_offsets[i]:wire_offsets[i+1]]``); the chunk
    ``[pos, end)`` lands in the reusable ``raw`` byte buffer with
    in-chunk ``offs``/``lens`` rows the device lane-gather consumes.
    Rows past ``end - pos`` point at a staged `EMPTY_UPDATE` tail so a
    short tail chunk decodes as no-ops at the compiled shape. Stale raw
    bytes from a previous occupant are harmless: the device gather
    (`gather_raw_lanes`) zero-masks every byte at or past each lane's
    length. Returns the staged byte count. ``width`` (the decode lane
    width) enables the same oversized-payload check `pack_updates_into`
    performs."""
    n = end - pos
    if n > offs.shape[0]:
        raise ValueError(f"chunk of {n} exceeds staging rows {offs.shape[0]}")
    b0 = int(wire_offsets[pos])
    b1 = int(wire_offsets[end])
    nb = b1 - b0
    if nb + len(EMPTY_UPDATE) > raw.shape[0]:
        raise ValueError(
            f"chunk of {nb} wire bytes exceeds staging capacity {raw.shape[0]}"
        )
    chunk_lens = wire_offsets[pos : end + 1]
    if width is not None and n:
        longest = int((chunk_lens[1:] - chunk_lens[:-1]).max())
        if longest + _PAD > width:
            raise ValueError(
                f"payload of {longest} bytes exceeds staging width {width}"
            )
    raw[:nb] = wire[b0:b1]
    raw[nb : nb + len(EMPTY_UPDATE)] = _EMPTY_NP
    offs[:n] = chunk_lens[:-1] - b0
    lens[:n] = chunk_lens[1:] - chunk_lens[:-1]
    offs[n:] = nb
    lens[n:] = len(EMPTY_UPDATE)
    return nb + len(EMPTY_UPDATE)


def gather_raw_lanes(raw, offs, lens, width: int):
    """``[RC]`` raw concatenated bytes + per-update offsets → the padded
    ``[S, L]`` lane matrix `pack_updates` builds on host, materialized ON
    DEVICE: one clamped lane-parallel gather + zero mask (the Stream-
    VByte-style control/data split — the offsets table is the control
    stream, the byte arena the data stream, and every update lane peels
    its window simultaneously). Bytes at ``j >= lens[s]`` are zeroed so
    the matrix is byte-identical to a freshly host-packed one — the
    varint state machine's prefix sums, gather guard, and key-hash
    windows read them, so the mask is what guarantees raw-vs-packed
    decode parity for every content kind (tests/test_async_raw_ingest).
    """
    iota = jnp.arange(width, dtype=I32)[None, :]
    idx = jnp.clip(offs[:, None].astype(I32) + iota, 0, raw.shape[0] - 1)
    lanes = jnp.take(raw, idx)
    return jnp.where(iota < lens[:, None].astype(I32), lanes, 0)


def identity_rank(k: int) -> jax.Array:
    """Rank table for raw-client-id streams: rank(c) = c."""
    return jnp.arange(k, dtype=I32)


def default_steps(max_rows: int, max_dels: int) -> int:
    """Safe iteration budget: fields per block ≤ 10 (+3/client header),
    2 per delete range (+2/ds client), +4 frame fields. Covers scalar
    content only — value-list content (Any/Json) costs one extra step per
    value; callers with a native pre-scan pass an exact ``n_steps``."""
    return 4 + 13 * max_rows + 4 * max_dels


def key_hash_host(key: bytes) -> int:
    """The device key hash, host side (must match the kernel's mixing)."""
    h = 0
    for i, byte in enumerate(key[:KEY_HASH_BYTES]):
        h = (h + byte * pow(31, i, 1 << 32)) & 0xFFFFFFFF
    h ^= (len(key) * 2654435761) & 0xFFFFFFFF
    return h & 0x7FFFFFFF


def client_hash_host(client: int) -> int:
    """Hash of a client id's varint wire bytes — how the device refers to
    ids beyond i32 (real Yjs clients are random 53-bit). Must match the
    kernel's in-window mixing; results live in [0, 2^30)."""
    h = 0
    i = 0
    v = client
    while True:
        byte = v & 0x7F
        v >>= 7
        if v:
            byte |= 0x80
        h = (h + byte * pow(31, i, 1 << 32)) & 0xFFFFFFFF
        i += 1
        if not v:
            break
    h ^= (i * 2654435761) & 0xFFFFFFFF
    return h & 0x3FFFFFFF


def exact_steps(
    n_client_sections: int,
    n_item_blocks: int,
    n_skip_gc_blocks: int,
    n_ds_sections: int,
    n_del_ranges: int,
    n_value_steps: int = 0,
) -> int:
    """Step budget for one update whose wire-section counts are known
    (native pre-scan): item blocks cost ≤ 10 fields, GC/Skip blocks 2,
    each client section 3 (n_blocks/client/clock), each ds section 2
    (client/n_ranges), each range 2 (clock/len), + 2 frame headers.
    ``n_value_steps`` covers value-list content: one step per Any/Json
    value plus one for a Format key."""
    return (
        2
        + 3 * n_client_sections
        + 10 * n_item_blocks
        + 2 * n_skip_gc_blocks
        + 2 * n_ds_sections
        + 2 * n_del_ranges
        + n_value_steps
    )


def steps_for_columns(cols) -> int:
    """Exact decode step budget for one update from its native pre-scan
    (`ytpu.native.NativeColumns`) — the single cost model shared by the
    ingest fast lane and the full-trace replay planner."""
    import numpy as np

    n_skip_gc = int(np.count_nonzero((cols.kind == 10) | (cols.kind == 0)))
    return exact_steps(
        cols.n_client_sections,
        cols.n_blocks - n_skip_gc + cols.n_zero_len_blocks,
        n_skip_gc,
        cols.n_ds_sections,
        cols.n_dels,
        getattr(cols, "n_value_steps", 0),
    )


@jax.named_scope("decode_v1")
def decode_updates_v1(
    buf: jax.Array,
    lens: jax.Array,
    max_rows: int,
    max_dels: int,
    n_steps: Optional[int] = None,
    client_table: Optional[Tuple[jax.Array, jax.Array]] = None,
    max_sections: Optional[int] = None,
    key_table: Optional[Tuple[jax.Array, jax.Array]] = None,
    client_hash_table: Optional[Tuple[jax.Array, jax.Array]] = None,
    primary_root_hash: Optional[jax.Array] = None,
    packed: bool = False,
    lane_table: Optional[jax.Array] = None,
) -> Tuple[UpdateBatch, jax.Array]:
    """Decode S updates into an ``[S, U] / [S, R]`` UpdateBatch stream.

    Returns ``(stream, flags)``; lanes with ``flags & FLAG_ERRORS`` decoded
    incompletely and must be re-decoded on host (their emitted rows are
    marked invalid so a mixed batch stays safe to apply).

    ``packed`` (a static) hands the stream back as a `PackedBatch`, the
    27 planes stacked inside the program: three output buffers with the
    flags, not 28. The served step asks for it (`ingest._merge_fast_lane`);
    a caller that traces this body into a program of its own, or reads
    planes, does not.

    ``client_table=(sorted_ids, perm)`` maps raw client ids to interned
    indices on device (``perm[j]`` is the interned index of ``sorted_ids
    [j]``), so decoded streams can mix with host-encoded batches that use
    a `ClientInterner`. Lanes mentioning an id outside the table flag
    ``FLAG_UNKNOWN_CLIENT`` (host fallback interns it for the next step).

    ``key_table=(sorted_hashes, perm)`` maps parent_sub key hashes (see
    `key_hash_host`) to interned key indices, enabling map rows on
    device; the host pre-scan guarantees every key in the step is in the
    table and collision-free (collisions route to the host lane). Lanes
    with a map row but no table — or a hash miss — flag
    ``FLAG_UNKNOWN_KEY``.

    ``client_hash_table=(sorted_hashes, perm)`` resolves client ids
    beyond i32 (real Yjs ids are random 53-bit): the kernel hashes the
    id's varint bytes in-window (`client_hash_host`) and the table maps
    hash -> interned index. Without the table such lanes flag
    ``FLAG_BIG_CLIENT``; a miss flags ``FLAG_UNKNOWN_CLIENT``.

    ``max_sections`` bounds the client-section header (default ``max_rows
    + 1``). Wire-legal updates can carry more sections than emitted rows
    (e.g. sections holding only already-covered Skip runs); callers that
    pre-scan the wire (native columns) pass the real count so such
    updates don't trip the garbage-header guard. Pair it with an
    ``n_steps`` budget that covers the extra section fields
    (`exact_steps`).

    ``primary_root_hash`` ([S] i32, -1 = legacy single-root lane) enables
    multi-root decode (doc.rs:156-228): a named-root parent whose name
    hash equals the lane's primary maps to the implicit branch
    (``p_root == -1``); other names resolve through ``key_table`` to the
    anchor key id (miss -> FLAG_UNKNOWN_KEY, name beyond the hash
    window -> FLAG_UNSUPPORTED). Without it every named root aliases to
    the primary branch — the pre-multi-root behavior.

    ``lane_table`` (``[LANE_FIELDS, S]`` i32, in place of ``lens`` and
    ``primary_root_hash``, which are then None) is the served step's: its
    rows `LANE_LEN` and `LANE_ROOT_HASH` are read here, inside the traced
    body, so the step hands over a device array its first program made
    and no host array crosses with the call.
    """
    if lane_table is not None:
        lens = lane_table[LANE_LEN]
        primary_root_hash = lane_table[LANE_ROOT_HASH]
    S, L = buf.shape
    U, R = max_rows, max_dels
    T = n_steps or default_steps(U, R)
    max_sec = max_sections if max_sections is not None else U + 1
    b = buf.astype(I32)
    lens = lens.astype(I32)

    # UTF-16 length prefix sums: a UTF-8 head byte (not 0b10xxxxxx) is one
    # code point; a 4-byte lead (>= 0xF0) is a surrogate pair, one extra.
    head = ((b & 0xC0) != 0x80).astype(I32)
    lead4 = (b >= 0xF0).astype(I32)
    zero = jnp.zeros((S, 1), I32)
    u16_psum = jnp.concatenate([zero, jnp.cumsum(head + lead4, axis=1)], axis=1)

    iota_u = jax.lax.broadcasted_iota(I32, (S, U), 1)
    iota_r = jax.lax.broadcasted_iota(I32, (S, R), 1)
    row_ids = jnp.arange(S, dtype=I32)

    def u16_span(a, bnd):
        """UTF-16 code units of bytes [a, b) per lane."""
        a = jnp.clip(a, 0, L)
        bnd = jnp.clip(bnd, 0, L)
        pa = jnp.take_along_axis(u16_psum, a[:, None], axis=1)[:, 0]
        pb = jnp.take_along_axis(u16_psum, bnd[:, None], axis=1)[:, 0]
        return pb - pa

    def init_carry():
        regs = dict(
            pos=jnp.zeros((S,), I32),
            st=jnp.full((S,), ST_NCLIENTS, I32),
            flags=jnp.zeros((S,), I32),
            clients_left=jnp.zeros((S,), I32),
            blocks_left=jnp.zeros((S,), I32),
            client=jnp.zeros((S,), I32),
            clock=jnp.zeros((S,), I32),
            info=jnp.zeros((S,), I32),
            oc=jnp.full((S,), -1, I32),
            ok=jnp.zeros((S,), I32),
            rc=jnp.full((S,), -1, I32),
            rk=jnp.zeros((S,), I32),
            ptag=jnp.zeros((S,), I32),
            pc=jnp.full((S,), -1, I32),
            pk=jnp.zeros((S,), I32),
            ds_clients_left=jnp.zeros((S,), I32),
            ds_ranges_left=jnp.zeros((S,), I32),
            ds_client=jnp.zeros((S,), I32),
            ds_clock=jnp.zeros((S,), I32),
            n_rows=jnp.zeros((S,), I32),
            n_dels=jnp.zeros((S,), I32),
            keyh=jnp.full((S,), -1, I32),  # parent_sub hash (-1 = none)
            rooth=jnp.full((S,), -1, I32),  # root parent name hash (-1 =
            # not a named-root parent; -2 = name beyond the hash window)
            vals_left=jnp.zeros((S,), I32),  # Any/Json values remaining
            vals_n=jnp.zeros((S,), I32),  # total value count (clock len)
            cref=jnp.full((S,), -1, I32),  # content span start byte
            mpairs=jnp.zeros((S,), I32),  # depth-1 object pairs remaining
            mvf=jnp.zeros((S,), I32),  # ContentMove flags
            msc=jnp.full((S,), -1, I32),
            msk=jnp.zeros((S,), I32),
            mec=jnp.full((S,), -1, I32),
        )
        rows = dict(
            client=jnp.zeros((S, U), I32),
            clock=jnp.zeros((S, U), I32),
            length=jnp.zeros((S, U), I32),
            oc=jnp.full((S, U), -1, I32),
            ok=jnp.zeros((S, U), I32),
            rc=jnp.full((S, U), -1, I32),
            rk=jnp.zeros((S, U), I32),
            kind=jnp.zeros((S, U), I32),
            ref=jnp.full((S, U), -1, I32),
            ptag=jnp.zeros((S, U), I32),
            pc=jnp.full((S, U), -1, I32),
            pk=jnp.zeros((S, U), I32),
            keyh=jnp.full((S, U), -1, I32),
            rooth=jnp.full((S, U), -1, I32),
            msc=jnp.full((S, U), -1, I32),
            msk=jnp.zeros((S, U), I32),
            msa=jnp.zeros((S, U), I32),
            mec=jnp.full((S, U), -1, I32),
            mek=jnp.zeros((S, U), I32),
            mea=jnp.zeros((S, U), I32),
            mprio=jnp.full((S, U), -1, I32),
            valid=jnp.zeros((S, U), bool),
        )
        dels = dict(
            client=jnp.zeros((S, R), I32),
            start=jnp.zeros((S, R), I32),
            end=jnp.zeros((S, R), I32),
            valid=jnp.zeros((S, R), bool),
        )
        return regs, rows, dels

    def step(_, carry):
        regs, rows, dels = carry
        pos, st = regs["pos"], regs["st"]
        active = (st != ST_DONE) & (st != ST_ERR)

        # --- one varint (or u8) at the cursor, all lanes at once ---------
        idx = jnp.clip(pos[:, None] + jnp.arange(10, dtype=I32)[None, :], 0, L - 1)
        in_buf = (pos[:, None] + jnp.arange(10, dtype=I32)[None, :]) < lens[:, None]
        bytes10 = jnp.where(in_buf, jnp.take_along_axis(b, idx, axis=1), 0)
        cont = bytes10 >= 0x80
        inb = jnp.concatenate(
            [jnp.ones((S, 1), I32), jnp.cumprod(cont[:, :9].astype(I32), axis=1)],
            axis=1,
        )  # inb[:, i] = byte i belongs to the varint
        nbytes = jnp.sum(inb, axis=1)
        shifts = (7 * jnp.arange(5, dtype=I32))[None, :]
        val = jnp.sum(
            jnp.where(
                inb[:, :5] == 1,
                (bytes10[:, :5].astype(U32) & 0x7F) << shifts.astype(U32),
                jnp.zeros((S, 5), U32),
            ),
            axis=1,
        ).astype(I32)
        ovf = (nbytes > 5) | ((nbytes == 5) & ((bytes10[:, 4] & 0x7F) >= 8))

        is_info = st == ST_INFO
        # the TypeRef tag is a raw u8 (EncoderV1.write_type_ref), like info
        is_u8 = is_info | (st == ST_TYPE_TAG)
        v = jnp.where(is_u8, bytes10[:, 0], val)
        consumed = jnp.where(is_u8, 1, nbytes)

        # string states consume the payload bytes too
        is_str_skip = (
            (st == ST_PARENT_NAME)
            | (st == ST_PARENT_SUB)
            | (st == ST_JSON_VAL)
            | (st == ST_FMT_KEY)
            | (st == ST_FMT_VAL)  # format values are JSON strings on wire
            | (st == ST_SPAN1)
            | (st == ST_TYPE_NAME)  # XmlElement/XmlHook branch name
            | (st == ST_ANY_MKEY)  # map-value keys: plain strings, no tag
        )
        is_str = st == ST_STR
        str_start = pos + nbytes
        consumed = consumed + jnp.where(is_str_skip | is_str, v, 0)

        # --- one lib0 Any value (ST_ANY_VAL / ST_FMT_VAL): tag byte at
        # pos, then a tag-dependent payload. A second varint extraction
        # over the window shifted by one covers int/string/buffer tags.
        is_any_val = st == ST_ANY_VAL
        is_any_mval = st == ST_ANY_MVAL
        tag = bytes10[:, 0]
        cont2 = bytes10[:, 1:] >= 0x80
        inb2 = jnp.concatenate(
            [jnp.ones((S, 1), I32), jnp.cumprod(cont2[:, :8].astype(I32), axis=1)],
            axis=1,
        )
        nb2 = jnp.sum(inb2, axis=1)
        val2 = jnp.sum(
            jnp.where(
                inb2[:, :5] == 1,
                (bytes10[:, 1:6].astype(U32) & 0x7F) << shifts.astype(U32),
                jnp.zeros((S, 5), U32),
            ),
            axis=1,
        ).astype(I32)
        any_extra = jnp.where(
            (tag == 127) | (tag == 126) | (tag == 121) | (tag == 120),
            0,
            jnp.where(
                tag == 125,  # integer: signed varint
                nb2,
                jnp.where(
                    tag == 124,  # float32
                    4,
                    jnp.where(
                        (tag == 123) | (tag == 122),  # float64 / bigint
                        8,
                        jnp.where(
                            (tag == 119) | (tag == 116),  # string / buffer
                            nb2 + val2,
                            jnp.where(
                                # array / depth-1 object header: tag + count
                                (tag == 117) | (tag == 118),
                                nb2,
                                0,
                            ),
                        ),
                    ),
                ),
            ),
        )
        # unknown tags — and non-scalar values INSIDE an object (depth-1
        # support) — fall back to the host lane; arrays and depth-1
        # objects are header tokens whose children step individually
        any_bad_tag = (is_any_val & (tag < 116)) | (
            is_any_mval & ((tag == 117) | (tag == 118) | (tag < 116))
        )
        consumed = jnp.where(is_any_val | is_any_mval, 1 + any_extra, consumed)

        # --- parent_sub key hash (device map rows): mix the key bytes so
        # the host-built (hash -> interned key) table resolves them
        kh_idx = jnp.clip(
            str_start[:, None] + jnp.arange(KEY_HASH_BYTES, dtype=I32)[None, :],
            0,
            L - 1,
        )
        kh_bytes = jnp.take_along_axis(b, kh_idx, axis=1).astype(U32)
        kh_mask = jnp.arange(KEY_HASH_BYTES, dtype=I32)[None, :] < v[:, None]
        pow31 = jnp.asarray(
            np.array(
                [pow(31, i, 1 << 32) for i in range(KEY_HASH_BYTES)],
                dtype=np.uint32,
            )
        )
        khash = jnp.sum(
            jnp.where(kh_mask, kh_bytes * pow31[None, :], 0).astype(U32), axis=1
        )
        khash = (
            (khash ^ (v.astype(U32) * jnp.uint32(2654435761)))
            & jnp.uint32(0x7FFFFFFF)
        ).astype(I32)
        key_too_long = (st == ST_PARENT_SUB) & (v > KEY_HASH_BYTES)

        pos_after = pos + consumed
        is_client_st = (
            (st == ST_CLIENT) | (st == ST_ORIGIN_C) | (st == ST_ROR_C)
            | (st == ST_PARENT_ID_C) | (st == ST_DS_CLIENT)
            | (st == ST_MV_SC) | (st == ST_MV_EC)
        )
        # client ids beyond i32 (ovf at a client state) are represented by
        # a hash of their varint bytes, encoded as -2 - hash (< -1); the
        # post-loop table lookup resolves them to interned indices
        cmask = jnp.arange(10, dtype=I32)[None, :] < nbytes[:, None]
        pow31_10 = jnp.asarray(
            np.array([pow(31, i, 1 << 32) for i in range(10)], dtype=np.uint32)
        )
        chash = jnp.sum(
            jnp.where(cmask, bytes10.astype(U32) * pow31_10[None, :], 0).astype(
                U32
            ),
            axis=1,
        )
        chash = (
            (chash ^ (nbytes.astype(U32) * jnp.uint32(2654435761)))
            & jnp.uint32(0x3FFFFFFF)
        ).astype(I32)
        vc = jnp.where(is_client_st & ovf, -2 - chash, v)
        bad = active & (
            (pos_after > lens)
            # a string length > L would wrap `pos + v` past int32 and slip
            # under the pos_after bound; no real payload exceeds its buffer
            | ((is_str_skip | is_str) & (v > L))
            | ((is_any_val | is_any_mval)
               & ((tag == 119) | (tag == 116))
               & (val2 > L))
            | (ovf & ~is_u8 & ~is_client_st & ~is_any_val & ~is_any_mval)
            | ((st == ST_NCLIENTS) & (v > max_sec))  # absurd header: garbage
        )
        act = active & ~bad

        def on(s):
            return act & (st == s)

        def upd(reg, cond, new):
            return jnp.where(cond, new, reg)

        # --- end-of-block / end-of-ds-range shared bookkeeping -----------
        # one token consumed per value step; an array header enqueues its
        # children onto the counter; a depth-1 object header suspends the
        # counter until its last pair's value lands (ST_ANY_MVAL)
        any_children = jnp.where((st == ST_ANY_VAL) & (tag == 117), val2, 0)
        map_open = on(ST_ANY_VAL) & (tag == 118) & (val2 > 0)
        mpairs2 = upd(regs["mpairs"], on(ST_ANY_MVAL), regs["mpairs"] - 1)
        map_done = on(ST_ANY_MVAL) & (mpairs2 == 0)
        vals_dec = (on(ST_ANY_VAL) & ~map_open) | on(ST_JSON_VAL) | map_done
        vals_left2 = upd(
            regs["vals_left"],
            vals_dec,
            regs["vals_left"] - 1 + any_children,
        )
        # states that finish a block this step (zero-count value lists
        # finish immediately and emit nothing)
        empty_list = (on(ST_ANY_COUNT) | on(ST_JSON_COUNT)) & (v == 0)
        list_done = vals_dec & (vals_left2 == 0)
        # TypeRef tags 3/5 (XmlElement/XmlHook) carry a name string; 7
        # (WeakRef: host-resolved link source) and unknown tags flag
        type_named = on(ST_TYPE_TAG) & ((v == 3) | (v == 5))
        type_done = (on(ST_TYPE_TAG) & ~type_named) | on(ST_TYPE_NAME)
        # a collapsed move (flags bit 0) ends at its start clock
        mv_collapsed = (regs["mvf"] & 1) != 0
        move_done = (on(ST_MV_SK) & mv_collapsed) | on(ST_MV_EK)
        emit_row_st = (
            on(ST_DEL_LEN)
            | on(ST_GC_LEN)
            | on(ST_SKIP_LEN)
            | on(ST_STR)
            | list_done
            | on(ST_SPAN1)
            | on(ST_FMT_VAL)
            | type_done
            | move_done
        )
        str_len16 = u16_span(str_start, str_start + v)
        is_list_done = list_done
        blk_len = jnp.where(
            is_str,
            str_len16,
            jnp.where(
                is_list_done,
                regs["vals_n"],
                jnp.where(
                    on(ST_SPAN1) | on(ST_FMT_VAL) | type_done | move_done,
                    1,
                    v,
                ),
            ),
        )
        block_end = emit_row_st | empty_list
        blocks_left2 = upd(regs["blocks_left"], block_end, regs["blocks_left"] - 1)
        # a client section with zero blocks (never produced by our encoders,
        # but legal wire) also closes at ST_CLOCK
        empty_client = on(ST_CLOCK) & (regs["blocks_left"] == 0)
        client_done = (block_end & (blocks_left2 == 0)) | empty_client
        clients_left2 = upd(regs["clients_left"], client_done, regs["clients_left"] - 1)
        after_block = jnp.where(
            blocks_left2 > 0,
            ST_INFO,
            jnp.where(clients_left2 > 0, ST_NBLOCKS, ST_DS_NCLIENTS),
        )

        ds_done_range = on(ST_DS_LEN)
        ds_ranges_left2 = upd(
            regs["ds_ranges_left"], ds_done_range, regs["ds_ranges_left"] - 1
        )
        # DS_NRANGES with 0 ranges also closes the ds-client section
        ds_client_done = (ds_done_range & (ds_ranges_left2 == 0)) | (
            on(ST_DS_NRANGES) & (v == 0)
        )
        ds_clients_left2 = upd(
            regs["ds_clients_left"], ds_client_done, regs["ds_clients_left"] - 1
        )
        after_ds_range = jnp.where(
            ds_ranges_left2 > 0,
            ST_DS_CLOCK,
            jnp.where(ds_clients_left2 > 0, ST_DS_CLIENT, ST_DONE),
        )

        # --- content dispatch after the last pre-content field -----------
        kind4 = regs["info"] & 0b1111
        content_st = jnp.where(
            kind4 == CONTENT_DELETED,
            ST_DEL_LEN,
            jnp.where(
                kind4 == CONTENT_STRING,
                ST_STR,
                jnp.where(
                    kind4 == CONTENT_ANY,
                    ST_ANY_COUNT,
                    jnp.where(
                        kind4 == CONTENT_JSON,
                        ST_JSON_COUNT,
                        jnp.where(
                            (kind4 == CONTENT_EMBED) | (kind4 == CONTENT_BINARY),
                            ST_SPAN1,
                            jnp.where(
                                kind4 == CONTENT_FORMAT,
                                ST_FMT_KEY,
                                jnp.where(
                                    kind4 == CONTENT_TYPE,
                                    ST_TYPE_TAG,
                                    jnp.where(
                                        kind4 == CONTENT_MOVE,
                                        ST_MV_FLAGS,
                                        ST_ERR,
                                    ),
                                ),
                            ),
                        ),
                    ),
                ),
            ),
        )
        content_unsupported = content_st == ST_ERR
        has_psub = ((regs["info"] & 0xC0) == 0) & ((regs["info"] & 0x20) != 0)
        after_parent = jnp.where(has_psub, ST_PARENT_SUB, content_st)

        # --- next state -----------------------------------------------------
        nclients_hdr = on(ST_NCLIENTS)
        info_gc = on(ST_INFO) & (v == BLOCK_GC)
        info_skip = on(ST_INFO) & (v == BLOCK_SKIP)
        info_item = on(ST_INFO) & ~info_gc & ~info_skip
        item_next = jnp.where(
            (v & 0x80) != 0,
            ST_ORIGIN_C,
            jnp.where((v & 0x40) != 0, ST_ROR_C, ST_PARENT_INFO),
        )

        st2 = st
        st2 = upd(st2, nclients_hdr, jnp.where(v > 0, ST_NBLOCKS, ST_DS_NCLIENTS))
        st2 = upd(st2, on(ST_NBLOCKS), ST_CLIENT)
        st2 = upd(st2, on(ST_CLIENT), ST_CLOCK)
        st2 = upd(
            st2,
            on(ST_CLOCK),
            jnp.where(
                regs["blocks_left"] > 0,
                ST_INFO,
                jnp.where(clients_left2 > 0, ST_NBLOCKS, ST_DS_NCLIENTS),
            ),
        )
        st2 = upd(st2, info_gc, ST_GC_LEN)
        st2 = upd(st2, info_skip, ST_SKIP_LEN)
        st2 = upd(st2, info_item, item_next)
        st2 = upd(st2, on(ST_ORIGIN_C), ST_ORIGIN_K)
        st2 = upd(
            st2,
            on(ST_ORIGIN_K),
            jnp.where((regs["info"] & 0x40) != 0, ST_ROR_C, content_st),
        )
        st2 = upd(st2, on(ST_ROR_C), ST_ROR_K)
        st2 = upd(st2, on(ST_ROR_K), content_st)
        st2 = upd(
            st2, on(ST_PARENT_INFO), jnp.where(v == 1, ST_PARENT_NAME, ST_PARENT_ID_C)
        )
        st2 = upd(st2, on(ST_PARENT_NAME), after_parent)
        st2 = upd(st2, on(ST_PARENT_ID_C), ST_PARENT_ID_K)
        st2 = upd(st2, on(ST_PARENT_ID_K), after_parent)
        st2 = upd(st2, on(ST_PARENT_SUB), content_st)
        st2 = upd(st2, on(ST_ANY_COUNT) & (v > 0), ST_ANY_VAL)
        st2 = upd(st2, map_open, ST_ANY_MKEY)
        st2 = upd(st2, on(ST_ANY_MKEY), ST_ANY_MVAL)
        st2 = upd(st2, on(ST_ANY_MVAL) & ~map_done, ST_ANY_MKEY)
        st2 = upd(
            st2, map_done & (vals_left2 > 0), ST_ANY_VAL
        )
        st2 = upd(st2, on(ST_JSON_COUNT) & (v > 0), ST_JSON_VAL)
        st2 = upd(st2, on(ST_FMT_KEY), ST_FMT_VAL)
        st2 = upd(st2, type_named, ST_TYPE_NAME)
        st2 = upd(st2, on(ST_MV_FLAGS), ST_MV_SC)
        st2 = upd(st2, on(ST_MV_SC), ST_MV_SK)
        st2 = upd(st2, on(ST_MV_SK) & ~mv_collapsed, ST_MV_EC)
        st2 = upd(st2, on(ST_MV_EC), ST_MV_EK)
        st2 = upd(st2, block_end, after_block)
        st2 = upd(st2, on(ST_DS_NCLIENTS), jnp.where(v > 0, ST_DS_CLIENT, ST_DONE))
        st2 = upd(st2, on(ST_DS_CLIENT), ST_DS_NRANGES)
        st2 = upd(
            st2,
            on(ST_DS_NRANGES),
            jnp.where(
                v > 0,
                ST_DS_CLOCK,
                jnp.where(ds_clients_left2 > 0, ST_DS_CLIENT, ST_DONE),
            ),
        )
        st2 = upd(st2, on(ST_DS_CLOCK), ST_DS_LEN)
        st2 = upd(st2, ds_done_range, after_ds_range)

        # unsupported content discovered at a dispatch point
        unsupported = (
            (on(ST_ORIGIN_K) & ((regs["info"] & 0x40) == 0) & content_unsupported)
            | (on(ST_ROR_K) & content_unsupported)
            | ((on(ST_PARENT_NAME) | on(ST_PARENT_ID_K)) & ~has_psub & content_unsupported)
            | (on(ST_PARENT_SUB) & content_unsupported)
            | (act & key_too_long)  # key exceeds the hash window
            | (act & any_bad_tag)  # recursive/unknown Any value
            # WeakRef branches (host-resolved link sources), Doc subtrees
            # and unknown TypeRef tags (valid device set: 0-6) stay on the
            # host lane
            | (on(ST_TYPE_TAG) & ((v == 7) | (v >= 8)))
        )
        # item with neither origin flag whose dispatch happens after parent
        st2 = upd(st2, unsupported, ST_ERR)
        st2 = upd(st2, bad, ST_ERR)

        # --- registers ------------------------------------------------------
        regs2 = dict(regs)
        regs2["pos"] = jnp.where(act, pos_after, pos)
        regs2["st"] = st2
        regs2["clients_left"] = upd(clients_left2, nclients_hdr, v)
        regs2["blocks_left"] = upd(blocks_left2, on(ST_NBLOCKS), v)
        regs2["client"] = upd(regs["client"], on(ST_CLIENT), vc)
        clock2 = upd(regs["clock"], on(ST_CLOCK), v)
        regs2["clock"] = upd(clock2, block_end, clock2 + blk_len)
        regs2["keyh"] = upd(
            upd(regs["keyh"], on(ST_INFO), -1), on(ST_PARENT_SUB), khash
        )
        # root-parent name hash (multi-root docs, doc.rs:156-228): khash is
        # computed from the CURRENT string's bytes, which at ST_PARENT_NAME
        # are the root name; names beyond the hash window mark -2 (resolved
        # lanes flag unsupported — legacy single-root callers ignore it)
        regs2["rooth"] = upd(
            upd(regs["rooth"], on(ST_INFO), -1),
            on(ST_PARENT_NAME),
            jnp.where(v <= KEY_HASH_BYTES, khash, -2),
        )
        count_st = on(ST_ANY_COUNT) | on(ST_JSON_COUNT)
        regs2["vals_n"] = upd(regs["vals_n"], count_st, v)
        regs2["vals_left"] = upd(vals_left2, count_st, v)
        regs2["cref"] = upd(
            regs["cref"], count_st | on(ST_FMT_KEY) | on(ST_TYPE_TAG), pos
        )
        regs2["info"] = upd(regs["info"], on(ST_INFO), v)
        # reset per-item registers when a new info byte arrives
        fresh = on(ST_INFO)
        regs2["oc"] = upd(upd(regs["oc"], fresh, -1), on(ST_ORIGIN_C), vc)
        regs2["ok"] = upd(upd(regs["ok"], fresh, 0), on(ST_ORIGIN_K), v)
        regs2["rc"] = upd(upd(regs["rc"], fresh, -1), on(ST_ROR_C), vc)
        regs2["rk"] = upd(upd(regs["rk"], fresh, 0), on(ST_ROR_K), v)
        ptag2 = upd(regs["ptag"], fresh, 0)
        regs2["ptag"] = upd(ptag2, on(ST_PARENT_INFO), jnp.where(v == 1, 1, 2))
        regs2["pc"] = upd(upd(regs["pc"], fresh, -1), on(ST_PARENT_ID_C), vc)
        regs2["pk"] = upd(upd(regs["pk"], fresh, 0), on(ST_PARENT_ID_K), v)
        regs2["ds_clients_left"] = upd(ds_clients_left2, on(ST_DS_NCLIENTS), v)
        regs2["ds_ranges_left"] = upd(ds_ranges_left2, on(ST_DS_NRANGES), v)
        regs2["ds_client"] = upd(regs["ds_client"], on(ST_DS_CLIENT), vc)
        regs2["ds_clock"] = upd(regs["ds_clock"], on(ST_DS_CLOCK), v)
        regs2["mpairs"] = upd(mpairs2, map_open, val2)
        regs2["mvf"] = upd(regs["mvf"], on(ST_MV_FLAGS), v)
        regs2["msc"] = upd(regs["msc"], on(ST_MV_SC), vc)
        regs2["msk"] = upd(regs["msk"], on(ST_MV_SK), v)
        regs2["mec"] = upd(regs["mec"], on(ST_MV_EC), vc)

        flags2 = (
            regs["flags"]
            | jnp.where(bad, FLAG_MALFORMED, 0)
            | jnp.where(unsupported, FLAG_UNSUPPORTED, 0)
            | jnp.where(nclients_hdr & (v > 1), FLAG_MULTI_CLIENT, 0)
        )

        # --- row / delete-range emission -----------------------------------
        emit = emit_row_st & ~on(ST_SKIP_LEN) & (blk_len > 0)
        row_ovf = emit & (regs["n_rows"] >= U)
        emit = emit & ~row_ovf
        oh = (iota_u == regs["n_rows"][:, None]) & emit[:, None]

        def put_row(name, vec):
            rows[name] = jnp.where(oh, vec[:, None], rows[name])

        is_gc_row = on(ST_GC_LEN)
        # the info register still holds the block's content kind for every
        # content-terminal state (Any/Json/Embed/Binary/Format/Deleted)
        row_kind = jnp.where(
            is_gc_row,
            BLOCK_GC,
            jnp.where(is_str, CONTENT_STRING, kind4),
        )
        row_ref = jnp.where(
            is_str,
            row_ids * L + str_start,
            jnp.where(
                is_list_done | on(ST_FMT_VAL) | on(ST_TYPE_NAME),
                row_ids * L + regs["cref"],
                jnp.where(
                    on(ST_SPAN1) | on(ST_TYPE_TAG),
                    row_ids * L + pos,
                    -1,
                ),
            ),
        )
        put_row("client", regs["client"])
        put_row("clock", regs["clock"])
        put_row("length", blk_len)
        put_row("oc", jnp.where(is_gc_row, -1, regs["oc"]))
        put_row("ok", jnp.where(is_gc_row, 0, regs["ok"]))
        put_row("rc", jnp.where(is_gc_row, -1, regs["rc"]))
        put_row("rk", jnp.where(is_gc_row, 0, regs["rk"]))
        put_row("kind", row_kind)
        put_row("ref", row_ref)
        put_row("ptag", jnp.where(is_gc_row, 0, regs["ptag"]))
        put_row("pc", jnp.where(is_gc_row, -1, regs["pc"]))
        put_row("pk", jnp.where(is_gc_row, 0, regs["pk"]))
        put_row("keyh", jnp.where(is_gc_row, -1, regs["keyh"]))
        put_row("rooth", jnp.where(is_gc_row, -1, regs["rooth"]))
        # ContentMove range fields (moving.rs:189-215 flag layout): assoc
        # columns use the engine convention 0 = After, -1 = Before; a
        # collapsed move's end is its start; end clock is the CURRENT
        # varint at ST_MV_EK (registers update after emission)
        is_move_emit = move_done
        mvf = regs["mvf"]
        msa = jnp.where((mvf & 2) != 0, 0, -1)
        mea = jnp.where((mvf & 4) != 0, 0, -1)
        # the CURRENT varint is the start clock when emitting collapsed at
        # ST_MV_SK, and the end clock at ST_MV_EK (registers update after
        # emission); the end id of a collapsed move is its start id
        msk_cur = jnp.where(on(ST_MV_SK), v, regs["msk"])
        mv_end_c = jnp.where(mv_collapsed, regs["msc"], regs["mec"])
        put_row("msc", jnp.where(is_move_emit, regs["msc"], -1))
        put_row("msk", jnp.where(is_move_emit, msk_cur, 0))
        put_row("msa", jnp.where(is_move_emit, msa, 0))
        put_row("mec", jnp.where(is_move_emit, mv_end_c, -1))
        put_row("mek", jnp.where(is_move_emit, v, 0))
        put_row("mea", jnp.where(is_move_emit, mea, 0))
        put_row("mprio", jnp.where(is_move_emit, mvf >> 6, -1))
        rows["valid"] = rows["valid"] | oh
        regs2["n_rows"] = regs["n_rows"] + emit.astype(I32)

        emit_d = ds_done_range & (v > 0)
        del_ovf = emit_d & (regs["n_dels"] >= R)
        emit_d = emit_d & ~del_ovf
        ohd = (iota_r == regs["n_dels"][:, None]) & emit_d[:, None]
        dels["client"] = jnp.where(ohd, regs["ds_client"][:, None], dels["client"])
        dels["start"] = jnp.where(ohd, regs["ds_clock"][:, None], dels["start"])
        dels["end"] = jnp.where(
            ohd, (regs["ds_clock"] + v)[:, None], dels["end"]
        )
        dels["valid"] = dels["valid"] | ohd
        regs2["n_dels"] = regs["n_dels"] + emit_d.astype(I32)

        regs2["flags"] = flags2 | jnp.where(row_ovf | del_ovf, FLAG_OVERFLOW, 0)
        return regs2, rows, dels

    regs, rows, dels = jax.lax.fori_loop(0, T, step, init_carry())
    flags = regs["flags"] | jnp.where(regs["st"] != ST_DONE, FLAG_MALFORMED, 0)

    stream, flags = _resolve_and_pack(
        rows, dels, flags, client_table, key_table, client_hash_table,
        primary_root_hash,
    )
    return (pack_batch(stream) if packed else stream), flags


def _resolve_and_pack(
    rows, dels, flags, client_table, key_table, client_hash_table,
    primary_root_hash=None,
):
    """Shared post-decode pass for the V1 and V2 device lanes: raw client
    ids -> interned indices (`client_table`), big-client hash entries ->
    indices (`client_hash_table`), parent_sub hashes -> key indices
    (`key_table`), error-lane row invalidation, and UpdateBatch packing."""
    S, U = rows["client"].shape
    R = dels["client"].shape[1]
    if client_table is not None:
        sorted_ids, perm = client_table
        K = sorted_ids.shape[0]
        if K == 0:
            # empty raw table: only lanes using RAW (>= 0) ids are unknown
            # — hashed big-client entries (<= -2) resolve below
            raw_used = jnp.zeros((S,), bool)
            for name, used in (
                ("client", rows["valid"]),
                ("oc", rows["valid"]),
                ("rc", rows["valid"]),
                ("pc", rows["valid"]),
                ("msc", rows["valid"]),
                ("mec", rows["valid"]),
            ):
                if name not in rows:
                    continue
                raw_used = raw_used | jnp.any(used & (rows[name] >= 0), axis=1)
            raw_used = raw_used | jnp.any(
                dels["valid"] & (dels["client"] >= 0), axis=1
            )
            flags = flags | jnp.where(raw_used, FLAG_UNKNOWN_CLIENT, 0)
            client_table = None

    if client_table is not None:

        def map_ids(arr, used):
            j = jnp.clip(jnp.searchsorted(sorted_ids, arr), 0, max(K - 1, 0))
            hit = (sorted_ids[j] == arr) & (arr >= 0)
            unknown = used & (arr >= 0) & ~hit
            # hashed big-client entries (<= -2) pass through to the hash
            # resolution below
            out = jnp.where(hit, perm[j], jnp.where(arr <= -2, arr, -1))
            return out, jnp.any(unknown, axis=1)

        unk = jnp.zeros((S,), bool)
        for name, used in (
            ("client", rows["valid"]),
            ("oc", rows["valid"]),
            ("rc", rows["valid"]),
            ("pc", rows["valid"]),
            ("msc", rows["valid"]),
            ("mec", rows["valid"]),
        ):
            if name not in rows:
                continue
            rows[name], u = map_ids(rows[name], used)
            unk = unk | u
        dels["client"], u = map_ids(dels["client"], dels["valid"])
        unk = unk | u
        flags = flags | jnp.where(unk, FLAG_UNKNOWN_CLIENT, 0)

    # big-client hash entries -> interned indices (client_hash_table), or
    # FLAG_BIG_CLIENT when no table can resolve them
    cht = client_hash_table
    if cht is not None and cht[0].shape[0] == 0:
        cht = None

    def map_hashed(arr, used):
        hashed = arr <= -2
        if cht is None:
            return arr, jnp.any(used & hashed, axis=1), jnp.zeros((S,), bool)
        hh, hperm = cht
        KH = hh.shape[0]
        hv = -2 - arr
        j = jnp.clip(jnp.searchsorted(hh, hv), 0, KH - 1)
        hit = hashed & (hh[j] == hv)
        out = jnp.where(hit, hperm[j], arr)
        miss = jnp.any(used & hashed & ~hit, axis=1)
        return out, jnp.zeros((S,), bool), miss

    bigf = jnp.zeros((S,), bool)
    unkh = jnp.zeros((S,), bool)
    for name, used in (
        ("client", rows["valid"]),
        ("oc", rows["valid"]),
        ("rc", rows["valid"]),
        ("pc", rows["valid"]),
        ("msc", rows["valid"]),
        ("mec", rows["valid"]),
    ):
        if name not in rows:
            continue
        rows[name], b, m = map_hashed(rows[name], used)
        bigf = bigf | b
        unkh = unkh | m
    dels["client"], b, m = map_hashed(dels["client"], dels["valid"])
    bigf = bigf | b
    unkh = unkh | m
    flags = (
        flags
        | jnp.where(bigf, FLAG_BIG_CLIENT, 0)
        | jnp.where(unkh, FLAG_UNKNOWN_CLIENT, 0)
    )

    # parent_sub key hashes -> interned key indices (map rows on device)
    has_key = rows["valid"] & (rows["keyh"] >= 0)
    key_col = jnp.full((S, U), -1, I32)
    key_miss = has_key
    if key_table is not None:
        khashes, kperm = key_table
        K2 = khashes.shape[0]
        if K2 > 0:
            kj = jnp.clip(jnp.searchsorted(khashes, rows["keyh"]), 0, K2 - 1)
            khit = has_key & (khashes[kj] == rows["keyh"])
            key_col = jnp.where(khit, kperm[kj], -1)
            key_miss = has_key & ~khit
    flags = flags | jnp.where(
        jnp.any(key_miss, axis=1), FLAG_UNKNOWN_KEY, 0
    )

    # named-root parents (multi-root docs): the lane's primary root name
    # maps to the implicit branch (p_root -1); other names resolve through
    # the same key table to their anchor's key id
    rooth = rows.get("rooth")
    p_root_col = jnp.full((S, U), -1, I32)
    if rooth is not None and primary_root_hash is not None:
        prim = primary_root_hash[:, None]
        named = rows["valid"] & (rows["ptag"] == 1) & (prim >= 0)
        nonprim = named & (rooth >= 0) & (rooth != prim)
        root_long = named & (rooth == -2)
        root_miss = nonprim
        if key_table is not None and key_table[0].shape[0] > 0:
            rhashes, rperm = key_table
            rj = jnp.clip(
                jnp.searchsorted(rhashes, rooth), 0, rhashes.shape[0] - 1
            )
            rhit = nonprim & (rhashes[rj] == rooth)
            p_root_col = jnp.where(rhit, rperm[rj], -1)
            root_miss = nonprim & ~rhit
        flags = (
            flags
            | jnp.where(jnp.any(root_miss, axis=1), FLAG_UNKNOWN_KEY, 0)
            | jnp.where(jnp.any(root_long, axis=1), FLAG_UNSUPPORTED, 0)
        )

    # lanes that errored out must not contribute partial rows
    lane_ok = (flags & FLAG_ERRORS) == 0
    valid = rows["valid"] & lane_ok[:, None]
    dvalid = dels["valid"] & lane_ok[:, None]
    z_u = jnp.zeros((S, U), I32)
    neg_u = jnp.full((S, U), -1, I32)
    stream = UpdateBatch(
        client=rows["client"],
        clock=rows["clock"],
        length=rows["length"],
        origin_client=rows["oc"],
        origin_clock=rows["ok"],
        ror_client=rows["rc"],
        ror_clock=rows["rk"],
        kind=rows["kind"],
        content_ref=rows["ref"],
        content_off=z_u,
        key=key_col,
        p_tag=rows["ptag"],
        p_client=rows["pc"],
        p_clock=rows["pk"],
        p_root=p_root_col,
        mv_sc=rows.get("msc", neg_u),
        mv_sk=rows.get("msk", z_u),
        mv_sa=rows.get("msa", z_u),
        mv_ec=rows.get("mec", neg_u),
        mv_ek=rows.get("mek", z_u),
        mv_ea=rows.get("mea", z_u),
        mv_prio=rows.get("mprio", neg_u),
        valid=valid,
        del_client=dels["client"],
        del_start=dels["start"],
        del_end=dels["end"],
        del_valid=dvalid,
    )
    return stream, flags


def utf8_slice_u16(buf: np.ndarray, start: int, off: int, length: int) -> str:
    """Slice ``length`` UTF-16 units at unit-offset ``off`` from the UTF-8
    string starting at byte ``start`` of ``buf``.

    Offsets landing inside a surrogate pair render the severed half as
    U+FFFD — exact `split_str_utf16` / SplittableString parity
    (block.rs:1386-1502, :1852-1860).
    """
    i = int(start)

    def unit_at(i):
        b0 = buf[i]
        if b0 < 0x80:
            return 1, 1
        if b0 < 0xE0:
            return 2, 1
        if b0 < 0xF0:
            return 3, 1
        return 4, 2

    out = []
    u = 0
    while u < off:
        nb, nu = unit_at(i)
        i += nb
        u += nu
    need = length
    if u > off:
        # the slice starts inside a surrogate pair: its severed low
        # half renders as U+FFFD
        out.append("�")
        need -= u - off
    s = i
    while need > 0:
        nb, nu = unit_at(i)
        if nu > need:
            # ends inside a pair: severed high half renders as U+FFFD
            out.append(bytes(buf[s:i]).decode("utf-8", errors="surrogatepass"))
            out.append("�")
            return "".join(out)
        i += nb
        need -= nu
    out.append(bytes(buf[s:i]).decode("utf-8", errors="surrogatepass"))
    return "".join(out)


def _wire_any_values(flat: np.ndarray, start: int, off: int, length: int) -> list:
    """ContentAny at wire offset `start`: count varint then Any values."""
    from ytpu.encoding.lib0 import Cursor, read_any

    cur = Cursor(bytes(flat[start:]))
    n = cur.read_var_uint()
    out = []
    for i in range(min(n, off + length)):
        v = read_any(cur)
        if i >= off:
            out.append(v)
    return out


def _wire_any_values_countless(
    flat: np.ndarray, start: int, off: int, length: int
) -> list:
    """V2-lane ContentAny span: values start AT `start` (the count lives in
    the len column — the caller's `off + length` bounds the read)."""
    from ytpu.encoding.lib0 import Cursor, read_any

    cur = Cursor(bytes(flat[start:]))
    out = []
    for i in range(off + length):
        v = read_any(cur)
        if i >= off:
            out.append(v)
    return out


def _wire_json_values(flat: np.ndarray, start: int, off: int, length: int) -> list:
    """ContentJson at `start`: count then JSON strings (parsed, None on
    parse failure — ContentJSON.values parity)."""
    import json as _json

    from ytpu.encoding.lib0 import Cursor

    cur = Cursor(bytes(flat[start:]))
    n = cur.read_var_uint()
    out = []
    for i in range(min(n, off + length)):
        s = cur.read_string()
        if i >= off:
            try:
                out.append(_json.loads(s))
            except (ValueError, TypeError):
                out.append(None)
    return out


def _wire_json_raw(flat: np.ndarray, start: int, off: int, length: int) -> list:
    """ContentJson raw strings (re-encode path: byte-exact round trips)."""
    from ytpu.encoding.lib0 import Cursor

    cur = Cursor(bytes(flat[start:]))
    n = cur.read_var_uint()
    out = []
    for i in range(min(n, off + length)):
        s = cur.read_string()
        if i >= off:
            out.append(s)
    return out


def _wire_embed_value(flat: np.ndarray, start: int):
    from ytpu.encoding.lib0 import Cursor, any_from_json

    return any_from_json(Cursor(bytes(flat[start:])).read_string())


def _wire_binary_value(flat: np.ndarray, start: int) -> bytes:
    from ytpu.encoding.lib0 import Cursor

    return Cursor(bytes(flat[start:])).read_buf()


def _wire_format_kv(flat: np.ndarray, start: int):
    from ytpu.encoding.lib0 import Cursor, any_from_json

    cur = Cursor(bytes(flat[start:]))
    key = cur.read_string()
    return key, any_from_json(cur.read_string())


def _wire_type_branch(flat: np.ndarray, start: int):
    """ContentType at wire offset `start`: TypeRef tag byte (+ name for
    XmlElement/XmlHook) → a Branch carrying just the rendering-relevant
    fields (branch.rs decode_type_ref; WeakRef never reaches here — the
    decoder flags it to the host lane)."""
    from ytpu.core.branch import Branch
    from ytpu.encoding.lib0 import Cursor

    cur = Cursor(bytes(flat[start:]))
    tag = cur.read_u8()
    if tag in (3, 5):  # TYPE_XML_ELEMENT / TYPE_XML_HOOK
        return Branch(tag, type_name=cur.read_string())
    return Branch(tag)


def _wire_type_raw(flat: np.ndarray, start: int) -> bytes:
    """The exact wire bytes of a ContentType payload (for re-emission by
    the encode finisher)."""
    from ytpu.encoding.lib0 import Cursor

    cur = Cursor(bytes(flat[start:]))
    tag = cur.read_u8()
    if tag in (3, 5):
        cur.read_buf()  # name
    return bytes(flat[start : start + cur.pos])


class RawPayloadView:
    """PayloadStore-shaped reader over the raw wire-byte matrix.

    Device-decoded rows address content payloads by ``ref = s * L +
    byte_start``. String refs point at the UTF-8 bytes with ``(off, len)``
    in UTF-16 code units; Any/Json refs at their count varint with
    ``(off, len)`` in values; Embed/Binary/Format refs at their span
    start.
    """

    def __init__(self, buf: np.ndarray, v2_any: bool = False):
        self.buf = np.ascontiguousarray(buf, dtype=np.uint8).reshape(-1)
        # V2-lane states: ContentAny refs point at the FIRST value byte
        # (the V2 wire keeps the element count in the len COLUMN, so the
        # span is count-less; the row's length is the count)
        self.v2_any = v2_any

    def slice_text(self, ref: int, off: int, length: int) -> str:
        return utf8_slice_u16(self.buf, int(ref), off, length)

    def slice_values(self, ref: int, off: int, length: int) -> list:
        if self.v2_any:
            return _wire_any_values_countless(self.buf, int(ref), off, length)
        return _wire_any_values(self.buf, int(ref), off, length)

    def json_values(self, ref: int, off: int, length: int) -> list:
        return _wire_json_values(self.buf, int(ref), off, length)

    def json_raw(self, ref: int, off: int, length: int) -> list:
        return _wire_json_raw(self.buf, int(ref), off, length)

    def embed_value(self, ref: int):
        return _wire_embed_value(self.buf, int(ref))

    def binary_value(self, ref: int) -> bytes:
        return _wire_binary_value(self.buf, int(ref))

    def format_kv(self, ref: int):
        return _wire_format_kv(self.buf, int(ref))

    def type_branch(self, ref: int):
        return _wire_type_branch(self.buf, int(ref))

    def type_raw(self, ref: int) -> bytes:
        return _wire_type_raw(self.buf, int(ref))


class ChunkedWirePayloads:
    """PayloadStore-compatible resolver over a host `PayloadStore` PLUS
    retained wire-byte chunks from device-decoded steps.

    Ref space: ``ref >= 0`` → the PayloadStore (host-encoded rows);
    ``ref <= -2`` → wire chunk byte offset ``-(ref + 2)`` (device-decoded
    rows; the ingestor rebases each step's ``s * L + start`` refs by the
    running total of retained bytes). ``-1`` stays "no payload".
    """

    def __init__(self, store):
        self.store = store
        self._chunks: List[Tuple[int, np.ndarray]] = []  # (base, flat bytes)
        self.total_bytes = 0
        # bumped whenever a chunk is dropped, so incremental consumers
        # (the native finisher's wire-buffer cache) know to resync
        self.generation = 0

    @property
    def items(self):
        return self.store.items

    def add_chunk(self, buf: np.ndarray) -> int:
        """Retain a step's byte matrix; returns the base offset its
        ``s * L + start`` refs must be rebased by."""
        flat = np.ascontiguousarray(buf, dtype=np.uint8).reshape(-1)
        base = self.total_bytes
        self._chunks.append((base, flat))
        self.total_bytes += flat.size
        return base

    def drop_if_unreferenced(self, base: int) -> None:
        """Release the most recent chunk (it turned out to hold no string
        refs — e.g. a delete-only step); only the latest can be dropped."""
        if self._chunks and self._chunks[-1][0] == base:
            self._chunks.pop()
            self.total_bytes = base
            self.generation += 1

    def _locate(self, ref: int) -> Tuple[np.ndarray, int]:
        off = -(int(ref) + 2)
        import bisect

        k = bisect.bisect_right([b for b, _ in self._chunks], off) - 1
        base, flat = self._chunks[k]
        return flat, off - base

    def slice_text(self, ref: int, off: int, length: int) -> str:
        if int(ref) >= 0:
            return self.store.slice_text(ref, off, length)
        flat, start = self._locate(ref)
        return utf8_slice_u16(flat, start, off, length)

    def slice_values(self, ref: int, off: int, length: int) -> list:
        if int(ref) >= 0:
            return self.store.slice_values(ref, off, length)
        flat, start = self._locate(ref)
        return _wire_any_values(flat, start, off, length)

    def json_values(self, ref: int, off: int, length: int) -> list:
        if int(ref) >= 0:
            return self.store.json_values(ref, off, length)
        flat, start = self._locate(ref)
        return _wire_json_values(flat, start, off, length)

    def json_raw(self, ref: int, off: int, length: int) -> list:
        if int(ref) >= 0:
            return self.store.json_raw(ref, off, length)
        flat, start = self._locate(ref)
        return _wire_json_raw(flat, start, off, length)

    def embed_value(self, ref: int):
        if int(ref) >= 0:
            return self.store.embed_value(ref)
        flat, start = self._locate(ref)
        return _wire_embed_value(flat, start)

    def binary_value(self, ref: int) -> bytes:
        if int(ref) >= 0:
            return self.store.binary_value(ref)
        flat, start = self._locate(ref)
        return _wire_binary_value(flat, start)

    def format_kv(self, ref: int):
        if int(ref) >= 0:
            return self.store.format_kv(ref)
        flat, start = self._locate(ref)
        return _wire_format_kv(flat, start)

    def type_branch(self, ref: int):
        if int(ref) >= 0:
            return self.store.items[int(ref)][1].branch
        flat, start = self._locate(ref)
        return _wire_type_branch(flat, start)

    def type_raw(self, ref: int) -> bytes:
        flat, start = self._locate(ref)
        return _wire_type_raw(flat, start)


# --- bounded resident-program wrapper (VERDICT r4 #7) -----------------------
# The decode lane's program is one of the process's LARGEST; jitting it
# per entry (instead of eager op-by-op tracing, which strands its big
# fori_loop executables in caches nothing can evict selectively) makes
# its executables per-function evictable under the progbudget registry.

_decode_updates_v1_impl = decode_updates_v1
_decode_updates_v1_jit = partial(
    jax.jit,
    static_argnames=(
        "max_rows", "max_dels", "n_steps", "max_sections", "packed",
    ),
)(_decode_updates_v1_impl)


def decode_updates_v1(
    buf,
    lens,
    max_rows,
    max_dels,
    n_steps=None,
    client_table=None,
    max_sections=None,
    key_table=None,
    client_hash_table=None,
    primary_root_hash=None,
    packed=False,
    lane_table=None,
):
    from ytpu.utils.phases import NULL_SPAN, phases, program_memory
    from ytpu.utils.progbudget import tick

    tick()
    if phases.enabled:
        if not isinstance(buf, jax.Array):
            # a host buffer crosses the link at this call; a device array
            # was uploaded, and its bytes counted, by whoever made it
            # (`ingest.merge.h2d`). jit tracers are jax.Arrays too.
            phases.transfer(
                "decode.v1",
                buf.size * buf.dtype.itemsize
                + lens.size * lens.dtype.itemsize,
                "h2d",
            )
        span = phases.span(
            "decode.v1",
            (buf.shape, max_rows, max_dels, n_steps, max_sections,
             client_table is not None, key_table is not None,
             client_hash_table is not None,
             primary_root_hash is not None or lane_table is not None,
             packed),
            axes=("buf", "max_rows", "max_dels", "n_steps",
                  "max_sections", "client_table", "key_table",
                  "client_hash_table", "primary_root_hash", "packed"),
            memory=program_memory(
                _decode_updates_v1_jit,
                buf,
                lens,
                max_rows=max_rows,
                max_dels=max_dels,
                n_steps=n_steps,
                client_table=client_table,
                max_sections=max_sections,
                key_table=key_table,
                client_hash_table=client_hash_table,
                primary_root_hash=primary_root_hash,
                packed=packed,
                lane_table=lane_table,
            ),
        )
    else:
        span = NULL_SPAN
    with span:
        return _decode_updates_v1_jit(
            buf,
            lens,
            max_rows=max_rows,
            max_dels=max_dels,
            n_steps=n_steps,
            client_table=client_table,
            max_sections=max_sections,
            key_table=key_table,
            client_hash_table=client_hash_table,
            primary_root_hash=primary_root_hash,
            packed=packed,
            lane_table=lane_table,
        )


decode_updates_v1.__doc__ = _decode_updates_v1_impl.__doc__


def _register_programs():
    from ytpu.utils import progbudget

    progbudget.register("decode_updates_v1", _decode_updates_v1_jit)


_register_programs()
