"""Sequence parallelism: one hot document sharded across devices.

The reference's scaling pain point for long documents is `find_position`'s
O(items) walk (/root/reference/yrs/src/types/text.rs:734; the Yjs search-
marker optimization is an acknowledged TODO at block.rs:723). This module is
the TPU answer sketched in SURVEY.md §5.7: treat item-sequence length like
sequence length in a long-context model —

- the visible sequence is partitioned into S contiguous chunks, one per
  device along the ``sp`` mesh axis (the ring/Ulysses-shaped axis of the
  §2 parallelism table);
- index→shard resolution is a prefix-sum over per-shard lengths
  (`all_gather` of S scalars — the distributed analogue of the prefix-sum
  position lookup the reference lacks);
- deletes spanning shard boundaries are applied distributively: every
  shard clips the global range against its own interval, so no op ever
  needs cross-shard coordination beyond the length vector;
- load is kept even by a **halo exchange**: a bidirectional ring step
  (`lax.ppermute`) that ships boundary characters toward the balanced
  cumulative-length profile, bounded by ``HALO`` chars per step.

Ops are position-based text edits (the B4 trace shape: insert(pos, str) /
delete(pos, len)), replayed under `jit` + `shard_map` as one `lax.scan`.
Payload characters ride as i32 codepoints; the host assembles the final
string (`read_text`).
"""

from __future__ import annotations

from functools import partial
from typing import List, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh

I32 = jnp.int32

AXIS_SP = "sp"
KIND_INSERT = 0
KIND_DELETE = 1
HALO = 256  # max chars crossing one boundary per rebalance step

__all__ = [
    "AXIS_SP",
    "ShardedTextState",
    "OpStream",
    "make_sp_mesh",
    "init_sharded",
    "build_op_stream",
    "apply_ops_sharded",
    "read_text",
]


class ShardedTextState(NamedTuple):
    text: jax.Array  # [S, CAP] i32 codepoints; visible prefix per shard
    length: jax.Array  # [S] i32 visible chars held by each shard
    error: jax.Array  # [S] i32 sticky flags (1 = shard overflow)


class OpStream(NamedTuple):
    kind: jax.Array  # [N] i32 KIND_INSERT | KIND_DELETE
    pos: jax.Array  # [N] i32 global position
    count: jax.Array  # [N] i32 chars inserted / deleted
    payload: jax.Array  # [N, MAX_INS] i32 codepoints (inserts)


def make_sp_mesh(n_devices: int) -> Mesh:
    devices = np.array(jax.devices()[:n_devices])
    return Mesh(devices, (AXIS_SP,))


def init_sharded(n_shards: int, cap: int) -> ShardedTextState:
    return ShardedTextState(
        text=jnp.zeros((n_shards, cap), I32),
        length=jnp.zeros((n_shards,), I32),
        error=jnp.zeros((n_shards,), I32),
    )


def build_op_stream(ops: Sequence[Tuple[str, int, object]], max_ins: int = 32) -> OpStream:
    """Pack (tag, pos, payload) ops; long inserts split into max_ins chunks."""
    kind: List[int] = []
    pos: List[int] = []
    count: List[int] = []
    payload: List[List[int]] = []
    for tag, p, arg in ops:
        if tag == "i":
            chars = [ord(c) for c in str(arg)]
            for off in range(0, len(chars), max_ins):
                chunk = chars[off : off + max_ins]
                kind.append(KIND_INSERT)
                pos.append(p + off)
                count.append(len(chunk))
                payload.append(chunk + [0] * (max_ins - len(chunk)))
        else:
            kind.append(KIND_DELETE)
            pos.append(p)
            count.append(int(arg))
            payload.append([0] * max_ins)
    return OpStream(
        kind=jnp.asarray(kind, I32),
        pos=jnp.asarray(pos, I32),
        count=jnp.asarray(count, I32),
        payload=jnp.asarray(np.asarray(payload, np.int32).reshape(-1, max_ins)),
    )


# --- per-shard op kernel (runs inside shard_map) ------------------------------


def _apply_one_op(carry, op, *, cap: int, max_ins: int):
    text, length, error = carry  # text [CAP], length/error scalar (per shard)
    kind, pos, count, payload = op
    idx = lax.axis_index(AXIS_SP)
    lengths = lax.all_gather(length, AXIS_SP)  # [S]
    cum = jnp.cumsum(lengths)
    start = cum[idx] - lengths[idx]
    total = cum[-1]
    iota = jnp.arange(cap, dtype=I32)

    # ---- insert: exactly one owner shard (first whose end >= pos) ----
    pos_i = jnp.minimum(pos, total)
    owner = jnp.searchsorted(cum, pos_i, side="left").astype(I32)
    owner = jnp.minimum(owner, lengths.shape[0] - 1)
    is_ins = (kind == KIND_INSERT) & (owner == idx) & (count > 0)
    local = jnp.clip(pos_i - start, 0, length)
    shifted = jnp.where(
        iota >= local + count,
        jnp.take(text, jnp.clip(iota - count, 0, cap - 1)),
        text,
    )
    ins_mask = (iota >= local) & (iota < local + count)
    ins_chars = jnp.take(payload, jnp.clip(iota - local, 0, max_ins - 1))
    inserted = jnp.where(ins_mask, ins_chars, shifted)
    text = jnp.where(is_ins, inserted, text)
    new_len = length + count
    error = jnp.where(is_ins & (new_len > cap), 1, error)
    length = jnp.where(is_ins, jnp.minimum(new_len, cap), length)

    # ---- delete: every shard applies its local overlap ----
    del_lo = jnp.clip(pos, 0, total)
    del_hi = jnp.clip(pos + count, 0, total)
    lo = jnp.clip(del_lo - start, 0, length)
    hi = jnp.clip(del_hi - start, 0, length)
    ndel = hi - lo
    is_del = (kind == KIND_DELETE) & (ndel > 0)
    removed = jnp.where(
        iota >= lo,
        jnp.take(text, jnp.clip(iota + ndel, 0, cap - 1)),
        text,
    )
    text = jnp.where(is_del, removed, text)
    length = jnp.where(is_del, length - ndel, length)

    return (text, length, error), None


# --- halo exchange: one bidirectional ring rebalance step ---------------------


def _rebalance(text, length, error, *, cap: int):
    """Ship boundary chars toward the balanced cumulative-length profile.

    flow[i] = cum[i] - target_cum[i]: the signed number of characters that
    should cross boundary i (between shard i and i+1) rightward. Positive →
    shard i sends its tail right; negative → shard i+1 sends its head left.
    Bounded by HALO per call; repeated calls converge.
    """
    idx = lax.axis_index(AXIS_SP)
    lengths = lax.all_gather(length, AXIS_SP)
    n_shards = lengths.shape[0]
    cum = jnp.cumsum(lengths)
    total = cum[-1]
    target_cum = (jnp.arange(1, n_shards + 1, dtype=I32) * total) // n_shards
    flow = cum - target_cum  # [S]; flow[-1] == 0 by construction

    flow_right = jnp.where(idx < n_shards - 1, flow[idx], 0)
    flow_left = jnp.where(idx > 0, flow[jnp.maximum(idx - 1, 0)], 0)
    send_r = jnp.clip(flow_right, 0, HALO)
    send_l = jnp.clip(-flow_left, 0, HALO)
    send_l = jnp.minimum(send_l, length)
    send_r = jnp.minimum(send_r, length - send_l)

    iota = jnp.arange(HALO, dtype=I32)
    # my head (to left neighbor) and tail (to right neighbor)
    head_buf = jnp.take(text, jnp.clip(iota, 0, cap - 1))
    tail_buf = jnp.take(text, jnp.clip(length - send_r + iota, 0, cap - 1))

    fwd = [(i, i + 1) for i in range(n_shards - 1)]
    bwd = [(i + 1, i) for i in range(n_shards - 1)]
    recv_l = lax.ppermute(tail_buf, AXIS_SP, fwd)  # from left neighbor's tail
    n_l = lax.ppermute(send_r, AXIS_SP, fwd)
    recv_r = lax.ppermute(head_buf, AXIS_SP, bwd)  # from right neighbor's head
    n_r = lax.ppermute(send_l, AXIS_SP, bwd)

    core_len = length - send_l - send_r
    new_len = n_l + core_len + n_r
    pos = jnp.arange(cap, dtype=I32)
    from_left = jnp.take(recv_l, jnp.clip(pos, 0, HALO - 1))
    from_core = jnp.take(text, jnp.clip(send_l + pos - n_l, 0, cap - 1))
    from_right = jnp.take(
        recv_r, jnp.clip(pos - n_l - core_len, 0, HALO - 1)
    )
    new_text = jnp.where(
        pos < n_l,
        from_left,
        jnp.where(pos < n_l + core_len, from_core, from_right),
    )
    new_text = jnp.where(pos < new_len, new_text, 0)
    return new_text, new_len, error


# --- public driver ------------------------------------------------------------


@partial(jax.jit, static_argnames=("mesh", "rebalance_every", "cap", "max_ins"))
def _apply_ops_impl(state, stream, *, mesh, rebalance_every, cap, max_ins):
    from jax.sharding import PartitionSpec as P

    n_ops = stream.kind.shape[0]

    def shard_fn(text, length, error, kind, pos, count, payload):
        text = text[0]  # [1, CAP] block → [CAP]
        length = length[0]
        error = error[0]
        carry = (text, length, error)
        step = partial(_apply_one_op, cap=cap, max_ins=max_ins)
        for chunk_start in range(0, n_ops, rebalance_every):
            chunk = slice(chunk_start, min(chunk_start + rebalance_every, n_ops))
            ops = (kind[chunk], pos[chunk], count[chunk], payload[chunk])
            carry, _ = lax.scan(step, carry, ops)
            carry = _rebalance(*carry, cap=cap)
        text, length, error = carry
        return text[None], length[None], error[None]

    text, length, error = jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(P(AXIS_SP), P(AXIS_SP), P(AXIS_SP), P(), P(), P(), P()),
        out_specs=(P(AXIS_SP), P(AXIS_SP), P(AXIS_SP)),
    )(state.text, state.length, state.error, stream.kind, stream.pos,
      stream.count, stream.payload)
    return ShardedTextState(text, length, error)


def apply_ops_sharded(
    state: ShardedTextState,
    stream: OpStream,
    mesh: Mesh,
    rebalance_every: int = 64,
) -> ShardedTextState:
    """Replay a position-op stream over the sp-sharded document."""
    return _apply_ops_impl(
        state,
        stream,
        mesh=mesh,
        rebalance_every=rebalance_every,
        cap=state.text.shape[1],
        max_ins=stream.payload.shape[1],
    )


def read_text(state: ShardedTextState) -> str:
    text = np.asarray(state.text)
    lengths = np.asarray(state.length)
    parts = [
        "".join(chr(c) for c in text[i, : lengths[i]]) for i in range(len(lengths))
    ]
    return "".join(parts)


def _register_programs():
    from ytpu.utils import progbudget

    progbudget.register("seq_shard_apply_ops", _apply_ops_impl)


_register_programs()
