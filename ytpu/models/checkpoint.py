"""Checkpoint / resume for the batched device engine (SURVEY §5.4).

The reference's three host mechanisms (full-state update re-apply,
incremental update logs, Snapshot+skip_gc time travel) are all available in
`ytpu.core`; this module adds the TPU-native fourth: persisting the device
block tensors themselves, so a multi-tenant engine restarts without
replaying history.

Layout: a checkpoint directory holds
- `arrays/` — the DocStateBatch pytree via orbax (sharding-aware; restores
  onto whatever mesh the arrays carried), or `arrays.npz` when orbax is
  unavailable;
- `host.pkl` — the host sidecars that give the tensors meaning: the
  encoder's client interner, key interner, payload store and root name,
  plus (for a BatchIngestor) the per-doc state-vector mirrors and pending
  stashes.

A checkpoint round-trips the FULL ingest contract: wire encode/decode,
pending retry and reads behave identically after `load_ingestor`.
"""

from __future__ import annotations

import os
import pickle
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ytpu.models.batch_doc import BatchEncoder, BlockCols, DocStateBatch
from ytpu.models.ingest import BatchIngestor

__all__ = ["save_state", "load_state", "save_ingestor", "load_ingestor"]

# 2: BlockCols gained move columns (moved, mv_sc..mv_prio) and the encoder
#    sidecar gained saw_move — format-1 checkpoints cannot be restored
# 3: BlockCols gained the origin_slot cache column. Format-2 checkpoints
#    restore fine: the cache is derived state, recomputed at load
_FORMAT = 3
_READABLE_FORMATS = (2, 3)


def _state_to_numpy(state: DocStateBatch) -> dict:
    flat = {f"blocks.{k}": np.asarray(v) for k, v in state.blocks._asdict().items()}
    flat["start"] = np.asarray(state.start)
    flat["n_blocks"] = np.asarray(state.n_blocks)
    flat["error"] = np.asarray(state.error)
    return flat


def _state_from_numpy(flat: dict) -> DocStateBatch:
    cols = {
        k.split(".", 1)[1]: jnp.asarray(v)
        for k, v in flat.items()
        if k.startswith("blocks.")
    }
    needs_cache = "origin_slot" not in cols  # format-2 checkpoint
    if needs_cache:
        cols["origin_slot"] = jnp.full_like(cols["client"], -1)
    state = DocStateBatch(
        blocks=BlockCols(**cols),
        start=jnp.asarray(flat["start"]),
        n_blocks=jnp.asarray(flat["n_blocks"]),
        error=jnp.asarray(flat["error"]),
    )
    if needs_cache:
        from ytpu.models.batch_doc import recompute_origin_slot

        state = recompute_origin_slot(state)
    return state


def _enc_sidecar(enc: BatchEncoder) -> dict:
    return {
        "root_name": enc.root_name,
        "root_adopted": getattr(enc, "_root_adopted", False),
        "interner_from_idx": list(enc.interner.from_idx),
        "key_names": dict(enc.keys.names),
        "payload_items": list(enc.payloads.items),
        "saw_map_or_nested": enc.saw_map_or_nested,
        "saw_move": enc.saw_move,
    }


def _enc_restore(side: dict) -> BatchEncoder:
    enc = BatchEncoder(root_name=side["root_name"])
    enc._root_adopted = bool(side.get("root_adopted", False))
    for client in side["interner_from_idx"]:
        enc.interner.intern(client)
    for kid in sorted(side["key_names"]):
        got = enc.keys.intern(side["key_names"][kid])
        assert got == kid
    enc.payloads.items = list(side["payload_items"])
    enc.saw_map_or_nested = side["saw_map_or_nested"]
    enc.saw_move = side["saw_move"]
    return enc


def save_state(path: str, state: DocStateBatch, enc: BatchEncoder) -> None:
    """Persist a device state + its host sidecars under `path` (a dir)."""
    _save(path, state, {"format": _FORMAT, "enc": _enc_sidecar(enc)})


def load_state(path: str) -> Tuple[DocStateBatch, BatchEncoder]:
    state, side = _load(path)
    return state, _enc_restore(side["enc"])


def save_ingestor(path: str, ing: BatchIngestor, extra: Optional[dict] = None) -> None:
    """Persist a BatchIngestor: device state + encoder + pending stashes.
    `extra` (JSON-serializable) rides the sidecar for embedding layers
    (e.g. DeviceSyncServer tenant metadata)."""
    from ytpu.models.batch_doc import ensure_origin_slot

    # refresh a stale cache ONCE and write it back: save-then-continue
    # must not pay the O(D·B²) rebuild again on the next apply
    ing.state = ensure_origin_slot(ing.state)
    side = {
        "extra": extra or {},
        "format": _FORMAT,
        "enc": _enc_sidecar(ing.enc),
        "n_docs": ing.n_docs,
        "ingest": ing.ingest,
        "svs": [dict(sv.clocks) for sv in ing.svs],
        "pending": [
            {c: list(q) for c, q in stash.items()} for stash in ing._pending
        ],
        "pending_ds": [
            {c: list(rs) for c, rs in ds.clients.items()}
            for ds in ing._pending_ds
        ],
        # fast-lane sidecar: retained wire chunks resolve device-decoded
        # string refs (<= -2) after resume
        "wire_chunks": [
            (base, flat.tobytes()) for base, flat in ing.payloads._chunks
        ],
        "wire_total": ing.payloads.total_bytes,
        # multi-root docs: which name maps to the implicit branch, and
        # which anchors already exist (anchor ROWS persist in the state)
        "primary_roots": dict(ing.primary_roots),
        "anchored_roots": [sorted(s) for s in ing._anchored_roots],
    }
    _save(path, ing.state, side)


def load_ingestor(path: str) -> BatchIngestor:
    return load_ingestor_with_extra(path)[0]


def load_ingestor_with_extra(path: str) -> Tuple[BatchIngestor, dict]:
    """Like `load_ingestor`, also returning the embedder sidecar saved via
    `save_ingestor(..., extra=...)` (empty dict for older checkpoints)."""
    from ytpu.core.id_set import DeleteSet
    from ytpu.core.state_vector import StateVector

    from ytpu.ops.decode_kernel import ChunkedWirePayloads

    state, side = _load(path)
    ing = BatchIngestor.__new__(BatchIngestor)
    ing.enc = _enc_restore(side["enc"])
    ing.n_docs = side["n_docs"]
    # pre-PR-9 checkpoints predate the fast-lane wire-shipping knob;
    # they restore onto the current default
    ing.ingest = side.get("ingest", "raw")
    ing.state = state
    # a checkpoint restores onto the default device: not doc-sharded, and
    # the ingestor says so (`DeviceSyncServer.shard_docs`, the gauge
    # `ingest.state_shards` of a freshly built one)
    ing.shard_docs = False
    ing._by_doc = ing._on_every_chip = None
    ing.svs = [StateVector(dict(c)) for c in side["svs"]]
    ing._pending = [dict(p) for p in side["pending"]]
    ing._pending_ds = [DeleteSet(dict(d)) for d in side["pending_ds"]]
    ing.payloads = ChunkedWirePayloads(ing.enc.payloads)
    ing.payloads._chunks = [
        (base, np.frombuffer(raw, dtype=np.uint8))
        for base, raw in side.get("wire_chunks", [])
    ]
    ing.payloads.total_bytes = side.get("wire_total", 0)
    ing.fast_docs = 0
    ing.slow_docs = 0
    ing.fast_recoveries = 0
    ing._last_fast_flags = None
    ing._bind_counters()
    # rebuild the device hash tables from the restored interners
    ing._reset_tables()
    ing._reset_rows(np.asarray(state.n_blocks))
    for key in ing.enc.keys.ids:
        ing._register_key(key)
    for cid in ing.enc.interner.from_idx:
        if cid > 2**31 - 1:
            ing._register_big_client(cid)
    ing.primary_roots = {
        int(d): name for d, name in side.get("primary_roots", {}).items()
    }
    ing._anchored_roots = [
        set(s)
        for s in side.get("anchored_roots", [[] for _ in range(ing.n_docs)])
    ]
    return ing, dict(side.get("extra", {}))


def save_device_server(path: str, server) -> None:
    """Persist a DeviceSyncServer: the ingestor checkpoint plus the tenant
    overlay (slot assignments and learned wire root names — without the
    names, a restored pod would re-emit every tenant root under the batch
    default name; code-review r3). Queued-but-unflushed updates integrate
    first so an acknowledged update can never be lost across a restart."""
    server.flush_device()
    if server.device_authoritative:
        # host docs matter only for demoted (multi-root) tenants
        host_docs = {
            name: server.doc(name).encode_state_as_update_v1()
            for name in server._host_tenants
        }
    else:
        # mirrored mode: the HOST docs are authoritative (the device batch
        # only shadows them) — snapshot every tenant
        host_docs = {
            name: server.doc(name).encode_state_as_update_v1()
            for name in server.tenants
        }
    save_ingestor(
        path,
        server.ingestor,
        extra={
            "slot_of": dict(server._slot_of),
            "root_names": dict(server._root_names),
            "host_tenants": sorted(server._host_tenants),
            "host_docs": host_docs,
            "device_authoritative": server.device_authoritative,
        },
    )


def load_device_server(path: str, **server_kwargs):
    """Restore a DeviceSyncServer around a checkpointed ingestor. Tenant
    docs/sessions are transient (clients resync via the greeting); slot
    assignments and root names are durable."""
    from ytpu.sync.device_server import DeviceSyncServer

    ing, extra = load_ingestor_with_extra(path)
    server_kwargs.setdefault(
        "device_authoritative", extra.get("device_authoritative", False)
    )
    server = DeviceSyncServer(ingestor=ing, **server_kwargs)
    server._slot_of = dict(extra.get("slot_of", {}))
    server._root_names = dict(extra.get("root_names", {}))
    server._host_tenants = set(extra.get("host_tenants", []))
    used = set(server._slot_of.values())
    server._next_slot = max(used, default=-1) + 1
    server._free_slots = sorted(set(range(server._next_slot)) - used)
    # re-register tenants so greetings answer from the restored slots
    for name in server._slot_of:
        server.tenant(name)
    for name, payload in extra.get("host_docs", {}).items():
        server.doc(name).apply_update_v1(payload)
    return server


# --- storage backends ---------------------------------------------------------


def _save(path: str, state: DocStateBatch, sidecar: dict) -> None:
    """Idempotent overwrite in both backends — periodic checkpointing to a
    fixed path must behave the same with and without orbax."""
    import shutil

    from ytpu.models.batch_doc import ensure_origin_slot

    os.makedirs(path, exist_ok=True)
    # format-3 checkpoints persist the origin_slot cache as authoritative;
    # a fused-lane state deferred its rebuild (lazy dirty-flag), so
    # refresh here iff it is marked stale
    state = ensure_origin_slot(state)
    flat = _state_to_numpy(state)
    arrays_dir = os.path.join(path, "arrays")
    npz_path = os.path.join(path, "arrays.npz")
    if os.path.exists(arrays_dir):
        shutil.rmtree(arrays_dir)
    if os.path.exists(npz_path):
        os.remove(npz_path)
    saved_with = "npz"
    try:
        import orbax.checkpoint as ocp

        ckpt = ocp.PyTreeCheckpointer()
        ckpt.save(arrays_dir, {k: jnp.asarray(v) for k, v in flat.items()})
        saved_with = "orbax"
    except Exception:
        shutil.rmtree(arrays_dir, ignore_errors=True)  # partial orbax dir
        np.savez_compressed(npz_path, **flat)
    sidecar = dict(sidecar)
    sidecar["saved_with"] = saved_with
    with open(os.path.join(path, "host.pkl"), "wb") as f:
        pickle.dump(sidecar, f)


def _load(path: str) -> Tuple[DocStateBatch, dict]:
    with open(os.path.join(path, "host.pkl"), "rb") as f:
        side = pickle.load(f)
    if side.get("format") not in _READABLE_FORMATS:
        raise ValueError(f"unsupported checkpoint format {side.get('format')}")
    if side.get("saved_with") == "orbax":
        import orbax.checkpoint as ocp

        ckpt = ocp.PyTreeCheckpointer()
        flat = ckpt.restore(os.path.join(path, "arrays"))
    else:
        with np.load(os.path.join(path, "arrays.npz"), allow_pickle=False) as z:
            flat = {k: z[k] for k in z.files}
    return _state_from_numpy(flat), side
