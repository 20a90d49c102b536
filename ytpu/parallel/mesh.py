"""Mesh construction and shardings for the batched engine.

Parallelism mapping (SURVEY.md §2 table):
- dp — the doc-batch axis: `DocStateBatch` shards its leading doc axis here
  (the reference analogue: N independent Docs; north-star 10k-doc batch).
- tp — the client axis of dense state-vector tensors ([D, C]) for
  encode_diff_batch's per-client clock compares.
- sp — the sequence axis inside one hot doc (sequence/context parallelism):
  `ytpu.parallel.seq_shard` — contiguous chunk partitioning, prefix-sum
  index routing, ppermute halo exchange.

All collectives ride ICI via XLA's sharding propagation — no hand-written
NCCL-style calls (reference has none either; its y-sync protocol is the
host-side analogue, see ytpu.sync).
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "make_mesh",
    "doc_sharding",
    "sv_sharding",
    "shard_state",
    "batch_mesh",
    "batch_sharding",
    "subbatch_devices",
    "shard_docs_put",
    "require_doc_mesh",
    "state_shards",
    "AXIS_DP",
    "AXIS_TP",
    "AXIS_BATCH",
]

AXIS_DP = "dp"
AXIS_TP = "tp"
#: doc-batch axis for sub-batched integrate dispatch (ISSUE-20): the
#: packed [NC, D, C] state splits into pow2 doc-width sub-batches and
#: each sub-batch lands on one mesh slot
AXIS_BATCH = "batch"


def make_mesh(
    n_devices: Optional[int] = None,
    axes: Tuple[str, str] = (AXIS_DP, AXIS_TP),
    tp: int = 1,
) -> Mesh:
    """Mesh with a doc-parallel axis and a (usually small) tp axis."""
    devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    n = len(devices)
    if n % tp != 0:
        raise ValueError(f"{n} devices not divisible by tp={tp}")
    arr = np.array(devices).reshape(n // tp, tp)
    return Mesh(arr, axes)


def doc_sharding(mesh: Mesh) -> NamedSharding:
    """Shard the leading doc axis over dp; block columns stay local."""
    return NamedSharding(mesh, P(AXIS_DP))


def sv_sharding(mesh: Mesh) -> NamedSharding:
    """[D, C] state-vector tensors: docs over dp, clients over tp."""
    return NamedSharding(mesh, P(AXIS_DP, AXIS_TP))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def shard_state(state, mesh: Mesh):
    """Place a DocStateBatch so its doc axis spans the dp mesh axis."""
    sh = doc_sharding(mesh)
    return jax.tree.map(lambda a: jax.device_put(a, sh), state)


def shard_batch(batch, mesh: Mesh):
    sh = doc_sharding(mesh)
    return jax.tree.map(lambda a: jax.device_put(a, sh), batch)


# --------------------------------------------------------------------------
# Doc-axis (batch) sharding for sub-batched integrate dispatch (ISSUE-20).
# All helpers degrade to a single-device no-op: `batch_mesh()` returns
# None when one device is visible, and every consumer treats None as
# "skip placement entirely", so the CPU tier-1 path stays byte-identical
# to the monolithic dispatch.


def batch_mesh(n_devices: Optional[int] = None) -> Optional[Mesh]:
    """1-D ``Mesh(('batch',))`` over the visible devices, or None on a
    single-device host (the fallback ISSUE-20 pins: no mesh, no
    device_put, byte-identical dispatch)."""
    devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    if len(devices) <= 1:
        return None
    return Mesh(np.array(devices), (AXIS_BATCH,))


def batch_sharding(mesh: Mesh, doc_axis: int = 0, ndim: int = 1) -> NamedSharding:
    """``NamedSharding(P('batch'))`` with the doc axis at ``doc_axis``
    of an ``ndim``-rank array (packed cols carry docs at axis 1)."""
    spec = [None] * max(int(ndim), doc_axis + 1)
    spec[doc_axis] = AXIS_BATCH
    return NamedSharding(mesh, P(*spec))


def subbatch_devices(n_sub: int, mesh: Optional[Mesh] = None):
    """Round-robin device placement for ``n_sub`` integrate sub-batches;
    None on a single-device host so the dispatch loop skips device_put."""
    if mesh is None:
        mesh = batch_mesh()
    if mesh is None:
        return None
    devs = list(mesh.devices.flat)
    return [devs[i % len(devs)] for i in range(int(n_sub))]


def shard_docs_put(arr, mesh: Optional[Mesh] = None, doc_axis: int = 0):
    """Place one array so its doc axis spans the batch mesh. Identity on
    a single-device host or when the doc axis doesn't divide the mesh
    (NamedSharding requires even splits; an uneven tail stays local)."""
    if mesh is None:
        mesh = batch_mesh()
    if mesh is None:
        return arr
    n = int(mesh.devices.size)
    if arr.ndim <= doc_axis or arr.shape[doc_axis] % n != 0:
        return arr
    return jax.device_put(arr, batch_sharding(mesh, doc_axis, arr.ndim))


def require_doc_mesh(n_docs: int) -> Optional[Mesh]:
    """The batch mesh a served state of `n_docs` rooms is laid over when
    its owner asked for `shard_docs=True`, or a refusal: `shard_docs_put`
    is the identity on one device and on a doc axis the mesh does not
    divide, so a server asked to shard would run unsharded and say
    nothing. None only off the chip with one device visible, the
    documented no-op the CPU tests run."""
    mesh = batch_mesh()
    if mesh is None:
        if jax.default_backend() == "tpu":
            raise RuntimeError(
                "shard_docs=True needs more than one chip, and jax sees "
                f"{len(jax.devices())}: refusing to serve unsharded"
            )
        return None
    n = int(mesh.devices.size)
    if n_docs % n:
        raise ValueError(
            f"shard_docs=True cannot lay {n_docs} rooms over {n} devices "
            "in equal blocks: refusing to serve unsharded"
        )
    return mesh


def state_shards(state) -> int:
    """Devices every plane of `state` spans, read off the arrays' own
    shardings: the fewest over the planes, so one plane gathered onto one
    chip reads 1. For a scrape or a test, never inside a step."""
    return min(len(a.sharding.device_set) for a in jax.tree.leaves(state))
