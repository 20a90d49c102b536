"""The doc mesh: a served state's rooms laid over the chips of one host.

One axis, `batch`: the leading (room) axis of every plane of a
`DocStateBatch` is split in equal contiguous blocks over the visible
devices, and a room's block columns stay whole on its chip. This is what
`DeviceSyncServer(shard_docs=True)` builds and what the benchmark's
four-chip cell (`yws-rooms-4k-x4.edit-flood`) measures. A step's small
inputs (wire bytes, lookup tables, the rank table) go whole onto every chip
(`replicated`); XLA's partitioner places the collectives, and no
hand-written one exists.
"""

from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "AXIS_BATCH",
    "batch_mesh",
    "batch_sharding",
    "replicated",
    "shard_docs_put",
    "require_doc_mesh",
    "state_shards",
]

#: the one mesh axis: rooms (docs) over chips
AXIS_BATCH = "batch"


# whole on every chip of the mesh: a step's tables and wire bytes
def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


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
