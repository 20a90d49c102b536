"""Device-backed sync server: y-sync tenants fanned into batch engine slots.

This closes the north-star loop (SURVEY §0 / BASELINE): clients speak the
y-sync protocol to `SyncServer`; updates land in the batched engine through
`BatchIngestor` — one `apply_update_batch` dispatch integrates one queued
update per tenant, with the ingestor's pending semantics absorbing
out-of-order arrival per slot without stalling the batch.

Two serving modes:

- mirrored (default, round-1 behavior): host tenant docs remain the
  protocol endpoints (diffs via `Doc.encode_state_as_update_v1`); the
  device batch shadows them. Every update integrates twice — useful when
  host-side observers/types must stay live, but the host is the
  bottleneck.
- **device-authoritative** (`device_authoritative=True`): the device
  batch IS the document store. SyncStep1 is answered from device state
  via `encode_diff_batch` + the pipelined finisher
  (`batch_doc.DiffPipeline`, ISSUE-10; store.rs:204-248 semantics over
  block columns), incoming updates are queued straight to
  the slot without a host apply, and the host tenant doc is demoted to
  an awareness/metadata anchor that never sees document content. This is
  the serving loop where the batch engine adds capacity instead of
  shadowing the host (VERDICT r1 #7).
"""

from __future__ import annotations

import time
import weakref
from typing import Dict, List, Optional

import numpy as np

from ytpu.core.state_vector import StateVector
from ytpu.encoding.lib0 import Writer
from ytpu.models.ingest import BatchIngestor
from ytpu.parallel.mesh import state_shards
from ytpu.sync.protocol import (
    MSG_SYNC,
    MSG_SYNC_STEP_1,
    Message,
    SyncMessage,
    message_reader,
)
from ytpu.sync.server import DeviceBatchFull, Session, SyncServer
from ytpu.utils.phases import phases
from ytpu.utils.trace import current_trace_id, tracer

__all__ = ["DeviceBatchFull", "DeviceSyncServer"]

#: the process's live servers, for `sync.device_queue_depth`: weak, so the
#: registry keeps no server alive
_SERVERS: "weakref.WeakSet[DeviceSyncServer]" = weakref.WeakSet()


def _queued_everywhere() -> int:
    return sum(srv.pending_device_updates() for srv in tuple(_SERVERS))


class DeviceSyncServer(SyncServer):
    """A SyncServer whose tenants live in device doc slots.

    `n_docs` bounds the tenant count (one slot per tenant, assigned on
    first touch). Updates accumulate per slot and ship on `flush_device()`
    — call it per request batch, on a timer, or from the serving loop.
    A slot holds `capacity` rows: a room that nears it is squashed,
    collected and defragmented inside the step (`BatchIngestor._make_room`;
    the policy is the server's own), and `ERR_CAPACITY` is left for a room
    whose squashed document does not fit (a slot cannot grow).
    Multi-root tenants (doc.rs:156-228, the reference's normal doc shape)
    are device-resident: the first named root maps onto the implicit
    device branch, later ones anchor through per-doc BLOCK_ROOT_ANCHOR
    rows the ingestor creates from the wire prescan.
    """

    def __init__(
        self,
        n_docs: Optional[int] = None,
        capacity: int = 2048,
        ingestor: Optional[BatchIngestor] = None,
        device_authoritative: bool = False,
        diff_sub_batch: int = 512,
        diff_depth: int = 2,
        telemetry_port: Optional[int] = None,
        shard_docs: bool = False,
        **kwargs,
    ):
        super().__init__(**kwargs)
        if ingestor is None:
            if n_docs is None:
                raise ValueError("pass n_docs or an ingestor")
            ingestor = BatchIngestor(n_docs, capacity, shard_docs=shard_docs)
        # the ingestor is the single source of truth for the slot count
        self.ingestor = ingestor
        # doc-axis sub-batching / sharding knob (ISSUE-20): surfaced in
        # telemetry and threaded into the default ingestor above (an
        # explicitly-passed ingestor keeps its own setting)
        self.shard_docs = bool(getattr(ingestor, "shard_docs", shard_docs))
        self.device_authoritative = device_authoritative
        from ytpu.utils import metrics

        self._diffs_encoded = metrics.counter(
            "sync.diffs_encoded", labelnames=("tenant",)
        )
        self._slots_gauge = metrics.gauge("sync.device_slots_assigned")
        self._slot_of: Dict[str, int] = {}
        # pipelined encode/diff driver (ISSUE-10): every SyncStep1 answer
        # and batched fan-out routes through it — single-tenant calls take
        # its inline one-sub-batch path, many-tenant fan-outs overlap
        # device compaction / D2H / native finisher as staged sub-batches
        from ytpu.models.batch_doc import DiffPipeline

        self._diff_pipeline = DiffPipeline(
            sub_batch=diff_sub_batch, depth=diff_depth
        )
        # per-tenant wire root name (the batch engine maps any single-root
        # tenant onto one device branch; the name must round-trip on the
        # wire — doc.rs root branches are keyed by name). Learned from the
        # native wire prescan of every inbound update.
        self._root_names: Dict[str, str] = {}
        # tenants demoted to the host path: a second distinct root name
        # appeared (multi-root tenants — doc.rs:156-228's normal shape —
        # exceed the single-root device scope, so they are served from the
        # host doc instead of being silently aliased onto one root)
        self._host_tenants: set = set()
        # slot allocation: next fresh slot + slots reclaimed by demotions
        self._next_slot = 0
        self._free_slots: List[int] = []
        self._queues: List[List[bytes]] = [
            [] for _ in range(ingestor.n_docs)
        ]
        # per-queued-update (request trace id, enqueue instant), in
        # lockstep with _queues (ISSUE-11): the device-dispatch span names
        # the requests whose updates it ships, closing the net → admission
        # → dispatch chain, and `sync.queue_wait` is the time from that
        # instant to the start of the step that carries the update
        self._queue_traces: List[List[tuple]] = [
            [] for _ in range(ingestor.n_docs)
        ]
        # per-queued-update native columns, in lockstep with _queues: an
        # update is decoded once, where it arrives (`_note_roots`), and
        # its columns ride to the step that integrates it (`apply_bytes`
        # takes them for its prescan). None where nothing decoded it: no
        # native library, or an update queued by another path
        self._queue_columns: List[list] = [
            [] for _ in range(ingestor.n_docs)
        ]
        # queued updates over all slots of the process's live servers,
        # worked out when `/metrics` or `/healthz` reads it: a walk over
        # every slot is no work for a flush
        _SERVERS.add(self)
        metrics.gauge("sync.device_queue_depth").set_function(_queued_everywhere)
        self._last_dispatch = metrics.gauge("sync.last_dispatch_unix")
        # live telemetry plane (ISSUE-11): `telemetry_port` starts the
        # scrapeable HTTP endpoint on its own daemon thread (0 = any
        # free port; None = off). docs/observability.md §Live telemetry.
        self.telemetry = None
        if telemetry_port is not None:
            from ytpu.utils.telemetry import TelemetryServer

            self.telemetry = TelemetryServer(port=telemetry_port)
            self.telemetry.add_provider("server", self._telemetry_provider)
            self.telemetry.start()

    def _telemetry_provider(self) -> Dict:
        """`/snapshot` extras: the serving-side state a scraper wants
        next to the raw metrics (JSON-safe, lock-free reads), plus the
        per-tenant occupancy/fragmentation ledger (ISSUE-18) — one
        scrape-time device pull per snapshot, never on the serve path."""
        out = {
            "tenants": len(self.tenants),
            "slots_assigned": len(self._slot_of),
            "n_docs": self.ingestor.n_docs,
            "queued_updates": self.pending_device_updates(),
            "device_authoritative": self.device_authoritative,
            # the flag as asked for, and what the planes do span
            "shard_docs": self.shard_docs,
            "state_shards": state_shards(self.ingestor.state),
        }
        try:
            out["capacity"] = self.capacity_snapshot()
        except Exception as e:  # scrape must not take the server down
            out["capacity"] = {"error": f"{type(e).__name__}: {e}"}
        return out

    def capacity_snapshot(self) -> Dict:
        """Per-tenant slot-occupancy ledger: live / dead (tombstoned,
        GC-able) / free rows per assigned tenant slot, summing to the
        slot capacity, plus batch-wide totals and what compaction has
        given back (a room's dead rows are collected and its typed runs
        squashed when it nears its capacity, inside `flush_device`:
        `BatchIngestor._make_room`). Backs the ``capacity``
        section of `/snapshot` and the per-tenant
        ``capacity.tenant_*_rows`` gauges."""
        from ytpu.utils import metrics

        live, dead, free = self.ingestor.capacity_ledger()
        slot_cap = int(live[0] + dead[0] + free[0]) if len(live) else 0
        tenants: Dict[str, Dict] = {}
        live_g = metrics.gauge("capacity.tenant_live_rows", labelnames=("tenant",))
        dead_g = metrics.gauge("capacity.tenant_dead_rows", labelnames=("tenant",))
        free_g = metrics.gauge("capacity.tenant_free_rows", labelnames=("tenant",))
        for name, slot in sorted(self._slot_of.items()):
            row = {
                "slot": slot,
                "live_rows": int(live[slot]),
                "dead_rows": int(dead[slot]),
                "free_rows": int(free[slot]),
                "dead_fraction": round(
                    int(dead[slot])
                    / float(max(int(live[slot]) + int(dead[slot]), 1)),
                    6,
                ),
            }
            tenants[name] = row
            live_g.labels(tenant=name).set(row["live_rows"])
            dead_g.labels(tenant=name).set(row["dead_rows"])
            free_g.labels(tenant=name).set(row["free_rows"])
        return {
            "slot_capacity": slot_cap,
            "live_rows": int(sum(int(x) for x in live)),
            "dead_rows": int(sum(int(x) for x in dead)),
            "free_rows": int(sum(int(x) for x in free)),
            # what the served path's compaction has done so far
            # (`BatchIngestor._make_room`): the process's counters
            "compactions": int(metrics.counter("ingest.room_compactions").value),
            "rows_reclaimed": int(metrics.counter("ingest.rows_reclaimed").value),
            "capacity_refusals": int(
                metrics.counter("ingest.capacity_refusals").value
            ),
            "tenants": tenants,
        }

    def _enqueue(self, slot: int, payload: bytes, columns=None) -> None:
        """Queue one update for a slot, recording the ambient request
        trace id (None outside a traced request), the instant and the
        update's native columns (`payload` decoded, where its sender has
        them) in lockstep."""
        self._queues[slot].append(payload)
        self._queue_columns[slot].append(columns)
        self._queue_traces[slot].append(
            (current_trace_id(), time.perf_counter())
        )

    # --- slot management -------------------------------------------------------

    def slot_of(self, tenant_name: str) -> int:
        """The device slot of an EXISTING tenant (KeyError otherwise)."""
        slot = self._slot_of.get(tenant_name)
        if slot is None:
            raise KeyError(f"tenant {tenant_name!r} has no device slot")
        return slot

    def _assign_slot(self, tenant_name: str) -> int:
        slot = self._slot_of.get(tenant_name)
        if slot is None:
            if self._free_slots:
                slot = self._free_slots.pop()
            elif self._next_slot < self.ingestor.n_docs:
                slot = self._next_slot
                self._next_slot += 1
            else:
                raise DeviceBatchFull(
                    f"device batch is full ({self.ingestor.n_docs} tenant slots)"
                )
            self._slot_of[tenant_name] = slot
            self._slots_gauge.set(len(self._slot_of))
        return slot

    def tenant(self, name: str):
        first_touch = name not in self.tenants
        if first_touch:
            # reserve the slot FIRST: exhaustion must fail before the tenant
            # registers, or retries would create an unmirrored ghost tenant
            self._assign_slot(name)
        t = super().tenant(name)
        if first_touch and not self.device_authoritative:
            # mirrored mode: shadow every host apply into the device queue
            # (device-authoritative tenants queue in receive_frames and
            # never touch the host doc).  The slot is resolved per event,
            # not captured — a live rebalance moves the tenant's slot out
            # from under this observer (ISSUE-9); a demoted host-resident
            # tenant has no slot and mirrors nothing
            def mirror(payload: bytes, origin, txn, _name=name):
                slot = self._slot_of.get(_name)
                if slot is not None:
                    self._enqueue(slot, payload)

            t.awareness.doc.observe_update_v1(mirror)
        return t

    # --- device-authoritative protocol path ------------------------------------

    def connect_frames(self, tenant_name: str):
        if not self.device_authoritative or tenant_name in self._host_tenants:
            return super().connect_frames(tenant_name)
        t = self.tenant(tenant_name)
        self._next_session += 1
        session = Session(self._next_session, tenant_name, self)
        t.sessions.append(session)
        self._sessions_gauge.inc()
        # greeting SyncStep1 carries the DEVICE state vector (flush first
        # so queued updates are reflected in the mirror)
        self.flush_device()
        sv = self.device_state_vector(tenant_name)
        return session, [
            Message.sync(SyncMessage.step1(sv)).encode_v1(),
            Message.awareness(t.awareness.update()).encode_v1(),
        ]

    def receive_frames(self, session: Session, data: bytes) -> List[bytes]:
        """Like `SyncServer.receive_frames`, but malformed-frame errors
        are isolated to the offending session (ISSUE-6): a frame that
        fails to parse or apply marks THIS session dead (`net.bad_frames`
        counter) and returns no replies instead of propagating into the
        serving loop — one hostile peer cannot take down a device batch
        that is serving every other tenant.  Device-step failures raised
        by `flush_device` are NOT caught here: those indict the batch,
        not a session, and keep their flight-recorder dump semantics."""
        try:
            return self._receive_frames_unsafe(session, data)
        except Exception as e:
            from ytpu.utils import metrics

            metrics.counter("net.bad_frames").inc()
            # the flight-recorder ring keeps WHAT threw (bounded,
            # drop-oldest: a hostile peer can't grow it) — a real
            # server-side bug must stay distinguishable from peer junk
            tracer.instant(
                "net.bad_frame",
                error=repr(e),
                tenant=session.tenant,
                session=session.id,
            )
            self._dropped.labels("bad_frame").inc()
            session.dead = True
            session.outbox = []
            self.disconnect(session)
            return []

    def _receive_frames_unsafe(
        self, session: Session, data: bytes
    ) -> List[bytes]:
        if not self.device_authoritative or session.tenant in self._host_tenants:
            return super().receive_frames(session, data)
        with phases.span("sync.receive"):
            return self._receive_device_frames(session, data)

    def _receive_device_frames(
        self, session: Session, data: bytes
    ) -> List[bytes]:
        t = self.tenant(session.tenant)
        slot = self.slot_of(session.tenant)
        replies: List[bytes] = []
        with phases.span("sync.receive.parse"):
            msgs = list(message_reader(data))
        for msg in msgs:
            if msg.kind == MSG_SYNC:
                sub: SyncMessage = msg.body
                if sub.tag == MSG_SYNC_STEP_1:
                    diff = self.device_encode_diff(session.tenant, sub.payload)
                    replies.append(
                        Message.sync(SyncMessage.step2(diff)).encode_v1()
                    )
                else:  # SyncStep2 / Update: straight to the device slot
                    ok, busy = self._admit_update(session)
                    if not ok:
                        if busy is not None:
                            replies.append(busy)
                        if session.dead:
                            break  # shed
                        continue
                    # record the tenant's root names (the first becomes the
                    # wire primary); non-primary roots stay device-resident
                    # via the ingestor's BLOCK_ROOT_ANCHOR rows — multi-root
                    # tenants are served from the batch like any other
                    # (doc.rs:156-228 is the reference's normal doc shape).
                    # This is the update's one columnar decode: its columns
                    # are queued beside the bytes, and the planning of the
                    # step that integrates it walks them (`apply_bytes`)
                    with phases.span("sync.receive.roots"):
                        cols = self._note_roots(session.tenant, sub.payload)
                    self._enqueue(slot, sub.payload, cols)
                    self._applied.inc()
                    t.applied.inc()
                    self.applied_local += 1
                    # broadcast at-least-once (idempotent CRDT updates;
                    # the host path dedups via observer events, the device
                    # path trades that for never touching a host doc)
                    with phases.span("sync.receive.fanout"):
                        frame = Message.sync(
                            SyncMessage.update(sub.payload)
                        ).encode_v1()
                        tframe = self._trace_frame()
                        for other in t.sessions:
                            if other is not session:
                                if tframe is not None:
                                    other.push(tframe)
                                other.push(frame)
                continue
            reply = self.protocol.handle_message(t.awareness, msg)
            if reply is not None:
                replies.append(reply.encode_v1())
        return replies

    @staticmethod
    def _scan_root_names(payload: bytes, cols) -> List[str]:
        """Distinct root-parent names in a wire update, in block order:
        read off its native columns `cols` (a loop of a microsecond or
        two; the decode that made them is what costs, ~100 us an update
        on the chip machine's host: `roots_us.flood`), falling back to
        the host decoder when the native library is absent (`cols` is
        None) or could not read the update."""
        names: List[str] = []
        if cols is not None and not cols.error:
            for i in range(cols.n_blocks):
                n = cols.parent_name(i)
                if n and n not in names:
                    names.append(n)
            return names
        from ytpu.core.update import Update

        try:
            up = Update.decode_v1(payload)
        except Exception:
            return names
        for blocks in up.blocks.values():
            for b in blocks:
                p = getattr(b, "parent", None)
                if isinstance(p, str) and p not in names:
                    names.append(p)
        return names

    def _note_roots(self, tenant: str, payload: bytes):
        """Decode one inbound update into its native columns (the C++
        pass the ingest fast lane plans from), record the tenant's root
        names from them, and hand the columns back for the queue: None
        without the native library. A tenant that turns multi-root is
        counted (`sync.multi_root_tenants`; observability only: the
        batch engine anchors non-primary roots per doc, so multi-root
        tenants stay device-resident)."""
        from ytpu.native import decode_update_columns

        cols = decode_update_columns(payload)
        names = self._scan_root_names(payload, cols)
        if names:
            known = self._root_names.setdefault(tenant, names[0])
            if any(n != known for n in names):
                from ytpu.utils import metrics

                metrics.counter("sync.multi_root_tenants").inc()
        return cols

    def _demote_to_host(self, tenant: str) -> None:
        """Escape hatch: move a tenant from its device slot to the host
        path (integrate everything queued, materialize the host doc from
        device state, route through `SyncServer` from now on). No longer
        used for multi-root tenants — the batch engine serves those via
        per-doc root anchors — but kept for operational fallback."""
        self.flush_device()
        doc = self.tenant(tenant).awareness.doc
        diff = self.device_encode_diff(tenant, doc.state_vector())
        self._host_tenants.add(tenant)
        # the apply fires the tenant's broadcast observer once (all
        # sessions receive a full-state update frame — idempotent)
        doc.apply_update_v1(diff)
        # reclaim the device slot for future tenants
        slot = self._slot_of.pop(tenant)
        self._slots_gauge.set(len(self._slot_of))
        self.ingestor.reset_slot(slot)
        self._free_slots.append(slot)

    def _tenant_queue_depth(self, tenant_name: str) -> int:
        """Admission input (ISSUE-9): this tenant's pending device-queue
        depth (0 for unassigned/host tenants — nothing device-bound)."""
        slot = self._slot_of.get(tenant_name)
        return 0 if slot is None else len(self._queues[slot])

    def release_tenant(self, tenant_name: str) -> None:
        """Cross-replica migration support (ISSUE-13): free a tenant's
        device slot after its hot-doc ownership moved to another mesh
        replica.  The tenant stays fully servable — `_demote_to_host`
        materializes the host doc from device state first — so existing
        sessions keep their protocol endpoints while the device slot
        follows ownership (`ReplicaMesh.migrate_tenant(...,
        free_source_slot=True)`).  A no-op for tenants that are already
        host-resident or never held a slot."""
        if tenant_name in self._host_tenants:
            return
        if tenant_name not in self._slot_of:
            return
        self._demote_to_host(tenant_name)

    def rebalance_tenant(
        self, tenant_name: str, to_slot: Optional[int] = None
    ) -> int:
        """Move a tenant to a different device slot LIVE (ISSUE-9): the
        mid-soak rebalance a real multi-tenant pod performs when one
        batch slot runs hot.  Returns the new slot.

        Parity-safe by construction: the tenant's full device state
        (pending stash folded in, exactly `device_encode_diff` vs the
        empty state vector) re-ingests into the fresh slot as one wire
        update, whose host planning rebuilds the slot's SV mirror — so
        the move rides the same exactness contract as any other update.
        Mirrored tenants re-ingest from the authoritative host doc
        instead.  Sessions stay connected (slot identity is server
        internal); queued updates flush first so nothing is re-homed
        mid-queue."""
        from ytpu.utils import metrics

        old = self.slot_of(tenant_name)
        if tenant_name in self._host_tenants:
            raise ValueError(f"tenant {tenant_name!r} is host-resident")
        self.flush_device()
        if self.device_authoritative:
            payload = self.device_encode_diff(tenant_name, StateVector())
        else:
            payload = self.doc(tenant_name).encode_state_as_update_v1()
        # allocate the destination BEFORE releasing the source: a full
        # batch must fail the rebalance, not strand the tenant slotless
        if to_slot is None:
            if self._free_slots:
                to_slot = self._free_slots.pop()
            elif self._next_slot < self.ingestor.n_docs:
                to_slot = self._next_slot
                self._next_slot += 1
            else:
                raise DeviceBatchFull(
                    "no free slot to rebalance into "
                    f"({self.ingestor.n_docs} tenant slots)"
                )
        else:
            if not 0 <= to_slot < self.ingestor.n_docs:
                raise ValueError(
                    f"slot {to_slot} out of range "
                    f"({self.ingestor.n_docs} tenant slots)"
                )
            if any(
                t != tenant_name and s == to_slot
                for t, s in self._slot_of.items()
            ):
                raise ValueError(f"slot {to_slot} is already assigned")
            # claim the explicit destination out of the allocator so a
            # later _assign_slot can never hand it to a second tenant:
            # pull it from the free list, or — when it lies beyond the
            # allocation frontier — advance the frontier past it,
            # freeing the slots skipped over
            if to_slot in self._free_slots:
                self._free_slots.remove(to_slot)
            elif to_slot >= self._next_slot:
                self._free_slots.extend(range(self._next_slot, to_slot))
                self._next_slot = to_slot + 1
        self.ingestor.reset_slot(old)
        if old != to_slot:
            self._free_slots.append(old)
        self._slot_of[tenant_name] = to_slot
        self._enqueue(to_slot, payload)
        self.flush_device()
        metrics.counter("sync.rebalances").inc()
        return to_slot

    def tenant_state_vector(self, tenant_name: str) -> StateVector:
        if not self.device_authoritative or tenant_name in self._host_tenants:
            return super().tenant_state_vector(tenant_name)
        return self.device_state_vector(tenant_name)

    def device_state_vector(self, tenant_name: str) -> StateVector:
        """The device mirror's state vector for one tenant (real ids)."""
        slot = self.slot_of(tenant_name)
        return StateVector(dict(self.ingestor.svs[slot].clocks))

    def _remote_matrix(self, slot_svs) -> "tuple[np.ndarray, int]":
        """One [n_docs, n_clients] remote-clock matrix over interned
        clients (n_clients pow2 to bound `encode_diff_batch` retraces),
        with each (slot, StateVector) pair filling its slot's row."""
        interner = self.ingestor.enc.interner
        n_clients = 1
        while n_clients < max(2, len(interner)):
            n_clients *= 2
        remote = np.zeros((self.ingestor.n_docs, n_clients), dtype=np.int32)
        for slot, sv in slot_svs:
            for client, clock in sv:
                idx = interner.to_idx.get(client)
                if idx is not None and idx < n_clients:
                    remote[slot, idx] = clock
        return remote, n_clients

    def _merge_pending(self, slot: int, payload: bytes) -> bytes:
        """Fold a slot's pending stash into an encoded diff, exactly like
        the reference's merge_pending (transaction.rs:247-263)."""
        ing = self.ingestor
        pending = ing.pending_update(slot)
        pending_ds = ing.pending_ds(slot)
        if pending is None and pending_ds is None:
            return payload
        from ytpu.compat import merge_updates
        from ytpu.core.update import Update as _U

        extras = []
        if pending is not None:
            extras.append(pending.encode_v1())
        if pending_ds is not None:
            # stashed delete ranges must reach fresh replicas too
            extras.append(_U({}, pending_ds).encode_v1())
        return merge_updates(payload, *extras)

    def device_encode_diff(
        self, tenant_name: str, remote_sv: StateVector
    ) -> bytes:
        """Sync step 2 answered from device state: `encode_diff_batch`
        masks/offsets on device, the pipelined finisher (`DiffPipeline`,
        ISSUE-10) compacts the shipped rows on device and emits wire
        bytes from ONE packed host tensor, and any pending stash folds in
        exactly like the reference's merge_pending (transaction.rs:
        247-263).  A single tenant takes the pipeline's inline
        one-sub-batch path (no thread hops); `device_encode_diff_many`
        is the fan-out entry that actually overlaps the stages."""
        import jax.numpy as jnp

        from ytpu.models.batch_doc import encode_diff_batch

        self.flush_device()
        ing = self.ingestor
        slot = self.slot_of(tenant_name)
        remote, n_clients = self._remote_matrix([(slot, remote_sv)])
        ship, offsets, _local, deleted = encode_diff_batch(
            ing.state, jnp.asarray(remote), n_clients
        )
        payload = self._diff_pipeline.run(
            ing.state,
            [slot],
            ship,
            offsets,
            deleted,
            ing.enc,
            payloads=ing.payloads,
            root_name=self._root_names.get(tenant_name),
        )[0]
        payload = self._merge_pending(slot, payload)
        self._diffs_encoded.labels(tenant_name).inc()
        return payload

    def device_encode_diff_many(self, requests) -> List[bytes]:
        """Batched sync-step-2 fan-out (ISSUE-10): answer MANY tenants'
        SyncStep1s in one device selection + one pipelined finisher pass
        — the shape a million-user fan-out actually ships.  `requests`
        is an iterable of (tenant_name, StateVector); returns the v1
        payloads in request order.  One request per tenant (two SVs for
        one tenant would collide on the slot's remote-clock row — issue
        separate calls for that)."""
        requests = list(requests)
        if not requests:
            return []
        import jax.numpy as jnp

        from ytpu.models.batch_doc import encode_diff_batch

        self.flush_device()
        ing = self.ingestor
        slots = [self.slot_of(t) for t, _ in requests]
        if len(set(slots)) != len(slots):
            raise ValueError(
                "device_encode_diff_many takes one request per tenant; "
                "duplicate tenants collide on the slot's remote-clock row"
            )
        remote, n_clients = self._remote_matrix(
            [(s, sv) for s, (_, sv) in zip(slots, requests)]
        )
        ship, offsets, _local, deleted = encode_diff_batch(
            ing.state, jnp.asarray(remote), n_clients
        )
        # the native finisher call carries ONE root name: group requests
        # by their tenant's wire root (usually a single group) and run
        # the pipeline per group
        out: List[Optional[bytes]] = [None] * len(requests)
        groups: Dict[Optional[str], List[int]] = {}
        for i, (t, _) in enumerate(requests):
            groups.setdefault(self._root_names.get(t), []).append(i)
        for root, idxs in groups.items():
            res = self._diff_pipeline.run(
                ing.state,
                [slots[i] for i in idxs],
                ship,
                offsets,
                deleted,
                ing.enc,
                payloads=ing.payloads,
                root_name=root,
            )
            for i, p in zip(idxs, res):
                out[i] = self._merge_pending(slots[i], p)
        for t, _ in requests:
            self._diffs_encoded.labels(t).inc()
        return out  # type: ignore[return-value]

    # --- device dispatch -------------------------------------------------------

    def pending_device_updates(self) -> int:
        return sum(len(q) for q in self._queues)

    def flush_device(self, max_steps: Optional[int] = None) -> int:
        """Ship queued updates to the device; one update per slot per step.

        Returns the number of batch steps dispatched. Slots with deeper
        queues keep shipping while others ride as no-ops (the engine's
        padding rows), so a chatty tenant never blocks a quiet one.

        Observability: the `sync.device_queue_depth` gauge reads the
        total queued updates (of every live server in the process) when
        it is scraped, and a device-step
        failure dumps the tracer's flight-recorder ring (`YTPU_TRACE`)
        before re-raising — a kernel abort leaves a replayable trace.
        """
        steps = 0
        while any(self._queues) and (max_steps is None or steps < max_steps):
            # dispatch span (ISSUE-11): names the request trace ids whose
            # updates this batch step ships, so the Chrome trace links a
            # frame's net/admission spans to the device dispatch that
            # integrated it (plus the ambient ctx of whoever flushed).
            # Without the ring the span is still live while `phases` is on.
            if tracer.enabled:
                span = tracer.span(
                    "sync.dispatch",
                    step=steps,
                    traces=[
                        t[0][0] for t in self._queue_traces if t and t[0][0]
                    ],
                )
            else:
                span = tracer.span("sync.dispatch")
            with span:
                # peek, apply, THEN pop — a failing step must not drop the
                # other slots' already-dequeued updates. The apply histogram
                # times the real device step here (the SLO metric), not the
                # enqueue.
                with phases.span("sync.dispatch.peek"):
                    payloads = [q[0] if q else None for q in self._queues]
                    columns = [c[0] if c else None for c in self._queue_columns]
                if phases.enabled:
                    start = time.perf_counter()
                    carried = [t[0][1] for t in self._queue_traces if t]
                    phases.add_value("sync.dispatch_updates", len(carried))
                    phases.add_time(
                        "sync.queue_wait",
                        sum(start - enqueued for enqueued in carried),
                        calls=len(carried),
                    )
                try:
                    with self._apply_hist.time():
                        self.ingestor.apply_bytes(payloads, columns)
                except Exception as e:
                    tracer.dump_on_error(error=e)
                    raise
                with phases.span("sync.dispatch.pop"):
                    # the three lists are in lockstep (`_enqueue`): one
                    # walk over the slots pops all three
                    for q, c, t in zip(
                        self._queues, self._queue_columns, self._queue_traces
                    ):
                        if q:
                            q.pop(0)
                            c.pop(0)
                            t.pop(0)
            steps += 1
        if steps:
            # only a REAL dispatch refreshes the freshness gauge: the
            # serve loop flushes on every frame/idle tick, and an
            # empty-queue flush must not make /healthz report a device
            # that never dispatched as fresh
            self._last_dispatch.set(time.time())
        return steps

    def device_text(self, tenant_name: str) -> str:
        """The device-side rendering of a tenant's root text (for parity
        checks and serving reads off the batch)."""
        from ytpu.models.batch_doc import get_string

        slot = self.slot_of(tenant_name)
        return get_string(self.ingestor.state, slot, self.ingestor.payloads)

    def device_diff(self, tenant_name: str) -> list:
        """Formatted-run rendering (Text.diff() shape) of a tenant's root
        text straight from the device block columns."""
        from ytpu.models.batch_doc import get_diff

        slot = self.slot_of(tenant_name)
        return get_diff(self.ingestor.state, slot, self.ingestor.payloads)

    def device_tree(self, tenant_name: str) -> dict:
        from ytpu.models.batch_doc import get_tree

        slot = self.slot_of(tenant_name)
        return get_tree(
            self.ingestor.state,
            slot,
            self.ingestor.payloads,
            self.ingestor.enc.keys,
            interner=self.ingestor.enc.interner,
        )
