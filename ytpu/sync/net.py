"""TCP transport for the y-sync protocol (SURVEY §5.8).

The reference keeps sockets out of the core crate (ecosystem providers —
yrs-warp etc. — supply transports over the transport-agnostic `Protocol`,
sync/protocol.rs:8-31). ytpu ships one batteries-included transport so the
multi-tenant server is usable end to end without extra dependencies:
asyncio TCP with lib0-style framing.

Wire format per connection:
- client → server, first frame: the tenant/room name (UTF-8);
- every frame after that, both directions: one y-sync / Awareness message
  exactly as `Protocol` encodes it;
- a frame is a lib0 var-uint length followed by that many bytes (the same
  `write_buf` layout the protocol messages use internally).

One `SyncServer` (or `DeviceSyncServer`) instance serves all connections;
each connection becomes a `Session`. Replies go straight back; broadcasts
land in the other sessions' outboxes, and every connection handler pushes
its OWN outbox to its socket after each processed frame or `idle_flush`
wakeup (one writer per task — no cross-coroutine drain races). With a
`DeviceSyncServer`, `flush_every` controls how often queued updates ship
to the device batch.
"""

from __future__ import annotations

import asyncio
import random
from typing import Optional, Tuple

from ytpu.encoding.lib0 import EncodingError, Writer
from ytpu.sync.protocol import (
    MSG_TRACE,
    Message,
    PermissionDenied,
    SyncMessage,
    UnsupportedMessage,
    decode_trace,
    message_reader,
    trace_message,
)
from ytpu.sync.server import DeviceBatchFull, SyncServer
from ytpu.utils import metrics, trace_context, tracer
from ytpu.utils.faults import faults
from ytpu.utils.trace import current_trace, resume_trace

# transport series (module-cached children: zero lookups per frame)
_FRAMES_IN = metrics.counter("net.frames_in")
_FRAMES_OUT = metrics.counter("net.frames_out")
_BYTES_IN = metrics.counter("net.bytes_in")
_BYTES_OUT = metrics.counter("net.bytes_out")
_CONNECTIONS = metrics.gauge("net.connections")
# resilience series (ISSUE-6, docs/robustness.md)
_FRAME_TIMEOUTS = metrics.counter("net.frame_timeouts")
metrics.counter("net.bad_frames")  # counted through _session_dropped()
_CONNECT_RETRIES = metrics.counter("net.connect_retries")
_RECONNECTS = metrics.counter("net.reconnects")
# per-session serving series (ISSUE-9): how many sessions are live right
# now, and — when one drops — WHY, so soak shed decisions are
# attributable from the one-line bench JSON (reasons: "bad_frame" for
# frames that failed to parse/apply, "timeout" for mid-frame stalls,
# "disconnect" for abortive transport closes that sent no bad frame,
# "shed" from admission/slow-consumer eviction in sync/server,
# "update_drop" for policy=drop refusals that keep the session,
# "failover" for sessions a killed replica dropped wholesale — they
# reconnect to a mesh survivor, ISSUE-13)
_SESSIONS_ACTIVE = metrics.gauge("net.sessions_active")
metrics.counter("net.sessions_dropped", labelnames=("reason",))


def _session_dropped(reason: str) -> None:
    """Count one dropped session by reason (and the bad frame behind a
    ``bad_frame`` drop). A drop is rare, so the families are looked up
    here, not cached at import like the per-frame series above: a
    test-time ``metrics.reset()`` orphans a cached family, and the
    exposition then misses the very series an operator reads after an
    incident."""
    if reason == "bad_frame":
        metrics.counter("net.bad_frames").inc()
    metrics.counter("net.sessions_dropped", labelnames=("reason",)).labels(
        reason
    ).inc()


class FrameTimeout(ConnectionError):
    """A peer stalled mid-frame past the whole-frame deadline.  The
    stream is desynced by construction (part of the frame was consumed)
    — the connection must be dropped; a reconnect resyncs via the
    state-vector handshake."""


# protocol-level garbage from a peer tears the connection down quietly
# (FrameTimeout is a ConnectionError: a stalled peer is peer-local too)
_PEER_ERRORS = (
    asyncio.IncompleteReadError,
    ConnectionError,
    EncodingError,
    UnsupportedMessage,
    PermissionDenied,
    UnicodeDecodeError,
    ValueError,
)

__all__ = [
    "serve",
    "SyncClient",
    "FrameTimeout",
    "connect_with_backoff",
    "read_frame",
    "write_frame",
]

_MAX_FRAME = 64 * 1024 * 1024

#: whole-frame deadline default: generous enough for a 64 MiB frame on a
#: slow link, small enough that a wedged peer frees its session the same
#: minute (override per call site)
FRAME_DEADLINE = 30.0


async def read_frame(
    reader: asyncio.StreamReader,
    first_byte_timeout: Optional[float] = None,
    frame_timeout: Optional[float] = FRAME_DEADLINE,
) -> Optional[bytes]:
    """One varint-length-prefixed frame; None on clean EOF or first-byte
    timeout.

    `first_byte_timeout` is the idle poll: no frame has started, so
    timing out is clean (None).  `frame_timeout` is the whole-frame
    deadline covering everything AFTER the first byte — a peer that
    stalls mid-frame used to hang the reader forever (the old timeout
    covered only the first byte).  Hitting it raises `FrameTimeout`: the
    partially-consumed frame has desynced the stream, so the connection
    is unusable and must be dropped (counted in `net.frame_timeouts`)."""
    stall = faults.delay_s("net.delay")
    if stall:
        await asyncio.sleep(stall)
    first = reader.read(1)
    if first_byte_timeout is not None:
        try:
            b = await asyncio.wait_for(first, first_byte_timeout)
        except asyncio.TimeoutError:
            return None
    else:
        b = await first
    if not b:
        return None  # clean EOF between frames

    async def rest() -> bytes:
        nonlocal b
        shift = 0
        size = 0
        header = 0
        while True:
            header += 1
            size |= (b[0] & 0x7F) << shift
            shift += 7
            if b[0] < 0x80:
                break
            if shift > 63:
                raise ConnectionError("oversized frame varint")
            b = await reader.read(1)
            if not b:
                # EOF inside a length prefix is truncation, not a clean
                # close
                raise ConnectionError("eof inside frame header")
        if size > _MAX_FRAME:
            raise ConnectionError(f"frame of {size} bytes exceeds limit")
        data = await reader.readexactly(size)
        _FRAMES_IN.inc()
        # header + payload, matching bytes_out (which counts the framed
        # write): the two series used to disagree by the varint prefix
        _BYTES_IN.inc(header + len(data))
        return data

    if frame_timeout is None:
        return await rest()
    try:
        return await asyncio.wait_for(rest(), frame_timeout)
    except asyncio.TimeoutError:
        _FRAME_TIMEOUTS.inc()
        raise FrameTimeout(
            f"peer stalled mid-frame past the {frame_timeout}s deadline"
        ) from None


def write_frame(writer: asyncio.StreamWriter, payload: bytes) -> None:
    if faults.active:
        if faults.fire("net.drop") is not None:
            return  # injected frame loss: nothing reaches the wire
        if faults.fire("net.truncate") is not None:
            # header + half the payload: the reader sees a started frame
            # that never completes — the whole-frame deadline's shape
            w = Writer()
            w.write_buf(payload)
            buf = w.to_bytes()
            cut = buf[: max(1, len(buf) - max(1, len(payload) // 2))]
            _BYTES_OUT.inc(len(cut))
            writer.write(cut)
            return
    w = Writer()
    w.write_buf(payload)
    buf = w.to_bytes()
    _FRAMES_OUT.inc()
    _BYTES_OUT.inc(len(buf))
    writer.write(buf)


async def serve(
    server: SyncServer,
    host: str = "127.0.0.1",
    port: int = 0,
    flush_every: int = 1,
    idle_flush: float = 0.2,
    frame_deadline: Optional[float] = FRAME_DEADLINE,
) -> Tuple[asyncio.AbstractServer, int]:
    """Start serving; returns (asyncio server, bound port).

    `idle_flush`: how long a connection may sit idle before its own queued
    broadcasts are pushed out anyway. Each handler writes ONLY its own
    socket — a broadcast enqueued by another connection's frame (or by an
    in-process write: server-side transaction, replica link) ships on this
    connection's next frame or idle wakeup. One writer per task means no
    two coroutines ever await drain() on the same transport.

    Error isolation (ISSUE-6): every failure inside one connection's
    handler — peer garbage, a mid-frame stall past `frame_deadline`, or
    an unexpected server-side exception while processing a frame — is
    confined to that session: the session is dropped (and counted in
    `net.bad_frames` when a frame triggered it) while the accept loop
    and every other session keep serving."""

    async def handle(reader: asyncio.StreamReader, writer: asyncio.StreamWriter):
        session = None
        frames_seen = 0
        _CONNECTIONS.inc()
        try:
            # the hello needs a FIRST-byte deadline too: frame_timeout
            # only starts after byte one, so a connect-and-say-nothing
            # peer would otherwise pin this handler (and its socket)
            # forever
            hello = await read_frame(
                reader,
                first_byte_timeout=frame_deadline,
                frame_timeout=frame_deadline,
            )
            if hello is None:
                return
            tenant = hello.decode("utf-8")
            try:
                session, greeting = server.connect_frames(tenant)
            except DeviceBatchFull:
                return  # capacity: reject quietly
            _SESSIONS_ACTIVE.inc()
            for frame in greeting:
                write_frame(writer, frame)
            await writer.drain()
            pending_trace = None  # wire trace ctx riding ahead of one frame
            while True:
                frame = await read_frame(
                    reader,
                    first_byte_timeout=idle_flush,
                    frame_timeout=frame_deadline,
                )
                if frame is None:
                    if reader.at_eof():
                        break
                elif frame and frame[0] == MSG_TRACE:
                    # wire trace-context extension (ISSUE-15): consumed
                    # at the transport, applies to the NEXT frame only —
                    # the frame that follows re-enters the sender's
                    # trace instead of minting a fresh id
                    if tracer.enabled:
                        try:
                            _v, _tid, _torigin = decode_trace(
                                next(message_reader(frame)).body
                            )
                            pending_trace = (_tid, _torigin)
                        except Exception:
                            pending_trace = None
                else:
                    # end-to-end request tracing (ISSUE-11): ONE trace id
                    # per inbound frame, carried by the ambient context
                    # through admission → apply/queue → device dispatch →
                    # reply, so a YTPU_TRACE dump shows the frame's full
                    # host-side life. Disabled tracer = shared no-op
                    # context, zero per-frame allocation.  A wire trace
                    # context that preceded this frame resumes the
                    # SENDER's id (ISSUE-15 cross-replica propagation).
                    tr, pending_trace = pending_trace, None
                    if tr is not None and tracer.enabled:
                        tctx = resume_trace(
                            tr[0], tr[1], tenant=tenant, session=session.id
                        )
                    else:
                        tctx = trace_context(tenant=tenant, session=session.id)
                    with tctx:
                        try:
                            with tracer.span("net.frame", bytes=len(frame)):
                                replies = server.receive_frames(
                                    session, frame
                                )
                            with tracer.span(
                                "net.reply", frames=len(replies)
                            ):
                                for f in replies:
                                    write_frame(writer, f)
                        except _PEER_ERRORS:
                            # malformed frame: this session's problem only
                            _session_dropped("bad_frame")
                            break
                        except Exception as e:
                            # a server-side bug triggered by one frame
                            # must not escape into asyncio's exception
                            # handler N times per reconnect storm; the
                            # session drops, the accept loop lives — and
                            # the flight recorder keeps what threw
                            # (bounded ring)
                            _session_dropped("bad_frame")
                            tracer.instant(
                                "net.bad_frame",
                                error=repr(e),
                                tenant=session.tenant,
                                session=session.id,
                            )
                            break
                        frames_seen += 1
                        if flush_every and frames_seen % flush_every == 0:
                            flush = getattr(server, "flush_device", None)
                            if flush is not None:
                                flush()
                # own outbox only (frame processed or idle wakeup)
                for payload in server.drain(session):
                    write_frame(writer, payload)
                await writer.drain()
                if session.dead:
                    break  # slow consumer: evicted by Session.push
        except FrameTimeout:
            # mid-frame stall past the deadline: attributable separately
            # from peer garbage (FrameTimeout IS a ConnectionError, so it
            # must be caught before the generic peer-error band)
            if session is not None:
                _session_dropped("timeout")
        except _PEER_ERRORS:
            # this band is mostly abortive transport closes (RST, EOF
            # inside a header) — a real malformed FRAME is counted
            # bad_frame at the receive loop above; conflating the two
            # would mis-attribute plain peer deaths in a churny soak
            if session is not None:
                _session_dropped("disconnect")
        finally:
            _CONNECTIONS.dec()
            if session is not None:
                _SESSIONS_ACTIVE.dec()
                server.disconnect(session)
            writer.close()

    srv = await asyncio.start_server(handle, host, port)
    bound = srv.sockets[0].getsockname()[1]
    return srv, bound


async def connect_with_backoff(
    host: str,
    port: int,
    retries: int = 4,
    backoff: float = 0.05,
    backoff_max: float = 2.0,
) -> Tuple[asyncio.StreamReader, asyncio.StreamWriter]:
    """`asyncio.open_connection` under the hardened-transport defaults
    (ISSUE-6): a refused/unreachable connect retries up to `retries`
    times with exponential backoff + full jitter (`backoff`·2^k capped
    at `backoff_max`, each × U[0.5, 1.5)) so a thundering herd of
    reconnecting peers spreads out.  Re-attempts count in
    `net.connect_retries`.  Shared by `SyncClient.connect` and the
    replica-mesh links (`ytpu.sync.replica`), so client and
    server↔server dialing can never drift apart."""
    delay = backoff
    attempt = 0
    while True:
        try:
            return await asyncio.open_connection(host, port)
        except OSError:
            if attempt >= retries:
                raise
            attempt += 1
            _CONNECT_RETRIES.inc()
            await asyncio.sleep(delay * (0.5 + random.random()))
            delay = min(delay * 2, backoff_max)


class SyncClient:
    """Minimal asyncio client: sync a local `Doc` with a served tenant.

    The client half of the handshake (sync/protocol.rs default handlers):
    send SyncStep1, answer the server's SyncStep1 with SyncStep2, apply
    its SyncStep2/Update messages, and push local edits as Updates.
    """

    def __init__(self, doc):
        self.doc = doc
        self.reader: Optional[asyncio.StreamReader] = None
        self.writer: Optional[asyncio.StreamWriter] = None
        self._unsub = None
        self._endpoint: Optional[Tuple[str, int, str]] = None

    async def connect(
        self,
        host: str,
        port: int,
        tenant: str,
        retries: int = 4,
        backoff: float = 0.05,
        backoff_max: float = 2.0,
    ) -> None:
        """Open the connection and start the handshake.

        A refused/unreachable connect retries up to `retries` times with
        exponential backoff + full jitter (`backoff`·2^k, capped at
        `backoff_max`, each multiplied by U[0.5, 1.5)) so a thundering
        herd of reconnecting clients spreads out (`net.connect_retries`
        counts the re-attempts).  The SyncStep1 sent here carries the
        doc's CURRENT state vector, so the same call is the resync path:
        after a reconnect the server's SyncStep2 fills exactly the gap."""
        self.reader, self.writer = await connect_with_backoff(
            host, port, retries=retries, backoff=backoff,
            backoff_max=backoff_max,
        )
        self._endpoint = (host, port, tenant)
        write_frame(self.writer, tenant.encode("utf-8"))
        write_frame(
            self.writer,
            Message.sync(SyncMessage.step1(self.doc.state_vector())).encode_v1(),
        )
        await self.writer.drain()

        def on_update(payload: bytes, origin, txn) -> None:
            if origin == "net":
                return  # do not echo remote updates back
            if tracer.enabled:
                # ship the ambient trace id ahead of the update
                # (ISSUE-15): the server resumes it around the apply,
                # and every peer rebroadcast carries it onward
                ctx = current_trace()
                if ctx is not None:
                    write_frame(
                        self.writer,
                        trace_message(
                            str(ctx.get("trace", "")),
                            str(ctx.get("replica", "") or ""),
                        ).encode_v1(),
                    )
            write_frame(
                self.writer,
                Message.sync(SyncMessage.update(payload)).encode_v1(),
            )

        self._unsub = self.doc.observe_update_v1(on_update)

    async def reconnect(self, **connect_kw) -> None:
        """Reconnect-with-resync after a dropped/desynced connection
        (FrameTimeout, eviction, transport error): tear down the old
        transport and redo `connect` to the remembered endpoint — the
        state-vector handshake pulls whatever this client missed while
        disconnected, and pending local edits re-ship on the doc's next
        update (counted in `net.reconnects`)."""
        if self._endpoint is None:
            raise RuntimeError("reconnect before a successful connect")
        host, port, tenant = self._endpoint
        await self.close()
        await self.connect(host, port, tenant, **connect_kw)
        # counted only once connect() succeeded: the metric's contract
        # is reconnect-with-resync, not reconnect attempts
        _RECONNECTS.inc()

    async def pump(
        self,
        max_frames: int = 1,
        timeout: float = 2.0,
        frame_timeout: Optional[float] = FRAME_DEADLINE,
    ) -> int:
        """Process up to `max_frames` inbound frames; returns the count.

        `timeout` is the idle first-byte poll (no frame = return early);
        `frame_timeout` is the whole-frame deadline — a server that
        stalls mid-frame raises `FrameTimeout` instead of hanging this
        client forever (reconnect() is the recovery)."""
        n = 0
        while n < max_frames:
            frame = await read_frame(
                self.reader,
                first_byte_timeout=timeout,
                frame_timeout=frame_timeout,
            )
            if frame is None:
                break
            for msg in message_reader(frame):
                if msg.kind != 0:
                    continue  # presence et al. — not this client's concern
                body = msg.body
                if body.tag == 0:  # server's SyncStep1 → reply SyncStep2
                    diff = self.doc.encode_state_as_update_v1(body.payload)
                    write_frame(
                        self.writer,
                        Message.sync(SyncMessage.step2(diff)).encode_v1(),
                    )
                    await self.writer.drain()
                else:  # SyncStep2 / Update → apply
                    self.doc.apply_update_v1(body.payload, origin="net")
            n += 1
        return n

    async def flush(self) -> None:
        if self.writer is not None:
            await self.writer.drain()

    async def close(self) -> None:
        if self._unsub is not None:
            self._unsub()
            self._unsub = None
        if self.writer is not None:
            self.writer.close()
            try:
                await self.writer.wait_closed()
            except Exception:
                pass
