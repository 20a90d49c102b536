"""Raw-offsets byte ingestion (ISSUE-7 tentpole): the host ships raw
concatenated update bytes + a tiny per-update offsets table, the device
gathers the update lanes and decodes the varints itself
(`decode_kernel.gather_raw_lanes` → `replay_chunk_program_raw`), and
per-chunk host staging collapses to a memcpy (`pack_raw_updates_into`).

Coverage: raw-vs-packed byte parity through the async replay (with ≥1
mid-stream compaction), the memcpy-staging invariant (zero per-update
payload reads per chunk), depth>2 pipelining, the gathered-lane matrix's
byte identity with `pack_updates` on streams carrying LIVE MOVES and
mixed content (which pins decode parity for every content kind without
compiling a second decode program), the V2 raw pack, and deferred
decode-error message parity across all three lanes.

Every replay here reuses test_async_overlap's workload and its ONE
(n_docs=2, capacity=256, chunk=16) compiled shape family — this file
sorts immediately after it, so the decode/xla_chunk_step/compaction
programs are already warm; the two chunk programs (raw + host-packed)
are this file's only fresh big traces. The fused interpret test routes
through `tests/_fused_interpret.run_or_skip` and runs LAST.
"""

import numpy as np
import pytest


from _fused_interpret import run_or_skip
from test_async_overlap import CAPACITY, CHUNK, D_BLOCK, N_DOCS, _workload

needs_native = pytest.mark.usefixtures("native_lib")


def _make(ingest: str, lane: str = "xla", interpret: bool = False, **kw):
    from ytpu.models.replay import FusedReplay

    _, _, plan = _workload()
    return FusedReplay(
        n_docs=N_DOCS,
        plan=plan,
        capacity=CAPACITY,
        max_capacity=CAPACITY,  # growth disabled: compaction must carry it
        d_block=D_BLOCK,
        chunk=CHUNK,
        lane=lane,
        interpret=interpret,
        overlap=True,
        ingest=ingest,
        **kw,
    )


# the access-counting payload list is shared with bench's ingest_raw
# rehearsal so the copy-only invariant cannot drift between CI and tests
from bench import _CountingList  # noqa: E402


@needs_native
def test_raw_vs_packed_byte_parity_with_compaction():
    """The raw-offsets lane must be byte-exact vs the host-packed lane
    (and the serial loop's oracle text) on a multi-chunk stream that
    trips ≥1 between-chunk compaction — slot layout permutes under
    compaction, so the decoded text is the byte-exact surface."""
    log, expect, _ = _workload()
    raw = _make(ingest="raw")
    s_raw = raw.run(log)
    packed = _make(ingest="packed")
    s_packed = packed.run(log)
    assert s_raw.ingest == "raw" and s_packed.ingest == "packed"
    assert s_raw.compactions >= 1 and s_packed.compactions >= 1
    assert s_raw.growths == 0, s_raw  # pins the shape-reuse property
    assert s_raw.chunks == s_packed.chunks
    for d in range(N_DOCS):
        assert raw.get_string(d) == packed.get_string(d) == expect
    # the raw lane actually staged the stream's bytes (payload bytes +
    # one EMPTY_UPDATE tail marker per chunk)
    wire_bytes = sum(len(p) for p in log)
    assert s_raw.stage_bytes == wire_bytes + 2 * s_raw.chunks, s_raw
    assert s_packed.stage_bytes == wire_bytes, s_packed


@needs_native
def test_raw_staging_is_copy_only():
    """The memcpy-staging invariant: after the one-time wire-table build
    (an O(bytes) join), per-chunk raw staging performs ZERO per-update
    payload reads — asserted structurally with a counting list, not a
    timer, so it cannot rot into a flaky benchmark."""
    log, expect, _ = _workload()
    counted = _CountingList(log)
    rep = _make(ingest="raw")
    rep.run(counted)
    assert counted.item_reads == 0, (
        f"raw staging read {counted.item_reads} payload items"
    )
    assert rep.get_string(0) == expect


@needs_native
def test_raw_depth3_pipeline():
    """Depth > 2 (free under raw staging): three preallocated raw slots,
    the in-flight cap held at 3, every later chunk re-packing a
    recycled slot — with byte parity."""
    from ytpu.models.replay import plan_overlap

    log, expect, _ = _workload()
    rep = _make(ingest="raw", depth=3)
    op = rep.overlap_plan()
    assert op == plan_overlap(len(log), CHUNK, depth=3)
    assert op.depth == 3 and op.buffers == 3
    stats = rep.run(log)
    assert rep.get_string(0) == expect
    assert 1 <= stats.max_inflight <= 3, stats
    assert stats.buffer_reuses == stats.chunks - 3, stats


@needs_native
def test_gather_raw_lanes_matches_pack_updates_with_moves():
    """The device lane-gather materializes a byte-IDENTICAL matrix to
    host `pack_updates` — including the zero mask past each lane's
    length that the decoder's prefix sums and gather guard read. Driven
    on a stream with LIVE MOVES, map rows, and Any content, this pins
    raw-vs-packed decode parity for every content kind the V1 decoder
    supports without compiling a second decode program."""
    import jax.numpy as jnp

    from ytpu.core import Doc
    from ytpu.models.replay import build_wire_table, raw_chunk_cap
    from ytpu.ops.decode_kernel import (
        gather_raw_lanes,
        pack_raw_updates_into,
        pack_updates,
    )

    doc = Doc(client_id=1)
    log = []
    doc.observe_update_v1(lambda p, o, t: log.append(p))
    arr = doc.get_array("a")
    with doc.transact() as txn:
        for v in range(12):
            arr.push_back(txn, v)
    for r in range(4):
        with doc.transact() as txn:
            arr.move_range_to(txn, 1, 3, len(arr) - 1)  # live moves
        with doc.transact() as txn:
            arr.insert(txn, 2, {"k": 100 + r})  # map-shaped Any content
        with doc.transact() as txn:
            arr.remove_range(txn, 3, 2)
    width = max(len(p) for p in log) + 16
    buf, lens = pack_updates(log, pad_to=width)
    wire, woffs = build_wire_table(log)
    chunk = len(log)
    cap = raw_chunk_cap(woffs, chunk)
    raw = np.zeros(cap, dtype=np.uint8)
    offs = np.zeros(chunk, dtype=np.int32)
    rlens = np.zeros(chunk, dtype=np.int32)
    pack_raw_updates_into(wire, woffs, 0, chunk, raw, offs, rlens, width=width)
    assert rlens.tolist() == lens.tolist()
    gathered = np.asarray(
        gather_raw_lanes(
            jnp.asarray(raw), jnp.asarray(offs), jnp.asarray(rlens), width
        )
    )
    assert (gathered == buf).all(), "gathered lane matrix != host-packed"
    # a short tail chunk decodes as EMPTY_UPDATE at the compiled shape
    pack_raw_updates_into(
        wire, woffs, 1, chunk, raw, offs, rlens, width=width
    )
    assert rlens[chunk - 1] == 2 and offs[chunk - 1] == int(
        woffs[chunk] - woffs[1]
    )
    with pytest.raises(ValueError, match="exceeds staging width"):
        pack_raw_updates_into(
            wire, woffs, 0, chunk, raw, offs, rlens, width=8
        )
    with pytest.raises(ValueError, match="exceeds staging capacity"):
        pack_raw_updates_into(
            wire, woffs, 0, chunk, raw[:8], offs, rlens, width=width
        )


@needs_native
def test_pack_updates_v2_raw_matches_packed():
    """The V2 raw pack ships the same bytes the padded V2 matrix holds:
    gathering the flat arena at the staged row extents reproduces
    `pack_updates_v2`'s matrix byte-for-byte (cold sidecars included —
    their refs point PAST the payload length, so the gather mask uses
    the staged extent, not the decode length)."""
    import jax.numpy as jnp

    from ytpu.core import Doc, Update
    from ytpu.ops.decode_kernel import gather_raw_lanes
    from ytpu.ops.decode_v2 import pack_updates_v2, pack_updates_v2_raw

    doc = Doc(client_id=5)
    log = []
    doc.observe_update_v1(lambda p, o, t: log.append(p))
    txt = doc.get_text("text")
    for i in range(4):
        with doc.transact() as txn:
            txt.insert(txn, i, "abcd"[i])
    with doc.transact() as txn:
        # Format content is a COLD kind: exercises the sidecar extent
        txt.format(txn, 0, 2, {"bold": True})
    v2 = [Update.decode_v1(p).encode_v2() for p in log]
    buf, lens, spans, side = pack_updates_v2(v2)
    wire, offs, row_lens, rlens, rspans, rside, width = pack_updates_v2_raw(v2)
    assert width == buf.shape[1]
    assert rlens.tolist() == lens.tolist()
    assert (rspans == spans).all()
    assert (side is None) == (rside is None)
    if side is not None:
        assert (rside == side).all()
    gathered = np.asarray(
        gather_raw_lanes(
            jnp.asarray(wire),
            jnp.asarray(offs),
            jnp.asarray(row_lens),
            width,
        )
    )
    assert (gathered == buf).all(), "V2 gathered matrix != host-packed"


@needs_native
def test_raw_deferred_decode_error_exact_message_parity():
    """A truncated update through the raw lane surfaces DEFERRED (the
    on-device varint decode ORs its flags into the sticky scalar) but
    the host re-identification must raise the serial loop's EXACT
    message — same contract as the packed lane (satellite of ISSUE-7)."""
    from ytpu.models.replay import FusedReplay

    log, _, plan = _workload()
    bad = list(log)
    bad[23] = bad[23][: len(bad[23]) // 2]  # truncation → FLAG_MALFORMED
    serial = FusedReplay(
        n_docs=N_DOCS, plan=plan, capacity=CAPACITY, max_capacity=CAPACITY,
        d_block=D_BLOCK, chunk=CHUNK, lane="xla",
    )
    with pytest.raises(RuntimeError, match="flagged updates") as serial_err:
        serial.run(bad)
    with pytest.raises(RuntimeError, match="flagged updates") as raw_err:
        _make(ingest="raw").run(bad)
    with pytest.raises(RuntimeError, match="flagged updates") as packed_err:
        _make(ingest="packed").run(bad)
    assert str(raw_err.value) == str(serial_err.value) == str(packed_err.value)
    assert "[23]" in str(raw_err.value)


@needs_native
def test_raw_ingest_dry_run_contract():
    """bench's host-only raw-ingest rehearsal: copy-only staging,
    depth-3 plan held, and the staging speedup recorded (the CI guard
    that catches a staging regression before a device round)."""
    import bench as _bench

    log, _, _ = _workload()
    out = _bench.ingest_raw_dry_run(log[: 6 * CHUNK], chunk=CHUNK, depth=3)
    assert out["copy_only_staging"] is True
    assert out["depth"] == 3 and out["buffers"] == 3
    assert out["n_chunks"] == 6 and out["max_inflight"] <= 3
    assert out["stage_speedup_vs_packed"] > 1.5
    assert out["stage_bytes_per_s"] > 0
    assert 0.0 <= out["stall_fraction"] <= 1.0


@needs_native
def test_raw_fused_interpret_or_skip():
    """The fused Pallas lane fed by the raw chunk program — or a SKIP
    when this container's jax cannot interpret the kernel (memoized
    across files by tests/_fused_interpret)."""
    log, _, _ = _workload()
    prefix = log[: 2 * CHUNK]
    oracle = _make(ingest="packed")
    oracle.run(prefix)
    rep = _make(ingest="raw", lane="fused", interpret=True)
    run_or_skip(lambda: rep.run(prefix))
    for d in range(N_DOCS):
        assert rep.get_string(d) == oracle.get_string(d)
