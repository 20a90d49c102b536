"""Native (C++) batched encode finisher — byte parity vs the Python finisher.

`finish_encode_diff_batch` must emit byte-identical v1 payloads to
`finish_encode_diff` for every supported row shape (VERDICT r2 #6;
reference equivalent: store.rs:204-248). Docs outside the native scope
fall back per doc, so the batch API is *always* byte-equal; these tests
additionally pin that the native path (not the fallback) handled the
common shapes, via the library's status codes.
"""

import jax
import numpy as np
import pytest

from ytpu.core import Doc, StateVector, Update
from ytpu.models.batch_doc import (
    BatchEncoder,
    apply_update_batch,
    encode_diff_batch,
    finish_encode_diff,
    finish_encode_diff_batch,
    init_state,
)

needs_native = pytest.mark.usefixtures("native_lib")


def build_device_docs(edit_fns, capacity=128, root="text"):
    """Host docs per slot + a device-state mirror (enc, state)."""
    docs, logs = [], []
    for i, fn in enumerate(edit_fns):
        d = Doc(client_id=i + 1)
        log = []
        d.observe_update_v1(lambda p, o, t, log=log: log.append(p))
        fn(d)
        docs.append(d)
        logs.append(log)
    enc = BatchEncoder(root_name=root)
    state = init_state(len(docs), capacity)
    max_steps = max(len(lg) for lg in logs)
    for step in range(max_steps):
        updates = [
            Update.decode_v1(lg[step]) if step < len(lg) else None for lg in logs
        ]
        batch = enc.build_batch(updates, n_rows=8, n_dels=4)
        state = apply_update_batch(state, batch, enc.interner.rank_table())
    assert int(np.asarray(state.error).max()) == 0
    return docs, state, enc


def diff_arrays(state, enc, remote):
    n_clients = remote.shape[1]
    ship, offsets, _sv, deleted = jax.tree_util.tree_map(
        np.asarray, encode_diff_batch(state, remote, n_clients)
    )
    return ship, offsets, deleted


def assert_parity(state, docs_idx, ship, offsets, deleted, enc, payloads=None):
    native = finish_encode_diff_batch(
        state, docs_idx, ship, offsets, deleted, enc, payloads
    )
    for i, d in enumerate(docs_idx):
        oracle = finish_encode_diff(
            state, d, ship, offsets, deleted, enc, payloads
        )
        assert native[i] == oracle, (
            f"doc {d}: native {native[i].hex()} != python {oracle.hex()}"
        )
    return native


def native_statuses(state, docs_idx, ship, offsets, deleted, enc, payloads=None):
    """Which docs the C++ core handled itself (0) vs punted (1).  Reads
    the module's `LAST_FINISH_STATUSES` introspection surface — the
    vectorized span readout (ISSUE-10) no longer makes per-doc
    `ytpu_finish_status` calls a spy could intercept."""
    from ytpu.models import batch_doc as bd

    bd.finish_encode_diff_batch(
        state, docs_idx, ship, offsets, deleted, enc, payloads
    )
    return list(bd.LAST_FINISH_STATUSES)


@needs_native
def test_text_parity_full_state():
    def edits(chunks):
        def fn(d):
            t = d.get_text("text")
            for pos, chunk in chunks:
                with d.transact() as txn:
                    t.insert(txn, pos, chunk)

        return fn

    docs, state, enc = build_device_docs(
        [
            edits([(0, "hello"), (5, " world")]),
            edits([(0, "doc-two"), (3, "✓🙂")]),
            edits([(0, "abc"), (0, "xyz"), (3, "mid")]),
        ]
    )
    remote = np.zeros((len(docs), 8), dtype=np.int32)
    ship, offsets, deleted = diff_arrays(state, enc, remote)
    payloads_list = assert_parity(
        state, list(range(len(docs))), ship, offsets, deleted, enc
    )
    # each payload replays into a correct replica
    for i, doc in enumerate(docs):
        replica = Doc(client_id=99)
        replica.apply_update_v1(payloads_list[i])
        assert (
            replica.get_text("text").get_string()
            == doc.get_text("text").get_string()
        )
    # the native core (not the Python fallback) must have produced these
    assert native_statuses(
        state, list(range(len(docs))), ship, offsets, deleted, enc
    ) == [0, 0, 0]


@needs_native
def test_text_parity_offset_trimmed():
    """A remote with partial coverage forces first-block offset trimming,
    including a boundary inside a surrogate pair."""

    def fn(d):
        t = d.get_text("text")
        with d.transact() as txn:
            t.insert(txn, 0, "ab🙂cd")  # 🙂 = 2 UTF-16 units at clocks 2-3

    docs, state, enc = build_device_docs([fn])
    cidx = enc.interner.to_idx[1]
    for cut in (1, 2, 3, 4):  # clock 3 lands inside the surrogate pair
        remote = np.zeros((1, 8), dtype=np.int32)
        remote[0, cidx] = cut
        ship, offsets, deleted = diff_arrays(state, enc, remote)
        assert_parity(state, [0], ship, offsets, deleted, enc)


@needs_native
def test_delete_set_parity():
    def fn(d):
        t = d.get_text("text")
        with d.transact() as txn:
            t.insert(txn, 0, "0123456789")
        with d.transact() as txn:
            t.remove_range(txn, 2, 3)
        with d.transact() as txn:
            t.remove_range(txn, 4, 2)

    docs, state, enc = build_device_docs([fn])
    remote = np.zeros((1, 8), dtype=np.int32)
    ship, offsets, deleted = diff_arrays(state, enc, remote)
    out = assert_parity(state, [0], ship, offsets, deleted, enc)
    replica = Doc(client_id=99)
    replica.apply_update_v1(out[0])
    assert (
        replica.get_text("text").get_string()
        == docs[0].get_text("text").get_string()
    )


@needs_native
def test_map_and_any_parity():
    """Map rows (parent_sub keys), ContentAny scalars/arrays, binary and
    embed payloads — host refs resolved through the pre-baked arenas."""
    from ytpu.types.shared import MapPrelim

    def fn(d):
        m = d.get_map("m")
        with d.transact() as txn:
            m.insert(txn, "name", "alice")
        with d.transact() as txn:
            m.insert(txn, "age", 31)
        with d.transact() as txn:
            m.insert(txn, "raw", b"\x01\x02")
        with d.transact() as txn:
            m.insert(txn, "flags", [True, None, 2.5, "s"])
        with d.transact() as txn:
            m.insert(txn, "nested", MapPrelim({"x": "y"}))

    docs, state, enc = build_device_docs([fn], root="m")
    remote = np.zeros((1, 8), dtype=np.int32)
    ship, offsets, deleted = diff_arrays(state, enc, remote)
    out = assert_parity(state, [0], ship, offsets, deleted, enc)
    replica = Doc(client_id=99)
    replica.apply_update_v1(out[0])
    assert replica.get_map("m").to_json() == docs[0].get_map("m").to_json()


@needs_native
def test_rich_text_parity():
    """Format marks + embeds (host content blobs)."""

    def fn(d):
        t = d.get_text("text")
        with d.transact() as txn:
            t.insert(txn, 0, "plain ")
        with d.transact() as txn:
            t.insert_with_attributes(txn, 6, "bold", {"b": True})
        with d.transact() as txn:
            t.insert_embed(txn, 4, {"img": "x.png"})

    docs, state, enc = build_device_docs([fn])
    remote = np.zeros((1, 8), dtype=np.int32)
    ship, offsets, deleted = diff_arrays(state, enc, remote)
    out = assert_parity(state, [0], ship, offsets, deleted, enc)
    replica = Doc(client_id=99)
    replica.apply_update_v1(out[0])
    assert replica.get_text("text").diff() == docs[0].get_text("text").diff()


@needs_native
def test_wire_ref_parity_fast_lane():
    """Rows ingested via the raw-bytes lane carry chunked (<= -2) refs into
    the retained wire bytes; the native finisher re-emits their spans."""
    from ytpu.models.ingest import BatchIngestor

    doc = Doc(client_id=5)
    log = []
    doc.observe_update_v1(lambda p, o, t: log.append(p))
    t = doc.get_text("text")
    with doc.transact() as txn:
        t.insert(txn, 0, "chunky")
    with doc.transact() as txn:
        t.insert(txn, 6, " refs 🙂π")
    with doc.transact() as txn:
        t.remove_range(txn, 2, 3)

    ing = BatchIngestor(n_docs=2, capacity=128)
    for p in log:
        ing.apply_bytes([p, p])
    assert ing.fast_docs == 2 * len(log)

    n_clients = max(8, len(ing.enc.interner))
    import jax.numpy as jnp

    for cut in (0, 3, 8):  # 8 lands mid-emoji in the second block
        remote = np.zeros((2, n_clients), dtype=np.int32)
        cidx = ing.enc.interner.to_idx[5]
        remote[1, cidx] = cut
        ship, offsets, _sv, deleted = map(
            np.asarray,
            encode_diff_batch(ing.state, jnp.asarray(remote), n_clients),
        )
        out = assert_parity(
            state=ing.state,
            docs_idx=[0, 1],
            ship=ship,
            offsets=offsets,
            deleted=deleted,
            enc=ing.enc,
            payloads=ing.payloads,
        )
        fresh = Doc(client_id=77)
        fresh.apply_update_v1(out[0])
        assert fresh.get_text("text").get_string() == t.get_string()


@needs_native
def test_wire_any_canonicalization_parity():
    """A hand-crafted update carrying non-canonical Any encodings (FLOAT32
    2.0, BIGINT 5 — both inside the INTEGER-safe range) must re-encode
    through the diff path exactly like Python's read_any → write_any round
    trip, whichever lane decoded it (VERDICT r3 review finding #2)."""
    import struct

    from ytpu.encoding.lib0 import Writer
    from ytpu.models.ingest import BatchIngestor

    w = Writer()
    w.write_var_uint(1)  # clients
    w.write_var_uint(1)  # blocks
    w.write_var_uint(99)  # client id
    w.write_var_uint(0)  # start clock
    w.write_u8(8)  # info: CONTENT_ANY, no origins, no parent_sub
    w.write_var_uint(1)  # parent_info: root name
    w.write_string("text")
    w.write_var_uint(3)  # Any count
    w.write_u8(124)  # FLOAT32 tag
    w.write_raw(struct.pack(">f", 2.0))  # canonical form would be INTEGER
    w.write_u8(122)  # BIGINT tag
    w.write_raw(struct.pack(">q", 5))  # canonical form would be INTEGER
    w.write_u8(124)  # FLOAT32 tag
    w.write_raw(struct.pack(">f", 2.5))  # stays FLOAT32
    w.write_var_uint(0)  # empty delete set
    payload = w.to_bytes()

    # sanity: the host oracle accepts it
    oracle = Doc(client_id=1)
    oracle.apply_update_v1(payload)

    ing = BatchIngestor(n_docs=1, capacity=64)
    ing.apply_bytes([payload])
    assert int(np.asarray(ing.state.error).max()) == 0

    import jax.numpy as jnp

    n_clients = max(8, len(ing.enc.interner))
    remote = np.zeros((1, n_clients), dtype=np.int32)
    ship, offsets, _sv, deleted = map(
        np.asarray,
        encode_diff_batch(ing.state, jnp.asarray(remote), n_clients),
    )
    out = assert_parity(
        ing.state, [0], ship, offsets, deleted, ing.enc, ing.payloads
    )
    # canonicalized payload still replays
    fresh = Doc(client_id=2)
    fresh.apply_update_v1(out[0])
    assert fresh.state_vector().get(99) == 3


@needs_native
def test_multi_client_ordering_parity():
    """Concurrent edits from several clients: per-update client sections
    must come out sorted by real client id descending, clocks ascending."""
    d1 = Doc(client_id=3)
    d2 = Doc(client_id=200)
    d3 = Doc(client_id=77)
    t1 = d1.get_text("text")
    with d1.transact() as txn:
        t1.insert(txn, 0, "base")
    for d in (d2, d3):
        d.apply_update_v1(d1.encode_state_as_update_v1(StateVector()))
    with d2.transact() as txn:
        d2.get_text("text").insert(txn, 2, "X")
    with d3.transact() as txn:
        d3.get_text("text").insert(txn, 2, "Y")
    for d in (d2, d3):
        d1.apply_update_v1(d.encode_state_as_update_v1(d1.state_vector()))

    merged = d1.encode_state_as_update_v1(StateVector())
    enc = BatchEncoder()
    state = init_state(1, 128)
    batch = enc.build_batch([Update.decode_v1(merged)], n_rows=12, n_dels=4)
    state = apply_update_batch(state, batch, enc.interner.rank_table())
    assert int(np.asarray(state.error).max()) == 0

    remote = np.zeros((1, 8), dtype=np.int32)
    ship, offsets, deleted = diff_arrays(state, enc, remote)
    out = assert_parity(state, [0], ship, offsets, deleted, enc)
    replica = Doc(client_id=99)
    replica.apply_update_v1(out[0])
    assert (
        replica.get_text("text").get_string() == t1.get_string()
    )


@needs_native
@pytest.mark.parametrize("layout", ["row_major", "plane_major", "not_row_contiguous"])
def test_finisher_reads_the_packed_tensor_through_its_own_strides(layout):
    """The packed [d_pad, 15, R] tensor comes back from the CPU row-major
    and from a TPU v5e plane-major (strides (4R, 4*d_pad*R, 4), PR 24);
    `finish` must read either in place, and copy only what it cannot."""
    import jax.numpy as jnp

    from ytpu.models import batch_doc as bd

    def edits(chunks):
        def fn(d):
            t = d.get_text("text")
            for pos, chunk in chunks:
                with d.transact() as txn:
                    t.insert(txn, pos, chunk)
            with d.transact() as txn:
                t.remove_range(txn, 1, 2)

        return fn

    docs, state, enc = build_device_docs(
        [edits([(0, "hello"), (5, " world")]), edits([(0, "abc"), (0, "xyz")]),
         edits([(0, "one")]), edits([(0, "other"), (2, "--")])]
    )
    remote = np.zeros((len(docs), 8), dtype=np.int32)
    ship, offsets, deleted = diff_arrays(state, enc, remote)
    want = [
        finish_encode_diff(state, d, ship, offsets, deleted, enc)
        for d in range(len(docs))
    ]
    idx = jnp.arange(len(docs), dtype=jnp.int32)
    packed = np.asarray(
        bd.compact_finisher_rows(
            state.blocks, jnp.asarray(ship), jnp.asarray(offsets),
            jnp.asarray(deleted), idx, 16,
        )
    )
    if layout == "plane_major":
        packed = np.ascontiguousarray(packed.transpose(1, 0, 2)).transpose(1, 0, 2)
        assert not packed.flags["C_CONTIGUOUS"] and packed.strides[2] == 4
    elif layout == "not_row_contiguous":
        packed = np.ascontiguousarray(packed.transpose(2, 0, 1)).transpose(1, 2, 0)
        assert packed.strides[2] != 4
    got = bd._FinisherContext(enc).finish(packed, len(docs), None, 1)
    assert got == want
    assert bd.LAST_FINISH_STATUSES == [0] * len(docs)  # native, not the fallback
