"""BatchIngestor.apply_bytes — the raw-bytes fast lane.

Eligible docs ship V1 wire bytes straight to the device (decode +
integrate on-chip); ineligible docs (pending stashes, out-of-order
arrival, host-only content) take the exact host lane. Oracle: a host
`Doc` replaying the same payloads, plus `apply()` equivalence.
"""

import jax
import numpy as np
import pytest

from test_table_cache import _flag_lanes
from ytpu.core import Doc
from ytpu.models.batch_doc import get_string
from ytpu.models.ingest import BatchIngestor


def _edit_log(ops, client_id=1, root="text"):
    doc = Doc(client_id=client_id)
    log = []
    doc.observe_update_v1(lambda p, o, t: log.append(p))
    txt = doc.get_text(root)
    for tag, pos, arg in ops:
        with doc.transact() as txn:
            if tag == "i":
                txt.insert(txn, pos, arg)
            else:
                txt.remove_range(txn, pos, arg)
    return log, txt.get_string()


def _flags_clean(ing):
    f = getattr(ing, "_last_fast_flags", None)
    if f is None:
        return True
    from ytpu.ops.decode_kernel import FLAG_ERRORS

    return (np.asarray(f) & FLAG_ERRORS == 0).all()


needs_native = pytest.mark.usefixtures("native_lib")


@needs_native
def test_fast_lane_in_order_stream():
    ops = [("i", 0, "hello"), ("i", 5, " world"), ("d", 2, 3), ("i", 4, "🙂π")]
    log, expect = _edit_log(ops)
    ing = BatchIngestor(n_docs=2, capacity=256)
    for p in log:
        ing.apply_bytes([p, p])
        assert _flags_clean(ing)
    assert ing.fast_docs == 2 * len(log)
    assert ing.slow_docs == 0
    assert int(np.asarray(ing.state.error).max()) == 0
    assert get_string(ing.state, 0, ing.payloads) == expect
    assert get_string(ing.state, 1, ing.payloads) == expect
    # mirror must match the real state vector
    u = Doc(client_id=1)
    for p in log:
        u.apply_update_v1(p)
    assert dict(ing.svs[0].clocks) == dict(u.state_vector().clocks)


@needs_native
def test_out_of_order_takes_slow_lane_and_stashes():
    ops = [("i", 0, "abc"), ("i", 3, "def"), ("i", 6, "ghi")]
    log, expect = _edit_log(ops)
    ing = BatchIngestor(n_docs=1, capacity=256)
    ing.apply_bytes([log[0]])  # fast
    ing.apply_bytes([log[2]])  # gap → slow lane, stashes
    assert ing.pending_update(0) is not None
    ing.apply_bytes([log[1]])  # fills the gap, drains the stash
    assert ing.pending_update(0) is None
    assert get_string(ing.state, 0, ing.payloads) == expect
    assert int(np.asarray(ing.state.error).max()) == 0
    assert ing.slow_docs >= 1 and ing.fast_docs >= 1


@needs_native
def test_mixed_lanes_one_step():
    """Doc 0 rides fast; doc 1 (a WeakRef branch — host-resolved link
    source) rides slow — same step. (Plain maps AND nested shared types
    now decode on device; WeakRef is the remaining host-only type.)"""
    from ytpu.types.weak import quote_range

    log0, expect0 = _edit_log([("i", 0, "fast lane")])
    d = Doc(client_id=7)
    t1 = d.get_text("src")
    with d.transact() as txn:
        t1.insert(txn, 0, "quote me")
    log1 = []
    d.observe_update_v1(lambda p, o, t: log1.append(p))
    with d.transact() as txn:
        q = quote_range(t1, txn, 1, 4)
        d.get_array("links").insert(txn, 0, q)
    ing = BatchIngestor(n_docs=2, capacity=256)
    ing.apply_bytes([log0[0], log1[0]])
    assert ing.fast_docs == 1 and ing.slow_docs == 1
    assert get_string(ing.state, 0, ing.payloads) == expect0
    assert int(np.asarray(ing.state.error).max()) == 0


@needs_native
def test_map_rows_ride_fast_lane():
    """Map rows (parent_sub keys), ContentAny scalars, and overwrites all
    decode + integrate on device (VERDICT r1 #5: B3-style map fan-in)."""
    from ytpu.models.batch_doc import get_map

    d = Doc(client_id=7)
    log = []
    d.observe_update_v1(lambda p, o, t: log.append(p))
    m = d.get_map("m")
    with d.transact() as txn:
        m.insert(txn, "name", "alice")
    with d.transact() as txn:
        m.insert(txn, "age", 31)
    with d.transact() as txn:
        m.insert(txn, "name", "bob")  # overwrite tombstones the loser
    with d.transact() as txn:
        m.insert(txn, "score", 2.5)
    with d.transact() as txn:
        m.insert(txn, "flags", [True, None, 2.5])  # array value: tokenized
    with d.transact() as txn:
        m.insert(txn, "obj", {"k": [1]})  # nested-in-object: host lane
    with d.transact() as txn:
        m.remove(txn, "age")
    ing = BatchIngestor(n_docs=1, capacity=256)
    for p in log:
        ing.apply_bytes([p])
        assert _flags_clean(ing)
    # everything rides fast except the map-valued (recursive) update
    assert ing.fast_docs == len(log) - 1
    assert ing.slow_docs == 1
    got = get_map(ing.state, 0, ing.payloads, ing.enc.keys)
    assert got == {
        "name": "bob",
        "score": 2.5,
        "flags": [True, None, 2.5],
        "obj": {"k": [1]},
    }


@needs_native
def test_equivalence_with_host_lane():
    """apply_bytes and apply produce identical device state + renderings."""
    import random

    rng = random.Random(11)
    ops = []
    length = 0
    for _ in range(60):
        if length > 8 and rng.random() < 0.3:
            pos = rng.randint(0, length - 2)
            n = rng.randint(1, 2)
            ops.append(("d", pos, n))
            length -= n
        else:
            w = "".join(rng.choice("abcd éπ🙂") for _ in range(rng.randint(1, 5)))
            ops.append(("i", rng.randint(0, length), w))
            length += len(w)
    log, expect = _edit_log(ops)

    fast = BatchIngestor(n_docs=2, capacity=1024)
    slow = BatchIngestor(n_docs=2, capacity=1024)
    for p in log:
        fast.apply_bytes([p, None])
        slow.apply([p, None])
    assert get_string(fast.state, 0, fast.payloads) == expect
    assert get_string(slow.state, 0, slow.enc.payloads) == expect
    assert dict(fast.svs[0].clocks) == dict(slow.svs[0].clocks)
    assert int(np.asarray(fast.state.error).max()) == 0


@needs_native
def test_big_client_id_rides_fast_lane():
    """Real Yjs client ids (random 53-bit) resolve through the device
    varint-byte hash table — no host fallback (VERDICT r1: B4.2 lane)."""
    log, expect = _edit_log(
        [("i", 0, "big"), ("i", 3, " ids"), ("d", 0, 1)], client_id=2**40 + 7
    )
    ing = BatchIngestor(n_docs=1, capacity=128)
    for p in log:
        ing.apply_bytes([p])
        assert _flags_clean(ing)
    assert ing.fast_docs == len(log) and ing.slow_docs == 0
    assert get_string(ing.state, 0, ing.payloads) == expect
    u = Doc(client_id=1)
    for p in log:
        u.apply_update_v1(p)
    assert dict(ing.svs[0].clocks) == dict(u.state_vector().clocks)


@needs_native
def test_multi_client_in_order_rides_fast():
    """A merged two-client update whose wire order is causally valid."""
    d1 = Doc(client_id=1)
    d2 = Doc(client_id=2)
    with d1.transact() as txn:
        d1.get_text("text").insert(txn, 0, "aa")
    d2.apply_update_v1(d1.encode_state_as_update_v1())
    with d2.transact() as txn:
        d2.get_text("text").insert(txn, 2, "bb")
    full = d2.encode_state_as_update_v1()
    expect = d2.get_text("text").get_string()

    ing = BatchIngestor(n_docs=1, capacity=128)
    ing.apply_bytes([full])
    assert int(np.asarray(ing.state.error).max()) == 0
    assert get_string(ing.state, 0, ing.payloads) == expect
    # wire order is clients-descending; client 2's blocks depend on client
    # 1's — eligibility must have checked order, whichever lane ran
    if ing.fast_docs:
        assert _flags_clean(ing)


@needs_native
def test_checkpoint_roundtrip_with_fast_refs(tmp_path):
    from ytpu.models.checkpoint import load_ingestor, save_ingestor

    log, expect = _edit_log([("i", 0, "persist"), ("i", 7, " me 🙂")])
    ing = BatchIngestor(n_docs=1, capacity=128)
    for p in log:
        ing.apply_bytes([p])
    assert ing.fast_docs == len(log)
    path = str(tmp_path / "ckpt")
    save_ingestor(path, ing)
    restored = load_ingestor(path)
    assert get_string(restored.state, 0, restored.payloads) == expect
    # the restored ingestor keeps ingesting on both lanes
    more, expect2 = _edit_log(
        [("i", 0, "persist"), ("i", 7, " me 🙂"), ("i", 0, "X")]
    )
    restored.apply_bytes([more[2]])
    assert get_string(restored.state, 0, restored.payloads) == expect2


@needs_native
def test_redelivered_update_is_idempotent_on_fast_lane():
    log, expect = _edit_log([("i", 0, "once"), ("i", 4, " twice")])
    ing = BatchIngestor(n_docs=1, capacity=128)
    ing.apply_bytes([log[0]])
    ing.apply_bytes([log[1]])
    ing.apply_bytes([log[1]])  # exact re-send
    assert int(np.asarray(ing.state.error).max()) == 0
    assert get_string(ing.state, 0, ing.payloads) == expect


@needs_native
def test_encode_diff_after_fast_lane_roundtrips():
    """Rows ingested via the fast lane carry chunked (<= -2) refs; the
    device diff encoder must resolve them through the ingestor's payload
    view, producing a wire update a fresh host doc can apply."""
    from ytpu.models.batch_doc import encode_diff_batch, finish_encode_diff

    log, expect = _edit_log([("i", 0, "chunky"), ("i", 6, " refs 🙂")])
    ing = BatchIngestor(n_docs=1, capacity=128)
    for p in log:
        ing.apply_bytes([p])
    assert ing.fast_docs == len(log)

    n_clients = max(8, len(ing.enc.interner))
    remote = np.zeros((1, n_clients), dtype=np.int32)  # empty remote SV
    import jax.numpy as jnp

    ship, offsets, _local_sv, deleted = map(
        np.asarray,
        encode_diff_batch(ing.state, jnp.asarray(remote), n_clients),
    )
    payload = finish_encode_diff(
        ing.state, 0, ship, offsets, deleted, ing.enc, ing.payloads
    )
    fresh = Doc(client_id=77)
    fresh.apply_update_v1(payload)
    assert fresh.get_text("text").get_string() == expect


@needs_native
def test_get_diff_over_mixed_lane_state():
    """Formatted text renders correct diff runs through the fast lane:
    format marks and plain inserts both decode on device (wire refs) and
    get_diff resolves format key/value pairs from the retained bytes."""
    from ytpu.models.batch_doc import get_diff

    doc = Doc(client_id=3)
    log = []
    doc.observe_update_v1(lambda p, o, t: log.append(p))
    t = doc.get_text("text")
    with doc.transact() as txn:
        t.insert(txn, 0, "plain ")           # fast lane
    with doc.transact() as txn:
        t.insert_with_attributes(txn, 6, "bold", {"b": True})  # slow lane
    with doc.transact() as txn:
        t.insert(txn, 10, " tail")           # fast lane

    ing = BatchIngestor(n_docs=1, capacity=256)
    for p in log:
        ing.apply_bytes([p])
    # format marks now decode on device too: the whole stream rides fast
    assert ing.fast_docs == len(log) and ing.slow_docs == 0
    expect = doc.get_text("text").diff()
    got = get_diff(ing.state, 0, ing.payloads)
    assert got == expect, f"{got!r} != {expect!r}"


@needs_native
def test_delete_only_steps_retain_no_wire_bytes():
    log, _ = _edit_log([("i", 0, "abcdef"), ("d", 1, 3), ("d", 0, 2)])
    ing = BatchIngestor(n_docs=1, capacity=128)
    ing.apply_bytes([log[0]])
    after_insert = ing.payloads.total_bytes
    assert after_insert > 0
    ing.apply_bytes([log[1]])  # delete-only update: no string refs
    ing.apply_bytes([log[2]])
    assert ing.payloads.total_bytes == after_insert


@needs_native
def test_degenerate_wire_shapes_no_wedge():
    """Wire-legal degenerate updates (many empty ds-client sections; many
    client sections holding only Skip runs) must not wedge the batch —
    they either route to the slow lane or decode clean on device with a
    section-aware step budget (ADVICE r1, medium)."""
    from ytpu.encoding.lib0 import Writer

    # (a) zero block sections + 40 empty ds-client sections → slow lane
    w = Writer()
    w.write_var_uint(0)
    w.write_var_uint(40)
    for c in range(40):
        w.write_var_uint(c + 1)
        w.write_var_uint(0)
    empty_ds = w.to_bytes()

    # (b) 30 client sections, each a single Skip run → fast lane, but the
    # section count exceeds the emitted-row count (0) by far
    w = Writer()
    w.write_var_uint(30)
    for c in range(30):
        w.write_var_uint(1)
        w.write_var_uint(c + 100)
        w.write_var_uint(0)
        w.write_u8(10)  # BLOCK_SKIP
        w.write_var_uint(5)
    w.write_var_uint(0)
    skip_heavy = w.to_bytes()

    ing = BatchIngestor(n_docs=1, capacity=128)
    ing.apply_bytes([empty_ds])
    assert _flags_clean(ing)
    ing.apply_bytes([skip_heavy])
    assert _flags_clean(ing)
    assert int(np.asarray(ing.state.error).max()) == 0

    # the engine still works afterwards
    log, expect = _edit_log([("i", 0, "still alive")])
    for p in log:
        ing.apply_bytes([p])
    assert get_string(ing.state, 0, ing.payloads) == expect


@needs_native
def test_fast_lane_flag_recovery(monkeypatch):
    """If the device decoder flags a lane the host pre-scan validated, the
    ingestor must rewind the mirror SV and replay that doc through the
    host lane — converging instead of raising (ADVICE r1, medium)."""
    from ytpu.ops import decode_kernel as dk

    real = dk.decode_updates_v1
    hits = {"n": 0}

    def sabotage(buf, lens, max_rows, max_dels, **kw):
        stream, flags = real(buf, lens, max_rows, max_dels, **kw)
        if hits["n"] == 0:
            hits["n"] = 1
            stream, flags = _flag_lanes(stream, flags)
        return stream, flags

    monkeypatch.setattr(dk, "decode_updates_v1", sabotage)
    log, expect = _edit_log([("i", 0, "hello"), ("i", 5, " world")])
    ing = BatchIngestor(n_docs=1, capacity=128)
    for p in log:
        ing.apply_bytes([p])
    assert hits["n"] == 1
    assert ing.fast_recoveries == 1
    assert get_string(ing.state, 0, ing.payloads) == expect
    u = Doc(client_id=9)
    for p in log:
        u.apply_update_v1(p)
    assert dict(ing.svs[0].clocks) == dict(u.state_vector().clocks)


@needs_native
def test_b3_style_map_fan_in_zero_host_fallbacks():
    """B3 micro-bench shape (yrs/benches/benches.rs:536-551): N clients
    each commit one transaction against a shared map/array doc; every
    update must ride the raw-bytes fast lane (VERDICT r1 #5 done
    criterion). Covers B3.1 (map num), B3.2 (flat object values —
    depth-1 Any objects decode on device since r3), B3.3 (map string),
    B3.4 (array insert)."""
    from ytpu.models.batch_doc import get_map

    n_clients = 24
    base = Doc(client_id=999)
    snapshot = base.encode_state_as_update_v1()
    payloads = []
    for i in range(n_clients):
        d = Doc(client_id=1000 + i)
        d.apply_update_v1(snapshot)
        log = []
        d.observe_update_v1(lambda p, o, t, log=log: log.append(p))
        m = d.get_map("map")
        with d.transact() as txn:
            if i % 4 == 0:
                m.insert(txn, f"n{i}", i)  # B3.1
            elif i % 4 == 1:
                m.insert(txn, f"o{i}", {"x": i, "y": f"v{i}"})  # B3.2
            elif i % 4 == 2:
                m.insert(txn, f"s{i}", f"val-{i}")  # B3.3
            else:
                m.insert(txn, f"a{i}", [i, i + 1])  # B3.4-ish
        payloads.append(log[-1])

    ing = BatchIngestor(n_docs=1, capacity=512)
    oracle = Doc(client_id=1)
    for p in payloads:
        ing.apply_bytes([p])
        assert _flags_clean(ing)
        oracle.apply_update_v1(p)
    assert ing.fast_docs == n_clients, "a B3 update fell back to host"
    assert ing.slow_docs == 0
    got = get_map(ing.state, 0, ing.payloads, ing.enc.keys)
    assert got == oracle.get_map("map").to_json()


def test_nested_types_ride_fast_lane():
    """ContentType rows (nested shared types) now decode on device: a map
    tenant holding a nested YText rides the raw-bytes lane end to end —
    fast_docs counts it, the tree renders, and the diff round-trips
    (north-star config #4 tenants; VERDICT r2 weak #4)."""
    from ytpu.core.state_vector import StateVector
    from ytpu.models.batch_doc import encode_diff_batch, finish_encode_diff
    from ytpu.types.shared import TextPrelim

    doc = Doc(client_id=1)
    log = []
    doc.observe_update_v1(lambda p, o, t: log.append(p))
    m = doc.get_map("root")
    with doc.transact() as txn:
        m.insert(txn, "title", "plain value")
    with doc.transact() as txn:
        m.insert(txn, "body", TextPrelim("nested"))
    nested = m.get("body")
    with doc.transact() as txn:
        nested.insert(txn, 6, " text")

    ing = BatchIngestor(1, 128)
    for p in log:
        ing.apply_bytes([p])
    assert ing.fast_docs == len(log), (ing.fast_docs, ing.slow_docs)
    assert int(np.asarray(ing.state.error).max()) == 0

    from ytpu.models.batch_doc import get_tree

    tree = get_tree(
        ing.state, 0, ing.payloads, ing.enc.keys, interner=ing.enc.interner
    )
    assert tree["map"]["title"] == "plain value"
    assert tree["map"]["body"] == "nested text"

    # serving: the diff re-applies on a fresh host doc with the nested
    # type intact (wire ContentType spans re-emitted verbatim)
    import jax.numpy as jnp

    n_clients = 2
    remote = np.zeros((1, n_clients), dtype=np.int32)
    ship, offsets, _loc, deleted = encode_diff_batch(
        ing.state, jnp.asarray(remote), n_clients
    )
    payload = finish_encode_diff(
        ing.state,
        0,
        np.asarray(ship),
        np.asarray(offsets),
        np.asarray(deleted),
        ing.enc,
        ing.payloads,
        root_name="root",
    )
    d = Doc(client_id=9)
    d.apply_update_v1(payload)
    got = d.get_map("root")
    assert got.get("title") == "plain value"
    assert got.get("body").get_string() == "nested text"


@needs_native
def test_fast_lane_multi_root_doc():
    """Multi-root docs (doc.rs:156-228, the reference's normal shape) ride
    the FAST lane: the wire prescan registers root names, non-primary
    roots anchor through BLOCK_ROOT_ANCHOR rows, and the device decode
    resolves them via the key table (p_root) with zero host fallbacks."""
    from ytpu.models.batch_doc import (
        encode_diff_batch,
        finish_encode_diff_batch,
        get_tree,
    )

    doc = Doc(client_id=3)
    log = []
    doc.observe_update_v1(lambda p, o, t: log.append(p))
    body = doc.get_text("body")
    title = doc.get_text("title")
    meta = doc.get_map("meta")
    with doc.transact() as txn:
        body.insert(txn, 0, "content here")
    with doc.transact() as txn:
        title.insert(txn, 0, "A Title")
    with doc.transact() as txn:
        meta.insert(txn, "lang", "en")
    with doc.transact() as txn:
        title.insert(txn, 7, "?")
        body.insert(txn, 0, "* ")

    ing = BatchIngestor(n_docs=2, capacity=256)
    for p in log:
        ing.apply_bytes([p, p])
        assert _flags_clean(ing)
    assert int(np.asarray(ing.state.error).max()) == 0
    # everything after the first update (which creates the primary) should
    # stay on the fast lane — anchors resolve on device
    assert ing.fast_docs == 2 * len(log)
    assert ing.primary_roots[0] == "body"
    assert get_string(ing.state, 0, ing.payloads) == body.get_string()
    for d in (0, 1):
        tree = get_tree(
            ing.state, d, ing.payloads, ing.enc.keys, interner=ing.enc.interner
        )
        assert tree["roots"]["title"]["seq"] == list("A Title?")
        assert tree["roots"]["meta"]["map"] == {"lang": "en"}

    # serving: a fresh replica reconstructs ALL roots from the device diff
    import jax.numpy as jnp

    C = max(8, len(ing.enc.interner))
    remote = np.zeros((2, C), dtype=np.int32)
    ship, offsets, _loc, deleted = encode_diff_batch(
        ing.state, jnp.asarray(remote), C
    )
    payloads = finish_encode_diff_batch(
        ing.state, [0, 1], ship, offsets, deleted, ing.enc,
        payloads=ing.payloads, root_name="body",
    )
    for p in payloads:
        d = Doc(client_id=77)
        d.apply_update_v1(p)
        assert d.get_text("body").get_string() == body.get_string()
        assert d.get_text("title").get_string() == "A Title?"
        assert d.get_map("meta").to_json() == {"lang": "en"}


# --- the merge as jitted programs (ISSUE-27) ---------------------------------

MERGE_DOCS = 16
# per room: an insert, a delete-only update, a second insert (multi-byte
# text); the last two are each applicable right after the first
_MERGE_OPS = lambda d: [("i", 0, f"hello{d:02d}"), ("d", 1, 2), ("i", 5, f"wörld🙂{d:02d}")]
INS, DEL, INS2 = 0, 1, 2
# case -> {slot: which update of its room's log rides the measured step}
MERGE_CASES = {
    "one_lane": {5: INS2},
    "eight_scattered_lanes": {d: INS2 for d in (0, 2, 5, 7, 9, 11, 14, 15)},
    "all_delete": {d: DEL for d in (1, 4, 6)},
    "mixed_with_host_lane": {0: INS2, 8: INS2, 12: DEL},  # + slot 3, below
    "every_slot": {d: INS2 for d in range(MERGE_DOCS)},
    "delete_lane_among_strings": {2: INS2, 6: DEL, 9: INS2},
}
HOST_SLOT = 3  # "mixed": a stash drains through the host lane in this slot


def _spy_on_merge(monkeypatch):
    """Record every `merge_stream` call: (args, kwargs, merged batch)."""
    from ytpu.models import ingest

    calls, real = [], ingest._merge_stream_jit

    def spy(*args, **kw):
        out = real(*args, **kw)
        calls.append((args, kw, out))
        return out

    monkeypatch.setattr(ingest, "_merge_stream_jit", spy)
    return calls


def _lane_operands(table):
    """`idx`, `prefix`, `base` as `merge_stream` reads them out of the lane
    table: a device array (the step's first program made it), no host
    operand of the call."""
    from ytpu.ops.decode_kernel import LANE_AT, LANE_BASE, LANE_FIELDS, LANE_PREFIX

    assert isinstance(table, jax.Array) and table.dtype == np.int32 and table.shape[0] == LANE_FIELDS
    table = np.asarray(table)
    assert (table[LANE_BASE] == table[LANE_BASE, 0]).all()
    return table[LANE_AT], table[LANE_PREFIX], table[LANE_BASE, 0]


def _reference_merge(batch, stream, idx, prefix, base, width):
    """The merge in numpy: rebase the string refs, then `full[idx] = fast`."""
    full = {k: np.array(v) for k, v in batch._asdict().items()}
    fast = {k: np.asarray(v) for k, v in stream._asdict().items()}
    ref = fast["content_ref"]
    lane = np.arange(len(idx), dtype=np.int32)[:, None]
    compact = prefix[:, None].astype(np.int32) + (ref - lane * np.int32(width))
    fast["content_ref"] = np.where(
        fast["valid"] & (ref >= 0), np.int32(-2 - base) - compact, ref
    )
    for k in full:
        full[k][idx] = fast[k]
    return full


@needs_native
@pytest.mark.parametrize("ingest_mode", ["raw", "packed"])
@pytest.mark.parametrize("case", sorted(MERGE_CASES))
def test_merge_stream_equals_numpy_reference(monkeypatch, case, ingest_mode):
    """One jitted program in place of the eager tree-map: same leaves, same
    dtypes, and `idx` / `prefix` / `base` as the step's payloads give them."""
    from ytpu.models.ingest import _bucket

    logs = [_edit_log(_MERGE_OPS(d), client_id=d + 1)[0] for d in range(MERGE_DOCS)]
    oracles = [Doc(client_id=99) for _ in range(MERGE_DOCS)]
    ing = BatchIngestor(n_docs=MERGE_DOCS, capacity=64, ingest=ingest_mode)

    def step(payloads):
        for d, p in enumerate(payloads):
            if p is not None:
                oracles[d].apply_update_v1(p)
        ing.apply_bytes(payloads)
        assert _flags_clean(ing)

    step([log[INS] for log in logs])
    plan = {d: logs[d][which] for d, which in MERGE_CASES[case].items()}
    if case == "mixed_with_host_lane":
        # slot 3 skips an update (stash), then receives it: the host lane
        # plans the stashed rows into the same step the fast lanes ride
        extra, _ = _edit_log(
            [("i", 0, "hello03"), ("i", 7, "abc"), ("i", 10, "def")], client_id=HOST_SLOT + 1
        )
        step([extra[2] if d == HOST_SLOT else None for d in range(MERGE_DOCS)])
        assert ing.pending_update(HOST_SLOT) is not None
        plan[HOST_SLOT] = extra[1]
    calls = _spy_on_merge(monkeypatch)
    base_before = ing.payloads.total_bytes
    fast_before = ing.fast_docs
    step([plan.get(d) for d in range(MERGE_DOCS)])

    lanes = sorted(MERGE_CASES[case])  # the fast lanes' slots, in slot order
    assert ing.fast_docs - fast_before == len(lanes)
    ((batch, stream, table), kw, merged), = calls
    idx, prefix, base = _lane_operands(table)
    # the host lane's batch, the decoded stream and what the merge makes of
    # them all cross as the two packed arrays; compared as planes
    from ytpu.models.batch_doc import PackedBatch, unpack_batch_jit

    assert all(type(b) is PackedBatch and len(jax.tree.leaves(b)) == 2 for b in (batch, stream, merged))
    batch, stream, merged = (unpack_batch_jit(b) for b in (batch, stream, merged))
    # what the step's payloads say the operands are
    keep = [MERGE_CASES[case][d] != DEL for d in lanes]
    kept_lens = [len(plan[d]) if k else 0 for d, k in zip(lanes, keep)]
    want_prefix = np.concatenate([[0], np.cumsum(kept_lens[:-1])]).astype(np.int32)
    assert idx.dtype == np.int32 and idx.tolist() == lanes
    assert prefix.dtype == np.int32 and prefix.tolist() == want_prefix.tolist()
    assert np.asarray(base).dtype == np.int32
    assert int(base) == (base_before if any(keep) else 0)
    assert kw == {"width": _bucket(max(len(plan[d]) for d in lanes) + 16, 64)}
    assert np.asarray(stream.valid).shape[0] == len(lanes)

    want = _reference_merge(batch, stream, idx, prefix, int(base), kw["width"])
    assert type(merged) is type(batch)
    for name, leaf in merged._asdict().items():
        got = np.asarray(leaf)
        assert got.dtype == want[name].dtype == np.asarray(getattr(batch, name)).dtype, name
        np.testing.assert_array_equal(got, want[name], err_msg=name)
    if case == "mixed_with_host_lane":
        # the host lane's rows sit in their slot, untouched by the scatter
        host_rows = np.asarray(batch.valid)[HOST_SLOT]
        assert host_rows.sum() == 2
        np.testing.assert_array_equal(np.asarray(merged.valid)[HOST_SLOT], host_rows)
        np.testing.assert_array_equal(
            np.asarray(merged.content_ref)[HOST_SLOT], np.asarray(batch.content_ref)[HOST_SLOT]
        )
    if any(keep):  # string rows point into the chunk this step retained
        refs = np.asarray(merged.content_ref)[lanes]
        assert (refs[np.asarray(stream.valid) & (np.asarray(stream.content_ref) >= 0)] <= -2 - base_before).all()
    # and the refs resolve: every room reads back as the host oracle does
    assert int(np.asarray(ing.state.error).max()) == 0
    for d in range(MERGE_DOCS):
        assert get_string(ing.state, d, ing.payloads) == oracles[d].get_text("text").get_string(), d


@needs_native
def test_merge_programs_do_not_retrace_on_values(monkeypatch):
    """`idx`, `prefix`, `base` and the payload bytes are operands: steps of
    one shape share one entry of each program's cache, a new lane count adds
    exactly one. (The guard against `base` becoming a static argument.)"""
    from ytpu.models import ingest
    from ytpu.utils import progbudget

    monkeypatch.setattr(progbudget, "_MAX", 10**9)  # no eviction under our feet
    logs = [_edit_log(_MERGE_OPS(d), client_id=d + 1)[0] for d in range(8)]
    ing = BatchIngestor(n_docs=8, capacity=64)
    programs = (ingest._gather_manifest_jit, ingest._merge_stream_jit)
    for jit in programs:  # earlier tests of this process may hold the same keys
        jit.clear_cache()
    sizes = lambda: tuple(jit._cache_size() for jit in programs)
    calls = _spy_on_merge(monkeypatch)

    def step(plan):
        ing.apply_bytes([logs[d][w] if (w := plan.get(d)) is not None else None for d in range(8)])
        assert _flags_clean(ing)

    step({d: INS for d in range(8)})
    step({0: INS2, 1: INS2, 2: INS2})
    first = sizes()
    step({4: DEL, 6: INS2, 7: INS2})  # other slots, prefix, base and bytes; same shapes
    idx_a, prefix_a, base_a = _lane_operands(calls[1][0][2])
    idx_b, prefix_b, base_b = _lane_operands(calls[2][0][2])
    assert idx_a.tolist() != idx_b.tolist() and prefix_a.tolist() != prefix_b.tolist()
    assert int(base_a) != int(base_b)
    assert sizes() == first
    step({3: INS2, 5: INS2})  # a new lane count
    assert sizes() == (first[0] + 1, first[1] + 1)
    assert int(np.asarray(ing.state.error).max()) == 0
