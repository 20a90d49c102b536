"""Device-side V1 update decoding (ytpu/ops/decode_kernel.py).

Oracle: `ytpu.core.Update.decode_v1` — every decoded row/delete-range must
match the host decoder field-for-field (raw client ids), and replaying the
device-decoded stream through the XLA integrate path must reproduce the
host doc byte-for-byte (reference semantics: update.rs:714-749, :433-488).
"""

import os

import numpy as np
import pytest

from ytpu.core import Doc, Update
from ytpu.core.block import GCRange, Item, SkipRange
from ytpu.core.content import BLOCK_GC, CONTENT_DELETED, CONTENT_STRING
from ytpu.models.batch_doc import apply_update_stream, get_string, init_state
from ytpu.ops.decode_kernel import (
    FLAG_BIG_CLIENT,
    FLAG_ERRORS,
    FLAG_MALFORMED,
    FLAG_MULTI_CLIENT,
    FLAG_OVERFLOW,
    FLAG_UNSUPPORTED,
    RawPayloadView,
    decode_updates_v1,
    identity_rank,
    pack_updates,
)


# every call the cases below make of the decoder, by the test that made it:
# the packed entry is held to them at the end of the file
_CALLS = {}
_decode_planes = decode_updates_v1


def _current_test() -> str:
    return os.environ.get("PYTEST_CURRENT_TEST", "").split("::")[-1].split(" ")[0]


def decode_updates_v1(*args, **kw):  # noqa: F811
    out = _decode_planes(*args, **kw)
    _CALLS.setdefault(_current_test(), []).append((args, kw, out))
    return out


def _edit_log(ops, client_id=1):
    """Wire updates from replaying (tag, pos, arg) text ops on a host doc."""
    doc = Doc(client_id=client_id)
    log = []
    doc.observe_update_v1(lambda p, o, t: log.append(p))
    txt = doc.get_text("text")
    for tag, pos, arg in ops:
        with doc.transact() as txn:
            if tag == "i":
                txt.insert(txn, pos, arg)
            else:
                txt.remove_range(txn, pos, arg)
    return log, txt.get_string()


def _expected_rows_dels(payload):
    """Wire-order (client, clock, len, oc, ok, rc, rk, kind, text) rows from
    the host decoder, plus (client, start, end) delete ranges."""
    u = Update.decode_v1(payload)
    rows = []
    for client, blocks in u.blocks.items():
        for carrier in blocks:
            if isinstance(carrier, SkipRange):
                continue
            if isinstance(carrier, GCRange):
                rows.append((client, carrier.id.clock, carrier.len, -1, 0, -1, 0,
                             BLOCK_GC, None))
                continue
            item: Item = carrier
            oc = item.origin.client if item.origin else -1
            ok = item.origin.clock if item.origin else 0
            rc = item.right_origin.client if item.right_origin else -1
            rk = item.right_origin.clock if item.right_origin else 0
            kind = item.content.kind
            text = item.content.text if kind == CONTENT_STRING else None
            rows.append((client, item.id.clock, item.len, oc, ok, rc, rk, kind, text))
    dels = []
    for client, ranges in u.delete_set.clients.items():
        for s, e in ranges:
            dels.append((client, s, e))
    return rows, dels


def _decode(log, U=4, R=8):
    buf, lens = pack_updates(log)
    stream, flags = decode_updates_v1(buf, lens, U, R)
    return buf, stream, np.asarray(flags)


def _check_field_parity(log, U=4, R=8):
    buf, stream, flags = _decode(log, U, R)
    view = RawPayloadView(buf)
    L = buf.shape[1]
    st = {k: np.asarray(v) for k, v in stream._asdict().items()}
    for s, payload in enumerate(log):
        assert flags[s] & FLAG_ERRORS == 0, f"update {s} flagged {flags[s]}"
        rows, dels = _expected_rows_dels(payload)
        got_n = int(st["valid"][s].sum())
        assert got_n == len(rows), (s, got_n, len(rows))
        for i, (client, clock, ln, oc, ok, rc, rk, kind, text) in enumerate(rows):
            assert st["client"][s, i] == client
            assert st["clock"][s, i] == clock
            assert st["length"][s, i] == ln
            assert st["origin_client"][s, i] == oc
            assert st["origin_clock"][s, i] == ok
            assert st["ror_client"][s, i] == rc
            assert st["ror_clock"][s, i] == rk
            assert st["kind"][s, i] == kind
            if text is not None:
                ref = int(st["content_ref"][s, i])
                assert ref // L == s
                assert view.slice_text(ref, 0, ln) == text
        got_d = int(st["del_valid"][s].sum())
        assert got_d == len(dels), (s, got_d, len(dels))
        for i, (client, start, end) in enumerate(dels):
            assert st["del_client"][s, i] == client
            assert st["del_start"][s, i] == start
            assert st["del_end"][s, i] == end
    return buf, stream, flags


def test_insert_delete_field_parity():
    ops = [
        ("i", 0, "hello"),
        ("i", 5, " world"),
        ("i", 3, "xyz"),
        ("d", 2, 4),
        ("i", 0, "A"),
        ("d", 0, 1),
        ("i", 7, "tail"),
    ]
    log, _ = _edit_log(ops)
    _check_field_parity(log)


def test_unicode_utf16_lengths():
    ops = [
        ("i", 0, "héllo"),  # 2-byte
        ("i", 2, "日本語"),  # 3-byte
        ("i", 1, "🙂🙃"),  # 4-byte → surrogate pairs, u16 len 4
        ("d", 1, 3),
    ]
    log, _ = _edit_log(ops)
    buf, stream, flags = _check_field_parity(log)
    # the astral insert must count UTF-16 units (2 per emoji)
    u = Update.decode_v1(log[2])
    (blocks,) = u.blocks.values()
    assert blocks[0].len == 4


def test_end_to_end_replay_matches_host():
    import random

    rng = random.Random(3)
    ops = []
    length = 0
    for _ in range(120):
        if length > 10 and rng.random() < 0.3:
            pos = rng.randint(0, length - 3)
            n = rng.randint(1, 3)
            ops.append(("d", pos, n))
            length -= n
        else:
            word = "".join(rng.choice("abcdefg håπ🙂") for _ in range(rng.randint(1, 6)))
            ops.append(("i", rng.randint(0, length), word))
            length += len(word)
    log, expect = _edit_log(ops)
    buf, stream, flags = _decode(log, U=4, R=8)
    assert (flags & FLAG_ERRORS == 0).all()

    n_docs = 4
    state = init_state(n_docs, 1024)
    state = apply_update_stream(state, stream, identity_rank(256))
    assert int(np.asarray(state.error).max()) == 0
    view = RawPayloadView(buf)
    assert get_string(state, 0, view) == expect
    assert get_string(state, n_docs - 1, view) == expect


def test_merged_update_multi_block():
    """merge_updates produces one update with many blocks per client."""
    from ytpu.core.update import merge_updates_v1

    ops = [("i", 0, "abc"), ("i", 3, "def"), ("i", 2, "XY"), ("d", 1, 2)]
    log, expect = _edit_log(ops)
    merged = merge_updates_v1(log)
    _check_field_parity([merged], U=8, R=8)

    buf, stream, flags = _decode([merged], U=8, R=8)
    state = init_state(2, 256)
    state = apply_update_stream(state, stream, identity_rank(256))
    assert int(np.asarray(state.error).max()) == 0
    assert get_string(state, 0, RawPayloadView(buf)) == expect


def test_multi_client_flagged_informational():
    d1 = Doc(client_id=1)
    d2 = Doc(client_id=2)
    with d1.transact() as txn:
        d1.get_text("text").insert(txn, 0, "aa")
    u1 = d1.encode_state_as_update_v1()
    d2.apply_update_v1(u1)
    with d2.transact() as txn:
        d2.get_text("text").insert(txn, 2, "bb")
    full = d2.encode_state_as_update_v1()

    buf, stream, flags = _decode([full], U=4, R=4)
    assert flags[0] & FLAG_MULTI_CLIENT
    assert flags[0] & FLAG_ERRORS == 0
    rows, _ = _expected_rows_dels(full)
    assert int(np.asarray(stream.valid)[0].sum()) == len(rows)


def test_content_any_scalars_decode_clean():
    """ContentAny scalar lists decode on device (one step per value)."""
    doc = Doc(client_id=5)
    log = []
    doc.observe_update_v1(lambda p, o, t: log.append(p))
    arr = doc.get_array("text")
    with doc.transact() as txn:
        arr.insert_range(txn, 0, [1, 2.5, "three", True, None])
    _, stream, flags = _decode(log, U=4, R=4)
    assert flags[0] & FLAG_ERRORS == 0
    valid = np.asarray(stream.valid)[0]
    assert valid.sum() == 1
    from ytpu.core.content import CONTENT_ANY

    assert int(np.asarray(stream.kind)[0][valid][0]) == CONTENT_ANY
    assert int(np.asarray(stream.length)[0][valid][0]) == 5


def test_recursive_any_flags_unsupported():
    """Nested array/map Any values exceed the one-step-per-value model."""
    doc = Doc(client_id=5)
    log = []
    doc.observe_update_v1(lambda p, o, t: log.append(p))
    arr = doc.get_array("text")
    with doc.transact() as txn:
        arr.insert(txn, 0, {"nested": [1, 2]})
    _, _, flags = _decode(log, U=4, R=4)
    assert flags[0] & FLAG_UNSUPPORTED


def test_map_parent_sub_without_table_flags_unknown_key():
    doc = Doc(client_id=5)
    log = []
    doc.observe_update_v1(lambda p, o, t: log.append(p))
    m = doc.get_map("m")
    with doc.transact() as txn:
        m.insert(txn, "key", "value")
    _, _, flags = _decode(log, U=4, R=4)
    from ytpu.ops.decode_kernel import FLAG_UNKNOWN_KEY

    assert flags[0] & FLAG_UNKNOWN_KEY


def test_map_parent_sub_with_key_table_decodes():
    import jax.numpy as jnp

    from ytpu.ops.decode_kernel import key_hash_host, pack_updates

    doc = Doc(client_id=5)
    log = []
    doc.observe_update_v1(lambda p, o, t: log.append(p))
    m = doc.get_map("m")
    with doc.transact() as txn:
        m.insert(txn, "title", "hello")
    buf, lens = pack_updates(log)
    h = key_hash_host(b"title")
    stream, flags = decode_updates_v1(
        jnp.asarray(buf),
        jnp.asarray(lens),
        4,
        4,
        key_table=(
            jnp.asarray(np.array([h], dtype=np.int32)),
            jnp.asarray(np.array([17], dtype=np.int32)),
        ),
    )
    flags = np.asarray(flags)
    assert flags[0] & FLAG_ERRORS == 0
    valid = np.asarray(stream.valid)[0]
    assert valid.sum() == 1
    assert int(np.asarray(stream.key)[0][valid][0]) == 17


def test_big_client_id_flags():
    doc = Doc(client_id=2**40)
    log = []
    doc.observe_update_v1(lambda p, o, t: log.append(p))
    with doc.transact() as txn:
        doc.get_text("text").insert(txn, 0, "x")
    _, _, flags = _decode(log, U=4, R=4)
    assert flags[0] & FLAG_BIG_CLIENT


def test_truncated_update_flags_malformed():
    log, _ = _edit_log([("i", 0, "hello world")])
    truncated = log[0][: len(log[0]) - 4]
    buf, lens = pack_updates([truncated])
    _, flags = decode_updates_v1(buf, lens, 4, 4)
    assert np.asarray(flags)[0] & FLAG_MALFORMED


def test_row_overflow_flags():
    from ytpu.core.update import merge_updates_v1

    ops = [("i", 0, "a"), ("i", 0, "b"), ("i", 0, "c"), ("i", 0, "d")]
    log, _ = _edit_log(ops)
    merged = merge_updates_v1(log)
    _, _, flags = _decode([merged], U=2, R=2)
    assert flags[0] & FLAG_OVERFLOW


def test_mixed_batch_bad_lane_emits_nothing():
    """A flagged lane's partial rows must be masked out of the stream."""
    good, expect = _edit_log([("i", 0, "ok")])
    doc = Doc(client_id=7)
    bad_log = []
    doc.observe_update_v1(lambda p, o, t: bad_log.append(p))
    with doc.transact() as txn:
        doc.get_map("m").insert(txn, "k", 1)
    log = [good[0], bad_log[0]]
    buf, stream, flags = _decode(log, U=4, R=4)
    assert flags[0] & FLAG_ERRORS == 0
    assert flags[1] & FLAG_ERRORS != 0
    v = np.asarray(stream.valid)
    assert v[0].sum() == 1
    assert v[1].sum() == 0
    assert np.asarray(stream.del_valid)[1].sum() == 0


def test_gc_rows_decode():
    """GC carriers (info byte 0 + len) decode as BLOCK_GC rows."""
    from collections import deque

    from ytpu.core.block import ID
    from ytpu.core.content import ContentString

    gc = GCRange(ID(3, 0), 4)
    item = Item(ID(3, 4), None, ID(3, 3), None, None, "text", None,
                ContentString("tail"))
    u = Update(blocks={3: deque([gc, item])})
    payload = u.encode_v1()
    rows, _ = _expected_rows_dels(payload)
    assert any(r[7] == BLOCK_GC for r in rows)
    _check_field_parity([payload], U=8, R=8)


def test_huge_string_length_varint_flags_malformed():
    """Regression: a string-length varint near 2^31 wrapped the cursor
    advance negative and bypassed the bounds check (flags stayed 0)."""
    from ytpu.encoding.lib0 import Writer

    w = Writer()
    w.write_var_uint(1)  # n_clients
    w.write_var_uint(1)  # n_blocks
    w.write_var_uint(7)  # client
    w.write_var_uint(0)  # clock
    w.write_u8(0x04 | 0x80)  # String content, has-origin
    w.write_var_uint(7)
    w.write_var_uint(0)  # origin id
    w.write_var_uint(2**31 - 16)  # absurd string byte length
    payload = w.to_bytes()
    buf, lens = pack_updates([payload])
    _, flags = decode_updates_v1(buf, lens, 4, 4)
    assert np.asarray(flags)[0] & FLAG_MALFORMED


def test_content_type_nested_types_decode():
    """Nested shared types (ContentType rows) decode on device: a map
    holding a YText and an XmlElement (named branch). WeakRef branches
    stay host-lane (flagged)."""
    from ytpu.core.content import CONTENT_TYPE

    from ytpu.types.shared import TextPrelim, XmlElementPrelim

    doc = Doc(client_id=1)
    log = []
    doc.observe_update_v1(lambda p, o, t: log.append(p))
    arr = doc.get_array("root")
    with doc.transact() as txn:
        arr.insert(txn, 0, TextPrelim("nested text"))
    frag = doc.get_xml_fragment("xml")
    with doc.transact() as txn:
        frag.insert(txn, 0, XmlElementPrelim("div"))

    buf, stream, flags = _decode(log, U=6)
    assert (flags & FLAG_ERRORS == 0).all(), flags
    st = {k: np.asarray(v) for k, v in stream._asdict().items()}
    view = RawPayloadView(buf)
    type_rows = [
        (s, u)
        for s in range(len(log))
        for u in range(st["valid"].shape[1])
        if st["valid"][s, u] and st["kind"][s, u] == CONTENT_TYPE
    ]
    assert type_rows, "expected ContentType rows on the device lane"
    branches = [
        view.type_branch(int(st["content_ref"][s, u])) for s, u in type_rows
    ]
    from ytpu.core.branch import TYPE_TEXT, TYPE_XML_ELEMENT

    refs = sorted(b.type_ref for b in branches)
    assert TYPE_TEXT in refs and TYPE_XML_ELEMENT in refs
    named = [b for b in branches if b.type_ref == TYPE_XML_ELEMENT]
    assert named and named[0].type_name == "div"


def test_weak_type_flags_unsupported():
    from ytpu.types.shared import TextPrelim

    doc = Doc(client_id=1)
    t = doc.get_text("src")
    arr = doc.get_array("links")
    with doc.transact() as txn:
        t.insert(txn, 0, "quote me")
    from ytpu.types.weak import quote_range

    log = []
    doc.observe_update_v1(lambda p, o, t_: log.append(p))
    with doc.transact() as txn:
        q = quote_range(t, txn, 1, 4)
        arr.insert(txn, 0, q)
    buf, stream, flags = _decode(log)
    assert (flags & FLAG_UNSUPPORTED != 0).any(), flags


def test_content_move_rows_decode():
    """ContentMove rows (array.move_to) decode on device with full range
    fields — bounds, assocs, priority (moving.rs:189-215 wire layout)."""
    from ytpu.core.content import CONTENT_MOVE

    doc = Doc(client_id=1)
    arr = doc.get_array("a")
    with doc.transact() as txn:
        for i in range(5):
            arr.push_back(txn, i)
    log = []
    doc.observe_update_v1(lambda p, o, t: log.append(p))
    with doc.transact() as txn:
        arr.move_to(txn, 0, 4)
    with doc.transact() as txn:
        arr.move_range_to(txn, 1, 2, 0)

    buf, stream, flags = _decode(log, U=4)
    assert (flags & FLAG_ERRORS == 0).all(), flags
    st = {k: np.asarray(v) for k, v in stream._asdict().items()}
    from ytpu.core import Update as _U

    for s, payload in enumerate(log):
        up = _U.decode_v1(payload)
        want = []
        for client, blocks in sorted(up.blocks.items()):
            for blk in blocks:
                mv = blk.content.move
                want.append(
                    (
                        mv.start.id.client,
                        mv.start.id.clock,
                        mv.start.assoc,
                        mv.end.id.client,
                        mv.end.id.clock,
                        mv.end.assoc,
                        max(mv.priority, 0),
                    )
                )
        got = [
            (
                int(st["mv_sc"][s, u]),
                int(st["mv_sk"][s, u]),
                int(st["mv_sa"][s, u]),
                int(st["mv_ec"][s, u]),
                int(st["mv_ek"][s, u]),
                int(st["mv_ea"][s, u]),
                int(st["mv_prio"][s, u]),
            )
            for u in range(st["valid"].shape[1])
            if st["valid"][s, u] and st["kind"][s, u] == CONTENT_MOVE
        ]
        assert got == want, (s, got, want)


def test_move_stream_rides_fast_lane_end_to_end():
    """An array move stream through BatchIngestor.apply_bytes: device
    decode + XLA integrate + claim recompute render the host-identical
    order."""
    from ytpu.models.ingest import BatchIngestor
    from ytpu.models.batch_doc import get_tree

    doc = Doc(client_id=1)
    arr = doc.get_array("a")
    log = []
    doc.observe_update_v1(lambda p, o, t: log.append(p))
    with doc.transact() as txn:
        for i in range(6):
            arr.push_back(txn, i)
    with doc.transact() as txn:
        arr.move_to(txn, 0, 5)
    with doc.transact() as txn:
        arr.move_range_to(txn, 1, 2, 0)

    ing = BatchIngestor(1, 256)
    for p in log:
        ing.apply_bytes([p])
    assert ing.fast_docs == len(log), (ing.fast_docs, ing.slow_docs)
    assert int(np.asarray(ing.state.error).max()) == 0
    tree = get_tree(
        ing.state, 0, ing.payloads, ing.enc.keys, interner=ing.enc.interner
    )
    assert tree["seq"] == arr.to_json()


def test_flat_map_any_values_decode_clean():
    """Depth-1 object values ({str: scalar}) decode on device: header +
    per-key + per-scalar-value steps; content refs re-parse on host via
    read_any."""
    doc = Doc(client_id=5)
    log = []
    doc.observe_update_v1(lambda p, o, t: log.append(p))
    arr = doc.get_array("a")
    with doc.transact() as txn:
        arr.insert(txn, 0, {"name": "zed", "age": 7, "tall": True})
    with doc.transact() as txn:
        arr.insert(txn, 1, [1, {"k": None}, "s"])
    buf, stream, flags = _decode(log, U=4, R=4)
    assert (flags & FLAG_ERRORS == 0).all(), flags
    view = RawPayloadView(buf)
    st = {k: np.asarray(v) for k, v in stream._asdict().items()}
    vals0 = view.slice_values(int(st["content_ref"][0, 0]), 0, 1)
    assert vals0 == [{"name": "zed", "age": 7, "tall": True}]
    # a python list inserts as ONE nested Any value (array token whose
    # children include a depth-1 object)
    vals1 = view.slice_values(int(st["content_ref"][1, 0]), 0, 1)
    assert vals1 == [[1, {"k": None}, "s"]]


def test_map_tenant_object_values_ride_fast_lane():
    from ytpu.models.batch_doc import get_tree
    from ytpu.models.ingest import BatchIngestor

    doc = Doc(client_id=1)
    log = []
    doc.observe_update_v1(lambda p, o, t: log.append(p))
    m = doc.get_map("root")
    with doc.transact() as txn:
        m.insert(txn, "config", {"theme": "dark", "size": 14})
    ing = BatchIngestor(1, 128)
    for p in log:
        ing.apply_bytes([p])
    assert ing.fast_docs == len(log), (ing.fast_docs, ing.slow_docs)
    tree = get_tree(
        ing.state, 0, ing.payloads, ing.enc.keys, interner=ing.enc.interner
    )
    assert tree["map"]["config"] == {"theme": "dark", "size": 14}


# the cases above that call the decoder themselves (the two `apply_bytes`
# cases go through the served entry already)
DECODING_CASES = [
    test_insert_delete_field_parity, test_unicode_utf16_lengths, test_end_to_end_replay_matches_host,
    test_merged_update_multi_block, test_multi_client_flagged_informational, test_content_any_scalars_decode_clean,
    test_recursive_any_flags_unsupported, test_map_parent_sub_without_table_flags_unknown_key,
    test_map_parent_sub_with_key_table_decodes, test_big_client_id_flags, test_truncated_update_flags_malformed,
    test_row_overflow_flags, test_mixed_batch_bad_lane_emits_nothing, test_gc_rows_decode,
    test_huge_string_length_varint_flags_malformed, test_content_type_nested_types_decode,
    test_weak_type_flags_unsupported, test_content_move_rows_decode, test_flat_map_any_values_decode_clean,
]


@pytest.mark.parametrize("case", DECODING_CASES, ids=lambda fn: fn.__name__[len("test_"):])
def test_the_served_entry_hands_back_the_same_batch_as_a_pair(case):
    """`decode_updates_v1(..., packed=True)`, the entry the served step
    calls: three output buffers (the pair and the flags), and the pair taken
    apart is the planes entry's 27 planes bit for bit, clean lanes and
    flagged ones alike, over every decode the case makes."""
    import jax

    from ytpu.models.batch_doc import PackedBatch, unpack_batch_jit

    made = case.__name__
    if made not in _CALLS:  # run on its own: the case's calls are made here, under this test's name
        case()
        made = _current_test()
    assert _CALLS[made]
    for args, kw, (planes, flags) in _CALLS[made]:
        pair, pair_flags = _decode_planes(*args, **kw, packed=True)
        assert type(pair) is PackedBatch and len(jax.tree.leaves((pair, pair_flags))) == 3
        U, R = planes.valid.shape[-1], planes.del_valid.shape[-1]
        assert pair.rows.shape == (len(flags), U, 23) and pair.dels.shape == (len(flags), R, 4)
        assert np.asarray(pair_flags).tobytes() == np.asarray(flags).tobytes()
        for name, got, want in zip(planes._fields, unpack_batch_jit(pair), planes):
            got, want = np.asarray(got), np.asarray(want)
            assert got.dtype == want.dtype and got.shape == want.shape and got.tobytes() == want.tobytes(), name


@pytest.mark.usefixtures("native_lib")
def test_pack_updates_into_reuse_is_clean():
    """Slot reuse can never alias stale bytes into a later decode: after
    re-packing a shorter payload over a longer one, the tail up to the
    previous occupant's length + guard is zeroed."""
    from ytpu.ops.decode_kernel import _PAD, pack_updates_into

    buf = np.zeros((4, 64), dtype=np.uint8)
    lens = np.zeros((4,), dtype=np.int32)
    pack_updates_into([b"\x01" * 40, b"\x02" * 8], buf, lens)
    assert lens.tolist() == [40, 8, 2, 2]  # short rows pad as EMPTY_UPDATE
    pack_updates_into([b"\x03" * 6], buf, lens)
    assert lens[0] == 6
    assert buf[0, :6].tolist() == [3] * 6
    assert not buf[0, 6 : 40 + _PAD].any(), "stale bytes survived reuse"
    with pytest.raises(ValueError, match="exceeds staging width"):
        pack_updates_into([b"\x04" * 60], buf, lens)


@pytest.mark.usefixtures("native_lib")
def test_gather_raw_lanes_matches_pack_updates_with_moves():
    """The device lane-gather materializes a byte-IDENTICAL matrix to
    host `pack_updates` — including the zero mask past each lane's
    length that the decoder's prefix sums and gather guard read. Driven
    on a stream with LIVE MOVES, map rows, and Any content, this pins
    raw-vs-packed decode parity for every content kind the V1 decoder
    supports without compiling a second decode program."""
    import jax.numpy as jnp

    from ytpu.core import Doc
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
    # the flat arena and its prefix table, with room for a padding tail
    wire = np.frombuffer(b"".join(log), dtype=np.uint8)
    woffs = np.zeros(len(log) + 1, dtype=np.int64)
    np.cumsum([len(p) for p in log], out=woffs[1:])
    chunk = len(log)
    cap = -(-(int(woffs[-1]) + 2) // 64) * 64
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
