"""Device-side V2 update decoding (ytpu/ops/decode_v2.py).

Parity oracle: host `Update.decode_v2` on the same bytes. The device lane
must emit identical block rows / delete ranges for the supported set
(GC / Skip / Deleted / String, root + nested parents, parent_sub keys,
multi-section, delete sets) and flag everything else to the host lane —
VERDICT r2 #5: a V2-encoded B4 stream rides the raw-bytes lane with zero
host fallbacks.
"""

import os
import random
import string as _string

import numpy as np
import pytest

from ytpu.core import Doc, Update
from ytpu.core.state_vector import StateVector
from ytpu.ops.decode_kernel import FLAG_ERRORS, FLAG_UNSUPPORTED, utf8_slice_u16
from ytpu.ops.decode_v2 import decode_updates_v2, pack_updates_v2


def v1_to_v2(payload: bytes) -> bytes:
    return Update.decode_v1(payload).encode_v2()


def capture_v1(ops_fn, client_id=1):
    doc = Doc(client_id=client_id)
    log = []
    doc.observe_update_v1(lambda p, o, t: log.append(p))
    ops_fn(doc)
    return doc, log


def decode(payloads_v2, max_rows=8, max_dels=8, **kw):
    buf, lens, spans, side = pack_updates_v2(payloads_v2)
    stream, flags = decode_updates_v2(
        buf, lens, spans, max_rows, max_dels, sidecar=side, **kw
    )
    return buf, stream, np.asarray(flags)


def oracle_rows(payload_v2):
    """(client, clock, length, kind-ish) rows from the host decoder."""
    up = Update.decode_v2(payload_v2)
    rows = []
    for client, blocks in sorted(up.blocks.items()):
        for b in blocks:
            rows.append((client, b.id.clock, b.len))
    return rows


def test_plain_text_inserts_roundtrip():
    def ops(doc):
        t = doc.get_text("text")
        for chunk in ["hello ", "world", "!"]:
            with doc.transact() as txn:
                t.insert(txn, len(t), chunk)

    doc, log = capture_v1(ops)
    v2 = [v1_to_v2(p) for p in log]
    buf, stream, flags = decode(v2)
    assert (flags & FLAG_ERRORS == 0).all(), flags
    valid = np.asarray(stream.valid)
    for s, payload in enumerate(v2):
        got = [
            (
                int(np.asarray(stream.client)[s, u]),
                int(np.asarray(stream.clock)[s, u]),
                int(np.asarray(stream.length)[s, u]),
            )
            for u in range(valid.shape[1])
            if valid[s, u]
        ]
        assert got == oracle_rows(payload), (s, got)
    # string contents slice straight out of the packed buffer
    flat = np.asarray(buf).reshape(-1)
    refs = np.asarray(stream.content_ref)
    texts = []
    for s in range(len(v2)):
        for u in range(valid.shape[1]):
            if valid[s, u] and refs[s, u] >= 0:
                texts.append(
                    utf8_slice_u16(
                        flat,
                        refs[s, u],
                        0,
                        int(np.asarray(stream.length)[s, u]),
                    )
                )
    assert texts == ["hello ", "world", "!"]


def test_deletes_and_delete_set():
    def ops(doc):
        t = doc.get_text("text")
        with doc.transact() as txn:
            t.insert(txn, 0, "abcdefgh")
        with doc.transact() as txn:
            t.remove_range(txn, 2, 3)
        with doc.transact() as txn:
            t.remove_range(txn, 0, 1)

    doc, log = capture_v1(ops)
    v2 = [v1_to_v2(p) for p in log]
    _, stream, flags = decode(v2)
    assert (flags & FLAG_ERRORS == 0).all(), flags
    dvalid = np.asarray(stream.del_valid)
    for s, payload in enumerate(v2):
        up = Update.decode_v2(payload)
        want = []
        for client, ranges in sorted(up.delete_set.clients.items()):
            for a, bnd in ranges:
                want.append((client, a, bnd))
        got = sorted(
            (
                int(np.asarray(stream.del_client)[s, r]),
                int(np.asarray(stream.del_start)[s, r]),
                int(np.asarray(stream.del_end)[s, r]),
            )
            for r in range(dvalid.shape[1])
            if dvalid[s, r]
        )
        assert got == sorted(want), (s, got, want)


def test_merged_multi_client_update_with_skips():
    """Merged updates exercise multi-section wire + Skip runs."""
    from ytpu.compat import merge_updates

    # build two docs whose merged update has 2 client sections + a skip
    d1 = Doc(client_id=1)
    with d1.transact() as txn:
        d1.get_text("text").insert(txn, 0, "aaaa")
    d2 = Doc(client_id=2)
    d2.apply_update_v1(d1.encode_state_as_update_v1(StateVector({})))
    with d2.transact() as txn:
        d2.get_text("text").insert(txn, 2, "bb")
    u_all = d2.encode_state_as_update_v1(StateVector({}))
    # a gapped second update from client 1 (skip synthesized on merge)
    with d1.transact() as txn:
        d1.get_text("text").insert(txn, 0, "x")
    with d1.transact() as txn:
        d1.get_text("text").insert(txn, 0, "y")
    full = d1.encode_state_as_update_v1(StateVector({}))
    merged = merge_updates(u_all, full)
    v2 = [v1_to_v2(merged)]
    _, stream, flags = decode(v2, max_rows=12)
    assert (flags & FLAG_ERRORS == 0).all(), flags
    valid = np.asarray(stream.valid)
    got = [
        (
            int(np.asarray(stream.client)[0, u]),
            int(np.asarray(stream.clock)[0, u]),
            int(np.asarray(stream.length)[0, u]),
        )
        for u in range(valid.shape[1])
        if valid[0, u]
    ]
    # oracle emits items + GC only (skips carry no row)
    up = Update.decode_v2(v2[0])
    want = []
    for client, blocks in sorted(up.blocks.items()):
        for blk in blocks:
            if type(blk).__name__ != "SkipRange":
                want.append((client, blk.id.clock, blk.len))
    assert sorted(got) == sorted(want), (got, want)


def test_map_rows_parent_sub_keys():
    from ytpu.ops.decode_kernel import key_hash_host

    def ops(doc):
        m = doc.get_map("config")
        with doc.transact() as txn:
            m.insert(txn, "title", "zedoc")

    doc, log = capture_v1(ops)
    v2 = [v1_to_v2(p) for p in log]
    # Round 4 widened the lane: ContentAny map values DEVICE-decode. The
    # parent_sub key must resolve through the key table — without one the
    # lane flags FLAG_UNKNOWN_KEY (host fallback interns for next step).
    from ytpu.ops.decode_kernel import FLAG_UNKNOWN_KEY

    _, stream, flags = decode(v2)
    assert (flags & FLAG_UNKNOWN_KEY != 0).all()
    assert not np.asarray(stream.valid).any()
    assert not np.asarray(stream.valid).any()


def test_random_text_trace_parity():
    rng = random.Random(11)

    def ops(doc):
        t = doc.get_text("text")
        for _ in range(40):
            with doc.transact() as txn:
                n = len(t)
                if n > 6 and rng.random() < 0.35:
                    pos = rng.randint(0, n - 3)
                    t.remove_range(txn, pos, rng.randint(1, 3))
                else:
                    word = "".join(
                        rng.choice(_string.ascii_lowercase)
                        for _ in range(rng.randint(1, 8))
                    )
                    t.insert(txn, rng.randint(0, n), word)

    doc, log = capture_v1(ops)
    v2 = [v1_to_v2(p) for p in log]
    _, stream, flags = decode(v2, max_rows=8, max_dels=8)
    assert (flags & FLAG_ERRORS == 0).all(), flags
    valid = np.asarray(stream.valid)
    dvalid = np.asarray(stream.del_valid)
    for s, payload in enumerate(v2):
        up = Update.decode_v2(payload)
        want = []
        for client, blocks in sorted(up.blocks.items()):
            for blk in blocks:
                want.append((client, blk.id.clock, blk.len))
        got = [
            (
                int(np.asarray(stream.client)[s, u]),
                int(np.asarray(stream.clock)[s, u]),
                int(np.asarray(stream.length)[s, u]),
            )
            for u in range(valid.shape[1])
            if valid[s, u]
        ]
        assert sorted(got) == sorted(want), (s, got, want)
        want_d = []
        for client, ranges in sorted(up.delete_set.clients.items()):
            for a, bnd in ranges:
                want_d.append((client, a, bnd))
        got_d = sorted(
            (
                int(np.asarray(stream.del_client)[s, r]),
                int(np.asarray(stream.del_start)[s, r]),
                int(np.asarray(stream.del_end)[s, r]),
            )
            for r in range(dvalid.shape[1])
            if dvalid[s, r]
        )
        assert got_d == sorted(want_d), (s, got_d, want_d)


def test_unicode_string_offsets():
    def ops(doc):
        t = doc.get_text("text")
        with doc.transact() as txn:
            t.insert(txn, 0, "héllo 🌍 wörld")
        with doc.transact() as txn:
            t.insert(txn, 3, "日本語")

    doc, log = capture_v1(ops)
    v2 = [v1_to_v2(p) for p in log]
    buf, stream, flags = decode(v2)
    assert (flags & FLAG_ERRORS == 0).all(), flags
    flat = np.asarray(buf).reshape(-1)
    valid = np.asarray(stream.valid)
    texts = [
        utf8_slice_u16(
            flat,
            int(np.asarray(stream.content_ref)[s, u]),
            0,
            int(np.asarray(stream.length)[s, u]),
        )
        for s in range(len(v2))
        for u in range(valid.shape[1])
        if valid[s, u]
    ]
    assert texts == ["héllo 🌍 wörld", "日本語"]
    # lengths are UTF-16 units (surrogate pair counts 2)
    assert int(np.asarray(stream.length)[0, 0]) == 14


def test_apply_v2_device_stream_end_to_end():
    """A V2 stream decoded on device integrates into the batch engine and
    renders the same text as the host replay — zero host fallbacks."""
    import jax.numpy as jnp

    from ytpu.models.batch_doc import (
        apply_update_stream,
        get_string,
        init_state,
    )
    from ytpu.models.batch_doc import BatchEncoder
    from ytpu.ops.decode_kernel import RawPayloadView, identity_rank

    rng = random.Random(5)

    def ops(doc):
        t = doc.get_text("text")
        for _ in range(25):
            with doc.transact() as txn:
                n = len(t)
                if n > 5 and rng.random() < 0.3:
                    t.remove_range(txn, rng.randint(0, n - 2), 1)
                else:
                    t.insert(
                        txn,
                        rng.randint(0, n),
                        rng.choice(_string.ascii_lowercase) * rng.randint(1, 4),
                    )

    doc, log = capture_v1(ops)
    v2 = [v1_to_v2(p) for p in log]
    buf, lens, spans, side = pack_updates_v2(v2)
    stream, flags = decode_updates_v2(buf, lens, spans, 4, 4, sidecar=side)
    assert (np.asarray(flags) & FLAG_ERRORS == 0).all(), np.asarray(flags)

    # the stream is already step-shaped: update s = step s over the batch
    state = init_state(1, 256)
    state = apply_update_stream(state, stream, identity_rank(2))
    payloads = RawPayloadView(np.asarray(buf))
    assert int(np.asarray(state.error).max()) == 0
    assert get_string(state, 0, payloads) == doc.get_text("text").get_string()


def test_b4_trace_prefix_rides_device_lane():
    """VERDICT r2 #5 'done' criterion: a V2-encoded B4 editing-trace stream
    decodes on the device lane with ZERO host fallbacks, and the decoded
    stream integrates to the same text as the host replay."""
    from _traces import load_b4_log

    from ytpu.models.batch_doc import apply_update_stream, get_string, init_state
    from ytpu.ops.decode_kernel import RawPayloadView, identity_rank

    log, expect = load_b4_log(400)
    v2 = [v1_to_v2(p) for p in log]
    buf, lens, spans, side = pack_updates_v2(v2)
    stream, flags = decode_updates_v2(buf, lens, spans, 4, 4, sidecar=side)
    f = np.asarray(flags)
    assert (f & FLAG_ERRORS == 0).all(), f[(f & FLAG_ERRORS) != 0][:5]

    state = init_state(1, 4096)
    state = apply_update_stream(state, stream, identity_rank(2))
    assert int(np.asarray(state.error).max()) == 0
    got = get_string(state, 0, RawPayloadView(np.asarray(buf)))
    assert got == expect


def test_big_client_ids_resolve_through_hash_table():
    """Real Yjs client ids (random 53-bit) ride the V2 lane: the expander
    reconstructs each big id's unsigned-varint bytes from its signed V2
    encoding and hashes with client_hash_host's mixing."""
    import jax.numpy as jnp

    from ytpu.ops.decode_kernel import client_hash_host

    big_a = (1 << 52) + 12345
    big_b = (1 << 45) + 7
    d1 = Doc(client_id=big_a)
    with d1.transact() as txn:
        d1.get_text("t").insert(txn, 0, "from-a")
    d2 = Doc(client_id=big_b)
    d2.apply_update_v1(d1.encode_state_as_update_v1(StateVector({})))
    with d2.transact() as txn:
        d2.get_text("t").insert(txn, 3, "-b-")
    with d2.transact() as txn:
        # a deletion: the DS client id (rest stream) must hash too
        d2.get_text("t").remove_range(txn, 0, 1)
    v2 = [v1_to_v2(d2.encode_state_as_update_v1(StateVector({})))]

    # interner tables: both ids interned; big ones registered in the hash
    # table exactly as BatchIngestor does
    idx = {big_a: 0, big_b: 1}
    hashes = {client_hash_host(c): i for c, i in idx.items()}
    hs = sorted(hashes)
    cht = (
        jnp.asarray(np.asarray(hs, dtype=np.int32)),
        jnp.asarray(np.asarray([hashes[h] for h in hs], dtype=np.int32)),
    )
    client_table = (
        jnp.asarray(np.zeros(0, dtype=np.int64)),
        jnp.asarray(np.zeros(0, dtype=np.int32)),
    )
    buf, lens, spans, side = pack_updates_v2(v2)
    stream, flags = decode_updates_v2(
        buf, lens, spans, 8, 8,
        client_table=client_table,
        client_hash_table=cht,
    )
    f = np.asarray(flags)
    assert (f & FLAG_ERRORS == 0).all(), f
    valid = np.asarray(stream.valid)
    got = sorted(
        int(np.asarray(stream.client)[0, u])
        for u in range(valid.shape[1])
        if valid[0, u]
    )
    # both blocks present, each client resolved to its DISTINCT index
    assert set(got) == {0, 1}
    dvalid = np.asarray(stream.del_valid)
    ds_clients = {
        int(np.asarray(stream.del_client)[0, r])
        for r in range(dvalid.shape[1])
        if dvalid[0, r]
    }
    assert ds_clients and ds_clients <= {0, 1}

    # without a hash table the lane flags FLAG_BIG_CLIENT
    from ytpu.ops.decode_kernel import FLAG_BIG_CLIENT

    _, flags2 = decode_updates_v2(buf, lens, spans, 8, 8)
    assert np.asarray(flags2)[0] & FLAG_BIG_CLIENT


@pytest.mark.skipif(
    not os.environ.get("YTPU_RUN_SLOW"),
    reason="full-trace V2 decode (minutes); set YTPU_RUN_SLOW=1",
)
def test_b4_full_trace_rides_v2_device_lane():
    """VERDICT r3 #4 'done' criterion, first half: the FULL 259,778-op B4
    editing trace, V2-encoded, decodes on the V2 device lane with ZERO
    host fallbacks (chunked; every lane's flags clean), and a sampled
    chunk integrates to text parity with the host replay."""
    from _traces import load_b4_log

    from ytpu.models.batch_doc import apply_update_stream, get_string, init_state
    from ytpu.ops.decode_kernel import RawPayloadView, identity_rank

    log, expect = load_b4_log()
    v2 = [v1_to_v2(p) for p in log]
    CHUNK = 8192
    total_flagged = 0
    for base in range(0, len(v2), CHUNK):
        part = v2[base : base + CHUNK]
        buf, lens, spans, side = pack_updates_v2(part, pad_to=64)
        stream, flags = decode_updates_v2(buf, lens, spans, 4, 4, sidecar=side)
        f = np.asarray(flags)
        total_flagged += int((f & FLAG_ERRORS != 0).sum())
    assert total_flagged == 0, f"{total_flagged} lanes fell back to host"

    # parity spot-check: integrate the first chunk and compare against a
    # host replay of the same prefix
    n = min(CHUNK, len(log))
    doc = Doc(client_id=99)
    for p in log[:n]:
        doc.apply_update_v1(p)
    buf, lens, spans, side = pack_updates_v2(v2[:n], pad_to=64)
    stream, flags = decode_updates_v2(buf, lens, spans, 4, 4, sidecar=side)
    state = init_state(1, 1 << 14)
    state = apply_update_stream(state, stream, identity_rank(2))
    assert int(np.asarray(state.error).max()) == 0
    got = get_string(state, 0, RawPayloadView(np.asarray(buf)))
    assert got == doc.get_text("text").get_string()


def test_widened_content_kinds_ride_device_lane():
    """VERDICT r3 #4: the V2 columnar decoder's rest WALKER device-decodes
    Any values (depth-1 lists/objects), Binary bufs, map LWW chains (via
    the key table) and Move payloads with ZERO host fallbacks — the V2
    lane's supported set now covers every north-star array/map workload
    shape. (Since round 5, Type/Embed/Format/Json also ride the lane via
    the pack-time V1-form sidecar — see the cold-content tests below;
    only Doc content and weak type tags stay per-lane flagged.)"""
    import jax.numpy as jnp

    from ytpu.models.batch_doc import (
        KeyInterner,
        apply_update_stream,
        get_tree,
        init_state,
    )
    from ytpu.ops.decode_kernel import (
        RawPayloadView,
        identity_rank,
        key_hash_host,
    )

    d = Doc(client_id=3)
    log = []
    d.observe_update_v1(lambda p, o, t: log.append(p))
    arr = d.get_array("a")
    with d.transact() as txn:
        arr.insert_range(txn, 0, [1, "two", 3.5, True, None])
    with d.transact() as txn:
        arr.insert_range(txn, 2, [[1, 2], {"k": 7}])
    with d.transact() as txn:
        arr.insert_range(txn, 0, [b"\x00\xffbinary"])
    with d.transact() as txn:
        arr.remove_range(txn, 2, 2)
    m = d.get_map("a")
    with d.transact() as txn:
        m.insert(txn, "x", 42)
    with d.transact() as txn:
        m.insert(txn, "x", 43)  # LWW replacement (origin-chained)
    with d.transact() as txn:
        arr.move_to(txn, 1, 3)

    v2 = [v1_to_v2(p) for p in log]
    buf, lens, spans, side = pack_updates_v2(v2, pad_to=128)
    keys = KeyInterner()
    kt = (
        jnp.asarray([key_hash_host(b"x")]),
        jnp.asarray([keys.intern("x")]),
    )
    stream, flags = decode_updates_v2(buf, lens, spans, 8, 4, key_table=kt, sidecar=side)
    f = np.asarray(flags)
    assert (f & FLAG_ERRORS == 0).all(), f"host fallbacks: {f}"

    state = init_state(1, 256)
    state = apply_update_stream(state, stream, identity_rank(2))
    assert int(np.asarray(state.error).max()) == 0
    view = RawPayloadView(np.asarray(buf), v2_any=True)
    tree = get_tree(state, 0, view, keys)
    assert tree["seq"] == arr.to_json(), (tree["seq"], arr.to_json())
    assert tree["map"] == {"x": 43}, tree["map"]


def test_nested_any_values_ride_device_lane():
    """Round 5: the rest walker's container STACK device-decodes Any
    values with maps nested to W_DEPTH - 1 = 3 levels and arrays nested
    arbitrarily (r4 flagged anything past depth 1)."""
    from ytpu.ops.decode_kernel import RawPayloadView

    deep_vals = [
        {"deep": [1, 2, 3]},                       # map -> array
        {"a": {"b": 7}, "c": [4, [5, 6]]},         # map -> map / arr -> arr
        [{"x": [1, {"y": 2}]}, 9],                 # arr -> map -> arr -> map
        {"e": [], "f": 2},                         # EMPTY array as pair value
        [{"g": [1, []]}, {}, []],                  # empty arr/map tails
        "plain",
    ]
    d = Doc(client_id=5)
    log = []
    d.observe_update_v1(lambda p, o, t: log.append(p))
    arr = d.get_array("a")
    with d.transact() as txn:
        arr.insert_range(txn, 0, deep_vals)
    with d.transact() as txn:
        arr.insert(txn, 2, {"tail": {"k": [10]}})
    v2 = [v1_to_v2(p) for p in log]
    buf, lens, spans, side = pack_updates_v2(v2, pad_to=256)
    stream, flags = decode_updates_v2(buf, lens, spans, 8, 4, sidecar=side)
    f = np.asarray(flags)
    assert (f & FLAG_ERRORS == 0).all(), f"host fallbacks: {f}"
    view = RawPayloadView(np.asarray(buf), v2_any=True)
    valid = np.asarray(stream.valid)
    refs = np.asarray(stream.content_ref)
    lengths = np.asarray(stream.length)
    got = []
    for s in range(len(v2)):
        for u in range(valid.shape[1]):
            if valid[s, u] and refs[s, u] >= 0:
                got.extend(view.slice_values(refs[s, u], 0, int(lengths[s, u])))
    assert got == deep_vals + [{"tail": {"k": [10]}}], got


def test_too_deep_any_values_fall_back_to_host():
    """Maps nested beyond the walker's W_DEPTH - 1 = 3 levels exceed the
    stacked scope and must flag the lane — never decode wrong."""
    d = Doc(client_id=5)
    log = []
    d.observe_update_v1(lambda p, o, t: log.append(p))
    arr = d.get_array("a")
    with d.transact() as txn:
        arr.insert_range(txn, 0, [{"a": {"b": {"c": {"d": 1}}}}])
    v2 = [v1_to_v2(p) for p in log]
    buf, lens, spans, side = pack_updates_v2(v2, pad_to=128)
    stream, flags = decode_updates_v2(buf, lens, spans, 4, 4, sidecar=side)
    f = np.asarray(flags)
    assert (f & FLAG_UNSUPPORTED != 0).all(), f
    assert not np.asarray(stream.valid).any()  # flagged lanes emit no rows


def test_cold_content_payload_refs_resolve_v1_form():
    """Round 5 (VERDICT r4 #4): Json / Embed / Format / Type content
    structure-decodes on the V2 device lane; each row's payload ref
    points at the pack-time V1-form sidecar span and every V1-shaped
    reader resolves it — validated field-by-field against the host
    decoder."""
    from collections import deque

    from ytpu.core.block import Item
    from ytpu.core.content import ContentJSON
    from ytpu.core.id_set import DeleteSet
    from ytpu.core.ids import ID
    from ytpu.ops.decode_kernel import RawPayloadView
    from ytpu.types import XmlElementPrelim

    d = Doc(client_id=11)
    log = []
    d.observe_update_v1(lambda p, o, t: log.append(p))
    t = d.get_text("t")
    with d.transact() as txn:
        t.insert(txn, 0, "hello world")
    with d.transact() as txn:
        t.format(txn, 0, 5, {"bold": True})
    with d.transact() as txn:
        t.insert_embed(txn, 5, {"img": "x.png"})
    frag = d.get_xml_fragment("x")
    with d.transact() as txn:
        frag.insert(txn, 0, XmlElementPrelim("div", attributes={"id": "a1"}))
    v2 = [v1_to_v2(p) for p in log]
    # hand-crafted legacy ContentJSON carrier (the host lib never emits
    # one; the wire still must decode — block.rs:1786-1835 uniformity)
    ContentJSON  # noqa: B018 — imported for the carrier below
    it = Item(
        ID(99, 0), None, None, None, None, "j", None,
        ContentJSON(["1", '{"a": 2}']),
    )
    up = Update({99: deque([it])}, DeleteSet())
    v2.append(up.encode_v2())

    buf, lens, spans, side = pack_updates_v2(v2, pad_to=256)
    assert side is not None  # cold kinds detected
    import jax.numpy as jnp

    from ytpu.models.batch_doc import KeyInterner
    from ytpu.ops.decode_kernel import key_hash_host

    keys = KeyInterner()
    kt = (
        jnp.asarray([key_hash_host(b"id")]),
        jnp.asarray([keys.intern("id")]),
    )
    stream, flags = decode_updates_v2(
        buf, lens, spans, 8, 4, key_table=kt, sidecar=side
    )
    f = np.asarray(flags)
    assert (f & FLAG_ERRORS == 0).all(), f"host fallbacks: {f}"

    view = RawPayloadView(np.asarray(buf), v2_any=True)
    valid = np.asarray(stream.valid)
    kinds = np.asarray(stream.kind)
    refs = np.asarray(stream.content_ref)
    lengths = np.asarray(stream.length)
    from ytpu.core.content import (
        CONTENT_EMBED as K_EMBED,
        CONTENT_FORMAT as K_FMT,
        CONTENT_JSON as K_JSON,
        CONTENT_TYPE as K_TYPE,
    )

    seen = {"fmt": 0, "embed": 0, "type": 0, "json": 0}
    for s, payload in enumerate(v2):
        hosts = []
        for client, blocks in sorted(Update.decode_v2(payload).blocks.items()):
            hosts.extend(b for b in blocks if getattr(b, "content", None))
        hi = 0
        for u in range(valid.shape[1]):
            if not valid[s, u]:
                continue
            host_content = hosts[hi].content if hi < len(hosts) else None
            hi += 1
            k, ref = int(kinds[s, u]), int(refs[s, u])
            if k == K_FMT:
                key, val = view.format_kv(ref)
                assert (key, val) == (host_content.key, host_content.value)
                seen["fmt"] += 1
            elif k == K_EMBED:
                assert view.embed_value(ref) == host_content.value
                seen["embed"] += 1
            elif k == K_TYPE:
                br = view.type_branch(ref)
                assert br.type_ref == host_content.branch.type_ref
                assert br.type_name == host_content.branch.type_name
                seen["type"] += 1
            elif k == K_JSON:
                assert (
                    view.json_raw(ref, 0, int(lengths[s, u]))
                    == host_content.raw
                )
                seen["json"] += 1
    assert all(v > 0 for v in seen.values()), seen


def test_rich_text_stream_rides_v2_device_lane():
    """Format + embed text streams decode on the V2 lane with zero host
    fallbacks and integrate to the same rich-text runs as the host."""
    from ytpu.models.batch_doc import (
        apply_update_stream,
        get_diff,
        init_state,
    )
    from ytpu.ops.decode_kernel import RawPayloadView, identity_rank

    def ops(doc):
        t = doc.get_text("text")
        with doc.transact() as txn:
            t.insert(txn, 0, "the quick brown fox")
        with doc.transact() as txn:
            t.format(txn, 4, 5, {"b": True})
        with doc.transact() as txn:
            t.insert_embed(txn, 9, {"u": "e.png"})
        with doc.transact() as txn:
            t.format(txn, 4, 5, {"b": None})  # unformat
        with doc.transact() as txn:
            t.remove_range(txn, 0, 4)

    doc, log = capture_v1(ops)
    v2 = [v1_to_v2(p) for p in log]
    buf, lens, spans, side = pack_updates_v2(v2, pad_to=256)
    stream, flags = decode_updates_v2(buf, lens, spans, 8, 4, sidecar=side)
    f = np.asarray(flags)
    assert (f & FLAG_ERRORS == 0).all(), f"host fallbacks: {f}"

    state = init_state(1, 256)
    state = apply_update_stream(state, stream, identity_rank(2))
    assert int(np.asarray(state.error).max()) == 0
    view = RawPayloadView(np.asarray(buf), v2_any=True)
    got = get_diff(state, 0, view)
    want = doc.get_text("text").diff()
    assert [(r.insert, r.attributes) for r in got] == [
        (r.insert, r.attributes) for r in want
    ]


@pytest.mark.usefixtures("native_lib")
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
