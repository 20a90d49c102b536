"""Native (C++) lib0 decoder parity vs the Python decoder."""

import random
import string

import numpy as np
import pytest

from ytpu.core import Doc, Update
from ytpu.native import decode_update_columns

pytestmark = pytest.mark.usefixtures("native_lib")


def flatten_python(update: Update):
    """Python-decoded update → comparable row list (client-desc order)."""
    rows = []
    for client in sorted(update.blocks.keys(), reverse=True):
        for b in update.blocks[client]:
            rows.append((b.id.client, b.id.clock, b.len))
    return sorted(rows)


def native_rows(cols):
    return sorted(
        zip(cols.client.tolist(), cols.clock.tolist(), cols.length.tolist())
    )


def test_native_matches_python_on_random_docs():
    rng = random.Random(5)
    doc = Doc(client_id=77)
    t = doc.get_text("t")
    m = doc.get_map("m")
    a = doc.get_array("a")
    with doc.transact() as txn:
        for _ in range(30):
            word = "".join(rng.choice(string.ascii_lowercase) for _ in range(5))
            t.insert(txn, rng.randint(0, len(t)), word + "é😀")
            m.insert(txn, rng.choice("xyz"), [1, {"k": "v"}, None])
            a.push_back(txn, rng.random())
    with doc.transact() as txn:
        t.remove_range(txn, 3, 10)
    payload = doc.encode_state_as_update_v1()
    cols = decode_update_columns(payload)
    assert cols is not None and not cols.error
    u = Update.decode_v1(payload)
    assert native_rows(cols) == flatten_python(u)
    # delete set parity
    py_dels = sorted(
        (c, s, e) for c, rs in u.delete_set.clients.items() for s, e in rs
    )
    nat_dels = sorted(
        zip(cols.del_client.tolist(), cols.del_start.tolist(), cols.del_end.tolist())
    )
    assert nat_dels == py_dels


def test_native_string_utf16_lengths():
    doc = Doc(client_id=1)
    t = doc.get_text("t")
    with doc.transact() as txn:
        t.insert(txn, 0, "a😀b")  # 4 utf-16 units
    payload = doc.encode_state_as_update_v1()
    cols = decode_update_columns(payload)
    assert cols.length.tolist() == [4]


def test_native_parent_and_sub_spans():
    doc = Doc(client_id=1)
    m = doc.get_map("mymap")
    with doc.transact() as txn:
        m.insert(txn, "thekey", "val")
    payload = doc.encode_state_as_update_v1()
    cols = decode_update_columns(payload)
    assert cols.parent_kind.tolist() == [1]
    assert cols.parent_name(0) == "mymap"
    assert cols.parent_sub(0) == "thekey"


def test_native_handles_yjs_capture():
    from tests.test_yjs_compat import TEXT_UPDATE, TEXT_CLIENT

    cols = decode_update_columns(TEXT_UPDATE)
    assert not cols.error
    assert cols.n_blocks == 5
    assert all(c == TEXT_CLIENT for c in cols.client.tolist())
    assert cols.clock.tolist() == [0, 3, 5, 6, 7]
    assert cols.length.tolist() == [3, 2, 1, 1, 2]
