"""C++ scalar YATA engine (`ytpu/native/engine.cpp`) — the native-speed
baseline. Oracle: the host `ytpu.core.Doc` replaying the same streams.

Reference semantics covered: YATA conflict scan with client-id tie-break
(yrs/src/block.rs:537-602), block splits on mid-block origins and delete
boundaries (block_store.rs:402-417), apply_delete (transaction.rs:472-575),
partial-redelivery offsets (block.rs:482 `offset` param), UTF-16 content
lengths (block.rs:1386-1502).
"""

import random

import pytest

from ytpu.core import Doc
from ytpu.native import (
    NativeEngine,
    NativeUnsupported,
    native_replay_v1,
)

needs_native = pytest.mark.usefixtures("native_lib")


def _edit_log(ops, client_id=1):
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


@needs_native
def test_sequential_inserts_deletes():
    ops = [
        ("i", 0, "hello world"),
        ("i", 5, ","),
        ("d", 2, 4),
        ("i", 0, ">> "),
        ("d", 0, 1),
        ("i", 8, "XYZ"),
    ]
    log, expect = _edit_log(ops)
    assert native_replay_v1(log) == expect


@needs_native
def test_utf16_surrogates_and_multibyte():
    ops = [
        ("i", 0, "aπc🙂e"),
        ("i", 2, "🙈🙉"),
        ("d", 1, 3),
        ("i", 0, "ß"),
    ]
    log, expect = _edit_log(ops)
    assert native_replay_v1(log) == expect


@needs_native
def test_random_single_client_fuzz():
    rng = random.Random(42)
    ops = []
    length = 0
    for _ in range(400):
        if length > 5 and rng.random() < 0.35:
            pos = rng.randint(0, length - 2)
            n = rng.randint(1, min(5, length - pos))
            ops.append(("d", pos, n))
            length -= n
        else:
            word = "".join(
                rng.choice("abcdefgπ🙂") for _ in range(rng.randint(1, 6))
            )
            ops.append(("i", rng.randint(0, length), word))
            length += len(word)
    log, expect = _edit_log(ops)
    assert native_replay_v1(log) == expect


@needs_native
def test_concurrent_two_client_convergence():
    """Concurrent edits exchanged both ways: the YATA conflict scan must
    order same-position inserts identically to the host engine."""
    rng = random.Random(7)
    a, b = Doc(client_id=1), Doc(client_id=2)
    log_a, log_b = [], []
    a.observe_update_v1(lambda p, o, t: log_a.append(p))
    b.observe_update_v1(lambda p, o, t: log_b.append(p))
    ta, tb = a.get_text("text"), b.get_text("text")

    interleaved = []  # causal application order for the engine
    for round_ in range(30):
        for doc, txt, log, mark in ((a, ta, log_a, "A"), (b, tb, log_b, "B")):
            n = len(txt.get_string())
            with doc.transact() as txn:
                if n > 4 and rng.random() < 0.3:
                    pos = rng.randint(0, n - 2)
                    txt.remove_range(txn, pos, rng.randint(1, 2))
                else:
                    txt.insert(txn, rng.randint(0, n), f"{mark}{round_}")
            interleaved.append(log[-1])
        # exchange after each round so dependencies stay satisfied (use the
        # captured payloads — observers also fire on remote applies)
        pa, pb = interleaved[-2], interleaved[-1]
        b.apply_update_v1(pa)
        a.apply_update_v1(pb)
    assert ta.get_string() == tb.get_string()

    eng = NativeEngine()
    for p in interleaved:
        eng.apply_update_v1(p)
    assert eng.text() == ta.get_string()
    eng.close()


@needs_native
def test_duplicate_and_partial_redelivery():
    ops = [("i", 0, "abcdef"), ("i", 3, "XY"), ("d", 1, 2)]
    log, expect = _edit_log(ops)
    eng = NativeEngine()
    for p in log:
        eng.apply_update_v1(p)
        eng.apply_update_v1(p)  # exact duplicate: idempotent
    assert eng.text() == expect
    eng.close()


@needs_native
def test_unsupported_stream_raises():
    # moves are the remaining out-of-scope content kind (map keys and
    # nested parents are in scope since round 5)
    doc = Doc(client_id=1)
    log = []
    doc.observe_update_v1(lambda p, o, t: log.append(p))
    arr = doc.get_array("a")
    with doc.transact() as txn:
        arr.insert_range(txn, 0, [1, 2, 3])
    with doc.transact() as txn:
        arr.move_to(txn, 0, 2)
    with pytest.raises(NativeUnsupported):
        native_replay_v1(log)


@needs_native
def test_map_and_nested_xml_parity():
    doc = Doc(client_id=1)
    log = []
    doc.observe_update_v1(lambda p, o, t: log.append(p))
    m = doc.get_map("m")
    from ytpu.types import XmlElementPrelim

    frag = doc.get_xml_fragment("x")
    with doc.transact() as txn:
        m.insert(txn, "k", "v")
        m.insert(txn, "n", [1, {"a": True}])
        frag.insert(txn, 0, XmlElementPrelim("div", attributes={"id": "d1"}))
    with doc.transact() as txn:
        m.insert(txn, "k", "v2")  # overwrite: last write wins
        m.remove(txn, "n")
    eng = NativeEngine()
    for p in log:
        eng.apply_update_v1(p)
    assert eng.root_json("m", "map") == m.to_json()
    assert eng.root_json("x", "seq") == [
        {"name": "div", "attrs": {"id": "d1"}, "children": []}
    ]
    eng.close()


@needs_native
def test_concurrent_array_parity():
    from _traces import build_array_relay_stream

    log, expect = build_array_relay_stream(n_clients=24, ops_per_client=2, seed=3)
    eng = NativeEngine()
    for p in log:
        eng.apply_update_v1(p)
    assert eng.root_json("a", "seq") == expect
    eng.close()


@needs_native
def test_b4_trace_prefix_parity():
    from _traces import build_updates, load_b4_log, synthetic_ops

    log, expect = load_b4_log(3000)
    assert native_replay_v1(log) == expect
    log, expect = build_updates(synthetic_ops(3000))
    assert native_replay_v1(log) == expect


def test_concurrent_loads_build_one_hash_named_library(tmp_path):
    """Two fresh processes that find no library both build-or-wait and end
    with the SAME whole, content-named file — the six-xdist-worker race
    that used to leave a worker without the native lanes."""
    import json
    import shutil
    import subprocess
    import sys
    from pathlib import Path

    import ytpu.native

    src = Path(ytpu.native.__file__).parent
    pkg = tmp_path / "native_copy"
    shutil.copytree(
        src, pkg, ignore=shutil.ignore_patterns("*.so", ".build.lock", "__pycache__")
    )
    assert not list(pkg.glob("*.so"))
    prog = (
        "import json, sys; sys.path.insert(0, sys.argv[1]); import native_copy as n;"
        "lib = n.load(); ok = lib is not None and n.native_replay_v1([]) == '';"
        "print(json.dumps({'path': lib and lib._name, 'ok': ok}))"
    )
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", prog, str(tmp_path)],
            stdout=subprocess.PIPE,
            text=True,
        )
        for _ in range(2)
    ]
    outs = [json.loads(p.communicate(timeout=300)[0].splitlines()[-1]) for p in procs]
    assert all(o["ok"] for o in outs), outs
    assert outs[0]["path"] == outs[1]["path"]
    name = Path(outs[0]["path"]).name
    assert name == Path(ytpu.native._lib_path()).name  # same sources, same hash
    assert [p.name for p in pkg.glob("*.so")] == [name]  # no temp left behind
