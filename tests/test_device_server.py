"""Device-backed sync server: protocol tenants mirrored into batch slots."""

import pytest

from ytpu.core import Doc
from ytpu.sync.device_server import DeviceSyncServer
from ytpu.sync.protocol import Message, SyncMessage


def push(server, session, peer_doc):
    sv = server.doc(session.tenant).state_vector()
    diff = peer_doc.encode_state_as_update_v1(sv)
    server.receive(session, Message.sync(SyncMessage.update(diff)).encode_v1())


def test_tenants_fan_into_device_slots():
    server = DeviceSyncServer(n_docs=4, capacity=256)
    s_pad, _ = server.connect("pad")
    s_doc, _ = server.connect("docs")

    alice = Doc(client_id=1)
    with alice.transact() as txn:
        alice.get_text("text").insert(txn, 0, "alice writes")
    push(server, s_pad, alice)

    bob = Doc(client_id=2)
    with bob.transact() as txn:
        bob.get_text("text").insert(txn, 0, "bob too")
    push(server, s_doc, bob)

    assert server.pending_device_updates() == 2
    steps = server.flush_device()
    assert steps == 1  # both tenants ship in ONE batch step
    assert server.pending_device_updates() == 0
    assert int(server.ingestor.state.error.max()) == 0
    assert server.device_text("pad") == "alice writes" == server.doc("pad").get_text("text").get_string()
    assert server.device_text("docs") == "bob too"


def test_chatty_tenant_does_not_block_quiet_one():
    server = DeviceSyncServer(n_docs=2, capacity=512)
    s_a, _ = server.connect("chatty")
    peer = Doc(client_id=5)
    for i in range(6):
        with peer.transact() as txn:
            t = peer.get_text("text")
            t.insert(txn, t.branch.content_len, f"{i}")
        push(server, s_a, peer)

    s_b, _ = server.connect("quiet")
    other = Doc(client_id=6)
    with other.transact() as txn:
        other.get_text("text").insert(txn, 0, "q")
    push(server, s_b, other)

    steps = server.flush_device()
    assert steps >= 1
    assert server.device_text("chatty") == "012345"
    assert server.device_text("quiet") == "q"
    assert int(server.ingestor.state.error.max()) == 0


def test_concurrent_sessions_converge_on_device():
    server = DeviceSyncServer(n_docs=1, capacity=512)
    s1, _ = server.connect("room")
    s2, _ = server.connect("room")
    a, b = Doc(client_id=11), Doc(client_id=22)
    for d, text in ((a, "left "), (b, "right ")):
        with d.transact() as txn:
            d.get_text("text").insert(txn, 0, text)
    push(server, s1, a)
    push(server, s2, b)
    server.flush_device()
    assert server.device_text("room") == server.doc("room").get_text("text").get_string()


def test_slot_exhaustion_raises():
    import pytest

    server = DeviceSyncServer(n_docs=1, capacity=64)
    server.connect("one")
    with pytest.raises(RuntimeError):
        server.connect("two")


def test_slot_exhaustion_retry_still_raises_and_leaves_no_ghost():
    import pytest

    server = DeviceSyncServer(n_docs=1, capacity=64)
    server.connect("one")
    with pytest.raises(RuntimeError):
        server.connect("two")
    assert "two" not in server.tenants  # no ghost tenant registered
    with pytest.raises(RuntimeError):
        server.connect("two")  # retry fails identically


def test_unknown_tenant_read_raises_instead_of_allocating():
    import pytest

    server = DeviceSyncServer(n_docs=2, capacity=64)
    server.connect("pad")
    with pytest.raises(KeyError):
        server.device_text("padd")  # typo: no silent slot allocation
    assert len(server._slot_of) == 1


def test_ingestor_is_slot_authority():
    from ytpu.models.ingest import BatchIngestor

    ing = BatchIngestor(3, 64)
    server = DeviceSyncServer(ingestor=ing)  # n_docs not needed
    for name in ("a", "b", "c"):
        server.connect(name)
    import pytest

    with pytest.raises(RuntimeError):
        server.connect("d")


def test_device_diff_formatted_tenant():
    from ytpu.sync.device_server import DeviceSyncServer

    srv = DeviceSyncServer(n_docs=2, capacity=256)
    t = srv.tenant("doc")
    doc = t.awareness.doc
    txt = doc.get_text("text")
    with doc.transact() as txn:
        txt.insert(txn, 0, "plain ")
    with doc.transact() as txn:
        txt.insert_with_attributes(txn, 6, "bold", {"b": True})
    srv.flush_device()
    got = srv.device_diff("doc")
    assert got == txt.diff(), f"{got!r} != {txt.diff()!r}"


def _client_pump(doc: Doc, server, session, client_frames: bytes) -> None:
    """Drive one client side of the y-sync handshake: process the server's
    frames against a local Doc and deliver replies back."""
    from ytpu.sync.protocol import Protocol, message_reader

    proto = Protocol()

    class _A:  # minimal awareness shim around the client doc
        def __init__(self, d):
            self.doc = d

        def update(self):
            from ytpu.sync.awareness import Awareness

            return Awareness(self.doc).update()

        def apply_update(self, u):
            pass

    aw = _A(doc)
    out = []
    for msg in message_reader(client_frames):
        reply = proto.handle_message(aw, msg)
        if reply is not None:
            out.append(reply.encode_v1())
    if out:
        server.receive(session, b"".join(out))


def test_device_authoritative_serving_converges_without_host_doc():
    """VERDICT r1 #7: sync step 2 answered from device state; the host
    tenant doc is demoted to an awareness anchor and never sees content."""
    server = DeviceSyncServer(n_docs=2, capacity=512, device_authoritative=True)

    # client A writes, connects, pushes its state as an update
    alice = Doc(client_id=1)
    with alice.transact() as txn:
        alice.get_text("text").insert(txn, 0, "hello from alice")
    s_a, greeting_a = server.connect("pad")
    _client_pump(alice, server, s_a, greeting_a)  # step1 -> client step2
    server.receive(
        s_a,
        Message.sync(
            SyncMessage.update(alice.encode_state_as_update_v1())
        ).encode_v1(),
    )
    server.flush_device()
    assert server.device_text("pad") == "hello from alice"

    # the host tenant doc never saw content (device-authoritative)
    assert server.doc("pad").get_text("text").get_string() == ""

    # client B connects fresh: sends step1, receives the device diff
    bob = Doc(client_id=2)
    s_b, greeting_b = server.connect("pad")
    _client_pump(bob, server, s_b, greeting_b)
    from ytpu.core.state_vector import StateVector
    from ytpu.sync.protocol import message_reader

    reply = server.receive(
        s_b, Message.sync(SyncMessage.step1(StateVector())).encode_v1()
    )
    for msg in message_reader(reply):
        assert msg.kind == 0 and msg.body.tag == 1  # SyncStep2
        bob.apply_update_v1(msg.body.payload)
    assert bob.get_text("text").get_string() == "hello from alice"

    # live edit from B broadcasts to A and lands on device
    with bob.transact() as txn:
        bob.get_text("text").insert(txn, 0, ">> ")
    sv_dev = server.device_state_vector("pad")
    server.receive(
        s_b,
        Message.sync(
            SyncMessage.update(bob.encode_state_as_update_v1(sv_dev))
        ).encode_v1(),
    )
    server.flush_device()
    assert server.device_text("pad") == ">> hello from alice"
    # A's outbox got the broadcast frame
    frames = server.drain(s_a)
    assert frames
    for f in frames:
        for msg in message_reader(f):
            if msg.kind == 0 and msg.body.tag == 2:
                alice.apply_update_v1(msg.body.payload)
    assert alice.get_text("text").get_string() == ">> hello from alice"


def test_device_authoritative_incremental_diff():
    """A reconnecting client with partial state gets only the missing
    blocks (diff vs its state vector, computed on device)."""
    server = DeviceSyncServer(n_docs=1, capacity=512, device_authoritative=True)
    writer = Doc(client_id=7)
    with writer.transact() as txn:
        writer.get_text("text").insert(txn, 0, "part one. ")
    s, greeting = server.connect("doc")
    server.receive(
        s,
        Message.sync(
            SyncMessage.update(writer.encode_state_as_update_v1())
        ).encode_v1(),
    )
    server.flush_device()

    # reader syncs fully now
    reader = Doc(client_id=8)
    sv0 = reader.state_vector()
    from ytpu.sync.protocol import message_reader

    reply = server.receive(s, Message.sync(SyncMessage.step1(sv0)).encode_v1())
    for msg in message_reader(reply):
        reader.apply_update_v1(msg.body.payload)
    assert reader.get_text("text").get_string() == "part one. "

    # writer adds more; reader reconnects with its current sv
    with writer.transact() as txn:
        t = writer.get_text("text")
        t.insert(txn, len(t.get_string()), "part two.")
    server.receive(
        s,
        Message.sync(
            SyncMessage.update(
                writer.encode_state_as_update_v1(
                    server.device_state_vector("doc")
                )
            )
        ).encode_v1(),
    )
    server.flush_device()
    reply = server.receive(
        s, Message.sync(SyncMessage.step1(reader.state_vector())).encode_v1()
    )
    for msg in message_reader(reply):
        reader.apply_update_v1(msg.body.payload)
    assert reader.get_text("text").get_string() == "part one. part two."


def test_multi_root_tenant_stays_device_resident():
    """A tenant whose clients use several named roots (text+map — the
    reference's normal doc shape, doc.rs:156-228) is served from the
    device batch: the first root maps onto the implicit branch, the
    second anchors through a BLOCK_ROOT_ANCHOR row, and a fresh replica
    syncing from device state reconstructs BOTH roots byte-exactly."""
    from ytpu.core import Doc
    from ytpu.core.state_vector import StateVector
    from ytpu.sync.device_server import DeviceSyncServer
    from ytpu.sync.protocol import Message, SyncMessage

    pod = DeviceSyncServer(n_docs=2, capacity=256, device_authoritative=True)
    session, _ = pod.connect_frames("app")

    c = Doc(client_id=31)
    log = []
    c.observe_update_v1(lambda p, o, t: log.append(p))
    with c.transact() as txn:
        c.get_text("body").insert(txn, 0, "words")
    with c.transact() as txn:
        c.get_map("meta").insert(txn, "title", "doc one")
    with c.transact() as txn:
        c.get_text("body").insert(txn, 5, "!")
    for p in log:
        pod.receive_frames(
            session, Message.sync(SyncMessage.update(p)).encode_v1()
        )
    pod.flush_device()
    assert "app" not in pod._host_tenants  # device-resident (VERDICT r3 #9)
    assert pod.device_text("app") == "words!"
    tree = pod.device_tree("app")
    assert tree["roots"]["meta"]["map"] == {"title": "doc one"}

    # a fresh client syncing sees BOTH roots intact
    session2, greeting = pod.connect_frames("app")
    step1 = Message.sync(
        SyncMessage.step1(StateVector({}))
    ).encode_v1()
    replies = pod.receive_frames(session2, step1)
    d = Doc(client_id=32)
    from ytpu.sync.protocol import message_reader

    for frame in list(greeting) + replies:
        for m in message_reader(frame):
            if m.kind == 0 and m.body.tag == 1:
                d.apply_update_v1(m.body.payload)
    assert d.get_text("body").get_string() == "words!"
    assert d.get_map("meta").to_json() == {"title": "doc one"}


def test_multi_root_tenant_checkpoint_roundtrip(tmp_path):
    """Multi-root tenants survive a checkpoint DEVICE-resident: anchor
    rows persist in the block state, the primary-root registry in the
    sidecar — a restored pod serves both roots from the batch."""
    from ytpu.core import Doc
    from ytpu.core.state_vector import StateVector
    from ytpu.models.checkpoint import load_device_server, save_device_server
    from ytpu.sync.device_server import DeviceSyncServer
    from ytpu.sync.protocol import Message, SyncMessage, message_reader

    pod = DeviceSyncServer(n_docs=2, capacity=256, device_authoritative=True)
    session, _ = pod.connect_frames("app")
    c = Doc(client_id=41)
    log = []
    c.observe_update_v1(lambda p, o, t: log.append(p))
    with c.transact() as txn:
        c.get_text("a").insert(txn, 0, "alpha")
    with c.transact() as txn:
        c.get_text("b").insert(txn, 0, "beta")
    for p in log:
        pod.receive_frames(
            session, Message.sync(SyncMessage.update(p)).encode_v1()
        )
    assert "app" not in pod._host_tenants

    save_device_server(str(tmp_path / "pod"), pod)
    restored = load_device_server(str(tmp_path / "pod"))
    assert "app" not in restored._host_tenants
    assert restored.device_text("app") == "alpha"
    assert restored.ingestor.primary_roots[restored.slot_of("app")] == "a"
    # a fresh replica syncs both roots from the restored device state
    s2, greeting = restored.connect_frames("app")
    replies = restored.receive_frames(
        s2, Message.sync(SyncMessage.step1(StateVector({}))).encode_v1()
    )
    d = Doc(client_id=42)
    for frame in list(greeting) + replies:
        for m in message_reader(frame):
            if m.kind == 0 and m.body.tag == 1:
                d.apply_update_v1(m.body.payload)
    assert d.get_text("a").get_string() == "alpha"
    assert d.get_text("b").get_string() == "beta"


def test_explicit_demotion_reclaims_device_slot():
    """The operational escape hatch (`_demote_to_host`) still moves a
    tenant to the host path losslessly and frees its slot for a new
    tenant — multi-root alone no longer triggers it."""
    from ytpu.core import Doc
    from ytpu.sync.device_server import DeviceSyncServer
    from ytpu.sync.protocol import Message, SyncMessage

    pod = DeviceSyncServer(n_docs=1, capacity=256, device_authoritative=True)
    session, _ = pod.connect_frames("multi")
    c = Doc(client_id=51)
    log = []
    c.observe_update_v1(lambda p, o, t: log.append(p))
    with c.transact() as txn:
        c.get_text("a").insert(txn, 0, "x")
    with c.transact() as txn:
        c.get_text("b").insert(txn, 0, "y")
    for p in log:
        pod.receive_frames(
            session, Message.sync(SyncMessage.update(p)).encode_v1()
        )
    assert "multi" not in pod._host_tenants  # multi-root stays on device
    pod._demote_to_host("multi")
    assert "multi" in pod._host_tenants
    doc = pod.tenant("multi").awareness.doc
    assert doc.get_text("a").get_string() == "x"
    assert doc.get_text("b").get_string() == "y"
    # the single slot was reclaimed: a NEW tenant fits a 1-slot pod
    s2, _ = pod.connect_frames("fresh")
    d = Doc(client_id=52)
    log2 = []
    d.observe_update_v1(lambda p, o, t: log2.append(p))
    with d.transact() as txn:
        d.get_text("t").insert(txn, 0, "fresh-tenant")
    for p in log2:
        pod.receive_frames(s2, Message.sync(SyncMessage.update(p)).encode_v1())
    pod.flush_device()
    assert pod.device_text("fresh") == "fresh-tenant"


def test_mirrored_server_checkpoint_keeps_host_docs(tmp_path):
    from ytpu.models.checkpoint import load_device_server, save_device_server
    from ytpu.sync.device_server import DeviceSyncServer

    pod = DeviceSyncServer(n_docs=2, capacity=256)  # mirrored mode
    doc = pod.doc("pad")
    with doc.transact() as txn:
        doc.get_text("t").insert(txn, 0, "persisted")
    pod.flush_device()
    save_device_server(str(tmp_path / "pod"), pod)
    restored = load_device_server(str(tmp_path / "pod"))
    assert not restored.device_authoritative
    assert restored.doc("pad").get_text("t").get_string() == "persisted"


def test_unflushed_queue_survives_checkpoint(tmp_path):
    from ytpu.core import Doc
    from ytpu.core.state_vector import StateVector
    from ytpu.models.checkpoint import load_device_server, save_device_server
    from ytpu.sync.device_server import DeviceSyncServer
    from ytpu.sync.protocol import Message, SyncMessage

    pod = DeviceSyncServer(n_docs=2, capacity=256, device_authoritative=True)
    session, _ = pod.connect_frames("pad")
    c = Doc(client_id=61)
    with c.transact() as txn:
        c.get_text("t").insert(txn, 0, "acked")
    upd = c.encode_state_as_update_v1(StateVector({}))
    pod.receive_frames(
        session, Message.sync(SyncMessage.update(upd)).encode_v1()
    )
    # NO flush_device() here: save must flush so the ack is durable
    save_device_server(str(tmp_path / "pod"), pod)
    restored = load_device_server(str(tmp_path / "pod"))
    assert restored.device_text("pad") == "acked"


# the apply stack that left in PR 48: the replay drivers, the fused kernel
GONE = ("ytpu.ops.integrate_kernel", "ytpu.models.replay", "ytpu.models.pipeline")


@pytest.mark.parametrize("module", ["ytpu", "ytpu.models", "ytpu.sync.device_server"])
def test_one_apply_stack_is_all_an_import_finds(module):
    """The package, its models and the served server load none of the
    replay stack's modules, and none of them can be imported: the served
    path (`DeviceSyncServer` -> `BatchIngestor` -> `apply_update_batch`) is
    the one apply stack. In a process of its own, so what other tests
    imported is not in the way."""
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = (
        "import importlib, importlib.util, sys\n"
        f"importlib.import_module({module!r})\n"
        f"gone = {GONE!r}\n"
        "print([m for m in gone if m in sys.modules], "
        "[m for m in gone if importlib.util.find_spec(m) is not None])"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=root)
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=root, env=env, capture_output=True, text=True, timeout=300
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == "[] []"
