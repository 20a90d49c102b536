"""Wire logs several test files share: a typing trace made from a seed, the
committed B4 editing trace, and the deep-conflict storm.

Every builder returns updates as a client's `Doc` emitted them, one a
transaction, and what the host oracle reads after them.
"""

import gzip
import os
import pickle
import random
import string

from ytpu.core import Doc

#: the B4 editing trace (yrs benches.rs B4: 259,778 single-character edits
#: of one paper) as the wire updates a client emitted, one an edit; the
#: benchmark's typed cell makes its `b4_flags.txt` from the same file
B4_LOG = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "benches", "data", "b4_log.pkl.gz",
)


def synthetic_ops(limit: int, seed: int = 7):
    """`limit` ("i", pos, word) / ("d", pos, n) edits of one text."""
    rng = random.Random(seed)
    ops = []
    length = 0
    for _ in range(limit):
        if length > 20 and rng.random() < 0.25:
            pos = rng.randint(0, length - 6)
            n = rng.randint(1, 5)
            ops.append(("d", pos, n))
            length -= n
        else:
            word = "".join(
                rng.choice(string.ascii_lowercase) for _ in range(rng.randint(3, 9))
            )
            ops.append(("i", rng.randint(0, length), word))
            length += len(word)
    return ops


def capture(doc):
    """The list `doc`'s transactions append their wire updates to."""
    log = []
    doc.observe_update_v1(lambda p, o, t: log.append(p))
    return log


def build_updates(ops):
    """Replay ops on a host doc: (one wire update an op, the text)."""
    doc = Doc(client_id=1)
    log = capture(doc)
    txt = doc.get_text("text")
    for tag, pos, arg in ops:
        with doc.transact() as txn:
            if tag == "i":
                txt.insert(txn, pos, arg)
            else:
                txt.remove_range(txn, pos, arg)
    return log, txt.get_string()


def load_b4_log(limit=None):
    """The first `limit` updates of the B4 trace (all of them for None)
    and the text a host replica reads after them. The file is this
    repo's own (`benches/data/`), written by the script that made it."""
    with gzip.open(B4_LOG, "rb") as f:
        cached = pickle.load(f)
    if limit is None:
        return cached["log"], cached["expect"]
    log = cached["log"][:limit]
    oracle = Doc(client_id=99)
    for update in log:
        oracle.apply_update_v1(update)
    return log, oracle.get_text("text").get_string()


def build_conflict_stream(n_clients: int, inserts_each: int,
                          erase_every: int = 4, rounds: int = 1,
                          typed: bool = False, erase_len: int = 2):
    """N concurrent clients all inserting at ONE origin position of a
    shared base text — the YATA worst case: every integration scans the
    other clients' already-integrated same-origin siblings. Clients
    never see each other before the merge, so the converged text does not
    depend on the interleave and the host oracle decides.

    `erase_every > 0` has every erase_every-th client delete `erase_len`
    chars of its round's inserts; `typed=True` types rightward (insert at
    5, 6, 7, ... — ascending clocks, sequence-adjacent) so the erased
    runs are the shape a compaction can merge and reclaim (the default
    stack-order inserts at one position produce DESCENDING-clock runs
    whose tombstones cannot merge); conflict depth survives `typed`
    because each run's FIRST insert still anchors on the shared base
    origin and scans every other client's run.

    Returns ``(payloads, expect_text)``: the base first, then round-robin
    across clients so the conflict set grows as wide as possible, and the
    host oracle's converged text. Clients are 1 (the base) and 10..."""
    base = Doc(client_id=1)
    base_log = capture(base)
    txt = base.get_text("text")
    with base.transact() as txn:
        txt.insert(txn, 0, "0123456789")
    base_update = base.encode_state_as_update_v1()

    per_client = []
    for k in range(n_clients):
        doc = Doc(client_id=10 + k)
        doc.apply_update_v1(base_update)
        log = capture(doc)
        t = doc.get_text("text")
        for _ in range(rounds):
            for i in range(inserts_each):
                with doc.transact() as txn:
                    t.insert(txn, 5 + (i if typed else 0),
                             "abcdefgh"[(k + i) % 8])
            if erase_every and k % erase_every == 0:
                # interleaved deletes: tombstones inside the conflict
                # neighborhood (the scan walks deleted rows too)
                with doc.transact() as txn:
                    t.remove_range(txn, 5, erase_len)
        per_client.append(log)

    payloads = list(base_log)
    for i in range(max(len(log) for log in per_client)):
        for log in per_client:
            if i < len(log):
                payloads.append(log[i])

    oracle = Doc(client_id=2)
    for p in payloads:
        oracle.apply_update_v1(p)
    return payloads, oracle.get_text("text").get_string()


def build_move_storm():
    """Concurrent same-origin ARRAY inserts (root "a", 48 siblings at index
    3), a live `move_range_to` a client and deletes: the conflict scan
    walks move rows and tombstones. Returns the payloads, round-robin
    across the 8 clients after the base, and the oracle's array."""
    base = Doc(client_id=1)
    base_log = capture(base)
    arr = base.get_array("a")
    with base.transact() as txn:
        for v in range(12):
            arr.push_back(txn, v)
    base_update = base.encode_state_as_update_v1()

    per_client = []
    for k in range(8):
        doc = Doc(client_id=10 + k)
        doc.apply_update_v1(base_update)
        log = capture(doc)
        a = doc.get_array("a")
        for i in range(6):  # concurrent same-origin inserts at index 3
            with doc.transact() as txn:
                a.insert(txn, 3, 1000 * k + i)
        with doc.transact() as txn:  # a live move spanning the storm
            a.move_range_to(txn, 1, 3, len(a) - 1)
        if k % 3 == 0:
            with doc.transact() as txn:
                a.remove_range(txn, 2, 3)
        per_client.append(log)

    payloads = list(base_log)
    for i in range(max(len(log) for log in per_client)):
        for log in per_client:
            if i < len(log):
                payloads.append(log[i])
    oracle = Doc(client_id=2)
    for p in payloads:
        oracle.apply_update_v1(p)
    expect = oracle.get_array("a").to_json()
    return payloads, expect


def build_array_relay_stream(n_clients: int, ops_per_client: int, seed: int = 11):
    """`n_clients` peers concurrently edit one array (root "a"), exchanging
    through a relay doc so every op becomes one wire update of the relay:
    (the relay's log, its array)."""
    rng = random.Random(seed)
    relay = Doc(client_id=0xFFFF)
    log = capture(relay)
    peers = [Doc(client_id=i + 1) for i in range(n_clients)]
    order = [i for i in range(n_clients) for _ in range(ops_per_client)]
    rng.shuffle(order)
    for i in order:
        peer = peers[i]
        arr = peer.get_array("a")
        n = len(arr)
        with peer.transact() as txn:
            if n > 4 and rng.random() < 0.3:
                arr.remove_range(txn, rng.randrange(n), 1)
            else:
                arr.insert(txn, rng.randrange(n + 1), [rng.randrange(1000)])
        relay.apply_update_v1(peer.encode_state_as_update_v1(relay.state_vector()))
        # relay fans back out so peers stay roughly in sync
        if rng.random() < 0.5:
            peer.apply_update_v1(relay.encode_state_as_update_v1(peer.state_vector()))
    return log, relay.get_array("a").to_json()
