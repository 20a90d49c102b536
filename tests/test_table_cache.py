"""The device-resident lookup tables of `BatchIngestor` (ISSUE-32).

The client, key, client-hash and rank tables hold everything interned, not
a step's, so a step hands its programs the device arrays of the last build
until a table's source changes. What changes is when a table is built,
never what it holds: every step's tables are compared, bit for bit, with
what the step used to build (the `_parent_*` functions below are that
code), over a served sequence in which clients, map keys, a key-hash
collision and a big-client collision arrive at chosen steps.

Since ISSUE-35 a table has the ingestor's `_table_floor` entries whoever
has written: what the parent built sits at its end, behind padding whose
key is -1 (the rank table's padding follows its ranks, as it did).
"""

import numpy as np
import pytest

from ytpu.core import Doc
from ytpu.models import ingest as ingest_mod
from ytpu.models.batch_doc import PackedBatch, get_string
from ytpu.models.ingest import BatchIngestor
from ytpu.ops import decode_kernel as dk
from ytpu.utils import metrics

pytestmark = pytest.mark.usefixtures("native_lib")

TABLES = ("client_table", "key_table", "client_hash_table", "client_rank")
COUNTERS = ("ingest.table_builds", "ingest.table_reuses")
# two keys of one device hash (byte0 + 31 * byte1, equal lengths) ...
KEY, KEY_TWIN = "Ab", "`a"
# ... and two ids past i32 whose varint bytes hash alike
BIG = 2**40 + 10 + (20 << 7)
BIG_TWIN = 2**40 + 41 + (19 << 7)
BIG_OTHER = 2**41 + 5
OUTSIDE = range(777, 783)  # interned from outside the ingestor, as benchmark/run.py does


def test_the_twins_collide():
    assert dk.key_hash_host(KEY.encode()) == dk.key_hash_host(KEY_TWIN.encode())
    assert dk.client_hash_host(BIG) == dk.client_hash_host(BIG_TWIN)
    assert dk.client_hash_host(BIG) != dk.client_hash_host(BIG_OTHER)


# --- what the parent built, every step -----------------------------------------


FLOOR = 1024  # `BatchIngestor._table_floor` of a server this small


def _parent_sorted_table(mapping):
    ks = sorted(mapping)
    pad = FLOOR - len(ks)
    return (
        np.asarray([-1] * pad + ks, dtype=np.int32),
        np.asarray([0] * pad + [mapping[k] for k in ks], dtype=np.int32),
    )


def _parent_tables(ing) -> dict:
    to_idx, from_idx = ing.enc.interner.to_idx, ing.enc.interner.from_idx
    ids = sorted(c for c in to_idx if 0 <= c <= 2**31 - 1)
    n = len(from_idx)
    ranks = np.zeros(FLOOR, dtype=np.int32)
    for rank, idx in enumerate(sorted(range(n), key=lambda i: from_idx[i])):
        ranks[idx] = rank
    return dict(
        client_table=_parent_sorted_table({c: to_idx[c] for c in ids}),
        key_table=_parent_sorted_table(ing._key_hashes),
        client_hash_table=_parent_sorted_table(ing._client_hashes),
        client_rank=ranks,
    )


def _host(table) -> tuple:
    """A table's leaves as host arrays: a table is one array or a pair."""
    leaves = table if isinstance(table, (tuple, list)) else (table,)
    return tuple(np.asarray(a) for a in leaves)


def _bit_equal(handed, want) -> bool:
    handed, want = _host(handed), _host(want)
    return len(handed) == len(want) and all(
        h.dtype == w.dtype and h.shape == w.shape and h.tobytes() == w.tobytes()
        for h, w in zip(handed, want)
    )


def _flag_lanes(stream, flags, bad=None):
    """The served decoder's `(PackedBatch, flags)` as the device hands them
    back when it flags the lanes `bad` ([S] bool; every lane by default): the
    flag set, and none of their rows or ranges valid (the last column of
    either array)."""
    import jax.numpy as jnp

    bad = jnp.ones(flags.shape, bool) if bad is None else jnp.asarray(bad)
    keep = (~bad).astype(jnp.int32)[:, None]
    stream = PackedBatch(stream.rows.at[..., -1].multiply(keep), stream.dels.at[..., -1].multiply(keep))
    return stream, jnp.where(bad, flags | dk.FLAG_MALFORMED, flags)


class _Spy:
    """What `decode_updates_v1` and `apply_update_batch` were handed, call
    by call, beside what the parent would have built at that moment."""

    def __init__(self, monkeypatch, flag_decode=None):
        self.ing = None
        self.handed = []  # per program call: {table: device arrays}
        self.wanted = []  # per program call: {table: the parent's host arrays}
        self.decodes = 0
        real_decode, real_apply = dk.decode_updates_v1, ingest_mod.apply_update_batch_in_place

        def decode(buf, lens, max_rows, max_dels, **kw):
            want = _parent_tables(self.ing)
            self.handed.append({t: kw[t] for t in TABLES[:3]})
            self.wanted.append({t: want[t] for t in TABLES[:3]})
            stream, flags = real_decode(buf, lens, max_rows, max_dels, **kw)
            self.decodes += 1
            if self.decodes == flag_decode:  # the device flags every lane of this call
                stream, flags = _flag_lanes(stream, flags)
            return stream, flags

        def apply(state, batch, client_rank, *rest):
            self.handed.append({"client_rank": client_rank})
            self.wanted.append({"client_rank": _parent_tables(self.ing)["client_rank"]})
            return real_apply(state, batch, client_rank, *rest)

        monkeypatch.setattr(dk, "decode_updates_v1", decode)
        monkeypatch.setattr(ingest_mod, "apply_update_batch_in_place", apply)

    def since(self, call: int) -> dict:
        """The tables handed over from program call `call` on, by name."""
        return {t: arrays for h in self.handed[call:] for t, arrays in h.items()}

    def wrong(self):
        return [
            (call, t)
            for call, (h, w) in enumerate(zip(self.handed, self.wanted))
            for t in h
            if not _bit_equal(h[t], w[t])
        ]


def _counts() -> dict:
    return {n: metrics.counter(n).value for n in COUNTERS}


def _counted(before: dict) -> dict:
    return {n: v - before[n] for n, v in _counts().items()}


class _Room:
    """One room's clients: whoever edits has seen every earlier update, so
    each update is applicable the moment it is sent."""

    def __init__(self):
        self.sent = []
        self.docs = {}  # client -> (its Doc, the updates it emitted, how many of `sent` it saw)

    def edit(self, client: int, fn) -> bytes:
        if client not in self.docs:
            doc, emitted = Doc(client_id=client), []
            doc.observe_update_v1(lambda p, o, t: emitted.append(p))
            self.docs[client] = (doc, emitted, 0)
        doc, emitted, seen = self.docs[client]
        for u in self.sent[seen:]:
            doc.apply_update_v1(u)
        with doc.transact() as txn:
            fn(doc, txn)
        self.sent.append(emitted[-1])  # the wire update of the edit, as a client sends it
        self.docs[client] = (doc, emitted, len(self.sent))
        return self.sent[-1]

    def oracle(self) -> Doc:
        doc = Doc(client_id=999_999)
        for u in self.sent:
            doc.apply_update_v1(u)
        return doc


def _type(word, at=0):
    return lambda doc, txn: doc.get_text("text").insert(txn, at, word)


def _cut(at, n):
    return lambda doc, txn: doc.get_text("text").remove_range(txn, at, n)


def _put(*pairs):
    def fn(doc, txn):
        for key, value in pairs:
            doc.get_map("m").insert(txn, key, value)

    return fn


# step -> (client, edit) of room 0; every other step client 1 types a word.
# Room 1 takes one plain insert a step from client 50 (so every step has a
# fast lane, whichever lane room 0's update takes); room 2 only BIG_TWIN's.
ROOM0 = {
    3: (2, _type("two ")),  # a client first seen
    6: (1, _put((KEY, 1))),  # a root name and a map key first seen
    7: (2, _put(("zz", "v"))),
    9: (BIG, _type("big ")),  # an id past i32: the hash table's first entry
    10: (BIG, _type("ids ", 2)),
    # one key comes and one goes (the twin's collision deletes KEY's entry):
    # the dict is as long as it was, and holds something else
    12: (1, _put(("q1", 2), (KEY_TWIN, 3))),
    # step 13 follows `OUTSIDE`'s interning, between two steps
    15: (BIG_OTHER, _type("other ")),  # comes as BIG's entry goes: see ROOM2
    16: (BIG, _type("host lane now ")),
    20: (2, _cut(1, 3)),
    24: (1, _put((KEY, 5))),  # a collided key: host lane, nothing registered
}
ROOM2 = {15: (BIG_TWIN, _type("twin"))}
N_STEPS = 32
# which tables a step has to build; every other table of every step is reused
BUILDS = {
    0: set(TABLES),
    3: {"client_table", "client_rank"},
    6: {"key_table"},
    7: {"key_table"},
    9: {"client_rank", "client_hash_table"},  # an id past i32 leaves the raw table alone
    12: {"key_table"},
    13: {"client_table", "client_rank"},
    15: {"client_rank", "client_hash_table"},
}


@pytest.fixture(scope="module")
def served():
    """The sequence served once; what every step handed its programs."""
    monkeypatch = pytest.MonkeyPatch()
    spy = _Spy(monkeypatch)
    rooms = [_Room(), _Room(), _Room()]
    ing = spy.ing = BatchIngestor(n_docs=3, capacity=256)
    steps = []
    try:
        for step in range(N_STEPS):
            if step == 13:
                for client in OUTSIDE:
                    ing.enc.interner.intern(client)
            client, fn = ROOM0.get(step, (1, _type(f"w{step} ")))
            payloads = [
                rooms[0].edit(client, fn),
                rooms[1].edit(50, _type(f"x{step}")),
                rooms[2].edit(*ROOM2[step]) if step in ROOM2 else None,
            ]
            calls, before = len(spy.handed), _counts()
            lens = len(ing._key_hashes), len(ing._client_hashes)
            ing.apply_bytes(payloads)
            steps.append(
                dict(
                    handed=spy.since(calls),
                    counted=_counted(before),
                    lens=(lens, (len(ing._key_hashes), len(ing._client_hashes))),
                )
            )
    finally:
        monkeypatch.undo()
    return ing, rooms, spy, steps


@pytest.mark.parametrize("table", TABLES)
def test_every_step_hands_its_programs_the_parents_table(served, table):
    _, _, spy, steps = served
    assert len(steps) >= 30 and all(set(s["handed"]) == set(TABLES) for s in steps)
    assert [w for w in spy.wrong() if w[1] == table] == []
    # and the table did change under the sequence: the comparison saw
    # several different ones
    seen = {b"|".join(a.tobytes() for a in _host(w[table])) for w in spy.wanted if table in w}
    assert len(seen) >= 3, table


def test_a_reuse_hands_over_the_arrays_of_the_last_build(served):
    _, _, _, steps = served
    for step, (prev, now) in enumerate(zip(steps, steps[1:]), start=1):
        rebuilt = {t for t in TABLES if now["handed"][t] is not prev["handed"][t]}
        assert rebuilt == BUILDS.get(step, set()), step


def test_the_counters_count_what_happened(served):
    _, _, _, steps = served
    for step, s in enumerate(steps):
        builds = len(BUILDS.get(step, ()))
        assert s["counted"] == {
            "ingest.table_builds": builds,
            "ingest.table_reuses": len(TABLES) - builds,
        }, step


def test_a_length_would_not_have_seen_the_collisions(served):
    """Steps 12 and 15 leave each hash dict as long as they found it."""
    ing, _, _, steps = served
    (keys_before, _), (keys_after, _) = steps[12]["lens"]
    assert keys_before == keys_after
    (_, clients_before), (_, clients_after) = steps[15]["lens"]
    assert clients_before == clients_after == 1
    assert {KEY, KEY_TWIN} <= ing._key_collisions
    assert {BIG, BIG_TWIN} <= ing._client_id_collisions
    # both lanes ran: the four updates that met a collision took the host lane
    assert (ing.slow_docs, ing.fast_docs) == (4, 2 * N_STEPS + 1 - 4)


def test_the_served_rooms_equal_the_oracle(served):
    import jax.numpy as jnp

    from ytpu.models.batch_doc import encode_diff_batch, finish_encode_diff_batch, get_tree

    ing, rooms, _, _ = served
    assert not np.asarray(ing.state.error).any()
    assert ing.fast_recoveries == 0
    want = [room.oracle() for room in rooms]
    for d, doc in enumerate(want):
        assert get_string(ing.state, d, ing.payloads) == doc.get_text("text").get_string(), d
        assert dict(ing.svs[d].clocks) == dict(doc.state_vector().clocks), d
    tree = get_tree(ing.state, 0, ing.payloads, ing.enc.keys, interner=ing.enc.interner)
    assert tree["roots"]["m"]["map"] == want[0].get_map("m").to_json()
    assert want[0].get_map("m").to_json() == {KEY: 5, "zz": "v", "q1": 2, KEY_TWIN: 3}
    # the full diff, off the device: a fresh replica ends where the oracle is
    C = max(8, len(ing.enc.interner))
    ship, offsets, _sv, deleted = encode_diff_batch(
        ing.state, jnp.zeros((3, C), dtype=jnp.int32), C
    )
    diffs = finish_encode_diff_batch(
        ing.state, [0, 1, 2], ship, offsets, deleted, ing.enc,
        payloads=ing.payloads, root_name="text",
    )
    for diff, doc in zip(diffs, want):
        fresh = Doc(client_id=77)
        fresh.apply_update_v1(diff)
        assert fresh.get_text("text").get_string() == doc.get_text("text").get_string()
        assert fresh.get_map("m").to_json() == doc.get_map("m").to_json()
        assert dict(fresh.state_vector().clocks) == dict(doc.state_vector().clocks)
        assert fresh.encode_state_as_update_v1() == _replayed(doc).encode_state_as_update_v1()


def _replayed(doc: Doc) -> Doc:
    """`doc`'s state as one update applied to a fresh replica: the form the
    device's diff is compared in (block boundaries as a full sync lays them)."""
    fresh = Doc(client_id=77)
    fresh.apply_update_v1(doc.encode_state_as_update_v1())
    return fresh


def test_recovery_and_apply_read_the_cached_rank_table(monkeypatch):
    """A flagged lane's follow-up step (`_recover_flagged`) and `apply()`
    take the rank table through the same cache: the recovery reuses the one
    its step just built for a first-seen client, `apply()` builds the next
    when its own planning interns another."""
    spy = _Spy(monkeypatch, flag_decode=2)
    room = _Room()
    ing = spy.ing = BatchIngestor(n_docs=1, capacity=256)
    ing.apply_bytes([room.edit(1, _type("one "))])
    before = _counts()
    # client 9 is first seen in the step whose decode the device flags
    ing.apply_bytes([room.edit(9, _type("nine "))])
    assert ing.fast_recoveries == 1
    ranks = [h["client_rank"] for h in spy.handed if "client_rank" in h]
    assert len(ranks) == 3 and ranks[1] is ranks[2] and ranks[0] is not ranks[1]
    # the step: client table + rank built, the two hash tables reused; the
    # recovery: the rank table once more, reused
    assert _counted(before) == {"ingest.table_builds": 2, "ingest.table_reuses": 3}
    ing.apply([room.edit(4, _type("four "))])  # the host path: `_plan_doc` interns 4
    assert len(ing.enc.interner) == 3
    ing.apply([room.edit(4, _type("more "))])
    ranks = [h["client_rank"] for h in spy.handed if "client_rank" in h]
    assert ranks[3] is not ranks[2] and ranks[4] is ranks[3]
    assert spy.wrong() == []
    assert get_string(ing.state, 0, ing.payloads) == room.oracle().get_text("text").get_string()


def test_a_restored_ingestor_builds_at_its_first_step(monkeypatch, tmp_path):
    from ytpu.models.checkpoint import load_ingestor, save_ingestor

    room = _Room()
    ing = BatchIngestor(n_docs=1, capacity=256)
    for client, fn in [(1, _type("saved ")), (BIG, _type("big ")), (1, _put((KEY, 1)))]:
        ing.apply_bytes([room.edit(client, fn)])
    path = str(tmp_path / "ckpt")
    save_ingestor(path, ing)
    restored = load_ingestor(path)
    assert restored._table_cache == {}
    nxt = room.edit(1, _type("on "))
    spy = _Spy(monkeypatch)
    handed = []
    for which in (restored, ing):
        spy.ing = which
        calls, before = len(spy.handed), _counts()
        which.apply_bytes([nxt])
        builds = len(TABLES) if which is restored else 0
        assert _counted(before) == {
            "ingest.table_builds": builds, "ingest.table_reuses": len(TABLES) - builds,
        }
        handed.append(spy.since(calls))
    assert spy.wrong() == []
    # what the restored one built is what the one that never stopped holds
    for t in TABLES:
        assert _bit_equal(handed[0][t], handed[1][t]), t
    want = room.oracle().get_text("text").get_string()
    assert get_string(restored.state, 0, restored.payloads) == want
    assert get_string(ing.state, 0, ing.payloads) == want
