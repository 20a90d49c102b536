"""Two-tier conflict scan (ISSUE-12) on the served integrate step:
adversarial deep-conflict streams — N concurrent clients inserting at ONE
origin, with interleaved deletes and live moves — must integrate through
`apply_update_batch` (the program `BatchIngestor.apply_bytes` runs) at
parity with the serial host oracle, with the vectorized WIDE tier
demonstrably firing (tier counters > 0) and the dispatch-trip accounting
coherent: the two-tier dispatch never pays more serial `while_loop` trips
than the one-candidate-per-trip loop it replaces, and the scan-WIDTH record
keeps its meaning (width still counts visited candidates, so the histogram
is tier-plan-invariant).

`apply_update_batch` drops the scan record inside its jit; the same traced
row body returns it through `apply_update_stream_raw` (state and a
`[D, SCAN_REC_WORDS]` record), so every test integrates its stream twice:
step by step through the served function for the state, once through the
stream body for the record, and holds the two states to the same reads.

One shape family (n_docs=2, capacity=256, 4 rows and 4 delete ranges an
update). The tier-knob test compiles ONE extra plan variant of each program
(that is the knob's documented retrace contract).
"""

from functools import lru_cache

import numpy as np

from _traces import build_conflict_stream, build_move_storm
from ytpu.core import Update
from ytpu.models.batch_doc import (
    SCAN_REC_CHEAP,
    SCAN_REC_CHEAP_TRIPS,
    SCAN_REC_MAX,
    SCAN_REC_WIDE,
    SCAN_REC_WIDE_TRIPS,
    SCAN_REC_WIDTH_SUM,
    SCAN_TIER_CHEAP_DEFAULT,
    SCAN_WIDTH_BUCKETS,
    BatchEncoder,
    apply_update_batch,
    apply_update_stream_raw,
    get_string,
    get_values,
    init_state,
    scan_tier_plan,
)
from ytpu.models.ingest import BatchIngestor
from ytpu.utils import metrics

N_DOCS, CAPACITY = 2, 256
ROWS, DELS = 4, 4


def _served_steps(payloads, root_name="text", capacity=CAPACITY):
    """Every update to every room, a call of `apply_update_batch` each."""
    enc = BatchEncoder(root_name=root_name)
    state = init_state(N_DOCS, capacity)
    for p in payloads:
        batch = enc.build_batch([Update.decode_v1(p)] * N_DOCS, ROWS, DELS)
        state = apply_update_batch(state, batch, enc.interner.rank_table())
    assert int(np.asarray(state.error).max()) == 0
    return state, enc


def _scan_record(payloads, root_name="text", capacity=CAPACITY):
    """The same stream through the stream body, for the record it returns:
    (state, encoder, room 0's words as a dict)."""
    enc = BatchEncoder(root_name=root_name)
    steps = [enc.build_step(Update.decode_v1(p), ROWS, DELS) for p in payloads]
    state, rec = apply_update_stream_raw(
        init_state(N_DOCS, capacity), BatchEncoder.stack_steps(steps),
        enc.interner.rank_table(), scan_tier_plan(),
    )
    assert int(np.asarray(state.error).max()) == 0
    rec = np.asarray(rec)
    assert (rec[0] == rec[1]).all()  # both rooms took the same stream
    words = {
        "hist": rec[0, :SCAN_WIDTH_BUCKETS].tolist(),
        "max": int(rec[0, SCAN_REC_MAX]),
        "cheap": int(rec[0, SCAN_REC_CHEAP]),
        "wide": int(rec[0, SCAN_REC_WIDE]),
        "trips_two_tier": int(rec[0, SCAN_REC_CHEAP_TRIPS] + rec[0, SCAN_REC_WIDE_TRIPS]),
        "trips_serial": int(rec[0, SCAN_REC_WIDTH_SUM]),
    }
    return state, enc, words


@lru_cache(maxsize=1)
def _deep():
    """The file's main adversarial stream: 10 clients × 12 same-origin
    inserts (~120 concurrent siblings — widths ramp well past the
    default cheap bound of 32) + interleaved deletes."""
    return build_conflict_stream(10, 12, erase_every=5, erase_len=11)


def test_deep_conflicts_wide_tier_fires_at_oracle_parity():
    """On an adversarial same-origin storm the served step stays exact
    against the serial host oracle AND the wide tier demonstrably fires —
    tier counters > 0, every scan lands in exactly one tier, and the
    two-tier dispatch pays strictly fewer serial while trips than the
    single-tier loop would have."""
    payloads, expect = _deep()
    state, enc = _served_steps(payloads)
    for d in range(N_DOCS):
        assert get_string(state, d, enc.payloads) == expect
    by_stream, enc_s, rec = _scan_record(payloads)
    assert get_string(by_stream, 0, enc_s.payloads) == expect
    cheap_bound, _ = scan_tier_plan()
    assert cheap_bound == SCAN_TIER_CHEAP_DEFAULT  # suite runs defaults
    assert rec["wide"] > 0, rec
    assert rec["cheap"] > 0, rec  # the shallow mass stays cheap
    assert rec["max"] > cheap_bound, rec
    assert rec["cheap"] + rec["wide"] == sum(rec["hist"]), rec
    assert 0 < rec["trips_two_tier"] < rec["trips_serial"], rec


def test_width_record_is_tier_plan_invariant(monkeypatch):
    """`scan_width_*` must keep its meaning: the SAME stream with the tier
    knob degenerated to the pre-ISSUE-12 loop (cheap=0, unroll=1 — every
    candidate is one while trip) yields an IDENTICAL width histogram/max,
    identical serial-trip accounting, and the degenerate plan pays exactly
    the serial trip count. Also pins the knob's documented env path:
    `apply_update_batch` re-reads it per call, so a changed value takes
    effect (via retrace) without a process restart."""
    payloads, expect = _deep()
    _, _, a = _scan_record(payloads)
    monkeypatch.setenv("YTPU_SCAN_TIER_CHEAP", "0")
    monkeypatch.setenv("YTPU_SCAN_WIDE_UNROLL", "1")
    assert scan_tier_plan() == (0, 1)
    state, enc = _served_steps(payloads)
    assert get_string(state, 0, enc.payloads) == expect
    _, _, b = _scan_record(payloads)
    assert b["hist"] == a["hist"], (a, b)
    assert b["max"] == a["max"]
    assert b["trips_serial"] == a["trips_serial"]
    # degenerate plan = the old dispatch: one candidate per while trip
    assert b["trips_two_tier"] == b["trips_serial"], b
    assert b["cheap"] + b["wide"] == sum(b["hist"])
    # the real plan strictly compresses the same workload
    assert a["trips_two_tier"] < a["trips_serial"]


def test_compaction_midstream_keeps_parity():
    """A tight-capacity storm (raw rows > capacity; a slot cannot grow) is
    carried by the served compaction (`compact_rooms`, from
    `BatchIngestor._make_room`) firing in the middle of it while the wide
    tier is firing."""
    payloads, expect = build_conflict_stream(
        8, 6, erase_every=1, rounds=6, typed=True, erase_len=5
    )
    by_stream, enc_s, rec = _scan_record(payloads, capacity=4 * CAPACITY)
    raw_rows = int(np.asarray(by_stream.n_blocks)[0])
    assert raw_rows > CAPACITY, "workload must not fit without compaction"
    assert get_string(by_stream, 0, enc_s.payloads) == expect
    assert rec["wide"] > 0, rec
    assert rec["cheap"] + rec["wide"] == sum(rec["hist"]), rec

    compactions = metrics.counter("ingest.room_compactions")
    before = compactions.value
    ing = BatchIngestor(N_DOCS, CAPACITY)
    for p in payloads:
        ing.apply_bytes([p] * N_DOCS)
    assert compactions.value - before >= N_DOCS
    assert int(np.asarray(ing.state.error).max()) == 0
    assert int(np.asarray(ing.state.n_blocks).max()) <= CAPACITY
    for d in range(N_DOCS):
        assert get_string(ing.state, d, ing.payloads) == expect


def test_live_moves_with_deep_conflicts_parity():
    """Concurrent same-origin ARRAY inserts + live `move_range_to`
    ranges + deletes: the scan walks move rows and tombstones in the
    conflict neighborhood, and move claims are recomputed a step —
    parity vs the host oracle with the wide tier firing."""
    payloads, expect = build_move_storm()

    state, enc = _served_steps(payloads, root_name="a")
    for d in range(N_DOCS):
        assert get_values(state, d, enc.payloads) == expect
    by_stream, enc_s, rec = _scan_record(payloads, root_name="a")
    assert get_values(by_stream, 0, enc_s.payloads) == expect
    assert rec["wide"] > 0, rec
    assert rec["trips_two_tier"] < rec["trips_serial"], rec
