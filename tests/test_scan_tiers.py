"""Two-tier conflict scan (ISSUE-12 tentpole): adversarial deep-conflict
streams — N concurrent clients inserting at ONE origin, with interleaved
deletes and live moves — must integrate at byte parity with the serial
host oracle on the packed-XLA lane (and fused-interpret, where this jax
can run it), with the vectorized WIDE tier demonstrably firing (tier
counters > 0) and the dispatch-trip accounting coherent: the two-tier
dispatch never pays more serial `while_loop` trips than the
one-candidate-per-trip loop it replaces, and the scan-WIDTH record keeps
its pre-ISSUE-12 meaning (width still counts visited candidates, so the
histogram is tier-plan-invariant).

Every replay reuses the suite-wide (n_docs=2, capacity=256, chunk=16)
shape family — the compiled decode/chunk-step/compaction programs are
shared with test_async_overlap/test_chaos_recovery (distinct big
programs are the suite's scarce resource, conftest.py LLVM-arena note).
The tier-knob test necessarily compiles ONE extra plan variant (that is
the knob's documented retrace contract). The fused interpret test routes
through `tests/_fused_interpret.run_or_skip` and runs LAST.
"""

from functools import lru_cache

import numpy as np
import pytest

from ytpu.core import Doc, Update
from ytpu.models.batch_doc import (
    SCAN_TIER_CHEAP_DEFAULT,
    BatchEncoder,
    get_string,
    get_values,
    init_state,
    scan_tier_plan,
)
from ytpu.ops import integrate_kernel as ik
from ytpu.ops.integrate_kernel import replay_stream_fused
from ytpu.utils.faults import faults

from _fused_interpret import run_or_skip

# the ONE adversarial-stream generator, shared with the bench so the
# acceptance stream (benches/scan_tiers.py dry-run leg) and this file's
# parity streams can never drift apart (conftest puts the repo root on
# sys.path; benches/ is a namespace package)
from benches.scan_tiers import build_conflict_stream

needs_native = pytest.mark.usefixtures("native_lib")

# the one shape family of this file (shared suite-wide)
N_DOCS, CAPACITY, CHUNK, D_BLOCK = 2, 256, 16, 2


@pytest.fixture(autouse=True)
def _clean_slate():
    """Armed faults and sticky lane demotions are process-global."""
    faults.clear()
    ik.reset_lane_health()
    yield
    faults.clear()
    ik.reset_lane_health()


def _capture(doc):
    log = []
    doc.observe_update_v1(lambda p, o, t: log.append(p))
    return log


def _stack(payloads, root_name="text"):
    enc = BatchEncoder(root_name=root_name)
    steps = [enc.build_step(Update.decode_v1(p), 4, 4) for p in payloads]
    return BatchEncoder.stack_steps(steps), enc


def _replay(stream, rank, lane="xla", interpret=False,
            max_capacity=4 * CAPACITY, policy=None):
    return replay_stream_fused(
        init_state(N_DOCS, CAPACITY),
        stream,
        rank,
        chunk_steps=CHUNK,
        d_block=D_BLOCK,
        lane=lane,
        interpret=interpret,
        max_capacity=max_capacity,
        policy=policy,
    )


@lru_cache(maxsize=1)
def _deep():
    """The file's main adversarial stream: 10 clients × 12 same-origin
    inserts (~120 concurrent siblings — widths ramp well past the
    default cheap bound of 32) + interleaved deletes."""
    payloads, expect = build_conflict_stream(
        10, 12, erase_every=5, erase_len=11
    )
    stream, enc = _stack(payloads)
    return payloads, expect, stream, enc


def test_deep_conflicts_wide_tier_fires_at_oracle_parity():
    """Tentpole acceptance: on an adversarial same-origin storm the
    packed-XLA lane stays byte-exact vs the serial host oracle AND the
    wide tier demonstrably fires — tier counters > 0, every scan lands
    in exactly one tier, and the two-tier dispatch pays strictly fewer
    serial while trips than the single-tier loop would have."""
    _, expect, stream, enc = _deep()
    st, stats = _replay(stream, enc.interner.rank_table())
    assert int(np.asarray(st.error).max()) == 0
    for d in range(N_DOCS):
        assert get_string(st, d, enc.payloads) == expect
    cheap_bound, _ = scan_tier_plan()
    assert cheap_bound == SCAN_TIER_CHEAP_DEFAULT  # suite runs defaults
    assert stats.scan_tier_wide > 0, stats
    assert stats.scan_tier_cheap > 0, stats  # the shallow mass stays cheap
    assert stats.scan_max > cheap_bound, stats
    assert stats.scan_tier_cheap + stats.scan_tier_wide == sum(
        stats.scan_hist
    ), stats
    assert (
        0 < stats.scan_trips_two_tier < stats.scan_trips_serial
    ), stats


def test_width_record_is_tier_plan_invariant(monkeypatch):
    """`scan_width_*` must keep its meaning (acceptance): replaying the
    SAME stream with the tier knob degenerated to the pre-ISSUE-12 loop
    (cheap=0, unroll=1 — every candidate is one while trip) yields an
    IDENTICAL width histogram/max, identical serial-trip accounting, and
    the degenerate plan pays exactly the serial trip count. Also pins
    the knob's documented env path: the driver re-reads it per chunk, so
    a changed value takes effect (via retrace) without a process
    restart."""
    _, expect, stream, enc = _deep()
    st_a, a = _replay(stream, enc.interner.rank_table())
    monkeypatch.setenv("YTPU_SCAN_TIER_CHEAP", "0")
    monkeypatch.setenv("YTPU_SCAN_WIDE_UNROLL", "1")
    assert scan_tier_plan() == (0, 1)
    st_b, b = _replay(stream, enc.interner.rank_table())
    assert get_string(st_b, 0, enc.payloads) == expect
    assert b.scan_hist == a.scan_hist, (a, b)
    assert b.scan_max == a.scan_max
    assert (b.scan_p50, b.scan_p99) == (a.scan_p50, a.scan_p99)
    assert b.scan_trips_serial == a.scan_trips_serial
    # degenerate plan = the old dispatch: one candidate per while trip
    assert b.scan_trips_two_tier == b.scan_trips_serial, b
    # the real plan strictly compresses the same workload
    assert a.scan_trips_two_tier < a.scan_trips_serial


def test_compaction_midstream_keeps_parity_and_tier_counts():
    """A tight-capacity storm (raw rows > capacity, growth disabled)
    must be carried by BETWEEN-CHUNK compaction while the wide tier is
    firing — the tier/trip meta words ride the packed meta through
    `compact_packed` untouched, so the record survives compaction."""
    payloads, expect = build_conflict_stream(
        8, 6, erase_every=1, rounds=6, typed=True, erase_len=5
    )
    stream, enc = _stack(payloads)
    raw_rows = int(np.asarray(stream.valid).sum())
    assert raw_rows > CAPACITY, "workload must not fit without compaction"
    st, stats = _replay(
        stream, enc.interner.rank_table(), max_capacity=CAPACITY
    )
    assert stats.compactions >= 1, stats
    assert stats.growths == 0, stats
    assert int(np.asarray(st.error).max()) == 0
    for d in range(N_DOCS):
        assert get_string(st, d, enc.payloads) == expect
    assert stats.scan_tier_wide > 0, stats
    assert stats.scan_tier_cheap + stats.scan_tier_wide == sum(
        stats.scan_hist
    ), stats


def test_live_moves_with_deep_conflicts_parity():
    """Concurrent same-origin ARRAY inserts + live `move_range_to`
    ranges + deletes: the scan walks move rows and tombstones in the
    conflict neighborhood, and move-claim recomputes run between chunks
    — parity vs the host oracle with the wide tier firing."""
    base = Doc(client_id=1)
    base_log = _capture(base)
    arr = base.get_array("a")
    with base.transact() as txn:
        for v in range(12):
            arr.push_back(txn, v)
    base_update = base.encode_state_as_update_v1()

    per_client = []
    for k in range(8):
        doc = Doc(client_id=10 + k)
        doc.apply_update_v1(base_update)
        log = _capture(doc)
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

    stream, enc = _stack(payloads, root_name="a")
    st, stats = _replay(stream, enc.interner.rank_table())
    assert int(np.asarray(st.error).max()) == 0
    assert get_values(st, 0, enc.payloads) == expect
    assert get_values(st, 1, enc.payloads) == expect
    assert stats.scan_tier_wide > 0, stats
    assert stats.scan_trips_two_tier < stats.scan_trips_serial, stats


@needs_native
def test_demotion_ladder_carries_deep_conflicts_to_host_oracle():
    """PR-6 ladder under the reworked scan: an injected packed-XLA
    dispatch failure on the deep-conflict stream demotes past the
    driver's rungs to the serial host oracle, which completes the storm
    at byte parity (the ladder is scan-implementation-agnostic)."""
    from ytpu.models.replay import FusedReplay, plan_replay

    payloads, expect, _, _ = _deep()
    faults.arm("dispatch.fail", lane="xla")
    r = FusedReplay(
        n_docs=N_DOCS,
        plan=plan_replay(payloads),
        capacity=CAPACITY,
        max_capacity=4 * CAPACITY,
        d_block=D_BLOCK,
        chunk=CHUNK,
        lane="xla",
    )
    r.run(payloads)
    assert r.stats.final_lane == "host"
    assert r.get_string(0) == expect
    assert r.get_string(1) == expect


def test_fused_interpret_matches_xla_on_deep_conflicts():
    """Both lanes share the tier-plan statics and the meta record: where
    this jax build can interpret the Pallas kernel, the fused lane must
    byte-match the packed-XLA lane on the storm AND produce the same
    tier/trip words (the record is lane-agnostic by construction)."""
    _, expect, stream, enc = _deep()
    rank = enc.interner.rank_table()
    _, a = _replay(stream, rank)

    def go():
        return _replay(stream, rank, lane="fused", interpret=True)

    st_f, b = run_or_skip(go)
    assert get_string(st_f, 0, enc.payloads) == expect
    assert b.scan_hist == a.scan_hist
    assert b.scan_max == a.scan_max
    assert (b.scan_tier_cheap, b.scan_tier_wide) == (
        a.scan_tier_cheap, a.scan_tier_wide
    )
    assert (b.scan_trips_two_tier, b.scan_trips_serial) == (
        a.scan_trips_two_tier, a.scan_trips_serial
    )
