"""Pipelined encode/diff (ISSUE-10): staged selection → D2H → batched
native finisher.

Covers: pipelined-vs-serial byte parity (including Python-fallback rows
mixed into a sub-batch — a wire-ref Embed/Format doc the native core
punts on), the zero-extra-device-syncs contract (counted host
materializations + exact D2H byte accounting), the stall/overlap gauge
contract, the pow2 recompile bound on the packed widths, the rows-based
finisher threading heuristic, the `diff.d2h_fail`/`finisher.raise`
degradation classes, and the overlap engine's own `stage.raise` site
reached through its one user.

Suite-cost hygiene: ONE compiled shape family for the whole file — the
(n_docs=4, capacity=256) ingest family test_device_server.py already
compiles — built once at module scope; the DiffPipeline's own pack
program compiles one (sub=2, R) instance reused by every test.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from ytpu.utils import metrics
from ytpu.utils.faults import faults

needs_native = pytest.mark.usefixtures("native_lib")

N_DOCS, CAPACITY = 4, 256  # the suite-wide device-server shape family
SUB, DEPTH = 2, 2

_FAM: dict = {}


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


def _family() -> dict:
    """Docs 0/1/3 are plain/emoji/deleted text (native-scope rows); doc 2
    carries wire-ref Embed + Format rows through the ingest fast lane —
    outside the native finisher's scope, so every batch call peels it
    per doc in Python (the mixed-sub-batch fallback case)."""
    if _FAM:
        return _FAM
    from ytpu.core import Doc
    from ytpu.models import batch_doc as bd
    from ytpu.models.ingest import BatchIngestor

    docs, logs = [], []
    for i in range(N_DOCS):
        d = Doc(client_id=i + 1)
        log = []
        d.observe_update_v1(lambda p, o, t, log=log: log.append(p))
        t = d.get_text("text")
        with d.transact() as txn:
            t.insert(txn, 0, f"doc{i} body")
        if i == 2:
            with d.transact() as txn:
                t.insert_embed(txn, 2, {"img": "x.png"})
            with d.transact() as txn:
                t.insert_with_attributes(txn, 0, "b", {"bold": True})
        else:
            with d.transact() as txn:
                t.insert(txn, 3, "✓🙂" if i else "tail")
        if i == 1:
            with d.transact() as txn:
                t.remove_range(txn, 1, 3)
        docs.append(d)
        logs.append(log)
    ing = BatchIngestor(N_DOCS, CAPACITY)
    for step in range(max(len(lg) for lg in logs)):
        ing.apply_bytes([lg[step] if step < len(lg) else None for lg in logs])
    assert int(np.asarray(ing.state.error).max()) == 0
    assert ing.fast_docs > 0  # doc 2's rows really are wire refs
    n_clients = max(8, len(ing.enc.interner))
    remote = np.zeros((N_DOCS, n_clients), dtype=np.int32)
    ship, offsets, _sv, deleted = bd.encode_diff_batch(
        ing.state, jnp.asarray(remote), n_clients
    )
    serial = bd.finish_encode_diff_batch(
        ing.state,
        list(range(N_DOCS)),
        ship,
        offsets,
        deleted,
        ing.enc,
        payloads=ing.payloads,
    )
    _FAM.update(
        ing=ing,
        docs=docs,
        ship=ship,
        offsets=offsets,
        deleted=deleted,
        serial=serial,
        fallback_statuses=list(bd.LAST_FINISH_STATUSES),
    )
    return _FAM


def _run_pipe(sel, sub_batch=SUB, depth=DEPTH):
    from ytpu.models.batch_doc import DiffPipeline

    fam = _family()
    pipe = DiffPipeline(sub_batch=sub_batch, depth=depth)
    out = pipe.run(
        fam["ing"].state,
        sel,
        fam["ship"],
        fam["offsets"],
        fam["deleted"],
        fam["ing"].enc,
        payloads=fam["ing"].payloads,
    )
    return pipe, out


@needs_native
def test_pipelined_matches_serial_with_fallback_rows_in_sub_batch():
    """Byte parity over the full selection, with doc 2's wire-ref
    Embed/Format rows forcing a per-doc Python peel INSIDE the second
    sub-batch while its neighbor stays native."""
    fam = _family()
    # the family's serial call really exercised the mixed case
    assert fam["fallback_statuses"] == [0, 0, 1, 0]
    pipe, out = _run_pipe(list(range(N_DOCS)))
    assert out == fam["serial"]
    assert pipe.stats.n_sub == 2 and pipe.stats.sub == SUB
    assert pipe.stats.fallback_docs == 1
    assert pipe.stats.demotions == 0
    # every payload replays into a correct replica
    from ytpu.core import Doc

    for i, payload in enumerate(out):
        r = Doc(client_id=99)
        r.apply_update_v1(payload)
        assert r.get_text("text").diff() == fam["docs"][i].get_text(
            "text"
        ).diff(), f"doc {i}"


@needs_native
def test_zero_extra_device_syncs_and_exact_d2h_accounting():
    """The pipeline performs exactly n_sub + 1 blocking host
    materializations (ONE counts pull + one drain per sub-batch) and the
    drained bytes are exactly n_sub * sub * 15 * R * 4 — any per-doc
    readout would break both counts.  Selection avoids the fallback doc
    (its Python peel legitimately pulls the full arrays)."""
    pipe, out = _run_pipe([0, 1, 3, 0])  # repeats are legal; no doc 2
    st = pipe.stats
    assert st.fallback_docs == 0
    assert st.n_sub == 2
    assert st.syncs == st.n_sub + 1, st
    assert st.d2h_bytes == st.n_sub * st.sub * 15 * st.R * 4, st
    fam = _family()
    assert out == [fam["serial"][0], fam["serial"][1], fam["serial"][3],
                   fam["serial"][0]]


@needs_native
def test_stall_overlap_gauge_contract():
    """With phases enabled, a multi-sub-batch run lands the documented
    encode gauges: select/finish/d2h_bytes plus the engine's
    stage/drain/stall/overlap_ratio/inflight_depth."""
    from ytpu.utils.phases import phases

    was_enabled = phases.enabled
    phases.reset()
    phases.enable()
    try:
        pipe, _ = _run_pipe(list(range(N_DOCS)))
        snap = phases.snapshot()
    finally:
        if not was_enabled:
            phases.disable()
    for key in (
        "encode.select",
        "encode.finish",
        "encode.d2h_bytes",
        "encode.stage",
        "encode.drain",
        "encode.stall",
        "encode.overlap_ratio",
        "encode.inflight_depth",
    ):
        assert key in snap, (key, sorted(snap))
    assert 0.0 <= snap["encode.overlap_ratio"]["value"] <= 1.0
    assert snap["encode.d2h_bytes"]["value"] == pipe.stats.d2h_bytes > 0
    assert snap["encode.d2h"]["d2h_bytes"] == pipe.stats.d2h_bytes
    assert snap["encode.select"]["calls"] == pipe.stats.n_sub
    assert 0.0 <= pipe.stats.overlap_ratio <= 1.0
    # the single-sub-batch (serving) path emits the stage gauges too,
    # just without an overlap ratio to report
    phases.reset()
    phases.enable()
    try:
        _run_pipe([1], sub_batch=64)
        snap1 = phases.snapshot()
    finally:
        if not was_enabled:
            phases.disable()
    assert "encode.select" in snap1 and "encode.finish" in snap1


@needs_native
def test_packed_width_recompile_bound():
    """Distinct selection lengths inside one pow2 bucket must share ONE
    compiled counts/pack family — the recompile-bounding contract of the
    pow2-rounded doc width and finisher row width (ISSUE-10 small fix:
    `growths` stays bounded)."""
    from ytpu.models import batch_doc as bd

    fam = _family()

    def caches():
        return (
            bd.compact_finisher_rows._cache_size(),
            bd._finish_counts._cache_size(),
        )

    # warm the (8, R) full-batch family once
    bd.finish_encode_diff_batch(
        fam["ing"].state, [0, 1, 3], fam["ship"], fam["offsets"],
        fam["deleted"], fam["ing"].enc, payloads=fam["ing"].payloads,
    )
    before = caches()
    for sel in ([0, 1, 3], [3, 1, 0, 2], [1, 0, 3, 2, 0], [0] * 7):
        got = bd.finish_encode_diff_batch(
            fam["ing"].state, sel, fam["ship"], fam["offsets"],
            fam["deleted"], fam["ing"].enc, payloads=fam["ing"].payloads,
        )
        assert got == [fam["serial"][d] for d in sel]
    after = caches()
    assert after == before, (
        f"selection-length retraces crept in: {before} -> {after}"
    )


def test_sub_batch_plan_is_pow2_and_reuses_one_slot():
    from ytpu.models.batch_doc import plan_diff_pipeline

    for n, sub_batch in ((12, 4), (10240, 512), (3, 512), (1, 512)):
        plan = plan_diff_pipeline(n, sub_batch=sub_batch)
        assert plan.sub & (plan.sub - 1) == 0, plan
        assert plan.n_sub == -(-n // plan.sub)
        assert plan.idx_buffers == 1
        assert plan.buffer_reuses == max(0, plan.n_sub - 1)
        assert plan.donate_idx
    empty = plan_diff_pipeline(0)
    assert empty.n_sub == 0 and empty.buffer_reuses == 0


def test_finisher_thread_heuristic_keys_on_rows_not_docs():
    """ISSUE-10 small fix: the native finisher threading decision is a
    threshold on TOTAL selected rows.  A few huge docs reach the pool
    (the old `len(docs) >= 128` rule left them single-threaded); many
    near-empty docs no longer pay pool spawn overhead."""
    from ytpu.models.batch_doc import (
        FINISHER_MT_MIN_ROWS,
        _finisher_threads,
    )

    # one huge doc: rows alone cross the threshold → pool (0)
    assert _finisher_threads(FINISHER_MT_MIN_ROWS) == 0
    assert _finisher_threads(FINISHER_MT_MIN_ROWS * 10) == 0
    # 200 docs × 2 rows (the old rule's pool case) stays single-threaded
    assert _finisher_threads(400) == 1
    assert _finisher_threads(0) == 1
    assert _finisher_threads(FINISHER_MT_MIN_ROWS - 1) == 1


@needs_native
@pytest.mark.parametrize("site", ["diff.d2h_fail", "finisher.raise"])
def test_fault_degrades_sub_batch_to_serial_path_with_parity(site):
    """A failing sub-batch demotes to the serial per-doc finisher
    (counted via `encode.demotions`) instead of dropping the diff."""
    fam = _family()
    spec = faults.arm(site)
    base = metrics.counter("encode.demotions").value
    pipe, out = _run_pipe(list(range(N_DOCS)))
    assert spec.fired == 1
    assert out == fam["serial"], f"{site}: degraded sub-batch lost parity"
    assert pipe.stats.demotions >= 1
    assert metrics.counter("encode.demotions").value - base >= 1


@needs_native
@pytest.mark.parametrize("prefix,fires", [("encode", True), ("replay", False)])
def test_a_staging_fault_surfaces_and_the_pipeline_runs_again(prefix, fires):
    """`stage.raise` lives in `OverlapPipeline`'s staging worker, and
    `DiffPipeline` is the engine's one user (`stage_prefix="encode"`): an
    armed fault re-raises on the caller at once, with no sub-batch left
    waiting on a queue, and the next run of the same pipeline ships the
    serial finisher's bytes. A spec armed for another prefix is no
    eligible pass there."""
    import time

    from ytpu.utils.faults import FaultError

    fam = _family()
    spec = faults.arm("stage.raise", prefix=prefix)
    sel = list(range(N_DOCS))
    t0 = time.perf_counter()
    if fires:
        with pytest.raises(FaultError, match="stage.raise"):
            _run_pipe(sel)
        assert time.perf_counter() - t0 < 30.0, "the caller was stranded"
    assert spec.fired == int(fires)
    pipe, out = _run_pipe(sel)  # the spec is spent, or never matched
    assert out == fam["serial"]
    assert pipe.stats.n_sub > 1 and pipe.stats.demotions == 0


@needs_native
def test_empty_and_out_of_range_selections():
    from ytpu.models.batch_doc import DiffPipeline

    fam = _family()
    pipe = DiffPipeline(sub_batch=SUB, depth=DEPTH)
    assert (
        pipe.run(
            fam["ing"].state, [], fam["ship"], fam["offsets"],
            fam["deleted"], fam["ing"].enc, payloads=fam["ing"].payloads,
        )
        == []
    )
    with pytest.raises(IndexError, match="doc selection out of range"):
        pipe.run(
            fam["ing"].state, [N_DOCS], fam["ship"], fam["offsets"],
            fam["deleted"], fam["ing"].enc, payloads=fam["ing"].payloads,
        )
