"""Device benchmarks for the remaining north-star configs (BASELINE.md §2).

Config #3 — YArray, 256-client concurrent insert/delete, randomized
  interleaving, replayed over an N-doc batch (CPU analogue B2.x/B3.4).
Config #4 — mixed YMap + nested YXmlFragment edits over a 4k-tenant batch
  (CPU analogue B3.1-B3.3; map rows force the XLA scan path).
Config #5 — D-doc x C-client state-vector diff + encode_diff_batch device
  selection (sync steps 1/2; CPU analogue store.rs:204-232).

Each config prints one JSON line: device rate, host-oracle rate measured
here, and the ratio. Usage: python benches/device.py [--config 3|4|5|all]
[--docs N].
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys
import time

sys.path.insert(0, ".")
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np

from ytpu.core import Doc, Update


def capture(doc):
    log = []
    doc.observe_update_v1(lambda p, o, t: log.append(p))
    return log


def fused_lane_rate(make_state, stream, rank, n_docs, n_updates, validate):
    """Measure the fused Pallas lane on the same stream (not run on the
    current machine). Runs AFTER the XLA measure — crash order — and only on real devices
    (interpret mode would take hours on CPU; set YTPU_CFG_FUSED=1 to
    force). Returns (updates_per_sec | None, error | None)."""
    import jax

    if (
        jax.devices()[0].platform == "cpu"
        and os.environ.get("YTPU_CFG_FUSED") != "1"
    ):
        return None, "skipped on cpu"
    from ytpu.ops.integrate_kernel import apply_update_stream_fused

    try:
        d_block = int(os.environ.get("YTPU_CFG_FUSED_DBLOCK", "32")) or 32
        while n_docs % d_block:
            d_block //= 2
        interpret = jax.devices()[0].platform == "cpu"

        def run(st):
            return apply_update_stream_fused(
                st, stream, rank, d_block=d_block, interpret=interpret,
                guard=False, refresh_cache=False,
            )

        st = run(make_state())  # compile + warm
        err = int(np.asarray(st.error).max())
        if err != 0:
            return None, f"error flags {err}"
        validate(st)
        st = make_state()
        np.asarray(st.n_blocks)
        t0 = time.perf_counter()
        st = run(st)
        np.asarray(st.n_blocks)
        return n_updates * n_docs / (time.perf_counter() - t0), None
    except Exception as e:  # noqa: BLE001 — a fused fault must not void the XLA capture
        return None, f"{type(e).__name__}: {e}"[:200]


def merge_fused_lane(result, fused_fn):
    """Run a deferred fused-lane measurement and fold it into a config's
    result dict (headline = best VALIDATED lane; both rates reported).
    Call AFTER every config's XLA measure has flushed — a fused Pallas
    fault can kill the TPU worker process, which no try/except catches."""
    fused_rate, fused_err = fused_fn()
    result["fused_updates_per_sec"] = (
        round(fused_rate, 1) if fused_rate else None
    )
    result["fused_error"] = fused_err
    if fused_rate and fused_rate > result["xla_updates_per_sec"]:
        result["value"] = round(fused_rate, 1)
        result["lane"] = "fused"
        native = result.get("native_updates_per_sec")
        if native:
            result["vs_native"] = round(fused_rate / native, 2)
            result["vs_baseline"] = result["vs_native"]
        py = result.get("py_oracle_updates_per_sec")
        if py:
            result["vs_py_oracle"] = round(fused_rate / py, 2)
    return result


def timed_host_replay(log):
    doc = Doc(client_id=0xBEEF)
    t0 = time.perf_counter()
    for p in log:
        doc.apply_update_v1(p)
    return time.perf_counter() - t0, doc


# Native baselines are PINNED once per capture session (VERDICT r5 Weak
# #4: config3's single-shot denominator swung 4.4x between same-day
# captures — the driver, the watcher and the suite time-share 1 vCPU, so
# one replay's timing is mostly scheduler noise). Keyed per config; the
# per-trial rates ride the result JSON under "native_baseline" so the
# pin is auditable from the artifact alone.
_NATIVE_PIN: dict = {}


def timed_native_replay(log, checks, key=None, trials=3):
    """Native single-core denominator (VERDICT r4 #3): replay through the
    C++ engine (ytpu/native/engine.cpp) and validate its visible state
    against the host oracle. `checks` = [(root, shape, expected), ...].
    Returns updates/s (best of `trials` replays — the least-contended
    estimate of the engine's true rate), or None when the native path is
    unavailable or the stream is out of the engine's scope. With `key`,
    the first measurement pins for the rest of the session."""
    if key is not None and key in _NATIVE_PIN:
        return _NATIVE_PIN[key]["rate"]
    try:
        from ytpu.native import NativeEngine

        rates = []
        for t in range(trials):
            eng = NativeEngine()
            t0 = time.perf_counter()
            for p in log:
                eng.apply_update_v1(p)
            dt = time.perf_counter() - t0
            if t == 0:  # validate once; the re-runs only time
                for root, shape, expected in checks:
                    got = eng.root_json(root, shape)
                    assert got == expected, f"native {root} diverged from oracle"
            eng.close()
            if dt > 0:
                rates.append(len(log) / dt)
        rate = max(rates) if rates else None
        if key is not None:
            _NATIVE_PIN[key] = {
                "rate": rate,
                "trials": [round(r, 1) for r in rates],
                "pinned": True,
            }
        return rate
    except Exception:
        return None


def stream_workload_array(n_clients: int, ops_per_client: int, seed=11):
    """Config #3 generator: n_clients peers concurrently edit one array,
    exchanging through a relay doc so every op becomes one wire update."""
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
        upd = peer.encode_state_as_update_v1(relay.state_vector())
        relay.apply_update_v1(upd)
        # relay fans back out so peers stay roughly in sync
        if rng.random() < 0.5:
            back = relay.encode_state_as_update_v1(peer.state_vector())
            peer.apply_update_v1(back)
    return log, relay.get_array("a").to_json()


def stream_workload_map_xml(n_steps: int, seed=13):
    """Config #4 generator: one tenant's YMap + nested XML edit stream."""
    rng = random.Random(seed)
    doc = Doc(client_id=1)
    log = capture(doc)
    m = doc.get_map("m")
    frag = doc.get_xml_fragment("x")
    from ytpu.types import XmlElementPrelim

    for s in range(n_steps):
        with doc.transact() as txn:
            r = rng.random()
            if r < 0.5:
                m.insert(txn, f"k{rng.randrange(32)}", rng.randrange(1000))
            elif r < 0.7 and len(m) > 0:
                key = next(iter(m.keys()))
                m.remove(txn, key)
            else:
                frag.insert(
                    txn,
                    len(frag),
                    XmlElementPrelim(f"div{s % 7}", attributes={"i": str(s)}),
                )
    return log


def bench_config3(n_docs: int):
    from ytpu.models.batch_doc import (
        BatchEncoder,
        apply_update_stream,
        get_values,
        init_state,
    )

    log, expect = stream_workload_array(n_clients=256, ops_per_client=2)
    # scan-width diagnostic (VERDICT r3 weak #10): the device integrate
    # runs the same YATA conflict scan as a while_loop; this distribution
    # bounds its per-row iteration count and explains why 256-concurrent-
    # client traffic costs more per update than sequential text
    import math

    import ytpu.core.store as _store

    # probe a SEPARATE (untimed) replay so the counters never inflate
    # host_dt / vs_baseline
    _store.SCAN_WIDTH_PROBE = widths = []
    try:
        timed_host_replay(log)
    finally:
        _store.SCAN_WIDTH_PROBE = None
    host_dt, host_doc = timed_host_replay(log)
    assert host_doc.get_array("a").to_json() == expect
    widths.sort()
    scan_stats = (
        {
            "p50": widths[len(widths) // 2],
            "p99": widths[max(0, math.ceil(0.99 * len(widths)) - 1)],
            "max": widths[-1],
            "scans": len(widths),
            # the device while_loop's TOTAL trip count over the replay —
            # each trip costs ~8 capacity-wide vector ops, dominated by
            # the case-2 origin find (_find_slot, an O(B) compare per
            # candidate). Cost model: iterations x 8B element-ops; the
            # recorded fix (VERDICT r4 #9) is an `origin_slot` cache
            # column maintained at insert/split so case 2 becomes one
            # gather — cuts per-candidate cost ~4x on wide scans.
            "scan_iterations_total": sum(widths),
        }
        if widths
        else {}
    )

    enc = BatchEncoder(root_name="a")
    steps = [enc.build_step(Update.decode_v1(p), 8, 4) for p in log]
    stream = BatchEncoder.stack_steps(steps)
    rank = enc.interner.rank_table()
    state = init_state(n_docs, 2048)
    state = apply_update_stream(state, stream, rank)  # compile + warm
    assert int(np.asarray(state.error).max()) == 0
    assert get_values(state, 0, enc.payloads) == expect
    state = init_state(n_docs, 2048)
    np.asarray(state.n_blocks)
    t0 = time.perf_counter()
    state = apply_update_stream(state, stream, rank)
    np.asarray(state.n_blocks)
    dt = time.perf_counter() - t0
    rate = len(log) * n_docs / dt
    py_rate = len(log) / host_dt
    native_rate = timed_native_replay(log, [("a", "seq", expect)], key="config3")

    def _validate(st):
        assert get_values(st, 0, enc.payloads) == expect

    # the honest baseline is the native-speed single-core CPU engine
    # (VERDICT r4 missing #2); the Python-oracle ratio stays visible but
    # never headlines
    result = {
        "metric": "config3_array_256client_updates_per_sec",
        "value": round(rate, 1),
        "lane": "xla",
        "unit": f"updates/s over {n_docs}-doc batch (256-client concurrent array)",
        "vs_baseline": round(rate / (native_rate or py_rate), 2),
        "baseline_kind": "native_cpp" if native_rate else "py_oracle_SOFT",
        "vs_native": round(rate / native_rate, 2) if native_rate else None,
        "vs_py_oracle": round(rate / py_rate, 2),
        "native_updates_per_sec": round(native_rate, 1) if native_rate else None,
        "native_baseline": _NATIVE_PIN.get("config3"),
        "py_oracle_updates_per_sec": round(py_rate, 1),
        "xla_updates_per_sec": round(rate, 1),
        "conflict_scan_width": scan_stats,
        # crash-ordered fused lane: callers run this AFTER every config's
        # XLA measure has flushed (merge_fused_lane); json-flush callers
        # must pop it first
        "_fused": lambda: fused_lane_rate(
            lambda: init_state(n_docs, 2048),
            stream, rank, n_docs, len(log), _validate,
        ),
    }
    return result


def bench_config4(n_docs: int):
    from ytpu.models.batch_doc import (
        BatchEncoder,
        apply_update_stream,
        ensure_root_anchor_all,
        get_tree,
        init_state,
    )

    log = stream_workload_map_xml(n_steps=300)
    host_dt, host_doc = timed_host_replay(log)

    enc = BatchEncoder(root_name="m")
    steps = [enc.build_step(Update.decode_v1(p), 6, 4) for p in log]
    stream = BatchEncoder.stack_steps(steps)
    rank = enc.interner.rank_table()

    def seed():
        # this doc is genuinely MULTI-ROOT (map "m" + xml fragment "x",
        # doc.rs:156-228's normal shape): the non-primary root needs its
        # per-doc BLOCK_ROOT_ANCHOR rows before the replay — one
        # vectorized dispatch seeds every slot
        st = init_state(n_docs, 2048)
        for name in ("m", "x"):
            if name != enc.root_name:
                st = ensure_root_anchor_all(st, enc.keys.intern(name))
        return st

    state = apply_update_stream(seed(), stream, rank)  # compile + warm
    assert int(np.asarray(state.error).max()) == 0
    got = get_tree(state, 0, enc.payloads, enc.keys)["map"]
    assert got == host_doc.get_map("m").to_json()
    state = seed()
    np.asarray(state.n_blocks)
    t0 = time.perf_counter()
    state = apply_update_stream(state, stream, rank)
    np.asarray(state.n_blocks)
    dt = time.perf_counter() - t0
    rate = len(log) * n_docs / dt
    py_rate = len(log) / host_dt
    host_xml = [
        {
            "name": ch.tag,
            "attrs": {k: v for k, v in ch.attributes()},
            "children": [],
        }
        for ch in host_doc.get_xml_fragment("x").children()
    ]
    native_rate = timed_native_replay(
        log,
        [
            ("m", "map", host_doc.get_map("m").to_json()),
            ("x", "seq", host_xml),
        ],
        key="config4",
    )

    def _validate(st):
        assert (
            get_tree(st, 0, enc.payloads, enc.keys)["map"]
            == host_doc.get_map("m").to_json()
        )

    return {
        "metric": "config4_map_xml_updates_per_sec",
        "value": round(rate, 1),
        "lane": "xla",
        "unit": f"updates/s over {n_docs}-doc batch (map+xml tenants)",
        "vs_baseline": round(rate / (native_rate or py_rate), 2),
        "baseline_kind": "native_cpp" if native_rate else "py_oracle_SOFT",
        "vs_native": round(rate / native_rate, 2) if native_rate else None,
        "vs_py_oracle": round(rate / py_rate, 2),
        "native_updates_per_sec": round(native_rate, 1) if native_rate else None,
        "native_baseline": _NATIVE_PIN.get("config4"),
        "py_oracle_updates_per_sec": round(py_rate, 1),
        "xla_updates_per_sec": round(rate, 1),
        "_fused": lambda: fused_lane_rate(
            seed, stream, rank, n_docs, len(log), _validate
        ),
    }


def bench_config5(n_docs: int, n_clients: int = 64):
    """Batched sync-step diff selection: D docs x C clients."""
    import jax

    from ytpu.models.batch_doc import (
        BatchEncoder,
        apply_update_stream,
        encode_diff_batch,
        init_state,
    )

    # seed every doc with a small multi-client history
    docs = [Doc(client_id=c + 1) for c in range(n_clients)]
    log = []
    relay = Doc(client_id=0xFFFF)
    relay.observe_update_v1(lambda p, o, t: log.append(p))
    for c, d in enumerate(docs):
        t = d.get_text("text")
        with d.transact() as txn:
            t.insert(txn, 0, f"client-{c} ")
        relay.apply_update_v1(d.encode_state_as_update_v1(relay.state_vector()))
    enc = BatchEncoder()
    steps = [enc.build_step(Update.decode_v1(p), 4, 2) for p in log]
    stream = BatchEncoder.stack_steps(steps)
    rank = enc.interner.rank_table()
    state = init_state(n_docs, 1024)
    state = apply_update_stream(state, stream, rank)
    assert int(np.asarray(state.error).max()) == 0

    C = max(8, len(enc.interner))
    rng = np.random.default_rng(5)
    remote = rng.integers(0, 12, size=(n_docs, C), dtype=np.int32)

    # host oracle: one encode_state_as_update per remote SV
    from ytpu.core import StateVector

    host_n = min(64, n_docs)
    t0 = time.perf_counter()
    for d in range(host_n):
        sv = StateVector(
            {
                enc.interner.from_idx[c]: int(remote[d, c])
                for c in range(len(enc.interner))
                if remote[d, c] > 0
            }
        )
        relay.encode_state_as_update_v1(sv)
    host_dt = (time.perf_counter() - t0) / host_n

    # native single-core denominator (VERDICT r4 #3): the C++ engine
    # replays the relay state once, then per-SV diff encodes. Validated
    # by applying host vs native bytes to fresh docs (granularity may
    # differ: the engine splits but never squashes).
    native_dt = None
    try:
        from ytpu.native import NativeEngine

        neng = NativeEngine()
        for p in log:
            neng.apply_update_v1(p)
        svs = [
            {
                enc.interner.from_idx[c]: int(remote[d, c])
                for c in range(len(enc.interner))
                if remote[d, c] > 0
            }
            for d in range(host_n)
        ]
        # best-of-3 (VERDICT r5 Weak #4): the per-SV loop is short enough
        # that box contention dominates a single shot
        trial_dts = []
        for _ in range(3):
            t0 = time.perf_counter()
            for sv in svs:
                neng.encode_diff_v1(sv)
            trial_dts.append((time.perf_counter() - t0) / host_n)
        native_dt = min(trial_dts)
        _NATIVE_PIN["config5"] = {
            "rate": 1.0 / native_dt,
            "trials": [round(1.0 / d, 1) for d in trial_dts],
            "pinned": True,
        }
        def coverage(payload):
            upd = Update.decode_v1(payload)
            cov = {}
            for client, blocks in upd.blocks.items():
                lo = min(b.id.clock for b in blocks)
                hi = max(b.id.clock + b.len for b in blocks)
                cov[client] = (lo, hi)
            ds = {
                c: sorted((s, e) for s, e in rs)
                for c, rs in upd.delete_set.clients.items()
                if rs
            }
            return cov, ds

        for sv in svs[:3]:
            host_b = relay.encode_state_as_update_v1(StateVector(dict(sv)))
            assert coverage(host_b) == coverage(neng.encode_diff_v1(sv))
        neng.close()
    except Exception:
        native_dt = None

    def select():
        out = encode_diff_batch(state, remote, C)
        jax.block_until_ready(out)
        return out

    out = select()  # compile + warm
    t0 = time.perf_counter()
    out = select()
    sel_dt = time.perf_counter() - t0
    assert out[0].shape == (n_docs, 1024)

    # the finisher: selected rows -> wire bytes. Python per-row loop vs the
    # native batched C++ finisher (VERDICT r2 #6; ref store.rs:204-248).
    # Selection outputs stay DEVICE-resident: the finisher compacts the
    # shipped rows on device and pulls one packed tensor (VERDICT r3 #3).
    from ytpu.models.batch_doc import finish_encode_diff, finish_encode_diff_batch

    ship, offsets, _sv, deleted = out
    py_n = min(256, n_docs)
    # the Python baseline gets host-resident arrays (one conversion, before
    # its timer) so it isn't billed per-doc device syncs the native path
    # no longer pays
    ship_np, off_np, del_np = (np.asarray(a) for a in (ship, offsets, deleted))
    t0 = time.perf_counter()
    py_payloads = [
        finish_encode_diff(state, d, ship_np, off_np, del_np, enc)
        for d in range(py_n)
    ]
    py_dt = (time.perf_counter() - t0) / py_n
    all_docs = list(range(n_docs))
    finish_encode_diff_batch(  # warm the payload arenas + compile compaction
        state, all_docs, ship, offsets, deleted, enc
    )
    t0 = time.perf_counter()
    nat_payloads = finish_encode_diff_batch(
        state, all_docs, ship, offsets, deleted, enc
    )
    nat_dt = (time.perf_counter() - t0) / n_docs
    assert nat_payloads[:py_n] == py_payloads  # byte parity
    finisher_speedup = py_dt / nat_dt if nat_dt > 0 else float("inf")

    # ISSUE-10: the staged pipeline — device compaction of sub-batch k+1
    # ‖ async D2H of k ‖ batched native finisher on k−1 — measured against
    # the serial finisher handoff above on the SAME selection, with byte
    # parity asserted.  This is the serving path (DeviceSyncServer routes
    # every SyncStep1 through it), so it headlines the config.
    from ytpu.models.batch_doc import DiffPipeline

    # default sub-batch: 512 at production doc counts (the 10240-doc
    # north-star runs 20 sub-batches), shrinking on small rehearsals so
    # the pipeline still actually overlaps (≥4 sub-batches)
    sub_env = os.environ.get("YTPU_CFG5_SUB")
    sub_batch = int(sub_env) if sub_env else min(512, max(8, n_docs // 4))
    pipe = DiffPipeline(sub_batch=sub_batch, depth=2)
    pipe.run(state, all_docs, ship, offsets, deleted, enc)  # warm the family
    t0 = time.perf_counter()
    pipe_payloads = pipe.run(state, all_docs, ship, offsets, deleted, enc)
    pipe_dt = (time.perf_counter() - t0) / n_docs
    assert pipe_payloads == nat_payloads  # pipelined-vs-serial byte parity
    st = pipe.stats
    diff_pipeline_speedup = nat_dt / pipe_dt if pipe_dt > 0 else float("inf")

    # headline = END-TO-END serving rate (selection + pipelined finisher),
    # the number an operator gets per sync round (VERDICT r3 weak #9: the
    # old value reported device selection alone and hid the finisher)
    e2e_dt = sel_dt / n_docs + pipe_dt
    serial_e2e_dt = sel_dt / n_docs + nat_dt
    return {
        "metric": "config5_encode_diff_batch_docs_per_sec",
        "value": round(1.0 / e2e_dt, 1),
        "unit": f"doc-diffs/s END-TO-END over {n_docs} docs x {C} clients "
        "(device selection + PIPELINED native finisher, byte parity "
        "asserted vs serial)",
        "vs_baseline": round((1.0 / e2e_dt) / (1.0 / (native_dt or host_dt)), 2),
        "baseline_kind": "native_cpp" if native_dt else "py_oracle_SOFT",
        "vs_native": round(native_dt / e2e_dt, 2) if native_dt else None,
        "vs_py_oracle": round(host_dt / e2e_dt, 2),
        "native_diffs_per_sec": round(1.0 / native_dt, 1) if native_dt else None,
        "native_baseline": _NATIVE_PIN.get("config5"),
        "selection_docs_per_sec": round(n_docs / sel_dt, 1),
        "serial_docs_per_sec": round(1.0 / serial_e2e_dt, 1),
        "finisher_native_docs_per_sec": round(1.0 / nat_dt, 1),
        "finisher_python_docs_per_sec": round(1.0 / py_dt, 1),
        "finisher_native_vs_python": round(finisher_speedup, 2),
        "diff_pipeline_speedup": round(diff_pipeline_speedup, 2),
        "pipeline": {
            "sub": st.sub,
            "n_sub": st.n_sub,
            "depth": st.depth,
            "R": st.R,
            "total_rows": st.total_rows,
            "threads": st.threads,
            "select_s": round(st.select_s, 6),
            "d2h_s": round(st.d2h_s, 6),
            "finish_s": round(st.finish_s, 6),
            "stall_s": round(st.stall_s, 6),
            "d2h_bytes": st.d2h_bytes,
            "overlap_ratio": round(st.overlap_ratio, 3),
            "demotions": st.demotions,
            "fallback_docs": st.fallback_docs,
        },
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="all", choices=["3", "4", "5", "all"])
    ap.add_argument("--docs", type=int, default=4096)
    args = ap.parse_args()
    runners = {"3": bench_config3, "4": bench_config4, "5": bench_config5}
    chosen = ["3", "4", "5"] if args.config == "all" else [args.config]
    results, deferred = [], []
    for key in chosen:
        n_docs = args.docs if key != "4" else min(args.docs, 4096)
        res = runners[key](n_docs)
        fused_fn = res.pop("_fused", None)
        results.append(res)
        if fused_fn is not None:
            deferred.append((res, fused_fn))
        print(json.dumps(res))
    # the crash-risky fused lane runs only after EVERY XLA measure printed
    for res, fused_fn in deferred:
        merge_fused_lane(res, fused_fn)
        print(json.dumps(res))


if __name__ == "__main__":
    main()
