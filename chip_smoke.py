#!/usr/bin/env python3
"""Does the served path still start on the chip, and is it still right?

One process drives `DeviceSyncServer` (device-authoritative) through its
normal entry points — `receive_frames` → `flush_device` → `apply_bytes` →
device decode + integrate, SyncStep1 answered from device state through the
diff pipeline and the native finisher — with `SoakDriver` over a seeded
`Scenario` of real client `Doc`s, at 1,024 resident rooms × capacity 4,096,
and compares RESULTS (texts, state vectors, diff bytes) with the host
oracle `ytpu.core.Doc`. It refuses to call a run good in which a recovery
path fired. Nothing it prints is a performance claim.

    python chip_smoke.py            # one chip; what the driver runs
    python chip_smoke.py --chips 4  # only the doc-sharded served phase
    python chip_smoke.py --chips 4 --rooms 4096  # ... at 1,024 rooms a chip,
                                    # the benchmark's `yws-rooms-4k-x4`

`--rooms` sets the resident room slots (default 1,024); capacity is never
an argument. The sharded phase also checks that the server says it is
sharded (`ingest.state_shards` = the chips) and that every state plane
still spans them after the last flush.

Exit 0 and a last line `{"ok": true, "device": {...}}` only on a TPU with
every check passed; any other outcome exits non-zero with `"ok": false`.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

N_DOCS = 1024
CAPACITY = 4096
N_SESSIONS = 2048
# Scale cut, twice over; slots and capacity are never cut. (1) The served
# path neither compacts nor grows a slot: 16 events per session put 8,559
# rows into the Zipf-hot room (CPU count, seed 0) against capacity 4,096,
# and 7 still 3,780. (2) Wall time: at 7 the run took 1,004 s on one v5e
# with 36 s of it compiles (5,285 full-width dispatches, 1,455 diffs; my
# chip run, PR 24), too near this script's 1,200 s limit; 3 is the cut.
EVENTS_PER_SESSION = 3
EXACT_ROOMS = 32  # hottest rooms whose diff bytes are checked finisher against finisher

#: recovery paths that stay in the library; a smoke in which one fired fails
WATCHED_COUNTERS = (
    "ingest.fast_recoveries",
    "encode.demotions",
    "lane.demotions",
    "net.bad_frames",
)


def _oracle_docs(scenario):
    """Per touched room, a host `Doc` that applied every session's updates."""
    from ytpu.core import Doc

    docs = {t: Doc(client_id=1) for t in scenario.tenants}
    for script in scenario.sessions:
        for kind, payload in script.events:
            if kind == "apply":
                docs[script.tenant].apply_update_v1(payload)
    return docs


def _diagnose(scenario, room, n_docs, capacity) -> str:
    """Where one room left the oracle: replay its updates alone, one per
    dispatch, through the raw-bytes lane (device decode) and through the
    host-decoded lane (same integrate), against the oracle after each."""
    from ytpu.core import Doc
    from ytpu.models.batch_doc import get_string
    from ytpu.models.ingest import BatchIngestor

    root = scenario.config.root
    payloads = [
        e.payload
        for e in scenario.events()
        if e.tenant == room and e.kind == "apply"
    ]
    raw, hostdec = BatchIngestor(n_docs, capacity), BatchIngestor(n_docs, capacity)
    for ing in (raw, hostdec):
        for script in scenario.sessions:
            ing.enc.interner.intern(script.client_id)
    doc = Doc(client_id=1)
    idle = [None] * (n_docs - 1)
    for step, p in enumerate(payloads):
        doc.apply_update_v1(p)
        want = doc.get_text(root).get_string()
        raw.apply_bytes([p] + idle)
        hostdec.apply([p] + idle)
        got_raw = get_string(raw.state, 0, raw.payloads)
        got_host = get_string(hostdec.state, 0, hostdec.payloads)
        if got_raw != want or got_host != want:
            stage = "integrate" if got_host != want else "decode"
            return (
                f"{room}: left the oracle at step {step} of {len(payloads)}: "
                f"{stage} differs (raw-bytes lane "
                f"{'ok' if got_raw == want else 'wrong'}, host-decoded lane "
                f"{'ok' if got_host == want else 'wrong'})"
            )
    return (
        f"{room}: replayed alone both lanes match the oracle at all "
        f"{len(payloads)} steps: the room is disturbed only in company, by "
        "its neighbours in the batch or by queue depth"
    )


def served_phase(
    n_docs: int = N_DOCS,
    capacity: int = CAPACITY,
    n_sessions: int = N_SESSIONS,
    events_per_session: int = EVENTS_PER_SESSION,
    seed: int = 0,
    shard_docs: bool = False,
    exact_rooms: int = EXACT_ROOMS,
    say=print,
):
    """Run the scenario through the server and check it against the oracle.
    Returns the list of failures (empty = the phase passed)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ytpu.core import Doc
    from ytpu.core.state_vector import StateVector
    from ytpu.models.batch_doc import encode_diff_batch, finish_encode_diff
    from ytpu.serving import Scenario, ScenarioConfig, SoakDriver
    from ytpu.sync.device_server import DeviceSyncServer
    from ytpu.utils import metrics

    failures = []
    scenario = Scenario(
        ScenarioConfig(
            n_tenants=n_docs,
            n_sessions=n_sessions,
            events_per_session=events_per_session,
            seed=seed,
        )
    )
    root = scenario.config.root
    oracle = _oracle_docs(scenario)
    applies = {t: 0 for t in oracle}
    kinds = {}
    for ev in scenario.events():
        kinds[ev.kind] = kinds.get(ev.kind, 0) + 1
        if ev.kind == "apply":
            applies[ev.tenant] += 1
    rooms = sorted(oracle, key=lambda t: (-applies[t], t))  # hottest first

    counters = {name: metrics.counter(name) for name in WATCHED_COUNTERS}
    before = {name: c.value for name, c in counters.items()}
    dispatch_hist = metrics.histogram("sync.apply_update")
    dispatches_before = dispatch_hist.count

    server = DeviceSyncServer(
        n_docs=n_docs,
        capacity=capacity,
        device_authoritative=True,
        shard_docs=shard_docs,
    )
    ing = server.ingestor
    resident = sum(a.nbytes for a in jax.tree.leaves(ing.state))
    n_planes = len(jax.tree.leaves(ing.state))
    say(
        f"smoke: {n_docs} room slots x capacity {capacity}, "
        f"{n_planes} planes, {resident} resident bytes; "
        f"{n_sessions} sessions x {events_per_session} events, seed {seed}; "
        f"{len(rooms)} rooms touched, hottest {rooms[0]} with "
        f"{applies[rooms[0]]} updates"
    )
    t0 = time.perf_counter()
    report = SoakDriver(server, scenario).run()
    say(
        f"smoke: events by kind {dict(sorted(kinds.items()))}; "
        f"{dispatch_hist.count - dispatches_before} dispatches; docs "
        f"integrated {ing.fast_docs} raw-bytes lane / {ing.slow_docs} "
        f"host-decoded lane; served in {time.perf_counter() - t0:.1f} s wall "
        "(compiles included)"
    )

    if not report["complete"] or report.get("dropped_updates", 0):
        failures.append(f"soak incomplete: {report.get('dropped_updates', 0)} dropped")
    rejects = {k: v for k, v in report["admission"].items() if k != "admitted" and v}
    if rejects:
        failures.append(f"admission rejects: {rejects}")

    # --- results against the oracle ---------------------------------------
    # Block granularity depends on history (the device never squashes rows,
    # the host squashes as it goes), so raw diff bytes are compared in the
    # form a fresh Doc gives them: apply the full state, encode it again.
    def canonical(update: bytes):
        fresh = Doc(client_id=2)
        fresh.apply_update_v1(update)
        return (
            fresh.get_text(root).get_string(),
            fresh.state_vector().clocks,
            fresh.encode_state_as_update_v1(),
        )

    def expected(t):
        want = oracle[t]
        return (
            want.get_text(root).get_string(),
            want.state_vector().clocks,
            canonical(want.encode_state_as_update_v1())[2],
        )

    text_wrong, diff_wrong = [], []
    diffs = server.device_encode_diff_many([(t, StateVector()) for t in rooms])
    for t, diff in zip(rooms, diffs):
        if server.device_text(t) != expected(t)[0]:
            text_wrong.append(t)
        if canonical(diff) != expected(t):
            diff_wrong.append(t)
    # the hottest rooms again, row for row: the native finisher's bytes
    # against the serial Python finisher over the same device selection
    hot = rooms[:exact_rooms]
    remote, n_clients = server._remote_matrix([])  # all zero: full state
    ship, offsets, _sv, deleted = (
        np.asarray(a)
        for a in encode_diff_batch(ing.state, jnp.asarray(remote), n_clients)
    )

    def python_finisher(t):
        return finish_encode_diff(
            ing.state, server.slot_of(t), ship, offsets, deleted, ing.enc,
            payloads=ing.payloads, root_name=server._root_names.get(t),
        )

    finisher_wrong = [
        t for t, diff in zip(hot, diffs) if diff != python_finisher(t)
    ]
    punted = server._diff_pipeline.stats.fallback_docs
    say(
        f"smoke: of {len(rooms)} touched rooms {len(rooms) - len(text_wrong)} "
        f"render the oracle's text and {len(rooms) - len(diff_wrong)} answer a "
        "full-state SyncStep1 with a diff that gives a fresh Doc the oracle's "
        "text, state vector and encoded bytes; the native finisher is "
        f"byte-equal to the Python one in {len(hot) - len(finisher_wrong)}/"
        f"{len(hot)} hottest rooms and punted {punted} rooms of the fan-out "
        "to it"
    )
    if text_wrong:
        failures.append(
            f"{len(text_wrong)} rooms render another text than the oracle "
            f"({', '.join(text_wrong[:8])}); first: "
            + _diagnose(scenario, text_wrong[0], n_docs, capacity)
        )
    elif diff_wrong or finisher_wrong:
        first = (diff_wrong or finisher_wrong)[0]
        py_right = canonical(python_finisher(first)) == expected(first)
        stage = (
            "the Python finisher over the same device selection gives the "
            "oracle's diff, so the pack, its copy to the host or the native "
            "finisher differs"
            if py_right
            else "the Python finisher over the device selection is wrong too, "
            "so the selection (encode_diff_batch) differs"
        )
        failures.append(
            f"{len(diff_wrong)} rooms hold the right text and answer a wrong "
            f"diff ({', '.join(diff_wrong[:8])}), {len(finisher_wrong)} of the "
            f"hottest differ finisher from finisher; first {first}: {stage}"
        )
    if punted:
        failures.append(
            f"the native finisher punted {punted} text-only rooms to the "
            "Python finisher"
        )

    # --- the slots nobody touched, and what must not have happened ---------
    used = set(server._slot_of.values())
    n_blocks = np.asarray(ing.state.n_blocks)
    start = np.asarray(ing.state.start)
    dirty = [s for s in range(n_docs) if s not in used and (n_blocks[s] or start[s] != -1)]
    if len(used) != len(rooms) or dirty:
        failures.append(f"untouched slots not empty: {dirty[:8]} ({len(used)} slots used)")
    flagged = np.nonzero(np.asarray(ing.state.error))[0].tolist()
    if flagged:
        failures.append(f"error flags on slots {flagged[:8]} (1 = capacity, 2 = missing dep)")
    stuck = [d for d in range(n_docs) if ing.pending_update(d) or ing.pending_ds(d)]
    if stuck:
        failures.append(f"updates left pending on slots {stuck[:8]}")
    fired = {n: c.value - before[n] for n, c in counters.items() if c.value != before[n]}
    say(
        f"smoke: fast_docs {ing.fast_docs}, fast_recoveries {ing.fast_recoveries}, "
        + ", ".join(f"{n} {c.value - before[n]}" for n, c in counters.items())
        + f", host-demoted tenants {len(server._host_tenants)}, "
        f"admission rejects {sum(rejects.values())}"
    )
    if ing.fast_docs <= 0:
        failures.append("fast_docs == 0: no update took the raw-bytes lane")
    if ing.fast_recoveries or fired:
        failures.append(f"recovery paths fired: {fired or ing.fast_recoveries}")
    if server._host_tenants:
        failures.append(f"tenants demoted to the host: {sorted(server._host_tenants)[:8]}")

    if shard_docs:
        n_dev = len(jax.devices())
        local = [
            i
            for i, a in enumerate(jax.tree.leaves(ing.state))
            if len(a.sharding.device_set) != n_dev
        ]
        shards = metrics.gauge("ingest.state_shards").value
        say(
            f"smoke: after the last flush {n_planes - len(local)} of "
            f"{n_planes} state planes span {n_dev} devices; the gauge "
            f"ingest.state_shards reads {shards}"
        )
        if local or shards != n_dev:
            failures.append(
                f"state planes {local} no longer span {n_dev} devices "
                f"(ingest.state_shards {shards})"
            )
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument(
        "--rooms",
        type=int,
        default=N_DOCS,
        help="resident room slots; with --chips 4 they are laid over the chips",
    )
    ap.add_argument(
        "--events-per-session",
        type=int,
        default=EVENTS_PER_SESSION,
        help="scale cut; slots and capacity are never cut",
    )
    args = ap.parse_args(argv)
    t_start = time.perf_counter()

    def finish(ok: bool, device=None, error=None) -> int:
        line = {"ok": ok, "device": device}
        if error:
            print(f"smoke: FAILED: {error}")
        print(json.dumps(line), flush=True)
        return 0 if ok else 1

    import jax
    import jaxlib

    try:
        devices = jax.devices()
    except RuntimeError as e:  # no backend at all
        return finish(False, error=f"jax found no device: {e}")
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if device["platform"] != "tpu":
        return finish(False, device, "not a TPU; this smoke runs on the chip only")
    if args.chips == 4 and device["count"] != 4:
        return finish(False, device, "--chips 4 needs four chips")

    from ytpu import native
    from ytpu.utils.compile_cache import build_totals, enable_compile_cache
    from ytpu.utils.phases import phases

    if not native.available():
        return finish(False, device, "native library did not build from the sources")
    cache_dir = enable_compile_cache()
    phases.enable()
    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:
        libtpu = "unknown"
    print(
        f"smoke: {device['count']} x {device['kind']}; jax {jax.__version__}, "
        f"jaxlib {jaxlib.__version__}, libtpu {libtpu}; native library "
        f"{native.load()._name.rsplit('/', 1)[-1]}; compile cache {cache_dir}"
    )
    failures = served_phase(
        n_docs=args.rooms,
        events_per_session=args.events_per_session,
        seed=args.seed,
        shard_docs=args.chips == 4,
    )
    stats = devices[0].memory_stats() or {}
    built = build_totals()
    print(
        f"smoke: peak_bytes_in_use {stats.get('peak_bytes_in_use', 'not reported')} "
        f"on device 0; {len(phases.compile_events())} program signatures "
        f"(compile_events), {built['builds']} XLA programs built: traced "
        f"{built['trace_s']:.1f} s, lowered {built['lower_s']:.1f} s, "
        f"{built['backend_s']:.1f} s in the backend of which "
        f"{built['cache_load_s']:.1f} s reading the {built['cache_hits']} that "
        f"came from the persistent cache "
        f"({built['cache_requests'] - built['cache_hits']} misses written to it; "
        f"a cold cache would have cost +{built['saved_s']:.1f} s of compiling); "
        f"{time.perf_counter() - t_start:.1f} s wall in all. Observations of "
        "a smoke, not metrics."
    )
    return finish(not failures, device, "; ".join(failures))


if __name__ == "__main__":
    sys.exit(main())
