#!/usr/bin/env python3
"""One run of one cell of `BENCHMARK.json`, on the machine it is started on.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: refuses anything but a TPU with the chips the cell asks for,
builds the server, prefills every room through the served path, warms up
every shape the window will use (all of that is `setup_s`), measures for
`--seconds`, checks the server against the host oracle (every number compared
is printed beside its limit, last on stderr and as the line's last key,
`compared`), and prints one JSON object as the last line. `--trace 0` reports the cell's end-to-end metrics,
`--trace 1` its per-layer metrics (profiler and the program's phase recorder
on). The window is `min(--seconds, the pool)` where a saturated pool does not
repeat; the traced slice is its last 4 s wherever it ends, and a window the
pool closed in under 8 s ends the run with exit code 5 and no result
(`benchmark/window.py`). See `benchmark/README.md`.

`--rehearse` (the benchmark's own tests) runs the same command path on the
CPU at the tiny sizes the data files give under "rehearsal", and prints no
metric. `--break lose-update` is the control: the loop silently loses one
update the oracle is told about, and `correct` must come out false.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def say(msg: str) -> None:
    print(f"bench: {msg}", flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_reader(directory: str, name: str):
    """A metric's reader: the module `benchmark/<directory>/<name>.py`, or,
    for a quantity split by the end-to-end metric it moves
    (`dispatch_ms.flood`, `dispatch_ms.steady`), `<name before the last dot>.py`."""
    path = os.path.join(HERE, directory, name + ".py")
    if not os.path.exists(path) and "." in name:
        path = os.path.join(HERE, directory, name.rsplit(".", 1)[0] + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"metric {name!r} has no reader under {os.path.join(HERE, directory)}")
    spec = importlib.util.spec_from_file_location(f"benchmark.{directory}.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_programs() -> dict:
    out = {}
    for path in sorted(glob.glob(os.path.join(HERE, "layers", "programs", "*.txt"))):
        with open(path) as f:
            out[os.path.splitext(os.path.basename(path))[0]] = [ln.strip() for ln in f if ln.strip()]
    return out


def applies(metric: dict, cell: str, reported: set) -> bool:
    """Does the cell report this metric? By its `workloads` key, else by the
    end-to-end metric it moves."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return "moves" not in metric or metric["moves"] in reported


class CompileWatch:
    """Program builds as jax.monitoring reports them (a program compiled or
    loaded from the persistent cache both count), with the names jax logs."""

    def __init__(self):
        self.builds = 0
        self.build_s = 0.0
        self.cache_hits = 0
        self.names = []

    def install(self, jax) -> None:
        def on_duration(name, secs, **_):
            if name.endswith("/backend_compile_duration"):
                self.builds += 1
                self.build_s += secs

        def on_event(name, **_):
            if name.endswith("/cache_hits"):
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)
        watch = self

        class Names(logging.Handler):
            def emit(self, record):
                msg = record.getMessage()
                if msg.startswith("Compiling "):
                    watch.names.append(msg.split(". Argument mapping", 1)[0][10:400])
                if record.levelno >= logging.WARNING:
                    logging.getLogger("jax").handle(record)

        # jax names a program at DEBUG when it builds it; listen there
        # without letting the DEBUG records reach jax's own stderr handler
        lg = logging.getLogger("jax._src.interpreters.pxla")
        lg.setLevel(logging.DEBUG)
        lg.propagate = False
        lg.addHandler(Names(level=logging.DEBUG))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true", help="CPU rehearsal at tiny sizes; prints no metric")
    ap.add_argument("--mix", default=None, help="with --rehearse: another traffic file over the cell's configuration")
    ap.add_argument("--break", dest="brk", choices=("lose-update",), default=None,
                    help="the control: must end with correct false")
    args = ap.parse_args(argv)
    seed = args.seed % (2**32)  # the driver's seeds pass 2**31; every RNG here is keyed by crc32 anyway

    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = next((w for w in bench["workloads"] if w["name"] == args.workload), None)
    if cell is None:
        print(f"bench: no workload {args.workload!r} in BENCHMARK.json", file=sys.stderr)
        return 2
    if args.mix:
        if not args.rehearse:
            print("bench: --mix is for rehearsals; a measured cell is an entry of BENCHMARK.json", file=sys.stderr)
            return 2
        cell = dict(cell, traffic=args.mix, name=cell["config"] + "." + args.mix)
        args.workload = cell["name"]
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    deploy = load_json(os.path.join(ROOT, config["file"]))
    mix = load_json(os.path.join(HERE, "traffic", cell["traffic"] + ".json"))
    if args.rehearse:
        deploy.update(deploy.get("rehearsal", {}))
        mix.update(mix.get("rehearsal", {}))

    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"bench: jax found no device: {e}", file=sys.stderr)
        return 3
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices)}
    if not args.rehearse:
        if device["platform"] != "tpu":
            print(f"bench: {device['platform']} is not a TPU; this benchmark measures on the chip only", file=sys.stderr)
            return 3
        if device["count"] < cell["chips"]:
            print(f"bench: the cell asks for {cell['chips']} chips, jax sees {device['count']}", file=sys.stderr)
            return 3

    from ytpu import native
    from ytpu.utils import metrics as prog_metrics
    from ytpu.utils.compile_cache import enable_compile_cache
    from ytpu.utils.phases import phases

    watch = CompileWatch()
    watch.install(jax)
    if not args.rehearse:
        cache_dir = enable_compile_cache()
        # every program, however small, is served from the cache on later runs
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        say(f"{device['count']} x {device['kind']}; jax {jax.__version__}; compile cache {cache_dir}")
    if not native.available():
        print("bench: the native library did not build from the sources", file=sys.stderr)
        return 3

    import numpy as np

    from benchmark import grammar as g
    from benchmark import oracle, peaks, trace_reduce, warmup, window
    from benchmark.ops import Op
    from benchmark.serve import ServerLoop, now
    from ytpu.sync.device_server import DeviceSyncServer

    # --- inputs from the seed -------------------------------------------------
    n_rooms = deploy["n_docs"]
    prefill = g.Prefill(deploy["prefill"], n_rooms, seed)
    generator = importlib.import_module("benchmark.generators." + mix["generator"])
    plan = generator.plan(deploy, mix, prefill, seed, args.seconds)
    filled = sum(prefill.for_room(k).rows for k in range(n_rooms))
    say(f"{args.workload} seed {args.seed}: {n_rooms} rooms x capacity {deploy['capacity']}, "
        f"{len(plan.session_rooms)} sessions, hottest room {plan.notes['hot_room_sessions']} sessions, "
        f"{len(plan.ops)} ops planned ({'saturated' if plan.saturated else 'open loop'}), "
        f"prefill {filled} rows = {100.0 * filled / (n_rooms * deploy['capacity']):.1f}% of the slots "
        f"({[(prefill.of_room.count(t), tp.rows) for t, tp in enumerate(prefill.templates)]} rooms x rows); "
        f"inputs made in {now() - T_START:.1f} s")

    lose_at, lose_in_preload = -1, False
    if args.brk == "lose-update":
        # the first update of the hottest room (its text is always checked):
        # of the window, or of the preload where the window sends no update
        upd = [op for op in plan.ops if op.kind == "update"]
        lose_in_preload = not upd
        lose_at = next((i for i, op in enumerate(upd or plan.preload) if op.room == 0), -1)
        if lose_at < 0:
            print("bench: --break lose-update found no update of room0 to lose", file=sys.stderr)
            return 2

    # --- the server, prefilled and warm --------------------------------------
    server = DeviceSyncServer(
        n_docs=n_rooms,
        capacity=deploy["capacity"],
        device_authoritative=deploy["device_authoritative"],
        shard_docs=deploy["shard_docs"],
    )
    for c in plan.clients:
        server.ingestor.enc.interner.intern(c)
    resident = peaks.state_bytes(server.ingestor.state)
    if args.trace:
        # on before the warm-up: the recorder lowers every program signature it
        # sees for the first time, and that must not happen inside the window
        phases.reset()
        phases.enable()
    loop = ServerLoop(server, plan, n_rooms, traced=bool(args.trace), lose_update_at=lose_at)
    counters_before = oracle.counter_values()

    t = now()
    loop.connect_loaders()
    loop.tag = "prefill"
    for stage in range(prefill.n_stages):  # one dispatch a stage, every room in each
        loop.tick(
            [Op("update", k, k, g.update_frame(u), update=u)
             for k in range(n_rooms)
             for u in (prefill.for_room(k).stages[stage],)],
            loop.loaders, count=False,
        )
    say(f"set-up: {resident} resident bytes; every room prefilled through the served path "
        f"in {now() - t:.1f} s ({loop.dispatches} dispatches)")
    t = now()
    loop.connect_sessions()
    loop.tag = "preload"
    for i in range(0, len(plan.preload), plan.tick_max_frames):
        loop.tick(plan.preload[i : i + plan.tick_max_frames], loop.sessions, count=lose_in_preload)
    if plan.preload:
        say(f"set-up: {len(plan.preload)} preload updates in {now() - t:.1f} s")
    loop.tag = "warm"
    t = now()
    builds0 = watch.builds
    sweeper = warmup.Sweeper(plan, seed, prefill)
    if plan.notes.get("needs_update_warm"):
        warmup.update_sweep(loop, plan, sweeper, say)
    if plan.notes.get("needs_sync_warm"):
        warmup.sync_sweep(loop, plan, sweeper, prefill, say)
    warmup.own_traffic(loop, plan, say)
    say(f"set-up: warm-up took {now() - t:.1f} s and built {watch.builds - builds0} programs; "
        f"{watch.builds} programs built so far in {watch.build_s:.1f} s, {watch.cache_hits} from the cache")
    if any(loop.rec.failed):
        print("bench: an op failed during set-up", file=sys.stderr)
        return 4

    # --- the window -----------------------------------------------------------
    loop.open_window()
    trace_dir = os.path.join(ROOT, ".bench_trace")
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
    tracing = {"on": False, "at": None, "end": None}  # the tick the profiler started at, the end it projected
    handed = {"share": 0.0}  # of a pool that does not repeat, after the last tick
    counter_names = ("ingest.fast_docs", "ingest.slow_docs")
    prog_before = {n: prog_metrics.counter(n).value for n in counter_names}
    phases_before = phases.snapshot() if args.trace else {}
    slice_s = min(window.TRACE_SLICE_S, args.seconds / 2.0)

    def on_tick(elapsed: float, handed_share: float) -> None:
        handed["share"] = handed_share
        if args.trace and not tracing["on"] and window.slice_opens(elapsed, handed_share, args.seconds, slice_s):
            tracing.update(at=elapsed, end=window.projected_end(elapsed, handed_share, args.seconds))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1  # the bench.* annotations, not every runtime call
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            tracing["on"] = True

    # everything made in set-up stays: keep the collector from walking it
    # (and pausing the loop for it) inside the window
    gc.collect()
    gc.freeze()
    builds_open, names_open = watch.builds, len(watch.names)
    setup_s = now() - T_START
    t_open, t_close = loop.run_window(args.seconds, on_tick)
    window_builds = watch.builds - builds_open
    window_names = watch.names[names_open:]
    if tracing["on"]:
        jax.profiler.stop_trace()
    if args.trace:
        phases.disable()
    peak_bytes = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices[: max(1, cell["chips"])])
    rec = loop.rec
    n_ops = len(rec.kind)
    say(f"window: {t_close - t_open:.3f} s, {n_ops} ops handed to the server "
        f"({ {k: rec.kind.count(k) for k in sorted(set(rec.kind))} }), {len(loop.dispatch_spans)} dispatches, "
        f"{loop.broadcast_frames} broadcast frames drained, {window_builds} programs built inside the window"
        + (f": {sorted(set(window_names))[:6]}" if window_builds else ""))

    done = [rec.done[i] for i in range(n_ops) if rec.kind[i] == "update" and not rec.failed[i] and rec.done[i] > 0.0]
    if done:  # is the work per update the same early and late in the window?
        third = (t_close - t_open) / 3.0
        by_third = [sum(1 for d in done if t_open + k * third <= d < t_open + (k + 1) * third) / third for k in range(3)]
        say("window: updates/s in its first, second and last third: " + ", ".join(f"{x:.2f}" for x in by_third))
    window_s = t_close - t_open
    by_pool = window.closed_by_pool(handed["share"], window_s, args.seconds)
    say(f"window: closed by {'the pool' if by_pool else '--seconds'} after {window_s:.3f} s, "
        + (f"{100.0 * handed['share']:.1f}% of the pool of {len(plan.ops)} ops handed over" if handed["share"]
           else "the plan repeats or is open loop")
        + ("; no traced slice (--trace 0)" if not args.trace
           else "; the traced slice never opened" if not tracing["on"]
           else f"; the traced slice opened at {tracing['at']:.3f} s (projected end {tracing['end']:.3f} s - {slice_s:g})"))
    if window.too_short_to_read(by_pool, window_s) and not args.rehearse:
        print(f"bench: pool of {len(plan.ops)} drained in {window_s:.3f} s, under {window.MIN_POOL_WINDOW_S:g} s: "
              f"too short a window to read a rate or a slice from; no result", file=sys.stderr)
        return window.EXIT_TOO_SHORT

    # --- correct --------------------------------------------------------------
    t = now()
    window_counts = [sum(1 for tag, _, _ in q if tag == "window") for q in loop.taken]
    if not any(window_counts):  # a mix without updates: heat is sessions per room
        window_counts = [0] * n_rooms
        for r in rec.room:
            window_counts[r] += 1
    room_updates = [[u for _, u, _ in q] for q in loop.taken]
    compared = {}  # short name -> [number, limit] of everything `correct` rests on
    correct = oracle.check(server, loop, plan, prefill, room_updates, window_counts, counters_before, seed, say, compared)
    flagged_rooms = set(np.nonzero(np.asarray(server.ingestor.state.error))[0].tolist())
    failed = sum(1 for i in range(n_ops) if rec.failed[i] or rec.room[i] in flagged_rooms)
    compared["window_punts"], compared["ops_failed"] = [loop.punted, 0], [failed, 0]
    if loop.punted:
        say(f"check: rooms the native finisher punted during the window: {loop.punted} (limit 0) FAILED")
        correct = False
    if failed:
        say(f"check: ops refused, dropped, killed as bad frames or landed in a flagged room: {failed} (limit 0) FAILED")
        correct = False
    fullest = min(server.capacity_snapshot()["tenants"].values(), key=lambda row: row["free_rows"])
    say(f"check: took {now() - t:.1f} s; correct {correct}; the fullest room has {fullest['free_rows']} of "
        f"{deploy['capacity']} rows free")

    # --- metrics --------------------------------------------------------------
    trace = {}
    if tracing["on"]:
        paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
        if paths:
            trace = trace_reduce.reduce(trace_reduce.load_xplane(paths[-1]))
            say(f"trace: {paths[-1]} ({os.path.getsize(paths[-1])} bytes); slice {trace.get('window_s')} s, "
                f"busy {trace.get('busy_s')} s; programs {sorted((trace.get('program_s') or {}).items(), key=lambda kv: -kv[1])[:8]}")
    phases_after = phases.snapshot() if args.trace else {}
    phase_delta = {}
    for stage, vals in phases_after.items():
        b = phases_before.get(stage, {})
        phase_delta[stage] = {k: v - b.get(k, 0) for k, v in vals.items() if isinstance(v, (int, float))}
    w = window.Window(
        rec=rec, t_open=t_open, t_close=t_close, setup_s=setup_s,
        dispatch_spans=loop.dispatch_spans,
        counters={n: prog_metrics.counter(n).value - prog_before[n] for n in counter_names},
        phases=phase_delta, trace=trace, programs=load_programs(),
        compiles=window_builds, state_bytes=resident, device_kind=device["kind"],
    )
    reported_e2e = {m["name"] for m in bench["end_to_end"] if applies(m, args.workload, set())}
    wanted = (
        [(m, "layers") for m in bench["per_layer"] if applies(m, args.workload, reported_e2e)]
        if args.trace
        else [(m, "end_to_end") for m in bench["end_to_end"] if m["name"] in reported_e2e]
    )
    lat = [(rec.done[i] - rec.due[i]) * 1e3 for i in w.indices("update")]
    if lat:
        say(f"samples: {len(lat)} update frames timed from due to the end of their dispatch")
    out_metrics = {}
    for m, directory in wanted:
        value = load_reader(directory, m["name"]).read(w)
        if value is not None and value == value:
            out_metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    # every number compared beside its limit: the last lines on stderr, the last key of the result line
    for name, (value, limit) in compared.items():
        print(f"bench: compared {name} {value} (limit {limit})", file=sys.stderr)
    sys.stderr.flush()
    if args.rehearse:
        print(json.dumps({"rehearsal": True, "correct": bool(correct), "attempted": n_ops, "failed": failed,
                          "would_report": sorted(out_metrics), "compared": compared}))
        return 0
    device["memory_peak_bytes"] = int(peak_bytes)
    line = {"correct": bool(correct), "attempted": n_ops, "failed": failed, "metrics": out_metrics, "device": device}
    if args.trace and trace:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        line["breakdown"] = {"device_ops": trace["device_ops"], "idle_gaps": trace["idle_gaps"]}
    line["compared"] = compared
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
