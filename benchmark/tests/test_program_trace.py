"""`benchmark/program_trace.py` and the six readers that use it or the
program's phases: on a hand-made event list, on a trace recorded here on the
CPU, and on what a program without the seam gives (nothing: every reader
returns None and never raises)."""

import glob
import os

import pytest

from benchmark import program_trace as pt
from benchmark.run import load_reader
from benchmark.window import Window

MS = 1e6  # ns
NEW_READERS = ("fanout_us.flood", "merge_ms.flood", "merge_scatter_ms.flood",
               "dispatch_self_pct.flood", "scan_dev_ms.flood", "idle_in_merge_pct.flood")
CHEAP = "jit(apply_update_batch)/vmap(integrate_rows)/while/body/closed_call/conflict_scan/cheap/while"
WIDE = "jit(apply_update_batch)/vmap(integrate_rows)/while/body/closed_call/conflict_scan/wide/while"


def _events():
    """One tick of 100 ms, one step: the host works 5..60 and the device
    runs the integrate program 60..90."""
    host = [
        ["bench.tick", 0.0, 100 * MS, 1],
        ["ytpu.sync.receive", 1 * MS, 2 * MS, 1],
        ["ytpu.sync.receive.fanout", 2 * MS, 1 * MS, 1],
        ["bench.dispatch", 5 * MS, 87 * MS, 1],  # 5..92
        ["ytpu.sync.dispatch", 5 * MS, 55 * MS, 1],  # 5..60
        ["ytpu.ingest.apply", 6 * MS, 54 * MS, 1],  # 6..60: a container
        ["ytpu.ingest.plan", 6 * MS, 10 * MS, 1],  # 6..16: a leaf
        ["ytpu.ingest.merge", 16 * MS, 40 * MS, 1],  # 16..56
        ["ytpu.ingest.merge.h2d", 16 * MS, 4 * MS, 1],  # 16..20
        ["ytpu.ingest.merge.scatter", 30 * MS, 26 * MS, 1],  # 30..56
        ["ytpu.integrate.xla_batch", 57 * MS, 2 * MS, 1],  # 57..59
        ["ytpu.other.thread", 6 * MS, 50 * MS, 2],  # another thread's span names nothing here
    ]
    dev = [
        ["fusion.1", "jit(gather)/gather", 20 * MS, 1 * MS],  # 20..21, inside the merge
        ["fusion.2", "jit(scatter)/scatter", 40 * MS, 2 * MS],  # 40..42
        ["while.663", CHEAP, 60 * MS, 18 * MS],  # 60..78
        ["fusion.7", CHEAP + "/body/add", 61 * MS, 1 * MS],  # nested in the while: counted once
        ["while.659", WIDE, 78 * MS, 10 * MS],  # 78..88
        ["fusion.9", "jit(apply_update_batch)/vmap(delete_pass)/while", 88 * MS, 2 * MS],  # 88..90
    ]
    return {"host": host, "device": {"/device:TPU:0": dev}}


def test_leaves_are_the_spans_with_nothing_nested():
    ev = _events()
    spans = pt.program_spans(ev, *pt.slice_bounds(ev))
    leaves = {sp[2] for sp in pt.leaf_spans(spans)}
    assert leaves == {
        "ytpu.sync.receive.fanout", "ytpu.ingest.plan", "ytpu.ingest.merge.h2d",
        "ytpu.ingest.merge.scatter", "ytpu.integrate.xla_batch", "ytpu.other.thread",
    }


def test_dispatch_self_is_the_time_no_leaf_names():
    # of 5..60: plan 6..16, h2d 16..20, scatter 30..56, integrate 57..59 = 42 named
    assert pt.dispatch_self_share(_events()) == pytest.approx(1.0 - 42.0 / 55.0)


def test_scoped_device_time_is_a_union():
    ev = _events()
    assert pt.scoped_device_seconds(ev, "conflict_scan") == pytest.approx(0.028)  # 60..88, the nested op once
    assert pt.scoped_device_seconds(ev, "conflict_scan/wide") == pytest.approx(0.010)
    assert pt.scoped_device_seconds(ev, "delete_pass") == pytest.approx(0.002)
    # a program without the scope (its ops still have their `op_name`s): no reading, not a zero
    assert pt.scoped_device_seconds(ev, "no_such_scope") is None


def test_idle_gaps_go_to_the_innermost_program_span():
    ev = _events()
    table = pt.idle_by_span(ev)
    # gaps: 0..20 (middle 10: plan), 21..40 (30.5: scatter), 42..60 (51: scatter), 90..100 (95: nothing)
    assert table["ytpu.ingest.plan"] == pytest.approx([0.020, 1, 0.020])
    assert table["ytpu.ingest.merge.scatter"] == pytest.approx([0.037, 2, 0.019])
    assert table[pt.OUTSIDE] == pytest.approx([0.010, 1, 0.010])
    assert sum(row[0] for row in table.values()) == pytest.approx(0.100 - 0.033)
    assert pt.idle_share_inside(ev, pt.MERGE) == pytest.approx(0.037 / 0.067)
    assert pt.idle_share_inside(ev, "ytpu.no.such.span") is None


def test_own_device_time_adds_up_to_the_busy_time():
    ev = _events()
    ev["device"]["/device:TPU:0"].append(["copy.5", "", 62 * MS, 2 * MS])  # the compiler's own op, in the loop
    own = pt.device_self_seconds(ev)
    assert own == pytest.approx({
        "gather": 0.001, "scatter": 0.002,
        "apply_update_batch/integrate_rows/conflict_scan/cheap": 0.016,  # the loop 17 of 18 (one op nested), its op 1, less the copy's 2
        "apply_update_batch/integrate_rows/conflict_scan/cheap/" + pt.UNNAMED: 0.002,
        "apply_update_batch/integrate_rows/conflict_scan/wide": 0.010,
        "apply_update_batch/delete_pass": 0.002,
    })
    assert sum(own.values()) == pytest.approx(0.033)


@pytest.mark.parametrize("op_name, path", [
    (CHEAP + "/body/add", "apply_update_batch/integrate_rows/conflict_scan/cheap"),
    (CHEAP, "apply_update_batch/integrate_rows/conflict_scan/cheap"),
    ("jit(apply_update_batch)/vmap(delete_pass)/while/body/cond/branch_1_fun/split/scatter", "apply_update_batch/delete_pass/split"),
    ("jit(decode_updates_v1)/decode_v1/while/body/decode_v1/while/body/decode_v1/gather", "decode_updates_v1/decode_v1"),
    ("jit(scatter)/scatter", "scatter"),
    ("", ""),
])
def test_scope_path_keeps_the_program_and_the_named_scopes(op_name, path):
    assert pt.scope_path(op_name) == path


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _msg(*fields):
    """A protobuf message by hand: (number, bytes | str | int) fields."""
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += _varint(num << 3) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(num << 3 | 2) + _varint(len(v)) + v
    return out


def test_op_names_come_out_of_the_hlo_protos_the_profiler_keeps():
    ins = lambda name, op_name=None: _msg((1, name), (2, "while"), (35, 7),
                                          *([(7, _msg((1, "while"), (2, op_name), (4, 881)))] if op_name else []))
    module = _msg((1, "jit_apply_update_batch"), (3, _msg((1, "main"), (2, ins("while.663", CHEAP)), (2, ins("copy.1")))),
                  (3, _msg((1, "body"), (2, ins("fusion.7", CHEAP + "/body/add")))), (5, 973))
    hlo = _msg((1, module), (3, b"\x08\x01"))
    kept = _msg((1, 5), (2, "jit_apply_update_batch(3425012894235273035)"), (5, _msg((1, 1), (6, hlo))))
    bare = _msg((1, 6), (2, "jit_iota(1)"))  # a program the profiler kept no HLO for
    meta_plane = _msg((2, pt.METADATA_PLANE), (4, _msg((1, 5), (2, kept))), (4, _msg((1, 6), (2, bare))),
                      (5, _msg((1, 1), (2, _msg((1, 1), (2, "Hlo Proto"))))))
    other = _msg((1, 2), (2, "/device:TPU:0"), (4, _msg((1, 9), (2, _msg((2, "%while.663 = ..."), (5, _msg((1, 3), (6, hlo))))))))
    got = pt.hlo_op_names(_msg((1, other), (1, meta_plane), (4, "host")))
    assert got == {"jit_apply_update_batch(3425012894235273035)": {"while.663": CHEAP, "fusion.7": CHEAP + "/body/add"}}
    assert pt.instruction_name("%while.663 = (s32[]{:T(128)}, s32[1024,4096]) while(%tuple.1192), condition=%c") == "while.663"
    assert pt.hlo_op_names(b"") == {}


def _window(trace=None, phases=None):
    return Window(rec=None, t_open=0.0, t_close=30.0, setup_s=1.0,
                  dispatch_spans=[(0.0, 0.1, 8), (0.1, 0.2, 8)],
                  phases=phases or {}, trace=trace or {})


def test_readers_give_numbers_from_the_seam(monkeypatch):
    monkeypatch.setattr(pt, "events", lambda trace_dir=pt.TRACE_DIR: _events())
    w = _window(
        trace={"window_s": 0.1, "busy_s": 0.033, "span_counts": {"bench.dispatch": 1}},
        phases={"sync.receive.fanout": {"calls": 16, "execute_s": 0.0008},
                "ingest.merge": {"calls": 2, "execute_s": 0.120},
                "ingest.merge.scatter": {"calls": 2, "execute_s": 0.050}},
    )
    got = {name: load_reader("layers", name).read(w) for name in NEW_READERS}
    assert got == pytest.approx({
        "fanout_us.flood": 50.0, "merge_ms.flood": 60.0, "merge_scatter_ms.flood": 25.0,
        "dispatch_self_pct.flood": 100.0 * (1.0 - 42.0 / 55.0), "scan_dev_ms.flood": 28.0,
        "idle_in_merge_pct.flood": 100.0 * 0.037 / 0.067,
    })


def test_readers_give_nothing_for_a_program_without_the_seam(monkeypatch):
    """The parent of PR 26 under this benchmark: no `ytpu.*` span, no scope
    on any op, none of the stages. Nothing is read and nothing raises."""
    bare = _events()
    bare["host"] = [e for e in bare["host"] if e[0].startswith("bench.")]
    for op in bare["device"]["/device:TPU:0"]:
        op[1] = op[1].replace("/conflict_scan/cheap", "").replace("/conflict_scan/wide", "")  # the parent's op_names
    traced = {"window_s": 0.1, "busy_s": 0.033, "span_counts": {"bench.dispatch": 1}}
    for ev, trace in ((bare, traced), (None, traced), (_events(), {})):
        monkeypatch.setattr(pt, "events", lambda trace_dir=pt.TRACE_DIR, ev=ev: ev)
        w = _window(trace=trace, phases={"ingest.plan": {"calls": 2, "execute_s": 0.02}})
        assert [load_reader("layers", name).read(w) for name in NEW_READERS] == [None] * 6


def test_loader_keeps_the_programs_spans_and_finds_the_newest_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    from ytpu.utils.phases import PhaseRecorder

    assert pt.newest_xplane(str(tmp_path)) is None and pt.events(str(tmp_path)) is None
    rec = PhaseRecorder(enabled=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.tick"):
        with rec.span("sync.dispatch"):
            with rec.span("ingest.apply"):
                jnp.ones((64, 64)).sum().block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))[0]
    assert pt.newest_xplane(str(tmp_path)) == path
    ev = pt.events(str(tmp_path))
    assert pt.events(str(tmp_path)) is ev  # parsed once for the process
    names = [e[0] for e in ev["host"]]
    assert {"bench.tick", "ytpu.sync.dispatch", "ytpu.ingest.apply"} <= set(names)
    assert ev["device"] == {}  # no TPU here: nothing is reported as device time
    assert pt.dispatch_self_share(ev) is not None  # the one leaf names most of its dispatch
    assert pt.scoped_device_seconds(ev, "conflict_scan") is None
    assert pt.idle_share_inside(ev, pt.MERGE) is None


def test_traced_rehearsal_reads_what_the_cpu_can_give():
    """The command path with the profiler on (`--seconds` short enough that
    the slice opens before the tiny pool runs out): the three readers of
    the program's phases report, the three of the device trace find no
    device plane on the CPU and report nothing, and none raises."""
    import json
    import subprocess
    import sys

    p = subprocess.run(
        [sys.executable, os.path.join(pt.ROOT, "benchmark", "run.py"), "--workload", "yws-rooms-1k.edit-flood",
         "--seed", "4000000019", "--seconds", "0.5", "--trace", "1", "--rehearse"],
        capture_output=True, text=True, env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=pt.ROOT, timeout=900,
    )
    assert p.returncode == 0, p.stderr[-2000:]
    assert "bench: trace: " in p.stdout  # a trace was taken and parsed
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert {"fanout_us.flood", "merge_ms.flood", "merge_scatter_ms.flood", "plan_ms.flood"} <= set(last["would_report"])
    assert not {"dispatch_self_pct.flood", "scan_dev_ms.flood", "idle_in_merge_pct.flood"} & set(last["would_report"])
