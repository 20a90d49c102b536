"""The readers of set-up from inside (PR 41): `build_trace_s`, `build_lower_s`,
`build_compile_s`, `build_cache_load_s`, `build_programs`, `build_cache_hit_pct`,
`setup_dispatch_s`, `native_build_s`, and the two window readings `roots_us.flood`
and `enqueue_wait_ms.flood`, each on a hand-made `Window` and recorder: a value
where the program keeps the build totals, `None` (never 0, never an exception)
where it does not, as on the parent. And `tools/setup_by_program.py` on a
hand-made journal."""

import json
import os

import pytest

from benchmark import setup_parts
from benchmark.run import applies, load_reader
from benchmark.tools import setup_by_program
from benchmark.window import Window

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELLS = ["yws-rooms-1k.edit-flood", "yws-rooms-4k-x4.edit-flood",
         "yws-rooms-1k-unregistered.author-flood", "yws-rooms-1k-records.record-flood"]
T_OPEN = 100.0


def _row(name, t, trace, lower, backend, load, cache, spans=(), saved=0.0):
    return {"fun_name": name, "t": t, "trace_s": trace, "lower_s": lower, "backend_s": backend, "cache_load_s": load,
            "saved_s": saved, "cache": cache, "spans": list(spans), "stage": spans[-1] if spans else None, "signature": None}


# two programs before the window (one inside a served dispatch, read from the
# cache), one built by the check after it
LOG = [
    _row("jit(iota)", 10.0, 0.5, 0.25, 1.0, 0.0, "miss"),
    _row("jit(apply_update_batch)", 50.0, 12.0, 4.0, 2.0, 1.5, "hit", ("sync.dispatch", "ingest.apply", "integrate.xla_batch"),
         saved=26.5),
    _row("jit(encode_diff)", 130.0, 3.0, 1.0, 8.0, 0.0, "miss"),
]
TOTALS = {"trace_s": 15.75, "lower_s": 5.25, "backend_s": 11.0, "cache_load_s": 1.5, "saved_s": 26.5,
          "builds": 3, "cache_hits": 1, "cache_requests": 3}
# `trace_s` holds 0.25 s of a trace that built no program (`jax.eval_shape`): it stays in the total
STAGE = {"calls": 40, "compile_s": 21.0, "execute_s": 9.0, "trace_s": 12.0}
WANT = {"build_trace_s": 12.75, "build_lower_s": 4.25, "build_compile_s": 1.5, "build_cache_load_s": 1.5,
        "build_programs": 2.0, "build_cache_hit_pct": 50.0, "setup_dispatch_s": 26.0}


def _window(phases=None):
    return Window(rec=None, t_open=T_OPEN, t_close=T_OPEN + 22.0, setup_s=90.0, dispatch_spans=[],
                  phases=phases if phases is not None else {"sync.dispatch": {"calls": 30, "compile_s": 0.0, "execute_s": 4.0}})


@pytest.fixture
def program(monkeypatch, tmp_path):
    """A program that keeps the totals, the log and the recorder above."""
    from ytpu.utils.phases import phases

    monkeypatch.setattr(setup_parts, "program", lambda: (dict(TOTALS), [dict(r) for r in LOG]))
    monkeypatch.setattr(phases, "snapshot", lambda: {"sync.dispatch": dict(STAGE)})
    monkeypatch.setattr(setup_parts, "JOURNAL", str(tmp_path / ".bench_trace" / "build_journal.json"))
    return tmp_path


@pytest.fixture
def parent(monkeypatch):
    """The parent of PR 41: no totals, a recorder whose stages have no parts."""
    from ytpu import native
    from ytpu.utils.phases import phases

    monkeypatch.setattr(setup_parts, "program", lambda: None)
    monkeypatch.setattr(phases, "snapshot", lambda: {"sync.dispatch": {"calls": 40, "compile_s": 21.0, "execute_s": 9.0}})
    monkeypatch.delattr(native, "startup")


@pytest.mark.parametrize("name", sorted(WANT))
def test_a_reader_gives_the_totals_at_the_windows_opening(program, name):
    assert load_reader("layers", name).read(_window()) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT) + ["native_build_s"])
def test_a_reader_gives_none_on_a_program_without_the_totals(parent, name):
    assert load_reader("layers", name).read(_window()) is None


def test_the_program_itself_is_read_where_nothing_is_patched():
    """`setup_parts.program()` against the real module: `{}` totals before
    anything listened is None, totals after a build are a pair."""
    import jax
    import numpy as np

    from ytpu.utils.compile_cache import listen_to_builds

    listen_to_builds()
    jax.jit(lambda x: x * 3 + 1)(np.arange(17, dtype=np.float32))
    totals, log = setup_parts.program()
    assert totals["builds"] >= 1 and log[-1]["fun_name"] == "jit(<lambda>)"
    w = _window()
    w.t_open = log[-1]["t"] + 1.0  # everything so far lies before the window
    assert setup_parts.at_opening(w) == totals
    w.t_open = log[-1]["t"] - 1e-3  # the newest program lies inside it
    assert setup_parts.at_opening(w)["builds"] == totals["builds"] - 1


def test_native_build_s_reads_the_startup_record(monkeypatch):
    from ytpu import native

    monkeypatch.setattr(native, "startup", {"built": True, "build_s": 14.5, "load_s": 0.25})
    assert load_reader("layers", "native_build_s").read(_window()) == 14.75


def test_cache_hit_pct_is_none_where_the_cache_was_never_asked(monkeypatch):
    monkeypatch.setattr(setup_parts, "program", lambda: (dict(TOTALS, cache_hits=0, cache_requests=0), []))
    assert load_reader("layers", "build_cache_hit_pct").read(_window()) is None


def test_build_programs_writes_the_journal_and_the_tool_prints_it(program, capsys):
    assert load_reader("layers", "build_programs").read(_window()) == 2.0
    path = setup_parts.JOURNAL
    with open(path) as f:
        journal = json.load(f)
    assert journal["totals"] == TOTALS and journal["at_opening"]["builds"] == 2 and journal["setup_dispatch_s"] == 26.0
    assert [r["before_window"] for r in journal["programs"]] == [True, True, False]
    rows = dict(setup_by_program.by_part(journal))
    assert sum(rows.values()) == pytest.approx(journal["setup_s"])  # the table adds up to setup_s
    # the dispatches less the 18 s of builds that lie inside them; the start, whatever this process's was
    assert rows["served dispatches less their builds"] == pytest.approx(26.0 - (12.0 + 4.0 + 2.0))
    assert rows["compile (backend less cache load)"] == pytest.approx(1.5)
    assert setup_by_program.main([os.path.dirname(path), "--top", "1"]) == 0
    out = capsys.readouterr().out
    assert "2 programs built before the window, 1 of 2 cache requests hit; a cold cache would have cost +26.5 s" in out
    assert "jit(apply_update_batch)" in out and "jit(encode_diff)" not in out  # the check's program only with --all
    assert setup_by_program.main([path, "--all"]) == 0
    assert "jit(encode_diff)" in capsys.readouterr().out
    assert setup_by_program.main([os.path.join(os.path.dirname(path), "nothing")]) == 1


def test_build_programs_writes_nothing_on_the_parent(parent, tmp_path, monkeypatch):
    monkeypatch.setattr(setup_parts, "JOURNAL", str(tmp_path / "build_journal.json"))
    assert load_reader("layers", "build_programs").read(_window()) is None
    assert not os.path.exists(setup_parts.JOURNAL)


@pytest.mark.parametrize("name,stage,want", [
    ("roots_us.flood", "sync.receive.roots", 110.0),  # 0.0011 s over 10 frames, in us
    ("enqueue_wait_ms.flood", "sync.queue_wait", 0.11),  # the same, in ms
])
def test_the_two_window_readings(name, stage, want):
    read = load_reader("layers", name).read
    assert read(_window({stage: {"calls": 10, "execute_s": 0.0011}})) == pytest.approx(want)
    assert read(_window({stage: {"calls": 0, "execute_s": 0.0}})) is None
    assert read(_window({})) is None


def test_the_ten_entries_are_appended_and_name_all_four_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = [m["name"] for m in bench["per_layer"]]
    new = ["build_trace_s", "build_lower_s", "build_compile_s", "build_cache_load_s", "build_programs",
           "build_cache_hit_pct", "setup_dispatch_s", "native_build_s", "roots_us.flood", "enqueue_wait_ms.flood"]
    assert names[-10:] == new and len(set(names)) == len(names)
    for m in bench["per_layer"][-10:]:
        assert m["workloads"] == CELLS and set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["moves"] == ("updates_per_s" if m["name"].endswith(".flood") else "setup_s")
        assert m["better"] == ("higher" if m["name"] == "build_cache_hit_pct" else "lower")
        assert all(applies(m, c, {"updates_per_s", "setup_s"}) for c in CELLS)
        load_reader("layers", m["name"])  # every entry has its reader
    assert {m["layer"] for m in bench["per_layer"][-10:-2]} == {"compile", "start-up"}
