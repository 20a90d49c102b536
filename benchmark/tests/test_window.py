"""The two rules on the window's length (`benchmark/window.py`, no jax): the
traced slice opens by where the window will end, not by the clock alone, and
a window its pool closed in under two slices is refused. And `run_window`
hands `on_tick` the share of a pool that does not repeat, rising to 1.0."""

import sys

import pytest

from benchmark import serve, window
from benchmark.ops import Op, Plan

POOL, TICK, SLICE = 12288, 16, window.TRACE_SLICE_S


def _plan(n_ops, saturated=True, repeat=False, due=0.0):
    ops = [Op("update", i % 7, i % 5, b"", due=due) for i in range(n_ops)]
    return Plan(clients=[], session_rooms=[], preload=[], warm=[], warm_session_rooms=[], ops=ops,
                saturated=saturated, repeat=repeat, tick_max_frames=TICK)


class FakeLoop(serve.ServerLoop):
    """`run_window` over a tick that only counts, on a clock the tick moves:
    every update takes `1 / rate` seconds."""

    def __init__(self, plan, rate, monkeypatch=None):
        self.plan, self.sessions, self.rate = plan, {}, rate
        self.clock, self.handed = 100.0, 0
        if monkeypatch:  # else the real clock, which the open loop's feeder sleeps on
            monkeypatch.setattr(serve, "now", lambda: self.clock)

    def tick(self, ops, table, dues=None, handeds=None, count=True):
        self.handed += len(ops)
        self.clock += len(ops) / self.rate


def _drive(rate, seconds, monkeypatch, pool=POOL, repeat=False, slice_s=SLICE):
    """The window at a steady rate: (elapsed at which the slice opened, ticks
    after it, the shares `on_tick` saw, the window's length)."""
    loop = FakeLoop(_plan(pool, repeat=repeat), rate, monkeypatch)
    seen, opened = [], []

    def on_tick(elapsed, share):
        seen.append(share)
        if not opened and window.slice_opens(elapsed, share, seconds, slice_s):
            opened.append((elapsed, len(seen)))

    t_open, t_close = loop.run_window(seconds, on_tick)
    at, tick_no = opened[0] if opened else (None, len(seen))
    return at, len(seen) - tick_no, seen, t_close - t_open


# rate, --seconds, where the slice opens, who closes the window and when
CASES = [
    (349.29, 30.0, 26.0, "seconds", 30.0),  # yws-rooms-1k.edit-flood (ledger, PR 35): as before this rule
    (226.02, 30.0, 26.0, "seconds", 30.0),  # yws-rooms-4k-x4.edit-flood
    (299.94, 30.0, 26.0, "seconds", 30.0),  # yws-rooms-1k-unregistered.author-flood
    (405.0, 30.0, 26.0, "seconds", 30.0),  # just under 409.6, the last rate at which the pool outlasts the window
    (472.6, 30.0, 22.0, "pool", 26.0),  # the parent's profiler never started from here on
    (520.0, 30.0, 19.63, "pool", 23.63),  # Speed 1(a) on one chip
    (1000.0, 30.0, 8.29, "pool", 12.29),
    (349.29, 40.0, 31.18, "pool", 35.18),  # the fault shown on today's tree: --seconds 40
]


@pytest.mark.parametrize("rate,seconds,opens,closer,closes", CASES)
def test_slice_opens_by_where_the_window_will_end(rate, seconds, opens, closer, closes, monkeypatch):
    at, ticks_after, seen, window_s = _drive(rate, seconds, monkeypatch)
    a_tick = TICK / rate
    assert opens <= at + 0.01 and at <= opens + a_tick + 0.01  # the first tick past the rule's instant
    assert window_s == pytest.approx(closes, abs=a_tick + 0.01)
    assert window.closed_by_pool(seen[-1], window_s, seconds) is (closer == "pool")
    assert (window_s - at) == pytest.approx(SLICE, abs=2 * a_tick + 0.01)  # the slice is the last 4 s either way
    assert not window.too_short_to_read(closer == "pool", window_s)


def test_the_clock_alone_would_have_missed_a_fast_window(monkeypatch):
    """The parent's rule, `elapsed >= seconds - slice_s`: never true once the
    pool drains before the 26th second."""
    _, _, _, window_s = _drive(520.0, 30.0, monkeypatch)
    assert window_s < 30.0 - SLICE


@pytest.mark.parametrize("rate", [226.02, 349.29, 520.0, 5000.0])
def test_a_plan_that_repeats_or_is_open_loop_opens_by_the_clock(rate, monkeypatch):
    at, _, seen, window_s = _drive(rate, 30.0, monkeypatch, repeat=True)
    assert set(seen) == {0.0} and window_s >= 30.0
    assert 26.0 <= at <= 26.0 + TICK / rate + 1e-9
    for elapsed in (0.0, 10.0, 25.99):
        assert not window.slice_opens(elapsed, 0.0, 30.0, SLICE)
    assert window.slice_opens(26.0, 0.0, 30.0, SLICE)


def test_no_projection_before_a_tenth_of_the_pool():
    # 9% of the pool in 0.4 s would end the window at 4.4 s: nothing is projected from so few ticks
    assert window.projected_end(0.4, 0.09, 30.0) == 30.0
    assert not window.slice_opens(0.4, 0.09, 30.0, SLICE)
    assert window.projected_end(0.4, 0.1, 30.0) == pytest.approx(4.0)
    assert window.slice_opens(0.4, 0.1, 30.0, SLICE)
    assert window.PROJECT_FROM_SHARE * POOL / 349.29 == pytest.approx(3.5, abs=0.05)  # 3.5 s today


def test_a_stall_early_in_the_window_only_delays_the_projection():
    # 10 s lost in the first tenth: the pool looks slower than it is, the clock's rule still holds at 26 s
    assert window.projected_end(13.5, 0.1, 30.0) == 30.0
    assert not window.slice_opens(25.9, 0.6, 30.0, SLICE) and window.slice_opens(26.0, 0.6, 30.0, SLICE)


@pytest.mark.parametrize("a_tick", [0.005, 0.02, 0.04, 0.08, 0.2, 0.3])
def test_the_rehearsals_pool_opens_its_slice_before_its_last_tick(a_tick, monkeypatch):
    """`--rehearse --seconds 0.5 --trace 1`: 72 ops, ticks of 16, a slice of
    0.25 s. On this sandbox the pool drains in 0.2-0.4 s (the parent never
    saw 0.25 s: `test_traced_rehearsal_reads_what_the_cpu_can_give`)."""
    at, ticks_after, seen, _ = _drive(TICK / a_tick, 0.5, monkeypatch, pool=72, slice_s=0.25)
    assert at is not None and ticks_after >= 1
    assert seen == pytest.approx([16 / 72, 32 / 72, 48 / 72, 64 / 72, 1.0][: len(seen)])


def test_run_window_hands_on_tick_a_share_that_rises_to_one(monkeypatch):
    loop = FakeLoop(_plan(100), 1000.0, monkeypatch)
    seen = []
    t_open, t_close = loop.run_window(30.0, lambda elapsed, share: seen.append((elapsed, share)))
    assert [s for _, s in seen] == pytest.approx([0.16, 0.32, 0.48, 0.64, 0.80, 0.96, 1.0])
    assert [e for e, _ in seen] == pytest.approx([0.016 * k for k in range(1, 7)] + [0.1])
    assert loop.handed == 100 and t_close - t_open == pytest.approx(0.1)


def test_run_window_open_loop_hands_no_share():
    """The feeder thread and the real clock: every op due at once."""
    loop = FakeLoop(_plan(40, saturated=False), 1e6)
    seen = []
    loop.run_window(0.2, lambda elapsed, share: seen.append(share))
    assert loop.handed == 40 and seen and set(seen) == {0.0}


@pytest.mark.parametrize("by_pool,window_s,refused", [
    (True, 7.99, True),  # 12,288 in under 8 s: past 1,536 updates/s
    (True, 8.0, False),
    (True, 23.63, False),  # 520 updates/s
    (False, 0.5, False),  # `--seconds` closed it: the caller asked for a short window
    (False, 30.0, False),
])
def test_a_window_the_pool_closed_in_under_two_slices_is_refused(by_pool, window_s, refused):
    assert window.too_short_to_read(by_pool, window_s) is refused
    assert POOL / window.MIN_POOL_WINDOW_S == 1536.0


def test_closed_by_pool_needs_the_whole_pool_and_time_to_spare():
    assert window.closed_by_pool(1.0, 23.6, 30.0)
    assert not window.closed_by_pool(0.85, 30.0, 30.0)  # today's windows
    assert not window.closed_by_pool(1.0, 30.004, 30.0)  # the last tick ran past `--seconds`: the clock closed it
    assert not window.closed_by_pool(0.0, 12.0, 30.0)  # a plan that repeats never drains


def test_the_rules_need_no_jax():
    import subprocess

    code = "import sys; from benchmark import window; sys.exit(int('jax' in sys.modules or 'numpy' in sys.modules))"
    assert subprocess.run([sys.executable, "-c", code], cwd=serve.__file__.rsplit("/", 2)[0]).returncode == 0
