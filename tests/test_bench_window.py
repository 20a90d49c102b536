"""The benchmark's two rules on the window's length, in tier-1.

`benchmark/window.py` (pure functions, no jax; `benchmark/tests/test_window.py`
drives them through `run_window`, which the tier-1 command does not collect):
a pool of 12,288 updates that does not repeat closes the window at
`min(run_seconds, pool / rate)`, the traced slice opens in the last 4 s of the
window as it will end, and a window the pool closed in under two slices is
refused. ISSUE-37 is the first change to lean on them: past 409.6 updates/s
the pool, not the clock, closes the 30 s window.
"""

import pytest

from benchmark import window

POOL, SECONDS, TICK = 12288, 30.0, 16


def _window(rate):
    """A flood window at a steady `rate` (updates/s), a tick of 16 updates at
    a time: (when the slice opened, when the window closed, who closed it)."""
    opened, elapsed, handed = None, 0.0, 0
    while handed < POOL and elapsed < SECONDS:
        handed += TICK
        elapsed += TICK / rate
        if opened is None and window.slice_opens(elapsed, handed / POOL, SECONDS, window.TRACE_SLICE_S):
            opened = elapsed
    return opened, elapsed, window.closed_by_pool(handed / POOL, elapsed, SECONDS)


# the rates ISSUE-37 starts from and expects: `yws-rooms-1k.edit-flood` before
# and after, `yws-rooms-4k-x4.edit-flood` and `...-unregistered.author-flood` after
@pytest.mark.parametrize("rate", [345.0, 520.0, 420.0, 435.0])
def test_the_window_is_the_shorter_of_the_clock_and_the_pool(rate):
    opened, closed, by_pool = _window(rate)
    want = min(SECONDS, POOL / rate)
    assert closed == pytest.approx(want, abs=TICK / rate)
    assert by_pool == (POOL / rate < SECONDS)
    # the slice opens at the first tick inside the window's last 4 s
    assert opened is not None
    assert want - window.TRACE_SLICE_S <= opened + 1e-9
    assert opened <= want - window.TRACE_SLICE_S + 2 * TICK / rate
    assert not window.too_short_to_read(by_pool, closed)
    assert window.projected_end(closed, 1.0 if by_pool else closed * rate / POOL, SECONDS) == pytest.approx(
        want, abs=TICK / rate
    )


@pytest.mark.parametrize("rate, refused", [(1535.0, False), (1536.0, False), (1537.0, True), (3000.0, True)])
def test_a_pool_drained_in_under_two_slices_is_refused(rate, refused):
    # 12,288 / 8 s = 1,536 updates/s: the first rate past it leaves no window to read
    _, closed, by_pool = _window(rate)
    assert by_pool and closed == pytest.approx(POOL / rate)
    assert window.too_short_to_read(by_pool, POOL / rate) == refused
    assert window.EXIT_TOO_SHORT == 5


def test_the_first_tenth_of_a_pool_projects_nothing():
    assert window.projected_end(1.0, 0.09, SECONDS) == SECONDS
    assert window.projected_end(2.36, 0.1, SECONDS) == pytest.approx(23.6)
    assert not window.slice_opens(1.0, 0.09, SECONDS, window.TRACE_SLICE_S)
