"""Shared memoized skip for fused-lane interpret-mode tests.

This container's jax interprets a trivial `pallas_call` fine but raises
NotImplementedError on a primitive the fused integrate kernel uses (seed
behavior), so the breakage cannot be
probed cheaply up front: every test that tries pays the full multi-second
kernel trace before the error surfaces.  The failure is environmental
(per jax build, not per shape), so the FIRST failure is remembered and
every later fused interpret test skips instantly — on a jax whose
interpreter can run the kernel, nothing here triggers and the tests run
in full.  The kernel is compiled for a v5e by tests/test_chip_compile.py;
it has not been run on the current machine.
"""

import pytest

_unavailable = None


def _raised_inside_jax(e: BaseException) -> bool:
    """True when the raising frame lives in jax itself (the interpreter's
    own NotImplementedError, e.g. jax/_src/state/discharge.py) — a
    NotImplementedError raised from ytpu code is a real failure and must
    not be memoized into an environment-wide skip."""
    tb, last = e.__traceback__, None
    while tb is not None:
        last = tb.tb_frame.f_code.co_filename
        tb = tb.tb_next
    return last is not None and "/jax/" in last.replace("\\", "/")


def run_or_skip(thunk):
    """Call ``thunk()``, SKIPPING (never failing) when interpret-mode
    Pallas cannot run the fused kernel in this jax build."""
    global _unavailable
    if _unavailable is not None:
        pytest.skip(_unavailable)
    try:
        return thunk()
    except NotImplementedError as e:
        if not _raised_inside_jax(e):
            raise
        _unavailable = f"interpret-mode Pallas unavailable in this jax: {e}"
        pytest.skip(_unavailable)
