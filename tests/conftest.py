"""Test configuration: force an 8-device virtual CPU mesh before JAX loads.

The tests exercise multi-chip sharding on a virtual CPU mesh
(xla_force_host_platform_device_count). The chip itself is covered by
`chip_smoke.py`; `tests/test_chip_compile.py` compiles the main path's
programs for a described v5e.
"""

import os
import sys

# The tests are CPU-only by design: pin the platform in the environment
# before jax is imported.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

# Belt and braces: even if something imported jax before us, pin cpu.
try:
    jax.config.update("jax_platforms", "cpu")
except Exception:
    pass

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


import pytest  # noqa: F401 — fixtures may be added below

# Round-4 root cause of the CPU-backend segfault (upstream repro): XLA:CPU
# executables are JIT-compiled into one LLVM memory arena per process;
# after many LARGE programs accumulate (each distinct decode/apply shape
# is one), the arena's allocator fails — "LLVM compilation error: Cannot
# allocate memory" (execution_engine.cc) — and the failure is mishandled
# into a SIGSEGV inside `backend_compile_and_load` (deterministically
# ~110 tests in; faulthandler stack captured in round 4; a 650-distinct-
# SMALL-program repro does NOT crash, so program SIZE is load-bearing).
#
# Round 5 retires the conftest-level `jax.clear_caches()` workaround
# (which doubled suite wall time and fixed nothing for real servers):
# the library now bounds its OWN live program set — the big jitted entry
# points register with `ytpu.utils.progbudget`, whose per-function
# eviction (`fn.clear_cache()` on the largest holders) keeps the LLVM
# arena bounded from inside the serving paths. No test fixture needed.


def _mapping_headroom_spent() -> bool:
    """Has this process used most of `vm.max_map_count`?"""
    try:
        with open("/proc/sys/vm/max_map_count") as f:
            limit = int(f.read())
        with open("/proc/self/maps") as f:
            used = sum(1 for _ in f)
    except (OSError, ValueError):
        return False
    return used > 0.6 * limit


@pytest.fixture(scope="module", autouse=True)
def _drop_compiled_programs_before_the_map_limit():
    """What the "Cannot allocate memory" abort above runs into is the
    kernel's per-process mapping limit (`vm.max_map_count`, 65,530 here):
    every compiled XLA:CPU program holds a few mappings, one test module
    leaves ~6,000 behind (measured, PR 24), and an xdist worker that is
    handed a dozen such modules aborts inside `backend_compile_and_load` —
    after which the run hangs until its time limit. `jax.clear_caches()`
    gives the mappings back (5,983 -> 678 after one module), so do that
    between modules, but only once a worker is past 60% of the limit: the
    usual worker never pays the recompiles."""
    yield
    if _mapping_headroom_spent():
        jax.clear_caches()


def pytest_configure(config):
    # the tier-1 gate runs `-m 'not slow'`; register the marker so slow
    # smoke tests (bench exporter guard) don't warn as unknown
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 gate (-m 'not slow')"
    )


@pytest.fixture(scope="session")
def native_lib():
    """The native library, built on first use; skips where it cannot be.
    A fixture and not an import-time `skipif`, so that collecting a test
    file never starts a compiler."""
    from ytpu import native

    lib = native.load()
    if lib is None:
        pytest.skip("native library unavailable (no g++?)")
    return lib
