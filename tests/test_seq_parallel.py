"""Sequence parallelism: sp-sharded single-document sequence kernel.

Covers SURVEY.md §5.7 / §2's SP axis: contiguous chunk partitioning over an
8-device mesh, prefix-sum index routing, boundary-spanning deletes, and the
ppermute halo exchange that rebalances shard load. Oracle = plain Python
string splicing (the device path models the sequence kernel, not the wire).
"""

import random
import string

import jax
import numpy as np
import pytest

from ytpu.parallel.seq_shard import (
    HALO,
    apply_ops_sharded,
    build_op_stream,
    init_sharded,
    make_sp_mesh,
    read_text,
)

N_SHARDS = 8


@pytest.fixture(scope="module")
def mesh():
    if len(jax.devices()) < N_SHARDS:
        pytest.skip(f"needs {N_SHARDS} devices")
    return make_sp_mesh(N_SHARDS)


def oracle(ops):
    buf = []
    for tag, p, arg in ops:
        if tag == "i":
            for i, c in enumerate(str(arg)):
                buf.insert(p + i, c)
        else:
            del buf[p : p + arg]
    return "".join(buf)


def replay(ops, mesh, cap=512, rebalance_every=64):
    state = init_sharded(N_SHARDS, cap)
    state = apply_ops_sharded(state, build_op_stream(ops), mesh, rebalance_every)
    assert int(np.asarray(state.error).max()) == 0, "shard overflow"
    return state


def test_basic_insert_delete(mesh):
    ops = [
        ("i", 0, "hello world"),
        ("i", 5, ","),
        ("d", 0, 6),
        ("i", 0, "W"),
        ("d", 1, 1),
    ]
    state = replay(ops, mesh)
    assert read_text(state) == oracle(ops)


def test_random_ops_match_oracle(mesh):
    rng = random.Random(1234)
    ops, length = [], 0
    for _ in range(400):
        if length > 10 and rng.random() < 0.3:
            p = rng.randint(0, length - 1)
            n = rng.randint(1, min(10, length - p))
            ops.append(("d", p, n))
            length -= n
        else:
            w = "".join(
                rng.choice(string.ascii_lowercase)
                for _ in range(rng.randint(1, 40))  # >max_ins forces chunking
            )
            ops.append(("i", rng.randint(0, length), w))
            length += len(w)
    state = replay(ops, mesh, cap=2048)
    assert read_text(state) == oracle(ops)


def test_skewed_prepends_balance_via_halo_exchange(mesh):
    """All inserts land at position 0; without the ppermute halo exchange
    shard 0 would overflow (2400 chars > cap=512)."""
    ops = [("i", 0, "abcdefgh") for _ in range(300)]
    state = replay(ops, mesh, cap=512, rebalance_every=32)
    lengths = np.asarray(state.length)
    assert read_text(state) == oracle(ops)
    assert lengths.sum() == 2400
    # balanced within one halo step of the mean
    assert lengths.max() - lengths.min() <= HALO


def test_boundary_spanning_delete(mesh):
    """A delete covering several shards' intervals applies distributively."""
    # appends are a hot-shard workload: keep per-chunk inflow (8 ops x 30
    # chars) under the halo bandwidth (HALO=256 chars/step)
    ops = [("i", 30 * i, "x" * 30) for i in range(80)]  # 2400 chars
    state = replay(ops, mesh, cap=512, rebalance_every=8)
    total = int(np.asarray(state.length).sum())
    del_ops = [("d", 100, total - 200)]  # spans ~all interior shards
    full = ops + del_ops
    state = replay(full, mesh, cap=512, rebalance_every=8)
    got = read_text(state)
    assert got == oracle(full)
    assert len(got) == 200


def test_editing_trace_prefix(mesh):
    """Replay a real B4 editing-trace prefix when the asset is present."""
    try:
        from bench import TRACE_PATH, load_b4_ops

        ops = load_b4_ops(500)
    except (ImportError, FileNotFoundError, OSError):
        pytest.skip("B4 trace asset unavailable")
    state = replay(ops, mesh, cap=2048, rebalance_every=64)
    assert read_text(state) == oracle(ops)
