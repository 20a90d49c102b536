"""Full-trace fused replay (ytpu/models/replay.py): chunked device decode +
fused integrate + packed compaction + capacity growth, vs the host oracle.

Runs in Pallas interpret mode on the CPU mesh; small capacities force the
compaction/growth machinery to fire many times mid-replay.
"""

import random

import numpy as np
import pytest

from ytpu.core import Doc

needs_native = pytest.mark.usefixtures("native_lib")


from _fused_interpret import run_or_skip as _interpret_or_skip


def run_or_skip(rep, log):
    """Drive a FusedReplay, SKIPPING when this container's jax cannot
    interpret Pallas TPU kernels (NotImplementedError from the
    interpreter — environmental, present at seed). The kernel is compiled
    for a v5e by tests/test_chip_compile.py and not run on the chip. The skip is
    memoized across files (tests/_fused_interpret.py) so only the first
    fused interpret test in the session pays the kernel trace."""
    return _interpret_or_skip(lambda: rep.run(log))


def _edit_log(ops, client_id=1):
    doc = Doc(client_id=client_id)
    log = []
    doc.observe_update_v1(lambda p, o, t: log.append(p))
    txt = doc.get_text("text")
    for tag, pos, arg in ops:
        with doc.transact() as txn:
            if tag == "i":
                txt.insert(txn, pos, arg)
            else:
                txt.remove_range(txn, pos, arg)
    return log, txt.get_string()


def _fuzz_ops(n, seed, alphabet="abcdefg π🙂"):
    rng = random.Random(seed)
    ops = []
    length = 0
    for _ in range(n):
        if length > 5 and rng.random() < 0.3:
            pos = rng.randint(0, length - 2)
            k = rng.randint(1, min(4, length - pos))
            ops.append(("d", pos, k))
            length -= k
        else:
            word = "".join(rng.choice(alphabet) for _ in range(rng.randint(1, 4)))
            ops.append(("i", rng.randint(0, length), word))
            length += len(word)
    return ops


@needs_native
def test_replay_with_compaction_and_growth():
    from ytpu.models.replay import FusedReplay, plan_replay

    log, expect = _edit_log(_fuzz_ops(400, seed=3))
    plan = plan_replay(log)
    rep = FusedReplay(
        n_docs=8,
        plan=plan,
        capacity=128,  # tiny: forces many compactions + growth
        max_capacity=4096,
        d_block=8,
        chunk=64,
        interpret=True,
    )
    stats = run_or_skip(rep, log)
    assert stats.compactions >= 1, "compaction never fired"
    assert rep.get_string(0) == expect
    assert rep.get_string(7) == expect


@needs_native
def test_sequential_typing_squashes_to_few_blocks():
    """Unit-addressed refs make cross-update typing runs mergeable: a pure
    append stream must collapse to a handful of blocks, not one per
    keystroke (try_squash parity, block.rs:775-799)."""
    from ytpu.models.replay import FusedReplay, plan_replay

    ops = [("i", i, "abcdefgh"[i % 8]) for i in range(300)]
    log, expect = _edit_log(ops)
    plan = plan_replay(log)
    rep = FusedReplay(
        n_docs=8,
        plan=plan,
        capacity=128,
        max_capacity=1024,
        d_block=8,
        chunk=64,
        interpret=True,
    )
    stats = run_or_skip(rep, log)
    assert rep.get_string(0) == expect
    # all 300 keystrokes (one block each on arrival) must collapse into a
    # handful of runs once a commit-style compaction has seen them
    assert rep.compact() <= 4, stats
    assert rep.get_string(0) == expect


@needs_native
def test_replay_matches_b4_prefix():
    import bench
    from ytpu.models.replay import FusedReplay, plan_replay

    try:
        ops = bench.load_b4_ops(800)
    except FileNotFoundError:
        ops = bench.synthetic_ops(800)
    log, expect = bench.build_updates(ops)
    plan = plan_replay(log)
    rep = FusedReplay(
        n_docs=8,
        plan=plan,
        capacity=256,
        max_capacity=8192,
        d_block=8,
        chunk=128,
        interpret=True,
    )
    stats = run_or_skip(rep, log)
    assert rep.get_string(0) == expect
    assert rep.get_string(7) == expect
    assert stats.chunks == (len(log) + 127) // 128


@needs_native
def test_unit_arena_view_surrogate_halves():
    from ytpu.models.replay import UnitArenaView

    # arena: "a🙂b" -> units: a=1, 🙂=2, b=1 (4 units total)
    arena = "a🙂b".encode("utf-8")
    unit_byte = np.array([0, 1, 1, 5, len(arena)], dtype=np.int64)
    v = UnitArenaView(unit_byte, arena)
    assert v.slice_text(0, 0, 4) == "a🙂b"
    assert v.slice_text(0, 0, 2) == "a�"  # cuts the pair
    assert v.slice_text(0, 2, 2) == "�b"  # starts at the second half
    assert v.slice_text(1, 0, 2) == "🙂"
    assert v.slice_text(0, 1, 2) == "🙂"


def test_xla_lane_replay_parity():
    """The un-fused XLA replay lane (bench fallback when Mosaic cannot
    compile the Pallas kernel on real hardware) must render the same text
    as the host oracle through compaction and growth."""
    import bench as _bench
    from ytpu.models.replay import FusedReplay, plan_replay

    ops = _bench.synthetic_ops(300, seed=13)
    log, expect = _bench.build_updates(ops)
    rep = FusedReplay(
        n_docs=8,
        plan=plan_replay(log),
        capacity=512,
        max_capacity=4096,
        d_block=4,
        chunk=64,
        lane="xla",
    )
    rep.run(log)
    assert rep.get_string(0) == expect
    assert rep.get_string(7) == expect
