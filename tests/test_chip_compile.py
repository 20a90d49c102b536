"""Compile the served path's device programs for a described TPU v5e.

No chip is attached here; the TPU compiler is, and it refuses what the chip
would refuse (a kernel over its fast-memory limit, an unaligned slice, a
program that does not fit HBM). Nothing runs, so these say nothing about
results or times — `chip_smoke.py` on the chip does.

Shapes are the ones `chip_smoke.py` drives: 1,024 rooms x capacity 4,096,
and the decode / pack buckets its scenario produces (journal of a CPU run
of the full scenario, PR 24).

Rules (on-chip-measurement guide, section 2): the topology is described
inside the module-scoped fixture below and nowhere else; nothing here
touches libtpu while a module is imported; no child process; ONE file.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

N_DOCS, CAPACITY = 1024, 4096
N_CLIENTS = 2048  # the scenario's 2,048 preregistered session clients


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # and cannot be read back without the chip: keep the cache off here
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(tree, sharding):
    """ShapeDtypeStructs on the described chip for a tree of arrays/shapes."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), tree
    )


def _state(one_chip, n_docs=N_DOCS, capacity=CAPACITY):
    from ytpu.models.batch_doc import init_state

    return _shapes(jax.eval_shape(lambda: init_state(n_docs, capacity)), one_chip)


def _hbm_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (
        m.argument_size_in_bytes
        + m.output_size_in_bytes
        - m.alias_size_in_bytes
        + m.temp_size_in_bytes
    )


V5E_HBM = 16 * 1024**3


def _assert_updates_state_in_place(compiled, state, chips=1, copies_no_plane=True):
    """What a served form that donates its state compiles to: every
    buffer of the state aliased to its output (a chip's share of it, laid
    by room over `chips`), and no `copy` whose result is a whole plane a
    chip holds (`[1024, 4096]` at the benchmark's sizes). Undonated, the
    compact step held 26 such copies, 52 us each a step on the chip
    (PERF.md section 6, PR 46). `copies_no_plane=False` for a dense step
    over 256 rooms a chip, which changes every room and whose loops stage
    a chip's 4 MB plane through fast memory (`S(1)`): not the carry-over."""
    m = compiled.memory_analysis()
    held = sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in jax.tree.leaves(state))
    assert m.alias_size_in_bytes == held // chips, (m, held)
    if not copies_no_plane:
        return
    plane = f"[{state.start.shape[0] // chips},{CAPACITY}]"
    copies = [
        ln.strip()[:160]
        for ln in compiled.as_text().splitlines()
        if re.search(r"= \w+" + re.escape(plane) + r"\S* copy\(", ln)
    ]
    assert not copies, copies


def _pair(slots, rows, dels=None):
    """The `PackedBatch` that crosses the served step's program boundaries,
    as host arrays: `[slots, rows, 23]` and `[slots, dels, 4]`."""
    from ytpu.models.batch_doc import BatchEncoder

    return BatchEncoder().batch_packed([[]] * slots, [[]] * slots, rows, dels or rows)


def _lane_table(lanes):
    """The served step's lane table (`decode_kernel.LANE_*`), as a shape."""
    from ytpu.ops.decode_kernel import LANE_FIELDS

    return jax.ShapeDtypeStruct((LANE_FIELDS, lanes), jnp.int32)


def _manifest(lanes, wire, step_width):
    """A step's manifest (`ingest.pack_manifest`) over a wire arena of
    `wire` bytes, as a shape."""
    from ytpu.ops.decode_kernel import LANE_FIELDS

    return jax.ShapeDtypeStruct((wire + 4 * (LANE_FIELDS * lanes + step_width),), jnp.uint8)


@pytest.mark.parametrize("rows", [4, 512], ids=["tick_bucket", "prefill_bucket"])
def test_served_integrate_step_fits_one_v5e(one_chip, rows):
    """`apply_update_batch`'s served form (`apply_update_batch_in_place`:
    the state donated) as `flush_device` dispatches it over every slot: the 4-row / 4-delete bucket the scenario's updates land in, and
    the 512-row bucket of the benchmark's prefill; the batch the pair it
    is handed, taken apart inside the program."""
    from ytpu.models.batch_doc import _apply_update_batch_in_place_jit, scan_tier_plan

    state = _state(one_chip)
    compiled = _apply_update_batch_in_place_jit.lower(
        state,
        _shapes(_pair(N_DOCS, rows), one_chip),
        jax.ShapeDtypeStruct((N_CLIENTS,), jnp.int32, sharding=one_chip),
        scan_tier_plan(),
    ).compile()
    m = compiled.memory_analysis()
    print(f"dense step, {rows}-row bucket: temp bytes {m.temp_size_in_bytes}, argument bytes {m.argument_size_in_bytes}")
    # donated: the state is there once, plus temporaries
    _assert_updates_state_in_place(compiled, state)
    assert _hbm_bytes(compiled) < V5E_HBM // 2, m


def test_doc_sharded_integrate_step_compiles_for_four_chips(topo):
    """`chip_smoke.py --chips 4`: the state's doc axis over a 4-chip mesh,
    the update batch as the host hands it over (unsharded). The step must
    keep every output plane doc-sharded and never gather the state."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ytpu.models.batch_doc import (
        _apply_update_batch_in_place_jit,
        init_state,
        scan_tier_plan,
    )
    from ytpu.parallel.mesh import AXIS_BATCH

    mesh = Mesh(np.array(topo.devices), (AXIS_BATCH,))
    on = lambda a, spec: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=NamedSharding(mesh, spec)
    )
    by_doc = lambda a: on(a, P(AXIS_BATCH, *([None] * (a.ndim - 1))))
    state = jax.tree.map(by_doc, jax.eval_shape(lambda: init_state(N_DOCS, CAPACITY)))
    batch = _pair(N_DOCS, 4)
    compiled = _apply_update_batch_in_place_jit.lower(
        state,
        jax.tree.map(lambda a: on(a, P()), batch),
        on(jnp.zeros((N_CLIENTS,), jnp.int32), P()),
        scan_tier_plan(),
    ).compile()
    assert "all-gather" not in compiled.as_text()
    for out in jax.tree.leaves(compiled.output_shardings):
        assert out.spec[0] == AXIS_BATCH, out
    # per chip: a quarter of the state, in place, plus temporaries
    _assert_updates_state_in_place(compiled, state, chips=4, copies_no_plane=False)
    assert _hbm_bytes(compiled) < V5E_HBM // 4, compiled.memory_analysis()


def test_merge_output_feeds_the_doc_sharded_step_at_4096_rooms(topo):
    """`yws-rooms-4k-x4`, the dense step (the prefill's all-room
    dispatches): 4,096 rooms over four chips, 1,024 a chip. A doc-sharded
    ingestor uploads a step's inputs onto the mesh (`BatchIngestor._upload`):
    the host lane's `PackedBatch` by room, the decoded stream (a pair too)
    and the rank table whole on every chip. `merge_stream` must scatter
    into the two arrays where they lie (no collective, two arrays out, by
    room), and the step must take that output as it is, gather no state
    plane and leave every plane of the state where it was."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ytpu.models.batch_doc import (
        _apply_update_batch_in_place_jit,
        init_state,
        scan_tier_plan,
    )
    from ytpu.models.ingest import _merge_stream_jit
    from ytpu.parallel.mesh import AXIS_BATCH

    rooms, lanes, rows = 4 * N_DOCS, 8, 4
    mesh = Mesh(np.array(topo.devices), (AXIS_BATCH,))
    on = lambda spec: lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=NamedSharding(mesh, spec)
    )
    by_room, whole = on(P(AXIS_BATCH)), on(P())
    packed = _pair(rooms, rows)
    merge = _merge_stream_jit.lower(
        jax.tree.map(by_room, packed),
        jax.tree.map(whole, _pair(lanes, rows)),
        whole(_lane_table(lanes)),  # the gather's output: a device array
        width=64,
    ).compile()
    assert not re.search(r"all-(gather|reduce|to-all)|collective-permute", merge.as_text())
    assert {s.spec for s in jax.tree.leaves(merge.output_shardings)} == {P(AXIS_BATCH)}
    assert [(o.shape, o.dtype) for o in jax.tree.leaves(merge.out_info)] == [(a.shape, a.dtype) for a in packed]

    merged = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        merge.out_info,
        merge.output_shardings,
    )
    state = jax.tree.map(by_room, jax.eval_shape(lambda: init_state(rooms, CAPACITY)))
    step = _apply_update_batch_in_place_jit.lower(
        state,
        merged,
        whole(jnp.zeros((2 * N_CLIENTS,), jnp.int32)),
        scan_tier_plan(),
    ).compile()
    assert "all-gather" not in step.as_text()
    for out in jax.tree.leaves(step.output_shardings):
        assert out.spec[0] == AXIS_BATCH, out
    # per chip: 1,024 rooms of state, in place, plus temporaries
    _assert_updates_state_in_place(step, state, chips=4)
    assert _hbm_bytes(step) < V5E_HBM // 4, step.memory_analysis()


COMPACT_WIDTH = 16  # `BatchIngestor._active_slots`: a tick of at most 16 rooms


def test_compact_integrate_step_needs_a_fraction_of_the_dense_steps_memory(one_chip):
    """The step `apply_bytes` dispatches for a tick of at most 16 rooms:
    16 rooms gathered, integrated, scattered back. The state is donated,
    as in the dense step: every buffer of it is the output's and no plane
    is copied; the temporaries are those of 16 rooms, not of 1,024.
    Donated, the dense form holds 744,856,576 B of temporaries (696,401,920
    undonated) and no second state, 1.16 GB in all where it took 1.52; the
    compact form 7,972,352 B (14,032,896 undonated). Both are handed the
    pair and take the planes apart inside themselves."""
    from ytpu.models.batch_doc import _apply_update_batch_in_place_jit, scan_tier_plan

    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    # the batch is as wide as the step: every slot, or the tick's 16
    batch = {w: _shapes(_pair(w, 4), one_chip) for w in (N_DOCS, COMPACT_WIDTH)}
    rest = (i32(N_CLIENTS), scan_tier_plan())
    state = _state(one_chip)
    dense = _apply_update_batch_in_place_jit.lower(state, batch[N_DOCS], *rest).compile().memory_analysis()
    compact = _apply_update_batch_in_place_jit.lower(state, batch[COMPACT_WIDTH], *rest, i32(COMPACT_WIDTH)).compile()
    m = compact.memory_analysis()
    print(f"temp bytes: dense step {dense.temp_size_in_bytes}, compact step {m.temp_size_in_bytes}")
    assert dense.temp_size_in_bytes <= 1.01 * 744_856_576 and m.temp_size_in_bytes <= 1.01 * 7_972_352
    _assert_updates_state_in_place(compact, state)
    assert m.output_size_in_bytes == dense.output_size_in_bytes
    assert m.temp_size_in_bytes < dense.temp_size_in_bytes // 16, (m, dense)
    assert _hbm_bytes(compact) < V5E_HBM // 4, m


def test_doc_sharded_compact_step_moves_no_plane_between_chips(topo):
    """`yws-rooms-4k-x4`, a tick: 4,096 rooms by room over four chips, the
    host lane's `[16, ...]` `PackedBatch` whole on every chip (`_upload`),
    `merge_stream` over it and the decoded lanes' pair (nothing of it is laid by
    room, so the program may hold no collective, and its two arrays come
    out whole on every chip), the rank table whole on every chip, `active` the
    device array the manifest's gather handed back. The partitioner must answer the state's gather with each chip's own
    rooms and a sum of the `[16, ...]` pieces, never with a gathered plane,
    and scatter into the planes where they lie."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ytpu.models.batch_doc import (
        _apply_update_batch_in_place_jit,
        init_state,
        scan_tier_plan,
    )
    from ytpu.models.ingest import _gather_manifest_jit, _merge_stream_jit
    from ytpu.ops.decode_kernel import LANE_FIELDS
    from ytpu.parallel.mesh import AXIS_BATCH

    rooms, lanes = 4 * N_DOCS, 8
    mesh = Mesh(np.array(topo.devices), (AXIS_BATCH,))
    on = lambda spec: lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=NamedSharding(mesh, spec)
    )
    by_room, whole = on(P(AXIS_BATCH)), on(P())
    batch = _pair(COMPACT_WIDTH, 4)
    # the step's one upload, whole on every chip, taken apart where it lies:
    # the lane matrix, the lane table and `active` come out whole on every chip
    gather = _gather_manifest_jit.lower(
        whole(_manifest(lanes, 256, COMPACT_WIDTH)), lanes=lanes, width=64, step_width=COMPACT_WIDTH
    ).compile()
    assert not re.search(r"all-(gather|reduce|to-all)|collective-permute", gather.as_text())
    assert {s.spec for s in jax.tree.leaves(gather.output_shardings)} == {P()}
    assert [(o.shape, o.dtype) for o in jax.tree.leaves(gather.out_info)] == [
        ((lanes, 64), jnp.uint8), ((LANE_FIELDS, lanes), jnp.int32), ((COMPACT_WIDTH,), jnp.int32)
    ]
    merge = _merge_stream_jit.lower(
        jax.tree.map(whole, batch),
        jax.tree.map(whole, _pair(lanes, 4)),
        whole(_lane_table(lanes)),
        width=64,
    ).compile()
    assert not re.search(r"all-(gather|reduce|to-all)|collective-permute", merge.as_text())
    assert {s.spec for s in jax.tree.leaves(merge.output_shardings)} == {P()}
    assert [(o.shape, o.dtype) for o in jax.tree.leaves(merge.out_info)] == [(a.shape, a.dtype) for a in batch]
    state = jax.tree.map(by_room, jax.eval_shape(lambda: init_state(rooms, CAPACITY)))
    step = _apply_update_batch_in_place_jit.lower(
        state,
        jax.tree.map(whole, batch),
        whole(jnp.zeros((2 * N_CLIENTS,), jnp.int32)),
        scan_tier_plan(),
        whole(jnp.zeros((COMPACT_WIDTH,), jnp.int32)),  # the gather's third output
    ).compile()
    text = step.as_text()
    assert not re.search(r"all-(gather|to-all)|collective-permute|reduce-scatter", text)
    # what crosses: the gathered rooms' pieces, nothing as large as a plane
    # (a chip's share of one is [1024, 4096])
    crossing = [ln for ln in text.splitlines() if re.search(r"\ball-reduce(-start)?\(", ln)]
    assert crossing
    for ln in crossing:
        result = ln.split(" all-reduce", 1)[0]
        for dims in re.findall(r"\w+\[([\d,]+)\]", result):
            assert np.prod([int(d) for d in dims.split(",")]) <= COMPACT_WIDTH * CAPACITY, ln[:200]
    for out in jax.tree.leaves(step.output_shardings):
        assert out.spec[0] == AXIS_BATCH, out
    m = step.memory_analysis()
    _assert_updates_state_in_place(step, state, chips=4)
    assert _hbm_bytes(step) < V5E_HBM // 8, m


def _compact_rooms_operands(place):
    """`compact_rooms`' host operands as `BatchIngestor._compact` uploads
    them: the call's slots, their mask, the payload store's length."""
    from ytpu.models.ingest import COMPACT_ROOMS_PER_CALL as k

    return (
        place(jnp.zeros((k,), jnp.int32)),
        place(jnp.zeros((k,), bool)),
        place(jnp.zeros((), jnp.int32)),
    )


def test_served_compaction_fits_one_v5e(one_chip):
    """`compact_rooms` as `apply_bytes` enqueues it when a room nears its
    capacity (PR 43): two rooms gathered (the room and an idle slot behind
    a mask, or two rooms due at once), squashed, collected, defragmented
    and scattered back. Donated, as the integrate step: the scatter
    writes two rooms where they are and no plane is copied; its
    temporaries are those of two rooms, and the report the host re-homes
    strings from is `[2, 4096, 6]`."""
    from ytpu.ops.compaction import REHOME_FIELDS, compact_rooms

    place = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    state = _state(one_chip)
    compiled = compact_rooms.lower(state, *_compact_rooms_operands(place)).compile()
    m = compiled.memory_analysis()
    print(f"compact_rooms: temp bytes {m.temp_size_in_bytes}, output bytes {m.output_size_in_bytes}")
    _assert_updates_state_in_place(compiled, state)
    report = jax.tree.leaves(compiled.out_info)[-2]
    assert report.shape == (2, CAPACITY, len(REHOME_FIELDS))
    assert m.temp_size_in_bytes < V5E_HBM // 64, m
    assert _hbm_bytes(compiled) < V5E_HBM // 4, m


def test_doc_sharded_compaction_moves_no_plane_between_chips(topo):
    """`yws-rooms-4k-x4`'s server compacts a room the same way: the state
    by room over four chips, the operands whole on every chip. As in the
    compact integrate step, what crosses is the gathered rooms' pieces,
    and every plane stays where it lies."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ytpu.models.batch_doc import init_state
    from ytpu.ops.compaction import compact_rooms
    from ytpu.parallel.mesh import AXIS_BATCH

    mesh = Mesh(np.array(topo.devices), (AXIS_BATCH,))
    on = lambda spec: lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=NamedSharding(mesh, spec)
    )
    by_room, whole = on(P(AXIS_BATCH)), on(P())
    state = jax.tree.map(by_room, jax.eval_shape(lambda: init_state(4 * N_DOCS, CAPACITY)))
    compiled = compact_rooms.lower(state, *_compact_rooms_operands(whole)).compile()
    text = compiled.as_text()
    assert not re.search(r"all-(gather|to-all)|collective-permute|reduce-scatter", text)
    for ln in [ln for ln in text.splitlines() if re.search(r"\ball-reduce(-start)?\(", ln)]:
        result = ln.split(" all-reduce", 1)[0]
        for dims in re.findall(r"\w+\[([\d,]+)\]", result):
            assert np.prod([int(d) for d in dims.split(",")]) <= 2 * 32 * CAPACITY, ln[:200]  # two rooms' stacked planes
    for out in jax.tree.leaves(compiled.output_shardings[0]):
        assert out.spec[0] == AXIS_BATCH, out
    m = compiled.memory_analysis()
    _assert_updates_state_in_place(compiled, state, chips=4)
    assert _hbm_bytes(compiled) < V5E_HBM // 8, m


# the window's lane counts at the 4-row bucket, then the benchmark's prefill
# step: every slot a lane, 6.6 KB of wire a lane, the 512-row bucket
DECODE_SHAPES = [(1, 64, 4, 16, 2), (8, 64, 4, 16, 2), (8, 64, 4, 16, None), (N_DOCS, 8192, 512, 2048, 2)]


@pytest.mark.parametrize("lanes,width,rows,n_steps,max_sections", DECODE_SHAPES)
def test_served_decode_compiles(one_chip, lanes, width, rows, n_steps, max_sections):
    """`decode_updates_v1` over [S, L] wire lanes with all four lookup
    tables, as `_merge_fast_lane` calls it: the pair and the flags out,
    three buffers, the planes stacked inside the program. The chip lays
    the dense `[1024, 512, 23]` array out unpadded (the field axis
    outermost): 48 MB, not the 268 a 23-wide minor axis would pad to."""
    from ytpu.ops.decode_kernel import _decode_updates_v1_jit

    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    compiled = _decode_updates_v1_jit.lower(
        jax.ShapeDtypeStruct((lanes, width), jnp.uint8, sharding=one_chip),
        None,
        max_rows=rows,
        max_dels=rows,
        n_steps=n_steps,
        client_table=(i32(N_CLIENTS), i32(N_CLIENTS)),
        max_sections=max_sections,
        key_table=(i32(1), i32(1)),
        client_hash_table=(i32(0), i32(0)),
        packed=True,
        lane_table=_shapes(_lane_table(lanes), one_chip),
    ).compile()
    m = compiled.memory_analysis()
    print(f"decode {lanes} x {width}: temp bytes {m.temp_size_in_bytes}, output bytes {m.output_size_in_bytes}")
    assert m.temp_size_in_bytes < V5E_HBM // 16
    assert [o.shape for o in jax.tree.leaves(compiled.out_info)] == [(lanes, rows, 23), (lanes, rows, 4), (lanes,)]
    if lanes == N_DOCS:  # what the arrays hold, the flags, and the tiles' padding: no lane padding of the field axis
        assert m.output_size_in_bytes < 1.05 * 4 * lanes * (rows * 27 + 1)


# (lanes, rows = deletes bucket, lane width, the step's width): the decode
# cases' lane counts at the 4-row bucket, in a tick's 16-wide step, and the
# benchmark's prefill step — every slot a lane, the 512-row bucket, 6.6 KB
# of wire a lane, the dense step
MERGE_SHAPES = [(1, 4, 64, 16), (8, 4, 64, 16), (N_DOCS, 512, 8192, N_DOCS)]


@pytest.mark.parametrize("lanes,rows,width,slots", MERGE_SHAPES)
def test_served_merge_compiles(one_chip, lanes, rows, width, slots):
    """`merge_stream` (the rebase and the two arrays' scatters, one
    program) as `_merge_fast_lane` calls it: the host lane's `PackedBatch`
    over the step's `slots`, the decoder's over `lanes` of them."""
    from ytpu.models.ingest import _merge_stream_jit

    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    packed = _pair(slots, rows)
    compiled = _merge_stream_jit.lower(
        _shapes(packed, one_chip),
        _shapes(_pair(lanes, rows), one_chip),
        _shapes(_lane_table(lanes), one_chip),
        width=width,
    ).compile()
    m = compiled.memory_analysis()
    print(f"merge {lanes} lanes into {slots} x {rows}: temp bytes {m.temp_size_in_bytes}")
    assert m.temp_size_in_bytes < V5E_HBM // 16
    # two output buffers, the pair over the step's slots
    assert [o.shape for o in jax.tree.leaves(compiled.out_info)] == [a.shape for a in packed]


@pytest.mark.parametrize("slots,rows", [(16, 4), (16, 512), (N_DOCS, 4)], ids=["tick", "load", "every_slot"])
def test_served_unpack_compiles(one_chip, slots, rows):
    """`unpack_batch` as a program of its own, for a caller that reads
    planes (`BatchEncoder.batch_from_rows`, a test); no served step runs
    it since PR 42. At a tick's 16-wide batch and the 4-row bucket, the
    record cell's load (16 rooms, the 512-row bucket), and every slot: 27
    planes out, and no device memory but the arrays in and out."""
    from ytpu.models.batch_doc import UpdateBatch, unpack_batch_jit

    packed = _pair(slots, rows)
    compiled = unpack_batch_jit.lower(_shapes(packed, one_chip)).compile()
    out = jax.tree.leaves(compiled.out_info)
    assert len(out) == len(UpdateBatch._fields) == 27
    assert {o.shape for o in out} == {(slots, rows)}
    assert [o.dtype == jnp.bool_ for o in out] == [i in (22, 26) for i in range(27)]
    m = compiled.memory_analysis()
    # what goes in, what comes out, nothing between; the chip pads the tick's small planes
    assert m.temp_size_in_bytes == 0 and _hbm_bytes(compiled) < max(3 * sum(a.nbytes for a in packed), 1 << 17), m


@pytest.mark.parametrize("lanes,width", [(s, w) for s, _, w, _ in MERGE_SHAPES])
def test_served_gather_compiles(one_chip, lanes, width):
    """The step's first program (`gather_manifest_lanes`): the manifest in
    (a bucketed wire arena, the lane table and, in a tick, the step's 16
    `active` slots: one u8 array), the padded [S, L] lane matrix, the lane
    table and `active` out; the prefill's dense step has no `active`."""
    from ytpu.models.ingest import _bucket, _gather_manifest_jit
    from ytpu.ops.decode_kernel import LANE_FIELDS

    wire = _bucket(lanes * (width - 16) * 13 // 16, 256)  # lanes about 13/16 full
    step_width = 0 if lanes == N_DOCS else COMPACT_WIDTH
    compiled = _gather_manifest_jit.lower(
        _shapes(_manifest(lanes, wire, step_width), one_chip), lanes=lanes, width=width, step_width=step_width
    ).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < V5E_HBM // 16
    assert [(o.shape, o.dtype) for o in jax.tree.leaves(compiled.out_info)] == [
        ((lanes, width), jnp.uint8), ((LANE_FIELDS, lanes), jnp.int32)
    ] + ([((step_width,), jnp.int32)] if step_width else [])


def test_served_diff_selection_compiles(one_chip):
    from ytpu.models.batch_doc import _encode_diff_batch_jit

    compiled = _encode_diff_batch_jit.lower(
        _state(one_chip),
        jax.ShapeDtypeStruct((N_DOCS, N_CLIENTS), jnp.int32, sharding=one_chip),
        N_CLIENTS,
    ).compile()
    assert _hbm_bytes(compiled) < V5E_HBM // 2, compiled.memory_analysis()


@pytest.mark.parametrize("sub,rows", [(1, 8), (1, 4096), (512, 4096)])
def test_served_diff_pack_compiles_donated(one_chip, sub, rows):
    """The DONATED `compact_finisher_rows`: `_donation_usable()` is false on
    the CPU, so no CPU test has ever taken this variant."""
    from ytpu.models.batch_doc import _compact_rows_donated

    state = _state(one_chip)
    plane = lambda dt: jax.ShapeDtypeStruct((N_DOCS, CAPACITY), dt, sharding=one_chip)
    compiled = _compact_rows_donated.lower(
        state.blocks,
        plane(jnp.bool_),
        plane(jnp.int32),
        plane(jnp.bool_),
        jax.ShapeDtypeStruct((sub,), jnp.int32, sharding=one_chip),
        rows,
    ).compile()
    out = compiled.memory_analysis().output_size_in_bytes
    assert out >= sub * 15 * rows * 4


def test_batch_compaction_compiles_donated(one_chip):
    """`compact_state` (donated, every slot at once): not on the served
    path, which compacts the rooms that are due (`compact_rooms`, above);
    tests are its callers."""
    from ytpu.ops.compaction import _compact_state_jit

    compiled = _compact_state_jit.lower(_state(one_chip)).compile()
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes > 0, m  # the donation took
    assert _hbm_bytes(compiled) < V5E_HBM // 2, m
