#!/usr/bin/env python
"""Doc-axis ceiling probe (ISSUE-18): where does the doc axis hit the
memory budget?

ROADMAP item 1 is a MEMORY story — the 1024-doc integrate shapes kill
the TPU worker — but until now the repo had no instrument that maps the
doc axis to device bytes. This sweep is that instrument, and it is
**compile-only**: every point AOT-lowers the capacity programs against
`jax.ShapeDtypeStruct` specs and reads `compiled.memory_analysis()`, so
a pow2 64→2048 doc sweep runs on a CPU dry-run without materializing a
single giant array.

Per point (docs = 64, 128, ..., 2048 at a fixed slot capacity):

- **grow transient** — `grow_packed` lowered at ``capacity → 2 *
  capacity``: arguments (old state) + outputs (new state) + temps, the
  exact allocation `PackedReplayDriver.ensure_room` asks the device for
  when the watermark trips, and the denial the typed `GrowOomError`
  reports. This is the curve the ceiling is read from.
- **compact program** — `compact_packed` at the same shape: the
  temp-heavy steady-state program that must also fit.
- **analytic model** — `packed_state_bytes(D, C) +
  packed_state_bytes(D, 2C)`: the formula `ytpu.utils.capacity` scores
  headroom with. The sweep feeds every MEASURED grow transient into a
  `HeadroomForecaster` and reports the model's worst relative error —
  forecaster math vs `memory_analysis()` truth stays an assertable
  delta, not vibes.
- **lane ladder** — the sticky `lane_health` floor for the point's
  shape family. On hosts without Mosaic the fused lane is reported as
  not probed (``fused_probed: false``), never silently "healthy".

The **ceiling** is the first docs whose grow transient exceeds the
budget (``YTPU_DOC_CEILING_BUDGET_BYTES``, else the observatory's
`memory_budget_bytes()`); ``doc_ceiling`` is the last surviving docs
count. The committed artifact (`doc_ceiling_pr18.json`) pins a 768-doc
-equivalent budget so the curve crosses inside the swept range and the
artifact NAMES the first failing family — the 1024-doc shapes, matching
the ROADMAP's observed TPU ceiling.

The ``--sub-batch`` leg (ISSUE-20) reruns the sweep with each point's
grow/compact programs lowered at the `plan_subbatches` width instead of
the full doc axis — the per-dispatch transient the sub-batched
`PackedReplayDriver` actually allocates. Under the same pinned PR-18
budget the curve then clears 1024/2048 (and the whole extended axis):
the committed `doc_ceiling_pr20.json` artifact pins that push. The leg
also measures throughput vs ``n_sub`` (`sub_batch_scaling`) on a real
CPU replay, so the doc-axis sharding path has a trendable speedup axis.

Standalone::

    JAX_PLATFORMS=cpu python benches/doc_ceiling.py [--sub-batch] [out.json]

`bench.py --dry-run` runs the same sweep as its ``doc_ceiling`` leg and
lifts ``doc_ceiling`` / ``memory_peak_bytes`` /
``capacity_headroom_fraction`` into the one-line JSON.
"""

from __future__ import annotations

import json
import os
import sys
import time

__all__ = ["doc_ceiling_sweep", "sub_batch_scaling", "main"]

#: the swept doc axis: pow2 64 → 8192 (ISSUE-20 extended it past the
#: flagship 2048-doc config4 shape into the 10k north-star's
#: neighborhood; 1024 is ROADMAP item 1's observed killer)
DOCS_AXIS = (64, 128, 256, 512, 1024, 2048, 4096, 8192)

#: slot capacity every point sweeps at — deliberately fixed so the doc
#: axis is the only variable in the curve
DEFAULT_CAPACITY = 512

#: kernel tiling for the lane-family key (matches the flagship d_block)
DEFAULT_D_BLOCK = 8


def _resident(kinds: dict) -> int:
    """The observatory's resident-bytes convention: arguments + outputs
    − donated alias overlap + temps (generated code reported separately)."""
    return (
        kinds["argument_bytes"]
        + kinds["output_bytes"]
        - kinds["alias_bytes"]
        + kinds["temp_bytes"]
    )


def doc_ceiling_sweep(
    docs_axis=DOCS_AXIS,
    capacity: int | None = None,
    budget_bytes: int | None = None,
    d_block: int = DEFAULT_D_BLOCK,
    sub_batch: bool = False,
) -> dict:
    """Run the compile-only sweep; returns the artifact dict.

    ``sub_batch=True`` (ISSUE-20) lowers each point's grow/compact
    programs at its `plan_subbatches` width instead of the full doc
    axis — the transient ONE sub-batched dispatch actually allocates —
    so the curve measures what the sharded driver pays per slice while
    the doc axis keeps growing."""
    import jax
    import jax.numpy as jnp

    from ytpu.models.replay import plan_subbatches
    from ytpu.ops.compaction import _compact_packed_jit, grow_packed
    from ytpu.ops.integrate_kernel import (
        M_PAD,
        NC,
        effective_lane,
        lane_family,
        lane_health,
        packed_state_bytes,
    )
    from ytpu.utils.capacity import HeadroomForecaster, memory_budget_bytes
    from ytpu.utils.phases import program_memory

    capacity = int(
        capacity
        if capacity is not None
        else os.environ.get("YTPU_DOC_CEILING_CAPACITY", DEFAULT_CAPACITY)
    )
    if budget_bytes is None:
        env = os.environ.get("YTPU_DOC_CEILING_BUDGET_BYTES")
        budget_bytes = int(env) if env else memory_budget_bytes()
    budget_bytes = int(budget_bytes)

    # the fused Pallas lane needs Mosaic — on a host backend the sweep
    # reports it unprobed rather than pretending the rung is healthy
    fused_probed = jax.default_backend() not in ("cpu",)

    grow_jit = jax.jit(grow_packed, static_argnums=(2,))
    fc = HeadroomForecaster(budget_bytes=budget_bytes)
    points = []
    first_failing = None
    prev_resident = -1
    monotone = True
    for docs in docs_axis:
        # sub-batch leg (ISSUE-20): the programs lower at the planned
        # pow2 slice width — the per-dispatch working set — while the
        # point still reports the full doc axis
        if sub_batch:
            plan = plan_subbatches(
                int(docs), capacity, d_block=d_block,
                budget_bytes=budget_bytes,
            )
            model_docs = plan.width
        else:
            model_docs = int(docs)
        cols = jax.ShapeDtypeStruct((NC, model_docs, capacity), jnp.int32)
        meta = jax.ShapeDtypeStruct((model_docs, M_PAD), jnp.int32)
        t0 = time.perf_counter()
        grow_kinds = program_memory(grow_jit, cols, meta, 2 * capacity)()
        compact_kinds = program_memory(
            _compact_packed_jit, cols, meta, False, False
        )()
        compile_s = time.perf_counter() - t0
        grow_resident = _resident(grow_kinds)
        analytic = packed_state_bytes(
            model_docs, capacity
        ) + packed_state_bytes(model_docs, 2 * capacity)
        # feed the MEASURED transient so the forecaster models reality
        fc.observe(
            n_docs=model_docs,
            capacity=capacity,
            occupied_rows=0,
            resident_bytes=grow_resident,
        )
        fam = lane_family(docs, d_block)
        ok = grow_resident <= budget_bytes
        if not ok and first_failing is None:
            first_failing = f"{docs}x{d_block}"
        if grow_resident < prev_resident:
            monotone = False
        prev_resident = grow_resident
        point = {
            "docs": int(docs),
            "capacity": capacity,
            "family": f"{docs}x{d_block}",
            "grow_resident_bytes": int(grow_resident),
            "grow_kinds": grow_kinds,
            "compact_resident_bytes": int(_resident(compact_kinds)),
            "analytic_bytes": int(analytic),
            "within_budget": bool(ok),
            "lane": effective_lane(fam, "fused" if fused_probed else "xla"),
            "compile_s": round(compile_s, 3),
            "model_docs": model_docs,
        }
        if sub_batch:
            point["subbatch_width"] = int(plan.width)
            point["n_sub"] = int(plan.n_sub)
            point["monolithic_bytes"] = int(plan.monolithic_bytes)
        points.append(point)

    # forecaster-vs-measured: worst relative error of the fitted model
    # across the swept points (the analytic formula is exact up to XLA's
    # small fixed overhead, so this should be well under 5%)
    model_err = 0.0
    for p in points:
        est = fc.model_bytes(p["model_docs"], capacity)
        err = abs(est - p["grow_resident_bytes"]) / max(
            p["grow_resident_bytes"], 1
        )
        model_err = max(model_err, err)

    surviving = [p["docs"] for p in points if p["within_budget"]]
    ceiling = max(surviving) if surviving else 0
    # headroom at the highest surviving rung: the budget fraction its
    # grow transient leaves unspent — shrinks toward 0 as the doc axis
    # approaches the ceiling (bench_compare regresses it on DROP)
    headroom = None
    for p in points:
        if p["docs"] == ceiling:
            headroom = round(
                1.0 - p["grow_resident_bytes"] / float(budget_bytes), 6
            )
    out = {
        "metric": "doc_axis_memory_ceiling",
        "unit": "docs surviving the grow-transient budget (compile-only)",
        "platform": jax.default_backend(),
        "capacity": capacity,
        "d_block": d_block,
        "budget_bytes": budget_bytes,
        "points": points,
        "memory_curve_monotone": monotone,
        "model_max_rel_err": round(model_err, 6),
        "doc_ceiling": int(ceiling),
        "first_failing_family": first_failing,
        "capacity_headroom_fraction": headroom,
        "fused_probed": fused_probed,
        "lane_health": lane_health(),
        "sub_batch": bool(sub_batch),
    }
    if sub_batch:
        # the monolithic cross-reference, from the analytic transient
        # (no extra AOT compiles): the first family whose ONE-dispatch
        # grow would bust the same budget — what the artifact's pushed
        # ceiling is measured against
        mono_failing = None
        for docs in docs_axis:
            mono = packed_state_bytes(
                int(docs), capacity
            ) + packed_state_bytes(int(docs), 2 * capacity)
            if mono > budget_bytes:
                mono_failing = f"{docs}x{d_block}"
                break
        out["monolithic_first_failing_family"] = mono_failing
    return out


def _build_typing_workload(n_ops: int = 60):
    """Wire updates of a small repetitive typing+erase session (host
    CRDT, one client) — every doc slot integrates the same stream, so
    throughput scales with the doc axis."""
    from ytpu.core import Doc

    doc = Doc(client_id=1)
    log = []
    doc.observe_update_v1(lambda p, o, t: log.append(p))
    txt = doc.get_text("text")
    for k in range(n_ops):
        with doc.transact() as txn:
            if k % 4 == 3:
                txt.remove_range(txn, 2, 3)
            else:
                txt.insert(txn, 0, f"w{k:03d}-abcdef")
    return log


def sub_batch_scaling(
    n_docs: int = 8,
    capacity: int = 256,
    n_ops: int = 60,
    chunk: int = 16,
) -> dict:
    """Throughput vs ``n_sub`` on a REAL replay (ISSUE-20): the same
    workload integrates at every pow2 sub-batch width from monolithic
    down to 2 docs/slice, each width forced through a budget that
    admits exactly it. On a single CPU device narrower widths pay the
    re-dispatch overhead (ratio ≤ 1); on a batch mesh the slices spread
    across devices — this leg is the trendable axis for that speedup
    (VERDICT Weak #5)."""
    import jax

    from ytpu.models.replay import FusedReplay, plan_replay
    from ytpu.ops.integrate_kernel import packed_state_bytes
    from ytpu.utils.capacity import HeadroomForecaster

    log = _build_typing_workload(n_ops)
    plan = plan_replay(log)

    def run_at(width: int | None) -> dict:
        kw = {}
        if width is not None:
            budget = packed_state_bytes(width, capacity) + packed_state_bytes(
                width, 2 * capacity
            )
            kw = dict(
                shard_docs=True,
                forecaster=HeadroomForecaster(budget_bytes=budget),
            )
        r = FusedReplay(
            n_docs,
            plan,
            capacity=capacity,
            max_capacity=4 * capacity,
            d_block=2,
            chunk=chunk,
            lane="xla",
            overlap=True,
            ingest="raw",
            sync_per_chunk=False,
            **kw,
        )
        t0 = time.perf_counter()
        r.run(log)
        wall = time.perf_counter() - t0
        applied = len(log) * n_docs
        return {
            "width": int(width if width is not None else n_docs),
            "n_sub": int(1 if width is None else (n_docs + width - 1) // width),
            "updates_per_s": round(applied / max(wall, 1e-9), 1),
            "wall_s": round(wall, 4),
            "subbatch_width": int(r.stats.subbatch_width),
            "syncs": int(r.stats.syncs),
        }

    widths: list = [None]
    w = n_docs // 2
    while w >= 2:
        widths.append(w)
        w //= 2
    # warm every width's compile caches off the clock (each slice width
    # is its own chunk-program shape family)
    for w in widths:
        run_at(w)
    points = [run_at(w) for w in widths]
    base = points[0]["updates_per_s"]
    best_sub = max((p["updates_per_s"] for p in points[1:]), default=base)
    return {
        "metric": "sub_batch_scaling",
        "platform": jax.default_backend(),
        "n_docs": int(n_docs),
        "capacity": int(capacity),
        "n_updates": len(log),
        "points": points,
        # best sub-batched throughput vs monolithic on THIS host —
        # neutral in bench_compare (single-device overhead is expected;
        # the mesh path is where the ratio exceeds 1)
        "sub_batch_scaling": round(best_sub / max(base, 1e-9), 4),
    }


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    sub_batch = "--sub-batch" in argv
    argv = [a for a in argv if a != "--sub-batch"]
    out_path = argv[0] if argv else None
    here = os.path.dirname(os.path.abspath(__file__))
    for p in (here, os.path.dirname(here)):
        if p not in sys.path:
            sys.path.insert(0, p)
    sweep = doc_ceiling_sweep(sub_batch=sub_batch)
    if sub_batch:
        sweep["sub_batch_scaling"] = sub_batch_scaling()
    line = json.dumps(sweep)
    print(line)
    if out_path:
        with open(out_path, "w") as f:
            f.write(json.dumps(sweep, indent=1, sort_keys=True) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
