"""Sequence-parallel (sp) axis benchmark — VERDICT r3 next-step #6.

Replays a prefix of the B4 editing trace through `ShardedDoc` at 1 vs 8
shards and measures:

- routed updates/s end-to-end (host router + device YATA per shard);
- `find_position` latency (the O(S) prefix-sum lookup vs the reference's
  O(items) walk, types/text.rs:734 / block.rs:723);
- the per-flush device step cost.

Run: python benches/sp_axis.py [--ops N]. Prints one JSON line per shard
count plus a summary comparing 8-shard to 1-shard throughput. CPU or TPU
(whatever backend jax resolves; the capture labels it).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# an 8-way host-device mesh lets the sp axis ACTUALLY partition when the
# backend is CPU (each virtual device gets an XLA thread — real speedup
# on multi-core boxes; harmless on 1 vCPU). Must precede the first jax
# import. On TPU the flag is ignored (it only affects the host platform).
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)


def b4_prefix_updates(n_ops: int):
    import bench as bench_mod

    if os.path.exists(bench_mod.TRACE_PATH):
        ops = bench_mod.load_b4_ops(n_ops)
    else:
        ops = bench_mod.synthetic_ops(n_ops)
    return bench_mod.build_updates(ops)


def run_shards(log, expect, n_shards: int, capacity: int = 8192) -> dict:
    import jax

    from ytpu.parallel.sharded_doc import ShardedDoc

    sd = ShardedDoc(n_shards=n_shards, capacity=capacity)
    mesh_devices = 0
    if n_shards > 1 and len(jax.devices()) >= n_shards:
        import numpy as _np
        from jax.sharding import Mesh

        mesh = Mesh(_np.array(jax.devices()[:n_shards]), ("sp",))
        sd.place_on_mesh(mesh)
        mesh_devices = n_shards
    # warm phase: the first ~half of the trace pays the jit compiles for
    # the flush bucket shapes (and any capacity growth); the steady phase
    # is the serving-regime number (flushes are async since round 5 —
    # host routing overlaps the device steps, `_sync` only at reads)
    warm = len(log) // 2
    t0 = time.perf_counter()
    for p in log[:warm]:
        sd.apply_update_v1(p)
    sd.flush()
    sd._sync()
    warm_dt = time.perf_counter() - t0
    t0 = time.perf_counter()
    for p in log[warm:]:
        sd.apply_update_v1(p)
    sd.flush()
    sd._sync()
    dt = time.perf_counter() - t0
    n_steady = len(log) - warm
    got = sd.get_string()
    assert got == expect, f"sp replay mismatch: {got[:40]!r} != {expect[:40]!r}"

    # find_position: prefix-sum lookup cost over the final doc
    lens = sd.shard_lengths()  # warm the cached pull
    total = int(lens.sum())
    t0 = time.perf_counter()
    n_lookups = 200
    for i in range(n_lookups):
        sd.find_position((i * 37) % max(1, total))
    pos_dt = (time.perf_counter() - t0) / n_lookups
    return {
        "metric": f"sp{n_shards}_updates_per_sec",
        "value": round(n_steady / dt, 1),
        "unit": f"steady-state routed updates/s, {n_shards}-shard "
        f"ShardedDoc ({n_steady} of {len(log)} B4-prefix updates; "
        "first half warms the jit buckets)",
        "cold_updates_per_sec": round(warm / warm_dt, 1),
        "find_position_us": round(1e6 * pos_dt, 1),
        "doc_units": total,
        "platform": jax.devices()[0].platform,
        "mesh_devices": mesh_devices,
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--ops", type=int, default=2000)
    args = ap.parse_args()
    log, expect = b4_prefix_updates(args.ops)
    # size capacity to the trace up front: mid-run growth recompiles the
    # apply program (~seconds each on CPU) and was the real reason the
    # round-4 capture read tens of updates/s
    cap = 1 << (max(2048, 4 * args.ops) - 1).bit_length()
    out = []
    for s in (1, 8):
        # capacity is PER SHARD: the segments partition the doc, so each
        # shard's columns need ~1/S of the total (2x headroom for skew)
        per_shard = 1 << (max(1024, 2 * cap // s) - 1).bit_length()
        r = run_shards(log, expect, s, capacity=per_shard)
        out.append(r)
        print(json.dumps(r), flush=True)
    print(
        json.dumps(
            {
                "metric": "sp_axis_8v1_speedup",
                "value": round(out[1]["value"] / out[0]["value"], 3),
                "unit": "8-shard / 1-shard routed updates/s "
                "(host router shared; device YATA parallel over sp)",
                "find_position_us_8": out[1]["find_position_us"],
                "find_position_us_1": out[0]["find_position_us"],
            }
        ),
        flush=True,
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
