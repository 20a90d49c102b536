#!/usr/bin/env python3
"""Compile the served path's big programs for a described TPU v5e, by hand,
before a chip call on a configuration that has not run there.

    JAX_PLATFORMS=cpu python3 benchmark/tools/compile_rehearsal.py [n_docs] [capacity]

No chip is attached; the TPU compiler is, and it refuses what the chip would
(a program that does not fit HBM). Nothing runs: these are bytes from
`memory_analysis()`, never times. It prints, for the integrate step at the
traffic's 4-row bucket and at the prefill's 512-row bucket, the decode
program at S = 64 lanes and at the prefill's S = n_docs lanes, and the diff
selection: argument, output, temp and total bytes on the one chip.
"""

from __future__ import annotations

import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

N_CLIENTS = 4096  # 2,048 sessions + warm-up + templates, as a power of two


def main(argv) -> int:
    n_docs = int(argv[0]) if argv else 4096
    capacity = int(argv[1]) if len(argv) > 1 else 4096
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    from ytpu.models.batch_doc import (
        BatchEncoder,
        _apply_update_batch_jit,
        _encode_diff_batch_jit,
        init_state,
        scan_tier_plan,
    )
    from ytpu.ops.decode_kernel import _decode_updates_v1_jit

    on = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip), tree
    )
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)
    state = on(jax.eval_shape(lambda: init_state(n_docs, capacity)))

    def report(what, compiled):
        m = compiled.memory_analysis()
        total = m.argument_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes + m.temp_size_in_bytes
        print(f"{what}: argument {m.argument_size_in_bytes} + output {m.output_size_in_bytes} "
              f"- alias {m.alias_size_in_bytes} + temp {m.temp_size_in_bytes} = {total} bytes "
              f"({total / 2**30:.2f} GiB of 16)", flush=True)

    for rows in (4, 512):
        batch = BatchEncoder().batch_from_rows([[]] * n_docs, [[]] * n_docs, rows, 4)
        report(
            f"integrate step [{n_docs}, {capacity}], {rows}-row bucket",
            _apply_update_batch_jit.lower(state, on(batch), i32(N_CLIENTS), scan_tier_plan()).compile(),
        )
    for lanes, width, rows, steps in ((64, 64, 4, 16), (n_docs, 8192, 512, 16 * ((13 * 512 + 4 + 15) // 16))):
        report(
            f"decode S={lanes} lanes x {width} bytes, {rows}-row bucket, {steps} steps",
            _decode_updates_v1_jit.lower(
                jax.ShapeDtypeStruct((lanes, width), jnp.uint8, sharding=chip),
                i32(lanes), max_rows=rows, max_dels=4, n_steps=steps,
                client_table=(i32(N_CLIENTS), i32(N_CLIENTS)), max_sections=2,
                key_table=(i32(1), i32(1)), client_hash_table=(i32(0), i32(0)),
                primary_root_hash=i32(lanes),
            ).compile(),
        )
    report(
        f"diff selection [{n_docs}, {capacity}] x {N_CLIENTS} clients",
        _encode_diff_batch_jit.lower(state, i32(n_docs, N_CLIENTS), N_CLIENTS).compile(),
    )
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
