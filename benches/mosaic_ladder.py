"""Mosaic fault bisection ladder (VERDICT r3 next-step #2).

Round 3's fused Pallas kernel crashed the TPU worker at compile time
(`tpu_compile_helper subprocess exit code 1`) with no record of WHICH
construct the Mosaic compiler died on. This ladder compiles and
runs a staircase of micro-kernels, each isolating one construct the
fused kernel (`ytpu/ops/integrate_kernel.py`) leans on, in increasing
order of suspicion. The step name is flushed to `mosaic_ladder.json`
BEFORE its compile starts, so even a hard worker crash identifies the
faulting rung from the artifact alone.

Rungs:
  0 copy          — pallas_call works at all (baseline)
  1 onehot_put    — one-hot lane scatter (the kernel's `put`)
  2 mrow_mask     — (DB,) bool -> (DB, 1) via astype(I32)[:, None] > 0
  3 fori_carry    — fori_loop with i32 carry over a VMEM ref
  4 while_scan    — while_loop w/ compound carry (YATA conflict scan shape)
  5 nested_fori   — fori inside fori (step -> row_body nesting)
  6 pl_when       — pl.when(jnp.any(mask)) guarded write phase
  7 big_tile      — 25 x d_block x 2048 i32 VMEM tile traffic (~3MB class)
  8 kernel_s1     — the REAL fused kernel, 1-step stream, tiny shapes
  9 kernel_quick  — the real kernel over a ~200-op synthetic replay
 10 kernel_moves  — the real kernel with move rows in the stream

Run on hardware:  python benches/mosaic_ladder.py
(CPU falls back to interpret mode — useful only to validate the ladder
itself, not Mosaic.)
"""

from __future__ import annotations

import json
import os
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(HERE, "benches", "mosaic_ladder.json")


def _flush(state: dict) -> None:
    with open(OUT + ".tmp", "w") as f:
        json.dump(state, f, indent=1)
    os.replace(OUT + ".tmp", OUT)


def main() -> int:
    import sys

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import pallas as pl

    platform = jax.devices()[0].platform
    interpret = platform == "cpu"
    state = {
        "platform": platform,
        "device_kind": jax.devices()[0].device_kind,
        "interpret": interpret,
        "steps": {},
        "started": time.strftime("%Y-%m-%dT%H:%M:%SZ"),
    }
    _flush(state)

    I32 = jnp.int32
    DB, C = 8, 256

    def run(name, fn):
        # the attempt is recorded BEFORE the compile so a worker crash
        # still names the rung
        state["steps"][name] = {"status": "attempting"}
        state["last_attempt"] = name
        _flush(state)
        t0 = time.time()
        try:
            fn()
            state["steps"][name] = {
                "status": "ok",
                "seconds": round(time.time() - t0, 1),
            }
        except Exception as e:  # noqa: BLE001 — record and continue
            state["steps"][name] = {
                "status": "fail",
                "seconds": round(time.time() - t0, 1),
                "error": f"{type(e).__name__}: {e}"[:800],
            }
        _flush(state)
        print(name, state["steps"][name]["status"], flush=True)

    # --- rung 0: trivial copy ------------------------------------------------
    def r0():
        def k(x_ref, o_ref):
            o_ref[...] = x_ref[...] + 1

        x = jnp.zeros((DB, C), I32)
        out = pl.pallas_call(
            k, out_shape=jax.ShapeDtypeStruct((DB, C), I32), interpret=interpret
        )(x)
        assert int(np.asarray(out)[0, 0]) == 1

    run("0_copy", r0)

    # --- rung 1: one-hot lane scatter ---------------------------------------
    def r1():
        def k(x_ref, o_ref):
            iota_c = jax.lax.broadcasted_iota(I32, (1, C), 1)
            idx = x_ref[:, 0][:, None]  # (DB, 1)
            oh = (iota_c == idx).astype(I32)
            o_ref[...] = x_ref[...] * (1 - oh) + 7 * oh

        x = jnp.tile(jnp.arange(DB, dtype=I32)[:, None], (1, C))
        out = pl.pallas_call(
            k, out_shape=jax.ShapeDtypeStruct((DB, C), I32), interpret=interpret
        )(x)
        assert int(np.asarray(out)[3, 3]) == 7

    run("1_onehot_put", r1)

    # --- rung 2: the mrow bool-minor-dim pattern -----------------------------
    def r2():
        def k(x_ref, o_ref):
            mask = x_ref[:, 0] > 2  # (DB,) i1
            m2 = mask.astype(I32)[:, None] > 0  # (DB, 1) — Mosaic r3 fix path
            o_ref[...] = jnp.where(m2, x_ref[...], -x_ref[...])

        x = jnp.tile(jnp.arange(DB, dtype=I32)[:, None], (1, C))
        out = np.asarray(
            pl.pallas_call(
                k, out_shape=jax.ShapeDtypeStruct((DB, C), I32), interpret=interpret
            )(x)
        )
        assert int(out[1, 1]) == -1 and int(out[3, 3]) == 3, out[:, 0]

    run("2_mrow_mask", r2)

    # --- rung 3: fori_loop carry over a ref ----------------------------------
    def r3():
        def k(x_ref, o_ref):
            def body(i, acc):
                return acc + jnp.sum(x_ref[:, i])

            total = jax.lax.fori_loop(0, 16, body, jnp.int32(0))
            o_ref[...] = jnp.full((DB, C), total, I32)

        x = jnp.ones((DB, C), I32)
        out = pl.pallas_call(
            k, out_shape=jax.ShapeDtypeStruct((DB, C), I32), interpret=interpret
        )(x)
        assert int(np.asarray(out)[0, 0]) == 16 * DB

    run("3_fori_carry", r3)

    # --- rung 4: while_loop with compound carry (conflict-scan shape) --------
    def r4():
        def k(x_ref, o_ref):
            iota_c = jax.lax.broadcasted_iota(I32, (1, C), 1)

            def cond(carry):
                o, brk, _ = carry
                return jnp.any((o < 12) & (brk == 0))

            def body(carry):
                o, brk, acc = carry
                oh = ((iota_c == o[:, None]) & (brk[:, None] == 0)).astype(I32)
                acc = acc + jnp.sum(oh * x_ref[...], axis=1)
                brk = brk | (acc > 40).astype(I32)
                return o + 1, brk, acc

            o0 = jnp.zeros((DB,), I32)
            _, _, acc = jax.lax.while_loop(
                cond, body, (o0, jnp.zeros((DB,), I32), jnp.zeros((DB,), I32))
            )
            o_ref[...] = jnp.tile(acc[:, None], (1, C))

        x = jnp.tile(jnp.arange(C, dtype=I32)[None, :], (DB, 1))
        pl.pallas_call(
            k, out_shape=jax.ShapeDtypeStruct((DB, C), I32), interpret=interpret
        )(x)

    run("4_while_scan", r4)

    # --- rung 5: nested fori -------------------------------------------------
    def r5():
        def k(x_ref, o_ref):
            def outer(s, acc):
                def inner(u, a):
                    return a + x_ref[0, (s * 4 + u) % C]

                return jax.lax.fori_loop(0, 4, inner, acc)

            total = jax.lax.fori_loop(0, 8, outer, jnp.int32(0))
            o_ref[...] = jnp.full((DB, C), total, I32)

        x = jnp.ones((DB, C), I32)
        pl.pallas_call(
            k, out_shape=jax.ShapeDtypeStruct((DB, C), I32), interpret=interpret
        )(x)

    run("5_nested_fori", r5)

    # --- rung 6: pl.when guarded write ---------------------------------------
    def r6():
        def k(x_ref, o_ref):
            o_ref[...] = x_ref[...]
            do = x_ref[:, 0] > 100

            @pl.when(jnp.any(do))
            def _():
                o_ref[...] = x_ref[...] + 1

        x = jnp.zeros((DB, C), I32)
        pl.pallas_call(
            k, out_shape=jax.ShapeDtypeStruct((DB, C), I32), interpret=interpret
        )(x)

    run("6_pl_when", r6)

    # --- rung 7: full-size VMEM tile -----------------------------------------
    def r7():
        NCOL, BIGC = 25, 2048

        def k(x_ref, o_ref):
            o_ref[...] = x_ref[...] * 2

        x = jnp.ones((NCOL, DB, BIGC), I32)
        pl.pallas_call(
            k,
            out_shape=jax.ShapeDtypeStruct((NCOL, DB, BIGC), I32),
            interpret=interpret,
        )(x)

    run("7_big_tile", r7)

    # --- rungs 8-10: the real kernel -----------------------------------------
    import sys

    sys.path.insert(0, HERE)
    from ytpu.core.doc import Doc
    from ytpu.models.batch_doc import get_string, init_state
    from ytpu.ops.decode_kernel import (
        RawPayloadView,
        decode_updates_v1,
        identity_rank,
        pack_updates,
    )
    from ytpu.ops.integrate_kernel import apply_update_stream_fused

    def replay(n_ops, with_moves=False):
        doc = Doc(client_id=1)
        log = []
        doc.observe_update_v1(lambda p, o, t: log.append(p))
        if with_moves:
            arr = doc.get_array("text")
            with doc.transact() as txn:
                for i in range(8):
                    arr.insert(txn, i, f"e{i}")
            for i in range(min(n_ops, 6)):
                with doc.transact() as txn:
                    arr.move_to(txn, i % 4, (i + 3) % 6)
            expect = None
        else:
            txt = doc.get_text("text")
            for i in range(n_ops):
                with doc.transact() as txn:
                    txt.insert(txn, i % max(1, min(i, 40)), f"w{i % 7}")
            expect = txt.get_string()
        return log, expect

    def run_kernel(log, expect, n_docs=8, cap=512):
        buf_np, lens_np = pack_updates(log)
        from functools import partial as _partial

        decode = jax.jit(_partial(decode_updates_v1, max_rows=4, max_dels=8))
        stream, flags = decode(jnp.asarray(buf_np), jnp.asarray(lens_np))
        st = init_state(n_docs, cap)
        st = apply_update_stream_fused(
            st, stream, identity_rank(256), d_block=min(8, n_docs),
            guard=False, interpret=interpret,
            refresh_cache=False,  # rung timings measure the kernel only
        )
        assert int(np.asarray(st.error).max()) == 0, "kernel error flag"
        if expect is not None:
            got = get_string(st, 0, RawPayloadView(buf_np))
            assert got == expect, f"{got[:40]!r} != {expect[:40]!r}"

    def r8():
        log, expect = replay(1)
        run_kernel(log, expect)

    run("8_kernel_s1", r8)

    def r9():
        log, expect = replay(200)
        run_kernel(log, expect)

    run("9_kernel_quick", r9)

    def r10():
        log, expect = replay(6, with_moves=True)
        run_kernel(log, expect)

    run("10_kernel_moves", r10)

    state["finished"] = time.strftime("%Y-%m-%dT%H:%M:%SZ")
    _flush(state)
    fails = [k for k, v in state["steps"].items() if v["status"] != "ok"]
    print("ladder complete; failures:", fails or "none", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
