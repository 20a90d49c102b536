"""`batch_doc._set`: one guarded element write per doc, vmapped over docs.

Pins what the integrate path relies on: a row whose index is the sentinel
B writes nothing, every other row writes exactly its element — whatever
mix of the two the batch holds. On a TPU v5e (jax 0.9.0 / libtpu 0.0.34)
the former `arr.at[idx].set(val, mode="drop")`, vmapped over 1,024 docs,
lost in-range writes of some rows whenever other rows carried the sentinel
(bool planes: every lone write at doc 256..~1000; PR 24). The CPU never
showed it, so the second test pins the construction itself: no
out-of-range index reaches a scatter.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ytpu.models.batch_doc import _set

D, B = 64, 32

PATTERNS = {
    "one_low": [3],
    "one_high": [D - 7],
    "every_third": list(range(1, D, 3)),
    "upper_half": list(range(D // 2, D)),
    "all": list(range(D)),
    "none": [],
}


@pytest.mark.parametrize("dtype", [jnp.bool_, jnp.int32], ids=["bool", "i32"])
@pytest.mark.parametrize("pattern", sorted(PATTERNS))
def test_vmapped_guarded_write_matches_a_loop(dtype, pattern):
    active = PATTERNS[pattern]
    rng = np.random.default_rng(len(active))
    start = rng.integers(0, 2, size=(D, B)).astype(np.dtype(dtype))
    idx = np.full(D, B, np.int32)  # the sentinel: no write
    idx[active] = rng.integers(0, B, size=len(active))
    val = np.ones(D, np.dtype(dtype)) if dtype == jnp.bool_ else np.arange(D, dtype=np.int32) + 100
    want = start.copy()
    for d in active:
        want[d, idx[d]] = val[d]
    got = jax.jit(jax.vmap(_set))(jnp.asarray(start), jnp.asarray(idx), jnp.asarray(val))
    np.testing.assert_array_equal(np.asarray(got), want)


def test_guarded_write_never_hands_a_scatter_an_out_of_range_index():
    from jax.lax import GatherScatterMode

    jaxpr = jax.make_jaxpr(jax.vmap(_set))(
        jnp.zeros((D, B), bool), jnp.zeros((D,), jnp.int32), jnp.ones((D,), bool)
    )
    modes = [
        eqn.params["mode"]
        for eqn in jaxpr.jaxpr.eqns
        if eqn.primitive.name.startswith("scatter")
    ]
    assert modes == [GatherScatterMode.PROMISE_IN_BOUNDS], modes
