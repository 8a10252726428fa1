"""fence() must be a true barrier and a no-op-safe utility.

These tests pin the contract that can be checked on any platform: it
accepts arbitrary pytrees, forces materialization (a data-dependent
readback), and leaves values untouched.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from node_replication_tpu.utils.fence import fence


def test_fence_accepts_pytrees_and_scalars():
    x = jnp.arange(8)
    tree = {"a": x, "b": (x * 2, jnp.float32(3.0))}
    fence(tree, x)  # must not raise
    fence()  # empty is fine
    fence(None, [], {"k": 7})  # non-array leaves are skipped


def test_fence_forces_materialization():
    @jax.jit
    def f(x):
        return x * 2 + 1

    y = f(jnp.ones((16, 16)))
    fence(y)
    np.testing.assert_allclose(np.asarray(y)[0, 0], 3.0)


def test_fence_chained_donated_steps():
    # donation matters: bench.py fences buffers whose predecessors were
    # donated away — fence's slice ops must not touch stale inputs
    @partial(jax.jit, donate_argnums=(0,))
    def step(x):
        return x + 1

    x = jnp.zeros((4,))
    for _ in range(10):
        x = step(x)
    fence(x)
    np.testing.assert_allclose(np.asarray(x), 10.0)


def test_fence_skips_empty_leaves():
    fence({"empty": jnp.zeros((0, 3)), "full": jnp.ones((2,))})
    fence(jnp.zeros((4, 0)))  # all-empty tree: nothing to wait for
