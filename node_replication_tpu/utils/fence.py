"""A device barrier for timed regions.

JAX dispatches asynchronously: a call returns once its work is
enqueued, so a timed region must end on a barrier or it measures the
enqueue. On a directly attached TPU (and on the CPU)
`jax.block_until_ready` is such a barrier. `fence` is the one the
repo's timed regions use: it folds one element of every array leaf
into a single scalar on device and fetches it — one tiny D2H transfer,
which cannot complete until every computation feeding those leaves has
executed. Being data-dependent, it stays a barrier whatever a
platform's `block_until_ready` does, at the cost of one transfer.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp


def fence(*trees) -> None:
    """Block until every computation producing the given pytrees has
    really finished on device (see module docstring for why
    `jax.block_until_ready` is not enough)."""
    leaves = [
        leaf
        for tree in trees
        for leaf in jax.tree_util.tree_leaves(tree)
        # size-0 leaves carry no pending data (and cannot be indexed)
        if isinstance(leaf, jax.Array) and leaf.size
    ]
    if not leaves:
        return
    acc = None
    for leaf in leaves:
        v = leaf[(0,) * leaf.ndim] if leaf.ndim else leaf
        v = v.astype(jnp.float32)
        acc = v if acc is None else acc + v
    np.asarray(acc)  # the one data-dependent D2H: the true barrier
