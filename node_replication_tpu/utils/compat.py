"""The two JAX names the package routes through one place (jax 0.9).

- `shard_map`: the explicit-collective multi-chip step
  (`parallel/collectives.py`).
- `x64_disabled()`: the pallas kernels trace pure-int32 programs while
  the package enables x64 globally.
"""

from __future__ import annotations

import jax

shard_map = jax.shard_map


def x64_disabled():
    """Context manager: trace with x64 disabled."""
    return jax.enable_x64(False)
