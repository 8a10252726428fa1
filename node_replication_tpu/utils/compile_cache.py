"""Where JAX's persistent compilation cache lives — one rule, one place.

The entry points (`chip_smoke.py`, `bench.py`, `benches/common.py`) call
`setup_compile_cache()` before their first compile:

- `JAX_COMPILATION_CACHE_DIR` set: JAX reads it itself; nothing is set
  in code, so the cache lands only there.
- unset: the cache goes to `<checkout>/.jax_cache` — a fixed path
  (listed in `.gitignore`), never one derived from a temp name, a pid
  or the time: the path is part of the cache key, so a moving
  directory never hits.

Library code and the test suite never call it: a test run compiles
nothing into the checkout.
"""

from __future__ import annotations

import os

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".jax_cache",
)


def setup_compile_cache() -> str:
    """Place the persistent compile cache; returns its directory."""
    env = os.environ.get(ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE)
    return CHECKOUT_CACHE
