"""Test env: force an 8-device virtual CPU mesh before jax initializes.

Multi-chip hardware is not available in CI; sharding tests run on
`--xla_force_host_platform_device_count=8` virtual CPU devices, the
"multi-node without a cluster" idiom (the reference simulates NUMA nodes
with pinned OS threads in one process, SURVEY.md §4 idiom 5).

The suite runs on the CPU: the tier-1 command sets `JAX_PLATFORMS=cpu`, and
`jax.config` forces it here as well, so a bare `pytest` on a chip host never
takes the chip (a chip belongs to one process; xdist runs several workers).
Kernels run in Pallas interpret mode there; `tests/test_chip_compile.py`
compiles them for a described TPU instead.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: excluded from the tier-1 budgeted run (-m 'not slow'); "
        "still runs in the unfiltered CI test job",
    )
