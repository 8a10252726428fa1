"""Shared CLI plumbing for the bench suite.

Every bench follows the reference's driver shape (`benches/hashmap.rs:317`
style `main()`s): parse knobs, build a ScaleBenchBuilder sweep, print
`>> X Mops` lines, append CSV records. Default sizes are smoke-scale;
`--full` switches to reference-scale workloads (the `smokebench` feature
flag inverted, `benches/Cargo.toml`)."""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# --cpu must take effect BEFORE any jax backend initializes (package
# imports can trigger it; switching platforms after init is silently
# ignored and a "--cpu" sweep would measure the real chip).
if "--cpu" in sys.argv:
    import jax

    jax.config.update("jax_platforms", "cpu")

from node_replication_tpu.utils.compile_cache import (  # noqa: E402
    setup_compile_cache,
)

setup_compile_cache()


def base_parser(desc: str) -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=desc)
    p.add_argument("--replicas", type=int, nargs="+", default=[4, 16],
                   help="replica counts to sweep (ReplicaStrategy analog)")
    p.add_argument("--batch", type=int, nargs="+", default=[32],
                   help="ops per replica per step (combiner batch)")
    p.add_argument("--duration", type=float, default=1.0,
                   help="seconds per config")
    p.add_argument("--out-dir", default=".", help="CSV output directory")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--full", action="store_true",
                   help="reference-scale workload sizes")
    p.add_argument("--cpu", action="store_true",
                   help="force the CPU backend (debug)")
    return p


def finish_args(args):
    if args.cpu:
        # the platform switch happened at module import (above), before
        # any backend could initialize — here we only VERIFY it took,
        # so a wrapper that rewrites argv can't silently measure the
        # real chip under a --cpu label
        import jax

        assert jax.devices()[0].platform == "cpu", (
            "--cpu requested but the active backend is "
            f"{jax.devices()[0].platform}; the flag must be on the "
            "command line before jax initializes (see common.py)"
        )
    return args
