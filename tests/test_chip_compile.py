"""Ahead-of-time compiles of the main path's kernels for a described TPU
v5e (no chip attached): what Mosaic or XLA would refuse on the chip
fails here, at no chip time.

The topology is described inside a module fixture, never at import —
only the xdist worker given this file loads the TPU compiler. Engines
are built with `interpret=False` explicitly, because the backend here
is the CPU. Every kernel compile asserts a `tpu_custom_call` in the
compiled program. Sizes are the ones the benches and `chip_smoke.py`
run.
"""

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from node_replication_tpu.core.log import LogSpec, log_init
from node_replication_tpu.core.replica import replicate_state
from node_replication_tpu.core.step import make_step
from node_replication_tpu.models import make_hashmap


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    from node_replication_tpu.parallel.mesh import replica_mesh

    return replica_mesh(devices=list(topo.devices))


def _shapes(fn, sharding):
    """`fn()`'s output shapes as ShapeDtypeStructs placed on the chip."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       sharding=sharding),
        jax.eval_shape(fn),
    )


def _arr(shape, sharding, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _step_args(spec, states_fn, sharding, bw=1, br=1):
    R, A = spec.n_replicas, spec.arg_width
    return (
        _shapes(lambda: log_init(spec), sharding),
        _shapes(states_fn, sharding),
        _arr((R, bw), sharding), _arr((R, bw, A), sharding),
        _arr((R, br), sharding), _arr((R, br, A), sharding),
    )


def _bench_spec(R: int) -> LogSpec:
    """bench.py's flagship LogSpec for R replicas, 1 write each."""
    return LogSpec(capacity=max(4 * R, 1 << 14), n_replicas=R,
                   arg_width=3, gc_slack=min(8192, R))


def test_flagship_combined_step(one_chip):
    R, K = 4096, 10_000
    spec = _bench_spec(R)
    d = make_hashmap(K)
    step = make_step(d, spec, 1, 1, jit=False, combined=True)
    _compile(step, *_step_args(
        spec, lambda: replicate_state(d.init_state(), R), one_chip))


def test_pallas_replay_step(one_chip):
    from node_replication_tpu.ops.pallas_replay import (
        make_pallas_step,
        pallas_hashmap_state,
    )

    R, K = 4096, 1024
    spec = _bench_spec(R)
    step = make_pallas_step(K, spec, 1, 1, interpret=False, jit=False)
    text = _compile(step, *_step_args(
        spec, lambda: pallas_hashmap_state(K, R), one_chip))
    assert "tpu_custom_call" in text


def _fused_round_args(spec, d, W, sharding, fenced=False):
    R = spec.n_replicas
    args = [
        _shapes(lambda: log_init(spec), sharding),
        _shapes(lambda: replicate_state(d.init_state(), R), sharding),
        _arr((W,), sharding), _arr((W, spec.arg_width), sharding),
        _arr((), sharding),
    ]
    if fenced:
        args.append(_arr((R,), sharding, jnp.bool_))
    return args


@pytest.mark.parametrize("R,K,fenced", [
    (4, 10_000, False),
    (4096, 1024, False),
    (4, 10_000, True),
])
def test_fused_hashmap_round(one_chip, R, K, fenced):
    """The ring append's per-column `[rows, 128]` planes (the old
    `[rows, 128, 3]` args span failed here: Mosaic refuses a slice whose
    minor dimension is not a multiple of 128)."""
    from node_replication_tpu.ops.pallas_replay import FusedHashmapEngine

    W = 256
    spec = LogSpec(n_replicas=R, arg_width=3)
    d = make_hashmap(K)
    eng = FusedHashmapEngine(K, spec, interpret=False)
    assert eng.supports(W)
    text = _compile(eng.round_fn(W, fenced),
                    *_fused_round_args(spec, d, W, one_chip, fenced))
    assert "tpu_custom_call" in text


def test_fused_vspace_round(one_chip):
    """benches/vspace.py --flat defaults: 2^18 pages, span 8, R=4,
    batch 32."""
    from node_replication_tpu.models import make_vspace
    from node_replication_tpu.ops.pallas_vspace import FusedVspaceEngine

    P, span, R, W = 1 << 18, 8, 4, 32
    spec = LogSpec(n_replicas=R, arg_width=3)
    d = make_vspace(P, max_span=span)
    eng = FusedVspaceEngine(P, span, spec, interpret=False)
    assert eng.supports(W)
    text = _compile(eng.round_fn(W),
                    *_fused_round_args(spec, d, W, one_chip))
    assert "tpu_custom_call" in text


def test_oahashmap_replay_step(one_chip):
    """benches/hashmap.py --sparse --replay pallas defaults: 10,000
    keys in 20,000 slots, probe 16, R=4, 16 writes + 16 reads."""
    from node_replication_tpu.ops.pallas_oahashmap import (
        make_pallas_oahashmap_step,
        pallas_oahashmap_state,
    )

    slots, R, bw = 20_000, 4, 16
    spec = LogSpec(n_replicas=R, arg_width=3)
    step = make_pallas_oahashmap_step(slots, 16, spec, bw, bw,
                                      interpret=False, jit=False)
    text = _compile(step, *_step_args(
        spec, lambda: pallas_oahashmap_state(slots, R), one_chip,
        bw, bw))
    assert "tpu_custom_call" in text


def test_vspace_plan_replay_step(one_chip):
    """benches/vspace.py --long-log --replay pallas-plan: 2^18 pages,
    span 64, batch 1024, R=4 (radix model)."""
    from node_replication_tpu.models import make_vspace_radix
    from node_replication_tpu.ops.pallas_vspace import (
        make_pallas_vspace_plan_step,
    )

    P, span, R, bw = 1 << 18, 64, 4, 1024
    spec = LogSpec(n_replicas=R, arg_width=3)
    d = make_vspace_radix(P, max_span=span)
    step = make_pallas_vspace_plan_step(P, spec, bw, 1, span, radix=True,
                                        dispatch=d, interpret=False,
                                        jit=False)
    text = _compile(step, *_step_args(
        spec, lambda: replicate_state(d.init_state(), R), one_chip,
        bw, 1))
    assert "tpu_custom_call" in text


def _mesh_fleet(mesh, spec, d):
    """(log, states) shapes with the canonical mesh placement."""
    from node_replication_tpu.parallel.mesh import (
        log_spec_tree,
        states_spec_tree,
    )

    def placed(fn, specs_of):
        shapes = jax.eval_shape(fn)
        return jax.tree.map(
            lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                               sharding=sh),
            shapes, specs_of(shapes, mesh),
        )

    R = spec.n_replicas
    return (placed(lambda: log_init(spec), log_spec_tree),
            placed(lambda: replicate_state(d.init_state(), R),
                   states_spec_tree))


def test_mesh_gspmd_exec_round(four_chips):
    """chip_smoke --four-chips, collectives="auto": the combined exec
    round GSPMD-partitioned over the 2x2 mesh (R=4096, K=10,000)."""
    from functools import partial

    from node_replication_tpu.core.log import log_catchup_all

    R, K = 4096, 10_000
    spec = LogSpec(n_replicas=R, arg_width=3)
    d = make_hashmap(K)
    log, states = _mesh_fleet(four_chips, spec, d)
    exec_round = jax.jit(partial(log_catchup_all, spec, d),
                         static_argnames=("window",))
    exec_round.lower(log, states, window=256).compile()


def test_mesh_fused_round(four_chips):
    """chip_smoke --four-chips, mesh-fused tier (R=128, 32 per chip,
    K=10,000): the shard_map round. Its cursor join must not
    all-reduce int64 with min/max (refused on v5e: only Sum lowers)."""
    from jax.sharding import NamedSharding, PartitionSpec

    from node_replication_tpu.parallel.collectives import MeshFusedEngine

    R, K, W = 128, 10_000, 64
    spec = LogSpec(n_replicas=R, arg_width=3)
    d = make_hashmap(K)
    eng = MeshFusedEngine(d, spec, four_chips, interpret=False)
    assert eng.supports(W)
    log, states = _mesh_fleet(four_chips, spec, d)
    rep = NamedSharding(four_chips, PartitionSpec())
    text = _compile(eng.round_fn(W), log, states, _arr((W,), rep),
                    _arr((W, 3), rep), _arr((), rep))
    assert "tpu_custom_call" in text
