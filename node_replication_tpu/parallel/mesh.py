"""Mesh sharding: replicas and logs across TPU chips.

The reference scales by placing replicas over the NUMA topology
(`ReplicaStrategy`, `benches/mkbench.rs:321-362`) and partitioning the op
stream over logs (`LogStrategy`, `benches/mkbench.rs:364-383`), with the
shared-memory ring as the communication backend (SURVEY.md §2.6). The TPU
equivalent (SURVEY.md §2.6 "TPU-native equivalent"):

- mesh axis 'replica' — the fleet of replica states shards across chips
  (data parallelism of *state*); each chip replays only its shard.
- mesh axis 'log' — CNR's stacked log axis shards across chips
  (tensor/expert parallelism of the *op stream*); each chip appends and
  scans only its logs.
- the log (single-log case) is *replicated* over the mesh: the append batch
  is identical on every chip, so XLA keeps one copy per chip updated with
  zero communication, and replicas gather entries locally — the all-gather
  of appended spans rides ICI only when the batch itself originates sharded.

No hand-written collectives: shardings are declared with
`jax.sharding.NamedSharding` on a jitted pure step and GSPMD inserts the
all-gathers (scaling-book recipe: pick a mesh, annotate, let XLA place
collectives).

PRODUCTION STATUS: this module is the placement layer of the stateful
wrappers, not a demo — `NodeReplicated(mesh=...)` and
`MultiLogReplicated(mesh=...)` call `place()` at construction (and
after every fleet-shape change) so their replica axis lives across the
mesh, and `replica_mesh()` is the one-liner most callers want. The
explicit-collective twin lives in `parallel/collectives.py`.
"""

from __future__ import annotations

import enum

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from node_replication_tpu.core.log import LogState
from node_replication_tpu.core.multilog import MultiLogState


class ReplicaStrategy(enum.Enum):
    """Replica-shard placement granularity (`ReplicaStrategy`,
    `benches/mkbench.rs:321-362`): the reference's One/Socket/L1.../
    PerThread ladder mapped onto the TPU hierarchy (device → host →
    slice, `parallel/topology.py`).

    ONE — the whole fleet on a single device, un-sharded (the reference's
    `One`: one replica, every thread shares it).
    PER_HOST — one replica shard per host, placed on each host's first
    device (the `Socket`/NUMA-node analog: shards communicate over DCN).
    PER_DEVICE — one replica shard on every device (the
    `L1`/`PerThread` analog: the full mesh, shards communicate over ICI).

    Consumed by `strategy_devices()` → `ShardedRunner` /
    `ScaleBenchBuilder.replica_strategies()`.
    """

    ONE = "one"
    PER_HOST = "per_host"
    PER_DEVICE = "per_device"


def strategy_devices(strategy: ReplicaStrategy, topo=None, mapping=None):
    """Ordered device list realizing a ReplicaStrategy (the
    `replica_core_allocation` analog, `benches/mkbench.rs:838-945`):
    topology walk + ThreadMapping placement pick which devices host
    replica shards."""
    from node_replication_tpu.parallel.topology import (
        MachineTopology,
        ThreadMapping,
    )

    topo = topo or MachineTopology()
    mapping = mapping or ThreadMapping.SEQUENTIAL
    if strategy == ReplicaStrategy.ONE:
        return topo.allocate(mapping, 1)
    if strategy == ReplicaStrategy.PER_HOST:
        hosts = sorted({i.process for i in topo.infos})
        return [topo.devices_on_host(p)[0] for p in hosts]
    return topo.allocate(mapping, topo.n_devices())


def make_mesh(
    n_replica_shards: int | None = None,
    n_log_shards: int = 1,
    devices=None,
) -> Mesh:
    """Build a ('replica', 'log') mesh. Defaults to all devices on the
    replica axis."""
    devices = list(devices if devices is not None else jax.devices())
    total = len(devices)
    if n_replica_shards is None:
        n_replica_shards = total // n_log_shards
    if n_replica_shards * n_log_shards != total:
        raise ValueError(
            f"{n_replica_shards}x{n_log_shards} mesh needs "
            f"{n_replica_shards * n_log_shards} devices, got {total}"
        )
    arr = np.asarray(devices).reshape(n_replica_shards, n_log_shards)
    return Mesh(arr, ("replica", "log"))


def replica_mesh(n_shards: int | None = None, devices=None,
                 strategy: "ReplicaStrategy | None" = None,
                 mapping=None) -> Mesh:
    """One-axis ('replica', 'log'=1) mesh for a replica-sharded fleet —
    the `NodeReplicated(mesh=...)` convenience. `n_shards=None` takes
    every device; a `ReplicaStrategy` picks the device set through the
    topology walk (`strategy_devices`)."""
    if strategy is not None:
        devices = strategy_devices(strategy, mapping=mapping)
        if n_shards is not None:
            devices = devices[:n_shards]
    elif devices is None:
        devices = jax.devices()
        if n_shards is not None:
            devices = list(devices)[:n_shards]
    return make_mesh(len(list(devices)), 1, devices=devices)


def log_spec_tree(log, mesh: Mesh):
    """Sharding pytree for a log state. Single-log: fully replicated
    (identical append on every chip). Multi-log: ring + cursors shard over
    the 'log' mesh axis on their leading log dimension."""
    if isinstance(log, MultiLogState):
        return MultiLogState(
            opcodes=NamedSharding(mesh, P("log")),
            args=NamedSharding(mesh, P("log")),
            head=NamedSharding(mesh, P("log")),
            tail=NamedSharding(mesh, P("log")),
            ctail=NamedSharding(mesh, P("log")),
            ltails=NamedSharding(mesh, P("log", "replica")),
        )
    assert isinstance(log, LogState)
    return LogState(
        opcodes=NamedSharding(mesh, P()),
        args=NamedSharding(mesh, P()),
        head=NamedSharding(mesh, P()),
        tail=NamedSharding(mesh, P()),
        ctail=NamedSharding(mesh, P()),
        ltails=NamedSharding(mesh, P("replica")),
    )


def states_spec_tree(states, mesh: Mesh):
    """Replica states shard on the leading (replica) axis."""
    return jax.tree.map(lambda _: NamedSharding(mesh, P("replica")), states)


# compat aliases (pre-production private names)
_log_spec_tree = log_spec_tree
_states_spec_tree = states_spec_tree


def place(log, states, mesh: Mesh):
    """device_put log + states with their canonical shardings."""
    log = jax.device_put(log, log_spec_tree(log, mesh))
    states = jax.device_put(states, states_spec_tree(states, mesh))
    return log, states


def announce_placement(mesh: Mesh, n_replicas: int, wrapper: str,
                       tier: str) -> None:
    """Record a wrapper's mesh placement in obs: `mesh.*` gauges
    (per-device replica count, device count) and one `mesh-place`
    trace event — the report CLI's Mesh section feeds on these."""
    from node_replication_tpu.obs.metrics import get_registry
    from node_replication_tpu.obs.recorder import get_tracer

    n_shards = int(np.prod(mesh.devices.shape))
    per_device = n_replicas // max(1, mesh.shape.get("replica", 1))
    reg = get_registry()
    reg.gauge("mesh.devices").set(n_shards)
    reg.gauge("mesh.replicas_per_device").set(per_device)
    get_tracer().emit(
        "mesh-place", wrapper=wrapper, devices=n_shards,
        replicas=n_replicas, per_device=per_device, tier=tier,
        shape=dict(mesh.shape),
    )


def shard_step(step_fn, mesh: Mesh, log_template, states_template,
               batch_spec: P | None = None, donate: bool = True):
    """Jit an (unjitted) `make_step`-style step with mesh shardings.

    Write/read batches are [R, B]-shaped: sharded over 'replica' like the
    states so each chip generates/answers only its shard's ops; the append
    concatenation all-gathers them (ICI) into the replicated log.
    """
    if batch_spec is None:
        batch_spec = P("replica")
    log_s = log_spec_tree(log_template, mesh)
    states_s = states_spec_tree(states_template, mesh)
    bs = NamedSharding(mesh, batch_spec)
    return jax.jit(
        step_fn,
        in_shardings=(log_s, states_s, bs, bs, bs, bs),
        out_shardings=(log_s, states_s, bs, bs),
        donate_argnums=(0, 1) if donate else (),
    )
