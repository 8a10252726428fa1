"""Explicit-collective multi-chip replay: shard_map + ICI primitives.

PRODUCTION STATUS: this module is no longer a dryrun demo. Since the
mesh-sharded-fleet work, `make_shmap_exec` IS `NodeReplicated`'s exec
round on a mesh (`NodeReplicated(mesh=..., collectives="shmap")` — the
default tier for scan-engine models), and `make_ring_exec` backs the
ring catch-up tier `NodeReplicated.sync()` takes for large uniform
backlogs. `make_shmap_step` remains the fused lock-step batch path
(`ShardedRunner`'s explicit twin and `__graft_entry__.dryrun_multichip`'s
convergence probe), and `MeshFusedEngine` is the MESH-FUSED exec tier
(run only under `NodeReplicated(mesh=..., engine="pallas")`): the
one-launch fused append+replay round embedded in a shard_map program so
a lock-step combiner round on an N-device fleet stays one launch per
device with the cursor lattice joined over ICI. Per-tier
selection counters live next to the other engine tiers
(`log.engine.shmap`, `log.engine.mesh_fused`, `nr.exec.engine.ring`,
`nr.exec.mesh.*` — core/log.py, core/replica.py).

`parallel/mesh.py` scales by annotation (GSPMD inserts the collectives);
this module is the hand-scheduled path for the places where owning the
communication pattern matters (SURVEY.md §2.6 "TPU-native equivalent"):

1. `make_shmap_step` — the fused append→replay→read step as a `shard_map`
   program: each chip generates its replica shard's write batch, the full
   append span is assembled with an explicit `all_gather` over the ICI
   ring (the moral equivalent of the reference's cross-replica entry
   publication, `nr/src/log.rs:391-418`), every chip appends the identical
   span to its local log copy, and replays only its shard. `ctail`/`head`
   bookkeeping uses `pmax`/`pmin` over the mesh axis — `fetch_max` /
   `min(ltails)` (`nr/src/log.rs:520-523`, `536-580`) as lattice
   reductions over ICI.

2. `make_ring_exec` — sequence parallelism for the op stream: a LONG
   replay window (W entries) is sharded over P chips; chunks rotate around
   the ICI ring (`ppermute`, ring-attention style) while replica-state
   shards stay resident. Unlike attention, log replay does NOT commute
   across chunks, so each chip masks its activity window to consume chunks
   in order: chip d sees chunk `(d + t) % P` at round t and is active for
   `t ∈ [P-d, 2P-d-1]` — a software pipeline whose fill/drain bubbles are
   masked NOOP replays (padded slots replay as identity, so masking is
   free of control flow). After `2P-1` rounds every replica shard has
   applied all W entries in log order.

   This is the structural analog of CNR's "scale the stream" story
   (SURVEY.md §5 long-context): one logical op stream, sharded transport,
   per-shard compute, order restored by schedule rather than by lock.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from node_replication_tpu.core.log import (
    LogSpec,
    LogState,
    _FAR,
    _exec_one,
    _m_engine_shmap,
)
from node_replication_tpu.ops.pallas_ring import FusedEngineHost
from node_replication_tpu.utils.compat import shard_map
from node_replication_tpu.ops.encoding import (
    Dispatch,
    NOOP,
    apply_write,
    dispatch_reads,
)


def make_shmap_step(
    dispatch: Dispatch,
    spec: LogSpec,
    mesh: Mesh,
    writes_per_replica: int,
    reads_per_replica: int,
    axis: str = "replica",
):
    """Explicit-collective twin of `core/step.make_step`.

    Shapes are the global ones (`[R, Bw]` etc.); states and batches shard
    over `axis`, the log replicates. Requires `R % mesh.shape[axis] == 0`.
    Returns a jitted step with the same signature/results as `make_step`.
    """
    R = spec.n_replicas
    Bw = int(writes_per_replica)
    nshards = mesh.shape[axis]
    if R % nshards:
        raise ValueError(f"R={R} not divisible by {nshards} shards")
    Rl = R // nshards
    span = R * Bw

    def local(log, states_l, wr_opc_l, wr_args_l, rd_opc_l, rd_args_l):
        # [Rl, Bw] local batches → [R*Bw] global span over the ICI ring.
        opc = lax.all_gather(wr_opc_l, axis, tiled=True).reshape(span)
        args = lax.all_gather(wr_args_l, axis, tiled=True).reshape(
            span, spec.arg_width
        )
        # every chip appends the identical span to its local log copy
        lanes = jnp.arange(span, dtype=jnp.int64)
        slot = ((log.tail + lanes) & spec.mask).astype(jnp.int32)
        log = log._replace(
            opcodes=log.opcodes.at[slot].set(opc),
            args=log.args.at[slot].set(args),
            tail=log.tail + span,
        )
        # replay the appended window into the local replica shard only
        states_l, resps_l, new_ltails_l = jax.vmap(
            lambda s, lt: _exec_one(spec, dispatch, log, s, lt, span)
        )(states_l, log.ltails)
        # lattice bookkeeping over the mesh axis: fetch_max(ctail),
        # min(ltails) GC — pmax/pmin ride ICI
        local_max = jnp.max(new_ltails_l)
        local_min = jnp.min(new_ltails_l)
        log = log._replace(
            ltails=new_ltails_l,
            ctail=jnp.maximum(log.ctail, pmax64(local_max, axis)),
            head=pmin64(local_min, axis),
        )
        # own responses: local replica r sits at global index
        # didx*Rl + r; its writes occupy window offsets [g*Bw, (g+1)*Bw)
        didx = lax.axis_index(axis)
        g = didx * Rl + jnp.arange(Rl, dtype=jnp.int32)[:, None]
        own = g * Bw + jnp.arange(Bw, dtype=jnp.int32)[None, :]
        wr_resps_l = jnp.take_along_axis(resps_l, own, axis=1)
        rd_resps_l = dispatch_reads(dispatch, states_l, rd_opc_l, rd_args_l)
        return log, states_l, wr_resps_l, rd_resps_l

    shardy = P(axis)
    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(
            LogState(opcodes=P(), args=P(), head=P(), tail=P(), ctail=P(),
                     ltails=shardy),
            jax.tree.map(lambda _: shardy, dispatch.init_state()),
            shardy, shardy, shardy, shardy,
        ),
        out_specs=(
            LogState(opcodes=P(), args=P(), head=P(), tail=P(), ctail=P(),
                     ltails=shardy),
            jax.tree.map(lambda _: shardy, dispatch.init_state()),
            shardy, shardy,
        ),
        check_vma=False,
    )
    return jax.jit(fn)


def pmin64(v, axis: str):
    """Min of an int64 cursor over the mesh axis. The v5e compiler
    lowers only Sum all-reduces of 64-bit values (`lax.pmin` on int64
    is refused), so the per-shard scalars are all-gathered and reduced
    locally — exact, and a few bytes per shard."""
    return jnp.min(lax.all_gather(v, axis))


def pmax64(v, axis: str):
    """Max twin of `pmin64`."""
    return jnp.max(lax.all_gather(v, axis))


def _cursor_lattice_join(log, new_lt, fenced_mask, reduce_min,
                         reduce_max):
    """The cross-shard half of the exec-round cursor lattice — ONE
    definition for the shmap chain and both mesh-fused forms, so the
    GC invariant cannot drift between tiers:

    - `ctail = max(ctail, reduce_max(max new_lt))` (fetch_max,
      `nr/src/log.rs:520-523`);
    - `head` through the `_gc_head` reduction: min over UNFENCED
      cursors with the fenced mask composed via the `_FAR` sentinel
      (an all-fenced fleet holds head still), clamped monotone
      (`max(head, ...)` — a no-op for valid cursors, where the min
      already sits at/above head, but it keeps head monotone by
      construction like `core/log._gc_head`).

    `reduce_min`/`reduce_max` close over the cross-shard reduction:
    `pmin64`/`pmax64` over ICI inside a shard_map local, the
    identity for host-side joins over already-concatenated cursors
    (`MeshFusedEngine._sliced_round`). Returns `log` with
    ctail/head replaced (the caller installs `ltails`)."""
    ctail = jnp.maximum(log.ctail, reduce_max(jnp.max(new_lt)))
    if fenced_mask is None:
        head = jnp.maximum(log.head, reduce_min(jnp.min(new_lt)))
    else:
        masked = jnp.where(
            jnp.asarray(fenced_mask, bool), jnp.int64(_FAR), new_lt
        )
        gmin = reduce_min(jnp.min(masked))
        head = jnp.where(
            gmin >= jnp.int64(_FAR), log.head,
            jnp.maximum(log.head, gmin),
        )
    return log._replace(ctail=ctail, head=head)


def make_shmap_exec(
    dispatch: Dispatch,
    spec: LogSpec,
    mesh: Mesh,
    window: int,
    axis: str = "replica",
    fenced: bool = False,
    donate: bool = True,
):
    """Explicit-collective twin of `core/log.py:log_exec_all` — the
    catch-up/exec-round half of `make_shmap_step`, promoted into
    `NodeReplicated._exec_round` for mesh-sharded fleets.

    Unlike the fused step, cursors may DIVERGE: each chip replays its
    replica shard from that shard's own `ltails` (the vmapped
    `_exec_one` scan — bit-identical to every engine by the
    differential contracts), and the cursor lattice is joined over ICI:
    `ctail = max(ctail, pmax(max local ltails))` (fetch_max,
    `nr/src/log.rs:520-523`) and `head = pmin(min local ltails)`
    (`advance_head` GC, `nr/src/log.rs:536-580`). The log's ring
    arrays are replicated, so replay reads are chip-local; the only
    cross-chip traffic is the two scalar lattice reductions.

    `fenced=True` builds the quarantine-mask variant
    (`fault/health.py`): the returned fn takes an extra bool[R] mask
    sharded over `axis`; fenced replicas are frozen at their ltail
    (limits) and excluded from the GC-head reduction — the masked min
    uses the `_FAR` sentinel, and an all-fenced fleet holds `head`
    still — exactly `core/log.py:_freeze_limits`/`_gc_head` with the
    min taken over ICI instead of one device. This keeps the
    fenced-head GC mask correct when the fenced replica lives on a
    different chip than the combiner.

    Returns a jitted `exec(log, states[, fenced]) -> (log, states,
    resps)` with the `log_exec_all` response-layout contract:
    `resps[r, i]` answers logical position `old_ltails[r] + i`.
    Requires `R % mesh.shape[axis] == 0`.
    """
    R = spec.n_replicas
    nshards = mesh.shape[axis]
    if R % nshards:
        raise ValueError(f"R={R} not divisible by {nshards} shards")
    _m_engine_shmap.inc()

    def local(log, states_l, *mask):
        lt_l = log.ltails  # the LOCAL [R/nshards] cursor shard
        if fenced:
            fenced_l = mask[0]
            # _freeze_limits, shard-local: a fenced replica is frozen
            # at its own ltail; others replay to the tail
            limits_l = jnp.where(fenced_l, lt_l, jnp.int64(_FAR))
            states_l, resps_l, new_lt = jax.vmap(
                lambda s, lt, lim: _exec_one(
                    spec, dispatch, log, s, lt, window, lim
                )
            )(states_l, lt_l, limits_l)
        else:
            fenced_l = None
            states_l, resps_l, new_lt = jax.vmap(
                lambda s, lt: _exec_one(spec, dispatch, log, s, lt,
                                        window)
            )(states_l, lt_l)
        # ctail/head joined over ICI (_gc_head with the fenced mask
        # composed via _FAR — the one shared lattice-join definition)
        log = _cursor_lattice_join(
            log, new_lt, fenced_l,
            lambda v: pmin64(v, axis), lambda v: pmax64(v, axis),
        )
        log = log._replace(ltails=new_lt)
        return log, states_l, resps_l

    shardy = P(axis)
    log_specs = LogState(opcodes=P(), args=P(), head=P(), tail=P(),
                         ctail=P(), ltails=shardy)
    state_specs = jax.tree.map(lambda _: shardy, dispatch.init_state())
    in_specs = (log_specs, state_specs) + ((shardy,) if fenced else ())
    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=(log_specs, state_specs, shardy),
        check_vma=False,
    )
    return jax.jit(fn, donate_argnums=(0, 1) if donate else ())


def make_ring_exec(
    dispatch: Dispatch,
    mesh: Mesh,
    axis: str = "replica",
):
    """Pipelined ring replay of a long, device-sharded op window.

    `ring_exec(opcodes, args, states)`:
      opcodes int32[W], args int32[W, A]  — sharded over `axis` in P chunks
      states  [R, ...] pytree            — replica shards over `axis`

    Every replica applies all W entries in log order; chunks move over ICI
    (`ppermute`), states stay resident. W and R must divide by P.
    Returns the updated states.
    """
    nshards = mesh.shape[axis]

    def apply_chunk(states_l, opc_l, args_l):
        def per_replica(state):
            def body(st, x):
                o, a = x
                st, _ = apply_write(dispatch, st, o, a)
                return st, jnp.int32(0)

            st, _ = lax.scan(body, state, (opc_l, args_l))
            return st

        return jax.vmap(per_replica)(states_l)

    def local(opc_l, args_l, states_l):
        didx = lax.axis_index(axis)
        # chunks rotate backward: chunk c sits on chip (c - t) % P at
        # round t, so chip d hosts chunk (d + t) % P
        perm = [(i, (i - 1) % nshards) for i in range(nshards)]
        opc, args = opc_l, args_l
        states = states_l
        for t in range(1, 2 * nshards):
            opc = lax.ppermute(opc, axis, perm)
            args = lax.ppermute(args, axis, perm)
            # ordered consumption: chip d applies chunks 0..P-1 during
            # rounds [P-d, 2P-d-1]; outside the window the chunk is
            # masked to NOOP (identity replay) — pipeline bubbles as
            # masked compute, no control flow
            active = (t >= nshards - didx) & (t <= 2 * nshards - didx - 1)
            masked = jnp.where(active, opc, jnp.int32(NOOP))
            states = apply_chunk(states, masked, args)
        return states

    shardy = P(axis)
    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(shardy, shardy,
                  jax.tree.map(lambda _: shardy, dispatch.init_state())),
        out_specs=jax.tree.map(lambda _: shardy, dispatch.init_state()),
        check_vma=False,
    )
    return jax.jit(fn)


class MeshFusedEngine(FusedEngineHost):
    """The MESH-FUSED exec tier: the fused append+replay engine's raw
    round (`ops/pallas_replay.FusedHashmapEngine` /
    `ops/pallas_vspace.FusedVspaceEngine`) embedded in a `shard_map`
    program over the replica mesh, so one combiner round on an
    N-device fleet is ONE shard_map-wrapped Pallas launch per device —
    issued as a single program — instead of the shmap tier's
    append-program → exec-program chain. A meshed `NodeReplicated`
    builds it only under `engine="pallas"`; `engine="auto"` keeps the
    shmap/gspmd chain.

    Composition (the junction of the PR 9 and PR 10 tiers):

    - the ring planes and scalar cursors are REPLICATED (`P()`), the
      replica-axis state blocks and `ltails` ride `P('replica')` —
      exactly the shard-slice layout the fused engines' chunk calls
      already use (tests/test_pallas_fused.py pins the composability:
      a per-shard invocation of the chunk calls IS the shard-local
      program);
    - each shard runs the whole fused round locally — append DMA over
      its replicated ring copy (identical spans on every chip, zero
      communication, the `parallel/mesh.py` replicated-log economics),
      in-order replay into its `P('replica')` state blocks, response
      gather for its own lanes;
    - the cursor lattice is joined over ICI exactly like
      `make_shmap_exec`: `ctail = max(ctail, pmax(max ltails))` and
      `head` as the `_gc_head` reduction with the fenced lane mask
      composed through the `_FAR` sentinel — so fenced-head GC stays
      correct when the quarantined replica lives on another chip, and
      an all-fenced shard cannot drag `head` backwards.

    Implements the engine contract `core/replica._try_fused_round`
    routes rounds through (`supports`/`launches`/`supports_fenced`/
    `round`), so the wrapper's eligibility check, WAL journaling,
    deferred-readback split rounds (`defer=True` issues the meshed
    launch at `_begin_round`, reads back at `_finish_round` — the
    serve pipeline's overlap works meshed), and bit-identity contract
    all apply unchanged. `tier`/`devices` redirect the shared
    instrumentation: rounds count under `log.engine.mesh_fused` and
    `kernel-launch` events carry `devices=`. `launches(window)` is the
    PER-DEVICE launch count (1 unless MAX_GRID or VMEM splits a
    shard) — the number that must hold at 1 as devices scale
    (`bench.py --kernel --kernel-devices`).

    Compilation policy: on TPU `round_fn` returns the shard_map
    program and the inherited round cache jits it with log+states
    donated. In interpret mode jit is unavailable (jit + interpret +
    the package's x64 default trips the MLIR where-fn dtype clash, the
    same reason every interpret test passes jit=False) and EAGER
    shard_map costs seconds per invocation on this jax, so the
    interpret rounds run `_sliced_round` instead: the per-shard inner
    round invoked eagerly on each `P('replica')` slice with the cursor
    lattice joined host-side — by construction the exact computation
    the shard_map local performs (the chunk call IS the shard-local
    program, and the joins are the same max/min/_FAR algebra as the
    pmax/pmin reductions). `_shmap_round` stays callable either way,
    and tests/test_mesh_fleet.py pins the two paths bit-identical
    against each other so the program the TPU jits is covered by the
    CPU suite.
    """

    tier = "mesh_fused"

    def __init__(self, dispatch, spec: LogSpec, mesh: Mesh,
                 axis: str = "replica", interpret: bool | None = None):
        if dispatch.fused_factory is None:
            raise ValueError(
                f"{dispatch.name} has no fused_factory (no fused "
                f"kernel to mesh-wrap)"
            )
        nshards = mesh.shape[axis]
        if spec.n_replicas % nshards:
            raise ValueError(
                f"R={spec.n_replicas} not divisible by {nshards} "
                f"mesh shards"
            )
        # the shard-local engine: the SAME ring/capacity, the shard's
        # slice of the replica axis — the factory raising ValueError
        # means "no fused form at this config", exactly as un-meshed
        shard_spec = dataclasses.replace(
            spec, n_replicas=spec.n_replicas // nshards
        )
        self.inner = dispatch.fused_factory(shard_spec,
                                            interpret=interpret)
        self.dispatch = dispatch
        self.spec = spec
        self.mesh = mesh
        self.axis = axis
        self.devices = int(nshards)
        self.supports_fenced = type(self.inner).supports_fenced
        self.interpret = bool(self.inner.interpret)
        self._init_host()

    def supports(self, window: int) -> bool:
        return self.inner.supports(window)

    def launches(self, window: int) -> int:
        """PER-DEVICE kernel launches per round (the shards run
        concurrently inside one program)."""
        return self.inner.launches(window)

    def round_fn(self, window: int, fenced: bool = False):
        """MODEL-layout round: `(log, states, opcodes, args, count[,
        fenced_vec]) -> (log, states, resps[R, W])` with the
        `FusedEngineHost.round` entry contract (cached +
        jitted/instrumented by the base class). The shard_map program
        on TPU, the bit-identical sliced composition in interpret mode
        (see the class docstring's compilation policy)."""
        if self.interpret:
            return self._sliced_round(window, fenced)
        return self._shmap_round(window, fenced)

    def _sliced_round(self, window: int, fenced: bool = False):
        """The shard-sliced twin of `_shmap_round`: each shard's slice
        runs the inner fused round eagerly and the cursor lattice is
        joined host-side with the same max/min/_FAR algebra the
        shard_map local expresses as pmax/pmin — bit-identical by the
        shard-slice composability contract
        (tests/test_pallas_fused.py), and pinned against the real
        shard_map program in tests/test_mesh_fleet.py."""
        inner_fn = self.inner.round_fn(window, fenced)
        nsh = self.devices
        Rl = self.spec.n_replicas // nsh

        def entry(log, states, opcodes, args, count, *mask):
            fen = mask[0] if fenced else None
            lt_parts, st_parts, resp_parts = [], [], []
            out_log = None
            for s in range(nsh):
                sl = slice(s * Rl, (s + 1) * Rl)
                log_s = log._replace(ltails=log.ltails[sl])
                states_s = jax.tree.map(lambda x: x[sl], states)
                fen_s = None if fen is None else fen[sl]
                out_log, states_s, resps_s = inner_fn(
                    log_s, states_s, opcodes, args, count, fen_s
                )
                lt_parts.append(out_log.ltails)
                st_parts.append(states_s)
                resp_parts.append(resps_s)
            # every shard computed identical ring planes + tail; the
            # cross-shard lattice join runs over the concatenated
            # cursors (identity reductions — same algebra as the
            # shard_map form's pmin/pmax)
            new_lt = jnp.concatenate(lt_parts)
            out_log = _cursor_lattice_join(
                out_log._replace(ctail=log.ctail, head=log.head),
                new_lt, fen if fenced else None,
                lambda v: v, lambda v: v,
            )._replace(ltails=new_lt)
            states = jax.tree.map(
                lambda *xs: jnp.concatenate(xs, axis=0), *st_parts
            )
            resps = jnp.concatenate(resp_parts, axis=0)
            return out_log, states, resps

        return entry

    def _shmap_round(self, window: int, fenced: bool = False):
        """The shard_map program itself: per-shard inner round +
        ctail/head joined as pmax/pmin lattice reductions over ICI
        (with the fenced mask composed through the `_FAR` sentinel).
        What `round_fn` returns on TPU; callable eagerly in interpret
        mode for the sliced-vs-shmap pinning test."""
        inner_fn = self.inner.round_fn(window, fenced)
        axis = self.axis

        def local(log, states_l, opcodes, args, count, *mask):
            fen_l = mask[0] if fenced else None
            # the shard-local fused round: append DMA (replicated ring
            # copy), replay + response gather for this shard's lanes,
            # and the SHARD-LOCAL cursor lattice
            log, states_l, resps_l = inner_fn(
                log, states_l, opcodes, args, count, fen_l
            )
            # re-join ctail/head over ICI: the shard-local lattice only
            # saw this shard's cursors (a fenced lane elsewhere must
            # still hold GC, a live lane elsewhere must still advance
            # ctail) — the same shared join as make_shmap_exec
            log = _cursor_lattice_join(
                log, log.ltails, fen_l,
                lambda v: pmin64(v, axis),
                lambda v: pmax64(v, axis),
            )
            return log, states_l, resps_l

        shardy = P(axis)
        log_specs = LogState(opcodes=P(), args=P(), head=P(), tail=P(),
                             ctail=P(), ltails=shardy)
        state_specs = jax.tree.map(
            lambda _: shardy, self.dispatch.init_state()
        )
        in_specs = (log_specs, state_specs, P(), P(), P())
        if fenced:
            in_specs += (shardy,)
        fn = shard_map(
            local,
            mesh=self.mesh,
            in_specs=in_specs,
            out_specs=(log_specs, state_specs, shardy),
            check_vma=False,
        )

        def entry(log, states, opcodes, args, count, *mask):
            # scalar count crosses the shard_map boundary as an array
            # (eager shard_map cannot shard a Python int)
            return fn(log, states, opcodes, args,
                      jnp.asarray(count, jnp.int64), *mask)

        return entry

    # round() — the host entry with the per-(window, fenced) program
    # cache, eager-in-interpret jit policy, metrics and the
    # kernel-launch event (now devices-stamped) — is inherited from
    # FusedEngineHost (ops/pallas_ring.py)
