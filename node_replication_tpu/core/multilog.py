"""Multi-log node replication: the CNR (`cnr` crate) equivalent.

The reference's `cnr` partitions the operation stream over many logs by a
commutativity hash (`LogMapper`, `cnr/src/lib.rs:123-137`): conflicting ops
must map to the same log; commutative ops may map to different logs and are
then combined/replayed in parallel by per-log combiners
(`cnr/src/replica.rs:93-98`, `430-445`).

TPU-first re-design (SURVEY.md §7 "CNR"):

- The L logs are one stacked `LogState` with a leading log axis
  (`opcodes: int32[L, C]`, cursors `[L]`, `ltails: [L, R]`) — a pytree that
  shards naturally over a `Mesh` 'log' axis (the tensor/expert-parallel
  analog of the op stream, SURVEY.md §2.5 #3).
- `LogMapper` is a host-side function `(opcode, args) -> hash`; the hash is
  reduced `% nlogs` exactly as `cnr/src/replica.rs:435`.
- Per-log combiner locks disappear (lock-step); what survives is that each
  log gets its own independent append batch and its own replay scan —
  `vmap` over the log axis replaces parallel combiner threads, and
  dispatch against shared replica state must be commutative across logs
  within a step (the same contract `dispatch_mut(&self)` demands of the
  user's concurrent DS, `cnr/src/lib.rs:167`).
- Reads sync only their mapped log (`cnr/src/replica.rs:599-617`);
  `sync_log` targets one log (`cnr/src/replica.rs:579-597`).

Mesh placement: `MultiLogState` is the pytree `parallel/mesh.py:place`
shards over a ('replica', 'log') mesh — rings and per-log cursors on
their 'log' column, replica states (and the ltails replica dimension)
over 'replica' rows — and every exec path here is sharding-agnostic:
`MultiLogReplicated(mesh=...)` and `ShardedCnrRunner` run these same
programs with GSPMD inserting the collectives (the annotation tier;
tests/test_mesh_fleet.py pins the wrapper bit-identical to the
un-meshed twin).

Replay layout: `multilog_exec_all` vmaps the single-log scan over
(log × replica). Because ops on different logs commute by contract, applying
each log's span to disjoint *state partitions* is exact. The bundled
partitioned models (`models/partitioned.py`, `PartitionedModel`) provide
`split`/`merge` reshapes plus a per-partition sub-Dispatch, so all L scans
run as ONE vmapped computation — the parallel-combining payoff. For
monolithic states the replay falls back to sequential per-log folding.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from node_replication_tpu.core.log import LogSpec, gather_window
from node_replication_tpu.obs.metrics import get_registry
from node_replication_tpu.ops.encoding import (
    Dispatch,
    NOOP,
    apply_write,
    dispatch_reads,
)
from node_replication_tpu.utils.checks import check

PyTree = Any

# Multi-log replay-engine selection counters (host-side of the tier
# decision in `multilog_exec_all`; under jit they count per trace —
# see the `log.engine.*` note in core/log.py).
_m_ml_lockstep = get_registry().counter("multilog.engine.combined_lockstep")
_m_ml_combined = get_registry().counter("multilog.engine.combined")
_m_ml_part_scan = get_registry().counter("multilog.engine.partitioned_scan")
_m_ml_seq = get_registry().counter("multilog.engine.sequential")

# LogMapper: host-side commutativity hash (`cnr/src/lib.rs:123-137`).
LogMapper = Callable[[int, tuple], int]


@dataclasses.dataclass(frozen=True)
class MultiLogSpec:
    """Static config for a stacked multi-log (hashable jit static)."""

    nlogs: int
    capacity: int = 1 << 14
    n_replicas: int = 1
    arg_width: int = 3
    gc_slack: int = 1024

    def __post_init__(self):
        cap = max(int(self.capacity), 2 * self.gc_slack)
        cap = 1 << (cap - 1).bit_length()
        object.__setattr__(self, "capacity", cap)
        if self.nlogs < 1:
            raise ValueError("need at least one log")

    @property
    def mask(self) -> int:
        return self.capacity - 1

    def one_log(self) -> LogSpec:
        return LogSpec(
            capacity=self.capacity,
            n_replicas=self.n_replicas,
            arg_width=self.arg_width,
            gc_slack=self.gc_slack,
        )


class MultiLogState(NamedTuple):
    """L stacked rings; every cursor grows a leading log axis.

    Mirrors `cnr`'s `slog: Vec<Arc<Log>>` + per-log registration
    (`cnr/src/replica.rs:93-98`) as one shardable pytree.
    """

    opcodes: jax.Array  # int32[L, C]
    args: jax.Array  # int32[L, C, A]
    head: jax.Array  # int64[L]
    tail: jax.Array  # int64[L]
    ctail: jax.Array  # int64[L]
    ltails: jax.Array  # int64[L, R]


def multilog_init(spec: MultiLogSpec) -> MultiLogState:
    L, C = spec.nlogs, spec.capacity
    return MultiLogState(
        opcodes=jnp.full((L, C), NOOP, jnp.int32),
        args=jnp.zeros((L, C, spec.arg_width), jnp.int32),
        head=jnp.zeros((L,), jnp.int64),
        tail=jnp.zeros((L,), jnp.int64),
        ctail=jnp.zeros((L,), jnp.int64),
        ltails=jnp.zeros((L, spec.n_replicas), jnp.int64),
    )


def multilog_space(spec: MultiLogSpec, ml: MultiLogState) -> jax.Array:
    return jnp.maximum(
        spec.capacity - spec.gc_slack - (ml.tail - ml.head), 0
    )


def multilog_append(
    spec: MultiLogSpec,
    ml: MultiLogState,
    opcodes: jax.Array,  # int32[L, B] — already partitioned per log
    args: jax.Array,  # int32[L, B, A]
    counts: jax.Array,  # int64[L] — valid prefix per log
) -> MultiLogState:
    """Per-log batched append (each log's combiner append,
    `cnr/src/replica.rs:708`, vectorized over the log axis)."""
    B = opcodes.shape[1]
    lanes = jnp.arange(B, dtype=jnp.int64)[None, :]
    counts = jnp.asarray(counts, jnp.int64)
    valid = lanes < counts[:, None]
    slot = jnp.where(
        valid, (ml.tail[:, None] + lanes) & spec.mask, spec.capacity
    ).astype(jnp.int32)

    def scatter_one(ring, slots, vals):
        return ring.at[slots].set(vals, mode="drop")

    return ml._replace(
        opcodes=jax.vmap(scatter_one)(ml.opcodes, slot, opcodes),
        args=jax.vmap(scatter_one)(ml.args, slot, args),
        tail=ml.tail + counts,
    )


def _exec_one_log(spec, d, opcodes_ring, args_ring, tail, state, ltail,
                  window: int):
    """Single (log, replica) replay scan — same algorithm as
    `core/log.py:_exec_one` over one ring of the stack."""

    def body(state, j):
        pos = ltail + j
        active = pos < tail
        idx = (pos & spec.mask).astype(jnp.int32)
        opcode = jnp.where(active, opcodes_ring[idx], NOOP)
        state, resp = apply_write(d, state, opcode, args_ring[idx])
        return state, resp

    state, resps = lax.scan(body, state, jnp.arange(window, dtype=jnp.int64))
    return state, resps, jnp.minimum(ltail + window, tail)


def _exec_one_log_combined(spec, d, opcodes_ring, args_ring, tail, state,
                           ltail, window: int):
    """Combined twin of `_exec_one_log`: gather the pending window from
    the ring (positions past `tail` mask to NOOP — inactive under
    `window_apply`) and apply it as one reduction (`Dispatch.window_apply`
    semantics; bit-identical to the scan)."""
    if window == 0:
        return state, jnp.zeros((0,), jnp.int32), ltail
    opcodes, args = gather_window(
        spec, opcodes_ring, args_ring, ltail, tail, window
    )
    state, resps = d.window_apply(state, opcodes, args)
    return state, resps, jnp.minimum(ltail + window, tail)


def multilog_exec_window(
    spec: MultiLogSpec,
    d: Dispatch,
    ml: MultiLogState,
    states: PyTree,
    window: int,
):
    """Replay up to `window` pending entries of EVERY log into every
    replica, as one program: the multi-log combiner round's replay
    (`MultiLogReplicated`), with the log set as data, not as a static
    log index.

    The union window (the `core/log._catchup_union_plan` idea, per log):
    log l's `window` positions from its laggard's cursor `m_l`, past its
    tail read as NOOP; the L windows lie end to end, log after log, and
    replica r applies the positions of each log from its own cursor on,
    so replicas that stand at different positions on any log are
    replayed exactly, and each to `max(ltail, min(m_l + window, tail))`.
    Ops on different logs commute (the LogMapper contract,
    `cnr/src/lib.rs:123-137`), so log order is as good as any
    interleaving; each log's own order is kept. The window is applied by
    the model's `window_apply_stacked` where it has one (the hashmap's
    writes each touched slot's column of replicas), else each replica's
    masked window by a vmapped `window_apply`, else by a scan of
    `apply_write`.

    Returns `(ml, states, resps int32[L, R, window])`; `resps[l, r, i]`
    answers log l's entry at position `old ltails[l, r] + i`.
    """
    L, W, A = spec.nlogs, window, spec.arg_width
    start = jnp.min(ml.ltails, axis=1)  # [L]
    pos = start[:, None] + jnp.arange(W, dtype=jnp.int64)  # [L, W]
    idx = (pos & spec.mask).astype(jnp.int32)
    logs = jnp.broadcast_to(jnp.arange(L)[:, None], (L, W))
    opcodes = jnp.where(pos < ml.tail[:, None], ml.opcodes[logs, idx], NOOP)
    args = ml.args[logs, idx]  # [L, W, A]
    # [L, W, R]: the positions replica r applies, from its own cursor
    applies = ((pos[:, :, None] >= ml.ltails[:, None, :])
               & (opcodes != NOOP)[:, :, None])
    opcodes = opcodes.reshape(L * W)
    args = args.reshape(L * W, A)
    applies = applies.reshape(L * W, -1)
    if d.window_apply_stacked is not None:
        states, resps = d.window_apply_stacked(states, opcodes, args,
                                               applies)
    else:
        def one(state, mask):
            opc = jnp.where(mask, opcodes, NOOP)
            if d.window_apply is not None:
                return d.window_apply(state, opc, args)
            return lax.scan(lambda s, x: apply_write(d, s, x[0], x[1]),
                            state, (opc, args))

        states, resps = jax.vmap(one, in_axes=(0, 1), out_axes=(0, 1))(
            states, applies)
    # replica r's answers from its own cursor: offset ltails - m_l
    resps = resps.reshape(L, W, -1)
    off = (ml.ltails - start[:, None])[:, :, None] + jnp.arange(
        W, dtype=jnp.int64)  # [L, R, W]
    resps = jnp.take_along_axis(
        jnp.swapaxes(resps, 1, 2), jnp.minimum(off, W - 1).astype(jnp.int32),
        axis=2)
    resps = jnp.where(off < W, resps, 0)
    end = jnp.minimum(start + W, ml.tail)
    ltails = jnp.maximum(ml.ltails, end[:, None])
    ml = ml._replace(
        ltails=ltails,
        ctail=jnp.maximum(ml.ctail, jnp.max(ltails, axis=1)),
        head=jnp.min(ltails, axis=1),
    )
    return ml, states, resps


def multilog_exec_all(
    spec: MultiLogSpec,
    d: Dispatch,
    ml: MultiLogState,
    states: PyTree,
    window: int,
    partitioned: "PartitionedModel | None" = None,
    combined: bool | None = None,
    lockstep: bool = False,
):
    """Replay `window` pending entries of every log into every replica.

    With a `PartitionedModel` (`models/partitioned.py`) the L per-log scans
    run as ONE computation vmapped over (log × replica), each scan mutating
    only its disjoint state partition — the lock-step analog of L combiners
    replaying in parallel (`cnr/src/replica.rs:713-720`). Without it, logs
    fold sequentially per replica (still correct for any state; ops on
    different logs commute by the LogMapper contract so order is free).

    `combined` selects the per-(log, replica) replay engine when the
    partitioned sub-model provides `window_apply` (None = auto): each
    log's window collapses to one parallel reduction on its partition
    instead of a `window`-long scan — the multi-log form of the combined
    replay (`core/step.py`).

    `lockstep=True` declares the caller's precondition that every replica
    of a log starts at the same ltail (true inside `make_multilog_step`):
    the combined path then gathers each log's window ONCE (ltails[0]
    speaks for the fleet) and shares its sort across the replica vmap —
    without it the window (and its sort) is recomputed per (log, replica)
    because ltails are formally per-replica values. The precondition is
    verified only under debug checks (`utils/checks.check`, armed by
    `debug_checks(True)` around a `checked()` trace — zero-cost
    otherwise); an unchecked caller with divergent ltails silently gets
    ltails[0] imposed on all replicas (ADVICE r3).

    Returns `(ml, states, resps[L, R, window])`.
    """
    if partitioned is not None:
        if partitioned.nlogs != spec.nlogs:
            raise ValueError(
                f"PartitionedModel is {partitioned.nlogs}-way but the "
                f"multilog has {spec.nlogs} logs"
            )
        if combined is None:
            combined = partitioned.sub.window_apply is not None
        if combined and partitioned.sub.window_apply is None:
            raise ValueError(
                f"combined=True but {partitioned.sub.name} has no "
                f"window_apply"
            )
        exec_one = _exec_one_log_combined if combined else _exec_one_log
        # [R, ...] states → per-replica split → [R, L, sub...] → [L, R, ...]
        stacked = jax.vmap(partitioned.split)(states)
        stacked = jax.tree.map(lambda x: jnp.moveaxis(x, 0, 1), stacked)

        if combined and lockstep and window > 0:
            # nrlint: disable=obs-in-traced — per-trace tier counter
            _m_ml_lockstep.inc()

            # lock-step: gather each log's window once (ltails[0] speaks
            # for the fleet) so the window-wide sort inside window_apply
            # stays UNBATCHED across the replica vmap
            def per_log(opc, arg, tail, sub_states, ltails):
                lt0 = ltails[0]
                check(
                    jnp.all(ltails == lt0),
                    "lockstep multilog replay requires equal per-replica "
                    "ltails on every log",
                )
                opc_w, args_w = gather_window(
                    spec, opc, arg, lt0, tail, window
                )
                new_states, resps = jax.vmap(
                    lambda s: partitioned.sub.window_apply(
                        s, opc_w, args_w
                    )
                )(sub_states)
                new_lt = jnp.minimum(lt0 + window, tail)
                return (
                    new_states,
                    resps,
                    jnp.broadcast_to(new_lt, ltails.shape),
                )
        else:
            # nrlint: disable=obs-in-traced — per-trace tier counter
            (_m_ml_combined if combined else _m_ml_part_scan).inc()

            def per_log(opc, arg, tail, sub_states, ltails):
                return jax.vmap(
                    lambda s, lt: exec_one(
                        spec, partitioned.sub, opc, arg, tail, s, lt,
                        window,
                    )
                )(sub_states, ltails)

        new_subs, resps, new_ltails = jax.vmap(per_log)(
            ml.opcodes, ml.args, ml.tail, stacked, ml.ltails
        )
        new_subs = jax.tree.map(lambda x: jnp.moveaxis(x, 0, 1), new_subs)
        states = jax.vmap(partitioned.merge)(new_subs)
    else:
        # nrlint: disable=obs-in-traced — per-trace tier counter
        _m_ml_seq.inc()
        resps_list = []
        ltails_list = []
        for l in range(spec.nlogs):
            states, resps_l, lt_l = jax.vmap(
                lambda s, lt, _l=l: _exec_one_log(
                    spec, d, ml.opcodes[_l], ml.args[_l], ml.tail[_l],
                    s, lt, window,
                )
            )(states, ml.ltails[l])
            resps_list.append(resps_l)
            ltails_list.append(lt_l)
        resps = jnp.stack(resps_list)
        new_ltails = jnp.stack(ltails_list)

    ml = ml._replace(
        ltails=new_ltails,
        ctail=jnp.maximum(ml.ctail, jnp.max(new_ltails, axis=1)),
        head=jnp.min(new_ltails, axis=1),
    )
    return ml, states, resps


def is_log_synced_for_reads(
    ml: MultiLogState, log_idx: int, ridx: int, ctail: jax.Array
) -> jax.Array:
    """Reads sync only their mapped log (`cnr/src/replica.rs:599-617`)."""
    return ml.ltails[log_idx, ridx] >= ctail


def make_multilog_step(
    dispatch: Dispatch,
    spec: MultiLogSpec,
    writes_per_log: int,
    reads_per_replica: int,
    partitioned: "PartitionedModel | None" = None,
    jit: bool = True,
    donate: bool = True,
    combined: bool | None = None,
    debug: bool = False,
):
    """Fused CNR step: per-log append → per-log replay → reads.

    The batch is already LogMapper-partitioned (see `partition_ops`):
    `wr_opcodes int32[L, B]`, `wr_args int32[L, B, A]`, `counts int64[L]`.
    Each log appends its bucket and every replica replays every log's new
    span — the lock-step analog of L parallel combiners
    (`cnr/src/replica.rs:673-720`). Reads run after replay against local
    replica state (per-log read sync holds trivially).

    Returns `(ml, states, wr_resps int32[L, R, B], rd_resps int32[R, Br])`.
    Precondition: all replicas synced on all logs at entry (true by
    induction when driven step-after-step).

    `debug=True` compiles the device-side invariants (`utils/checks`,
    here the lockstep equal-ltails precondition) into the program via
    checkify and raises on violation — the `make_multilog_step` twin of
    `NodeReplicated(debug=True)`. Donation is disabled in debug mode.
    """
    B = int(writes_per_log)
    Br = int(reads_per_replica)
    max_batch = spec.capacity - spec.gc_slack
    if B > max_batch:
        raise ValueError(
            f"per-log batch {B} exceeds appendable capacity {max_batch}"
        )

    def step(ml, states, wr_opcodes, wr_args, counts, rd_opcodes, rd_args):
        ml = multilog_append(spec, ml, wr_opcodes, wr_args, counts)
        ml, states, wr_resps = multilog_exec_all(
            spec, dispatch, ml, states, B, partitioned=partitioned,
            combined=combined, lockstep=True,
        )
        rd_resps = dispatch_reads(dispatch, states, rd_opcodes, rd_args)
        return ml, states, wr_resps, rd_resps

    if debug:
        from node_replication_tpu.utils.checks import checked, debug_checks

        inner = checked(step)
        if jit:
            inner = jax.jit(inner)

        def step_checked(*args):
            with debug_checks(True):  # checks live at (re-)trace time
                err, out = inner(*args)
            err.throw()
            return out

        return step_checked
    if jit:
        step = jax.jit(step, donate_argnums=(0, 1) if donate else ())
    return step


def partition_ops(
    mapper: LogMapper,
    nlogs: int,
    ops: list[tuple[int, tuple]],
    arg_width: int,
    pad_to: int | None = None,
):
    """Host-side LogMapper application: split an op list into per-log
    fixed-shape batches (`hash % nlogs`, `cnr/src/replica.rs:435`).

    Returns `(opcodes int32[L, B], args int32[L, B, A], counts int64[L],
    placements)` where `placements[i] = (log, slot)` for op i.
    """
    import numpy as np

    buckets: list[list[tuple[int, tuple]]] = [[] for _ in range(nlogs)]
    placements = []
    for opcode, args in ops:
        h = mapper(opcode, args) % nlogs
        placements.append((h, len(buckets[h])))
        buckets[h].append((opcode, args))
    B = pad_to if pad_to is not None else max(
        1, max(len(b) for b in buckets)
    )
    opcodes = np.full((nlogs, B), NOOP, np.int32)
    args_arr = np.zeros((nlogs, B, arg_width), np.int32)
    counts = np.zeros((nlogs,), np.int64)
    for l, bucket in enumerate(buckets):
        if len(bucket) > B:
            raise ValueError(f"log {l} bucket {len(bucket)} > pad {B}")
        counts[l] = len(bucket)
        for j, (opcode, a) in enumerate(bucket):
            opcodes[l, j] = opcode
            args_arr[l, j, : len(a)] = a
    return (
        jnp.asarray(opcodes),
        jnp.asarray(args_arr),
        jnp.asarray(counts),
        placements,
    )
