"""The shared operation log as a device-resident ring buffer.

TPU-native re-design of the reference's lock-free MPMC ring
(`nr/src/log.rs`). The mapping (SURVEY.md §2.6, §7):

- `Entry<T>` cells with `alivef` liveness bits (`nr/src/log.rs:51-65`) become
  a struct-of-arrays ring `(opcodes: int32[L], args: int32[L, A])`. Liveness
  parity (`lmasks`) disappears entirely: within a lock-step append→replay
  step, append happens-before replay by data dependence, so an entry is live
  iff its logical position is `< tail`.
- The CAS tail-reservation loop (`nr/src/log.rs:391-399`) becomes a batched
  reserve-then-write: the caller presents a fixed-shape batch plus a valid
  count; slots `[tail, tail+count)` are filled with one masked scatter and
  `tail` advances once. Cross-replica batches are concatenated by the step
  builder (`core/step.py`) with prefix-sum offsets — the whole-fleet append
  is one scatter, no contention point at all.
- `exec` (`nr/src/log.rs:473-524`) becomes `log_exec_all`: a `lax.scan` over
  a static replay window, vmapped over replicas, each starting from its own
  `ltails[r]` with per-position `pos < tail` masking (per-replica divergent
  progress, SURVEY.md §7 "hard parts").
- `advance_head` GC (`nr/src/log.rs:536-580`) is the reduction
  `head = min(ltails)`, folded into `log_exec_all`. "Help replay before
  appending when full" (`nr/src/log.rs:364-387`) becomes the host-side rule:
  if `log_space` cannot fit the batch, run replay windows first
  (`core/replica.py`).
- `ctail` (completed tail, `nr/src/log.rs:520-523` fetch_max) is
  `max(ctail, max(new ltails))`.

Logical positions (`head`/`tail`/`ctail`/`ltails`) are monotonically
increasing int64 scalars; the physical slot is `pos & (L-1)` with L a power
of two (`nr/src/log.rs:194-196`, `527-530`).

Mesh placement: every function here is sharding-agnostic — under the
canonical mesh placement (`parallel/mesh.py:place`: ring arrays and
scalar cursors replicated, `ltails` and the replica axis of `states`
sharded over 'replica') the same programs run across a TPU mesh with
GSPMD inserting the collectives, and `parallel/collectives.py:
make_shmap_exec` is the explicit-collective twin of `log_exec_all`
(same lattice bookkeeping as `pmax`/`pmin` over ICI). The sharded and
unsharded programs are differentially pinned bit-identical in
tests/test_mesh_fleet.py.
"""

from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from node_replication_tpu.obs.metrics import get_registry
from node_replication_tpu.ops.encoding import Dispatch, NOOP, apply_write
from node_replication_tpu.utils.checks import check

PyTree = Any

# Engine-dispatch counters: which replay tier `log_catchup_all` routed a
# call to (scan / per-replica window_apply / union-window plan; the
# pallas tier is counted at its construction site, ops/pallas_replay via
# bench.py). These increment on the HOST side of the tier decision, so
# under jit they count per trace/compile; eager callers (and the
# recovery loop's first call, re-trace after fleet growth, …) count per
# invocation. Per-round engine usage of the stateful wrappers is the
# separate `nr.exec.engine.*` / `cnr.exec.rounds` family.
_m_engine_scan = get_registry().counter("log.engine.scan")
_m_engine_window = get_registry().counter("log.engine.window_apply")
_m_engine_union = get_registry().counter("log.engine.union_plan")
_m_idle_skips = get_registry().counter("log.engine.idle_skip")
# mesh tier: shard_map exec programs built by parallel/collectives.py
# (make_shmap_exec — counted per build, like the per-trace counters
# above; per-ROUND mesh usage is the wrapper's nr.exec.mesh.* family)
_m_engine_shmap = get_registry().counter("log.engine.shmap")
# fused pallas tier: whole combiner rounds (append + replay + response
# gather) executed as one kernel launch (`ops/pallas_replay.py:
# FusedHashmapEngine`, routed by `core/replica._try_fused_round` /
# the CNR twin). Counted per ROUND on the host side of the jit
# boundary — fused rounds are host-invoked, so unlike the per-trace
# counters above this one is an exact round count.
_m_engine_pallas_fused = get_registry().counter("log.engine.pallas_fused")
# mesh-fused tier: the same one-launch fused round embedded in a
# shard_map program over the replica mesh
# (`parallel/collectives.py:MeshFusedEngine`) — exact per-round host
# count, like the pallas_fused counter above.
_m_engine_mesh_fused = get_registry().counter("log.engine.mesh_fused")

# Default number of log entries. The reference defaults to 32 MiB of 64-byte
# entries = 2^19 slots "based on the ASPLOS 2017 paper" (`nr/src/log.rs:19-22`);
# device HBM is more precious than DRAM, and a 2^16-entry ring already covers
# the largest single-step replay window we schedule.
DEFAULT_LOG_ENTRIES = 1 << 16

# GC slack: an appender must leave this many slots between tail and head so
# laggards can catch up before slots are overwritten. The reference uses
# MAX_PENDING_OPS * MAX_THREADS_PER_REPLICA = 8192 (`nr/src/log.rs:36`).
GC_FROM_HEAD = 8192

# Spin-diagnostic threshold analog: after this many fruitless host-side
# replay rounds the watchdog warns (`nr/src/log.rs:43` WARN_THRESHOLD).
WARN_ROUNDS = 64


@dataclasses.dataclass(frozen=True)
class LogSpec:
    """Static log configuration (hashable: used as a jit static argument).

    `capacity` is rounded up to a power of two with a floor of
    `2 * gc_slack`, mirroring `Log::new` (`nr/src/log.rs:184-196`).
    """

    capacity: int = DEFAULT_LOG_ENTRIES
    n_replicas: int = 1
    arg_width: int = 3
    gc_slack: int = GC_FROM_HEAD

    def __post_init__(self):
        cap = max(int(self.capacity), 2 * self.gc_slack)
        cap = 1 << (cap - 1).bit_length()  # next power of two
        object.__setattr__(self, "capacity", cap)
        if self.n_replicas < 1:
            raise ValueError("need at least one replica")

    @property
    def mask(self) -> int:
        return self.capacity - 1


class LogState(NamedTuple):
    """Device-resident log: ring arrays + monotone int64 cursors."""

    opcodes: jax.Array  # int32[L]
    args: jax.Array  # int32[L, A]
    head: jax.Array  # int64 scalar
    tail: jax.Array  # int64 scalar
    ctail: jax.Array  # int64 scalar (completed tail)
    ltails: jax.Array  # int64[R] (per-replica local tails)


def host_cursors(ltails, tail) -> tuple[np.ndarray, int]:
    """Host copies of a fleet's cursors, `(ltails, tail)`, fetched side
    by side in one round trip (`jax.device_get` starts both transfers
    before waiting). Not a device-side concatenate: on a mesh `ltails`
    is replica-sharded and `tail` replicated, and the v5e compiler
    aborts on that int64 concatenate ("Unsupported conversion from
    vmreg/vreg to U64")."""
    lts, t = jax.device_get((ltails, tail))
    return np.array(lts), int(t)


def log_init(spec: LogSpec) -> LogState:
    """Allocate an empty log (`Log::new`, `nr/src/log.rs:179-241`)."""
    return LogState(
        opcodes=jnp.full((spec.capacity,), NOOP, jnp.int32),
        args=jnp.zeros((spec.capacity, spec.arg_width), jnp.int32),
        head=jnp.zeros((), jnp.int64),
        tail=jnp.zeros((), jnp.int64),
        ctail=jnp.zeros((), jnp.int64),
        ltails=jnp.zeros((spec.n_replicas,), jnp.int64),
    )


def log_reset(spec: LogSpec, log: LogState) -> LogState:
    """Zero the log for bench reuse (`Log::reset`, `nr/src/log.rs:593-611`)."""
    del log
    return log_init(spec)


def log_space(spec: LogSpec, log: LogState) -> jax.Array:
    """Free slots an append may consume while preserving the GC slack
    (`nr/src/log.rs:364-387`)."""
    used = log.tail - log.head
    return jnp.maximum(spec.capacity - spec.gc_slack - used, 0)


def log_append(
    spec: LogSpec,
    log: LogState,
    opcodes: jax.Array,
    args: jax.Array,
    count: jax.Array | int,
) -> LogState:
    """Batched reserve-then-write of `count` valid slots from a fixed-shape
    batch (`Log::append`, `nr/src/log.rs:343-427`, minus the CAS loop).

    Capacity is NOT checked here (jit-hot path); callers go through
    `log_space` / the replica layer's help-first rule, exactly as reference
    appenders must help GC before appending.
    """
    batch = opcodes.shape[0]
    count = jnp.asarray(count, jnp.int64)
    # Debug invariant (the panic the reference compiles in at
    # `nr/src/log.rs:487-489`'s append-side dual): an append that runs
    # past `head + capacity` overwrites entries some replica has not yet
    # replayed — silent data loss in release, an error under
    # NR_TPU_DEBUG (utils/checks.py).
    check(
        log.tail + count <= log.head + spec.capacity,
        "log_append overwrites unconsumed entries: tail {t} + count {c} "
        "> head {h} + capacity " + str(spec.capacity),
        t=log.tail, c=count, h=log.head,
    )
    lanes = jnp.arange(batch, dtype=jnp.int64)
    valid = lanes < count
    # Invalid lanes scatter to index L, which mode="drop" discards: the
    # fixed-shape equivalent of only publishing `count` entries.
    slot = jnp.where(
        valid, (log.tail + lanes) & spec.mask, spec.capacity
    ).astype(jnp.int32)
    return log._replace(
        opcodes=log.opcodes.at[slot].set(opcodes, mode="drop"),
        args=log.args.at[slot].set(args, mode="drop"),
        tail=log.tail + count,
    )


# Far-future sentinel for fenced-cursor masking: past any reachable
# logical position (int64 cursors; 2^60 leaves headroom for cursor
# arithmetic without overflow), so masked mins ignore fenced replicas.
_FAR = 1 << 60


def _freeze_limits(log: LogState, limits, fenced):
    """Fold a fenced mask into the per-replica replay `limits`: a fenced
    replica is frozen at its own ltail (no replay progress — its state
    may be corrupt and its cursor must hold still for repair), others
    keep their caller limit (or no limit)."""
    fenced = jnp.asarray(fenced, bool)
    frozen = jnp.where(fenced, log.ltails, jnp.int64(_FAR))
    if limits is None:
        return frozen
    return jnp.minimum(jnp.asarray(limits, jnp.int64), frozen)


def _gc_head(log: LogState, new_ltails, fenced):
    """The GC reduction `head = min(ltails)` with quarantined replicas
    fenced OUT of the min (`fault/health.py`): one dead replica's
    frozen cursor must not stall log GC for the fleet. Monotone
    (clamped at the old head) so a later unfence — repair re-seats the
    cursor at a healthy donor's ltail, which is >= head — can never
    move head backwards. `fenced=None` is the exact pre-fault
    reduction, bit-for-bit."""
    if fenced is None:
        return jnp.min(new_ltails)
    fenced = jnp.asarray(fenced, bool)
    masked = jnp.where(fenced, jnp.int64(_FAR), new_ltails)
    # all-fenced degenerate fleet: hold head still rather than min(FAR)
    return jnp.where(
        jnp.all(fenced), log.head,
        jnp.maximum(log.head, jnp.min(masked)),
    )


def gather_window(spec, opcodes_ring, args_ring, start, tail, window: int):
    """Gather `window` ring entries from logical position `start`, masking
    positions at or past `tail` to NOOP (positional liveness — the shared
    read side of every combined-replay engine; keep the masking rule in
    ONE place so the engines cannot desynchronize)."""
    lanes = jnp.arange(window, dtype=jnp.int64)
    pos = start + lanes
    idx = (pos & spec.mask).astype(jnp.int32)
    opcodes = jnp.where(pos < tail, opcodes_ring[idx], NOOP)
    return opcodes, args_ring[idx]


def _exec_one(
    spec: LogSpec,
    d: Dispatch,
    log: LogState,
    state: PyTree,
    ltail: jax.Array,
    window: int,
    limit: jax.Array | None = None,
):
    """Replay up to `window` entries of `[ltail, tail)` into one replica.

    The reference's hot replay loop (`nr/src/log.rs:473-524`): per entry,
    spin on `alivef` then `dispatch_mut`. Here the spin is gone (liveness is
    `pos < tail`) and the loop is a `lax.scan` whose body is one masked
    `apply_write`.

    `limit` (optional) caps how far this replica replays: the effective
    tail is `min(tail, limit)`. A limited replica is a *dormant* one — it
    stops consuming the log early, its `ltail` lags, and GC (`head =
    min(ltails)`) stalls on it exactly as a slow reference replica stalls
    `advance_head` (`nr/src/log.rs:536-539`).
    """
    eff_tail = log.tail if limit is None else jnp.minimum(log.tail, limit)
    # Debug invariants (`nr/src/log.rs:487-489` panics on a local tail
    # past the global tail; replaying below `head` reads slots GC may
    # have handed to appenders — both silently clamp in release):
    check(ltail <= log.tail,
          "replica ltail {lt} ahead of log tail {t}",
          lt=ltail, t=log.tail)
    check(ltail >= log.head,
          "replay window starts at {lt}, behind GC head {h}: entries "
          "already overwritten",
          lt=ltail, h=log.head)

    def body(state, j):
        pos = ltail + j
        active = pos < eff_tail
        idx = (pos & spec.mask).astype(jnp.int32)
        opcode = jnp.where(active, log.opcodes[idx], NOOP)
        state, resp = apply_write(d, state, opcode, log.args[idx])
        return state, resp

    state, resps = lax.scan(body, state, jnp.arange(window, dtype=jnp.int64))
    new_ltail = jnp.minimum(ltail + window, eff_tail)
    new_ltail = jnp.maximum(new_ltail, ltail)  # limit below ltail: no-op
    return state, resps, new_ltail


def log_exec_all(
    spec: LogSpec,
    d: Dispatch,
    log: LogState,
    states: PyTree,
    window: int,
    limits: jax.Array | None = None,
    fenced: jax.Array | None = None,
):
    """Replay a static `window` of pending entries into every replica in
    lock-step (vmapped `_exec_one`), then fold in progress bookkeeping:

    - `ltails[r] = min(ltails[r] + window, tail)`,
    - `ctail = max(ctail, max(ltails))`   (fetch_max, `nr/src/log.rs:520-523`),
    - `head  = min(ltails)`               (GC, `nr/src/log.rs:536-580`).

    `limits` (optional, int64[R]) caps each replica's replay at
    `min(tail, limits[r])` — simulated dormant replicas: laggards hold GC
    back (`head` stalls at their ltail) until a later un-limited call lets
    them catch up, mirroring `Replica::sync` (`nr/src/replica.rs:469-479`).

    `fenced` (optional, bool[R]) marks QUARANTINED replicas
    (`fault/health.py`): a fenced replica is frozen at its ltail (its
    state may be corrupt; repair will discard it) AND excluded from the
    `head = min(ltails)` GC reduction, so a dead replica cannot stall
    log GC for the fleet — the runtime difference between a dormant
    laggard (`limits`) and a quarantined casualty.

    Returns `(log, states, resps)` with `resps: int32[R, window]`;
    `resps[r, i]` answers the entry at logical position `old_ltails[r] + i`.
    """
    if fenced is not None:
        limits = _freeze_limits(log, limits, fenced)
    if limits is None:
        states, resps, new_ltails = jax.vmap(
            lambda s, lt: _exec_one(spec, d, log, s, lt, window)
        )(states, log.ltails)
    else:
        states, resps, new_ltails = jax.vmap(
            lambda s, lt, lim: _exec_one(spec, d, log, s, lt, window, lim)
        )(states, log.ltails, jnp.asarray(limits, jnp.int64))
    log = log._replace(
        ltails=new_ltails,
        ctail=jnp.maximum(log.ctail, jnp.max(new_ltails)),
        head=_gc_head(log, new_ltails, fenced),
    )
    return log, states, resps


def log_catchup_all(
    spec: LogSpec,
    d: Dispatch,
    log: LogState,
    states: PyTree,
    window: int,
    limits: jax.Array | None = None,
    need_resps: bool = True,
    on_trajectory: bool = True,
    union: bool | None = None,
    fenced: jax.Array | None = None,
):
    """Combined catch-up: `log_exec_all` semantics at combined speed.

    `fenced` (optional, bool[R]) carries the quarantine mask
    (`fault/health.py`) through every tier: fenced replicas are frozen
    at their ltail, excluded from the GC head reduction, and — on the
    union-plan tier — excluded from BOTH the plan-donor election and
    the merge mask (a quarantined replica's state may be corrupt; a
    plan computed from it, or a merge into it, would be garbage).

    `on_trajectory=False` opts OUT of the union-plan tier for hand-built
    fleets whose states are NOT folds of the shared log (tier 1's
    soundness argument needs the trajectory property); such fleets take
    the per-replica `window_apply` tier, which is correct for arbitrary
    state. Every log-driven fleet (NodeReplicated, the runners, recovery,
    grow_fleet) is on-trajectory by construction.

    `union` selects the union-plan tier: None (default) takes it only
    for models that declare `Dispatch.window_canonical=True` — the
    explicit opt-in to the prefix-absorbing/canonical-responses
    contract (ADVICE r5: presence of window_plan alone only claims the
    lock-step contract and must not route a third-party model through
    the stronger-contract engine). True FORCES the tier (the
    `engine='combined'` caller asserting the contract); False never
    takes it.

    `need_resps=False` (pure recovery: checkpoint replay, crash
    rebuild, the catch-up bench) skips the per-replica response
    re-indexing — on the union-plan path that is an O(R x window)
    random gather that dominates fleet-scale rounds (measured 840 ms of
    an 874 ms round at R=4096) — and returns zeros; the reference's
    catch-up likewise applies other replicas' entries without
    delivering their responses (`nr/src/log.rs:473-524` hands resps
    only to the calling combiner's own batch).

    In the reference, catch-up IS the hot loop — a lagging replica replays
    through the same `exec` everyone uses (`nr/src/log.rs:473-524`). The
    fused step's plan/merge split can't serve that role directly (it
    needs the lock-step precondition, `core/step.py`), so this runs one
    of three engines, fastest applicable first:

    1. **union-window plan** (model provides `window_plan`/`window_merge`
       and no `limits`): every replica of a log-driven fleet lies on the
       SAME replay trajectory — `states[r]` is the fold of
       `[0, ltails[r])` from common init — so the plan of the union
       window `[min(ltails), min(ltails)+window)`, computed ONCE from the
       most-lagging replica's state, merges correctly into every replica
       inside the window: cells the window touches take the plan's final
       value (identical no matter how much of the window a replica
       already applied — deterministic replay), untouched cells keep the
       replica's own (already-canonical) value. Replicas past the window
       end are left untouched. ONE sort serves the fleet — the same
       economics as the lock-step fast path, now for divergent cursors.
       NOT valid for hand-built fleets with off-trajectory states; those
       use `window_apply` (`combined=...` paths) or the scan.
    2. **per-replica `window_apply`** (arbitrary state; also the `limits`
       path — a limit truncates a replica's window individually, so no
       shared plan exists): each replica gathers and combines its own
       window; pays R sorts.
    3. **`log_exec_all` scan** when the model has no combined form.

    Cursor lattice updates match `log_exec_all` except that the
    union-window engine advances every lagging replica to the SAME
    position (the window end) — a faster join of the same lattice.
    Response layout is preserved: `resps[r, i]` answers logical position
    `old_ltails[r] + i` (0 past the replica's advancement), which is
    exactly what response delivery consumes. Differentially tested in
    `tests/test_window.py::TestCombinedCatchup`.
    """
    if d.window_apply is None and d.window_plan is None:
        # nrlint: disable=obs-in-traced — per-trace tier counter by design
        _m_engine_scan.inc()
        return log_exec_all(spec, d, log, states, window, limits,
                            fenced=fenced)
    take_union = (
        d.window_canonical if union is None else union
    ) and d.window_plan is not None
    if take_union and limits is None and on_trajectory:
        return _catchup_union_plan(spec, d, log, states, window,
                                   need_resps, fenced=fenced)
    if d.window_apply is None:
        # nrlint: disable=obs-in-traced — per-trace tier counter by design
        _m_engine_scan.inc()
        return log_exec_all(spec, d, log, states, window, limits,
                            fenced=fenced)
    # nrlint: disable=obs-in-traced — per-trace tier counter by design
    _m_engine_window.inc()
    if fenced is not None:
        limits = _freeze_limits(log, limits, fenced)

    def one(state, ltail, limit=None):
        eff_tail = (
            log.tail if limit is None else jnp.minimum(log.tail, limit)
        )
        check(ltail <= log.tail,
              "replica ltail {lt} ahead of log tail {t}",
              lt=ltail, t=log.tail)
        check(ltail >= log.head,
              "catch-up window starts at {lt}, behind GC head {h}: "
              "entries already overwritten",
              lt=ltail, h=log.head)
        opcodes, args = gather_window(
            spec, log.opcodes, log.args, ltail, eff_tail, window
        )
        state, resps = d.window_apply(state, opcodes, args)
        new_ltail = jnp.minimum(ltail + window, eff_tail)
        new_ltail = jnp.maximum(new_ltail, ltail)  # limit below ltail
        return state, resps, new_ltail

    if limits is None:
        states, resps, new_ltails = jax.vmap(
            lambda s, lt: one(s, lt)
        )(states, log.ltails)
    else:
        states, resps, new_ltails = jax.vmap(one)(
            states, log.ltails, jnp.asarray(limits, jnp.int64)
        )
    log = log._replace(
        ltails=new_ltails,
        ctail=jnp.maximum(log.ctail, jnp.max(new_ltails)),
        head=_gc_head(log, new_ltails, fenced),
    )
    return log, states, resps


def _catchup_union_plan(
    spec: LogSpec,
    d: Dispatch,
    log: LogState,
    states: PyTree,
    window: int,
    need_resps: bool = True,
    fenced: jax.Array | None = None,
):
    """Union-window catch-up (see `log_catchup_all` engine 1).

    Soundness: with deterministic replay from common init, `states[r]`
    is the fold of `[0, ltails[r])`, so for any position p in
    `[m, end]`, `window_merge(state(p), window_plan(state(m), W_m))`
    equals `state(end)` — cells the window `W_m = [m, end)` touches take
    the plan's final value (independent of how much of `W_m` the replica
    already applied: replay of the shared log is deterministic, so the
    replica's own application of a prefix wrote exactly the values the
    plan's events record), untouched cells keep the replica's value,
    which equals the canonical one. Replicas whose cursor is PAST the
    window end must not merge (the plan's final values could rewind
    them); they are masked out and keep their state and cursor.

    `fenced` (bool[R], optional — the quarantine mask, `fault/`): a
    fenced replica is OFF the shared trajectory by assumption (that is
    why it was quarantined), so it is excluded from the plan-donor
    election (`argmin` over unfenced ltails — a corrupt donor would
    poison the whole fleet's merge), from the union-window start
    (`m = min` over unfenced), from the merge mask, and from the GC
    head reduction; its state and cursor hold still for repair.
    """
    if fenced is not None:
        fenced = jnp.asarray(fenced, bool)
    masked_lt = (
        log.ltails if fenced is None
        else jnp.where(fenced, jnp.int64(_FAR), log.ltails)
    )
    # Idle short-circuit (ADVICE r5): when even the most-lagging replica
    # is at the tail there is nothing to replay, and the full
    # plan-sort + vmapped merge below would run for nothing. Host-side
    # check, so it only triggers for EAGER callers whose cursors are
    # concrete; under jit the cursors are tracers and the caller is
    # responsible for the skip (NodeReplicated._exec_round holds the
    # jit-hot equivalent).
    if (
        not isinstance(log.tail, jax.core.Tracer)
        and not isinstance(log.ltails, jax.core.Tracer)
        and not isinstance(fenced, jax.core.Tracer)
    ):
        lt = np.asarray(log.ltails)
        # every LIVE cursor exactly at tail (the max bound lets
        # corrupted ltails > tail fall through to the debug-mode
        # checks below); fenced cursors are frozen and don't count
        live = lt if fenced is None else lt[~np.asarray(fenced)]
        idle = bool(
            live.size
            and int(live.min()) >= int(log.tail) >= int(live.max())
        )
        if idle and fenced is not None:
            # a freshly fenced laggard may still pin head below the
            # live min: one device round must run to advance GC
            idle = int(np.asarray(log.head)) >= int(live.min())
        if idle:
            _m_idle_skips.inc()
            R = log.ltails.shape[0]
            return log, states, jnp.zeros((R, window), jnp.int32)
    # nrlint: disable=obs-in-traced — per-trace tier counter by design
    _m_engine_union.inc()
    m = jnp.min(masked_lt)
    end = jnp.minimum(m + window, log.tail)
    check(m >= log.head,
          "catch-up window starts at {m}, behind GC head {h}: entries "
          "already overwritten",
          m=m, h=log.head)
    check(jnp.max(log.ltails) <= log.tail,
          "replica ltail {lt} ahead of log tail {t}",
          lt=jnp.max(log.ltails), t=log.tail)
    opcodes, args = gather_window(
        spec, log.opcodes, log.args, m, end, window
    )
    donor = jnp.argmin(masked_lt)
    donor_state = jax.tree.map(lambda x: x[donor], states)
    plan = d.window_plan(donor_state, opcodes, args)

    def replicas_taking():
        take = log.ltails < end
        return take if fenced is None else take & ~fenced

    if d.window_merge_stacked is not None:
        # the model writes the plan into the taking replicas itself,
        # with no pass over the rest of the state
        states, presps = d.window_merge_stacked(states, plan,
                                                replicas_taking())
    else:
        merged, presps = jax.vmap(lambda s: d.window_merge(s, plan))(states)
        take = replicas_taking()
        states = jax.tree.map(
            lambda a, b: jnp.where(
                take.reshape((-1,) + (1,) * (a.ndim - 1)), b, a
            ),
            states, merged,
        )
    if need_resps:
        # response layout contract: resps[r, i] answers logical position
        # old_ltails[r] + i — gathered from the canonical per-position
        # plan responses; positions at/past the replica's new cursor are
        # 0 (never consumed by delivery)
        offs = (log.ltails - m)[:, None] + jnp.arange(
            window, dtype=jnp.int64
        )[None, :]
        resps = jnp.take_along_axis(
            presps, jnp.clip(offs, 0, window - 1).astype(jnp.int32),
            axis=1,
        )
        # fenced cursors can sit BELOW the (live-min) window start, so
        # their offsets go negative — mask those rows to 0 alongside
        # the past-window positions (delivery never consumes a fenced
        # replica's row anyway: its ltail does not advance)
        resps = jnp.where((offs >= 0) & (offs < (end - m)), resps, 0)
    else:
        resps = jnp.zeros_like(presps)
    new_ltails = jnp.maximum(log.ltails, end)
    if fenced is not None:
        new_ltails = jnp.where(fenced, log.ltails, new_ltails)
    log = log._replace(
        ltails=new_ltails,
        ctail=jnp.maximum(log.ctail, jnp.max(new_ltails)),
        head=_gc_head(log, new_ltails, fenced),
    )
    return log, states, resps


def ring_slice(
    spec: LogSpec, log: LogState, start: int, stop: int
) -> tuple[np.ndarray, np.ndarray]:
    """Host-side readback of ring entries `[start, stop)` as numpy
    `(opcodes int32[n], args int32[n, A])`.

    The durability plane's bridge out of device memory
    (`durable/wal.py`): `NodeReplicated.attach_wal(backfill=True)`
    persists entries that were appended BEFORE the WAL attached, and
    they are only readable while the ring still physically holds them —
    `start >= tail - capacity` (a wrapped slot has been overwritten; a
    WAL attached that late needs a snapshot instead). The durable-tail
    cursor itself lives host-side on the WAL (`WriteAheadLog.
    durable_tail`), not in `LogState`: fsync progress is host truth and
    must never enter the compiled step. Positions at/past `tail` raise
    — they are not live entries.
    """
    start, stop = int(start), int(stop)
    tail = int(log.tail)
    if stop > tail:
        raise ValueError(
            f"ring_slice [{start}, {stop}) runs past tail {tail}"
        )
    if start < tail - spec.capacity:
        raise ValueError(
            f"ring_slice [{start}, {stop}) starts below "
            f"tail - capacity = {tail - spec.capacity}: entries "
            f"already overwritten by ring wrap"
        )
    if stop < start:
        raise ValueError(f"ring_slice [{start}, {stop}) is negative")
    idx = (np.arange(start, stop, dtype=np.int64)
           & spec.mask).astype(np.int32)
    opcodes = np.asarray(log.opcodes)[idx]
    args = np.asarray(log.args)[idx]
    return opcodes, args


def is_replica_synced_for_reads(
    log: LogState, ridx: int, ctail: jax.Array
) -> jax.Array:
    """`nr/src/log.rs:671-675`: may replica `ridx` serve reads issued when
    the completed tail was `ctail`?"""
    return log.ltails[ridx] >= ctail


def get_ctail(log: LogState) -> jax.Array:
    """`nr/src/log.rs:677-679`."""
    return log.ctail
