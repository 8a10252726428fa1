"""Replica runtime: flat-combining batching + lock-step replay.

The TPU re-design of `nr/src/replica.rs`. What changes and why
(SURVEY.md §7):

- The reference elects a combiner thread with a CAS lock
  (`nr/src/replica.rs:508-540`) because threads race; replay here is a
  lock-step device computation, so combiner *election* is meaningless. What
  survives is the *batching* contract: per-thread `Context` rings are
  drained whole, in thread order, into one append batch per replica
  (`Replica::combine`, `nr/src/replica.rs:543-595`).
- `data: CachePadded<RwLock<D>>` (`nr/src/replica.rs:108-114`) becomes a
  vmapped pytree with a leading replica axis — functional state needs no
  reader/writer lock (SURVEY.md §7 "RwLock → unnecessary on-device"). A
  native C++ distributed RwLock still backs the CPU engine
  (`node_replication_tpu/native/`).
- `execute_mut` = stage → combine → collect response
  (`nr/src/replica.rs:345-356`); `execute` (read) waits until this replica's
  ltail passes the completed tail, helping replay while it waits, then
  dispatches locally (`nr/src/replica.rs:404-410`, `483-497`).
- "Append must help GC when the log is full" (`nr/src/log.rs:364-387`)
  becomes: run replay windows until `log_space` fits the batch.
- The reference's spin-diagnostic `WARN_THRESHOLD` warnings
  (`nr/src/log.rs:43`) become a host-side watchdog: after `WARN_ROUNDS`
  replay rounds without progress, a structured warning fires and the
  CNR-style GC starvation callback (`cnr/src/log.rs:135-142`) is invoked
  with the most dormant replica.

`NodeReplicated` is the stateful convenience wrapper (per-op API parity with
the reference examples, `nr/examples/hashmap.rs:55-105`); the jit-hot batch
path is `core/step.py`.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import threading

from node_replication_tpu.analysis.locks import make_lock, make_rlock
import time
from collections import deque
from functools import partial
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from node_replication_tpu.core.log import (
    LogSpec,
    WARN_ROUNDS,
    gather_window,
    log_append,
    log_catchup_all,
    log_exec_all,
    log_init,
    host_cursors,
    log_space,
    ring_slice,
)
from node_replication_tpu.fault.inject import fault_hook
from node_replication_tpu.obs.metrics import COUNT_BUCKETS, get_registry
from node_replication_tpu.ops.context import MAX_PENDING_OPS, Context
from node_replication_tpu.ops.encoding import (
    Dispatch,
    apply_read,
    encode_ops,
)
from node_replication_tpu.obs.recorder import PHASE_PREFIX, get_tracer, span

logger = logging.getLogger("node_replication_tpu")

# Max logical threads per replica (`nr/src/replica.rs:56`).
MAX_THREADS_PER_REPLICA = 256

# Default static replay window per device round (jit-compiled once).
DEFAULT_EXEC_WINDOW = 256

# Reserved context key for `execute_mut_batch` response sinks: real
# thread ids are allocated from 0 upward by `register`, so -1 can never
# collide, and `combine`'s thread-order drain (`range(threads)`) never
# visits it.
BATCH_TID = -1

# Reads one batched read program answers (`NodeReplicated.execute`),
# padded to the next of READ_BUCKETS (x4 a step, so five programs
# compile, once, with the first read; a padded read costs the device a
# few bytes). A combiner drains at most READ_DRAIN_MAX posted reads a
# hold, one program per READ_BATCH_MAX of them.
READ_BATCH_MAX = 256
READ_DRAIN_MAX = 4 * READ_BATCH_MAX
READ_BUCKETS = (1, 4, 16, 64, READ_BATCH_MAX)


class _BatchSink:
    """Response sink for caller-assembled batches (`execute_mut_batch`).

    Duck-types the response half of `ops.context.Context`
    (`enqueue_resps`) so `_exec_round`'s delivery loop needs no special
    case, but skips the 32-slot pending ring entirely — a serve batch
    is already assembled and can be any size up to the log's appendable
    capacity. Guarded by the wrapper's combiner lock like every other
    context structure.
    """

    __slots__ = ("_resps", "_inflight")

    def __init__(self) -> None:
        self._resps: list = []
        self._inflight = 0

    def expect(self, n: int) -> None:
        self._inflight += n

    def enqueue_resps(self, resps) -> None:
        self._inflight -= len(resps)
        self._resps.extend(resps)

    def take(self) -> list:
        out = self._resps
        self._resps = []
        return out

    def reset(self) -> None:
        """Discard delivered responses and the expectation count (the
        failed-batch cleanup path: stale replies must never prefix the
        next batch's)."""
        self._resps = []
        self._inflight = 0


class _PendingRound:
    """One combiner round between `begin` and `finish` — the split
    round protocol behind `begin_mut_batch`/`finish_mut_batch` (and
    the serve pipeline's assembly/completion overlap,
    `serve/frontend.py`).

    After `begin` the batch is APPENDED: the ops are in the in-memory
    log (and the WAL, when one is attached), so a failure from here on
    is post-append (`maybe_executed` semantics). What `begin` defers
    is only this replica's replay-to-target (chain tier) or the
    response readback (fused tier — the kernel is already launched and
    running on the device); `finish` completes it. `done` marks a
    round that `begin` ran eagerly end-to-end (serial callers, empty
    batches) so `finish` only collects.
    `log_idx` is the CNR per-log variant's mapped log (None for NR).
    """

    __slots__ = ("rid", "tids", "n", "pos0", "target", "batch",
                 "log_idx", "fused_resps", "done", "tier")

    def __init__(self, rid: int, tids: list[int], n: int, pos0: int,
                 batch: bool = False, log_idx: int | None = None):
        self.rid = rid
        self.tids = tids
        self.n = n
        self.pos0 = pos0
        self.target = pos0 + n
        self.batch = batch
        self.log_idx = log_idx
        #: device array of the fused launch awaiting readback
        self.fused_resps = None
        self.done = False
        #: engine tier of a deferred fused launch (readback delivery)
        self.tier: str | None = None


class _ReadSlot:
    """One read posted to the read combiner (`NodeReplicated.execute`):
    the request, then the answer or the error the combiner leaves in it.
    `ready` wakes the reader, with `lead` set when it is to combine the
    reads posted meanwhile instead."""

    __slots__ = ("rid", "op", "ready", "lead", "value", "error")

    def __init__(self, rid: int, op: tuple):
        self.rid = rid
        self.op = op
        self.ready = threading.Event()
        self.lead = False
        self.value = None
        self.error: BaseException | None = None


class ReplicaToken(NamedTuple):
    """Registration handle (`ReplicaToken`, `nr/src/replica.rs:27-30`).

    The reference makes it `!Send` to pin it to a thread; here it is just an
    index pair the caller must not share across logical threads.
    """

    rid: int
    tid: int


class LogTooSmallError(RuntimeError):
    """A single batch exceeds the log's appendable capacity."""


class ReplicaFencedError(RuntimeError):
    """The operation targets a fenced (quarantined) replica.

    A fenced replica's replay is frozen and its cursor is excluded from
    GC (`fault/health.py`), so waiting on its progress would hang
    forever; appends, reads, and single-replica syncs against it fail
    fast instead. Repair (`fault/repair.py`) unfences and readmits.
    """

    def __init__(self, rid: int):
        super().__init__(
            f"replica {rid} is fenced (quarantined); repair and "
            f"unfence it before routing operations to it"
        )
        self.rid = rid


# Locked methods emit trace events and update instruments; the tracer
# and instrument handles come from module-level get_* accessors the
# analyzer cannot type through, so the nesting is declared:
# nrcheck: lock-order NodeReplicated._lock -> Tracer._lock — locked methods emit trace events
# nrcheck: lock-order MultiLogReplicated._lock -> Tracer._lock — CNR locked methods emit trace events
# nrcheck: lock-order NodeReplicated._lock -> Counter._lock — locked methods bump counters
# nrcheck: lock-order MultiLogReplicated._lock -> Counter._lock — CNR locked methods bump counters
# nrcheck: lock-order NodeReplicated._lock -> Histogram._lock — locked methods observe durations
# nrcheck: lock-order MultiLogReplicated._lock -> Histogram._lock — CNR locked methods observe durations
# nrcheck: lock-order NodeReplicated._lock -> WriteAheadLog._lock — the combiner round journals the batch into the attached WAL
# nrcheck: lock-order MultiLogReplicated._lock -> WriteAheadLog._lock — same journaling through the CNR wrapper
def _locked(fn):
    """Run a method under the instance's combiner lock (`self._lock`).

    The reference elects a combiner with a CAS lock
    (`nr/src/replica.rs:508-540`); threads that lose the race spin or
    enqueue. Here the wrappers' shared mutable host state (`log`,
    `states`, contexts, in-flight queues, counters) is guarded by one
    reentrant combiner lock: each public entry point is one critical
    section, so concurrent logical threads can call `execute_mut` /
    `execute` / `combine` from real OS threads and observe consistent
    cursors. Reentrant because combine -> _exec_round -> gc_callback ->
    sync_log chains re-enter on the same thread. The nrlint
    `lock-discipline` rule understands this decorator as a whole-method
    `with self._lock` region.

    Lock-wait accounting (host-budget input, ROADMAP item 2): when
    metrics are on, a contended acquisition is timed into
    `nr.lock.wait_s` — the combiner-lock analogue of the reference's
    lost-CAS spin. Disabled = one `enabled` branch; the uncontended
    fast path adds one `acquire(blocking=False)` either way, which an
    RLock satisfies reentrantly.
    """
    return _locked_phase(None)(fn)


def _locked_phase(phase: str | None, fields=None):
    """`_locked`, and for the entry points whose lock time the serve
    path pays: with metrics on, the thread's outermost acquisition is
    split into two spans (`obs/recorder.span`), `<phase>.lock_wait`
    from the call to the acquisition and `<phase>.hold` from the
    acquisition to the release — histograms `nr.<phase>.lock_wait_s`
    and `nr.<phase>.hold_s`, and profiler annotations of those names
    carrying `fields(*args, **kwargs)` (the join keys `rid`, `n`,
    `pos0`). An uncontended acquisition observes a wait of 0 and opens
    no wait span, so the wait's count is the number of calls. A nested
    call (the lock is reentrant) records no phase, so
    `execute_mut_batch` counts once, not once more for each of
    `begin_mut_batch` and `finish_mut_batch`; the split protocol
    itself takes the lock twice a round."""

    def deco(fn):
        reg = get_registry()
        m_wait = reg.histogram("nr.lock.wait_s")
        if phase is not None:
            wait_event, hold_event = f"{phase}.lock_wait", f"{phase}.hold"
            m_phase_wait = reg.histogram(f"{PHASE_PREFIX}{wait_event}_s")

        def wait_for(lock) -> None:
            t0 = time.monotonic()
            lock.acquire()
            m_wait.observe(time.monotonic() - t0)

        @functools.wraps(fn)
        def inner(self, *args, **kwargs):
            lock = self._lock
            if not reg.enabled:
                with lock:
                    return fn(self, *args, **kwargs)
            if phase is None or lock._is_owned():
                if not lock.acquire(blocking=False):
                    wait_for(lock)
                try:
                    return fn(self, *args, **kwargs)
                finally:
                    lock.release()
            f = fields(*args, **kwargs)
            if lock.acquire(blocking=False):
                m_phase_wait.observe(0.0)
            else:
                with span(wait_event, **f):
                    wait_for(lock)
            try:
                with span(hold_event, **f):
                    return fn(self, *args, **kwargs)
            finally:
                lock.release()

        return inner

    return deco


def _batch_fields(ops, rid=0) -> dict:
    return {"rid": int(rid), "n": len(ops)}


def _pending_fields(pending) -> dict:
    return {"rid": pending.rid, "n": pending.n, "pos0": pending.pos0}


def _read_fields(op, token, *_, **__) -> dict:
    return {"rid": token.rid}


def _slot_fields(slot, *_) -> dict:
    return {"rid": slot.rid}


def _named(fn, name: str):
    """`fn` under `name`. `jax.jit` names its program `jit_<name>`, the
    module every op of it shows under in a profiler trace; a bare
    `partial` has no name and would show as `jit__unknown`."""
    fn.__name__ = fn.__qualname__ = name
    return fn


@partial(jax.jit, static_argnums=1)
def _replicated(state, n_replicas: int):
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (n_replicas,) + x.shape),
        state,
    )


def replicate_state(state, n_replicas: int):
    """Stack one replica state into an [R, ...] lock-step fleet. One
    program, so the fleet is the only buffer it allocates: eager ops
    would also materialize the expanded state and a broadcast copy,
    twice the fleet at the peak (a record store of 2.1 GB a replica
    would not fit 4 replicas in 16 GB)."""
    return _replicated(state, int(n_replicas))


def states_equal(states) -> bool:
    """All replicas of an [R, ...] state pytree are bit-identical (the
    `replicas_are_equal` convergence idiom, `nr/tests/stack.rs:434-489`).
    Shared by every runner/wrapper so the check can't drift."""
    return all(
        jax.tree.leaves(
            jax.tree.map(
                lambda a: bool(np.all(np.asarray(a) == np.asarray(a)[0:1])),
                states,
            )
        )
    )


class _ReadCombining:
    """Read combining, the one read path of `NodeReplicated` and
    `MultiLogReplicated` (`core/cnr.py`) for models with
    `Dispatch.read_stacked`: concurrent readers post their reads, and
    one of them answers every posted read under one hold of the
    combiner lock, with one batched `jit_read_stacked` program per
    READ_BATCH_MAX reads and one readback.

    The host provides `_lock`, `states`, `dispatch`, `spec.arg_width`,
    `_read_jit` (the single-read program), `_execute_one(op, token)` (a
    read alone, under its own hold), `_read_refused(rid)` (the error a
    read of replica `rid` fails with, or None) and `_sync_reads(live)`
    (replay, under the lock, what each read must see: NR syncs a lagging
    replica to the completed tail, CNR a read's mapped log on its
    replica to that log's completed tail, `cnr/src/replica.rs:599-617`)."""

    def _init_read_combining(self) -> None:
        # reads posted for the next combined hold, and whether a reader
        # is combining; guarded by `_read_mu`, never by the combiner
        # lock, so posting never waits on a round
        self._read_mu = make_lock("_ReadCombining._read_mu")
        self._read_posted: deque[_ReadSlot] = deque()
        self._read_combining = False
        self._read_batch = None
        self._m_read_batch = get_registry().histogram(
            "nr.read.batch_size", buckets=COUNT_BUCKETS)

    def execute(self, op: tuple, token: ReplicaToken):
        """Read path (`Replica::execute` → `read_only`,
        `nr/src/replica.rs:404-410`, `483-497`): wait until this replica has
        replayed what the read must see (`_sync_reads`: NR's completed
        tail, a CNR read's mapped log's), helping replay while waiting,
        then dispatch locally against replica state. Answers an int
        where the read program answers one word (the hashmap's get),
        else an `np.ndarray` of int32 words (the record store's wide
        read).

        Reads of a model with `Dispatch.read_stacked` are combined, as
        writes are: a reader posts its read, and if no reader is
        combining, it answers every posted read under one hold of the
        combiner lock (`_lead_reads`); otherwise it waits for its
        answer, or for the role. A caller that holds the lock already
        reads alone, as does every read of a model without
        `read_stacked` (`_read_one` would copy a whole replica per
        batched read)."""
        if self.dispatch.read_stacked is None or self._lock._is_owned():
            return self._execute_one(op, token)
        slot = _ReadSlot(token.rid, op)
        with self._read_mu:
            self._read_posted.append(slot)
            slot.lead = not self._read_combining
            self._read_combining = True
        if not slot.lead:
            slot.ready.wait()
        if slot.lead:
            self._lead_reads(slot)
        if slot.error is not None:
            raise slot.error
        return slot.value

    def _lead_reads(self, slot: _ReadSlot) -> None:
        """The read combiner's turn: answer the posted reads under one
        hold (`_combine_reads`); then, with the lock released, hand the
        role to the oldest read posted meanwhile (or clear it) and wake
        the readers. An answered reader never takes the lock. Whatever
        is raised reaches every read of the batch, and the role is
        handed on or cleared, so no reader waits forever."""
        batch: list[_ReadSlot] = []
        try:
            self._combine_reads(slot, batch)
        except BaseException as e:
            for s in batch:
                if s is not slot and s.error is None:
                    s.error = e
            raise
        finally:
            with self._read_mu:
                if not batch:  # raised before its drain: withdraw it
                    self._read_posted.remove(slot)
                posted = self._read_posted
                nxt = posted[0] if posted else None
                self._read_combining = nxt is not None
                if nxt is not None:
                    nxt.lead = True
            if nxt is not None:
                nxt.ready.set()
            for s in batch:
                s.ready.set()

    @_locked_phase("read", _slot_fields)
    def _combine_reads(self, slot: _ReadSlot, batch: list) -> None:
        """One hold for the posted reads, `slot` (the oldest) first:
        drain up to READ_DRAIN_MAX of them into `batch`, sync what they
        must see (`_sync_reads`, its cursors fetched once), and answer
        the reads from one batched read program per READ_BATCH_MAX of
        them, with one readback. A read its host refuses
        (`_read_refused`: a fenced replica's), or one its `read-sync`
        fault hook raises out of, fails alone.

        Linearizable as a lone read is: every read of the batch was
        posted before the completed tails were fetched, an acknowledged
        write advanced its log's completed tail before its ack, and all
        the reads see the replicas' states at one instant under the
        lock, inside each read's interval."""
        with self._read_mu:
            posted = self._read_posted
            batch.extend(posted.popleft()
                         for _ in range(min(len(posted), READ_DRAIN_MAX)))
        live = []
        for s in batch:
            s.error = self._read_refused(s.rid)
            if s.error is not None:
                continue
            try:
                fault_hook("read-sync", s.rid, self)
            except Exception as e:
                s.error = e
                continue
            live.append(s)
        if not live:
            return
        self._sync_reads(live)
        d = self.dispatch
        if d.host_counters is not None and get_registry().enabled:
            d.host_counters("read", [s.op for s in live])
        run = self._read_batch_program()
        chunks = [live[i:i + READ_BATCH_MAX]
                  for i in range(0, len(live), READ_BATCH_MAX)]
        outs = []
        for chunk in chunks:
            outs.append(run(self.states, *self._pack_reads(chunk)))
            self._m_read_batch.observe(len(chunk))
        for chunk, out in zip(chunks, jax.device_get(outs)):
            out = out[: len(chunk)]
            for s, v in zip(chunk, out.tolist() if out.ndim == 1 else out):
                s.value = v

    def _pack_reads(self, chunk: list[_ReadSlot]):
        """Host arrays of a chunk's rids, opcodes and args, padded to
        the next size of READ_BUCKETS with NOOP reads (opcode 0) of
        replica 0, whose answers are dropped."""
        n = len(chunk)
        b = next(b for b in READ_BUCKETS if b >= n)
        rids = np.zeros((b,), np.int32)
        opcodes = np.zeros((b,), np.int32)
        args = np.zeros((b, self.spec.arg_width), np.int32)
        rids[:n] = [s.rid for s in chunk]
        opcodes[:n] = [s.op[0] for s in chunk]
        for j, s in enumerate(chunk):
            args[j, : len(s.op) - 1] = s.op[1:]
        return rids, opcodes, args

    @_locked
    def _read_batch_program(self):
        """The batched read program: `_read_jit` vmapped over the reads
        (the stacked states shared, one rid, opcode and args a read),
        run as `jit_read_stacked`. Built from whatever `_read_jit` is,
        so a batch answers what a lone read does; every size of
        READ_BUCKETS compiles here, with the first read, so that none
        compiles later."""
        read = self._read_jit
        if self._read_batch is None or self._read_batch[0] is not read:
            run = jax.jit(_named(jax.vmap(read, in_axes=(None, 0, 0, 0)),
                                 "read_stacked"))
            for b in READ_BUCKETS:
                z = np.zeros((b,), np.int32)
                run(self.states, z, z,
                    np.zeros((b, self.spec.arg_width), np.int32))
            self._read_batch = (read, run)
        return self._read_batch[1]

    def _dispatch_read(self, rid: int, op: tuple) -> int | np.ndarray:
        """Shared read-dispatch tail: pack args, run the read jit
        against replica `rid`'s current state. A lone read (synced) and
        NR's `execute_stale` (brownout) must never diverge on this step.
        A 0-d answer comes back as an int, a wider one as the array of
        its words. Caller holds the combiner lock and has fence-checked."""
        d = self.dispatch
        if d.host_counters is not None and get_registry().enabled:
            d.host_counters("read", [op])
        args = np.zeros((self.spec.arg_width,), np.int32)
        args[: len(op) - 1] = op[1:]
        out = np.asarray(
            self._read_jit(
                self.states,
                jnp.int32(rid),
                jnp.int32(op[0]),
                jnp.asarray(args),
            )
        )
        return int(out) if out.ndim == 0 else out


class _FusedTier:
    """Fused-pallas-tier plumbing shared by `NodeReplicated` and
    `MultiLogReplicated` (`core/cnr.py`): the tier is ON only when the
    caller forces it with `engine='pallas'`, and then builds its
    spec-bound engine eagerly and rebuilds it lazily after a fleet-shape
    change. Hosts provide `_fused_log_spec()` — the `LogSpec` the
    engine is built against (a CNR derives one per-log spec for all its
    logs). All methods run under the host's combiner lock.

    On a mesh (`NodeReplicated(mesh=)`) the tier is the MESH-FUSED
    composition (`parallel/collectives.py:MeshFusedEngine`): the same
    one-launch round wrapped in shard_map with the cursor lattice
    joined over ICI, in place of the shmap/gspmd chain."""

    def _fused_log_spec(self) -> LogSpec:
        return self.spec

    def _init_fused_tier(self, engine: str, dispatch, mesh, reg,
                         prefix: str, debug: bool = False,
                         mesh_fused: bool = False) -> None:
        """Initialize the tier state + counters and resolve the mode —
        the one constructor block both wrappers share. `engine='pallas'`
        FORCES the tier (validated loudly here: the model must carry a
        `fused_factory`, and checkify `debug` has no fused twin; on a
        mesh the host must support the mesh-fused composition —
        `mesh_fused=True`, NodeReplicated only); any other engine
        leaves the tier off and never consults it."""
        self._fused = None
        self._fused_spec = None
        self._fused_mode = "off"
        self._fused_mesh = mesh if mesh_fused else None
        self._fused_rounds = 0
        self.last_round_tier: str | None = None
        self._tier_by_rid: dict[int, str] = {}
        self._pos_by_rid: dict[int, int] = {}
        self._m_engine_fused = reg.counter(
            f"{prefix}.exec.engine.pallas_fused"
        )
        self._m_fused_fallback = reg.counter(
            f"{prefix}.exec.engine.fused_fallback"
        )
        if engine != "pallas":
            return
        if dispatch.fused_factory is None:
            raise ValueError(
                f"engine='pallas' but {dispatch.name} has no "
                f"fused_factory (no fused kernel for this model)"
            )
        if mesh is not None and not mesh_fused:
            raise ValueError(
                "engine='pallas' does not take mesh= here (the "
                "mesh-fused composition is NodeReplicated-only; "
                "the CNR per-log tier runs un-meshed — see README "
                "'Engines')"
            )
        if debug:
            raise ValueError(
                "engine='pallas' has no checkify twin; use "
                "debug=False (the fused round replays inside the "
                "kernel, outside the checks' reach)"
            )
        # build eagerly so an unsupported config fails loudly at
        # construction (the explicit ask), not mid-traffic
        spec = self._fused_log_spec()
        self._fused = self._build_fused_engine(spec)
        self._fused_spec = spec
        self._fused_mode = "forced"

    def _build_fused_engine(self, spec: LogSpec):
        """The tier's engine for `spec`: the dispatch's own fused
        engine un-meshed, the shard_map-wrapped MeshFusedEngine on a
        mesh. Both raise ValueError for unsupported configs."""
        if self._fused_mesh is not None:
            from node_replication_tpu.parallel.collectives import (
                MeshFusedEngine,
            )

            return MeshFusedEngine(self.dispatch, spec,
                                   self._fused_mesh)
        return self.dispatch.fused_factory(spec)

    def _fused_engine(self):
        """The engine to route a combiner round through, or None for
        the ordinary chain (tier off). Lazily rebuilds the engine for
        the CURRENT spec (fleet growth rebinds it); a factory rejection
        after a shape change degrades the tier to off with a warning
        rather than killing live traffic."""
        if self._fused_mode == "off":
            return None
        spec = self._fused_log_spec()
        if self._fused is None or self._fused_spec != spec:
            try:
                self._fused = self._build_fused_engine(spec)
                self._fused_spec = spec
            except ValueError as e:
                logger.warning(
                    "fused engine rejected spec after fleet change "
                    "(%s); falling back to the ordinary chain", e
                )
                self._fused_mode = "off"
                return None
        return self._fused

    def round_tier(self, rid: int) -> str | None:
        """The engine tier that served replica `rid`'s most recent
        combiner round — per-rid, so concurrent serve workers cannot
        misattribute each other's rounds (`last_round_tier` is the
        wrapper-wide convenience for single-driver callers). For a CNR
        batch spanning several logs this is the LAST sub-batch's
        tier."""
        return self._tier_by_rid.get(rid)

    def round_pos(self, rid: int) -> int | None:
        """The log position replica `rid`'s most recent combiner round
        appended at (`pos0`) — the per-record trace join key the serve
        layer stamps onto its `serve-batch` ack event, so a record's
        submit→ack hop is joinable with the append/ship/apply hops
        downstream (`obs/` fleet tracing). Same per-rid discipline as
        `round_tier`."""
        return self._pos_by_rid.get(rid)


class NodeReplicated(_ReadCombining, _FusedTier):
    """N replicas of one `Dispatch` data structure behind a shared log.

    Mirrors the user-facing surface of `Replica` + `Log` wiring from the
    reference examples: `register`, `execute_mut`, `execute`, `sync`,
    `verify`, plus batched `enqueue_mut`/`flush` (the flat-combining fast
    path made explicit).
    """

    def __init__(
        self,
        dispatch: Dispatch,
        n_replicas: int = 1,
        log_entries: int | None = None,
        gc_slack: int | None = None,
        exec_window: int = DEFAULT_EXEC_WINDOW,
        gc_callback: Callable[[int, int], None] | None = None,
        debug: bool | None = None,
        engine: str = "auto",
        mesh=None,
        collectives: str = "auto",
    ):
        kw = {}
        if log_entries is not None:
            kw["capacity"] = log_entries
        if gc_slack is not None:
            kw["gc_slack"] = gc_slack
        self.spec = LogSpec(
            n_replicas=n_replicas, arg_width=dispatch.arg_width, **kw
        )
        self.dispatch = dispatch
        self.exec_window = int(exec_window)
        self.gc_callback = gc_callback
        # `debug` compiles device-side cursor invariants into the append
        # and replay programs (checkify — utils/checks.py): invalid
        # ltails and window-overrunning appends raise instead of
        # clamping. Off (default) the compiled programs are unchanged;
        # None defers to the NR_TPU_DEBUG env var.
        if debug is None:
            from node_replication_tpu.utils.checks import debug_default

            debug = debug_default()
        self.debug = bool(debug)

        self.log = log_init(self.spec)
        self.states = replicate_state(dispatch.init_state(), n_replicas)

        # Combiner lock (see `_locked`): guards log/states/cursor and
        # context bookkeeping against concurrent OS-thread callers.
        self._lock = make_rlock("NodeReplicated._lock")
        self._init_read_combining()
        self._contexts: dict[tuple[int, int], Context] = {}
        self._threads_per_replica = [0] * n_replicas
        # Appended-but-unanswered ops per replica: deque[(logical_pos, tid)].
        self._inflight: list[deque] = [deque() for _ in range(n_replicas)]
        # Split-round registry (`begin_mut_batch`): at most ONE
        # begun-but-unfinished round per replica — the pipeline-depth-1
        # invariant that keeps future ordering, `maybe_executed`
        # attribution, and WAL group-commit per-round.
        self._pending_batch: dict[int, "_PendingRound"] = {}
        # Quarantine mask (`fault/health.py`): None until the first
        # `fence_replica` so the no-fault hot path stays byte-identical
        # (the compiled programs never see a mask argument); a bool[R]
        # numpy array while any replica is fenced.
        self._fenced: np.ndarray | None = None
        # Write-ahead log (`durable/wal.py`): None (the default) costs
        # one branch per append/exec round, the obs/metrics discipline.
        # While attached, every combiner append is mirrored into it
        # and GC-head progress drives segment reclamation.
        self._wal = None
        self._exec_rounds = 0
        # Rounds short-circuited because every replica was already at the
        # tail (empty combine() help, read-sync polling) — the device
        # sort+merge those rounds used to pay is skipped (ADVICE r5).
        self._idle_rounds = 0

        # Metric handles are created once here; each hot-path update is
        # one branch when the registry is disabled (obs/metrics.py).
        reg = get_registry()
        self._m_rounds = reg.counter("nr.exec.rounds")
        self._m_idle = reg.counter("nr.exec.idle_rounds")
        self._m_batch = reg.histogram("nr.combine.batch_size",
                                      buckets=COUNT_BUCKETS)
        self._m_stalls = reg.counter("nr.watchdog.stalls")
        self._m_lag = reg.histogram("nr.replica.lag",
                                    buckets=COUNT_BUCKETS)

        # Replay engine for every cursor catch-up loop (sync, read-sync,
        # combine-replay, recovery): 'combined' routes through
        # `log_catchup_all` — for plan/merge models the union-window
        # plan, sound because this wrapper's fleet is always ON the
        # shared replay trajectory (states are folds of the log from
        # common init; the reference's catch-up-at-hot-loop-speed
        # contract, `nr/src/log.rs:473-524`) — 'scan' forces the
        # generic vmapped scan, 'auto' (default) picks combined when
        # the model provides a combined form. Off-trajectory hand-built
        # states must not use 'combined' (see log_catchup_all's
        # `on_trajectory`).
        if engine not in ("auto", "combined", "scan", "pallas"):
            raise ValueError(f"unknown engine {engine!r}")
        if (dispatch.window_plan is None) != (
            dispatch.window_merge is None
        ):
            raise ValueError(
                f"{dispatch.name}: window_plan and window_merge come "
                f"as a pair (got only one)"
            )
        has_any_combined = (
            dispatch.window_apply is not None
            or dispatch.window_plan is not None
        )
        if engine == "combined" and not has_any_combined:
            raise ValueError(
                f"engine='combined' but {dispatch.name} has no "
                f"window_apply or window_plan"
            )
        # 'auto' resolves to the combined engine only when a combined
        # tier will actually run: window_apply, or a plan/merge pair
        # that opted into the union contract (window_canonical). A
        # lock-step-only plan/merge model would otherwise fall through
        # to the scan inside log_catchup_all every round while
        # stats()/metrics reported 'combined'.
        auto_combined = (
            dispatch.window_apply is not None
            or (dispatch.window_plan is not None
                and dispatch.window_canonical)
        )
        # engine='pallas' forces the FUSED tier for combiner rounds;
        # the catch-up loops below it still need a divergent-cursor
        # engine, resolved exactly as 'auto' would
        use_combined = (
            auto_combined if engine in ("auto", "pallas")
            else engine == "combined"
        )
        self.engine = "combined" if use_combined else "scan"
        # engine='combined' is the caller EXPLICITLY asserting the
        # union-tier contract; 'auto' defers to the model's own
        # `window_canonical` opt-in (ADVICE r5: presence of a
        # plan/merge pair only claims the lock-step contract)
        self._union = True if engine == "combined" else None
        # per-round engine usage (host truth for the wrapper; core/log.py
        # counts per-trace selections of the inner tiers)
        self._m_engine = reg.counter(f"nr.exec.engine.{self.engine}")

        # ---- mesh placement (parallel/): shard the replica axis -----
        # `mesh` puts the fleet across devices: states (and ltails)
        # shard over the mesh's 'replica' axis, the log's ring arrays
        # and scalar cursors replicate (`parallel/mesh.py:place` — the
        # NamedSharding(mesh, P('replica')) batch-dim pattern). Accepts
        # a jax Mesh, a device count (first N devices), or a
        # ReplicaStrategy. `collectives` picks the cross-device exec
        # tier: 'shmap' = the explicit-collective shard_map exec
        # (`parallel/collectives.py:make_shmap_exec`, pmax/pmin lattice
        # over ICI), 'gspmd' = the annotation path (the exact
        # single-device programs, GSPMD inserts the collectives from
        # the placed inputs), 'auto' = shmap for scan-engine fleets,
        # gspmd when the combined engine (whose union-plan economics
        # GSPMD preserves) or debug checks are in play. Both tiers are
        # differentially pinned bit-identical to the un-meshed wrapper
        # (tests/test_mesh_fleet.py). mesh=None is byte-identical to
        # the pre-mesh wrapper: no placement, no extra branches traced.
        if collectives not in ("auto", "shmap", "gspmd"):
            raise ValueError(f"unknown collectives tier {collectives!r}")
        self.mesh = None
        self._mesh_shards = 0
        self._mesh_tier = None
        self._ring_rounds = 0
        if mesh is not None:
            from node_replication_tpu.parallel.mesh import (
                ReplicaStrategy,
                announce_placement,
                replica_mesh,
            )

            if isinstance(mesh, int):
                mesh = replica_mesh(mesh)
            elif isinstance(mesh, ReplicaStrategy):
                mesh = replica_mesh(strategy=mesh)
            if "replica" not in mesh.axis_names:
                raise ValueError(
                    f"mesh {mesh.axis_names} has no 'replica' axis"
                )
            shards = mesh.shape["replica"]
            if n_replicas % shards:
                raise ValueError(
                    f"R={n_replicas} replicas cannot shard over "
                    f"{shards} mesh shards"
                )
            if collectives == "auto":
                tier = (
                    "gspmd"
                    if (self.engine == "combined" or self.debug)
                    else "shmap"
                )
            else:
                tier = collectives
            if tier == "shmap" and self.debug:
                raise ValueError(
                    "collectives='shmap' has no checkify twin; use "
                    "the gspmd tier (or debug=False) on a mesh"
                )
            self.mesh = mesh
            self._mesh_shards = shards
            self._mesh_tier = tier
            self._m_mesh_round = reg.counter(f"nr.exec.mesh.{tier}")
            self._m_mesh_sync_bytes = reg.counter("mesh.sync_bytes")
            self._m_mesh_dur = reg.histogram("mesh.round.duration_s")
            self._m_ring = reg.counter("nr.exec.engine.ring")
            # mesh-fused rounds (the shard_map-wrapped one-launch tier)
            # count separately from the shmap/gspmd chain rounds
            self._m_mesh_fused_round = reg.counter(
                "nr.exec.mesh.mesh_fused"
            )
            announce_placement(mesh, n_replicas, "NodeReplicated", tier)

        # ---- fused pallas combiner-round tier (ops/pallas_replay) ----
        # One kernel launch per combiner round: append + replay +
        # response gather fused into a single program, replacing the
        # append-jit → exec-jit chain (and its per-round host syncs)
        # when the round is lock-step eligible. On a mesh the tier is
        # the MESH-FUSED composition (`parallel/collectives.py:
        # MeshFusedEngine`): one shard_map-wrapped launch per device
        # with the cursor lattice joined over ICI, replacing the
        # shmap/gspmd chain for eligible rounds. The tier runs only
        # under engine='pallas'; 'auto' resolves to the chain above and
        # never consults it. Validation + the lazy rebuild: `_FusedTier`
        # (shared with the CNR twin; initialized AFTER mesh
        # normalization so the tier binds the real Mesh object). The
        # tier never changes results — it is differentially pinned
        # bit-identical to the scan engine (tests/test_pallas_fused.py,
        # tests/test_mesh_fleet.py) — only the launch count.
        self._init_fused_tier(engine, dispatch, self.mesh, reg, "nr",
                              debug=self.debug, mesh_fused=True)
        if self.mesh is not None:
            self._place_on_mesh()
        self._build_jits()

    @_locked
    def _place_on_mesh(self) -> None:
        """(Re)apply the canonical mesh shardings to log + states —
        after construction and after every fleet-shape change
        (`grow_fleet`, `recover`, `restore`) whose fresh arrays would
        otherwise land on the default device. No-op un-meshed."""
        if self.mesh is None:
            return
        from node_replication_tpu.parallel.mesh import place

        self.log, self.states = place(self.log, self.states, self.mesh)

    def replica_device(self, rid: int):
        """The device hosting replica `rid`'s state shard (None when
        un-meshed) — the serve layer's worker-per-replica→device map.
        NamedSharding(P('replica')) splits the replica axis into
        contiguous blocks in mesh device order."""
        if self.mesh is None:
            return None
        if not 0 <= rid < self.n_replicas:
            raise ValueError(f"replica {rid} out of range")
        shard = rid // (self.n_replicas // self._mesh_shards)
        return self.mesh.devices.reshape(self._mesh_shards, -1)[shard][0]

    @_locked
    def _shmap_fn(self, window: int, fenced: bool):
        """Build-once cache of the explicit-collective exec programs
        (`parallel/collectives.py:make_shmap_exec`), keyed (window,
        fenced) like jit's own static cache."""
        fn = self._shmap_cache.get((window, fenced))
        if fn is None:
            from node_replication_tpu.parallel.collectives import (
                make_shmap_exec,
            )

            fn = make_shmap_exec(self.dispatch, self.spec, self.mesh,
                                 window, fenced=fenced)
            self._shmap_cache[(window, fenced)] = fn
        return fn

    def _shmap_exec_entry(self, log, states, window):
        return self._shmap_fn(window, False)(log, states)

    def _shmap_exec_fenced_entry(self, log, states, fenced, window):
        return self._shmap_fn(window, True)(log, states, fenced)

    @_locked
    def _build_jits(self) -> None:
        """(Re)build the compiled append/exec/read entry points against the
        CURRENT `self.spec` — called from `__init__` and `grow_fleet`
        (growing changes `n_replicas`, so the partials must rebind)."""
        # mesh program caches are spec-bound too
        self._shmap_cache: dict = {}
        self._ring_fn = None
        self._ring_gather = None
        # the fused engine is spec-bound (R, capacity): rebuild lazily
        # after any fleet-shape change
        self._fused = None
        dispatch = self.dispatch
        exec_fn = (
            partial(log_catchup_all, union=self._union)
            if self.engine == "combined" else log_exec_all
        )
        def _exec_fenced(log, states, fenced, window):
            return exec_fn(self.spec, dispatch, log, states,
                           window=window, fenced=fenced)

        exec_prog = _named(partial(exec_fn, self.spec, dispatch), "log_exec")
        fenced_prog = _named(_exec_fenced, "log_exec_fenced")
        append_prog = _named(partial(log_append, self.spec), "log_append")
        if self.debug:
            from node_replication_tpu.utils.checks import checked

            self._exec_jit = jax.jit(
                _named(checked(exec_prog), "log_exec_checked"),
                static_argnames=("window",),
            )
            self._exec_fenced_jit = jax.jit(
                _named(checked(fenced_prog), "log_exec_fenced_checked"),
                static_argnames=("window",),
            )
            self._append_jit = jax.jit(
                _named(checked(append_prog), "log_append_checked")
            )
        else:
            self._exec_jit = jax.jit(
                exec_prog, static_argnames=("window",),
                donate_argnums=(0, 1),
            )
            # Fenced twin of the exec program (compiled only if a
            # replica is ever fenced — jit compilation is lazy, so the
            # fault-free path never pays for it).
            self._exec_fenced_jit = jax.jit(
                fenced_prog, static_argnames=("window",),
                donate_argnums=(0, 1),
            )
            self._append_jit = jax.jit(append_prog, donate_argnums=(0,))

        if self.mesh is not None and self._mesh_tier == "shmap":
            # the explicit-collective tier REPLACES the exec programs
            # (append + read jits stay: appends are replicated writes,
            # reads a one-replica gather — GSPMD handles both)
            self._exec_jit = self._shmap_exec_entry
            self._exec_fenced_jit = self._shmap_exec_fenced_entry

        def _read_one(states, rid, opcode, args):
            state = jax.tree.map(lambda a: a[rid], states)
            return apply_read(dispatch, state, opcode, args)

        # The read program. `_read_one` copies replica `rid` whole (XLA
        # does not fuse the slice into the branches of `apply_read`'s
        # switch), so a model's `read_stacked` is taken wherever given;
        # `_read_stacked_jit` is the name the benchmark's record faults
        # call it by. `execute` answers a model's `read_stacked` reads
        # in batches, by `_read_batch_program` (built on first use).
        if dispatch.read_stacked is None:
            self._read_jit = jax.jit(_read_one)
        else:
            self._read_jit = self._read_stacked_jit = jax.jit(
                _named(partial(dispatch.read_stacked), "read_stacked"))
        self._read_batch = None

    # ------------------------------------------------------------------ API

    @property
    def n_replicas(self) -> int:
        return self.spec.n_replicas

    @_locked
    def ltail(self, rid: int) -> int:
        """Replica `rid`'s applied cursor (host int). Locked: an
        unlocked read races the exec round's buffer donation (the old
        `log` arrays are DELETED once donated) — the bounded-staleness
        read path (`serve/frontend.py`, `repl/`) polls this."""
        if not 0 <= rid < self.n_replicas:
            raise ValueError(f"replica {rid} out of range")
        return int(np.asarray(self.log.ltails)[rid])

    @_locked
    def register(self, rid: int = 0) -> ReplicaToken:
        """Register a logical thread on replica `rid`
        (`Replica::register`, `nr/src/replica.rs:279-298`)."""
        if not 0 <= rid < self.n_replicas:
            raise ValueError(f"replica {rid} out of range")
        tid = self._threads_per_replica[rid]
        if tid >= MAX_THREADS_PER_REPLICA:
            raise RuntimeError(
                f"replica {rid} already has {MAX_THREADS_PER_REPLICA} threads"
            )
        self._threads_per_replica[rid] = tid + 1
        self._contexts[(rid, tid)] = Context()
        return ReplicaToken(rid, tid)

    @_locked
    def grow_fleet(self, k: int = 1, donor: int | None = None,
                   catch_up: bool = True) -> list[int]:
        """Dynamic replica registration: add `k` replicas to a LIVE
        instance and return their new rids.

        The reference registers replicas against a live log at any time —
        `Log::register` CASes a fresh id (`nr/src/log.rs:272-292`) and
        `Replica::new` calls it at construction
        (`nr/src/replica.rs:184-232`); the newcomer starts from `Default`
        at position 0, which is only sound before the ring wraps. Here
        the newcomer instead CLONES the most caught-up replica's state —
        a consistent snapshot at exactly `ltails[donor]` (induction: a
        replica's state is the fold of `[0, ltails[r])`) — inherits that
        cursor, and catches up through the same combined/scan exec loop
        every replica uses (`log_catchup_all`), so a join is valid at ANY
        point in the log's lifetime, wraps included. Existing tokens stay
        valid (rids are stable); register threads on the new rids to use
        them. GC is never held back: the newcomer's ltail equals the
        donor's, which is >= min(ltails), so `head = min(ltails)` is
        unchanged (with the default most-caught-up donor it is in fact
        the max, but the invariant only needs >= min).
        """
        if k < 1:
            raise ValueError("grow_fleet needs k >= 1")
        R = self.n_replicas
        if self.mesh is not None and (R + k) % self._mesh_shards:
            # validated BEFORE any state mutates: an indivisible fleet
            # cannot keep the P('replica') placement balanced
            raise ValueError(
                f"grown fleet of {R + k} replicas cannot shard over "
                f"{self._mesh_shards} mesh shards (grow in multiples "
                f"of the shard count)"
            )
        ltails = np.asarray(self.log.ltails)
        if donor is None:
            # never clone from a fenced (possibly corrupt) replica
            masked = (
                ltails if self._fenced is None
                else np.where(self._fenced, -1, ltails)
            )
            donor = int(np.argmax(masked))
        elif not 0 <= donor < R:
            raise ValueError(f"donor replica {donor} out of range")
        elif self._is_fenced(donor):
            raise ReplicaFencedError(donor)
        donor_ltail = int(ltails[donor])

        self.spec = dataclasses.replace(
            self.spec, n_replicas=R + k
        )
        # states: stack k bit-copies of the donor's snapshot onto the
        # replica axis; cursors: the newcomers start at the donor's ltail
        self.states = jax.tree.map(
            lambda x: jnp.concatenate(
                [x] + [x[donor][None]] * k, axis=0
            ),
            self.states,
        )
        # built on the host copy: a device concatenate of a
        # replica-sharded int64 ltails aborts the v5e compiler
        # (`core/log.host_cursors`); `_place_on_mesh` re-places it
        self.log = self.log._replace(
            ltails=jnp.asarray(np.concatenate(
                [ltails, np.full(k, donor_ltail, np.int64)]
            ))
        )
        self._threads_per_replica.extend([0] * k)
        self._inflight.extend(deque() for _ in range(k))
        if self._fenced is not None:
            self._fenced = np.concatenate(
                [self._fenced, np.zeros(k, bool)]
            )
        self._place_on_mesh()
        self._build_jits()
        new_rids = list(range(R, R + k))
        get_tracer().emit(
            "grow_fleet", k=k, donor=donor, donor_ltail=donor_ltail,
            n_replicas=R + k,
        )
        if catch_up:
            for rid in new_rids:
                self.sync(rid)
        return new_rids

    # ------------------------------------------------- fencing (fault/)

    def _is_fenced(self, rid: int) -> bool:
        f = self._fenced
        return f is not None and bool(f[rid])

    @property
    def fenced_rids(self) -> list[int]:
        """Currently fenced (quarantined) replicas."""
        f = self._fenced
        return [] if f is None else [int(r) for r in np.where(f)[0]]

    @_locked
    def fence_replica(self, rid: int) -> None:
        """Fence `rid` out of the fleet (the QUARANTINED half of the
        lifecycle machine, `fault/health.py`): its replay freezes at
        its current ltail, and the GC reduction `head = min(ltails)`
        skips it (`core/log.py:_gc_head`) so one dead replica cannot
        stall log GC. Its in-flight responses are dropped (crash
        semantics, like `recover`): a fenced replica's replay never
        advances, so they are undeliverable. Idempotent."""
        if not 0 <= rid < self.n_replicas:
            raise ValueError(f"replica {rid} out of range")
        if self._fenced is None:
            self._fenced = np.zeros(self.n_replicas, bool)
        if self._fenced[rid]:
            return
        self._fenced[rid] = True
        self._inflight[rid] = deque()
        sink = self._contexts.get((rid, BATCH_TID))
        if sink is not None:
            sink.reset()
        # crash semantics for a begun-but-unfinished split round too:
        # its delivery state is gone with the sink, and a repaired
        # replica must be able to begin fresh rounds
        stale = self._pending_batch.pop(rid, None)
        if stale is not None:
            stale.done = True
            stale.fused_resps = None
        get_tracer().emit(
            "fault-fence", rid=rid,
            ltail=int(np.asarray(self.log.ltails)[rid]),
        )

    @_locked
    def unfence_replica(self, rid: int) -> None:
        """Readmit `rid` to replay and GC accounting. The caller must
        have re-seated its state/cursor first (`clone_replica_from` —
        a fenced cursor may have fallen behind the GC head, where the
        log no longer holds its entries). Idempotent."""
        if not 0 <= rid < self.n_replicas:
            raise ValueError(f"replica {rid} out of range")
        if self._fenced is None or not self._fenced[rid]:
            return
        self._fenced[rid] = False
        if not self._fenced.any():
            self._fenced = None  # restore the no-mask hot path
        get_tracer().emit("fault-unfence", rid=rid)

    @_locked
    def clone_replica_from(self, rid: int,
                           donor: int | None = None) -> tuple[int, int]:
        """Overwrite replica `rid`'s state and cursor with a bit-copy
        of a healthy donor's — the `grow_fleet` donor-copy invariant
        applied IN PLACE (a replica's state is the fold of
        `[0, ltails[r])` from common init, so the copy is a consistent
        snapshot at exactly the donor's ltail). The first half of
        repair-by-replay (`fault/repair.py`); the second half is the
        ordinary catch-up loop after `unfence_replica`. Defaults to
        the most caught-up unfenced replica. Returns
        `(donor, donor_ltail)`."""
        if not 0 <= rid < self.n_replicas:
            raise ValueError(f"replica {rid} out of range")
        ltails = np.asarray(self.log.ltails)
        eligible = np.ones(self.n_replicas, bool)
        eligible[rid] = False
        if self._fenced is not None:
            eligible &= ~self._fenced
        if donor is None:
            if not eligible.any():
                raise RuntimeError(
                    "no healthy donor replica available (all fenced)"
                )
            masked = np.where(eligible, ltails, -1)
            donor = int(np.argmax(masked))
        elif donor == rid or not 0 <= donor < self.n_replicas:
            raise ValueError(f"bad donor replica {donor}")
        elif self._is_fenced(donor):
            raise ReplicaFencedError(donor)
        donor_ltail = int(ltails[donor])
        self.states = jax.tree.map(
            lambda x: x.at[rid].set(x[donor]), self.states
        )
        self.log = self.log._replace(
            ltails=self.log.ltails.at[rid].set(donor_ltail)
        )
        self._inflight[rid] = deque()
        get_tracer().emit(
            "fault-clone", rid=rid, donor=donor,
            donor_ltail=donor_ltail,
        )
        return donor, donor_ltail

    # ------------------------------------------------- durability (durable/)

    @property
    def wal(self):
        """The attached write-ahead log (None when not durable)."""
        return self._wal

    @_locked
    def attach_wal(self, wal, backfill: bool = True) -> None:
        """Attach a `durable/wal.py:WriteAheadLog`: every subsequent
        combiner append is persisted into it (fsync per its policy),
        and the exec loop drives segment reclamation from GC-head
        progress.

        `backfill=True` (default) persists entries the log already
        holds past the WAL's tail — `[wal.tail, tail)` read back from
        the ring (`core/log.py:ring_slice`) — so a WAL can attach to a
        live, mid-traffic instance. That is only possible while the
        ring still physically holds those entries; attaching later
        than `capacity` appends needs a snapshot-based recovery
        (`durable/recovery.py`) instead. A WAL ahead of the log is
        refused: its unreplayed tail must go through recovery first.
        """
        if self._wal is not None:
            raise RuntimeError("a WAL is already attached")
        tail = int(self.log.tail)
        wal_tail = wal.tail
        if wal_tail > tail:
            raise ValueError(
                f"WAL tail {wal_tail} is ahead of the log tail {tail}; "
                f"recover the WAL into the fleet first "
                f"(durable/recovery.py)"
            )
        if wal_tail < tail:
            if not backfill:
                raise ValueError(
                    f"WAL tail {wal_tail} is behind the log tail "
                    f"{tail} and backfill=False"
                )
            opcodes, args = ring_slice(self.spec, self.log,
                                       wal_tail, tail)
            wal.append(wal_tail, [
                (int(opcodes[i]), *(int(a) for a in args[i]))
                for i in range(opcodes.shape[0])
            ])
        self._wal = wal
        get_tracer().emit("wal-attach", tail=tail,
                          backfilled=tail - wal_tail)

    @_locked
    def detach_wal(self):
        """Detach and return the WAL (not closed — the caller owns its
        lifecycle)."""
        wal, self._wal = self._wal, None
        return wal

    def wal_sync(self) -> int:
        """fsync the attached WAL (`WriteAheadLog.sync`) — the serve
        frontend's durable-ack barrier. Deliberately NOT under the
        combiner lock: fsync latency must not stall concurrent
        combiner rounds; the WAL has its own lock."""
        wal = self._wal
        if wal is None:
            raise RuntimeError("no WAL attached (attach_wal)")
        return wal.sync()

    @_locked
    def execute_mut(self, op: tuple, token: ReplicaToken):
        """Stage one write op, combine, and return its response
        (`Replica::execute_mut`, `nr/src/replica.rs:345-356`)."""
        ctx = self._contexts[(token.rid, token.tid)]
        if not ctx.enqueue(op[0], tuple(op[1:])):
            self.combine(token.rid)
            ctx.enqueue(op[0], tuple(op[1:]))
        self.combine(token.rid)
        # This op is the thread's newest enqueue, so after the combine its
        # response is the newest delivered. Earlier `enqueue_mut`
        # responses stay queued, in order, for `responses()`.
        return ctx.res_newest()

    @_locked
    def enqueue_mut(self, op: tuple, token: ReplicaToken) -> None:
        """Stage a write without combining (explicit flat-combining batch
        building). Combines first if this thread's 32-slot ring is full."""
        ctx = self._contexts[(token.rid, token.tid)]
        if not ctx.enqueue(op[0], tuple(op[1:])):
            self.combine(token.rid)
            ctx.enqueue(op[0], tuple(op[1:]))

    @_locked
    def flush(self, rid: int | None = None) -> None:
        """Combine pending batches (all replicas by default)."""
        for r in range(self.n_replicas) if rid is None else [rid]:
            self.combine(r)

    @_locked
    def responses(self, token: ReplicaToken) -> list:
        """Drain delivered responses for this thread, in enqueue order."""
        ctx = self._contexts[(token.rid, token.tid)]
        out = []
        r = ctx.res()
        while r is not None:
            out.append(r)
            r = ctx.res()
        return out

    @_locked_phase("read", _read_fields)
    def _execute_one(self, op: tuple, token: ReplicaToken):
        """`execute` of one read alone, under its own hold."""
        rid = token.rid
        if self._is_fenced(rid):
            raise ReplicaFencedError(rid)
        fault_hook("read-sync", rid, self)
        ctail = int(self.log.ctail)
        rounds = 0
        while int(np.asarray(self.log.ltails)[rid]) < ctail:
            self._exec_round()
            rounds = self._watchdog(rounds, "read-sync")
        return self._dispatch_read(rid, op)

    def _read_refused(self, rid: int) -> BaseException | None:
        """The read combiner's admission of a read of replica `rid`: a
        fenced replica's read fails alone."""
        return ReplicaFencedError(rid) if self._is_fenced(rid) else None

    def _sync_reads(self, live: list[_ReadSlot]) -> None:
        """The read combiner's sync (`nr/src/replica.rs:404-410`): with
        the cursors fetched once, replay each lagging replica among the
        reads to the completed tail."""
        ltails, ctail = host_cursors(self.log.ltails, self.log.ctail)
        for rid in sorted({s.rid for s in live}):
            rounds = 0
            while ltails[rid] < ctail:
                self._exec_round()
                rounds = self._watchdog(rounds, "read-sync")
                ltails = np.asarray(self.log.ltails)

    @_locked
    def read_lag(self, rid: int) -> int:
        """Positions the completed tail leads replica `rid`'s applied
        cursor by — the staleness a sync-free read on `rid` would
        serve at. Locked for the same buffer-donation reason as
        `ltail`. The serve brownout read path
        (`serve/frontend.py:read`) checks this against its staleness
        bound before taking `execute_stale`."""
        if not 0 <= rid < self.n_replicas:
            raise ValueError(f"replica {rid} out of range")
        ctail = int(self.log.ctail)
        return max(0, ctail - int(np.asarray(self.log.ltails)[rid]))

    @_locked_phase("read", _read_fields)
    def execute_stale(self, op: tuple, token: ReplicaToken):
        """Bounded-staleness read: dispatch against this replica's
        CURRENT state with NO read-sync — the on-primary analog of the
        follower read path (`repl/follower.py`), used by the serve
        brownout mode. The caller owns the staleness contract: check
        `read_lag(rid)` against the bound first (under load the
        combiner rounds advance the replica continuously, so the lag
        observed there still bounds what this read serves at — replay
        only moves the replica FORWARD). Fenced replicas reject as on
        every other entry point."""
        rid = token.rid
        if self._is_fenced(rid):
            raise ReplicaFencedError(rid)
        return self._dispatch_read(rid, op)

    @_locked_phase("read", _read_fields)
    def execute_stale_bounded(self, op: tuple, token: ReplicaToken,
                              max_lag: int):
        """`execute_stale` with the staleness bound enforced ATOMICALLY:
        lag check and dispatch happen under one lock acquisition, so a
        concurrent batch cannot advance the completed tail between a
        caller's `read_lag` peek and the dispatch (that window would
        let a "bounded" read silently serve beyond its bound — and
        under-report the lag the bound gate records). Returns
        `(value, lag)` when `lag <= max_lag`, else None (the caller
        falls back to the synced path)."""
        rid = token.rid
        if self._is_fenced(rid):
            raise ReplicaFencedError(rid)
        ctail = int(self.log.ctail)
        lag = max(0, ctail - int(np.asarray(self.log.ltails)[rid]))
        if lag > int(max_lag):
            return None
        return self._dispatch_read(rid, op), lag

    @_locked
    def combine(self, rid: int) -> None:
        """Drain this replica's thread contexts (thread order —
        `nr/src/replica.rs:555-557`), append the batch, and replay until
        this replica has applied its own ops (`nr/src/replica.rs:543-595`).
        Responses are delivered to every replica's contexts as replay
        progresses."""
        ops: list[tuple] = []  # (opcode, *args)
        tids: list[int] = []  # per-op response destination
        for tid in range(self._threads_per_replica[rid]):
            for opcode, args in self._contexts[(rid, tid)].ops():
                ops.append((opcode, *args))
                tids.append(tid)
        if not ops:
            self._exec_round()  # combine with nothing staged still helps
            return
        self._append_and_replay(ops, rid, tids)

    @_locked
    def _try_fused_round(self, ops, rid, tids, n, pos0, pad,
                         opcodes, args, pending=None) -> bool:
        """Route one combiner round through the fused engine when
        eligible; False falls back to the append+exec chain. The
        eligibility is exactly the lock-step precondition the fused
        kernel requires, checked host-side against one fused cursor
        readback: every LIVE cursor at the pre-append tail, no
        in-flight responses owed (the fused round delivers only its
        own batch), and a window the engine's ring-span append
        supports. Results are bit-identical to the chain either way;
        only launch count and latency differ.

        With `pending` (a `_PendingRound` — the split-round path), the
        kernel is LAUNCHED and journaled here but the response
        readback (the round's host fence) is deferred to
        `_finish_round`: the whole device round overlaps whatever host
        work the caller does between begin and finish."""
        eng = self._fused_engine()
        if eng is None:
            return False
        if self._fenced is not None and not eng.supports_fenced:
            self._m_fused_fallback.inc()
            return False
        if not eng.supports(pad):
            self._m_fused_fallback.inc()
            return False
        if any(self._inflight):
            self._m_fused_fallback.inc()
            return False
        lts, tail = host_cursors(self.log.ltails, self.log.tail)
        live = lts if self._fenced is None else lts[~self._fenced]
        if not (live.size
                and int(live.min()) == tail == int(live.max())):
            self._m_fused_fallback.inc()
            return False
        # tail == pos0: the GC-help loop never appends
        fenced = self._fenced
        extra = {"deferred": True} if pending is not None else {}
        if eng.tier == "mesh_fused":
            extra["devices"] = eng.devices
        with span("fused-round", rid=rid, n=n, pos0=pos0,
                  window=pad, **extra) as sp:
            self.log, self.states, resps = eng.round(
                self.log, self.states, opcodes, args, n, fenced=fenced
            )
            if pending is None:
                # the response readback is also the round's host
                # fence: delivery below needs the values
                resps_np = np.asarray(resps)
                sp.fence(self.log, self.states)
        if self._wal is not None:
            # same order as the chain: journal once the ops ARE in the
            # in-memory log, before any response is delivered
            self._wal.append(pos0, ops)
            if fenced is None or not fenced.any():
                floor = pos0 + n
            else:
                floor = min(int(lts[fenced].min()), pos0 + n)
            self._wal.maybe_reclaim(floor)
        self._fused_rounds += 1
        self._m_engine_fused.inc()
        if eng.tier == "mesh_fused":
            # a mesh round by tier: counted next to the shmap/gspmd
            # chain rounds (nr.exec.mesh.*)
            self._m_mesh_fused_round.inc()
        if pending is not None:
            # split round: the launch is in flight; `_finish_round`
            # reads the responses back and delivers
            pending.fused_resps = resps
            pending.tier = eng.tier
            return True
        for j, tid in enumerate(tids):
            self._contexts[(rid, tid)].enqueue_resps(
                [int(resps_np[rid, j])]
            )
        self.last_round_tier = eng.tier
        self._tier_by_rid[rid] = eng.tier
        self._pos_by_rid[rid] = pos0
        return True

    @_locked
    def _begin_round(self, ops: list[tuple], rid: int,
                     tids: list[int], batch: bool = False,
                     defer: bool = False) -> _PendingRound:
        """First half of the shared combiner-round protocol (one
        protocol, every caller): fence guard, append-site fault hook,
        wait for ring space (helping GC), encode + append the batch,
        journal it, record each op's in-flight response destination.
        Returns the `_PendingRound` that `_finish_round` completes.

        `defer=False` is the serial shape: the caller runs
        `_finish_round` immediately (that composition IS
        `_append_and_replay`). `defer=True` (the split-round path,
        `begin_mut_batch`) leaves this replica's replay-to-target —
        or, on the fused tier, the response readback of the
        already-launched kernel — for `finish`, so a pipelined caller
        overlaps the next batch's host work with this round's device
        work. The lock is reentrant: callers already hold it.

        When the fused pallas tier is selected and the round is
        lock-step eligible, the whole round — append, replay, response
        gather — is ONE kernel launch (`_try_fused_round`); the WAL
        journaling, response-delivery order, and cursor lattice are
        identical by construction."""
        if self._is_fenced(rid):
            # a fenced replica's replay is frozen: waiting for it to
            # apply its own batch would hang forever — fail fast, the
            # serve layer re-homes (`ServeFrontend._fail_replica`)
            raise ReplicaFencedError(rid)
        fault_hook("append", rid, self)
        n = len(ops)
        max_batch = self.spec.capacity - self.spec.gc_slack
        if n > max_batch:
            raise LogTooSmallError(
                f"batch of {n} exceeds appendable capacity {max_batch}"
            )
        self._m_batch.observe(n)
        rounds = 0
        while int(log_space(self.spec, self.log)) < n:
            self._exec_round()
            rounds = self._watchdog(rounds, "append-gc")

        pos0 = int(self.log.tail)
        pad = 1 << (max(n, 1) - 1).bit_length()
        opcodes, args, _ = encode_ops(
            ops, self.spec.arg_width, pad_to=pad
        )
        counters = self.dispatch.host_counters
        if counters is not None and get_registry().enabled:
            counters("append", ops)
        pending = _PendingRound(rid, list(tids), n, pos0, batch=batch)
        if self._try_fused_round(ops, rid, tids, n, pos0, pad,
                                 opcodes, args,
                                 pending if defer else None):
            if pending.fused_resps is None:
                pending.done = True  # ran eagerly end-to-end
            return pending
        extra = {"batch": True} if batch else {}
        with span("append", rid=rid, n=n, pos0=pos0, **extra) as sp:
            self.log = self._append_call(opcodes, args, n)
            sp.fence(self.log)
        if self._wal is not None:
            # WAL write AFTER the device append, under the same lock:
            # a WAL record exists only for ops that ARE in the
            # in-memory log, so the two never disagree about history.
            # A WAL failure here raises out of the round after the ops
            # are appended — the post-append failure class the serve
            # layer already treats as maybe_executed (not retryable);
            # with fsync policy `always` the records are durable
            # before any response is delivered.
            self._wal.append(pos0, ops)
        inflight = self._inflight[rid]
        for j, tid in enumerate(tids):
            inflight.append((pos0 + j, tid))
        return pending

    @_locked
    def _finish_round(self, pending: _PendingRound) -> None:
        """Second half of the combiner-round protocol: replay until
        replica `rid` has applied its own ops (chain tier), or read
        back and deliver the fused launch's responses. No-op for a
        round `begin` already completed eagerly."""
        if pending.done:
            return
        pending.done = True
        rid = pending.rid
        if self._is_fenced(rid):
            # fenced between begin and finish (failover quarantine):
            # the chain replay cursor is frozen — waiting on it would
            # hang — and `fence_replica` dropped the in-flight
            # deliveries with crash semantics, so a computed fused
            # round's responses are equally undeliverable. Post-append
            # by construction: maybe_executed semantics.
            raise ReplicaFencedError(rid)
        if pending.fused_resps is not None:
            # the readback is the split round's host fence: the fused
            # launch (append+replay+gather) completes here
            resps_np = np.asarray(pending.fused_resps)
            pending.fused_resps = None
            for j, tid in enumerate(pending.tids):
                self._contexts[(rid, tid)].enqueue_resps(
                    [int(resps_np[rid, j])]
                )
            tier = pending.tier or "pallas_fused"
            self.last_round_tier = tier
            self._tier_by_rid[rid] = tier
            self._pos_by_rid[rid] = pending.pos0
            return
        target = pending.target
        rounds = 0
        with span("combine-replay", rid=rid, target=target) as sp:
            while int(np.asarray(self.log.ltails)[rid]) < target:
                self._exec_round()
                rounds = self._watchdog(rounds, "combine-replay")
            sp.fence(self.log, self.states)
        self.last_round_tier = self.engine
        self._tier_by_rid[rid] = self.engine
        self._pos_by_rid[rid] = pending.pos0

    @_locked
    def _append_and_replay(self, ops: list[tuple], rid: int,
                           tids: list[int], batch: bool = False) -> None:
        """Shared combiner-round tail (one protocol, every caller):
        `_begin_round` + `_finish_round` back-to-back. `combine`, the
        batch entry points, and nothing else — serve-path,
        split-round, and thread-context rounds cannot diverge because
        they all run this composition (the serve pipeline merely
        spreads the two halves across its stages)."""
        self._finish_round(
            self._begin_round(ops, rid, tids, batch=batch)
        )

    @_locked
    def _drop_batch_inflight(self, rid: int) -> None:
        """Failed-batch hygiene: appended ops stay in the log (they
        WILL replay — the log is the source of truth), but their
        responses are undeliverable. Drop this batch's pending
        deliveries and reset the sink so the NEXT batch's responses
        cannot be prefixed with stale replies."""
        self._inflight[rid] = deque(
            (p, t) for p, t in self._inflight[rid]
            if t != BATCH_TID
        )
        self._contexts[(rid, BATCH_TID)].reset()

    @_locked_phase("round", _batch_fields)
    def begin_mut_batch(self, ops: list[tuple],
                        rid: int = 0) -> _PendingRound:
        """Split-round batch entry, first half (the serve pipeline's
        assembly stage, `serve/frontend.py`): GC-wait, encode, append,
        journal — everything up to (not including) this replica's
        replay-to-target, which `finish_mut_batch` completes. On the
        fused tier the kernel (append+replay+response gather in one
        launch) is already ISSUED when this returns; only the readback
        waits — so the whole device round overlaps whatever host work
        the caller does before `finish`.

        At most ONE begun-but-unfinished round per replica
        (`RuntimeError` otherwise): a second in-flight round would
        interleave response delivery and make post-append failure
        attribution (`maybe_executed`) ambiguous — that invariant is
        why the serve pipeline's overlap depth is capped at 1.

        Failure semantics: a raise out of `begin` is pre-append only
        when it is the fence guard or an append-site injection
        (`FaultError(site='append')`) — both fire before the batch
        reaches the log; anything later (WAL journal failure) is
        post-append. A raise out of `finish` is always post-append:
        the ops are in the log and WILL replay, only responses are
        lost."""
        if not 0 <= rid < self.n_replicas:
            raise ValueError(f"replica {rid} out of range")
        if self._pending_batch.get(rid) is not None:
            raise RuntimeError(
                f"replica {rid} already has a round in flight; "
                f"finish_mut_batch it before beginning another "
                f"(at most one split round per replica)"
            )
        n = len(ops)
        sink = self._contexts.get((rid, BATCH_TID))
        if sink is None:
            sink = _BatchSink()
            self._contexts[(rid, BATCH_TID)] = sink
        if n == 0:
            pending = _PendingRound(rid, [], 0, int(self.log.tail),
                                    batch=True)
            pending.done = True
            self._pending_batch[rid] = pending
            return pending
        sink.expect(n)
        try:
            pending = self._begin_round(
                list(ops), rid, [BATCH_TID] * n, batch=True,
                defer=True,
            )
        except BaseException:
            self._drop_batch_inflight(rid)
            raise
        self._pending_batch[rid] = pending
        return pending

    @_locked_phase("round", _pending_fields)
    def finish_mut_batch(self, pending: _PendingRound) -> list:
        """Split-round batch entry, second half (the serve pipeline's
        completion stage): replay to the round's target (or read back
        the fused launch), collect the responses, release the
        replica's in-flight slot. Responses come back in op order.
        `pending` must be the replica's registered in-flight round
        (`begin_mut_batch`'s return value, finished exactly once)."""
        rid = pending.rid
        if self._pending_batch.get(rid) is not pending:
            raise RuntimeError(
                f"pending round for replica {rid} is not this "
                f"replica's in-flight round (already finished?)"
            )
        sink = self._contexts[(rid, BATCH_TID)]
        try:
            self._finish_round(pending)
            resps = sink.take()
            assert len(resps) == pending.n, (len(resps), pending.n)
            return resps
        except BaseException:
            self._drop_batch_inflight(rid)
            raise
        finally:
            self._pending_batch.pop(rid, None)

    @_locked
    def abort_mut_batch(self, pending: _PendingRound) -> None:
        """Abandon a begun-but-unfinished split round (the serve
        pipeline's failover teardown): its ops are in the log — they
        WILL replay, the log is the source of truth — but their
        responses are undeliverable, so the batch's pending deliveries
        drop (`_drop_batch_inflight`) and the replica's in-flight slot
        releases. Idempotent; a no-op for a round already finished or
        already torn down (e.g. by `fence_replica`'s crash
        semantics)."""
        rid = pending.rid
        if self._pending_batch.get(rid) is not pending:
            return
        self._pending_batch.pop(rid, None)
        pending.done = True
        pending.fused_resps = None
        self._drop_batch_inflight(rid)

    @_locked_phase("round", _batch_fields)
    def execute_mut_batch(self, ops: list[tuple],
                          rid: int = 0) -> list:
        """Execute a caller-assembled batch of write ops as ONE
        flat-combining round and return their responses in op order.

        The serve frontend's serial entry point (`serve/frontend.py`):
        the frontend's worker already holds a whole batch, so routing
        it through per-thread 32-slot contexts would just re-chunk it.
        This IS `begin_mut_batch` + `finish_mut_batch` back-to-back
        under one lock hold — the split-round protocol and the serial
        path cannot diverge because the serial path is the
        composition. One `encode_ops` + one append + one
        replay-to-target pass, sharing the combiner lock, GC helping
        loop, and response-delivery machinery with `combine`;
        responses collect through a dedicated `_BatchSink` keyed
        `(rid, BATCH_TID)` so concurrent per-thread contexts on the
        same replica keep their own deliveries.

        Interleaving with `execute_mut`/`enqueue_mut` from other OS
        threads is safe: the reentrant lock serializes rounds, and the
        shared `_inflight` deque orders deliveries by log position.
        """
        return self.finish_mut_batch(self.begin_mut_batch(ops, rid))

    @_locked
    def sync(self, rid: int | None = None) -> None:
        """Catch replicas up with the log tail (`Replica::sync`,
        `nr/src/replica.rs:469-479`); `rid=None` syncs all UNFENCED
        replicas (a fenced replica's replay is frozen — waiting on it
        would never terminate; syncing it explicitly fails fast).

        On a mesh, a large uniform backlog takes the RING tier first
        (`_ring_catchup` — `parallel/collectives.py:make_ring_exec`):
        the pending window shards over the chips and chunks rotate the
        ICI ring while replica shards stay resident, so catch-up
        bandwidth scales with the mesh instead of one chip's replay
        rate. Falls back to ordinary exec rounds for the remainder."""
        if rid is not None and self._is_fenced(rid):
            raise ReplicaFencedError(rid)
        rounds = 0
        while True:
            ltails = np.asarray(self.log.ltails)
            tail = int(self.log.tail)
            if rid is None:
                live = (
                    ltails if self._fenced is None
                    else ltails[~self._fenced]
                )
                done = all(int(lt) >= tail for lt in live)
            else:
                done = int(ltails[rid]) >= tail
            if done:
                return
            if self._ring_catchup():
                continue  # made >= shard-count positions of progress
            self._exec_round()
            rounds = self._watchdog(rounds, "sync")

    @_locked
    def _ring_catchup(self) -> bool:
        """One ring-replay pass over the pending window — the mesh
        catch-up tier (`nr.exec.engine.ring` counter). Eligible only
        when it is provably equivalent to the scan rounds it replaces:
        a mesh is placed, no replica is fenced (the ring applies the
        window to EVERY shard), no in-flight responses are owed (the
        ring produces none — the reference's catch-up likewise applies
        other replicas' entries without delivering their responses),
        and every cursor sits at the same position (one shared window).
        Applies `chunk * shards` entries in log order to all replicas
        (bit-identical to the scan by the ring-schedule contract,
        tests/test_collectives.py) and joins the cursor lattice
        host-side. Returns False when ineligible; progress when True
        is >= 2*shards positions, so callers cannot livelock on it."""
        if self.mesh is None or self._mesh_tier == "gspmd":
            return False
        if self._fenced is not None or any(self._inflight):
            return False
        lts, tail = host_cursors(self.log.ltails, self.log.tail)
        lt = int(lts.min())
        if int(lts.max()) != lt:
            return False
        shards = self._mesh_shards
        pending = tail - lt
        if shards < 2 or pending < 2 * shards:
            return False
        # power-of-two per-chip chunk bounded by exec_window: bounds
        # the per-window jit specializations (one per distinct W,
        # keyed by the static `window` argument) to log2 widths
        chunk = min(self.exec_window,
                    1 << ((pending // shards).bit_length() - 1))
        W = chunk * shards
        if self._ring_gather is None:
            self._ring_gather = jax.jit(
                _named(partial(gather_window, self.spec), "ring_gather"),
                static_argnames=("window",),
            )
        opc, args = self._ring_gather(self.log.opcodes, self.log.args,
                                      jnp.int64(lt), jnp.int64(tail),
                                      window=W)
        from jax.sharding import NamedSharding, PartitionSpec

        sh = NamedSharding(self.mesh, PartitionSpec("replica"))
        opc = jax.device_put(opc, sh)
        args = jax.device_put(args, sh)
        if self._ring_fn is None:
            from node_replication_tpu.parallel.collectives import (
                make_ring_exec,
            )

            self._ring_fn = make_ring_exec(self.dispatch, self.mesh)
        with span("ring-exec", window=W, chunk=chunk,
                  shards=shards, start=lt) as sp:
            self.states = self._ring_fn(opc, args, self.states)
            sp.fence(self.states)
        # cursor-lattice join, host-side: every replica consumed
        # [lt, lt+W) in order, so ltails/ctail/head land at lt+W
        # (head = min(ltails); no fenced mask here by eligibility)
        new_lt = lt + W
        self.log = self.log._replace(
            ltails=jax.device_put(
                np.full(self.n_replicas, new_lt, np.int64), sh
            ),
            ctail=jnp.maximum(self.log.ctail, jnp.int64(new_lt)),
            head=jnp.int64(new_lt),
        )
        if self._wal is not None:
            self._wal.maybe_reclaim(new_lt)
        self._ring_rounds += 1
        self._m_ring.inc()
        # rotated-window ICI traffic: each chip forwards its chunk
        # around the ring (2*shards - 1 hops of W/shards entries ≈ 2x
        # the window) — counted once per pass, documented estimate
        self._m_mesh_sync_bytes.inc(2 * (opc.nbytes + args.nbytes))
        return True

    @_locked
    def checkpoint(self, path: str) -> None:
        """Durable snapshot of log + all replica states (see
        `core/checkpoint.py`; the recovery model is deterministic-init +
        replay, SURVEY.md §5)."""
        from node_replication_tpu.core.checkpoint import save_snapshot

        save_snapshot(path, self.spec, self.log, self.states)

    @classmethod
    def restore(cls, path: str, dispatch: Dispatch,
                **kwargs) -> "NodeReplicated":
        """Rebuild a NodeReplicated from a snapshot. Thread registrations
        are not part of a snapshot (tokens are process-local, like the
        reference's !Send ReplicaToken); re-register after restore."""
        from node_replication_tpu.core.checkpoint import (
            load_snapshot,
            peek_spec,
        )

        spec = peek_spec(path)
        nr = cls(dispatch, n_replicas=spec.n_replicas,
                 log_entries=spec.capacity, gc_slack=spec.gc_slack,
                 **kwargs)
        _, nr.log, nr.states = load_snapshot(path, nr.states)
        nr._place_on_mesh()  # loaded arrays land on the default device
        return nr

    @_locked
    def recover(self, base_states=None, base_pos: int | None = None) -> None:
        """Discard replica states and rebuild them by replay
        (deterministic-init + replay — the reference's recovery model,
        SURVEY.md §5). Without a base, replay starts at position 0, which
        requires `tail <= capacity` (no slot overwritten yet); a
        long-running instance passes `base_states`/`base_pos` from a
        `checkpoint()` snapshot instead. In-flight responses are lost,
        matching a crash."""
        from node_replication_tpu.core.checkpoint import recover_states

        self.log, self.states = recover_states(
            self.dispatch, self.spec, self.log,
            base_states=base_states, base_pos=base_pos,
            window=self.exec_window,
        )
        self._place_on_mesh()  # rebuilt states: restore the shardings
        self._inflight = [deque() for _ in range(self.n_replicas)]
        # crash semantics: begun-but-unfinished split rounds die with
        # the rebuild (their ops are in the log and replayed; the
        # responses are gone, like every other in-flight delivery)
        self._pending_batch.clear()
        # full-fleet rebuild: every replica is freshly consistent, so
        # any quarantine fencing is moot
        self._fenced = None

    @_locked
    def stats(self) -> dict:
        """Flat observability counters (the harness's per-second ops
        capture is the reference's profiling story,
        `benches/mkbench.rs:755-761`). The original five keys are stable;
        `snapshot()` is the structured superset."""
        ltails = np.asarray(self.log.ltails)
        tail = int(self.log.tail)
        return {
            "appended": tail,
            "head": int(self.log.head),
            "ctail": int(self.log.ctail),
            "min_ltail": int(ltails.min()),
            "exec_rounds": self._exec_rounds,
            "idle_rounds": self._idle_rounds,
            "ring_rounds": self._ring_rounds,
            "engine": self.engine,
            "fused_rounds": self._fused_rounds,
            "fused_tier": self._fused_mode,
            "mesh_devices": self._mesh_shards,
            "max_lag": tail - int(ltails.min()),
        }

    @_locked
    def snapshot(self) -> dict:
        """Structured observability snapshot (JSON-safe): log cursors and
        ring occupancy, per-replica lag (`tail - ltails[r]`), exec-round
        progress vs. idle skips, in-flight response depths, and the
        process-wide metrics registry view when enabled. One host
        readback of the cursor arrays; safe to call on a live instance.
        """
        ltails = np.asarray(self.log.ltails)
        tail = int(self.log.tail)
        head = int(self.log.head)
        lags = [tail - int(lt) for lt in ltails]
        return {
            "log": {
                "tail": tail,
                "head": head,
                "ctail": int(self.log.ctail),
                "capacity": self.spec.capacity,
                # append occupancy: live entries held against GC slack
                "occupancy": (tail - head) / self.spec.capacity,
                "space": int(log_space(self.spec, self.log)),
            },
            "replicas": {
                "n": self.n_replicas,
                "ltails": [int(lt) for lt in ltails],
                "lag": lags,
                "max_lag": max(lags) if lags else 0,
                "threads": list(self._threads_per_replica),
                "inflight": [len(q) for q in self._inflight],
                "fenced": self.fenced_rids,
            },
            "exec": {
                "engine": self.engine,
                "window": self.exec_window,
                "rounds": self._exec_rounds,
                "idle_rounds": self._idle_rounds,
                "ring_rounds": self._ring_rounds,
                "fused_rounds": self._fused_rounds,
                "fused_tier": self._fused_mode,
            },
            "mesh": (
                # shard shape only: a per-rid device dict would be
                # O(R) reshapes + strings per snapshot poll at fleet
                # scale (R=4096) — per-rid lookup is replica_device()
                None if self.mesh is None else {
                    "devices": self._mesh_shards,
                    "tier": self._mesh_tier,
                    "replicas_per_device":
                        self.n_replicas // self._mesh_shards,
                }
            ),
            "metrics": get_registry().snapshot(),
        }

    @_locked
    def verify(self, fn: Callable[[Any], Any], rid: int = 0):
        """Test hook (`Replica::verify`, `nr/src/replica.rs:443-467`):
        force-sync, then expose replica `rid`'s state (as host numpy pytree)
        to `fn` for assertions."""
        self.sync()
        state = jax.tree.map(lambda a: np.asarray(a[rid]), self.states)
        return fn(state)

    @_locked
    def replicas_equal(self) -> bool:
        """All replicas converged to identical state."""
        return states_equal(self.states)

    # ------------------------------------------------------------ internals

    def _append_call(self, opcodes, args, n):
        if self.debug:
            from node_replication_tpu.utils.checks import debug_checks

            with debug_checks(True):  # checks live at (re-)trace time
                err, log = self._append_jit(self.log, opcodes, args, n)
            err.throw()
            return log
        return self._append_jit(self.log, opcodes, args, n)

    @_locked
    def _exec_round(self) -> bool:
        """One static-window replay round for every replica, plus response
        distribution. Returns True if any replica made progress.

        Idle short-circuit (ADVICE r5): when every replica is already at
        the tail there is nothing to replay, so the device round — a full
        sort+merge on the combined engine — is skipped entirely with a
        host-side cursor check. Empty-combine "help" calls and read-sync
        polling hit this constantly; the skip is counted in the
        `idle_rounds` stat / `nr.exec.idle_rounds` metric. Every caller
        loops on a cursor condition that is already satisfied when
        `min(ltails) == tail` (target <= tail, ctail <= tail), so
        skipping cannot livelock.
        """
        fault_hook("replay", -1, self)
        fenced = self._fenced
        # one fused cursor readback (ltails + tail): each D2H is a
        # host round trip, so two serial fetches would double every
        # round's host-sync latency
        ltails_before, tail = host_cursors(self.log.ltails,
                                           self.log.tail)
        # skip only when EVERY live cursor sits exactly at the tail: for
        # valid states min==tail implies that already (ltails <= tail),
        # and the max bound keeps a corrupted ltail > tail falling
        # through to the device round so debug-mode invariants still
        # fire on it. Fenced cursors are frozen and don't count — but a
        # freshly fenced laggard may still pin the GC head below the
        # live min, and only a device round advances head, so the skip
        # additionally requires head to have caught up.
        live = (
            ltails_before if fenced is None
            else ltails_before[~fenced]
        )
        idle = bool(
            live.size
            and int(live.min()) >= tail
            and int(live.max()) <= tail
        )
        if idle and fenced is not None:
            idle = int(np.asarray(self.log.head)) >= int(live.min())
        if idle:
            self._idle_rounds += 1
            self._m_idle.inc()
            return False
        self._exec_rounds += 1
        self._m_rounds.inc()
        self._m_engine.inc()
        tracer = get_tracer()
        # manual span: the hot path pays one branch when tracing is off
        # (no context-manager frame, no clock read); mesh rounds always
        # time — the collective-time histogram is part of the mesh.*
        # observability contract
        t0 = (
            time.perf_counter()
            if (tracer.enabled or self.mesh is not None) else 0.0
        )
        f_arr = None if fenced is None else jnp.asarray(fenced)
        if self.debug:
            from node_replication_tpu.utils.checks import debug_checks

            with debug_checks(True):  # checks live at (re-)trace time
                if f_arr is None:
                    err, (self.log, self.states, resps) = self._exec_jit(
                        self.log, self.states, window=self.exec_window
                    )
                else:
                    err, (self.log, self.states, resps) = (
                        self._exec_fenced_jit(
                            self.log, self.states, f_arr,
                            window=self.exec_window,
                        )
                    )
            err.throw()
        elif f_arr is None:
            self.log, self.states, resps = self._exec_jit(
                self.log, self.states, window=self.exec_window
            )
        else:
            self.log, self.states, resps = self._exec_fenced_jit(
                self.log, self.states, f_arr, window=self.exec_window
            )
        ltails_after = np.asarray(self.log.ltails)
        # worst remaining lag after this round (tail is fixed across the
        # round: replay never appends); one observe, values already host
        self._m_lag.observe(tail - int(ltails_after.min()))
        if self._wal is not None:
            # GC/head coupling (`durable/wal.py`): min(ltails) is the
            # head this round just computed (<= head under fencing —
            # an under-estimate only ever under-reclaims); O(1) when
            # no whole segment has fallen below the floor
            self._wal.maybe_reclaim(int(ltails_after.min()))
        resps_np = np.asarray(resps)
        for r in range(self.n_replicas):
            q = self._inflight[r]
            while q and q[0][0] < int(ltails_after[r]):
                pos, tid = q.popleft()
                self._contexts[(r, tid)].enqueue_resps(
                    [int(resps_np[r, pos - int(ltails_before[r])])]
                )
        progressed = bool(np.any(ltails_after > ltails_before))
        sync_bytes = 0
        if self.mesh is not None:
            # mesh.* observability: rounds by tier, collective/round
            # time, and the cross-device bytes this round FORCED back
            # to the host (response matrix + the two cursor readbacks —
            # the measurable gather traffic; the on-ICI lattice
            # reductions are a few scalars on top)
            sync_bytes = resps_np.nbytes + ltails_before.nbytes + 8 \
                + ltails_after.nbytes
            self._m_mesh_round.inc()
            self._m_mesh_dur.observe(time.perf_counter() - t0)
            self._m_mesh_sync_bytes.inc(sync_bytes)
        if tracer.enabled:
            if tracer.fence_spans:
                # device-honest end: a data-dependent readback
                # (utils/fence.py)
                from node_replication_tpu.utils.fence import fence

                fence(self.log, self.states)
            extra = (
                {"mesh_tier": self._mesh_tier,
                 "mesh_devices": self._mesh_shards,
                 "sync_bytes": sync_bytes}
                if self.mesh is not None else {}
            )
            tracer.emit(
                "exec-round",
                duration_s=time.perf_counter() - t0,
                fenced=tracer.fence_spans,
                engine=self.engine,
                window=self.exec_window,
                progressed=progressed,
                advanced=int((ltails_after - ltails_before).sum()),
                **extra,
            )
        return progressed

    def _watchdog(self, rounds: int, where: str) -> int:
        rounds += 1
        # Re-warn every WARN_ROUNDS, not once: the reference's spin
        # diagnostics fire every WARN_THRESHOLD iterations forever
        # (`nr/src/log.rs:43`, `351-358`) so a genuinely stuck run stays
        # loud (VERDICT r1 weak #4).
        if rounds % WARN_ROUNDS == 0:
            self._m_stalls.inc()
            dormant = int(np.argmin(np.asarray(self.log.ltails)))
            ltail = int(np.asarray(self.log.ltails)[dormant])
            tail = int(self.log.tail)
            logger.warning(
                "replay stalled in %s after %d rounds; most dormant "
                "replica=%d (ltail=%d, tail=%d)",
                where, rounds, dormant, ltail, tail,
            )
            get_tracer().emit(
                "watchdog", where=where, rounds=rounds, dormant=dormant,
                ltail=ltail, tail=tail,
            )
            if self.gc_callback is not None:
                self.gc_callback(0, dormant)
        return rounds
