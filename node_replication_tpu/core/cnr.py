"""`MultiLogReplicated`: the stateful CNR surface (the `cnr` crate's
`Replica` API, per-op form).

Mirrors `cnr/src/replica.rs`: every op is routed to a log by the user's
`LogMapper` (`hash % nlogs`, `cnr/src/replica.rs:435`); writes stage in the
issuing thread's context tagged with their log and combine per log
(`cnr/src/replica.rs:673-720`); reads sync only their mapped log
(`cnr/src/replica.rs:599-617`); `sync()` loops all logs and `sync_log`
targets one (`cnr/src/replica.rs:579-597`). The per-log GC-starvation
callback (`cnr/src/log.rs:135-142`) fires as `gc_callback(log_idx,
dormant_replica)` from the host-side watchdog when a log's replay stalls.

The jit-hot batch path is `core/multilog.make_multilog_step`; this wrapper
is the per-op convenience with the same replay kernels underneath.
"""

from __future__ import annotations

import logging
import threading

from node_replication_tpu.analysis.locks import make_rlock
import time
from collections import deque
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from node_replication_tpu.core.log import (
    LogSpec,
    LogState,
    WARN_ROUNDS,
    host_cursors,
)
from node_replication_tpu.core.multilog import (
    LogMapper,
    MultiLogSpec,
    _exec_one_log,
    multilog_init,
)
from node_replication_tpu.core.replica import (
    BATCH_TID,
    MAX_THREADS_PER_REPLICA,
    LogTooSmallError,
    ReplicaToken,
    _FusedTier,
    _locked,
    _PendingRound,
    replicate_state,
    states_equal,
)
from node_replication_tpu.fault.inject import fault_hook
from node_replication_tpu.obs.metrics import COUNT_BUCKETS, get_registry
from node_replication_tpu.ops.encoding import Dispatch, apply_read, encode_ops
from node_replication_tpu.obs.recorder import get_tracer, span

logger = logging.getLogger("node_replication_tpu")


class _PendingCnrBatch:
    """One `begin_mut_batch` batch between begin and finish: the
    per-log `_PendingRound` sub-rounds (in the log order they were
    appended) plus the scatter map back to submission indices. The
    CNR face of the split-round protocol (`core/replica.py:
    _PendingRound`); NOT atomic across logs, like the serial path."""

    __slots__ = ("rid", "n", "subs")

    def __init__(self, rid: int, n: int):
        self.rid = rid
        self.n = n
        #: list of (log_idx, submission_indices, _PendingRound)
        self.subs: list[tuple[int, list[int], _PendingRound]] = []


class MultiLogReplicated(_FusedTier):
    """N replicas of one `Dispatch` behind L commutativity-partitioned logs."""

    def __init__(
        self,
        dispatch: Dispatch,
        log_mapper: LogMapper,
        nlogs: int,
        n_replicas: int = 1,
        log_entries: int = 1 << 12,
        gc_slack: int = 128,
        exec_window: int = 128,
        gc_callback: Callable[[int, int], None] | None = None,
        mesh=None,
        engine: str = "auto",
    ):
        self.spec = MultiLogSpec(
            nlogs=nlogs,
            capacity=log_entries,
            n_replicas=n_replicas,
            arg_width=dispatch.arg_width,
            gc_slack=gc_slack,
        )
        self.dispatch = dispatch
        self.log_mapper = log_mapper
        self.exec_window = int(exec_window)
        self.gc_callback = gc_callback

        self.ml = multilog_init(self.spec)
        self.states = replicate_state(dispatch.init_state(), n_replicas)

        # mesh placement (the NodeReplicated(mesh=) twin): the stacked
        # log rings shard over the mesh 'log' axis, replica states (and
        # the [L, R] ltails' replica dimension) over 'replica'
        # (`parallel/mesh.py:place` handles MultiLogState). Exec/append
        # jits are unchanged — GSPMD propagates the placed inputs'
        # shardings and inserts the cross-column collectives (the
        # annotation tier; the ShardedCnrRunner proves the placement on
        # the fused step, this wires it into the stateful wrapper).
        self.mesh = None
        self._mesh_shards = 0
        self._mesh_rep_shards = 1
        if mesh is not None:
            from jax.sharding import Mesh

            from node_replication_tpu.parallel.mesh import (
                announce_placement,
                place,
            )

            if not isinstance(mesh, Mesh) or not {
                "replica", "log"
            } <= set(mesh.axis_names):
                # the placement spec trees name both axes — a partial
                # mesh would die inside NamedSharding with an opaque
                # resource-axis error instead of this
                raise ValueError(
                    f"MultiLogReplicated needs a ('replica', 'log') "
                    f"Mesh (parallel/mesh.py:make_mesh); got "
                    f"{mesh!r}"
                )
            shape = dict(mesh.shape)
            if n_replicas % shape["replica"]:
                raise ValueError(
                    f"R={n_replicas} replicas cannot shard over "
                    f"{shape['replica']} mesh rows"
                )
            if nlogs % shape["log"]:
                raise ValueError(
                    f"L={nlogs} logs cannot shard over "
                    f"{shape['log']} mesh columns"
                )
            self.mesh = mesh
            self._mesh_shards = int(np.prod(mesh.devices.shape))
            self._mesh_rep_shards = shape["replica"]
            announce_placement(mesh, n_replicas, "MultiLogReplicated",
                               "gspmd")
            self.ml, self.states = place(self.ml, self.states, mesh)

        # Combiner lock (`replica._locked`): one combiner pass at a
        # time across all logs; reentrant so watchdog gc_callbacks can
        # re-enter sync_log on the same thread.
        self._lock = make_rlock("MultiLogReplicated._lock")
        self._threads_per_replica = [0] * n_replicas
        # staged ops: (rid, tid) -> deque[(log, opcode, args)]
        self._pending: dict[tuple[int, int], deque] = {}
        # appended-but-unanswered: (rid, log) -> deque[(pos, tid)]
        self._inflight: dict[tuple[int, int], deque] = {}
        # delivered responses per thread, in enqueue order per log
        self._resps: dict[tuple[int, int], deque] = {}
        # split-round registry (`begin_mut_batch`): at most ONE
        # begun-but-unfinished batch per replica (the NodeReplicated
        # invariant, here spanning the batch's per-log sub-rounds)
        self._pending_batch: dict[int, "_PendingCnrBatch"] = {}
        # per-log observability: LogMapper routing counts, combiner
        # passes, replay rounds (+ idle skips) per log
        self._log_selected = [0] * nlogs
        self._combine_rounds = [0] * nlogs
        self._exec_rounds = 0
        self._idle_rounds = 0
        reg = get_registry()
        self._m_rounds = reg.counter("cnr.exec.rounds")
        self._m_idle = reg.counter("cnr.exec.idle_rounds")
        self._m_combine = reg.counter("cnr.combine.rounds")
        self._m_batch = reg.histogram("cnr.combine.batch_size",
                                      buckets=COUNT_BUCKETS)
        self._m_stalls = reg.counter("cnr.watchdog.stalls")

        # ---- fused pallas per-log combiner tier (the NodeReplicated
        # twin, `core/replica._FusedTier`): a per-log sub-batch whose
        # log is lock-step eligible appends+replays+answers as ONE
        # kernel launch. Only engine='pallas' runs it; 'auto' and
        # 'scan' keep the chain and never consult it. CNR has no
        # fencing, so the fenced kernel variant never builds here.
        if engine not in ("auto", "scan", "pallas"):
            raise ValueError(f"unknown engine {engine!r}")
        self._fused_cnr_cache: dict = {}
        self._init_fused_tier(engine, dispatch, mesh, reg, "cnr")
        if self.mesh is not None:
            self._m_mesh_round = reg.counter("cnr.exec.mesh.gspmd")
            self._m_mesh_sync_bytes = reg.counter("mesh.sync_bytes")

        spec, d = self.spec, dispatch

        def exec_round(ml, states, log_idx: int, window: int):
            states, resps, lt = jax.vmap(
                lambda s, t: _exec_one_log(
                    spec, d, ml.opcodes[log_idx], ml.args[log_idx],
                    ml.tail[log_idx], s, t, window,
                )
            )(states, ml.ltails[log_idx])
            ml = ml._replace(
                ltails=ml.ltails.at[log_idx].set(lt),
                ctail=ml.ctail.at[log_idx].set(
                    jnp.maximum(ml.ctail[log_idx], jnp.max(lt))
                ),
                head=ml.head.at[log_idx].set(jnp.min(lt)),
            )
            return ml, states, resps

        self._exec_jit = jax.jit(
            exec_round, static_argnames=("log_idx", "window"),
            donate_argnums=(0, 1),
        )

        def append_one(ml, log_idx: int, opcodes, args, count):
            B = opcodes.shape[0]
            lanes = jnp.arange(B, dtype=jnp.int64)
            valid = lanes < count
            slot = jnp.where(
                valid, (ml.tail[log_idx] + lanes) & spec.mask, spec.capacity
            ).astype(jnp.int32)
            return ml._replace(
                opcodes=ml.opcodes.at[log_idx, slot].set(
                    opcodes, mode="drop"
                ),
                args=ml.args.at[log_idx, slot].set(args, mode="drop"),
                tail=ml.tail.at[log_idx].add(count),
            )

        self._append_jit = jax.jit(
            append_one, static_argnames=("log_idx",), donate_argnums=(0,)
        )

        def read_one(states, rid, opcode, args):
            state = jax.tree.map(lambda a: a[rid], states)
            return apply_read(d, state, opcode, args)

        self._read_jit = jax.jit(read_one)

    # ------------------------------------------------------------------ API

    @property
    def n_replicas(self) -> int:
        return self.spec.n_replicas

    @property
    def nlogs(self) -> int:
        return self.spec.nlogs

    def replica_device(self, rid: int):
        """First device of the mesh row hosting replica `rid`'s state
        shard (None when un-meshed) — the NodeReplicated twin the
        serve frontend's worker→device map consumes. A CNR replica's
        state lives on one 'replica' row but its per-log ring columns
        span that row, so the row's first device stands for the
        shard's home."""
        if self.mesh is None:
            return None
        if not 0 <= rid < self.n_replicas:
            raise ValueError(f"replica {rid} out of range")
        row = rid // (self.n_replicas // self._mesh_rep_shards)
        return self.mesh.devices[row].flat[0]

    @_locked
    def register(self, rid: int = 0) -> ReplicaToken:
        """Register a logical thread on replica `rid` — registration spans
        every log, as `cnr`'s replica registers with each
        (`cnr/src/replica.rs:209-281`)."""
        if not 0 <= rid < self.n_replicas:
            raise ValueError(f"replica {rid} out of range")
        tid = self._threads_per_replica[rid]
        if tid >= MAX_THREADS_PER_REPLICA:
            raise RuntimeError(f"replica {rid} thread limit reached")
        self._threads_per_replica[rid] = tid + 1
        self._pending[(rid, tid)] = deque()
        self._resps[(rid, tid)] = deque()
        return ReplicaToken(rid, tid)

    @_locked
    def _map(self, op: tuple) -> int:
        h = self.log_mapper(op[0], tuple(op[1:])) % self.nlogs
        self._log_selected[h] += 1
        return h

    @_locked
    def execute_mut(self, op: tuple, token: ReplicaToken):
        """Route the write to its log, combine that log, return its
        response (`cnr/src/replica.rs:430-445`)."""
        h = self.enqueue_mut(op, token)
        self.combine(token.rid, h)
        # The combine delivered this op's response last (it is the
        # thread's newest append on its log); pop it from the tail so
        # earlier enqueued-but-unread responses stay for `responses()`.
        q = self._resps[(token.rid, token.tid)]
        return q.pop() if q else None

    @_locked
    def enqueue_mut(self, op: tuple, token: ReplicaToken) -> int:
        """Stage a write without combining (explicit batch building, the
        NodeReplicated twin). Its response arrives via `responses()`
        after a later combine of its mapped log. Returns the mapped log
        index (the staging path `execute_mut` shares)."""
        h = self._map(op)
        self._pending[(token.rid, token.tid)].append(
            (h, op[0], tuple(op[1:]))
        )
        return h

    @_locked
    def flush(self, rid: int | None = None) -> None:
        """Combine every log with staged ops (all replicas by default)."""
        for r in range(self.n_replicas) if rid is None else [rid]:
            logs = {
                h
                for tid in range(self._threads_per_replica[r])
                for (h, _, _) in self._pending[(r, tid)]
            }
            for h in sorted(logs):
                self.combine(r, h)

    @_locked
    def responses(self, token: ReplicaToken) -> list:
        """Drain delivered responses for this thread (enqueue order per
        log; delivery order across logs follows combine order)."""
        q = self._resps[(token.rid, token.tid)]
        out = list(q)
        q.clear()
        return out

    @_locked
    def execute(self, op: tuple, token: ReplicaToken):
        """Read path: sync only the mapped log, then dispatch locally
        (`cnr/src/replica.rs:599-617`)."""
        h = self._map(op)
        rid = token.rid
        fault_hook("read-sync", rid, self)
        ctail = int(np.asarray(self.ml.ctail)[h])
        rounds = 0
        while int(np.asarray(self.ml.ltails)[h, rid]) < ctail:
            self._exec_round(h)
            rounds = self._watchdog(rounds, h, "read-sync")
        args = np.zeros((self.spec.arg_width,), np.int32)
        args[: len(op) - 1] = op[1:]
        return int(
            self._read_jit(
                self.states, jnp.int32(rid), jnp.int32(op[0]),
                jnp.asarray(args),
            )
        )

    @_locked
    def combine(self, rid: int, log_idx: int) -> None:
        """Drain replica `rid`'s staged ops for `log_idx` (thread order),
        append them to that log, and replay it until `rid` has applied its
        own ops — one log's combiner pass (`cnr/src/replica.rs:673-720`)."""
        ops: list[tuple] = []  # (opcode, *args)
        tids: list[int] = []
        for tid in range(self._threads_per_replica[rid]):
            q = self._pending[(rid, tid)]
            keep = deque()
            while q:
                h, opcode, args = q.popleft()
                if h == log_idx:
                    ops.append((opcode, *args))
                    tids.append(tid)
                else:
                    keep.append((h, opcode, args))
            q.extend(keep)
        if not ops:
            self._exec_round(log_idx)
            return
        self._append_and_replay_log(log_idx, rid, ops, tids)

    def _fused_log_spec(self) -> LogSpec:
        """The single-log `LogSpec` the fused engine is built against —
        every CNR log shares it (same capacity/slack), so ONE engine
        serves all per-log rounds."""
        return LogSpec(
            capacity=self.spec.capacity,
            n_replicas=self.spec.n_replicas,
            arg_width=self.spec.arg_width,
            gc_slack=self.spec.gc_slack,
        )

    @_locked
    def _fused_cnr_round(self, eng, window: int):
        """Per-window fused round over ONE mapped log: view the log's
        column of the stacked `MultiLogState` as a `LogState`, run the
        engine's model-layout round, write the column back. `log_idx`
        is a traced operand so one program serves every log."""
        fn = self._fused_cnr_cache.get(window)
        if fn is None:
            inner = eng.round_fn(window, fenced=False)

            def cnr_round(ml, states, log_idx, opcodes, args, count):
                log = LogState(
                    opcodes=ml.opcodes[log_idx],
                    args=ml.args[log_idx],
                    head=ml.head[log_idx],
                    tail=ml.tail[log_idx],
                    ctail=ml.ctail[log_idx],
                    ltails=ml.ltails[log_idx],
                )
                log, states, resps = inner(
                    log, states, opcodes, args, count
                )
                ml = ml._replace(
                    opcodes=ml.opcodes.at[log_idx].set(log.opcodes),
                    args=ml.args.at[log_idx].set(log.args),
                    head=ml.head.at[log_idx].set(log.head),
                    tail=ml.tail.at[log_idx].set(log.tail),
                    ctail=ml.ctail.at[log_idx].set(log.ctail),
                    ltails=ml.ltails.at[log_idx].set(log.ltails),
                )
                return ml, states, resps

            # interpret mode runs eagerly (jit+interpret+x64 trips the
            # MLIR dtype mismatch — see FusedHashmapEngine.round)
            fn = (
                cnr_round if eng.interpret
                else jax.jit(cnr_round, donate_argnums=(0, 1))
            )
            self._fused_cnr_cache[window] = fn
        return fn

    @_locked
    def _try_fused_round_log(self, log_idx: int, rid: int, ops, tids,
                             n: int, pos0: int, pad: int,
                             opcodes, args, pending=None) -> bool:
        """Route one per-log combiner pass through the fused engine
        when the log is lock-step eligible (the NodeReplicated
        `_try_fused_round` twin, minus fencing/WAL, which CNR does not
        carry). With `pending` (the split-round path) the kernel is
        launched here and the response readback deferred to
        `_finish_round_log`."""
        eng = self._fused_engine()
        if eng is None:
            return False
        if not eng.supports(pad):
            self._m_fused_fallback.inc()
            return False
        if any(self._inflight.get((r, log_idx))
               for r in range(self.n_replicas)):
            self._m_fused_fallback.inc()
            return False
        lts, tail = host_cursors(self.ml.ltails[log_idx],
                                 self.ml.tail[log_idx])
        if not (int(lts.min()) == tail == int(lts.max())):
            self._m_fused_fallback.inc()
            return False
        t0 = time.perf_counter()
        fn = self._fused_cnr_round(eng, pad)
        extra = {"deferred": True} if pending is not None else {}
        with span("fused-round", log=log_idx, rid=rid, n=n, pos0=pos0,
                  window=pad, **extra) as sp:
            self.ml, self.states, resps = fn(
                self.ml, self.states, jnp.int32(log_idx), opcodes,
                args, n,
            )
            if pending is None:
                resps_np = np.asarray(resps)
                sp.fence(self.ml, self.states)
        dt = time.perf_counter() - t0
        # the CNR path embeds the raw round_fn in its own program, so
        # the engine's round() wrapper never runs — report through the
        # same instrumentation hook (tier counter + kernel.* metrics +
        # kernel-launch event; one contract, never two)
        eng.note_round(pad, n, dt)
        self._fused_rounds += 1
        self._m_engine_fused.inc()
        if pending is not None:
            pending.fused_resps = resps
            return True
        for j, tid in enumerate(tids):
            self._resps[(rid, tid)].append(int(resps_np[rid, j]))
        self.last_round_tier = "pallas_fused"
        self._tier_by_rid[rid] = "pallas_fused"
        self._pos_by_rid[rid] = pos0
        return True

    @_locked
    def _begin_round_log(self, log_idx: int, rid: int,
                         ops: list[tuple], tids: list[int],
                         batch: bool = False,
                         defer: bool = False) -> _PendingRound:
        """First half of the per-log combiner pass (the NodeReplicated
        `_begin_round` twin): wait for ring space on this log, encode
        + append, record each op's in-flight response destination.
        `defer=True` leaves the replay-to-target (or the fused
        launch's readback) for `_finish_round_log`. The lock is
        reentrant: callers already hold it."""
        fault_hook("append", rid, self)
        n = len(ops)
        self._combine_rounds[log_idx] += 1
        self._m_combine.inc()
        self._m_batch.observe(n)
        rounds = 0
        while (
            self.spec.capacity - self.spec.gc_slack
            - int(np.asarray(self.ml.tail - self.ml.head)[log_idx])
        ) < n:
            self._exec_round(log_idx)
            rounds = self._watchdog(rounds, log_idx, "append-gc")
        pos0 = int(np.asarray(self.ml.tail)[log_idx])
        pad = 1 << (max(n, 1) - 1).bit_length()
        opcodes, args, _ = encode_ops(
            ops, self.spec.arg_width, pad_to=pad
        )
        pending = _PendingRound(rid, list(tids), n, pos0, batch=batch,
                                log_idx=log_idx)
        if self._try_fused_round_log(log_idx, rid, ops, tids, n, pos0,
                                     pad, opcodes, args,
                                     pending if defer else None):
            if pending.fused_resps is None:
                pending.done = True  # ran eagerly end-to-end
            return pending
        extra = {"batch": True} if batch else {}
        with span("append", log=log_idx, rid=rid, n=n, pos0=pos0,
                  **extra) as sp:
            self.ml = self._append_jit(
                self.ml, log_idx, opcodes, args, jnp.int64(n)
            )
            sp.fence(self.ml)
        infl = self._inflight.setdefault((rid, log_idx), deque())
        for j, tid in enumerate(tids):
            infl.append((pos0 + j, tid))
        return pending

    @_locked
    def _finish_round_log(self, pending: _PendingRound) -> None:
        """Second half of the per-log combiner pass: replay this log
        until replica `rid` has applied its own ops, or read back and
        deliver the fused launch's responses."""
        if pending.done:
            return
        pending.done = True
        rid, log_idx = pending.rid, pending.log_idx
        if pending.fused_resps is not None:
            resps_np = np.asarray(pending.fused_resps)
            pending.fused_resps = None
            for j, tid in enumerate(pending.tids):
                self._resps[(rid, tid)].append(int(resps_np[rid, j]))
            self.last_round_tier = "pallas_fused"
            self._tier_by_rid[rid] = "pallas_fused"
            self._pos_by_rid[rid] = pending.pos0
            return
        target = pending.target
        rounds = 0
        with span("combine-replay", log=log_idx, rid=rid,
                  target=target) as sp:
            while int(np.asarray(self.ml.ltails)[log_idx, rid]) < target:
                self._exec_round(log_idx)
                rounds = self._watchdog(rounds, log_idx, "combine-replay")
            sp.fence(self.ml, self.states)
        self.last_round_tier = "scan"
        self._tier_by_rid[rid] = "scan"
        self._pos_by_rid[rid] = pending.pos0

    @_locked
    def _append_and_replay_log(self, log_idx: int, rid: int,
                               ops: list[tuple], tids: list[int],
                               batch: bool = False) -> None:
        """Shared per-log combiner-pass tail (`combine` and
        `execute_mut_batch`'s sub-batches — one protocol, never two):
        `_begin_round_log` + `_finish_round_log` back-to-back; the
        split-round path (`begin_mut_batch`) runs the same halves
        spread across the serve pipeline's stages. Lock-step-eligible
        passes route through the fused pallas tier when selected
        (`_try_fused_round_log`) — one kernel launch per sub-batch."""
        self._finish_round_log(
            self._begin_round_log(log_idx, rid, ops, tids, batch=batch)
        )

    @_locked
    def _drop_batch_inflight(self, rid: int) -> None:
        """Failed-batch hygiene (the NodeReplicated twin): drop every
        pending BATCH_TID delivery for this replica on every log and
        clear the sink, so the next batch cannot inherit stale replies
        (and a short sink cannot wedge every later batch on this
        replica)."""
        for key in [(rid, h) for h in range(self.nlogs)
                    if (rid, h) in self._inflight]:
            self._inflight[key] = deque(
                (p, t) for p, t in self._inflight[key]
                if t != BATCH_TID
            )
        sink = self._resps.get((rid, BATCH_TID))
        if sink is not None:
            sink.clear()

    @_locked
    def begin_mut_batch(self, ops: list[tuple],
                        rid: int = 0) -> "_PendingCnrBatch":
        """Split-round batch entry, first half (the
        `NodeReplicated.begin_mut_batch` twin): route each op through
        the `LogMapper`, then append + journal every per-log sub-batch
        in log order, deferring each log's replay-to-target to
        `finish_mut_batch`. At most ONE begun-but-unfinished batch per
        replica (`RuntimeError` otherwise). NOT atomic across logs —
        the same per-log contract as `execute_mut_batch`."""
        if not 0 <= rid < self.n_replicas:
            raise ValueError(f"replica {rid} out of range")
        if self._pending_batch.get(rid) is not None:
            raise RuntimeError(
                f"replica {rid} already has a batch in flight; "
                f"finish_mut_batch it before beginning another "
                f"(at most one split round per replica)"
            )
        n = len(ops)
        sink = self._resps.get((rid, BATCH_TID))
        if sink is None:
            sink = deque()
            self._resps[(rid, BATCH_TID)] = sink
        groups: dict[int, list[int]] = {}
        for i, op in enumerate(ops):
            groups.setdefault(self._map(op), []).append(i)
        max_batch = self.spec.capacity - self.spec.gc_slack
        for h, idxs in groups.items():
            if len(idxs) > max_batch:
                raise LogTooSmallError(
                    f"log {h}: sub-batch of {len(idxs)} exceeds "
                    f"appendable capacity {max_batch}"
                )
        pend = _PendingCnrBatch(rid, n)
        try:
            for h in sorted(groups):
                idxs = groups[h]
                sub = self._begin_round_log(
                    h, rid, [ops[i] for i in idxs],
                    [BATCH_TID] * len(idxs), batch=True, defer=True,
                )
                pend.subs.append((h, idxs, sub))
        except BaseException:
            self._drop_batch_inflight(rid)
            raise
        self._pending_batch[rid] = pend
        return pend

    @_locked
    def finish_mut_batch(self, pend: "_PendingCnrBatch") -> list:
        """Split-round batch entry, second half: replay every per-log
        sub-round to its target (in the same log order `begin`
        appended), scatter responses back to submission indices,
        release the replica's in-flight slot."""
        rid = pend.rid
        if self._pending_batch.get(rid) is not pend:
            raise RuntimeError(
                f"pending batch for replica {rid} is not this "
                f"replica's in-flight batch (already finished?)"
            )
        sink = self._resps[(rid, BATCH_TID)]
        out: list = [None] * pend.n
        try:
            for h, idxs, sub in pend.subs:
                self._finish_round_log(sub)
                assert len(sink) == len(idxs), (len(sink), len(idxs))
                for i in idxs:
                    out[i] = sink.popleft()
            return out
        except BaseException:
            self._drop_batch_inflight(rid)
            raise
        finally:
            self._pending_batch.pop(rid, None)

    @_locked
    def abort_mut_batch(self, pend: "_PendingCnrBatch") -> None:
        """Abandon a begun-but-unfinished split batch (the
        `NodeReplicated.abort_mut_batch` twin): every appended sub-
        batch WILL replay; only response delivery drops. Idempotent."""
        rid = pend.rid
        if self._pending_batch.get(rid) is not pend:
            return
        self._pending_batch.pop(rid, None)
        for _, _, sub in pend.subs:
            sub.done = True
            sub.fused_resps = None
        self._drop_batch_inflight(rid)

    @_locked
    def execute_mut_batch(self, ops: list[tuple],
                          rid: int = 0) -> list:
        """Execute a caller-assembled batch as one combiner pass PER
        MAPPED LOG and return responses in submission order — the CNR
        twin of `NodeReplicated.execute_mut_batch` (the serve
        frontend's serial entry point).

        Each op routes through the `LogMapper` exactly as `execute_mut`
        would (`cnr/src/replica.rs:435`); the batch then splits into
        per-log sub-batches that append and replay one log at a time,
        in log order (each pass is `_begin_round_log` +
        `_finish_round_log` back-to-back, the same halves the
        split-round path runs). A failure during log `h`'s pass
        therefore leaves later logs' sub-batches UNappended — the
        historical serial contract — whereas the split path
        (`begin_mut_batch`) appends every sub-batch up front so the
        whole batch shares one post-append failure class. Responses
        come back through a dedicated deque sink keyed
        `(rid, BATCH_TID)` and are scattered back to the callers'
        submission indices, so interleaving with per-thread
        `execute_mut` traffic on the same replica stays ordered.
        """
        if not 0 <= rid < self.n_replicas:
            raise ValueError(f"replica {rid} out of range")
        if self._pending_batch.get(rid) is not None:
            # the NodeReplicated guard (there via begin_mut_batch): a
            # serial batch interleaved with a begun split batch would
            # deliver the split batch's appended entries into the
            # shared BATCH_TID sink and scatter wrong responses
            raise RuntimeError(
                f"replica {rid} already has a batch in flight; "
                f"finish_mut_batch it before executing another"
            )
        n = len(ops)
        if n == 0:
            return []
        sink = self._resps.get((rid, BATCH_TID))
        if sink is None:
            sink = deque()
            self._resps[(rid, BATCH_TID)] = sink
        groups: dict[int, list[int]] = {}
        for i, op in enumerate(ops):
            groups.setdefault(self._map(op), []).append(i)
        max_batch = self.spec.capacity - self.spec.gc_slack
        for h, idxs in groups.items():
            if len(idxs) > max_batch:
                raise LogTooSmallError(
                    f"log {h}: sub-batch of {len(idxs)} exceeds "
                    f"appendable capacity {max_batch}"
                )
        out: list = [None] * n
        try:
            for h in sorted(groups):
                idxs = groups[h]
                m = len(idxs)
                self._append_and_replay_log(
                    h, rid, [ops[i] for i in idxs],
                    [BATCH_TID] * m, batch=True,
                )
                assert len(sink) == m, (len(sink), m)
                for i in idxs:
                    out[i] = sink.popleft()
            return out
        except BaseException:
            self._drop_batch_inflight(rid)
            raise

    @_locked
    def sync(self, rid: int | None = None) -> None:
        """Catch up on every log (`cnr/src/replica.rs:579-597`)."""
        for l in range(self.nlogs):
            self.sync_log(rid, l)

    @_locked
    def sync_log(self, rid: int | None, log_idx: int) -> None:
        """Targeted single-log sync (`sync_log`,
        `cnr/src/replica.rs:579-597`). The harness wires the GC callback
        to this, answering starvation reports (`benches/mkbench.rs:
        763-772`)."""
        rounds = 0
        while True:
            lt = np.asarray(self.ml.ltails)[log_idx]
            tail = int(np.asarray(self.ml.tail)[log_idx])
            done = (
                all(int(x) >= tail for x in lt)
                if rid is None
                else int(lt[rid]) >= tail
            )
            if done:
                return
            self._exec_round(log_idx)
            rounds = self._watchdog(rounds, log_idx, "sync")

    @_locked
    def verify(self, fn: Callable[[Any], Any], rid: int = 0):
        self.sync()
        state = jax.tree.map(lambda a: np.asarray(a[rid]), self.states)
        return fn(state)

    @_locked
    def replicas_equal(self) -> bool:
        return states_equal(self.states)

    @_locked
    def stats(self) -> dict:
        """Flat per-log counters (original three keys stable);
        `snapshot()` is the structured superset."""
        return {
            "tails": [int(t) for t in np.asarray(self.ml.tail)],
            "ctails": [int(t) for t in np.asarray(self.ml.ctail)],
            "heads": [int(t) for t in np.asarray(self.ml.head)],
            "log_selected": list(self._log_selected),
            "combine_rounds": list(self._combine_rounds),
            "exec_rounds": self._exec_rounds,
            "idle_rounds": self._idle_rounds,
            "fused_rounds": self._fused_rounds,
            "fused_tier": self._fused_mode,
        }

    @_locked
    def snapshot(self) -> dict:
        """Structured observability snapshot (JSON-safe), the CNR twin of
        `NodeReplicated.snapshot()`: per-log cursors and per-(log,
        replica) lag, LogMapper routing counts (skew at a glance),
        combiner passes and replay rounds per log, plus the process-wide
        metrics view when enabled."""
        tails = np.asarray(self.ml.tail)
        heads = np.asarray(self.ml.head)
        ctails = np.asarray(self.ml.ctail)
        ltails = np.asarray(self.ml.ltails)
        logs = []
        for l in range(self.nlogs):
            lag = [int(tails[l] - lt) for lt in ltails[l]]
            logs.append({
                "tail": int(tails[l]),
                "head": int(heads[l]),
                "ctail": int(ctails[l]),
                "lag": lag,
                "max_lag": max(lag) if lag else 0,
                "selected": self._log_selected[l],
                "combine_rounds": self._combine_rounds[l],
                "occupancy": (int(tails[l]) - int(heads[l]))
                / self.spec.capacity,
            })
        total_sel = sum(self._log_selected)
        return {
            "nlogs": self.nlogs,
            "capacity": self.spec.capacity,
            "logs": logs,
            # routing imbalance: max over mean selections (1.0 = even)
            "selection_imbalance": (
                max(self._log_selected) * self.nlogs / total_sel
                if total_sel else 0.0
            ),
            "replicas": {
                "n": self.n_replicas,
                "threads": list(self._threads_per_replica),
            },
            "exec": {
                "window": self.exec_window,
                "rounds": self._exec_rounds,
                "idle_rounds": self._idle_rounds,
                "fused_rounds": self._fused_rounds,
                "fused_tier": self._fused_mode,
            },
            "mesh": (
                None if self.mesh is None else {
                    "devices": self._mesh_shards,
                    "tier": "gspmd",
                    "shape": dict(self.mesh.shape),
                }
            ),
            "metrics": get_registry().snapshot(),
        }

    # ------------------------------------------------------------ internals

    @_locked
    def _exec_round(self, log_idx: int) -> None:
        fault_hook("replay", -1, self)
        # one fused cursor readback per round (see the
        # NodeReplicated._exec_round note on D2H round trips)
        lt_before, tail = host_cursors(self.ml.ltails[log_idx],
                                       self.ml.tail[log_idx])
        # idle short-circuit (the NodeReplicated._exec_round twin): all
        # replicas at this log's tail → nothing to replay, skip the
        # device round; every caller loops on a cursor condition already
        # satisfied, so skipping cannot livelock
        if int(lt_before.min()) >= tail and int(lt_before.max()) <= tail:
            self._idle_rounds += 1
            self._m_idle.inc()
            return
        self._exec_rounds += 1
        self._m_rounds.inc()
        self.ml, self.states, resps = self._exec_jit(
            self.ml, self.states, log_idx=log_idx, window=self.exec_window
        )
        lt_after = np.asarray(self.ml.ltails)[log_idx]
        resps_np = np.asarray(resps)
        if self.mesh is not None:
            self._m_mesh_round.inc()
            self._m_mesh_sync_bytes.inc(resps_np.nbytes
                                        + lt_before.nbytes + 8
                                        + lt_after.nbytes)
        for r in range(self.n_replicas):
            q = self._inflight.get((r, log_idx))
            if not q:
                continue
            while q and q[0][0] < int(lt_after[r]):
                pos, tid = q.popleft()
                self._resps[(r, tid)].append(
                    int(resps_np[r, pos - int(lt_before[r])])
                )

    def _watchdog(self, rounds: int, log_idx: int, where: str) -> int:
        rounds += 1
        # Re-warn every WARN_ROUNDS forever, like the reference's per-log
        # GC starvation callback (`cnr/src/log.rs:505-515`).
        if rounds % WARN_ROUNDS == 0:
            self._m_stalls.inc()
            lt = np.asarray(self.ml.ltails)[log_idx]
            dormant = int(np.argmin(lt))
            tail = int(np.asarray(self.ml.tail)[log_idx])
            logger.warning(
                "cnr replay stalled in %s on log %d after %d rounds; "
                "dormant replica=%d (ltail=%d, tail=%d)",
                where, log_idx, rounds, dormant, int(lt[dormant]), tail,
            )
            get_tracer().emit(
                "watchdog", where=where, log=log_idx, rounds=rounds,
                dormant=dormant, ltail=int(lt[dormant]), tail=tail,
            )
            if self.gc_callback is not None:
                self.gc_callback(log_idx, dormant)
        return rounds
