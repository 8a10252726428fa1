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

A combiner round is one hold of the lock, as `NodeReplicated`'s is: its
ops are routed through the `LogMapper`, every touched log's sub-batch is
appended by one program (`jit_cnr_append`), and one program replays every
log into every replica (`jit_cnr_exec`, `core/multilog.multilog_exec_window`);
the logs are data to both, so no program is compiled per log. Reads are
`NodeReplicated`'s combined reads (`core/replica._ReadCombining`), each
synced on its mapped log only.

The jit-hot batch path is `core/multilog.make_multilog_step`.
"""

from __future__ import annotations

import dataclasses
import logging
import time
from collections import deque
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from node_replication_tpu.analysis.locks import make_rlock
from node_replication_tpu.core.log import LogSpec, LogState, WARN_ROUNDS
from node_replication_tpu.core.multilog import (
    LogMapper,
    MultiLogSpec,
    multilog_append,
    multilog_exec_window,
    multilog_init,
)
from node_replication_tpu.core.replica import (
    BATCH_TID,
    MAX_THREADS_PER_REPLICA,
    LogTooSmallError,
    ReplicaToken,
    _batch_fields,
    _FusedTier,
    _locked,
    _locked_phase,
    _named,
    _pending_fields,
    _read_fields,
    _ReadCombining,
    _ReadSlot,
    replicate_state,
    states_equal,
)
from node_replication_tpu.fault.inject import fault_hook
from node_replication_tpu.obs.metrics import COUNT_BUCKETS, get_registry
from node_replication_tpu.obs.recorder import get_tracer, span
from node_replication_tpu.ops.encoding import NOOP, Dispatch, apply_read

logger = logging.getLogger("node_replication_tpu")


class _PendingCnrRound:
    """One CNR combiner round between `_begin_round` and `_finish_round`
    (the `core/replica._PendingRound` twin): the replica, the number of
    ops, each touched log's target position (its pre-append tail plus its
    sub-batch), and, on the fused tier, the per-log launches whose
    responses await readback. After begin every op is APPENDED, on every
    touched log. `out` collects a batch's responses in submission order;
    `pos0` is the last touched log's pre-append tail (the serve layer's
    trace join key, `round_pos`)."""

    __slots__ = ("rid", "n", "targets", "pos0", "fused", "done", "out")

    def __init__(self, rid: int, n: int):
        self.rid = rid
        self.n = n
        #: [(log, target position)]
        self.targets: list[tuple[int, int]] = []
        self.pos0 = 0
        #: [(log, op indices, device responses)] of deferred fused launches
        self.fused: list | None = None
        self.done = False
        self.out: list | None = None


class MultiLogReplicated(_ReadCombining, _FusedTier):
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

        # Combiner lock (`replica._locked`): one combiner round at a time
        # across all logs; reentrant so watchdog gc_callbacks can
        # re-enter sync_log on the same thread.
        self._lock = make_rlock("MultiLogReplicated._lock")
        self._init_read_combining()
        self._threads_per_replica = [0] * n_replicas
        # staged ops: (rid, tid) -> deque[(log, opcode, args)]
        self._pending: dict[tuple[int, int], deque] = {}
        # appended-but-unanswered: (rid, log) -> deque[(pos, tid, index)],
        # `index` placing a batch op's response in its batch's `out`
        self._inflight: dict[tuple[int, int], deque] = {}
        # delivered responses per thread, in enqueue order per log
        self._resps: dict[tuple[int, int], deque] = {}
        # the responses of each replica's batch in flight, by submission
        # index (`execute_mut_batch`, `begin_mut_batch`)
        self._batch_out: dict[int, list] = {}
        # split-round registry (`begin_mut_batch`): at most ONE
        # begun-but-unfinished round per replica (the NodeReplicated
        # invariant)
        self._pending_batch: dict[int, _PendingCnrRound] = {}
        # per-log observability: LogMapper routing counts, combiner
        # rounds that appended to each log, replay rounds (+ idle skips)
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
        self._m_logs = reg.histogram("cnr.round.logs",
                                     buckets=COUNT_BUCKETS)
        self._m_stalls = reg.counter("cnr.watchdog.stalls")

        # ---- fused pallas per-log combiner tier (the NodeReplicated
        # twin, `core/replica._FusedTier`): a round whose touched logs
        # are all lock-step eligible appends+replays+answers each log's
        # sub-batch as ONE kernel launch. Only engine='pallas' runs it;
        # 'auto' and 'scan' never consult it. CNR has no fencing, so
        # the fenced kernel variant never builds here.
        if engine not in ("auto", "scan", "pallas"):
            raise ValueError(f"unknown engine {engine!r}")
        self._fused_cnr_cache: dict = {}
        self._init_fused_tier(engine, dispatch, mesh, reg, "cnr")
        if self.mesh is not None:
            self._m_mesh_round = reg.counter("cnr.exec.mesh.gspmd")
            self._m_mesh_sync_bytes = reg.counter("mesh.sync_bytes")

        # The round's two programs. Replay: the model's
        # `window_apply_stacked` or `window_apply` over every log's window
        # (the hashmap scatters each key's last write), or, for
        # engine='scan' and models with neither, the scan of
        # `apply_write` (`multilog_exec_window`).
        d = dispatch
        if engine == "scan" or (d.window_apply is None
                                and d.window_apply_stacked is None):
            d = dataclasses.replace(d, window_apply=None,
                                    window_apply_stacked=None)
            self.engine = "scan"
        else:
            self.engine = "combined"
        spec = self.spec
        self._exec_jit = jax.jit(
            _named(partial(multilog_exec_window, spec, d), "cnr_exec"),
            static_argnames=("window",), donate_argnums=(0, 1),
        )
        self._append_jit = jax.jit(
            _named(partial(multilog_append, spec), "cnr_append"),
            donate_argnums=(0,),
        )

        # The read program: `read_stacked`, one slot of the stacked
        # replicas, where the model has it (combined by `execute`);
        # else `read_one`, which slices replica `rid` out whole.
        def read_one(states, rid, opcode, args):
            state = jax.tree.map(lambda a: a[rid], states)
            return apply_read(dispatch, state, opcode, args)

        if dispatch.read_stacked is None:
            self._read_jit = jax.jit(read_one)
        else:
            self._read_jit = self._read_stacked_jit = jax.jit(
                _named(partial(dispatch.read_stacked), "read_stacked"))

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
        # thread's newest append); pop it from the tail so earlier
        # enqueued-but-unread responses stay for `responses()`.
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
        """Combine every staged op, every log's, as one round per
        replica (all replicas by default)."""
        for r in range(self.n_replicas) if rid is None else [rid]:
            self._combine(r, None)

    @_locked
    def responses(self, token: ReplicaToken) -> list:
        """Drain delivered responses for this thread (enqueue order per
        log; delivery order across logs follows combine order)."""
        q = self._resps[(token.rid, token.tid)]
        out = list(q)
        q.clear()
        return out

    @_locked_phase("read", _read_fields)
    def _execute_one(self, op: tuple, token: ReplicaToken):
        """A read alone, under its own hold: sync only the mapped log,
        then dispatch locally (`cnr/src/replica.rs:599-617`). Every read
        of a model without `read_stacked` takes it, and any read by a
        caller that holds the lock already (`_ReadCombining.execute`)."""
        h = self._map(op)
        fault_hook("read-sync", token.rid, self)
        self._sync_read_logs([(h, token.rid)])
        return self._dispatch_read(token.rid, op)

    def _read_refused(self, rid: int) -> BaseException | None:
        return None  # CNR fences no replica

    def _sync_reads(self, live: list[_ReadSlot]) -> None:
        """The read combiner's sync: each read's mapped log, on its
        replica, to that log's completed tail."""
        self._sync_read_logs([(self._map(s.op), s.rid) for s in live])

    @_locked
    def _sync_read_logs(self, pairs: list[tuple[int, int]]) -> None:
        """Replay until replica `r` has applied log `h` up to the
        completed tail `h` had when the reads were taken, for every
        `(h, r)`: the cursors are fetched once, and each replay round
        replays every log."""
        lt, ctail = jax.device_get((self.ml.ltails, self.ml.ctail))
        rounds = 0
        while True:
            lagging = [h for h, r in pairs if lt[h, r] < ctail[h]]
            if not lagging:
                return
            lt = self._exec_round()
            rounds = self._watchdog(rounds, lagging[0], "read-sync")

    @_locked
    def combine(self, rid: int, log_idx: int) -> None:
        """Drain replica `rid`'s staged ops for `log_idx` (thread order),
        append them to that log, and replay until `rid` has applied its
        own ops — one log's combiner pass (`cnr/src/replica.rs:673-720`)."""
        self._combine(rid, log_idx)

    @_locked
    def _combine(self, rid: int, log_idx: int | None) -> None:
        """`combine` of one log, or of every log (None) in one round."""
        ops: list[tuple] = []  # (opcode, *args)
        tids: list[int] = []
        logs: list[int] = []
        for tid in range(self._threads_per_replica[rid]):
            q = self._pending[(rid, tid)]
            keep = deque()
            while q:
                h, opcode, args = q.popleft()
                if log_idx is None or h == log_idx:
                    ops.append((opcode, *args))
                    tids.append(tid)
                    logs.append(h)
                else:
                    keep.append((h, opcode, args))
            q.extend(keep)
        if not ops:
            self._exec_round()  # combine with nothing staged still helps
            return
        self._finish_round(self._begin_round(ops, rid, tids, logs))

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
    def _try_fused_round(self, groups: dict[int, list[int]], ops, rid: int,
                         tids: list[int], pending: _PendingCnrRound,
                         defer: bool) -> bool:
        """Route the round through the fused engine, one launch per
        touched log, when every touched log is lock-step eligible (the
        NodeReplicated `_try_fused_round` twin, minus fencing/WAL, which
        CNR does not carry); False runs the append+replay programs. With
        `defer` (the split-round path) the launches are issued here and
        their responses read back by `_finish_round`."""
        eng = self._fused_engine()
        if eng is None:
            return False
        pads = {h: 1 << (len(idxs) - 1).bit_length()
                for h, idxs in groups.items()}
        lts, tails = jax.device_get((self.ml.ltails, self.ml.tail))
        if (not all(eng.supports(p) for p in pads.values())
                or any(self._inflight.get((r, h))
                       for h in groups for r in range(self.n_replicas))
                or not all((lts[h] == tails[h]).all() for h in groups)):
            self._m_fused_fallback.inc()
            return False
        launched = []
        for h in sorted(groups):
            idxs = groups[h]
            opcodes, args = self._encode([ops[i] for i in idxs], pads[h])
            t0 = time.perf_counter()
            fn = self._fused_cnr_round(eng, pads[h])
            with span("fused-round", log=h, rid=rid, n=len(idxs),
                      pos0=int(tails[h]), window=pads[h]) as sp:
                self.ml, self.states, resps = fn(
                    self.ml, self.states, jnp.int32(h), jnp.asarray(opcodes),
                    jnp.asarray(args), len(idxs),
                )
                if not defer:
                    resps = np.asarray(resps)
                    sp.fence(self.ml, self.states)
            # the CNR path embeds the raw round_fn in its own program, so
            # the engine's round() wrapper never runs — report through the
            # same instrumentation hook (tier counter + kernel.* metrics +
            # kernel-launch event; one contract, never two)
            eng.note_round(pads[h], len(idxs), time.perf_counter() - t0)
            self._fused_rounds += 1
            self._m_engine_fused.inc()
            launched.append((h, idxs, resps))
        pending.fused = launched
        if not defer:
            self._deliver_fused(pending, tids)
        return True

    def _deliver_fused(self, pending: _PendingCnrRound,
                       tids: list[int]) -> None:
        rid = pending.rid
        for h, idxs, resps in pending.fused:
            resps = np.asarray(resps)
            for j, i in enumerate(idxs):
                self._deliver(rid, tids[i], i, int(resps[rid, j]))
        pending.fused = None
        pending.done = True
        self._note_tier(rid, "pallas_fused", pending.pos0)

    def _encode(self, ops: list[tuple], pad: int):
        opcodes = np.full((pad,), NOOP, np.int32)
        args = np.zeros((pad, self.spec.arg_width), np.int32)
        for j, op in enumerate(ops):
            opcodes[j] = op[0]
            args[j, : len(op) - 1] = op[1:]
        return opcodes, args

    def _note_tier(self, rid: int, tier: str, pos0: int) -> None:
        self.last_round_tier = tier
        self._tier_by_rid[rid] = tier
        self._pos_by_rid[rid] = pos0

    def _deliver(self, rid: int, tid: int, index: int, resp: int) -> None:
        if tid == BATCH_TID:
            self._batch_out[rid][index] = resp
        else:
            self._resps[(rid, tid)].append(resp)

    @_locked
    def _begin_round(self, ops: list[tuple], rid: int, tids: list[int],
                     logs: list[int] | None = None,
                     defer: bool = False) -> _PendingCnrRound:
        """First half of the combiner round (the NodeReplicated
        `_begin_round` twin): route every op through the `LogMapper`,
        wait for ring space on every touched log (helping GC), and append
        every touched log's sub-batch with ONE program (`jit_cnr_append`)
        — or, on the fused tier, launch each log's round. Records each
        op's in-flight response destination; `tids[i]` is op i's thread
        (BATCH_TID: the replica's batch, by index), `logs[i]` its log
        where staging mapped it already. `defer` leaves the
        fused launches' readback for `_finish_round`; the chain's replay
        is always `_finish_round`'s. The lock is reentrant: callers
        already hold it."""
        fault_hook("append", rid, self)
        n = len(ops)
        groups: dict[int, list[int]] = {}
        for i, op in enumerate(ops):
            h = self._map(op) if logs is None else logs[i]
            groups.setdefault(h, []).append(i)
        max_batch = self.spec.capacity - self.spec.gc_slack
        for h, idxs in groups.items():
            if len(idxs) > max_batch:
                raise LogTooSmallError(
                    f"log {h}: sub-batch of {len(idxs)} exceeds "
                    f"appendable capacity {max_batch}"
                )
        counts = np.zeros((self.nlogs,), np.int64)
        for h, idxs in groups.items():
            counts[h] = len(idxs)
            self._combine_rounds[h] += 1
        self._m_combine.inc()
        self._m_batch.observe(n)
        self._m_logs.observe(len(groups))
        # ring space on every touched log, the cursors fetched once a try
        rounds = 0
        while True:
            tail, head = jax.device_get((self.ml.tail, self.ml.head))
            short = np.flatnonzero(max_batch - (tail - head) < counts)
            if not short.size:
                break
            self._exec_round()
            rounds = self._watchdog(rounds, int(short[0]), "append-gc")
        pending = _PendingCnrRound(rid, n)
        pending.targets = [(h, int(tail[h] + counts[h])) for h in sorted(groups)]
        pending.pos0 = int(tail[max(groups)])
        if self._try_fused_round(groups, ops, rid, tids, pending, defer):
            return pending
        pad = 1 << (int(counts.max()) - 1).bit_length()
        opcodes = np.full((self.nlogs, pad), NOOP, np.int32)
        args = np.zeros((self.nlogs, pad, self.spec.arg_width), np.int32)
        for h, idxs in groups.items():
            opcodes[h], args[h] = self._encode([ops[i] for i in idxs], pad)
        with span("append", rid=rid, n=n, logs=len(groups)) as sp:
            self.ml = self._append_jit(self.ml, opcodes, args, counts)
            sp.fence(self.ml)
        for h, idxs in groups.items():
            infl = self._inflight.setdefault((rid, h), deque())
            for j, i in enumerate(idxs):
                infl.append((int(tail[h]) + j, tids[i], i))
        return pending

    @_locked
    def _finish_round(self, pending: _PendingCnrRound) -> None:
        """Second half of the combiner round: replay every log until
        replica `rid` has applied its own ops on each touched log (one
        replay program a pass, `jit_cnr_exec`), or read back and deliver
        the fused launches' responses."""
        if pending.done:
            return
        rid = pending.rid
        if pending.fused is not None:
            self._deliver_fused(pending, [BATCH_TID] * pending.n)
            return
        pending.done = True
        rounds = 0
        with span("combine-replay", rid=rid, logs=len(pending.targets)) as sp:
            while True:
                lt = self._exec_round()
                behind = [h for h, t in pending.targets if lt[h, rid] < t]
                if not behind:
                    break
                rounds = self._watchdog(rounds, behind[0], "combine-replay")
            sp.fence(self.ml, self.states)
        self._note_tier(rid, self.engine, pending.pos0)

    @_locked
    def _drop_batch_inflight(self, rid: int) -> None:
        """Failed-batch hygiene (the NodeReplicated twin): drop every
        pending BATCH_TID delivery for this replica on every log and
        the batch's response list, so the next batch cannot inherit
        stale replies. The appended ops stay in the logs and replay."""
        for key in [(rid, h) for h in range(self.nlogs)
                    if (rid, h) in self._inflight]:
            self._inflight[key] = deque(
                e for e in self._inflight[key] if e[1] != BATCH_TID
            )
        self._batch_out.pop(rid, None)

    @_locked_phase("round", _batch_fields)
    def begin_mut_batch(self, ops: list[tuple],
                        rid: int = 0) -> _PendingCnrRound:
        """Split-round batch entry, first half (the
        `NodeReplicated.begin_mut_batch` twin): route, wait for ring
        space and append every touched log's sub-batch, deferring the
        replay to `finish_mut_batch`. At most ONE begun-but-unfinished
        batch per replica (`RuntimeError` otherwise). A raise out of
        `begin` before the append leaves no op appended; after it, every
        op is appended, on every log."""
        if not 0 <= rid < self.n_replicas:
            raise ValueError(f"replica {rid} out of range")
        if self._pending_batch.get(rid) is not None:
            raise RuntimeError(
                f"replica {rid} already has a batch in flight; "
                f"finish_mut_batch it before beginning another "
                f"(at most one split round per replica)"
            )
        n = len(ops)
        out: list = [None] * n
        if n == 0:
            pending = _PendingCnrRound(rid, 0)
            pending.done = True
        else:
            self._batch_out[rid] = out
            try:
                pending = self._begin_round(list(ops), rid, [BATCH_TID] * n,
                                            defer=True)
            except BaseException:
                self._drop_batch_inflight(rid)
                raise
        pending.out = out
        self._pending_batch[rid] = pending
        return pending

    @_locked_phase("round", _pending_fields)
    def finish_mut_batch(self, pending: _PendingCnrRound) -> list:
        """Split-round batch entry, second half: replay to every touched
        log's target (or read back the fused launches), collect the
        responses in submission order, release the replica's in-flight
        slot."""
        rid = pending.rid
        if self._pending_batch.get(rid) is not pending:
            raise RuntimeError(
                f"pending batch for replica {rid} is not this "
                f"replica's in-flight batch (already finished?)"
            )
        try:
            self._finish_round(pending)
            assert None not in pending.out, pending.out
            return pending.out
        except BaseException:
            self._drop_batch_inflight(rid)
            raise
        finally:
            self._pending_batch.pop(rid, None)
            if self._batch_out.get(rid) is pending.out:
                del self._batch_out[rid]

    @_locked
    def abort_mut_batch(self, pending: _PendingCnrRound) -> None:
        """Abandon a begun-but-unfinished split batch (the
        `NodeReplicated.abort_mut_batch` twin): every appended op WILL
        replay; only response delivery drops. Idempotent."""
        rid = pending.rid
        if self._pending_batch.get(rid) is not pending:
            return
        self._pending_batch.pop(rid, None)
        pending.done = True
        pending.fused = None
        self._drop_batch_inflight(rid)

    @_locked_phase("round", _batch_fields)
    def execute_mut_batch(self, ops: list[tuple],
                          rid: int = 0) -> list:
        """Execute a caller-assembled batch as ONE combiner round and
        return responses in submission order — the CNR twin of
        `NodeReplicated.execute_mut_batch` (the serve frontend's serial
        entry point), and `begin_mut_batch` + `finish_mut_batch`
        back-to-back under one lock hold.

        Each op routes through the `LogMapper` exactly as `execute_mut`
        would (`cnr/src/replica.rs:435`); one program appends every
        touched log's sub-batch, and one program a pass replays every
        log until this replica has applied its ops. Interleaving with
        per-thread `execute_mut` traffic on the same replica stays
        ordered: the batch's responses go to its own list, by index."""
        return self.finish_mut_batch(self.begin_mut_batch(ops, rid))

    @_locked
    def sync(self, rid: int | None = None) -> None:
        """Catch up on every log (`cnr/src/replica.rs:579-597`)."""
        for l in range(self.nlogs):
            self.sync_log(rid, l)

    @_locked
    def sync_log(self, rid: int | None, log_idx: int) -> None:
        """Targeted single-log sync (`sync_log`,
        `cnr/src/replica.rs:579-597`): replay until log `log_idx` is
        applied to its tail on replica `rid` (every replica for None);
        each replay round replays every log. The harness wires the GC
        callback to this, answering starvation reports
        (`benches/mkbench.rs:763-772`)."""
        rounds = 0
        lt, tail = jax.device_get((self.ml.ltails, self.ml.tail))
        while True:
            row = lt[log_idx] if rid is None else lt[log_idx, rid]
            if (row >= tail[log_idx]).all():
                return
            lt = self._exec_round()
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
    def _exec_round(self) -> np.ndarray:
        """One replay pass of every log into every replica (one program,
        `jit_cnr_exec`), plus response delivery. Returns the host copy of
        the replicas' cursors after it, `ltails[log, replica]`."""
        fault_hook("replay", -1, self)
        # one cursor fetch (ltails + tail) per round
        lt_before, tail = jax.device_get((self.ml.ltails, self.ml.tail))
        # idle short-circuit (the NodeReplicated._exec_round twin): every
        # replica at every log's tail → nothing to replay, skip the
        # device round; every caller loops on a cursor condition already
        # satisfied, so skipping cannot livelock
        if (lt_before == tail[:, None]).all():
            self._idle_rounds += 1
            self._m_idle.inc()
            return lt_before
        self._exec_rounds += 1
        self._m_rounds.inc()
        self.ml, self.states, resps = self._exec_jit(
            self.ml, self.states, window=self.exec_window
        )
        lt_after, resps_np = jax.device_get((self.ml.ltails, resps))
        if self.mesh is not None:
            self._m_mesh_round.inc()
            self._m_mesh_sync_bytes.inc(resps_np.nbytes + lt_before.nbytes
                                        + tail.nbytes + lt_after.nbytes)
        for (r, h), q in self._inflight.items():
            done = int(lt_after[h, r])
            while q and q[0][0] < done:
                pos, tid, i = q.popleft()
                self._deliver(r, tid, i,
                              int(resps_np[h, r, pos - int(lt_before[h, r])]))
        return lt_after

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
