"""Pallas TPU kernels for the hashmap hot loop: replay, and the FUSED
append+replay combiner round.

Two contracts live here:

1. **Replay-only** (`make_hashmap_replay` / `make_pallas_step`): the
   original hand-tiled window replay — the caller appends to the ring
   separately and hands the kernel the window.
2. **Fused round** (`FusedHashmapEngine` / `make_fused_hashmap_calls`):
   a whole combiner round is ONE `pallas_call` — the log-window append
   (two pre-blended DMA spans over the un-blocked, aliased ring planes,
   `ops/pallas_ring.py`), the per-entry replay into the transposed
   `[K, R]` state tiles, the response gather, and the fenced-lane mask
   (quarantined replicas skip state writeback and report zeroed
   responses, `fault/health.py`) all happen inside the kernel. The
   engine is the `log.engine.pallas_fused` tier `NodeReplicated` /
   `MultiLogReplicated` route `_append_and_replay` rounds through under
   `engine="pallas"` (`core/replica.py`), collapsing the
   host-sequenced encode → `log_append` → sort/merge → replay chain —
   and its per-round host syncs — into one launch per serve batch.
   Interpret-mode bit-identity vs the scan engine (ring wrap, fenced,
   batch, CNR sub-batch paths): tests/test_pallas_fused.py.

The generic replay path (`core/log.log_exec_all`) is a vmapped `lax.scan`
whose every iteration scatters one element per replica into HBM-resident
state. These kernels are the hand-tiled alternative for the flagship
hashmap model (SURVEY.md §7: "Pallas kernels for the append/reserve and
scan-replay inner loops if XLA fusion falls short"):

- state is laid out TRANSPOSED, `[K, R]`: keys on the sublane axis,
  replicas on the 128-wide lane axis. Replay touches one dynamic KEY per
  entry but all replicas at once — on TPU the dynamically-indexed axis
  must be the sublane one (Mosaic has no dynamic lane indexing), and the
  replica axis is naturally lane-parallel;
- the replica axis is tiled into VMEM blocks (`[Kp, tile_r]`, ~16 MB/core
  budget); each entry is a dynamic single-ROW read-modify-write IN VMEM
  (`ref[pl.ds(k, 1), :]`), so the inner loop never round-trips HBM;
- per-tile state is written back exactly once.

All replicas replay the same window at the same offsets (the lock-step
precondition of the fused step), so one kernel grid covers the fleet.

Hardware-proven (round 3, TPU v5e, fenced D2H measurement): at
R=4096/K=1024 the Mosaic lowering compiles and runs, and `bench.py
--pallas` measures 1.22G dispatches/s vs 13.0M for the generic vmapped
scan at the identical config — a ~94x win over per-entry XLA replay, the
comparison this kernel exists for (`nr/src/log.rs:473-524` is the
reference's hot loop). The *combined* window replay
(`Dispatch.window_apply`, `models/hashmap.py`) measures 1.75G at the same
config by replacing sequential replay with a parallel reduction — an
algorithmic change, available only to models with last-writer-wins write
semantics; this kernel remains the fast path for per-entry sequential
replay (and the template for models that need it). Non-interpret smoke:
`NR_TPU_SMOKE=1 pytest tests/test_pallas.py::TestHardwareSmoke`.

Opcodes follow `models/hashmap.py`: PUT=1 (k, v → 0), REMOVE=2 (k → was
present). `present` is int32 here (lane-friendly); `make_pallas_step`
exposes the same step contract as `core/step.make_step` over the
transposed state (`pallas_hashmap_state`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from node_replication_tpu.core.log import LogSpec, log_append
from node_replication_tpu.ops.pallas_ring import FusedEngineHost
from node_replication_tpu.utils.compat import x64_disabled


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _replay_kernel(opc_ref, key_ref, val_ref, val_in, pres_in, val_out,
                   pres_out, resp_ref, *, n_keys: int, window: int):
    # load the tile's state into the output VMEM blocks once
    val_out[:] = val_in[:]
    pres_out[:] = pres_in[:]

    def body(i, carry):
        # opcode/key/value live in SMEM: Mosaic requires dynamic-slice
        # indices to come from scalar memory, not VMEM loads
        opcode = opc_ref[i]
        # floored mod (matching the generic model's non-negative `%`):
        # lax.rem truncates toward zero, so adjust negatives or a negative
        # key would index a negative VMEM row
        k = jax.lax.rem(key_ref[i], jnp.int32(n_keys))
        k = jnp.where(k < 0, k + jnp.int32(n_keys), k)
        v = val_ref[i]
        is_put = opcode == 1
        is_rem = opcode == 2
        row_v = val_out[pl.ds(k, 1), :]
        row_p = pres_out[pl.ds(k, 1), :]
        val_out[pl.ds(k, 1), :] = jnp.where(
            is_put, v, jnp.where(is_rem, 0, row_v)
        )
        pres_out[pl.ds(k, 1), :] = jnp.where(
            is_put, 1, jnp.where(is_rem, 0, row_p)
        )
        resp_ref[pl.ds(i, 1), :] = jnp.where(is_rem, row_p, 0)
        return carry

    # int32 loop bounds: under jax_enable_x64 a Python-int fori_loop index
    # becomes int64, which Mosaic cannot lower
    jax.lax.fori_loop(jnp.int32(0), jnp.int32(window), body, jnp.int32(0))


def make_hashmap_replay(
    n_keys: int,
    n_replicas: int,
    window: int,
    tile_r: int = 512,
    interpret: bool = False,
):
    """Build `replay(opcodes[W], keys[W], vals[W], values[Kp, R],
    present[Kp, R]) -> (values, present, resps[W, R])` with Kp = n_keys
    padded to the 8-sublane boundary. Window entries replay in order into
    every replica.
    """
    from jax.experimental.pallas import tpu as pltpu
    kp = _round_up(n_keys, 8)
    # lane (last) dim of a block must be a multiple of 128 or the full
    # array dim; sublane dims of the state blocks are full (Kp, W). The
    # four state blocks (values/present × in/out) plus the resp block must
    # fit the ~16 MB VMEM: shrink the replica tile until they do.
    budget = 14 << 20

    def block_bytes(t: int) -> int:
        # x2: Mosaic double-buffers every DMA'd block for grid pipelining
        return 2 * 4 * (4 * kp * t + window * t)

    candidates = [t for t in (1024, 512, 256, 128)
                  if n_replicas % t == 0] or [n_replicas]
    for t in candidates:
        if (n_replicas % tile_r == 0
                and (tile_r % 128 == 0 or tile_r == n_replicas)
                and block_bytes(tile_r) <= budget):
            break  # caller's tile is legal and fits
        tile_r = t
        if block_bytes(t) <= budget:
            break
    if block_bytes(tile_r) > budget and not interpret:
        raise ValueError(
            f"hashmap pallas replay needs {block_bytes(tile_r)} bytes of "
            f"VMEM at the smallest legal tile ({tile_r} replicas) for "
            f"n_keys={n_keys}, window={window}; use the generic scan path "
            f"(core/step.make_step) for this config"
        )
    grid = (n_replicas // tile_r,)
    kernel = functools.partial(
        _replay_kernel, n_keys=n_keys, window=window
    )
    state_spec = pl.BlockSpec((kp, tile_r), lambda i: (0, i))
    call = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            state_spec,
            state_spec,
        ],
        out_specs=[
            state_spec,
            state_spec,
            pl.BlockSpec((window, tile_r), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((kp, n_replicas), jnp.int32),
            jax.ShapeDtypeStruct((kp, n_replicas), jnp.int32),
            jax.ShapeDtypeStruct((window, n_replicas), jnp.int32),
        ],
        interpret=interpret,
    )

    def replay(opcodes, keys, vals, values, present):
        # trace the kernel with x64 off: the package enables jax_enable_x64
        # for int64 log cursors, but x64-canonicalized index-map constants
        # (i64) send the Mosaic lowering into an unsupported-convert loop.
        # Every kernel operand is int32, so the narrowing context is inert.
        with x64_disabled():
            return call(opcodes, keys, vals, values, present)

    return replay


def make_pallas_step(
    n_keys: int,
    spec: LogSpec,
    writes_per_replica: int,
    reads_per_replica: int,
    tile_r: int = 512,
    interpret: bool = False,
    jit: bool = True,
    donate: bool = True,
):
    """Pallas twin of `core/step.make_step` for the hashmap model.

    Same contract: append the fleet's write batch to the ring, replay it
    into every replica (via the kernel), answer reads locally. State is
    `{"values": int32[Kp, R], "present": int32[Kp, R]}` (transposed) —
    create it with `pallas_hashmap_state(n_keys, R)`.
    """
    R = spec.n_replicas
    Bw = int(writes_per_replica)
    span = R * Bw
    # replay in window chunks: a smaller kernel window frees VMEM for the
    # state blocks (the chunks apply strictly in order, so semantics hold)
    chunk = span
    while chunk > 1024 and chunk % 2 == 0:
        chunk //= 2
    replay = make_hashmap_replay(
        n_keys, R, chunk, tile_r=tile_r, interpret=interpret
    )

    def step(log, states, wr_opcodes, wr_args, rd_opcodes, rd_args):
        opc = wr_opcodes.reshape(span)
        args = wr_args.reshape(span, spec.arg_width)
        log = log_append(spec, log, opc, args, span)
        values, present = states["values"], states["present"]
        resp_chunks = []
        for c0 in range(0, span, chunk):
            values, present, r = replay(
                opc[c0 : c0 + chunk],
                args[c0 : c0 + chunk, 0],
                args[c0 : c0 + chunk, 1],
                values,
                present,
            )
            resp_chunks.append(r)
        resps = (
            jnp.concatenate(resp_chunks, axis=0)
            if len(resp_chunks) > 1
            else resp_chunks[0]
        )
        states = {"values": values, "present": present}
        # cursors advance in lock-step (every replica replayed the span)
        log = log._replace(
            ltails=log.ltails + span,
            ctail=log.ctail + span,
            head=log.head + span,
        )
        # resps is [W, R]; replica r's own writes are entries
        # [r*Bw, (r+1)*Bw)
        own = jnp.arange(R, dtype=jnp.int32)[:, None] * Bw + jnp.arange(
            Bw, dtype=jnp.int32
        )[None, :]  # [R, Bw]
        wr_resps = resps[own, jnp.arange(R, dtype=jnp.int32)[:, None]]
        # reads: gather values[k, r] per (replica, read slot)
        k = rd_args[..., 0] % n_keys  # [R, Br]
        r_idx = jnp.arange(R, dtype=jnp.int32)[:, None]
        vals = values[k, r_idx]
        pres = present[k, r_idx]
        rd_resps = jnp.where(
            (rd_opcodes == 1) & (pres > 0), vals, jnp.int32(-1)
        )
        rd_resps = jnp.where(rd_opcodes == 0, 0, rd_resps)
        return log, states, wr_resps, rd_resps

    if jit:
        step = jax.jit(step, donate_argnums=(0, 1) if donate else ())
    return step


def pallas_hashmap_state(n_keys: int, n_replicas: int):
    kp = _round_up(n_keys, 8)
    return {
        "values": jnp.zeros((kp, n_replicas), jnp.int32),
        "present": jnp.zeros((kp, n_replicas), jnp.int32),
    }


# ---------------------------------------------------------------------------
# Fused append+replay engine (one pallas_call per combiner round)
# ---------------------------------------------------------------------------


def _fused_hashmap_kernel(meta_ref, opc_ref, key_ref, val_ref, *refs,
                          n_planes: int, n_keys: int, window: int,
                          win_rows: int, fenced: bool):
    """One combiner round: ring-window append (DMA, grid step 0) +
    in-order replay of the SMEM batch into the `[Kp, tile_r]` state
    blocks + response gather. `meta = [s_lo, count]`; batch slots at or
    past `count` are NOOP by the `encode_ops` contract, so the replay
    loop needs no count gate. `refs` opens with the `n_planes` lo
    spans, hi spans and aliased ring inputs (`split_ring_refs`). With
    `fenced`, an extra `[1, tile_r]` int32 plane marks quarantined
    lanes: they replay in VMEM like everyone (keeping the loop
    branch-free) but their writeback is restored from the input at the
    end — state and responses of a fenced replica must not move (the
    caller zeroes their resp rows)."""
    from node_replication_tpu.ops.pallas_ring import (
        ring_append_dma,
        split_ring_refs,
    )

    lo, hi, _ring_in, rest = split_ring_refs(refs, n_planes)
    val_in, pres_in, *rest = rest
    fen_in = rest.pop(0) if fenced else None
    ring_out = rest[:n_planes]
    val_out, pres_out, resp_ref, sem = rest[n_planes:]
    # the ring content only flows through the aliasing: the replay
    # reads the batch from SMEM (append happens-before replay by the
    # lock-step data dependence, core/log.py)
    with x64_disabled():
        @pl.when(pl.program_id(0) == 0)
        def _append():
            ring_append_dma(sem, meta_ref[0], win_rows, lo, hi, ring_out)

        val_out[:] = val_in[:]
        pres_out[:] = pres_in[:]

        def body(i, carry):
            opcode = opc_ref[i]
            k = jax.lax.rem(key_ref[i], jnp.int32(n_keys))
            k = jnp.where(k < 0, k + jnp.int32(n_keys), k)
            v = val_ref[i]
            is_put = opcode == 1
            is_rem = opcode == 2
            row_v = val_out[pl.ds(k, 1), :]
            row_p = pres_out[pl.ds(k, 1), :]
            val_out[pl.ds(k, 1), :] = jnp.where(
                is_put, v, jnp.where(is_rem, 0, row_v)
            )
            pres_out[pl.ds(k, 1), :] = jnp.where(
                is_put, 1, jnp.where(is_rem, 0, row_p)
            )
            resp_ref[pl.ds(i, 1), :] = jnp.where(is_rem, row_p, 0)
            return carry

        jax.lax.fori_loop(jnp.int32(0), jnp.int32(window), body,
                          jnp.int32(0))
        if fenced:
            fen = fen_in[0:1, :]
            val_out[:] = jnp.where(fen > 0, val_in[:], val_out[:])
            pres_out[:] = jnp.where(fen > 0, pres_in[:], pres_out[:])


def make_fused_hashmap_calls(
    n_keys: int,
    spec: LogSpec,
    window: int,
    tile_r: int = 512,
    interpret: bool = False,
    fenced: bool = False,
):
    """Build the per-chunk fused `pallas_call`s for one window size.

    Returns `(calls, chunk_r, tile_r)` where `calls[sub]` runs `sub`
    replica lanes (`sub // tile_r` grid steps, capped at MAX_GRID per
    call by `pallas_chunk` chunking — the r5 belt-and-braces rule).
    The ring planes thread through the chunk calls via aliasing, so a
    multi-chunk round re-issues the (idempotent) append DMA per chunk.
    """
    from jax.experimental.pallas import tpu as pltpu

    from node_replication_tpu.ops.pallas_chunk import (
        build_calls,
        chunk_size,
    )
    from node_replication_tpu.ops.pallas_ring import (
        fused_window_ok,
        ring_rows,
        window_rows,
    )

    if not fused_window_ok(spec.capacity, window):
        raise ValueError(
            f"fused hashmap round: window {window} does not fit the "
            f"ring-row append spans of capacity {spec.capacity}"
        )
    R = spec.n_replicas
    A = spec.arg_width
    kp = _round_up(n_keys, 8)
    win = window_rows(window)
    rows = ring_rows(spec.capacity)
    budget = 14 << 20
    app_bytes = 2 * 4 * (2 * win * 128 * (1 + A))

    def block_bytes(t: int) -> int:
        # states (values/present x in/out) + the resp block, all
        # double-buffered by the grid pipeline, plus the append planes
        return 2 * 4 * (4 * kp * t + window * t) + app_bytes

    candidates = [t for t in (1024, 512, 256, 128)
                  if R % t == 0] or [R]
    for t in candidates:
        if (R % tile_r == 0
                and (tile_r % 128 == 0 or tile_r == R)
                and block_bytes(tile_r) <= budget):
            break
        tile_r = t
        if block_bytes(t) <= budget:
            break
    if block_bytes(tile_r) > budget and not interpret:
        raise ValueError(
            f"fused hashmap round needs {block_bytes(tile_r)} bytes of "
            f"VMEM at the smallest legal tile ({tile_r} lanes) for "
            f"n_keys={n_keys}, window={window}; fall back to the "
            f"append+exec chain for this config"
        )
    n_planes = 1 + A
    kernel = functools.partial(
        _fused_hashmap_kernel, n_planes=n_planes, n_keys=n_keys,
        window=window, win_rows=win, fenced=fenced,
    )
    smem = lambda: pl.BlockSpec(memory_space=pltpu.SMEM)
    anyspec = lambda: pl.BlockSpec(memory_space=pltpu.ANY)
    vmem = lambda: pl.BlockSpec(memory_space=pltpu.VMEM)
    ring_shape = jax.ShapeDtypeStruct((rows, 128), jnp.int32)

    def build_call(sub_r: int):
        state_spec = pl.BlockSpec((kp, tile_r), lambda i: (0, i))
        in_specs = (
            [smem()] * 4                          # meta, opc/key/val
            + [vmem()] * (2 * n_planes)           # lo + hi spans
            + [anyspec()] * n_planes              # ring planes
            + [state_spec, state_spec]            # values/present
        )
        if fenced:
            in_specs.append(
                pl.BlockSpec((1, tile_r), lambda i: (0, i))
            )
        ring0 = 4 + 2 * n_planes
        return pl.pallas_call(
            kernel,
            grid=(sub_r // tile_r,),
            in_specs=in_specs,
            out_specs=[anyspec()] * n_planes + [
                state_spec, state_spec,
                pl.BlockSpec((window, tile_r), lambda i: (0, i)),
            ],
            out_shape=[ring_shape] * n_planes + [
                jax.ShapeDtypeStruct((kp, sub_r), jnp.int32),
                jax.ShapeDtypeStruct((kp, sub_r), jnp.int32),
                jax.ShapeDtypeStruct((window, sub_r), jnp.int32),
            ],
            # UN-BLOCKED ring planes aliased in->out: outside the grid
            # pipeline, so exempt from the r5 blocked-plane rule (see
            # ops/pallas_ring.py and nrlint aliased-pallas-planes)
            input_output_aliases={ring0 + i: i for i in range(n_planes)},
            scratch_shapes=[pltpu.SemaphoreType.DMA(())],
            interpret=interpret,
        )

    chunk_r = chunk_size(R, tile_r)
    return build_calls(R, chunk_r, build_call), chunk_r, tile_r



class FusedHashmapEngine(FusedEngineHost):
    """The fused combiner-round engine for the hashmap model.

    `round(log, states, opcodes, args, count, fenced=None)` executes
    one whole combiner round — append `count` entries at the tail,
    replay them into every (unfenced) replica, gather responses — as a
    single jitted program whose device work is ONE kernel launch per
    replica chunk (usually exactly one). Requires the lock-step
    precondition the caller checks host-side: every live cursor at the
    pre-append tail (`core/replica._try_fused_round`).

    States cross the boundary in MODEL layout (`[R, K]` values +
    bool present, `models/hashmap.py`); the transposes to the kernel's
    `[Kp, R]` planes live inside the jit. `raw_round` exposes the
    transposed-resident form for the kernel bench
    (`harness/mkbench.measure_kernel`), where state stays in kernel
    layout across rounds — the flagship configuration.

    The tile layout keeps the replica axis as the blocked lane axis in
    contiguous `tile_r`-wide chunks, i.e. exactly the
    `P('replica')`-sharded slicing of the PR 9 mesh tier: a per-shard
    invocation of the chunk calls is the shard-local program
    (tests/test_pallas_fused.py pins chunk-slice composability). The
    MESH-FUSED exec tier (`parallel/collectives.py:MeshFusedEngine`)
    is exactly that composition routed into the wrapper: this engine
    built at the shard's slice of the replica axis, wrapped in
    shard_map with the cursor lattice joined over ICI — one launch
    per device per combiner round at every mesh width.
    """

    supports_fenced = True

    def __init__(self, n_keys: int, spec: LogSpec, tile_r: int = 512,
                 interpret: bool | None = None):
        from node_replication_tpu.ops.pallas_ring import fused_window_ok

        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        if not fused_window_ok(spec.capacity, 1):
            raise ValueError(
                f"fused hashmap engine: ring capacity {spec.capacity} "
                f"has no 128-slot row layout"
            )
        self.n_keys = int(n_keys)
        self.spec = spec
        self.tile_r = int(tile_r)
        self.interpret = bool(interpret)
        self.kp = _round_up(self.n_keys, 8)
        self._calls: dict = {}    # (W, fenced) -> (calls, chunk_r)
        self._init_host()

    def supports(self, window: int) -> bool:
        """Window fits the ring-row spans, the appendable capacity,
        and (non-interpret) the VMEM tile budget."""
        from node_replication_tpu.ops.pallas_ring import fused_window_ok

        if not fused_window_ok(self.spec.capacity, window):
            return False
        if window > self.spec.capacity - self.spec.gc_slack:
            return False
        try:
            self._built(window, False)
        except ValueError:
            return False
        return True

    def launches(self, window: int) -> int:
        """Kernel launches per round (chunk calls over the replica
        axis; 1 unless MAX_GRID or VMEM splits the fleet)."""
        _, chunk_r = self._built(window, False)
        return -(-self.spec.n_replicas // chunk_r)

    def _built(self, window: int, fenced: bool):
        key = (window, fenced)
        if key not in self._calls:
            calls, chunk_r, _ = make_fused_hashmap_calls(
                self.n_keys, self.spec, window, tile_r=self.tile_r,
                interpret=self.interpret, fenced=fenced,
            )
            self._calls[key] = (calls, chunk_r)
        return self._calls[key]

    def raw_round(self, window: int, fenced: bool = False):
        """Pure fn over TRANSPOSED planes: `(log, vals_t, pres_t,
        opcodes, args, count[, fenced_vec]) -> (log, vals_t, pres_t,
        resps[W, R])`. Composable inside a caller's jit (the CNR
        per-log wrapper, the kernel bench)."""
        from node_replication_tpu.ops.pallas_ring import (
            append_window_planes,
            fused_cursor_lattice,
            ring_log,
            ring_planes,
        )

        calls, chunk_r = self._built(window, fenced)
        spec = self.spec
        R = spec.n_replicas

        def raw(log, vals_t, pres_t, opcodes, args, count,
                fenced_vec=None):
            ring = ring_planes(log)
            s_lo, lo, hi = append_window_planes(
                spec.mask, ring, opcodes, args, log.tail, count,
            )
            meta = jnp.stack(
                [s_lo, jnp.asarray(count, jnp.int32)]
            )
            key = args[:, 0]
            val = args[:, 1]
            fen_plane = (
                None if fenced_vec is None
                else jnp.asarray(fenced_vec, jnp.int32).reshape(1, R)
            )
            n = len(ring)
            v_chunks, p_chunks, r_chunks = [], [], []
            with x64_disabled():
                for r0 in range(0, R, chunk_r):
                    sub = min(chunk_r, R - r0)
                    ins = [meta, opcodes, key, val, *lo, *hi, *ring,
                           vals_t[:, r0:r0 + sub],
                           pres_t[:, r0:r0 + sub]]
                    if fen_plane is not None:
                        ins.append(fen_plane[:, r0:r0 + sub])
                    out = calls[sub](*ins)
                    ring, (v, p, r) = tuple(out[:n]), out[n:]
                    v_chunks.append(v)
                    p_chunks.append(p)
                    r_chunks.append(r)
            cat = (
                lambda xs: xs[0] if len(xs) == 1
                else jnp.concatenate(xs, axis=1)
            )
            vals_t, pres_t = cat(v_chunks), cat(p_chunks)
            resps = cat(r_chunks)
            log = ring_log(log, ring)
            log = fused_cursor_lattice(log, count, fenced_vec)
            return log, vals_t, pres_t, resps

        return raw

    def round_fn(self, window: int, fenced: bool = False):
        """Pure MODEL-layout round fn (transposes inside): `(log,
        states, opcodes, args, count[, fenced_vec]) -> (log, states,
        resps[R, W])` with `resps[r, j]` answering window offset j
        (= logical position tail+j under lock-step) and fenced rows
        zeroed — the layout response delivery consumes."""
        raw = self.raw_round(window, fenced)
        K, kp = self.n_keys, self.kp

        def fn(log, states, opcodes, args, count, fenced_vec=None):
            vals_t = jnp.zeros(
                (kp, states["values"].shape[0]), jnp.int32
            ).at[:K].set(states["values"].T)
            pres_t = jnp.zeros_like(vals_t).at[:K].set(
                states["present"].T.astype(jnp.int32)
            )
            log, vals_t, pres_t, resps = raw(
                log, vals_t, pres_t, opcodes, args, count, fenced_vec
            )
            states = {
                "values": vals_t[:K].T,
                "present": pres_t[:K].T > 0,
            }
            resps = resps.T  # [R, W]
            if fenced_vec is not None:
                resps = jnp.where(
                    jnp.asarray(fenced_vec, bool)[:, None], 0, resps
                )
            return log, states, resps

        return fn

    # round() — the host entry with metrics + the kernel-launch event —
    # is inherited from FusedEngineHost (ops/pallas_ring.py)
