"""Ring-window machinery shared by the FUSED append+replay kernels.

The fused engines (`ops/pallas_replay.py:FusedHashmapEngine`,
`ops/pallas_vspace.py:FusedVspaceEngine`) run a whole combiner round —
log-window append, replay, response gather — as ONE `pallas_call`. The
append half is the part they share, and it lives here.

Layout contract: the log's ring enters the kernel UN-BLOCKED
(`memory_space=pltpu.ANY`, aliased in→out) as `1 + A` planes of
`[capacity/128, 128]` (`ring_planes`): the opcodes, then one plane per
arg column — ring rows of 128 slots each, so every slice the append
DMA takes is 128-lane aligned, as Mosaic requires. The appended window
`[tail, tail+count)` covers at most `window_rows(W)` consecutive rows
(mod ring wrap), so the kernel updates the ring with TWO fixed-size
async copies of pre-blended row spans:

- the **lo span**: `win_rows` rows starting at the (dynamic, clamped)
  row of the tail slot,
- the **hi span**: rows `[0, win_rows)` — the wrap landing zone.

`append_window_planes` builds both spans XLA-side in O(window) work: it
gathers the spans' current content, blends the batch over exactly the
slots `[tail, tail+count)` (delta-mod arithmetic handles the wrap), and
leaves every other covered slot bit-identical — so DMA-ing a span back
rewrites untouched slots with their own values. When the window does
not wrap, the hi span degenerates to an identity rewrite of the first
rows. Both spans may overlap on small rings; they carry identical
content, and the kernel issues them sequentially.

Why DMA instead of per-entry stores: Mosaic has no dynamic LANE
indexing, and a ring row puts the slot index on the lane axis. The
pre-blended spans turn the scatter into two aligned block copies — the
double-buffered-VMEM-window idiom over the ring — while the un-blocked
ANY refs keep the aliasing OUTSIDE the grid pipeline, which is exactly
the regime the r5 corruption rule (`ops/pallas_chunk.py`, nrlint
`aliased-pallas-planes`) says is safe: only BLOCKED planes race the
pipeline's prefetch/writeback.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

RING_LANES = 128


def window_rows(window: int) -> int:
    """Ring rows covering any 128-phase alignment of `window` slots."""
    return -(-window // RING_LANES) + 1


def ring_rows(capacity: int) -> int:
    return capacity // RING_LANES


def fused_window_ok(capacity: int, window: int) -> bool:
    """Can a `window`-slot append ride the two fixed row spans?

    Needs the ring to be row-shaped (capacity a multiple of 128 — every
    power of two >= 128 qualifies) and tall enough that a span of
    `window_rows(window)` rows fits; `window + 128 <= capacity` keeps
    the lo span's clamp (`min(r0, rows - win_rows)`) able to cover the
    tail row. Callers fall back to the ordinary append+exec chain when
    this is False.
    """
    if capacity % RING_LANES or window < 1:
        return False
    return (
        window_rows(window) <= ring_rows(capacity)
        and window + RING_LANES <= capacity
    )


def ring_planes(log):
    """The log's ring as `1 + A` planes of `[rows, 128]` int32: the
    opcode plane, then one plane per arg column. Every plane's minor
    dimension is the 128-lane ring row, so the append DMA slices only
    128-aligned tiles (a `[rows, 128, A]` args view has a minor
    dimension of A, which Mosaic refuses to slice)."""
    rows = ring_rows(log.opcodes.shape[0])
    cols = [log.args[:, a] for a in range(log.args.shape[1])]
    return tuple(x.reshape(rows, RING_LANES) for x in (log.opcodes, *cols))


def ring_log(log, planes):
    """Inverse of `ring_planes`: write the (appended) planes back into
    the log's canonical `opcodes: [L]` / `args: [L, A]` layout."""
    opc, *cols = (x.reshape(-1) for x in planes)
    return log._replace(opcodes=opc, args=jnp.stack(cols, axis=1))


def append_window_planes(mask: int, ring, opcodes, args, tail, count):
    """XLA-side prep: desired POST-append content of the two row spans.

    `ring` is the `ring_planes` tuple, `opcodes`/`args` the NOOP-padded
    batch (`[W]` / `[W, A]`), `tail` the int64 append cursor and
    `count` the number of live entries (`count <= W`). Returns
    `(s_lo, lo, hi)`: `s_lo` the lo span's starting row (int32) and
    `lo`/`hi` one `[win_rows, 128]` span per ring plane — ready to DMA
    over the ring rows.
    """
    W = opcodes.shape[0]
    win = window_rows(W)
    rows = (mask + 1) // RING_LANES
    tail_slot = (tail & mask).astype(jnp.int32)
    r0 = tail_slot // RING_LANES
    s_lo = jnp.minimum(r0, jnp.int32(rows - win))
    count32 = jnp.asarray(count, jnp.int32)
    batch = (opcodes, *(args[:, a] for a in range(args.shape[1])))

    def span(row0):
        s = row0 * RING_LANES + jnp.arange(
            win * RING_LANES, dtype=jnp.int32
        )
        # slot-space delta from the tail: in [0, capacity); slots whose
        # delta lands below `count` are the appended entries
        d = (s - tail_slot) & jnp.int32(mask)
        live = d < count32
        gi = jnp.clip(d, 0, W - 1)
        return tuple(
            jnp.where(
                live, col[gi],
                lax.dynamic_slice(
                    plane, (row0, jnp.int32(0)), (win, RING_LANES)
                ).reshape(win * RING_LANES),
            ).reshape(win, RING_LANES)
            for plane, col in zip(ring, batch)
        )

    return s_lo, span(s_lo), span(jnp.int32(0))


def ring_append_dma(sem, s_lo, win_rows: int, lo_planes, hi_planes,
                    ring_outs):
    """Kernel-side append: copy the pre-blended spans over the ring.

    `lo_planes`/`hi_planes` are VMEM refs of the spans built by
    `append_window_planes`; `ring_outs` the matching UN-BLOCKED
    (aliased) `[rows, 128]` ring output refs. Copies run sequentially
    — the spans may overlap on small rings, and they carry identical
    content for shared rows, so ordering only matters for write-write
    tearing, which the serialization removes.
    """
    from jax.experimental.pallas import tpu as pltpu

    for src, dst in zip(lo_planes, ring_outs):
        cp = pltpu.make_async_copy(
            src, dst.at[pl.ds(s_lo, win_rows)], sem
        )
        cp.start()
        cp.wait()
    for src, dst in zip(hi_planes, ring_outs):
        cp = pltpu.make_async_copy(
            src, dst.at[pl.ds(0, win_rows)], sem
        )
        cp.start()
        cp.wait()


def split_ring_refs(refs, n_planes: int):
    """Unpack a fused kernel's leading ring refs: `(lo, hi, ring_in,
    rest)` — the lo spans, the hi spans, the aliased ring inputs (one
    of each per ring plane), then the kernel's own refs."""
    n = n_planes
    return refs[:n], refs[n:2 * n], refs[2 * n:3 * n], refs[3 * n:]


class FusedEngineHost:
    """Shared host-side plumbing for the fused engines
    (`ops/pallas_replay.FusedHashmapEngine`,
    `ops/pallas_vspace.FusedVspaceEngine`): the per-window round cache
    (jit on TPU, EAGER in interpret mode — jit + interpret + the
    package's x64 default trips an MLIR where-fn dtype mismatch in
    this jax, the same reason every interpret test passes jit=False),
    the `kernel.*` metrics, the `log.engine.pallas_fused` tier counter,
    and the `kernel-launch` trace event. Subclasses provide
    `round_fn(window, fenced)`, `launches(window)`, `supports(window)`,
    a `supports_fenced` class flag, and set `self.interpret`.

    `note_round` is public so callers that embed `round_fn` in their
    own program (the CNR per-log wrapper, the kernel bench) report the
    same metrics as callers of `round()` — one instrumentation
    contract, never two.

    `tier`/`devices` identify the engine in that contract: the plain
    single-device engines are `pallas_fused` on 1 device; the
    shard_map-wrapped mesh composition
    (`parallel/collectives.py:MeshFusedEngine`) overrides both, so its
    rounds count under `log.engine.mesh_fused` and its `kernel-launch`
    events carry the mesh width.
    """

    supports_fenced = False
    tier = "pallas_fused"
    devices = 1

    def _init_host(self) -> None:
        from node_replication_tpu.obs.metrics import (
            COUNT_BUCKETS,
            get_registry,
        )

        reg = get_registry()
        self._m_launches = reg.counter("kernel.launches")
        self._m_ops = reg.counter("kernel.fused_window_ops")
        self._m_window = reg.histogram("kernel.window",
                                       buckets=COUNT_BUCKETS)
        self._m_dur = reg.histogram("kernel.round.duration_s")
        self._rounds: dict = {}

    def note_round(self, window: int, count: int, duration_s: float,
                   fenced: bool = False) -> None:
        """Count one fused round: tier counter, kernel.* metrics,
        kernel-launch event. Duration is enqueue-side (JAX returns
        at dispatch); fenced timing is the caller's span contract.
        `kernel.launches` advances by
        `launches(window)` — the engine's claim, derived from the same
        built chunk structure the round loop iterates (a compiled
        round's dispatches are invisible to the host, so this is the
        best available truth; the bench's chain runners, whose
        dispatches ARE host calls, count at the call sites instead)."""
        from node_replication_tpu.core import log as _corelog
        from node_replication_tpu.obs.recorder import get_tracer

        n_launch = self.launches(window)
        if self.tier == "mesh_fused":
            _corelog._m_engine_mesh_fused.inc()
        else:
            _corelog._m_engine_pallas_fused.inc()
        self._m_launches.inc(n_launch)
        self._m_ops.inc(int(count))
        self._m_window.observe(window)
        self._m_dur.observe(duration_s)
        tracer = get_tracer()
        if tracer.enabled:
            tracer.emit(
                "kernel-launch", tier=self.tier, window=window,
                count=int(count), launches=n_launch,
                devices=self.devices,
                duration_s=duration_s, fenced=fenced,
            )

    def round(self, log, states, opcodes, args, count, fenced=None):
        """Host entry: cached model-layout round + instrumentation.
        `count` is a host int; `opcodes` must be NOOP-padded past it
        (`encode_ops`)."""
        import time as _time

        import jax as _jax
        import jax.numpy as _jnp

        if fenced is not None and not self.supports_fenced:
            raise ValueError(
                f"{type(self).__name__} has no fenced kernel variant "
                f"(supports_fenced=False)"
            )
        W = int(opcodes.shape[0])
        is_fenced = fenced is not None
        fn = self._rounds.get((W, is_fenced))
        if fn is None:
            inner = self.round_fn(W, is_fenced)
            fn = (
                inner if self.interpret
                else _jax.jit(inner, donate_argnums=(0, 1))
            )
            self._rounds[(W, is_fenced)] = fn
        t0 = _time.perf_counter()
        if is_fenced:
            out = fn(log, states, opcodes, args, count,
                     _jnp.asarray(fenced, bool))
        else:
            out = fn(log, states, opcodes, args, count)
        self.note_round(W, count, _time.perf_counter() - t0,
                        fenced=is_fenced)
        return out


def fused_cursor_lattice(log, count, fenced=None):
    """The fused round's cursor join — the same lattice `log_exec_all`
    computes, specialized to the lock-step precondition (every live
    cursor at the pre-append tail, the whole window consumed):

    - `tail += count`;
    - unfenced `ltails` land on the new tail, fenced cursors freeze;
    - `ctail = max(ctail, max(ltails))` (= the new tail, since the
      eligibility check guarantees a live replica);
    - `head` = the `_gc_head` reduction (min over unfenced, clamped
      monotone), so a fenced corpse neither stalls GC nor rewinds it.
    """
    from node_replication_tpu.core.log import _gc_head

    new_tail = log.tail + jnp.asarray(count, jnp.int64)
    R = log.ltails.shape[0]
    if fenced is None:
        new_lt = jnp.broadcast_to(new_tail, (R,))
        # ctail/head written as their true lattice joins (both reduce
        # to the new tail here) rather than re-using `new_tail`: three
        # cursor outputs sharing ONE buffer would make the next
        # donating program reject the log ("donate the same buffer
        # twice")
        return log._replace(
            tail=new_tail,
            ltails=new_lt,
            ctail=jnp.maximum(log.ctail, new_tail),
            head=jnp.maximum(log.head, jnp.min(new_lt)),
        )
    fen = jnp.asarray(fenced, bool)
    new_lt = jnp.where(fen, log.ltails, new_tail)
    out = log._replace(
        tail=new_tail,
        ltails=new_lt,
        ctail=jnp.maximum(log.ctail, jnp.max(new_lt)),
    )
    return out._replace(head=_gc_head(out, new_lt, fen))
