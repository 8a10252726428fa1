"""Operation encoding and the `Dispatch` contract.

Replaces the reference's `Dispatch` trait (`nr/src/lib.rs:103-125`): instead
of associated `ReadOperation` / `WriteOperation` / `Response` types and
`dispatch(&self)` / `dispatch_mut(&mut self)` methods, an operation here is a
fixed-width record `(opcode: int32, args: int32[arg_width])` and the data
structure is described by a `Dispatch` value holding

- `make_state()` — builds the replica state pytree (the reference requires
  `D: Default`, `nr/examples/stack.rs:30-35`; deterministic init is the
  recovery model, SURVEY.md §5),
- `write_ops[i]`  : (state, args) -> (state, resp)   — pure `dispatch_mut`,
- `read_ops[i]`   : (state, args) -> resp            — pure `dispatch`; resp
  is one int32 word, or a vector of int32 words where the Dispatch's
  `read_stacked` returns one (reads are answered through `read_stacked`
  wherever the Dispatch gives it).

Opcode 0 is reserved as NOOP in both spaces so that padded / masked batch
slots replay as no-ops (the fixed-shape substitute for the reference's
`Option<T>` log-entry payloads and `alivef` liveness bits,
`nr/src/log.rs:51-65`). User write opcodes therefore start at 1.

Everything here is jit-safe: `apply_write` / `apply_read` lower to a single
`lax.switch`, which XLA compiles to a branch table executed uniformly across
a vmapped replica axis.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
from jax import lax

PyTree = Any

# Reserved opcode: replay/padding no-op in both the write and read spaces.
NOOP = 0

# Responses are int32 words. A write answers one word, and so does a read
# unless its Dispatch's `read_stacked` returns a vector, which is then the
# answer (the record store's read answers a whole record of 250 words,
# `models/records.py`). The reference's
# responses are word-sized as well (`Response = Option<u64>` style, e.g.
# `nr/examples/stack.rs:46-49`); "None" is conventionally encoded as -1 by
# the bundled models.
RESP_DTYPE = jnp.int32


@dataclasses.dataclass(frozen=True)
class Dispatch:
    """A replicated data structure: state constructor + pure transitions.

    Hashable (frozen, tuples of functions) so it can be a jit static arg.

    `window_plan` / `window_merge` (optional, come as a pair) split the
    combined replay for models whose window algebra DEPENDS on the
    running state (stack, queue: every slot assignment needs the clamped
    depth walk from the initial top): `window_plan(state, opcodes, args)
    -> plan` runs ONCE per window on a representative replica — this is
    where the sorts and scans live — and `window_merge(state, plan) ->
    (state, resps)` applies the plan's dense result per replica
    (elementwise, the honest per-replica replay work). Sound under the
    fused step's lock-step precondition (all replicas identical by
    induction).

    STRENGTHENED CONTRACT for divergent cursors (`window_canonical`):
    the union-window catch-up tier is an explicit OPT-IN, not implied
    by the pair's presence. A model sets `window_canonical=True` to
    declare its plan/merge satisfies the stronger contract below;
    only then do `NodeReplicated(engine='auto')` and `log_catchup_all`
    route it through `core/log.py:_catchup_union_plan` (and
    `engine='combined'` still FORCES that tier explicitly, canonical
    flag or not — the caller is asserting the contract). The tier
    merges the plan of the union window `[min(ltails), end)` (computed
    from the most-lagging replica's state) into replicas that already
    applied an arbitrary PREFIX of that window. Beyond the lock-step
    precondition this requires:

    - **prefix-absorbing plan**: for every split point p in the window,
      merging `window_plan(state(m), W)` into `state(p)` (the fold of
      the prefix `[m, p)`) must equal `state(end)` — cells the window
      touches take the plan's final value regardless of how much of the
      window the replica already applied, untouched cells keep the
      replica's value;
    - **canonical (state-independent) merge responses**: the per-position
      responses `window_merge` reports must depend only on the plan
      (equivalently: on the shared replay trajectory), never on the
      merging replica's pre-merge state, because catch-up re-indexes the
      donor plan's responses for every replica's own offsets.

    A model whose plan/merge satisfies only the lock-step contract
    simply leaves `window_canonical=False` (the default): it keeps the
    fused lock-step fast path, and catch-up falls back to the
    per-replica `window_apply` tier or the scan — third-party models
    are never silently routed through the stronger-contract engine
    (ADVICE r5). Hand-built off-trajectory fleets additionally pass
    `log_catchup_all(..., on_trajectory=False)`. Differential
    coverage: `tests/test_window.py::TestCombinedCatchup`.

    `window_apply` (optional) is the *combined replay* fast path:
    `(state, opcodes[W], args[W, A]) -> (state, resps[W])`, bit-identical
    to folding `apply_write` over the window in order. Models whose write
    ops are per-key last-writer-wins (hashmap, sorted set, page tables…)
    can compute a whole window with one sort + predecessor lookup + one
    dense merge instead of W sequential scatters — the flat-combining idea
    (`nr/src/replica.rs:543-595` batches ops to amortize the log CAS)
    taken to its TPU conclusion: the *application* itself is batched into
    a parallel reduction, turning the HBM-bound sequential replay scan
    into a handful of vectorized passes. `core/step.make_step` uses it
    automatically when present; the generic `lax.scan` path remains for
    order-dependent models (stack, queue) and divergent-cursor replay.
    """

    name: str
    make_state: Callable[[], PyTree]
    write_ops: tuple
    read_ops: tuple
    arg_width: int = 3
    window_apply: Callable | None = None
    window_plan: Callable | None = None
    window_merge: Callable | None = None
    # Explicit opt-in to the union-window catch-up tier: asserts the
    # plan is prefix-absorbing and merge responses are canonical (see
    # class docstring). Mere presence of window_plan/window_merge only
    # claims the weaker lock-step contract.
    window_canonical: bool = False
    # Optional, with a canonical plan: `window_merge_stacked(states, plan,
    # take) -> (states, resps)` merges the plan into the replicas of the
    # stacked [R, ...] states that `take` (bool[R]) marks, and leaves the
    # others as they are. The union-window tier then runs it in place of
    # a vmapped `window_merge` and a select over every replica's whole
    # state: a model whose plan touches a few rows of a large state
    # (`models/records.py`) writes those rows and nothing else.
    window_merge_stacked: Callable | None = None
    # Optional: `window_apply_stacked(states, opcodes[N], args[N, A],
    # applies[N, R]) -> (states, resps[N, R])`, `window_apply` of every
    # replica of the stacked [R, ...] states at once, replica r applying
    # the window's entries that `applies[:, r]` marks, in window order
    # (an entry it does not apply answers 0). The multi-log round's
    # replay (`core/multilog.multilog_exec_window`) runs it in place of
    # a vmapped `window_apply`: the hashmap's writes each touched slot's
    # column of replicas and nothing else.
    window_apply_stacked: Callable | None = None
    # Fused pallas combiner-round engine (optional): a callable
    # `(spec: LogSpec, interpret=None) -> engine` building the model's
    # one-kernel-launch append+replay round (e.g.
    # `ops/pallas_replay.py:FusedHashmapEngine`). The engine contract:
    # `round(log, states, opcodes, args, count, fenced=None)` under the
    # lock-step precondition, `supports(window)`, `launches(window)`,
    # and a `supports_fenced` class flag. Raising ValueError from the
    # factory means "no fused form at this config": `engine="pallas"`
    # refuses it at construction, and after a fleet-shape change the
    # wrappers fall back to the append+exec chain (`core/replica.py`
    # `_FusedTier`).
    fused_factory: Callable | None = None
    # Optional: `read_stacked(states, rid, opcode, args) -> int32[] or
    # int32[n]`, the read op of replica `rid` indexed in the stacked
    # [R, ...] states, so that a read never slices a whole replica out.
    # It answers as `apply_read` would, 0 for NOOP and unknown opcodes.
    # `NodeReplicated.execute` runs it as its own program
    # (`jit_read_stacked`) and answers an int for a 0-d answer (the
    # hashmap's get), else an `np.ndarray` of n words (the record
    # store's read).
    read_stacked: Callable | None = None
    # Host-side instrumentation (optional): `host_counters(event, ops)`
    # with event "append" (a combiner round's batch, as it is encoded) or
    # "read" (one read op). Called only while the obs registry is
    # enabled, never inside a traced function.
    host_counters: Callable | None = None

    @property
    def n_write_ops(self) -> int:
        return len(self.write_ops)

    @property
    def n_read_ops(self) -> int:
        return len(self.read_ops)

    def init_state(self) -> PyTree:
        return self.make_state()


def _noop_write(state: PyTree, args: jax.Array):
    return state, RESP_DTYPE(0)


def _noop_read(state: PyTree, args: jax.Array):
    return RESP_DTYPE(0)


def apply_write(d: Dispatch, state: PyTree, opcode: jax.Array, args: jax.Array):
    """Apply one encoded write op: the jit-safe `dispatch_mut`.

    Unknown / out-of-range opcodes route to the NOOP branch (inert),
    mirroring how padded log slots must replay as no-ops — and matching
    the native engine's unknown-opcode behavior for differential tests.
    """

    def wrap(f):
        def g(s, a):
            s2, r = f(s, a)
            return s2, RESP_DTYPE(r)

        return g

    branches = (_noop_write,) + tuple(wrap(f) for f in d.write_ops)
    valid = (opcode >= 0) & (opcode < len(branches))
    idx = jnp.where(valid, opcode, 0)
    return lax.switch(idx, branches, state, args)


def apply_read(d: Dispatch, state: PyTree, opcode: jax.Array, args: jax.Array):
    """Apply one encoded read op: the jit-safe `dispatch` (never mutates)."""

    def wrap(f):
        def g(s, a):
            return RESP_DTYPE(f(s, a))

        return g

    branches = (_noop_read,) + tuple(wrap(f) for f in d.read_ops)
    valid = (opcode >= 0) & (opcode < len(branches))
    idx = jnp.where(valid, opcode, 0)
    return lax.switch(idx, branches, state, args)


def dispatch_reads(d: Dispatch, states: PyTree, rd_opcodes, rd_args):
    """Answer per-replica read batches against local replica state:
    `rd_opcodes int32[R, Br]`, `rd_args int32[R, Br, A]` → `int32[R, Br]`.
    The batched read path shared by the single- and multi-log steps
    (`nr/src/replica.rs:483-497` local dispatch, vectorized)."""
    return jax.vmap(
        lambda state, opcs, args: jax.vmap(
            lambda o, a: apply_read(d, state, o, a)
        )(opcs, args)
    )(states, rd_opcodes, rd_args)


def encode_ops(
    ops: Sequence[tuple], arg_width: int, pad_to: int | None = None
) -> tuple[jax.Array, jax.Array, int]:
    """Encode a host-side list of `(opcode, *args)` tuples into device arrays.

    Returns `(opcodes: int32[B], args: int32[B, arg_width], count)` where
    slots past `count` are NOOP padding. `pad_to` fixes B (for shape-stable
    jit entry); defaults to `len(ops)`.
    """
    n = len(ops)
    pad = n if pad_to is None else pad_to
    if n > pad:
        raise ValueError(f"{n} ops do not fit in pad_to={pad}")
    opcodes = [int(o[0]) for o in ops] + [NOOP] * (pad - n)
    args = [
        list(o[1:]) + [0] * (arg_width - (len(o) - 1)) for o in ops
    ] + [[0] * arg_width] * (pad - n)
    return (
        jnp.asarray(opcodes, jnp.int32),
        jnp.asarray(args, jnp.int32).reshape(pad, arg_width),
        n,
    )
