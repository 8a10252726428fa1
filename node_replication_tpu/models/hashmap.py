"""Replicated hash map, dense-keyspace variant.

The reference's flagship workload (`benches/hashmap.rs:29-48`: a
`HashMap<u64, u64>` with Put/Get behind NR). TPU-first re-design
(SURVEY.md §7 "data-structure state as arrays"): the bench keyspace is
bounded, so the map is a dense `values: int32[K]` + `present: bool[K]` pair,
making every Put one scatter and every Get one gather — both vectorize
perfectly across a vmapped replica axis. An open-addressing variant for
sparse keyspaces lives in `models/oahashmap.py`.

Write opcodes: HM_PUT=1 (args k, v → resp 0), HM_REMOVE=2 (args k → resp 1
if the key was present else 0).
Read opcodes: HM_GET=1 (args k → resp value, or -1 when absent — the
encoding of the reference's `Option<u64>` response). `NodeReplicated`
answers a get through `read_stacked`, one slot of the stacked replicas.
`hashmap_log_mapper` is the key, the LogMapper `MultiLogReplicated` routes
the hashmap's ops by; its replay runs `window_apply_stacked`.
Keys hash onto the dense table with `k % K` (uniform bench keys are already
dense; the modulus mirrors a hash).
"""

from __future__ import annotations

import jax.numpy as jnp
from jax.lax import cummax, sort

from node_replication_tpu.ops.encoding import Dispatch

HM_PUT = 1
HM_REMOVE = 2
HM_GET = 1

ABSENT = -1


def hashmap_log_mapper(opcode: int, args: tuple) -> int:
    """The `cnr` LogMapper of the hashmap (`benches/chashbench.rs:91-100`):
    the key. Ops on different keys commute, ops on one key share a log
    (`hash % nlogs`, `core/cnr.py`)."""
    return args[0]


def make_hashmap(n_keys: int, prefill_value: int | None = None) -> Dispatch:
    """Build the hashmap Dispatch over a dense table of `n_keys` slots.

    `prefill_value` pre-populates every key (the reference prefills 2^26
    entries before measuring, `benches/hashmap.rs:131-139`).
    """

    def make_state():
        if prefill_value is None:
            return {
                "values": jnp.zeros((n_keys,), jnp.int32),
                "present": jnp.zeros((n_keys,), jnp.bool_),
            }
        return {
            "values": jnp.full((n_keys,), prefill_value, jnp.int32),
            "present": jnp.ones((n_keys,), jnp.bool_),
        }

    def put(state, args):
        k = args[0] % n_keys
        return {
            "values": state["values"].at[k].set(args[1]),
            "present": state["present"].at[k].set(True),
        }, jnp.int32(0)

    def remove(state, args):
        k = args[0] % n_keys
        was = state["present"][k]
        return {
            "values": state["values"].at[k].set(0),
            "present": state["present"].at[k].set(False),
        }, was.astype(jnp.int32)

    def get(state, args):
        k = args[0] % n_keys
        return jnp.where(
            state["present"][k], state["values"][k], jnp.int32(ABSENT)
        )

    def read_stacked(states, rid, opcode, args):
        """`get` of replica `rid`, gathered from the stacked [R, K]
        planes: one slot, where slicing the replica out first would copy
        its whole row. Any other opcode answers 0 (the NOOP read's
        answer)."""
        k = args[0] % n_keys
        got = jnp.where(states["present"][rid, k], states["values"][rid, k],
                        jnp.int32(ABSENT))
        return jnp.where(opcode == HM_GET, got, jnp.int32(0))

    def window_plan(state, opcodes, args):
        """Combined replay of a whole window (see `Dispatch.window_apply`).

        PUT/REMOVE are last-writer-wins per key, so the final state needs
        only each key's LAST active entry, and a REMOVE's response
        (was-present) needs only its immediate same-key PREDECESSOR — both
        parallel computations:

        1. group entries by key with one stable sort,
        2. presence-before(entry) = predecessor-was-PUT, or the replica's
           initial presence for each key's first touch,
        3. merge each key's last write into the dense table (elementwise).

        Bit-identical to folding put/remove over the window in order
        (differentially tested in tests/test_window.py). Replaces the
        reference's per-entry replay loop (`nr/src/log.rs:473-524`) with
        O(W log W) parallel work instead of W sequential scatters.

        Packaged as plan/merge (r5): the sort half runs once per window
        (fused step AND union-window catch-up — the plan is
        prefix-absorbing: per-key finals are absolute); the vmapped
        merge is the honest per-replica dense blend.

        Cost: an O(W log W) sort, O(W) gathers and scatters, and O(K)
        fills of the dense outputs — no gather indexed by all K slots.
        Each key's last writer is the final entry of its sorted group,
        so it is unique: the scatters of those entries never collide and
        are deterministic. Slots the window does not touch read
        `touched=False` and zeros, which the merge never reads.
        """
        W = opcodes.shape[0]
        k = args[:, 0] % n_keys
        v = args[:, 1]
        is_put = opcodes == HM_PUT
        is_rem = opcodes == HM_REMOVE
        active = is_put | is_rem
        # inactive slots (NOOP / unknown opcodes) group into a sentinel
        # bucket past the keyspace so they never touch real keys
        key_eff = jnp.where(active, k, n_keys).astype(jnp.int64)
        # stable key grouping: argsort is stable, so equal keys keep
        # window order — no composite `key*(W+1)+idx` key, which would
        # overflow int32 under the NR_TPU_NO_X64=1 opt-out (ADVICE r3)
        order = jnp.argsort(key_eff, stable=True)
        sk = key_eff[order]
        same_prev = jnp.concatenate(
            [jnp.zeros((1,), jnp.bool_), sk[1:] == sk[:-1]]
        )
        prev = jnp.concatenate([order[:1], order[:-1]])
        # presence just before each entry: its same-key predecessor's
        # effect, else the replica's initial presence of that key
        # sentinel index n_keys clamps onto the last real key; harmless
        # because sentinel slots are never REMOVEs (resp forced to 0)
        init_present = state["present"].at[
            sk.astype(jnp.int32)
        ].get(mode="clip")
        pres_before = jnp.where(same_prev, is_put[prev], init_present)
        resp_sorted = jnp.where(
            is_rem[order], pres_before.astype(jnp.int32), jnp.int32(0)
        )
        resps = jnp.zeros((W,), jnp.int32).at[order].set(resp_sorted)
        # last active entry per key wins: the final entry of each sorted
        # group; every other entry (and the sentinel bucket) scatters to
        # index n_keys, which mode="drop" discards
        is_last = jnp.concatenate(
            [sk[1:] != sk[:-1], jnp.ones((1,), jnp.bool_)]
        )
        dst = jnp.where(is_last & (sk < n_keys), sk, n_keys)
        put_sorted = is_put[order]
        return {
            "touched": jnp.zeros((n_keys,), jnp.bool_)
            .at[dst].set(True, mode="drop"),
            "value": jnp.zeros((n_keys,), v.dtype)
            .at[dst].set(jnp.where(put_sorted, v[order], 0), mode="drop"),
            "present": jnp.zeros((n_keys,), jnp.bool_)
            .at[dst].set(put_sorted, mode="drop"),
            "resps": resps,
        }

    def window_merge(state, plan):
        return {
            "values": jnp.where(plan["touched"], plan["value"],
                                state["values"]),
            "present": jnp.where(plan["touched"], plan["present"],
                                 state["present"]),
        }, plan["resps"]

    def window_apply(state, opcodes, args):
        # arbitrary-state form: the plan's presence-before half reads
        # THIS state, so the composition is the full per-replica fold
        return window_merge(state, window_plan(state, opcodes, args))

    def window_apply_stacked(states, opcodes, args, applies):
        """`window_apply` of every replica of the stacked [R, K] planes,
        replica r applying the entries `applies[:, r]` marks
        (`Dispatch.window_apply_stacked`); resps [N, R].

        One sort groups the window's entries by key (window order within
        a key). Per replica, a cumulative max finds each entry's latest
        applied predecessor in its group: a REMOVE's presence-before is
        that entry's PUT-ness, else the replica's own slot; and the
        group's last applied entry is the replica's final write. Each
        touched slot's column of replicas is read once and written once
        (a column keeps the layout of the [R, K] planes: a scatter by
        (replica, slot) would relay the planes out whole), and a second
        sort puts the responses back in window order. No op indexes all
        K slots."""
        N, R = applies.shape
        is_put = opcodes == HM_PUT
        is_rem = opcodes == HM_REMOVE
        key = jnp.where(is_put | is_rem, args[:, 0] % n_keys, n_keys)
        pos = jnp.arange(N, dtype=jnp.int32)
        # (key, window position) is unique: no reliance on sort stability
        sk, order = sort((key.astype(jnp.int32), pos), num_keys=2)
        sput, srem, sval = is_put[order], is_rem[order], args[order, 1]
        live = sk < n_keys
        sapp = applies[order] & live[:, None]
        first = jnp.concatenate([jnp.ones((1,), jnp.bool_), sk[1:] != sk[:-1]])
        last = jnp.concatenate([sk[1:] != sk[:-1], jnp.ones((1,), jnp.bool_)])
        start = cummax(jnp.where(first, pos, 0))[:, None]
        # per replica: the latest applied entry at or before each entry
        upto = cummax(jnp.where(sapp, pos[:, None], -1), axis=0)
        before = jnp.concatenate([jnp.full((1, R), -1, jnp.int32), upto[:-1]])
        slot = jnp.minimum(sk, n_keys - 1)
        old_present = states["present"][:, slot].T
        old_values = states["values"][:, slot].T
        pres_before = jnp.where(before >= start, sput[jnp.maximum(before, 0)],
                                old_present)
        resp_sorted = jnp.where(sapp & srem[:, None],
                                pres_before.astype(jnp.int32), 0)
        wrote = upto >= start
        fin = jnp.maximum(upto, 0)
        new_present = jnp.where(wrote, sput[fin], old_present)
        new_values = jnp.where(wrote, jnp.where(sput[fin], sval[fin], 0),
                               old_values)
        dst = jnp.where(last & live, sk, n_keys)
        states = {
            "values": states["values"].at[:, dst].set(new_values.T,
                                                      mode="drop"),
            "present": states["present"].at[:, dst].set(new_present.T,
                                                        mode="drop"),
        }
        back = sort((order, *resp_sorted.T), num_keys=1)[1:]
        return states, jnp.stack(back, axis=1)

    # fused pallas combiner round (ops/pallas_replay.py): one kernel
    # launch per serve batch — append + replay + response gather on the
    # transposed [K, R] planes. Lazily imported so the model stays
    # importable where pallas is not.
    def fused_factory(spec, interpret=None):
        from node_replication_tpu.ops.pallas_replay import (
            FusedHashmapEngine,
        )

        return FusedHashmapEngine(n_keys, spec, interpret=interpret)

    return Dispatch(
        name=f"hashmap{n_keys}",
        make_state=make_state,
        write_ops=(put, remove),
        read_ops=(get,),
        arg_width=3,
        window_apply=window_apply,
        window_plan=window_plan,
        window_merge=window_merge,
        # prefix-absorbing plan + canonical responses pinned by
        # tests/test_window.py::test_plan_is_prefix_absorbing
        window_canonical=True,
        window_apply_stacked=window_apply_stacked,
        fused_factory=fused_factory,
        read_stacked=read_stacked,
    )
