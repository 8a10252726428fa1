"""Replicated record store: YCSB's records, at their published width.

YCSB's core workloads (`workloads/workloada` and `CoreWorkload` in
brianfrankcooper/YCSB) keep records of `fieldcount=10` fields of
`fieldlength=100` bytes. Here a record is 10 fields of 25 int32 words
(1,000 B) and a presence flag; the key is the record number, mapped onto
the dense table by `k % K` as `models/hashmap.py` maps its keys. YCSB's
key strings (`user<hash>`) are not stored.

State: `fields: int32[K, 256]`, field f of record k in words
`[25 f, 25 f + 25)` of row k, and `present: bool[K]`. The row's last 6
words are padding and stay 0: a row of 256 words tiles the TPU's
(8, 128) layout, where `int32[K, 10, 25]` would pad its 25-word minor
dimension to 128 lanes and take 5.1 times the memory.

Write opcode: REC_UPDATE=1, args `(k, f, w[25])` -> resp 0: field
`f % 10` of record k takes the 25 words and the record becomes present
(YCSB's update with `writeallfields=false`: one field).
Read opcode: REC_READ=1, args `(k)` -> all 250 words of the record
(`readallfields=true`), field-major, or `ABSENT` in every word when the
record is absent.

A read answers `RECORD_WORDS` words, so the Dispatch gives a
`read_stacked` that gathers the one record from the stacked replicas: a
replica is 2.1 GB at 2^21 records, and slicing it out for a 1 KB read
would copy all of it.

A window replays as one plan (`window_plan`): the entries grouped by
`(key, field)` with one stable sort, each pair's last writer kept, and
the kept rows moved to the front. The plan reads no state, so it is
prefix-absorbing and its responses (all 0) canonical: the union-window
tier (`core/log.py:_catchup_union_plan`) computes it once and
`window_merge_stacked` writes its rows into every replica that takes
the window, one row at a time for as many rows as the plan kept. Nothing
in a round is the size of the table. (A vmapped `window_merge` and a
select would read and write every byte of every replica each round.)
`window_merge`, for the per-replica tiers, is the same loop on a stack
of one replica.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from node_replication_tpu.obs.metrics import get_registry
from node_replication_tpu.ops.encoding import Dispatch

REC_UPDATE = 1
REC_READ = 1

N_FIELDS = 10
FIELD_WORDS = 25
RECORD_WORDS = N_FIELDS * FIELD_WORDS
ARG_WIDTH = 2 + FIELD_WORDS  # key, field, the field's words
ROW_WORDS = 256  # a record's row: RECORD_WORDS and padding to 2 x 128 lanes

ABSENT = -1

# host-side counters (obs registry), bumped only while it is enabled
_reg = get_registry()
_m_entries = _reg.counter("nr.rec.window_entries")
_m_rows = _reg.counter("nr.rec.window_rows")
_m_reads = _reg.counter("nr.rec.reads")


def make_records(n_records: int) -> Dispatch:
    """The record store over a dense table of `n_records` records."""
    n = int(n_records)
    rows = n * N_FIELDS  # one row per (record, field); also the drop index
    if rows >= 2**31:
        raise ValueError(f"{n} records: (key, field) rows overflow int32")

    def make_state():
        return {
            "fields": jnp.zeros((n, ROW_WORDS), jnp.int32),
            "present": jnp.zeros((n,), jnp.bool_),
        }

    def update(state, args):
        k = args[0] % n
        f = args[1] % N_FIELDS
        return {
            "fields": lax.dynamic_update_slice(
                state["fields"], args[None, 2:ARG_WIDTH],
                (k, f * FIELD_WORDS)),
            "present": state["present"].at[k].set(True),
        }, jnp.int32(0)

    def read(state, args):
        k = args[0] % n
        return jnp.where(state["present"][k],
                         state["fields"][k, :RECORD_WORDS],
                         jnp.int32(ABSENT))

    def read_stacked(states, rid, opcode, args):
        """REC_READ of replica `rid`, gathered from the stacked states;
        any other opcode answers zeros (the NOOP read's answer)."""
        k = args[0] % n
        rec = jnp.where(states["present"][rid, k],
                        states["fields"][rid, k, :RECORD_WORDS],
                        jnp.int32(ABSENT))
        return jnp.where(opcode == REC_READ, rec, jnp.int32(0))

    def window_plan(state, opcodes, args):
        """Each `(key, field)` row's last writer in the window, and where
        it goes. The row id `k * 10 + f` fits int32 (checked above); a
        stable sort keeps window order inside a row, so a row's last
        writer is the final entry of its group. Inactive entries take
        the row id `rows`, past the table; they and every superseded
        entry are sent to `rows`, which the merges drop. The `count`
        kept rows come first. The plan reads no state."""
        del state
        W = opcodes.shape[0]
        active = opcodes == REC_UPDATE
        row = (args[:, 0] % n) * N_FIELDS + args[:, 1] % N_FIELDS
        row = jnp.where(active, row, rows)
        order = jnp.argsort(row, stable=True)
        sr = row[order]
        keep = jnp.concatenate([sr[1:] != sr[:-1],
                                jnp.ones((1,), jnp.bool_)]) & (sr < rows)
        first = jnp.argsort(~keep, stable=True)
        return {
            "rows": jnp.where(keep, sr, rows)[first],
            "data": args[order, 2:ARG_WIDTH][first],
            "count": jnp.sum(keep, dtype=jnp.int32),
            "resps": jnp.zeros((W,), jnp.int32),
        }

    def window_merge_stacked(states, plan, take):
        """The plan's kept rows into each replica `take` marks: a loop
        over `count` rows, each read and written at once on all replicas
        (a replica that does not take keeps its words). Row id r is
        record r // 10, columns from 25 (r % 10)."""
        R = take.shape[0]
        zero = jnp.int32(0)

        def one(i, st):
            fields, present = st
            k = plan["rows"][i] // N_FIELDS
            col = plan["rows"][i] % N_FIELDS * FIELD_WORDS
            old = lax.dynamic_slice(fields, (zero, k, col), (R, 1, FIELD_WORDS))
            new = jnp.where(take[:, None, None], plan["data"][i][None, None], old)
            was = lax.dynamic_slice(present, (zero, k), (R, 1))
            return (lax.dynamic_update_slice(fields, new, (zero, k, col)),
                    lax.dynamic_update_slice(present, was | take[:, None], (zero, k)))

        fields, present = lax.fori_loop(
            zero, plan["count"], one, (states["fields"], states["present"]))
        resps = jnp.broadcast_to(plan["resps"], (R,) + plan["resps"].shape)
        return {"fields": fields, "present": present}, resps

    def window_merge(state, plan):
        """`window_merge_stacked` on a stack of one replica."""
        states, resps = window_merge_stacked(
            {name: a[None] for name, a in state.items()}, plan,
            jnp.ones((1,), jnp.bool_))
        return {name: a[0] for name, a in states.items()}, resps[0]

    def window_apply(state, opcodes, args):
        return window_merge(state, window_plan(state, opcodes, args))

    def host_counters(event, ops):
        """`nr.rec.*` from the ops on the host: a combiner round's batch
        as it is encoded ("append"), or one read ("read")."""
        if event == "read":
            _m_reads.inc(len(ops))
            return
        ups = [op for op in ops if op[0] == REC_UPDATE]
        _m_entries.inc(len(ops))
        _m_rows.inc(len({(op[1] % n, op[2] % N_FIELDS) for op in ups}))

    return Dispatch(
        name=f"records{n}",
        make_state=make_state,
        write_ops=(update,),
        read_ops=(read,),
        arg_width=ARG_WIDTH,
        window_apply=window_apply,
        window_plan=window_plan,
        window_merge=window_merge,
        window_canonical=True,
        window_merge_stacked=window_merge_stacked,
        read_stacked=read_stacked,
        host_counters=host_counters,
    )
