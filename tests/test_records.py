"""The record store (`models/records.py`): YCSB-A's 1 KB records.

Its window replay against the per-op fold, the program's oracle and the
benchmark's plain reference (`benchmark/references/records.py`); the
served path under zipfian multi-writer traffic, field by field as
registers; the wide read path of `NodeReplicated`; the host counters;
and compiles at the deployment's size (2^21 records, 4 replicas) whose
temporaries show that no program of a round or a read spans the table.
"""

import dataclasses
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.references.records import RecordsReference, prefill_words
from node_replication_tpu import NodeReplicated
from node_replication_tpu.core.log import LogSpec, log_catchup_all, log_init
from node_replication_tpu.core.replica import replicate_state
from node_replication_tpu.models import (
    HM_GET,
    HM_PUT,
    REC_READ,
    REC_UPDATE,
    ST_PEEK,
    ST_PUSH,
    make_hashmap,
    make_records,
    make_stack,
)
from node_replication_tpu.models.records import (
    ABSENT,
    ARG_WIDTH,
    FIELD_WORDS,
    RECORD_WORDS,
    ROW_WORDS,
)
from node_replication_tpu.obs.metrics import get_registry
from node_replication_tpu.ops.encoding import apply_write
from node_replication_tpu.sim.oracle import make_oracle

K, R = 4096, 4
SEED = 2**40 + 25


def seeded_state(n: int, seed: int = SEED) -> dict:
    """The load phase as the reference computes it, in the model's rows."""
    words = prefill_words(np.arange(n), seed).reshape(n, RECORD_WORDS)
    rows = np.zeros((n, ROW_WORDS), np.int32)
    rows[:, :RECORD_WORDS] = words
    return {"fields": jnp.asarray(rows), "present": jnp.ones((n,), jnp.bool_)}


def seeded(n: int, seed: int = SEED):
    return dataclasses.replace(make_records(n),
                               make_state=lambda: seeded_state(n, seed))


def hot_window(rng, W: int, n: int, hot: int = 6):
    """Updates crowded onto a few hot (key, field) pairs, with NOOPs,
    unknown opcodes, keys past the table and fields past 10."""
    opc = rng.choice([0, REC_UPDATE, 7], size=W, p=[0.15, 0.75, 0.1])
    keys = np.where(rng.random(W) < 0.7, rng.integers(0, hot, W),
                    rng.integers(0, 3 * n, W))
    fields = np.where(rng.random(W) < 0.8, rng.integers(0, 3, W),
                      rng.integers(0, 25, W))
    words = rng.integers(-2**31, 2**31 - 1, (W, FIELD_WORDS))
    args = np.concatenate([keys[:, None], fields[:, None], words], axis=1)
    return opc.astype(np.int32), args.astype(np.int32)


@jax.jit
def _fold(state, opc, args):
    d = make_records(K)
    return jax.lax.scan(lambda s, x: apply_write(d, s, x[0], x[1]),
                        state, (opc, args))


def as_fields(state) -> np.ndarray:
    return np.asarray(state["fields"])[:, :RECORD_WORDS].reshape(-1, 10, FIELD_WORDS)


class TestWindowApply:
    @pytest.mark.parametrize("seed", [0, 1, 2])
    @pytest.mark.parametrize("W", [16, 256])
    def test_matches_fold_and_reference(self, seed, W):
        rng = np.random.default_rng(seed)
        opc, args = hot_window(rng, W, K)
        d = make_records(K)
        state0 = seeded_state(K)
        got, resps = jax.jit(d.window_apply)(state0, jnp.asarray(opc), jnp.asarray(args))
        want, fold_resps = _fold(state0, jnp.asarray(opc), jnp.asarray(args))
        for leaf in ("fields", "present"):
            np.testing.assert_array_equal(np.asarray(got[leaf]), np.asarray(want[leaf]))
        np.testing.assert_array_equal(np.asarray(resps), np.asarray(fold_resps))
        assert not np.asarray(resps).any()
        ref = RecordsReference(K, SEED)
        ref.update_window([(int(a[0]), int(a[1]), a[2:]) for o, a in zip(opc, args)
                           if o == REC_UPDATE])
        np.testing.assert_array_equal(as_fields(got), ref.dense())
        assert not np.asarray(got["fields"])[:, RECORD_WORDS:].any()  # padding stays 0

    def test_inactive_window_is_identity(self):
        d = make_records(K)
        state0 = seeded_state(K)
        opc = jnp.asarray([0, 7, -1, 0], jnp.int32)
        args = jnp.ones((4, ARG_WIDTH), jnp.int32)
        got, _ = d.window_apply(state0, opc, args)
        np.testing.assert_array_equal(np.asarray(got["fields"]), np.asarray(state0["fields"]))

    def test_plan_keeps_one_row_per_pair(self):
        rng = np.random.default_rng(5)
        opc, args = hot_window(rng, 256, K)
        plan = make_records(K).window_plan(None, jnp.asarray(opc), jnp.asarray(args))
        rows = np.asarray(plan["rows"])
        live = rows[rows < K * 10]
        active = opc == REC_UPDATE
        pairs = {(int(a[0]) % K, int(a[1]) % 10) for a in args[active]}
        assert len(live) == len(set(live.tolist())) == len(pairs)
        assert set(live.tolist()) == {k * 10 + f for k, f in pairs}

    def test_one_merge_serves_every_tier(self):
        """`window_merge` is `window_merge_stacked`'s row loop on one
        replica, with no scatter of all W plan rows (the dropped ones
        included)."""
        opc, args = hot_window(np.random.default_rng(6), 256, K)
        d = make_records(K)
        plan = d.window_plan(None, jnp.asarray(opc), jnp.asarray(args))
        jaxpr = jax.make_jaxpr(d.window_merge)(seeded_state(K), plan)
        prims = {e.primitive.name for e in jaxpr.jaxpr.eqns}
        assert "while" in prims and not any("scatter" in p for p in prims)

    def test_empty_store_sets_presence(self):
        d = make_records(8)
        state, _ = d.window_apply(d.init_state(), jnp.asarray([REC_UPDATE], jnp.int32),
                                  jnp.asarray([[11] + [2] + list(range(25))], jnp.int32))
        assert np.asarray(state["present"]).tolist() == [False, False, False, True] + [False] * 4


def test_oracle_matches_the_model():
    rng = np.random.default_rng(9)
    n = 64
    d = make_records(n)
    opc, args = hot_window(rng, 64, n)
    state, _ = d.window_apply(d.init_state(), jnp.asarray(opc), jnp.asarray(args))
    orc = make_oracle("records", n)
    for o, a in zip(opc, args):
        if o == REC_UPDATE:
            assert orc.apply((int(o), *a.tolist())) == 0
    for leaf, arr in orc.arrays().items():
        np.testing.assert_array_equal(np.asarray(state[leaf]), arr)
    read = jax.jit(d.read_ops[0])
    for k in (0, int(args[0, 0]), n - 1):
        np.testing.assert_array_equal(np.asarray(read(state, jnp.asarray([k] + [0] * 26, jnp.int32))),
                                      orc.read((REC_READ, k)))


class TestWideRead:
    def test_record_read_returns_every_word(self):
        nr = NodeReplicated(make_records(64), n_replicas=2)
        t0, t1 = nr.register(0), nr.register(1)
        words = list(range(100, 125))
        assert nr.execute_mut((REC_UPDATE, 70, 4, *words), t0) == 0
        got = nr.execute((REC_READ, 70), t1)  # 70 % 64: record 6, on the other replica
        assert isinstance(got, np.ndarray) and got.dtype == np.int32
        assert got.shape == (RECORD_WORDS,)
        assert got[100:125].tolist() == words
        assert not got[:100].any() and not got[125:].any()
        assert (nr.execute((REC_READ, 7), t1) == ABSENT).all()

    @pytest.mark.parametrize("model", ["hashmap", "stack"])
    def test_scalar_read_still_answers_an_int(self, model):
        """A one-word read answers an int: the hashmap's get through its
        `read_stacked`, the stack's peek through the sliced replica
        (`_read_one`), the form of a model without `read_stacked`."""
        d, write, read = {
            "hashmap": (make_hashmap(16), (HM_PUT, 3, 42), (HM_GET, 3)),
            "stack": (make_stack(16), (ST_PUSH, 42), (ST_PEEK,)),
        }[model]
        nr = NodeReplicated(d, n_replicas=2)
        t0, t1 = nr.register(0), nr.register(1)
        nr.execute_mut(write, t0)
        got = nr.execute(read, t1)
        assert type(got) is int and got == 42
        assert hasattr(nr, "_read_stacked_jit") == (model == "hashmap")

    def test_read_stacked_alone_selects_the_wide_read(self):
        """No width is declared: a Dispatch with `read_stacked` answers
        the vector it returns, whatever its length."""
        def two_words(states, rid, opcode, args):
            return jnp.stack([states["values"][rid, args[0] % 8], opcode])

        nr = NodeReplicated(dataclasses.replace(make_hashmap(8), read_stacked=two_words),
                            n_replicas=2)
        nr.execute_mut((HM_PUT, 3, 42), nr.register(0))
        got = nr.execute((HM_GET, 3), nr.register(1))
        assert isinstance(got, np.ndarray) and got.tolist() == [42, HM_GET]

    def test_no_fused_tier_is_consulted(self):
        nr = NodeReplicated(make_records(64), n_replicas=2, engine="auto")
        assert nr._fused_mode == "off" and nr.engine == "combined"
        nr.execute_mut_batch([(REC_UPDATE, 1, 1, *range(25))] * 3, rid=0)
        assert nr._fused_engine() is None
        assert nr.round_tier(0) == "combined"


def test_host_counters_follow_the_registry():
    reg = get_registry()
    names = ("nr.rec.window_entries", "nr.rec.window_rows", "nr.rec.reads")
    before = {k: reg.counter(k).value for k in names}
    nr = NodeReplicated(make_records(64), n_replicas=2)
    ops = [(REC_UPDATE, 5, 1, *range(25)), (REC_UPDATE, 69, 1, *range(25)),
           (REC_UPDATE, 5, 2, *range(25))]
    was = reg.enabled
    reg.enable()
    try:
        nr.execute_mut_batch(ops, rid=0)
        nr.execute((REC_READ, 5), nr.register(1))
    finally:
        if not was:
            reg.disable()
    delta = {k: reg.counter(k).value - before[k] for k in names}
    assert delta == {"nr.rec.window_entries": 3, "nr.rec.window_rows": 2,
                     "nr.rec.reads": 1}
    if not was:
        nr.execute_mut_batch(ops, rid=1)  # off: nothing counted
        assert reg.counter("nr.rec.window_entries").value - before[names[0]] == 3


def tag_words(tag: int) -> list[int]:
    rest = np.random.default_rng(tag).integers(0, 2**31 - 1, FIELD_WORDS - 1)
    return [tag] + rest.tolist()


def test_served_zipf_multi_writer_matches_the_reference():
    """8 clients through ServeFrontend, zipfian keys over a few hot
    records, any client updating any key: every field of every read has
    an admissible origin, and the replicas end equal."""
    from node_replication_tpu.serve import ServeConfig, ServeFrontend

    n, clients, per = 256, 8, 40
    nr = NodeReplicated(seeded(n), n_replicas=R)
    ups, rds = [], []
    lock = threading.Lock()
    ranks = np.arange(1, n + 1, dtype=np.float64) ** -0.99
    cdf = np.cumsum(ranks) / ranks.sum()

    def client(c, fe):
        rng = np.random.default_rng([SEED, c])
        for i in range(per):
            k = int(np.searchsorted(cdf, rng.random()))
            t0 = time.perf_counter()
            if rng.random() < 0.5:
                f, tag = int(rng.integers(0, 10)), c << 20 | i
                resp = fe.submit((REC_UPDATE, k, f, *tag_words(tag)), rid=c % R).result(60)
                with lock:
                    ups.append((t0, time.perf_counter(), k, f, tag, resp))
            else:
                got = fe.read((REC_READ, k), rid=c % R)
                with lock:
                    rds.append((t0, time.perf_counter(), k, got))

    with ServeFrontend(nr, ServeConfig(queue_depth=64, batch_max_ops=8)) as fe:
        threads = [threading.Thread(target=client, args=(c, fe)) for c in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
    assert len(ups) + len(rds) == clients * per
    assert all(u[5] == 0 for u in ups)
    assert nr.replicas_equal()
    by_kf = {}
    for u in sorted(ups, key=lambda u: u[1]):
        by_kf.setdefault((u[2], u[3]), []).append(u)
    by_tag = {u[4]: u for u in ups}
    ref = RecordsReference(n, SEED)

    def admissible(k, f, v, t0, t1):
        us = by_kf.get((k, f), [])
        acked_before = [u for u in us if u[1] < t0]
        if np.array_equal(v, ref.field(k, f)):
            return not acked_before
        u = by_tag.get(int(v[0]))
        if u is None or (u[2], u[3]) != (k, f) or u[0] >= t1 or v.tolist() != tag_words(u[4]):
            return False
        return all(w[0] <= u[1] for w in acked_before)  # none sent after u's ack

    for t0, t1, k, got in rds:
        assert got.shape == (RECORD_WORDS,)
        v = got.reshape(10, FIELD_WORDS)
        for f in range(10):
            assert admissible(k, f, v[f], t0, t1), (k, f)
    final = as_fields(jax.tree.map(lambda a: a[0], nr.states))
    for (k, f) in by_kf:
        assert admissible(k, f, final[k, f], float("inf"), float("inf"))
    untouched = [k for k in range(n) if not any((k, f) in by_kf for f in range(10))]
    np.testing.assert_array_equal(final[untouched], ref.dense()[untouched])


class TestCompileAtSize:
    """2^21 records x 4 replicas, W = 256, lowered from shapes on the CPU:
    the round program aliases the donated table and keeps its
    temporaries small (no plane of the table's size), and the record
    read gathers one record from the stacked replicas."""

    N = 1 << 21

    def test_round_program_has_no_table_sized_temporary(self):
        d = make_records(self.N)
        spec = LogSpec(n_replicas=R, arg_width=d.arg_width)
        log_s = jax.eval_shape(lambda: log_init(spec))
        st_s = jax.eval_shape(lambda: replicate_state(d.init_state(), R))

        def exec_fn(log, states, window):
            return log_catchup_all(spec, d, log, states, window=window)

        c = jax.jit(exec_fn, static_argnames=("window",), donate_argnums=(0, 1)) \
            .lower(log_s, st_s, window=256).compile()
        m = c.memory_analysis()
        table = R * self.N * ROW_WORDS * 4
        assert m.temp_size_in_bytes < 16 * 2**20
        assert m.alias_size_in_bytes >= table

    def test_record_read_gathers_one_record(self):
        d = make_records(self.N)
        st_s = jax.eval_shape(lambda: replicate_state(d.init_state(), R))
        i32 = jax.ShapeDtypeStruct((), jnp.int32)
        c = jax.jit(d.read_stacked).lower(
            st_s, i32, i32, jax.ShapeDtypeStruct((ARG_WIDTH,), jnp.int32)).compile()
        m = c.memory_analysis()
        assert m.temp_size_in_bytes < 2**20
        assert m.output_size_in_bytes == RECORD_WORDS * 4


def test_hashmap_get_gathers_one_slot_at_size():
    """The served hashmap's size, 2^26 slots x 4 replicas, lowered from
    shapes: the get gathers one slot of the stacked planes, with no
    temporary of a replica's row (5 B a slot, 320 MB)."""
    d = make_hashmap(1 << 26)
    st_s = jax.eval_shape(lambda: replicate_state(d.init_state(), R))
    i32 = jax.ShapeDtypeStruct((), jnp.int32)
    c = jax.jit(d.read_stacked).lower(
        st_s, i32, i32, jax.ShapeDtypeStruct((d.arg_width,), jnp.int32)).compile()
    m = c.memory_analysis()
    assert m.temp_size_in_bytes < 2**20
    assert m.output_size_in_bytes == 4


def test_union_catchup_matches_the_scan_with_divergent_cursors():
    """The union-window tier with `window_merge_stacked`: replicas at
    different cursors take only the part of the window they lack, and
    one already at the tail takes nothing."""
    from node_replication_tpu.core.log import log_append, log_exec_all

    n = 64
    d = seeded(n)
    spec = LogSpec(n_replicas=R, arg_width=d.arg_width)
    opc, args = hot_window(np.random.default_rng(3), 40, n)
    log = log_append(spec, log_init(spec), jnp.asarray(opc), jnp.asarray(args), 40)
    states = replicate_state(d.init_state(), R)
    log, states, _ = log_catchup_all(spec, d, log, states, window=64,
                                     limits=jnp.asarray([0, 10, 25, 40]))
    assert np.asarray(log.ltails).tolist() == [0, 10, 25, 40]
    got_log, got, _ = log_catchup_all(spec, d, log, states, window=64)
    want_log, want, _ = log_exec_all(spec, d, log, states, window=64)
    np.testing.assert_array_equal(np.asarray(got_log.ltails), np.asarray(want_log.ltails))
    for leaf in ("fields", "present"):
        np.testing.assert_array_equal(np.asarray(got[leaf]), np.asarray(want[leaf]))
