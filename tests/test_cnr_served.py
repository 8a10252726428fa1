"""The CNR served path (`core/cnr.MultiLogReplicated` behind
`ServeFrontend`): one append program and one replay program a round
whatever logs it touches, reads combined by NR's read combiner and synced
on their mapped log only, and the hashmap's sparse `window_apply` the
replay runs. Small sizes: K=4,096 slots, 4 replicas, 1, 4 or 16 logs,
rings of 64 entries, so that they wrap."""

import random
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from node_replication_tpu import NodeReplicated
from node_replication_tpu.core.cnr import MultiLogReplicated
from node_replication_tpu.core.multilog import (
    MultiLogSpec,
    multilog_append,
    multilog_exec_window,
    multilog_init,
    partition_ops,
)
from node_replication_tpu.models import (
    HM_GET,
    HM_PUT,
    hashmap_log_mapper,
    make_hashmap,
    make_seqreg,
)
from node_replication_tpu.obs.metrics import get_registry
from node_replication_tpu.serve import ServeConfig, ServeFrontend

K = 4096
R = 4
SEED = 2**37 + 11


def cnr(nlogs, model=None, **kw):
    kw = {"n_replicas": R, "log_entries": 64, "gc_slack": 8,
          "exec_window": 16, **kw}
    return MultiLogReplicated(model or make_hashmap(K), hashmap_log_mapper,
                              nlogs=nlogs, **kw)


@pytest.fixture
def registry():
    r = get_registry()
    was = r.enabled
    r.enable()
    yield r
    r.enabled = was


def _hist(reg, name):
    h = reg.histogram(name)
    return h.count, h.sum


class _Counting:
    """A jitted program, counted at each call."""

    def __init__(self, fn):
        self.fn, self.calls = fn, 0

    def __call__(self, *a, **k):
        self.calls += 1
        return self.fn(*a, **k)


@pytest.mark.parametrize("nlogs", [1, 4, 16])
def test_threads_through_the_frontend_read_registers(nlogs):
    """40 threads, each the one writer of its keys, put and get through
    `ServeFrontend`: a get of a thread's own key answers its last put,
    a get of another's key one of that key's puts (or absent), and the
    final tables equal a dict's and `NodeReplicated`'s on the same
    puts."""
    ml = cnr(nlogs)
    T = 40
    puts = [[] for _ in range(T)]
    bad, errors = [], []
    with ServeFrontend(ml, ServeConfig(batch_max_ops=16,
                                       batch_linger_s=0.0)) as fe:
        def client(t):
            rng = random.Random(SEED + t)
            own = [t + T * j for j in range(4)]
            last = {}
            try:
                for i in range(24):
                    k = rng.choice(own)
                    if rng.random() < 0.6:
                        v = rng.randrange(1 << 30)
                        assert fe.call((HM_PUT, k, v), rid=t % R) == 0
                        puts[t].append((k, v))
                        last[k] = v
                    elif rng.random() < 0.5:
                        got = fe.read((HM_GET, k), rid=(t + i) % R)
                        if got != last.get(k, -1):
                            bad.append((t, k, got, last.get(k)))
                    else:
                        o = rng.randrange(T * 4)
                        got = fe.read((HM_GET, o), rid=(t + i) % R)
                        if got != -1 and got >= 1 << 30:
                            bad.append((t, o, got))
            except Exception as e:
                errors.append(e)

        threads = [threading.Thread(target=client, args=(t,))
                   for t in range(T)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(120)
    assert not errors and not bad, (errors[:3], bad[:3])
    ml.sync()
    assert ml.replicas_equal()
    want = {}
    for p in puts:
        want.update(dict(p))
    st = jax.tree.map(np.asarray, ml.states)
    for k, v in want.items():
        assert st["values"][0, k] == v and st["present"][0, k]
    assert int(st["present"][0].sum()) == len(want)
    nr = NodeReplicated(make_hashmap(K), n_replicas=R, log_entries=1024,
                        gc_slack=32)
    for t, p in enumerate(puts):
        if p:
            nr.execute_mut_batch([(HM_PUT, k, v) for k, v in p], t % R)
    nr.sync()
    for a, b in zip(jax.tree.leaves(nr.states), jax.tree.leaves(ml.states)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    tails = np.asarray(ml.ml.tail)
    assert tails.sum() == sum(len(p) for p in puts) + 0
    assert (tails > 64).any() or nlogs == 16  # the rings wrapped


@pytest.mark.parametrize("touched", [[2], [0, 3], [0, 1, 2, 3]])
def test_a_round_over_one_some_or_all_logs(touched, registry):
    """A batch routed to one, some or all of 4 logs: one append and one
    replay program, the touched logs' tails advance by their share, the
    answers come back in submission order."""
    ml = cnr(4, make_seqreg(8))
    ops = [(1, h, i + 1) for i in range(12) for h in touched]
    want, seen = [], {}
    for op in ops:
        want.append(seen.get(op[1], 0))
        seen[op[1]] = op[2]
    logs0 = _hist(registry, "cnr.round.logs")
    hold0 = _hist(registry, "nr.round.hold_s")
    app, exe = _Counting(ml._append_jit), _Counting(ml._exec_jit)
    ml._append_jit, ml._exec_jit = app, exe
    assert ml.execute_mut_batch(ops, rid=1) == want
    assert (app.calls, exe.calls) == (1, 1)
    assert _hist(registry, "nr.round.hold_s")[0] - hold0[0] == 1  # NR's phase
    tails = np.asarray(ml.ml.tail)
    assert [int(tails[h]) for h in range(4)] == \
        [12 if h in touched else 0 for h in range(4)]
    c, s = _hist(registry, "cnr.round.logs")
    assert (c - logs0[0], s - logs0[1]) == (1, len(touched))
    assert ml.stats()["combine_rounds"] == [int(h in touched) for h in range(4)]


def test_one_replay_program_serves_every_log(registry):
    """Rounds over 16 logs, each touching a different set: one compiled
    append program per width and ONE replay program, one call of each
    a round, one `cnr.exec.rounds` a replay."""
    ml = cnr(16, exec_window=64, log_entries=256)
    rounds0 = registry.counter("cnr.exec.rounds").value
    rng = np.random.default_rng(SEED)
    exe = _Counting(ml._exec_jit)
    ml._exec_jit = exe
    for n in (1, 5, 16, 40, 3):
        ks = rng.integers(0, K, n)
        ml.execute_mut_batch([(HM_PUT, int(k), int(k) + 1) for k in ks],
                             rid=n % R)
    assert exe.calls == 5
    assert registry.counter("cnr.exec.rounds").value - rounds0 == 5
    assert exe.fn._cache_size() == 1
    assert ml._exec_jit.fn.__name__ == "cnr_exec"
    assert ml._append_jit.__name__ == "cnr_append"
    text = exe.fn.lower(ml.ml, ml.states, window=64).as_text()
    assert "jit_cnr_exec" in text


def _one_hold(ml, calls):
    """`calls` (thunks that read through `execute`) on threads of their
    own, all posted while the test holds the combiner lock, so that one
    combiner answers them."""
    out = [None] * len(calls)

    def run(i):
        out[i] = calls[i]()

    threads = [threading.Thread(target=run, args=(i,)) for i in range(len(calls))]
    with ml._lock:
        for t in threads:
            t.start()
        deadline = time.monotonic() + 60
        while len(ml._read_posted) < len(calls):
            assert time.monotonic() < deadline
            time.sleep(0.002)
    for t in threads:
        t.join(60)
    assert not ml._read_posted and not ml._read_combining
    return out


def _single(ml, rid, op):
    args = np.zeros((ml.spec.arg_width,), np.int32)
    args[: len(op) - 1] = op[1:]
    return int(ml._read_stacked_jit(ml.states, np.int32(rid),
                                    np.int32(op[0]), args))


def test_combined_reads_answer_as_lone_reads(registry):
    ml = cnr(4)
    toks = [ml.register(r) for r in range(R)]
    ml.execute_mut_batch([(HM_PUT, k, 10 * k) for k in range(0, 64, 3)], 0)
    ml.execute((HM_GET, 0), toks[0])  # compiles the read programs
    keys = list(range(-5, 70))
    b0 = _hist(registry, "nr.read.batch_size")
    h0 = _hist(registry, "nr.read.hold_s")
    got = _one_hold(ml, [lambda k=k: ml.execute((HM_GET, k), toks[k % R])
                         for k in keys])
    assert got == [_single(ml, k % R, (HM_GET, k)) for k in keys]
    assert got[5] == 0 and got[6] == -1  # key 0 put, key 1 never
    c, s = _hist(registry, "nr.read.batch_size")
    assert (c - b0[0], s - b0[1]) == (1, float(len(keys)))
    assert _hist(registry, "nr.read.hold_s")[0] - h0[0] == 1


def test_a_replica_rolled_back_on_one_log_syncs_that_log():
    """Replica 1 is rolled back one round on log 2 only, state and
    cursor: a combined batch of reads replays it before it answers, and
    the reads of the other logs answer as they stand."""
    ml = cnr(4)
    toks = [ml.register(r) for r in range(R)]
    ml.execute_mut_batch([(HM_PUT, 6, 60), (HM_PUT, 5, 50)], 0)
    old = jax.tree.map(jnp.copy, ml.states)
    old_lt = np.asarray(ml.ml.ltails).copy()
    ml.execute_mut_batch([(HM_PUT, 6, 61), (HM_PUT, 7, 71)], 0)
    st = jax.tree.map(lambda a: a, ml.states)
    ml.states = {"values": st["values"].at[1, 6].set(old["values"][1, 6]),
                 "present": st["present"]}
    lt = np.asarray(ml.ml.ltails).copy()
    lt[2, 1] = old_lt[2, 1]
    ml.ml = ml.ml._replace(ltails=jnp.asarray(lt))
    assert int(np.asarray(ml.states["values"])[1, 6]) == 60
    got = _one_hold(ml, [lambda r=r, k=k: ml.execute((HM_GET, k), toks[r])
                         for k in (6, 7, 5) for r in range(R)])
    assert got == [61] * R + [71] * R + [50] * R
    lt, ctail = np.asarray(ml.ml.ltails), np.asarray(ml.ml.ctail)
    assert lt[2, 1] == ctail[2]


def test_a_lone_read_syncs_only_its_mapped_log():
    ml = cnr(4)
    t = ml.register(3)
    ml.execute_mut_batch([(HM_PUT, 1, 11)], 0)
    lt = np.asarray(ml.ml.ltails).copy()
    lt[1, 3] -= 1
    ml.ml = ml.ml._replace(ltails=jnp.asarray(lt))
    with ml._lock:  # a caller that holds the lock reads alone
        assert ml.execute((HM_GET, 1), t) == 11
    assert np.asarray(ml.ml.ltails)[1, 3] == 1


def test_hashmap_window_apply_stacked_is_each_replicas_fold():
    """`window_apply_stacked` against each replica's own fold of the
    entries it applies, on windows of puts, removes, NOOPs and unknown
    opcodes with repeated keys, replicas of different tables and random
    masks of applied entries."""
    n, R_ = 64, 4
    d = make_hashmap(n)
    rng = np.random.default_rng(SEED)
    for w in (1, 7, 64, 200):
        values = rng.integers(0, 100, (R_, n)).astype(np.int32)
        present = rng.random((R_, n)) < 0.5
        opc = rng.choice([0, 1, 1, 2, 2, 9], w).astype(np.int32)
        args = np.stack([rng.integers(-n, 2 * n, w), rng.integers(0, 1000, w),
                         np.zeros(w, int)], 1).astype(np.int32)
        applies = rng.random((w, R_)) < 0.7
        st, resps = d.window_apply_stacked(
            {"values": jnp.asarray(values), "present": jnp.asarray(present)},
            jnp.asarray(opc), jnp.asarray(args), jnp.asarray(applies))
        want = np.zeros((w, R_), np.int32)
        for r in range(R_):
            for i in range(w):
                k = int(args[i, 0]) % n
                if not applies[i, r]:
                    continue
                if opc[i] == HM_PUT:
                    values[r, k], present[r, k] = args[i, 1], True
                elif opc[i] == 2:
                    want[i, r] = int(present[r, k])
                    values[r, k], present[r, k] = 0, False
        np.testing.assert_array_equal(np.asarray(st["values"]), values)
        np.testing.assert_array_equal(np.asarray(st["present"]), present)
        np.testing.assert_array_equal(np.asarray(resps), want)


@pytest.mark.parametrize("engine", ["stacked", "window_apply", "scan"])
def test_exec_window_replays_each_replica_from_its_cursors(engine):
    """`multilog_exec_window` (one program, every log, replicas at
    different positions on each log) against a plain fold: replica r
    applies log l's entries from its cursor up to the laggard's cursor
    plus the window (or the tail), log after log, and answers each from
    its own cursor."""
    import dataclasses

    L, W, R_, n = 4, 8, 3, 32
    d = make_hashmap(n)
    if engine != "stacked":
        d = dataclasses.replace(d, window_apply_stacked=None)
    if engine == "scan":
        d = dataclasses.replace(d, window_apply=None)
    spec = MultiLogSpec(nlogs=L, capacity=64, n_replicas=R_, gc_slack=8)
    ml = multilog_init(spec)
    rng = np.random.default_rng(SEED + 1)
    ops = [(int(rng.choice([1, 1, 2, 7])), (int(k), int(v)))
           for k, v in zip(rng.integers(0, 2 * n, 60), rng.integers(0, 99, 60))]
    opc, args, counts, _ = partition_ops(hashmap_log_mapper, L, ops, 3)
    ml = multilog_append(spec, ml, opc, args, counts)
    tail = np.asarray(ml.tail)
    lt = np.minimum(rng.integers(0, 6, (L, R_)), tail[:, None])
    ml = ml._replace(ltails=jnp.asarray(lt, jnp.int64))
    values = rng.integers(0, 99, (R_, n)).astype(np.int32)
    present = rng.random((R_, n)) < 0.5
    states = {"values": jnp.asarray(values), "present": jnp.asarray(present)}
    got_ml, got_st, got_r = multilog_exec_window(spec, d, ml, states, W)
    want_r = np.zeros((L, R_, W), np.int32)
    want_lt = lt.copy()
    ring_o, ring_a = np.asarray(ml.opcodes), np.asarray(ml.args)
    for r in range(R_):
        for h in range(L):
            end = max(lt[h, r], min(lt[h].min() + W, tail[h]))
            for p in range(lt[h, r], end):
                o, (k, v, _) = ring_o[h, p % 64], ring_a[h, p % 64]
                k %= n
                if o == HM_PUT:
                    values[r, k], present[r, k] = v, True
                elif o == 2:
                    want_r[h, r, p - lt[h, r]] = int(present[r, k])
                    values[r, k], present[r, k] = 0, False
            want_lt[h, r] = end
    np.testing.assert_array_equal(np.asarray(got_st["values"]), values)
    np.testing.assert_array_equal(np.asarray(got_st["present"]), present)
    np.testing.assert_array_equal(np.asarray(got_r), want_r)
    np.testing.assert_array_equal(np.asarray(got_ml.ltails), want_lt)
    np.testing.assert_array_equal(np.asarray(got_ml.head), want_lt.min(axis=1))


def test_scan_engine_never_runs_the_window_forms():
    """engine='scan' replays the hashmap by the scan of `apply_write`: a
    round traces neither `window_apply_stacked` nor `window_apply`, answers
    as the default engine does, and reports its tier as the scan."""
    import dataclasses

    def tripped(*a, **k):
        raise AssertionError("engine='scan' ran a window form")

    wired = dataclasses.replace(make_hashmap(K), window_apply=tripped,
                                window_apply_stacked=tripped)
    scan, auto = cnr(4, wired, engine="scan"), cnr(4)
    assert scan.engine == "scan" and auto.engine == "combined"
    rng = np.random.default_rng(SEED + 2)
    for rid in range(R):
        ops = [(HM_PUT, int(k), int(v)) for k, v in
               zip(rng.integers(0, K, 24), rng.integers(0, 99, 24))]
        assert scan.execute_mut_batch(ops, rid) == \
            auto.execute_mut_batch(ops, rid)
        assert scan.round_tier(rid) == "scan"
    scan.sync()
    auto.sync()
    for name in ("values", "present"):
        np.testing.assert_array_equal(np.asarray(scan.states[name]),
                                      np.asarray(auto.states[name]))
    with pytest.raises(AssertionError, match="window form"):
        cnr(4, wired).execute_mut_batch([(HM_PUT, 1, 2)], rid=0)
