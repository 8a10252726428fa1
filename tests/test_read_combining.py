"""Read combining (`NodeReplicated.execute`): reads that arrive while the
combiner lock is busy are answered together, under one hold, by one
batched `read_stacked` program per 256 reads.

Concurrent readers against a writer thread's rounds, each answer checked
as a single-writer register read; batches of set sizes answered as lone
reads are; a fenced replica's read and an error in the batch; and the
models without `read_stacked`, which read one at a time."""

import os
import random
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from node_replication_tpu import NodeReplicated
from node_replication_tpu.core.replica import ReplicaFencedError
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
from node_replication_tpu.models.records import FIELD_WORDS
from node_replication_tpu.obs.metrics import get_registry

R = 4
K = 64
SEED = 2**41 + 29
HISTS = ("nr.read.batch_size", "nr.read.lock_wait_s", "nr.read.hold_s")

# model: (dispatch, put(k, v), get(k), the register's words in an answer)
MODELS = {
    "hashmap": (lambda: make_hashmap(K), lambda k, v: (HM_PUT, k, v),
                lambda k: (HM_GET, k), lambda got: [got]),
    "records": (lambda: make_records(K),
                lambda k, v: (REC_UPDATE, k, 0, *[v] * FIELD_WORDS),
                lambda k: (REC_READ, k), lambda got: got[:FIELD_WORDS].tolist()),
}


@pytest.fixture
def registry():
    if os.environ.get("NR_TPU_TRACE"):
        pytest.skip("tracer force-enabled via NR_TPU_TRACE")
    r = get_registry()
    was = r.enabled
    r.enable()
    yield r
    r.enabled = was


def _hists(reg):
    return {n: (reg.histogram(n).count, reg.histogram(n).sum) for n in HISTS}


def _delta(reg, before, name):
    c0, s0 = before[name]
    h = reg.histogram(name)
    return h.count - c0, h.sum - s0


def _stacked_hashmap() -> NodeReplicated:
    """A hashmap fleet whose replicas hold different values and
    presence, so that an answer read from the wrong replica shows."""
    nr = NodeReplicated(make_hashmap(K), n_replicas=R)
    rng = np.random.default_rng(SEED)
    slots = np.arange(K)[None, :] + np.arange(R)[:, None]
    nr.states = {"values": jnp.asarray(rng.integers(0, 2**31, (R, K)), jnp.int32),
                 "present": jnp.asarray(slots % 3 != 0)}
    return nr


def _one_hold(nr, calls, under_lock=None, timeout=60.0):
    """Run `calls` (thunks that each read through `execute`) on threads
    of their own while the test holds the combiner lock, until all of
    them are posted; run `under_lock`, then release the lock, so that
    one combiner answers them all. Returns each call's answer or
    exception, in order."""
    out = [None] * len(calls)

    def run(i):
        try:
            out[i] = calls[i]()
        except Exception as e:
            out[i] = e

    threads = [threading.Thread(target=run, args=(i,), daemon=True)
               for i in range(len(calls))]
    with nr._lock:
        for t in threads:
            t.start()
        deadline = time.monotonic() + timeout
        while len(nr._read_posted) < len(calls):
            assert time.monotonic() < deadline, len(nr._read_posted)
            time.sleep(0.002)
        if under_lock is not None:
            under_lock()
    for t in threads:
        t.join(timeout)
    assert not any(t.is_alive() for t in threads)
    assert not nr._read_posted and not nr._read_combining
    return out


def _single(nr, rid, op):
    """The single-read program's answer (`_read_stacked_jit`)."""
    args = np.zeros((nr.spec.arg_width,), np.int32)
    args[: len(op) - 1] = op[1:]
    return int(nr._read_stacked_jit(nr.states, np.int32(rid), np.int32(op[0]), args))


@pytest.mark.parametrize("model", sorted(MODELS))
def test_concurrent_reads_are_register_reads_against_a_writer(model, registry):
    """48 readers and one writer thread whose rounds put an increasing
    value into every key: a read of key k answers a value no older than
    the last put acknowledged before it was sent and no newer than the
    last put sent before it returned, whole, not torn."""
    make, put, get, words = MODELS[model]
    nr = NodeReplicated(make(), n_replicas=R, log_entries=1024, gc_slack=64)
    keys = list(range(8))
    nr.execute_mut_batch([put(k, 0) for k in keys], 0)
    toks = [nr.register(r) for r in range(R)]
    nr.execute(get(0), toks[0])  # compiles the read programs
    sent = {k: 0 for k in keys}
    acked = dict(sent)
    stop = threading.Event()
    bad, errors = [], []

    def writer():
        v = 0
        while not stop.is_set():
            v += 1
            for k in keys:
                sent[k] = v
            nr.execute_mut_batch([put(k, v) for k in keys], v % R)
            for k in keys:
                acked[k] = v

    def reader(c):
        rng = random.Random(SEED + c)
        try:
            for _ in range(40):
                k = rng.choice(keys)
                lo = acked[k]
                got = words(nr.execute(get(k), toks[c % R]))
                hi = sent[k]
                if len(set(got)) != 1 or not lo <= got[0] <= hi:
                    bad.append((k, lo, got, hi))
        except Exception as e:
            errors.append(e)

    before = _hists(registry)
    w = threading.Thread(target=writer, daemon=True)
    readers = [threading.Thread(target=reader, args=(c,), daemon=True)
               for c in range(48)]
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-4)  # interleave the threads more finely
    try:
        w.start()
        for t in readers:
            t.start()
        for t in readers:
            t.join(120)
    finally:
        stop.set()
        sys.setswitchinterval(switch)
    w.join(60)
    assert not w.is_alive() and not any(t.is_alive() for t in readers)
    assert not errors and not bad, (errors, bad[:5])
    assert max(acked.values()) > 0  # the writer's rounds ran among the reads
    n, total = _delta(registry, before, "nr.read.batch_size")
    assert total == 48 * 40
    assert total > n  # some program answered more than one read
    assert _delta(registry, before, "nr.read.hold_s")[0] == n


def test_a_lone_read_records_one_wait_and_one_hold(registry):
    nr = _stacked_hashmap()
    tok = nr.register(1)
    nr.execute((HM_GET, 3), tok)
    before = _hists(registry)
    assert nr.execute((HM_GET, 3), tok) == _single(nr, 1, (HM_GET, 3))
    assert _delta(registry, before, "nr.read.lock_wait_s") == (1, 0.0)
    assert _delta(registry, before, "nr.read.hold_s")[0] == 1
    assert _delta(registry, before, "nr.read.batch_size") == (1, 1.0)


@pytest.mark.parametrize("n", [1, 2, 255, 256, 257])
def test_one_hold_answers_a_batch_as_lone_reads_do(n, registry):
    """n reads with mixed replicas, keys and opcodes posted behind the
    lock: one hold answers them, one program per 256 reads, each
    answer the single-read program's."""
    nr = _stacked_hashmap()
    toks = [nr.register(r) for r in range(R)]
    rng = np.random.default_rng([SEED, n])
    ops = [(int(rng.choice([HM_GET, HM_GET, HM_GET, 0, 7])),
            int(rng.integers(-2 * K, 3 * K))) for _ in range(n)]
    rids = [int(r) for r in rng.integers(0, R, n)]
    nr.execute((HM_GET, 0), toks[0])  # compiles the read programs
    before = _hists(registry)
    got = _one_hold(nr, [lambda op=op, r=r: nr.execute(op, toks[r])
                         for op, r in zip(ops, rids)])
    want = [_single(nr, r, op) for op, r in zip(ops, rids)]
    assert all(type(g) is int for g in got) and got == want
    assert len(set(want)) > (n > 8)  # the reads tell replicas apart
    assert _delta(registry, before, "nr.read.hold_s")[0] == 1
    assert _delta(registry, before, "nr.read.batch_size") == (-(-n // 256), float(n))


def test_lagging_replicas_are_replayed_before_the_batch_is_answered():
    """Replicas 1 and 3 are rolled back one round, state and cursor (as
    a replica that replays only in its own rounds would stand): one
    hold replays them to the completed tail before it answers."""
    nr = NodeReplicated(make_hashmap(K), n_replicas=R)
    toks = [nr.register(r) for r in range(R)]
    nr.execute_mut_batch([(HM_PUT, 5, 50)], 0)
    nr.execute((HM_GET, 5), toks[0])
    old, old_lt = jax.tree.map(jnp.copy, nr.states), np.asarray(nr.log.ltails).copy()
    nr.execute_mut_batch([(HM_PUT, 5, 51), (HM_PUT, 6, 61)], 0)
    lag = np.array([1, 3])
    nr.states = jax.tree.map(lambda a, b: a.at[lag].set(b[lag]), nr.states, old)
    lt = np.asarray(nr.log.ltails).copy()
    lt[lag] = old_lt[lag]
    nr.log = nr.log._replace(ltails=jnp.asarray(lt))
    got = _one_hold(nr, [lambda r=r, k=k: nr.execute((HM_GET, k), toks[r])
                         for k in (5, 6) for r in range(R)])
    assert got == [51] * R + [61] * R
    assert (np.asarray(nr.log.ltails) == int(nr.log.ctail)).all()


def test_a_batch_of_record_reads_answers_whole_records():
    nr = NodeReplicated(make_records(K), n_replicas=R)
    toks = [nr.register(r) for r in range(R)]
    for k in range(0, K, 3):
        nr.execute_mut((REC_UPDATE, k, k % 10, *range(k, k + FIELD_WORDS)), toks[k % R])
    lone = {k: nr.execute((REC_READ, k), toks[k % R]) for k in range(K)}
    got = _one_hold(nr, [lambda k=k: nr.execute((REC_READ, k), toks[(k + 1) % R])
                         for k in range(K)])
    for k in range(K):
        assert isinstance(got[k], np.ndarray) and got[k].dtype == np.int32
        np.testing.assert_array_equal(got[k], lone[k])


def test_a_fenced_replica_fails_only_its_own_reads():
    """Replica 2 is fenced after its reads were posted: they raise, and
    the other reads of the batch are answered."""
    nr = _stacked_hashmap()
    toks = [nr.register(r) for r in range(R)]
    nr.execute((HM_GET, 0), toks[0])
    rids = list(range(R)) * 2
    got = _one_hold(nr, [lambda r=r: nr.execute((HM_GET, 5), toks[r]) for r in rids],
                    under_lock=lambda: nr.fence_replica(2))
    for r, g in zip(rids, got):
        if r == 2:
            assert isinstance(g, ReplicaFencedError) and g.rid == 2
        else:
            assert g == _single(nr, r, (HM_GET, 5)), (r, g)
    with pytest.raises(ReplicaFencedError):
        nr.execute((HM_GET, 5), toks[2])
    assert nr.execute((HM_GET, 5), toks[3]) == _single(nr, 3, (HM_GET, 5))


def test_an_error_in_the_batch_reaches_every_reader_and_passes():
    nr = _stacked_hashmap()
    toks = [nr.register(r) for r in range(R)]
    nr.execute((HM_GET, 0), toks[0])
    orig = nr._read_jit

    def broken(*a):
        raise RuntimeError("broken read program")

    nr._read_jit = broken
    got = _one_hold(nr, [lambda r=r: nr.execute((HM_GET, r), toks[r % R])
                         for r in range(12)])
    assert all(isinstance(g, RuntimeError) and "broken" in str(g) for g in got)
    nr._read_jit = orig
    assert nr.execute((HM_GET, 9), toks[1]) == _single(nr, 1, (HM_GET, 9))


def test_a_model_without_read_stacked_reads_one_at_a_time(registry):
    """The stack has no `read_stacked`: each read holds the lock alone,
    through `_read_one`, and no batched program is built."""
    nr = NodeReplicated(make_stack(16), n_replicas=R)
    toks = [nr.register(r) for r in range(R)]
    nr.execute_mut((ST_PUSH, 42), toks[0])
    assert nr.execute((ST_PEEK,), toks[1]) == 42
    before = _hists(registry)
    got = []
    threads = [threading.Thread(target=lambda r=r: got.append(
        nr.execute((ST_PEEK,), toks[r % R]))) for r in range(16)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    assert got == [42] * 16
    assert _delta(registry, before, "nr.read.hold_s")[0] == 16
    assert _delta(registry, before, "nr.read.batch_size") == (0, 0.0)
    assert nr._read_batch is None and not hasattr(nr, "_read_stacked_jit")


def test_a_grown_fleet_reads_through_a_rebuilt_program():
    nr = NodeReplicated(make_hashmap(K), n_replicas=2)
    t0 = nr.register(0)
    nr.execute_mut((HM_PUT, 7, 70), t0)
    assert nr.execute((HM_GET, 7), t0) == 70
    (new,) = nr.grow_fleet(1)
    tn = nr.register(new)
    nr.execute_mut((HM_PUT, 8, 80), t0)
    got = _one_hold(nr, [lambda: nr.execute((HM_GET, 7), tn),
                         lambda: nr.execute((HM_GET, 8), tn),
                         lambda: nr.execute((HM_GET, 8), t0)])
    assert got == [70, 80, 80]
