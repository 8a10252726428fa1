"""The CNR cell and the skewed hashmap cell rehearsed on the CPU at tiny
sizes; three faults planted in the CNR system, each of which must make
the run's `correct` false; the CNR readers on synthetic contexts; and
`roofline_cnr`'s byte count by hand."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run as bench
from benchmark.common import load_json, load_module
from benchmark.roofline import roofline_pct
from benchmark.roofline_cnr import program_s, round_bytes
from benchmark.tests.tiny import ROOT, TINY_SERVED

CNR = "cnr-hashmap-67m-r4-l16.closed192-wr80-cnr"
ZIPF = "hashmap-67m-r4.closed192-wr80-zipf"
SEED = 2**36 + 123
METRICS = os.path.join(ROOT, "benchmark", "metrics")


def run(root, cell, trace=False, hooks=None, seconds=1.2):
    return bench.run_cell(cell, SEED, seconds, trace, root=root,
                          overrides=TINY_SERVED, hooks=hooks)


@pytest.mark.parametrize("cell", [CNR, ZIPF])
@pytest.mark.parametrize("trace", [False, True])
def test_new_cell_is_correct(bench_root, cell, trace):
    res = run(bench_root, cell, trace)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert ("logs_off" in res["checks"]) == (cell == CNR)
    spec = load_json(os.path.join(bench_root, "BENCHMARK.json"))
    if not trace:
        assert set(res["metrics"]) == {"ops_per_s", "setup_s"}
    else:
        for name in res["metrics"]:
            m = next(m for m in spec["per_layer"] if m["name"] == name)
            assert cell in m["workloads"]
        # every metric listed for the cell reads something, bar the
        # device trace's, which the CPU does not give
        listed = {m["name"] for m in spec["per_layer"]
                  if cell in m.get("workloads", [])
                  and m["source"] != "device_trace"}
        assert listed <= set(res["metrics"]), listed - set(res["metrics"])
        for name in ("read_p99_ms", "read_hold_us", "read_wait_ms",
                     "fe.queue_wait_ms"):
            assert name in listed
        assert res["metrics"]["compiles_in_window"]["value"] == 0
        if cell == CNR:
            assert res["metrics"]["cnr.logs_per_round"]["value"] >= 1


def _cnr_system(patch):
    drv = load_module(os.path.join(ROOT, "benchmark", "drivers", "served_closed_cnr.py"),
                      "scc_faults")

    def make(cfg, seed):
        sys_ = drv.CnrSystem(cfg, seed)
        patch(sys_.nr)
        return sys_
    return {"system": make}


def wrong_log(nr):
    """The append writes each round's first op into the next log's ring,
    past the tail that log counts, and leaves a NOOP where its own log
    counts it: the op goes to a log the round does not count it in, and
    the acknowledged put is never applied."""
    from node_replication_tpu.ops.encoding import NOOP

    orig = nr._append_jit
    mask = nr.spec.capacity - 1

    def append(ml, opcodes, args, counts):
        tail = np.asarray(ml.tail)
        h = int(np.flatnonzero(np.asarray(counts))[0])
        g = (h + 1) % nr.nlogs
        at = int(tail[g] + np.asarray(counts)[g]) & mask
        op, a = np.asarray(opcodes)[h, 0], np.asarray(args)[h, 0]
        ml = orig(ml, opcodes, args, counts)
        return ml._replace(
            opcodes=ml.opcodes.at[h, int(tail[h]) & mask].set(NOOP).at[g, at].set(op),
            args=ml.args.at[g, at].set(a))
    nr._append_jit = append


def read_skips_sync(nr):
    """Reads skip their mapped log's sync, on replicas that replay only
    in their own rounds (as upstream's do): a get is served from its
    replica's state as its own last round left it. Skipping the sync
    alone changes nothing here, since every round replays every log
    into every replica."""
    seen = {rid: jax.tree.map(jnp.copy, nr.states) for rid in range(nr.n_replicas)}
    orig = nr.execute_mut_batch

    def batch(ops, rid=0):
        with nr._lock:  # the next round donates the states' buffers
            out = orig(ops, rid)
            seen[rid] = jax.tree.map(jnp.copy, nr.states)
        return out

    def stale(op, token):
        args = np.zeros((nr.spec.arg_width,), np.int32)
        args[: len(op) - 1] = op[1:]
        with nr._lock:
            return int(nr._read_jit(seen[token.rid], jnp.int32(token.rid),
                                    jnp.int32(op[0]), jnp.asarray(args)))
    nr.execute_mut_batch = batch
    nr.execute = stale


def replica_skipped(nr):
    """Every replay leaves replica 2's state as it was, and moves its
    cursors on."""
    orig = nr._exec_jit

    def run_(ml, states, window):
        keep = jax.tree.map(lambda a: jnp.copy(a[2]), states)
        ml, states, resps = orig(ml, states, window=window)
        return ml, jax.tree.map(lambda a, k: a.at[2].set(k), states, keep), resps
    nr._exec_jit = run_


@pytest.mark.parametrize("fault", [wrong_log, read_skips_sync, replica_skipped],
                         ids=lambda f: f.__name__)
def test_cnr_fault_is_caught(bench_root, fault):
    res = run(bench_root, CNR, hooks=_cnr_system(fault))
    assert not res["correct"], res["checks"]


def reader(name):
    return load_module(os.path.join(METRICS, name + ".py"), "cnr_" + name)


TRACE = {"busy_s": 0.5, "ops": [("jit_cnr_exec:fusion.2", 0.004),
                                ("jit_cnr_append:scatter", 0.001),
                                ("jit_read_stacked:fusion", 0.3)]}
PARENT_TRACE = {"busy_s": 0.5, "ops": [("jit_log_exec:fusion", 0.4),
                                       ("jit_read_stacked:fusion", 0.1)]}


def test_logs_per_round():
    r = reader("cnr.logs_per_round")
    assert r.read({"reg": {"cnr.round.logs": {"count": 10, "sum": 140.0}}}) == 14.0
    assert r.read({"reg": {"cnr.round.logs": {"count": 0, "sum": 0.0}}}) is None
    assert r.read({"reg": {"nr.read.batch_size": {"count": 3, "sum": 9.0}}}) is None
    assert r.read({}) is None


def test_replay_device_ms():
    r = reader("cnr.replay_device_ms")
    ctx = {"trace": TRACE, "reg_traced": {"cnr.exec.rounds": 5}}
    assert r.read(ctx) == pytest.approx(1.0)
    assert r.read({**ctx, "trace": None}) is None
    assert r.read({**ctx, "reg_traced": {}}) is None
    assert r.read({"trace": PARENT_TRACE, "reg_traced": {"nr.exec.rounds": 9}}) is None
    assert r.read({}) is None


def test_replay_roofline():
    r = reader("cnr.replay_roofline")
    ctx = {"trace": TRACE, "work_traced": {"puts": 100, "gets": 40, "replicas": 4},
           "peaks": {"hbm_bytes_per_s": 819e9}}
    want = 100.0 * (round_bytes(100, 100, 4, 3) / 819e9) / 0.005
    assert r.read(ctx) == pytest.approx(want)
    assert r.read({**ctx, "trace": PARENT_TRACE}) is None
    assert r.read({**ctx, "trace": None}) is None
    assert r.read({**ctx, "work_traced": {"puts": 0, "gets": 5, "replicas": 4}}) is None
    assert r.read({}) is None


def test_round_bytes_by_hand():
    # 3 puts to 2 distinct slots on 4 replicas, a log entry of an opcode and 3 args
    got = round_bytes(puts=3, distinct=2, n_replicas=4, arg_width=3)
    want = (4 * 2 * 5        # each replica's copy of each written slot
            + 3 * 4          # one int32 response a put
            + 3 * (1 + 3) * 4)  # one log entry a put
    assert got == want == 100
    assert program_s(TRACE) == pytest.approx(0.005)
    assert roofline_pct(round_bytes(3, 2, 4, 3), 1e-6, 819e9) < 100
