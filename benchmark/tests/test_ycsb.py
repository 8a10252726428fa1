"""The YCSB-A cell at a tiny size on the CPU: its driver through the check,
the control (which must come out not correct), faults planted under the
timed path (each must make `correct` false), and the readers of the
record store's metrics on synthetic traces and registry deltas."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import control_ycsb
from benchmark import run as bench
from benchmark.common import load_json, load_module
from benchmark.references.records import FIELD_WORDS, RECORD_WORDS, prefill_words
from benchmark.roofline_records import ENTRY_BYTES, READ_BYTES, read_bytes, round_bytes
from benchmark.tests.tiny import ROOT

CELL = "ycsb-a-1kb-2m-r4.closed192-rw50-zipf99"
TINY = {"config": {"n_records": 4096},
        "traffic": {"clients": 16, "trace_skip_s": 0.3, "trace_s": 0.6}}
SEED = 2**40 + 2531
METRICS = os.path.join(ROOT, "benchmark", "metrics")


def run(root, trace=False, seconds=1.5, hooks=None, seed=SEED):
    return bench.run_cell(CELL, seed, seconds, trace, root=root, overrides=TINY,
                          hooks=hooks)


@pytest.mark.parametrize("trace", [False, True])
def test_cell_is_correct(bench_root, trace):
    res = run(bench_root, trace)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert all(c["value"] == 0 for c in res["checks"].values())
    spec = load_json(os.path.join(bench_root, "BENCHMARK.json"))
    if not trace:
        assert set(res["metrics"]) == {"ops_per_s", "setup_s"}
    else:  # no device trace on the CPU: only the registry's and the clock's readers answer
        assert res["metrics"]["rec.rows_per_round"]["value"] > 0
        for name in res["metrics"]:
            m = next(m for m in spec["per_layer"] if m["name"] == name)
            assert m["source"] in ("program_counter", "host_clock")


def test_control_is_not_correct(bench_root):
    _, _, _, traffic = bench.load_cell(bench_root, CELL)
    res = run(bench_root, seconds=1.0, hooks=control_ycsb.hooks_for(traffic),
              seed=2**35 + 3)
    assert not res["correct"]
    over = {k for k, c in res["checks"].items() if c["value"] > c["limit"]}
    assert {"wrong_reads", "replicas_off", "touched_off"} <= over, res["checks"]


def test_parent_without_the_model_fails_at_once(bench_root, monkeypatch):
    """A program without the record store cannot import the driver: the
    run raises before any set-up, and does not hang."""
    import node_replication_tpu.models as models

    monkeypatch.delattr(models, "REC_UPDATE")
    with pytest.raises(ImportError):
        run(bench_root)


def _system(patch):
    drv = load_module(os.path.join(ROOT, "benchmark", "drivers", "ycsb_closed.py"),
                      "ycsb_faults")

    def make(cfg, seed):
        sys_ = drv.RecordSystem(cfg, seed)
        patch(sys_, seed)
        return sys_
    return {"system": make}


def stale_field(sys_, seed):
    """Reads answer field 3 of every record as the load phase left it."""
    orig = sys_.read

    def read(k, rid):
        got = np.array(orig(k, rid))
        got[75:100] = prefill_words([k], seed)[0, 3]
        return got
    sys_.read = read


def torn_field(sys_, seed):
    """Reads answer the second half of each field's words from the load
    phase: a field half written."""
    orig = sys_.read

    def read(k, rid):
        got = np.array(orig(k, rid)).reshape(10, FIELD_WORDS)
        got[:, 13:] = prefill_words([k], seed)[0, :, 13:]
        return got.reshape(RECORD_WORDS)
    sys_.read = read


def replica_misses_a_round(sys_, seed):
    """The last replica keeps its state through the fifth round that
    carries updates: it misses every update of that round."""
    nr = sys_.nr
    orig = nr._exec_jit
    rounds = []

    def exec_(log, states, window):
        keep = jax.tree.map(jnp.copy, states)
        log, new, resps = orig(log, states, window=window)
        rounds.append(1)
        if len(rounds) == 5 + 7:  # after the 7 warm-up rounds
            new = jax.tree.map(lambda a, b: a.at[-1].set(b[-1]), new, keep)
        return log, new, resps
    nr._exec_jit = exec_


def read_without_sync(sys_, seed):
    """Reads skip the read sync, on replicas that replay only in their
    own rounds (as upstream's do): a read is served from its replica's
    state as its own last round left it."""
    nr = sys_.nr
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
            return np.asarray(nr._read_stacked_jit(
                seen[token.rid], jnp.int32(token.rid), jnp.int32(op[0]), jnp.asarray(args)))
    nr.execute_mut_batch = batch
    nr.execute = stale


FAULTS = {"stale_field": stale_field, "torn_field": torn_field,
          "replica_misses_a_round": replica_misses_a_round,
          "read_without_sync": read_without_sync}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_caught(bench_root, fault):
    res = run(bench_root, hooks=_system(FAULTS[fault]))
    assert not res["correct"], res["checks"]


# ------------------------------------------------------------- the readers
def reader(name):
    return load_module(os.path.join(METRICS, name + ".py"), "rec_" + name)


REC = ["rec.replay_roofline", "rec.read_device_us", "rec.read_roofline", "rec.rows_per_round"]
PEAK = 819e9


def trace(exec_s, read_s):
    return {"busy_s": exec_s + read_s + 0.5,
            "ops": [("jit_log_exec:fusion.1", exec_s / 2), ("jit_log_exec:scatter", exec_s / 2),
                    ("jit_read_stacked:dynamic-slice", read_s),
                    ("jit_log_append:scatter", 0.5)]}


def test_readers_on_a_synthetic_window():
    ctx = {"trace": trace(0.002, 0.004), "peaks": {"hbm_bytes_per_s": PEAK},
           "reg": {"nr.rec.window_rows": 900, "nr.exec.rounds": 30},
           "reg_traced": {"nr.rec.window_entries": 400, "nr.rec.window_rows": 380,
                          "nr.rec.reads": 200, "nr.exec.rounds": 12},
           "work_traced": {"puts": 400, "gets": 200, "replicas": 4}}
    got = {n: reader(n).read(ctx) for n in REC}
    want_bytes = 400 * 112 + 380 * 100 + 4 * 380 * 100
    assert round_bytes(400, 380, 4) == want_bytes
    assert got["rec.replay_roofline"] == pytest.approx(100 * want_bytes / PEAK / 0.002)
    assert got["rec.read_device_us"] == pytest.approx(1e6 * 0.004 / 200)
    assert got["rec.read_roofline"] == pytest.approx(100 * 200 * 1000 / PEAK / 0.004)
    assert got["rec.rows_per_round"] == pytest.approx(30.0)


@pytest.mark.parametrize("name", REC)
def test_program_without_the_counters_reads_nothing(name):
    """The parent's program, or a CPU run: no `nr.rec.*` counter, or no
    device trace. Nothing is read and nothing raises."""
    parent = {"trace": trace(0.002, 0.0), "peaks": {"hbm_bytes_per_s": PEAK},
              "reg": {"nr.exec.rounds": 30}, "reg_traced": {"nr.exec.rounds": 12},
              "work_traced": {"puts": 1, "gets": 1, "replicas": 4}}
    assert reader(name).read(parent) is None
    assert reader(name).read({}) is None
    cpu = {**parent, "trace": None,
           "reg_traced": {"nr.rec.window_entries": 4, "nr.rec.window_rows": 4, "nr.rec.reads": 3}}
    assert name == "rec.rows_per_round" or reader(name).read(cpu) is None


def test_read_wait_reads_the_reads_own_waits():
    """`read_wait_ms` is the reads' wait alone: rounds' waits, which the
    combined `nr.lock.wait_s` also counts, do not enter it."""
    ctx = {"reg": {"nr.read.lock_wait_s": {"sum": 3.0, "count": 1500},
                   "nr.lock.wait_s": {"sum": 9.0, "count": 1600}}}
    assert reader("read_wait_ms").read(ctx) == pytest.approx(2.0)
    assert reader("read_wait_ms").read({"reg": {"nr.lock.wait_s": {"sum": 9.0, "count": 9}}}) is None
    assert reader("read_wait_ms").read({}) is None


def test_byte_counts_by_hand():
    assert ENTRY_BYTES == 28 * 4 and READ_BYTES == 1000
    # one entry, one row merged into 4 replicas: 112 + 100 read + 400 written
    assert round_bytes(1, 1, 4) == 612
    # hot keys collapse: 10 entries into one row move far less than 10 rows
    assert round_bytes(10, 1, 4) < round_bytes(10, 10, 4)
    assert read_bytes(3) == 3000
