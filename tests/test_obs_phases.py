"""Phases on the profiler's clock: the combiner lock split into wait and
hold where it is taken (`core/replica._locked_phase`), each served
request's queue wait (`serve.request.queue_s`), the process hooks on
jaxpr traces and GC pauses (`obs/metrics._install_process_hooks`), and
the spans that feed a registry histogram and a profiler annotation
(`obs/recorder.span`).

The cost contract: with the metrics registry off, none of these creates
a `TraceAnnotation` or observes anything. Annotations are counted with
a monkeypatched `jax.profiler.TraceAnnotation`.
"""

import gc
import os
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from node_replication_tpu.core.replica import NodeReplicated
from node_replication_tpu.models import HM_GET, HM_PUT, make_hashmap
from node_replication_tpu.obs.metrics import get_registry
from node_replication_tpu.obs.recorder import get_tracer, span
from node_replication_tpu.serve import ServeConfig, ServeFrontend

PHASES = ("nr.round.lock_wait_s", "nr.round.hold_s", "nr.read.lock_wait_s",
          "nr.read.hold_s", "serve.request.queue_s", "py.gc.pause_s",
          "nr.compile.backend_s")


class _Annotations:
    """Stands in for `jax.profiler.TraceAnnotation`: records each
    annotation's name and fields, and is a real context manager."""

    def __init__(self):
        self.made = []
        rec = self.made

        class Fake:
            def __init__(self, name, **fields):
                rec.append((name, fields))

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                return False

        self.cls = Fake

    def names(self):
        return [n for n, _ in self.made]


@pytest.fixture
def ann(monkeypatch):
    a = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", a.cls)
    return a


@pytest.fixture
def registry():
    """The process-wide registry, off unless a test enables it; its
    enable flag is restored afterwards."""
    if os.environ.get("NR_TPU_TRACE"):
        pytest.skip("tracer force-enabled via NR_TPU_TRACE")
    r = get_registry()
    was = r.enabled
    r.disable()
    yield r
    r.enabled = was


def _counts(reg):
    return {n: (reg.histogram(n).count, reg.histogram(n).sum)
            for n in PHASES} | {
        "nr.compile.traces": reg.counter("nr.compile.traces").value}


def _hist_delta(reg, before, name):
    h = reg.histogram(name)
    c0, s0 = before[name]
    return h.count - c0, h.sum - s0


@pytest.fixture(scope="module")
def nr():
    n = NodeReplicated(make_hashmap(64), n_replicas=2, log_entries=512,
                       gc_slack=16)
    n.execute_mut_batch([(HM_PUT, 1, 5)], 0)  # compile outside the tests
    n.execute((HM_GET, 1), n.register(1))
    return n


class TestRegistryOff:
    def test_locked_calls_sweep_and_read_do_nothing(self, nr, registry, ann):
        # enable once so the process hooks exist, as after any window
        registry.enable()
        registry.disable()
        before = _counts(registry)
        tok = nr.register(1)
        assert nr.execute_mut_batch([(HM_PUT, 2, 9), (HM_PUT, 3, 4)], 1) == [0, 0]
        assert nr.execute((HM_GET, 2), tok) == 9
        with ServeFrontend(nr, ServeConfig()) as fe:
            assert fe.submit((HM_PUT, 4, 7), rid=0).result(30) == 0
            assert fe.read((HM_GET, 4), rid=1) == 7
        gc.collect()
        jax.jit(lambda x: x * 11 + 2)(jnp.ones(5)).block_until_ready()
        assert ann.made == []
        assert _counts(registry) == before

    def test_span_with_both_switches_off_is_the_shared_null(self, registry):
        assert not get_tracer().enabled
        assert span("a", rid=1) is span("b")


class TestRoundPhases:
    def test_uncontended_round_observes_one_zero_wait_and_one_hold(
            self, nr, registry, ann):
        registry.enable()
        before = _counts(registry)
        nr.execute_mut_batch([(HM_PUT, 5, 1), (HM_PUT, 6, 2)], 1)
        # begin_mut_batch and finish_mut_batch ran nested: no extra phase
        assert _hist_delta(registry, before, "nr.round.lock_wait_s") == (1, 0.0)
        n_hold, hold = _hist_delta(registry, before, "nr.round.hold_s")
        assert n_hold == 1 and hold > 0
        assert _hist_delta(registry, before, "nr.read.hold_s") == (0, 0.0)
        assert ("nr.round.hold", {"rid": 1, "n": 2}) in ann.made
        assert "nr.round.lock_wait" not in ann.names()  # no wait, no span
        assert "nr.append" in ann.names()  # the round's own spans are phases too

    def test_call_under_a_held_lock_adds_nothing(self, nr, registry, ann):
        registry.enable()
        before = _counts(registry)
        with nr._lock:
            nr.execute_mut_batch([(HM_PUT, 7, 3)], 0)
            nr.execute((HM_GET, 7), nr.register(0))
        for name in ("nr.round.lock_wait_s", "nr.round.hold_s",
                     "nr.read.lock_wait_s", "nr.read.hold_s"):
            assert _hist_delta(registry, before, name) == (0, 0.0), name
        assert not {"nr.round.hold", "nr.read.hold"} & set(ann.names())

    def test_split_round_counts_each_acquisition(self, nr, registry):
        registry.enable()
        before = _counts(registry)
        p = nr.begin_mut_batch([(HM_PUT, 8, 8)], 0)
        assert nr.finish_mut_batch(p) == [0]
        assert _hist_delta(registry, before, "nr.round.hold_s")[0] == 2


class TestReadPhases:
    def test_read_blocked_behind_the_lock_waits(self, nr, registry, ann):
        registry.enable()
        tok = nr.register(0)
        before = _counts(registry)
        lw = registry.histogram("nr.lock.wait_s")
        lw0 = lw.count
        got = []
        nr._lock.acquire()
        try:
            t = threading.Thread(
                target=lambda: got.append(nr.execute((HM_GET, 1), tok)))
            t.start()
            time.sleep(0.1)
        finally:
            nr._lock.release()
        t.join(30)
        assert not t.is_alive() and got == [5]
        n, waited = _hist_delta(registry, before, "nr.read.lock_wait_s")
        assert n == 1 and waited >= 0.05
        assert _hist_delta(registry, before, "nr.read.hold_s")[0] == 1
        assert lw.count == lw0 + 1  # read_lock_wait_ms's histogram stays
        assert ("nr.read.lock_wait", {"rid": 0}) in ann.made


class TestRequestQueue:
    def test_one_observation_per_served_request(self, nr, registry):
        registry.enable()
        before = _counts(registry)
        fe = ServeFrontend(nr, ServeConfig(), auto_start=False)
        try:
            futs = [fe.submit((HM_PUT, 10 + i, i), rid=i % 2) for i in range(6)]
            time.sleep(0.02)
            fe.start()
            assert [f.result(30) for f in futs] == [0] * 6
        finally:
            fe.close()
        n, waited = _hist_delta(registry, before, "serve.request.queue_s")
        assert n == 6 and waited >= 6 * 0.02


class TestProcessHooks:
    def test_retrace_counts_and_gc_pauses_are_timed(self, registry, ann):
        registry.enable()
        before = _counts(registry)
        jax.jit(lambda x: x * 13 - 1)(jnp.ones(7)).block_until_ready()
        gc.collect()
        assert _counts(registry)["nr.compile.traces"] > before["nr.compile.traces"]
        n, paused = _hist_delta(registry, before, "py.gc.pause_s")
        assert n >= 1 and paused > 0
        assert "nr.gc" in ann.names()

    def test_nothing_while_off(self, registry, ann):
        registry.enable()  # the hooks are installed ...
        registry.disable()  # ... and inert
        before = _counts(registry)
        jax.jit(lambda x: x * 17 + 3)(jnp.ones(9)).block_until_ready()
        gc.collect()
        assert _counts(registry) == before
        assert ann.made == []


class TestSpanSinks:
    def test_registry_on_annotates_and_observes_without_jsonl(self, registry, ann):
        registry.enable()
        h = registry.histogram("nr.test-phase_s")
        c0 = h.count
        with span("test-phase", rid=3, n=2, pos0=40):
            pass
        assert h.count == c0 + 1
        assert ann.made == [("nr.test-phase", {"rid": 3, "n": 2, "pos0": 40})]
        assert not get_tracer().enabled

    def test_tracer_alone_keeps_the_jsonl_event(self, registry, ann):
        t = get_tracer()
        t.enable(None)
        try:
            with span("test-jsonl", rid=1) as sp:
                sp.add(pos0=4)
            ev = t.events()[-1]
        finally:
            t.disable()
        assert ev["event"] == "test-jsonl" and ev["rid"] == 1 and ev["pos0"] == 4
        assert ann.made == []


@pytest.mark.parametrize("debug", [False, True])
def test_programs_are_named(debug):
    n = NodeReplicated(make_hashmap(16), n_replicas=2, log_entries=256,
                       gc_slack=16, debug=debug)
    tail = "_checked" if debug else ""
    exec_text = n._exec_jit.lower(n.log, n.states, window=4).as_text()
    assert f"module @jit_log_exec{tail} " in exec_text
    ops = jnp.zeros((4,), jnp.int32)
    args = jnp.zeros((4, n.spec.arg_width), jnp.int32)
    app_text = n._append_jit.lower(n.log, ops, args, 1).as_text()
    assert f"module @jit_log_append{tail} " in app_text
