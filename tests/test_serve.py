"""Serve layer (ISSUE 3): batch-submit entry points, admission
control, deadlines, drain/close, retry, elasticity under load, and
the serve report section.

The elasticity test is the satellite's sequence-numbered
linearizability check: 8 client OS threads drive ~10k fetch-and-set
ops through the frontend while `grow()` adds a replica mid-flight;
every response must equal the register's previous value, so a lost,
duplicated, or reordered execution is directly client-observable.
"""

import threading
import time

import pytest

from node_replication_tpu import NodeReplicated
from node_replication_tpu.core.cnr import MultiLogReplicated
from node_replication_tpu.core.replica import LogTooSmallError
from node_replication_tpu.models import (
    HM_GET,
    HM_PUT,
    SR_GET,
    SR_SET,
    make_hashmap,
    make_seqreg,
)
from node_replication_tpu.serve import (
    DeadlineExceeded,
    FrontendClosed,
    Overloaded,
    RetryPolicy,
    ServeConfig,
    ServeFrontend,
    call_with_retry,
)
from node_replication_tpu.serve.future import ServeFuture


def small_nr(dispatch=None, **kw):
    kw.setdefault("n_replicas", 2)
    kw.setdefault("log_entries", 512)
    kw.setdefault("gc_slack", 32)
    kw.setdefault("exec_window", 64)
    return NodeReplicated(dispatch or make_hashmap(64), **kw)


def fast_cfg(**kw):
    kw.setdefault("batch_linger_s", 0.0)
    return ServeConfig(**kw)


class TestExecuteMutBatch:
    def test_responses_in_submission_order(self):
        # seqreg's fetch-and-set response is order-sensitive: resps of
        # sequential writes to one slot must be 0, 1, 2, ...
        nr = small_nr(make_seqreg(4))
        resps = nr.execute_mut_batch(
            [(SR_SET, 0, i + 1) for i in range(100)], rid=0
        )
        assert resps == list(range(100))

    def test_empty_batch(self):
        nr = small_nr()
        assert nr.execute_mut_batch([], rid=0) == []

    def test_oversized_batch_raises(self):
        nr = small_nr(log_entries=128, gc_slack=16)
        with pytest.raises(LogTooSmallError):
            nr.execute_mut_batch(
                [(HM_PUT, 0, 0)] * 200, rid=0
            )

    def test_bad_rid_raises(self):
        nr = small_nr()
        with pytest.raises(ValueError):
            nr.execute_mut_batch([(HM_PUT, 0, 0)], rid=9)

    def test_ring_wrap(self):
        # three 60-op batches through a 128-slot ring: positions wrap,
        # the global per-slot sequence must stay exact
        nr = small_nr(make_seqreg(2), log_entries=128, gc_slack=16)
        expect = 0
        for _ in range(3):
            resps = nr.execute_mut_batch(
                [(SR_SET, 0, expect + j + 1) for j in range(60)],
                rid=0,
            )
            assert resps == [expect + j for j in range(60)]
            expect += 60
        nr.sync()
        assert nr.replicas_equal()

    def test_does_not_drain_staged_thread_contexts(self):
        # a batch appends EXACTLY the given ops; enqueue_mut backlogs
        # stay staged until their own combine
        nr = small_nr()
        tok = nr.register(0)
        nr.enqueue_mut((HM_PUT, 1, 5), tok)
        nr.execute_mut_batch([(HM_PUT, 2, 7)], rid=0)
        assert nr.responses(tok) == []
        nr.flush(0)
        assert nr.responses(tok) == [0]
        reader = nr.register(1)
        assert nr.execute((HM_GET, 1), reader) == 5
        assert nr.execute((HM_GET, 2), reader) == 7

    def test_cnr_batch_submission_order_across_logs(self):
        # slots route to different logs; responses must come back in
        # SUBMISSION order, not per-log completion order
        ml = MultiLogReplicated(
            make_seqreg(4), lambda opc, args: args[0], nlogs=2,
            n_replicas=2, log_entries=128, gc_slack=8, exec_window=16,
        )
        ops, expect = [], []
        counts = [0, 0, 0, 0]
        for i in range(40):
            slot = i % 4
            ops.append((SR_SET, slot, counts[slot] + 1))
            expect.append(counts[slot])
            counts[slot] += 1
        assert ml.execute_mut_batch(ops, rid=0) == expect
        ml.sync()
        assert ml.replicas_equal()

    def test_cnr_empty_and_bad_rid(self):
        ml = MultiLogReplicated(
            make_seqreg(4), lambda opc, args: args[0], nlogs=2,
            n_replicas=1, log_entries=128, gc_slack=8, exec_window=16,
        )
        assert ml.execute_mut_batch([], rid=0) == []
        with pytest.raises(ValueError):
            ml.execute_mut_batch([(SR_SET, 0, 1)], rid=3)


class TestFailedBatchHygiene:
    def test_nr_failed_batch_does_not_poison_next(self, monkeypatch):
        # a replay failure AFTER the append must not leave stale sink
        # state: the next batch's responses are its own, exactly
        nr = small_nr(make_seqreg(2))
        orig = NodeReplicated._exec_round
        state = {"fail": True}

        def flaky(self_nr):
            if state["fail"]:
                state["fail"] = False
                raise RuntimeError("injected replay failure")
            return orig(self_nr)

        monkeypatch.setattr(NodeReplicated, "_exec_round", flaky)
        with pytest.raises(RuntimeError):
            nr.execute_mut_batch(
                [(SR_SET, 0, i + 1) for i in range(5)], rid=0
            )
        # the failed batch's ops ARE in the log and replay; only their
        # responses were lost. The next batch sees clean deliveries.
        resps = nr.execute_mut_batch(
            [(SR_SET, 0, i + 6) for i in range(5)], rid=0
        )
        assert resps == [5, 6, 7, 8, 9]

    def test_cnr_failed_batch_does_not_wedge_replica(self, monkeypatch):
        ml = MultiLogReplicated(
            make_seqreg(4), lambda opc, args: args[0], nlogs=2,
            n_replicas=1, log_entries=128, gc_slack=8, exec_window=16,
        )
        orig = MultiLogReplicated._exec_round
        state = {"fail": True}

        def flaky(self_ml):
            if state["fail"]:
                state["fail"] = False
                raise RuntimeError("injected replay failure")
            return orig(self_ml)

        monkeypatch.setattr(MultiLogReplicated, "_exec_round", flaky)
        with pytest.raises(RuntimeError):
            ml.execute_mut_batch(
                [(SR_SET, 0, 1), (SR_SET, 1, 1)], rid=0
            )
        resps = ml.execute_mut_batch(
            [(SR_SET, 0, 2), (SR_SET, 1, 2)], rid=0
        )
        # the failure hit during the round's replay: one program had
        # already appended both logs' sub-batches, so both writes
        # replay (only their responses were lost). The next batch's
        # responses are exactly its own.
        assert resps == [1, 1]

    def test_worker_guard_rejects_whole_batch(self):
        # an exception OUTSIDE the execute try-block (here: a metrics
        # handle blowing up in the deadline sweep) must reject the
        # batch's futures instead of stranding their callers
        nr = small_nr(make_seqreg(2))
        fe = ServeFrontend(nr, fast_cfg(), auto_start=False)

        class BoomOnce:
            armed = True

            def inc(self, n=1):
                if self.armed:
                    self.armed = False
                    raise RuntimeError("metrics boom")

        fe._m_miss = BoomOnce()
        expired = fe.submit((SR_SET, 0, 1), deadline_s=0.001)
        live = fe.submit((SR_SET, 0, 2))
        time.sleep(0.05)
        fe.start()
        with pytest.raises(DeadlineExceeded):
            expired.result(10.0)  # resolved before the boom: kept
        with pytest.raises(RuntimeError):
            live.result(10.0)  # rejected by the worker's guard
        # the worker survived: the frontend still serves
        assert fe.call((SR_SET, 1, 1), timeout=10.0) == 0
        fe.close()


class TestServeFuture:
    def test_resolve_and_done(self):
        f = ServeFuture(rid=0)
        assert not f.done()
        assert f._resolve(42)
        assert f.done() and f.result() == 42
        assert f.exception() is None
        assert f.latency_s is not None and f.latency_s >= 0

    def test_single_resolution_wins(self):
        f = ServeFuture(rid=0)
        assert f._resolve(1)
        assert not f._reject(RuntimeError("late"))
        assert f.result() == 1

    def test_reject_raises_typed(self):
        f = ServeFuture(rid=3)
        f._reject(Overloaded(3, 8))
        with pytest.raises(Overloaded):
            f.result()
        assert isinstance(f.exception(), Overloaded)

    def test_result_timeout(self):
        f = ServeFuture(rid=0)
        with pytest.raises(TimeoutError):
            f.result(timeout=0.01)

    def test_callbacks_before_and_after(self):
        f = ServeFuture(rid=0)
        seen = []
        f.add_done_callback(lambda fut: seen.append(("pre", fut.result())))
        f._resolve(5)
        f.add_done_callback(lambda fut: seen.append(("post", fut.result())))
        assert seen == [("pre", 5), ("post", 5)]

    def test_callback_exception_swallowed(self):
        f = ServeFuture(rid=0)

        def bad(fut):
            raise RuntimeError("handler bug")

        f.add_done_callback(bad)
        assert f._resolve(1)  # must not raise
        assert f.result() == 1


class TestAdmissionControl:
    def test_overload_typed_and_counted(self):
        nr = small_nr(make_seqreg(2))
        fe = ServeFrontend(nr, fast_cfg(queue_depth=4),
                           auto_start=False)
        futs = [fe.submit((SR_SET, 0, i + 1)) for i in range(4)]
        with pytest.raises(Overloaded) as ei:
            fe.submit((SR_SET, 0, 99))
        assert ei.value.rid == 0 and ei.value.depth == 4
        st = fe.stats()
        assert st["shed"] == 1 and st["accepted"] == 4
        fe.start()
        assert [f.result(10.0) for f in futs] == [0, 1, 2, 3]
        assert fe.stats()["completed"] == 4
        fe.close()

    def test_unknown_rid_raises(self):
        fe = ServeFrontend(small_nr(), fast_cfg())
        with pytest.raises(ValueError):
            fe.submit((HM_PUT, 0, 0), rid=7)
        with pytest.raises(ValueError):
            fe.read((HM_GET, 0), rid=7)
        fe.close()

    def test_backpressure_bounds_memory(self):
        # flood a paused depth-8 frontend with 1000 submissions: 992
        # shed as typed Overloaded, queue never exceeds its bound
        nr = small_nr(make_seqreg(2))
        fe = ServeFrontend(nr, fast_cfg(queue_depth=8),
                           auto_start=False)
        shed = 0
        for i in range(1000):
            try:
                fe.submit((SR_SET, 0, i + 1))
            except Overloaded:
                shed += 1
        st = fe.stats()
        assert shed == 992
        assert st["queued"] == 8 and st["shed"] == 992
        fe.start()
        fe.close()  # drains the 8 accepted


class TestDeadlines:
    def test_expired_request_dropped_before_append(self):
        nr = small_nr(make_seqreg(2))
        fe = ServeFrontend(nr, fast_cfg(), auto_start=False)
        fut = fe.submit((SR_SET, 0, 77), deadline_s=0.005)
        time.sleep(0.05)
        fe.start()
        with pytest.raises(DeadlineExceeded):
            fut.result(10.0)
        fe.drain()
        # the op must have had NO effect: register still 0
        assert fe.read((SR_GET, 0)) == 0
        assert fe.stats()["deadline_missed"] == 1
        # frontend still serves after a miss
        assert fe.call((SR_SET, 0, 1), timeout=10.0) == 0
        fe.close()

    def test_default_deadline_from_config(self):
        nr = small_nr(make_seqreg(2))
        fe = ServeFrontend(
            nr, fast_cfg(default_deadline_s=0.005), auto_start=False
        )
        fut = fe.submit((SR_SET, 1, 5))
        time.sleep(0.05)
        fe.start()
        with pytest.raises(DeadlineExceeded):
            fut.result(10.0)
        fe.close()


class TestDrainClose:
    def test_close_drains_queued_ops(self):
        nr = small_nr(make_seqreg(2))
        fe = ServeFrontend(nr, fast_cfg())
        futs = [fe.submit((SR_SET, 0, i + 1), rid=0)
                for i in range(50)]
        fe.close()  # drain=True: flush everything first
        assert [f.result(0.0) for f in futs] == list(range(50))
        assert fe.stats()["completed"] == 50

    def test_close_without_drain_rejects_backlog(self):
        nr = small_nr(make_seqreg(2))
        fe = ServeFrontend(nr, fast_cfg(), auto_start=False)
        futs = [fe.submit((SR_SET, 0, i + 1)) for i in range(5)]
        fe.close(drain=False)
        for f in futs:
            with pytest.raises(FrontendClosed):
                f.result(1.0)
        # the ops never executed
        assert int(nr.log.tail) == 0

    def test_submit_after_close_raises(self):
        fe = ServeFrontend(small_nr(), fast_cfg())
        fe.close()
        with pytest.raises(FrontendClosed):
            fe.submit((HM_PUT, 0, 0))
        fe.close()  # idempotent

    def test_context_manager_drains(self):
        nr = small_nr(make_seqreg(2))
        with ServeFrontend(nr, fast_cfg()) as fe:
            futs = [fe.submit((SR_SET, 1, i + 1)) for i in range(20)]
        assert [f.result(0.0) for f in futs] == list(range(20))

    def test_drain_is_a_flush_not_a_shutdown(self):
        nr = small_nr(make_seqreg(2))
        fe = ServeFrontend(nr, fast_cfg())
        fe.submit((SR_SET, 0, 1))
        assert fe.drain(timeout=30.0)
        assert fe.stats()["queued"] == 0
        # admission still open
        assert fe.call((SR_SET, 0, 2), timeout=10.0) == 1
        fe.close()


class TestRetry:
    class FlakyFrontend:
        def __init__(self, fail_times):
            self.fail_times = fail_times
            self.calls = 0

        def call(self, op, rid=0, deadline_s=None, timeout=None):
            self.calls += 1
            if self.calls <= self.fail_times:
                raise Overloaded(rid, 8)
            return 42

    def test_retries_overloaded_then_succeeds(self):
        fe = self.FlakyFrontend(fail_times=2)
        sheds = []
        policy = RetryPolicy(max_attempts=5, base_backoff_s=0.0001,
                             max_backoff_s=0.001)
        out = call_with_retry(fe, (HM_PUT, 0, 0), policy=policy,
                              on_shed=lambda a, d: sheds.append(a))
        assert out == 42 and fe.calls == 3
        assert sheds == [0, 1]

    def test_policy_exhaustion_reraises(self):
        fe = self.FlakyFrontend(fail_times=99)
        policy = RetryPolicy(max_attempts=3, base_backoff_s=0.0001,
                             max_backoff_s=0.001)
        sheds = []
        with pytest.raises(Overloaded):
            call_with_retry(fe, (HM_PUT, 0, 0), policy=policy,
                            on_shed=lambda a, d: sheds.append(a))
        assert fe.calls == 3
        # the final exhausted rejection is counted too
        assert sheds == [0, 1, 2]

    def test_policy_validation(self):
        with pytest.raises(ValueError):
            RetryPolicy(max_attempts=0)

    def test_backoff_caps(self):
        import random

        policy = RetryPolicy(base_backoff_s=0.01, max_backoff_s=0.04)
        rng = random.Random(7)
        for attempt in range(10):
            assert 0.0 <= policy.backoff_s(attempt, rng) <= 0.04

    # -------------------------------------------- total deadline budget

    def test_total_deadline_budget_bounds_attempts(self):
        # ISSUE 8 satellite regression: the budget is enforced ACROSS
        # attempts — with always-Overloaded service and backoffs far
        # larger than the budget, the call gives up long before the
        # attempt cap, and the whole call (backoffs included) never
        # outlives the budget. SimClock makes the elapsed time exact.
        from node_replication_tpu.utils.clock import SimClock, installed

        fe = self.FlakyFrontend(fail_times=99)
        policy = RetryPolicy(max_attempts=50, base_backoff_s=0.5,
                             max_backoff_s=2.0, total_deadline_s=3.0)
        with installed(SimClock()) as clock:
            with pytest.raises(Overloaded):
                call_with_retry(fe, (HM_PUT, 0, 0), policy=policy)
            # backoff sleeps are capped by the remaining budget, so
            # virtual elapsed time never exceeds it
            assert clock.now() <= 3.0 + 1e-9
        assert fe.calls < 50

    def test_no_backoff_outlives_the_budget(self):
        # a drawn backoff larger than the remaining budget re-raises
        # instead of sleeping (so the slept delays observed by on_shed
        # always fit inside the budget, and total virtual elapsed time
        # never exceeds it)
        from node_replication_tpu.utils.clock import SimClock, installed

        fe = self.FlakyFrontend(fail_times=99)
        delays = []
        policy = RetryPolicy(max_attempts=50, base_backoff_s=1.0,
                             max_backoff_s=10.0, total_deadline_s=2.0)
        with installed(SimClock()) as clock:
            with pytest.raises(Overloaded):
                call_with_retry(
                    fe, (HM_PUT, 0, 0), policy=policy,
                    on_shed=lambda a, d: delays.append(d),
                )
            now = clock.now()
        assert delays, "on_shed observed no attempts"
        assert all(d <= 2.0 for d in delays)
        assert now <= 2.0 + 1e-9

    def test_budget_exhausted_before_sleep_reraises(self):
        # a retry whose backoff would eat the whole remaining budget
        # re-raises instead of sleeping into a guaranteed timeout
        from node_replication_tpu.utils.clock import SimClock, installed

        fe = self.FlakyFrontend(fail_times=99)
        policy = RetryPolicy(max_attempts=5, base_backoff_s=1e9,
                             max_backoff_s=1e9, total_deadline_s=0.5)
        with installed(SimClock()) as clock:
            with pytest.raises(Overloaded):
                call_with_retry(fe, (HM_PUT, 0, 0), policy=policy)
            assert clock.now() == 0.0  # gave up without sleeping
        assert fe.calls >= 1

    def test_no_budget_keeps_legacy_behavior(self):
        fe = self.FlakyFrontend(fail_times=2)
        policy = RetryPolicy(max_attempts=5, base_backoff_s=0.0001,
                             max_backoff_s=0.001)
        assert policy.total_deadline_s is None
        assert call_with_retry(fe, (HM_PUT, 0, 0),
                               policy=policy) == 42

    def test_budget_validation(self):
        with pytest.raises(ValueError):
            RetryPolicy(total_deadline_s=0.0)
        with pytest.raises(ValueError):
            RetryPolicy(total_deadline_s=-1.0)


class TestReadPath:
    def test_read_your_writes_and_no_queue_traffic(self):
        nr = small_nr()
        fe = ServeFrontend(nr, fast_cfg())
        assert fe.call((HM_PUT, 3, 30), rid=0, timeout=10.0) == 0
        before = fe.stats()["accepted"]
        # reads on BOTH replicas observe the completed write (ctail
        # gate) and never touch the admission queues
        assert fe.read((HM_GET, 3), rid=0) == 30
        assert fe.read((HM_GET, 3), rid=1) == 30
        assert fe.stats()["accepted"] == before
        fe.close()

    def test_frontend_over_cnr(self):
        ml = MultiLogReplicated(
            make_seqreg(4), lambda opc, args: args[0], nlogs=2,
            n_replicas=2, log_entries=128, gc_slack=8, exec_window=16,
        )
        with ServeFrontend(ml, fast_cfg()) as fe:
            futs = [fe.submit((SR_SET, i % 4, i // 4 + 1),
                              rid=i % 2) for i in range(16)]
            for i, f in enumerate(futs):
                assert f.result(10.0) == i // 4
            assert fe.read((SR_GET, 2), rid=1) == 4


class TestConfigValidation:
    def test_bad_config_raises(self):
        with pytest.raises(ValueError):
            ServeConfig(queue_depth=0)
        with pytest.raises(ValueError):
            ServeConfig(batch_max_ops=0)
        with pytest.raises(ValueError):
            ServeConfig(batch_linger_s=-1.0)

    def test_frontend_requires_batch_entry_point(self):
        with pytest.raises(TypeError):
            ServeFrontend(object())


class TestElasticityUnderLoad:
    """grow_fleet while serve traffic is in flight: the ~10k-op,
    8-thread sequence-numbered linearizability check (ISSUE 3
    satellite). Client c owns register c and writes 1..N in order;
    every fetch-and-set response must equal the previous value, so a
    lost op shows as a gap, a duplicate as a repeat, a reorder as a
    mismatch — no response stream can hide any of them."""

    CLIENTS = 8
    PER_CLIENT = 1250  # 8 x 1250 = 10k ops

    def test_grow_mid_traffic_loses_nothing(self):
        from collections import deque

        nr = small_nr(
            make_seqreg(self.CLIENTS), n_replicas=2,
            log_entries=4096, gc_slack=256, exec_window=256,
        )
        # depth 512 >= clients x window: this run exercises ordering
        # under pipelining, not shedding (TestAdmissionControl does)
        fe = ServeFrontend(
            nr, fast_cfg(queue_depth=512, batch_max_ops=64)
        )
        errors: list = []
        grown = threading.Event()
        WINDOW = 32  # outstanding futures per client (pipelined)

        def client(c: int) -> None:
            rid = c % 2
            outstanding: deque = deque()

            def harvest(down_to: int) -> None:
                while len(outstanding) > down_to:
                    i, fut = outstanding.popleft()
                    resp = fut.result(timeout=120.0)
                    if resp != i:
                        errors.append((c, i, resp))
                        raise AssertionError("sequence broken")

            try:
                for i in range(self.PER_CLIENT):
                    outstanding.append(
                        (i, fe.submit((SR_SET, c, i + 1), rid=rid))
                    )
                    harvest(WINDOW - 1)
                    if c == 0 and i == self.PER_CLIENT // 2:
                        fe.grow(1)  # mid-traffic elasticity
                        grown.set()
                harvest(0)
            except AssertionError:
                pass
            except BaseException as e:  # pragma: no cover
                errors.append((c, type(e).__name__, str(e)))

        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(self.CLIENTS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        assert not errors, errors[:5]
        assert grown.is_set()
        assert nr.n_replicas == 3
        # the grown replica serves: sequences continue seamlessly on it
        for c in range(self.CLIENTS):
            resp = fe.call((SR_SET, c, self.PER_CLIENT + 2), rid=2,
                           timeout=60.0)
            assert resp == self.PER_CLIENT, (c, resp)
        st = fe.stats()
        assert st["completed"] == st["accepted"]
        assert st["deadline_missed"] == 0
        fe.close()
        nr.sync()
        assert nr.replicas_equal()
        reader = nr.register(2)
        for c in range(self.CLIENTS):
            assert nr.execute((SR_GET, c), reader) == \
                self.PER_CLIENT + 2


class TestMeasureServe:
    def test_closed_loop_measurement(self):
        from node_replication_tpu.harness.mkbench import measure_serve

        nr = small_nr(make_seqreg(2))
        errors_expected = []

        def check(c, i, resp):
            return None if resp == i else f"{c}/{i}: {resp}"

        with ServeFrontend(nr, fast_cfg()) as fe:
            res = measure_serve(
                fe, lambda c, i: (SR_SET, c, i + 1), 40, 2,
                mode="closed", check=check, name="t",
            )
        assert res.completed == 40 and res.accepted == 40
        assert res.attempts == 40
        assert res.errors == errors_expected
        assert res.transport_errors == []
        assert len(res.latencies_s) == 40
        assert res.percentile_ms(99) >= res.percentile_ms(50) >= 0
        assert res.throughput > 0

    def test_open_loop_requires_rate(self):
        from node_replication_tpu.harness.mkbench import measure_serve

        with pytest.raises(ValueError):
            measure_serve(None, None, 1, 1, mode="open")
        with pytest.raises(ValueError):
            measure_serve(None, None, 1, 1, mode="bogus")


class TestServeReportSection:
    def test_serve_section_from_events(self):
        from node_replication_tpu.obs.report import analyze, render

        events = [
            {"event": "serve-batch", "mono": 100.0 + 0.1 * i,
             "rid": 0, "n": 4, "queue_depth": i, "duration_s": 0.002}
            for i in range(5)
        ] + [
            {"event": "serve-batch", "mono": 101.5, "rid": 1, "n": 9,
             "queue_depth": 2, "duration_s": 0.004},
            {"event": "serve-shed", "mono": 101.6, "rid": 0,
             "depth": 8},
            {"event": "serve-deadline-miss", "mono": 101.7, "rid": 0,
             "n": 3},
        ]
        rep = analyze(events)
        s = rep["serve"]
        assert s["batches"] == 6 and s["ops"] == 29
        assert s["shed"] == 1 and s["deadline_miss"] == 3
        assert s["max_batch"] == 9
        assert s["batch_size_hist"] == {4: 5, 16: 1}
        # queue-depth timeline keeps the per-second MAX
        assert s["queue_depth_timeline"][0] == 4
        assert s["queue_depth_timeline"][1] == 2
        import io

        out = io.StringIO()
        render(rep, out=out)
        text = out.getvalue()
        assert "== serve ==" in text
        assert "shed (Overloaded): 1" in text

    def test_no_serve_events_no_section(self):
        from node_replication_tpu.obs.report import analyze, render

        rep = analyze([{"event": "append", "mono": 1.0, "n": 2}])
        assert rep["serve"] is None
        import io

        out = io.StringIO()
        render(rep, out=out)
        assert "== serve ==" not in out.getvalue()


class TestServeMetricsAndTrace:
    def test_counters_and_trace_events(self):
        from node_replication_tpu.obs.metrics import get_registry
        from node_replication_tpu.obs.recorder import get_tracer

        reg = get_registry()
        was = reg.enabled
        reg.enable()
        tracer = get_tracer()
        was_tracing = tracer.enabled
        tracer.enable(None)  # memory-buffer mode
        try:
            base_sub = reg.counter("serve.submitted").value
            base_shed = reg.counter("serve.shed").value
            nr = small_nr(make_seqreg(2))
            fe = ServeFrontend(nr, fast_cfg(queue_depth=2),
                               auto_start=False)
            fe.submit((SR_SET, 0, 1))
            fe.submit((SR_SET, 0, 2))
            with pytest.raises(Overloaded):
                fe.submit((SR_SET, 0, 3))
            fe.start()
            fe.drain()
            fe.close()
            assert reg.counter("serve.submitted").value - base_sub == 2
            assert reg.counter("serve.shed").value - base_shed == 1
            names = [e.get("event") for e in tracer.events()]
            assert "serve-shed" in names
            assert "serve-batch" in names
            assert "serve-close" in names
        finally:
            if not was:
                reg.disable()
            if not was_tracing:
                tracer.disable()


class TestSplitRoundProtocol:
    """begin_mut_batch / finish_mut_batch (ISSUE 14): the wrapper half
    of pipelined serving."""

    def test_begin_finish_responses_in_order(self):
        nr = small_nr(make_seqreg(4))
        pending = nr.begin_mut_batch(
            [(SR_SET, 0, i + 1) for i in range(20)], rid=0
        )
        assert nr.finish_mut_batch(pending) == list(range(20))
        nr.sync()
        assert nr.replicas_equal()

    def test_at_most_one_round_in_flight(self):
        nr = small_nr(make_seqreg(2))
        pending = nr.begin_mut_batch([(SR_SET, 0, 1)], rid=0)
        with pytest.raises(RuntimeError):
            nr.begin_mut_batch([(SR_SET, 0, 2)], rid=0)
        assert nr.finish_mut_batch(pending) == [0]
        # finished: the slot is free again
        p2 = nr.begin_mut_batch([(SR_SET, 0, 2)], rid=0)
        assert nr.finish_mut_batch(p2) == [1]

    def test_finish_twice_raises(self):
        nr = small_nr(make_seqreg(2))
        pending = nr.begin_mut_batch([(SR_SET, 0, 1)], rid=0)
        nr.finish_mut_batch(pending)
        with pytest.raises(RuntimeError):
            nr.finish_mut_batch(pending)

    def test_empty_begin_finish(self):
        nr = small_nr()
        pending = nr.begin_mut_batch([], rid=0)
        assert nr.finish_mut_batch(pending) == []

    def test_failed_finish_hygiene(self, monkeypatch):
        # a replay failure in finish must not poison the next batch
        # (the execute_mut_batch hygiene regression, split shape)
        nr = small_nr(make_seqreg(2))
        orig = NodeReplicated._exec_round
        state = {"fail": True}

        def flaky(self_nr):
            if state["fail"]:
                state["fail"] = False
                raise RuntimeError("injected replay failure")
            return orig(self_nr)

        monkeypatch.setattr(NodeReplicated, "_exec_round", flaky)
        pending = nr.begin_mut_batch(
            [(SR_SET, 0, i + 1) for i in range(5)], rid=0
        )
        with pytest.raises(RuntimeError):
            nr.finish_mut_batch(pending)
        # the appended ops replay; the next batch's responses are
        # exactly its own
        resps = nr.execute_mut_batch(
            [(SR_SET, 0, i + 6) for i in range(5)], rid=0
        )
        assert resps == [5, 6, 7, 8, 9]

    def test_abort_releases_the_slot(self):
        nr = small_nr(make_seqreg(2))
        pending = nr.begin_mut_batch([(SR_SET, 0, 1)], rid=0)
        nr.abort_mut_batch(pending)
        nr.abort_mut_batch(pending)  # idempotent
        # the aborted round's op IS in the log and replays; only its
        # response was dropped — the next round sees its effect
        resps = nr.execute_mut_batch([(SR_SET, 0, 2)], rid=0)
        assert resps == [1]

    def test_cnr_begin_finish_scatter(self):
        ml = MultiLogReplicated(
            make_seqreg(4), lambda opc, args: args[0], nlogs=2,
            n_replicas=2, log_entries=128, gc_slack=8, exec_window=16,
        )
        ops, expect = [], []
        counts = [0, 0, 0, 0]
        for i in range(16):
            slot = i % 4
            ops.append((SR_SET, slot, counts[slot] + 1))
            expect.append(counts[slot])
            counts[slot] += 1
        pending = ml.begin_mut_batch(ops, rid=0)
        with pytest.raises(RuntimeError):
            ml.begin_mut_batch([(SR_SET, 0, 99)], rid=0)
        assert ml.finish_mut_batch(pending) == expect
        ml.sync()
        assert ml.replicas_equal()


class TestPipelinedServing:
    """ServeConfig.pipeline_depth=1 (ISSUE 14): the assembly /
    completion split, overlap semantics, and its failure discipline."""

    def test_depth_validation(self):
        with pytest.raises(ValueError):
            ServeConfig(pipeline_depth=2)
        with pytest.raises(ValueError):
            ServeConfig(pipeline_depth=-1)

    def test_pipelined_sequence_exact(self):
        nr = small_nr(make_seqreg(2))
        fe = ServeFrontend(
            nr, fast_cfg(pipeline_depth=1, batch_max_ops=8)
        )
        futs = [fe.submit((SR_SET, 0, i + 1), rid=0)
                for i in range(200)]
        assert [f.result(60.0) for f in futs] == list(range(200))
        st = fe.stats()
        assert st["completed"] == 200 and st["in_service"] == 0
        fe.close()
        nr.sync()
        assert nr.replicas_equal()

    def test_depth0_and_depth1_logs_bit_identical(self):
        # the acceptance pin: same ops through both worker shapes ->
        # same responses AND same log contents (ring_slice)
        from node_replication_tpu.core.log import ring_slice

        outs, slices = [], []
        for depth in (0, 1):
            nr = small_nr(make_seqreg(4))
            fe = ServeFrontend(
                nr, fast_cfg(pipeline_depth=depth, batch_max_ops=4)
            )
            futs = [fe.submit((SR_SET, i % 4, i + 1), rid=0)
                    for i in range(64)]
            outs.append([f.result(60.0) for f in futs])
            fe.close()
            nr.sync()
            slices.append(ring_slice(nr.spec, nr.log, 0,
                                     int(nr.log.tail)))
        assert outs[0] == outs[1]
        ops0, ops1 = slices
        assert (ops0[0] == ops1[0]).all() and (ops0[1] == ops1[1]).all()

    def test_close_drain_waits_for_inflight_round(self):
        nr = small_nr(make_seqreg(2))
        fe = ServeFrontend(
            nr, fast_cfg(pipeline_depth=1, batch_max_ops=4)
        )
        futs = [fe.submit((SR_SET, 1, i + 1), rid=1)
                for i in range(40)]
        fe.close()  # drain=True must flush assembled AND in-flight
        assert [f.result(0.0) for f in futs] == list(range(40))

    def test_worker_death_with_round_in_flight(self):
        # the two-stage failover pin: the in-flight round's futures
        # get post-append ReplicaFailed (maybe_executed=True), the
        # not-yet-begun round's get pre-append retryable
        from node_replication_tpu.fault.inject import (
            FaultPlan,
            FaultSpec,
        )
        from node_replication_tpu.serve import ReplicaFailed

        nr = small_nr(make_seqreg(2), n_replicas=1)
        fe = ServeFrontend(
            nr, fast_cfg(pipeline_depth=1, batch_max_ops=2,
                         failover=True),
            auto_start=False,
        )
        futs = [fe.submit((SR_SET, 0, i + 1), rid=0)
                for i in range(4)]
        plan = FaultPlan([
            FaultSpec(site="serve-complete", action="raise")
        ])
        with plan.armed():
            fe.start()
            excs = [f.exception(30.0) for f in futs]
        assert all(isinstance(e, ReplicaFailed) for e in excs)
        # first batch (2 ops) was in flight: post-append
        assert [e.maybe_executed for e in excs[:2]] == [True, True]
        # the rest never reached begin: exactly-once retryable
        assert [e.maybe_executed for e in excs[2:]] == [False, False]
        fe.close()

    def test_pre_append_kill_retryable_in_assembly_stage(self):
        # serve-batch fires in the ASSEMBLY stage pre-append: a kill
        # there must stay exactly-once retryable (both-stages pin)
        from node_replication_tpu.fault.inject import (
            FaultPlan,
            FaultSpec,
        )
        from node_replication_tpu.serve import ReplicaFailed

        nr = small_nr(make_seqreg(2), n_replicas=1)
        fe = ServeFrontend(
            nr, fast_cfg(pipeline_depth=1, failover=True),
            auto_start=False,
        )
        fut = fe.submit((SR_SET, 0, 1), rid=0)
        plan = FaultPlan([
            FaultSpec(site="serve-batch", action="raise")
        ])
        with plan.armed():
            fe.start()
            exc = fut.exception(30.0)
        assert isinstance(exc, ReplicaFailed)
        assert exc.maybe_executed is False
        # the op provably never reached the log
        assert int(nr.log.tail) == 0
        fe.close()

    def test_deadline_late_success_counted_and_delivered(self):
        # a request that expires while its round is in flight still
        # resolves (first resolution wins, the op executed) but lands
        # in serve.deadline_late_success — SLO honesty
        from node_replication_tpu.fault.inject import (
            FaultPlan,
            FaultSpec,
        )
        from node_replication_tpu.obs.metrics import get_registry

        reg = get_registry()
        was = reg.enabled
        reg.enable()
        try:
            base = reg.counter("serve.deadline_late_success").value
            nr = small_nr(make_seqreg(2))
            fe = ServeFrontend(
                nr, fast_cfg(pipeline_depth=1), auto_start=False
            )
            fut = fe.submit((SR_SET, 0, 7), rid=0, deadline_s=0.05)
            # stall the completion stage past the deadline: the round
            # is begun (appended) when the stall fires
            plan = FaultPlan([
                FaultSpec(site="serve-complete", action="stall",
                          stall_s=0.5)
            ])
            with plan.armed():
                fe.start()
                assert fut.result(30.0) == 0  # delivered, not dropped
            assert (reg.counter("serve.deadline_late_success").value
                    - base) == 1
            assert fe.stats()["deadline_missed"] == 0
            fe.close()
        finally:
            if not was:
                reg.disable()

    def test_grow_mid_traffic_pipelined(self):
        # elasticity under the two-stage worker: sequences stay exact
        # across a grow() while pipelined traffic is in flight
        nr = small_nr(
            make_seqreg(4), n_replicas=2,
            log_entries=4096, gc_slack=256, exec_window=256,
        )
        fe = ServeFrontend(
            nr, fast_cfg(queue_depth=256, batch_max_ops=16,
                         pipeline_depth=1)
        )
        errors = []

        def client(c):
            try:
                for i in range(200):
                    resp = fe.submit(
                        (SR_SET, c, i + 1), rid=c % 2
                    ).result(60.0)
                    if resp != i:
                        errors.append((c, i, resp))
                        return
                    if c == 0 and i == 100:
                        fe.grow(1)
            except Exception as e:  # pragma: no cover
                errors.append((c, type(e).__name__, str(e)))

        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        assert not errors, errors[:3]
        assert nr.n_replicas == 3
        # the grown replica serves pipelined rounds too (client 0
        # wrote 1..200, so the fetch-and-set returns 200)
        assert fe.call((SR_SET, 0, 202), rid=2, timeout=30.0) == 200
        fe.close()
        nr.sync()
        assert nr.replicas_equal()

    def test_cnr_pipelined_frontend(self):
        ml = MultiLogReplicated(
            make_seqreg(4), lambda opc, args: args[0], nlogs=2,
            n_replicas=2, log_entries=128, gc_slack=8, exec_window=16,
        )
        with ServeFrontend(
            ml, fast_cfg(pipeline_depth=1, batch_max_ops=4)
        ) as fe:
            futs = [fe.submit((SR_SET, i % 4, i // 4 + 1),
                              rid=i % 2) for i in range(32)]
            for i, f in enumerate(futs):
                assert f.result(30.0) == i // 4
            assert fe.read((SR_GET, 2), rid=1) == 8

    def test_simclock_pipelined_handoff(self):
        # the two-stage handoff under virtual time: every wait in the
        # channel and queue routes through the injectable clock, so a
        # SimClock(auto_advance) run completes without real sleeps
        from node_replication_tpu.utils.clock import (
            SimClock,
            installed,
        )

        with installed(SimClock(auto_advance=True)):
            nr = small_nr(make_seqreg(2))
            fe = ServeFrontend(
                nr, fast_cfg(pipeline_depth=1, batch_max_ops=4)
            )
            futs = [fe.submit((SR_SET, 0, i + 1), rid=0)
                    for i in range(24)]
            assert [f.result(60.0) for f in futs] == list(range(24))
            fe.close()

    def test_serve_assemble_event_and_report_line(self):
        from node_replication_tpu.obs.report import analyze, render
        from node_replication_tpu.obs.recorder import get_tracer

        tracer = get_tracer()
        was = tracer.enabled
        tracer.enable(None)  # memory ring
        try:
            nr = small_nr(make_seqreg(2))
            fe = ServeFrontend(
                nr, fast_cfg(pipeline_depth=1, batch_max_ops=8)
            )
            futs = [fe.submit((SR_SET, 0, i + 1), rid=0)
                    for i in range(40)]
            for f in futs:
                f.result(30.0)
            fe.close()
            events = tracer.events()
            assert any(e.get("event") == "serve-assemble"
                       for e in events)
            rep = analyze(events)
            pipe = rep["serve"]["pipeline"]
            assert pipe is not None
            assert pipe["assemble_events"] >= 1
            assert pipe["device_busy_s"] >= 0.0
            import io

            out = io.StringIO()
            render(rep, out=out)
            assert "pipeline overlap" in out.getvalue()
        finally:
            if not was:
                tracer.disable()


class TestPipelinedFailurePaths:
    """Review-hardening regressions: the pipelined failure paths that
    the first cut left untested."""

    def test_non_failover_finish_failure_keeps_serving(self):
        # a completion-stage failure WITHOUT failover must reject its
        # own round and keep the pipeline alive (the channel's busy
        # flag releases; a wedged channel would hang every later op)
        from node_replication_tpu.fault.inject import (
            FaultError,
            FaultPlan,
            FaultSpec,
        )

        nr = small_nr(make_seqreg(2), n_replicas=1)
        fe = ServeFrontend(
            nr, fast_cfg(pipeline_depth=1, batch_max_ops=4),
            auto_start=False,
        )
        doomed = fe.submit((SR_SET, 0, 1), rid=0)
        plan = FaultPlan([
            FaultSpec(site="serve-complete", action="raise")
        ])
        with plan.armed():
            fe.start()
            with pytest.raises(FaultError):
                doomed.result(30.0)
        # the frontend still serves — and the wrapper's in-flight slot
        # was released, so the next round begins cleanly. The doomed
        # op's append DID land (post-append failure), so the register
        # already moved to 1.
        assert fe.call((SR_SET, 0, 2), rid=0, timeout=30.0) == 1
        assert fe.call((SR_SET, 0, 3), rid=0, timeout=30.0) == 2
        fe.close()

    def test_failover_completion_kill_then_restart_serves(self):
        # the completion-stage kill fires BEFORE finish_mut_batch, so
        # the begun round must be aborted during failover — otherwise
        # restart_replica yields a replica whose first begin refuses
        # forever ("already has a round in flight")
        from node_replication_tpu.fault.inject import (
            FaultPlan,
            FaultSpec,
        )
        from node_replication_tpu.serve import ReplicaFailed

        nr = small_nr(make_seqreg(2), n_replicas=1)
        fe = ServeFrontend(
            nr, fast_cfg(pipeline_depth=1, batch_max_ops=4,
                         failover=True),
            auto_start=False,
        )
        doomed = fe.submit((SR_SET, 0, 1), rid=0)
        plan = FaultPlan([
            FaultSpec(site="serve-complete", action="raise")
        ])
        with plan.armed():
            fe.start()
            exc = doomed.exception(30.0)
        assert isinstance(exc, ReplicaFailed) and exc.maybe_executed
        # restart WITHOUT the lifecycle manager's fence/repair cycle
        # (the path that cannot rely on fence_replica's cleanup)
        fe.restart_replica(0)
        # the killed round's op was appended and replays: register is 1
        assert fe.call((SR_SET, 0, 2), rid=0, timeout=30.0) == 1
        fe.close()

    def test_cnr_serial_batch_refused_while_split_in_flight(self):
        ml = MultiLogReplicated(
            make_seqreg(4), lambda opc, args: args[0], nlogs=2,
            n_replicas=1, log_entries=128, gc_slack=8, exec_window=16,
        )
        pending = ml.begin_mut_batch(
            [(SR_SET, 0, 1), (SR_SET, 1, 1)], rid=0
        )
        with pytest.raises(RuntimeError):
            ml.execute_mut_batch([(SR_SET, 2, 1)], rid=0)
        assert ml.finish_mut_batch(pending) == [0, 0]
        # with the split round finished, serial batches run again
        assert ml.execute_mut_batch([(SR_SET, 2, 1)], rid=0) == [0]
