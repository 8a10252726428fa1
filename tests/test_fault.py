"""fault/ — replica lifecycle (ISSUE 4): injection determinism, the
health state machine, fenced-head GC progress, repair bit-identity,
and serve failover under injected kills.

The failover test is the acceptance story: clients drive sequence-
numbered ops through a failover-enabled frontend while a FaultPlan
kills a replica's worker; every client must get either a correct
response or a retryable `ReplicaFailed` — no hangs, and no duplicates
after retry (the seqreg oracle would surface a duplicate as a
mismatched previous-value response).
"""

import threading
import time

import numpy as np
import pytest

from node_replication_tpu import NodeReplicated
from node_replication_tpu.core.replica import ReplicaFencedError
from node_replication_tpu.fault import (
    HEALTHY,
    MAX_STALL_S,
    QUARANTINED,
    REPAIRING,
    SUSPECT,
    FaultError,
    FaultPlan,
    FaultSpec,
    HealthTracker,
    IllegalTransition,
    ReplicaLifecycleManager,
    corrupt_states,
    divergence_vote,
    fault_hook,
    repair_replica,
)
from node_replication_tpu.models import (
    HM_GET,
    HM_PUT,
    SR_GET,
    SR_SET,
    make_hashmap,
    make_seqreg,
)
from node_replication_tpu.serve import (
    ReplicaFailed,
    RetryPolicy,
    ServeConfig,
    ServeFrontend,
    call_with_retry,
)


def small_nr(dispatch=None, **kw):
    kw.setdefault("n_replicas", 2)
    kw.setdefault("log_entries", 512)
    kw.setdefault("gc_slack", 32)
    kw.setdefault("exec_window", 64)
    return NodeReplicated(dispatch or make_seqreg(4), **kw)


class TestFaultPlanDeterminism:
    def test_same_seed_same_schedule(self):
        a = FaultPlan.chaos(seed=42, n_faults=5, n_replicas=4)
        b = FaultPlan.chaos(seed=42, n_faults=5, n_replicas=4)
        assert a.schedule() == b.schedule()

    def test_different_seed_different_schedule(self):
        a = FaultPlan.chaos(seed=1, n_faults=8, n_replicas=4)
        b = FaultPlan.chaos(seed=2, n_faults=8, n_replicas=4)
        assert a.schedule() != b.schedule()

    def test_fires_on_exact_hit_and_spends(self):
        plan = FaultPlan([FaultSpec(site="append", action="raise",
                                    rid=0, after=2, count=1)])
        with plan.armed():
            fault_hook("append", 0)  # hit 0
            fault_hook("append", 0)  # hit 1
            with pytest.raises(FaultError) as ei:
                fault_hook("append", 0)  # hit 2: fires
            assert ei.value.site == "append" and ei.value.rid == 0
            fault_hook("append", 0)  # spent: no second fire
        assert [f["hit"] for f in plan.fired] == [2]

    def test_rid_filter_and_site_isolation(self):
        plan = FaultPlan([FaultSpec(site="replay", action="raise",
                                    rid=1, after=0)])
        with plan.armed():
            fault_hook("append", 1)   # wrong site
            fault_hook("replay", 0)   # wrong rid
            with pytest.raises(FaultError):
                fault_hook("replay", 1)
        assert len(plan.fired) == 1

    def test_disarmed_is_inert(self):
        plan = FaultPlan([FaultSpec(site="replay", action="raise")])
        fault_hook("replay", 0)  # not armed: nothing happens
        plan.arm()
        plan.disarm()
        fault_hook("replay", 0)
        assert plan.fired == []

    def test_same_call_sequence_same_fires(self):
        # determinism end to end: replaying the same hook sequence
        # against two same-seed plans fires identically
        def drive(plan):
            hits = []
            with plan.armed():
                for site, rid in [("replay", 0), ("append", 1),
                                  ("replay", 1), ("serve-batch", 0),
                                  ("replay", 0), ("append", 1)]:
                    try:
                        fault_hook(site, rid)
                    except FaultError:
                        pass
                    time.sleep(0)  # scheduler noise must not matter
                hits = [dict(f) for f in plan.fired]
            return hits

        p1 = FaultPlan.chaos(seed=9, n_faults=4, n_replicas=2,
                             actions=("raise",), max_after=3)
        p2 = FaultPlan.chaos(seed=9, n_faults=4, n_replicas=2,
                             actions=("raise",), max_after=3)
        assert drive(p1) == drive(p2)

    def test_rid_filtered_after_counts_victim_hits_only(self):
        # determinism under concurrency: a rid-filtered spec triggers
        # on the VICTIM's own hit sequence — other replicas' hits at
        # the same site (whatever the thread interleaving produced)
        # must not advance it
        plan = FaultPlan([FaultSpec(site="serve-batch",
                                    action="raise", rid=1, after=2)])
        with plan.armed():
            for _ in range(10):
                fault_hook("serve-batch", 0)  # noise from replica 0
            fault_hook("serve-batch", 1)  # victim hit 0
            fault_hook("serve-batch", 1)  # victim hit 1
            with pytest.raises(FaultError):
                fault_hook("serve-batch", 1)  # victim hit 2: fires
        assert plan.fired[0]["hit"] == 2

    def test_stall_is_bounded(self):
        spec = FaultSpec(site="replay", action="stall", stall_s=999.0)
        assert spec.effective_stall_s == MAX_STALL_S
        plan = FaultPlan([FaultSpec(site="replay", action="stall",
                                    stall_s=0.01)])
        t0 = time.monotonic()
        with plan.armed():
            fault_hook("replay", 0)
        assert 0.005 <= time.monotonic() - t0 < 1.0
        assert plan.fired[0]["action"] == "stall"

    def test_spec_validation(self):
        with pytest.raises(ValueError):
            FaultSpec(site="bogus", action="raise")
        with pytest.raises(ValueError):
            FaultSpec(site="replay", action="bogus")
        with pytest.raises(ValueError):
            FaultSpec(site="replay", action="raise", count=0)


class TestHealthStateMachine:
    def test_full_lifecycle_walk(self):
        h = HealthTracker(2)
        assert h.state(0) == HEALTHY
        assert h.report_worker_exception(0) == SUSPECT
        h.transition(0, QUARANTINED)
        h.transition(0, REPAIRING)
        h.transition(0, HEALTHY)
        assert h.state(0) == HEALTHY
        assert h.state(1) == HEALTHY  # untouched
        walked = [(rid, frm, to) for _, rid, frm, to in h.timeline]
        assert walked == [
            (0, HEALTHY, SUSPECT), (0, SUSPECT, QUARANTINED),
            (0, QUARANTINED, REPAIRING), (0, REPAIRING, HEALTHY),
        ]

    def test_timeline_stamps_use_injected_clock(self):
        # ISSUE 8 satellite regression: under SimClock the lifecycle
        # timeline (and obs/report.py's fault section built from it)
        # carries VIRTUAL stamps — a simulated quarantine at t=100.5
        # is recorded at t=100.5, not at some wall-clock instant
        from node_replication_tpu.utils.clock import SimClock, installed

        with installed(SimClock(start=100.0)) as clock:
            h = HealthTracker(1)
            h.report_worker_exception(0)
            clock.advance(0.5)
            h.quarantine(0)
        stamps = [ts for ts, *_ in h.timeline]
        assert stamps == [100.0, 100.5]

    def test_illegal_transitions_raise(self):
        h = HealthTracker(1)
        with pytest.raises(IllegalTransition):
            h.transition(0, REPAIRING)  # healthy -> repairing
        h.report_worker_exception(0)
        with pytest.raises(IllegalTransition):
            h.transition(0, REPAIRING)  # suspect -> repairing

    def test_failed_repair_goes_back_to_quarantine(self):
        h = HealthTracker(1)
        h.quarantine(0)
        h.transition(0, REPAIRING)
        h.transition(0, QUARANTINED)  # legal: repair failed
        assert h.state(0) == QUARANTINED

    def test_stall_threshold(self):
        h = HealthTracker(1, stall_threshold=3)
        assert h.report_stall(0) == HEALTHY
        assert h.report_stall(0) == HEALTHY
        assert h.report_stall(0) == SUSPECT

    def test_probation_clears_strikes(self):
        h = HealthTracker(1, exc_threshold=2)
        h.report_worker_exception(0)
        h.report_worker_exception(0)
        assert h.state(0) == SUSPECT
        h.clear_suspect(0)
        assert h.state(0) == HEALTHY
        # strikes were reset: one new strike does not re-suspect
        assert h.report_worker_exception(0) == HEALTHY

    def test_healthy_rids_and_grow(self):
        h = HealthTracker(3)
        h.quarantine(1)
        assert h.healthy_rids() == [0, 2]
        h.grow(2)
        assert h.healthy_rids() == [0, 2, 3, 4]

    def test_divergence_vote_names_minority(self):
        nr = small_nr(make_seqreg(4), n_replicas=3)
        nr.execute_mut_batch([(SR_SET, i % 4, i + 1)
                              for i in range(12)], rid=0)
        nr.sync()
        assert divergence_vote(nr.states) == []
        nr.states = corrupt_states(nr.states, 1)
        assert divergence_vote(nr.states) == [1]

    def test_vote_without_quorum_names_nobody(self):
        # a 1-1 split in a 2-replica fleet has no strict majority: the
        # vote must NOT name anyone — acting on an arbitrary bloc
        # could quarantine the healthy replica and clone the corrupt
        # donor fleet-wide
        nr = small_nr(make_seqreg(4), n_replicas=2)
        nr.execute_mut_batch([(SR_SET, 0, 1)], rid=0)
        nr.sync()
        nr.states = corrupt_states(nr.states, 0)
        assert divergence_vote(nr.states) == []
        h = HealthTracker(2)
        assert h.probe(nr.states) == []
        assert h.states() == [HEALTHY, HEALTHY]

    def test_probe_quarantines_minority(self):
        nr = small_nr(make_seqreg(4), n_replicas=3)
        nr.sync()
        nr.states = corrupt_states(nr.states, 2)
        h = HealthTracker(3)
        assert h.probe(nr.states) == [2]
        assert h.state(2) == QUARANTINED
        # a second probe does not re-quarantine (already in pipeline)
        assert h.probe(nr.states) == [2]
        assert h.states().count(QUARANTINED) == 1


class TestFencedGC:
    def test_fenced_head_advances_scan_engine(self):
        # seqreg has no window form: the scan engine's fenced path
        nr = small_nr(make_seqreg(2), log_entries=128, gc_slack=16)
        nr.execute_mut_batch([(SR_SET, 0, i + 1)
                              for i in range(20)], rid=0)
        nr.sync()
        nr.fence_replica(1)
        expect = 20
        # 3 x 60 appends push tail to 200 > capacity 128: impossible
        # unless GC advanced head past the fenced replica's ltail (20)
        for _ in range(3):
            resps = nr.execute_mut_batch(
                [(SR_SET, 0, expect + j + 1) for j in range(60)],
                rid=0,
            )
            assert resps == [expect + j for j in range(60)]
            expect += 60
        ltails = np.asarray(nr.log.ltails)
        assert int(ltails[1]) == 20  # frozen
        assert int(np.asarray(nr.log.head)) > 20  # GC passed it
        assert int(np.asarray(nr.log.tail)) == 200
        assert nr.fenced_rids == [1]

    def test_fenced_head_advances_union_engine(self):
        # hashmap routes through the combined catch-up engine
        nr = small_nr(make_hashmap(32), log_entries=128, gc_slack=16)
        assert nr.engine == "combined"
        nr.execute_mut_batch([(HM_PUT, i % 32, i)
                              for i in range(20)], rid=0)
        nr.sync()
        nr.fence_replica(1)
        for _ in range(3):
            nr.execute_mut_batch(
                [(HM_PUT, j % 32, j + 100) for j in range(60)], rid=0
            )
        assert int(np.asarray(nr.log.head)) > 20
        assert int(np.asarray(nr.log.ltails)[1]) == 20

    def test_fenced_guards_fail_fast(self):
        nr = small_nr(make_seqreg(2))
        tok = nr.register(1)
        nr.fence_replica(1)
        with pytest.raises(ReplicaFencedError):
            nr.execute_mut_batch([(SR_SET, 0, 1)], rid=1)
        with pytest.raises(ReplicaFencedError):
            nr.execute((SR_GET, 0), tok)
        with pytest.raises(ReplicaFencedError):
            nr.sync(1)
        nr.sync()  # all-replica sync skips the fenced one: no hang

    def test_fence_idempotent_unfence_restores_fast_path(self):
        nr = small_nr(make_seqreg(2))
        nr.fence_replica(1)
        nr.fence_replica(1)
        assert nr.fenced_rids == [1]
        nr.clone_replica_from(1)
        nr.unfence_replica(1)
        nr.unfence_replica(1)
        assert nr.fenced_rids == []
        assert nr._fenced is None  # no-mask hot path restored

    def test_grow_fleet_never_clones_fenced_donor(self):
        nr = small_nr(make_seqreg(2), n_replicas=2)
        nr.execute_mut_batch([(SR_SET, 0, i + 1)
                              for i in range(8)], rid=0)
        nr.sync()
        nr.states = corrupt_states(nr.states, 1)
        nr.fence_replica(1)
        with pytest.raises(ReplicaFencedError):
            nr.grow_fleet(1, donor=1)
        new = nr.grow_fleet(1)  # auto-donor must pick replica 0
        repair_replica(nr, 1)
        nr.sync()
        assert nr.replicas_equal()
        assert nr.n_replicas == 3 and new == [2]

    def test_snapshot_reports_fenced(self):
        nr = small_nr(make_seqreg(2))
        nr.fence_replica(0)
        assert nr.snapshot()["replicas"]["fenced"] == [0]


class TestRepairBitIdentity:
    def test_repaired_state_matches_never_faulted_fleet(self):
        # fleet A suffers a corruption + quarantine + repair mid-way
        # through an op stream; fleet B runs the same stream untouched.
        # Deterministic replay makes their final states bit-identical.
        def ops(base):
            return [(SR_SET, i % 4, base + i + 1) for i in range(40)]

        a = small_nr(make_seqreg(4), n_replicas=3)
        b = small_nr(make_seqreg(4), n_replicas=3)
        a.execute_mut_batch(ops(0), rid=0)
        b.execute_mut_batch(ops(0), rid=0)
        a.sync()
        b.sync()

        a.states = corrupt_states(a.states, 1)
        assert divergence_vote(a.states) == [1]
        a.fence_replica(1)
        a.execute_mut_batch(ops(100), rid=0)  # traffic during repair
        b.execute_mut_batch(ops(100), rid=0)
        report = repair_replica(a, 1)
        assert report["rid"] == 1 and report["donor"] != 1
        a.sync()
        b.sync()
        assert a.replicas_equal() and b.replicas_equal()
        assert divergence_vote(a.states) == []
        import jax

        for la, lb in zip(jax.tree.leaves(a.states),
                          jax.tree.leaves(b.states)):
            assert np.array_equal(np.asarray(la), np.asarray(lb))

    def test_repair_after_ring_wrap(self):
        # the fenced cursor falls behind the GC head and the ring
        # wraps over its entries; repair must still be exact because
        # it replays from the DONOR's cursor, not the corpse's
        nr = small_nr(make_seqreg(2), log_entries=128, gc_slack=16)
        nr.execute_mut_batch([(SR_SET, 0, i + 1)
                              for i in range(10)], rid=0)
        nr.sync()
        nr.fence_replica(1)
        expect = 10
        for _ in range(4):
            nr.execute_mut_batch(
                [(SR_SET, 0, expect + j + 1) for j in range(60)],
                rid=0,
            )
            expect += 60
        assert int(np.asarray(nr.log.tail)) > 128  # wrapped
        repair_replica(nr, 1)
        nr.sync()
        assert nr.replicas_equal()
        reader = nr.register(1)
        assert nr.execute((SR_GET, 0), reader) == expect

    def test_manager_probe_repairs_silent_corruption(self):
        nr = small_nr(make_seqreg(4), n_replicas=3)
        nr.execute_mut_batch([(SR_SET, i % 4, i + 1)
                              for i in range(12)], rid=0)
        nr.sync()
        mgr = ReplicaLifecycleManager(nr)
        assert mgr.probe() == []  # healthy fleet: vote is unanimous
        nr.states = corrupt_states(nr.states, 2)
        assert mgr.probe() == [2]
        assert mgr.health.state(2) == HEALTHY  # repaired
        assert len(mgr.repairs) == 1
        nr.sync()
        assert nr.replicas_equal()


class TestServeFailover:
    CLIENTS = 8
    PER_CLIENT = 60

    def test_kill_under_load_no_loss_no_dup_no_hang(self):
        """The acceptance story: 8 clients, a kill mid-run, and every
        client gets either a correct response or a retryable
        `ReplicaFailed`; with retry enabled nothing is lost and the
        seqreg oracle proves nothing duplicated."""
        nr = small_nr(make_seqreg(self.CLIENTS), n_replicas=2,
                      log_entries=2048, gc_slack=128,
                      exec_window=128)
        fe = ServeFrontend(nr, ServeConfig(
            queue_depth=128, batch_max_ops=16, batch_linger_s=0.0,
            failover=True,
        ))
        mgr = ReplicaLifecycleManager(nr, fe)
        plan = FaultPlan([FaultSpec(site="serve-batch",
                                    action="raise", rid=1, after=10)])
        errors: list = []

        def client(c):
            rid = c % 2
            pol = RetryPolicy(max_attempts=16, base_backoff_s=0.001,
                              max_backoff_s=0.1)
            for i in range(self.PER_CLIENT):
                try:
                    resp = call_with_retry(
                        fe, (SR_SET, c, i + 1), rid=rid, policy=pol,
                        timeout=120.0,
                    )
                except ReplicaFailed as e:
                    # acceptable ONLY if typed retryable (policy
                    # exhausted); an unretryable one means a possible
                    # duplicate and fails the test
                    if not e.retryable:
                        errors.append((c, i, "unretryable", str(e)))
                    else:
                        errors.append((c, i, "exhausted", str(e)))
                    return
                except Exception as e:  # no hangs, no untyped errors
                    errors.append((c, i, type(e).__name__, str(e)))
                    return
                if resp != i:
                    errors.append((c, i, "sequence", resp))
                    return

        with plan.armed():
            threads = [threading.Thread(target=client, args=(c,))
                       for c in range(self.CLIENTS)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=300)
            assert not any(t.is_alive() for t in threads), "hung client"
        assert not errors, errors[:5]
        assert plan.fired, "kill never fired"
        assert mgr.wait_idle(60)
        assert mgr.health.state(1) == HEALTHY
        assert len(mgr.repairs) == 1
        # the repaired replica serves again on its own queue
        assert fe.healthy_rids() == [0, 1]
        assert fe.call((SR_SET, 0, self.PER_CLIENT + 1), rid=1,
                       timeout=60.0) == self.PER_CLIENT
        st = fe.stats()
        assert st["completed"] == self.CLIENTS * self.PER_CLIENT + 1
        fe.close()
        nr.sync()
        assert nr.replicas_equal()

    def test_submit_to_failed_replica_is_typed_retryable(self):
        nr = small_nr(make_seqreg(2))
        fe = ServeFrontend(nr, ServeConfig(batch_linger_s=0.0,
                                           failover=True))
        mgr = ReplicaLifecycleManager(nr, fe)
        plan = FaultPlan([FaultSpec(site="serve-batch",
                                    action="raise", rid=1, after=0)])
        with plan.armed():
            fut = fe.submit((SR_SET, 0, 1), rid=1)
            with pytest.raises(ReplicaFailed) as ei:
                fut.result(30.0)
            assert ei.value.retryable  # pre-append kill: exactly-once
            # mid-quarantine submits are typed + retryable, never hangs
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                try:
                    fe.submit((SR_SET, 0, 2), rid=1)
                    break  # restarted already
                except ReplicaFailed as e:
                    assert e.retryable
                    time.sleep(0.01)
        assert mgr.wait_idle(60)
        assert fe.call((SR_SET, 1, 1), rid=1, timeout=30.0) == 0
        fe.close()

    def test_queued_requests_rehomed_to_healthy_replica(self):
        # a paused frontend stacks a backlog on the victim; the first
        # batch takes some, the kill re-homes the remainder onto the
        # healthy replica — every future still resolves correctly
        # (fresh slots: order across replicas is immaterial)
        nr = small_nr(make_seqreg(16), n_replicas=2)
        fe = ServeFrontend(
            nr,
            ServeConfig(queue_depth=32, batch_max_ops=4,
                        batch_linger_s=0.0, failover=True),
            auto_start=False,
        )
        mgr = ReplicaLifecycleManager(nr, fe)
        plan = FaultPlan([FaultSpec(site="serve-batch",
                                    action="raise", rid=1, after=0)])
        futs = [fe.submit((SR_SET, s, 7), rid=1) for s in range(12)]
        with plan.armed():
            fe.start()
            outcomes = []
            for s, fut in enumerate(futs):
                try:
                    outcomes.append(("ok", fut.result(60.0)))
                except ReplicaFailed as e:
                    assert e.retryable
                    outcomes.append(("failed", None))
        assert mgr.wait_idle(60)
        oks = [o for o in outcomes if o[0] == "ok"]
        # the first batch (up to batch_max_ops) died; the re-homed
        # remainder completed with the correct previous value 0
        assert len(oks) >= 12 - 4
        assert all(v == 0 for _, v in oks)
        assert fe.stats()["rehomed"] >= 8
        fe.close()

    def test_maybe_executed_is_not_auto_retried(self):
        class OneShotFrontend:
            def __init__(self):
                self.calls = 0

            def call(self, op, rid=0, deadline_s=None, timeout=None):
                self.calls += 1
                raise ReplicaFailed(rid, RuntimeError("mid-replay"),
                                    maybe_executed=True)

            def healthy_rids(self):
                return [0, 1]

        fe = OneShotFrontend()
        with pytest.raises(ReplicaFailed) as ei:
            call_with_retry(fe, (SR_SET, 0, 1),
                            policy=RetryPolicy(max_attempts=5))
        assert fe.calls == 1  # refused: retry could duplicate the op
        assert not ei.value.retryable

    def test_retry_reroutes_to_healthy_rid(self):
        class FailThenServe:
            def __init__(self):
                self.rids_seen = []

            def call(self, op, rid=0, deadline_s=None, timeout=None):
                self.rids_seen.append(rid)
                if rid == 1:
                    raise ReplicaFailed(1, maybe_executed=False)
                return 42

            def healthy_rids(self):
                return [0]

        fe = FailThenServe()
        out = call_with_retry(
            fe, (SR_SET, 0, 1), rid=1,
            policy=RetryPolicy(max_attempts=4, base_backoff_s=0.0001,
                               max_backoff_s=0.001),
        )
        assert out == 42
        assert fe.rids_seen == [1, 0]

    def test_repair_runs_even_below_suspect_threshold(self):
        # a tracker with exc_threshold > 1 leaves the replica HEALTHY
        # after the single report that killed its worker; the medic
        # must still quarantine (through SUSPECT) and repair — not die
        # on an illegal HEALTHY -> QUARANTINED edge
        nr = small_nr(make_seqreg(2))
        fe = ServeFrontend(nr, ServeConfig(batch_linger_s=0.0,
                                           failover=True))
        mgr = ReplicaLifecycleManager(
            nr, fe, health=HealthTracker(2, exc_threshold=3)
        )
        plan = FaultPlan([FaultSpec(site="serve-batch",
                                    action="raise", rid=1, after=0)])
        with plan.armed():
            fut = fe.submit((SR_SET, 0, 1), rid=1)
            with pytest.raises(ReplicaFailed):
                fut.result(30.0)
        assert mgr.wait_idle(60)
        assert len(mgr.repairs) == 1
        assert mgr.health.state(1) == HEALTHY
        assert fe.call((SR_SET, 0, 1), rid=1, timeout=30.0) == 0
        fe.close()

    def test_closed_frontend_wins_over_failed_replica(self):
        # FrontendClosed is permanent; after close() a still-failed
        # rid must not feed retry loops a retryable ReplicaFailed
        from node_replication_tpu.serve import FrontendClosed

        nr = small_nr(make_seqreg(2))
        fe = ServeFrontend(nr, ServeConfig(batch_linger_s=0.0,
                                           failover=True))
        # no lifecycle manager: the replica stays failed
        plan = FaultPlan([FaultSpec(site="serve-batch",
                                    action="raise", rid=1, after=0)])
        with plan.armed():
            fut = fe.submit((SR_SET, 0, 1), rid=1)
            with pytest.raises(ReplicaFailed):
                fut.result(30.0)
        with pytest.raises(ReplicaFailed):
            fe.submit((SR_SET, 0, 2), rid=1)  # open + failed: typed
        fe.close()
        with pytest.raises(FrontendClosed):
            fe.submit((SR_SET, 0, 3), rid=1)  # closed: permanent

    def test_rehome_does_not_double_count_accepted(self):
        nr = small_nr(make_seqreg(8), n_replicas=2)
        fe = ServeFrontend(
            nr,
            ServeConfig(queue_depth=32, batch_max_ops=4,
                        batch_linger_s=0.0, failover=True),
            auto_start=False,
        )
        mgr = ReplicaLifecycleManager(nr, fe)
        plan = FaultPlan([FaultSpec(site="serve-batch",
                                    action="raise", rid=1, after=0)])
        futs = [fe.submit((SR_SET, s, 7), rid=1) for s in range(8)]
        assert fe.stats()["accepted"] == 8
        with plan.armed():
            fe.start()
            for fut in futs:
                try:
                    fut.result(60.0)
                except ReplicaFailed:
                    pass
        assert mgr.wait_idle(60)
        fe.drain(30.0)
        st = fe.stats()
        # re-homing moved requests, it did not re-admit them: the 8
        # original admissions stay 8 (retired-queue folding included)
        assert st["accepted"] == 8, st
        assert st["rehomed"] >= 4
        fe.close()

    def test_restart_requires_failed_replica(self):
        nr = small_nr(make_seqreg(2))
        fe = ServeFrontend(nr, ServeConfig(failover=True))
        with pytest.raises(ValueError):
            fe.restart_replica(0)
        fe.close()

    def test_failover_off_keeps_worker_alive(self):
        # the pre-fault contract: without failover a failed batch
        # rejects its own futures and the SAME worker keeps serving
        nr = small_nr(make_seqreg(2))
        fe = ServeFrontend(nr, ServeConfig(batch_linger_s=0.0))
        plan = FaultPlan([FaultSpec(site="serve-batch",
                                    action="raise", rid=0, after=0)])
        with plan.armed():
            fut = fe.submit((SR_SET, 0, 1), rid=0)
            with pytest.raises(FaultError):
                fut.result(30.0)
        assert fe.healthy_rids() == [0, 1]
        assert fe.call((SR_SET, 0, 1), rid=0, timeout=30.0) == 0
        fe.close()


class TestMeasureChaos:
    def test_measure_chaos_and_rows(self):
        from node_replication_tpu.harness.mkbench import (
            chaos_rows,
            measure_chaos,
        )

        clients = 4
        nr = small_nr(make_seqreg(clients), n_replicas=2,
                      log_entries=2048, gc_slack=128)
        fe = ServeFrontend(nr, ServeConfig(
            queue_depth=64, batch_max_ops=8, batch_linger_s=0.0,
            failover=True,
        ))
        mgr = ReplicaLifecycleManager(nr, fe)
        plan = FaultPlan([FaultSpec(site="serve-batch",
                                    action="raise", rid=1, after=5)])

        def check(c, i, resp):
            return None if resp == i else f"{c}/{i}: {resp}"

        with fe:
            res = measure_chaos(
                fe, mgr, plan, lambda c, i: (SR_SET, c, i + 1),
                120, clients, retry=RetryPolicy(max_attempts=16),
                check=check, name="t",
            )
        assert res.serve.completed == 120
        assert res.serve.errors == []
        assert res.availability == 1.0
        assert len(res.fired) == 1 and len(res.repairs) == 1
        assert res.health["states"] == [HEALTHY, HEALTHY]
        assert res.repair_ms(50) > 0
        (row,) = chaos_rows("t", res)
        assert row["lost"] == 0 and row["kills"] == 1
        assert row["availability"] == 1.0
        assert row["repair_p95_ms"] >= row["repair_p50_ms"] > 0


class TestFaultReportSection:
    def test_fault_section_from_events(self):
        from node_replication_tpu.obs.report import analyze, render

        events = [
            {"event": "fault-inject", "mono": 10.0, "site":
                "serve-batch", "rid": 1, "action": "raise"},
            {"event": "fault-transition", "mono": 10.1, "rid": 1,
             "frm": "healthy", "to": "suspect"},
            {"event": "fault-transition", "mono": 10.2, "rid": 1,
             "frm": "suspect", "to": "quarantined"},
            {"event": "fault-transition", "mono": 10.3, "rid": 1,
             "frm": "quarantined", "to": "repairing"},
            {"event": "fault-repair", "mono": 10.8, "rid": 1,
             "donor": 0, "duration_s": 0.5},
            {"event": "fault-transition", "mono": 10.8, "rid": 1,
             "frm": "repairing", "to": "healthy"},
            {"event": "serve-rehome", "mono": 10.15, "rid": 1, "n": 3},
        ]
        rep = analyze(events)
        f = rep["fault"]
        assert f["injected"] == 1 and f["quarantines"] == 1
        assert f["repairs"] == 1 and f["rehomed"] == 3
        assert f["repair_p50_s"] == 0.5
        assert f["repair_hist_ms"] == {512: 1}
        assert [to for _, _, to in f["timeline"][1]] == [
            "suspect", "quarantined", "repairing", "healthy",
        ]
        import io

        out = io.StringIO()
        render(rep, out=out)
        text = out.getvalue()
        assert "== fault ==" in text
        assert "re-homed requests: 3" in text
        assert "r1:" in text

    def test_no_fault_events_no_section(self):
        from node_replication_tpu.obs.report import analyze, render

        rep = analyze([{"event": "append", "mono": 1.0, "n": 2}])
        assert rep["fault"] is None
        import io

        out = io.StringIO()
        render(rep, out=out)
        assert "== fault ==" not in out.getvalue()

    def test_lifecycle_events_flow_to_report(self):
        # end to end: a real quarantine+repair, traced in memory mode,
        # renders a fault section
        from node_replication_tpu.obs.report import analyze
        from node_replication_tpu.obs.recorder import get_tracer

        tracer = get_tracer()
        was = tracer.enabled
        tracer.enable(None)  # memory-buffer mode
        try:
            # 3 replicas: the digest vote needs a strict majority
            nr = small_nr(make_seqreg(4), n_replicas=3)
            nr.execute_mut_batch([(SR_SET, 0, 1)], rid=0)
            nr.sync()
            mgr = ReplicaLifecycleManager(nr)
            nr.states = corrupt_states(nr.states, 1)
            mgr.probe()
            rep = analyze(list(tracer.events()))
            assert rep["fault"] is not None
            assert rep["fault"]["quarantines"] == 1
            assert rep["fault"]["repairs"] == 1
        finally:
            if not was:
                tracer.disable()
