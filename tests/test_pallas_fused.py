"""Fused append+replay engine tests (ISSUE 11; interpret mode on CPU).

The fused pallas combiner round (`ops/pallas_replay.FusedHashmapEngine`,
`ops/pallas_vspace.FusedVspaceEngine`) must be BIT-IDENTICAL to the scan
engine across every path it replaces: plain batches, NOOP padding,
ring-wrap windows, fenced replicas, the wrapper batch entry point, and
the CNR per-log sub-batch path — plus the tier's routing
(`core/replica._FusedTier`: forced by `engine="pallas"`, never consulted
by `engine="auto"`) asserted via the `log.engine.*` /
`nr.exec.engine.*` counters, and a serve round-trip whose `serve-batch`
events carry the engine tier. `bench.py --kernel --kernel-interpret` is
the CI twin of the bit-identity half (kernel-smoke job).
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from node_replication_tpu.core.log import (
    LogSpec,
    log_append,
    log_exec_all,
    log_init,
)
from node_replication_tpu.core.replica import NodeReplicated, replicate_state
from node_replication_tpu.models import make_hashmap
from node_replication_tpu.obs.metrics import get_registry
from node_replication_tpu.ops.encoding import encode_ops
from node_replication_tpu.ops.pallas_ring import (
    fused_window_ok,
    window_rows,
)


def _mixed_ops(rng, n, n_keys):
    ops = []
    for _ in range(n):
        if rng.rand() < 0.7:
            ops.append((1, int(rng.randint(n_keys)),
                        int(rng.randint(1000))))
        else:
            ops.append((2, int(rng.randint(n_keys))))
    return ops


def _assert_trees_equal(a, b, what=""):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        xa, ya = np.asarray(x), np.asarray(y)
        assert xa.dtype == ya.dtype, (what, xa.dtype, ya.dtype)
        assert np.array_equal(xa, ya), (what, xa, ya)


class TestRingWindow:
    def test_window_rows_covers_any_phase(self):
        # a window of W slots starting at any 128-phase spans at most
        # window_rows(W) ring rows
        for w in (1, 5, 127, 128, 129, 512):
            rows = window_rows(w)
            worst = (127 + w + 127) // 128  # start at lane 127
            assert rows >= worst, (w, rows, worst)

    def test_fused_window_ok_bounds(self):
        assert fused_window_ok(512, 64)
        assert fused_window_ok(512, 256)
        assert not fused_window_ok(512, 512)   # no room for the spans
        assert not fused_window_ok(96, 8)      # no 128-slot row layout


class TestFusedRoundBitIdentity:
    """Raw engine round vs the scan chain, across wrap + fencing."""

    def test_rounds_including_wrap_and_fence(self):
        K, R = 13, 4
        spec = LogSpec(capacity=256, n_replicas=R, arg_width=3,
                       gc_slack=64)
        d = make_hashmap(K)
        eng = d.fused_factory(spec, interpret=True)
        assert eng.supports(16)
        assert eng.launches(16) == 1

        rng = np.random.RandomState(0)
        log_a, log_b = log_init(spec), log_init(spec)
        st_a = replicate_state(d.init_state(), R)
        st_b = replicate_state(d.init_state(), R)
        fenced = None
        # 30 x (<=12)-op rounds wrap the 256-slot ring twice; fencing
        # toggles mid-run so frozen-cursor GC masking is exercised
        for rnd in range(30):
            n = int(rng.randint(1, 13))
            opc, args, _ = encode_ops(
                _mixed_ops(rng, n, K), 3, pad_to=16
            )
            if rnd == 12:
                fenced = np.zeros(R, bool)
                fenced[2] = True
            if rnd == 20:
                # "repair": reseat the fenced cursor/state from donor
                # 0 in BOTH fleets, then unfence
                fenced = None
                st_a = jax.tree.map(lambda x: x.at[2].set(x[0]), st_a)
                st_b = jax.tree.map(lambda x: x.at[2].set(x[0]), st_b)
                log_a = log_a._replace(
                    ltails=log_a.ltails.at[2].set(log_a.ltails[0]))
                log_b = log_b._replace(
                    ltails=log_b.ltails.at[2].set(log_b.ltails[0]))
            f = None if fenced is None else jnp.asarray(fenced)
            log_a = log_append(spec, log_a, opc, args, n)
            while True:
                lts = np.asarray(log_a.ltails)
                live = lts if fenced is None else lts[~fenced]
                if int(live.min()) >= int(log_a.tail):
                    break
                log_a, st_a, resps_a = log_exec_all(
                    spec, d, log_a, st_a, 16, fenced=f
                )
            log_b, st_b, resps_b = eng.round(
                log_b, st_b, opc, args, n, fenced=fenced
            )
            _assert_trees_equal(st_a, st_b, f"states round {rnd}")
            _assert_trees_equal(log_a, log_b, f"log round {rnd}")
            ra = np.asarray(resps_a)[:, :n]
            rb = np.asarray(resps_b)[:, :n]
            live = np.ones(R, bool) if fenced is None else ~fenced
            assert np.array_equal(ra[live], rb[live]), rnd
            # fenced rows report zeros (the scan engine's frozen rows)
            if fenced is not None:
                assert not np.asarray(resps_b)[fenced].any()

    def test_shard_slice_composability(self):
        # the P('replica') claim: running the round on lane slices of
        # the transposed state (each with its ltails slice) reproduces
        # the full-fleet round bit-for-bit — the chunk call IS the
        # shard-local program
        K, R = 11, 8
        spec = LogSpec(capacity=256, n_replicas=R, arg_width=3,
                       gc_slack=64)
        half = LogSpec(capacity=256, n_replicas=R // 2, arg_width=3,
                       gc_slack=64)
        d = make_hashmap(K)
        eng = d.fused_factory(spec, interpret=True)
        eng_h = d.fused_factory(half, interpret=True)
        rng = np.random.RandomState(5)
        opc, args, _ = encode_ops(_mixed_ops(rng, 8, K), 3, pad_to=8)

        log = log_init(spec)
        st = replicate_state(d.init_state(), R)
        full_log, full_st, full_resps = eng.round(
            log, st, opc, args, 8
        )

        raw = eng_h.raw_round(8)
        kp = eng_h.kp
        shard_states, shard_resps = [], []
        for s0 in (0, R // 2):
            sl = slice(s0, s0 + R // 2)
            vals = jnp.zeros((kp, R // 2), jnp.int32).at[:K].set(
                st["values"][sl].T)
            pres = jnp.zeros_like(vals).at[:K].set(
                st["present"][sl].T.astype(jnp.int32))
            shard_log = log_init(spec)._replace(
                ltails=log_init(spec).ltails[sl]
            )
            out_log, v, p, r = raw(shard_log, vals, pres, opc, args, 8)
            shard_states.append(
                {"values": v[:K].T, "present": p[:K].T > 0}
            )
            shard_resps.append(np.asarray(r).T)
            # every shard computes the identical ring + scalar cursors
            assert np.array_equal(np.asarray(out_log.opcodes),
                                  np.asarray(full_log.opcodes))
            assert int(out_log.tail) == int(full_log.tail)
        got = jax.tree.map(
            lambda *xs: jnp.concatenate(xs, axis=0), *shard_states
        )
        _assert_trees_equal(full_st, got, "shard-sliced states")
        assert np.array_equal(
            np.concatenate(shard_resps, axis=0), np.asarray(full_resps)
        )


class TestWrapperTier:
    def _twins(self, K=29, R=3, **kw):
        nr_f = NodeReplicated(make_hashmap(K), n_replicas=R,
                              log_entries=512, gc_slack=64,
                              engine="pallas", **kw)
        nr_s = NodeReplicated(make_hashmap(K), n_replicas=R,
                              log_entries=512, gc_slack=64,
                              engine="scan", **kw)
        return nr_f, nr_s

    def test_forced_tier_per_op_and_counters(self):
        reg = get_registry()
        reg.enable()
        before = reg.counter("log.engine.pallas_fused").value
        nr_f, nr_s = self._twins()
        t_f = [nr_f.register(r) for r in range(3)]
        t_s = [nr_s.register(r) for r in range(3)]
        rng = np.random.RandomState(1)
        for i in range(25):
            r = int(rng.randint(3))
            op = _mixed_ops(rng, 1, 29)[0]
            assert nr_f.execute_mut(op, t_f[r]) == \
                nr_s.execute_mut(op, t_s[r])
        nr_f.sync(); nr_s.sync()
        _assert_trees_equal(nr_f.states, nr_s.states)
        st = nr_f.stats()
        assert st["fused_tier"] == "forced"
        assert st["fused_rounds"] == 25
        assert st["exec_rounds"] == 0  # every round went fused
        assert reg.counter("log.engine.pallas_fused").value \
            - before == 25
        assert nr_f.last_round_tier == "pallas_fused"
        for k in range(5):
            assert nr_f.execute((1, k), t_f[0]) == \
                nr_s.execute((1, k), t_s[0])

    def test_batch_path_bit_identical(self):
        nr_f, nr_s = self._twins()
        rng = np.random.RandomState(2)
        ops = _mixed_ops(rng, 40, 29)
        assert nr_f.execute_mut_batch(ops, rid=1) == \
            nr_s.execute_mut_batch(ops, rid=1)
        nr_f.sync(); nr_s.sync()
        _assert_trees_equal(nr_f.states, nr_s.states)

    def test_fenced_fleet_round_and_repair(self):
        nr_f, nr_s = self._twins()
        rng = np.random.RandomState(3)
        ops = _mixed_ops(rng, 10, 29)
        nr_f.execute_mut_batch(ops, rid=0)
        nr_s.execute_mut_batch(ops, rid=0)
        for nr in (nr_f, nr_s):
            nr.fence_replica(2)
        ops2 = _mixed_ops(rng, 10, 29)
        assert nr_f.execute_mut_batch(ops2, rid=0) == \
            nr_s.execute_mut_batch(ops2, rid=0)
        assert nr_f.stats()["fused_rounds"] >= 2  # fenced round fused
        for nr in (nr_f, nr_s):
            nr.clone_replica_from(2, donor=0)
            nr.unfence_replica(2)
            nr.sync()
        _assert_trees_equal(nr_f.states, nr_s.states)
        assert nr_f.replicas_equal()

    def test_oversized_window_falls_back(self):
        # pad past capacity/2 cannot ride the ring spans: the round
        # must fall back to the chain, counted, and stay correct
        reg = get_registry()
        reg.enable()
        nr_f, nr_s = self._twins()
        fb = reg.counter("nr.exec.engine.fused_fallback")
        before = fb.value
        rng = np.random.RandomState(4)
        ops = _mixed_ops(rng, 300, 29)  # pad 512 > 512 - 128
        assert nr_f.execute_mut_batch(ops, rid=0) == \
            nr_s.execute_mut_batch(ops, rid=0)
        assert fb.value > before
        assert nr_f.last_round_tier == nr_f.engine  # chain served it
        nr_f.sync(); nr_s.sync()
        _assert_trees_equal(nr_f.states, nr_s.states)

    def test_wal_journals_fused_rounds(self, tmp_path):
        # the durability contract survives the tier swap: a fused
        # round journals exactly the batch at its log positions, and
        # fsync covers it before any later ack could
        from node_replication_tpu.durable.wal import WriteAheadLog

        nr = NodeReplicated(make_hashmap(19), n_replicas=2,
                            log_entries=512, gc_slack=64,
                            engine="pallas")
        wal = WriteAheadLog(str(tmp_path / "wal"), policy="batch",
                            arg_width=3)
        nr.attach_wal(wal)
        ops = [(1, i % 19, i) for i in range(10)]
        nr.execute_mut_batch(ops, rid=0)
        nr.wal_sync()
        assert nr.stats()["fused_rounds"] == 1
        flat = [
            (int(o), tuple(int(x) for x in a))
            for r in wal.records(0)
            for o, a in zip(r.opcodes, r.args)
        ]
        assert flat == [(1, (i % 19, i, 0)) for i in range(10)]
        assert wal.durable_tail == 10
        wal.close()

    def test_grow_fleet_rebuilds_engine(self):
        nr_f, nr_s = self._twins()
        rng = np.random.RandomState(5)
        nr_f.execute_mut_batch(_mixed_ops(rng, 6, 29), rid=0)
        rng = np.random.RandomState(5)
        nr_s.execute_mut_batch(_mixed_ops(rng, 6, 29), rid=0)
        nr_f.grow_fleet(1); nr_s.grow_fleet(1)
        rng = np.random.RandomState(6)
        ops = _mixed_ops(rng, 6, 29)
        assert nr_f.execute_mut_batch(ops, rid=3) == \
            nr_s.execute_mut_batch(ops, rid=3)
        assert nr_f.stats()["fused_rounds"] >= 2
        nr_f.sync(); nr_s.sync()
        _assert_trees_equal(nr_f.states, nr_s.states)

    def test_pallas_engine_validation(self):
        from node_replication_tpu.models import make_seqreg

        with pytest.raises(ValueError, match="fused_factory"):
            NodeReplicated(make_seqreg(4), n_replicas=2,
                           engine="pallas")
        with pytest.raises(ValueError, match="checkify|debug"):
            NodeReplicated(make_hashmap(8), n_replicas=2,
                           log_entries=512, gc_slack=64,
                           engine="pallas", debug=True)


def _vspace_ops(rng, n, pages):
    ops = []
    for _ in range(n):
        if rng.rand() < 0.7:
            ops.append((1, int(rng.randint(pages)),
                        int(rng.randint(1, 1000)),
                        int(rng.randint(0, 12))))
        else:
            ops.append((2, int(rng.randint(pages)),
                        int(rng.randint(0, 12))))
    return ops


class TestAutoEngine:
    """`engine='auto'` is the chain on every backend: the fused tier
    runs only where the caller forces it with `engine='pallas'`."""

    def test_cpu_default_keeps_tier_off(self):
        nr = NodeReplicated(make_hashmap(8), n_replicas=2,
                            log_entries=512, gc_slack=64, engine="auto")
        assert nr.stats()["fused_tier"] == "off"

    @pytest.mark.parametrize("case", [
        "hashmap", "vspace", "hashmap-fenced", "hashmap-mesh2",
        "hashmap-grow", "cnr-hashmap",
    ])
    def test_auto_never_consults_fused_tier(self, case, monkeypatch):
        # on a TPU backend too: no round, batch or split, may build or
        # consult the model's fused engine, and every answer matches
        # the scan twin's
        from node_replication_tpu.core.cnr import MultiLogReplicated
        from node_replication_tpu.models.vspace import make_vspace

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

        def consulted(*args, **kwargs):
            pytest.fail("engine='auto' consulted the fused tier")

        rng = np.random.RandomState(11)
        if case == "vspace":
            model = lambda: make_vspace(512, max_span=8)
            gen = lambda n: _vspace_ops(rng, n, 512)
        else:
            model = lambda: make_hashmap(29)
            gen = lambda n: _mixed_ops(rng, n, 29)
        kw = dict(n_replicas=4, log_entries=512, gc_slack=64)
        auto_model = dataclasses.replace(model(), fused_factory=consulted)
        if case == "cnr-hashmap":
            mapper = lambda opc, args: args[0]
            nr = MultiLogReplicated(auto_model, mapper, nlogs=2,
                                    engine="auto", **kw)
            twin = MultiLogReplicated(model(), mapper, nlogs=2,
                                      engine="scan", **kw)
        else:
            mesh = 2 if case == "hashmap-mesh2" else None
            nr = NodeReplicated(auto_model, engine="auto", mesh=mesh,
                                **kw)
            twin = NodeReplicated(model(), engine="scan", **kw)
        if case == "hashmap-fenced":
            for w in (nr, twin):
                w.fence_replica(3)
        ops = gen(12)
        assert nr.execute_mut_batch(ops, rid=0) == \
            twin.execute_mut_batch(ops, rid=0)
        if case == "hashmap-grow":
            for w in (nr, twin):
                w.grow_fleet(1)
        ops = gen(12)
        p = nr.begin_mut_batch(ops, rid=1)
        assert nr.finish_mut_batch(p) == \
            twin.execute_mut_batch(ops, rid=1)
        # per-op rounds: five of one window, back to back
        t, t_twin = nr.register(2), twin.register(2)
        for op in gen(5):
            assert nr.execute_mut(op, t) == twin.execute_mut(op, t_twin)
        for k in (0, 5, 22):
            assert nr.execute((1, k), t) == twin.execute((1, k), t_twin)
        st = nr.stats()
        assert st["fused_tier"] == "off"
        assert st["fused_rounds"] == 0
        assert nr.round_tier(2) not in ("pallas_fused", "mesh_fused")

    def test_kernel_compile_error_surfaces(self):
        # a Mosaic refusal at the fused round's compile is an error,
        # not a shape rejection: it must reach the caller and leave
        # the tier forced — never a silent, counted switch to the chain
        from jax._src.pallas.mosaic.error_handling import MosaicError

        from node_replication_tpu.ops.pallas_replay import (
            FusedHashmapEngine,
        )

        def refused(self, window, fenced=False):
            def fn(*args):
                raise MosaicError("Mosaic failed to compile TPU kernel")
            return fn

        reg = get_registry()
        reg.enable()
        fb = reg.counter("nr.exec.engine.fused_fallback")
        nr = NodeReplicated(make_hashmap(17), n_replicas=2,
                            log_entries=512, gc_slack=64,
                            engine="pallas")
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(FusedHashmapEngine, "round_fn", refused)
            before = fb.value
            with pytest.raises(MosaicError):
                nr.execute_mut((1, 1, 1), nr.register(0))
            with pytest.raises(MosaicError):
                nr.execute_mut_batch([(1, 2, 2)], rid=1)
            assert fb.value == before
        st = nr.stats()
        assert st["fused_tier"] == "forced"
        assert st["fused_rounds"] == 0


class TestCNRFused:
    def test_per_log_sub_batches_bit_identical(self):
        from node_replication_tpu.core.cnr import MultiLogReplicated

        reg = get_registry()
        reg.enable()
        before = reg.counter("cnr.exec.engine.pallas_fused").value
        mapper = lambda opc, args: args[0]
        c_f = MultiLogReplicated(make_hashmap(23), mapper, nlogs=3,
                                 n_replicas=2, log_entries=512,
                                 gc_slack=64, engine="pallas")
        c_s = MultiLogReplicated(make_hashmap(23), mapper, nlogs=3,
                                 n_replicas=2, log_entries=512,
                                 gc_slack=64, engine="scan")
        rng = np.random.RandomState(3)
        ops = _mixed_ops(rng, 24, 23)
        assert c_f.execute_mut_batch(ops, rid=0) == \
            c_s.execute_mut_batch(ops, rid=0)
        t_f, t_s = c_f.register(1), c_s.register(1)
        for op in _mixed_ops(rng, 10, 23):
            assert c_f.execute_mut(op, t_f) == c_s.execute_mut(op, t_s)
        c_f.sync(); c_s.sync()
        _assert_trees_equal(c_f.states, c_s.states)
        st = c_f.stats()
        assert st["fused_tier"] == "forced"
        assert st["fused_rounds"] > 0
        assert st["exec_rounds"] == 0
        assert reg.counter("cnr.exec.engine.pallas_fused").value > before
        for k in (1, 5, 22):
            assert c_f.execute((1, k), t_f) == c_s.execute((1, k), t_s)


class TestServeFused:
    def test_serve_roundtrip_and_event_tier(self):
        from node_replication_tpu.serve import ServeConfig, ServeFrontend
        from node_replication_tpu.obs.recorder import get_tracer

        nr = NodeReplicated(make_hashmap(31), n_replicas=2,
                            log_entries=512, gc_slack=64,
                            engine="pallas")
        t = get_tracer()
        t.enable(None)
        try:
            with ServeFrontend(
                nr, ServeConfig(queue_depth=32, batch_max_ops=8,
                                batch_linger_s=0.002),
            ) as fe:
                for i in range(30):
                    assert fe.call((1, i % 31, i),
                                   rid=fe.rids[i % 2]) == 0
                assert fe.read((1, 5), rid=fe.rids[0]) >= 0
            events = t.events()
        finally:
            t.disable()
        batches = [e for e in events if e["event"] == "serve-batch"]
        assert batches
        assert all(e.get("engine") == "pallas_fused" for e in batches)
        assert any(e["event"] == "kernel-launch" for e in events)
        assert nr.stats()["fused_rounds"] > 0
        # per-rid attribution (the event's source): each served
        # replica's last round was fused
        for rid in {e["rid"] for e in batches}:
            assert nr.round_tier(rid) == "pallas_fused"


class TestVspaceFused:
    def test_flat_vspace_wrapper_bit_identical(self):
        from node_replication_tpu.models.vspace import make_vspace

        P = 512
        nr_f = NodeReplicated(make_vspace(P, max_span=8), n_replicas=2,
                              log_entries=512, gc_slack=64,
                              engine="pallas")
        nr_s = NodeReplicated(make_vspace(P, max_span=8), n_replicas=2,
                              log_entries=512, gc_slack=64,
                              engine="scan")
        ops = _vspace_ops(np.random.RandomState(7), 20, P)
        assert nr_f.execute_mut_batch(ops, rid=0) == \
            nr_s.execute_mut_batch(ops, rid=0)
        assert nr_f.stats()["fused_rounds"] > 0
        nr_f.sync(); nr_s.sync()
        _assert_trees_equal(nr_f.states, nr_s.states)
        t_f, t_s = nr_f.register(0), nr_s.register(0)
        for k in (0, 5, 100):
            assert nr_f.execute((1, k), t_f) == \
                nr_s.execute((1, k), t_s)

    def test_fenced_fleet_falls_back(self):
        # no fenced kernel variant: a fenced fleet must take the chain
        # (and stay correct), not the fused round
        from node_replication_tpu.models.vspace import make_vspace

        reg = get_registry()
        reg.enable()
        fb = reg.counter("nr.exec.engine.fused_fallback")
        nr = NodeReplicated(make_vspace(512, max_span=8), n_replicas=3,
                            log_entries=512, gc_slack=64,
                            engine="pallas")
        nr.execute_mut_batch([(1, 0, 7, 4)], rid=0)
        assert nr.last_round_tier == "pallas_fused"
        nr.fence_replica(2)
        before = fb.value
        nr.execute_mut_batch([(1, 8, 9, 4)], rid=0)
        assert fb.value > before
        assert nr.last_round_tier == nr.engine

    def test_fenced_split_round_defers(self):
        # with no fenced variant a fenced fleet's split round takes the
        # chain, and the chain still defers its replay to finish and
        # answers as the scan twin does
        from node_replication_tpu.models.vspace import make_vspace

        nr, twin = (
            NodeReplicated(make_vspace(512, max_span=8), n_replicas=3,
                           log_entries=512, gc_slack=64, engine=engine)
            for engine in ("pallas", "scan")
        )
        for w in (nr, twin):
            w.execute_mut_batch([(1, 0, 7, 4)], rid=0)
            w.fence_replica(2)
        ops = [(1, 5, 6, 1), (1, 2, 9, 2)]
        p = nr.begin_mut_batch(ops, rid=0)
        assert p.done is False
        assert nr.finish_mut_batch(p) == \
            twin.execute_mut_batch(ops, rid=0)
        assert nr.last_round_tier == nr.engine
        assert nr.stats()["fused_tier"] == "forced"


class TestMkbenchKernel:
    def test_measure_kernel_rows_and_csv(self, tmp_path):
        from node_replication_tpu.harness.mkbench import (
            KERNEL_CSV,
            append_kernel_csv,
            kernel_rows,
            measure_kernel,
        )

        pts = measure_kernel(32, 4, 32, duration_s=0.05,
                             interpret=True, verify_rounds=2)
        assert {p.tier for p in pts} == {
            "pallas_fused", "combined", "scan"
        }
        assert all(p.bit_identical for p in pts)
        fused = next(p for p in pts if p.tier == "pallas_fused")
        # launches_per_round is the kernel.launches counter delta per
        # timed round, not a hardcoded constant (ISSUE 15 satellite)
        assert fused.launches_per_round == 1
        assert all(p.launches_per_round == 2 for p in pts
                   if p.tier != "pallas_fused")
        rows = kernel_rows("t", pts)
        append_kernel_csv(str(tmp_path), rows)
        body = (tmp_path / KERNEL_CSV).read_text()
        assert "pallas_fused" in body and "dispatches_per_sec" in body

    def test_measure_kernel_mesh_devices(self, tmp_path):
        # the --kernel-devices axis: at devices>1 the sweep measures
        # the MESH tier pair, bit-identity still vs the 1-device scan
        # chain, and launches_per_round (counter-derived) holds at 1
        # per device for the one-launch mesh-fused round
        if len(jax.devices()) < 2:
            pytest.skip("needs 2 virtual devices")
        from node_replication_tpu.harness.mkbench import (
            append_kernel_csv,
            kernel_rows,
            measure_kernel,
        )

        pts = measure_kernel(32, 4, 32, duration_s=0.02,
                             interpret=True, verify_rounds=2,
                             devices=2)
        assert {p.tier for p in pts} == {"mesh_fused", "shmap"}
        assert all(p.bit_identical for p in pts)
        assert all(p.devices == 2 for p in pts)
        fused = next(p for p in pts if p.tier == "mesh_fused")
        shmap = next(p for p in pts if p.tier == "shmap")
        assert fused.launches_per_round == 1
        assert shmap.launches_per_round == 2
        rows = kernel_rows("t", pts)
        append_kernel_csv(str(tmp_path), rows)
        body = (tmp_path / "kernel_benchmarks.csv").read_text()
        assert "mesh_fused" in body
        assert "devices" in body.splitlines()[0]
        # indivisible replica counts are rejected loudly
        with pytest.raises(ValueError):
            measure_kernel(32, 3, 32, interpret=True, devices=2)
