"""Mesh-sharded fleet differential suite (ISSUE 10 + the ISSUE 15
mesh-fused tier).

The acceptance contract of the mesh work: placement changes SPEED,
never results. Every test drives the same op sequence through an
un-meshed wrapper and a mesh-sharded twin (replica axis under
`NamedSharding(mesh, P('replica'))`, 8 forced host devices — see
conftest.py) and requires bit-identical responses, states, and cursor
lattices — scan AND union engines, both collective tiers (shmap /
gspmd), hashmap AND seqreg models, with a fenced-replica case pinning
the cross-device GC-head mask and a ring-tier case pinning the
collective catch-up path. `TestMeshFused` extends the contract to the
MESH-FUSED exec tier (`parallel/collectives.py:MeshFusedEngine`): one
shard_map-wrapped Pallas launch per combiner round, pinned
bit-identical to the un-meshed scan wrapper across ring wraps, a
fence/repair cycle with the corpse on a non-zero shard, and depth-1
pipelined serve. This file is the CI
`mesh-smoke` job (the mesh-fused half also rides `kernel-smoke`).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from node_replication_tpu import NodeReplicated
from node_replication_tpu.core.cnr import MultiLogReplicated
from node_replication_tpu.core.log import log_append
from node_replication_tpu.models import (
    HM_GET,
    HM_PUT,
    SR_GET,
    SR_SET,
    make_hashmap,
    make_seqreg,
)
from node_replication_tpu.obs.metrics import get_registry
from node_replication_tpu.parallel import make_mesh, replica_mesh


@pytest.fixture(scope="module")
def mesh():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    return replica_mesh(8)


def _assert_fleets_equal(ref, got):
    for a, b in zip(jax.tree.leaves(ref.states),
                    jax.tree.leaves(got.states)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(
        np.asarray(ref.log.ltails), np.asarray(got.log.ltails)
    )
    for cursor in ("tail", "ctail", "head"):
        assert int(getattr(ref.log, cursor)) == int(
            getattr(got.log, cursor)
        ), cursor


def _seqreg_pair(mesh, **kw):
    mk = lambda **extra: NodeReplicated(
        make_seqreg(8), n_replicas=8, log_entries=1 << 12,
        gc_slack=64, exec_window=32, **extra,
    )
    return mk(**kw), mk(mesh=mesh, **kw)


def _hashmap_pair(mesh, **kw):
    mk = lambda **extra: NodeReplicated(
        make_hashmap(64), n_replicas=8, log_entries=1 << 12,
        gc_slack=64, exec_window=32, **extra,
    )
    return mk(**kw), mk(mesh=mesh, **kw)


class TestNodeReplicatedMesh:
    def test_scan_engine_shmap_tier_bit_identical(self, mesh):
        # seqreg has no combined form on purpose: the scan engine →
        # the explicit-collective shard_map tier
        ref, got = _seqreg_pair(mesh)
        assert got.engine == "scan" and got._mesh_tier == "shmap"
        t_ref, t_got = ref.register(2), got.register(2)
        for i in range(60):
            op = (SR_SET, i % 8, i)
            assert ref.execute_mut(op, t_ref) == got.execute_mut(
                op, t_got
            )
        assert ref.execute((SR_GET, 5), t_ref) == got.execute(
            (SR_GET, 5), t_got
        )
        ref.sync()
        got.sync()
        _assert_fleets_equal(ref, got)

    def test_union_engine_gspmd_tier_bit_identical(self, mesh):
        # hashmap is window_canonical → combined engine → GSPMD tier
        # (the union-plan economics survive sharding by annotation)
        ref, got = _hashmap_pair(mesh)
        assert got.engine == "combined" and got._mesh_tier == "gspmd"
        t_ref, t_got = ref.register(0), got.register(0)
        rng = np.random.default_rng(3)
        for i in range(60):
            op = (HM_PUT, int(rng.integers(64)),
                  int(rng.integers(1000)), 0)
            assert ref.execute_mut(op, t_ref) == got.execute_mut(
                op, t_got
            )
        for k in (0, 7, 31):
            assert ref.execute((HM_GET, k), t_ref) == got.execute(
                (HM_GET, k), t_got
            )
        ref.sync()
        got.sync()
        _assert_fleets_equal(ref, got)

    def test_shmap_forced_on_combined_model(self, mesh):
        # collectives='shmap' on a combined-engine model: the scan
        # collective replaces the union plan — still bit-identical
        # (the engines are pinned equal), placement-only difference
        ref = NodeReplicated(make_hashmap(64), n_replicas=8,
                             log_entries=1 << 12, gc_slack=64,
                             exec_window=32)
        got = NodeReplicated(make_hashmap(64), n_replicas=8,
                             log_entries=1 << 12, gc_slack=64,
                             exec_window=32, mesh=mesh,
                             collectives="shmap")
        assert got._mesh_tier == "shmap"
        t_ref, t_got = ref.register(0), got.register(0)
        for i in range(40):
            op = (HM_PUT, i % 64, i, 0)
            assert ref.execute_mut(op, t_ref) == got.execute_mut(
                op, t_got
            )
        ref.sync()
        got.sync()
        _assert_fleets_equal(ref, got)

    def test_batch_path_bit_identical(self, mesh):
        # the serve entry point (execute_mut_batch) over the mesh
        ref, got = _seqreg_pair(mesh)
        ops = [(SR_SET, i % 8, i) for i in range(96)]
        assert ref.execute_mut_batch(ops, rid=1) == \
            got.execute_mut_batch(ops, rid=1)
        ref.sync()
        got.sync()
        _assert_fleets_equal(ref, got)

    @pytest.mark.parametrize("pair", ["seqreg", "hashmap"])
    def test_fenced_gc_mask_across_devices(self, mesh, pair):
        # the fenced-head GC mask must stay correct when the corpse
        # lives on a different device than the combiner: fence a
        # replica mid-run on BOTH engines' tiers, require identical
        # heads/ltails/states, then repair and require convergence
        mk = _seqreg_pair if pair == "seqreg" else _hashmap_pair
        ref, got = mk(mesh)
        mkop = (
            (lambda i: (SR_SET, i % 8, i)) if pair == "seqreg"
            else (lambda i: (HM_PUT, i % 64, i, 0))
        )
        for nr in (ref, got):
            t = nr.register(0)
            for i in range(24):
                nr.execute_mut(mkop(i), t)
            nr.fence_replica(5)
            for i in range(24, 48):
                nr.execute_mut(mkop(i), t)
        # the fenced cursor is frozen; head advanced past it
        assert int(np.asarray(got.log.ltails)[5]) < int(got.log.head)
        _assert_fleets_equal(ref, got)
        for nr in (ref, got):
            nr.clone_replica_from(5)
            nr.unfence_replica(5)
            nr.sync()
            assert nr.replicas_equal()
        _assert_fleets_equal(ref, got)

    def test_ring_catchup_tier_bit_identical(self, mesh):
        # a large uniform backlog takes the ring tier on the mesh
        # (make_ring_exec promoted into sync()) — and must land on the
        # same states/cursors as the un-meshed scan rounds
        ref, got = _seqreg_pair(mesh)
        rng = np.random.default_rng(0)
        N = 400
        opc = np.full(N, SR_SET, np.int32)
        args = np.zeros((N, 3), np.int32)
        args[:, 0] = rng.integers(0, 8, N)
        args[:, 1] = rng.integers(0, 1000, N)
        for nr in (ref, got):
            nr.log = log_append(nr.spec, nr.log, jnp.asarray(opc),
                                jnp.asarray(args), N)
            nr.sync()
        assert got._ring_rounds > 0, "ring tier never fired"
        assert ref._ring_rounds == 0
        _assert_fleets_equal(ref, got)

    def test_ring_tier_counter(self, mesh):
        reg = get_registry()
        reg.enable()
        try:
            _, got = _seqreg_pair(mesh)
            before = reg.counter("nr.exec.engine.ring").value
            N = 200
            opc = np.full(N, SR_SET, np.int32)
            args = np.zeros((N, 3), np.int32)
            got.log = log_append(got.spec, got.log, jnp.asarray(opc),
                                 jnp.asarray(args), N)
            got.sync()
            assert reg.counter("nr.exec.engine.ring").value > before
            assert reg.counter("nr.exec.mesh.shmap").value > 0
            assert reg.counter("mesh.sync_bytes").value > 0
            assert reg.gauge("mesh.replicas_per_device").value == 1
        finally:
            reg.disable()

    def test_grow_fleet_keeps_placement(self, mesh):
        ref, got = _seqreg_pair(mesh)
        t_ref, t_got = ref.register(0), got.register(0)
        for i in range(16):
            op = (SR_SET, i % 8, i)
            ref.execute_mut(op, t_ref)
            got.execute_mut(op, t_got)
        # growing by a non-multiple of the shard count is rejected
        # BEFORE any state mutates
        with pytest.raises(ValueError):
            got.grow_fleet(3)
        assert got.n_replicas == 8
        ref.grow_fleet(8)
        new = got.grow_fleet(8)
        assert new == list(range(8, 16))
        for i in range(16, 32):
            op = (SR_SET, i % 8, i)
            assert ref.execute_mut(op, t_ref) == got.execute_mut(
                op, t_got
            )
        ref.sync()
        got.sync()
        _assert_fleets_equal(ref, got)
        assert got.replicas_equal()

    def test_checkpoint_restore_replaces(self, mesh, tmp_path):
        _, got = _seqreg_pair(mesh)
        t = got.register(0)
        for i in range(20):
            got.execute_mut((SR_SET, i % 8, i), t)
        path = str(tmp_path / "snap.npz")
        got.checkpoint(path)
        back = NodeReplicated.restore(path, make_seqreg(8), mesh=mesh)
        _assert_fleets_equal(got, back)
        # the restored fleet still runs mesh rounds
        t2 = back.register(0)
        assert back.execute_mut((SR_SET, 0, 999), t2) is not None
        assert back._mesh_tier is not None

    def test_validation(self, mesh):
        with pytest.raises(ValueError):  # 8 shards can't take R=6
            NodeReplicated(make_seqreg(4), n_replicas=6, mesh=mesh)
        with pytest.raises(ValueError):  # unknown tier
            NodeReplicated(make_seqreg(4), n_replicas=8, mesh=mesh,
                           collectives="nope")
        with pytest.raises(ValueError):  # shmap has no checkify twin
            NodeReplicated(make_seqreg(4), n_replicas=8, mesh=mesh,
                           collectives="shmap", debug=True)

    def test_replica_device_map(self, mesh):
        _, got = _seqreg_pair(mesh)
        devs = [str(got.replica_device(r)) for r in range(8)]
        assert len(set(devs)) == 8  # 8 replicas over 8 devices
        snap = got.snapshot()
        assert snap["mesh"]["devices"] == 8
        assert snap["mesh"]["replicas_per_device"] == 1
        un = NodeReplicated(make_seqreg(4), n_replicas=2)
        assert un.replica_device(0) is None
        assert un.snapshot()["mesh"] is None

    def test_serve_frontend_maps_workers_to_devices(self, mesh):
        from node_replication_tpu.serve import ServeConfig, ServeFrontend

        _, got = _seqreg_pair(mesh)
        with ServeFrontend(got, ServeConfig(batch_max_ops=8,
                                            batch_linger_s=0.0)) as fe:
            for i in range(1, 9):
                assert fe.call((SR_SET, 2, i),
                               rid=i % got.n_replicas) == i - 1
            st = fe.stats()
        assert st["mesh"]["devices"] == 8
        assert sum(st["mesh"]["replicas_per_device"].values()) == 8
        assert len(st["mesh"]["device_of_rid"]) == 8


def _mixed_ops(rng, n, n_keys):
    ops = []
    for _ in range(n):
        if rng.rand() < 0.7:
            ops.append((HM_PUT, int(rng.randint(n_keys)),
                        int(rng.randint(1000))))
        else:
            ops.append((2, int(rng.randint(n_keys))))
    return ops


class TestMeshFused:
    """The mesh-fused exec tier differential contract (interpret mode
    on forced host devices; the shard_map program runs eagerly — same
    convention as every other interpret pallas test)."""

    def test_forced_30_rounds_two_wraps_fence_repair(self):
        # 30 mesh-fused combiner rounds vs the un-meshed scan chain:
        # ~18-op batches against a 256-slot ring wrap it twice, a
        # replica is fenced mid-run with the corpse on a NON-ZERO
        # shard (rid 3 = shard 1 of the 2-wide mesh), repaired, and
        # every round's responses + the final states/cursor lattice
        # must be bit-identical — the tier changes launch count, never
        # results
        mesh = replica_mesh(2)
        K, R = 31, 4
        nr_m = NodeReplicated(make_hashmap(K), n_replicas=R,
                              log_entries=256, gc_slack=32,
                              exec_window=32, engine="pallas",
                              mesh=mesh)
        nr_s = NodeReplicated(make_hashmap(K), n_replicas=R,
                              log_entries=256, gc_slack=32,
                              exec_window=32, engine="scan")
        reg = get_registry()
        reg.enable()
        before = reg.counter("log.engine.mesh_fused").value
        mesh_before = reg.counter("nr.exec.mesh.mesh_fused").value
        rng = np.random.RandomState(7)
        for rnd in range(30):
            if rnd == 12:
                for nr in (nr_m, nr_s):
                    nr.fence_replica(3)
                assert 3 in nr_m.fenced_rids
            if rnd == 16:
                for nr in (nr_m, nr_s):
                    nr.clone_replica_from(3, donor=0)
                    nr.unfence_replica(3)
            ops = _mixed_ops(rng, int(rng.randint(18, 26)), K)
            assert nr_m.execute_mut_batch(ops, rid=0) == \
                nr_s.execute_mut_batch(ops, rid=0), rnd
        assert int(nr_m.log.tail) > 2 * 256  # two genuine ring wraps
        nr_m.sync(); nr_s.sync()
        _assert_fleets_equal(nr_s, nr_m)
        assert nr_m.replicas_equal()
        st = nr_m.stats()
        assert st["fused_tier"] == "forced"
        assert st["fused_rounds"] == 30  # every round one meshed launch
        assert st["exec_rounds"] == 0
        assert nr_m.last_round_tier == "mesh_fused"
        assert nr_m.round_tier(0) == "mesh_fused"
        assert reg.counter("log.engine.mesh_fused").value \
            - before == 30
        assert reg.counter("nr.exec.mesh.mesh_fused").value \
            - mesh_before == 30

    def test_shmap_program_matches_sliced_composition(self):
        # the compilation-policy pin: interpret rounds run the
        # shard-sliced composition, TPU jits the shard_map program —
        # the two must be bit-identical, unfenced AND fenced (the
        # _FAR-composed GC join), so the program the TPU compiles is
        # covered by this CPU suite. One eager shard_map call per
        # variant (seconds each on this jax — why the bulk suite uses
        # the sliced path).
        from node_replication_tpu.core.log import LogSpec, log_init
        from node_replication_tpu.core.replica import replicate_state
        from node_replication_tpu.ops.encoding import encode_ops
        from node_replication_tpu.parallel import MeshFusedEngine

        K, R = 13, 4
        spec = LogSpec(capacity=256, n_replicas=R, arg_width=3,
                       gc_slack=32)
        d = make_hashmap(K)
        eng = MeshFusedEngine(d, spec, replica_mesh(2))
        rng = np.random.RandomState(3)
        ops = [(HM_PUT, int(rng.randint(K)), int(rng.randint(100)))
               for _ in range(7)]
        opc, args, n = encode_ops(ops, 3, pad_to=8)
        for fenced_vec in (None, np.array([False, False, True,
                                           False])):
            is_f = fenced_vec is not None
            log = log_init(spec)
            states = replicate_state(d.init_state(), R)
            sliced = eng._sliced_round(8, is_f)
            shmap = eng._shmap_round(8, is_f)
            extra = (
                (jnp.asarray(fenced_vec, bool),) if is_f else ()
            )
            a = sliced(log, states, opc, args, n, *extra)
            b = shmap(log, states, opc, args, n, *extra)
            for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
                np.testing.assert_array_equal(
                    np.asarray(la), np.asarray(lb),
                    err_msg=f"fenced={is_f}",
                )

    def test_fenced_head_gc_corpse_on_other_shard(self):
        # the composed _FAR mask: with the corpse fenced on shard 1,
        # mesh-fused rounds must keep advancing head past its frozen
        # cursor (the pmin lattice join excludes it), exactly like the
        # un-meshed fleet
        mesh = replica_mesh(2)
        nr = NodeReplicated(make_hashmap(16), n_replicas=4,
                            log_entries=256, gc_slack=32,
                            engine="pallas", mesh=mesh)
        nr.execute_mut_batch([(HM_PUT, 1, 1), (HM_PUT, 2, 2)], rid=0)
        nr.fence_replica(2)  # shard 1 hosts rids 2, 3
        frozen = int(np.asarray(nr.log.ltails)[2])
        for i in range(3):
            nr.execute_mut_batch([(HM_PUT, i, i * 3)], rid=0)
        assert nr.stats()["fused_rounds"] == 4
        assert int(np.asarray(nr.log.ltails)[2]) == frozen
        assert int(nr.log.head) > frozen  # GC not stalled by the corpse

    def test_vspace_mesh_fused_and_fenced_fallback(self):
        # the second fused model rides the same factory composition:
        # flat-vspace mesh-fused rounds are bit-identical to the
        # un-meshed scan chain, and a fenced meshed fleet falls back
        # (no fenced kernel variant) with identical results
        from node_replication_tpu.models.vspace import make_vspace

        mesh = replica_mesh(2)
        P_pages = 512
        mk = lambda **kw: NodeReplicated(
            make_vspace(P_pages, max_span=8), n_replicas=4,
            log_entries=512, gc_slack=64, **kw,
        )
        nr_m = mk(engine="pallas", mesh=mesh)
        nr_s = mk(engine="scan")
        rng = np.random.RandomState(11)
        ops = []
        for _ in range(12):
            if rng.rand() < 0.7:
                ops.append((1, int(rng.randint(P_pages)),
                            int(rng.randint(1, 1000)),
                            int(rng.randint(0, 8))))
            else:
                ops.append((2, int(rng.randint(P_pages)),
                            int(rng.randint(0, 8))))
        assert nr_m.execute_mut_batch(ops, rid=0) == \
            nr_s.execute_mut_batch(ops, rid=0)
        assert nr_m.last_round_tier == "mesh_fused"
        reg = get_registry()
        reg.enable()
        fb = reg.counter("nr.exec.engine.fused_fallback")
        before = fb.value
        for nr in (nr_m, nr_s):
            nr.fence_replica(3)
        ops2 = [(1, 9, 99, 4)]
        assert nr_m.execute_mut_batch(ops2, rid=0) == \
            nr_s.execute_mut_batch(ops2, rid=0)
        assert fb.value > before
        assert nr_m.last_round_tier == nr_m.engine  # chain served it
        nr_m.sync(); nr_s.sync()
        for a, b in zip(jax.tree.leaves(nr_m.states),
                        jax.tree.leaves(nr_s.states)):
            np.testing.assert_array_equal(np.asarray(a),
                                          np.asarray(b))

    def test_pipelined_serve_depth1_meshed(self):
        # PR 14's overlap on a meshed fleet: defer=True issues the
        # meshed launch at _begin_round (assembly stage) and reads
        # back at _finish_round (completion stage) — serve-batch
        # events must carry the mesh_fused tier and kernel-launch
        # events the mesh width, with responses exact
        from node_replication_tpu.serve import ServeConfig, ServeFrontend
        from node_replication_tpu.obs.recorder import get_tracer

        mesh = replica_mesh(2)
        nr = NodeReplicated(make_seqreg(8), n_replicas=2,
                            log_entries=512, gc_slack=64,
                            engine="scan", mesh=mesh)
        # seqreg has no fused factory; the hashmap twin drives the
        # fused tier — use hashmap for the fused serve and seqreg
        # only as the no-factory sanity check
        assert nr.stats()["fused_tier"] == "off"
        nr_f = NodeReplicated(make_hashmap(32), n_replicas=2,
                              log_entries=512, gc_slack=64,
                              engine="pallas", mesh=mesh)
        t = get_tracer()
        t.enable(None)
        try:
            with ServeFrontend(
                nr_f,
                ServeConfig(queue_depth=32, batch_max_ops=8,
                            batch_linger_s=0.002, pipeline_depth=1),
            ) as fe:
                for i in range(24):
                    assert fe.call((HM_PUT, i % 32, i),
                                   rid=fe.rids[i % 2]) == 0
                assert fe.read((HM_GET, 5), rid=fe.rids[0]) >= 0
            events = t.events()
        finally:
            t.disable()
        batches = [e for e in events if e["event"] == "serve-batch"]
        assert batches
        assert all(e.get("engine") == "mesh_fused" for e in batches)
        launches = [e for e in events if e["event"] == "kernel-launch"]
        assert launches
        assert all(e["tier"] == "mesh_fused" and e["devices"] == 2
                   for e in launches)
        assert nr_f.stats()["fused_rounds"] > 0


class TestCnrMesh:
    def _pair(self, mesh_shape=(2, 4)):
        mesh = make_mesh(*mesh_shape)
        mapper = lambda opc, args: args[0]
        mk = lambda **extra: MultiLogReplicated(
            make_hashmap(64), mapper, nlogs=4, n_replicas=2,
            log_entries=1 << 10, gc_slack=32, exec_window=32, **extra,
        )
        return mk(), mk(mesh=mesh)

    def test_cnr_bit_identical(self, mesh):
        ref, got = self._pair()
        rng = np.random.default_rng(5)
        for nr in (ref, got):
            t = nr.register(0)
            r2 = nr.register(1)
            rr = np.random.default_rng(5)
            for i in range(60):
                nr.execute_mut(
                    (HM_PUT, int(rr.integers(64)),
                     int(rr.integers(1000)), 0), t)
            nr.sync()
            assert nr.execute((HM_GET, 7), r2) is not None
        for a, b in zip(jax.tree.leaves(ref.states),
                        jax.tree.leaves(got.states)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for cur in ("tail", "ctail", "head"):
            np.testing.assert_array_equal(
                np.asarray(getattr(ref.ml, cur)),
                np.asarray(getattr(got.ml, cur)),
            )
        np.testing.assert_array_equal(
            np.asarray(ref.ml.ltails), np.asarray(got.ml.ltails)
        )
        assert got.snapshot()["mesh"]["shape"] == {
            "replica": 2, "log": 4,
        }

    def test_cnr_batch_bit_identical(self, mesh):
        ref, got = self._pair()
        ops = [(HM_PUT, i % 64, i, 0) for i in range(48)]
        assert ref.execute_mut_batch(ops, rid=0) == \
            got.execute_mut_batch(ops, rid=0)
        ref.sync()
        got.sync()
        for a, b in zip(jax.tree.leaves(ref.states),
                        jax.tree.leaves(got.states)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_cnr_serve_frontend(self, mesh):
        # the frontend serves the meshed CNR twin too: construction
        # must record the worker→device map through replica_device
        # (regression: getattr(nr, 'mesh') passed but the method was
        # NR-only, crashing __init__)
        from node_replication_tpu.serve import ServeConfig, ServeFrontend

        _, got = self._pair()
        with ServeFrontend(got, ServeConfig(batch_max_ops=8,
                                            batch_linger_s=0.0)) as fe:
            assert fe.call((HM_PUT, 3, 7, 0), rid=1) == 0
            st = fe.stats()
        assert len(st["mesh"]["device_of_rid"]) == 2
        assert st["mesh"]["devices"] == 2  # one row device per shard

    def test_cnr_validation(self, mesh):
        mapper = lambda opc, args: args[0]
        with pytest.raises(ValueError):  # L=3 can't shard over 4 cols
            MultiLogReplicated(make_hashmap(8), mapper, nlogs=3,
                               n_replicas=2, mesh=make_mesh(2, 4))
        with pytest.raises(ValueError):  # R=3 can't shard over 2 rows
            MultiLogReplicated(make_hashmap(8), mapper, nlogs=4,
                               n_replicas=3, mesh=make_mesh(2, 4))
        with pytest.raises(ValueError):  # not a ('replica','log') Mesh
            MultiLogReplicated(make_hashmap(8), mapper, nlogs=4,
                               n_replicas=2, mesh=4)
