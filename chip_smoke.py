#!/usr/bin/env python
"""One-chip smoke of the replicated hashmap's main path.

`python chip_smoke.py` runs, in ONE process on one TPU, with data made
from `--seed`, and every result checked bit-exactly against a host-side
sequential replay (`sim/oracle.HashmapOracle`, numpy):

1. flagship  — `core/step.make_step` at the `bench.py` configuration
   (hashmap, K=10,000, R=4,096, 1 put + 1 get per replica);
2. served    — `ServeFrontend` over `NodeReplicated(engine="auto")` at
   the reference deployment (`benches/hashmap.rs:30-48`: 2^26 prefilled
   entries, 50M keyspace, 4 replicas); every acked put is read back
   from every replica;
3. fused     — `engine="pallas"` at K=10,000 and the largest R <= 4,096
   whose fused round fits VMEM; every round must run `pallas_fused`;
4. pallas    — `ops/pallas_replay.make_pallas_step` at R=4,096, K=1,024.

`--four-chips` runs only the mesh phase (K=10,000, R=4,096 on
`replica_mesh(4)`, `collectives="auto"` and the mesh-fused tier) against
the same fleet on one chip.

Each phase prints one `{"phase": ...}` JSON line (wall and compile
seconds, engine tier, counters, peak device bytes); the last line is
`{"ok": true, "device": {...}}`. Without a TPU it exits non-zero before
any phase and prints no result. Any mismatch raises.
"""

from __future__ import annotations

import argparse
import json
import sys
import threading
import time

import numpy as np


class SmokeFailure(RuntimeError):
    """A phase's result disagreed with its reference."""


def _check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def _engine_counters() -> dict:
    from node_replication_tpu.obs.metrics import get_registry

    return {
        k: v for k, v in get_registry().snapshot().items()
        if ".engine." in k
    }


def _mixed_ops(rng, n: int, n_keys: int) -> list[tuple]:
    """70% puts / 30% removes over `n_keys` keys (the fused suites' mix)."""
    from node_replication_tpu.models import HM_PUT, HM_REMOVE

    puts = rng.random(n) < 0.7
    keys = rng.integers(0, n_keys, n)
    vals = rng.integers(0, 1 << 20, n)
    return [
        (HM_PUT, int(k), int(v)) if p else (HM_REMOVE, int(k))
        for p, k, v in zip(puts, keys, vals)
    ]


def _check_fleet(states, oracle, what: str) -> None:
    """Every replica's `values`/`present` bit-equal to the oracle's."""
    import jax.numpy as jnp

    ref_v = jnp.asarray(oracle.values)[None]
    ref_p = jnp.asarray(oracle.present)[None]
    ok = bool(jnp.all(states["values"] == ref_v)
              & jnp.all(states["present"] == ref_p))
    _check(ok, f"{what}: replica states differ from the oracle")


def phase_flagship(R: int = 4096, K: int = 10_000, steps: int = 8,
                   seed: int = 0) -> dict:
    """`make_step` (combined engine) at the bench.py configuration."""
    import jax
    import jax.numpy as jnp

    from node_replication_tpu import LogSpec, log_init, make_step
    from node_replication_tpu.core.replica import replicate_state
    from node_replication_tpu.models import HM_GET, HM_PUT, make_hashmap
    from node_replication_tpu.sim.oracle import HashmapOracle

    t_phase = time.perf_counter()
    spec = LogSpec(capacity=max(4 * R, 1 << 14), n_replicas=R,
                   arg_width=3, gc_slack=min(8192, R))
    d = make_hashmap(K)
    rng = np.random.default_rng(seed)
    wk = rng.integers(0, K, (steps, R), dtype=np.int32)
    wv = rng.integers(0, 1 << 20, (steps, R), dtype=np.int32)
    rk = rng.integers(0, K, (steps, R), dtype=np.int32)
    zeros = np.zeros((steps, R), np.int32)
    wr_args = jnp.asarray(np.stack([wk, wv, zeros], -1)[:, :, None])
    rd_args = jnp.asarray(np.stack([rk, zeros, zeros], -1)[:, :, None])
    wr_opc = jnp.full((R, 1), HM_PUT, jnp.int32)
    rd_opc = jnp.full((R, 1), HM_GET, jnp.int32)

    log = log_init(spec)
    states = replicate_state(d.init_state(), R)
    step = make_step(d, spec, 1, 1)
    t0 = time.perf_counter()
    step = step.lower(log, states, wr_opc, wr_args[0], rd_opc,
                      rd_args[0]).compile()
    compile_s = time.perf_counter() - t0

    oracle = HashmapOracle(K)
    t0 = time.perf_counter()
    for t in range(steps):
        log, states, wr_resps, rd_resps = step(
            log, states, wr_opc, wr_args[t], rd_opc, rd_args[t]
        )
        # log order is replica-major: replica r's put is entry r
        want_wr = [oracle.apply((HM_PUT, int(k), int(v)))
                   for k, v in zip(wk[t], wv[t])]
        want_rd = np.where(oracle.present[rk[t] % K],
                           oracle.values[rk[t] % K], -1)
        _check(np.array_equal(np.asarray(wr_resps)[:, 0], want_wr),
               f"flagship step {t}: put responses differ")
        _check(np.array_equal(np.asarray(rd_resps)[:, 0], want_rd),
               f"flagship step {t}: get responses differ")
    run_s = time.perf_counter() - t0
    _check_fleet(states, oracle, "flagship")
    _check(int(log.tail) == steps * R and
           bool(jax.numpy.all(log.ltails == log.tail)),
           "flagship: log cursors not at steps*R")
    return {"R": R, "K": K, "steps": steps, "compile_s": compile_s,
            "steps_s": run_s, "wall_s": time.perf_counter() - t_phase,
            "tier": "combined"}


def phase_served(n_keys: int = 1 << 26, keyspace: int = 50_000_000,
                 n_replicas: int = 4, clients: int = 4,
                 ops_per_client: int = 100, prefill: int = 7,
                 seed: int = 0) -> dict:
    """ServeFrontend over NodeReplicated(engine="auto") at the reference
    deployment size; closed-loop clients, each owning the keys congruent
    to its index, so the oracle is each client's own write order."""
    import jax.numpy as jnp

    from node_replication_tpu import NodeReplicated
    from node_replication_tpu.models import HM_GET, HM_PUT, make_hashmap
    from node_replication_tpu.serve import ServeConfig, ServeFrontend

    # keys map to distinct slots (k % n_keys) only when keyspace fits
    _check(keyspace <= n_keys, "served: keyspace must fit the table")
    t_phase = time.perf_counter()
    nr = NodeReplicated(make_hashmap(n_keys, prefill_value=prefill),
                        n_replicas=n_replicas)
    build_s = time.perf_counter() - t_phase
    acked: list[dict] = [{} for _ in range(clients)]
    errors: list[str] = []
    lat: list[float] = []

    def client(c: int, fe) -> None:
        rng = np.random.default_rng([seed, c])
        rid = c % n_replicas
        mine = acked[c]
        try:
            for _ in range(ops_per_client):
                if rng.random() < 0.5 or not mine:
                    k = c + clients * int(rng.integers(keyspace // clients))
                    v = int(rng.integers(0, 1 << 20))
                    t0 = time.perf_counter()
                    resp = fe.submit((HM_PUT, k, v), rid=rid).result(600)
                    lat.append(time.perf_counter() - t0)
                    if resp != 0:
                        errors.append(f"client {c} put {k}: resp {resp}")
                    mine[k] = v
                else:
                    keys = list(mine)
                    k = keys[int(rng.integers(len(keys)))]
                    got = fe.read((HM_GET, k), rid=rid)
                    if got != mine[k]:
                        errors.append(f"client {c} get {k}: {got} != "
                                      f"{mine[k]}")
        except Exception as e:  # surfaced below, with the others
            errors.append(f"client {c}: {type(e).__name__}: {e}")

    t0 = time.perf_counter()
    with ServeFrontend(nr, ServeConfig()) as fe:
        threads = [threading.Thread(target=client, args=(c, fe))
                   for c in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        _check(not errors, "served: " + "; ".join(errors[:5]))
        traffic_s = time.perf_counter() - t0
        tail = int(nr.log.tail)
        final = {k: v for mine in acked for k, v in mine.items()}
        rng = np.random.default_rng([seed, clients])
        untouched = [int(k) for k in rng.integers(0, keyspace, 32)
                     if int(k) not in final]
        for rid in range(n_replicas):
            for k, v in final.items():
                got = fe.read((HM_GET, k), rid=rid, min_pos=tail)
                _check(got == v, f"served: replica {rid} key {k}: "
                                 f"{got} != {v}")
            for k in untouched:
                got = fe.read((HM_GET, k), rid=rid, min_pos=tail)
                _check(got == prefill, f"served: replica {rid} untouched "
                                       f"key {k}: {got}")
    # whole-table checksums on device: per replica, the count of slots
    # off the prefill and the int64 sum of every value
    vals = nr.states["values"]
    changed = np.asarray(jnp.sum(vals != prefill, axis=1))
    total = np.asarray(jnp.sum(vals.astype(jnp.int64), axis=1))
    want_changed = sum(1 for v in final.values() if v != prefill)
    want_total = prefill * (n_keys - len(final)) + sum(final.values())
    _check(bool(np.all(changed == want_changed)),
           f"served: changed-slot counts {changed} != {want_changed}")
    _check(bool(np.all(total == want_total)),
           f"served: table sums {total} != {want_total}")
    _check(bool(jnp.all(nr.states["present"])),
           "served: a prefilled key went absent")
    stats = nr.stats()
    return {"n_keys": n_keys, "replicas": n_replicas, "clients": clients,
            "keys_put": len(final),
            "ops": clients * ops_per_client, "build_s": build_s,
            "traffic_s": traffic_s,
            "put_p50_s": float(np.median(lat)) if lat else None,
            "wall_s": time.perf_counter() - t_phase,
            "tier": nr.last_round_tier or stats.get("engine"),
            "fused_tier": stats.get("fused_tier")}


def largest_fused_r(K: int, R_max: int, window: int) -> int:
    """Largest R <= R_max (halving) whose fused round fits the chip:
    the VMEM budget check is the non-interpret one, wherever it runs."""
    from node_replication_tpu.core.log import LogSpec
    from node_replication_tpu.ops.pallas_replay import FusedHashmapEngine

    R = R_max
    while R >= 1:
        spec = LogSpec(n_replicas=R, arg_width=3)
        if FusedHashmapEngine(K, spec, interpret=False).supports(window):
            return R
        R //= 2
    raise SmokeFailure(f"fused: no R <= {R_max} fits K={K}, W={window}")


def phase_fused(K: int = 10_000, R_max: int = 4096, batch: int = 64,
                batches: int = 6, seed: int = 0) -> dict:
    """NodeReplicated(engine="pallas"): every round one fused kernel."""
    import jax

    from node_replication_tpu import NodeReplicated
    from node_replication_tpu.models import HM_GET, make_hashmap
    from node_replication_tpu.sim.oracle import HashmapOracle

    t_phase = time.perf_counter()
    R = largest_fused_r(K, R_max, batch)
    nr = NodeReplicated(make_hashmap(K), n_replicas=R, engine="pallas")
    oracle = HashmapOracle(K)
    rng = np.random.default_rng(seed)
    round_s = []
    for b in range(batches):
        ops = _mixed_ops(rng, batch, K)
        t0 = time.perf_counter()
        resps = nr.execute_mut_batch(ops, rid=b % R)
        round_s.append(time.perf_counter() - t0)
        want = [oracle.apply(op) for op in ops]
        _check(list(resps) == want, f"fused batch {b}: responses differ")
        _check(nr.last_round_tier == "pallas_fused",
               f"fused batch {b}: ran {nr.last_round_tier}")
    interpret = nr._fused.interpret  # the engine the rounds ran on
    states = jax.device_get(nr.states)
    _check(np.array_equal(states["values"],
                          np.broadcast_to(oracle.values, (R, K)))
           and np.array_equal(states["present"],
                              np.broadcast_to(oracle.present, (R, K))),
           "fused: replica states differ from the oracle")
    for rid in (0, R - 1):
        tok = nr.register(rid)
        for k in rng.integers(0, K, 8):
            _check(nr.execute((HM_GET, int(k)), tok)
                   == oracle.read((HM_GET, int(k))),
                   f"fused: replica {rid} get {k} differs")
    return {"R": R, "K": K, "batch": batch, "batches": batches,
            "interpret": interpret, "first_round_s": round_s[0],
            "round_s": float(np.median(round_s[1:] or round_s)),
            "wall_s": time.perf_counter() - t_phase,
            "tier": nr.last_round_tier,
            "fused_rounds": nr.stats()["fused_rounds"]}


def phase_pallas_step(R: int = 4096, K: int = 1024, steps: int = 4,
                      seed: int = 0, interpret: bool = False) -> dict:
    """`make_pallas_step` (replay kernel over `[Kp, R]` planes)."""
    import jax.numpy as jnp

    from node_replication_tpu import LogSpec, log_init
    from node_replication_tpu.models import HM_GET, HM_PUT
    from node_replication_tpu.ops.pallas_replay import (
        make_pallas_step,
        pallas_hashmap_state,
    )
    from node_replication_tpu.sim.oracle import HashmapOracle

    t_phase = time.perf_counter()
    spec = LogSpec(capacity=max(4 * R, 1 << 14), n_replicas=R,
                   arg_width=3, gc_slack=min(8192, R))
    rng = np.random.default_rng(seed)
    wk = rng.integers(0, K, (steps, R), dtype=np.int32)
    wv = rng.integers(0, 1 << 20, (steps, R), dtype=np.int32)
    rk = rng.integers(0, K, (steps, R), dtype=np.int32)
    zeros = np.zeros((steps, R), np.int32)
    wr_args = jnp.asarray(np.stack([wk, wv, zeros], -1)[:, :, None])
    rd_args = jnp.asarray(np.stack([rk, zeros, zeros], -1)[:, :, None])
    wr_opc = jnp.full((R, 1), HM_PUT, jnp.int32)
    rd_opc = jnp.full((R, 1), HM_GET, jnp.int32)
    # jit + interpret trips an MLIR dtype clash under x64 (see
    # FusedEngineHost); the interpreted rehearsal runs eagerly
    step = make_pallas_step(K, spec, 1, 1, interpret=interpret,
                            jit=not interpret)
    log = log_init(spec)
    states = pallas_hashmap_state(K, R)
    oracle = HashmapOracle(K)
    step_s = []
    for t in range(steps):
        t0 = time.perf_counter()
        log, states, wr_resps, rd_resps = step(
            log, states, wr_opc, wr_args[t], rd_opc, rd_args[t]
        )
        wr_np, rd_np = np.asarray(wr_resps), np.asarray(rd_resps)
        step_s.append(time.perf_counter() - t0)
        want_wr = [oracle.apply((HM_PUT, int(k), int(v)))
                   for k, v in zip(wk[t], wv[t])]
        want_rd = np.where(oracle.present[rk[t] % K],
                           oracle.values[rk[t] % K], -1)
        _check(np.array_equal(wr_np[:, 0], want_wr),
               f"pallas step {t}: put responses differ")
        _check(np.array_equal(rd_np[:, 0], want_rd),
               f"pallas step {t}: get responses differ")
    vals = np.asarray(states["values"])[:K]
    pres = np.asarray(states["present"])[:K]
    _check(np.array_equal(vals, np.broadcast_to(oracle.values[:, None],
                                                (K, R)))
           and np.array_equal(pres > 0, np.broadcast_to(
               oracle.present[:, None], (K, R))),
           "pallas: replica states differ from the oracle")
    return {"R": R, "K": K, "steps": steps, "interpret": interpret,
            "first_step_s": step_s[0],
            "step_s": float(np.median(step_s[1:] or step_s)),
            "wall_s": time.perf_counter() - t_phase, "tier": "pallas"}


def _drive_fleet(nr, K: int, batch: int, batches: int, seed: int):
    """Seeded batches through `nr`; returns (responses, reads, states)."""
    import jax

    from node_replication_tpu.models import HM_GET

    rng = np.random.default_rng(seed)
    R = nr.n_replicas
    resps, tiers = [], set()
    for b in range(batches):
        ops = _mixed_ops(rng, batch, K)
        resps.append(list(nr.execute_mut_batch(ops, rid=b % R)))
        tiers.add(nr.last_round_tier)
    toks = {rid: nr.register(rid) for rid in (0, R // 2, R - 1)}
    reads = [nr.execute((HM_GET, int(k)), toks[rid])
             for rid in toks for k in rng.integers(0, K, 8)]
    nr.sync()  # chain rounds replay only the batch's own replica
    return resps, reads, jax.device_get(nr.states), tiers


def phase_four_chips(R: int = 4096, K: int = 10_000, batch: int = 64,
                     batches: int = 6, seed: int = 0,
                     n_dev: int = 4) -> dict:
    """A replica-sharded fleet on `replica_mesh(n_dev)` (collectives
    "auto", then the mesh-fused tier) vs the same fleet on one chip."""
    from node_replication_tpu import NodeReplicated
    from node_replication_tpu.models import make_hashmap
    from node_replication_tpu.parallel.mesh import replica_mesh
    from node_replication_tpu.sim.oracle import HashmapOracle

    t_phase = time.perf_counter()
    mesh = replica_mesh(n_dev)
    d = make_hashmap(K)
    oracle = HashmapOracle(K)
    rng = np.random.default_rng(seed)  # the stream _drive_fleet draws
    for _ in range(batches):
        for op in _mixed_ops(rng, batch, K):
            oracle.apply(op)
    out = {"R": R, "K": K, "devices": n_dev}

    def same(a, b, what):
        _check(a[0] == b[0], f"{what}: responses differ from one chip")
        _check(a[1] == b[1], f"{what}: reads differ from one chip")
        for leaf in ("values", "present"):
            _check(np.array_equal(a[2][leaf], b[2][leaf]),
                   f"{what}: {leaf} differ from one chip")
        Rn = b[2]["values"].shape[0]
        _check(np.array_equal(b[2]["values"], np.broadcast_to(
            oracle.values, (Rn, K))), f"{what}: values differ from oracle")

    def devices_of(nr) -> int:
        return len({s.device for s in nr.states["values"]
                    .addressable_shards})

    one = _drive_fleet(NodeReplicated(d, n_replicas=R), K, batch,
                       batches, seed)
    t0 = time.perf_counter()
    nr = NodeReplicated(d, n_replicas=R, mesh=mesh, collectives="auto")
    meshed = _drive_fleet(nr, K, batch, batches, seed)
    out["auto_s"] = time.perf_counter() - t0
    out["auto_devices"] = devices_of(nr)
    out["auto_tiers"] = sorted(t or "chain" for t in meshed[3])
    _check(out["auto_devices"] == n_dev,
           f"mesh auto: states on {out['auto_devices']} devices")
    same(one, meshed, "mesh auto")

    # mesh-fused: the largest R whose per-shard fused round fits VMEM
    Rf = n_dev * largest_fused_r(K, R // n_dev, batch)
    one_f = _drive_fleet(NodeReplicated(d, n_replicas=Rf), K, batch,
                         batches, seed)
    t0 = time.perf_counter()
    nr = NodeReplicated(d, n_replicas=Rf, mesh=mesh, engine="pallas")
    fused = _drive_fleet(nr, K, batch, batches, seed)
    out["fused_R"] = Rf
    out["fused_s"] = time.perf_counter() - t0
    out["fused_interpret"] = nr._fused.interpret
    out["fused_devices"] = devices_of(nr)
    out["fused_tiers"] = sorted(fused[3])
    _check(fused[3] == {"mesh_fused"},
           f"mesh fused: rounds ran {fused[3]}")
    _check(out["fused_devices"] == n_dev,
           f"mesh fused: states on {out['fused_devices']} devices")
    same(one_f, fused, "mesh fused")
    out["wall_s"] = time.perf_counter() - t_phase
    return out


def _run(name: str, fn, **kw) -> dict:
    t0 = time.perf_counter()
    res = fn(**kw)
    res = {"phase": name, **res, "phase_s": time.perf_counter() - t0,
           "engine_counters": _engine_counters(),
           "peak_bytes_in_use": _peak_bytes()}
    print(json.dumps(res, default=str), flush=True)
    return res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip mesh phase")
    args = ap.parse_args(argv)

    import jax

    from node_replication_tpu.obs.metrics import get_registry
    from node_replication_tpu.utils.compile_cache import (
        setup_compile_cache,
    )

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devs[0].platform}",
              file=sys.stderr)
        return 2
    want = 4 if args.four_chips else 1
    if len(devs) < want:
        print(f"chip_smoke: needs {want} chips, found {len(devs)}",
              file=sys.stderr)
        return 2
    cache = setup_compile_cache()  # before the first compile
    get_registry().enable()
    print(json.dumps({"compile_cache": cache,
                      "device_kind": devs[0].device_kind,
                      "devices": len(devs)}), flush=True)
    s = args.seed
    if args.four_chips:
        four = _run("four_chips", phase_four_chips, seed=s)
        _check(four["fused_interpret"] is False,
               "four_chips: fused kernel ran interpreted")
    else:
        _run("flagship", phase_flagship, seed=s)
        _run("served", phase_served, seed=s)
        fused = _run("fused", phase_fused, seed=s)
        pallas = _run("pallas_step", phase_pallas_step, seed=s)
        _check(fused["interpret"] is False and
               pallas["interpret"] is False,
               "a kernel ran interpreted on the chip")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
