#!/usr/bin/env python
"""Flagship benchmark: aggregate log-replay throughput, hashmap 50/50 R/W.

Reproduces the BASELINE.json headline config — NR hashmap, 10K keys, 50/50
get/put, 4096 simulated replicas on one chip — and prints ONE JSON line:
`{"metric", "value", "unit", "vs_baseline"}` with vs_baseline relative to
the 10M ops/sec driver target.

Accounting is honest per SURVEY.md §7: the value counts *executed
dispatches* — every log entry replayed by every replica (R × span per step,
the reference's definition of replayed work, `nr/src/log.rs:473-524`) plus
every read dispatched against a replica (reads never enter the log,
`nr/src/replica.rs:483-497`). Appends are not counted.

Replay engine (`--path`): the default is the *combined* window replay —
`Dispatch.window_apply` computes each window as one parallel reduction
(sort + predecessor lookup + dense merge), bit-identical to the sequential
fold (tests/test_window.py) but ~1000x faster at this config than the
generic per-entry scan (measured r3 on TPU v5e: 3.9 ms/step combined vs
20.3 s/step scan at R=4096, K=10000). `--path scan` measures the faithful
per-entry analog of the reference's replay loop.

Measurement methodology: duration-based repeats, each ending on a
data-dependent D2H readback (`utils/fence.py`), so a timed window
covers executed device work, not the enqueue. A calibration pass sizes the
per-repeat step count to cover `--min-time` seconds of device work; each
of `--repeats` repeats then times that many steps (async-dispatched,
donated buffers, one real fence at the end) and the JSON value is the
MEDIAN across repeats with the min→max spread reported in `spread_pct`.
Step inputs cycle through `--steps` pre-generated batches resident on
device, so the measured loop never transfers host data.
"""

import argparse
import json
import math
import os
import statistics
import sys
import time

import jax
import jax.numpy as jnp

from node_replication_tpu import LogSpec, log_init, make_step
from node_replication_tpu.core.replica import replicate_state
from node_replication_tpu.models import HM_GET, HM_PUT, make_hashmap
from node_replication_tpu.utils.compile_cache import setup_compile_cache
from node_replication_tpu.utils.fence import fence


def cpu_child_env(role: str, env: dict | None = None) -> dict:
    """Environment for a child process of a multi-process mode. A chip
    belongs to one process, and the launching parent is the one that
    may hold it, so every child runs with `JAX_PLATFORMS=cpu`
    (assigned, not defaulted: an inherited `JAX_PLATFORMS=tpu` would
    send the child after the parent's chip)."""
    env = dict(os.environ if env is None else env)
    env["JAX_PLATFORMS"] = "cpu"
    print(f"# chip: held by parent pid {os.getpid()} only; child "
          f"{role} runs JAX_PLATFORMS=cpu", file=sys.stderr)
    return env


def serve_main(args) -> int:
    """`--serve`: benchmark the serving frontend (ISSUE 3).

    Phase 1 (closed loop, sequence-verified): `--serve-clients` OS
    threads drive `--serve-ops` fetch-and-set ops through a
    `ServeFrontend` over the seqreg model (`models/seqreg.py`); client
    `c` owns register `c` and writes `1..N` in order, so every
    response must equal the previous value — a lost, duplicated, or
    reordered response is a hard failure (exit 1), which is the CI
    serve-smoke gate. Reports client-perceived p50/p95/p99 latency and
    throughput.

    Phase 2 (open loop, overload probe): a deliberately tiny admission
    queue under an arrival rate far above service capacity must
    produce typed `Overloaded` rejections — counted both by the
    frontend and the `serve.shed` obs metric — while memory stays
    bounded by the queue depth. Zero sheds under pressure, or any
    untyped failure, is a failure.

    Both phases append rows to `serve_benchmarks.csv` and the combined
    result prints as one JSON line (p50/p95/p99 + shed-rate next to
    throughput, the BENCH artifact shape).
    """
    from node_replication_tpu import NodeReplicated
    from node_replication_tpu.harness.mkbench import (
        append_serve_csv,
        measure_serve,
        serve_rows,
    )
    from node_replication_tpu.models import SR_GET, SR_SET, make_seqreg
    from node_replication_tpu.obs.metrics import get_registry
    from node_replication_tpu.serve import (
        RetryPolicy,
        ServeConfig,
        ServeFrontend,
    )

    reg = get_registry()
    reg.enable()  # sheds must land in obs metrics (acceptance gate)
    clients = args.serve_clients
    per_client = max(1, args.serve_ops // clients)
    n_ops = per_client * clients
    failures: list[str] = []
    csv_out: list[dict] = []

    # ---- phase 1: closed-loop, sequence-verified, both worker
    # shapes (ISSUE 14: pipeline_overlap=0 is the serial worker,
    # =1 overlaps round N+1's host work with round N's device work;
    # each run is fully verified, and both land in the CSV so the
    # p50/p95/p99 comparison is a recorded artifact) ----------------
    def op_of(c, i):
        return (SR_SET, c, i + 1)

    def check(c, i, resp):
        if resp != i:
            return (f"client {c} op {i}: expected previous value "
                    f"{i}, got {resp} (lost/dup/reordered)")
        return None

    def run_closed(pipeline_depth: int, tag: str | None = None,
                   profile_hz: float | None = None, csv: bool = True):
        if tag is None:
            tag = "seqreg-closed" if pipeline_depth == 0 \
                else "seqreg-closed-pipelined"
        nr = NodeReplicated(
            make_seqreg(clients),
            n_replicas=args.serve_replicas,
            log_entries=4096,
            gc_slack=256,
            exec_window=256,
        )
        cfg = ServeConfig(
            queue_depth=args.serve_queue_depth,
            batch_max_ops=args.serve_batch,
            batch_linger_s=args.serve_linger,
            pipeline_depth=pipeline_depth,
            profile_hz=profile_hz,
        )
        with ServeFrontend(nr, cfg) as fe:
            r = measure_serve(
                fe, op_of, n_ops, clients, mode="closed",
                retry=RetryPolicy(), check=check, name=tag,
            )
            finals = [
                fe.read((SR_GET, c), rid=fe.rids[c % len(fe.rids)])
                for c in range(clients)
            ]
            profiler = fe.profiler
        # fe closed: the profiler (if any) is stopped but its
        # aggregate survives for snapshot()/folded(); the summary
        # event lands in the trace artifact (when NR_TPU_TRACE is
        # set), where obs.report's Host budget section reads it back
        snap = None
        if profiler is not None:
            snap = profiler.emit_summary(workload=tag)
        for c, v in enumerate(finals):
            if v != per_client:
                failures.append(
                    f"{tag}: client {c}: final register {v} != "
                    f"{per_client}"
                )
        nr.sync()
        if not nr.replicas_equal():
            failures.append(f"{tag}: replicas diverged")
        if r.completed != n_ops:
            failures.append(
                f"{tag}: lost responses: completed {r.completed} "
                f"!= {n_ops}"
            )
        # oracle violations (lost/dup/reordered) AND transport
        # failures (nothing may shed or deadline out of the verified
        # closed run)
        for c, i, msg in (r.errors + r.transport_errors)[:10]:
            failures.append(msg)
        if csv:
            csv_out.extend(serve_rows("bench", r))
        return r, snap

    res, _ = run_closed(0)
    res_pipe, _ = run_closed(1)

    # ---- phase 2: open-loop overload probe -------------------------
    overload = None
    if args.serve_overload_ops > 0:
        nr2 = NodeReplicated(
            make_seqreg(clients), n_replicas=1,
            log_entries=4096, gc_slack=256, exec_window=256,
        )
        shed_before = reg.counter("serve.shed").value
        with ServeFrontend(
            nr2,
            ServeConfig(queue_depth=4, batch_max_ops=8,
                        batch_linger_s=0.005),
        ) as fe2:
            res2 = measure_serve(
                fe2, op_of, args.serve_overload_ops, clients,
                mode="open", rate=args.serve_overload_rate,
                name="seqreg-overload",
            )
            depth_now = fe2.stats()["queued"]
        shed_metric = reg.counter("serve.shed").value - shed_before
        if res2.shed <= 0:
            failures.append(
                "overload probe produced no Overloaded rejections "
                "(admission control not engaging)"
            )
        if shed_metric != res2.shed:
            failures.append(
                f"obs serve.shed counter {shed_metric} != frontend "
                f"shed count {res2.shed}"
            )
        if res2.accepted + res2.shed != res2.attempts:
            failures.append(
                f"accounting leak: accepted {res2.accepted} + shed "
                f"{res2.shed} != attempts {res2.attempts}"
            )
        if res2.completed + res2.deadline_missed != res2.accepted:
            failures.append(
                f"dropped responses: completed {res2.completed} + "
                f"missed {res2.deadline_missed} != accepted "
                f"{res2.accepted}"
            )
        overload = {
            "attempts": res2.attempts,
            "accepted": res2.accepted,
            "completed": res2.completed,
            "shed": res2.shed,
            "shed_rate": round(res2.shed_rate, 4),
            "metrics_shed_counter": shed_metric,
            "queue_depth_cap": 4,
            "queued_after_drain": depth_now,
            "p95_ms": round(res2.percentile_ms(95), 3),
        }
        csv_out.extend(serve_rows("bench", res2))

    # ---- phase 3 (--profile): host-budget + overhead gate ----------
    # Paired closed runs of the same workload, profiler OFF then ON at
    # --profile-hz (phase 1 above already warmed compilation). Gate:
    # ON must hold >= 95% of OFF throughput. Each retry re-measures
    # BOTH sides — run-to-run variance on a shared CPU box exceeds the
    # profiler's real cost (PR 16 measured ~0-3% at 97 Hz), so
    # comparing a fresh
    # ON against a stale OFF measures drift, not the profiler. Best
    # pair of up to 3 wins; a profiler that genuinely costs > 5%
    # fails every pair.
    profile_out = None
    if args.profile:
        from node_replication_tpu.obs.profile import (
            folded_from_snapshot,
            host_budget,
        )

        ratio = 0.0
        res_off = res_on = snap_on = None
        for _attempt in range(3):
            off_try, _ = run_closed(
                0, tag="seqreg-profile-off", csv=False)
            on_try, snap_try = run_closed(
                0, tag="seqreg-profile-on",
                profile_hz=args.profile_hz, csv=False,
            )
            r_try = (on_try.throughput / off_try.throughput
                     if off_try.throughput else 0.0)
            if r_try > ratio or res_on is None:
                ratio, res_off, res_on, snap_on = (
                    r_try, off_try, on_try, snap_try
                )
            if ratio >= 0.95:
                break
        budget = host_budget(snap_on)
        prof_cols = {
            "hz": args.profile_hz,
            "samples": budget["thread_samples"],
            "duty_cycle": round(budget["duty_cycle"], 6),
            "attributed_frac": round(budget["attributed_frac"], 4),
            "overhead_ratio": round(ratio, 4),
        }
        csv_out.extend(serve_rows("bench", res_on, profile=prof_cols))
        budget_stages = {
            k: {"samples": v["samples"], "frac": round(v["frac"], 4)}
            for k, v in budget["stages"].items()
        }
        if ratio < 0.95:
            failures.append(
                f"profile overhead gate: profiler-ON throughput "
                f"{res_on.throughput:.1f} ops/s is "
                f"{100.0 * ratio:.1f}% of OFF "
                f"{res_off.throughput:.1f} ops/s (< 95%)"
            )
        if budget["attributed_frac"] < 0.9:
            print(
                f"# WARN: host budget attributes only "
                f"{100.0 * budget['attributed_frac']:.1f}% of "
                f"samples to named stages (< 90%)",
                file=sys.stderr,
            )
        if args.profile_folded:
            with open(args.profile_folded, "w") as f:
                f.write(folded_from_snapshot(snap_on))
        profile_out = {
            "hz": args.profile_hz,
            "thread_samples": budget["thread_samples"],
            "duty_cycle": round(budget["duty_cycle"], 6),
            "busy_frac": round(budget["busy_frac"], 4),
            "stages": budget_stages,
            "attributed_frac": round(budget["attributed_frac"], 4),
            "throughput_off": round(res_off.throughput, 1),
            "throughput_on": round(res_on.throughput, 1),
            "overhead_ratio": round(ratio, 4),
            "overhead_gate": "pass" if ratio >= 0.95 else "FAIL",
        }

    append_serve_csv(args.serve_out, csv_out)
    print(json.dumps({
        "metric": "serve_seqreg_closed_loop",
        "value": round(res.percentile_ms(95), 3),
        "unit": "p95_ms",
        "clients": clients,
        "ops": n_ops,
        "throughput_ops_per_sec": round(res.throughput, 1),
        "p50_ms": round(res.percentile_ms(50), 3),
        "p95_ms": round(res.percentile_ms(95), 3),
        "p99_ms": round(res.percentile_ms(99), 3),
        "shed": res.shed,
        "shed_rate": round(res.shed_rate, 4),
        "deadline_miss": res.deadline_missed,
        "pipelined": {
            "pipeline_overlap": 1,
            "throughput_ops_per_sec": round(res_pipe.throughput, 1),
            "p50_ms": round(res_pipe.percentile_ms(50), 3),
            "p95_ms": round(res_pipe.percentile_ms(95), 3),
            "p99_ms": round(res_pipe.percentile_ms(99), 3),
            "p99_vs_serial": round(
                res_pipe.percentile_ms(99) / res.percentile_ms(99), 3
            ) if res.percentile_ms(99) else None,
        },
        "verified": {
            "completed": res.completed,
            "lost": n_ops - res.completed,
            "sequence_errors": len(res.errors),
            "transport_errors": len(res.transport_errors),
            "replicas_equal": not any(
                "diverged" in f for f in failures
            ),
        },
        "overload": overload,
        "profile": profile_out,
    }))
    if failures:
        for f in failures:
            print(f"# FAIL: {f}", file=sys.stderr)
        return 1
    print(
        f"# serve OK: 2x{n_ops} sequence-verified ops from {clients} "
        f"clients, zero lost/duplicated; "
        f"serial p50/p95/p99 = {res.percentile_ms(50):.2f}/"
        f"{res.percentile_ms(95):.2f}/{res.percentile_ms(99):.2f} ms; "
        f"pipelined p50/p95/p99 = {res_pipe.percentile_ms(50):.2f}/"
        f"{res_pipe.percentile_ms(95):.2f}/"
        f"{res_pipe.percentile_ms(99):.2f} ms"
        + (f"; overload shed {overload['shed']}/"
           f"{overload['attempts']} (typed, metered)"
           if overload else "")
        + (f"; profile overhead {100.0 * profile_out['overhead_ratio']:.1f}%"
           f" of OFF, {100.0 * profile_out['attributed_frac']:.1f}%"
           f" attributed over {len(profile_out['stages'])} stage(s)"
           if profile_out else ""),
        file=sys.stderr,
    )
    return 0


def kernel_main(args) -> int:
    """`--kernel`: the combiner-round engine gate (ISSUE 11).

    For each `RxKxW` point in `--kernel-points`, measures one combiner
    round per tier — `pallas_fused` (the one-launch fused
    append+replay engine, `ops/pallas_replay.py`) vs the `combined`
    and `scan` append+exec chains — with BIT-IDENTITY verified against
    the scan engine before any timing (states, cursors, ring content,
    responses; `harness/mkbench.measure_kernel`). Per-round latency is
    fenced, so the reported p50/p95 is the real per-batch latency
    floor, and `launches_per_round` shows the chain-vs-fused launch
    collapse.

    Gates: ANY bit-identity failure exits 1 on every platform. On TPU
    the flagship point (R=4096, K=10000) additionally requires
    `pallas_fused >= combined` dispatches/s — the ROADMAP item-1
    target; off-TPU (or `--kernel-interpret`) the throughput gate
    self-skips, matching the `--mesh` baseline-gate convention.

    `--kernel-devices N` (default 1) re-points the sweep at the MESH
    tiers: `mesh_fused` (the shard_map-wrapped one-launch round,
    `parallel/collectives.py:MeshFusedEngine`) vs the `shmap`
    append+exec chain at N devices, still bit-identity-verified
    against the 1-device scan chain; the flagship TPU gate becomes
    `mesh_fused >= shmap`. `launches_per_round` in the CSV is derived
    from the `kernel.launches` counter delta, so the
    one-launch-per-round claim is measured, not asserted — and must
    hold as devices scale.
    """
    from node_replication_tpu.harness.mkbench import (
        append_kernel_csv,
        kernel_rows,
        measure_kernel,
    )

    on_tpu = jax.devices()[0].platform == "tpu"
    interpret = args.kernel_interpret
    if not interpret and not on_tpu:
        print("--kernel needs a TPU; pass --kernel-interpret for the "
              "CPU rehearsal", file=sys.stderr)
        return 2
    devices = args.kernel_devices
    failures: list[str] = []
    results = []
    csv_rows: list[dict] = []
    for spec_str in args.kernel_points.split(","):
        try:
            R, K, W = (int(x) for x in spec_str.strip().split("x"))
        except ValueError:
            sys.exit(f"--kernel-points entry {spec_str!r} is not RxKxW")
        try:
            points = measure_kernel(
                K, R, W, duration_s=args.kernel_duration,
                interpret=interpret, seed=args.seed,
                devices=devices,
            )
        except ValueError as e:
            failures.append(f"{spec_str}: {e}")
            continue
        by_tier = {p.tier: p for p in points}
        for p in points:
            if not p.bit_identical:
                failures.append(
                    f"{spec_str}: tier {p.tier} NOT bit-identical to "
                    f"the scan engine"
                )
        gate = None
        flagship = (R, K) == (4096, 10_000)
        if flagship and not interpret:
            fused_tier, chain_tier = (
                ("mesh_fused", "shmap") if devices > 1
                else ("pallas_fused", "combined")
            )
            fused = by_tier[fused_tier].dispatches_per_sec
            comb = by_tier[chain_tier].dispatches_per_sec
            gate = fused >= comb
            if not gate:
                failures.append(
                    f"{spec_str}: {fused_tier} {fused:.3g} "
                    f"dispatches/s < {chain_tier} {comb:.3g} on the "
                    f"flagship config"
                )
        results.append({
            "point": spec_str.strip(),
            "devices": devices,
            "flagship": flagship,
            "tiers": {
                p.tier: {
                    "dispatches_per_sec": round(
                        p.dispatches_per_sec, 1),
                    "launches_per_round": p.launches_per_round,
                    "p50_ms": round(p.p50_ms, 4),
                    "p95_ms": round(p.p95_ms, 4),
                    "rounds": p.rounds,
                    "bit_identical": p.bit_identical,
                } for p in points
            },
            "fused_vs_chain_gate": gate,
        })
        csv_rows.extend(kernel_rows(f"bench/{spec_str.strip()}", points))
    append_kernel_csv(args.serve_out, csv_rows)
    print(json.dumps({
        "metric": "kernel_round_engines",
        "value": len(results),
        "unit": "points",
        "interpret": interpret,
        "devices": devices,
        "throughput_gate": (
            "enforced" if (on_tpu and not interpret) else "skipped"
        ),
        "points": results,
    }))
    if not on_tpu or interpret:
        print("# kernel throughput gate skipped (no TPU / interpret "
              "mode); bit-identity still enforced", file=sys.stderr)
    if failures:
        for f in failures:
            print(f"# FAIL: {f}", file=sys.stderr)
        return 1
    print(
        f"# kernel OK: {len(results)} points, every tier "
        f"bit-identical to scan"
        + ("" if interpret else "; flagship fused>=combined gate held"),
        file=sys.stderr,
    )
    return 0


def mesh_main(args) -> int:
    """`--mesh`: the 1→N-device scaling curve (ISSUE 10).

    Runs the flagship hashmap 50/50 configuration at every requested
    device count — 1 device through the plain un-sharded step (the
    exact flagship program), N devices through `ShardedRunner`
    (replica axis under `NamedSharding(mesh, P('replica'))`,
    `parallel/mesh.py`) — and emits the curve as one JSON line plus
    `mesh_benchmarks.csv` rows (devices, throughput, scaling_x,
    efficiency — mkbench `mesh_rows`/`append_mesh_csv`).

    Hard gates (exit 1):

    - **bit-identity** — before each point is timed, the sharded fleet
      replays fixed verification steps and its states must equal the
      1-device fleet's bit-for-bit (placement changes speed, never
      results);
    - **flagship stays honest** — on real TPU devices the 1-device
      point must stay within `--mesh-baseline-tolerance` of
      `--mesh-baseline`, so the mesh work cannot silently regress the
      single-chip number the scaling claims are relative to. Off by
      default (`--mesh-baseline 0`: no flagship number is measured on
      today's code) and skipped on CPU/forced-host meshes, where the
      absolute number is meaningless;
    - **mesh-fused wins at every width** — the per-width exec-TIER
      column: at each multi-device width the combiner-round pair
      {`mesh_fused` (one shard_map-wrapped launch per device,
      `parallel/collectives.py:MeshFusedEngine`), `shmap` (the PR 9
      append+exec chain)} is measured at `--mesh-window` with
      bit-identity vs the 1-DEVICE scan chain verified before timing
      at every point (enforced everywhere); on TPU at the flagship
      4096×10000 config, `mesh_fused >= shmap` must hold at EVERY
      width — the "one launch per round at every mesh width" claim,
      with `launches_per_round` counter-derived in the CSV.
    """
    from node_replication_tpu.harness.mkbench import (
        append_mesh_csv,
        measure_kernel,
        measure_mesh,
        mesh_rows,
        mesh_tier_rows,
    )
    from node_replication_tpu.models import (
        HM_GET,
        HM_PUT,
        make_hashmap,
    )

    devices = jax.devices()
    interpret = args.kernel_interpret
    if not interpret and devices[0].platform != "tpu":
        print("--mesh needs a TPU for its kernel tiers; pass "
              "--kernel-interpret for the CPU rehearsal", file=sys.stderr)
        return 2
    n_dev = len(devices)
    R = args.replicas
    failures: list[str] = []
    if args.mesh_devices:
        counts = sorted({int(x)
                         for x in args.mesh_devices.split(",")})
        for c in counts:
            if c > n_dev:
                failures.append(f"{c} devices requested, {n_dev} "
                                f"available")
            if c < 1 or (R % c):
                failures.append(f"R={R} not divisible by {c} devices")
    else:
        counts = sorted({
            d for d in {1, 2, 4, 8, 16, 32, 64, 128, n_dev}
            if 1 <= d <= n_dev and R % d == 0
        })
    if failures:
        for f in failures:
            print(f"# FAIL: {f}", file=sys.stderr)
        return 1
    if counts[0] != 1:
        counts = [1] + counts  # the curve is relative to 1 device

    points = measure_mesh(
        lambda: make_hashmap(args.keys), counts, R,
        args.writes_per_replica, args.reads_per_replica,
        keyspace=args.keys, duration_s=args.mesh_duration,
        seed=args.seed, wr_opcode=HM_PUT, rd_opcode=HM_GET,
    )
    for p in points:
        if not p.bit_identical:
            failures.append(
                f"{p.devices}-device fleet is NOT bit-identical to "
                f"the 1-device reference after the verification "
                f"steps — the curve would compare different "
                f"computations"
            )

    single_dps = points[0].result.mops * 1e6
    platform = devices[0].platform.lower()
    gate_active = args.mesh_baseline > 0 and platform == "tpu"
    baseline_ratio = (
        single_dps / args.mesh_baseline if args.mesh_baseline else None
    )
    if gate_active:
        tol = args.mesh_baseline_tolerance
        if abs(single_dps - args.mesh_baseline) > \
                tol * args.mesh_baseline:
            failures.append(
                f"1-device flagship throughput {single_dps:.3g} "
                f"dispatches/s is outside ±{tol * 100:.0f}% of the "
                f"baseline {args.mesh_baseline:.3g} (mesh work "
                f"regressed — or improved past — the single-chip "
                f"number; re-baseline deliberately)"
            )

    # ---- per-width exec-TIER column: mesh_fused vs shmap ----------
    # (combiner-round engines at each multi-device width; bit-identity
    # vs the 1-device scan chain enforced everywhere, the
    # mesh_fused >= shmap throughput gate on TPU at the flagship
    # config — the mesh-fused acceptance contract)
    tier_gate_active = (
        not interpret and (R, args.keys) == (4096, 10_000)
    )
    tier_curve = []
    tier_csv_rows: list[dict] = []
    W = args.mesh_window
    for c in counts:
        if c < 2:
            continue  # the tier pair needs a mesh; 1-device is the
            # --kernel flagship sweep's job
        try:
            tpts = measure_kernel(
                args.keys, R, W, duration_s=args.mesh_duration,
                interpret=interpret, seed=args.seed, devices=c,
            )
        except ValueError as e:
            failures.append(f"tier column at {c} devices: {e}")
            continue
        by_tier = {p.tier: p for p in tpts}
        for p in tpts:
            if not p.bit_identical:
                failures.append(
                    f"tier {p.tier} at {p.devices} devices is NOT "
                    f"bit-identical to the 1-device scan chain"
                )
        if tier_gate_active:
            fused = by_tier["mesh_fused"].dispatches_per_sec
            shmap = by_tier["shmap"].dispatches_per_sec
            if fused < shmap:
                failures.append(
                    f"mesh_fused {fused:.3g} dispatches/s < shmap "
                    f"{shmap:.3g} at {c} devices (the one-launch "
                    f"tier must win at every width on the flagship "
                    f"config)"
                )
        tier_curve.append({
            "devices": c,
            "window": W,
            "tiers": {
                p.tier: {
                    "throughput_dps": round(p.dispatches_per_sec, 1),
                    "launches_per_round": p.launches_per_round,
                    "bit_identical": p.bit_identical,
                } for p in tpts
            },
        })
        tier_csv_rows.extend(mesh_tier_rows("bench", W, tpts))

    batch = args.writes_per_replica + args.reads_per_replica
    rows = mesh_rows("bench", points, batch=batch, keys=args.keys,
                     replicas=R)
    append_mesh_csv(args.serve_out, rows + tier_csv_rows)
    base = points[0].result.mops or 1e-9
    curve = [{
        "devices": p.devices,
        "throughput_dps": round(p.result.mops * 1e6, 1),
        "scaling_x": round(p.result.mops / base, 4),
        "efficiency": round(p.result.mops / base / p.devices, 4),
        "spread_pct": round(p.spread_pct, 2),
        "bit_identical": p.bit_identical,
    } for p in points]
    print(json.dumps({
        "metric": "mesh_scaling_curve",
        "value": curve[-1]["scaling_x"],
        "unit": "x_vs_1_device",
        "replicas": R,
        "keys": args.keys,
        "device_counts": counts,
        "device_kind": devices[0].device_kind,
        "platform": platform,
        "single_device_dps": round(single_dps, 1),
        "baseline_dps": args.mesh_baseline,
        "baseline_ratio": (
            round(baseline_ratio, 4)
            if baseline_ratio is not None else None
        ),
        "baseline_gate": (
            "enforced" if gate_active else "skipped (non-TPU)"
        ),
        "curve": curve,
        "tier_window": W,
        "tier_gate": (
            "enforced" if tier_gate_active
            else "skipped (non-TPU or non-flagship)"
        ),
        "tier_curve": tier_curve,
        "bit_identical": all(p.bit_identical for p in points),
    }))
    if failures:
        for f in failures:
            print(f"# FAIL: {f}", file=sys.stderr)
        return 1
    print(
        f"# mesh OK: 1→{counts[-1]} device(s), "
        + " ".join(
            f"{c['devices']}d={c['throughput_dps']:.3g}dps"
            f"({c['efficiency']:.0%})" for c in curve
        )
        + f"; bit-identical at every width; baseline gate "
          f"{'enforced' if gate_active else 'skipped (non-TPU)'}",
        file=sys.stderr,
    )
    return 0


def overload_main(args) -> int:
    """`--overload`: the graceful-degradation gate (ISSUE 9).

    Three phases over the seqreg oracle model:

    1. **Capacity probe** — a short closed-loop run measures the
       frontend's service capacity C (completed ops/sec) and its p95
       latency, from which the SLO deadline D is derived.
    2. **Static baseline** — open-loop arrivals at `--overload-factor`
       × C (default 2×: sustained overload by construction), Poisson
       epochs with heavy-tailed (Pareto) burst sizes and a
       CRITICAL/NORMAL/BULK priority mix, against the PR 3 frontend
       (static `queue_depth` bound, per-request deadline D, no
       controller). The standing queue this builds converts most
       completions into deadline misses — the binary degradation the
       overload plane exists to fix.
    3. **Adaptive run** — the SAME arrival schedule (same seed)
       against `ServeConfig(overload=OverloadConfig(target=D/4))`
       plus client-side circuit breakers; reads ride along and may
       degrade to brownout (bounded-staleness) serving.

    The reported metric is **goodput-under-SLO**: completed ops whose
    client-perceived latency beat D, per second of wall. Hard gates
    (exit 1): adaptive goodput must be STRICTLY higher than static;
    the ack-chain verifier must find zero lost/duplicated acked ops in
    either run (every completed fetch-and-set response must chain
    `0 -> v1 -> ... -> final register read`, covering exactly the
    acked set — a shed op that secretly executed breaks the chain);
    zero CRITICAL sheds while lower-priority ops sat queued
    (`priority_inversions == 0`); and no brownout read served beyond
    its staleness bound. Rows append to `overload_benchmarks.csv`.
    """
    import random as _random
    import threading

    from node_replication_tpu import NodeReplicated
    from node_replication_tpu.harness.mkbench import (
        append_overload_csv,
        overload_rows,
    )
    from node_replication_tpu.models import SR_GET, SR_SET, make_seqreg
    from node_replication_tpu.obs.metrics import get_registry
    from node_replication_tpu.serve import (
        BULK,
        CRITICAL,
        NORMAL,
        CircuitBreaker,
        CircuitOpen,
        DeadlineExceeded,
        OverloadConfig,
        Overloaded,
        ServeConfig,
        ServeFrontend,
    )

    get_registry().enable()
    clients = args.overload_clients
    rng = _random.Random(args.seed)
    failures: list[str] = []

    def build_fe(cfg):
        nr = NodeReplicated(
            make_seqreg(clients), n_replicas=1,
            log_entries=1 << 14, gc_slack=1024, exec_window=1024,
        )
        return ServeFrontend(nr, cfg)

    # ---- phase 1: capacity probe -----------------------------------
    # Service capacity must be measured at FULL batching — a
    # closed-loop probe with `clients` ops in flight measures
    # concurrency-limited latency, not what the combiner can drain,
    # and an arrival rate set from that number is not overload at
    # all. So: pre-fill the queue open-loop and time the drain.
    probe_cfg = ServeConfig(
        queue_depth=max(4096, args.overload_probe_ops),
        batch_max_ops=args.overload_batch, batch_linger_s=0.0005,
    )
    n_probe = args.overload_probe_ops
    with build_fe(probe_cfg) as fe:
        # warm pass, SAME shape as the timed one: the first batch of
        # each padded size jit-compiles, and a compile inside the
        # timed fill+drain would undermeasure capacity — the arrival
        # rate derived from it would then not be overload at all
        # (measured: ~2x undercount on cold caches)
        for _ in range(2):
            warm = [fe.submit((SR_SET, i % clients, 0), rid=0)
                    for i in range(n_probe)]
            fe.drain(timeout=60.0)
            for f in warm:
                f.result(5.0)
        t0 = time.perf_counter()
        futs = [fe.submit((SR_SET, i % clients, 0), rid=0)
                for i in range(n_probe)]
        fe.drain(timeout=60.0)
        probe_dur = time.perf_counter() - t0
        bad = sum(1 for f in futs if f.exception(5.0) is not None)
        if bad:
            failures.append(f"capacity probe: {bad} ops failed")
    capacity = n_probe / probe_dur
    # the SLO: a well-controlled queue (a couple of batches deep)
    # completes within a handful of batch service times
    batch_s = args.overload_batch / capacity
    deadline = min(1.0, max(0.02, 8.0 * batch_s))
    rate = args.overload_factor * capacity
    # size the static queue so a FULL queue's standing delay is ~4x
    # the deadline: at sustained 2x overload the baseline then lives
    # the bufferbloat failure (admitted -> queued past the deadline ->
    # swept), which is precisely the regime adaptive admission fixes —
    # with a queue shorter than capacity x deadline the static bound
    # would accidentally approximate a well-tuned limit and the
    # comparison would measure nothing
    qdepth = max(args.overload_queue_depth,
                 int(capacity * deadline * 4.0))

    # ---- arrival schedule: Poisson epochs, Pareto burst sizes ------
    # one shared schedule (same seed) for both runs: (t, client, kind,
    # priority, burst_id). ~1 in 6 arrivals is a read.
    n_events = min(args.overload_ops,
                   max(200, int(rate * args.overload_seconds)))
    mean_burst = 3.0
    schedule = []
    t = 0.0
    while len(schedule) < n_events:
        t += rng.expovariate(rate / mean_burst)
        burst = min(16, int(rng.paretovariate(1.5)))
        for _ in range(burst):
            kind = "r" if rng.random() < 1 / 6 else "w"
            prio = rng.choices((CRITICAL, NORMAL, BULK),
                               weights=(15, 55, 30))[0]
            schedule.append((t, rng.randrange(clients), kind, prio))
            if len(schedule) >= n_events:
                break
    # writes and reads run on SEPARATE per-client threads: a synced
    # read under load blocks its thread for a full read-sync, and a
    # blocking read inline in the write loop would silently convert
    # the open loop into a submission-limited half-closed one — the
    # "2x capacity" arrival rate would be fiction exactly when the
    # system is busiest (measured: static never built a queue at all)
    by_client = [[] for _ in range(clients)]
    reads_by_client = [[] for _ in range(clients)]
    for ev in schedule:
        (reads_by_client if ev[2] == "r" else by_client)[
            ev[1]].append(ev)

    # ---- open-loop runner (used by both modes) ---------------------
    def run_mode(mode, cfg, use_breaker):
        fe = build_fe(cfg)
        # warm THIS mode's fresh wrapper off-clock: the batch-size
        # tiers and the read path re-trace/compile per instance, and a
        # first-round compile inside the schedule window would expire
        # the entire flood against a ~10ms-scale deadline before the
        # worker can serve one batch. Warm writes write value 0, so
        # the per-register ack chain still starts at 0.
        warm = [fe.submit((SR_SET, i % clients, 0), rid=0)
                for i in range(256)]
        fe.drain(timeout=60.0)
        for f in warm:
            f.result(5.0)
        for c in range(clients):
            fe.read((SR_GET, c), rid=0, min_pos=0)
        before = fe.stats()
        acks = [[] for _ in range(clients)]  # (value, fut)
        shed_vals = [[] for _ in range(clients)]
        copen = [0]
        copen_lock = threading.Lock()
        breakers = [CircuitBreaker(failure_threshold=16,
                                   cooldown_s=0.05)
                    for _ in range(clients)] if use_breaker else None

        def reader(c):
            crng = _random.Random(args.seed * 1000 + c)
            t0 = time.monotonic()
            for ev_t, _c, _kind, _prio in reads_by_client[c]:
                now = time.monotonic()
                due = t0 + ev_t
                if now < due:
                    time.sleep(due - now)
                try:
                    fe.read((SR_GET, crng.randrange(clients)),
                            rid=0)
                except Exception:
                    pass  # reads are load (+ brownout), not the oracle

        def writer(c):
            seq = 0
            t0 = time.monotonic()
            for ev_t, _c, _kind, prio in by_client[c]:
                now = time.monotonic()
                due = t0 + ev_t
                if now < due:
                    time.sleep(due - now)
                if breakers is not None:
                    try:
                        breakers[c].before_call()
                    except CircuitOpen:
                        with copen_lock:
                            copen[0] += 1
                        continue
                value = seq + 1
                try:
                    fut = fe.submit((SR_SET, c, value), rid=0,
                                    deadline_s=deadline,
                                    priority=prio)
                except Overloaded:
                    if breakers is not None:
                        breakers[c].record_failure()
                    shed_vals[c].append(value)
                    continue
                if breakers is not None:
                    breakers[c].record_success()
                seq = value
                acks[c].append((value, fut))

        ths = [threading.Thread(target=writer, args=(c,),
                                name=f"bench-writer-{c}")
               for c in range(clients)]
        ths += [threading.Thread(target=reader, args=(c,),
                                 name=f"bench-reader-{c}")
                for c in range(clients)]
        t0 = time.perf_counter()
        for th in ths:
            th.start()
        for th in ths:
            th.join()
        fe.drain(timeout=30.0)
        duration = time.perf_counter() - t0
        # goodput denominator: the SHARED experiment horizon — last
        # scheduled arrival + the SLO deadline (no in-SLO completion
        # can land later). Using measured wall (arrival window + drain
        # tail) instead would let scheduler noise in the drain decide
        # the static-vs-adaptive comparison; the horizon is identical
        # for both modes by construction, so the gate reduces to the
        # honest question: who completed more ops WITHIN the SLO.
        horizon = schedule[-1][0] + deadline
        # harvest futures + verify the ack chain per register
        completed = good = evicted = missed = lost = dup = 0
        lats: list[float] = []
        for c in range(clients):
            chain = {}  # resp -> written value, acked ops only
            # min_pos=0 forces the SYNCED read path: the verification
            # read must never be served from a brownout-stale replica
            final = fe.read((SR_GET, c), rid=0, min_pos=0)
            for value, fut in acks[c]:
                exc = fut.exception(timeout=30.0)
                if isinstance(exc, DeadlineExceeded):
                    missed += 1
                    continue
                if isinstance(exc, Overloaded):
                    evicted += 1
                    shed_vals[c].append(value)
                    continue
                if exc is not None:
                    failures.append(
                        f"{mode}: client {c} value {value}: "
                        f"unexpected {type(exc).__name__}: {exc}"
                    )
                    continue
                completed += 1
                lats.append(fut.latency_s)
                if fut.latency_s <= deadline:
                    good += 1
                resp = int(fut.result())
                if resp in chain:
                    dup += 1
                    failures.append(
                        f"{mode}: client {c}: two acks chain from "
                        f"{resp} (duplicated op)"
                    )
                chain[resp] = value
            # walk 0 -> ... : must visit every acked op exactly once
            # and end at the final register value
            cur, visited = 0, 0
            while cur in chain:
                cur = chain.pop(cur)
                visited += 1
            if chain or cur != final:
                lost += 1
                failures.append(
                    f"{mode}: client {c}: ack chain broke (visited "
                    f"{visited}, {len(chain)} unreachable acks, "
                    f"chain end {cur} vs register {final}) — a lost "
                    f"ack or a shed op with a log effect"
                )
        after = fe.stats()
        st = {k: after[k] - before[k]
              for k in ("accepted", "shed", "evicted",
                        "deadline_missed", "priority_inversions")}
        st["shed_by_priority"] = {
            k: (after["shed_by_priority"][k]
                - before["shed_by_priority"][k])
            for k in after["shed_by_priority"]
        }
        gov = fe.governor.stats() if fe.governor is not None else {}
        fe.close()
        lats.sort()

        def pct(p):
            return lats[int(p * (len(lats) - 1))] * 1e3 if lats else 0.0

        arrivals = sum(len(b) for b in by_client)
        return {
            "mode": mode,
            "pipeline_overlap": cfg.pipeline_depth,
            "clients": clients,
            "capacity_ops": capacity,
            "rate": rate,
            "deadline_s": deadline,
            "duration_s": duration,
            "arrivals": arrivals,
            "accepted": st["accepted"],
            "completed": completed,
            "good": good,
            "goodput": good / horizon if horizon else 0.0,
            "shed": st["shed"],
            "shed_by_priority": st["shed_by_priority"],
            "evicted": st["evicted"],
            "circuit_open": copen[0],
            "deadline_miss": st["deadline_missed"],
            "brownout_reads": gov.get("brownout_reads", 0),
            "max_brownout_lag": gov.get("max_brownout_lag", 0),
            "priority_inversions": st["priority_inversions"],
            "p50_ms": pct(0.50),
            "p99_ms": pct(0.99),
            "lost": lost,
            "duplicated": dup,
        }

    # ---- phase 2: static baseline ----------------------------------
    static_cfg = ServeConfig(
        queue_depth=qdepth,
        batch_max_ops=args.overload_batch, batch_linger_s=0.0005,
    )
    static = run_mode("static", static_cfg, use_breaker=False)

    # ---- phase 3: adaptive controller ------------------------------
    adaptive_cfg = ServeConfig(
        queue_depth=qdepth,
        batch_max_ops=args.overload_batch, batch_linger_s=0.0005,
        overload=OverloadConfig(
            # the setpoint leaves the batch service time inside the
            # SLO: queue delay ~deadline/2 + a couple of batch times
            # of service still beats the deadline
            target_delay_s=deadline / 2.0,
            min_limit=max(4, args.overload_batch // 4),
            brownout_max_lag=4096,
        ),
    )
    adaptive = run_mode("adaptive", adaptive_cfg, use_breaker=True)

    # ---- phase 4: pipelined serving (ISSUE 14) ----------------------
    # the SAME adaptive controller with the serve pipeline at depth 1:
    # round N+1's assembly overlaps round N's device work, so the
    # sojourn time the AIMD loop controls shrinks — at 2x capacity
    # that overlap must convert into strictly more goodput-under-SLO
    # than the serial adaptive run (same schedule, same seed, same
    # ack-chain verification)
    import dataclasses as _dc

    pipelined_cfg = _dc.replace(adaptive_cfg, pipeline_depth=1)
    pipelined = run_mode("pipelined", pipelined_cfg, use_breaker=True)

    # ---- gates ------------------------------------------------------
    # The pipelined-vs-serial THROUGHPUT comparison enforces on TPU
    # only (the --kernel/--mesh convention: off-TPU the "device work"
    # the pipeline overlaps is GIL-contended host compute, and at this
    # bench's millisecond rounds the comparison measures scheduler
    # noise, not the overlap — both directions, run to run). The
    # pipelined run's CORRECTNESS gates — zero lost/dup acks, zero
    # priority inversions, in-bound brownout reads — are hard on
    # every platform, same as the other modes.
    on_tpu = jax.devices()[0].platform == "tpu"
    if on_tpu and pipelined["goodput"] <= adaptive["goodput"]:
        failures.append(
            f"pipelined goodput {pipelined['goodput']:.1f} ops/s did "
            f"not strictly beat the serial adaptive run "
            f"{adaptive['goodput']:.1f} ops/s — the overlap bought "
            f"nothing at {args.overload_factor}x capacity"
        )
    if not on_tpu:
        print(
            f"# pipelined-vs-serial throughput gate self-skipped "
            f"(platform={jax.devices()[0].platform}): pipelined "
            f"{pipelined['goodput']:.1f} vs serial adaptive "
            f"{adaptive['goodput']:.1f} good ops/s (recorded, not "
            f"gated)",
            file=sys.stderr,
        )
    if adaptive["goodput"] <= static["goodput"]:
        failures.append(
            f"adaptive goodput {adaptive['goodput']:.1f} ops/s did "
            f"not beat static {static['goodput']:.1f} ops/s at "
            f"{args.overload_factor}x capacity"
        )
    for run in (static, adaptive, pipelined):
        if run["priority_inversions"]:
            failures.append(
                f"{run['mode']}: {run['priority_inversions']} "
                f"CRITICAL shed(s) while BULK/NORMAL ops sat queued"
            )
    for run in (adaptive, pipelined):
        if run["max_brownout_lag"] > 4096:
            failures.append(
                f"{run['mode']}: brownout read served at lag "
                f"{run['max_brownout_lag']} > bound 4096"
            )
    if adaptive["shed_by_priority"]["critical"] > \
            adaptive["shed_by_priority"]["bulk"] and \
            adaptive["shed"] > 0:
        failures.append(
            "adaptive run shed more CRITICAL than BULK ops — "
            "strict-priority shedding is not engaging"
        )

    rows = overload_rows("bench", static) + \
        overload_rows("bench", adaptive) + \
        overload_rows("bench", pipelined)
    append_overload_csv(args.serve_out, rows)
    print(json.dumps({
        "metric": "serve_overload_goodput_under_slo",
        "value": round(adaptive["goodput"], 1),
        "unit": "good_ops_per_sec",
        "vs_static": round(
            adaptive["goodput"] / static["goodput"], 3
        ) if static["goodput"] else None,
        "capacity_ops_per_sec": round(capacity, 1),
        "arrival_rate": round(rate, 1),
        "deadline_ms": round(deadline * 1e3, 2),
        "static": {k: (round(v, 3) if isinstance(v, float) else v)
                   for k, v in static.items()
                   if k != "shed_by_priority"},
        "adaptive": {k: (round(v, 3) if isinstance(v, float) else v)
                     for k, v in adaptive.items()
                     if k != "shed_by_priority"},
        "pipelined": {k: (round(v, 3) if isinstance(v, float) else v)
                      for k, v in pipelined.items()
                      if k != "shed_by_priority"},
        "pipelined_vs_serial": round(
            pipelined["goodput"] / adaptive["goodput"], 3
        ) if adaptive["goodput"] else None,
        "shed_by_priority": {
            "static": static["shed_by_priority"],
            "adaptive": adaptive["shed_by_priority"],
            "pipelined": pipelined["shed_by_priority"],
        },
    }))
    if failures:
        for f in failures:
            print(f"# FAIL: {f}", file=sys.stderr)
        return 1
    ratio = (
        f"{adaptive['goodput'] / static['goodput']:.2f}x"
        if static["goodput"] > 0 else "static collapsed to 0"
    )
    print(
        f"# overload OK: goodput-under-SLO {adaptive['goodput']:.0f} "
        f"vs static {static['goodput']:.0f} ops/s ({ratio}); "
        f"pipelined {pipelined['goodput']:.0f} ops/s "
        f"({pipelined['goodput'] / adaptive['goodput']:.2f}x serial "
        f"adaptive) "
        f"at {args.overload_factor}x capacity "
        f"({rate:.0f} arrivals/s, deadline {deadline * 1e3:.0f}ms); "
        f"sheds c/n/b = "
        f"{adaptive['shed_by_priority']['critical']}/"
        f"{adaptive['shed_by_priority']['normal']}/"
        f"{adaptive['shed_by_priority']['bulk']}, "
        f"{adaptive['circuit_open']} circuit-open fast-fails, "
        f"{adaptive['brownout_reads']} brownout read(s) "
        f"(max lag {adaptive['max_brownout_lag']}); "
        f"zero lost/duplicated acks in both runs",
        file=sys.stderr,
    )
    return 0


def chaos_main(args) -> int:
    """`--chaos`: the serve bench under injected replica kills (ISSUE 4).

    Reuses the `--serve` closed-loop seqreg verifier — client `c` owns
    register `c` and writes `1..N`, so every fetch-and-set response
    must equal the previous value — while a deterministic `FaultPlan`
    kills replica 1's serve worker mid-run (`serve-batch` site: the
    injection fires BEFORE the batch touches the log, so every
    in-flight request is exactly-once retryable). The lifecycle
    manager quarantines (fencing the corpse out of log GC), repairs by
    donor-clone + replay, and restarts the worker; clients ride
    `call_with_retry`'s transparent re-route.

    Hard gates (exit 1): any lost/duplicated/reordered response, a
    kill that did not fire, a repair that did not complete back to
    HEALTHY, replicas not bit-identical after the run, or availability
    below `--chaos-availability-min`. Emits one JSON line with repair
    p50/p95 latency + availability and appends a
    `chaos_benchmarks.csv` row.
    """
    from node_replication_tpu import NodeReplicated
    from node_replication_tpu.fault import (
        HEALTHY,
        FaultPlan,
        FaultSpec,
        ReplicaLifecycleManager,
    )
    from node_replication_tpu.harness.mkbench import (
        append_chaos_csv,
        chaos_rows,
        measure_chaos,
    )
    from node_replication_tpu.models import SR_SET, make_seqreg
    from node_replication_tpu.obs.metrics import get_registry
    from node_replication_tpu.serve import (
        RetryPolicy,
        ServeConfig,
        ServeFrontend,
    )

    get_registry().enable()
    clients = args.serve_clients
    per_client = max(1, args.serve_ops // clients)
    n_ops = per_client * clients
    failures: list[str] = []

    nr = NodeReplicated(
        make_seqreg(clients),
        n_replicas=max(2, args.serve_replicas),
        log_entries=4096,
        gc_slack=256,
        exec_window=256,
    )
    cfg = ServeConfig(
        queue_depth=args.serve_queue_depth,
        batch_max_ops=args.serve_batch,
        batch_linger_s=args.serve_linger,
        failover=True,
    )
    victim = nr.n_replicas - 1
    plan = FaultPlan(
        [
            FaultSpec(site="serve-batch", action="raise", rid=victim,
                      after=args.chaos_kill_after, count=1)
            for _ in range(args.chaos_kills)
        ],
        seed=args.seed,
    )

    def op_of(c, i):
        return (SR_SET, c, i + 1)

    def check(c, i, resp):
        if resp != i:
            return (f"client {c} op {i}: expected previous value "
                    f"{i}, got {resp} (lost/dup/reordered)")
        return None

    retry = RetryPolicy(max_attempts=args.chaos_retry_attempts,
                        base_backoff_s=0.001, max_backoff_s=0.25)
    with ServeFrontend(nr, cfg) as fe:
        manager = ReplicaLifecycleManager(nr, fe)
        res = measure_chaos(
            fe, manager, plan, op_of, n_ops, clients, retry=retry,
            check=check, name="seqreg-chaos",
        )
    s = res.serve

    if not res.fired:
        failures.append("fault plan never fired (no kill injected)")
    if len(res.repairs) < len(res.fired):
        failures.append(
            f"{len(res.fired)} kill(s) but only {len(res.repairs)} "
            f"completed repair(s)"
        )
    if res.health["states"].count(HEALTHY) != nr.n_replicas:
        failures.append(
            f"fleet not fully healthy after settle: "
            f"{res.health['states']}"
        )
    if s.completed != n_ops:
        failures.append(
            f"lost responses: completed {s.completed} != {n_ops}"
        )
    for c, i, msg in (s.errors + s.transport_errors)[:10]:
        failures.append(str(msg))
    if res.availability < args.chaos_availability_min:
        failures.append(
            f"availability {res.availability:.4f} < "
            f"{args.chaos_availability_min}"
        )
    # the repaired replica must be bit-identical to a healthy donor's
    # replay — the repair-by-replay acceptance gate
    nr.sync()
    if not nr.replicas_equal():
        failures.append(
            "replicas diverged after repair (bit-identity violated)"
        )

    append_chaos_csv(args.serve_out, chaos_rows("bench", res))
    print(json.dumps({
        "metric": "chaos_seqreg_closed_loop",
        "value": round(res.availability, 6),
        "unit": "availability",
        "clients": clients,
        "ops": n_ops,
        "kills": len(res.fired),
        "repairs": len(res.repairs),
        "rehomed": res.rehomed,
        "repair_p50_ms": round(res.repair_ms(50), 3),
        "repair_p95_ms": round(res.repair_ms(95), 3),
        "repair_max_ms": round(res.repair_ms(100), 3),
        "throughput_ops_per_sec": round(s.throughput, 1),
        "p50_ms": round(s.percentile_ms(50), 3),
        "p95_ms": round(s.percentile_ms(95), 3),
        "p99_ms": round(s.percentile_ms(99), 3),
        "verified": {
            "completed": s.completed,
            "lost": n_ops - s.completed,
            "sequence_errors": len(s.errors),
            "transport_errors": len(s.transport_errors),
            "replicas_equal": not any("diverged" in f
                                      for f in failures),
            "health": res.health["states"],
        },
    }))
    if failures:
        for f in failures:
            print(f"# FAIL: {f}", file=sys.stderr)
        return 1
    print(
        f"# chaos OK: {n_ops} sequence-verified ops from {clients} "
        f"clients survived {len(res.fired)} replica kill(s); "
        f"availability {res.availability:.4f}, repair p50/p95 = "
        f"{res.repair_ms(50):.0f}/{res.repair_ms(95):.0f} ms, "
        f"{res.rehomed} request(s) re-homed",
        file=sys.stderr,
    )
    return 0


def crash_child_main(args) -> int:
    """`--crash-child` (internal): the victim process of the crash
    harness. Serves an ENDLESS sequence-verified seqreg stream with
    durable acks (`ServeConfig(durability=...)` over an attached WAL),
    records every fsync-acked response into `<dir>/acks.log` (one
    flushed line per ack, written only AFTER `result()` — so an ack
    line implies the op's WAL record is fsynced), and takes one
    durable snapshot mid-stream. It never exits on its own: the parent
    SIGKILLs it at a seeded ack count, exactly the preemption the
    durability plane exists for."""
    import os
    import threading

    from node_replication_tpu import NodeReplicated
    from node_replication_tpu.durable import (
        WriteAheadLog,
        save_durable_snapshot,
    )
    from node_replication_tpu.models import SR_SET, make_seqreg
    from node_replication_tpu.serve import (
        RetryPolicy,
        ServeConfig,
        ServeFrontend,
        call_with_retry,
    )

    d = args.crash_dir
    clients = args.serve_clients
    nr = NodeReplicated(
        make_seqreg(clients),
        n_replicas=max(1, args.serve_replicas),
        log_entries=1 << 15,
        gc_slack=512,
        exec_window=256,
    )
    wal = WriteAheadLog(os.path.join(d, "wal"),
                        policy=args.crash_durability)
    nr.attach_wal(wal)
    cfg = ServeConfig(
        queue_depth=args.serve_queue_depth,
        batch_max_ops=args.serve_batch,
        batch_linger_s=args.serve_linger,
        durability=args.crash_durability,
    )
    fe = ServeFrontend(nr, cfg)
    rids = fe.rids
    ack_lock = threading.Lock()
    ack_f = open(os.path.join(d, "acks.log"), "a")
    acked = [0]
    retry = RetryPolicy(max_attempts=64, base_backoff_s=0.001,
                        max_backoff_s=0.1)

    def client(c: int) -> None:
        i = 1
        while True:
            resp = call_with_retry(
                fe, (SR_SET, c, i), rid=rids[c % len(rids)],
                policy=retry,
            )
            with ack_lock:
                if resp != i - 1:
                    ack_f.write(f"ERR {c} {i} {resp}\n")
                else:
                    ack_f.write(f"{c} {i}\n")
                ack_f.flush()
                acked[0] += 1
            i += 1

    for c in range(clients):
        threading.Thread(target=client, args=(c,),
                         name=f"bench-client-{c}",
                         daemon=True).start()
    # one durable snapshot mid-stream, so recovery exercises the real
    # snapshot-base + WAL-tail split (not just replay-from-zero)
    snap_after = args.crash_snapshot_after
    while True:
        time.sleep(0.02)
        if snap_after > 0:
            with ack_lock:
                n = acked[0]
            if n >= snap_after:
                save_durable_snapshot(nr, d)
                snap_after = 0  # once


def crash_main(args) -> int:
    """`--crash`: the crash-consistency gate (ISSUE 5).

    Forks a child serve loop (durable-ack seqreg stream journaled into
    a WAL), SIGKILLs it at a seeded ack count, then restarts FROM DISK
    via `ServeFrontend.from_recovery` and verifies, with hard exits:

    - **no lost ack**: every fsync-acked `(client, i)` recorded before
      the kill is reflected in the recovered registers
      (`value[c] >= max acked i`);
    - **no duplicate**: the recovered WAL's per-slot history is
      exactly `1..k`, each value once, in order — a duplicated or
      reordered record would break the chain;
    - **bit-identical restart**: replaying the recovered log from
      deterministic init reproduces the recovered fleet's states
      bit-for-bit (the paper's recovery model, now crash-tested);
    - **serves on**: each client pushes a few more ops through the
      recovered frontend and the fetch-and-set oracle still holds.
    """
    import os
    import shutil
    import signal
    import subprocess
    import tempfile

    import numpy as np

    from node_replication_tpu.core.checkpoint import recover_states
    from node_replication_tpu.harness.mkbench import (
        append_recovery_csv,
        recovery_rows,
    )
    from node_replication_tpu.models import SR_GET, SR_SET, make_seqreg
    from node_replication_tpu.serve import ServeConfig, ServeFrontend

    clients = args.serve_clients
    kill_after = args.crash_kill_after_acks
    if kill_after <= 0:
        import random as _random

        kill_after = _random.Random(args.seed).randrange(250, 600)
    snap_after = args.crash_snapshot_after
    if snap_after < 0:
        snap_after = kill_after // 2
    d = args.crash_dir or tempfile.mkdtemp(prefix="nr-crash-")
    os.makedirs(d, exist_ok=True)
    acks_path = os.path.join(d, "acks.log")
    failures: list[str] = []

    child_log = open(os.path.join(d, "child.log"), "w")
    child = subprocess.Popen(
        [
            sys.executable, os.path.abspath(__file__), "--crash-child",
            "--crash-dir", d,
            "--serve-clients", str(clients),
            "--serve-replicas", str(args.serve_replicas),
            "--serve-queue-depth", str(args.serve_queue_depth),
            "--serve-batch", str(args.serve_batch),
            "--serve-linger", str(args.serve_linger),
            "--crash-durability", args.crash_durability,
            "--crash-snapshot-after", str(snap_after),
            "--seed", str(args.seed),
        ],
        stdout=child_log, stderr=child_log,
        env=cpu_child_env("--crash-child"),
    )

    def ack_lines() -> list[str]:
        try:
            with open(acks_path) as f:
                data = f.read()
        except FileNotFoundError:
            return []
        lines = data.split("\n")
        return [ln for ln in lines[:-1] if ln]  # drop partial tail

    t_end = time.monotonic() + args.crash_timeout
    killed = False
    while time.monotonic() < t_end:
        if child.poll() is not None:
            break
        if len(ack_lines()) >= kill_after:
            os.kill(child.pid, signal.SIGKILL)
            killed = True
            break
        time.sleep(0.02)
    if not killed:
        if child.poll() is None:
            os.kill(child.pid, signal.SIGKILL)
            failures.append(
                f"child reached only {len(ack_lines())} acks within "
                f"{args.crash_timeout}s (wanted {kill_after}); see "
                f"{d}/child.log"
            )
        else:
            failures.append(
                f"child exited early (rc {child.returncode}) before "
                f"the seeded kill; see {d}/child.log"
            )
    child.wait()
    child_log.close()

    # what the clients were TOLD is durable
    acked_max = [0] * clients
    acked_total = 0
    for ln in ack_lines():
        parts = ln.split()
        if parts[0] == "ERR":
            failures.append(f"child observed oracle violation: {ln}")
            continue
        c, i = int(parts[0]), int(parts[1])
        if i != acked_max[c] + 1:
            failures.append(
                f"client {c} ack sequence broken at {i} "
                f"(after {acked_max[c]})"
            )
        acked_max[c] = max(acked_max[c], i)
        acked_total += 1

    # restart from disk through the serve-layer recovery entry
    dispatch = make_seqreg(clients)
    cfg = ServeConfig(
        queue_depth=args.serve_queue_depth,
        batch_max_ops=args.serve_batch,
        batch_linger_s=args.serve_linger,
        durability=args.crash_durability,
    )
    fe = ServeFrontend.from_recovery(d, dispatch, cfg)
    report = fe.recovery_report
    nr = fe.nr

    lost = 0
    values = []
    for c in range(clients):
        v = fe.read((SR_GET, c), rid=0)
        values.append(v)
        if v < acked_max[c]:
            lost += acked_max[c] - v
            failures.append(
                f"client {c}: fsync-acked up to {acked_max[c]} but "
                f"recovered register holds {v} (LOST ACKED WRITES)"
            )

    # duplicate/reorder scan over the recovered WAL's full history
    # (single segment at this run size, so position 0 is still there)
    duplicated = 0
    seen_next = [1] * clients
    for rec in nr.wal.records(0):
        for opc, row in zip(rec.opcodes, rec.args):
            c, v = int(row[0]) % clients, int(row[1])
            if v < seen_next[c]:
                duplicated += 1
                failures.append(
                    f"client {c}: WAL holds value {v} again after "
                    f"reaching {seen_next[c] - 1} (DUPLICATED OP)"
                )
            elif v > seen_next[c]:
                failures.append(
                    f"client {c}: WAL skips from {seen_next[c] - 1} "
                    f"to {v} (hole in journaled history)"
                )
                seen_next[c] = v + 1
            else:
                seen_next[c] += 1

    # bit-identity: the recovered fleet must equal a from-init replay
    # of the recovered log (the acceptance criterion's third clause)
    import jax

    _, replay_states = recover_states(dispatch, nr.spec, nr.log)
    for a, b in zip(jax.tree.leaves(nr.states),
                    jax.tree.leaves(replay_states)):
        if not np.array_equal(np.asarray(a), np.asarray(b)):
            failures.append(
                "recovered states are NOT bit-identical to replaying "
                "the recovered log from init"
            )
            break

    # the recovered frontend must serve on: continue each sequence
    post_ops = 0
    with fe:
        for c in range(clients):
            for i in range(values[c] + 1, values[c] + 4):
                resp = fe.call((SR_SET, c, i),
                               rid=fe.rids[c % len(fe.rids)])
                if resp != i - 1:
                    failures.append(
                        f"post-restart client {c} op {i}: expected "
                        f"{i - 1}, got {resp}"
                    )
                post_ops += 1

    append_recovery_csv(args.serve_out, recovery_rows(
        "bench", report, clients=clients,
        durability=args.crash_durability, acked=acked_total,
        kill_after=kill_after, lost=lost, duplicated=duplicated,
        post_restart_ops=post_ops,
    ))
    print(json.dumps({
        "metric": "crash_recovery_durable_acks",
        "value": lost + duplicated,
        "unit": "lost_or_duplicated_acked_ops",
        "clients": clients,
        "durability": args.crash_durability,
        "acked_before_kill": acked_total,
        "kill_after_acks": kill_after,
        "snapshot_pos": report.snapshot_pos,
        "wal_records": report.wal_records,
        "wal_ops_replayed": report.wal_ops,
        "wal_truncated_bytes": report.wal_truncated_bytes,
        "recovery_s": round(report.duration_s, 4),
        "tail": report.tail,
        "lost": lost,
        "duplicated": duplicated,
        "post_restart_ops": post_ops,
        "bit_identical": not any("bit-identical" in f
                                 for f in failures),
    }))
    if not args.crash_dir:
        shutil.rmtree(d, ignore_errors=True)
    if failures:
        for f in failures:
            print(f"# FAIL: {f}", file=sys.stderr)
        return 1
    print(
        f"# crash OK: SIGKILL after {acked_total} fsync-acked ops; "
        f"recovery (snapshot@{report.snapshot_pos} + "
        f"{report.wal_ops} WAL ops, {report.duration_s * 1e3:.0f}ms) "
        f"lost 0, duplicated 0, bit-identical restart, served "
        f"{post_ops} more ops",
        file=sys.stderr,
    )
    return 0


def follower_primary_main(args) -> int:
    """`--follower-primary` (internal): the PRIMARY process of the
    follower-fleet harness. The `--crash-child` durable-ack seqreg
    loop (acks journaled to `<dir>/acks.log` only after `result()`)
    with the replication plane attached: a `ReplicationShipper`
    streams the WAL into `--feed-dir` and is installed as the
    frontend's `ack_barrier`, so every acked op is BOTH fsynced and
    shipped (ship-before-ack — the property that makes the parent's
    zero-lost-acks gate meaningful across a promotion). Never exits on
    its own: the parent SIGKILLs it at a seeded ack count."""
    import os
    import threading

    from node_replication_tpu import NodeReplicated
    from node_replication_tpu.durable import (
        WriteAheadLog,
        save_durable_snapshot,
    )
    from node_replication_tpu.models import SR_SET, make_seqreg
    from node_replication_tpu.repl import DirectoryFeed, ReplicationShipper
    from node_replication_tpu.serve import (
        RetryPolicy,
        ServeConfig,
        ServeFrontend,
        call_with_retry,
    )

    d = args.crash_dir
    clients = args.serve_clients
    nr = NodeReplicated(
        make_seqreg(clients),
        n_replicas=max(1, args.serve_replicas),
        log_entries=1 << 15,
        gc_slack=512,
        exec_window=256,
    )
    wal = WriteAheadLog(os.path.join(d, "wal"),
                        policy=args.crash_durability)
    nr.attach_wal(wal)
    feed = DirectoryFeed(args.feed_dir, arg_width=nr.spec.arg_width)
    shipper = ReplicationShipper(wal, feed, poll_s=0.002,
                                 heartbeat_interval_s=0.02)
    cfg = ServeConfig(
        queue_depth=args.serve_queue_depth,
        batch_max_ops=args.serve_batch,
        batch_linger_s=args.serve_linger,
        durability=args.crash_durability,
        # --tree-obs mode: a metrics exporter on a side port, address
        # published for the parent's FleetCollector
        obs_port=0 if args.obs_port_file else None,
        obs_node_id="primary",
    )
    fe = ServeFrontend(nr, cfg)
    if args.obs_port_file:
        from node_replication_tpu.durable.wal import durable_publish

        durable_publish(
            args.obs_port_file,
            f"{fe.exporter.address[0]} "
            f"{fe.exporter.address[1]}".encode(),
        )
    if args.tree_port_file:
        # --tree mode: serve the feed (and snapshots) over TCP and
        # gate acks on downstream receipt too — an ack then implies
        # fsynced AND feed-visible AND received by every direct relay,
        # which is exactly what makes a mid-tree promotion lossless
        # after this process is SIGKILLed (the relays are all a
        # promoted follower can still reach)
        from node_replication_tpu.durable.wal import durable_publish
        from node_replication_tpu.repl import (
            FeedServer,
            make_tree_barrier,
        )

        server = FeedServer(feed, snapshot_dir=d, wal=wal)
        fe.ack_barrier = make_tree_barrier(
            shipper, server,
            min_clients=max(1, args.tree_min_downstream),
            timeout=60.0,
        )
        durable_publish(
            args.tree_port_file,
            f"{server.address[0]} {server.address[1]}".encode(),
        )
    else:
        fe.ack_barrier = shipper.barrier  # ship-before-ack
    rids = fe.rids
    ack_lock = threading.Lock()
    ack_f = open(os.path.join(d, "acks.log"), "a")
    acked = [0]
    retry = RetryPolicy(max_attempts=64, base_backoff_s=0.001,
                       max_backoff_s=0.1)

    def client(c: int) -> None:
        i = 1
        while True:
            resp = call_with_retry(
                fe, (SR_SET, c, i), rid=rids[c % len(rids)],
                policy=retry,
            )
            with ack_lock:
                if resp != i - 1:
                    ack_f.write(f"ERR {c} {i} {resp}\n")
                else:
                    ack_f.write(f"{c} {i}\n")
                ack_f.flush()
                acked[0] += 1
            i += 1

    for c in range(clients):
        threading.Thread(target=client, args=(c,),
                         name=f"bench-client-{c}",
                         daemon=True).start()
    # one durable snapshot mid-stream: raises the WAL reclaim floor,
    # so the run also exercises the reclaim-vs-ship pin interplay
    snap_after = args.crash_snapshot_after
    while True:
        time.sleep(0.02)
        if snap_after > 0:
            with ack_lock:
                n = acked[0]
            if n >= snap_after:
                save_durable_snapshot(nr, d)
                snap_after = 0  # once


def follower_main(args) -> int:
    """`--follower`: the replication gate (ISSUE 6).

    Forks a primary serve loop (durable, shipped acks — see
    `--follower-primary`), follows its feed with an IN-PROCESS
    `Follower` (a second, independent fleet in this process: the
    multi-process split runs primary | follower), and verifies, with
    hard exits:

    - **bounded staleness**: reads served by the follower at
      `max_lag_pos` never observe an applied position older than the
      bound (checked per read), and per-client values are monotone;
    - **failover**: SIGKILL of the primary at a seeded ack count is
      detected by heartbeat silence (`fault/` health machine), the
      most-advanced follower is promoted (feed drained under
      torn-tail rules, epoch fenced), and the measured RTO
      (detect + promote) is reported;
    - **no lost ack**: every fsync-and-ship-acked `(client, i)` is in
      the promoted registers;
    - **no duplicate**: the promoted follower's WAL per-slot history
      is exactly `1..k` in order;
    - **bit-identity at a common position**: the primary's on-disk
      WAL and the follower's WAL hold identical records up to
      `min(primary durable tail, follower applied)`, and the
      follower's live states equal a from-init replay of its own log
      — composed, follower state IS the primary's fold;
    - **zombie fencing**: a publish stamped with the dead primary's
      epoch is rejected by the feed;
    - **serves on**: clients continue their sequences through the
      promoted frontend with durable acks.
    """
    import os
    import shutil
    import signal
    import subprocess
    import tempfile

    import numpy as np

    from node_replication_tpu.core.checkpoint import recover_states
    from node_replication_tpu.durable import WriteAheadLog
    from node_replication_tpu.harness.mkbench import (
        append_replication_csv,
        replication_rows,
    )
    from node_replication_tpu.models import SR_GET, SR_SET, make_seqreg
    from node_replication_tpu.repl import (
        DirectoryFeed,
        EpochFencedError,
        Follower,
        PromotionManager,
    )
    from node_replication_tpu.serve import ServeConfig, StaleRead

    clients = args.serve_clients
    kill_after = args.follower_kill_after_acks
    if kill_after <= 0:
        import random as _random

        kill_after = _random.Random(args.seed).randrange(250, 600)
    snap_after = args.crash_snapshot_after
    if snap_after < 0:
        snap_after = kill_after // 2
    max_lag = args.follower_max_lag
    base = args.follower_dir or tempfile.mkdtemp(prefix="nr-follower-")
    primary_d = os.path.join(base, "primary")
    feed_d = os.path.join(base, "feed")
    follower_d = os.path.join(base, "follower")
    for p in (primary_d, feed_d, follower_d):
        os.makedirs(p, exist_ok=True)
    acks_path = os.path.join(primary_d, "acks.log")
    failures: list[str] = []

    dispatch = make_seqreg(clients)
    feed = DirectoryFeed(feed_d, arg_width=dispatch.arg_width)
    follower = Follower(
        dispatch, feed, follower_d,
        config=ServeConfig(
            queue_depth=args.serve_queue_depth,
            batch_max_ops=args.serve_batch,
            batch_linger_s=args.serve_linger,
            durability="batch",
        ),
        poll_s=0.002,
        nr_kwargs=dict(n_replicas=1, log_entries=1 << 15,
                       gc_slack=512, exec_window=256),
    )
    manager = PromotionManager(
        feed, [follower],
        heartbeat_timeout_s=args.follower_heartbeat_timeout,
        check_interval_s=0.03,
    )
    manager.start()

    child_log = open(os.path.join(base, "child.log"), "w")
    child = subprocess.Popen(
        [
            sys.executable, os.path.abspath(__file__),
            "--follower-primary",
            "--crash-dir", primary_d,
            "--feed-dir", feed_d,
            "--serve-clients", str(clients),
            "--serve-replicas", str(args.serve_replicas),
            "--serve-queue-depth", str(args.serve_queue_depth),
            "--serve-batch", str(args.serve_batch),
            "--serve-linger", str(args.serve_linger),
            "--crash-durability", "batch",
            "--crash-snapshot-after", str(snap_after),
            "--seed", str(args.seed),
        ],
        stdout=child_log, stderr=child_log,
        env=cpu_child_env("--follower-primary"),
    )

    def ack_lines() -> list[str]:
        try:
            with open(acks_path) as f:
                data = f.read()
        except FileNotFoundError:
            return []
        lines = data.split("\n")
        return [ln for ln in lines[:-1] if ln]  # drop partial tail

    # ---- phase 1: staleness-bounded follower reads under load ------
    reads = 0
    stale_reads = 0
    last_seen = [0] * clients
    t_end = time.monotonic() + args.follower_timeout
    killed = False
    t_kill = None
    while time.monotonic() < t_end:
        if child.poll() is not None:
            break
        if len(ack_lines()) >= kill_after:
            os.kill(child.pid, signal.SIGKILL)
            t_kill = time.monotonic()
            killed = True
            break
        c = reads % clients
        try:
            v, applied, bound = follower.read_result(
                (SR_GET, c), max_lag_pos=max_lag, wait_s=0.25,
            )
        except StaleRead:
            stale_reads += 1
            continue
        finally:
            reads += 1
        if applied < bound:
            failures.append(
                f"read {reads} served below its staleness bound: "
                f"applied {applied} < bound {bound} (max_lag_pos "
                f"{max_lag})"
            )
        if v < last_seen[c]:
            failures.append(
                f"client {c} read went backwards: {v} after "
                f"{last_seen[c]} (follower reads must be monotone)"
            )
        last_seen[c] = max(last_seen[c], v)
    if not killed:
        if child.poll() is None:
            os.kill(child.pid, signal.SIGKILL)
            failures.append(
                f"primary reached only {len(ack_lines())} acks within "
                f"{args.follower_timeout}s (wanted {kill_after}); see "
                f"{base}/child.log"
            )
        else:
            failures.append(
                f"primary exited early (rc {child.returncode}) before "
                f"the seeded kill; see {base}/child.log"
            )
        t_kill = time.monotonic()
    child.wait()
    child_log.close()

    # what the clients were TOLD is durable AND shipped
    acked_max = [0] * clients
    acked_total = 0
    for ln in ack_lines():
        parts = ln.split()
        if parts[0] == "ERR":
            failures.append(f"primary observed oracle violation: {ln}")
            continue
        c, i = int(parts[0]), int(parts[1])
        if i != acked_max[c] + 1:
            failures.append(
                f"client {c} ack sequence broken at {i} "
                f"(after {acked_max[c]})"
            )
        acked_max[c] = max(acked_max[c], i)
        acked_total += 1

    # ---- phase 2: detection + election + promotion (measured RTO) --
    report = manager.wait(timeout=args.follower_timeout)
    rto_wall = time.monotonic() - t_kill
    if report is None:
        for f in failures:
            print(f"# FAIL: {f}", file=sys.stderr)
        print("# FAIL: promotion did not complete (no report)",
              file=sys.stderr)
        return 1
    if not follower.promoted or follower.frontend.read_only:
        failures.append("follower not serving writes after promotion")

    # no lost ack: every acked value is in the promoted registers
    lost = 0
    values = []
    for c in range(clients):
        v = follower.frontend.read((SR_GET, c), rid=0)
        values.append(v)
        if v < acked_max[c]:
            lost += acked_max[c] - v
            failures.append(
                f"client {c}: acked up to {acked_max[c]} but the "
                f"promoted follower holds {v} (LOST ACKED WRITES)"
            )

    # no duplicate: the follower's journaled per-slot history chains
    duplicated = 0
    seen_next = [1] * clients
    for rec in follower.nr.wal.records(0):
        for opc, row in zip(rec.opcodes, rec.args):
            c, v = int(row[0]) % clients, int(row[1])
            if v < seen_next[c]:
                duplicated += 1
                failures.append(
                    f"client {c}: follower WAL holds value {v} again "
                    f"after reaching {seen_next[c] - 1} (DUPLICATED)"
                )
            elif v > seen_next[c]:
                failures.append(
                    f"client {c}: follower WAL skips from "
                    f"{seen_next[c] - 1} to {v} (hole in history)"
                )
                seen_next[c] = v + 1
            else:
                seen_next[c] += 1

    # bit-identity at a common position: the primary's on-disk WAL and
    # the follower's WAL must hold IDENTICAL records up to
    # min(primary durable tail, follower applied) — with deterministic
    # replay (checked next) that makes the states folds of the same
    # history, i.e. bit-identical at that position
    primary_wal = WriteAheadLog(os.path.join(primary_d, "wal"),
                                policy="batch",
                                arg_width=dispatch.arg_width)
    common = min(primary_wal.tail, follower.applied_pos())
    base_pos = max(primary_wal.base, follower.nr.wal.base)
    mismatches = 0
    p_iter = primary_wal.records(base_pos)
    f_iter = follower.nr.wal.records(base_pos)

    def flat_ops(it, upto):
        for rec in it:
            for j in range(rec.count):
                pos = rec.pos + j
                if pos >= upto:
                    return
                yield pos, int(rec.opcodes[j]), tuple(
                    int(a) for a in rec.args[j]
                )

    for (pp, po, pa), (fp, fo, fa) in zip(
        flat_ops(p_iter, common), flat_ops(f_iter, common)
    ):
        if (pp, po, pa) != (fp, fo, fa):
            mismatches += 1
            if mismatches <= 3:
                failures.append(
                    f"common-position divergence at {pp}: primary "
                    f"({po}, {pa}) vs follower ({fo}, {fa})"
                )
    primary_wal.close()

    # ...and the follower's live states equal a from-init replay of
    # its own recovered log (the same determinism clause --crash pins)
    import jax

    _, replay_states = recover_states(dispatch, follower.nr.spec,
                                      follower.nr.log)
    for a, b in zip(jax.tree.leaves(follower.nr.states),
                    jax.tree.leaves(replay_states)):
        if not np.array_equal(np.asarray(a), np.asarray(b)):
            failures.append(
                "promoted follower states are NOT bit-identical to "
                "replaying its log from init"
            )
            break

    # zombie fencing: the dead primary's epoch must be rejected
    try:
        feed.publish(report.new_epoch - 1, follower.applied_pos(),
                     np.zeros(1, np.int32),
                     np.zeros((1, dispatch.arg_width), np.int32))
        failures.append(
            "feed accepted a publish stamped with the dead primary's "
            "epoch (zombie not fenced)"
        )
    except EpochFencedError:
        pass

    # serves on: continue each client's sequence with durable acks
    post_ops = 0
    for c in range(clients):
        for i in range(values[c] + 1, values[c] + 4):
            resp = follower.frontend.call((SR_SET, c, i), rid=0)
            if resp != i - 1:
                failures.append(
                    f"post-promotion client {c} op {i}: expected "
                    f"{i - 1}, got {resp}"
                )
            post_ops += 1
    follower.close()

    append_replication_csv(args.serve_out, replication_rows(
        "bench", report, clients=clients, acked=acked_total,
        kill_after=kill_after, max_lag_pos=max_lag, reads=reads,
        stale_reads=stale_reads, lost=lost, duplicated=duplicated,
        post_restart_ops=post_ops,
    ))
    print(json.dumps({
        "metric": "follower_failover_rto",
        "value": round(report.rto_s, 4),
        "unit": "seconds",
        "clients": clients,
        "acked_before_kill": acked_total,
        "kill_after_acks": kill_after,
        "max_lag_pos": max_lag,
        "follower_reads": reads,
        "stale_reads": stale_reads,
        "applied_pos": report.applied_pos,
        "new_epoch": report.new_epoch,
        "drained_records": report.drained_records,
        "detect_s": round(report.detect_s, 4),
        "promote_s": round(report.promote_s, 4),
        "rto_s": round(report.rto_s, 4),
        "rto_wall_s": round(rto_wall, 4),
        "lost": lost,
        "duplicated": duplicated,
        "common_position": int(common),
        "record_mismatches": mismatches,
        "post_restart_ops": post_ops,
        "bit_identical": not any("bit-identical" in f or
                                 "divergence" in f for f in failures),
    }))
    if not args.follower_dir:
        shutil.rmtree(base, ignore_errors=True)
    if failures:
        for f in failures:
            print(f"# FAIL: {f}", file=sys.stderr)
        return 1
    print(
        f"# follower OK: {reads} staleness-bounded reads "
        f"(max_lag_pos={max_lag}, {stale_reads} typed stale "
        f"rejections) over {acked_total} shipped acks; SIGKILL -> "
        f"promotion in {report.rto_s:.3f}s (detect "
        f"{report.detect_s:.3f}s + promote {report.promote_s:.3f}s), "
        f"lost 0, duplicated 0, bit-identical at position {common}, "
        f"served {post_ops} more ops at epoch {report.new_epoch}",
        file=sys.stderr,
    )
    return 0


def shard_primary_main(args) -> int:
    """`--shard-primary` (internal): ONE shard primary process of the
    `--sharded` fleet. The `--follower-primary` durable-ack pipeline
    (seqreg NR + WAL + `DirectoryFeed` + a `ReplicationShipper`
    installed as the frontend's `ack_barrier`, so every acked op is
    BOTH fsynced and shipped — the property the parent's zero-lost-
    acks gate rides across a promotion) with the submit path exposed
    through a `ShardServer` instead of in-process client threads: the
    parent's router is the only writer, and every sub-batch is
    congruence- and version-checked at the door. The process watches
    the fleet's published `ShardMap` and adopts bumped versions, so a
    promotion elsewhere immediately fences stale peers at HELLO.
    Never exits on its own: the parent SIGKILLs it."""
    import os

    from node_replication_tpu import NodeReplicated
    from node_replication_tpu.durable import WriteAheadLog
    from node_replication_tpu.durable.wal import durable_publish
    from node_replication_tpu.models import SR_GET, SR_SET, make_seqreg
    from node_replication_tpu.repl import DirectoryFeed, ReplicationShipper
    from node_replication_tpu.serve import ServeConfig, ServeFrontend
    from node_replication_tpu.shard import ShardMap, ShardServer

    d = args.shard_dir
    n_shards = args.sharded_shards
    # client slots (clients x shards) plus one reserved probe slot per
    # shard: the warm-up write below and the parent's stale-map fence
    # check land there, keeping the verified client sequences clean
    slots = args.sharded_clients * n_shards + n_shards
    nr = NodeReplicated(
        make_seqreg(slots),
        n_replicas=1,
        log_entries=1 << 15,
        gc_slack=512,
        exec_window=256,
    )
    wal = WriteAheadLog(os.path.join(d, "wal"), policy="batch")
    nr.attach_wal(wal)
    feed = DirectoryFeed(os.path.join(d, "feed"),
                         arg_width=nr.spec.arg_width)
    shipper = ReplicationShipper(wal, feed, poll_s=0.002,
                                 heartbeat_interval_s=0.02)
    fe = ServeFrontend(nr, ServeConfig(
        queue_depth=args.serve_queue_depth,
        batch_max_ops=args.serve_batch,
        batch_linger_s=args.serve_linger,
        durability="batch",
    ))
    fe.ack_barrier = shipper.barrier  # ship-before-ack
    # warm the whole pipeline (combiner JIT + WAL + ship barrier +
    # read plane) on this shard's reserved slot BEFORE opening the
    # server, so the parent's first routed ops don't eat the compile
    probe = args.sharded_clients * n_shards + args.shard_id
    fe.call((SR_SET, probe, 0), rid=0)
    fe.read((SR_GET, probe), rid=0)
    m = ShardMap.load(args.shard_map_dir)
    server = ShardServer(args.shard_id, fe, m, name="bench")
    durable_publish(args.shard_port_file,
                    f"{server.host} {server.port}".encode())
    while True:  # adopt re-published maps until the parent kills us
        time.sleep(0.05)
        try:
            cur = ShardMap.load(args.shard_map_dir)
        except (OSError, ValueError, KeyError):
            continue
        if cur.version > m.version:
            m = cur
            server.set_map(m)


def sharded_main(args) -> int:
    """`--sharded`: the keyspace-sharded fleet gate (ISSUE 18).

    Two legs over real processes (one shard primary per process, the
    parent holding the `ShardRouter` + per-shard `Follower`s):

    - **scaling**: closed-loop clients (one thread per (client, shard)
      keyspace slot, monotone seqreg sequences verified on every
      response) measure 1-shard baseline throughput, then the N-shard
      fleet under the same per-shard load — aggregate acked writes
      must clear `--sharded-scaling-min` x the baseline;
    - **per-shard failover**: SIGKILL one shard's primary mid-load.
      Its promotion (heartbeat silence -> parent-side
      `PromotionManager` -> feed drained, epoch fenced) is measured as
      RTO; the router re-homes the slice onto the promoted follower
      under a bumped, durably re-published `ShardMap`; and hard gates
      verify zero lost / zero duplicated acked writes on the victim
      slice (journal per-slot chain scan + register floor), shard
      isolation of the victim's journal, BOTH zombie fences (the dead
      primary's epoch at the feed, a stale map version at a survivor's
      HELLO), and that the OTHER shards' goodput from the kill through
      the post window holds `--sharded-hold-min` of their pre-kill
      rate — a shard's death must cost its own slice an RTO and
      nobody else anything.
    """
    import os
    import shutil
    import signal
    import subprocess
    import tempfile
    import threading

    import numpy as np

    from node_replication_tpu.harness.mkbench import (
        append_sharded_csv,
        sharded_rows,
    )
    from node_replication_tpu.models import SR_GET, SR_SET, make_seqreg
    from node_replication_tpu.repl import (
        DirectoryFeed,
        EpochFencedError,
        Follower,
        PromotionManager,
    )
    from node_replication_tpu.serve import (
        RetryPolicy,
        ServeConfig,
        ShardUnavailable,
        WrongShard,
        call_with_retry,
    )
    from node_replication_tpu.shard import (
        LocalBackend,
        ShardMap,
        ShardRouter,
        SocketShardClient,
    )

    clients = args.sharded_clients
    n_shards = args.sharded_shards
    window = args.sharded_seconds
    base = args.sharded_dir or tempfile.mkdtemp(prefix="nr-sharded-")
    failures: list[str] = []
    retry = RetryPolicy(max_attempts=128, base_backoff_s=0.001,
                        max_backoff_s=0.1)

    class _PooledBackend:
        """Per-shard connection pool behind the single-backend
        surface the router expects: one `SocketShardClient` per
        concurrent caller. A shard's clients are independent closed
        loops — parallel in-flight submits are the combiner's
        batching feedstock — and one shared connection would
        serialize them into linger-long rounds of one op each. A map
        adoption re-arms idle connections lazily (each replays HELLO
        under the new version on its next checkout), never blocking
        the adopt path on an in-flight request."""

        def __init__(self, shard: int, address):
            self.shard = shard
            self._plock = threading.Lock()
            self._address = address
            self._map = None  # newest adopted ShardMap (None = v1)
            self._version = 1
            self._idle: list = []  # (armed_version, client)
            self._all: list = []

        def submit_batch(self, ops, peer_version, **kw):
            with self._plock:
                if self._idle:
                    ver, c = self._idle.pop()
                    if ver != self._version:
                        c.update_version(self._map)
                else:
                    c = SocketShardClient(
                        self.shard, self._address, self._version,
                        io_timeout_s=60.0,
                    )
                    self._all.append(c)
                got = self._version
            try:
                return c.submit_batch(ops, peer_version, **kw)
            finally:
                with self._plock:
                    self._idle.append((got, c))

        def update_version(self, m) -> None:
            with self._plock:
                self._version = m.version
                self._map = m
                addr = m.addresses[self.shard]
                if addr is not None:
                    self._address = (str(addr[0]), int(addr[1]))

        def close(self) -> None:
            with self._plock:
                for c in self._all:
                    c.close()

    class _Fleet:
        """One leg's fleet: N shard-primary processes behind a router
        (socket backends), a parent-side follower per shard (the
        per-shard replication tree), and closed-loop client threads
        driving disjoint keyspace slots (`slot = client * N + shard`,
        so `slot % N == shard` — the congruence contract)."""

        def __init__(self, tag: str, n: int):
            self.n = n
            self.d = os.path.join(base, tag)
            self.map_d = os.path.join(self.d, "map")
            os.makedirs(self.map_d, exist_ok=True)
            self.map = ShardMap(n)
            self.map.publish(self.map_d)
            self.children: list = []
            self.logs: list = []
            port_files = []
            for s in range(n):
                sd = os.path.join(self.d, f"s{s}")
                os.makedirs(os.path.join(sd, "feed"), exist_ok=True)
                pf = os.path.join(sd, "port")
                port_files.append(pf)
                log = open(os.path.join(sd, "child.log"), "w")
                self.logs.append(log)
                self.children.append(subprocess.Popen(
                    [
                        sys.executable, os.path.abspath(__file__),
                        "--shard-primary",
                        "--shard-id", str(s),
                        "--shard-dir", sd,
                        "--shard-map-dir", self.map_d,
                        "--shard-port-file", pf,
                        "--sharded-shards", str(n),
                        "--sharded-clients", str(clients),
                        "--serve-queue-depth",
                        str(args.serve_queue_depth),
                        "--serve-batch", str(args.serve_batch),
                        "--serve-linger",
                        str(args.sharded_linger),
                    ],
                    stdout=log, stderr=log,
                    env=cpu_child_env(f"--shard-primary {s}"),
                ))
            self.addrs = []
            t_end = time.monotonic() + args.sharded_timeout
            for s, pf in enumerate(port_files):
                while True:
                    if self.children[s].poll() is not None:
                        raise RuntimeError(
                            f"shard {s} exited early (rc "
                            f"{self.children[s].returncode}); see "
                            f"{self.d}/s{s}/child.log"
                        )
                    if time.monotonic() > t_end:
                        raise RuntimeError(
                            f"shard {s} never published its port; "
                            f"see {self.d}/s{s}/child.log"
                        )
                    try:
                        with open(pf) as f:
                            host, port = f.read().split()
                        self.addrs.append((host, int(port)))
                        break
                    except (FileNotFoundError, ValueError):
                        time.sleep(0.05)
            self.dispatch = make_seqreg(clients * n + n)
            # the parent follows shard 0's feed (the fleet leg's
            # victim): one follower per shard is the full deployment,
            # but ONE keeps the single-core CI box honest about the
            # scaling leg — every shard still ships its durable feed,
            # so a follower can attach to any of them at any time
            self.feed = DirectoryFeed(
                os.path.join(self.d, "s0", "feed"),
                arg_width=self.dispatch.arg_width,
            )
            self.follower = Follower(
                self.dispatch, self.feed,
                os.path.join(self.d, "s0", "follower"),
                config=ServeConfig(durability="batch"),
                poll_s=0.002,
                nr_kwargs=dict(n_replicas=1, log_entries=1 << 15,
                               gc_slack=512, exec_window=256),
            )
            self.router = ShardRouter(
                self.map,
                {s: _PooledBackend(s, self.addrs[s])
                 for s in range(n)},
                map_path=self.map_d,
            )
            self.lock = threading.Lock()
            self.stop = threading.Event()
            self.acked_max: dict[int, int] = {}
            self.ack_count = [0] * n  # per shard, monotone
            self.parked: dict[int, tuple[int, bool]] = {}
            self.errors: list[str] = []
            self.threads: list = []
            for c in range(clients):
                for s in range(n):
                    self.start_client(c * n + s, s, 1)

        def start_client(self, slot: int, s: int, start_i: int):
            t = threading.Thread(
                target=self._client, args=(slot, s, start_i),
                name=f"bench-shard-client-{slot}", daemon=True,
            )
            self.threads.append(t)
            t.start()

        def _client(self, slot: int, s: int, i: int) -> None:
            while not self.stop.is_set():
                try:
                    resp = call_with_retry(
                        self.router, (SR_SET, slot, int(i)),
                        policy=retry,
                    )
                except (ShardUnavailable, WrongShard) as e:
                    # the slice is down past the retry budget, or the
                    # op is in doubt (sent, response lost): park — the
                    # parent verifies this slot against the promoted
                    # follower's journaled truth and resumes it
                    doubt = (isinstance(e, ShardUnavailable)
                             and e.maybe_executed)
                    with self.lock:
                        self.parked[slot] = (int(i), doubt)
                    return
                with self.lock:
                    if int(resp) != i - 1:
                        self.errors.append(
                            f"slot {slot} op {i}: expected {i - 1}, "
                            f"got {resp} (ack chain broken)"
                        )
                    self.acked_max[slot] = int(i)
                    self.ack_count[s] += 1
                i += 1

        def counts(self) -> list:
            with self.lock:
                return list(self.ack_count)

        def warmup(self) -> None:
            t_end = time.monotonic() + args.sharded_timeout
            while min(self.counts()) < 25:
                for s, ch in enumerate(self.children):
                    if ch.poll() is not None:
                        raise RuntimeError(
                            f"shard {s} died during warmup (rc "
                            f"{ch.returncode}); see "
                            f"{self.d}/s{s}/child.log"
                        )
                if time.monotonic() > t_end:
                    raise RuntimeError(
                        f"fleet never warmed up: per-shard acks "
                        f"{self.counts()} after "
                        f"{args.sharded_timeout}s"
                    )
                time.sleep(0.05)

        def close(self) -> None:
            self.stop.set()
            for t in self.threads:
                t.join(timeout=10.0)
            with self.lock:
                failures.extend(self.errors)
                self.errors.clear()
            for ch in self.children:
                if ch.poll() is None:
                    os.kill(ch.pid, signal.SIGKILL)
            for ch in self.children:
                ch.wait()
            self.router.close()
            try:
                self.follower.close()
            except Exception:
                pass
            for log in self.logs:
                log.close()

    def rate_window(fleet: "_Fleet", seconds: float) -> list:
        c0 = fleet.counts()
        t0 = time.monotonic()
        time.sleep(seconds)
        c1 = fleet.counts()
        dt = time.monotonic() - t0
        return [(b - a) / dt for a, b in zip(c0, c1)]

    # ---- leg 1: the 1-shard baseline (same per-shard client load) --
    baseline_ops = 0.0
    if args.sharded_scaling_min > 0:
        fl = _Fleet("baseline", 1)
        try:
            fl.warmup()
            baseline_ops = sum(rate_window(fl, window))
        finally:
            fl.close()
        if fl.parked:
            failures.append(
                f"baseline clients parked with no fault injected: "
                f"{sorted(fl.parked)}"
            )
        print(
            f"# baseline: 1 shard x {clients} clients -> "
            f"{baseline_ops:.1f} acked writes/s",
            file=sys.stderr,
        )

    # ---- leg 2: the N-shard fleet, then SIGKILL one slice ----------
    victim = 0
    fl = _Fleet("fleet", n_shards)
    try:
        fl.warmup()
        pre = rate_window(fl, window)
        aggregate_ops = sum(pre)
        manager = PromotionManager(
            fl.feed, [fl.follower],
            heartbeat_timeout_s=args.sharded_heartbeat_timeout,
            check_interval_s=0.03,
        )
        manager.start()
        c_kill = fl.counts()
        victim_acked = c_kill[victim]
        t_kill = time.monotonic()
        os.kill(fl.children[victim].pid, signal.SIGKILL)
        report = manager.wait(timeout=args.sharded_timeout)
        manager.stop()
        if report is None:
            for f in failures:
                print(f"# FAIL: {f}", file=sys.stderr)
            print("# FAIL: promotion did not complete (no report)",
                  file=sys.stderr)
            return 1
        follower = fl.follower
        if not follower.promoted or follower.frontend.read_only:
            failures.append(
                "follower not serving writes after promotion"
            )
        # re-home: bump + durably re-publish FIRST (fences every stale
        # peer fleet-wide), then repoint the router onto the promoted
        # follower in-process — the same order ShardGroup.promote pins
        new_map = fl.router.map.with_address(victim, None)
        new_map.publish(fl.map_d)
        fl.router.repoint(
            victim,
            LocalBackend(victim, follower.frontend, new_map),
            new_map=new_map,
        )
        # resume parked victim slots from the journaled truth: the
        # register must hold exactly the acked floor, or (for an
        # in-doubt op) the pending value whose response was lost
        time.sleep(0.2)
        lost = 0
        with fl.lock:
            parked = dict(fl.parked)
            fl.parked.clear()
        for slot in sorted(parked):
            pending, doubt = parked[slot]
            s = slot % n_shards
            if s != victim:
                failures.append(
                    f"slot {slot} (shard {s}) parked during shard "
                    f"{victim}'s outage — a survivor slice observed "
                    f"the failure"
                )
                continue
            v = int(follower.frontend.read((SR_GET, slot), rid=0))
            acked = fl.acked_max.get(slot, 0)
            if v < acked:
                lost += acked - v
                failures.append(
                    f"slot {slot}: acked up to {acked} but the "
                    f"promoted follower holds {v} (LOST ACKED WRITES)"
                )
            elif v != acked and not (doubt and v == pending):
                failures.append(
                    f"slot {slot}: journal holds {v} vs acked {acked}"
                    f" / pending {pending} (INVENTED WRITE)"
                )
            with fl.lock:
                fl.acked_max[slot] = max(acked, v)
            fl.start_client(slot, s, v + 1)
        # post window: measured from the KILL, so the victim's outage
        # and the re-home are inside it — survivors must not notice
        time.sleep(window)
        c_end = fl.counts()
        t_end_m = time.monotonic()
        post = [(b - a) / (t_end_m - t_kill)
                for a, b in zip(c_kill, c_end)]
        surv_pre = sum(r for s, r in enumerate(pre) if s != victim)
        surv_post = sum(r for s, r in enumerate(post) if s != victim)
        survivor_hold = (surv_post / surv_pre) if surv_pre > 0 else 0.0
        if c_end[victim] <= c_kill[victim]:
            failures.append(
                f"victim shard {victim} served nothing after the "
                f"re-home ({c_kill[victim]} -> {c_end[victim]} acks)"
            )
        fl.stop.set()
        for t in fl.threads:
            t.join(timeout=10.0)
        with fl.lock:
            failures.extend(fl.errors)
            fl.errors.clear()
            if fl.parked:
                failures.append(
                    f"clients parked after the re-home: "
                    f"{sorted(fl.parked)}"
                )
            acked_snapshot = dict(fl.acked_max)

        # no lost ack: every verified ack is in the promoted registers
        for slot in sorted(acked_snapshot):
            if slot % n_shards != victim:
                continue
            v = int(follower.frontend.read((SR_GET, slot), rid=0))
            if v < acked_snapshot[slot]:
                lost += acked_snapshot[slot] - v
                failures.append(
                    f"slot {slot}: acked up to "
                    f"{acked_snapshot[slot]} but the promoted "
                    f"follower holds {v} (LOST ACKED WRITES)"
                )

        # no duplicate + shard isolation: the promoted follower's
        # journal holds ONLY the victim's congruence class, and each
        # client slot's history chains 1..k with no repeat
        duplicated = 0
        seen_next: dict[int, int] = {}
        for rec in follower.nr.wal.records(0):
            for _opc, row in zip(rec.opcodes, rec.args):
                slot = int(row[0])
                if slot % n_shards != victim:
                    failures.append(
                        f"shard-isolation violation: slot {slot} "
                        f"(shard {slot % n_shards}) journaled in "
                        f"shard {victim}'s slice"
                    )
                    continue
                if slot >= clients * n_shards:
                    continue  # reserved warm-up/probe slot
                v = int(row[1])
                nxt = seen_next.get(slot, 1)
                if v < nxt:
                    duplicated += 1
                    failures.append(
                        f"slot {slot}: value {v} journaled again "
                        f"after reaching {nxt - 1} (DUPLICATED)"
                    )
                elif v > nxt:
                    failures.append(
                        f"slot {slot}: journal skips from {nxt - 1} "
                        f"to {v} (hole in history)"
                    )
                    seen_next[slot] = v + 1
                else:
                    seen_next[slot] = v + 1

        # zombie fence, log plane: the dead primary's epoch can no
        # longer publish into its shard's feed
        try:
            fl.feed.publish(
                report.new_epoch - 1, follower.applied_pos(),
                np.zeros(1, np.int32),
                np.zeros((1, fl.dispatch.arg_width), np.int32),
            )
            failures.append(
                "feed accepted a publish stamped with the dead "
                "primary's epoch (zombie not fenced)"
            )
        except EpochFencedError:
            pass

        # zombie fence, routing tier: once a survivor adopts the
        # re-published map, a peer still carrying the old version is
        # refused at HELLO (typed WrongShard, zero log effect)
        surv = (victim + 1) % n_shards
        probe_slot = clients * n_shards + surv
        fence_ok = False
        probe_i = 0
        t_f = time.monotonic() + 10.0
        while time.monotonic() < t_f:
            stale = SocketShardClient(surv, fl.addrs[surv], 1)
            try:
                probe_i += 1
                stale.submit_batch([(SR_SET, probe_slot, probe_i)], 1)
                time.sleep(0.1)  # survivor has not adopted v2 yet
            except WrongShard:
                fence_ok = True
                break
            except ShardUnavailable as e:
                failures.append(
                    f"survivor shard {surv} unreachable during the "
                    f"stale-map fence check: {e}"
                )
                break
            finally:
                stale.close()
        if not fence_ok and not any("unreachable" in f
                                    for f in failures):
            failures.append(
                f"survivor shard {surv} still accepts map-version-1 "
                f"submits after the promotion published version "
                f"{new_map.version} (stale router not fenced)"
            )

        # serves on THROUGH THE ROUTER: each victim slot continues its
        # sequence over the re-homed path with verified responses
        post_ops = 0
        for c in range(clients):
            slot = c * n_shards + victim
            v = int(follower.frontend.read((SR_GET, slot), rid=0))
            for i in range(v + 1, v + 4):
                resp = call_with_retry(fl.router, (SR_SET, slot, i),
                                       policy=retry)
                if int(resp) != i - 1:
                    failures.append(
                        f"post-promotion slot {slot} op {i}: "
                        f"expected {i - 1}, got {resp}"
                    )
                post_ops += 1
        acked_total = sum(fl.counts()) + post_ops
    finally:
        fl.close()

    scaling_x = (aggregate_ops / baseline_ops) if baseline_ops else 0.0
    if baseline_ops and scaling_x < args.sharded_scaling_min:
        failures.append(
            f"{n_shards} shards scaled only {scaling_x:.2f}x over the "
            f"1-shard baseline ({aggregate_ops:.1f} vs "
            f"{baseline_ops:.1f} acked writes/s; gate "
            f"{args.sharded_scaling_min}x)"
        )
    if survivor_hold < args.sharded_hold_min:
        failures.append(
            f"survivor goodput held only {survivor_hold:.2f} of the "
            f"pre-kill window through shard {victim}'s outage (gate "
            f"{args.sharded_hold_min})"
        )

    run = {
        "n_shards": n_shards,
        "clients": clients * n_shards,
        "duration": window,
        "baseline_ops": baseline_ops,
        "aggregate_ops": aggregate_ops,
        "scaling_x": scaling_x,
        "acked": acked_total,
        "victim_shard": victim,
        "victim_acked": victim_acked,
        "detect_s": report.detect_s,
        "promote_s": report.promote_s,
        "rto_s": report.rto_s,
        "survivor_hold": survivor_hold,
        "lost": lost,
        "duplicated": duplicated,
        "post_promote_ops": post_ops,
    }
    append_sharded_csv(args.serve_out, sharded_rows("bench", run))
    print(json.dumps({
        "metric": "sharded_scaling_x",
        "value": round(scaling_x, 3),
        "unit": "x",
        "n_shards": n_shards,
        "clients_per_shard": clients,
        "baseline_ops": round(baseline_ops, 1),
        "aggregate_ops": round(aggregate_ops, 1),
        "acked": acked_total,
        "victim_shard": victim,
        "victim_acked_before_kill": victim_acked,
        "detect_s": round(report.detect_s, 4),
        "promote_s": round(report.promote_s, 4),
        "rto_s": round(report.rto_s, 4),
        "new_epoch": report.new_epoch,
        "map_version": new_map.version,
        "survivor_hold": round(survivor_hold, 3),
        "lost": lost,
        "duplicated": duplicated,
        "post_promote_ops": post_ops,
    }))
    if not args.sharded_dir:
        shutil.rmtree(base, ignore_errors=True)
    if failures:
        for f in failures:
            print(f"# FAIL: {f}", file=sys.stderr)
        return 1
    print(
        f"# sharded OK: {n_shards} shards x {clients} clients -> "
        f"{aggregate_ops:.1f} acked writes/s"
        + (f" ({scaling_x:.2f}x the 1-shard baseline)"
           if baseline_ops else "")
        + f"; SIGKILL shard {victim} -> promotion in "
          f"{report.rto_s:.3f}s (detect {report.detect_s:.3f}s + "
          f"promote {report.promote_s:.3f}s), survivors held "
          f"{survivor_hold:.2f}, lost 0, duplicated 0, both zombie "
          f"fences proven, map v{new_map.version}, served "
          f"{post_ops} more ops through the re-homed router",
        file=sys.stderr,
    )
    return 0


# ==========================================================================
# --txn / --reshard: the cross-shard atomicity + online-split gates
# ==========================================================================

#: txn bench values live above this floor so the WAL exactly-once scan
#: can tell transactional writes from preload/background traffic
_TXN_VAL_BASE = 1_000_000

_TXN_NR_KW = dict(n_replicas=1, log_entries=1 << 12, gc_slack=64,
                  exec_window=128)


def _txn_group(base: str, keys: int, recover: bool = False,
               with_txn: bool = True, with_followers: bool = False):
    from node_replication_tpu.shard.primary import ShardGroup
    return ShardGroup(
        2, make_hashmap(keys), base,
        nr_kwargs=_TXN_NR_KW,
        with_followers=with_followers,
        with_txn=with_txn,
        recover=recover,
        concurrent_router=False,
    )


def txn_child_main(args) -> int:
    """`--txn-child` (internal): the crash victim of ONE `--txn` kill
    round. Builds a 2-shard `ShardGroup` + `TxnCoordinator` in
    `--txn-dir`, arms a REAL SIGKILL (`FaultSpec(action="kill")`) at
    the requested txn fault site, then drives cross-shard
    transactions flat-out, fsyncing each ACKED txn's ops to
    `acked.jsonl` — the parent's ground truth for the
    zero-half-committed read-back. The expected exit is the SIGKILL
    itself; exit 3 means the armed kill never fired (a parent-side
    round failure), exit 0 is the unkilled calibration run."""
    import os

    from node_replication_tpu.fault.inject import FaultPlan, FaultSpec

    g = _txn_group(args.txn_dir, args.txn_keys)
    coord = g.coordinator(name="bench")
    if args.txn_kill_site != "none":
        FaultPlan([FaultSpec(site=args.txn_kill_site, action="kill",
                             rid=-1, after=args.txn_kill_after)],
                  seed=args.seed).arm()
    acked = open(os.path.join(args.txn_dir, "acked.jsonl"), "a")
    k = 0
    for i in range(args.txn_count):
        # k and k+1 differ mod 2 -> every txn spans both shards; keys
        # strictly increase so each is written exactly once ever and
        # an aborted txn's keys must read back absent (-1)
        ops = [(HM_PUT, k, _TXN_VAL_BASE + k),
               (HM_PUT, k + 1, _TXN_VAL_BASE + k + 1)]
        if i % 3 == 0:
            ops.append((HM_PUT, k + 2, _TXN_VAL_BASE + k + 2))
        k += len(ops)
        coord.execute_txn([tuple(op) for op in ops])
        acked.write(json.dumps({"ops": [list(o) for o in ops]}) + "\n")
        acked.flush()
        os.fsync(acked.fileno())
    acked.close()
    g.close()
    return 3 if args.txn_kill_site != "none" else 0


def txn_main(args) -> int:
    """`--txn`: the crash-proof cross-shard transaction gate (ISSUE
    20). Two legs:

    - **SIGKILL matrix**: `--txn-rounds` child processes each drive
      cross-shard 2PC transactions and die by a REAL `SIGKILL`
      injected at a seeded point inside one of the three crash
      windows — `txn-prepare` (coordinator mid-prepare: some
      participants voted yes, no decision), `txn-commit` (participant
      mid-commit: ops applied, resolved record missing), `txn-decide`
      (decision durable, phase 2 not started). The parent then
      restarts the fleet in place (`recover=True`), bumps the
      coordinator epoch, re-drives published commit decisions, runs
      every participant's in-doubt resolution, and hard-gates: every
      acked txn fully visible by per-key read-back, every in-doubt
      intent resolved to its durable decision (absence => presumed
      abort, zero visible effect), ZERO half-committed multi-key ops,
      and a WAL scan proving no txn write was applied twice.
    - **parity**: non-txn single-shard throughput on a `with_txn`
      fleet vs a txn-free build, alternating slices — 2PC must cost
      nothing unused (`--txn-parity-min`, default 0.9).
    """
    import os
    import random
    import shutil
    import signal
    import subprocess
    import tempfile
    import time

    from node_replication_tpu.harness.mkbench import (
        append_sharded_csv,
        txn_rows,
    )

    t_start = time.monotonic()
    base = args.txn_dir or tempfile.mkdtemp(prefix="nr-txn-")
    os.makedirs(base, exist_ok=True)
    failures: list[str] = []
    rng = random.Random(args.seed)
    T = args.txn_count
    sites = ["txn-prepare", "txn-commit", "txn-decide"]
    # site-wide fault hits per driven txn: prepare fires at the
    # participant AND after each coordinator leg (2 shards -> 4),
    # commit once per participant, decide once per txn
    per_txn = {"txn-prepare": 4, "txn-commit": 2, "txn-decide": 1}
    acked_total = in_doubt_total = resolved_total = 0
    half_committed = duplicated = 0
    kills = 0

    for r in range(args.txn_rounds):
        site = sites[r % len(sites)]
        rdir = os.path.join(base, f"round{r}")
        shutil.rmtree(rdir, ignore_errors=True)
        os.makedirs(rdir)
        after = rng.randrange(per_txn[site] * (T // 4),
                              per_txn[site] * (3 * T // 4))
        cmd = [
            sys.executable, os.path.abspath(__file__),
            "--txn-child", "--txn-dir", rdir,
            "--txn-kill-site", site,
            "--txn-kill-after", str(after),
            "--txn-count", str(T),
            "--txn-keys", str(args.txn_keys),
            "--seed", str(args.seed + r),
        ]
        try:
            proc = subprocess.run(cmd, env=cpu_child_env("--txn-child"),
                                  timeout=args.txn_timeout,
                                  stdout=subprocess.DEVNULL)
        except subprocess.TimeoutExpired:
            failures.append(f"round {r}: child hung past "
                            f"{args.txn_timeout}s ({site})")
            continue
        if proc.returncode != -signal.SIGKILL:
            failures.append(
                f"round {r}: child exited {proc.returncode}, expected "
                f"death by SIGKILL at {site} hit {after}"
            )
            continue
        kills += 1

        # restart-in-place over the dead fleet's artifacts
        g = _txn_group(rdir, args.txn_keys, recover=True)
        try:
            pre: dict[str, dict[int, list]] = {}
            for p in g.primaries:
                for txn, info in p.txn.log.unresolved().items():
                    pre.setdefault(txn, {})[p.txn.shard] = [
                        tuple(op) for op in info["ops"]
                    ]
            in_doubt = sum(len(v) for v in pre.values())
            in_doubt_total += in_doubt
            # a NEW coordinator generation (durable epoch bump) makes
            # the dead one's undecided intents presumed-abortable,
            # then published commits are re-driven and every
            # participant resolves against the decision log
            coord2 = g.coordinator(name="recover")
            coord2.recover()
            g.resolve_in_doubt()
            remaining = 0
            for p in g.primaries:
                left = p.txn.log.unresolved()
                remaining += len(left)
                if left:
                    failures.append(
                        f"round {r}: shard {p.txn.shard} still in "
                        f"doubt after recovery: {sorted(left)}"
                    )
                if p.txn.has_locks():
                    failures.append(
                        f"round {r}: shard {p.txn.shard} holds txn "
                        f"locks after recovery"
                    )
            resolved_total += in_doubt - remaining

            def _read(k: int) -> int:
                s = g.map.shard_of(k)
                return int(g.primaries[s].live_frontend.read(
                    (HM_GET, k)))

            # gate: every ACKED txn is fully visible after restart
            acked_path = os.path.join(rdir, "acked.jsonl")
            n_acked = 0
            if os.path.exists(acked_path):
                with open(acked_path) as fh:
                    for line in fh:
                        line = line.strip()
                        if not line:
                            continue
                        ops = json.loads(line)["ops"]
                        n_acked += 1
                        gone = [(k, v) for _c, k, v in ops
                                if _read(k) != v]
                        if gone:
                            half_committed += 1
                            failures.append(
                                f"round {r}: acked txn lost writes "
                                f"{gone}"
                            )
            acked_total += n_acked
            if n_acked == 0:
                failures.append(
                    f"round {r}: zero txns acked before the kill "
                    f"(site {site} hit {after} fired too early to "
                    f"exercise the matrix)"
                )

            # gate: every in-doubt txn is all-or-nothing per its
            # durable decision (absence == presumed abort)
            for txn, per_shard in sorted(pre.items()):
                outcome = g.decisions.outcome(txn) or "abort"
                flat = [op for s in sorted(per_shard)
                        for op in per_shard[s]]
                vis = sum(1 for _c, k, v in flat if _read(k) == v)
                if outcome == "commit" and vis != len(flat):
                    half_committed += 1
                    failures.append(
                        f"round {r}: committed txn {txn} applied "
                        f"{vis}/{len(flat)} journaled ops"
                    )
                elif outcome == "abort" and vis:
                    half_committed += 1
                    failures.append(
                        f"round {r}: aborted txn {txn} left "
                        f"{vis}/{len(flat)} ops visible"
                    )

            # gate: exactly-once — no txn write appended twice across
            # the crash + re-driven commit (the commit-begin dedup)
            for p in g.primaries:
                seen: set[tuple[int, int]] = set()
                for rec in p.wal.records(p.wal.base):
                    for op in rec.ops():
                        if (int(op[0]) != HM_PUT
                                or int(op[2]) < _TXN_VAL_BASE):
                            continue
                        pair = (int(op[1]), int(op[2]))
                        if pair in seen:
                            duplicated += 1
                            failures.append(
                                f"round {r}: shard {p.txn.shard} "
                                f"applied {pair} twice"
                            )
                        seen.add(pair)
        finally:
            g.close()

    # ------------------------------------------------------ parity leg
    groups = {}
    ops_done = {True: 0, False: 0}
    for cfg in (True, False):
        d = os.path.join(base, f"parity-{int(cfg)}")
        shutil.rmtree(d, ignore_errors=True)
        groups[cfg] = _txn_group(d, args.txn_keys, with_txn=cfg)
    try:
        slice_s = args.txn_parity_seconds / 6.0
        for _ in range(3):
            # alternate short slices so machine drift hits both
            # configurations evenly
            for cfg in (True, False):
                g = groups[cfg]
                n = ops_done[cfg]
                end = time.monotonic() + slice_s
                while time.monotonic() < end:
                    # even keys: single-shard, never the txn path
                    g.router.call((HM_PUT, (n % 64) * 2, n))
                    n += 1
                ops_done[cfg] = n
    finally:
        for g in groups.values():
            g.close()
    parity = (ops_done[True] / ops_done[False]
              if ops_done[False] else 0.0)
    if parity < args.txn_parity_min:
        failures.append(
            f"with_txn fleet served {ops_done[True]} non-txn ops vs "
            f"{ops_done[False]} txn-free ({parity:.3f}x, gate "
            f"{args.txn_parity_min})"
        )

    run = {
        "n_shards": 2,
        "clients": 1,
        "duration": time.monotonic() - t_start,
        "acked": acked_total,
        "lost": half_committed,
        "duplicated": duplicated,
        "txn_rounds": args.txn_rounds,
        "txn_acked": acked_total,
        "txn_in_doubt": in_doubt_total,
        "txn_resolved": resolved_total,
        "txn_half_committed": half_committed,
        "txn_parity": parity,
    }
    append_sharded_csv(args.serve_out, txn_rows("bench", run))
    print(json.dumps({
        "metric": "txn_half_committed",
        "value": half_committed,
        "unit": "txns",
        "rounds": args.txn_rounds,
        "kills": kills,
        "txns_per_round": T,
        "acked": acked_total,
        "in_doubt": in_doubt_total,
        "resolved": resolved_total,
        "duplicated": duplicated,
        "parity": round(parity, 3),
    }))
    if not args.txn_dir:
        shutil.rmtree(base, ignore_errors=True)
    if failures:
        for f in failures:
            print(f"# FAIL: {f}", file=sys.stderr)
        return 1
    print(
        f"# txn OK: {kills}/{args.txn_rounds} SIGKILL rounds across "
        f"prepare/commit/decide windows; {acked_total} acked txns "
        f"intact, {in_doubt_total} in-doubt intents resolved "
        f"({resolved_total} resolutions), 0 half-committed, 0 "
        f"double-applied; non-txn parity {parity:.3f}x "
        f"(gate {args.txn_parity_min})",
        file=sys.stderr,
    )
    return 0


def reshard_main(args) -> int:
    """`--reshard`: the online keyspace-split gate (ISSUE 20). A
    2-shard `ShardGroup` serves closed-loop per-key writers (one
    thread per congruence class mod 4, monotone values per key) while
    `ReshardPlan(donor=0).split()` refines the map 2 -> 4 live,
    re-homing class 2 onto the donor's promoted follower. Hard gates:

    - ZERO acked writes lost across the cutover (final read-back per
      key >= the last acked value) and nothing dropped in the move
      (every moved-key write in the donor WAL is in the recipient's);
    - ZERO duplicated applies (each moved key's recipient-WAL value
      sequence is strictly increasing — single writer, monotone);
    - the moved keys' measured unavailability (worst per-key ack gap
      ridden out by `call_with_retry`) stays under
      `--reshard-unavail-max`: the window is the FENCE, never
      state-sized;
    - the quiesced `merge()` folds class 2 back with the same final
      values at the survivor.
    """
    import os
    import shutil
    import tempfile
    import threading
    import time

    from node_replication_tpu.harness.mkbench import (
        append_sharded_csv,
        reshard_rows,
    )
    from node_replication_tpu.serve import RetryPolicy, call_with_retry
    from node_replication_tpu.shard.reshard import ReshardPlan

    t_start = time.monotonic()
    base = args.reshard_dir or tempfile.mkdtemp(prefix="nr-reshard-")
    failures: list[str] = []
    keys = args.txn_keys
    g = _txn_group(base, keys, with_followers=True)
    merged_ok = False
    try:
        retry = RetryPolicy(max_attempts=512, base_backoff_s=0.001,
                            max_backoff_s=0.05)
        stop = threading.Event()
        n_writers = max(4, args.reshard_clients)
        # background state OUTSIDE the writer key range, so the WAL
        # sequence scans below see writer values only
        for k in range(n_writers, n_writers + 16):
            g.router.call((HM_PUT, k, 10_000 + k))
        acked = [0] * n_writers       # last acked value, key = index
        acks_t = [[] for _ in range(n_writers)]
        errs: list = []

        def writer(k: int) -> None:
            v = 0
            while not stop.is_set():
                v += 1
                try:
                    call_with_retry(g.router, (HM_PUT, k, v),
                                    policy=retry, deadline_s=30.0)
                except Exception as e:
                    errs.append((k, v, e))
                    return
                acked[k] = v
                acks_t[k].append(time.monotonic())
                time.sleep(0.001)

        threads = [
            threading.Thread(target=writer, args=(k,),
                             name=f"reshard-w{k}")
            for k in range(n_writers)
        ]
        for th in threads:
            th.start()
        time.sleep(args.reshard_warmup)
        plan = ReshardPlan(g, donor=0)
        t_split = time.monotonic()
        rep = plan.split(catchup_timeout_s=args.sharded_timeout,
                         drain_timeout_s=args.sharded_timeout)
        time.sleep(args.reshard_window)
        stop.set()
        for th in threads:
            th.join(timeout=15)
        t_end = time.monotonic()
        if errs:
            failures.append(f"writer errors across the split: "
                            f"{errs[:3]}")

        moved = [k for k in range(n_writers) if k % 4 == 2]
        recipient = plan._recipient

        def _read(k: int) -> int:
            s = g.map.shard_of(k)
            if s == rep.moved:
                return int(recipient.frontend.read((HM_GET, k)))
            return int(g.primaries[s % 2].live_frontend.read(
                (HM_GET, k)))

        # zero lost acks: values are monotone per key, so a final
        # state below the last ack means an acked write vanished
        lost = 0
        finals = {}
        for k in range(n_writers):
            got = _read(k)
            finals[k] = got
            if got < acked[k]:
                lost += 1
                failures.append(
                    f"key {k}: last acked value {acked[k]} but "
                    f"read-back {got} after the split"
                )

        # the move dropped nothing and applied nothing twice: every
        # moved-key write the donor WAL holds is in the recipient's,
        # and each moved key's recipient sequence strictly increases
        def _wal_seq(wal, want_moved: bool):
            seqs: dict[int, list[int]] = {}
            for rec in wal.records(wal.base):
                for op in rec.ops():
                    k = int(op[1])
                    if int(op[0]) != HM_PUT or k >= n_writers:
                        continue
                    if (k % 4 == 2) == want_moved:
                        seqs.setdefault(k, []).append(int(op[2]))
            return seqs

        donor_seq = _wal_seq(g.primaries[0].wal, True)
        recip_seq = _wal_seq(recipient.nr.wal, True)
        dup = 0
        for k in moved:
            rs = recip_seq.get(k, [])
            for a, b in zip(rs, rs[1:]):
                if b <= a:
                    dup += 1
                    failures.append(
                        f"moved key {k}: recipient applied value {b} "
                        f"after {a} (duplicate/reorder)"
                    )
            missing = set(donor_seq.get(k, [])) - set(rs)
            if missing:
                lost += len(missing)
                failures.append(
                    f"moved key {k}: donor-WAL writes {sorted(missing)[:4]} "
                    f"never reached the recipient"
                )

        # bounded per-moved-key unavailability: the worst ack gap a
        # moved key saw, anchored at its last pre-fence ack — a key
        # that NEVER recovered scores the whole remaining run
        unavail = 0.0
        for k in moved:
            prev = [t for t in acks_t[k] if t <= t_split]
            post = [t for t in acks_t[k] if t > t_split]
            anchor = prev[-1] if prev else t_split
            if post:
                gaps = [post[0] - anchor]
                gaps += [b - a for a, b in zip(post, post[1:])]
                unavail = max(unavail, max(gaps))
            else:
                unavail = max(unavail, t_end - anchor)
        if unavail > args.reshard_unavail_max:
            failures.append(
                f"moved-key unavailability {unavail:.3f}s exceeds "
                f"--reshard-unavail-max {args.reshard_unavail_max}s"
            )
        moved_writes = sum(len(v) for v in recip_seq.values())

        # quiesced merge folds the class back bit-for-bit
        rep2 = plan.merge(apply_timeout_s=args.sharded_timeout)
        for k in range(n_writers):
            s = g.map.shard_of(k)
            got = int(g.primaries[s].live_frontend.read((HM_GET, k)))
            if got != finals[k]:
                failures.append(
                    f"merge moved key {k} from {finals[k]} to {got}"
                )
                break
        else:
            merged_ok = True
    finally:
        g.close()

    acked_count = sum(len(t) for t in acks_t)
    run = {
        "n_shards": 2,
        "clients": n_writers,
        "duration": time.monotonic() - t_start,
        "acked": acked_count,
        "lost": lost,
        "duplicated": dup,
        "moved_keys": len(moved),
        "reshard_lost": lost,
        "reshard_dup": dup,
        "fence_s": rep.fence_s,
        "moved_unavail_s": unavail,
    }
    append_sharded_csv(args.serve_out, reshard_rows("bench", run))
    print(json.dumps({
        "metric": "reshard_unavail_s",
        "value": round(unavail, 4),
        "unit": "s",
        "fence_s": round(rep.fence_s, 4),
        "catchup_s": round(rep.catchup_s, 4),
        "drained_records": rep.drained_records,
        "moved_keys": len(moved),
        "moved_writes": moved_writes,
        "acked": acked_count,
        "lost": lost,
        "duplicated": dup,
        "map_versions": [rep.old_version, rep.new_version,
                         rep2.new_version],
        "merge_replayed": rep2.drained_records,
        "merged_ok": merged_ok,
    }))
    if not args.reshard_dir:
        shutil.rmtree(base, ignore_errors=True)
    if failures:
        for f in failures:
            print(f"# FAIL: {f}", file=sys.stderr)
        return 1
    print(
        f"# reshard OK: live 2->4 split re-homed class 2 "
        f"({moved_writes} writes) under {acked_count} concurrent "
        f"acks; lost 0, duplicated 0, fence {rep.fence_s:.3f}s, "
        f"worst moved-key gap {unavail:.3f}s (gate "
        f"{args.reshard_unavail_max}s); merge folded "
        f"{rep2.drained_records} records back exactly",
        file=sys.stderr,
    )
    return 0


def tree_follower_main(args) -> int:
    """`--tree-follower` (internal): one LEAF follower process of the
    `--tree` harness. Connects to its assigned relay over TCP, catches
    up to `--tree-target` (bootstrapping from a shipped snapshot when
    the tree holds one and `--tree-bootstrap` allows), signals
    readiness, waits for the parent's go-file barrier, then serves
    local reads flat-out for `--tree-read-seconds` and writes a JSON
    result file. A separate PROCESS per follower, so the aggregate
    read-throughput claim is measured GIL-free — the way a real
    deployment's followers scale."""
    import os

    from node_replication_tpu.durable.wal import durable_publish
    from node_replication_tpu.models import SR_GET, make_seqreg
    from node_replication_tpu.repl import Follower, SocketFeed

    clients = args.serve_clients
    host, port = args.tree_connect.split(":")
    dispatch = make_seqreg(clients)
    feed = SocketFeed(host, int(port), arg_width=dispatch.arg_width)
    f = Follower(
        dispatch, feed, args.crash_dir,
        nr_kwargs=dict(n_replicas=1, log_entries=1 << 15,
                       gc_slack=512, exec_window=256),
        poll_s=0.002, bootstrap=bool(args.tree_bootstrap),
        name=os.path.basename(args.crash_dir),
        # --tree-obs mode: exporter on a side port for the collector
        obs_port=0 if args.obs_port_file else None,
    )
    if args.obs_port_file:
        exp = f.frontend.exporter
        durable_publish(
            args.obs_port_file,
            f"{exp.address[0]} {exp.address[1]}".encode(),
        )
    caught_up = f.wait_applied(args.tree_target,
                               timeout=args.tree_timeout)
    durable_publish(args.tree_ready_file, b"ready")
    t_wait = time.monotonic() + args.tree_timeout
    while not os.path.exists(args.tree_go_file):
        if time.monotonic() > t_wait:
            break
        time.sleep(0.005)
    reads = 0
    t0 = time.monotonic()
    t_end = t0 + args.tree_read_seconds
    while time.monotonic() < t_end:
        f.frontend.read((SR_GET, reads % clients), rid=0)
        reads += 1
    elapsed = time.monotonic() - t0
    durable_publish(args.tree_result_file, json.dumps({
        "reads": reads,
        "seconds": elapsed,
        "caught_up": bool(caught_up),
        "applied": f.applied_pos(),
        "bootstrap_pos": (
            f.bootstrap_report[0] if f.bootstrap_report else 0
        ),
    }).encode())
    f.close()
    return 0


def tree_main(args) -> int:
    """`--tree`: the multi-host replication-tree gate (ISSUE 12).

    Forks a primary whose acks are fsynced + shipped + CONFIRMED
    RECEIVED by every relay (`make_tree_barrier`), builds a
    primary → `--tree-relays` relays → `--tree-followers` leaf
    topology over localhost TCP, and verifies, with hard exits:

    - **read scale-out**: aggregate leaf read throughput (each leaf
      its own process — GIL-free) must exceed a single leaf's by
      `--tree-scaling-min`, while the primary's ack rate holds within
      `--tree-primary-hold` of its single-leaf rate;
    - **snapshot bootstrap**: a cold follower bootstrapping from the
      shipped `snap-<pos>.npz` must catch up strictly faster than an
      identical follower replaying the full history;
    - **mid-tree failover**: SIGKILL of the primary is detected
      through the relay's forwarded heartbeat, the candidate follower
      promotes (fence forwarded over the socket into the relay's
      journal), and every acked `(client, i)` is present exactly once
      — zero lost, zero duplicated — with the measured RTO reported;
    - **zombie fencing over the wire**: a record stamped with the
      dead primary's epoch, injected into the primary's feed, is
      dropped by the fenced relay and never reaches the subtree.
    """
    import os
    import shutil
    import signal
    import subprocess
    import tempfile

    import numpy as np

    from node_replication_tpu.harness.mkbench import (
        append_tree_csv,
        tree_rows,
    )
    from node_replication_tpu.models import SR_GET, SR_SET, make_seqreg
    from node_replication_tpu.repl import (
        DirectoryFeed,
        Follower,
        PromotionManager,
        RelayNode,
        SocketFeed,
    )

    clients = args.serve_clients
    n_relays = max(1, args.tree_relays)
    n_leaves = max(1, args.tree_followers)
    kill_after = args.tree_kill_after_acks
    if kill_after <= 0:
        import random as _random

        kill_after = _random.Random(args.seed).randrange(400, 700)
    snap_after = args.crash_snapshot_after
    if snap_after < 0:
        snap_after = kill_after // 3
    base = args.tree_dir or tempfile.mkdtemp(prefix="nr-tree-")
    primary_d = os.path.join(base, "primary")
    feed_d = os.path.join(base, "feed")
    os.makedirs(primary_d, exist_ok=True)
    os.makedirs(feed_d, exist_ok=True)
    acks_path = os.path.join(primary_d, "acks.log")
    port_file = os.path.join(base, "primary.port")
    failures: list[str] = []
    dispatch = make_seqreg(clients)
    aw = dispatch.arg_width

    # ---- fleet observability (--tree-obs): exporters in EVERY tree
    # process, a FleetCollector merging their scrapes + trace tails
    # into tree_fleet.jsonl, and a hard gate below on a reconstructed
    # cross-process per-record hop timeline (obs/export, obs/collect,
    # obs/report Fleet section)
    obs = bool(args.tree_obs)
    collector = None
    child_env = None
    fleet_path = None
    primary_obs_file = os.path.join(base, "primary.obs")
    if obs:
        from node_replication_tpu.obs import (
            get_registry,
            get_tracer,
            set_trace_sample,
        )
        from node_replication_tpu.obs.collect import FleetCollector

        # this process hosts the relays: same posture as the children.
        # The tracer must be BUFFERED (ring) — a pre-existing
        # file-mode NR_TPU_TRACE would export zero events from the
        # relay exporters and fail the gate below for the wrong
        # reason, so --tree-obs owns the parent tracer outright.
        get_registry().enable()
        t = get_tracer()
        if not t.enabled or not t.buffered:
            if t.enabled:
                print(
                    "# --tree-obs: re-routing the parent tracer from "
                    "file mode to ring mode (exporters serve the "
                    "in-memory tail; the file would export nothing)",
                    file=sys.stderr,
                )
            t.enable(None, ring=1 << 14)
        set_trace_sample(args.tree_obs_sample)
        child_env = {
            **os.environ,
            "NR_TPU_METRICS": "1",
            "NR_TPU_TRACE": "mem",
            "NR_TPU_TRACE_RING": str(1 << 14),
            "NR_TPU_TRACE_SAMPLE": f"1/{args.tree_obs_sample}",
        }
        os.makedirs(args.serve_out, exist_ok=True)
        fleet_path = os.path.join(args.serve_out, "tree_fleet.jsonl")
        for stale in (fleet_path, primary_obs_file):
            # a reused --tree-dir must not hand the collector last
            # run's (dead) exporter port or append to its merge
            try:
                os.remove(stale)
            except FileNotFoundError:
                pass

    child_log = open(os.path.join(base, "child.log"), "w")
    child = subprocess.Popen(
        [
            sys.executable, os.path.abspath(__file__),
            "--follower-primary",
            "--crash-dir", primary_d,
            "--feed-dir", feed_d,
            "--tree-port-file", port_file,
            "--tree-min-downstream", str(n_relays),
            "--serve-clients", str(clients),
            "--serve-replicas", str(args.serve_replicas),
            "--serve-queue-depth", str(args.serve_queue_depth),
            "--serve-batch", str(args.serve_batch),
            "--serve-linger", str(args.serve_linger),
            "--crash-durability", "batch",
            "--crash-snapshot-after", str(snap_after),
            "--seed", str(args.seed),
        ]
        + (["--obs-port-file", primary_obs_file] if obs else []),
        stdout=child_log, stderr=child_log,
        env=cpu_child_env("--follower-primary", child_env),
    )

    def fail_out(msg: str) -> int:
        for f in failures:
            print(f"# FAIL: {f}", file=sys.stderr)
        print(f"# FAIL: {msg} (see {base}/child.log)", file=sys.stderr)
        if child.poll() is None:
            os.kill(child.pid, signal.SIGKILL)
        return 1

    t_wait = time.monotonic() + args.tree_timeout
    while not os.path.exists(port_file):
        if child.poll() is not None or time.monotonic() > t_wait:
            return fail_out("primary never published its port")
        time.sleep(0.01)
    with open(port_file) as f:
        p_host, p_port = f.read().split()

    # ---- the tree: relays in this process, leaves as processes -----
    relays = [
        RelayNode(
            SocketFeed(p_host, int(p_port), arg_width=aw),
            os.path.join(base, f"relay{r}"), arg_width=aw,
            poll_s=0.001, name=f"relay{r}",
            obs_port=0 if obs else None,
        )
        for r in range(n_relays)
    ]
    if obs:
        t_wait = time.monotonic() + args.tree_timeout
        while not os.path.exists(primary_obs_file):
            if child.poll() is not None or time.monotonic() > t_wait:
                return fail_out(
                    "primary never published its exporter port"
                )
            time.sleep(0.01)
        with open(primary_obs_file) as f:
            o_host, o_port = f.read().split()
        # relays are in THIS process: hand the collector their
        # exporter objects (loopback fast path), so their identities
        # are known before the first cycle and component
        # re-attribution covers the whole run
        collector = FleetCollector(
            [f"{o_host}:{o_port}"] + [r.exporter for r in relays],
            interval_s=0.25, out_path=fleet_path,
        )
        collector.start()

    def ack_lines() -> list[str]:
        try:
            with open(acks_path) as f:
                data = f.read()
        except FileNotFoundError:
            return []
        return [ln for ln in data.split("\n")[:-1] if ln]

    def wait_acks(n: int, why: str) -> bool:
        t_end = time.monotonic() + args.tree_timeout
        while len(ack_lines()) < n:
            if child.poll() is not None or time.monotonic() > t_end:
                failures.append(
                    f"primary reached only {len(ack_lines())} acks "
                    f"waiting for {n} ({why})"
                )
                return False
            time.sleep(0.02)
        return True

    def spawn_leaf(idx: int, bootstrap: bool):
        relay = relays[idx % n_relays]
        d = os.path.join(base, f"leaf{idx}")
        ready = os.path.join(base, f"leaf{idx}.ready")
        result = os.path.join(base, f"leaf{idx}.json")
        obs_file = os.path.join(base, f"leaf{idx}.obs")
        for stale in (ready, result,  # the single-window leaf's dir
                      obs_file):  # is reused (crash-resume); its
            try:  # barrier/port files not
                os.remove(stale)
            except FileNotFoundError:
                pass
        target = len(ack_lines())
        proc = subprocess.Popen(
            [
                sys.executable, os.path.abspath(__file__),
                "--tree-follower",
                "--crash-dir", d,
                "--tree-connect",
                f"{relay.address[0]}:{relay.address[1]}",
                "--tree-target", str(target),
                "--tree-ready-file", ready,
                "--tree-go-file", os.path.join(base, "go"),
                "--tree-result-file", result,
                "--tree-read-seconds", str(args.tree_read_seconds),
                "--tree-timeout", str(args.tree_timeout),
                "--tree-bootstrap", "1" if bootstrap else "0",
                "--serve-clients", str(clients),
            ]
            + (["--obs-port-file", obs_file] if obs else []),
            stdout=child_log, stderr=child_log,
            env=cpu_child_env("--tree-follower", child_env),
        )
        return proc, ready, result

    def scrape_leaves(count: int) -> None:
        """Point the collector at the window's leaf exporters (each
        spawn publishes a fresh ephemeral port; a dead previous
        window's target just counts scrape errors)."""
        if collector is None:
            return
        for i in range(count):
            try:
                with open(os.path.join(base, f"leaf{i}.obs")) as f:
                    h, prt = f.read().split()
            except (FileNotFoundError, ValueError):
                continue
            collector.add_target(f"{h}:{prt}")

    def run_leaves(count: int, tag: str):
        """Spawn `count` leaves, barrier them on the go file, collect
        results; returns (results, primary ack rate over the window).
        A hung or crashed leaf fails the PHASE (diagnostics + leaf
        cleanup), never the harness with a raw traceback."""
        go = os.path.join(base, "go")
        if os.path.exists(go):
            os.remove(go)
        leaves = [spawn_leaf(i, bootstrap=False)
                  for i in range(count)]
        leaf_procs.extend(pr for pr, _, _ in leaves)
        try:
            t_end = time.monotonic() + args.tree_timeout
            while not all(os.path.exists(r) for _, r, _ in leaves):
                if (time.monotonic() > t_end
                        or any(pr.poll() is not None
                               for pr, _, _ in leaves)):
                    failures.append(
                        f"{tag}: a leaf exited or never caught up"
                    )
                    return [], 0.0
                time.sleep(0.02)
            scrape_leaves(count)
            acks0 = len(ack_lines())
            t0 = time.monotonic()
            with open(go, "w") as f:
                f.write("go")
            results = []
            for pr, _, res in leaves:
                pr.wait(timeout=args.tree_timeout)
                with open(res) as f:
                    results.append(json.load(f))
            # a leaf that never replicated must not count: its reads
            # against near-empty state would inflate the scaling gate
            bad = [r for r in results if not r.get("caught_up")]
            if bad:
                failures.append(
                    f"{tag}: {len(bad)} leaf/leaves reported reads "
                    f"without catching up (applied "
                    f"{[r.get('applied') for r in bad]})"
                )
                return [], 0.0
            window = max(time.monotonic() - t0, 1e-6)
            ack_rate = (len(ack_lines()) - acks0) / window
            return results, ack_rate
        except (OSError, subprocess.TimeoutExpired,
                json.JSONDecodeError) as e:
            failures.append(
                f"{tag}: leaf harness failed "
                f"({type(e).__name__}: {e})"
            )
            return [], 0.0
        finally:
            for pr, _, _ in leaves:
                if pr.poll() is None:
                    pr.kill()

    report = None
    run = {}
    candidate = None
    leaf_procs: list = []
    try:
        # ---- phase 1: read scale-out (1 leaf, then all leaves) -----
        if not wait_acks(max(snap_after, 50), "warmup"):
            return fail_out("primary produced no load")
        single_res, single_ack_rate = run_leaves(1, "single")
        if not single_res:
            return fail_out("single-leaf window failed")
        all_res, all_ack_rate = run_leaves(n_leaves, "aggregate")
        if not all_res:
            return fail_out("aggregate window failed")
        single_tput = single_res[0]["reads"] / single_res[0]["seconds"]
        agg_tput = sum(r["reads"] / r["seconds"] for r in all_res)
        scaling = agg_tput / max(single_tput, 1e-9)
        hold = all_ack_rate / max(single_ack_rate, 1e-9)
        if scaling < args.tree_scaling_min:
            failures.append(
                f"aggregate follower read throughput does not scale: "
                f"{agg_tput:.0f} ops/s across {n_leaves} leaves vs "
                f"{single_tput:.0f} single ({scaling:.2f}x < "
                f"{args.tree_scaling_min}x)"
            )
        if hold < args.tree_primary_hold:
            failures.append(
                f"primary write throughput collapsed under the tree: "
                f"{all_ack_rate:.0f} acks/s with {n_leaves} leaves vs "
                f"{single_ack_rate:.0f} with one ({hold:.2f} < "
                f"{args.tree_primary_hold})"
            )

        # ---- phase 2: snapshot bootstrap vs full-WAL replay --------
        if not wait_acks(snap_after + 20, "snapshot"):
            return fail_out("no snapshot landed")
        target = len(ack_lines())
        t0 = time.perf_counter()
        cold = Follower(
            dispatch, SocketFeed(*relays[0].address, arg_width=aw),
            os.path.join(base, "cold-bootstrap"),
            nr_kwargs=dict(n_replicas=1, log_entries=1 << 15,
                           gc_slack=512, exec_window=256),
            poll_s=0.001, bootstrap=True, name="cold-bootstrap",
            obs_port=0 if obs else None,
        )
        if collector is not None:
            # in-process exporter: the collector's loopback fast path
            collector.add_target(cold.frontend.exporter)
        if not cold.wait_applied(target, timeout=args.tree_timeout):
            failures.append("bootstrap follower never caught up")
        bootstrap_s = time.perf_counter() - t0
        boot_pos = (cold.bootstrap_report[0]
                    if cold.bootstrap_report else 0)
        if cold.bootstrap_report is None:
            failures.append(
                "cold follower did not bootstrap from a snapshot "
                "(none served?)"
            )
        elif cold.recovery_report.snapshot_pos != boot_pos:
            failures.append(
                f"bootstrap snapshot at {boot_pos} was fetched but "
                f"recovery booted from "
                f"{cold.recovery_report.snapshot_pos}"
            )
        t0 = time.perf_counter()
        full = Follower(
            dispatch, SocketFeed(*relays[0].address, arg_width=aw),
            os.path.join(base, "cold-full"),
            nr_kwargs=dict(n_replicas=1, log_entries=1 << 15,
                           gc_slack=512, exec_window=256),
            poll_s=0.001, bootstrap=False, name="cold-full",
        )
        if not full.wait_applied(target, timeout=args.tree_timeout):
            failures.append("full-replay follower never caught up")
        full_replay_s = time.perf_counter() - t0
        # bit-identity between the two catch-up paths: both keep
        # applying live traffic, so compare their journaled histories
        # position-aligned over the common range (deterministic
        # replay then makes the states folds of the same history —
        # the clause tests/test_transport.py pins state-level at a
        # quiesced barrier)
        common = min(cold.applied_pos(), full.applied_pos())
        base_pos = max(cold.nr.wal.base, full.nr.wal.base)

        def flat_ops(it, upto):
            for rec in it:
                for j in range(rec.count):
                    if rec.pos + j >= upto:
                        return
                    yield (rec.pos + j, int(rec.opcodes[j]),
                           tuple(int(a) for a in rec.args[j]))

        for pa, pb in zip(
            flat_ops(cold.nr.wal.records(base_pos), common),
            flat_ops(full.nr.wal.records(base_pos), common),
        ):
            if pa != pb:
                failures.append(
                    f"bootstrap history diverges from full replay at "
                    f"{pa[0]}: {pa[1:]} vs {pb[1:]}"
                )
                break
        full.close()
        if bootstrap_s >= full_replay_s:
            failures.append(
                f"snapshot bootstrap ({bootstrap_s:.2f}s) did not "
                f"beat full-WAL replay ({full_replay_s:.2f}s)"
            )

        # ---- phase 3: SIGKILL -> mid-tree promotion ----------------
        candidate = cold  # keeps applying through relay 0
        manager = PromotionManager(
            SocketFeed(*relays[0].address, arg_width=aw), [candidate],
            heartbeat_timeout_s=args.follower_heartbeat_timeout,
            check_interval_s=0.03,
        )
        manager.start()
        if not wait_acks(kill_after, "kill point"):
            return fail_out("never reached the kill point")
        os.kill(child.pid, signal.SIGKILL)
        t_kill = time.monotonic()
        child.wait()
        report = manager.wait(timeout=args.tree_timeout)
        rto_wall = time.monotonic() - t_kill
        if report is None:
            return fail_out("mid-tree promotion did not complete")
        if not candidate.promoted or candidate.frontend.read_only:
            failures.append(
                "candidate not serving writes after promotion"
            )

        acked_max = [0] * clients
        acked_total = 0
        for ln in ack_lines():
            parts = ln.split()
            if parts[0] == "ERR":
                failures.append(f"primary oracle violation: {ln}")
                continue
            c, i = int(parts[0]), int(parts[1])
            if i != acked_max[c] + 1:
                failures.append(
                    f"client {c} ack sequence broken at {i}"
                )
            acked_max[c] = max(acked_max[c], i)
            acked_total += 1
        lost = 0
        values = []
        for c in range(clients):
            v = candidate.frontend.read((SR_GET, c), rid=0)
            values.append(v)
            if v < acked_max[c]:
                lost += acked_max[c] - v
                failures.append(
                    f"client {c}: acked to {acked_max[c]} but the "
                    f"promoted mid-tree follower holds {v} "
                    f"(LOST ACKED WRITES)"
                )
        duplicated = 0
        seen_next = [1] * clients
        for rec in candidate.nr.wal.records(0):
            for _opc, row in zip(rec.opcodes, rec.args):
                c, v = int(row[0]) % clients, int(row[1])
                if v < seen_next[c]:
                    duplicated += 1
                    failures.append(
                        f"client {c}: value {v} DUPLICATED in the "
                        f"promoted follower's WAL"
                    )
                elif v > seen_next[c]:
                    seen_next[c] = v + 1
                else:
                    seen_next[c] += 1

        # zombie fencing over the wire: a restarted zombie primary
        # re-serves its old feed on the old port and publishes a
        # record stamped with its superseded epoch — relay 0's
        # degraded-mode client reconnects and DELIVERS it, and the
        # fence the promotion pushed into the relay must drop it
        # before it reaches the subtree's journal
        from node_replication_tpu.repl import FeedServer

        zfeed = DirectoryFeed(feed_d, arg_width=aw)
        zcursor = relays[0].cursor()
        ztail = relays[0].local.tail_pos()
        # the fence never reached the dead primary's feed (its server
        # died), so the zombie's local epoch check passes — exactly
        # the split-brain publish the relay-side fence exists for
        zfeed.publish(zfeed.epoch(), zcursor,
                      np.zeros(1, np.int32),
                      np.zeros((1, aw), np.int32))
        zsrv = FeedServer(zfeed, host=p_host, port=int(p_port))
        try:
            t_end = time.monotonic() + 10.0
            while (relays[0].cursor() <= zcursor
                   and time.monotonic() < t_end):
                time.sleep(0.01)
            if relays[0].cursor() <= zcursor:
                failures.append(
                    "zombie probe inconclusive: relay 0 never "
                    "observed the zombie record"
                )
            if relays[0].local.tail_pos() != ztail:
                failures.append(
                    "a record stamped with the dead primary's epoch "
                    "reached the relay journal (zombie not fenced)"
                )
        finally:
            zsrv.close()

        post_ops = 0
        for c in range(clients):
            for i in range(values[c] + 1, values[c] + 4):
                resp = candidate.frontend.call((SR_SET, c, i), rid=0)
                if resp != i - 1:
                    failures.append(
                        f"post-promotion client {c} op {i}: expected "
                        f"{i - 1}, got {resp}"
                    )
                post_ops += 1

        run = {
            "relays": n_relays,
            "followers": n_leaves,
            "acked": acked_total,
            "agg_reads_ops": agg_tput,
            "single_reads_ops": single_tput,
            "read_scaling_x": scaling,
            "primary_tput_hold": hold,
            "bootstrap_pos": boot_pos,
            "bootstrap_s": bootstrap_s,
            "full_replay_s": full_replay_s,
            "bootstrap_speedup_x": full_replay_s
            / max(bootstrap_s, 1e-9),
            "detect_s": report.detect_s,
            "promote_s": report.promote_s,
            "rto_s": report.rto_s,
            "lost": lost,
            "duplicated": duplicated,
            "post_restart_ops": post_ops,
        }

        # ---- --tree-obs gate: the merged fleet trace must let the
        # report reconstruct at least one sampled record's FULL
        # submit->ack hop timeline across >= 3 processes, with
        # per-edge latency percentiles, and the Fleet section must
        # show every tree node (the observability acceptance of
        # ISSUE 13 — a fleet you cannot observe is a fleet you
        # cannot autoscale)
        if collector is not None:
            collector.stop()
            from node_replication_tpu.obs import report as obs_report

            fl = (obs_report.analyze(
                obs_report.load_events(fleet_path)
            ).get("fleet")) or {}
            node_ids = {n.get("node_id")
                        for n in (fl.get("nodes") or [])}
            expected = (
                {"primary", "cold-bootstrap"}
                | {f"relay{r}" for r in range(n_relays)}
                | {f"leaf{i}" for i in range(n_leaves)}
            )
            missing = sorted(expected - node_ids)
            if missing:
                failures.append(
                    f"fleet section is missing node(s) {missing} "
                    f"(has {sorted(node_ids)})"
                )
            multi = int(fl.get("complete_multiprocess_records", 0))
            edges = fl.get("edges") or {}
            if multi < 1:
                failures.append(
                    "no sampled record's full submit->ack hop "
                    "timeline spans >= 3 processes "
                    f"(records={fl.get('records', 0)}, complete="
                    f"{fl.get('complete_records', 0)})"
                )
            if "submit->ack" not in edges or not edges:
                failures.append(
                    "fleet section has no per-edge latency "
                    f"percentiles (edges={sorted(edges)})"
                )
            run.update(
                obs_nodes=len(node_ids),
                obs_records=int(fl.get("records", 0)),
                obs_multiproc_records=multi,
                obs_edges=len(edges),
            )
            print(
                f"# --tree-obs: {len(node_ids)} node(s), "
                f"{fl.get('records', 0)} traced record(s), {multi} "
                f"full multi-process chain(s), {len(edges)} "
                f"edge(s) -> {fleet_path}",
                file=sys.stderr,
            )
    finally:
        if collector is not None:
            collector.close()
        for pr in leaf_procs:
            if pr.poll() is None:
                pr.kill()
        if candidate is not None:
            candidate.close()
        for relay in relays:
            relay.close()
        if child.poll() is None:
            os.kill(child.pid, signal.SIGKILL)
            child.wait()
        child_log.close()

    append_tree_csv(args.serve_out, tree_rows("bench", run))
    print(json.dumps({
        "metric": "tree_replication",
        "value": round(report.rto_s, 4),
        "unit": "seconds_rto",
        "rto_wall_s": round(rto_wall, 4),
        **{k: (round(v, 4) if isinstance(v, float) else v)
           for k, v in run.items()},
    }))
    if not args.tree_dir:
        shutil.rmtree(base, ignore_errors=True)
    if failures:
        for f in failures:
            print(f"# FAIL: {f}", file=sys.stderr)
        return 1
    print(
        f"# tree OK: {n_relays} relay(s) x {n_leaves} leaf "
        f"process(es); reads {run['single_reads_ops']:.0f} -> "
        f"{run['agg_reads_ops']:.0f} ops/s ({run['read_scaling_x']:.2f}x, "
        f"primary hold {run['primary_tput_hold']:.2f}); bootstrap "
        f"{run['bootstrap_s']:.2f}s vs full replay "
        f"{run['full_replay_s']:.2f}s "
        f"({run['bootstrap_speedup_x']:.2f}x); SIGKILL -> mid-tree "
        f"promotion in {report.rto_s:.3f}s, lost 0, duplicated 0, "
        f"zombie fenced, served {run['post_restart_ops']} more ops",
        file=sys.stderr,
    )
    return 0


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--replicas", type=int, default=4096)
    p.add_argument("--keys", type=int, default=10_000)
    p.add_argument("--writes-per-replica", type=int, default=1)
    p.add_argument("--reads-per-replica", type=int, default=1)
    p.add_argument("--steps", type=int, default=64,
                   help="distinct pre-generated step inputs (cycled)")
    p.add_argument("--repeats", type=int, default=5,
                   help="timed repeats; the JSON value is their median")
    p.add_argument("--min-time", type=float, default=1.0,
                   help="minimum seconds of device work per repeat")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--path", choices=["auto", "combined", "scan", "pallas"],
                   default="auto",
                   help="replay engine: 'combined' = Dispatch.window_apply "
                        "parallel reduction (sort + merge; the TPU-first "
                        "fast path), 'scan' = generic vmapped lax.scan "
                        "(one sequential apply per entry — the faithful "
                        "analog of the reference's replay loop, ~1000x "
                        "slower at this config), 'pallas' = hand-tiled "
                        "VMEM kernel (needs a small keyspace, e.g. "
                        "--keys 1024), 'auto' = combined when the model "
                        "provides window_apply")
    p.add_argument("--pallas", action="store_true",
                   help="alias for --path pallas")
    p.add_argument("--spread-threshold", type=float, default=5.0,
                   help="max acceptable min-to-max spread (%%) across "
                        "repeats; a noisier window is treated as "
                        "CONTENDED and re-measured (VERDICT r3 weak #2)")
    p.add_argument("--max-attempts", type=int, default=3,
                   help="measurement windows to try before accepting a "
                        "contended one (the cleanest attempt is "
                        "reported either way)")
    serve = p.add_argument_group(
        "serve", "serve-frontend benchmark (--serve): a closed-loop "
                 "sequence-verified run (zero lost/duplicated "
                 "responses, p50/p95/p99 latency) plus an open-loop "
                 "overload probe demonstrating typed backpressure")
    serve.add_argument("--serve", action="store_true",
                       help="run the serve benchmark instead of the "
                            "replay flagship")
    serve.add_argument("--serve-clients", type=int, default=8,
                       help="client OS threads")
    serve.add_argument("--serve-ops", type=int, default=10_000,
                       help="total sequence-numbered ops across clients")
    serve.add_argument("--serve-replicas", type=int, default=2)
    serve.add_argument("--serve-queue-depth", type=int, default=256,
                       help="admission bound per replica (closed run)")
    serve.add_argument("--serve-batch", type=int, default=64,
                       help="combiner batch size trigger")
    serve.add_argument("--serve-linger", type=float, default=0.001,
                       help="batch deadline trigger, seconds")
    serve.add_argument("--serve-overload-ops", type=int, default=2000,
                       help="open-loop submissions in the overload "
                            "probe (0 disables the probe)")
    serve.add_argument("--serve-overload-rate", type=float,
                       default=20_000.0,
                       help="open-loop arrival rate (ops/sec) for the "
                            "overload probe")
    serve.add_argument("--serve-out", default=".",
                       help="directory for serve_benchmarks.csv")
    serve.add_argument("--profile", action="store_true",
                       help="host-profiling phase: rerun the closed "
                            "workload profiler-OFF then profiler-ON "
                            "(obs/profile.py), emit the host-budget "
                            "JSON, and gate ON >= 95%% of OFF "
                            "throughput (exit 1)")
    serve.add_argument("--profile-hz", type=float, default=97.0,
                       help="sampling rate for --profile (prime "
                            "default avoids phase-locking with "
                            "ms-periodic serve work)")
    serve.add_argument("--profile-folded", default=None,
                       help="write the profiled run's folded stacks "
                            "to this path (flamegraph/speedscope "
                            "input; CI artifact)")
    overload = p.add_argument_group(
        "overload", "graceful-degradation benchmark (--overload): "
                    "open-loop Poisson + heavy-tailed burst arrivals "
                    "at a multiple of measured capacity; exits 1 "
                    "unless the adaptive controller beats the static "
                    "queue_depth baseline on goodput-under-SLO with "
                    "zero lost/dup acks, zero priority inversions, "
                    "and in-bound brownout reads")
    overload.add_argument("--overload", action="store_true",
                          help="run the overload benchmark")
    overload.add_argument("--overload-clients", type=int, default=4,
                          help="client threads (and seqreg registers)")
    overload.add_argument("--overload-probe-ops", type=int,
                          default=1200,
                          help="closed-loop ops for the capacity "
                               "probe")
    overload.add_argument("--overload-ops", type=int, default=8000,
                          help="max open-loop arrivals per run (caps "
                               "the schedule the rate would produce "
                               "over --overload-seconds)")
    overload.add_argument("--overload-seconds", type=float,
                          default=4.0,
                          help="target arrival-window length")
    overload.add_argument("--overload-factor", type=float, default=2.0,
                          help="arrival rate as a multiple of "
                               "measured capacity")
    overload.add_argument("--overload-queue-depth", type=int,
                          default=256,
                          help="static admission bound floor (grown "
                               "to 4x capacity x deadline so the "
                               "baseline actually exhibits "
                               "bufferbloat)")
    overload.add_argument("--overload-batch", type=int, default=8,
                          help="batch_max_ops for both runs. Also "
                               "sets the experiment's scale: service "
                               "capacity (and so the 2x arrival "
                               "rate) grows with it, and the fixed "
                               "--overload-ops schedule must span "
                               "many deadlines of sustained overload "
                               "for the comparison to measure "
                               "admission policy rather than "
                               "transients — 8 puts the window near "
                               "1s on a typical CPU runner")

    kernel = p.add_argument_group(
        "kernel", "combiner-round engine benchmark (--kernel): fused "
                  "pallas round vs the combined/scan append+exec "
                  "chains at each RxKxW point, bit-identity verified "
                  "before timing; exits 1 on any divergence, and (on "
                  "TPU) when fused < combined at the flagship point")
    kernel.add_argument("--kernel", action="store_true",
                        help="run the kernel-engine benchmark")
    kernel.add_argument("--kernel-points",
                        default="256x1024x512,1024x4096x1024,"
                                "4096x10000x4096",
                        help="comma-separated RxKxW points (replicas x "
                             "keys x window); the flagship 4096x10000 "
                             "point carries the fused>=combined gate")
    kernel.add_argument("--kernel-duration", type=float, default=1.0,
                        help="seconds of fenced timed rounds per tier")
    kernel.add_argument("--kernel-interpret", action="store_true",
                        help="interpret-mode kernels for --kernel and "
                             "--mesh (the CPU rehearsal; throughput "
                             "gates self-skip). Without it both modes "
                             "refuse to run off the TPU")
    kernel.add_argument("--kernel-devices", type=int, default=1,
                        help="measure the MESH tier pair (mesh_fused "
                             "vs shmap) at N devices instead of the "
                             "single-device tiers; launches_per_round "
                             "in the CSV is counter-derived, so the "
                             "one-launch claim is checked as devices "
                             "scale")
    mesh = p.add_argument_group(
        "mesh", "mesh scaling benchmark (--mesh): the flagship "
                "hashmap 50/50 config at 1→N devices with the "
                "replica axis sharded over the mesh; exits 1 unless "
                "every width is bit-identical to the 1-device fleet "
                "and (on TPU) the 1-device point stays within "
                "tolerance of the committed flagship baseline")
    mesh.add_argument("--mesh", action="store_true",
                      help="run the mesh scaling curve instead of the "
                           "replay flagship (reuses --replicas/--keys/"
                           "--writes-per-replica/--reads-per-replica)")
    mesh.add_argument("--mesh-devices", default=None,
                      help="comma-separated device counts to measure "
                           "(default: powers of two dividing "
                           "--replicas, up to every visible device; "
                           "1 is always included as the curve base)")
    mesh.add_argument("--mesh-duration", type=float, default=1.0,
                      help="seconds of timed stepping per point")
    mesh.add_argument("--mesh-window", type=int, default=4096,
                      help="combiner-round window of the per-width "
                           "exec-tier column (mesh_fused vs shmap; "
                           "the flagship --kernel window by default)")
    mesh.add_argument("--mesh-baseline", type=float, default=0.0,
                      help="flagship dispatches/s the 1-device point "
                           "is gated against on TPU (default 0: the "
                           "gate is off)")
    mesh.add_argument("--mesh-baseline-tolerance", type=float,
                      default=0.15,
                      help="allowed relative deviation from "
                           "--mesh-baseline (run spread plus "
                           "methodology skew between the flagship "
                           "repeats loop and the curve's chunked "
                           "measurement)")
    chaos = p.add_argument_group(
        "chaos", "fault-injection benchmark (--chaos): the closed-loop "
                 "sequence-verified serve run with a FaultPlan killing "
                 "and repairing replicas mid-flight; exits 1 on any "
                 "lost/duplicated response or unrepaired replica")
    chaos.add_argument("--chaos", action="store_true",
                       help="run the chaos benchmark (reuses the "
                            "--serve-* knobs for load shape)")
    chaos.add_argument("--chaos-kills", type=int, default=1,
                       help="how many worker kills to inject")
    chaos.add_argument("--chaos-kill-after", type=int, default=20,
                       help="serve-batch hook hits before the kill "
                            "fires (deterministic schedule position)")
    chaos.add_argument("--chaos-retry-attempts", type=int, default=16,
                       help="client retry budget across kill+repair")
    chaos.add_argument("--chaos-availability-min", type=float,
                       default=1.0,
                       help="minimum completed/attempted ratio (the "
                            "pre-append failover design target is "
                            "1.0: kills cost latency, not responses)")
    crash = p.add_argument_group(
        "crash", "crash-consistency benchmark (--crash): fork a "
                 "durable-ack serve loop, SIGKILL it at a seeded ack "
                 "count, restart from disk (snapshot + WAL replay) "
                 "and exit 1 if any fsync-acked response is lost or "
                 "duplicated, or the restart is not bit-identical")
    crash.add_argument("--crash", action="store_true",
                       help="run the crash-recovery benchmark (reuses "
                            "the --serve-* knobs for load shape)")
    crash.add_argument("--crash-child", action="store_true",
                       help=argparse.SUPPRESS)  # internal: the victim
    crash.add_argument("--crash-dir", default=None,
                       help="durability directory (default: a temp "
                            "dir, removed after a clean run)")
    crash.add_argument("--crash-kill-after-acks", type=int, default=0,
                       help="SIGKILL the child once this many acks "
                            "are on disk (0 = seeded from --seed)")
    crash.add_argument("--crash-snapshot-after", type=int, default=-1,
                       help="child takes one durable snapshot after "
                            "this many acks (-1 = half the kill "
                            "point, 0 = never)")
    crash.add_argument("--crash-durability",
                       choices=["batch", "always"], default="batch",
                       help="durable-ack mode under test (WAL fsync "
                            "per batch vs per append)")
    crash.add_argument("--crash-timeout", type=float, default=90.0,
                       help="parent gives up waiting for the kill "
                            "point after this many seconds")
    follower = p.add_argument_group(
        "follower", "replication benchmark (--follower): fork a "
                    "primary serve loop with shipped durable acks, "
                    "follow its WAL feed in-process, verify "
                    "staleness-bounded reads, SIGKILL the primary, "
                    "and exit 1 unless a promotion with zero "
                    "lost/duplicated acked writes completes — the "
                    "measured RTO is the reported metric")
    follower.add_argument("--follower", action="store_true",
                          help="run the follower-replication "
                               "benchmark (reuses the --serve-* "
                               "knobs for load shape)")
    follower.add_argument("--follower-primary", action="store_true",
                          help=argparse.SUPPRESS)  # internal: primary
    follower.add_argument("--feed-dir", default=None,
                          help=argparse.SUPPRESS)  # internal: feed
    follower.add_argument("--follower-dir", default=None,
                          help="working directory (default: a temp "
                               "dir, removed after a clean run)")
    follower.add_argument("--follower-kill-after-acks", type=int,
                          default=0,
                          help="SIGKILL the primary once this many "
                               "acks are shipped (0 = seeded from "
                               "--seed)")
    follower.add_argument("--follower-max-lag", type=int, default=64,
                          help="staleness bound (positions) for the "
                               "verified follower reads")
    follower.add_argument("--follower-heartbeat-timeout", type=float,
                          default=0.5,
                          help="heartbeat silence before the "
                               "promotion watch strikes the primary")
    follower.add_argument("--follower-timeout", type=float,
                          default=90.0,
                          help="parent gives up waiting for the kill "
                               "point / promotion after this many "
                               "seconds")
    tree = p.add_argument_group(
        "tree", "multi-host replication tree benchmark (--tree): "
                "fork a primary serving its feed + snapshots over "
                "TCP, build a primary -> relays -> leaf-process "
                "topology on localhost, and exit 1 unless aggregate "
                "leaf reads scale while primary writes hold, "
                "snapshot bootstrap beats full-WAL replay, and a "
                "SIGKILL promotes a mid-tree follower with zero "
                "lost/duplicated acked writes")
    tree.add_argument("--tree", action="store_true",
                      help="run the replication-tree benchmark "
                           "(reuses the --serve-* knobs for load "
                           "shape)")
    tree.add_argument("--tree-relays", type=int, default=2,
                      help="interior relay nodes (each one TCP "
                           "stream off the primary)")
    tree.add_argument("--tree-followers", type=int, default=4,
                      help="leaf follower PROCESSES for the "
                           "read-scale-out phase")
    tree.add_argument("--tree-read-seconds", type=float, default=2.0,
                      help="measured read window per leaf phase")
    tree.add_argument("--tree-kill-after-acks", type=int, default=0,
                      help="SIGKILL the primary once this many acks "
                           "shipped (0 = seeded from --seed)")
    tree.add_argument("--tree-scaling-min", type=float, default=1.25,
                      help="aggregate/single leaf read-throughput "
                           "gate (conservative: CI cores bound it "
                           "well below the leaf count)")
    tree.add_argument("--tree-primary-hold", type=float, default=0.4,
                      help="primary ack-rate hold gate (all-leaves "
                           "rate / single-leaf rate)")
    tree.add_argument("--tree-timeout", type=float, default=120.0,
                      help="per-phase give-up budget")
    tree.add_argument("--tree-dir", default=None,
                      help="working directory (default: a temp dir, "
                           "removed after a clean run)")
    tree.add_argument("--tree-obs", action="store_true",
                      help="fleet observability on the tree: a "
                           "metrics exporter in every process, a "
                           "FleetCollector merging scrapes + trace "
                           "tails into tree_fleet.jsonl, and a hard "
                           "gate on a reconstructed cross-process "
                           "per-record hop timeline (obs/)")
    tree.add_argument("--tree-obs-sample", type=int, default=4,
                      help="per-record trace sampling modulus for "
                           "--tree-obs (keep 1 record in N; default "
                           "4)")
    tree.add_argument("--tree-follower", action="store_true",
                      help=argparse.SUPPRESS)  # internal: leaf proc
    tree.add_argument("--tree-connect", default=None,
                      help=argparse.SUPPRESS)  # internal: host:port
    tree.add_argument("--tree-target", type=int, default=0,
                      help=argparse.SUPPRESS)  # internal: catch-up pos
    tree.add_argument("--tree-ready-file", default=None,
                      help=argparse.SUPPRESS)  # internal
    tree.add_argument("--tree-go-file", default=None,
                      help=argparse.SUPPRESS)  # internal
    tree.add_argument("--tree-result-file", default=None,
                      help=argparse.SUPPRESS)  # internal
    tree.add_argument("--tree-bootstrap", type=int, default=1,
                      help=argparse.SUPPRESS)  # internal: leaf flag
    tree.add_argument("--tree-port-file", default=None,
                      help=argparse.SUPPRESS)  # internal: primary
    tree.add_argument("--tree-min-downstream", type=int, default=1,
                      help=argparse.SUPPRESS)  # internal: ack gate
    tree.add_argument("--obs-port-file", default=None,
                      help=argparse.SUPPRESS)  # internal: child
    # processes publish their exporter address here (--tree-obs)
    sharded = p.add_argument_group(
        "sharded", "keyspace-sharded fleet benchmark (--sharded): N "
                   "shard-primary processes (each the --follower "
                   "durable-ack pipeline: WAL + shipped feed + "
                   "ship-before-ack) behind a ShardRouter, with a "
                   "parent-side follower per shard; exits 1 unless "
                   "aggregate acked-write throughput scales over the "
                   "1-shard baseline, a SIGKILLed shard promotes its "
                   "follower and re-homes under a bumped published "
                   "ShardMap with zero lost/duplicated acks, and the "
                   "other shards' goodput holds through the outage")
    sharded.add_argument("--sharded", action="store_true",
                         help="run the sharded-fleet benchmark")
    sharded.add_argument("--sharded-shards", type=int, default=3,
                         help="shard-primary processes in the fleet "
                              "leg (the scaling gate is calibrated "
                              "for 3; must be >= 2 for the kill leg)")
    sharded.add_argument("--sharded-clients", type=int, default=4,
                         help="closed-loop client threads PER shard "
                              "(one thread per (client, shard) "
                              "keyspace slot)")
    sharded.add_argument("--sharded-seconds", type=float, default=3.0,
                         help="measured window per leg")
    sharded.add_argument("--sharded-linger", type=float,
                         default=0.025,
                         help="the shard primaries' combiner linger. "
                              "Acked writes are LATENCY-bound rounds "
                              "(linger + fsync + ship) — the regime "
                              "where horizontal sharding pays and "
                              "where the scaling leg measures fleet "
                              "parallelism rather than one host's "
                              "spare cores; a shard's concurrent "
                              "clients batch into each round, so the "
                              "linger amortizes, not serializes")
    sharded.add_argument("--sharded-scaling-min", type=float,
                         default=2.2,
                         help="aggregate/baseline acked-write "
                              "throughput gate (<= 0 skips the "
                              "baseline leg entirely — the CI smoke "
                              "mode, which keeps only the failover "
                              "gates)")
    sharded.add_argument("--sharded-hold-min", type=float,
                         default=0.9,
                         help="survivor goodput gate: the other "
                              "shards' acked rate from the SIGKILL "
                              "through the post window over their "
                              "pre-kill window")
    sharded.add_argument("--sharded-heartbeat-timeout", type=float,
                         default=0.5,
                         help="heartbeat silence before the victim's "
                              "promotion watch strikes")
    sharded.add_argument("--sharded-timeout", type=float,
                         default=90.0,
                         help="per-phase give-up budget (spawn, "
                              "warmup, promotion)")
    sharded.add_argument("--sharded-dir", default=None,
                         help="working directory (default: a temp "
                              "dir, removed after a clean run)")
    sharded.add_argument("--shard-primary", action="store_true",
                         help=argparse.SUPPRESS)  # internal: shard
    sharded.add_argument("--shard-id", type=int, default=0,
                         help=argparse.SUPPRESS)  # internal
    sharded.add_argument("--shard-dir", default=None,
                         help=argparse.SUPPRESS)  # internal
    sharded.add_argument("--shard-map-dir", default=None,
                         help=argparse.SUPPRESS)  # internal
    sharded.add_argument("--shard-port-file", default=None,
                         help=argparse.SUPPRESS)  # internal

    txn = p.add_argument_group(
        "txn", "cross-shard transaction + online-resharding gates "
        "(--txn / --reshard): a SIGKILL matrix over the 2PC crash "
        "windows with zero-half-committed read-back gates, and a "
        "live 2->4 keyspace split under closed-loop writers with "
        "zero-lost/zero-dup + bounded-unavailability gates")
    txn.add_argument("--txn", action="store_true",
                     help="run the 2PC crash-matrix gate")
    txn.add_argument("--txn-rounds", type=int, default=3,
                     help="SIGKILL rounds (cycling the prepare / "
                     "commit / decide crash windows; default 3)")
    txn.add_argument("--txn-count", type=int, default=24,
                     help="transactions each kill-round child "
                     "drives (the kill lands mid-stream)")
    txn.add_argument("--txn-keys", type=int, default=4096,
                     help="hashmap keyspace for the txn/reshard "
                     "fleets")
    txn.add_argument("--txn-parity-seconds", type=float, default=1.5,
                     help="total wall time of the non-txn "
                     "throughput-parity leg (alternating slices)")
    txn.add_argument("--txn-parity-min", type=float, default=0.9,
                     help="gate: with_txn fleet must serve non-txn "
                     "single-shard writes at >= this fraction of a "
                     "txn-free build (default 0.9)")
    txn.add_argument("--txn-timeout", type=float, default=60.0,
                     help="per-child watchdog for the kill rounds")
    txn.add_argument("--txn-dir", default=None,
                     help="working dir for --txn (kept; default: "
                     "fresh temp dir, removed)")
    txn.add_argument("--reshard", action="store_true",
                     help="run the live-split + merge gate")
    txn.add_argument("--reshard-clients", type=int, default=8,
                     help="closed-loop writer threads (one key "
                     "each, covering all mod-4 classes)")
    txn.add_argument("--reshard-warmup", type=float, default=0.5,
                     help="seconds of traffic before the split")
    txn.add_argument("--reshard-window", type=float, default=1.5,
                     help="seconds of traffic after the split")
    txn.add_argument("--reshard-unavail-max", type=float, default=5.0,
                     help="gate: worst per-moved-key ack gap across "
                     "the cutover (seconds)")
    txn.add_argument("--reshard-dir", default=None,
                     help="working dir for --reshard (kept; "
                     "default: fresh temp dir, removed)")
    txn.add_argument("--txn-child", action="store_true",
                     help=argparse.SUPPRESS)  # internal: kill victim
    txn.add_argument("--txn-kill-site", default="none",
                     help=argparse.SUPPRESS)  # internal
    txn.add_argument("--txn-kill-after", type=int, default=0,
                     help=argparse.SUPPRESS)  # internal
    args = p.parse_args()
    setup_compile_cache()
    if args.max_attempts < 1:
        p.error("--max-attempts must be >= 1")
    if sum(map(bool, (args.chaos, args.serve, args.crash,
                      args.follower, args.tree, args.overload,
                      args.mesh, args.kernel, args.sharded,
                      args.txn, args.reshard))) > 1:
        p.error("--chaos, --serve, --crash, --follower, --tree, "
                "--overload, --mesh, --kernel, --sharded, --txn "
                "and --reshard are mutually exclusive")
    if args.sharded and args.sharded_shards < 2:
        p.error("--sharded needs --sharded-shards >= 2 (the kill leg "
                "promotes one shard while the others hold)")
    if args.shard_primary:
        if not args.shard_dir or not args.shard_map_dir \
                or not args.shard_port_file:
            p.error("--shard-primary requires --shard-dir, "
                    "--shard-map-dir and --shard-port-file")
        sys.exit(shard_primary_main(args))
    if args.crash_child:
        if not args.crash_dir:
            p.error("--crash-child requires --crash-dir")
        sys.exit(crash_child_main(args))
    if args.follower_primary:
        if not args.crash_dir or not args.feed_dir:
            p.error("--follower-primary requires --crash-dir and "
                    "--feed-dir")
        sys.exit(follower_primary_main(args))
    if args.tree_follower:
        if not args.crash_dir or not args.tree_connect \
                or not args.tree_result_file:
            p.error("--tree-follower requires --crash-dir, "
                    "--tree-connect and --tree-result-file")
        sys.exit(tree_follower_main(args))
    if args.txn_child:
        if not args.txn_dir:
            p.error("--txn-child requires --txn-dir")
        sys.exit(txn_child_main(args))
    if args.follower:
        sys.exit(follower_main(args))
    if args.sharded:
        sys.exit(sharded_main(args))
    if args.txn:
        sys.exit(txn_main(args))
    if args.reshard:
        sys.exit(reshard_main(args))
    if args.tree:
        sys.exit(tree_main(args))
    if args.crash:
        sys.exit(crash_main(args))
    if args.chaos:
        sys.exit(chaos_main(args))
    if args.serve:
        sys.exit(serve_main(args))
    if args.overload:
        sys.exit(overload_main(args))
    if args.mesh:
        sys.exit(mesh_main(args))
    if args.kernel:
        sys.exit(kernel_main(args))
    if args.pallas:
        if args.path not in ("auto", "pallas"):
            p.error(f"--pallas conflicts with --path {args.path}")
        args.path = "pallas"

    R, Bw, Br = args.replicas, args.writes_per_replica, args.reads_per_replica
    span = R * Bw
    spec = LogSpec(
        capacity=max(4 * span, 1 << 14),
        n_replicas=R,
        arg_width=3,
        gc_slack=min(8192, span),
    )
    d = make_hashmap(args.keys)
    log = log_init(spec)
    if args.path == "pallas":
        from node_replication_tpu.obs.metrics import get_registry
        from node_replication_tpu.ops.pallas_replay import (
            make_pallas_step,
            pallas_hashmap_state,
        )

        try:
            step = make_pallas_step(args.keys, spec, Bw, Br)
        except ValueError as e:
            sys.exit(f"--pallas config rejected: {e}")
        # third engine tier of the log.engine.* selection counters
        # (scan / window_apply / union_plan live in core/log.py)
        get_registry().counter("log.engine.pallas").inc()
        states = pallas_hashmap_state(args.keys, R)
    else:
        combined = None if args.path == "auto" else (args.path == "combined")
        step = make_step(d, spec, Bw, Br, combined=combined)
        states = replicate_state(d.init_state(), R)

    S = args.steps

    @jax.jit
    def gen(key):
        kk, kv, kr = jax.random.split(key, 3)
        wr_args = jnp.zeros((S, R, Bw, 3), jnp.int32)
        wr_args = wr_args.at[..., 0].set(
            jax.random.randint(kk, (S, R, Bw), 0, args.keys, jnp.int32)
        )
        wr_args = wr_args.at[..., 1].set(
            jax.random.randint(kv, (S, R, Bw), 0, 1 << 20, jnp.int32)
        )
        rd_args = jnp.zeros((S, R, Br, 3), jnp.int32)
        rd_args = rd_args.at[..., 0].set(
            jax.random.randint(kr, (S, R, Br), 0, args.keys, jnp.int32)
        )
        return wr_args, rd_args

    wr_all, rd_all = gen(jax.random.PRNGKey(args.seed))
    # pre-split into per-step device arrays so the measured loop does no
    # slicing work at all — just step dispatch
    wr_steps = [wr_all[t] for t in range(S)]
    rd_steps = [rd_all[t] for t in range(S)]
    wr_opc = jnp.full((R, Bw), HM_PUT, jnp.int32)
    rd_opc = jnp.full((R, Br), HM_GET, jnp.int32)
    fence(wr_steps, rd_steps)

    def run(n, log, states):
        out = None
        for i in range(n):
            t = i % S
            log, states, wr_resps, rd_resps = step(
                log, states, wr_opc, wr_steps[t], rd_opc, rd_steps[t]
            )
            out = (wr_resps, rd_resps)
        # the real barrier: block_until_ready does not wait on this
        # platform (see utils/fence.py)
        fence(log, states, out)
        return log, states

    from node_replication_tpu.obs.recorder import get_tracer
    from node_replication_tpu.obs.recorder import span as trace_span

    per_step = R * span + R * Br  # executed dispatches per step

    with trace_span("bench-warmup", steps=S):
        log, states = run(S, log, states)  # compile + warm

    # calibrate: size the per-repeat step count to cover --min-time
    cal = max(S, 32)
    t0 = time.perf_counter()
    log, states = run(cal, log, states)
    t_step = (time.perf_counter() - t0) / cal
    n_steps = max(cal, math.ceil(args.min_time / max(t_step, 1e-9)))

    # Spread-aware measurement: a window can land in a noisy slot
    # (the host's CPU cores are shared) and carry a misleading spread.
    # Measure up to --max-attempts windows; accept
    # the first whose min-to-max spread across repeats is within
    # --spread-threshold, else report the CLEANEST window with an
    # explicit contended=true — the committed JSON always carries the
    # most reproducible number the run could obtain, plus every
    # attempt's median for the audit trail.
    attempts = []
    tracer = get_tracer()
    measure_t0 = time.perf_counter()
    with trace_span("bench-measure", steps=n_steps * args.repeats):
        for attempt in range(args.max_attempts):
            values = []
            for _ in range(args.repeats):
                start = time.perf_counter()
                log, states = run(n_steps, log, states)
                elapsed = time.perf_counter() - start
                values.append(per_step * n_steps / elapsed)
                if tracer.enabled:
                    # per-second throughput samples for the report CLI's
                    # timeline; `run` ends on a real fence, so the ops
                    # count covers executed device work, not dispatch.
                    # A repeat can span several seconds — spread its ops
                    # over the seconds it covered (proportional to
                    # overlap) so the timeline's per-second rate is
                    # honest instead of bulk-dumping a multi-second
                    # repeat into one inflated bucket.
                    rel0 = start - measure_t0
                    rel1 = time.perf_counter() - measure_t0
                    total_ops = per_step * n_steps
                    dur = max(rel1 - rel0, 1e-9)
                    for sec in range(int(rel0), int(rel1) + 1):
                        overlap = min(rel1, sec + 1) - max(rel0, sec)
                        if overlap <= 0:
                            continue
                        tracer.emit(
                            "throughput",
                            second=sec,
                            ops=int(round(total_ops * overlap / dur)),
                            ops_per_sec=values[-1],
                            attempt=attempt,
                        )
            med = statistics.median(values)
            spread = 100.0 * (max(values) - min(values)) / med
            attempts.append((spread, med, values))
            if spread <= args.spread_threshold:
                break
            more = attempt + 1 < args.max_attempts
            print(
                f"# attempt {attempt + 1}: spread {spread:.1f}% > "
                f"{args.spread_threshold}% — contended window"
                + (", re-measuring" if more else
                   "; out of attempts, reporting the cleanest"),
                file=sys.stderr,
            )
    spread_pct, value, values = min(attempts, key=lambda a: a[0])
    contended = spread_pct > args.spread_threshold
    get_tracer().emit(
        "bench", replicas=R,
        steps=n_steps * args.repeats * len(attempts),
        repeats=args.repeats, steps_per_repeat=n_steps,
        ops_per_sec=value, spread_pct=spread_pct,
        attempts=len(attempts), contended=contended,
        path=args.path,
    )
    print(
        json.dumps(
            {
                "metric": "hashmap_5050_aggregate_replay_ops_per_sec",
                "value": round(value, 1),
                "unit": "ops/sec",
                "vs_baseline": round(value / 1e7, 3),
                "repeats": args.repeats,
                "spread_pct": round(spread_pct, 2),
                "contended": contended,
                "attempts": len(attempts),
                "attempt_medians": [round(m, 1) for _, m, _ in attempts],
                "steps_timed": n_steps * args.repeats,
                "path": args.path,
            }
        )
    )
    print(
        f"# path={args.path} | median of {args.repeats} repeats x "
        f"{n_steps} steps "
        f"(~{per_step * n_steps / value:.2f}s/repeat) | {R} replicas x "
        f"(span {span} replayed + {Br} reads) = {per_step} dispatches/step "
        f"| spread {spread_pct:.1f}% {[f'{v:.4g}' for v in values]} | "
        f"attempts {len(attempts)}{' CONTENDED' if contended else ''} | "
        f"device={jax.devices()[0].device_kind}",
        file=sys.stderr,
    )


if __name__ == "__main__":
    main()
