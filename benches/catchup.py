#!/usr/bin/env python
"""Catch-up bench: divergent-cursor recovery, scan vs combined.

In the reference, catch-up IS the hot loop — a lagging replica replays
through the same `exec` as everyone (`nr/src/log.rs:473-524`), at full
speed. r4's combined engines only covered the lock-step fused step, so
every divergent-cursor path (sync, checkpoint recovery, GC-stall
release) inherited the sequential scan. r5's `log_catchup_all` routes
them through per-replica `window_apply`; this bench measures the gap.

Scenario: R replicas share a log holding W pending entries; the fleet's
cursors are staggered (replica 0 fully dormant — the GC-stall shape of
`__graft_entry__.dryrun_multichip` scenario B). Measure wall-clock to
full convergence (`min(ltails) == tail`, fenced) for each engine,
replaying in `--window`-sized rounds.

One row per engine lands in scaleout_benchmarks.csv: `ops` = entries
caught up (client view: W per replica behind), `dispatches` = total
entries replayed across the fleet.
"""

import os
import time

import numpy as np

from common import base_parser, finish_args


def main():
    p = base_parser("divergent-cursor catch-up: scan vs combined")
    p.add_argument("--pending", type=int, default=32768,
                   help="log entries pending at the start of catch-up")
    p.add_argument("--window", type=int, default=8192,
                   help="entries replayed per compiled round")
    p.add_argument("--keys", type=int, default=None)
    p.add_argument("--scan-window", type=int, default=None,
                   help="smaller per-round window for the scan engine "
                        "(its per-entry lax.scan compiles slowly at "
                        "large windows); defaults to --window")
    args = finish_args(p.parse_args())
    keys = args.keys or 10_000

    import jax
    import jax.numpy as jnp

    from node_replication_tpu import LogSpec, log_init
    from node_replication_tpu.core.log import (
        log_append,
        log_catchup_all,
        log_exec_all,
    )
    from node_replication_tpu.core.replica import replicate_state
    from node_replication_tpu.harness.mkbench import (
        SCALEOUT_CSV,
        _append_csv,
        _CSV_FIELDS,
    )
    from node_replication_tpu.models import HM_PUT, make_hashmap
    from node_replication_tpu.utils.fence import fence

    R = args.replicas[0]
    W = args.pending
    d = make_hashmap(keys)
    cap = 1 << (2 * W - 1).bit_length()  # ring holds the window + slack
    spec = LogSpec(capacity=cap, n_replicas=R, arg_width=3,
                   gc_slack=min(8192, W))
    rng = np.random.default_rng(args.seed)
    opc = jnp.full((W,), HM_PUT, jnp.int32)
    ag = np.zeros((W, 3), np.int32)
    ag[:, 0] = rng.integers(0, keys, W)
    ag[:, 1] = rng.integers(1, 1 << 30, W)
    ag = jnp.asarray(ag)
    # staggered dormancy: replica r starts (R-r)/R of the window behind
    ltails0 = jnp.asarray([(r * W) // R for r in range(R)], jnp.int64)

    rows = []
    for engine, fn in (("scan", log_exec_all),
                       ("combined", log_catchup_all)):
        win = (args.scan_window or args.window) if engine == "scan" \
            else args.window
        # no donation: inputs are reused for warmup then the timed run.
        # Recovery semantics: no response consumers (need_resps=False on
        # the combined engine; the scan computes them inline anyway)
        step = jax.jit(
            lambda lg, st, fn=fn, win=win: (
                fn(spec, d, lg, st, win, need_resps=False)
                if fn is log_catchup_all
                else fn(spec, d, lg, st, win)
            )
        )
        log0 = log_init(spec)
        log0 = log_append(spec, log0, opc, ag, W)
        log0 = log0._replace(ltails=ltails0)
        states0 = replicate_state(d.init_state(), R)
        wl, ws, _ = step(log0, states0)  # warmup compile
        fence(wl, ws)
        log, states = log0, states0
        # the most dormant replica starts at 0 and advances `win` per
        # round, so convergence takes exactly ceil(W/win) rounds — chain
        # them and fence ONCE (a per-round readback would add a host
        # round trip per round and drown the fast engine)
        rounds = -(-W // win)
        t0 = time.perf_counter()
        for _ in range(rounds):
            log, states, _ = step(log, states)
        lt = np.asarray(log.ltails)  # data-dependent D2H: true barrier
        dt = time.perf_counter() - t0
        assert int(lt.min()) >= W, f"{engine} failed to converge: {lt}"
        behind = sum(W - int(x) for x in np.asarray(ltails0))
        print(f">> catchup/{engine} R={R} pending={W} window={win}: "
              f"converged in {rounds} rounds, {dt * 1e3:.1f} ms "
              f"({behind / dt / 1e6:.2f} M dispatches/s caught up)")
        rows.append({
            "name": f"catchup{keys}/{engine}", "rs": R, "ls": 1,
            "tm": "none", "batch": win, "threads": R,
            "duration": round(dt, 4), "thread_id": -1, "core_id": -1,
            "second": -1, "ops": W, "dispatches": behind,
            "wr_eff": 100,
        })
    _append_csv(os.path.join(args.out_dir, SCALEOUT_CSV), _CSV_FIELDS,
                rows)


if __name__ == "__main__":
    main()
