#!/usr/bin/env python
"""Log microbench: raw append throughput, no replay (`benches/log.rs`).

Two engines:
- device: jitted `log_append` chains on the TPU ring (the batched
  reserve-then-write path), counting appended entries/sec;
- native: the C++ MPMC ring's CAS-reserve path under real threads
  (`nr_bench_log_append`).

Like the reference (12 GiB log, GC disabled by reset, `benches/log.rs:
48-79`), GC never engages: the device loop resets logical cursors between
chunks; the native loop pins the chaser's ltail to tail.
"""

import time

from common import base_parser, finish_args

import jax
import jax.numpy as jnp

from node_replication_tpu.utils.fence import fence


def device_append_bench(capacity: int, batch: int, duration_s: float,
                        chain: int = 64) -> float:
    from node_replication_tpu.core.log import (
        LogSpec, log_append, log_init,
    )

    spec = LogSpec(capacity=capacity, n_replicas=1, gc_slack=batch)
    log = log_init(spec)
    opc = jnp.ones((batch,), jnp.int32)
    args = jnp.zeros((batch, 3), jnp.int32)

    @jax.jit
    def chain_append(log):
        def body(lg, _):
            return log_append(spec, lg, opc, args, batch), 0

        log, _ = jax.lax.scan(body, log, None, length=chain)
        # reset the cursor so the ring never trips capacity accounting
        return log._replace(tail=jnp.zeros((), jnp.int64))

    log = chain_append(log)  # compile
    fence(log)
    # Amortize the fence: one D2H readback costs a host round trip,
    # so fencing every chain would measure the RTT, not the appends.
    # Dispatch k chains per fence and grow k until a fenced round is
    # RTT-dominated no more (>= ~0.5s).
    n = 0
    k = 1
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < duration_s:
        r0 = time.perf_counter()
        for _ in range(k):
            log = chain_append(log)
        fence(log)
        n += k * chain * batch
        if time.perf_counter() - r0 < 0.5:
            k *= 2
    return n / (time.perf_counter() - t0)


def main():
    p = base_parser("log append microbench")
    p.add_argument("--capacity", type=int, default=1 << 20)
    p.add_argument("--native-threads", type=int, nargs="+",
                   default=[1, 2, 4])
    args = finish_args(p.parse_args())

    for batch in args.batch:
        rate = device_append_bench(args.capacity, batch, args.duration)
        print(f">> log/device batch={batch}: {rate / 1e6:.2f} M appends/s")

    from node_replication_tpu.native.engine import bench_log_append

    for t in args.native_threads:
        for batch in args.batch:
            total = bench_log_append(
                args.capacity, t, batch, int(args.duration * 1000)
            )
            print(f">> log/native threads={t} batch={batch}: "
                  f"{total / args.duration / 1e6:.2f} M appends/s")


if __name__ == "__main__":
    main()
