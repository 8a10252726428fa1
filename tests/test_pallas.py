"""Pallas replay kernel tests (interpret mode on CPU).

Differential contract: the kernel must agree with the generic scan path
(`make_step`) on responses and final state for random put/remove/get
streams.
"""

import os

import jax.numpy as jnp
import numpy as np
import pytest

from node_replication_tpu.core.log import LogSpec, log_init
from node_replication_tpu.core.replica import replicate_state
from node_replication_tpu.core.step import make_step
from node_replication_tpu.models import make_hashmap
from node_replication_tpu.ops.pallas_replay import (
    make_hashmap_replay,
    make_pallas_step,
    pallas_hashmap_state,
)


class TestReplayKernel:
    def test_put_remove_semantics(self):
        R, W, K = 4, 8, 130  # K padded to 256 internally
        replay = make_hashmap_replay(K, R, W, tile_r=2, interpret=True)
        opc = jnp.asarray([1, 1, 2, 2, 1, 0, 1, 2], jnp.int32)
        args = jnp.zeros((W, 4), jnp.int32)
        #            put k5=9  put k5=7  rm k5   rm k5  put k129=3 noop put k1=4 rm k1
        keys = [5, 5, 5, 5, 129, 0, 1, 1]
        vals = [9, 7, 0, 0, 3, 0, 4, 0]
        args = args.at[:, 0].set(jnp.asarray(keys, jnp.int32))
        args = args.at[:, 1].set(jnp.asarray(vals, jnp.int32))
        st = pallas_hashmap_state(K, R)
        values, present, resps = replay(
            opc, args[:, 0], args[:, 1], st["values"], st["present"]
        )
        v = np.asarray(values)
        p = np.asarray(present)
        r = np.asarray(resps)
        assert np.all(p[5, :] == 0)  # put,put,remove,remove → absent
        assert np.all(v[129, :] == 3) and np.all(p[129, :] == 1)
        assert np.all(p[1, :] == 0)
        # remove resps: first rm of k5 → was present(1); second rm → 0;
        # rm k1 → was present
        assert np.all(r[2, :] == 1)
        assert np.all(r[3, :] == 0)
        assert np.all(r[7, :] == 1)

    def test_kernel_matches_scan_step(self):
        R, Bw, Br, K = 8, 4, 2, 200
        spec = LogSpec(capacity=1 << 10, n_replicas=R, gc_slack=32)
        d = make_hashmap(K)
        scan_step = make_step(d, spec, Bw, Br, jit=False)
        pl_step = make_pallas_step(
            K, spec, Bw, Br, tile_r=2, interpret=True, jit=False
        )
        log_a, log_b = log_init(spec), log_init(spec)
        st_a = replicate_state(d.init_state(), R)
        st_b = pallas_hashmap_state(K, R)
        rng = np.random.default_rng(0)
        for s in range(4):
            wr_opc = jnp.asarray(
                rng.choice([1, 1, 2], (R, Bw)).astype(np.int32)
            )
            wr_args = jnp.zeros((R, Bw, 3), jnp.int32)
            wr_args = wr_args.at[..., 0].set(
                jnp.asarray(rng.integers(0, K, (R, Bw)), jnp.int32)
            )
            wr_args = wr_args.at[..., 1].set(
                jnp.asarray(rng.integers(1, 999, (R, Bw)), jnp.int32)
            )
            rd_opc = jnp.ones((R, Br), jnp.int32)
            rd_args = jnp.zeros((R, Br, 3), jnp.int32).at[..., 0].set(
                jnp.asarray(rng.integers(0, K, (R, Br)), jnp.int32)
            )
            log_a, st_a, wa, ra = scan_step(
                log_a, st_a, wr_opc, wr_args, rd_opc, rd_args
            )
            log_b, st_b, wb, rb = pl_step(
                log_b, st_b, wr_opc, wr_args, rd_opc, rd_args
            )
            np.testing.assert_array_equal(np.asarray(wa), np.asarray(wb))
            np.testing.assert_array_equal(np.asarray(ra), np.asarray(rb))
        np.testing.assert_array_equal(
            np.asarray(st_a["values"]), np.asarray(st_b["values"][:K, :]).T
        )
        np.testing.assert_array_equal(
            np.asarray(st_a["present"]).astype(np.int32),
            np.asarray(st_b["present"][:K, :]).T,
        )
        assert int(log_a.tail) == int(log_b.tail)
        assert int(log_a.ctail) == int(log_b.ctail)

    def test_uneven_replicas_pick_smaller_tile(self):
        # R=6 not divisible by 64: falls back to tile_r=2
        R, W, K = 6, 4, 64
        replay = make_hashmap_replay(K, R, W, tile_r=64, interpret=True)
        opc = jnp.ones((W,), jnp.int32)
        args = jnp.zeros((W, 4), jnp.int32).at[:, 0].set(3).at[:, 1].set(9)
        st = pallas_hashmap_state(K, R)
        values, present, _ = replay(
            opc, args[:, 0], args[:, 1], st["values"], st["present"]
        )
        assert np.all(np.asarray(values)[3, :] == 9)


class TestNegativeKeys:
    def test_negative_key_matches_generic_floored_mod(self):
        # ADVICE r1: lax.rem truncates toward zero; the kernel must floor
        # like the generic model's `%` or a negative key indexes a
        # negative VMEM row.
        R, W, K = 2, 4, 16
        replay = make_hashmap_replay(K, R, W, tile_r=2, interpret=True)
        opc = jnp.asarray([1, 1, 1, 0], jnp.int32)
        keys = jnp.asarray([-1, -16, 3, 0], jnp.int32)
        vals = jnp.asarray([111, 222, 333, 0], jnp.int32)
        st = pallas_hashmap_state(K, R)
        values, present, _ = replay(
            opc, keys, vals, st["values"], st["present"]
        )
        v = np.asarray(values)
        # floored: -1 % 16 = 15, -16 % 16 = 0
        assert np.all(v[15, :] == 111)
        assert np.all(v[0, :] == 222)
        assert np.all(v[3, :] == 333)


class TestHardwareSmoke:
    @pytest.mark.skipif(
        os.environ.get("NR_TPU_SMOKE") != "1",
        reason="set NR_TPU_SMOKE=1 to run the non-interpret Mosaic "
               "lowering on real TPU hardware (needs the chip; the suite "
               "itself runs on forced-CPU)",
    )
    def test_kernel_compiles_and_runs_on_tpu(self):
        # subprocess: the suite's conftest forces jax_platforms=cpu, so
        # the hardware probe needs a fresh interpreter on the default
        # (TPU) platform
        import subprocess
        import sys

        code = """
import numpy as np, jax, jax.numpy as jnp
assert jax.devices()[0].platform != "cpu", jax.devices()
from node_replication_tpu.ops.pallas_replay import make_hashmap_replay
K, R, W = 64, 256, 32
replay = make_hashmap_replay(K, R, W, interpret=False)
opc = jnp.asarray([1, 2] * (W // 2), jnp.int32)
keys = jnp.arange(W, dtype=jnp.int32) % K
vals = 100 + jnp.arange(W, dtype=jnp.int32)
values = jnp.zeros((K, R), jnp.int32)
present = jnp.zeros((K, R), jnp.int32)
values, present, resps = replay(opc, keys, vals, values, present)
v = np.asarray(values)
# even entries PUT key i val 100+i; odd entries REMOVE key i
assert np.all(v[0, :] == 100)
assert np.all(np.asarray(present)[1, :] == 0)
print("pallas-on-tpu OK", jax.devices()[0].device_kind)
"""
        out = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True, text=True, timeout=300,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        assert out.returncode == 0, out.stderr[-2000:]
        assert "pallas-on-tpu OK" in out.stdout
