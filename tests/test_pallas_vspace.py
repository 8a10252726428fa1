"""VSpace Pallas replay kernel tests (interpret mode on CPU).

Differential contract: the span kernels (flat + 4-level radix) must agree
BIT-identically with the sequential `apply_write` fold — responses and
final state — across adversarial windows: span overlaps, wrapped negative
vpages (flat), table teardown epochs (radix), NOOP padding, unknown
opcodes. `NR_TPU_SMOKE=1` additionally compiles and checks the Mosaic
lowering on real hardware.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from node_replication_tpu.core.log import LogSpec, log_init
from node_replication_tpu.core.replica import replicate_state
from node_replication_tpu.core.step import make_step
from node_replication_tpu.models import make_vspace, make_vspace_radix
from node_replication_tpu.ops.encoding import apply_write
from node_replication_tpu.ops.pallas_vspace import (
    make_pallas_vspace_step,
    make_vspace_replay,
    model_view,
    pallas_vspace_state,
)


def fold(d, state, opcodes, args):
    step = jax.jit(lambda s, o, a: apply_write(d, s, o, a))
    resps = []
    for i in range(len(opcodes)):
        state, r = step(state, opcodes[i], args[i])
        resps.append(int(r))
    return state, resps


def run_kernel(d, n_pages, max_span, radix, model_state, opcodes, args, R=3):
    replay = make_vspace_replay(
        n_pages, R, len(opcodes), max_span, radix, interpret=True
    )
    st = pallas_vspace_state(n_pages, R, radix, model_state)
    if radix:
        pt, pd, pdpt, pml4, resps = replay(
            opcodes, args, st["pt"], st["pd"], st["pdpt"], st["pml4"]
        )
        st = {"pt": pt, "pd": pd, "pdpt": pdpt, "pml4": pml4}
    else:
        frames, resps = replay(opcodes, args, st["frames"])
        st = {"frames": frames}
    return model_view(st, n_pages, radix), resps


class TestFlatKernel:
    @pytest.mark.parametrize("seed", [0, 1])
    def test_matches_sequential_fold(self, seed):
        K, S, W = 300, 5, 48
        d = make_vspace(K, max_span=S)
        rng = np.random.default_rng(seed)
        opcodes = jnp.asarray(
            rng.choice([0, 1, 2, 9], size=W, p=[0.1, 0.5, 0.3, 0.1]),
            jnp.int32,
        )
        # negative vpages wrap through the mod → split spans in-kernel
        args = jnp.asarray(
            np.stack([rng.integers(-4, K + 4, W), rng.integers(0, 50, W),
                      rng.integers(-1, S + 3, W)], axis=1),
            jnp.int32,
        )
        st0 = d.init_state()
        st0["frames"] = st0["frames"].at[::5].set(7)
        ref_state, ref_resps = fold(d, st0, opcodes, args)
        got, resps = run_kernel(d, K, S, False, st0, opcodes, args)
        # responses are the single canonical copy (lock-step invariant)
        assert [int(x) for x in resps] == ref_resps
        for r in range(got["frames"].shape[0]):
            np.testing.assert_array_equal(
                np.asarray(got["frames"][r]), np.asarray(ref_state["frames"])
            )


class TestRadixKernel:
    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_matches_sequential_fold(self, seed):
        P, S, W = 1500, 20, 64
        d = make_vspace_radix(P, max_span=S)
        rng = np.random.default_rng(seed)
        opcodes = jnp.asarray(
            rng.choice([0, 1, 2, 3, 4, 9], size=W,
                       p=[0.06, 0.3, 0.14, 0.25, 0.2, 0.05]),
            jnp.int32,
        )
        args = jnp.asarray(
            np.stack([rng.integers(0, 2 * P, W), rng.integers(-2, 60, W),
                      rng.integers(-1, S + 3, W)], axis=1),
            jnp.int32,
        )
        st0 = d.init_state()
        st0["pt"] = st0["pt"].at[10:40].set(5).at[1100:1130].set(9)
        st0["pd"] = st0["pd"].at[0].set(True).at[1].set(True)
        st0["pdpt"] = st0["pdpt"].at[0].set(True)
        st0["pml4"] = st0["pml4"].at[0].set(True)
        ref_state, ref_resps = fold(d, st0, opcodes, args)
        got, resps = run_kernel(d, P, S, True, st0, opcodes, args)
        assert [int(x) for x in resps] == ref_resps
        for r in range(got["pt"].shape[0]):
            for k in ("pt", "pd", "pdpt", "pml4"):
                np.testing.assert_array_equal(
                    np.asarray(got[k][r]), np.asarray(ref_state[k]), k
                )


class TestPallasVspaceStep:
    def test_step_matches_scan_step(self):
        R, Bw, Br, P, S, STEPS = 3, 4, 2, 1100, 8, 4
        d = make_vspace_radix(P, max_span=S)
        spec = LogSpec(capacity=1 << 10, n_replicas=R, gc_slack=32)
        rng = np.random.default_rng(5)
        scan_step = make_step(d, spec, Bw, Br, jit=False, combined=False)
        pl_step = make_pallas_vspace_step(
            P, spec, Bw, Br, S, radix=True, interpret=True, jit=False
        )
        log_a, st_a = log_init(spec), replicate_state(d.init_state(), R)
        log_b = log_init(spec)
        st_b = pallas_vspace_state(P, R, True, d.init_state())
        for _ in range(STEPS):
            wr_opc = jnp.asarray(
                rng.choice([0, 1, 2, 3, 4], size=(R, Bw)), jnp.int32
            )
            wr_args = jnp.asarray(
                np.stack([rng.integers(0, P, (R, Bw)),
                          rng.integers(0, 60, (R, Bw)),
                          rng.integers(0, S + 1, (R, Bw))], axis=-1),
                jnp.int32,
            )
            rd_opc = jnp.asarray(
                rng.choice([1, 2, 3], size=(R, Br)), jnp.int32
            )
            rd_args = jnp.asarray(
                np.stack([rng.integers(0, P, (R, Br)),
                          rng.integers(1, 9, (R, Br)),
                          np.zeros((R, Br))], axis=-1),
                jnp.int32,
            )
            log_a, st_a, wr_a, rd_a = scan_step(
                log_a, st_a, wr_opc, wr_args, rd_opc, rd_args
            )
            log_b, st_b, wr_b, rd_b = pl_step(
                log_b, st_b, wr_opc, wr_args, rd_opc, rd_args
            )
            np.testing.assert_array_equal(np.asarray(wr_a), np.asarray(wr_b))
            np.testing.assert_array_equal(np.asarray(rd_a), np.asarray(rd_b))
        view = model_view(st_b, P, True)
        for k in ("pt", "pd", "pdpt", "pml4"):
            np.testing.assert_array_equal(
                np.asarray(view[k]), np.asarray(st_a[k]), k
            )
        for name in ("tail", "ctail"):
            assert int(getattr(log_a, name)) == int(getattr(log_b, name))


class TestPlanStep:
    """Pallas-planned step (r5): canonical-replica kernel plan + vmapped
    model-side window_merge. Bit-exact vs the generic scan step across
    multi-step drives — states, write resps, read resps, cursors."""

    @pytest.mark.parametrize("seed", [0, 1])
    def test_radix_plan_step_matches_scan_step(self, seed):
        from node_replication_tpu.ops.pallas_vspace import (
            make_pallas_vspace_plan_step,
        )

        R, Bw, Br, P, S, STEPS = 3, 4, 2, 1100, 8, 4
        d = make_vspace_radix(P, max_span=S)
        spec = LogSpec(capacity=1 << 10, n_replicas=R, gc_slack=32)
        rng = np.random.default_rng(seed)
        scan_step = make_step(d, spec, Bw, Br, jit=False, combined=False)
        plan_step = make_pallas_vspace_plan_step(
            P, spec, Bw, Br, S, radix=True, dispatch=d, interpret=True,
            jit=False,
        )
        log_a, st_a = log_init(spec), replicate_state(d.init_state(), R)
        log_b, st_b = log_init(spec), replicate_state(d.init_state(), R)
        for _ in range(STEPS):
            wr_opc = jnp.asarray(
                rng.choice([0, 1, 2, 3, 4], size=(R, Bw)), jnp.int32
            )
            wr_args = jnp.asarray(
                np.stack([rng.integers(0, P, (R, Bw)),
                          rng.integers(0, 60, (R, Bw)),
                          rng.integers(0, S + 1, (R, Bw))], axis=-1),
                jnp.int32,
            )
            rd_opc = jnp.asarray(
                rng.choice([1, 2, 3], size=(R, Br)), jnp.int32
            )
            rd_args = jnp.asarray(
                np.stack([rng.integers(0, P, (R, Br)),
                          rng.integers(1, 9, (R, Br)),
                          np.zeros((R, Br))], axis=-1),
                jnp.int32,
            )
            log_a, st_a, wr_a, rd_a = scan_step(
                log_a, st_a, wr_opc, wr_args, rd_opc, rd_args
            )
            log_b, st_b, wr_b, rd_b = plan_step(
                log_b, st_b, wr_opc, wr_args, rd_opc, rd_args
            )
            np.testing.assert_array_equal(np.asarray(wr_a),
                                          np.asarray(wr_b))
            np.testing.assert_array_equal(np.asarray(rd_a),
                                          np.asarray(rd_b))
        for k in ("pt", "pd", "pdpt", "pml4"):
            np.testing.assert_array_equal(
                np.asarray(st_b[k]), np.asarray(st_a[k]), k
            )
        for name in ("tail", "ctail", "head"):
            assert int(getattr(log_a, name)) == int(getattr(log_b, name))
        np.testing.assert_array_equal(
            np.asarray(log_a.ltails), np.asarray(log_b.ltails)
        )

    def test_flat_plan_step_matches_scan_step(self):
        from node_replication_tpu.models import make_vspace
        from node_replication_tpu.ops.pallas_vspace import (
            make_pallas_vspace_plan_step,
        )

        R, Bw, Br, P, S, STEPS = 2, 4, 2, 1024, 8, 4
        d = make_vspace(P, max_span=S)
        spec = LogSpec(capacity=1 << 10, n_replicas=R, gc_slack=32)
        rng = np.random.default_rng(3)
        scan_step = make_step(d, spec, Bw, Br, jit=False, combined=False)
        plan_step = make_pallas_vspace_plan_step(
            P, spec, Bw, Br, S, radix=False, dispatch=d, interpret=True,
            jit=False,
        )
        log_a, st_a = log_init(spec), replicate_state(d.init_state(), R)
        log_b, st_b = log_init(spec), replicate_state(d.init_state(), R)
        for _ in range(STEPS):
            wr_opc = jnp.asarray(
                rng.choice([0, 1, 2], size=(R, Bw)), jnp.int32
            )
            wr_args = jnp.asarray(
                np.stack([rng.integers(-3, P, (R, Bw)),
                          rng.integers(0, 60, (R, Bw)),
                          rng.integers(0, S + 1, (R, Bw))], axis=-1),
                jnp.int32,
            )
            rd_opc = jnp.asarray(
                rng.choice([1, 2], size=(R, Br)), jnp.int32
            )
            rd_args = jnp.asarray(
                np.stack([rng.integers(0, P, (R, Br)),
                          rng.integers(1, 9, (R, Br)),
                          np.zeros((R, Br))], axis=-1),
                jnp.int32,
            )
            log_a, st_a, wr_a, rd_a = scan_step(
                log_a, st_a, wr_opc, wr_args, rd_opc, rd_args
            )
            log_b, st_b, wr_b, rd_b = plan_step(
                log_b, st_b, wr_opc, wr_args, rd_opc, rd_args
            )
            np.testing.assert_array_equal(np.asarray(wr_a),
                                          np.asarray(wr_b))
            np.testing.assert_array_equal(np.asarray(rd_a),
                                          np.asarray(rd_b))
        np.testing.assert_array_equal(
            np.asarray(st_b["frames"]), np.asarray(st_a["frames"])
        )


@pytest.mark.skipif(
    not os.environ.get("NR_TPU_SMOKE"),
    reason="hardware smoke (set NR_TPU_SMOKE=1 on a real TPU; the "
           "suite itself runs on the CPU)",
)
class TestHardwareSmoke:
    def test_radix_kernel_on_device(self):
        # subprocess: the suite's conftest forces jax_platforms=cpu, so
        # the hardware probe needs a fresh interpreter on the default
        # (TPU) platform
        import subprocess
        import sys

        code = """
import numpy as np, jax, jax.numpy as jnp
assert jax.devices()[0].platform != "cpu", jax.devices()
from node_replication_tpu.models import make_vspace_radix
from node_replication_tpu.ops.encoding import apply_write
from node_replication_tpu.ops.pallas_vspace import (
    make_vspace_replay, pallas_vspace_state, model_view)
P, S, W, R = 1 << 14, 64, 256, 4
d = make_vspace_radix(P, max_span=S)
rng = np.random.default_rng(0)
opc = jnp.asarray(rng.choice([1, 2, 3, 4], size=W), jnp.int32)
args = jnp.asarray(np.stack([rng.integers(0, P, W),
    rng.integers(0, 1000, W), 1 + rng.integers(0, S, W)], axis=1),
    jnp.int32)
st0 = d.init_state()
step = jax.jit(lambda s, o, a: apply_write(d, s, o, a))
ref, rresp = st0, []
for i in range(W):
    ref, r = step(ref, opc[i], args[i])
    rresp.append(int(r))
replay = jax.jit(make_vspace_replay(P, R, W, S, radix=True))
st = pallas_vspace_state(P, R, True, st0)
pt, pd, pdpt, pml4, resps = replay(
    opc, args, st["pt"], st["pd"], st["pdpt"], st["pml4"])
view = model_view({"pt": pt, "pd": pd, "pdpt": pdpt, "pml4": pml4}, P, True)
for k in ("pt", "pd", "pdpt", "pml4"):
    for r in range(R):
        np.testing.assert_array_equal(
            np.asarray(view[k][r]), np.asarray(ref[k]), k)
assert [int(x) for x in np.asarray(resps)] == rresp
print("vspace-pallas-on-tpu OK", jax.devices()[0].device_kind)
"""
        out = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=560, cwd="/root/repo",
        )
        assert "vspace-pallas-on-tpu OK" in out.stdout, (
            out.stdout + out.stderr
        )
