"""CPU rehearsal of `chip_smoke.py`: its phase functions at tiny sizes
(interpret-mode kernels, the suite's forced CPU), each checking its
results against the host oracle — and `main()` refusing the CPU."""

import json
import os
import sys

import pytest

import jax

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
import chip_smoke  # noqa: E402


def test_flagship_phase():
    res = chip_smoke.phase_flagship(R=32, K=50, steps=3, seed=1)
    assert res["tier"] == "combined" and res["compile_s"] > 0


def test_served_phase():
    res = chip_smoke.phase_served(n_keys=2048, keyspace=2000,
                                  n_replicas=4, clients=3,
                                  ops_per_client=12, seed=1)
    assert res["keys_put"] > 0 and res["replicas"] == 4


def test_fused_phase():
    res = chip_smoke.phase_fused(K=40, R_max=8, batch=16, batches=3,
                                 seed=1)
    assert res["tier"] == "pallas_fused" and res["fused_rounds"] == 3
    assert res["interpret"] is True  # the CPU; chip runs must be False


def test_pallas_step_phase():
    res = chip_smoke.phase_pallas_step(R=128, K=32, steps=2, seed=1,
                                       interpret=True)
    assert res["interpret"] is True


def test_four_chips_phase():
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    res = chip_smoke.phase_four_chips(R=16, K=40, batch=16, batches=2,
                                      seed=1)
    assert res["auto_devices"] == res["fused_devices"] == 4
    assert res["fused_tiers"] == ["mesh_fused"]


def test_largest_fused_r_is_the_vmem_bound():
    # K=10,000: the [K, tile] state blocks fit the VMEM budget only at
    # small R (one full-width tile); the probe uses the chip's check
    assert chip_smoke.largest_fused_r(10_000, 4096, 64) == 32
    assert chip_smoke.largest_fused_r(1024, 4096, 256) == 4096


def test_oracle_mismatch_fails(monkeypatch):
    from node_replication_tpu.sim import oracle

    class Off(oracle.HashmapOracle):
        def apply(self, op):
            return super().apply(op) + 1

    monkeypatch.setattr(oracle, "HashmapOracle", Off)
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.phase_flagship(R=8, K=10, steps=1)


def test_main_refuses_the_cpu(capsys):
    rc = chip_smoke.main([])
    out = capsys.readouterr().out
    assert rc != 0
    assert '"ok": true' not in out
    for line in out.splitlines():
        assert not json.loads(line).get("ok")


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_lands_in_one_place(tmp_path, from_env):
    """`setup_compile_cache`: JAX_COMPILATION_CACHE_DIR when set, else
    `<checkout>/.jax_cache` — run from a copy of the helper laid out as
    a checkout under tmp_path, in a child process (it sets global jax
    config)."""
    import shutil
    import subprocess

    from node_replication_tpu.utils import compile_cache

    checkout = tmp_path / "checkout"
    utils = checkout / "node_replication_tpu" / "utils"
    utils.mkdir(parents=True)
    for init in (utils.parent / "__init__.py", utils / "__init__.py"):
        init.write_text("")
    shutil.copy(compile_cache.__file__, utils / "compile_cache.py")
    env_dir = tmp_path / "env_cache"
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=str(checkout), JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    code = (
        "import jax, jax.numpy as jnp\n"
        "from node_replication_tpu.utils.compile_cache import "
        "setup_compile_cache\n"
        "print(setup_compile_cache())\n"
        "jax.jit(lambda x: x * 2 + 1)(jnp.arange(8)).block_until_ready()\n"
    )
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120,
                         cwd=tmp_path)
    assert out.returncode == 0, out.stderr[-2000:]
    in_checkout = checkout / ".jax_cache"
    want, other = ((env_dir, in_checkout) if from_env
                   else (in_checkout, env_dir))
    assert out.stdout.strip() == str(want)
    assert want.is_dir() and any(want.iterdir())
    assert not other.exists()
