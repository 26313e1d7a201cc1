"""chip_smoke.py's phases rehearsed on the CPU at the smoke preset: the
serve-and-check path through the launcher, the kernel-vs-oracle checks in
interpret mode, the one-replica-per-device fleet on four virtual CPU
devices, and the script's refusal to run without a TPU."""
import os
import subprocess
import sys

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from repro.configs import get_config  # noqa: E402

# the smoke preset, with a pool and workload small enough for the CPU
SMOKE = dict(smoke=True, prompt_len=32, min_prompt_len=8, max_new_tokens=6,
             page_size=8, max_active=8, raw_rate=4, horizon=6, num_pages=64)


def _env(**extra):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env.update(extra)
    return env


def test_serve_and_check_smoke():
    res = chip_smoke.serve_and_check(chip_smoke.Spec(**SMOKE))
    assert res["served"] > 0
    assert res["exact"] + res["near_ties"] == res["served"]
    assert res["dispatches_per_slot"] <= 1.0     # one mixed dispatch a slot


def test_check_kernels_smoke_interpret():
    cfg = get_config("granite-3-2b", smoke=True)
    errs = chip_smoke.check_kernels(cfg, impl="interpret", seq=64, block=32,
                                    page_size=8)
    assert set(errs) == {"paged", "paged_int8", "chunk", "flash"}
    assert max(errs.values()) <= chip_smoke.KERNEL_TOL[
        chip_smoke.jnp.dtype(cfg.dtype)]


def test_fleet_check_one_replica_per_cpu_device():
    code = ("import jax, chip_smoke\n"
            f"spec = chip_smoke.Spec(**{SMOKE!r})\n"
            "res = chip_smoke.fleet_check(spec, jax.devices()[:4])\n"
            "assert sum(res['served_per_replica']) == res['requests'] > 0\n"
            "assert all(n > 0 for n in res['served_per_replica']), res\n"
            "print('FLEET_OK', res['served_per_replica'])\n")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=900, cwd=ROOT,
        env=_env(JAX_PLATFORMS="cpu",
                 XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert out.returncode == 0, out.stdout + out.stderr
    assert "FLEET_OK" in out.stdout


def test_chip_smoke_refuses_cpu():
    out = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                         capture_output=True, text=True, timeout=300,
                         env=_env(JAX_PLATFORMS="cpu"))
    assert out.returncode != 0
    assert "needs a TPU" in out.stderr
    assert '"ok"' not in out.stdout             # no result line
