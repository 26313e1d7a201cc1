"""End-to-end behaviour tests: sharding rules + tiny-mesh lower/compile.

The full 512-device dry-run is exercised by ``python -m repro.launch.dryrun``
(see EXPERIMENTS.md §Dry-run); here we verify the same code paths lower and
*execute* on a small forced-host mesh so CI catches sharding regressions.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config, list_archs
from repro.launch import shardings as SH
from repro.launch.shapes import SHAPES, cache_len_for, input_specs
from repro.models import model as M

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_param_specs_cover_tree_and_divide():
    """Every spec leaf matches its param rank and only shards divisible dims."""
    mesh = jax.make_mesh((1, 1), ("data", "model"))

    class FakeMesh:  # 16-way checker without 256 devices
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}

    for arch in list_archs():
        cfg = get_config(arch)
        aparams = M.abstract_params(cfg)
        specs = SH.param_specs(aparams, cfg, FakeMesh())
        flat_p = jax.tree.leaves(aparams)
        flat_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
        assert len(flat_p) == len(flat_s)
        for p, s in zip(flat_p, flat_s, strict=True):
            assert len(s) <= len(p.shape), (arch, p.shape, s)
            for dim, ax in zip(p.shape, tuple(s) + (None,) * 8, strict=False):
                if ax == "model":
                    assert dim % 16 == 0, (arch, p.shape, s)


def test_decode_state_specs_shard_cache_seq():
    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}

    cfg = get_config("qwen3-8b")
    case = SHAPES["decode_32k"]
    from repro.launch.shapes import decode_inputs

    state, toks = decode_inputs(cfg, case)
    specs = SH.decode_state_specs(state, cfg, FakeMesh(), case.global_batch)
    def norm(ax):
        return (ax,) if isinstance(ax, str) else tuple(ax)

    k_spec = specs.caches[0].k
    assert norm(k_spec[1]) == ("data",)       # batch
    assert norm(k_spec[2]) == ("model",)      # cache sequence stripe
    assert norm(specs.pos[0]) == ("data",)


@pytest.mark.parametrize("arch", ["granite-3-2b", "mamba2-130m", "olmoe-1b-7b"])
def test_tiny_mesh_train_step_executes(arch):
    """Lower AND run a sharded train step on a 1x1 mesh (semantics check)."""
    cfg = get_config(arch, smoke=True)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    from repro.training import AdamW, make_train_step

    opt = AdamW(warmup=1, total_steps=10)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    aparams = jax.eval_shape(lambda: params)
    pspecs = SH.param_specs(aparams, cfg, mesh)
    named = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                         is_leaf=lambda x: isinstance(x, P))
    step = make_train_step(cfg, opt)
    B, S = 4, 32
    batch = {
        "tokens": jnp.zeros((B, S), jnp.int32),
        "targets": jnp.ones((B, S), jnp.int32),
    }
    with mesh:
        jitted = jax.jit(step, in_shardings=(named, None, None))
        p2, o2, metrics = jitted(params, opt.init(params), batch)
    assert np.isfinite(float(metrics["loss"]))


def test_dryrun_entrypoint_smoke():
    """The real dryrun module (512 host devices) runs one small case."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "mamba2-130m",
         "--shape", "decode_32k", "--out", "/tmp/test_dryrun_smoke.jsonl"],
        capture_output=True, text=True, timeout=1200, env=env,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    assert "dry-run complete: 1 ok, 0 failed" in out.stdout


def test_make_production_mesh_is_lazy_import():
    """Importing mesh.py, the serving launcher or the runtime must not
    initialize jax devices (module hygiene): a process that imports the
    package would otherwise hold the accelerator."""
    code = (
        "import repro.launch.mesh, repro.launch.serve, repro.runtime, jax\n"
        "assert not jax._src.xla_bridge._backends, 'devices initialized at import'\n"
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120, env=env)
    assert out.returncode == 0, out.stdout + out.stderr
