import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "").replace(
        "--xla_force_host_platform_device_count=512", ""
    )
).strip()

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh).

For each combination this builds abstract inputs (ShapeDtypeStruct — no
allocation), the sharding specs from repro.launch.shardings, and runs

    with mesh:
        lowered  = jax.jit(step, in_shardings=..., out_shardings=...).lower(...)
        compiled = lowered.compile()
        compiled.memory_analysis() / cost_analysis() / HLO text

recording the roofline terms via repro.roofline. Results stream to a JSONL
file consumed by EXPERIMENTS.md tables and benchmarks/run.py.

Usage:
  python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--out results.jsonl]
"""
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config, list_archs
from repro.launch import shardings as SH
from repro.launch.mesh import make_production_mesh
from repro.launch.shapes import SHAPES, ShapeCase, cache_len_for, effective_window, input_specs
from repro.models import model as M
from repro.roofline.analysis import analytic_workload, build_roofline
from repro.sharding.ctx import activation_sharding
from repro.training.optimizer import AdamW


def _named(mesh, tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), tree, is_leaf=lambda x: isinstance(x, P)
    )


def default_microbatch(cfg) -> int:
    """Gradient-accumulation depth for train_4k: big dense models need the
    activation cut to fit v5e HBM (adopted §Perf iteration A1: mb16 for the
    20B class; past that the floor is gradient storage, not activations)."""
    n = cfg.param_count()
    if n > 10e9:
        return 16
    if n > 2e9:
        return 4
    return 1


def lower_case(arch: str, shape: str, mesh, opt=None, microbatch: int | None = None,
               overrides: dict | None = None, moe_parallel: bool = False,
               prefill_block: int | None = None):
    """Lower + compile one (arch, shape) on the given mesh.

    overrides: ModelConfig.replace(**overrides) — the §Perf hillclimb hook
    (e.g. {"cache_dtype": "float8_e4m3fn"}, {"remat_policy": "dots"}).
    moe_parallel: install the expert-parallel (E,C,D) sharding constraint.
    Returns (lowered, compiled, meta dict).
    """
    cfg = get_config(arch)
    if overrides:
        cfg = cfg.replace(**overrides)
    case = SHAPES[shape]
    opt = opt or AdamW(total_steps=1000)
    microbatch = default_microbatch(cfg) if microbatch is None else microbatch
    aparams = M.abstract_params(cfg)
    pspecs = SH.param_specs(aparams, cfg, mesh)
    p_shard = _named(mesh, pspecs)
    act_ns = NamedSharding(mesh, SH.activation_spec(mesh, case.global_batch))
    moe_ns = NamedSharding(mesh, P("model", None, None)) if moe_parallel else None
    window = effective_window(cfg, case)

    with mesh, activation_sharding(act_ns, moe_ecd=moe_ns):
        if case.kind == "train":
            batch = input_specs(cfg, case)
            bspecs = SH.train_batch_specs(mesh, cfg, case.global_batch)
            ospecs = SH.zero1_specs(pspecs, aparams, mesh)
            aopt = jax.eval_shape(opt.init, aparams)

            from repro.training.train import make_train_step

            _step = make_train_step(cfg, opt, microbatch=microbatch)

            def train_step(params, opt_state, batch):
                params, opt_state, metrics = _step(params, opt_state, batch)
                return params, opt_state, metrics["loss"]

            # AdamState(step, mu, nu): step replicated; mu/nu get ZeRO-1 specs
            from repro.training.optimizer import AdamState

            opt_shardings = AdamState(
                step=NamedSharding(mesh, P()),
                mu=_named(mesh, ospecs),
                nu=_named(mesh, ospecs),
            )
            jf = jax.jit(
                train_step,
                in_shardings=(p_shard, opt_shardings, _named(mesh, bspecs)),
                out_shardings=(p_shard, opt_shardings, NamedSharding(mesh, P())),
                donate_argnums=(0, 1),  # params/opt_state update in place
            )
            lowered = jf.lower(aparams, aopt, batch)
        elif case.kind == "prefill":
            batch = input_specs(cfg, case)
            bspecs = SH.train_batch_specs(mesh, cfg, case.global_batch)
            bspecs.pop("targets", None)
            cache_len = cache_len_for(cfg, case)
            bb = prefill_block or None  # batch-slice only when explicitly set

            def prefill_step(params, batch):
                logits, state = M.prefill(params, batch, cfg, cache_len,
                                          shape_window=window, batch_block=bb)
                return jnp.argmax(logits, -1).astype(jnp.int32), state

            # CRITICAL (§Perf E'): shard the OUTPUT decode-state exactly like
            # serve_step's input state — without out_shardings XLA leaves the
            # built cache unsharded along S (12 GiB/dev for internlm2).
            state_shape = jax.eval_shape(prefill_step, aparams, batch)[1]
            sspecs = SH.decode_state_specs(state_shape, cfg, mesh, case.global_batch)
            t_shard = NamedSharding(mesh, SH.batch_spec(mesh, case.global_batch))
            jf = jax.jit(
                prefill_step,
                in_shardings=(p_shard, _named(mesh, bspecs)),
                out_shardings=(t_shard, _named(mesh, sspecs)),
            )
            lowered = jf.lower(aparams, batch)
        else:  # decode
            from repro.launch.shapes import decode_inputs

            state_shapes, toks = decode_inputs(cfg, case)
            sspecs = SH.decode_state_specs(state_shapes, cfg, mesh, case.global_batch)
            s_shard = _named(mesh, sspecs)
            t_shard = NamedSharding(mesh, SH.batch_spec(mesh, case.global_batch))

            def serve_step(params, state, toks):
                logits, state = M.decode_step(params, state, toks, cfg, shape_window=window)
                return jnp.argmax(logits, -1).astype(jnp.int32), state

            jf = jax.jit(
                serve_step,
                in_shardings=(p_shard, s_shard, t_shard),
                out_shardings=(t_shard, s_shard),
                donate_argnums=(1,),  # KV/state cache updates in place
            )
            lowered = jf.lower(aparams, state_shapes, toks)

        compiled = lowered.compile()
    return lowered, compiled, {"cfg": cfg, "case": case}


def run_case(arch: str, shape: str, multi_pod: bool = False,
             microbatch: int | None = None, overrides: dict | None = None,
             moe_parallel: bool = False, prefill_block: int | None = None,
             tag: str = "baseline") -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.size
    t0 = time.time()
    lowered, compiled, meta = lower_case(
        arch, shape, mesh, microbatch=microbatch, overrides=overrides,
        moe_parallel=moe_parallel, prefill_block=prefill_block,
    )
    dt = time.time() - t0
    cost = dict(compiled.cost_analysis() or {})
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    rf = build_roofline(meta["cfg"], meta["case"], n_chips, cost, hlo, mem)
    row = {
        "arch": arch,
        "shape": shape,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_chips": n_chips,
        "compile_s": round(dt, 1),
        "hlo_bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        **{k: (round(v, 6) if isinstance(v, float) else v) for k, v in rf.row().items()},
    }
    row["arg_bytes_per_dev"] = int(getattr(mem, "argument_size_in_bytes", 0))
    row["temp_bytes_per_dev"] = int(getattr(mem, "temp_size_in_bytes", 0))
    row["microbatch"] = (
        microbatch if microbatch is not None else default_microbatch(meta["cfg"])
    ) if SHAPES[shape].kind == "train" else 0
    row["tag"] = tag
    if overrides:
        row["overrides"] = overrides
    if moe_parallel:
        row["moe_parallel"] = True
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="dryrun_results.jsonl")
    args = ap.parse_args()

    archs = list_archs() if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    ok = fail = 0
    with open(args.out, "a") as f:
        for mp in meshes:
            for arch in archs:
                for shape in shapes:
                    tag = f"{arch} x {shape} x {'2x16x16' if mp else '16x16'}"
                    try:
                        row = run_case(arch, shape, multi_pod=mp)
                        f.write(json.dumps(row) + "\n")
                        f.flush()
                        ok += 1
                        print(
                            f"OK   {tag}: dominant={row['dominant']} "
                            f"c={row['compute_s']:.4g}s m={row['memory_s']:.4g}s "
                            f"x={row['collective_s']:.4g}s fits={row['fits_hbm']} "
                            f"({row['compile_s']}s compile)"
                        )
                    except Exception as e:
                        fail += 1
                        print(f"FAIL {tag}: {type(e).__name__}: {e}")
                        traceback.print_exc()
    print(f"\ndry-run complete: {ok} ok, {fail} failed")
    raise SystemExit(1 if fail else 0)


if __name__ == "__main__":
    main()
