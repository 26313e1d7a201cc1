"""Size a configuration's page pool by compiling its step for a described v5e.

  JAX_PLATFORMS=cpu PYTHONPATH=src python bench/plan_memory.py \
      --config granite-3-2b --traffic chat-g2b --pages 768 1024 1280

For each pool size this compiles the two step programs a cell serves with
(the mixed chunk+decode step and the decode-only step) for one chip of a
described v5e (no chip needed) and prints ``memory_analysis()``: arguments,
outputs, temporaries, and their sum with the weights, against the chip's
memory. Nothing runs; shapes only. ``--here`` compiles for this process's
first device instead (on a chip: the compiler that serves), and
``--engine key=value ...`` overrides the engine geometry.
"""
from __future__ import annotations

import argparse
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(os.path.dirname(BENCH), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import model_cfg  # noqa: E402
import traffic_gen  # noqa: E402
import weights  # noqa: E402


def step_programs(spec: dict, mix: dict, pages: int, sharding):
    """(name, lowered) of the chunk+decode and decode-only steps."""
    from repro.models import model as M
    from repro.models import transformer as T
    from repro.runtime import engine as E

    arch = model_cfg.load_arch(spec["arch"])
    cfg = model_cfg.register(spec, arch)
    e = spec["engine"]
    P, CL = traffic_gen.bucket(mix, e["page_size"])
    R, ps, C, n = e["max_active"], e["page_size"], e["chunk_size"], e["steps_per_slot"]
    MP = max(CL // ps, P // ps + 1)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    on = lambda tree: jax.tree.map(lambda x: sds(x.shape, x.dtype), tree)
    params = on(jax.eval_shape(lambda: arch.to_program(
        weights.make_flat(arch, spec, 0))))
    pools = on(jax.eval_shape(lambda: T.paged_pools_init(cfg, pages, ps)))
    state = M.PagedDecodeState(pools=pools, block_tables=sds((R, MP), jnp.int32),
                               pos=sds((R,), jnp.int32), last_tok=sds((R,), jnp.int32))
    sync = on(jax.eval_shape(lambda: E.sync_state_init(R, CL)))
    sig = E._DecodeSig(False, None, None)
    i32 = lambda *s: sds(s, jnp.int32)
    mixed = E._chunk_decode_sync_paged.lower(
        params, state, sync, i32(R, C), i32(R), i32(R), i32(R),
        sds((R,), jnp.bool_), i32(R), None, n=n, cfg=cfg, sig=sig)
    decode = E._decode_n_sync_paged.lower(params, state, sync, None, n=n,
                                          cfg=cfg, sig=sig)
    return [("chunk+decode", mixed), ("decode", decode)]


def main(argv=None) -> int:
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--pages", type=int, nargs="+", required=True)
    ap.add_argument("--here", action="store_true")
    ap.add_argument("--engine", nargs="*", default=[], metavar="KEY=VALUE")
    opts = ap.parse_args(argv)
    spec = model_cfg.load(opts.config)
    for kv in opts.engine:
        key, value = kv.split("=", 1)
        spec["engine"][key] = type(spec["engine"][key])(value)
    mix = traffic_gen.load_mix(opts.traffic)
    if opts.here:
        one = SingleDeviceSharding(jax.devices()[0])
    else:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        one = SingleDeviceSharding(topo.devices[0])
    gib = 2 ** 30
    for pages in opts.pages:
        for name, lowered in step_programs(spec, mix, pages, one):
            m = lowered.compile().memory_analysis()
            total = (m.argument_size_in_bytes + m.output_size_in_bytes
                     - m.alias_size_in_bytes + m.temp_size_in_bytes)
            print(f"{opts.config} pages={pages} rows={spec['engine']['max_active']} "
                  f"chunk={spec['engine']['chunk_size']} {name}: "
                  f"args={m.argument_size_in_bytes / gib:.2f} "
                  f"out={m.output_size_in_bytes / gib:.2f} "
                  f"alias={m.alias_size_in_bytes / gib:.2f} "
                  f"temp={m.temp_size_in_bytes / gib:.2f} "
                  f"total={total / gib:.2f} GiB", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
