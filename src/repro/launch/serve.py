"""Serving launcher: model + engine + Policy-driven admission control.

  PYTHONPATH=src python -m repro.launch.serve --arch granite-3-2b --smoke \
      --horizon 40 --policy adaptive

``--policy static --rate 5`` runs the paper's fixed-rate baseline;
``--policy latency-aware`` adds a virtual-queue cost budget on the sampling
rate; ``--policy memory-aware`` prices KV page-pool occupancy (pairs with
``--paged``). ``--paged`` serves from the paged KV cache (shared page pool,
block tables, ``--page-size``/``--num-pages``/``--max-active`` geometry)
instead of dense per-slot cache rows. ``--legacy-loop`` switches the dense
engine off the fused (1 prefill + 1 decode dispatch per slot) path for
before/after comparison. ``--replicas N`` serves from a ``ReplicaFleet`` of
N equal engines (one compile, shared jit cache) with requests routed by
``--router`` — ``drift`` joins the shortest drift-plus-penalty queue
(request backlog + pending prompt tokens + paged occupancy, priced through
the one Algorithm-1 argmax), ``round-robin``/``least-loaded`` are the
classical baselines.

``--temperature``/``--top-k``/``--top-p``/``--rep-penalty``/
``--sampling-seed`` switch generation off pure greedy: they build the
engine-default ``SamplingParams`` every admitted request inherits
(DESIGN.md §13). The RNG is request-keyed — (seed, rid, token index) — so
the sampled streams are identical at any ``--replicas`` count, batch
packing, or preemption schedule. Omit them all and the engine serves the
bit-identical greedy path (argmax, no sampling layer traced).

With several devices in the process (a four-chip host), ``--replicas N``
places replica i on ``jax.devices()[i % n_devices]``: its params, page
pools and sync state are committed there, and one process drives every
chip. ``parse_args`` / ``build`` / ``run`` are the pieces of ``main`` that
other entry points (``chip_smoke.py``) call to construct the same system.

``--metrics`` prints the Prometheus text exposition of every engine counter
at shutdown; ``--trace-out PATH`` records the full request lifecycle and
writes a Chrome-trace JSON (open in Perfetto); ``--decisions-out PATH``
saves the control plane's per-slot argmax decompositions. All three thread
one ``repro.obs.Observability`` bundle through engine, fleet, scheduler,
and router — and none of them changes a single generated token.
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
import numpy as np

from repro.configs import get_config
from repro.configs.base import ModelConfig
from repro.control import ROUTER_KINDS, FleetRouter, LatencyAware
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params
from repro.obs import OBS_OFF, observability
from repro.reliability import ConformalScheduler, TenantSLO
from repro.runtime import (AdaptiveScheduler, Engine, EngineConfig,
                           MemoryAwareScheduler, PagedEngine,
                           PagedEngineConfig, PolicyScheduler,
                           PrecisionAwareScheduler, ReplicaFleet,
                           RequestSource, SamplingParams, StaticScheduler,
                           TenantSpec, TokenAwareScheduler, latency_stats,
                           serve)


def _parse_tenants(spec: str, quantile: float, error):
    """``name:frac:priority:deadline,...`` -> (TenantSpec..., TenantSLO...).

    Tenants with no deadline (empty or ``-``) join the traffic mix but carry
    no SLO virtual queue.
    """
    sources, slos = [], []
    for part in spec.split(","):
        fields = part.strip().split(":")
        if not (1 <= len(fields) <= 4) or not fields[0]:
            error(f"--tenants: bad entry {part!r} "
                  "(want name[:frac[:priority[:deadline]]])")
        name = fields[0]
        try:
            frac = float(fields[1]) if len(fields) > 1 and fields[1] else 1.0
            prio = int(fields[2]) if len(fields) > 2 and fields[2] else 0
            dl = (int(fields[3])
                  if len(fields) > 3 and fields[3] not in ("", "-") else None)
        except ValueError:
            error(f"--tenants: bad entry {part!r} "
                  "(frac float, priority int, deadline int slots)")
        if frac <= 0:
            error(f"--tenants: {name}: frac must be > 0, got {frac}")
        if dl is not None and dl <= 0:
            error(f"--tenants: {name}: deadline must be > 0 slots, got {dl}")
        sources.append(TenantSpec(name=name, frac=frac, priority=prio,
                                  deadline_slots=dl))
        if dl is not None:
            slos.append(TenantSLO(name=name, deadline_slots=dl,
                                  quantile=quantile, priority=prio))
    return tuple(sources), tuple(slos)


def parse_args(argv=None) -> argparse.Namespace:
    """Parse and validate the launcher's arguments. Adds the derived
    ``sampling`` (engine-default SamplingParams or None) and the parsed
    ``tenant_specs`` / ``tenant_slos``."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--policy",
                    choices=["adaptive", "static", "latency-aware",
                             "memory-aware", "token-aware", "conformal-slo",
                             "precision-aware"],
                    default="adaptive")
    ap.add_argument("--kv-precision", choices=["native", "int8", "fp8"],
                    default="",
                    help="KV-cache storage precision (DESIGN.md §14): int8/"
                         "fp8 store pages quantized with per-token-per-head "
                         "scales and dequantize inside the attention "
                         "kernels; default inherits the model config")
    ap.add_argument("--quant-pages", type=int, default=-1,
                    help="paged + quantized: size of the quantized page "
                         "region (-1 = every page; 0 < n < num-pages builds "
                         "a mixed pool for --policy precision-aware)")
    ap.add_argument("--quant-budget", type=float, default=0.6,
                    help="precision-aware: target time-average quantized-"
                         "region occupancy (virtual-queue budget)")
    ap.add_argument("--downgrade-at", type=float, default=0.75,
                    help="precision-aware: pool occupancy at which new "
                         "admissions flip onto quantized pages")
    ap.add_argument("--upgrade-at", type=float, default=0.5,
                    help="precision-aware: occupancy below which admissions "
                         "return to native pages (hysteresis dead band)")
    ap.add_argument("--cost-budget", type=float, default=4.0,
                    help="latency-aware: time-average rate budget")
    ap.add_argument("--paged", action="store_true",
                    help="serve from the paged KV cache (page pool + block tables)")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--num-pages", type=int, default=64)
    ap.add_argument("--max-active", type=int, default=16,
                    help="paged: decode batch rows (concurrency bound)")
    ap.add_argument("--prefix-sharing", action="store_true",
                    help="paged: radix prefix cache — admissions map shared "
                         "prompt prefixes onto resident pages (refcounted, "
                         "copy-on-write) and prefill only the novel suffix")
    ap.add_argument("--occupancy-budget", type=float, default=0.6,
                    help="memory-aware: target time-average pool occupancy")
    ap.add_argument("--legacy-loop", action="store_true",
                    help="per-step loop (k prefills + n decode dispatches)")
    ap.add_argument("--sync-free", action="store_true",
                    help="device-resident decode loop: on-device sampling/"
                         "EOS, async counter readback, 0 blocking syncs/slot")
    ap.add_argument("--chunked", action="store_true",
                    help="continuous batching: chunked prefill interleaved "
                         "with decode in ONE dispatch per slot (implies the "
                         "sync-free protocol)")
    ap.add_argument("--chunk-size", type=int, default=0,
                    help="chunked: prompt tokens per row per slot "
                         "(0 = prompt_len/4, page-aligned on --paged)")
    ap.add_argument("--chunk-budget", type=int, default=0,
                    help="chunked: max prefill tokens per slot across rows "
                         "(0 = unlimited)")
    ap.add_argument("--token-budget", type=float, default=64.0,
                    help="token-aware: target time-average pending prompt tokens")
    ap.add_argument("--tenants", type=str, default=None,
                    help="multi-tenant mix: name:frac:priority:deadline,... "
                         "(e.g. gold:0.3:1:6,bulk:0.7:0:24; deadline in "
                         "slots, '-' = no SLO). Pairs with "
                         "--policy conformal-slo")
    ap.add_argument("--slo-quantile", type=float, default=0.9,
                    help="conformal-slo: per-tenant attainment target q")
    ap.add_argument("--slo-gain", type=float, default=1.0,
                    help="conformal-slo: price scale on the SLO queues")
    ap.add_argument("--temperature", type=float, default=None,
                    help="softmax temperature, >= 0 (0 = greedy argmax; "
                         "default: pure greedy engine, no sampling layer)")
    ap.add_argument("--top-k", type=int, default=None,
                    help="sample from the k highest logits, >= 0 "
                         "(0 = full vocabulary; > vocab clamps)")
    ap.add_argument("--top-p", type=float, default=None,
                    help="nucleus mass in (0, 1] (1.0 = off)")
    ap.add_argument("--rep-penalty", type=float, default=None,
                    help="CTRL repetition penalty on generated tokens, > 0 "
                         "(1.0 = off)")
    ap.add_argument("--sampling-seed", type=int, default=None,
                    help="base RNG seed; the per-token key is "
                         "fold_in(fold_in(PRNGKey(seed), rid), token_index)")
    ap.add_argument("--min-prompt-len", type=int, default=None,
                    help="ragged workload: prompt lengths uniform in "
                         "[min, prompt-len] (exercises bucketed prefill)")
    ap.add_argument("--eos-id", type=int, default=None,
                    help="stop token for on-device EOS detection")
    ap.add_argument("--replicas", type=int, default=1,
                    help="serve from a ReplicaFleet of N equal engines "
                         "(1 = plain single engine)")
    ap.add_argument("--router", choices=list(ROUTER_KINDS), default="drift",
                    help="fleet request routing: drift = join the shortest "
                         "drift-plus-penalty queue")
    ap.add_argument("--metrics", action="store_true",
                    help="print the Prometheus exposition at shutdown")
    ap.add_argument("--trace-out", type=str, default=None,
                    help="write a Chrome-trace JSON of the request "
                         "lifecycle (open in Perfetto)")
    ap.add_argument("--decisions-out", type=str, default=None,
                    help="save the control plane's recorded Algorithm-1 "
                         "decisions (JSON; benchmarks/report.py renders)")
    ap.add_argument("--rate", type=float, default=5.0, help="static policy rate")
    ap.add_argument("--V", type=float, default=20.0)
    ap.add_argument("--raw-rate", type=int, default=5)
    ap.add_argument("--horizon", type=int, default=40)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--cache-len", type=int, default=64)
    ap.add_argument("--capacity", type=int, default=32)
    ap.add_argument("--max-new-tokens", type=int, default=4,
                    help="tokens each request generates")
    args = ap.parse_args(argv)
    if args.paged and args.legacy_loop:
        ap.error("--legacy-loop is a dense-engine comparison path; "
                 "the paged engine has no per-step loop")
    if args.sync_free and args.legacy_loop:
        ap.error("--sync-free and --legacy-loop are mutually exclusive")
    if args.chunked and args.legacy_loop:
        ap.error("--chunked and --legacy-loop are mutually exclusive")
    if args.prefix_sharing and not args.paged:
        ap.error("--prefix-sharing shares pages of the paged KV pool; "
                 "it requires --paged")
    if args.policy == "memory-aware" and not args.paged:
        ap.error("--policy memory-aware prices page-pool occupancy; "
                 "it requires --paged (the dense engine reports none)")
    if args.policy == "precision-aware":
        if not args.paged:
            ap.error("--policy precision-aware picks the page region per "
                     "admission; it requires --paged")
        if args.kv_precision not in ("int8", "fp8"):
            ap.error("--policy precision-aware needs a quantized page "
                     "region: pass --kv-precision int8 (or fp8)")
        if not 0 < args.quant_pages < args.num_pages:
            ap.error("--policy precision-aware admits between regions of a "
                     "mixed pool: pass --quant-pages in (0, num-pages), "
                     f"got {args.quant_pages}/{args.num_pages}")
    if args.quant_pages != -1 and args.kv_precision not in ("int8", "fp8"):
        ap.error("--quant-pages sizes the quantized page region; it needs "
                 "--kv-precision int8 (or fp8)")
    if args.quant_pages != -1 and not args.paged:
        ap.error("--quant-pages is paged-pool geometry; it requires --paged")
    if not 0.0 <= args.upgrade_at <= args.downgrade_at:
        ap.error("hysteresis needs 0 <= --upgrade-at <= --downgrade-at, got "
                 f"{args.upgrade_at} / {args.downgrade_at}")

    if args.replicas < 1:
        ap.error("--replicas must be >= 1")
    if args.replicas > 1 and args.legacy_loop:
        ap.error("--legacy-loop is a single-engine comparison path; "
                 "the fleet steps replicas through the fused protocols")
    # geometry/rate arguments surface as deep JAX shape errors if they reach
    # the engine invalid — reject them here with one-line messages instead
    if args.chunk_size < 0:
        ap.error(f"--chunk-size must be >= 0 (0 = auto prompt_len/4), "
                 f"got {args.chunk_size}")
    if args.chunk_budget < 0:
        ap.error(f"--chunk-budget must be >= 0 (0 = unlimited), "
                 f"got {args.chunk_budget}")
    for name in ("slots", "prompt_len", "cache_len", "page_size",
                 "num_pages", "max_active", "capacity", "horizon",
                 "raw_rate", "max_new_tokens"):
        if getattr(args, name) < 1:
            ap.error(f"--{name.replace('_', '-')} must be >= 1, "
                     f"got {getattr(args, name)}")
    if not 0.0 < args.slo_quantile < 1.0:
        ap.error(f"--slo-quantile must be in (0, 1), got {args.slo_quantile}")
    # sampling knobs: mirror SamplingParams' admission-time validation as
    # one-line CLI errors naming the valid range
    if args.temperature is not None and not args.temperature >= 0.0:
        ap.error(f"--temperature must be >= 0 (0 = greedy), "
                 f"got {args.temperature}")
    if args.top_k is not None and args.top_k < 0:
        ap.error(f"--top-k must be >= 0 (0 = full vocabulary), "
                 f"got {args.top_k}")
    if args.top_p is not None and not 0.0 < args.top_p <= 1.0:
        ap.error(f"--top-p must be in (0, 1], got {args.top_p}")
    if args.rep_penalty is not None and not args.rep_penalty > 0.0:
        ap.error(f"--rep-penalty must be > 0 (1.0 = off), "
                 f"got {args.rep_penalty}")
    sampling = None
    if any(v is not None for v in (args.temperature, args.top_k, args.top_p,
                                   args.rep_penalty, args.sampling_seed)):
        sampling = SamplingParams(
            temperature=args.temperature if args.temperature is not None
            else 1.0,
            top_k=args.top_k or 0,
            top_p=args.top_p if args.top_p is not None else 1.0,
            repetition_penalty=args.rep_penalty if args.rep_penalty is not None
            else 1.0,
            seed=args.sampling_seed)
    tenant_specs, tenant_slos = (), ()
    if args.tenants:
        tenant_specs, tenant_slos = _parse_tenants(
            args.tenants, args.slo_quantile, ap.error)
    if args.policy == "conformal-slo" and not tenant_slos:
        ap.error("--policy conformal-slo needs at least one tenant with a "
                 "deadline via --tenants (e.g. gold:0.3:1:6,bulk:0.7:0:24)")
    args.sampling = sampling
    args.tenant_specs, args.tenant_slos = tenant_specs, tenant_slos
    return args


@dataclasses.dataclass
class Launch:
    """The system ``main`` serves: model, engine (or fleet), scheduler,
    request source and the observability bundle."""

    cfg: ModelConfig
    params: dict
    engine: object
    sched: object
    src: RequestSource
    obs: object
    telemetry: bool


def build(args: argparse.Namespace, params=None, obs=None) -> Launch:
    """Construct the launcher's system from parsed arguments. ``params``
    reuses an existing weight tree (random from ``PRNGKey(0)`` otherwise);
    ``obs`` an ``Observability`` bundle the caller keeps (else one from the
    telemetry flags)."""
    sampling = args.sampling
    cfg = get_config(args.arch, smoke=args.smoke)
    if params is None:
        params = init_params(jax.random.PRNGKey(0), cfg)
    telemetry = bool(args.metrics or args.trace_out or args.decisions_out)
    if obs is None:
        obs = observability() if telemetry else OBS_OFF
    telemetry = telemetry or obs is not OBS_OFF
    if args.paged:
        mk_engine = lambda: PagedEngine(cfg, params, PagedEngineConfig(
            prompt_len=args.prompt_len, cache_len=args.cache_len,
            page_size=args.page_size, num_pages=args.num_pages,
            max_active=args.max_active, eos_id=args.eos_id,
            prefix_sharing=args.prefix_sharing,
            chunk_size=args.chunk_size, chunk_budget=args.chunk_budget,
            kv_precision=args.kv_precision, quant_pages=args.quant_pages,
            sampling=sampling), obs=obs)
    else:
        mk_engine = lambda: Engine(cfg, params, EngineConfig(
            batch_slots=args.slots, prompt_len=args.prompt_len,
            cache_len=args.cache_len, eos_id=args.eos_id,
            chunk_size=args.chunk_size, chunk_budget=args.chunk_budget,
            kv_precision=args.kv_precision,
            sampling=sampling), obs=obs)
    if args.replicas > 1:
        router = FleetRouter(kind=args.router,
                             decisions=obs.decisions if telemetry else None)
        devices = jax.devices()
        engine = ReplicaFleet.build(mk_engine, args.replicas, router=router,
                                    obs=obs if telemetry else None,
                                    devices=devices if len(devices) > 1
                                    else None)
    else:
        engine = mk_engine()
    rates = tuple(float(f) for f in range(1, args.raw_rate + 1))
    sched_obs = obs if telemetry else None
    if args.policy == "adaptive":
        sched = AdaptiveScheduler(rates=rates, V=args.V,
                                  capacity=args.capacity, obs=sched_obs)
    elif args.policy == "latency-aware":
        sched = PolicyScheduler(
            policy=LatencyAware(rates=rates, V=args.V, cost_gain=1.0,
                                cost_budget=args.cost_budget),
            capacity=args.capacity, obs=sched_obs)
    elif args.policy == "memory-aware":
        sched = MemoryAwareScheduler(
            rates=rates, V=args.V, occupancy_budget=args.occupancy_budget,
            capacity=args.capacity, obs=sched_obs)
    elif args.policy == "token-aware":
        sched = TokenAwareScheduler(
            rates=rates, V=args.V, token_budget=args.token_budget,
            tokens_per_request=float(args.prompt_len),
            capacity=args.capacity, obs=sched_obs)
    elif args.policy == "conformal-slo":
        sched = ConformalScheduler(rates=rates, V=args.V,
                                   tenants=args.tenant_slos,
                                   slo_gain=args.slo_gain,
                                   capacity=args.capacity, obs=sched_obs)
    elif args.policy == "precision-aware":
        sched = PrecisionAwareScheduler(
            rates=rates, V=args.V, quant_budget=args.quant_budget,
            downgrade_at=args.downgrade_at, upgrade_at=args.upgrade_at,
            quant_precision=args.kv_precision,
            capacity=args.capacity, obs=sched_obs)
    else:
        sched = StaticScheduler(rate=args.rate, capacity=args.capacity,
                                obs=sched_obs)
    src = RequestSource(vocab_size=cfg.vocab_size, prompt_len=args.prompt_len,
                        raw_rate=args.raw_rate,
                        max_new_tokens=args.max_new_tokens,
                        min_prompt_len=args.min_prompt_len,
                        tenants=args.tenant_specs or None)
    return Launch(cfg=cfg, params=params, engine=engine, sched=sched, src=src,
                  obs=obs, telemetry=telemetry)


def run(args: argparse.Namespace, launch: Launch) -> dict:
    """Serve ``args.horizon`` control slots; returns the serve trace."""
    return serve(launch.engine, launch.sched, launch.src,
                 horizon=args.horizon, steps_per_slot=2,
                 fused=not args.legacy_loop, sync_free=args.sync_free,
                 chunked=args.chunked)


def report(args: argparse.Namespace, launch: Launch, tr: dict) -> None:
    """Print the launcher's summary lines."""
    engine, sched, obs = launch.engine, launch.sched, launch.obs
    sampling, telemetry = args.sampling, launch.telemetry
    print(f"policy={args.policy} served={int(tr['served'].sum())} "
          f"dropped={sched.dropped} "
          f"tail_backlog={float(tr['backlog'][-5:].mean()):.1f} "
          f"mean_rate={float(np.mean(sched.rate_history)):.2f} "
          f"dispatches_per_slot={float(tr['dispatches'].mean()):.2f} "
          f"blocking_syncs_per_slot={float(tr['syncs'].mean()):.2f}")
    if sampling is not None:
        engines = engine.replicas if args.replicas > 1 else [engine]
        print(f"sampling: temperature={sampling.temperature} "
              f"top_k={sampling.top_k} top_p={sampling.top_p} "
              f"rep_penalty={sampling.repetition_penalty} "
              f"seed={sampling.seed if sampling.seed is not None else 0} "
              f"requests_sampled={sum(e.requests_sampled for e in engines)}")
    if args.replicas > 1:
        per = [len(e.finished) for e in engine.replicas]
        print(f"fleet: replicas={args.replicas} router={args.router} "
              f"served_per_replica={per} requeues={engine.requeues}")
    if args.paged:
        engines = engine.replicas if args.replicas > 1 else [engine]
        st = [e.allocator.stats() for e in engines]
        print(f"paged: peak_occupancy={float(tr['occupancy'].max()):.2f} "
              f"peak_pages={max(s.peak_used_pages for s in st)}"
              f"/{st[0].num_pages} "
              f"peak_active={max(e.peak_active for e in engines)} "
              f"alloc_failures={sum(e.alloc_failures for e in engines)} "
              f"preemptions={sum(e.preemptions for e in engines)}")
        if args.kv_precision in ("int8", "fp8"):
            c = [e.counters() for e in engines]
            flips = (len(obs.decisions.precisions) if telemetry else
                     len(sched.rate_history) * 0)
            print(f"quant: precision={args.kv_precision} "
                  f"pages_quant={c[0]['pages_quant']}"
                  f"/{st[0].num_pages} "
                  f"quant_occupancy="
                  + ",".join(f"{x['quant_occupancy']:.2f}" for x in c)
                  + (f" admit={getattr(engines[0], 'admit_precision', '-')}"
                     f" precision_flips={flips}"
                     if args.policy == "precision-aware" else ""))
        if args.prefix_sharing:
            print(f"prefix: hit_tokens={sum(e.prefix_hits for e in engines)} "
                  f"forks={sum(e.prefix_forks for e in engines)} "
                  f"indexed_pages={sum(len(e._prefix) for e in engines)} "
                  f"evicted={sum(e._prefix.evicted_pages for e in engines)}")
    if args.policy == "conformal-slo":
        c = sched.counters()
        att = sched.attainment()
        print(f"slo: degrade_level={c['degrade_level']} "
              f"pressure={c['slo_pressure']:.2f} "
              f"shed_expired={c['requests_shed_expired']} "
              f"shed_priority={c['requests_shed_priority']} "
              f"shed_capped={c['requests_shed_capped']} "
              "attainment="
              + ",".join(f"{k}:{v:.3f}" for k, v in sorted(att.items())))
    print("latency:", latency_stats(engine))
    if telemetry:
        engine.export_metrics()
        if args.policy == "conformal-slo":
            obs.export(sched.counters())
        if args.metrics:
            print(obs.registry.prometheus_text(), end="")
        if args.trace_out:
            print(f"trace: {obs.trace.save(args.trace_out)} "
                  f"({len(obs.trace)} events, {obs.trace.dropped} dropped)")
        if args.decisions_out:
            print(f"decisions: {obs.decisions.save(args.decisions_out)} "
                  f"({len(obs.decisions.rates)} rate, "
                  f"{len(obs.decisions.routes)} route)")


def main(argv=None):
    enable_compile_cache()
    args = parse_args(argv)
    launch = build(args)
    report(args, launch, run(args, launch))


if __name__ == "__main__":
    main()
