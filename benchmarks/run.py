"""Benchmark harness — one entry per paper figure/claim + framework perf.

Prints ``name,us_per_call,derived`` CSV rows (one per benchmark), matching
the repo convention. The paper has a single evaluation artifact (Fig. 2
queue dynamics); the remaining rows cover the controller itself, the
serving engine it drives, and the roofline table from the dry-run.

Run: PYTHONPATH=src python -m benchmarks.run [--quick]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.compile_cache import enable_compile_cache


def _timeit(fn, n=100, warmup=3):
    for _ in range(warmup):
        fn()
    t0 = time.perf_counter()
    for _ in range(n):
        fn()
    return (time.perf_counter() - t0) / n * 1e6  # us


def bench_queue_dynamics():
    """Paper Fig. 2: four curves, shared service trace."""
    from repro.core.trace import Fig2Config, fig2_experiment, summarize

    cfg = Fig2Config()
    f = jax.jit(lambda: fig2_experiment(cfg))
    res = f()
    jax.block_until_ready(res["fixed_10"]["backlog"])
    us = _timeit(lambda: jax.block_until_ready(f()["fixed_10"]["backlog"]), n=10)
    s = summarize(res)
    derived = (
        f"fixed10_final={s['fixed_10']['final_backlog']:.0f}"
        f";Vhi_tailQ={s['V_high']['tail_mean_backlog']:.1f}"
        f";Vlo_tailQ={s['V_low']['tail_mean_backlog']:.1f}"
        f";Vhi_rate={s['V_high']['mean_rate']:.2f}"
        f";Vlo_rate={s['V_low']['mean_rate']:.2f}"
        f";fixed1_rate={s['fixed_1']['mean_rate']:.2f}"
    )
    return us, derived


def bench_v_sweep():
    """O(V) backlog / O(1/V) utility trade-off across V."""
    from repro.control import LyapunovController
    from repro.core.queueing import ServiceProcess
    from repro.core.utility import paper_utility

    svc = ServiceProcess(kind="markov", rate=10.8, slow_rate=8.4, p_stay=0.9)
    rows = []
    t0 = time.perf_counter()
    for V in (10.0, 50.0, 200.0, 800.0):
        c = LyapunovController(rates=tuple(float(x) for x in range(1, 11)), V=V,
                               utility=paper_utility(10.0))
        tr = c.run(svc, horizon=3000, key=jax.random.PRNGKey(0))
        rows.append((V, float(jnp.mean(tr["backlog"][-500:])),
                     float(jnp.mean(tr["utility"][-500:]))))
    us = (time.perf_counter() - t0) / len(rows) * 1e6
    derived = ";".join(f"V{int(v)}:Q={q:.1f},U={u:.3f}" for v, q, u in rows)
    return us, derived


def bench_controller_overhead():
    """Cost of one Algorithm-1 decision (jitted) — the knob a real serving
    loop pays every control slot."""
    from repro.control import drift_plus_penalty_action

    f = jnp.arange(1, 11, dtype=jnp.float32)
    s = f / 10.0
    q = jnp.float32(12.0)
    act = jax.jit(lambda q: drift_plus_penalty_action(q, f, s, f, 50.0)[0])
    act(q).block_until_ready()
    us = _timeit(lambda: act(q).block_until_ready(), n=1000)
    return us, "actions=10"


def bench_serving_engine(quick=False):
    """End-to-end engine steps/sec with the Lyapunov scheduler (smoke model)."""
    from repro.configs import get_config
    from repro.models import init_params
    from repro.runtime import AdaptiveScheduler, Engine, EngineConfig, RequestSource, serve

    cfg = get_config("granite-3-2b", smoke=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    eng = Engine(cfg, params, EngineConfig(batch_slots=4, prompt_len=16, cache_len=64))
    sch = AdaptiveScheduler(rates=tuple(float(f) for f in range(1, 6)), V=20.0, capacity=32)
    src = RequestSource(vocab_size=cfg.vocab_size, prompt_len=16, raw_rate=5, max_new_tokens=4)
    horizon = 10 if quick else 30
    t0 = time.perf_counter()
    tr = serve(eng, sch, src, horizon=horizon, steps_per_slot=2)
    dt = time.perf_counter() - t0
    us = dt / (horizon * 2) * 1e6
    derived = (
        f"served={int(tr['served'].sum())};dropped={sch.dropped}"
        f";tail_backlog={float(tr['backlog'][-5:].mean()):.1f}"
        f";dispatches_per_slot={float(tr['dispatches'].mean()):.2f}"
    )
    return us, derived


def bench_serve_fused_vs_legacy(quick=False):
    """Control-slot cost before/after batched admission + fused decode.

    Same scheduler, source seed, and engine config; the only difference is
    the serve loop's dispatch pattern: legacy = k batch-1 prefills +
    steps_per_slot decode dispatches per slot, fused = <= 1 bucketed
    prefill + 1 scan decode dispatch. Reports requests/sec and
    jit-dispatches/slot for both. us_per_call = fused us per control slot.
    """
    from repro.configs import get_config
    from repro.models import init_params
    from repro.runtime import AdaptiveScheduler, Engine, EngineConfig, RequestSource, serve

    cfg = get_config("granite-3-2b", smoke=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    steps_per_slot = 4
    horizon = 10 if quick else 30
    reps = 2 if quick else 3

    def run(fused):
        mk_sched = lambda: AdaptiveScheduler(
            rates=tuple(float(f) for f in range(1, 9)), V=20.0, capacity=32)
        eng = Engine(cfg, params, EngineConfig(batch_slots=4, prompt_len=16,
                                               cache_len=64))
        mk_src = lambda s: RequestSource(vocab_size=cfg.vocab_size, prompt_len=16,
                                         raw_rate=8, max_new_tokens=5, seed=s)
        serve(eng, mk_sched(), mk_src(0), horizon=6,
              steps_per_slot=steps_per_slot, fused=fused)  # warm the jits
        best_rps, best_t, disp = 0.0, 0.0, 0.0
        for rep in range(reps):
            eng.pending.clear()  # no backlog carryover between reps
            sch = mk_sched()
            t0 = time.perf_counter()
            tr = serve(eng, sch, mk_src(rep + 1), horizon=horizon,
                       steps_per_slot=steps_per_slot, fused=fused)
            dt = time.perf_counter() - t0
            rps = int(tr["served"].sum()) / dt  # served paired with ITS time
            if rps > best_rps:
                best_rps, best_t = rps, dt
            disp = float(tr["dispatches"].mean())
        return best_rps, best_t, disp

    rps_f, t_fused, disp_f = run(True)
    rps_l, _, disp_l = run(False)
    us = t_fused / horizon * 1e6
    derived = (
        f"fused_rps={rps_f:.1f};legacy_rps={rps_l:.1f}"
        f";speedup={rps_f / rps_l:.2f}x"
        f";fused_disp_per_slot={disp_f:.2f};legacy_disp_per_slot={disp_l:.2f}"
    )
    return us, derived


def bench_paged_vs_dense_decode(quick=False):
    """Paged vs dense KV cache at EQUAL memory (256 KV rows/layer each):
    dense = 4 slots x 64 rows, paged = 16 pages x 16 rows shared. Short
    requests (16-prompt + 8 new = <= 32 rows) strand 32 rows/slot on the
    dense engine but hold only 2 pages on the paged one, so the paged
    engine runs up to 8 requests in flight vs 4 — same workload, greedy,
    and (asserted here) identical generated tokens. us_per_call = paged us
    per control slot."""
    import copy

    from repro.configs import get_config
    from repro.models import init_params
    from repro.runtime import (Engine, EngineConfig, PagedEngine,
                               PagedEngineConfig, RequestSource)

    cfg = get_config("granite-3-2b", smoke=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    n_req = 8 if quick else 16
    src = RequestSource(vocab_size=cfg.vocab_size, prompt_len=16, raw_rate=n_req,
                        max_new_tokens=8, seed=5)
    reqs = src.poll(0, float(n_req))

    def drive(eng):
        eng.submit([copy.deepcopy(r) for r in reqs])
        eng.step_slot(0, n_steps=2)   # warm the jits before timing
        warm_toks = sum(len(r.generated) for r in eng.finished) + sum(
            len(r.generated) for r in eng.active if r is not None)
        slots = 1
        t0 = time.perf_counter()
        while len(eng.finished) < len(reqs) and slots < 200:
            eng.step_slot(slots, n_steps=2)
            slots += 1
        dt = time.perf_counter() - t0
        # tokens generated inside the timed window only (the warm slot's
        # output is excluded, same as its time)
        toks = sum(len(r.generated) for r in eng.finished) - warm_toks
        return toks / dt, dt, slots, {r.rid: r.generated for r in eng.finished}

    dense = Engine(cfg, params, EngineConfig(batch_slots=4, prompt_len=16,
                                             cache_len=64))
    paged = PagedEngine(cfg, params, PagedEngineConfig(
        prompt_len=16, cache_len=64, page_size=16, num_pages=16, max_active=16))
    tps_p, dt_p, slots_p, gen_p = drive(paged)
    tps_d, dt_d, slots_d, gen_d = drive(dense)
    same = gen_p == gen_d
    us = dt_p / max(slots_p - 1, 1) * 1e6
    derived = (
        f"paged_tps={tps_p:.1f};dense_tps={tps_d:.1f}"
        f";speedup={tps_p / tps_d:.2f}x"
        f";max_concurrent_paged={paged.peak_active};max_concurrent_dense=4"
        f";kv_rows_each=256;same_tokens={same}"
        f";paged_slots={slots_p};dense_slots={slots_d}"
    )
    if not same:
        derived = "TOKEN_MISMATCH;" + derived
    return us, derived


def bench_serve_sync_free(quick=False):
    """Sync-free serving (device-resident decode loop + ragged length-aware
    prefill) vs the PR-1 fused path at equal engine geometry.

    Throughput: continuous serving into an over-provisioned 64-token prompt
    bucket with short ragged prompts (4..16) — the padding-waste + host-sync
    regime the optimization targets. The fused baseline pads every admission
    to the full bucket and blocks on a token readback every slot (~2
    dispatch-gating syncs); the sync-free loop samples, detects EOS, and
    accumulates tokens on device, reading back only a tiny async counter
    copy one slot later (0 blocking syncs).

    Equivalence: a fixed request set driven to completion must produce
    bit-identical greedy tokens across legacy fused / sync-free on BOTH the
    dense and paged engines. us_per_call = sync-free us per control slot.
    """
    import copy

    from repro.configs import get_config
    from repro.models import init_params
    from repro.runtime import (Engine, EngineConfig, PagedEngine,
                               PagedEngineConfig, RequestSource,
                               StaticScheduler, serve)

    cfg = get_config("granite-3-2b", smoke=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    P, horizon = 64, (10 if quick else 25)
    reps = 2 if quick else 3
    mk_src = lambda s: RequestSource(vocab_size=cfg.vocab_size, prompt_len=16,
                                     min_prompt_len=4, raw_rate=8,
                                     max_new_tokens=6, seed=s)

    def tokens_of(eng):
        return (sum(len(r.generated) for r in eng.finished)
                + sum(len(r.generated or []) for r in eng.active if r))

    def run(ragged, sync_free):
        eng = Engine(cfg, params, EngineConfig(batch_slots=8, prompt_len=P,
                                               cache_len=128,
                                               ragged_prefill=ragged))
        serve(eng, StaticScheduler(rate=8.0, capacity=256), mk_src(0),
              horizon=6, steps_per_slot=2, sync_free=sync_free)  # warm jits
        best_tps, syncs, dt_best = 0.0, 0.0, 0.0
        for rep in range(reps):
            eng.pending.clear()
            tok0, t0 = tokens_of(eng), time.perf_counter()
            tr = serve(eng, StaticScheduler(rate=8.0, capacity=256),
                       mk_src(rep + 1), horizon=horizon, steps_per_slot=2,
                       sync_free=sync_free)
            dt = time.perf_counter() - t0
            tps = (tokens_of(eng) - tok0) / dt
            if tps > best_tps:
                best_tps, dt_best = tps, dt
            syncs = float(tr["syncs"].mean())
        return best_tps, syncs, dt_best

    tps_s, syncs_s, dt_s = run(ragged=True, sync_free=True)
    tps_f, syncs_f, _ = run(ragged=False, sync_free=False)

    # registry-sourced dispatch/sync accounting: a short observed run whose
    # counters land in BENCH_*.json as row["metrics"] — the regression gate
    # reads disp_per_slot/syncs_per_slot from the metrics registry, not a
    # hand-maintained stats dict (deterministic: fixed source seed)
    from repro.obs import observability
    obs = observability()
    eng = Engine(cfg, params, EngineConfig(batch_slots=8, prompt_len=P,
                                           cache_len=128,
                                           ragged_prefill=True), obs=obs)
    serve(eng, StaticScheduler(rate=8.0, capacity=256), mk_src(0),
          horizon=8, steps_per_slot=2, sync_free=True)
    eng.export_metrics()
    snap = obs.registry.snapshot()
    slots = snap["repro_steps"] / 2
    metrics = {
        "disp_per_slot": (snap["repro_prefill_dispatches"]
                          + snap["repro_decode_dispatches"]) / slots,
        "syncs_per_slot": snap["repro_blocking_syncs"] / slots,
    }

    def drive(eng, sync):
        src = RequestSource(vocab_size=cfg.vocab_size, prompt_len=16,
                            min_prompt_len=3, raw_rate=12, max_new_tokens=6,
                            seed=7)
        eng.submit(copy.deepcopy(src.poll(0, 12.0)))
        step = eng.step_slot_sync if sync else eng.step_slot
        t = 0
        while len(eng.finished) < 12 and t < 60:
            step(t, n_steps=2)
            t += 1
        if sync:
            eng.drain()
        return {r.rid: r.generated for r in eng.finished}

    mk_d = lambda: Engine(cfg, params, EngineConfig(batch_slots=4,
                                                    prompt_len=16, cache_len=64))
    mk_p = lambda: PagedEngine(cfg, params, PagedEngineConfig(
        prompt_len=16, cache_len=64, page_size=16, num_pages=24, max_active=8))
    dense_legacy, dense_sync = drive(mk_d(), False), drive(mk_d(), True)
    paged_legacy, paged_sync = drive(mk_p(), False), drive(mk_p(), True)
    same = (dense_legacy == dense_sync == paged_sync
            and paged_legacy == paged_sync)

    us = dt_s / horizon * 1e6
    derived = (
        f"sync_free_tps={tps_s:.1f};fused_tps={tps_f:.1f}"
        f";speedup={tps_s / tps_f:.2f}x"
        f";sync_free_syncs_per_slot={syncs_s:.2f}"
        f";fused_syncs_per_slot={syncs_f:.2f}"
        f";same_tokens={same}"
    )
    if not same:
        derived = "TOKEN_MISMATCH;" + derived
    if syncs_s > 0:
        derived = "SYNC_VIOLATION;" + derived
    return us, derived, metrics


def bench_continuous_batching(quick=False):
    """Continuous batching (chunked prefill, ONE mixed dispatch per slot) vs
    the PR-3 sync-free baseline at equal engine geometry on a long/short
    mixed trickle workload — the admission-dominated regime the tentpole
    targets.

    The baseline admits via a dedicated bucketed prefill dispatch padded to
    all batch rows and the covering power-of-two bucket: a trickle of ragged
    long prompts makes nearly every slot pay a full (B x bucket) prefill for
    one or two admissions, and that dispatch stalls every in-flight decode
    (head-of-line). The chunked engine feeds prompts into the cache
    chunk_size tokens per row per slot *inside* the decode dispatch, paying
    exactly the prompt tokens it has — so slots cost <= 1 dispatch of
    near-constant width. Reports tokens/s and the p99 *wall-clock* admission
    arrival->finish latency (in seconds of cumulative slot time — slot
    counts are not comparable across modes: the baseline's admission slots
    are several times longer).

    Equivalence: a fixed request set driven to completion must produce
    bit-identical greedy tokens across legacy fused / chunked on BOTH
    engines. us_per_call = chunked us per control slot.
    """
    import copy

    import numpy as _np

    from repro.configs import get_config
    from repro.models import init_params
    from repro.runtime import (Engine, EngineConfig, PagedEngine,
                               PagedEngineConfig, RequestSource,
                               StaticScheduler)

    cfg = get_config("granite-3-2b", smoke=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    P, horizon = 96, (12 if quick else 25)
    reps = 2 if quick else 3
    rate = 2.0
    mk_src = lambda s: RequestSource(
        vocab_size=cfg.vocab_size, prompt_len=16, min_prompt_len=12,
        long_frac=0.5, long_prompt_len=56, raw_rate=int(rate),
        max_new_tokens=4, seed=s)

    def tokens_of(eng):
        return (sum(len(r.generated) for r in eng.finished)
                + sum(len(r.generated or []) for r in eng.active if r))

    def loop(eng, src, chunked, n_slots, record=False):
        """The serve loop, with per-slot wall times so waits can be
        reported in seconds (drain deferred to the caller)."""
        sch = StaticScheduler(rate=rate, capacity=256)
        step = eng.step_slot_chunked if chunked else eng.step_slot_sync
        slot_t = []
        d0 = eng.prefill_dispatches + eng.decode_dispatches
        for t in range(n_slots):
            t0 = time.perf_counter()
            sch.control_async(eng.queue_len())
            sch.admit(eng, src.poll(t, rate), t)
            step(t, n_steps=2)
            slot_t.append(time.perf_counter() - t0)
        disp = (eng.prefill_dispatches + eng.decode_dispatches - d0) / n_slots
        return _np.asarray(slot_t), disp

    def wall_p99_latency(eng, slot_t):
        """p99 arrival->finish latency in SECONDS (cumulative slot time)."""
        cum = _np.concatenate([[0.0], _np.cumsum(slot_t)])
        lat = [cum[min(r.finish_slot + 1, len(slot_t))]
               - cum[min(r.arrival_slot, len(slot_t))]
               for r in eng.finished
               if r.finish_slot is not None and r.arrival_slot is not None]
        return float(_np.percentile(lat, 99)) if lat else float("nan")

    def run(chunked):
        fresh = lambda: Engine(cfg, params, EngineConfig(
            batch_slots=8, prompt_len=P, cache_len=128,
            chunk_size=16, chunk_budget=0))
        warm = fresh()
        loop(warm, mk_src(0), chunked, 5)  # warm the jits (module-level)
        warm.drain()
        best_tps, dt_best, disp_max, wait = 0.0, 0.0, 0.0, 0.0
        for rep in range(reps):
            eng = fresh()  # fresh state per rep; compiles are shared
            slot_t, disp = loop(eng, mk_src(rep + 1), chunked, horizon)
            eng.drain()
            dt = float(slot_t.sum())
            tps = tokens_of(eng) / dt
            # the dispatch budget is gated on the WORST rep; tps/wait are
            # latched together from the best rep (one coherent run)
            disp_max = max(disp_max, disp)
            if tps > best_tps:
                best_tps, dt_best = tps, dt
                wait = wall_p99_latency(eng, slot_t)
        return best_tps, dt_best, disp_max, wait

    tps_c, dt_c, disp_c, wait_c = run(chunked=True)
    tps_s, _, disp_s, wait_s = run(chunked=False)

    def drive(eng, mode):
        src = RequestSource(vocab_size=cfg.vocab_size, prompt_len=16,
                            min_prompt_len=3, long_frac=0.3,
                            long_prompt_len=48, raw_rate=10,
                            max_new_tokens=6, seed=7)
        eng.submit(copy.deepcopy(src.poll(0, 10.0)))
        step = eng.step_slot_chunked if mode == "chunked" else eng.step_slot
        t = 0
        while len(eng.finished) < 10 and t < 80:
            step(t, n_steps=2)
            t += 1
        if mode == "chunked":
            eng.drain()
        return {r.rid: r.generated for r in eng.finished}

    mk_d = lambda: Engine(cfg, params, EngineConfig(
        batch_slots=4, prompt_len=48, cache_len=64, chunk_size=8))
    mk_p = lambda: PagedEngine(cfg, params, PagedEngineConfig(
        prompt_len=48, cache_len=64, page_size=16, num_pages=24,
        max_active=8, chunk_size=16))
    dense_legacy, dense_cb = drive(mk_d(), "fused"), drive(mk_d(), "chunked")
    paged_legacy, paged_cb = drive(mk_p(), "fused"), drive(mk_p(), "chunked")
    same = (dense_legacy == dense_cb == paged_cb and paged_legacy == paged_cb)

    # registry-sourced accounting for the gate (see bench_serve_sync_free)
    from repro.obs import observability
    obs = observability()
    eng = Engine(cfg, params, EngineConfig(batch_slots=8, prompt_len=P,
                                           cache_len=128, chunk_size=16,
                                           chunk_budget=0), obs=obs)
    loop(eng, mk_src(0), True, 8)
    eng.drain()
    eng.export_metrics()
    snap = obs.registry.snapshot()
    slots = snap["repro_steps"] / 2
    metrics = {
        "disp_per_slot": (snap["repro_prefill_dispatches"]
                          + snap["repro_decode_dispatches"]) / slots,
        "syncs_per_slot": snap["repro_blocking_syncs"] / slots,
    }

    us = dt_c / horizon * 1e6
    derived = (
        f"chunked_tps={tps_c:.1f};sync_free_tps={tps_s:.1f}"
        f";speedup={tps_c / tps_s:.2f}x"
        f";chunked_p99_latency_s={wait_c:.3f}"
        f";sync_free_p99_latency_s={wait_s:.3f}"
        f";chunked_disp_per_slot={disp_c:.2f}"
        f";sync_free_disp_per_slot={disp_s:.2f}"
        f";same_tokens={same}"
    )
    if not same:
        derived = "TOKEN_MISMATCH;" + derived
    if disp_c > 1.0:
        derived = "DISPATCH_VIOLATION;" + derived
    return us, derived, metrics


def bench_fleet_scaling(quick=False):
    """Replica-fleet burst absorption: the same burst trace on 1/2/4 paged
    replicas behind the drift router (one Lyapunov control plane, N queues).

    The mechanism being measured is *capacity* scaling, not dispatch
    parallelism: the burst oversubscribes a single replica's page pool, so
    its rows grow past the free list mid-decode and the engine preempts and
    recomputes (PR 2's preempt-and-requeue protocol — correct, greedy-
    identical, but the recomputed prefills and lost decode progress are
    wasted device work). The fleet's aggregate pool absorbs the same burst:
    join-the-shortest-drift routing spreads it so almost no replica
    preempts, and the wasted-work term vanishes — fewer control slots,
    fewer dispatches, higher useful tokens/s. The slot counts, preemption
    counts, and dispatch totals are deterministic (the router and engines
    are); only wall-clock varies across reps, so tokens/s latches from the
    best rep per config (noisy-container convention shared with the other
    serving benches).

    Equivalence: every fleet size must produce bit-identical greedy streams
    (merged across replicas) — TOKEN_MISMATCH fails the smoke gate.
    us_per_call = 4-replica us per control slot.
    """
    import copy

    from repro.configs import get_config
    from repro.control import FleetRouter
    from repro.models import init_params
    from repro.runtime import PagedEngine, PagedEngineConfig, ReplicaFleet
    from repro.runtime.request import Request

    cfg = get_config("granite-3-2b", smoke=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    n_req = 16
    reps = 2 if quick else 3
    max_new = 40

    def mk():
        # pool sized so ONE replica cannot hold the full burst at its
        # grown length (16 rows x 7 pages >> 32) but a 4-way split can
        # (4 rows x 7 pages <= 32 per replica)
        return PagedEngine(cfg, params, PagedEngineConfig(
            prompt_len=16, cache_len=64, page_size=8, num_pages=32,
            max_active=16))

    def workload(seed):
        rng = np.random.default_rng(seed)
        return [Request(rid=i, arrival_slot=0,
                        tokens=rng.integers(0, cfg.vocab_size,
                                            int(rng.integers(8, 17)),
                                            dtype=np.int32),
                        max_new_tokens=max_new)
                for i in range(n_req)]

    def run(n, seed=1):
        fleet = ReplicaFleet.build(mk, n, router=FleetRouter())
        reqs = workload(seed)
        fleet.submit([copy.deepcopy(r) for r in reqs])
        t0 = time.perf_counter()
        t = 0
        while len(fleet.finished) < len(reqs) and t < 600:
            fleet.step_slot_sync(t, n_steps=2)
            t += 1
        fleet.drain()
        dt = time.perf_counter() - t0
        toks = sum(len(r.generated) for r in fleet.finished)
        return {
            "tps": toks / dt, "slots": t, "dt": dt,
            "preempt": sum(e.preemptions for e in fleet.replicas),
            "disp": fleet.prefill_dispatches + fleet.decode_dispatches,
            "streams": {r.rid: tuple(r.generated) for r in fleet.finished},
        }

    for n in (4, 2, 1):
        run(n, seed=0)   # warm every fleet size's shapes (shared jit cache)
    best = {}
    for n in (1, 2, 4):
        for _ in range(reps):
            r = run(n)
            if n not in best or r["tps"] > best[n]["tps"]:
                best[n] = r
    same = best[1]["streams"] == best[2]["streams"] == best[4]["streams"]
    us = best[4]["dt"] / best[4]["slots"] * 1e6
    derived = (
        f"fleet_tps_1={best[1]['tps']:.1f};fleet_tps_2={best[2]['tps']:.1f}"
        f";fleet_tps_4={best[4]['tps']:.1f}"
        f";scaling_4v1={best[4]['tps'] / best[1]['tps']:.2f}x"
        f";preempt_1={best[1]['preempt']};preempt_4={best[4]['preempt']}"
        f";slots_1={best[1]['slots']};slots_4={best[4]['slots']}"
        f";dispatches_1={best[1]['disp']};dispatches_4={best[4]['disp']}"
        f";same_tokens={same}"
    )
    if not same:
        derived = "TOKEN_MISMATCH;" + derived
    return us, derived


def bench_prefix_sharing(quick=False):
    """Prefix-sharing paged KV cache (DESIGN.md §10) vs the exclusive-page
    baseline at EQUAL pool bytes, on a multi-tenant trace where every
    tenant's requests open with that tenant's long system prompt.

    The pool (20 pages x 8 rows) holds ~2.5 private copies of a 47-token
    prompt, so the exclusive allocator serializes admissions; the sharing
    allocator pins each tenant's prefix pages once (refcounted, COW) and
    charges later admissions only their novel suffix pages, so more
    requests decode concurrently from the same bytes. Reported:

      * prefix_capacity_scaling — peak concurrent requests, sharing/on
        over sharing/off. Deterministic (admission is alloc-gated), so the
        CI regression gate compares it across runs.
      * prefix_tps_speedup — end-to-end tokens/s ratio at equal pool
        bytes (both sides timed on this machine, best of reps).
      * ttft_p50/p99 per side, in control slots (arrival -> first token):
        sharing admits earlier, so TTFT collapses with the queueing delay.

    Equivalence: greedy streams must be bit-identical across the two
    allocator modes (same_tokens=True) — TOKEN_MISMATCH fails the smoke
    gate. us_per_call = sharing-on us per control slot.
    """
    import copy

    from repro.configs import get_config
    from repro.models import init_params
    from repro.runtime import PagedEngine, PagedEngineConfig
    from repro.runtime.request import Request

    cfg = get_config("granite-3-2b", smoke=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    per_tenant = 5 if quick else 8
    reps = 2 if quick else 3

    def trace():
        """2 tenants x per_tenant requests: 40-token tenant prefix +
        7-token unique suffix, one request per tenant per slot."""
        rng = np.random.default_rng(11)
        prefixes = [rng.integers(1, 250, 40, dtype=np.int32)
                    for _ in range(2)]
        reqs, rid = [], 0
        for j in range(per_tenant):
            for pre in prefixes:
                reqs.append(Request(
                    rid=rid, arrival_slot=j,
                    tokens=np.concatenate(
                        [pre, rng.integers(1, 250, 7, dtype=np.int32)]),
                    max_new_tokens=4))
                rid += 1
        return reqs

    def run(share):
        eng = PagedEngine(cfg, params, PagedEngineConfig(
            prompt_len=48, cache_len=64, page_size=8, num_pages=20,
            max_active=8, prefix_sharing=share))
        reqs = trace()
        by_slot = {}
        for r in reqs:
            by_slot.setdefault(r.arrival_slot, []).append(copy.deepcopy(r))
        t, t0 = 0, time.perf_counter()
        while len(eng.finished) < len(reqs) and t < 300:
            eng.submit(by_slot.get(t, []))
            eng.step_slot(t, n_steps=2)
            t += 1
        dt = time.perf_counter() - t0
        toks = sum(len(r.generated) for r in eng.finished)
        ttft = np.asarray([r.first_token_slot - r.arrival_slot
                           for r in eng.finished], np.float64)
        eng.allocator.check()  # leak/ownership invariant rides the bench
        return {
            "tps": toks / dt, "dt": dt, "slots": t, "peak": eng.peak_active,
            "hits": eng.prefix_hits,
            "ttft_p50": float(np.percentile(ttft, 50)),
            "ttft_p99": float(np.percentile(ttft, 99)),
            "streams": {r.rid: tuple(r.generated) for r in eng.finished},
        }

    run(True), run(False)                      # warm the jits
    best = {}
    for share in (False, True):
        for _ in range(reps):
            r = run(share)
            if share not in best or r["tps"] > best[share]["tps"]:
                best[share] = r
    on, off = best[True], best[False]
    same = on["streams"] == off["streams"]
    us = on["dt"] / on["slots"] * 1e6
    derived = (
        f"prefix_capacity_scaling={on['peak'] / off['peak']:.2f}x"
        f";prefix_tps_speedup={on['tps'] / off['tps']:.2f}x"
        f";sharing_tps={on['tps']:.1f};exclusive_tps={off['tps']:.1f}"
        f";peak_active_sharing={on['peak']};peak_active_exclusive={off['peak']}"
        f";hit_tokens={on['hits']}"
        f";ttft_p50_sharing={on['ttft_p50']:.1f}"
        f";ttft_p99_sharing={on['ttft_p99']:.1f}"
        f";ttft_p50_exclusive={off['ttft_p50']:.1f}"
        f";ttft_p99_exclusive={off['ttft_p99']:.1f}"
        f";pool_pages_each=20;same_tokens={same}"
    )
    if not same:
        derived = "TOKEN_MISMATCH;" + derived
    return us, derived


def bench_observability(quick=False):
    """Telemetry overhead: the sync-free serve loop with the full
    observability bundle (live trace ring + decision log + registry export)
    vs OBS_OFF, same engine geometry, scheduler, and source seeds.

    The bundle is host-side and pull-based, so the hot path pays one
    ``enabled`` branch plus tuple builds into a preallocated ring —
    ``telemetry_speedup`` (on/off tokens/s, best of reps each) must stay
    ~1.0 and is gated higher-is-better by the regression machinery.

    Equivalence: a fixed request set driven both ways must produce
    bit-identical greedy streams (observability cannot change a token) —
    TOKEN_MISMATCH fails the smoke gate. us_per_call = telemetry-on us per
    control slot.
    """
    import copy

    from repro.configs import get_config
    from repro.models import init_params
    from repro.obs import OBS_OFF, observability
    from repro.runtime import (Engine, EngineConfig, RequestSource,
                               StaticScheduler, serve)

    cfg = get_config("granite-3-2b", smoke=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    P, horizon = 64, (10 if quick else 25)
    reps = 3 if quick else 4
    mk_src = lambda s: RequestSource(vocab_size=cfg.vocab_size, prompt_len=16,
                                     min_prompt_len=4, raw_rate=8,
                                     max_new_tokens=6, seed=s)

    def tokens_of(eng):
        return (sum(len(r.generated) for r in eng.finished)
                + sum(len(r.generated or []) for r in eng.active if r))

    def run(obs):
        live = obs is not OBS_OFF
        eng = Engine(cfg, params, EngineConfig(batch_slots=8, prompt_len=P,
                                               cache_len=128,
                                               ragged_prefill=True), obs=obs)
        mk_sch = lambda: StaticScheduler(rate=8.0, capacity=256,
                                         obs=obs if live else None)
        serve(eng, mk_sch(), mk_src(0), horizon=6, steps_per_slot=2,
              sync_free=True)   # warm the jits
        best_tps, dt_best = 0.0, 0.0
        for rep in range(reps):
            eng.pending.clear()
            if live:
                obs.trace.clear()
            tok0, t0 = tokens_of(eng), time.perf_counter()
            serve(eng, mk_sch(), mk_src(rep + 1), horizon=horizon,
                  steps_per_slot=2, sync_free=True)
            dt = time.perf_counter() - t0
            tps = (tokens_of(eng) - tok0) / dt
            if tps > best_tps:
                best_tps, dt_best = tps, dt
        if live:
            eng.export_metrics()
        return best_tps, dt_best

    tps_off, _ = run(OBS_OFF)
    obs = observability()
    tps_on, dt_on = run(obs)

    def drive(o):
        eng = Engine(cfg, params, EngineConfig(batch_slots=4, prompt_len=16,
                                               cache_len=64), obs=o)
        src = RequestSource(vocab_size=cfg.vocab_size, prompt_len=16,
                            min_prompt_len=3, raw_rate=12, max_new_tokens=6,
                            seed=7)
        eng.submit(copy.deepcopy(src.poll(0, 12.0)))
        t = 0
        while len(eng.finished) < 12 and t < 60:
            eng.step_slot_sync(t, n_steps=2)
            t += 1
        eng.drain()
        return {r.rid: r.generated for r in eng.finished}

    same = drive(OBS_OFF) == drive(observability())
    ratio = tps_on / tps_off
    us = dt_on / horizon * 1e6
    derived = (
        f"telemetry_speedup={ratio:.2f}x"
        f";telemetry_on_tps={tps_on:.1f};telemetry_off_tps={tps_off:.1f}"
        f";trace_events={len(obs.trace)}"
        f";registry_metrics={len(obs.registry)}"
        f";decisions={len(obs.decisions.rates)}"
        f";same_tokens={same}"
    )
    if not same:
        derived = "TOKEN_MISMATCH;" + derived
    return us, derived


def bench_overload_slo(quick=False):
    """Two-tier overload: does the reliability layer (DESIGN.md §12) keep
    the high tier's TTFT deadline that admission-blind baselines miss?

    The trace oversubscribes a 4-row dense engine for a burst window —
    each slot brings several low-priority ``bulk`` requests plus one
    ``gold`` request with a tight first-token deadline. All three
    schedulers see the identical trace and engine geometry; the only
    difference is the control plane. Static and LatencyAware admit FIFO
    until the queue cap silently drops the overflow, so gold requests
    either queue behind bulk past their deadline or are dropped outright.
    ConformalSLO + SLOScheduler arm the degradation ladder instead: expired
    bulk is dropped from the queue, the bulk tier of each overloaded slot's
    arrivals is shed, and admissions are capped highest-tier-first — every
    shed recorded, none silent.

    Attainment is computed over every gold request the trace *created*
    (shed or dropped = missed), not just the survivors — the honest
    denominator. All TTFTs are in control slots, so the attainment numbers
    are deterministic; the smoke gate fails (SLO_VIOLATION) if conformal
    misses the target or stops beating both baselines, and the checked-in
    ``attainment_gold`` gates regressions. us_per_call = conformal us per
    control slot (wall-clock, reported not gated).
    """
    import copy

    from repro.configs import get_config
    from repro.control import LatencyAware
    from repro.models import init_params
    from repro.reliability import ConformalScheduler, TenantSLO
    from repro.runtime import (Engine, EngineConfig, PolicyScheduler,
                               StaticScheduler)
    from repro.runtime.request import Request

    cfg = get_config("granite-3-2b", smoke=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    gold_deadline, bulk_deadline = 6, 10
    burst_slots = 10 if quick else 16
    bulk_per_slot = 4
    max_slots = burst_slots + 120
    capacity = 8

    rng = np.random.default_rng(7)

    def req(rid, t, tenant, priority, deadline):
        return Request(rid=rid, arrival_slot=t,
                       tokens=rng.integers(0, cfg.vocab_size, 12,
                                           dtype=np.int32),
                       max_new_tokens=4, tenant=tenant, priority=priority,
                       deadline_slots=deadline)

    trace, rid = {}, 0
    for t in range(burst_slots):
        batch = []
        for _ in range(bulk_per_slot):
            batch.append(req(rid, t, "bulk", 0, bulk_deadline))
            rid += 1
        batch.append(req(rid, t, "gold", 1, gold_deadline))
        rid += 1
        trace[t] = batch
    n_gold = burst_slots

    def mk_engine():
        return Engine(cfg, params, EngineConfig(
            batch_slots=4, prompt_len=16, cache_len=64))

    def run(sched):
        eng = mk_engine()
        t0 = time.perf_counter()
        t = 0
        while t < max_slots:
            # control() drives the policy's observation (TTFT calibration
            # for conformal); its rate is not used to throttle the offer —
            # the ladder/capacity is the admission control under test
            sched.control(eng.queue_len())
            arrivals = [copy.deepcopy(r) for r in trace.get(t, [])]
            sched.admit(eng, arrivals, t)
            eng.step_slot(t, n_steps=2)
            t += 1
            if (t > burst_slots and not eng.pending
                    and all(r is None for r in eng.active)):
                break
        dt = time.perf_counter() - t0
        ontime = {"gold": 0, "bulk": 0}
        for r in eng.finished:
            if (r.first_token_slot is not None
                    and r.first_token_slot - r.arrival_slot
                    <= r.deadline_slots):
                ontime[r.tenant] += 1
        return {"gold": ontime["gold"] / n_gold,
                "bulk": ontime["bulk"] / (burst_slots * bulk_per_slot),
                "slots": t, "dt": dt}

    rates = tuple(float(f) for f in range(1, 7))
    conf_sched = ConformalScheduler(
        rates=rates, V=20.0,
        tenants=(TenantSLO("gold", gold_deadline, quantile=0.99, priority=1),
                 TenantSLO("bulk", bulk_deadline, quantile=0.5, weight=0.1)),
        window=64, capacity=capacity,
        # arm the ladder early: a 4-row engine is already overloaded when
        # two slots' worth of arrivals are queued
        overload_backlog_frac=0.25, cap_backlog_frac=0.75)
    conf = run(conf_sched)
    static = run(StaticScheduler(rate=6.0, capacity=capacity))
    lat = run(PolicyScheduler(
        policy=LatencyAware(rates=rates, V=20.0, cost_gain=1.0,
                            cost_budget=4.0),
        capacity=capacity))

    target = 0.99
    c = conf_sched.counters()
    us = conf["dt"] / conf["slots"] * 1e6
    derived = (
        f"attainment_gold={conf['gold']:.3f};target={target}"
        f";ontime_gold_static={static['gold']:.3f}"
        f";ontime_gold_latency={lat['gold']:.3f}"
        f";ontime_bulk_conformal={conf['bulk']:.3f}"
        f";shed_expired={c['requests_shed_expired']}"
        f";shed_priority={c['requests_shed_priority']}"
        f";shed_capped={c['requests_shed_capped']}"
        f";dropped_capacity={c['requests_dropped_capacity']}"
        f";slots_conformal={conf['slots']};slots_static={static['slots']}"
    )
    if conf["gold"] < target or conf["gold"] <= max(static["gold"],
                                                    lat["gold"]):
        derived = "SLO_VIOLATION;" + derived
    return us, derived


def bench_sampling_layer(quick=False):
    """Per-request sampling (DESIGN.md §13): heterogeneous per-row params in
    ONE dispatch vs the per-params-group serial baseline.

    Before the sampling layer, temperature/top-k were *static jit keys* on
    an engine-global sampler: requests with different knobs could not share
    a decode dispatch, so a mixed trace had to be served group by group
    (the serial baseline — each params-group on its own engine, summed
    wall-clock). The per-row device tables make the knobs dispatch
    *arguments*, so the heterogeneous trace runs as one batch.

    Gates: (1) the chunked path still costs exactly 1.00 dispatches/slot
    with the sampler fused in (DISPATCH_VIOLATION); (2) every token of the
    heterogeneous run matches the per-group run bit for bit AND a
    direct sweep of the fused sampler matches the host-side eager oracle
    `sample_oracle` row by row — placement-dependent RNG or filter drift
    surfaces as TOKEN_MISMATCH.
    """
    import copy

    from repro.configs import get_config
    from repro.models import init_params
    from repro.runtime import Engine, EngineConfig, Request, SamplingParams
    from repro.runtime.sampling import row_tables, sample_oracle, sample_rows

    cfg = get_config("granite-3-2b", smoke=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    presets = [
        SamplingParams(temperature=0.7, top_k=8, seed=101),
        SamplingParams(temperature=1.2, top_p=0.85, seed=102),
        SamplingParams(temperature=0.9, top_k=12, repetition_penalty=1.3,
                       seed=103),
        SamplingParams(temperature=0.8, presence_penalty=0.5,
                       frequency_penalty=0.2, seed=104),
    ]
    n_reqs = 8 if quick else 16
    max_new = 6 if quick else 8
    reps = 2 if quick else 3

    def mk_reqs():
        rng = np.random.default_rng(9)
        return [Request(rid=i, arrival_slot=0,
                        tokens=rng.integers(0, cfg.vocab_size, 16,
                                            dtype=np.int32),
                        max_new_tokens=max_new,
                        sampling=presets[i % len(presets)])
                for i in range(n_reqs)]

    def mk_eng(**kw):
        base = dict(batch_slots=8, prompt_len=16, cache_len=64)
        base.update(kw)
        return Engine(cfg, params, EngineConfig(**base))

    def drive(eng, reqs, chunked=False):
        eng.submit([copy.deepcopy(r) for r in reqs])
        step = eng.step_slot_chunked if chunked else eng.step_slot
        t = 0
        while len(eng.finished) < len(reqs) and t < 200:
            step(t, n_steps=2)
            t += 1
        if chunked:
            eng.drain()
        return {r.rid: tuple(r.generated) for r in eng.finished}, t

    reqs = mk_reqs()
    groups = {}
    for r in reqs:
        groups.setdefault(r.sampling, []).append(r)

    drive(mk_eng(), reqs)                      # warm the sampling jits
    hetero_streams, serial_streams = {}, {}
    best_h = best_s = float("inf")
    slots_h = 1
    for _ in range(reps):
        t0 = time.perf_counter()
        hetero_streams, slots_h = drive(mk_eng(), reqs)
        best_h = min(best_h, time.perf_counter() - t0)
        t0 = time.perf_counter()
        serial_streams = {}
        for grp in groups.values():            # one engine per params-group
            got, _ = drive(mk_eng(), grp)
            serial_streams.update(got)
        best_s = min(best_s, time.perf_counter() - t0)
    toks = sum(len(g) for g in hetero_streams.values())
    tps_h, tps_s = toks / best_h, toks / best_s
    same = hetero_streams == serial_streams

    # dispatch budget: the chunked path must absorb the fused sampler at
    # exactly ONE mixed dispatch per slot
    eng = mk_eng(chunk_size=4)
    _, slots = drive(eng, reqs, chunked=True)
    disp = (eng.prefill_dispatches + eng.decode_dispatches) / max(slots, 1)
    metrics = {"disp_per_slot": disp}

    # fused sampler vs the host-side eager oracle, heterogeneous rows with
    # penalties and live histories
    rng = np.random.default_rng(13)
    B, V = (32, 64) if quick else (64, 128)
    lg = rng.normal(size=(B, V)).astype(np.float32)
    ages = rng.integers(0, 6, B).astype(np.int32)
    gen = rng.integers(0, V, (B, 8)).astype(np.int32)
    resolved = [(presets[i % len(presets)], 1000 + i) for i in range(B)]
    out = np.asarray(sample_rows(
        jnp.asarray(lg), row_tables(resolved, 0), jnp.asarray(ages),
        jnp.asarray(gen), jnp.asarray(ages)))
    oracle_ok = all(
        int(out[i]) == sample_oracle(lg[i], presets[i % len(presets)],
                                     1000 + i, 0, int(ages[i]),
                                     history=gen[i, :ages[i]])
        for i in range(B))

    us = best_h / max(slots_h, 1) * 1e6
    derived = (
        f"hetero_tps={tps_h:.1f};serial_tps={tps_s:.1f}"
        f";speedup={tps_h / tps_s:.2f}x"
        f";groups={len(groups)};reqs={n_reqs}"
        f";same_tokens={same};oracle_ok={oracle_ok}"
    )
    if not (same and oracle_ok):
        derived = "TOKEN_MISMATCH;" + derived
    if round(disp, 2) != 1.0:
        derived = "DISPATCH_VIOLATION;" + derived
    return us, derived, metrics


def bench_kv_precision(quick=False):
    """Quantized KV-cache pages at EQUAL pool bytes (DESIGN.md §14).

    The same KV byte budget buys a native-f32 pool or ~3.7x as many int8
    pages (1 byte/element + one f32 per-token-per-head scale), so the int8
    engine runs the identical burst at higher peak concurrency —
    ``capacity_speedup`` (gated >= 1.5x in --smoke via DIVERGENCE_VIOLATION
    and as higher-is-better by --check-against).

    Correctness rides in the same row: (1) int8 paged streams must be
    byte-for-byte the int8 *dense* engine's (deterministic quantize-on-
    write + in-kernel dequant are mode-invariant); (2) against the native
    oracle, no stream may diverge before its first generated token —
    prefill attends over the native staging buffer, so token 0 is exact
    and only decode reads pay quantization error. Either failure prefixes
    DIVERGENCE_VIOLATION."""
    import copy

    from repro.cache.precision import parse_kv_precision
    from repro.configs import get_config
    from repro.models import init_params
    from repro.runtime import (Engine, EngineConfig, PagedEngine,
                               PagedEngineConfig, RequestSource)

    cfg = get_config("granite-3-2b", smoke=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    ps = 16
    ratio = (parse_kv_precision("native").page_bytes(ps, cfg.n_kv_heads,
                                                     cfg.head_dim_)
             / parse_kv_precision("int8").page_bytes(ps, cfg.n_kv_heads,
                                                     cfg.head_dim_))
    n_native = 8 if quick else 12
    n_int8 = int(n_native * ratio)
    n_req = 12 if quick else 24
    src = RequestSource(vocab_size=cfg.vocab_size, prompt_len=16,
                        raw_rate=n_req, max_new_tokens=8, seed=5)
    reqs = src.poll(0, float(n_req))

    def drive(eng):
        eng.submit([copy.deepcopy(r) for r in reqs])
        eng.step_slot(0, n_steps=2)   # warm the jits before timing
        slots, t0 = 1, time.perf_counter()
        while len(eng.finished) < len(reqs) and slots < 200:
            eng.step_slot(slots, n_steps=2)
            slots += 1
        dt = time.perf_counter() - t0
        toks = sum(len(r.generated) for r in eng.finished)
        return toks / dt, slots, {r.rid: r.generated for r in eng.finished}

    def paged(prec, pages):
        return PagedEngine(cfg, params, PagedEngineConfig(
            prompt_len=16, cache_len=64, page_size=ps, num_pages=pages,
            max_active=n_req, kv_precision=prec))

    eng_n = paged("", n_native)
    tps_n, slots_n, gen_n = drive(eng_n)
    eng_q = paged("int8", n_int8)
    t0 = time.perf_counter()
    tps_q, slots_q, gen_q = drive(eng_q)
    dt_q = time.perf_counter() - t0
    # mode-invariance oracle: the int8 dense engine, same workload
    dense_q = Engine(cfg, params, EngineConfig(
        batch_slots=4, prompt_len=16, cache_len=64, kv_precision="int8"))
    _, _, gen_dq = drive(dense_q)
    modes_exact = gen_q == gen_dq
    # divergence stats vs the native oracle: first differing token index
    firsts = []
    for rid, ref in gen_n.items():
        got = gen_q.get(rid, [])
        d = next((i for i, (a, b) in enumerate(zip(got, ref)) if a != b),
                 None if len(got) == len(ref) else min(len(got), len(ref)))
        firsts.append(d)
    diverged = [d for d in firsts if d is not None]
    min_first = min(diverged) if diverged else -1
    cap = eng_q.peak_active / max(eng_n.peak_active, 1)
    us = dt_q / max(slots_q - 1, 1) * 1e6
    derived = (
        f"capacity_speedup={cap:.2f}x"
        f";peak_concurrency_int8={eng_q.peak_active}"
        f";peak_concurrency_native={eng_n.peak_active}"
        f";pages_int8={n_int8};pages_native={n_native}"
        f";bytes_ratio={ratio:.2f}"
        f";int8_tps={tps_q:.1f};native_tps={tps_n:.1f}"
        f";slots_int8={slots_q};slots_native={slots_n}"
        f";streams={len(firsts)};identical={firsts.count(None)}"
        f";min_first_divergence={min_first}"
        f";modes_exact={modes_exact}"
    )
    if not modes_exact or min_first == 0 or cap < 1.5:
        derived = "DIVERGENCE_VIOLATION;" + derived
    return us, derived


def bench_flash_attention(quick=False):
    """XLA flash path per-call time + kernel/oracle agreement."""
    from repro.kernels import ops
    from repro.kernels.ref import attention_ref

    B, S, H, KVH, hd = 1, 1024, 8, 2, 64
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (B, S, H, hd), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, KVH, hd), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, KVH, hd), jnp.float32)
    f = lambda: ops.flash_attention(q, k, v, impl="xla").block_until_ready()
    f()
    us = _timeit(f, n=5 if quick else 20)
    err = float(jnp.abs(ops.flash_attention(q, k, v, impl="xla")
                        - attention_ref(q, k, v)).max())
    return us, f"S={S};max_err_vs_ref={err:.1e}"


def bench_ssd_scan(quick=False):
    from repro.kernels import ops
    from repro.kernels.ref import ssd_ref

    B, S, H, P, N = 1, 1024, 4, 64, 64
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 1), (B, S, H)))
    A = -jnp.exp(jnp.linspace(0.0, 1.0, H))
    Bm = jax.random.normal(jax.random.fold_in(key, 2), (B, S, N))
    Cm = jax.random.normal(jax.random.fold_in(key, 3), (B, S, N))
    f = lambda: ops.ssd(x, dt, A, Bm, Cm, chunk=128, impl="xla")[0].block_until_ready()
    f()
    us = _timeit(f, n=5 if quick else 20)
    y, _ = ops.ssd(x, dt, A, Bm, Cm, chunk=128, impl="xla")
    yr, _ = ssd_ref(x, dt, A, Bm, Cm)
    err = float(jnp.abs(y - yr).max())
    return us, f"S={S};max_err_vs_ref={err:.1e}"


def bench_roofline_table():
    """Summarize the dry-run roofline JSONL (if present)."""
    path = os.environ.get("DRYRUN_RESULTS", "dryrun_results.jsonl")
    if not os.path.exists(path):
        return 0.0, "missing:run python -m repro.launch.dryrun --all first"
    rows = [json.loads(l) for l in open(path)]
    single = [r for r in rows if r["mesh"] == "16x16"]
    doms = {}
    fits = sum(1 for r in single if r.get("fits_hbm"))
    for r in single:
        doms[r["dominant"]] = doms.get(r["dominant"], 0) + 1
    derived = (
        f"cases={len(single)};fits={fits};"
        + ";".join(f"{k}={v}" for k, v in sorted(doms.items()))
    )
    return 0.0, derived


# Fast subset exercised by `--smoke` (and CI): one controller row, four
# engine rows — enough to catch a rotten perf entrypoint in ~a minute. The
# gate fails on errors, token mismatches, any steady-state blocking sync in
# the sync-free serve loop, and a continuous-batching slot exceeding its
# one-dispatch budget.
SMOKE_BENCHES = ("controller_overhead", "paged_vs_dense_decode",
                 "serve_sync_free", "continuous_batching", "fleet_scaling",
                 "prefix_sharing", "observability", "overload_slo",
                 "sampling_layer", "kv_precision")

# ------------------------------------------------- benchmark-regression gate
# `--check-against baseline.json[,baseline2.json]` compares this run's rows
# to checked-in BENCH_*.json baselines and exits nonzero on regression.
# Only machine-portable metrics are gated: within-run throughput *ratios*
# (speedup/scaling — both sides of the ratio ran on the same machine) as
# higher-is-better, and dispatch/sync accounting (deterministic) as
# lower-is-better. Absolute throughputs (tps/rps) and us_per_call are
# machine-bound — comparing them across the baseline machine and a CI
# runner would gate on hardware, not code — so they are never compared.
_HIGHER_BETTER = ("speedup", "scaling", "attainment")
_LOWER_BETTER = ("disp_per_slot", "syncs_per_slot")


def _derived_metrics(derived: str) -> dict:
    """Parse `key=value` pairs out of a derived string (floats only;
    trailing 'x' on ratios is stripped)."""
    out = {}
    for part in (derived or "").split(";"):
        key, sep, val = part.partition("=")
        if not sep:
            continue
        try:
            out[key] = float(val.rstrip("x"))
        except ValueError:
            pass
    return out


def _metric_direction(key: str):
    """'higher'/'lower' for gated metrics, None for uncompared ones."""
    if any(tok in key for tok in _LOWER_BETTER):
        return "lower"
    if any(tok in key for tok in _HIGHER_BETTER):
        return "higher"
    return None


def _gated_metrics(row: dict) -> dict:
    """All of a row's comparable metrics: derived-string key=value pairs
    plus the registry-sourced ``metrics`` dict (rows that embed one)."""
    out = _derived_metrics(row.get("derived"))
    for key, val in (row.get("metrics") or {}).items():
        try:
            out[key] = float(val)
        except (TypeError, ValueError):
            pass
    return out


def check_against(rows: list, baseline_paths: list, tolerance: float) -> list:
    """Compare a run's rows to baseline JSON rows; return violation strings.

    For each benchmark present in BOTH the run and a baseline, every gated
    baseline metric must (a) exist in the current run (a vanished metric is
    how an ERROR row slips past a name-only check) and (b) sit within
    ``tolerance`` of the baseline: current >= base * (1 - tol) for
    higher-is-better, current <= base * (1 + tol) for lower-is-better.
    The tolerance absorbs container noise in the ratio metrics, not
    machine deltas — absolutes are excluded by ``_metric_direction``.
    """
    base = {}
    for path in baseline_paths:
        with open(path) as f:
            for row in json.load(f):
                base.setdefault(row["name"], row)
    current = {row["name"]: row for row in rows}
    violations = []
    for name, brow in base.items():
        crow = current.get(name)
        if crow is None:
            continue   # not part of this run's subset
        cur = _gated_metrics(crow)
        for key, bval in _gated_metrics(brow).items():
            direction = _metric_direction(key)
            if direction is None:
                continue
            cval = cur.get(key)
            if cval is None:
                violations.append(
                    f"REGRESSION:{name}.{key}: metric missing from current "
                    f"run (baseline={bval:g})")
            elif direction == "higher" and cval < bval * (1.0 - tolerance):
                violations.append(
                    f"REGRESSION:{name}.{key}: {cval:g} < baseline {bval:g} "
                    f"* (1 - {tolerance:g})")
            elif direction == "lower" and cval > bval * (1.0 + tolerance):
                violations.append(
                    f"REGRESSION:{name}.{key}: {cval:g} > baseline {bval:g} "
                    f"* (1 + {tolerance:g})")
    return violations


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate: run the fast subset (implies --quick) and "
                         "exit nonzero if any benchmark errors")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write rows to a BENCH_*.json file")
    ap.add_argument("--only", default=None,
                    help="comma-separated benchmark-name filter")
    ap.add_argument("--check-against", default=None, metavar="PATHS",
                    help="comma-separated baseline BENCH_*.json files; exit "
                         "nonzero if a gated throughput/dispatch metric "
                         "regresses past --tolerance")
    ap.add_argument("--tolerance", type=float, default=0.15,
                    help="relative regression tolerance for --check-against")
    args, _ = ap.parse_known_args()
    if args.smoke:
        args.quick = True

    benches = [
        ("fig2_queue_dynamics", bench_queue_dynamics),
        ("v_sweep_OV_tradeoff", bench_v_sweep),
        ("controller_overhead", bench_controller_overhead),
        ("serving_engine_e2e", lambda: bench_serving_engine(args.quick)),
        ("serve_fused_vs_legacy", lambda: bench_serve_fused_vs_legacy(args.quick)),
        ("paged_vs_dense_decode", lambda: bench_paged_vs_dense_decode(args.quick)),
        ("serve_sync_free", lambda: bench_serve_sync_free(args.quick)),
        ("continuous_batching", lambda: bench_continuous_batching(args.quick)),
        ("fleet_scaling", lambda: bench_fleet_scaling(args.quick)),
        ("prefix_sharing", lambda: bench_prefix_sharing(args.quick)),
        ("observability", lambda: bench_observability(args.quick)),
        ("overload_slo", lambda: bench_overload_slo(args.quick)),
        ("sampling_layer", lambda: bench_sampling_layer(args.quick)),
        ("kv_precision", lambda: bench_kv_precision(args.quick)),
        ("flash_attention_xla", lambda: bench_flash_attention(args.quick)),
        ("ssd_scan_xla", lambda: bench_ssd_scan(args.quick)),
        ("roofline_table", bench_roofline_table),
    ]
    if args.only:
        keep = set(args.only.split(","))
        benches = [(n, f) for n, f in benches if n in keep]
    elif args.smoke:
        benches = [(n, f) for n, f in benches if n in SMOKE_BENCHES]
    rows = []
    print("name,us_per_call,derived")
    for name, fn in benches:
        try:
            out = fn()
            # benches may return (us, derived) or (us, derived, metrics) —
            # the third element is a registry-sourced dict gated like the
            # derived ratios and embedded in the JSON row
            us, derived = out[0], out[1]
            metrics = out[2] if len(out) > 2 else None
            print(f"{name},{us:.1f},{derived}")
            row = {"name": name, "us_per_call": round(us, 1),
                   "derived": derived}
            if metrics:
                row["metrics"] = {k: round(float(v), 4)
                                  for k, v in metrics.items()}
            rows.append(row)
        except Exception as e:  # keep the harness robust
            print(f"{name},nan,ERROR:{type(e).__name__}:{e}")
            rows.append({"name": name, "us_per_call": None,
                         "derived": f"ERROR:{type(e).__name__}:{e}"})
    if args.json:
        with open(args.json, "w") as f:
            json.dump(rows, f, indent=1)
    failed = False
    if args.check_against:
        violations = check_against(rows, args.check_against.split(","),
                                   args.tolerance)
        for v in violations:
            print(v)
        failed |= bool(violations)
    if args.smoke and any(r["us_per_call"] is None or
                          r["derived"].startswith(("TOKEN_MISMATCH",
                                                   "SYNC_VIOLATION",
                                                   "DISPATCH_VIOLATION",
                                                   "SLO_VIOLATION",
                                                   "DIVERGENCE_VIOLATION"))
                          for r in rows):
        failed = True
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
