"""Chip smoke test: the paged, chunked, sync-free serving path on a TPU at
granite-3-2b's published widths (40 layers, d_model 2048, 32/8 heads, d_ff
8192, vocab 49155, bf16; random weights from PRNGKey(0)).

  python chip_smoke.py            # one chip: serve, first-token and kernel checks
  python chip_smoke.py --chips 4  # four replicas, one per chip, vs one replica

One process drives every chip it uses and starts no other. The script fails
unless JAX's first device is a TPU; it has no CPU fallback. Each check
raises on failure, so the script exits non-zero and prints no result line.
On success the last line of stdout is

  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

The system is built through the serving launcher's own construction code
(``repro.launch.serve.parse_args`` / ``build``), served by its ``serve``
loop under the paper's Algorithm 1 (``--policy adaptive``), then drained.
Checks:

* every admitted request is served, with token ids in [0, vocab);
* each request's first token equals the argmax of ``M.forward``'s logits at
  its last prompt position, computed on the same chip; where that top-1 and
  top-2 logit lie within ``FIRST_TOKEN_TOL`` of the top-1 magnitude, the
  second token is accepted too, and counted;
* the paged decode (native and int8), chunk and flash Pallas kernels, at the
  model's head widths, agree with their ``kernels/ref.py`` oracles within
  ``KERNEL_TOL``.

``--chips 4`` runs only the replica phase: the same requests (static rate,
so both runs draw one stream) on one replica and on four, one per chip; each
request's greedy tokens must agree, and every chip must hold bytes.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time
from functools import partial

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.cache.precision import parse_kv_precision  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.kernels.quant import quantize_kv  # noqa: E402
from repro.launch import serve as launcher  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import model as M  # noqa: E402

# Two bf16 computations of one logit in different op orders (paged chunked
# prefill vs the full-sequence forward, 40 layers deep) are taken to agree
# within 1/32 of the top logit's magnitude: 8x bf16's 2^-8 relative step.
FIRST_TOKEN_TOL = 2.0 ** -5
# bf16 kernel inputs against the f32 oracle on the same (rounded) inputs —
# the bound tests/test_kernels.py holds the interpret-mode kernels to.
KERNEL_TOL = {jnp.dtype(jnp.bfloat16): 2e-2, jnp.dtype(jnp.float32): 2e-5}


@dataclasses.dataclass(frozen=True)
class Spec:
    """One smoke run: the launcher's workload and pool geometry.

    The pool takes ``pool_share`` of the chip's HBM, counted at the model's
    logical KV bytes per token (granite-3-2b: 40 layers x 2 (K, V) x 8 KV
    heads x 64 x 2 B = 80 KiB). On the v5e the compiled paged step holds
    about six times the pool's logical bytes in temporaries (hd 64 pads to
    the 128-lane tile, and the layer scan writes a new pool rather than
    updating in place), so 8% of HBM for the pool plus ~48% of step
    temporaries plus 30% of bf16 weights is ~86% of the chip.
    ``num_pages`` > 0 fixes the pool instead.
    """

    arch: str = "granite-3-2b"
    smoke: bool = False
    prompt_len: int = 512
    min_prompt_len: int = 128
    max_new_tokens: int = 32
    page_size: int = 16
    max_active: int = 16
    raw_rate: int = 4
    horizon: int = 12
    capacity: int = 64
    pool_share: float = 0.08
    num_pages: int = 0

    def argv(self, num_pages: int, *extra: str) -> list:
        ps = self.page_size
        cache_len = -(-(self.prompt_len + 2 * self.max_new_tokens) // ps) * ps
        return ["--arch", self.arch, *(["--smoke"] if self.smoke else []),
                "--paged", "--chunked",
                "--prompt-len", str(self.prompt_len),
                "--min-prompt-len", str(self.min_prompt_len),
                "--max-new-tokens", str(self.max_new_tokens),
                "--cache-len", str(cache_len), "--page-size", str(ps),
                "--num-pages", str(num_pages),
                "--max-active", str(self.max_active),
                "--raw-rate", str(self.raw_rate),
                "--horizon", str(self.horizon),
                "--capacity", str(self.capacity), *extra]

    def pages(self, cfg, device) -> int:
        if self.num_pages:
            return self.num_pages
        hbm = device.memory_stats()["bytes_limit"]
        token_bytes = (cfg.n_layers * 2 * cfg.n_kv_heads * cfg.head_dim_
                       * jnp.dtype(cfg.dtype).itemsize)
        return int(self.pool_share * hbm) // (self.page_size * token_bytes)


class CompileLog:
    """Compile seconds and persistent-cache hits/misses, from JAX's own
    monitoring events (registered once per process)."""

    def __init__(self):
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            self.seconds += secs

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def _say(*parts) -> None:
    print("chip_smoke:", *parts, flush=True)


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def finish(engine, start_slot: int, n_steps: int = 2,
           max_slots: int = 10_000) -> int:
    """Step the chunked sync-free protocol with no new arrivals until every
    queued and in-flight request has retired; returns the slots taken."""
    t = start_slot
    while engine.queue_len() or any(r is not None for r in engine.active):
        _check(t - start_slot < max_slots, "requests never drained")
        engine.step_slot_chunked(t, n_steps=n_steps)
        t += 1
    engine.drain()
    return t - start_slot


def check_served(launch) -> dict:
    """Every admitted request finished with its full budget of in-vocab
    token ids. Returns {rid: generated tokens}."""
    admitted = launch.src._next_id - launch.sched.dropped
    done = launch.engine.finished
    _check(len(done) == admitted,
           f"served {len(done)} of {admitted} admitted requests")
    V = launch.cfg.vocab_size
    for r in done:
        _check(len(r.generated) == r.max_new_tokens,
               f"request {r.rid}: {len(r.generated)} of "
               f"{r.max_new_tokens} tokens")
        _check(all(0 <= t < V for t in r.generated),
               f"request {r.rid}: token id outside [0, {V})")
    return {r.rid: tuple(r.generated) for r in done}


@partial(jax.jit, static_argnums=3)
def _last_logits(params, tokens, last, cfg):
    logits, _ = M.forward(params, {"tokens": tokens}, cfg)
    return logits[jnp.arange(tokens.shape[0]), last].astype(jnp.float32)


def check_first_tokens(launch, batch: int = 4) -> dict:
    """First generated token vs the argmax of the full-sequence forward at
    the last prompt position. Returns counts of exact and near-tie
    matches."""
    reqs = sorted(launch.engine.finished, key=lambda r: r.rid)
    P = max(len(r.tokens) for r in reqs)
    exact = ties = 0
    for i in range(0, len(reqs), batch):
        group = reqs[i:i + batch]
        toks = np.zeros((batch, P), np.int32)
        last = np.zeros(batch, np.int32)
        for j, r in enumerate(group):
            toks[j, :len(r.tokens)] = r.tokens
            last[j] = len(r.tokens) - 1
        logits = np.asarray(_last_logits(launch.params, jnp.asarray(toks),
                                         jnp.asarray(last), launch.cfg))
        for j, r in enumerate(group):
            top2 = np.argsort(logits[j])[::-1][:2]
            hi, lo = logits[j, top2[0]], logits[j, top2[1]]
            first = r.generated[0]
            if first == top2[0]:
                exact += 1
            elif first == top2[1] and hi - lo <= FIRST_TOKEN_TOL * abs(hi):
                ties += 1
            else:
                raise AssertionError(
                    f"request {r.rid}: first token {first}, forward argmax "
                    f"{top2[0]} (top-2 logits {hi:.4f}, {lo:.4f})")
    return {"exact": exact, "near_ties": ties}


def serve_and_check(spec: Spec, device=None) -> dict:
    """Build the system through the launcher, serve ``spec.horizon`` slots
    under Algorithm 1, drain, and run the served-request and first-token
    checks. Returns the numbers the script prints."""
    device = device or jax.devices()[0]
    cfg = get_config(spec.arch, smoke=spec.smoke)
    pages = spec.pages(cfg, device)
    args = launcher.parse_args(spec.argv(pages, "--policy", "adaptive"))
    launch = launcher.build(args)
    t0 = time.perf_counter()
    tr = launcher.run(args, launch)
    tail = finish(launch.engine, args.horizon)
    wall = time.perf_counter() - t0
    tokens = check_served(launch)
    first = check_first_tokens(launch)
    return {"pages": pages, "serve_wall_s": wall, "slots": args.horizon + tail,
            "served": len(tokens), "dropped": launch.sched.dropped,
            "dispatches_per_slot": float(tr["dispatches"].mean()),
            "peak_active": launch.engine.peak_active, **first}


def check_kernels(cfg, impl: str = "pallas", seq: int = 1024,
                  block: int = 512, page_size: int = 16) -> dict:
    """Run the paged decode (native, int8), chunk and flash kernels once at
    the model's head widths against their oracles (f32, highest matmul
    precision, on the same rounded inputs). Returns each kernel's error
    as max |got - want| / (1 + |want|)."""
    H, KVH, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    dt = jnp.dtype(cfg.dtype)
    tol = KERNEL_TOL[dt]
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 16))
    rnd = lambda *shape: jax.random.normal(next(keys), shape, jnp.float32).astype(dt)
    f32 = lambda x: x.astype(jnp.float32)
    errs = {}

    def record(name, got, want):
        got, want = np.asarray(f32(got)), np.asarray(want)
        _check(bool(np.all(np.isfinite(got))), f"{name}: non-finite output")
        err = float(np.max(np.abs(got - want) / (1.0 + np.abs(want))))
        _check(err <= tol, f"{name}: error {err:.3g} > {tol}")
        errs[name] = err

    # paged decode: 4 rows, one full, one partial, one short, one page
    B, MP = 4, seq // page_size
    N = B * MP
    q = rnd(B, H, hd)
    kp, vp = rnd(N, page_size, KVH, hd), rnd(N, page_size, KVH, hd)
    perm = np.random.default_rng(0).permutation(N).reshape(B, MP)
    npages = [MP, MP // 2, 3, 1]
    bt = np.where(np.arange(MP)[None] < np.asarray(npages)[:, None], perm, -1)
    bt = jnp.asarray(bt, jnp.int32)
    pos = jnp.asarray([n * page_size - 2 for n in npages], jnp.int32)
    with jax.default_matmul_precision("highest"):
        want = ref.paged_decode_attention_ref(f32(q), f32(kp), f32(vp), bt, pos)
    record("paged", ops.paged_decode_attention(q, kp, vp, bt, pos, impl=impl),
           want)
    int8 = parse_kv_precision("int8")
    (qk, ks), (qv, vs) = quantize_kv(kp, int8), quantize_kv(vp, int8)
    with jax.default_matmul_precision("highest"):
        want = ref.paged_decode_attention_quant_ref(f32(q), qk, qv, ks, vs,
                                                    bt, pos)
    record("paged_int8", ops.paged_decode_attention(
        q, qk, qv, bt, pos, k_scale=ks, v_scale=vs, impl=impl), want)

    # chunk: a first chunk and a mid-prompt partial chunk over the cache
    C = block // 4
    k, v = rnd(2, seq, KVH, hd), rnd(2, seq, KVH, hd)
    qc = rnd(2, C, H, hd)
    pos0 = jnp.asarray([0, seq // 2], jnp.int32)
    valid = jnp.asarray([C, C // 2], jnp.int32)
    written = pos0 + valid
    j = jnp.arange(seq)[None, :]
    sp = jnp.where(j < written[:, None], j, -1).astype(jnp.int32)
    with jax.default_matmul_precision("highest"):
        want = ref.chunk_attention_ref(f32(qc), f32(k), f32(v), sp, pos0, valid)
    record("chunk", ops.chunk_attention(qc, k, v, sp, pos0, valid, impl=impl,
                                        block_l=block), want)

    # flash: causal prefill over two query and two key blocks
    qf = rnd(1, seq, H, hd)
    with jax.default_matmul_precision("highest"):
        want = ref.attention_ref(f32(qf), f32(k[:1]), f32(v[:1]), causal=True)
    record("flash", ops.flash_attention(qf, k[:1], v[:1], impl=impl,
                                        block_q=block, block_k=block), want)
    return errs


def _leaf_devices(tree) -> set:
    return {d for x in jax.tree.leaves(tree) for d in x.devices()}


def fleet_check(spec: Spec, devices: list) -> dict:
    """The same requests on one replica, then on one replica per device;
    each request's greedy tokens must agree. Every replica's params, pools
    and sync state must live on its own device."""
    n = len(devices)
    _check(len(jax.devices()) >= n, f"{n} replicas need {n} devices")
    cfg = get_config(spec.arch, smoke=spec.smoke)
    pages = spec.pages(cfg, devices[0])
    # a static rate equal to the raw rate samples every arrival, so both
    # runs see one request stream
    extra = ("--policy", "static", "--rate", str(spec.raw_rate))
    args = launcher.parse_args(spec.argv(pages, *extra))
    single = launcher.build(args)
    params = single.params
    t0 = time.perf_counter()
    launcher.run(args, single)
    finish(single.engine, args.horizon)
    single_wall = time.perf_counter() - t0
    want = check_served(single)
    del single
    gc.collect()

    args = launcher.parse_args(spec.argv(pages, *extra,
                                         "--replicas", str(n)))
    fleet = launcher.build(args, params=params)
    for i, eng in enumerate(fleet.engine.replicas):
        held = _leaf_devices((eng.params, eng.pools, eng.sync))
        _check(held == {devices[i]},
               f"replica {i} state on {held}, want {devices[i]}")
    t0 = time.perf_counter()
    launcher.run(args, fleet)
    finish(fleet.engine, args.horizon)
    wall = time.perf_counter() - t0
    got = check_served(fleet)
    _check(got.keys() == want.keys(), "the runs served different requests")
    bad = [rid for rid in want if got[rid] != want[rid]]
    _check(not bad, f"greedy tokens differ between 1 and {n} replicas for "
                    f"requests {bad}")
    served = [len(e.finished) for e in fleet.engine.replicas]
    in_use = [(d.memory_stats() or {}).get("bytes_in_use") for d in devices]
    return {"requests": len(want), "served_per_replica": served,
            "single_wall_s": single_wall, "fleet_wall_s": wall,
            "bytes_in_use": in_use}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the one-replica-per-chip fleet phase")
    opts = ap.parse_args(argv)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {dev.platform!r}",
              file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    compiles = CompileLog()
    _say(f"device_kind={dev.device_kind} count={len(devices)} "
         f"compile_cache={cache_dir}")
    spec = Spec()
    if opts.chips == 4:
        _check(len(devices) >= 4, f"--chips 4 found {len(devices)} devices")
        res = fleet_check(spec, devices[:4])
        _check(all(b is not None and b > 0 for b in res["bytes_in_use"]),
               f"a chip reports no bytes in use: {res['bytes_in_use']}")
        _say(f"fleet: requests={res['requests']} replicas=4 "
             f"served_per_replica={res['served_per_replica']} "
             f"greedy_tokens_agree=True "
             f"single_wall_s={res['single_wall_s']:.3f} "
             f"fleet_wall_s={res['fleet_wall_s']:.3f} (both include compile)")
        _say("bytes_in_use_per_chip=" + ",".join(
            str(b) for b in res["bytes_in_use"]))
        count = 4
    else:
        res = serve_and_check(spec, device=dev)
        _say(f"serve: arch={spec.arch} pages={res['pages']} "
             f"served={res['served']} dropped={res['dropped']} "
             f"slots={res['slots']} "
             f"dispatches_per_slot={res['dispatches_per_slot']:.2f} "
             f"peak_active={res['peak_active']} "
             f"wall_s={res['serve_wall_s']:.3f} (includes compile)")
        _say(f"first_token: exact={res['exact']} "
             f"near_ties={res['near_ties']} tol={FIRST_TOKEN_TOL}")
        cfg = get_config(spec.arch)
        errs = check_kernels(cfg)
        _say("kernels: " + " ".join(f"{k}_err={v:.3g}"
                                    for k, v in errs.items())
             + f" tol={KERNEL_TOL[jnp.dtype(cfg.dtype)]}")
        count = 1
    stats = dev.memory_stats()
    _say(f"compile_s={compiles.seconds:.3f} cache_hits={compiles.hits} "
         f"cache_misses={compiles.misses} "
         f"peak_bytes_in_use={stats['peak_bytes_in_use']}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
