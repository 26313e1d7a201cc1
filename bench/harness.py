"""One run of one cell: set up, serve an open-loop window, check, report.

The window drives the program's own serve loop (``repro.runtime.serve``)
over the engine and Algorithm-1 scheduler that the serving launcher builds
(``repro.launch.serve.parse_args`` / ``build``: ``--paged --chunked
--policy adaptive``), so every slot goes through
``PagedEngine.step_slot_chunked``. The benchmark brings only:

* ``WallClockSource``: the request source. Requests fall due on the host
  clock from the mix's schedule; each is admitted with probability
  ``f / raw_rate`` for the rate ``f`` Algorithm 1 chose, as the program's
  ``RequestSource`` thins. It closes the loop once the window is over and
  every admitted request has retired.
* ``SlotClock`` and ``Spans``: thin proxies that stamp each slot on the host
  clock and, in a traced run, wrap each call into a layer in a
  ``jax.profiler.TraceAnnotation``.

The sync-free engine learns a request's tokens only from the readback of a
slot, which lands during the next slot's call. So a token computed in slot
``s`` is taken to reach the host when call ``s + 1`` returns (or the final
drain, for the last slot), and first-token and finish times are those
landing times.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import json
import math
import sys
import shutil
import tempfile
import time
from pathlib import Path

import jax
import numpy as np

import counts
import model_cfg
import reference
import trace_reduce
import traffic_gen
import weights

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
clock = time.perf_counter
# Seconds the traced run profiles, about the window's middle: some tens of
# slots, so a per-slot reading does not hang on which few slots it caught.
TRACE_S = 8.0


class CompileLog:
    """Compile seconds and persistent-cache hits/misses from JAX's own
    monitoring events (registered once per process). ``lowerings`` counts
    programs lowered, so a lowering inside the window shows."""

    def __init__(self):
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        self.lowerings = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            self.seconds += secs
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.lowerings += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


class WindowClosed(Exception):
    """Raised from ``poll`` to end the serve loop."""


def _span(traced: bool, name: str):
    return (jax.profiler.TraceAnnotation(name) if traced
            else contextlib.nullcontext())


class SlotClock:
    """Engine proxy: host-clock stamps at the start and end of each slot's
    ``step_slot_chunked``; everything else passes through."""

    def __init__(self, engine, traced: bool):
        self._engine = engine
        self._traced = traced
        self.starts: dict = {}
        self.ends: dict = {}
        self.queue: dict = {}        # slot -> requests pending after it
        self.dispatching: set = set()  # slots that dispatched device work

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def step_slot_chunked(self, now, n_steps=1):
        eng = self._engine
        d0 = eng.decode_dispatches
        self.starts[now] = clock()
        with _span(self._traced, "bench.step_slot_chunked"):
            out = eng.step_slot_chunked(now, n_steps=n_steps)
        self.ends[now] = clock()
        if eng.decode_dispatches != d0:
            self.dispatching.add(now)
        self.queue[now] = eng.queue_len()
        return out


class Spans:
    """Scheduler proxy: spans around Algorithm 1's decision and admission."""

    def __init__(self, sched, traced: bool):
        self._sched = sched
        self._traced = traced

    def __getattr__(self, name):
        return getattr(self._sched, name)

    def control_async(self, *a, **kw):
        with _span(self._traced, "bench.control_async"):
            return self._sched.control_async(*a, **kw)

    def admit(self, *a, **kw):
        with _span(self._traced, "bench.admit"):
            return self._sched.admit(*a, **kw)


class WallClockSource:
    """Open-loop arrivals on the host clock, thinned by Algorithm 1's rate.

    ``poll(slot, rate)`` hands the serve loop every request that fell due
    since the last poll; each is admitted with probability
    ``min(rate / raw_rate, 1)`` (the rest are refused). Once the window has
    closed, ``poll`` raises ``WindowClosed`` as soon as the engine holds no
    request, or at the drain deadline. ``on_time`` is called with the
    window offset at every poll (the traced run starts and stops the
    profiler from it)."""

    def __init__(self, arrivals, seconds: float, raw_rate: int, seed: int,
                 engine, drain_s: float, traced: bool = False, on_time=None):
        self.arrivals = arrivals
        self.seconds = seconds
        self.raw_rate = raw_rate
        self.engine = engine
        self.drain_s = drain_s
        self.traced = traced
        self.on_time = on_time
        self.rng = np.random.default_rng(
            np.random.SeedSequence([int(seed) & (2 ** 63 - 1), 7]))
        self.t0 = None
        self.next = 0
        self.refused: set = set()
        self.late_s: list = []

    def start(self) -> float:
        self.t0 = clock()
        return self.t0

    def _idle(self) -> bool:
        c = self.engine.counters()
        return c["requests_active"] == 0 and c["requests_pending"] == 0

    def poll(self, slot: int, rate: float) -> list:
        from repro.runtime import Request

        with _span(self.traced, "bench.poll"):
            now = clock() - self.t0
            if self.on_time is not None:
                self.on_time(now)
            if now >= self.seconds and self.next == len(self.arrivals):
                if self._idle() or now >= self.seconds + self.drain_s:
                    raise WindowClosed
            p = min(float(rate) / self.raw_rate, 1.0)
            out = []
            while self.next < len(self.arrivals) and \
                    self.arrivals[self.next].at_s <= now:
                a = self.arrivals[self.next]
                self.next += 1
                self.late_s.append(now - a.at_s)
                if self.rng.random() < p:
                    out.append(Request(rid=a.rid, arrival_slot=slot,
                                       tokens=a.prompt,
                                       max_new_tokens=a.max_new_tokens))
                else:
                    self.refused.add(a.rid)
            return out


class _WarmSource:
    """Two requests at slot 0 — one prompt of two chunks, one of a chunk —
    then nothing; closes the loop once they retire."""

    def __init__(self, engine, vocab: int, chunk: int, out_tokens: int):
        self.engine = engine
        self.reqs = [(chunk + 3, out_tokens), (max(chunk // 2, 1), 2)]
        self.vocab = vocab

    def poll(self, slot: int, rate: float) -> list:
        from repro.runtime import Request

        if slot == 0:
            return [Request(rid=i, arrival_slot=0,
                            tokens=np.arange(n, dtype=np.int32) % self.vocab,
                            max_new_tokens=m)
                    for i, (n, m) in enumerate(self.reqs)]
        c = self.engine.counters()
        if c["requests_active"] == 0 and c["requests_pending"] == 0:
            raise WindowClosed
        if slot > 10_000:
            raise RuntimeError("warm-up requests never retired")
        return []


# ------------------------------------------------------------------ cells
def load_manifest(root: Path = ROOT) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


@dataclasses.dataclass
class Cell:
    name: str
    spec: dict            # configuration file
    arch: object          # its architecture module (model_cfg's contract)
    mix: dict             # traffic file
    chips: int
    end_to_end: list      # manifest entries reported with --trace 0
    per_layer: list       # manifest entries reported with --trace 1
    limits: dict


def load_cell(manifest: dict, workload: str, bench_dir: Path = BENCH_DIR) -> Cell:
    wl = {w["name"]: w for w in manifest["workloads"]}.get(workload)
    if wl is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cfgs = {c["name"]: c for c in manifest["configs"]}
    spec = model_cfg.load(wl["config"], bench_dir / "configs")
    # a directory of fixtures may bring architectures beside the benchmark's
    arch = model_cfg.load_arch(spec["arch"], bench_dir / "arch", model_cfg.ARCH_DIR)
    if cfgs[wl["config"]]["reduced"] != spec["reduced"]:
        raise ValueError(f"{wl['config']}: BENCHMARK.json and the file list "
                         "different reduced keys")
    mix = traffic_gen.load_mix(wl["traffic"], bench_dir / "traffic")
    limits = json.loads((bench_dir / "limits" / f"{workload}.json").read_text())
    return Cell(name=workload, spec=spec, arch=arch, mix=mix, chips=wl["chips"],
                end_to_end=manifest["end_to_end"], per_layer=manifest["per_layer"],
                limits=limits)


def launcher_argv(spec: dict, mix: dict) -> list:
    """The serving launcher's arguments for a configuration under a mix."""
    e = spec["engine"]
    P, CL = traffic_gen.bucket(mix, e["page_size"])
    return ["--arch", spec["name"], "--paged", "--chunked",
            "--policy", "adaptive", "--V", str(e["V"]),
            "--raw-rate", str(e["raw_rate"]), "--capacity", str(e["capacity"]),
            "--prompt-len", str(P), "--cache-len", str(CL),
            "--page-size", str(e["page_size"]),
            "--num-pages", str(e["num_pages"]),
            "--max-active", str(e["max_active"]),
            "--chunk-size", str(e["chunk_size"]),
            "--chunk-budget", str(e["chunk_budget"]),
            "--max-new-tokens", str(mix["output"]["max"])]


def warm_up(launcher, args, params, spec: dict) -> None:
    """Serve two requests through a throwaway engine built like the timed
    one: compiles the mixed chunk+decode and the decode-only step programs
    and Algorithm 1's decision, then frees its pool."""
    from repro.runtime import serve
    from repro.runtime import engine as engine_mod

    e = spec["engine"]
    warm = launcher.build(args, params=params)
    src = _WarmSource(warm.engine, spec["vocab_size"], e["chunk_size"],
                      3 * e["steps_per_slot"] + 1)
    with contextlib.suppress(WindowClosed):
        serve(warm.engine, warm.sched, src, horizon=10 ** 9,
              steps_per_slot=e["steps_per_slot"], chunked=True)
    warm.engine.drain()
    # preemption's device-side row clear, so a preemption in the window
    # does not compile there
    clear = getattr(engine_mod, "_sync_clear", None)
    if clear is not None:
        R = e["max_active"]
        jax.block_until_ready(clear(warm.engine.sync,
                                    jax.numpy.full((R,), R, jax.numpy.int32)))
    del warm, src
    gc.collect()


# ------------------------------------------------------------- the window
@dataclasses.dataclass
class Served:
    """One request due in the window, as the benchmark saw it."""

    rid: int
    arrival_s: float          # scheduled, from the window's start
    prompt: np.ndarray
    max_new_tokens: int
    refused: bool
    req: object = None        # the program's Request once admitted

    @property
    def finished(self) -> bool:
        r = self.req
        return r is not None and r.finish_slot is not None and r.generated is not None


@dataclasses.dataclass
class RunData:
    """What one run measured; every metric reader takes this."""

    spec: dict
    arch: object              # the configuration's architecture module
    mix: dict
    seconds: float
    n_steps: int
    chunk: int
    setup_s: float
    compile_s: float
    served: list              # [Served]
    starts: dict              # slot -> host time the slot's call began
    ends: dict                # slot -> host time the slot's call returned
    t0: float
    drain_end: float
    counters: dict
    peak: dict                # peaks.json entry of the device
    dispatching: frozenset = frozenset()   # slots that dispatched work
    trace: dict | None = None  # trace_reduce.reduce() of the traced segment
    traced_from: float | None = None
    traced_to: float | None = None

    def landing(self, slot: int) -> float:
        """Host time at which slot ``slot``'s results reached the host."""
        return self.ends.get(slot + 1, self.drain_end)

    def token_slot(self, r, k: int) -> int:
        """Slot whose dispatch computed token k of request ``r``."""
        n = self.n_steps
        return r.first_token_slot + max(0, math.ceil((k - n) / n))

    def done(self) -> list:
        return [s for s in self.served if s.finished]

    def ttft_s(self, s: Served) -> float:
        return self.landing(s.req.first_token_slot) - (self.t0 + s.arrival_s)

    def tpot_s(self, s: Served) -> float | None:
        n = len(s.req.generated)
        if n < 2:
            return None
        last = self.landing(self.token_slot(s.req, n - 1))
        return (last - self.landing(s.req.first_token_slot)) / (n - 1)

    def queue_wait_s(self, s: Served) -> float:
        return self.starts[s.req.admit_slot] - (self.t0 + s.arrival_s)

    def window_slots(self) -> list:
        end = self.t0 + self.seconds
        return [t for t, st in self.starts.items() if self.t0 <= st < end]

    def traced_slots(self) -> list:
        if self.traced_from is None:
            return []
        return [t for t, st in self.starts.items()
                if self.traced_from <= st < self.traced_to]

    def traced_work(self) -> list:
        """The device steps (``counts.Work``) of the traced segment's slots."""
        parts = counts.slot_parts(self.arch, self.spec,
                                  [s.req for s in self.done()],
                                  self.n_steps, self.chunk)
        return [w for t in self.traced_slots() for w in parts.get(t, [])]


def serve_window(launch, arrivals, seconds: float, n_steps: int, raw_rate: int,
                 seed: int, drain_s: float, traced: bool, on_time=None):
    """Drive the program's serve loop over the window and drain."""
    from repro.runtime import serve

    engine = SlotClock(launch.engine, traced)
    sched = Spans(launch.sched, traced)
    src = WallClockSource(arrivals, seconds, raw_rate, seed, launch.engine,
                          drain_s, traced, on_time)
    t0 = src.start()
    with contextlib.suppress(WindowClosed):
        serve(engine, sched, src, horizon=10 ** 9, steps_per_slot=n_steps,
              chunked=True)
    launch.engine.drain()
    drain_end = clock()
    return src, engine, t0, drain_end


def queue_thirds(data: RunData, queue: dict) -> tuple:
    """Mean pending queue over the first and the last third of the
    window's slots: a queue that grows through the window is over the
    knee."""
    q = [queue[t] for t in sorted(data.window_slots())]
    if not q:
        return math.nan, math.nan
    third = max(1, len(q) // 3)
    return float(np.mean(q[:third])), float(np.mean(q[-third:]))


# ------------------------------------------------------------ correctness
def sample_requests(done: list, seed: int, target_tokens: int) -> list:
    """The longest served request, then others drawn by the seed, until
    ``target_tokens`` served tokens are in the sample."""
    if not done:
        return []
    order = sorted(done, key=lambda s: (-(len(s.prompt) + len(s.req.generated)), s.rid))
    pick = [order[0]]
    rest = order[1:]
    rng = np.random.default_rng(np.random.SeedSequence([int(seed) & (2 ** 63 - 1), 11]))
    rng.shuffle(rest)
    total = len(pick[0].req.generated)
    for s in rest:
        if total >= target_tokens:
            break
        pick.append(s)
        total += len(s.req.generated)
    return pick


def _gap_stats(gaps: list) -> dict:
    g = np.concatenate(gaps) if gaps else np.zeros(0)
    return {"logit_gap_mean": float(g.mean()) if g.size else None,
            "logit_gap_max": float(g.max()) if g.size else None,
            "compared_tokens": int(g.size)}


def compare(arch, spec: dict, flat: dict, sample: list, pad_to: int,
            rows_pad: int, control: bool = False) -> dict:
    """Gaps by which each served token's reference logit lies below the
    reference's best at its position (0 where they agree), over the sample:
    the widest (the number compared) and their mean over every compared
    position. With ``control`` the same is read, under ``"control"``, for the
    token the int8 reference puts first at each position of the same
    prompts and served tokens."""
    prog, ctrl = [], []
    for s in sample:
        toks = np.asarray(s.req.generated, np.int32)
        ids = np.concatenate([s.prompt, toks[:-1]]).astype(np.int32)
        rows = np.arange(len(s.prompt) - 1, len(ids))
        ref = arch.logits_at(spec, flat, ids, rows, pad_to, rows_pad)
        prog.append(reference.served_gaps(ref, toks))
        if control:
            low = arch.logits_at(spec, flat, ids, rows, pad_to, rows_pad,
                                 int8=True)
            ctrl.append(reference.served_gaps(ref, low.argmax(axis=-1)))
    out = {"program": _gap_stats(prog), "compared_requests": len(sample)}
    if control:
        out["control"] = _gap_stats(ctrl)
    return out


def token_faults(served: list, vocab: int) -> int:
    """Served requests whose tokens are not max_new_tokens in-vocab ids."""
    bad = 0
    for s in served:
        if not s.finished:
            continue
        g = s.req.generated
        if len(g) != s.max_new_tokens or any(not 0 <= t < vocab for t in g):
            bad += 1
    return bad


# ---------------------------------------------------------------- the run
def _device_info(count: int, peak_bytes: int | None) -> dict:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": count,
            "memory_peak_bytes": peak_bytes}


def readers(kind: str, bench_dir: Path = BENCH_DIR) -> dict:
    """{metric name: read function} from ``bench_dir/kind/<name>.py``."""
    import importlib.util

    out = {}
    for path in sorted((Path(bench_dir) / kind).glob("*.py")):
        spec = importlib.util.spec_from_file_location(f"{kind}.{path.stem}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        out[path.stem] = mod.read
    return out


def read_metrics(entries: list, kind: str, data: RunData,
                 bench_dir: Path = BENCH_DIR) -> dict:
    """{name: {"value", "unit"}} for each manifest entry whose reader
    finds something to read."""
    found = readers(kind, bench_dir)
    out = {}
    for m in entries:
        if m["name"] not in found:
            raise KeyError(f"no reader {kind}/{m['name']}.py under {bench_dir}")
        v = found[m["name"]](data)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def say(*parts) -> None:
    print("bench:", *parts, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Setup:
    """A cell made ready to serve: launcher arguments, weights, compiles."""

    cell: Cell
    launcher: object
    args: object
    flat: dict
    params: dict
    compiles: CompileLog
    peak: dict


def prepare(cell: Cell, seed: int, peak_table: dict | None = None) -> Setup:
    """Weights from the seed and a warm-up of every step program."""
    from repro.launch import serve as launcher
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    compiles = CompileLog()
    spec, arch = cell.spec, cell.arch
    peak = peak_table or counts.peaks(jax.devices()[0].device_kind)
    cfg = model_cfg.register(spec, arch)
    args = launcher.parse_args(launcher_argv(spec, cell.mix))
    flat = weights.make_flat(arch, spec, seed)
    params = arch.to_program(flat)
    weights.check_layout(params, cfg)
    warm_up(launcher, args, params, spec)
    jax.block_until_ready(flat)
    return Setup(cell, launcher, args, flat, params, compiles, peak)


def reseed(setup: Setup, seed: int) -> None:
    """New weights for ``seed`` in place of the old (freed first)."""
    setup.flat = setup.params = None
    gc.collect()
    arch = setup.cell.arch
    setup.flat = weights.make_flat(arch, setup.cell.spec, seed)
    setup.params = arch.to_program(setup.flat)
    jax.block_until_ready(setup.flat)


def window(setup: Setup, seed: int, seconds: float, t_start: float,
           trace: bool = False, rate_per_s: float | None = None,
           drain_s: float = 90.0) -> tuple:
    """Serve one open-loop window on a fresh engine; returns (RunData,
    notes). The engine, its pool and the scheduler are freed on return."""
    spec, mix, e = setup.cell.spec, setup.cell.mix, setup.cell.spec["engine"]
    launch = setup.launcher.build(setup.args, params=setup.params)
    arrivals = traffic_gen.generate(mix, seed, seconds, spec["vocab_size"],
                                    rate_per_s=rate_per_s)
    served = {a.rid: Served(a.rid, a.at_s, a.prompt, a.max_new_tokens, False)
              for a in arrivals}
    compile_s, lowered = setup.compiles.seconds, setup.compiles.lowerings
    prof = {"dir": None, "from": None, "to": None, "span": None}

    def stop() -> None:
        prof["to"] = clock()
        prof["span"].__exit__(None, None, None)
        jax.profiler.stop_trace()

    def on_time(now: float) -> None:
        # the traced run profiles TRACE_S seconds about the window's middle
        mid = max(0.0, (seconds - TRACE_S) / 2)
        if prof["dir"] is None and now >= mid:
            prof["dir"] = tempfile.mkdtemp(prefix="bench-trace-")
            jax.profiler.start_trace(prof["dir"])
            prof["span"] = jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN)
            prof["span"].__enter__()
            prof["from"] = clock()
        elif prof["to"] is None and prof["from"] is not None and now >= mid + TRACE_S:
            stop()

    src, eng, t0, drain_end = serve_window(
        launch, arrivals, seconds, e["steps_per_slot"], e["raw_rate"], seed,
        drain_s, trace, on_time if trace else None)
    if prof["from"] is not None and prof["to"] is None:
        stop()
    for r in launch.engine.finished:
        served[r.rid].req = r
    for rid in src.refused:
        served[rid].refused = True
    stats = jax.devices()[0].memory_stats() or {}
    notes = {"window_lowerings": setup.compiles.lowerings - lowered,
             "late_s": np.asarray(src.late_s or [0.0]),
             "peak_bytes": stats.get("peak_bytes_in_use"),
             "queue": eng.queue, "dispatching": eng.dispatching,
             "rates": list(launch.sched.rate_history)}
    data = RunData(spec=spec, arch=setup.cell.arch, mix=mix, seconds=seconds,
                   n_steps=e["steps_per_slot"], chunk=e["chunk_size"],
                   setup_s=t0 - t_start, compile_s=compile_s,
                   served=list(served.values()), starts=eng.starts,
                   ends=eng.ends, t0=t0, drain_end=drain_end,
                   counters=launch.engine.counters(), peak=setup.peak,
                   dispatching=frozenset(eng.dispatching),
                   traced_from=prof["from"], traced_to=prof["to"])
    # the program's state goes before the reference runs: pools, engine,
    # scheduler (the weights are the benchmark's and stay)
    del launch, eng, src
    gc.collect()
    if prof["dir"] is not None:
        data.trace = trace_reduce.reduce(trace_reduce.load(
            trace_reduce.find_xplane(prof["dir"])))
        shutil.rmtree(prof["dir"], ignore_errors=True)
    return data, notes


def compared(setup: Setup, data: RunData, seed: int, control: bool = False) -> dict:
    """``compare`` over the sample of served requests a run draws."""
    spec, mix = setup.cell.spec, setup.cell.mix
    _, CL = traffic_gen.bucket(mix, spec["engine"]["page_size"])
    sample = sample_requests(data.done(), seed, setup.cell.limits["sample_tokens"])
    return compare(setup.cell.arch, spec, setup.flat, sample, CL,
                   mix["output"]["max"], control)


def check(setup: Setup, data: RunData, seed: int, control: bool = False,
          cmp: dict | None = None) -> dict:
    """The comparison that decides ``correct``: each number with its limit,
    and whether all hold. With ``control`` the int8 reference's tokens stand
    in the program's place. ``cmp`` is a ``compared`` result to reuse."""
    limits = setup.cell.limits
    admitted = [s for s in data.served if not s.refused]
    failed = sum(1 for s in admitted if not s.finished)
    bad = token_faults(data.served, setup.cell.spec["vocab_size"])
    cmp = cmp or compared(setup, data, seed, control)
    got = cmp["control" if control else "program"]
    checks = {
        "logit_gap_max": {"value": got["logit_gap_max"],
                          "limit": limits["logit_gap_max"]},
        "unfinished": {"value": failed, "limit": 0},
        "bad_token_requests": {"value": bad, "limit": 0},
        "compared_tokens": {"value": got["compared_tokens"],
                            "limit": limits["sample_tokens"]},
    }
    correct = (got["logit_gap_max"] is not None
               and got["logit_gap_max"] <= limits["logit_gap_max"]
               and failed == 0 and bad == 0
               and got["compared_tokens"] >= limits["sample_tokens"])
    return {"correct": correct, "failed": failed, "checks": checks,
            "compared_requests": cmp["compared_requests"],
            "logit_gap_mean": got["logit_gap_mean"]}


def run(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
        peak_table: dict | None = None) -> dict:
    """One run of ``cell``; returns the result line's object."""
    setup = prepare(cell, seed, peak_table)
    data, notes = window(setup, seed, seconds, t_start, trace)
    verdict = check(setup, data, seed)
    if trace:
        metrics = read_metrics(cell.per_layer, "layer_metrics", data)
    else:
        metrics = read_metrics(cell.end_to_end, "e2e_metrics", data)
    device = _device_info(len(jax.devices()), notes["peak_bytes"])
    result = {"correct": bool(verdict["correct"]), "attempted": len(data.served),
              "failed": verdict["failed"], "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = data.trace["busy_s"]
        device["window_s"] = data.trace["window_s"]
        result["breakdown"] = {"device_ops": data.trace["device_ops"],
                               "idle_gaps": data.trace["idle_gaps"]}
    c, late = data.counters, notes["late_s"]
    q0, q1 = queue_thirds(data, notes["queue"])
    say(f"cell={cell.name} seed={seed} seconds={seconds} trace={int(trace)} "
        f"sent={len(data.served)} "
        f"refused={sum(s.refused for s in data.served)} "
        f"served={len(data.done())} "
        f"slots_in_window={len(data.window_slots())} "
        f"setup_s={data.setup_s:.3f} compile_s={data.compile_s:.3f} "
        f"cache_hits={setup.compiles.hits} cache_misses={setup.compiles.misses} "
        f"window_lowerings={notes['window_lowerings']} "
        f"preemptions={c['preemptions']} readback_waits={c['readback_waits']} "
        f"peak_pages={c['peak_pages']}/{cell.spec['engine']['num_pages']} "
        f"queue_thirds={q0:.3f}->{q1:.3f} "
        f"peak_bytes={notes['peak_bytes']} "
        f"poll_late_p50_ms={1e3 * np.median(late):.3f} "
        f"poll_late_max_ms={1e3 * late.max():.3f} "
        f"compared_requests={verdict['compared_requests']} "
        f"logit_gap_mean={verdict['logit_gap_mean']}")
    for k, v in verdict["checks"].items():
        say(f"check {k}={v['value']} limit={v['limit']}")
    result["checks"] = verdict["checks"]
    return result
