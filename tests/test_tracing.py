"""Host spans, named device scopes and wall stamps (DESIGN.md §11).

* ``span()``: the disabled recorder hands back one shared no-op context and
  stays inside the no-op overhead budget; a live one records each span on
  the Unix-epoch clock the profiler stamps host events with;
* the serve loop over ``PagedEngine.step_slot_chunked`` records the
  serve-loop, scheduler and engine spans of every slot in order, with the
  host's wait on the device nested in the readback consume;
* every retired request carries ``arrival_us <= admit_us <= first_token_us
  <= finish_us``, preemptions included, with the recorder on or off;
* the step programs carry the ``chunk``/``decode`` and ``attn``/``ffn``
  scopes in their ops' ``op_name`` metadata;
* ``--metrics`` exports the stamps as the two latency histograms.
"""
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch import serve as launcher
from repro.models import init_params
from repro.models import model as M
from repro.obs import (EVENT_KINDS, NULL_TRACE, OBS_OFF, Observability,
                       TraceRecorder, observability, parse_prometheus)
from repro.runtime import (AdaptiveScheduler, PagedEngine, PagedEngineConfig,
                           RequestSource, serve)
from repro.runtime import engine as E
from repro.runtime.request import Request

_CACHE = {}
SLOT_SPANS = ("control.decide", "control.admit", "engine.admit", "engine.plan",
              "engine.pages", "engine.upload", "engine.dispatch",
              "engine.readback")


def _model():
    if "m" not in _CACHE:
        cfg = get_config("granite-3-2b", smoke=True)
        _CACHE["m"] = (cfg, init_params(jax.random.PRNGKey(0), cfg))
    return _CACHE["m"]


def _paged(obs=None, num_pages=32):
    cfg, params = _model()
    return PagedEngine(cfg, params, PagedEngineConfig(
        prompt_len=16, cache_len=48, page_size=8, num_pages=num_pages,
        max_active=4, chunk_size=8), obs=obs)


def _serve(eng, obs, horizon=8):
    cfg, _ = _model()
    sched = AdaptiveScheduler(rates=(1.0, 2.0), V=5.0, obs=obs)
    src = RequestSource(vocab_size=cfg.vocab_size, prompt_len=16, raw_rate=2,
                        max_new_tokens=4, min_prompt_len=4, seed=3)
    return serve(eng, sched, src, horizon=horizon, steps_per_slot=2,
                 chunked=True)


# ------------------------------------------------------------------- span API
def test_null_span_is_inert():
    a = NULL_TRACE.span("engine.dispatch", slot=1, what="decode")
    b = NULL_TRACE.span("serve.slot", slot=2)
    assert a is b                  # one shared no-op context
    with a as sp:
        sp.set(retired=3)
    assert len(NULL_TRACE) == 0
    n = 50_000
    t0 = time.perf_counter()
    for i in range(n):
        with NULL_TRACE.span("engine.dispatch", slot=i, pid=0):
            pass
    assert (time.perf_counter() - t0) / n < 5e-6


def test_span_records_on_the_epoch_clock():
    tr = TraceRecorder(capacity=8)
    before = time.time_ns() / 1e3
    with tr.span("engine.consume", slot=3, pid=1) as sp:
        with tr.span("engine.wait", slot=3, pid=1):
            time.sleep(0.002)
        sp.set(retired=2)
    after = time.time_ns() / 1e3
    wait, consume = tr.events()     # a span lands when it closes
    assert (wait["kind"], consume["kind"]) == ("engine.wait", "engine.consume")
    assert before <= consume["ts"] <= wait["ts"]
    assert wait["ts"] + wait["dur"] <= consume["ts"] + consume["dur"] <= after
    assert wait["dur"] >= 2000 and consume["retired"] == 2
    assert consume["slot"] == 3 and consume["pid"] == 1
    ev = next(e for e in tr.chrome_trace()["traceEvents"]
              if e["name"] == "engine.consume")
    assert ev["ph"] == "X" and ev["ts"] == consume["ts"]   # exported as is


def test_span_overhead_budget_on_the_chunked_loop():
    """The paged chunked loop (every span of DESIGN.md §11 on its path)
    with everything recorded stays within the serve-loop budget of the
    same loop with the recorder off."""
    _serve(_paged(), None, horizon=4)          # compile off the clock
    on, off = [], []
    for _ in range(3):
        t0 = time.perf_counter()
        _serve(_paged(), None)
        off.append(time.perf_counter() - t0)
        obs = observability()
        t0 = time.perf_counter()
        _serve(_paged(obs), obs)
        on.append(time.perf_counter() - t0)
    assert min(on) <= min(off) * 1.05 + 0.05, (on, off)


# ------------------------------------------------------------- engine spans
def test_chunked_slot_records_spans_in_order():
    obs = Observability(trace=TraceRecorder())
    eng = _paged(obs)
    t_start = time.time_ns() / 1e3
    _serve(eng, obs)
    ev = obs.trace.events()
    assert {e["kind"] for e in ev} <= set(EVENT_KINDS)
    spans = [e for e in ev if "dur" in e]
    assert all(t_start <= e["ts"] <= time.time_ns() / 1e3 for e in spans)
    slots = {e["slot"]: e for e in spans if e["kind"] == "serve.slot"}
    assert sorted(slots) == list(range(8))
    dispatched = 0
    for t, outer in slots.items():
        lo, hi = outer["ts"], outer["ts"] + outer["dur"]
        inside = sorted((e for e in spans
                         if e is not outer and lo <= e["ts"] <= hi),
                        key=lambda e: e["ts"])
        assert all(e["ts"] + e["dur"] <= hi for e in inside)
        kinds = [e["kind"] for e in inside
                 if e["kind"] not in ("engine.consume", "engine.wait")]
        if "engine.dispatch" in kinds:
            dispatched += 1
            assert kinds == list(SLOT_SPANS), (t, kinds)
            assert all(e["slot"] == t for e in inside
                       if e["kind"] in SLOT_SPANS)
    assert dispatched >= 5
    consumes = [e for e in spans if e["kind"] == "engine.consume"]
    waits = [e for e in spans if e["kind"] == "engine.wait"]
    assert consumes and len(waits) == len(consumes)
    for w in waits:   # each wait nests in the consume of the same packet
        c = next(c for c in consumes if c["slot"] == w["slot"])
        assert c["ts"] <= w["ts"] and w["ts"] + w["dur"] <= c["ts"] + c["dur"]
    assert sum(c["retired"] for c in consumes) == len(eng.finished)


@pytest.mark.parametrize("traced", [False, True])
def test_wall_stamps_ordered_through_preemption(traced):
    """A pool too small for four rows preempts; every retired request
    still carries its stamps in order (the re-admission restamps them),
    whether or not a recorder is live."""
    obs = observability() if traced else OBS_OFF
    eng = _paged(obs, num_pages=7)
    rng = np.random.default_rng(5)
    reqs = [Request(rid=i, arrival_slot=0, max_new_tokens=10,
                    tokens=rng.integers(0, 256, 14, dtype=np.int32))
            for i in range(6)]
    eng.submit(reqs)
    t = 0
    while len(eng.finished) < len(reqs) and t < 200:
        eng.step_slot_chunked(t, n_steps=2)
        t += 1
    eng.drain()
    assert len(eng.finished) == len(reqs) and eng.preemptions > 0
    for r in eng.finished:
        assert r.arrival_us <= r.admit_us <= r.first_token_us <= r.finish_us
        assert r.first_token_slot < r.finish_slot or \
            r.first_token_us == r.finish_us


def test_unadmit_clears_admission_stamps():
    r = Request(rid=0, arrival_slot=0, tokens=np.zeros(4, np.int32))
    r.arrival_us, r.admit_us, r.first_token_us = 1.0, 2.0, 3.0
    r.admit_slot = r.start_slot = r.first_token_slot = 1
    r.generated = [7]
    r.unadmit()
    assert r.arrival_us == 1.0 and r.generated is None
    assert r.admit_us is r.first_token_us is r.admit_slot is None
    assert r.start_slot is r.first_token_slot is None


# --------------------------------------------------------------- device scopes
def _op_names(fn, *args, **kw) -> set:
    text = fn.lower(*args, **kw).compile().as_text()
    return set(re.findall(r'op_name="([^"]*)"', text))


def test_step_programs_carry_named_scopes():
    eng = _paged()
    R, C = 4, 8
    state = M.PagedDecodeState(
        pools=eng.pools, block_tables=jnp.asarray(eng.block_tables),
        pos=jnp.asarray(eng.pos), last_tok=jnp.zeros_like(eng.sync.cur_tok))
    z = jnp.zeros(R, jnp.int32)
    mixed = _op_names(E._chunk_decode_sync_paged, eng.params, state, eng.sync,
                      jnp.zeros((R, C), jnp.int32), z, z, z,
                      jnp.zeros(R, bool), z, None, n=2, cfg=eng.cfg,
                      sig=eng._sig)
    decode = _op_names(E._decode_n_sync_paged, eng.params, state, eng.sync,
                       None, n=2, cfg=eng.cfg, sig=eng._sig)

    def has(names, *parts):
        return any(all(f"/{p}/" in n for p in parts) for n in names)

    for outer in ("chunk", "decode"):
        for inner in ("attn", "ffn"):
            assert has(mixed, outer, inner), (outer, inner)
    assert has(decode, "decode", "attn") and has(decode, "decode", "ffn")
    assert not has(decode, "chunk")


# ------------------------------------------------------------------- launcher
def test_build_takes_an_observability_bundle():
    args = launcher.parse_args(["--arch", "granite-3-2b", "--smoke", "--paged",
                                "--chunked", "--horizon", "4"])
    obs = Observability(trace=TraceRecorder())
    launch = launcher.build(args, params=_model()[1], obs=obs)
    assert launch.obs is obs and launch.engine.obs is obs and launch.telemetry
    launcher.run(args, launch)
    kinds = {e["kind"] for e in obs.trace.events()}
    assert {"serve.slot", "control.decide", "engine.dispatch"} <= kinds
    off = launcher.build(args, params=_model()[1])
    assert off.obs is OBS_OFF and not off.telemetry


def test_metrics_export_latency_histograms(capsys):
    launcher.main(["--arch", "granite-3-2b", "--smoke", "--paged", "--chunked",
                   "--horizon", "12", "--metrics"])
    out = capsys.readouterr().out
    text = out[out.index("# HELP"):]
    got = parse_prometheus(text)
    served = got["repro_requests_finished"]
    assert served > 0
    for name in ("repro_prefill_seconds", "repro_first_token_seconds"):
        assert f"# TYPE {name} histogram" in text
        assert got[f"{name}_count"] == served
        assert got[f'{name}_bucket{{le="+Inf"}}'] == served
    assert got["repro_first_token_seconds_sum"] >= got["repro_prefill_seconds_sum"] > 0
