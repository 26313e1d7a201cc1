"""xspace_scopes and the readers of the program's spans, scopes and stamps.

The traces were recorded on a TPU v5e: ``scoped.xplane.pb`` and its ring
``scoped.ring.json`` by ``make_scoped_fixture.py`` (a chain of matmuls under
``chunk``/``decode`` and ``attn``/``ffn`` scopes, dispatched and awaited
inside the program's own spans), ``step.xplane.pb`` by
``make_trace_fixture.py`` (no scopes). The stamps are read through the
harness at the tiny fixture cell on the CPU.
"""
import json
import time
from types import SimpleNamespace

import benchpath
import numpy as np
import pytest

import harness
import trace_reduce
import xspace_scopes

FIX = benchpath.FIXTURES
SCOPED = FIX / "scoped.xplane.pb"
LAYERS = ("chunk/attn", "chunk/ffn", "decode/attn", "decode/ffn")


@pytest.fixture(scope="module")
def scoped():
    pd, xp = trace_reduce.load(SCOPED), xspace_scopes.read(SCOPED)
    scopes, ambiguous = xspace_scopes.op_scopes(xp)
    return pd, xp, scopes, ambiguous


def test_unscoped_trace_still_decodes():
    pd, xp = trace_reduce.load(FIX / "step.xplane.pb"), \
        xspace_scopes.read(FIX / "step.xplane.pb")
    assert any(p["name"] == "/device:TPU:0" for p in xp)
    scopes, ambiguous = xspace_scopes.op_scopes(xp)
    names = {n for v in xspace_scopes.device_events(pd).values() for _, _, n in v}
    assert names <= set(scopes) and ambiguous == 0
    assert {xspace_scopes.scope_of(scopes[n]) for n in names} == {"other"}
    split = xspace_scopes.scope_split(pd, scopes)
    assert split["chunk"] == split["decode"] == 0
    assert split["other"] == pytest.approx(trace_reduce.reduce(pd)["busy_s"], rel=1e-6)


def test_scope_of_reads_two_levels():
    f = xspace_scopes.scope_of
    assert f("jit(s)/chunk/while/body/closed_call/attn/dot_general") == "chunk/attn"
    assert f("jit(s)/decode/while/body/closed_call/while/body/ffn/add") == "decode/ffn"
    assert f("jit(s)/chunk/jit(clip)/min") == "chunk"
    assert f("jit(s)/attn/dot_general") == f("") == f(None) == "other"


def test_each_scope_gets_its_own_fusions(scoped):
    pd, _, scopes, ambiguous = scoped
    assert ambiguous == 0
    by = {}
    for plane_ops in xspace_scopes.device_events(pd).values():
        for _, _, name in plane_ops:
            by.setdefault(xspace_scopes.scope_of(scopes[name]), set()).add(
                name.split(" ")[0])
    assert set(LAYERS) <= set(by)
    fusions = [by[k] for k in LAYERS]
    assert all(fusions) and sum(map(len, fusions)) == len(set().union(*fusions))


def test_scoped_self_time_sums_to_busy(scoped):
    pd, _, scopes, _ = scoped
    split = xspace_scopes.scope_split(pd, scopes)
    busy = trace_reduce.reduce(pd)["busy_s"]
    assert split["chunk"] + split["decode"] + split["other"] == \
        pytest.approx(busy, rel=0.01)
    for step in ("chunk", "decode"):
        parts = split[f"{step}/attn"] + split[f"{step}/ffn"]
        assert 0 < parts <= split[step] * (1 + 1e-9)
    # four matmul steps per half, the same shapes: the halves match
    assert split["chunk"] == pytest.approx(split["decode"], rel=0.2)
    assert split["chunk"] + split["decode"] >= 0.9 * busy


def test_idle_gaps_charged_to_program_spans(scoped):
    pd = scoped[0]
    r = trace_reduce.reduce(pd)
    gaps = dict(xspace_scopes.idle_by_span(pd))
    # a gap goes whole to the span holding its midpoint: the first round's
    # planning sleep opens the window; each later one follows a poll sleep
    # in the same gap, whose midpoint lies in the poll
    assert gaps["engine.plan"] >= 0.003 * 0.9
    assert gaps["bench.poll"] >= 3 * 0.004 * 0.9
    assert dict(r["idle_gaps"])["host.other"] == pytest.approx(gaps["engine.plan"])
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6)


def test_ring_lines_up_with_the_profile(scoped):
    """The recorder's epoch microseconds less the profile's start are the
    profile's own offsets: the ring's and the profile's engine.dispatch
    spans start within 50 us of each other."""
    pd, xp, _, _ = scoped
    start = xspace_scopes.profile_start_ns(xp)
    ring = json.loads((FIX / "scoped.ring.json").read_text())
    got = sorted(e["ts"] * 1e3 - start for e in ring if e["kind"] == "engine.dispatch")
    prof = sorted(s for s, _, n in trace_reduce.host_spans(pd, "engine.dispatch"))
    assert len(got) == len(prof) == 3
    assert float(np.median(np.abs(np.subtract(got, prof)))) < 50e3


# ------------------------------------------------------------------ readers
def _run(**kw):
    base = dict(trace=None, ring=None, t0=0.0, seconds=10.0, starts={},
                dispatching=frozenset(), served=[])
    base.update(kw)
    run = SimpleNamespace(**base)
    run.traced_slots = lambda: sorted(run.starts)
    run.done = lambda: [s for s in run.served if s.req is not None]
    return run


def test_device_scope_readers(scoped):
    pd, _, scopes, _ = scoped
    read = harness.readers("layer_metrics")
    split = xspace_scopes.scope_split(pd, scopes)
    run = _run(trace={"scopes": split}, starts={0: 0.0, 1: 1.0, 2: 2.0})
    assert read["chunk_device_ms"](run) == pytest.approx(1e3 * split["chunk"] / 3)
    assert read["decode_device_ms"](run) == pytest.approx(1e3 * split["decode"] / 3)
    # a program without scopes (all "other") and a trace without a split
    # leave both metrics out of the line
    flat = {"chunk": 0.0, "decode": 0.0, "other": 1.0}
    for trace in ({"scopes": flat}, {"busy_s": 1.0}, None):
        run = _run(trace=trace, starts={0: 0.0})
        assert read["chunk_device_ms"](run) is None
        assert read["decode_device_ms"](run) is None


def test_slot_host_ms_reads_the_ring():
    ring = json.loads((FIX / "scoped.ring.json").read_text())
    slots = {e["slot"]: e for e in ring if e["kind"] == "serve.slot"}
    waits = {e["slot"]: e["dur"] for e in ring if e["kind"] == "engine.wait"}
    read = harness.readers("layer_metrics")["slot_host_ms"]
    run = _run(ring=ring, starts={t: float(t) for t in slots},
               dispatching=frozenset({0, 1, 2}))
    want = [slots[t]["dur"] - waits[t] for t in (0, 1, 2)]
    assert read(run) == pytest.approx(1e-3 * float(np.median(want)))
    assert all(w > 3000 for w in want)               # the planning sleep
    # slots that dispatched nothing, or began outside the window, stay out
    run.dispatching, run.seconds = frozenset({0, 2}), 1.5
    assert read(run) == pytest.approx(1e-3 * want[0])
    assert read(_run(starts=run.starts, dispatching=run.dispatching)) is None


def test_prefill_p50_ms_reads_the_stamps():
    read = harness.readers("layer_metrics")["prefill_p50_ms"]
    reqs = [SimpleNamespace(admit_us=1e6 * i, first_token_us=1e6 * i + d)
            for i, d in enumerate((2000.0, 5000.0, 9000.0))]
    run = _run(served=[SimpleNamespace(req=r) for r in reqs])
    assert read(run) == pytest.approx(5.0)
    # requests a program without stamps served: left out of the line
    old = [SimpleNamespace(req=SimpleNamespace(finish_slot=3))]
    assert read(_run(served=old)) is None


# ----------------------------------------------------------- through harness
@pytest.fixture(scope="module")
def tiny_window():
    spec = json.loads((FIX / "configs" / "tiny-dense.json").read_text())
    man = {"workloads": [{"name": "tiny-chat", "config": "tiny-dense",
                          "traffic": "tiny-chat", "chips": 1, "why": "t"}],
           "configs": [{"name": "tiny-dense", "reduced": spec["reduced"]}],
           "end_to_end": [], "per_layer": []}
    cell = harness.load_cell(man, "tiny-chat", FIX)
    setup = harness.prepare(cell, 5, {"bf16_flops_per_s": 1e12,
                                      "hbm_bytes_per_s": 1e11})
    data, _ = harness.window(setup, 5, 3.0, time.perf_counter())
    # one reading of both clocks puts the program's epoch stamps on the
    # harness's perf_counter
    offset_us = time.time_ns() / 1e3 - time.perf_counter() * 1e6
    return data, offset_us


def test_first_token_lands_no_later_than_the_harness_says(tiny_window):
    data, offset_us = tiny_window
    done = data.done()
    assert done
    for s in done:
        r = s.req
        assert r.arrival_us <= r.admit_us <= r.first_token_us <= r.finish_us
        first = (r.first_token_us - offset_us) * 1e-6
        assert first <= data.landing(r.first_token_slot) + 2e-3
        assert first >= data.starts[r.first_token_slot] - 2e-3
    got = harness.read_metrics([{"name": "prefill_p50_ms", "unit": "ms"}],
                               "layer_metrics", data)
    want = np.median([1e-3 * (s.req.first_token_us - s.req.admit_us) for s in done])
    assert got["prefill_p50_ms"]["value"] == pytest.approx(want)
