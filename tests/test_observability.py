"""The ``repro.obs`` subsystem: registry, trace ring, decision log.

Covers the PR-7 acceptance contract layer by layer:

  * metrics: counter/gauge/histogram semantics, label hygiene, the
    snapshot <-> Prometheus text exposition round-trip (``parse_prometheus``
    is the CI validator, so its strictness is pinned here too),
  * trace: bounded ring overflow accounting, event ordering, the Chrome
    trace export structure Perfetto consumes (pid = replica, tid = row),
  * counters surface: dense and paged engines expose the SAME key set —
    paged-only counters (preemptions, pages_*) are present-as-zero on the
    dense engine, never missing — and fleets aggregate it label-wise,
  * decision log: every scheduler argmax recorded with the decomposition
    that explains it (sync-free control records the one-slot lag), and
    ``replay_rollout`` regenerating the Fig.-2 backlog/rate trajectory
    BIT-identically to the lax.scan rollout — the acceptance check that
    the decision log really captures the controller the analysis runs,
  * overhead: the disabled (NullRecorder/OBS_OFF) path stays within the
    5% budget on the sync-free serve loop,
  * latency: queue-wait percentiles and the preemption-reset TTFT path
    after a fleet requeue (the re-admission restamps ``admit_slot``).
"""
import json

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.control import (DriftPlusPenalty, FleetRouter, LatencyAware,
                           MemoryAware, Static, TokenBacklogAware)
from repro.control.rollout import rollout
from repro.models import init_params
from repro.obs import (EVENT_KINDS, GAUGE_KEYS, NULL_TRACE, OBS_OFF,
                       DecisionLog, MetricsRegistry, NullRecorder,
                       TraceRecorder, export_counters, observability,
                       parse_prometheus, replay_rollout)
from repro.runtime import (AdaptiveScheduler, Engine, EngineConfig,
                           PagedEngine, PagedEngineConfig, ReplicaFleet,
                           RequestSource, StaticScheduler, latency_stats,
                           serve)
from repro.runtime.request import Request

KEY = jax.random.PRNGKey(0)
_CACHE = {}


def _setup():
    if "m" not in _CACHE:
        cfg = get_config("granite-3-2b", smoke=True)
        _CACHE["m"] = (cfg, init_params(KEY, cfg))
    return _CACHE["m"]


def _reqs(n=6, plen=8, seed=0, max_new=3):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, arrival_slot=0,
                    tokens=rng.integers(0, 256, plen, dtype=np.int32),
                    max_new_tokens=max_new) for i in range(n)]


# ------------------------------------------------------------------ metrics
def test_registry_counter_gauge_labels():
    reg = MetricsRegistry()
    c = reg.counter("repro_widgets_total", "widgets", labels=("replica",))
    c.inc(replica="0")
    c.inc(2, replica="0")
    c.inc(replica="1")
    assert c.get(replica="0") == 3.0 and c.get(replica="1") == 1.0
    g = reg.gauge("repro_level", "level")
    g.set(0.5)
    g.set(0.25)
    assert g.get() == 0.25
    # same name, same type, same labels -> the SAME family object
    assert reg.counter("repro_widgets_total", labels=("replica",)) is c
    # re-registration with a different type or label set is an error
    with pytest.raises(ValueError):
        reg.gauge("repro_widgets_total", labels=("replica",))
    with pytest.raises(ValueError):
        reg.counter("repro_widgets_total", labels=("zone",))
    # undeclared labels are rejected at the sample site
    with pytest.raises(ValueError):
        c.inc(zone="us")
    with pytest.raises(ValueError):
        MetricsRegistry().counter("bad name")


def test_histogram_cumulative_buckets():
    reg = MetricsRegistry()
    h = reg.histogram("repro_wait", buckets=(1.0, 4.0))
    for x in (0.5, 1.0, 3.0, 100.0):
        h.observe(x)
    snap = reg.snapshot()
    # prometheus semantics: le buckets are cumulative, +Inf == _count
    assert snap['repro_wait_bucket{le="1"}'] == 2
    assert snap['repro_wait_bucket{le="4"}'] == 3
    assert snap['repro_wait_bucket{le="+Inf"}'] == 4
    assert snap["repro_wait_count"] == 4
    assert snap["repro_wait_sum"] == pytest.approx(104.5)
    assert h.get() == {"count": 4, "sum": pytest.approx(104.5)}


def test_prometheus_round_trip():
    reg = MetricsRegistry()
    reg.counter("repro_steps", "slots", ("replica",)).set(7, replica="0")
    reg.counter("repro_steps", labels=("replica",)).set(9, replica="1")
    reg.gauge("repro_occupancy").set(0.625)
    reg.histogram("repro_ttft", buckets=(2.0, 8.0)).observe(3.0)
    text = reg.prometheus_text()
    assert "# TYPE repro_steps counter" in text
    assert "# TYPE repro_occupancy gauge" in text
    assert "# TYPE repro_ttft histogram" in text
    parsed = parse_prometheus(text)
    assert parsed == reg.snapshot()


def test_parse_prometheus_rejects_malformed():
    assert parse_prometheus("# just a comment\n\n") == {}
    for bad in ("no_value_here", "name{unclosed 3", 'm{k="v"} notafloat',
                'm{k=unquoted} 3'):
        with pytest.raises(ValueError):
            parse_prometheus(bad)


def test_export_counters_gauge_split():
    reg = MetricsRegistry()
    export_counters(reg, {"steps": 12, "occupancy": 0.5, "skipme": "str"},
                    labels={"replica": "1"})
    assert reg._metrics["repro_steps"].kind == "counter"
    assert reg._metrics["repro_occupancy"].kind == "gauge"  # GAUGE_KEYS
    assert "occupancy" in GAUGE_KEYS
    snap = reg.snapshot()
    assert snap['repro_steps{replica="1"}'] == 12
    assert "repro_skipme" not in str(snap)
    # repeated export overwrites (running totals), never double-counts
    export_counters(reg, {"steps": 15}, labels={"replica": "1"})
    assert reg.snapshot()['repro_steps{replica="1"}'] == 15


# -------------------------------------------------------------------- trace
def test_trace_ring_overflow_and_order():
    tr = TraceRecorder(capacity=4)
    for i in range(7):
        tr.emit("arrival", rid=i, slot=i)
    assert len(tr) == 4 and tr.dropped == 3
    ev = tr.events()
    assert [e["rid"] for e in ev] == [3, 4, 5, 6]  # oldest dropped first
    tr.clear()
    assert len(tr) == 0 and tr.dropped == 0 and tr.events() == []
    with pytest.raises(ValueError):
        TraceRecorder(capacity=0)


def test_chrome_trace_structure():
    tr = TraceRecorder(capacity=16)
    tr.emit("dispatch", slot=0, pid=1, row=2, ts=10.0, dur=5.0, what="decode")
    tr.emit("retirement", slot=1, rid=7, row=2, pid=1)
    doc = tr.chrome_trace()
    assert doc["otherData"]["dropped_events"] == 0
    evs = doc["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    assert {"name": "process_name", "ph": "M", "pid": 1, "tid": 0,
            "args": {"name": "replica 1"}} in meta
    span = next(e for e in evs if e["ph"] == "X")
    assert span["name"] == "dispatch:decode" and span["dur"] == 5.0
    assert span["pid"] == 1 and span["tid"] == 2
    inst = next(e for e in evs if e["ph"] == "i")
    assert inst["cat"] == "retirement" and inst["args"]["rid"] == 7
    json.dumps(doc)   # the export is pure JSON


def test_null_recorder_is_inert():
    assert NULL_TRACE.enabled is False
    NULL_TRACE.emit("arrival", rid=1)
    assert len(NULL_TRACE) == 0
    assert isinstance(NULL_TRACE, NullRecorder)
    assert OBS_OFF.enabled is False and OBS_OFF.trace is NULL_TRACE
    obs = observability()
    assert obs.enabled and obs.trace.enabled and obs.decisions.enabled


# ---------------------------------------------------------- counters surface
def test_counters_key_parity_dense_vs_paged():
    """Dense and paged engines expose one key set; paged-only counters are
    present-as-zero on dense (preemptions is the satellite's example)."""
    cfg, params = _setup()
    dense = Engine(cfg, params, EngineConfig(batch_slots=4, prompt_len=8,
                                             cache_len=32))
    paged = PagedEngine(cfg, params, PagedEngineConfig(
        prompt_len=8, cache_len=32, page_size=8, num_pages=16, max_active=4))
    cd, cp = dense.counters(), paged.counters()
    assert set(cd) == set(cp)
    assert cd["preemptions"] == 0 and "pages_used" in cd
    assert {"mixed_dispatches", "chunk_rows"} <= set(cd)
    for k in cd:
        assert isinstance(cd[k], (int, float)), k


def test_engine_emits_lifecycle_and_exports():
    cfg, params = _setup()
    obs = observability()
    eng = Engine(cfg, params, EngineConfig(batch_slots=4, prompt_len=8,
                                           cache_len=32), obs=obs)
    reqs = _reqs(5)
    eng.submit(reqs)
    t = 0
    while len(eng.finished) < len(reqs) and t < 40:
        eng.step_slot_sync(t, n_steps=2)
        t += 1
    eng.drain()
    kinds = {e["kind"] for e in obs.trace.events()}
    assert kinds <= set(EVENT_KINDS)
    counts = {k: sum(e["kind"] == k for e in obs.trace.events())
              for k in kinds}
    assert counts["arrival"] == counts["admission"] == len(reqs)
    assert counts["retirement"] == len(reqs)
    # dispatches, readback initiations and consumes are host spans now
    assert min(counts.get(k, 0) for k in
               ("engine.dispatch", "engine.readback", "engine.consume")) >= 1
    eng.export_metrics()
    snap = obs.registry.snapshot()
    assert snap["repro_requests_finished"] == len(reqs)
    assert snap["repro_steps"] == eng.counters()["steps"]
    parse_prometheus(obs.registry.prometheus_text())


def test_fleet_counters_aggregation_and_labels():
    cfg, params = _setup()
    obs = observability()
    mk = lambda: PagedEngine(cfg, params, PagedEngineConfig(
        prompt_len=8, cache_len=32, page_size=8, num_pages=16,
        max_active=4), obs=obs)
    fleet = ReplicaFleet.build(mk, 2, router=FleetRouter(
        decisions=obs.decisions), obs=obs)
    reqs = _reqs(8)
    fleet.submit(reqs)
    t = 0
    while len(fleet.finished) < len(reqs) and t < 40:
        fleet.step_slot_sync(t, n_steps=2)
        t += 1
    fleet.drain()
    agg = fleet.counters()
    per = [e.counters() for e in fleet.replicas]
    # totals sum; level keys fold by max (occupancy is a worst-replica story)
    assert agg["requests_finished"] == sum(c["requests_finished"]
                                           for c in per) == len(reqs)
    assert agg["peak_active"] == max(c["peak_active"] for c in per)
    assert agg["replicas"] == 2 and agg["replicas_alive"] == 2
    assert agg["routed_total"] == len(reqs)
    # labeled export: per-replica families + unlabeled fleet keys coexist
    fleet.export_metrics()
    snap = obs.registry.snapshot()
    assert snap['repro_requests_finished{replica="0"}'] == per[0][
        "requests_finished"]
    assert snap['repro_requests_finished{replica="1"}'] == per[1][
        "requests_finished"]
    assert snap["repro_replicas"] == 2
    parse_prometheus(obs.registry.prometheus_text())
    # route decisions were recorded with per-replica score vectors
    assert len(obs.decisions.routes) == len(reqs)
    assert all(len(r["scores"]) == 2 for r in obs.decisions.routes)


# -------------------------------------------------------------- decision log
def test_decision_log_capacity_and_json_round_trip(tmp_path):
    log = DecisionLog(capacity=4)
    for t in range(6):
        log.record_rate(t=t, backlog=float(t), vq=0.0, V=20.0,
                        chosen=float(t % 3), rates=(1.0, 2.0),
                        drift=(-1.0, -2.0), penalty=(3.0, 4.0), argmax=2.0)
    assert len(log.rates) == 4 and log.rates[0]["t"] == 2  # bounded deque
    log.record_route(rid=9, chosen=1, scores=np.asarray([0.5, 1.5]),
                     loads=[2.0, 1.0], kind="drift", V=20.0)
    path = str(tmp_path / "d.json")
    log.save(path)
    back = DecisionLog.load(path)
    assert [r["chosen"] for r in back.rates] == [
        r["chosen"] for r in log.rates]
    assert back.routes[0]["scores"] == [0.5, 1.5]
    assert back.route_counts(2).tolist() == [0, 1]
    assert "f=     2" in log.explain_rate(-1)
    assert "<-- chosen" in log.explain_rate(-1)


def test_scheduler_records_every_decision():
    """Synchronous control: each slot's recorded ``chosen`` matches the
    applied rate_history entry and the host decomposition's argmax."""
    obs = observability()
    sched = AdaptiveScheduler(rates=(1.0, 2.0, 4.0, 8.0), V=10.0, obs=obs)
    for q in (0, 3, 9, 30, 100):
        sched.control(q)
    recs = list(obs.decisions.rates)
    assert [r["chosen"] for r in recs] == sched.rate_history
    for r in recs:
        assert not r["lagged"]
        assert r["chosen"] == r["argmax"]   # no pipeline lag: they agree
        i = r["rates"].index(r["argmax"])
        T = [p + d for p, d in zip(r["penalty"], r["drift"])]
        assert T[i] == max(T)


def test_scheduler_async_records_lag():
    """Sync-free control applies the PREVIOUS slot's decision; the record
    carries lagged=True and chosen tracks rate_history exactly."""
    obs = observability()
    sched = AdaptiveScheduler(rates=(1.0, 2.0, 4.0, 8.0), V=10.0, obs=obs)
    applied = [sched.control_async(q) for q in (0, 50, 50, 0)]
    recs = list(obs.decisions.rates)
    assert applied == sched.rate_history == [r["chosen"] for r in recs]
    assert all(r["lagged"] for r in recs)
    # the lag is visible: once backlog jumps, the recorded argmax (this
    # slot's decision) diverges from the applied rate at least once
    assert any(r["chosen"] != r["argmax"] for r in recs)
    # static policies short-circuit the pipeline — never lagged
    obs2 = observability()
    st = StaticScheduler(rate=5.0, obs=obs2)
    st.control_async(10)
    assert not list(obs2.decisions.rates)[0]["lagged"]


_POLICIES = [
    Static(rate=4.0),
    DriftPlusPenalty(rates=(1.0, 2.0, 4.0, 8.0), V=20.0),
    DriftPlusPenalty(rates=(1.0, 2.0, 4.0, 8.0), V=0.5),
    LatencyAware(rates=(1.0, 2.0, 4.0, 8.0), V=20.0, cost_gain=1.0,
                 cost_budget=3.0),
    MemoryAware(rates=(1.0, 2.0, 4.0, 8.0), V=20.0),
    TokenBacklogAware(rates=(1.0, 2.0, 4.0, 8.0), V=20.0),
]


@pytest.mark.parametrize("policy", _POLICIES,
                         ids=lambda p: type(p).__name__)
@pytest.mark.parametrize("capacity", [np.inf, 40.0])
def test_replay_rollout_bit_identical(policy, capacity):
    """The Fig.-2 acceptance: the recording host replay reproduces the
    lax.scan rollout's backlog/rate(/vq) series BIT for bit, so decision
    logs regenerate the paper's trajectories from real runs."""
    rng = np.random.default_rng(42)
    mus = rng.uniform(0.0, 6.0, 48).astype(np.float32)
    ref = rollout(policy, mus, capacity=capacity)
    got = replay_rollout(policy, mus, capacity=capacity)
    assert np.array_equal(np.asarray(ref["backlog"]), got["backlog"])
    assert np.array_equal(np.asarray(ref["rate"]), got["rate"])
    if "vq" in ref:
        assert np.array_equal(np.asarray(ref["vq"]), got["vq"])
    log = got["log"]
    assert len(log.rates) == len(mus)
    s = log.rate_series()
    assert np.array_equal(s["backlog"], got["backlog"])
    assert np.array_equal(s["rate"], got["rate"])


# ----------------------------------------------------------------- overhead
def _timed_serve(obs):
    import time

    cfg, params = _setup()
    eng = Engine(cfg, params, EngineConfig(batch_slots=4, prompt_len=8,
                                           cache_len=32), obs=obs)
    sched = StaticScheduler(rate=4.0, obs=obs)
    src = RequestSource(vocab_size=cfg.vocab_size, prompt_len=8,
                        max_new_tokens=3, raw_rate=4, seed=1)
    t0 = time.perf_counter()
    serve(eng, sched, src, horizon=10, steps_per_slot=2, sync_free=True)
    return time.perf_counter() - t0


def test_noop_recorder_overhead_budget():
    """Satellite (c): telemetry must be cheap. The disabled path is a pure
    attribute-load-plus-branch (microbenched against an explicit bound),
    and even fully ENABLED recording stays within the serve-loop budget
    (min-of-reps, interleaved, with absolute slack against CI noise)."""
    import time

    tr = NullRecorder()
    n = 50_000
    t0 = time.perf_counter()
    for i in range(n):
        if tr.enabled:
            tr.emit("arrival", rid=i)
    per_site = (time.perf_counter() - t0) / n
    assert per_site < 5e-6   # the guard is nanoseconds, not microseconds

    _timed_serve(None)       # warm the jit cache off the clock
    on, off = [], []
    for _ in range(3):       # interleave so drift hits both arms equally
        off.append(_timed_serve(None))
        on.append(_timed_serve(observability()))
    t_on, t_off = min(on), min(off)
    # <5% relative budget, with a small absolute floor for timer noise on
    # loops this short (dispatch dominates; emits are host-side tuples)
    assert t_on <= t_off * 1.05 + 0.05, (t_on, t_off)


# ------------------------------------------------------------------ latency
def test_latency_stats_queue_wait():
    cfg, params = _setup()
    eng = Engine(cfg, params, EngineConfig(batch_slots=2, prompt_len=8,
                                           cache_len=32))
    reqs = _reqs(6)          # 6 requests through 2 rows: later ones wait
    eng.submit(reqs)
    t = 0
    while len(eng.finished) < len(reqs) and t < 60:
        eng.step_slot(t, n_steps=2)
        t += 1
    st = latency_stats(eng)
    assert {"queue_wait_p50", "queue_wait_p99"} <= set(st)
    assert st["queue_wait_p99"] >= st["queue_wait_p50"] >= 0.0
    assert st["queue_wait_p99"] > 0.0   # the overflow cohort queued


def test_queue_wait_restamped_after_fleet_requeue():
    """Satellite (b): a fleet failure resets admit_slot; the surviving
    replica's re-admission restamps it, so queue-wait (arrival ->
    LAST admission) reflects the requeue penalty and TTFT stays sane."""
    cfg, params = _setup()
    obs = observability()
    mk = lambda: Engine(cfg, params, EngineConfig(batch_slots=4,
                                                  prompt_len=8,
                                                  cache_len=32), obs=obs)
    fleet = ReplicaFleet.build(mk, 2, obs=obs)
    reqs = _reqs(8, max_new=6)
    fleet.submit(reqs)
    fleet.step_slot_sync(0, n_steps=1)
    victim = next(i for i, e in enumerate(fleet.replicas)
                  if any(r is not None for r in e.active))
    moved = fleet.fail_replica(victim)
    assert moved and all(r.admit_slot is None and r.generated is None
                         for r in moved)
    fail_slot = 1
    t = fail_slot
    while len(fleet.finished) < len(reqs) and t < 60:
        fleet.step_slot_sync(t, n_steps=2)
        t += 1
    fleet.drain()
    assert len(fleet.finished) == len(reqs)
    by_rid = {r.rid: r for r in fleet.finished}
    for req in moved:
        assert by_rid[req.rid].admit_slot >= fail_slot   # restamped
    st = latency_stats(fleet)
    assert st["queue_wait_p99"] >= 1.0   # the requeue penalty is visible
    kinds = [e["kind"] for e in obs.trace.events()]
    assert kinds.count("requeue") == len(moved)
