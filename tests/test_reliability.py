"""Reliability layer (DESIGN.md §12): conformal SLO queues, the overload
degradation ladder, the readback watchdog, and the fault-injection (chaos)
harness.

The chaos differential matrix (marked ``chaos`` + ``slow``) re-asserts the
repo's equivalence contract — bit-identical surviving streams, zero page
leaks, served-count conservation — under seeded replica failures, forced
allocator shortfalls, delayed readbacks, and prefix-eviction races.
"""
import copy
import os
from types import SimpleNamespace

import jax.numpy as jnp
import numpy as np
import pytest

from repro.control import FleetRouter
from repro.obs import observability
from repro.reliability import (ChaosConfig, ChaosInjector, ConformalQuantile,
                               ConformalScheduler, ConformalSLO, TenantSLO,
                               assert_no_leaks, chaos_drive, save_artifacts)
from repro.reliability.chaos import _DelayedArray
from repro.runtime import (Engine, ReadbackTimeout, ReplicaFleet, Request,
                           RequestSource, TenantSpec)
from test_differential import (_mk_engine, _setup, drive,
                               make_shared_workload, make_workload)


# --------------------------------------------------------- conformal quantile
def test_conformal_quantile_is_exact_order_statistic():
    cq = ConformalQuantile(window=256)
    for v in range(1, 101):
        cq.push(float(v))
    # split-conformal rank: ceil((n+1)q) = ceil(101*0.9) = 91 -> x_(91)
    assert cq.quantile(0.9) == 91.0
    assert cq.ready(0.9)
    assert len(cq) == 100


def test_conformal_quantile_window_slides():
    cq = ConformalQuantile(window=8)
    for v in range(100):
        cq.push(float(v))
    assert len(cq) == 8
    assert sorted(cq.samples()) == [float(v) for v in range(92, 100)]
    assert cq.quantile(0.5) > 91


def test_conformal_quantile_small_n_clamps_conservative():
    cq = ConformalQuantile()
    assert cq.quantile(0.9) == 0.0          # empty: no evidence, no price
    cq.push(5.0)
    cq.push(3.0)
    # ceil(3*0.99)=3 > n=2 -> clamp to the max (conservative) and report
    # the calibration as not yet valid at that coverage
    assert cq.quantile(0.99) == 5.0
    assert not cq.ready(0.99)


def test_conformal_slo_queue_rises_on_misses_and_drains():
    pol = ConformalSLO(rates=(1.0, 2.0), V=10.0,
                       tenants=(TenantSLO("a", deadline_slots=4,
                                          quantile=0.9),),
                       window=32)
    carry = pol.init()
    carry = pol.observe(carry, [("a", 10.0)] * 10)  # all miss the deadline
    z_hot = carry.z["a"]
    assert z_hot > 0 and carry.value > 0
    assert carry.qhat["a"] == 10.0
    for _ in range(20):
        carry = pol.observe(carry, [("a", 1.0)] * 16)  # window refills on-time
    assert carry.z["a"] < z_hot
    carry = pol.observe(carry, [("unknown_tenant", 99.0)])  # ignored
    assert "unknown_tenant" not in carry.z


def test_conformal_policy_prices_through_table_path():
    sched = ConformalScheduler(rates=(1.0, 2.0, 4.0), V=10.0,
                               tenants=(TenantSLO("a", 4),), capacity=16)
    # the shared jitted dispatch path requires tables + a per-rate price
    assert hasattr(sched.policy, "tables")
    assert sched.policy.vq_cost_per_rate == sched.policy.slo_gain
    rate = sched.control(0)
    assert rate in (1.0, 2.0, 4.0)


# ------------------------------------------------------------ tenant tagging
def test_request_source_tenant_mix_is_seeded():
    tenants = (TenantSpec("gold", frac=0.25, priority=1, deadline_slots=6),
               TenantSpec("bulk", frac=0.75))
    a = RequestSource(vocab_size=64, prompt_len=8, raw_rate=4, seed=3,
                      tenants=tenants)
    b = RequestSource(vocab_size=64, prompt_len=8, raw_rate=4, seed=3,
                      tenants=tenants)
    ra = [r for t in range(40) for r in a.poll(t, 4.0)]
    rb = [r for t in range(40) for r in b.poll(t, 4.0)]
    assert [r.tenant for r in ra] == [r.tenant for r in rb]
    names = {r.tenant for r in ra}
    assert names == {"gold", "bulk"}
    gold = [r for r in ra if r.tenant == "gold"]
    assert all(r.priority == 1 and r.deadline_slots == 6 for r in gold)
    frac = len(gold) / len(ra)
    assert 0.1 < frac < 0.45     # seeded draw around 0.25


def test_request_source_rejects_nonpositive_mix():
    with pytest.raises(ValueError):
        RequestSource(vocab_size=64, prompt_len=8,
                      tenants=(TenantSpec("a", frac=0.0),))


# -------------------------------------------------------- degradation ladder
class _FakeEngine:
    """Just enough engine surface for SLOScheduler.admit: a queue, rows,
    and a finished list (no device, no model)."""

    def __init__(self, rows=4):
        self.pending = []
        self.active = [None] * rows
        self.finished = []

    def queue_len(self):
        return len(self.pending)

    def submit(self, reqs):
        self.pending.extend(reqs)


def _req(rid, t, tenant="default", priority=0, deadline=None):
    return Request(rid=rid, arrival_slot=t,
                   tokens=np.zeros(4, np.int32), max_new_tokens=2,
                   tenant=tenant, priority=priority, deadline_slots=deadline)


def _mk_sched(**kw):
    return ConformalScheduler(rates=(1.0, 2.0), V=10.0,
                              tenants=(TenantSLO("gold", 4, priority=1),),
                              capacity=8, **kw)


def test_ladder_level0_admits_everything_in_priority_order():
    sched, eng = _mk_sched(), _FakeEngine()
    sched.admit(eng, [_req(0, 0, "bulk"), _req(1, 0, "gold", priority=1)], 0)
    assert [r.rid for r in eng.pending] == [1, 0]   # gold first
    assert sched.degrade_level == 0 and not sched.shed_log


def test_ladder_drops_expired_and_sheds_lowest_tier():
    obs = observability()
    sched = _mk_sched(obs=obs)
    eng = _FakeEngine()
    # queue fill >= overload_backlog_frac * capacity arms level 1
    eng.pending = [_req(i, 0, "bulk", deadline=3) for i in range(7)]
    offer = [_req(10, 9, "bulk"), _req(11, 9, "gold", priority=1)]
    sched.admit(eng, offer, 9)
    assert sched.degrade_level >= 1
    assert sched.shed_expired == 7          # all queued bulk are 9 slots old
    assert sched.shed_priority == 1         # the offered bulk request
    rids = [r.rid for r in eng.pending]
    assert 11 in rids and 10 not in rids
    reasons = {(e["rid"], e["reason"]) for e in obs.decisions.sheds}
    assert (10, "priority") in reasons and (0, "expired") in reasons
    c = sched.counters()
    assert c["requests_shed_expired"] == 7
    assert c["requests_shed_priority"] == 1
    assert c["degrade_level"] >= 1


def test_ladder_never_starves_a_uniform_batch():
    sched, eng = _mk_sched(), _FakeEngine()
    eng.pending = [_req(i, 8) for i in range(6)]    # overloaded, no deadline
    sched.admit(eng, [_req(10, 9, "bulk"), _req(11, 9, "bulk")], 9)
    # single-tier offer: the priority rung must not shed it
    assert sched.shed_priority == 0
    assert {10, 11} <= {r.rid for r in eng.pending}


def test_ladder_level2_caps_admissions_highest_tier_first():
    sched = _mk_sched(cap_frac=0.5)
    eng = _FakeEngine(rows=4)
    eng.active = [object()] * 4
    eng.pending = [_req(i, 9) for i in range(8)]    # full queue -> level 2
    # three tiers: the priority rung sheds the lowest, then the cap
    # (cap_frac * 4 rows = 2) falls on the middle tier, keeping gold
    offer = ([_req(20, 9, "free", priority=0), _req(21, 9, "free", priority=0)]
             + [_req(25, 9, "bulk", priority=1),
                _req(26, 9, "bulk", priority=1)]
             + [_req(30, 9, "gold", priority=2),
                _req(31, 9, "gold", priority=2)])
    sched.admit(eng, offer, 9)
    assert sched.degrade_level == 2
    assert sched.shed_priority == 2
    assert sched.shed_capped == 2
    assert sched.counters()["requests_shed_capped"] == 2
    capped = [e for e in sched.shed_log if e[3] == "capped"]
    assert {e[1] for e in capped} == {25, 26}
    # the gold survivors reach the base scheduler; the full queue turns
    # them into *recorded* capacity drops, never silence
    assert sched.dropped == 2


def test_slo_scheduler_collects_ttft_samples_and_attainment():
    sched, eng = _mk_sched(), _FakeEngine()
    sched.admit(eng, [], 0)                         # latch the engine
    r_hit = _req(0, 0, "gold", priority=1, deadline=4)
    r_hit.first_token_slot = 2
    r_miss = _req(1, 0, "gold", priority=1, deadline=4)
    r_miss.first_token_slot = 9
    eng.finished = [r_hit, r_miss]
    sched.control(0)
    assert sched.attainment() == {"gold": 0.5}
    assert len(sched._carry.calib["gold"]) == 2
    sched.control(0)                                # samples not re-consumed
    assert len(sched._carry.calib["gold"]) == 2


# ---------------------------------------------------------- readback watchdog
def test_await_readback_raises_diagnosable_timeout():
    stub = SimpleNamespace(ecfg=SimpleNamespace(readback_timeout_s=0.05),
                           active=[object(), None, object()], _cursors={2: 1})
    hung = _DelayedArray(np.zeros(3, np.int32), polls=-1)
    with pytest.raises(ReadbackTimeout) as ei:
        Engine._await_readback(stub, {"slot": 7, "arrays": {"done": hung}})
    err = ei.value
    assert err.slot == 7 and err.array == "done" and err.timeout_s == 0.05
    assert err.rows == [0]          # row 2 is mid-chunked-prefill, row 1 free
    assert "slot 7" in str(err) and "done" in str(err)


def test_await_readback_tolerates_bounded_delay():
    stub = SimpleNamespace(ecfg=SimpleNamespace(readback_timeout_s=5.0),
                           active=[None], _cursors={})
    slow = _DelayedArray(jnp.arange(4), polls=3)
    Engine._await_readback(stub, {"slot": 0, "arrays": {"age": slow}})
    assert np.asarray(slow).tolist() == [0, 1, 2, 3]


def test_await_readback_disabled_bound_never_raises():
    stub = SimpleNamespace(ecfg=SimpleNamespace(readback_timeout_s=0.0),
                           active=[], _cursors={})
    hung = _DelayedArray(np.zeros(1), polls=-1)
    # timeout <= 0 restores the pre-watchdog unbounded behavior: the loop
    # must break out rather than spin or raise
    Engine._await_readback(stub, {"slot": 0, "arrays": {"done": hung}})


def test_engine_readback_hang_raises_readback_timeout():
    cfg, params = _setup()
    eng = _mk_engine("dense", cfg, params)
    eng.ecfg.readback_timeout_s = 0.2
    chaos = ChaosInjector(seed=0, p_readback_hang=1.0).arm(eng)
    reqs, schedule = make_workload(seed=3, n_reqs=2)
    with pytest.raises(ReadbackTimeout):
        chaos_drive(eng, "sync", reqs, schedule, chaos=chaos, max_slots=30)
    assert chaos.hangs_injected >= 1


def test_engine_readback_delay_is_invisible_to_tokens():
    cfg, params = _setup()
    reqs, schedule = make_workload(seed=4, n_reqs=6)
    ref = drive(_mk_engine("dense", cfg, params), "fused", reqs, schedule)
    eng = _mk_engine("dense", cfg, params)
    chaos = ChaosInjector(seed=1, p_readback_delay=1.0, delay_polls=2).arm(eng)
    streams, retired, (served, finished) = chaos_drive(
        eng, "sync", reqs, schedule, chaos=chaos)
    assert streams == ref[0] and retired == ref[1]
    assert served == finished == len(reqs)
    assert chaos.delays_injected > 0 and eng.readback_waits > 0


# ----------------------------------------------------------------- chaos unit
def test_chaos_forced_alloc_shortfall_defers_cleanly():
    cfg, params = _setup()
    reqs, schedule = make_workload(seed=5, n_reqs=6)
    ref = drive(_mk_engine("paged", cfg, params), "fused", reqs, schedule)
    eng = _mk_engine("paged", cfg, params)
    chaos = ChaosInjector(seed=0, shortfall_at=(0, 2)).arm(eng)
    streams, retired, (served, finished) = chaos_drive(
        eng, "sync", reqs, schedule, chaos=chaos)
    assert streams == ref[0] and retired == ref[1]
    assert chaos.shortfalls_injected == 2
    assert eng.alloc_failures >= 1      # the engine saw (and absorbed) them
    assert_no_leaks(eng)


def test_chaos_log_is_deterministic_per_seed():
    # the synchronous protocol: retirement timing is logical, so the full
    # fault log (not just the draw stream) must replay exactly from its seed
    cfg, params = _setup()
    reqs, schedule = make_shared_workload(seed=9, n_reqs=8)

    def run():
        chaos = ChaosInjector(ChaosConfig(
            seed=11, start_slot=1, p_replica_fail=0.3, max_failures=1,
            p_alloc_shortfall=0.1, p_evict_prefix=0.2))
        fleet = ReplicaFleet.build(lambda: _mk_engine("shared", cfg, params),
                                   2, router=FleetRouter(kind="drift"),
                                   chaos=chaos)
        chaos_drive(fleet, "fused", reqs, schedule, chaos=chaos)
        return chaos.log

    log = run()
    assert log == run()
    assert any(e["kind"] == "alloc_shortfall" for e in log)


# ----------------------------------------- satellite: requeue storm vs pool
def test_fleet_requeue_storm_into_near_full_survivor():
    """Failing a replica dumps its whole backlog onto survivors whose page
    pools are already nearly full. Every requeued request must either be
    admitted cleanly (deferred until pages free) or surface in a recorded
    counter — and the survivor's allocator must stay consistent."""
    cfg, params = _setup()
    reqs, schedule = make_workload(seed=21, n_reqs=10)
    ref = drive(_mk_engine("paged", cfg, params), "fused", reqs, schedule)
    fleet = ReplicaFleet.build(
        lambda: _mk_engine("paged", cfg, params, tight=True), 2,
        router=FleetRouter(kind="drift"))
    sched = {t: [copy.deepcopy(r) for r in batch] for t, batch in schedule}
    failed = False
    t = 0
    while len(fleet.finished) < len(reqs) and t < 200:
        if t in sched:
            fleet.submit(sched[t])
        if not failed and t == 2:
            requeued = fleet.fail_replica(0)
            failed = True
            assert requeued, "storm test needs in-flight work to requeue"
        fleet.step_slot_sync(t, n_steps=2)
        t += 1
    fleet.drain()
    assert len(fleet.finished) == len(reqs)
    streams = {r.rid: tuple(r.generated) for r in fleet.finished}
    assert streams == ref[0]
    assert fleet.requeues > 0
    assert_no_leaks(fleet)
    # deferred admissions under the shortfall show up as recorded counters,
    # not silence
    survivor = fleet.replicas[1]
    assert survivor.alloc_failures >= 0     # counter exists and is consistent
    survivor.allocator.check()


# ------------------------------------------------- chaos differential matrix
_CHAOS_MATRIX = [(mode, n) for mode in ("dense", "paged", "shared")
                 for n in (1, 2, 4)]


@pytest.mark.chaos
@pytest.mark.slow
@pytest.mark.parametrize("mode,n", _CHAOS_MATRIX,
                         ids=[f"{m}-x{n}" for m, n in _CHAOS_MATRIX])
def test_chaos_differential_matrix(mode, n):
    """The full equivalence contract under the full fault mix: surviving
    streams bit-identical to the clean dense reference, identical retirement
    sets, served-count conservation, zero page leaks — for every cache mode
    and fleet size."""
    cfg, params = _setup()
    reqs, schedule = make_shared_workload(seed=100 + n, n_reqs=12)
    ref = drive(_mk_engine("dense", cfg, params), "fused", reqs, schedule)
    chaos = ChaosInjector(ChaosConfig(
        seed=40 + 3 * n, start_slot=2,
        p_replica_fail=0.25 if n > 1 else 0.0, max_failures=max(n - 1, 1),
        p_alloc_shortfall=0.1, p_readback_delay=0.25, delay_polls=2,
        p_evict_prefix=0.25 if mode == "shared" else 0.0, evict_pages=2))
    # CI's chaos lane sets CHAOS_ARTIFACT_DIR and uploads the dumped
    # trace/decision/fault logs when a cell fails
    artifact_dir = os.environ.get("CHAOS_ARTIFACT_DIR")
    obs = observability() if artifact_dir else None
    fleet = ReplicaFleet.build(
        lambda: _mk_engine(mode, cfg, params, obs=obs), n,
        router=FleetRouter(kind="drift"), obs=obs, chaos=chaos)
    try:
        streams, retired, (served, finished) = chaos_drive(
            fleet, "sync", reqs, schedule, chaos=chaos)
        assert streams == ref[0], f"stream divergence (chaos: {chaos.log})"
        assert retired == ref[1]
        assert served == finished == len(reqs)
        assert_no_leaks(fleet)
    finally:
        if artifact_dir:
            save_artifacts(artifact_dir, f"{mode}_x{n}", obs=obs, chaos=chaos)
