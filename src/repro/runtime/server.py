"""Slot-time serve loop: source -> scheduler (Policy) -> engine.

``serve`` runs T control slots. Each slot: the scheduler evaluates its
Policy on the current backlog, the source yields that many requests, the
engine runs ``steps_per_slot`` decode steps (its service capacity). With the
default fused path each slot costs at most one prefill dispatch (batched
admission of every free slot) plus one decode dispatch (``steps_per_slot``
steps fused in a lax.scan); ``fused=False`` keeps the legacy per-step loop
(k batch-1 prefills + steps_per_slot decode dispatches) for before/after
benchmarking. Returns a trace for analysis/plots — the serving-system
analogue of the paper's Fig. 2, but with a *real* model in the loop instead
of a simulated service.

``engine`` may equally be a ``repro.runtime.fleet.ReplicaFleet`` — the
fleet presents this exact engine surface (aggregated observations, routed
``submit``, per-protocol ``step_slot*``), so one serve loop drives N
replicas behind one Policy (DESIGN.md §9).

``sync_free=True`` selects the zero-blocking-sync protocol (DESIGN.md §7):
the scheduler's decision pipelines through ``control_async`` (one-slot-
lagged control) and the engine's ``step_slot_sync`` dispatches every slot
from device-resident state, draining the previous slot's async counter
readback afterwards. The trace's ``served`` counts therefore lag the device
by one slot; ``serve`` flushes the tail with ``engine.drain()`` and folds
it into the final slot. The per-slot ``syncs`` column counts dispatch-
gating synchronous readbacks (0 in the steady state; the legacy paths pay
1-2 per slot).

Each slot is one ``serve.slot`` span on the engine's recorder
(``engine.obs.trace``; DESIGN.md §11), the parent of the scheduler's and
the engine's spans inside it.
"""
from __future__ import annotations

import numpy as np

from repro.obs import OBS_OFF
from repro.runtime.engine import Engine
from repro.runtime.request import RequestSource


def serve(engine: Engine, scheduler, source: RequestSource, *,
          horizon: int, steps_per_slot: int = 2, fused: bool = True,
          sync_free: bool = False, chunked: bool = False) -> dict:
    trace = {"backlog": [], "rate": [], "served": [], "active": [],
             "dropped": [], "dispatches": [], "occupancy": [], "syncs": []}
    paged = hasattr(engine, "occupancy")
    tr = getattr(engine, "obs", OBS_OFF).trace
    for t in range(horizon):
        with tr.span("serve.slot", slot=t):
            d0 = engine.prefill_dispatches + engine.decode_dispatches
            s0 = engine.blocking_syncs
            # the observation is the previous slot's commitment peak: end-of-slot
            # occupancy dips as retirements free pages, hiding the pressure the
            # controller must price
            occ = max(engine.occupancy(), engine.occupancy_hwm) if paged else None
            tok = engine.token_backlog() if hasattr(engine, "token_backlog") else None
            qocc = (engine.quant_occupancy()
                    if hasattr(engine, "quant_occupancy") else None)
            if (sync_free or chunked) and hasattr(scheduler, "control_async"):
                rate = scheduler.control_async(engine.queue_len(), occupancy=occ,
                                               token_backlog=tok,
                                               quant_occupancy=qocc)
            else:
                rate = scheduler.control(engine.queue_len(), occupancy=occ,
                                         token_backlog=tok, quant_occupancy=qocc)
            # the precision lever (DESIGN.md §14): a policy exposing
            # admit_precision picks the page region for this slot's admissions
            # (every latch flip is DecisionLog-recorded inside the scheduler)
            if occ is not None and hasattr(scheduler, "admit_precision"):
                chosen = scheduler.admit_precision(occ)
                if chosen is not None and hasattr(engine, "admit_precision"):
                    engine.admit_precision = chosen
            reqs = source.poll(t, rate)
            scheduler.admit(engine, reqs, t)
            if chunked:
                m = engine.step_slot_chunked(t, n_steps=steps_per_slot)
                served = m["served"]
            elif sync_free:
                m = engine.step_slot_sync(t, n_steps=steps_per_slot)
                served = m["served"]
            elif fused:
                m = engine.step_slot(t, n_steps=steps_per_slot)
                served = m["served"]
            else:
                served = 0
                for _ in range(steps_per_slot):
                    m = engine.step(t)
                    served += m["served"]
            trace["backlog"].append(engine.queue_len())
            trace["rate"].append(rate)
            trace["served"].append(served)
            trace["active"].append(m["active"])
            trace["dropped"].append(scheduler.dropped)
            trace["dispatches"].append(
                engine.prefill_dispatches + engine.decode_dispatches - d0
            )
            trace["occupancy"].append(engine.occupancy_hwm if paged else 0.0)
            trace["syncs"].append(engine.blocking_syncs - s0)
    if (sync_free or chunked) and trace["served"]:
        # flush the in-flight slot's readback so totals match the synchronous
        # paths; its completions belong to the last dispatched slot
        trace["served"][-1] += engine.drain()["served"]
    return {k: np.asarray(v) for k, v in trace.items()}


def latency_stats(engine: Engine) -> dict:
    """Wait/total latency percentiles over finished requests.

    ``waits`` and ``totals`` filter on *different* fields (start_slot vs
    finish_slot), so they can legitimately diverge — e.g. a request retired
    through the sync-free readback after a preemption reset its start_slot —
    and each percentile set is guarded on its own list. ``ttft`` is
    admission-to-first-token (arrival to the slot whose dispatch emitted the
    first generated token) — the latency prefix caching attacks: a cached
    prefix skips its prefill chunks, so the activating dispatch arrives
    slots earlier. ``queue_wait`` is arrival-to-engine-claim (admit_slot is
    stamped when the engine claims a row, and re-stamped after a
    preemption or fleet requeue, so it prices the *last* wait the request
    actually paid). Also reports ``admitted_but_unfinished``: requests
    holding an engine row or queue slot at shutdown (a drain/accounting
    leak shows up here).
    """
    waits = [r.start_slot - r.arrival_slot for r in engine.finished
             if r.start_slot is not None]
    qwaits = [r.admit_slot - r.arrival_slot for r in engine.finished
              if r.admit_slot is not None]
    totals = [r.finish_slot - r.arrival_slot for r in engine.finished
              if r.finish_slot is not None]
    ttfts = [r.first_token_slot - r.arrival_slot for r in engine.finished
             if r.first_token_slot is not None]
    unfinished = (sum(1 for r in engine.active if r is not None)
                  + len(engine.pending))
    out = {"n": len(totals), "admitted_but_unfinished": unfinished}
    if totals:
        out["total_p50"] = float(np.percentile(totals, 50))
        out["total_p99"] = float(np.percentile(totals, 99))
    if waits:
        out["wait_p50"] = float(np.percentile(waits, 50))
        out["wait_p99"] = float(np.percentile(waits, 99))
    if qwaits:
        out["queue_wait_p50"] = float(np.percentile(qwaits, 50))
        out["queue_wait_p99"] = float(np.percentile(qwaits, 99))
    if ttfts:
        out["ttft_p50"] = float(np.percentile(ttfts, 50))
        out["ttft_p99"] = float(np.percentile(ttfts, 99))
    # multi-tenant workloads (repro.reliability, DESIGN.md §12): per-tenant
    # TTFT percentiles and deadline attainment — the samples ConformalSLO
    # calibrates on, broken out the way the SLO is stated. Single-tenant
    # runs keep the flat dict unchanged.
    tenants = {r.tenant for r in engine.finished}
    if tenants - {"default"}:
        out["tenants"] = {}
        for name in sorted(tenants):
            rs = [r for r in engine.finished if r.tenant == name]
            tt = [r.first_token_slot - r.arrival_slot for r in rs
                  if r.first_token_slot is not None]
            entry = {"n": len(rs)}
            if tt:
                entry["ttft_p50"] = float(np.percentile(tt, 50))
                entry["ttft_p99"] = float(np.percentile(tt, 99))
            with_deadline = [r for r in rs if r.deadline_slots is not None
                             and r.first_token_slot is not None]
            if with_deadline:
                ontime = sum(
                    r.first_token_slot - r.arrival_slot <= r.deadline_slots
                    for r in with_deadline)
                entry["attainment"] = ontime / len(with_deadline)
            out["tenants"][name] = entry
    return out
