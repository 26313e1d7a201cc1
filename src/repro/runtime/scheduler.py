"""Policy-driven admission scheduler — the control plane meeting the engine.

``PolicyScheduler`` consumes any ``repro.control.Policy``: each control slot
it observes the engine's backlog Q(t) (pending requests), evaluates the
policy (for ``DriftPlusPenalty`` that is the paper's Algorithm 1,
f* = argmax_f { V*S(f) - Q(t)*lambda(f) }), and tells the request source to
sample at f*. The queue is bounded (capacity) so sustained mis-control shows
up as drops — exactly the paper's reliability failure.

Hot-path note: the per-slot decision is ONE module-level jitted function
over device-resident tables (F, S(F), lambda(F) are uploaded once per
scheduler and passed as arrays). Because the jit cache keys on shapes, every
scheduler instance with the same action-set size shares a single compile —
constructing schedulers in a loop (sweeps, tests) never re-traces.

``AdaptiveScheduler`` / ``StaticScheduler`` are the historical names, kept
as thin constructors over ``PolicyScheduler``.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.control import (DriftPlusPenalty, LatencyAware, MemoryAware,
                           Policy, PrecisionAware, Static, TokenBacklogAware)
from repro.control.policy import drift_plus_penalty_action
from repro.core.utility import Utility, paper_utility
from repro.obs import NULL_TRACE, explain_tables

# trace counter for the no-retrace regression test: the body runs only when
# jax traces (not on cached calls), so this counts compiles, not calls.
_TRACE_COUNT = {"n": 0}


@jax.jit
def _act_on_tables(backlog, f_tab, s_tab, lam_tab, V, vq_value, cost_tab):
    """Shared Algorithm-1 dispatch over device-resident tables.

    vq_value/cost_tab price an optional virtual-queue constraint
    (zeros = unconstrained; the term vanishes).
    """
    _TRACE_COUNT["n"] += 1
    extra = vq_value * cost_tab
    f_star, _ = drift_plus_penalty_action(backlog, f_tab, s_tab, lam_tab, V, extra)
    return f_star


@partial(jax.jit, static_argnums=0)
def _act_generic(policy, carry, backlog):
    """Any user Policy, jitted with the (hashable) policy as a static arg.

    Equal policy instances share one trace; unlike the table path, distinct
    configurations (e.g. different V) each compile once.
    """
    return policy.act(carry, backlog)


def trace_count() -> int:
    return _TRACE_COUNT["n"]


@dataclasses.dataclass
class PolicyScheduler:
    """Admission control for the engine, driven by any Policy."""

    policy: Policy = None  # type: ignore[assignment]
    capacity: int = 256
    # optional Observability bundle (repro.obs): when its DecisionLog is
    # live, every control-slot argmax is recorded with its host-recomputed
    # drift/penalty decomposition — off the decision path, after the jitted
    # dispatch, so recording never changes what the engine sees
    obs: Optional[object] = None

    def __post_init__(self):
        if self.policy is None:
            self.policy = DriftPlusPenalty(
                rates=tuple(float(f) for f in range(1, 11)), V=50.0
            )
        self._static_rate = self.policy.rate if isinstance(self.policy, Static) else None
        # Any policy exposing device tables goes through one module-wide
        # jitted action over them (same table shapes => same compile, so
        # sweeps over V never re-trace); the virtual-queue price comes from
        # the policy's own ``vq_cost_per_rate`` so new constrained policies
        # (repro.reliability's ConformalSLO) plug in without this class
        # enumerating them. Anything else that satisfies the Policy protocol
        # runs its own act() via the shared static-arg jit.
        self._table_path = (self._static_rate is None
                            and hasattr(self.policy, "tables"))
        if self._table_path:
            f, s, lam = self.policy.tables()
            self._f_tab = jax.device_put(f)
            self._s_tab = jax.device_put(s)
            self._lam_tab = jax.device_put(lam)
            self._V = jax.device_put(jnp.float32(self.policy.V))
            cost = float(getattr(self.policy, "vq_cost_per_rate", 0.0))
            self._cost_tab = jax.device_put(
                jnp.float32(cost) * f if cost else jnp.zeros_like(f)
            )
            # host float32 mirrors of the tables for decision recording
            # (same IEEE arithmetic as the device dispatch)
            self._f_np = np.asarray(f, np.float32)
            self._s_np = np.asarray(s, np.float32)
            self._lam_np = np.asarray(lam, np.float32)
            self._cost_np = np.float32(cost) * self._f_np
        self._decisions = self.obs.decisions if self.obs is not None else None
        self._trace = getattr(self.obs, "trace", NULL_TRACE)
        self._carry = self.policy.init()
        self._admit_precision = "native"
        self.dropped = 0
        self.rate_history: list = []
        self._pending_rate = None  # control_async: last dispatched decision

    def _observe(self, occupancy: Optional[float],
                 token_backlog: Optional[float],
                 quant_occupancy: Optional[float] = None) -> None:
        """Feed observation-driven virtual queues: a policy exposing
        ``observe`` names the engine signal it consumes via its
        ``observation`` attribute ("occupancy" for MemoryAware,
        "token_backlog" for TokenBacklogAware, "quant_occupancy" for
        PrecisionAware) and advances on it before acting; other policies
        ignore all three."""
        if not hasattr(self.policy, "observe"):
            return
        sig = {"occupancy": occupancy, "token_backlog": token_backlog,
               "quant_occupancy": quant_occupancy}.get(
            getattr(self.policy, "observation", "occupancy"))
        if sig is not None:
            self._carry = self.policy.observe(self._carry, sig)

    def admit_precision(self, occupancy: Optional[float]) -> Optional[str]:
        """The policy's page-region choice for upcoming admissions (None if
        the policy has no such lever). The serve loop assigns the result to
        ``engine.admit_precision``; every latch flip — in particular every
        native->quantized downgrade — is recorded in the DecisionLog before
        the engine sees it, so degrading precision is never silent."""
        if occupancy is None or not hasattr(self.policy, "admit_precision"):
            return None
        chosen, self._carry = self.policy.admit_precision(
            self._carry, occupancy)
        prev, self._admit_precision = self._admit_precision, chosen
        d = self._decisions
        if chosen != prev and d is not None and d.enabled:
            d.record_precision(t=len(self.rate_history),
                               occupancy=float(occupancy),
                               vq=self._vq_value(), prev=prev, chosen=chosen)
        return chosen

    def control(self, backlog: int, occupancy: Optional[float] = None,
                token_backlog: Optional[float] = None,
                quant_occupancy: Optional[float] = None) -> float:
        """One control-slot decision. ``occupancy`` (the paged engine's
        page-pool fill fraction), ``token_backlog`` (pending prompt
        tokens), and ``quant_occupancy`` (quantized-region fill) feed
        observation-driven virtual queues via ``_observe``."""
        with self._trace.span("control.decide", slot=len(self.rate_history)):
            self._observe(occupancy, token_backlog, quant_occupancy)
            d = self._decisions
            rec = d is not None and d.enabled
            vq = self._vq_value() if rec else 0.0
            if self._static_rate is not None:  # no device round-trip for baselines
                f = float(self._static_rate)
            else:
                f = float(self._dispatch_decision(backlog))
            self.rate_history.append(f)
            if rec:
                self._record(backlog, vq, f, lagged=False)
            return f

    def _vq_value(self) -> float:
        return float(np.asarray(getattr(self._carry, "value", 0.0)))

    def _record(self, backlog, vq: float, applied: float,
                lagged: bool) -> None:
        """Log one control decision with its host-recomputed decomposition
        (table policies only have one; others record the scalar inputs)."""
        V = float(getattr(self.policy, "V", 0.0))
        t = len(self.rate_history) - 1
        if self._table_path:
            ex = explain_tables(float(backlog), self._f_np, self._s_np,
                                self._lam_np, V, vq=vq,
                                cost_tab=self._cost_np)
            self._decisions.record_rate(
                t=t, backlog=float(backlog), vq=vq, V=V, chosen=applied,
                rates=ex["rates"], drift=ex["drift"], penalty=ex["penalty"],
                argmax=ex["argmax"], lagged=lagged)
        else:
            self._decisions.record_rate(t=t, backlog=float(backlog), vq=vq,
                                        V=V, chosen=applied, lagged=lagged)

    def _dispatch_decision(self, backlog: int):
        """Evaluate the policy on device; return the (unread) decision."""
        if self._table_path:
            vq = getattr(self._carry, "value", jnp.float32(0.0))
            f_star = _act_on_tables(
                jnp.asarray(backlog, jnp.float32), self._f_tab, self._s_tab,
                self._lam_tab, self._V, vq, self._cost_tab,
            )
            if isinstance(self.policy, LatencyAware):
                self._carry = self._carry.step(self.policy.cost_gain * f_star)
            return f_star
        f_star, self._carry = _act_generic(
            self.policy, self._carry, jnp.asarray(backlog, jnp.float32)
        )
        return f_star

    def control_async(self, backlog: int, occupancy: Optional[float] = None,
                      token_backlog: Optional[float] = None,
                      quant_occupancy: Optional[float] = None) -> float:
        """Sync-free control: dispatch this slot's Algorithm-1 decision and
        return the PREVIOUS one — the readback of decision t overlaps slot
        t's compute, so the serve loop never blocks on the controller.
        One-slot-lagged control; the drift-plus-penalty argument tolerates
        bounded observation delay (the backlog moves by at most one slot's
        arrivals/services). The first call blocks once to seed the pipeline;
        Static policies short-circuit with no device work at all."""
        with self._trace.span("control.decide", slot=len(self.rate_history)):
            self._observe(occupancy, token_backlog, quant_occupancy)
            d = self._decisions
            rec = d is not None and d.enabled
            vq = self._vq_value() if rec else 0.0
            if self._static_rate is not None:
                f = float(self._static_rate)
                self.rate_history.append(f)
                if rec:
                    self._record(backlog, vq, f, lagged=False)
                return f
            f_star = self._dispatch_decision(backlog)
            f_star.copy_to_host_async()
            prev, self._pending_rate = self._pending_rate, f_star
            f = float(prev if prev is not None else f_star)
            self.rate_history.append(f)
            if rec:
                # the applied rate is the previous slot's decision; the recorded
                # decomposition explains THIS slot's argmax (chosen may differ)
                self._record(backlog, vq, f, lagged=True)
            return f

    def admit(self, engine, reqs: list, now: int) -> list:
        with self._trace.span("control.admit", slot=now):
            room = max(self.capacity - engine.queue_len(), 0)
            admitted = reqs[:room]
            self.dropped += len(reqs) - len(admitted)
            for r in admitted:
                r.admit_slot = now
            engine.submit(admitted)
            return admitted


def AdaptiveScheduler(
    rates: tuple = tuple(float(f) for f in range(1, 11)),
    V: float = 50.0,
    utility: Optional[Utility] = None,
    capacity: int = 256,
    obs=None,
) -> PolicyScheduler:
    """Algorithm-1 scheduler (historical constructor)."""
    policy = DriftPlusPenalty(
        rates=tuple(float(f) for f in rates), V=V,
        utility=utility or paper_utility(max(rates)),
    )
    return PolicyScheduler(policy=policy, capacity=capacity, obs=obs)


def StaticScheduler(rate: float = 10.0, capacity: int = 256,
                    obs=None) -> PolicyScheduler:
    """Paper baseline: fixed sampling rate, no queue awareness."""
    return PolicyScheduler(policy=Static(rate=float(rate)),
                           capacity=capacity, obs=obs)


def TokenAwareScheduler(
    rates: tuple = tuple(float(f) for f in range(1, 11)),
    V: float = 50.0,
    tokens_per_request: float = 16.0,
    token_budget: float = 64.0,
    tok_gain: float = 1.0,
    capacity: int = 256,
    obs=None,
) -> PolicyScheduler:
    """Algorithm-1 scheduler that also prices pending prompt tokens (pairs
    with the continuous-batching engines' ``token_backlog()`` observation)."""
    policy = TokenBacklogAware(
        rates=tuple(float(f) for f in rates), V=V,
        tokens_per_request=tokens_per_request,
        token_budget=token_budget, tok_gain=tok_gain,
    )
    return PolicyScheduler(policy=policy, capacity=capacity, obs=obs)


def PrecisionAwareScheduler(
    rates: tuple = tuple(float(f) for f in range(1, 11)),
    V: float = 50.0,
    pages_per_request: float = 2.0,
    quant_budget: float = 0.6,
    quant_gain: float = 1.0,
    downgrade_at: float = 0.75,
    upgrade_at: float = 0.5,
    quant_precision: str = "int8",
    capacity: int = 256,
    obs=None,
) -> PolicyScheduler:
    """Algorithm-1 scheduler with the quantized-page admission lever: calls
    ``admit_precision(engine.occupancy())`` each slot for the page region,
    and prices the quantized pool's fill (``engine.quant_occupancy()``)
    as a virtual queue."""
    policy = PrecisionAware(
        rates=tuple(float(f) for f in rates), V=V,
        pages_per_request=pages_per_request,
        quant_budget=quant_budget, quant_gain=quant_gain,
        downgrade_at=downgrade_at, upgrade_at=upgrade_at,
        quant_precision=quant_precision,
    )
    return PolicyScheduler(policy=policy, capacity=capacity, obs=obs)


def MemoryAwareScheduler(
    rates: tuple = tuple(float(f) for f in range(1, 11)),
    V: float = 50.0,
    pages_per_request: float = 2.0,
    occupancy_budget: float = 0.6,
    mem_gain: float = 1.0,
    capacity: int = 256,
    obs=None,
) -> PolicyScheduler:
    """Algorithm-1 scheduler that also prices page-pool occupancy."""
    policy = MemoryAware(
        rates=tuple(float(f) for f in rates), V=V,
        pages_per_request=pages_per_request,
        occupancy_budget=occupancy_budget, mem_gain=mem_gain,
    )
    return PolicyScheduler(policy=policy, capacity=capacity, obs=obs)
