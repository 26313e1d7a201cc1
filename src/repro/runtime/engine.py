"""Continuous-batching inference engine.

Fixed decode slots (batch dimension B). Each slot holds one in-flight
request's KV/recurrent cache row. Per control slot (``step_slot``):

  1. batched admission: pop up to k pending requests for the k free slots,
     run ONE bucketed prefill of batch k (fixed prompt_len), and splice all
     k new cache rows into the batch cache with one jitted scatter on the
     slot axis — replacing k sequential batch-1 prefill+splice dispatches,
  2. fused decode: ``n_steps`` decode steps run inside a single jit'd
     lax.scan over all B slots (inactive slots compute but are masked out —
     the standard continuous-batching trade), returning per-step sampled
     tokens so the host can attribute service mu(t) to individual steps,
  3. retire finished requests (max_new_tokens reached or EOS), freeing
     slots.

So one control slot costs <= 1 prefill + 1 decode jit dispatch (tracked in
``prefill_dispatches`` / ``decode_dispatches``), where the legacy per-step
path (``step``, kept for equivalence tests and the before/after benchmark)
costs k prefills + n_steps decodes. The engine reports per-step service
counts — the mu(t) the Lyapunov controller observes. Model-agnostic: works
for every registered arch via the Model API (prefill/decode_step).

Sync-free serving (``step_slot_sync``, DESIGN.md §7)
----------------------------------------------------
``step_slot`` still pays >= 1 *blocking* host sync per slot: it reads the
sampled tokens back to scan for finished requests before it can dispatch
anything else. ``step_slot_sync`` moves sampling, EOS detection, per-slot
stop masks, and a generated-token ring buffer into the jitted decode scan
(``SyncState``; the model state is donated, so XLA updates the caches in
place), so the host
dispatches the next fused decode from device-resident state alone and only
*initiates* an async copy of tiny ``done/age/served`` counters. The copy of
slot t is consumed at slot t+1 — readback overlaps compute — so a
steady-state control slot performs **zero blocking host syncs**
(``blocking_syncs`` counts the protocol's misses; the legacy paths count
every synchronous readback there). The price is one slot of retirement lag:
a finished request's slot frees at t+1, and the serve trace's served counts
arrive one slot late (``drain`` flushes the tail).

Ragged length-aware prefill
---------------------------
Admission buckets prompts into power-of-two sub-buckets (P/4, P/2, P) of
``prompt_len`` and passes per-row real lengths to the length-aware prefill
(``model.prefill(prompt_lens=...)``): logits come from each row's real last
token, decode resumes at pos = len, and cache slots beyond len stay empty.
Results are bit-identical across bucket sizes (pads are inert under the
causal mask), so admission groups can pick the smallest bucket that fits —
short prompts stop paying full-bucket FLOPs and, on the paged engine, stop
allocating full-prompt pages. Gated to dense-attention stacks
(``ragged_prefill_supported``); other archs fall back to the padded bucket.

``PagedEngine`` (below) is the paged-KV-cache variant: same dispatch
budget, but admission allocates pages from a shared pool instead of
claiming a dense slot — see DESIGN.md §6.

All hot-path jits are *module-level*, keyed on static (cfg, sig, n) — every
engine instance with the same geometry shares one compile (``trace_count``
backs the no-retrace regression tests, mirroring the scheduler's).
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.cache import PageAllocator, PrefixIndex
from repro.cache.paged import pages_for
from repro.cache.precision import resolve_kv_precision
from repro.configs.base import ModelConfig
from repro.models import model as M
from repro.models.transformer import (
    chunked_prefill_supported,
    paged_pools_init,
    paged_segments_supported,
    ragged_prefill_supported,
)
from repro.obs import OBS_OFF, wall_us
from repro.runtime.request import Request
from repro.runtime.sampling import SamplingParams, row_tables, sample_rows

# Sentinel for short-prompt padding. Padding used to cycle the prompt via
# np.resize, which silently duplicated content; a constant sentinel keeps
# padded positions observable (and identical across requests).
PAD_ID = 0

# trace counter for the no-retrace regression tests: the increments run only
# when jax traces (not on cached calls), so this counts compiles, not calls.
_TRACE_COUNT = {"n": 0}


def trace_count() -> int:
    return _TRACE_COUNT["n"]


class ReadbackTimeout(RuntimeError):
    """A pending readback packet never became ready within the engine's
    ``readback_timeout_s`` bound. Carries enough context to diagnose the
    wedge: the control slot whose counters were in flight, the array that
    stalled, and the rows whose retirement the packet was carrying."""

    def __init__(self, slot: int, array: str, rows: list, timeout_s: float):
        self.slot = slot
        self.array = array
        self.rows = list(rows)
        self.timeout_s = timeout_s
        super().__init__(
            f"readback for slot {slot} not ready after {timeout_s:g}s "
            f"(array {array!r}; rows awaiting retirement: {self.rows})")


@dataclasses.dataclass
class EngineConfig:
    batch_slots: int = 8
    prompt_len: int = 32
    cache_len: int = 128
    greedy: bool = True           # False => temperature/top-k sampling
    temperature: float = 1.0
    top_k: int = 0                # 0 = full distribution
    seed: int = 0
    # engine-wide default SamplingParams for requests that carry none
    # (request.sampling always wins). None + greedy=False falls back to
    # SamplingParams(temperature, top_k) so pre-sampling-layer configs
    # keep serving; the RNG is request-keyed either way (DESIGN.md §13).
    sampling: Optional[SamplingParams] = None
    shape_window: Optional[int] = None
    eos_id: Optional[int] = None  # stop token (None = length-only stopping)
    ragged_prefill: bool = True   # length-aware bucketed prefill (auto-gated)
    gen_buf_len: int = 0          # sync-free token ring capacity; 0 => cache_len
    # continuous batching (step_slot_chunked): prompt chunk width per row per
    # slot (0 => prompt_len // 4, page-size-aligned on the paged engine) and
    # the per-slot prefill token budget across rows (0 => unlimited).
    chunk_size: int = 0
    chunk_budget: int = 0
    # readback watchdog (DESIGN.md §12): the bounded wait on a pending
    # readback packet before the consumer raises ReadbackTimeout instead of
    # hanging drain()/retirement forever on a wedged transfer. <= 0 disables
    # the bound (the pre-watchdog blocking behavior).
    readback_timeout_s: float = 30.0
    # KV-cache precision spec (DESIGN.md §14): "" inherits the model config;
    # "native" / "int8" / "fp8" override it (applied via cfg.replace at
    # engine construction, so the jitted paths key on one source of truth).
    kv_precision: str = ""


@dataclasses.dataclass
class PagedEngineConfig(EngineConfig):
    """Engine config plus the paged-pool geometry.

    KV memory = num_pages * page_size rows (vs batch_slots * cache_len for
    the dense engine); ``max_active`` is the decode batch (rows), bounded by
    compute, not memory. ``max_pages_per_req`` bounds one request's block
    table; 0 derives it from cache_len, and raising it past
    cache_len/page_size is how requests grow beyond the dense cache_len.
    """

    page_size: int = 16
    num_pages: int = 64
    max_active: int = 8
    max_pages_per_req: int = 0    # 0 => cache_len // page_size
    # size of the quantized page region (physical ids at the top of the
    # pool; DESIGN.md §14). -1 auto-derives: every page quantized when the
    # resolved kv_precision is quantized, none otherwise. Values between 0
    # and num_pages build a *mixed* pool — the PrecisionAware policy's
    # playground: admission picks the region per request.
    quant_pages: int = -1
    # prefix sharing (DESIGN.md §10): admission maps a prompt's shared
    # prefix onto resident pages through a radix index; only the novel
    # suffix allocates/prefills. Off by default — sharing-off behavior is
    # bit-for-bit the pre-sharing engine (no pins, no refcounts > 1).
    prefix_sharing: bool = False


@dataclasses.dataclass(frozen=True)
class _DecodeSig:
    """The hashable slice of EngineConfig the jitted decode path closes
    over — a static jit key, so equal-config engines share executables.

    ``sampling`` is the only per-dispatch bit: False traces the pure-argmax
    scan (byte-identical to the pre-sampling-layer executable — greedy
    serving never pays for the sampling layer), True traces the per-row
    heterogeneous sampler. The actual knobs (temperature/top-k/...) are
    *runtime* per-row tables now, not static keys, so changing a request's
    params never recompiles."""

    sampling: bool = False
    shape_window: Optional[int] = None
    eos_id: Optional[int] = None

    @staticmethod
    def of(ecfg: EngineConfig) -> "_DecodeSig":
        return _DecodeSig(False, ecfg.shape_window, ecfg.eos_id)


class SyncState(NamedTuple):
    """Device-resident per-slot generation state for the sync-free loop.

    The decode scan owns sampling, stop masks, and the generated-token ring
    buffer, so the host never blocks on token values. ``gen_buf`` is written
    at ``age % cap`` (cap >= max_new_tokens in practice, so it never wraps
    before retirement); ``done`` freezes a row — its decode keeps running,
    masked, until the host retires it one slot later.
    """

    cur_tok: jax.Array   # (B,)    next decode input (last sampled token)
    age: jax.Array       # (B,)    tokens generated so far (prefill's counts)
    budget: jax.Array    # (B,)    max_new_tokens; 0 = inactive row
    done: jax.Array      # (B,)    bool — finished or inactive
    gen_buf: jax.Array   # (B, cap) generated-token ring buffer


def sync_state_init(batch: int, cap: int) -> SyncState:
    return SyncState(
        cur_tok=jnp.zeros((batch,), jnp.int32),
        age=jnp.zeros((batch,), jnp.int32),
        budget=jnp.zeros((batch,), jnp.int32),
        done=jnp.ones((batch,), jnp.bool_),
        gen_buf=jnp.zeros((batch, cap), jnp.int32),
    )


def _bucket_prompt(tokens, prompt_len: int) -> tuple[np.ndarray, bool]:
    """Fit a prompt to the fixed prefill bucket.

    Long prompts are truncated (flagged, so the caller can record it on the
    Request); short prompts are padded with the PAD_ID sentinel.
    """
    toks = np.asarray(tokens[:prompt_len], np.int32)
    truncated = len(tokens) > prompt_len
    if len(toks) < prompt_len:
        toks = np.concatenate(
            [toks, np.full(prompt_len - len(toks), PAD_ID, np.int32)]
        )
    return toks, truncated


def _prompt_buckets(P: int, quantum: int = 1) -> list:
    """Power-of-two prompt sub-buckets {P/4, P/2, P}, rounded up to the
    engine's placement quantum (page_size for the paged engine)."""
    out = set()
    for b in (P // 4, P // 2, P):
        b = -(-max(b, 1) // quantum) * quantum
        if 0 < b <= P:
            out.add(b)
    return sorted(out) or [P]


# First-token sampling for the host-side admission paths (the sync paths
# compute it inside _sync_admit/_sync_activate): one jitted call over the
# prefill logits, age 0, empty history.
_sample_first = jax.jit(sample_rows)


# ------------------------------------------------------- module-level jits
@partial(jax.jit, static_argnames=("cfg", "cache_len", "shape_window"))
def _prefill_padded(params, batch, cfg, cache_len, shape_window):
    _TRACE_COUNT["n"] += 1
    return M.prefill(params, batch, cfg, cache_len, shape_window=shape_window)


@partial(jax.jit, static_argnames=("cfg", "cache_len", "shape_window"))
def _prefill_ragged(params, batch, lens, cfg, cache_len, shape_window):
    _TRACE_COUNT["n"] += 1
    return M.prefill(params, batch, cfg, cache_len, shape_window=shape_window,
                     prompt_lens=lens)


@partial(jax.jit, static_argnames=("cfg", "sig"))
def _decode_one(params, state, toks, samp, ages, hist, *, cfg, sig):
    _TRACE_COUNT["n"] += 1
    logits, state = M.decode_step(params, state, toks, cfg,
                                  shape_window=sig.shape_window)
    if not sig.sampling:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), state
    return sample_rows(logits, samp, ages, hist, ages), state


def _scan_decode(decode_fn, state, toks, samp, ages, hist, n, sig):
    """The shared fused-decode scan. sig.sampling=False traces the exact
    pre-sampling-layer greedy body (two-element carry, no tables — greedy
    executables stay byte-identical); True threads the host-built history
    through the carry so mid-scan penalties see every token, including the
    ones sampled earlier in the same dispatch."""
    if not sig.sampling:
        def body(carry, i):
            toks, state = carry
            logits, state = decode_fn(state, toks)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (nxt, state), nxt

        (_, state), outs = jax.lax.scan(body, (toks, state), jnp.arange(n))
        return outs, state

    B, cap = hist.shape

    def body(carry, i):
        toks, state, hist, ages = carry
        logits, state = decode_fn(state, toks)
        nxt = sample_rows(logits, samp, ages, hist, ages)
        hist = hist.at[jnp.arange(B), ages % cap].set(nxt)
        return (nxt, state, hist, ages + 1), nxt

    (_, state, _, _), outs = jax.lax.scan(
        body, (toks, state, hist, ages), jnp.arange(n))
    return outs, state


@partial(jax.jit, static_argnames=("n", "cfg", "sig"))
def _decode_n(params, state, toks, samp, ages, hist, *, n, cfg, sig):
    """n fused decode steps; returns per-step tokens (n, B)."""
    _TRACE_COUNT["n"] += 1
    fn = lambda state, toks: M.decode_step(params, state, toks, cfg,
                                           shape_window=sig.shape_window)
    return _scan_decode(fn, state, toks, samp, ages, hist, n, sig)


@partial(jax.jit, static_argnames=("n", "cfg", "sig"))
def _decode_n_paged(params, state, toks, samp, ages, hist, *, n, cfg, sig):
    _TRACE_COUNT["n"] += 1
    fn = lambda state, toks: M.decode_step_paged(params, state, toks, cfg)
    return _scan_decode(fn, state, toks, samp, ages, hist, n, sig)


def _sync_step(sync: SyncState, nxt, sig: _DecodeSig):
    """One decode step's sync-state advance: write the sampled token into
    the ring buffer, advance ages, latch stop masks; returns the newly-
    finished count (this step's mu contribution)."""
    B, cap = sync.gen_buf.shape
    active = ~sync.done
    tok = jnp.where(active, nxt, sync.cur_tok)
    written = sync.gen_buf.at[jnp.arange(B), sync.age % cap].set(tok)
    gen_buf = jnp.where(active[:, None], written, sync.gen_buf)
    age = sync.age + active.astype(jnp.int32)
    fin = age >= sync.budget
    if sig.eos_id is not None:
        fin = fin | (tok == sig.eos_id)
    done = sync.done | (active & fin)
    served = jnp.sum((done & active).astype(jnp.int32))
    return SyncState(tok, age, sync.budget, done, gen_buf), served


# Donating the model state (argument 1) lets XLA reuse the KV caches/pools
# in place on every backend; the donated input is deleted at dispatch, so
# callers rebind it from the outputs and never read it again.
# SyncState is deliberately NOT donated: the previous slot's pending readback
# packet still references its done/age/gen_buf arrays until the
# post-dispatch consume — donating them would delete buffers with a
# device->host copy outstanding.
_DONATE = (1,)


def _sync_next(sig: _DecodeSig, logits, samp, sync: SyncState):
    """One sync-free step's token draw: greedy argmax, or the per-row
    sampler reading the device ring buffer as history (sync.age tokens are
    live in gen_buf; admission validates age never exceeds the ring)."""
    if not sig.sampling:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return sample_rows(logits, samp, sync.age, sync.gen_buf, sync.age)


@partial(jax.jit, static_argnames=("n", "cfg", "sig"), donate_argnums=_DONATE)
def _decode_n_sync(params, state, sync, samp, *, n, cfg, sig):
    """Sync-free fused decode: sampling/EOS/ring buffer live in the scan.

    Rows whose stop mask latches keep computing (masked — the standard
    continuous-batching trade) but stop writing: their pos freezes, so a
    finished row re-writes its own last cache slot instead of marching
    forward. Returns (state, sync, served_per_step) — the host reads the
    tiny sync counters back asynchronously, a slot later.
    """
    _TRACE_COUNT["n"] += 1

    def body(carry, i):
        state, sync = carry
        logits, state2 = M.decode_step(params, state, sync.cur_tok, cfg,
                                       shape_window=sig.shape_window)
        nxt = _sync_next(sig, logits, samp, sync)
        state2 = state2._replace(pos=jnp.where(sync.done, state.pos, state2.pos))
        sync2, served = _sync_step(sync, nxt, sig)
        return (state2, sync2), served

    with jax.named_scope("decode"):
        (state, sync), served = jax.lax.scan(body, (state, sync), jnp.arange(n))
    return state, sync, served


@partial(jax.jit, static_argnames=("n", "cfg", "sig"), donate_argnums=_DONATE)
def _decode_n_sync_paged(params, state, sync, samp, *, n, cfg, sig):
    _TRACE_COUNT["n"] += 1

    def body(carry, i):
        state, sync = carry
        logits, state2 = M.decode_step_paged(params, state, sync.cur_tok, cfg)
        nxt = _sync_next(sig, logits, samp, sync)
        state2 = state2._replace(pos=jnp.where(sync.done, state.pos, state2.pos))
        sync2, served = _sync_step(sync, nxt, sig)
        return (state2, sync2), served

    with jax.named_scope("decode"):
        (state, sync), served = jax.lax.scan(body, (state, sync), jnp.arange(n))
    return state, sync, served


@dataclasses.dataclass
class PrefillCursor:
    """Host-side chunked-prefill progress for one admitted request.

    The request occupies its engine row from admission, but its prompt is
    written chunk by chunk — ``off`` tokens are already in the cache. The
    row joins decode (and becomes retirable) only at the *activation*
    dispatch, the one carrying its final chunk; until then the device's
    ``done`` flag for the row is stale and the readback consumer must skip
    it (see ``Engine._consume_read``).
    """

    req: Request
    row: int
    toks: np.ndarray          # (L,) int32 — the real (truncated) prompt
    # tokens already resident when the row was claimed (a prefix-cache hit):
    # the cursor starts past them, so their chunks are never dispatched —
    # "skip the cached chunks" is just a nonzero starting offset.
    cached: int = 0

    def __post_init__(self):
        self.off = self.cached
        self.started = False   # start_slot stamped at the first real chunk

    @property
    def remaining(self) -> int:
        return len(self.toks) - self.off


def _sync_activate(sync: SyncState, logits, final, budgets, samp, *,
                   sig: _DecodeSig):
    """Device-side activation of rows finishing their prompt this dispatch:
    the first generated token — argmax, or the row's sampler at age 0 with
    an empty history, matching every other admission path — comes from the
    final chunk's last-token logits, masked into the sync state. Runs
    inside the chunked dispatch — no logits readback."""
    if sig.sampling:
        first = sample_rows(logits, samp, jnp.zeros_like(sync.age))
    else:
        first = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    fin = budgets <= 1
    if sig.eos_id is not None:
        fin = fin | (first == sig.eos_id)
    return SyncState(
        cur_tok=jnp.where(final, first, sync.cur_tok),
        age=jnp.where(final, 1, sync.age),
        budget=jnp.where(final, budgets, sync.budget),
        done=jnp.where(final, fin, sync.done),
        gen_buf=sync.gen_buf.at[:, 0].set(
            jnp.where(final, first, sync.gen_buf[:, 0])),
    )


# the plan arrays each mixed dispatch takes, in argument order
_CHUNK_ARGS_DENSE = ("toks", "pos0", "valid", "reset", "final", "budgets")
_CHUNK_ARGS_PAGED = ("toks", "pos0", "valid", "base", "final", "budgets")


@partial(jax.jit, static_argnames=("n", "cfg", "sig"), donate_argnums=_DONATE)
def _chunk_decode_sync(params, state, sync, toks, pos0, valid, reset, final,
                       budgets, samp, *, n, cfg, sig):
    """One continuous-batching control slot in ONE dispatch: per-row prompt
    chunks (K/V written at [pos0, pos0+valid)) + device-side activation of
    rows finishing their prompt + the n-step fused sync-free decode scan.

    Rows mid-prompt carry done=True, so the scan freezes their pos and
    discards their (masked) decode compute; the one masked write they make —
    K/V of a dummy token at their next chunk offset — is overwritten by that
    chunk before anything attends it.

    Only the rows with ``valid > 0`` run the chunk pass (a device-valued
    trip count: one program whatever the number of chunk rows).
    """
    _TRACE_COUNT["n"] += 1
    with jax.named_scope("chunk"):
        logits, state = M.chunk_step(params, state, toks, pos0, valid, reset,
                                     cfg, shape_window=sig.shape_window)
        sync = _sync_activate(sync, logits, final, budgets, samp, sig=sig)

    def body(carry, i):
        state, sync = carry
        logits, state2 = M.decode_step(params, state, sync.cur_tok, cfg,
                                       shape_window=sig.shape_window)
        nxt = _sync_next(sig, logits, samp, sync)
        state2 = state2._replace(pos=jnp.where(sync.done, state.pos, state2.pos))
        sync2, served = _sync_step(sync, nxt, sig)
        return (state2, sync2), served

    with jax.named_scope("decode"):
        (state, sync), served = jax.lax.scan(body, (state, sync), jnp.arange(n))
    return state, sync, served


@partial(jax.jit, static_argnames=("n", "cfg", "sig"), donate_argnums=_DONATE)
def _chunk_decode_sync_paged(params, state, sync, toks, pos0, valid, base,
                             final, budgets, samp, *, n, cfg, sig):
    _TRACE_COUNT["n"] += 1
    with jax.named_scope("chunk"):
        logits, state = M.chunk_step_paged(params, state, toks, pos0, valid,
                                           cfg, base=base)
        sync = _sync_activate(sync, logits, final, budgets, samp, sig=sig)

    def body(carry, i):
        state, sync = carry
        logits, state2 = M.decode_step_paged(params, state, sync.cur_tok, cfg)
        nxt = _sync_next(sig, logits, samp, sync)
        state2 = state2._replace(pos=jnp.where(sync.done, state.pos, state2.pos))
        sync2, served = _sync_step(sync, nxt, sig)
        return (state2, sync2), served

    with jax.named_scope("decode"):
        (state, sync), served = jax.lax.scan(body, (state, sync), jnp.arange(n))
    return state, sync, served


@partial(jax.jit, static_argnames=("sig",))
def _sync_admit(sync: SyncState, logits, rows, budgets, samp, *, sig):
    """Device-side admission: first token (argmax, or each admitted row's
    sampler at age 0, matching the legacy paths) + per-row sync-state
    reset, all in one scatter — no logits readback. ``samp`` is aligned
    with the *prefill* batch rows (``rows`` maps them to engine rows). Pad
    rows carry an out-of-range index and are dropped."""
    _TRACE_COUNT["n"] += 1
    if sig.sampling:
        first = sample_rows(logits, samp,
                            jnp.zeros(logits.shape[0], jnp.int32))
    else:
        first = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    fin = budgets <= 1
    if sig.eos_id is not None:
        fin = fin | (first == sig.eos_id)
    return SyncState(
        cur_tok=sync.cur_tok.at[rows].set(first, mode="drop"),
        age=sync.age.at[rows].set(1, mode="drop"),
        budget=sync.budget.at[rows].set(budgets, mode="drop"),
        done=sync.done.at[rows].set(fin, mode="drop"),
        gen_buf=sync.gen_buf.at[rows, 0].set(first, mode="drop"),
    )


@jax.jit
def _sync_clear(sync: SyncState, rows):
    """Deactivate rows (paged preemption): latch done, zero the budget."""
    _TRACE_COUNT["n"] += 1
    return sync._replace(
        done=sync.done.at[rows].set(True, mode="drop"),
        budget=sync.budget.at[rows].set(0, mode="drop"),
    )


@partial(jax.jit, static_argnames=("slot",))
def _splice_one(state, one, slot):
    """Insert batch-1 prefill state into batch state at slot."""
    _TRACE_COUNT["n"] += 1
    caches = jax.tree.map(
        lambda big, new: jax.lax.dynamic_update_index_in_dim(
            big, new[:, 0], slot, axis=1
        ),
        state.caches, one.caches,
    )
    return M.DecodeState(
        caches=caches,
        pos=state.pos.at[slot].set(one.pos[0]),
        last_tok=state.last_tok.at[slot].set(one.last_tok[0]),
    )


@jax.jit
def _splice_many(state, new, slots):
    """Insert prefill rows at the given slot indices (one scatter).

    Pad rows carry an out-of-range slot index; mode="drop" discards them,
    so the bucketed batch-B prefill can splice any k <= B rows with a
    single fixed-shape executable.
    """
    _TRACE_COUNT["n"] += 1
    caches = jax.tree.map(
        lambda big, nw: big.at[:, slots].set(nw, mode="drop"),
        state.caches, new.caches,
    )
    return M.DecodeState(
        caches=caches,
        pos=state.pos.at[slots].set(new.pos, mode="drop"),
        last_tok=state.last_tok.at[slots].set(new.last_tok, mode="drop"),
    )


_paged_splice = jax.jit(M.paged_splice_prompt)
_fork_pages = jax.jit(M.fork_pages)


def _host_take(row_toks, req: Request, age: int, n_steps: int,
               eos_id: Optional[int]) -> tuple[int, bool]:
    """Legacy-path helper: how many of this slot's tokens a request consumes
    (budget- and EOS-limited) and whether it finished. Mirrors the device
    stop mask exactly."""
    if eos_id is not None and req.generated and req.generated[-1] == eos_id:
        return 0, True  # finished at admission: first token was EOS
    limit = int(min(n_steps, req.max_new_tokens - age))
    if eos_id is not None:
        for j in range(limit):
            if int(row_toks[j]) == eos_id:
                return j + 1, True
    return limit, age + limit >= req.max_new_tokens


class Engine:
    def __init__(self, cfg: ModelConfig, params, ecfg: EngineConfig,
                 extra_batch=None, obs=None):
        if ecfg.kv_precision:
            cfg = cfg.replace(kv_precision=ecfg.kv_precision)
        # one resolution point: warns (once per dtype) when only the
        # deprecated cache_dtype is set; the jitted paths re-derive the same
        # spec warning-free via the lru-cached models.attention.kv_precision_of
        self.kvp = resolve_kv_precision(cfg.kv_precision, cfg.cache_dtype)
        # the dense cache stores quantized values + scales directly, so the
        # dense engine prefills under its own cfg (the paged engine swaps in
        # a native-storage variant — see PagedEngine.__init__)
        self._prefill_cfg = cfg
        self.cfg, self.params, self.ecfg = cfg, params, ecfg
        self.extra = extra_batch or {}
        # observability is host-side and pull-based: the jitted hot paths
        # never see it, so on/off cannot change a single token
        self.obs = obs or OBS_OFF
        self.obs_pid = 0          # replica index (the fleet stamps it)
        self.device = None        # committed device (see ``place``)
        self._now = 0             # current control slot, for deep emit sites
        B, P = ecfg.batch_slots, ecfg.prompt_len
        self._sig = _DecodeSig.of(ecfg)
        self._init_sampling(ecfg)
        self._ragged = ecfg.ragged_prefill and ragged_prefill_supported(cfg)
        self._buckets = _prompt_buckets(P)
        self._gen_cap = ecfg.gen_buf_len or ecfg.cache_len

        # back-compat instance handles over the shared module-level jits
        # (greedy-only oracles: key is accepted and ignored — the RNG is
        # request-keyed now, see DESIGN.md §13)
        self._prefill = lambda params, batch: _prefill_padded(
            params, batch, self.cfg, self.ecfg.cache_len, self.ecfg.shape_window)
        self._decode = lambda params, state, toks, key=None: _decode_one(
            params, state, toks, None, None, None, cfg=self.cfg, sig=self._sig)
        self._decode_n = lambda params, state, toks, key, n: _decode_n(
            params, state, toks, None, None, None, n=n, cfg=self.cfg,
            sig=self._sig)
        self._splice = _splice_one
        self._splice_many = _splice_many

        # boot: empty batch state from a dummy prefill over the whole batch
        boot = {"tokens": jnp.zeros((B, P), jnp.int32), **self.extra}
        _, self.state = self._prefill(params, boot)
        self.sync = sync_state_init(B, self._gen_cap)
        self.active: list = [None] * B
        self.pending: list = []
        self.finished: list = []
        self._exported = 0            # finished requests export_metrics saw
        self.slot_age = np.zeros(B, np.int32)
        self.steps = 0
        self.served_history: list = []
        self.prefill_dispatches = 0   # excludes the boot prefill
        self.decode_dispatches = 0
        self.blocking_syncs = 0       # dispatch-gating synchronous readbacks
        self.readback_waits = 0       # sync-free consume-side overlap misses
        self._pending_read = None     # sync-free: last slot's async readback
        self._chaos = None            # fault-injection seam (reliability)
        # paged-only counters, carried at 0 by the dense engine so the
        # counters() key set never drifts between engine types (DESIGN.md
        # §11: `preemptions` is reported as 0, never missing)
        self.preemptions = 0
        self.alloc_failures = 0
        self.peak_active = 0
        self.prefix_hits = 0
        self.prefix_forks = 0
        self.fork_dispatches = 0
        self.mixed_dispatches = 0     # chunk+decode dispatches
        self.chunk_rows = 0           # rows those dispatches chunked
        self.eviction_raced_hits = 0
        self.occupancy_hwm = 0.0
        # admission epoch per row: a readback packet only retires a row if
        # the row still hosts the request it observed (guards against a
        # stale pre-admission done flag retiring a freshly admitted request)
        self._row_epoch = np.zeros(B, np.int64)
        # continuous batching: per-row chunked-prefill cursors (insertion
        # order = admission order = chunk-scheduling priority)
        self._cursors: dict = {}
        self._chunk = ecfg.chunk_size or max(P // 4, 1)
        self._chunk_ok = (chunked_prefill_supported(cfg)
                          and ecfg.shape_window is None)

    # ------------------------------------------------------------------
    def queue_len(self) -> int:
        return len(self.pending)

    def token_backlog(self) -> int:
        """Pending prompt *tokens*: queued prompts plus the unwritten tails
        of in-flight chunked prefills — the observation the TokenBacklogAware
        policy prices (a request count hides that one 4k prompt costs what
        250 short ones do)."""
        P = self.ecfg.prompt_len
        t = sum(max(1, min(len(r.tokens), P)) for r in self.pending)
        return t + sum(c.remaining for c in self._cursors.values())

    def submit(self, reqs: list) -> None:
        now_us = wall_us()
        for r in reqs:
            if r.arrival_us is None:   # a fleet requeue keeps the first
                r.arrival_us = now_us
        tr = self.obs.trace
        if tr.enabled:
            for r in reqs:
                tr.emit("arrival", slot=r.arrival_slot, rid=r.rid,
                        pid=self.obs_pid, prompt_len=len(r.tokens))
        self.pending.extend(reqs)

    # ------------------------------------------------ per-request sampling
    def _init_sampling(self, ecfg: EngineConfig) -> None:
        """Resolve the engine-default SamplingParams once (DESIGN.md §13)."""
        default = ecfg.sampling
        if default is None and not ecfg.greedy:
            # pre-sampling-layer configs: greedy=False + temperature/top_k
            default = SamplingParams(temperature=ecfg.temperature,
                                     top_k=ecfg.top_k)
        if default is not None and default.is_pure_greedy:
            default = None
        self._default_samp = default
        self._sig_sampling = dataclasses.replace(self._sig, sampling=True)
        self.requests_sampled = 0   # admissions of sampling-path requests

    def _effective(self, req: Request) -> Optional[SamplingParams]:
        """The params actually governing a request — request-level wins,
        then the engine default; None means the pure-argmax path (so greedy
        traffic never pays for the sampling layer)."""
        p = req.sampling if req.sampling is not None else self._default_samp
        if p is None or p.is_pure_greedy:
            return None
        return p

    def _resolve_rows(self, reqs) -> list:
        """Per-row ``(params, rid)`` entries (None = greedy/empty row)."""
        out = []
        for r in reqs:
            e = self._effective(r) if r is not None else None
            out.append((e, r.rid) if e is not None else None)
        return out

    def _samp_args(self) -> tuple:
        """(tables, sig) for a decode dispatch over the current active
        rows. All-greedy batches get (None, base sig): the dispatch routes
        to the sampling-free executable, bit-identical to the
        pre-sampling-layer engine."""
        resolved = self._resolve_rows(self.active)
        if not any(e is not None for e in resolved):
            return None, self._sig
        return row_tables(resolved, self.ecfg.seed), self._sig_sampling

    def _samp_decode_args(self) -> tuple:
        """(tables, ages, hist, sig) for the host-side (non-sync) decode
        paths: ages = each row's generated-token count, hist = the
        generated history the penalties read (admission validates
        max_new_tokens <= gen cap for sampled requests, so it never
        wraps)."""
        samp, sig = self._samp_args()
        if not sig.sampling:
            return None, None, None, sig
        B, cap = len(self.active), self._gen_cap
        ages = np.zeros(B, np.int32)
        hist = np.zeros((B, cap), np.int32)
        for row, r in enumerate(self.active):
            if r is None or not r.generated:
                continue
            g = r.generated[-cap:]
            hist[row, : len(g)] = g
            ages[row] = len(r.generated)
        return samp, jnp.asarray(ages), jnp.asarray(hist), sig

    def _admit_samp_args(self, reqs, rows: int) -> tuple:
        """(tables, sig) for an admission dispatch: entry j describes
        prefill row j (the admitted request), padded to ``rows``."""
        resolved = self._resolve_rows(reqs)
        if not any(e is not None for e in resolved):
            return None, self._sig
        resolved += [None] * (rows - len(resolved))
        return row_tables(resolved, self.ecfg.seed), self._sig_sampling

    def _validate_sampled(self, req: Request) -> None:
        """Sampled requests must fit the generated-token history buffer on
        every path (the sync paths already demand this for the ring): the
        penalties read it, so overflowing it would silently change
        streams."""
        if self._effective(req) is not None and \
                req.max_new_tokens > self._gen_cap:
            raise ValueError(
                f"request {req.rid}: max_new_tokens {req.max_new_tokens} "
                f"exceeds the sampled-request history capacity {self._gen_cap} "
                "(gen_buf_len)")

    # ----------------------------------------------------- observability
    def counters(self) -> dict:
        """The one counter/gauge surface every engine type shares.

        Replaces the per-step-mode stats dicts as the source of truth for
        cumulative state: a dense engine reports the paged-only keys
        (preemptions, pages_*, occupancy...) as 0 rather than omitting
        them, so fleet aggregation and the metrics exporter never branch
        on engine type. Level keys (GAUGE_KEYS in repro.obs.metrics)
        export as gauges; everything else is a monotone counter.
        """
        return {
            "steps": self.steps,
            "requests_finished": len(self.finished),
            "requests_active": sum(r is not None for r in self.active),
            "requests_pending": len(self.pending),
            "requests_prefilling": len(self._cursors),
            "requests_sampled": self.requests_sampled,
            "prefill_dispatches": self.prefill_dispatches,
            "decode_dispatches": self.decode_dispatches,
            "fork_dispatches": self.fork_dispatches,
            "mixed_dispatches": self.mixed_dispatches,
            "chunk_rows": self.chunk_rows,
            "blocking_syncs": self.blocking_syncs,
            "readback_waits": self.readback_waits,
            "preemptions": self.preemptions,
            "alloc_failures": self.alloc_failures,
            "eviction_raced_hits": self.eviction_raced_hits,
            "peak_active": self.peak_active,
            "prefix_hit_tokens": self.prefix_hits,
            "prefix_forks": self.prefix_forks,
            "prefix_inserted_pages": 0,
            "prefix_evicted_pages": 0,
            "occupancy": 0.0,
            "occupancy_hwm": float(self.occupancy_hwm),
            "committed_occupancy": 0.0,
            "pages_used": 0,
            "pages_free": 0,
            "pages_shared": 0,
            "pages_pinned": 0,
            "frag_tokens": 0,
            "peak_pages": 0,
            "pages_quant": 0,
            "pages_quant_used": 0,
            "quant_occupancy": 0.0,
        }

    def export_metrics(self, labels: Optional[dict] = None) -> None:
        """Publish counters() and the latency stamps of the requests retired
        since the last export into the obs registry (no-op when off)."""
        self.obs.export(self.counters(), labels)
        self.obs.export_requests(self.finished[self._exported:], labels)
        self._exported = len(self.finished)

    def place(self, device) -> None:
        """Commit this engine's device state — params, model state, sync
        state — to ``device`` (one replica per chip; see ReplicaFleet)."""
        self.device = device
        self.params, self.state, self.sync = jax.device_put(
            (self.params, self.state, self.sync), device)

    def _slot_stats(self, n_active: int, served: int, **extra) -> dict:
        """The per-slot stats dict every step mode returns — one builder
        instead of four hand-rolled near-duplicates, so the key set cannot
        drift between modes or engine types (dense modes report
        occupancy=0.0 and preemptions=0, not missing keys)."""
        self.peak_active = max(self.peak_active, n_active)
        d = {
            "active": n_active,
            "queue": len(self.pending),
            "served": served,
            "finished_total": len(self.finished),
            "prefilling": len(self._cursors),
            "occupancy": 0.0,
            "preemptions": self.preemptions,
            "blocking_syncs": self.blocking_syncs,
        }
        d.update(extra)
        return d

    def _emit_admission(self, req: Request, row: int, now: int) -> None:
        """Stamp engine-claim time (queue-wait = admit_slot - arrival_slot)
        and record the admission event. Preemption resets the stamp; the
        re-claim restamps it, like start_slot/first_token_slot. A blocking
        admission already holds the first token on the host (``generated``
        set), so its first-token stamp is the claim's."""
        req.admit_slot = now
        req.admit_us = wall_us()
        if req.generated:
            req.first_token_us = req.admit_us
        if self._effective(req) is not None:
            self.requests_sampled += 1
        tr = self.obs.trace
        if tr.enabled:
            tr.emit("admission", slot=now, rid=req.rid, row=row,
                    pid=self.obs_pid)

    def _emit_retire(self, req: Request, row: int, slot: int,
                     us: Optional[float] = None) -> None:
        req.finish_us = wall_us() if us is None else us
        tr = self.obs.trace
        if tr.enabled:
            tr.emit("retirement", slot=slot, rid=req.rid, row=row,
                    pid=self.obs_pid, tokens=len(req.generated or ()))

    def _raced_hit(self, row: int, what: str) -> None:
        """A prefix-cache hit degraded by a concurrent eviction — counted,
        and traced so the cache's race window is visible on the timeline."""
        self.eviction_raced_hits += 1
        tr = self.obs.trace
        if tr.enabled:
            tr.emit("raced_hit", slot=self._now, row=row, pid=self.obs_pid,
                    what=what)

    def free_slots(self) -> list:
        return [i for i, r in enumerate(self.active) if r is None]

    def _bucket(self, tokens, req: Optional[Request] = None,
                bucket: Optional[int] = None) -> np.ndarray:
        toks, truncated = _bucket_prompt(tokens, bucket or self.ecfg.prompt_len)
        if req is not None and truncated:
            req.truncated = True
        return toks

    def _pick_bucket(self, need: int) -> int:
        for b in self._buckets:
            if b >= need:
                return b
        return self.ecfg.prompt_len

    def _run_prefill(self, batch, lens: Optional[np.ndarray], cache_len: int):
        """One bucketed prefill dispatch — ragged (length-aware) when the
        arch supports it, padded otherwise."""
        if self._ragged:
            return _prefill_ragged(self.params, batch, jnp.asarray(lens),
                                   self._prefill_cfg, cache_len,
                                   self.ecfg.shape_window)
        return _prefill_padded(self.params, batch, self._prefill_cfg,
                               cache_len, self.ecfg.shape_window)

    def _admit_one(self, req: Request, slot: int, now: int) -> None:
        """Legacy batch-1 admission (the fused path's equivalence oracle)."""
        self._validate_sampled(req)
        P = self.ecfg.prompt_len
        L = max(1, min(len(req.tokens), P))
        bucket = self._pick_bucket(L) if self._ragged else P
        batch = {"tokens": jnp.asarray(self._bucket(req.tokens, req, bucket))[None, :],
                 **_slice_extra(self.extra, 1)}
        logits, one = self._run_prefill(
            batch, np.asarray([L], np.int32), self.ecfg.cache_len)
        self.prefill_dispatches += 1
        self.state = _splice_one(self.state, one, slot)
        self.blocking_syncs += 1
        req.start_slot = now
        req.first_token_slot = now   # first token came from this prefill
        samp, sig = self._admit_samp_args([req], 1)
        if sig.sampling:
            first = int(_sample_first(logits[:1], samp,
                                      jnp.zeros(1, jnp.int32))[0])
        else:
            first = int(jnp.argmax(logits[0]))
        req.generated = [first]
        self.active[slot] = req
        self.slot_age[slot] = 1  # first token came from prefill
        self._emit_admission(req, slot, now)

    def admit_pending(self, now: int, sync: bool = False) -> int:
        """Fill all free slots from the pending queue with ONE prefill.

        k requests -> one bucketed prefill + one scatter splice, instead of
        k (prefill + splice) dispatches. The prefill batch is padded to the
        full batch_slots rows (pad rows are dropped by the splice's
        out-of-range slot index) and, when the arch supports ragged prefill,
        to the smallest power-of-two prompt bucket covering the admitted
        lengths. ``sync=True`` computes the first token on device
        (``_sync_admit``) instead of reading logits back. Returns k.
        """
        B, P = self.ecfg.batch_slots, self.ecfg.prompt_len
        slots = self.free_slots()[: len(self.pending)]
        if not slots:
            return 0
        for r in self.pending[: len(slots)]:
            # validate BEFORE popping — a raise must not drop requests
            if sync and r.max_new_tokens > self._gen_cap:
                raise ValueError(
                    f"request {r.rid}: max_new_tokens {r.max_new_tokens} "
                    f"exceeds gen_buf_len {self._gen_cap}")
            self._validate_sampled(r)
        reqs = [self.pending.pop(0) for _ in slots]
        k = len(reqs)
        lens = np.full(B, P, np.int32)
        for j, r in enumerate(reqs):
            lens[j] = max(1, min(len(r.tokens), P))
        bucket = self._pick_bucket(int(lens[:k].max())) if self._ragged else P
        lens = np.minimum(lens, bucket)
        toks = np.zeros((B, bucket), np.int32)
        for j, r in enumerate(reqs):
            toks[j] = self._bucket(r.tokens, r, bucket)
        slot_idx = np.full(B, B, np.int32)  # B = out of range -> scatter drops
        slot_idx[:k] = slots
        batch = {"tokens": jnp.asarray(toks), **self.extra}
        with self.obs.trace.span("engine.dispatch", slot=now, pid=self.obs_pid,
                                 what="prefill", rows=k):
            logits, new = self._run_prefill(batch, lens, self.ecfg.cache_len)
            self.state = _splice_many(self.state, new, jnp.asarray(slot_idx))
        self.prefill_dispatches += 1
        if sync:
            budgets = np.zeros(B, np.int32)
            budgets[:k] = [r.max_new_tokens for r in reqs]
            samp, sig = self._admit_samp_args(reqs, B)
            self.sync = _sync_admit(self.sync, logits, jnp.asarray(slot_idx),
                                    jnp.asarray(budgets), samp, sig=sig)
            for req, slot in zip(reqs, slots, strict=True):
                req.start_slot = now
                req.first_token_slot = now
                req.generated = None  # filled from the device ring at retire
                self.active[slot] = req
                self.slot_age[slot] = 1
                self._row_epoch[slot] += 1
                self._emit_admission(req, slot, now)
            return k
        self.blocking_syncs += 1
        samp, sig = self._admit_samp_args(reqs, B)
        if sig.sampling:
            first = np.asarray(_sample_first(
                logits, samp, jnp.zeros(B, jnp.int32)))[:k]
        else:
            first = np.asarray(jnp.argmax(logits[:k], axis=-1))
        for j, (req, slot) in enumerate(zip(reqs, slots, strict=True)):
            req.start_slot = now
            req.first_token_slot = now
            req.generated = [int(first[j])]
            self.active[slot] = req
            self.slot_age[slot] = 1  # first token came from prefill
            self._emit_admission(req, slot, now)
        return k

    def step(self, now: int) -> dict:
        """Legacy engine slot: admit one-by-one -> one decode -> retire."""
        self._now = now
        eos = self.ecfg.eos_id
        for slot in self.free_slots():
            if not self.pending:
                break
            self._admit_one(self.pending.pop(0), slot, now)

        served = 0  # finishers THIS call (finish_slot alone double-counts
        #             when the serve loop reuses `now` across engine steps)
        for i, r in enumerate(self.active):  # already complete (prefill
            if r is not None and (self.slot_age[i] >= r.max_new_tokens or (
                    eos is not None and r.generated[-1] == eos)):
                r.finish_slot = now          # covered max_new_tokens<=1)
                self.finished.append(r)
                self.active[i] = None
                self._emit_retire(r, i, now)
                served += 1
        n_active = sum(r is not None for r in self.active)
        if n_active:
            toks = jnp.asarray(
                [r.generated[-1] if r else 0 for r in self.active], jnp.int32
            )
            samp, ages, hist, sig = self._samp_decode_args()
            with self.obs.trace.span("engine.dispatch", slot=now,
                                     pid=self.obs_pid, what="decode", n=1):
                nxt, self.state = _decode_one(self.params, self.state, toks,
                                              samp, ages, hist,
                                              cfg=self.cfg, sig=sig)
            self.decode_dispatches += 1
            self.blocking_syncs += 1
            nxt = np.asarray(nxt)
            for i, r in enumerate(self.active):
                if r is None:
                    continue
                r.generated.append(int(nxt[i]))
                self.slot_age[i] += 1
                if self.slot_age[i] >= r.max_new_tokens or (
                        eos is not None and int(nxt[i]) == eos):
                    r.finish_slot = now
                    self.finished.append(r)
                    self.active[i] = None
                    self._emit_retire(r, i, now)
                    served += 1

        self.served_history.append(served)
        self.steps += 1
        return self._slot_stats(n_active, served)

    def step_slot(self, now: int, n_steps: int = 1) -> dict:
        """One control slot, fused: batched admit -> scan decode -> retire.

        Issues at most 1 prefill + 1 decode jit dispatch regardless of how
        many requests are admitted or how many decode steps run. A slot
        whose request finishes mid-scan keeps decoding (masked — its extra
        tokens are discarded on the host), so per-step served counts mu(t)
        match what the legacy per-step loop would observe; the one semantic
        difference is that admission happens only at slot boundaries.
        """
        self._now = now
        admitted = self.admit_pending(now)
        n_active = sum(r is not None for r in self.active)
        per_step = [0] * n_steps
        if n_active:
            toks = jnp.asarray(
                [r.generated[-1] if r else 0 for r in self.active], jnp.int32
            )
            samp, ages, hist, sig = self._samp_decode_args()
            with self.obs.trace.span("engine.dispatch", slot=now,
                                     pid=self.obs_pid, what="decode", n=n_steps):
                all_toks, self.state = _decode_n(
                    self.params, self.state, toks, samp, ages, hist,
                    n=n_steps, cfg=self.cfg, sig=sig,
                )
            self.decode_dispatches += 1
            self.blocking_syncs += 1
            all_toks = np.asarray(all_toks)  # (n_steps, B)
            for i, r in enumerate(self.active):
                if r is None:
                    continue
                take, hit = _host_take(all_toks[:, i], r, int(self.slot_age[i]),
                                       n_steps, self.ecfg.eos_id)
                r.generated.extend(int(x) for x in all_toks[:take, i])
                self.slot_age[i] += take
                if hit or self.slot_age[i] >= r.max_new_tokens:
                    r.finish_slot = now
                    self.finished.append(r)
                    per_step[max(take - 1, 0)] += 1
                    self.active[i] = None
                    self._emit_retire(r, i, now)
        served = sum(per_step)
        self.served_history.append(served)
        self.steps += n_steps
        return self._slot_stats(n_active, served, served_per_step=per_step,
                                admitted=admitted)

    # ------------------------------------------------- sync-free protocol
    def _release_row(self, row: int) -> None:
        """Engine-specific cleanup when the sync-free path retires a row."""

    def _post_readback(self, now: int, served_steps, extra: Optional[dict] = None):
        """Initiate the async device->host copy of this slot's counters."""
        arrays = {"done": self.sync.done, "age": self.sync.age,
                  "gen": self.sync.gen_buf, "served": served_steps}
        if extra:
            arrays.update(extra)
        with self.obs.trace.span("engine.readback", slot=now, pid=self.obs_pid):
            for a in arrays.values():
                a.copy_to_host_async()
        packet = {"slot": now, "arrays": arrays,
                  "epoch": self._row_epoch.copy()}
        if self._chaos is not None:  # fault-injection seam (reliability)
            packet = self._chaos.wrap_readback(packet)
        self._pending_read = packet

    def _readback_ready(self, p: dict) -> bool:
        """Non-blocking: has the packet's device->host transfer completed?"""
        for a in p["arrays"].values():
            if not a.is_ready():
                return False
        return True

    def _await_readback(self, p: dict) -> None:
        """Bounded-wait watchdog (DESIGN.md §12): poll the packet's arrays
        until ready or ``readback_timeout_s`` elapses, then raise a
        diagnosable ``ReadbackTimeout`` instead of letting ``np.asarray``
        block forever on a wedged transfer. Disabled (<= 0) restores the
        unbounded blocking read."""
        timeout = getattr(self.ecfg, "readback_timeout_s", 0.0)
        deadline = None
        for name, a in p["arrays"].items():
            while not a.is_ready():
                if timeout <= 0:
                    break  # unbounded: the asarray below blocks as before
                now_s = time.monotonic()
                if deadline is None:
                    deadline = now_s + timeout
                elif now_s > deadline:
                    rows = [i for i, r in enumerate(self.active)
                            if r is not None and i not in self._cursors]
                    raise ReadbackTimeout(p["slot"], name, rows, timeout)
                time.sleep(2e-4)

    def _consume_read(self, p: Optional[dict],
                      count_waits: bool = True) -> tuple[int, list]:
        """Consume one readback packet: retire finished rows from host
        copies alone. By protocol this runs *after* the next slot's
        dispatches are in flight, so the read never gates the device
        pipeline; a not-yet-ready array is an overlap miss, tracked in
        ``readback_waits`` (the host waited, the device never idled).

        The packet is where tokens reach the host: a live row showing age
        >= 1 for the first time gets its ``first_token_us``, and a retired
        row its ``finish_us``, both at the moment the copy landed."""
        if p is None:
            return 0, []
        tr = self.obs.trace
        with tr.span("engine.consume", slot=p["slot"], pid=self.obs_pid) as sp:
            waited = False
            if count_waits:
                for a in p["arrays"].values():
                    if not a.is_ready():
                        self.readback_waits += 1
                        waited = True
                        break
            with tr.span("engine.wait", slot=p["slot"], pid=self.obs_pid):
                self._await_readback(p)
                done = np.asarray(p["arrays"]["done"])
            landed = wall_us()
            age = np.asarray(p["arrays"]["age"])
            gen = np.asarray(p["arrays"]["gen"])
            per_step = [int(x) for x in np.asarray(p["arrays"]["served"])]
            served = 0
            for row, req in enumerate(self.active):
                if req is None or row in self._cursors:
                    continue  # empty, or mid-chunked-prefill: the device done
                    #           flag is the previous tenant's — not live yet
                if p["epoch"][row] != self._row_epoch[row]:
                    continue  # row re-admitted after this packet was dispatched
                if req.first_token_us is None and age[row] >= 1:
                    req.first_token_us = landed
                if not done[row]:
                    continue
                a = int(age[row])
                req.generated = [int(t) for t in gen[row, :min(a, gen.shape[1])]]
                req.finish_slot = p["slot"]
                self.finished.append(req)
                self.active[row] = None
                self.slot_age[row] = 0
                self._release_row(row)
                self._emit_retire(req, row, p["slot"], us=landed)
                served += 1
            sp.set(retired=served, waited=waited)
        extra = served - sum(per_step)
        if extra > 0:  # admission-time finishers (budget <= 1 / EOS first tok)
            per_step = per_step or [0]
            per_step[0] += extra
        return served, per_step

    def step_slot_sync(self, now: int, n_steps: int = 1) -> dict:
        """One sync-free control slot: batched admit (device-side first
        token) -> dispatch the fused decode from device-resident state ->
        initiate an async counter copy -> THEN drain the previous slot's
        copy, which by now rode alongside a full slot of queued compute.

        No device read ever gates a dispatch — zero blocking host syncs per
        steady-state slot. The price is retirement lag: a request finishing
        in slot t is retired at the end of slot t+1 — or before slot t+1's
        admission when its transfer has already landed (the opportunistic
        early consume below, free because the read is non-blocking) — so
        its slot is reusable after at most two slots (call ``drain`` after
        the last slot to flush the tail).
        """
        self._now = now
        prev, self._pending_read = self._pending_read, None
        early = prev is not None and self._readback_ready(prev)
        served_prev, per_step_prev = (self._consume_read(prev) if early
                                      else (0, []))
        admitted = self.admit_pending(now, sync=True)
        n_active = sum(r is not None for r in self.active)
        if n_active:
            samp, sig = self._samp_args()
            with self.obs.trace.span("engine.dispatch", slot=now,
                                     pid=self.obs_pid, what="decode", n=n_steps):
                self.state, self.sync, served_steps = _decode_n_sync(
                    self.params, self.state, self.sync, samp,
                    n=n_steps, cfg=self.cfg, sig=sig,
                )
            self.decode_dispatches += 1
            self._post_readback(now, served_steps)
        if not early:
            served_prev, per_step_prev = self._consume_read(prev)
        self.served_history.append(served_prev)
        self.steps += n_steps
        return self._slot_stats(n_active, served_prev,
                                served_per_step=per_step_prev,
                                admitted=admitted)

    def drain(self) -> dict:
        """Flush the in-flight slot's readback (shutdown; blocks once)."""
        p, self._pending_read = self._pending_read, None
        served, per_step = self._consume_read(p, count_waits=False)
        return {"served": served, "served_per_step": per_step}

    # --------------------------------------- continuous batching (chunked)
    def _require_chunked(self) -> None:
        if not self._chunk_ok:
            raise ValueError(
                f"{self.cfg.name}: chunked prefill needs a dense-attention "
                "stack and no sliding window")

    def _validate_chunked(self, req: Request) -> None:
        if req.max_new_tokens > self._gen_cap:
            raise ValueError(
                f"request {req.rid}: max_new_tokens {req.max_new_tokens} "
                f"exceeds gen_buf_len {self._gen_cap}")

    def _admit_chunked(self, now: int) -> int:
        """Claim free rows for pending requests — pure host bookkeeping.

        No prefill dispatch happens here: the prompt is staged on the host
        and enters the cache chunk by chunk through the mixed dispatch, so
        admission cost no longer scales with prompt length. ``now`` is
        recorded as ``admit``-time only; ``start_slot`` stamps at the first
        chunk dispatch (actual service start).
        """
        P = self.ecfg.prompt_len
        k = 0
        with self.obs.trace.span("engine.admit", slot=now, pid=self.obs_pid):
            for row in self.free_slots():
                if not self.pending:
                    break
                self._validate_chunked(self.pending[0])  # raise before popping
                req = self.pending.pop(0)
                L = max(1, min(len(req.tokens), P))
                if len(req.tokens) > P:
                    req.truncated = True
                toks = np.asarray(req.tokens[:L], np.int32)
                if len(toks) < L:
                    toks = np.concatenate(
                        [toks, np.full(L - len(toks), PAD_ID, np.int32)])
                self.active[row] = req
                self.slot_age[row] = 0
                cached = self._claim_row(row, toks)
                self._cursors[row] = PrefillCursor(req=req, row=row, toks=toks,
                                                   cached=cached)
                self._emit_admission(req, row, now)
                k += 1
        return k

    def _claim_row(self, row: int, toks: np.ndarray) -> int:
        """Engine-specific setup when a chunked admission claims a row;
        returns the prompt tokens already resident (the paged engine's
        prefix-cache hit — the cursor starts past them)."""
        return 0

    def _on_activate(self, row: int, cur: PrefillCursor, now: int) -> None:
        """Hook: a row's final chunk just shipped, its first generated token
        is computed in this slot's dispatch (``_sync_activate``)."""
        cur.req.first_token_slot = now

    def _chunk_reserve(self, row: int, cur: PrefillCursor, take: int,
                       fin: bool, n_steps: int) -> bool:
        """Engine-specific capacity check for one scheduled chunk (the paged
        engine extends the row's block table here). False = defer the chunk
        to a later slot."""
        return True

    def _chunk_plan(self, n_steps: int) -> Optional[dict]:
        """Pick this slot's chunk rows under the per-slot token budget.

        Cursors are visited in admission (FIFO) order; each scheduled row
        advances up to ``chunk_size`` tokens, and the slot stops scheduling
        once ``chunk_budget`` prompt tokens are packed — the knob bounding
        how much prefill compute any single dispatch can add on top of
        decode. Chunks may be partial (budget or prompt tail), so any
        budget >= 1 makes progress.
        """
        if not self._cursors:
            return None
        B, C = len(self.active), self._chunk
        left = self.ecfg.chunk_budget or (B * C)
        toks = np.zeros((B, C), np.int32)
        pos0 = np.zeros(B, np.int32)
        valid = np.zeros(B, np.int32)
        reset = np.zeros(B, bool)
        final = np.zeros(B, bool)
        budgets = np.zeros(B, np.int32)
        base = np.zeros(B, np.int32)
        plan = []
        for row, cur in list(self._cursors.items()):
            if left <= 0:
                break
            take = min(C, cur.remaining, left)
            if take <= 0:
                continue
            fin = cur.off + take == len(cur.toks)
            if not self._chunk_reserve(row, cur, take, fin, n_steps):
                continue
            left -= take
            toks[row, :take] = cur.toks[cur.off:cur.off + take]
            pos0[row] = cur.off
            valid[row] = take
            reset[row] = cur.off == 0
            final[row] = fin
            budgets[row] = cur.req.max_new_tokens
            base[row] = cur.cached   # pool-resident prefix (staging split)
            plan.append((row, cur, take, fin))
        if not plan:
            return None
        return {"toks": toks, "pos0": pos0, "valid": valid, "reset": reset,
                "final": final, "budgets": budgets, "base": base,
                "plan": plan}

    def _finish_chunk_plan(self, plan: dict, now: int) -> None:
        """Advance cursors after the chunk dispatch. A row whose final chunk
        just shipped becomes live: its cursor drops (the readback consumer
        may retire it again) and its epoch bumps, so done-flag packets from
        pre-activation dispatches can never retire it (they carry the old
        epoch or meet the cursor guard)."""
        self.mixed_dispatches += 1
        self.chunk_rows += len(plan["plan"])
        tr = self.obs.trace
        for row, cur, take, fin in plan["plan"]:
            if not cur.started:
                cur.started = True   # off may start past 0 (cached prefix)
                cur.req.start_slot = now
            if tr.enabled:
                tr.emit("chunk", slot=now, rid=cur.req.rid, row=row,
                        pid=self.obs_pid, off=cur.off, take=take)
            cur.off += take
            if fin:
                del self._cursors[row]
                self._row_epoch[row] += 1
                self.slot_age[row] = 1
                self._on_activate(row, cur, now)
                if tr.enabled:
                    tr.emit("activation", slot=now, rid=cur.req.rid, row=row,
                            pid=self.obs_pid, cached=cur.cached)

    def step_slot_chunked(self, now: int, n_steps: int = 1) -> dict:
        """One continuous-batching control slot: admit (host bookkeeping
        only) -> ONE mixed dispatch interleaving per-row prompt chunks with
        the fused sync-free decode scan -> async counter readback.

        A slot costs exactly one dispatch regardless of prompt length, and a
        long prompt adds at most ``chunk_budget`` prefill tokens to any
        slot, so in-flight decodes are never stalled behind it — the
        head-of-line hazard the bucketed-admission paths pay. First-token
        sampling stays on device (``_sync_activate``); greedy streams are
        bit-identical to every legacy path.
        """
        self._require_chunked()
        self._now = now
        prev, self._pending_read = self._pending_read, None
        early = prev is not None and self._readback_ready(prev)
        served_prev, per_step_prev = (self._consume_read(prev) if early
                                      else (0, []))
        admitted = self._admit_chunked(now)
        tr, pid = self.obs.trace, self.obs_pid
        with tr.span("engine.plan", slot=now, pid=pid):
            plan = self._chunk_plan(n_steps)
        n_active = sum(r is not None for r in self.active)
        if plan is not None:
            with tr.span("engine.upload", slot=now, pid=pid):
                samp, sig = self._samp_args()
                chunk = [jnp.asarray(plan[k]) for k in _CHUNK_ARGS_DENSE]
            with tr.span("engine.dispatch", slot=now, pid=pid, what="mixed",
                         n=n_steps, chunk_rows=len(plan["plan"])):
                self.state, self.sync, served_steps = _chunk_decode_sync(
                    self.params, self.state, self.sync, *chunk,
                    samp, n=n_steps, cfg=self.cfg, sig=sig,
                )
            self.decode_dispatches += 1
            self._finish_chunk_plan(plan, now)
            self._post_readback(now, served_steps)
        elif n_active:
            with tr.span("engine.upload", slot=now, pid=pid):
                samp, sig = self._samp_args()
            with tr.span("engine.dispatch", slot=now, pid=pid, what="decode",
                         n=n_steps):
                self.state, self.sync, served_steps = _decode_n_sync(
                    self.params, self.state, self.sync, samp,
                    n=n_steps, cfg=self.cfg, sig=sig,
                )
            self.decode_dispatches += 1
            self._post_readback(now, served_steps)
        if not early:
            served_prev, per_step_prev = self._consume_read(prev)
        self.served_history.append(served_prev)
        self.steps += n_steps
        return self._slot_stats(n_active, served_prev,
                                served_per_step=per_step_prev,
                                admitted=admitted)


class PagedEngine(Engine):
    """Continuous batching over a paged KV cache (see DESIGN.md §6).

    Where ``Engine`` reserves a dense ``batch_slots x cache_len`` cache row
    per request, this engine admits a request by *allocating pages* from one
    shared pool (``repro.cache.PageAllocator``): a short request holds only
    the pages it writes, so at equal KV memory many more requests are in
    flight. Requests grow by appending pages — past ``cache_len`` if
    ``max_pages_per_req`` allows — and retirement returns pages to the free
    list. With ragged admission a short prompt also allocates only
    ceil(len / page_size) prompt pages instead of the full bucket.

    The dense engine's dispatch budget is preserved: one control slot costs
    <= 1 bucketed batch prefill (all admissions of the slot) + 1 fused
    lax.scan decode over all ``max_active`` rows. Page-table maintenance is
    host-side arithmetic; block tables/positions ride into the dispatch as
    arguments. Before each decode the engine pre-extends every active
    request to cover the slot's ``n_steps`` writes; if the pool cannot
    cover a request it is preempted (pages freed, request re-queued for a
    fresh prefill — deterministic under greedy decoding).

    Generation is bit-identical to the dense engine per request (greedy):
    every per-row op matches the dense path, so tokens are a pure function
    of the prompt. ``occupancy()`` exposes the page pool's fill fraction —
    the signal the ``MemoryAware`` policy prices. The sync-free protocol
    (``step_slot_sync``) mirrors the dense engine's, with the decode
    dispatch additionally carrying block tables/positions and retirement
    freeing pages one slot late.
    """

    def __init__(self, cfg: ModelConfig, params, ecfg: PagedEngineConfig,
                 obs=None):
        if not paged_segments_supported(cfg):
            raise ValueError(f"{cfg.name}: paged decode needs an all-attention stack")
        if ecfg.shape_window is not None:
            raise ValueError("paged decode does not support sliding windows")
        ps, P, R = ecfg.page_size, ecfg.prompt_len, ecfg.max_active
        if P % ps:
            raise ValueError(f"prompt_len {P} must be a multiple of page_size {ps}")
        if ecfg.kv_precision:
            cfg = cfg.replace(kv_precision=ecfg.kv_precision)
        self.kvp = resolve_kv_precision(cfg.kv_precision, cfg.cache_dtype)
        # under a *quantized* precision prefill runs with native storage (a
        # dense int8 cache cannot hold native rows) and the page splice
        # quantizes per destination region; casts keep the legacy prefill so
        # those paths stay bit-identical to the pre-KVPrecision engine
        self._prefill_cfg = (cfg.replace(kv_precision="native", cache_dtype="")
                             if self.kvp.is_quantized else cfg)
        self.cfg, self.params, self.ecfg = cfg, params, ecfg
        self.obs = obs or OBS_OFF
        self.obs_pid = 0
        self.device = None
        self._now = 0
        self.MP = ecfg.max_pages_per_req or max(ecfg.cache_len // ps, P // ps + 1)
        self._sig = _DecodeSig.of(ecfg)
        self._init_sampling(ecfg)
        self._ragged = ecfg.ragged_prefill and ragged_prefill_supported(cfg)
        self._buckets = _prompt_buckets(P, quantum=ps)
        self._gen_cap = ecfg.gen_buf_len or ecfg.cache_len

        self._cursors = {}
        base_chunk = ecfg.chunk_size or max(P // 4, 1)
        self._chunk = -(-base_chunk // ps) * ps if not ecfg.chunk_size else base_chunk
        self._chunk_ok = chunked_prefill_supported(cfg)

        # two-region pool geometry (DESIGN.md §14): quant_pages physical ids
        # at the top of the pool store K/V quantized; -1 auto-derives from
        # the resolved precision (all-or-nothing)
        qp = ecfg.quant_pages
        if qp < 0:
            qp = ecfg.num_pages if self.kvp.is_quantized else 0
        if qp and not self.kvp.is_quantized:
            raise ValueError(
                f"quant_pages={qp} needs a quantized kv_precision, got "
                f"{self.kvp.tag!r}")
        staged = self._chunk_ok and self.kvp.lossy and self.kvp.staging == "auto"
        self.pools = paged_pools_init(
            cfg, ecfg.num_pages, ps, native_pages=ecfg.num_pages - qp,
            stage_rows=R if staged else 0, stage_len=P)
        self.allocator = PageAllocator(
            ecfg.num_pages, ps, quant_pages=qp,
            quant_precision=self.kvp.tag if qp else "int8")
        # the region new admissions draw from — the PrecisionAware policy's
        # actuator (serve loop writes it between slots; every change is
        # recorded in the DecisionLog before it takes effect)
        self.admit_precision = "native" if qp < ecfg.num_pages else self.kvp.tag
        # prefix sharing: the radix index over resident prompt pages, plus
        # the per-slot COW fork plan (row -> (src, dst); flushed as one
        # device dispatch before the slot's mixed dispatch)
        self._prefix = PrefixIndex(self.allocator) if ecfg.prefix_sharing else None
        self._fork_plan: dict[int, tuple[int, int]] = {}
        self.prefix_hits = 0          # prompt tokens served from the cache
        self.prefix_forks = 0         # COW forks of partially-matched pages
        self.fork_dispatches = 0
        self.mixed_dispatches = 0
        self.chunk_rows = 0
        self.block_tables = np.full((R, self.MP), -1, np.int32)
        self.pos = np.zeros(R, np.int32)
        self.sync = sync_state_init(R, self._gen_cap)
        self.active = [None] * R
        self.pending = []
        self.finished = []
        self._exported = 0
        self.slot_age = np.zeros(R, np.int32)
        self.steps = 0
        self.served_history = []
        self.prefill_dispatches = 0
        self.decode_dispatches = 0
        self.blocking_syncs = 0
        self.readback_waits = 0
        self._pending_read = None
        self._chaos = None            # fault-injection seam (reliability)
        self._row_epoch = np.zeros(R, np.int64)
        self.alloc_failures = 0       # admissions deferred: pool exhausted
        self.preemptions = 0          # active requests bounced for pages
        self.peak_active = 0
        self.eviction_raced_hits = 0  # prefix hits degraded by racing evictions
        # high-water occupancy of the last control slot (post-admission,
        # pre-retirement) — the commitment peak the controller must price;
        # end-of-slot occupancy dips as finished requests free pages.
        self.occupancy_hwm = 0.0

    # ----------------------------------------------------- observability
    def counters(self) -> dict:
        c = super().counters()
        st = self.allocator.stats()
        c.update(
            occupancy=self.allocator.occupancy(),
            committed_occupancy=self.allocator.committed_occupancy(),
            pages_used=st.used_pages,
            pages_free=st.free_pages,
            pages_shared=st.shared_pages,
            pages_pinned=st.pinned_pages,
            frag_tokens=st.frag_tokens,
            peak_pages=st.peak_used_pages,
            pages_quant=st.quant_pages,
            pages_quant_used=st.quant_used_pages,
            quant_occupancy=st.quant_occupancy,
        )
        if self._prefix is not None:
            c.update(prefix_inserted_pages=self._prefix.inserted_pages,
                     prefix_evicted_pages=self._prefix.evicted_pages)
        return c

    def _slot_stats(self, n_active: int, served: int, **extra) -> dict:
        d = super()._slot_stats(n_active, served, **extra)
        d["occupancy"] = self.occupancy()
        return d

    def place(self, device) -> None:
        self.device = device
        self.params, self.pools, self.sync = jax.device_put(
            (self.params, self.pools, self.sync), device)

    # ------------------------------------------------------------------
    def occupancy(self) -> float:
        # with prefix sharing the controller prices *committed* occupancy:
        # pin-only cached pages are reclaimable on demand, so charging them
        # would make MemoryAware throttle admission below the pool's true
        # marginal cost (identical to raw occupancy with sharing off)
        if self._prefix is None:
            return self.allocator.occupancy()
        return self.allocator.committed_occupancy()

    def quant_occupancy(self) -> float:
        """In-use fraction of the quantized page region — the signal the
        PrecisionAware policy prices (0.0 without a quantized region)."""
        return self.allocator.quant_occupancy()

    def prefix_hit_tokens(self, tokens) -> int:
        """Prompt tokens of ``tokens`` resident in this engine's prefix
        cache at the *current admission precision* — the router's affinity
        probe (LRU state untouched)."""
        if self._prefix is None:
            return 0
        L = max(1, min(len(tokens), self.ecfg.prompt_len))
        return min(self._prefix.peek_tokens(np.asarray(tokens[:L], np.int32),
                                            precision=self.admit_precision),
                   L - 1)

    # ------------------------------------------- page acquisition helpers
    def _evict_short(self, short: int) -> bool:
        """Reclaim ``short`` pages from the prefix index's LRU tail."""
        return (self._prefix is not None and short > 0
                and self._prefix.evict(short) >= short)

    def _alloc_pages(self, row: int, tokens: int, shared=(),
                     precision: str = "native") -> tuple[Optional[list], list]:
        """Allocator alloc with eviction retry. Returns (block table or
        None, the shared pages actually acquired) — after a deep eviction a
        shared page may itself have been reclaimed, in which case sharing
        is abandoned for this request (a hit is an optimization, never a
        correctness dependency). ``precision`` names the region novel pages
        come from; the shortfall math counts only that region's free list
        (eviction can still free the other region's pages — harmless)."""
        shared = list(shared)
        pages = self.allocator.alloc(row, tokens, shared=shared,
                                     precision=precision)
        if pages is not None or self._prefix is None:
            return pages, shared
        short = (pages_for(tokens, self.ecfg.page_size) - len(shared)
                 - self.allocator.free_pages_for(precision))
        if not self._evict_short(short):
            return None, shared
        if any(self.allocator.refcount(p) <= 0 for p in shared):
            self._raced_hit(row, "shared-page-evicted")
            shared = []
        return self.allocator.alloc(row, tokens, shared=shared,
                                    precision=precision), shared

    def _extend_pages(self, row: int, tokens: int) -> Optional[list]:
        """Allocator extend with eviction retry (decode growth and chunk
        reservations reclaim cold cached prefixes before giving up). Growth
        stays inside the row's own precision region."""
        pages = self.allocator.extend(row, tokens)
        if pages is None and self._prefix is not None:
            prec = self.allocator.precision_of(row)
            short = (pages_for(tokens, self.ecfg.page_size)
                     - len(self.allocator.block_table(row))
                     - self.allocator.free_pages_for(prec))
            if self._evict_short(short):
                pages = self.allocator.extend(row, tokens)
        return pages

    def _flush_forks(self) -> None:
        """Dispatch every staged COW page copy in ONE fixed-shape op (pad
        rows carry an out-of-range dst and are dropped). Runs before the
        slot's mixed dispatch, so forked rows are resident before any chunk
        writes or reads touch the private copy."""
        if not self._fork_plan:
            return
        R, N = self.ecfg.max_active, self.ecfg.num_pages
        tr, now, pid = self.obs.trace, self._now, self.obs_pid
        with tr.span("engine.pages", slot=now, pid=pid, what="fork"):
            src = np.zeros(R, np.int32)
            dst = np.full(R, N, np.int32)
            for j, (s, d) in enumerate(self._fork_plan.values()):
                src[j], dst[j] = s, d
            self._fork_plan.clear()
            with tr.span("engine.dispatch", slot=now, pid=pid, what="fork",
                         rows=j + 1):
                self.pools = _fork_pages(self.pools, jnp.asarray(src),
                                         jnp.asarray(dst))
        self.fork_dispatches += 1

    def step(self, now: int) -> dict:
        raise NotImplementedError("the paged engine has no legacy per-step path")

    def _admit_one(self, req: Request, slot: int, now: int) -> None:
        raise NotImplementedError("the paged engine admits via admit_pending")

    def _retire(self, row: int, now: int) -> None:
        req = self.active[row]
        req.finish_slot = now
        self.finished.append(req)
        self.active[row] = None
        self._release_row(row)
        self._emit_retire(req, row, now)

    def _release_row(self, row: int) -> None:
        self.allocator.free(row)   # refcounted: shared prefix pages survive
        self._fork_plan.pop(row, None)
        self.block_tables[row] = -1
        self.pos[row] = 0
        self.slot_age[row] = 0

    def _preempt(self, row: int) -> None:
        """Bounce an active request back to pending (pages exhausted).

        Its pages return to the pool and its generation restarts from a
        fresh prefill on re-admission — identical tokens under greedy.
        """
        req = self.active[row]
        self._release_row(row)
        self.active[row] = None
        req.unadmit()
        self.pending.insert(0, req)
        self.preemptions += 1
        tr = self.obs.trace
        if tr.enabled:
            tr.emit("preemption", slot=self._now, rid=req.rid, row=row,
                    pid=self.obs_pid, what="decode")

    def admit_pending(self, now: int, lookahead: int = 1, sync: bool = False) -> int:
        """Fill free rows from the pending queue with ONE bucketed prefill.

        Admission = page allocation: a request enters only if the pool can
        cover its prompt plus this slot's ``lookahead`` decode writes (the
        slot's page demand is known, so pre-paying it here means admission
        never immediately preempts; growth beyond the slot still comes page
        by page). Ragged admission pays only for the *real* prompt length.
        All k admissions share one batch-R prefill + one scatter per
        segment; pad rows carry out-of-range page ids and are dropped.
        """
        R, P, ps = self.ecfg.max_active, self.ecfg.prompt_len, self.ecfg.page_size
        take: list = []
        for row in self.free_slots():
            if not self.pending:
                break
            req = self.pending[0]
            if req.max_new_tokens > self.MP * ps - P + 1:
                raise ValueError(
                    f"request {req.rid}: max_new_tokens {req.max_new_tokens} "
                    f"exceeds the block table ({self.MP} pages x {ps})"
                )
            if sync and req.max_new_tokens > self._gen_cap:
                raise ValueError(
                    f"request {req.rid}: max_new_tokens {req.max_new_tokens} "
                    f"exceeds gen_buf_len {self._gen_cap}")
            self._validate_sampled(req)
            L = max(1, min(len(req.tokens), P)) if self._ragged else P
            # prefix sharing: resident full pages cover the prompt head; cap
            # at (L-1)//ps so the final prompt token always recomputes (its
            # logits activate the row) and no decode write ever lands in a
            # shared page. This path shares at page granularity only — the
            # whole prompt prefills anyway (one bucketed dispatch), so the
            # win here is pool capacity, not FLOPs; token-granular skipping
            # (and COW forks) lives on the chunked path.
            shared: list = []
            if self._prefix is not None:
                hit = self._prefix.lookup(np.asarray(req.tokens[:L], np.int32),
                                          precision=self.admit_precision)
                shared = hit.pages[: (L - 1) // ps]
            # pages are keyed by engine row, not req.rid: a row uniquely owns
            # its request while active, whereas rids are only unique per
            # RequestSource (two sources feeding one engine may collide)
            pages, shared = self._alloc_pages(
                row, min(L + lookahead, self.MP * ps), shared=shared,
                precision=self.admit_precision)
            if pages is None:
                self.alloc_failures += 1
                break
            self.prefix_hits += len(shared) * ps
            self.pending.pop(0)
            take.append((row, req, pages, L, len(shared)))
        if not take:
            return 0
        bucket = self._pick_bucket(max(L for *_, L, _ns in take)) if self._ragged else P
        npp = bucket // ps
        toks = np.zeros((R, bucket), np.int32)
        lens = np.full(R, bucket, np.int32)
        page_idx = np.full((R, npp), self.ecfg.num_pages, np.int32)  # pad: drop
        for j, (_row, req, pages, L, n_shared) in enumerate(take):
            toks[j] = self._bucket(req.tokens, req, bucket)
            lens[j] = L
            pg = pages[:npp]
            page_idx[j, : len(pg)] = pg
            # shared pages already hold these blocks' K/V (bit-identical by
            # the purity invariant) — point them at the drop sentinel so the
            # splice never writes into a page other requests are reading
            page_idx[j, : n_shared] = self.ecfg.num_pages
        # cache_len == bucket: the dense prefill cache is exactly the prompt
        # rows, ready to scatter into pages (no ring wraparound).
        with self.obs.trace.span("engine.dispatch", slot=now, pid=self.obs_pid,
                                 what="prefill", rows=len(take)):
            logits, state = self._run_prefill(
                {"tokens": jnp.asarray(toks)}, lens, bucket)
            self.pools = _paged_splice(self.pools, state.caches,
                                       jnp.asarray(page_idx))
        self.prefill_dispatches += 1
        admit_reqs = [req for _row, req, _pages, _L, _ns in take]
        if sync:
            rows_arr = np.full(R, R, np.int32)
            budgets = np.zeros(R, np.int32)
            for j, (row, req, _pages, _L, _ns) in enumerate(take):
                rows_arr[j] = row
                budgets[j] = req.max_new_tokens
            samp, sig = self._admit_samp_args(admit_reqs, R)
            self.sync = _sync_admit(self.sync, logits, jnp.asarray(rows_arr),
                                    jnp.asarray(budgets), samp, sig=sig)
            first = [None] * len(take)
        else:
            self.blocking_syncs += 1
            samp, sig = self._admit_samp_args(admit_reqs, R)
            if sig.sampling:
                first = np.asarray(_sample_first(
                    logits, samp, jnp.zeros(R, jnp.int32)))[: len(take)]
            else:
                first = np.asarray(jnp.argmax(logits[: len(take)], axis=-1))
        for j, (row, req, pages, L, _ns) in enumerate(take):
            req.start_slot = now
            req.first_token_slot = now
            req.generated = None if sync else [int(first[j])]
            self._emit_admission(req, row, now)
            self.active[row] = req
            self.block_tables[row, : len(pages)] = pages
            self.pos[row] = L
            self.slot_age[row] = 1   # first token came from prefill
            if self._prefix is not None:
                # register this prompt's fully-written full pages (shared
                # ones are already indexed — insert walks past them), under
                # the precision they were written at
                self._prefix.insert(np.asarray(req.tokens[:L], np.int32),
                                    pages[: L // ps],
                                    precision=self.allocator.precision_of(row))
            if sync:
                self._row_epoch[row] += 1
        self.peak_active = max(self.peak_active, sum(r is not None for r in self.active))
        return len(take)

    def _ensure_pages(self, n_steps: int, sync: bool = False) -> None:
        """Pre-extend every active row to cover this slot's decode writes.

        The fused scan writes rows pos..pos+n_steps-1 for every active row
        (finished-mid-scan rows keep writing, masked — the dense trade), so
        pages must exist up front; growing here keeps the decode dispatch
        free of host round-trips. Rows the pool cannot cover are preempted
        (and, under the sync-free protocol, deactivated on device with one
        scatter). Mid-chunked-prefill rows are skipped — their page demand
        is reserved chunk by chunk in ``_chunk_reserve``."""
        ps = self.ecfg.page_size
        cleared = []
        with self.obs.trace.span("engine.pages", slot=self._now,
                                 pid=self.obs_pid):
            for row, req in enumerate(self.active):
                if req is None or row in self._cursors:
                    continue
                need = min(int(self.pos[row]) + n_steps, self.MP * ps)
                pages = self._extend_pages(row, need)
                if pages is None:
                    self._preempt(row)
                    cleared.append(row)
                    continue
                self.block_tables[row, : len(pages)] = pages
            if sync and cleared:
                R = self.ecfg.max_active
                rows_arr = np.full(R, R, np.int32)
                rows_arr[: len(cleared)] = cleared
                self.sync = _sync_clear(self.sync, jnp.asarray(rows_arr))

    def step_slot(self, now: int, n_steps: int = 1) -> dict:
        """One control slot: batched admit -> page extension -> scan decode
        -> retire (pages freed). <= 1 prefill + 1 decode dispatch."""
        self._now = now
        admitted = self.admit_pending(now, lookahead=n_steps)
        self._ensure_pages(n_steps)
        self.occupancy_hwm = self.occupancy()
        n_active = sum(r is not None for r in self.active)
        per_step = [0] * n_steps
        if n_active:
            toks = jnp.asarray(
                [r.generated[-1] if r else 0 for r in self.active], jnp.int32
            )
            state = M.PagedDecodeState(
                pools=self.pools,
                block_tables=jnp.asarray(self.block_tables),
                pos=jnp.asarray(self.pos),
                last_tok=toks,
            )
            samp, ages, hist, sig = self._samp_decode_args()
            with self.obs.trace.span("engine.dispatch", slot=now,
                                     pid=self.obs_pid, what="decode", n=n_steps):
                all_toks, state = _decode_n_paged(
                    self.params, state, toks, samp, ages, hist,
                    n=n_steps, cfg=self.cfg, sig=sig,
                )
            self.pools = state.pools
            self.decode_dispatches += 1
            self.blocking_syncs += 1
            all_toks = np.asarray(all_toks)  # (n_steps, R)
            for row, req in enumerate(self.active):
                if req is None:
                    continue
                self.pos[row] += n_steps     # the scan wrote n_steps rows
                take, hit = _host_take(all_toks[:, row], req,
                                       int(self.slot_age[row]), n_steps,
                                       self.ecfg.eos_id)
                req.generated.extend(int(x) for x in all_toks[:take, row])
                self.slot_age[row] += take
                if hit or self.slot_age[row] >= req.max_new_tokens:
                    per_step[max(take - 1, 0)] += 1
                    self._retire(row, now)
        served = sum(per_step)
        self.served_history.append(served)
        self.steps += n_steps
        return self._slot_stats(n_active, served, served_per_step=per_step,
                                admitted=admitted)

    def step_slot_sync(self, now: int, n_steps: int = 1) -> dict:
        """Sync-free control slot over the paged pool: admit (pages + device
        first token) -> extend pages -> dispatch -> initiate readback ->
        drain the previous slot's readback. Page-table maintenance is pure
        host arithmetic: an active row's position advances exactly n_steps
        per dispatch (rows that finished on device froze instead, but those
        retire at the next drain — their host mirror transiently
        over-covers by <= n_steps rows, i.e. at most one page, returned
        when the row frees). The decode dispatch never waits on the device.
        """
        self._now = now
        prev, self._pending_read = self._pending_read, None
        early = prev is not None and self._readback_ready(prev)
        served_prev, per_step_prev = (self._consume_read(prev) if early
                                      else (0, []))
        admitted = self.admit_pending(now, lookahead=n_steps, sync=True)
        self._ensure_pages(n_steps, sync=True)
        self.occupancy_hwm = self.occupancy()
        n_active = sum(r is not None for r in self.active)
        if n_active:
            # .copy(): jnp.asarray may alias the numpy buffer (CPU zero-copy)
            # and this path never blocks — the host mutates pos/block_tables
            # before the async decode is guaranteed to have read them.
            # last_tok is dead on entry (the scan decodes from sync.cur_tok);
            # a fresh zeros buffer keeps the donated state free of aliases
            # into the non-donated SyncState.
            state = M.PagedDecodeState(
                pools=self.pools,
                block_tables=jnp.asarray(self.block_tables.copy()),
                pos=jnp.asarray(self.pos.copy()),
                last_tok=jnp.zeros_like(self.sync.cur_tok),
            )
            samp, sig = self._samp_args()
            with self.obs.trace.span("engine.dispatch", slot=now,
                                     pid=self.obs_pid, what="decode", n=n_steps):
                state, self.sync, served_steps = _decode_n_sync_paged(
                    self.params, state, self.sync, samp,
                    n=n_steps, cfg=self.cfg, sig=sig,
                )
            self.pools = state.pools
            self.decode_dispatches += 1
            for row, req in enumerate(self.active):
                if req is not None:
                    self.pos[row] += n_steps
            self._post_readback(now, served_steps)
        if not early:
            served_prev, per_step_prev = self._consume_read(prev)
        self.served_history.append(served_prev)
        self.steps += n_steps
        return self._slot_stats(n_active, served_prev,
                                served_per_step=per_step_prev,
                                admitted=admitted)

    # --------------------------------------- continuous batching (chunked)
    def _validate_chunked(self, req: Request) -> None:
        ps, P = self.ecfg.page_size, self.ecfg.prompt_len
        if req.max_new_tokens > self.MP * ps - P + 1:
            raise ValueError(
                f"request {req.rid}: max_new_tokens {req.max_new_tokens} "
                f"exceeds the block table ({self.MP} pages x {ps})"
            )
        # A prompt that cannot fit the WHOLE pool (plus its first decode
        # write) can never activate: refusing it at admission beats the
        # silent stall the per-chunk reservation would otherwise spin in.
        from repro.cache.paged import pages_for

        L = max(1, min(len(req.tokens), P))
        if pages_for(L + 1, ps) > self.ecfg.num_pages:
            raise ValueError(
                f"request {req.rid}: prompt of {L} tokens needs "
                f"{pages_for(L + 1, ps)} pages; the pool holds "
                f"{self.ecfg.num_pages}")
        super()._validate_chunked(req)

    def _claim_row(self, row: int, toks: np.ndarray) -> int:
        """Claim a row for chunked prefill; with prefix sharing, acquire the
        prompt's resident prefix so the cursor starts past it.

        Full resident pages are shared outright (one extra refcount each).
        When the radix walk additionally matches a *partial* block — the
        next resident page agrees on its first ``fork_len`` tokens — that
        page is copy-on-write forked: a private copy joins this row's table,
        the device copy is staged for ``_flush_forks``, and only the
        divergent tail of the block recomputes. The hit is capped at L-1
        tokens so the final prompt token always recomputes (its logits
        activate the row).
        """
        prec = self.admit_precision
        if self._prefix is None:
            # register an empty block table (remembers the row's precision)
            self.allocator.alloc(row, 0, precision=prec)
            return 0
        ps, L = self.ecfg.page_size, len(toks)
        hit = self._prefix.lookup(np.asarray(toks, np.int32), precision=prec)
        want = hit.pages[: (L - 1) // ps]
        fork_len = 0
        if hit.fork_src is not None and len(want) == len(hit.pages):
            fork_len = max(0, min(hit.fork_len, L - 1 - len(want) * ps))
        pages, shared = self._alloc_pages(row, len(want) * ps + fork_len,
                                          shared=want, precision=prec)
        if pages is None:
            self.allocator.alloc(row, 0, precision=prec)  # cold start
            return 0
        if len(shared) < len(want):
            cached = len(shared) * ps      # deep eviction ate part of the hit
        else:
            cached = len(want) * ps
            # the fork source is pin-only (refcount 1) and could have been
            # reclaimed by this very allocation's eviction retry — fork only
            # if its pin survives (a still-pinned page is still the node's)
            if fork_len > 0:
                if self.allocator.pages[hit.fork_src].pinned:
                    self._fork_plan[row] = (hit.fork_src, pages[-1])
                    self.prefix_forks += 1
                    cached += fork_len
                else:
                    self._raced_hit(row, "fork-source-evicted")
        self.block_tables[row, : len(pages)] = pages
        self.pos[row] = cached   # chunk writes resume past the resident rows
        self.prefix_hits += cached
        return cached

    def _on_activate(self, row: int, cur: PrefillCursor, now: int) -> None:
        super()._on_activate(row, cur, now)
        if self._prefix is not None:
            # every prompt row is now written — index the full pages (the
            # forked boundary page qualifies: its pre-fork rows are
            # bit-identical to a recompute by the purity invariant)
            L = len(cur.toks)
            pages = self.allocator.block_table(row)
            self._prefix.insert(np.asarray(cur.toks, np.int32),
                                pages[: L // self.ecfg.page_size],
                                precision=self.allocator.precision_of(row))

    def _chunk_reserve(self, row: int, cur: PrefillCursor, take: int,
                       fin: bool, n_steps: int) -> bool:
        """Chunk admission = page allocation: the chunk enters only if the
        pool covers its writes (plus the slot's decode lookahead when it is
        the activating chunk). A refused chunk just waits — pages free as
        decodes retire."""
        ps = self.ecfg.page_size
        need = min(cur.off + take + (n_steps if fin else 0), self.MP * ps)
        pages = self._extend_pages(row, need)
        if pages is None:
            self.alloc_failures += 1
            return False
        self.block_tables[row, :len(pages)] = pages
        return True

    def _preempt_cursor(self, row: int) -> None:
        """Bounce a mid-prefill request back to pending (pool starved by
        concurrent prefills). Its pages return to the pool and its prompt
        restarts from chunk 0 on re-admission — identical tokens under
        greedy, exactly like decode preemption."""
        cur = self._cursors.pop(row)
        req = self.active[row]
        self._release_row(row)
        self.active[row] = None
        req.unadmit()
        self.pending.insert(0, req)
        self.preemptions += 1
        tr = self.obs.trace
        if tr.enabled:
            tr.emit("preemption", slot=self._now, rid=req.rid, row=row,
                    pid=self.obs_pid, what="prefill", off=cur.off)

    def step_slot_chunked(self, now: int, n_steps: int = 1) -> dict:
        """Continuous batching over the paged pool: one mixed dispatch per
        slot carrying per-row prompt chunks (written through block tables)
        plus the fused sync-free decode scan. Chunk page demand is reserved
        at schedule time; decode rows pre-extend as in the sync-free path.
        """
        self._require_chunked()
        self._now = now
        prev, self._pending_read = self._pending_read, None
        early = prev is not None and self._readback_ready(prev)
        served_prev, per_step_prev = (self._consume_read(prev) if early
                                      else (0, []))
        admitted = self._admit_chunked(now)
        self._flush_forks()   # COW copies land before this slot's chunks
        self.peak_active = max(self.peak_active,
                               sum(r is not None for r in self.active))
        tr, pid = self.obs.trace, self.obs_pid
        with tr.span("engine.plan", slot=now, pid=pid):
            plan = self._chunk_plan(n_steps)
            if plan is None and len(self._cursors) > 1 and all(
                    r is None or row in self._cursors
                    for row, r in enumerate(self.active)):
                # every occupied row is a stalled prefill: no decode will
                # ever retire and free pages — bounce the youngest prefill
                # so the oldest can finish (re-prefilled later;
                # greedy-identical)
                self._preempt_cursor(next(reversed(self._cursors)))
                plan = self._chunk_plan(n_steps)
        self._ensure_pages(n_steps, sync=True)
        self.occupancy_hwm = self.occupancy()
        n_active = sum(r is not None for r in self.active)
        decoding = any(r is not None and row not in self._cursors
                       for row, r in enumerate(self.active))
        if plan is not None or decoding:
            with tr.span("engine.upload", slot=now, pid=pid):
                # .copy(): see step_slot_sync — the non-blocking loop
                # mutates pos/block_tables before the async dispatch must
                # have read them
                state = M.PagedDecodeState(
                    pools=self.pools,
                    block_tables=jnp.asarray(self.block_tables.copy()),
                    pos=jnp.asarray(self.pos.copy()),
                    last_tok=jnp.zeros_like(self.sync.cur_tok),
                )
                samp, sig = self._samp_args()
                if plan is not None:
                    chunk = [jnp.asarray(plan[k]) for k in _CHUNK_ARGS_PAGED]
            with tr.span("engine.dispatch", slot=now, pid=pid,
                         what="mixed" if plan is not None else "decode",
                         n=n_steps,
                         chunk_rows=len(plan["plan"]) if plan else 0):
                if plan is not None:
                    state, self.sync, served_steps = _chunk_decode_sync_paged(
                        self.params, state, self.sync, *chunk,
                        samp, n=n_steps, cfg=self.cfg, sig=sig,
                    )
                else:
                    state, self.sync, served_steps = _decode_n_sync_paged(
                        self.params, state, self.sync, samp,
                        n=n_steps, cfg=self.cfg, sig=sig,
                    )
            self.pools = state.pools
            self.decode_dispatches += 1
            for row, req in enumerate(self.active):
                if req is not None and row not in self._cursors:
                    self.pos[row] += n_steps   # decode rows (host mirror)
            if plan is not None:
                for row, _cur, take, fin in plan["plan"]:
                    # chunk writes, plus the same-slot decode scan for rows
                    # the chunk activated (over-covers if done at activation
                    # — the documented <= n_steps trade)
                    self.pos[row] += take + (n_steps if fin else 0)
                self._finish_chunk_plan(plan, now)
            self._post_readback(now, served_steps)
        if not early:
            served_prev, per_step_prev = self._consume_read(prev)
        self.served_history.append(served_prev)
        self.steps += n_steps
        return self._slot_stats(n_active, served_prev,
                                served_per_step=per_step_prev,
                                admitted=admitted)


def _slice_extra(extra: dict, b: int) -> dict:
    return {k: v[:b] for k, v in extra.items()}
