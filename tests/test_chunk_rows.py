"""The mixed step's chunk pass runs only over the rows that carry a chunk.

``T.chunk_hidden(_paged)`` loop, inside each layer, over the rows with
``valid > 0`` (a device-valued trip count) instead of running the whole
``[B, C]`` block. The oracle here is the padded pass they replaced (every
row through attention and FFN, ``valid == 0`` rows computing and being
thrown away), kept below verbatim: the row loop must give the same pool
pages, the same chunk-row logits and the same streams, on the dense and the
paged engine, native and int8, with prefix-cache rows and sampled requests;
and it must stay one compiled program whatever the number of chunk rows.
"""
import copy
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import attention as A
from repro.models import init_params
from repro.models import model as M
from repro.models import transformer as T
from repro.models.layers import rmsnorm
from repro.runtime import Engine, EngineConfig, PagedEngine, PagedEngineConfig
from repro.runtime import engine as E
from repro.runtime.request import Request
from repro.runtime.sampling import SamplingParams

KEY = jax.random.PRNGKey(5)
_CACHE = {}
B, C, PS, MP = 4, 8, 4, 8          # rows, chunk, page size, pages per row


def _setup():
    if "m" not in _CACHE:
        cfg = get_config("granite-3-2b", smoke=True)
        _CACHE["m"] = (cfg, init_params(KEY, cfg))
    return _CACHE["m"]


# ------------------------------------------------- the padded pass (oracle)
def _padded_chunk_hidden(stack, h, caches, pos0, valid, reset, cfg, *,
                         shape_window=None):
    new = []
    for seg, params, cache in zip(T.plan_segments(cfg), stack, caches,
                                  strict=True):
        def body(hh, pc):
            p, c = pc
            a, c = A.attn_chunk(p["attn"], rmsnorm(p["ln1"], hh, cfg.norm_eps),
                                c, pos0, valid, cfg, window=shape_window,
                                reset=reset)
            hh, _ = T._ffn(p, hh + a, cfg)
            return hh, c

        h, c = jax.lax.scan(body, h, (params, cache))
        new.append(c)
    return h, new


def _padded_chunk_hidden_paged(stack, h, pools, block_table, pos0, valid, cfg,
                               base=None):
    new = []
    for seg, params, pool in zip(T.plan_segments(cfg), stack, pools,
                                 strict=True):
        def body(hh, pp):
            p, pool_l = pp
            a, pool_l = A.attn_chunk_paged(
                p["attn"], rmsnorm(p["ln1"], hh, cfg.norm_eps), pool_l,
                block_table, pos0, valid, cfg, base=base)
            hh, _ = T._ffn(p, hh + a, cfg)
            return hh, pool_l

        h, pool = jax.lax.scan(body, h, (params, pool))
        new.append(pool)
    return h, new


def _clear_step_caches():
    for f in (E._chunk_decode_sync, E._chunk_decode_sync_paged):
        f.clear_cache()


@pytest.fixture
def padded(monkeypatch):
    """Call to swap the padded pass in (the jitted step programs are cleared
    on both sides, so no executable traced with one pass serves the other)."""
    def use():
        monkeypatch.setattr(T, "chunk_hidden", _padded_chunk_hidden)
        monkeypatch.setattr(T, "chunk_hidden_paged", _padded_chunk_hidden_paged)
        _clear_step_caches()
    yield use
    monkeypatch.undo()
    _clear_step_caches()


# ------------------------------------------------------- model-level cases
def _fill(tree, seed):
    """Random contents for every leaf: earlier chunks, prefix pages and
    staged rows for the chunk pass to attend."""
    leaves, treedef = jax.tree.flatten(tree)
    rng = np.random.default_rng(seed)
    out = []
    for x in leaves:
        if x.dtype == jnp.int8:
            y = rng.integers(-127, 128, x.shape)
        elif x.dtype == jnp.int32:        # dense slot_pos: slot j holds j
            y = np.broadcast_to(np.arange(x.shape[-1]), x.shape)
        else:
            y = rng.standard_normal(x.shape)
        out.append(jnp.asarray(y, x.dtype))
    return jax.tree.unflatten(treedef, out)


def _positive_scales(tree):
    if isinstance(tree, (A.PagedKVPool, A.KVCache)) and tree.k_scale is not None:
        return tree._replace(k_scale=jnp.abs(tree.k_scale) * 0.01 + 1e-3,
                             v_scale=jnp.abs(tree.v_scale) * 0.01 + 1e-3)
    return tree


# (row, pos0, valid, base) per chunk row; kv precision
CASES = {
    "one_row": ([(2, 0, 8, 0)], ""),
    "two_rows_apart": ([(3, 8, 5, 0), (0, 0, 8, 0)], ""),
    "all_rows": ([(1, 8, 8, 0), (0, 0, 8, 0), (3, 16, 8, 0), (2, 4, 3, 0)], ""),
    "int8_staged": ([(0, 8, 8, 0), (3, 0, 8, 0)], "int8"),
    "prefix_base_int8": ([(1, 12, 8, 12), (2, 0, 6, 0)], "int8"),
}


def _plan(case):
    pos0, valid, base = (np.zeros(B, np.int32) for _ in range(3))
    for r, p, v, b in case:
        pos0[r], valid[r], base[r] = p, v, b
    return jnp.asarray(pos0), jnp.asarray(valid), jnp.asarray(base)


def _paged_state(cfg, seed):
    pools = T.paged_pools_init(cfg, B * MP, PS, stage_rows=B,
                               stage_len=MP * PS)
    pools = [_positive_scales(p) for p in _fill(pools, seed)]
    table = np.random.default_rng(seed).permutation(B * MP).reshape(B, MP)
    return M.PagedDecodeState(pools=pools,
                              block_tables=jnp.asarray(table, jnp.int32),
                              pos=jnp.zeros(B, jnp.int32),
                              last_tok=jnp.zeros(B, jnp.int32))


def _dense_state(cfg, params, seed, kv_precision):
    eng = Engine(cfg, params, EngineConfig(
        batch_slots=B, prompt_len=MP * PS, cache_len=MP * PS, chunk_size=C,
        kv_precision=kv_precision))
    caches = [_positive_scales(c) for c in _fill(eng.state.caches, seed)]
    return eng.state._replace(caches=caches), eng.cfg


def _pages_of(table, rows):
    return np.unique(np.asarray(table)[list(rows)].ravel())


@pytest.mark.parametrize("kind,case", [
    (kind, case) for kind in ("paged", "dense") for case in CASES
    if kind == "paged" or "prefix" not in case])   # dense has no prefix cache
def test_row_loop_matches_padded_pass(kind, case, monkeypatch):
    cfg, params = _setup()
    plan, kvp = CASES[case]
    pos0, valid, base = _plan(plan)
    toks = jnp.asarray(np.random.default_rng(1).integers(0, 256, (B, C)),
                       jnp.int32)
    if kind == "paged":
        cfg = cfg.replace(kv_precision=kvp) if kvp else cfg
        state = _paged_state(cfg, seed=2)
        step = lambda: jax.jit(partial(M.chunk_step_paged, cfg=cfg))(
            params, state, toks, pos0, valid, base=base)
        leaves = lambda s: s.pools
    else:
        state, cfg = _dense_state(cfg, params, 2, kvp)
        reset = (pos0 == 0) & (valid > 0)   # the plan's first chunks
        step = lambda: jax.jit(partial(M.chunk_step, cfg=cfg))(
            params, state, toks, pos0, valid, reset)
        leaves = lambda s: s.caches
    logits, new = step()
    monkeypatch.setattr(T, "chunk_hidden", _padded_chunk_hidden)
    monkeypatch.setattr(T, "chunk_hidden_paged", _padded_chunk_hidden_paged)
    ref_logits, ref = step()

    on = [r for r, *_ in plan]
    np.testing.assert_array_equal(np.asarray(logits)[on],
                                  np.asarray(ref_logits)[on])
    for got, want in zip(jax.tree.leaves(leaves(new)),
                         jax.tree.leaves(leaves(ref)), strict=True):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(new.pos), np.asarray(ref.pos))
    np.testing.assert_array_equal(np.asarray(new.last_tok),
                                  np.asarray(ref.last_tok))

    # rows with valid == 0 write nothing: their pages, staging and cache
    # rows are what they were
    off = [r for r in range(B) if r not in on]
    for old, got in zip(leaves(state), leaves(new), strict=True):
        if kind == "paged":
            pages = _pages_of(state.block_tables, off)
            nn = old.native_pages
            native, quant = pages[pages < nn], pages[pages >= nn] - nn
            for leaf in ("k", "v", "qk", "qv", "k_scale", "v_scale"):
                a, b = getattr(old, leaf), getattr(got, leaf)
                if a is not None:
                    ids = native if leaf in ("k", "v") else quant
                    np.testing.assert_array_equal(np.asarray(a)[:, ids],
                                                  np.asarray(b)[:, ids])
            pairs = [(old.stage_k, got.stage_k), (old.stage_v, got.stage_v)]
        else:
            pairs = list(zip(old, got))
        for a, b in pairs:
            if a is not None:
                np.testing.assert_array_equal(np.asarray(a)[:, off],
                                              np.asarray(b)[:, off])


# ------------------------------------------------------ engine-level streams
SAMPLED = (SamplingParams(temperature=0.8, top_k=8, seed=7),
           SamplingParams(temperature=1.1, top_p=0.9, seed=8), None)


def _workload():
    """Ragged prompts in bursts, a shared opening for the prefix cache, and
    sampled requests beside greedy ones."""
    rng = np.random.default_rng(3)
    prefix = rng.integers(0, 256, 8, dtype=np.int32)
    sched = {}
    for rid in range(9):
        n = int(rng.integers(3, 17))
        toks = rng.integers(0, 256, n, dtype=np.int32)
        if rid % 3 == 0:
            toks[:min(n, 8)] = prefix[:min(n, 8)]
        sched.setdefault(rid // 3 * 2, []).append(Request(
            rid=rid, arrival_slot=rid // 3 * 2, tokens=toks,
            max_new_tokens=int(rng.integers(2, 7)),
            sampling=SAMPLED[rid % len(SAMPLED)]))
    return sched


def _serve(kind, kvp):
    cfg, params = _setup()
    if kind == "dense":
        eng = Engine(cfg, params, EngineConfig(
            batch_slots=4, prompt_len=16, cache_len=64, chunk_size=4,
            kv_precision=kvp))
    else:
        eng = PagedEngine(cfg, params, PagedEngineConfig(
            prompt_len=16, cache_len=64, page_size=4, num_pages=48,
            max_active=4, chunk_size=4, prefix_sharing=(kind == "shared"),
            kv_precision=kvp))
    sched = copy.deepcopy(_workload())
    t = 0
    while (len(eng.finished) < 9 or t <= max(sched)) and t < 200:
        eng.submit(sched.get(t, []))
        eng.step_slot_chunked(t, n_steps=2)
        t += 1
    eng.drain()
    assert len(eng.finished) == 9
    return {r.rid: tuple(r.generated) for r in eng.finished}, eng.counters()


@pytest.mark.parametrize("kind,kvp", [("dense", ""), ("paged", ""),
                                      ("shared", ""), ("shared", "int8")])
def test_served_streams_match_padded_pass(kind, kvp, padded):
    streams, c = _serve(kind, kvp)
    assert c["mixed_dispatches"] > 0
    assert c["mixed_dispatches"] < c["chunk_rows"] < B * c["mixed_dispatches"]
    padded()
    ref, _ = _serve(kind, kvp)
    assert streams == ref


# ---------------------------------------------------------- one program
@pytest.mark.parametrize("kind", ["paged", "dense"])
def test_mixed_program_traces_once_over_row_counts(kind):
    cfg, params = _setup()
    _clear_step_caches()
    if kind == "paged":
        eng = PagedEngine(cfg, params, PagedEngineConfig(
            prompt_len=16, cache_len=32, page_size=PS, num_pages=B * MP,
            max_active=B, chunk_size=C))
        fn, extra = E._chunk_decode_sync_paged, jnp.zeros(B, jnp.int32)

        def fresh():
            return M.PagedDecodeState(
                pools=jax.tree.map(jnp.copy, eng.pools),
                block_tables=jnp.asarray(eng.block_tables),
                pos=jnp.asarray(eng.pos), last_tok=jnp.zeros(B, jnp.int32))
    else:
        eng = Engine(cfg, params, EngineConfig(
            batch_slots=B, prompt_len=16, cache_len=32, chunk_size=C))
        fn, extra = E._chunk_decode_sync, jnp.ones(B, bool)

        def fresh():
            return jax.tree.map(jnp.copy, eng.state)
    before = E.trace_count()
    for k in range(1, B + 1):
        valid = np.zeros(B, np.int32)
        valid[np.arange(B)[::-1][:k]] = C
        fn(params, fresh(), eng.sync, jnp.zeros((B, C), jnp.int32),
           jnp.zeros(B, jnp.int32), jnp.asarray(valid), extra,
           jnp.zeros(B, bool), jnp.full(B, 4, jnp.int32), None,
           n=2, cfg=eng.cfg, sig=eng._sig)
    assert E.trace_count() - before == 1
    _clear_step_caches()


# -------------------------------------------------------------- counters
@pytest.mark.parametrize("kind", ["paged", "dense"])
def test_mixed_dispatch_counters_on_a_scripted_schedule(kind):
    """Prompts of 16, 8 and 24 tokens at slot 0 and one of 8 at slot 4,
    chunk 8: slots 0-2 carry 3, 2, 1 chunk rows, slots 3 decode only, slot
    4 carries 1 — four mixed dispatches, seven chunk rows."""
    cfg, params = _setup()
    if kind == "paged":
        eng = PagedEngine(cfg, params, PagedEngineConfig(
            prompt_len=24, cache_len=64, page_size=PS, num_pages=64,
            max_active=B, chunk_size=C))
    else:
        eng = Engine(cfg, params, EngineConfig(
            batch_slots=B, prompt_len=24, cache_len=64, chunk_size=C))
    reqs = {0: [Request(rid=i, arrival_slot=0, tokens=np.arange(n) % 200 + 1,
                        max_new_tokens=12) for i, n in enumerate((16, 8, 24))],
            4: [Request(rid=3, arrival_slot=4, tokens=np.arange(8) + 5,
                        max_new_tokens=2)]}
    seen = []
    for t in range(6):
        eng.submit(reqs.get(t, []))
        eng.step_slot_chunked(t, n_steps=2)
        c = eng.counters()
        seen.append((c["mixed_dispatches"], c["chunk_rows"]))
    assert seen == [(1, 3), (2, 5), (3, 6), (3, 6), (4, 7), (4, 7)]
    eng.drain()
