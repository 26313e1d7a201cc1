"""The paged step programs address the layer-stacked pools in place.

``T.decode_hidden_paged`` and ``T.chunk_hidden_paged`` carry each segment's
stacked ``PagedKVPool`` through the layer scan and write and gather every
leaf at its layer index. The oracle is the pass they replaced, kept below:
each layer's pool slab goes through the scan as ``xs`` and comes back as
``ys``, and the attention sees one layer's pool. Both step programs must
give the same pools, leaf for leaf, the same sync state and the same served
counts, on native, int8 and fp8 pools, with a native and a quantized region
side by side, staging, a prefix-cache base and an activation.
"""
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
from repro.runtime import engine as E

B, C, PS, MP, STEPS = 4, 8, 4, 8, 2     # rows, chunk, page size, pages a row
_CACHE = {}


def _setup():
    if "m" not in _CACHE:
        cfg = get_config("granite-3-2b", smoke=True)
        _CACHE["m"] = (cfg, init_params(jax.random.PRNGKey(11), cfg))
    return _CACHE["m"]


# ------------------------------------------- the slab-through-xs pass (oracle)
def _slab_decode_hidden_paged(stack, h, pools, block_table, pos, cfg):
    new = []
    for params, pool in zip(stack, pools, strict=True):
        def body(hh, pp):
            p, pool_l = pp
            a, pool_l = A.attn_decode_paged(
                p["attn"], rmsnorm(p["ln1"], hh, cfg.norm_eps), pool_l,
                block_table, pos, cfg)
            hh, _ = T._ffn(p, hh + a, cfg)
            return hh, pool_l

        h, pool = jax.lax.scan(body, h, (params, pool))
        new.append(pool)
    return h, new


def _slab_chunk_hidden_paged(stack, h, pools, block_table, pos0, valid, cfg,
                             base=None):
    new = []
    for params, pool in zip(stack, pools, strict=True):
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
    for f in (E._chunk_decode_sync_paged, E._decode_n_sync_paged):
        f.clear_cache()


# ------------------------------------------------------------------ inputs
def _state(cfg, native_pages, seed):
    """Random contents in every leaf (each layer's differ), a block table
    whose rows hold pages of both regions, rows 1 and 3 decoding."""
    pools = T.paged_pools_init(cfg, B * MP, PS, native_pages=native_pages,
                               stage_rows=B, stage_len=MP * PS)
    leaves, treedef = jax.tree.flatten(pools)
    rng = np.random.default_rng(seed)
    out = []
    for x in leaves:
        if x.dtype == jnp.int8:
            y = rng.integers(-127, 128, x.shape)
        elif x.dtype == jnp.float32 and x.ndim == 4:     # scales
            y = np.abs(rng.standard_normal(x.shape)) * 0.01 + 1e-3
        else:
            y = rng.standard_normal(x.shape)
        out.append(jnp.asarray(y, x.dtype))
    pools = jax.tree.unflatten(treedef, out)
    table = rng.permutation(B * MP).reshape(B, MP)
    return M.PagedDecodeState(
        pools=pools, block_tables=jnp.asarray(table, jnp.int32),
        pos=jnp.asarray([0, 13, 0, 21], jnp.int32),
        last_tok=jnp.zeros(B, jnp.int32))


def _sync():
    s = E.sync_state_init(B, MP * PS)
    on = jnp.asarray([False, True, False, True])
    return s._replace(cur_tok=jnp.asarray([0, 7, 0, 9], jnp.int32),
                      age=jnp.where(on, 1, 0), budget=jnp.where(on, 9, 0),
                      done=~on)


# row 0 chunks [8, 16) over an 8-token prefix-cache hit and activates; row 2
# chunks [0, 6); rows 1 and 3 decode
PLAN = dict(toks=jnp.asarray(np.random.default_rng(3).integers(0, 256, (B, C)),
                             jnp.int32),
            pos0=jnp.asarray([8, 0, 0, 0], jnp.int32),
            valid=jnp.asarray([8, 0, 6, 0], jnp.int32),
            base=jnp.asarray([8, 0, 0, 0], jnp.int32),
            final=jnp.asarray([True, False, False, False]),
            budgets=jnp.asarray([5, 0, 0, 0], jnp.int32))

# kv precision, native pages of the B * MP, pool leaves
POOLS = {"native": ("", None, 2), "int8": ("int8", 0, 6),
         "int8_mixed": ("int8", B * MP // 2, 8),
         "fp8_mixed": ("fp8", B * MP // 2, 8)}


def _run(program, cfg, params, native_pages):
    state, sync = _state(cfg, native_pages, seed=2), _sync()
    sig = E._DecodeSig()
    if program == "mixed":
        return E._chunk_decode_sync_paged(
            params, state, sync, PLAN["toks"], PLAN["pos0"], PLAN["valid"],
            PLAN["base"], PLAN["final"], PLAN["budgets"], None,
            n=STEPS, cfg=cfg, sig=sig)
    return E._decode_n_sync_paged(params, state, sync, None, n=STEPS,
                                  cfg=cfg, sig=sig)


@pytest.mark.parametrize("program", ["mixed", "decode"])
@pytest.mark.parametrize("pool", sorted(POOLS))
def test_step_matches_slab_scan(program, pool, monkeypatch):
    cfg, params = _setup()
    kvp, native_pages, n_leaves = POOLS[pool]
    cfg = cfg.replace(kv_precision=kvp)
    _clear_step_caches()
    got = _run(program, cfg, params, native_pages)
    monkeypatch.setattr(T, "decode_hidden_paged", _slab_decode_hidden_paged)
    monkeypatch.setattr(T, "chunk_hidden_paged", _slab_chunk_hidden_paged)
    _clear_step_caches()
    want = _run(program, cfg, params, native_pages)
    monkeypatch.undo()
    _clear_step_caches()

    assert len(jax.tree.leaves(got[0].pools)) == n_leaves
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # every layer was written, so equal leaves are not untouched copies
    before = jax.tree.leaves(_state(cfg, native_pages, seed=2).pools)
    after = jax.tree.leaves(got[0].pools)
    for layer in range(cfg.n_layers):
        assert any(not np.array_equal(np.asarray(x)[layer], np.asarray(y)[layer])
                   for x, y in zip(before, after, strict=True)), layer
