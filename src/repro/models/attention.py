"""Attention: GQA/MQA, qk-norm, RoPE, sliding window, cross-attn, KV cache.

Two execution paths:

* ``chunked_attention`` — memory-bounded online-softmax attention in pure
  XLA (lax.scan over KV blocks inside a scan over Q blocks). This is the
  default for train/prefill everywhere (CPU dry-run, smoke tests) because it
  lowers on any backend with O(block²) temporaries instead of O(S²). The
  Pallas flash kernel (repro.kernels.flash_attention) is the TPU-targeted
  drop-in with identical semantics, validated against the same oracle.
* ``decode_attention`` — single-token attention against a KV cache. The
  cache is a uniform ring buffer: ``cache_len = window or max_len``; each
  slot stores the *absolute* position it holds (slot_pos), so full-cache and
  sliding-window decode share one code path (slot validity is computed from
  slot_pos, not layout).

Shapes: x (B, S, D); q (B, S, H, hd); k,v (B, S, KVH, hd); caches
(B, cache_len, KVH, hd) with slot_pos (B, cache_len) int32 (-1 = empty).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.cache.precision import KVPrecision, parse_kv_precision
from repro.configs.base import ModelConfig
from repro.kernels.quant import dequantize_kv, qdtype_of, quantize_kv
from repro.models.layers import apply_rope, cdtype, dense_init, headwise_rmsnorm

NEG_INF = -1e30


# ------------------------------------------------------------- KV precision
@functools.lru_cache(maxsize=None)
def _prec_of(kv_precision: str, legacy_cache_dtype: str) -> KVPrecision:
    if kv_precision:
        return parse_kv_precision(kv_precision)
    if legacy_cache_dtype:  # deprecated cast — engines warn once at init
        return KVPrecision(dtype=legacy_cache_dtype, granularity="none")
    return KVPrecision()


def kv_precision_of(cfg: ModelConfig) -> KVPrecision:
    """The config's resolved KVPrecision (DESIGN.md §14). Warning-free —
    this runs inside traced code; ``resolve_kv_precision`` (which flags the
    deprecated ``cache_dtype``) is called once by the engine constructors."""
    return _prec_of(cfg.kv_precision, cfg.cache_dtype)


def _staged(prec: KVPrecision) -> bool:
    """Does chunked prefill need a native staging buffer? Exactly when the
    cache storage is lossy: chunk N re-reads chunk N-1's K/V, and reading
    rounded values would break the one-shot-prefill equivalence contract."""
    return prec.lossy and prec.staging == "auto"


# ------------------------------------------------------------------- params
def attn_init(key, cfg: ModelConfig, cross: bool = False):
    D, H, KVH, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    ks = jax.random.split(key, 4)
    dt = cdtype(cfg)
    p = {
        "wq": dense_init(ks[0], D, H * hd, dt).reshape(D, H, hd),
        "wk": dense_init(ks[1], D, KVH * hd, dt).reshape(D, KVH, hd),
        "wv": dense_init(ks[2], D, KVH * hd, dt).reshape(D, KVH, hd),
        "wo": dense_init(ks[3], H * hd, D, dt).reshape(H, hd, D),
    }
    if cfg.qk_norm and not cross:
        p["q_norm"] = jnp.ones((hd,), jnp.float32)
        p["k_norm"] = jnp.ones((hd,), jnp.float32)
    return p


class KVCache(NamedTuple):
    """Dense ring cache. Under a quantized KVPrecision, k/v hold the
    storage dtype (int8/fp8) and k_scale/v_scale the per-token-per-head
    f32 scales; under a lossy precision stage_k/stage_v additionally carry
    the chunked-prefill native staging buffer (DESIGN.md §14). All
    optional leaves are None at native precision, so native pytrees (and
    jaxprs) carry exactly the pre-quantization three leaves."""

    k: jax.Array          # (B, L, KVH, hd) — RoPE already applied
    v: jax.Array          # (B, L, KVH, hd)
    slot_pos: jax.Array   # (B, L) int32, absolute position held; -1 empty
    k_scale: Optional[jax.Array] = None   # (B, L, KVH) f32 — quantized only
    v_scale: Optional[jax.Array] = None
    stage_k: Optional[jax.Array] = None   # (B, L, KVH, hd) native — chunked
    stage_v: Optional[jax.Array] = None

    @property
    def cache_len(self) -> int:
        return self.k.shape[1]


def cache_dtype(cfg: ModelConfig):
    """KV-cache storage dtype (cast dtype, quantized dtype, or native)."""
    prec = kv_precision_of(cfg)
    if prec.is_quantized:
        return qdtype_of(prec)
    if prec.is_cast:
        return jnp.dtype(prec.dtype)
    return cdtype(cfg)


def kv_cache_init(batch: int, cache_len: int, cfg: ModelConfig) -> KVCache:
    KVH, hd = cfg.n_kv_heads, cfg.head_dim_
    prec = kv_precision_of(cfg)
    dt = cache_dtype(cfg)
    scale = [jnp.zeros((batch, cache_len, KVH), jnp.float32)
             for _ in range(2)] if prec.is_quantized else (None, None)
    staged = [jnp.zeros((batch, cache_len, KVH, hd), cdtype(cfg))
              for _ in range(2)] if _staged(prec) else (None, None)
    return KVCache(
        k=jnp.zeros((batch, cache_len, KVH, hd), dt),
        v=jnp.zeros((batch, cache_len, KVH, hd), dt),
        slot_pos=jnp.full((batch, cache_len), -1, jnp.int32),
        k_scale=scale[0], v_scale=scale[1],
        stage_k=staged[0], stage_v=staged[1],
    )


class PagedKVPool(NamedTuple):
    """Shared-pool paged KV storage for one layer, or for a segment's layers
    stacked on a leading layer axis (as the engine holds it; the attention
    functions address a stacked pool at a layer index).

    k/v: ([n,] native_pages, page_size, KVH, hd). Rows are owned via
    ``repro.cache.PageAllocator`` block tables; logical slot j of a request
    lives at (table[j // page_size], j % page_size) and holds absolute
    position j — paged caches never wrap, they grow by appending pages.
    Recycled pages are not zeroed: the validity mask (j <= pos on allocated
    pages) hides stale rows before they can influence the softmax.

    Physical page ids are split into two regions (DESIGN.md §14): ids
    [0, native_pages) live in k/v at the native (or legacy cast) dtype;
    ids [native_pages, num_pages) live in qk/qv quantized with
    k_scale/v_scale per-token-per-head f32 scales. Either region may be
    empty (leaves None) — an all-native pool has exactly the
    pre-quantization two leaves. stage_k/stage_v is the chunked-prefill
    native staging buffer (rows, stage_len, KVH, hd), present only under a
    lossy precision.
    """

    k: Optional[jax.Array]
    v: Optional[jax.Array]
    qk: Optional[jax.Array] = None        # (quant_pages, ps, KVH, hd)
    qv: Optional[jax.Array] = None
    k_scale: Optional[jax.Array] = None   # (quant_pages, ps, KVH) f32
    v_scale: Optional[jax.Array] = None
    stage_k: Optional[jax.Array] = None   # (rows, stage_len, KVH, hd)
    stage_v: Optional[jax.Array] = None

    # sizes read from the trailing axes: the same on one layer's pool and
    # on the layer-stacked pool

    @property
    def native_pages(self) -> int:
        return self.k.shape[-4] if self.k is not None else 0

    @property
    def quant_pages(self) -> int:
        return self.qk.shape[-4] if self.qk is not None else 0

    @property
    def num_pages(self) -> int:
        return self.native_pages + self.quant_pages

    @property
    def page_size(self) -> int:
        return (self.k if self.k is not None else self.qk).shape[-3]


def paged_pool_init(num_pages: int, page_size: int, cfg: ModelConfig,
                    native_pages: Optional[int] = None, stage_rows: int = 0,
                    stage_len: int = 0) -> PagedKVPool:
    KVH, hd = cfg.n_kv_heads, cfg.head_dim_
    prec = kv_precision_of(cfg)
    if native_pages is None:
        native_pages = 0 if prec.is_quantized else num_pages
    nq = num_pages - native_pages
    if nq and not prec.is_quantized:
        raise ValueError("a quantized page region needs a quantized kv_precision")
    ndt = cdtype(cfg) if prec.is_quantized else cache_dtype(cfg)
    kw = {}
    if native_pages:
        shape = (native_pages, page_size, KVH, hd)
        kw.update(k=jnp.zeros(shape, ndt), v=jnp.zeros(shape, ndt))
    else:
        kw.update(k=None, v=None)
    if nq:
        qshape = (nq, page_size, KVH, hd)
        kw.update(qk=jnp.zeros(qshape, qdtype_of(prec)),
                  qv=jnp.zeros(qshape, qdtype_of(prec)),
                  k_scale=jnp.zeros((nq, page_size, KVH), jnp.float32),
                  v_scale=jnp.zeros((nq, page_size, KVH), jnp.float32))
    if stage_rows and _staged(prec):
        sshape = (stage_rows, stage_len, KVH, hd)
        kw.update(stage_k=jnp.zeros(sshape, cdtype(cfg)),
                  stage_v=jnp.zeros(sshape, cdtype(cfg)))
    return PagedKVPool(**kw)


def _at(layer, *idx):
    """Index of a pool leaf: ``idx`` on one layer's pool (``layer`` None),
    ``(layer, *idx)`` on the layer-stacked pool, which is then read and
    written where it lies, never sliced to a layer first."""
    return idx if layer is None else (layer, *idx)


def _pool_read(pool: PagedKVPool, block_table: jax.Array, dtype, layer=None):
    """Gather the logical K/V of every row through its block table ->
    (B, MP*ps, KVH, hd), from layer ``layer`` of a stacked pool (``None``:
    ``pool`` is one layer's). Quantized pages are dequantized to ``dtype``;
    native/cast pages are returned in their storage dtype when the pool has
    no quantized region (the callers' downstream casts are unchanged from
    the pre-quantization code, keeping those paths bit-identical)."""
    B, MP = block_table.shape
    ps = pool.page_size
    nn, nq = pool.native_pages, pool.quant_pages
    if pool.qk is None:
        gather = _at(layer, jnp.clip(block_table, 0, nn - 1))
        kk = pool.k[gather]
        vv = pool.v[gather]
    else:
        qpage = jnp.clip(block_table - nn, 0, nq - 1)
        qidx = _at(layer, qpage)
        # scales are gathered one page row at a time: with whole-page windows
        # XLA relays the whole stacked scale array out anew in every layer
        sidx = _at(layer, qpage[:, :, None], jnp.arange(ps))
        kk = dequantize_kv(pool.qk[qidx], pool.k_scale[sidx], dtype)
        vv = dequantize_kv(pool.qv[qidx], pool.v_scale[sidx], dtype)
        if pool.k is not None:   # mixed pool: per-page precision select
            nidx = _at(layer, jnp.clip(block_table, 0, nn - 1))
            is_native = ((block_table >= 0) & (block_table < nn))[:, :, None, None, None]
            kk = jnp.where(is_native, pool.k[nidx].astype(dtype), kk)
            vv = jnp.where(is_native, pool.v[nidx].astype(dtype), vv)
    KVH, hd = kk.shape[-2], kk.shape[-1]
    return kk.reshape(B, MP * ps, KVH, hd), vv.reshape(B, MP * ps, KVH, hd)


def _pool_write(pool: PagedKVPool, phys: jax.Array, off: jax.Array,
                k: jax.Array, v: jax.Array, prec: KVPrecision,
                layer=None) -> PagedKVPool:
    """Scatter native-dtype K/V rows into the pool at (phys, off), in layer
    ``layer`` of a stacked pool (``None``: ``pool`` is one layer's).
    ``phys`` must already carry the drop sentinel (num_pages) for invalid
    entries; each region's scatter drops writes aimed at the other region."""
    nn, nq = pool.native_pages, pool.quant_pages
    new = pool
    if pool.k is not None:
        nidx = _at(layer, jnp.minimum(phys, nn), off)   # quant region / pads -> drop
        new = new._replace(
            k=new.k.at[nidx].set(k.astype(new.k.dtype), mode="drop"),
            v=new.v.at[nidx].set(v.astype(new.v.dtype), mode="drop"),
        )
    if pool.qk is not None:
        qidx = _at(layer, jnp.where(phys >= nn, phys - nn, nq), off)   # native region -> drop
        qk_, ks_ = quantize_kv(k, prec)
        qv_, vs_ = quantize_kv(v, prec)
        new = new._replace(
            qk=new.qk.at[qidx].set(qk_, mode="drop"),
            qv=new.qv.at[qidx].set(qv_, mode="drop"),
            k_scale=new.k_scale.at[qidx].set(ks_, mode="drop"),
            v_scale=new.v_scale.at[qidx].set(vs_, mode="drop"),
        )
    return new


# ------------------------------------------------- chunked online-softmax
#
# Differentiable via a FLASH BACKWARD (custom_vjp): the forward saves only
# (q, k, v, o, lse); the backward re-materializes each (q-block, k-block)
# score tile and accumulates dq/dk/dv. Without this, the fwd scans would
# stash every per-tile softmax for the bwd — ~2 GiB/layer/device at 4k
# train shapes (measured: 76 GiB temp vs 11 GiB with flash-bwd; see
# EXPERIMENTS.md §Perf).
def chunked_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    prefix_len: int = 0,
    q_offset: int = 0,
    block_q: int = 512,
    block_k: int = 512,
) -> jax.Array:
    """Online-softmax attention over KV blocks. q:(B,Sq,H,hd) k,v:(B,Sk,KVH,hd).

    Memory: O(block_q * block_k) per (head-group) instead of O(Sq*Sk).
    ``window`` restricts attention to keys with qpos - window < kpos <= qpos.
    ``prefix_len`` > 0 gives prefix-LM masking: keys with kpos < prefix_len
    are visible to every query (PaliGemma-style bidirectional prefix).
    ``q_offset`` is the absolute position of q[0] (cross-block prefill).
    Non-divisible sequence lengths are zero-padded; padded keys sit at
    positions >= Sk so the causal mask hides them from real queries.
    """
    Sq_real, Sk_real = q.shape[1], k.shape[1]
    block_q = min(block_q, Sq_real)
    block_k = min(block_k, Sk_real)
    pad_q = (-Sq_real) % block_q
    pad_k = (-Sk_real) % block_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    k_limit = Sk_real if (pad_k and not causal) else None

    f = _make_flash(causal, window, prefix_len, q_offset, block_q, block_k, k_limit)
    out = f(q, k, v)
    if pad_q:
        out = out[:, :Sq_real]
    return out


def _tile_mask(qpos, kpos, causal, window, prefix_len, k_limit):
    mask = jnp.ones((qpos.shape[0], kpos.shape[0]), jnp.bool_)
    if causal:
        cmask = kpos[None, :] <= qpos[:, None]
        if window is not None:
            cmask &= kpos[None, :] > (qpos[:, None] - window)
        if prefix_len:
            cmask |= kpos[None, :] < prefix_len
        mask &= cmask
    if k_limit is not None:
        mask &= kpos[None, :] < k_limit
    return mask


def _flash_fwd_impl(q, k, v, causal, window, prefix_len, q_offset, block_q, block_k, k_limit):
    """Tiled forward. Returns (out (B,Sq,H,hd), lse (B,KVH,G,Sq))."""
    B, Sq, H, hd = q.shape
    _, Sk, KVH, _ = k.shape
    G = H // KVH
    scale = hd ** -0.5
    nq, nk = Sq // block_q, Sk // block_k
    qg = q.reshape(B, nq, block_q, KVH, G, hd)
    kb = k.reshape(B, nk, block_k, KVH, hd)
    vb = v.reshape(B, nk, block_k, KVH, hd)

    def q_block(qi, qblk):
        qpos = q_offset + qi * block_q + jnp.arange(block_q)

        def kv_step(carry, inputs):
            m, l, acc = carry
            kj, kblk, vblk = inputs
            kpos = kj * block_k + jnp.arange(block_k)
            s = jnp.einsum(
                "bqkgh,bskh->bkgqs", qblk, kblk, preferred_element_type=jnp.float32
            ) * scale
            mask = _tile_mask(qpos, kpos, causal, window, prefix_len, k_limit)
            s = jnp.where(mask, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l = l * alpha + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bkgqs,bskh->bkgqh", p.astype(vblk.dtype), vblk)
            acc = acc * alpha[..., None] + pv.astype(jnp.float32)
            return (m_new, l, acc), None

        m0 = jnp.full((B, KVH, G, block_q), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KVH, G, block_q), jnp.float32)
        a0 = jnp.zeros((B, KVH, G, block_q, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0), (jnp.arange(nk), kb.swapaxes(0, 1), vb.swapaxes(0, 1))
        )
        out = acc / jnp.maximum(l, 1e-30)[..., None]            # (B,KVH,G,bq,hd)
        lse = jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-30)), NEG_INF)
        return out.transpose(0, 3, 1, 2, 4), lse                 # (B,bq,KVH,G,hd)

    _, (outs, lses) = jax.lax.scan(
        lambda _, x: (None, q_block(*x)), None, (jnp.arange(nq), qg.swapaxes(0, 1))
    )  # outs (nq,B,bq,KVH,G,hd); lses (nq,B,KVH,G,bq)
    out = outs.swapaxes(0, 1).reshape(B, Sq, H, hd).astype(q.dtype)
    lse = lses.transpose(1, 2, 3, 0, 4).reshape(B, KVH, G, Sq)
    return out, lse


def _flash_bwd_impl(q, k, v, o, lse, do, causal, window, prefix_len, q_offset,
                    block_q, block_k, k_limit):
    """Flash backward: recompute tiles from (q,k,v,lse); O(bq*bk) memory."""
    B, Sq, H, hd = q.shape
    _, Sk, KVH, _ = k.shape
    G = H // KVH
    scale = hd ** -0.5
    nq, nk = Sq // block_q, Sk // block_k
    qg = q.reshape(B, nq, block_q, KVH, G, hd).swapaxes(0, 1)
    kb = k.reshape(B, nk, block_k, KVH, hd)
    vb = v.reshape(B, nk, block_k, KVH, hd)
    dog = do.reshape(B, nq, block_q, KVH, G, hd).swapaxes(0, 1)
    og = o.reshape(B, nq, block_q, KVH, G, hd).swapaxes(0, 1)
    lseg = lse.reshape(B, KVH, G, nq, block_q).transpose(3, 0, 1, 2, 4)  # (nq,B,KVH,G,bq)
    # D_i = rowsum(do * o)  (B,KVH,G,bq) per q block
    Dg = jnp.einsum("nbqkgh,nbqkgh->nbkgq", dog.astype(jnp.float32), og.astype(jnp.float32))

    def q_step(carry, inputs):
        dk_acc, dv_acc = carry                                   # (nk,B,bk,KVH,hd) f32
        qi, qblk, dob, ob, lseb, Db = inputs
        qpos = q_offset + qi * block_q + jnp.arange(block_q)

        def kv_step(dq_acc, inputs2):
            kj, kblk, vblk = inputs2
            kpos = kj * block_k + jnp.arange(block_k)
            s = jnp.einsum(
                "bqkgh,bskh->bkgqs", qblk, kblk, preferred_element_type=jnp.float32
            ) * scale
            mask = _tile_mask(qpos, kpos, causal, window, prefix_len, k_limit)
            s = jnp.where(mask, s, NEG_INF)
            p = jnp.exp(s - lseb[..., None])                     # normalized probs
            dv_blk = jnp.einsum("bkgqs,bqkgh->bskh", p, dob.astype(jnp.float32))
            dp = jnp.einsum("bqkgh,bskh->bkgqs", dob.astype(jnp.float32),
                            vblk.astype(jnp.float32))
            ds = p * (dp - Db[..., None])                        # (B,KVH,G,bq,bk)
            dq_blk = jnp.einsum("bkgqs,bskh->bqkgh", ds, kblk.astype(jnp.float32)) * scale
            dk_blk = jnp.einsum("bkgqs,bqkgh->bskh", ds, qblk.astype(jnp.float32)) * scale
            return dq_acc + dq_blk, (dk_blk, dv_blk)

        dq0 = jnp.zeros((B, block_q, KVH, G, hd), jnp.float32)
        dq, (dk_blks, dv_blks) = jax.lax.scan(
            kv_step, dq0, (jnp.arange(nk), kb.swapaxes(0, 1), vb.swapaxes(0, 1))
        )
        return (dk_acc + dk_blks, dv_acc + dv_blks), dq

    dk0 = jnp.zeros((nk, B, block_k, KVH, hd), jnp.float32)
    dv0 = jnp.zeros((nk, B, block_k, KVH, hd), jnp.float32)
    (dk, dv), dqs = jax.lax.scan(
        q_step, (dk0, dv0), (jnp.arange(nq), qg, dog, og, lseg, Dg)
    )
    dq = dqs.swapaxes(0, 1).reshape(B, Sq, H, hd).astype(q.dtype)
    dk = dk.swapaxes(0, 1).reshape(B, Sk, KVH, hd).astype(k.dtype)
    dv = dv.swapaxes(0, 1).reshape(B, Sk, KVH, hd).astype(v.dtype)
    return dq, dk, dv


@functools.lru_cache(maxsize=None)
def _make_flash(causal, window, prefix_len, q_offset, block_q, block_k, k_limit):
    meta = (causal, window, prefix_len, q_offset, block_q, block_k, k_limit)

    @jax.custom_vjp
    def f(q, k, v):
        return _flash_fwd_impl(q, k, v, *meta)[0]

    def fwd(q, k, v):
        o, lse = _flash_fwd_impl(q, k, v, *meta)
        return o, (q, k, v, o, lse)

    def bwd(res, do):
        q, k, v, o, lse = res
        return _flash_bwd_impl(q, k, v, o, lse, do, *meta)

    f.defvjp(fwd, bwd)
    return f


# --------------------------------------------------------------- full pass
def attn_forward(
    params,
    x: jax.Array,
    cfg: ModelConfig,
    *,
    positions: Optional[jax.Array] = None,
    causal: bool = True,
    window: Optional[int] = None,
    prefix_len: int = 0,
    kv_x: Optional[jax.Array] = None,
    use_rope: bool = True,
) -> jax.Array:
    """Full-sequence attention (train / prefill / encoder / cross).

    kv_x: if given, keys/values come from it (cross-attention, no rope/mask).
    """
    B, S, D = x.shape
    src = kv_x if kv_x is not None else x
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", src, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", src, params["wv"])
    if "q_norm" in params:
        q = headwise_rmsnorm(params["q_norm"], q, cfg.norm_eps)
        k = headwise_rmsnorm(params["k_norm"], k, cfg.norm_eps)
    if use_rope and kv_x is None:
        pos = positions if positions is not None else jnp.arange(S)[None, :]
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
    out = chunked_attention(
        q, k, v, causal=causal and kv_x is None, window=window, prefix_len=prefix_len
    )
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"])


def attn_prefill(
    params,
    x: jax.Array,
    cfg: ModelConfig,
    cache_len: int,
    *,
    window: Optional[int] = None,
    prefix_len: int = 0,
    seq_lens: Optional[jax.Array] = None,
) -> tuple[jax.Array, KVCache]:
    """Causal self-attention over the prompt + build the decode cache.

    Stores the last ``cache_len`` (window or max) roped K/V into a ring cache
    positioned so that slot index = absolute_pos % cache_len.

    ``seq_lens`` (B,) int32 makes the prefill length-aware (ragged): cache
    slots at or beyond a row's real length stay empty (zero K/V, slot_pos
    -1) so padding never enters decode attention. The attention compute
    itself needs no masking — pads sit at the *end* of the prompt, so under
    the causal mask no real position ever attends one; real rows' outputs
    (and therefore the cache rows written) are bit-identical for any bucket
    size >= the row's length.
    """
    B, S, D = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    if "q_norm" in params:
        q = headwise_rmsnorm(params["q_norm"], q, cfg.norm_eps)
        k = headwise_rmsnorm(params["k_norm"], k, cfg.norm_eps)
    pos = jnp.arange(S)[None, :]
    q = apply_rope(q, pos, cfg.rope_theta)
    k = apply_rope(k, pos, cfg.rope_theta)
    out = chunked_attention(q, k, v, causal=True, window=window, prefix_len=prefix_len)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])

    cache = kv_cache_init(B, cache_len, cfg)
    prec = kv_precision_of(cfg)
    cdt = cache_dtype(cfg)
    n = min(S, cache_len)
    tail = jnp.arange(S - n, S)                       # absolute positions kept
    slots = tail % cache_len                          # ring placement
    keep = tail[None, :] < seq_lens[:, None] if seq_lens is not None else None
    if prec.is_quantized:
        # per-token symmetric quantization: pad rows are zeroed AFTER the
        # quantize, so real rows' scales never depend on the bucket size
        kw, ksw = quantize_kv(k[:, S - n:], prec)
        vw, vsw = quantize_kv(v[:, S - n:], prec)
        if keep is not None:
            kw = jnp.where(keep[..., None, None], kw, 0)
            vw = jnp.where(keep[..., None, None], vw, 0)
            ksw = jnp.where(keep[..., None], ksw, 0)
            vsw = jnp.where(keep[..., None], vsw, 0)
        cache = cache._replace(
            k_scale=cache.k_scale.at[:, slots].set(ksw),
            v_scale=cache.v_scale.at[:, slots].set(vsw),
        )
    else:
        kw = k[:, S - n :].astype(cdt)
        vw = v[:, S - n :].astype(cdt)
        if keep is not None:
            kw = jnp.where(keep[..., None, None], kw, 0)
            vw = jnp.where(keep[..., None, None], vw, 0)
    spw = jnp.broadcast_to(tail[None, :], (B, n)).astype(jnp.int32)
    if keep is not None:
        spw = jnp.where(keep, spw, -1)
    if cache.stage_k is not None:
        skw, svw = k[:, S - n:], v[:, S - n:]
        if keep is not None:
            skw = jnp.where(keep[..., None, None], skw, 0)
            svw = jnp.where(keep[..., None, None], svw, 0)
        cache = cache._replace(
            stage_k=cache.stage_k.at[:, slots].set(skw.astype(cache.stage_k.dtype)),
            stage_v=cache.stage_v.at[:, slots].set(svw.astype(cache.stage_v.dtype)),
        )
    cache = cache._replace(
        k=cache.k.at[:, slots].set(kw),
        v=cache.v.at[:, slots].set(vw),
        slot_pos=cache.slot_pos.at[:, slots].set(spw),
    )
    return y, cache


def attn_decode(
    params,
    x: jax.Array,            # (B, D) — one new token's residual input
    cache: KVCache,
    pos: jax.Array,          # (B,) absolute position of the new token
    cfg: ModelConfig,
    *,
    window: Optional[int] = None,
) -> tuple[jax.Array, KVCache]:
    """One decode step: rope at pos, ring-write, attend over valid slots."""
    B, D = x.shape
    L = cache.cache_len
    q = jnp.einsum("bd,dhk->bhk", x, params["wq"])
    k = jnp.einsum("bd,dhk->bhk", x, params["wk"])
    v = jnp.einsum("bd,dhk->bhk", x, params["wv"])
    if "q_norm" in params:
        q = headwise_rmsnorm(params["q_norm"], q, cfg.norm_eps)
        k = headwise_rmsnorm(params["k_norm"], k, cfg.norm_eps)
    q = apply_rope(q[:, None], pos[:, None], cfg.rope_theta)[:, 0]
    k = apply_rope(k[:, None], pos[:, None], cfg.rope_theta)[:, 0]

    slot = (pos % L).astype(jnp.int32)                # (B,)
    b_idx = jnp.arange(B)
    cdt = cache.k.dtype
    if cache.k_scale is not None:
        prec = kv_precision_of(cfg)
        qk_, ks_ = quantize_kv(k, prec)
        qv_, vs_ = quantize_kv(v, prec)
        cache = cache._replace(
            k=cache.k.at[b_idx, slot].set(qk_),
            v=cache.v.at[b_idx, slot].set(qv_),
            k_scale=cache.k_scale.at[b_idx, slot].set(ks_),
            v_scale=cache.v_scale.at[b_idx, slot].set(vs_),
            slot_pos=cache.slot_pos.at[b_idx, slot].set(pos.astype(jnp.int32)),
        )
        ck = dequantize_kv(cache.k, cache.k_scale, q.dtype)
        cv = dequantize_kv(cache.v, cache.v_scale, q.dtype)
    else:
        cache = cache._replace(
            k=cache.k.at[b_idx, slot].set(k.astype(cdt)),
            v=cache.v.at[b_idx, slot].set(v.astype(cdt)),
            slot_pos=cache.slot_pos.at[b_idx, slot].set(pos.astype(jnp.int32)),
        )
        ck, cv = cache.k, cache.v

    H, KVH, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    G = H // KVH
    qg = q.reshape(B, KVH, G, hd)
    s = jnp.einsum(
        "bkgh,blkh->bkgl", qg, ck.astype(q.dtype),
        preferred_element_type=jnp.float32,
    ) * (hd ** -0.5)
    valid = (cache.slot_pos >= 0) & (cache.slot_pos <= pos[:, None])
    if window is not None:
        valid &= cache.slot_pos > (pos[:, None] - window)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgl,blkh->bkgh", p.astype(q.dtype), cv.astype(q.dtype))
    out = out.reshape(B, H, hd)
    y = jnp.einsum("bhk,hkd->bd", out, params["wo"])
    return y, cache


def attn_chunk(
    params,
    x: jax.Array,            # (B, C, D) — one prompt chunk per row
    cache: KVCache,
    pos0: jax.Array,         # (B,) absolute position of the chunk's first token
    valid: jax.Array,        # (B,) real tokens in this chunk (0 = no chunk work)
    cfg: ModelConfig,
    *,
    window: Optional[int] = None,
    reset: Optional[jax.Array] = None,   # (B,) bool — first chunk of a recycled row
) -> tuple[jax.Array, KVCache]:
    """Chunked-prefill attention: write the chunk's K/V at per-row offsets
    [pos0, pos0+valid), then attend the chunk's queries over the row's cache
    (all earlier chunks plus the chunk itself, position-ordered).

    Numerics mirror ``chunked_attention``'s single-KV-tile forward exactly —
    scores in f32 (preferred_element_type) * scale then mask, rowwise max,
    (p @ v) accumulated then divided by l — so every real query position
    produces the same floats it would inside ``attn_prefill`` over the whole
    prompt: masked keys (slot_pos -1 / future positions) contribute
    exp(NEG_INF - m) == 0.0 exactly, and trailing exact zeros are inert in
    the reductions. By induction over layers and chunks the cache rows and
    last-token logits are bit-identical to the one-shot prefill, which is
    the continuous-batching engine's equivalence contract.

    ``reset`` marks rows whose cache still holds a previous tenant: their
    ``slot_pos`` is invalidated before the write (stale K/V need no zeroing
    — an invalid slot's weight is exactly 0). Rows with ``valid == 0``
    write nothing (their scatter indices are out of range) and their output
    is discarded by the caller.
    """
    B, C, D = x.shape
    L = cache.cache_len
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    if "q_norm" in params:
        q = headwise_rmsnorm(params["q_norm"], q, cfg.norm_eps)
        k = headwise_rmsnorm(params["k_norm"], k, cfg.norm_eps)
    pos = pos0[:, None] + jnp.arange(C)[None, :]          # (B, C)
    q = apply_rope(q, pos, cfg.rope_theta)
    k = apply_rope(k, pos, cfg.rope_theta)

    slot_pos = cache.slot_pos
    if reset is not None:
        slot_pos = jnp.where(reset[:, None], -1, slot_pos)
    keep = jnp.arange(C)[None, :] < valid[:, None]        # (B, C)
    slots = jnp.where(keep, pos % L, L)                   # L = out of range -> drop
    cdt = cache.k.dtype
    b_idx = jnp.arange(B)[:, None]
    if cache.k_scale is not None:
        prec = kv_precision_of(cfg)
        qk_, ks_ = quantize_kv(k, prec)
        qv_, vs_ = quantize_kv(v, prec)
        cache = cache._replace(
            k=cache.k.at[b_idx, slots].set(qk_, mode="drop"),
            v=cache.v.at[b_idx, slots].set(qv_, mode="drop"),
            k_scale=cache.k_scale.at[b_idx, slots].set(ks_, mode="drop"),
            v_scale=cache.v_scale.at[b_idx, slots].set(vs_, mode="drop"),
            slot_pos=slot_pos.at[b_idx, slots].set(pos.astype(jnp.int32),
                                                   mode="drop"),
        )
    else:
        cache = cache._replace(
            k=cache.k.at[b_idx, slots].set(k.astype(cdt), mode="drop"),
            v=cache.v.at[b_idx, slots].set(v.astype(cdt), mode="drop"),
            slot_pos=slot_pos.at[b_idx, slots].set(pos.astype(jnp.int32),
                                                   mode="drop"),
        )

    H, KVH, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    G = H // KVH
    qg = q.reshape(B, C, KVH, G, hd)
    if cache.stage_k is not None:
        # lossy storage: the prompt phase attends the NATIVE staging copy
        # (chunk N re-reads chunk N-1 exactly; the rounded cache is read
        # only by post-activation decode). During the prompt every position
        # <= qpos was written by the row's current tenant — chunks are
        # contiguous from 0 — so validity is purely causal.
        cache = cache._replace(
            stage_k=cache.stage_k.at[b_idx, slots].set(
                k.astype(cache.stage_k.dtype), mode="drop"),
            stage_v=cache.stage_v.at[b_idx, slots].set(
                v.astype(cache.stage_v.dtype), mode="drop"),
        )
        src_k, src_v = cache.stage_k, cache.stage_v
        ok = jnp.arange(L)[None, None, :] <= pos[:, :, None]   # (B, C, L)
    else:
        src_k, src_v = cache.k, cache.v
        sp = cache.slot_pos[:, None, :]                   # (B, 1, L)
        ok = (sp >= 0) & (sp <= pos[:, :, None])          # (B, C, L)
        if window is not None:
            ok &= sp > (pos[:, :, None] - window)
    s = jnp.einsum(
        "bqkgh,bskh->bkgqs", qg, src_k.astype(q.dtype),
        preferred_element_type=jnp.float32,
    ) * (hd ** -0.5)                                      # (B, KVH, G, C, L)
    s = jnp.where(ok[:, None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    pv = jnp.einsum("bkgqs,bskh->bkgqh", p.astype(src_v.dtype), src_v)
    out = pv.astype(jnp.float32) / jnp.maximum(l, 1e-30)[..., None]
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, C, H, hd).astype(q.dtype)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    return y, cache


def attn_chunk_paged(
    params,
    x: jax.Array,            # (B, C, D) — one prompt chunk per row
    pool: PagedKVPool,
    block_table: jax.Array,  # (B, MP) int32 physical page ids; -1 = unallocated
    pos0: jax.Array,         # (B,)
    valid: jax.Array,        # (B,)
    cfg: ModelConfig,
    base: Optional[jax.Array] = None,   # (B,) prefix-cache hit tokens per row
    *,
    layer: Optional[jax.Array] = None,      # () layer of a stacked pool
    stage_row: Optional[jax.Array] = None,  # (B,) staging row of each row
) -> tuple[jax.Array, PagedKVPool]:
    """``attn_chunk`` over the shared page pool: the chunk's K/V rows land in
    block-table pages (logical slot j at (table[j // ps], j % ps)), then the
    queries attend the gathered logical cache with the per-query validity
    mask ``allocated & (j <= qpos)``. Same single-tile flash numerics as the
    dense variant; no slot_pos reset is needed — a previous tenant's rows
    survive only at logical slots this request has not yet written, all of
    which sit at j > qpos and are masked.

    Under a lossy KVPrecision the pool write is rounded but the row's own
    chunks additionally land in the native staging buffer
    (``pool.stage_k/v``), and prompt-phase attention reads pool pages only
    for the prefix-cache hit ``base`` (positions written by an earlier,
    already-rounded tenant) while positions >= base come from staging —
    chunk N re-reads chunk N-1 exactly, restoring the one-shot-prefill
    equivalence contract for quantized/cast storage.

    ``layer`` addresses layer ``layer`` of a layer-stacked pool in place
    (``None``: ``pool`` is one layer's); ``stage_row`` names the staging
    row of each of the B rows (default: row b stages in row b).
    """
    B, C, D = x.shape
    N, ps = pool.num_pages, pool.page_size
    MP = block_table.shape[1]
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    if "q_norm" in params:
        q = headwise_rmsnorm(params["q_norm"], q, cfg.norm_eps)
        k = headwise_rmsnorm(params["k_norm"], k, cfg.norm_eps)
    pos = pos0[:, None] + jnp.arange(C)[None, :]          # (B, C)
    q = apply_rope(q, pos, cfg.rope_theta)
    k = apply_rope(k, pos, cfg.rope_theta)

    keep = jnp.arange(C)[None, :] < valid[:, None]
    lp = jnp.clip(pos // ps, 0, MP - 1)
    phys = jnp.take_along_axis(block_table, lp, axis=1)   # (B, C)
    phys = jnp.where(keep & (phys >= 0), phys, N)         # N = out of range -> drop
    off = pos % ps
    pool = _pool_write(pool, phys, off, k, v, kv_precision_of(cfg), layer)

    H, KVH, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    G = H // KVH
    qg = q.reshape(B, C, KVH, G, hd)
    j = jnp.arange(MP * ps)[None, None, :]
    allocated = jnp.repeat(block_table >= 0, ps, axis=1)[:, None, :]
    if pool.stage_k is not None:
        # native staging for the in-flight prompt: pool pages serve only the
        # prefix-cache hit [0, base); staging serves [base, qpos]
        SL = pool.stage_k.shape[-3]
        srow = jnp.arange(B) if stage_row is None else stage_row
        spos = jnp.where(keep, jnp.minimum(pos, SL), SL)  # SL = drop
        sidx = _at(layer, srow[:, None], spos)
        pool = pool._replace(
            stage_k=pool.stage_k.at[sidx].set(
                k.astype(pool.stage_k.dtype), mode="drop"),
            stage_v=pool.stage_v.at[sidx].set(
                v.astype(pool.stage_v.dtype), mode="drop"),
        )
        if base is None:
            base = jnp.zeros((B,), jnp.int32)
        kk, vv = _pool_read(pool, block_table, q.dtype, layer)
        kk = kk.astype(q.dtype)
        vv = vv.astype(q.dtype)
        ok_pool = allocated & (j < base[:, None, None]) & (j <= pos[:, :, None])
        js = jnp.arange(SL)[None, None, :]
        ok_stage = (js >= base[:, None, None]) & (js <= pos[:, :, None])
        sk, sv = pool.stage_k[_at(layer, srow)], pool.stage_v[_at(layer, srow)]
        kk = jnp.concatenate([kk, sk.astype(q.dtype)], axis=1)
        vv = jnp.concatenate([vv, sv.astype(q.dtype)], axis=1)
        ok = jnp.concatenate([ok_pool, jnp.broadcast_to(
            ok_stage, (B, C, SL))], axis=-1)
    else:
        kk, vv = _pool_read(pool, block_table, q.dtype, layer)
        ok = allocated & (j <= pos[:, :, None])           # (B, C, MP*ps)
    s = jnp.einsum(
        "bqkgh,bskh->bkgqs", qg, kk.astype(q.dtype),
        preferred_element_type=jnp.float32,
    ) * (hd ** -0.5)                                      # (B, KVH, G, C, S)
    s = jnp.where(ok[:, None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    pv = jnp.einsum("bkgqs,bskh->bkgqh", p.astype(vv.dtype), vv)
    out = pv.astype(jnp.float32) / jnp.maximum(l, 1e-30)[..., None]
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, C, H, hd).astype(q.dtype)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    return y, pool


def attn_decode_paged(
    params,
    x: jax.Array,            # (B, D) — one new token's residual input
    pool: PagedKVPool,
    block_table: jax.Array,  # (B, MP) int32 physical page ids; -1 = unallocated
    pos: jax.Array,          # (B,) absolute position of the new token
    cfg: ModelConfig,
    *,
    layer: Optional[jax.Array] = None,   # () layer of a stacked pool
) -> tuple[jax.Array, PagedKVPool]:
    """One decode step against the paged pool: rope at pos, write the new
    row into the block-table page, attend over the gathered logical cache.

    Mirrors ``attn_decode`` op for op, so on a shared-length workload
    (MP * page_size == cache_len, no wraparound) the two paths are
    bit-identical: the gather reassembles exactly the dense cache array and
    the validity mask (j <= pos on allocated pages) equals the dense
    slot_pos mask. Rows of inactive requests carry an all(-1) block table —
    their write is dropped and their output is discarded by the engine.
    ``layer`` addresses layer ``layer`` of a layer-stacked pool in place
    (``None``: ``pool`` is one layer's).
    """
    B, D = x.shape
    N, ps = pool.num_pages, pool.page_size
    MP = block_table.shape[1]
    q = jnp.einsum("bd,dhk->bhk", x, params["wq"])
    k = jnp.einsum("bd,dhk->bhk", x, params["wk"])
    v = jnp.einsum("bd,dhk->bhk", x, params["wv"])
    if "q_norm" in params:
        q = headwise_rmsnorm(params["q_norm"], q, cfg.norm_eps)
        k = headwise_rmsnorm(params["k_norm"], k, cfg.norm_eps)
    q = apply_rope(q[:, None], pos[:, None], cfg.rope_theta)[:, 0]
    k = apply_rope(k[:, None], pos[:, None], cfg.rope_theta)[:, 0]

    lp = (pos // ps).astype(jnp.int32)                # logical page of pos
    phys = jnp.take_along_axis(block_table, lp[:, None], axis=1)[:, 0]
    phys = jnp.where(phys < 0, N, phys)               # N = out of range -> drop
    off = (pos % ps).astype(jnp.int32)
    pool = _pool_write(pool, phys, off, k, v, kv_precision_of(cfg), layer)
    kk, vv = _pool_read(pool, block_table, q.dtype, layer)

    H, KVH, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    G = H // KVH
    qg = q.reshape(B, KVH, G, hd)
    s = jnp.einsum(
        "bkgh,blkh->bkgl", qg, kk.astype(q.dtype),
        preferred_element_type=jnp.float32,
    ) * (hd ** -0.5)
    j = jnp.arange(MP * ps)[None, :]
    allocated = jnp.repeat(block_table >= 0, ps, axis=1)
    valid = allocated & (j <= pos[:, None])
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgl,blkh->bkgh", p.astype(q.dtype), vv.astype(q.dtype))
    out = out.reshape(B, H, hd)
    y = jnp.einsum("bhk,hkd->bd", out, params["wo"])
    return y, pool


def paged_splice_prompt(pool: PagedKVPool, cache: KVCache,
                        page_idx: jax.Array) -> PagedKVPool:
    """Scatter a prefill-built dense cache into the page pool (one layer).

    cache k/v: (B, P, KVH, hd) with the prompt occupying slots 0..P-1
    (prefill with cache_len == prompt_len never wraps). page_idx: (B, npp)
    physical destination pages, npp = P / page_size; pad rows carry an
    out-of-range id (>= num_pages) and are dropped, so one fixed-shape
    scatter handles any number of admitted requests.

    Under a quantized precision the engine runs prefill with a
    native-storage config variant (the dense prefill cache cannot hold
    native values in int8 arrays), so ``cache.k/v`` arrive native here and
    the splice quantizes per destination region: pages landing in the
    quantized region get rounded rows + scales, pages in the native region
    get the plain cast — each region's scatter drops ids aimed at the other.
    """
    B, P = cache.k.shape[0], cache.k.shape[1]
    npp = page_idx.shape[1]
    ps = P // npp
    rows_k = cache.k.reshape(B, npp, ps, *cache.k.shape[2:])
    rows_v = cache.v.reshape(B, npp, ps, *cache.v.shape[2:])
    nn, nq = pool.native_pages, pool.quant_pages
    new = pool
    if pool.k is not None:
        nidx = jnp.minimum(page_idx, nn)              # quant region / pads -> drop
        new = new._replace(
            k=new.k.at[nidx].set(rows_k.astype(new.k.dtype), mode="drop"),
            v=new.v.at[nidx].set(rows_v.astype(new.v.dtype), mode="drop"),
        )
    if pool.qk is not None:
        prec = parse_kv_precision(str(pool.qk.dtype))
        qidx = jnp.where(page_idx >= nn, page_idx - nn, nq)
        qk_, ks_ = quantize_kv(rows_k, prec)
        qv_, vs_ = quantize_kv(rows_v, prec)
        new = new._replace(
            qk=new.qk.at[qidx].set(qk_, mode="drop"),
            qv=new.qv.at[qidx].set(qv_, mode="drop"),
            k_scale=new.k_scale.at[qidx].set(ks_, mode="drop"),
            v_scale=new.v_scale.at[qidx].set(vs_, mode="drop"),
        )
    return new


def fork_pages(pool: PagedKVPool, src_idx: jax.Array,
               dst_idx: jax.Array) -> PagedKVPool:
    """Copy whole pages src -> dst inside one layer's pool (COW forks).

    src_idx/dst_idx: (F,) physical page ids; pad entries carry an
    out-of-range dst (>= num_pages, dropped by the scatter) with src
    clamped into range (the gathered rows land nowhere), so one fixed-shape
    dispatch forks any number of pages. The copy is whole-page: rows past
    the fork point are overwritten by the new holder's chunks and rows past
    its pos are masked, so over-copying is free. Forks never cross the
    precision boundary (the allocator hands out dst pages from the src's
    region), so each region copies independently — quantized pages move
    with their scales, byte-for-byte.
    """
    nn, nq = pool.native_pages, pool.quant_pages
    new = pool
    if pool.k is not None:
        srcn = jnp.clip(src_idx, 0, nn - 1)
        dstn = jnp.minimum(dst_idx, nn)
        new = new._replace(
            k=new.k.at[dstn].set(new.k[srcn], mode="drop"),
            v=new.v.at[dstn].set(new.v[srcn], mode="drop"),
        )
    if pool.qk is not None:
        srcq = jnp.clip(src_idx - nn, 0, nq - 1)
        dstq = jnp.where(dst_idx >= nn, dst_idx - nn, nq)
        new = new._replace(
            qk=new.qk.at[dstq].set(new.qk[srcq], mode="drop"),
            qv=new.qv.at[dstq].set(new.qv[srcq], mode="drop"),
            k_scale=new.k_scale.at[dstq].set(new.k_scale[srcq], mode="drop"),
            v_scale=new.v_scale.at[dstq].set(new.v_scale[srcq], mode="drop"),
        )
    return new


def cross_attn_cache(params, enc_out: jax.Array):
    """Precompute cross-attention K/V from encoder output (B, Se, D)."""
    k = jnp.einsum("bsd,dhk->bshk", enc_out, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", enc_out, params["wv"])
    return {"k": k, "v": v}


def cross_attn_decode(params, x: jax.Array, xcache, cfg: ModelConfig) -> jax.Array:
    """One-token cross-attention against fixed encoder K/V."""
    B, D = x.shape
    H, KVH, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    G = H // KVH
    q = jnp.einsum("bd,dhk->bhk", x, params["wq"]).reshape(B, KVH, G, hd)
    s = jnp.einsum(
        "bkgh,blkh->bkgl", q, xcache["k"], preferred_element_type=jnp.float32
    ) * (hd ** -0.5)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgl,blkh->bkgh", p.astype(xcache["v"].dtype), xcache["v"])
    return jnp.einsum("bhk,hkd->bd", out.reshape(B, H, hd), params["wo"])
