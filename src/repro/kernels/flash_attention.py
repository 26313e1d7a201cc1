"""Pallas TPU flash attention (prefill/train forward).

Grid: (B, H, nq, nk) — nk is the innermost (sequential on TPU) axis so the
online-softmax running state (m, l, acc) lives in VMEM scratch across the
KV sweep of one Q tile. The wrapper moves heads ahead of the sequence axis
((B, S, H, hd) -> (B, H, S, hd)) so every tile's last two dims are
(block, hd): Mosaic tiles the last two dims of a block and refuses a
one-head (block, 1, hd) slice of a (S, H, hd) array. Tiles:

  q     (1, 1, block_q, hd)   VMEM    — one (batch, head) Q tile
  k/v   (1, 1, block_k, hd)   VMEM    — KV head = q_head // G (GQA folding)
  out   (1, 1, block_q, hd)   VMEM    — written on the last KV step

block_q/block_k default 512/512: with hd=128 and f32 scratch the working set
is ~ (512*128)*3 (q,k,v) * 2B + (512*128 + 2*512)*4B acc ≈ 0.7 MB — well
under the ~16 MB VMEM of a v5e core, and all matmul dims are multiples of
the 128-lane MXU. Causal masking skips fully-masked KV tiles via pl.when
(no FLOPs spent above the diagonal). Optional sliding window.

Ragged (length-aware) prefill: ``seq_lens`` (B,) int32 rides in via
``PrefetchScalarGridSpec`` scalar prefetch, so the per-row prompt length is
known *before* each tile's DMA is issued. KV tiles that lie entirely in a
row's padding (``k_start >= seq_lens[b]``) are ``pl.when``-skipped — short
prompts in a shared bucket stop paying full-bucket FLOPs — and padded key
columns are masked. Skipping is bit-exact: a fully-padded tile contributes
exp(-inf) = 0 to the online softmax, i.e. a no-op. Query rows at or beyond
the row's length are zeroed in the output (their values are padding and
must not be consumed).

Numerics: scores/softmax in f32 (preferred_element_type), inputs bf16/f32.

Quantized K/V (DESIGN.md §14): pass ``k_scale``/``v_scale`` (B, Sk, KVH)
f32 alongside int8/fp8 ``k``/``v`` and the kernel dequantizes each tile
*after* the HBM->VMEM DMA — the bandwidth win is the point; scale tiles ride
their own (1, 1, block_k, 1) BlockSpecs over the head-major (B, KVH, Sk, 1)
scales. Dequant matches
``repro.kernels.quant.dequantize_kv`` exactly (f32 multiply, cast to the
query dtype) so the XLA fallback and the kernel agree bit-for-float. Note
the TPU int8 minimum tile is (32, 128): block_k stays >= 32 on hardware;
interpret mode has no such floor.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _dequant_tile(x, s_ref, dtype):
    """Per-token-per-head dequant of one (bk, hd) K/V tile; ``s_ref`` holds
    the tile's (1, 1, bk, 1) scale block."""
    return (x.astype(jnp.float32) * s_ref[0, 0]).astype(dtype)


def _attn_kernel(q_ref, k_ref, v_ref, *rest, scale, block_q, block_k, nk,
                 causal, window, quantized=False):
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q
    k_start = ki * block_k

    # Tile is live unless it's entirely above the diagonal / outside window.
    live = True
    if causal:
        live = k_start <= q_start + block_q - 1
    if window is not None:
        live = jnp.logical_and(live, k_start + block_k - 1 > q_start - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0]                             # (bq, hd)
        k = k_ref[0, 0]                             # (bk, hd)
        v = v_ref[0, 0]
        if quantized:
            k = _dequant_tile(k, ks_ref, q.dtype)
            v = _dequant_tile(v, vs_ref, q.dtype)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                    # (bq, bk)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _attn_kernel_ragged(lens_ref, q_ref, k_ref, v_ref, *rest, scale, block_q,
                        block_k, nk, causal, window, quantized=False):
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    slen = lens_ref[b]

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q
    k_start = ki * block_k

    # Live unless entirely above the diagonal / outside window / entirely in
    # this row's padding (the length-aware skip — no FLOPs on padded tiles).
    live = k_start < slen
    if causal:
        live = jnp.logical_and(live, k_start <= q_start + block_q - 1)
    if window is not None:
        live = jnp.logical_and(live, k_start + block_k - 1 > q_start - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        if quantized:
            k = _dequant_tile(k, ks_ref, q.dtype)
            v = _dequant_tile(v, vs_ref, q.dtype)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = kpos < slen
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
        out = jnp.where(rows < slen, acc_ref[...] / l, 0.0)
        o_ref[0, 0] = out.astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,                 # (B, S, H, hd)
    k: jax.Array,                 # (B, S, KVH, hd) — int8/fp8 when scales given
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    seq_lens: jax.Array | None = None,   # (B,) int32 per-row real lengths
    k_scale: jax.Array | None = None,    # (B, S, KVH) f32 per-token-per-head
    v_scale: jax.Array | None = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    B, Sq, H, hd = q.shape
    _, Sk, KVH, _ = k.shape
    G = H // KVH
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0, (Sq, block_q, Sk, block_k)
    nq, nk = Sq // block_q, Sk // block_k
    scale = hd ** -0.5
    quantized = k_scale is not None
    assert quantized == (v_scale is not None), "k_scale/v_scale come in pairs"

    # head-major operands: each tile's last two dims are (block, hd)
    qt = q.transpose(0, 2, 1, 3)                 # (B, H, Sq, hd)
    kt = k.transpose(0, 2, 1, 3)                 # (B, KVH, Sk, hd)
    vt = v.transpose(0, 2, 1, 3)
    q_spec = pl.BlockSpec((1, 1, block_q, hd),
                          lambda b, h, qi, ki, *_: (b, h, qi, 0))
    kv_spec = pl.BlockSpec((1, 1, block_k, hd),
                           lambda b, h, qi, ki, *_: (b, h // G, ki, 0))
    in_specs = [q_spec, kv_spec, kv_spec]
    operands = [qt, kt, vt]
    if quantized:
        s_spec = pl.BlockSpec((1, 1, block_k, 1),
                              lambda b, h, qi, ki, *_: (b, h // G, ki, 0))
        in_specs += [s_spec, s_spec]
        operands += [s.transpose(0, 2, 1)[..., None]   # (B, KVH, Sk, 1)
                     for s in (k_scale, v_scale)]
    scratch = [
        pltpu.VMEM((block_q, 1), jnp.float32),      # m
        pltpu.VMEM((block_q, 1), jnp.float32),      # l
        pltpu.VMEM((block_q, hd), jnp.float32),     # acc
    ]
    out_shape = jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype)
    if seq_lens is not None:
        kernel = functools.partial(
            _attn_kernel_ragged, scale=scale, block_q=block_q,
            block_k=block_k, nk=nk, causal=causal, window=window,
            quantized=quantized,
        )
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H, nq, nk),
            in_specs=in_specs,
            out_specs=q_spec,
            scratch_shapes=scratch,
        )
        out = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=out_shape,
            interpret=interpret,
        )(seq_lens.astype(jnp.int32), *operands)
        return out.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _attn_kernel, scale=scale, block_q=block_q, block_k=block_k,
        nk=nk, causal=causal, window=window, quantized=quantized,
    )
    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=in_specs,
        out_specs=q_spec,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
    )(*operands)
    return out.transpose(0, 2, 1, 3)
