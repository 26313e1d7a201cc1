"""Pallas TPU chunked-prefill attention: a block of C prompt-chunk queries
per row against that row's KV cache.

This is the kernel behind continuous batching (engine ``step_slot_chunked``):
each admitted prompt enters the cache ``chunk_size`` tokens per control slot,
and the chunk's queries attend everything the row has written so far — the
earlier chunks (streamed from the cache) plus the chunk itself (already
written by the time the kernel runs). It reuses the ragged flash machinery
from the length-aware prefill kernel:

* **Scalar-prefetched chunk extents**: per-row ``pos0`` (the chunk's first
  absolute position) and ``valid`` (its real token count) ride in via
  ``PrefetchScalarGridSpec``, so KV tiles that lie entirely beyond the row's
  written prefix (``k_start > pos0[b] + valid[b] - 1``) are ``pl.when``-
  skipped before their DMA is issued. A row early in its prompt touches only
  the tiles it has filled — chunk cost grows with progress, not cache_len.
  The skip is bit-exact: a chunk row's cache is position-ordered (slot j
  holds absolute position j or is invalid; chunked prefill never wraps), so
  skipped tiles hold only masked keys, i.e. exact zeros in the softmax.
* **Slot-validity masking** (as in the decode kernel): ``slot_pos`` tiles
  stream alongside K/V and mask empty (-1) and future (> qpos) slots, so
  intra-chunk causality and the prior-chunk prefix share one mask.
* **Grid (B, H, nk)**: the whole chunk is one Q tile (C is small — 16..128);
  the KV axis is innermost/sequential so the online-softmax state (m, l,
  acc) lives in VMEM scratch across the cache sweep.
* **Head-major tiles**: the wrapper moves heads ahead of the sequence axis
  ((B, L, KVH, hd) -> (B, KVH, L, hd)) and gives ``slot_pos`` a unit axis
  ((B, 1, L)), so every tile's last two dims are (rows, hd) or (1, block_l)
  — the layouts Mosaic can tile.

Query rows at or beyond ``valid`` are zeroed in the output (they are
padding; the engine discards them). The pure-jnp oracle is
``repro.kernels.ref.chunk_attention_ref``.

Quantized K/V (DESIGN.md §14): ``k_scale``/``v_scale`` (B, L, KVH) f32
stream as their own (1, 1, block_l, 1) tiles of the head-major
(B, KVH, L, 1) scales, and each K/V tile is dequantized in-kernel right
after its DMA — same f32-multiply-then-cast as
``repro.kernels.quant.dequantize_kv``, so XLA fallback and kernel agree.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _chunk_kernel(pos0_ref, valid_ref, q_ref, k_ref, v_ref, sp_ref, *rest,
                  scale, C, block_l, nk, quantized=False):
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    ki = pl.program_id(2)
    p0 = pos0_ref[b]
    nv = valid_ref[b]

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    k_start = ki * block_l
    # Live unless entirely beyond the row's written prefix (position-ordered
    # cache: nothing at slot > last written position can be valid).
    live = jnp.logical_and(nv > 0, k_start <= p0 + nv - 1)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0]                              # (C, hd)
        k = k_ref[0, 0]                              # (bl, hd)
        v = v_ref[0, 0]
        if quantized:
            k = (k.astype(jnp.float32) * ks_ref[0, 0]).astype(q.dtype)
            v = (v.astype(jnp.float32) * vs_ref[0, 0]).astype(q.dtype)
        sp = sp_ref[0, 0]                            # (bl,) slot_pos
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                    # (C, bl)
        qpos = p0 + jax.lax.broadcasted_iota(jnp.int32, (C, block_l), 0)
        spb = jnp.broadcast_to(sp[None, :], (C, block_l))
        mask = (spb >= 0) & (spb <= qpos)
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
        rows = jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0)
        out = jnp.where(rows < nv, acc_ref[...] / l, 0.0)
        o_ref[0, 0] = out.astype(o_ref.dtype)


def chunk_attention(
    q: jax.Array,          # (B, C, H, hd) — the chunk's queries, already roped
    k: jax.Array,          # (B, L, KVH, hd) — the row's KV cache (chunk written)
    v: jax.Array,
    slot_pos: jax.Array,   # (B, L) int32 absolute position per slot; -1 empty
    pos0: jax.Array,       # (B,) int32 absolute position of the chunk's first token
    valid: jax.Array,      # (B,) int32 real tokens in the chunk (0 = inactive row)
    *,
    k_scale: jax.Array | None = None,   # (B, L, KVH) f32: k/v are int8/fp8
    v_scale: jax.Array | None = None,
    block_l: int = 512,
    interpret: bool = False,
) -> jax.Array:
    B, C, H, hd = q.shape
    _, L, KVH, _ = k.shape
    G = H // KVH
    block_l = min(block_l, L)
    assert L % block_l == 0, (L, block_l)
    nk = L // block_l
    scale = hd ** -0.5
    quantized = k_scale is not None
    assert quantized == (v_scale is not None), "k_scale/v_scale come in pairs"

    kernel = functools.partial(
        _chunk_kernel, scale=scale, C=C, block_l=block_l, nk=nk,
        quantized=quantized,
    )
    q_spec = pl.BlockSpec((1, 1, C, hd), lambda b, h, ki, p0, nv: (b, h, 0, 0))
    kv_spec = pl.BlockSpec((1, 1, block_l, hd),
                           lambda b, h, ki, p0, nv: (b, h // G, ki, 0))
    in_specs = [
        q_spec, kv_spec, kv_spec,
        pl.BlockSpec((1, 1, block_l), lambda b, h, ki, p0, nv: (b, 0, ki)),
    ]
    # head-major operands (see module docstring)
    operands = [pos0.astype(jnp.int32), valid.astype(jnp.int32),
                q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                v.transpose(0, 2, 1, 3), slot_pos[:, None, :]]
    if quantized:
        s_spec = pl.BlockSpec((1, 1, block_l, 1),
                              lambda b, h, ki, p0, nv: (b, h // G, ki, 0))
        in_specs += [s_spec, s_spec]
        operands += [s.transpose(0, 2, 1)[..., None]   # (B, KVH, L, 1)
                     for s in (k_scale, v_scale)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, H, nk),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((C, 1), jnp.float32),     # m
            pltpu.VMEM((C, 1), jnp.float32),     # l
            pltpu.VMEM((C, hd), jnp.float32),    # acc
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, C, hd), q.dtype),
        interpret=interpret,
    )(*operands)
    return out.transpose(0, 2, 1, 3)
