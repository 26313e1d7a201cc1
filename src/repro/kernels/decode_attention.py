"""Pallas TPU flash-decode: one-token attention against a (ring) KV cache.

Decode attention is memory-bound (the whole cache streams HBM->VMEM once per
token); the kernel's job is to stream K/V tiles at full bandwidth while the
small matmuls ride along. Two design points make it TPU-native:

* **GQA group packing**: the grid iterates (B, KVH, nl) and each tile holds
  ALL G = H/KVH query heads of one KV head as a (G, hd) block — the cache
  is streamed once per KV head, not once per query head: a G× cut of the
  dominant HBM term (e.g. 4× for the 32/8-head dense archs). The (G, bl)
  score matmul still feeds the MXU.
* **Sequential innermost cache axis**: online-softmax state (m, l, acc)
  persists in VMEM scratch across cache tiles of one (batch, kv-head).

Slot validity (ring buffer: absolute position in slot_pos, -1 = empty,
optional sliding window) is evaluated per tile. The wrapper makes the cache
head-major ((B, L, KVH, hd) -> (B, KVH, L, hd)) and gives ``slot_pos`` a
unit axis, so every tile's last two dims are ones Mosaic can tile. Tiles:
k/v (1, 1, block_l, hd) VMEM; slot_pos (1, 1, block_l); q (1, 1, G, hd);
``pos`` is scalar-prefetched; out written at the last cache tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, sp_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, scale, block_l, nl, window):
    b = pl.program_id(0)
    li = pl.program_id(2)

    @pl.when(li == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0, :, :]                               # (G, hd)
    k = k_ref[0, 0]                                     # (bl, hd)
    v = v_ref[0, 0]
    sp = sp_ref[0, 0]                                   # (bl,) int32 abs pos
    pos = pos_ref[b]                                    # scalar int32

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale                                           # (G, bl)
    valid = (sp >= 0) & (sp <= pos)
    if window is not None:
        valid &= sp > pos - window
    s = jnp.where(valid[None, :], s, NEG_INF)

    m_prev, l_prev = m_ref[...], l_ref[...]             # (G, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    m_ref[...] = m_new
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                   # (G, hd)

    @pl.when(li == nl - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0, :, :] = (acc_ref[...] / l).astype(o_ref.dtype)


def decode_attention(
    q: jax.Array,          # (B, H, hd) — already roped; H = KVH * G grouped
    k: jax.Array,          # (B, L, KVH, hd) cache
    v: jax.Array,
    slot_pos: jax.Array,   # (B, L) int32
    pos: jax.Array,        # (B,) int32 current position
    *,
    window: int | None = None,
    block_l: int = 512,
    interpret: bool = False,
) -> jax.Array:
    B, H, hd = q.shape
    _, L, KVH, _ = k.shape
    G = H // KVH
    block_l = min(block_l, L)
    assert L % block_l == 0, (L, block_l)
    nl = L // block_l
    scale = hd ** -0.5
    qg = q.reshape(B, KVH, G, hd)

    kernel = functools.partial(
        _decode_kernel, scale=scale, block_l=block_l, nl=nl, window=window
    )
    q_spec = pl.BlockSpec((1, 1, G, hd), lambda b, kv, li, pos: (b, kv, 0, 0))
    kv_spec = pl.BlockSpec((1, 1, block_l, hd),
                           lambda b, kv, li, pos: (b, kv, li, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, KVH, nl),
        in_specs=[
            q_spec, kv_spec, kv_spec,
            pl.BlockSpec((1, 1, block_l), lambda b, kv, li, pos: (b, 0, li)),
        ],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KVH, G, hd), q.dtype),
        interpret=interpret,
    )(pos.astype(jnp.int32), qg, k.transpose(0, 2, 1, 3),
      v.transpose(0, 2, 1, 3), slot_pos[:, None, :])
    return out.reshape(B, H, hd)
