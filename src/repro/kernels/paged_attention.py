"""Pallas TPU paged decode-attention: gather K/V by block table in-kernel.

The paged KV cache stores rows in a shared pool of fixed-size pages
(``repro.cache``); a request's cache is the sequence of pages named by its
block table. Decode attention must therefore gather pages — and the whole
point of the kernel is that the gather happens *inside* the DMA schedule,
not as a materialized (B, L, KVH, hd) copy in HBM:

* **Scalar-prefetched block tables**: ``block_tables`` (and ``pos``) arrive
  via ``PrefetchScalarGridSpec``, so each K/V tile's ``index_map`` reads the
  physical page id for grid step (b, kv, p) *before* the DMA is issued —
  the pool page streams HBM->VMEM directly, exactly like the dense kernel
  streams contiguous tiles. Unallocated logical pages (table entry -1)
  clamp to page 0 and are masked in-kernel.
* **Whole-page tiles**: grid (B, MP); one K/V tile is a full pool page
  (page_size, KVH, hd) and the query tile holds all H heads packed as
  (KVH, G, hd). The pool is read in place, in its engine layout: a tile
  that spans the KV-head axis is the one Mosaic can tile without a
  head-major copy of the pool (a one-head (page_size, 1, hd) slice is
  refused). A static loop over the KVH heads attends each head's (G, hd)
  queries to its (page_size, hd) keys — the GQA packing of
  ``decode_attention``, so each page streams HBM->VMEM once.
* **Sequential innermost page axis**: online-softmax state (m, l, acc) per
  KV head persists in VMEM scratch across the MP pages of one row.

Validity of row i of logical page p is ``p * page_size + i <= pos[b]``
(logical slot j holds absolute position j — paged caches never wrap; they
grow by appending pages) AND the page is allocated. The pure-jnp oracle is
``repro.kernels.ref.paged_decode_attention_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(bt_ref, pos_ref, q_ref, k_ref, v_ref, *rest,
                  scale, page_size, num_pages_per_req, kv_heads,
                  quantized=False):
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos = pos_ref[b]                                    # scalar int32
    allocated = bt_ref[b, p] >= 0
    logical = p * page_size + jax.lax.iota(jnp.int32, page_size)
    valid = allocated & (logical <= pos)
    for kv in range(kv_heads):
        q = q_ref[0, kv]                                # (G, hd)
        k = k_ref[0, :, kv, :]                          # (ps, hd)
        v = v_ref[0, :, kv, :]
        if quantized:
            # per-token-per-head dequant of the gathered page, right after
            # its DMA — matches repro.kernels.quant.dequantize_kv (f32 mul,
            # cast)
            k = (k.astype(jnp.float32)
                 * ks_ref[0, :, kv][:, None]).astype(q.dtype)
            v = (v.astype(jnp.float32)
                 * vs_ref[0, :, kv][:, None]).astype(q.dtype)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                       # (G, ps)
        s = jnp.where(valid[None, :], s, NEG_INF)
        m_prev, l_prev = m_ref[kv], l_ref[kv]           # (G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        pexp = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[kv] = l_prev * alpha + jnp.sum(pexp, axis=-1, keepdims=True)
        m_ref[kv] = m_new
        acc_ref[kv] = acc_ref[kv] * alpha + jax.lax.dot_general(
            pexp.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                               # (G, hd)

    @pl.when(p == num_pages_per_req - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_decode_attention(
    q: jax.Array,             # (B, H, hd) — already roped
    k_pages: jax.Array,       # (N, page_size, KVH, hd) shared pool
    v_pages: jax.Array,
    block_tables: jax.Array,  # (B, MP) int32 physical page ids; -1 = unallocated
    pos: jax.Array,           # (B,) int32 absolute position just written
    *,
    k_scale: jax.Array | None = None,  # (N, ps, KVH) f32: pools are int8/fp8
    v_scale: jax.Array | None = None,
    interpret: bool = False,
) -> jax.Array:
    B, H, hd = q.shape
    N, page_size, KVH, _ = k_pages.shape
    MP = block_tables.shape[1]
    G = H // KVH
    scale = hd ** -0.5
    qg = q.reshape(B, KVH, G, hd)
    quantized = k_scale is not None
    assert quantized == (v_scale is not None), "k_scale/v_scale come in pairs"

    kernel = functools.partial(
        _paged_kernel, scale=scale, page_size=page_size,
        num_pages_per_req=MP, kv_heads=KVH, quantized=quantized,
    )

    def page_map(b, p, bt_ref, pos_ref):
        # clamp -1 (unallocated) to 0: the tile is DMA'd but masked in-kernel
        return (jnp.maximum(bt_ref[b, p], 0), 0, 0, 0)

    def scale_map(b, p, bt_ref, pos_ref):
        return (jnp.maximum(bt_ref[b, p], 0), 0, 0)

    q_spec = pl.BlockSpec((1, KVH, G, hd), lambda b, p, bt, ps_: (b, 0, 0, 0))
    in_specs = [
        q_spec,
        pl.BlockSpec((1, page_size, KVH, hd), page_map),
        pl.BlockSpec((1, page_size, KVH, hd), page_map),
    ]
    operands = [block_tables, pos, qg, k_pages, v_pages]
    if quantized:
        # scale pools (N, ps, KVH) gather by the same block-table indirection
        in_specs += [
            pl.BlockSpec((1, page_size, KVH), scale_map),
            pl.BlockSpec((1, page_size, KVH), scale_map),
        ]
        operands += [k_scale, v_scale]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, MP),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((KVH, G, 1), jnp.float32),
            pltpu.VMEM((KVH, G, 1), jnp.float32),
            pltpu.VMEM((KVH, G, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KVH, G, hd), q.dtype),
        interpret=interpret,
    )(*operands)
    return out.reshape(B, H, hd)
