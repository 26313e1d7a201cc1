"""Jit'd public wrappers around the Pallas kernels.

``impl`` selects the execution path:
  "pallas"     — the compiled Mosaic TPU kernel. flash, chunk, decode and
                 paged decode (native and int8) compile for a TPU v5e at
                 granite-3-2b's head widths (tests/test_tpu_compile.py) and
                 match their oracles on the chip (chip_smoke.py). ssd does
                 not compile: Mosaic has no lowering for its cumsum.
  "interpret"  — Pallas interpret mode (kernel body run op by op), what the
                 CPU tests check against ``kernels/ref.py``
  "xla"        — a pure-XLA path with identical semantics; the default

The served path does not call these wrappers: ``models/attention.py`` runs
its own XLA attention on every backend.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import chunk_attention as _ca
from repro.kernels import decode_attention as _da
from repro.kernels import flash_attention as _fa
from repro.kernels import paged_attention as _pa
from repro.kernels import ssd_scan as _ssd
from repro.kernels.quant import dequantize_kv
from repro.models.attention import chunked_attention
from repro.models.ssm import ssd_chunked


@partial(jax.jit, static_argnames=("causal", "window", "impl", "block_q", "block_k"))
def flash_attention(q, k, v, seq_lens=None, *, k_scale=None, v_scale=None,
                    causal=True, window=None,
                    impl="xla", block_q=512, block_k=512):
    """seq_lens (B,) int32 selects the ragged length-aware path: padded keys
    are masked, padded query rows zeroed, and the Pallas kernel skips KV
    tiles that lie entirely in a row's padding (scalar-prefetched lengths).

    k_scale/v_scale (B, S, KVH) f32 select the quantized path: k/v hold
    int8/fp8 codes and the kernel dequantizes per tile in VMEM; the XLA
    fallback dequantizes eagerly with identical arithmetic."""
    if impl == "xla":
        if k_scale is not None:
            k = dequantize_kv(k, k_scale, q.dtype)
            v = dequantize_kv(v, v_scale, q.dtype)
        if seq_lens is not None and not causal:
            from repro.kernels.ref import attention_ref

            return attention_ref(q, k, v, causal=False, window=window,
                                 seq_lens=seq_lens)
        out = chunked_attention(q, k, v, causal=causal, window=window,
                                block_q=block_q, block_k=block_k)
        if seq_lens is not None:
            # pads never leak into real rows under a causal mask (they sit at
            # the end); zero the pad rows to match the kernel's output.
            pos = jnp.arange(q.shape[1])[None, :, None, None]
            out = jnp.where(pos < seq_lens[:, None, None, None], out, 0)
        return out
    return _fa.flash_attention(
        q, k, v, causal=causal, window=window, seq_lens=seq_lens,
        k_scale=k_scale, v_scale=v_scale,
        block_q=block_q, block_k=block_k, interpret=(impl == "interpret"),
    )


@partial(jax.jit, static_argnames=("window", "impl", "block_l"))
def decode_attention(q, k, v, slot_pos, pos, *, window=None, impl="xla", block_l=512):
    if impl == "xla":
        from repro.kernels.ref import decode_attention_ref

        return decode_attention_ref(q, k, v, slot_pos, pos, window=window)
    return _da.decode_attention(
        q, k, v, slot_pos, pos, window=window, block_l=block_l,
        interpret=(impl == "interpret"),
    )


@partial(jax.jit, static_argnames=("impl", "block_l"))
def chunk_attention(q, k, v, slot_pos, pos0, valid, *, k_scale=None,
                    v_scale=None, impl="xla", block_l=512):
    """Chunked-prefill attention (continuous batching): per-row chunk
    queries at offsets pos0 over the row's KV cache. The Pallas path skips
    KV tiles beyond each row's written prefix via scalar-prefetched
    (pos0, valid). k_scale/v_scale (B, L, KVH) f32 select the quantized
    cache path (in-kernel dequant)."""
    if impl == "xla":
        from repro.kernels.ref import chunk_attention_ref

        if k_scale is not None:
            k = dequantize_kv(k, k_scale, q.dtype)
            v = dequantize_kv(v, v_scale, q.dtype)
        return chunk_attention_ref(q, k, v, slot_pos, pos0, valid)
    return _ca.chunk_attention(
        q, k, v, slot_pos, pos0, valid, k_scale=k_scale, v_scale=v_scale,
        block_l=block_l, interpret=(impl == "interpret"),
    )


@partial(jax.jit, static_argnames=("impl",))
def paged_decode_attention(q, k_pages, v_pages, block_tables, pos, *,
                           k_scale=None, v_scale=None, impl="xla"):
    """k_scale/v_scale (N, ps, KVH) f32 select the quantized-pool path: the
    kernel gathers scale pages by the same block-table indirection as K/V
    and dequantizes in VMEM right after each page's DMA."""
    if impl == "xla":
        from repro.kernels.ref import paged_decode_attention_ref

        if k_scale is not None:
            k_pages = dequantize_kv(k_pages, k_scale, q.dtype)
            v_pages = dequantize_kv(v_pages, v_scale, q.dtype)
        return paged_decode_attention_ref(q, k_pages, v_pages, block_tables, pos)
    return _pa.paged_decode_attention(
        q, k_pages, v_pages, block_tables, pos, k_scale=k_scale,
        v_scale=v_scale, interpret=(impl == "interpret")
    )


@partial(jax.jit, static_argnames=("chunk", "impl"))
def ssd(x, dt, A, Bm, Cm, *, chunk=128, impl="xla"):
    if impl == "xla":
        y, st = ssd_chunked(x, dt, A, Bm.astype(jnp.float32), Cm.astype(jnp.float32), chunk)
        return y, st
    return _ssd.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, interpret=(impl == "interpret"))
