"""Symmetric per-token-per-head KV quantization (DESIGN.md §14).

The storage format every quantized path shares — XLA cache ops in
``models/attention.py``, the dequantizing Pallas kernels, and the
oracles in ``kernels/ref.py``:

  * scales are per *token* per *KV head* over the head dim (one f32 per
    cached row per head). Per-token granularity is what makes the cache
    append-only under quantization: a new token can never force
    retired rows to requantize, so COW pages stay immutable and
    prefix-shared pages stay bit-stable — the invariants the paged
    allocator is built on. ("Quantize on chunk retirement" is therefore
    identical to quantize-on-write: each token's row is final the
    moment it is written.)
  * int8: ``scale = amax / 127``, value = round(x / scale) clipped to
    [-127, 127]; fp8 (e4m3): ``scale = amax / 448``, value = cast.
  * dequant = ``q.astype(f32) * scale`` then cast to the compute dtype.

Rounding is deterministic (jnp.round, round-half-to-even): every step
mode that writes the same native values produces bit-identical
quantized pages, which is what lets the differential harness hold
quantized engines to ``Exact()`` *across modes* (divergence appears
only against a native-precision engine).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.cache.precision import KVPrecision

__all__ = ["qdtype_of", "quantize_kv", "dequantize_kv"]

_EPS = 1e-8  # amax floor: all-zero rows quantize to zeros, scale stays finite


def qdtype_of(prec: KVPrecision):
    """Resolve the spec's storage dtype to a jnp dtype (an unknown name
    raises — no silent fallback)."""
    if not hasattr(jnp, prec.dtype):
        raise ValueError(
            f"kv_precision dtype {prec.dtype!r} is not a jax.numpy dtype")
    return jnp.dtype(getattr(jnp, prec.dtype))


def quantize_kv(x: jnp.ndarray, prec: KVPrecision):
    """Quantize K or V rows ``x (..., head_dim)`` -> ``(q, scale)`` with
    ``q`` in the storage dtype and ``scale (...,)`` float32."""
    qdt = qdtype_of(prec)
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.maximum(amax, _EPS) / prec.qmax
    scaled = xf / scale[..., None]
    if prec.dtype == "int8":
        q = jnp.clip(jnp.round(scaled), -prec.qmax, prec.qmax).astype(qdt)
    else:  # fp8: the cast itself rounds; scaling keeps amax inside range
        q = scaled.astype(qdt)
    return q, scale


def dequantize_kv(q: jnp.ndarray, scale: jnp.ndarray, dtype) -> jnp.ndarray:
    """Dequantize ``q (..., head_dim)`` with ``scale (...,)`` -> dtype."""
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)
