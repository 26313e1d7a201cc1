"""The plain reference's toolkit: float32 jax.numpy, imported from nothing of
the program.

Each architecture module (``arch/<arch>.py``) builds its block's reference,
``logits_at``, from these: it reads the configuration file and the
benchmark's own weights (``weights.make_flat``), upcasts each layer's weights
to float32 as it runs it, and computes one sequence at a time, layer by
layer, so it fits beside the served model's weights. Every matmul runs at
``Precision.HIGHEST`` (``HI``, ``_mm``).

``int8=True`` is the control: every projection and the logits computed from
int8 operands (weights per output channel, activations per token,
symmetric), as an int8 serving path would (``_q8``, ``_mm``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def _q8(x, axis):
    """Symmetric int8 rounding along ``axis`` (the scale's reduction axis)."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s > 0, s, 1.0)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _mm(x, w, int8: bool):
    """x (S, K) @ w (K, N) in float32, or from int8 operands."""
    if int8:
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.dot(x, w, precision=HI)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """x (S, H, hd), pos (S,)."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def served_gaps(ref_logits: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """Per position: how far the reference's logit of the served token lies
    below the reference's best logit there (0 where they agree)."""
    picked = ref_logits[np.arange(len(tokens)), tokens]
    return ref_logits.max(axis=-1) - picked
