"""The plain reference: the dense Granite-style decoder in float32 jax.numpy.

It imports nothing of the program. It reads the configuration file and the
benchmark's own weights (``weights.make_flat``), upcasts each layer's weights
to float32 as it runs it, and computes one sequence at a time, layer by
layer, so it fits beside the served model's weights. Every matmul runs at
``Precision.HIGHEST``.

The block follows the configuration file as run (see ``model_cfg``):
pre-norm RMSNorm, rotary embedding on the two halves of each head,
grouped-query causal attention scaled by ``attention_multiplier``, SwiGLU
MLP, residual scaled by ``residual_multiplier``, final RMSNorm, logits from
the tied embedding divided by ``logits_scaling``, and the embedding scaled
by ``embedding_multiplier``.

``int8=True`` is the control: every projection and the logits computed from
int8 operands (weights per output channel, activations per token,
symmetric), as an int8 serving path would.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from model_cfg import head_dim

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


@partial(jax.jit, static_argnames=("dims", "int8"))
def _layer(x, w: dict, layer, *, dims: tuple, int8: bool):
    """One decoder block over a whole sequence x (S, D)."""
    D, H, KVH, hd, F, theta, eps, att, res = dims
    S = x.shape[0]
    lw = {k: jax.lax.dynamic_index_in_dim(v, layer, 0, keepdims=False).astype(jnp.float32)
          for k, v in w.items()}
    pos = jnp.arange(S)
    h = _rms(x, lw["ln1/scale"], eps)
    q = _mm(h, lw["attn/wq"].reshape(D, H * hd), int8).reshape(S, H, hd)
    k = _mm(h, lw["attn/wk"].reshape(D, KVH * hd), int8).reshape(S, KVH, hd)
    v = _mm(h, lw["attn/wv"].reshape(D, KVH * hd), int8).reshape(S, KVH, hd)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    G = H // KVH
    qg = q.reshape(S, KVH, G, hd)
    s = jnp.einsum("qkgh,skh->kgqs", qg, k, precision=HI) * att
    causal = pos[None, :] <= pos[:, None]
    s = jnp.where(causal[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("kgqs,skh->qkgh", p, v, precision=HI).reshape(S, H * hd)
    x = x + res * _mm(o, lw["attn/wo"].reshape(H * hd, D), int8)
    h = _rms(x, lw["ln2/scale"], eps)
    g = _mm(h, lw["mlp/w_gate"], int8)
    u = _mm(h, lw["mlp/w_up"], int8)
    return x + res * _mm(jax.nn.silu(g) * u, lw["mlp/w_down"], int8)


@partial(jax.jit, static_argnames=("emb_mult",))
def _embed(tok, ids, *, emb_mult: float):
    return tok[ids].astype(jnp.float32) * emb_mult


@partial(jax.jit, static_argnames=("eps", "logit_scale", "int8"))
def _logits(x, rows, ln_f, tok, *, eps: float, logit_scale: float, int8: bool):
    h = _rms(x[rows], ln_f, eps)
    return _mm(h, tok.astype(jnp.float32).T, int8) / logit_scale


_LAYER_KEYS = ("ln1/scale", "ln2/scale", "attn/wq", "attn/wk", "attn/wv",
               "attn/wo", "mlp/w_gate", "mlp/w_up", "mlp/w_down")


def logits_at(spec: dict, flat: dict, ids: np.ndarray, rows: np.ndarray,
              pad_to: int, rows_pad: int, int8: bool = False) -> np.ndarray:
    """Logits (len(rows), V) of one sequence ``ids`` at positions ``rows``.
    The sequence is padded to ``pad_to`` tokens and the rows to ``rows_pad``
    so every call shares one program; causal masking keeps the padding out
    of every real position."""
    S, n = len(ids), len(rows)
    if S > pad_to or n > rows_pad:
        raise ValueError(f"{S} tokens / {n} rows exceed the pads "
                         f"{pad_to} / {rows_pad}")
    rows = np.concatenate([np.asarray(rows, np.int32),
                           np.zeros(rows_pad - n, np.int32)])
    dims = (spec["hidden_size"], spec["num_attention_heads"],
            spec["num_key_value_heads"], head_dim(spec),
            spec["intermediate_size"], float(spec["rope_theta"]),
            float(spec["rms_norm_eps"]), float(spec["attention_multiplier"]),
            float(spec["residual_multiplier"]))
    padded = np.zeros(pad_to, np.int32)
    padded[:S] = ids
    x = _embed(flat["embed/tok"], jnp.asarray(padded),
               emb_mult=float(spec["embedding_multiplier"]))
    w = {k: flat[k] for k in _LAYER_KEYS}
    for layer in range(spec["num_hidden_layers"]):
        x = _layer(x, w, jnp.int32(layer), dims=dims, int8=int8)
    out = _logits(x, jnp.asarray(rows, jnp.int32), flat["ln_f/scale"],
                  flat["embed/tok"], eps=float(spec["rms_norm_eps"]),
                  logit_scale=float(spec["logits_scaling"]), int8=int8)
    return np.asarray(out)[:n]


def served_gaps(ref_logits: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """Per position: how far the reference's logit of the served token lies
    below the reference's best logit there (0 where they agree)."""
    picked = ref_logits[np.arange(len(tokens)), tokens]
    return ref_logits.max(axis=-1) - picked
