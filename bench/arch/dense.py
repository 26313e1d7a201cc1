"""The dense Granite-style decoder: every layer grouped-query attention with
rotary positions and a SwiGLU MLP, pre-norm RMSNorm, a tied embedding.

The architecture contract of ``model_cfg``: the program's ``ModelConfig``,
the weights in the layout of the program's dense stack (``init_params``),
the plain float32 reference with its int8 control, and the work counts.

The block follows the configuration file as run: pre-norm RMSNorm, rotary
embedding on the two halves of each head, grouped-query causal attention
scaled by ``attention_multiplier``, SwiGLU MLP, residual scaled by
``residual_multiplier``, final RMSNorm, logits from the tied embedding
divided by ``logits_scaling``, and the embedding scaled by
``embedding_multiplier``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from counts import ITEMSIZE, Work
from reference import HI, _mm, _rms, _rope


def head_dim(spec: dict) -> int:
    return spec.get("head_dim") or spec["hidden_size"] // spec["num_attention_heads"]


# ------------------------------------------------------------- the program
def check_departures(spec: dict) -> None:
    """The program's dense block has no Granite multipliers: it scales the
    embedding by sqrt(hidden), the scores by head_dim^-0.5, and leaves the
    residual and the logits unscaled. The file must say so."""
    d, hd = spec["hidden_size"], head_dim(spec)
    want = {"embedding_multiplier": d ** 0.5, "attention_multiplier": hd ** -0.5,
            "residual_multiplier": 1.0, "logits_scaling": 1.0}
    for key, value in want.items():
        if abs(spec[key] - value) > 1e-9 * max(1.0, value):
            raise ValueError(f"{spec['name']}: {key} {spec[key]} is not what the "
                             f"program runs ({value})")


def model_config(spec: dict, dtype: str | None = None):
    """The program's ModelConfig for a configuration file. ``dtype``
    overrides the served type (tests run small float32 copies)."""
    from repro.configs.base import ModelConfig

    check_departures(spec)
    return ModelConfig(
        name=spec["name"], arch_type="dense",
        n_layers=spec["num_hidden_layers"], d_model=spec["hidden_size"],
        n_heads=spec["num_attention_heads"],
        n_kv_heads=spec["num_key_value_heads"],
        d_ff=spec["intermediate_size"], vocab_size=spec["vocab_size"],
        head_dim=spec.get("head_dim"), rope_theta=float(spec["rope_theta"]),
        norm_eps=float(spec["rms_norm_eps"]),
        tie_embeddings=bool(spec["tie_word_embeddings"]),
        act=spec["hidden_act"], dtype=dtype or spec["torch_dtype"])


# ----------------------------------------------------------------- weights
def shapes(spec: dict) -> dict:
    """{path: shape} of every weight, in the program's layout: the tied
    embedding, one layer-stacked segment of attention + gated MLP blocks,
    and the final norm."""
    L, D = spec["num_hidden_layers"], spec["hidden_size"]
    H, KVH, hd = spec["num_attention_heads"], spec["num_key_value_heads"], head_dim(spec)
    F, V = spec["intermediate_size"], spec["vocab_size"]
    return {
        "embed/tok": (V, D),
        "ln1/scale": (L, D), "ln2/scale": (L, D), "ln_f/scale": (D,),
        "attn/wq": (L, D, H, hd), "attn/wk": (L, D, KVH, hd),
        "attn/wv": (L, D, KVH, hd), "attn/wo": (L, H, hd, D),
        "mlp/w_gate": (L, D, F), "mlp/w_up": (L, D, F), "mlp/w_down": (L, F, D),
    }


# Standard deviation of each weight: fan-in scaling for the projections
# (what the program's own init uses) and norm scales drawn around 1, so the
# reference has to apply them. The tied embedding is drawn at 0.002: at the
# program's init of 0.02, the input embedding times sqrt(hidden) outweighs
# every block's output in the residual stream, so the model only copies its
# input token, with a top-1 margin so wide that no arithmetic error could
# ever change a greedy token and the comparison could not tell bfloat16 from
# int8.
EMBED_STD = 0.002


def _scale(path: str, spec: dict) -> float:
    D, F, hd = spec["hidden_size"], spec["intermediate_size"], head_dim(spec)
    H = spec["num_attention_heads"]
    return {"embed/tok": EMBED_STD, "attn/wo": (H * hd) ** -0.5,
            "mlp/w_down": F ** -0.5}.get(path, D ** -0.5)


def draw(spec: dict, path: str) -> tuple:
    """(mean, std, float32): norm scales 1 + 0.1 N(0, 1) in float32 (the
    program's norms are float32), the rest ``_scale`` N(0, 1) served."""
    if path.endswith("/scale"):
        return 1.0, 0.1, True
    return 0.0, _scale(path, spec), False


def to_program(flat: dict) -> dict:
    """The program's params tree over the same arrays (no copies)."""
    seg = {
        "ln1": {"scale": flat["ln1/scale"]},
        "attn": {k: flat[f"attn/{k}"] for k in ("wq", "wk", "wv", "wo")},
        "ln2": {"scale": flat["ln2/scale"]},
        "mlp": {k: flat[f"mlp/{k}"] for k in ("w_gate", "w_up", "w_down")},
    }
    return {"embed": {"tok": flat["embed/tok"]}, "stack": [seg],
            "ln_f": {"scale": flat["ln_f/scale"]}}


# --------------------------------------------------------------- reference
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


# ------------------------------------------------------------------ counts
def token_flops(spec: dict) -> float:
    """Projection + MLP FLOPs of one token through every layer."""
    D, H, KVH, hd = (spec["hidden_size"], spec["num_attention_heads"],
                     spec["num_key_value_heads"], head_dim(spec))
    F, L = spec["intermediate_size"], spec["num_hidden_layers"]
    return 2.0 * L * (D * H * hd + 2 * D * KVH * hd + H * hd * D + 3 * D * F)


def attn_flops(spec: dict, keys: int) -> float:
    """QK^T and PV of one query over ``keys`` live keys, every layer."""
    return 4.0 * spec["num_hidden_layers"] * spec["num_attention_heads"] \
        * head_dim(spec) * keys


def logits_flops(spec: dict) -> float:
    return 2.0 * spec["hidden_size"] * spec["vocab_size"]


def weight_bytes(spec: dict) -> float:
    """Every weight once, at the served type (norm scales float32)."""
    D, H, KVH, hd = (spec["hidden_size"], spec["num_attention_heads"],
                     spec["num_key_value_heads"], head_dim(spec))
    F, L, V = spec["intermediate_size"], spec["num_hidden_layers"], spec["vocab_size"]
    n = V * D + L * (D * H * hd + 2 * D * KVH * hd + H * hd * D + 3 * D * F)
    return n * ITEMSIZE[spec["torch_dtype"]] + (2 * L + 1) * D * 4


def kv_token_bytes(spec: dict) -> float:
    """Logical K and V of one token, every layer."""
    return (2.0 * spec["num_hidden_layers"] * spec["num_key_value_heads"]
            * head_dim(spec) * ITEMSIZE[spec["torch_dtype"]])


def chunk_work(spec: dict, chunks: list) -> Work:
    """One dispatch's prompt-chunk part. ``chunks``: (pos0, take, last)
    per row — ``take`` prompt tokens written from ``pos0``, ``last`` when
    the chunk ends the prompt (its last token's logits are needed)."""
    if not chunks:
        return Work()
    w = Work(bytes=weight_bytes(spec))
    kvb = kv_token_bytes(spec)
    for pos0, take, last in chunks:
        # query at position p attends p + 1 keys: sum over the chunk
        keys = take * pos0 + take * (take + 1) / 2
        w.flops += take * token_flops(spec) + attn_flops(spec, keys)
        w.flops += logits_flops(spec) if last else 0.0
        w.bytes += kvb * (pos0 + take)     # context read once, chunk written
    return w


def decode_work(spec: dict, contexts: list) -> Work:
    """One decode step over the live rows; ``contexts`` holds each row's
    key count including the new token."""
    if not contexts:
        return Work()
    kvb = kv_token_bytes(spec)
    n = len(contexts)
    return Work(
        flops=n * (token_flops(spec) + logits_flops(spec))
        + sum(attn_flops(spec, c) for c in contexts),
        bytes=weight_bytes(spec) + kvb * sum(contexts))
