"""Test fixture: the dense block with an output head of its own,
``embed/head`` (hidden, vocab), in place of the tied embedding (the program
builds one where ``tie_word_embeddings`` is false).

It shows that an architecture brought as files alone reaches every part of
the benchmark: the program's config, the weights, the reference and the
counts. It is not a configuration of the benchmark.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

import model_cfg
from counts import ITEMSIZE
from reference import _mm, _rms

dense = model_cfg.load_arch("dense")
HEAD = "embed/head"


def model_config(spec: dict, dtype: str | None = None):
    if spec["tie_word_embeddings"]:
        raise ValueError(f"{spec['name']}: the head is not tied")
    return dense.model_config(spec, dtype)


def shapes(spec: dict) -> dict:
    return {**dense.shapes(spec), HEAD: (spec["hidden_size"], spec["vocab_size"])}


def draw(spec: dict, path: str) -> tuple:
    """The head at the tied embedding's scale, so its logits are as small."""
    if path == HEAD:
        return 0.0, dense.EMBED_STD, False
    return dense.draw(spec, path)


def to_program(flat: dict) -> dict:
    params = dense.to_program(flat)
    params["embed"]["head"] = flat[HEAD]
    return params


@partial(jax.jit, static_argnames=("eps", "int8"))
def _logits(x, rows, ln_f, head, *, eps: float, int8: bool):
    return _mm(_rms(x[rows], ln_f, eps), head.astype(jnp.float32), int8)


def logits_at(spec: dict, flat: dict, ids: np.ndarray, rows: np.ndarray,
              pad_to: int, rows_pad: int, int8: bool = False) -> np.ndarray:
    """``dense.logits_at`` with the logits read through the head."""
    S, n = len(ids), len(rows)
    if S > pad_to or n > rows_pad:
        raise ValueError(f"{S} tokens / {n} rows exceed the pads "
                         f"{pad_to} / {rows_pad}")
    rows = np.concatenate([np.asarray(rows, np.int32),
                           np.zeros(rows_pad - n, np.int32)])
    dims = (spec["hidden_size"], spec["num_attention_heads"],
            spec["num_key_value_heads"], dense.head_dim(spec),
            spec["intermediate_size"], float(spec["rope_theta"]),
            float(spec["rms_norm_eps"]), float(spec["attention_multiplier"]),
            float(spec["residual_multiplier"]))
    padded = np.zeros(pad_to, np.int32)
    padded[:S] = ids
    x = dense._embed(flat["embed/tok"], jnp.asarray(padded),
                     emb_mult=float(spec["embedding_multiplier"]))
    w = {k: flat[k] for k in dense._LAYER_KEYS}
    for layer in range(spec["num_hidden_layers"]):
        x = dense._layer(x, w, jnp.int32(layer), dims=dims, int8=int8)
    out = _logits(x, jnp.asarray(rows, jnp.int32), flat["ln_f/scale"],
                  flat[HEAD], eps=float(spec["rms_norm_eps"]), int8=int8)
    return np.asarray(out)[:n]


def _head_bytes(spec: dict) -> float:
    return spec["hidden_size"] * spec["vocab_size"] * ITEMSIZE[spec["torch_dtype"]]


def chunk_work(spec: dict, chunks: list):
    """The dense counts, and the head read where a chunk ends its prompt."""
    w = dense.chunk_work(spec, chunks)
    if any(last for _, _, last in chunks):
        w.bytes += _head_bytes(spec)
    return w


def decode_work(spec: dict, contexts: list):
    w = dense.decode_work(spec, contexts)
    if contexts:
        w.bytes += _head_bytes(spec)
    return w
