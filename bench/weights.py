"""Random weights for a configuration, made on the device from the seed.

One jitted call builds the whole tree in the served type, in the layout the
program's dense stack takes (``init_params``): the tied embedding, one
layer-stacked segment of attention + gated MLP blocks, and the final norm.
The benchmark owns these weights: the program is handed them, and the plain
reference reads the same arrays, so the reference takes nothing the program
has made. ``check_layout`` holds the layout to the program's own
``abstract_params`` so a change of layout fails here, not as wrong numbers.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from model_cfg import head_dim


def shapes(spec: dict) -> dict:
    """{path: shape} of every weight, in the program's layout."""
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


@partial(jax.jit, static_argnums=(1, 2))
def _make(key, spec_items: tuple, dtype: str):
    spec = dict(spec_items)
    out = {}
    for i, (path, shape) in enumerate(sorted(shapes(spec).items())):
        k = jax.random.fold_in(key, i)
        if path.endswith("/scale"):
            out[path] = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
        else:
            w = _scale(path, spec) * jax.random.normal(k, shape, jnp.float32)
            out[path] = w.astype(dtype)
    return out


_SPEC_KEYS = ("num_hidden_layers", "hidden_size", "num_attention_heads",
              "num_key_value_heads", "intermediate_size", "vocab_size",
              "head_dim")


def make_flat(spec: dict, seed: int, dtype: str | None = None) -> dict:
    """{path: array} on the default device; norm scales stay float32 (the
    program's norms are float32), everything else ``dtype`` (default: the
    configuration's served type)."""
    items = tuple((k, spec.get(k)) for k in _SPEC_KEYS)
    key = jax.random.fold_in(jax.random.key(int(seed) % (2 ** 32)),
                             int(seed) >> 32)
    return _make(key, items, dtype or spec["torch_dtype"])


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


def check_layout(params: dict, cfg) -> None:
    """Raise unless ``params`` has exactly the program's tree, shapes and
    types for ``cfg``."""
    from repro.models.model import abstract_params

    want = abstract_params(cfg)
    got = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
    if jax.tree.structure(want) != jax.tree.structure(got):
        raise ValueError(f"weight tree {jax.tree.structure(got)} is not the "
                         f"program's {jax.tree.structure(want)}")
    for (path, w), g in zip(jax.tree_util.tree_leaves_with_path(want),
                            jax.tree.leaves(got), strict=True):
        if (w.shape, w.dtype) != (g.shape, g.dtype):
            raise ValueError(f"{jax.tree_util.keystr(path)}: {g.shape} {g.dtype}, "
                             f"program wants {w.shape} {w.dtype}")
