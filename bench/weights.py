"""Random weights for a configuration, made on the device from the seed.

One jitted call builds the whole tree in the served type, in the layout and
by the draw rules of the configuration's architecture module
(``arch/<arch>.py``: ``shapes``, ``draw``). The benchmark owns these
weights: the program is handed them (the module's ``to_program``), and the
plain reference reads the same arrays, so the reference takes nothing the
program has made. ``check_layout`` holds the layout to the program's own
``abstract_params`` so a change of layout fails here, not as wrong numbers.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


@partial(jax.jit, static_argnums=(1, 2))
def _make(key, plan: tuple, dtype: str):
    out = {}
    for i, (path, shape, mean, std, f32) in enumerate(plan):
        x = std * jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        if mean:
            x = mean + x
        out[path] = x if f32 else x.astype(dtype)
    return out


def make_flat(arch, spec: dict, seed: int, dtype: str | None = None) -> dict:
    """{path: array} on the default device: each weight drawn by its rule,
    in sorted path order, from its own fold of the seed's key; the served
    type is ``dtype`` (default: the configuration's)."""
    plan = tuple((path, tuple(shape), *arch.draw(spec, path))
                 for path, shape in sorted(arch.shapes(spec).items()))
    key = jax.random.fold_in(jax.random.key(int(seed) % (2 ** 32)),
                             int(seed) >> 32)
    return _make(key, plan, dtype or spec["torch_dtype"])


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
