"""Model configurations of the benchmark (``bench/configs/<name>.json``).

A file holds the published ``config.json`` keys as they are run, the keys
changed from the source (``reduced``), sizes set without a source
(``assumed``), the deployment it stands for, and the engine geometry. This
module turns one into the program's ``ModelConfig`` and registers it under
its file name when the program does not already know that name, so the
launcher builds it by name.
"""
from __future__ import annotations

import json
from pathlib import Path

CONFIG_DIR = Path(__file__).resolve().parent / "configs"


def load(name: str, directory: Path = CONFIG_DIR) -> dict:
    path = Path(directory) / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no configuration {name!r} at {path}")
    spec = json.loads(path.read_text())
    if spec.get("name") != name:
        raise ValueError(f"{path}: name {spec.get('name')!r} != {name!r}")
    return spec


def head_dim(spec: dict) -> int:
    return spec.get("head_dim") or spec["hidden_size"] // spec["num_attention_heads"]


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


_SHAPE_FIELDS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "d_ff",
                 "vocab_size", "head_dim_", "rope_theta", "norm_eps",
                 "tie_embeddings", "act", "dtype", "arch_type")


def register(spec: dict, dtype: str | None = None):
    """Register the configuration with the program under its name (or check
    that the program's own entry of that name runs the same shapes) and
    return the ModelConfig the launcher will build."""
    from repro.configs import base

    cfg = model_config(spec, dtype)
    base._ensure_loaded()
    have = base._REGISTRY.get(cfg.name)
    if have is None:
        base.register(cfg, smoke=cfg)
        return cfg
    known = have[0]
    for f in _SHAPE_FIELDS:
        if getattr(known, f) != getattr(cfg, f):
            raise ValueError(f"{cfg.name}: the program's {f} is "
                             f"{getattr(known, f)!r}, the file says "
                             f"{getattr(cfg, f)!r}")
    return known
