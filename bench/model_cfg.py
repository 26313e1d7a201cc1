"""Model configurations of the benchmark (``bench/configs/<name>.json``).

A file holds the published ``config.json`` keys as they are run, the keys
changed from the source (``reduced``), sizes set without a source
(``assumed``), the deployment it stands for, the engine geometry, and
``arch``: the name of the module, ``arch/<arch>.py``, that knows its block.
This module loads both, and registers the configuration with the program
under its file name when the program does not already know that name, so the
launcher builds it by name.

An architecture module gives exactly these functions (``CONTRACT``), and the
benchmark reaches the block through nothing else:

* ``model_config(spec, dtype)``: the program's ``ModelConfig`` for the file,
  after the architecture's own check that the file says what the program
  runs; ``dtype`` overrides the served type (tests run float32 copies).
* ``shapes(spec)``: ``{path: shape}`` of every weight. ``draw(spec, path)``:
  that weight's rule, ``(mean, std, float32)``, drawn as ``mean + std *
  N(0, 1)``, kept in float32 where ``float32`` is true and cast to the
  served type otherwise. ``to_program(flat)``: the program's params tree
  over the arrays ``{path: array}``, without copies.
* ``logits_at(spec, flat, ids, rows, pad_to, rows_pad, int8=False)``: the
  plain float32 reference's logits (every matmul at ``HIGHEST``) of one
  sequence at the given positions, imported from nothing of the program; with
  ``int8`` the control, its projections and logits from int8 operands.
* ``chunk_work(spec, chunks)`` and ``decode_work(spec, contexts)``: the
  ``counts.Work`` that one dispatch's prompt-chunk part and one decode step
  need (see ``counts``).
"""
from __future__ import annotations

import functools
import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
CONFIG_DIR = BENCH_DIR / "configs"
ARCH_DIR = BENCH_DIR / "arch"
CONTRACT = ("model_config", "shapes", "draw", "to_program", "logits_at",
            "chunk_work", "decode_work")


def load(name: str, directory: Path = CONFIG_DIR) -> dict:
    path = Path(directory) / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no configuration {name!r} at {path}")
    spec = json.loads(path.read_text())
    if spec.get("name") != name:
        raise ValueError(f"{path}: name {spec.get('name')!r} != {name!r}")
    if "arch" not in spec:
        raise ValueError(f"{path}: no 'arch' names the configuration's block")
    return spec


def load_arch(name: str, *dirs: Path):
    """The module ``<dir>/<name>.py`` of the first of ``dirs`` (default: the
    benchmark's own ``arch/``) that has it, loaded once per process."""
    paths = [Path(d) / f"{name}.py" for d in dirs or (ARCH_DIR,)]
    for path in paths:
        if path.is_file():
            return _load_module(path.resolve())
    raise FileNotFoundError(f"no architecture {name!r} at "
                            + " or ".join(map(str, paths)))


@functools.cache
def _load_module(path: Path):
    spec = importlib.util.spec_from_file_location(f"arch.{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    missing = [f for f in CONTRACT if not callable(getattr(mod, f, None))]
    if missing:
        raise ValueError(f"{path} lacks {', '.join(missing)} of the "
                         "architecture contract")
    return mod


_SHAPE_FIELDS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "d_ff",
                 "vocab_size", "head_dim_", "rope_theta", "norm_eps",
                 "tie_embeddings", "act", "dtype", "arch_type")


def register(spec: dict, arch, dtype: str | None = None):
    """Register the configuration with the program under its name (or check
    that the program's own entry of that name runs the same shapes) and
    return the ModelConfig the launcher will build."""
    from repro.configs import base

    cfg = arch.model_config(spec, dtype)
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
