"""Open-loop traffic from a mix file (``bench/traffic/<name>.json``).

One general generator reads every mix. A mix names an arrival process, a
rate, a distribution for prompt and output lengths, and the seed of its
schedule. A run of ``seconds`` at rate ``r`` carries ``n = round(r *
seconds)`` requests:

* gaps are the ``n`` mid-quantiles of the exponential distribution with mean
  ``1 / r`` (a Poisson process), rescaled to fill the window exactly;
* prompt and output lengths are the ``n`` mid-quantiles of their
  distributions (clipped);
* gaps and lengths are shuffled by the mix's ``schedule_seed``, so every
  run replays one arrival trace: which request is long and when it arrives
  is the same for every run seed;
* prompt token ids are drawn uniformly from the vocabulary by the run's
  seed.

So two run seeds differ in what the requests say (and, in the harness, in
the weights), never in how much work the window holds or when it comes.
With a few dozen requests of heavy-tailed lengths in a window, drawing the
order from the run seed moved output tokens/s by ~14% and the gap between
tokens by ~20% from seed to seed on one TPU v5e (granite-3-2b, chat mix),
where two runs of one order agreed within 0.3% in both and within 4% in
time to first token.
"""
from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from statistics import NormalDist

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


def load_mix(name: str, directory: Path = TRAFFIC_DIR) -> dict:
    path = Path(directory) / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} at {path}")
    mix = json.loads(path.read_text())
    for key in ("arrival", "prompt", "output", "schedule_seed"):
        if key not in mix:
            raise ValueError(f"traffic mix {name!r} lacks {key!r}")
    return mix


def _mid_quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def length_quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at the distribution's mid-quantiles, clipped to
    [min, max], as int64 in ascending order."""
    u = _mid_quantiles(n)
    kind = spec["dist"]
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
        vals = spec["median"] * np.exp(spec["sigma"] * z)
    elif kind == "uniform":
        lo, hi = spec["min"], spec["max"]
        vals = lo + u * (hi + 1 - lo) - 0.5
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.rint(vals), spec["min"], spec["max"]).astype(np.int64)


def arrival_offsets(spec: dict, n: int, seconds: float,
                    rng: np.random.Generator) -> np.ndarray:
    """Scheduled arrival times in [0, seconds), ascending."""
    if spec["process"] != "poisson":
        raise ValueError(f"unknown arrival process {spec['process']!r}")
    gaps = -np.log1p(-_mid_quantiles(n))     # Exp(1) mid-quantiles
    gaps = gaps * (seconds / gaps.sum())     # n gaps fill the window
    gaps = rng.permutation(gaps)
    # the first request is due at 0 and the last one gap before the end
    return np.cumsum(gaps) - gaps[0]


@dataclasses.dataclass
class Arrival:
    rid: int
    at_s: float            # scheduled offset from the window's start
    prompt: np.ndarray     # int32 token ids
    max_new_tokens: int


def generate(mix: dict, seed: int, seconds: float, vocab_size: int,
             rate_per_s: float | None = None) -> list[Arrival]:
    """The window's requests, in arrival order. ``rate_per_s`` overrides the
    mix's rate (the knee sweep uses it)."""
    rate = float(rate_per_s if rate_per_s is not None
                 else mix["arrival"]["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    order = np.random.default_rng(np.random.SeedSequence(
        [int(mix["schedule_seed"]), n]))
    at = arrival_offsets(mix["arrival"], n, seconds, order)
    prompts = order.permutation(length_quantiles(mix["prompt"], n))
    outs = order.permutation(length_quantiles(mix["output"], n))
    rng = np.random.default_rng(np.random.SeedSequence([int(seed) & (2**63 - 1),
                                                       n]))
    out = []
    for i in range(n):
        toks = rng.integers(0, vocab_size, int(prompts[i]), dtype=np.int32)
        out.append(Arrival(rid=i, at_s=float(at[i]), prompt=toks,
                           max_new_tokens=int(outs[i])))
    return out


def bucket(mix: dict, page_size: int) -> tuple[int, int]:
    """(prompt bucket, cache_len) the mix needs: the longest prompt rounded
    up to a page, and room for the longest output after it."""
    p = int(math.ceil(mix["prompt"]["max"] / page_size) * page_size)
    c = int(math.ceil((p + mix["output"]["max"]) / page_size) * page_size)
    return p, c
