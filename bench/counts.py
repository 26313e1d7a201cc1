"""Operations and bytes the served work needs, from shapes and live lengths.

Counted per device step of the served loop: a slot's prompt-chunk part, and
each of its decode steps, by the configuration's architecture module
(``arch/<arch>.py``: ``chunk_work``, ``decode_work``), since what a step
needs depends on the block. What is counted is what the work needs, whatever
implements it; for the dense block:

* FLOPs: the projections and MLP of every token processed, attention over
  each token's live causal context, and the logits where the step needs
  them (each decode token, and a prompt's last token);
* bytes: the weights once per step; the logical K/V of each live row's
  context read once; the new K/V written once.

A gathered copy of the cache, its padding to ``cache_len`` and rows that are
masked or idle are never counted, so serving through a paged kernel that
reads only live pages raises the roofline share toward, and never past,
100%. Peaks come only from ``peaks.json``, keyed by ``device_kind``.
"""
from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"
ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def peaks(device_kind: str, path: Path = PEAKS) -> dict:
    table = json.loads(Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"peaks.json has {sorted(table)}")
    return table[device_kind]


@dataclasses.dataclass
class Work:
    flops: float = 0.0
    bytes: float = 0.0


def least_seconds(work: Work, peak: dict) -> tuple[float, str]:
    """The least time the chip could take for ``work``, and its bound."""
    t_c = work.flops / peak["bf16_flops_per_s"]
    t_m = work.bytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def slot_parts(arch, spec: dict, requests: list, n_steps: int, chunk: int) -> dict:
    """{slot: [Work, ...]}: the device steps of each control slot, rebuilt
    from the served requests' stamps. The timeline is the serve loop's;
    ``arch`` counts each step's work.

    A request of prompt length L starts chunking in ``start_slot`` and
    activates in ``first_token_slot`` (its last chunk, whose logits give
    the first token); chunks of ``chunk`` tokens go one per slot. The
    activating slot also runs ``n_steps`` decode steps, and each later slot
    ``n_steps`` more, until the request's tokens are all generated: token
    k >= 1 comes from a decode step at position L + k - 1 over L + k keys.
    """
    chunks: dict = {}
    steps: dict = {}
    for r in requests:
        if r.first_token_slot is None or not r.generated:
            continue
        L, f = len(r.tokens), r.first_token_slot
        s0 = r.start_slot if r.start_slot is not None else f
        n_chunks = max(1, math.ceil(L / chunk))
        for i in range(n_chunks):
            slot = f if i == n_chunks - 1 else min(s0 + i, f)
            take = min(chunk, L - i * chunk)
            chunks.setdefault(slot, []).append((i * chunk, take, i == n_chunks - 1))
        for k in range(1, len(r.generated)):
            slot = f + max(0, math.ceil((k - n_steps) / n_steps))
            steps.setdefault((slot, (k - 1) % n_steps), []).append(L + k)
    out: dict = {}
    for slot, rows in chunks.items():
        out.setdefault(slot, []).append(arch.chunk_work(spec, rows))
    for (slot, _j), ctxs in sorted(steps.items()):
        out.setdefault(slot, []).append(arch.decode_work(spec, ctxs))
    return out
