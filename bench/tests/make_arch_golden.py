"""Record what the dense block gives, for ``test_bench_arch.py`` to hold it to.

  JAX_PLATFORMS=cpu python3 bench/tests/make_arch_golden.py

Writes ``fixtures/arch_golden.json`` and ``fixtures/arch_golden_logits.npz``:

* the sha256, dtype and shape of every array that ``make_flat`` gives
  ``tiny-dense`` at two seeds;
* the reference logits of ``tiny-dense`` at fixed ids and rows, in the
  served type and with the int8 control;
* ``chunk_work``, ``decode_work`` and ``slot_parts`` for ``granite-3-2b``
  over a scripted list of requests, as exact floats.

The files were recorded on the tree from before the dense block moved into
``bench/arch/dense.py``, where the spec alone selected the block
(``weights.make_flat(spec, seed)``, ``reference.logits_at(spec, ...)``,
``counts.chunk_work(spec, ...)``); ``api()`` takes that path where
``bench/arch/`` is absent. On a tree that has it, the script records through
the dense module, and must write the same files.
"""
from __future__ import annotations

import hashlib
import json
import os
import sys
from functools import partial
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
FIXTURES = BENCH / "tests" / "fixtures"
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(BENCH.parent / "src"))

import numpy as np  # noqa: E402

GOLDEN = FIXTURES / "arch_golden.json"
LOGITS = FIXTURES / "arch_golden_logits.npz"

SEEDS = (7, 2 ** 32 + 5)            # the second exercises the seed's high bits
IDS = np.random.default_rng(0).integers(0, 512, 37).astype(np.int32)
ROWS = np.arange(29, 37)
PAD_TO, ROWS_PAD = 48, 10
CHUNKS = ([(0, 4, True)], [(0, 256, False), (256, 44, True)],
          [(32, 8, False)], [(1000, 256, False), (0, 17, True)], [])
CONTEXTS = ([100, 300], [41], [17, 2000, 5], [2560] * 16, [])
# (prompt length, start_slot, first_token_slot, tokens generated)
REQUESTS = ((40, 3, 5, 6), (300, 0, 1, 9), (1, None, 2, 3), (513, 4, 7, 1),
            (2048, 10, 17, 512), (77, 2, None, 0), (256, 5, 5, 130))
N_STEPS, CHUNK = 2, 256


class Req:
    """The fields of the program's ``Request`` that ``slot_parts`` reads."""

    def __init__(self, length, start, first, n_gen):
        self.tokens = [0] * length
        self.start_slot, self.first_token_slot = start, first
        self.generated = [1] * n_gen


def api():
    """(make_flat, logits_at, chunk_work, decode_work, slot_parts) of the
    dense block on this tree, each taking the spec first."""
    import counts
    import weights

    if (BENCH / "arch" / "dense.py").is_file():
        import model_cfg

        dense = model_cfg.load_arch("dense")
        return (partial(weights.make_flat, dense), dense.logits_at,
                dense.chunk_work, dense.decode_work,
                partial(counts.slot_parts, dense))
    import reference

    return (weights.make_flat, reference.logits_at, counts.chunk_work,
            counts.decode_work, counts.slot_parts)


def spec_of(name: str, directory: Path) -> dict:
    return json.loads((directory / f"{name}.json").read_text())


def digests(flat: dict) -> dict:
    return {path: [str(a.dtype), list(a.shape),
                   hashlib.sha256(np.asarray(a).tobytes()).hexdigest()]
            for path, a in sorted(flat.items())}


def record() -> tuple[dict, dict]:
    make_flat, logits_at, chunk_work, decode_work, slot_parts = api()
    tiny = spec_of("tiny-dense", FIXTURES / "configs")
    g2b = spec_of("granite-3-2b", BENCH / "configs")
    golden = {"weights": {str(s): digests(make_flat(tiny, s)) for s in SEEDS}}
    flat = make_flat(tiny, SEEDS[0])
    logits = {name: logits_at(tiny, flat, IDS, ROWS, PAD_TO, ROWS_PAD, int8=int8)
              for name, int8 in (("served", False), ("int8", True))}
    golden["chunk_work"] = [[w.flops, w.bytes] for w in map(partial(chunk_work, g2b), CHUNKS)]
    golden["decode_work"] = [[w.flops, w.bytes] for w in map(partial(decode_work, g2b), CONTEXTS)]
    parts = slot_parts(g2b, [Req(*r) for r in REQUESTS], N_STEPS, CHUNK)
    golden["slot_parts"] = {str(t): [[w.flops, w.bytes] for w in ws]
                            for t, ws in sorted(parts.items())}
    return golden, logits


def main() -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    golden, logits = record()
    GOLDEN.write_text("{\n" + ",\n".join(f" {json.dumps(k)}: {json.dumps(v)}"
                                         for k, v in golden.items()) + "\n}\n")
    np.savez(LOGITS, **logits)
    print(f"wrote {GOLDEN} and {LOGITS}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
