"""Readings that set a cell's ``logit_gap_max`` limit, all in one process.

  python3 bench/calibrate.py --workload g2b-chat --seconds 51 --seeds 11 12 13

For each seed: weights from the seed, one open-loop window at the cell's own
rate and sizes on the timed path, then over the same sample of served
requests that a run compares:

* ``program``: the widest gap by which a served token's float32-reference
  logit lies below the reference's best (the lower reading), and the mean
  gap over every compared position;
* ``control``: the same for the token the int8 reference puts first at each
  position of the same prompts and tokens (the upper reading).

Set-up and compiles are paid once. One JSON line per seed.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(os.path.dirname(BENCH), "src"))


def readings(setup, data, seed: int) -> dict:
    import harness

    cmp = harness.compared(setup, data, seed, control=True)
    prog = harness.check(setup, data, seed, cmp=cmp)
    ctrl = harness.check(setup, data, seed, control=True, cmp=cmp)
    return {"program": prog["checks"]["logit_gap_max"]["value"],
            "control": ctrl["checks"]["logit_gap_max"]["value"],
            "program_mean": prog["logit_gap_mean"],
            "control_mean": ctrl["logit_gap_mean"],
            "compared_tokens": prog["checks"]["compared_tokens"]["value"],
            "compared_requests": prog["compared_requests"],
            "unfinished": prog["failed"],
            "bad_token_requests": prog["checks"]["bad_token_requests"]["value"],
            "served": len(data.done()), "sent": len(data.served)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    opts = ap.parse_args(argv)
    import harness
    import jax

    if jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 2
    cell = harness.load_cell(harness.load_manifest(), opts.workload)
    setup = harness.prepare(cell, opts.seeds[0])
    for i, seed in enumerate(opts.seeds):
        if i:
            harness.reseed(setup, seed)
        t = time.perf_counter()
        data, _ = harness.window(setup, seed, opts.seconds, T_START)
        t_ref = time.perf_counter()
        out = readings(setup, data, seed)
        out.update(workload=opts.workload, seed=seed,
                   window_s=round(t_ref - t, 3),
                   reference_s=round(time.perf_counter() - t_ref, 3))
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
