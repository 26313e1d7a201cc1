"""Run one benchmark cell once on the accelerator this process finds.

  python3 bench/run.py --workload g2b-chat --seed 7 --seconds 30 --trace 0

The cell (configuration, traffic mix, metrics, check limits) is found by
name from ``BENCHMARK.json`` and the files under ``bench/``. The last line
of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with ``--trace 1``
its per-layer metrics), ``device``, with ``--trace 1`` a ``breakdown``, and
last ``checks``: each number compared with its limit, which also close
standard error.

Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(ROOT, "src"))


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    import harness

    cell = harness.load_cell(harness.load_manifest(), args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: needs a TPU, found {devices[0].platform!r}", file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    result = harness.run(cell, args.seed, args.seconds, bool(args.trace), T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
