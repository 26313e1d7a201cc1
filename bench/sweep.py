"""Find a cell's knee: one set-up, then windows at rising Poisson rates.

  python3 bench/sweep.py --workload g2b-chat --seed 5 --seconds 45 --rates 0.5 0.7 0.9

Each window serves the cell's mix at one rate on a fresh engine (same
weights, programs compiled once) and prints one JSON line: the cell's
end-to-end metrics and its per-layer metrics that need no trace, as the
benchmark's own readers compute them, and besides the share Algorithm 1
admitted, how the pending queue moved through the window (mean over its
first and last thirds), the mean rate the controller chose, preemptions and
peak pages. The knee is the highest rate at which Algorithm 1 still admits
nearly every arrival and the queue does not grow through the window.

``--engine key=value ...`` overrides the configuration's engine geometry
(rows, pool pages) for a probe of another geometry.
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


def summary(cell, data, notes, rate: float) -> dict:
    import numpy as np

    import harness

    sent = len(data.served)
    refused = sum(s.refused for s in data.served)
    q0, q1 = harness.queue_thirds(data, notes["queue"])
    out = {"rate_per_s": rate, "sent": sent,
           "admitted_share": round(1 - refused / sent, 4),
           "queue_first_third": round(q0, 3), "queue_last_third": round(q1, 3),
           "mean_rate": round(float(np.mean(notes["rates"])), 3),
           "preemptions": data.counters["preemptions"],
           "peak_pages": data.counters["peak_pages"],
           "peak_bytes": notes["peak_bytes"]}
    for kind, entries in (("e2e_metrics", cell.end_to_end),
                          ("layer_metrics", cell.per_layer)):
        for name, m in harness.read_metrics(entries, kind, data).items():
            out[name] = round(m["value"], 3)
    return out


KNEE_CRITERION = ("the highest swept rate at which Algorithm 1 admitted at "
                  "least 98% of arrivals, at least 98% were served, and the "
                  "mean pending queue over the window's last third exceeded "
                  "that over its first third by less than one request (as "
                  "at every lower swept rate)")


def knee(rows: list) -> float | None:
    """The knee of a sweep's summary lines, by ``KNEE_CRITERION``."""
    best = None
    for r in sorted(rows, key=lambda r: r["rate_per_s"]):
        ok = (r["admitted_share"] >= 0.98 and r["served_share"] >= 98
              and r["queue_last_third"] - r["queue_first_third"] < 1.0)
        if not ok:
            break
        best = r["rate_per_s"]
    return best


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--engine", nargs="*", default=[], metavar="KEY=VALUE")
    opts = ap.parse_args(argv)
    import harness
    import jax

    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 2
    cell = harness.load_cell(harness.load_manifest(), opts.workload)
    for kv in opts.engine:
        key, value = kv.split("=", 1)
        if key not in cell.spec["engine"]:
            raise KeyError(f"no engine key {key!r}")
        cell.spec["engine"][key] = type(cell.spec["engine"][key])(value)
    setup = harness.prepare(cell, opts.seed)
    harness.say(f"sweep {opts.workload}: set-up {time.perf_counter() - T_START:.3f} s "
                f"compile {setup.compiles.seconds:.3f} s")
    for rate in opts.rates:
        data, notes = harness.window(setup, opts.seed, opts.seconds, T_START,
                                     rate_per_s=rate)
        print(json.dumps({"workload": opts.workload, "engine": opts.engine,
                          **summary(cell, data, notes, rate)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
