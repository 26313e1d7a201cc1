"""Record the small chip trace that ``test_bench_trace.py`` reduces.

  python3 bench/tests/make_trace_fixture.py OUT_DIR

On a TPU: three rounds of a jitted matmul chain, each dispatched inside a
``bench.step_slot_chunked`` span and followed by a host sleep inside a
``bench.poll`` span, all inside ``bench.traced``. Writes OUT_DIR/step.xplane.pb
and prints the planes, lines and what ``trace_reduce`` makes of it.
"""
from __future__ import annotations

import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import trace_reduce  # noqa: E402


@jax.jit
def chain(x):
    for _ in range(8):
        x = jnp.tanh(x @ x)
    return x


def main(out_dir: str) -> int:
    if jax.devices()[0].platform != "tpu":
        print("make_trace_fixture: needs a TPU", file=sys.stderr)
        return 2
    x = jnp.ones((2048, 2048), jnp.bfloat16) / 2048
    chain(x).block_until_ready()
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("bench.traced"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step_slot_chunked"):
                y = chain(x)
                y.block_until_ready()
            with jax.profiler.TraceAnnotation("bench.poll"):
                time.sleep(0.004)
    jax.profiler.stop_trace()
    src = trace_reduce.find_xplane(tmp)
    os.makedirs(out_dir, exist_ok=True)
    dst = os.path.join(out_dir, "step.xplane.pb")
    shutil.copy(src, dst)
    shutil.rmtree(tmp, ignore_errors=True)
    pd = trace_reduce.load(dst)
    for plane in pd.planes:
        lines = [(line.name, sum(1 for _ in line.events)) for line in plane.lines]
        print("plane", plane.name, lines)
    for name, ops in trace_reduce.device_ops(pd).items():
        print("device", name, len(ops), ops[:3])
    print("spans", trace_reduce.host_spans(pd))
    print("reduce", trace_reduce.reduce(pd))
    print("bytes", os.path.getsize(dst))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
