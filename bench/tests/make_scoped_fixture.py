"""Record the small chip trace that ``test_bench_scopes.py`` reads.

  python3 bench/tests/make_scoped_fixture.py OUT_DIR

On a TPU: three rounds of a jitted chain of eight matmuls, its first half
under ``jax.named_scope("chunk")`` and its second under ``"decode"``, each
step alternately under ``"attn"`` and ``"ffn"``, then an unscoped sum. The
host side of each round is what the serve loop records with the program's
own recorder: a ``serve.slot`` span holding an ``engine.plan`` span (a 3 ms
host sleep), the ``engine.dispatch`` of the chain, and an ``engine.consume``
whose ``engine.wait`` blocks on the result; then a 4 ms ``bench.poll``
sleep, all inside ``bench.traced``. Writes OUT_DIR/scoped.xplane.pb and the
recorder's ring as OUT_DIR/scoped.ring.json, and prints what
``xspace_scopes`` makes of them.
"""
from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(os.path.dirname(BENCH), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import trace_reduce  # noqa: E402
import xspace_scopes  # noqa: E402
from repro.obs import TraceRecorder  # noqa: E402


@jax.jit
def chain(x):
    for half in ("chunk", "decode"):
        with jax.named_scope(half):
            for i in range(4):
                with jax.named_scope("attn" if i % 2 == 0 else "ffn"):
                    x = jnp.tanh(x @ x)
    return x, jnp.sum(x.astype(jnp.float32))


def main(out_dir: str) -> int:
    if jax.devices()[0].platform != "tpu":
        print("make_scoped_fixture: needs a TPU", file=sys.stderr)
        return 2
    x = jnp.ones((2048, 2048), jnp.bfloat16) / 2048
    jax.block_until_ready(chain(x))
    tr = TraceRecorder()
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("bench.traced"):
        for t in range(3):
            with tr.span("serve.slot", slot=t):
                with tr.span("engine.plan", slot=t):
                    time.sleep(0.003)
                with tr.span("engine.dispatch", slot=t):
                    y = chain(x)
                with tr.span("engine.consume", slot=t):
                    with tr.span("engine.wait", slot=t):
                        jax.block_until_ready(y)
            with jax.profiler.TraceAnnotation("bench.poll"):
                time.sleep(0.004)
    jax.profiler.stop_trace()
    os.makedirs(out_dir, exist_ok=True)
    dst = os.path.join(out_dir, "scoped.xplane.pb")
    shutil.copy(trace_reduce.find_xplane(tmp), dst)
    shutil.rmtree(tmp, ignore_errors=True)
    with open(os.path.join(out_dir, "scoped.ring.json"), "w") as f:
        json.dump(tr.events(), f)
    pd, xp = trace_reduce.load(dst), xspace_scopes.read(dst)
    scopes, ambiguous = xspace_scopes.op_scopes(xp)
    print("scopes", sorted(set(scopes.values())), "ambiguous", ambiguous)
    print("split", xspace_scopes.scope_split(pd, scopes))
    print("idle", xspace_scopes.idle_by_span(pd))
    print("reduce", trace_reduce.reduce(pd))
    print("profile_start_ns", xspace_scopes.profile_start_ns(xp))
    print("bytes", os.path.getsize(dst))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
