"""The command refuses to run without a TPU: exit 2, no result line."""
import os
import subprocess
import sys

import benchpath

RUN = benchpath.BENCH / "run.py"


def test_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(RUN), "--workload", "g2b-chat",
                        "--seed", str(2 ** 31 + 5), "--seconds", "10",
                        "--trace", "0"], capture_output=True, text=True,
                       env=env, cwd=benchpath.BENCH.parent, timeout=120)
    assert p.returncode == 2, p.stderr
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_refuses_unknown_workload():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(RUN), "--workload", "nope",
                        "--seed", "1", "--seconds", "10"], capture_output=True,
                       text=True, env=env, cwd=benchpath.BENCH.parent, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""
