"""Seconds JAX spent tracing, lowering and compiling during set-up, from its
own monitoring events (persistent-cache hits included at their cost)."""


def read(run):
    return run.compile_s
