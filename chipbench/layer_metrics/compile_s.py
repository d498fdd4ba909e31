"""Seconds JAX spent tracing, lowering and compiling during set-up, from
its monitoring events (a persistent-cache hit skips the backend part)."""


def read(run):
    return run.compile_s
