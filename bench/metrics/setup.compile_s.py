"""Seconds JAX spent tracing, lowering and compiling during set-up (its
``/jax/core/compile/`` monitoring events)."""


def read(run):
    return run["setup"]["compile_s"]
