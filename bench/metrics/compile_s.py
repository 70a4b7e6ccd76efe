"""Seconds JAX spent tracing, lowering and compiling (or reading the
compilation cache) during set-up, summed from its own
``jax.monitoring`` duration events."""


def read(run):
    return run.compile_s
