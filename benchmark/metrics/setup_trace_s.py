"""Seconds of set-up in which JAX traced, lowered or compiled: the union of
its own monitoring intervals (`/jax/core/compile/*`)."""


def read(run):
    return run.setup_trace_s
