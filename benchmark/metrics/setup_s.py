"""Seconds from the start of the benchmark's process to the start of the
window: imports, the cache, tracing and compiling, the warm-up check."""


def read(run):
    return run.setup_s
