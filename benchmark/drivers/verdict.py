"""Exhaustive checks: the spec checked to the end, its verdict read.

    TensorModelAdapter(spec).checker().<spawn>().join(); .discoveries()

The verdict's examples are paths the checker rebuilds on the host, so
reading them is timed under `bench:path`.
"""

from __future__ import annotations

import time

from benchmark.harness.window import Check, finish, spawn


def make_check(spec, config, traffic, devices):
    import jax

    from stateright_tpu import TensorModelAdapter

    span = jax.profiler.TraceAnnotation

    def check() -> Check:
        t0 = time.perf_counter()
        with span("bench:spawn"):
            checker = spawn(TensorModelAdapter(spec).checker(), traffic, devices)
        with span("bench:join"):
            checker.join()
        rec = Check()
        with span("bench:path"):
            rec.discoveries = sorted(checker.discoveries())
        return finish(rec, checker, t0)

    return check
