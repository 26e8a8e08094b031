"""Checks stopped at the first counterexample of the configuration's
`counterexample.property`, its actions read.

    TensorModelAdapter(spec).checker()
        .finish_when(HasDiscoveries.any_of([property])).<spawn>().join()
    .discovery(property).into_actions()

`path_s` is the host time from `join()` returning to the actions in hand.
"""

from __future__ import annotations

import time

from benchmark.harness.window import Check, finish, spawn


def make_check(spec, config, traffic, devices):
    import jax

    from stateright_tpu import HasDiscoveries, TensorModelAdapter

    span = jax.profiler.TraceAnnotation
    stop_at = config["counterexample"]["property"]

    def check() -> Check:
        t0 = time.perf_counter()
        with span("bench:spawn"):
            options = TensorModelAdapter(spec).checker()
            checker = spawn(options.finish_when(HasDiscoveries.any_of([stop_at])), traffic, devices)
        with span("bench:join"):
            checker.join()
        t_join = time.perf_counter()
        rec = Check()
        with span("bench:path"):
            path = checker.discovery(stop_at)
            if path is not None:
                path.into_actions()
        rec.path_s = time.perf_counter() - t_join
        rec.rows = path.into_states() if path is not None else None
        return finish(rec, checker, t0)

    return check
