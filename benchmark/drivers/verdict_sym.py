"""Exhaustive checks under symmetry reduction: the spec checked to the end
with `.symmetry()` on the `CheckerBuilder`, its verdict read and its example paths
kept for the judge.

    TensorModelAdapter(spec).checker().symmetry().<spawn>().join()
    .discoveries()  # {name: path}; path.into_states()

The examples are paths the checker rebuilds on the host in representative
space, so reading them is timed under `bench:path`. Besides the counters
of `harness/window.py`, each check keeps `states_generated` and, where the
program counts it, `canon_rewrites`; its `examples` map each property with
a discovery to its path's states.
"""

from __future__ import annotations

import time

from benchmark.harness.window import Check, finish, spawn

EXTRA_COUNTERS = ("states_generated", "canon_rewrites")


def make_check(spec, config, traffic, devices):
    import jax

    from stateright_tpu import TensorModelAdapter

    span = jax.profiler.TraceAnnotation

    def check() -> Check:
        t0 = time.perf_counter()
        with span("bench:spawn"):
            checker = spawn(TensorModelAdapter(spec).checker().symmetry(), traffic, devices)
        with span("bench:join"):
            checker.join()
        rec = Check()
        with span("bench:path"):
            paths = checker.discoveries()
            rec.examples = {name: path.into_states() for name, path in paths.items()}
        rec.discoveries = sorted(rec.examples)
        finish(rec, checker, t0)
        tel = checker.telemetry()
        rec.counters.update({k: int(tel[k]) for k in EXTRA_COUNTERS if k in tel})
        return rec

    return check
