"""Seconds in which JAX traced, lowered or compiled.

JAX reports each of these as a monitoring event with a duration. Nested
traces overlap, so the clock counts the union of the intervals between two
readings of `time.perf_counter`.
"""

from __future__ import annotations

import threading
import time

COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


def union_seconds(spans, t0: float, t1: float) -> float:
    """Length of the union of `(start, end)` spans, clipped to [t0, t1]."""
    clipped = sorted((max(a, t0), min(b, t1)) for a, b in spans if b > t0 and a < t1)
    total, cur_a, cur_b = 0.0, None, None
    for a, b in clipped:
        if cur_b is None or a > cur_b:
            if cur_b is not None:
                total += cur_b - cur_a
            cur_a, cur_b = a, b
        else:
            cur_b = max(cur_b, b)
    if cur_b is not None:
        total += cur_b - cur_a
    return total


class CompileClock:
    def __init__(self):
        import jax

        self._spans = []
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_kw):
        if event in COMPILE_EVENTS:
            end = time.perf_counter()
            with self._lock:
                self._spans.append((end - duration, end))

    def secs_between(self, t0: float, t1: float) -> float:
        with self._lock:
            spans = list(self._spans)
        return union_seconds(spans, t0, t1)
