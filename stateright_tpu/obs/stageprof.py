"""Per-stage era profiling: isolated-stage microbenches + attribution.

The device engines run their whole search inside `lax.while_loop` eras —
one dispatch, thousands of fused steps — so there is no place to put a
host timer *inside* a step: XLA fuses the stages and the platform has no
device-side timestamp primitive the loop could carry. What CAN be
measured is each stage in isolation, at the exact shapes the era loop
compiles for: each engine builds one jitted kernel per stage (successor
expansion, fingerprint/hash, visited-set probe, claim dedup, validity
compaction, ring append, canonicalization — see the engine's
`_build_stage_kernels`) that repeats that single stage `iters` times
inside a `lax.fori_loop`, with a data dependence chaining the iterations
so XLA can neither elide nor overlap them. Amortizing `iters` repetitions
behind one dispatch keeps the fixed per-dispatch cost from swamping
sub-ms stages; an empty-loop null kernel measures that fixed dispatch
cost and it is subtracted out.

Attribution is PROPORTIONAL: the isolated per-step stage costs give each
stage's share, and those shares scale the run's measured `device_era`
wall time — so the reported `stage_*` phase timers sum to the era total
by construction, while the raw isolated measurements stay visible as the
`stage_us_per_step` gauge. The `stage_profile_model_pct` gauge reports
how much of the measured era time the isolated-stage cost model predicts
(per-step sum x steps / era wall time): near 100 means the stages account
for the loop; far below means fixed per-step overheads (loop condition,
carry bookkeeping) or fusion effects dominate, far above means the
isolated kernels run slower than the fused loop (fusion wins).

Surfacing is automatic once the phases are in the registry: the
`stage_*` keys ride `Checker.telemetry()['phase_ms']`, the JSONL trace's
`run_end` event, the Chrome trace's per-phase duration lanes, and
Prometheus exposition — see the phase catalog in obs/metrics.py.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, Tuple

# Canonical display order for the per-stage breakdown (engines populate
# the subset their architecture has; e.g. `canon` only under symmetry,
# `exchange` only on the sharded engine, the walk stages only on the
# simulation engine).
STAGE_ORDER = (
    "expand",
    "hash",
    "probe",
    "claim",
    "compact",
    "ring",
    "canon",
    "exchange",
    "cycle",
    "choose",
    "record",
)


def build_null_kernel(iters: int):
    """An empty `iters`-round fori loop: measures the fixed dispatch +
    readback cost a stage kernel pays regardless of its work."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    @jax.jit
    def null(seed):
        def body(_i, c):
            return c + jnp.uint32(1)

        return lax.fori_loop(0, iters, body, seed)

    return null


def time_dispatch(fn: Callable, args: Tuple, repeats: int = 2) -> float:
    """Best-of-`repeats` wall seconds for one dispatch of a jitted kernel.

    The first (untimed) call compiles and warms; every timed call ends in
    a host readback of the kernel's small output, so the time covers the
    device work and not only its dispatch.
    """
    import numpy as np

    np.asarray(fn(*args))  # compile + warm
    best = float("inf")
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        np.asarray(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def measure_stage_kernels(
    kernels: Dict[str, Tuple[Callable, Tuple]],
    iters: int,
    repeats: int = 2,
) -> Dict[str, float]:
    """Time each stage kernel; returns per-ITERATION seconds per stage,
    with the null-kernel dispatch baseline subtracted (floored at 0)."""
    import jax.numpy as jnp

    null = build_null_kernel(iters)
    seed = jnp.asarray(1, dtype=jnp.uint32)
    base = time_dispatch(null, (seed,), repeats)
    out: Dict[str, float] = {}
    for name, (fn, args) in kernels.items():
        secs = time_dispatch(fn, args, repeats)
        out[name] = max(0.0, secs - base) / max(1, iters)
    return out


def attribute_stages(
    metrics,
    per_step_secs: Dict[str, float],
    era_secs: float,
    steps: int,
    iters: int,
) -> Dict[str, float]:
    """Record the breakdown into the metrics registry as `stage_<name>`
    phase timers scaled so their sum equals `era_secs` exactly, plus the
    raw-measurement gauges. Returns the scaled seconds per stage."""
    total = sum(per_step_secs.values())
    scaled: Dict[str, float] = {}
    if total > 0.0 and era_secs > 0.0:
        for name, secs in per_step_secs.items():
            share = era_secs * (secs / total)
            metrics.add_phase("stage_" + name, share)
            scaled["stage_" + name] = share
    metrics.set_gauge("stage_profile_iters", int(iters))
    metrics.set_gauge(
        "stage_us_per_step",
        {k: round(v * 1e6, 3) for k, v in per_step_secs.items()},
    )
    if steps and era_secs > 0.0:
        metrics.set_gauge(
            "stage_profile_model_pct",
            round(100.0 * total * steps / era_secs, 1),
        )
    return scaled


def stage_rows(phase_ms: Dict[str, float]):
    """(name, ms) rows for every populated stage phase, in STAGE_ORDER
    then alphabetically for any stage this module doesn't know."""
    rows = []
    seen = set()
    for name in STAGE_ORDER:
        key = "stage_" + name
        if key in phase_ms:
            rows.append((name, phase_ms[key]))
            seen.add(key)
    for key in sorted(phase_ms):
        if key.startswith("stage_") and key not in seen:
            rows.append((key[len("stage_"):], phase_ms[key]))
    return rows
