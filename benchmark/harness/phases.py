"""Milliseconds per check of the program's own phases, in a traced run.

While a profiler session records, the checker adds the seconds of every
phase timer it runs to process-wide totals
(`stateright_tpu.obs.spans.profiled_phase_totals`). The session covers the
window only, so in a `--trace 1` run the totals are the window's checks'.
A program that keeps no such totals reads None, as does an untraced run.
"""

from __future__ import annotations


def ms_per_check(run, phases, work: int):
    """Milliseconds of `phases` summed over the traced window, over the
    window's checks. `work` counts what the phases time in the window (a
    growth, a path, a check); with none the phases never ran and read 0.
    With some but none of the phases recorded, the phases were renamed or
    lost: that raises rather than read 0."""
    if run.trace is None or not run.checks:
        return None
    try:
        from stateright_tpu.obs.spans import profiled_phase_totals
    except ImportError:  # a program whose phase timers are not profiler spans
        return None
    if work == 0:
        return 0.0
    totals = profiled_phase_totals()
    secs = [totals[p]["secs"] for p in phases if p in totals]
    if not secs:
        raise ValueError(
            f"the traced window did the work of {list(phases)} ({work} times) "
            f"but recorded none of them; recorded {sorted(totals)}"
        )
    return 1000.0 * sum(secs) / len(run.checks)


def paths(run) -> int:
    """Counterexample paths the window's checks rebuilt."""
    return sum(1 for c in run.checks if c.rows is not None)
