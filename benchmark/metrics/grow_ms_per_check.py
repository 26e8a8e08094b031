"""Milliseconds per check in the checker's `table_grow` phase: the visited
set's growth, the device rehash included (the phase waits for it), from
the profiled phase totals of a traced window."""

from benchmark.harness.phases import ms_per_check


def read(run):
    growths = sum(c.counters.get("table_growths", 0) for c in run.checks)
    return ms_per_check(run, ("table_grow",), growths)
