"""Host milliseconds per check in the era dispatch loop's own phases:
the engine's constructor, run set-up, parameter uploads, dispatch calls
and the host work on each era's readback (`engine_init`, `run_setup`,
`upload`, `era_dispatch`, `era_host`), from the profiled phase totals of
a traced window."""

from benchmark.harness.phases import ms_per_check

PHASES = ("engine_init", "run_setup", "upload", "era_dispatch", "era_host")


def read(run):
    return ms_per_check(run, PHASES, len(run.checks))
