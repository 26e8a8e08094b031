"""Host milliseconds per check in the checker's `path_download` phase (path
reconstruction), from the profiled phase totals of a traced window."""

from benchmark.harness.phases import ms_per_check, paths


def read(run):
    return ms_per_check(run, ("path_download",), paths(run))
