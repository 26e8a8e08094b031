"""Percent of the traced window in which no operation ran on the device,
in the first-counterexample cells."""

from benchmark.harness.trace import idle_percent


def read(run):
    return idle_percent(run.trace)
