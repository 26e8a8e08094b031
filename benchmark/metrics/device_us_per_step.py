"""Device busy microseconds (union of device-op intervals in the traced
window, mean over the cell's chips) per era step (`telemetry()["steps"]`
summed over the window's checks). Names no program of the system, so a
rename cannot silence it."""


def read(run):
    if run.trace is None:
        return None
    steps = sum(c.counters.get("steps", 0) for c in run.checks)
    if not steps or run.trace["busy_s"] <= 0:
        return None
    return run.trace["busy_s"] * 1e6 / steps
