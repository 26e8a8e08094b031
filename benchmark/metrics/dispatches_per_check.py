"""Mean of the era dispatch loop's `telemetry()["dispatches"]` over the window's
checks: host round trips per check."""


def read(run):
    counted = [c.counters["dispatches"] for c in run.checks if "dispatches" in c.counters]
    if not counted:
        return None
    return sum(counted) / len(counted)
