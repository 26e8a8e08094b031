"""The window's seconds over the right counterexamples it returned (host
clock)."""


def read(run):
    found = sum(1 for c in run.checks if c.right and c.rows is not None)
    if not found:
        return None
    return run.window_s / found
