"""Host milliseconds from `join()` returning to the counterexample's actions
in hand (`discovery(...).into_actions()`), total over the window's checks
over their number. A span in the benchmark's own code."""


def read(run):
    spans = [c.path_s for c in run.checks if c.path_s is not None]
    if not spans:
        return None
    return 1000.0 * sum(spans) / len(spans)
