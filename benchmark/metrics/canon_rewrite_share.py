"""Percent of the window's generated successors that the symmetry
canonicalizer replaced by a different representative: 100 x the sum of
`canon_rewrites` over the sum of `states_generated`, both from each
check's `telemetry()`. None on a program that does not count rewrites."""


def read(run):
    counted = [c.counters for c in run.checks if "canon_rewrites" in c.counters]
    generated = sum(c.get("states_generated", 0) for c in counted)
    if not generated:
        return None
    return 100.0 * sum(c["canon_rewrites"] for c in counted) / generated
