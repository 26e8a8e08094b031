"""Judge exhaustive checks by the unique-state count and the set of
properties with a discovery, against the reference's answer for the spec.

The spec is fixed and breadth-first search is deterministic, so that answer
is one number and one set; `reference/<module>.py` computes them and the
configuration file pins them under `golden` (benchmark/tests re-derive
every pin). Every limit is 0: the comparison is exact.
"""

from __future__ import annotations


def judge(config: dict, checks) -> tuple:
    """(number of wrong checks, {name: (value, limit)})."""
    golden = config["golden"]
    wrong = 0
    count_gap = 0
    mismatches = 0
    for c in checks:
        if c.error is not None:
            wrong += 1
            continue
        gap = abs(c.states - golden["unique_states"])
        bad_verdict = c.discoveries != sorted(golden["discoveries"])
        count_gap = max(count_gap, gap)
        mismatches += int(bad_verdict)
        c.right = not (gap > 0 or bad_verdict)
        wrong += int(not c.right)
    return wrong, {
        "count_gap": (count_gap, 0),
        "verdict_mismatches": (mismatches, 0),
        "errors": (sum(c.error is not None for c in checks), 0),
    }
