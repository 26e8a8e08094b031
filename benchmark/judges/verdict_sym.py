"""Judge exhaustive symmetric checks: the verdict judge's count and set of
properties against the closure reference's answer, and every example path
replayed through that reference.

A path has to start at the representative of the initial state, each row
has to be the representative of a successor of the row before
(`reference/<module>.py` `judge_path`), and its last row has to be an
example of its property. Every limit is 0.
"""

from __future__ import annotations

import importlib

from benchmark.judges import verdict


def judge(config: dict, checks) -> tuple:
    """(number of wrong checks, {name: (value, limit)})."""
    ref = importlib.import_module("benchmark.reference." + config["reference"]["module"])
    args = config["reference"]["args"]
    _, compared = verdict.judge(config, checks)
    wrong = 0
    invalid = 0
    unmet = 0
    for c in checks:
        if c.error is not None:
            wrong += 1
            continue
        bad = 0
        for name, rows in c.examples.items():
            r = ref.judge_path(rows, *args, name)
            invalid = max(invalid, r["invalid_steps"])
            unmet += int(not r["met"])
            bad += int(r["invalid_steps"] > 0 or not r["met"])
        c.right = c.right and bad == 0
        wrong += int(not c.right)
    return wrong, {
        **compared,
        "invalid_steps": (invalid, 0),
        "unmet_examples": (unmet, 0),
    }
