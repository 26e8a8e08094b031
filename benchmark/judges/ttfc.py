"""Judge counterexamples by replaying them through the reference.

Each counterexample is replayed state by state: every step has to be a
step of the reference machine, the last state has to break the property,
and its depth has to be the reference's least depth of such a state,
computed here after the window. Every limit is 0.
"""

from __future__ import annotations

import importlib


def judge(config: dict, checks) -> tuple:
    """(number of wrong checks, {name: (value, limit)})."""
    ref = importlib.import_module("benchmark.reference." + config["reference"]["module"])
    args = config["reference"]["args"]
    least = ref.shortest_counterexample_depth(*args)
    wrong = 0
    invalid = 0
    holds = 0
    depth_gap = 0
    missing = 0
    for c in checks:
        if c.error is not None or c.rows is None:
            wrong += 1
            missing += 1
            continue
        r = ref.judge_path(c.rows, *args)
        gap = abs(r["depth"] - least)
        invalid = max(invalid, r["invalid_steps"])
        holds += int(r["final_holds"])
        depth_gap = max(depth_gap, gap)
        c.right = not (r["invalid_steps"] > 0 or r["final_holds"] or gap > 0)
        wrong += int(not c.right)
    return wrong, {
        "invalid_steps": (invalid, 0),
        "final_holds": (holds, 0),
        "depth_gap": (depth_gap, 0),
        "missing": (missing, 0),
    }
