"""Two-phase commit as a plain breadth-first search.

Gray and Lamport's "Consensus on Transaction Commit" as upstream stateright
states it in `examples/2pc.rs`: one transaction manager, `n` resource
managers, a set of messages that is only ever added to. A state is a tuple
`(rm_states, tm_state, tm_prepared, msgs)`; the search keeps every state in a
Python set, so two states are the same exactly when their tuples are equal.

    python benchmark/reference/two_phase_commit.py 7   # prints the count

This file imports nothing of the system under test.
"""

from __future__ import annotations

import sys

WORKING, PREPARED, COMMITTED, ABORTED = range(4)
TM_INIT, TM_COMMITTED, TM_ABORTED = range(3)
COMMIT, ABORT = "commit", "abort"


def init_state(n: int):
    return ((WORKING,) * n, TM_INIT, frozenset(), frozenset())


def _with(rms, rm, value):
    return rms[:rm] + (value,) + rms[rm + 1 :]


def successors(state, n: int):
    rms, tm, prepared, msgs = state
    out = []
    if tm == TM_INIT and len(prepared) == n:
        out.append((rms, TM_COMMITTED, prepared, msgs | {COMMIT}))
    if tm == TM_INIT:
        out.append((rms, TM_ABORTED, prepared, msgs | {ABORT}))
    for rm in range(n):
        if tm == TM_INIT and rm in msgs:
            out.append((rms, tm, prepared | {rm}, msgs))
        if rms[rm] == WORKING:
            out.append((_with(rms, rm, PREPARED), tm, prepared, msgs | {rm}))
            out.append((_with(rms, rm, ABORTED), tm, prepared, msgs))
        if COMMIT in msgs:
            out.append((_with(rms, rm, COMMITTED), tm, prepared, msgs))
        if ABORT in msgs:
            out.append((_with(rms, rm, ABORTED), tm, prepared, msgs))
    return out


PROPERTIES = {
    # name: (expectation, predicate)
    "abort agreement": ("sometimes", lambda s: all(r == ABORTED for r in s[0])),
    "commit agreement": ("sometimes", lambda s: all(r == COMMITTED for r in s[0])),
    "consistent": (
        "always",
        lambda s: not (ABORTED in s[0] and COMMITTED in s[0]),
    ),
}


def explore(n: int):
    """Every reachable state. Returns (unique count, names of the
    properties with a discovery: a sometimes-property that some state
    satisfies, an always-property that some state breaks)."""
    start = init_state(n)
    seen = {start}
    frontier = [start]
    while frontier:
        nxt = []
        for s in frontier:
            for t in successors(s, n):
                if t not in seen:
                    seen.add(t)
                    nxt.append(t)
        frontier = nxt
    found = set()
    for name, (kind, pred) in PROPERTIES.items():
        hit = any(pred(s) for s in seen) if kind == "sometimes" else not all(
            pred(s) for s in seen
        )
        if hit:
            found.add(name)
    return len(seen), sorted(found)


if __name__ == "__main__":
    count, found = explore(int(sys.argv[1]))
    print(count, found)
