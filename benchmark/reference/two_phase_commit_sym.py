"""Two-phase commit under upstream's symmetry reduction, as a plain
breadth-first search of the canonical closure.

Upstream `examples/2pc.rs` (`check-sym`) reduces a state to its
representative: the resource managers (RMs) stably sorted by their state,
each carrying its `tm_prepared` flag and its `Prepared` message along
(`representative`, examples/2pc.rs:203-229). Ties between RMs in the same
state keep their order, so two equivalent states need not share one
representative. The closure starts from the representative of the initial
state, and the successors of every representative it holds are replaced
by their representatives. That is the set a batched breadth-first search
with symmetry explores, whatever the order of its visits.

    python -m benchmark.reference.two_phase_commit_sym 10

prints the closure's size, the properties with a discovery, the successors
generated, how many of them the representative changed, and the depth.

This file imports nothing of the system under test.
"""

from __future__ import annotations

import functools
import sys

from . import two_phase_commit as tpc


def representative(state, n: int):
    """The state with its RMs stably sorted by RM state."""
    rms, tm, prepared, msgs = state
    order = sorted(range(n), key=lambda i: rms[i])
    new_index = {old: new for new, old in enumerate(order)}
    return (
        tuple(rms[i] for i in order),
        tm,
        frozenset(new_index[i] for i in prepared),
        frozenset(new_index[m] if isinstance(m, int) else m for m in msgs),
    )


@functools.lru_cache(maxsize=4)
def _closure(n: int):
    """(representatives, successors generated, successors the
    representative changed, breadth-first levels)."""
    start = representative(tpc.init_state(n), n)
    seen = {start}
    frontier = [start]
    generated = changed = levels = 0
    while frontier:
        levels += 1
        nxt = []
        for s in frontier:
            for t in tpc.successors(s, n):
                r = representative(t, n)
                generated += 1
                changed += r != t
                if r not in seen:
                    seen.add(r)
                    nxt.append(r)
        frontier = nxt
    return frozenset(seen), generated, changed, levels


def explore_sym(n: int):
    """(unique count of the closure, names of the properties with a
    discovery in it)."""
    seen = _closure(n)[0]
    found = []
    for name, (kind, pred) in tpc.PROPERTIES.items():
        holds = [pred(s) for s in seen]
        if (any(holds) if kind == "sometimes" else not all(holds)):
            found.append(name)
    return len(seen), sorted(found)


def generated(n: int) -> int:
    """Successors generated from every representative of the closure."""
    return _closure(n)[1]


def rewrites(n: int) -> int:
    """Generated successors that differ from their representative: what
    the checker's `canon_rewrites` counts."""
    return _closure(n)[2]


def from_lanes(row, n: int):
    """A state from the checker's three uint32 lanes: lane 0 is the
    transaction manager's state in bits 0-1 and the `tm_prepared` flag of
    RM i in bit 2+i; lane 1 holds RM i's state in bits 2i and 2i+1; lane 2
    holds RM i's `Prepared` message in bit i, `Commit` in bit 30 and
    `Abort` in bit 31. The state codes are those of `two_phase_commit`."""
    lane0, lane1, lane2 = (int(v) for v in row)
    msgs = {i for i in range(n) if (lane2 >> i) & 1}
    if (lane2 >> 30) & 1:
        msgs.add(tpc.COMMIT)
    if (lane2 >> 31) & 1:
        msgs.add(tpc.ABORT)
    return (
        tuple((lane1 >> (2 * i)) & 3 for i in range(n)),
        lane0 & 3,
        frozenset(i for i in range(n) if (lane0 >> (2 + i)) & 1),
        frozenset(msgs),
    )


def judge_path(rows, n: int, prop: str) -> dict:
    """Replay an example path of `prop` given as lane rows. Counts the
    steps that are no step of the closure (the first row counts as one
    unless it is the initial state's representative; each later row has
    to be the representative of a successor of the row before), and says
    whether the last row is an example of `prop`: a state that satisfies
    a sometimes-property, or breaks an always-property."""
    states = [from_lanes(r, n) for r in rows]
    bad = int(states[0] != representative(tpc.init_state(n), n))
    for a, b in zip(states, states[1:]):
        if b not in {representative(t, n) for t in tpc.successors(a, n)}:
            bad += 1
    kind, pred = tpc.PROPERTIES[prop]
    last = pred(states[-1])
    return {
        "invalid_steps": bad,
        "met": last if kind == "sometimes" else not last,
        "length": len(states),
    }


if __name__ == "__main__":
    size = int(sys.argv[1])
    count, found = explore_sym(size)
    print(count, found, generated(size), rewrites(size), _closure(size)[3])
