"""Unique states of the window's right checks, over the window's seconds.

Host clock; the window holds whole checks only. Unique states are fixed by
the spec, so this reads the same work whatever engine does it."""


def read(run):
    states = sum(c.states for c in run.checks if c.right)
    if not states:
        return None
    return states / run.window_s
