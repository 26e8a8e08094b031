"""The measured window, and what every driver shares.

A traffic mix (`traffic/<name>.json`) is data. It names:

- `driver`: `drivers/<driver>.py`, whose `make_check(spec, config, traffic,
  devices)` returns a function that makes one whole check and returns its
  `Check`;
- `judge`: `judges/<judge>.py`, whose `judge(config, checks)` says which
  checks answered right;
- `spawn`: the `CheckerBuilder` engine method, called with no options but
  `devices` (the cell's chips) where `pass_devices` is true.

Every check goes through the public `checker()` path as a user calls it, on
the one spec object built at set-up, back to back, one at a time (a closed
loop of one client). Each is timed by the host clock from `checker()` to
the answer in hand. Drivers write the benchmark's own host spans
(`bench:spawn`, `bench:join`, `bench:path`) into the profiler's trace so
that idle device time can be put down to what the host was doing.
"""

from __future__ import annotations

import importlib
import time
from dataclasses import dataclass, field
from typing import List, Optional

COUNTERS = ("eras", "steps", "dispatches", "table_growths", "spill_rows")


@dataclass
class Check:
    wall_s: float = 0.0
    states: int = 0
    discoveries: List[str] = field(default_factory=list)
    counters: dict = field(default_factory=dict)  # of `telemetry()`, see COUNTERS
    path_s: Optional[float] = None
    rows: Optional[list] = None  # the counterexample's states
    error: Optional[str] = None
    right: bool = False  # set by the judge


def build_spec(config: dict):
    module, attr = config["spec"]["factory"].split(":")
    return getattr(importlib.import_module(module), attr)(*config["spec"]["args"])


def spawn(options, traffic: dict, devices):
    """Start the engine the traffic mix names on a `CheckerBuilder`."""
    kwargs = {"devices": list(devices)} if traffic.get("pass_devices") else {}
    return getattr(options, traffic["spawn"])(**kwargs)


def finish(rec: Check, checker, t0: float) -> Check:
    """Close a check begun at `t0`: its wall time, count and counters."""
    rec.wall_s = time.perf_counter() - t0
    rec.states = checker.unique_state_count()
    tel = checker.telemetry()
    rec.counters = {k: int(tel.get(k, 0)) for k in COUNTERS}
    return rec


def guarded(check) -> Check:
    """One check; an exception is its answer."""
    try:
        return check()
    except Exception as exc:  # noqa: BLE001 — a failed check is a result
        return Check(error=repr(exc)[:500])


def run_window(check, seconds: float):
    """Checks back to back until `seconds` have passed; the check in flight
    then finishes, so the window holds whole checks only. A check that
    raises ends the window. Returns (checks, window seconds)."""
    checks = []
    t0 = time.perf_counter()
    while True:
        checks.append(guarded(check))
        if checks[-1].error is not None or time.perf_counter() - t0 >= seconds:
            break
    return checks, time.perf_counter() - t0
