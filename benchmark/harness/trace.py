"""Reduce a profiler trace of the window to device busy time and its gaps.

The JAX profiler writes `<dir>/plugins/profile/<time>/<host>.xplane.pb`. In
it, each chip is a plane `/device:TPU:<id>` whose line `XLA Ops` holds one
event per operation run (a `while` holds the operations of its body), and
whose line `XLA Modules` holds one event per program run. The host plane
holds the benchmark's own spans (`bench:*`, written with
`jax.profiler.TraceAnnotation`). Device and host events share one clock.

`load` keeps what the reduction needs; `reduce` computes, within the span
`bench:window`:

- `busy_s`: the union of a chip's operation intervals, mean over the chips;
- `collective_s`: the union of its collective operations (opcode
  all-to-all, all-gather, all-reduce, reduce-scatter, collective-permute),
  mean over the chips: what a multi-chip cell's exchange metric would read
  (no cell reads it yet);
- `device_ops`: the ten operations with the most self time on the first
  chip (time not covered by an operation nested in them), as
  `[module/op, seconds]`;
- `idle_gaps`: the first chip's idle time within the window, summed by the
  benchmark span open at the time (`bench:window` where none of spawn, join
  and path is), ten largest first.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict

# On the TPU an `XLA Ops` event is named by its instruction's text. Match by
# opcode, not by name: `lax.all_to_all` compiles for a v5e 2x2 mesh to
# `%all_to_all.678 = ... all-to-all(...)`, and a psum to an `all-reduce` of
# any name.
_COLLECTIVE = re.compile(
    r" (all-to-all|all-gather|all-reduce|reduce-scatter|collective-permute)(-start|-done)?\("
)
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


def short_op(text: str) -> str:
    """`%fusion.12 = u32[8]{0} fusion(...)` -> `fusion.12`."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def short_module(text: str) -> str:
    """`jit_loop(1326472792497226314)` -> `jit_loop`."""
    return text.split("(", 1)[0]


def load(xplane_path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    devices = {}
    host = []
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            lines = {ln.name: ln for ln in plane.lines}
            ops = lines.get("XLA Ops")
            mods = lines.get("XLA Modules")
            events = [
                (e.name, e.start_ns, e.start_ns + e.duration_ns)
                for e in (ops.events if ops is not None else ())
            ]
            devices[int(m.group(1))] = {
                "ops": [(short_op(text), a, b) for text, a, b in events],
                "collectives": [(a, b) for text, a, b in events if _COLLECTIVE.search(text)],
                "modules": [
                    (short_module(e.name), e.start_ns, e.start_ns + e.duration_ns)
                    for e in (mods.events if mods is not None else ())
                ],
            }
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith("bench:"):
                        host.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
    return {"devices": devices, "host": host}


def merge(intervals, lo, hi):
    """Sorted disjoint union of `(start, end)` intervals clipped to [lo, hi]."""
    out = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return out


def _length(merged) -> float:
    return sum(b - a for a, b in merged)


def self_times(ops, modules, lo, hi) -> dict:
    """Seconds of self time by `module/op` for ops that start in [lo, hi]."""
    ops = sorted((e for e in ops if lo <= e[1] < hi), key=lambda e: (e[1], -e[2]))
    mods = sorted(modules, key=lambda e: e[1])
    totals = defaultdict(float)
    stack = []  # [name, start, end, child_ns]
    mod_i = 0

    def close(entry):
        name, a, b, child = entry
        totals[name] += max(0.0, (b - a) - child) / 1e9

    for name, a, b in ops:
        while stack and stack[-1][2] <= a:
            close(stack.pop())
        if stack:
            stack[-1][3] += b - a
        while mod_i + 1 < len(mods) and mods[mod_i + 1][1] <= a:
            mod_i += 1
        module = mods[mod_i][0] if mods and mods[mod_i][1] <= a < mods[mod_i][2] else "?"
        stack.append([f"{module}/{name}", a, b, 0.0])
    while stack:
        close(stack.pop())
    return totals


def split_by_span(gaps, spans):
    """Seconds of each `(start, end)` gap by the span it falls in, for spans
    that do not overlap (the benchmark's spawn, join and path spans follow
    one another); time in none of them counts as `bench:window`."""
    spans = sorted(spans, key=lambda s: s[1])
    starts = [s[1] for s in spans]
    out = defaultdict(float)
    for a, b in gaps:
        k = max(0, bisect.bisect_right(starts, a) - 1)
        t = a
        while t < b:
            while k < len(spans) and spans[k][2] <= t:
                k += 1
            if k < len(spans) and spans[k][1] <= t:
                name, end = spans[k][0], min(b, spans[k][2])
            else:
                name, end = "bench:window", min(b, spans[k][1]) if k < len(spans) else b
            out[name] += (end - t) / 1e9
            t = end
    return out


def reduce(trace: dict, device_ids) -> dict:
    windows = [(a, b) for name, a, b in trace["host"] if name == "bench:window"]
    if not windows:
        raise ValueError("the trace holds no bench:window span")
    lo, hi = windows[0]
    busy, coll = [], []
    for dev in device_ids:
        lines = trace["devices"].get(dev, {})
        busy.append(_length(merge([(a, b) for _n, a, b in lines.get("ops", [])], lo, hi)))
        coll.append(_length(merge(lines.get("collectives", []), lo, hi)))
    first = trace["devices"].get(device_ids[0], {"ops": [], "modules": []})
    merged = merge([(a, b) for _n, a, b in first["ops"]], lo, hi)
    edges = [lo] + [x for ab in merged for x in ab] + [hi]
    gaps = split_by_span(
        [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a],
        [s for s in trace["host"] if s[0] != "bench:window"],
    )
    ops = self_times(first["ops"], first["modules"], lo, hi)
    n = len(device_ids)
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy) / n / 1e9,
        "collective_s": sum(coll) / n / 1e9,
        "device_ops": [[k, v] for k, v in sorted(ops.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": [[k, v] for k, v in sorted(gaps.items(), key=lambda kv: -kv[1])[:10]],
    }


def idle_percent(reduction):
    """Percent of the traced window in which no operation ran on the
    device, or None for an untraced run."""
    if reduction is None or reduction["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - reduction["busy_s"] / reduction["window_s"])


def reduce_dir(trace_dir: str, device_ids) -> dict:
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if len(paths) != 1:
        raise ValueError(f"expected one xplane.pb under {trace_dir}, found {len(paths)}")
    return reduce(load(paths[0]), list(device_ids))
