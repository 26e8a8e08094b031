"""Chip smoke: the checker's main path on the TPU, every result golden-checked.

    python chip_smoke.py             # one chip: 2pc-7, paxos-3, single-copy-4,
                                     # the increment race, the serve path
    python chip_smoke.py --full      # also 2pc-10 exhaustive (61,515,776 states)
    python chip_smoke.py --chips 4   # only 2pc-7 and paxos-3 sharded over four
                                     # chips, and the same specs solo

Every phase runs through the public entry points with default options
(`CheckerBuilder.spawn_tpu_bfs`, `spawn_sharded_bfs`, `RunService` behind
`ServeServer`) and prints one JSON line: spec, golden, wall seconds with
compilation reported apart as set-up, eras and dispatches, the device kind,
and the platform of an array the engine produced. The last line is
``{"ok": true, "device": {...}}``. With no TPU, or when a phase misses its
golden, the script exits nonzero and prints no such line. Everything runs in
this one process, which alone holds the chip.
"""

from __future__ import annotations

import argparse
import json
import sys
import threading
import time

TPC7_GOLDEN = 296_448
PAXOS3_GOLDEN = 1_194_428
SINGLE_COPY4_GOLDEN = 400_233
TPC10_GOLDEN = 61_515_776
# increment:2 / 2pc:3 / 2pc:5 through the serve path's multiplex lanes.
SERVE_GOLDENS = {"increment:2": 13, "2pc:3": 288, "2pc:5": 8_832}

_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


class CompileClock:
    """Wall seconds in which JAX was tracing, lowering or compiling (its
    own monitoring events), so a phase's set-up can be reported apart.
    Nested traces overlap, so the clock counts the union of the intervals."""

    def __init__(self):
        import jax

        self._spans = []
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_kw):
        if event in _COMPILE_EVENTS:
            end = time.perf_counter()
            with self._lock:
                self._spans.append((end - duration, end))

    def secs_between(self, t0: float, t1: float) -> float:
        with self._lock:
            spans = sorted(
                (max(a, t0), min(b, t1)) for a, b in self._spans if b > t0 and a < t1
            )
        total, cur_a, cur_b = 0.0, None, None
        for a, b in spans:
            if cur_b is None or a > cur_b:
                if cur_b is not None:
                    total += cur_b - cur_a
                cur_a, cur_b = a, b
            else:
                cur_b = max(cur_b, b)
        if cur_b is not None:
            total += cur_b - cur_a
        return total


def _platforms(arrays) -> list:
    return sorted({d.platform for a in arrays for d in a.devices()})


def _device_ids(arrays) -> list:
    return sorted({d.id for a in arrays for d in a.devices()})


def _timed(clock, fn):
    t0 = time.perf_counter()
    out = fn()
    t1 = time.perf_counter()
    return out, t1 - t0, clock.secs_between(t0, t1)


def _check_run(c, golden) -> dict:
    tel = c.telemetry()
    if c.unique_state_count() != golden:
        raise AssertionError(
            f"unique_state_count {c.unique_state_count()} != golden {golden}"
        )
    table = c._table_dev
    platforms = _platforms(table)
    if platforms != ["tpu"]:
        raise AssertionError(f"engine table lives on {platforms}, not the TPU")
    return {
        "eras": int(tel.get("eras", 0)),
        "dispatches": int(tel.get("dispatches", 0)),
        "array_platform": platforms[0],
        "array_devices": _device_ids(table),
    }


def bfs_phase(clock, spec, tm, golden, spawn, runs=2, check=None) -> dict:
    """`runs` runs of one spec (the first compiles, the next reuse the
    executables); each must land on the golden."""
    from stateright_tpu import TensorModelAdapter

    row = {"spec": spec, "golden": golden}
    for i in range(runs):
        c, wall, comp = _timed(
            clock, lambda: spawn(TensorModelAdapter(tm).checker()).join()
        )
        info = _check_run(c, golden)
        if check is not None:
            info.update(check(c))
        tag = "cold" if i == 0 else "warm"
        row.update(
            {
                f"{tag}_wall_s": wall,
                f"{tag}_setup_compile_s": comp,
                f"{tag}_run_s": wall - comp,
            }
        )
        row.update(info)
    return row


def increment_race_check(c) -> dict:
    trace = c.discovery("fin")
    if trace is None:
        raise AssertionError("no counterexample for 'fin'")
    actions = trace.into_actions()
    if len(actions) != 4:
        raise AssertionError(f"counterexample has {len(actions)} steps, not 4")
    return {"counterexample_steps": len(actions)}


def serve_phase(clock) -> dict:
    import jax

    from stateright_tpu.serve import RunService, ServeServer

    seen = set()  # platforms of the arrays live while the jobs ran

    def work():
        svc = RunService(workers=1, lanes=8, lint_samples=32)
        server = ServeServer(svc, "127.0.0.1:0").serve_in_background()
        base = server.url.rstrip("/")

        def req(method, path, body=None):
            import urllib.request

            data = json.dumps(body).encode() if body is not None else None
            r = urllib.request.Request(base + path, data=data, method=method)
            with urllib.request.urlopen(r, timeout=600) as resp:
                return json.loads(resp.read())

        try:
            req("POST", "/scheduler/pause")
            ids = {
                spec: req("POST", "/submit", {"spec": spec})["job_id"]
                for spec in SERVE_GOLDENS
            }
            req("POST", "/scheduler/resume")
            deadline = time.monotonic() + 600
            while time.monotonic() < deadline:
                seen.update(_platforms(jax.live_arrays()))
                views = req("GET", "/jobs")["jobs"]
                if all(v["status"] not in ("queued", "running") for v in views):
                    break
                time.sleep(0.05)
            got = {}
            for spec, job_id in ids.items():
                body = req("GET", f"/jobs/{job_id}/result")
                result = body.get("result") or {}
                got[spec] = (result.get("unique_state_count"), result.get("engine"))
            return got
        finally:
            server.shutdown()

    got, wall, comp = _timed(clock, work)
    for spec, golden in SERVE_GOLDENS.items():
        count, engine = got[spec]
        if count != golden or engine != "multiplex":
            raise AssertionError(f"{spec}: {count} via {engine}, want {golden} via multiplex")
    return {
        "spec": "serve " + ",".join(SERVE_GOLDENS),
        "golden": list(SERVE_GOLDENS.values()),
        "got": [got[s][0] for s in SERVE_GOLDENS],
        "cold_wall_s": wall,
        "cold_setup_compile_s": comp,
        "cold_run_s": wall - comp,
        "live_array_platforms": sorted(seen),
    }


def one_chip_phases(full: bool):
    from stateright_tpu.models import IncrementTensor, TwoPhaseTensor
    from stateright_tpu.models.paxos import PaxosTensorExhaustive
    from stateright_tpu.models.single_copy import SingleCopyTensor

    def solo(b):
        return b.spawn_tpu_bfs()

    def phase(spec, tm, golden, spawn=solo, **kw):
        return lambda clk: bfs_phase(clk, spec, tm, golden, spawn, **kw)

    phases = [
        ("2pc-7", phase("TwoPhaseTensor(7)", TwoPhaseTensor(7), TPC7_GOLDEN)),
        ("paxos-3", phase("PaxosTensorExhaustive(3)", PaxosTensorExhaustive(3), PAXOS3_GOLDEN)),
        ("single-copy-4", phase("SingleCopyTensor(4)", SingleCopyTensor(4), SINGLE_COPY4_GOLDEN)),
        ("increment-race", phase("IncrementTensor(2)", IncrementTensor(2), 13, check=increment_race_check)),
        ("serve", serve_phase),
    ]
    if full:
        # Sized up front as bench.py sizes it (the table 61.5M states end
        # in, 2^28 slots = 4 GiB), so the run neither rehashes six times nor
        # spills its frontier to the host; every other option is default.
        def solo_2pc10(b):
            return b.spawn_tpu_bfs(
                chunk_size=12288, queue_capacity=1 << 24, table_capacity=1 << 28
            )

        phases.append(
            ("2pc-10", phase("TwoPhaseTensor(10)", TwoPhaseTensor(10), TPC10_GOLDEN, solo_2pc10, runs=1))
        )
    return phases


def four_chip_phases(devices):
    from stateright_tpu.models import TwoPhaseTensor
    from stateright_tpu.models.paxos import PaxosTensorExhaustive

    def sharded(b):
        return b.spawn_sharded_bfs(devices=devices)

    def solo(b):
        return b.spawn_tpu_bfs()

    def peaks(row):
        row["peak_bytes_in_use"] = [
            (d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices
        ]
        return row

    specs = [
        ("2pc-7", "TwoPhaseTensor(7)", TwoPhaseTensor(7), TPC7_GOLDEN),
        ("paxos-3", "PaxosTensorExhaustive(3)", PaxosTensorExhaustive(3), PAXOS3_GOLDEN),
    ]

    def phase(spec, tm, golden, spawn):
        return lambda clk: peaks(bfs_phase(clk, spec, tm, golden, spawn))

    # Sharded first, so each device's peak shows the sharded run alone.
    return [
        (f"{name} sharded x{len(devices)}", phase(spec, tm, golden, sharded))
        for name, spec, tm, golden in specs
    ] + [
        (f"{name} solo", phase(spec, tm, golden, solo))
        for name, spec, tm, golden in specs
    ]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--full", action="store_true", help="add 2pc-10 exhaustive")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(
            f"chip_smoke: JAX found no TPU (platform {devices[0].platform!r}); "
            "nothing was run",
            file=sys.stderr,
        )
        return 2
    if len(devices) < args.chips:
        print(
            f"chip_smoke: --chips {args.chips} needs {args.chips} TPUs, "
            f"JAX found {len(devices)}",
            file=sys.stderr,
        )
        return 2

    from stateright_tpu.compat import configure_compilation_cache

    configure_compilation_cache()
    kind = devices[0].device_kind
    clock = CompileClock()
    if args.chips == 4:
        phases = four_chip_phases(devices[:4])
    else:
        phases = one_chip_phases(args.full)

    failed = []
    for name, run in phases:
        try:
            row = {"phase": name, "ok": True, **run(clock)}
        except Exception as exc:  # noqa: BLE001 — report, then fail the script
            row = {"phase": name, "ok": False, "error": repr(exc)[:500]}
            failed.append(name)
        row["device_kind"] = kind
        print(json.dumps(row), flush=True)

    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    print(
        json.dumps(
            {
                "ok": True,
                "device": {
                    "platform": devices[0].platform,
                    "kind": kind,
                    "count": len(devices),
                },
            }
        ),
        flush=True,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
