"""Run one cell of BENCHMARK.json on the chips of this machine.

    python benchmark/run.py --workload 2pc-7.verdict --seed 7 --seconds 30 --trace 0

One process holds the cell's chips. It loads the cell by name, turns on
JAX's persistent compilation cache at `benchmark/.jax_cache`, builds the
spec once, makes one warm-up check (set-up ends there), then makes checks
back to back until `--seconds` have passed and lets the one in flight
finish. Every check of the window is judged by the plain reference. With
`--trace 0` the last line of standard output reports the cell's end-to-end
metrics, with `--trace 1` its per-layer metrics from a profiler trace of the
whole window. The numbers compared, each with its limit, are the last lines
of standard error and the last key of that line.

Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result. No verdict depends on `--seed`: the specs are fixed and
breadth-first search is deterministic; the seed is recorded.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
CACHE_DIR = os.path.join(BENCH_DIR, ".jax_cache")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import cell as cells  # noqa: E402
from benchmark.harness.clock import CompileClock  # noqa: E402
from benchmark.harness.window import build_spec, guarded, run_window  # noqa: E402


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def configure_cache(jax) -> None:
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def load_peaks(kind: str) -> dict:
    table = cells.load_json(os.path.join(BENCH_DIR, "peaks.json"))["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in benchmark/peaks.json")
    return table[kind]


def run_cell(resolved: dict, seed: int, seconds: float, trace: bool, devices, peaks: dict,
             t_start: float = T_START):
    """Set-up, window, judgement and metrics of one run. Returns the result
    line as a dict (with `compared` last) and the memory line."""
    import jax

    from benchmark.harness import trace as tracing

    config, traffic = resolved["config"], resolved["traffic"]
    judge = cells.module("judges", traffic["judge"]).judge
    clock = CompileClock()
    spec = build_spec(config)
    check = cells.module("drivers", traffic["driver"]).make_check(spec, config, traffic, devices)
    warmup = guarded(check)
    t_window = time.perf_counter()
    setup_s = t_window - t_start
    setup_trace_s = clock.secs_between(t_start, t_window)

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # keep the host at its untraced pace
        options.enable_hlo_proto = False
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    with jax.profiler.TraceAnnotation("bench:window"):
        if warmup.error is None:
            checks, window_s = run_window(check, seconds)
        else:  # a warm-up that failed leaves nothing worth timing
            checks, window_s = [], 0.0
    if trace:
        jax.profiler.stop_trace()
    window_compile_s = clock.secs_between(t_window, time.perf_counter())
    peak = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    del check, spec

    reduction = None
    if trace:
        try:
            reduction = tracing.reduce_dir(trace_dir, [d.id for d in devices])
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)

    wrong_warmup, compared_warmup = judge(config, [warmup])
    wrong, compared = judge(config, checks) if checks else (0, compared_warmup)
    run = SimpleNamespace(
        chips=len(devices), setup_s=setup_s, setup_trace_s=setup_trace_s,
        window_s=window_s, checks=checks, trace=reduction,
    )
    metrics = {}
    for m in resolved["per_layer" if trace else "end_to_end"]:
        value = cells.reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    within = all(v <= limit for v, limit in compared.values())
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(jax.devices()),
        "memory_peak_bytes": max(peak),
    }
    result = {
        "correct": bool(checks) and wrong == 0 and wrong_warmup == 0 and within,
        "attempted": len(checks),
        "failed": wrong,
        "metrics": metrics,
        "device": device,
    }
    if reduction is not None:
        device["busy_s"] = reduction["busy_s"]
        device["window_s"] = reduction["window_s"]
        result["breakdown"] = {
            "device_ops": reduction["device_ops"],
            "idle_gaps": reduction["idle_gaps"],
        }
    result["workload"] = resolved["cell"]["name"]
    result["seed"] = seed
    result["compared"] = {k: {"value": v, "limit": lim} for k, (v, lim) in compared.items()}
    memory = {
        "memory": [
            {"device": d.id, "peak_bytes_in_use": p, "hbm_bytes": peaks["hbm_bytes"],
             "share": p / peaks["hbm_bytes"]}
            for d, p in zip(devices, peak)
        ],
        "window_checks": len(checks),
        "window_compile_s": window_compile_s,
        "warmup_wall_s": warmup.wall_s,
        "check_wall_s": [c.wall_s for c in checks],
        "check_counters": sorted({tuple(sorted(c.counters.items())) for c in checks}),
    }
    return result, memory


def main(argv=None) -> int:
    args = parse_args(argv)
    resolved = cells.resolve(cells.load_benchmark(), args.workload)
    chips = resolved["cell"]["chips"]

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(
            f"benchmark: {args.workload} needs {chips} TPU chip(s); JAX found "
            f"{len(devices)} {devices[0].platform!r} device(s). Nothing was run.",
            file=sys.stderr,
        )
        return 2
    configure_cache(jax)
    peaks = load_peaks(devices[0].device_kind)
    result, memory = run_cell(
        resolved, args.seed, args.seconds, bool(args.trace), devices[:chips], peaks
    )
    print(json.dumps(memory), flush=True)
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']} limit {c['limit']}", file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
