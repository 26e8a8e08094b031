"""Find a cell's configuration, traffic mix and code by name.

Everything that belongs to one configuration, one traffic mix or one metric
sits in a file of its own under `benchmark/`: `configs/<config>.json`,
`traffic/<traffic>.json` and `metrics/<metric>.py`. A traffic mix names the
driver that makes its checks (`drivers/<driver>.py`) and the judge of their
answers (`judges/<judge>.py`); a configuration names its control
(`controls/<control>.py`). `BENCHMARK.json` names the rest; nothing here
lists cells, modes or metrics, so a new one is a new file.
"""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def traffic(name: str) -> dict:
    return load_json(os.path.join(BENCH_DIR, "traffic", name + ".json"))


def resolve(bench: dict, workload: str, root: str = ROOT) -> dict:
    """The cell `workload` with its configuration and traffic mix loaded,
    and the metrics it reports: `end_to_end` (with `--trace 0`) and
    `per_layer` (with `--trace 1`), each a list of metric entries."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; have {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}

    def applies(metric):
        return "workloads" not in metric or workload in metric["workloads"]

    return {
        "cell": cell,
        "config": load_json(os.path.join(root, configs[cell["config"]]["file"])),
        "traffic": traffic(cell["traffic"]),
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


def module(kind: str, name: str):
    """The module in `benchmark/<kind>/<name>.py` (a name may hold dots)."""
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"benchmark_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name: str):
    """The `read(run)` function of `metrics/<name>.py`."""
    return module("metrics", name).read
