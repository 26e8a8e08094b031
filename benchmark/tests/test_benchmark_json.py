"""BENCHMARK.json against the benchmark's own files: every name it uses is
found, and the structural rules hold."""

import glob
import json
import os
import re

from benchmark.harness import cell as cells

BENCH = cells.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_keys_and_names():
    assert set(BENCH) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"
    }
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for c in BENCH["configs"]:
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
    for w in BENCH["workloads"]:
        assert 1 <= len(w["why"]) <= 200 and w["chips"] in (1, 4)
    assert 1 <= BENCH["run_seconds"] <= 51


def test_every_name_has_its_file():
    for c in BENCH["configs"]:
        config = cells.load_json(os.path.join(cells.ROOT, c["file"]))
        assert sorted(config["reduced"]) == sorted(c["reduced"])
        assert config["name"] == c["name"]
    for w in BENCH["workloads"]:
        resolved = cells.resolve(BENCH, w["name"])
        traffic, config = resolved["traffic"], resolved["config"]
        assert callable(cells.module("drivers", traffic["driver"]).make_check)
        assert callable(cells.module("judges", traffic["judge"]).judge)
        assert callable(cells.module("controls", config["control"]).install)
        assert any(m["name"] == "setup_s" for m in resolved["end_to_end"])
        assert len(resolved["end_to_end"]) >= 2 and resolved["per_layer"]
    for path in glob.glob(os.path.join(cells.BENCH_DIR, "traffic", "*.json")):
        traffic = cells.traffic(os.path.basename(path)[: -len(".json")])
        assert callable(cells.module("drivers", traffic["driver"]).make_check)
        assert callable(cells.module("judges", traffic["judge"]).judge)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert callable(cells.reader(m["name"]))
        assert set(m.get("workloads", [])) <= {w["name"] for w in BENCH["workloads"]}


def test_bounds_and_chips():
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    assert next(m for m in BENCH["end_to_end"] if m["name"] == "setup_s")["bound"] == 0.25
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 2)


def test_per_layer_moves_an_end_to_end_metric_of_its_cells():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        target = e2e[m["moves"]]
        if "workloads" in m and "workloads" in target:
            assert set(m["workloads"]) <= set(target["workloads"])


def test_file_is_small():
    assert len(json.dumps(BENCH)) < 64 * 1024
