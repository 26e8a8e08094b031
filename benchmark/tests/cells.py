"""Tiny cells for CPU tests: the same window, judges and readers as the
chip's cells, at sizes a test run can hold."""

from benchmark.harness import cell as cells

CPU_PEAKS = {"hbm_bytes": 16e9}


def tiny(workload: str, config: dict, traffic: str) -> dict:
    """A resolved cell as `cells.resolve` returns it, for `config` under the
    benchmark's traffic mix `traffic`, reporting every metric that applies
    to a cell of BENCHMARK.json whose traffic has the same driver."""
    bench = cells.load_benchmark()
    mix = cells.traffic(traffic)
    same = {
        w["name"]
        for w in bench["workloads"]
        if cells.traffic(w["traffic"])["driver"] == mix["driver"]
    }

    def applies(m):
        return "workloads" not in m or same & set(m["workloads"])

    return {
        "cell": {"name": workload, "config": config["name"], "traffic": traffic, "chips": 1},
        "config": config,
        "traffic": mix,
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


TPC3 = {
    "name": "2pc-3",
    "spec": {"factory": "stateright_tpu.models:TwoPhaseTensor", "args": [3]},
    "reference": {"module": "two_phase_commit", "args": [3]},
    "golden": {"unique_states": 288, "discoveries": ["abort agreement", "commit agreement"]},
    "control": "narrow_fingerprints",
}

SINGLE_COPY_2X2 = {
    "name": "single-copy-2x2",
    "spec": {"factory": "stateright_tpu.models.single_copy:SingleCopyTensor", "args": [2, 2]},
    "reference": {"module": "single_copy", "args": [2, 2]},
    "counterexample": {"property": "linearizable"},
    "control": "random_walks",
}
