"""The mesh exchange's share of device time, and the four-chip cell's
per-layer metrics at a tiny size on the CPU."""

from types import SimpleNamespace

import jax

from benchmark import run
from benchmark.harness import cell as cells
from benchmark.tests.cells import CPU_PEAKS, TPC3, tiny


def test_exchange_share_of_busy_time():
    read = cells.reader("exchange_share")
    assert read(SimpleNamespace(trace=None)) is None
    assert read(SimpleNamespace(trace={"busy_s": 0.0, "collective_s": 0.0})) is None
    assert read(SimpleNamespace(trace={"busy_s": 8.0, "collective_s": 2.0})) == 25.0
    assert read(SimpleNamespace(trace={"busy_s": 8.0, "collective_s": 0.0})) == 0.0


def test_traced_four_chip_cell_reports_its_counters():
    # No TPU plane on the CPU: the device readers find nothing, the
    # counters of the mesh are per check.
    resolved = tiny("2pc-3.verdict-x4", TPC3, "verdict-x4")
    result, memory = run.run_cell(resolved, 2**31 + 4, 0.5, True, jax.devices()[:4], CPU_PEAKS)
    assert result["correct"], result["compared"]
    metrics = result["metrics"]
    assert "exchange_share" not in metrics and "device_us_per_step" not in metrics
    assert metrics["dispatches_per_check"]["value"] >= 1
    assert metrics["grow_ms_per_check"]["value"] >= 0
    assert len({c for c in memory["check_counters"]}) == 1
