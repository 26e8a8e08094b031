"""Readers of the program's profiled phase totals."""

from types import SimpleNamespace

import jax
import pytest

from benchmark import run
from benchmark.harness import cell as cells
from benchmark.harness.window import Check
from benchmark.tests.cells import CPU_PEAKS, SINGLE_COPY_2X2, TPC3, tiny
from stateright_tpu.obs import spans

READERS = {
    "path_download_ms": ("path_download",),
    "path_walk_ms": ("path_walk",),
    "path_replay_ms": ("path_replay",),
    "engine_host_ms": ("engine_init", "run_setup", "upload", "era_dispatch", "era_host"),
    "grow_ms_per_check": ("table_grow",),
}


def window(n=4, traced=True):
    """`n` checks that each found a counterexample and grew the table once."""
    checks = [Check(rows=[[0]], counters={"table_growths": 1}) for _ in range(n)]
    return SimpleNamespace(checks=checks, trace={"busy_s": 0.0} if traced else None)


@pytest.fixture
def totals():
    spans.reset_profiled_phase_totals()
    yield spans.record_profiled_phase
    spans.reset_profiled_phase_totals()


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_is_none_without_a_trace(name, totals):
    for phase in READERS[name]:
        totals(phase, 1.0)
    assert cells.reader(name)(window(traced=False)) is None


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_divides_the_totals_by_the_window_checks(name, totals):
    for i, phase in enumerate(READERS[name]):
        totals(phase, 0.5 + i)
        totals(phase, 0.5)
    totals("readback", 100.0)  # a phase no reader reads
    expected = 1000.0 * sum(1.0 + i for i in range(len(READERS[name]))) / 4
    assert cells.reader(name)(window(4)) == pytest.approx(expected)


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_raises_when_its_phases_are_missing(name, totals):
    totals("readback", 1.0)
    with pytest.raises(ValueError, match="recorded none"):
        cells.reader(name)(window())


def test_no_work_reads_zero(totals):
    quiet = SimpleNamespace(
        checks=[Check(counters={"table_growths": 0})], trace={"busy_s": 0.0}
    )
    assert cells.reader("grow_ms_per_check")(quiet) == 0.0
    assert cells.reader("path_walk_ms")(quiet) == 0.0


def test_traced_counterexample_cell_reports_the_program_phases():
    resolved = tiny("single-copy-2x2.ttfc", SINGLE_COPY_2X2, "ttfc")
    spans.reset_profiled_phase_totals()
    result, _ = run.run_cell(resolved, 2**31 + 5, 1.0, True, jax.devices()[:1], CPU_PEAKS)
    assert result["correct"]
    metrics = result["metrics"]
    for name in ("path_download_ms", "path_walk_ms", "path_replay_ms", "engine_host_ms"):
        assert metrics[name]["value"] > 0, (name, metrics)
        assert metrics[name]["unit"] == "ms"
    # Host timings on the CPU: the three path phases lie inside path_ms.
    parts = sum(metrics[n]["value"] for n in ("path_download_ms", "path_walk_ms", "path_replay_ms"))
    assert parts <= metrics["path_ms"]["value"]


def test_traced_verdict_cell_reads_growth():
    resolved = tiny("2pc-3.verdict", TPC3, "verdict")
    spans.reset_profiled_phase_totals()
    result, _ = run.run_cell(resolved, 3, 0.5, True, jax.devices()[:1], CPU_PEAKS)
    assert result["correct"]
    # 2pc-3 never grows the default table: the growth reads 0, not an error.
    assert result["metrics"]["grow_ms_per_check"]["value"] == 0.0
