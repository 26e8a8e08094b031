"""The measured window, judges and metric readers at tiny specs on the CPU."""

import jax
import pytest

from benchmark import run
from benchmark.tests.cells import CPU_PEAKS, SINGLE_COPY_2X2, TPC3, tiny


def one(resolved, chips=1, seconds=0.5):
    return run.run_cell(resolved, 2**31 + 11, seconds, False, jax.devices()[:chips], CPU_PEAKS)


@pytest.mark.parametrize(
    "workload,config,traffic,chips",
    [
        ("2pc-3.verdict", TPC3, "verdict", 1),
        ("single-copy-2x2.ttfc", SINGLE_COPY_2X2, "ttfc", 1),
        ("2pc-3.verdict-x4", TPC3, "verdict-x4", 4),
    ],
)
def test_cell_is_correct_and_reports_its_metrics(workload, config, traffic, chips):
    resolved = tiny(workload, config, traffic)
    result, memory = one(resolved, chips)
    assert result["correct"], result["compared"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert list(result)[-1] == "compared"
    assert all(c["value"] <= c["limit"] for c in result["compared"].values())
    names = {m["name"] for m in resolved["end_to_end"]}
    assert set(result["metrics"]) == names
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert result["device"]["count"] == len(jax.devices())
    assert len(memory["memory"]) == chips
    assert memory["window_compile_s"] == 0


def test_every_check_of_the_window_is_judged():
    result, _ = one(tiny("2pc-3.verdict", TPC3, "verdict"), seconds=1.0)
    assert result["attempted"] >= 2


def test_run_refuses_a_machine_without_a_tpu(capsys):
    rc = run.main(["--workload", "2pc-8.verdict", "--seed", "1", "--seconds", "1"])
    out = capsys.readouterr()
    assert rc == 2
    assert out.out == ""
    assert "needs 1 TPU" in out.err


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        run.load_peaks("cpu")
    assert run.load_peaks("TPU v5 lite")["hbm_bytes"] == 16e9


def test_traced_run_reports_busy_time_and_a_breakdown():
    # The CPU has no TPU plane: busy time reads 0 and the device readers
    # find nothing, but the traced window and its reduction run as on the
    # chip.
    resolved = tiny("2pc-3.verdict", TPC3, "verdict")
    result, memory = run.run_cell(
        resolved, 7, 1.0, True, jax.devices()[:1], CPU_PEAKS
    )
    assert result["correct"]
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    assert result["device"]["window_s"] > 0 and result["device"]["busy_s"] == 0
    assert "setup_trace_s" in result["metrics"] and "dispatches_per_check" in result["metrics"]
    assert "device_us_per_step" not in result["metrics"]
    assert result["metrics"]["idle_share.verdict"]["value"] == 100.0
    assert memory["window_checks"] == result["attempted"] >= 2
