"""A run with its timed path broken underneath comes out not correct, and
so does the control. At tiny specs on the CPU, past the look for a chip."""

import importlib

import jax
import pytest

from benchmark import control, run
from benchmark.tests.cells import CPU_PEAKS, SINGLE_COPY_2X2, TPC3, tiny

TPC7 = {
    **TPC3,
    "name": "2pc-7",
    "spec": {"factory": "stateright_tpu.models:TwoPhaseTensor", "args": [7]},
    "golden": {"unique_states": 296448, "discoveries": ["abort agreement", "commit agreement"]},
}
SINGLE_COPY_3X2 = {
    **SINGLE_COPY_2X2,
    "name": "single-copy-3x2",
    "spec": {"factory": "stateright_tpu.models.single_copy:SingleCopyTensor", "args": [3, 2]},
    "reference": {"module": "single_copy", "args": [3, 2]},
}
CELLS = {
    "verdict": ("2pc-3.verdict", TPC3, "verdict", 1),
    "ttfc": ("single-copy-2x2.ttfc", SINGLE_COPY_2X2, "ttfc", 1),
    "x4": ("2pc-3.verdict-x4", TPC3, "verdict-x4", 4),
}


def result_of(cell, seconds=0.3, seed=5):
    workload, config, traffic, chips = CELLS[cell] if isinstance(cell, str) else cell
    res, _ = run.run_cell(
        tiny(workload, config, traffic), seed, seconds, False, jax.devices()[:chips], CPU_PEAKS
    )
    return res


def wrap_step(monkeypatch, change):
    """Build the spec with its step replaced by `change(xp, lanes, succs,
    masks)`: every engine steps through `spec.step_lanes`."""
    build = run.build_spec

    def patched_build(config):
        spec = build(config)
        step = spec.step_lanes

        def broken(xp, lanes):
            succs, masks = step(xp, lanes)
            return change(xp, lanes, succs, masks)

        spec.step_lanes = broken
        return spec

    monkeypatch.setattr(run, "build_spec", patched_build)


def test_sound_runs_are_correct():
    for cell in CELLS:
        assert result_of(cell)["correct"]


@pytest.mark.parametrize("cell", ["verdict", "ttfc", "x4"])
def test_step_that_returns_its_state_unchanged(monkeypatch, cell):
    wrap_step(monkeypatch, lambda xp, lanes, succs, masks: ([tuple(lanes)] * len(succs), masks))
    assert not result_of(cell)["correct"]


@pytest.mark.parametrize("cell", ["verdict", "x4"])
def test_half_of_each_batch_left_out(monkeypatch, cell):
    def drop_half(xp, lanes, succs, masks):
        keep = (lanes[0] + lanes[1] + lanes[2]) % xp.uint32(2) == xp.uint32(0)
        return succs, [m & keep for m in masks]

    wrap_step(monkeypatch, drop_half)
    res = result_of(cell)
    assert not res["correct"] and res["compared"]["count_gap"]["value"] > 0


def test_exchange_between_chips_left_out(monkeypatch):
    monkeypatch.setattr(jax.lax, "all_to_all", lambda x, *a, **k: x)
    res = result_of("x4")
    # States land on shards that do not own them: the count is off, or the
    # verdict's paths cannot be rebuilt and the check fails outright.
    compared = res["compared"]
    assert not res["correct"]
    assert compared["count_gap"]["value"] > 0 or compared["errors"]["value"] > 0


def test_count_altered_where_produced(monkeypatch):
    from stateright_tpu.engines.tpu_bfs import TpuBfsChecker

    count = TpuBfsChecker.unique_state_count
    monkeypatch.setattr(TpuBfsChecker, "unique_state_count", lambda self: count(self) + 1)
    res = result_of("verdict")
    assert not res["correct"] and res["compared"]["count_gap"]["value"] == 1


def test_counterexample_altered_where_produced(monkeypatch):
    from stateright_tpu.path import Path

    states = Path.into_states

    def altered(self):
        rows = [list(r) for r in states(self)]
        rows[-1][0] ^= 1  # server 0's stored value
        return [tuple(r) for r in rows]

    monkeypatch.setattr(Path, "into_states", altered)
    res = result_of("ttfc")
    assert not res["correct"] and res["compared"]["invalid_steps"]["value"] > 0


def test_control_narrow_fingerprints_fails_at_2pc7(monkeypatch):
    # Let monkeypatch put back what the control replaces.
    fp = importlib.import_module("stateright_tpu.fingerprint")
    monkeypatch.setattr(fp, "hash_lanes_jnp", fp.hash_lanes_jnp)
    for path in ("stateright_tpu.fingerprint", "stateright_tpu.tensor", "stateright_tpu.parallel.mesh"):
        mod = importlib.import_module(path)
        monkeypatch.setattr(mod, "hash_words_np", mod.hash_words_np)
    control.install(TPC7, 2**31 + 3)
    res = result_of(("2pc-7.verdict", TPC7, "verdict", 1), seconds=0.1)
    assert not res["correct"] and res["compared"]["count_gap"]["value"] > 0


def test_control_random_walks_fail_at_3x2(monkeypatch):
    from stateright_tpu.checker import CheckerBuilder

    monkeypatch.setattr(CheckerBuilder, "spawn_tpu_bfs", CheckerBuilder.spawn_tpu_bfs)
    control.install(SINGLE_COPY_3X2, 2**31 + 3)
    res = result_of(("single-copy-3x2.ttfc", SINGLE_COPY_3X2, "ttfc", 1), seconds=0.5)
    assert not res["correct"] and res["compared"]["depth_gap"]["value"] > 0
    assert res["compared"]["invalid_steps"]["value"] == 0
