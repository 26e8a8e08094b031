"""The closure reference of 2pc under symmetry reduction, the judge that
replays symmetric example paths, the cell at a tiny size, and its
control."""

import jax
import pytest

from benchmark import run
from benchmark.harness import cell as cells
from benchmark.reference import two_phase_commit, two_phase_commit_sym
from benchmark.tests.cells import CPU_PEAKS, tiny
from benchmark.tests.test_reference import golden
from benchmark.harness.window import Check

TPC4_SYM = {
    "name": "2pc-4-sym",
    "spec": {"factory": "stateright_tpu.models:TwoPhaseTensor", "args": [4]},
    "reference": {"module": "two_phase_commit_sym", "args": [4]},
    "golden": {"unique_states": 363, "discoveries": ["abort agreement", "commit agreement"]},
    "control": "detached_flags",
}
TPC5_SYM = {
    **TPC4_SYM,
    "name": "2pc-5-sym",
    "spec": {"factory": "stateright_tpu.models:TwoPhaseTensor", "args": [5]},
    "reference": {"module": "two_phase_commit_sym", "args": [5]},
    "golden": {"unique_states": 1092, "discoveries": ["abort agreement", "commit agreement"]},
}


@pytest.mark.parametrize("n", range(3, 9))
def test_closure_has_its_closed_form(n):
    assert two_phase_commit_sym.explore_sym(n)[0] == (3 ** (n + 2) - 3) // 2


@pytest.mark.parametrize("n", range(3, 8))
def test_symmetric_and_full_verdicts_agree(n):
    assert two_phase_commit_sym.explore_sym(n)[1] == two_phase_commit.explore(n)[1]


def test_closure_pinned_golden():
    # About 20-30 s on one core.
    g = golden("2pc-10-sym")
    assert two_phase_commit_sym.explore_sym(10) == (g["unique_states"], g["discoveries"])
    assert two_phase_commit_sym.generated(10) == 2_918_816
    assert two_phase_commit_sym.rewrites(10) == 556_008


def test_representative_sorts_and_carries_flags():
    rep = two_phase_commit_sym.representative
    # RM 0 prepared (its flag and message), RM 1 working.
    s = ((1, 0), 0, frozenset({0}), frozenset({0}))
    assert rep(s, 2) == ((0, 1), 0, frozenset({1}), frozenset({1}))
    # Equal states keep their order: nothing moves.
    t = ((1, 1), 0, frozenset({1}), frozenset({0, "commit"}))
    assert rep(t, 2) == t


def _example_rows(n):
    from stateright_tpu import TensorModelAdapter
    from stateright_tpu.models import TwoPhaseTensor

    checker = (
        TensorModelAdapter(TwoPhaseTensor(n))
        .checker()
        .symmetry()
        .spawn_tpu_bfs(chunk_size=512, queue_capacity=1 << 13, table_capacity=1 << 14)
        .join()
    )
    return {name: path.into_states() for name, path in checker.discoveries().items()}


def test_judge_path_accepts_the_examples_and_rejects_a_skipped_step():
    n = 4
    for name, rows in _example_rows(n).items():
        assert two_phase_commit_sym.judge_path(rows, n, name) == {
            "invalid_steps": 0, "met": True, "length": len(rows)
        }
        assert len(rows) in (n + 1, 3 * n + 2)
        skipped = rows[:2] + rows[3:]
        assert two_phase_commit_sym.judge_path(skipped, n, name)["invalid_steps"] == 1
        assert not two_phase_commit_sym.judge_path(rows[:-1], n, name)["met"]
        assert two_phase_commit_sym.judge_path(rows[1:], n, name)["invalid_steps"] == 1


def test_from_lanes_reads_the_documented_layout():
    # TM committed, RM 1 prepared at the TM; RM 0 committed, RM 1 prepared;
    # Prepared(1) and Commit sent.
    row = (1 | (1 << 3), 2 | (1 << 2), (1 << 1) | (1 << 30))
    assert two_phase_commit_sym.from_lanes(row, 2) == (
        (2, 1), 1, frozenset({1}), frozenset({1, "commit"})
    )


def _fabricated(rows, states=363, discoveries=("abort agreement", "commit agreement")):
    c = Check(states=states, discoveries=sorted(discoveries))
    c.examples = rows
    return c


def test_judge_on_fabricated_checks():
    judge = cells.module("judges", "verdict_sym").judge
    rows = _example_rows(4)
    good = _fabricated(rows)
    wrong, compared = judge(TPC4_SYM, [good])
    assert wrong == 0 and good.right
    assert all(v == 0 for v, _ in compared.values())
    assert list(compared) == [
        "count_gap", "verdict_mismatches", "errors", "invalid_steps", "unmet_examples"
    ]

    skipped = {k: v[:2] + v[3:] for k, v in rows.items()}
    short = {k: v[:-1] for k, v in rows.items()}
    checks = [
        _fabricated(rows, states=360),
        _fabricated(skipped),
        _fabricated(short),
        Check(error="RuntimeError('lost')"),
        _fabricated(rows),
    ]
    wrong, compared = judge(TPC4_SYM, checks)
    assert wrong == 4
    assert [c.right for c in checks] == [False, False, False, False, True]
    assert compared["count_gap"][0] == 3
    assert compared["invalid_steps"][0] == 1
    assert compared["unmet_examples"][0] == 2
    assert compared["errors"][0] == 1


def test_tiny_symmetric_cell_is_correct_and_counts_rewrites():
    resolved = tiny("2pc-4-sym.verdict-sym", TPC4_SYM, "verdict-sym")
    result, memory = run.run_cell(resolved, 2**31 + 25, 0.5, False, jax.devices()[:1], CPU_PEAKS)
    assert result["correct"], result["compared"]
    assert set(result["metrics"]) == {"unique_states_per_s", "setup_s"}
    counters = dict(memory["check_counters"][0])
    assert counters["canon_rewrites"] == two_phase_commit_sym.rewrites(4)
    assert counters["states_generated"] == two_phase_commit_sym.generated(4)

    result, _ = run.run_cell(resolved, 7, 0.5, True, jax.devices()[:1], CPU_PEAKS)
    share = result["metrics"]["canon_rewrite_share"]["value"]
    assert share == 100 * two_phase_commit_sym.rewrites(4) / two_phase_commit_sym.generated(4)


def test_control_detached_flags_is_flagged(monkeypatch):
    from stateright_tpu.models import TwoPhaseTensor

    monkeypatch.setattr(
        TwoPhaseTensor, "representative_lanes", TwoPhaseTensor.representative_lanes
    )
    cells.module("controls", "detached_flags").install(3)
    resolved = tiny("2pc-5-sym.verdict-sym", TPC5_SYM, "verdict-sym")
    result, _ = run.run_cell(resolved, 3, 0.3, False, jax.devices()[:1], CPU_PEAKS)
    assert not result["correct"]
    compared = result["compared"]
    assert compared["count_gap"]["value"] > 0 or compared["invalid_steps"]["value"] > 0


def test_canon_rewrite_share_reads_none_without_the_counter():
    from types import SimpleNamespace

    read = cells.reader("canon_rewrite_share")
    old = SimpleNamespace(checks=[Check(counters={"states_generated": 10})])
    assert read(old) is None
    new = SimpleNamespace(
        checks=[Check(counters={"states_generated": 10, "canon_rewrites": 2})] * 2
    )
    assert read(new) == 20.0
