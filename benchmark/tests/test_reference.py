"""The plain references: upstream's published counts, every golden pinned in
a configuration file, and agreement with the system under test at sizes
where both are quick."""

import json
import os

import pytest

from benchmark.harness import cell as cells
from benchmark.reference import paxos, register, single_copy, two_phase_commit

CONFIGS = os.path.join(cells.BENCH_DIR, "configs")


def golden(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)["golden"]


def test_two_phase_commit_upstream_counts():
    assert two_phase_commit.explore(3) == (288, ["abort agreement", "commit agreement"])
    assert two_phase_commit.explore(5)[0] == 8832


def test_two_phase_commit_pinned_golden():
    # About 20 s on one core.
    g = golden("2pc-8")
    assert two_phase_commit.explore(8) == (g["unique_states"], g["discoveries"])


def test_paxos_upstream_count():
    assert paxos.explore(1) == (265, ["value chosen"])
    assert paxos.explore(2) == (16668, ["value chosen"])


def test_paxos_pinned_golden():
    # About 70 s and 2 GB on one core.
    g = golden("paxos-3")
    assert paxos.explore(3) == (g["unique_states"], g["discoveries"])


@pytest.mark.parametrize("c,s", [(2, 1), (2, 2), (3, 1), (3, 2)])
def test_single_copy_agrees_with_the_program(c, s):
    from stateright_tpu import TensorModelAdapter
    from stateright_tpu.models.single_copy import SingleCopyTensor

    checker = TensorModelAdapter(SingleCopyTensor(c, s)).checker().spawn_bfs().join()
    assert single_copy.explore(c, s) == checker.unique_state_count()
    assert ("linearizable" in checker.discoveries()) == (
        single_copy.shortest_counterexample_depth(c, s) is not None
    )


def test_single_copy_counterexample_is_replayed():
    from stateright_tpu import HasDiscoveries, TensorModelAdapter
    from stateright_tpu.models.single_copy import SingleCopyTensor

    checker = (
        TensorModelAdapter(SingleCopyTensor(3, 2))
        .checker()
        .finish_when(HasDiscoveries.any_of(["linearizable"]))
        .spawn_bfs()
        .join()
    )
    rows = checker.discovery("linearizable").into_states()
    assert single_copy.shortest_counterexample_depth(3, 2) == 4
    assert single_copy.judge_path(rows, 3, 2) == {
        "invalid_steps": 0, "final_holds": False, "depth": 4
    }
    assert single_copy.judge_path(rows[1:], 3, 2)["invalid_steps"] == 1


def test_linearizability_by_search():
    none = register.NO_READ
    # Client 0 read nothing back after its own write: not linearizable.
    assert not register.linearizable(((2, None, (0, 0)), (0, none, ())))
    # It read its own value: fine.
    assert register.linearizable(((2, 0, (0, 0)), (0, none, ())))
    # It read client 1's value while client 1's write is still in flight:
    # fine, that write may take effect after client 0's.
    assert register.linearizable(((2, 1, (0, 0)), (0, none, ())))
    # Both wrote, both read their own value after seeing the other's write
    # complete: one of the reads must be wrong.
    assert not register.linearizable(((2, 0, (0, 1)), (2, 1, (1, 0))))
