"""TPU batched-BFS engine: discovery-output equivalence with the host oracle.

The host BFS run on the same TensorModel is the correctness oracle
(SURVEY.md §7 step 2): unique-state counts, property verdicts, and the
validity of reconstructed discovery paths must agree. Runs on the virtual
CPU platform in CI; the same code path is what executes on the TPU chip.
"""

import collections
import re

import numpy as np
import pytest

from stateright_tpu import Expectation, HasDiscoveries, Property, TensorModelAdapter
from stateright_tpu.engines.compiled import era_geometry
from stateright_tpu.engines.multiplex import run_multiplexed
from stateright_tpu.engines.tpu_bfs import _build_loop, _vcap, loop_abstract_args
from stateright_tpu.models import IncrementTensor, TwoPhaseTensor
from stateright_tpu.models.abd import AbdTensor
from stateright_tpu.models.paxos import PaxosTensor, PaxosTensorExhaustive
from stateright_tpu.models.single_copy import SingleCopyTensor
from stateright_tpu.path import Path
from stateright_tpu.tensor import TensorModel, TensorProperty


def host_check(tm):
    return TensorModelAdapter(tm).checker().spawn_bfs().join()


def tpu_check(tm, **kw):
    return TensorModelAdapter(tm).checker().spawn_tpu_bfs(**kw).join()


def test_2pc3_matches_host_oracle():
    tm = TwoPhaseTensor(3)
    host = host_check(tm)
    tpu = tpu_check(tm)
    assert tpu.unique_state_count() == host.unique_state_count() == 288
    tpu.assert_properties()
    # Both sometimes-properties discovered with valid paths.
    for name in ("abort agreement", "commit agreement"):
        path = tpu.discovery(name)
        assert path is not None
        # The path must be replayable through the model (actions are real).
        assert len(path.into_actions()) >= 1


def test_2pc5():
    tm = TwoPhaseTensor(5)
    tpu = tpu_check(tm)
    assert tpu.unique_state_count() == 8832
    tpu.assert_properties()


def test_increment_race_discovered():
    tm = IncrementTensor(2)
    tpu = tpu_check(tm)
    path = tpu.discovery("fin")
    assert path is not None
    # Validate the counterexample end-to-end: final state violates "fin".
    final = tuple(
        np.asarray([v], dtype=np.uint32) for v in path.last_state()
    )
    prop = next(p for p in tm.tensor_properties() if p.name == "fin")
    assert not bool(np.asarray(prop.check(np, final))[0])
    # BFS discovers a shortest counterexample: the classic 4-step schedule.
    assert len(path.into_actions()) == 4


def test_table_growth_and_queue_spill():
    # Tiny table (forces growth) and tiny queue (forces spill) on the
    # 8832-state space: counts must still be exact.
    tm = TwoPhaseTensor(5)
    tpu = tpu_check(tm, table_capacity=1 << 8, queue_capacity=1 << 12, chunk_size=64)
    assert tpu.unique_state_count() == 8832
    tpu.assert_properties()


def test_eventually_property_tensor():
    # A 4-lane counter that counts 0..3 and stops; eventually x>=3 holds.
    class Counter(TensorModel):
        state_width = 1
        max_actions = 1

        def init_states_array(self):
            return np.zeros((1, 1), dtype=np.uint32)

        def step_lanes(self, xp, lanes):
            x = lanes[0]
            return [(xp.minimum(x + xp.uint32(1), xp.uint32(3)),)], [
                x < xp.uint32(3)
            ]

        def tensor_properties(self):
            return [
                TensorProperty.eventually(
                    "reaches3", lambda xp, lanes: lanes[0] >= xp.uint32(3)
                )
            ]

    tpu = tpu_check(Counter())
    tpu.assert_properties()  # no counterexample: every path reaches 3

    class Stuck(Counter):
        def step_lanes(self, xp, lanes):
            x = lanes[0]
            return [(xp.minimum(x + xp.uint32(1), xp.uint32(2)),)], [
                x < xp.uint32(2)
            ]

    tpu = tpu_check(Stuck())
    path = tpu.discovery("reaches3")
    assert path is not None  # terminal state 2 never satisfies the property
    assert [int(s[0]) for s in path.into_states()] == [0, 1, 2]


def test_target_state_count_and_timeout():
    tm = TwoPhaseTensor(5)
    tpu = tpu_check(tm, chunk_size=64)
    full = tpu.state_count()
    capped = (
        TensorModelAdapter(tm)
        .checker()
        .target_state_count(500)
        .spawn_tpu_bfs(chunk_size=64)
        .join()
    )
    assert 500 <= capped.state_count() < full


def test_rejects_rich_models_and_visitors():
    from stateright_tpu.models import LinearEquation

    with pytest.raises(TypeError, match="TensorModel"):
        LinearEquation(2, 10, 14).checker().spawn_tpu_bfs()
    with pytest.raises(ValueError, match="visitor"):
        TensorModelAdapter(IncrementTensor(2)).checker().visitor(
            lambda p: None
        ).spawn_tpu_bfs()


def test_telemetry_surfaces_engine_gauges():
    """Engine health (eras, steps, load factor, take_cap) must be visible
    through the public Checker.telemetry()/report surface, not just
    STPU_DEBUG (reference report.rs:66-74 role)."""
    import io

    from stateright_tpu.models import TwoPhaseTensor
    from stateright_tpu.report import WriteReporter
    from stateright_tpu.tensor import TensorModelAdapter

    c = TensorModelAdapter(TwoPhaseTensor(4)).checker().spawn_tpu_bfs(
        chunk_size=256
    )
    buf = io.StringIO()
    c.report(WriteReporter(buf))
    t = c.telemetry()
    assert t["eras"] >= 1
    assert t["steps"] >= 1
    assert 0 < t["load_factor"] < 1
    assert t["take_cap"] >= 1
    assert "Telemetry." in buf.getvalue()


_GATHER = re.compile(
    r'"stablehlo\.gather".*: \(tensor<(\d+)x\w+>, tensor<\d+x1x\w+>\)'
    r" -> tensor<(\d+)x\w+>"
)


@pytest.mark.parametrize("canon", [False, True], ids=["plain", "symmetric"])
def test_state_lanes_gathered_once_at_the_distinct_width(canon):
    """Without symmetry the era body hashes every padded candidate and
    gathers only the two hash lanes at the valid width; the S state lanes
    are gathered once, from the padded rows, at the distinct width. The
    symmetric body canonicalizes the gathered rows before it hashes them,
    so its S lanes are gathered at the valid width and again from there."""
    tm = TwoPhaseTensor(4) if canon else PaxosTensor(2)
    S, A = tm.state_width, tm.max_actions
    props = tm.tensor_properties()
    g = era_geometry(tm)
    chunk = g["chunk"]
    program = _build_loop(
        tm, props, chunk, g["qcap"], canon, g["cov"], sample_k=g["sample_k"]
    )
    args = loop_abstract_args(
        tm, props, chunk, g["qcap"], g["tcap"], g["cov"], g["sample_k"], canon
    )
    # (operand elements, result elements) of every 1-D gather
    widths = collections.Counter(
        (int(m.group(1)), int(m.group(2)))
        for m in _GATHER.finditer(program.serial.lower(*args).as_text())
    )
    padded, vcap = chunk * A, _vcap(A, chunk)
    rcap = max(128 * A, (2 * vcap) // 5)
    assert S >= 3
    if canon:
        assert widths[(padded, vcap)] == S
        assert widths[(padded, rcap)] == 0
        assert widths[(vcap, rcap)] >= S
    else:
        assert widths[(padded, vcap)] == 2
        assert widths[(padded, rcap)] == S
        assert widths[(vcap, rcap)] < S


_NEVER = HasDiscoveries.all_of(["<no such property>"])

PARITY_MODELS = {
    "2pc-5": lambda: TwoPhaseTensor(5),
    "paxos-2": lambda: PaxosTensorExhaustive(2),
    "single-copy-2x2": lambda: SingleCopyTensor(2, 2),
    "abd-2": lambda: AbdTensor(2),
}


def _assert_matches_host_oracle(tm, dev):
    """Counts, discoveries and replayable shortest paths against the
    vectorized host BFS run to exhaustion on the same model."""
    adapter = TensorModelAdapter(tm)
    host = adapter.checker().finish_when(_NEVER).threads(4).spawn_bfs().join()
    assert dev.unique_state_count() == host.unique_state_count()
    assert dev.state_count() == host.state_count()
    found = dev.discoveries()
    assert sorted(found) == sorted(host.discoveries())
    props = {p.name: p for p in adapter.properties()}
    for name, path in found.items():
        states = path.into_states()
        replayed = Path.from_actions(adapter, states[0], path.into_actions())
        assert replayed is not None and replayed.into_states() == states, name
        assert len(path) == len(host.discovery(name)), name
        holds = props[name].condition(adapter, states[-1])
        assert holds == (props[name].expectation == Expectation.SOMETIMES), name


@pytest.mark.parametrize("name", sorted(PARITY_MODELS))
def test_era_body_matches_host_oracle(name):
    tm = PARITY_MODELS[name]()
    dev = (
        TensorModelAdapter(tm)
        .checker()
        .finish_when(_NEVER)
        .spawn_tpu_bfs(chunk_size=256, queue_capacity=1 << 15, table_capacity=1 << 15)
        .join()
    )
    _assert_matches_host_oracle(tm, dev)


def test_multiplexed_era_body_matches_host_oracle():
    builders = [
        TensorModelAdapter(TwoPhaseTensor(4)).checker().finish_when(_NEVER)
        for _ in range(2)
    ]
    for lane in run_multiplexed(builders, lanes=2):
        _assert_matches_host_oracle(TwoPhaseTensor(4), lane)
