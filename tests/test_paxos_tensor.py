"""Paxos tensor twin: host-oracle equivalence and reference goldens.

The host `examples/paxos.py` ActorModel is the correctness oracle (its own
golden, 16,668 uniques at 2 clients, matches examples/paxos.rs:327). The
tensor twin must agree on unique-state counts — which requires its lane
encoding to capture the FULL host state identity, including the
linearizability tester's thread histories and real-time snapshots.
"""


import pytest

from stateright_tpu.models.paxos import (
    PaxosTensorExhaustive as PaxosTensorFull,
)
from stateright_tpu.tensor import TensorModelAdapter


def test_c1_twin_matches_host_actor_model():
    from examples.paxos import paxos_model

    host = paxos_model(1, 3).checker().spawn_bfs().join()
    host.assert_properties()
    twin = TensorModelAdapter(PaxosTensorFull(1)).checker().spawn_bfs().join()
    assert twin.unique_state_count() == host.unique_state_count() == 265
    assert twin.discovery("value chosen") is not None


def test_c1_device_engine_matches():
    twin = (
        TensorModelAdapter(PaxosTensorFull(1))
        .checker()
        .spawn_tpu_bfs(chunk_size=256, queue_capacity=1 << 14, table_capacity=1 << 12)
        .join()
    )
    assert twin.unique_state_count() == 265
    path = twin.discovery("value chosen")
    assert path is not None
    # BFS finds a shortest example; every prefix action must replay.
    assert len(path.into_actions()) >= 1


def test_c2_device_engine_reference_golden():
    # The reference's headline golden: 16,668 unique states at 2 clients
    # (examples/paxos.rs:327), with an 8-step "value chosen" discovery
    # (paxos.rs:330-340). Default-on since round 4: the era-loop engine +
    # the persistent compilation cache make this affordable in CI (the
    # round-3 block engine needed several minutes on CPU).
    twin = (
        TensorModelAdapter(PaxosTensorFull(2))
        .checker()
        .spawn_tpu_bfs(
            chunk_size=1024, queue_capacity=1 << 16, table_capacity=1 << 16
        )
        .join()
    )
    assert twin.unique_state_count() == 16_668
    path = twin.discovery("value chosen")
    assert path is not None
    assert len(path.into_actions()) == 8


def test_c2_threaded_host_oracle_golden():
    """The vectorized threaded host engine re-derives the reference golden
    in under a second."""
    twin = (
        TensorModelAdapter(PaxosTensorFull(2))
        .checker()
        .threads(4)
        .spawn_bfs()
        .join()
    )
    assert twin.unique_state_count() == 16_668
    assert twin.discovery("linearizable") is None


def test_c2_sharded_engine_agrees():
    """Single-device and sharded engines must agree on the paxos golden
    (the scale-capability criterion: the same program that runs paxos-3 on
    one chip shards over the mesh)."""
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    twin = (
        TensorModelAdapter(PaxosTensorFull(2))
        .checker()
        .spawn_sharded_bfs(
            devices=jax.devices()[:4],
            chunk_size=256,
            queue_capacity_per_shard=1 << 15,
            table_capacity_per_shard=1 << 15,
        )
        .join()
    )
    assert twin.unique_state_count() == 16_668
