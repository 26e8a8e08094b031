"""Visited-table growth: the rehash probes only the compacted occupied rows.

`vs.rehash` compacts the old table's occupied rows into a batch of
`rehash_width(old capacity)` = MAX_LOAD x capacity rows. These tests hold
it to the invariants its callers rely on: a table at exactly MAX_LOAD
grows with every row placed within MAX_PROBES (found again by
`lookup_parent_np` and `walk_chains`), a table past MAX_LOAD reports its
overflow as unresolved instead of dropping rows, and the mesh's per-shard
grow and a sharded check that grows several times keep both.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from stateright_tpu.ops import visited_set as vs
from stateright_tpu.tensor import TensorModelAdapter

N_SHARDS = 4


def _keys(rng, n, shard=None, chained=0):
    """n distinct nonzero (h1, h2) keys with parents; `shard` fixes
    h1 % N_SHARDS, as the mesh's owner routing leaves a shard's keys.
    The last `chained` keys differ only above bit 16, so in any table of
    up to 2^16 slots they share one probe sequence: each takes two claim
    rounds, and the eight primary rounds leave `chained` - 4 of them to
    the tail stages."""
    h1 = rng.integers(1, 2**32, size=2 * n, dtype=np.uint64).astype(np.uint32)
    if shard is not None:
        h1 = (h1 - h1 % np.uint32(N_SHARDS)) + np.uint32(shard)
    h2 = rng.integers(1, 2**32, size=2 * n, dtype=np.uint64).astype(np.uint32)
    high = np.arange(1, chained + 1, dtype=np.uint32) << np.uint32(16)
    h1[:chained] = (h1[0] & np.uint32(0xFFFF)) | high
    h2[:chained] = (h2[0] & np.uint32(0xFFFF)) | high
    _, first = np.unique((h1.astype(np.uint64) << 32) | h2, return_index=True)
    keep = np.sort(first)[:n]
    h1, h2 = h1[keep], h2[keep]
    p1 = rng.integers(1, 2**32, size=n, dtype=np.uint64).astype(np.uint32)
    return h1, h2, p1, h2 ^ p1


def _filled(cap, keys):
    h1, h2, p1, p2 = (jnp.asarray(k) for k in keys)
    table, is_new, unresolved, _ = vs.insert_jit(
        vs.empty_table(cap), h1, h2, p1, p2, jnp.ones(h1.shape, dtype=bool)
    )
    assert bool(np.asarray(is_new).all())
    assert not bool(np.asarray(unresolved).any())
    return table


def _assert_all_found(table, keys):
    h1, h2, p1, p2 = keys
    lanes = vs.unpack_lanes_np(table)
    for a, b, c, d in zip(h1, h2, p1, p2):
        assert vs.lookup_parent_np(lanes, int(a), int(b)) == (True, int(c), int(d))
    c1, c2, count, status = vs.walk_chains_jit(
        table, jnp.asarray(h1), jnp.asarray(h2), hops=1
    )
    assert (np.asarray(status) == vs.WALK_EXHAUSTED).all()
    assert (np.asarray(count) == 1).all()
    np.testing.assert_array_equal(np.asarray(c1)[:, 0], p1)
    np.testing.assert_array_equal(np.asarray(c2)[:, 0], p2)


KEYS = {"plain": {}, "shard0": {"shard": 0}, "shard3": {"shard": 3},
        "chained": {"chained": 8}}


@pytest.mark.parametrize("extra", [0, 37], ids=["at_max_load", "over_max_load"])
@pytest.mark.parametrize("kind", sorted(KEYS))
def test_rehash_places_every_row_or_reports_it(kind, extra):
    cap = 1 << 12
    width = vs.rehash_width(cap)
    keys = _keys(np.random.default_rng(11), width + extra, **KEYS[kind])
    table, counts = vs.rehash_jit(_filled(cap, keys), vs.empty_table(2 * cap))
    counts = np.asarray(counts)
    assert counts[vs.REHASH_ROWS] == width + extra
    # Rows past the batch width are never dropped silently.
    assert counts[vs.REHASH_UNRESOLVED] == extra
    assert counts[vs.REHASH_STRAGGLERS] >= KEYS[kind].get("chained", 4) - 4
    if not extra:
        _assert_all_found(table, keys)


@pytest.mark.parametrize("extra", [0, 37], ids=["at_max_load", "over_max_load"])
def test_mesh_grow_rehashes_every_shard(extra):
    from stateright_tpu.parallel.mesh import _build_grow

    devices = jax.devices()[:N_SHARDS]
    if len(devices) < N_SHARDS:
        pytest.skip("needs 4 virtual devices")
    cap = 1 << 11
    width = vs.rehash_width(cap)
    rng = np.random.default_rng(23)
    # The last shard is the one past MAX_LOAD, if any, and the one whose
    # chained keys engage the tail stages.
    last = N_SHARDS - 1
    keys = [
        _keys(rng, width + (extra if s == last else 0), s, 8 if s == last else 0)
        for s in range(N_SHARDS)
    ]
    shards = [_filled(cap, k) for k in keys]
    sharding = NamedSharding(Mesh(np.array(devices), ("shards",)), PartitionSpec("shards"))
    table = tuple(
        jax.device_put(jnp.stack([t[i] for t in shards]), sharding) for i in range(3)
    )
    grow = _build_grow(cap, 2 * cap, sharding.mesh, "shards")
    grown, counts = grow(table)
    counts = np.asarray(counts)
    assert counts.shape == (N_SHARDS, 3)
    np.testing.assert_array_equal(
        counts[:, vs.REHASH_ROWS], [len(k[0]) for k in keys]
    )
    np.testing.assert_array_equal(
        counts[:, vs.REHASH_UNRESOLVED], [0] * last + [extra]
    )
    assert counts[last, vs.REHASH_STRAGGLERS] >= 4
    if not extra:
        for s in range(N_SHARDS):
            _assert_all_found(tuple(lane[s] for lane in grown), keys[s])


def test_sharded_check_grows_and_rebuilds_its_path():
    """Paxos with 2 clients sharded over 4 devices, as the four-chip cell
    spawns it, from 1024-slot shard tables: the shards grow at least four
    times in one check, and the example path walks the grown shard tables
    with `lookup_parent_np`."""
    from stateright_tpu.models.paxos import PaxosTensorExhaustive

    if len(jax.devices()) < N_SHARDS:
        pytest.skip("needs 4 virtual devices")
    checker = (
        TensorModelAdapter(PaxosTensorExhaustive(2))
        .checker()
        .spawn_sharded_bfs(
            devices=jax.devices()[:N_SHARDS], table_capacity_per_shard=1 << 10
        )
        .join()
    )
    assert checker.unique_state_count() == 16_668
    tel = checker.telemetry()
    assert tel["table_growths"] >= 4
    assert 0 < tel["rehash_rows"] <= tel["table_growths"] * 16_668
    assert "rehash_stragglers" in tel
    assert checker.discovery("linearizable") is None
    path = checker.discovery("value chosen")
    assert path is not None and len(path.into_actions()) == 8


def test_tpu_bfs_telemetry_counts_rehashed_rows():
    from stateright_tpu.models import TwoPhaseTensor

    checker = (
        TensorModelAdapter(TwoPhaseTensor(4))
        .checker()
        .spawn_tpu_bfs(chunk_size=64, queue_capacity=1 << 12, table_capacity=1 << 8)
        .join()
    )
    assert checker.unique_state_count() == 1_568
    tel = checker.telemetry()
    assert tel["table_growths"] >= 1
    assert 0 < tel["rehash_rows"] <= tel["table_growths"] * 1_568
    assert "rehash_stragglers" in tel
