"""Parent-chain walks inside the device visited table.

`vs.walk_chains` follows parent pointers on the device and
`walk_parent_chains` drives it from the host, a padded batch a dispatch,
walking on from a chain's last fingerprint when the hop budget runs out.
Both are held to the numpy walk over a downloaded table
(`vs.lookup_parent_np`), on tables filled through `vs.insert_jit`.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from stateright_tpu.engines.tpu_bfs import WALK_HOPS, walk_parent_chains
from stateright_tpu.fingerprint import combine64, split64
from stateright_tpu.models import TwoPhaseTensor
from stateright_tpu.obs.metrics import MetricsRegistry
from stateright_tpu.ops import visited_set as vs
from stateright_tpu.tensor import TensorModelAdapter


def _distinct_fps(rng, n, cap, crowd=0.3):
    """`n` distinct nonzero (h1, h2) pairs; a `crowd` share of them start
    their probe at one of a few shared slots, so probes run past the first
    slot."""
    hot = rng.integers(0, cap, size=4)
    out = {}
    while len(out) < n:
        h1 = int(rng.integers(1, 2**32))
        if rng.random() < crowd:
            h1 = (h1 & ~(cap - 1)) | int(rng.choice(hot))
        out[combine64(h1, int(rng.integers(1, 2**32)))] = None
    return list(out)


def _fill(cap, fps, parents):
    """A device table holding `fps` with their `parents` (0 = init state)."""
    table = vs.empty_table(cap)
    h = np.array([split64(fp) for fp in fps], dtype=np.uint32)
    p = np.array([split64(fp) for fp in parents], dtype=np.uint32)
    table, is_new, unresolved, _ = vs.insert_jit(
        table, jnp.asarray(h[:, 0]), jnp.asarray(h[:, 1]),
        jnp.asarray(p[:, 0]), jnp.asarray(p[:, 1]),
        jnp.ones(len(fps), dtype=bool),
    )
    assert bool(np.asarray(is_new).all()) and not bool(np.asarray(unresolved).any())
    return table


def _forest(rng, cap, n, roots):
    fps = _distinct_fps(rng, n, cap)
    parents = [0] * roots + [fps[int(rng.integers(0, i))] for i in range(roots, n)]
    return fps, parents


def _chain(rng, cap, length):
    fps = _distinct_fps(rng, length, cap)
    return fps, [0] + fps[:-1]


def case_forest(rng):
    fps, parents = _forest(rng, 1 << 12, 900, roots=5)
    return _fill(1 << 12, fps, parents), fps[-6:] + fps[:2], 4, 2


def case_grown(rng):
    fps, parents = _forest(rng, 1 << 10, 240, roots=3)
    table, counts = vs.rehash_jit(_fill(1 << 10, fps, parents), vs.empty_table(1 << 11))
    assert int(counts[vs.REHASH_UNRESOLVED]) == 0
    return table, fps[-5:], 4, 2


def case_long_chain(rng):
    fps, parents = _chain(rng, 1 << 12, 2 * WALK_HOPS + 22)
    # The first chain takes three dispatches, the second two.
    return _fill(1 << 12, fps, parents), [fps[-1], fps[WALK_HOPS]], 4, 3


def case_absent_start(rng):
    fps, parents = _forest(rng, 1 << 12, 300, roots=2)
    absent = _distinct_fps(np.random.default_rng(99), 1, 1 << 12)[0]
    assert absent not in fps
    return _fill(1 << 12, fps, parents), [fps[-1], absent], 4, 1


def case_dangling_parent(rng):
    fps, parents = _chain(rng, 1 << 12, 40)
    # The chain's middle node is left out: its child's parent is missing.
    keep = [i for i in range(40) if i != 20]
    table = _fill(1 << 12, [fps[i] for i in keep], [parents[i] for i in keep])
    return table, [fps[-1]], 4, 1


def case_mixed_batch(rng):
    fps, parents = _forest(rng, 1 << 12, 500, roots=4)
    long_fps, long_parents = _chain(rng, 1 << 12, WALK_HOPS + 9)
    long_parents[0] = fps[-1]
    table = _fill(1 << 12, fps + long_fps, parents + long_parents)
    # Batches of three rows, the second padded: a root, forest nodes, one
    # fingerprint twice, and a chain that outruns the hop budget and walks
    # on in the second batch.
    return table, [fps[0], long_fps[-1], fps[-2], fps[-2], fps[123]], 3, 2


CASES = {
    "forest": case_forest,
    "grown": case_grown,
    "long_chain": case_long_chain,
    "absent_start": case_absent_start,
    "dangling_parent": case_dangling_parent,
    "mixed_batch": case_mixed_batch,
}


def _numpy_chain(table_np, fp):
    chain = [fp]
    while True:
        found, p1, p2 = vs.lookup_parent_np(table_np, *split64(chain[-1]))
        if not found:
            raise RuntimeError(
                f"fingerprint {chain[-1]} missing from visited table "
                "during path reconstruction"
            )
        if p1 == 0 and p2 == 0:
            return chain[::-1]
        chain.append(combine64(p1, p2))


def _numpy_row(table_np, h1, h2, hops):
    """What one row of `walk_chains` must read: (parents, status)."""
    if h1 == 0 and h2 == 0:
        return [], vs.WALK_ROOT
    parents, cur = [], (h1, h2)
    for _ in range(hops):
        found, p1, p2 = vs.lookup_parent_np(table_np, *cur)
        if not found:
            return parents, vs.WALK_MISSING
        if p1 == 0 and p2 == 0:
            return parents, vs.WALK_ROOT
        cur = (p1, p2)
        parents.append(cur)
    return parents, vs.WALK_EXHAUSTED


@pytest.mark.parametrize("case", sorted(CASES))
def test_walk_chains_matches_numpy_walk(case):
    table, fps, width, dispatches = CASES[case](np.random.default_rng(sorted(CASES).index(case)))
    table_np = vs.unpack_lanes_np(table)

    # One dispatch of the device walk, padded with (0, 0) rows.
    rows = [split64(fp) for fp in fps] + [(0, 0)]
    h = np.array(rows, dtype=np.uint32)
    c1, c2, count, status = (
        np.asarray(a) for a in vs.walk_chains_jit(table, h[:, 0], h[:, 1], hops=WALK_HOPS)
    )
    for row, (h1, h2) in enumerate(rows):
        parents, want = _numpy_row(table_np, h1, h2, WALK_HOPS)
        assert (int(status[row]), int(count[row])) == (want, len(parents)), row
        got = list(zip(c1[row].tolist(), c2[row].tolist()))
        assert got == parents + [(0, 0)] * (WALK_HOPS - len(parents)), row

    # The host driver: whole chains, continued past the hop budget.
    try:
        want = [_numpy_chain(table_np, fp) for fp in fps]
    except RuntimeError as e:
        want = e
    metrics = MetricsRegistry()
    if isinstance(want, RuntimeError):
        with pytest.raises(RuntimeError) as err:
            walk_parent_chains(table, fps, width, metrics)
        assert str(err.value) == str(want)
    else:
        assert walk_parent_chains(table, fps, width, metrics) == want
    assert metrics.get("path_walk_dispatches") == dispatches
    assert metrics.snapshot()["phase_ms"].keys() == {"path_walk", "path_download"}


def test_reconstruct_raises_for_a_fingerprint_missing_from_the_table():
    c = (
        TensorModelAdapter(TwoPhaseTensor(3))
        .checker()
        .spawn_tpu_bfs(chunk_size=64, queue_capacity=1 << 10, table_capacity=1 << 12)
        .join()
    )
    assert c.unique_state_count() == 288
    absent = combine64(0x12345678, 0x9ABCDEF1)
    with pytest.raises(
        RuntimeError,
        match=f"fingerprint {absent} missing from visited table during path reconstruction",
    ):
        c._reconstruct(absent)
    assert c.telemetry()["path_walk_dispatches"] == 1
