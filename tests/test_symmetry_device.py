"""Device symmetry reduction (SURVEY §7 step 8).

Semantics note, derived by measurement on 2pc-5 (and documented in
models/two_phase_commit.py): with the reference's IMPERFECT canonicalizer
(stable sort by rm_state only, examples/2pc.rs:203-229), the symmetry-
reduced "unique count" is traversal-defined, not semantic — the reference
itself gets 8,832 from its BFS (which ignores symmetry), 665 from its
sequential DFS (expand-original, dedup-by-rep, DFS order), and an
expand-original BFS gets 508. All variants soundly cover the same
equivalence classes (rep(s) == rep(t) implies s ~ t, and successor sets
of equivalent states are equivalent). The device engine explores the
CANONICAL CLOSURE (expand representatives), the only order-independent
variant a batched level-synchronous BFS can define: deterministically
1,092 representatives for 2pc-5 — an 8.1x reduction over the full space,
with identical property verdicts.
"""

import numpy as np
import pytest

from benchmark.reference import two_phase_commit_sym as closure
from stateright_tpu import TensorModelAdapter
from stateright_tpu.models import TwoPhaseTensor

TPC5_SYM_CLOSURE = 1_092  # deterministic canonical-closure golden
TPC5_FULL = 8_832  # examples/2pc.rs:159


def _spawn(tm, symmetry):
    b = TensorModelAdapter(tm).checker()
    if symmetry:
        b = b.symmetry()
    return b.spawn_tpu_bfs(
        chunk_size=512, queue_capacity=1 << 13, table_capacity=1 << 14
    ).join()


def test_2pc5_device_symmetry_closure_golden():
    full = _spawn(TwoPhaseTensor(5), symmetry=False)
    sym = _spawn(TwoPhaseTensor(5), symmetry=True)
    assert full.unique_state_count() == TPC5_FULL
    assert sym.unique_state_count() == TPC5_SYM_CLOSURE
    # Identical verdicts, with VALID reconstructed discovery paths.
    for name in ("abort agreement", "commit agreement"):
        assert full.discovery(name) is not None
        p = sym.discovery(name)
        assert p is not None and len(p.into_states()) >= 2
    assert sym.discovery("consistent") is None


def test_canonicalizer_matches_host_representative_2pc4():
    """The lane canonicalizer must agree with the rich host model's
    representative() on every reachable state (same stable-sort rule)."""
    from collections import deque

    from stateright_tpu.models.two_phase_commit import TwoPhaseState

    n = 4
    tm = TwoPhaseTensor(n)
    ad = TensorModelAdapter(tm)
    seen = set()
    q = deque(ad.init_states())
    seen.update(q)
    while q:
        s = q.popleft()
        acts = []
        ad.actions(s, acts)
        for a in acts:
            ns = ad.next_state(s, a)
            if ns is not None and ns not in seen:
                seen.add(ns)
                q.append(ns)

    def to_host(row):
        lane0, lane1, lane2 = row
        return TwoPhaseState(
            rm_state=tuple((lane1 >> (2 * i)) & 3 for i in range(n)),
            tm_state=lane0 & 3,
            tm_prepared=tuple(bool((lane0 >> (2 + i)) & 1) for i in range(n)),
            msgs=frozenset(
                [i for i in range(n) if (lane2 >> i) & 1]
                + ([-1] if (lane2 >> 30) & 1 else [])
                + ([-2] if (lane2 >> 31) & 1 else [])
            ),
        )

    def from_host(s):
        lane0 = s.tm_state | sum(
            (1 << (2 + i)) for i in range(n) if s.tm_prepared[i]
        )
        lane1 = sum((s.rm_state[i] & 3) << (2 * i) for i in range(n))
        lane2 = sum(1 << m for m in s.msgs if m >= 0)
        if -1 in s.msgs:
            lane2 |= 1 << 30
        if -2 in s.msgs:
            lane2 |= 1 << 31
        return (lane0, lane1, lane2)

    for st in seen:
        hrep = from_host(to_host(st).representative())
        crep = ad.representative_state(st)
        assert hrep == crep, st


def test_symmetry_without_canonicalizer_raises():
    from stateright_tpu.models import IncrementTensor

    with pytest.raises(ValueError, match="representative_lanes"):
        TensorModelAdapter(IncrementTensor(2)).checker().symmetry().spawn_tpu_bfs()


@pytest.mark.parametrize("n", [3, 4, 5, 6, 7])
def test_device_closure_matches_the_plain_closure(n):
    """Count, successors generated and `canon_rewrites` against the plain
    closure BFS (benchmark/reference/two_phase_commit_sym.py), and every
    example path a chain of representatives ending in an example."""
    sym = _spawn(TwoPhaseTensor(n), symmetry=True)
    tel = sym.telemetry()
    assert (sym.unique_state_count(), sorted(sym.discoveries())) == closure.explore_sym(n)
    assert tel["states_generated"] == closure.generated(n)
    assert tel["canon_rewrites"] == closure.rewrites(n)
    for name, path in sym.discoveries().items():
        r = closure.judge_path(path.into_states(), n, name)
        assert r["invalid_steps"] == 0 and r["met"], (name, r)
    assert {
        name: len(path.into_states()) for name, path in sym.discoveries().items()
    } == {"abort agreement": n + 1, "commit agreement": 3 * n + 2}


def test_canon_rewrites_under_pipelined_short_eras():
    """Chained short eras carry the counter across eras and retried
    partial steps without counting twice."""
    sym = (
        TensorModelAdapter(TwoPhaseTensor(5))
        .checker()
        .symmetry()
        .spawn_tpu_bfs(
            chunk_size=64, queue_capacity=1 << 13, table_capacity=1 << 14,
            sync_steps=4,
        )
        .join()
    )
    tel = sym.telemetry()
    assert sym.unique_state_count() == TPC5_SYM_CLOSURE
    assert tel["canon_rewrites"] == closure.rewrites(5) == 1_560
    # The params' last word is the counter; the memory ledger carves it.
    snap = tel["memory"]
    assert snap["components"]["canon_tail"]["bytes"] == 4
    assert snap["total_bytes"] == sym._memory.ledger.live_nbytes()


def test_no_canon_rewrites_without_symmetry():
    assert "canon_rewrites" not in _spawn(TwoPhaseTensor(4), symmetry=False).telemetry()
