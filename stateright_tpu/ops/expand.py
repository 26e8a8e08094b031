"""Shared per-chunk evaluate-and-expand core for the batched BFS engines.

Both the single-device engine (engines/tpu_bfs.py) and the sharded engine
(parallel/mesh.py) are required to be state-for-state equivalent to the
reference checker's hot loop (src/checker/bfs.rs:196-334); they share this
builder so the semantics live in exactly one place. The engines differ only
in what happens *after* expansion: the single-device engine inserts locally,
the sharded engine first exchanges candidates across the mesh.

Everything is structure-of-arrays: states are tuples of dense [C] uint32
lane arrays, and the C*A candidate batch is laid out ACTION-MAJOR
(index = a*C + c) so it is built with cheap concatenations of per-action
lanes — never a [C, A, S] materialization, whose small minor axes would
waste the TPU's 8x128 vector tiles.

Property verdicts are returned as RAW PER-ROW HIT MASKS (`prop_hits`), not
as extracted fingerprints: a loop-carried value computed through a
reduction -> broadcast -> reduction chain (argmax selects, one-hot
extractions, max reduces) adds dependent reductions to every step.
Callers carry the masks (or mask snapshots) through their loops with pure
elementwise ops and extract fingerprints once per block, outside the
loop.
"""

from __future__ import annotations

from typing import NamedTuple

from ..core import Expectation


class Expanded(NamedTuple):
    ebits: object  # [C] uint32, post property evaluation
    flat: object  # tuple of S lane arrays, each [C*A] (action-major)
    h1: object  # [C*A] candidate fingerprints
    h2: object
    parent1: object  # [C*A] parent fingerprints
    parent2: object
    child_ebits: object  # [C*A]
    child_depth: object  # [C*A]
    valid: object  # [C*A] bool: action valid & in boundary & parent live
    generated: object  # scalar uint32: number of valid candidates
    prop_hits: object  # list of P [C] bool masks (see module docstring)


class ExpandedLean(NamedTuple):
    ebits: object  # [C] uint32, post property evaluation
    flat: object  # tuple of S lane arrays, each [C*A] (action-major)
    valid: object  # [C*A] bool: action valid & in boundary & parent live
    generated: object  # scalar uint32: number of valid candidates
    prop_hits: object  # list of P [C] bool masks (see module docstring)


def build_expand_lean(tm, props, chunk: int):
    """The compact-early variant of `build_eval_and_expand` (round 5).

    Returns f(rows, ebits, depth, active, depth_limit) -> ExpandedLean.

    Rationale (round 5, before any v5e measurement): EVERY random-access
    op costs time in proportion to its padded width — so the old contract, which materialized
    fingerprints, parent tiles, and ebits/depth tiles at the padded [C*A]
    width, made the engine pay the full padded width in a dozen wide ops
    per step while only ~20% of slots are valid. This builder returns only
    what is genuinely [C*A]-wide by nature (the successor lanes and the
    validity mask); the engine compacts ONCE and derives hashes, parents,
    and queue rows at the compacted width. Fingerprints of popped rows are
    recomputed elementwise on pop instead of being carried in the ring —
    elementwise work is effectively free here, ring lanes are not.

    Semantics are identical to `build_eval_and_expand` (the reference hot
    loop, bfs.rs:196-334): property evaluation with eventually-bit
    clearing, depth limiting, boundary filtering, the terminal rule, and
    terminal eventually-bit discoveries.
    """
    import jax.numpy as jnp

    S = tm.state_width
    A = tm.max_actions

    def expand_lean(rows, ebits, depth, active, depth_limit):
        u = jnp.uint32
        live = active & (depth < depth_limit)

        prop_hits = []
        e_idx = 0
        e_slot = {}
        for i, p in enumerate(props):
            if p.expectation == Expectation.EVENTUALLY:
                vals = p.check(jnp, rows) & live
                ebits = jnp.where(vals, ebits & ~u(1 << e_idx), ebits)
                e_slot[i] = e_idx
                e_idx += 1
                prop_hits.append(None)
                continue
            if p.expectation == Expectation.ALWAYS:
                prop_hits.append(live & ~p.check(jnp, rows))
            else:  # SOMETIMES
                prop_hits.append(live & p.check(jnp, rows))

        succs, amask = tm.step_lanes(jnp, rows)
        valid_per_a = []
        any_valid = None
        for a in range(A):
            v = amask[a] & live & tm.within_boundary_lanes(jnp, succs[a])
            valid_per_a.append(v)
            any_valid = v if any_valid is None else (any_valid | v)
        valid = jnp.concatenate(valid_per_a)  # [A*C], action-major
        generated = valid.sum(dtype=u)

        terminal = live & ~any_valid
        for i, p in enumerate(props):
            if p.expectation != Expectation.EVENTUALLY:
                continue
            bit = u(1 << e_slot[i])
            prop_hits[i] = terminal & ((ebits & bit) != 0)

        flat = tuple(
            jnp.concatenate([succs[a][s] for a in range(A)]) for s in range(S)
        )
        return ExpandedLean(
            ebits=ebits,
            flat=flat,
            valid=valid,
            generated=generated,
            prop_hits=prop_hits,
        )

    return expand_lean


def build_eval_and_expand(tm, props, chunk: int):
    """Returns f(rows, row_h1, row_h2, ebits, depth, active, depth_limit)
    -> Expanded, where `rows` is a tuple of S [C] lane arrays.

    `row_h1`/`row_h2` are the popped rows' fingerprint halves, computed when
    the rows were first enqueued (the frontier ring carries them), so popped
    states are never re-hashed.

    Implements, batched: property evaluation with eventually-bit clearing
    (bfs.rs:231-277), depth limiting (bfs.rs:219-224), successor generation
    with boundary filtering, the terminal rule (no successor passed the
    boundary, dups included — bfs.rs:283-333), and terminal eventually-bit
    discoveries (bfs.rs:326-333). `prop_hits[i]` marks the rows whose visit
    discovers property i: a violated always / satisfied sometimes condition,
    or a terminal state with property i's eventually-bit still pending.
    """
    import jax.numpy as jnp

    from ..fingerprint import hash_lanes_jnp

    S = tm.state_width
    A = tm.max_actions

    def eval_and_expand(rows, row_h1, row_h2, ebits, depth, active, depth_limit):
        u = jnp.uint32
        # Depth-limited rows are popped but neither evaluated nor expanded.
        live = active & (depth < depth_limit)

        prop_hits = []
        e_idx = 0
        e_slot = {}
        for i, p in enumerate(props):
            if p.expectation == Expectation.EVENTUALLY:
                vals = p.check(jnp, rows) & live
                ebits = jnp.where(vals, ebits & ~u(1 << e_idx), ebits)
                e_slot[i] = e_idx
                e_idx += 1
                prop_hits.append(None)  # filled in after terminal rule
                continue
            if p.expectation == Expectation.ALWAYS:
                prop_hits.append(live & ~p.check(jnp, rows))
            else:  # SOMETIMES
                prop_hits.append(live & p.check(jnp, rows))

        # succs: list over A of S-lane tuples; masks: list over A of [C] bool
        succs, amask = tm.step_lanes(jnp, rows)
        valid_per_a = []
        any_valid = None
        for a in range(A):
            v = amask[a] & live & tm.within_boundary_lanes(jnp, succs[a])
            valid_per_a.append(v)
            any_valid = v if any_valid is None else (any_valid | v)
        valid = jnp.concatenate(valid_per_a)  # [A*C], action-major
        generated = valid.sum(dtype=u)

        terminal = live & ~any_valid
        for i, p in enumerate(props):
            if p.expectation != Expectation.EVENTUALLY:
                continue
            bit = u(1 << e_slot[i])
            prop_hits[i] = terminal & ((ebits & bit) != 0)

        flat = tuple(
            jnp.concatenate([succs[a][s] for a in range(A)]) for s in range(S)
        )
        h1, h2 = hash_lanes_jnp(flat)
        return Expanded(
            ebits=ebits,
            flat=flat,
            h1=h1,
            h2=h2,
            parent1=jnp.tile(row_h1, A),
            parent2=jnp.tile(row_h2, A),
            child_ebits=jnp.tile(ebits, A),
            child_depth=jnp.tile(depth + u(1), A),
            valid=valid,
            generated=generated,
            prop_hits=prop_hits,
        )

    return eval_and_expand
