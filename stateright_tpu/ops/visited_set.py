"""Device-resident visited set: batched open-addressing hash table.

TPU-native replacement for the reference BFS's concurrent visited map
(DashMap<Fingerprint, Option<Fingerprint>> at src/checker/bfs.rs:29-30).
Fingerprints are (h1, h2) uint32 pairs (64-bit effective, nonzero as a
pair). The table is structure-of-arrays: a paired-lane key buffer
`keys[2 * capacity]` (slot i's h1 word at `keys[i]`, its h2 word at
`keys[capacity + i]`) plus two dense [capacity] parent lanes (parent_h1,
parent_h2), with the all-zero key pair meaning "empty" and parent (0, 0)
meaning "no parent" (initial state) — mirroring the reference's
Option<Fingerprint> parent pointers used for path reconstruction
(bfs.rs:380-409). SoA matters: a [capacity, 4] row table makes every
gather/scatter move 4-wide rows that waste the TPU's 8x128 vector tiles
(measured >1000x slower than flat 1-D accesses). The paired-lane key
buffer goes one further: each probe round reads BOTH key words with ONE
gather over the concatenated index vector [idx, capacity + idx] (and
claims them with one scatter), halving the dependent-gather chain that
dominates insert cost. The on-disk checkpoint format keeps the original
four flat lanes (table0..3); the engines split/concat the key buffer at
the save/load boundary, so checkpoint meta geometry is unchanged.

Probing is DOUBLE HASHING: slot_0 = h1 & mask, stride = h2 | 1 (odd, so it
cycles the whole power-of-two table). Unlike linear probing there is no
cluster growth, so probe chains stay geometric in the load factor and a
small fixed probe budget suffices at load <= MAX_LOAD.

Batched insert uses claim-arbitrated probe rounds. Each round every
pending candidate:

  1. reads its slot; a key match means "already visited" (done, not new),
  2. if the slot is empty, scatters its candidate index into a claim
     scratch array at that slot — among same-slot contenders exactly one
     index survives the scatter,
  3. the claim winner (readback == own index) scatters its lanes into the
     table (winner slots are unique, so these scatters take the fast
     unique-indices path), and
  4. losers wait one round: re-reading the slot next round either reveals
     a key match (the winner carried the same fingerprint — an in-batch
     duplicate, resolved exactly like the reference's benign insert races,
     bfs.rs:302-315) or a foreign key (probe advances by the stride).

Duplicate keys *within* a batch therefore need no separate dedup pass:
the claim protocol guarantees exactly one winner per distinct key, and
`is_new` counts each distinct new key exactly once.

PALLAS NOTE (round 4, on an earlier JAX and chip): a hand-written Pallas
probe kernel was prototyped and set aside. TPU Pallas rejected vector
dynamic indexing into a ref ("Cannot do int indexing on TPU"), so the
open-addressing probe's random gathers could not be expressed inside a
kernel — they go through XLA's native gather, which is exactly what this
module does. The insert's cost is dependent-gather latency, chained per
probe round (not measured on the v5e yet).

The probe loops are COUNTED fori loops in phases: a short full-width
phase resolves the overwhelming majority, then the rare stragglers are
cumsum-compacted into a cascade of count-gated tail stages at narrowing
widths that probe further, so the common-case step pays only the short
full-width phase. The candidates that no phase resolves are reported
`unresolved`; callers must grow the table and keep load <= MAX_LOAD so
that outcome stays (measurably) one-in-millions — and fail loudly if it
happens.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

PRIMARY_ROUNDS = 3  # primary full-width probe rounds
# At MAX_LOAD=0.25, P(probe chain > 3) ~ 1.6%: three full-width rounds keep
# the straggler population under the first tail stage's cap even for the
# largest bench batches (2pc-10: rcap ~ 85K distinct candidates at load
# 0.23 leaves ~1K stragglers; TWO rounds left ~4.5K — overflowing the
# 4096-wide tail and force-engaging every later stage on every step, the
# stage-profiled cause of the 2pc-10 per-step cliff).
REHASH_ROUNDS = 8  # deeper primary phase for whole-table rehashes
# Tail stages: (rounds, width) pairs at GEOMETRICALLY NARROWING widths.
# Each stage re-compacts the candidates still unresolved at that point
# into its own [width] batch, so late stages probe at the width of the
# straggler population they actually face (tens of candidates) instead of
# the first stage's worst-case cap. The whole stage — compaction, probe
# rounds, and fold-back — is GATED on its live straggler count
# (lax.cond): a stage with nothing to do costs one scalar reduction
# instead of a cumsum + gathers + probe rounds. Total probe budget per
# insert is PRIMARY (or REHASH) + sum of stage rounds; stages engage
# automatically as the load factor pushes chains longer.
TAIL_STAGES = ((4, 4096), (4, 1024), (8, 256))
# Lookups must probe at least as deep as the deepest possible placement:
# a rehash insert can place a key up to REHASH_ROUNDS + sum(tail) probes
# along its sequence. (Keep this >= the budget of every table written by
# older builds: checkpointed tables are probed with TODAY'S constant.)
MAX_PROBES = REHASH_ROUNDS + sum(r for r, _ in TAIL_STAGES)
# First-stage width: stragglers after the primary phase scale with the
# batch (expected ~ n * load^PRIMARY_ROUNDS near MAX_LOAD), so giant
# batches at high load CAN overflow it — overflow surfaces as
# `unresolved` candidates, which engine callers must treat as RETRYABLE
# (shrink the batch via the partial-commit take_cap protocol and redo;
# inserts are idempotent), not as instant failure.
# Probe chains stay within these budgets when the load factor stays under
# MAX_LOAD (double hashing => geometric chains: P(len>3) ~ MAX_LOAD^3 per
# candidate, and the tail phase absorbs the stragglers).
MAX_LOAD = 0.25


def empty_table(capacity: int):
    """Packed zero table: (keys[2*capacity], parent_h1[capacity],
    parent_h2[capacity]); capacity must be a power of two."""
    if capacity & (capacity - 1):
        raise ValueError("visited-set capacity must be a power of two")
    # Distinct buffers (not one aliased zeros array): the lanes are
    # donated independently by the jitted insert/loop programs.
    return (
        jnp.zeros(2 * capacity, dtype=jnp.uint32),
        jnp.zeros(capacity, dtype=jnp.uint32),
        jnp.zeros(capacity, dtype=jnp.uint32),
    )


def abstract_table(capacity: int):
    """`jax.ShapeDtypeStruct` twin of `empty_table` — the shapes without
    the buffers, for tracing/lowering insert/rehash programs statically
    (analysis/program.py STR6xx)."""
    import jax

    if capacity & (capacity - 1):
        raise ValueError("visited-set capacity must be a power of two")
    sds = jax.ShapeDtypeStruct
    return (
        sds((2 * capacity,), jnp.uint32),
        sds((capacity,), jnp.uint32),
        sds((capacity,), jnp.uint32),
    )


def table_capacity(table) -> int:
    return table[1].shape[0]


def pack_lanes(k1, k2, v1, v2):
    """Build the packed device table from four flat key/parent lanes (the
    checkpoint / host-seeding representation)."""
    return (
        jnp.concatenate([jnp.asarray(k1), jnp.asarray(k2)]),
        jnp.asarray(v1),
        jnp.asarray(v2),
    )


def unpack_lanes_np(table):
    """Download a packed device table into the four flat numpy lanes used
    by checkpoints and `lookup_parent_np` (key halves are free views)."""
    import numpy as np

    keys = np.asarray(table[0])
    cap = keys.shape[0] // 2
    return keys[:cap], keys[cap:], np.asarray(table[1]), np.asarray(table[2])


def _probe_rounds(table, claim, h1, h2, p1, p2, idx, done, is_new, rounds):
    """One counted phase of the claim protocol over one candidate set.

    The probe stride is DERIVED here (`h2 | 1`) rather than passed in:
    every probe sequence in this module uses the same double-hashing
    stride, so deriving it from the gathered h2 words keeps the tail-stage
    cascade free of a per-stage stride gather (loop-invariant hoisting).
    """
    keys, v1, v2 = table
    capacity = v1.shape[0]
    u = jnp.uint32
    mask = u(capacity - 1)
    claim_cap = claim.shape[0]
    cmask = u(claim_cap - 1)
    n = h1.shape[0]
    my_id = jnp.arange(n, dtype=u)
    stride = h2 | u(1)
    # The claim scratch and the table have DIFFERENT sizes, so each needs
    # its own out-of-bounds drop-target range (an index that is OOB for
    # the claim would land INSIDE the larger table and corrupt it). For
    # the packed [2*capacity] key buffer the drop targets start at
    # 2*capacity — `capacity + my_id` would land inside the h2 half — and
    # the two key-scatter halves get DISJOINT ranges ([2c, 2c+n) and
    # [2c+n, 2c+2n)) so the concatenated scatter keeps unique indices.
    claim_oob = u(claim_cap) + my_id
    table_oob = u(2 * capacity) + my_id
    table_oob2 = u(2 * capacity) + u(n) + my_id
    hcap = u(capacity)

    def body(_r, carry):
        keys, v1, v2, claim, idx, done, is_new = carry
        # ONE gather reads both key words: h1 at idx, h2 at capacity+idx.
        rk = keys[jnp.concatenate([idx, hcap + idx])]
        rk1 = rk[:n]
        rk2 = rk[n:]
        slot_match = (rk1 == h1) & (rk2 == h2)
        done = done | slot_match  # already visited (or in-batch dup winner)
        slot_empty = (rk1 == 0) & (rk2 == 0)
        want = ~done & slot_empty
        # Same-slot contenders intentionally collide here — the surviving
        # write is the arbitration (no unique-indices promise). The claim
        # scratch is a HASHED domain much smaller than the table (see
        # `_claim_cap`): contenders for DIFFERENT table slots may collide
        # on one claim slot, in which case all but one harmlessly lose and
        # retry the same still-empty table slot next round — soundness
        # never depends on the claim being collision-free, only on "claim
        # readback == my id" being unforgeable within a round, which a
        # per-candidate unique id guarantees.
        ci = idx & cmask
        claim = claim.at[jnp.where(want, ci, claim_oob)].set(my_id, mode="drop")
        won = want & (claim[ci] == my_id)
        # Winner slots are unique; losers/dones get distinct out-of-bounds
        # targets so the unique-indices fast path stays valid. Both key
        # words land with ONE scatter over the concatenated targets.
        tgt = jnp.where(won, idx, table_oob)
        tgt2 = jnp.where(won, hcap + idx, table_oob2)
        keys = keys.at[jnp.concatenate([tgt, tgt2])].set(
            jnp.concatenate([h1, h2]), mode="drop", unique_indices=True
        )
        v1 = v1.at[tgt].set(p1, mode="drop", unique_indices=True)
        v2 = v2.at[tgt].set(p2, mode="drop", unique_indices=True)
        is_new = is_new | won
        done = done | won
        # Occupied-by-foreign-key probes advance by their stride; claim
        # losers re-examine the same (now occupied) slot next round to
        # learn dup-vs-foreign. Resolved candidates PIN their index to slot
        # 0: their (masked) gathers in later rounds then all hit one cache
        # line instead of scattering across HBM — the probe loop's cost
        # tracks the *unresolved* population, and fully-masked no-op steps
        # become nearly free.
        advance = ~done & ~slot_empty
        idx = jnp.where(advance, (idx + stride) & mask, idx)
        idx = jnp.where(done, u(0), idx)
        return keys, v1, v2, claim, idx, done, is_new

    out = lax.fori_loop(
        0, rounds, body, (keys, v1, v2, claim, idx, done, is_new)
    )
    return (out[0], out[1], out[2]), out[3], out[4], out[5], out[6]


def _compact_ids(mask, cap: int):
    """Pack the indices of set bits in `mask` into a [cap] id buffer.

    Returns (ids[cap], valid[cap], n_set). Entries past min(n_set, cap) are
    invalid; set bits ranked >= cap overflow (not represented).
    """
    u = jnp.uint32
    n = mask.shape[0]
    my_id = jnp.arange(n, dtype=u)
    rank = jnp.cumsum(mask.astype(u)) - 1
    # Overflowed set bits (rank >= cap) must ALSO take distinct out-of-bounds
    # positions — a bare rank could collide with an unset entry's cap+my_id,
    # violating the unique-indices promise below.
    pos = jnp.where(mask & (rank < u(cap)), rank, u(cap) + my_id)
    ids = (
        jnp.zeros(cap, dtype=u)
        .at[pos]
        .set(my_id, mode="drop", unique_indices=True)
    )
    n_set = mask.sum(dtype=u)
    valid = jnp.arange(cap, dtype=u) < jnp.minimum(n_set, u(cap))
    return ids, valid, n_set


def _probe_all(table, claim, h1, h2, p1, p2, idx, done, is_new, rounds):
    """Primary probe rounds, then a cascade of gated straggler stages at
    narrowing widths. Returns (table, claim, done, is_new)."""
    u = jnp.uint32
    n = h1.shape[0]

    table, claim, idx, done, is_new = _probe_rounds(
        table, claim, h1, h2, p1, p2, idx, done, is_new, rounds
    )

    for stage_rounds, stage_cap in TAIL_STAGES:
        # Gate each stage — INCLUDING its compaction — on the live
        # straggler count: with nothing left the stage reduces to one
        # scalar sum + a branch instead of a full-width cumsum, gathers,
        # and stage_rounds probe rounds. Candidates that overflow a
        # stage's width stay un-done and fall through to the next stage
        # (or out, reported unresolved by the caller).
        pending = (~done).sum(dtype=u)

        def run_stage(op, stage_rounds=stage_rounds, stage_cap=stage_cap):
            table, claim, idx, done, is_new = op
            tail_ids, t_valid, _n_un = _compact_ids(~done, stage_cap)
            th1 = h1[tail_ids]
            th2 = h2[tail_ids]
            tp1 = p1[tail_ids]
            tp2 = p2[tail_ids]
            # No per-stage stride gather: _probe_rounds re-derives the
            # stride from the gathered th2 words (loop-invariant hoist).
            t_idx = jnp.where(t_valid, idx[tail_ids], u(0))
            t_done = ~t_valid
            # All-false but derived from varying data so the loop carry
            # type stays consistent under shard_map (constant zeros would
            # be unvarying).
            t_new = t_valid & ~t_valid
            table, claim, t_idx, t_done, t_new = _probe_rounds(
                table, claim, th1, th2, tp1, tp2, t_idx, t_done,
                t_new, stage_rounds,
            )
            # Fold the stage's results back into the full-width masks; the
            # probe POSITION folds back too, so the next stage's batch
            # resumes each survivor's chain where this one left it.
            t_my = jnp.arange(stage_cap, dtype=u)
            upd = jnp.where(t_valid, tail_ids, u(n) + t_my)
            is_new = is_new.at[upd].max(
                t_new, mode="drop", unique_indices=True
            )
            done = done.at[upd].max(t_done, mode="drop", unique_indices=True)
            idx = idx.at[upd].set(t_idx, mode="drop", unique_indices=True)
            return table, claim, idx, done, is_new

        def skip_stage(op):
            return op

        table, claim, idx, done, is_new = lax.cond(
            pending > u(0), run_stage, skip_stage,
            (table, claim, idx, done, is_new),
        )

    return table, claim, done, is_new


def insert(table, h1, h2, p1, p2, active, rcap: int | None = None,
           primary_rounds: int = PRIMARY_ROUNDS):
    """Insert fingerprints (h1,h2) with parents (p1,p2) where `active`.

    Returns (table, is_new, unresolved, n_overflow):
      is_new[i]     — candidate i claimed a fresh slot (first visit). Among
                      in-batch duplicates exactly one wins.
      unresolved[i] — probe budget exhausted (table too full or tail
                      overflow); callers must grow + retry, otherwise
                      states would be silently lost.
      n_overflow    — active candidates beyond `rcap` that were NOT probed
                      at all this call (0 when rcap is None). Overflowed
                      candidates are neither inserted nor marked is_new;
                      callers must re-submit them (inserts are idempotent,
                      so re-running a partially-inserted batch is safe).

    Duplicate keys among active candidates are allowed (though on this
    platform every probed candidate costs width-proportional gather time,
    so pre-deduplicated, `rcap`-compacted batches are much faster: probe
    traffic then scales with the number of distinct candidates instead of
    the padded batch width).
    """
    capacity = table_capacity(table)
    u = jnp.uint32
    mask = u(capacity - 1)
    n = h1.shape[0]
    # Claim scratch: stale values are harmless — a winner check only reads
    # slots that were written earlier in the same round. Seeded from a
    # varying input (h1) so the carry type stays consistent under shard_map
    # (a constant-zeros init would be unvarying on the mesh axis).
    # Capped at 2^22 entries: table-width up to there (a tightly hashed
    # claim measured SLOWER in situ at these sizes), hashed beyond —
    # giant tables (2pc-10 needs 2^28 slots) must not pay a 1GB memset
    # plus table-width claim traffic per insert call. Aliased claim slots
    # only cost a harmless retry round (see _probe_rounds).
    claim_cap = min(capacity, 1 << 22)
    claim = jnp.zeros(claim_cap, dtype=u) + (h1[0] & u(0))

    if rcap is None:
        # Inactive candidates start pinned at slot 0 (coalesced masked
        # gathers); see the pinning note in _probe_rounds.
        idx = jnp.where(active, h1 & mask, u(0))
        table, _claim, done, is_new = _probe_all(
            table, claim, h1, h2, p1, p2, idx, ~active,
            jnp.zeros_like(active), primary_rounds,
        )
        return table, is_new, active & ~done, u(0)

    # Compacted path: probe only the active candidates, at [rcap] width.
    cids, cvalid, n_act = _compact_ids(active, rcap)
    ch1 = h1[cids]
    ch2 = h2[cids]
    cp1 = p1[cids]
    cp2 = p2[cids]
    c_idx = jnp.where(cvalid, ch1 & mask, u(0))
    table, _claim, c_done, c_new = _probe_all(
        table, claim, ch1, ch2, cp1, cp2, c_idx, ~cvalid,
        cvalid & ~cvalid, primary_rounds,
    )
    # Scatter results back to the full-width domain.
    c_my = jnp.arange(rcap, dtype=u)
    upd = jnp.where(cvalid, cids, u(n) + c_my)
    is_new = jnp.zeros_like(active).at[upd].max(
        c_new, mode="drop", unique_indices=True
    )
    resolved = jnp.zeros_like(active).at[upd].max(
        c_done & cvalid, mode="drop", unique_indices=True
    )
    probed = jnp.zeros_like(active).at[upd].max(
        cvalid, mode="drop", unique_indices=True
    )
    unresolved = active & probed & ~resolved
    n_overflow = n_act - jnp.minimum(n_act, u(rcap))
    return table, is_new, unresolved, n_overflow


def lookup_parent(table, h1, h2):
    """Probe for fingerprints; returns (found, parent_h1, parent_h2).

    Same double-hashing sequence as `insert`. One device call per query:
    use only for rare host-side queries (prefer `lookup_parent_np` on a
    downloaded table for chain walks).
    """
    keys, v1, v2 = table
    capacity = v1.shape[0]
    u = jnp.uint32
    mask = u(capacity - 1)
    hcap = u(capacity)
    stride = h2 | u(1)
    idx = h1 & mask
    done = jnp.zeros(h1.shape, dtype=bool)
    found = jnp.zeros(h1.shape, dtype=bool)
    par1 = jnp.zeros_like(h1)
    par2 = jnp.zeros_like(h2)

    def body(_r, carry):
        idx, done, found, par1, par2 = carry
        rk1 = keys[idx]
        rk2 = keys[hcap + idx]
        slot_empty = (rk1 == 0) & (rk2 == 0)
        slot_match = (rk1 == h1) & (rk2 == h2)
        hit = ~done & slot_match
        par1 = jnp.where(hit, v1[idx], par1)
        par2 = jnp.where(hit, v2[idx], par2)
        found = found | hit
        done = done | slot_match | slot_empty  # empty slot ends the chain
        idx = jnp.where(done, idx, (idx + stride) & mask)
        return idx, done, found, par1, par2

    _idx, _done, found, par1, par2 = lax.fori_loop(
        0, MAX_PROBES, body, (idx, done, found, par1, par2)
    )
    return found, par1, par2


def occupied_mask(table):
    """Mask of nonempty slots — used when rehashing into a larger table."""
    cap = table_capacity(table)
    return (table[0][:cap] != 0) | (table[0][cap:] != 0)


def rehash(old_table, new_table):
    """Re-insert every occupied row of `old_table` into `new_table`.

    Runs entirely on device (table growth must not round-trip the table
    through the host). Returns (new_table, n_unresolved).
    """
    from ..obs.spans import REHASH_SCOPE

    with jax.named_scope(REHASH_SCOPE):
        occ = occupied_mask(old_table)
        cap = table_capacity(old_table)
        k1 = old_table[0][:cap]
        k2 = old_table[0][cap:]
        v1, v2 = old_table[1], old_table[2]
        # A rehash inserts millions of rows at once; use a deeper primary
        # phase so the fixed-size tail only sees genuine stragglers.
        new_table, _is_new, unresolved, _ovf = insert(
            new_table, k1, k2, v1, v2, occ, primary_rounds=REHASH_ROUNDS
        )
        return new_table, unresolved.sum(dtype=jnp.uint32)


# Host-callable jitted twins. Never call `insert`/`lookup_parent`/`rehash`
# eagerly: an eagerly-traced lax loop closes over its operands as embedded
# array constants. Under jit the operands are tracers.
# Donation is gated off on CPU: persistent-cache-deserialized executables
# corrupt donated buffers there (stateright_tpu.compat docstring).
from ..compat import donate_argnums_safe as _donate

insert_jit = jax.jit(insert, donate_argnums=_donate(0))
lookup_parent_jit = jax.jit(lookup_parent)
rehash_jit = jax.jit(rehash, donate_argnums=_donate(1))


def lookup_parent_np(table_np, h1: int, h2: int):
    """Pure-numpy probe over a host copy of the table lanes.

    Path reconstruction walks one parent per step; doing that on-device
    would cost a host round-trip per node, so the table is downloaded once
    and chains are walked here. Same double-hashing sequence as `insert`.
    Returns (found, parent_h1, parent_h2).
    """
    k1, k2, v1, v2 = table_np
    cap = len(k1)
    mask = cap - 1
    stride = (h2 | 1) & 0xFFFFFFFF
    idx = h1 & mask
    for _ in range(MAX_PROBES):
        if k1[idx] == h1 and k2[idx] == h2:
            return True, int(v1[idx]), int(v2[idx])
        if k1[idx] == 0 and k2[idx] == 0:
            return False, 0, 0
        idx = (idx + stride) & mask
    return False, 0, 0
