"""The TPU-native batched BFS engine.

This is the framework's reason to exist (SURVEY.md §7, BASELINE.json north
star): the reference's per-thread hot loop — pop a state, evaluate
properties, enumerate actions, fingerprint successors, dedup against a
concurrent map (src/checker/bfs.rs:196-334) — re-designed as a data-parallel
frontier program that lives on the device:

  - the pending queue is a device-resident ring buffer in structure-of-
    arrays form: one dense [qcap] uint32 array per state lane plus lanes
    for the fingerprint halves, eventually-bits, and depth — states are
    hashed exactly once, when first enqueued,
  - one BFS step pops a CHUNK of rows and runs batched property
    evaluation, batched successor generation (`TensorModel.step_lanes`),
    vectorized 64-bit fingerprinting, claim-arbitrated insertion into the
    SoA open-addressing visited table (in-batch dedup falls out of the
    claim protocol — no sorting), and a cumsum-compacted ring append,
  - MANY steps run back-to-back inside a single `lax.while_loop` on the
    device; the host thread synchronizes only when the loop exits — queue
    near overflow (spill to host), table near full (grow + rehash), a new
    property discovery (finish-policy check), a step budget (progress
    reporting / timeout / state-count targets), or frontier exhaustion.

Everything stays in flat 1-D uint32 arrays because TPU vector tiling makes
gathers/scatters of [N, small] rows catastrophically slow (>1000x measured
vs per-lane access) — see ops/visited_set.py.

Semantics match the reference engine state-for-state (same property
timing, terminal rule, eventually-bit propagation, boundary filtering,
depth accounting); only scheduling order differs (level-synchronous
instead of a work-stealing interleave — the same freedom the reference's
multithreaded mode already has). Parent fingerprints stored in the table
drive the same TLC-style path reconstruction (bfs.rs:380-409).
"""

from __future__ import annotations

import os
import time
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import numpy as np

from ..obs.log import get_logger

_DEBUG = bool(os.environ.get("STPU_DEBUG"))
_log = get_logger("engines.tpu_bfs")


def _dbg(msg: str) -> None:
    # STPU_DEBUG is its own opt-in gate, so the stream bypasses the
    # logger threshold (force) — setting the env var IS the request.
    if _DEBUG:
        _log.force("debug", msg, t=round(time.monotonic(), 3))

from ..checker import CheckerBuilder
from ..core import Expectation
from ..fingerprint import combine64, split64
from ..obs.metrics import MetricsRegistry
from ..path import Path
from ..tensor import TensorModel, TensorModelAdapter
from ..ops.tiering import TieredSpillStore, spill_host_budget_bytes
from .common import (
    HostEngineBase,
    load_checkpoint_folded,
    register_signal_checkpoint_flush,
    save_checkpoint_tiered,
    validate_checkpoint_cadence,
)


class _ProbeBudgetExhausted(RuntimeError):
    """An era closed with unresolved inserts (probe budget exhausted).
    Recoverable when a crash-safe checkpoint exists: reload it, regrow the
    table, and re-run the lost era (graceful degradation)."""


# Loop cache: (id(tm), chunk, qcap, n_props, ...) -> (tm ref, EraProgram).
# Reusing the same function object across checker instances is what lets
# JAX's trace cache and the persistent compilation cache actually hit (a
# fresh closure per checker would recompile every run).
_LOOP_CACHE: Dict[Tuple, Tuple[TensorModel, Any]] = {}


class EraProgram(NamedTuple):
    """One era program, jitted under two donation policies.

    ``serial``: full operand donation — safe only when the host has
    already consumed every input buffer (fresh upload, or dispatch after
    the readback landed). ``chain``: the params operand pinned
    (compat.donate_argnums_pinned) so a speculative chained dispatch can
    feed the previous era's output back in while its async readback is
    still in flight. On CPU both donation sets are empty and the two
    fields alias ONE jitted callable.
    """

    serial: Any
    chain: Any


# Packed scalar-parameter layout. Every host<->device transfer is a fixed
# cost of its own, so ALL scalar state crosses in ONE uint32 vector per
# direction. The loop reads [0:8], passes the config fields through, and
# writes the stats tail — so its own output can be fed straight back in
# with zero uploads when the host has nothing to change.
P_HEAD = 0  # ring head index
P_COUNT = 1  # frontier row count
P_UNIQUE = 2  # unique states so far
P_REC = 3  # recorded-discovery bitmask (bit i = property i)
P_DEPTH_LIMIT = 4
P_GROW_LIMIT = 5  # era exits when unique exceeds this (host grows table)
P_HIGH_WATER = 6  # era exits when count exceeds this (host spills)
P_MAX_STEPS = 7  # IN: step budget per era (host polls timeout/targets/ckpt);
# OUT: the NEXT era's adaptive budget (device-emitted, see P_BUDGET_CAP)
P_GEN = 8  # OUT: generated states this era
P_MAXD = 9  # OUT: max depth seen this era
P_STEPS = 10  # OUT: steps actually executed this era
P_ERR = 11  # IN: pre-existing error (seed unresolved); OUT: >0 = probe budget exhausted
P_TAKE_CAP = 12  # persisted across eras (self-tuned on vcap overflow)
P_FIN_ANY = 13  # era exits when (rec & fin_any) != 0
P_FIN_ALL = 14  # era exits when fin_all_en and (rec & fin_all) == fin_all
P_FIN_ALL_EN = 15
P_BUDGET_CAP = 16  # upper clamp for the device-adaptive step budget;
# 0 = adaptivity OFF (P_MAX_STEPS passes through unchanged — free-running
# and target-bounded runs keep the legacy fixed-budget behavior)
P_LEN = 17
# The packed vector is P_LEN + 2*P (+ coverage tail) words long: the tail
# carries the recorded discovery fingerprint halves (rec_fp1 | rec_fp2),
# so the era result download returns counters AND discovery fingerprints
# in ONE round-trip (a separate rec_fp read would be a second transfer,
# directly on the time-to-first-counterexample path). With
# coverage enabled (the default) the tail additionally carries this
# era's on-device coverage histograms (obs/coverage.py) — per-action
# valid-candidate counts [A], per-property hit counts [P], the consumed
# row count [1], and the per-depth unique-insert histogram [DEPTH_CAP] —
# so coverage costs ZERO extra host round-trips. The loop reads only
# [0:P_LEN] of its input; the tail is write-only output.


_COV_W = 16  # relative depth-offset window of the era loop's histogram
# Low-side slack of that window: the ring append lands children in
# candidate (action-major) order, so a pop window spanning a BFS depth
# boundary interleaves depth-(d+1) and depth-(d+2) children in the ring.
# A later window's lane-0 row is then NOT its shallowest — inserts from
# the shallower interleaved parents sit up to a few levels BELOW
# depth[0]+1. _COV_LO buckets below the anchor absorb them exactly
# (uint32-wrapped offsets compare exactly against their biased bucket).
_COV_LO = 8

# Adaptive era budget floor: the smallest per-era step budget the device
# emission may shrink to under spill/grow pressure, and the slow-start
# seed the host begins wall-clock-polled runs at.
BUDGET_MIN = 64

# Speculative chain depth: one extra era of overlap beyond a single
# chained era covers the readback+bookkeeping gap; deeper chains only pay
# off when host work per era exceeds a full device era (rare), while every
# extra in-flight era grows the wasted-work window on host-intervention
# exits.
CHAIN_DEPTH = 2

# Divisor of the compacted candidate width (`_vcap`): one step keeps
# C*A/VCAP_DIV valid candidates, floored at 128*A.
VCAP_DIV = 3


def _cov_len(A: int, P: int) -> int:
    from ..obs.coverage import DEPTH_CAP

    return A + P + 1 + DEPTH_CAP


def _vcap(A: int, chunk: int) -> int:
    """Compacted candidate-batch width (probe + enqueue width).

    Every op downstream of validity compaction runs at this width, so it
    bounds both the insert probe batch and the per-step enqueue. Sized for
    typical valid-candidate counts (~20-40%% of the padded C*A batch for
    the protocol models); the take_cap mechanism adapts when a model's
    step exceeds it. This is a SOUNDNESS-COUPLED constant: the device
    loop treats it as the overflow threshold while the host sizes
    grow_limit / pre-growth headroom from it — all sites must use this
    one definition.
    """
    return min(chunk * A, max(128 * A, (chunk * A) // VCAP_DIV))


def params_len(A: int, P: int, cov: bool, sample_k: int,
               canon: bool = False) -> int:
    """Length of the packed uint32 params vector the era loop carries:
    scalars + rec_fp tail + optional coverage tail + optional sampling
    tail + optional symmetry word (the dispatch's `canon_rewrites`,
    last). This is THE layout contract —
    the engine, the checkpoint codec, and the STR6xx program lint all
    size their buffers from it."""
    n = P_LEN + 2 * P
    if cov:
        n += _cov_len(A, P)
    if sample_k:
        from ..obs.sample import slab_entries

        n += 4 + 5 * slab_entries(sample_k)
    return n + int(canon)


def loop_abstract_args(tm: TensorModel, props, chunk: int, qcap: int,
                       tcap: int, cov: bool, sample_k: int,
                       canon: bool = False):
    """`jax.ShapeDtypeStruct` pytree matching `_build_loop`'s signature
    `(table, queue, rec_fp1, rec_fp2, params)` — lets the STR6xx program
    lint (analysis/program.py) trace/lower the era loop WITHOUT
    allocating a single device buffer or executing anything."""
    import jax
    import jax.numpy as jnp

    S, A, P = tm.state_width, tm.max_actions, len(props)
    u32 = jnp.uint32
    sds = jax.ShapeDtypeStruct
    table = (sds((2 * tcap,), u32), sds((tcap,), u32), sds((tcap,), u32))
    queue = tuple(sds((qcap,), u32) for _ in range(S + 2))
    plen = params_len(A, P, cov, sample_k, canon)
    return (table, queue, sds((P,), u32), sds((P,), u32), sds((plen,), u32))


def seed_loop_abstract_args(tm: TensorModel, props, chunk: int, qcap: int,
                            tcap: int, cov: bool, sample_k: int,
                            n_init: int):
    """Abstract args for `_build_seed_loop`'s fused
    `seed_run(qinit, h1, h2, params, rec_fp1, rec_fp2)` dispatch."""
    import jax
    import jax.numpy as jnp

    S, A, P = tm.state_width, tm.max_actions, len(props)
    u32 = jnp.uint32
    sds = jax.ShapeDtypeStruct
    n_init = max(1, n_init)
    plen = params_len(A, P, cov, sample_k)
    return (
        sds((S + 2, n_init), u32),
        sds((n_init,), u32),
        sds((n_init,), u32),
        sds((plen,), u32),
        sds((P,), u32),
        sds((P,), u32),
    )


def _build_loop(tm: TensorModel, props, chunk: int, qcap: int, canon: bool = False,
                cov: bool = True, raw: bool = False, sample_k: int = 0):
    """Compile the BFS device "era" loop.

    Returns a jitted function
      (table, queue, rec_fp1, rec_fp2, params[P_LEN])
      -> (table, queue, rec_fp1, rec_fp2, params[P_LEN])
    that runs BFS steps in a device-resident `lax.while_loop` until a
    host-intervention condition closes the gate: frontier exhausted, ring
    near overflow (host spills), table near full (host grows), step budget
    reached (host polls timeouts/targets/checkpoints), probe budget
    exhausted (host raises), or the finish policy's discovery masks are
    satisfied. One era = ONE dispatch + ONE readback, so a full run that
    needs no host intervention pays the host round-trip once, regardless
    of depth.

    With ``raw=True`` the UN-jitted loop function is returned instead (no
    donation): that is what the multiplexed lane engine
    (engines/multiplex.py) wraps in `jax.vmap` — an inner jit would defeat
    batching and donation is illegal on a vmapped operand it does not own.

    With ``sample_k > 0`` the loop additionally maintains the bottom-k
    space-sampling slab (obs/sample.py): every exactly-once insert whose
    fingerprint is lexicographically below the host-supplied threshold
    (read from the sample tail of the INPUT params — pass-through, so
    chained speculative dispatches reuse a stale-but-looser threshold,
    which only ever admits a superset of candidates) is appended to a
    fixed in-carry slab; the epilogue ranks the slab by h1 via one
    `top_k` and ships the smallest ``slab_entries(k)`` rows in the params
    tail, so the drain rides the existing once-per-era readback with
    ZERO extra round-trips. The host applies the exact 64-bit tie cut.

    With ``canon`` (symmetry reduction) every compacted candidate is
    replaced by its representative, and the params end in one more word:
    the dispatch's count of valid candidates the representative changed
    (``canon_rewrites``), gated like the generated count so retried
    partial steps never count twice. Without it the carry and the
    layout are those of a build that never heard of the counter.

    Non-raw builds return an `EraProgram(serial, chain)` pair: the same
    traced program jitted twice — ``serial`` donates the full operand
    set (table, queue, rec_fps, params; the driver only uses it when
    every input was already consumed host-side), ``chain`` excludes the
    readback-pinned params operand (compat.donate_argnums_pinned) for
    speculative chained dispatches. On CPU both donation sets resolve
    empty and ONE jitted object serves both slots (no double compile).
    """
    key = (id(tm), chunk, qcap, len(props), canon, cov, raw, sample_k)
    cached = _LOOP_CACHE.get(key)
    if cached is not None and cached[0] is tm:
        return cached[1]
    while len(_LOOP_CACHE) >= 16:  # bound executable/model pinning
        _LOOP_CACHE.pop(next(iter(_LOOP_CACHE)))

    import jax
    import jax.numpy as jnp
    from jax import lax

    from ..compat import donate_argnums_pinned
    from ..fingerprint import hash_lanes_jnp
    from ..obs.coverage import DEPTH_CAP
    from ..ops import frontier as fr
    from ..ops import visited_set as vs
    from ..obs.spans import stage_scope
    from ..ops.expand import build_expand_lean

    S = tm.state_width
    A = tm.max_actions
    P = len(props)
    expand_lean = build_expand_lean(tm, props, chunk)
    qmask = qcap - 1
    vcap = _vcap(A, chunk)
    if sample_k:
        from ..obs.sample import (
            DEVICE_STEP_CAP,
            slab_capacity,
            slab_entries,
            slab_high_water,
        )

        sk2 = slab_entries(sample_k)  # entries drained per era
        s_high = slab_high_water(sample_k)  # era-exit occupancy gate
        scap = slab_capacity(sample_k, DEVICE_STEP_CAP)  # in-carry slab
        # Loose-threshold take clamp: while the threshold is still MAX
        # (sampler under-full — fresh runs only) EVERY insert is a
        # candidate, so cap the pop so one step can never produce more
        # than the per-step capture width (candidates <= take * A). This
        # is what makes the sample EXACT from state one; once the host
        # drains k entries the threshold tightens and the clamp is moot.
        s_take = max(1, DEVICE_STEP_CAP // max(1, A))
        # Input-params offsets of the threshold words (the sample tail
        # starts right after the coverage tail; layout below).
        s_base = P_LEN + 2 * P + (_cov_len(A, P) if cov else 0)
    # Distinct-candidate (probe + enqueue) width: 2/5 of the valid width
    # measured fastest on 2pc-7 (vcap/2 pays ~15% more probe width than
    # needed; vcap/3 sits under the distinct-count peaks and burns steps
    # on partial-commit retries).
    rcap = max(128 * A, (2 * vcap) // 5)
    # Dedup scratch ~4x the valid width: cross-key collisions (which
    # harmlessly retain duplicates) stay rare, and the scratch stays small
    # enough to be cache-hot.
    dedup_cap = 1 << max(1, (4 * vcap - 1).bit_length())

    def loop(table, queue, rec_fp1, rec_fp2, params):
        u = jnp.uint32
        head0 = params[P_HEAD]
        count0 = params[P_COUNT]
        unique0 = params[P_UNIQUE]
        rec_bits0 = params[P_REC]
        depth_limit = params[P_DEPTH_LIMIT]
        grow_limit = params[P_GROW_LIMIT]
        high_water = params[P_HIGH_WATER]
        max_steps0 = params[P_MAX_STEPS]
        fin_any = params[P_FIN_ANY]
        fin_all = params[P_FIN_ALL]
        fin_all_en = params[P_FIN_ALL_EN]
        budget_cap = params[P_BUDGET_CAP]
        if sample_k:
            # Sampling threshold (exclusive; hi/lo uint32 words of the
            # host sampler's 64-bit kth-smallest). Copied through to the
            # output tail so chained dispatches keep a valid (stale =>
            # looser => superset, host re-filters) threshold.
            st1 = params[s_base]
            st2 = params[s_base + 1]
        # The era is a data-dependent `lax.while_loop` whose predicate runs
        # ON DEVICE (measured round 4: a jitted while predicate costs
        # nothing extra — the old belief that it forced a host round-trip
        # per iteration only holds for NON-jitted top-level loops). This
        # matters doubly here: (a) no wasted gated no-op iterations (every
        # iteration costs nearly a full step's gather traffic whether or
        # not it does work), and (b) no per-block dispatch+readback
        # (~350-400ms measured) for the progressive block ramp the old
        # design needed.
        #
        # Inside the body only uint32 sum-reduction chains may feed values
        # that GATE the next iteration (count/unique/rec_acc-style); a gate
        # routed through a boolean any()-derived carry serializes the
        # pipeline (~1.5s/step measured), as do reduction -> broadcast ->
        # reduction chains anywhere in the carry (argmax selects, one-hot
        # extractions, max reduces). Discovery fingerprints are therefore
        # accumulated as per-position lane snapshots (first hit per
        # position wins, pure elementwise) and extracted once AFTER the
        # loop; only the scalar discovery BITS (via per-property uint32
        # sums) feed the gate, implementing the finish policy's early exit
        # on device (reference bfs.rs:134-144 checks between blocks).
        def cond(carry):
            (
                _table, _queue, _head, count, unique, _gen, steps,
                err_cnt, _take_cap, rec_acc, _hseen, _f1, _f2, _fd, _covc,
                sampc, _canc, max_steps,
            ) = carry
            fin_hit = ((rec_acc & fin_any) != u(0)) | (
                (fin_all_en != u(0)) & ((rec_acc & fin_all) == fin_all)
            )
            keep = (
                (count > u(0))
                & (count <= high_water)
                & (unique <= grow_limit)
                & (steps < max_steps)
                & (err_cnt == u(0))
                & ~fin_hit
            )
            if sample_k:
                # Slab-occupancy gate: exit the era so the host can drain
                # before the slab can overflow (one more step adds at most
                # DEVICE_STEP_CAP entries, and scap = s_high + that).
                # sampc[4] (occupied) is a uint32 sum chain — carry-safe.
                keep = keep & (sampc[4] <= u(s_high))
            return keep

        def body(carry):
            (
                table,
                queue,
                head,
                count,
                unique,
                gen,
                steps,
                err_cnt,
                take_cap,
                rec_acc,
                hseen,
                facc1,
                facc2,
                faccd,
                covc,
                sampc,
                canc,
                max_steps,
            ) = carry
            take = jnp.minimum(jnp.minimum(count, u(chunk)), take_cap)
            if sample_k:
                # Loose-threshold clamp (see the sizing block above): only
                # binds while the sampler is under-full (threshold MAX).
                loose = (st1 == u(0xFFFFFFFF)) & (st2 == u(0xFFFFFFFF))
                take = jnp.minimum(
                    take, jnp.where(loose, u(s_take), u(chunk))
                )
            active = jnp.arange(chunk, dtype=jnp.uint32) < take
            with stage_scope("ring"):
                popped, _idx = fr.ring_gather(queue, head, chunk)
            rows = popped[:S]
            ebits = popped[S]
            depth = popped[S + 1]
            # Fingerprints are recomputed on pop (elementwise — effectively
            # free) instead of being carried in the ring: two fewer ring
            # lanes in every ring gather/scatter, which ARE the cost here.
            with stage_scope("hash"):
                row_h1, row_h2 = hash_lanes_jnp(rows)

            with stage_scope("expand"):
                ex = expand_lean(rows, ebits, depth, active, depth_limit)
            # COMPACT EARLY (the round-5 redesign): validity compaction is
            # the only [C*A]-wide random-access work in the step. Parent
            # lookup, the visited-set insert, and the ring append all run
            # at the compacted widths. In-batch duplicate
            # candidates need no separate dedup pass — the insert's claim
            # protocol arbitrates them exactly (one winner per distinct
            # key, same benign-race semantics as the reference's DashMap
            # entry API, bfs.rs:302-315).
            with stage_scope("compact"):
                vids, vvalid, n_val = vs._compact_ids(ex.valid, vcap)
            if canon:
                # Symmetry reduction: canonicalize at the compacted width,
                # before fingerprinting — the ring and table then only
                # ever see representatives.
                with stage_scope("compact"):
                    cl = tuple(ex.flat[s][vids] for s in range(S))
                with stage_scope("canon"):
                    rep = tm.representative_lanes(jnp, cl)
                    moved = rep[0] != cl[0]
                    for s in range(1, S):
                        moved = moved | (rep[s] != cl[s])
                    rewrites = (moved & vvalid).sum(dtype=jnp.uint32)
                    cl = rep
                with stage_scope("hash"):
                    ch1, ch2 = hash_lanes_jnp(cl)
            else:
                # Without symmetry the rows are final at expansion: hash
                # at the padded width (elementwise, so the expand
                # arithmetic fuses into it) and gather only the two hash
                # lanes here. A gather costs by the indices it touches,
                # so the S state lanes are gathered once, below, at the
                # deduplicated width.
                with stage_scope("hash"):
                    fh1, fh2 = hash_lanes_jnp(ex.flat)
                with stage_scope("compact"):
                    ch1 = fh1[vids]
                    ch2 = fh2[vids]
            # Stage-2 compaction: in-batch dedup (claim-arbitrated,
            # approximate — the insert arbitrates leftovers exactly) then
            # re-compact to the distinct-candidate width, so the probe
            # batch, parent lookups, AND the ring append all run at rcap
            # (~half of vcap) instead of the valid width. The dedup's
            # scratch is small (cache-hot), so its four random ops cost
            # far less than the width they save downstream.
            with stage_scope("claim"):
                reps = fr.claim_dedup(ch1, ch2, vvalid, dedup_cap)
            with stage_scope("compact"):
                dids, dvalid, n_d = vs._compact_ids(reps, rcap)
                dh1 = ch1[dids]
                dh2 = ch2[dids]
                # fid: flat candidate id a*C+c. (The symmetric body takes
                # its rows from the canonicalized `cl` instead.)
                if canon:
                    dl = tuple(cl[s][dids] for s in range(S))
                    fid = vids[dids]
                else:
                    fid = vids[dids]
                    dl = tuple(ex.flat[s][fid] for s in range(S))
                # parent row of candidate a*C+c is c
                src = fid % u(chunk)
                dp1 = jnp.where(dvalid, row_h1[src], u(0))
                dp2 = jnp.where(dvalid, row_h2[src], u(0))
                debits = ex.ebits[src]
                ddepth = depth[src] + u(1)
            with stage_scope("probe"):
                table, c_new, unresolved, _n_ovf = vs.insert(
                    table, dh1, dh2, dp1, dp2, dvalid
                )
                unres = unresolved.sum(dtype=jnp.uint32)
                new_count = c_new.sum(dtype=jnp.uint32)

            if sample_k:
                with stage_scope("sample"):
                    # Space-sampling capture: exactly-once inserts (c_new is
                    # exact even on retried partial steps — inserts commit
                    # once) whose 64-bit fingerprint is lexicographically
                    # below the threshold. Compacted to the small fixed
                    # capture width and appended to the slab; entries past
                    # the width are counted (sdrop) — astronomically rare
                    # under a tight threshold and impossible under a loose
                    # one thanks to the take clamp.
                    below = c_new & (
                        (dh1 < st1) | ((dh1 == st1) & (dh2 < st2))
                    )

                    def _capture(sc):
                        sfp1, sfp2, sdep, sact, socc, sdrp = sc
                        cids, cvalid, n_c = vs._compact_ids(
                            below, DEVICE_STEP_CAP
                        )
                        fit = jnp.minimum(n_c, u(DEVICE_STEP_CAP))
                        pos = socc + jnp.arange(DEVICE_STEP_CAP, dtype=u)
                        ok_w = cvalid & (pos < u(scap))
                        # Masked lanes land in the trash slot (index scap) —
                        # the slab lanes are scap+1 wide and the epilogue
                        # reads [:scap] only.
                        widx = jnp.where(ok_w, pos, u(scap))
                        # flat id a*C+c -> action a
                        dact = vids[dids] // u(chunk)
                        return (
                            sfp1.at[widx].set(dh1[cids]),
                            sfp2.at[widx].set(dh2[cids]),
                            sdep.at[widx].set(ddepth[cids]),
                            sact.at[widx].set(dact[cids]),
                            socc + fit,
                            sdrp + (n_c - fit),
                        )

                    # Once the threshold tightens (k-th smallest of the seen
                    # set) almost every step captures NOTHING — the cond
                    # skips the compaction and four slab scatters entirely,
                    # so steady-state sampling costs one compare + reduce
                    # per step.
                    sampc = lax.cond(
                        below.any(), _capture, lambda sc: sc, sampc
                    )

            # Overflow (> vcap valid candidates, > rcap distinct
            # candidates, OR probe-tail overflow reported as unresolved
            # candidates) => PARTIAL step: the inserted prefix is enqueued
            # (inserts are idempotent and enqueue==inserted keeps them
            # exactly-once), but the pops are NOT consumed — the same
            # parents re-expand with a halved take_cap until everything
            # fits/resolves. take_cap creeps back up on success.
            # Unresolved candidates are only FATAL when the batch cannot
            # shrink further (take == 1): that means genuinely exhausted
            # probe chains, i.e. state loss.
            err_cnt = err_cnt + jnp.where(take <= u(1), unres, u(0))
            ovf = (n_val > u(vcap)) | (n_d > u(rcap)) | (unres > u(0))
            with stage_scope("ring"):
                tail = (head + count) & u(qmask)
                queue = fr.ring_scatter(
                    queue, tail, dl + (debits, ddepth), c_new
                )

            consumed = jnp.where(ovf, u(0), take)
            head = (head + consumed) & u(qmask)
            count = count - consumed + new_count
            unique = unique + new_count
            gen = gen + jnp.where(ovf, u(0), ex.generated)
            if canon:
                canc = (canc[0] + jnp.where(ovf, u(0), rewrites),)
            steps = steps + (~ovf).astype(jnp.uint32)
            # Regrow at chunk/16 per clean step (was chunk/64): after an
            # overflow halves the cap, the old creep needed ~64 steps per
            # doubling to climb back — on 2pc-10 the run spent whole eras
            # popping quarter-width batches, paying full fixed per-step
            # cost for a fraction of the throughput (stage-profiled: the
            # per-step cost is width-insensitive below chunk). /16 restores
            # full width within ~16 clean steps while still backing off
            # geometrically under repeated overflow. Recovery is counted
            # in STEPS, not eras, and the cap round-trips through
            # P_TAKE_CAP — so adaptive era budgets (which make early eras
            # as short as BUDGET_MIN steps) and chained speculative
            # dispatches never reset or stall the climb; a halved cap
            # keeps recovering seamlessly across era boundaries.
            take_cap = jnp.where(
                ovf,
                jnp.maximum(take >> u(1), u(1)),
                jnp.minimum(take_cap + u(max(1, chunk // 16)), u(chunk)),
            )

            if cov:
                with stage_scope("coverage"):
                    # Coverage histograms (obs/coverage.py), all in-carry:
                    # per-action valid counts (the action-major [A*C] validity
                    # mask reshaped and row-summed; gated on ovf exactly like
                    # `gen`, so retried partial steps never double-count),
                    # consumed rows (the per-property evaluation count), and
                    # the per-depth insert histogram (inserts count
                    # unconditionally, matching `unique`). None of these feed
                    # the loop gate.
                    #
                    # The depth histogram deliberately avoids a scatter at the
                    # distinct-candidate width (XLA:CPU scatter-adds cost
                    # ~90ns/slot — 1.1ms/step at rcap width vs 0.13ms for this
                    # form, microbenched) AND the reduction->broadcast
                    # min-select the platform notes forbid in this carry:
                    # candidate depths bucket into a fixed window of relative
                    # offsets around depth[0]+1 via plain masked uint32 sums
                    # (the carry-safe reduction pattern, same class as the
                    # discovery-gate sums) and ONE fixed-width scatter lands
                    # them. The window is TWO-SIDED: ring depth is only
                    # non-decreasing up to the interleaved zones the
                    # candidate-order append leaves at depth boundaries (see
                    # _COV_LO above), so lane 0 is an anchor, not a minimum —
                    # inserts up to _COV_LO levels below it count exactly via
                    # wrapped-offset equality. Offsets past either edge clamp
                    # into the boundary bucket — a step would have to pop
                    # states spanning >= _COV_W (or interleave >= _COV_LO)
                    # BFS levels at once to smear a depth, which no bundled
                    # model comes near.
                    act, covp, expanded, dhist = covc
                    pa = ex.valid.reshape(A, chunk).sum(axis=1, dtype=u)
                    act = act + jnp.where(ovf, u(0), pa)
                    expanded = expanded + consumed
                    dmin = depth[0] + u(1)
                    # Biased offset: soffs == _COV_LO <=> ddepth == dmin.
                    soffs = ddepth + u(_COV_LO) - dmin
                    under = soffs >= u(0x80000000)  # beyond the low-side slack
                    cnts = jnp.stack(
                        [(((soffs == u(0)) | under) & c_new).sum(dtype=u)]
                        + [
                            ((soffs == u(w)) & c_new).sum(dtype=u)
                            for w in range(1, _COV_LO + _COV_W - 1)
                        ]
                        + [
                            (
                                (soffs >= u(_COV_LO + _COV_W - 1))
                                & ~under
                                & c_new
                            ).sum(dtype=u)
                        ]
                    )
                    # Bucket w holds depth dmin - _COV_LO + w; saturate the
                    # subtraction at 0 (early eras have dmin < _COV_LO — the
                    # duplicate zero indices only ever receive zero counts,
                    # since no insert sits at depth < 2).
                    dd = dmin + jnp.arange(_COV_LO + _COV_W, dtype=u)
                    idx = jnp.minimum(
                        jnp.where(dd >= u(_COV_LO), dd - u(_COV_LO), u(0)),
                        u(DEPTH_CAP - 1),
                    )
                    dhist = dhist.at[idx].add(cnts)
                    covc = (act, covp, expanded, dhist)

            if P:
                hseen_n = []
                facc1_n = []
                facc2_n = []
                faccd_n = []
                covp_n = []
                for i in range(P):
                    hits = ex.prop_hits[i]
                    first = hits & ~hseen[i]
                    facc1_n.append(jnp.where(first, row_h1, facc1[i]))
                    facc2_n.append(jnp.where(first, row_h2, facc2[i]))
                    faccd_n.append(jnp.where(first, depth, faccd[i]))
                    hseen_n.append(hseen[i] | hits)
                    # Scalar discovery bit for the gate: a uint32 sum, not
                    # a boolean any().
                    hs = hits.sum(dtype=jnp.uint32)
                    rec_acc = rec_acc | (jnp.minimum(hs, u(1)) << u(i))
                    if cov:
                        # Per-property hit totals ride the same sums the
                        # gate already pays for; ovf-gated like `gen` so
                        # retried rows are not re-counted.
                        covp_n.append(covc[1][i] + jnp.where(ovf, u(0), hs))
                hseen = tuple(hseen_n)
                facc1 = tuple(facc1_n)
                facc2 = tuple(facc2_n)
                faccd = tuple(faccd_n)
                if cov:
                    covc = (covc[0], tuple(covp_n), covc[2], covc[3])

            return (
                table,
                queue,
                head,
                count,
                unique,
                gen,
                steps,
                err_cnt,
                take_cap,
                rec_acc,
                hseen,
                facc1,
                facc2,
                faccd,
                covc,
                sampc,
                canc,
                max_steps,
            )

        zero_lane = jnp.zeros(chunk, dtype=jnp.uint32) + (head0 & u(0))
        false_lane = zero_lane != 0
        covc0 = (
            (
                jnp.zeros(A, dtype=jnp.uint32),  # per-action valid counts
                tuple(u(0) for _ in range(P)),  # per-property hit counts
                u(0),  # consumed rows (property evaluation count)
                jnp.zeros(DEPTH_CAP, dtype=jnp.uint32),  # depth histogram
            )
            if cov
            else ()
        )
        sampc0 = (
            (
                # scap+1 wide: index scap is the masked-write trash slot.
                jnp.zeros(scap + 1, dtype=jnp.uint32),  # fp1
                jnp.zeros(scap + 1, dtype=jnp.uint32),  # fp2
                jnp.zeros(scap + 1, dtype=jnp.uint32),  # depth
                jnp.zeros(scap + 1, dtype=jnp.uint32),  # action index
                u(0),  # occupied
                u(0),  # dropped (per-step capture-width overflow)
            )
            if sample_k
            else ()
        )
        canc0 = (u(0),) if canon else ()  # canon_rewrites of the dispatch

        # The era: the data-dependent while_loop plus its once-per-era
        # epilogue. The fingerprint snapshot lanes start empty; rec_bits
        # keeps first-discovery-wins across eras, matching the host
        # ordering of the serial driver.
        err0 = params[P_ERR]
        take_cap0 = params[P_TAKE_CAP]
        init = (
            table,
            queue,
            head0,
            count0,
            unique0,
            u(0),  # generated delta
            u(0),  # steps executed
            err0,  # unresolved-insert count (gates the era closed;
            # nonzero input = a seeding-time error surfacing on first
            # read)
            jnp.minimum(jnp.maximum(take_cap0, u(1)), u(chunk)),
            rec_bits0,  # scalar discovery bits accumulated for fin gate
            tuple(false_lane for _ in range(P)),
            tuple(zero_lane for _ in range(P)),
            tuple(zero_lane for _ in range(P)),
            tuple(zero_lane for _ in range(P)),
            covc0,
            sampc0,
            canc0,
            max_steps0,
        )
        (
            table,
            queue,
            head,
            count,
            unique,
            gen,
            steps,
            err_cnt,
            take_cap_out,
            _rec_acc,
            hseen,
            facc1,
            facc2,
            faccd,
            covc_out,
            sampc_out,
            canc_out,
            _ms,
        ) = lax.while_loop(cond, body, init)

        # Era-level epilogue (runs ONCE per era, outside the step loop,
        # where argmax / dynamic gathers are cheap): extract discovery
        # fingerprints from the snapshots and the max depth from the
        # ring. Depth along the ring is non-decreasing, so the deepest
        # state visited is the last one popped, at ring slot head-1.
        with stage_scope("epilogue"):
            rec_bits_out = rec_bits0
            for i in range(P):
                found = jnp.any(hseen[i])
                # Select the SHALLOWEST snapshot hit, not an arbitrary
                # one: BFS must report a shortest counterexample even when
                # later, deeper iterations hit the property at other chunk
                # positions.
                sel = jnp.argmin(
                    jnp.where(hseen[i], faccd[i], u(0xFFFFFFFF))
                )
                take_new = found & (((rec_bits_out >> u(i)) & u(1)) == u(0))
                rec_fp1 = rec_fp1.at[i].set(
                    jnp.where(take_new, facc1[i][sel], rec_fp1[i])
                )
                rec_fp2 = rec_fp2.at[i].set(
                    jnp.where(take_new, facc2[i][sel], rec_fp2[i])
                )
                rec_bits_out = rec_bits_out | (found.astype(u) << u(i))
            maxd = jnp.where(
                steps > 0, queue[S + 1][(head - u(1)) & u(qmask)], u(0)
            )
            # Adaptive era budget (device-side emission): the NEXT era's
            # step budget rides the P_MAX_STEPS output slot, so a chained
            # (speculative) dispatch follows the exact deterministic
            # schedule the serial driver would. TCP-slow-start shape:
            # double after an era that exhausted its budget with no other
            # exit reason pending, halve under spill/grow pressure, floor at
            # BUDGET_MIN, clamp at budget_cap. budget_cap == 0 turns the
            # emission off (pure pass-through — free-running and
            # target-bounded runs keep their fixed budgets). The host's
            # wall-clock cap keeps checkpoint cadence and reporter updates
            # honest (see the engine driver).
            fin_hit_final = ((rec_bits_out & fin_any) != u(0)) | (
                (fin_all_en != u(0)) & ((rec_bits_out & fin_all) == fin_all)
            )
            pressure = (count > high_water) | (unique > grow_limit)
            budget_only = (
                (steps >= max_steps0)
                & (count > u(0))
                & ~pressure
                & (err_cnt == u(0))
                & ~fin_hit_final
            )
            # In adaptive mode max_steps <= budget_cap <= 2^30 always
            # (host clamp), so the doubling cannot overflow uint32.
            grown = jnp.minimum(
                jnp.maximum(max_steps0, u(1)) * u(2), budget_cap
            )
            shrunk = jnp.maximum(
                jnp.minimum(max_steps0, budget_cap) >> u(1), u(BUDGET_MIN)
            )
            next_budget = jnp.where(
                budget_cap == u(0),
                max_steps0,
                jnp.where(
                    pressure, shrunk,
                    jnp.where(budget_only, grown, max_steps0),
                ),
            )

            parts = [
                jnp.stack(
                    [
                        head,
                        count,
                        unique,
                        rec_bits_out,
                        depth_limit,
                        grow_limit,
                        high_water,
                        next_budget,
                        gen,
                        maxd,
                        steps,
                        (err_cnt > 0).astype(u),
                        take_cap_out,
                        fin_any,
                        fin_all,
                        fin_all_en,
                        budget_cap,
                    ]
                ),
                rec_fp1,
                rec_fp2,
            ]
            if cov:
                # Coverage tail: act[A] | prop_hits[P] | expanded[1] | depth
                # hist[DEPTH_CAP] — this era's deltas, consumed by the host in
                # the SAME params download as everything else.
                act, covp, expanded, dhist = covc_out
                parts += [
                    act,
                    jnp.stack(list(covp)) if P else jnp.zeros(0, dtype=u),
                    expanded[None],
                    dhist,
                ]
            if sample_k:
                # Sample tail: [T1, T2, occupied, sdrop] + the sk2 smallest
                # slab entries by h1 (one top_k in the once-per-block
                # epilogue, where such reductions are cheap) with an explicit
                # validity lane — a real fp1 of 0xFFFFFFFF keys to 0 and
                # would otherwise be indistinguishable from padding. Ranking
                # by h1 alone skips 64-bit compares on device; the sk2 - k
                # pad rows plus the host's tie cut make the 64-bit bottom-k
                # exact (obs/sample.py module doc).
                sfp1, sfp2, sdep, sact, socc, sdrp = sampc_out
                used = jnp.arange(scap, dtype=u) < socc
                skey = jnp.where(used, ~sfp1[:scap], u(0))
                _topv, topi = lax.top_k(skey, sk2)
                parts += [
                    jnp.stack([st1, st2, socc, sdrp]),
                    sfp1[:scap][topi],
                    sfp2[:scap][topi],
                    sdep[:scap][topi],
                    sact[:scap][topi],
                    used[topi].astype(u),
                ]
            if canon:
                parts.append(canc_out[0][None])  # canon_rewrites, last
            params_out = jnp.concatenate(parts)
        return table, queue, rec_fp1, rec_fp2, params_out

    if raw:
        _LOOP_CACHE[key] = (tm, loop)
        return loop
    # Two donation variants of the SAME traced program (device backends
    # only — donation under the CPU persistent compilation cache
    # miscompiles, compat docstring). The serial variant donates every
    # operand including the params row and the rec_fp lanes: the driver
    # only takes it when all five inputs were consumed host-side (fresh
    # upload / post-readback dispatch). The chain variant pins the params
    # operand (argnum 4): a speculative chained dispatch feeds the
    # PREVIOUS era's params output straight back in while its async
    # device->host readback is still in flight — donating it would alias
    # the in-place write over the copy source. rec_fps stay donated in
    # both: solo discovery state rides the params row, the fp handles are
    # never read back mid-chain.
    d_serial = donate_argnums_pinned((0, 1, 2, 3, 4))
    d_chain = donate_argnums_pinned((0, 1, 2, 3, 4), pinned=(4,))
    serial = jax.jit(loop, donate_argnums=d_serial)
    # On CPU both sets resolve () — reuse ONE executable, no double
    # compile (tier-1 runs on the CPU backend).
    chain = (
        serial
        if d_chain == d_serial
        else jax.jit(loop, donate_argnums=d_chain)
    )
    program = EraProgram(serial, chain)
    _LOOP_CACHE[key] = (tm, program)
    return program


_SEED_CACHE: Dict[Tuple, Any] = {}
_SEED_LOOP_CACHE: Dict[Tuple, Tuple[TensorModel, Any]] = {}


def _build_seed_loop(tm: TensorModel, props, chunk: int, qcap: int, tcap: int,
                     canon: bool, cov: bool, sample_k: int = 0):
    """Fuse run seeding and the FIRST era into one jitted dispatch.

    Every dispatch is a host round-trip, and time-to-first-counterexample
    is a primary metric (BASELINE.md): a bug a few steps deep should cost
    ONE round-trip, not a seed trip plus an era trip. The composed program
    inlines the raw seeder and era loop; a run whose discovery fires in
    era 1 (or that completes outright) never pays a second dispatch.
    """
    key = (id(tm), chunk, qcap, tcap, len(props), canon, cov, sample_k)
    cached = _SEED_LOOP_CACHE.get(key)
    if cached is not None and cached[0] is tm:
        return cached[1]
    while len(_SEED_LOOP_CACHE) >= 16:
        _SEED_LOOP_CACHE.pop(next(iter(_SEED_LOOP_CACHE)))

    import jax

    loop = _build_loop(tm, props, chunk, qcap, canon, cov, raw=True,
                       sample_k=sample_k)
    seed = _build_seed(tm.state_width, qcap, tcap)

    @jax.jit
    def seed_run(qinit, h1, h2, params, rec_fp1, rec_fp2):
        table, queue, params2 = seed(qinit, h1, h2, params)
        return loop(table, queue, rec_fp1, rec_fp2, params2)

    _SEED_LOOP_CACHE[key] = (tm, seed_run)
    return seed_run


def _build_seed(S: int, qcap: int, tcap: int):
    """Compile the one-dispatch run seeder.

    Takes (qinit[W, n_init], params[P_LEN]) and returns (table, queue,
    params_out): fresh table/ring created ON DEVICE, init fingerprints
    claim-inserted (duplicate inits resolve exactly like the reference's
    bfs.rs:76-82 — all rows enqueue, the table keeps one), and the packed
    params filled in (count, unique, err). The output params feed the
    first era directly, so a run starts with ONE upload (qinit+params) and
    needs NO seed-time download (the old eager seed path paid three
    host<->device syncs).
    """
    key = (S, qcap, tcap)
    cached = _SEED_CACHE.get(key)
    if cached is not None:
        return cached
    while len(_SEED_CACHE) >= 16:
        _SEED_CACHE.pop(next(iter(_SEED_CACHE)))

    import jax
    import jax.numpy as jnp

    from ..obs.spans import SEED_SCOPE
    from ..ops import visited_set as vs

    W = S + 2  # ring lanes: state | ebits | depth (hashes recomputed on pop)

    @jax.jit
    def seed(qinit, h1, h2, params):
        with jax.named_scope(SEED_SCOPE):
            u = jnp.uint32
            n_init = qinit.shape[1]
            table = vs.empty_table(tcap)
            zero = jnp.zeros(n_init, dtype=jnp.uint32)
            table, is_new, unresolved, _ovf = vs.insert(
                table, h1, h2, zero, zero,
                jnp.ones(n_init, bool),
            )
            queue = tuple(
                jnp.zeros(qcap, dtype=jnp.uint32).at[:n_init].set(qinit[i])
                for i in range(W)
            )
            params_out = (
                params.at[P_HEAD].set(u(0))
                .at[P_COUNT].set(u(n_init))
                .at[P_UNIQUE].set(is_new.sum(dtype=u))
                .at[P_ERR].set(unresolved.sum(dtype=u))
            )
            return table, queue, params_out

    _SEED_CACHE[key] = seed
    return seed


# Parent lookups one walk program makes per chain; a deeper chain walks on
# from its last fingerprint in another dispatch of the same program.
WALK_HOPS = 64


def walk_parent_chains(table, fps, width: int, metrics):
    """Fingerprint chains from an init state to each of `fps`, walked
    inside the device `table` (`vs.walk_chains`), `width` rows a dispatch:
    only the chains cross to the host, not the table. `path_walk` times
    the dispatch, `path_download` the readback that waits for it. Raises
    RuntimeError for a fingerprint missing from the table."""
    import jax

    from ..ops import visited_set as vs

    chains = [[fp] for fp in fps]
    pending = list(range(len(chains)))
    while pending:
        batch, pending = pending[:width], pending[width:]
        h1 = np.zeros(width, dtype=np.uint32)
        h2 = np.zeros(width, dtype=np.uint32)
        for row, i in enumerate(batch):
            h1[row], h2[row] = split64(chains[i][-1])
        with metrics.phase("path_walk"):
            out = vs.walk_chains_jit(table, h1, h2, hops=WALK_HOPS)
            metrics.inc("path_walk_dispatches")
        with metrics.phase("path_download"):
            c1, c2, count, status = jax.device_get(out)
        for row, i in enumerate(batch):
            n = int(count[row])
            chains[i].extend(
                combine64(a, b) for a, b in zip(c1[row, :n], c2[row, :n])
            )
            if status[row] == vs.WALK_MISSING:
                raise RuntimeError(
                    f"fingerprint {chains[i][-1]} missing from visited table "
                    "during path reconstruction"
                )
            if status[row] == vs.WALK_EXHAUSTED:
                pending.append(i)
    for chain in chains:
        chain.reverse()
    return chains


# Below roughly this many unique states, the host engine's per-state cost
# beats the device engine's fixed per-dispatch round-trips and compile time
# (set before any measurement on the v5e; see the README "Host/device
# crossover" note).
SMALL_WORKLOAD_STATES = 10_000


class TpuBfsChecker(HostEngineBase):
    """Batched BFS over a TensorModel on the default JAX device."""

    # Parallelism here is the data-parallel chunk, not worker threads;
    # .threads(n) is accepted (and is a no-op) for API compatibility.
    _supports_threads = True

    def __init__(
        self,
        builder: CheckerBuilder,
        *,
        chunk_size: int = 8192,
        queue_capacity: int = 1 << 20,
        table_capacity: int = 1 << 22,
        sync_steps: int = 4096,
        checkpoint_path: Optional[str] = None,
        checkpoint_every: Optional[float] = None,
        resume_from: Optional[str] = None,
        keep_checkpoints: int = 2,
        compiled=None,
    ):
        # The constructor's own time, in the caller's thread (the base
        # class adopts the registry).
        metrics = MetricsRegistry()
        with metrics.phase("engine_init"):
            model = builder.model
            if isinstance(model, TensorModel):
                model = TensorModelAdapter(model)
            if not isinstance(model, TensorModelAdapter):
                raise TypeError(
                    "spawn_tpu_bfs requires a TensorModel (or its adapter); "
                    "rich host models must be encoded first — see stateright_tpu.tensor."
                )
            if compiled is not None:
                # Build/run split (engines/compiled.py): run against the
                # compiled check's interned model instance so every id(tm)-keyed
                # jit cache below hits — the request pays a dict lookup, not a
                # trace + XLA compile.
                from .compiled import model_signature

                if model_signature(model.tm) != compiled.signature:
                    raise ValueError(
                        "CompiledCheck signature mismatch: executable was built "
                        f"for {compiled.signature!r}, builder model is "
                        f"{model_signature(model.tm)!r}"
                    )
                if model.tm is not compiled.tm:
                    model = TensorModelAdapter(compiled.tm)
            super().__init__(builder, model=model, metrics=metrics)
            if self._visitor is not None:
                raise ValueError("the TPU engine does not support visitors")

            self.tm: TensorModel = model.tm
            # Symmetry reduction ON DEVICE (beyond the reference, whose BFS
            # ignores options.symmetry — only its DFS canonicalizes): when the
            # builder asks for symmetry, candidates are canonicalized by the
            # model's batched representative_lanes program before hashing and
            # insertion, so the frontier and visited set live entirely in
            # representative space (2pc-5: 8,832 -> 1,092 states).
            self._canon = builder.symmetry_fn_ is not None
            if self._canon and self.tm.representative_lanes is None:
                raise ValueError(
                    f"symmetry requested but {type(self.tm).__name__} defines "
                    "no representative_lanes canonicalizer"
                )
            self._tprops = self.tm.tensor_properties()
            n_event = sum(
                1 for p in self._tprops if p.expectation == Expectation.EVENTUALLY
            )
            if n_event > 32:
                raise ValueError("at most 32 eventually-properties supported")
            if len(self._tprops) > 32:
                # The recorded-discovery set crosses the host boundary as one
                # uint32 bitmask (see the packed-params layout above).
                raise ValueError("at most 32 tensor properties supported")
            if queue_capacity & (queue_capacity - 1):
                raise ValueError("queue_capacity must be a power of two")
            # qcap >= 2*C*A guarantees (a) the ring append never wraps over
            # unconsumed rows while count <= high_water and (b) a spill block
            # (<= C*A rows) always fits during refill, so spilled states are
            # never stranded.
            self._chunk = min(
                chunk_size, queue_capacity // (2 * max(1, self.tm.max_actions))
            )
            if self._chunk == 0:
                raise ValueError("queue_capacity too small for this model's fanout")
            self._qcap = queue_capacity
            self._tcap = table_capacity
            self._max_sync_steps = sync_steps
            # Checkpoint/resume: a capability the reference lacks (its runs are
            # in-memory only, SURVEY.md §5) — the dense table/ring layout makes
            # a checkpoint a straight array download. Writes are crash-atomic
            # with rolling generations and a content digest (engines/common.py);
            # checkpoint_every is wall-clock seconds, polled at era boundaries.
            validate_checkpoint_cadence(
                checkpoint_every, checkpoint_path, keep_checkpoints
            )
            self._ckpt_path = checkpoint_path
            self._ckpt_every = checkpoint_every
            self._ckpt_keep = keep_checkpoints
            self._resume_from = resume_from
            self._last_ckpt = time.monotonic()
            # Chaos-injection hook (tests/test_durability_chaos.py): pretend the
            # probe budget was exhausted once this era count is reached,
            # exercising the degraded-regrow recovery without needing a
            # pathological probe sequence.
            self._chaos_probe_error_era: Optional[int] = None
            if checkpoint_path is not None:
                register_signal_checkpoint_flush(self)
            self._cov = self._coverage.enabled
            # Bottom-k space sampling (obs/sample.py): the compiled loop
            # carries the capture slab only when the builder asked for it.
            self._sample_k = self._sampler.k if self._sampler is not None else 0
            program = _build_loop(
                self.tm, self._tprops, self._chunk, self._qcap, self._canon,
                self._cov, sample_k=self._sample_k,
            )
            self._loop = program.serial
            self._loop_chain = program.chain

            # Host-side bookkeeping.
            self._unique = 0
            self._discovery_fps: Dict[str, int] = {}
            # Tiered spill staging (ops/tiering.py): a budgeted host-RAM LIFO
            # with an npz disk tier below it; unbudgeted (env unset) it is a
            # plain in-RAM stack, byte-for-byte the old list behavior.
            self._spill = TieredSpillStore(
                host_budget_bytes=spill_host_budget_bytes(),
                on_tier=self._on_spill_tier,
            )
            # Delta-checkpoint chain state (engines/common.py
            # save_checkpoint_tiered): None = next save is a full base.
            self._ckpt_delta = None
            # Era of the last proactive reshard (one doubling per forecast).
            self._reshard_last_era = -1
            # The metrics registry (obs/metrics.py, created by the base class)
            # carries the engine's health gauges — eras dispatched, steps
            # executed, spill/refill row volume, table growths, take_cap —
            # surfaced via Checker.telemetry() / report, plus per-era phase
            # timers (device_era, readback, spill, refill, table_grow).
            self._metrics.set_gauge("take_cap", self._chunk)
            self._era_t0: Optional[float] = None
            # Multiplexed-lane runs are the INTENDED path for sub-crossover
            # state spaces (serve/README.md): a lane shares one compiled
            # executable and one fused era with its whole batch, so the
            # per-run dispatch/compile overheads the hint warns about do not
            # apply — firing it there would steer users away from the right
            # engine.
            self._mux_lane = bool(getattr(builder, "multiplex_lane_", False))
            # Speculative era pipelining (CheckerBuilder.pipeline(), default
            # on): keep up to CHAIN_DEPTH eras chained off the on-device
            # state while their readbacks are in flight. See the _run driver
            # for the soundness argument (chained dispatch is an identity
            # no-op on every device-visible host-intervention exit).
            self._pipeline = bool(getattr(builder, "pipeline_", True))
            # High-water mark of in-flight chained dispatches (gauge).
            self._chain_max = 0
            # Small-workload guard: with a state-count target under the
            # crossover, the host engine will beat this one — say so up front
            # (the run-end check below catches untargeted small runs).
            if (
                builder.target_state_count_ is not None
                and builder.target_state_count_ < SMALL_WORKLOAD_STATES
            ):
                self._small_workload_hint(builder.target_state_count_, "targeted")

            self._init_ebits_tensor = 0
            e = 0
            for p in self._tprops:
                if p.expectation == Expectation.EVENTUALLY:
                    self._init_ebits_tensor |= 1 << e
                    e += 1

        self._start()

    # -- engine body --------------------------------------------------------

    def _run(self) -> None:
        import jax.numpy as jnp

        from ..fingerprint import hash_words_np
        from ..ops import visited_set as vs

        tm = self.tm
        S = tm.state_width
        A = tm.max_actions
        C = self._chunk
        P = len(self._tprops)
        W = S + 2  # queue lanes: state | ebits | depth
        ncov = _cov_len(A, P) if self._cov else 0
        # Sample tail (obs/sample.py): [T1, T2, occupied, sdrop] plus
        # five drained lanes of slab_entries(k) words each.
        if self._sample_k:
            from ..obs.sample import slab_entries

            sk2 = slab_entries(self._sample_k)
            s_base = P_LEN + 2 * P + ncov
        else:
            sk2 = s_base = 0
        last_thresh = None  # threshold words last uploaded to the device

        depth_limit = (
            self._target_max_depth
            if self._target_max_depth is not None
            else 0xFFFFFFFF
        )
        high_water = self._qcap - C * A
        # Era budget: the device loop exits by itself on every meaningful
        # condition (empty frontier, spill, grow, discovery-finish, probe
        # error); the step budget only exists so the host can poll wall-
        # clock concerns — timeouts and checkpoint cadence — at bounded
        # granularity. Unbudgeted runs use the full sync_steps allowance.
        max_sync = (
            self._max_sync_steps
            if self._timeout is None and self._ckpt_every is None
            else min(BUDGET_MIN, self._max_sync_steps)
        )
        # Adaptive era budget (TCP-slow-start): engaged only when a wall-
        # clock concern forces polling. The DEVICE emits the next era's
        # budget through the P_MAX_STEPS output slot (doubling after
        # budget-only exits, halving under spill/grow pressure — see
        # _build_loop's epilogue), which keeps the schedule deterministic
        # and identical whether eras are dispatched serially or
        # speculatively. The host only moves the CAP, by wall-clock
        # feedback, so growing eras can never starve checkpoint cadence or
        # reporter updates. budget_cap == 0 disables the emission entirely
        # (free-running runs keep the full fixed allowance).
        adaptive = self._timeout is not None or self._ckpt_every is not None
        budget = max_sync
        budget_cap = min(BUDGET_MIN, max_sync) if adaptive else 0
        cap_limit = min(self._max_sync_steps, 1 << 30)  # uint32-safe doubling
        poll_target = None
        if self._ckpt_every is not None:
            poll_target = self._ckpt_every / 4.0
        if self._timeout is not None:
            t = self._timeout / 4.0
            poll_target = t if poll_target is None else min(poll_target, t)
        # Finish-policy discovery masks for the device-side early exit.
        fin_any, fin_all, fin_all_en = self._finish_when.device_masks(
            self._tprops
        )
        params_dev = None
        last_max_steps = None
        last_budget_cap = budget_cap
        take_cap = self._chunk
        # Symmetry word (canon_rewrites), last in the params.
        ncanon = int(self._canon)
        c_off = params_len(A, P, self._cov, self._sample_k)

        _dbg("run: encoding inits")
        with self._metrics.phase("run_setup"):
            if self._resume_from is not None:
                table, queue, head, count, rec_bits, rec_fp1, rec_fp2 = (
                    self._load_checkpoint(self._resume_from, W)
                )
                first_result_pending = False
            else:
                inits = np.asarray(tm.init_states_array(), dtype=np.uint32)
                init_lanes = tuple(inits[:, i] for i in range(S))
                inb = np.asarray(
                    tm.within_boundary_lanes(np, init_lanes), dtype=bool
                )
                inits = inits[inb]
                if self._canon:
                    canon_lanes = tm.representative_lanes(
                        np, tuple(inits[:, i] for i in range(S))
                    )
                    inits = np.stack(
                        [np.asarray(l, dtype=np.uint32) for l in canon_lanes],
                        axis=1,
                    )
                    # Dedupe representatives host-side: distinct raw inits can
                    # canonicalize to one representative, and while the table's
                    # claim insert would keep exactly one, every duplicate ROW
                    # would still enqueue (a redundant re-expansion each) and
                    # count toward state_count — ring contents and counters
                    # must agree with the table's view under symmetry.
                    inits = np.unique(inits, axis=0)
                n_init = len(inits)
                self._state_count = n_init
                if n_init == 0:
                    return
                if self._cov:
                    # Unique initial states enter the visited set at depth 1
                    # inside the fused seeder, before the loop's histogram
                    # starts counting — record them host-side (distinct rows
                    # == distinct fingerprints short of a hash collision).
                    self._coverage.record_depth(
                        1, len(np.unique(inits, axis=0))
                    )
                if n_init > self._qcap:
                    raise ValueError("more initial states than queue capacity")
                vcap = _vcap(A, C)
                while n_init + vcap > vs.MAX_LOAD * self._tcap:
                    self._tcap *= 2

                # One upload (qinit rows + params template), zero downloads: the
                # jitted seeder builds the table/ring on device, claim-inserts
                # the init fingerprints (dup inits resolve like bfs.rs:76-82;
                # all rows enqueue), and fills count/unique/err into the packed
                # params, which feed the first era dispatch directly.
                h1, h2 = hash_words_np(inits)
                if self._sampler is not None:
                    # The seeder inserts init states before the era loop's
                    # slab starts capturing — offer them host-side (their
                    # rows are in hand anyway, so the sample records carry
                    # real state lanes for free).
                    self._sampler.offer_array(
                        (h1.astype(np.uint64) << np.uint64(32))
                        | h2.astype(np.uint64),
                        depths=np.ones(n_init, dtype=np.int64),
                        states=inits,
                    )
                qinit = np.zeros((W, n_init), dtype=np.uint32)
                qinit[:S] = inits.T
                qinit[S] = self._init_ebits_tensor
                qinit[S + 1] = 1

                max_steps0 = max_sync
                if self._target_state_count is not None:
                    remaining = max(0, self._target_state_count - n_init)
                    max_steps0 = max(
                        1, min(max_steps0, 1 + remaining // max(1, C * A))
                    )
                template = np.zeros(c_off + ncanon, dtype=np.uint32)
                if self._sampler is not None:
                    t1, t2 = self._sampler.threshold_parts()
                    template[s_base] = t1
                    template[s_base + 1] = t2
                    last_thresh = (t1, t2)
                template[P_DEPTH_LIMIT] = depth_limit
                template[P_HIGH_WATER] = high_water
                template[P_MAX_STEPS] = max_steps0
                template[P_TAKE_CAP] = take_cap
                template[P_FIN_ANY] = fin_any
                template[P_FIN_ALL] = fin_all
                template[P_FIN_ALL_EN] = fin_all_en
                template[P_BUDGET_CAP] = budget_cap
                template[P_GROW_LIMIT] = max(
                    0, int(vs.MAX_LOAD * self._tcap) - vcap
                )

                rec_bits = 0
                rec_fp1 = jnp.zeros(P, dtype=jnp.uint32)
                rec_fp2 = jnp.zeros(P, dtype=jnp.uint32)
                _dbg("run: dispatching fused seed+first-era")
                seed_run = _build_seed_loop(
                    tm, self._tprops, C, self._qcap, self._tcap, self._canon,
                    self._cov, sample_k=self._sample_k,
                )
                seed_args = (
                    jnp.asarray(qinit), jnp.asarray(h1), jnp.asarray(h2),
                    jnp.asarray(template), rec_fp1, rec_fp2,
                )
                first_result_pending = True

        if first_result_pending:
            self._era_t0 = time.monotonic()
            with self._metrics.phase("era_dispatch"):
                table, queue, rec_fp1, rec_fp2, params_dev = seed_run(
                    *seed_args
                )
            self._metrics.inc("dispatches")
            head = 0
            count = n_init
            # Provisional (exact unless dup inits); corrected at first read.
            self._unique = n_init
            self._mem_register(table, queue, (rec_fp1, rec_fp2), params_dev)
            last_max_steps = max_steps0
            _dbg("run: seeded; entering era loop")

        # Spill hysteresis: drain down to / refill up to this margin below
        # high_water, so a spilling run still gets long eras between host
        # round-trips instead of bouncing on the watermark (see the drain
        # note below). Guaranteed >= one block of room: qcap >= 2*C*A.
        spill_target = max(high_water // 2, high_water - 64 * C * A)
        stop = False

        def process_result(spec_in_flight=False):
            """Read back one era result (the fused seed+first-era dispatch
            or a loop dispatch) and consume it on the host."""
            with self._metrics.phase("readback"):
                vals = np.asarray(params_dev)  # the ONE download per block
            with self._metrics.phase("era_host"):
                consume(vals, spec_in_flight)

        def consume(vals, spec_in_flight):
            """Consume one era's readback `vals`: counters, discoveries,
            spill, checkpoints, and stop conditions. With
            ``spec_in_flight`` a chained speculative era is still
            executing on device: the checkpoint
            save is deferred to the next serial boundary (the table/queue
            bindings here are the NEXT era's output buffers, so a save now
            could pair this era's head/count with a ring the next era has
            already advanced — unless that era is a no-op, which the
            caller cannot know yet)."""
            nonlocal head, count, take_cap, rec_bits, stop, params_dev
            nonlocal budget, budget_cap, last_thresh
            era_dt = 0.0
            if self._era_t0 is not None:
                # The era's true wall time: dispatch through readback
                # complete (dispatch alone returns immediately: JAX
                # dispatches asynchronously).
                era_dt = time.monotonic() - self._era_t0
                self._metrics.add_phase("device_era", era_dt)
                # Distribution twin of the cumulative phase: era latency
                # percentiles for /stats and the Prometheus exposition.
                self._metrics.observe("era_secs", era_dt)
                self._era_t0 = None
            _dbg(
                f"era result steps={vals[10]} gen={vals[8]} count={vals[1]} "
                f"unique={vals[2]} rec={vals[3]:b}"
            )
            err = int(vals[11])
            if not err and self._chaos_probe_error_era is not None and (
                self._metrics.get("eras") >= self._chaos_probe_error_era
            ):
                self._chaos_probe_error_era = None
                err = 1
            if err:
                # Cannot happen with the proactive growth short of a
                # pathological probe sequence; losing states would be an
                # unsound "verified", so the era's work must be discarded.
                # A nonzero error with ZERO steps on the first era means the
                # unresolved count flowed in from the seeder (init-state
                # insert), not the era loop — attribute it correctly.
                if self._metrics.get("eras") == 0 and int(vals[10]) == 0:
                    raise RuntimeError(
                        "init-state seeding exhausted the visited-table "
                        "probe budget (duplicate-heavy or adversarial "
                        "initial fingerprints); raise table_capacity"
                    )
                # Recoverable when a checkpoint exists: the while loop
                # reloads the pre-era state, regrows, and re-runs.
                raise _ProbeBudgetExhausted(
                    "visited-table probe budget exhausted despite headroom"
                )
            head = int(vals[0])
            count = int(vals[1])
            take_cap = int(vals[P_TAKE_CAP])
            # Device-emitted next-era budget (pass-through when adaptivity
            # is off); the budget USED by the era just consumed is gauged
            # for the obs catalog.
            budget = int(vals[P_MAX_STEPS])
            if last_max_steps is not None:
                self._metrics.set_gauge(
                    "era_step_budget", int(last_max_steps)
                )
            if poll_target is not None and era_dt > 0.0:
                # Wall-clock cap feedback: let the device's slow-start
                # climb only while eras stay well inside the polling
                # cadence; back the cap off when an era overshoots it.
                if era_dt < poll_target / 2 and budget_cap < cap_limit:
                    budget_cap = min(budget_cap * 2, cap_limit)
                elif era_dt > poll_target and budget_cap > BUDGET_MIN:
                    budget_cap = max(budget_cap // 2, BUDGET_MIN)
            self._metrics.inc("eras")
            self._metrics.inc("steps", int(vals[10]))
            self._metrics.inc("states_generated", int(vals[8]))
            if ncanon:
                self._metrics.inc("canon_rewrites", int(vals[c_off]))
            self._metrics.set_gauge("take_cap", take_cap)
            self._unique = int(vals[2])
            self._state_count += int(vals[8])
            self._max_depth = max(self._max_depth, int(vals[9]))
            # Record first discovery per property (reference races are
            # benign; ours are deterministic per compiled program).
            new_bits = int(vals[3])
            if new_bits != rec_bits:
                # Discovery fingerprints ride the params tail — no extra
                # device read on the counterexample path.
                fp1 = vals[P_LEN : P_LEN + P]
                fp2 = vals[P_LEN + P : P_LEN + 2 * P]
                for i, p in enumerate(self._tprops):
                    if (new_bits >> i) & 1 and p.name not in self._discovery_fps:
                        self._discovery_fps[p.name] = combine64(fp1[i], fp2[i])
                rec_bits = new_bits

            if self._cov:
                # The era's coverage deltas ride the same download
                # (layout: act[A] | prop_hits[P] | expanded | depth hist).
                base = P_LEN + 2 * P
                cov_acc = self._coverage
                cov_acc.record_action_counts(vals[base : base + A])
                expanded = int(vals[base + A + P])
                for i, p in enumerate(self._tprops):
                    cov_acc.record_property_eval(p.name, expanded)
                    cov_acc.record_property_hit(
                        p.name, int(vals[base + A + i])
                    )
                cov_acc.record_depth_counts(
                    vals[base + A + P + 1 : base + ncov]
                )

            if self._sampler is not None:
                # Sample-slab drain: same download as everything else.
                occupied = int(vals[s_base + 2])
                sdrop = int(vals[s_base + 3])
                off = s_base + 4
                if occupied or sdrop:
                    self._sampler.drain_slab(
                        vals[off : off + sk2],
                        vals[off + sk2 : off + 2 * sk2],
                        vals[off + 2 * sk2 : off + 3 * sk2],
                        vals[off + 4 * sk2 : off + 5 * sk2],
                        occupied,
                        dropped=sdrop,
                        actions=vals[off + 3 * sk2 : off + 4 * sk2],
                    )
                if self._sampler.threshold_parts() != last_thresh:
                    # The drain tightened the threshold: force a fresh
                    # params upload next era so the device stops
                    # capturing (sound either way — a stale threshold
                    # only admits a superset — but a tighter one keeps
                    # eras long and the slab quiet). Converges fast:
                    # expected total captures are ~k * ln(n / k).
                    params_dev = None

            # Spill if the next chunk could overflow the ring. Drain to the
            # MARGIN below the watermark, not just to it: draining only the
            # overhang lets the very next era re-cross the line after a few
            # steps, thrashing spill round-trips (measured on ABD c=4:
            # 2-3 useful steps per ~7s spill cycle). The margin trades one
            # bigger drain for eras long enough to amortize it.
            spilled = 0
            if count > high_water:
                k = count - spill_target
                take_idx = jnp.asarray(
                    (head + count - k + np.arange(k)) & (self._qcap - 1)
                )
                # Stack on device, download ONCE (not one transfer per
                # lane).
                with self._metrics.phase("spill"):
                    big = np.asarray(
                        jnp.stack(
                            [queue[i][take_idx] for i in range(W)], axis=1
                        )
                    )
                # Keep blocks refill-sized so partial refills stay possible.
                for off in range(0, k, C * A):
                    self._spill.append(big[off : off + C * A])
                count -= k
                spilled = k
                self._metrics.inc("spill_rows", k)
                # Refills can place these rows after deeper children, breaking
                # the ring's depth monotonicity that the block-level maxd read
                # relies on — fold their depth in here. (Counts rows that are
                # guaranteed to be visited unless the run stops early; a rare
                # slight over-report beats a systematic under-report.)
                self._max_depth = max(self._max_depth, int(big[:, S + 1].max()))
                params_dev = None  # host-side count changed; force re-upload
                if self._memory is not None:
                    self._memory.staging(
                        self._spill.host_bytes(),
                        event="spill",
                        rows=int(k),
                    )

            self._obs_event(
                "era",
                frontier=count,
                load_factor=round(self._unique / self._tcap, 4),
                take_cap=take_cap,
                steps=int(vals[10]),
                generated=int(vals[8]),
                spill_rows=spilled,
            )

            if not spec_in_flight and self._ckpt_path is not None and (
                self._ckpt_every is not None
                and time.monotonic() - self._last_ckpt >= self._ckpt_every
            ):
                self._save_checkpoint(
                    table, queue, head, count, rec_bits, rec_fp1, rec_fp2
                )

            # Flight record after spill/checkpoint so this era's host work
            # lands in its own host_gap (zero extra device reads: every
            # field is from `vals` or host clocks).
            self._flight_record(
                device_era_secs=era_dt,
                steps=int(vals[10]),
                generated=int(vals[8]),
                unique=self._unique,
                frontier=count,
                load_factor=round(self._unique / self._tcap, 4),
                take_cap=take_cap,
                spill_rows=spilled,
            )

            if self._finish_matched(self._discovery_fps):
                stop = True
            elif (
                self._target_state_count is not None
                and self._state_count >= self._target_state_count
            ):
                stop = True
            elif self._timed_out():
                stop = True
            elif self._ckpt_stop.is_set():
                # Graceful-stop request (SIGTERM/SIGINT flush): exit the
                # loop; the final checkpoint below captures this boundary.
                self._metrics.set_gauge("interrupted", 1)
                stop = True

        if first_result_pending:
            process_result()

        # Graceful degradation budget: each recovery doubles the table, so
        # a handful of rounds covers any realistic exhaustion; an unbounded
        # loop would mask a genuinely pathological model.
        regrow_budget = 8

        # Speculative era pipelining (tentpole; CheckerBuilder.pipeline()):
        # the device loop re-derives EVERY exit condition from the chained
        # params vector — count/high_water/grow_limit/fin bits/err_cnt all
        # gate the while predicate, and err_cnt seeds from P_ERR — so an
        # era dispatched off a host-intervention boundary is an exact
        # identity no-op (outputs value-identical to inputs). That makes
        # chaining era N+1 before era N's readback unconditionally SOUND
        # for device-visible exits; the chain is simply not entered while
        # any host-ONLY concern (spill-backlog refill, checkpoint cadence,
        # timeout, graceful stop, state-count targets) could fire, and the
        # two that can still land mid-era (timeout, SIGTERM) are handled
        # by consuming the speculative era's real, sound work before
        # stopping. Results are bit-identical to the serial driver either
        # way; only the dispatch gap between eras disappears.
        pipeline = self._pipeline and self._target_state_count is None

        while not stop and (count > 0 or self._spill):
            host_dirty = params_dev is None
            # Refill from host spill, leaving room for the worst-case append
            # (count must stay <= high_water going into the loop, or the
            # ring append could wrap over unconsumed frontier rows; the
            # margin below keeps refills from re-crossing the line
            # immediately). An empty frontier always refills at least one
            # block (a block is <= C*A <= high_water), so spill can't
            # strand.
            refill = []
            refill_rows = 0
            while self._spill and (
                count + refill_rows + self._spill.peek_rows() <= spill_target
                or (count == 0 and not refill)
            ):
                refill.append(self._spill.pop())
                refill_rows += len(refill[-1])
            if refill:
                rows = np.concatenate(refill, axis=0)
                k = len(rows)
                tail_idx = jnp.asarray(
                    (head + count + np.arange(k)) & (self._qcap - 1)
                )
                with self._metrics.phase("refill"):
                    rows_dev = jnp.asarray(rows)  # ONE upload for all blocks
                    queue = tuple(
                        queue[i].at[tail_idx].set(rows_dev[:, i])
                        for i in range(W)
                    )
                count += k
                self._metrics.inc("refill_rows", k)
                host_dirty = True
                if self._memory is not None:
                    self._memory.staging(
                        self._spill.host_bytes(),
                        event="refill",
                        rows=int(k),
                    )
            if count == 0:
                break

            # Proactive growth: guarantee the worst-case insert batch keeps
            # the load factor under vs.MAX_LOAD, so probe budgets can't be
            # exhausted (exhaustion would silently drop states).
            vcap = _vcap(A, C)
            grew = False
            while self._unique + vcap > vs.MAX_LOAD * self._tcap:
                with self._metrics.phase("table_grow"):
                    table, self._tcap = self._grow_table(table)
                self._metrics.inc("table_growths")
                host_dirty = True
                grew = True
            # Elastic re-shard (ISSUE 20): when the forecaster projects
            # growth within the horizon, take the doubling NOW at an era
            # boundary we already own — same rehash as the degraded
            # regrow, but before any probe-budget abort could trigger it.
            # Output is untouched: a bigger table changes slots, never
            # membership (growth rebuilds from the same fingerprints). At
            # most one proactive doubling per era: the forecast refreshes
            # at every _flight_record, so each further doubling needs a
            # projection that already accounts for the last one.
            if (
                self._proactive_reshard_due()
                and self._metrics.get("eras") != self._reshard_last_era
            ):
                self._reshard_last_era = self._metrics.get("eras")
                with self._metrics.phase("table_grow"):
                    table, self._tcap = self._grow_table(table)
                self._metrics.inc("table_growths")
                self._metrics.inc("reshard_proactive")
                self._obs_event(
                    "reshard_proactive", table_capacity=self._tcap
                )
                host_dirty = True
                grew = True
            if grew:
                self._mem_register(table, queue, (rec_fp1, rec_fp2), params_dev)
            grow_limit = max(0, int(vs.MAX_LOAD * self._tcap) - vcap)

            # The era budget is the device-emitted one (== max_sync
            # verbatim when adaptivity is off), host-clamped to the wall-
            # clock cap; a host override of either the budget or the cap
            # is a param change the feedback path cannot carry.
            max_steps = min(budget, budget_cap) if adaptive else budget
            if self._target_state_count is not None:
                # Bound overshoot past the state-count target: each step
                # generates at most C*A states.
                remaining = max(0, self._target_state_count - self._state_count)
                max_steps = max(1, min(max_steps, 1 + remaining // max(1, C * A)))
            if max_steps != budget or budget_cap != last_budget_cap:
                host_dirty = True

            if host_dirty:
                arr = np.zeros(c_off + ncanon, dtype=np.uint32)
                if self._sampler is not None:
                    t1, t2 = self._sampler.threshold_parts()
                    arr[s_base] = t1
                    arr[s_base + 1] = t2
                    last_thresh = (t1, t2)
                arr[:P_LEN] = [
                    head,
                    count,
                    self._unique,
                    rec_bits,
                    depth_limit,
                    grow_limit,
                    high_water,
                    max_steps,
                    0,
                    0,
                    0,
                    0,
                    take_cap,
                    fin_any,
                    fin_all,
                    fin_all_en,
                    budget_cap,
                ]
                with self._metrics.phase("upload"):
                    params_in = jnp.asarray(arr)
            else:
                params_in = params_dev
            last_max_steps = max_steps
            last_budget_cap = budget_cap

            _t0 = time.monotonic()
            self._era_t0 = _t0
            with self._metrics.phase("era_dispatch"):
                table, queue, rec_fp1, rec_fp2, params_dev = self._loop(
                    table, queue, rec_fp1, rec_fp2, params_in
                )
            self._metrics.inc("dispatches")
            _dbg(
                f"block dirty={host_dirty} max_steps={max_steps} "
                f"dispatch={time.monotonic() - _t0:.3f}s"
            )
            # Speculative chain (oldest first, at most CHAIN_DEPTH): chain[i] is the
            # params output of the i-th era chained past the one whose
            # readback (params_dev) the host is about to consume;
            # chain_t0[i] its dispatch timestamp.
            chain: List[Any] = []
            chain_t0: List[float] = []
            try:
                while True:
                    # Top up the chain while every host-only concern is
                    # quiet: each chained era launches off the newest
                    # on-device params with its readback queued behind the
                    # ones already in flight.
                    while (
                        pipeline
                        and len(chain) < CHAIN_DEPTH
                        and not self._spill
                        and not self._ckpt_stop.is_set()
                        and not self._timed_out()
                        and not self._proactive_reshard_due()
                        and (
                            self._ckpt_every is None
                            or time.monotonic() - self._last_ckpt
                            < self._ckpt_every
                        )
                    ):
                        # Kick the oldest pending readback without
                        # blocking, then chain off the on-device state
                        # (the chain variant pins the params operand, so
                        # every readback source stays live).
                        src = chain[-1] if chain else params_dev
                        try:
                            src.copy_to_host_async()
                        except AttributeError:
                            pass  # CPU backend: the copy is free anyway
                        t0 = time.monotonic()
                        with self._metrics.phase("era_dispatch"):
                            (
                                table, queue, rec_fp1, rec_fp2, nxt,
                            ) = self._loop_chain(
                                table, queue, rec_fp1, rec_fp2, src
                            )
                        self._metrics.inc("dispatches")
                        self._metrics.inc("spec_dispatch")
                        chain.append(nxt)
                        chain_t0.append(t0)
                        if len(chain) > self._chain_max:
                            self._chain_max = len(chain)
                            self._metrics.set_gauge(
                                "spec_chain_depth", self._chain_max
                            )
                    if not chain:
                        # Serial boundary: consume the in-flight era with
                        # full host services (spill, checkpoint, stop).
                        process_result()
                        break
                    process_result(spec_in_flight=True)
                    if (
                        not stop
                        and count > 0
                        and not self._spill
                        and params_dev is not None
                        and self._unique + vcap <= vs.MAX_LOAD * self._tcap
                        and not self._proactive_reshard_due()
                    ):
                        # The era ended inside every gate: the oldest
                        # chained era IS the next era and has been
                        # executing since this readback completed.
                        # Marginal timing anchor: readback-to-readback, so
                        # the overlapped dispatch books as device time,
                        # not host gap.
                        params_dev = chain.pop(0)
                        chain_t0.pop(0)
                        last_max_steps = budget
                        self._era_t0 = time.monotonic()
                        continue
                    # Host action at this boundary: drain the chain in
                    # order. A device-visible exit (spill, grow, fin,
                    # empty frontier) made every later chained era an
                    # identity no-op — account those as wasted
                    # speculation, keep their (value-identical) outputs.
                    # A host-ONLY stop (timeout, SIGTERM) can land
                    # mid-chain instead; the chained eras then ran real,
                    # sound work — consume each normally before stopping.
                    while chain:
                        spec = chain.pop(0)
                        spec_t0 = chain_t0.pop(0)
                        if int(np.asarray(spec)[P_STEPS]) == 0:
                            self._metrics.inc("spec_wasted")
                            self._era_t0 = None
                            if params_dev is not None:
                                # Chain tail (value-equal): later
                                # dispatches feed off this one.
                                params_dev = spec
                            continue
                        params_dev = spec
                        self._era_t0 = spec_t0  # overlap-aware
                        last_max_steps = budget
                        process_result(spec_in_flight=bool(chain))
                    break
            except _ProbeBudgetExhausted:
                # Graceful degradation (degraded_regrow): discard the failed
                # era, reload the last crash-safe checkpoint (the pre-era
                # state), double the table, and continue — instead of
                # aborting the whole run. Only possible with a checkpoint:
                # the consumed frontier rows are otherwise gone.
                if chain:
                    # Chained eras were in flight. A REAL probe error is
                    # device-visible (err_cnt seeds from P_ERR), so every
                    # chained era was an identity no-op; a chaos-injected
                    # fake may have let them run real work. Either way the
                    # checkpoint reload below discards their buffers
                    # wholesale — just quiesce the dispatches and count
                    # the speculation as wasted.
                    for spec in chain:
                        np.asarray(spec)
                        self._metrics.inc("spec_wasted")
                    chain = []
                    chain_t0 = []
                from .common import checkpoint_generations

                if (
                    self._ckpt_path is None
                    or regrow_budget == 0
                    or not checkpoint_generations(self._ckpt_path)
                ):
                    raise
                regrow_budget -= 1
                table, queue, head, count, rec_bits, rec_fp1, rec_fp2 = (
                    self._load_checkpoint(self._ckpt_path, W)
                )
                with self._metrics.phase("table_grow"):
                    table, self._tcap = self._grow_table(table)
                self._metrics.inc("degraded_regrow")
                self._metrics.inc("table_growths")
                self._obs_event(
                    "degraded_regrow", frontier=count, new_tcap=self._tcap
                )
                params_dev = None  # host state changed; force re-upload
                if self._memory is not None:
                    self._memory.event(
                        "checkpoint_load", frontier=int(count)
                    )
                    self._mem_register(
                        table, queue, (rec_fp1, rec_fp2), params_dev
                    )

        # A final checkpoint makes interrupted runs (targets, timeouts)
        # resumable from their exact stopping point.
        if self._ckpt_path is not None:
            self._save_checkpoint(
                table, queue, head, count, rec_bits, rec_fp1, rec_fp2
            )
        # Any disk-tier spool is dead weight past this point (a resume
        # rebuilds the stack from the checkpoint's spill arrays).
        self._spill.close()

        if self._unique < SMALL_WORKLOAD_STATES:
            self._small_workload_hint(self._unique, "explored")

        # The deepest speculative chain reached.
        self._metrics.set_gauge("spec_chain_depth", self._chain_max)

        # Retained (on device) for path reconstruction, which walks it there.
        self._table_dev = table
        if self._memory is not None:
            # Re-point the ledger at the final era's live buffers (shapes
            # are identical across an era; this keeps the nbytes parity
            # check honest against what is actually resident at run end).
            led = self._memory.ledger
            led.attach("visited_table", table)
            led.attach("frontier_queue", queue)
            led.attach("record_fps", (rec_fp1, rec_fp2))
            if params_dev is not None:
                led.attach("packed_params", params_dev)
                led.attach("coverage_slab", params_dev)
                led.attach("sample_slab", params_dev)
        return

    def _on_spill_tier(self, direction, rows, nbytes, disk_bytes) -> None:
        """Tier-move hook from the TieredSpillStore: counters + the
        memory ledger's disk component and `spill_tier` event, so
        `plan == ledger == nbytes` stays exact across all three tiers."""
        if direction == "ram_to_disk":
            self._metrics.inc("spill_tier_rows", int(rows))
        else:
            self._metrics.inc("spill_tier_refill_rows", int(rows))
        self._metrics.set_gauge("spill_disk_bytes", int(disk_bytes))
        if self._memory is not None:
            self._memory.ledger.register(
                "spill_disk", nbytes=int(disk_bytes), kind="disk"
            )
            self._memory.event(
                "spill_tier",
                direction=direction,
                rows=int(rows),
                bytes=int(nbytes),
                disk_bytes=int(disk_bytes),
            )

    def _proactive_reshard_due(self) -> bool:
        """Forecast-triggered elastic reshard (ISSUE 20): with a device
        limit set and exhaustion projected, front-run the next table
        doubling once the forecaster puts it within the reshard horizon
        — the growth lands at a host-chosen era boundary (chain drained)
        instead of the forced mid-pressure one, and never fires on
        unlimited runs (eras_to_exhaustion needs a limit).  The measured
        load-fraction floor keeps it self-limiting: each doubling halves
        ``load_frac``, so a diverging fit cannot re-trigger every era."""
        rec = self._memory
        if rec is None:
            return False
        fc = rec.last_forecast()
        if fc.get("eras_to_exhaustion") is None:
            return False
        eta_grow = fc.get("eras_to_grow")
        from ..obs.memory import RESHARD_HORIZON_ERAS, RESHARD_MIN_LOAD_FRAC

        return (
            eta_grow is not None
            and eta_grow <= RESHARD_HORIZON_ERAS
            and fc.get("load_frac", 0.0) >= RESHARD_MIN_LOAD_FRAC
        )

    def _mem_register(self, table, queue, rec_fps, params_dev) -> None:
        """(Re-)register every device buffer with the memory ledger from
        the shared size formulas (obs/memory.py bfs_component_sizes) —
        the planner predicts exactly what lands here, and the parity test
        locks the formulas to the live nbytes. Called after seeding and
        after every table growth; re-registration at a new size logs the
        growth event. A ``None`` params_dev keeps the previous reference
        (sizes are unchanged; .nbytes is aval metadata either way)."""
        rec = self._memory
        if rec is None:
            return
        from ..obs.memory import bfs_component_sizes
        from ..ops import visited_set as vs

        sizes = bfs_component_sizes(
            self.tm.state_width,
            self.tm.max_actions,
            len(self._tprops),
            chunk=self._chunk,
            queue_capacity=self._qcap,
            table_capacity=self._tcap,
            coverage=self._cov,
            sample_k=self._sample_k,
            canon=self._canon,
        )
        arrays = {
            "visited_table": table,
            "frontier_queue": queue,
            "record_fps": rec_fps,
            "packed_params": params_dev,
            "coverage_slab": params_dev,
            "sample_slab": params_dev,
        }
        if self._canon:
            arrays["canon_tail"] = params_dev
        rec.register_components(sizes, arrays=arrays)
        rec.set_geometry(
            rows=self._tcap,
            max_load=vs.MAX_LOAD,
            reserve_rows=_vcap(self.tm.max_actions, self._chunk),
        )

    def _small_workload_hint(self, n: int, kind: str) -> None:
        """One-line telemetry warning: below the crossover the host engine
        wins (the device engine's fixed dispatch/compile overheads dominate
        small state spaces — README "engine crossover")."""
        if getattr(self, "_mux_lane", False):
            return  # multiplexed lanes ARE the small-workload path
        if getattr(self, "_hinted_small", False):
            return  # once per run
        self._hinted_small = True
        self._metrics.set_gauge("small_workload_hint", n)
        _log.warning(
            "small workload: spawn_bfs() on the host is typically faster "
            "than spawn_tpu_bfs() here",
            states=n,
            kind=kind,
            crossover=SMALL_WORKLOAD_STATES,
        )

    def _grow_table(self, table):
        """Double capacity and rehash on device (no table round-trips)."""
        from ..ops import visited_set as vs

        new_cap = self._tcap * 2
        new_table, counts = vs.rehash_jit(table, vs.empty_table(new_cap))
        counts = np.asarray(counts)
        self._metrics.inc("rehash_rows", counts[vs.REHASH_ROWS])
        self._metrics.inc("rehash_stragglers", counts[vs.REHASH_STRAGGLERS])
        if counts[vs.REHASH_UNRESOLVED] != 0:
            raise RuntimeError("rehash failed; table pathologically full")
        return new_table, new_cap

    # -- checkpoint/resume --------------------------------------------------

    def _save_checkpoint(
        self, table, queue, head, count, rec_bits, rec_fp1, rec_fp2
    ) -> None:
        """Serialize the full engine state (table, ring, spill, counters) to
        one .npz via the crash-safe protocol in engines/common.py: tmp +
        fsync + generation rotation + rename, content digest in the meta.
        The reference has no equivalent — killed runs restart from scratch
        (SURVEY.md §5)."""
        from ..ops import visited_set as vs
        from .common import checkpoint_meta

        meta = checkpoint_meta(
            self.tm,
            self._tprops,
            ring_lanes=len(queue),
            head=head,
            count=count,
            rec_bits=rec_bits,
            state_count=self._state_count,
            unique=self._unique,
            max_depth=self._max_depth,
            tcap=self._tcap,
            qcap=self._qcap,
            chunk=self._chunk,
            max_probes=vs.MAX_PROBES,
            discovery_fps={k: str(v) for k, v in self._discovery_fps.items()},
            sampler=(
                self._sampler.export_state()
                if self._sampler is not None
                else None
            ),
        )
        arrays = {
            "rec_fp1": np.asarray(rec_fp1),
            "rec_fp2": np.asarray(rec_fp2),
        }
        # On-disk format keeps the four flat lanes (table0..3); the packed
        # key buffer is split host-side (free views over one download).
        for t, lane in enumerate(vs.unpack_lanes_np(table)):
            arrays[f"table{t}"] = lane
        for w, lane in enumerate(queue):
            arrays[f"queue{w}"] = np.asarray(lane)
        for i, blk in enumerate(self._spill.iter_blocks()):
            arrays[f"spill{i}"] = blk
        # Tiered save (ISSUE 20): a full base when the chain state says so
        # (first save, tcap changed, chain at max), else a delta holding
        # only the table rows inserted since the base.
        self._ckpt_delta = save_checkpoint_tiered(
            self._ckpt_path, meta, arrays,
            state=self._ckpt_delta, tcap=self._tcap,
            keep=self._ckpt_keep, metrics=self._metrics,
        )
        self._last_ckpt = time.monotonic()
        _dbg(f"checkpoint saved: {self._ckpt_path}")

    def _load_checkpoint(self, path: str, W: int):
        import jax.numpy as jnp

        from ..ops import visited_set as vs
        from .common import validate_checkpoint_meta

        # Digest-verified load with automatic fallback to the previous
        # generation when the newest file is truncated/corrupt, folding any
        # surviving delta chain onto the base (engines/common.py).
        data, meta = load_checkpoint_folded(path, metrics=self._metrics)
        validate_checkpoint_meta(
            meta,
            self.tm,
            self._tprops,
            exact={
                "qcap": self._qcap,
                "state_width": self.tm.state_width,
                # Ring layout changed in round 5 (hashes no longer carried);
                # checkpoints from the old layout must not load silently.
                "ring_lanes": W,
                # The probe cascade is part of the table's on-disk meaning:
                # a table written under a different probe schedule would
                # mis-resolve lookups.
                "max_probes": vs.MAX_PROBES,
            },
        )
        self._tcap = meta["tcap"]
        self._state_count = meta["state_count"]
        self._unique = meta["unique"]
        self._max_depth = meta["max_depth"]
        self._discovery_fps = {
            k: int(v) for k, v in meta["discovery_fps"].items()
        }
        if self._sampler is not None and meta.get("sampler"):
            # Restore the sampler's kept set + threshold: a resumed run's
            # sample must be identical to an uninterrupted one.
            self._sampler.restore_state(meta["sampler"])
        self._spill.reset(
            data[k] for k in sorted(
                (k for k in data if k.startswith("spill")),
                key=lambda s: int(s[5:]),
            )
        )
        # A reload invalidates the delta-chain baseline (the resumed run's
        # next save must be a fresh full base).
        self._ckpt_delta = None
        table = vs.pack_lanes(*(data[f"table{t}"] for t in range(4)))
        queue = tuple(jnp.asarray(data[f"queue{w}"]) for w in range(W))
        return (
            table,
            queue,
            meta["head"],
            meta["count"],
            meta["rec_bits"],
            jnp.asarray(data["rec_fp1"]),
            jnp.asarray(data["rec_fp2"]),
        )

    # -- accessors ----------------------------------------------------------

    def telemetry(self) -> Dict[str, Any]:
        m = self._metrics
        m.set_gauge("table_capacity", self._tcap)
        m.set_gauge("load_factor", round(self._unique / self._tcap, 4))
        m.set_gauge("chunk", self._chunk)
        return super().telemetry()

    def unique_state_count(self) -> int:
        return self._unique

    def discoveries(self) -> Dict[str, Path]:
        self.join()
        fps = dict(self._discovery_fps)
        return dict(zip(fps, self._reconstruct_all(list(fps.values()))))

    def _sample_resolver(self):
        # Device slabs drain fingerprint-only; sample rows are resolved
        # lazily at profile build by the same table-parent walk that
        # reconstructs counterexample paths.
        return self._path_sample_resolver(self._reconstruct)

    def _reconstruct(self, fp64: int) -> Path:
        return self._reconstruct_all([fp64])[0]

    def _reconstruct_all(self, fps: List[int]) -> List[Path]:
        """Walk table parent pointers on the device, then re-execute the
        model along each fingerprint chain (reference bfs.rs:380-409). One
        walk program serves every chain (`walk_parent_chains`); it is
        padded to the property count, so every check of a model, and every
        sampled fingerprint, reuses one compiled program."""
        chains = walk_parent_chains(
            self._table_dev, fps, max(1, len(self._tprops)), self._metrics
        )
        model = self._model
        if self._canon:
            # The table stores representative fingerprints; match raw
            # successors by their canonical fingerprint while walking.
            from ..tensor import CanonicalTensorAdapter

            model = CanonicalTensorAdapter(self.tm)
        paths = []
        for chain in chains:
            with self._metrics.phase("path_replay"):
                paths.append(Path.from_fingerprints(model, chain))
        return paths
