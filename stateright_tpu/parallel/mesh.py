"""Sharded batched BFS: the multi-chip engine.

Design (SURVEY.md §7 step 4, §5 "distributed communication backend"):

  - mesh axis "shards" over N devices,
  - visited table: fingerprint-ownership sharding — shard `h1 % N` owns a
    fingerprint; four [N, cap] uint32 lanes (structure-of-arrays, see
    ops/visited_set.py), sharded on dim 0,
  - frontier: per-shard ring lanes [N, qcap], holding only owned states,
  - per era (ONE shard_map'ed jitted program, a device-resident while
    loop whose predicate is a GLOBALLY UNIFORM gate — one stacked psum
    per step yields work-left / congestion / probe-error / finish-policy
    discovery bits, identical on every shard; same design as
    engines/tpu_bfs.py's era loop):
      each shard pops a chunk, evaluates properties, expands successors,
      buckets the candidates BY OWNER into fixed per-destination quotas,
      and exchanges them with `lax.all_to_all` — each candidate crosses
      the ICI exactly once, to its owner, instead of the naive
      all_gather's N-fold broadcast. The owner runs the claim-arbitrated
      insert (cross-shard duplicates resolve exactly like in-batch ones)
      and appends fresh states to its ring.
  - bucket overflow (more candidates for one destination than the quota)
    uses the same partial-commit protocol as the single-device engine:
    delivered candidates are inserted+enqueued (idempotent), the pops are
    NOT consumed, and a per-shard take_cap halves until everything fits.

The host syncs once per era: one [N, P_LEN] stats download, then spill /
growth / finish-policy decisions (discovery-finish already exits the era
on device). Cross-shard discovery paths reconstruct
on the host by walking parent pointers across the downloaded table shards
(owner = h1 % N per hop).
"""

from __future__ import annotations

from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import numpy as np

from ..checker import Checker, CheckerBuilder
from ..core import Expectation
from ..engines.common import HostEngineBase
from ..fingerprint import combine64, hash_words_np, split64
from ..path import Path
from ..tensor import TensorModel, TensorModelAdapter

# Packed per-shard scalar params (one uint32 row per shard). Mirrors the
# single-device layout (engines/tpu_bfs.py) plus an overflow counter.
P_HEAD = 0
P_COUNT = 1
P_UNIQUE = 2
P_REC = 3
P_DEPTH_LIMIT = 4
P_GROW_LIMIT = 5
P_HIGH_WATER = 6
P_MAX_STEPS = 7
P_GEN = 8
P_MAXD = 9
P_STEPS = 10
P_ERR = 11
P_TAKE_CAP = 12  # persisted across eras (self-tuned on bucket overflow)
P_FIN_ANY = 13  # era exits when (global rec & fin_any) != 0
P_FIN_ALL = 14  # era exits when fin_all_en and (rec & fin_all) == fin_all
P_FIN_ALL_EN = 15
P_BUDGET_CAP = 16  # upper clamp for the device-adaptive step budget;
# 0 = adaptivity OFF (P_MAX_STEPS passes through unchanged)
P_LEN = 17

#: Cross-shard frontier imbalance (max/mean occupancy) above which the
#: engine logs a skew warning once per run. Hash-based ownership keeps
#: real models near 1.0; several-fold skew means one device does most of
#: the work while the rest idle in the lockstep collective.
SHARD_IMBALANCE_WARN = 4.0

#: Speculative chain depth: blocks kept chained off the on-device state
#: while their readbacks are in flight (engines/tpu_bfs.py CHAIN_DEPTH).
CHAIN_DEPTH = 2

_LOOP_CACHE: Dict[Tuple, Tuple[TensorModel, Any]] = {}


class BlockProgram(NamedTuple):
    """The compiled sharded era block under its two donation policies.

    ``serial``: the host consumed every readback before re-dispatching,
    so the table/queue lanes AND the freshly-uploaded params rows are
    donatable. ``chain``: a speculative chained dispatch feeds the
    previous block's params/rec_fp OUTPUTS straight back in while the
    host still needs to read them (the readback and the discovery
    fp/depth arrays), so only the table/queue lanes — which the host
    never touches mid-chain — are donated. Same traced function, so one
    lowering serves both (and on CPU, where donation is a no-op, they
    are literally the same executable)."""

    serial: Any
    chain: Any


def shard_params_len(A: int, P: int, cov: bool, sample_k: int) -> int:
    """Length of one shard's packed uint32 params row: scalars +
    optional coverage tail + optional sampling tail ([T1,T2,occ,0] and
    four drained lanes). Mirrors `engines.tpu_bfs.params_len` minus the
    rec_fp tail (the sharded block passes rec_fp as separate args)."""
    from ..obs.coverage import DEPTH_CAP

    n = P_LEN + ((A + P + 1 + DEPTH_CAP) if cov else 0)
    if sample_k:
        from ..obs.sample import slab_entries

        n += 4 + 4 * slab_entries(sample_k)
    return n


def block_abstract_args(tm: TensorModel, props, qcap: int, tcap: int,
                        n_shards: int, cov: bool, sample_k: int):
    """`jax.ShapeDtypeStruct` pytree matching `_build_block`'s jitted
    signature `(table, queue, rec_fp1, rec_fp2, params)` — global shapes
    with the leading shard axis. Used by the STR6xx program lint to
    lower the sharded era block without touching device memory."""
    import jax
    import jax.numpy as jnp

    S, A, P = tm.state_width, tm.max_actions, len(props)
    u32 = jnp.uint32
    sds = jax.ShapeDtypeStruct
    N = n_shards
    table = (
        sds((N, 2 * tcap), u32),
        sds((N, tcap), u32),
        sds((N, tcap), u32),
    )
    queue = tuple(sds((N, qcap), u32) for _ in range(S + 2))
    plen = shard_params_len(A, P, cov, sample_k)
    return (
        table,
        queue,
        sds((N, P), u32),
        sds((N, P), u32),
        sds((N, plen), u32),
    )


def _build_block(tm: TensorModel, props, chunk: int, qcap: int, n_shards: int,
                 quota: int, mesh, axis: str, cov: bool = True,
                 sample_k: int = 0):
    key = (
        id(tm), chunk, qcap, n_shards, quota, len(props), cov, sample_k,
        tuple(id(d) for d in mesh.devices.flat),
    )
    cached = _LOOP_CACHE.get(key)
    if cached is not None and cached[0] is tm:
        return cached[1]
    while len(_LOOP_CACHE) >= 16:  # bound executable/model pinning
        _LOOP_CACHE.pop(next(iter(_LOOP_CACHE)))

    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec

    from ..compat import donate_argnums_pinned
    from ..engines.tpu_bfs import _vcap
    from ..fingerprint import hash_lanes_jnp
    from ..obs.coverage import DEPTH_CAP
    from ..obs.spans import stage_scope
    from ..ops import frontier as fr
    from ..ops import visited_set as vs
    from ..ops.expand import build_expand_lean

    S = tm.state_width
    A = tm.max_actions
    NP_ = len(props)
    expand_lean = build_expand_lean(tm, props, chunk)
    qmask = qcap - 1
    X = S + 4  # exchanged lanes: state | p1 | p2 | ebits | depth — the
    # candidate's own fingerprint is NOT exchanged; the owner recomputes it
    # elementwise from the state lanes (elementwise work is free here,
    # ICI lanes are not: this cuts exchange traffic by 2 lanes)
    vcap = _vcap(A, chunk)
    # Pre-exchange dedup scratch, at the COMPACTED width (round 5): in the
    # sharded engine the dedup pass still earns its cost — every retained
    # duplicate would cross the ICI to its owner before losing the claim
    # there. Approximate as ever; the owner's insert arbitrates exactly.
    dedup_cap = 1 << max(1, (2 * vcap - 1).bit_length())
    # Space-sampling slab (obs/sample.py): each SHARD keeps its own
    # fixed slab of candidate fingerprints below the host's bottom-k
    # threshold, captured at the owner-side insert (is_new is exactly-once
    # globally, so slab entries are distinct fps and the host's exact h1
    # tie cut applies). Captures happen at the exchange receive width R,
    # never truncated — slab capacity s_high + R plus the psum'd
    # occupancy gate guarantee every below-threshold insert is captured,
    # so per-(shard, era) drains of the sk2 smallest merge into the exact
    # global bottom-k by trivial union (PSUM-FREE: the tails ride the
    # per-shard params rows un-reduced).
    R = n_shards * quota
    if sample_k:
        from ..obs.sample import slab_entries, slab_high_water

        sk2 = slab_entries(sample_k)
        s_high = slab_high_water(sample_k)
        scap = s_high + R  # next step's captures (<= R) always fit
    s_base = P_LEN + ((A + NP_ + 1 + DEPTH_CAP) if cov else 0)

    def per_device(table, queue, rec_fp1, rec_fp2, params):
        u = jnp.uint32
        table = tuple(t[0] for t in table)
        queue = tuple(q[0] for q in queue)
        rec_fp1 = rec_fp1[0]
        rec_fp2 = rec_fp2[0]
        params = params[0]

        me = lax.axis_index(axis).astype(jnp.uint32)
        high_water = params[P_HIGH_WATER]
        grow_limit = params[P_GROW_LIMIT]
        depth_limit = params[P_DEPTH_LIMIT]
        max_steps0 = params[P_MAX_STEPS]
        rec_bits0 = params[P_REC]
        fin_any = params[P_FIN_ANY]
        fin_all = params[P_FIN_ALL]
        fin_all_en = params[P_FIN_ALL_EN]
        budget_cap = params[P_BUDGET_CAP]
        if sample_k:
            # Host bottom-k threshold (exclusive; uint32 halves). Stale
            # (looser) thresholds only over-capture — always sound.
            st1 = params[s_base]
            st2 = params[s_base + 1]

        zero_lane = jnp.zeros(chunk, dtype=u) + (params[0] & u(0))
        false_lane = zero_lane != 0
        # Scalars seeded from varying data so carry types stay consistent
        # under shard_map (constants would be unvarying on the mesh axis).
        vzero = params[0] & u(0)

        sampc0 = (
            (
                jnp.zeros(scap + 1, dtype=u) + vzero,  # fp1 (+ trash slot)
                jnp.zeros(scap + 1, dtype=u) + vzero,  # fp2
                jnp.zeros(scap + 1, dtype=u) + vzero,  # depth
                vzero,  # occupied
            )
            if sample_k
            else ()
        )
        covc0 = (
            (
                jnp.zeros(A, dtype=u) + vzero,  # per-action valid counts
                tuple(vzero for _ in range(NP_)),  # per-property hits
                vzero,  # consumed rows
                jnp.zeros(DEPTH_CAP, dtype=u) + vzero,  # depth histogram
            )
            if cov
            else ()
        )

        # The era: the lockstep step loop plus its once-per-era epilogue.
        head0 = params[P_HEAD]
        count0 = params[P_COUNT]
        unique0 = params[P_UNIQUE]
        err0 = params[P_ERR]
        take_cap0 = params[P_TAKE_CAP]

        def global_gates(count, unique, err_cnt, hseen, rec_acc0, its,
                         socc):
            """One stacked psum produces every exit condition,
            IDENTICAL on all shards (the while predicate must be
            uniform): work left, congestion (a shard cannot refuse
            all_to_all deliveries, so no shard may pop while ANY
            shard's ring or table is within one step's receive of its
            limit), probe errors, and the finish policy's GLOBAL
            discovery bits."""
            local = [
                (count > u(0)).astype(u),
                ((count > high_water) | (unique > grow_limit)).astype(u),
                (err_cnt > u(0)).astype(u),
            ] + [
                jnp.minimum(hseen[pi].sum(dtype=u), u(1))
                for pi in range(NP_)
            ]
            if sample_k:
                # Sampling-slab occupancy: when ANY shard's slab passes
                # its high-water mark the era ends so the host can
                # drain it (appended LAST so the established g[]
                # indices hold).
                local.append((socc > u(s_high)).astype(u))
            g = lax.psum(jnp.stack(local), axis)
            rec_acc = rec_acc0
            for pi in range(NP_):
                rec_acc = rec_acc | (
                    jnp.minimum(g[3 + pi], u(1)) << u(pi)
                )
            fin_hit = ((rec_acc & fin_any) != u(0)) | (
                (fin_all_en != u(0)) & ((rec_acc & fin_all) == fin_all)
            )
            g_cont = (
                (g[0] > u(0))
                & (g[1] == u(0))
                & (g[2] == u(0))
                & ~fin_hit
                & (its < max_steps0)
            )
            if sample_k:
                g_cont = g_cont & (g[3 + NP_] == u(0))
            return g_cont.astype(u)

        def cond(carry):
            return carry[-1] != u(0)  # carried uniform gate

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
                hseen,
                facc1,
                facc2,
                faccd,
                covc,
                sampc,
                its,
                _g_cont,
            ) = carry
            pred = count > 0
            take = jnp.where(
                pred, jnp.minimum(jnp.minimum(count, u(chunk)), take_cap), u(0)
            )
            active = jnp.arange(chunk, dtype=u) < take
            with stage_scope("ring"):
                popped, _ = fr.ring_gather(queue, head, chunk)
            rows = popped[:S]
            ebits = popped[S]
            depth = popped[S + 1]
            # Recomputed on pop, elementwise (the ring no longer carries
            # fingerprints — same round-5 redesign as engines/tpu_bfs.py).
            with stage_scope("hash"):
                row_h1, row_h2 = hash_lanes_jnp(rows)

            with stage_scope("expand"):
                ex = expand_lean(rows, ebits, depth, active, depth_limit)

            # COMPACT EARLY: validity compaction is the only padded-width
            # random-access op; hashing, dedup, bucketing, and the exchange
            # all run at the compacted [vcap] width.
            with stage_scope("compact"):
                vids, vvalid, n_val = vs._compact_ids(ex.valid, vcap)
                cl = tuple(ex.flat[s][vids] for s in range(S))
            with stage_scope("hash"):
                ch1, ch2 = hash_lanes_jnp(cl)
            with stage_scope("compact"):
                src = vids % u(chunk)
                cp1 = jnp.where(vvalid, row_h1[src], u(0))
                cp2 = jnp.where(vvalid, row_h2[src], u(0))
                cebits = ex.ebits[src]
                cdepth = depth[src] + u(1)

            with stage_scope("claim"):
                reps = fr.claim_dedup(ch1, ch2, vvalid, dedup_cap)

            with stage_scope("exchange"):
                owner = ch1 % u(n_shards)
                # Bucket by owner with ONE rank computation (no per-
                # destination Python loop — program size stays flat in
                # n_shards): a [vcap, N] one-hot cumsum yields each
                # candidate's rank within its owner bucket and the
                # per-owner counts in one pass.
                onehot = (
                    owner[:, None] == jnp.arange(n_shards, dtype=u)[None, :]
                ) & reps[:, None]
                csum = jnp.cumsum(onehot.astype(u), axis=0)  # [vcap, N]
                rank = (csum * onehot.astype(u)).sum(axis=1) - u(1)
                counts_per_owner = csum[-1]  # [N]
                n_ovf_total = (
                    counts_per_owner
                    - jnp.minimum(counts_per_owner, u(quota))
                ).sum(dtype=u)
                my = jnp.arange(vcap, dtype=u)
                dest = jnp.where(
                    reps & (rank < u(quota)),
                    owner * u(quota) + rank,
                    u(n_shards * quota) + my,  # distinct drop targets
                )
                send_cand = cl + (cp1, cp2, cebits, cdepth)
                send = [
                    jnp.zeros(n_shards * quota, dtype=u)
                    .at[dest]
                    .set(c, mode="drop", unique_indices=True)
                    for c in send_cand
                ]

                # The ICI hop: one all_to_all per lane; each shard
                # receives the buckets addressed to it from every shard.
                recv = [
                    lax.all_to_all(
                        x, axis, split_axis=0, concat_axis=0, tiled=True
                    )
                    for x in send
                ]
            rstates = tuple(recv[t] for t in range(S))
            rp1 = recv[S]
            rp2 = recv[S + 1]
            # Parent fingerprints are nonzero as a pair for every real
            # candidate; an all-zero parent pair means "empty slot".
            r_valid = (rp1 | rp2) != u(0)
            with stage_scope("hash"):
                rh1, rh2 = hash_lanes_jnp(rstates)  # owner-side recompute

            with stage_scope("probe"):
                table, is_new, unresolved, _ovf_ins = vs.insert(
                    table, rh1, rh2, rp1, rp2, r_valid
                )
                unres = unresolved.sum(dtype=u)
                new_count = is_new.sum(dtype=u)

            if sample_k:
                with stage_scope("sample"):
                    # Capture below-threshold inserts into this shard's slab.
                    # `is_new` is exactly-once (retried partial-commit steps
                    # re-deliver already-inserted rows, which are not new), so
                    # no fingerprint is ever captured twice. Writes happen at
                    # the full receive width R — never truncated; the trash
                    # slot at index scap absorbs masked lanes.
                    below = is_new & (
                        (rh1 < st1) | ((rh1 == st1) & (rh2 < st2))
                    )

                    def _capture(sc):
                        sfp1, sfp2, sdep, socc = sc
                        cids, cvalid, n_c = vs._compact_ids(below, R)
                        pos = socc + jnp.arange(R, dtype=u)
                        ok_w = cvalid & (pos < u(scap))
                        widx = jnp.where(ok_w, pos, u(scap))
                        return (
                            sfp1.at[widx].set(rh1[cids]),
                            sfp2.at[widx].set(rh2[cids]),
                            sdep.at[widx].set(recv[S + 3][cids]),
                            socc + n_c,
                        )

                    # Tight-threshold steps capture nothing almost always;
                    # the cond skips the compaction and slab scatters then.
                    # Per-shard predicate — shards diverge, which is fine:
                    # nothing inside the branch communicates.
                    sampc = lax.cond(
                        below.any(), _capture, lambda sc: sc, sampc
                    )

            with stage_scope("ring"):
                qrows = rstates + (recv[S + 2], recv[S + 3])
                tail = (head + count) & u(qmask)
                queue = fr.ring_scatter(queue, tail, qrows, is_new)

            # Partial-commit overflow protocol (see module docstring).
            # Probe-tail overflow (unresolved candidates at the OWNER) is
            # retryable the same way, but the veto must be GLOBAL: the
            # unresolved candidates' parents were popped on OTHER shards,
            # so every shard must decline to consume and shrink its take
            # (a sender cannot know which owner overflowed). Fatal only
            # when no shard can shrink further — genuinely exhausted
            # probe chains, i.e. state loss.
            g_us = lax.psum(
                jnp.stack([unres, (take > u(1)).astype(u)]), axis
            )
            g_unres = g_us[0]
            g_can_shrink = g_us[1]
            err_cnt = err_cnt + jnp.where(
                g_can_shrink == u(0), g_unres, u(0)
            )
            ovf = (n_ovf_total > u(0)) | (g_unres > u(0))
            consumed = jnp.where(ovf, u(0), take)
            head = (head + consumed) & u(qmask)
            count = count - consumed + new_count
            unique = unique + new_count
            gen = gen + jnp.where(ovf, u(0), ex.generated)
            steps = steps + (pred & ~ovf).astype(u)
            take_cap = jnp.where(
                ovf,
                jnp.maximum(take >> u(1), u(1)),
                jnp.minimum(take_cap + u(max(1, chunk // 16)), u(chunk)),
            )

            if cov:
                with stage_scope("coverage"):
                    # Shard-local coverage (obs/coverage.py): action counts at
                    # the SENDER (where expansion attributes candidates to
                    # their action slot; ovf-gated like `gen`), the consumed
                    # row count, and the depth histogram at the OWNER (where
                    # inserts happen; unconditional like `unique`). Shards
                    # psum these once in the block epilogue.
                    act, covp, expanded, dhist = covc
                    pa = ex.valid.astype(u).reshape(A, chunk).sum(axis=1)
                    act = act + jnp.where(ovf, u(0), pa)
                    expanded = expanded + consumed
                    dhist = dhist.at[
                        jnp.minimum(recv[S + 3], u(DEPTH_CAP - 1))
                    ].add(is_new.astype(u))
                    covc = (act, covp, expanded, dhist)

            if NP_:
                hseen_n, facc1_n, facc2_n, faccd_n, covp_n = [], [], [], [], []
                for pi in range(NP_):
                    hits = ex.prop_hits[pi]
                    first = hits & ~hseen[pi]
                    facc1_n.append(jnp.where(first, row_h1, facc1[pi]))
                    facc2_n.append(jnp.where(first, row_h2, facc2[pi]))
                    faccd_n.append(jnp.where(first, depth, faccd[pi]))
                    hseen_n.append(hseen[pi] | hits)
                    if cov:
                        covp_n.append(
                            covc[1][pi]
                            + jnp.where(ovf, u(0), hits.sum(dtype=u))
                        )
                hseen = tuple(hseen_n)
                facc1 = tuple(facc1_n)
                facc2 = tuple(facc2_n)
                faccd = tuple(faccd_n)
                if cov:
                    covc = (covc[0], tuple(covp_n), covc[2], covc[3])

            its = its + u(1)
            g_cont = global_gates(
                count, unique, err_cnt, hseen, rec_bits0, its,
                sampc[3] if sample_k else its,
            )
            return (
                table, queue, head, count, unique, gen, steps, err_cnt,
                take_cap, hseen, facc1, facc2, faccd, covc, sampc, its,
                g_cont,
            )

        # err seeds from err0 (like engines/tpu_bfs.py): a chained
        # (speculative) dispatch off a probe-error era re-derives the
        # error exit and becomes an identity no-op instead of running
        # on a table with dropped states.
        g0 = global_gates(
            count0,
            unique0,
            err0,
            tuple(false_lane for _ in range(NP_)),
            rec_bits0,
            vzero,
            sampc0[3] if sample_k else vzero,
        )
        init = (
            table,
            queue,
            head0,
            count0,
            unique0,
            vzero,
            vzero,
            err0,  # carried: closes the gate on a chained dispatch
            jnp.minimum(jnp.maximum(take_cap0, u(1)), u(chunk)),
            tuple(false_lane for _ in range(NP_)),
            tuple(zero_lane for _ in range(NP_)),
            tuple(zero_lane for _ in range(NP_)),
            tuple(zero_lane for _ in range(NP_)),
            covc0,
            sampc0,
            vzero,  # iteration counter (uniform: shards run lockstep)
            g0,
        )
        (
            table, queue, head, count, unique, gen, steps, err_cnt,
            take_cap_out, hseen, facc1, facc2, faccd, covc_out,
            sampc_out, its_out, _gc,
        ) = lax.while_loop(cond, body, init)

        # Era epilogue (once per era): BLOCK-LOCAL discovery reports.
        # The host keeps the min-depth discovery across blocks and
        # shards — shards skew, so a shallower hit can surface in a
        # LATER block than a deeper one (the reference's multithreaded
        # BFS has the same benign race, bfs.rs:243-244; tracking min
        # depth host-side makes us strictly better, not just equal).
        with stage_scope("epilogue"):
            if NP_:
                ef1, ef2, edd = [], [], []
                for pi in range(NP_):
                    found = jnp.any(hseen[pi])
                    sel = jnp.argmin(
                        jnp.where(hseen[pi], faccd[pi], u(0xFFFFFFFF))
                    )
                    ef1.append(jnp.where(found, facc1[pi][sel], u(0)))
                    ef2.append(jnp.where(found, facc2[pi][sel], u(0)))
                    edd.append(
                        jnp.where(found, faccd[pi][sel], u(0xFFFFFFFF))
                    )
                rec_fp1 = jnp.stack(ef1)
                rec_fp2 = jnp.stack(ef2)
                disc_depth = jnp.stack(edd)
            else:
                rec_fp1 = jnp.zeros(0, dtype=u) + vzero
                rec_fp2 = jnp.zeros(0, dtype=u) + vzero
                disc_depth = jnp.zeros(0, dtype=u) + vzero
            maxd = jnp.where(
                steps > 0, queue[S + 1][(head - u(1)) & u(qmask)], u(0)
            )
            # Adaptive era budget (device-side emission, mirroring
            # engines/tpu_bfs.py): every input to the formula is globally
            # uniform (one epilogue psum for pressure/err/work/global rec
            # bits; `its_out` runs lockstep), so every shard emits the SAME
            # next budget and a chained dispatch stays uniform too. The
            # cost is one collective per ERA, not per step.
            glocal = [
                ((count > high_water) | (unique > grow_limit)).astype(u),
                (err_cnt > u(0)).astype(u),
                (count > u(0)).astype(u),
            ] + [
                jnp.minimum(hseen[pi].sum(dtype=u), u(1))
                for pi in range(NP_)
            ]
            gb = lax.psum(jnp.stack(glocal), axis)
            g_pressure = gb[0] > u(0)
            g_err = gb[1] > u(0)
            g_work = gb[2] > u(0)
            rec_all = rec_bits0
            for pi in range(NP_):
                rec_all = rec_all | (jnp.minimum(gb[3 + pi], u(1)) << u(pi))
            fin_hit_final = ((rec_all & fin_any) != u(0)) | (
                (fin_all_en != u(0)) & ((rec_all & fin_all) == fin_all)
            )
            budget_only = (
                (its_out >= max_steps0)
                & g_work
                & ~g_pressure
                & ~g_err
                & ~fin_hit_final
            )
            grown = jnp.minimum(
                jnp.maximum(max_steps0, u(1)) * u(2), budget_cap
            )
            shrunk = jnp.maximum(
                jnp.minimum(max_steps0, budget_cap) >> u(1),
                u(64),  # BUDGET_MIN (engines/tpu_bfs.py)
            )
            next_budget = jnp.where(
                budget_cap == u(0),
                max_steps0,
                jnp.where(
                    g_pressure, shrunk,
                    jnp.where(budget_only, grown, max_steps0),
                ),
            )

        # P_REC emits the GLOBAL accumulated bits (rec_all), not the
        # shard-local bits: the host ORs the rows anyway, and a chained
        # (speculative) dispatch feeds the row straight back in —
        # shard-local bits would make the finish gate non-uniform across
        # shards and deadlock the lockstep collectives. Per-shard
        # discovery attribution rides disc_depth, not this word.
        with stage_scope("epilogue"):
            parts = [
                jnp.stack(
                    [
                        head, count, unique, rec_all, depth_limit,
                        grow_limit, high_water, next_budget, gen, maxd, steps,
                        (err_cnt > 0).astype(u), take_cap_out,
                        fin_any, fin_all, fin_all_en, budget_cap,
                    ]
                )
            ]
            if cov:
                # Coverage tail, psum'd across the mesh so every shard's row
                # carries the GLOBAL histograms (the host reads row 0):
                # act[A] | prop_hits[NP_] | expanded[1] | depth[DEPTH_CAP].
                act, covp, expanded, dhist = covc_out
                covp_vec = (
                    jnp.stack(list(covp)) if NP_ else jnp.zeros(0, dtype=u) + vzero
                )
                parts.append(
                    lax.psum(
                        jnp.concatenate([act, covp_vec, expanded[None], dhist]),
                        axis,
                    )
                )
            if sample_k:
                # Per-shard sample tail, deliberately UN-psum'd (fingerprints
                # don't reduce): [T1, T2, occupied, 0] + the sk2 smallest slab
                # entries by h1 (fp1 | fp2 | depth | ok). One top_k in the
                # once-per-era epilogue; the ok lane disambiguates padding
                # from a real fp1 of 0xFFFFFFFF; the host applies the exact
                # 64-bit tie cut (obs/sample.py) and unions the shards.
                sfp1, sfp2, sdep, socc = sampc_out
                used = jnp.arange(scap, dtype=u) < socc
                skey = jnp.where(used, ~sfp1[:scap], u(0))
                _topv, topi = lax.top_k(skey, sk2)
                parts += [
                    jnp.stack([st1, st2, socc, vzero]),
                    sfp1[:scap][topi],
                    sfp2[:scap][topi],
                    sdep[:scap][topi],
                    used[topi].astype(u),
                ]
            params_out = jnp.concatenate(parts)

        def exp(x):
            return jnp.expand_dims(x, 0)

        return (
            tuple(exp(t) for t in table),
            tuple(exp(q) for q in queue),
            exp(rec_fp1),
            exp(rec_fp2),
            exp(params_out),
            exp(disc_depth),
        )

    spec = PartitionSpec(axis)
    mapped = jax.shard_map(
        per_device,
        mesh=mesh,
        in_specs=(spec,) * 5,
        out_specs=(spec,) * 6,
    )
    # Two donation policies over ONE traced function (see BlockProgram):
    # the serial driver's params rows are a fresh host upload each
    # dispatch, so they are donatable on top of the table/queue lanes; a
    # chained dispatch feeds the previous block's params output back in
    # while its readback (and the discovery fp/depth reads) are still
    # pending, so the chain variant pins it. The rec_fp arrays are never
    # donated — the host reads the OUTPUT handles in consume(), and under
    # chaining those same handles are the next dispatch's inputs.
    d_serial = donate_argnums_pinned((0, 1, 4))
    d_chain = donate_argnums_pinned((0, 1, 4), pinned=(4,))
    serial = jax.jit(mapped, donate_argnums=d_serial)
    chain = (
        serial
        if d_chain == d_serial
        else jax.jit(mapped, donate_argnums=d_chain)
    )
    program = BlockProgram(serial, chain)
    _LOOP_CACHE[key] = (tm, program)
    return program


_GROW_CACHE: Dict[Tuple, Any] = {}


def _build_grow(old_cap: int, new_cap: int, mesh, axis: str):
    """Compile a shard_map'd per-shard rehash old_cap -> new_cap.

    Runs entirely on device: each shard re-inserts its occupied rows into
    a fresh table created in-program. Returns (new_table, counts[N, 3]),
    each shard's `vs.rehash` count vector.
    """
    key = (old_cap, new_cap, tuple(id(d) for d in mesh.devices.flat))
    cached = _GROW_CACHE.get(key)
    if cached is not None:
        return cached
    while len(_GROW_CACHE) >= 8:
        _GROW_CACHE.pop(next(iter(_GROW_CACHE)))

    import jax
    from jax.sharding import PartitionSpec

    from ..compat import donate_argnums_safe
    from ..ops import visited_set as vs

    def per_device(table):
        import jax.numpy as jnp

        shard = tuple(t[0] for t in table)
        # Fresh tables seeded from varying input so their shard_map type is
        # varying on the mesh axis (constant zeros would be unvarying and
        # fail the rehash loop's carry typing).
        vz = shard[0][0] & jnp.uint32(0)
        empty = tuple(l + vz for l in vs.empty_table(new_cap))
        new_table, counts = vs.rehash(shard, empty)
        return (
            tuple(jnp.expand_dims(l, 0) for l in new_table),
            jnp.expand_dims(counts, 0),
        )

    spec = PartitionSpec(axis)
    grow = jax.jit(
        jax.shard_map(
            per_device,
            mesh=mesh,
            in_specs=((spec,) * 3,),
            out_specs=((spec,) * 3, spec),
        ),
        donate_argnums=donate_argnums_safe(0),
    )

    _GROW_CACHE[key] = grow
    return grow


class ShardedBfsChecker(HostEngineBase):
    """Multi-device batched BFS behind the standard Checker API.

    Spawn with `CheckerBuilder.spawn_sharded_bfs()`. Tables and frontiers
    are fingerprint-ownership-sharded across the device mesh; see module
    docstring.
    """

    _supports_threads = True  # parallelism = the mesh, not worker threads

    def __init__(
        self,
        builder: CheckerBuilder,
        *,
        devices: Optional[List] = None,
        chunk_size: int = 1024,
        queue_capacity_per_shard: int = 1 << 16,
        table_capacity_per_shard: int = 1 << 18,
        sync_steps: int = 4096,
        checkpoint_path: Optional[str] = None,
        checkpoint_every: Optional[float] = None,
        resume_from: Optional[str] = None,
        keep_checkpoints: int = 2,
    ):
        import jax
        from jax.sharding import Mesh

        from ..obs.metrics import MetricsRegistry

        # The constructor's own time, in the caller's thread (the base
        # class adopts the registry).
        metrics = MetricsRegistry()
        with metrics.phase("engine_init"):
            model = builder.model
            if isinstance(model, TensorModel):
                model = TensorModelAdapter(model)
            if not isinstance(model, TensorModelAdapter):
                raise TypeError(
                    "spawn_sharded_bfs requires a TensorModel (or its adapter)"
                )
            super().__init__(builder, model=model, metrics=metrics)
            if self._visitor is not None:
                raise ValueError("the sharded engine does not support visitors")

            self.tm: TensorModel = model.tm
            self._tprops = self.tm.tensor_properties()
            if len(self._tprops) > 32:
                raise ValueError("at most 32 tensor properties supported")
            devices = devices if devices is not None else jax.devices()
            self.n_shards = len(devices)
            self.mesh = Mesh(np.array(devices), ("shards",))
            if queue_capacity_per_shard & (queue_capacity_per_shard - 1):
                raise ValueError("queue capacity must be a power of two")
            A = max(1, self.tm.max_actions)
            self._chunk = min(chunk_size, queue_capacity_per_shard // (2 * A))
            if self._chunk == 0:
                raise ValueError("queue capacity too small for this model's fanout")
            self._qcap = queue_capacity_per_shard
            self._tcap = table_capacity_per_shard
            self._max_sync_steps = sync_steps
            # Per-destination exchange quota: the receive width is
            # n_shards * quota, so this also caps per-step inserts per shard.
            self._quota = max(64, (self._chunk * A) // (4 * self.n_shards))
            if self._qcap < 4 * self.n_shards * self._quota:
                raise ValueError(
                    "queue_capacity_per_shard must be at least 4 * n_shards * "
                    f"quota (= {4 * self.n_shards * self._quota}); got "
                    f"{self._qcap}. Raise the queue capacity or lower chunk_size."
                )
            self._cov = self._coverage.enabled
            self._sample_k = self._sampler.k if self._sampler is not None else 0
            # Speculative era pipelining (CheckerBuilder.pipeline(), default
            # on) — see _run_loop and engines/tpu_bfs.py for the soundness
            # argument.
            self._pipeline = bool(getattr(builder, "pipeline_", True))
            self._chain_max = 0
            self._block = _build_block(
                self.tm, self._tprops, self._chunk, self._qcap, self.n_shards,
                self._quota, self.mesh, "shards", self._cov,
                sample_k=self._sample_k,
            )

            self._unique = 0
            self._discovery_fps: Dict[str, int] = {}
            # Tiered spill staging (ops/tiering.py): one budgeted host-RAM
            # LIFO per shard with an npz disk tier below; the host budget is
            # split evenly across shards. Unbudgeted (env unset) each store
            # is a plain in-RAM stack, byte-for-byte the old list behavior.
            from ..ops.tiering import TieredSpillStore, spill_host_budget_bytes

            _budget = spill_host_budget_bytes()
            if _budget is not None:
                _budget = max(1, _budget // self.n_shards)
            self._spill: List[TieredSpillStore] = [
                TieredSpillStore(
                    host_budget_bytes=_budget,
                    on_tier=self._on_spill_tier,
                    label=f"spill-s{s}",
                )
                for s in range(self.n_shards)
            ]
            # Delta-checkpoint chain state (engines/common.py
            # save_checkpoint_tiered): None = next save is a full base.
            self._ckpt_delta = None
            # Era of the last proactive reshard (one doubling per forecast).
            self._reshard_last_era = -1
            # Sharded checkpoint/resume: per-shard tables, rings, spill lists,
            # take_caps and counters serialize to one .npz at block boundaries
            # (all arrays are host-visible there). Writes are crash-atomic with
            # rolling generations and a content digest (engines/common.py);
            # checkpoint_every is wall-clock seconds, polled at era boundaries.
            from ..engines.common import (
                register_signal_checkpoint_flush,
                validate_checkpoint_cadence,
            )

            validate_checkpoint_cadence(
                checkpoint_every, checkpoint_path, keep_checkpoints
            )
            self._ckpt_path = checkpoint_path
            self._ckpt_every = checkpoint_every
            self._ckpt_keep = keep_checkpoints
            self._resume_from = resume_from
            import time as _time

            self._last_ckpt = _time.monotonic()
            # Chaos-injection hook (tests/test_durability_chaos.py): fake a
            # probe-budget exhaustion at this era count to exercise the
            # degraded-regrow recovery.
            self._chaos_probe_error_era: Optional[int] = None
            if checkpoint_path is not None:
                register_signal_checkpoint_flush(self)
            self._init_ebits = 0
            e = 0
            for p in self._tprops:
                if p.expectation == Expectation.EVENTUALLY:
                    self._init_ebits |= 1 << e
                    e += 1
        self._start()

    # -- engine body --------------------------------------------------------

    def _run(self) -> None:
        with self._metrics.phase("run_setup"):
            start = self._setup_run()
        if start is not None:
            self._run_loop(*start)

    def _setup_run(self):
        """Host seeding (or a checkpoint load) up to the first dispatch:
        the `_run_loop` arguments, or None when there is nothing to run."""
        tm = self.tm
        S = tm.state_width
        A = tm.max_actions
        C = self._chunk
        N = self.n_shards
        NP_ = len(self._tprops)
        W = S + 2  # ring lanes: state | ebits | depth

        if self._resume_from is not None:
            (
                table,
                queue,
                heads,
                counts,
                rec_bits,
                rec_fp1,
                rec_fp2,
                take_caps,
                disc_depth_best,
                per_shard_unique,
            ) = self._load_checkpoint(self._resume_from, W)
            depth_limit = (
                self._target_max_depth
                if self._target_max_depth is not None
                else 0xFFFFFFFF
            )
            return (
                table, queue, heads, counts, rec_bits, rec_fp1, rec_fp2,
                take_caps, disc_depth_best, per_shard_unique, depth_limit,
                self._qcap - N * self._quota, W,
            )

        inits = np.asarray(tm.init_states_array(), dtype=np.uint32)
        init_lanes = tuple(inits[:, i] for i in range(S))
        inb = np.asarray(tm.within_boundary_lanes(np, init_lanes), dtype=bool)
        inits = inits[inb]
        self._state_count = len(inits)
        if len(inits) == 0:
            return None
        h1, h2 = hash_words_np(inits)

        # Route init states to their owners; seed tables host-side with the
        # SAME double-hash probe sequence the device insert uses.
        queue_np = np.zeros((N, self._qcap, W), dtype=np.uint32)
        counts = np.zeros(N, dtype=np.int64)
        table_np = np.zeros((N, self._tcap, 4), dtype=np.uint32)
        seen = set()
        owners = h1.astype(np.int64) % N
        per_owner = np.bincount(owners, minlength=N)
        if per_owner.max() > self._qcap:
            raise ValueError(
                f"shard {int(per_owner.argmax())} would receive "
                f"{int(per_owner.max())} initial states, exceeding "
                f"queue_capacity_per_shard={self._qcap}; raise the per-shard "
                "queue capacity (mirrors the single-device n_init > qcap check)"
            )
        for i in range(len(inits)):
            o = int(owners[i])
            fp = combine64(h1[i], h2[i])
            row = queue_np[o, counts[o]]
            row[:S] = inits[i]
            row[S] = self._init_ebits
            row[S + 1] = 1
            counts[o] += 1
            if fp not in seen:
                seen.add(fp)
                self._host_insert(table_np[o], int(h1[i]), int(h2[i]))
                self._unique += 1
        self._coverage.record_depth(1, len(seen))
        if self._sampler is not None:
            # Init states never pass the device slab (they are host-seeded,
            # not exchanged) — offer them here; the sampler dedups.
            fps = (h1.astype(np.uint64) << np.uint64(32)) | h2.astype(
                np.uint64
            )
            self._sampler.offer_array(
                fps,
                depths=np.ones(len(inits), dtype=np.int64),
                states=inits,
            )

        # Pack the host-seeded 4-lane rows into the device table layout:
        # per-shard key buffer [2*tcap] (h1 half | h2 half) + parent lanes.
        table = (
            self._put(
                np.concatenate([table_np[:, :, 0], table_np[:, :, 1]], axis=1)
            ),
            self._put(table_np[:, :, 2]),
            self._put(table_np[:, :, 3]),
        )
        queue = tuple(self._put(queue_np[:, :, t]) for t in range(W))
        rec_fp1 = self._put(np.zeros((N, NP_), dtype=np.uint32))
        rec_fp2 = self._put(np.zeros((N, NP_), dtype=np.uint32))
        heads = np.zeros(N, dtype=np.int64)

        depth_limit = (
            self._target_max_depth
            if self._target_max_depth is not None
            else 0xFFFFFFFF
        )
        # The per-step append is bounded by the receive width.
        high_water = self._qcap - N * self._quota
        rec_bits = 0
        take_caps = [self._chunk] * N
        disc_depth_best: Dict[str, int] = {}
        per_shard_unique = self._per_shard_uniques(table_np)
        return (
            table, queue, heads, counts, rec_bits, rec_fp1, rec_fp2,
            take_caps, disc_depth_best, per_shard_unique, depth_limit,
            high_water, W,
        )

    def _mem_register(self, table, queue, rec_fps, params_dev) -> None:
        """(Re-)register the mesh's device buffers with the memory ledger
        from the shared size formulas (obs/memory.py
        mesh_component_sizes); every component carries the shard
        dimension. Called at loop entry and after every uniform all-shard
        table growth; a re-registration at a new size logs the growth
        event. The packed params row block is attached at each dispatch
        (it is rebuilt per era)."""
        rec = self._memory
        if rec is None:
            return
        from ..obs.memory import mesh_component_sizes
        from ..ops import visited_set as vs

        sizes = mesh_component_sizes(
            self.tm.state_width,
            self.tm.max_actions,
            len(self._tprops),
            chunk=self._chunk,
            queue_capacity_per_shard=self._qcap,
            table_capacity_per_shard=self._tcap,
            n_shards=self.n_shards,
            coverage=self._cov,
            sample_k=self._sample_k,
        )
        arrays = {
            "visited_table": table,
            "frontier_queue": queue,
            "record_fps": rec_fps,
            "packed_params": params_dev,
            "coverage_slab": params_dev,
            "sample_slab": params_dev,
        }
        rec.register_components(sizes, arrays=arrays)
        rec.set_geometry(
            rows=self._tcap,
            max_load=vs.MAX_LOAD,
            reserve_rows=self.n_shards * self._quota,
        )

    def _spill_host_bytes(self) -> int:
        return sum(
            self._spill[s].host_bytes() for s in range(self.n_shards)
        )

    def _on_spill_tier(self, direction, rows, nbytes, disk_bytes) -> None:
        """Tier-move hook shared by every shard's TieredSpillStore: keep
        the spill_tier counters, the disk gauge, and the ledger's disk
        component exact (disk bytes re-register at the ALL-shard total,
        so plan == ledger == nbytes holds per kind)."""
        if direction == "ram_to_disk":
            self._metrics.inc("spill_tier_rows", rows)
        else:
            self._metrics.inc("spill_tier_refill_rows", rows)
        total_disk = sum(
            self._spill[s].disk_bytes() for s in range(self.n_shards)
        )
        self._metrics.set_gauge("spill_disk_bytes", total_disk)
        if self._memory is not None:
            led = self._memory.ledger
            led.register("spill_disk", nbytes=total_disk, kind="disk")
            led.event(
                "spill_tier",
                direction=direction,
                rows=int(rows),
                bytes=int(nbytes),
                disk_bytes=int(total_disk),
            )

    def _proactive_reshard_due(self) -> bool:
        """Forecast-triggered elastic reshard (ISSUE 20; mirrors
        engines/tpu_bfs.py): with a device limit set and exhaustion
        projected, front-run the next uniform table doubling once the
        forecaster puts it within the reshard horizon.  The measured
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

    def _run_loop(
        self, table, queue, heads, counts, rec_bits, rec_fp1, rec_fp2,
        take_caps, disc_depth_best, per_shard_unique, depth_limit,
        high_water, W,
    ) -> None:
        import time as _time

        import jax.numpy as jnp

        self._mem_register(table, queue, (rec_fp1, rec_fp2), None)

        from ..ops import visited_set as vs

        from ..obs.coverage import DEPTH_CAP

        tm = self.tm
        S = tm.state_width
        A = tm.max_actions
        C = self._chunk
        N = self.n_shards
        NP_ = len(self._tprops)
        ncov = (A + NP_ + 1 + DEPTH_CAP) if self._cov else 0
        sk2 = nsamp = 0
        if self._sample_k:
            from ..obs.sample import slab_entries

            sk2 = slab_entries(self._sample_k)
            nsamp = 4 + 4 * sk2  # [T1,T2,occupied,0] + fp1|fp2|dep|ok
        s_base = P_LEN + ncov
        last_thresh = None
        max_sync = (
            self._max_sync_steps
            if self._timeout is None and self._ckpt_every is None
            else min(64, self._max_sync_steps)
        )
        # Adaptive era budgets (see engines/tpu_bfs.py): the device epilogue
        # emits the next era's budget through the P_MAX_STEPS output slot
        # (globally uniform — computed from psum'd pressure bits), doubling
        # after clean budget-only exits and halving under pressure. The host
        # only steers the CAP by wall-clock feedback so checkpoint cadence
        # and timeout polling hold.
        adaptive = self._timeout is not None or self._ckpt_every is not None
        budget = max_sync
        budget_cap = min(64, max_sync) if adaptive else 0
        cap_limit = min(self._max_sync_steps, 1 << 30)
        poll_target = None
        if self._ckpt_every is not None:
            poll_target = self._ckpt_every / 4.0
        if self._timeout is not None:
            t = self._timeout / 4.0
            poll_target = t if poll_target is None else min(poll_target, t)
        fin_any, fin_all, fin_all_en = self._finish_when.device_masks(
            self._tprops
        )
        # Spill hysteresis (see engines/tpu_bfs.py): drain to / refill up
        # to a margin below the watermark so spilling runs still get long
        # eras between host round-trips.
        spill_target = max(high_water // 2, high_water - 64 * N * self._quota)
        # Graceful-degradation budget: each recovery doubles every shard
        # table, so a handful of rounds covers any realistic exhaustion.
        regrow_budget = 8

        # Per-shard exchange accounting: the per-era delta of each shard's
        # P_UNIQUE row is the rows that shard accepted from the all_to_all
        # exchange (plus its locally-kept share — ownership routing makes
        # every insert an exchanged row). prev starts at ZERO, not the
        # seeded values, so on a clean run the shard_exchange_rows series
        # sums exactly to the final unique_state_count (seeding is era-0
        # exchange volume by definition). A degraded_regrow reload resets
        # prev to the checkpoint: replayed rows are physically re-exchanged
        # and count again, so the identity is exact only for clean runs.
        flight_prev_unique = np.zeros(N, dtype=np.int64)
        imbalance_warned = False
        stop = False
        # Speculative era pipelining (tentpole; see engines/tpu_bfs.py for
        # the full soundness argument): the block re-derives EVERY
        # host-intervention exit from the chained params rows — count /
        # high_water / grow_limit / GLOBAL rec bits / err (seeded from
        # P_ERR) all close the uniform gate — so a block chained off a
        # host-action boundary is an exact identity no-op. The chain is
        # not entered while any host-ONLY concern (spill-backlog refill,
        # checkpoint cadence, timeout, graceful stop, state-count target)
        # could fire.
        pipeline = self._pipeline and self._target_state_count is None

        def consume(*args, **kwargs):
            with self._metrics.phase("era_host"):
                return consume_block(*args, **kwargs)

        def consume_block(vals, fp1_dev, fp2_dev, dd_dev, era_wall,
                          era_budget, spec_in_flight=False):
            """Consume one block result: error recovery, counters,
            discoveries, spill drain, telemetry, checkpoint cadence, and
            stop conditions. Returns False when the era was discarded
            (probe error -> degraded-regrow reload), True otherwise.
            With ``spec_in_flight`` a chained block is still executing on
            device: the checkpoint save is deferred to the next serial
            boundary (the table/queue bindings here are the NEXT block's
            output buffers — pairing this era's heads/counts with them is
            only safe when that block is a no-op, which the caller cannot
            know yet)."""
            nonlocal table, queue, heads, counts, take_caps
            nonlocal per_shard_unique, rec_bits, rec_fp1, rec_fp2
            nonlocal budget, budget_cap, regrow_budget, disc_depth_best
            nonlocal flight_prev_unique, imbalance_warned, stop
            err = bool(vals[:, P_ERR].any())
            if not err and self._chaos_probe_error_era is not None and (
                self._metrics.get("eras") >= self._chaos_probe_error_era
            ):
                self._chaos_probe_error_era = None
                err = True
            if err:
                # Graceful degradation (degraded_regrow): the failed era's
                # work is unsound (unresolved inserts dropped states), so
                # discard it — reload the last crash-safe checkpoint,
                # double every shard table, and continue. Without a
                # checkpoint the consumed frontier rows are gone: abort.
                from ..engines.common import checkpoint_generations

                if (
                    self._ckpt_path is None
                    or regrow_budget == 0
                    or not checkpoint_generations(self._ckpt_path)
                ):
                    raise RuntimeError(
                        "visited-table probe budget exhausted despite "
                        "headroom"
                    )
                regrow_budget -= 1
                (
                    table, queue, heads, counts, rec_bits, rec_fp1, rec_fp2,
                    take_caps, disc_depth_best, per_shard_unique,
                ) = self._load_checkpoint(self._ckpt_path, W)
                flight_prev_unique = np.asarray(
                    per_shard_unique, dtype=np.int64
                )
                with self._metrics.phase("table_grow"):
                    table = self._grow_tables(table)
                self._metrics.inc("degraded_regrow")
                self._metrics.inc("table_growths")
                self._obs_event(
                    "degraded_regrow",
                    frontier=int(counts.sum()),
                    new_tcap=self._tcap,
                )
                if self._memory is not None:
                    self._memory.event(
                        "checkpoint_load", frontier=int(counts.sum())
                    )
                    self._mem_register(table, queue, (rec_fp1, rec_fp2), None)
                return False
            heads = vals[:, P_HEAD].astype(np.int64)
            counts = vals[:, P_COUNT].astype(np.int64)
            take_caps = list(vals[:, P_TAKE_CAP].astype(np.int64))
            # Device-emitted next-era budget (uniform across shards — it is
            # computed from psum'd inputs); the host steers only the cap.
            budget = int(vals[0, P_MAX_STEPS])
            self._metrics.set_gauge("era_step_budget", int(era_budget))
            # Wall feedback steers the per-era budget cap.
            if poll_target is not None and era_wall > 0.0:
                if era_wall < poll_target / 2 and budget_cap < cap_limit:
                    budget_cap = min(budget_cap * 2, cap_limit)
                elif era_wall > poll_target and budget_cap > 64:
                    budget_cap = max(budget_cap // 2, 64)
            per_shard_unique = list(vals[:, P_UNIQUE].astype(np.int64))
            self._unique = int(sum(per_shard_unique))
            self._state_count += int(vals[:, P_GEN].sum())
            self._max_depth = max(self._max_depth, int(vals[:, P_MAXD].max()))
            self._metrics.inc("eras")
            self._metrics.inc("steps", int(vals[:, P_STEPS].sum()))
            self._metrics.inc("states_generated", int(vals[:, P_GEN].sum()))
            self._metrics.set_gauge("take_cap", int(min(take_caps)))

            if self._cov:
                # The coverage tail is psum'd on device — every shard row
                # carries the global era deltas; read row 0.
                base = P_LEN
                cov_row = vals[0]
                cov_acc = self._coverage
                cov_acc.record_action_counts(cov_row[base : base + A])
                expanded = int(cov_row[base + A + NP_])
                for pi, p in enumerate(self._tprops):
                    cov_acc.record_property_eval(p.name, expanded)
                    cov_acc.record_property_hit(
                        p.name, int(cov_row[base + A + pi])
                    )
                cov_acc.record_depth_counts(
                    cov_row[base + A + NP_ + 1 : base + ncov]
                )

            if self._sampler is not None:
                # Drain every shard's sample tail (un-psum'd, per-shard
                # rows): the global bottom-k is the trivial union of the
                # per-shard drains — the sampler's offer dedups and keeps
                # the k smallest.
                for s in range(N):
                    row = vals[s]
                    occupied = int(row[s_base + 2])
                    if occupied:
                        off = s_base + 4
                        self._sampler.drain_slab(
                            row[off : off + sk2],
                            row[off + sk2 : off + 2 * sk2],
                            row[off + 2 * sk2 : off + 3 * sk2],
                            row[off + 3 * sk2 : off + 4 * sk2],
                            occupied,
                        )

            block_bits = int(np.bitwise_or.reduce(vals[:, P_REC]))
            if block_bits:
                fp1 = np.asarray(fp1_dev)
                fp2 = np.asarray(fp2_dev)
                depths = np.asarray(dd_dev)  # [N, NP_]
                for pi, p in enumerate(self._tprops):
                    if not (block_bits >> pi) & 1:
                        continue
                    s = int(np.argmin(depths[:, pi]))
                    d = int(depths[s, pi])
                    if (
                        p.name not in self._discovery_fps
                        or d < disc_depth_best.get(p.name, 1 << 62)
                    ):
                        disc_depth_best[p.name] = d
                        self._discovery_fps[p.name] = combine64(
                            fp1[s, pi], fp2[s, pi]
                        )
                rec_bits |= block_bits

            # Per-shard spill: drain to the hysteresis margin, ONE stacked
            # download per shard.
            spilled = 0
            for s in range(N):
                if counts[s] > high_water:
                    k = int(counts[s] - spill_target)
                    idx = jnp.asarray(
                        (heads[s] + counts[s] - k + np.arange(k))
                        & (self._qcap - 1)
                    )
                    with self._metrics.phase("spill"):
                        big = np.asarray(
                            jnp.stack(
                                [queue[t][s, idx] for t in range(W)], axis=1
                            )
                        )
                    for off in range(0, k, N * self._quota):
                        self._spill[s].append(big[off : off + N * self._quota])
                    counts[s] -= k
                    spilled += k
                    self._metrics.inc("spill_rows", k)
                    self._max_depth = max(
                        self._max_depth, int(big[:, S + 1].max())
                    )
            if spilled and self._memory is not None:
                self._memory.staging(
                    self._spill_host_bytes(), event="spill", rows=int(spilled)
                )

            # Per-shard telemetry off the same per-shard params rows (zero
            # extra device reads): labeled counter series (Prometheus
            # `{shard="k"}` via SHARD_SERIES_LABELS), per-shard gauges, and
            # the cross-shard frontier imbalance gauge. The labeled sums
            # equal the engine totals exactly — same vals columns.
            shard_unique = np.asarray(per_shard_unique, dtype=np.int64)
            exchange = np.maximum(0, shard_unique - flight_prev_unique)
            flight_prev_unique = shard_unique
            shards_rec = {}
            for s in range(N):
                key = str(s)
                self._metrics.inc_labeled(
                    "shard_steps", key, int(vals[s, P_STEPS])
                )
                self._metrics.inc_labeled(
                    "shard_states_generated", key, int(vals[s, P_GEN])
                )
                self._metrics.inc_labeled(
                    "shard_exchange_rows", key, int(exchange[s])
                )
                shards_rec[key] = {
                    "frontier": int(counts[s]),
                    "load_factor": round(
                        int(shard_unique[s]) / max(1, self._tcap), 4
                    ),
                    "exchange_rows": int(exchange[s]),
                }
            self._metrics.set_gauge(
                "shard_frontier_rows",
                {k: v["frontier"] for k, v in shards_rec.items()},
            )
            self._metrics.set_gauge(
                "shard_load_factor",
                {k: v["load_factor"] for k, v in shards_rec.items()},
            )
            occ_mean = float(counts.mean())
            imbalance = (
                float(counts.max()) / occ_mean if occ_mean > 0 else 1.0
            )
            self._metrics.set_gauge("shard_imbalance", round(imbalance, 4))
            # Skew on a near-empty frontier (the drain phase) is noise —
            # only warn when the mean shard holds at least a full take.
            if (
                imbalance > SHARD_IMBALANCE_WARN
                and occ_mean >= self._chunk
                and not imbalance_warned
            ):
                imbalance_warned = True
                from ..obs.log import get_logger

                get_logger("parallel.mesh").warning(
                    "cross-shard frontier imbalance: the busiest shard "
                    "holds several times the mean occupancy (ownership "
                    "hashing is skewed for this model)",
                    imbalance=round(imbalance, 2),
                    max_rows=int(counts.max()),
                    mean_rows=round(occ_mean, 1),
                )

            self._obs_event(
                "era",
                frontier=int(counts.sum()),
                load_factor=round(
                    max(per_shard_unique) / max(1, self._tcap), 4
                ),
                take_cap=int(min(take_caps)),
                steps=int(vals[:, P_STEPS].sum()),
                generated=int(vals[:, P_GEN].sum()),
                spill_rows=spilled,
            )

            if not spec_in_flight and self._ckpt_path is not None and (
                self._ckpt_every is not None
                and _time.monotonic() - self._last_ckpt >= self._ckpt_every
            ):
                self._save_checkpoint(
                    table, queue, heads, counts, rec_bits, rec_fp1, rec_fp2,
                    take_caps, disc_depth_best, per_shard_unique,
                )

            # Flight record after spill/checkpoint so this era's host work
            # lands in its own host_gap. Under pipelining era_wall is the
            # MARGINAL readback-to-readback span, so the summary still
            # reconciles with the external wall clock (obs/flight.py
            # overlap-aware accounting).
            self._flight_record(
                device_era_secs=era_wall,
                steps=int(vals[:, P_STEPS].sum()),
                generated=int(vals[:, P_GEN].sum()),
                unique=self._unique,
                frontier=int(counts.sum()),
                load_factor=round(
                    max(per_shard_unique) / max(1, self._tcap), 4
                ),
                take_cap=int(min(take_caps)),
                spill_rows=spilled,
                shards=shards_rec,
                grow_rows=int(max(per_shard_unique)),
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
                # Graceful-stop request (SIGTERM/SIGINT flush): the final
                # checkpoint below captures this era boundary — the same
                # path timeout/target stops take.
                self._metrics.set_gauge("interrupted", 1)
                stop = True
            return True

        while not stop and (
            counts.sum() > 0 or any(self._spill[s] for s in range(N))
        ):
            # Refill spills per shard (one batched upload per shard).
            for s in range(N):
                refill = []
                refill_rows = 0
                # Spill blocks are <= N*quota rows and spill_target >=
                # 1.5*N*quota (qcap >= 4*N*quota in __init__), so an empty
                # shard always refills at least one block.
                while self._spill[s] and (
                    counts[s] + refill_rows + self._spill[s].peek_rows()
                    <= spill_target
                ):
                    refill.append(self._spill[s].pop())
                    refill_rows += len(refill[-1])
                if refill:
                    rows = np.concatenate(refill, axis=0)
                    k = len(rows)
                    idx = jnp.asarray(
                        (heads[s] + counts[s] + np.arange(k)) & (self._qcap - 1)
                    )
                    with self._metrics.phase("refill"):
                        rows_dev = jnp.asarray(rows)
                        queue = tuple(
                            queue[t].at[s, idx].set(rows_dev[:, t])
                            for t in range(W)
                        )
                    counts[s] += k
                    self._metrics.inc("refill_rows", k)
                    if self._memory is not None:
                        self._memory.staging(
                            self._spill_host_bytes(),
                            event="refill",
                            rows=int(k),
                        )
            if counts.sum() == 0:
                if any(self._spill[s] for s in range(N)):
                    # Unreachable by the block-size invariant above; loud
                    # beats silently dropping spilled states.
                    raise RuntimeError("empty frontier with stranded spill")
                break

            # Grow ALL shard tables together when any shard nears the load
            # limit (uniform shapes keep one compiled program).
            grew = False
            while (
                max(per_shard_unique) + N * self._quota
                > vs.MAX_LOAD * self._tcap
            ):
                with self._metrics.phase("table_grow"):
                    table = self._grow_tables(table)
                self._metrics.inc("table_growths")
                grew = True
            # Elastic re-shard (ISSUE 20; see engines/tpu_bfs.py): when
            # the forecaster projects growth within the horizon, take the
            # uniform doubling NOW at this host-owned boundary. At most
            # one proactive doubling per era — the forecast refreshes at
            # every _flight_record.
            if (
                self._proactive_reshard_due()
                and self._metrics.get("eras") != self._reshard_last_era
            ):
                self._reshard_last_era = self._metrics.get("eras")
                with self._metrics.phase("table_grow"):
                    table = self._grow_tables(table)
                self._metrics.inc("table_growths")
                self._metrics.inc("reshard_proactive")
                self._obs_event(
                    "reshard_proactive", table_capacity_per_shard=self._tcap
                )
                grew = True
            if grew:
                self._mem_register(table, queue, (rec_fp1, rec_fp2), None)
            grow_limit = max(
                0, int(vs.MAX_LOAD * self._tcap) - N * self._quota
            )

            max_steps = min(budget, budget_cap) if adaptive else budget
            if self._target_state_count is not None:
                remaining = max(
                    0, self._target_state_count - self._state_count
                )
                max_steps = max(
                    1, min(max_steps, 1 + remaining // max(1, N * C * A))
                )

            params_np = np.zeros(
                (N, P_LEN + ncov + nsamp), dtype=np.uint32
            )
            for s in range(N):
                params_np[s, :P_LEN] = [
                    heads[s], counts[s], per_shard_unique[s], rec_bits,
                    depth_limit, grow_limit, high_water, max_steps,
                    0, 0, 0, 0, take_caps[s],
                    fin_any, fin_all, fin_all_en, budget_cap,
                ]
            if self._sample_k:
                t1, t2 = self._sampler.threshold_parts()
                params_np[:, s_base] = t1
                params_np[:, s_base + 1] = t2
                last_thresh = (t1, t2)
            with self._metrics.phase("upload"):
                params_in = self._put(params_np)
            _era_w0 = _time.monotonic()
            with self._metrics.phase("era_dispatch"):
                table, queue, rec_fp1, rec_fp2, params, disc_depth = (
                    self._block.serial(
                        table, queue, rec_fp1, rec_fp2, params_in
                    )
                )
            self._metrics.inc("dispatches")
            if self._memory is not None:
                self._memory.attach("packed_params", params)
                self._memory.attach("coverage_slab", params)
                self._memory.attach("sample_slab", params)
            cur_budget = max_steps
            # Speculative chain (oldest first, at most CHAIN_DEPTH): chain[i] holds the
            # i-th chained block's OUTPUT handles (params, rec_fp1,
            # rec_fp2, disc_depth) plus its dispatch timestamp. Unlike the
            # single-device engine, each entry pairs the era with its OWN
            # fp/depth arrays — the mesh discovery path reads them.
            chain: List[Tuple[Any, Any, Any, Any, float]] = []
            while True:
                # Top up the chain while every host-only concern is quiet:
                # each chained block launches off the newest on-device
                # state with its predecessor's readback queued
                # (non-blocking) behind the ones already in flight.
                while (
                    pipeline
                    and len(chain) < CHAIN_DEPTH
                    and not any(self._spill[s] for s in range(N))
                    and not self._ckpt_stop.is_set()
                    and not self._timed_out()
                    and not self._proactive_reshard_due()
                    and (
                        self._ckpt_every is None
                        or _time.monotonic() - self._last_ckpt
                        < self._ckpt_every
                    )
                ):
                    if chain:
                        src_p, src_f1, src_f2 = chain[-1][:3]
                    else:
                        src_p, src_f1, src_f2 = params, rec_fp1, rec_fp2
                    # Kick the pending readback without blocking, then
                    # chain off the on-device state (the chain program
                    # variant pins the params operand, so every readback
                    # source stays live).
                    try:
                        src_p.copy_to_host_async()
                    except AttributeError:
                        pass  # CPU backend: the copy below is free anyway
                    t0 = _time.monotonic()
                    with self._metrics.phase("era_dispatch"):
                        table, queue, c_f1, c_f2, c_p, c_dd = (
                            self._block.chain(
                                table, queue, src_f1, src_f2, src_p
                            )
                        )
                    self._metrics.inc("dispatches")
                    self._metrics.inc("spec_dispatch")
                    chain.append((c_p, c_f1, c_f2, c_dd, t0))
                    if len(chain) > self._chain_max:
                        self._chain_max = len(chain)
                        self._metrics.set_gauge(
                            "spec_chain_depth", self._chain_max
                        )
                if not chain:
                    # Serial boundary: block on the readback, consume with
                    # full host services (spill drain, checkpoint, stop).
                    with self._metrics.phase("readback"):
                        vals = np.asarray(params)  # one download per block
                    era_wall = _time.monotonic() - _era_w0
                    self._metrics.add_phase("device_era", era_wall)
                    self._metrics.observe("era_secs", era_wall)
                    consume(vals, rec_fp1, rec_fp2, disc_depth, era_wall,
                            cur_budget)
                    break
                with self._metrics.phase("readback"):
                    vals = np.asarray(params)
                era_wall = _time.monotonic() - _era_w0
                self._metrics.add_phase("device_era", era_wall)
                self._metrics.observe("era_secs", era_wall)
                ok = consume(vals, rec_fp1, rec_fp2, disc_depth, era_wall,
                             cur_budget, spec_in_flight=True)
                if not ok:
                    # Probe error -> checkpoint reload. The real-err case
                    # makes every chained block a guaranteed no-op (the
                    # carried P_ERR closes the gate); a chaos-faked err may
                    # have let them run real work — either way the reload
                    # discards the whole chain. Quiesce each dispatch
                    # before dropping its handles so the reload's uploads
                    # don't race the blocks.
                    for c_p, _f1, _f2, _dd, _t0 in chain:
                        np.asarray(c_p)
                        self._metrics.inc("spec_wasted")
                    chain.clear()
                    break
                cur_budget = budget
                if (
                    not stop
                    and counts.sum() > 0
                    and not any(self._spill[s] for s in range(N))
                    and max(per_shard_unique) + N * self._quota
                    <= vs.MAX_LOAD * self._tcap
                    and not self._proactive_reshard_due()
                    and (
                        self._sampler is None
                        or self._sampler.threshold_parts() == last_thresh
                    )
                ):
                    # Clean boundary: the oldest chained block IS the next
                    # era and has been executing since this readback
                    # completed (marginal readback-to-readback timing).
                    # (A tightened sampling threshold also breaks the chain
                    # — stale thresholds are sound but over-capture; the
                    # serial rebuild below uploads the fresh one.)
                    # grow_limit check mirrors the proactive-grow trigger
                    # above, so a growth boundary always falls through to
                    # the drain below.
                    params, rec_fp1, rec_fp2, disc_depth, _t0 = chain.pop(0)
                    _era_w0 = _time.monotonic()
                    continue
                # Host action at this boundary (stop request, drained
                # frontier, spill backlog, or table growth due): drain the
                # chain in order. Every DEVICE-visible case makes each
                # remaining block an identity no-op (see the soundness note
                # above); peek its steps to tell. steps > 0 means a
                # host-ONLY stop (timeout/SIGTERM) landed mid-chain while
                # the device legitimately ran — consume that real, sound
                # work before stopping.
                while chain:
                    c_p, c_f1, c_f2, c_dd, c_t0 = chain.pop(0)
                    svals = np.asarray(c_p)  # blocking: quiesce
                    # Keep the rebound handles either way — a no-op's
                    # outputs are value-equal to its inputs, and later
                    # chained blocks feed off these buffers.
                    params, rec_fp1, rec_fp2, disc_depth = (
                        c_p, c_f1, c_f2, c_dd,
                    )
                    if int(svals[:, P_STEPS].sum()) == 0:
                        self._metrics.inc("spec_wasted")
                        continue
                    era_wall = _time.monotonic() - c_t0
                    self._metrics.add_phase("device_era", era_wall)
                    self._metrics.observe("era_secs", era_wall)
                    ok = consume(svals, c_f1, c_f2, c_dd, era_wall,
                                 cur_budget, spec_in_flight=bool(chain))
                    cur_budget = budget
                    if not ok:
                        for d_p, _f1, _f2, _dd, _t0 in chain:
                            np.asarray(d_p)
                            self._metrics.inc("spec_wasted")
                        chain.clear()
                        break
                break

        if self._ckpt_path is not None:
            self._save_checkpoint(
                table, queue, heads, counts, rec_bits, rec_fp1, rec_fp2,
                take_caps, disc_depth_best, per_shard_unique,
            )
        # Any disk-tier spools are dead weight past this point (a resume
        # rebuilds the stacks from the checkpoint's spill arrays).
        for s in range(N):
            self._spill[s].close()
        # The deepest speculative chain reached.
        self._metrics.set_gauge("spec_chain_depth", self._chain_max)
        self._table_dev = table
        if self._memory is not None:
            # Final era's live buffers, for the post-run nbytes parity.
            led = self._memory.ledger
            led.attach("visited_table", table)
            led.attach("frontier_queue", queue)
            led.attach("record_fps", (rec_fp1, rec_fp2))
        return

    # -- checkpoint/resume --------------------------------------------------

    def _save_checkpoint(
        self, table, queue, heads, counts, rec_bits, rec_fp1, rec_fp2,
        take_caps, disc_depth_best, per_shard_unique,
    ) -> None:
        """Serialize the full sharded engine state (per-shard tables, rings,
        spill lists, take_caps, counters) to one .npz via the crash-safe
        protocol in engines/common.py (tmp + fsync + generation rotation +
        rename, content digest in the meta). Mirrors the single-device
        engine's checkpoint (engines/tpu_bfs.py); the reference has no
        equivalent."""
        import time as _time

        from ..engines.common import checkpoint_meta, save_checkpoint_tiered
        from ..ops import visited_set as vs

        meta = checkpoint_meta(
            self.tm,
            self._tprops,
            n_shards=self.n_shards,
            ring_lanes=len(queue),
            qcap=self._qcap,
            tcap=self._tcap,
            chunk=self._chunk,
            quota=self._quota,
            max_probes=vs.MAX_PROBES,
            rec_bits=rec_bits,
            state_count=self._state_count,
            unique=self._unique,
            max_depth=self._max_depth,
            discovery_fps={k: str(v) for k, v in self._discovery_fps.items()},
            disc_depth_best={k: int(v) for k, v in disc_depth_best.items()},
            per_shard_unique=[int(u) for u in per_shard_unique],
            take_caps=[int(t) for t in take_caps],
            sampler=(
                self._sampler.export_state()
                if self._sampler is not None
                else None
            ),
        )
        arrays = {
            "heads": np.asarray(heads, dtype=np.int64),
            "counts": np.asarray(counts, dtype=np.int64),
            "rec_fp1": np.asarray(rec_fp1),
            "rec_fp2": np.asarray(rec_fp2),
        }
        # On-disk format keeps the four flat lanes (table0..3) per shard;
        # the packed key buffer is split host-side (views, one download).
        keys = np.asarray(table[0])
        cap = keys.shape[1] // 2
        arrays["table0"] = keys[:, :cap]
        arrays["table1"] = keys[:, cap:]
        arrays["table2"] = np.asarray(table[1])
        arrays["table3"] = np.asarray(table[2])
        for w, lane in enumerate(queue):
            arrays[f"queue{w}"] = np.asarray(lane)
        for s in range(self.n_shards):
            for i, blk in enumerate(self._spill[s].iter_blocks()):
                arrays[f"spill_{s}_{i}"] = blk
        # Tiered save (ISSUE 20): a full base when the chain state says so
        # (first save, tcap changed, chain at max), else a delta holding
        # only the table rows inserted since the base — the per-shard
        # lanes flatten into one occupancy vector, so the shared delta
        # protocol applies unchanged.
        self._ckpt_delta = save_checkpoint_tiered(
            self._ckpt_path, meta, arrays,
            state=self._ckpt_delta, tcap=self._tcap,
            keep=self._ckpt_keep, metrics=self._metrics,
        )
        self._last_ckpt = _time.monotonic()

    def _load_checkpoint(self, path: str, W: int):
        import jax.numpy as jnp

        from ..engines.common import (
            load_checkpoint_folded,
            validate_checkpoint_meta,
        )
        from ..ops import visited_set as vs

        # Digest-verified load with automatic fallback to the previous
        # generation when the newest file is truncated/corrupt, folding any
        # surviving delta chain onto the base (engines/common.py).
        data, meta = load_checkpoint_folded(path, metrics=self._metrics)
        validate_checkpoint_meta(
            meta,
            self.tm,
            self._tprops,
            exact={
                "n_shards": self.n_shards,
                "qcap": self._qcap,
                "state_width": self.tm.state_width,
                # The exchange program and spill headroom are compiled
                # around these; a silent mismatch would change behavior
                # mid-run.
                "chunk": self._chunk,
                "quota": self._quota,
                # Ring layout changed in round 5 (hashes no longer carried).
                "ring_lanes": W,
                # The probe cascade is part of the table's on-disk meaning.
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
            self._sampler.restore_state(meta["sampler"])
        for s in range(self.n_shards):
            blocks = sorted(
                (k for k in data if k.startswith(f"spill_{s}_")),
                key=lambda n: int(n.rsplit("_", 1)[1]),
            )
            self._spill[s].reset(data[k] for k in blocks)
        # A reload invalidates the delta-chain baseline (the resumed run's
        # next save must be a fresh full base).
        self._ckpt_delta = None
        table = (
            self._put(np.concatenate([data["table0"], data["table1"]], axis=1)),
            self._put(data["table2"]),
            self._put(data["table3"]),
        )
        queue = tuple(self._put(data[f"queue{w}"]) for w in range(W))
        return (
            table,
            queue,
            data["heads"].astype(np.int64),
            data["counts"].astype(np.int64),
            meta["rec_bits"],
            self._put(data["rec_fp1"]),
            self._put(data["rec_fp2"]),
            list(meta["take_caps"]),
            {k: int(v) for k, v in meta["disc_depth_best"].items()},
            list(meta["per_shard_unique"]),
        )

    @staticmethod
    def _host_insert(table_shard: np.ndarray, h1: int, h2: int) -> None:
        cap = table_shard.shape[0]
        stride = (h2 | 1) & 0xFFFFFFFF
        idx = h1 & (cap - 1)
        while table_shard[idx, 0] != 0 or table_shard[idx, 1] != 0:
            if table_shard[idx, 0] == h1 and table_shard[idx, 1] == h2:
                return
            idx = (idx + stride) & (cap - 1)
        table_shard[idx] = (h1, h2, 0, 0)

    def _put(self, x):
        """Upload a host ``[n_shards, ...]`` array straight to its shards:
        row s goes to mesh device s, never through one device first."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        return jax.device_put(
            x, NamedSharding(self.mesh, PartitionSpec("shards"))
        )

    def _per_shard_uniques(self, table_np) -> List[int]:
        return [
            int(((table_np[s, :, 0] != 0) | (table_np[s, :, 1] != 0)).sum())
            for s in range(self.n_shards)
        ]

    def _grow_tables(self, table):
        """Double every shard's capacity with an ON-DEVICE shard_map'd
        rehash — the table never round-trips through the host (round 5;
        the old implementation downloaded, rehashed, and re-uploaded every
        shard, a multi-GB host bounce at real table sizes)."""
        from ..ops import visited_set as vs

        new_cap = self._tcap * 2
        grow = _build_grow(self._tcap, new_cap, self.mesh, "shards")
        table, counts = grow(table)
        counts = np.asarray(counts).sum(axis=0)
        self._metrics.inc("rehash_rows", counts[vs.REHASH_ROWS])
        self._metrics.inc("rehash_stragglers", counts[vs.REHASH_STRAGGLERS])
        if counts[vs.REHASH_UNRESOLVED] != 0:
            raise RuntimeError("rehash failed; table pathologically full")
        self._tcap = new_cap
        return table

    # -- accessors ----------------------------------------------------------

    def telemetry(self) -> Dict[str, Any]:
        m = self._metrics
        m.set_gauge("n_shards", self.n_shards)
        m.set_gauge("quota", self._quota)
        m.set_gauge("chunk", self._chunk)
        m.set_gauge("table_capacity", self._tcap)
        m.set_gauge(
            "load_factor",
            round(self._unique / max(1, self.n_shards * self._tcap), 4),
        )
        return super().telemetry()

    def unique_state_count(self) -> int:
        return self._unique

    def discoveries(self) -> Dict[str, Path]:
        self.join()
        return {
            name: self._reconstruct(fp)
            for name, fp in list(self._discovery_fps.items())
        }

    def _sample_resolver(self):
        # Device slabs carry only (fp, depth): resolve sampled states
        # lazily via cross-shard parent-pointer reconstruction.
        return self._path_sample_resolver(self._reconstruct)

    def _reconstruct(self, fp64: int) -> Path:
        """Walk parent pointers ACROSS shard tables (owner = h1 % N per
        hop), then re-execute the model along the fingerprint chain."""
        from ..ops import visited_set as vs

        if not hasattr(self, "_table_np"):
            # Split the packed per-shard key buffer into the four flat
            # lanes lookup_parent_np walks (views over one download each).
            with self._metrics.phase("path_download"):
                keys = np.asarray(self._table_dev[0])
                cap = keys.shape[1] // 2
                self._table_np = [
                    keys[:, :cap],
                    keys[:, cap:],
                    np.asarray(self._table_dev[1]),
                    np.asarray(self._table_dev[2]),
                ]
        with self._metrics.phase("path_walk"):
            chain = [fp64]
            cur = fp64
            for _ in range(10_000_000):
                h1, h2 = split64(cur)
                s = h1 % self.n_shards
                shard = tuple(self._table_np[t][s] for t in range(4))
                found, p1, p2 = vs.lookup_parent_np(shard, h1, h2)
                if not found:
                    raise RuntimeError(
                        f"fingerprint {cur} missing from shard {s} during "
                        "path reconstruction"
                    )
                if p1 == 0 and p2 == 0:
                    break
                cur = combine64(p1, p2)
                chain.append(cur)
            chain.reverse()
        with self._metrics.phase("path_replay"):
            return Path.from_fingerprints(self._model, chain)


# Back-compat style helper mirroring the original prototype's interface.
class ShardedBfs:
    """Thin wrapper: build a ShardedBfsChecker from a bare TensorModel."""

    def __init__(self, tm: TensorModel, devices=None, **kw):
        self._tm = tm
        self._devices = devices
        self._kw = kw
        self.checker: Optional[ShardedBfsChecker] = None

    def run(self) -> "ShardedBfs":
        builder = TensorModelAdapter(self._tm).checker()
        self.checker = ShardedBfsChecker(
            builder, devices=self._devices, **self._kw
        )
        self.checker.join()
        return self

    @property
    def state_count(self):
        return self.checker.state_count()

    @property
    def unique_state_count(self):
        return self.checker.unique_state_count()

    @property
    def max_depth(self):
        return self.checker.max_depth()

    @property
    def discovery_fps(self):
        return self.checker._discovery_fps
