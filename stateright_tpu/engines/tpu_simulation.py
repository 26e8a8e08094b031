"""Batched device simulation: B independent seeded random walks per step.

The device twin of the host simulation engine (engines/simulation.py;
reference src/checker/simulation.rs:138-201, where parallelism = one
independent seeded walk per OS thread). Here parallelism is data-parallel:
B walks advance together, one random transition per walk per device step,
inside the same era-loop architecture as the batched BFS engine (many
steps per dispatch; the host syncs once per era).

Design notes (TPU-first, not a translation):

  - Walk state is structure-of-arrays: S state lanes of width [B], plus
    per-walk seed / path-length / eventually-bits lanes.
  - Each walk's fingerprint path lives in a device-resident [B, L] buffer
    (L = walk_cap). That one structure serves THREE roles the reference
    implements separately: per-run cycle detection (membership test is an
    elementwise [B, L] compare — simulation.rs:285-289's HashSet), the
    depth bound, and counterexample reporting (a discovery's full
    fingerprint path is read straight out of the buffer — no replay).
  - The chooser is a counter-based PRNG (splitmix-style avalanche of
    (walk_seed, step)): stateless, so any walk's trace is reproducible
    from the master seed alone, matching the reference's reseeded-
    per-trace discipline (simulation.rs:154-197).
  - Ended walks (terminal / cycle / depth-cap) restart IN PLACE with an
    evolved seed; a walk that records a discovery freezes until the era
    ends so its path buffer survives for extraction. The frozen flag is a
    walk lane that CROSSES the era boundary: the host harvests discovery
    paths between dispatches, and the next era restarts frozen walks
    (fresh init, evolved seed, cleared path row) — resuming them mid-walk
    would make each see its own recorded path as a cycle and fabricate
    EVENTUALLY counterexamples.

Semantic divergences from the host engine (documented, both benign for
the engine's purpose of finding examples/counterexamples fast):
  - boundary handling: the device walk never *enters* an out-of-boundary
    state (such successors are masked off as disabled), while the host
    walk may select one and then end; walk distributions differ when a
    boundary is active.
  - the uniform chooser picks among actions whose successor is valid,
    rather than retrying disabled actions without replacement — the same
    distribution, computed without the swap_remove loop.
"""

from __future__ import annotations

import functools
import os
import time
from typing import Any, Dict, List, Tuple

import numpy as np

from ..checker import CheckerBuilder
from ..core import Expectation
from ..fingerprint import combine64
from ..path import Path
from ..tensor import TensorModel, TensorModelAdapter
from .common import HostEngineBase

# Packed scalar params (one uint32 vector per direction, as in tpu_bfs).
P_REC = 0  # recorded-discovery bitmask
P_MAX_STEPS = 1
P_FIN_ANY = 2
P_FIN_ALL = 3
P_FIN_ALL_EN = 4
P_TARGET_GEN = 5  # era exits when generated-this-run exceeds this (0 = off)
P_GEN0 = 6  # generated before this era (for the target check)
P_GEN = 7  # OUT: generated states total after era
P_STEPS = 8  # OUT: device steps executed this era
P_MAXD = 9  # OUT: max walk length seen
P_SEED = 10  # master seed (consumed by the fused seed+first-era dispatch)
P_LEN = 11

_LOOP_CACHE: Dict[Tuple, Tuple[TensorModel, Any]] = {}


def _build_sim_loop(tm: TensorModel, props, B: int, L: int, cov: bool = True,
                    sample_k: int = 0):
    key = (id(tm), B, L, len(props), cov, sample_k)
    cached = _LOOP_CACHE.get(key)
    if cached is not None and cached[0] is tm:
        return cached[1]  # (loop, seed_run, n_init)
    while len(_LOOP_CACHE) >= 16:
        _LOOP_CACHE.pop(next(iter(_LOOP_CACHE)))

    import jax
    import jax.numpy as jnp
    from jax import lax

    from ..fingerprint import hash_lanes_jnp
    from ..obs.coverage import DEPTH_CAP
    from ..obs.spans import stage_scope

    S = tm.state_width
    A = tm.max_actions
    P = len(props)
    if sample_k:
        # Bottom-k space sampling (obs/sample.py): counted states below
        # the host threshold append to an in-carry slab drained in the
        # params tail (same protocol as tpu_bfs, with two differences:
        # the capture width is the full walk batch B — so per-step drops
        # are impossible even under a loose threshold — and the slab
        # carries the S state lanes, since walks revisit states and no
        # visited table exists to reconstruct rows from later).
        from ..obs.sample import slab_entries, slab_high_water

        sk2 = slab_entries(sample_k)
        s_high = slab_high_water(sample_k)
        scap = s_high + B  # one more step always fits
        s_base = P_LEN + 2 * P + ((A + P + DEPTH_CAP) if cov else 0)

    init_np = np.asarray(tm.init_states_array(), dtype=np.uint32)
    # Boundary-filter init states at build time (host-side, static) so the
    # fused device seeder and the host path agree on the init set.
    _inb = np.asarray(
        tm.within_boundary_lanes(np, tuple(init_np[:, s] for s in range(S))),
        dtype=bool,
    )
    init_np = init_np[_inb]
    n_init = len(init_np)
    init_lanes_const = tuple(init_np[:, s] for s in range(S))

    init_ebits = 0
    e_slot = {}
    e_idx = 0
    for i, p in enumerate(props):
        if p.expectation == Expectation.EVENTUALLY:
            e_slot[i] = e_idx
            init_ebits |= 1 << e_idx
            e_idx += 1

    def prng(x):
        u = jnp.uint32
        x = (x ^ (x >> u(16))) * u(0x7FEB352D)
        x = (x ^ (x >> u(15))) * u(0x846CA68B)
        return x ^ (x >> u(16))

    from ..compat import donate_argnums_safe

    @functools.partial(jax.jit, donate_argnums=donate_argnums_safe(0, 1, 2))
    def loop(walk, fp1buf, fp2buf, params):
        """walk = (rows[S], seed, ptr, ebits, frozen) lanes of [B];
        fp*buf = [B * L] flat path buffers. The frozen lane MUST cross the
        era boundary: a walk freezes when it records a discovery and its
        current state is already in its own path buffer, so silently
        thawing it mid-walk (the pre-fix behavior) made its first
        membership test see itself — a fake cycle, which with surviving
        eventually-bits fabricated spurious EVENTUALLY counterexamples.
        The host harvests discovery paths between dispatches, so frozen
        arrivals RESTART here (fresh init, evolved seed, cleared path row)
        instead of resuming — sound, and generation never starves."""
        u = jnp.uint32
        rec_bits0 = params[P_REC]
        max_steps = params[P_MAX_STEPS]
        fin_any = params[P_FIN_ANY]
        fin_all = params[P_FIN_ALL]
        fin_all_en = params[P_FIN_ALL_EN]
        target_gen = params[P_TARGET_GEN]
        gen0 = params[P_GEN0]
        if sample_k:
            # Sampling threshold words (pass-through; stale = looser =
            # superset, host re-filters).
            st1 = params[s_base]
            st2 = params[s_base + 1]
        iota_b = jnp.arange(B, dtype=u)
        iota_l = jnp.arange(L, dtype=u)
        inits = tuple(jnp.asarray(l) for l in init_lanes_const)

        def cond(carry):
            (
                _w, _f1, _f2, gen, steps, rec_acc, _h, _pl, maxd, _covc,
                sampc,
            ) = carry
            fin_hit = ((rec_acc & fin_any) != u(0)) | (
                (fin_all_en != u(0)) & ((rec_acc & fin_all) == fin_all)
            )
            under_target = (target_gen == u(0)) | (gen0 + gen < target_gen)
            keep = (steps < max_steps) & ~fin_hit & under_target
            if sample_k:
                # Slab-occupancy gate (uint32 sum chain — carry-safe):
                # exit so the host drains before the slab can overflow.
                keep = keep & (sampc[3] <= u(s_high))
            return keep

        def body(carry):
            (
                (rows, seed, ptr, ebits, frozen),
                fp1buf,
                fp2buf,
                gen,
                steps,
                rec_acc,
                hseen,
                plen,
                maxd,
                covc,
                sampc,
            ) = carry
            active = ~frozen
            with stage_scope("hash"):
                h1, h2 = hash_lanes_jnp(rows)

            # Cycle detection: membership of the current state in the
            # walk's own path so far ([B, L] elementwise compare).
            with stage_scope("cycle"):
                f1m = fp1buf.reshape(B, L)
                f2m = fp2buf.reshape(B, L)
                in_path = (
                    ((f1m == h1[:, None]) & (f2m == h2[:, None])
                     & (iota_l[None, :] < ptr[:, None])).sum(axis=1, dtype=u)
                    > u(0)
                )
                cycle = active & in_path

            # Record the current state into the path buffer.
            with stage_scope("record"):
                pos = jnp.where(
                    active & ~cycle, iota_b * u(L) + ptr, u(B * L) + iota_b
                )
                fp1buf = fp1buf.at[pos].set(
                    h1, mode="drop", unique_indices=True
                )
                fp2buf = fp2buf.at[pos].set(
                    h2, mode="drop", unique_indices=True
                )
            counted = active & ~cycle
            ptr = jnp.where(counted, ptr + u(1), ptr)
            gen = gen + counted.sum(dtype=u)
            if sample_k:
                with stage_scope("sample"):
                    # Sample capture: counted states lexicographically below
                    # the threshold, with their state rows in hand (walks
                    # revisit states across traces — the host sampler dedups
                    # by fingerprint, so the sample stays a pure function of
                    # the visited set). Full-B capture width: no drops, ever.
                    from ..ops.visited_set import _compact_ids

                    below = counted & (
                        (h1 < st1) | ((h1 == st1) & (h2 < st2))
                    )

                    def _capture(sc):
                        sfp1, sfp2, sdep, socc, sst = sc
                        cids, cvalid, n_c = _compact_ids(below, B)
                        pos = socc + iota_b
                        ok_w = cvalid & (pos < u(scap))
                        widx = jnp.where(ok_w, pos, u(scap))
                        return (
                            sfp1.at[widx].set(h1[cids]),
                            sfp2.at[widx].set(h2[cids]),
                            sdep.at[widx].set(ptr[cids]),
                            socc + n_c,
                            tuple(
                                sst[s].at[widx].set(rows[s][cids])
                                for s in range(S)
                            ),
                        )

                    # Tight-threshold steps capture nothing almost always;
                    # the cond skips the compaction and the (3+S)-lane slab
                    # scatter on those steps.
                    sampc = lax.cond(
                        below.any(), _capture, lambda sc: sc, sampc
                    )
            if cov:
                with stage_scope("coverage"):
                    # Depth histogram: each counted state lands at its walk
                    # depth (the just-incremented ptr; clamped into the
                    # DEPTH_CAP overflow bucket). One scatter-add at [B].
                    act, covp, dhist = covc
                    dhist = dhist.at[
                        jnp.minimum(ptr, u(DEPTH_CAP - 1))
                    ].add(counted.astype(u))
                    covc = (act, covp, dhist)
            # maxd is a PER-WALK lane, reduced once in the epilogue rather
            # than by a scalar max-reduce in the carry every step.
            maxd = jnp.maximum(maxd, ptr)

            # Property evaluation on the current states (simulation.rs
            # property loop; eventually-bits clear on satisfaction).
            with stage_scope("expand"):
                prop_hits = [None] * P
                for i, p in enumerate(props):
                    if p.expectation == Expectation.EVENTUALLY:
                        sat = p.check(jnp, rows) & counted
                        ebits = jnp.where(sat, ebits & ~u(1 << e_slot[i]), ebits)
                    elif p.expectation == Expectation.ALWAYS:
                        prop_hits[i] = counted & ~p.check(jnp, rows)
                    else:
                        prop_hits[i] = counted & p.check(jnp, rows)

                # Successors + enabled mask.
                succs, amask = tm.step_lanes(jnp, rows)
                valid_a = []
                ne = jnp.zeros(B, dtype=u)
                for a in range(A):
                    v = amask[a] & tm.within_boundary_lanes(jnp, succs[a])
                    valid_a.append(v)
                    ne = ne + v.astype(u)

                terminal = counted & (ne == u(0))
                capped = counted & (ptr >= u(L))
                # Walk-end eventually discoveries (terminal rule; a cycle exit
                # matches the host engine, which also falls through to the
                # terminal ebits check on loops. Depth-capped walks skip it,
                # like the host's target_max_depth path).
                ended_for_ebits = terminal | cycle
                for i, p in enumerate(props):
                    if p.expectation == Expectation.EVENTUALLY:
                        prop_hits[i] = ended_for_ebits & (
                            (ebits & u(1 << e_slot[i])) != u(0)
                        )

            # Discovery snapshots: first hit per property freezes its walk
            # so the path buffer survives until the era ends.
            newly_frozen = frozen & False
            for i in range(P):
                hits = prop_hits[i]
                first = hits & ~hseen[i]
                plen = tuple(
                    jnp.where(first, ptr, plen[j]) if j == i else plen[j]
                    for j in range(P)
                )
                hseen = tuple(
                    (hseen[j] | hits) if j == i else hseen[j] for j in range(P)
                )
                hs = hits.sum(dtype=u)
                rec_acc = rec_acc | (jnp.minimum(hs, u(1)) << u(i))
                if cov:
                    # Per-property hit totals ride the sums the discovery
                    # gate already pays for.
                    act, covp, dhist = covc
                    covc = (
                        act,
                        tuple(
                            (covp[j] + hs) if j == i else covp[j]
                            for j in range(P)
                        ),
                        dhist,
                    )
                newly_frozen = newly_frozen | first
            frozen = frozen | newly_frozen

            # Choose one enabled action uniformly (counter-based PRNG).
            with stage_scope("choose"):
                r = prng(seed ^ (ptr * u(0x9E3779B9)))
                pick = jnp.where(ne > u(0), r % jnp.maximum(ne, u(1)), u(0))
                cum = jnp.zeros(B, dtype=u)
                new_rows = rows
                chosen_any = ne < u(0)  # all-false, varying
                sels = []
                for a in range(A):
                    sel = valid_a[a] & (cum == pick) & ~chosen_any
                    chosen_any = chosen_any | sel
                    sels.append(sel)
                    new_rows = tuple(
                        jnp.where(sel, succs[a][s], new_rows[s]) for s in range(S)
                    )
                    cum = cum + valid_a[a].astype(u)

            advance = counted & ~terminal & ~capped & ~newly_frozen
            restart = active & ~newly_frozen & (cycle | terminal | capped)
            if cov:
                with stage_scope("coverage"):
                    # Action coverage: the transition each advancing walk
                    # actually took this step (the simulation twin of the BFS
                    # engines' valid-successor attribution).
                    act, covp, dhist = covc
                    act = act + jnp.stack(
                        [(sels[a] & advance).sum(dtype=u) for a in range(A)]
                    )
                    covc = (act, covp, dhist)

            # Restarts: evolved seed, fresh init state, cleared path row.
            with stage_scope("choose"):
                seed2 = prng(seed + u(0x6A09E667))
                init_pick = prng(seed2) % u(n_init)
                rows = tuple(
                    jnp.where(
                        restart,
                        inits[s][init_pick],
                        jnp.where(advance, new_rows[s], rows[s]),
                    )
                    for s in range(S)
                )
            seed = jnp.where(restart, seed2, seed)
            ebits = jnp.where(restart, u(init_ebits), ebits)
            with stage_scope("record"):
                keep_row = ~restart
                fp1buf = (fp1buf.reshape(B, L) * keep_row[:, None]).reshape(-1)
                fp2buf = (fp2buf.reshape(B, L) * keep_row[:, None]).reshape(-1)
            ptr = jnp.where(restart, u(0), ptr)

            steps = steps + u(1)
            return (
                (rows, seed, ptr, ebits, frozen),
                fp1buf,
                fp2buf,
                gen,
                steps,
                rec_acc,
                hseen,
                plen,
                maxd,
                covc,
                sampc,
            )

        rows, seed, ptr, ebits = walk[:S], walk[S], walk[S + 1], walk[S + 2]
        # Era prologue: restart walks that arrived frozen (see docstring).
        frozen_in = walk[S + 3] != u(0)
        fseed = prng(seed + u(0x6A09E667))
        fpick = prng(fseed) % u(n_init)
        rows = tuple(
            jnp.where(frozen_in, inits[s][fpick], rows[s]) for s in range(S)
        )
        seed = jnp.where(frozen_in, fseed, seed)
        ebits = jnp.where(frozen_in, u(init_ebits), ebits)
        ptr = jnp.where(frozen_in, u(0), ptr)
        keep = ~frozen_in
        fp1buf = (fp1buf.reshape(B, L) * keep[:, None]).reshape(-1)
        fp2buf = (fp2buf.reshape(B, L) * keep[:, None]).reshape(-1)
        zero_b = seed & u(0)
        false_b = zero_b != 0
        covc0 = (
            (
                jnp.zeros(A, dtype=u),  # per-action taken counts
                tuple(zero_b[0] for _ in range(P)),  # per-property hits
                jnp.zeros(DEPTH_CAP, dtype=u),  # depth histogram
            )
            if cov
            else ()
        )
        sampc0 = (
            (
                # scap+1 wide: index scap is the masked-write trash slot.
                jnp.zeros(scap + 1, dtype=u),  # fp1
                jnp.zeros(scap + 1, dtype=u),  # fp2
                jnp.zeros(scap + 1, dtype=u),  # depth (walk position)
                zero_b[0],  # occupied
                tuple(jnp.zeros(scap + 1, dtype=u) for _ in range(S)),
            )
            if sample_k
            else ()
        )
        init_carry = (
            (tuple(rows), seed, ptr, ebits, false_b),
            fp1buf,
            fp2buf,
            zero_b[0],
            zero_b[0],
            rec_bits0,
            tuple(false_b for _ in range(P)),
            tuple(zero_b for _ in range(P)),
            zero_b,
            covc0,
            sampc0,
        )
        (
            (rows, seed, ptr, ebits, frozen),
            fp1buf,
            fp2buf,
            gen,
            steps,
            rec_acc,
            hseen,
            plen,
            maxd,
            covc_out,
            sampc_out,
        ) = lax.while_loop(cond, body, init_carry)

        # Epilogue: per newly-hit property, report the SHORTEST hit's walk
        # (parity with the BFS engine's shallowest-snapshot rule) as
        # (walk_index, path_length, fp pair).
        with stage_scope("epilogue"):
            rec_bits_out = rec_bits0
            disc_walk = jnp.zeros(P, dtype=u)
            disc_plen = jnp.zeros(P, dtype=u)
            for i in range(P):
                found = jnp.any(hseen[i])
                sel = jnp.argmin(jnp.where(hseen[i], plen[i], u(0xFFFFFFFF)))
                disc_walk = disc_walk.at[i].set(sel.astype(u))
                disc_plen = disc_plen.at[i].set(plen[i][sel])
                rec_bits_out = rec_bits_out | (found.astype(u) << u(i))

            walk_out = tuple(rows) + (seed, ptr, ebits, frozen.astype(u))
            # Discovery walk indices and path lengths ride the params tail so
            # the era result is ONE download (each separate device read is a
            # host round-trip on the TTFC path). With coverage enabled
            # the era's histograms (act[A] | prop_hits[P] | depth[DEPTH_CAP])
            # ride the same download.
            parts = [
                jnp.stack(
                    [
                        rec_bits_out,
                        params[P_MAX_STEPS],
                        params[P_FIN_ANY],
                        params[P_FIN_ALL],
                        params[P_FIN_ALL_EN],
                        params[P_TARGET_GEN],
                        gen0 + gen,
                        gen0 + gen,
                        steps,
                        maxd.max(),
                        params[P_SEED],
                    ]
                ),
                disc_walk,
                disc_plen,
            ]
            if cov:
                act, covp, dhist = covc_out
                parts += [
                    act,
                    jnp.stack(list(covp)) if P else jnp.zeros(0, dtype=u),
                    dhist,
                ]
            if sample_k:
                # Sample tail: [T1, T2, occupied, sdrop=0] + the sk2 smallest
                # slab entries by h1 (fp1 | fp2 | depth | S state lanes | ok)
                # — one top_k in the once-per-era epilogue. The ok lane
                # disambiguates padding from a real fp1 of 0xFFFFFFFF.
                # Walks revisit states, so the slab holds duplicate fps and a
                # plain top_k would spend all sk2 lanes on copies of the few
                # smallest — dedup first (first occurrence wins; O(scap^2)
                # bool matrix, epilogue-only so it costs once per era).
                sfp1, sfp2, sdep, socc, sst = sampc_out
                used = jnp.arange(scap, dtype=u) < socc
                f1, f2 = sfp1[:scap], sfp2[:scap]
                same = (
                    (f1[:, None] == f1[None, :])
                    & (f2[:, None] == f2[None, :])
                    & used[None, :]
                )
                idx = jnp.arange(scap, dtype=u)
                dup = (same & (idx[None, :] < idx[:, None])).any(axis=1)
                used = used & ~dup
                skey = jnp.where(used, ~sfp1[:scap], u(0))
                _topv, topi = lax.top_k(skey, sk2)
                parts += [
                    jnp.stack([st1, st2, socc, u(0)]),
                    sfp1[:scap][topi],
                    sfp2[:scap][topi],
                    sdep[:scap][topi],
                ]
                parts += [sst[s][:scap][topi] for s in range(S)]
                parts += [used[topi].astype(u)]
        params_out = jnp.concatenate(parts)
        return walk_out, fp1buf, fp2buf, params_out

    @jax.jit
    def seed_run(params):
        """Fused seeding + first era: ONE small upload, walk state and
        path buffers created on device (host<->device round-trips sit on
        the TTFC path; the walk lanes would otherwise cost an upload
        each). Walk 0 uses the master seed directly for
        reproducibility parity with the host engine (simulation.rs:154)."""
        u = jnp.uint32
        master = params[P_SEED]
        iota_b = jnp.arange(B, dtype=u)
        seeds = prng(master ^ (iota_b * u(0x9E3779B9)))
        seeds = seeds.at[0].set(master)
        picks = prng(seeds) % u(n_init)
        rows = tuple(jnp.asarray(l)[picks] for l in init_lanes_const)
        walk = rows + (
            seeds,
            jnp.zeros(B, dtype=u),
            jnp.full(B, init_ebits, dtype=u),
            jnp.zeros(B, dtype=u),  # frozen lane: nothing frozen yet
        )
        fp1buf = jnp.zeros(B * L, dtype=u)
        fp2buf = jnp.zeros(B * L, dtype=u)
        return loop(walk, fp1buf, fp2buf, params)


    _LOOP_CACHE[key] = (tm, (loop, seed_run, n_init))
    return loop, seed_run, n_init


class TpuSimulationChecker(HostEngineBase):
    """B batched seeded random walks on the default JAX device."""

    _supports_threads = True  # parallelism = the walk batch

    def __init__(
        self,
        builder: CheckerBuilder,
        seed: int,
        *,
        walks: int = 1024,
        walk_cap: int = 256,
        sync_steps: int = 1024,
    ):
        model = builder.model
        if isinstance(model, TensorModel):
            model = TensorModelAdapter(model)
        if not isinstance(model, TensorModelAdapter):
            raise TypeError(
                "spawn_tpu_simulation requires a TensorModel (or its adapter)"
            )
        super().__init__(builder, model=model)
        if self._visitor is not None:
            raise ValueError("the device simulation engine does not support visitors")
        if self._symmetry is not None:
            raise ValueError(
                "the device simulation engine does not support symmetry "
                "reduction (use the host simulation engine)"
            )
        self.tm = model.tm
        self._tprops = self.tm.tensor_properties()
        if len(self._tprops) > 32:
            raise ValueError("at most 32 tensor properties supported")
        self._seed = seed & 0xFFFFFFFF
        self._B = walks
        self._L = (
            min(walk_cap, self._target_max_depth)
            if self._target_max_depth is not None
            else walk_cap
        )
        self._sync = sync_steps
        self._discovery_paths: Dict[str, List[int]] = {}
        self._metrics.set_gauge("walks", self._B)
        self._metrics.set_gauge("walk_cap", self._L)
        self._cov = self._coverage.enabled
        self._sample_k = self._sampler.k if self._sampler is not None else 0
        self._loop, self._seed_run, self._n_init = _build_sim_loop(
            self.tm, self._tprops, self._B, self._L, self._cov,
            sample_k=self._sample_k,
        )
        self._start()

    def _run(self) -> None:
        import jax.numpy as jnp

        tm = self.tm
        S = tm.state_width
        B, L, P = self._B, self._L, len(self._tprops)

        fin_any, fin_all, fin_all_en = self._finish_when.device_masks(
            self._tprops
        )
        if self._n_init == 0:
            # No in-boundary init states: the compiled seeder's modulo
            # over n_init would be undefined — never dispatch it.
            return
        rec_bits = 0
        gen_total = 0

        max_sync = (
            self._sync
            if self._timeout is None
            else min(64, self._sync)
        )
        target_gen = self._target_state_count or 0

        from ..obs.coverage import DEPTH_CAP

        A = tm.max_actions
        ncov = (A + P + DEPTH_CAP) if self._cov else 0
        # Sample tail: [T1, T2, occupied, sdrop] + (fp1|fp2|depth|S state
        # lanes|ok) x slab_entries(k) words.
        if self._sample_k:
            from ..obs.sample import slab_entries

            sk2 = slab_entries(self._sample_k)
            nsamp = 4 + (4 + S) * sk2
            s_base = P_LEN + 2 * P + ncov
        else:
            sk2 = nsamp = s_base = 0
        last_thresh = None
        params = np.zeros(P_LEN + 2 * P + ncov + nsamp, dtype=np.uint32)
        if self._sampler is not None:
            t1, t2 = self._sampler.threshold_parts()
            params[s_base] = t1
            params[s_base + 1] = t2
            last_thresh = (t1, t2)
        params[P_MAX_STEPS] = max_sync
        params[P_FIN_ANY] = fin_any
        params[P_FIN_ALL] = fin_all
        params[P_FIN_ALL_EN] = fin_all_en
        params[P_TARGET_GEN] = min(target_gen, 0xFFFFFFFF)
        params[P_SEED] = self._seed

        # Fused seeding + first era: one small upload, one dispatch (walk
        # lanes and path buffers are created on device).
        first = True
        walk = fp1buf = fp2buf = None
        params_dev = jnp.asarray(params)

        while True:
            era_t0 = time.monotonic()
            if first:
                walk, fp1buf, fp2buf, params_dev = self._seed_run(params_dev)
                first = False
                if self._memory is not None:
                    # Static footprint (no growth/spill): register once
                    # from the shared size formulas so the planner and the
                    # nbytes parity test agree with the live allocation.
                    from ..obs.memory import sim_component_sizes

                    self._memory.register_components(
                        sim_component_sizes(
                            S,
                            A,
                            P,
                            walks=B,
                            walk_cap=L,
                            coverage=self._cov,
                            sample_k=self._sample_k,
                        ),
                        arrays={
                            "walk_lanes": walk,
                            "path_fps": (fp1buf, fp2buf),
                            "packed_params": params_dev,
                            "coverage_slab": params_dev,
                            "sample_slab": params_dev,
                        },
                    )
            else:
                walk, fp1buf, fp2buf, params_dev = self._loop(
                    walk, fp1buf, fp2buf, params_dev
                )
            with self._metrics.phase("readback"):
                vals = np.asarray(params_dev)
            era_dt = time.monotonic() - era_t0
            self._metrics.add_phase("device_era", era_dt)
            self._metrics.observe("era_secs", era_dt)
            self._metrics.inc("eras")
            self._metrics.inc("steps", int(vals[P_STEPS]))
            gen_prev = gen_total
            gen_total = int(vals[P_GEN])
            self._metrics.inc("states_generated", gen_total - gen_prev)
            self._state_count = gen_total
            self._max_depth = max(self._max_depth, int(vals[P_MAXD]))

            if self._cov:
                # Era coverage deltas ride the same params download
                # (layout: act[A] | prop_hits[P] | depth hist).
                base = P_LEN + 2 * P
                cov_acc = self._coverage
                cov_acc.record_action_counts(vals[base : base + A])
                for i, p in enumerate(self._tprops):
                    # Every property is evaluated on every counted state.
                    cov_acc.record_property_eval(p.name, gen_total - gen_prev)
                    cov_acc.record_property_hit(
                        p.name, int(vals[base + A + i])
                    )
                cov_acc.record_depth_counts(
                    vals[base + A + P : base + ncov]
                )

            if self._sampler is not None:
                # Sample-slab drain (same download). Device walks revisit
                # states, so re-drains of the same fingerprint are normal;
                # the sampler dedups.
                occupied = int(vals[s_base + 2])
                off = s_base + 4
                if occupied:
                    srows = np.stack(
                        [
                            vals[
                                off + (3 + s) * sk2 : off + (4 + s) * sk2
                            ]
                            for s in range(S)
                        ],
                        axis=1,
                    )
                    # exact=False: walk revisits put DUPLICATE fps in the
                    # slab, so occupied > drained means duplicates, not
                    # truncation — the exact tie cut would starve the sample.
                    self._sampler.drain_slab(
                        vals[off : off + sk2],
                        vals[off + sk2 : off + 2 * sk2],
                        vals[off + 2 * sk2 : off + 3 * sk2],
                        vals[off + (3 + S) * sk2 : off + (4 + S) * sk2],
                        occupied,
                        states=srows,
                        exact=False,
                    )
                if self._sampler.threshold_parts() != last_thresh:
                    # Tightened threshold: re-upload the params vector
                    # (everything else in it is the era's own pass-through
                    # output, so a host copy with only the T words changed
                    # is exact).
                    arr = np.array(vals)
                    t1, t2 = self._sampler.threshold_parts()
                    arr[s_base] = t1
                    arr[s_base + 1] = t2
                    last_thresh = (t1, t2)
                    params_dev = jnp.asarray(arr)

            new_bits = int(vals[P_REC])
            if new_bits != rec_bits:
                # Extract the freshly-hit walks' fingerprint paths from the
                # device buffers: the walk/length indices came with the
                # params download; the two path buffers stack into ONE read.
                both = np.asarray(jnp.stack([fp1buf, fp2buf]))
                f1 = both[0].reshape(B, L)
                f2 = both[1].reshape(B, L)
                dw = vals[P_LEN : P_LEN + P]
                dp = vals[P_LEN + P : P_LEN + 2 * P]
                for i, p in enumerate(self._tprops):
                    if not ((new_bits >> i) & 1) or p.name in self._discovery_paths:
                        continue
                    w = int(dw[i])
                    n = int(dp[i])  # plen snapshots the post-write count
                    chain = [
                        combine64(int(f1[w, k]), int(f2[w, k]))
                        for k in range(min(n, L))
                    ]
                    self._discovery_paths[p.name] = chain
                rec_bits = new_bits

            self._obs_event(
                "era",
                frontier=self._B,
                steps=int(vals[P_STEPS]),
                generated=gen_total - gen_prev,
            )
            self._flight_record(
                device_era_secs=era_dt,
                steps=int(vals[P_STEPS]),
                generated=gen_total - gen_prev,
                unique=gen_total,
                frontier=self._B,
            )
            if self._finish_matched(self._discovery_paths):
                break
            if target_gen and gen_total >= target_gen:
                break
            if self._timed_out():
                break

    # -- accessors ----------------------------------------------------------

    def unique_state_count(self) -> int:
        # Like the host simulation engine: no global visited set is kept
        # (simulation.rs:413-417).
        return self._state_count

    def discoveries(self) -> Dict[str, Path]:
        self.join()
        return {
            name: Path.from_fingerprints(self._model, chain)
            for name, chain in list(self._discovery_paths.items())
        }
