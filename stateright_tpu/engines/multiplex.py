"""Scenario multiplexing: N same-shape checks as vmapped lanes of ONE era.

The small-workload guard (engines/tpu_bfs.py, ~10k-state crossover) exists
because a solo device run pays fixed compile + dispatch overheads that
dwarf the actual search for small state spaces. A run *service* sees
thousands of such checks — overwhelmingly same-shaped (same model class +
config, different tenants) — and the fix is the BASELINE vmap insight
applied across tenants instead of across states: wrap the existing era
loop, UN-jitted (`_build_loop(..., raw=True)`), in `jax.vmap`, and run N
independent BFS instances as batch lanes of one fused device program.

Per-lane semantics are *identical to a solo run by construction*: JAX's
`lax.while_loop` batching rule iterates while ANY lane's condition holds
and select-masks finished lanes' carries through unchanged, and every
other op in the loop body is lane-local. One compiled executable, one
dispatch, one params readback for the whole batch.

Lane state is deliberately fixed-shape and small (default: chunk 256,
ring 2^13, table 2^16 — comfortable for any sub-crossover check): the
compiled program depends only on (model signature, lane count, shape
options), so ANY batch of ≤ `lanes` same-signature checks reuses it. The
engine targets single-era completion; a lane that outgrows its table/ring
budget raises with guidance to raise the capacities or run solo
(`spawn_tpu_bfs` exists precisely for those).

Deliberate non-goals (run solo instead): symmetry reduction, visitors,
timeouts, state-count targets, tracing.

Durability: `run_multiplexed(checkpoint_path=...)` snapshots every
completed batch of lanes (the per-lane result vectors + lane tables) via
the crash-safe protocol in engines/common.py; `resume_from=` skips the
batches whose snapshots verify and re-runs only the rest, so a killed
thousand-check sweep resumes instead of restarting.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ..checker import Checker, CheckerBuilder
from ..core import Expectation
from ..fingerprint import combine64, split64
from ..obs.coverage import Coverage
from ..obs.metrics import MetricsRegistry
from ..path import Path
from ..tensor import TensorModel, TensorModelAdapter
from .compiled import intern_model, model_signature
from .tpu_bfs import (
    P_COUNT,
    P_DEPTH_LIMIT,
    P_ERR,
    P_FIN_ALL,
    P_FIN_ALL_EN,
    P_FIN_ANY,
    P_GEN,
    P_GROW_LIMIT,
    P_HEAD,
    P_HIGH_WATER,
    P_LEN,
    P_MAXD,
    P_MAX_STEPS,
    P_REC,
    P_STEPS,
    P_TAKE_CAP,
    P_UNIQUE,
    _build_loop,
    _cov_len,
    _vcap,
)

__all__ = ["MultiplexLaneChecker", "run_multiplexed", "warm_lane_program"]

# Per-era step budget for a lane. Generous: small checks finish in tens to
# hundreds of steps; the budget only backstops a runaway model (a lane
# exiting on it without finishing raises below).
_LANE_MAX_STEPS = 1 << 20

# One vmapped program per (model instance, shape). Bounded like the solo
# loop caches.
_MUX_CACHE: Dict[Tuple, Tuple[TensorModel, Any]] = {}


def _build_lane_program(tm: TensorModel, props, lanes: int, chunk: int,
                        qcap: int, tcap: int, icap: int, cov: bool):
    """jit(vmap(seed + era loop)) over `lanes` independent lane instances.

    Signature (leading axis = lane):
      (qinit[N,W,icap], n_init[N], h1[N,icap], h2[N,icap],
       params[N,plen], rec_fp1[N,P], rec_fp2[N,P])
      -> (tables[N,4,tcap], params_out[N,plen])

    The seeder differs from the solo engine's in one load-bearing way:
    the init count is DATA (`n_init`, masking a fixed `icap`-wide slab),
    not a baked shape — so lanes with different init counts, and empty
    padding lanes (n_init=0, whose era condition is False immediately),
    all share the one compiled program.
    """
    key = (id(tm), lanes, chunk, qcap, tcap, icap, len(props), cov)
    cached = _MUX_CACHE.get(key)
    if cached is not None and cached[0] is tm:
        return cached[1]
    while len(_MUX_CACHE) >= 8:
        _MUX_CACHE.pop(next(iter(_MUX_CACHE)))

    import jax
    import jax.numpy as jnp

    from ..ops import visited_set as vs

    loop_fn = _build_loop(tm, props, chunk, qcap, False, cov, raw=True)
    S = tm.state_width
    W = S + 2

    def one_lane(qinit, n_init, h1, h2, params, rec_fp1, rec_fp2):
        u = jnp.uint32
        valid = jnp.arange(icap, dtype=u) < n_init
        table = vs.empty_table(tcap)
        zero = jnp.zeros(icap, dtype=u)
        table, is_new, unresolved, _ovf = vs.insert(
            table,
            jnp.where(valid, h1, u(0)),
            jnp.where(valid, h2, u(0)),
            zero,
            zero,
            valid,
        )
        # All valid init rows enqueue (duplicate inits resolve exactly like
        # the solo seeder: the table keeps one, every row still expands).
        queue = tuple(
            jnp.zeros(qcap, dtype=u)
            .at[:icap]
            .set(jnp.where(valid, qinit[i], u(0)))
            for i in range(W)
        )
        params = (
            params.at[P_HEAD].set(u(0))
            .at[P_COUNT].set(n_init)
            .at[P_UNIQUE].set(is_new.sum(dtype=u))
            .at[P_ERR].set(unresolved.sum(dtype=u))
        )
        table, queue, rec_fp1, rec_fp2, params_out = loop_fn(
            table, queue, rec_fp1, rec_fp2, params
        )
        # Split the packed key buffer back into the four flat lanes the
        # bundle/snapshot format stacks (see visited_set.empty_table).
        keys, pv1, pv2 = table
        return jnp.stack([keys[:tcap], keys[tcap:], pv1, pv2]), params_out

    program = jax.jit(jax.vmap(one_lane))
    _MUX_CACHE[key] = (tm, program)
    return program


def _shape_options(tm: TensorModel, chunk: int, qcap: int, tcap: int,
                   icap: int) -> Tuple[int, int, int, int]:
    """Validate + clamp the lane shape exactly like the solo engine."""
    if qcap & (qcap - 1):
        raise ValueError("queue_capacity must be a power of two")
    A = max(1, tm.max_actions)
    chunk = min(chunk, qcap // (2 * A))
    if chunk == 0:
        raise ValueError("queue_capacity too small for this model's fanout")
    return chunk, qcap, tcap, icap


def warm_lane_program(tm: TensorModel, *, lanes: int = 32, chunk: int = 256,
                      queue_capacity: int = 1 << 13,
                      table_capacity: int = 1 << 16,
                      init_capacity: int = 64,
                      coverage: bool = True) -> None:
    """Build (trace + lower) the vmapped lane program for this model shape
    without running anything — `CompiledCheck.warm()`'s hook."""
    chunk, qcap, tcap, icap = _shape_options(
        tm, chunk, queue_capacity, table_capacity, init_capacity
    )
    _build_lane_program(
        tm, tm.tensor_properties(), lanes, chunk, qcap, tcap, icap, coverage
    )


class MultiplexLaneChecker(Checker):
    """One lane's results, behind the standard `Checker` query API.

    Constructed done (the batch ran synchronously); `join()` is a no-op.
    Discovery paths reconstruct lazily from the lane's visited table —
    the stacked table download is shared across the whole batch.
    """

    def __init__(self, model: TensorModelAdapter, tprops, vals: np.ndarray,
                 tables, lane: int, n_init: int, cov_enabled: bool,
                 lanes: int, chunk: int, tcap: int, init_rows=None):
        self._model = model
        self._tprops = tprops
        self._tables = tables  # shared _TableBundle
        self._lane = lane
        P = len(tprops)
        A = model.tm.max_actions
        self._state_count = n_init + int(vals[P_GEN])
        self._unique = int(vals[P_UNIQUE])
        self._max_depth = int(vals[P_MAXD])
        self._discovery_fps: Dict[str, int] = {}
        rec_bits = int(vals[P_REC])
        for i, p in enumerate(tprops):
            if (rec_bits >> i) & 1:
                self._discovery_fps[p.name] = combine64(
                    int(vals[P_LEN + i]), int(vals[P_LEN + P + i])
                )
        self._paths: Optional[Dict[str, Path]] = None

        self._metrics = MetricsRegistry()
        m = self._metrics
        m.inc("eras")  # the lane's share of the batch: one fused era
        m.inc("steps", int(vals[P_STEPS]))
        m.inc("states_generated", int(vals[P_GEN]))
        m.set_gauge("chunk", chunk)
        m.set_gauge("table_capacity", tcap)
        m.set_gauge("load_factor", round(self._unique / tcap, 4))
        m.set_gauge("max_depth", self._max_depth)
        m.set_gauge("frontier_size", int(vals[P_COUNT]))
        m.set_gauge("multiplexed_lanes", lanes)

        self._coverage = Coverage(enabled=cov_enabled)
        self._coverage.register_properties(p.name for p in tprops)
        self._coverage.register_actions(
            model.tm.format_action(a) for a in range(A)
        )
        if cov_enabled:
            if init_rows is not None and len(init_rows):
                # Unique inits insert at depth 1 in the seeder, before the
                # loop histogram starts counting (same as the solo engine).
                self._coverage.record_depth(
                    1, len(np.unique(init_rows, axis=0))
                )
            base = P_LEN + 2 * P
            self._coverage.record_action_counts(vals[base : base + A])
            expanded = int(vals[base + A + P])
            for i, p in enumerate(tprops):
                self._coverage.record_property_eval(p.name, expanded)
                self._coverage.record_property_hit(
                    p.name, int(vals[base + A + i])
                )
            self._coverage.record_depth_counts(vals[base + A + P + 1 :])

    # -- Checker API ---------------------------------------------------------

    def state_count(self) -> int:
        return self._state_count

    def unique_state_count(self) -> int:
        return self._unique

    def max_depth(self) -> int:
        return self._max_depth

    def is_done(self) -> bool:
        return True

    def join(self) -> "MultiplexLaneChecker":
        return self

    def telemetry(self) -> Dict[str, Any]:
        snap = self._metrics.snapshot()
        snap["engine"] = type(self).__name__
        return snap

    def coverage(self) -> Dict[str, Any]:
        return self._coverage.snapshot()

    def discoveries(self) -> Dict[str, Path]:
        if self._paths is None:
            self._paths = {
                name: self._reconstruct(fp)
                for name, fp in self._discovery_fps.items()
            }
        return dict(self._paths)

    def _reconstruct(self, fp64: int) -> Path:
        from ..ops import visited_set as vs

        table_np = self._tables.lane(self._lane)
        chain = [fp64]
        cur = fp64
        for _ in range(10_000_000):
            h1, h2 = split64(cur)
            found, p1, p2 = vs.lookup_parent_np(table_np, h1, h2)
            if not found:
                raise RuntimeError(
                    f"fingerprint {cur} missing from lane {self._lane}'s "
                    "visited table during path reconstruction"
                )
            if p1 == 0 and p2 == 0:
                break
            cur = combine64(p1, p2)
            chain.append(cur)
        chain.reverse()
        return Path.from_fingerprints(self._model, chain)


class _TableBundle:
    """Lazily downloads the batch's stacked tables ONCE, shared by every
    lane's path reconstruction (per-lane downloads would pay a device
    round-trip each)."""

    def __init__(self, tables_dev):
        self._dev = tables_dev
        self._np: Optional[np.ndarray] = None

    def lane(self, i: int):
        return tuple(self.asarray()[i][t] for t in range(4))

    def asarray(self) -> np.ndarray:
        """The whole [lanes, 4, tcap] stack on host (downloaded once) —
        also what the batch progress snapshot persists."""
        if self._np is None:
            self._np = np.asarray(self._dev)
            self._dev = None
        return self._np


def _reject_unsupported(builder: CheckerBuilder) -> None:
    for attr, what in (
        ("symmetry_fn_", "symmetry reduction"),
        ("visitor_", "visitors"),
        ("timeout_", "timeouts"),
        ("target_state_count_", "state-count targets"),
        ("trace_path_", "tracing"),
    ):
        if getattr(builder, attr, None) is not None:
            raise ValueError(
                f"multiplexed lanes do not support {what}; run this check "
                "solo via spawn_tpu_bfs/spawn_bfs"
            )


def _batch_snapshot_path(base: str, off: int) -> str:
    return f"{base}.batch{off}.npz"


def _save_batch_snapshot(base: str, off: int, n: int, tm: TensorModel,
                         tprops, shape: dict, vals: np.ndarray,
                         tables_np: np.ndarray) -> None:
    from .common import checkpoint_meta, save_checkpoint_atomic

    meta = checkpoint_meta(
        tm, tprops, batch_off=off, batch_n=n, **shape
    )
    save_checkpoint_atomic(
        _batch_snapshot_path(base, off), meta,
        {"vals": vals, "tables": tables_np},
    )


def _load_batch_snapshot(base: str, off: int, n: int, tm: TensorModel,
                         tprops, shape: dict):
    """A verifiable snapshot of this exact batch, or None (missing or
    corrupt snapshots simply re-run the batch — progress snapshots are an
    optimization, never a correctness dependency)."""
    import os

    from .common import (
        CheckpointCorruptError,
        load_checkpoint_verified,
        validate_checkpoint_meta,
    )

    path = _batch_snapshot_path(base, off)
    if not os.path.exists(path):
        return None
    try:
        arrays, meta = load_checkpoint_verified(path)
        validate_checkpoint_meta(
            meta, tm, tprops,
            exact={
                "batch_off": off, "batch_n": n,
                "state_width": tm.state_width, **shape,
            },
        )
    except (CheckpointCorruptError, ValueError):
        return None
    return arrays


def run_multiplexed(
    builders: List[CheckerBuilder],
    *,
    lanes: int = 32,
    chunk: int = 256,
    queue_capacity: int = 1 << 13,
    table_capacity: int = 1 << 16,
    init_capacity: int = 64,
    checkpoint_path: Optional[str] = None,
    resume_from: Optional[str] = None,
) -> List[MultiplexLaneChecker]:
    """Run every builder's check as one lane of a fused vmapped era.

    All builders must carry models with the SAME shape signature
    (engines/compiled.py) — that is what makes one compiled program serve
    them all. Batches larger than `lanes` run as multiple dispatches of
    the same (padded) executable; smaller batches pad with empty lanes.
    Returns one `MultiplexLaneChecker` per builder, in order.

    `checkpoint_path` writes one crash-safe progress snapshot per
    COMPLETED batch (`<path>.batch<off>.npz`: per-lane result vectors +
    lane tables); `resume_from` rebuilds lanes from every snapshot that
    verifies and dispatches only the remaining batches.
    """
    import jax.numpy as jnp

    from ..fingerprint import hash_words_np
    from ..ops import visited_set as vs

    if not builders:
        return []
    tm, sig = intern_model(builders[0].model)
    for b in builders:
        _reject_unsupported(b)
        if model_signature(b.model) != sig:
            raise ValueError(
                "multiplexed lanes must share one model shape signature; "
                f"got {model_signature(b.model)!r} != {sig!r}"
            )
        # Lanes are the intended sub-crossover path; the small-workload
        # hint must not fire if a lane later re-runs solo off this builder.
        b.multiplex_lane_ = True
    tprops = tm.tensor_properties()
    P = len(tprops)
    if P > 32:
        raise ValueError("at most 32 tensor properties supported")
    cov = all(getattr(b, "coverage_", True) for b in builders)

    chunk, qcap, tcap, icap = _shape_options(
        tm, chunk, queue_capacity, table_capacity, init_capacity
    )
    S = tm.state_width
    A = tm.max_actions
    W = S + 2
    vcap = _vcap(A, chunk)
    ncov = _cov_len(A, P) if cov else 0
    plen = P_LEN + 2 * P + ncov

    # Shared init prep: signature-equal models generate identical inits.
    inits = np.asarray(tm.init_states_array(), dtype=np.uint32)
    init_lanes = tuple(inits[:, i] for i in range(S))
    inb = np.asarray(tm.within_boundary_lanes(np, init_lanes), dtype=bool)
    inits = inits[inb]
    n_init = len(inits)
    if n_init > icap:
        raise ValueError(
            f"{n_init} initial states exceed the lane init capacity "
            f"({icap}); raise init_capacity"
        )
    if n_init + vcap > vs.MAX_LOAD * tcap:
        raise ValueError(
            "lane table_capacity too small for this model's init count + "
            "insert batch; raise table_capacity"
        )
    init_ebits = 0
    e = 0
    for p in tprops:
        if p.expectation == Expectation.EVENTUALLY:
            init_ebits |= 1 << e
            e += 1
    h1_row = np.zeros(icap, dtype=np.uint32)
    h2_row = np.zeros(icap, dtype=np.uint32)
    if n_init:
        h1_row[:n_init], h2_row[:n_init] = hash_words_np(inits)
    qinit_row = np.zeros((W, icap), dtype=np.uint32)
    qinit_row[:S, :n_init] = inits.T
    qinit_row[S, :n_init] = init_ebits
    qinit_row[S + 1, :n_init] = 1

    def lane_params(b: CheckerBuilder) -> np.ndarray:
        t = np.zeros(plen, dtype=np.uint32)
        t[P_DEPTH_LIMIT] = (
            b.target_max_depth_ if b.target_max_depth_ is not None
            else 0xFFFFFFFF
        )
        t[P_HIGH_WATER] = qcap - chunk * A
        t[P_MAX_STEPS] = _LANE_MAX_STEPS
        t[P_TAKE_CAP] = chunk
        fin_any, fin_all, fin_all_en = b.finish_when_.device_masks(tprops)
        t[P_FIN_ANY] = fin_any
        t[P_FIN_ALL] = fin_all
        t[P_FIN_ALL_EN] = fin_all_en
        t[P_GROW_LIMIT] = max(0, int(vs.MAX_LOAD * tcap) - vcap)
        return t

    # The snapshot identity: a batch snapshot only resumes under the exact
    # lane geometry that wrote it (different shapes compile different
    # programs and lay tables out differently).
    shape = dict(lanes=lanes, chunk=chunk, qcap=qcap, tcap=tcap,
                 icap=icap, cov=cov)
    program = None  # built lazily: a fully-resumed sweep never compiles
    model = TensorModelAdapter(tm)
    out: List[MultiplexLaneChecker] = []

    for off in range(0, len(builders), lanes):
        batch = builders[off : off + lanes]
        n = len(batch)
        vals = None
        resumed = False
        batch_secs = 0.0
        if resume_from is not None:
            snap = _load_batch_snapshot(resume_from, off, n, tm, tprops, shape)
            if snap is not None:
                vals = snap["vals"]
                tables = _TableBundle(snap["tables"])
                resumed = True
        if vals is None:
            if program is None:
                program = _build_lane_program(
                    tm, tprops, lanes, chunk, qcap, tcap, icap, cov
                )
            qinit = np.zeros((lanes, W, icap), dtype=np.uint32)
            qinit[:n] = qinit_row
            n_inits = np.zeros(lanes, dtype=np.uint32)
            n_inits[:n] = n_init
            h1 = np.zeros((lanes, icap), dtype=np.uint32)
            h2 = np.zeros((lanes, icap), dtype=np.uint32)
            h1[:n] = h1_row
            h2[:n] = h2_row
            params = np.zeros((lanes, plen), dtype=np.uint32)
            for i, b in enumerate(batch):
                params[i] = lane_params(b)
            rec_fp = jnp.zeros((lanes, P), dtype=jnp.uint32)

            _era_t0 = time.monotonic()
            tables_dev, params_dev = program(
                jnp.asarray(qinit), jnp.asarray(n_inits), jnp.asarray(h1),
                jnp.asarray(h2), jnp.asarray(params), rec_fp, rec_fp,
            )
            vals = np.asarray(params_dev)  # ONE readback for the whole batch
            batch_secs = time.monotonic() - _era_t0
            tables = _TableBundle(tables_dev)

        for i, b in enumerate(batch):
            v = vals[i]
            if int(v[P_ERR]):
                raise RuntimeError(
                    f"lane {off + i}: visited-table probe budget exhausted; "
                    "raise table_capacity"
                )
            checker = MultiplexLaneChecker(
                model, tprops, v, tables, i, n_init, cov,
                lanes=lanes, chunk=chunk, tcap=tcap, init_rows=inits,
            )
            if batch_secs > 0.0:
                # Every lane shared the ONE fused dispatch+readback, so
                # each reports the batch's era wall time (the phase) and
                # its latency sample (the distribution twin).
                checker._metrics.add_phase("device_era", batch_secs)
                checker._metrics.observe("era_secs", batch_secs)
            if int(v[P_COUNT]) > 0 and not b.finish_when_.matches(
                set(checker._discovery_fps), model.properties()
            ):
                # The lane exited its era with work left and no finish —
                # it hit the ring/table/step budget. Lanes are sized for
                # sub-crossover checks; anything bigger runs solo.
                raise RuntimeError(
                    f"lane {off + i} did not complete within the lane "
                    f"budget (frontier={int(v[P_COUNT])}, "
                    f"unique={int(v[P_UNIQUE])}); raise "
                    "queue_capacity/table_capacity or run it solo via "
                    "spawn_tpu_bfs"
                )
            out.append(checker)
        # Snapshot only after every lane of the batch validated: a snapshot
        # asserts "this batch is done and correct", never partial work.
        if checkpoint_path is not None and not (
            resumed and checkpoint_path == resume_from
        ):
            _save_batch_snapshot(
                checkpoint_path, off, n, tm, tprops, shape,
                vals, tables.asarray(),
            )
    return out
