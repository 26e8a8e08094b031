"""Device-memory ledger, growth forecaster, and capacity planner.

The observability stack covers the *time* axis end to end (phase timers,
flight records, span ledger) but every hard exit in the device engines —
spill at the ring high-water mark, table grow at the load limit, degraded
regrow, OOM-classified serve retries — is a *memory* event. This module
makes the memory axis first-class, in three coupled layers:

``MemoryLedger``
    Exact analytic accounting of every device allocation, registered by
    component (visited table, frontier queue, packed params, coverage
    slab, spill/refill staging, per-shard tables on the mesh) with
    shape/dtype/bytes and a bounded growth-event log. The engines
    register each buffer from the SAME size formulas the planner uses,
    and keep a live reference to the underlying arrays, so

        ledger analytic bytes == sum(unique buf.nbytes)

    is an exact, test-locked invariant (``.nbytes`` is aval metadata —
    shape x itemsize — so it stays readable even on donated buffers).

``Forecaster`` / ``MemoryRecorder``
    Fit the per-era unique-state growth curve (geometric ratio over a
    sliding window) to project eras-to-grow, eras-to-exhaustion, and the
    final table size; per-era memory records ride the existing flight
    recorder readback (zero extra device round-trips) and surface as
    ``telemetry()["memory"]``, labeled ``memory_bytes{component=...}``
    Prometheus gauges, and an early warning with a concrete
    recommendation (regrow now / expect spill / use the sharded mesh)
    that fires once per approach — it re-arms after every table growth
    or proactive reshard, so the run warns again at each new wall.

``plan()``
    Static capacity planning: predict the full device footprint from the
    model's packed-state width and engine geometry BEFORE any dispatch.
    Exposed as ``python -m stateright_tpu.obs.memory SPEC``, a ``plan``
    subcommand on the example CLIs, and enforced at serve admission
    (predicted footprint > device memory -> HTTP 413; multiplex lane
    packing right-sized by per-lane footprint).

Every device buffer in this codebase is uint32, so sizes below are in
4-byte words; host staging (the spill blocks) is tracked separately from
the device total.
"""

from __future__ import annotations

import json
import os
import sys
import threading
from typing import Any, Dict, List, Optional, Sequence, Tuple

__all__ = [
    "MemoryLedger",
    "MemoryRecorder",
    "Forecaster",
    "plan",
    "recommend_engine",
    "device_memory_bytes",
    "format_plan",
    "bfs_component_sizes",
    "sim_component_sizes",
    "mesh_component_sizes",
    "multiplex_component_sizes",
    "main",
]

WORD_BYTES = 4  # every device buffer is uint32
#: One visited-table row is keys(2) + parent_h1(1) + parent_h2(1) words.
TABLE_ROW_BYTES = 4 * WORD_BYTES
#: Early-warning horizon: warn when exhaustion projects within this many
#: eras (or headroom is already below one further table doubling).
WARN_HORIZON_ERAS = 32
#: Proactive-reshard horizon: with a device limit set and exhaustion
#: projected, the engines front-run a table growth once the next
#: doubling is forecast within this many eras (the growth lands at a
#: host-chosen era boundary instead of a forced one — see ISSUE 20).
RESHARD_HORIZON_ERAS = 8
#: A proactive reshard additionally requires the table to have consumed
#: at least this fraction of its growth trigger.  Each doubling halves
#: the fraction, so the engine stays at most one doubling ahead of real
#: occupancy instead of chasing a diverging fit era after era.
RESHARD_MIN_LOAD_FRAC = 0.5
#: Forecast projection stops once the simulated table passes this many
#: bytes with no device limit in reach — past an exbibyte the only
#: information left is "diverging", and doubling further would overflow.
_PROJECTION_CEILING = float(1 << 62)
#: Bounded growth-event log (events beyond this are counted, not kept).
MAX_EVENTS = 512

_UNSET = object()


def device_memory_bytes(default: Optional[int] = None) -> Optional[int]:
    """Best-effort device memory limit in bytes.

    ``STPU_DEVICE_MEMORY_BYTES`` wins (deterministic tests / CI); else the
    first local device's ``memory_stats()`` where the backend exposes it
    (TPU and GPU do, CPU does not); else ``default`` (no enforcement).
    """
    env = os.environ.get("STPU_DEVICE_MEMORY_BYTES")
    if env:
        try:
            return int(env)
        except ValueError:
            pass
    try:
        import jax

        dev = jax.local_devices()[0]
        stats = dev.memory_stats() if hasattr(dev, "memory_stats") else None
        if stats:
            limit = stats.get("bytes_limit") or stats.get(
                "bytes_reservable_limit"
            )
            if limit:
                return int(limit)
    except Exception:
        pass
    return default


# -- component size formulas ------------------------------------------------
#
# One definition per engine, used by BOTH the static planner and the live
# ledger registration inside the engines — predicted footprint equals
# ledger footprint by construction, and the ledger-vs-nbytes parity test
# locks the formulas to the real allocations.


def _entry(shape: Sequence[int], dtype: str = "uint32") -> Dict[str, Any]:
    n = 1
    for d in shape:
        n *= int(d)
    return {
        "shape": tuple(int(d) for d in shape),
        "dtype": dtype,
        "bytes": n * WORD_BYTES,
    }


def bfs_component_sizes(
    S: int,
    A: int,
    P: int,
    *,
    chunk: int = 8192,
    queue_capacity: int = 1 << 20,
    table_capacity: int = 1 << 22,
    coverage: bool = True,
    sample_k: int = 64,
    canon: bool = False,
) -> Dict[str, Dict[str, Any]]:
    """Device buffers of the solo BFS engine (engines/tpu_bfs.py).

    The visited table is (keys[2t] | parent_h1[t] | parent_h2[t]) = 4t
    words; the frontier ring is W = S+2 lanes (state | ebits | depth);
    the packed params vector carries P_LEN counters + 2P recorded
    fingerprint halves + the coverage tail + the sample tail (one
    buffer — the coverage and sample slabs are carved out analytically
    but share the params allocation). The sample tail is
    [T1, T2, occupied, sdrop] + (fp1|fp2|depth|action|ok) x
    slab_entries(k) words (``sample_k=0`` = sampling off). Under
    symmetry reduction (``canon``) one last word carries the dispatch's
    ``canon_rewrites``.
    """
    from ..engines.tpu_bfs import P_LEN, _cov_len

    A = max(1, int(A))
    chunk = min(int(chunk), int(queue_capacity) // (2 * A))
    W = int(S) + 2
    ncov = _cov_len(A, P) if coverage else 0
    sizes = {
        "visited_table": _entry((4 * int(table_capacity),)),
        "frontier_queue": _entry((W, int(queue_capacity))),
        "record_fps": _entry((2, int(P))),
        "packed_params": _entry((P_LEN + 2 * int(P),)),
    }
    if coverage:
        sizes["coverage_slab"] = _entry((ncov,))
    if sample_k:
        from .sample import slab_entries

        sizes["sample_slab"] = _entry((4 + 5 * slab_entries(int(sample_k)),))
    if canon:
        sizes["canon_tail"] = _entry((1,))
    return sizes


def sim_component_sizes(
    S: int,
    A: int,
    P: int,
    *,
    walks: int = 1024,
    walk_cap: int = 256,
    target_max_depth: Optional[int] = None,
    coverage: bool = True,
    sample_k: int = 64,
) -> Dict[str, Dict[str, Any]]:
    """Device buffers of the simulation engine (engines/tpu_simulation.py).

    The walk block is S+4 lanes (state | seed | ptr | ebits | frozen) x B
    walks; the path-fingerprint ring is B*L per hash half (L clamps to
    the depth target); params is P_LEN + 2P + (A + P + DEPTH_CAP)
    coverage words + the sample tail — [T1, T2, occupied, sdrop] +
    (fp1|fp2|depth|S state lanes|ok) x slab_entries(k) words (the walk
    slab carries full state rows: walks revisit states and there is no
    visited table to reconstruct them from later). Static footprint —
    no growth, no spill.
    """
    from ..engines.tpu_simulation import P_LEN
    from .coverage import DEPTH_CAP

    B = int(walks)
    L = (
        min(int(walk_cap), int(target_max_depth))
        if target_max_depth
        else int(walk_cap)
    )
    sizes = {
        "walk_lanes": _entry((int(S) + 4, B)),
        "path_fps": _entry((2, B * L)),
        "packed_params": _entry((P_LEN + 2 * int(P),)),
    }
    if coverage:
        sizes["coverage_slab"] = _entry((int(A) + int(P) + DEPTH_CAP,))
    if sample_k:
        from .sample import slab_entries

        sizes["sample_slab"] = _entry(
            (4 + (4 + int(S)) * slab_entries(int(sample_k)),)
        )
    return sizes


def mesh_component_sizes(
    S: int,
    A: int,
    P: int,
    *,
    chunk: int = 1024,
    queue_capacity_per_shard: int = 1 << 16,
    table_capacity_per_shard: int = 1 << 18,
    n_shards: int = 8,
    coverage: bool = True,
    sample_k: int = 64,
) -> Dict[str, Dict[str, Any]]:
    """Device buffers of the sharded mesh engine (parallel/mesh.py).

    Every component carries the shard dimension N: per-shard visited
    tables (keys[N,2t] | p1[N,t] | p2[N,t]), the W = S+2 queue lanes at
    [N, qcap] each, and the per-shard packed params rows (counters + a
    coverage tail of A + P + 1 + DEPTH_CAP words, psum'd on device, +
    per-shard sample tails of 4 + 4*slab_entries(k) words — fp1|fp2|
    depth|ok, un-reduced: the host unions the per-shard bottom-k).
    """
    from .coverage import DEPTH_CAP

    MESH_P_LEN = 17  # parallel/mesh.py P_LEN (pinned by the parity test)
    N = int(n_shards)
    t = int(table_capacity_per_shard)
    W = int(S) + 2
    ncov = (int(A) + int(P) + 1 + DEPTH_CAP) if coverage else 0
    sizes = {
        "visited_table": _entry((N, 4 * t)),
        "frontier_queue": _entry((W, N, int(queue_capacity_per_shard))),
        "record_fps": _entry((2, N, int(P))),
        "packed_params": _entry((N, MESH_P_LEN)),
    }
    if coverage:
        sizes["coverage_slab"] = _entry((N, ncov))
    if sample_k:
        from .sample import slab_entries

        sizes["sample_slab"] = _entry(
            (N, 4 + 4 * slab_entries(int(sample_k)))
        )
    return sizes


def multiplex_component_sizes(
    S: int,
    A: int,
    P: int,
    *,
    lanes: int = 32,
    chunk: int = 256,
    queue_capacity: int = 1 << 13,
    table_capacity: int = 1 << 16,
    init_capacity: int = 64,
    coverage: bool = True,
) -> Dict[str, Dict[str, Any]]:
    """Device buffers of one multiplexed lane batch (engines/multiplex.py).

    Everything scales linearly with the lane count: stacked [N,4,t] lane
    tables, W = S+2 queue lanes at [N, qcap], the padded init slab
    (qinit + hash rows at icap width), per-lane packed params (P_LEN +
    2P + coverage tail), and the recorded-fingerprint rows. Used for
    footprint-based lane packing, not nbytes parity (lane batches are
    transient inside one fused dispatch).
    """
    from ..engines.tpu_bfs import P_LEN, _cov_len

    A = max(1, int(A))
    chunk = min(int(chunk), int(queue_capacity) // (2 * A))
    N = int(lanes)
    W = int(S) + 2
    icap = int(init_capacity)
    ncov = _cov_len(A, P) if coverage else 0
    plen = P_LEN + 2 * int(P) + ncov
    return {
        "lane_tables": _entry((N, 4, int(table_capacity))),
        "lane_queues": _entry((N, W, int(queue_capacity))),
        "lane_params": _entry((N, plen)),
        "lane_init_slab": _entry((N, (W + 2) * icap)),
        "record_fps": _entry((2, N, int(P))),
    }


# -- the ledger -------------------------------------------------------------


def _iter_arrays(ref) -> List[Any]:
    if ref is None:
        return []
    if isinstance(ref, (tuple, list)):
        out: List[Any] = []
        for r in ref:
            out.extend(_iter_arrays(r))
        return out
    return [ref]


class MemoryLedger:
    """Per-component device/host byte accounting with a growth-event log.

    Thread-safe: the engine thread registers/updates while telemetry
    polls snapshot from serve/Explorer threads.
    """

    def __init__(self, engine: str = "engine"):
        self.engine = str(engine)
        self._lock = threading.RLock()
        # name -> {"bytes", "shape", "dtype", "kind": "device"|"host"}
        self._components: Dict[str, Dict[str, Any]] = {}
        # name -> live array (or tuple/list of arrays) backing the entry
        self._arrays: Dict[str, Any] = {}
        self._events: List[Dict[str, Any]] = []
        self._events_dropped = 0
        self._peak_bytes = 0

    def register(
        self,
        name: str,
        *,
        nbytes: Optional[int] = None,
        shape: Optional[Sequence[int]] = None,
        dtype: str = "uint32",
        array: Any = None,
        kind: str = "device",
    ) -> None:
        """Add or replace one component entry; re-registering at a new
        size appends a resize event (table growth, staging churn)."""
        if nbytes is None:
            if shape is None:
                raise ValueError(f"component {name!r} needs nbytes or shape")
            nbytes = _entry(shape)["bytes"]
        entry = {
            "bytes": int(nbytes),
            "shape": tuple(int(d) for d in shape) if shape is not None else None,
            "dtype": dtype,
            "kind": kind,
        }
        with self._lock:
            prev = self._components.get(name)
            self._components[name] = entry
            if array is not None:
                self._arrays[name] = array
            elif prev is None:
                self._arrays.pop(name, None)
            if prev is not None and prev["bytes"] != entry["bytes"]:
                self._append_event(
                    {
                        "event": "resize",
                        "component": name,
                        "from_bytes": prev["bytes"],
                        "to_bytes": entry["bytes"],
                    }
                )
            self._peak_bytes = max(self._peak_bytes, self._total_locked())

    def register_sizes(
        self,
        sizes: Dict[str, Dict[str, Any]],
        arrays: Optional[Dict[str, Any]] = None,
    ) -> None:
        """Bulk-register from a ``*_component_sizes`` dict, attaching the
        live arrays per component where the engine has them."""
        arrays = arrays or {}
        for name, entry in sizes.items():
            self.register(
                name,
                nbytes=entry["bytes"],
                shape=entry.get("shape"),
                dtype=entry.get("dtype", "uint32"),
                array=arrays.get(name),
            )

    def attach(self, name: str, array: Any) -> None:
        """Update only the live array reference behind a component (the
        engines' era loops rebind buffers every dispatch)."""
        with self._lock:
            if name in self._components:
                self._arrays[name] = array

    def event(self, kind: str, **fields: Any) -> None:
        """Append one growth-log event (grow / spill / refill /
        checkpoint_load / ...)."""
        rec = {"event": kind}
        rec.update(fields)
        with self._lock:
            self._append_event(rec)

    def _append_event(self, rec: Dict[str, Any]) -> None:
        if len(self._events) >= MAX_EVENTS:
            self._events_dropped += 1
            self._events.pop(0)
        self._events.append(rec)

    def _total_locked(self, kind: str = "device") -> int:
        return sum(
            c["bytes"] for c in self._components.values() if c["kind"] == kind
        )

    def total_bytes(self) -> int:
        """Analytic device bytes across all registered components."""
        with self._lock:
            return self._total_locked("device")

    def host_bytes(self) -> int:
        """Host-side staging bytes (spill blocks waiting for refill)."""
        with self._lock:
            return self._total_locked("host")

    def disk_bytes(self) -> int:
        """Disk-tier spill bytes (npz segments below the host budget)."""
        with self._lock:
            return self._total_locked("disk")

    def peak_bytes(self) -> int:
        with self._lock:
            return self._peak_bytes

    def component_bytes(self, name: str) -> int:
        with self._lock:
            c = self._components.get(name)
            return c["bytes"] if c else 0

    def live_nbytes(self) -> int:
        """Sum of ``.nbytes`` over the UNIQUE live arrays behind device
        components (components carved from one buffer — packed_params /
        coverage_slab — are deduplicated by identity). ``.nbytes`` is
        aval metadata, safe on donated buffers. The parity invariant:
        ``live_nbytes() == total_bytes()``."""
        seen = set()
        total = 0
        with self._lock:
            refs = [
                self._arrays.get(name)
                for name, c in self._components.items()
                if c["kind"] == "device"
            ]
        for arr in _iter_arrays(refs):
            if arr is None or id(arr) in seen:
                continue
            seen.add(id(arr))
            total += int(arr.nbytes)
        return total

    def components(self) -> Dict[str, Dict[str, Any]]:
        with self._lock:
            return {
                name: dict(entry) for name, entry in self._components.items()
            }

    def events(self) -> List[Dict[str, Any]]:
        with self._lock:
            return [dict(e) for e in self._events]

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            comps = {n: dict(c) for n, c in self._components.items()}
            return {
                "engine": self.engine,
                "components": comps,
                "total_bytes": self._total_locked("device"),
                "host_bytes": self._total_locked("host"),
                "disk_bytes": self._total_locked("disk"),
                "peak_bytes": self._peak_bytes,
                "events": [dict(e) for e in self._events],
                "events_dropped": self._events_dropped,
            }


# -- the forecaster ---------------------------------------------------------


class Forecaster:
    """Fit per-era unique-row growth; project grow/exhaustion horizons.

    The model is a damped geometric: recent deltas d_i with mean ratio
    r = mean(d_{i+1}/d_i). BFS frontiers expand geometrically until the
    wavefront saturates, then decay — both regimes are one ratio. The
    projection simulates forward era by era, doubling the table whenever
    ``unique + reserve_rows > max_load * rows`` (the engines' exact grow
    trigger), until growth dies out, the device limit is crossed, or the
    era bound is hit.
    """

    def __init__(self, window: int = 8):
        self.window = max(2, int(window))
        self._unique: List[int] = []

    def observe(self, unique: int) -> None:
        self._unique.append(int(unique))
        if len(self._unique) > self.window + 1:
            self._unique.pop(0)

    def fit(self) -> Tuple[Optional[float], Optional[int]]:
        """(ratio, last_delta), or (None, None) below 3 observations."""
        us = self._unique
        if len(us) < 3:
            return None, None
        deltas = [us[i + 1] - us[i] for i in range(len(us) - 1)]
        ratios = [
            deltas[i + 1] / deltas[i]
            for i in range(len(deltas) - 1)
            if deltas[i] > 0
        ]
        if not ratios:
            return 0.0, deltas[-1]
        r = sum(ratios) / len(ratios)
        # Clamp: a wild early ratio (tiny first deltas) must not overflow
        # the forward simulation.
        return max(0.0, min(r, 8.0)), deltas[-1]

    def forecast(
        self,
        *,
        unique: int,
        rows: int,
        max_load: float,
        reserve_rows: int,
        table_bytes: int,
        fixed_bytes: int = 0,
        device_limit: Optional[int] = None,
        max_eras: int = 4096,
    ) -> Dict[str, Any]:
        """Project forward from the current era.

        ``unique``/``rows``/``reserve_rows`` are in the grow trigger's own
        units (per-shard rows on the mesh); ``table_bytes`` is the global
        table allocation (doubles in lockstep with ``rows``) and
        ``fixed_bytes`` everything else on device.
        """
        r, d = self.fit()
        out: Dict[str, Any] = {
            "ratio": None if r is None else round(r, 4),
            "delta_rows": d,
            "eras_to_grow": None,
            "eras_to_exhaustion": None,
            "projected_unique": None,
            "projected_table_bytes": None,
            "projected_total_bytes": None,
            # Fraction of the growth trigger the CURRENT occupancy has
            # consumed (1.0 == a load-factor growth is due right now).
            # Measured, not simulated — the reshard gate keys off this.
            "load_frac": round(
                (max(0, unique) + reserve_rows)
                / max(1.0, max_load * max(1, int(rows))),
                4,
            ),
        }
        if r is None or d is None:
            return out
        u = float(max(0, unique))
        step = float(max(0, d))
        if 0.0 <= r < 1.0:
            out["projected_unique"] = int(u + (step * r / (1.0 - r) if r else 0.0))
        # The simulation runs in floats: a diverging fit (r >= 1) with no
        # device limit doubles cap_rows every era, and int arithmetic
        # would overflow the float comparison long before max_eras.
        cap_rows = float(max(1, int(rows)))
        t_bytes = float(table_bytes)
        eras_to_grow: Optional[int] = None
        eras_to_exhaustion: Optional[int] = None
        if u + reserve_rows > max_load * cap_rows:
            eras_to_grow = 0
        for era in range(1, int(max_eras) + 1):
            u += step
            step *= r
            grew = False
            while u + reserve_rows > max_load * cap_rows:
                cap_rows *= 2
                t_bytes *= 2
                grew = True
                if eras_to_grow is None:
                    eras_to_grow = era
                if (
                    device_limit is not None
                    and fixed_bytes + t_bytes > device_limit
                ):
                    eras_to_exhaustion = era
                    break
            if eras_to_exhaustion is not None:
                break
            if step < 1.0 and not grew:
                break  # growth died out before any limit
            if t_bytes > _PROJECTION_CEILING:
                break  # diverging with no limit in reach; enough signal
        out["eras_to_grow"] = eras_to_grow
        out["eras_to_exhaustion"] = eras_to_exhaustion
        out["projected_table_bytes"] = int(t_bytes)
        out["projected_total_bytes"] = int(fixed_bytes + t_bytes)
        return out


# -- the engine-facing recorder ---------------------------------------------


class MemoryRecorder:
    """Ledger + forecaster + gauges + once-per-approach warning, as one
    object the engines feed at their existing once-per-era readback."""

    def __init__(
        self,
        engine: str = "engine",
        metrics=None,
        device_limit_bytes=_UNSET,
    ):
        self.ledger = MemoryLedger(engine)
        self.forecaster = Forecaster()
        self._metrics = metrics
        self.device_limit_bytes = (
            device_memory_bytes()
            if device_limit_bytes is _UNSET
            else device_limit_bytes
        )
        # Table-growth geometry, set by engines with a growable table:
        # {"rows", "max_load", "reserve_rows"} in the grow trigger's units.
        self._geometry: Optional[Dict[str, Any]] = None
        self._eras = 0
        self._warning: Optional[str] = None
        self._last_forecast: Dict[str, Any] = {}
        self._last_record: Dict[str, Any] = {}

    # -- registration passthroughs (engine call sites stay one-liners) --

    def register_components(self, sizes, arrays=None) -> None:
        self.ledger.register_sizes(sizes, arrays)

    def attach(self, name: str, array: Any) -> None:
        self.ledger.attach(name, array)

    def set_geometry(
        self, *, rows: int, max_load: float, reserve_rows: int
    ) -> None:
        prev = self._geometry
        self._geometry = {
            "rows": int(rows),
            "max_load": float(max_load),
            "reserve_rows": int(reserve_rows),
        }
        # A growth/reshard changed the wall the warning was about: re-arm
        # it so a second approach to the (new) wall warns again instead
        # of staying silent behind the one-shot latch.
        if prev is not None and int(rows) > prev["rows"]:
            self.rearm_warning()

    def staging(self, nbytes: int, event: Optional[str] = None, **fields) -> None:
        """Update the host spill-staging component; optionally log the
        spill/refill event that moved it."""
        self.ledger.register("spill_staging", nbytes=int(nbytes), kind="host")
        if event:
            self.ledger.event(event, host_bytes=int(nbytes), **fields)

    def event(self, kind: str, **fields) -> None:
        self.ledger.event(kind, **fields)

    @property
    def warning(self) -> Optional[str]:
        return self._warning

    def rearm_warning(self) -> None:
        """Clear a fired memory-pressure warning so the NEXT approach to
        the wall warns again (called after growth/reshard events — via
        ``set_geometry`` — and by the engines' proactive reshard)."""
        if self._warning is None:
            return
        self._warning = None
        self.ledger.event("memory_warning_rearmed")
        if self._metrics is not None:
            self._metrics.set_gauge("memory_warning", 0)

    def last_forecast(self) -> Dict[str, Any]:
        """The most recent ``on_era`` forecast (empty before the fit has
        enough observations) — the engines' proactive-reshard trigger."""
        return dict(self._last_forecast)

    # -- the per-era hook ------------------------------------------------

    def on_era(
        self,
        *,
        unique: int = 0,
        load_factor: float = 0.0,
        grow_rows: Optional[int] = None,
    ) -> Dict[str, Any]:
        """Called once per era at the readback; returns the compact memory
        record that rides the flight record. ``grow_rows`` is the row
        count the engine's grow trigger actually compares (max per-shard
        unique on the mesh); defaults to ``unique``."""
        self._eras += 1
        rows_now = int(grow_rows if grow_rows is not None else unique)
        self.forecaster.observe(rows_now)
        led = self.ledger
        total = led.total_bytes()
        host = led.host_bytes()
        limit = self.device_limit_bytes
        headroom = (limit - total) if limit is not None else None
        fc: Dict[str, Any] = {}
        if self._geometry is not None:
            g = self._geometry
            table_bytes = led.component_bytes("visited_table")
            fc = self.forecaster.forecast(
                unique=rows_now,
                rows=g["rows"],
                max_load=g["max_load"],
                reserve_rows=g["reserve_rows"],
                table_bytes=table_bytes,
                fixed_bytes=total - table_bytes,
                device_limit=limit,
            )
            self._last_forecast = fc
        self._maybe_warn(total, headroom, fc)
        rec = {
            "total_bytes": total,
            "host_bytes": host,
            "by_component": {
                name: c["bytes"]
                for name, c in led.components().items()
                if c["kind"] == "device"
            },
            "load_factor": float(load_factor),
        }
        if headroom is not None:
            rec["headroom_bytes"] = headroom
        if fc.get("eras_to_grow") is not None:
            rec["eras_to_grow"] = fc["eras_to_grow"]
        if fc.get("eras_to_exhaustion") is not None:
            rec["eras_to_exhaustion"] = fc["eras_to_exhaustion"]
        self._last_record = rec
        m = self._metrics
        if m is not None:
            m.set_gauge("memory_bytes", dict(rec["by_component"]))
            m.set_gauge("memory_total_bytes", total)
            m.set_gauge("memory_host_bytes", host)
            m.set_gauge("memory_peak_bytes", led.peak_bytes())
            if headroom is not None:
                m.set_gauge("memory_headroom_bytes", headroom)
            m.set_gauge(
                "memory_eta_exhaustion_eras",
                fc.get("eras_to_exhaustion")
                if fc.get("eras_to_exhaustion") is not None
                else -1,
            )
            m.set_gauge("memory_warning", 1 if self._warning else 0)
        return rec

    def _maybe_warn(
        self,
        total: int,
        headroom: Optional[int],
        fc: Dict[str, Any],
    ) -> None:
        if self._warning is not None or headroom is None:
            return
        eta = fc.get("eras_to_exhaustion")
        projected = fc.get("projected_total_bytes")
        limit = self.device_limit_bytes
        # One more table doubling is the next allocation the engine will
        # attempt; no room for it (or a projected exhaustion inside the
        # horizon) is the warn condition.
        table_bytes = self.ledger.component_bytes("visited_table")
        imminent = table_bytes > 0 and headroom < table_bytes
        horizon = eta is not None and eta <= WARN_HORIZON_ERAS
        over = projected is not None and limit is not None and projected > limit
        if not (imminent or horizon or over):
            return
        if over or horizon:
            if self.ledger.engine in ("ShardedBfsChecker",):
                rec = "expect spill past the device (out-of-core tiering)"
            else:
                rec = "use the sharded mesh (spawn_sharded_bfs)"
        else:
            rec = (
                "regrow now (reduce table_capacity or pre-size it: the next "
                "doubling will not fit)"
            )
        eta_s = f" exhaustion in ~{eta} eras;" if eta is not None else ""
        self._warning = (
            f"device memory pressure: {_fmt_bytes(total)} resident, "
            f"{_fmt_bytes(headroom)} headroom;{eta_s} recommendation: {rec}"
        )
        try:
            from .log import get_logger

            get_logger("obs.memory").warning(self._warning)
        except Exception:
            pass

    def snapshot(self) -> Dict[str, Any]:
        """``telemetry()["memory"]``: ledger snapshot + forecast + the
        one-shot warning (when fired)."""
        snap = self.ledger.snapshot()
        snap["eras"] = self._eras
        snap["live_nbytes"] = self.ledger.live_nbytes()
        if self.device_limit_bytes is not None:
            snap["device_limit_bytes"] = self.device_limit_bytes
            snap["headroom_bytes"] = self.device_limit_bytes - snap["total_bytes"]
        if self._last_forecast:
            snap["forecast"] = dict(self._last_forecast)
        if self._warning:
            snap["warning"] = self._warning
        return snap


# -- the capacity planner ---------------------------------------------------

_ENGINE_ALIASES = {
    "tpu_bfs": "tpu_bfs",
    "bfs": "tpu_bfs",
    "device": "tpu_bfs",
    "solo": "tpu_bfs",
    "tpu_simulation": "tpu_simulation",
    "simulation": "tpu_simulation",
    "sim": "tpu_simulation",
    "sharded": "sharded",
    "mesh": "sharded",
    "tpu_sharded_bfs": "sharded",
    "multiplex": "multiplex",
    "lanes": "multiplex",
}


def _tensor_model(model):
    from ..tensor import TensorModel, TensorModelAdapter

    if isinstance(model, TensorModelAdapter):
        return model.tm
    if isinstance(model, TensorModel):
        return model
    tm = getattr(model, "tm", None)
    if tm is not None and isinstance(tm, TensorModel):
        return tm
    raise TypeError(
        f"plan() needs a TensorModel (or its adapter), got "
        f"{type(model).__name__}; host-only models have no device footprint"
    )


def plan(
    model,
    *,
    engine: str = "tpu_bfs",
    chunk: Optional[int] = None,
    queue_capacity: Optional[int] = None,
    table_capacity: Optional[int] = None,
    walks: Optional[int] = None,
    walk_cap: Optional[int] = None,
    lanes: Optional[int] = None,
    init_capacity: Optional[int] = None,
    n_shards: Optional[int] = None,
    coverage: bool = True,
    sample_k: int = 64,
    device_limit_bytes=_UNSET,
) -> Dict[str, Any]:
    """Predict the full device footprint for ``model`` on ``engine``
    BEFORE any dispatch, from the model's packed-state width and the
    engine geometry (engine defaults where not given). Returns the plan
    dict: per-component sizes, total bytes, and — where a device limit
    is known — fit verdict and headroom.
    """
    tm = _tensor_model(model)
    S = int(tm.state_width)
    A = int(tm.max_actions)
    P = len(tm.tensor_properties())
    kind = _ENGINE_ALIASES.get(str(engine).lower())
    if kind is None:
        raise ValueError(
            f"unknown engine {engine!r}; one of "
            f"{sorted(set(_ENGINE_ALIASES.values()))}"
        )
    limit = (
        device_memory_bytes()
        if device_limit_bytes is _UNSET
        else device_limit_bytes
    )
    if kind == "tpu_bfs":
        geometry = {
            "chunk": chunk if chunk is not None else 8192,
            "queue_capacity": (
                queue_capacity if queue_capacity is not None else 1 << 20
            ),
            "table_capacity": (
                table_capacity if table_capacity is not None else 1 << 22
            ),
        }
        sizes = bfs_component_sizes(
            S, A, P, coverage=coverage, sample_k=sample_k, **geometry
        )
    elif kind == "tpu_simulation":
        geometry = {
            "walks": walks if walks is not None else 1024,
            "walk_cap": walk_cap if walk_cap is not None else 256,
        }
        sizes = sim_component_sizes(
            S, A, P, coverage=coverage, sample_k=sample_k, **geometry
        )
    elif kind == "sharded":
        geometry = {
            "chunk": chunk if chunk is not None else 1024,
            "queue_capacity_per_shard": (
                queue_capacity if queue_capacity is not None else 1 << 16
            ),
            "table_capacity_per_shard": (
                table_capacity if table_capacity is not None else 1 << 18
            ),
            "n_shards": n_shards if n_shards is not None else 8,
        }
        sizes = mesh_component_sizes(
            S, A, P, coverage=coverage, sample_k=sample_k, **geometry
        )
    else:  # multiplex
        geometry = {
            "lanes": lanes if lanes is not None else 32,
            "chunk": chunk if chunk is not None else 256,
            "queue_capacity": (
                queue_capacity if queue_capacity is not None else 1 << 13
            ),
            "table_capacity": (
                table_capacity if table_capacity is not None else 1 << 16
            ),
            "init_capacity": init_capacity if init_capacity is not None else 64,
        }
        sizes = multiplex_component_sizes(S, A, P, coverage=coverage, **geometry)
    total = sum(e["bytes"] for e in sizes.values())
    result: Dict[str, Any] = {
        "engine": kind,
        "model": type(tm).__name__,
        "state_width": S,
        "max_actions": A,
        "properties": P,
        "coverage": bool(coverage),
        "sample_k": int(sample_k) if kind != "multiplex" else 0,
        "geometry": geometry,
        "components": sizes,
        "total_bytes": total,
        "device_limit_bytes": limit,
        "fits": (total <= limit) if limit is not None else None,
        "headroom_bytes": (limit - total) if limit is not None else None,
    }
    if kind == "multiplex":
        result["per_lane_bytes"] = total // max(1, geometry["lanes"])
    return result


def recommend_engine(
    model, device_limit_bytes=_UNSET, exclude: Sequence[str] = ()
) -> Optional[str]:
    """The first engine (at default geometry) whose predicted footprint
    fits the device limit, in escalation order; None when nothing fits
    or no limit is known."""
    for engine in ("tpu_bfs", "sharded", "tpu_simulation"):
        if engine in exclude:
            continue
        p = plan(model, engine=engine, device_limit_bytes=device_limit_bytes)
        if p["fits"]:
            return engine
    return None


def max_lanes_for_budget(
    model,
    limit_bytes: Optional[int],
    *,
    lanes: int = 32,
    safety: float = 0.9,
    **geometry,
) -> int:
    """Footprint-based lane packing for the multiplex engine: the largest
    lane count whose batch footprint stays under ``safety * limit``.
    Returns ``lanes`` unchanged when no limit is known; always >= 1 (a
    single lane that does not fit is the admission gate's problem)."""
    if limit_bytes is None:
        return int(lanes)
    p = plan(
        model,
        engine="multiplex",
        lanes=lanes,
        device_limit_bytes=limit_bytes,
        **geometry,
    )
    per_lane = max(1, p["per_lane_bytes"])
    fit = int((limit_bytes * safety) // per_lane)
    return max(1, min(int(lanes), fit))


# -- rendering + CLI --------------------------------------------------------


def _fmt_bytes(n: Optional[int]) -> str:
    if n is None:
        return "?"
    v = float(n)
    sign = "-" if v < 0 else ""
    v = abs(v)
    for unit in ("B", "KiB", "MiB", "GiB"):
        if v < 1024 or unit == "GiB":
            return (
                f"{sign}{v:.0f} {unit}"
                if unit == "B"
                else f"{sign}{v:.1f} {unit}"
            )
        v /= 1024
    return f"{sign}{v:.1f} GiB"


def format_plan(p: Dict[str, Any]) -> str:
    """Human-readable rendering of a ``plan()`` dict (the CLI output)."""
    lines = [
        f"capacity plan · engine={p['engine']} · model={p['model']}",
        (
            f"  state_width={p['state_width']} words  "
            f"max_actions={p['max_actions']}  properties={p['properties']}  "
            f"coverage={'on' if p['coverage'] else 'off'}"
        ),
        "  geometry: "
        + " ".join(f"{k}={v}" for k, v in p["geometry"].items()),
        f"  {'component':<18} {'shape':<22} {'bytes':>14}",
    ]
    for name, e in p["components"].items():
        shape = "x".join(str(d) for d in e["shape"]) if e.get("shape") else "-"
        lines.append(
            f"  {name:<18} {shape:<22} {_fmt_bytes(e['bytes']):>14}"
        )
    lines.append(f"  {'total':<18} {'':<22} {_fmt_bytes(p['total_bytes']):>14}")
    if p.get("per_lane_bytes") is not None:
        lines.append(f"  per-lane footprint: {_fmt_bytes(p['per_lane_bytes'])}")
    limit = p.get("device_limit_bytes")
    if limit is not None:
        verdict = "fits" if p["fits"] else "DOES NOT FIT"
        lines.append(
            f"  device limit {_fmt_bytes(limit)}: {verdict} "
            f"(headroom {_fmt_bytes(p['headroom_bytes'])})"
        )
    else:
        lines.append(
            "  device limit unknown (set STPU_DEVICE_MEMORY_BYTES or run "
            "on a backend with memory_stats)"
        )
    return "\n".join(lines)


def main(argv: Optional[List[str]] = None) -> int:
    """``python -m stateright_tpu.obs.memory SPEC [--engine E] ...``:
    static capacity planning from the command line. Exit 0 = fits (or no
    limit known), 3 = predicted footprint exceeds the device limit."""
    import argparse

    parser = argparse.ArgumentParser(
        prog="python -m stateright_tpu.obs.memory",
        description=(
            "predict a model's device memory footprint before any dispatch"
        ),
    )
    parser.add_argument(
        "model", help="bundled shorthand (2pc:7) or pkg.module:Factory:ARGS"
    )
    parser.add_argument(
        "--engine",
        default="tpu_bfs",
        help="tpu_bfs | tpu_simulation | sharded | multiplex (default tpu_bfs)",
    )
    parser.add_argument("--chunk", type=int, default=None)
    parser.add_argument("--queue-capacity", type=int, default=None)
    parser.add_argument("--table-capacity", type=int, default=None)
    parser.add_argument("--walks", type=int, default=None)
    parser.add_argument("--walk-cap", type=int, default=None)
    parser.add_argument("--lanes", type=int, default=None)
    parser.add_argument("--shards", type=int, default=None)
    parser.add_argument(
        "--no-coverage", action="store_true", help="plan without coverage slabs"
    )
    parser.add_argument(
        "--sample-k",
        type=int,
        default=64,
        help="bottom-k sample size the run will use (0 = sampling off; "
        "default matches CheckerBuilder.sample())",
    )
    parser.add_argument(
        "--limit-bytes",
        type=int,
        default=None,
        help="override the detected device memory limit",
    )
    parser.add_argument("--json", action="store_true")
    args = parser.parse_args(argv)

    from ..analysis.__main__ import resolve_model

    model = resolve_model(args.model)
    kw: Dict[str, Any] = dict(
        engine=args.engine,
        chunk=args.chunk,
        queue_capacity=args.queue_capacity,
        table_capacity=args.table_capacity,
        walks=args.walks,
        walk_cap=args.walk_cap,
        lanes=args.lanes,
        n_shards=args.shards,
        coverage=not args.no_coverage,
        sample_k=max(0, args.sample_k),
    )
    if args.limit_bytes is not None:
        kw["device_limit_bytes"] = args.limit_bytes
    try:
        p = plan(model, **kw)
    except (TypeError, ValueError) as e:
        print(str(e), file=sys.stderr)
        return 2
    if args.json:
        print(json.dumps(p, indent=2, default=list))
    else:
        print(format_plan(p))
    if p["fits"] is False:
        alt = recommend_engine(
            model,
            device_limit_bytes=p["device_limit_bytes"],
            exclude=(p["engine"],),
        )
        if alt:
            print(f"  recommended alternative: --engine {alt}")
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())
