"""The metrics registry: counters, gauges, and monotonic phase timers.

One `MetricsRegistry` per checker run (created in `HostEngineBase.__init__`)
backs `Checker.telemetry()` for every engine, replacing the old
`tpu_bfs`-only `_telemetry` dict. The API is deliberately tiny — engines hot
loops must stay hot — and every method is thread-safe (host engines mutate
from worker threads while `Checker.report()` polls from the caller's).

The full metric-name catalog — every counter, gauge, phase timer, and
histogram with its meaning, plus the flight-recorder record schema —
lives consolidated in ``stateright_tpu/obs/README.md`` (it used to be a
docstring table here, with README.md and serve/README.md both pointing
at it; one catalog now serves all three). Engines only populate the
names that exist on their architecture; absent names simply never
appear in the snapshot.
"""

from __future__ import annotations

import bisect
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

from .spans import phase_annotation, record_profiled_phase


class _PhaseTimer:
    """Context manager accumulating wall time into one phase bucket.

    While a profiler session records it is also a ``phase:<name>`` span
    of the profile, and its seconds add to the process-wide profiled
    totals (obs/spans.py)."""

    __slots__ = ("_registry", "_name", "_t0", "_span")

    def __init__(self, registry: "MetricsRegistry", name: str):
        self._registry = registry
        self._name = name

    def __enter__(self) -> "_PhaseTimer":
        self._span = phase_annotation(self._name)
        if self._span is not None:
            self._span.__enter__()
        self._t0 = time.monotonic()
        return self

    def __exit__(self, *exc) -> None:
        secs = time.monotonic() - self._t0
        self._registry.add_phase(self._name, secs)
        if self._span is not None:
            self._span.__exit__(*exc)
            record_profiled_phase(self._name, secs)


def _log_bounds(start: float, factor: float, count: int) -> Tuple[float, ...]:
    bounds = []
    edge = start
    for _ in range(count):
        bounds.append(edge)
        edge *= factor
    return tuple(bounds)


#: Default log-spaced bucket bounds (seconds): 100 µs doubling up to ~14 min.
#: 24 finite edges keep the Prometheus exposition compact while spanning
#: every latency this system produces, from one fused-era readback to a
#: deep 2pc-9 serve job with backoff retries.
DEFAULT_BOUNDS = _log_bounds(1e-4, 2.0, 24)


class Histogram:
    """Thread-safe log-spaced histogram with Prometheus semantics.

    Buckets are cumulative at export (`le` upper bounds, implicit +Inf),
    exactly the `_bucket/_sum/_count` contract scrapers expect.
    `quantile()` interpolates linearly inside the winning bucket — the
    standard Prometheus `histogram_quantile` estimate, so p99 here and
    p99 in Grafana agree."""

    __slots__ = ("_lock", "bounds", "_counts", "_sum", "_count", "_max")

    def __init__(self, bounds: Optional[Sequence[float]] = None):
        edges = tuple(sorted(bounds)) if bounds else DEFAULT_BOUNDS
        if not edges:
            raise ValueError("histogram needs at least one bucket bound")
        self._lock = threading.Lock()
        self.bounds = edges
        self._counts = [0] * (len(edges) + 1)  # +1 for the +Inf bucket
        self._sum = 0.0
        self._count = 0
        self._max = 0.0

    def observe(self, value: float) -> None:
        value = float(value)
        idx = bisect.bisect_left(self.bounds, value)
        with self._lock:
            self._counts[idx] += 1
            self._sum += value
            self._count += 1
            if value > self._max:
                self._max = value

    def merge(self, other: "Histogram") -> None:
        """Fold another histogram's observations into this one.

        Both histograms must share bucket bounds — merging across
        different bucketings would silently mis-bucket every count, so
        a mismatch raises ``ValueError`` instead. The other histogram is
        snapshotted under its own lock first, then applied under ours
        (sequentially, never nested), so concurrent observers on either
        side — or a self-merge — cannot deadlock."""
        if other.bounds != self.bounds:
            raise ValueError(
                "cannot merge histograms with different bucket bounds: "
                f"{len(self.bounds)} edges vs {len(other.bounds)}"
            )
        with other._lock:
            counts = list(other._counts)
            total, count, mx = other._sum, other._count, other._max
        with self._lock:
            for idx, n in enumerate(counts):
                self._counts[idx] += n
            self._sum += total
            self._count += count
            if mx > self._max:
                self._max = mx

    @property
    def count(self) -> int:
        with self._lock:
            return self._count

    @property
    def sum(self) -> float:
        with self._lock:
            return self._sum

    def quantile(self, q: float) -> float:
        """Estimated q-quantile (0..1), linearly interpolated within the
        winning bucket; the +Inf bucket clamps to the observed max."""
        if not 0.0 <= q <= 1.0:
            raise ValueError(f"quantile must be in [0, 1], got {q}")
        with self._lock:
            if self._count == 0:
                return 0.0
            rank = q * self._count
            cum = 0
            for idx, n in enumerate(self._counts):
                cum += n
                if cum >= rank and n:
                    if idx >= len(self.bounds):
                        return self._max
                    hi = self.bounds[idx]
                    lo = self.bounds[idx - 1] if idx else 0.0
                    frac = (rank - (cum - n)) / n
                    return min(lo + (hi - lo) * frac, self._max or hi)
            return self._max

    def buckets(self) -> List[Tuple[float, int]]:
        """Cumulative ``(le, count)`` pairs, +Inf last (Prometheus shape)."""
        with self._lock:
            out = []
            cum = 0
            for edge, n in zip(self.bounds, self._counts):
                cum += n
                out.append((edge, cum))
            out.append((float("inf"), cum + self._counts[-1]))
            return out

    def snapshot(self) -> Dict[str, Any]:
        """JSON-friendly export: count/sum/max, cumulative buckets, and
        the three operator quantiles (p50/p95/p99)."""
        buckets = self.buckets()
        with self._lock:
            count, total, mx = self._count, self._sum, self._max
        return {
            "count": count,
            "sum": round(total, 6),
            "max": round(mx, 6),
            "buckets": [
                ["+Inf" if le == float("inf") else le, n] for le, n in buckets
            ],
            "p50": round(self.quantile(0.50), 6),
            "p95": round(self.quantile(0.95), 6),
            "p99": round(self.quantile(0.99), 6),
        }


class MetricsRegistry:
    """Thread-safe counters + gauges + phase timers for one checker run."""

    def __init__(self):
        self._lock = threading.Lock()
        self._counters: Dict[str, int] = {}
        self._gauges: Dict[str, Any] = {}
        self._phase_secs: Dict[str, float] = {}
        self._phase_calls: Dict[str, int] = {}
        self._histograms: Dict[str, Histogram] = {}

    # -- counters ------------------------------------------------------------

    def inc(self, name: str, delta: int = 1) -> None:
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + int(delta)

    def get(self, name: str, default: int = 0) -> int:
        with self._lock:
            return self._counters.get(name, default)

    def inc_labeled(self, name: str, key: str, delta: int = 1) -> None:
        """Increment one series of a dict-valued counter (e.g. per-tenant
        request totals). The snapshot carries the whole dict under `name`;
        `render_prometheus(..., labels={name: "tenant"})` turns it into a
        labeled Prometheus family."""
        with self._lock:
            series = self._counters.get(name)
            if not isinstance(series, dict):
                series = {}
                self._counters[name] = series
            series[key] = series.get(key, 0) + int(delta)

    # -- gauges --------------------------------------------------------------

    def set_gauge(self, name: str, value: Any) -> None:
        with self._lock:
            self._gauges[name] = value

    # -- phase timers --------------------------------------------------------

    def phase(self, name: str) -> _PhaseTimer:
        """`with registry.phase("device_era"): ...` accumulates wall time."""
        return _PhaseTimer(self, name)

    def add_phase(self, name: str, secs: float) -> None:
        with self._lock:
            self._phase_secs[name] = self._phase_secs.get(name, 0.0) + secs
            self._phase_calls[name] = self._phase_calls.get(name, 0) + 1

    def phase_ms(self) -> Dict[str, float]:
        """Cumulative milliseconds per phase (sorted by name)."""
        with self._lock:
            return {
                k: round(v * 1000.0, 3)
                for k, v in sorted(self._phase_secs.items())
            }

    # -- histograms ----------------------------------------------------------

    def histogram(
        self, name: str, bounds: Optional[Sequence[float]] = None
    ) -> Histogram:
        """The named histogram, created on first use (catalog above)."""
        with self._lock:
            hist = self._histograms.get(name)
            if hist is None:
                hist = self._histograms[name] = Histogram(bounds)
            return hist

    def observe(self, name: str, value: float) -> None:
        """Record one sample into the named histogram."""
        self.histogram(name).observe(value)

    # -- export --------------------------------------------------------------

    def snapshot(self) -> Dict[str, Any]:
        """Flat counters + gauges, plus nested ``phase_ms`` when any phase
        has been timed and nested ``histograms`` when any sample has been
        observed. This is what `Checker.telemetry()` returns."""
        with self._lock:
            out: Dict[str, Any] = {
                k: dict(v) if isinstance(v, dict) else v
                for k, v in self._counters.items()
            }
            out.update(self._gauges)
            if self._phase_secs:
                out["phase_ms"] = {
                    k: round(v * 1000.0, 3)
                    for k, v in sorted(self._phase_secs.items())
                }
            hists = dict(self._histograms)
        if hists:
            out["histograms"] = {
                name: hists[name].snapshot() for name in sorted(hists)
            }
        return out


# -- Prometheus exposition ----------------------------------------------------

_PROM_BAD = frozenset(" .-/:")


def _prom_name(name: str, prefix: str) -> str:
    safe = "".join("_" if ch in _PROM_BAD else ch for ch in name)
    return prefix + safe


#: Dict-valued metric name -> Prometheus label key for the sharded
#: engine's per-shard series (`MetricsRegistry.inc_labeled` counters and
#: dict gauges populated by parallel/mesh.py). Merge this into the
#: ``labels=`` argument of `render_prometheus` so the per-shard series
#: render as ``stateright_shard_steps{shard="3"} 1021`` instead of being
#: skipped as non-numeric; the serve and Explorer endpoints do.
SHARD_SERIES_LABELS = {
    "shard_steps": "shard",
    "shard_states_generated": "shard",
    "shard_exchange_rows": "shard",
    "shard_frontier_rows": "shard",
    "shard_load_factor": "shard",
}

#: Dict-valued memory-ledger gauge (obs/memory.py MemoryRecorder) ->
#: Prometheus label key, so per-component residency renders as
#: ``stateright_memory_bytes{component="visited_table"} 67108864``.
#: Merge alongside SHARD_SERIES_LABELS wherever snapshots are rendered.
MEMORY_SERIES_LABELS = {
    "memory_bytes": "component",
}

#: Dict-valued deployment metric (obs/netobs.py NetObs) -> Prometheus
#: label key: the per-actor and per-fault-kind series a spawned actor
#: system populates live, rendering as e.g.
#: ``stateright_actor_messages_sent{actor="1"} 42`` and
#: ``stateright_fault_injected{kind="drop"} 3``. Merge alongside the
#: other *_SERIES_LABELS wherever deployment snapshots are rendered.
NETOBS_SERIES_LABELS = {
    "actor_handlers": "actor",
    "actor_messages_sent": "actor",
    "actor_messages_delivered": "actor",
    "actor_timer_set": "actor",
    "actor_timer_fired": "actor",
    "actor_mailbox_depth": "actor",
    "fault_injected": "kind",
    "conformance_fault_kinds": "kind",
}


def render_prometheus(
    snapshot: Dict[str, Any],
    prefix: str = "stateright_",
    labels: Dict[str, str] | None = None,
) -> str:
    """Render a telemetry snapshot (flat counters/gauges + nested
    ``phase_ms``) in the Prometheus text exposition format (v0.0.4).

    Every numeric metric becomes ``<prefix><name> <value>``; the phase
    timers flatten to ``<prefix>phase_ms{phase="<name>"}``. Snapshots
    merge counters and gauges into one namespace, so everything is
    emitted untyped; non-numeric values (the ``engine`` tag) become
    labels on an info-style gauge. ``labels`` maps the name of a
    dict-valued metric (`MetricsRegistry.inc_labeled`) to the label key
    its series render under, e.g. ``{"serve_tenant_requests": "tenant"}``
    -> ``serve_tenant_requests{tenant="acme"} 3``. Serve it from the
    Explorer via ``GET /metrics?format=prometheus`` (alias
    ``/metrics.prom``).
    """
    labels = labels or {}
    lines = []
    engine = snapshot.get("engine")
    if engine:
        name = _prom_name("engine_info", prefix)
        lines.append(f"# TYPE {name} gauge")
        lines.append(f'{name}{{engine="{engine}"}} 1')
    for key in sorted(snapshot):
        value = snapshot[key]
        if key == "phase_ms" and isinstance(value, dict):
            name = _prom_name("phase_ms", prefix)
            lines.append(f"# TYPE {name} untyped")
            for phase in sorted(value):
                lines.append(f'{name}{{phase="{phase}"}} {value[phase]}')
            continue
        if key == "histograms" and isinstance(value, dict):
            for hist_name in sorted(value):
                snap = value[hist_name]
                if not isinstance(snap, dict) or "buckets" not in snap:
                    continue
                name = _prom_name(hist_name, prefix)
                lines.append(f"# TYPE {name} histogram")
                for le, n in snap["buckets"]:
                    lines.append(f'{name}_bucket{{le="{le}"}} {n}')
                lines.append(f'{name}_sum {snap.get("sum", 0)}')
                lines.append(f'{name}_count {snap.get("count", 0)}')
            continue
        if key in labels and isinstance(value, dict):
            name = _prom_name(key, prefix)
            label = labels[key]
            lines.append(f"# TYPE {name} untyped")
            for series in sorted(value):
                v = value[series]
                if isinstance(v, bool):
                    v = int(v)
                if isinstance(v, (int, float)):
                    safe = str(series).replace("\\", "\\\\").replace('"', '\\"')
                    lines.append(f'{name}{{{label}="{safe}"}} {v}')
            continue
        if isinstance(value, bool):
            value = int(value)
        if not isinstance(value, (int, float)):
            continue
        name = _prom_name(key, prefix)
        lines.append(f"# TYPE {name} untyped")
        lines.append(f"{name} {value}")
    return "\n".join(lines) + "\n"
