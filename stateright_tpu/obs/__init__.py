"""Unified engine observability: metrics, coverage, and run traces.

Every engine owns a `MetricsRegistry` and a `Coverage` accumulator
(created by `HostEngineBase`) and populates them through one common API —
counters, gauges, monotonic phase timers, and per-action/per-depth/
per-property coverage tallies — backing `Checker.telemetry()` and
`Checker.coverage()` uniformly across all nine engines.
`CheckerBuilder.trace(path)` additionally streams one JSONL event per
era/wave/round to disk via `TraceWriter` (`format="chrome"` swaps in the
Perfetto-loadable `ChromeTraceWriter`), and `CheckerBuilder.profile(dir)`
brackets the run with `jax.profiler` traces when the profiler is
available. `render_prometheus` serializes any telemetry snapshot in the
Prometheus text exposition format (the Explorer serves it at
``GET /metrics?format=prometheus``).

`obs/flight.py` adds the era-granularity flight recorder: per-era
``device_era`` vs ``host_gap`` wall-time split plus frontier/table/spill
counters, populated from the packed-params readback the device engines
already do (`Checker.flight()`; `CheckerBuilder.flight()` configures it).

`obs/sample.py` adds the space profiler: deterministic bottom-k
fingerprint sampling of the explored state space (identical sample set
across engines/shards/pipelining), rendered into per-field distribution
sketches, depth/action exemplars, and a packing-saturation detector
(`Checker.space_profile()`; `CheckerBuilder.sample()` configures it).

See `stateright_tpu/obs/README.md` for the consolidated metric-name
catalog, `obs/coverage.py` for coverage-count semantics, and
`obs/trace.py` for the trace event schema.
"""

from .coverage import DEPTH_CAP, Coverage
from .flight import DEFAULT_FLIGHT_CAPACITY, FlightRecorder
from .log import get_logger
from .memory import (
    Forecaster,
    MemoryLedger,
    MemoryRecorder,
    device_memory_bytes,
    format_plan,
    plan,
    recommend_engine,
)
from .sample import (
    DEFAULT_SAMPLE_K,
    DEVICE_STEP_CAP,
    NO_ACTION,
    SLAB_PAD,
    SpaceSampler,
    build_space_profile,
    detect_saturation,
    slab_capacity,
    slab_entries,
    slab_high_water,
)
from .metrics import (
    MEMORY_SERIES_LABELS,
    NETOBS_SERIES_LABELS,
    SHARD_SERIES_LABELS,
    Histogram,
    MetricsRegistry,
    render_prometheus,
)
from .netobs import (
    DEFAULT_CAUSAL_PAST_K,
    NetObs,
    as_netobs,
    assign_lamport,
    causal_order,
    causal_past,
    deployment_view,
    export_chrome_trace,
    flow_pairs,
    format_event,
)
from .spans import (
    ENGINE_STAGES,
    ERA_STAGES,
    SpanRecorder,
    attach_phase_spans,
    new_span_id,
    new_trace_id,
    profiled_phase_totals,
    reset_profiled_phase_totals,
)
from .trace import (
    ChromeTraceWriter,
    TraceWriter,
    make_trace_writer,
    start_profile,
    stop_profile,
)

__all__ = [
    "DEFAULT_CAUSAL_PAST_K",
    "DEFAULT_FLIGHT_CAPACITY",
    "DEFAULT_SAMPLE_K",
    "DEPTH_CAP",
    "DEVICE_STEP_CAP",
    "ENGINE_STAGES",
    "ERA_STAGES",
    "NO_ACTION",
    "SLAB_PAD",
    "ChromeTraceWriter",
    "Coverage",
    "SpaceSampler",
    "build_space_profile",
    "detect_saturation",
    "slab_capacity",
    "slab_entries",
    "slab_high_water",
    "FlightRecorder",
    "Forecaster",
    "Histogram",
    "MEMORY_SERIES_LABELS",
    "MemoryLedger",
    "MemoryRecorder",
    "MetricsRegistry",
    "NETOBS_SERIES_LABELS",
    "NetObs",
    "SHARD_SERIES_LABELS",
    "SpanRecorder",
    "TraceWriter",
    "as_netobs",
    "assign_lamport",
    "attach_phase_spans",
    "causal_order",
    "causal_past",
    "deployment_view",
    "device_memory_bytes",
    "export_chrome_trace",
    "flow_pairs",
    "format_event",
    "format_plan",
    "get_logger",
    "make_trace_writer",
    "new_span_id",
    "new_trace_id",
    "plan",
    "profiled_phase_totals",
    "recommend_engine",
    "render_prometheus",
    "reset_profiled_phase_totals",
    "start_profile",
    "stop_profile",
]
