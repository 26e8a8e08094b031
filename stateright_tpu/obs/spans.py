"""The run ledger's span recorder: hierarchical traces with causal ids.

A *span* is one timed operation (`name`, wall-clock `start`/`end`,
`attributes`, point-in-time `events`) linked into a *trace* by three ids:

  ``trace_id``   one request/job end-to-end (every serve job gets one at
                 submit; it is persisted into the job journal so retries,
                 backoff waits, and crash→restart replay all land in the
                 SAME trace)
  ``span_id``    this span
  ``parent_id``  the enclosing span (None for a trace's root)

`SpanRecorder` is thread-safe and cheap: recording a span is a dict
build plus one deque append under a lock, so engines can afford one span
per era and the serve layer one per job phase. Durations use
`time.monotonic()` deltas (immune to wall clocks stepping); the epoch
anchor is `time.time()` captured once per open span, so spans from
different components align on one wall timeline.

Exports:

  - `to_dicts()` / `export_jsonl(path)` — OTel-compatible JSONL (one
    span object per line: traceId/spanId/parentSpanId camelCase ids,
    start/end in unix nanos) an OpenTelemetry collector ingests as-is;
  - `export_chrome(path)` / `chrome_events()` — Chrome trace-event
    B/E duration pairs (same format obs/trace.py writes) loadable in
    Perfetto / chrome://tracing; `ChromeTraceWriter.embed_spans` uses
    `chrome_events()` to merge request spans into an engine phase trace
    on one aligned clock;
  - `subscribe()` — a Queue receiving every COMPLETED span as a dict,
    feeding the servers' `GET /events` SSE streams.

`attach_phase_spans` turns a MetricsRegistry ``phase_ms`` dict (the
engines' existing per-phase wall-time accounting) into one child span
per phase, so an engine run shows up in a job's waterfall without the
hot loops knowing anything about tracing.

Profiler spans. While a `jax.profiler` session records (for example
under `CheckerBuilder.profile(dir)`), every phase timer
(`MetricsRegistry.phase`) also opens ``TraceAnnotation("phase:<name>")``:
the span lands on the host plane of the profile, on the same clock as
the device operations, and its seconds add to a process-wide total per
phase (`profiled_phase_totals`). With no session recording a phase
timer does nothing beyond its own ``phase_ms`` accounting.

Device scopes. The compiled programs wrap each stage in
``jax.named_scope`` (`stage_scope`): ``era/<stage>`` inside the era
bodies (`ERA_STAGES`; `ENGINE_STAGES` says which engine carries which),
`REHASH_SCOPE` around the visited set's growth and `SEED_SCOPE` around
the run seeder. Scopes are HLO metadata only; a profile viewer groups
the device operations of a `CheckerBuilder.profile(dir)` trace by them.
"""

from __future__ import annotations

import json
import os
import queue
import threading
import time
import uuid
from collections import deque
from typing import Any, Dict, Iterable, List, Optional

__all__ = [
    "ENGINE_STAGES",
    "ERA_STAGES",
    "REHASH_SCOPE",
    "SEED_SCOPE",
    "SpanRecorder",
    "attach_phase_spans",
    "new_span_id",
    "new_trace_id",
    "phase_annotation",
    "profiled_phase_totals",
    "record_profiled_phase",
    "reset_profiled_phase_totals",
    "stage_scope",
]

#: Stage scopes of the era programs (``era/<stage>``), in display order.
ERA_STAGES = (
    "ring",
    "expand",
    "compact",
    "hash",
    "claim",
    "probe",
    "canon",
    "exchange",
    "cycle",
    "record",
    "choose",
    "coverage",
    "sample",
    "epilogue",
)

#: The stages each device engine's era program carries: ``canon`` only
#: under symmetry reduction, ``coverage`` and ``sample`` only while
#: coverage and space sampling are on (both are by default).
ENGINE_STAGES = {
    "tpu_bfs": (
        "ring", "expand", "compact", "hash", "claim", "probe", "canon",
        "coverage", "sample", "epilogue",
    ),
    "sharded": (
        "ring", "expand", "compact", "hash", "claim", "exchange", "probe",
        "coverage", "sample", "epilogue",
    ),
    "tpu_simulation": (
        "hash", "cycle", "record", "expand", "choose", "coverage", "sample",
        "epilogue",
    ),
}

#: The visited set's growth (`ops.visited_set.rehash`).
REHASH_SCOPE = "table/rehash"
#: The run seeder: table and ring built on the device, inits inserted.
SEED_SCOPE = "seed"


def stage_scope(stage: str):
    """``jax.named_scope("era/<stage>")`` for one of `ERA_STAGES`."""
    if stage not in ERA_STAGES:
        raise ValueError(f"unknown era stage {stage!r}; known: {ERA_STAGES}")
    import jax

    return jax.named_scope("era/" + stage)


_annotation_cls = None
_profiled_lock = threading.Lock()
_profiled: Dict[str, List[float]] = {}


def phase_annotation(name: str):
    """An unentered ``TraceAnnotation("phase:<name>")`` while a profiler
    session records, else None (one `is_enabled()` call, nothing built)."""
    global _annotation_cls
    cls = _annotation_cls
    if cls is None:
        from jax.profiler import TraceAnnotation

        cls = _annotation_cls = TraceAnnotation
    if not cls.is_enabled():
        return None
    return cls("phase:" + name)


def record_profiled_phase(name: str, secs: float) -> None:
    """Add one profiled span of phase `name` to the process-wide totals."""
    with _profiled_lock:
        total = _profiled.get(name)
        if total is None:
            _profiled[name] = [secs, 1]
        else:
            total[0] += secs
            total[1] += 1


def profiled_phase_totals() -> Dict[str, Dict[str, float]]:
    """``{phase: {"secs": total seconds, "count": spans}}`` of every phase
    timed while a profiler session recorded, over all checkers of this
    process since the last `reset_profiled_phase_totals`."""
    with _profiled_lock:
        return {
            name: {"secs": secs, "count": int(count)}
            for name, (secs, count) in sorted(_profiled.items())
        }


def reset_profiled_phase_totals() -> None:
    with _profiled_lock:
        _profiled.clear()


def new_trace_id() -> str:
    """A fresh 32-hex-char trace id (OTel width)."""
    return uuid.uuid4().hex


def new_span_id() -> str:
    """A fresh 16-hex-char span id (OTel width)."""
    return uuid.uuid4().hex[:16]


class _OpenSpan:
    """An in-flight span handle; `finish()` (or the context manager)
    seals it into the recorder. Mutating `attributes` / `add_event`
    before the finish is allowed and lock-free (single-owner)."""

    __slots__ = (
        "recorder", "name", "trace_id", "span_id", "parent_id",
        "start", "attributes", "events", "_mono0", "_finished",
    )

    def __init__(self, recorder: "SpanRecorder", name: str, trace_id: str,
                 span_id: str, parent_id: Optional[str],
                 attributes: Optional[Dict[str, Any]]):
        self.recorder = recorder
        self.name = name
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.start = time.time()
        self.attributes = dict(attributes or {})
        self.events: List[Dict[str, Any]] = []
        self._mono0 = time.monotonic()
        self._finished = False

    def add_event(self, name: str, **attributes: Any) -> None:
        """A point-in-time annotation inside the span (OTel span event)."""
        self.events.append(
            {"name": name, "ts": time.time(), "attributes": attributes}
        )

    def finish(self, status: str = "ok", **attributes: Any) -> Dict[str, Any]:
        if self._finished:
            return {}
        self._finished = True
        self.attributes.update(attributes)
        # Monotonic duration anchored at the wall-clock start: wall steps
        # cannot produce negative or inflated span widths.
        end = self.start + (time.monotonic() - self._mono0)
        return self.recorder.record(
            self.name, start=self.start, end=end,
            trace_id=self.trace_id, span_id=self.span_id,
            parent_id=self.parent_id, attributes=self.attributes,
            events=self.events, status=status,
        )

    def __enter__(self) -> "_OpenSpan":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if exc is not None:
            self.finish(status="error", error=repr(exc))
        else:
            self.finish()


class SpanRecorder:
    """Thread-safe ledger of completed spans (bounded ring) + live feed."""

    def __init__(self, capacity: int = 8192, metrics=None):
        self._lock = threading.Lock()
        self._spans: deque = deque(maxlen=capacity)
        self._subscribers: List[queue.Queue] = []
        self._metrics = metrics

    # -- recording -----------------------------------------------------------

    def start_span(self, name: str, *, trace_id: Optional[str] = None,
                   parent_id: Optional[str] = None,
                   span_id: Optional[str] = None,
                   attributes: Optional[Dict[str, Any]] = None) -> _OpenSpan:
        """Open a span now; close it with `.finish()` or `with`."""
        return _OpenSpan(
            self, name, trace_id or new_trace_id(),
            span_id or new_span_id(), parent_id, attributes,
        )

    def record(self, name: str, *, start: float, end: float,
               trace_id: Optional[str] = None,
               span_id: Optional[str] = None,
               parent_id: Optional[str] = None,
               attributes: Optional[Dict[str, Any]] = None,
               events: Optional[List[Dict[str, Any]]] = None,
               status: str = "ok") -> Dict[str, Any]:
        """Record an already-timed span (after-the-fact spans: queue
        waits, backoff windows, journal-replayed history). Returns the
        completed span dict (also fanned out to subscribers)."""
        span = {
            "name": name,
            "trace_id": trace_id or new_trace_id(),
            "span_id": span_id or new_span_id(),
            "parent_id": parent_id,
            "start": float(start),
            "end": float(max(end, start)),
            "status": status,
        }
        if attributes:
            span["attributes"] = dict(attributes)
        if events:
            span["events"] = list(events)
        with self._lock:
            self._spans.append(span)
            subs = list(self._subscribers)
        if self._metrics is not None:
            self._metrics.inc("spans_recorded")
        for q in subs:
            try:
                q.put_nowait(dict(span))
            except queue.Full:
                pass  # a stalled SSE client must not block recording
        return span

    # -- live feed -----------------------------------------------------------

    def subscribe(self, maxsize: int = 1024) -> queue.Queue:
        """A Queue receiving every span completed from now on (dicts).
        Unsubscribe when done; a full queue drops, never blocks."""
        q: queue.Queue = queue.Queue(maxsize=maxsize)
        with self._lock:
            self._subscribers.append(q)
        return q

    def unsubscribe(self, q: queue.Queue) -> None:
        with self._lock:
            try:
                self._subscribers.remove(q)
            except ValueError:
                pass

    # -- queries -------------------------------------------------------------

    def spans(self, trace_id: Optional[str] = None) -> List[Dict[str, Any]]:
        """Completed spans, oldest first; `trace_id` filters to one trace."""
        with self._lock:
            out = list(self._spans)
        if trace_id is not None:
            out = [s for s in out if s["trace_id"] == trace_id]
        return out

    def trace(self, trace_id: str) -> List[Dict[str, Any]]:
        """One trace's spans sorted by start time (the waterfall order)."""
        return sorted(self.spans(trace_id), key=lambda s: s["start"])

    def trace_ids(self) -> List[str]:
        """Distinct trace ids in completion order (oldest first)."""
        seen: Dict[str, None] = {}
        for s in self.spans():
            seen.setdefault(s["trace_id"], None)
        return list(seen)

    def __len__(self) -> int:
        with self._lock:
            return len(self._spans)

    # -- export --------------------------------------------------------------

    def to_dicts(self, trace_id: Optional[str] = None) -> List[Dict[str, Any]]:
        """OTel-compatible span objects (ids camelCased, times in unix
        nanos) — what `export_jsonl` writes one-per-line."""
        return [_otel(s) for s in self.spans(trace_id)]

    def export_jsonl(self, path: str, trace_id: Optional[str] = None) -> int:
        """Write the ledger as OTel-compatible JSONL; returns span count."""
        rows = self.to_dicts(trace_id)
        with open(path, "w", encoding="utf-8") as fh:
            for row in rows:
                fh.write(json.dumps(row, default=repr) + "\n")
            fh.flush()
            os.fsync(fh.fileno())
        return len(rows)

    def chrome_events(
        self, trace_id: Optional[str] = None
    ) -> List[Dict[str, Any]]:
        """The ledger as Chrome trace-event records: one B/E duration
        pair per span (ts in microseconds), tracks (tid) keyed by trace
        so each request reads as one lane in Perfetto."""
        return spans_to_chrome(self.spans(trace_id))

    def export_chrome(self, path: str,
                      trace_id: Optional[str] = None) -> int:
        """Write a standalone Chrome trace-event JSON file of the ledger
        (Perfetto / chrome://tracing); returns the event count."""
        events = self.chrome_events(trace_id)
        with open(path, "w", encoding="utf-8") as fh:
            json.dump(events, fh, default=repr)
        return len(events)


def _otel(span: Dict[str, Any]) -> Dict[str, Any]:
    out = {
        "traceId": span["trace_id"],
        "spanId": span["span_id"],
        "parentSpanId": span.get("parent_id") or "",
        "name": span["name"],
        "startTimeUnixNano": int(span["start"] * 1e9),
        "endTimeUnixNano": int(span["end"] * 1e9),
        "status": {"code": "OK" if span.get("status") == "ok" else "ERROR"},
    }
    attrs = span.get("attributes") or {}
    if attrs:
        out["attributes"] = [
            {"key": k, "value": {"stringValue": str(v)}}
            for k, v in attrs.items()
        ]
    events = span.get("events") or []
    if events:
        out["events"] = [
            {
                "name": e["name"],
                "timeUnixNano": int(e.get("ts", span["start"]) * 1e9),
                "attributes": [
                    {"key": k, "value": {"stringValue": str(v)}}
                    for k, v in (e.get("attributes") or {}).items()
                ],
            }
            for e in events
        ]
    return out


def spans_to_chrome(spans: Iterable[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """Span dicts -> Chrome trace-event B/E pairs on per-trace tracks.

    Events are sorted so begins nest outermost-first and ends close
    innermost-first at equal timestamps, which is what the trace-event
    format's per-track stack discipline expects."""
    raw = []
    for s in spans:
        ts = s["start"] * 1e6
        dur = max(0.0, (s["end"] - s["start"]) * 1e6)
        tid = f"trace:{s['trace_id'][:8]}"
        args: Dict[str, Any] = {
            "trace_id": s["trace_id"],
            "span_id": s["span_id"],
        }
        if s.get("parent_id"):
            args["parent_id"] = s["parent_id"]
        for k, v in (s.get("attributes") or {}).items():
            args[k] = v
        raw.append((ts, 1, -dur, {
            "name": s["name"], "ph": "B", "ts": round(ts, 1),
            "pid": 1, "tid": tid, "args": args,
        }))
        raw.append((ts + dur, 0, -dur, {
            "name": s["name"], "ph": "E", "ts": round(ts + dur, 1),
            "pid": 1, "tid": tid,
        }))
    # Sort: time, then E before B at ties, then longer spans open first /
    # close last (the -dur key inverts for E via the tuple above).
    raw.sort(key=lambda r: (r[0], r[1], r[2] if r[1] else -r[2]))
    return [r[3] for r in raw]


def attach_phase_spans(
    recorder: SpanRecorder,
    phase_ms: Dict[str, float],
    *,
    trace_id: str,
    parent_id: Optional[str],
    end: Optional[float] = None,
    attributes: Optional[Dict[str, Any]] = None,
) -> List[Dict[str, Any]]:
    """One child span per engine phase timer (obs/metrics.py catalog).

    The metrics registry keeps cumulative per-phase wall time, not
    per-interval timestamps, so each phase renders as one span whose
    width is the phase's total milliseconds, right-aligned at `end`
    (default now). Widths are exact; only the offsets are a layout
    convention — the waterfall reads "this run spent X ms in phase P".
    """
    end = time.time() if end is None else end
    out = []
    for phase in sorted(phase_ms):
        ms = float(phase_ms[phase])
        if ms <= 0.0:
            continue
        attrs = {"phase": phase, "ms": round(ms, 3)}
        if attributes:
            attrs.update(attributes)
        out.append(recorder.record(
            f"phase:{phase}", start=end - ms / 1e3, end=end,
            trace_id=trace_id, parent_id=parent_id, attributes=attrs,
        ))
    return out
