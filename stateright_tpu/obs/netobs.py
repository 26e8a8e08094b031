"""Network flight recorder: live deployment observability for real actor runs.

The checker side has flight/spans/memory; this module is the
*deployment* side's equivalent lens — what actually happened on the wire
when a system ran over loopback UDP under a seeded `FaultPlan`:

  `NetObs`              per-deployment labeled runtime metrics (counters,
                        gauges, histograms in a `MetricsRegistry`),
                        populated live by both spawn engines' handler
                        hooks, by the `FaultInjector` at injection time,
                        and by the `TraceRecorder`'s send/deliver matcher
  `assign_lamport`      the deterministic causal-order reconstructor:
                        Lamport-stamps a trace's events (recomputing
                        exactly what a schema-v2 recorder wrote, and
                        backfilling v1 traces that carry no stamps)
  `causal_order`        a total-order extension of happened-before —
                        events sorted by (lc, actor, seq); a pure
                        function of the trace, so two engines that made
                        the same logical run reconstruct the same order
  `causal_past`         the last K events that happened-before a given
                        event (per-actor program order + send->deliver
                        edges, transitively) — divergence forensics
  `flow_pairs`          every (send event, deliver event) match; drops
                        never pair, duplicates pair as redeliveries
  `export_chrome_trace` Perfetto-loadable Chrome trace: one lane per
                        actor, handler slices, fault instants, and
                        ph:"s"/"f" flow arrows from each send to its
                        deliver — a faulted run opens as a message-
                        sequence diagram
  `deployment_view`     the Explorer's ``GET /deployment`` payload:
                        actor topology, per-edge delivered/fault counts,
                        and a formatted live event tail

The matching discipline shared by the recorder, the reconstructor, and
the flow exporter: a ``deliver`` event is paired with the earliest
unconsumed ``send`` event carrying the same (src, dst, canonical msg)
key — valid because the recorder writes an actor's ``send`` line before
the datagram hits the wire, so the send line always precedes its deliver
line in the file, and loopback UDP is FIFO per socket pair. A deliver
with no unconsumed send is a *redelivery* (a duplicated datagram) and
pairs with the most recently consumed send for its key.
"""

from __future__ import annotations

import json
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

from .metrics import MetricsRegistry

#: Default number of happened-before events rendered with a divergence.
DEFAULT_CAUSAL_PAST_K = 8

#: Slice duration (µs) drawn for handler events that carry no ``dur``
#: (v1 traces): wide enough for Perfetto to anchor flow arrows.
_DEFAULT_SLICE_US = 30.0


# ---------------------------------------------------------------------------
# Live per-deployment metrics.
# ---------------------------------------------------------------------------

class NetObs:
    """Per-deployment runtime metrics (see obs/README.md, "Deployment
    observability"). One instance per `spawn`; both engines call the same
    hooks, so on an identical logical run the counters are identical.

    Data sources:

      - engine handler hooks: `handler(index, kind, duration)` after
        every on_start/on_msg/on_timeout/on_random;
      - engine command dispatch: `command(index, kind)` per Out command,
        `transmit()` per datagram actually written to the wire;
      - `FaultInjector`: `fault(kind)` at decision time;
      - `TraceRecorder`'s matcher: `latency(secs)` per matched deliver
        and `mailbox(outstanding)` with per-actor in-flight depth.
    """

    def __init__(self, registry: Optional[MetricsRegistry] = None):
        self.registry = registry if registry is not None else MetricsRegistry()
        self._lock = threading.Lock()
        self._transmissions = 0
        self._delivered = 0

    # -- engine hooks --------------------------------------------------------

    def attach(self, actors, engine: str) -> None:
        """Called once per spawn with the resolved (Id, Actor) roster."""
        self.registry.set_gauge("deployment_actors", len(actors))
        self.registry.set_gauge("engine", engine)

    def handler(self, index: int, kind: str, duration: Optional[float] = None) -> None:
        """One handler execution on actor `index` (init/deliver/timeout/random)."""
        key = str(index)
        self.registry.inc_labeled("actor_handlers", key)
        if kind == "deliver":
            self.registry.inc_labeled("actor_messages_delivered", key)
            with self._lock:
                self._delivered += 1
                in_flight = self._transmissions - self._delivered
            self.registry.set_gauge("net_in_flight", max(in_flight, 0))
        elif kind == "timeout":
            self.registry.inc_labeled("actor_timer_fired", key)
        if duration is not None:
            self.registry.observe("handler_duration_secs", duration)

    def command(self, index: int, kind: str) -> None:
        """One Out command dispatched by actor `index` (send/timer_set/...)."""
        if kind == "send":
            self.registry.inc_labeled("actor_messages_sent", str(index))
        elif kind == "timer_set":
            self.registry.inc_labeled("actor_timer_set", str(index))

    def transmit(self) -> None:
        """One datagram actually written to the wire (post-injector: drops
        never transmit, duplicates transmit twice)."""
        self.registry.inc("net_transmissions")
        with self._lock:
            self._transmissions += 1
            in_flight = self._transmissions - self._delivered
        self.registry.set_gauge("net_in_flight", max(in_flight, 0))

    def fault(self, kind: str) -> None:
        """One fault-injector decision that was not a clean deliver."""
        self.registry.inc_labeled("fault_injected", kind)

    def latency(self, secs: float) -> None:
        """Send-line-to-deliver-line latency of one matched transmission."""
        self.registry.observe("delivery_latency_secs", secs)

    def mailbox(self, outstanding: Dict[int, int]) -> None:
        """Per-actor in-flight depth (sends recorded, not yet delivered)."""
        self.registry.set_gauge(
            "actor_mailbox_depth", {str(k): v for k, v in outstanding.items()}
        )

    # -- export --------------------------------------------------------------

    def snapshot(self) -> Dict[str, Any]:
        return self.registry.snapshot()


def as_netobs(netobs, default: bool = False) -> Optional[NetObs]:
    """Normalize `spawn`'s ``netobs=`` argument: ``None`` auto-creates one
    when `default` says the deployment is instrumented (recording or
    fault-injecting), ``False`` disables, ``True`` forces one, and an
    existing `NetObs` is used as-is."""
    if isinstance(netobs, NetObs):
        return netobs
    if netobs is False:
        return None
    if netobs is True:
        return NetObs()
    if netobs is None:
        return NetObs() if default else None
    raise TypeError(f"netobs must be a NetObs, True/False, or None; got {netobs!r}")


# ---------------------------------------------------------------------------
# Causal reconstruction (shared by both engines: a pure trace function).
# ---------------------------------------------------------------------------

def _msg_key(msg: Any) -> str:
    return json.dumps(msg, sort_keys=True)


def assign_lamport(events: List[dict]) -> List[dict]:
    """Lamport-stamp a trace's events: returns copies in file order with
    ``lc`` on every handler/command event, ``sent_by`` ([src actor, send
    seq]) on every matched deliver, and ``redelivery`` on duplicates.

    This recomputes exactly what a schema-v2 `TraceRecorder` stamped at
    record time (locked by tests/test_netobs.py), so v1 traces load into
    the same causal structure. Fault events pass through unstamped —
    they are link metadata, not handler occurrences."""
    clocks: Dict[int, int] = {}
    pending: Dict[Tuple[Any, Any, str], deque] = {}
    consumed: Dict[Tuple[Any, Any, str], dict] = {}
    out: List[dict] = []
    for ev in events:
        kind = ev.get("kind")
        if kind in ("fault", "meta"):
            out.append(ev)
            continue
        actor = ev.get("actor")
        stamped = dict(ev)
        stamped.pop("lc", None)
        stamped.pop("sent_by", None)
        stamped.pop("redelivery", None)
        if "cause" in ev:  # command child
            lc = clocks.get(actor, 0) + 1
            clocks[actor] = lc
            stamped["lc"] = lc
            if kind == "send":
                key = (actor, ev.get("dst"), _msg_key(ev.get("msg")))
                pending.setdefault(key, deque()).append(
                    {"actor": actor, "seq": ev.get("seq"), "lc": lc, "ts": ev.get("ts")}
                )
        else:  # handler event
            entry = None
            if kind == "deliver":
                key = (ev.get("src"), actor, _msg_key(ev.get("msg")))
                queue = pending.get(key)
                if queue:
                    entry = queue.popleft()
                    consumed[key] = entry
                else:
                    entry = consumed.get(key)
                    if entry is not None:
                        stamped["redelivery"] = True
            if entry is not None:
                lc = max(clocks.get(actor, 0), entry["lc"]) + 1
                stamped["sent_by"] = [entry["actor"], entry["seq"]]
            else:
                lc = clocks.get(actor, 0) + 1
            clocks[actor] = lc
            stamped["lc"] = lc
        out.append(stamped)
    return out


def causal_order(events: List[dict]) -> List[dict]:
    """A deterministic total-order extension of happened-before: the
    stamped handler/command events sorted by (lc, actor, seq). A pure
    function of the trace — two engines that made the same logical run
    (same seeded FaultPlan, same message chain) reconstruct byte-identical
    orders even though their wall-clock timestamps differ."""
    stamped = [ev for ev in assign_lamport(events) if "lc" in ev]
    return sorted(stamped, key=lambda ev: (ev["lc"], ev["actor"], ev["seq"]))


def causal_past(
    events: List[dict],
    actor: int,
    seq: int,
    k: int = DEFAULT_CAUSAL_PAST_K,
) -> List[dict]:
    """The last `k` events that happened-before the (actor, seq) event:
    the transitive closure of per-actor program order plus the
    send->deliver edges `assign_lamport` matched, sorted causally.
    `events` may be raw (v1) or already stamped — stamps are recomputed."""
    stamped = [ev for ev in assign_lamport(events) if "lc" in ev]
    by_ref = {(ev["actor"], ev["seq"]): ev for ev in stamped}
    per_actor: Dict[int, List[dict]] = {}
    for ev in stamped:
        per_actor.setdefault(ev["actor"], []).append(ev)
    for seqs in per_actor.values():
        seqs.sort(key=lambda ev: ev["seq"])

    target = by_ref.get((actor, seq))
    if target is None:
        return []

    def predecessors(ev: dict) -> List[dict]:
        preds = []
        lane = per_actor[ev["actor"]]
        pos = next(
            (i for i, cand in enumerate(lane) if cand["seq"] == ev["seq"]), 0
        )
        if pos > 0:
            preds.append(lane[pos - 1])
        sent_by = ev.get("sent_by")
        if sent_by is not None:
            src_ev = by_ref.get((sent_by[0], sent_by[1]))
            if src_ev is not None:
                preds.append(src_ev)
        return preds

    seen = set()
    frontier = predecessors(target)
    ancestors: List[dict] = []
    while frontier:
        ev = frontier.pop()
        ref = (ev["actor"], ev["seq"])
        if ref in seen:
            continue
        seen.add(ref)
        ancestors.append(ev)
        frontier.extend(predecessors(ev))
    ancestors.sort(key=lambda ev: (ev["lc"], ev["actor"], ev["seq"]))
    return ancestors[-k:]


def format_event(ev: dict) -> str:
    """One-line rendering of a (stamped) trace event for causal-past
    reports and the deployment view's event tail."""
    kind = ev.get("kind", "?")
    parts = [f"lc={ev.get('lc', '?')}", f"actor={ev.get('actor')}"]
    if "seq" in ev:
        parts.append(f"seq={ev['seq']}")
    parts.append(kind)
    if kind == "deliver":
        parts.append(f"src={ev.get('src')}")
        parts.append(f"msg={json.dumps(ev.get('msg'))}")
        if ev.get("redelivery"):
            parts.append("(redelivery)")
    elif kind == "send":
        parts.append(f"dst={ev.get('dst')}")
        parts.append(f"msg={json.dumps(ev.get('msg'))}")
    elif kind in ("timeout", "timer_set", "timer_cancel"):
        parts.append(f"timer={json.dumps(ev.get('timer'))}")
    elif kind == "random":
        parts.append(f"value={json.dumps(ev.get('value'))}")
    elif kind == "choose":
        parts.append(f"key={ev.get('key')}")
    elif kind == "fault":
        parts = [f"actor={ev.get('actor')}", "fault", ev.get("fault", "?"),
                 f"dst={ev.get('dst')}", f"link_seq={ev.get('link_seq')}"]
    return " ".join(str(p) for p in parts)


def flow_pairs(events: List[dict]) -> List[Tuple[dict, dict]]:
    """Every (send event, deliver event) matched pair in the trace. Each
    non-dropped transmission that was delivered contributes exactly one
    pair (duplicates pair as redeliveries of the same send); dropped
    datagrams contribute none."""
    stamped = assign_lamport(events)
    sends = {
        (ev["actor"], ev["seq"]): ev
        for ev in stamped
        if ev.get("kind") == "send"
    }
    pairs: List[Tuple[dict, dict]] = []
    for ev in stamped:
        if ev.get("kind") != "deliver":
            continue
        sent_by = ev.get("sent_by")
        if sent_by is None:
            continue
        send_ev = sends.get((sent_by[0], sent_by[1]))
        if send_ev is not None:
            pairs.append((send_ev, ev))
    return pairs


# ---------------------------------------------------------------------------
# Chrome trace export (Perfetto message-sequence diagram).
# ---------------------------------------------------------------------------

def _load(trace) -> Tuple[dict, List[dict]]:
    if isinstance(trace, (str, bytes)) or hasattr(trace, "__fspath__"):
        from ..conformance.events import load_trace  # lazy: avoids a cycle

        return load_trace(trace)
    return trace


def export_chrome_trace(trace, path: str) -> int:
    """Write a recorded deployment trace (a path or ``(meta, events)``)
    as a Chrome trace-event JSON array at `path`: one lane (tid) per
    actor, handler events as duration slices, command/fault events as
    instants, and one ``ph:"s"`` / ``ph:"f"`` flow pair per matched
    send->deliver (the arrows Perfetto draws as a sequence diagram).
    Returns the number of flow pairs emitted."""
    meta, events = _load(trace)
    stamped = assign_lamport(events)
    handler_ts = [ev.get("ts", 0.0) for ev in stamped]
    t0 = min(handler_ts) if handler_ts else 0.0

    def us(ev: dict) -> float:
        return round((ev.get("ts", t0) - t0) * 1e6, 1)

    records: List[dict] = [
        {"ph": "M", "pid": 1, "tid": 0, "name": "process_name",
         "args": {"name": f"deployment ({meta.get('engine', '?')})"}}
    ]
    for entry in meta.get("actors", []):
        records.append(
            {"ph": "M", "pid": 1, "tid": entry["index"], "name": "thread_name",
             "args": {"name": f"actor {entry['index']} ({entry['actor']}) "
                              f"{entry.get('addr', '')}"}}
        )
    for ev in stamped:
        kind = ev.get("kind")
        if kind == "fault":
            records.append(
                {"ph": "i", "s": "t", "pid": 1, "tid": ev.get("actor", 0),
                 "ts": us(ev), "cat": "fault", "name": f"fault:{ev.get('fault')}",
                 "args": {"dst": ev.get("dst"), "link_seq": ev.get("link_seq"),
                          "seed_key": ev.get("seed_key")}}
            )
            continue
        if "cause" in ev:
            records.append(
                {"ph": "i", "s": "t", "pid": 1, "tid": ev["actor"], "ts": us(ev),
                 "cat": "cmd", "name": kind,
                 "args": {"seq": ev["seq"], "lc": ev.get("lc"),
                          "dst": ev.get("dst"), "msg": ev.get("msg"),
                          "timer": ev.get("timer")}}
            )
            continue
        dur_us = max(float(ev.get("dur", 0.0)) * 1e6, _DEFAULT_SLICE_US)
        records.append(
            {"ph": "X", "pid": 1, "tid": ev["actor"], "ts": us(ev),
             "dur": round(dur_us, 1), "cat": "handler", "name": kind,
             "args": {"seq": ev["seq"], "lc": ev.get("lc"),
                      "src": ev.get("src"), "msg": ev.get("msg"),
                      "timer": ev.get("timer"), "value": ev.get("value")}}
        )
    # Flow arrows: the "s" anchors inside the sending handler's slice (the
    # send instant shares its parent's ts), the "f" inside the deliver slice.
    pairs = flow_pairs(events)
    for flow_id, (send_ev, deliver_ev) in enumerate(pairs):
        common = {"cat": "net", "name": "msg", "id": flow_id, "pid": 1}
        records.append(
            {**common, "ph": "s", "tid": send_ev["actor"], "ts": us(send_ev) + 1.0}
        )
        records.append(
            {**common, "ph": "f", "bp": "e", "tid": deliver_ev["actor"],
             "ts": us(deliver_ev) + 1.0}
        )
    with open(path, "w", encoding="utf-8") as f:
        f.write("[\n")
        f.write(",\n".join(json.dumps(r) for r in records))
        f.write("\n]\n")
    return len(pairs)


# ---------------------------------------------------------------------------
# The Explorer's GET /deployment payload.
# ---------------------------------------------------------------------------

def deployment_view(
    trace_path: Optional[str] = None,
    handle=None,
    tail: int = 40,
) -> Dict[str, Any]:
    """Actor topology + per-edge delivery/fault counts + live event tail.

    `trace_path` names a recorded (possibly still-growing — `load_trace`
    tolerates a torn final line) conformance trace; `handle` is a live
    `SpawnHandle`/`NativeSpawnHandle` whose `telemetry()` contributes the
    NetObs metric snapshot. At least one must be given."""
    if trace_path is None and handle is None:
        raise KeyError(
            "no deployment attached (start the Explorer with --trace PATH "
            "or serve(..., deployment=handle))"
        )
    view: Dict[str, Any] = {"ts": time.time()}
    if handle is not None:
        telemetry = getattr(handle, "telemetry", None)
        if callable(telemetry):
            view["telemetry"] = telemetry()
    if trace_path is None:
        return view

    meta, events = _load(trace_path)
    stamped = assign_lamport(events)
    actors = [
        {"index": entry["index"], "actor": entry["actor"],
         "addr": entry.get("addr", ""), "handlers": 0, "sent": 0, "delivered": 0}
        for entry in meta.get("actors", [])
    ]

    def actor_row(index) -> Optional[dict]:
        return actors[index] if isinstance(index, int) and 0 <= index < len(actors) else None

    edges: Dict[Tuple[Any, Any], dict] = {}

    def edge(src, dst) -> dict:
        key = (src, dst)
        if key not in edges:
            edges[key] = {"src": src, "dst": dst, "sent": 0, "delivered": 0,
                          "faults": {}}
        return edges[key]

    for ev in stamped:
        kind = ev.get("kind")
        if kind == "fault":
            counts = edge(ev.get("actor"), ev.get("dst"))["faults"]
            fault = ev.get("fault", "?")
            counts[fault] = counts.get(fault, 0) + 1
        elif "cause" in ev:
            if kind == "send":
                edge(ev["actor"], ev.get("dst"))["sent"] += 1
                row = actor_row(ev["actor"])
                if row is not None:
                    row["sent"] += 1
        else:
            row = actor_row(ev["actor"])
            if row is not None:
                row["handlers"] += 1
                if kind == "deliver":
                    row["delivered"] += 1
            if kind == "deliver":
                edge(ev.get("src"), ev["actor"])["delivered"] += 1

    view.update(
        {
            "path": str(trace_path),
            "engine": meta.get("engine"),
            "v": meta.get("v", 1),
            "faults_plan": meta.get("faults"),
            "actors": actors,
            "edges": [edges[key] for key in sorted(edges, key=str)],
            "events": len(events),
            "tail": [format_event(ev) for ev in stamped[-max(tail, 0):]],
        }
    )
    return view
