"""Network flight recorder tests (obs/netobs.py): live per-actor metrics,
Lamport causal reconstruction, Chrome flow export, the /deployment view,
and schema v1/v2 compatibility.

Ports here live in the 43100-43199 range (test_conformance.py uses
43000-43099, test_spawn.py 42000-42020, the demos/CI 46xxx).

Cross-engine determinism uses a dedicated ping-pong pair whose every
application payload is unique: a seeded duplicate-only FaultPlan then
preserves per-socket FIFO, so the whole logical run — causal order,
counters, fault schedule — is identical across engines and across runs.
(The counter demo's idempotent re-replies emit byte-identical payloads,
which makes duplicate matching ambiguous under thread interleaving —
correct but not canonical, so it is not used for the identity test.)
"""

import collections
import fcntl
import json
import time
from dataclasses import dataclass

import pytest

from examples.increment import record_counter_demo
from stateright_tpu.actor import Actor, Id, Out
from stateright_tpu.conformance import FaultPlan, check_trace, load_trace
from stateright_tpu.obs.metrics import (
    NETOBS_SERIES_LABELS,
    render_prometheus,
)
from stateright_tpu.obs.netobs import (
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


def _engines():
    from stateright_tpu.native import runtime as native_runtime

    engines = ["python"]
    if native_runtime.is_available():
        engines.append("native")
    return engines


# -- deterministic ping-pong workload ----------------------------------------


@dataclass(frozen=True)
class Ping:
    n: int


@dataclass(frozen=True)
class Pong:
    n: int
    hits: int


class EchoServer(Actor):
    """Replies to every delivered Ping — including duplicates — with a
    Pong carrying a delivery counter, so every send payload is unique."""

    def name(self):
        return "EchoServer"

    def on_start(self, id: Id, out: Out):
        return 0

    def on_msg(self, id: Id, state, src: Id, msg, out: Out):
        if not isinstance(msg, Ping):
            return None
        hits = state + 1
        out.send(src, Pong(msg.n, hits))
        return hits


@dataclass(frozen=True)
class PingState:
    awaiting: int
    done: int


class PingClient(Actor):
    def __init__(self, server_id, max_ops: int):
        self.server_id = Id(server_id)
        self.max_ops = max_ops

    def name(self):
        return "PingClient"

    def on_start(self, id: Id, out: Out):
        out.set_timer("retry", (60.0, 60.0))  # never fires in-test
        out.send(self.server_id, Ping(1))
        return PingState(awaiting=1, done=0)

    def on_msg(self, id: Id, state, src: Id, msg, out: Out):
        if not isinstance(msg, Pong) or msg.n != state.awaiting:
            return None  # duplicate/stale Pong
        done = state.done + 1
        if done >= self.max_ops:
            return PingState(awaiting=0, done=done)
        out.send(self.server_id, Ping(done + 1))
        return PingState(awaiting=done + 1, done=done)

    def on_timeout(self, id: Id, state, timer, out: Out):
        out.set_timer("retry", (60.0, 60.0))
        return None


# Duplicate-only: drops would stall the chain, delay/reorder would break
# the per-socket FIFO the deterministic matching relies on.
PLAN = FaultPlan(seed=11, duplicate=0.35)
MAX_OPS = 12
PORT = 43100  # shared by every run: the plan's RNG keys embed the ports


def _record_pingpong(path, engine):
    from stateright_tpu.actor.spawn import (
        json_serializer,
        make_json_deserializer,
        spawn,
    )

    ids = [Id.from_addr("127.0.0.1", PORT + i) for i in range(2)]
    actors = [
        (ids[0], EchoServer()),
        (ids[1], PingClient(ids[0], max_ops=MAX_OPS)),
    ]
    nob = NetObs()
    handle = spawn(
        json_serializer,
        make_json_deserializer(Ping, Pong),
        actors,
        background=True,
        engine=engine,
        record=str(path),
        faults=PLAN,
        netobs=nob,
    )
    deadline = time.monotonic() + 8.0
    while time.monotonic() < deadline:
        if getattr(handle.state(ids[1]), "done", 0) >= MAX_OPS:
            break
        time.sleep(0.01)
    time.sleep(0.2)  # let straggler duplicates land
    handle.shutdown()
    return nob.snapshot()


def _canonical(events):
    """The engine-independent projection: causal order with the causal
    fields only (wall-clock ts/dur excluded)."""
    return [
        (
            ev["lc"],
            ev["actor"],
            ev["seq"],
            ev["kind"],
            tuple(ev.get("sent_by") or ()),
            bool(ev.get("redelivery")),
            json.dumps(ev.get("msg"), sort_keys=True),
        )
        for ev in causal_order(events)
    ]


def _counterish(snapshot):
    return {
        k: v
        for k, v in snapshot.items()
        if k.startswith(("actor_", "fault_", "net_"))
        and not k.endswith("_secs")
    }


@pytest.fixture(scope="module")
def engine_runs(tmp_path_factory):
    """One seeded faulted ping-pong run per available engine, plus a
    second python run for run-to-run determinism."""
    tmp = tmp_path_factory.mktemp("netobs")
    runs = {}
    # Every run binds PORT, and pytest-xdist workers that share this module
    # each build the fixture: a lock in the session's shared temp root keeps
    # their runs apart.
    lock_path = tmp_path_factory.getbasetemp().parent / "netobs-port.lock"
    with open(lock_path, "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        for tag, engine in [("python", "python"), ("python2", "python")] + [
            (e, e) for e in _engines() if e != "python"
        ]:
            path = tmp / f"{tag}.jsonl"
            snapshot = _record_pingpong(path, engine)
            meta, events = load_trace(str(path))
            runs[tag] = (str(path), meta, events, snapshot)
    return runs


def test_causal_order_identical_across_engines_and_runs(engine_runs):
    _, _, base_events, base_snap = engine_runs["python"]
    base = _canonical(base_events)
    assert len(base) > 2 * MAX_OPS  # the run actually did work
    for tag, (_, _, events, snapshot) in engine_runs.items():
        assert _canonical(events) == base, f"{tag} causal order differs"
        assert _counterish(snapshot) == _counterish(base_snap), (
            f"{tag} counters differ"
        )


def test_fault_counters_match_trace_fault_lines(engine_runs):
    for tag, (_, _, events, snapshot) in engine_runs.items():
        recorded = collections.Counter(
            ev["fault"] for ev in events if ev["kind"] == "fault"
        )
        assert dict(recorded) == snapshot.get("fault_injected", {}), tag
        assert recorded, "the seeded plan injected no faults"


def test_recorded_stamps_equal_offline_reconstruction(engine_runs):
    """The recorder's live v2 stamps are exactly what assign_lamport
    recomputes offline — one matching discipline, two implementations."""
    for tag, (_, _, events, _snap) in engine_runs.items():
        recomputed = assign_lamport(events)
        for orig, new in zip(events, recomputed):
            assert orig.get("lc") == new.get("lc"), tag
            assert orig.get("sent_by") == new.get("sent_by"), tag
            assert bool(orig.get("redelivery")) == bool(
                new.get("redelivery")
            ), tag


def test_meta_carries_schema_v2_and_plan(engine_runs):
    path, meta, _events, _snap = engine_runs["python"]
    assert meta["v"] == 2
    assert meta["faults"]["seed"] == PLAN.seed
    assert meta["faults"]["duplicate"] == PLAN.duplicate
    plan = FaultPlan.from_meta(meta)
    assert plan == PLAN


def test_fault_lines_carry_replayable_seed_keys(engine_runs):
    """record_fault's seed_key + the meta plan replay the schedule from
    the trace alone: decide() on the recorded link/seq reproduces the
    recorded fault kind."""
    _path, meta, events, _snap = engine_runs["python"]
    plan = FaultPlan.from_meta(meta)
    roster = {entry["index"]: entry for entry in meta["actors"]}

    def id_of(index):
        ip, _, port = roster[index]["addr"].partition(":")
        return int(Id.from_addr(ip, int(port)))

    faults = [ev for ev in events if ev["kind"] == "fault"]
    assert faults
    for ev in faults:
        src, dst = id_of(ev["actor"]), id_of(ev["dst"])
        assert ev["seed_key"] == f"{plan.seed}|{src}|{dst}|{ev['link_seq']}"
        assert plan.decide(src, dst, ev["link_seq"]).kind == ev["fault"]


# -- chrome flow export -------------------------------------------------------


def test_chrome_flow_events_pair_exactly(tmp_path):
    """Every ``s`` has its ``f``; each pair is one matched transmission;
    drops contribute none. Uses a droppy 2-client counter run so all
    fault kinds appear."""
    path = tmp_path / "droppy.jsonl"
    record_counter_demo(
        str(path), duration=0.8, client_count=2, seed=7,
        engine="python", base_port=43110,
    )
    meta, events = load_trace(str(path))
    out = tmp_path / "trace.chrome.json"
    pair_count = export_chrome_trace((meta, events), str(out))

    records = json.loads(out.read_text())
    starts = [r for r in records if r.get("ph") == "s"]
    finishes = [r for r in records if r.get("ph") == "f"]
    assert len(starts) == pair_count == len(finishes)
    assert {r["id"] for r in starts} == {r["id"] for r in finishes}
    # Exact accounting: one pair per deliver that matched a send.
    matched = [
        ev for ev in assign_lamport(events)
        if ev["kind"] == "deliver" and "sent_by" in ev
    ]
    assert pair_count == len(matched) == len(flow_pairs(events))
    # Per-actor metadata lanes and handler slices exist.
    lanes = [r for r in records if r.get("name") == "thread_name"]
    assert len(lanes) == len(meta["actors"])
    assert any(r.get("ph") == "X" for r in records)
    assert any(
        r.get("ph") == "i" and r.get("cat") == "fault" for r in records
    )


def test_dropped_transmissions_never_pair(tmp_path):
    path = tmp_path / "dropsonly.jsonl"
    record_counter_demo(
        str(path), duration=0.6, client_count=1, engine="python",
        base_port=43114, plan=FaultPlan(seed=3, drop=0.4),
        retry_range=(0.05, 0.08),
    )
    meta, events = load_trace(str(path))
    drops = sum(
        1 for ev in events
        if ev["kind"] == "fault" and ev["fault"] == "drop"
    )
    sends = sum(1 for ev in events if ev["kind"] == "send")
    assert drops > 0
    # Every pair consumes a distinct send; dropped sends never appear.
    pairs = flow_pairs(events)
    fresh = [p for p in pairs if not p[1].get("redelivery")]
    assert len(fresh) <= sends - drops


# -- live metrics / prometheus ------------------------------------------------


def test_labeled_prometheus_series(engine_runs):
    _path, _meta, _events, snapshot = engine_runs["python"]
    text = render_prometheus(snapshot, labels=NETOBS_SERIES_LABELS)
    assert 'stateright_actor_messages_sent{actor="1"}' in text
    assert 'stateright_actor_messages_delivered{actor="0"}' in text
    assert 'stateright_fault_injected{kind="duplicate"}' in text
    assert "stateright_handler_duration_secs_count" in text
    assert "stateright_delivery_latency_secs_count" in text
    assert 'stateright_engine_info{engine="python"}' in text


def test_netobs_gauges_and_histograms(engine_runs):
    _path, _meta, _events, snapshot = engine_runs["python"]
    assert snapshot["deployment_actors"] == 2
    assert snapshot["net_transmissions"] >= 2 * MAX_OPS
    assert snapshot["net_in_flight"] >= 0
    hists = snapshot["histograms"]
    assert hists["handler_duration_secs"]["count"] > 0
    assert hists["delivery_latency_secs"]["count"] > 0
    # timer_set counted per actor (the client arms its retry timer).
    assert snapshot["actor_timer_set"]["1"] >= 1


def test_as_netobs_normalization():
    nob = NetObs()
    assert as_netobs(nob) is nob
    assert as_netobs(False) is None
    assert as_netobs(False, default=True) is None
    assert isinstance(as_netobs(True), NetObs)
    assert as_netobs(None) is None
    assert isinstance(as_netobs(None, default=True), NetObs)
    with pytest.raises(TypeError):
        as_netobs("yes")


# -- causal past / divergence forensics ---------------------------------------


def test_causal_past_walks_happened_before(engine_runs):
    _path, _meta, events, _snap = engine_runs["python"]
    # The last deliver on the client: its past must include the server's
    # send that caused it, and every entry happened-before it.
    target = [
        ev for ev in assign_lamport(events)
        if ev["kind"] == "deliver" and ev["actor"] == 1
    ][-1]
    past = causal_past(events, target["actor"], target["seq"], k=6)
    assert 0 < len(past) <= 6
    assert all(ev["lc"] <= target["lc"] for ev in past)
    sent_by = tuple(target["sent_by"])
    assert any((ev["actor"], ev["seq"]) == sent_by for ev in past)
    # And renders as one line per event.
    lines = [format_event(ev) for ev in past]
    assert all(line.startswith("lc=") for line in lines)


def test_divergence_report_carries_causal_past(tmp_path):
    path = tmp_path / "mutated.jsonl"
    record_counter_demo(
        str(path), duration=0.6, client_count=2, seed=7,
        engine="python", base_port=43116,
    )
    meta, events = load_trace(str(path))
    mutated = False
    for ev in events:
        if (
            not mutated
            and ev.get("kind") == "deliver"
            and ev.get("seq", 0) > 2
            and isinstance(ev.get("state"), list)
            and ev["state"][0] == "CounterState"
        ):
            ev["state"][1] += 100
            mutated = True
    assert mutated
    from examples.increment import Bump, BumpOk, counter_model
    from stateright_tpu.actor import Network
    from stateright_tpu.conformance import make_decoder

    report = check_trace(
        counter_model(2, Network.new_unordered_duplicating()),
        (meta, events),
        decode=make_decoder(Bump, BumpOk),
    )
    assert not report.ok
    d = report.divergences[0]
    assert d.kind == "state-mismatch"
    assert d.causal_past, "divergence carries no causal past"
    assert all(line.startswith("lc=") for line in d.causal_past)
    rendered = d.format()
    assert "causal past" in rendered
    # The causal past rides along the json report too.
    assert report.to_dict()["divergences"][0]["causal_past"]


def test_check_trace_emits_labeled_fault_kind_counters(tmp_path):
    from examples.increment import conform_counter_trace
    from stateright_tpu.obs.metrics import MetricsRegistry

    path = tmp_path / "faulty.jsonl"
    record_counter_demo(
        str(path), duration=0.6, client_count=2, seed=7,
        engine="python", base_port=43118,
    )
    _meta, events = load_trace(str(path))
    recorded = collections.Counter(
        ev["fault"] for ev in events if ev["kind"] == "fault"
    )
    metrics = MetricsRegistry()
    report, _tester = conform_counter_trace(str(path), metrics=metrics)
    snap = metrics.snapshot()
    # conformance_* counters reconcile exactly against the fault lines.
    assert snap["conformance_faults"] == sum(recorded.values())
    assert snap["conformance_fault_kinds"] == dict(recorded)
    assert report.faults == sum(recorded.values())


# -- schema v1 back-compat ----------------------------------------------------


def test_v1_trace_still_loads_and_checks(tmp_path):
    path = tmp_path / "v2.jsonl"
    record_counter_demo(
        str(path), duration=0.5, client_count=1, seed=7,
        engine="python", base_port=43120,
    )
    v1 = tmp_path / "v1.jsonl"
    with open(path) as src, open(v1, "w") as dst:
        for line in src:
            ev = json.loads(line)
            if ev.get("kind") == "meta":
                ev.pop("v", None)
                ev.pop("faults", None)
            for key in ("lc", "sent_by", "redelivery", "dur", "seed_key"):
                ev.pop(key, None)
            dst.write(json.dumps(ev) + "\n")

    meta, events = load_trace(str(v1))
    assert "v" not in meta
    assert all("lc" not in ev for ev in events)
    # The reconstructor backfills stamps; the checker still runs.
    order = causal_order(events)
    assert order and all("lc" in ev for ev in order)
    from examples.increment import conform_counter_trace

    report, _tester = conform_counter_trace(str(v1))
    assert report.ok, report.format()
    with pytest.raises(ValueError):
        FaultPlan.from_meta(meta)


# -- deployment view ----------------------------------------------------------


def test_deployment_view_topology_and_tail(engine_runs):
    path, meta, events, _snap = engine_runs["python"]
    view = deployment_view(trace_path=path, tail=10)
    assert view["v"] == 2
    assert view["engine"] == "python"
    assert view["faults_plan"]["seed"] == PLAN.seed
    assert [a["actor"] for a in view["actors"]] == [
        "EchoServer", "PingClient",
    ]
    assert view["actors"][1]["sent"] >= MAX_OPS
    edges = {(e["src"], e["dst"]): e for e in view["edges"]}
    assert edges[(1, 0)]["sent"] >= MAX_OPS
    assert edges[(0, 1)]["delivered"] >= MAX_OPS
    total_faults = sum(
        sum(e["faults"].values()) for e in view["edges"]
    )
    assert total_faults == sum(
        1 for ev in events if ev["kind"] == "fault"
    )
    assert len(view["tail"]) == 10
    assert all(isinstance(line, str) for line in view["tail"])


def test_deployment_view_requires_a_source():
    with pytest.raises(KeyError):
        deployment_view()


def test_deployment_view_merges_live_telemetry(engine_runs):
    path, _meta, _events, _snap = engine_runs["python"]

    class FakeHandle:
        def telemetry(self):
            return {"net_transmissions": 42}

    view = deployment_view(trace_path=path, handle=FakeHandle())
    assert view["telemetry"]["net_transmissions"] == 42
    assert view["actors"]


def test_explorer_serves_deployment(engine_runs, tmp_path):
    import urllib.error
    import urllib.request

    from examples.increment import counter_model
    from stateright_tpu.explorer.server import serve

    path, _meta, _events, _snap = engine_runs["python"]
    server = serve(
        counter_model(1).checker(), "127.0.0.1:0", block=False, trace=path
    )
    try:
        base = server.url.rstrip("/")
        body = json.loads(
            urllib.request.urlopen(base + "/deployment?tail=5").read()
        )
        assert body["actors"] and body["edges"]
        assert len(body["tail"]) == 5
    finally:
        server.shutdown()


def test_explorer_deployment_404_without_trace():
    import urllib.error
    import urllib.request

    from examples.increment import counter_model
    from stateright_tpu.explorer.server import serve

    server = serve(counter_model(1).checker(), "127.0.0.1:0", block=False)
    try:
        base = server.url.rstrip("/")
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(base + "/deployment")
        assert err.value.code == 404
    finally:
        server.shutdown()


# -- timers base-port footgun -------------------------------------------------


def test_timers_demo_rejects_odd_base_port(tmp_path):
    from examples.timers import record_timers_demo, spawn_info

    with pytest.raises(ValueError, match="must be even"):
        record_timers_demo(str(tmp_path / "t.jsonl"), base_port=43131)
    with pytest.raises(ValueError, match="must be even"):
        spawn_info(record=str(tmp_path / "t2.jsonl"), base_port=43133)


def test_timers_spawn_info_accepts_even_base_port(tmp_path):
    from examples.timers import conform_timers_trace, spawn_info

    path = tmp_path / "timers.jsonl"
    spawn_info(record=str(path), duration=0.2, base_port=43140)
    report, _none = conform_timers_trace(str(path))
    assert report.ok, report.format()
