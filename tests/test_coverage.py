"""Coverage observability (obs/coverage.py): per-action fire counts with
exact host/device parity, depth histograms that reconcile with unique
counts, dead-action detection (runtime + speclint STR306 + reporter
warning block), counterexample forensics (Path.explain), and the
Explorer/trace/bench/Prometheus wiring."""

from __future__ import annotations

import io
import json
import urllib.error
import urllib.request
from typing import List

import numpy as np
import pytest

from stateright_tpu import TensorModelAdapter, WriteReporter
from stateright_tpu.analysis import analyze
from stateright_tpu.has_discoveries import HasDiscoveries
from stateright_tpu.models import Increment, IncrementTensor, TwoPhaseTensor
from stateright_tpu.models.fixtures import BinaryClock
from stateright_tpu.tensor import TensorModel, TensorProperty

# ---------------------------------------------------------------------------
# Fixture models.
# ---------------------------------------------------------------------------


class IncrementTensorCov(IncrementTensor):
    """IncrementTensor plus an always-holding property, so exhaustive runs
    stay exhaustive after the 'fin' counterexample is found (with only
    violated properties, the host engines stop expanding once every
    property has a discovery — reference parity — which would make
    host/device visit sets diverge)."""

    def tensor_properties(self) -> List[TensorProperty]:
        return super().tensor_properties() + [
            TensorProperty.always("live", lambda xp, lanes: lanes[0] == lanes[0])
        ]


class DeadGuardTensor(TensorModel):
    """One live counter action and one action whose guard is never true on
    any reachable state — the canonical dead transition."""

    state_width = 1
    max_actions = 2

    def init_states_array(self) -> np.ndarray:
        return np.zeros((1, 1), dtype=np.uint32)

    def step_lanes(self, xp, lanes):
        u = xp.uint32
        x = lanes[0]
        succs = [((x + u(1)) & u(3),), ((x + u(7)) & u(15),)]
        masks = [x < u(3), x == u(999)]  # slot 1: unreachable guard
        return succs, masks

    def tensor_properties(self):
        return [
            TensorProperty.always("bounded", lambda xp, l: l[0] <= xp.uint32(4))
        ]

    def format_action(self, a: int) -> str:
        return "Step" if a == 0 else "Never"


EXHAUST = HasDiscoveries.any_of([])  # never matches: run to exhaustion


def _tiny_tpu_opts():
    return dict(chunk_size=64, queue_capacity=1 << 10, table_capacity=1 << 12)


# ---------------------------------------------------------------------------
# Host/device per-action parity (the acceptance criterion).
# ---------------------------------------------------------------------------


def test_action_counts_match_host_device_increment():
    tm = IncrementTensorCov(2)
    host = TensorModelAdapter(tm).checker().spawn_bfs().join()
    dev = TensorModelAdapter(tm).checker().spawn_tpu_bfs(**_tiny_tpu_opts()).join()
    hc, dc = host.coverage(), dev.coverage()
    assert hc["actions"] == dc["actions"]
    assert sum(hc["actions"].values()) > 0
    assert hc["depths"] == dc["depths"]
    assert host.unique_state_count() == dev.unique_state_count()


def test_action_counts_match_host_device_2pc4():
    tm = TwoPhaseTensor(4)
    host = TensorModelAdapter(tm).checker().spawn_bfs().join()
    dev = (
        TensorModelAdapter(tm)
        .checker()
        .spawn_tpu_bfs(chunk_size=256, queue_capacity=1 << 12, table_capacity=1 << 13)
        .join()
    )
    hc, dc = host.coverage(), dev.coverage()
    assert hc["actions"] == dc["actions"]
    assert dc["depths"] == hc["depths"]
    # Action counts decompose states_generated exactly.
    assert sum(dc["actions"].values()) == dev.telemetry()["states_generated"]


def test_action_counts_match_vbfs():
    tm = TwoPhaseTensor(4)
    host = TensorModelAdapter(tm).checker().spawn_bfs().join()
    v = TensorModelAdapter(tm).checker().threads(2).spawn_vbfs().join()
    assert v.coverage()["actions"] == host.coverage()["actions"]
    assert v.coverage()["depths"] == host.coverage()["depths"]


def test_action_counts_match_sharded():
    tm = TwoPhaseTensor(3)
    host = TensorModelAdapter(tm).checker().spawn_bfs().join()
    s = (
        TensorModelAdapter(tm)
        .checker()
        .spawn_sharded_bfs(
            chunk_size=128,
            queue_capacity_per_shard=1 << 12,
            table_capacity_per_shard=1 << 12,
        )
        .join()
    )
    assert s.coverage()["actions"] == host.coverage()["actions"]
    assert s.coverage()["depths"] == host.coverage()["depths"]


# ---------------------------------------------------------------------------
# Depth histograms reconcile with unique counts.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("spawn", ["bfs", "dfs", "vbfs", "tpu"])
def test_depth_histogram_sums_to_unique(spawn):
    builder = TensorModelAdapter(TwoPhaseTensor(3)).checker()
    if spawn == "bfs":
        c = builder.spawn_bfs().join()
    elif spawn == "dfs":
        c = builder.spawn_dfs().join()
    elif spawn == "vbfs":
        c = builder.threads(2).spawn_vbfs().join()
    else:
        c = builder.spawn_tpu_bfs(**_tiny_tpu_opts()).join()
    cov = c.coverage()
    assert sum(cov["depths"].values()) == c.unique_state_count()
    assert cov["max_depth"] == max(cov["depths"])


def test_simulation_coverage_counts_walk_states():
    c = (
        TensorModelAdapter(IncrementTensor(2))
        .checker()
        .target_state_count(150)
        .spawn_simulation(7)
        .join()
    )
    cov = c.coverage()
    # No dedup in simulation: depths count visited states, actions count
    # transitions taken (one fewer than states per walk).
    assert sum(cov["depths"].values()) == c.state_count()
    assert sum(cov["actions"].values()) > 0


def test_tpu_simulation_coverage():
    c = (
        TensorModelAdapter(IncrementTensor(2))
        .checker()
        .target_state_count(150)
        .spawn_tpu_simulation(7, walks=32, walk_cap=16)
        .join()
    )
    cov = c.coverage()
    assert sum(cov["depths"].values()) == c.state_count()
    assert sum(cov["actions"].values()) > 0
    assert cov["properties"]["fin"]["evaluations"] == c.state_count()


def test_pbfs_coverage():
    from stateright_tpu.models.two_phase_commit import TwoPhaseSys

    c = TwoPhaseSys(3).checker().threads(2).spawn_parallel_bfs().join()
    cov = c.coverage()
    assert sum(cov["depths"].values()) == c.unique_state_count()
    assert sum(cov["actions"].values()) > 0


def test_on_demand_coverage():
    c = BinaryClock().checker().spawn_on_demand()
    c.run_to_completion()
    c.join()
    cov = c.coverage()
    assert sum(cov["depths"].values()) == c.unique_state_count()
    assert sum(cov["actions"].values()) > 0


# ---------------------------------------------------------------------------
# Dead-action detection: runtime, reporter block, speclint STR306.
# ---------------------------------------------------------------------------


def test_dead_action_detected_host_and_device():
    tm = DeadGuardTensor()
    for checker in (
        TensorModelAdapter(tm).checker().spawn_bfs().join(),
        TensorModelAdapter(tm).checker().spawn_tpu_bfs(**_tiny_tpu_opts()).join(),
    ):
        cov = checker.coverage()
        assert cov["dead_actions"] == ["Never"]
        assert cov["actions"]["Step"] > 0
        assert checker.telemetry()["coverage_dead_actions"] == 1


def test_reporter_prints_dead_action_warning():
    out = io.StringIO()
    c = TensorModelAdapter(DeadGuardTensor()).checker().spawn_bfs()
    c.report(WriteReporter(out))
    text = out.getvalue()
    assert "Coverage. actions_fired=1/2" in text
    assert "never fired" in text and "STR306" in text
    assert "- Never" in text


def test_speclint_str306_flags_dead_guard():
    report = analyze(DeadGuardTensor())
    findings = report.by_code("STR306")
    assert findings and findings[0].severity.value == "warning"
    assert "Never" in findings[0].message
    assert report.ok  # warning, not error


def test_speclint_str306_clean_on_full_sample():
    report = analyze(TwoPhaseTensor(3), samples=512)
    assert not report.by_code("STR306")


def test_coverage_disabled():
    c = (
        TensorModelAdapter(IncrementTensorCov(2))
        .checker()
        .coverage(False)
        .spawn_tpu_bfs(**_tiny_tpu_opts())
        .join()
    )
    cov = c.coverage()
    assert cov["enabled"] is False
    assert not any(cov["actions"].values())
    assert not cov["depths"]
    # ...and disabling must not change the verdicts.
    assert c.discovery("fin") is not None


# ---------------------------------------------------------------------------
# Counterexample forensics: Path.explain / explain_steps.
# ---------------------------------------------------------------------------


def test_path_explain_narrative():
    c = Increment(2).checker().spawn_bfs().join()
    path = c.discovery("fin")
    text = path.explain(c.model())
    assert text.startswith(f"Path[{len(path)}] explained:")
    assert "'fin'" in text and "FALSE" in text  # the property flip
    assert "->" in text  # field-level diffs present


def test_path_explain_steps_structure():
    c = TensorModelAdapter(IncrementTensorCov(2)).checker().spawn_bfs().join()
    path = c.discovery("fin")
    steps = path.explain_steps(c.model())
    assert steps[0]["step"] == 0 and steps[0]["action"] is None
    assert len(steps) == len(path) + 1
    for rec in steps[1:]:
        assert isinstance(rec["action"], str)
        assert isinstance(rec["changes"], dict)
    # The final step flips 'fin' from True to False.
    assert steps[-1]["property_flips"].get("fin") == [True, False]
    # Records are JSON-serializable (the Explorer endpoint contract).
    json.dumps(steps)


def test_reporter_discovery_includes_explanation():
    out = io.StringIO()
    Increment(2).checker().spawn_bfs().report(WriteReporter(out))
    text = out.getvalue()
    assert 'Discovered "fin"' in text
    assert "explained:" in text


# ---------------------------------------------------------------------------
# Wiring round-trips: trace field, Chrome trace, Explorer, Prometheus, bench.
# ---------------------------------------------------------------------------


def test_trace_events_carry_coverage(tmp_path):
    path = str(tmp_path / "run.jsonl")
    c = (
        TensorModelAdapter(TwoPhaseTensor(3))
        .checker()
        .trace(path)
        .spawn_tpu_bfs(chunk_size=128)
        .join()
    )
    lines = [json.loads(line) for line in open(path)]
    eras = [rec for rec in lines if rec["event"] == "era"]
    assert eras
    assert all("coverage" in rec for rec in eras)
    final_actions = eras[-1]["coverage"]["actions"]
    assert final_actions == c.coverage()["actions"]


def test_chrome_trace_loads_in_perfetto_format(tmp_path):
    path = str(tmp_path / "run.chrome.json")
    TensorModelAdapter(TwoPhaseTensor(3)).checker().trace(
        path, format="chrome"
    ).spawn_bfs().join()
    events = json.load(open(path))  # closed file is a full JSON array
    phases = {e.get("ph") for e in events if e}
    assert "i" in phases  # instant events (waves / run brackets)
    assert "X" in phases  # duration events (phase timers)
    names = {e.get("name") for e in events if e}
    assert "run_start" in names and "run_end" in names
    assert "check_block" in names
    for e in events:
        if e.get("ph") == "X":
            assert e["dur"] > 0 and e["ts"] >= 0


def test_trace_format_validation():
    with pytest.raises(ValueError, match="chrome"):
        BinaryClock().checker().trace("/tmp/x", format="perfetto")


def test_explorer_coverage_prometheus_and_explain():
    from stateright_tpu.explorer.server import serve

    server = serve(
        TensorModelAdapter(IncrementTensorCov(2)).checker(),
        "127.0.0.1:0",
        block=False,
    )
    try:
        base = server.url.rstrip("/")

        def get(path):
            return urllib.request.urlopen(base + path)

        def get_json(path):
            with get(path) as r:
                assert r.status == 200
                return json.loads(r.read())

        # Drive the on-demand checker to completion so coverage fills in.
        req = urllib.request.Request(base + "/.runtocompletion", method="POST")
        with urllib.request.urlopen(req) as r:
            assert r.status == 200
        server.checker.join()

        body = get_json("/coverage")
        cov = body["coverage"]
        assert cov["enabled"] and sum(cov["actions"].values()) > 0
        assert get_json("/.coverage")["coverage"]["actions"] == cov["actions"]

        # Prometheus exposition: content type + stateright_ prefix.
        for path in ("/metrics?format=prometheus", "/metrics.prom"):
            with get(path) as r:
                assert r.status == 200
                ctype = r.headers.get("Content-Type", "")
                assert ctype.startswith("text/plain")
                assert "version=0.0.4" in ctype
                text = r.read().decode()
            assert "stateright_state_count" in text
            assert "stateright_engine_info" in text
        # The JSON endpoint still works with no format param.
        assert "telemetry" in get_json("/metrics")

        # Path-detail forensics over a discovered counterexample.
        status = get_json("/.status")
        discovery = next(
            enc for (_e, name, enc) in status["properties"] if name == "fin" and enc
        )
        body = get_json("/.explain/" + discovery)
        assert "narrative" in body and "explained:" in body["narrative"]
        assert body["steps"][0]["step"] == 0
        # Bad paths 404 instead of crashing the server.
        with pytest.raises(urllib.error.HTTPError):
            get("/.explain/notafingerprint")
    finally:
        server.shutdown()


def test_explorer_ui_ships_coverage_panel():
    # The SPA bundle must actually wire the coverage dashboard: panel +
    # explain view in the page, polling/render logic in the script.
    from pathlib import Path as FsPath

    ui = FsPath(__file__).parent.parent / "stateright_tpu" / "explorer" / "ui"
    html = (ui / "index.html").read_text()
    js = (ui / "app.js").read_text()
    css = (ui / "app.css").read_text()
    assert "coverage-panel" in html and "action-bars" in html
    assert "depth-hist" in html and "explain-path" in html
    assert "/coverage" in js and "pollCoverage" in js
    assert "/.explain/" in js and "renderDeadActions" in js
    assert ".cov-bar" in css and ".hist-bar" in css


def test_coverage_in_telemetry_gauges():
    c = TensorModelAdapter(IncrementTensorCov(2)).checker().spawn_bfs().join()
    t = c.telemetry()
    assert t["coverage_actions_fired"] == 4
    assert t["coverage_dead_actions"] == 0
