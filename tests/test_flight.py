"""Flight recorder (stateright_tpu/obs/flight.py): ring semantics, the
per-era device/host-gap wall split, the engine integrations (single
device, simulation, sharded mesh with per-shard labeled metrics), and
the export surfaces (JSONL, Chrome counter tracks, /flight).
"""

import json

import jax
import pytest

from stateright_tpu import TensorModelAdapter
from stateright_tpu.models import TwoPhaseTensor
from stateright_tpu.obs.flight import FlightRecorder
from stateright_tpu.obs.metrics import SHARD_SERIES_LABELS, render_prometheus
from stateright_tpu.parallel import ShardedBfs


@pytest.fixture(scope="module")
def devices():
    devs = jax.devices()
    assert len(devs) >= 8, "conftest should force 8 virtual CPU devices"
    return devs[:8]


# -- recorder unit semantics --------------------------------------------------


def test_capacity_validation():
    with pytest.raises(ValueError, match="capacity"):
        FlightRecorder(capacity=0)


def test_device_gap_wall_identity_per_record():
    fr = FlightRecorder()
    fr.start(t=100.0)
    fr.record(device_era_secs=0.2, t=100.5)  # 0.3s of host gap
    fr.record(device_era_secs=0.4, t=101.0)  # 0.1s of host gap
    recs = fr.records()
    assert [r["era"] for r in recs] == [1, 2]
    for r in recs:
        # The load-bearing overlap-aware identity, exact per record.
        assert r["device_era_secs"] - r["overlap_secs"] + r[
            "host_gap_secs"
        ] == pytest.approx(r["wall_secs"])
        assert r["overlap_secs"] == 0.0  # serial eras: no overlap
    assert recs[0]["host_gap_secs"] == pytest.approx(0.3)
    assert recs[1]["host_gap_secs"] == pytest.approx(0.1)
    s = fr.summary()
    assert s["eras"] == 2
    assert s["device_secs"] == pytest.approx(0.6)
    assert s["host_gap_secs"] == pytest.approx(0.4)
    assert s["overlap_secs"] == 0.0
    assert s["wall_secs"] == pytest.approx(1.0)
    assert s["host_gap_pct"] == pytest.approx(40.0)


def test_overlap_booked_when_device_exceeds_wall():
    # A pipelined engine can report a device span larger than the wall
    # delta since the previous readback (its dispatch overlapped the
    # previous era's host work). The excess is booked as overlap_secs —
    # not silently clamped — so device - overlap + gap == wall stays
    # exact and the run-level totals reconcile with the external clock.
    fr = FlightRecorder()
    fr.start(t=0.0)
    fr.record(device_era_secs=2.0, t=1.0)
    fr.record(device_era_secs=0.5, t=2.0)  # serial era afterwards
    recs = fr.records()
    assert recs[0]["host_gap_secs"] == 0.0
    assert recs[0]["overlap_secs"] == pytest.approx(1.0)
    assert recs[0]["device_era_secs"] - recs[0]["overlap_secs"] + recs[0][
        "host_gap_secs"
    ] == pytest.approx(recs[0]["wall_secs"])
    # Exactly one of gap/overlap is nonzero per record.
    assert recs[1]["overlap_secs"] == 0.0
    assert recs[1]["host_gap_secs"] == pytest.approx(0.5)
    s = fr.summary()
    assert s["overlap_secs"] == pytest.approx(1.0)
    assert s["device_secs"] - s["overlap_secs"] + s[
        "host_gap_secs"
    ] == pytest.approx(s["wall_secs"])


def test_lazy_anchor_without_start():
    # An engine that skips start(): the first record's wall time equals
    # its device time (zero gap) instead of measuring from the epoch.
    fr = FlightRecorder()
    fr.record(device_era_secs=0.25, t=50.0)
    rec = fr.records()[0]
    assert rec["wall_secs"] == pytest.approx(0.25)
    assert rec["host_gap_secs"] == 0.0


def test_ring_eviction_keeps_summary_exact():
    fr = FlightRecorder(capacity=4)
    fr.start(t=0.0)
    for i in range(10):
        fr.record(device_era_secs=0.1, t=float(i + 1))
    assert len(fr) == 4
    recs = fr.records()
    assert [r["era"] for r in recs] == [7, 8, 9, 10]  # oldest evicted
    s = fr.summary()
    assert s["eras"] == 10
    assert s["recorded"] == 4
    assert s["dropped"] == 6
    # Totals accumulate across the WHOLE run, not just the retained ring.
    assert s["wall_secs"] == pytest.approx(10.0)
    assert s["device_secs"] == pytest.approx(1.0)


def test_export_jsonl_and_chrome_shapes(tmp_path):
    fr = FlightRecorder(engine="TestEngine")
    fr.start(t=0.0)
    fr.record(device_era_secs=0.1, frontier=10, load_factor=0.5, t=0.2)
    jpath = tmp_path / "f.jsonl"
    fr.export_jsonl(str(jpath))
    lines = [json.loads(ln) for ln in jpath.read_text().splitlines()]
    assert lines[0]["era"] == 1
    assert lines[-1]["summary"]["eras"] == 1
    assert lines[-1]["engine"] == "TestEngine"

    events = fr.chrome_counter_events()
    assert {e["name"] for e in events} == {
        "flight era (ms)",
        "flight frontier",
        "flight load_factor",
    }
    assert all(e["ph"] == "C" for e in events)
    cpath = tmp_path / "f.trace.json"
    fr.export_chrome(str(cpath))
    assert json.loads(cpath.read_text()) == events


# -- builder surface ----------------------------------------------------------


def test_builder_flight_format_validation():
    with pytest.raises(ValueError, match="format"):
        TensorModelAdapter(TwoPhaseTensor(3)).checker().flight(
            path="x.jsonl", format="xml"
        )


# -- device-engine integration ------------------------------------------------


def test_device_run_records_flight_by_default(tmp_path):
    path = str(tmp_path / "run.flight.jsonl")
    c = (
        TensorModelAdapter(TwoPhaseTensor(3))
        .checker()
        .flight(path=path)
        .spawn_tpu_bfs(chunk_size=128)
        .join()
    )
    assert c.unique_state_count() == 288
    recs = c.flight()
    assert recs, "device run recorded no flight records"
    tel = c.telemetry()
    assert len(recs) == tel["eras"]
    for r in recs:
        # Overlap-aware identity: with pipelining ON (the default) a
        # chained era's device span can overlap the previous host gap.
        # Each of the four fields is rounded to 6 decimals on its own, so
        # the identity holds to 4 half-units of the last place.
        assert r["device_era_secs"] - r["overlap_secs"] + r[
            "host_gap_secs"
        ] == pytest.approx(r["wall_secs"], abs=2e-6)
        assert r["take_cap"] >= 1
    # The last record reconciles with the engine's own counters.
    assert recs[-1]["unique"] == c.unique_state_count()
    assert sum(r["generated"] for r in recs) == tel["states_generated"]
    assert sum(r["steps"] for r in recs) == tel["steps"]
    # Summary rides telemetry, plus the flat Prometheus-visible gauges.
    fsum = tel["flight"]
    assert fsum["eras"] == len(recs)
    assert fsum["device_secs"] - fsum["overlap_secs"] + fsum[
        "host_gap_secs"
    ] == pytest.approx(fsum["wall_secs"], rel=1e-6, abs=1e-6)
    assert tel["flight_eras"] == fsum["eras"]
    assert tel["flight_device_era_secs"] == pytest.approx(
        fsum["device_secs"]
    )
    # The JSONL export landed at run end: records + summary line.
    lines = [json.loads(ln) for ln in open(path)]
    assert [r["era"] for r in lines[:-1]] == [r["era"] for r in recs]
    assert lines[-1]["summary"]["eras"] == fsum["eras"]


def test_flight_disabled_is_clean():
    c = (
        TensorModelAdapter(TwoPhaseTensor(3))
        .checker()
        .flight(False)
        .spawn_tpu_bfs(chunk_size=128)
        .join()
    )
    assert c.unique_state_count() == 288
    assert c.flight() == []
    assert "flight" not in c.telemetry()


def test_flight_counter_tracks_ride_chrome_trace(tmp_path):
    path = str(tmp_path / "run.trace.json")
    c = (
        TensorModelAdapter(TwoPhaseTensor(3))
        .checker()
        .trace(path, format="chrome")
        .spawn_tpu_bfs(chunk_size=128)
        .join()
    )
    assert c.unique_state_count() == 288
    events = json.loads(open(path).read())
    counters = [e for e in events if e.get("ph") == "C"]
    assert len(counters) == 3 * len(c.flight())
    assert {"flight era (ms)", "flight frontier"} <= {
        e["name"] for e in counters
    }


def test_simulation_engine_records_flight():
    from stateright_tpu.models import IncrementTensor

    c = (
        TensorModelAdapter(IncrementTensor(2))
        .checker()
        .target_state_count(100)
        .spawn_tpu_simulation(7, walks=32, walk_cap=16)
        .join()
    )
    recs = c.flight()
    assert recs
    assert recs[0]["frontier"] == 32  # the walk batch width
    assert "era_secs" in c.telemetry().get("histograms", {})


# -- sharded mesh: per-shard labeled metrics ----------------------------------


def _shard_sum(tel, name):
    series = tel[name]
    assert isinstance(series, dict) and len(series) == tel["n_shards"]
    return sum(series.values())


def test_sharded_flight_and_labeled_sums_abd2(devices):
    from stateright_tpu.models.abd import AbdTensor

    sb = ShardedBfs(AbdTensor(2), devices, chunk_size=256).run()
    c = sb.checker
    assert c.unique_state_count() == 544
    tel = c.telemetry()
    # The mesh readback rows carry PER-ERA step/gen counts, so the
    # labeled per-shard series sum EXACTLY to the engine totals.
    assert _shard_sum(tel, "shard_steps") == tel["steps"]
    assert _shard_sum(tel, "shard_states_generated") == (
        tel["states_generated"]
    )
    # Exchange accounting: on a clean run every unique state was
    # accepted by exactly one shard, so the sum is the unique count.
    assert _shard_sum(tel, "shard_exchange_rows") == 544
    assert "shard_frontier_rows" in tel and "shard_load_factor" in tel
    assert tel["shard_imbalance"] >= 1.0
    # Flight records carry the per-shard breakdown.
    recs = c.flight()
    assert recs and "shards" in recs[-1]
    assert len(recs[-1]["shards"]) == len(devices)
    assert sum(
        s["exchange_rows"] for r in recs for s in r["shards"].values()
    ) == 544


def test_sharded_multi_era_identity_2pc5(devices):
    # sync_steps=4 forces many short eras; the per-era exchange deltas
    # must still sum exactly across records AND shards.
    sb = ShardedBfs(
        TwoPhaseTensor(5), devices, chunk_size=256, sync_steps=4
    ).run()
    c = sb.checker
    assert c.unique_state_count() == 8832
    tel = c.telemetry()
    assert tel["eras"] > 1, "sync_steps=4 should force a multi-era run"
    assert len(c.flight()) == tel["eras"]
    assert _shard_sum(tel, "shard_exchange_rows") == 8832
    assert _shard_sum(tel, "shard_steps") == tel["steps"]


def test_sharded_labeled_sums_paxos2(devices):
    from stateright_tpu.models.paxos import PaxosTensorExhaustive

    sb = ShardedBfs(PaxosTensorExhaustive(2), devices, chunk_size=256).run()
    c = sb.checker
    assert c.unique_state_count() == 16_668
    tel = c.telemetry()
    assert _shard_sum(tel, "shard_exchange_rows") == 16_668
    assert _shard_sum(tel, "shard_steps") == tel["steps"]
    assert _shard_sum(tel, "shard_states_generated") == (
        tel["states_generated"]
    )
    assert tel["shard_imbalance"] >= 1.0


def test_sharded_prometheus_renders_shard_series(devices):
    from stateright_tpu.models import IncrementTensor

    sb = ShardedBfs(IncrementTensor(2), devices, chunk_size=64).run()
    text = render_prometheus(
        sb.checker.telemetry(), labels=SHARD_SERIES_LABELS
    )
    assert 'stateright_shard_exchange_rows{shard="0"}' in text
    assert 'stateright_shard_frontier_rows{shard="7"}' in text
    assert "stateright_shard_imbalance" in text


# -- Explorer /flight ---------------------------------------------------------


def test_explorer_flight_endpoint():
    import urllib.request

    from stateright_tpu.explorer.server import serve
    from stateright_tpu.models.fixtures import BinaryClock

    # The Explorer drives an on-demand HOST checker, so its live /flight
    # is well-formed but empty — the panel only lights up for device
    # runs (the populated view is covered below via _flight_view).
    server = serve(BinaryClock().checker(), "127.0.0.1:0", block=False)
    try:
        with urllib.request.urlopen(
            server.url.rstrip("/") + "/flight"
        ) as r:
            assert r.status == 200
            body = json.loads(r.read())
        assert body["records"] == []
        assert body["summary"] == {}
        assert "ts" in body and "done" in body
    finally:
        server.shutdown()


def test_flight_view_populated_for_device_checker():
    from stateright_tpu.explorer.server import _flight_view

    checker = (
        TensorModelAdapter(TwoPhaseTensor(3))
        .checker()
        .spawn_tpu_bfs(chunk_size=128)
        .join()
    )
    view = _flight_view(checker)
    assert view["done"] is True
    assert view["records"] == checker.flight() and view["records"]
    assert view["summary"]["eras"] == len(view["records"])


def test_explorer_ui_ships_flight_panel():
    from pathlib import Path

    ui = Path(__file__).parent.parent / "stateright_tpu" / "explorer" / "ui"
    assert "flight-panel" in (ui / "index.html").read_text()
    js = (ui / "app.js").read_text()
    assert "/flight" in js and "pollFlight" in js
