"""Checking-as-a-service: run-server lifecycle, quotas, the executable
cache, cancellation, the speclint admission gate, and multiplexed-lane
parity with the host oracle.

The HTTP tests run one module-scoped in-process server (workers=1,
lanes=8) on an ephemeral port — the scheduler `pause()`/`resume()` hook
makes the batching deterministic, and the shared server keeps the CPU
compile budget to one lane program. The parity tests drive
`run_multiplexed` directly: per-lane results must match an individual
host `spawn_bfs` on the seed goldens (increment:2 = 13 unique,
2pc-3 = 288 unique).
"""

import json
import random
import time
import urllib.error
import urllib.request
from typing import List

import numpy as np
import pytest

from stateright_tpu import Model, Property, TensorModelAdapter
from stateright_tpu.engines.compiled import (
    ExecutableCache,
    intern_model,
    model_signature,
)
from stateright_tpu.engines.multiplex import run_multiplexed
from stateright_tpu.models import IncrementTensor, TwoPhaseTensor
from stateright_tpu.serve import RunService, ServeServer


# ---------------------------------------------------------------------------
# HTTP fixture + helpers
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def server():
    svc = RunService(workers=1, lanes=8, lint_samples=32)
    srv = ServeServer(svc, "127.0.0.1:0").serve_in_background()
    yield srv
    srv.shutdown()


def _req(server, method, path, body=None):
    data = json.dumps(body).encode() if body is not None else None
    request = urllib.request.Request(
        server.url.rstrip("/") + path, data=data, method=method
    )
    try:
        with urllib.request.urlopen(request) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def _await_done(server, job_id, timeout=300.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        code, view = _req(server, "GET", f"/jobs/{job_id}")
        assert code == 200, view
        if view["status"] not in ("queued", "running"):
            return view
        time.sleep(0.1)
    raise AssertionError(f"job {job_id} did not finish")


# ---------------------------------------------------------------------------
# Lifecycle over REST
# ---------------------------------------------------------------------------


def test_submit_status_result_lifecycle(server):
    _req(server, "POST", "/scheduler/pause")
    ids = []
    for _ in range(4):
        code, body = _req(
            server, "POST", "/submit", {"spec": "increment:2", "tenant": "acme"}
        )
        assert code == 202 and body["status"] == "queued", body
        ids.append(body["job_id"])
    code, body = _req(
        server, "POST", "/submit", {"spec": "2pc:3", "tenant": "acme"}
    )
    assert code == 202
    two_phase = body["job_id"]
    # Still queued while paused.
    assert _req(server, "GET", f"/jobs/{ids[0]}")[1]["status"] == "queued"
    code, body = _req(server, "GET", f"/jobs/{ids[0]}/result")
    assert code == 409  # no result yet
    _req(server, "POST", "/scheduler/resume")

    for job_id in ids:
        assert _await_done(server, job_id)["status"] == "done"
    assert _await_done(server, two_phase)["status"] == "done"

    # Results carry the seed goldens + Path.explain forensics.
    code, body = _req(server, "GET", f"/jobs/{ids[0]}/result")
    assert code == 200
    result = body["result"]
    assert result["engine"] == "multiplex"
    assert result["unique_state_count"] == 13
    assert result["max_depth"] == 5
    fin = result["discoveries"]["fin"]
    assert fin["expectation"] == "always"  # "fin" counterexample
    assert fin["depth"] == 4
    assert "explained" in fin["explain"]
    assert fin["encoded"].count("/") == 4

    code, body = _req(server, "GET", f"/jobs/{two_phase}/result")
    assert body["result"]["unique_state_count"] == 288
    assert set(body["result"]["discoveries"]) == {
        "abort agreement",
        "commit agreement",
    }

    # The 4 increment lanes shared ONE multiplexed batch + executable.
    telemetry = _req(server, "GET", "/metrics")[1]
    assert telemetry["serve_multiplexed_jobs"] >= 4
    assert telemetry["serve_completed"] >= 5

    # /jobs filters by tenant.
    jobs = _req(server, "GET", "/jobs?tenant=acme")[1]["jobs"]
    assert {j["job_id"] for j in jobs} >= set(ids) | {two_phase}
    assert _req(server, "GET", "/jobs?tenant=nobody")[1]["jobs"] == []


def test_exec_cache_hit_on_second_same_shape_submit(server):
    before = _req(server, "GET", "/stats")[1]["cache"]
    code, body = _req(server, "POST", "/submit", {"spec": "increment:2"})
    assert code == 202
    assert _await_done(server, body["job_id"])["status"] == "done"
    after = _req(server, "GET", "/stats")[1]["cache"]
    # Same shape signature as the lifecycle test's lanes: warm executable,
    # zero new compiles.
    assert after["hits"] == before["hits"] + 1
    assert after["misses"] == before["misses"]


def test_cancellation(server):
    _req(server, "POST", "/scheduler/pause")
    code, body = _req(server, "POST", "/submit", {"spec": "increment:2"})
    assert code == 202
    job_id = body["job_id"]
    code, body = _req(server, "POST", f"/jobs/{job_id}/cancel")
    assert code == 200 and body["status"] == "cancelled"
    # Cancelled jobs never run, re-cancelling conflicts, results 409.
    code, _ = _req(server, "POST", f"/jobs/{job_id}/cancel")
    assert code == 409
    code, _ = _req(server, "GET", f"/jobs/{job_id}/result")
    assert code == 409
    _req(server, "POST", "/scheduler/resume")
    assert _req(server, "GET", f"/jobs/{job_id}")[1]["status"] == "cancelled"
    code, _ = _req(server, "POST", "/jobs/nope/cancel")
    assert code == 404


def test_submit_rejects_malformed(server):
    assert _req(server, "POST", "/submit", {})[0] == 400
    assert _req(server, "POST", "/submit", {"spec": "no-such-model"})[0] == 400
    assert (
        _req(server, "POST", "/submit", {"spec": "increment:2", "engine": "warp"})[0]
        == 400
    )
    # Device engines need tensor models.
    code, body = _req(
        server, "POST", "/submit",
        {"spec": "increment-host:2", "engine": "multiplex"},
    )
    assert code == 400 and "tensor" in body["error"]


def test_tenant_labels_in_prometheus(server):
    raw = urllib.request.urlopen(
        server.url.rstrip("/") + "/metrics.prom"
    ).read().decode()
    assert 'stateright_serve_tenant_requests{tenant="acme"}' in raw
    assert "stateright_serve_exec_cache_hits" in raw


# ---------------------------------------------------------------------------
# Quotas (service-level; a paused scheduler keeps everything queued so no
# engine work happens)
# ---------------------------------------------------------------------------


def test_quota_max_active_returns_429():
    svc = RunService(workers=1, quota_max_active=2)
    svc.pause()
    try:
        for _ in range(2):
            code, _ = svc.submit({"spec": "increment:2", "tenant": "greedy"})
            assert code == 202
        code, body = svc.submit({"spec": "increment:2", "tenant": "greedy"})
        assert code == 429 and "quota" in body["error"]
        # Other tenants are unaffected.
        code, _ = svc.submit({"spec": "increment:2", "tenant": "polite"})
        assert code == 202
        assert svc.metrics.get("serve_rejected_quota") == 1
    finally:
        svc.shutdown()


def test_rate_limit_returns_429():
    svc = RunService(workers=1, quota_per_minute=3)
    svc.pause()
    try:
        ids = []
        for _ in range(3):
            code, body = svc.submit({"spec": "increment:2", "tenant": "t"})
            assert code == 202
            ids.append(body["job_id"])
        # Active-job quota is NOT the limiter here: cancel them all.
        for job_id in ids:
            assert svc.cancel(job_id)[0] == 200
        code, body = svc.submit({"spec": "increment:2", "tenant": "t"})
        assert code == 429 and "minute" in body["error"]
    finally:
        svc.shutdown()


# ---------------------------------------------------------------------------
# Speclint admission gate
# ---------------------------------------------------------------------------


class RngNextStateModel(Model):
    """STR1xx fixture: `next_state` flips a hidden coin."""

    def init_states(self):
        return [0]

    def actions(self, state, actions: List) -> None:
        actions.append("step")

    def next_state(self, state, action):
        return (state + random.randint(0, 1 << 30)) % 97

    def properties(self):
        return [Property.always("true", lambda _m, _s: True)]


def test_lint_admission_gate_rejects_with_strxxx_codes(server, monkeypatch):
    from stateright_tpu.analysis import __main__ as registry

    monkeypatch.setitem(registry.BUNDLED, "broken", RngNextStateModel)
    code, body = _req(server, "POST", "/submit", {"spec": "broken"})
    assert code == 422
    assert "speclint" in body["error"]
    codes = {d["code"] for d in body["diagnostics"]["diagnostics"]}
    assert codes & {"STR101", "STR102"}
    telemetry = _req(server, "GET", "/metrics")[1]
    assert telemetry["serve_rejected_lint"] >= 1


class CallbackIncrementTensor(IncrementTensor):
    """STR601 fixture: a host callback hidden inside the hot-loop program.

    The state-space families cannot see it (numpy and jax evaluations
    agree — the probe is multiplied by zero); only the STR6xx program
    lint, which scans the traced jaxpr, catches it.
    """

    def step_lanes(self, xp, lanes):
        succs, masks = super().step_lanes(xp, lanes)
        if xp is not np:
            import jax

            probe = jax.pure_callback(
                lambda x: np.asarray(x, dtype=np.uint32),
                jax.ShapeDtypeStruct(lanes[0].shape, np.uint32),
                lanes[0],
            )
            first = list(succs[0])
            first[0] = first[0] + probe * xp.uint32(0)
            succs = [tuple(first)] + list(succs[1:])
        return succs, masks


def test_proglint_admission_gate_rejects_compiled_program(server, monkeypatch):
    """A spec whose COMPILED program is broken is refused before any
    compile, and the refusal is attributed to the program family."""
    from stateright_tpu.analysis import __main__ as registry

    monkeypatch.setitem(
        registry.BUNDLED, "callback-broken", CallbackIncrementTensor
    )
    code, body = _req(server, "POST", "/submit", {"spec": "callback-broken:2"})
    assert code == 422
    assert "speclint" in body["error"]
    codes = {d["code"] for d in body["diagnostics"]["diagnostics"]}
    assert "STR601" in codes
    telemetry = _req(server, "GET", "/metrics")[1]
    assert telemetry["serve_rejected_proglint"] >= 1
    assert telemetry["serve_rejected_lint"] >= 1


# ---------------------------------------------------------------------------
# Multiplexed lanes: parity with individual host spawn_bfs runs
# ---------------------------------------------------------------------------


def _host(tm):
    return TensorModelAdapter(tm).checker().spawn_bfs().join()


@pytest.mark.parametrize(
    "factory,golden_unique",
    [(lambda: IncrementTensor(2), 13), (lambda: TwoPhaseTensor(3), 288)],
    ids=["increment", "2pc-3"],
)
def test_multiplexed_lanes_match_spawn_bfs(factory, golden_unique):
    host = _host(factory())
    builders = [TensorModelAdapter(factory()).checker() for _ in range(4)]
    lanes = run_multiplexed(builders, lanes=4)
    assert len(lanes) == 4
    for lane in lanes:
        assert lane.unique_state_count() == host.unique_state_count()
        assert lane.unique_state_count() == golden_unique
        assert lane.state_count() == host.state_count()
        assert lane.max_depth() == host.max_depth()
        assert sorted(lane.discoveries()) == sorted(host.discoveries())
        for name, path in lane.discoveries().items():
            # BFS finds shallowest counterexamples: depths must agree
            # (the tie-broken path itself may differ).
            assert len(path) == len(host.discoveries()[name])
            assert path.explain(lane.model())  # replayable forensics
        telemetry = lane.telemetry()
        assert telemetry["eras"] == 1
        assert "small_workload_hint" not in telemetry


def test_multiplexed_batch_wider_than_lanes_dispatches_twice():
    builders = [
        TensorModelAdapter(IncrementTensor(2)).checker() for _ in range(5)
    ]
    lanes = run_multiplexed(builders, lanes=4)
    assert [c.unique_state_count() for c in lanes] == [13] * 5


def test_multiplexed_rejects_unsupported_options():
    builder = TensorModelAdapter(IncrementTensor(2)).checker().timeout(1.0)
    with pytest.raises(ValueError, match="timeouts"):
        run_multiplexed([builder], lanes=4)


def test_mixed_signatures_rejected():
    builders = [
        TensorModelAdapter(IncrementTensor(2)).checker(),
        TensorModelAdapter(IncrementTensor(3)).checker(),
    ]
    with pytest.raises(ValueError, match="signature"):
        run_multiplexed(builders, lanes=4)


# ---------------------------------------------------------------------------
# Small-workload guard: multiplexed lanes ARE the intended small path
# ---------------------------------------------------------------------------


def test_small_workload_hint_fires(capsys):
    # 2pc-3 explores 288 states, far below the ~10k crossover where the
    # device engine's dispatch overhead stops paying for itself.
    c = (
        TensorModelAdapter(TwoPhaseTensor(3))
        .checker()
        .spawn_tpu_bfs(chunk_size=64, queue_capacity=1 << 10, table_capacity=1 << 10)
        .join()
    )
    assert c.telemetry().get("small_workload_hint") == 288
    err = capsys.readouterr().err
    assert "spawn_bfs() on the host" in err
    # One line only, even though both the spawn-time and run-end checks see
    # a small number.
    assert err.count("small workload") == 1


def test_small_workload_hint_suppressed_for_multiplexed_lane(capsys):
    checker = (
        TensorModelAdapter(TwoPhaseTensor(3))
        .checker()
        .multiplex_lane()
        .spawn_tpu_bfs(
            chunk_size=64, queue_capacity=1 << 10, table_capacity=1 << 10
        )
        .join()
    )
    # Same 288-state run that fires the hint above —
    # flagged as a multiplexed lane it must stay silent.
    assert checker.unique_state_count() == 288
    assert "small_workload_hint" not in checker.telemetry()
    assert "small workload" not in capsys.readouterr().err


# ---------------------------------------------------------------------------
# Build/run split primitives
# ---------------------------------------------------------------------------


def test_model_signature_stable_across_instances():
    assert model_signature(IncrementTensor(2)) == model_signature(
        IncrementTensor(2)
    )
    assert model_signature(IncrementTensor(2)) != model_signature(
        IncrementTensor(3)
    )
    tm_a, sig = intern_model(IncrementTensor(2))
    tm_b, _ = intern_model(IncrementTensor(2))
    assert tm_a is tm_b  # one canonical instance -> id(tm) jit caches hit


def test_executable_cache_keys_by_shape_and_options():
    cache = ExecutableCache(capacity=4)
    a, hit_a = cache.get(IncrementTensor(2), "multiplex", lanes=4, chunk=64)
    assert not hit_a
    b, hit_b = cache.get(IncrementTensor(2), "multiplex", lanes=4, chunk=64)
    assert hit_b and b is a
    _, hit_c = cache.get(IncrementTensor(2), "multiplex", lanes=8, chunk=64)
    assert not hit_c  # different shape options = different executable
    stats = cache.stats()
    assert stats == {"hits": 1, "misses": 2, "size": 2, "capacity": 4}
