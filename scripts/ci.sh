#!/usr/bin/env bash
# The CI gate, reproducible locally with one command:
#
#   scripts/ci.sh
#
# Fourteen stages, fail-fast:
#   1. ruff over the repo (mechanical lint scope; see ruff.toml) — a hard
#      failure when $CI is set, a loud skip on dev machines without it,
#   2. the speclint dogfood — every bundled model must analyze with zero
#      error-severity findings (`python -m stateright_tpu.analysis`),
#   3. the proglint dogfood — every bundled TensorModel's device programs
#      must pass the deep STR6xx tier (`--program`: transfer/donation/
#      dtype detectors, the committed op-count budgets, the STR606 cost
#      model), and a deliberately perturbed budget file must TRIP the
#      STR604 gate — proving the ratchet actually fails CI,
#   4. a profiler smoke: one tiny device-engine run under
#      `.profile(dir)` must write a profile and record its phase timers
#      as profiled spans (`profiled_phase_totals`),
#   5. a conformance smoke: the replicated counter runs ~1s on loopback
#      UDP under seeded drop/duplicate/delay faults, records a trace, and
#      the trace must conform against the actor model with ZERO
#      divergences and yield a nonzero linearizable client history,
#   6. a netobs smoke: a ~1s faulted counter run on every available
#      engine with a live NetObs attached — the live fault-kind counters
#      must match the trace's recorded fault lines exactly, the Chrome
#      flow events must balance 1:1 (every `s` start has its `f` finish,
#      one pair per matched delivery), and `GET /deployment` must serve
#      the topology + per-link edges from the recorded trace,
#   7. a serve smoke: the run server admits a 2pc-3 check plus a batch of
#      8 small increment checks over REST, multiplexes the batch into one
#      fused executable, matches the golden state counts, and reports an
#      executable-cache hit on resubmission,
#   8. a durability smoke: a checkpointed 2pc-5 device run is stopped
#      mid-flight, resumed from its crash-safe checkpoint to the exact
#      golden, and a journaled run service is killed with queued jobs and
#      restarted — every job must recover and finish,
#   9. an observability smoke: one submitted job must yield span events
#      over the /events SSE stream, histogram _bucket series in
#      /metrics.prom, and a Chrome-trace export that JSON-parses with
#      matching B/E pairs,
#  10. a pipelining smoke: a tiny run with speculative era dispatch
#      forced ON (many short eras) must golden-match the serial driver
#      bit-for-bit and report a flight summary with `host_gap_pct`,
#  11. a memory smoke: the capacity planner predicts a small run's
#      footprint before dispatch, the run's memory ledger must match
#      the live buffers' nbytes EXACTLY and the planner's prediction,
#      and the `memory_bytes{component=...}` series must render in the
#      Prometheus exposition,
#  12. a space smoke: the deterministic bottom-k state sample from a
#      pipelined device run must equal the host oracle's sample
#      EXACTLY, the profile must carry field sketches, and the
#      `space_*` gauges must render in the Prometheus exposition,
#  13. an out-of-core smoke: 2pc-5 under a device byte cap AND a spill
#      host-RAM budget small enough to force the frontier onto the disk
#      tier, with delta checkpoints at a tight cadence — must match the
#      8,832 golden bit-for-bit while having tiered spill to disk (and
#      refilled every row back), fired >= 1 forecast-triggered proactive
#      reshard, written >= 2 delta checkpoint generations, and kept the
#      mean delta save strictly smaller than the mean full save,
#  14. the tier-1 pytest line from ROADMAP.md (host/CPU; the device
#      goldens run under JAX_PLATFORMS=cpu like the test suite does).
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== ruff =="
if command -v ruff >/dev/null 2>&1; then
  ruff check .
elif python -m ruff --version >/dev/null 2>&1; then
  python -m ruff check .
elif [ -n "${CI:-}" ]; then
  # A CI lane without the linter is a misconfigured lane, not a lane
  # that gets to skip linting.
  echo "ERROR: \$CI is set but ruff is not installed" >&2
  exit 1
else
  # Dev machines stay runnable without the linter baked in; skipping is
  # LOUD so the gap is still visible.
  echo "WARNING: ruff not installed; skipping the lint stage" >&2
fi

echo "== speclint dogfood =="
for model in 2pc:4 2pc-host:3 abd:2 abd-ordered:2 binary-clock \
             increment:2 increment-host:2 increment-lock:2 \
             increment-lock-host:2 linear-equation:1,2,20 \
             linearizable-register:2,2 lww-register:2 paxos:2 \
             single-copy:2,2 write-once-register:2; do
  echo "-- $model"
  JAX_PLATFORMS=cpu python -m stateright_tpu.analysis "$model"
done

echo "== proglint dogfood =="
# The deep STR6xx tier over every bundled TensorModel: trace + scan all
# five device programs, gate op counts against the committed budgets
# (analysis/op_budgets.json), and run the STR606 compile + cost model.
for model in 2pc:4 2pc:7 abd:2 abd-ordered:2 increment:2 \
             increment-lock:2 paxos:2 single-copy:2,2; do
  echo "-- $model"
  JAX_PLATFORMS=cpu python -m stateright_tpu.analysis "$model" --program
done

# The ratchet must-fail smoke: shrink one committed budget by one op so
# the measured count EXCEEDS it — the STR604 gate must fail the lint.
proglint_tmp="$(mktemp -d /tmp/_proglint_smoke.XXXXXX)"
JAX_PLATFORMS=cpu python - "$proglint_tmp/budgets.json" <<'PY'
import json
import sys

from stateright_tpu.analysis.program import BUDGETS_PATH
from stateright_tpu.engines.compiled import model_signature
from stateright_tpu.models import TwoPhaseTensor

doc = json.load(open(BUDGETS_PATH))
key = f"tpu_bfs|{model_signature(TwoPhaseTensor(4))}"
doc["entries"][key]["ops"] -= 1
print(f"perturbed {key}: budget now {doc['entries'][key]['ops']} ops")
json.dump(doc, open(sys.argv[1], "w"))
PY
if JAX_PLATFORMS=cpu python -m stateright_tpu.analysis 2pc:4 --program \
   --budgets "$proglint_tmp/budgets.json"; then
  echo "proglint smoke FAILED: op-count growth passed the STR604 gate" >&2
  exit 1
fi
rm -rf "$proglint_tmp"
echo "proglint smoke OK: budgets green, perturbed budget tripped STR604"

echo "== profiler smoke =="
JAX_PLATFORMS=cpu python - <<'PY'
import glob
import tempfile

from stateright_tpu.models import TwoPhaseTensor
from stateright_tpu.obs import profiled_phase_totals
from stateright_tpu.tensor import TensorModelAdapter

out = tempfile.mkdtemp()
c = (
    TensorModelAdapter(TwoPhaseTensor(3))
    .checker()
    .profile(out)
    .spawn_tpu_bfs(chunk_size=64, queue_capacity=1 << 10, table_capacity=1 << 10)
    .join()
)
assert c.unique_state_count() == 288
assert glob.glob(f"{out}/plugins/profile/*/*.xplane.pb"), "no profile written"
totals = profiled_phase_totals()
for phase in ("run_setup", "era_dispatch", "readback", "era_host"):
    assert totals.get(phase, {}).get("count", 0) >= 1, (phase, totals)
print(f"profiler smoke OK: {len(totals)} phases recorded as profiled spans")
PY

echo "== conformance smoke =="
JAX_PLATFORMS=cpu python - <<'PY'
from examples.increment import conform_counter_trace, record_counter_demo

path = "/tmp/_conform_smoke.jsonl"
record_counter_demo(path, duration=1.0, seed=7, base_port=46100, client_count=2)
report, tester = conform_counter_trace(path, client_count=2)
print(report.format())
assert not report.divergences, report.format()
assert tester.serialized_history() is not None and len(tester) > 0, (
    "expected a nonzero linearizable client history"
)
print(f"conformance smoke OK: {report.steps} steps, {len(tester)} history ops")
PY

echo "== netobs smoke =="
JAX_PLATFORMS=cpu python - <<'PY'
import collections
import json
import os
import tempfile
import urllib.request

from examples.increment import counter_model, record_counter_demo
from stateright_tpu.conformance import load_trace
from stateright_tpu.explorer.server import serve
from stateright_tpu.native import runtime as native_runtime
from stateright_tpu.obs.netobs import NetObs, assign_lamport, export_chrome_trace

tmp = tempfile.mkdtemp(prefix="_netobs_smoke.")
engines = ["python"] + (["native"] if native_runtime.is_available() else [])
for i, engine in enumerate(engines):
    path = os.path.join(tmp, f"{engine}.jsonl")
    nob = NetObs()
    # ~1s faulted counter run, live-instrumented on both engines.
    record_counter_demo(
        path, duration=1.0, seed=7, base_port=46600 + 10 * i,
        client_count=2, engine=engine, netobs=nob,
    )
    meta, events = load_trace(path)
    assert meta["v"] == 2 and meta["faults"]["seed"] == 7, meta.get("faults")

    # Live fault counters must match the trace's recorded fault lines.
    recorded = collections.Counter(
        ev["fault"] for ev in events if ev["kind"] == "fault"
    )
    live = nob.snapshot().get("fault_injected", {})
    assert dict(recorded) == live, (engine, dict(recorded), live)
    assert recorded, "seeded plan injected no faults"

    # Chrome flow events must balance: every s has its f, 1:1 by id.
    out = os.path.join(tmp, f"{engine}.chrome.json")
    pairs = export_chrome_trace((meta, events), out)
    records = json.load(open(out))
    starts = {r["id"] for r in records if r.get("ph") == "s"}
    finishes = {r["id"] for r in records if r.get("ph") == "f"}
    assert starts == finishes and len(starts) == pairs, (engine, pairs)
    matched = sum(
        1 for ev in assign_lamport(events)
        if ev["kind"] == "deliver" and "sent_by" in ev
    )
    assert pairs == matched, (engine, pairs, matched)
    print(f"  {engine}: {sum(recorded.values())} faults, {pairs} flow pairs")

# GET /deployment must serve topology + edges from the recorded trace.
server = serve(
    counter_model(2).checker(), "127.0.0.1:0", block=False,
    trace=os.path.join(tmp, "python.jsonl"),
)
try:
    body = json.loads(
        urllib.request.urlopen(server.url.rstrip("/") + "/deployment").read()
    )
    assert body["actors"] and body["edges"] and body["tail"], body.keys()
finally:
    server.shutdown()
print(f"netobs smoke OK: {len(engines)} engines, /deployment serves")
PY

echo "== serve smoke =="
JAX_PLATFORMS=cpu python - <<'PY'
import json
import time
import urllib.request

from stateright_tpu import TensorModelAdapter
from stateright_tpu.models import TwoPhaseTensor
from stateright_tpu.serve import RunService, ServeServer

# Host oracle for the 2pc-3 golden (288 uniques) before anything serves.
oracle = TensorModelAdapter(TwoPhaseTensor(3)).checker().spawn_bfs().join()
assert oracle.unique_state_count() == 288, oracle.unique_state_count()

service = RunService(workers=1, lanes=8, lint_samples=32)
server = ServeServer(service, "127.0.0.1:0").serve_in_background()
base = server.url.rstrip("/")


def req(method, path, body=None):
    data = json.dumps(body).encode() if body is not None else None
    r = urllib.request.Request(base + path, data=data, method=method)
    with urllib.request.urlopen(r) as resp:
        return json.loads(resp.read())


req("POST", "/scheduler/pause")
inc_ids = [
    req("POST", "/submit", {"spec": "increment:2"})["job_id"] for _ in range(8)
]
tpc_id = req("POST", "/submit", {"spec": "2pc:3"})["job_id"]
req("POST", "/scheduler/resume")

deadline = time.time() + 600
while time.time() < deadline:
    views = req("GET", "/jobs")["jobs"]
    if all(v["status"] not in ("queued", "running") for v in views):
        break
    time.sleep(0.2)
for v in req("GET", "/jobs")["jobs"]:
    assert v["status"] == "done", v

for job_id in inc_ids:
    result = req("GET", f"/jobs/{job_id}/result")["result"]
    assert result["unique_state_count"] == 13, result
    assert result["engine"] == "multiplex", result
tpc = req("GET", f"/jobs/{tpc_id}/result")["result"]
assert tpc["unique_state_count"] == oracle.unique_state_count(), tpc

# Same-shape resubmission must hit the executable cache.
before = req("GET", "/stats")["cache"]
job_id = req("POST", "/submit", {"spec": "increment:2"})["job_id"]
while req("GET", f"/jobs/{job_id}")["status"] in ("queued", "running"):
    time.sleep(0.2)
after = req("GET", "/stats")["cache"]
assert after["hits"] == before["hits"] + 1, (before, after)
assert after["misses"] == before["misses"], (before, after)
server.shutdown()
print(
    f"serve smoke OK: 8 multiplexed + 2pc-3 golden-matched, "
    f"cache {after['hits']} hits / {after['misses']} misses"
)
PY

echo "== durability smoke =="
JAX_PLATFORMS=cpu python - <<'PY'
import os
import tempfile
import time

from stateright_tpu import TensorModelAdapter
from stateright_tpu.models import TwoPhaseTensor
from stateright_tpu.serve import RunService

tmp = tempfile.mkdtemp(prefix="_dura_smoke.")
opts = dict(chunk_size=64, queue_capacity=1 << 12, table_capacity=1 << 11)

# Crash-safe checkpoints: stop a 2pc-5 run mid-flight, then resume the
# checkpoint to the exact golden (8,832 uniques).
ckpt = os.path.join(tmp, "2pc5.ckpt.npz")
part = (
    TensorModelAdapter(TwoPhaseTensor(5))
    .checker()
    .target_state_count(3_000)
    .spawn_tpu_bfs(checkpoint_path=ckpt, **opts)
    .join()
)
assert 0 < part.unique_state_count() < 8832, part.unique_state_count()
assert os.path.exists(ckpt)
resumed = (
    TensorModelAdapter(TwoPhaseTensor(5))
    .checker()
    .spawn_tpu_bfs(resume_from=ckpt, **opts)
    .join()
)
assert resumed.unique_state_count() == 8832, resumed.unique_state_count()

# Serve journal recovery: kill a service with queued jobs, restart on the
# same journal, and every job must finish with its result served.
dura = dict(
    journal_path=os.path.join(tmp, "jobs.jsonl"),
    results_dir=os.path.join(tmp, "results"),
)
svc = RunService(workers=1, lint_samples=32, **dura)
svc.pause()
ids = [svc.submit({"spec": "increment:2"})[1]["job_id"] for _ in range(3)]
svc.shutdown()  # "crash" with everything still queued

svc = RunService(workers=1, lint_samples=32, **dura)
assert svc.telemetry().get("journal_recovered_queued") == 3
deadline = time.time() + 600
while time.time() < deadline:
    if all(svc.job(i).status not in ("queued", "running") for i in ids):
        break
    time.sleep(0.2)
for i in ids:
    job = svc.job(i)
    assert job.status == "done", (i, job.status, job.error)
    assert job.result["unique_state_count"] == 13, job.result
svc.shutdown()
print("durability smoke OK: checkpoint resumed to 8832; 3 jobs recovered")
PY

echo "== observability smoke =="
JAX_PLATFORMS=cpu python - <<'PY'
import json
import os
import tempfile
import time
import urllib.request

from stateright_tpu.serve import RunService, ServeServer

service = RunService(workers=1, lanes=8, lint_samples=32)
server = ServeServer(service, "127.0.0.1:0").serve_in_background()
base = server.url.rstrip("/")


def req(method, path, body=None):
    data = json.dumps(body).encode() if body is not None else None
    r = urllib.request.Request(base + path, data=data, method=method)
    with urllib.request.urlopen(r) as resp:
        return json.loads(resp.read())


body = req("POST", "/submit", {"spec": "increment:2"})
job_id, trace_id = body["job_id"], body["trace_id"]
while req("GET", f"/jobs/{job_id}")["status"] in ("queued", "running"):
    time.sleep(0.2)
assert req("GET", f"/jobs/{job_id}")["status"] == "done"

# The /events SSE stream must yield span events (replay seeds the
# already-finished job's ledger; limit+duration bound the read).
raw = urllib.request.urlopen(
    f"{base}/events?replay=50&limit=5&duration=5"
).read().decode()
span_events = [
    json.loads(blk.split("data: ", 1)[1])
    for blk in raw.split("\n\n")
    if blk.startswith("event: span")
]
assert span_events, raw[:400]
names = {s["name"] for s in span_events}
assert names & {"job", "admission", "queue_wait", "execute"}, names

# The job's full ledger hangs off /jobs/{id}/trace in submit order.
ledger = req("GET", f"/jobs/{job_id}/trace")
assert ledger["trace_id"] == trace_id
lnames = [s["name"] for s in ledger["spans"]]
for expected in ("admission", "queue_wait", "execute", "job"):
    assert expected in lnames, lnames

# Prometheus exposition must carry the latency histogram series.
prom = urllib.request.urlopen(f"{base}/metrics.prom").read().decode()
assert "_bucket{le=" in prom, prom[:400]
assert "submit_to_result_secs_count" in prom, prom[:400]

# The exported Chrome trace must JSON-parse with matching B/E pairs.
from stateright_tpu.obs.spans import spans_to_chrome

out = os.path.join(tempfile.mkdtemp(prefix="_obs_smoke."), "trace.json")
service.spans.export_chrome(out)
with open(out) as fh:
    events = json.load(fh)
begins = sum(1 for e in events if e.get("ph") == "B")
ends = sum(1 for e in events if e.get("ph") == "E")
assert begins and begins == ends, (begins, ends)
assert begins == len(spans_to_chrome(service.spans.spans())) // 2

server.shutdown()
print(
    f"observability smoke OK: {len(span_events)} SSE spans, "
    f"{len(ledger['spans'])}-span job ledger, {begins} B/E pairs"
)
PY

echo "== pipelining smoke =="
JAX_PLATFORMS=cpu python - <<'PY'
from stateright_tpu import TensorModelAdapter
from stateright_tpu.models import TwoPhaseTensor

# Many short eras (sync_steps=4) so speculative chains actually engage.
opts = dict(
    chunk_size=64, queue_capacity=1 << 12, table_capacity=1 << 11,
    sync_steps=4,
)


def run(pipelined):
    c = (
        TensorModelAdapter(TwoPhaseTensor(5))
        .checker()
        .coverage()
        .pipeline(pipelined)
        .spawn_tpu_bfs(**opts)
        .join()
    )
    cov = c.coverage()
    return c, (
        c.unique_state_count(), c.state_count(), c.max_depth(),
        dict(c._discovery_fps), cov["actions"], cov["depths"],
    )


piped, fp_on = run(True)
_serial, fp_off = run(False)
assert fp_on[0] == 8832, fp_on[0]
assert fp_on == fp_off, "pipelined run diverged from the serial driver"
tel = piped.telemetry()
assert tel.get("spec_dispatch", 0) >= 1, "pipelining never speculated"
fsum = tel["flight"]
assert "host_gap_pct" in fsum, fsum
print(
    f"pipelining smoke OK: 8832 uniques golden-match serial, "
    f"{tel['spec_dispatch']} speculative dispatches "
    f"({tel.get('spec_wasted', 0)} wasted), "
    f"host_gap_pct={fsum['host_gap_pct']}"
)
PY

echo "== memory smoke =="
JAX_PLATFORMS=cpu python - <<'PY'
from stateright_tpu import TensorModelAdapter
from stateright_tpu.models import TwoPhaseTensor
from stateright_tpu.obs.memory import plan
from stateright_tpu.obs.metrics import MEMORY_SERIES_LABELS, render_prometheus

# Plan BEFORE dispatch at a fixed no-growth geometry...
geometry = dict(chunk=256, queue_capacity=1 << 12, table_capacity=1 << 15)
model = TensorModelAdapter(TwoPhaseTensor(3))
p = plan(model, engine="tpu_bfs", **geometry)
assert p["total_bytes"] > 0, p

# ...then run at the same geometry: the ledger must equal BOTH the live
# buffers' nbytes and the planner's prediction, exactly.
c = (
    model.checker()
    .spawn_tpu_bfs(
        chunk_size=geometry["chunk"],
        queue_capacity=geometry["queue_capacity"],
        table_capacity=geometry["table_capacity"],
    )
    .join()
)
assert c.unique_state_count() == 288, c.unique_state_count()
snap = c.telemetry()["memory"]
assert snap["total_bytes"] == c._memory.ledger.live_nbytes(), snap
assert snap["total_bytes"] == p["total_bytes"], (snap["total_bytes"], p)

# The per-component residency must land in the Prometheus exposition.
prom = render_prometheus(c.telemetry(), labels=MEMORY_SERIES_LABELS)
assert 'memory_bytes{component="visited_table"}' in prom, prom[:400]
print(
    f"memory smoke OK: plan == ledger == nbytes == {p['total_bytes']} B "
    f"across {len(snap['components'])} components"
)
PY

echo "== space smoke =="
JAX_PLATFORMS=cpu python - <<'PY'
from stateright_tpu import TensorModelAdapter
from stateright_tpu.models import IncrementTensor, TwoPhaseTensor
from stateright_tpu.obs.metrics import render_prometheus

# The sample is a pure function of the explored set: the pipelined
# device run must produce the host oracle's sample bit-for-bit.
host = (
    TensorModelAdapter(TwoPhaseTensor(4)).checker().sample(k=64)
    .spawn_bfs().join()
)
dev = (
    TensorModelAdapter(TwoPhaseTensor(4)).checker().sample(k=64)
    .spawn_tpu_bfs(chunk_size=64, queue_capacity=1 << 12,
                   table_capacity=1 << 11)
    .join()
)
assert dev.unique_state_count() == 1568, dev.unique_state_count()
hfps, dfps = host._sampler.fingerprints(), dev._sampler.fingerprints()
assert dfps == hfps, "device sample diverged from the host oracle"
assert not dev._sampler.degraded

profile = dev.space_profile()
assert profile["fields"], profile.keys()
assert profile["unresolved"] == 0, profile["unresolved"]
assert profile["depths"] and profile["actions"]

# Below k the sample IS the space: KMV estimate exact on increment.
tiny = (
    TensorModelAdapter(IncrementTensor(2)).checker().sample(k=64)
    .spawn_bfs().join()
)
assert tiny.telemetry()["space"]["est_states"] == 13

# The flat gauges must land in the Prometheus exposition.
prom = render_prometheus(dev.telemetry())
assert "space_samples 64" in prom, prom[:400]
assert "space_est_states" in prom, prom[:400]
print(
    f"space smoke OK: 64-sample parity on 2pc-4, "
    f"est_states={profile['est_states']}, "
    f"{len(profile['fields'])} field sketches"
)
PY

echo "== out-of-core smoke =="
_OC_TMP=$(mktemp -d)
JAX_PLATFORMS=cpu STPU_OC_TMP="$_OC_TMP" python - <<'PY'
import os

# Uncapped oracle FIRST — the caps are read from the environment at
# engine construction, so the reference spawns before they exist.
from stateright_tpu import TensorModelAdapter
from stateright_tpu.models import TwoPhaseTensor


def fingerprint(c):
    return (c.unique_state_count(), c.state_count(), c.max_depth(),
            dict(c._discovery_fps))


# chunk 32 / queue 1<<10: small enough that the 2pc-5 frontier overflows
# the device queue AND the 8 KiB host budget, pushing spill blocks onto
# the npz disk tier; sync_steps 4 gives the forecaster many short eras.
opts = dict(chunk_size=32, queue_capacity=1 << 10, table_capacity=1 << 8,
            sync_steps=4)
ref = TensorModelAdapter(TwoPhaseTensor(5)).checker().spawn_tpu_bfs(**opts).join()
assert ref.unique_state_count() == 8832, ref.unique_state_count()

os.environ["STPU_DEVICE_MEMORY_BYTES"] = "300000"   # forces exhaustion forecast
os.environ["STPU_SPILL_HOST_BUDGET_BYTES"] = "8192"  # forces the disk tier
ckpt = os.path.join(os.environ["STPU_OC_TMP"], "oc.ckpt.npz")
capped = (
    TensorModelAdapter(TwoPhaseTensor(5)).checker()
    .spawn_tpu_bfs(checkpoint_path=ckpt, checkpoint_every=1e-4, **opts)
    .join()
)
tel = capped.telemetry()
assert fingerprint(capped) == fingerprint(ref), "capped run diverged"
assert tel.get("spill_tier_rows", 0) > 0, "no frontier rows hit the disk tier"
assert tel.get("spill_tier_refill_rows") == tel.get("spill_tier_rows"), (
    "disk tier not fully refilled", tel.get("spill_tier_rows"),
    tel.get("spill_tier_refill_rows"))
assert tel.get("reshard_proactive", 0) >= 1, "no proactive reshard fired"
assert tel.get("checkpoint_delta_saves", 0) >= 2, tel.get("checkpoint_delta_saves")
delta_per = tel["checkpoint_delta_bytes"] / tel["checkpoint_delta_saves"]
full_per = tel["checkpoint_bytes"] / tel["checkpoint_saves"]
assert delta_per < full_per, (delta_per, full_per)
print(
    f"out-of-core smoke OK: 8832 golden under 300 KB cap, "
    f"{tel['spill_tier_rows']} rows tiered to disk and refilled, "
    f"{tel['reshard_proactive']} proactive reshards, "
    f"{tel['checkpoint_delta_saves']} delta saves "
    f"({delta_per / 1024:.1f} KiB/delta vs {full_per / 1024:.1f} KiB/full)"
)
PY
rm -rf "$_OC_TMP"

echo "== tier-1 tests =="
set -o pipefail
rm -f /tmp/_t1.log
timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' \
  --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly \
  2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)
exit $rc
