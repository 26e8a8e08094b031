"""Benchmark: the BASELINE.md metrics on the device engine.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "detail"}
(progressively refined — each section re-prints the line so a harness
timeout still leaves the latest complete refinement).

Headline (continuity with earlier rounds): generated states/sec on the
exhaustive 2pc-7 check, device engine, single chip. `vs_baseline` is the
speedup over the THREADED host engine (vbfs: numpy lane batches + the
native concurrent visited set, .threads(8)) on the same workload —
divided by the RECORDED reference rate pinned in
`TPC7_HOST_THREADED_REFERENCE_RATE` (round-7 change: the earlier
same-run live host race made the headline ratio noisy; the live rate
still rides along in detail as `host_threaded_rate` /
`vs_host_threaded_live` for drift detection, and `vs_host_single`
keeps continuity with the pre-round-5 single-threaded comparison).

Measurement discipline: every timed device workload runs 3x warm, median
with min/max spread (bench.sh runs each workload 3x for the same reason);
all timings are call + host-readback wall time.

Per-phase detail: checker.telemetry() now returns the engine's metrics
registry (obs/metrics.py) — counters, gauges, AND cumulative per-phase
wall millis (device_era / readback / spill / refill / table_grow) — so
the BENCH_*.json telemetry blocks carry a phase breakdown of where each
workload's wall time went, not just end-to-end seconds.

Workload parity vs /root/reference/bench.sh:27-34:
  - `2pc check 10`  -> device exhaustive, 61,515,776 golden (and the
    265,719-representative canonical closure under device symmetry,
    231x reduction)
  - `paxos check 6` -> device exhaustive, 9,357,525 golden (plus
    paxos-3, the BASELINE.json north star; space growth measured at
    ~x2/client past c=3 with the capacity and ballot-round encoding
    guards quiet)
  - `single-copy-register check 4` -> represented by the 3x2
    time-to-first-counterexample line (first linearizability violation,
    not an exhaustive count)
  - `linearizable-register check 2` -> device exhaustive (544)
  - `linearizable-register check 3 ordered` -> device exhaustive
    (46,516) via the round-5 ordered-network lane encoding
Plus: device symmetry reduction, batched device simulation TTFC, and the
fused seed+first-era TTFC lines. Full bench is ~35-45 minutes; sections
run cheapest-first and each one re-emits the JSON line when it lands.

Round 6 additions:
  - ROOFLINE (`python bench.py --roofline [BENCH.json]`, also emitted
    in-run as detail.roofline): analytic bytes-moved-per-generated-state
    through the probe/ring hot path vs HBM bandwidth — the memory-bound
    ceiling on states/sec and the bandwidth the 50M st/s north star
    implies.
  - PBFS: workers-vs-serial rates for the multiprocessing host engine on
    the RICH paxos-3 actor model (same bounded workload both ways) —
    the number behind README's "true parallelism beyond the GIL".
  - `single-copy-register check 4` run exhaustively (host oracle +
    device twin, golden-matched) instead of only the 3x2 TTFC line.

Perf history + regression gate:
  - `--history FILE` appends one compact summary row (JSONL: section
    rates, medians, latency quantiles, instrumentation overheads) after
    the run, so FILE accumulates a rolling perf record across rounds.
  - `--gate FILE` compares the run against the rolling baseline (the
    per-metric median of the last 5 history rows) and exits nonzero on
    any regression beyond budget (rates -15%; latency/overheads +25%
    with an absolute noise floor). The gate evaluates BEFORE the
    history append so a regressed run never poisons its own baseline.
  - `--from BENCH.json` applies either flag to a prior record with no
    device run and no jax import (CI's cheap path); `--smoke` runs a
    tiny 2pc-5 device workload instead of the full bench — the CI
    perf-gate smoke stage uses it, with BENCH_PERTURB_SLEEP (secs)
    injecting deliberate degradation to prove the gate trips.
"""

import json
import os
import statistics
import sys
import time

PAXOS2_GOLDEN = 16_668  # examples/paxos.rs:327
PAXOS3_GOLDEN = 1_194_428  # host-oracle run of PaxosTensorExhaustive(3)
PAXOS6_GOLDEN = 9_357_525  # threaded-host exhaustive run (round 5; the
# paxos space grows ~x2/client past c=3: 2.37M @ c4, 4.71M @ c5, 9.36M @ c6,
# with the capacity + ballot-round encoding guards quiet throughout)
TPC7_GOLDEN = 296_448  # EXACT-row oracle count of TwoPhaseTensor(7)
TPC7_HOST_THREADED_REFERENCE_RATE = 6_394_369.6  # generated states/sec of the
# threaded host oracle on 2pc-7 (vbfs, .threads(8)): mean of the recorded
# BENCH_r04 (6,491,078.6) and BENCH_r05 (6,297,660.5) runs. `vs_baseline`
# divides by THIS pinned reference so the headline ratio is stable
# run-to-run; the live same-run host rate still lands in detail
# (host_threaded_rate / vs_host_threaded_live) as a drift check.
TPC10_GOLDEN = 61_515_776  # threaded-host exhaustive run (round 4)
ABD3_ORDERED_GOLDEN = 46_516  # host actor-model exhaustive run (round 5)
TPC5_SYM_CLOSURE = 1_092  # deterministic canonical-closure golden
TPC10_SYM_CLOSURE = 265_719  # deterministic canonical-closure golden
SINGLE_COPY4_GOLDEN = 400_233  # host-oracle run of SingleCopyTensor(4)
# (4 clients / 1 server; linearizable HOLDS — the 3x2 TTFC line is the
# violating configuration, this one is the reference bench's exhaustive
# `single-copy-register check 4`)

# -- roofline: the memory-bandwidth ceiling on device states/sec --------------

# Lane-geometry constants of the device BFS hot path (engines/tpu_bfs.py):
RING_EXTRA_LANES = 2  # ebits + depth ride the ring beside the S state lanes
VISITED_LANES = 4  # key_h1, key_h2, parent_h1, parent_h2 (ops/visited_set.py)
LANE_BYTES = 4  # every lane is uint32

# Peak HBM bandwidth comes from the device-kind table single-sourced with
# the STR606 program-lint roofline (stateright_tpu/analysis/program.py
# HBM_GBPS_BY_KIND), so the analytic and the XLA-cost-model predictions
# never assume different hardware. A kind missing there is an error.


def roofline_report(
    state_width,
    max_actions,
    device_kind,
    generated=None,
    unique=None,
    measured_rate=None,
):
    """Analytic bytes-moved-per-GENERATED-state through the era hot path,
    and the states/sec ceiling that HBM bandwidth implies.

    The device BFS is memory-bound: every era step pops `take` ring rows
    (W = S+2 uint32 lanes), expands chunk*A candidate successors (S lanes
    materialized + re-read by the fingerprint pass), probes the visited
    table (PRIMARY_ROUNDS rounds x 2 gathered key lanes; the staged tail
    handles the straggler fraction and is amortized ~0 here), and for
    each NEW unique state scatters a 4-lane table insert plus a W-lane
    ring append. Summing those lane movements:

        bytes/generated = 2*S*4                 (expand write + hash read)
                        + PRIMARY_ROUNDS*2*4    (probe key gathers)
                        + W*4/A                 (ring pop, amortized)
                        + u * (4*4 + W*4)       (insert + append, u = unique/generated)

    This is a LOWER bound on traffic (compaction scratch, claim dedup,
    property masks and depth bookkeeping all move more lanes), so the
    st/s ceiling it yields is OPTIMISTIC — headroom numbers read as "at
    most this much is left on the table".
    """
    from stateright_tpu.analysis.program import hbm_gbps as _peak
    from stateright_tpu.ops.visited_set import PRIMARY_ROUNDS

    hbm_gbps = _peak(device_kind)
    if hbm_gbps is None:
        raise ValueError(
            f"no peak HBM bandwidth is known for device kind {device_kind!r}"
            " (analysis/program.py HBM_GBPS_BY_KIND)"
        )
    S = int(state_width)
    A = max(1, int(max_actions))
    W = S + RING_EXTRA_LANES
    u = (unique / generated) if (generated and unique) else 0.1
    probe_bytes = PRIMARY_ROUNDS * 2 * LANE_BYTES + u * VISITED_LANES * LANE_BYTES
    ring_bytes = W * LANE_BYTES / A + u * W * LANE_BYTES
    expand_hash_bytes = 2 * S * LANE_BYTES
    bytes_per_state = probe_bytes + ring_bytes + expand_hash_bytes
    ceiling = hbm_gbps * 1e9 / bytes_per_state
    out = {
        "device_kind": device_kind,
        "hbm_gbps_assumed": hbm_gbps,
        "state_width": S,
        "max_actions": A,
        "unique_per_generated": round(u, 4),
        "bytes_per_state": {
            "probe": round(probe_bytes, 2),
            "ring": round(ring_bytes, 2),
            "expand_hash": round(expand_hash_bytes, 2),
            "total": round(bytes_per_state, 2),
        },
        "ceiling_states_per_sec": round(ceiling, 1),
        "north_star_50M_needs_gbps": round(50e6 * bytes_per_state / 1e9, 2),
    }
    if measured_rate:
        out["measured_states_per_sec"] = round(measured_rate, 1)
        out["achieved_gbps"] = round(measured_rate * bytes_per_state / 1e9, 3)
        out["headroom_x"] = round(ceiling / measured_rate, 1)
    return out


def print_roofline(report, out=None):
    out = out if out is not None else sys.stderr
    bps = report["bytes_per_state"]
    out.write("-- roofline (probe/ring hot path, analytic lower bound) --\n")
    out.write(
        f"  peak HBM: {report['hbm_gbps_assumed']:.0f} GB/s"
        f"  ({report['device_kind']})\n"
    )
    out.write(
        f"  bytes/generated state: {bps['total']:.1f}"
        f"  (probe {bps['probe']:.1f}, ring {bps['ring']:.1f},"
        f" expand+hash {bps['expand_hash']:.1f};"
        f" unique/generated {report['unique_per_generated']})\n"
    )
    out.write(
        f"  bandwidth ceiling: {report['ceiling_states_per_sec']:,.0f} st/s;"
        f" 50M st/s north star needs"
        f" {report['north_star_50M_needs_gbps']:.1f} GB/s\n"
    )
    if "measured_states_per_sec" in report:
        out.write(
            f"  measured: {report['measured_states_per_sec']:,.0f} st/s"
            f" = {report['achieved_gbps']:.2f} GB/s moved"
            f" -> {report['headroom_x']:.0f}x headroom"
            " (dispatch/serialization-bound, not bandwidth-bound)\n"
        )


def timed3(mk_checker, golden=None, check=None):
    """Run a device workload 3x warm; return (median_secs, spread, last).

    BENCH_PERTURB_SLEEP (secs, float) injects a sleep INSIDE the timing
    window of every run — the deliberate-degradation knob the perf-gate
    smoke stage uses to prove `--gate` actually trips (ci.sh).
    """
    perturb = float(os.environ.get("BENCH_PERTURB_SLEEP", "0") or 0.0)
    secs = []
    last = None
    for _ in range(3):
        t0 = time.perf_counter()
        last = mk_checker().join()
        if perturb > 0.0:
            time.sleep(perturb)
        secs.append(time.perf_counter() - t0)
        if golden is not None:
            assert last.unique_state_count() == golden, (
                last.unique_state_count(),
                golden,
            )
        if check is not None:
            assert check(last)
    return statistics.median(secs), (min(secs), max(secs)), last


# -- BENCH json comparison (`python bench.py --compare A.json B.json`) --------


def _flatten_metrics(prefix, obj, out):
    """Dotted-path -> numeric value for every number in a BENCH record
    (bool excluded: golden_match deltas are not metrics)."""
    if isinstance(obj, dict):
        for k, v in obj.items():
            _flatten_metrics(f"{prefix}.{k}" if prefix else str(k), v, out)
    elif isinstance(obj, bool):
        pass
    elif isinstance(obj, (int, float)):
        out[prefix] = float(obj)


def _load_bench(path):
    """Last parseable JSON line of a BENCH file (bench re-emits the line
    as sections land; the last one is the most complete refinement).

    Accepts both raw bench stdout AND the driver's BENCH_rN.json wrapper,
    whose ``tail`` field holds the captured stdout — so
    ``--compare BENCH_r04.json BENCH_r05.json`` works on round artifacts
    as committed.
    """
    last = None
    with open(path) as f:
        text = f.read()
    for line in text.splitlines():
        line = line.strip()
        if not line:
            continue
        try:
            record = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(record, dict):
            last = record
    if last is None:
        # Not line-oriented: try the whole file as one (pretty-printed)
        # JSON document.
        try:
            record = json.loads(text)
        except json.JSONDecodeError:
            raise SystemExit(f"{path}: no JSON record found")
        if not isinstance(record, dict):
            raise SystemExit(f"{path}: no JSON record found")
        last = record
    if "metric" not in last and isinstance(last.get("tail"), str):
        inner = None
        for line in last["tail"].splitlines():
            line = line.strip()
            if not line:
                continue
            try:
                record = json.loads(line)
            except json.JSONDecodeError:
                continue
            if isinstance(record, dict):
                inner = record
        if inner is not None:
            last = inner
    return last


def compare_bench(path_a, path_b, out=None):
    """Per-metric/per-phase delta table between two BENCH json files.

    Makes regressions diagnosable from phase breakdowns instead of
    eyeballing JSON: every numeric leaf (rates, secs, telemetry counters,
    phase_ms entries, coverage counts) becomes one row with both values
    and the relative delta, sorted by path.
    """
    out = out if out is not None else sys.stdout
    a, b = _load_bench(path_a), _load_bench(path_b)
    fa, fb = {}, {}
    _flatten_metrics("", a, fa)
    _flatten_metrics("", b, fb)
    keys = sorted(set(fa) | set(fb))
    name_w = max((len(k) for k in keys), default=6)
    out.write(
        f"{'metric':<{name_w}}  {path_a:>14}  {path_b:>14}  {'delta':>12}  {'pct':>8}\n"
    )

    def fmt(v):
        if v is None:
            return "-"
        return f"{v:.3f}".rstrip("0").rstrip(".") if v % 1 else f"{int(v)}"

    for k in keys:
        va, vb = fa.get(k), fb.get(k)
        if va is None or vb is None:
            delta = pct = "-"
        else:
            delta = fmt(vb - va)
            pct = f"{(vb - va) / va * 100.0:+.1f}%" if va else "-"
        out.write(
            f"{k:<{name_w}}  {fmt(va):>14}  {fmt(vb):>14}  {delta:>12}  {pct:>8}\n"
        )

    def _focus(title, selected, unit=""):
        rows = [k for k in keys if selected(k)]
        if not rows:
            return
        out.write(f"\n{title}:\n")
        for k in rows:
            va, vb = fa.get(k), fb.get(k)
            pct = (
                f"{(vb - va) / va * 100.0:+.1f}%"
                if va not in (None, 0) and vb is not None
                else "-"
            )
            out.write(
                f"  {k:<{name_w}}  {fmt(va):>12}{unit}  ->"
                f"  {fmt(vb):>12}{unit}  {pct:>8}\n"
            )

    # Focused recaps of the observability sections so a review doesn't
    # have to fish them out of the flat dump: latency-histogram quantile
    # shifts, and the instrumented-overhead percentages (span ledger,
    # checkpointing, flight recorder).
    _focus(
        "latency quantiles (secs)",
        lambda k: ".latency." in k
        and k.rsplit(".", 1)[-1] in ("p50", "p95", "p99"),
    )
    _focus(
        "instrumentation overhead (pct of device rate)",
        lambda k: k.endswith("overhead_pct"),
    )
    return 0


# -- perf history + regression gate (`--history FILE` / `--gate FILE`) --------
#
# `--history FILE` appends one compact summary row (JSONL) per bench run;
# `--gate FILE` compares the current run against the rolling baseline —
# the per-metric median of the last GATE_BASELINE_WINDOW history rows —
# and exits nonzero on any regression beyond the metric's budget.
# Both accept `--from BENCH.json` to operate on a prior record without a
# device run (and without importing jax): CI's cheap path.

GATE_BASELINE_WINDOW = 5

# Direction inference by metric-name fragment. Higher-better: throughput
# rates and speedups, plus the mega-dispatch gauges — `spec_chain_depth`
# (how deep the speculative era chain actually got) and
# `fused_eras_per_dispatch` (eras folded into each compiled dispatch;
# checked before the lower-better "eras" fragment would claim it).
# Lower-better: wall times, latency quantiles, instrumentation overheads,
# the flight recorder's host-gap share (dispatch-bound idle time the
# pipelining work exists to remove), era and dispatch counts (fewer
# dispatches = deeper fusion = fewer host round-trips), and memory
# residency per unique state (ledger peak / unique — footprint
# regressions surface here). Keys matching neither stay out of the gate.
_GATE_HIGHER = (
    "states_per_sec", "checks_per_sec", "per_sec", "speedup",
    "spec_chain_depth", "fused_eras_per_dispatch",
    # Out-of-core: capped-run throughput as a % of the unconstrained run
    # on the same workload, and the auto-picked fusion factor (shallower
    # auto-fusion = the gap heuristic regressed).
    "retention_pct", "fuse_auto_n",
)
_GATE_LOWER = (
    "p50", "p95", "p99", "secs", "ms", "overhead_pct",
    "host_gap_pct", "eras", "dispatches", "bytes_per_state",
    # Out-of-core: mean npz bytes per checkpoint save — the delta
    # protocol's whole point is keeping this far below a full save.
    "bytes_per_save",
)

# Sections whose numeric leaves are environment/diagnostic detail, not
# performance contracts — excluded from the gated summary.
_GATE_EXCLUDE = (
    ".telemetry.",
    ".coverage.",
    ".speclint.",
    ".roofline.",
    ".flight.",
)


def _gate_direction(key):
    if key == "value":  # the headline states/sec
        return "higher"
    leaf = key.rsplit(".", 1)[-1]
    for frag in _GATE_HIGHER:
        if frag in leaf:
            return "higher"
    for frag in _GATE_LOWER:
        if frag in leaf:
            return "lower"
    return None


def bench_summary(record):
    """Compact, gate-relevant flat summary of one BENCH record: section
    rates and medians, latency quantiles, instrumentation overheads.
    This is the JSONL row ``--history`` appends and ``--gate`` compares."""
    flat = {}
    _flatten_metrics("", record, flat)
    return {
        key: value
        for key, value in sorted(flat.items())
        if not any(frag in key for frag in _GATE_EXCLUDE)
        and _gate_direction(key) is not None
    }


def load_history(path):
    """History rows (list of dicts), oldest first; [] when missing."""
    rows = []
    try:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    row = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if isinstance(row, dict):
                    rows.append(row)
    except OSError:
        pass
    return rows


def append_history(path, record):
    summary = bench_summary(record)
    with open(path, "a") as f:
        f.write(json.dumps(summary, sort_keys=True) + "\n")
    return summary


def _gate_check(key, base, cur):
    """None when `cur` is within budget of `base`, else a reason string.

    Rates get a 15% budget; latency/overhead metrics get 25% plus an
    absolute floor (0.05s-equivalent; 1.0 percentage point for
    `overhead_pct` / `host_gap_pct`) so near-zero baselines don't trip
    on noise.
    """
    if base <= 0:
        return None
    if _gate_direction(key) == "higher":
        if cur < base * (1.0 - 0.15):
            return f"{(cur / base - 1.0) * 100.0:+.1f}% (budget -15%)"
        return None
    floor = 1.0 if key.endswith(("overhead_pct", "host_gap_pct")) else 0.05
    if cur > base * (1.0 + 0.25) and cur - base > floor:
        return f"{(cur / base - 1.0) * 100.0:+.1f}% (budget +25%)"
    return None


def gate_bench(history_path, record, out=None):
    """Exit code for the perf gate: 0 when every metric shared with the
    rolling baseline (median of the last GATE_BASELINE_WINDOW history
    rows) is within budget, 1 on any regression. An empty or missing
    history passes — the first run seeds the baseline."""
    out = out if out is not None else sys.stdout
    rows = load_history(history_path)
    if not rows:
        out.write(f"perf gate: no history at {history_path} — pass (seed run)\n")
        return 0
    window = rows[-GATE_BASELINE_WINDOW:]
    summary = bench_summary(record)
    checked = 0
    regressions = []
    for key, cur in summary.items():
        base_vals = [
            row[key]
            for row in window
            if isinstance(row.get(key), (int, float))
            and not isinstance(row.get(key), bool)
        ]
        if not base_vals:
            continue
        checked += 1
        base = statistics.median(base_vals)
        reason = _gate_check(key, base, float(cur))
        if reason is not None:
            regressions.append((key, base, float(cur), reason))
    for key, base, cur, reason in regressions:
        out.write(
            f"perf gate: REGRESSION {key}: baseline {base:g} -> {cur:g} "
            f"[{reason}]\n"
        )
    out.write(
        f"perf gate: {checked} metrics vs median of last {len(window)} "
        f"run(s): {'FAIL' if regressions else 'ok'} "
        f"({len(regressions)} regression(s))\n"
    )
    return 1 if regressions else 0


def main() -> int:
    if "--compare" in sys.argv:
        i = sys.argv.index("--compare")
        try:
            path_a, path_b = sys.argv[i + 1 : i + 3]
        except ValueError:
            print("usage: python bench.py --compare BENCH_rA.json BENCH_rB.json")
            return 2
        return compare_bench(path_a, path_b)

    def _flag_value(flag):
        if flag in sys.argv:
            i = sys.argv.index(flag)
            if i + 1 >= len(sys.argv) or sys.argv[i + 1].startswith("-"):
                raise SystemExit(f"usage: python bench.py {flag} FILE")
            return sys.argv[i + 1]
        return None

    if "--roofline" in sys.argv:
        # Standalone roofline: no device run — the analytic traffic model
        # at the 2pc-7 bench shape for `--device-kind KIND`, optionally
        # seeded with the measured rate + generated/unique counters of a
        # prior BENCH json.
        kind = _flag_value("--device-kind")
        if not kind:
            print(
                "usage: python bench.py --roofline [BENCH.json] "
                "--device-kind KIND (e.g. 'TPU v5 lite')"
            )
            return 2
        i = sys.argv.index("--roofline")
        generated = unique = measured = None
        if i + 1 < len(sys.argv) and not sys.argv[i + 1].startswith("-"):
            rec = _load_bench(sys.argv[i + 1])
            tpc7 = (rec.get("detail") or {}).get("tpc7") or {}
            tel = tpc7.get("telemetry") or {}
            generated = tel.get("states_generated")
            unique = tpc7.get("unique")
            measured = tpc7.get("states_per_sec")
        from stateright_tpu.models import TwoPhaseTensor as _T7

        tm = _T7(7)
        rep = roofline_report(
            tm.state_width,
            tm.max_actions,
            kind,
            generated=generated,
            unique=unique,
            measured_rate=measured,
        )
        print_roofline(rep, out=sys.stdout)
        print(json.dumps({"roofline": rep}))
        return 0

    history_path = _flag_value("--history")
    gate_path = _flag_value("--gate")
    from_path = _flag_value("--from")

    def _gate_and_record(record):
        # Gate BEFORE appending: a regressed run must not poison the
        # baseline it was judged against.
        code = gate_bench(gate_path, record) if gate_path else 0
        if history_path:
            append_history(history_path, record)
        return code

    if from_path is not None:
        # Operate on a prior BENCH record — no device run, no jax import.
        if not (history_path or gate_path):
            raise SystemExit(
                "usage: python bench.py --from BENCH.json "
                "[--history FILE] [--gate FILE]"
            )
        return _gate_and_record(_load_bench(from_path))

    import jax

    from stateright_tpu.compat import configure_compilation_cache

    configure_compilation_cache()

    from stateright_tpu import TensorModelAdapter
    from stateright_tpu.models import IncrementTensor, TwoPhaseTensor
    from stateright_tpu.models.paxos import PaxosTensorExhaustive

    if "--smoke" in sys.argv:
        # Tiny device workload (2pc-5) emitting the standard BENCH json —
        # just enough signal for the CI perf-gate smoke stage to exercise
        # --history/--gate end-to-end without the full bench's runtime.
        tm5s = TwoPhaseTensor(5)
        smoke_opts = dict(
            chunk_size=512, queue_capacity=1 << 13, table_capacity=1 << 14
        )
        TensorModelAdapter(tm5s).checker().spawn_tpu_bfs(
            **smoke_opts
        ).join()  # compile
        med5s, _spread5s, dev5s = timed3(
            lambda: TensorModelAdapter(tm5s).checker().spawn_tpu_bfs(
                **smoke_opts
            ),
            golden=8_832,
        )
        rate5s = dev5s.state_count() / med5s
        record = {
            "metric": "2pc-5 smoke, generated states/sec "
            "(device engine, median of 3)",
            "value": round(rate5s, 1),
            "unit": "states/sec",
            "detail": {
                "tpc5_smoke": {
                    "states_per_sec": round(rate5s, 1),
                    "secs_median": round(med5s, 3),
                    "unique": dev5s.unique_state_count(),
                }
            },
        }
        print(json.dumps(record), flush=True)
        return _gate_and_record(record)

    detail = {}
    result = {}

    # --- speclint pre-flight ----------------------------------------------
    # Fast static analysis of every bench model BEFORE spending device
    # time on it (a fast engine checking a broken spec benches nothing);
    # diagnostic counts per code ride the BENCH json next to telemetry.
    from stateright_tpu.analysis import analyze
    from stateright_tpu.analysis.program import program_summary

    from stateright_tpu.models import AbdOrderedTensor as _AbdO
    from stateright_tpu.models import AbdTensor as _Abd
    from stateright_tpu.models import SingleCopyTensor as _SC

    speclint = {}
    program_static = {}
    for mk in (
        lambda: TwoPhaseTensor(7),
        lambda: PaxosTensorExhaustive(2),
        lambda: _Abd(2),
        lambda: _AbdO(3),
        lambda: IncrementTensor(2),
        lambda: _SC(3, 2),
    ):
        m = mk()
        # 64 samples keeps the pre-flight under ~1 min even for the paxos
        # lane program (whose single-row adapter steps dominate replay
        # cost) while still exercising every rule family.
        rep = analyze(m, samples=64)
        speclint[type(m).__name__] = {
            "ok": rep.ok,
            "errors": len(rep.errors),
            "warnings": len(rep.warnings),
            "counts_by_code": rep.counts_by_code(),
        }
        assert rep.ok, (
            f"speclint found errors on bench model {type(m).__name__}:\n"
            + rep.format()
        )
        # Static program section (proglint deep tier, STR6xx): per-program
        # op counts plus the STR606 cost model — flops/bytes per era step
        # and the memory-bound predicted st/s. Running it here also primes
        # the program-summary cache, so each device run's telemetry below
        # carries the predicted-vs-measured attribution for free.
        summ = program_summary(m, cost=True)
        ent = {
            "signature": summ.get("signature"),
            "ops": {
                name: p.get("ops")
                for name, p in (summ.get("programs") or {}).items()
            },
        }
        cost_d = summ.get("cost") or {}
        for ck in (
            "flops_per_step",
            "bytes_per_step",
            "predicted_states_per_sec",
        ):
            if cost_d.get(ck) is not None:
                ent[ck] = round(float(cost_d[ck]), 1)
        program_static[type(m).__name__] = ent
    detail["speclint"] = speclint
    detail["program_static"] = program_static

    def emit(value, vs_baseline, partial):
        result.update(
            {
                "metric": "2pc-7 exhaustive check, generated states/sec "
                "(device engine, median of 3)",
                "value": round(value, 1),
                "unit": "states/sec",
                "vs_baseline": round(vs_baseline, 2),
                "detail": dict(detail, partial=partial) if partial else detail,
            }
        )
        print(json.dumps(result), flush=True)

    # --- host baselines ----------------------------------------------------
    t0 = time.perf_counter()
    host5 = TensorModelAdapter(TwoPhaseTensor(5)).checker().spawn_bfs().join()
    host_secs = time.perf_counter() - t0
    detail["host_single_rate"] = round(host5.state_count() / host_secs, 1)

    # --- 2pc-4: device vs LIVE host oracle --------------------------------
    host4 = TensorModelAdapter(TwoPhaseTensor(4)).checker().spawn_bfs().join()
    tm4 = TwoPhaseTensor(4)
    TensorModelAdapter(tm4).checker().spawn_tpu_bfs().join()  # compile
    med4, _spread4, dev4 = timed3(
        lambda: TensorModelAdapter(tm4).checker().spawn_tpu_bfs(),
        golden=host4.unique_state_count(),
    )
    detail["tpc4"] = {
        "states_per_sec": round(dev4.state_count() / med4, 1),
        "unique": dev4.unique_state_count(),
        "oracle_match": True,
    }

    # --- 2pc-7 headline: device vs THREADED host, same run ----------------
    tpc7_golden = TPC7_GOLDEN
    host_threaded_rate = None
    try:
        # Warm the native build + tiny spawn OUTSIDE the timing window.
        TensorModelAdapter(TwoPhaseTensor(3)).checker().threads(2).spawn_bfs().join()
        t0 = time.perf_counter()
        live7 = (
            TensorModelAdapter(TwoPhaseTensor(7))
            .checker()
            .threads(8)
            .spawn_bfs()
            .join()
        )
        vb_secs = time.perf_counter() - t0
        assert live7.unique_state_count() == TPC7_GOLDEN, (
            live7.unique_state_count()
        )
        tpc7_golden = live7.unique_state_count()
        host_threaded_rate = live7.state_count() / vb_secs
        detail["host_threaded_rate"] = round(host_threaded_rate, 1)
        detail["tpc7_oracle"] = "live"
    except RuntimeError as e:
        detail["tpc7_oracle"] = f"cached ({e})"

    tm7 = TwoPhaseTensor(7)
    opts = dict(chunk_size=6144, queue_capacity=1 << 20, table_capacity=1 << 22)
    TensorModelAdapter(tm7).checker().spawn_tpu_bfs(**opts).join()  # compile
    med7, spread7, dev7 = timed3(
        lambda: TensorModelAdapter(tm7).checker().spawn_tpu_bfs(**opts),
        golden=tpc7_golden,
    )
    dev_rate = dev7.state_count() / med7
    cov7 = dev7.coverage()
    detail["tpc7"] = {
        "states_per_sec": round(dev_rate, 1),
        "unique": dev7.unique_state_count(),
        "secs_median": round(med7, 3),
        "secs_spread": [round(s, 3) for s in spread7],
        "golden_match": True,
        "telemetry": dev7.telemetry(),
        "coverage": cov7,
    }
    assert not cov7["dead_actions"], cov7["dead_actions"]
    assert sum(cov7["depths"].values()) == dev7.unique_state_count()

    # Coverage cost: the same workload with .coverage(False) — the era
    # loop compiles WITHOUT the in-carry histograms. Both rates land in
    # BENCH json (acceptance: enabling coverage costs < 5%).
    TensorModelAdapter(tm7).checker().coverage(False).spawn_tpu_bfs(
        **opts
    ).join()  # compile
    med7off, _spread7off, dev7off = timed3(
        lambda: (
            TensorModelAdapter(tm7).checker().coverage(False)
            .spawn_tpu_bfs(**opts)
        ),
        golden=tpc7_golden,
    )
    rate_off = dev7off.state_count() / med7off
    detail["tpc7_coverage_cost"] = {
        "states_per_sec_coverage_on": round(dev_rate, 1),
        "states_per_sec_coverage_off": round(rate_off, 1),
        "overhead_pct": round((1.0 - dev_rate / rate_off) * 100.0, 2),
    }

    # Span cost: the same workload with a span recorder attached — the
    # run ledger's engine tier (obs/spans.py: one run span, a progress
    # span per era, phase spans at seal). Both rates land in BENCH json
    # (acceptance: enabling spans costs < 2% — recording is a dict
    # append per era, far off the device hot path).
    from stateright_tpu.obs.spans import SpanRecorder as _SpanRecorder

    TensorModelAdapter(tm7).checker().spans(_SpanRecorder()).spawn_tpu_bfs(
        **opts
    ).join()  # compile
    med7sp, _spread7sp, dev7sp = timed3(
        lambda: (
            TensorModelAdapter(tm7).checker().spans(_SpanRecorder())
            .spawn_tpu_bfs(**opts)
        ),
        golden=tpc7_golden,
    )
    rate_sp = dev7sp.state_count() / med7sp
    span_overhead_pct = (1.0 - rate_sp / dev_rate) * 100.0
    detail["tpc7_span_cost"] = {
        "states_per_sec_spans_on": round(rate_sp, 1),
        "states_per_sec_spans_off": round(dev_rate, 1),
        "overhead_pct": round(span_overhead_pct, 2),
    }
    assert span_overhead_pct < 2.0, detail["tpc7_span_cost"]

    # Checkpoint cost: the same workload writing periodic crash-safe
    # checkpoints (atomic tmp+fsync+rename at era boundaries) vs the
    # plain run above. Both rates land in BENCH json (acceptance:
    # enabling checkpoints costs < 5%).
    import tempfile as _tempfile

    with _tempfile.TemporaryDirectory(prefix="_bench_ckpt.") as ckpt_dir:
        ckpt7 = os.path.join(ckpt_dir, "2pc7.ckpt.npz")
        med7ck, _spread7ck, dev7ck = timed3(
            lambda: (
                TensorModelAdapter(tm7).checker().spawn_tpu_bfs(
                    checkpoint_path=ckpt7, checkpoint_every=0.5, **opts
                )
            ),
            golden=tpc7_golden,
        )
        rate_ck = dev7ck.state_count() / med7ck
        saves = dev7ck.telemetry().get("checkpoint_saves", 0)
    ckpt_overhead_pct = (1.0 - rate_ck / dev_rate) * 100.0
    detail["tpc7_checkpoint_cost"] = {
        "states_per_sec_checkpoint_on": round(rate_ck, 1),
        "states_per_sec_checkpoint_off": round(dev_rate, 1),
        "checkpoint_saves": saves,
        "overhead_pct": round(ckpt_overhead_pct, 2),
    }
    assert saves >= 1, "checkpoint cadence never fired during the bench"
    assert ckpt_overhead_pct < 5.0, detail["tpc7_checkpoint_cost"]

    # Flight-recorder cost: the headline runs above record a flight by
    # default, so the control is the same workload with .flight(False).
    # Every flight field comes from the once-per-era packed-params
    # readback plus host clocks — zero extra device round-trips —
    # (acceptance: recording costs < 2%, and the per-era device/host-gap
    # wall split reconciles with an externally timed run within 5%).
    TensorModelAdapter(tm7).checker().flight(False).spawn_tpu_bfs(
        **opts
    ).join()  # compile
    med7fl, _spread7fl, dev7fl = timed3(
        lambda: (
            TensorModelAdapter(tm7).checker().flight(False)
            .spawn_tpu_bfs(**opts)
        ),
        golden=tpc7_golden,
    )
    rate_fl_off = dev7fl.state_count() / med7fl
    flight_overhead_pct = (1.0 - dev_rate / rate_fl_off) * 100.0
    t0 = time.perf_counter()
    recon7 = TensorModelAdapter(tm7).checker().spawn_tpu_bfs(**opts).join()
    recon_wall = time.perf_counter() - t0
    fsum = recon7.telemetry()["flight"]
    # Overlap-aware identity: under speculative pipelining the engine's
    # per-era device spans can exceed the wall deltas between readbacks;
    # the recorder books the excess as overlap_secs, and the run-level
    # reconciliation is device - overlap + gap == wall.
    recon_err_pct = (
        abs(
            fsum["device_secs"]
            - fsum.get("overlap_secs", 0.0)
            + fsum["host_gap_secs"]
            - recon_wall
        )
        / recon_wall
        * 100.0
    )
    detail["tpc7_flight_cost"] = {
        "states_per_sec_flight_on": round(dev_rate, 1),
        "states_per_sec_flight_off": round(rate_fl_off, 1),
        "overhead_pct": round(flight_overhead_pct, 2),
        "eras": fsum["eras"],
        "host_gap_pct": fsum["host_gap_pct"],
        "wall_reconciliation_err_pct": round(recon_err_pct, 2),
    }
    assert flight_overhead_pct < 2.0, detail["tpc7_flight_cost"]
    assert recon_err_pct < 5.0, detail["tpc7_flight_cost"]

    # Mega-dispatch: the SAME workload with the K-deep speculative chain
    # at depth 4 and 4 eras fused per compiled dispatch. Golden must
    # still match (the whole point: fusion is output-invisible), and the
    # three chain gauges are gate-tracked — `dispatches` lower-better
    # (fewer host round-trips), `spec_chain_depth` and
    # `fused_eras_per_dispatch` higher-better (the chain actually
    # filling / the fusion actually engaging are the perf contracts).
    t0 = time.perf_counter()
    mega7 = (
        TensorModelAdapter(tm7)
        .checker()
        .pipeline(depth=4, fuse=4)
        .spawn_tpu_bfs(**opts)
        .join()
    )
    mega_secs = time.perf_counter() - t0
    assert mega7.unique_state_count() == tpc7_golden
    mtel = mega7.telemetry()
    detail["tpc7_mega"] = {
        "states_per_sec": round(mega7.state_count() / mega_secs, 1),
        "secs": round(mega_secs, 3),
        "eras": int(mtel.get("eras", 0)),
        "dispatches": int(mtel.get("dispatches", 0)),
        "spec_chain_depth": int(mtel.get("spec_chain_depth", 0)),
        "fused_eras_per_dispatch": float(
            mtel.get("fused_eras_per_dispatch", 0.0)
        ),
        "spec_wasted": int(mtel.get("spec_wasted", 0)),
    }
    if detail["tpc7_mega"]["eras"] > 1:
        assert (
            detail["tpc7_mega"]["dispatches"] < detail["tpc7_mega"]["eras"]
        ), detail["tpc7_mega"]

    # Memory: the headline run's ledger peak (obs/memory.py), residency
    # per unique state (gate-tracked, lower-better), and the capacity
    # planner's static prediction at the same geometry vs the measured
    # peak (acceptance: within 15%). The control is the same workload
    # with .memory(False) (acceptance: ledger + forecaster cost < 1% —
    # the accounting is analytic host arithmetic riding the existing
    # per-era readback). The 1% budget is asserted on each side's BEST
    # of 3 times: a real fixed cost survives at the noise floor, while
    # per-run scheduler jitter (several % on shared CPU hosts) does not.
    from stateright_tpu.obs.memory import plan as memory_plan

    mem_snap = dev7.telemetry().get("memory") or {}
    measured_peak = int(mem_snap.get("peak_bytes", 0))
    assert measured_peak > 0, "headline run recorded no memory ledger"
    p7 = memory_plan(
        TensorModelAdapter(tm7),
        engine="tpu_bfs",
        chunk=opts["chunk_size"],
        queue_capacity=opts["queue_capacity"],
        table_capacity=opts["table_capacity"],
    )
    predicted = int(p7["total_bytes"])
    plan_err_pct = abs(predicted - measured_peak) / measured_peak * 100.0
    TensorModelAdapter(tm7).checker().memory(False).spawn_tpu_bfs(
        **opts
    ).join()  # compile
    med7mm, spread7mm, dev7mm = timed3(
        lambda: (
            TensorModelAdapter(tm7).checker().memory(False)
            .spawn_tpu_bfs(**opts)
        ),
        golden=tpc7_golden,
    )
    rate_mm_off = dev7mm.state_count() / med7mm
    rate_on_best = dev7.state_count() / spread7[0]
    rate_off_best = dev7mm.state_count() / spread7mm[0]
    mem_overhead_pct = (1.0 - rate_on_best / rate_off_best) * 100.0
    detail["tpc7_memory"] = {
        "peak_bytes": measured_peak,
        "memory_peak_bytes_per_state": round(
            measured_peak / dev7.unique_state_count(), 2
        ),
        "predicted_bytes": predicted,
        "plan_err_pct": round(plan_err_pct, 2),
        "states_per_sec_memory_on": round(dev_rate, 1),
        "states_per_sec_memory_off": round(rate_mm_off, 1),
        "overhead_pct": round(mem_overhead_pct, 2),
    }
    assert plan_err_pct <= 15.0, detail["tpc7_memory"]
    assert mem_overhead_pct < 1.0, detail["tpc7_memory"]

    # Space-sampling cost: the headline runs sample by default (bottom-k
    # state sampling, obs/sample.py — the candidate slab rides the era
    # carry and drains on the existing packed-params readback, zero
    # extra round-trips), so the control is the same workload with
    # .sample(False). Budget asserted on each side's BEST of 3 (the
    # memory section's noise-floor idiom: a real fixed cost survives at
    # the noise floor, per-run scheduler jitter does not). Acceptance:
    # sampling costs < 2%, and the headline sample is full at k=64.
    TensorModelAdapter(tm7).checker().sample(False).spawn_tpu_bfs(
        **opts
    ).join()  # compile
    med7sm, spread7sm, dev7sm = timed3(
        lambda: (
            TensorModelAdapter(tm7).checker().sample(False)
            .spawn_tpu_bfs(**opts)
        ),
        golden=tpc7_golden,
    )
    rate_sm_off = dev7sm.state_count() / med7sm
    rate_sm_off_best = dev7sm.state_count() / spread7sm[0]
    sample_overhead_pct = (1.0 - rate_on_best / rate_sm_off_best) * 100.0
    space7 = dev7.telemetry().get("space") or {}
    detail["tpc7_sample"] = {
        "states_per_sec_sample_on": round(dev_rate, 1),
        "states_per_sec_sample_off": round(rate_sm_off, 1),
        "space_sample_overhead_pct": round(sample_overhead_pct, 2),
        "samples": space7.get("samples", 0),
        "est_states": space7.get("est_states", 0),
        "device_drops": space7.get("device_drops", 0),
    }
    assert space7.get("samples") == space7.get("k"), detail["tpc7_sample"]
    assert not space7.get("degraded"), detail["tpc7_sample"]
    assert sample_overhead_pct < 2.0, detail["tpc7_sample"]

    # Roofline: analytic bandwidth ceiling seeded with THIS run's
    # generated/unique ratio and measured rate (see roofline_report).
    detail["roofline"] = roofline_report(
        tm7.state_width,
        tm7.max_actions,
        jax.devices()[0].device_kind,
        generated=dev7.telemetry().get("states_generated"),
        unique=dev7.unique_state_count(),
        measured_rate=dev_rate,
    )
    print_roofline(detail["roofline"])

    vs_threaded = dev_rate / TPC7_HOST_THREADED_REFERENCE_RATE
    if host_threaded_rate:
        detail["vs_host_threaded_live"] = round(dev_rate / host_threaded_rate, 2)
    detail["vs_host_single"] = round(
        dev_rate / detail["host_single_rate"], 2
    )
    emit(dev_rate, vs_threaded, partial=True)

    # --- paxos-2: the reference's flagship workload on device -------------
    try:
        livep = (
            TensorModelAdapter(PaxosTensorExhaustive(2))
            .checker()
            .threads(8)
            .spawn_bfs()
            .join()
        )
        assert livep.unique_state_count() == PAXOS2_GOLDEN, (
            livep.unique_state_count()
        )
        detail["paxos2_oracle"] = "live"
    except RuntimeError as e:
        detail["paxos2_oracle"] = f"cached ({e})"

    px = PaxosTensorExhaustive(2)
    pxopts = dict(chunk_size=2048, queue_capacity=1 << 18, table_capacity=1 << 20)
    TensorModelAdapter(px).checker().spawn_tpu_bfs(**pxopts).join()  # compile
    medp, _spreadp, devp = timed3(
        lambda: TensorModelAdapter(px).checker().spawn_tpu_bfs(**pxopts),
        golden=PAXOS2_GOLDEN,
    )
    detail["paxos2"] = {
        "states_per_sec": round(devp.state_count() / medp, 1),
        "unique": devp.unique_state_count(),
        "secs_median": round(medp, 3),
        "golden_match": True,
        "telemetry": devp.telemetry(),
    }

    # --- linearizable-register check 2 (ABD, unordered): bench.sh:33 ------
    from stateright_tpu.models.abd import AbdOrderedTensor, AbdTensor

    abdopts = dict(
        chunk_size=512, queue_capacity=1 << 14, table_capacity=1 << 13
    )
    abdtm = AbdTensor(2)
    TensorModelAdapter(abdtm).checker().spawn_tpu_bfs(**abdopts).join()
    meda, _spreada, deva = timed3(
        lambda: TensorModelAdapter(abdtm).checker().spawn_tpu_bfs(**abdopts),
        golden=544,  # linearizable-register.rs:287
        check=lambda c: c.discovery("linearizable") is None,
    )
    detail["abd2"] = {
        "unique": deva.unique_state_count(),
        "secs_median": round(meda, 3),
        "golden_match": True,
        "linearizable": "held",
    }

    # --- linearizable-register check 3 ORDERED: bench.sh:33 parity --------
    # Round 5: the ordered-network lane encoding (per-flow FIFO ranks)
    # runs the reference's ordered workload ON DEVICE, golden-matched to
    # the host actor model (46,516; linearizable holds).
    aotm = AbdOrderedTensor(3)
    aoopts = dict(
        chunk_size=2048, queue_capacity=1 << 15, table_capacity=1 << 18
    )
    TensorModelAdapter(aotm).checker().spawn_tpu_bfs(**aoopts).join()
    medo, _spreado, devo = timed3(
        lambda: TensorModelAdapter(aotm).checker().spawn_tpu_bfs(**aoopts),
        golden=ABD3_ORDERED_GOLDEN,
        check=lambda c: c.discovery("linearizable") is None,
    )
    detail["abd3_ordered"] = {
        "states_per_sec": round(devo.state_count() / medo, 1),
        "unique": devo.unique_state_count(),
        "secs_median": round(medo, 3),
        "golden_match": True,
        "linearizable": "held",
    }

    # --- 2pc-5 device symmetry reduction ----------------------------------
    # Canonical-closure semantics (see models/two_phase_commit.py): the
    # deterministic order-independent count a batched BFS admits.
    tm5 = TwoPhaseTensor(5)
    symopts = dict(chunk_size=512, queue_capacity=1 << 13, table_capacity=1 << 14)
    TensorModelAdapter(tm5).checker().symmetry().spawn_tpu_bfs(**symopts).join()
    meds, _spreads, devs = timed3(
        lambda: TensorModelAdapter(tm5).checker().symmetry().spawn_tpu_bfs(**symopts),
        golden=TPC5_SYM_CLOSURE,
    )
    detail["tpc5_symmetry"] = {
        "unique_representatives": devs.unique_state_count(),
        "full_space": 8832,
        "reduction": round(8832 / devs.unique_state_count(), 2),
        "secs_median": round(meds, 3),
    }

    # --- TTFC: increment race (BFS, fused seed+first-era) ------------------
    # One dispatch + one readback end to end: seeding, the era loop, AND
    # the discovery fingerprints all ride a single device round-trip.
    inc = IncrementTensor(2)
    incopts = dict(chunk_size=64, queue_capacity=1 << 10, table_capacity=1 << 10)
    TensorModelAdapter(inc).checker().spawn_tpu_bfs(**incopts).join()  # compile
    medt, _spreadt, _devi = timed3(
        lambda: TensorModelAdapter(inc).checker().spawn_tpu_bfs(**incopts),
        check=lambda c: c.discovery("fin") is not None,
    )
    detail["ttfc_increment_race_secs"] = round(medt, 3)

    # --- TTFC: single-copy-register 3x2 linearizability violation ----------
    from stateright_tpu.has_discoveries import HasDiscoveries
    from stateright_tpu.models.single_copy import SingleCopyTensor

    sct = SingleCopyTensor(3, 2)
    scopts = dict(chunk_size=256, queue_capacity=1 << 12, table_capacity=1 << 12)
    fin = HasDiscoveries.any_of(["linearizable"])

    def mk_sc():
        return (
            TensorModelAdapter(sct)
            .checker()
            .finish_when(fin)
            .spawn_tpu_bfs(**scopts)
        )

    mk_sc().join()  # compile
    medsc, _spreadsc, _devsc = timed3(
        mk_sc, check=lambda c: c.discovery("linearizable") is not None
    )
    detail["ttfc_single_copy_3x2_secs"] = round(medsc, 3)

    # --- TTFC via the batched device SIMULATION engine ---------------------
    fin_inc = HasDiscoveries.any_of(["fin"])

    def mk_sim():
        return (
            TensorModelAdapter(inc)
            .checker()
            .finish_when(fin_inc)
            .spawn_tpu_simulation(7, walks=256, walk_cap=32)
        )

    mk_sim().join()  # compile
    medsim, _spreadsim, _devsim = timed3(
        mk_sim, check=lambda c: c.discovery("fin") is not None
    )
    detail["ttfc_increment_race_simulation_secs"] = round(medsim, 3)

    emit(dev_rate, vs_threaded, partial=True)

    def section(name, fn):
        """Run one big device section; a PLATFORM failure (remote-compile
        hiccup, worker restart) records the error and lets later sections
        run — a golden mismatch (AssertionError) still fails the bench
        loudly. (Observed round 5: a transient 'remote_compile: response
        body closed' killed an otherwise-green bench mid-run.)"""
        try:
            fn()
        except AssertionError:
            raise
        except Exception as e:  # noqa: BLE001 - platform fault tolerance
            detail[name] = {"error": repr(e)[:200]}
        emit(dev_rate, vs_threaded, partial=True)

    def _sec_tpc10_symmetry():
        # --- 2pc-10 with device symmetry: the state-space lever at scale ------
        # Canonical closure of the 61,515,776-state space: 265,719
        # representatives (231x fewer), verdicts identical. One run, WARMED
        # (the first call compiles the loop for this shape; the timed call
        # reuses it), because a full closure takes ~45s — the 3x-median
        # discipline is reserved for the sub-minute sections.
        sym10opts = dict(
            chunk_size=8192,
            queue_capacity=1 << 21,
            table_capacity=1 << 24,
            sync_steps=128,
        )
        tm10 = TwoPhaseTensor(10)
        TensorModelAdapter(tm10).checker().symmetry().spawn_tpu_bfs(
            **sym10opts
        ).join()  # compile
        t0 = time.perf_counter()
        d10s = (
            TensorModelAdapter(tm10)
            .checker()
            .symmetry()
            .spawn_tpu_bfs(**sym10opts)
            .join()
        )
        secs10s = time.perf_counter() - t0
        assert d10s.unique_state_count() == TPC10_SYM_CLOSURE, (
            d10s.unique_state_count()
        )
        assert d10s.discovery("consistent") is None
        detail["tpc10_symmetry"] = {
            "unique_representatives": d10s.unique_state_count(),
            "full_space": TPC10_GOLDEN,
            "reduction": round(TPC10_GOLDEN / d10s.unique_state_count(), 1),
            "secs": round(secs10s, 1),
        }

    def _sec_paxos3():
        # --- paxos-3: the BASELINE.json north-star workload -------------------
        # Timed at the mega-dispatch config (chain depth 4, 4 eras fused
        # per dispatch) — this is the acceptance workload for the
        # dispatch-gap work, so its timing row carries the chain gauges
        # and a pure device_secs (phase_ms, host gap excluded) alongside
        # the wall secs.
        px3 = PaxosTensorExhaustive(3)
        opts3 = dict(
            chunk_size=16384, queue_capacity=1 << 21, table_capacity=1 << 26
        )

        def mk3():
            return (
                TensorModelAdapter(px3)
                .checker()
                .pipeline(depth=4, fuse=4)
                .spawn_tpu_bfs(**opts3)
            )

        mk3().join()  # compile
        t0 = time.perf_counter()
        d3 = mk3().join()
        secs3 = time.perf_counter() - t0
        assert d3.unique_state_count() == PAXOS3_GOLDEN, d3.unique_state_count()
        tel3 = d3.telemetry()
        detail["paxos3"] = {
            "states_per_sec": round(d3.state_count() / secs3, 1),
            "unique": d3.unique_state_count(),
            "secs": round(secs3, 3),
            "device_secs": round(
                float(tel3.get("phase_ms", {}).get("device_era", 0.0)) / 1e3,
                3,
            ),
            "dispatches": int(tel3.get("dispatches", 0)),
            "spec_chain_depth": int(tel3.get("spec_chain_depth", 0)),
            "fused_eras_per_dispatch": float(
                tel3.get("fused_eras_per_dispatch", 0.0)
            ),
            "golden_match": True,
            "telemetry": tel3,
        }

    def _sec_paxos6():
        # --- paxos check 6: bench.sh:31 parity — ON DEVICE (round 5) ----------
        # The full reference bench workload, checked exhaustively: 9,357,525
        # uniques, golden-matched against the threaded host's 17-minute run
        # (the device does it in ~8). Encoding guards (network capacity,
        # ballot-round range) asserted quiet.
        px6 = PaxosTensorExhaustive(6)
        t0 = time.perf_counter()
        d6 = (
            TensorModelAdapter(px6)
            .checker()
            .spawn_tpu_bfs(
                chunk_size=8192,
                queue_capacity=1 << 21,
                table_capacity=1 << 26,
                sync_steps=128,
            )
            .join()
        )
        secs6 = time.perf_counter() - t0
        assert d6.unique_state_count() == PAXOS6_GOLDEN, d6.unique_state_count()
        assert d6.discovery("network within capacity") is None
        assert d6.discovery("ballot rounds within range") is None
        detail["paxos6"] = {
            "states_per_sec": round(d6.state_count() / secs6, 1),
            "unique": d6.unique_state_count(),
            "secs": round(secs6, 1),
            "golden_match": True,
            "host_threaded_secs": 1037.3,
            "telemetry": d6.telemetry(),
        }

    def _sec_tpc10_device():
        # --- 2pc check 10: bench.sh:28 scale parity — ON DEVICE (round 5) -----
        # 61,515,776 uniques checked exhaustively by the device engine (the
        # round-4 worker crash was long single dispatches; short eras fixed
        # it). The threaded host cross-check ran in round 4 (3.84M st/s).
        t0 = time.perf_counter()
        d10 = (
            TensorModelAdapter(TwoPhaseTensor(10))
            .checker()
            .spawn_tpu_bfs(
                chunk_size=12288,
                queue_capacity=1 << 24,
                table_capacity=1 << 28,
                sync_steps=128,
            )
            .join()
        )
        secs10 = time.perf_counter() - t0
        assert d10.unique_state_count() == TPC10_GOLDEN, d10.unique_state_count()
        detail["tpc10_device"] = {
            "states_per_sec": round(d10.state_count() / secs10, 1),
            "unique": d10.unique_state_count(),
            "secs": round(secs10, 1),
            "golden_match": True,
            "telemetry": d10.telemetry(),
        }

    def _sec_tpc7_outofcore():
        # --- 2pc-7 out-of-core: capped-run retention + delta bytes ------------
        # The SAME pipelined workload twice — unconstrained, then under a
        # device byte cap + spill host-RAM budget + tight-cadence delta
        # checkpoints (ISSUE 20). The gate tracks how much throughput the
        # out-of-core tier costs (retention_pct, higher is better), how
        # small a delta save stays vs a full save (bytes_per_save, lower
        # is better), and the auto-picked fusion factor. The capped run
        # must stay bit-identical to the unconstrained one.
        import shutil
        import tempfile

        oc_opts = dict(
            chunk_size=6144,
            queue_capacity=1 << 16,
            table_capacity=1 << 16,
            sync_steps=16,
        )

        def run(ckpt=None):
            kw = dict(oc_opts)
            if ckpt is not None:
                kw.update(checkpoint_path=ckpt, checkpoint_every=0.5)
            t0 = time.perf_counter()
            c = (
                TensorModelAdapter(TwoPhaseTensor(7))
                .checker()
                .pipeline(depth=4, fuse=4)
                .spawn_tpu_bfs(**kw)
                .join()
            )
            return c, time.perf_counter() - t0

        free, free_secs = run()
        assert free.unique_state_count() == TPC7_GOLDEN, (
            free.unique_state_count()
        )
        tmp = tempfile.mkdtemp(prefix="stpu-bench-oc-")
        os.environ["STPU_DEVICE_MEMORY_BYTES"] = "16000000"
        # 64 KiB host budget: small enough that the 2pc-7 spill wave
        # actually reaches the npz disk tier (1 MiB never filled).
        os.environ["STPU_SPILL_HOST_BUDGET_BYTES"] = str(1 << 16)
        try:
            capped, capped_secs = run(os.path.join(tmp, "oc.ckpt.npz"))
        finally:
            os.environ.pop("STPU_DEVICE_MEMORY_BYTES", None)
            os.environ.pop("STPU_SPILL_HOST_BUDGET_BYTES", None)
            shutil.rmtree(tmp, ignore_errors=True)
        assert capped.unique_state_count() == free.unique_state_count()
        assert capped.state_count() == free.state_count()
        assert dict(capped._discovery_fps) == dict(free._discovery_fps)
        tel = capped.telemetry()
        d_saves = tel.get("checkpoint_delta_saves", 0)
        f_saves = tel.get("checkpoint_saves", 0)
        detail["tpc7_outofcore"] = {
            "retention_pct": round(100.0 * free_secs / capped_secs, 1),
            "capped_states_per_sec": round(
                capped.state_count() / capped_secs, 1
            ),
            "fuse_auto_n": tel.get("fuse_auto_n"),
            "reshard_proactive": tel.get("reshard_proactive", 0),
            "spill_tier_rows": tel.get("spill_tier_rows", 0),
            "delta_saves": d_saves,
            "delta_bytes_per_save": round(
                tel.get("checkpoint_delta_bytes", 0) / max(1, d_saves), 1
            ),
            "full_bytes_per_save": round(
                tel.get("checkpoint_bytes", 0) / max(1, f_saves), 1
            ),
            "golden_match": True,
            "telemetry": tel,
        }

    def _sec_single_copy4():
        # --- single-copy-register check 4: bench.sh:30 parity -----------------
        # EXHAUSTIVE this round (previously only the 3x2 TTFC line): the
        # 4-client/1-server single-copy register, where linearizability
        # HOLDS — host oracle and device twin golden-matched.
        from stateright_tpu.models.single_copy import SingleCopyTensor

        sc4 = SingleCopyTensor(4)
        # Threaded host oracle (vbfs): the serial Python engine needs ~8
        # minutes for the 400k-state space; the lane-batched one doesn't.
        t0 = time.perf_counter()
        h = (
            TensorModelAdapter(sc4).checker().threads(8).spawn_bfs().join()
        )
        host_secs = time.perf_counter() - t0
        assert h.unique_state_count() == SINGLE_COPY4_GOLDEN, (
            h.unique_state_count()
        )
        assert h.discovery("linearizable") is None
        # 400k uniques at the 0.25 max load factor want ~1.6M slots:
        # start at 1<<21 so the timed runs never pay a growth+rehash.
        sc4opts = dict(
            chunk_size=2048, queue_capacity=1 << 17, table_capacity=1 << 21
        )
        TensorModelAdapter(sc4).checker().spawn_tpu_bfs(**sc4opts).join()
        medsc4, _sp, d = timed3(
            lambda: TensorModelAdapter(sc4).checker().spawn_tpu_bfs(**sc4opts),
            golden=h.unique_state_count(),
            check=lambda c: c.discovery("linearizable") is None,
        )
        detail["single_copy4"] = {
            "unique": d.unique_state_count(),
            "golden": h.unique_state_count(),
            "golden_match": True,
            "linearizable": "held",
            "host_secs": round(host_secs, 3),
            "device_secs_median": round(medsc4, 3),
            "states_per_sec": round(d.state_count() / medsc4, 1),
        }

    def _sec_pbfs_paxos3():
        # --- pbfs: multiprocessing host engine on a RICH model ----------------
        # The ownership-sharded engine (engines/pbfs.py) is the only host
        # path that parallelizes arbitrary picklable Python models — the
        # README's "true parallelism beyond the GIL" claim. Measured on
        # the rich paxos-3 actor model against the SAME single-threaded
        # engine on the SAME bounded workload; rates are generated
        # states/sec (state_count / wall secs).
        from examples.paxos import paxos_model

        target = 60_000
        # min() so a core-starved box still runs the section; cpu_count
        # rides the json because a 1-core container CANNOT show a speedup
        # (workers only beat serial with real cores to run on).
        workers = min(8, os.cpu_count() or 1)
        t0 = time.perf_counter()
        serial = (
            paxos_model(3)
            .checker()
            .target_state_count(target)
            .spawn_bfs()
            .join()
        )
        serial_secs = time.perf_counter() - t0
        t0 = time.perf_counter()
        par = (
            paxos_model(3)
            .checker()
            .threads(workers)
            .target_state_count(target)
            .spawn_bfs()
            .join()
        )
        par_secs = time.perf_counter() - t0
        assert serial.state_count() >= target and par.state_count() >= target
        serial_rate = serial.state_count() / serial_secs
        par_rate = par.state_count() / par_secs
        detail["pbfs_paxos3"] = {
            "workers": workers,
            "cpu_count": os.cpu_count(),
            "target_state_count": target,
            "serial_states_per_sec": round(serial_rate, 1),
            "workers_states_per_sec": round(par_rate, 1),
            "speedup": round(par_rate / serial_rate, 2),
            "serial_secs": round(serial_secs, 2),
            "workers_secs": round(par_secs, 2),
        }

    def _sec_service():
        # --- checking-as-a-service: 32 concurrent small checks over REST ------
        # The run server's reason to exist (ROADMAP item 3): many small
        # same-shape checks packed as vmapped lanes of ONE fused era,
        # sharing one compiled executable via the ExecutableCache, vs the
        # status-quo serial per-request device spawns where every fresh
        # model instance re-traces the loop (id(tm)-keyed jit caches).
        # Acceptance: >= 5x aggregate checks/sec, exactly 1 cache miss,
        # every result on the 13-unique increment golden.
        import json as _json
        import urllib.request

        from stateright_tpu.serve import RunService, ServeServer

        n_checks = 32
        # Serial baseline first: per-request spawns over FRESH instances
        # (a service without the intern pool sees a new id(tm) each time).
        serial_n = 8
        solo_opts = dict(
            chunk_size=64, queue_capacity=1 << 10, table_capacity=1 << 12
        )
        t0 = time.perf_counter()
        for _ in range(serial_n):
            c = (
                TensorModelAdapter(IncrementTensor(2))
                .checker()
                .multiplex_lane()  # silence the (correct) small-workload hint
                .spawn_tpu_bfs(**solo_opts)
                .join()
            )
            assert c.unique_state_count() == 13, c.unique_state_count()
        serial_secs = time.perf_counter() - t0
        serial_rate = serial_n / serial_secs

        svc = RunService(workers=1, lanes=n_checks, lint_samples=32)
        server = ServeServer(svc, "127.0.0.1:0").serve_in_background()
        base = server.url.rstrip("/")

        def req(method, path, body=None):
            data = _json.dumps(body).encode() if body is not None else None
            r = urllib.request.Request(base + path, data=data, method=method)
            with urllib.request.urlopen(r) as resp:
                return _json.loads(resp.read())

        try:
            req("POST", "/scheduler/pause")
            ids = [
                req("POST", "/submit", {"spec": "increment:2"})["job_id"]
                for _ in range(n_checks)
            ]
            t0 = time.perf_counter()
            req("POST", "/scheduler/resume")
            while True:
                views = req("GET", "/jobs")["jobs"]
                if all(v["status"] not in ("queued", "running") for v in views):
                    break
                time.sleep(0.05)
            mux_secs = time.perf_counter() - t0
            for job_id in ids:
                result = req("GET", f"/jobs/{job_id}/result")["result"]
                assert result["unique_state_count"] == 13, result
            stats = req("GET", "/stats")
            cache = stats["cache"]
            # One shape, one executable: the whole batch compiled ONCE.
            assert cache["misses"] == 1, cache
        finally:
            server.shutdown()
        mux_rate = n_checks / mux_secs
        speedup = mux_rate / serial_rate
        # Submit->result latency distribution (obs/metrics.py Histogram
        # behind /stats "latency"): the whole batch rode one fused era,
        # so even the p99 must land within the bench's own wall-clock.
        latency = stats.get("latency") or {}
        s2r = latency.get("submit_to_result") or {}
        detail["service"] = {
            "concurrent_checks": n_checks,
            "multiplexed_checks_per_sec": round(mux_rate, 2),
            "serial_per_request_checks_per_sec": round(serial_rate, 2),
            "speedup": round(speedup, 1),
            "cache": cache,
            "cache_hit_rate": round(
                cache["hits"] / max(1, cache["hits"] + cache["misses"]), 3
            ),
            "latency": latency,
            "golden_match": True,
        }
        assert speedup >= 5.0, detail["service"]
        assert s2r.get("count", 0) >= n_checks, latency
        assert 0.0 < s2r.get("p99", 0.0) < 60.0, latency

    def _sec_service_durable():
        # --- serve durability cost: the same 32-check REST batch with the
        # write-ahead job journal + persisted results enabled (ISSUE 9).
        # Every submit fsyncs a journal record before the 202 and every
        # result lands on disk before its terminal journal record, so this
        # rate IS the durable-path throughput; detail records it next to
        # the journal/result-store footprints for comparison against the
        # in-memory-only `service` section above.
        import json as _json
        import tempfile as _tempfile
        import urllib.request

        from stateright_tpu.serve import RunService, ServeServer

        n_checks = 32
        tmp = _tempfile.mkdtemp(prefix="_bench_serve_dura.")
        svc = RunService(
            workers=1,
            lanes=n_checks,
            lint_samples=32,
            journal_path=os.path.join(tmp, "jobs.jsonl"),
            results_dir=os.path.join(tmp, "results"),
        )
        server = ServeServer(svc, "127.0.0.1:0").serve_in_background()
        base = server.url.rstrip("/")

        def req(method, path, body=None):
            data = _json.dumps(body).encode() if body is not None else None
            r = urllib.request.Request(base + path, data=data, method=method)
            with urllib.request.urlopen(r) as resp:
                return _json.loads(resp.read())

        try:
            req("POST", "/scheduler/pause")
            ids = [
                req("POST", "/submit", {"spec": "increment:2"})["job_id"]
                for _ in range(n_checks)
            ]
            t0 = time.perf_counter()
            req("POST", "/scheduler/resume")
            while True:
                views = req("GET", "/jobs")["jobs"]
                if all(v["status"] not in ("queued", "running") for v in views):
                    break
                time.sleep(0.05)
            dura_secs = time.perf_counter() - t0
            for job_id in ids:
                result = req("GET", f"/jobs/{job_id}/result")["result"]
                assert result["unique_state_count"] == 13, result
            stats = req("GET", "/stats")
        finally:
            server.shutdown()
        in_memory = (detail.get("service") or {}).get(
            "multiplexed_checks_per_sec"
        )
        durable_rate = n_checks / dura_secs
        detail["service_durable"] = {
            "concurrent_checks": n_checks,
            "durable_checks_per_sec": round(durable_rate, 2),
            "in_memory_checks_per_sec": in_memory,
            "journal": stats.get("journal"),
            "results": stats.get("results"),
            "golden_match": True,
        }

    def _sec_record_overhead():
        # --- record_overhead: live NetObs cost on the actor hot path ----------
        # The flight recorder's acceptance bar (conformance/README.md):
        # attaching live deployment metrics (per-actor counters, Lamport
        # stamping feed, latency/mailbox gauges) to a recorded run must
        # cost < 3% of recorded-event throughput. A fixed-work run (one
        # client, max_ops bumps, retries parked far out so every op is a
        # clean round trip) on ONE base_port (FaultPlan RNG keys embed
        # ports, so this keeps the duplicate/delay schedule identical),
        # best-of-3 each way; rate = trace events per handler-span
        # second, so socket setup/teardown stays out of the measurement.
        import tempfile as _tempfile

        from examples.increment import record_counter_demo
        from stateright_tpu.conformance import FaultPlan, load_trace
        from stateright_tpu.obs.netobs import NetObs

        ops = 400
        plan = FaultPlan(
            seed=5, duplicate=0.2, delay=0.1, delay_range=(0.0005, 0.002)
        )
        tmp = _tempfile.mkdtemp(prefix="_bench_netobs.")

        def rate_once(tag, netobs):
            path = os.path.join(tmp, f"{tag}.jsonl")
            record_counter_demo(
                path, duration=30.0, client_count=1, base_port=46700,
                plan=plan, max_ops=ops, netobs=netobs,
                retry_range=(30.0, 60.0),
            )
            _meta, events = load_trace(path)
            stamps = [ev["ts"] for ev in events if ev["kind"] != "fault"]
            span = stamps[-1] - stamps[0]
            assert span > 0 and len(events) >= 4 * ops, (tag, len(events))
            return len(events) / span

        rate_bare = max(rate_once(f"bare{i}", False) for i in range(3))
        rate_obs = max(rate_once(f"obs{i}", NetObs()) for i in range(3))
        overhead = max(0.0, (1.0 - rate_obs / rate_bare) * 100.0)
        detail["record_overhead"] = {
            "ops": ops,
            "rate_bare": round(rate_bare, 1),
            "rate_netobs": round(rate_obs, 1),
            "netobs_overhead_pct": round(overhead, 2),
        }
        assert overhead < 3.0, detail["record_overhead"]

    section("record_overhead", _sec_record_overhead)
    section("single_copy4", _sec_single_copy4)
    section("service", _sec_service)
    section("service_durable", _sec_service_durable)
    section("pbfs_paxos3", _sec_pbfs_paxos3)
    section("tpc10_symmetry", _sec_tpc10_symmetry)
    section("paxos3", _sec_paxos3)
    section("paxos6", _sec_paxos6)
    section("tpc10_device", _sec_tpc10_device)
    section("tpc7_outofcore", _sec_tpc7_outofcore)

    # partial stays True if any section recorded a (platform) error: the
    # final line only claims completeness when every golden actually ran.
    any_errors = any(
        isinstance(v, dict) and "error" in v for v in detail.values()
    )

    emit(dev_rate, vs_threaded, partial=any_errors)

    return _gate_and_record(result)


if __name__ == "__main__":
    sys.exit(main())
