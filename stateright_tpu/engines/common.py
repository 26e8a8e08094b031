"""Shared scaffolding for the host engines.

Mirrors the per-thread structure of the reference engines (spawn → background
work loop → block processing with finish_when checks between 1500-state
blocks; src/checker/bfs.rs:90-164, dfs.rs:93-168). CPython threads provide the
same lifecycle semantics (join/report polling) even though the GIL serializes
Python-level work; the parallel hot paths live in the TPU engine and the
native core.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional

from ..checker import Checker, CheckerBuilder
from ..core import Expectation
from ..obs.coverage import Coverage
from ..obs.flight import FlightRecorder
from ..obs.log import get_logger
from ..obs.memory import MemoryRecorder
from ..obs.metrics import MetricsRegistry
from ..obs.trace import make_trace_writer, start_profile, stop_profile

BLOCK_SIZE = 1500  # states per finish_when re-check; reference bfs.rs:130

# Auto-N fusion (ISSUE 20): `_fuse_auto_n` needs this much flight history
# before trusting host_gap_pct, re-evaluates at this era cadence, and
# halves the factor only when the gap is already below this share of the
# wall clock (fusion has nothing left to amortize there).
FUSE_AUTO_MIN_ERAS = 8
FUSE_AUTO_RECHECK_ERAS = 8
FUSE_AUTO_LOW_GAP_PCT = 2.0

_log = get_logger("engines.common")


class HostEngineBase(Checker):
    """Common counters, lifecycle, and property bookkeeping for host engines."""

    # Host engines run one Python worker; parallel checking is the device
    # engine's job. Engines that genuinely parallelize set this True.
    _supports_threads = False

    def __init__(self, builder: CheckerBuilder, model=None, metrics=None):
        if builder.thread_count_ > 1 and not self._supports_threads:
            raise NotImplementedError(
                f"{type(self).__name__} is single-threaded; "
                "state-space parallelism lives in the batched device engine "
                "(CheckerBuilder.spawn_tpu_bfs). Drop .threads(n) or use the "
                "device engine."
            )
        # `model` lets engines that wrap the builder's model (e.g. a raw
        # TensorModel into its adapter) pass the WRAPPED model through
        # without mutating the caller's builder — a builder constructed
        # directly over a raw TensorModel must not crash in this base
        # constructor on the raw object's missing Model API.
        self._model = model if model is not None else builder.model
        self._properties = builder.model.properties()
        self._symmetry = builder.symmetry_fn_
        self._target_state_count = builder.target_state_count_
        self._target_max_depth = builder.target_max_depth_
        self._visitor = builder.visitor_
        self._finish_when = builder.finish_when_
        self._timeout = builder.timeout_
        self._thread_count = builder.thread_count_

        self._state_count = 0
        self._max_depth = 0
        # Observability: one metrics registry per run (obs/metrics.py) backs
        # Checker.telemetry() for every engine; an optional JSONL trace
        # stream and jax.profiler bracket ride the builder options. An
        # engine that times its own constructor passes its registry in.
        self._metrics = metrics if metrics is not None else MetricsRegistry()
        # Speclint pre-flight (stateright_tpu.analysis): in strict mode the
        # engine refuses to launch over error-severity findings; whenever a
        # report exists (strict auto-run or an explicit builder.lint()),
        # its diagnostic counts ride the metrics registry into telemetry.
        self._lint_preflight(builder)
        # Coverage accumulator (obs/coverage.py): per-action fire counts,
        # per-depth unique-state histogram, per-property eval/hit counts,
        # and dead-action detection — populated by every engine, surfaced
        # via Checker.coverage(). Tensor-backed models register their full
        # action universe up front (that is what makes a zero count a DEAD
        # action rather than merely an unobserved one).
        self._coverage = Coverage(enabled=getattr(builder, "coverage_", True))
        self._coverage.register_properties(p.name for p in self._properties)
        tm = getattr(self._model, "tm", None)
        if tm is not None and hasattr(tm, "max_actions"):
            self._coverage.register_actions(
                tm.format_action(a) for a in range(tm.max_actions)
            )
        self._action_label_memo: Dict[Any, str] = {}
        trace_path = getattr(builder, "trace_path_", None)
        self._trace = (
            make_trace_writer(
                trace_path,
                engine=type(self).__name__,
                format=getattr(builder, "trace_format_", "jsonl"),
            )
            if trace_path
            else None
        )
        self._profile_dir: Optional[str] = getattr(builder, "profile_dir_", None)
        # Flight recorder (obs/flight.py): bounded ring of per-era records
        # — device_era vs host_gap wall split plus frontier/table/spill
        # counters — fed by each device engine at its existing once-per-era
        # packed-params readback. Host engines carry the (empty) recorder
        # too so Checker.flight() and telemetry stay uniform.
        self._flight = (
            FlightRecorder(
                capacity=getattr(builder, "flight_capacity_", 4096),
                engine=type(self).__name__,
            )
            if getattr(builder, "flight_", True)
            else None
        )
        self._flight_path: Optional[str] = getattr(builder, "flight_path_", None)
        self._flight_format: str = getattr(builder, "flight_format_", "jsonl")
        self._flight_prev_counters: Dict[str, int] = {}
        # Memory recorder (obs/memory.py): exact per-component ledger of
        # device allocations + growth forecaster. Device engines register
        # their buffers after seeding and feed it at the same per-era
        # readback as the flight recorder; host engines carry the (empty)
        # recorder so telemetry()["memory"] stays uniform.
        self._memory = (
            MemoryRecorder(engine=type(self).__name__, metrics=self._metrics)
            if getattr(builder, "memory_", True)
            else None
        )
        # Space sampler (obs/sample.py): deterministic bottom-k
        # fingerprint sample of the explored space. Host engines offer at
        # visited-insertion; device engines drain their on-device
        # candidate slab at the per-era readback. The sample set is a
        # pure function of the explored set, so every engine over the
        # same model keeps the identical sample.
        from ..obs.sample import DEFAULT_SAMPLE_K, SpaceSampler

        self._sampler = (
            SpaceSampler(k=getattr(builder, "sample_k_", DEFAULT_SAMPLE_K))
            if getattr(builder, "sample_", True)
            else None
        )
        self._space_profile_cache: Optional[Dict[str, Any]] = None
        # Span ledger (obs/spans.py) via CheckerBuilder.spans(): the whole
        # run becomes one "run" span with phase-timer children; the run
        # span's id is pre-assigned so per-era progress spans can parent to
        # it before it is sealed in _run_guarded's finally.
        self._spans = getattr(builder, "span_recorder_", None)
        if self._spans is not None:
            from ..obs.spans import new_span_id, new_trace_id

            self._span_trace_id = (
                getattr(builder, "span_trace_id_", None) or new_trace_id()
            )
            self._span_parent_id = getattr(builder, "span_parent_id_", None)
            self._span_run_id = new_span_id()
        else:
            self._span_trace_id = None
            self._span_parent_id = None
            self._span_run_id = None
        self._span_run_start: Optional[float] = None
        self._span_last_event: Optional[float] = None
        self._last_phase_ms: Dict[str, float] = {}
        self._done = threading.Event()
        # Graceful-stop request (SIGTERM/SIGINT flush, see
        # install_signal_checkpoint_flush below): checkpointing engines poll
        # this at era boundaries, flush a final checkpoint, and exit clean.
        self._ckpt_stop = threading.Event()
        self._error: Optional[BaseException] = None
        self._deadline = (
            time.monotonic() + self._timeout if self._timeout is not None else None
        )

        # Eventually-property bitmask: bit i set <=> property i is an
        # eventually property not yet satisfied on the current path
        # (reference EventuallyBits, checker.rs:580-587).
        self._init_ebits = 0
        for i, p in enumerate(self._properties):
            if p.expectation == Expectation.EVENTUALLY:
                self._init_ebits |= 1 << i

        self._thread: Optional[threading.Thread] = None
        # Pre-run snapshot for deterministic first "Checking." report lines;
        # engines refresh it after seeding counts, before starting the thread.
        self._initial_snapshot = (0, 0, 0)

    def _lint_preflight(self, builder: CheckerBuilder) -> None:
        report = getattr(builder, "lint_report_", None)
        if getattr(builder, "strict_", False) and report is None:
            report = builder.lint(samples=getattr(builder, "strict_samples_", 128))
        if report is None:
            return
        for code, n in report.counts_by_code().items():
            self._metrics.inc(f"lint_{code}", n)
        self._metrics.set_gauge("lint_errors", len(report.errors))
        self._metrics.set_gauge("lint_warnings", len(report.warnings))
        if getattr(builder, "strict_", False) and not report.ok:
            from ..analysis import SpecLintError

            raise SpecLintError(report)

    # -- lifecycle ----------------------------------------------------------

    def _start(self) -> None:
        self._initial_snapshot = (self._state_count, self.unique_state_count(), 0)
        self._thread = threading.Thread(target=self._run_guarded, daemon=True)
        self._thread.start()

    def _run_guarded(self) -> None:
        profiling = (
            start_profile(self._profile_dir) if self._profile_dir else False
        )
        if self._spans is not None:
            self._span_run_start = time.time()
            self._span_last_event = self._span_run_start
        if self._trace is not None:
            self._trace.emit(
                "run_start",
                states=int(self._state_count),
                unique=int(self.unique_state_count()),
            )
        if self._flight is not None:
            self._flight.start()
        try:
            self._run()
        except BaseException as e:  # surfaces at join(), like a Rust panic
            self._error = e
        finally:
            if profiling:
                stop_profile()
            if self._trace is not None:
                self._trace.emit(
                    "run_end",
                    states=int(self._state_count),
                    unique=int(self.unique_state_count()),
                    max_depth=int(self._max_depth),
                    phase_ms=self._metrics.phase_ms(),
                    error=repr(self._error) if self._error else None,
                    **(
                        {"space": self._sampler.snapshot()}
                        if self._sampler is not None and self._sampler.size()
                        else {}
                    ),
                )
            self._flush_flight()
            if self._spans is not None:
                self._seal_run_span()
            if self._trace is not None:
                self._trace.close()
            self._done.set()

    def _flush_flight(self) -> None:
        """At run end: export the flight recording if a path was
        configured, and append its counter tracks to a Chrome-format run
        trace so Perfetto lines them up under the phase lanes. Must run
        before ``self._trace.close()``."""
        fr = self._flight
        if fr is None or not len(fr):
            return
        if self._flight_path:
            try:
                if self._flight_format == "chrome":
                    fr.export_chrome(self._flight_path)
                else:
                    fr.export_jsonl(self._flight_path)
            except OSError as exc:
                _log.warning(
                    "flight export failed",
                    path=self._flight_path,
                    error=repr(exc),
                )
        if self._trace is not None and hasattr(self._trace, "write_counter_events"):
            self._trace.write_counter_events(fr.chrome_counter_events())

    def _seal_run_span(self) -> None:
        """Record the run span (pre-assigned id, so per-era children are
        already parented to it), attach one child span per phase timer,
        and — when the run also wrote a Chrome trace — embed the ledger
        into the trace file so phases and request spans share one
        Perfetto timeline."""
        from ..obs.spans import attach_phase_spans

        end = time.time()
        attach_phase_spans(
            self._spans,
            self._metrics.phase_ms(),
            trace_id=self._span_trace_id,
            parent_id=self._span_run_id,
            end=end,
            attributes={"engine": type(self).__name__},
        )
        self._spans.record(
            "run",
            start=self._span_run_start or end,
            end=end,
            trace_id=self._span_trace_id,
            span_id=self._span_run_id,
            parent_id=self._span_parent_id,
            status="error" if self._error else "ok",
            attributes={
                "engine": type(self).__name__,
                "states": int(self._state_count),
                "unique": int(self.unique_state_count()),
                "max_depth": int(self._max_depth),
                **({"error": repr(self._error)} if self._error else {}),
            },
        )
        if self._trace is not None and hasattr(self._trace, "embed_spans"):
            self._trace.embed_spans(self._spans.spans(self._span_trace_id))

    def _run(self) -> None:
        raise NotImplementedError

    def join(self) -> "HostEngineBase":
        if self._thread is not None:
            self._thread.join()
        if self._error is not None:
            raise self._error
        return self

    def is_done(self) -> bool:
        return self._done.is_set()

    def request_checkpoint_stop(self) -> None:
        """Ask the run to stop at its next era/block boundary, flushing a
        final checkpoint first (checkpointing engines poll this; engines
        without checkpoint support simply finish their run). Thread- and
        signal-safe: only sets an event."""
        self._ckpt_stop.set()

    def interrupted(self) -> bool:
        """True when the run stopped early on a graceful-stop request
        (SIGTERM/SIGINT flush or an explicit request_checkpoint_stop)."""
        return self._ckpt_stop.is_set() and self._done.is_set()

    # -- counters -----------------------------------------------------------

    def state_count(self) -> int:
        return self._state_count

    def max_depth(self) -> int:
        return self._max_depth

    # -- observability ------------------------------------------------------

    def telemetry(self) -> Dict[str, Any]:
        """The run's metrics-registry snapshot (counters + gauges +
        cumulative phase_ms; names catalogued in obs/metrics.py)."""
        if self._coverage.enabled:
            acts = self._coverage.action_counts()
            self._metrics.set_gauge(
                "coverage_actions_fired", sum(1 for v in acts.values() if v)
            )
            if self._coverage.action_labels is not None:
                self._metrics.set_gauge(
                    "coverage_dead_actions", len(self._coverage.dead_actions())
                )
        if self._sampler is not None and self._sampler.size():
            self._sampler.set_gauges(self._metrics)
        snap = self._metrics.snapshot()
        if self._flight is not None:
            fsum = self._flight.summary()
            if fsum["eras"]:
                snap["flight"] = fsum
        if self._memory is not None and self._memory.ledger.components():
            snap["memory"] = self._memory.snapshot()
        if self._sampler is not None and self._sampler.size():
            snap["space"] = self._sampler.snapshot()
        program = self._program_snapshot(snap)
        if program:
            snap["program"] = program
        snap["engine"] = type(self).__name__
        return snap

    def _program_snapshot(self, snap: Dict[str, Any]) -> Dict[str, Any]:
        """The STR6xx static program summary for this run's model, when a
        program-lint pass has produced one this process (a cached dict
        lookup — this NEVER traces or compiles), with the flight
        recorder's measured rate beside the STR606 prediction as an
        attribution ratio: measured/predicted ≈ 1 means the roofline
        explains the run; << 1 means dispatch gap or host stalls own it."""
        tm = getattr(self._model, "tm", None)
        if tm is None:
            return {}
        try:
            from ..analysis.program import cached_summary
            from .compiled import model_signature

            summary = cached_summary(model_signature(tm))
        except Exception:
            return {}
        if not summary:
            return {}
        era = summary.get("programs", {}).get("era_loop", {})
        out: Dict[str, Any] = {
            "signature": summary.get("signature"),
            "era_ops": era.get("ops"),
            "era_distinct_ops": era.get("distinct"),
        }
        cost = summary.get("cost") or {}
        if cost:
            out.update(
                {
                    "flops_per_step": cost.get("flops_per_step"),
                    "bytes_per_step": cost.get("bytes_per_step"),
                    "device_kind": cost.get("device_kind"),
                    "hbm_gbps": cost.get("hbm_gbps"),
                }
            )
            predicted = cost.get("predicted_states_per_sec")
            if predicted:
                out["predicted_states_per_sec"] = predicted
                wall = (snap.get("flight") or {}).get("wall_secs") or 0.0
                if wall > 0 and self._state_count:
                    measured = self._state_count / wall
                    out["measured_states_per_sec"] = measured
                    out["attribution_ratio"] = measured / predicted
        return out

    def coverage(self) -> Dict[str, Any]:
        """The run's coverage snapshot (obs/coverage.py)."""
        return self._coverage.snapshot()

    def flight(self) -> list:
        """Retained flight records (obs/flight.py), oldest first. Empty
        for engines without an era loop or when .flight(False) was set."""
        return self._flight.records() if self._flight is not None else []

    def _sample_resolver(self):
        """fp64 -> {"state","pred","action"} backfill for samples drained
        fingerprint-only (device engines override with their path
        reconstructor); None means rows were captured at offer time."""
        return None

    def _path_sample_resolver(self, reconstruct):
        """Wrap an fp -> Path reconstructor into a sample resolver: the
        path's final state is the sample, its last step the (pred,
        action) exemplar transition, its length the BFS depth."""

        def resolve(fp: int):
            path = reconstruct(fp)
            pairs = path.into_vec()
            out = {"state": pairs[-1][0], "depth": len(pairs)}
            if len(pairs) >= 2:
                out["pred"], out["action"] = pairs[-2]
            return out

        return resolve

    def space_profile(self) -> Dict[str, Any]:
        """The run's space profile (obs/sample.py): the bottom-k sample
        rendered into field sketches, depth/action exemplars, and
        saturation warnings. Built on demand; cached once the run is
        done (device engines resolve sample rows via path
        reconstruction, which is worth doing once, not per poll)."""
        if self._sampler is None or not self._sampler.size():
            return {}
        if self._space_profile_cache is not None:
            return self._space_profile_cache
        from ..obs.sample import build_space_profile

        profile = build_space_profile(
            self._model, self._sampler, resolver=self._sample_resolver()
        )
        if self.is_done():
            self._space_profile_cache = profile
        return profile

    def _flight_record(
        self,
        *,
        device_era_secs: float,
        steps: int = 0,
        generated: int = 0,
        unique: int = 0,
        frontier: int = 0,
        load_factor: float = 0.0,
        take_cap: int = 0,
        spill_rows: int = 0,
        shards: Optional[Dict[str, Any]] = None,
        grow_rows: Optional[int] = None,
        inner: Optional[List[Dict[str, Any]]] = None,
    ) -> None:
        """Append one era to the flight recording (no-op when disabled).
        Registry counters that move off the hot path (refill/grow/
        checkpoint) are diffed against the previous era here, so engines
        don't have to thread per-era volumes through their loops.
        ``grow_rows`` is what the engine's table-grow trigger compares
        (max per-shard unique on the mesh); the memory forecaster fits
        its growth curve to it, defaulting to ``unique``. ``inner`` is
        the per-inner-era attribution of a FUSED dispatch (fields per
        FlightRecorder.record_fused): the one readback then appends
        len(inner) records, with the once-per-dispatch counters on the
        last."""
        mem = None
        if self._memory is not None:
            mem = self._memory.on_era(
                unique=unique, load_factor=load_factor, grow_rows=grow_rows
            )
        fr = self._flight
        if fr is None:
            return
        cur = {
            name: self._metrics.get(name)
            for name in ("refill_rows", "table_growths", "checkpoint_saves")
        }
        prev = self._flight_prev_counters
        self._flight_prev_counters = cur
        if inner is not None and len(inner) > 1:
            rec = fr.record_fused(
                device_era_secs=device_era_secs,
                inner=inner,
                take_cap=take_cap,
                spill_rows=spill_rows,
                refill_rows=cur["refill_rows"] - prev.get("refill_rows", 0),
                table_growths=(
                    cur["table_growths"] - prev.get("table_growths", 0)
                ),
                checkpoint_saves=(
                    cur["checkpoint_saves"]
                    - prev.get("checkpoint_saves", 0)
                ),
                shards=shards,
                memory=mem,
            )
        else:
            rec = fr.record(
                device_era_secs=device_era_secs,
                steps=steps,
                generated=generated,
                unique=unique,
                frontier=frontier,
                load_factor=load_factor,
                take_cap=take_cap,
                spill_rows=spill_rows,
                refill_rows=cur["refill_rows"] - prev.get("refill_rows", 0),
                table_growths=(
                    cur["table_growths"] - prev.get("table_growths", 0)
                ),
                checkpoint_saves=(
                    cur["checkpoint_saves"]
                    - prev.get("checkpoint_saves", 0)
                ),
                shards=shards,
                memory=mem,
            )
        # Flat twins of the latest record for Prometheus (nested dicts are
        # skipped by render_prometheus) and the SSE metrics deltas.
        m = self._metrics
        m.set_gauge("flight_eras", rec["era"])
        m.set_gauge("flight_device_era_secs", rec["device_era_secs"])
        m.set_gauge("flight_host_gap_secs", rec["host_gap_secs"])

    def _fuse_auto_n(self, fuse: int) -> int:
        """Auto-N fusion pick (ISSUE 20 satellite of ROADMAP item 1a):
        instead of pinning the compiled maximum, choose the inner-era cap
        from recent flight history. A high host gap means the dispatch
        gap dominates — run the full factor; a near-zero gap means fusion
        has little left to amortize, so halve the exposure to mid-dispatch
        overshoot (never below 2: one compiled program serves every N and
        a degrade-to-1 already has its own triggers in `_fuse_lim_now`).
        Recomputed every FUSE_AUTO_RECHECK_ERAS eras — `summary()` walks
        the whole recording. The chosen N lands on the `fuse_auto_n`
        gauge (gate-tracked in bench history)."""
        eras = self._metrics.get("eras")
        cached = getattr(self, "_fuse_auto_cache", None)
        if cached is not None and eras - cached[0] < FUSE_AUTO_RECHECK_ERAS:
            return cached[1]
        n = fuse
        fr = self._flight
        if fr is not None:
            s = fr.summary()
            if s.get("eras", 0) >= FUSE_AUTO_MIN_ERAS:
                gap = float(s.get("host_gap_pct") or 0.0)
                if gap < FUSE_AUTO_LOW_GAP_PCT:
                    n = max(2, fuse // 2)
        self._fuse_auto_cache = (eras, n)
        self._metrics.set_gauge("fuse_auto_n", n)
        return n

    def _action_label(self, action: Any) -> str:
        """Memoized model.format_action — hot-loop action attribution must
        not re-format per generated successor. Unhashable actions fall
        back to formatting each time."""
        try:
            label = self._action_label_memo.get(action)
            if label is None:
                label = self._model.format_action(action)
                self._action_label_memo[action] = label
            return label
        except TypeError:
            return self._model.format_action(action)

    def _phase_ms_delta(self) -> Dict[str, float]:
        """Per-event phase-timer deltas (ms since the previous trace
        event) — what the JSONL era/wave events carry."""
        cur = self._metrics.phase_ms()
        last = self._last_phase_ms
        self._last_phase_ms = cur
        return {
            k: round(v - last.get(k, 0.0), 3)
            for k, v in cur.items()
            if v - last.get(k, 0.0) > 0.0 or k not in last
        }

    def _obs_event(self, event: str, frontier: int = 0, **extra: Any) -> None:
        """Record one unit of forward progress: refresh the standard gauges
        and, when tracing, emit the JSONL event for it."""
        m = self._metrics
        m.set_gauge("frontier_size", int(frontier))
        m.set_gauge("max_depth", int(self._max_depth))
        if self._spans is not None:
            # One progress span per era/wave/round, spanning the gap since
            # the previous progress event, under the run span.
            now = time.time()
            self._spans.record(
                event,
                start=self._span_last_event or now,
                end=now,
                trace_id=self._span_trace_id,
                parent_id=self._span_run_id,
                attributes={
                    "states": int(self._state_count),
                    "unique": int(self.unique_state_count()),
                    "frontier": int(frontier),
                },
            )
            self._span_last_event = now
        if self._trace is not None:
            if self._coverage.enabled and "coverage" not in extra:
                # Cumulative per-action fire counts ride every progress
                # event, so a trace alone reconstructs coverage over time.
                extra["coverage"] = {"actions": self._coverage.action_counts()}
            self._trace.emit(
                event,
                states=int(self._state_count),
                unique=int(self.unique_state_count()),
                frontier=int(frontier),
                max_depth=int(self._max_depth),
                phase_ms=self._phase_ms_delta(),
                **extra,
            )

    # -- shared helpers -----------------------------------------------------

    def _timed_out(self) -> bool:
        return self._deadline is not None and time.monotonic() >= self._deadline

    def _fp(self, state: Any) -> int:
        return self._model.fingerprint_state(state)

    def _check_properties(
        self, state: Any, ebits: int, discoveries: Dict[str, Any], discovery_value
    ) -> tuple[int, bool]:
        """Evaluate all properties on one state being processed.

        Returns (updated ebits, is_awaiting_discoveries). Inserts discoveries
        for failed always / satisfied sometimes properties. Mirrors the
        property loop at bfs.rs:231-277 / dfs.rs:235-281.
        """
        model = self._model
        cov = self._coverage if self._coverage.enabled else None
        is_awaiting = False
        for i, prop in enumerate(self._properties):
            if prop.name in discoveries:
                continue
            if cov is not None:
                cov.record_property_eval(prop.name)
            if prop.expectation == Expectation.ALWAYS:
                if not prop.condition(model, state):
                    discoveries[prop.name] = discovery_value()
                    if cov is not None:
                        cov.record_property_hit(prop.name)
                else:
                    is_awaiting = True
            elif prop.expectation == Expectation.SOMETIMES:
                if prop.condition(model, state):
                    discoveries[prop.name] = discovery_value()
                    if cov is not None:
                        cov.record_property_hit(prop.name)
                else:
                    is_awaiting = True
            else:  # EVENTUALLY: discoveries only arise at terminal states
                is_awaiting = True
                if prop.condition(model, state):
                    ebits &= ~(1 << i)
        return ebits, is_awaiting

    def _terminal_ebit_discoveries(
        self, ebits: int, discoveries: Dict[str, Any], discovery_value
    ) -> None:
        """At a terminal state, any surviving eventually-bit is a counterexample
        (bfs.rs:326-333)."""
        if not ebits:
            return
        for i, prop in enumerate(self._properties):
            if ebits & (1 << i):
                discoveries[prop.name] = discovery_value()
                if self._coverage.enabled:
                    self._coverage.record_property_hit(prop.name)

    def _finish_matched(self, discoveries: Dict[str, Any]) -> bool:
        return self._finish_when.matches(set(discoveries), self._properties)


# -- checkpoint metadata (shared by the device engines) ----------------------

FP_VER = 2  # round-4 decorrelated hash pair (fingerprint.py mix note)


def checkpoint_meta(tm, tprops, **fields) -> dict:
    """Common identity header for engine checkpoints: fingerprint version,
    model class + parameter digest, and property set — a resumed table is
    only meaningful for the exact model, properties, and hash that wrote
    it. Engine-specific fields are passed through."""
    meta = {
        "fp_ver": FP_VER,
        "model": f"{type(tm).__module__}.{type(tm).__qualname__}",
        "model_config": tm.config_digest(),
        "prop_names": [p.name for p in tprops],
        "state_width": tm.state_width,
    }
    meta.update(fields)
    return meta


def validate_checkpoint_meta(meta: dict, tm, tprops, exact: dict) -> None:
    """Reject a checkpoint whose identity or layout does not match this
    checker. `exact` maps field name -> required value (qcap, n_shards,
    chunk, quota, ...); every listed field must match exactly."""
    if meta.get("fp_ver") != FP_VER:
        raise ValueError(
            "checkpoint was written with a different fingerprint hash "
            f"version ({meta.get('fp_ver')!r} != {FP_VER}); its table keys "
            "are incompatible"
        )
    this_model = f"{type(tm).__module__}.{type(tm).__qualname__}"
    if meta.get("model") != this_model:
        raise ValueError(
            f"checkpoint was written by model {meta.get('model')!r}; "
            f"resuming it with {this_model!r} would silently produce wrong "
            "results"
        )
    if meta.get("model_config") != tm.config_digest():
        raise ValueError(
            f"checkpoint was written with model config "
            f"{meta.get('model_config')!r}; this instance has "
            f"{tm.config_digest()!r} — same-width different-parameter "
            "models must not share a visited table"
        )
    this_props = [p.name for p in tprops]
    if meta.get("prop_names") != this_props:
        raise ValueError(
            f"checkpoint property set {meta.get('prop_names')} does not "
            f"match this checker's {this_props}; rec_fp/rec_bits would "
            "misalign"
        )
    for field, want in exact.items():
        if meta.get(field) != want:
            raise ValueError(
                f"checkpoint {field}={meta.get(field)!r} does not match "
                f"this checker's {want!r}; resume with matching engine "
                "options"
            )


# -- crash-safe checkpoint IO (shared by the device engines) ------------------
#
# The write protocol: serialize to `<path>.tmp.npz`, fsync the file, rotate
# the previous generations (`<path>` -> `<path>.1` -> ... -> `<path>.N-1`),
# rename the tmp over `<path>`, and fsync the directory so the rename itself
# survives a crash. Every checkpoint carries a sha256 content digest in its
# meta; the loader recomputes it and rejects truncated/corrupt files with
# CheckpointCorruptError, falling back to the previous good generation.


class CheckpointCorruptError(RuntimeError):
    """A checkpoint file is unreadable, truncated, or fails its digest."""


def validate_checkpoint_cadence(checkpoint_every, checkpoint_path,
                                keep_checkpoints) -> None:
    """Builder-time validation of the checkpoint knobs, shared by the
    device engines. `checkpoint_every` is wall-clock SECONDS between
    periodic checkpoints (polled at era boundaries); non-positive values
    are a configuration error, not "checkpoint constantly"."""
    if checkpoint_every is not None:
        if checkpoint_path is None:
            raise ValueError(
                "checkpoint_every requires checkpoint_path (nothing would "
                "be written otherwise)"
            )
        if not float(checkpoint_every) > 0.0:
            raise ValueError(
                "checkpoint_every is wall-clock seconds between periodic "
                f"checkpoints and must be positive (got {checkpoint_every!r}); "
                "omit it to checkpoint only at run end"
            )
    if keep_checkpoints < 1:
        raise ValueError(
            f"keep_checkpoints must be >= 1 (got {keep_checkpoints})"
        )


def _checkpoint_digest(arrays: dict) -> str:
    """sha256 over every payload array's name, dtype, shape, and bytes
    (sorted by name; the meta array itself is excluded — it carries the
    digest)."""
    import hashlib

    import numpy as np

    h = hashlib.sha256()
    for name in sorted(arrays):
        if name == "meta":
            continue
        a = np.ascontiguousarray(arrays[name])
        h.update(name.encode())
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def checkpoint_generations(path: str) -> list:
    """All on-disk generations for `path`, newest first (`path`, then
    `path.1`, `path.2`, ...)."""
    import os

    out = [path] if os.path.exists(path) else []
    g = 1
    while os.path.exists(f"{path}.{g}"):
        out.append(f"{path}.{g}")
        g += 1
    return out


def _write_npz_atomic(path: str, meta: dict, arrays: dict) -> dict:
    """Digest + serialize one npz to ``path + ".tmp.npz"``, fsynced.
    Returns the final meta (with the digest); the caller finishes the
    rename so it can interleave generation rotation."""
    import json
    import os

    import numpy as np

    meta = dict(meta)
    meta["digest"] = _checkpoint_digest(arrays)
    payload = dict(arrays)
    payload["meta"] = np.frombuffer(
        json.dumps(meta).encode(), dtype=np.uint8
    ).copy()
    tmp = path + ".tmp.npz"  # savez appends .npz to bare paths otherwise
    with open(tmp, "wb") as f:
        np.savez_compressed(f, **payload)
        f.flush()
        os.fsync(f.fileno())
    return meta


def _fsync_dir(path: str) -> None:
    import os

    try:
        dfd = os.open(os.path.dirname(os.path.abspath(path)), os.O_RDONLY)
        try:
            os.fsync(dfd)
        finally:
            os.close(dfd)
    except OSError:
        pass  # platforms without directory fsync still get the file fsync


def save_checkpoint_atomic(path: str, meta: dict, arrays: dict, *,
                           keep: int = 1, metrics=None) -> dict:
    """Write one checkpoint crash-safely: tmp + fsync + generation rotation
    + rename + directory fsync, with the content digest in the manifest.
    Returns the final meta — the delta layer pins its chain to the
    returned ``digest``."""
    import os

    t0 = time.monotonic()
    meta = _write_npz_atomic(path, meta, arrays)
    tmp = path + ".tmp.npz"
    # Rotate the survivors BEFORE the rename lands: the previous good
    # checkpoint must exist (as `.1`) at every instant a crash could hit.
    if keep > 1 and os.path.exists(path):
        for g in range(keep - 1, 1, -1):
            older = f"{path}.{g - 1}"
            if os.path.exists(older):
                os.replace(older, f"{path}.{g}")
        os.replace(path, f"{path}.1")
    os.replace(tmp, path)
    _fsync_dir(path)
    if metrics is not None:
        metrics.inc("checkpoint_saves")
        metrics.inc("checkpoint_bytes", os.path.getsize(path))
        metrics.add_phase("checkpoint_save", time.monotonic() - t0)
    return meta


def load_checkpoint_verified(path: str):
    """Load one checkpoint file and verify its content digest. Returns
    ``(arrays, meta)``; raises CheckpointCorruptError on an unreadable
    zip, missing/garbled meta, or digest mismatch."""
    import json

    import numpy as np

    try:
        data = np.load(path)
        meta = json.loads(bytes(data["meta"]).decode())
        arrays = {k: data[k] for k in data.files if k != "meta"}
    except Exception as exc:
        raise CheckpointCorruptError(
            f"checkpoint {path!r} is unreadable (truncated or corrupt): "
            f"{type(exc).__name__}: {exc}"
        ) from exc
    want = meta.get("digest")
    if want is None:
        raise CheckpointCorruptError(
            f"checkpoint {path!r} carries no content digest (pre-durability "
            "layout); re-create it with the current engine"
        )
    got = _checkpoint_digest(arrays)
    if got != want:
        raise CheckpointCorruptError(
            f"checkpoint {path!r} fails its content digest "
            f"({got[:12]}... != recorded {want[:12]}...); the file is corrupt"
        )
    return arrays, meta


def load_checkpoint_with_fallback(path: str, metrics=None):
    """Load the newest verifiable checkpoint generation. A corrupt or
    truncated `path` falls back to `path.1`, `path.2`, ... (written by
    `save_checkpoint_atomic(keep=N)`); only when every generation fails
    does the error propagate, carrying each failure."""
    candidates = checkpoint_generations(path)
    if not candidates:
        raise FileNotFoundError(f"no checkpoint at {path!r}")
    failures = []
    for cand in candidates:
        try:
            arrays, meta = load_checkpoint_verified(cand)
        except CheckpointCorruptError as exc:
            failures.append(str(exc))
            if metrics is not None:
                metrics.inc("checkpoint_corrupt_rejected")
            continue
        if cand != path:
            if metrics is not None:
                metrics.inc("checkpoint_fallbacks")
            _log.warning(
                "checkpoint rejected; resuming from previous generation",
                path=path,
                reason=failures[-1] if failures else "missing",
                fallback=cand,
            )
        return arrays, meta
    raise CheckpointCorruptError(
        "no loadable checkpoint generation:\n  " + "\n  ".join(failures)
    )


# -- incremental delta checkpoints (ISSUE 20, on top of the generational
# protocol above) -------------------------------------------------------------
#
# A large visited table rewrites gigabytes every cadence tick under the
# full-save protocol, yet between ticks only newly claimed slots change
# (slots never move absent a rehash, and a rehash doubles tcap — which
# forces a fresh base). A delta checkpoint therefore carries: every
# non-table array verbatim (ring, heads/counts, rec fps, spill blocks —
# all small next to the table) plus ONLY the table slots occupied since
# the BASE generation was written (cumulative-vs-base, so a single
# delta + the base reconstructs the newest state and every older delta
# is disposable). The meta manifest pins the chain to the base's content
# digest and records per-region occupancy watermarks; the fold validates
# both, and any failure falls back delta-by-delta to the plain base
# (then the base's own generation fallback). Rolling compaction: once
# the chain reaches DELTA_CHAIN_MAX the next save is a fresh full base
# and the old chain is cleared.

DELTA_CHAIN_MAX = 4
# Occupancy watermarks are recorded per probe region (equal flat-index
# stripes of the table): a fold that silently dropped or duplicated
# rows shows up as a region-count mismatch even when digests agree.
TABLE_DELTA_REGIONS = 64


def delta_chain_paths(path: str) -> list:
    """On-disk delta chain for base `path`, oldest first
    (`path.d1`, `path.d2`, ...)."""
    import os

    out = []
    g = 1
    while os.path.exists(f"{path}.d{g}"):
        out.append(f"{path}.d{g}")
        g += 1
    return out


def clear_delta_chain(path: str) -> None:
    """Remove every delta of base `path` (after a compacting full save;
    a crash in between leaves stale deltas whose base-digest check
    rejects them on load — safe either way)."""
    import os

    for dpath in delta_chain_paths(path):
        try:
            os.unlink(dpath)
        except OSError:
            pass


def table_region_occupancy(occ_flat) -> list:
    """Per-region occupied-slot counts over the flattened table
    occupancy mask (the delta manifest's insert watermarks)."""
    import numpy as np

    occ_flat = np.asarray(occ_flat).reshape(-1)
    n = occ_flat.shape[0]
    r = min(TABLE_DELTA_REGIONS, max(1, n))
    edges = (np.arange(r, dtype=np.int64) * n) // r
    return [int(v) for v in np.add.reduceat(occ_flat.astype(np.int64), edges)]


def save_checkpoint_tiered(path: str, meta: dict, arrays: dict, *,
                           state, tcap: int, keep: int = 1, metrics=None,
                           chain_max: int = DELTA_CHAIN_MAX):
    """Save either a full base generation or a delta against the current
    base, whichever the chain state calls for. ``state`` is the opaque
    per-engine chain state (``None`` initially and after any resume);
    returns the new state. A tcap change (growth/reshard rehashed every
    slot) or a chain at ``chain_max`` forces a compacting full save."""
    import numpy as np

    occ = (
        (np.asarray(arrays["table0"]) != 0)
        | (np.asarray(arrays["table1"]) != 0)
    ).reshape(-1)
    if (
        state is None
        or state.get("tcap") != tcap
        or state.get("seq", 0) >= chain_max
    ):
        full_meta = save_checkpoint_atomic(
            path, meta, arrays, keep=keep, metrics=metrics
        )
        clear_delta_chain(path)
        return {
            "occ": occ,
            "tcap": int(tcap),
            "seq": 0,
            "base_digest": full_meta["digest"],
        }
    seq = state["seq"] + 1
    idx = np.flatnonzero(occ & ~state["occ"])
    darrays = {
        k: v for k, v in arrays.items() if not k.startswith("table")
    }
    darrays["delta_idx"] = idx.astype(np.int64)
    for t in range(4):
        darrays[f"delta_t{t}"] = (
            np.asarray(arrays[f"table{t}"]).reshape(-1)[idx]
        )
    meta = dict(meta)
    meta["delta"] = {
        "base_digest": state["base_digest"],
        "seq": int(seq),
        "base_tcap": int(tcap),
        "regions": table_region_occupancy(occ),
    }
    save_checkpoint_delta(f"{path}.d{seq}", meta, darrays, metrics=metrics)
    state = dict(state)
    state["seq"] = seq
    return state


def save_checkpoint_delta(dpath: str, meta: dict, arrays: dict, *,
                          metrics=None) -> dict:
    """Crash-safe write of one delta file (tmp + fsync + rename + dir
    fsync; no generation rotation — the chain IS the history)."""
    import os

    t0 = time.monotonic()
    meta = _write_npz_atomic(dpath, meta, arrays)
    os.replace(dpath + ".tmp.npz", dpath)
    _fsync_dir(dpath)
    if metrics is not None:
        metrics.inc("checkpoint_delta_saves")
        metrics.inc("checkpoint_delta_bytes", os.path.getsize(dpath))
        metrics.inc("checkpoint_delta_rows", int(len(arrays["delta_idx"])))
        metrics.add_phase("checkpoint_save", time.monotonic() - t0)
    return meta


def _fold_table_delta(base_data: dict, ddata: dict) -> dict:
    """Newest engine state = the delta's non-table arrays + the base's
    table lanes with the delta rows scattered in."""
    import numpy as np

    folded = {
        k: v for k, v in ddata.items() if not k.startswith("delta_")
    }
    idx = np.asarray(ddata["delta_idx"]).reshape(-1)
    for t in range(4):
        lane = np.array(base_data[f"table{t}"])  # copy; base stays pristine
        lane.reshape(-1)[idx] = ddata[f"delta_t{t}"]
        folded[f"table{t}"] = lane
    return folded


def load_checkpoint_folded(path: str, metrics=None):
    """Load the newest recoverable engine state: the newest verifiable
    base generation with the newest verifiable delta (pinned to that
    base's digest, region watermarks revalidated post-fold) folded on.
    Falls back delta-by-delta to the plain base; base-generation
    fallback itself is `load_checkpoint_with_fallback`."""
    import numpy as np

    base_data, base_meta = load_checkpoint_with_fallback(
        path, metrics=metrics
    )
    base_digest = base_meta.get("digest")
    for dpath in reversed(delta_chain_paths(path)):
        try:
            ddata, dmeta = load_checkpoint_verified(dpath)
            man = dmeta.get("delta") or {}
            if man.get("base_digest") != base_digest:
                # STALE, not corrupt: the base itself fell back a
                # generation (or the chain outlived a compaction), so a
                # digest-mismatched delta is the EXPECTED leftover of the
                # newer base — skip it without the corruption counters
                # (the base-fallback counter already told that story).
                if metrics is not None:
                    metrics.inc("checkpoint_delta_stale")
                _log.warning(
                    "delta checkpoint stale for the loaded base; skipped",
                    path=dpath,
                )
                continue
            folded = _fold_table_delta(base_data, ddata)
            occ = (
                (np.asarray(folded["table0"]) != 0)
                | (np.asarray(folded["table1"]) != 0)
            )
            if table_region_occupancy(occ) != list(man.get("regions", [])):
                raise CheckpointCorruptError(
                    f"delta checkpoint {dpath!r} fails its per-region "
                    "insert watermarks after folding"
                )
        except CheckpointCorruptError as exc:
            if metrics is not None:
                metrics.inc("checkpoint_corrupt_rejected")
                metrics.inc("checkpoint_fallbacks")
            _log.warning(
                "delta checkpoint rejected; falling back",
                path=dpath,
                reason=str(exc),
            )
            continue
        if metrics is not None:
            metrics.inc("checkpoint_delta_folds")
        return folded, dmeta
    return base_data, base_meta


# -- SIGTERM/SIGINT final-checkpoint flush ------------------------------------
#
# Preempted runs should resume, not restart: the FIRST signal asks every
# live checkpointing engine to stop at its next era boundary (each flushes
# a final checkpoint on the way out; the caller's join() then returns
# normally with partial results). The previous handler is restored after
# that first delivery, so a second signal behaves as before (force-kill /
# KeyboardInterrupt).

_signal_engines = None  # lazy WeakSet; module import must not cost anything
_signal_installed: Dict[int, Any] = {}


def register_signal_checkpoint_flush(engine) -> None:
    """Enroll a checkpointing engine in the graceful-flush set and install
    the SIGTERM/SIGINT handlers (first call only; no-op off the main
    thread, where CPython forbids signal.signal)."""
    global _signal_engines
    import signal
    import weakref

    if _signal_engines is None:
        _signal_engines = weakref.WeakSet()
    _signal_engines.add(engine)
    if _signal_installed:
        return
    for signum in (signal.SIGTERM, signal.SIGINT):
        try:
            _signal_installed[signum] = signal.signal(
                signum, _flush_signal_handler
            )
        except ValueError:
            # Not the main thread (e.g. an engine constructed inside a serve
            # worker): graceful flush still works via an explicit
            # request_checkpoint_stop(); only the OS hook is unavailable.
            _signal_installed.clear()
            return


def _flush_signal_handler(signum, frame) -> None:
    import signal

    for engine in list(_signal_engines or ()):
        engine.request_checkpoint_stop()
    # One graceful chance: restore the previous handlers so the next
    # signal is forceful.
    for num, prev in _signal_installed.items():
        try:
            signal.signal(num, prev if prev is not None else signal.SIG_DFL)
        except (ValueError, TypeError):
            pass
    _signal_installed.clear()
