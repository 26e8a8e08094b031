"""CheckerBuilder / Checker: configure, launch, and query checking runs.

Reference: src/checker.rs:65-578. The builder carries the model plus options
(threads, symmetry, target_state_count, target_max_depth, finish_when,
timeout, visitor) and spawns one of the engines:

  - `spawn_bfs()`        host breadth-first search (engines/bfs.py)
  - `spawn_dfs()`        host depth-first search (engines/dfs.py)
  - `spawn_on_demand()`  lazy BFS for the Explorer (engines/on_demand.py)
  - `spawn_simulation()` seeded random walks (engines/simulation.py)
  - `spawn_tpu_bfs()`    the TPU-native batched frontier engine
                         (engines/tpu_bfs.py) — new in this framework
  - `serve()`            Explorer web service over an on-demand checker

`Checker` exposes state_count / unique_state_count / max_depth / discoveries
and assertion helpers, matching checker.rs:294-578.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional

from .core import Expectation, Model
from .has_discoveries import HasDiscoveries
from .path import Path
from .report import ReportData, ReportDiscovery, Reporter
from .visitor import CheckerVisitor, as_visitor


class DiscoveryClassification:
    """Reference: checker.rs:39-53."""

    EXAMPLE = "example"
    COUNTEREXAMPLE = "counterexample"


class CheckerBuilder:
    """Fluent options builder. Reference: checker.rs:65-288."""

    def __init__(self, model: Model):
        self.model = model
        self.symmetry_fn_: Optional[Any] = None
        self.target_state_count_: Optional[int] = None
        self.target_max_depth_: Optional[int] = None
        self.thread_count_: int = 1
        self.visitor_: Optional[CheckerVisitor] = None
        self.finish_when_: HasDiscoveries = HasDiscoveries.ALL
        self.timeout_: Optional[float] = None
        self.trace_path_: Optional[str] = None
        self.trace_format_: str = "jsonl"
        self.profile_dir_: Optional[str] = None
        self.coverage_: bool = True
        self.strict_: bool = False
        self.strict_samples_: int = 128
        self.lint_report_: Optional[Any] = None
        self.multiplex_lane_: bool = False
        self.span_recorder_: Optional[Any] = None
        self.span_trace_id_: Optional[str] = None
        self.span_parent_id_: Optional[str] = None
        self.flight_: bool = True
        self.flight_capacity_: int = 4096
        self.flight_path_: Optional[str] = None
        self.flight_format_: str = "jsonl"
        self.memory_: bool = True
        self.pipeline_: bool = True
        self.pipeline_depth_: Optional[int] = None  # None = auto (2)
        self.fuse_eras_: Optional[int] = None  # None/1 = no multi-era fusion
        self.sample_: bool = True
        self.sample_k_: int = 64  # obs/sample.py DEFAULT_SAMPLE_K

    # -- options ------------------------------------------------------------

    def symmetry(self) -> "CheckerBuilder":
        """Enable symmetry reduction via the state's `representative()` method.

        Reference: checker.rs:219-227.
        """
        return self.symmetry_fn(lambda state: state.representative())

    def symmetry_fn(self, representative) -> "CheckerBuilder":
        self.symmetry_fn_ = representative
        return self

    def finish_when(self, has_discoveries: HasDiscoveries) -> "CheckerBuilder":
        self.finish_when_ = has_discoveries
        return self

    def target_state_count(self, count: int) -> "CheckerBuilder":
        self.target_state_count_ = count if count > 0 else None
        return self

    def target_max_depth(self, depth: int) -> "CheckerBuilder":
        self.target_max_depth_ = depth if depth > 0 else None
        return self

    def threads(self, thread_count: int) -> "CheckerBuilder":
        """Worker count for engines that support parallel checking.

        With thread_count > 1, `spawn_bfs()` on a tensor-backed model runs
        the vectorized threaded host engine (engines/vbfs.py: numpy lane
        batches + the native concurrent visited set, reference
        job_market.rs role); rich host models raise there. The other host
        Python engines stay single-threaded and raise NotImplementedError
        rather than silently ignoring the setting. The device engine
        accepts any value (its parallelism is the data-parallel chunk, not
        worker threads).
        """
        self.thread_count_ = thread_count
        return self

    def visitor(self, visitor) -> "CheckerBuilder":
        self.visitor_ = as_visitor(visitor)
        return self

    def timeout(self, seconds: float) -> "CheckerBuilder":
        self.timeout_ = seconds
        return self

    def trace(self, path: str, format: str = "jsonl") -> "CheckerBuilder":
        """Stream one event per era/wave/round to `path` (obs/trace.py
        documents the event schema). Works with every engine.
        `format="jsonl"` (default) writes standalone JSON lines;
        `format="chrome"` writes Chrome trace-event JSON loadable in
        Perfetto / `chrome://tracing` (phase timers as duration events,
        eras/waves as instant events)."""
        from .obs.trace import TRACE_FORMATS

        if format not in TRACE_FORMATS:
            raise ValueError(
                f"unknown trace format {format!r}; available: {TRACE_FORMATS}"
            )
        self.trace_path_ = path
        self.trace_format_ = format
        return self

    def coverage(self, enable: bool = True) -> "CheckerBuilder":
        """Toggle coverage accounting (obs/coverage.py): per-action fire
        counts, the per-depth unique-state histogram, per-property
        evaluation/hit counts, and dead-action detection, surfaced via
        `Checker.coverage()`. On by default; device engines fold the
        histograms into their era loops, so disabling buys back only a
        few percent of throughput (bench.py records both numbers)."""
        self.coverage_ = enable
        return self

    def flight(
        self,
        enable: bool = True,
        capacity: int = 4096,
        path: Optional[str] = None,
        format: str = "jsonl",
    ) -> "CheckerBuilder":
        """Configure the era-granularity flight recorder (obs/flight.py):
        a bounded ring of per-era records — wall time split into
        ``device_era`` vs ``host_gap`` (the dispatch gap), states/frontier/
        table counters — populated from the packed-params readback the
        device engines already do once per era (zero extra round-trips;
        <2% overhead, asserted by bench.py). On by default with a
        `capacity`-record ring; `Checker.flight()` returns the records
        and ``telemetry()["flight"]`` the summary. `path` additionally
        exports the recording at run end — JSONL (`format="jsonl"`) or a
        standalone Chrome counter-track trace (`format="chrome"`); a run
        traced with ``.trace(p, format="chrome")`` also gets the counter
        tracks embedded into that trace automatically. Host engines
        ignore the recorder (they have no era dispatch gap to measure)."""
        if format not in ("jsonl", "chrome"):
            raise ValueError(
                f"unknown flight format {format!r}; available: jsonl, chrome"
            )
        self.flight_ = enable
        self.flight_capacity_ = max(1, int(capacity))
        self.flight_path_ = path
        self.flight_format_ = format
        return self

    def memory(self, enable: bool = True) -> "CheckerBuilder":
        """Toggle the device-memory ledger (obs/memory.py): exact
        per-component accounting of every device allocation (visited
        table, frontier queue, packed params, coverage slab, spill
        staging) plus the per-era growth forecaster that projects
        eras-to-grow / eras-to-exhaustion and fires a one-shot pressure
        warning. On by default; the records ride the flight recorder's
        existing once-per-era readback (zero extra device round-trips,
        <1% overhead asserted by bench.py). Surfaced via
        ``telemetry()["memory"]``, ``memory_bytes{component=...}``
        Prometheus gauges, and the Explorer's ``GET /memory``."""
        self.memory_ = enable
        return self

    def sample(self, enable: bool = True, k: int = 64) -> "CheckerBuilder":
        """Configure the space profiler (obs/sample.py): deterministic
        bottom-k fingerprint sampling of the explored state space. A
        state is sampled iff its 64-bit fingerprint is among the `k`
        smallest seen, so the sample set is a pure function of the
        explored set — identical across engines (host bfs == tpu_bfs ==
        sharded mesh, locked by tests), visitation orders, shard
        layouts, and pipelining. On by default at small k (<2% overhead
        on the device engines, asserted by bench.py; candidates ride
        the existing once-per-era packed-params readback). Surfaced via
        `Checker.space_profile()` (field-distribution sketches, depth/
        action exemplars, packing-saturation warnings),
        ``telemetry()["space"]``, flat ``space_*`` gauges, and the
        Explorer's ``GET /space`` panel."""
        self.sample_ = bool(enable)
        self.sample_k_ = max(1, int(k))
        return self

    def multiplex_lane(self, enable: bool = True) -> "CheckerBuilder":
        """Mark this run as one lane of a multiplexed batch
        (engines/multiplex.py / the serve/ run service). Lanes share one
        compiled executable and one fused device era with their whole
        batch, so the device engines' small-workload hint — which warns
        about exactly the per-run overheads multiplexing amortizes away —
        is suppressed for them."""
        self.multiplex_lane_ = enable
        return self

    def profile(self, log_dir: str) -> "CheckerBuilder":
        """Bracket the run with `jax.profiler` start/stop_trace into
        `log_dir`. A no-op when the profiler is unavailable. The profile
        carries the run's phase timers as ``phase:<name>`` host spans and
        the device engines' stages as ``era/<stage>`` scopes of their
        device operations (obs/spans.py), on one clock."""
        self.profile_dir_ = log_dir
        return self

    def spans(
        self,
        recorder: Any,
        trace_id: Optional[str] = None,
        parent_id: Optional[str] = None,
    ) -> "CheckerBuilder":
        """Record this run into a `SpanRecorder` (obs/spans.py): one
        ``run`` span for the whole check plus one ``phase:<name>`` child
        per phase timer at run end — the run ledger's engine tier.
        `trace_id` / `parent_id` link the run into an enclosing trace
        (the serve layer passes the job's ids so engine time nests under
        the job's ``execute`` span); omitted, the run starts its own
        trace. With `trace(path, format="chrome")` also set, the
        recorder's spans are embedded into the Chrome trace at close, so
        one Perfetto file shows phases and request spans on aligned
        clocks."""
        self.span_recorder_ = recorder
        self.span_trace_id_ = trace_id
        self.span_parent_id_ = parent_id
        return self

    def pipeline(
        self,
        enable: bool = True,
        depth: Optional[int] = None,
        fuse: Optional[int] = None,
    ) -> "CheckerBuilder":
        """Speculative era pipelining on the device engines (default ON).

        While era N's packed-params readback is still in flight, the
        driver chains further eras directly off the still-on-device
        table/queue/params — the device loop's entry gate makes a
        chained dispatch an exact no-op whenever an earlier era actually
        needed host intervention (spill, grow, discovery finish, probe
        error), so results are bit-identical to the serial driver; only
        the dispatch gap between eras disappears. Disable to force the
        serial dispatch -> readback -> dispatch driver (useful when
        bisecting timing-sensitive telemetry).

        ``depth`` bounds the speculative in-flight chain: up to that many
        era dispatches are kept queued beyond the one being consumed,
        each with a non-blocking readback queued behind it (``None`` =
        auto, currently 2; ``1`` reproduces the original depth-1
        speculation). The host consumes readbacks strictly in order and
        peeks ``P_STEPS`` to tell consumed work from wasted speculation.

        ``fuse`` rolls that many eras into ONE compiled device program
        (an inner loop around the era body that continues only on pure
        budget exits), so one dispatch+readback can retire up to ``fuse``
        eras. ``None``/``1`` = no fusion. The packed params grow
        per-inner-era flight-record lanes, and the driver auto-degrades
        a dispatch to one era whenever per-era host work is pending
        (spill backlog, checkpoint cadence nearly due, state-count
        targets, timeouts)."""
        self.pipeline_ = bool(enable)
        if depth is not None:
            depth = int(depth)
            if depth < 1:
                raise ValueError(f"pipeline depth must be >= 1, got {depth}")
        self.pipeline_depth_ = depth
        if fuse is not None:
            fuse = int(fuse)
            if fuse < 1:
                raise ValueError(f"pipeline fuse must be >= 1, got {fuse}")
        self.fuse_eras_ = fuse
        return self

    # -- static analysis (speclint; stateright_tpu.analysis) -----------------

    def lint(self, samples: int = 256, program_cost: bool = False) -> Any:
        """Run the speclint pre-flight over this builder's model and
        symmetry options WITHOUT launching an engine.

        Tensor-backed models additionally get the STR6xx program family:
        the compiled era loop is lowered (never executed) and scanned
        for host transfers, dropped donation, dtype drift, and op-budget
        regressions; ``program_cost=True`` widens that to every device
        program plus the STR606 cost-model roofline (seconds — the CLI's
        ``--program``).

        Returns an `analysis.AnalysisReport`; its diagnostic counts are
        also exported through `Checker.telemetry()` (as ``lint_<code>``
        counters) by any engine subsequently spawned from this builder.
        """
        from . import tensor as _tensor
        from .analysis import analyze

        # Tensor-backed models canonicalize via representative_lanes (the
        # thing the device engines actually run); the host-level
        # symmetry lambda only applies to rich host states.
        tensorish = isinstance(
            self.model, (_tensor.TensorModel, _tensor.TensorModelAdapter)
        )
        self.lint_report_ = analyze(
            self.model,
            samples=samples,
            symmetry_fn=None if tensorish else self.symmetry_fn_,
            program_cost=program_cost,
        )
        return self.lint_report_

    def strict(self, enable: bool = True, samples: int = 128) -> "CheckerBuilder":
        """Refuse to launch ANY engine while speclint finds error-severity
        diagnostics: every spawn_* first runs `lint()` (reusing an
        explicit earlier `lint()` result) and raises `SpecLintError` when
        the model's determinism, device encoding, properties, or symmetry
        are broken — engines checking a broken spec are worse than
        useless. `samples` bounds the pre-flight state sample."""
        self.strict_ = enable
        self.strict_samples_ = samples
        return self

    # -- engines ------------------------------------------------------------

    def spawn_bfs(self) -> "Checker":
        # .threads(n > 1) routes tensor-backed models to the vectorized
        # threaded engine (reference parity: multithreaded spawn_bfs,
        # bfs.rs:90-164). Rich host models get the multiprocessing
        # ownership-sharded engine (engines/pbfs.py) — true parallelism
        # for ANY picklable model, the job market's role re-designed for
        # CPython (round 5; closes SURVEY component #7).
        if self.thread_count_ > 1:
            from .tensor import TensorModel, TensorModelAdapter

            if isinstance(self.model, (TensorModel, TensorModelAdapter)):
                from .engines.vbfs import VectorizedBfsChecker

                return VectorizedBfsChecker(self)
            from .engines.pbfs import ParallelBfsChecker

            return ParallelBfsChecker(self)
        from .engines.bfs import BfsChecker

        return BfsChecker(self)

    def spawn_parallel_bfs(self) -> "Checker":
        """The multiprocessing ownership-sharded BFS for rich models."""
        from .engines.pbfs import ParallelBfsChecker

        return ParallelBfsChecker(self)

    def spawn_vbfs(self, **kw) -> "Checker":
        """The vectorized threaded host engine over a TensorModel."""
        from .engines.vbfs import VectorizedBfsChecker

        return VectorizedBfsChecker(self, **kw)

    def spawn_dfs(self) -> "Checker":
        from .engines.dfs import DfsChecker

        return DfsChecker(self)

    def spawn_on_demand(self) -> "Checker":
        from .engines.on_demand import OnDemandChecker

        return OnDemandChecker(self)

    def spawn_simulation(self, seed: int, chooser=None) -> "Checker":
        from .engines.simulation import SimulationChecker, UniformChooser

        return SimulationChecker(self, seed, chooser or UniformChooser())

    def spawn_tpu_bfs(self, **kw) -> "Checker":
        """The TPU-native batched BFS engine over a TensorModel."""
        from .engines.tpu_bfs import TpuBfsChecker

        return TpuBfsChecker(self, **kw)

    def spawn_tpu_simulation(self, seed: int, **kw) -> "Checker":
        """Batched device simulation over a TensorModel: B independent
        seeded random walks advance one transition per device step
        (engines/tpu_simulation.py; the data-parallel twin of the
        reference's per-thread walks, simulation.rs:138-201)."""
        from .engines.tpu_simulation import TpuSimulationChecker

        return TpuSimulationChecker(self, seed, **kw)

    def spawn_sharded_bfs(self, **kw) -> "Checker":
        """The multi-device sharded BFS engine over a TensorModel.

        Tables and frontiers shard by fingerprint ownership across a
        `jax.sharding.Mesh`; candidates cross the ICI once, to their owner,
        via all_to_all (parallel/mesh.py).
        """
        from .parallel.mesh import ShardedBfsChecker

        return ShardedBfsChecker(self, **kw)

    def serve(self, address: str, trace=None, deployment=None):
        """Start the Explorer web service. Reference: checker.rs:144-151.

        `trace` attaches a recorded conformance trace (a JSONL path from
        `spawn(..., record=...)`), served at ``GET /trace``; `deployment`
        attaches a live spawn handle whose netobs telemetry feeds
        ``GET /deployment``."""
        from .explorer.server import serve

        return serve(self, address, trace=trace, deployment=deployment)


class Checker:
    """Query interface over a (possibly still-running) checking run.

    Reference: the `Checker` trait, checker.rs:294-578.
    """

    # Engines must set: _model, and implement the count/discovery accessors.

    def model(self) -> Model:
        return self._model  # type: ignore[attr-defined]

    # -- to be implemented by engines ---------------------------------------

    def state_count(self) -> int:
        raise NotImplementedError

    def unique_state_count(self) -> int:
        raise NotImplementedError

    def max_depth(self) -> int:
        raise NotImplementedError

    def discoveries(self) -> Dict[str, Path]:
        raise NotImplementedError

    def is_done(self) -> bool:
        raise NotImplementedError

    def join(self) -> "Checker":
        return self

    def telemetry(self) -> Dict[str, Any]:
        """The engine's metrics-registry snapshot: counters, gauges, and
        cumulative per-phase wall millis (obs/metrics.py documents the
        names). Every engine populates one registry through the common
        API, so an occupancy or throughput regression is visible here
        without STPU_DEBUG."""
        return {}

    def coverage(self) -> Dict[str, Any]:
        """The engine's coverage snapshot (obs/coverage.py): per-action
        fire counts (`actions`), registered-but-never-fired actions
        (`dead_actions`), the per-depth unique-state histogram
        (`depths`), and per-property evaluation/hit counts
        (`properties`). Engines without coverage support return {}."""
        return {}

    def flight(self) -> List[Dict[str, Any]]:
        """The engine's flight recording (obs/flight.py): the retained
        per-era records, oldest first — each splitting the era's wall
        time into ``device_era_secs`` + ``host_gap_secs`` beside the
        frontier/table/spill counters read from that era's packed-params
        readback. The run-level summary rides ``telemetry()["flight"]``.
        Engines without an era loop return []."""
        return []

    def space_profile(self) -> Dict[str, Any]:
        """The run's space profile (obs/sample.py): the deterministic
        bottom-k sample of the explored state space rendered into
        per-field distribution sketches, per-depth exemplar states,
        per-action exemplar transitions, and packing-saturation
        warnings. Engines without sampling support return {}."""
        return {}

    # -- on-demand engine hooks (no-ops elsewhere; checker.rs:298-306) ------

    def check_fingerprint(self, fingerprint: int) -> None:
        pass

    def run_to_completion(self) -> None:
        pass

    # -- derived helpers ----------------------------------------------------

    def discovery(self, name: str) -> Optional[Path]:
        return self.discoveries().get(name)

    def discovery_classification(self, name: str) -> str:
        """Reference: checker.rs:455-464."""
        prop = self.model().property(name)
        if prop.expectation in (Expectation.ALWAYS, Expectation.EVENTUALLY):
            return DiscoveryClassification.COUNTEREXAMPLE
        return DiscoveryClassification.EXAMPLE

    def report(self, reporter: Reporter) -> "Checker":
        """Poll progress until done, then emit a discovery summary.

        Reference: checker.rs:412-452.
        """
        start = time.monotonic()
        target = getattr(self, "_target_state_count", None)
        snap = getattr(self, "_initial_snapshot", None)
        if snap is not None:
            reporter.report_checking(
                ReportData(
                    total_states=snap[0],
                    unique_states=snap[1],
                    max_depth=snap[2],
                    duration_secs=0.0,
                    done=False,
                    target_states=target,
                )
            )
        while not self.is_done():
            reporter.report_checking(
                ReportData(
                    total_states=self.state_count(),
                    unique_states=self.unique_state_count(),
                    max_depth=self.max_depth(),
                    duration_secs=time.monotonic() - start,
                    done=False,
                    target_states=target,
                )
            )
            time.sleep(reporter.delay())
        self.join()
        reporter.report_checking(
            ReportData(
                total_states=self.state_count(),
                unique_states=self.unique_state_count(),
                max_depth=self.max_depth(),
                duration_secs=time.monotonic() - start,
                done=True,
                telemetry=self.telemetry(),
                coverage=self.coverage(),
                space=self.space_profile(),
            )
        )
        discoveries = {
            name: ReportDiscovery(path, self.discovery_classification(name))
            for name, path in self.discoveries().items()
        }
        reporter.report_discoveries(self.model(), discoveries)
        return self

    def join_and_report(self, reporter: Reporter) -> "Checker":
        return self.report(reporter)

    # -- assertion helpers (checker.rs:466-577) -----------------------------

    def assert_properties(self) -> None:
        for p in self.model().properties():
            if p.expectation in (Expectation.ALWAYS, Expectation.EVENTUALLY):
                self.assert_no_discovery(p.name)
            else:
                self.assert_any_discovery(p.name)

    def assert_any_discovery(self, name: str) -> Path:
        found = self.discovery(name)
        if found is not None:
            return found
        if not self.is_done():
            raise AssertionError(
                f'Discovery for "{name}" not found, but model checking is incomplete.'
            )
        raise AssertionError(f'Discovery for "{name}" not found.')

    def assert_no_discovery(self, name: str) -> None:
        found = self.discovery(name)
        if found is not None:
            raise AssertionError(
                f'Unexpected "{name}" {self.discovery_classification(name)} '
                f"{found}Last state: {found.last_state()!r}\n"
            )
        if not self.is_done():
            raise AssertionError(
                f'Discovery for "{name}" not found, but model checking is incomplete.'
            )

    def assert_discovery(self, name: str, actions: List[Any]) -> None:
        """Assert `actions` forms a valid discovery for property `name`.

        Reference: checker.rs:519-577.
        """
        additional_info: List[str] = []
        found = self.assert_any_discovery(name)
        model = self.model()
        for init_state in model.init_states():
            path = Path.from_actions(model, init_state, actions)
            if path is None:
                continue
            prop = model.property(name)
            if prop.expectation == Expectation.ALWAYS:
                if not prop.condition(model, path.last_state()):
                    return
            elif prop.expectation == Expectation.EVENTUALLY:
                states = path.into_states()
                is_liveness_satisfied = any(
                    prop.condition(model, s) for s in states
                )
                last_actions: List[Any] = []
                model.actions(states[-1], last_actions)
                is_path_terminal = not last_actions
                if not is_liveness_satisfied and is_path_terminal:
                    return
                if is_liveness_satisfied:
                    additional_info.append(
                        "incorrect counterexample satisfies eventually property"
                    )
                if not is_path_terminal:
                    additional_info.append("incorrect counterexample is nonterminal")
            else:  # SOMETIMES
                if prop.condition(model, path.last_state()):
                    return
        extra = f" ({'; '.join(additional_info)})" if additional_info else ""
        raise AssertionError(
            f'Invalid discovery for "{name}"{extra}, but a valid one was found. '
            f"found={found.into_actions()!r}"
        )
