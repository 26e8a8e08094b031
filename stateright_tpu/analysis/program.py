"""STR6xx proglint: static analysis of the COMPILED device programs.

The other speclint families look at the model; this one looks at what the
model compiles INTO. Each device engine's jitted programs (the era loop,
the fused seed+era loop, the visited-set insert/rehash kernels, the
multiplexed lane program, the sharded shard_map block) are traced and
lowered to jaxpr/StableHLO from `jax.ShapeDtypeStruct` abstract arguments
— no device buffer is allocated and nothing executes — then the lowered
artifacts are scanned for the regression classes that runtime profilers
(profiler traces, the flight recorder) can only report AFTER a run paid for
them:

  STR600  a program failed to trace/lower — the family's findings for it
          are incomplete (the device family usually has the root cause)
  STR601  host<->device transfer or callback primitives in a device hot
          loop (pure_callback / io_callback / device_put / infeed / ...)
          — each one stalls the era on a host round-trip
  STR602  broken/missed buffer donation: the program requests donation
          via `donate_argnums_safe` but the lowered StableHLO aliases
          fewer inputs to outputs than were donated (the regression class
          that forced donation off in PR 14)
  STR603  dtype drift: 64-bit or floating-point values inside the
          uint32/bool/int32 device programs, or `step_lanes` outputs that
          leave uint32 (the static twin of runtime STR207)
  STR604  per-era primitive op-count accounting against the committed
          `analysis/op_budgets.json`: growth over budget is an ERROR
          (the dispatch-gap push lives and dies on hot-loop op count,
          ROADMAP 1), shrink below budget is a WARNING to ratchet the
          budget down
  STR605  compile-signature instability: two fresh instances of the same
          model must produce equal `model_signature()` and intern to one
          canonical instance — otherwise every serve request retraces
          and the ExecutableCache never hits
  STR606  static cost model: XLA `cost_analysis()` flops + bytes-accessed
          per era step yield a memory-bound predicted roofline st/s,
          surfaced against the flight recorder's measured rate as an
          attribution ratio (`telemetry()["program"]`, the WriteReporter
          recap)

Tiers: the default lint pass (``Checker.lint()`` / ``strict()`` / serve
admission) traces the SOLO ERA LOOP only (~1s, cached per
`model_signature`). The deep pass (``--program`` on the CLI)
additionally lowers the seed loop, visited-set insert/rehash, the mux
lane program, and the sharded block, and compiles the era loop for the
STR606 cost model (seconds — kept off the admission path).

The code -> meaning -> fix catalog lives in `analysis/README.md`; budget
regeneration is documented there too (`--write-budgets`).
"""

from __future__ import annotations

import copy
import json
import os
import threading
from collections import Counter
from typing import Any, Dict, Optional, Tuple

from ..tensor import TensorModel
from .diagnostics import AnalysisReport, Severity

__all__ = [
    "BUDGETS_PATH",
    "HBM_GBPS_BY_KIND",
    "TRANSFER_PRIMITIVES",
    "cached_summary",
    "check_donation_text",
    "program_summary",
    "run",
    "write_budgets",
]

#: Committed op-count budgets (STR604). One JSON document, versioned,
#: keyed "engine|model_signature". Regenerate with
#: ``python -m stateright_tpu.analysis MODEL --program --write-budgets``
#: after an INTENTIONAL hot-loop change (see analysis/README.md).
BUDGETS_PATH = os.path.join(os.path.dirname(__file__), "op_budgets.json")

#: Primitives that move data across the host<->device boundary or call
#: back into Python from inside a compiled program. NONE of these belong
#: in a device hot loop: each stalls the era on a host round-trip, and
#: callbacks additionally serialize on the GIL. `convert_element_type` is
#: NOT here — u32<->i32/bool converts are free lane reinterpretations.
TRANSFER_PRIMITIVES = frozenset(
    {
        "pure_callback",
        "io_callback",
        "debug_callback",
        "callback",
        "device_put",
        "infeed",
        "outfeed",
        "copy_to_host",
        "transfer_to_host",
    }
)

#: 64-bit dtypes never belong in the uint32 lane programs (STR603):
#: TPU has no i64/f64 ALU — XLA widens to pairs (2x every op) or rejects.
WIDE_DTYPES = frozenset({"int64", "uint64", "float64"})

#: Peak HBM bandwidth (GB/s) by `jax.Device.device_kind`, for the STR606
#: predicted rate. A kind not listed gets no prediction, never a default.
HBM_GBPS_BY_KIND = {
    # Google Cloud documentation, "TPU v5e": 16 GB of HBM at 819 GB/s.
    "TPU v5 lite": 819.0,
}

# model-signature -> {"tier", "budgets_path", "diags", "summary"}.
# Replaying cached diagnostics keeps repeat lints (strict mode re-spawns,
# serve admission, the dogfood suite) at dict-lookup cost instead of a
# fresh ~1s trace per fresh model INSTANCE (the jit caches key by id()).
_SUMMARY_CACHE: Dict[Tuple[str, str], Dict[str, Any]] = {}
_CACHE_LOCK = threading.Lock()
_CACHE_CAP = 64


def _loc(tm: TensorModel, member: str) -> str:
    return f"{type(tm).__name__}.{member}"


def hbm_gbps(device_kind: str) -> Optional[float]:
    """Peak HBM GB/s of `device_kind`, or None when the table lacks it."""
    return HBM_GBPS_BY_KIND.get(device_kind)


def _default_device_kind() -> str:
    import jax

    return jax.devices()[0].device_kind


# -- jaxpr walking -----------------------------------------------------------


def _walk_jaxpr(jaxpr, prims: Counter, dtypes: set) -> None:
    """Count every primitive in `jaxpr` INCLUDING nested call/control-flow
    bodies (pjit, while, cond, scan carry their sub-jaxprs in eqn params),
    and collect every output aval dtype seen along the way. The outer
    pjit/while/cond eqns count too — each is a real dispatch boundary."""
    for eqn in jaxpr.eqns:
        prims[eqn.primitive.name] += 1
        for v in eqn.outvars:
            aval = getattr(v, "aval", None)
            if aval is not None and hasattr(aval, "dtype"):
                dtypes.add(str(aval.dtype))
        for p in eqn.params.values():
            _walk_param(p, prims, dtypes)


def _walk_param(p: Any, prims: Counter, dtypes: set) -> None:
    inner = getattr(p, "jaxpr", None)  # ClosedJaxpr
    if inner is not None and hasattr(inner, "eqns"):
        _walk_jaxpr(inner, prims, dtypes)
    elif hasattr(p, "eqns"):  # bare Jaxpr
        _walk_jaxpr(p, prims, dtypes)
    elif isinstance(p, (list, tuple)):
        for x in p:
            _walk_param(x, prims, dtypes)


def count_ops(closed_jaxpr) -> Tuple[Counter, set]:
    """(primitive -> count, dtype-name set) over the whole nested jaxpr."""
    prims: Counter = Counter()
    dtypes: set = set()
    _walk_jaxpr(closed_jaxpr.jaxpr, prims, dtypes)
    return prims, dtypes


def _trace(fn, args):
    """(closed_jaxpr, traced) for a jitted `fn` over abstract args; the
    traced handle lowers without tracing again."""
    traced = fn.trace(*args)
    return traced.jaxpr, traced


# -- program lowering --------------------------------------------------------


GEOMETRY_KEYS = ("chunk", "qcap", "tcap", "cov", "sample_k")


def _era_geometry(tm: TensorModel) -> Dict[str, Any]:
    from ..engines.compiled import era_geometry

    return era_geometry(tm)


def _sharded_geometry(
    tm: TensorModel, n_shards: Optional[int] = None
) -> Dict[str, Any]:
    """Mirror `ShardedBfsChecker.__init__`'s default shape resolution
    (over every device unless `n_shards` says otherwise)."""
    import jax

    from ..obs.sample import DEFAULT_SAMPLE_K

    n_shards = n_shards or len(jax.devices())
    qcap = 1 << 16
    tcap = 1 << 18
    A = max(1, tm.max_actions)
    chunk = min(1024, qcap // (2 * A))
    quota = max(64, (chunk * A) // (4 * n_shards))
    return {
        "chunk": chunk,
        "qcap": qcap,
        "tcap": tcap,
        "n_shards": n_shards,
        "quota": quota,
        "cov": True,
        "sample_k": DEFAULT_SAMPLE_K,
    }


def _lower_era(tm: TensorModel, g: Dict[str, Any]):
    from ..engines.tpu_bfs import _build_loop, loop_abstract_args

    props = tm.tensor_properties()
    loop = _build_loop(
        tm, props, g["chunk"], g["qcap"], False, g["cov"],
        sample_k=g["sample_k"],
    ).serial
    args = loop_abstract_args(
        tm, props, g["chunk"], g["qcap"], g["tcap"], g["cov"], g["sample_k"]
    )
    return loop, args


def _lower_seed_loop(tm: TensorModel, g: Dict[str, Any]):
    from ..engines.tpu_bfs import _build_seed_loop, seed_loop_abstract_args

    props = tm.tensor_properties()
    fn = _build_seed_loop(
        tm, props, g["chunk"], g["qcap"], g["tcap"], False, g["cov"],
        sample_k=g["sample_k"],
    )
    args = seed_loop_abstract_args(
        tm, props, g["chunk"], g["qcap"], g["tcap"], g["cov"],
        g["sample_k"], g["n_init"],
    )
    return fn, args


def _lower_visited(tm: TensorModel, g: Dict[str, Any], which: str):
    import jax
    import jax.numpy as jnp

    from ..engines.tpu_bfs import _vcap
    from ..ops import visited_set as vs

    sds = jax.ShapeDtypeStruct
    u32 = jnp.uint32
    tcap = g["tcap"]
    if which == "insert":
        vcap = _vcap(max(1, tm.max_actions), g["chunk"])
        fn = jax.jit(
            lambda table, h1, h2, p1, p2, act: vs.insert(
                table, h1, h2, p1, p2, act
            )
        )
        lane = sds((vcap,), u32)
        args = (
            vs.abstract_table(tcap),
            lane, lane, lane, lane,
            sds((vcap,), jnp.bool_),
        )
        return fn, args
    fn = jax.jit(lambda old, new: vs.rehash(old, new))
    return fn, (vs.abstract_table(tcap), vs.abstract_table(2 * tcap))


def _lower_mux(tm: TensorModel):
    import jax
    import jax.numpy as jnp

    from ..engines.multiplex import _build_lane_program, _shape_options
    from ..engines.tpu_bfs import params_len

    props = tm.tensor_properties()
    lanes, icap = 32, 64
    chunk, qcap, tcap, icap = _shape_options(tm, 256, 1 << 13, 1 << 16, icap)
    fn = _build_lane_program(tm, props, lanes, chunk, qcap, tcap, icap, True)
    S, A, P = tm.state_width, tm.max_actions, len(props)
    plen = params_len(A, P, True, 0)  # raw loop: no sampling tail
    sds = jax.ShapeDtypeStruct
    u32 = jnp.uint32
    N, W = lanes, S + 2
    args = (
        sds((N, W, icap), u32),
        sds((N,), u32),
        sds((N, icap), u32),
        sds((N, icap), u32),
        sds((N, plen), u32),
        sds((N, P), u32),
        sds((N, P), u32),
    )
    return fn, args


def _lower_sharded(tm: TensorModel, g: Dict[str, Any]):
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from ..parallel.mesh import _build_block, block_abstract_args

    props = tm.tensor_properties()
    mesh = Mesh(np.array(jax.devices()), ("shards",))
    fn = _build_block(
        tm, props, g["chunk"], g["qcap"], g["n_shards"], g["quota"], mesh,
        "shards", cov=g["cov"], sample_k=g["sample_k"],
    ).serial
    args = block_abstract_args(
        tm, props, g["qcap"], g["tcap"], g["n_shards"], g["cov"],
        g["sample_k"],
    )
    return fn, args


# -- detectors ---------------------------------------------------------------


def _check_transfers(
    tm: TensorModel, program: str, prims: Counter, report: AnalysisReport
) -> None:
    found = {p: n for p, n in prims.items() if p in TRANSFER_PRIMITIVES}
    if not found:
        return
    listing = ", ".join(f"{p} x{n}" for p, n in sorted(found.items()))
    report.add(
        "STR601",
        Severity.ERROR,
        f"host<->device transfer/callback primitives inside the {program} "
        f"program: {listing} — each stalls every era on a host "
        "round-trip",
        _loc(tm, "step_lanes"),
        "compute device-side; move host logic outside the jitted loop "
        "(or into the era epilogue's packed params tail)",
        program=program,
        primitives=found,
    )


def check_donation_text(
    tm: TensorModel,
    program: str,
    lowered_text: str,
    expected_donated: int,
    report: AnalysisReport,
) -> None:
    """STR602 over a lowered StableHLO module: when `expected_donated`
    input buffers were requested for donation (`donate_argnums_safe`
    resolved non-empty), the lowering must carry at least that many
    input->output aliasing attributes; fewer means XLA dropped donations
    (shape/layout mismatch after a refactor) and the run silently doubles
    its working set. Factored over the raw text so tests can drive it
    against hand-built programs."""
    if expected_donated <= 0:
        report.add(
            "STR602",
            Severity.INFO,
            f"donation disabled for the {program} program on this backend "
            "(donate_argnums_safe resolved empty — expected on CPU, where "
            "persistent-cache executables corrupt donated buffers)",
            _loc(tm, "step_lanes"),
            program=program,
        )
        return
    aliased = lowered_text.count("tf.aliasing_output") + lowered_text.count(
        "jax.buffer_donor"
    )
    if aliased < expected_donated:
        report.add(
            "STR602",
            Severity.ERROR,
            f"{program} requests donation of {expected_donated} input "
            f"buffer(s) but the lowered program aliases only {aliased} to "
            "outputs — XLA dropped the rest (shape/layout drift between a "
            "donated input and every output), doubling device residency",
            _loc(tm, "step_lanes"),
            "keep donated operands shape- and dtype-identical to the "
            "outputs they hand their buffers to (PR 14's regression class)",
            program=program,
            expected=expected_donated,
            aliased=aliased,
        )


def _check_dtypes(
    tm: TensorModel, program: str, dtypes: set, report: AnalysisReport
) -> None:
    wide = sorted(d for d in dtypes if d in WIDE_DTYPES)
    if wide:
        report.add(
            "STR603",
            Severity.ERROR,
            f"64-bit values ({', '.join(wide)}) inside the {program} "
            "program; TPUs have no 64-bit ALU — XLA widens every op to "
            "pairs or rejects the program outright",
            _loc(tm, "step_lanes"),
            "keep lane math in uint32 (split wide fields across lanes)",
            program=program,
            dtypes=wide,
        )
    floats = sorted(
        d for d in dtypes if d.startswith(("float", "bfloat")) and d not in WIDE_DTYPES
    )
    if floats:
        report.add(
            "STR603",
            Severity.WARNING,
            f"floating-point values ({', '.join(floats)}) inside the "
            f"{program} program; the lane programs are integer-only — a "
            "float usually means an accidental true-division or mean()",
            _loc(tm, "step_lanes"),
            "use // and integer reductions in step_lanes",
            program=program,
            dtypes=floats,
        )


def _check_lane_dtypes(tm: TensorModel, report: AnalysisReport) -> None:
    """STR603 on `step_lanes` itself via `jax.eval_shape` — catches a
    non-uint32 lane (e.g. an int64 constant silently demoted to int32)
    from shapes alone, without executing the model."""
    import jax
    import jax.numpy as jnp
    import jax.tree_util as jtu

    lanes = tuple(
        jax.ShapeDtypeStruct((8,), jnp.uint32) for _ in range(tm.state_width)
    )
    try:
        out = jax.eval_shape(lambda ls: tm.step_lanes(jnp, ls), lanes)
    except Exception:
        return  # not traceable at all: STR201's finding, not ours
    bad = sorted(
        {
            str(leaf.dtype)
            for leaf in jtu.tree_leaves(out)
            if hasattr(leaf, "dtype")
            and str(leaf.dtype) not in ("uint32", "bool")
        }
    )
    if bad:
        report.add(
            "STR603",
            Severity.ERROR,
            f"step_lanes outputs leave uint32 under abstract evaluation "
            f"({', '.join(bad)}); the queue/table lanes are uint32 — the "
            "store truncates or the trace widens every downstream op",
            _loc(tm, "step_lanes"),
            "cast successor lanes back with .astype(xp.uint32) after "
            "arithmetic that promotes",
            dtypes=bad,
        )


def _check_signature_stability(tm: TensorModel, report: AnalysisReport) -> str:
    """STR605: the model's compile signature must be a pure function of
    its configuration. Three probes: repeated calls on one instance
    (catches RNG/time in `config_digest`), a deepcopied twin (catches
    `id()`-based digests — the classic), and the intern pool returning
    one canonical instance for both."""
    from ..engines.compiled import intern_model, model_signature

    sig1 = model_signature(tm)
    sig2 = model_signature(tm)
    if sig1 != sig2:
        report.add(
            "STR605",
            Severity.ERROR,
            "model_signature() differs across two calls on the SAME "
            "instance — config_digest() is reading a clock or RNG; every "
            "serve request will retrace and the ExecutableCache never hits",
            _loc(tm, "config_digest"),
            "derive config_digest purely from constructor parameters",
        )
        return sig1
    try:
        twin = copy.deepcopy(tm)
    except Exception:
        report.add(
            "STR605",
            Severity.INFO,
            "model is not deepcopy-able; cross-instance signature "
            "stability could not be probed",
            _loc(tm, "config_digest"),
        )
        return sig1
    sig_twin = model_signature(twin)
    if sig_twin != sig1:
        report.add(
            "STR605",
            Severity.ERROR,
            "two instances with identical configuration produce different "
            "model_signature() values — config_digest() depends on id() "
            "or other instance identity; every fresh instance recompiles "
            f"({sig1!r} vs {sig_twin!r})",
            _loc(tm, "config_digest"),
            "hash constructor parameters, never object identity",
        )
        return sig1
    canon, _ = intern_model(tm)
    canon_twin, _ = intern_model(twin)
    if canon_twin is not canon:
        report.add(
            "STR605",
            Severity.ERROR,
            "equal-signature instances intern to DIFFERENT canonical "
            "instances — the intern pool is broken for this model and "
            "the id()-keyed jit caches will never hit across requests",
            _loc(tm, "config_digest"),
            "report this as an intern_model bug with the model attached",
        )
    return sig1


# -- op budgets (STR604) -----------------------------------------------------


def _load_budgets(path: str) -> Dict[str, Any]:
    try:
        with open(path, "r", encoding="utf-8") as fh:
            doc = json.load(fh)
    except (OSError, ValueError):
        return {}
    return doc if isinstance(doc, dict) else {}


def _check_budget(
    tm: TensorModel,
    engine: str,
    signature: str,
    ops: int,
    geometry: Dict[str, Any],
    budgets: Dict[str, Any],
    report: AnalysisReport,
) -> None:
    import jax

    entries = budgets.get("entries", {})
    entry = entries.get(f"{engine}|{signature}")
    loc = _loc(tm, "step_lanes")
    if entry is None:
        report.add(
            "STR604",
            Severity.INFO,
            f"no committed op budget for the {engine} era program of this "
            f"model ({ops} ops measured); the hot-loop gate is not armed",
            loc,
            "commit one with `python -m stateright_tpu.analysis MODEL "
            "--program --write-budgets`",
            engine=engine,
            ops=ops,
        )
        return
    if entry.get("geometry") != geometry:
        report.add(
            "STR604",
            Severity.INFO,
            f"op budget for {engine} was committed at a different engine "
            "geometry; gate skipped (op counts are only comparable at "
            "equal shapes)",
            loc,
            "regenerate with --write-budgets on this host",
            engine=engine,
            committed=entry.get("geometry"),
            current=geometry,
        )
        return
    if entry.get("jax") != jax.__version__:
        report.add(
            "STR604",
            Severity.INFO,
            f"op budget for {engine} was committed under jax "
            f"{entry.get('jax')}; running {jax.__version__} — gate "
            "skipped (lowering differs across versions)",
            loc,
            "regenerate with --write-budgets under the CI jax version",
            engine=engine,
        )
        return
    budget = int(entry.get("ops", 0))
    if ops > budget:
        report.add(
            "STR604",
            Severity.ERROR,
            f"{engine} era program grew to {ops} primitives, over the "
            f"committed budget of {budget} (+{ops - budget}) — the "
            "dispatch-gap push (ROADMAP 1) forbids silent hot-loop growth",
            loc,
            "shrink the loop back, or (for an intentional change) "
            "regenerate analysis/op_budgets.json with --write-budgets and "
            "justify the growth in the PR",
            engine=engine,
            ops=ops,
            budget=budget,
        )
    elif ops < budget:
        report.add(
            "STR604",
            Severity.WARNING,
            f"{engine} era program shrank to {ops} primitives, under the "
            f"committed budget of {budget} (-{budget - ops}); ratchet the "
            "budget down so the win cannot silently regress",
            loc,
            "run --write-budgets and commit the smaller budget",
            engine=engine,
            ops=ops,
            budget=budget,
        )


# -- the family entry --------------------------------------------------------


def _trace_failed(
    tm: TensorModel, program: str, exc: BaseException, report: AnalysisReport
) -> None:
    report.add(
        "STR600",
        Severity.WARNING,
        f"the {program} program failed to trace/lower "
        f"({type(exc).__name__}: {exc}); STR6xx findings for it are "
        "incomplete",
        _loc(tm, "step_lanes"),
        "the device family (STR2xx) usually has the root cause",
        program=program,
    )


def _prog_summary(prims: Counter, dtypes: set) -> Dict[str, Any]:
    return {
        "ops": int(sum(prims.values())),
        "distinct": len(prims),
        "top": [
            {"primitive": p, "count": n} for p, n in prims.most_common(5)
        ],
        "dtypes": sorted(dtypes),
    }


def _analyze_programs(
    tm: TensorModel,
    report: AnalysisReport,
    *,
    cost: bool,
    budgets_path: str,
    device_kind: str,
) -> Dict[str, Any]:
    """Trace, scan, and budget-gate the device programs; returns the
    summary dict that `cached_summary` later serves to telemetry."""
    import jax

    from ..compat import donate_argnums_safe
    from ..engines.compiled import model_signature

    sig = model_signature(tm)
    g = _era_geometry(tm)
    budgets = _load_budgets(budgets_path)
    summary: Dict[str, Any] = {
        "signature": sig,
        "backend": jax.default_backend(),
        "geometry": {k: g[k] for k in GEOMETRY_KEYS},
        "programs": {},
    }

    # The era loop: the one program every run's wall clock is made of.
    # The SERIAL program variant fully donates its operands — table (3
    # lanes) + queue (S+2 lanes) + rec_fp1/rec_fp2 + the params vector
    # (the readback-tail donation: serial dispatches always feed a fresh
    # upload or a consumed buffer back in).
    donated_leaves = 0
    if donate_argnums_safe(0, 1):
        donated_leaves = 3 + (tm.state_width + 2) + 2 + 1
    era_traced = None
    try:
        loop, args = _lower_era(tm, g)
        closed, era_traced = _trace(loop, args)
        prims, dtypes = count_ops(closed)
        summary["programs"]["era_loop"] = _prog_summary(prims, dtypes)
        _check_transfers(tm, "era_loop", prims, report)
        _check_dtypes(tm, "era_loop", dtypes, report)
        _check_budget(
            tm, "tpu_bfs", sig,
            int(sum(prims.values())), summary["geometry"], budgets, report,
        )
        # Lowering to StableHLO text is the expensive half of this pass;
        # pay it only when donation is actually expected (the detector
        # has attrs to count) or the deep tier needs the compile anyway.
        lowered = None
        if donated_leaves > 0 or cost:
            lowered = era_traced.lower()
        if donated_leaves > 0:
            check_donation_text(
                tm, "era_loop", lowered.as_text(), donated_leaves, report
            )
        else:
            # expected <= 0 short-circuits to the backend-disabled info
            # without scanning any text.
            check_donation_text(tm, "era_loop", "", donated_leaves, report)
    except Exception as exc:  # noqa: BLE001 — lint must not crash the lint
        _trace_failed(tm, "era_loop", exc, report)
        lowered = None

    if cost:
        deep = {
            "seed_loop": lambda: _lower_seed_loop(tm, g),
            "visited_insert": lambda: _lower_visited(tm, g, "insert"),
            "visited_rehash": lambda: _lower_visited(tm, g, "rehash"),
            "mux_expand": lambda: _lower_mux(tm),
        }
        for name, build in deep.items():
            try:
                fn, fargs = build()
                closed, _ = _trace(fn, fargs)
                prims, dtypes = count_ops(closed)
                summary["programs"][name] = _prog_summary(prims, dtypes)
                _check_transfers(tm, name, prims, report)
                _check_dtypes(tm, name, dtypes, report)
            except Exception as exc:  # noqa: BLE001
                _trace_failed(tm, name, exc, report)
        # The sharded block, with its own geometry and budget line. Its
        # serial variant donates table + queue + params (rec_fps stay
        # live for the host discovery reads).
        sharded_donated = (
            3 + (tm.state_width + 2) + 1 if donated_leaves > 0 else 0
        )
        sg = _sharded_geometry(tm)
        prog_name = "sharded_era"
        try:
            fn, fargs = _lower_sharded(tm, sg)
            closed, straced = _trace(fn, fargs)
            prims, dtypes = count_ops(closed)
            summary["programs"][prog_name] = _prog_summary(prims, dtypes)
            summary["sharded_geometry"] = dict(sg)
            _check_transfers(tm, prog_name, prims, report)
            _check_dtypes(tm, prog_name, dtypes, report)
            _check_budget(
                tm, "sharded", sig, int(sum(prims.values())), dict(sg),
                budgets, report,
            )
            if sharded_donated > 0:
                slow = straced.lower()
                check_donation_text(
                    tm, prog_name, slow.as_text(), sharded_donated, report
                )
            else:
                check_donation_text(tm, prog_name, "", sharded_donated, report)
        except Exception as exc:  # noqa: BLE001
            _trace_failed(tm, prog_name, exc, report)

        if lowered is not None:
            _cost_model(tm, g, lowered, summary, report, device_kind)
    return summary


def _cost_model(
    tm: TensorModel,
    g: Dict[str, Any],
    lowered,
    summary: Dict[str, Any],
    report: AnalysisReport,
    device_kind: str,
) -> None:
    """STR606: compile the era loop and turn XLA's static cost analysis
    into a memory-bound roofline prediction for `device_kind`. `cost_analysis` charges the
    while-loop body ONCE, so flops/bytes are per era STEP; one step pops
    `chunk` frontier rows, giving predicted st/s = chunk / step_secs with
    step_secs = bytes_accessed / HBM bandwidth (the survey's roofline —
    these programs are memory-bound, gather/scatter over HBM tables)."""
    try:
        compiled = lowered.compile()
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
    except Exception as exc:  # noqa: BLE001
        report.add(
            "STR606",
            Severity.INFO,
            f"XLA cost analysis unavailable ({type(exc).__name__}: {exc}); "
            "no predicted roofline for this run",
            _loc(tm, "step_lanes"),
        )
        return
    flops = float(ca.get("flops", 0.0) or 0.0)
    bytes_accessed = float(ca.get("bytes accessed", 0.0) or 0.0)
    gbps = hbm_gbps(device_kind)
    cost: Dict[str, Any] = {
        "flops_per_step": flops,
        "bytes_per_step": bytes_accessed,
        "device_kind": device_kind,
        "hbm_gbps": gbps,
    }
    if gbps is None:
        report.add(
            "STR606",
            Severity.INFO,
            f"no peak HBM bandwidth is known for device kind "
            f"{device_kind!r} (analysis/program.py HBM_GBPS_BY_KIND); "
            "predicted roofline omitted",
            _loc(tm, "step_lanes"),
        )
    elif bytes_accessed > 0:
        step_secs = bytes_accessed / (gbps * 1e9)
        cost["predicted_step_secs"] = step_secs
        cost["predicted_states_per_sec"] = g["chunk"] / step_secs
    else:
        report.add(
            "STR606",
            Severity.INFO,
            "cost analysis reports zero bytes accessed; predicted "
            "roofline omitted",
            _loc(tm, "step_lanes"),
        )
    summary["cost"] = cost


def run(
    tm: TensorModel,
    report: AnalysisReport,
    *,
    cost: bool = False,
    budgets_path: Optional[str] = None,
    device_kind: Optional[str] = None,
) -> None:
    """Run the STR6xx program family over `tm` into `report`.

    ``cost=False`` (the default lint/strict/serve tier) probes signature
    stability, step_lanes dtypes, and the solo era loop. ``cost=True``
    (CLI ``--program``) adds the remaining device programs, the
    sharded budget gate, and the STR606 compile + cost model, whose
    predicted rate is for `device_kind` (default: the first device's)."""
    report.families_run.append("program")
    budgets_path = budgets_path or BUDGETS_PATH
    device_kind = device_kind or _default_device_kind()

    sig = _check_signature_stability(tm, report)
    _check_lane_dtypes(tm, report)

    key = (sig, budgets_path, device_kind)
    with _CACHE_LOCK:
        cached = _SUMMARY_CACHE.get(key)
    if cached is not None and (cached["tier"] >= (2 if cost else 1)):
        for code, sev, msg, loc, sugg, details in cached["diags"]:
            report.add(code, sev, msg, loc, sugg, **details)
        return

    before = len(report.diagnostics)
    summary = _analyze_programs(
        tm, report, cost=cost, budgets_path=budgets_path,
        device_kind=device_kind,
    )
    diags = [
        (d.code, d.severity, d.message, d.location, d.suggestion, d.details)
        for d in report.diagnostics[before:]
    ]
    with _CACHE_LOCK:
        while len(_SUMMARY_CACHE) >= _CACHE_CAP:
            _SUMMARY_CACHE.pop(next(iter(_SUMMARY_CACHE)))
        _SUMMARY_CACHE[key] = {
            "tier": 2 if cost else 1,
            "diags": diags,
            "summary": summary,
        }


def program_summary(
    tm: TensorModel, *, cost: bool = True,
    budgets_path: Optional[str] = None,
    device_kind: Optional[str] = None,
) -> Dict[str, Any]:
    """The program-lint summary for `tm` (ops per program, geometry, and
    — with ``cost=True`` — the STR606 flops/bytes/predicted roofline for
    `device_kind`, default the first device's), computing and caching it
    if absent."""
    report = AnalysisReport(type(tm).__name__)
    device_kind = device_kind or _default_device_kind()
    run(
        tm, report, cost=cost, budgets_path=budgets_path,
        device_kind=device_kind,
    )
    from ..engines.compiled import model_signature

    key = (model_signature(tm), budgets_path or BUDGETS_PATH, device_kind)
    with _CACHE_LOCK:
        # Re-insert: `cached_summary` serves the summary asked for last.
        cached = _SUMMARY_CACHE.pop(key, None)
        if cached is not None:
            _SUMMARY_CACHE[key] = cached
    return dict(cached["summary"]) if cached else {}


def cached_summary(signature: str) -> Optional[Dict[str, Any]]:
    """The cached program summary for a model signature, if any pass of
    the family has produced one this process — telemetry()'s cheap hook
    (a dict lookup; NEVER traces or compiles)."""
    best = None
    with _CACHE_LOCK:
        for (sig, _path, _kind), ent in _SUMMARY_CACHE.items():
            # Several entries can share a signature (one per budgets
            # path and device kind); prefer the deepest tier — only it
            # carries the STR606 cost fields — and among those the one
            # asked for last.
            if sig == signature and (best is None or ent["tier"] >= best["tier"]):
                best = ent
    return dict(best["summary"]) if best else None


def write_budgets(
    tm: TensorModel, label: str = "", path: Optional[str] = None
) -> Dict[str, Any]:
    """Measure the era programs and commit their op counts as the new
    budgets (STR604's ratchet). Returns the entries written."""
    import jax

    from ..engines.compiled import model_signature

    path = path or BUDGETS_PATH
    sig = model_signature(tm)
    doc = _load_budgets(path)
    doc.setdefault("version", 1)
    entries = doc.setdefault("entries", {})

    g = _era_geometry(tm)
    sg = _sharded_geometry(tm)
    written = {}
    loop, args = _lower_era(tm, g)
    closed, _ = _trace(loop, args)
    prims, _dt = count_ops(closed)
    written[f"tpu_bfs|{sig}"] = {
        "model": label,
        "ops": int(sum(prims.values())),
        "geometry": {k: g[k] for k in GEOMETRY_KEYS},
        "jax": jax.__version__,
    }

    fn, fargs = _lower_sharded(tm, sg)
    closed, _ = _trace(fn, fargs)
    prims, _dt = count_ops(closed)
    written[f"sharded|{sig}"] = {
        "model": label,
        "ops": int(sum(prims.values())),
        "geometry": dict(sg),
        "jax": jax.__version__,
    }

    entries.update(written)
    doc["entries"] = dict(sorted(entries.items()))
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(doc, fh, indent=2, sort_keys=True)
        fh.write("\n")
    return written
