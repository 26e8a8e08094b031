"""proglint (analysis/program.py): each STR6xx detector must flag its
deliberately broken device program, the bundled models must pass the
light tier clean, and the CLI exit-status contract (0/1/2) plus the
bundled-model registry stay honest."""

from __future__ import annotations

import copy
import io
import json
import os
import warnings

import numpy as np
import pytest

from stateright_tpu.analysis import AnalysisReport, analyze
from stateright_tpu.analysis import program as proglint
from stateright_tpu.models import IncrementTensor
from stateright_tpu.tensor import TensorModel, TensorProperty


def codes(report: AnalysisReport) -> set:
    return {d.code for d in report.diagnostics}


def error_codes(report: AnalysisReport) -> set:
    return {d.code for d in report.errors}


def run_program_family(tm, **kw) -> AnalysisReport:
    report = AnalysisReport(type(tm).__name__)
    proglint.run(tm, report, **kw)
    return report


# ---------------------------------------------------------------------------
# Broken-model fixtures, one per detector.
# ---------------------------------------------------------------------------


class CallbackTensor(TensorModel):
    """STR601: a host callback inside `step_lanes` — every era would pay
    a device->host round-trip."""

    state_width = 1
    max_actions = 1

    def init_states_array(self) -> np.ndarray:
        return np.zeros((1, 1), dtype=np.uint32)

    def step_lanes(self, xp, lanes):
        u = xp.uint32
        nxt = (lanes[0] + u(1)) & u(7)
        if xp is not np:  # keep the host-oracle replay pure
            import jax

            nxt = jax.pure_callback(
                lambda a: a, jax.ShapeDtypeStruct(nxt.shape, nxt.dtype), nxt
            )
        return [(nxt,)], [lanes[0] < u(8)]

    def tensor_properties(self):
        return [TensorProperty.always("true", lambda xp, l: l[0] == l[0])]


class WideLaneTensor(TensorModel):
    """STR603: `step_lanes` emits an off-contract lane dtype (the int64
    cast lands as int32 under disabled x64 — still not uint32)."""

    state_width = 1
    max_actions = 1

    def init_states_array(self) -> np.ndarray:
        return np.zeros((1, 1), dtype=np.uint32)

    def step_lanes(self, xp, lanes):
        u = xp.uint32
        nxt = ((lanes[0] + u(1)) & u(7)).astype(xp.int64)  # the bug
        return [(nxt,)], [lanes[0] < u(8)]

    def tensor_properties(self):
        return [TensorProperty.always("true", lambda xp, l: l[0] == l[0])]


class UnstableSignatureTensor(TensorModel):
    """STR605: `config_digest` leaks the instance address, so an
    equal-config twin gets a different compile signature and every
    signature-keyed cache (intern pool, executables, lint) misses."""

    state_width = 1
    max_actions = 1

    def init_states_array(self) -> np.ndarray:
        return np.zeros((1, 1), dtype=np.uint32)

    def config_digest(self) -> str:
        return hex(id(self))  # the bug

    def step_lanes(self, xp, lanes):
        u = xp.uint32
        return [(((lanes[0] + u(1)) & u(7)),)], [lanes[0] < u(8)]

    def tensor_properties(self):
        return [TensorProperty.always("true", lambda xp, l: l[0] == l[0])]


# ---------------------------------------------------------------------------
# STR601 — transfers/callbacks in hot-loop programs
# ---------------------------------------------------------------------------


def test_callback_in_step_lanes_flagged():
    report = run_program_family(CallbackTensor())
    assert "STR601" in error_codes(report)


# ---------------------------------------------------------------------------
# STR602 — broken/missed buffer donation
# ---------------------------------------------------------------------------


def test_missing_donation_attrs_flagged():
    report = AnalysisReport("x")
    proglint.check_donation_text(
        IncrementTensor(2), "era_loop", "module @jit_loop { }", 2, report
    )
    assert "STR602" in error_codes(report)


def test_satisfied_donation_is_clean():
    report = AnalysisReport("x")
    text = (
        "%arg0 {tf.aliasing_output = 0 : i32}, "
        "%arg1 {tf.aliasing_output = 1 : i32}"
    )
    proglint.check_donation_text(
        IncrementTensor(2), "era_loop", text, 2, report
    )
    assert "STR602" not in codes(report)


def test_disabled_donation_degrades_to_info():
    report = AnalysisReport("x")
    proglint.check_donation_text(
        IncrementTensor(2), "era_loop", "module @jit_loop { }", 0, report
    )
    assert report.ok  # info only — the backend opted out, not the model
    assert "STR602" in codes(report)


def test_real_lowering_with_broken_donation_flagged():
    # A donated buffer whose output shape differs cannot alias: XLA drops
    # the donation (UserWarning) and the StableHLO carries no aliasing
    # attr — exactly what the detector keys on.
    import jax
    import jax.numpy as jnp

    def bad(buf):
        return jnp.zeros((buf.shape[0] + 1,), buf.dtype)

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        text = (
            jax.jit(bad, donate_argnums=(0,))
            .lower(jax.ShapeDtypeStruct((8,), jnp.uint32))
            .as_text()
        )
    report = AnalysisReport("x")
    proglint.check_donation_text(
        IncrementTensor(2), "era_loop", text, 1, report
    )
    assert "STR602" in error_codes(report)


# ---------------------------------------------------------------------------
# STR603 — dtype drift
# ---------------------------------------------------------------------------


def test_off_contract_lane_dtype_flagged():
    report = run_program_family(WideLaneTensor())
    assert "STR603" in error_codes(report)


# ---------------------------------------------------------------------------
# STR604 — the op-count budget gate
# ---------------------------------------------------------------------------


def _perturbed_budgets(tmp_path, delta: int) -> str:
    """The committed budget file with IncrementTensor(2)'s tpu_bfs entry
    shifted by `delta` ops."""
    from stateright_tpu.engines.compiled import model_signature

    with open(proglint.BUDGETS_PATH) as fh:
        doc = json.load(fh)
    key = f"tpu_bfs|{model_signature(IncrementTensor(2))}"
    assert key in doc["entries"], sorted(doc["entries"])
    doc["entries"][key]["ops"] += delta
    path = os.path.join(str(tmp_path), f"budgets{delta:+d}.json")
    with open(path, "w") as fh:
        json.dump(doc, fh)
    return path


def test_op_count_growth_over_budget_is_error(tmp_path):
    # Budget one op BELOW the measured count: the trace "grew" past it.
    report = run_program_family(
        IncrementTensor(2), budgets_path=_perturbed_budgets(tmp_path, -1)
    )
    assert "STR604" in error_codes(report)


def test_op_count_shrink_under_budget_is_ratchet_warning(tmp_path):
    report = run_program_family(
        IncrementTensor(2), budgets_path=_perturbed_budgets(tmp_path, +1)
    )
    assert report.ok  # warning, not error
    assert any(
        d.code == "STR604" for d in report.warnings
    ), report.format()


def test_exact_budget_match_is_silent():
    report = run_program_family(IncrementTensor(2))
    assert "STR604" not in codes(report)


# ---------------------------------------------------------------------------
# STR605 — compile-signature instability
# ---------------------------------------------------------------------------


def test_unstable_config_digest_flagged():
    report = run_program_family(UnstableSignatureTensor())
    assert "STR605" in error_codes(report)


# ---------------------------------------------------------------------------
# STR606 — the cost model / predicted roofline
# ---------------------------------------------------------------------------


def test_deep_tier_produces_predicted_roofline():
    summary = proglint.program_summary(
        IncrementTensor(2), cost=True, device_kind="TPU v5 lite"
    )
    cost = summary["cost"]
    assert cost["bytes_per_step"] > 0
    assert cost["hbm_gbps"] == 819.0
    assert cost["predicted_states_per_sec"] > 0
    # The deep tier lowered every device program, not just the era loop.
    for name in (
        "era_loop", "seed_loop", "visited_insert", "visited_rehash",
        "mux_expand", "sharded_era",
    ):
        assert summary["programs"][name]["ops"] > 0, name


def test_unknown_device_kind_gets_no_prediction():
    report = AnalysisReport("IncrementTensor")
    proglint.run(
        IncrementTensor(2), report, cost=True, device_kind="no such chip"
    )
    assert any(
        d.code == "STR606" and "no such chip" in d.message
        for d in report.diagnostics
    )
    summary = proglint.program_summary(
        IncrementTensor(2), cost=True, device_kind="no such chip"
    )
    assert summary["cost"]["hbm_gbps"] is None
    assert "predicted_states_per_sec" not in summary["cost"]


def test_device_run_telemetry_carries_program_snapshot():
    from stateright_tpu import TensorModelAdapter
    from stateright_tpu.engines.compiled import model_signature

    tm = IncrementTensor(2)
    # Prime the summary cache.
    proglint.program_summary(tm, cost=True, device_kind="TPU v5 lite")
    assert proglint.cached_summary(model_signature(tm)) is not None
    checker = (
        TensorModelAdapter(tm)
        .checker()
        .spawn_tpu_bfs(
            chunk_size=64, queue_capacity=1 << 10, table_capacity=1 << 10
        )
        .join()
    )
    snap = checker.telemetry()["program"]
    assert snap["signature"] == model_signature(tm)
    assert snap["era_ops"] > 0
    assert snap["predicted_states_per_sec"] > 0
    if snap.get("measured_states_per_sec"):
        assert snap["attribution_ratio"] > 0


def test_write_reporter_prints_program_recap():
    from stateright_tpu.report import ReportData, WriteReporter

    out = io.StringIO()
    WriteReporter(out).report_checking(
        ReportData(
            total_states=100,
            unique_states=100,
            max_depth=3,
            duration_secs=1.0,
            done=True,
            telemetry={
                "steps": 5,
                "program": {
                    "predicted_states_per_sec": 2_000_000.0,
                    "measured_states_per_sec": 500_000.0,
                    "attribution_ratio": 0.25,
                    "era_ops": 1400,
                },
            },
        )
    )
    text = out.getvalue()
    assert "Program. predicted=2.00M/s" in text
    assert "attribution=0.25" in text
    assert "program" not in text.split("Telemetry.")[1].split("\n")[0]


# ---------------------------------------------------------------------------
# count_ops / cache mechanics
# ---------------------------------------------------------------------------


def test_count_ops_recurses_into_control_flow():
    import jax
    import jax.numpy as jnp

    def f(x):
        def body(c, _):
            return c + jnp.uint32(1), None

        y, _ = jax.lax.scan(body, x, None, length=3)
        return y * jnp.uint32(2)

    prims, dtypes = proglint.count_ops(jax.make_jaxpr(f)(jnp.uint32(0)))
    assert prims["scan"] == 1
    assert prims["add"] >= 1  # the body's add, behind the scan param
    assert any(np.dtype(d) == np.uint32 for d in dtypes)


def test_cached_program_pass_replays_identical_diags():
    tm = IncrementTensor(2)
    first = run_program_family(tm)
    second = run_program_family(tm)  # summary-cache hit
    assert codes(first) == codes(second)
    assert "program" in second.families_run


# ---------------------------------------------------------------------------
# The default lint tier includes the family; bundled models stay clean.
# ---------------------------------------------------------------------------


def test_default_analyze_runs_program_family_clean():
    report = analyze(IncrementTensor(2), samples=64)
    assert "program" in report.families_run
    assert report.ok, report.format()


# ---------------------------------------------------------------------------
# CLI contract: exit statuses 0/1/2, --json shape, --program.
# ---------------------------------------------------------------------------


def test_cli_exit_zero_on_clean_model(capsys):
    from stateright_tpu.analysis.__main__ import main

    assert main(["increment:2", "--samples", "32"]) == 0
    assert "IncrementTensor" in capsys.readouterr().out


def test_cli_exit_one_on_error_findings(capsys):
    from stateright_tpu.analysis.__main__ import main

    assert main(["tests.test_proglint:WideLaneTensor", "--samples", "32"]) == 1
    assert "STR603" in capsys.readouterr().out


def test_cli_exit_two_on_unknown_shorthand(capsys):
    from stateright_tpu.analysis.__main__ import main

    with pytest.raises(SystemExit) as exc:
        main(["no-such-model:3"])
    assert exc.value.code == 2
    assert "unknown model" in capsys.readouterr().err


def test_cli_exit_two_on_broken_dotted_path(capsys):
    from stateright_tpu.analysis.__main__ import main

    with pytest.raises(SystemExit) as exc:
        main(["no.such.module:Thing"])
    assert exc.value.code == 2

    capsys.readouterr()
    with pytest.raises(SystemExit) as exc:
        main(["tests.test_proglint:NoSuchFactory"])
    assert exc.value.code == 2
    assert "cannot resolve" in capsys.readouterr().err


def test_cli_json_shape_includes_program_family(capsys):
    from stateright_tpu.analysis.__main__ import main

    assert main(["increment:2", "--samples", "32", "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    for key in (
        "model", "ok", "errors", "warnings", "counts_by_code",
        "families_run", "diagnostics",
    ):
        assert key in doc, key
    assert doc["ok"] is True
    assert "program" in doc["families_run"]


def test_cli_program_flag_runs_deep_tier(capsys):
    from stateright_tpu.analysis.__main__ import main

    assert main(["increment:2", "--samples", "32", "--program"]) == 0
    capsys.readouterr()


# ---------------------------------------------------------------------------
# The bundled-model registry and the models package stay in sync.
# ---------------------------------------------------------------------------

# One constructing spec per registered shorthand (the arg tuples the CI
# dogfood stage uses).
BUNDLED_SPECS = [
    "2pc:3",
    "2pc-host:3",
    "abd:2",
    "abd-ordered:2",
    "binary-clock",
    "increment:2",
    "increment-host:2",
    "increment-lock:2",
    "increment-lock-host:2",
    "linear-equation:1,2,20",
    "linearizable-register:2,2",
    "lww-register:2",
    "paxos:2",
    "single-copy:2,2",
    "write-once-register:2",
]

# models.__all__ entries that are deliberately NOT lintable demo models:
# broken-by-design lint fixtures exercised by the speclint test suite.
LINT_FIXTURES = {"DGraph", "Panicker"}


def test_every_bundled_shorthand_constructs():
    from stateright_tpu.analysis.__main__ import (
        BUNDLED,
        _register,
        resolve_model,
    )

    _register()
    assert {s.split(":")[0] for s in BUNDLED_SPECS} == set(BUNDLED)
    for spec in BUNDLED_SPECS:
        assert resolve_model(spec) is not None, spec


def test_models_package_is_fully_registered():
    import stateright_tpu.models as models_pkg
    from stateright_tpu.analysis.__main__ import BUNDLED, _register

    _register()
    registered_classes = {v for v in BUNDLED.values() if isinstance(v, type)}
    for name in models_pkg.__all__:
        if name in LINT_FIXTURES:
            continue
        assert getattr(models_pkg, name) in registered_classes, (
            f"models.{name} has no bundled lint shorthand "
            "(stateright_tpu/analysis/__main__.py BUNDLED)"
        )


def test_signature_stable_across_deepcopy_for_bundled_model():
    # The positive control for STR605: the bundled models' signatures
    # must survive the very probe the detector uses.
    from stateright_tpu.engines.compiled import model_signature

    tm = IncrementTensor(2)
    assert model_signature(tm) == model_signature(copy.deepcopy(tm))
