"""Phase timers as profiler spans, and the device programs' stage scopes.

While a `jax.profiler` session records, every phase timer is also a
``phase:<name>`` span of the profile and adds to the process-wide
profiled totals (obs/spans.py); with no session it records neither. The
compiled programs carry ``era/<stage>`` scopes (`jax.named_scope`) in
their HLO metadata, which a profile groups device time by.
"""

import glob

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from stateright_tpu import HasDiscoveries
from stateright_tpu.models import IncrementTensor, TwoPhaseTensor
from stateright_tpu.models.single_copy import SingleCopyTensor
from stateright_tpu.obs import spans
from stateright_tpu.obs.spans import (
    ENGINE_STAGES,
    ERA_STAGES,
    profiled_phase_totals,
    reset_profiled_phase_totals,
)
from stateright_tpu.tensor import TensorModelAdapter

SMALL = dict(chunk_size=64, queue_capacity=1 << 10, table_capacity=1 << 10)
SHARDED = dict(
    chunk_size=64, queue_capacity_per_shard=1 << 11, table_capacity_per_shard=1 << 10
)


def _lower_tpu_bfs():
    from stateright_tpu.engines.tpu_bfs import _build_loop, loop_abstract_args

    tm = TwoPhaseTensor(3)
    props = tm.tensor_properties()
    program = _build_loop(tm, props, 64, 1 << 10, canon=True, cov=True, sample_k=8)
    args = loop_abstract_args(tm, props, 64, 1 << 10, 1 << 10, True, 8)
    return program.serial.lower(*args)


def _lower_tpu_simulation():
    from stateright_tpu.engines.tpu_simulation import P_LEN, _build_sim_loop
    from stateright_tpu.obs.coverage import DEPTH_CAP
    from stateright_tpu.obs.sample import slab_entries

    tm = IncrementTensor(2)
    props = tm.tensor_properties()
    S, A, P = tm.state_width, tm.max_actions, len(props)
    _loop, seed_run, _n = _build_sim_loop(tm, props, 32, 8, cov=True, sample_k=8)
    plen = P_LEN + 2 * P + (A + P + DEPTH_CAP) + 4 + (4 + S) * slab_entries(8)
    return seed_run.lower(jax.ShapeDtypeStruct((plen,), np.uint32))


def _lower_sharded():
    from stateright_tpu.parallel.mesh import _build_block, block_abstract_args

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    mesh = Mesh(np.array(jax.devices()[:4]), ("shards",))
    tm = TwoPhaseTensor(3)
    props = tm.tensor_properties()
    block = _build_block(
        tm, props, 64, 1 << 11, 4, 64, mesh, "shards", cov=True, sample_k=8
    )
    sharding = NamedSharding(mesh, PartitionSpec("shards"))
    args = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        block_abstract_args(tm, props, 1 << 11, 1 << 10, 4, True, 8),
    )
    return block.serial.lower(*args)


LOWER = {
    "tpu_bfs": _lower_tpu_bfs,
    "tpu_simulation": _lower_tpu_simulation,
    "sharded": _lower_sharded,
}


@pytest.mark.parametrize("engine", sorted(LOWER))
def test_era_program_names_every_stage_scope(engine):
    text = LOWER[engine]().as_text(debug_info=True)
    missing = [s for s in ENGINE_STAGES[engine] if f"era/{s}" not in text]
    assert not missing, (engine, missing)
    assert set(ENGINE_STAGES[engine]) <= set(ERA_STAGES)


def test_rehash_and_seed_programs_carry_their_scopes():
    from stateright_tpu.engines.tpu_bfs import _build_seed_loop, seed_loop_abstract_args
    from stateright_tpu.ops import visited_set as vs

    rehash = vs.rehash_jit.lower(vs.abstract_table(1 << 8), vs.abstract_table(1 << 9))
    assert spans.REHASH_SCOPE in rehash.as_text(debug_info=True)
    tm = TwoPhaseTensor(3)
    props = tm.tensor_properties()
    seed_run = _build_seed_loop(tm, props, 64, 1 << 10, 1 << 10, False, True)
    args = seed_loop_abstract_args(tm, props, 64, 1 << 10, 1 << 10, True, 0, 1)
    text = seed_run.lower(*args).as_text(debug_info=True)
    # The seeder is a jitted function of its own inside the fused program,
    # so its name stack starts at its scope.
    assert f'"{spans.SEED_SCOPE}/' in text and "era/probe" in text


def test_no_span_or_total_without_a_profiler_session(monkeypatch):
    built = []

    class Annotation:
        @staticmethod
        def is_enabled():
            return False

        def __init__(self, name):
            built.append(name)

    monkeypatch.setattr(spans, "_annotation_cls", Annotation)
    reset_profiled_phase_totals()
    c = TensorModelAdapter(TwoPhaseTensor(3)).checker().spawn_tpu_bfs(**SMALL).join()
    assert c.unique_state_count() == 288
    phase_ms = c.telemetry()["phase_ms"]
    for phase in ("engine_init", "run_setup", "era_dispatch", "readback", "era_host"):
        assert phase in phase_ms, (phase, phase_ms)
    assert built == []
    assert profiled_phase_totals() == {}


def _host_span_names(trace_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    names = set()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names.update(e.name for e in line.events)
    return names


def test_phases_are_profiler_spans_and_totals(tmp_path):
    reset_profiled_phase_totals()
    with jax.profiler.trace(str(tmp_path)):
        c = TensorModelAdapter(TwoPhaseTensor(3)).checker().spawn_tpu_bfs(**SMALL)
        c.join()
    assert c.unique_state_count() == 288
    totals = profiled_phase_totals()
    names = _host_span_names(tmp_path)
    phase_ms = c.telemetry()["phase_ms"]
    for phase in ("readback", "run_setup", "era_host", "engine_init"):
        assert f"phase:{phase}" in names, (phase, sorted(names))
        assert totals[phase]["count"] >= 1, totals
        # One checker ran, so its own accounting and the totals agree.
        assert totals[phase]["secs"] * 1e3 == pytest.approx(phase_ms[phase], abs=1e-2)
    reset_profiled_phase_totals()
    assert profiled_phase_totals() == {}


class LinearizableOnly(SingleCopyTensor):
    """The register spec with its one safety property, so that a check
    stopped at the counterexample holds exactly one discovery."""

    def tensor_properties(self):
        return [p for p in super().tensor_properties() if p.name == "linearizable"]


def _single_copy(engine, builder):
    if engine == "sharded":
        if len(jax.devices()) < 4:
            pytest.skip("needs 4 virtual devices")
        return builder.spawn_sharded_bfs(devices=jax.devices()[:4], **SHARDED)
    return builder.spawn_tpu_bfs(**SMALL)


@pytest.mark.parametrize("engine", ["tpu_bfs", "sharded"])
def test_counterexample_path_phases(engine, tmp_path):
    def check():
        builder = (
            TensorModelAdapter(LinearizableOnly(2, 2))
            .checker()
            .finish_when(HasDiscoveries.any_of(["linearizable"]))
        )
        c = _single_copy(engine, builder).join()
        assert list(c._discovery_fps) == ["linearizable"]
        return c.discovery("linearizable").into_actions()

    plain = check()
    reset_profiled_phase_totals()
    with jax.profiler.trace(str(tmp_path)):
        traced = check()
    totals = profiled_phase_totals()
    assert traced == plain and plain
    for phase in ("path_download", "path_walk", "path_replay"):
        assert totals[phase]["count"] == 1, (phase, totals)
    assert {"phase:path_download", "phase:path_replay"} <= _host_span_names(tmp_path)
