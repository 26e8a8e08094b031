"""Pipelined-vs-serial parity (ISSUE 14 tentpole): the speculative era
driver must be GOLDEN-IDENTICAL to the serial one.

A speculative era is dispatched off the still-on-device params chain
before the host has read era N's result. The device cond re-derives
every host-intervention exit from the chained params, so a speculative
era dispatched across a host-action boundary is an exact identity no-op
and the consumed stream of eras is the same either way. These tests pin
that equivalence end to end on both device engines: unique counts,
total state counts, max depth, discovery fingerprints, and coverage
histograms — with pipelining forced OFF via ``CheckerBuilder.pipeline``
against the default ON — plus the chaos path (a probe-error era with a
speculative era in flight is discarded wholesale by the checkpoint
reload and never corrupts the resumed run).
"""

import jax
import pytest

from stateright_tpu.engines import tpu_bfs
from stateright_tpu.models import (
    AbdOrderedTensor,
    AbdTensor,
    IncrementLockTensor,
    SingleCopyTensor,
    TwoPhaseTensor,
)
from stateright_tpu.models.paxos import PaxosTensor
from stateright_tpu.parallel import mesh
from stateright_tpu.tensor import TensorModelAdapter

# sync_steps=4 forces many short eras so speculative chains actually
# engage (a run that finishes in one era never reaches a chain point).
OPTS = dict(
    chunk_size=64,
    queue_capacity=1 << 12,
    table_capacity=1 << 11,
    sync_steps=4,
)


def _paxos_opts():
    return dict(
        chunk_size=1024,
        queue_capacity=1 << 16,
        table_capacity=1 << 16,
        sync_steps=64,
    )


def _fingerprint(c):
    """Everything the golden contract covers, in one comparable dict."""
    cov = c.coverage()
    fp = dict(
        unique=c.unique_state_count(),
        states=c.state_count(),
        max_depth=c.max_depth(),
        discovery_fps=dict(c._discovery_fps),
        coverage_actions=cov["actions"],
        coverage_depths=cov["depths"],
    )
    sampler = getattr(c, "_sampler", None)
    if sampler is not None and sampler.size():
        # The deterministic bottom-k sample is part of the golden
        # contract too: the chain reorders nothing the sampler can see.
        fp["sample"] = tuple(sampler.fingerprints())
    return fp


@pytest.fixture(scope="module")
def devices():
    devs = jax.devices()
    if len(devs) < 4:
        pytest.skip("needs 4 virtual devices")
    return devs[:4]


def test_tpu_bfs_parity_2pc5():
    runs = {}
    for on in (True, False):
        c = (
            TensorModelAdapter(TwoPhaseTensor(5))
            .checker()
            .coverage()
            .pipeline(on)
            .spawn_tpu_bfs(**OPTS)
            .join()
        )
        c.assert_properties()
        runs[on] = (_fingerprint(c), c.telemetry())
    fp_on, tel_on = runs[True]
    fp_off, tel_off = runs[False]
    assert fp_on["unique"] == 8832
    assert fp_on == fp_off
    # The pipelined run actually speculated; the serial run never did.
    assert tel_on.get("spec_dispatch", 0) >= 1
    assert tel_off.get("spec_dispatch", 0) == 0


def test_tpu_bfs_parity_paxos2():
    from stateright_tpu.models.paxos import PaxosTensorExhaustive

    runs = {}
    for on in (True, False):
        c = (
            TensorModelAdapter(PaxosTensorExhaustive(2))
            .checker()
            .coverage()
            .pipeline(on)
            .spawn_tpu_bfs(**_paxos_opts())
            .join()
        )
        runs[on] = (_fingerprint(c), c.telemetry())
    fp_on, tel_on = runs[True]
    fp_off, _ = runs[False]
    assert fp_on["unique"] == 16_668
    assert fp_on == fp_off
    assert "value chosen" in fp_on["discovery_fps"]
    assert tel_on.get("spec_dispatch", 0) >= 1


def test_mesh_parity_2pc5(devices):
    runs = {}
    opts = dict(
        devices=devices,
        chunk_size=64,
        queue_capacity_per_shard=1 << 11,
        table_capacity_per_shard=1 << 10,
        sync_steps=4,
    )
    for on in (True, False):
        c = (
            TensorModelAdapter(TwoPhaseTensor(5))
            .checker()
            .coverage()
            .pipeline(on)
            .spawn_sharded_bfs(**opts)
            .join()
        )
        runs[on] = (_fingerprint(c), c.telemetry())
    fp_on, tel_on = runs[True]
    fp_off, tel_off = runs[False]
    assert fp_on["unique"] == 8832
    assert fp_on == fp_off
    assert tel_on.get("spec_dispatch", 0) >= 1
    assert tel_off.get("spec_dispatch", 0) == 0


# ---------------------------------------------------------------------------
# Era-length sweep: the chain against the serial driver at the same
# sync_steps.
# ---------------------------------------------------------------------------
#
# sync_steps sets where eras end, and so how often the chain is entered
# and how often a chained era does real work rather than an identity
# no-op. Every length must reproduce the serial run at that length: the
# unique count, max depth, discovery fingerprints, coverage histograms
# and the deterministic bottom-k sample.

SYNC_SWEEP = [1, 2, 4, 64]


def _mesh_opts(devices, sync_steps=4):
    return dict(
        devices=devices,
        chunk_size=64,
        queue_capacity_per_shard=1 << 11,
        table_capacity_per_shard=1 << 10,
        sync_steps=sync_steps,
    )


@pytest.fixture(scope="module")
def serial_2pc5_solo(sync_steps):
    c = (
        TensorModelAdapter(TwoPhaseTensor(5))
        .checker()
        .coverage()
        .pipeline(False)
        .spawn_tpu_bfs(**dict(OPTS, sync_steps=sync_steps))
        .join()
    )
    return _fingerprint(c)


@pytest.mark.parametrize("sync_steps", SYNC_SWEEP, scope="module")
def test_tpu_bfs_mega_sweep_2pc5(sync_steps, serial_2pc5_solo):
    c = (
        TensorModelAdapter(TwoPhaseTensor(5))
        .checker()
        .coverage()
        .spawn_tpu_bfs(**dict(OPTS, sync_steps=sync_steps))
        .join()
    )
    fp = _fingerprint(c)
    assert fp == serial_2pc5_solo
    assert fp["unique"] == 8832
    assert c.telemetry()["spec_chain_depth"] <= tpu_bfs.CHAIN_DEPTH


@pytest.fixture(scope="module")
def serial_2pc5_mesh(sync_steps, devices):
    c = (
        TensorModelAdapter(TwoPhaseTensor(5))
        .checker()
        .coverage()
        .pipeline(False)
        .spawn_sharded_bfs(**_mesh_opts(devices, sync_steps))
        .join()
    )
    return _fingerprint(c)


@pytest.mark.parametrize("sync_steps", SYNC_SWEEP, scope="module")
def test_mesh_mega_sweep_2pc5(sync_steps, devices, serial_2pc5_mesh):
    c = (
        TensorModelAdapter(TwoPhaseTensor(5))
        .checker()
        .coverage()
        .spawn_sharded_bfs(**_mesh_opts(devices, sync_steps))
        .join()
    )
    fp = _fingerprint(c)
    assert fp == serial_2pc5_mesh
    assert fp["unique"] == 8832
    assert c.telemetry()["spec_chain_depth"] <= mesh.CHAIN_DEPTH


def test_tpu_bfs_mega_parity_paxos2():
    """The chain against serial on the bigger model."""
    from stateright_tpu.models.paxos import PaxosTensorExhaustive

    fps = {}
    for on in (False, True):
        b = TensorModelAdapter(PaxosTensorExhaustive(2)).checker().coverage()
        fps[on] = _fingerprint(
            b.pipeline(on).spawn_tpu_bfs(**_paxos_opts()).join()
        )
    assert fps[True] == fps[False]
    assert fps[False]["unique"] == 16_668


def test_mesh_mega_parity_paxos2(devices):
    from stateright_tpu.models.paxos import PaxosTensorExhaustive

    opts = dict(
        devices=devices,
        chunk_size=256,
        queue_capacity_per_shard=1 << 14,
        table_capacity_per_shard=1 << 13,
        sync_steps=64,
    )
    fps = {}
    for on in (False, True):
        b = TensorModelAdapter(PaxosTensorExhaustive(2)).checker().coverage()
        fps[on] = _fingerprint(b.pipeline(on).spawn_sharded_bfs(**opts).join())
    assert fps[True] == fps[False]
    assert fps[False]["unique"] == 16_668


# ---------------------------------------------------------------------------
# The other bundled lane twins, at short eras, on both device engines.
# ---------------------------------------------------------------------------

TWINS = {
    "increment-lock-3": lambda: IncrementLockTensor(3),
    "single-copy-2x2": lambda: SingleCopyTensor(2, 2),
    "abd-2": lambda: AbdTensor(2),
    "abd-ordered-2": lambda: AbdOrderedTensor(2),
    "paxos-1": lambda: PaxosTensor(1),
}


def _spawn(builder, engine, devices, **kw):
    if engine == "tpu_bfs":
        return builder.spawn_tpu_bfs(**dict(OPTS, **kw))
    return builder.spawn_sharded_bfs(**dict(_mesh_opts(devices), **kw))


@pytest.mark.parametrize("twin", sorted(TWINS))
@pytest.mark.parametrize("engine", ["tpu_bfs", "sharded"])
def test_twin_parity_short_eras(engine, twin, devices):
    fps = {}
    for on in (False, True):
        b = TensorModelAdapter(TWINS[twin]()).checker().coverage().pipeline(on)
        fps[on] = _fingerprint(_spawn(b, engine, devices).join())
    assert fps[True] == fps[False]
    assert fps[False]["unique"] > 0


# ---------------------------------------------------------------------------
# The adaptive step budget: a timeout or a checkpoint cadence makes eras
# end on their step budget, so chained eras do real work at budget exits.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("poll", ["timeout", "checkpoint"])
@pytest.mark.parametrize("engine", ["tpu_bfs", "sharded"])
def test_adaptive_budget_parity(engine, poll, devices, tmp_path):
    fps = {}
    for on in (False, True):
        b = (
            TensorModelAdapter(TwoPhaseTensor(5))
            .checker()
            .coverage()
            .pipeline(on)
        )
        kw = dict(sync_steps=4096)
        if poll == "timeout":
            b = b.timeout(600)
        else:
            kw.update(
                checkpoint_path=str(tmp_path / f"adaptive-{on}.ckpt.npz"),
                checkpoint_every=600,
            )
        c = _spawn(b, engine, devices, **kw).join()
        fps[on] = (_fingerprint(c), c.telemetry())
    (fp_on, tel_on), (fp_off, tel_off) = fps[True], fps[False]
    assert fp_on == fp_off
    assert fp_on["unique"] == 8832
    # Budget exits: more than one era in both runs, and the chain ran.
    assert tel_off["eras"] > 1
    assert tel_on.get("spec_dispatch", 0) >= 1
    assert tel_off.get("spec_dispatch", 0) == 0


def test_tpu_bfs_kill_resume_under_deep_chain(tmp_path):
    """A checkpointed run killed at a boundary and resumed with the
    speculative chain must land on the serial golden (the final
    checkpoint of a partial run is the exact stopping point, and the
    resumed pipelined driver replays nothing and skips nothing)."""
    ckpt = str(tmp_path / "deep.ckpt.npz")
    part = (
        TensorModelAdapter(TwoPhaseTensor(5))
        .checker()
        .target_state_count(2_000)
        .spawn_tpu_bfs(checkpoint_path=ckpt, **OPTS)
        .join()
    )
    assert 0 < part.unique_state_count() < 8832
    c = (
        TensorModelAdapter(TwoPhaseTensor(5))
        .checker()
        .coverage()
        .spawn_tpu_bfs(resume_from=ckpt, **OPTS)
        .join()
    )
    assert c.unique_state_count() == 8832
    c.assert_properties()


# ---------------------------------------------------------------------------
# Chaos: a probe-error era with a speculative era in flight
# ---------------------------------------------------------------------------
#
# The degraded-regrow path (reload last checkpoint, double the table,
# continue) must discard the WHOLE chain: the error era's unsound work
# and whatever the speculative era did. A real probe error closes the
# chained dispatch's gate (the carried P_ERR makes it an identity
# no-op); the chaos hook fakes the error host-side, so the speculative
# era may have run real work — the reload discards it wholesale either
# way, and the resumed run must still land on the exact golden.


def test_tpu_bfs_chaos_spec_discard_recovers(tmp_path):
    ckpt = str(tmp_path / "spec.ckpt.npz")
    # Seed a checkpoint generation (state-count targets run serial).
    part = (
        TensorModelAdapter(TwoPhaseTensor(5))
        .checker()
        .target_state_count(2_000)
        .spawn_tpu_bfs(checkpoint_path=ckpt, **OPTS)
        .join()
    )
    assert 0 < part.unique_state_count() < 8832
    # Resume pipelined: a long cadence keeps the chain gate open, so the
    # chaos-faked error lands while a speculative era is in flight.
    checker = (
        TensorModelAdapter(TwoPhaseTensor(5))
        .checker()
        .spawn_tpu_bfs(
            resume_from=ckpt,
            checkpoint_path=ckpt,
            checkpoint_every=30.0,
            **OPTS,
        )
    )
    checker._chaos_probe_error_era = 1
    checker.join()
    assert checker.unique_state_count() == 8832
    checker.assert_properties()
    tel = checker.telemetry()
    assert tel.get("degraded_regrow", 0) == 1
    assert tel.get("spec_dispatch", 0) >= 1
    assert tel.get("spec_wasted", 0) >= 1


def test_mesh_chaos_spec_discard_recovers(tmp_path, devices):
    ckpt = str(tmp_path / "mesh-spec.ckpt.npz")
    opts = _mesh_opts(devices)
    part = (
        TensorModelAdapter(TwoPhaseTensor(5))
        .checker()
        .target_state_count(3_000)
        .spawn_sharded_bfs(checkpoint_path=ckpt, **opts)
        .join()
    )
    assert 0 < part.unique_state_count() < 8832
    checker = (
        TensorModelAdapter(TwoPhaseTensor(5))
        .checker()
        .spawn_sharded_bfs(
            resume_from=ckpt,
            checkpoint_path=ckpt,
            checkpoint_every=30.0,
            **opts,
        )
    )
    checker._chaos_probe_error_era = 1
    checker.join()
    assert checker.unique_state_count() == 8832
    tel = checker.telemetry()
    assert tel.get("degraded_regrow", 0) == 1
    assert tel.get("spec_dispatch", 0) >= 1
    assert tel.get("spec_wasted", 0) >= 1
