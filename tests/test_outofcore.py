"""Out-of-core state spaces (ISSUE 20): tiered frontier spill, delta
checkpoints, and forecast-triggered proactive resharding.

The contract under test, for every device engine:

* a spill stack bounded by `STPU_SPILL_HOST_BUDGET_BYTES` demotes its
  oldest blocks to npz disk segments and promotes them back newest-first
  — LIFO order preserved across tiers, so counts stay EXACT goldens
  (2pc-5: 8,832);
* checkpoints past the first save write table DELTAS (rows inserted
  since the base), folding back onto the base at load; a corrupt delta
  falls back to the previous link; a resumed run hits the golden;
* under `STPU_DEVICE_MEMORY_BYTES` the forecaster's projection triggers
  a proactive table doubling (`reshard_proactive`) at a host-owned era
  boundary, output-identical to the uncapped run;
* the one-shot memory warning re-arms after a growth/reshard.
"""

import os

import numpy as np
import pytest

from stateright_tpu.models import TwoPhaseTensor
from stateright_tpu.tensor import TensorModelAdapter

OPTS = dict(chunk_size=64, queue_capacity=1 << 12, table_capacity=1 << 11)
# Small ring + chunk: 2pc-5 crosses the high-water mark and spills.
SPILL_OPTS = dict(chunk_size=32, queue_capacity=1 << 10, table_capacity=1 << 11)


# ---------------------------------------------------------------------------
# TieredSpillStore unit tests (ops/tiering.py)
# ---------------------------------------------------------------------------


def _blk(tag, rows=8, width=4):
    return np.full((rows, width), tag, dtype=np.uint32)


def test_tiering_unbudgeted_is_plain_lifo():
    from stateright_tpu.ops.tiering import TieredSpillStore

    st = TieredSpillStore()
    for t in range(5):
        st.append(_blk(t))
    assert len(st) == 5 and st.segments() == 0
    assert st.peek_rows() == 8
    out = [int(st.pop()[0, 0]) for _ in range(5)]
    assert out == [4, 3, 2, 1, 0]
    assert not st


def test_tiering_budget_demotes_oldest_and_preserves_lifo(tmp_path):
    from stateright_tpu.ops.tiering import TieredSpillStore

    moves = []
    st = TieredSpillStore(
        host_budget_bytes=2 * _blk(0).nbytes,
        spool_dir=str(tmp_path),
        on_tier=lambda d, r, b, db: moves.append((d, r)),
    )
    for t in range(6):
        st.append(_blk(t))
    # Oldest blocks demoted to disk; newest always stays in RAM.
    assert st.segments() >= 1
    assert st.disk_bytes() > 0
    assert st.host_bytes() <= 2 * _blk(0).nbytes
    assert st.rows() == 6 * 8
    assert moves and moves[0][0] == "ram_to_disk"
    # iter_blocks walks oldest-first without consuming anything.
    tags = [int(b[0, 0]) for b in st.iter_blocks()]
    assert tags == [0, 1, 2, 3, 4, 5]
    assert len(st) == 6
    # pop returns strict LIFO across the RAM/disk boundary.
    out = [int(st.pop()[0, 0]) for _ in range(6)]
    assert out == [5, 4, 3, 2, 1, 0]
    assert any(d == "disk_to_ram" for d, _ in moves)
    assert st.disk_bytes() == 0


def test_tiering_reset_and_clear_remove_segments(tmp_path):
    from stateright_tpu.ops.tiering import TieredSpillStore

    st = TieredSpillStore(
        host_budget_bytes=_blk(0).nbytes, spool_dir=str(tmp_path)
    )
    for t in range(4):
        st.append(_blk(t))
    assert st.segments() >= 1
    st.reset([_blk(9)])
    assert len(st) == 1 and int(st.pop()[0, 0]) == 9
    for t in range(4):
        st.append(_blk(t))
    st.clear()
    assert not st and st.disk_bytes() == 0
    assert not any(f.endswith(".npz") for f in os.listdir(tmp_path))
    with pytest.raises(IndexError):
        st.peek_rows()


def test_spill_host_budget_env(monkeypatch):
    from stateright_tpu.ops.tiering import spill_host_budget_bytes

    monkeypatch.delenv("STPU_SPILL_HOST_BUDGET_BYTES", raising=False)
    assert spill_host_budget_bytes() is None
    monkeypatch.setenv("STPU_SPILL_HOST_BUDGET_BYTES", "4096")
    assert spill_host_budget_bytes() == 4096
    monkeypatch.setenv("STPU_SPILL_HOST_BUDGET_BYTES", "0")
    assert spill_host_budget_bytes() is None
    monkeypatch.setenv("STPU_SPILL_HOST_BUDGET_BYTES", "nope")
    assert spill_host_budget_bytes() is None


# ---------------------------------------------------------------------------
# Disk-tier spill parity on the engines
# ---------------------------------------------------------------------------


def test_tpu_bfs_disk_spill_golden(monkeypatch):
    """A host budget far below the spill volume forces the disk tier;
    the run must still land on the exact golden with every demoted row
    promoted back."""
    monkeypatch.setenv("STPU_SPILL_HOST_BUDGET_BYTES", str(1 << 13))
    checker = (
        TensorModelAdapter(TwoPhaseTensor(5))
        .checker()
        .spawn_tpu_bfs(**SPILL_OPTS)
    )
    checker.join()
    assert checker.unique_state_count() == 8832
    checker.assert_properties()
    tel = checker.telemetry()
    assert tel.get("spill_rows", 0) > 0
    assert tel.get("spill_tier_rows", 0) > 0
    # Every demoted row came back up.
    assert tel.get("spill_tier_refill_rows", 0) == tel["spill_tier_rows"]
    assert tel.get("spill_disk_bytes") == 0  # drained by run end


def test_tpu_bfs_kill_resume_mid_spill_with_deltas(tmp_path, monkeypatch):
    """Kill at a spilling era boundary with a delta-chain checkpoint on
    disk (base + >=1 delta), resume, land on the golden."""
    monkeypatch.setenv("STPU_SPILL_HOST_BUDGET_BYTES", str(1 << 13))
    ckpt = str(tmp_path / "oc.ckpt.npz")
    part = (
        TensorModelAdapter(TwoPhaseTensor(5))
        .checker()
        .target_state_count(4_000)
        .spawn_tpu_bfs(
            checkpoint_path=ckpt, checkpoint_every=1e-4, **SPILL_OPTS
        )
        .join()
    )
    assert 0 < part.unique_state_count() < 8832
    tel = part.telemetry()
    assert tel.get("checkpoint_saves", 0) >= 1
    assert tel.get("checkpoint_delta_saves", 0) >= 1
    assert os.path.exists(ckpt + ".d1")
    # The partial run actually checkpointed mid-spill at least once: its
    # final save carries staged spill blocks.
    resumed = (
        TensorModelAdapter(TwoPhaseTensor(5))
        .checker()
        .spawn_tpu_bfs(resume_from=ckpt, **SPILL_OPTS)
        .join()
    )
    assert resumed.unique_state_count() == 8832
    resumed.assert_properties()


def test_tpu_bfs_corrupt_delta_falls_back_to_previous_link(
    tmp_path, monkeypatch
):
    """Truncating the newest delta must fall back to the previous chain
    link (or the base) and still resume to the golden."""
    ckpt = str(tmp_path / "cd.ckpt.npz")
    part = (
        TensorModelAdapter(TwoPhaseTensor(5))
        .checker()
        .target_state_count(4_000)
        .spawn_tpu_bfs(checkpoint_path=ckpt, checkpoint_every=1e-4, **OPTS)
        .join()
    )
    tel = part.telemetry()
    assert tel.get("checkpoint_delta_saves", 0) >= 1
    from stateright_tpu.engines.common import delta_chain_paths

    chain = delta_chain_paths(ckpt)
    assert chain
    newest = chain[-1]
    with open(newest, "r+b") as f:
        f.truncate(os.path.getsize(newest) // 2)
    resumed = (
        TensorModelAdapter(TwoPhaseTensor(5))
        .checker()
        .spawn_tpu_bfs(resume_from=ckpt, **OPTS)
        .join()
    )
    assert resumed.unique_state_count() == 8832
    rtel = resumed.telemetry()
    assert rtel.get("checkpoint_corrupt_rejected", 0) >= 1
    assert rtel.get("checkpoint_fallbacks", 0) >= 1


def test_delta_chain_compacts_to_new_base(tmp_path):
    """A chain longer than DELTA_CHAIN_MAX rolls up: the next save is a
    full base and the stale chain is cleared."""
    from stateright_tpu.engines.common import (
        DELTA_CHAIN_MAX,
        delta_chain_paths,
        load_checkpoint_folded,
        save_checkpoint_tiered,
    )

    path = str(tmp_path / "chain.ckpt.npz")
    tcap = 64
    t0 = np.zeros(tcap, dtype=np.uint32)
    t1 = np.zeros(tcap, dtype=np.uint32)
    t2 = np.zeros(tcap, dtype=np.uint32)
    t3 = np.zeros(tcap, dtype=np.uint32)
    state = None
    n_saves = DELTA_CHAIN_MAX + 2
    for i in range(n_saves):
        # Insert one new row per save.
        t0[i] = i + 1
        t1[i] = 100 + i
        arrays = {
            "table0": t0.copy(), "table1": t1.copy(),
            "table2": t2.copy(), "table3": t3.copy(),
            "extra": np.asarray([i], dtype=np.int64),
        }
        state = save_checkpoint_tiered(
            path, {"tick": i}, arrays, state=state, tcap=tcap
        )
    # Saves: full, d1..dMAX, then compaction -> full again.
    assert len(delta_chain_paths(path)) == 0
    data, meta = load_checkpoint_folded(path)
    assert meta["tick"] == n_saves - 1
    np.testing.assert_array_equal(data["table0"], t0)
    np.testing.assert_array_equal(data["table1"], t1)
    assert int(data["extra"][0]) == n_saves - 1


def test_delta_fold_reconstructs_exact_table(tmp_path):
    """base + newest delta == the full state at the newest save, bit for
    bit, including non-table arrays taken from the delta only."""
    from stateright_tpu.engines.common import (
        delta_chain_paths,
        load_checkpoint_folded,
        save_checkpoint_tiered,
    )

    path = str(tmp_path / "fold.ckpt.npz")
    rng = np.random.default_rng(7)
    tcap = 4096
    lanes = [np.zeros(tcap, dtype=np.uint32) for _ in range(4)]
    occ_idx = rng.choice(tcap, size=600, replace=False)
    for i in occ_idx[:400]:
        for t, lane in enumerate(lanes):
            lane[i] = rng.integers(1, 1 << 30)
    state = save_checkpoint_tiered(
        path, {"n": 400},
        {f"table{t}": l.copy() for t, l in enumerate(lanes)},
        state=None, tcap=tcap,
    )
    for i in occ_idx[400:]:
        for t, lane in enumerate(lanes):
            lane[i] = rng.integers(1, 1 << 30)
    arrays = {f"table{t}": l.copy() for t, l in enumerate(lanes)}
    arrays["spill0"] = np.arange(12, dtype=np.uint32)
    save_checkpoint_tiered(
        path, {"n": 600}, arrays, state=state, tcap=tcap
    )
    assert len(delta_chain_paths(path)) == 1
    data, meta = load_checkpoint_folded(path)
    assert meta["n"] == 600
    for t, lane in enumerate(lanes):
        np.testing.assert_array_equal(data[f"table{t}"], lane)
    np.testing.assert_array_equal(
        data["spill0"], np.arange(12, dtype=np.uint32)
    )
    # The delta (200 inserted rows) is smaller than a FULL save of the
    # same final arrays would be.  The base itself isn't a fair yardstick:
    # npz compression deflates its zero rows to almost nothing, while the
    # delta carries only incompressible inserted values.
    full_path = str(tmp_path / "full.ckpt.npz")
    save_checkpoint_tiered(
        full_path, {"n": 600}, arrays, state=None, tcap=tcap
    )
    full_bytes = os.path.getsize(full_path)
    delta_bytes = os.path.getsize(delta_chain_paths(path)[0])
    assert delta_bytes < full_bytes


def test_tcap_change_forces_full_base(tmp_path):
    from stateright_tpu.engines.common import (
        delta_chain_paths,
        save_checkpoint_tiered,
    )

    path = str(tmp_path / "grow.ckpt.npz")
    arrays = lambda cap: {  # noqa: E731
        f"table{t}": np.zeros(cap, dtype=np.uint32) for t in range(4)
    }
    state = save_checkpoint_tiered(
        path, {}, arrays(64), state=None, tcap=64
    )
    state = save_checkpoint_tiered(
        path, {}, arrays(64), state=state, tcap=64
    )
    assert len(delta_chain_paths(path)) == 1
    # Growth doubled the table: rows moved, deltas are meaningless.
    state = save_checkpoint_tiered(
        path, {}, arrays(128), state=state, tcap=128
    )
    assert state["seq"] == 0
    assert len(delta_chain_paths(path)) == 0


# ---------------------------------------------------------------------------
# Proactive reshard parity (solo + mesh, pipelined)
# ---------------------------------------------------------------------------


def test_tpu_bfs_proactive_reshard_parity(monkeypatch):
    """Capped run must proactively double the table off the forecast and
    still match the uncapped golden exactly."""
    monkeypatch.setenv("STPU_DEVICE_MEMORY_BYTES", "300000")
    checker = (
        TensorModelAdapter(TwoPhaseTensor(5))
        .checker()
        .spawn_tpu_bfs(
            chunk_size=64, queue_capacity=1 << 12, table_capacity=1 << 8
        )
    )
    checker.join()
    assert checker.unique_state_count() == 8832
    checker.assert_properties()
    tel = checker.telemetry()
    assert tel.get("reshard_proactive", 0) >= 1
    assert tel.get("table_growths", 0) >= tel["reshard_proactive"]


def test_mesh_proactive_reshard_parity(monkeypatch):
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    monkeypatch.setenv("STPU_DEVICE_MEMORY_BYTES", "2000000")
    checker = (
        TensorModelAdapter(TwoPhaseTensor(5))
        .checker()
        .spawn_sharded_bfs(
            devices=jax.devices()[:4],
            chunk_size=64,
            queue_capacity_per_shard=1 << 11,
            table_capacity_per_shard=1 << 8,
        )
    )
    checker.join()
    assert checker.unique_state_count() == 8832
    tel = checker.telemetry()
    assert tel.get("reshard_proactive", 0) >= 1


def test_mesh_disk_spill_golden(tmp_path, monkeypatch):
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    monkeypatch.setenv("STPU_SPILL_HOST_BUDGET_BYTES", str(1 << 13))
    checker = (
        TensorModelAdapter(TwoPhaseTensor(5))
        .checker()
        .spawn_sharded_bfs(
            devices=jax.devices()[:4],
            chunk_size=64,
            queue_capacity_per_shard=1 << 10,
            table_capacity_per_shard=1 << 10,
        )
    )
    checker.join()
    assert checker.unique_state_count() == 8832
    tel = checker.telemetry()
    if tel.get("spill_rows", 0):  # ring pressure is config-dependent
        assert tel.get("spill_tier_rows", 0) >= 0


@pytest.mark.slow
def test_mesh_paxos2_outofcore_parity(monkeypatch):
    """ISSUE 20 acceptance shape: paxos-2 on the full 8-device virtual
    mesh under a device cap + spill budget, pipelined, must be
    bit-identical to the unconstrained mesh run."""
    import jax

    from stateright_tpu.models.paxos import PaxosTensorExhaustive

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    mesh_opts = dict(
        chunk_size=256,
        queue_capacity_per_shard=1 << 11,
        table_capacity_per_shard=1 << 8,
    )
    ref = (
        TensorModelAdapter(PaxosTensorExhaustive(2))
        .checker()
        .spawn_sharded_bfs(devices=jax.devices()[:8], **mesh_opts)
        .join()
    )
    assert ref.unique_state_count() == 16_668
    monkeypatch.setenv("STPU_DEVICE_MEMORY_BYTES", "1000000")
    monkeypatch.setenv("STPU_SPILL_HOST_BUDGET_BYTES", str(1 << 13))
    capped = (
        TensorModelAdapter(PaxosTensorExhaustive(2))
        .checker()
        .spawn_sharded_bfs(devices=jax.devices()[:8], **mesh_opts)
        .join()
    )
    assert capped.unique_state_count() == ref.unique_state_count()
    assert capped.state_count() == ref.state_count()
    assert dict(capped._discovery_fps) == dict(ref._discovery_fps)


# ---------------------------------------------------------------------------
# Forecaster warning re-arm (satellite)
# ---------------------------------------------------------------------------


def test_memory_warning_rearms_after_growth():
    from stateright_tpu.obs.memory import MemoryRecorder

    rec = MemoryRecorder("t", device_limit_bytes=1 << 20)
    rec.ledger.register("visited_table", nbytes=900_000, kind="device")
    rec.set_geometry(rows=1 << 10, max_load=0.25, reserve_rows=64)
    rec.on_era(unique=200)
    assert rec.warning is not None  # headroom below the next doubling
    # Growth doubles the rows: the warning must re-arm...
    rec.set_geometry(rows=1 << 11, max_load=0.25, reserve_rows=64)
    assert rec.warning is None
    # ...so a second approach to the (new) wall warns again.
    rec.on_era(unique=400)
    assert rec.warning is not None
    # Same-size geometry updates do NOT re-arm.
    w = rec.warning
    rec.set_geometry(rows=1 << 11, max_load=0.25, reserve_rows=64)
    assert rec.warning == w


def test_rearm_warning_is_idempotent():
    from stateright_tpu.obs.memory import MemoryRecorder

    rec = MemoryRecorder("t", device_limit_bytes=None)
    rec.rearm_warning()  # nothing armed: no-op, no events
    assert rec.warning is None
