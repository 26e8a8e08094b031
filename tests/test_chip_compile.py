"""Compile the main path's device programs for a described TPU v5e.

Nothing here runs on a chip: the TPU compiler, installed beside JAX,
compiles for a v5e:2x2 topology that is described, not attached, and
refuses what the chip's compiler would refuse (memory that does not fit,
shapes it cannot tile, collectives it cannot place). The programs are the
ones the engine runs on the chip, donated buffers included: on the CPU
backend `compat.donate_argnums_safe` drops donation, so each test builds
the donated variant itself.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from stateright_tpu.compat import donate_argnums_pinned
from stateright_tpu.engines.compiled import era_geometry
from stateright_tpu.engines.tpu_bfs import _build_loop, _vcap, loop_abstract_args
from stateright_tpu.models import TwoPhaseTensor
from stateright_tpu.models.paxos import PaxosTensorExhaustive
from stateright_tpu.ops import visited_set as vs

V5E_HBM_BYTES = 16 * 1024**3

SPECS = {
    "2pc-7": lambda: TwoPhaseTensor(7),
    "paxos-3": lambda: PaxosTensorExhaustive(3),
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """A described-chip executable is written to the persistent cache but
    cannot be read back without the chip; keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture
def donating(monkeypatch):
    """Donation as on the chip (the CPU backend turns it off)."""
    import stateright_tpu.compat as compat

    monkeypatch.setattr(compat, "donate_argnums_safe", lambda *a: tuple(a))


def _placed(args, sharding):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding), args
    )


def _fits(compiled, budget=V5E_HBM_BYTES):
    mem = compiled.memory_analysis()
    total = (
        mem.argument_size_in_bytes
        + mem.output_size_in_bytes
        - mem.alias_size_in_bytes
        + mem.temp_size_in_bytes
    )
    assert 0 < total < budget, mem
    return mem


@pytest.mark.parametrize("variant", ["serial", "chain"])
@pytest.mark.parametrize("spec", sorted(SPECS))
def test_solo_era_program_compiles_for_v5e(spec, variant, one_chip, donating):
    tm = SPECS[spec]()
    props = tm.tensor_properties()
    g = era_geometry(tm)
    raw = _build_loop(
        tm, props, g["chunk"], g["qcap"], False, g["cov"], raw=True,
        sample_k=g["sample_k"],
    )
    # The EraProgram's two donation policies (engines/tpu_bfs.py).
    pinned = (4,) if variant == "chain" else ()
    donate = donate_argnums_pinned((0, 1, 2, 3, 4), pinned=pinned)
    assert donate == tuple(a for a in range(5) if a not in pinned)
    args = loop_abstract_args(
        tm, props, g["chunk"], g["qcap"], g["tcap"], g["cov"], g["sample_k"]
    )
    compiled = (
        jax.jit(raw, donate_argnums=donate)
        .lower(*_placed(args, one_chip))
        .compile()
    )
    mem = _fits(compiled)
    # Donation took: the table and ring alias their outputs in place.
    table_bytes = 4 * (4 * g["tcap"])
    assert mem.alias_size_in_bytes >= table_bytes, mem


def test_visited_insert_compiles_at_2pc10_table(one_chip):
    tm = TwoPhaseTensor(10)
    tcap = 1 << 28  # 61.5M states at MAX_LOAD: the table 2pc-10 ends at
    vcap = _vcap(tm.max_actions, era_geometry(tm)["chunk"])
    lane = jax.ShapeDtypeStruct((vcap,), jnp.uint32, sharding=one_chip)
    act = jax.ShapeDtypeStruct((vcap,), jnp.bool_, sharding=one_chip)
    compiled = (
        jax.jit(vs.insert, donate_argnums=(0,))
        .lower(_placed(vs.abstract_table(tcap), one_chip), lane, lane, lane, lane, act)
        .compile()
    )
    mem = _fits(compiled)
    assert mem.alias_size_in_bytes >= 4 * 4 * tcap, mem


def test_visited_rehash_compiles_into_2pc10_table(one_chip):
    tcap = 1 << 28
    compiled = (
        jax.jit(vs.rehash, donate_argnums=(1,))
        .lower(
            _placed(vs.abstract_table(tcap // 2), one_chip),
            _placed(vs.abstract_table(tcap), one_chip),
        )
        .compile()
    )
    mem = _fits(compiled)
    assert mem.alias_size_in_bytes >= 4 * 4 * tcap, mem


@pytest.mark.parametrize("spec", sorted(SPECS))
def test_sharded_era_program_compiles_for_four_v5e(spec, topo, donating):
    from stateright_tpu.analysis.program import _sharded_geometry
    from stateright_tpu.parallel.mesh import _build_block, block_abstract_args

    devices = topo.devices[:4]
    mesh = Mesh(np.array(devices), ("shards",))
    tm = SPECS[spec]()
    props = tm.tensor_properties()
    g = dict(_sharded_geometry(tm, n_shards=len(devices)))
    block = _build_block(
        tm, props, g["chunk"], g["qcap"], g["n_shards"], g["quota"], mesh,
        "shards", cov=g["cov"], sample_k=g["sample_k"],
    )
    args = block_abstract_args(
        tm, props, g["qcap"], g["tcap"], g["n_shards"], g["cov"], g["sample_k"]
    )
    compiled = block.serial.lower(
        *_placed(args, NamedSharding(mesh, PartitionSpec("shards")))
    ).compile()
    mem = _fits(compiled)
    # Per-device bytes: each chip holds its own shard, not the whole state.
    global_bytes = sum(
        int(np.prod(a.shape)) * a.dtype.itemsize for a in jax.tree.leaves(args)
    )
    per_device = global_bytes // len(devices)
    assert per_device <= mem.argument_size_in_bytes < per_device + 4096, mem
    assert "all-to-all" in compiled.as_text()
