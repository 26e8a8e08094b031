"""Test configuration: run JAX on a virtual 8-device CPU mesh.

The tests run on the CPU backend (`JAX_PLATFORMS=cpu`); sharding is
exercised on 8 virtual CPU devices. The chip itself is exercised by
`python chip_smoke.py` (one chip) and `python chip_smoke.py --chips 4`
(the sharded mesh), run through the chip tool.
"""

import gc
import os
import sys

import pytest

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from stateright_tpu.compat import configure_compilation_cache  # noqa: E402

# Persistent compilation cache: the suite's wall-clock is dominated by XLA
# compiles (every engine-option variation builds a fresh loop).
configure_compilation_cache()

# Every XLA:CPU executable a worker keeps alive holds memory mappings,
# and the suite's jit caches keep thousands alive. At the kernel's
# vm.max_map_count (65,530) the next compile or cache load cannot map its
# code and the worker dies with SIGSEGV/SIGABRT inside XLA. Dropping the
# compiled executables once a worker nears that limit keeps it well clear;
# the persistent cache makes the recompiles cheap.
_MAPPED_REGIONS_LIMIT = 24_000


def _mapped_regions() -> int:
    try:
        with open("/proc/self/maps", "rb") as fh:
            return sum(1 for _ in fh)
    except OSError:
        return 0


@pytest.fixture(autouse=True)
def _bound_mapped_regions():
    yield
    if _mapped_regions() > _MAPPED_REGIONS_LIMIT:
        jax.clear_caches()
        gc.collect()


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running golden-count integration tests"
    )
