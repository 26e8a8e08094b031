"""JAX backend compatibility shims (written for the installed JAX 0.9).

Buffer donation is UNSOUND on XLA:CPU when the persistent compilation
cache is enabled (first measured on jax 0.4.37; kept for 0.9, where the
tests still run on the CPU backend with the cache on): an executable
DESERIALIZED from the cache mis-executes donated-buffer while-loop
programs — in the visited-set claim protocol, 8 of 2,556 inserted keys
landed off their double-hash probe sequence, silently breaking dedup (a
resumed 2pc-5 run counted 28,003 "uniques" in an 8,832-state space).
Freshly compiled executables are always correct; only the cache-hit path
corrupts, and only with donation. Donation only matters on device
backends (it keeps the 2x table/ring footprint out of HBM); on CPU the
arrays are host RAM and the copy is cheap. `donate_argnums_safe(...)`
therefore returns the requested argnums on TPU/GPU backends and `()` on
CPU, keeping the persistent cache sound.

`configure_compilation_cache()` is the one place that decides where the
persistent compilation cache lives.
"""
from __future__ import annotations

import os
from typing import Tuple


def donate_argnums_safe(*argnums: int) -> Tuple[int, ...]:
    """`argnums` on device backends, `()` on CPU.

    See the module docstring: deserialized persistent-cache executables
    corrupt donated buffers on XLA:CPU, so donation is only requested
    where it pays (device HBM) and is known sound.
    """
    import jax

    if jax.default_backend() == "cpu":
        return ()
    return tuple(argnums)


def donate_argnums_pinned(
    argnums: Tuple[int, ...], pinned: Tuple[int, ...] = ()
) -> Tuple[int, ...]:
    """`donate_argnums_safe` minus the argnums whose INPUT buffers the
    host may still read after dispatch — the pinned-source analysis for
    speculative era chaining.

    Donating an input aliases its buffer to an output, so the handle the
    caller still holds is dead the moment the dispatch is enqueued. That
    is fine for operands the driver has already consumed (the serial
    dispatch->readback->dispatch path reads every readback before the
    next launch), but a CHAINED dispatch launches while the previous
    era's packed-params readback is still in flight: its params operand
    is exactly that not-yet-consumed output, and donating it would race
    the async device->host copy against the aliased in-place write (JAX
    surfaces the race as a deleted-buffer error on the readback). The
    engines therefore build two jit variants of one era program — a
    serial variant donating the full operand set and a chain variant
    with the readback-pinned argnums excluded — and pick per dispatch.

    ``argnums`` is the full donation set; ``pinned`` the subset whose
    sources an in-flight readback may pin. Returns `()` on CPU exactly
    like `donate_argnums_safe` (same miscompile hazard).
    """
    pin = set(pinned)
    return tuple(a for a in donate_argnums_safe(*argnums) if a not in pin)


#: The checkout root: the persistent compilation cache's fixed home when
#: the environment names none.
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def configure_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
    nothing here names another path. Otherwise the cache lives at one
    fixed path, ``<checkout>/.jax_cache`` (the path is part of what makes
    an entry hit, so it must not move between runs).
    """
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(_REPO_ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
