"""The build/run split: compiled checking executables reusable across runs.

Every device engine in this package keys its jit caches by ``id(tm)``
(engines/tpu_bfs.py `_LOOP_CACHE`, parallel/mesh.py) — correct for a
single checking run, but a *service* receives a fresh model instance per
request, and a fresh instance means a fresh cache key means a fresh XLA
compile, even though two `IncrementTensor(2)` instances lower to the
identical program. The compile is the dominant per-request cost for small
workloads (seconds, vs milliseconds of actual search), so a run server
amortizing it across requests is the difference between "demo" and
"serves traffic" (ROADMAP item 3).

Three layers fix this, composing with (not replacing) the per-``id(tm)``
jit caches and JAX's persistent compilation cache:

  1. `model_signature(tm)` — a stable *shape signature* for a tensor
     model: class identity + `config_digest()` + the property set. Two
     instances with equal signatures lower to the identical device
     program (the digest covers every scalar baked into `step_lanes`).
  2. the model *intern pool* — `intern_model()` maps a signature to one
     canonical `TensorModel` instance. Every downstream ``id(tm)``-keyed
     cache (era loops, seed loops, mux programs, expand programs) then
     hits naturally for same-shape requests; this is the load-bearing
     refactor, and it benefits `spawn_tpu_bfs`, `spawn_sharded_bfs`, and
     the vectorized host engines alike because they all key by the model
     instance.
  3. `CompiledCheck` + `ExecutableCache` — an LRU of warm executables
     keyed by (engine kind, signature, shape options). A `CompiledCheck`
     pins the interned model together with the engine shape (chunk /
     queue / table capacities, mux lane count), builds the jitted
     programs once (`warm()`), and hands out fresh `CheckerBuilder`s
     whose runs all reuse that one executable.

The cache sits *on top of* the persistent compilation cache: a persistent-
cache hit still pays trace + lowering per new model instance (hundreds of
ms); an executable-cache hit pays a dict lookup.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Any, Dict, Optional, Tuple

from ..tensor import TensorModel, TensorModelAdapter

__all__ = [
    "CompiledCheck",
    "ExecutableCache",
    "era_geometry",
    "intern_model",
    "model_signature",
]


def _tm_of(model: Any) -> TensorModel:
    if isinstance(model, TensorModelAdapter):
        return model.tm
    if isinstance(model, TensorModel):
        return model
    raise TypeError(
        "compiled checks require a TensorModel (or its adapter); "
        f"got {type(model).__name__}"
    )


def model_signature(model: Any) -> str:
    """Stable shape signature of a tensor model: two models with equal
    signatures lower to the identical device program.

    Covers class identity (the `step_lanes` code), `config_digest()`
    (every scalar constant baked into that code), and the property set
    (names + expectations fix the property-evaluation program and the
    rec_bits layout). Deliberately NOT ``id()``-based: equality across
    instances is the whole point.
    """
    tm = _tm_of(model)
    cls = type(tm)
    props = ",".join(
        f"{p.name}:{p.expectation.value}" for p in tm.tensor_properties()
    )
    return (
        f"{cls.__module__}.{cls.__qualname__}|{tm.config_digest()}|{props}"
    )


# Signature -> canonical instance. Bounded: each retained instance pins its
# jit caches (the per-id loop caches evict at 16, but the pool is what keeps
# an instance's id stable enough for them to hit at all).
_INTERN_CAP = 64
_INTERN: "OrderedDict[str, TensorModel]" = OrderedDict()
_INTERN_LOCK = threading.Lock()


def intern_model(model: Any) -> Tuple[TensorModel, str]:
    """Map `model` to the canonical instance for its shape signature.

    Returns ``(tm, signature)`` where `tm` is the first instance seen with
    this signature (possibly `model` itself). All ``id(tm)``-keyed jit
    caches hit across requests once every caller interns first.
    """
    tm = _tm_of(model)
    sig = model_signature(tm)
    with _INTERN_LOCK:
        cached = _INTERN.get(sig)
        if cached is not None:
            _INTERN.move_to_end(sig)
            return cached, sig
        while len(_INTERN) >= _INTERN_CAP:
            _INTERN.popitem(last=False)
        _INTERN[sig] = tm
    return tm, sig


def era_geometry(model: Any, options: Optional[Dict[str, Any]] = None) -> Dict[str, int]:
    """The solo engine shape a default run compiles at, resolved from
    `options` exactly like `CompiledCheck.warm()` / `spawn_tpu_bfs`:
    chunk clamp, coverage/sample defaults, and the proactive table
    pre-growth. Single source of truth shared by `warm()` and the
    STR6xx program lint (analysis/program.py) — if lint lowered at a
    different shape its op budgets would gate a program no run executes.
    """
    from ..obs.sample import DEFAULT_SAMPLE_K
    from ..ops import visited_set as vs
    from .tpu_bfs import _vcap

    tm = _tm_of(model)
    options = options or {}
    qcap = int(options.get("queue_capacity", 1 << 20))
    tcap = int(options.get("table_capacity", 1 << 22))
    chunk = min(
        int(options.get("chunk_size", 8192)),
        qcap // (2 * max(1, tm.max_actions)),
    )
    cov = bool(options.get("coverage", True))
    sample_k = int(options.get("sample_k", DEFAULT_SAMPLE_K))
    n_init = len(tm.init_states_array())
    vcap = _vcap(tm.max_actions, chunk)
    while n_init + vcap > vs.MAX_LOAD * tcap:
        tcap *= 2
    return {
        "chunk": chunk,
        "qcap": qcap,
        "tcap": tcap,
        "cov": cov,
        "sample_k": sample_k,
        "n_init": n_init,
    }


class CompiledCheck:
    """One warm checking executable: an interned model + engine shape.

    ``engine`` is ``"tpu_bfs"`` (the solo device engine) or
    ``"multiplex"`` (the vmapped lane-batched engine,
    engines/multiplex.py). `warm()` builds the jitted programs through the
    same ``id(tm)``-keyed caches the engines use, so a subsequent run over
    the same `CompiledCheck` re-traces nothing.
    """

    def __init__(self, engine: str, model: Any, options: Dict[str, Any]):
        self.tm, self.signature = intern_model(model)
        self.engine = engine
        self.options = dict(options)
        self.uses = 0
        self._warmed = False

    def builder(self):
        """A fresh `CheckerBuilder` over the interned model. Every run
        spawned from it shares this executable."""
        return TensorModelAdapter(self.tm).checker()

    def warm(self) -> "CompiledCheck":
        """Build (trace + lower) the device programs now, outside any
        request's latency budget. Idempotent."""
        if self._warmed:
            return self
        if self.engine == "tpu_bfs":
            from .tpu_bfs import _build_loop, _build_seed_loop

            tm = self.tm
            props = tm.tensor_properties()
            # Space sampling defaults ON at k=64 (CheckerBuilder.sample) and
            # the engine pre-grows the table proactively; era_geometry()
            # mirrors both, so the loop is traced at the shape a default
            # run actually compiles.
            g = era_geometry(tm, self.options)
            chunk, qcap, tcap = g["chunk"], g["qcap"], g["tcap"]
            cov, sample_k = g["cov"], g["sample_k"]
            _build_loop(tm, props, chunk, qcap, False, cov, sample_k=sample_k)
            _build_seed_loop(
                tm, props, chunk, qcap, tcap, False, cov, sample_k=sample_k
            )
        elif self.engine == "multiplex":
            from .multiplex import warm_lane_program

            warm_lane_program(self.tm, **self.options)
        else:
            raise ValueError(f"unknown compiled-check engine {self.engine!r}")
        self._warmed = True
        return self

    def spawn(self, builder=None, **kw):
        """Spawn a solo device run reusing this executable. Only valid for
        ``engine="tpu_bfs"`` (multiplexed batches go through
        `multiplex.run_multiplexed`)."""
        if self.engine != "tpu_bfs":
            raise ValueError(
                f"spawn() is for tpu_bfs compiled checks, not {self.engine!r}"
            )
        if builder is None:
            builder = self.builder()
        opts = {
            k: self.options[k]
            for k in ("chunk_size", "queue_capacity", "table_capacity")
            if k in self.options
        }
        opts.update(kw)
        self.uses += 1
        return builder.spawn_tpu_bfs(compiled=self, **opts)


class ExecutableCache:
    """Thread-safe LRU of `CompiledCheck`s keyed by (engine, signature,
    shape options) — the run service's executable cache, layered on top of
    the persistent compilation cache."""

    def __init__(self, capacity: int = 8):
        self.capacity = max(1, int(capacity))
        self.hits = 0
        self.misses = 0
        self._lock = threading.Lock()
        self._entries: "OrderedDict[Tuple, CompiledCheck]" = OrderedDict()

    def get(self, model: Any, engine: str, **options) -> Tuple[CompiledCheck, bool]:
        """Return ``(compiled, hit)`` for this model shape + engine shape,
        building (and warming) a new executable on miss."""
        sig = model_signature(model)
        key = (engine, sig, tuple(sorted(options.items())))
        with self._lock:
            cached = self._entries.get(key)
            if cached is not None:
                self._entries.move_to_end(key)
                self.hits += 1
                return cached, True
            self.misses += 1
        # Build outside the lock: warm() can take seconds (trace + lower)
        # and the underlying id(tm)-keyed caches already dedupe races.
        compiled = CompiledCheck(engine, model, options).warm()
        with self._lock:
            existing = self._entries.get(key)
            if existing is not None:
                return existing, False
            self._entries[key] = compiled
            while len(self._entries) > self.capacity:
                self._entries.popitem(last=False)
        return compiled, False

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {
                "hits": self.hits,
                "misses": self.misses,
                "size": len(self._entries),
                "capacity": self.capacity,
            }
