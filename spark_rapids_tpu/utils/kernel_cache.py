"""Process-lifetime device-kernel cache.

The reference's device compute comes from libcudf's pre-compiled kernel
library: planning a query never compiles CUDA. The XLA analog is keeping one
``jax.jit``-wrapped callable alive per (operator kind, bound-expression
signature) for the life of the process, so re-planning or re-running a query
reuses the already-compiled program — jit's own cache then specializes per
(schema, capacity-bucket) through the batch pytree treedef.

Execs must not create ``@jax.jit`` closures inside ``execute()``: a fresh
wrapper has an empty compile cache, which recompiles the whole pipeline on
every query run. They call :func:`cached_kernel` with a structural key built
by :func:`kernel_key` from their bound expressions instead.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Callable, Dict, Optional, Tuple

import jax

from . import lockdep

_CACHE: Dict[tuple, Callable] = {}
_LOCK = lockdep.lock("kernel_cache._LOCK")
#: build_ns: host time spent constructing kernels on cache misses — the
#: kernelBuildNs source for query profiles (what XLA then compiles is
#: counted by compile/xla_events.py).
_STATS = {"hits": 0, "misses": 0, "build_ns": 0}


def kernel_key(*parts) -> tuple:
    """Build a hashable structural signature from expressions, schemas,
    dtypes, dataclasses, and plain containers/primitives."""
    return tuple(_sig_value(p) for p in parts)


def _sig_value(v) -> tuple:
    # Late import: expression depends on data/batch which must not import us
    # circularly at module load.
    from ..ops.expression import Expression

    if isinstance(v, Expression):
        return _expr_signature(v)
    if isinstance(v, (list, tuple)):
        return ("seq",) + tuple(_sig_value(x) for x in v)
    if v is None or isinstance(v, (bool, int, float, str, bytes)):
        return (type(v).__name__, v)
    if isinstance(v, frozenset):
        return ("fset",) + tuple(sorted(map(_sig_value, v)))
    if dataclasses.is_dataclass(v) and not isinstance(v, type):
        return (type(v).__qualname__,) + tuple(
            (f.name, _sig_value(getattr(v, f.name)))
            for f in dataclasses.fields(v))
    if isinstance(v, dict):
        return ("dict",) + tuple(
            (k, _sig_value(x)) for k, x in sorted(v.items()))
    return ("repr", type(v).__qualname__, repr(v))


def _expr_signature(e) -> tuple:
    extras = tuple(
        (k, _sig_value(v)) for k, v in sorted(e.__dict__.items())
        if k != "children")
    return ("expr", type(e).__qualname__, extras,
            tuple(_expr_signature(c) for c in e.children))


#: Exec attributes that are per-instance data, not structure.
#: ``_ml_registry`` (exec/ml_score.py) is the session ModelRegistry
#: handle — the (model_name, model_version) statics carry its identity.
PLAN_SIG_SKIP_ATTRS = frozenset({"children", "partitions", "_pf_cache",
                                 "_tails", "_ml_registry"})


def plan_signature(p) -> tuple:
    """Structural signature of a physical plan: node types + static params
    (expressions, schemas, goals) — NOT input shapes, which jax.jit keys on
    itself through argument avals. Shared by the whole-stage fusion and
    mesh SPMD caches."""
    extras = tuple(sorted(
        (k, _sig_value(v)) for k, v in vars(p).items()
        if k not in PLAN_SIG_SKIP_ATTRS))
    return (type(p).__name__, extras,
            tuple(plan_signature(c) for c in p.children))


def program_name(kind: str, suffix: str = "") -> str:
    """The name a device program is jitted under: ``kind`` plus a short
    readable ``suffix``, in ``[a-z0-9_]``. It becomes the XLA module's
    name (``jit_<name>``), which the profiler's ``XLA Modules`` line and
    the HLO dumps show, and it is part of JAX's compile-cache key — so it
    must be a pure function of the kernel's cache key: never of the data,
    a seed or an object id."""
    raw = f"{kind}_{suffix}" if suffix else kind
    return re.sub(r"[^a-z0-9]+", "_", raw.lower()).strip("_")


def cached_kernel(kind: str, key: tuple, builder: Callable[[], Callable],
                  static_argnums: Optional[Tuple[int, ...]] = None,
                  suffix: str = "") -> Callable:
    """Return the process-wide jitted kernel for (kind, key), building and
    wrapping ``builder()`` in ``jax.jit`` on first use. The program is
    named :func:`program_name` ``(kind, suffix)``; ``suffix`` is the
    caller's readable digest of ``key``."""
    import time
    k = (kind, key)
    with _LOCK:
        fn = _CACHE.get(k)
        if fn is not None:
            _STATS["hits"] += 1
            return fn
    t0 = time.perf_counter_ns()
    raw = builder()

    # Builders return closures called `kern`, `build`, a lambda: the
    # wrapper carries the program's name without renaming their function.
    def program(*args, **kwargs):
        return raw(*args, **kwargs)
    program.__name__ = program.__qualname__ = program_name(kind, suffix)
    # The engine's ONE sanctioned runtime jit site: the cache above
    # guarantees a single wrapper per structural key for the process
    # lifetime — exactly the dedup the jit-nested lint rule routes
    # every other module toward (it names cached_kernel as the fix).
    jitted = jax.jit(program,  # tpu-lint: ignore
                     static_argnums=static_argnums)
    build_ns = time.perf_counter_ns() - t0
    with _LOCK:
        fn = _CACHE.setdefault(k, jitted)
        if fn is jitted:
            _STATS["misses"] += 1
            _STATS["build_ns"] += build_ns
        else:
            _STATS["hits"] += 1
    return fn


def cache_stats() -> dict:
    with _LOCK:
        return dict(_STATS, entries=len(_CACHE))


def clear_cache() -> None:
    with _LOCK:
        _CACHE.clear()
        _STATS["hits"] = _STATS["misses"] = _STATS["build_ns"] = 0
