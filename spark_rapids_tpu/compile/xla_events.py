"""What JAX compiled, counted from JAX's own monitoring events.

The kernel cache's ``build_ns`` (``engine.compile.kernelBuildNs``) is the
host time spent *building* kernels; the XLA compile behind the first
dispatch of each program was seen only by whoever registered a
``jax.monitoring`` listener of their own. This module registers the one
listener of the engine, once per process, and keeps process totals; a
query's share is the delta over the query (``QueryProfiler``, as for the
kernel cache's counters), so two queries that overlap in time each see
the other's compiles.

Event names are those of the installed JAX (``jax/_src/dispatch.py``,
``jax/_src/compiler.py``, ``jax/_src/compilation_cache.py``):

* ``/jax/core/compile/backend_compile_duration`` — one per program
  handed to the backend, around ``compile_or_get_cached``: the compile
  itself, or the persistent cache's lookup and load;
* ``/jax/compilation_cache/cache_hits`` — the program was loaded from
  the persistent cache;
* ``/jax/compilation_cache/cache_misses`` — the program was compiled and
  written to the persistent cache (a compile below
  ``spark.rapids.tpu.compileCache.minCompileSecs`` is not written and
  not counted; the default persists everything).
"""

from __future__ import annotations

from typing import Dict

from ..utils import lockdep

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"

_LOCK = lockdep.lock("xla_events._LOCK")
_STATS = {"compile_ns": 0, "compiles": 0, "cache_hits": 0,
          "cache_misses": 0}
_INSTALLED = False


def _on_duration(event: str, secs: float, **_kw) -> None:
    if event == BACKEND_COMPILE_EVENT:
        with _LOCK:
            _STATS["compile_ns"] += int(secs * 1e9)
            _STATS["compiles"] += 1


def _on_event(event: str, **_kw) -> None:
    if event == CACHE_HIT_EVENT:
        with _LOCK:
            _STATS["cache_hits"] += 1
    elif event == CACHE_MISS_EVENT:
        with _LOCK:
            _STATS["cache_misses"] += 1


def install() -> None:
    """Register the listeners (idempotent; ``compile.configure`` calls it
    at session construction). JAX offers no way to take one listener out
    again, so they stay for the life of the process."""
    global _INSTALLED
    import jax
    with _LOCK:
        if _INSTALLED:
            return
        _INSTALLED = True
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)


def stats() -> Dict[str, int]:
    """Process totals since :func:`install`."""
    with _LOCK:
        return dict(_STATS)
