"""The compile-once layer — everything that turns "XLA compiles a program
per (plan, shape)" from a cold-start tax into a managed, warmable cache.

The reference engine never compiles device code at query time: libcudf ships
pre-compiled kernels, so the first run of a query is as fast as the tenth.
Under XLA the first run of every (plan shape, capacity bucket) pays a full
compile — seconds on a remote-compile TPU backend — which is the dominant
cold-start cost for a serving system that sees the same query shapes from
millions of users. This package is the analog of the reference's
"kernels are already compiled" property, built from five pieces:

* :mod:`.ladder` — the bucket ladder: every dynamic size in the engine
  (row capacities, string byte capacities) is rounded onto one shared,
  configurable geometric ladder, which bounds the number of distinct
  programs XLA can ever be asked for.
* :mod:`.persist` — the persistent executable cache: wires JAX's on-disk
  compilation cache to the session conf, and keeps a small manifest of
  (plan hash -> capacity vectors) so a NEW process knows which rungs the
  previous one ran.
* :mod:`.executables` — the in-process program cache: one jitted callable
  per plan signature plus AOT-compiled executables per input-aval
  signature, so warm-up work is visible to the dispatch path (jit's own
  lower().compile() does not populate its tracing cache).
* :mod:`.warmup` — AOT warm-up: builds abstract (ShapeDtypeStruct) batches
  at neighbor ladder rungs and compiles them in the background, so a
  growing dataset never stalls at a rung boundary and a restarted process
  re-compiles everything it served yesterday before the first query.
* :mod:`.xla_events` — the engine's one ``jax.monitoring`` listener:
  what JAX compiled and what the persistent cache served, as process
  totals the query profile takes deltas of.

See docs/compile-cache.md for the user-facing story.
"""

from __future__ import annotations

from .ladder import BucketLadder, bucket_capacity, get_ladder, set_ladder

__all__ = [
    "BucketLadder",
    "bucket_capacity",
    "get_ladder",
    "set_ladder",
    "configure",
]


def configure(conf) -> dict:
    """Configure every compile-layer global from a :class:`..config.TpuConf`
    snapshot: the process bucket ladder, the persistent XLA cache, and the
    warm-up worker. Called by ``TpuSession`` at construction; idempotent.

    Returns a status dict (ladder + persistent-cache state) for
    diagnostics."""
    from . import budget as _budget
    from . import persist as _persist
    from . import warmup as _warmup
    from . import xla_events as _xla_events
    _xla_events.install()
    ladder = _ladder_from_conf(conf)
    if ladder != get_ladder() and _programs_exist():
        # Capacities bake into compiled programs: changing the ladder
        # mid-process (e.g. with_conf on a live session) silently carries
        # BOTH rung populations — the duplication this layer exists to
        # prevent. Allowed, but never silent.
        import warnings
        warnings.warn(
            "bucket ladder reconfigured after programs were compiled "
            f"({get_ladder()} -> {ladder}); existing sessions will "
            "re-bucket onto the new rungs and already-compiled programs "
            "for the old rungs stay resident (docs/compile-cache.md)",
            stacklevel=3)
    set_ladder(ladder)
    cache_status = _persist.configure(conf)
    _warmup.configure(conf)
    _budget.configure(conf)
    return {"ladder": ladder, "persistent_cache": dict(cache_status)}


def _programs_exist() -> bool:
    from ..exec import fusion
    from ..utils import kernel_cache
    return bool(fusion._FUSED_CACHE) \
        or kernel_cache.cache_stats()["entries"] > 0


def _ladder_from_conf(conf) -> BucketLadder:
    from ..config import (POLYMORPHIC_TIER_GROWTH, TPU_CAPACITY_BUCKETING,
                          TPU_LADDER_GROWTH, TPU_LADDER_MAX_CAPACITY,
                          TPU_MIN_CAPACITY)
    return BucketLadder(
        min_capacity=conf.get(TPU_MIN_CAPACITY),
        growth=conf.get(TPU_LADDER_GROWTH),
        max_capacity=conf.get(TPU_LADDER_MAX_CAPACITY),
        enabled=conf.get(TPU_CAPACITY_BUCKETING),
        tier_growth=conf.get(POLYMORPHIC_TIER_GROWTH),
    )
