"""Persistent XLA executable cache + compile manifest.

Two cooperating pieces of cross-process memory:

1. **JAX persistent compilation cache** — XLA executables keyed by HLO
   hash. The package turns it on at import and decides its directory
   (``spark_rapids_tpu.COMPILE_CACHE_DIR``: ``JAX_COMPILATION_CACHE_DIR``
   where set, else a fixed directory in the checkout). With it, a
   restarted process pays deserialization (milliseconds) instead of
   compilation (seconds to minutes per program on a TPU) for every
   program any previous process built.

2. **Compile manifest** (``tpu_compile_manifest.json`` in the same dir) —
   the engine-level index the JAX cache lacks: which (plan signature,
   capacity vector) pairs were actually executed. The JAX cache can only
   answer "have I compiled this exact HLO"; the manifest lets a NEW
   process *ask the right questions* — warm-up replays the recorded rungs
   through AOT lowering (:mod:`.warmup`), each of which then hits the
   on-disk executable, so cold start collapses to tracing time.

This module only *adds* to what the package set up: an explicit
``spark.rapids.tpu.compileCache.dir`` moves the cache (and the manifest)
there unless ``JAX_COMPILATION_CACHE_DIR`` placed it from outside, and
disabling the conf key restores exactly what this module changed — it
never turns off or moves a cache it did not itself configure. The
environment kill-switch ``JAX_ENABLE_COMPILATION_CACHE=false`` (jax's own)
always wins: no manifest is kept for a cache that cannot persist.
Configuration failures degrade to disabled, never to an error: a broken
cache must not break queries.
"""

from __future__ import annotations

import hashlib
import json
import os
from typing import Callable, Dict, List, Optional, Tuple

from ..utils import lockdep

_LOCK = lockdep.lock("persist._LOCK", io_ok=True)
_STATUS: Dict[str, object] = {"enabled": False, "reason": "not configured"}
_MANIFEST: Optional["CompileManifest"] = None
#: True while this process's jax config carries this module's overrides,
#: and what they replaced (key -> previous value): a later disable
#: restores those instead of switching the cache off.
_APPLIED = False
_SAVED: Dict[str, object] = {}

#: Bounds on the manifest so it stays a small index, not a log.
_MAX_PLANS = 256
_MAX_VECTORS_PER_PLAN = 8

MANIFEST_NAME = "tpu_compile_manifest.json"


def _env_killed() -> bool:
    return os.environ.get("JAX_ENABLE_COMPILATION_CACHE", "").strip().lower() \
        in ("false", "0", "no")


def default_cache_dir() -> str:
    """The directory the package chose at import (the one place that
    decides: ``spark_rapids_tpu/__init__.py``)."""
    from .. import COMPILE_CACHE_DIR
    return COMPILE_CACHE_DIR


def configure(conf) -> Dict[str, object]:
    """Apply the conf's compile-cache keys to the process. Idempotent;
    returns the resulting status dict (also available via :func:`status`)."""
    global _MANIFEST, _APPLIED
    from ..config import (COMPILE_CACHE_DIR, COMPILE_CACHE_ENABLED,
                          COMPILE_CACHE_MIN_COMPILE_SECS)
    with _LOCK:
        if not conf.get(COMPILE_CACHE_ENABLED):
            _deactivate_locked("disabled by conf")
            return dict(_STATUS)
        if _env_killed():
            _deactivate_locked(
                "JAX_ENABLE_COMPILATION_CACHE=false in environment")
            return dict(_STATUS)
        cache_dir = conf.get(COMPILE_CACHE_DIR) or default_cache_dir()
        try:
            os.makedirs(cache_dir, exist_ok=True)
            _apply_jax_config(cache_dir,
                              conf.get(COMPILE_CACHE_MIN_COMPILE_SECS))
            _APPLIED = True
        except Exception as e:  # noqa: BLE001 - cache must never break queries
            _deactivate_locked(f"jax cache config failed: {e}")
            return dict(_STATUS)
        if _MANIFEST is None or _MANIFEST.path != \
                os.path.join(cache_dir, MANIFEST_NAME):
            _MANIFEST = CompileManifest(os.path.join(cache_dir,
                                                     MANIFEST_NAME))
        _STATUS.update(enabled=True, reason="", dir=cache_dir)
        return dict(_STATUS)


def _deactivate_locked(reason: str) -> None:
    """Drop the manifest and restore the jax config values an earlier
    :func:`configure` overrode. The package-level cache stays as the
    package (or ``JAX_COMPILATION_CACHE_DIR``) placed it."""
    global _MANIFEST, _APPLIED
    if _APPLIED:
        # The compile layer is process-global and follows the most
        # recently constructed session's conf: undoing what an earlier
        # session configured is allowed, but never silent.
        import warnings
        warnings.warn(
            f"compile-cache conf overrides reverted ({reason}); they were "
            "applied by an earlier session's conf — the compile layer is "
            "process-global (docs/compile-cache.md)", stacklevel=4)
        try:
            _revert_jax_config()
        except Exception:  # noqa: BLE001 - cache must never break queries
            pass
        _APPLIED = False
    _STATUS.clear()
    _STATUS.update(enabled=False, reason=reason)
    _MANIFEST = None


def _revert_jax_config() -> None:
    import jax
    while _SAVED:
        key, value = _SAVED.popitem()
        jax.config.update(key, value)


def _apply_jax_config(cache_dir: str, min_secs: float) -> None:
    import jax
    updates = {
        "jax_persistent_cache_min_compile_time_secs": float(min_secs),
        # Entry size floor of 0: tiny shrink/transition kernels recompile
        # per rung too.
        "jax_persistent_cache_min_entry_size_bytes": 0,
    }
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        updates["jax_compilation_cache_dir"] = cache_dir
    for key, value in updates.items():
        _SAVED.setdefault(key, getattr(jax.config, key))
        jax.config.update(key, value)


def status() -> Dict[str, object]:
    with _LOCK:
        return dict(_STATUS)


def manifest() -> Optional["CompileManifest"]:
    """The configured manifest, or None when the cache is off."""
    with _LOCK:
        return _MANIFEST


def plan_hash(plan_sig: tuple) -> str:
    """Stable short hash of a structural plan signature
    (utils.kernel_cache.plan_signature output: type names + primitives,
    deterministic across processes)."""
    return hashlib.sha256(repr(plan_sig).encode()).hexdigest()[:16]


def _to_jsonable(v):
    if isinstance(v, (list, tuple)):
        return [_to_jsonable(x) for x in v]
    return int(v)


def _to_hashable(v):
    if isinstance(v, list):
        return tuple(_to_hashable(x) for x in v)
    return int(v)


class CompileManifest:
    """Tiny crash-safe index: plan hash -> capacity vectors executed.

    A capacity vector mirrors the nesting of a fused program's boundary
    inputs (boundary -> partition -> batch) with each batch replaced by
    its integer row capacity — exactly what :mod:`.warmup` needs to
    rebuild abstract inputs for another rung. Writes are atomic
    (tmp + rename); a corrupt or missing file loads as empty.
    """

    def __init__(self, path: str):
        self.path = path
        self._lock = lockdep.lock("CompileManifest._lock", io_ok=True)
        self._plans: Dict[str, List[tuple]] = {}
        #: plan hash -> fusion split level (compile/budget.py): plans
        #: whose fused region historically blew the compile budget.
        self._levels: Dict[str, int] = {}
        self._load()

    def _load(self) -> None:
        try:
            with open(self.path, encoding="utf-8") as f:
                data = json.load(f)
            for h, vecs in data.get("plans", {}).items():
                self._plans[str(h)] = [_to_hashable(v) for v in vecs]
            for h, lvl in data.get("split_levels", {}).items():
                self._levels[str(h)] = int(lvl)
        except (OSError, ValueError):
            self._plans = {}
            self._levels = {}

    def record(self, plan_hash_: str, cap_vector: tuple) -> bool:
        """Remember that ``plan_hash_`` ran with ``cap_vector``. Returns
        True (and flushes) when the pair is new."""
        with self._lock:
            vecs = self._plans.setdefault(plan_hash_, [])
            if cap_vector in vecs:
                return False
            vecs.append(cap_vector)
            del vecs[:-_MAX_VECTORS_PER_PLAN]
            while len(self._plans) > _MAX_PLANS:
                self._plans.pop(next(iter(self._plans)))
            self._flush_locked()
            return True

    def vectors_for(self, plan_hash_: str,
                    canonicalize: Optional[Callable] = None) -> List[tuple]:
        """Recorded capacity vectors for a plan. With ``canonicalize``
        (the polymorphic tier mapper — warmup.py passes capacity->tier),
        vectors are mapped through it and DEDUPED post-map: a manifest
        written by per-rung processes holds one vector per rung, and
        replaying those raw would recompile the same polymorphic
        executable once per recorded rung on every restart."""
        with self._lock:
            vecs = list(self._plans.get(plan_hash_, []))
        if canonicalize is None:
            return vecs
        out: List[tuple] = []
        seen = set()
        for v in vecs:
            cv = canonicalize(v)
            if cv not in seen:
                seen.add(cv)
                out.append(cv)
        return out

    def split_level(self, plan_hash_: str) -> int:
        """Fusion split level recorded for a plan (compile/budget.py)."""
        with self._lock:
            return int(self._levels.get(plan_hash_, 0))

    def has_split_levels(self) -> bool:
        with self._lock:
            return bool(self._levels)

    def record_split_level(self, plan_hash_: str, level: int) -> None:
        """Remember that ``plan_hash_``'s fused region blew the compile
        budget and future builds should split at ``level``."""
        with self._lock:
            if self._levels.get(plan_hash_) == int(level):
                return
            self._levels[plan_hash_] = int(level)
            while len(self._levels) > _MAX_PLANS:
                self._levels.pop(next(iter(self._levels)))
            self._flush_locked()

    def _flush_locked(self) -> None:
        data = {
            "comment": "Compile manifest: capacity vectors each plan "
                       "signature has executed with; warm-up replays "
                       "them after restart (docs/compile-cache.md). "
                       "split_levels records plans whose fused region "
                       "blew the compile budget (compile/budget.py).",
            "plans": {h: [_to_jsonable(v) for v in vecs]
                      for h, vecs in self._plans.items()},
        }
        if self._levels:
            data["split_levels"] = dict(self._levels)
        tmp = self.path + ".tmp"
        try:
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump(data, f, indent=1)
            os.replace(tmp, self.path)
        except OSError:
            pass  # manifest is an optimization; never fail the query


def reset_for_tests() -> None:
    global _MANIFEST, _APPLIED
    with _LOCK:
        _MANIFEST = None
        _APPLIED = False
        _SAVED.clear()
        _STATUS.clear()
        _STATUS.update(enabled=False, reason="not configured")
