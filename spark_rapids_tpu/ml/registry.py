"""Session-scoped ML model registry — trained models as first-class,
SPILLABLE engine citizens (the ml-integration tentpole, pieces 1 and 3).

A registered model is not a Python object floating beside the engine: its
array leaves are packed into one byte-exact device buffer and registered
in the session's :class:`~..memory.spill.BufferCatalog` with a QoS-stamped
owner (``spark.rapids.tpu.tenantId``), exactly like a query's build table.
That buys the whole memory discipline for free:

* a concurrent query's OOM-retry ladder (memory/retry.py) can evict a
  cold model to host/disk through the PR-11 spill state machine, in QoS
  victim order — training/model residency that "steals" HBM resolves
  through spill + retry instead of crashing either side;
* ``spill_tenant_over_budget`` (the serving layer's budget enforcement)
  sees model bytes as the owning tenant's residency;
* scoring a spilled model restores it through ``acquire_batch``'s tier
  climb, wrapped in the retry taxonomy (site ``ml.modelAcquire``).

The registry also carries the **feature-schema contract**: every model
records how many features it consumes (``n_features``), and both the
DataFrame API (`with_model_score`) and the plan-lint pass
(analysis/plan_lint.py) verify the operator's feature list against it —
a mismatched handoff fails at plan time, not as a shape error mid-query.

Training sets (the ``(X, y, mask)`` pytree from
:func:`~.export.feature_matrix`) get the same treatment via
:meth:`ModelRegistry.put_training` / :meth:`ModelRegistry.take_training`,
so exported matrices awaiting a trainer are spillable too.

Packing is byte-exact: every array leaf is bitcast to an ``int8`` lane
(``jax.lax.bitcast_convert_type``), concatenated, and padded onto a
bucket-ladder capacity — spill/restore round-trips reproduce the model
bit for bit (asserted by tests/test_ml_pipeline.py).

Observability: module-wide counters (export rows, train seconds, model
bytes, registrations) feed the ``engine.ml`` section of every
QueryProfile (metrics/profile.py, docs/monitoring.md).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import types as T
from ..data.batch import ColumnarBatch
from ..data.column import DeviceColumn, bucket_capacity
from ..utils import lockdep

# ---------------------------------------------------------------------------
# Process-wide ML stats (engine.ml QueryProfile section reads deltas)
# ---------------------------------------------------------------------------

_STATS_LOCK = lockdep.lock("ml_registry._STATS_LOCK")
_STATS = {"export_rows": 0, "train_seconds": 0.0, "model_bytes": 0,
          "models_registered": 0}


def stats() -> dict:
    """Snapshot of the process-wide ML counters (deltas become the
    ``engine.ml`` QueryProfile section)."""
    with _STATS_LOCK:
        return dict(_STATS)


def note(name: str, amount) -> None:
    with _STATS_LOCK:
        _STATS[name] = _STATS.get(name, 0) + amount


# ---------------------------------------------------------------------------
# Byte-exact pytree packing (one int8 lane per model / training set)
# ---------------------------------------------------------------------------

_PACK_SCHEMA = T.Schema([T.StructField("ml_bytes", T.BYTE, False)])

#: model kinds the score operator understands; each names its predict twin
#: in ml/export.py.
KINDS = ("gbt", "logistic")


def infer_kind(model: dict) -> str:
    if "feats" in model and "leaves" in model:
        return "gbt"
    if "w" in model and "b" in model:
        return "logistic"
    raise ValueError(
        "cannot infer model kind: expected a train_gbt dict (feats/leaves) "
        "or a train_logistic_regression dict (w/b)")


def _is_array(v) -> bool:
    return isinstance(v, (jax.Array, np.ndarray)) or (
        hasattr(v, "shape") and hasattr(v, "dtype"))


def pack_arrays(arrays: Dict[str, jax.Array]
                ) -> Tuple[ColumnarBatch, tuple, int]:
    """Pack named array leaves into ONE int8 device column (byte-exact
    bitcast), padded to a bucket-ladder capacity. Returns
    ``(batch, leaf_meta, payload_bytes)`` where ``leaf_meta`` is the
    static recipe :func:`unpack_arrays` rebuilds the pytree from."""
    metas, parts, total = [], [], 0
    for key in sorted(arrays):
        a = jnp.asarray(arrays[key])
        orig_dtype = str(a.dtype)
        if a.dtype == jnp.bool_:
            a = a.astype(jnp.int8)
        flat = a.reshape(-1)
        itemsize = np.dtype(a.dtype).itemsize
        nbytes = int(flat.size) * itemsize
        metas.append((key, tuple(int(s) for s in np.shape(arrays[key])),
                      orig_dtype, nbytes))
        if nbytes == 0:
            continue
        b = flat.astype(jnp.int8) if itemsize == 1 else \
            jax.lax.bitcast_convert_type(flat, jnp.int8).reshape(-1)
        parts.append(b)
        total += nbytes
    cap = bucket_capacity(max(total, 1))
    data = jnp.zeros(cap, jnp.int8)
    if parts:
        flat_all = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
        data = data.at[:total].set(flat_all)
    validity = jnp.arange(cap, dtype=jnp.int32) < total
    batch = ColumnarBatch(
        (DeviceColumn(data=data, validity=validity, dtype=T.BYTE),),
        jnp.asarray(total, jnp.int32), _PACK_SCHEMA)
    return batch, tuple(metas), total


def unpack_arrays(batch: ColumnarBatch, leaf_meta: tuple
                  ) -> Dict[str, jax.Array]:
    """Rebuild the named leaves from a packed batch (bit-exact inverse of
    :func:`pack_arrays`; survives any number of spill/restore trips)."""
    flat = batch.columns[0].data
    out: Dict[str, jax.Array] = {}
    off = 0
    for key, shape, dtype_s, nbytes in leaf_meta:
        want_bool = dtype_s == "bool"
        dt = np.dtype("int8" if want_bool else dtype_s)
        if nbytes == 0:
            arr = jnp.zeros(shape, jnp.bool_ if want_bool else dt)
            out[key] = arr
            continue
        seg = jax.lax.slice(flat, (off,), (off + nbytes,))
        if dt.itemsize == 1:
            arr = seg.astype(dt)
        else:
            arr = jax.lax.bitcast_convert_type(
                seg.reshape(-1, dt.itemsize), dt)
        if want_bool:
            arr = arr.astype(jnp.bool_)
        out[key] = arr.reshape(shape)
        off += nbytes
    return out


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ModelMeta:
    """Static contract of one registered model: everything the score
    operator and the plan-lint pass need WITHOUT touching the device."""

    name: str
    kind: str                   # "gbt" | "logistic"
    version: int                # bumps on every re-register of the name
    n_features: int             # the feature-schema contract
    static: tuple               # sorted (key, value) non-array model fields
    leaves: tuple               # pack_arrays leaf_meta
    payload_bytes: int          # exact packed bytes (pre-padding)
    device_bytes: int           # HBM footprint of the padded buffer
    buffer_id: int              # BufferCatalog id


def _n_features(kind: str, arrays: Dict[str, jax.Array]) -> int:
    if kind == "gbt":
        return int(arrays["edges"].shape[1])
    return int(arrays["w"].shape[0])


class ModelRegistry:
    """Session-scoped registry of trained models + parked training sets
    (see module doc). Shared by ``with_conf``-derived sessions, so a
    traced or differently-gated twin scores the same models."""

    def __init__(self, session):
        self._session = session
        self._catalog = session.device_manager.catalog
        self._lock = lockdep.lock("ModelRegistry._lock")
        self._models: Dict[str, ModelMeta] = {}
        self._versions: Dict[str, int] = {}
        #: name -> (buffer_id, leaf_meta) of parked training pytrees
        self._training: Dict[str, Tuple[int, tuple]] = {}
        from ..config import TPU_ML_MAX_MODELS
        self._max_models = int(session.conf.get(TPU_ML_MAX_MODELS))

    # -- helpers ------------------------------------------------------------
    def _owner(self, ctx=None):
        """QoS identity stamped on every registry buffer: the running
        query's tag when available, else a tag for the session tenant —
        either way the catalog's victim selection sees model/training
        bytes as THIS tenant's residency (docs/fault-tolerance.md)."""
        qos = getattr(ctx, "qos", None)
        if qos is not None:
            return qos
        from ..config import TENANT_ID
        from ..memory.spill import QosTag
        try:
            tenant = self._session.conf.get(TENANT_ID) or ""
        except (AttributeError, TypeError):
            tenant = ""
        return QosTag(tenant=tenant)

    def _acquire_ctx(self, ctx):
        """A context the retry combinator can drive spill/backoff
        through; callers outside a query (train scripts) get a bare one
        over the session conf + catalog."""
        if ctx is not None:
            return ctx
        from ..plan.physical import ExecContext
        return ExecContext(self._session.conf, catalog=self._catalog)

    def _acquire_packed(self, buffer_id: int, site: str, ctx) -> ColumnarBatch:
        """Unspill a registry buffer through the retry taxonomy: an OOM
        during the tier-climb restore spills lower-priority buffers and
        retries (PR-4 ladder over the PR-11 state machine)."""
        from ..memory import retry as R
        actx = self._acquire_ctx(ctx)
        [batch] = R.with_retry(
            actx, site, buffer_id,
            lambda bid: self._catalog.acquire_batch(bid),
            split=None, node="ModelRegistry")
        return batch

    # -- models -------------------------------------------------------------
    def register(self, name: str, model: dict, kind: Optional[str] = None,
                 ctx=None) -> ModelMeta:
        """Register (or replace) ``name``. The model's array leaves move
        into one spillable catalog buffer; non-array fields (lr, depth,
        objective) become static metadata. Returns the new meta."""
        from ..metrics import trace as TR
        from ..utils.fault_injection import maybe_inject
        maybe_inject(ctx, "ml.registerModel")
        kind = kind or infer_kind(model)
        if kind not in KINDS:
            raise ValueError(f"unknown model kind {kind!r}; one of {KINDS}")
        arrays = {k: v for k, v in model.items() if _is_array(v)}
        static = {k: v for k, v in model.items() if not _is_array(v)}
        for k, v in static.items():
            if not isinstance(v, (str, int, float, bool, type(None))):
                raise TypeError(
                    f"model field {k!r} is neither an array leaf nor a "
                    f"primitive ({type(v).__name__}); registry models are "
                    "pytrees of arrays plus scalar hyperparameters")
        # Bound pre-check BEFORE any device work: a refused register must
        # be free and side-effect-less (packing + register_batch can spill
        # a neighbor's buffers to make room). Re-checked after the insert
        # races below.
        with self._lock:
            self._check_bound_locked(name)
        batch, leaf_meta, payload = pack_arrays(arrays)
        device_bytes = batch.device_size_bytes
        bid = self._catalog.register_batch(batch, owner=self._owner(ctx))
        old = None
        meta = None
        with self._lock:
            if name in self._models \
                    or len(self._models) < self._max_models:
                version = self._versions.get(name, 0) + 1
                self._versions[name] = version
                old = self._models.get(name)
                meta = ModelMeta(
                    name=name, kind=kind, version=version,
                    n_features=_n_features(kind, arrays),
                    static=tuple(sorted(static.items())), leaves=leaf_meta,
                    payload_bytes=payload, device_bytes=device_bytes,
                    buffer_id=bid)
                self._models[name] = meta
        if meta is None:
            # Lost the pre-check race (a concurrent register filled the
            # registry while we packed): release the just-registered
            # buffer before surfacing — no leaked catalog entries.
            self._catalog.free(bid)
            raise ValueError(
                f"model registry is full ({self._max_models} models); "
                "drop one or raise "
                "spark.rapids.tpu.ml.maxRegisteredModels")
        if old is not None:
            self._catalog.free(old.buffer_id)
        note("model_bytes", device_bytes - (old.device_bytes if old else 0))
        note("models_registered", 1)
        TR.record_event("ml.registerModel", model=name, kind=kind,
                        bytes=device_bytes)
        return meta

    def _check_bound_locked(self, name: str) -> None:
        if name not in self._models \
                and len(self._models) >= self._max_models:
            raise ValueError(
                f"model registry is full ({self._max_models} models); "
                "drop one or raise "
                "spark.rapids.tpu.ml.maxRegisteredModels")

    def meta_maybe(self, name: str) -> Optional[ModelMeta]:
        with self._lock:
            return self._models.get(name)

    def meta(self, name: str) -> ModelMeta:
        m = self.meta_maybe(name)
        if m is None:
            raise KeyError(
                f"model {name!r} is not registered on this session "
                f"(registered: {self.names()}); call "
                "session.ml_models.register(name, model) first")
        return m

    def names(self) -> list:
        with self._lock:
            return sorted(self._models)

    def drop(self, name: str) -> None:
        with self._lock:
            meta = self._models.pop(name, None)
        if meta is not None:
            self._catalog.free(meta.buffer_id)
            note("model_bytes", -meta.device_bytes)

    def acquire(self, name: str, ctx=None) -> Tuple[ModelMeta, dict]:
        """The model's pytree, device-resident (unspilled if needed via
        the retry ladder; site ``ml.modelAcquire``). The returned leaves
        are independent slices — the catalog buffer may spill again
        immediately without affecting them.

        Safe against a CONCURRENT re-register of the same name: that
        frees the version we read between the meta lookup and the
        catalog acquire, which surfaces as a gone-buffer error — the
        loop re-reads and scores the CURRENT version (the same
        latest-wins semantic the planner's plan-time version resolution
        gives). A dropped name surfaces as :meth:`meta`'s KeyError."""
        for _ in range(8):
            meta = self.meta(name)
            try:
                batch = self._acquire_packed(meta.buffer_id,
                                             "ml.modelAcquire", ctx)
            except (KeyError, AssertionError):
                cur = self.meta_maybe(name)
                if cur is None:
                    # Concurrent drop(): surface the friendly model-name
                    # KeyError, not the catalog's internal buffer-id one.
                    self.meta(name)
                if cur is not None and cur.buffer_id != meta.buffer_id:
                    continue  # re-registered mid-acquire: retry on latest
                raise
            model = dict(unpack_arrays(batch, meta.leaves))
            model.update(dict(meta.static))
            return meta, model
        raise RuntimeError(
            f"model {name!r} was re-registered continuously during "
            "acquire (8 attempts)")

    # -- training sets ------------------------------------------------------
    def put_training(self, name: str, arrays: tuple, ctx=None) -> int:
        """Park an exported training pytree (X, y, mask, ...) as ONE
        spillable catalog buffer so matrices awaiting a trainer are
        memory-QoS citizens too. Returns the device byte footprint."""
        from ..utils.fault_injection import maybe_inject
        maybe_inject(ctx, "ml.putTraining")
        named = {f"a{i}": a for i, a in enumerate(arrays)}
        batch, leaf_meta, _payload = pack_arrays(named)
        bid = self._catalog.register_batch(batch, owner=self._owner(ctx))
        with self._lock:
            old = self._training.pop(name, None)
            self._training[name] = (bid, leaf_meta)
        if old is not None:
            self._catalog.free(old[0])
        return batch.device_size_bytes

    def take_training(self, name: str, ctx=None) -> tuple:
        """Reclaim a parked training pytree (restoring through the retry
        ladder; site ``ml.takeTraining``) and release its buffer."""
        with self._lock:
            entry = self._training.pop(name, None)
            parked = sorted(self._training)
        if entry is None:
            raise KeyError(f"no training set {name!r} parked "
                           f"(parked: {parked})")
        bid, leaf_meta = entry
        batch = self._acquire_packed(bid, "ml.takeTraining", ctx)
        out = unpack_arrays(batch, leaf_meta)
        self._catalog.free(bid)
        return tuple(out[f"a{i}"] for i in range(len(out)))
