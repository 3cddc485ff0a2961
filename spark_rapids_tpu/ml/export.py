"""Zero-copy ML handoff — the ``ColumnarRdd`` analog.

The reference exports the GPU-resident columnar output of a query directly
to ML frameworks (XGBoost) with no host round trip
(``ColumnarRdd.scala:41-49``, ``InternalColumnarRddConverter.scala``; gated
by ``spark.rapids.sql.exportColumnarRdd``, RapidsConf.scala:329). The TPU
analog is stronger: a query's result batches are already ``jax.Array``
columns in HBM, so the handoff to a JAX trainer is literally passing
pytrees — :func:`feature_matrix` packs them into the dense ``[n, d]``
matrix an ML loop wants via one traced kernel, and the trainers below
never leave the device.

``DataFrame.to_device_batches()`` (plan/logical.py) is the entry point;
it requires ``spark.rapids.sql.exportColumnarRdd`` like the reference.

Compile discipline (ISSUE 14 satellite): every trainer routes its jit
through :func:`~..utils.kernel_cache.cached_kernel` keyed on the static
hyperparameters — re-training the same shape NEVER re-traces (visible to
the PR-2/PR-6 compile-once counters via ``compile_status()``), and each
build is noted in the compile manifest (compile/persist.py) when the
persistent cache is on.

Scaling (tentpole piece 2): :func:`sharded_feature_matrix` places the
exported ``(X, y, mask)`` across the device mesh (``parallel/mesh.py``
``shard_map`` idiom) and :func:`train_gbt_sharded` /
:func:`train_logistic_regression_sharded` fit data-parallel — per-shard
gradient/histogram partial sums combined with ``lax.psum`` over the
``part`` axis — so training scales past one chip's HBM while staying
numerically equivalent to the single-chip fit (tolerance of the float
reduction-order difference; exact on a one-device mesh).

Fault seams: ``ml.featureMatrix`` / ``ml.train`` register with the
deterministic fault injector (``spark.rapids.tpu.test.faultInjection.*``
``sites=ml.`` matches them all), so the ETL→train→score pipeline runs
under the same injected-OOM matrices as the rest of the engine.
"""

from __future__ import annotations

import functools
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from .. import types as T
from ..data.batch import ColumnarBatch
from ..exec.execs import _coalesce_device
from ..parallel.mesh import PART_AXIS, make_mesh, partitioned, shard_map
from ..utils.fault_injection import maybe_inject
from ..utils.kernel_cache import cached_kernel, kernel_key
from . import registry as _reg


def _note_manifest(kind: str, key: tuple, shape) -> None:
    """Record a trainer build in the compile manifest (when the
    persistent cache is on) so restarted processes see which trainer
    (hyperparams, input shape) pairs this one compiled — the PR-2
    manifest discipline extended to the ML layer. Foreign entries are
    inert to warm-up (it replays only its own fused-program hashes)."""
    from ..compile import persist
    m = persist.manifest()
    if m is None:
        return
    try:
        m.record(persist.plan_hash((kind, key)),
                 tuple(int(s) for s in shape))
    except (OSError, TypeError, ValueError):
        pass  # manifest is an aid, never a gate


def _mesh_token(mesh) -> tuple:
    """Cache-key identity of a mesh: the ordered device ids (two meshes
    of the SAME size over different devices must not share a cached
    shard_map kernel — the build closure captures the mesh object)."""
    return tuple(int(getattr(d, "id", i))
                 for i, d in enumerate(mesh.devices.flat))


def feature_matrix(batches: Sequence[ColumnarBatch],
                   feature_cols: Sequence[str],
                   label_col: Optional[str] = None,
                   dtype=jnp.float32, ctx=None):
    """Pack device batches into ``(X[cap, d], y[cap], row_mask[cap])``.

    Entirely on-device: one capacity-sized concat plus a stacking kernel —
    no host transfer anywhere (the zero-copy contract of the reference's
    ColumnarRdd; the only host traffic is one scalar sync counting the
    exported rows for the ``engine.ml`` profile section). Rows with a
    null in any used column are masked out, the standard ML semantic. The
    row count stays traced; consumers use ``row_mask`` (static shapes)
    instead of slicing.

    A query that legitimately returns ZERO batches yields a SHAPED empty
    ``(X[0, d], y[0], mask[0])`` instead of crashing the handoff — the
    downstream trainer/scorer sees an ordinary (empty) matrix."""
    batches = list(batches)
    feature_cols = list(feature_cols)
    if not feature_cols:
        raise ValueError("feature_matrix needs at least one feature column")
    maybe_inject(ctx, "ml.featureMatrix")
    d = len(feature_cols)
    if not batches:
        return (jnp.zeros((0, d), dtype), jnp.zeros((0,), dtype),
                jnp.zeros((0,), jnp.bool_))
    batch = _coalesce_device(batches)
    schema = batch.schema
    f_idx = tuple(schema.index_of(c) for c in feature_cols)
    l_idx = schema.index_of(label_col) if label_col is not None else None

    def build():
        def pack(b: ColumnarBatch):
            live = b.row_mask()
            cols = []
            valid = live
            for i in f_idx:
                c = b.columns[i]
                cols.append(c.data.astype(dtype))
                valid = valid & c.validity
            x = jnp.stack(cols, axis=1)
            if l_idx is not None:
                lc = b.columns[l_idx]
                y = lc.data.astype(dtype)
                valid = valid & lc.validity
            else:
                y = jnp.zeros(b.capacity, dtype)
            return x, y, valid
        return pack
    pack = cached_kernel("ml_feature_matrix",
                         kernel_key(schema, f_idx, l_idx, str(dtype)),
                         build)
    x, y, mask = pack(batch)
    _reg.note("export_rows", int(jax.device_get(jnp.sum(mask))))
    return x, y, mask


def sharded_feature_matrix(batches: Sequence[ColumnarBatch],
                           feature_cols: Sequence[str],
                           label_col: Optional[str] = None,
                           dtype=jnp.float32, mesh=None, ctx=None):
    """:func:`feature_matrix` placed ACROSS the device mesh for
    data-parallel training: the leading (row) dimension of ``X``/``y``/
    ``mask`` shards over the canonical ``part`` axis
    (``parallel/mesh.py``), padded so every shard is equal-sized (padding
    lanes are dead by the mask invariant). Returns
    ``(x, y, mask, mesh)`` — feed to the ``*_sharded`` trainers."""
    mesh = mesh or make_mesh()
    x, y, mask = feature_matrix(batches, feature_cols, label_col, dtype,
                                ctx=ctx)
    n_parts = int(mesh.devices.size)
    pad = (-x.shape[0]) % n_parts
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        y = jnp.pad(y, (0, pad))
        mask = jnp.pad(mask, (0, pad))
    shard = partitioned(mesh)
    return (jax.device_put(x, shard), jax.device_put(y, shard),
            jax.device_put(mask, shard), mesh)


# ---------------------------------------------------------------------------
# Logistic regression
# ---------------------------------------------------------------------------


def _logreg_fit_fn(steps: int, lr: float):
    """Single-chip masked logistic-regression fit (full-batch GD with
    feature standardization); returns (w, b, mean, scale)."""
    def fit(x, y, mask):
        d = x.shape[1]
        m = mask.astype(x.dtype)
        n = jnp.maximum(jnp.sum(m), 1.0)
        mean = jnp.sum(x * m[:, None], axis=0) / n
        var = jnp.sum(((x - mean) ** 2) * m[:, None], axis=0) / n
        xs = (x - mean) / jnp.sqrt(var + 1e-6)

        def loss_fn(params):
            w, b = params
            z = xs @ w + b
            p = jax.nn.sigmoid(z)
            eps = 1e-7
            bce = -(y * jnp.log(p + eps) + (1 - y) * jnp.log(1 - p + eps))
            return jnp.sum(bce * m) / n

        def step(_, params):
            g = jax.grad(loss_fn)(params)
            return jax.tree_util.tree_map(lambda p, gg: p - lr * gg,
                                          params, g)
        w, b = jax.lax.fori_loop(
            0, steps, step, (jnp.zeros(d, x.dtype), jnp.zeros((), x.dtype)))
        return w, b, mean, jnp.sqrt(var + 1e-6)
    return fit


def _finish_train(kind: str, key: tuple, x, out, t0: float):
    """Shared trainer epilogue: fence for an honest wall-clock, feed the
    engine.ml counters, note the build in the compile manifest."""
    jax.block_until_ready(out)
    _reg.note("train_seconds", time.perf_counter() - t0)
    _note_manifest(kind, key, x.shape)
    return out


def train_logistic_regression(x, y, mask, steps: int = 100, lr: float = 0.1,
                              ctx=None):
    """Reference on-device consumer: masked logistic regression by
    full-batch gradient descent, one cached jitted training loop (the
    BASELINE.md config-4 "query output -> JAX trainer" shape). Returns
    the fitted model dict for :func:`predict_logistic`."""
    maybe_inject(ctx, "ml.train")
    key = kernel_key("logreg", int(steps), float(lr))
    fit = cached_kernel("ml_train_logreg", key,
                        lambda: _logreg_fit_fn(int(steps), float(lr)))
    t0 = time.perf_counter()
    w, b, mean, scale = _finish_train("ml_train_logreg", key, x,
                                      fit(x, y, mask), t0)
    return {"w": w, "b": b, "mean": mean, "scale": scale}


def train_logistic_regression_sharded(x, y, mask, steps: int = 100,
                                      lr: float = 0.1, mesh=None, ctx=None):
    """Data-parallel :func:`train_logistic_regression` over the mesh:
    per-shard moment/gradient partial sums combined with ``lax.psum``
    over the ``part`` axis each step (the shard_map idiom of
    parallel/distributed.py), so the full matrix never needs to fit one
    chip. Numerically equivalent to the single-chip fit up to float
    reduction order (exact on a one-device mesh)."""
    mesh = mesh or make_mesh()
    maybe_inject(ctx, "ml.train")
    steps, lr = int(steps), float(lr)
    key = kernel_key("logreg_sharded", steps, lr, _mesh_token(mesh))

    def build():
        from jax.sharding import PartitionSpec
        spec = PartitionSpec(PART_AXIS)
        rep = PartitionSpec()

        @functools.partial(
            shard_map, mesh=mesh, in_specs=(spec, spec, spec),
            out_specs=(rep, rep, rep, rep), check_vma=False)
        def fit(xs, ys, ms):
            def psum(a):
                return jax.lax.psum(a, PART_AXIS)
            d = xs.shape[1]
            m = ms.astype(xs.dtype)
            n = jnp.maximum(psum(jnp.sum(m)), 1.0)
            mean = psum(jnp.sum(xs * m[:, None], axis=0)) / n
            var = psum(jnp.sum(((xs - mean) ** 2) * m[:, None], axis=0)) / n
            xstd = (xs - mean) / jnp.sqrt(var + 1e-6)

            def loss_sum(params):
                # LOCAL unnormalized loss; its grad psums below, and the
                # shared 1/n rescale reproduces the single-chip gradient.
                w, b = params
                p = jax.nn.sigmoid(xstd @ w + b)
                eps = 1e-7
                bce = -(ys * jnp.log(p + eps)
                        + (1 - ys) * jnp.log(1 - p + eps))
                return jnp.sum(bce * m)

            def step(_, params):
                g = jax.tree_util.tree_map(psum,
                                           jax.grad(loss_sum)(params))
                return jax.tree_util.tree_map(
                    lambda p, gg: p - lr * gg / n, params, g)
            w, b = jax.lax.fori_loop(
                0, steps, step,
                (jnp.zeros(d, xs.dtype), jnp.zeros((), xs.dtype)))
            return w, b, mean, jnp.sqrt(var + 1e-6)
        return fit
    fit = cached_kernel("ml_train_logreg_sharded", key, build)
    t0 = time.perf_counter()
    w, b, mean, scale = _finish_train("ml_train_logreg_sharded", key, x,
                                      fit(x, y, mask), t0)
    return {"w": w, "b": b, "mean": mean, "scale": scale}


def predict_logistic(model, x):
    xs = (x - model["mean"]) / model["scale"]
    return jax.nn.sigmoid(xs @ model["w"] + model["b"])


# ---------------------------------------------------------------------------
# Gradient-boosted trees (the XGBoost-on-Spark handoff, BASELINE config 4)
# ---------------------------------------------------------------------------


def _quantile_edges(xf, mask, n_bins: int):
    """Per-feature quantile bin edges over the masked matrix (global
    semantics — under GSPMD on a sharded matrix XLA computes the same
    global quantiles, so sharded and single-chip fits bin identically)."""
    xm = jnp.where(mask[:, None], xf, jnp.nan)
    qs = jnp.linspace(0.0, 1.0, n_bins + 1)[1:-1]
    edges = jnp.nanquantile(xm, qs, axis=0)          # [n_bins-1, d]
    return jnp.where(jnp.isnan(edges), jnp.inf, edges)


def _bin_features(edges, xf):
    return jax.vmap(jnp.searchsorted, in_axes=(1, 1))(
        edges, xf).astype(jnp.int32).T               # [n, d] in 0..n_bins-1


def _grad_hess(F, yf, m, objective: str):
    if objective == "binary":
        p = jax.nn.sigmoid(F)
        g = (p - yf) * m
        h = jnp.maximum(p * (1 - p), 1e-6) * m
    else:
        g = (F - yf) * m
        h = m
    return g, h


def _fit_tree(bins, g, h, n_bins: int, max_depth: int, reg_lambda: float,
              reduce):
    """One level-wise tree over pre-binned features. ``reduce`` combines
    histogram/leaf partial sums across data shards: identity on a single
    chip, ``lax.psum`` over the part axis in the sharded fit — split
    decisions are then computed REPLICATED from the global histograms
    while row→node assignment stays local."""
    n, d = bins.shape
    max_w = 1 << (max_depth - 1)
    node = jnp.zeros(n, jnp.int32)
    feats = jnp.zeros((max_depth, max_w), jnp.int32)
    ths = jnp.zeros((max_depth, max_w), jnp.int32)
    fidx = jnp.arange(d, dtype=jnp.int32)
    rows = jnp.arange(n, dtype=jnp.int32)
    for depth in range(max_depth):
        n_nodes = 1 << depth
        flat = ((node[:, None] * d + fidx[None, :]) * n_bins
                + bins)                          # [n, d]
        segs = n_nodes * d * n_bins
        G = reduce(jax.ops.segment_sum(
            jnp.broadcast_to(g[:, None], (n, d)).reshape(-1),
            flat.reshape(-1), num_segments=segs
        ).reshape(n_nodes, d, n_bins))
        H = reduce(jax.ops.segment_sum(
            jnp.broadcast_to(h[:, None], (n, d)).reshape(-1),
            flat.reshape(-1), num_segments=segs
        ).reshape(n_nodes, d, n_bins))
        Gc = jnp.cumsum(G, axis=2)[:, :, :-1]    # left sums per split
        Hc = jnp.cumsum(H, axis=2)[:, :, :-1]
        Gt = jnp.sum(G, axis=2)[:, :, None]
        Ht = jnp.sum(H, axis=2)[:, :, None]
        GR, HR = Gt - Gc, Ht - Hc
        gain = (Gc ** 2 / (Hc + reg_lambda)
                + GR ** 2 / (HR + reg_lambda)
                - Gt ** 2 / (Ht + reg_lambda))
        gain_f = gain.reshape(n_nodes, d * (n_bins - 1))
        best = jnp.argmax(gain_f, axis=1)
        bf = (best // (n_bins - 1)).astype(jnp.int32)
        bt = (best % (n_bins - 1)).astype(jnp.int32)
        feats = feats.at[depth, :n_nodes].set(bf)
        ths = ths.at[depth, :n_nodes].set(bt)
        go_right = bins[rows, bf[node]] > bt[node]
        node = node * 2 + go_right.astype(jnp.int32)
    n_leaves = 1 << max_depth
    Gl = reduce(jax.ops.segment_sum(g, node, num_segments=n_leaves))
    Hl = reduce(jax.ops.segment_sum(h, node, num_segments=n_leaves))
    leaf = -Gl / (Hl + reg_lambda)
    return feats, ths, leaf, leaf[node]


def _boost(bins, yf, m, n_trees: int, max_depth: int, n_bins: int,
           learning_rate: float, reg_lambda: float, objective: str, reduce):
    n = bins.shape[0]
    F0 = jnp.zeros(n, jnp.float32)

    def step(carry, _):
        F, = carry
        g, h = _grad_hess(F, yf, m, objective)
        feats, ths, leaf, pred = _fit_tree(bins, g, h, n_bins, max_depth,
                                           reg_lambda, reduce)
        return (F + learning_rate * pred,), (feats, ths, leaf)

    (_,), trees = jax.lax.scan(step, (F0,), None, length=n_trees)
    return trees


def train_gbt(x, y, mask, *, n_trees: int = 20, max_depth: int = 4,
              n_bins: int = 32, learning_rate: float = 0.3,
              reg_lambda: float = 1.0, objective: str = "binary", ctx=None):
    """Histogram-based gradient-boosted trees trained ENTIRELY on device —
    the consumer the reference hands query output to via XGBoost-on-Spark
    (docs/ml-integration.md; ColumnarRdd.scala:41-49 -> here a jax pytree).

    XLA-shaped like the reference's GPU hist algorithm: features quantize
    to ``n_bins`` once; every level builds (node, feature, bin)
    gradient/hessian histograms with one ``segment_sum`` scatter, split
    gains come from bin cumsums, and trees grow level-wise to a STATIC
    ``max_depth`` — no data-dependent control flow, one compiled program
    for the whole boosting loop, cached per hyperparameter signature
    (re-training the same shape never re-traces).

    objective: "binary" (logistic) or "regression" (squared error).
    Returns a model dict for :func:`predict_gbt`.
    """
    maybe_inject(ctx, "ml.train")
    hyper = (int(n_trees), int(max_depth), int(n_bins), float(learning_rate),
             float(reg_lambda), str(objective))
    key = kernel_key("gbt", *hyper)

    def build():
        nt, md, nb, lr, rl, obj = hyper

        def fit(x, y, mask):
            xf = x.astype(jnp.float32)
            m = mask.astype(jnp.float32)
            yf = y.astype(jnp.float32)
            edges = _quantile_edges(xf, mask, nb)
            bins = _bin_features(edges, xf)
            feats, ths, leaves = _boost(bins, yf, m, nt, md, nb, lr, rl,
                                        obj, lambda a: a)
            return edges, feats, ths, leaves
        return fit
    fit = cached_kernel("ml_train_gbt", key, build)
    t0 = time.perf_counter()
    edges, feats, ths, leaves = _finish_train("ml_train_gbt", key, x,
                                              fit(x, y, mask), t0)
    return {"edges": edges, "feats": feats, "ths": ths, "leaves": leaves,
            "lr": float(learning_rate), "max_depth": int(max_depth),
            "objective": str(objective)}


def train_gbt_sharded(x, y, mask, *, mesh=None, n_trees: int = 20,
                      max_depth: int = 4, n_bins: int = 32,
                      learning_rate: float = 0.3, reg_lambda: float = 1.0,
                      objective: str = "binary", ctx=None):
    """Data-parallel :func:`train_gbt` over the mesh: bin edges come from
    the GLOBAL quantiles of the sharded matrix (GSPMD — identical to the
    single-chip edges), then each boosting level builds per-shard
    (node, feature, bin) histograms and ``lax.psum``-combines them over
    the ``part`` axis, so split decisions replicate while rows never
    leave their shard (the shard_map idiom of parallel/distributed.py).
    Equivalent to the single-chip fit up to float reduction order (exact
    trees on a one-device mesh)."""
    mesh = mesh or make_mesh()
    maybe_inject(ctx, "ml.train")
    hyper = (int(n_trees), int(max_depth), int(n_bins), float(learning_rate),
             float(reg_lambda), str(objective))
    key = kernel_key("gbt_sharded", *hyper, _mesh_token(mesh))

    def build():
        from jax.sharding import PartitionSpec
        nt, md, nb, lr, rl, obj = hyper
        spec = PartitionSpec(PART_AXIS)
        rep = PartitionSpec()

        def fit(x, y, mask):
            xf = x.astype(jnp.float32)
            edges = _quantile_edges(xf, mask, nb)

            @functools.partial(
                shard_map, mesh=mesh, in_specs=(spec, spec, spec, rep),
                out_specs=(rep, rep, rep), check_vma=False)
            def boost_shards(xs, ys, ms, edges_):
                def psum(a):
                    return jax.lax.psum(a, PART_AXIS)
                bins = _bin_features(edges_, xs.astype(jnp.float32))
                return _boost(bins, ys.astype(jnp.float32),
                              ms.astype(jnp.float32), nt, md, nb, lr, rl,
                              obj, psum)
            feats, ths, leaves = boost_shards(xf, y, mask, edges)
            return edges, feats, ths, leaves
        return fit
    fit = cached_kernel("ml_train_gbt_sharded", key, build)
    t0 = time.perf_counter()
    edges, feats, ths, leaves = _finish_train("ml_train_gbt_sharded", key,
                                              x, fit(x, y, mask), t0)
    return {"edges": edges, "feats": feats, "ths": ths, "leaves": leaves,
            "lr": float(learning_rate), "max_depth": int(max_depth),
            "objective": str(objective)}


def predict_gbt(model, x):
    """Apply a :func:`train_gbt` model on device: re-bin, walk every
    tree's level arrays by gathers, sum leaf values."""
    xf = x.astype(jnp.float32)
    n = xf.shape[0]
    bins = jax.vmap(jnp.searchsorted, in_axes=(1, 1))(
        model["edges"], xf).astype(jnp.int32).T
    rows = jnp.arange(n, dtype=jnp.int32)
    max_depth = model["max_depth"]

    def one_tree(carry, tree):
        feats, ths, leaf = tree
        node = jnp.zeros(n, jnp.int32)
        for depth in range(max_depth):
            bf = feats[depth][node]
            bt = ths[depth][node]
            go_right = bins[rows, bf] > bt
            node = node * 2 + go_right.astype(jnp.int32)
        return carry + model["lr"] * leaf[node], None

    F, _ = jax.lax.scan(one_tree, jnp.zeros(n, jnp.float32),
                        (model["feats"], model["ths"], model["leaves"]))
    if model["objective"] == "binary":
        return jax.nn.sigmoid(F)
    return F
