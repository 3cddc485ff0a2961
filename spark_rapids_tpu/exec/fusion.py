"""Whole-stage fusion — the TPU answer to Spark's ``WholeStageCodegenExec``.

The reference leans on Spark's whole-stage codegen for CPU operators and on
libcudf's pre-compiled kernels for GPU ones (SURVEY.md §2.10): a query still
dispatches one kernel per operator per batch. Under XLA the natural unit is
larger. Every device operator in this engine is already a pure traced
function over batch pytrees, so an entire device subtree
(source -> filter -> project -> join -> aggregate) can be traced ONCE into a
single jitted program. XLA then fuses across operator boundaries, and —
decisive on a high-latency host<->TPU link — the host dispatches ONE program
and performs ONE device->host transfer per query instead of one per
operator-batch.

Contract:

* :func:`fusable` — True when the plan root is ``DeviceToHostExec`` over a
  columnar subtree. Non-whitelisted *columnar* subtrees (window, broadcast
  exchange, shuffle, scans...) become fusion BOUNDARIES: they execute
  eagerly outside the trace and feed the fused program as traced inputs, so
  fusion degrades gracefully instead of turning off.
* The fused callable is cached per structural plan signature (expression
  trees, schemas, static params — the :mod:`..utils.kernel_cache`
  discipline); ``jax.jit`` re-specializes per input capacity bucket through
  the pytree avals, so re-running a query never recompiles. With
  ``spark.rapids.tpu.polymorphic.enabled`` (default) boundary inputs are
  padded onto coarse capacity TIERS first (compile/ladder.py ``tier()``),
  so ONE compiled executable serves every ladder rung inside a tier —
  O(kernels) compiles instead of O(rungs x kernels); the per-rung path
  (conf off) stays as the bit-identity oracle.
* Fusion regions split by compile-cost budget: when a region's compile
  blew ``spark.rapids.tpu.fusion.compileBudgetSecs`` (recorded per plan
  hash, persisted in the compile manifest), later builds demote the most
  expensive join(s) to boundaries (compile/budget.py).
* Results return through ONE ``jax.device_get`` of ``(n_rows, overflow
  flags, guess-shrunk batch)``. If the result had more rows than the guess
  bucket, the full batch (still device-resident) downloads in a second
  round trip — the price only large collects pay.
* Join overflow flags ride the same transfer; ``TpuSession.execute``
  re-runs the query with learned exact join capacities when one trips.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa

from .. import types as T
from ..compile import budget as _budget
from ..compile import persist as _persist
from ..compile import warmup as _warmup
from ..compile.executables import FusedProgram
from ..utils import lockdep as _lockdep
from ..compile.ladder import get_ladder
from ..data.batch import ColumnarBatch, _grow_batch, _shrink_batch
from ..data.column import bucket_capacity
from ..plan.physical import ExecContext
from ..utils.kernel_cache import plan_signature as _plan_sig
from .coalesce import TpuCoalesceBatchesExec
from .execs import (DeviceToHostExec, TpuExec, TpuExpandExec, TpuFilterExec,
                    TpuHashAggregateExec, TpuLimitExec, TpuLocalLimitExec,
                    TpuProjectExec, TpuTopKExec,
                    TpuUnionExec, _coalesce_device)


class _NotFusable(Exception):
    pass


class FusedInputExec(TpuExec):
    """Leaf of a fused plan: replays pre-materialized device batches from
    ``ctx.fused_inputs`` — the fused program's traced arguments."""

    def __init__(self, index: int, schema: T.Schema):
        self.children = []
        self.index = index
        self._schema = schema

    @property
    def schema(self):
        return self._schema

    def describe(self):
        return f"FusedInput #{self.index}"

    def execute(self, ctx):
        return [iter(list(p)) for p in ctx.fused_inputs[self.index]]


#: Execs whose execute() path is fully traceable (no host syncs, no host
#: data): these are inlined into the fused program. Everything else columnar
#: becomes a boundary input.
_INLINE = (TpuProjectExec, TpuFilterExec, TpuHashAggregateExec,
           TpuCoalesceBatchesExec, TpuExpandExec,
           TpuUnionExec, TpuLimitExec, TpuLocalLimitExec,
           FusedInputExec)

#: TpuTopKExec is deliberately NOT inlined: as a boundary it keeps its
#: child subtree on the streaming path, where dense-join outputs shrink
#: to their live buckets between operators — for join-chain plans that
#: beats one fused program running every stage at full lazy capacity
#: (measured round 5: q10 fused-at-full-capacity 1073ms vs 174ms).
assert TpuTopKExec not in _INLINE


def _inline_types():
    """Joins inline too when the conf allows: one fused program per query
    instead of per-join boundary dispatches + intermediate
    materialization. Default ON; the conf exists because a fused
    multi-join program accumulates enough lax.sort stages to make one
    compile very long — boundaries amortize their per-join kernels across
    queries."""
    from .execs import TpuShuffledHashJoinExec
    return _INLINE + (TpuShuffledHashJoinExec,)


def _is_boundary(p, inline=None) -> bool:
    if isinstance(p, inline or _INLINE):
        return False
    return bool(getattr(p, "columnar", False))


def _split(plan, boundaries: List, inline=None,
           demote: frozenset = frozenset()) -> TpuExec:
    """Rebuild the device subtree with every boundary subtree replaced by a
    :class:`FusedInputExec` leaf; boundary nodes append to ``boundaries`` in
    deterministic traversal order (the fused program's argument order).
    Nodes in ``demote`` (by identity — the compile-cost budget's split
    decision, :func:`_budget_split`) become boundaries even though they
    are inlineable."""
    inline = inline or _INLINE
    if id(plan) in demote or _is_boundary(plan, inline):
        boundaries.append(plan)
        return FusedInputExec(len(boundaries) - 1, plan.schema)
    if not isinstance(plan, inline):
        raise _NotFusable(type(plan).__name__)
    kids = [_split(c, boundaries, inline, demote) for c in plan.children]
    return plan.with_children(kids) if kids else plan


def _conf_inline(conf):
    return _inline_types() if conf is not None \
        and conf.fusion_inline_joins else _INLINE


def fusable(root, conf=None) -> bool:
    if not isinstance(root, DeviceToHostExec):
        return False
    child = root.children[0]
    if not getattr(child, "columnar", False):
        return False
    try:
        _split(child, [], _conf_inline(conf))
    except _NotFusable:
        return False
    return True


_FUSED_CACHE = {}


def clear_fused_cache() -> None:
    _FUSED_CACHE.clear()


def _budget_split(device_plan, conf, base_hash: str):
    """Apply the compile-cost budget's split decision for this plan
    (compile/budget.py): returns ``(inline types, demoted node ids,
    level)``. Level 1 demotes the single largest inlined join (by inline
    subtree size — the region's most expensive boundary candidate, and
    the cut that best halves the region); level 2 demotes every join."""
    inline = _conf_inline(conf)
    level = _budget.split_level(base_hash)
    if level <= 0 or inline is _INLINE:
        return inline, frozenset(), level
    if level >= _budget.MAX_SPLIT_LEVEL:
        return _INLINE, frozenset(), level
    from .execs import TpuShuffledHashJoinExec
    joins: List[list] = []  # [inline subtree size, pre-order slot, id]

    def walk(p) -> int:
        if _is_boundary(p, inline):
            return 0
        slot = None
        if isinstance(p, TpuShuffledHashJoinExec):
            slot = len(joins)
            joins.append([0, slot, id(p)])
        size = 1 + sum(walk(c) for c in p.children)
        if slot is not None:
            joins[slot][0] = size
        return size
    walk(device_plan)
    if not joins:
        return inline, frozenset(), level
    joins.sort(key=lambda j: (-j[0], j[1]))
    return inline, frozenset({joins[0][2]}), level


def _has_inline_join(plan) -> bool:
    """True when the (already split) fused region still inlines a join —
    i.e. the compile-cost budget has a boundary left to demote."""
    from .execs import TpuShuffledHashJoinExec
    if isinstance(plan, TpuShuffledHashJoinExec):
        return True
    return any(_has_inline_join(c) for c in plan.children)


#: Distinct (input aval signature, tier) pairs the tier padding has
#: dispatched ``_grow_batch`` for. Each pair is one TINY XLA pad kernel
#: compiled on first visit of a rung — the O(rungs x boundary-schemas)
#: residue of tier padding (the fused programs themselves are O(tiers)).
#: Tracked so the compile-count gate (tests/test_compile_gate.py) can
#: ratchet it; these kernels bypass utils/kernel_cache, so the
#: ``kernels_compiled`` counter alone would never see them growing.
_PAD_PROGRAMS: set = set()


def pad_program_count() -> int:
    return len(_PAD_PROGRAMS)


def _pad_inputs_to_tiers(inputs):
    """Pad every boundary batch up to its polymorphic capacity tier
    (compile/ladder.py tier()) so the fused program's input avals — and
    therefore its compiled executable — are shared by every bucket rung
    inside a tier. Row counts stay dynamic scalar operands; padded rows
    are dead by the engine invariant, so results are bit-identical to
    the per-rung path. Returns ``(padded inputs, rows of padding)``."""
    from ..compile.executables import aval_signature
    ladder = get_ladder()
    pad_rows = 0

    def rec(x):
        nonlocal pad_rows
        if isinstance(x, tuple):
            return tuple(rec(v) for v in x)
        if not x.columns:
            return x
        tier = ladder.tier(x.capacity)
        if tier <= x.capacity:
            return x
        pad_rows += tier - x.capacity
        _PAD_PROGRAMS.add((aval_signature((x,)), tier))
        return _grow_batch(x, tier)
    return rec(inputs), pad_rows


def _build_fused(fused_plan, conf, join_growth: float, guess_rows: int,
                 join_caps=None, dense_modes=None, name: str = "fused"):
    caps = dict(join_caps or {})
    nd = dict(dense_modes or {})

    def run(inputs):
        ictx = ExecContext(conf, catalog=None)
        ictx.join_growth = join_growth
        ictx.join_caps = dict(caps)
        ictx.dense_modes = dict(nd)
        ictx.fused_inputs = inputs
        ictx.in_fusion = True
        outs = []
        for part in fused_plan.execute(ictx):
            outs.extend(part)
        flags = (jnp.stack(ictx.overflow_flags) if ictx.overflow_flags
                 else jnp.zeros((0,), jnp.bool_))
        # Inlined joins' observed match totals ride the head transfer as a
        # static-keyed dict so the session's capacity learning still works
        # (without it every overflow repeats the growth-escalation ladder,
        # and each rung is a fresh whole-program compile).
        totals = {site: t for site, t in ictx.join_totals}
        # OR per-site: one agg site reports a fail per batch + merge pass,
        # and a single True must survive to teach the dense-mode retry
        dfails: dict = {}
        for site, f in ictx.dense_fails:
            dfails[site] = f if site not in dfails else (dfails[site] | f)
        if not outs:
            # Statically empty (no batches at all) — no device work needed.
            return (None, flags, totals, dfails, None), None
        from ..ops.kernels import rowops as KR
        batch = KR.physical(_coalesce_device(outs))
        guess_cap = min(batch.capacity, bucket_capacity(guess_rows))
        shrunk = _shrink_batch(batch, guess_cap) \
            if guess_cap < batch.capacity else batch
        # The head tuple is the single downloaded transfer; the full batch
        # stays device-resident for the (rare) guess-miss second pass.
        return (batch.n_rows, flags, totals, dfails, shrunk), batch
    # The XLA module's name (``jit_fused_<8 hex of the plan hash>``): a
    # pure function of the program cache's key, as
    # utils/kernel_cache.py:program_name requires.
    run.__name__ = run.__qualname__ = name
    return jax.jit(run)


def fused_collect(root: DeviceToHostExec, ctx: ExecContext
                  ) -> Tuple[Optional[pa.Table], bool]:
    """Run a fusable plan as one compiled program.

    Returns ``(table, overflowed)``; ``table`` is None when a join's
    deferred overflow check tripped and the caller must retry with the
    learned exact join capacities (``ctx.join_caps``)."""
    device_plan = root.children[0]
    # Compile-cost budget (compile/budget.py): a plan whose fused region
    # historically blew the budget builds SPLIT — the most expensive
    # join(s) demoted to boundaries — trading one giant compile for
    # smaller cacheable ones. The base hash is the pre-split signature,
    # so history accumulates across split levels; it is computed lazily
    # (an extra full-tree signature walk) only when some plan actually
    # escalated or when this dispatch is about to compile.
    budget_secs = ctx.conf.fusion_compile_budget_secs \
        if ctx.conf is not None else 0.0
    base_hash = None
    inline, demote, level = _conf_inline(ctx.conf), frozenset(), 0
    if budget_secs > 0 and _budget.has_levels():
        base_hash = _persist.plan_hash(_plan_sig(device_plan))
        inline, demote, level = _budget_split(device_plan, ctx.conf,
                                              base_hash)
    boundaries: List = []
    fused_plan = _split(device_plan, boundaries, inline, demote)
    guess_rows = ctx.conf.collect_guess_rows
    caps = tuple(sorted(ctx.join_caps.items())) if ctx.join_caps else ()
    sig = (_plan_sig(fused_plan), float(ctx.join_growth), guess_rows, caps,
           tuple(sorted(ctx.dense_modes.items())))
    fn = _FUSED_CACHE.get(sig)
    if fn is None:
        # FusedProgram: the jitted callable plus its AOT executable table,
        # so background warm-ups (compile/warmup.py) are visible to this
        # dispatch instead of rotting in jit's invisible lower() path.
        fn = FusedProgram(
            _build_fused(fused_plan, ctx.conf, ctx.join_growth, guess_rows,
                         ctx.join_caps, ctx.dense_modes,
                         name="fused_" + _persist.plan_hash(sig)[:8]),
            label=type(device_plan).__name__)
        # Last-wins under concurrent sessions: a GIL-atomic dict store
        # of an equivalent program (same sig); the loser only wasted a
        # build. No lock on the dispatch path.
        _FUSED_CACHE[sig] = fn  # concurrency: ignore
    # Boundary subtrees run eagerly (uploads, windows, shuffles, ...); their
    # materialized batches are the fused program's positional arguments.
    # Independent boundaries materialize CONCURRENTLY on the shared
    # pipeline pool (exec/pipeline.py) — argument order and accumulator
    # merge order stay deterministic; serial when the pipeline is off or
    # a fault injector is active.
    from . import pipeline as _pipeline
    from ..metrics import trace as _trace
    tr = ctx.trace
    with _trace.span(tr, "fusion.boundaries", cat="dispatch",
                     n=len(boundaries)):
        inputs = _pipeline.materialize_boundaries(boundaries, ctx)
    reg = ctx.registry
    # Shape polymorphism (spark.rapids.tpu.polymorphic.enabled): pad the
    # boundary inputs onto coarse capacity tiers so one executable serves
    # every ladder rung in a tier. The unpadded per-rung path (conf off)
    # is the bit-identity oracle.
    polymorphic = ctx.conf is not None and ctx.conf.polymorphic_enabled
    if polymorphic:
        inputs, pad_rows = _pad_inputs_to_tiers(inputs)
        if pad_rows and reg.enabled:
            reg.add("WholeStageFusion", "polymorphicPadRows", pad_rows)
    key_compiled_before = fn.jit_compiled(inputs)
    import time as _time
    t_dispatch = _time.perf_counter_ns()
    # Lockdep blocking marker: the fused dispatch (and on first touch of
    # a signature, its trace+compile) is THE device wait of the engine —
    # holding any engine lock across it serializes every sibling thread
    # behind the device (utils/lockdep.py, docs/concurrency.md).
    with _trace.span(tr, "fusion.dispatch", cat="dispatch") as _sp, \
            _lockdep.blocking("fusion.dispatch"):
        head, full = fn(inputs)
        if tr is not None and not key_compiled_before \
                and fn.jit_compiled(inputs):
            _sp.annotate(compiled=True)
    if budget_secs > 0 and not key_compiled_before \
            and fn.jit_compiled(inputs):
        # THIS key's dispatch paid trace+compile (per-key, so a
        # concurrent thread compiling another signature on the same
        # program cannot misattribute; and unlike seen() it catches the
        # rare AOT-table fall-through): feed the observed cost back
        # into the budget so chronically expensive regions split. A
        # region with no inlined join left has nothing to demote —
        # report at the ceiling so the level cannot escalate uselessly.
        if base_hash is None:
            base_hash = _persist.plan_hash(_plan_sig(device_plan))
        compile_secs = (_time.perf_counter_ns() - t_dispatch) / 1e9
        _budget.note_compile(base_hash, compile_secs,
                             level if _has_inline_join(fused_plan)
                             else _budget.MAX_SPLIT_LEVEL)
        # Flight-recorder breadcrumb (ISSUE 13): fused compiles are the
        # single largest cold-path cost — a post-mortem dump must show
        # which plan paid one and when (Flare's amortized-compile thesis
        # verified on the warm timeline: these events vanish).
        _trace.record_event("compile.fused", plan=base_hash,
                            secs=round(compile_secs, 3))
    # Between dispatch and download: record this run's capacity rungs in
    # the compile manifest and schedule neighbor-rung AOT warm-ups, so the
    # scheduling work overlaps the device->host transfer below.
    _warmup.note_run(fn, sig, inputs, polymorphic=polymorphic)
    if reg.device_timing:
        # Device-time attribution (spark.rapids.tpu.metrics.deviceTiming):
        # fence the fused dispatch so dispatch->ready is measurable. The
        # ONLY place a fence is ever inserted — off by default, and tests
        # assert the default path stays fence-free.
        jax.block_until_ready(head)
        reg.add("WholeStageFusion", "deviceTime",
                _time.perf_counter_ns() - t_dispatch)
    with _trace.span(tr, "fusion.download", cat="download"):
        head_np = jax.device_get(head)  # ONE round trip
    n_rows_np, flags_np, totals_np, dfails_np, shrunk_np = head_np
    if reg.enabled:
        reg.add("WholeStageFusion", "opTime",
                _time.perf_counter_ns() - t_dispatch)
        reg.add(root.node_name(), "downloadBytes", _host_nbytes(head_np))
    # Surface inlined joins' observed totals and dense-fail flags for the
    # session's learning (capacity ratchet + no_dense re-planning).
    for site, t in totals_np.items():
        ctx.join_totals.append((site, t))
    for site, f in dfails_np.items():
        ctx.dense_fails.append((site, f))
    if flags_np.size and bool(np.any(flags_np)):
        return None, True
    arrow_schema = T.schema_to_arrow(root.schema)
    if n_rows_np is None:
        if reg.enabled:
            reg.add(root.node_name(), "numOutputRows", 0)
        return pa.Table.from_batches([], schema=arrow_schema), False
    n = int(n_rows_np)
    if reg.enabled:
        reg.add(root.node_name(), "numOutputRows", n)
        reg.add(root.node_name(), "numOutputBatches", 1)
    if n <= shrunk_np.capacity:
        arrays = [c.arrow_from_host(c.device_buffers(), n)
                  for c in shrunk_np.columns]
    else:
        # Guess miss: download the full device-resident batch, shrunk to the
        # now-known row bucket (second round trip; bandwidth-bound anyway).
        cap = bucket_capacity(n)
        fb = _shrink_batch(full, cap) if cap < full.capacity else full
        host = jax.device_get([c.device_buffers() for c in fb.columns])
        if reg.enabled:
            reg.add(root.node_name(), "downloadBytes", _host_nbytes(host))
        arrays = [c.arrow_from_host(bufs, n)
                  for c, bufs in zip(fb.columns, host)]
    rb = pa.RecordBatch.from_arrays(arrays, schema=arrow_schema)
    return pa.Table.from_batches([rb]).cast(arrow_schema), False


def _host_nbytes(tree) -> int:
    """Byte footprint of a downloaded host pytree (downloadBytes metric)."""
    return sum(getattr(leaf, "nbytes", 0)
               for leaf in jax.tree_util.tree_leaves(tree))


def any_overflow(ctx: ExecContext) -> bool:
    """One deferred check for the non-fused streaming path: a single stacked
    download instead of the per-join-batch syncs it replaced."""
    if not ctx.overflow_flags:
        return False
    return bool(jax.device_get(jnp.any(jnp.stack(ctx.overflow_flags))))
