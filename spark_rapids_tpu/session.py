"""TpuSession — the user entry point (SparkSession + Plugin bootstrap analog).

The reference's lifecycle: driver plugin fixes configs and installs the SQL
extension; executor plugin initializes the device, memory pool, and semaphore
(Plugin.scala:104-143, GpuDeviceManager.scala:120). Standalone, the session
owns all of that: it holds the :class:`TpuConf`, initializes the device
runtime once, builds DataFrames, and runs plans through the planner +
TpuOverrides rewrite.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import pyarrow as pa

from . import types as T
from .config import TpuConf
from .data.batch import HostBatch
from .memory.device_manager import DeviceManager
from .plan import logical as L
from .plan import physical as P
from .plan.overrides import TpuOverrides


class DataFrameReader:
    def __init__(self, session: "TpuSession"):
        self._session = session
        self._options: Dict[str, str] = {}

    def option(self, key: str, value) -> "DataFrameReader":
        self._options[key] = value
        return self

    def _scan(self, fmt: str, paths) -> "L.DataFrame":
        from .io.files import infer_schema
        if isinstance(paths, str):
            paths = [paths]
        schema = infer_schema(fmt, paths, self._options)
        plan = L.Scan(fmt, paths, schema, self._options)
        return L.DataFrame(plan, self._session)

    def parquet(self, *paths):
        return self._scan("parquet", list(paths))

    def orc(self, *paths):
        return self._scan("orc", list(paths))

    def csv(self, *paths):
        return self._scan("csv", list(paths))


class TpuSession:
    def __init__(self, conf: Optional[dict] = None):
        self.conf = TpuConf(conf)
        self.device_manager = DeviceManager.get_or_create(self.conf)
        self._overrides = TpuOverrides(self.conf)
        from .config import TPU_UPLOAD_CACHE_BYTES
        from .data import upload_cache
        upload_cache.set_budget(self.conf.get(TPU_UPLOAD_CACHE_BYTES))
        # Compile-once layer: bucket ladder, persistent XLA executable
        # cache, AOT warm-up worker (compile/, docs/compile-cache.md).
        from . import compile as compile_layer
        compile_layer.configure(self.conf)
        # Pipelined execution layer: shared worker pool sizing
        # (exec/pipeline.py, docs/tuning-guide.md).
        from .exec import pipeline as pipeline_layer
        pipeline_layer.configure(self.conf)
        # Query-profile layer (metrics/, docs/monitoring.md). Profiles
        # key by QUERY ID (ISSUE 12): concurrent queries on one session
        # (the serving pool) no longer clobber a single slot —
        # last_query_profile() stays as the last-slot shim.
        self._last_profile = None
        self._last_load_profile = None
        self._query_seq = 0
        self._event_log = None
        self._profiles = {}
        # Distributed-tracing layer (metrics/trace.py, ISSUE 13): snapshot
        # the trace confs; per-query tracers are created lazily in
        # execute() only when spark.rapids.tpu.trace.enabled is on.
        self._last_tracer = None
        from .metrics import trace as _trace
        _trace.configure(self.conf)
        from .utils import lockdep as _lockdep
        self._profiles_lock = _lockdep.lock("TpuSession._profiles_lock")
        # close() is idempotent and safe under concurrent callers — the
        # serving pool's reaper may race an in-flight query (ISSUE 12).
        self._close_lock = _lockdep.lock("TpuSession._close_lock")
        # Concurrency analysis layer (utils/lockdep.py,
        # docs/concurrency.md): the conf covers locks constructed from
        # here on (session-scoped catalogs, deadlines, registries); the
        # TPU_LOCKDEP env var is the full-coverage import-time switch.
        from .config import LOCKDEP_ENABLED
        if self.conf.get(LOCKDEP_ENABLED):
            from .utils import lockdep
            lockdep.enable(True)
        # OOM-resilience layer (memory/retry.py, docs/fault-tolerance.md):
        # the fault injector is SESSION-scoped so its deterministic visit
        # counters survive per-dispatch context rebuilds.
        from .utils.fault_injection import FaultInjector
        self._fault_injector = FaultInjector.maybe(self.conf)
        # Distributed durability layer (ISSUE 7): the shuffle map-output
        # tracker is session-scoped so lineage recompute budgets and peer
        # blacklists persist across queries (docs/fault-tolerance.md).
        from .shuffle.exchange import MapOutputTracker
        self._shuffle_tracker = MapOutputTracker(self.conf)
        # Self-healing layer (ISSUE 19): once a mesh dispatch loses a
        # device (typed MeshDegradedError), the session marks the mesh
        # DEGRADED and re-plans onto the single-chip path — sticky until
        # spark.rapids.tpu.mesh.health.reprobeSecs elapses and a health
        # probe passes (0 = stay degraded; docs/fault-tolerance.md).
        self._mesh_degraded = False
        self._mesh_degraded_at = 0.0
        # ML scenario subsystem (ml/registry.py, docs/ml-integration.md):
        # the model registry is built EAGERLY (cheap: a dict + named
        # lock; no device work) so with_conf-derived sessions always
        # share it — a traced or differently-gated twin scores the same
        # registered models regardless of derive/register order.
        from .ml.registry import ModelRegistry
        self._ml_models = ModelRegistry(self)

    # -- conf ---------------------------------------------------------------
    def with_conf(self, **kv) -> "TpuSession":
        s = TpuSession.__new__(TpuSession)
        s.conf = self.conf.with_overrides(**kv)
        s.device_manager = self.device_manager
        s._overrides = TpuOverrides(s.conf)
        from . import compile as compile_layer
        compile_layer.configure(s.conf)
        from .exec import pipeline as pipeline_layer
        pipeline_layer.configure(s.conf)
        s._last_profile = None
        s._last_load_profile = None
        s._query_seq = 0
        s._event_log = None
        s._profiles = {}
        s._last_tracer = None
        from .metrics import trace as _trace
        _trace.configure(s.conf)
        from .utils import lockdep as _lockdep
        s._profiles_lock = _lockdep.lock("TpuSession._profiles_lock")
        s._close_lock = _lockdep.lock("TpuSession._close_lock")
        from .config import LOCKDEP_ENABLED
        if s.conf.get(LOCKDEP_ENABLED):
            from .utils import lockdep
            lockdep.enable(True)
        from .utils.fault_injection import FaultInjector
        s._fault_injector = FaultInjector.maybe(s.conf)
        from .shuffle.exchange import MapOutputTracker
        s._shuffle_tracker = MapOutputTracker(s.conf)
        s._mesh_degraded = False
        s._mesh_degraded_at = 0.0
        # Derived sessions score the SAME models (docs/ml-integration.md).
        s._ml_models = self._ml_models
        return s

    def close(self) -> None:
        """Quiesce session-owned background machinery: drop queued
        warm-ups and wait out the in-flight warm-up compile
        (compile/warmup.quiesce), then join every shared pipeline worker
        thread (exec/pipeline.py — the conftest leak check asserts none
        survive close). The pool is process-wide and lazily recreated,
        so a session used after close keeps working; close only
        guarantees no pipeline thread is left running NOW.

        Idempotent and safe under CONCURRENT callers (ISSUE 12): a pool
        reaper racing an in-flight query serializes closers through
        ``_close_lock``, both quiesce steps tolerate multiple closers,
        and a query that loses the race sees the typed TRANSIENT
        ``PoolShutdownError`` and retries onto the lazily recreated
        pool — a neighbor's teardown is a non-event, not a failure."""
        with self._close_lock:
            from .compile import warmup as warmup_layer
            from .exec import pipeline as pipeline_layer
            warmup_layer.quiesce()
            leaked = pipeline_layer.shutdown()
        if leaked:
            import logging
            logging.getLogger(__name__).warning(
                "pipeline pool shutdown left %d worker(s) running: %s",
                len(leaked), [t.name for t in leaked])

    def compile_status(self) -> dict:
        """Diagnostic snapshot of the compile-once layer: the process
        bucket ladder, persistent-cache state, warm-up counters, fused
        program dispatch stats, and the operator kernel cache. See
        docs/compile-cache.md."""
        import dataclasses
        from .compile import budget, executables, ladder, persist, warmup
        from .exec import fusion
        from .utils import kernel_cache
        return {
            "ladder": dataclasses.asdict(ladder.get_ladder()),
            "persistent_cache": persist.status(),
            "warmup": warmup.stats(),
            "fused_programs": executables.stats(),
            "fused_cache_entries": len(fusion._FUSED_CACHE),
            "pad_programs": fusion.pad_program_count(),
            "kernel_cache": kernel_cache.cache_stats(),
            "compile_budget": budget.stats(),
        }

    # -- ML scenario subsystem (ml/, docs/ml-integration.md) ----------------
    @property
    def ml_models(self):
        """This session's :class:`~spark_rapids_tpu.ml.registry.
        ModelRegistry`: register trained models here
        (``session.ml_models.register(name, model)``) and score them
        inside queries with ``df.with_model_score``. All
        ``with_conf``-derived sessions share one registry, regardless of
        derive/register order."""
        return self._ml_models

    # -- data sources -------------------------------------------------------
    @property
    def read(self) -> DataFrameReader:
        return DataFrameReader(self)

    def create_dataframe(self, data, schema: Optional[T.Schema] = None
                         ) -> L.DataFrame:
        if isinstance(data, pa.Table):
            rbs = data.combine_chunks().to_batches()
            s = T.schema_from_arrow(data.schema)
        elif isinstance(data, pa.RecordBatch):
            rbs = [data]
            s = T.schema_from_arrow(data.schema)
        elif isinstance(data, dict):
            hb = HostBatch.from_pydict(data, schema)
            rbs = [hb.rb]
            s = hb.schema
        else:  # pandas
            table = pa.Table.from_pandas(data)
            rbs = table.combine_chunks().to_batches()
            s = T.schema_from_arrow(table.schema)
        return L.DataFrame(L.LocalRelation(rbs, schema or s), self)

    def range(self, start: int, end: Optional[int] = None, step: int = 1
              ) -> L.DataFrame:
        if end is None:
            start, end = 0, start
        return L.DataFrame(L.Range(start, end, step), self)

    # -- execution ----------------------------------------------------------
    def plan(self, logical: L.LogicalPlan) -> P.PhysicalPlan:
        from .analysis.plan_lint import verify_plan
        from .plan.input_file import rewrite_input_file_exprs
        from .plan.optimizer import prune_columns
        from .plan.planner import plan_and_verify
        logical = rewrite_input_file_exprs(logical)
        cpu_plan = plan_and_verify(prune_columns(logical), self.conf)
        converted = self._overrides.apply(cpu_plan)
        # Post-rewrite static verification (docs/plan-lint.md): error
        # severity raised inside verify_plan; warn severity falls the
        # query back to the un-rewritten CPU plan.
        warns = verify_plan(converted, self.conf, stage="post-overrides")
        if warns:
            import warnings

            from .analysis.plan_lint import PlanLintError
            from .plan.overrides import finalize_plan
            if self.conf.test_enabled:
                # Test mode promises "no silent CPU fallback"; a silent
                # warn-fallback here would run the differential harness
                # CPU-vs-CPU and mask the regression it exists to catch.
                raise PlanLintError(warns)
            for w in warns:
                warnings.warn(f"plan-lint: {w}; falling back to the CPU "
                              "plan", stacklevel=2)
            # The CPU tree may still hold device-resident leaves
            # (DeviceSourceExec); finalize so it is runnable like every
            # other plan the session emits.
            return finalize_plan(cpu_plan, self.conf)
        return converted

    #: plan signature -> ({join site ordinal: exact output capacity},
    #: {join site ordinal: dense-mode escalation}). Learned from observed
    #: match totals the first time a plan's optimistic sizing overflows;
    #: persists for the session so re-running the same query shape
    #: executes exactly once (no retry ladder, no re-compiles).
    _JOIN_CAP_CACHE: Dict[tuple, Tuple[dict, dict]] = {}

    #: Deferred overflow attempts before the guaranteed eager rung: each
    #: attempt learns exact capacities for every join it reached, so a
    #: chain of N joins converges in <= N attempts (a truncated join feeds
    #: its consumer an underestimate, which the next attempt corrects).
    _MAX_LEARN_ATTEMPTS = 6

    # -- degraded-mesh fallback (ISSUE 19) ---------------------------------
    def _mesh_usable(self) -> bool:
        """Whether this query may take the SPMD mesh path. False while
        the mesh is marked degraded; with
        ``spark.rapids.tpu.mesh.health.reprobeSecs`` > 0 a degraded mesh
        is re-probed once the window elapses and heals on a clean probe
        (0 keeps it degraded for the session's lifetime — the operator
        re-probes manually via :meth:`probe_mesh`)."""
        if not self._mesh_degraded:
            return True
        from .config import MESH_HEALTH_REPROBE_SECS
        reprobe = float(self.conf.get(MESH_HEALTH_REPROBE_SECS))
        if reprobe <= 0:
            return False
        import time
        if time.monotonic() - self._mesh_degraded_at < reprobe:
            return False
        return not self.probe_mesh()

    def probe_mesh(self) -> list:
        """Health-probe every mesh device now
        (parallel/mesh.probe_devices); returns the failed devices. A
        clean probe CLEARS the degraded flag, a failed one (re)marks it —
        the manual recovery path after the hardware comes back."""
        import time
        from .parallel.mesh import probe_devices
        failed = probe_devices()
        self._mesh_degraded = bool(failed)
        if failed:
            self._mesh_degraded_at = time.monotonic()
        return failed

    def _record_mesh_failover(self, ctx, exc) -> None:
        """Mark the mesh degraded and record the failover: the
        ``meshFailovers`` durability counter (harvested across the
        discarded attempt), a flight-recorder event, and a flight dump
        carrying the failover timeline (ISSUE 13 artifact)."""
        import time
        from .metrics import trace as TR
        self._mesh_degraded = True
        self._mesh_degraded_at = time.monotonic()
        ctx.metric("TpuSession", "meshFailovers", 1)
        TR.record_event("mesh.failover", reason=str(exc),
                        failed_devices=[str(d) for d in getattr(
                            exc, "failed_devices", ())])
        TR.flight_dump("mesh_degraded", detail=str(exc))

    def _run_with_retries(self, fn, eager_only: bool = False,
                          plan_sig: Optional[tuple] = None,
                          deadline=None, trace=None):
        """Run ``fn(ctx, mode) -> (result, overflowed)``; on a deferred join
        overflow, learn the exact output capacities from the run's observed
        match totals and retry with them (cached per plan signature).

        Dispatch failures route through the retry taxonomy
        (memory/retry.py): transient faults (remote-compile/helper races,
        spill-disk OSError) retry in place with the shared backoff policy;
        a classified OOM that escaped every operator-level retry re-runs
        the whole query after a device sync + full spill-down — the
        task-retry analog — except for side-effecting (write) plans, which
        must not re-execute after partial commits. Fatal errors propagate
        untouched."""
        import time

        import jax
        from .data.column import bucket_capacity
        from .memory import retry as R
        from .metrics import trace as TR
        from .utils.deadline import Deadline
        from .utils.fault_injection import maybe_inject
        policy = R.RetryPolicy.from_conf(self.conf)
        # One deadline spans the WHOLE query including its retry ladder
        # (spark.rapids.tpu.query.deadlineSecs): re-running after a fault
        # does not reset the user's wall-clock contract. The serving
        # layer passes its own (per-tenant budget / cancellable) Deadline
        # instead (serve/service.py, docs/serving.md).
        if deadline is None:
            deadline = Deadline.maybe(self.conf)
        cached = self._JOIN_CAP_CACHE.get(plan_sig) \
            if plan_sig is not None else None
        caps, dense_modes = (dict(cached[0]), dict(cached[1])) \
            if cached is not None else ({}, {})
        attempts = 1 if eager_only else self._MAX_LEARN_ATTEMPTS + 1
        # Growth escalation covers paths that size from ctx.join_growth but
        # report no per-site totals (the mesh SPMD path, exec/mesh.py):
        # when an attempt overflows without teaching us any capacity, the
        # next attempt multiplies the optimistic bucket instead of
        # re-running the identical program.
        growth = 1.0
        force_eager = False
        # Dispatch-retry totals live OUTSIDE the attempt loop: failed
        # attempts' contexts are discarded, so the cumulative counts are
        # re-recorded into each successful context — the profiled (last)
        # one ends up carrying them.
        dispatch_retries = 0
        dispatch_block_ns = 0
        # Same for the durability counters (ISSUE 7): a shuffle refetch or
        # map recompute on an attempt that later overflows (join sizing)
        # would vanish with its context, under-reporting recovery in the
        # profile and the bench `faults` section.
        durability_carry: Dict[str, int] = {}
        # Runs of the plan behind this one call: 1, plus capacity re-runs
        # and dispatch retries. Carried the same way onto the final
        # context's TpuSession node.
        plan_runs = 0

        def _harvest_durability(c) -> None:
            from .metrics.profile import (DURABILITY_COUNTERS,
                                          PROCESS_DELTA_COUNTERS,
                                          _registry_total)
            for cname in DURABILITY_COUNTERS:
                if cname in PROCESS_DELTA_COUNTERS:
                    # The profile reads these from process-wide stats
                    # deltas, which span discarded attempts natively —
                    # carrying the registry value would be dead data at
                    # best, a double count if the profile ever switched
                    # to summing the registry.
                    continue
                total = _registry_total(c.registry, cname)
                if total:
                    durability_carry[cname] = \
                        durability_carry.get(cname, 0) + total
        for attempt in range(attempts):
            eager = eager_only or force_eager or attempt == attempts - 1
            dispatch_try = 0
            while True:
                ctx = P.ExecContext(self.conf,
                                    catalog=self.device_manager.catalog,
                                    fault_injector=self._fault_injector,
                                    semaphore=self.device_manager.semaphore,
                                    deadline=deadline,
                                    shuffle_tracker=self._shuffle_tracker,
                                    trace=trace)
                ctx.join_caps = caps
                ctx.dense_modes = dict(dense_modes)
                ctx.join_growth = growth
                ctx.eager_overflow = eager
                try:
                    if deadline is not None:
                        deadline.check("session.dispatch", ctx,
                                       "TpuSession")
                    maybe_inject(ctx, "session.dispatch")
                    # Task admission: bound concurrent queries holding the
                    # device (GpuSemaphore.acquireIfNecessary analog; conf
                    # spark.rapids.sql.concurrentTpuTasks). Wait time is
                    # accumulated by the semaphore itself (wait_ns); the
                    # query profile reports the per-query delta.
                    with TR.span(trace, "session.dispatch", cat="session",
                                 attempt=attempt, retry=dispatch_try), \
                            self.device_manager.semaphore:
                        plan_runs += 1
                        result, overflowed = fn(
                            ctx, "eager" if eager else "deferred")
                    if dispatch_retries:
                        ctx.metric("TpuSession", "retryCount",
                                   dispatch_retries)
                        ctx.metric("TpuSession", "retryBlockTimeNs",
                                   dispatch_block_ns)
                    break
                except Exception as e:  # noqa: BLE001 - classified below
                    cls = R.classify(e)
                    # Write plans (eager_only) committed partial output
                    # already: re-running would duplicate it, so only the
                    # pre-dispatch transient class (compile-helper races)
                    # retries there — a mid-write disk OSError must NOT
                    # re-execute the plan.
                    transient_ok = cls == R.Classification.TRANSIENT and \
                        not (eager_only and isinstance(e, OSError))
                    retryable = transient_ok or \
                        (cls == R.Classification.OOM and not eager_only)
                    if not retryable or dispatch_try >= policy.max_retries:
                        raise
                    _harvest_durability(ctx)
                    if cls == R.Classification.OOM:
                        # Sync-only under the lock (ISSUE 11): the spill
                        # catalog's state machine makes concurrent
                        # spill-downs safe off-lock.
                        with R._OOM_RECOVERY_LOCK:
                            R.synchronize_device()
                        R.spill_device_below(ctx)
                    dispatch_retries += 1
                    t0 = time.perf_counter_ns()
                    with TR.span(trace, "retry.backoff", cat="retry",
                                 site="session.dispatch"):
                        R.backoff_sleep(policy, "session.dispatch",
                                        dispatch_try)
                    dispatch_block_ns += time.perf_counter_ns() - t0
                    dispatch_try += 1
                finally:
                    ctx.close()
            if not overflowed:
                # Recovery that happened on discarded attempts still
                # belongs to this query's profile.
                for cname, v in durability_carry.items():
                    ctx.metric("TpuSession", cname, v)
                ctx.metric("TpuSession", "planRuns", plan_runs)
                if plan_sig is not None and (caps or dense_modes):
                    if len(self._JOIN_CAP_CACHE) > 512:
                        self._JOIN_CAP_CACHE.pop(
                            next(iter(self._JOIN_CAP_CACHE)))
                    self._JOIN_CAP_CACHE[plan_sig] = (caps,
                                                      dict(dense_modes))
                return result
            _harvest_durability(ctx)  # overflowed attempt: ctx discarded
            # Learn exact capacities from this run's observations (one
            # batched download). Totals observed downstream of a truncated
            # join are underestimates; max() keeps monotone convergence
            # within one query. (Across queries the cache only ratchets up,
            # so a plan shape re-run on much smaller data keeps the larger
            # buckets — bounded by the largest data actually seen for that
            # shape, and the cache itself is bounded at 512 entries.)
            learned = False
            if ctx.dense_fails:
                # Dense-path ineligibility observed this run: escalate the
                # site's mode (build-table -> swapped table -> general).
                sites_d = [s for s, _ in ctx.dense_fails]
                fails = jax.device_get([f for _, f in ctx.dense_fails])
                for s, f in zip(sites_d, fails):
                    if bool(f):
                        dense_modes[s] = dense_modes.get(s, 0) + 1
                        learned = True
            if ctx.join_totals:
                sites = [s for s, _ in ctx.join_totals]
                totals = jax.device_get([t for _, t in ctx.join_totals])
                for s, t in zip(sites, totals):
                    new_cap = bucket_capacity(max(int(t), 128))
                    if new_cap > caps.get(s, 0):
                        caps[s] = new_cap
                        learned = True
            if not learned:
                # Non-learning path (mesh SPMD): escalate the optimistic
                # bucket, but cap at 64x — beyond that the allocation
                # itself is the risk, so fall to the guaranteed eager rung.
                if growth >= 64.0:
                    force_eager = True
                else:
                    growth *= 8.0
        raise AssertionError("unreachable: eager join path cannot overflow")

    def _device_root(self, physical: P.PhysicalPlan) -> P.PhysicalPlan:
        """The columnar subtree to execute device-side; pure host plans
        (e.g. a bare local table) get an upload so results are
        device-resident."""
        from .exec.execs import DeviceToHostExec, HostToDeviceExec
        if isinstance(physical, DeviceToHostExec) \
                and physical.children[0].columnar:
            return physical.children[0]
        if not physical.columnar:
            return HostToDeviceExec(physical, self.conf.batch_size_rows)
        return physical

    def execute(self, logical: L.LogicalPlan, deadline=None,
                profile_sink=None, trace=None) -> pa.Table:
        """Plan + run. Joins size their output optimistically with a
        deferred device-side overflow flag (no per-batch host syncs); when a
        flag trips the query re-runs with the EXACT capacities learned from
        the observed match totals (cached per plan signature, so the same
        query shape never pays the retry twice). Fusable device plans run
        as ONE compiled program (exec/fusion.py); mesh-capable plans as one
        SPMD program (exec/mesh.py).

        ``deadline`` overrides the conf-derived query deadline (the
        serving layer passes its per-tenant budget / cancellable one);
        ``profile_sink`` receives THIS query's QueryProfile — the
        race-free way for a concurrent caller to get its own profile
        instead of reading the last-slot shim (docs/serving.md);
        ``trace`` threads in a caller-owned span tracer (the serving
        layer's — it exports the stitched trace itself), else one is
        created here when spark.rapids.tpu.trace.enabled is on and
        exported beside the event log at query end (ISSUE 13,
        docs/monitoring.md#distributed-tracing)."""
        from .exec import fusion
        from .metrics import trace as TR
        from .metrics.profile import QueryProfiler
        import contextlib
        tracer = trace
        created_trace = False
        if tracer is None:
            from .config import TENANT_ID
            tracer = TR.maybe_tracer(
                self.conf, str(self.conf.get(TENANT_ID) or ""))
            created_trace = tracer is not None
        # A session-created tracer gets an explicit root span covering
        # the whole query, so plan/dispatch/export are SIBLINGS under it
        # (a serving-owned tracer already has serve.query as the root).
        _root = contextlib.ExitStack()
        if created_trace:
            _root.enter_context(TR.span(tracer, "session.query",
                                        cat="session"))
        try:
            with TR.span(tracer, "session.plan", cat="session"):
                physical = self.plan(logical)
        except BaseException:
            if created_trace:
                _root.close()
                self._export_trace(tracer)
            raise
        profiler = QueryProfiler.maybe(self)
        final = {}

        def run(ctx, mode):
            # run() executes on the query thread (the retry loop calls it
            # inline); worker-reachability here is generous-taint noise.
            final["ctx"] = ctx  # concurrency: ignore
            if mode == "deferred" and self.conf.sql_enabled \
                    and self.conf.mesh_enabled and self._mesh_usable() \
                    and _mesh().mesh_capable(physical, self.conf):
                from .config import MESH_HEALTH_PROBE_ENABLED
                from .parallel.mesh import MeshDegradedError
                failed = self.probe_mesh() \
                    if self.conf.get(MESH_HEALTH_PROBE_ENABLED) else []
                if failed:
                    # The pre-dispatch probe caught the loss: record the
                    # failover and continue THIS attempt on the
                    # single-chip path — no exception round-trip.
                    self._record_mesh_failover(ctx, MeshDegradedError(
                        "pre-dispatch health probe failed", failed))
                else:
                    try:
                        return _mesh().mesh_collect(physical, ctx)
                    except MeshDegradedError as e:
                        # Mid-dispatch device loss: record, mark the
                        # mesh degraded, and re-raise — TRANSIENT per
                        # the retry taxonomy, and the re-run skips the
                        # degraded mesh branch (single-chip path). Same
                        # answer, one failover, never a wrong result.
                        self._record_mesh_failover(ctx, e)
                        raise
            if mode == "deferred" and self.conf.sql_enabled \
                    and self.conf.fusion_enabled \
                    and fusion.fusable(physical, self.conf):
                table, overflowed = fusion.fused_collect(physical, ctx)
                # Boundary subtrees (windows, broadcasts, ...) executed
                # eagerly with THIS ctx: their deferred flags gate too.
                return table, overflowed or fusion.any_overflow(ctx)
            # Streaming (non-fused) path: one span covering the whole
            # operator-at-a-time collect, so partially-offloaded plans
            # still show where execution time went (ISSUE 13).
            with TR.span(tracer, "session.stream_collect", cat="dispatch"):
                table = P.collect_partitions(physical, ctx)
            return table, fusion.any_overflow(ctx)
        # Write plans are side-effecting: a discard-and-retry would commit
        # truncated files first, so they always use the eager exact-resize
        # join path (writes are IO-bound anyway).
        from .utils.kernel_cache import plan_signature
        sig = plan_signature(physical)
        try:
            result = self._run_with_retries(
                run, eager_only=_contains_write(physical),
                plan_sig=sig, deadline=deadline, trace=tracer)
        except BaseException:
            if created_trace:
                _root.close()
                self._export_trace(tracer)
            raise
        if profiler is not None and final.get("ctx") is not None:
            self._note_profile(profiler, physical, final["ctx"], sig,
                               profile_sink, tracer=tracer)
        if created_trace:
            _root.close()
            self._export_trace(tracer)
        return result

    def _export_trace(self, tracer) -> None:
        """Finish and export a session-created tracer (best-effort; a
        failed export never fails the query). The last tracer is kept
        for diagnostics/tests like the last-profile shim."""
        from .metrics import trace as TR
        self._last_tracer = tracer
        try:
            TR.export_chrome(tracer, TR.export_dir(self.conf))
        except Exception:  # noqa: BLE001 - observability aid, not a gate
            pass

    def materialize(self, logical: L.LogicalPlan) -> "L.CachedRelation":
        """Execute now and pin the result (eager df.cache()). Under a
        device session the batches stay resident in HBM."""
        from .exec import fusion
        physical = self.plan(logical)
        if not self.conf.sql_enabled:
            ctx = P.ExecContext(self.conf,
                                catalog=self.device_manager.catalog)
            try:
                table = P.collect_partitions(physical, ctx)
            finally:
                ctx.close()
            rbs = table.combine_chunks().to_batches()
            return L.CachedRelation(logical.schema, host_batches=rbs,
                                    n_rows=table.num_rows)
        device_root = self._device_root(physical)

        def run(ctx, mode):
            parts = [list(p) for p in device_root.execute(ctx)]
            if fusion.any_overflow(ctx):
                return None, True
            n = sum(int(b.n_rows) for p in parts for b in p)
            return L.CachedRelation(logical.schema, device_parts=parts,
                                    n_rows=n), False
        return self._run_load(run, device_root)

    def collect_device(self, logical: L.LogicalPlan) -> List:
        """Execute and return HBM-resident ColumnarBatches with NO host
        transfer (zero-copy ML export; ColumnarRdd.scala:41-49 analog).
        Gated like the reference by spark.rapids.sql.exportColumnarRdd."""
        from .config import EXPORT_COLUMNAR_RDD
        from .exec import fusion
        if not self.conf.get(EXPORT_COLUMNAR_RDD):
            raise RuntimeError(
                "device-batch export requires "
                "spark.rapids.sql.exportColumnarRdd=true "
                "(reference RapidsConf.scala:329)")
        if not self.conf.sql_enabled:
            raise RuntimeError("device-batch export needs a TPU session "
                               "(spark.rapids.sql.enabled)")
        device_root = self._device_root(self.plan(logical))

        def run(ctx, mode):
            parts = [list(p) for p in device_root.execute(ctx)]
            if fusion.any_overflow(ctx):
                return None, True
            return [b for p in parts for b in p], False
        return self._run_load(run, device_root)

    def _run_load(self, run, device_root):
        """Run a load (``materialize`` / ``collect_device``: the batches
        stay on the device) under a QueryProfiler, as ``execute`` runs a
        query. Its profile goes to the event log and to the load's own
        slot, :meth:`last_load_profile` — not to
        :meth:`last_query_profile`, whose readers sum queries."""
        from .metrics.profile import QueryProfiler
        from .utils.kernel_cache import plan_signature
        profiler = QueryProfiler.maybe(self)
        final = {}

        def noted(ctx, mode):
            final["ctx"] = ctx  # concurrency: ignore - the query thread
            return run(ctx, mode)
        sig = plan_signature(device_root)
        result = self._run_with_retries(noted, plan_sig=sig)
        if profiler is not None and final.get("ctx") is not None:
            self._note_profile(profiler, device_root, final["ctx"], sig,
                               load=True)
        return result

    def explain(self, logical: L.LogicalPlan) -> str:
        physical = self.plan(logical)
        return physical.tree_string()

    # -- query-profile layer (metrics/, docs/monitoring.md) -----------------

    #: profiles kept per session before the oldest query ids are evicted
    _MAX_PROFILES = 256

    def _note_profile(self, profiler, physical, ctx, plan_sig,
                      profile_sink=None, tracer=None,
                      load: bool = False) -> None:
        """Snapshot the finished query into the session's per-query-id
        profile map, the last-slot shim, and the structured event log
        (best-effort: observability must never fail a query). Query ids
        are assigned under the profile lock — concurrent queries on one
        session (the serving pool) each get their own id and slot
        instead of clobbering a single field (ISSUE 12)."""
        try:
            with self._profiles_lock:
                self._query_seq += 1
                qid = self._query_seq
            if tracer is not None:
                # Stamp the profile's query id into the trace header so
                # the two artifacts join without a side channel.
                tracer.query_id = qid
            prof = profiler.finish(physical, ctx, plan_sig, qid)
        except Exception:  # noqa: BLE001 - profile is an aid, not a gate
            return
        with self._profiles_lock:
            self._profiles[qid] = prof
            while len(self._profiles) > self._MAX_PROFILES:
                self._profiles.pop(next(iter(self._profiles)))
            if load:
                self._last_load_profile = prof
            else:
                self._last_profile = prof
            log_dir = self.conf.metrics_event_log_dir
            log = None
            if log_dir:
                if self._event_log is None or self._event_log.dir != log_dir:
                    from .config import METRICS_EVENT_LOG_MAX_BYTES
                    from .metrics.eventlog import EventLog
                    self._event_log = EventLog(
                        log_dir,
                        max_bytes=int(
                            self.conf.get(METRICS_EVENT_LOG_MAX_BYTES)))
                log = self._event_log
        if profile_sink is not None:
            try:
                profile_sink(prof)
            except Exception:  # noqa: BLE001 - caller's sink, not a gate
                pass
        if log is not None:
            log.append(prof)

    def query_profile(self, query_id: int):
        """The :class:`~spark_rapids_tpu.metrics.profile.QueryProfile`
        recorded for ``query_id`` on this session, or None (evicted past
        the retention window, metrics level NONE, or never run). The
        race-free accessor for concurrent queries — each profile's
        ``query_id`` field is the key."""
        with self._profiles_lock:
            return self._profiles.get(query_id)

    def last_query_profile(self):
        """The :class:`~spark_rapids_tpu.metrics.profile.QueryProfile` of
        the most recent query this session executed, or None (metrics level
        NONE, or nothing run yet). Render with ``.render()``; serialize
        with ``.to_dict()``. Under CONCURRENT queries this last-slot shim
        is whichever finished most recently — use :meth:`query_profile`
        (or ``execute``'s ``profile_sink``) for race-free attribution."""
        with self._profiles_lock:
            return self._last_profile

    def last_load_profile(self):
        """The QueryProfile of the most recent load this session ran —
        ``DataFrame.cache()`` (``materialize``) or ``collect_device`` —
        or None. What the load read, decoded, uploaded and compiled;
        kept apart from :meth:`last_query_profile`."""
        with self._profiles_lock:
            return self._last_load_profile

    def last_trace(self):
        """The :class:`~spark_rapids_tpu.metrics.trace.Tracer` of the
        most recent SESSION-created traced query (None when tracing is
        off or the serving layer owned the tracer) — the
        last-query-profile shim's tracing twin, for tests/diagnostics."""
        return self._last_tracer

    def explain_metrics(self, logical: L.LogicalPlan) -> str:
        """The metric-annotated EXPLAIN tree (df.explain(metrics=True)):
        the physical plan annotated with the metrics of this session's last
        execution of the SAME plan shape. Falls back to the plain tree with
        a note when no matching profile exists."""
        from .metrics.profile import plan_profile_hash
        from .utils.kernel_cache import plan_signature
        physical = self.plan(logical)
        prof = self._last_profile
        if prof is not None and \
                prof.plan_hash == plan_profile_hash(plan_signature(physical)):
            return prof.render()
        return (physical.tree_string()
                + "(no QueryProfile recorded for this plan shape yet — run "
                ".collect() first, with spark.rapids.tpu.metrics.level "
                "above NONE)\n")


def _mesh():
    from .exec import mesh
    return mesh


def _contains_write(plan: P.PhysicalPlan) -> bool:
    from .io.writers import _WriteFilesBase
    if isinstance(plan, _WriteFilesBase):
        return True
    return any(_contains_write(c) for c in plan.children)
