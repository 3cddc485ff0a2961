"""Pipelined execution layer — overlap the host side of the query pipeline.

BENCH r05 showed the device path losing to the CPU oracle on most
multi-boundary queries: not because device compute was slow, but because
every fusion boundary (scan, decode, upload, shuffle, window) materialized
one after another on a single thread before the one fused dispatch. The
reference explicitly overlaps the next host buffer assembly with the
previous GPU decode (GpuParquetScan.scala:314 readPartFile /
Table.readParquet split), and the data-movement literature (Theseus,
arxiv 2508.05029; "Accelerating Presto with GPUs", arxiv 2606.24647)
attributes most accelerator wins to keeping transfer and compute
concurrent. This module is the engine-wide version of that discipline:

* :class:`PipelinePool` — ONE shared, elastic worker pool for every
  pipeline stage (prefetch iterators, decode tasks, boundary
  materialization, shuffle serialization), replacing the raw
  ``threading.Thread``-per-iterator pattern (ratcheted by the
  ``raw-thread`` tpu_lint rule). Elastic on purpose: a fixed-size pool
  deadlocks when every slot holds a producer whose consumer is itself a
  queued task; here a submit never waits behind a busy worker, and idle
  workers are reused. :func:`shutdown` joins every worker
  (``TpuSession.close`` calls it; the conftest leak check asserts no
  pipeline thread survives).
* :func:`ordered_map_iter` / :func:`unit_partitions` — bounded decode-ahead
  for the file readers: up to ``prefetchDepth`` files/row-groups decode
  concurrently (capped globally by ``decodeThreads``) while results yield
  in deterministic input order.
* :func:`materialize_boundaries` — independent fusion-boundary subtrees
  materialize concurrently on forked :class:`~..plan.physical.ExecContext`
  children (private accumulators merged back in boundary order; disjoint
  deterministic join-site namespaces), with device admission still
  serialized through the existing task semaphore: each worker acquires it,
  and the dispatching thread releases its own slot while it waits — the
  reference's release-during-shuffle-fetch discipline.

Determinism contract: results are bit-identical with the pipeline on or
off — concurrency only reorders WHEN work happens, never what it
computes, and everything order-sensitive (fused argument order, decode
output order, accumulator merges) is sequenced explicitly. When a fault
injector is active the parallel paths fall back to serial execution so
per-site injection schedules stay deterministic
(:func:`parallel_active`; docs/fault-tolerance.md).

Occupancy counters (ESSENTIAL level, folded into the QueryProfile):
``prefetchProducerStallNs`` / ``prefetchConsumerStallNs`` (which side of
each bounded queue is the bottleneck), ``decodeThreadBusyNs`` (decode-pool
utilization), ``boundaryOverlapNs`` (wall time saved by concurrent
boundary materialization). See docs/tuning-guide.md for sizing.
"""

from __future__ import annotations

import collections
import contextlib
import math
import os
import queue
import threading
import time
from concurrent.futures import Future
from typing import Callable, Iterator, List, Optional, Sequence

from ..metrics import trace as _trace
from ..utils import lockdep

_STOP = object()


class PoolShutdownError(RuntimeError):
    """The shared pool was shut down under this caller (a concurrent
    ``TpuSession.close`` — e.g. the serving layer's session reaper
    retiring a crashed neighbor). Classified TRANSIENT by the retry
    taxonomy (memory/retry.py): the pool is lazily recreated, so a
    retry in place lands on fresh workers and the query survives."""


# ---------------------------------------------------------------------------
# The shared elastic worker pool
# ---------------------------------------------------------------------------


class PipelinePool:
    """Shared elastic worker pool for pipeline stages.

    Unlike a fixed-size executor, ``submit`` never queues a task behind a
    busy worker: it hands the task to an idle worker when one exists and
    spawns a fresh (reusable, daemon) thread otherwise. Long-lived
    occupants — prefetch producers that block for their whole iterator
    lifetime — therefore can never starve short decode tasks into a
    deadlock. Concurrency limits live at the call sites (decode slots,
    boundary slots, prefetch depth), not in the pool size.
    """

    def __init__(self, name: str = "tpu-pipeline"):
        self._name = name
        self._tasks: "queue.Queue" = queue.Queue()
        self._lock = lockdep.lock("PipelinePool._lock")
        self._threads: List[threading.Thread] = []
        self._idle = 0
        self._seq = 0
        self._closed = False
        #: Set when shutdown starts; prefetch producers poll it so a
        #: blocked put() cannot outlive the pool.
        self.shutting_down = threading.Event()

    def submit(self, fn: Callable, *args) -> Future:
        f: Future = Future()
        # Enqueue AND start entirely under the lock (the queue is
        # unbounded, so neither blocks): shutdown() snapshots alive
        # threads under the same lock, so a spawned worker is either
        # visible to its join + _STOP accounting or the submit already
        # saw _closed and raised — no window where a late-starting
        # worker misses both.
        with self._lock:
            if self._closed:
                raise PoolShutdownError("pipeline pool is shut down")
            spawn = self._idle == 0
            if not spawn:
                self._idle -= 1
            self._tasks.put((f, fn, args))
            if spawn:
                # The engine's ONE sanctioned thread-spawn site: every
                # other module routes here (tpu_lint rule raw-thread).
                t = threading.Thread(  # tpu-lint: ignore
                    target=self._work, name=f"{self._name}-{self._seq}",
                    daemon=True)
                self._seq += 1
                self._threads.append(t)
                t.start()
        return f

    def _work(self) -> None:
        while True:
            item = self._tasks.get()
            if item is _STOP:
                return
            f, fn, args = item
            ran = f.set_running_or_notify_cancel()
            result = exc = None
            if ran:
                try:
                    result = fn(*args)
                # Forwarded verbatim to the future: the CONSUMER's
                # result() re-raises it where the retry taxonomy (or the
                # exchange/reader handlers) classify it — the pool must
                # stay classification-neutral.
                except BaseException as e:  # tpu-lint: ignore
                    exc = e
            # Return to the idle pool BEFORE publishing the result: a
            # consumer that wakes on result() and immediately submits its
            # next task must see this worker as reusable — publishing
            # first left a window where sequential submit/result loops
            # spawned one fresh thread per task.
            with self._lock:
                closed = self._closed
                if not closed:
                    self._idle += 1
            if ran:
                if exc is not None:
                    f.set_exception(exc)
                else:
                    f.set_result(result)
            if closed:
                return

    def alive_threads(self) -> List[threading.Thread]:
        with self._lock:
            return [t for t in self._threads if t.is_alive()]

    def shutdown(self, timeout: float = 10.0) -> List[threading.Thread]:
        """Stop accepting work, wake every worker, join them. Returns the
        threads (if any) that failed to stop within ``timeout`` — the
        conftest leak check asserts this list is empty."""
        self.shutting_down.set()
        with self._lock:
            self._closed = True
            threads = [t for t in self._threads if t.is_alive()]
        for _ in threads:
            self._tasks.put(_STOP)
        deadline = time.monotonic() + timeout
        leaked = []
        for t in threads:
            t.join(max(0.0, deadline - time.monotonic()))
            if t.is_alive():
                leaked.append(t)
        # Cancel anything that raced past the closed check into the queue,
        # so no consumer blocks forever on a future nobody will run.
        while True:
            try:
                item = self._tasks.get_nowait()
            except queue.Empty:
                break
            if item is not _STOP:
                item[0].cancel()
        return leaked


_LOCK = lockdep.lock("pipeline._LOCK")
_POOL: Optional[PipelinePool] = None
_DECODE_SLOTS: Optional[threading.BoundedSemaphore] = None
#: Conf snapshot (TpuSession.configure); defaults match the conf defaults.
_CONF = {"decode_threads": 0, "boundary_parallelism": 0,
         "prefetch_depth": 2}


def configure(conf) -> None:
    """Snapshot the pool-sizing confs from a session's TpuConf (the same
    configure() idiom as the compile layer). Limiter semaphores rebuild
    lazily so a resize takes effect for new work without disturbing
    in-flight holders of the old one."""
    global _DECODE_SLOTS
    with _LOCK:
        try:
            _CONF["decode_threads"] = int(conf.pipeline_decode_threads)
            _CONF["boundary_parallelism"] = \
                int(conf.pipeline_boundary_parallelism)
            _CONF["prefetch_depth"] = int(conf.pipeline_prefetch_depth)
        except AttributeError:
            return  # bare test conf without the pipeline properties
        _DECODE_SLOTS = None


def get_pool() -> PipelinePool:
    """The process-wide shared pool (lazily created; recreated after a
    shutdown, so closing one session only quiesces it)."""
    global _POOL
    with _LOCK:
        if _POOL is None or _POOL.shutting_down.is_set():
            _POOL = PipelinePool()
        return _POOL


def shutdown(timeout: float = 10.0) -> List[threading.Thread]:
    """Join every pipeline worker thread (TpuSession.close / conftest leak
    check). Returns threads that failed to stop in time."""
    global _POOL
    with _LOCK:
        pool, _POOL = _POOL, None
    if pool is None:
        return []
    return pool.shutdown(timeout)


def _auto_threads() -> int:
    return max(2, min(4, os.cpu_count() or 2))


def submit_spill_io(fn, *args) -> Optional[Future]:
    """Spill-IO lane entry (memory/spill.py): hand one spill/restore copy
    or disk append/read to the shared pool. Concurrency is bounded by the
    CALLER's lane slots (``spark.rapids.tpu.spill.ioThreads`` — each
    catalog holds its own slot semaphore inside the submitted unit, the
    decode-limiter pattern), never by pool size. Returns None when the
    pool refuses the task (shutdown race) — the caller runs the unit
    inline, because spilling must survive pool teardown: a query draining
    memory during session close still has to land its bytes."""
    try:
        return get_pool().submit(fn, *args)
    except RuntimeError:
        return None


def _conf_int(conf, prop: str, fallback_key: str) -> int:
    """Per-session conf value when available (sizing must not leak
    between sessions through the process-global snapshot), else the
    configure() fallback."""
    try:
        if conf is not None:
            return int(getattr(conf, prop))
    except (AttributeError, TypeError, ValueError):
        pass
    return _CONF[fallback_key]


def _decode_limiter(conf=None) -> threading.BoundedSemaphore:
    """Global decode-slot semaphore, keyed by the effective size so two
    sessions with different decodeThreads each get their bound (in-flight
    holders of a resized limiter keep their own reference)."""
    global _DECODE_SLOTS
    n = _conf_int(conf, "pipeline_decode_threads", "decode_threads")
    n = n if n > 0 else _auto_threads()
    with _LOCK:
        if _DECODE_SLOTS is None \
                or getattr(_DECODE_SLOTS, "_initial_value", None) != n:
            _DECODE_SLOTS = threading.BoundedSemaphore(n)
        return _DECODE_SLOTS


def boundary_parallelism(conf=None) -> int:
    n = _conf_int(conf, "pipeline_boundary_parallelism",
                  "boundary_parallelism")
    return n if n > 0 else _auto_threads()


def prefetch_depth(conf=None) -> int:
    try:
        if conf is not None:
            return max(1, int(conf.pipeline_prefetch_depth))
    except AttributeError:
        pass
    return max(1, _CONF["prefetch_depth"])


def parallel_active(ctx) -> bool:
    """True when the pipeline's PARALLEL paths may engage for this
    execution. A live fault injector forces the serial path: concurrent
    visits to one injection site would make WHICH visit faults depend on
    thread interleaving, and injection schedules are contractually
    per-site deterministic (docs/fault-tolerance.md)."""
    if getattr(ctx, "fault_injector", None) is not None:
        return False
    conf = getattr(ctx, "conf", None)
    try:
        return bool(conf.pipeline_enabled)
    except AttributeError:
        return True


# ---------------------------------------------------------------------------
# Bounded decode-ahead (io readers)
# ---------------------------------------------------------------------------


def _result_or_shutdown(f: Future, timeout: Optional[float] = None):
    """``f.result(timeout)`` with pool-teardown cancellation translated
    to the typed (transient) :class:`PoolShutdownError` — raw
    CancelledError derives from BaseException on modern Pythons and
    would sail past every ``except Exception`` retry arm. The futures
    TimeoutError passes through untouched for the caller's deadline
    loop."""
    from concurrent.futures import CancelledError
    try:
        return f.result(timeout=timeout)
    except CancelledError:
        raise PoolShutdownError(
            "pipeline pool shut down while this future was awaited "
            "(concurrent TpuSession.close); the unit was cancelled "
            "unrun") from None


def _stalled_result(f: Future, ctx, node: Optional[str]):
    """future.result() with the blocked time accounted to the consumer
    stall counter — the signal that the producer side is the bottleneck.
    An active query deadline bounds the wait: an expired deadline raises
    QueryDeadlineExceeded instead of blocking on a slow producer forever
    (cooperative cancellation — the worker's in-flight unit completes and
    is discarded)."""
    from concurrent.futures import TimeoutError as _FutTimeout
    deadline = getattr(ctx, "deadline", None)
    if f.done():
        return _result_or_shutdown(f)
    t0 = time.perf_counter_ns()
    # ONE span for the whole wait (opened only once we know we block):
    # the deadline branch polls in 0.1s ticks, and a span per tick would
    # flood the tracer and flight ring during a long producer stall.
    try:
        with _trace.span(getattr(ctx, "trace", None), "pipeline.wait",
                         cat="pipeline", node=node or "prefetch"):
            if deadline is None:
                with lockdep.blocking("pipeline.future_wait"):
                    return _result_or_shutdown(f)
            while True:
                try:
                    with lockdep.blocking("pipeline.future_wait"):
                        # An INFINITE deadline (the serving layer's
                        # cancel-only Deadline(math.inf)) polls bounded:
                        # result(timeout=inf) is an OverflowError in
                        # CPython, and a cancel() could never wake an
                        # unbounded wait.
                        rem = deadline.remaining()
                        return _result_or_shutdown(
                            f, timeout=max(rem, 0.0)
                            if math.isfinite(rem) else 0.1)
                except _FutTimeout:
                    # On py3.11+ futures.TimeoutError IS the builtin
                    # TimeoutError, which a WORKER can legitimately raise
                    # (requestTimeout, injected stall). A done future
                    # means the exception came from the work — re-raise
                    # it instead of misreading it as a wait-timeout and
                    # spinning.
                    if f.done():
                        return _result_or_shutdown(f)
                    # Raises once expired; a spurious early wake re-arms.
                    deadline.check(f"pipeline.wait:{node or 'prefetch'}",
                                   ctx, node)
    finally:
        if ctx is not None and node:
            ctx.metric(node, "prefetchConsumerStallNs",
                       time.perf_counter_ns() - t0)


def _decode_task(fn: Callable, item, ctx, node: Optional[str]):
    """One decode unit on the shared pool: bounded by the global decode
    slots, busy time accounted to decodeThreadBusyNs. Runs on a worker
    thread — its span parents under the trace root (the fork fallback),
    which is exactly where concurrent decode lanes belong on the
    timeline."""
    with _decode_limiter(getattr(ctx, "conf", None)):
        t0 = time.perf_counter_ns()
        try:
            with _trace.span(getattr(ctx, "trace", None), "pipeline.decode",
                             cat="decode", node=node or "scan"):
                return fn(item)
        finally:
            if ctx is not None and node:
                ctx.metric(node, "decodeThreadBusyNs",
                           time.perf_counter_ns() - t0)


def ordered_map_iter(fn: Callable, items: Sequence, ctx=None,
                     node: Optional[str] = None,
                     depth: Optional[int] = None) -> Iterator:
    """Map ``fn`` over ``items`` with up to ``depth`` results decoding
    ahead on the shared pool, yielding in input order — the bounded
    producer side of every single-stream reader (ORC stripes, CSV files).
    Serial (plain map) when the pipeline is off or an injector is live."""
    if not parallel_active(ctx):
        for item in items:
            yield fn(item)
        return
    pool = get_pool()
    if depth is None:
        depth = prefetch_depth(getattr(ctx, "conf", None))
    futs: "collections.deque[Future]" = collections.deque()
    try:
        for item in items:
            futs.append(pool.submit(_decode_task, fn, item, ctx, node))
            if len(futs) >= max(depth, 1):
                yield _stalled_result(futs.popleft(), ctx, node)
        while futs:
            yield _stalled_result(futs.popleft(), ctx, node)
    finally:
        # Early abandonment (LIMIT): drop the look-ahead; running decodes
        # finish and are discarded, unstarted ones never run.
        for f in futs:
            f.cancel()


class _UnitScheduler:
    """Decode-ahead over per-unit scan partitions (parquet's one
    partition per row group): partition i's generator waits on future i,
    and pulling it schedules units i..i+depth-1 — so the next row groups
    decode while the consumer uploads/dispatches the current one, without
    changing the scan's partition structure."""

    def __init__(self, fn: Callable, units: Sequence, ctx,
                 node: Optional[str]):
        self._fn = fn
        self._units = list(units)
        self._ctx = ctx
        self._node = node
        self._depth = prefetch_depth(getattr(ctx, "conf", None))
        self._pool = get_pool()
        self._futs: dict = {}
        self._lock = lockdep.lock("_UnitScheduler._lock")
        # A LIMIT can abandon trailing partitions; drop their look-ahead
        # at query end (running decodes finish, unstarted never run).
        if hasattr(ctx, "add_cleanup"):
            ctx.add_cleanup(self._cancel_pending)

    def _ensure(self, i: int) -> Future:
        with self._lock:
            for j in range(i, min(i + self._depth, len(self._units))):
                if j not in self._futs:
                    self._futs[j] = self._pool.submit(
                        _decode_task, self._fn, self._units[j],
                        self._ctx, self._node)
            return self._futs[i]

    def _cancel_pending(self) -> None:
        with self._lock:
            for f in self._futs.values():
                f.cancel()

    def partition(self, i: int) -> Iterator:
        yield _stalled_result(self._ensure(i), self._ctx, self._node)


def _serial_unit(fn: Callable, unit) -> Iterator:
    yield fn(unit)


def unit_partitions(fn: Callable, units: Sequence, ctx,
                    node: Optional[str] = None) -> List[Iterator]:
    """One single-batch partition per unit (the scan partition contract),
    decoded ahead on the shared pool when the pipeline is active."""
    units = list(units)
    if len(units) <= 1 or not parallel_active(ctx):
        return [_serial_unit(fn, u) for u in units]
    sched = _UnitScheduler(fn, units, ctx, node)
    return [sched.partition(i) for i in range(len(units))]


# ---------------------------------------------------------------------------
# Concurrent fusion-boundary materialization
# ---------------------------------------------------------------------------


def _serial_boundary(b, index: int, ctx, tr) -> tuple:
    """One boundary materialized on the calling thread (single boundary,
    pipeline off, or injector active) — same span as the worker path so
    traces always show the boundary stage, overlapped or not."""
    with _trace.span(tr, "pipeline.boundary", cat="pipeline", index=index,
                     node=type(b).__name__):
        return tuple(tuple(p) for p in b.execute(ctx))


def materialize_boundaries(boundaries: Sequence, ctx,
                           node: str = "WholeStageFusion") -> tuple:
    """Materialize every fusion-boundary subtree's partitions, preserving
    the deterministic argument order of the fused program.

    With the pipeline active and more than one boundary, each boundary
    executes on a worker with a forked context (private accumulator
    lists, a disjoint deterministic join-site namespace — see
    ExecContext.fork_for_boundary) and the parent absorbs the forks in
    boundary order afterward, so accumulator contents never depend on
    thread interleaving. Device admission stays serialized through the
    existing task semaphore: every worker acquires it, and the
    dispatching thread releases its own slot(s) while it waits so the
    default concurrentTpuTasks budget actually admits the workers."""
    boundaries = list(boundaries)
    parallelism = boundary_parallelism(getattr(ctx, "conf", None))
    if len(boundaries) <= 1 or not parallel_active(ctx) \
            or parallelism <= 1:
        tr = getattr(ctx, "trace", None)
        return tuple(
            _serial_boundary(b, i, ctx, tr)
            for i, b in enumerate(boundaries))
    subs = [ctx.fork_for_boundary(i) for i in range(len(boundaries))]
    pool = get_pool()
    slots = threading.BoundedSemaphore(parallelism)
    sem = getattr(ctx, "semaphore", None)
    # Span context forked ONCE on the dispatching thread: every worker's
    # boundary span parents under the span open HERE (fusion.boundaries),
    # not wherever the worker's own stack happens to be.
    span_fork = _trace.fork(getattr(ctx, "trace", None))

    def run_one(b, sub, index):
        with slots:
            admission = sem if sem is not None else contextlib.nullcontext()
            with admission:
                t0 = time.perf_counter_ns()
                with _trace.span(span_fork, "pipeline.boundary",
                                 cat="pipeline", index=index,
                                 node=type(b).__name__):
                    out = tuple(tuple(p) for p in b.execute(sub))
                return out, time.perf_counter_ns() - t0

    t_wall = time.perf_counter_ns()
    futs = [pool.submit(run_one, b, sub, i)
            for i, (b, sub) in enumerate(zip(boundaries, subs))]
    release = sem.released() if sem is not None \
        else contextlib.nullcontext()
    results: List = []
    err: Optional[BaseException] = None
    with release:
        # Wait for EVERY worker even after a failure: forks must not be
        # absorbed (or their cleanups run) while a worker still mutates
        # them, and cleanups of successful boundaries must reach the
        # parent so ctx.close() can run them.
        for f in futs:
            try:
                with _trace.span(getattr(ctx, "trace", None),
                                 "pipeline.boundary_wait", cat="pipeline"), \
                        lockdep.blocking("pipeline.boundary_wait"):
                    results.append(f.result())
            # Collect-and-re-raise: the FIRST failure propagates verbatim
            # after every worker has stopped touching its fork (the
            # session's retry loop then classifies it).
            except BaseException as e:  # tpu-lint: ignore
                err = err or e
                results.append(None)
    for sub in subs:
        ctx.absorb_boundary(sub)
    if err is not None:
        raise err
    wall = time.perf_counter_ns() - t_wall
    busy = sum(ns for _, ns in results)
    # Reported whenever the boundaries ran on workers, 0 when their spans
    # happened not to overlap: a profile then says that the concurrent
    # path ran, not how the threads were scheduled.
    ctx.metric(node, "boundaryOverlapNs", max(busy - wall, 0))
    return tuple(out for out, _ in results)
