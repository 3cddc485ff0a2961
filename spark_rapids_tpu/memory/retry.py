"""OOM-resilience retry framework — the ``RmmRapidsRetryIterator`` analog.

The reference survives GPU memory exhaustion by catching allocation
failures at operator boundaries, spilling lower-priority buffers, and
re-executing with the input split in half
(``RmmRapidsRetryIterator.withRetry`` / ``splitSpillableInHalfByRows``,
with ``RmmSpark.forceRetryOOM``-style injection to exercise the paths).
XLA owns the TPU allocator and raises ``RESOURCE_EXHAUSTED`` instead of
calling back, so the TPU-native port classifies *exceptions* at operator
boundaries:

* :data:`Classification.OOM` — device HBM exhaustion (XLA
  ``RESOURCE_EXHAUSTED`` messages, :class:`RetryOOM`). The retry first
  synchronizes the device (drain in-flight work so freed buffers are
  really reusable), synchronously spills every spillable buffer below
  on-deck priority (:func:`spill_device_below`), and re-runs the attempt
  with capped exponential backoff + deterministic jitter. After
  ``spark.rapids.tpu.retry.maxRetries`` it escalates to splitting the
  input batch in half by rows (:func:`halve_by_rows`) and processing the
  halves; sites that cannot split raise :class:`SplitAndRetryOOM` naming
  the site.
* :data:`Classification.TRANSIENT` — remote-compile/helper races and
  spill-disk ``OSError``: retried in place with the same backoff, never
  spilled or split.
* :data:`Classification.FATAL` — everything else propagates untouched.

:func:`with_retry` is the combinator the memory-intensive operator
boundaries wrap (coalesce concat, join build + probe, external-sort runs
and merges, window evaluation, shuffle partition split, device writers);
``TpuSession._run_with_retries`` rebases its transient-compile loop onto
the same taxonomy and backoff policy. Every retry site doubles as a
deterministic fault-injection point (:mod:`..utils.fault_injection`), so
all of these paths are exercised in tier-1 on the CPU backend.

Observability: ``retryCount`` / ``splitAndRetryCount`` /
``retryBlockTimeNs`` / ``retryWastedComputeNs`` flow into the metrics
registry under the wrapping operator's node name and surface in the
query profile (docs/monitoring.md). See docs/fault-tolerance.md.
"""

from __future__ import annotations

import dataclasses
import logging
import time
import zlib
from typing import Callable, List, Optional

from ..utils import lockdep

_LOG = logging.getLogger(__name__)

#: Serializes concurrent DEVICE SYNCS between OOM recoveries (ISSUE 11 —
#: narrowed from the whole sync+spill sequence): overlapping
#: effects_barriers would each re-drain the other's freshly dispatched
#: work for no benefit. The SPILL step no longer needs this lock at all:
#: the spill catalog's state machine (memory/spill.py) reserves each
#: victim exactly once under the catalog lock, respects pins, and never
#: selects an in-flight buffer — so concurrent spill-downs divide the
#: victims instead of corrupting each other, and one query's sync->spill
#: no longer serializes behind another query's disk write.
_OOM_RECOVERY_LOCK = lockdep.rlock("retry._OOM_RECOVERY_LOCK", io_ok=True)

#: Hard ceiling on attempts one ``with_retry`` call may make across all
#: split fragments — a runaway-injection backstop, far above any real
#: retry ladder (maxRetries deep on each of up to ~dozens of fragments).
_MAX_ATTEMPTS_PER_CALL = 256

#: Smallest fragment :func:`halve_by_rows` will split further; below this
#: the rows fit one VPU lane tile and splitting cannot relieve pressure.
_MIN_SPLIT_ROWS = 2


class Classification:
    """The error taxonomy's three buckets."""

    OOM = "oom"
    TRANSIENT = "transient"
    FATAL = "fatal"


class RetryOOM(MemoryError):
    """Device memory exhaustion an operator boundary may survive by
    spilling + retrying (the reference's ``RetryOOM``). Raised directly by
    budget checks; XLA's own ``RESOURCE_EXHAUSTED`` errors classify the
    same without wrapping."""


class SplitAndRetryOOM(RetryOOM):
    """Retries alone could not fit the attempt: the input must split in
    half by rows (the reference's ``SplitAndRetryOOM``). Escapes to the
    user only from sites that cannot split — the message names the site."""

    def __init__(self, site: Optional[str] = None, detail: str = ""):
        self.site = site
        msg = "retries exhausted and the input cannot be split"
        if site:
            msg += f" at retry site '{site}'"
        if detail:
            msg += f": {detail}"
        super().__init__(msg)


#: Substrings identifying device memory exhaustion in backend errors
#: (XlaRuntimeError carries the grpc-style RESOURCE_EXHAUSTED code).
_OOM_MARKERS = ("RESOURCE_EXHAUSTED", "Resource exhausted",
                "resource exhausted", "out of memory", "Out of memory",
                "OUT_OF_MEMORY", "HBM space exhausted")

#: Substrings identifying transient infrastructure races: a compile
#: service's failure markers (the synthetic faults of
#: utils/fault_injection.py emit them, so the tier-1 retry tests cover
#: this class), plus the pipeline pool's teardown
#: signals — a query racing a concurrent ``TpuSession.close()`` sees the
#: shared pool shut down under it, and the pool is lazily recreated, so
#: retrying in place succeeds (the serving layer's session-reaper relies
#: on this: retiring a crashed session must be a non-event for its
#: neighbors' in-flight queries; docs/serving.md).
_TRANSIENT_MARKERS = ("remote_compile", "tpu_compile_helper",
                      "pool is shut down", "pool shut down while")

#: OSError shapes that are DETERMINISTIC user errors (missing input path,
#: permissions, write target already exists), not I/O flakiness —
#: retrying only delays the real message.
_DETERMINISTIC_OS_ERRORS = (FileNotFoundError, PermissionError,
                            FileExistsError, IsADirectoryError,
                            NotADirectoryError)


def classify(exc: BaseException) -> str:
    """Classify an exception into the retry taxonomy (see module doc)."""
    from ..utils.deadline import QueryDeadlineExceeded
    if isinstance(exc, QueryDeadlineExceeded):
        # A deadline is a user contract, not a fault: retrying through it
        # would spend wall time the user explicitly capped.
        return Classification.FATAL
    if isinstance(exc, RetryOOM):
        return Classification.OOM
    from ..parallel.mesh import MeshDegradedError
    if isinstance(exc, MeshDegradedError):
        # Device/host loss mid-SPMD-dispatch (ISSUE 19): the session
        # marks the mesh degraded before this classifies, so the re-run
        # plans the surviving work onto the single-chip path — a slower
        # correct answer, never a wrong one.
        return Classification.TRANSIENT
    from concurrent.futures import CancelledError
    if isinstance(exc, CancelledError):
        # The only canceller of pipeline futures is pool shutdown (a
        # concurrent TpuSession.close); the pool lazily recreates, so a
        # retry in place lands on fresh workers. CancelledError derives
        # from BaseException on modern Pythons — wait sites translate it
        # (exec/pipeline.PoolShutdownError), this arm covers any that
        # escapes raw.
        return Classification.TRANSIENT
    msg = str(exc)
    if any(m in msg for m in _OOM_MARKERS):
        return Classification.OOM
    # Spill-disk I/O failures (full/slow disk, vanished spill file) are
    # worth a bounded in-place retry; so are the remote-compile races.
    # Deterministic path errors are not — they reproduce identically.
    if isinstance(exc, OSError) \
            and not isinstance(exc, _DETERMINISTIC_OS_ERRORS):
        return Classification.TRANSIENT
    if any(m in msg for m in _TRANSIENT_MARKERS):
        return Classification.TRANSIENT
    return Classification.FATAL


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """Retry count + backoff shape, snapshotted from conf."""

    max_retries: int = 3
    backoff_base_ms: float = 10.0
    backoff_max_ms: float = 1000.0

    @classmethod
    def from_conf(cls, conf) -> "RetryPolicy":
        from ..config import (RETRY_BACKOFF_BASE_MS, RETRY_BACKOFF_MAX_MS,
                              RETRY_MAX_RETRIES)
        try:
            return cls(int(conf.get(RETRY_MAX_RETRIES)),
                       float(conf.get(RETRY_BACKOFF_BASE_MS)),
                       float(conf.get(RETRY_BACKOFF_MAX_MS)))
        except AttributeError:
            # Bare test contexts whose conf is not a TpuConf.
            return cls()

    def delay_seconds(self, site: str, attempt: int) -> float:
        """Capped exponential backoff with DETERMINISTIC jitter: the
        jitter fraction hashes (site, attempt), so a re-run of the same
        query faults and sleeps identically — retries must not make plan
        timing nondeterministic."""
        if self.backoff_base_ms <= 0:
            return 0.0
        raw = min(self.backoff_base_ms * (2.0 ** attempt),
                  self.backoff_max_ms)
        frac = (zlib.crc32(f"{site}:{attempt}".encode()) % 1000) / 1000.0
        return raw * (0.5 + 0.5 * frac) / 1000.0


def _policy_of(ctx) -> RetryPolicy:
    policy = getattr(ctx, "_retry_policy", None)
    if policy is None:
        policy = RetryPolicy.from_conf(getattr(ctx, "conf", None))
        try:
            ctx._retry_policy = policy
        except AttributeError:  # frozen/slots test doubles
            pass
    return policy


def synchronize_device() -> None:
    """Drain in-flight device work so buffers freed by the spill below are
    actually reusable before the retry (the cudaDeviceSynchronize step of
    the reference's retry loop). Best-effort: backends without an effects
    barrier just proceed."""
    try:
        import jax
        jax.effects_barrier()
    except Exception:  # tpu-lint: ignore - best-effort barrier, no classes
        pass


def spill_device_below(ctx, priority_ceiling: Optional[int] = None) -> int:
    """Push every spillable device buffer below ``priority_ceiling``
    (default: everything under on-deck priority) off the device, and drop
    the upload memo entirely — the forced device drain between OOM
    retries. The catalog drains victims in QoS order keyed by this
    query's :class:`~.spill.QosTag` (its OWN buffers first, then by
    tenant and deadline slack — an OOM ladder must not evict its
    neighbors' hot tables while its own spillable state suffices), with
    the copies overlapped off-lock on the spill-IO lane. Returns device
    bytes moved."""
    from . import spill as SP
    if priority_ceiling is None:
        priority_ceiling = SP.ACTIVE_ON_DECK_PRIORITY
    moved = 0
    catalog = getattr(ctx, "catalog", None)
    if catalog is not None:
        moved = catalog.spill_below(priority_ceiling,
                                    requester=getattr(ctx, "qos", None))
    from ..data import upload_cache
    moved += upload_cache.shrink_by(upload_cache.cache_bytes())
    return moved


def backoff_sleep(policy: RetryPolicy, site: str, attempt: int,
                  ctx=None, node: Optional[str] = None) -> None:
    """Sleep the policy's backoff for this attempt, accounting the block
    time to the node's ``retryBlockTimeNs``. An active query deadline
    bounds the sleep and cancels the retry once expired (a retry ladder
    must never outlive the user's wall-clock contract)."""
    delay = policy.delay_seconds(site, attempt)
    deadline = getattr(ctx, "deadline", None)
    if deadline is not None:
        deadline.check(site, ctx, node)
        delay = deadline.bound(delay)
    if delay <= 0:
        return
    from ..metrics import trace as TR
    t0 = time.perf_counter_ns()
    with TR.span(getattr(ctx, "trace", None), "retry.backoff", cat="retry",
                 site=site, attempt=attempt), \
            lockdep.blocking("retry.backoff_sleep"):
        time.sleep(delay)
    if ctx is not None and node is not None:
        ctx.metric(node, "retryBlockTimeNs", time.perf_counter_ns() - t0)


def halve_by_rows(batch):
    """Split one device ``ColumnarBatch`` into two row-halves (the
    ``splitSpillableInHalfByRows`` analog). Materializes lazy batches
    first (slicing is positional), so it must only run on the failure
    path. Raises :class:`SplitAndRetryOOM` when the batch is too small to
    split further."""
    import jax
    import jax.numpy as jnp

    from ..data.column import bucket_capacity
    from ..exec.external_sort import _slice_kernel
    from ..ops.kernels import rowops as KR
    batch = KR.physical_jit(batch)
    n = int(jax.device_get(batch.n_rows))
    if n < _MIN_SPLIT_ROWS:
        raise SplitAndRetryOOM(
            detail=f"a {n}-row batch cannot be halved")
    slice_k = _slice_kernel(batch.schema)
    k = n // 2
    first = slice_k(batch, jnp.asarray(0, jnp.int32),
                    jnp.asarray(k, jnp.int32),
                    bucket_capacity(max(k, 128)))
    second = slice_k(batch, jnp.asarray(k, jnp.int32),
                     jnp.asarray(n - k, jnp.int32),
                     bucket_capacity(max(n - k, 128)))
    return [first, second]


class SplitTracker:
    """Wraps a split function and remembers whether it ever ran. Join
    sites consult :attr:`split_happened` inside their attempt to suppress
    capacity learning on fragments — a half batch's match total would
    under-teach the cached capacity of the full batch (see
    execs.join_batch)."""

    def __init__(self, split: Callable):
        self._split = split
        self.split_happened = False

    def __call__(self, item):
        self.split_happened = True
        return self._split(item)


def halve_list(items):
    """Split a list of inputs (batches or spill-catalog buffer ids) into
    its two halves; a single remaining item cannot split at the list
    level."""
    if len(items) < 2:
        raise SplitAndRetryOOM(
            detail="a single pending buffer cannot be split")
    k = len(items) // 2
    return [list(items[:k]), list(items[k:])]


def with_retry(ctx, site: str, inputs, attempt: Callable,
               split: Optional[Callable] = None,
               node: Optional[str] = None) -> List:
    """Run ``attempt(inputs)``, surviving classified OOM and transient
    faults (the ``withRetry`` / ``withRetryNoSplit`` combinator).

    Returns the list of results — one element normally; several after a
    split escalation (each fragment produced by ``split`` is processed
    with a fresh retry budget, so downstream consumers must accept a
    stream of results). ``split=None`` marks the site unsplittable:
    exhausted OOM retries raise :class:`SplitAndRetryOOM` naming it.

    The success path adds no device fences and no syncs — classification,
    spilling, and splitting all live on the failure path. Under
    whole-stage fusion tracing the combinator is a passthrough (tracers
    cannot be retried, and injection inside a trace would poison the
    cached program).

    ``node`` keys the retry metrics in the registry (defaults to the site
    name up to the first dot, the wrapping exec's node_name()).
    """
    if node is None:
        node = site.split(".", 1)[0]
    if getattr(ctx, "in_fusion", False):
        return [attempt(inputs)]
    from ..utils.fault_injection import register_site
    register_site(site)
    injector = getattr(ctx, "fault_injector", None)
    deadline = getattr(ctx, "deadline", None)
    policy = _policy_of(ctx)
    work: List = [inputs]
    results: List = []
    attempts_total = 0
    while work:
        item = work.pop(0)
        retries = 0
        while True:
            attempts_total += 1
            if attempts_total > _MAX_ATTEMPTS_PER_CALL:
                raise RetryOOM(
                    f"retry site '{site}' exceeded "
                    f"{_MAX_ATTEMPTS_PER_CALL} attempts (runaway fault "
                    "schedule or unrecoverable memory pressure)")
            t0 = time.perf_counter_ns()
            try:
                if deadline is not None:
                    deadline.check(site, ctx, node)
                if injector is not None:
                    injector.check(site)
                results.append(attempt(item))
                break
            except Exception as e:  # noqa: BLE001 - classified below
                cls = classify(e)
                if cls == Classification.FATAL:
                    raise
                ctx.metric(node, "retryWastedComputeNs",
                           time.perf_counter_ns() - t0)
                if cls == Classification.OOM:
                    # The lock covers ONLY the device sync (ISSUE 11);
                    # the spill-down runs off-lock — the catalog's state
                    # machine makes concurrent drains safe, so one
                    # query's recovery never queues behind a neighbor's
                    # disk write.
                    from ..metrics import trace as TR
                    with TR.span(getattr(ctx, "trace", None),
                                 "retry.oom_recovery", cat="retry",
                                 site=site):
                        with _OOM_RECOVERY_LOCK:
                            synchronize_device()
                        spill_device_below(ctx)
                    if retries >= policy.max_retries:
                        if split is None:
                            raise SplitAndRetryOOM(site) from e
                        try:
                            halves = split(item)
                        except SplitAndRetryOOM as se:
                            raise SplitAndRetryOOM(site, str(se)) from e
                        except Exception as se:  # noqa: BLE001
                            # The split itself does device work (halving
                            # materializes + slices) at peak pressure; an
                            # OOM there must surface as this site's
                            # SplitAndRetryOOM, not escape raw.
                            if classify(se) == Classification.OOM:
                                raise SplitAndRetryOOM(
                                    site,
                                    f"splitting itself hit OOM: {se}"
                                ) from se
                            raise
                        _LOG.info("retry site %s: splitting input after "
                                  "%d OOM retries", site, retries)
                        ctx.metric(node, "splitAndRetryCount", 1)
                        work[:0] = halves
                        break
                elif retries >= policy.max_retries:
                    raise
                ctx.metric(node, "retryCount", 1)
                backoff_sleep(policy, site, retries, ctx, node)
                retries += 1
    return results
