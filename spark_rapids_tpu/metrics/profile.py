"""Per-query profiles: the operator tree annotated with its metrics.

A :class:`QueryProfile` snapshots, at query end:

* the physical operator tree (node names + describe strings) with each
  node's metrics from the query's :class:`~.registry.MetricsRegistry`;
* "extra" metric nodes that are not plan operators (WholeStageFusion,
  TpuSemaphore) — work the plan tree cannot attribute;
* engine-level counters folded in from the other subsystems: spill-catalog
  byte deltas (memory/spill.py), semaphore wait, HBM watermarks
  (memory/device_manager.py), and the compile-once layer's counters
  (utils/kernel_cache.py, compile/executables.py, compile/warmup.py) — the
  PR-2 counters now reporting through the same profile instead of their own
  side channels.

Profiles serialize to one JSON line in the event log
(:mod:`.eventlog`), render as a metric-annotated EXPLAIN tree
(``df.explain(metrics=True)`` / ``TpuSession.last_query_profile()``), and
diff against an earlier run (:func:`compare_profiles`) — the regression
ratchet ``tools/profile_bench.py --compare`` runs on.

Metrics are keyed by node_name(), so two instances of the same exec type in
one plan share accumulators; the render marks repeated names with ``*``.
"""

from __future__ import annotations

import dataclasses
import hashlib
import time
from typing import Dict, List, Optional

from .registry import NONE, MetricsRegistry, level_name, parse_level

#: Profile schema version (bump on incompatible event-log layout changes).
VERSION = 1

#: Durability counters (ISSUE 7) summed across nodes into the engine
#: section — ALSO the list TpuSession harvests from attempts discarded by
#: the join-sizing re-run ladder (one list, or a new counter silently
#: stops surviving dispatch retries).
DURABILITY_COUNTERS = ("checksumFailures", "shuffleBlocksRefetched",
                       "mapTasksRecomputed", "deadlineCancels",
                       "peersBlacklisted", "hedgedFetches", "hedgeWins",
                       "replicaReads", "meshFailovers")

#: The subset of DURABILITY_COUNTERS the profile reads from process-wide
#: stats deltas instead of the per-query registry (they span discarded
#: dispatch attempts natively, so the session must NOT also carry them).
PROCESS_DELTA_COUNTERS = ("checksumFailures",)


def plan_profile_hash(plan_sig: tuple) -> str:
    """Short stable hash of a structural plan signature
    (utils.kernel_cache.plan_signature output) — lets explain(metrics=True)
    check that the last profile belongs to THIS query shape."""
    return hashlib.sha256(repr(plan_sig).encode()).hexdigest()[:16]


@dataclasses.dataclass
class QueryProfile:
    """One executed query's observability record."""

    query_id: int
    plan_hash: str
    wall_ns: int
    level: str
    #: nested {"name", "describe", "metrics": {..}, "children": [..]}
    tree: dict
    #: metric nodes with no plan operator: {node: {name: value}}
    extras: Dict[str, dict]
    #: engine counters: spill/semaphore/hbm/compile sections
    engine: dict
    timestamp: str = ""
    version: int = VERSION
    #: the executing session's ``spark.rapids.tpu.tenantId`` (ISSUE 12):
    #: stamped into the header AND therefore into every event-log record,
    #: so per-tenant attribution (tools/serve_bench.py) groups profiles
    #: directly instead of joining against a side channel.
    tenant: str = ""

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "QueryProfile":
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in known})

    def totals(self) -> Dict[str, float]:
        """Every numeric metric of the profile summed by name: THE sum of
        a profile. Operators of one node name share one metrics entry
        (the registry keys by ``node_name()``), so each node name of the
        tree counts once, however many operators carry it; then every
        extras node. The engine section is not in it."""
        total: Dict[str, float] = {}
        seen: set = set()

        def add(metrics: dict) -> None:
            for key, value in metrics.items():
                if isinstance(value, (int, float)) \
                        and not isinstance(value, bool):
                    total[key] = total.get(key, 0) + value

        def walk(node: dict) -> None:
            if node["name"] not in seen:
                seen.add(node["name"])
                add(node["metrics"])
            for child in node["children"]:
                walk(child)
        walk(self.tree)
        for metrics in self.extras.values():
            add(metrics)
        return total

    # -- rendering ----------------------------------------------------------
    def render(self) -> str:
        """The metric-annotated EXPLAIN tree."""
        counts: Dict[str, int] = {}
        _count_names(self.tree, counts)
        tenant = f", tenant={self.tenant}" if self.tenant else ""
        lines = [f"== Query Profile #{self.query_id} "
                 f"(level={self.level}, wall={_fmt_ns(self.wall_ns)}"
                 f"{tenant}) =="]
        _render_node(self.tree, 0, counts, lines)
        shared = sorted(n for n, c in counts.items() if c > 1)
        if shared:
            lines.append("(* metrics are keyed by node name and shared by "
                         f"repeated operators: {', '.join(shared)})")
        for node in sorted(self.extras):
            lines.append(f"+ {node}  {_fmt_metrics(self.extras[node])}")
        eng = {k: v for k, v in self.engine.items() if not isinstance(v, dict)}
        if eng:
            lines.append(f"+ engine  {_fmt_metrics(eng)}")
        comp = self.engine.get("compile")
        if comp:
            lines.append(f"+ compile  {_fmt_metrics(comp)}")
        dur = self.engine.get("durability")
        if dur:
            lines.append(f"+ durability  {_fmt_metrics(dur)}")
        mlsec = self.engine.get("ml")
        if mlsec and any(v for v in mlsec.values()
                         if isinstance(v, (int, float))):
            lines.append(f"+ ml  {_fmt_metrics(mlsec)}")
        return "\n".join(lines) + "\n"


def _count_names(node: dict, counts: Dict[str, int]) -> None:
    counts[node["name"]] = counts.get(node["name"], 0) + 1
    for c in node["children"]:
        _count_names(c, counts)


def _render_node(node: dict, indent: int, counts, lines: List[str]) -> None:
    star = "*" if counts.get(node["name"], 0) > 1 and node["metrics"] else ""
    tail = f"  {_fmt_metrics(node['metrics'])}{star}" if node["metrics"] \
        else ""
    lines.append("  " * indent + node["describe"] + tail)
    for c in node["children"]:
        _render_node(c, indent + 1, counts, lines)


def _fmt_ns(v) -> str:
    return f"{v / 1e6:.1f}ms"


def _fmt_metrics(metrics: dict) -> str:
    parts = []
    for name in sorted(metrics):
        v = metrics[name]
        if isinstance(v, dict):
            continue
        if (name.endswith("Ns") or name.endswith("Time")) \
                and isinstance(v, (int, float)):
            parts.append(f"{name}={_fmt_ns(v)}")
        elif isinstance(v, float):
            parts.append(f"{name}={v:.2f}")
        else:
            parts.append(f"{name}={v}")
    return "[" + ", ".join(parts) + "]"


# ---------------------------------------------------------------------------
# Collection
# ---------------------------------------------------------------------------


class QueryProfiler:
    """Brackets one query execution: captures engine-counter baselines at
    start, snapshots the registry + deltas at finish. Created only when the
    metrics level is above NONE — at NONE nothing is measured at all."""

    def __init__(self, session):
        self._session = session
        self._t0 = time.perf_counter_ns()
        from ..compile import executables as _exe
        from ..compile import warmup as _warmup
        from ..compile import xla_events as _xla
        from ..utils import checksum as _ck
        from ..utils import kernel_cache as _kc
        self._kc0 = _kc.cache_stats()
        self._xla0 = _xla.stats()
        self._exe0 = _exe.stats()
        self._warm0 = _warmup.stats()
        self._ck0 = _ck.stats()
        dm = session.device_manager
        self._spill0 = dict(dm.catalog.metrics)
        self._sem0 = dm.semaphore.wait_ns

    @classmethod
    def maybe(cls, session) -> Optional["QueryProfiler"]:
        if parse_level(session.conf.metrics_level) == NONE:
            return None
        return cls(session)

    def finish(self, physical, ctx, plan_sig: tuple,
               query_id: int) -> QueryProfile:
        import datetime

        from ..compile import executables as _exe
        from ..compile import warmup as _warmup
        from ..compile import xla_events as _xla
        from ..utils import checksum as _ck
        from ..utils import kernel_cache as _kc
        wall_ns = time.perf_counter_ns() - self._t0
        registry: MetricsRegistry = ctx.registry
        # What JAX compiled over this query (process totals' delta, as
        # the kernel cache's): on the TpuSession node too, because
        # readers that sum the profile by node (QueryProfile.totals) do
        # not read the engine section.
        xla = _xla.stats()
        xla_compile = {
            "xlaCompileNs": _delta(xla, self._xla0, "compile_ns"),
            "xlaCompiles": _delta(xla, self._xla0, "compiles"),
            "persistentCacheHits": _delta(xla, self._xla0, "cache_hits"),
            "persistentCacheMisses": _delta(xla, self._xla0,
                                            "cache_misses"),
        }
        for cname, value in xla_compile.items():
            registry.add("TpuSession", cname, value)
        tree = _tree_of(physical, registry)
        tree_names: set = set()
        _collect_names(tree, tree_names)
        extras = {node: registry.node_metrics(node)
                  for node in registry.node_names()
                  if node not in tree_names}

        dm = self._session.device_manager
        spill = dm.catalog.metrics
        kc = _kc.cache_stats()
        exe = _exe.stats()
        warm = _warmup.stats()
        ck = _ck.stats()
        engine = {
            "semaphoreWaitNs": dm.semaphore.wait_ns - self._sem0,
            "spillBytes":
                _delta(spill, self._spill0, "spill_bytes_to_host")
                + _delta(spill, self._spill0, "spill_bytes_to_disk"),
            "spillBytesToHost":
                _delta(spill, self._spill0, "spill_bytes_to_host"),
            "spillBytesToDisk":
                _delta(spill, self._spill0, "spill_bytes_to_disk"),
            # Live size of the shared disk spill file (compaction keeps it
            # from leaking freed ranges — memory/spill.py).
            "diskSpillFileBytes": int(spill.get("disk_spill_file_bytes", 0)),
            # Async spill engine (ISSUE 11, docs/monitoring.md):
            # bytes-per-second through the off-lock spill-IO lane this
            # query (copies + restores; 0 when nothing spilled), the
            # process watermark of queued-not-finished lane units, and ns
            # this query's threads spent WAITING for the catalog lock —
            # the convoy detector that the old synchronous design kept
            # pegged during any spill.
            "spillThroughputBytesPerSec": _rate_per_sec(
                _delta(spill, self._spill0, "spill_io_bytes"),
                _delta(spill, self._spill0, "spill_io_ns")),
            "spillQueueDepth": int(spill.get("spill_queue_peak", 0)),
            "spillLockWaitNs": _delta(spill, self._spill0,
                                      "spill_lock_wait_ns"),
            "deviceStoreBytes": dm.catalog.device_bytes,
            **dm.hbm_watermarks(
                device_session=self._session.conf.sql_enabled),
            "compile": {
                "kernelBuildNs": _delta(kc, self._kc0, "build_ns"),
                **xla_compile,
                "kernelCompiles": _delta(kc, self._kc0, "misses"),
                "kernelHits": _delta(kc, self._kc0, "hits"),
                "fusedPrograms": exe.get("programs", 0),
                "aotExecutables": exe.get("aot_executables", 0),
                "aotHits": _delta(exe, self._exe0, "aot_hits"),
                "jitCalls": _delta(exe, self._exe0, "jit_calls"),
                # Polymorphic-tier counters (ISSUE 6): fused executables
                # actually compiled this query vs dispatches an existing
                # executable served (the cross-rung reuse the tier
                # padding buys), and the compile seconds paid.
                "fusedCompiles": _delta(exe, self._exe0, "jit_compiles"),
                "fusedCompileSeconds": round(
                    float(exe.get("compile_seconds", 0.0))
                    - float(self._exe0.get("compile_seconds", 0.0)), 3),
                "executablesReused":
                    _delta(exe, self._exe0, "aot_hits")
                    + _delta(exe, self._exe0, "jit_calls")
                    - _delta(exe, self._exe0, "jit_compiles"),
                "warmupCompiled": _delta(warm, self._warm0, "compiled"),
                "warmupSkippedCovered": _delta(warm, self._warm0,
                                               "skipped_covered"),
            },
            # ML scenario attribution (ISSUE 14, docs/monitoring.md):
            # rows exported to trainers, rows scored by ModelScore
            # operators (one deferred device read of the traced per-batch
            # counts — the hot path never synced), trainer wall seconds,
            # and registered-model HBM bytes — so serving/event-log
            # attribution covers ML work like every other subsystem.
            "ml": _ml_section(ctx),
            # Distributed-durability counters (ISSUE 7,
            # docs/fault-tolerance.md): a clean run reads all zeros; after
            # an injected or real fault the non-zero counters PROVE the
            # recovery machinery ran (bench.py surfaces them as the
            # per-query `faults` section).
            "durability": {
                # checksumFailures comes from the process-wide stats
                # delta (it spans discarded attempts natively); the rest
                # sum the per-query registry.
                "checksumVerified": _delta(ck, self._ck0, "verified"),
                **{name: (_delta(ck, self._ck0, "failures")
                          if name == "checksumFailures"
                          else _registry_total(registry, name))
                   for name in DURABILITY_COUNTERS},
            },
        }
        from ..config import TENANT_ID
        try:
            tenant = str(self._session.conf.get(TENANT_ID) or "")
        except Exception:  # noqa: BLE001 - attribution is an aid
            tenant = ""
        return QueryProfile(
            query_id=query_id,
            plan_hash=plan_profile_hash(plan_sig),
            wall_ns=wall_ns,
            level=level_name(registry.level),
            tree=tree,
            extras=extras,
            engine=engine,
            timestamp=datetime.datetime.now(datetime.timezone.utc)
            .isoformat(timespec="seconds"),
            tenant=tenant,
        )


def _delta(now: dict, base: dict, key: str) -> int:
    return int(now.get(key, 0)) - int(base.get(key, 0))


def _rate_per_sec(amount: int, ns: int) -> int:
    """amount / (ns as seconds), 0 when nothing was measured."""
    return int(amount * 1e9 / ns) if ns > 0 else 0


def _ml_section(ctx) -> dict:
    """The ``engine.ml`` section. ``scoreRows`` is PER QUERY (this
    query's ModelScore output, from the context's deferred traced
    counts — one device read here, zero syncs on the hot path). The
    export/train/model counters are process-CUMULATIVE: that work runs
    BETWEEN queries (the ETL→train handoff), so a per-query delta would
    always read zero — consecutive event-log records diff to attribute
    it, the same way a metrics scraper reads any monotonic counter."""
    from ..ml import registry as _mlreg
    now = _mlreg.stats()
    score_rows = 0
    vals = getattr(ctx, "ml_score_rows", None)
    if vals:
        try:
            import jax
            score_rows = int(sum(int(v) for v in jax.device_get(list(vals))))
        except Exception:  # noqa: BLE001 - attribution is an aid
            score_rows = 0
    return {
        "exportRows": int(now.get("export_rows", 0)),
        "scoreRows": score_rows,
        "trainSeconds": round(float(now.get("train_seconds", 0.0)), 3),
        "modelBytes": int(now.get("model_bytes", 0)),
        "modelsRegistered": int(now.get("models_registered", 0)),
    }


def _registry_total(registry: MetricsRegistry, name: str) -> int:
    """Sum one metric name across every node of a per-query registry
    (the durability counters are recorded under whichever operator hit
    the fault; the engine section wants the query total)."""
    total = 0
    for node in registry.node_names():
        v = registry.node_metrics(node).get(name)
        if isinstance(v, (int, float)):
            total += int(v)
    return total


def _tree_of(plan, registry: MetricsRegistry) -> dict:
    return {
        "name": plan.node_name(),
        "describe": plan.describe(),
        "metrics": registry.node_metrics(plan.node_name()),
        "children": [_tree_of(c, registry) for c in plan.children],
    }


def _collect_names(node: dict, out: set) -> None:
    out.add(node["name"])
    for c in node["children"]:
        _collect_names(c, out)


# ---------------------------------------------------------------------------
# Comparison (tools/profile_bench.py --compare)
# ---------------------------------------------------------------------------


def _flatten(node: dict, _path: str, out: Dict[str, dict]) -> None:
    # Keyed by node NAME, not tree position: metrics are shared by
    # node_name() across repeated operators (registry.py), so positional
    # keys would report the same shared accumulator once per duplicate and
    # inflate the regression count.
    out[node["name"]] = node["metrics"]
    for c in node["children"]:
        _flatten(c, _path, out)


def compare_profiles(old: dict, new: dict, threshold: float = 0.20,
                     min_ns: int = 1_000_000) -> List[dict]:
    """Per-operator regression diff of two profile dicts.

    Flags timing metrics (``*Time``/``*Ns``) that grew by more than
    ``threshold`` (default 20%) AND by more than ``min_ns`` (noise floor,
    default 1ms). Returns [{path, metric, old, new, ratio}] sorted by
    severity."""
    o_ops: Dict[str, dict] = {}
    n_ops: Dict[str, dict] = {}
    _flatten(old["tree"], "", o_ops)
    _flatten(new["tree"], "", n_ops)
    o_ops["<extras>"] = {k: v for m in old.get("extras", {}).values()
                         for k, v in m.items()}
    n_ops["<extras>"] = {k: v for m in new.get("extras", {}).values()
                         for k, v in m.items()}
    out: List[dict] = []
    for path, n_metrics in n_ops.items():
        o_metrics = o_ops.get(path)
        if o_metrics is None:
            continue
        for name, nv in n_metrics.items():
            if not (name.endswith("Time") or name.endswith("Ns")):
                continue
            ov = o_metrics.get(name)
            if not isinstance(ov, (int, float)) \
                    or not isinstance(nv, (int, float)) or ov <= 0:
                continue
            if nv - ov > min_ns and nv > ov * (1.0 + threshold):
                out.append({"path": path, "metric": name,
                            "old": ov, "new": nv,
                            "ratio": round(nv / ov, 3)})
    return sorted(out, key=lambda r: -r["ratio"])


def dump_profiles(path: str, profiles: Dict[str, QueryProfile]) -> None:
    """Write a {query name: profile dict} bundle (bench.py /
    tools/profile_bench.py emit these next to BENCH_*.json)."""
    import json
    data = {name: (p.to_dict() if isinstance(p, QueryProfile) else p)
            for name, p in profiles.items() if p is not None}
    with open(path, "w", encoding="utf-8") as f:
        json.dump(data, f, indent=1, default=str)
        f.write("\n")


def load_profiles(path: str) -> Dict[str, dict]:
    import json
    with open(path, encoding="utf-8") as f:
        data = json.load(f)
    if isinstance(data, dict) and "tree" in data:
        return {"query": data}  # a single bare profile
    return data
