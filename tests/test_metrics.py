"""Query-profile layer tests (docs/monitoring.md):

* registry kinds/levels (GpuMetric analog): accumulation semantics, level
  gating, the NONE-is-inert contract;
* registry.timer exception safety (metric accumulates even when the body
  raises) and non-numeric merge (the seed's overwrite bug);
* the deprecated ExecContext.metrics dict shim (reads silent, writes warn);
* thread-safety hammer (warm-up + transport threads report concurrently);
* event-log round-trip and crash-safe append (torn lines isolated);
* deviceTiming off-by-default equivalence: bit-identical results and ZERO
  block-until-ready fences on the default path;
* per-exec taxonomy completeness on the streaming path;
* the acceptance query: one TPC-H and one TPC-DS query at ESSENTIAL with
  an event-log dir produce QueryProfiles whose operator tree matches the
  physical plan and whose rows/bytes metrics are non-zero;
* explain(metrics=True) rendering and profile regression diffing;
* the tier-1 TPC-H smoke event log exported as a build artifact.
"""

import json
import os
import threading

import pytest

from spark_rapids_tpu.metrics import eventlog
from spark_rapids_tpu.metrics.profile import (QueryProfile, compare_profiles,
                                              plan_profile_hash)
from spark_rapids_tpu.metrics.registry import (DEBUG, ESSENTIAL, MODERATE,
                                               NONE, TAXONOMY, MetricKind,
                                               MetricsRegistry, parse_level,
                                               taxonomy_markdown)
from spark_rapids_tpu.ops import aggregates as AGG
from spark_rapids_tpu.ops.expression import col, lit
from spark_rapids_tpu.session import TpuSession

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _simple_df(s, n=300):
    return (s.create_dataframe({"k": [1, 2, 3] * (n // 3),
                                "v": list(range(n))})
            .where(col("v") > lit(10))
            .group_by(col("k"))
            .agg(AGG.AggregateExpression(AGG.Sum(col("v")), "sv")))


def _tpch_q3(s):
    from spark_rapids_tpu.workloads import tpch
    return tpch.QUERIES["q3"](tpch.load(s, tpch.gen_tables(1 << 9, seed=4)))


#: the queries the fence tests run: one aggregate, and joins + aggregate +
#: top-k in one fused program
_FENCE_QUERIES = pytest.mark.parametrize(
    "query", [_simple_df, _tpch_q3], ids=["simple", "tpch-q3"])


class TestRegistry:
    def test_level_parsing(self):
        assert parse_level("none") == NONE
        assert parse_level("ESSENTIAL") == ESSENTIAL
        assert parse_level("Debug") == DEBUG
        # unknown / unset -> the reference's MODERATE default
        assert parse_level(None) == MODERATE
        assert parse_level("bogus") == MODERATE

    def test_sum_and_nano_timing_accumulate(self):
        r = MetricsRegistry(DEBUG)
        r.add("N", "numOutputRows", 3)
        r.add("N", "numOutputRows", 4)
        r.add("N", "opTime", 100)
        r.add("N", "opTime", 50)
        m = r.node_metrics("N")
        assert m["numOutputRows"] == 7 and m["opTime"] == 150

    def test_peak_and_average_kinds(self):
        r = MetricsRegistry(DEBUG)
        for v in (5, 9, 2):
            r.add("N", "peakDeviceBytes", v)
            r.add("N", "avgBatchRows", v)
        m = r.node_metrics("N")
        assert m["peakDeviceBytes"] == 9          # PEAK keeps max
        assert m["avgBatchRows"] == pytest.approx(16 / 3)  # AVERAGE

    def test_level_gating_drops_above_level(self):
        r = MetricsRegistry(ESSENTIAL)
        r.add("N", "numOutputRows", 1)            # ESSENTIAL: kept
        r.add("N", "semaphoreWaitNs", 100)        # MODERATE: dropped
        r.add("N", "concatTime", 100)             # DEBUG: dropped
        assert set(r.node_metrics("N")) == {"numOutputRows"}
        r2 = MetricsRegistry(DEBUG)
        r2.add("N", "concatTime", 100)
        assert r2.node_metrics("N")["concatTime"] == 100

    def test_level_none_is_inert(self):
        r = MetricsRegistry(NONE)
        assert not r.enabled and not r.device_timing
        r.add("N", "numOutputRows", 1)
        assert r.snapshot() == {}

    def test_ad_hoc_names_record_at_moderate(self):
        r = MetricsRegistry(MODERATE)
        r.add("N", "aqeOutputPartitions", 4)
        assert r.node_metrics("N")["aqeOutputPartitions"] == 4
        assert MetricsRegistry(ESSENTIAL).records("aqeOutputPartitions") \
            is False

    def test_timer_is_exception_safe(self):
        r = MetricsRegistry(DEBUG)
        with pytest.raises(ValueError):
            with r.timer("N", "opTime"):
                raise ValueError("boom")
        assert r.node_metrics("N")["opTime"] > 0

    def test_gated_timer_records_nothing(self):
        r = MetricsRegistry(ESSENTIAL)
        with r.timer("N", "concatTime"):   # DEBUG-level, gated
            pass
        assert r.snapshot() == {}

    def test_thread_safety_hammer(self):
        r = MetricsRegistry(DEBUG)
        n_threads, n_iter = 8, 5000

        def work():
            for _ in range(n_iter):
                r.add("N", "numOutputBatches", 1)
                r.add("N", "opTime", 2)
        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        m = r.node_metrics("N")
        assert m["numOutputBatches"] == n_threads * n_iter
        assert m["opTime"] == 2 * n_threads * n_iter


class TestRegistryTimer:
    """The NanoTimer cases, moved with its body into registry.timer."""

    def test_exception_still_accumulates(self):
        r = MetricsRegistry(DEBUG)
        with pytest.raises(RuntimeError):
            with r.timer("N", "ns", trace="t"):
                raise RuntimeError("body failed")
        assert r.node_metrics("N")["ns"] > 0

    def test_non_numeric_existing_value_merges_not_raises(self):
        r = MetricsRegistry(DEBUG)
        r.set_value("N", "ns", "corrupt")
        with r.timer("N", "ns", trace="t"):
            pass
        ns = r.node_metrics("N")["ns"]
        assert isinstance(ns, int) and ns > 0

    def test_registry_sink(self):
        from spark_rapids_tpu.metrics import trace as TR
        r = MetricsRegistry(DEBUG)
        tracer = TR.Tracer("t-registry-timer")
        with r.timer("N", "opTime", trace="t", owner=tracer):
            pass
        assert r.node_metrics("N")["opTime"] > 0
        assert [s["name"] for s in tracer.spans] == ["t"]


class TestLegacyDictShim:
    def _ctx(self):
        from spark_rapids_tpu.config import TpuConf
        from spark_rapids_tpu.plan.physical import ExecContext
        return ExecContext(TpuConf())

    def test_reads_are_silent_and_dictlike(self):
        ctx = self._ctx()
        ctx.metric("NodeA", "numOutputRows", 5)
        assert "NodeA" in ctx.metrics
        assert set(ctx.metrics) == {"NodeA"}
        assert ctx.metrics.get("NodeA", {}).get("numOutputRows") == 5
        assert ctx.metrics.get("Missing", {}) == {}
        assert dict(ctx.metrics["NodeA"].items())["numOutputRows"] == 5

    def test_direct_mutation_warns_but_works(self):
        ctx = self._ctx()
        with pytest.warns(DeprecationWarning):
            ctx.metrics["NodeA"]["custom"] = 7
        assert ctx.metrics["NodeA"]["custom"] == 7

    def test_metric_is_thread_safe_on_context(self):
        ctx = self._ctx()

        def work():
            for _ in range(2000):
                ctx.metric("N", "numOutputBatches", 1)
        ts = [threading.Thread(target=work) for _ in range(6)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert ctx.metrics["N"]["numOutputBatches"] == 12000


class TestEventLog:
    def _profile_dict(self, qid=1):
        return QueryProfile(
            query_id=qid, plan_hash="abc", wall_ns=123, level="ESSENTIAL",
            tree={"name": "Root", "describe": "Root", "metrics": {},
                  "children": []},
            extras={}, engine={}).to_dict()

    def test_round_trip(self, tmp_path):
        log = eventlog.EventLog(str(tmp_path))
        assert log.append(self._profile_dict(1))
        assert log.append(self._profile_dict(2))
        recs = eventlog.read(log.path)
        assert [r["query_id"] for r in recs] == [1, 2]
        prof = QueryProfile.from_dict(recs[0])
        assert prof.plan_hash == "abc" and prof.tree["name"] == "Root"

    def test_crash_safe_append_skips_torn_line(self, tmp_path):
        log = eventlog.EventLog(str(tmp_path))
        log.append(self._profile_dict(1))
        # Simulate a writer crash: torn half-record, no trailing newline.
        with open(log.path, "a") as f:
            f.write('{"query_id": 99, "tr')
        log.append(self._profile_dict(2))
        recs = eventlog.read(log.path)
        assert [r["query_id"] for r in recs] == [1, 2]

    def test_append_failure_is_swallowed(self, tmp_path):
        log = eventlog.EventLog(str(tmp_path / "as_file"))
        # Make the "directory" an existing file: makedirs/open must fail.
        (tmp_path / "as_file").write_text("not a dir")
        assert log.append(self._profile_dict()) is False


class TestDeviceTimingAndEquivalence:
    @_FENCE_QUERIES
    def test_no_fences_by_default_and_bit_identical(self, monkeypatch,
                                                    query):
        import jax
        fences = []
        orig = jax.block_until_ready

        def counting(x):
            fences.append(1)
            return orig(x)
        monkeypatch.setattr(jax, "block_until_ready", counting)

        off = TpuSession({"spark.rapids.sql.enabled": True,
                          "spark.rapids.sql.variableFloatAgg.enabled": True,
                          "spark.rapids.tpu.metrics.level": "NONE"})
        got_off = query(off).collect()
        assert not fences, "metrics disabled must insert zero fences"

        ess = TpuSession({"spark.rapids.sql.enabled": True,
                          "spark.rapids.sql.variableFloatAgg.enabled": True,
                          "spark.rapids.tpu.metrics.level": "ESSENTIAL"})
        got_ess = query(ess).collect()
        assert not fences, \
            "metrics WITHOUT deviceTiming must still insert zero fences"
        assert got_off.equals(got_ess), "metrics must not perturb results"
        assert off.last_query_profile() is None
        prof = ess.last_query_profile()
        assert prof is not None
        assert "deviceTime" not in json.dumps(prof.to_dict()), \
            "no device time without the fence that measures it"

    @_FENCE_QUERIES
    def test_device_timing_records_fenced_device_time(self, monkeypatch,
                                                      query):
        import jax
        fences = []
        orig = jax.block_until_ready

        def counting(x):
            fences.append(1)
            return orig(x)
        monkeypatch.setattr(jax, "block_until_ready", counting)
        s = TpuSession({"spark.rapids.sql.enabled": True,
                        "spark.rapids.sql.variableFloatAgg.enabled": True,
                        "spark.rapids.tpu.metrics.level": "ESSENTIAL",
                        "spark.rapids.tpu.metrics.deviceTiming": "true"})
        got = query(s).collect()
        assert got.num_rows > 0
        assert fences, "deviceTiming=true must fence the fused dispatch"
        prof = s.last_query_profile()
        assert prof.extras["WholeStageFusion"]["deviceTime"] > 0


class TestStreamingInstrumentation:
    def test_taxonomy_completeness_per_exec_node(self):
        """Every exec on the streaming path registers its ESSENTIAL
        numOutputBatches (the runtime counterpart of the exec-no-metrics
        lint ratchet)."""
        from spark_rapids_tpu.plan.logical import SortOrder
        s = TpuSession({"spark.rapids.sql.enabled": True,
                        "spark.rapids.tpu.fusion.enabled": False,
                        "spark.rapids.tpu.metrics.level": "MODERATE"})
        probe = s.create_dataframe({"k": [1, 2, 3, 4] * 50,
                                    "v": list(range(200))})
        build = s.create_dataframe({"k": [1, 2, 3, 4],
                                    "w": [10, 20, 30, 40]})
        df = (probe.where(col("v") > lit(5))
              .join(build, on="k", how="inner")
              .group_by(col("k"))
              .agg(AGG.AggregateExpression(AGG.Sum(col("w")), "sw"))
              .sort(SortOrder(col("k"))))
        df.collect()
        prof = s.last_query_profile()
        seen = {}

        def walk(node):
            seen[node["name"]] = node["metrics"]
            for c in node["children"]:
                walk(c)
        walk(prof.tree)
        # The small build side plans as a broadcast hash join (the
        # TpuShuffledHashJoinExec core with a broadcast build).
        for node in ("TpuFilterExec", "TpuProjectExec",
                     "TpuBroadcastHashJoinExec", "TpuHashAggregateExec",
                     "TpuSortExec", "HostToDeviceExec", "DeviceToHostExec"):
            assert node in seen, sorted(seen)
            assert seen[node].get("numOutputBatches", 0) >= 1, \
                (node, seen[node])
        assert seen["HostToDeviceExec"]["uploadBytes"] > 0
        assert seen["DeviceToHostExec"]["downloadBytes"] > 0
        assert seen["DeviceToHostExec"]["numOutputRows"] == 4
        assert seen["TpuBroadcastHashJoinExec"]["buildTime"] > 0
        assert seen["TpuBroadcastExchangeExec"]["dataSize"] > 0

    def test_essential_level_drops_moderate_metrics(self):
        s = TpuSession({"spark.rapids.sql.enabled": True,
                        "spark.rapids.tpu.fusion.enabled": False,
                        "spark.rapids.tpu.metrics.level": "ESSENTIAL"})
        _simple_df(s).collect()
        prof = s.last_query_profile()
        flat = {}

        def walk(node):
            flat.update({(node["name"], k): v
                         for k, v in node["metrics"].items()})
            for c in node["children"]:
                walk(c)
        walk(prof.tree)
        assert ("HostToDeviceExec", "uploadBytes") in flat
        # numInputRows is MODERATE: gated out at ESSENTIAL
        assert ("HostToDeviceExec", "numInputRows") not in flat


class TestAcceptanceQueries:
    """ISSUE acceptance: one TPC-H and one TPC-DS query at ESSENTIAL with
    an event-log dir produce QueryProfiles whose tree matches the physical
    plan and whose row/byte metrics are non-zero where applicable."""

    def _check(self, session, df, log_dir):
        got = df.collect()
        assert got.num_rows > 0
        prof = session.last_query_profile()
        assert prof is not None and prof.level == "ESSENTIAL"
        # Operator tree matches the physical plan (same shape + names).
        physical = session.plan(df._plan)

        def match(node, plan):
            assert node["name"] == plan.node_name(), \
                (node["name"], plan.node_name())
            assert len(node["children"]) == len(plan.children)
            for c_node, c_plan in zip(node["children"], plan.children):
                match(c_node, c_plan)
        match(prof.tree, physical)
        assert prof.plan_hash == plan_profile_hash(
            __import__("spark_rapids_tpu.utils.kernel_cache",
                       fromlist=["plan_signature"]).plan_signature(physical))
        flat = {}

        def walk(node):
            for k, v in node["metrics"].items():
                flat[k] = flat.get(k, 0) + v
            for c in node["children"]:
                walk(c)
        walk(prof.tree)
        assert flat.get("numOutputRows", 0) > 0
        assert flat.get("uploadBytes", 0) > 0, flat
        assert flat.get("downloadBytes", 0) > 0, flat
        assert prof.engine["spillBytes"] >= 0
        recs = eventlog.read(os.path.join(log_dir, eventlog.FILENAME))
        assert recs and recs[-1]["plan_hash"] == prof.plan_hash
        return prof

    def test_tpch_q6_profile(self, tmp_path):
        from spark_rapids_tpu.workloads import tpch
        log_dir = str(tmp_path / "events")
        s = TpuSession({"spark.rapids.sql.enabled": True,
                        "spark.rapids.sql.variableFloatAgg.enabled": True,
                        "spark.rapids.tpu.metrics.level": "ESSENTIAL",
                        "spark.rapids.tpu.metrics.eventLog.dir": log_dir})
        tables = tpch.gen_tables(1 << 12, seed=7)
        t = tpch.load(s, tables, cache=False)   # uncached: uploads visible
        self._check(s, tpch.QUERIES["q6"](t), log_dir)

    def test_tpcds_q3_profile(self, tmp_path):
        from spark_rapids_tpu.workloads import tpcds
        log_dir = str(tmp_path / "events")
        s = TpuSession({"spark.rapids.sql.enabled": True,
                        "spark.rapids.sql.variableFloatAgg.enabled": True,
                        "spark.rapids.tpu.metrics.level": "ESSENTIAL",
                        "spark.rapids.tpu.metrics.eventLog.dir": log_dir})
        tables = tpcds.gen_tables(1 << 12, seed=7)
        t = tpcds.load(s, tables, cache=False)
        self._check(s, tpcds.q3(t), log_dir)


class TestExplainMetrics:
    def test_explain_metrics_renders_last_profile(self, capsys):
        s = TpuSession({"spark.rapids.sql.enabled": True,
                        "spark.rapids.tpu.metrics.level": "MODERATE"})
        df = _simple_df(s)
        text = df.explain(metrics=True)
        assert "no QueryProfile recorded" in text
        df.collect()
        text = df.explain(metrics=True)
        assert "Query Profile" in text
        assert "uploadBytes=" in text
        assert "DeviceToHostExec" in text

    def test_other_plan_shape_does_not_match(self):
        s = TpuSession({"spark.rapids.sql.enabled": True,
                        "spark.rapids.tpu.metrics.level": "MODERATE"})
        _simple_df(s).collect()
        other = s.create_dataframe({"a": [1, 2]}).where(col("a") > lit(1))
        assert "no QueryProfile recorded" in s.explain_metrics(other._plan)


class TestCompareProfiles:
    def _prof(self, op_ns):
        return {"tree": {"name": "Root", "describe": "Root",
                         "metrics": {"opTime": op_ns, "numOutputRows": 10},
                         "children": [
                             {"name": "Child", "describe": "Child",
                              "metrics": {"opTime": 5_000_000},
                              "children": []}]},
                "extras": {}}

    def test_flags_large_regression_only(self):
        regs = compare_profiles(self._prof(10_000_000),
                                self._prof(20_000_000))
        assert [r["path"] for r in regs] == ["Root"]
        assert regs[0]["metric"] == "opTime"
        assert regs[0]["ratio"] == pytest.approx(2.0)

    def test_noise_floor_and_threshold(self):
        # +15% is under the 20% threshold; +0.5ms is under the 1ms floor.
        assert compare_profiles(self._prof(10_000_000),
                                self._prof(11_500_000)) == []
        small_old = self._prof(1_000_000)
        small_new = self._prof(1_500_000)
        assert compare_profiles(small_old, small_new) == []

    def test_counts_never_flagged(self):
        newer = self._prof(10_000_000)
        newer["tree"]["metrics"]["numOutputRows"] = 10_000
        assert compare_profiles(self._prof(10_000_000), newer) == []


class TestArtifacts:
    def test_tpch_smoke_event_log_build_artifact(self):
        """Tier-1 exports the TPC-H smoke query's event log as a build
        artifact (artifacts/tpch_smoke/query_profiles.jsonl; gitignored,
        uploaded by the CI run)."""
        from spark_rapids_tpu.workloads import tpch
        art_root = os.environ.get("SRTPU_ARTIFACT_DIR",
                                  os.path.join(REPO, "artifacts"))
        log_dir = os.path.join(art_root, "tpch_smoke")
        path = os.path.join(log_dir, eventlog.FILENAME)
        if os.path.exists(path):
            os.remove(path)   # fresh log per tier-1 run
        s = TpuSession({"spark.rapids.sql.enabled": True,
                        "spark.rapids.sql.variableFloatAgg.enabled": True,
                        "spark.rapids.tpu.metrics.level": "ESSENTIAL",
                        "spark.rapids.tpu.metrics.eventLog.dir": log_dir})
        tables = tpch.gen_tables(1 << 12, seed=11)
        t = tpch.load(s, tables, cache=False)
        tpch.QUERIES["q6"](t).collect()
        recs = eventlog.read(path)
        assert len(recs) == 1
        assert recs[0]["level"] == "ESSENTIAL"
        # The artifact is valid single-line JSON (one record per line).
        with open(path) as f:
            lines = [ln for ln in f.read().splitlines() if ln.strip()]
        assert len(lines) == 1 and json.loads(lines[0])


class TestDocsInSync:
    def test_monitoring_doc_taxonomy_table_is_current(self):
        path = os.path.join(REPO, "docs", "monitoring.md")
        assert taxonomy_markdown() in open(path).read(), \
            "docs/monitoring.md taxonomy table is stale; regenerate from " \
            "spark_rapids_tpu.metrics.taxonomy_markdown()"

    def test_every_taxonomy_timing_is_nano(self):
        for name, spec in TAXONOMY.items():
            if name.endswith("Time") or name.endswith("Ns"):
                assert spec.kind == MetricKind.NANO_TIMING, name
