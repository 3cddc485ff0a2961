"""Deterministic fault-injection tests (utils/fault_injection.py +
memory/retry.py, docs/fault-tolerance.md): injector determinism, the
per-unit reader host fallbacks under injected device faults, end-to-end
TPC-H smoke under OOM injection at every registered retry site
(bit-identical results, nonzero retry counters), split escalation, and
the zero-counter default path."""

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.session import TpuSession
from spark_rapids_tpu.utils.fault_injection import (FaultInjector,
                                                    InjectedFault,
                                                    known_sites)


def _inject_conf(sites="*", oom=0, transient=0, seed=0, **extra):
    conf = {
        "spark.rapids.sql.enabled": True,
        "spark.rapids.tpu.retry.backoffBaseMs": 0.0,
        "spark.rapids.tpu.test.faultInjection.sites": sites,
        "spark.rapids.tpu.test.faultInjection.oomEveryN": oom,
        "spark.rapids.tpu.test.faultInjection.transientEveryN": transient,
        "spark.rapids.tpu.test.faultInjection.seed": seed,
    }
    conf.update(extra)
    return conf


def _cpu():
    return TpuSession({"spark.rapids.sql.enabled": False})


def _sum_metric(profile, name):
    total = [0]

    def walk(node):
        total[0] += node["metrics"].get(name, 0)
        for c in node["children"]:
            walk(c)
    walk(profile.tree)
    for m in profile.extras.values():
        total[0] += m.get(name, 0)
    return total[0]


class TestInjectorSchedule:
    def _fault_visits(self, inj, site, n=24):
        out = []
        for i in range(1, n + 1):
            try:
                inj.check(site)
            except InjectedFault:
                out.append(i)
        return out

    def test_every_n_is_deterministic_and_seed_shifted(self):
        a = self._fault_visits(FaultInjector(0, "*", 3, 0), "s")
        b = self._fault_visits(FaultInjector(0, "*", 3, 0), "s")
        c = self._fault_visits(FaultInjector(1, "*", 3, 0), "s")
        assert a == b == [3, 6, 9, 12, 15, 18, 21, 24]
        assert c == [2, 5, 8, 11, 14, 17, 20, 23]

    def test_negative_n_faults_first_visits_then_heals(self):
        assert self._fault_visits(FaultInjector(0, "*", -3, 0), "s") \
            == [1, 2, 3]

    def test_site_matching(self):
        inj = FaultInjector(0, "io.parquet, TpuSortExec.sort", -1, 0)
        assert inj.matches("io.parquet.rowGroup")
        assert inj.matches("TpuSortExec.sort")
        assert not inj.matches("io.orc.stripe")

    def test_transient_flavors_are_deterministic(self):
        inj = FaultInjector(0, "*", 0, -8)
        self._fault_visits(inj, "s")
        assert inj.injected["oom"] == 0
        assert inj.injected["transient"] + inj.injected["disk"] == 8
        inj2 = FaultInjector(0, "*", 0, -8)
        self._fault_visits(inj2, "s")
        assert inj2.injected == inj.injected

    def test_disabled_conf_builds_no_injector(self):
        from spark_rapids_tpu.config import TpuConf
        assert FaultInjector.maybe(TpuConf({})) is None
        s = TpuSession({"spark.rapids.sql.enabled": True})
        assert s._fault_injector is None


def _reader_roundtrip(tmp_path, fmt, sites, fallback_metric):
    """Write a small file, read it with every device-decode visit
    faulting: the per-unit host fallback must produce bit-identical
    results and bump its fallback metric."""
    rng = np.random.default_rng(7)
    table = pa.table({
        "seq": np.arange(4000, dtype=np.int64),
        "v": rng.integers(-1000, 1000, 4000).astype(np.int64),
        "f": rng.normal(size=4000),
    })
    path = str(tmp_path / f"t.{fmt}")
    if fmt == "parquet":
        import pyarrow.parquet as pq
        pq.write_table(table, path, row_group_size=1000)
    elif fmt == "orc":
        import pyarrow.orc as orc
        orc.write_table(table, path)
    else:
        import pyarrow.csv as pacsv
        pacsv.write_csv(table, path)
    tpu = TpuSession(_inject_conf(sites=sites, oom=1))

    def q(s):
        # the device decoder swaps in under a device subtree (same
        # contract as test_orc_device's session-scan test)
        from spark_rapids_tpu.ops import predicates as P
        from spark_rapids_tpu.ops.expression import col, lit
        return getattr(s.read, fmt)(path).where(
            P.GreaterThanOrEqual(col("seq"), lit(0)))
    got = q(tpu).collect().sort_by("seq")
    want = q(_cpu()).collect().sort_by("seq")
    assert got.equals(want), f"{fmt} fallback result diverged from oracle"
    assert tpu._fault_injector.injected["oom"] > 0
    prof = tpu.last_query_profile()
    assert _sum_metric(prof, fallback_metric) > 0, prof.to_dict()


class TestReaderFallbacksUnderInjection:
    def test_parquet_row_group_fallback(self, tmp_path):
        _reader_roundtrip(tmp_path, "parquet", "io.parquet",
                          "hostFallbackRowGroups")

    def test_orc_stripe_fallback(self, tmp_path):
        _reader_roundtrip(tmp_path, "orc", "io.orc", "stripeHostFallback")

    def test_csv_file_fallback(self, tmp_path):
        _reader_roundtrip(tmp_path, "csv", "io.csv", "fileHostFallback")


class TestEndToEndInjection:
    def _join_query(self, s):
        from spark_rapids_tpu.ops import aggregates as AGG
        from spark_rapids_tpu.ops.expression import col
        rng = np.random.default_rng(3)
        probe = pa.RecordBatch.from_pydict({
            "k": rng.integers(0, 500, 6000).astype(np.int64),
            "v": rng.integers(0, 100, 6000).astype(np.int64)})
        build = pa.RecordBatch.from_pydict({
            "k": np.arange(500, dtype=np.int64),
            "w": np.arange(500, dtype=np.int64) * 10})
        p = s.create_dataframe(probe)
        b = s.create_dataframe(build)
        return (p.join(b, on="k", how="inner")
                .select(col("v"), col("w")).group_by(col("v"))
                .agg(AGG.AggregateExpression(AGG.Sum(col("w")), "sw"),
                     AGG.AggregateExpression(AGG.Count(), "c")))

    def test_oom_at_every_site_bit_identical_with_retries(self):
        # Every registered site's first visit OOMs (oomEveryN=-1); fusion
        # off so each operator boundary executes (and faults) eagerly.
        tpu = TpuSession(_inject_conf(
            sites="*", oom=-1, seed=0,
            **{"spark.rapids.tpu.fusion.enabled": False}))
        got = self._join_query(tpu).collect().sort_by("v")
        want = self._join_query(_cpu()).collect().sort_by("v")
        assert got.equals(want)
        assert tpu._fault_injector.injected["oom"] > 0
        prof = tpu.last_query_profile()
        assert _sum_metric(prof, "retryCount") > 0, prof.render()
        # every site the query visited got at least one injected OOM
        visited = [s for s in known_sites()
                   if tpu._fault_injector.visit_count(s) > 0]
        assert len(visited) >= 4, visited

    def test_split_and_retry_escalation(self):
        # First 4 probe visits fault with only 1 retry allowed: retries
        # exhaust and the probe batch splits in half by rows (twice),
        # then the halves heal — results stay bit-identical.
        tpu = TpuSession(_inject_conf(
            sites="TpuShuffledHashJoinExec.probe,"
                  "TpuBroadcastHashJoinExec.probe",
            oom=-4, seed=0,
            **{"spark.rapids.tpu.fusion.enabled": False,
               "spark.rapids.tpu.retry.maxRetries": 1}))
        got = self._join_query(tpu).collect().sort_by("v")
        want = self._join_query(_cpu()).collect().sort_by("v")
        assert got.equals(want)
        prof = tpu.last_query_profile()
        assert _sum_metric(prof, "splitAndRetryCount") > 0, prof.render()

    def test_transient_dispatch_faults_are_retried(self):
        tpu = TpuSession(_inject_conf(sites="session.dispatch",
                                      transient=-2))
        got = self._join_query(tpu).collect().sort_by("v")
        want = self._join_query(_cpu()).collect().sort_by("v")
        assert got.equals(want)
        flavors = tpu._fault_injector.injected
        assert flavors["transient"] + flavors["disk"] == 2
        # dispatch-level retries survive into the profiled (successful)
        # context even though the failed contexts are discarded
        prof = tpu.last_query_profile()
        assert prof.extras.get("TpuSession", {}).get("retryCount") == 2, \
            prof.to_dict()

    def test_injection_off_counters_read_zero(self):
        # The acceptance criterion's healthy half: with no injection the
        # default path records ZERO retry metrics and matches the oracle
        # (fence-freedom itself is asserted in test_metrics).
        tpu = TpuSession({"spark.rapids.sql.enabled": True})
        got = self._join_query(tpu).collect().sort_by("v")
        want = self._join_query(_cpu()).collect().sort_by("v")
        assert got.equals(want)
        prof = tpu.last_query_profile()
        for name in ("retryCount", "splitAndRetryCount",
                     "retryBlockTimeNs", "retryWastedComputeNs"):
            assert _sum_metric(prof, name) == 0, (name, prof.render())


class TestTpchSmokeUnderInjection:
    """The acceptance smoke: TPC-H queries complete bit-identically with
    at least one injected OOM at every retry site they visit."""

    #: (first visits that OOM, seed, fused): -1 unfused faults every
    #: operator boundary once; -3 under the fused executor makes the
    #: split-in-half escalation change batch capacities mid-query, so the
    #: join and aggregate kernels run across shapes while faults retry.
    @pytest.mark.parametrize("name,oom,seed,fused", [
        ("q1", -1, 0, False), ("q6", -1, 0, False), ("q3", -1, 0, False),
        ("q5", -1, 0, False), ("q3", -3, 11, True), ("q5", -3, 11, True)])
    def test_query_with_oom_at_every_site(self, name, oom, seed, fused):
        from spark_rapids_tpu.workloads import tpch
        from spark_rapids_tpu.workloads.compare import tables_match
        tables = tpch.gen_tables(1 << 10, seed=7)
        tpu = TpuSession(_inject_conf(
            sites="*", oom=oom, seed=seed,
            **{"spark.rapids.tpu.fusion.enabled": fused,
               "spark.rapids.sql.variableFloatAgg.enabled": True}))
        q = tpch.QUERIES[name]
        got = q(tpch.load(tpu, tables)).collect()
        want = q(tpch.load(_cpu(), tables)).collect()
        assert tables_match(got, want, rel_tol=1e-9, abs_tol=1e-9)
        assert tpu._fault_injector.injected["oom"] > 0
