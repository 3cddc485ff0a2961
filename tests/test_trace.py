"""Distributed-tracing tests (metrics/trace.py, ISSUE 13): span-tree
invariants, the zero-cost disabled default (bit-identical, fence-free,
no tracer), balance under the PR-4 OOM ladder / PR-7 net-fault matrix /
PR-12 serve chaos matrix (all under the conftest's TPU_LOCKDEP=1),
wire-propagated trace context over both protocols, flight-recorder dumps
on deadline / quarantine / session-crash, event-log rotation, the serve
health/inflight view, and the tier-1 q3 serving-path trace artifact with
Chrome trace-event schema validation."""

import glob
import json
import os
import threading
import time

import pytest

import tools.trace_report as trace_report
from spark_rapids_tpu.metrics import eventlog
from spark_rapids_tpu.metrics import trace as TR
from spark_rapids_tpu.plan.logical import col, lit
from spark_rapids_tpu.session import TpuSession

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ARTIFACTS = os.environ.get("SRTPU_ARTIFACT_DIR",
                           os.path.join(REPO, "artifacts"))

ROWS = 1 << 10


@pytest.fixture(scope="module")
def tpch_tables():
    from spark_rapids_tpu.workloads import tpch
    return tpch.gen_tables(ROWS, seed=7)


def _traced_conf(tmp, **extra):
    conf = {
        "spark.rapids.sql.enabled": True,
        "spark.rapids.tpu.trace.enabled": True,
        "spark.rapids.tpu.trace.dir": str(tmp),
    }
    conf.update(extra)
    return conf


def validate_chrome_trace(path):
    """The CI schema gate: a trace artifact must be well-formed Chrome
    trace-event JSON — loadable, every event a complete X (dur >= 0,
    ts >= 0) or matched B/E pair or metadata M, Perfetto-loadable shape
    (traceEvents list + displayTimeUnit)."""
    with open(path, encoding="utf-8") as f:
        data = json.load(f)
    assert isinstance(data.get("traceEvents"), list)
    assert data.get("displayTimeUnit") in ("ms", "ns")
    begins = []
    for ev in data["traceEvents"]:
        ph = ev.get("ph")
        assert ph in ("X", "B", "E", "M"), f"unexpected phase {ph!r}"
        if ph == "M":
            continue
        assert float(ev["ts"]) >= 0.0, "non-monotonic (negative) ts"
        assert isinstance(ev.get("name"), str) and ev["name"]
        if ph == "X":
            assert float(ev.get("dur", -1)) >= 0.0
        elif ph == "B":
            begins.append((ev.get("tid"), ev["name"]))
        elif ph == "E":
            assert (ev.get("tid"), ev["name"]) in begins, "unmatched E"
            begins.remove((ev.get("tid"), ev["name"]))
    assert not begins, f"unmatched B events: {begins}"
    return data


# ---------------------------------------------------------------------------
# Tracer core
# ---------------------------------------------------------------------------


class TestTracerCore:
    def test_disabled_path_opens_the_bare_annotation(self):
        import jax
        sp = TR.span(None, "anything")
        assert type(sp) is jax.profiler.TraceAnnotation
        assert TR.fork(None) is None
        with sp:
            pass  # no tracer, nothing recorded

    def test_span_tree_parents_nest_and_balance(self):
        t = TR.Tracer("t-core-1")
        with TR.span(t, "root"):
            with TR.span(t, "child"):
                with TR.span(t, "grandchild"):
                    pass
            with TR.span(t, "sibling"):
                pass
        t.assert_balanced()
        by_name = {s["name"]: s for s in t.spans}
        assert by_name["root"]["parent"] == 0
        assert by_name["child"]["parent"] == by_name["root"]["id"]
        assert by_name["grandchild"]["parent"] == by_name["child"]["id"]
        assert by_name["sibling"]["parent"] == by_name["root"]["id"]

    def test_cross_thread_fork_parents_under_captured_span(self):
        t = TR.Tracer("t-core-2")
        seen = {}
        with TR.span(t, "root"):
            with TR.span(t, "stage"):
                fk = TR.fork(t)

                def worker():
                    with TR.span(fk, "worker"):
                        pass
                    seen["ok"] = True
                th = threading.Thread(target=worker)
                th.start()
                th.join()
        assert seen["ok"]
        t.assert_balanced()
        by_name = {s["name"]: s for s in t.spans}
        assert by_name["worker"]["parent"] == by_name["stage"]["id"]

    def test_worker_without_fork_parents_under_trace_root(self):
        t = TR.Tracer("t-core-3")
        with TR.span(t, "root"):
            def worker():
                with TR.span(t, "lane"):
                    pass
            th = threading.Thread(target=worker)
            th.start()
            th.join()
        by_name = {s["name"]: s for s in t.spans}
        assert by_name["lane"]["parent"] == by_name["root"]["id"]

    def test_error_spans_close_tagged_and_stay_balanced(self):
        t = TR.Tracer("t-core-4")
        with pytest.raises(ValueError):
            with TR.span(t, "failing"):
                raise ValueError("boom")
        t.assert_balanced()
        (s,) = t.spans
        assert s["args"]["error"] == "ValueError"

    def test_unbalanced_open_span_is_detected(self):
        t = TR.Tracer("t-core-5")
        h = TR.span(t, "left-open")
        h.__enter__()
        with pytest.raises(AssertionError, match="left open"):
            t.assert_balanced()
        h.__exit__(None, None, None)
        t.assert_balanced()

    def test_span_cap_counts_drops(self):
        t = TR.Tracer("t-core-6", max_spans=2)
        for i in range(5):
            with TR.span(t, f"s{i}"):
                pass
        assert len(t.spans) == 2 and t.dropped == 3
        assert t.to_chrome()["otherData"]["dropped_spans"] == 3

    def test_chrome_export_schema(self, tmp_path):
        t = TR.Tracer("t-core-7", tenant="ten")
        with TR.span(t, "a", cat="serve", k=1):
            with TR.span(t, "b"):
                pass
        path = TR.export_chrome(t, str(tmp_path))
        assert path is not None and os.path.exists(path)
        data = validate_chrome_trace(path)
        xs = [e for e in data["traceEvents"] if e["ph"] == "X"]
        assert {e["name"] for e in xs} == {"a", "b"}
        assert data["otherData"]["tenant"] == "ten"
        # ts is monotonic in exported order
        tss = [e["ts"] for e in xs]
        assert tss == sorted(tss)

    def test_export_retention_prunes_oldest(self, tmp_path, monkeypatch):
        monkeypatch.setattr(TR, "_MAX_FILES", 3)
        paths = []
        for i in range(6):
            t = TR.Tracer(f"prune-{i}")
            with TR.span(t, "s"):
                pass
            paths.append(TR.export_chrome(t, str(tmp_path)))
            os.utime(paths[-1], (i, i))  # deterministic mtime order
        left = sorted(os.path.basename(p)
                      for p in glob.glob(str(tmp_path / "trace_*.json")))
        assert left == ["trace_prune-3.json", "trace_prune-4.json",
                        "trace_prune-5.json"]

    def test_adopted_sibling_exports_peer_discriminated_file(
            self, tmp_path):
        from spark_rapids_tpu.config import TpuConf
        TR.configure(TpuConf({"spark.rapids.tpu.trace.enabled": True}))
        origin = TR.Tracer("shared-id-1")
        with TR.span(origin, "client"):
            pass
        # Simulate the cross-process peer: drop the live registry entry
        # so adopt() builds a sibling instead of joining.
        with TR._STATE_LOCK:
            TR._LIVE.pop("shared-id-1", None)
        sibling = TR.adopt("shared-id-1", parent_span_id=1)
        with TR.span(sibling, "server"):
            pass
        p1 = TR.export_chrome(origin, str(tmp_path))
        p2 = TR.export_chrome(sibling, str(tmp_path))
        assert p1 != p2, "sibling export must not clobber the origin's"
        assert f".peer{os.getpid()}" in os.path.basename(p2)
        assert os.path.exists(p1) and os.path.exists(p2)

    def test_wire_roundtrip_and_live_registry(self):
        t = TR.Tracer("t-core-8")
        with TR.span(t, "root"):
            wire = TR.format_wire(t)
            tid, parent = TR.parse_wire(wire)
            assert tid == "t-core-8"
            assert parent >= 1  # the open root span's id
        assert TR.live_tracer("t-core-8") is t
        assert TR.live_tracer(TR.wire_hash("t-core-8")) is t
        assert TR.parse_wire(None) == (None, 0)
        assert TR.parse_wire("x/notanint") == ("x", 0)


# ---------------------------------------------------------------------------
# Zero-cost default: bit-identity + fence-free + no tracer
# ---------------------------------------------------------------------------


class TestDisabledDefault:
    @pytest.mark.parametrize("qname", ["q1", "q3"])
    def test_traced_vs_untraced_bit_identical(self, qname, tpch_tables,
                                              tmp_path):
        from spark_rapids_tpu.workloads import tpch
        plain = TpuSession({"spark.rapids.sql.enabled": True,
                            "spark.rapids.sql.variableFloatAgg.enabled":
                                True})
        base = tpch.QUERIES[qname](tpch.load(plain, tpch_tables)).collect()
        traced = TpuSession(_traced_conf(
            tmp_path, **{"spark.rapids.sql.variableFloatAgg.enabled": True}))
        got = tpch.QUERIES[qname](tpch.load(traced, tpch_tables)).collect()
        assert got.equals(base), f"{qname}: traced result diverged"
        assert traced.last_trace() is not None
        traced.last_trace().assert_balanced()
        assert plain.last_trace() is None

    def test_untraced_run_is_fence_free_and_tracer_free(self, monkeypatch):
        import jax
        fences = []
        orig = jax.block_until_ready

        def counting(x):
            fences.append(1)
            return orig(x)
        monkeypatch.setattr(jax, "block_until_ready", counting)
        s = TpuSession({"spark.rapids.sql.enabled": True})
        df = s.create_dataframe({"a": [1, 2, 3]}).where(col("a") > lit(1))
        assert df.collect().num_rows == 2
        assert not fences, "tracing-off default must insert zero fences"
        assert s.last_trace() is None


# ---------------------------------------------------------------------------
# Balance under the fault matrices (all under TPU_LOCKDEP=1 via conftest)
# ---------------------------------------------------------------------------


class TestBalancedUnderFaults:
    def test_oom_ladder_spans_balanced(self, tpch_tables, tmp_path):
        """Every retry site faulting its first visit: the whole PR-4
        ladder (sync, spill, backoff, split) runs, and every span it
        opened must close with valid parents."""
        from spark_rapids_tpu.workloads import tpch
        s = TpuSession(_traced_conf(
            tmp_path,
            **{"spark.rapids.sql.variableFloatAgg.enabled": True,
               "spark.rapids.tpu.retry.backoffBaseMs": 0.1,
               "spark.rapids.tpu.test.faultInjection.sites": "*",
               "spark.rapids.tpu.test.faultInjection.oomEveryN": -1}))
        # cache=False: loading must not execute anything, or it consumes
        # the first-visit fault schedule before the traced query runs.
        t = tpch.load(s, tpch_tables, cache=False)
        tpch.QUERIES["q6"](t).collect()
        tr = s.last_trace()
        assert tr is not None
        tr.assert_balanced()
        assert s._fault_injector.injected["oom"] > 0
        names = {x["name"] for x in tr.spans}
        assert "retry.oom_recovery" in names or "retry.backoff" in names

    def test_net_fault_matrix_spans_balanced(self, tpch_tables, tmp_path):
        """Wire-path q3 with every block's first two fetch visits torn:
        refetch/recompute machinery runs; spans stay balanced and the
        fetch spans are present."""
        from spark_rapids_tpu.workloads import tpch
        s = TpuSession(_traced_conf(
            tmp_path,
            **{"spark.rapids.sql.variableFloatAgg.enabled": True,
               "spark.rapids.tpu.shuffle.net.enabled": True,
               "spark.rapids.tpu.test.faultInjection.sites":
                   "shuffle.fetchBlock",
               "spark.rapids.tpu.test.faultInjection.netEveryN": -2,
               "spark.rapids.tpu.test.faultInjection.netFaults": "torn",
               "spark.rapids.tpu.test.faultInjection.seed": 3}))
        t = tpch.load(s, tpch_tables)
        t["lineitem"] = t["lineitem"].repartition(4, "l_orderkey")
        tpch.QUERIES["q3"](t).collect()
        tr = s.last_trace()
        assert tr is not None
        tr.assert_balanced()
        assert s._fault_injector.injected["net.torn"] > 0
        names = {x["name"] for x in tr.spans}
        assert "shuffle.fetch" in names

    def test_serve_chaos_spans_balanced_and_crash_dump(self, tpch_tables,
                                                       tmp_path):
        """sessionCrash injected on the first serve.execute visit: the
        query re-runs on the replaced session; the caller-owned tracer
        stays balanced across the crash and a flight-recorder dump
        lands in artifacts/."""
        from spark_rapids_tpu.serve import QueryService
        from spark_rapids_tpu.workloads import tpch
        before = set(glob.glob(
            os.path.join(ARTIFACTS, "flight_session_crash_*.json")))
        svc = QueryService(conf=_traced_conf(
            tmp_path,
            **{"spark.rapids.tpu.serve.sessions": 1,
               "spark.rapids.tpu.trace.flightRecorder.dir": ARTIFACTS,
               "spark.rapids.tpu.test.faultInjection.sites": "serve.",
               "spark.rapids.tpu.test.faultInjection.serveEveryN": -1,
               "spark.rapids.tpu.test.faultInjection.serveFaults":
                   "sessionCrash"}),
            tables=tpch_tables,
            queries={"q1": tpch.QUERIES["q1"]})
        try:
            tracer = TR.Tracer("chaos-crash-1", tenant="a")
            res = svc.execute("a", "q1", trace=tracer)
            assert res.table.num_rows > 0
            assert svc.stats()["crash_reruns"] == 1
            tracer.assert_balanced()
            names = {x["name"] for x in tracer.spans}
            assert {"serve.query", "serve.admission",
                    "serve.execute"} <= names
            # Both attempts are on the timeline: the injected crash
            # fires at the seam BEFORE serve.execute opens, so the
            # crashed attempt shows as its serve.plan span and only the
            # rerun reaches serve.execute.
            assert sum(1 for x in tracer.spans
                       if x["name"] == "serve.plan") == 2
            assert sum(1 for x in tracer.spans
                       if x["name"] == "serve.execute") == 1
        finally:
            svc.close()
        after = set(glob.glob(
            os.path.join(ARTIFACTS, "flight_session_crash_*.json")))
        assert after - before, "no session-crash flight dump written"
        dump = json.loads(open(sorted(after - before)[0]).read())
        assert dump["reason"] == "session_crash"

    def test_quarantine_trips_write_flight_dump(self, tpch_tables,
                                                tmp_path):
        """Repeated crashes quarantine the plan (PR-12 breaker) — the
        trip writes a quarantine flight dump to artifacts/."""
        from spark_rapids_tpu.serve import (QueryService,
                                            SessionCrashError)
        from spark_rapids_tpu.workloads import tpch
        before = set(glob.glob(
            os.path.join(ARTIFACTS, "flight_quarantine_*.json")))
        svc = QueryService(conf=_traced_conf(
            tmp_path,
            **{"spark.rapids.tpu.serve.sessions": 1,
               "spark.rapids.tpu.trace.flightRecorder.dir": ARTIFACTS,
               "spark.rapids.tpu.serve.quarantine.maxFailures": 1,
               "spark.rapids.tpu.test.faultInjection.sites": "serve.",
               "spark.rapids.tpu.test.faultInjection.serveEveryN": 1,
               "spark.rapids.tpu.test.faultInjection.serveFaults":
                   "sessionCrash"}),
            tables=tpch_tables,
            queries={"q1": tpch.QUERIES["q1"]})
        try:
            # Every serve.execute visit crashes: the read-only re-run
            # crashes too, the plan's failure count trips the breaker.
            with pytest.raises(SessionCrashError):
                svc.execute("a", "q1")
            assert svc.stats()["quarantine_trips"] >= 1
        finally:
            svc.close()
        after = set(glob.glob(
            os.path.join(ARTIFACTS, "flight_quarantine_*.json")))
        assert after - before, "no quarantine flight dump written"


class TestFlightRecorderDeadline:
    def test_deadline_exceeded_writes_dump(self, tmp_path, tpch_tables):
        """An expired per-tenant time budget (PR-7 deadline through the
        PR-12 serving layer) dumps the flight recorder on its first
        observation."""
        from spark_rapids_tpu.serve import QueryService
        from spark_rapids_tpu.utils.deadline import QueryDeadlineExceeded
        from spark_rapids_tpu.workloads import tpch
        before = set(glob.glob(
            os.path.join(ARTIFACTS, "flight_deadline_exceeded_*.json")))
        svc = QueryService(conf=_traced_conf(
            tmp_path,
            **{"spark.rapids.tpu.serve.sessions": 1,
               "spark.rapids.tpu.trace.flightRecorder.dir": ARTIFACTS,
               "spark.rapids.tpu.serve.tenantTimeBudgetSecs":
                   "default:0.000001"}),
            tables=tpch_tables,
            queries={"q1": tpch.QUERIES["q1"]})
        try:
            with pytest.raises(QueryDeadlineExceeded):
                svc.execute("a", "q1")
        finally:
            svc.close()
        after = set(glob.glob(
            os.path.join(ARTIFACTS, "flight_deadline_exceeded_*.json")))
        assert after - before, "no deadline flight dump written"
        dump = json.loads(open(sorted(after - before)[0]).read())
        assert dump["reason"] == "deadline_exceeded"
        assert "site" in dump["context"]


# ---------------------------------------------------------------------------
# Wire propagation over the serve (SRTQS) protocol
# ---------------------------------------------------------------------------


class TestWirePropagation:
    def test_srtqs_trace_field_stitches_into_client_tracer(
            self, tpch_tables, tmp_path):
        """A client that sends its trace context in the SRTQS ``trace``
        field gets the SERVER's spans recorded into its own (in-process
        live) tracer — one tree across the wire."""
        from spark_rapids_tpu.serve import (QueryService, ServeClient,
                                            ServeFrontend)
        from spark_rapids_tpu.workloads import tpch
        svc = QueryService(conf=_traced_conf(tmp_path),
                           tables=tpch_tables,
                           queries={"q6": tpch.QUERIES["q6"]})
        frontend = ServeFrontend(svc)
        client = ServeClient(frontend.address)
        try:
            tracer = TR.Tracer("wire-cli-1", tenant="a")
            # NESTED client spans: the wire parent must be the innermost
            # RPC span, not the trace root — pins the parent-id half of
            # the SRTQS propagation.
            with TR.span(tracer, "client.session"):
                with TR.span(tracer, "client.request"):
                    resp = client.query("a", "q6",
                                        trace=TR.format_wire(tracer))
            assert resp["ok"], resp
            tracer.assert_balanced()
            names = {s["name"] for s in tracer.spans}
            assert "client.request" in names
            assert "serve.query" in names, \
                "server spans did not stitch into the client trace"
            by_name = {s["name"]: s for s in tracer.spans}
            assert by_name["serve.query"]["parent"] \
                == by_name["client.request"]["id"]
        finally:
            client.close()
            frontend.close()
            svc.close()

    def test_health_and_stats_ops_expose_inflight_view(self, tpch_tables,
                                                       tmp_path):
        from spark_rapids_tpu.serve import (QueryService, ServeClient,
                                            ServeFrontend)
        from spark_rapids_tpu.workloads import tpch
        svc = QueryService(conf=_traced_conf(tmp_path),
                           tables=tpch_tables,
                           queries={"q6": tpch.QUERIES["q6"]})
        frontend = ServeFrontend(svc)
        client = ServeClient(frontend.address)
        try:
            h = client.health()
            assert h["ok"] and h["health"]["inflight"] == []
            assert "queue_depth" in h["health"]
            assert "hbm" in h["health"]
            st = client.stats()
            assert "health" in st and "inflight" in st["health"]
        finally:
            client.close()
            frontend.close()
            svc.close()

    def test_inflight_shows_running_query_with_current_span(
            self, tpch_tables, tmp_path):
        from spark_rapids_tpu.serve import QueryService
        from spark_rapids_tpu.workloads import tpch
        gate = threading.Event()
        release = threading.Event()

        def slow_builder(dfs):
            gate.set()
            assert release.wait(10), "test did not release the builder"
            return tpch.QUERIES["q6"](dfs)
        svc = QueryService(conf=_traced_conf(tmp_path),
                           tables=tpch_tables, queries={"slow": slow_builder})
        box = {}

        def run():
            box["res"] = svc.execute("tenantX", "slow")
        th = threading.Thread(target=run, daemon=True)
        try:
            th.start()
            assert gate.wait(10)
            h = svc.health()
            assert len(h["inflight"]) == 1
            entry = h["inflight"][0]
            assert entry["tenant"] == "tenantX"
            assert entry["query"] == "slow"
            assert entry["elapsed_ms"] >= 0
            # The builder runs inside the serve.plan span.
            assert entry["span"] == "serve.plan"
        finally:
            release.set()
            th.join(30)
            svc.close()
        assert box["res"].table.num_rows >= 0
        assert svc.health()["inflight"] == []


# ---------------------------------------------------------------------------
# Event-log rotation (satellite)
# ---------------------------------------------------------------------------


class TestEventLogRotation:
    def _record(self, i):
        return {"query_id": i, "pad": "x" * 64}

    def test_rotation_caps_file_and_keeps_one_generation(self, tmp_path):
        log = eventlog.EventLog(str(tmp_path), max_bytes=256)
        for i in range(20):
            assert log.append(self._record(i))
        assert os.path.exists(log.path)
        assert os.path.exists(log.path + ".1")
        assert os.path.getsize(log.path) <= 256
        # The current + rotated generations hold the most recent records
        # contiguously (older generations are dropped by design).
        recs = eventlog.read_all(str(tmp_path))
        ids = [r["query_id"] for r in recs]
        assert ids == list(range(ids[0], 20))
        assert len(ids) >= 2

    def test_zero_max_bytes_never_rotates(self, tmp_path):
        log = eventlog.EventLog(str(tmp_path), max_bytes=0)
        for i in range(50):
            log.append(self._record(i))
        assert not os.path.exists(log.path + ".1")
        assert len(eventlog.read(log.path)) == 50

    def test_torn_line_isolated_across_rotation(self, tmp_path):
        log = eventlog.EventLog(str(tmp_path), max_bytes=200)
        log.append(self._record(0))
        with open(log.path, "ab") as f:
            f.write(b'{"torn": tru')  # crash mid-append, no newline
        log.append(self._record(1))
        log.append(self._record(2))
        recs = eventlog.read_all(str(tmp_path))
        assert [r["query_id"] for r in recs] == [0, 1, 2]

    def test_oversized_single_record_still_appends(self, tmp_path):
        log = eventlog.EventLog(str(tmp_path), max_bytes=64)
        big = {"query_id": 1, "pad": "y" * 500}
        assert log.append(big)
        assert eventlog.read(log.path)[0]["query_id"] == 1

    def test_session_threads_max_bytes_from_conf(self, tmp_path):
        s = TpuSession({
            "spark.rapids.sql.enabled": True,
            "spark.rapids.tpu.metrics.eventLog.dir": str(tmp_path),
            "spark.rapids.tpu.metrics.eventLog.maxBytes": 400,
        })
        df = s.create_dataframe({"a": [1, 2, 3]}).where(col("a") > lit(0))
        for _ in range(6):
            df.collect()
        assert s._event_log is not None
        assert s._event_log.max_bytes == 400
        # One profile record is larger than this tiny cap, so every
        # append rotates: the current file holds exactly the newest
        # record and one prior generation exists.
        assert os.path.exists(s._event_log.path + ".1")
        assert len(eventlog.read(s._event_log.path)) == 1


# ---------------------------------------------------------------------------
# trace_report (critical path, overlap, tenant breakdown)
# ---------------------------------------------------------------------------


def _mk_trace(tenant, spans):
    """Hand-built chrome trace: spans = [(name, cat, id, parent, t0, t1)]
    in microseconds."""
    return {"traceEvents": [
        {"name": n, "cat": c, "ph": "X", "ts": t0, "dur": t1 - t0,
         "pid": 1, "tid": 1, "args": {"id": i, "parent": p}}
        for n, c, i, p, t0, t1 in spans],
        "displayTimeUnit": "ms",
        "otherData": {"trace_id": "t", "tenant": tenant}}


class TestTraceReport:
    def test_critical_path_and_self_time(self):
        t = _mk_trace("a", [
            ("serve.query", "serve", 1, 0, 0, 1000),
            ("serve.execute", "serve", 2, 1, 100, 900),
            ("fusion.dispatch", "dispatch", 3, 2, 200, 800),
        ])
        rep = trace_report.summarize(t)
        assert [h["name"] for h in rep["critical_path"]] \
            == ["serve.query", "serve.execute", "fusion.dispatch"]
        # self of serve.query = 1000 - (900-100) = 200us = 0.2ms
        assert rep["critical_path"][0]["self_ms"] == pytest.approx(0.2)
        assert rep["critical_path"][2]["self_ms"] == pytest.approx(0.6)

    def test_concurrent_children_not_double_subtracted(self):
        t = _mk_trace("a", [
            ("root", "serve", 1, 0, 0, 1000),
            ("laneA", "spill", 2, 1, 100, 600),
            ("laneB", "spill", 3, 1, 200, 700),  # overlaps laneA
        ])
        rep = trace_report.summarize(t)
        root = rep["critical_path"][0]
        # union of children = [100, 700) = 600us -> self 400us
        assert root["self_ms"] == pytest.approx(0.4)

    def test_overlap_efficiency_measures_concurrency(self):
        serial = _mk_trace("a", [
            ("decode1", "decode", 1, 0, 0, 500),
            ("decode2", "decode", 2, 0, 500, 1000)])
        overlapped = _mk_trace("a", [
            ("decode1", "decode", 1, 0, 0, 500),
            ("decode2", "decode", 2, 0, 0, 500)])
        assert trace_report.summarize(serial)["overlap"]["efficiency"] \
            == pytest.approx(1.0)
        assert trace_report.summarize(overlapped)["overlap"]["efficiency"] \
            == pytest.approx(2.0)

    def test_overlap_excludes_wait_and_backoff_spans(self):
        # A consumer waiting out a producer is a STALL, not 2-way
        # concurrency: pipeline.wait / *.backoff must not count as work.
        t = _mk_trace("a", [
            ("pipeline.decode", "decode", 1, 0, 0, 1000),
            ("pipeline.wait", "pipeline", 2, 0, 0, 1000),
            ("shuffle.backoff", "shuffle", 3, 0, 0, 1000),
            ("spill.io_wait", "spill", 4, 0, 0, 1000)])
        ov = trace_report.summarize(t)["overlap"]
        assert ov["spans"] == 1
        assert ov["efficiency"] == pytest.approx(1.0)

    def test_tenant_breakdown_queue_vs_execute(self, tmp_path):
        for i, tenant in enumerate(["a", "a", "b"]):
            t = _mk_trace(tenant, [
                ("serve.query", "serve", 1, 0, 0, 1000),
                ("serve.admission", "serve", 2, 1, 0, 300),
                ("serve.execute", "serve", 3, 1, 300, 1000)])
            with open(tmp_path / f"trace_{tenant}-{i}.json", "w") as f:
                json.dump(t, f)
        rep = trace_report.summarize_dir(str(tmp_path))
        assert rep["traces"] == 3
        assert rep["per_tenant"]["a"]["queries"] == 2
        assert rep["per_tenant"]["a"]["queue_ms"] == pytest.approx(0.6)
        assert rep["per_tenant"]["a"]["execute_ms"] == pytest.approx(1.4)
        assert rep["per_tenant"]["b"]["wall_ms"] == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# The tier-1 q3 serving-path trace artifact (CI satellite + acceptance)
# ---------------------------------------------------------------------------


class TestQ3ServingTraceArtifact:
    def test_q3_serving_trace_artifact_and_critical_path(self,
                                                         tpch_tables):
        """ONE q3 run through QueryService with tracing on emits ONE
        Perfetto-loadable trace stitching serve admission -> session
        dispatch -> pipeline workers -> spill IO -> shuffle fetch (the
        wire-propagated v4 context), exported under
        artifacts/tpch_smoke/ as a tier-1 build artifact;
        tools/trace_report.py computes its critical path and overlap
        efficiency."""
        from spark_rapids_tpu.serve import QueryService
        from spark_rapids_tpu.workloads import tpch
        trace_dir = os.path.join(ARTIFACTS, "tpch_smoke")
        for old in glob.glob(os.path.join(trace_dir, "trace_*.json")):
            os.remove(old)  # fresh artifact per tier-1 run

        def q3_wire(t):
            t = dict(t)
            t["lineitem"] = t["lineitem"].repartition(4, "l_orderkey")
            return tpch.QUERIES["q3"](t)
        svc = QueryService(conf={
            "spark.rapids.sql.enabled": True,
            "spark.rapids.sql.variableFloatAgg.enabled": True,
            "spark.rapids.tpu.trace.enabled": True,
            "spark.rapids.tpu.trace.dir": trace_dir,
            # The wire shuffle plane: reduce reads fetch through the v4
            # protocol, so the trace proves wire-context propagation.
            "spark.rapids.tpu.shuffle.net.enabled": True,
            # A tiny device spill budget forces the PR-11 spill-IO lane
            # into the timeline (join build tables register as spillable
            # and immediately overflow the budget).
            "spark.rapids.memory.tpu.spillBudgetBytes": 10_000,
        }, tables=tpch_tables, queries={"q3": q3_wire})
        try:
            res = svc.execute("smoke", "q3")
            assert res.table.num_rows >= 1
        finally:
            svc.close()
        files = glob.glob(os.path.join(trace_dir, "trace_*.json"))
        assert len(files) == 1, f"expected ONE trace, got {files}"
        data = validate_chrome_trace(files[0])
        names = {e["name"] for e in data["traceEvents"]
                 if e.get("ph") == "X"}
        for expected in ("serve.query", "serve.admission",
                         "session.dispatch", "pipeline.boundary",
                         "spill.io", "shuffle.fetch",
                         "shuffle.serve.fetch", "fusion.dispatch"):
            assert expected in names, \
                f"span {expected!r} missing from the serving trace " \
                f"(have {sorted(names)})"
        # Critical path + overlap efficiency from the analyzer.
        rep = trace_report.summarize(data)
        assert rep["critical_path"], "empty critical path"
        assert rep["critical_path"][0]["name"] == "serve.query"
        assert rep["overlap"]["spans"] > 0
        assert rep["overlap"]["efficiency"] is not None
        assert rep["overlap"]["efficiency"] >= 1.0
        # Per-tenant breakdown over the artifact directory.
        dir_rep = trace_report.summarize_dir(trace_dir)
        assert dir_rep["per_tenant"]["smoke"]["queries"] == 1
        assert dir_rep["per_tenant"]["smoke"]["execute_ms"] > 0
