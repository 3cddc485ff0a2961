"""What PR 38 added to the harness, held without a chip: `pytest benchmarks/`.

`steadiness.py` on two recorded sets of six runs of `tpch_sf1_parquet.q6`
(PR 37's chip calls g and h, the parent's side; `fixtures/README.md`), the
`host` object of the result line and the reader of
`stalled_queries_in_window`.
"""

import json
import os

import pytest

import run
import selfcheck
import steadiness

FIXTURES = os.path.join(run.HERE, "fixtures")
HOST_KEYS = {"cpu_count": int, "affinity": int, "loadavg_before": list,
             "loadavg_after": list, "steal_share": float,
             "busy_share": float, "process_cpu_s": float, "nvcsw": int,
             "nivcsw": int}


def recorded(tag: str) -> list:
    return steadiness.load_runs(
        [os.path.join(FIXTURES, f"steadiness_{tag}_parent.jsonl")])


@pytest.mark.parametrize("tag,less_farthest,range_", [
    ("g", 0.033, 0.095), ("h", 0.015, 0.022)])
def test_recorded_sets_read_as_the_driver_reads_them(tag, less_farthest,
                                                     range_):
    values = steadiness.metric_values(recorded(tag))["query_s"]
    s = steadiness.spreads(values)
    assert s["n"] == 6
    assert s["less_farthest"] == pytest.approx(less_farthest, abs=5e-4)
    assert s["range"] == pytest.approx(range_, abs=5e-4)
    assert s["iqr_less_farthest"] <= s["iqr"] <= s["range"]


def test_less_farthest_never_widens():
    # the farthest run is the lowest here, and leaving it out narrows
    assert steadiness.spreads([1.0, 1.0, 1.0, 0.5])["less_farthest"] == 0.0
    # two runs: nothing to leave out
    assert steadiness.spreads([1.0, 3.0])["less_farthest"] == 1.0


def test_report_prints_every_metric_beside_its_bound():
    lines = steadiness.report(recorded("g"), {"query_s": 0.05,
                                              "query_p95_s": 0.08,
                                              "setup_s": 0.25}, [10.0])
    table = {row.split()[0]: row.split() for row in lines[2:5]}
    assert set(table) == {"query_s", "query_p95_s", "setup_s"}
    assert table["query_s"][-1] == "0.05" and table["query_s"][1] == "6"
    runs = [row for row in lines if row.startswith("  2147483")]
    assert len(runs) == 6
    # seed ...805 read high in all four quarters of its window
    high = next(row for row in runs if "2147483805" in row)
    quarters = [float(x) for x in high.split("|")[1].split()]
    assert len(quarters) == 4 and min(quarters) >= 0.255
    assert lines[-1].startswith("cut 10 s: 13 windows")


def test_quarters_and_cuts_follow_the_elapsed_time():
    latencies = [1.0] * 8 + [2.0] * 4        # 16 s: quarters of 4 s
    assert steadiness.quarters(latencies) == [1.0, 1.0, 2.0, 2.0]
    windows = steadiness.cuts(latencies, 8.0)
    assert [w["queries"] for w in windows] == [8, 4]
    assert [w["query_s"] for w in windows] == [1.0, 2.0]
    # a query belongs to the window it starts in; an unfilled one is left out
    assert [w["queries"] for w in steadiness.cuts([3.0] * 5, 6.0)] == [2, 2]


def test_out_file_is_parsed(tmp_path):
    path = tmp_path / "one.out"
    path.write_text(
        "[bench] cell tpch_sf1_cached.q1 seed 2147483999 on TPU v5 lite x1\n"
        "[bench] setup_s 20.000 latencies 0.1000 0.1000 0.9000 0.1000\n"
        + json.dumps({"correct": True, "attempted": 4, "failed": 0,
                      "metrics": {"query_s": {"value": 0.3, "unit": "s"}},
                      "host": {k: ([0.5, 0.4, 0.3] if t is list else t(1))
                               for k, t in HOST_KEYS.items()}}) + "\n")
    (one,) = steadiness.load_runs([str(path)])
    assert one["seed"] == 2147483999 and one["cell"] == "tpch_sf1_cached.q1"
    assert steadiness.stalled(one["latencies"]) == 1
    text = steadiness.report([one, one], {}, [])
    assert "steal 1" in text[-1] and "cpus 1/1" in text[-1]


def test_host_object_has_its_keys():
    before = run.host_reading()
    sum(i * i for i in range(200000))       # some CPU seconds of our own
    host = run.host_of(before, run.host_reading())
    assert list(host) == list(HOST_KEYS)
    for key, kind in HOST_KEYS.items():
        assert isinstance(host[key], kind), key
    assert len(host["loadavg_before"]) == len(host["loadavg_after"]) == 3
    assert 0.0 <= host["steal_share"] <= host["busy_share"] <= 1.0
    assert host["process_cpu_s"] > 0 and host["affinity"] >= 1
    json.dumps(host)


def test_stalled_queries_reader():
    read = run.load_module(os.path.join(
        run.HERE, "metrics", "stalled_queries_in_window.py")).read
    assert read({"latencies": []}) is None
    assert read({"latencies": [0.25] * 100}) == 0
    assert read({"latencies": [0.25] * 100 + [0.76, 2.1, 0.74]}) == 2


def test_result_line_carries_host_before_compared():
    result = selfcheck.check_cell("tpch_sf1_parquet.q6")
    line = run.result_line(result, False, {"platform": "cpu", "kind": "none",
                                           "count": 1}, {})
    assert list(line)[-1] == "compared"
    assert set(line["host"]) == set(HOST_KEYS)
    assert line["host"]["process_cpu_s"] > 0
    assert result["window_s"] >= 1.0      # selfcheck's --seconds
