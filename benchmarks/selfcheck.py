"""The benchmark checked without a chip, on the CPU backend.

    JAX_PLATFORMS=cpu python3 benchmarks/selfcheck.py

Each check raises on failure. It drives ``run.run_cell`` — everything of a
run but the look for a chip — at 1/90 of the cell's rows (66,680 of
lineitem), and never prints a device metric: times, rates and shares come
only from a chip, so here they are "not measured" and there is no result
line.

- every cell runs to ``correct`` true;
- an answer altered where it is produced (a float moved by 1e-6 of itself,
  a key changed, a row dropped, a value made null) makes ``correct``
  false, and so does a row group read on the host by a query of set-up;
- the float32 control comes out as not correct, in every cell whose answer
  holds a floating-point column (an answer of counts alone has nothing to
  round: the altered key and the dropped row hold it);
- a mix that gives a query its parameters is held to each entry's own
  reference;
- ``trace_reduce`` gives the hand-checked numbers for the recorded trace
  under ``fixtures/`` and for a hand-written one.
"""

import contextlib
import gzip
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)

import control  # noqa: E402
import run  # noqa: E402
import trace_reduce  # noqa: E402

SCALE = 1 / 90
FAULTS = ("float", "key", "row", "null", "setup_host_read")


# cells that wait for a later PR (PERF.md, Open questions) but whose files
# are here, {name: (config, traffic)}: held to the same checks, so that an
# entry is all they need
WAITING = {}


def cells():
    bench = run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    return [w["name"] for w in bench["workloads"]] + list(WAITING)


def load_cell(name: str) -> dict:
    if name not in WAITING:
        return run.load_cell(name)
    bench = run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    return run.build_cell(bench, name, *WAITING[name], 1)


@contextlib.contextmanager
def altered_answers(fault: str):
    """Break the engine underneath the harness: every collect() returns its
    answer with one fault planted, or the first profile of set-up counts a
    row group read on the host. Yields the count of faults planted (a query
    with no exact-typed column has no key to change; ``null`` makes the
    first value that is not null in the answer's first row null)."""
    import datetime

    import pyarrow as pa
    from spark_rapids_tpu.plan import logical
    planted = [0]
    if fault == "setup_host_read":
        real_counters = run.counters_of

        def counters_of(profile):
            planted[0] += 1
            found = real_counters(profile)
            if planted[0] == 1:         # the first query of set-up
                found["hostFallbackRowGroups"] = 1
            return found
        run.counters_of = counters_of
        try:
            yield planted
        finally:
            run.counters_of = real_counters
        return

    def alter(table: pa.Table) -> pa.Table:
        if fault == "row":
            planted[0] += 1
            return table.slice(0, table.num_rows - 1)
        for i, field in enumerate(table.schema):
            if fault != "null" and \
                    pa.types.is_floating(field.type) != (fault == "float"):
                continue
            values = table.column(i).to_pylist()
            first = values[0] if values else None
            if fault == "null":
                if first is None:
                    continue
                values[0] = None
            elif isinstance(first, float):
                values[0] = first * (1 + 1e-6)
            elif isinstance(first, str):
                values[0] = first + "x"
            elif isinstance(first, datetime.date):
                values[0] = first + datetime.timedelta(days=1)
            else:
                values[0] = first + 1
            planted[0] += 1
            return table.set_column(i, field, pa.array(values, field.type))
        return table

    real = logical.DataFrame.collect
    logical.DataFrame.collect = lambda self: alter(real(self))
    try:
        yield planted
    finally:
        logical.DataFrame.collect = real


def check_cell(name: str) -> dict:
    result = run.run_cell(load_cell(name), seed=7, seconds=1.0,
                          trace=False, scale=SCALE)
    if not result["correct"] or not result["completed"]:
        raise AssertionError(f"{name}: not correct: {result['compared']}")
    print(f"selfcheck {name}: correct over {result['completed']} queries, "
          f"{result['compared']}; query_s, setup_s and every device metric: "
          "not measured (CPU backend)")
    return result


def check_fault(name: str, fault: str):
    """The rest of a run with the timed path broken underneath."""
    with altered_answers(fault) as planted:
        result = run.run_cell(load_cell(name), seed=7, seconds=0.5,
                              trace=False, scale=SCALE)
    if not planted[0]:
        print(f"selfcheck {name}: fault {fault!r} finds no such column in "
              "the answer")
        return None
    if result["correct"]:
        raise AssertionError(f"{name}: fault {fault!r} passed as correct: "
                             f"{result['compared']}")
    print(f"selfcheck {name}: fault {fault!r} -> correct false, "
          f"{result['compared']}")
    return result


def check_params() -> None:
    """A mix whose entries give a query its parameters, in a shuffled
    order: each answer is held to the reference for its own parameters."""
    cell = run.load_cell("tpch_sf1_parquet.q6")
    cell["mix"] = [{"name": f"q6.{year}", "query": "q6",
                    "params": {"year": year, "discount": 0.04,
                               "quantity": 25}} for year in (1993, 1996)]
    cell["traffic"] = dict(cell["traffic"], order="shuffled")
    result = run.run_cell(cell, seed=9, seconds=0.5, trace=False,
                          scale=SCALE)
    if not result["correct"] or len(result["compared"]) != 6:
        raise AssertionError(f"parameters: {result['compared']}")
    with altered_answers("float"):
        result = run.run_cell(cell, seed=9, seconds=0.5, trace=False,
                              scale=SCALE)
    if result["correct"]:
        raise AssertionError(f"parameters, float: {result['compared']}")
    print(f"selfcheck parameters: correct, {result['compared']}")


def check_control(name: str) -> None:
    correct, compared = control.control_reading(load_cell(name), seed=7,
                                                scale=SCALE)
    if correct is None:
        print(f"selfcheck {name}: the float32 control finds no "
              "floating-point column in the answer; its exact cells are "
              "held by the faults 'key' and 'row'")
        return
    if correct:
        raise AssertionError(f"{name}: the float32 control passed: {compared}")
    print(f"selfcheck {name}: float32 control -> correct false, {compared}")


def check_trace_reduce() -> None:
    from jax.profiler import ProfileData
    expected = run.load_json(os.path.join(HERE, "fixtures", "expected.json"))
    os.makedirs(run.DATA_DIR, exist_ok=True)
    for fixture, want in expected.items():
        source = os.path.join(HERE, "fixtures", fixture)
        if fixture.endswith(".textproto"):
            with open(source) as f:
                blob = ProfileData.text_proto_to_serialized_xspace(f.read())
        else:
            with gzip.open(source) as f:
                blob = f.read()
        path = os.path.join(run.DATA_DIR, fixture + ".xplane.pb")
        with open(path, "wb") as f:
            f.write(blob)
        got = trace_reduce.reduce(path)
        for key, value in want.items():
            if isinstance(value, list):     # [[name, seconds], ...]
                same = len(got[key]) == len(value) and all(
                    g[0] == v[0] and abs(g[1] - v[1]) <= 1e-9
                    for g, v in zip(got[key], value))
            else:
                same = abs(got[key] - value) <= 1e-9 * max(1.0, abs(value))
            if not same:
                raise AssertionError(f"{fixture}: {key} = {got[key]!r}, "
                                     f"expected {value!r}")
        print(f"selfcheck trace_reduce {fixture}: {sorted(want)} as expected")


def main() -> int:
    import jax
    if jax.devices()[0].platform != "cpu":
        print("selfcheck.py is the CPU-backend check; run it with "
              "JAX_PLATFORMS=cpu", file=sys.stderr)
        return 2
    check_trace_reduce()
    check_params()
    for name in cells():
        check_cell(name)
        check_control(name)
        for fault in FAULTS:
            check_fault(name, fault)
    print("selfcheck: all checks passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
