"""The control of ``correct``: the plain reference put in the engine's
place and computed in float32, the nearest precision below the float64 the
configurations state. It has to come out as not correct.

    python3 benchmarks/control.py --workload <cell> --seeds 1,2,3 [--scale S]

Pure numpy over the benchmark's own parquet files: it needs no chip and
reads the same on any host. Prints one line per seed with each number
compared beside its limit. The benchmark's own runs never run it. A cell
whose answer holds exact columns alone (Q13: counts) has no control: float32
rounds nothing there, and the altered count and the dropped row of
``selfcheck.py`` hold it instead.
"""

import argparse
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import compare  # noqa: E402
import run  # noqa: E402


def control_reading(cell: dict, seed: int, scale=1.0, real=np.float32):
    """(correct, compared) of the cell's queries answered by the reference
    in ``real`` against the reference in float64; ``correct`` is None where
    no answer holds a floating-point column, which is all that a lower
    precision can move."""
    paths, _ = cell["generator"].ensure(
        run.DATA_DIR, cell["config"], run.tables_of(cell["queries"]), seed,
        scale)
    references = run.reference_answers(cell, paths)
    answers = run.reference_answers(cell, paths, real)
    correct, compared = compare.judge(list(answers.items()), references,
                                      cell, {})
    if not any(a.dtype.kind == "f" for answer in references.values()
               for a in answer.values()):
        correct = None
    return correct, compared


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--scale", type=float, default=1.0)
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    passed = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        correct, compared = control_reading(cell, seed, args.scale)
        if correct is None:
            print(f"control {args.workload} seed {seed}: no floating-point "
                  "column in the answer, nothing for float32 to round")
            continue
        passed += correct
        print(f"control {args.workload} seed {seed} correct={correct} "
              + " ".join(f"{k}={v['value']!r}/{v['limit']!r}"
                         for k, v in compared.items()), flush=True)
    return 1 if passed else 0   # a control that passes is the failure


if __name__ == "__main__":
    sys.exit(main())
