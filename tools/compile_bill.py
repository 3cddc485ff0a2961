"""What a cold chip call pays the TPU compiler, asked without the chip.

Runs chip_smoke's body here on the CPU backend at the real size while
recording every ``jax.jit`` program the engine dispatches (the jitted
function and the abstract shapes of each new specialization), then lowers
and compiles each recorded program for one described v5e chip
(on-chip-measurement guide, section 2) and prints seconds per compile and
``memory_analysis()`` beside each. Nothing runs on a TPU: the seconds are
this sandbox's compiler, the shapes and programs are the engine's own.

    JAX_PLATFORMS=cpu python tools/compile_bill.py --queries q6,q1,q3
    JAX_PLATFORMS=cpu python tools/compile_bill.py --cell tpch_sf1_parquet.q3

``--cell`` records one cell of the benchmark instead (its set-up and one
query of the window, from the specification's files; a cell that waits in
``benchmarks/selfcheck.py`` too), to size a cell's first run.

Eager ``jnp`` ops outside any ``jax.jit`` (tiny programs) are not counted.
Programs compiled here cannot be read back on a chip, so the persistent
cache is off for this process.
"""

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

RECORDS = []   # (name, jitted, abstract args, kwargs)
_real_jit = jax.jit


def _abstract(x):
    return jax.ShapeDtypeStruct(x.shape, x.dtype) \
        if isinstance(x, jax.Array) else x


class _RecordingJit:
    """``jax.jit(fun)`` that remembers each new top-level specialization."""

    def __init__(self, fun, **kw):
        self._jitted = _real_jit(fun, **kw)
        self._name = getattr(fun, "__qualname__", repr(fun))
        self._seen = set()

    def __call__(self, *args, **kwargs):
        leaves, tree = jax.tree_util.tree_flatten((args, kwargs))
        if not any(isinstance(x, jax.core.Tracer) for x in leaves):
            key = (tree, tuple((x.shape, str(x.dtype))
                               if isinstance(x, jax.Array) else repr(x)
                               for x in leaves))
            if key not in self._seen:
                self._seen.add(key)
                a, k = jax.tree_util.tree_map(_abstract, (args, kwargs))
                RECORDS.append((self._name, self._jitted, a, k))
        return self._jitted(*args, **kwargs)

    def __getattr__(self, name):
        return getattr(self._jitted, name)


def _recording_jit(fun=None, **kw):
    if fun is None:
        return lambda f: _RecordingJit(f, **kw)
    return _RecordingJit(fun, **kw)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--queries", default="q6,q1,q3")
    ap.add_argument("--rows", type=int, default=6_001_215)
    ap.add_argument("--min-elements", type=int, default=1 << 16,
                    help="skip programs whose largest argument is smaller")
    ap.add_argument("--out", default=None, help="also write JSON lines here")
    ap.add_argument("--cell", default=None,
                    help="a cell of BENCHMARK.json (or one that waits in "
                         "benchmarks/selfcheck.py) instead of --queries")
    ap.add_argument("--seed", type=int, default=41, help="of --cell's data")
    args = ap.parse_args()

    jax.jit = _recording_jit          # before the engine is imported
    marks = []
    if args.cell:
        sys.path.insert(0, os.path.join(sys.path[0], "benchmarks"))
        import selfcheck
        run = selfcheck.run.run_cell(selfcheck.load_cell(args.cell),
                                     args.seed, 0.0, False)
        assert run["correct"], run["compared"]
        marks.append((args.cell, 0, len(RECORDS)))
    else:
        import chip_smoke
        for q in args.queries.split(","):
            n0 = len(RECORDS)
            chip_smoke.run([q], args.rows, 1)
            marks.append((q, n0, len(RECORDS)))
    jax.jit = _real_jit

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])

    def place(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip) \
            if isinstance(x, jax.ShapeDtypeStruct) else x

    out = open(args.out, "w") if args.out else None
    for q, lo, hi in marks:
        total = 0.0
        for name, jitted, a, k in RECORDS[lo:hi]:
            biggest = max((x.size for x in jax.tree_util.tree_leaves((a, k))
                           if isinstance(x, jax.ShapeDtypeStruct)), default=0)
            if biggest < args.min_elements:
                continue
            a, k = jax.tree_util.tree_map(place, (a, k))
            t0 = time.perf_counter()
            try:
                compiled = jitted.lower(*a, **k).compile()
                mem = compiled.memory_analysis()
                rec = {"ok": True,
                       "temp_bytes": mem.temp_size_in_bytes,
                       "argument_bytes": mem.argument_size_in_bytes,
                       "output_bytes": mem.output_size_in_bytes}
            except Exception as e:  # noqa: BLE001 - the refusal is the finding
                rec = {"ok": False, "error": f"{type(e).__name__}: {e}"[:400]}
            secs = time.perf_counter() - t0
            total += secs
            rec = {"query": q, "program": name, "largest_arg_elements": biggest,
                   "compile_seconds": round(secs, 2), **rec}
            print(json.dumps(rec), flush=True)
            if out:
                out.write(json.dumps(rec) + "\n")
                out.flush()
        print(json.dumps({"query": q, "total_compile_seconds": round(total, 1),
                          "programs": hi - lo}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
