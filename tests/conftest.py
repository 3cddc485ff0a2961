"""Test configuration: two tiers, mirroring the reference's strategy
(SURVEY.md §4).

Default tier — virtual 8-device CPU mesh: unit tests run locally and
deterministically; multi-chip sharding logic is exercised on a faked
8-device mesh via ``xla_force_host_platform_device_count``, exactly as the
driver validates ``dryrun_multichip``. The CPU backend also makes float64
tests exact: a TPU emulates f64, which the differential harness would flag
as false diffs.

Device tier — ``pytest --tpu``: the same differential tests run on the REAL
TPU backend (the reference runs its whole suite on the real GPU,
docs/testing.md). Float comparisons get a documented tolerance
(docs/compatibility.md:31-66 stance, applied in harness.py), and tests
that require the virtual multi-device mesh skip (one real chip).
Recommended device run:

    python -m pytest --tpu tests/test_expressions.py \
        tests/test_expressions2.py tests/test_cast_matrix.py \
        tests/test_string_datetime_ops.py tests/test_queries.py \
        tests/test_complex_types.py -q

Backend selection happens in ``pytest_configure`` (after option parsing,
before any test module imports jax), so PYTEST_ADDOPTS / ini addopts forms
of ``--tpu`` work the same as the literal flag.
"""
import os


def pytest_addoption(parser):
    parser.addoption(
        "--tpu", action="store_true", default=False,
        help="run the differential suite on the real TPU backend "
             "(float comparisons get tolerance; virtual-mesh tests skip)")


def pytest_configure(config):
    # Runtime lockdep (utils/lockdep.py, docs/concurrency.md): instrument
    # every engine lock so the WHOLE suite runs as a lockdep-supervised
    # schedule corpus. Must be exported before any test module imports
    # the engine — module-level locks are constructed at import time.
    # The session gate below fails the run on any recorded violation.
    # An explicit falsey export (0/false/no/off) opts a local debug run
    # out (tests/test_lockdep.py then SKIPS its corpus-contract test
    # rather than failing); anything else — unset, empty, or a value
    # lockdep would not recognize — arms the gate. CI never sets it.
    if os.environ.get("TPU_LOCKDEP", "").strip().lower() \
            not in ("0", "false", "no", "off"):
        os.environ["TPU_LOCKDEP"] = "1"
    if config.getoption("--tpu"):
        # Signal the harness to compare floats with tolerance.
        os.environ["SRTPU_TEST_TPU"] = "1"
        return
    # Must be set before the jax backend initializes; the jax_platforms
    # config update below is what pins the tier to the CPU backend.
    os.environ.setdefault("JAX_PLATFORM_NAME", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    # Persistent compilation cache off for the CPU tier: six workers
    # compile tens of thousands of small XLA:CPU programs that no later
    # process reads, tests of the compile layer count real compiles
    # (tests/test_compile_cache.py, test_polymorphic.py), and the v5e
    # programs tests/test_chip_compile.py builds cannot be read back
    # without a chip. jax reads this variable itself; the directory the
    # package (or JAX_COMPILATION_CACHE_DIR) chose is left where it is.
    os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    # The full suite JIT-compiles thousands of XLA executables; each maps
    # several code regions, and once the process crosses the kernel's
    # vm.max_map_count (default 65530 — observed ~4k maps/minute here) a
    # failed mmap inside XLA's loader SIGSEGVs mid-suite. Root-only best
    # effort; harmless when already high or not permitted.
    try:
        with open("/proc/sys/vm/max_map_count") as f:
            if int(f.read()) < (1 << 20):
                with open("/proc/sys/vm/max_map_count", "w") as g:
                    g.write(str(1 << 20))
    except (OSError, ValueError):
        pass
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_enable_compilation_cache", False)


def pytest_sessionfinish(session, exitstatus):
    """Pipeline-worker leak check (docs/tuning-guide.md): every shared
    pipeline pool thread must join on shutdown — the same guarantee
    ``TpuSession.close`` makes. A worker that cannot be joined here is a
    leaked producer (stuck put, undrained queue) and fails the run."""
    import sys
    mod = sys.modules.get("spark_rapids_tpu.exec.pipeline")
    if mod is None:
        return  # suite never touched the engine
    leaked = mod.shutdown(timeout=15)
    if leaked:
        session.exitstatus = 1
        print("ERROR: pipeline worker threads survived shutdown "
              f"(TpuSession.close leak): {[t.name for t in leaked]}",
              file=sys.stderr)
    # Lockdep gate (docs/concurrency.md): the suite doubles as a schedule
    # corpus — any lock-order inversion, self-deadlock, or
    # hold-across-blocking recorded by ANY test fails the run. Tests that
    # provoke violations on purpose drain them (lockdep.drain_violations).
    ld = sys.modules.get("spark_rapids_tpu.utils.lockdep")
    if ld is not None and ld.violations():
        session.exitstatus = 1
        print("ERROR: lockdep recorded lock-discipline violation(s) "
              "during the suite (utils/lockdep.py, docs/concurrency.md):",
              file=sys.stderr)
        for v in ld.violations():
            print(f"  {v}", file=sys.stderr)


#: Test modules that need the 8-device virtual mesh (single real chip
#: cannot run them; the driver's dryrun_multichip covers that path).
_NEEDS_VIRTUAL_MESH = {"test_distributed", "test_mesh"}


def pytest_collection_modifyitems(config, items):
    if not config.getoption("--tpu"):
        return
    import jax
    import pytest
    n_dev = len(jax.devices())
    skip = pytest.mark.skip(
        reason=f"needs the 8-device virtual CPU mesh (have {n_dev} real)")
    for item in items:
        if item.module.__name__ in _NEEDS_VIRTUAL_MESH and n_dev < 8:
            item.add_marker(skip)
