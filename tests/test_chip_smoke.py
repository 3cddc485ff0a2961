"""What keeps chip_smoke.py honest without a chip: it cannot pass on the
CPU backend, the compile cache goes where JAX_COMPILATION_CACHE_DIR says
(else to one fixed directory in the checkout), and under
``spark.rapids.sql.test.enabled`` a row group the device decoder cannot
read is an error, not a quiet host read."""

import os
import subprocess
import sys

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import chip_smoke
from spark_rapids_tpu.session import TpuSession
from spark_rapids_tpu.utils.fault_injection import InjectedFault

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _child(args, **env):
    """Run python in the checkout, pinned to the CPU backend."""
    full = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    full.update(JAX_PLATFORMS="cpu", **env)
    return subprocess.run([sys.executable, *args], cwd=REPO, env=full,
                          capture_output=True, text=True, timeout=300)


def test_chip_smoke_fails_without_a_chip():
    proc = _child(["chip_smoke.py"])
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr


@pytest.mark.parametrize("placed_from_outside", [True, False])
def test_compile_cache_directory(tmp_path, placed_from_outside):
    env = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)} \
        if placed_from_outside else {}
    proc = _child(["-c", "import jax, spark_rapids_tpu as s; "
                         "print(jax.config.jax_compilation_cache_dir); "
                         "print(s.COMPILE_CACHE_DIR)"], **env)
    assert proc.returncode == 0, proc.stderr
    want = str(tmp_path) if placed_from_outside \
        else os.path.join(REPO, ".jax_cache")
    assert proc.stdout.split() == [want, want]


@pytest.mark.parametrize("test_enabled", [True, False])
def test_parquet_decode_failure_is_loud_under_test_enabled(tmp_path,
                                                           test_enabled):
    from spark_rapids_tpu.ops import predicates as P
    from spark_rapids_tpu.ops.expression import col, lit
    path = str(tmp_path / "t.parquet")
    pq.write_table(pa.table({"seq": np.arange(4000, dtype=np.int64)}), path,
                   row_group_size=1000)
    session = TpuSession({
        "spark.rapids.sql.enabled": True,
        "spark.rapids.sql.test.enabled": test_enabled,
        "spark.rapids.tpu.metrics.level": "ESSENTIAL",
        "spark.rapids.tpu.retry.backoffBaseMs": 0.0,
        # every visit of the device decoder's seam faults
        "spark.rapids.tpu.test.faultInjection.sites": "io.parquet.rowGroup",
        "spark.rapids.tpu.test.faultInjection.oomEveryN": 1})
    query = session.read.parquet(path).where(
        P.GreaterThanOrEqual(col("seq"), lit(0)))
    if test_enabled:
        with pytest.raises(InjectedFault, match="io.parquet.rowGroup"):
            query.collect()
        return
    assert query.collect().num_rows == 4000
    profile = session.last_query_profile()
    totals = profile.totals()
    assert totals["hostFallbackRowGroups"] == 4
    assert totals.get("deviceDecodedRowGroups", 0) == 0
