"""spark_rapids_tpu — a TPU-native columnar SQL execution framework.

A brand-new framework with the capabilities of the RAPIDS Accelerator for
Apache Spark (the reference at /root/reference): columnar operators whose
batches live in TPU HBM and are evaluated as fused XLA programs, a
plan-rewrite layer with per-operator CPU fallback and explain output, a
collective-based shuffle over the device mesh, a device→host→disk spill
hierarchy, a UDF bytecode compiler, and zero-copy export to JAX ML.

See SURVEY.md for the capability blueprint and the mapping from each
reference component to its TPU-native counterpart here.
"""

import os

import jax

# The SQL type system requires real int64/float64 columns (Spark bigint /
# double). jax disables 64-bit types by default; turn them on before any
# array is created anywhere in the package.
jax.config.update("jax_enable_x64", True)

# Persistent XLA compilation cache: on, and this is the one place that
# decides where it lives. Where JAX_COMPILATION_CACHE_DIR is set, jax reads
# it itself and no code here or in compile/persist.py sets another
# directory. Otherwise it is one fixed directory inside the checkout,
# computed from __file__ — never a temporary name, the home directory, a
# pid or the time: a cache that moves between processes never hits.
COMPILE_CACHE_DIR = os.environ.get("JAX_COMPILATION_CACHE_DIR")
if not COMPILE_CACHE_DIR:
    COMPILE_CACHE_DIR = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
# jax's own floor is 1 s; the filter + aggregate programs of the scan-bound
# queries compile in ~3 s for the TPU and must be kept, the sub-half-second
# eager ops are not worth a file each.
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)

from .version import __version__  # noqa: E402,F401
from . import types  # noqa: E402,F401
from .config import TpuConf  # noqa: E402,F401
