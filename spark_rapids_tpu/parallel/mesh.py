"""Device mesh management — the multi-chip execution substrate.

The reference's parallelism model is Spark data parallelism: one process per
executor, one GPU each, exchange via shuffle (SURVEY.md §2.6 "Parallelism
strategy inventory"). The TPU-native model replaces one-process-per-device
with a single SPMD program over a ``jax.sharding.Mesh``: partitions live as
shards of device arrays, and the exchange runs as XLA collectives over ICI
(:mod:`..shuffle.ici`) instead of a point-to-point UCX transport.

The canonical mesh axis is ``"part"`` — the partition-parallel axis that
carries both the data-parallel scan/filter/project work and the all_to_all
shuffle. This is the honest analog of the reference's executor grid; a SQL
engine has no tensor/pipeline axes (the reference has none either).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax import shard_map  # noqa: F401  (re-exported: the one import seam)
from jax.sharding import Mesh, NamedSharding, PartitionSpec

PART_AXIS = "part"

#: Backend error substrings that mean a mesh device (or its host) is
#: gone mid-query rather than the program being wrong: the runtime's
#: wire-level disconnect codes plus the PJRT device-health vocabulary.
#: Matched by :func:`is_device_loss` so exec/mesh.py can convert an
#: opaque XlaRuntimeError into the typed :class:`MeshDegradedError`.
_DEVICE_LOSS_MARKERS = ("DATA_LOSS", "device is in an invalid state",
                        "Device or resource busy", "UNAVAILABLE",
                        "device unavailable", "halted", "ICI topology",
                        "slice health", "missing devices")


class MeshDegradedError(RuntimeError):
    """A device/host in the SPMD mesh was lost (or failed its health
    probe) mid-query. Typed so the retry taxonomy classifies it
    TRANSIENT: the session records a ``meshFailovers`` counter, dumps
    the failover timeline to the flight recorder, marks the mesh
    degraded, and re-runs the query on the single-chip path — a slower
    correct answer, never a wrong one (docs/fault-tolerance.md)."""

    def __init__(self, reason: str, failed_devices: Sequence = ()):
        self.reason = reason
        self.failed_devices = list(failed_devices)
        detail = f"mesh degraded: {reason}"
        if self.failed_devices:
            detail += f" (failed devices: {self.failed_devices})"
        super().__init__(detail)


def is_device_loss(exc: BaseException) -> bool:
    """Whether a backend error reads as a lost device/host rather than a
    program bug. Conservative: only the known runtime disconnect and
    device-health markers match; anything else stays FATAL."""
    msg = str(exc)
    return any(m in msg for m in _DEVICE_LOSS_MARKERS)


def probe_devices(devices: Optional[Sequence] = None) -> list:
    """Health-probe each device with a tiny transfer; return the list of
    devices that failed (empty = healthy mesh). A one-scalar
    ``device_put`` + ``block_until_ready`` round-trips the runtime's
    enqueue/execute/transfer path per device — the cheapest signal that
    the chip still answers — without touching any query state. Used by
    the optional pre-dispatch probe
    (spark.rapids.tpu.mesh.health.probeEnabled) and by tests."""
    if devices is None:
        devices = jax.devices()
    failed = []
    for d in devices:
        try:
            jax.device_put(np.int32(0), d).block_until_ready()
        except Exception:  # noqa: BLE001 - any failure means unhealthy
            failed.append(d)
    return failed


def make_mesh(n_devices: Optional[int] = None,
              devices: Optional[Sequence] = None) -> Mesh:
    if devices is None:
        devices = jax.devices()
        if n_devices is not None:
            if len(devices) < n_devices:
                raise ValueError(
                    f"need {n_devices} devices, have {len(devices)}")
            devices = devices[:n_devices]
    return Mesh(np.asarray(devices), (PART_AXIS,))


def partitioned(mesh: Mesh) -> NamedSharding:
    """Sharding that splits the leading (row/partition) dim across the mesh."""
    return NamedSharding(mesh, PartitionSpec(PART_AXIS))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())
