"""Device manager — the ``GpuDeviceManager`` analog.

The reference acquires the single GPU per executor, initializes the RMM pool
with a fraction of VRAM, and wires the spill event handler
(GpuDeviceManager.scala:120-214). JAX/XLA owns HBM allocation on TPU, so the
TPU-native analog manages: backend selection, the one-device invariant for
local execution, HBM budget accounting for the spill framework, and the task
semaphore bootstrap. Multi-chip execution goes through the mesh layer
(:mod:`..parallel.mesh`) instead of one-process-per-device.

Backend init is LAZY: constructing a session (CPU-oracle sessions included,
``sql.enabled=false``) must never initialize the accelerator backend — the
reference likewise only touches the GPU from the *executor* plugin, never on
the driver (Plugin.scala:104-143). ``jax.devices()`` on a broken/unreachable
TPU backend can hang or raise; deferring it to first device use keeps pure
host paths (oracle runs, planning, explain) alive regardless.
"""

from __future__ import annotations

import logging

from ..config import (CONCURRENT_ACQUIRE_TIMEOUT, CONCURRENT_TPU_TASKS,
                      DEVICE_BACKEND, DEVICE_SPILL_BUDGET,
                      HBM_ALLOC_FRACTION, HOST_SPILL_STORAGE_SIZE,
                      MEMORY_DEBUG, SPILL_DIR, SPILL_IO_THREADS, TpuConf)
from ..utils import lockdep
from .semaphore import TpuSemaphore

#: Conservative HBM guess used when the backend can't report a size (CPU
#: backend, or device never touched). Matches the reference's stance of a
#: fraction-of-total pool (RapidsConf.scala:257).
_DEFAULT_HBM_BYTES = 16 << 30

#: Probe-shaped failures of ``device.memory_stats()``: the backend simply
#: cannot report (CPU backends, plugin API drift). Tolerated alongside the
#: retry taxonomy's OOM/transient classes; anything else raises.
_PROBE_ERRORS = (NotImplementedError, AttributeError, TypeError,
                 ValueError, KeyError)


class DeviceManager:
    _instances: dict = {}
    _lock = lockdep.lock("DeviceManager._lock")

    def __init__(self, conf: TpuConf):
        self._backend = conf.get(DEVICE_BACKEND)
        self._frac = conf.get(HBM_ALLOC_FRACTION)
        self.debug = conf.get(MEMORY_DEBUG)
        self.semaphore = TpuSemaphore(
            conf.get(CONCURRENT_TPU_TASKS),
            conf.get(CONCURRENT_ACQUIRE_TIMEOUT))
        self._devices = None
        self._hbm_budget = None
        self._init_lock = lockdep.lock("DeviceManager._init_lock", io_ok=True)
        self._warned_probes: set = set()
        # Spill catalog: the GpuShuffleEnv.initStorage chain
        # (device -> host -> disk, GpuShuffleEnv.scala:52-69). The device
        # budget resolves lazily on the first budget check — by then device
        # buffers exist, so the backend is necessarily live.
        from .spill import BufferCatalog
        explicit = conf.get(DEVICE_SPILL_BUDGET)
        self.catalog = BufferCatalog(
            explicit if explicit > 0 else (lambda: self.hbm_budget_bytes),
            conf.get(HOST_SPILL_STORAGE_SIZE),
            conf.get(SPILL_DIR),
            io_threads=conf.get(SPILL_IO_THREADS))

    @property
    def devices(self):
        if self._devices is None:
            with self._init_lock:
                if self._devices is None:
                    import jax
                    self._devices = (jax.devices(self._backend)
                                     if self._backend else jax.devices())
        return self._devices

    @property
    def device(self):
        return self.devices[0]

    def _classify_probe_failure(self, what: str, e: Exception) -> None:
        """Narrowed swallow for memory-probe failures: OOM/transient
        classes from the retry taxonomy and probe-shaped backend errors
        degrade to defaults with ONE warning per probe; anything else —
        a genuinely broken backend — raises instead of silently lying."""
        from .retry import Classification, classify
        if not isinstance(e, _PROBE_ERRORS) \
                and classify(e) == Classification.FATAL:
            raise e
        if what not in self._warned_probes:
            self._warned_probes.add(what)
            logging.getLogger(__name__).warning(
                "device memory probe %s failed (%s: %s); reporting "
                "defaults from here on", what, type(e).__name__, e)

    @property
    def hbm_budget_bytes(self) -> int:
        """Fraction-of-HBM byte budget for the spill framework; jax doesn't
        expose exact HBM sizes for every backend, so fall back to a
        conservative default."""
        if self._hbm_budget is None:
            try:
                stats = self.device.memory_stats() or {}
                total = stats.get("bytes_limit", _DEFAULT_HBM_BYTES)
            except Exception as e:  # noqa: BLE001 - classify-narrowed
                # An assumed budget is fine on the CPU backend and wrong
                # on a chip, whose HBM size must come from the device.
                if self.device.platform == "tpu":
                    raise
                self._classify_probe_failure("memory_stats(bytes_limit)", e)
                total = _DEFAULT_HBM_BYTES
            self._hbm_budget = int(total * self._frac)
        return self._hbm_budget

    @classmethod
    def get_or_create(cls, conf: TpuConf) -> "DeviceManager":
        # One manager per distinct device/memory configuration: sessions that
        # override spill budgets or directories (test hooks) must not silently
        # inherit the first session's catalog.
        key = (conf.get(DEVICE_BACKEND), conf.get(HBM_ALLOC_FRACTION),
               conf.get(DEVICE_SPILL_BUDGET),
               conf.get(HOST_SPILL_STORAGE_SIZE), conf.get(SPILL_DIR),
               conf.get(SPILL_IO_THREADS),
               conf.get(CONCURRENT_TPU_TASKS),
               conf.get(CONCURRENT_ACQUIRE_TIMEOUT))
        with cls._lock:
            inst = cls._instances.get(key)
            if inst is None:
                inst = cls._instances[key] = DeviceManager(conf)
            return inst

    @classmethod
    def reset(cls):
        with cls._lock:
            for inst in cls._instances.values():
                inst.catalog.close()
            cls._instances.clear()

    def hbm_watermarks(self, device_session: bool = False) -> dict:
        """HBM usage snapshot for the query profile, as the device's
        allocator reports it (``memory_stats()``: ``bytes_in_use`` now,
        ``peak_bytes_in_use`` the process's high-water mark so far — not
        this query's alone; 0 where the backend keeps no stats, as the
        CPU's). Never initializes the backend on its own: a CPU-oracle
        session (sql.enabled=false) querying its profile must not touch
        the accelerator, so the watermarks report 0 until something has
        resolved the devices (the lazy-init contract above). A device
        session's query has run on the device whether or not it came
        through this manager; ``device_session`` resolves them here."""
        if self._devices is None and not device_session:
            return {"hbmBytesInUse": 0, "hbmPeakBytesInUse": 0}
        try:
            stats = self.device.memory_stats() or {}
        except Exception as e:  # noqa: BLE001 - classify-narrowed
            self._classify_probe_failure("memory_stats", e)
            stats = {}
        return {"hbmBytesInUse": int(stats.get("bytes_in_use", 0)),
                "hbmPeakBytesInUse": int(stats.get("peak_bytes_in_use", 0))}
