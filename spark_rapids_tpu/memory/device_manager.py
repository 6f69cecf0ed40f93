"""Device manager (reference `GpuDeviceManager.scala`: initializeGpuAndMemory
`:128`, pool sizing `computeRmmPoolSize` `:192`, rmm init `:247-343`).

Binds the TPU device, computes the HBM budget for columnar data (fraction of the
chip's HBM minus reserve, like the RMM pool sizing), and owns process-wide
singletons: the memory budget tracker and the admission semaphore. XLA owns the
actual allocator; our budget tracker does pre-flight accounting so memory pressure
raises host-side RetryOOM before kernels launch (ARCHITECTURE.md #6)."""

from __future__ import annotations

import os
import threading
import time
from typing import Optional

from ..config import TpuConf, get_default_conf
from ..errors import DeviceStartupError

# The CPU backend has no HBM to report; the tests that run on it size their
# budget as one v5e chip (16 GiB). Never assumed for a real device.
_DEFAULT_HBM = 16 << 30


def _backend_touch():
    """The first backend touch — client init + device enumeration. Split out
    so tests can substitute a hanging/failing backend. The injection point
    sits INSIDE the touch (it runs on the deadline-guarded worker thread),
    so an injected wedge exercises the same hang path as a device runtime
    that hangs at first touch."""
    from .. import faults
    faults.fire(faults.DEVICE_INIT)
    import jax
    return jax.devices()


class DeviceManager:
    _lock = threading.Lock()
    _initialized = False
    device = None
    hbm_total = 0
    budget_bytes = 0
    # observed fatal startup failure, remembered so every later query fails
    # fast instead of re-arming a fresh deadline against a wedged runtime
    _startup_error: Optional[DeviceStartupError] = None

    @classmethod
    def _first_touch(cls, conf: TpuConf):
        """Enumerate devices under a deadline. A device runtime can HANG (not
        raise) inside client init at first touch; a query must fail in
        seconds with a typed error, not block forever
        (`Plugin.scala:436-459` analog)."""
        if cls._startup_error is not None:
            raise cls._startup_error
        timeout = conf.get("spark.rapids.tpu.device.startupTimeoutSec")
        if timeout is None or timeout <= 0:
            return _backend_touch()
        result: dict = {}

        def touch():
            try:
                result["devices"] = _backend_touch()
            except Exception as exc:  # noqa: BLE001 — re-raised typed below
                result["error"] = exc

        t0 = time.monotonic()
        worker = threading.Thread(target=touch, daemon=True,
                                  name="tpu-backend-first-touch")
        worker.start()
        worker.join(timeout)
        diags = {
            "elapsed_s": round(time.monotonic() - t0, 2),
            "timeout_s": timeout,
            "jax_platforms_env": os.environ.get("JAX_PLATFORMS", ""),
            "xla_flags": os.environ.get("XLA_FLAGS", ""),
        }
        if worker.is_alive():
            err = DeviceStartupError(
                "TPU backend did not respond within "
                f"{timeout:g}s of first touch (client init / device "
                "enumeration hang). Device "
                f"execution disabled for this process. Diagnostics: {diags}",
                diagnostics=diags)
            cls._startup_error = err
            raise err
        if "error" in result:
            diags["cause"] = repr(result["error"])
            err = DeviceStartupError(
                f"TPU backend failed at first touch: {result['error']}. "
                f"Diagnostics: {diags}", diagnostics=diags)
            cls._startup_error = err
            raise err from result["error"]
        return result["devices"]

    @classmethod
    def initialize(cls, conf: Optional[TpuConf] = None) -> None:
        with cls._lock:
            if cls._initialized:
                return
            conf = conf or get_default_conf()
            devices = cls._first_touch(conf)
            ordinal = conf.get("spark.rapids.tpu.device.ordinal")
            cls.device = devices[ordinal if ordinal >= 0 else 0]
            cls.hbm_total = cls._query_hbm(cls.device)
            frac = conf.get("spark.rapids.memory.gpu.allocFraction")
            max_frac = conf.get("spark.rapids.memory.gpu.maxAllocFraction")
            min_frac = conf.get("spark.rapids.memory.gpu.minAllocFraction")
            reserve = conf.get("spark.rapids.memory.gpu.reserve")
            frac = min(frac, max_frac)
            budget = int(cls.hbm_total * frac) - reserve
            if budget < int(cls.hbm_total * min_frac):
                raise RuntimeError(
                    f"HBM budget {budget} below minAllocFraction "
                    f"({min_frac} of {cls.hbm_total}); adjust "
                    "spark.rapids.memory.gpu.* settings")
            cls.budget_bytes = budget
            from .budget import MemoryBudget
            MemoryBudget.initialize(budget, conf)
            from .semaphore import TpuSemaphore
            TpuSemaphore.initialize(conf.concurrent_tpu_tasks, conf)
            cls._initialized = True

    @staticmethod
    def _query_hbm(device) -> int:
        """The device's memory limit as the device reports it. Only the CPU
        backend (no HBM, reports nothing) gets the default; a real device
        that will not say is a startup failure, not a 16 GiB guess."""
        why = "memory_stats() reported no bytes_limit"
        try:
            stats = device.memory_stats()
            if stats and "bytes_limit" in stats:
                return int(stats["bytes_limit"])
        except Exception as e:  # noqa: BLE001 — re-raised typed below
            why = f"memory_stats() failed: {e!r}"
        platform = getattr(device, "platform", "")
        if platform == "cpu":
            return _DEFAULT_HBM
        raise DeviceStartupError(
            f"cannot size the HBM budget on platform {platform!r}: {why}",
            diagnostics={"platform": platform, "cause": why})

    @classmethod
    def shutdown(cls) -> None:
        """Tear down device state; buffers still registered in the spill
        catalog are leaks (an unclosed SpillableColumnarBatch) and log a
        warning with the allocator state, like the reference's
        shutdown-time RMM leak logging (GpuDeviceManager.scala:295-305,
        MemoryCleaner leak log)."""
        import logging
        try:
            from .catalog import BufferCatalog
            # guard on the existing instance: get() would lazily build a
            # catalog (and its spill temp dir) as a teardown side effect
            leaks = BufferCatalog.get().leak_report() \
                if BufferCatalog._instance is not None else []
            if leaks:
                log = logging.getLogger("spark_rapids_tpu.memory")
                log.warning(
                    "device shutdown with %d leaked buffer handle(s) "
                    "(%d bytes) — close() every SpillableColumnarBatch:\n%s",
                    len(leaks), sum(e["nbytes"] for e in leaks),
                    BufferCatalog.get().debug_dump())
        except Exception:
            pass
        with cls._lock:
            cls._initialized = False
            cls.device = None
            cls._startup_error = None
