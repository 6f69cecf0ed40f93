"""spark_rapids_tpu — TPU-native accelerator with the capabilities of the RAPIDS
Accelerator for Apache Spark (see ARCHITECTURE.md / SURVEY.md)."""

__version__ = "0.1.0"

import jax as _jax

# LONG/DOUBLE are core SQL types; the framework is unusable with 32-bit-only math.
# (On TPU, f64 lowers to XLA's emulation; the planner can demote DOUBLE compute to f32
# when spark.rapids.tpu.f64.emulation=false.)
_jax.config.update("jax_enable_x64", True)



def _tune_host_malloc() -> bool:
    """glibc's allocator as a long-lived executor wants it: heaps that grow
    in large steps, keep what was freed, and serve buffers of up to 32 MiB.

    By default an arena other than the main one grows its heap a page at a
    time (one `mprotect` per 4 KiB), gives the top back as soon as it is
    free, and every buffer over 128 KiB is a fresh `mmap` that faults in page
    by page. The engine works on threads (scan decode, prefetch), so its
    allocations land in such arenas: XLA loading the fact decode program
    from the persistent cache took 14.2-15.6 s on a scan thread and 2.0-3.2 s
    on the main thread, the same bytes in the same process, and which of the
    two a restarted executor's first query got was a matter of which arena
    its thread drew (PERF.md, fault 19). An operator who set glibc's own
    knobs in the environment keeps them; another libc has no `mallopt`."""
    import ctypes
    import os
    if any(k.startswith("MALLOC_") or k == "GLIBC_TUNABLES"
           for k in os.environ):
        return False
    try:
        mallopt = ctypes.CDLL(None).mallopt
    except (OSError, AttributeError):
        return False
    m_trim_threshold, m_top_pad, m_mmap_threshold = -1, -2, -3
    return bool(mallopt(m_mmap_threshold, 32 << 20)
                and mallopt(m_top_pad, 256 << 20)
                and mallopt(m_trim_threshold, 1 << 30))


_tune_host_malloc()

# seconds between two `settle_host_heap()`s that do anything
_SETTLE_EVERY_S = 60.0
_settled_at = None


def settle_host_heap() -> bool:
    """Python's cyclic collector as a long-lived executor wants it: what a
    query that compiled leaves behind (programs, their jaxprs, the plan and
    kernel caches, jax's own modules: some 150,000 container objects) lives
    as long as the process, and every full collection walks all of it:
    55-60 ms in the sandbox and 0.115 s on the chip machine, once in some
    thirty warm queries, in the middle of whichever query crosses the
    collector's threshold (PERF.md, fault 23). Called at the end of a query
    that compiled: one full collection, then everything alive moves to the
    permanent generation (`gc.freeze`), so that later full collections walk
    only what was made since. What was frozen earlier and has died since is
    thawed and collected first, so nothing stays frozen for longer than to
    the next call that works, and a call works at most once a minute: a
    process that compiles in every query pays one collection a minute for
    it. False where the call came too early, or the collector is off."""
    import gc
    import time
    global _settled_at
    now = time.monotonic()
    if not gc.isenabled() or (
            _settled_at is not None and now - _settled_at < _SETTLE_EVERY_S):
        return False
    _settled_at = now
    gc.unfreeze()
    gc.collect()
    gc.freeze()
    return True

from . import types  # noqa: F401
from .config import TpuConf, get_default_conf  # noqa: F401
