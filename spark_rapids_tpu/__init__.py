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

from . import types  # noqa: F401
from .config import TpuConf, get_default_conf  # noqa: F401
