"""Tracing ranges (reference `NvtxWithMetrics.scala`; NVTX → jax.profiler).

`trace_range` is the one place that touches `jax.profiler`: it opens a
`TraceAnnotation`, so an engine region lands in the profiler's own trace, on
the device trace's clock, the way Nsight consumed NVTX ranges. Every engine
annotation carries `SPAN_PREFIX`, so a trace reader can tell an engine span
from a traced Python frame. With no profiler session an annotation is one
atomic load. `utils/spans.span` opens every span through it; the compile
service uses it directly for the regions that are too many for a query
profile (`dispatch.<op>`, `compile.<stage>.<op>`)."""

from __future__ import annotations

import time

try:
    import jax.profiler as _profiler
    _HAVE_PROFILER = True
except Exception:  # pragma: no cover
    _profiler = None
    _HAVE_PROFILER = False

__all__ = ["SPAN_PREFIX", "trace_range"]

SPAN_PREFIX = "srt:"


class trace_range:
    """`with trace_range(name, metric=None)`: the annotation
    `SPAN_PREFIX + name`, optionally feeding a timing metric as well."""

    __slots__ = ("_ann", "_metric", "_t0")

    def __init__(self, name: str, metric=None):
        self._ann = _profiler.TraceAnnotation(SPAN_PREFIX + name) \
            if _HAVE_PROFILER else None
        self._metric = metric
        self._t0 = 0

    def __enter__(self) -> "trace_range":
        if self._metric is not None:
            self._t0 = time.monotonic_ns()
        if self._ann is not None:
            self._ann.__enter__()
        return self

    def __exit__(self, *exc) -> bool:
        if self._ann is not None:
            self._ann.__exit__(*exc)
        # also on an exception inside the region (ANSI violation, OOM-retry):
        # the elapsed time must still be charged to the metric
        if self._metric is not None:
            self._metric.add(time.monotonic_ns() - self._t0)
        return False
