"""Tracing ranges (reference `NvtxWithMetrics.scala`; NVTX → jax.profiler).

`trace_range` is the one place that touches `jax.profiler`: it opens a
`TraceAnnotation`, so an engine region lands in the profiler's own trace, on
the device trace's clock, the way Nsight consumed NVTX ranges. Every engine
annotation carries `SPAN_PREFIX`, so a trace reader can tell an engine span
from a traced Python frame. With no profiler session an annotation is one
atomic load. `utils/spans.span` opens every span through it; the compile
service uses it directly for the regions that are too many for a query
profile (`dispatch.<op>`, `compile.<stage>.<op>`).

`STAGES` names the hot path's stages: a range whose name it holds adds its
wall time to the `TaskMetrics` field beside it, always, with a profile or
without, so the query's record in the ring of recent queries says where its
host time went. Benchmark readers import the table and `stage_of`, so what
they attribute and what the engine counts are one list."""

from __future__ import annotations

import time
from typing import Optional

from .metrics import TaskMetrics

try:
    import jax.profiler as _profiler
    _HAVE_PROFILER = True
except Exception:  # pragma: no cover
    _profiler = None
    _HAVE_PROFILER = False

__all__ = ["SPAN_PREFIX", "STAGES", "stage_of", "trace_range"]

SPAN_PREFIX = "srt:"

# stage span -> the TaskMetrics field it charges; a key ending in `.*` holds
# every name under that head (`dispatch.<op>`). Times are thread-seconds: a
# stage that runs on several threads at once (the ORC walkers) adds them up
STAGES = {
    "scan.walk": "scan_walk_ns",          # a chunk's or stripe's host walk
    "scan.pack": "scan_pack_ns",          # signatures, buckets, packing
    "scan.h2d": "h2d_ns",                 # the scan's batched transfers
    "dispatch.*": "dispatch_ns",          # digest, lookup and launch
    "sync.row_count": "host_sync_ns",     # a device row count read
    "sync.join_size": "host_sync_ns",     # a join's output size read
    "sink.d2h": "d2h_ns",                 # the sink's device -> host copy
    "plan.rewrite": "plan_rewrite_ns",    # Overrides.apply
}


def stage_of(name: str) -> Optional[str]:
    """The key of `STAGES` that span `name` (no prefix) falls under, or
    None: `dispatch.exec.sort` -> `dispatch.*`, `op.TpuSortExec` -> None."""
    if name in STAGES:
        return name
    head, dot, _ = name.partition(".")
    head += ".*"
    return head if dot and head in STAGES else None


class trace_range:
    """`with trace_range(name, metric=None)`: the annotation
    `SPAN_PREFIX + name`, its wall time added to the stage's `TaskMetrics`
    field where `STAGES` names one, and optionally to a timing metric."""

    __slots__ = ("_ann", "_metric", "_field", "_t0")

    def __init__(self, name: str, metric=None):
        self._ann = _profiler.TraceAnnotation(SPAN_PREFIX + name) \
            if _HAVE_PROFILER else None
        self._metric = metric
        stage = stage_of(name)
        self._field = None if stage is None else STAGES[stage]
        self._t0 = 0

    def __enter__(self) -> "trace_range":
        if self._metric is not None or self._field is not None:
            self._t0 = time.perf_counter_ns()
        if self._ann is not None:
            self._ann.__enter__()
        return self

    def __exit__(self, *exc) -> bool:
        if self._ann is not None:
            self._ann.__exit__(*exc)
        # also on an exception inside the region (ANSI violation, OOM-retry):
        # the elapsed time must still be charged
        if self._metric is not None or self._field is not None:
            dt = time.perf_counter_ns() - self._t0
            if self._field is not None:
                TaskMetrics.get().add(self._field, dt)
            if self._metric is not None:
                self._metric.add(dt)
        return False
