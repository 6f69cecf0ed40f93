"""The process's walker threads: where a scan's host phase walks its column
chunks side by side (an ORC stripe's columns, `orc_device.decode_stripe`;
every column chunk of a parquet dispatch group, `parquet_device._read_chunks`)
where execution is pipelined (`spark.rapids.tpu.pipeline.enabled`).

A walk reads its chunk with `os.pread` and parses it in native code through
`ctypes`, and both release the GIL, so the walks of one group overlap and
the chip waits for the longest of them, not for their sum."""

from __future__ import annotations

import concurrent.futures as cf
import os
import threading
from typing import Callable, List, Optional, Sequence, TypeVar

from ..utils import spans
from ..utils.metrics import TaskMetrics

__all__ = ["walk_all", "walker_pool"]

R = TypeVar("R")

_WALKERS: Optional[cf.ThreadPoolExecutor] = None
_WALKERS_LOCK = threading.Lock()


def walker_pool() -> cf.ThreadPoolExecutor:
    """The process's few threads that walk a scan's column chunks side by
    side. They live as long as the process: a thread keeps its allocator
    arena, so a query's walk finds the buffers the last one's left, where a
    fresh thread for every query faults its pages in anew (PERF.md, fault
    22)."""
    global _WALKERS
    with _WALKERS_LOCK:
        if _WALKERS is None:
            _WALKERS = cf.ThreadPoolExecutor(
                max_workers=max(1, min(4, len(os.sched_getaffinity(0)) - 1)),
                thread_name_prefix="srtpu-walk")
        return _WALKERS


def walk_all(walkers: cf.ThreadPoolExecutor,
             walks: Sequence[Callable[[], R]]) -> List[R]:
    """Run every walk at once on `walkers` -> their results, in order. Each
    walk counts in the calling query's `TaskMetrics` under a `scan.walk`
    span of its own, so the stage reads thread-seconds. The first walk (in
    order) that raised raises here, once no walk is left running: the
    caller may close the file they read as soon as this returns."""
    tm = TaskMetrics.get()

    def run(walk):
        # a walker thread outlives the query: its walk counts in the
        # query's TaskMetrics, and nothing after it does
        with TaskMetrics.adopted(tm), \
                spans.span("scan.walk", kind=spans.KIND_IO):
            return walk()

    futures = [walkers.submit(run, w) for w in walks]
    try:
        return [fut.result() for fut in futures]
    finally:
        for fut in futures:
            fut.cancel()
        cf.wait(futures)
