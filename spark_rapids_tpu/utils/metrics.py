"""Operator metrics (reference `GpuMetric`/`GpuExec.scala:42-150` and
`GpuTaskMetrics.scala`).

Levels ESSENTIAL < MODERATE < DEBUG; an exec creates metrics at declared levels and the
session's metrics level filters which are live (dead metrics are no-ops). Timers are
wall-clock nanoseconds (reference createNanoTimingMetric)."""

from __future__ import annotations

import collections
import contextlib
import threading
import time
from typing import Any, Dict, List, Tuple

ESSENTIAL = 0
MODERATE = 1
DEBUG = 2

_LEVELS = {"ESSENTIAL": ESSENTIAL, "MODERATE": MODERATE, "DEBUG": DEBUG}

# canonical metric names, mirroring GpuMetric companion object constants
NUM_OUTPUT_ROWS = "numOutputRows"
NUM_OUTPUT_BATCHES = "numOutputBatches"
NUM_INPUT_ROWS = "numInputRows"
NUM_INPUT_BATCHES = "numInputBatches"
OP_TIME = "opTime"
COLLECT_TIME = "collectTime"
CONCAT_TIME = "concatTime"
SORT_TIME = "sortTime"
AGG_TIME = "computeAggTime"
# segmented reductions of the executed aggregate kernels, by how they lowered:
# a prefix sum and a difference at the group ends, or a scatter
NUM_PREFIX_REDUCTIONS = "numPrefixSumReductions"
NUM_SCATTER_REDUCTIONS = "numScatterReductions"
# row-aligned arrays the executed kernels' row gathers moved, by route: as
# rows of a stacked word matrix, or one array a gather (ops.rowops.gather_vecs)
NUM_PACKED_GATHER_ARRAYS = "numPackedGatherArrays"
NUM_SINGLE_GATHER_ARRAYS = "numSingleGatherArrays"
JOIN_TIME = "joinTime"
FILTER_TIME = "filterTime"
BUILD_TIME = "buildTime"
STREAM_TIME = "streamTime"
SPILL_TIME = "spillTime"
READ_TIME = "readTime"
WRITE_TIME = "writeTime"
PARTITION_TIME = "partitionTime"
WINDOW_TIME = "windowTime"
NUM_WINDOW_PARTITIONS = "numWindowPartitions"
NUM_DECIMAL_WINDOW_AGGS = "numDecimalWindowAggs"
NUM_DECIMAL_DIVIDES = "numDecimalDivides"
BROADCAST_TIME = "broadcastTime"
DATA_SIZE = "dataSize"
SEMAPHORE_WAIT_TIME = "semaphoreWaitTime"
PEAK_DEVICE_MEMORY = "peakDevMemory"
NUM_PARTITIONS = "numPartitions"


class Metric:
    __slots__ = ("name", "level", "_value", "_lock", "live")

    def __init__(self, name: str, level: int = MODERATE, live: bool = True):
        self.name = name
        self.level = level
        self.live = live
        self._value = 0
        self._lock = threading.Lock()

    @property
    def value(self) -> int:
        return self._value

    def add(self, v: int) -> None:
        if self.live:
            with self._lock:
                self._value += int(v)

    def set(self, v: int) -> None:
        if self.live:
            with self._lock:
                self._value = int(v)

    def set_max(self, v: int) -> None:
        if self.live:
            with self._lock:
                self._value = max(self._value, int(v))

    @contextlib.contextmanager
    def timed(self):
        if not self.live:
            yield
            return
        t0 = time.monotonic_ns()
        try:
            yield
        finally:
            self.add(time.monotonic_ns() - t0)


NOOP = Metric("noop", live=False)

_END = object()


def timed_pulls(it, metric: "Metric"):
    """Drive iterator `it`, charging the wait for each item to `metric` —
    the shared shape of stream-side timing (join probe streamTime,
    exchange read side): upstream wait is the consumer's cost, distinct
    from the consumer's own kernel timers."""
    while True:
        with metric.timed():
            item = next(it, _END)
        if item is _END:
            return
        yield item


class MetricsSet:
    """Per-exec metric dictionary filtered by the session metrics level.
    Thread-safe: exchange and shuffle paths create/snapshot against the
    same set from worker threads."""

    def __init__(self, session_level: str = "MODERATE"):
        self._max_level = _LEVELS[session_level]
        self._metrics: Dict[str, Metric] = {}
        self._lock = threading.Lock()

    def create(self, name: str, level: int = MODERATE) -> Metric:
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = Metric(name, level, live=(level <= self._max_level))
                self._metrics[name] = m
            return m

    def __getitem__(self, name: str) -> Metric:
        with self._lock:
            return self._metrics.get(name, NOOP)

    def snapshot(self) -> Dict[str, int]:
        with self._lock:
            return {k: m.value for k, m in self._metrics.items() if m.live}


class TaskMetrics:
    """Task-level accumulators (reference GpuTaskMetrics): spill/retry wall time and
    counts, aggregated across operators within a task."""

    _tls = threading.local()

    def __init__(self):
        self.semaphore_wait_ns = 0
        self.retry_count = 0
        self.split_retry_count = 0
        self.retry_block_ns = 0
        # per-attempt OOM-retry backoff (ms), in attempt order: a retry STORM
        # (many attempts, growing waits) is visible at a glance instead of
        # hiding inside one aggregate nanosecond counter
        self.retry_backoff_ms: list = []
        self.spill_to_host_ns = 0
        self.spill_to_disk_ns = 0
        self.read_spill_ns = 0
        # shuffle fetch robustness counters (retry/refetch/failover path)
        self.shuffle_retry_count = 0
        self.shuffle_refetch_count = 0
        self.shuffle_failover_count = 0
        # shuffle data-plane accounting: serialized bytes written to the
        # block store, frame bytes read back, and wall ns spent waiting on
        # block fetch/read (the data-movement signal Theseus-class engines
        # show dominates accelerator SQL)
        self.shuffle_bytes_written = 0
        self.shuffle_bytes_read = 0
        self.shuffle_fetch_wait_ns = 0
        # compile-service counters (compile/service.py): real XLA compiles
        # this task triggered, wall ns inside them, program-cache traffic,
        # persistent-tier loads, and degraded direct-jit fallbacks
        self.compile_count = 0
        self.compile_ns = 0
        self.compile_cache_hits = 0
        self.compile_cache_misses = 0
        self.compile_persist_hits = 0
        self.compile_fallbacks = 0
        # pipelined-execution counters (exec/base.py PrefetchIterator +
        # io/parquet_device.py fused multi-chunk decode): prefetch threads
        # spawned for this task, batches they parked, wall ns the CONSUMER
        # spent stalled on an empty prefetch queue (the pipeline's residual
        # serial cost), and the scan decode's dispatch accounting — device
        # dispatch events (program executions + H2D transfer calls) vs
        # row-group chunks vs produced batches, the amortization signal
        self.prefetch_threads = 0
        self.prefetch_batches = 0
        self.prefetch_stall_ns = 0
        self.scan_dispatches = 0
        self.scan_chunks = 0
        self.scan_batches = 0
        # scan pushdown (plan/scan_pushdown.py): rows the pushed predicate
        # removed before downstream operators, ROW DATA bytes the decode
        # actually materialized on device (with pushdown, survivors only —
        # the machine-independent proxy for the decode-path win), and
        # whole row groups skipped via footer stats before any page read
        self.scan_rows_pruned = 0
        self.scan_bytes_materialized = 0
        self.scan_rowgroups_pruned = 0
        # units of an ORC scan that the host's reader (pyarrow) decoded in
        # the chip's place (io/scanbase._orc_batches): a file read whole, a
        # stripe that fell back, a column of a stripe the footer routed to
        # the host. The fallback is the default and never silent.
        self.scan_host_decoded = 0
        # CPU-fallback stage re-runs: a device-side CpuFallbackRequired
        # (e.g. require_flat_strings on a >headWidth key) silently re-ran
        # the whole stage on the host engine this many times
        self.cpu_fallback_reruns = 0
        # result/fragment-cache counters (rescache/): hits and misses this
        # task saw across the seams, entries it stored, wall ns it spent
        # parked behind another query computing the same fingerprint
        # (single-flight dedup), and faults degraded to recompute
        self.rescache_hits = 0
        self.rescache_misses = 0
        self.rescache_stores = 0
        self.rescache_singleflight_wait_ns = 0
        self.rescache_degraded = 0
        # hits answered from the persistent result tier (restart warm path)
        self.rescache_persist_hits = 0
        # query-scheduler counters (sched/): wall ns queued for admission,
        # grants, load-shed rejections, cooperative cancellations and
        # deadline expiries observed by this task, and the deepest
        # admission queue it saw on arrival (overload signal)
        self.sched_queue_wait_ns = 0
        self.sched_admissions = 0
        self.sched_rejected = 0
        self.sched_cancelled = 0
        self.sched_deadline_exceeded = 0
        self.sched_queue_depth = 0
        # sharded mesh execution (mesh/ + exec/exchange.py ICI path):
        # collectives executed, bytes moved over the interconnect (the
        # post-exchange slot plane — the data that would otherwise ride
        # the host shuffle), scan shards produced across mesh positions,
        # and exchanges that degraded to the host data plane on a
        # shard-count vs partition-count mismatch; mesh_out_devices is the
        # sorted ids of the devices that held the collectives' output shards
        # (code that never met more than one real chip may put them all on
        # the first)
        self.mesh_exchanges = 0
        self.mesh_ici_bytes = 0
        self.mesh_shards = 0
        self.mesh_degraded = 0
        self.mesh_out_devices: List[int] = []
        # whole-stage fusion (plan/fusion.py + exec/fused.py):
        # device_dispatches counts every host-side program launch at the
        # compile-service execute seam (cached-executable calls AND the
        # direct/fallback jit paths; nested in-trace calls are free and
        # not counted) — dispatches-per-query is THE fusion gate metric.
        # fused_stages/fused_ops count fused stages executed and the
        # member operators they absorbed.
        self.device_dispatches = 0
        self.fused_stages = 0
        self.fused_ops = 0
        # the host's time by stage, charged by the stage spans themselves
        # (`utils/tracing.STAGES`, always on): the chunk or stripe walk
        # (`scan.walk`), signatures and packing (`scan.pack`), the scan's
        # batched transfers (`scan.h2d`), the compile service's digest,
        # lookup and launch (`dispatch.<op>`; a launch that compiles holds
        # its compile), the plan rewrite (`plan.rewrite`), the sink's
        # device -> host copies (`sink.d2h`, its own row-count sync
        # included) and the host's reads of a device count (`sync.row_count`
        # in `ColumnarBatch.row_count()`, `sync.join_size` in a join: the
        # host blocks until every program queued before it has run).
        # Thread-seconds: the threads that do one query's work share its
        # instance (a prefetch producer, the ORC walkers), so stages that
        # run side by side add up and may exceed the query's wall time.
        self.scan_walk_ns = 0
        self.scan_pack_ns = 0
        self.dispatch_ns = 0
        self.plan_rewrite_ns = 0
        self.host_sync_ns = 0
        self.host_sync_count = 0
        self.h2d_ns = 0
        self.h2d_bytes = 0
        self.d2h_ns = 0

    _add_mu = threading.Lock()

    def add(self, field: str, delta: int) -> None:
        """`field += delta`, safe when the threads that share this instance
        charge it at once."""
        with TaskMetrics._add_mu:
            setattr(self, field, getattr(self, field) + delta)

    @classmethod
    def get(cls) -> "TaskMetrics":
        tm = getattr(cls._tls, "metrics", None)
        if tm is None:
            tm = TaskMetrics()
            cls._tls.metrics = tm
        return tm

    @classmethod
    def reset(cls) -> None:
        cls._tls.metrics = TaskMetrics()

    @classmethod
    @contextlib.contextmanager
    def adopted(cls, tm: "TaskMetrics"):
        """This thread counts into `tm` for the scope and into its own
        instance again after it: a pooled thread that lives longer than the
        query it works for."""
        prev = getattr(cls._tls, "metrics", None)
        cls._tls.metrics = tm
        try:
            yield tm
        finally:
            cls._tls.metrics = prev

    def explain_string(self) -> str:
        """Retry/recovery summary for explain output; empty when the task
        saw no memory-pressure retries and no shuffle recovery events."""
        parts = []
        if self.retry_count or self.split_retry_count:
            backoffs = ", ".join(f"{b:.1f}" for b in self.retry_backoff_ms)
            parts.append(
                f"oomRetries={self.retry_count} "
                f"splitRetries={self.split_retry_count} "
                f"retryBlockedMs={self.retry_block_ns / 1e6:.1f} "
                f"backoffsMs=[{backoffs}]")
        if self.shuffle_retry_count or self.shuffle_refetch_count or \
                self.shuffle_failover_count:
            parts.append(
                f"shuffleFetchRetries={self.shuffle_retry_count} "
                f"shuffleRefetches={self.shuffle_refetch_count} "
                f"shuffleFailovers={self.shuffle_failover_count}")
        if self.shuffle_bytes_written or self.shuffle_bytes_read:
            parts.append(
                f"shuffleBytesWritten={self.shuffle_bytes_written} "
                f"shuffleBytesRead={self.shuffle_bytes_read} "
                f"shuffleFetchWaitMs={self.shuffle_fetch_wait_ns / 1e6:.1f}")
        if self.compile_count or self.compile_cache_hits or \
                self.compile_cache_misses or self.compile_persist_hits or \
                self.compile_fallbacks:
            parts.append(
                f"compiles={self.compile_count} "
                f"compileMs={self.compile_ns / 1e6:.1f} "
                f"compileCacheHits={self.compile_cache_hits} "
                f"compileCacheMisses={self.compile_cache_misses} "
                f"compilePersistHits={self.compile_persist_hits} "
                f"compileFallbacks={self.compile_fallbacks}")
        if self.prefetch_threads or self.prefetch_batches:
            parts.append(
                f"prefetchThreads={self.prefetch_threads} "
                f"prefetchBatches={self.prefetch_batches} "
                f"prefetchStallMs={self.prefetch_stall_ns / 1e6:.1f}")
        if self.scan_dispatches:
            per_batch = self.scan_dispatches / max(self.scan_batches, 1)
            parts.append(
                f"scanDispatches={self.scan_dispatches} "
                f"scanChunks={self.scan_chunks} "
                f"scanBatches={self.scan_batches} "
                f"dispatchesPerScanBatch={per_batch:.2f}")
        if self.scan_rows_pruned or self.scan_rowgroups_pruned:
            parts.append(
                f"scanRowsPruned={self.scan_rows_pruned} "
                f"scanRowGroupsPruned={self.scan_rowgroups_pruned} "
                f"scanBytesMaterialized={self.scan_bytes_materialized}")
        if self.cpu_fallback_reruns:
            parts.append(f"cpuFallbackReruns={self.cpu_fallback_reruns}")
        if self.rescache_hits or self.rescache_misses or \
                self.rescache_stores or self.rescache_degraded:
            parts.append(
                f"rescacheHits={self.rescache_hits} "
                f"rescacheMisses={self.rescache_misses} "
                f"rescacheStores={self.rescache_stores} "
                f"rescacheSingleFlightWaitMs="
                f"{self.rescache_singleflight_wait_ns / 1e6:.1f} "
                f"rescacheDegraded={self.rescache_degraded}"
                + (f" rescachePersistHits={self.rescache_persist_hits}"
                   if self.rescache_persist_hits else ""))
        if self.sched_admissions or self.sched_rejected or \
                self.sched_cancelled or self.sched_deadline_exceeded:
            parts.append(
                f"schedAdmissions={self.sched_admissions} "
                f"schedQueueWaitMs={self.sched_queue_wait_ns / 1e6:.1f} "
                f"schedQueueDepth={self.sched_queue_depth} "
                f"schedRejected={self.sched_rejected} "
                f"schedCancelled={self.sched_cancelled} "
                f"schedDeadlineExceeded={self.sched_deadline_exceeded}")
        if self.mesh_exchanges or self.mesh_shards or self.mesh_degraded:
            parts.append(
                f"meshExchanges={self.mesh_exchanges} "
                f"meshShards={self.mesh_shards} "
                f"meshIciBytes={self.mesh_ici_bytes}"
                + (f" meshDegraded={self.mesh_degraded}"
                   if self.mesh_degraded else ""))
        if self.device_dispatches or self.fused_stages:
            parts.append(
                f"deviceDispatches={self.device_dispatches}"
                + (f" fusedStages={self.fused_stages} "
                   f"fusedOps={self.fused_ops}"
                   if self.fused_stages else ""))
        return "" if not parts else "TaskMetrics: " + "; ".join(parts)


# The last queries this process finished, with profiling off too: the "why
# was that one slow" record (did it wait on the chip or on the host?).
# Process-wide because whoever asks (an operator's console, a benchmark
# reader) holds no session; bounded, so a serving process keeps 64.
RECENT_QUERIES = 64
_recent: "collections.deque" = collections.deque(maxlen=RECENT_QUERIES)
_recent_mu = threading.Lock()


def note_query(wall_s: float, label: str, task_metrics: Dict[str, Any]) -> None:
    with _recent_mu:
        _recent.append((wall_s, label, task_metrics))


def recent_queries() -> List[Tuple[float, str, Dict[str, Any]]]:
    """`(wall_s, label, task_metrics_dict)` of the last `RECENT_QUERIES`
    finished device queries of this process, oldest first."""
    with _recent_mu:
        return list(_recent)
