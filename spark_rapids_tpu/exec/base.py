"""TPU exec operator base (reference `GpuExec.scala:179-315`: metrics plumbing +
internalDoExecuteColumnar).

Execution model: an exec produces an iterator of device `ColumnarBatch`es per
partition. Device compute happens in jit-compiled kernels created once per exec
instance; XLA's compile cache makes repeat shapes cheap, and the bucketed padding
keeps the shape set small. Host code between kernels handles iteration, coalescing
decisions, and spill/retry control flow — mirroring how reference operators are host
Scala around cudf kernel launches."""

from __future__ import annotations

import queue as _queue
import threading
from typing import Iterator, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..columnar.batch import ColumnarBatch, Schema
from ..config import TpuConf, get_default_conf
from ..expr.base import EvalContext, Vec
from ..sched import context as _qctx
from .. import live as _live
from ..utils import metrics as M
from ..utils import spans

_END = object()


class TpuExec:
    def __init__(self, children: Sequence["TpuExec"], conf: TpuConf = None):
        self.children = list(children)
        self.conf = conf or get_default_conf()
        self.metrics = M.MetricsSet(self.conf.get("spark.rapids.sql.metrics.level"))
        self.num_output_rows = self.metrics.create(M.NUM_OUTPUT_ROWS, M.ESSENTIAL)
        self.num_output_batches = self.metrics.create(M.NUM_OUTPUT_BATCHES,
                                                      M.MODERATE)
        self.op_time = self.metrics.create(M.OP_TIME, M.MODERATE)
        # task-metric slices attributed to this operator's pulls (inclusive
        # of children, like every wall-time tree metric): spill wall time,
        # admission wait, and the device-budget watermark observed while
        # this operator was producing (GpuTaskMetrics surfaced per-op)
        self.spill_time = self.metrics.create(M.SPILL_TIME, M.DEBUG)
        self.semaphore_wait_time = self.metrics.create(
            M.SEMAPHORE_WAIT_TIME, M.DEBUG)
        self.peak_dev_memory = self.metrics.create(
            M.PEAK_DEVICE_MEMORY, M.DEBUG)

    @property
    def output(self) -> Schema:
        raise NotImplementedError

    @property
    def name(self) -> str:
        return type(self).__name__

    def execute(self) -> Iterator[ColumnarBatch]:
        """Produce output batches (single-partition stream; exchange operators
        introduce partitioned streams). Each PULL runs under its own
        `op.<ExecName>` span, not the stream: a stream-long span stays open
        while the consumer works, a pull is open only while this operator
        (or a child it pulls) does. Pulls nest, so the innermost open one
        is the operator at work."""
        prof = spans.current_profile()
        if prof is not None or self.spill_time.live \
                or self.semaphore_wait_time.live or self.peak_dev_memory.live:
            yield from self._instrumented_execute(prof)
            return
        # disabled path: one global read + three attribute reads per
        # operator per query, an annotation per pull (an atomic load with no
        # profiler session) — no span objects, no per-batch syncs.
        # Each pull is a cancellation point (sched.context.checkpoint
        # is one module-global read with no context active): a
        # cancelled/deadline-exceeded query unwinds between batches
        # with the typed error, through every operator's finally.
        name = "op." + self.name
        it = self.do_execute()
        while True:
            with spans.span(name, kind=spans.KIND_OPERATOR):
                batch = next(it, _END)
            if batch is _END:
                return
            _qctx.checkpoint()
            # live-introspection observer (one module-global bool
            # when off): stamps this op as the query's current
            # position — rows/batches come from the MetricsSet
            _live.note_pull(self)
            yield batch

    def _instrumented_execute(self, prof) -> Iterator[ColumnarBatch]:
        """Profiling/DEBUG-metrics path: an operator span per pull, and
        per-pull deltas of the task-level accumulators are charged to this
        operator (inclusive of children, like opTime)."""
        from ..memory.budget import MemoryBudget
        tm = M.TaskMetrics.get()
        budget = MemoryBudget.get()
        name = "op." + self.name
        attrs = {} if prof is None else {"op_id": prof.ensure_operator(self)}
        it = self.do_execute()
        while True:
            _qctx.checkpoint()  # per-pull cancellation point
            spill0 = (tm.spill_to_host_ns + tm.spill_to_disk_ns
                      + tm.read_spill_ns)
            sem0 = tm.semaphore_wait_ns
            with spans.span(name, kind=spans.KIND_OPERATOR, **attrs) as sp:
                try:
                    batch = next(it, _END)
                finally:
                    self.spill_time.add(tm.spill_to_host_ns
                                        + tm.spill_to_disk_ns
                                        + tm.read_spill_ns - spill0)
                    self.semaphore_wait_time.add(
                        tm.semaphore_wait_ns - sem0)
                    # the watermark, not used: a transient reserve/release
                    # inside the pull must still register (the budget
                    # resets its peak at query start)
                    self.peak_dev_memory.set_max(budget.peak_used)
                if batch is _END:
                    return
                if prof is not None:  # attr computation syncs; skip if off
                    sp.inc(batches=1, rows=int(batch.row_count()),
                           bytes=int(batch.device_memory_size()))
            _live.note_pull(self)
            yield batch

    def do_execute(self) -> Iterator[ColumnarBatch]:
        raise NotImplementedError

    def _count_output(self, batch: ColumnarBatch) -> ColumnarBatch:
        self.num_output_batches.add(1)
        return batch

    def tree_string(self, indent: int = 0) -> str:
        s = "  " * indent + f"{self.name}{self._arg_string()}\n"
        for c in self.children:
            s += c.tree_string(indent + 1)
        return s

    def _arg_string(self) -> str:
        return ""


# ----------------------------------------------------------------------------
# Pipelined execution: bounded async batch prefetch
# ----------------------------------------------------------------------------

# process-wide count of prefetch threads ever spawned — the pipeline-off CI
# gate asserts this stays ZERO when spark.rapids.tpu.pipeline.enabled=false
# (scripts/pipeline_matrix.sh)
PREFETCH_THREADS_STARTED = 0

_PREFETCH_END = object()


class _PrefetchError:
    __slots__ = ("exc",)

    def __init__(self, exc: BaseException):
        self.exc = exc


class PrefetchIterator:
    """Bounded-depth async prefetch of an upstream batch iterator.

    A background thread pulls upstream batches while the consumer computes,
    overlapping host-side work (parquet page prep, shuffle fetch, coalesce
    input, D2H of the previous result) with device execution. Discipline:

      * bounded depth: the queue holds at most `depth` parked batches, so
        the producer can never run away from the consumer;
      * budget-visible parking: each prefetched batch parks as a
        SpillableColumnarBatch (MemoryBudget.note_parked accounting), so a
        tight budget spills prefetched batches to host instead of letting
        the pipeline inflate device residency invisibly;
      * semaphore order: the prefetch is part of the CONSUMER's task and
        adds no admission traffic of its own — the producer ADOPTS the
        task's standing (adopt_task_hold; with concurrentGpuTasks=1 a
        producer-owned permit would deadlock against the task thread's,
        and a dead producer could leak one), and the consumer
        materializes parked batches without re-admission (they are the
        task's own in-flight stream, held live on device by the serial
        path with no admission either);
      * typed error propagation: any producer-side exception (including
        CpuFallbackRequired and injected faults) crosses the queue and
        re-raises in the consumer with its original type; the producer
        thread always terminates — a consumer that stops early (LIMIT,
        downstream error) drains and closes parked batches and joins the
        thread, so no deadlock and no leaked catalog handles;
      * shared task accounting: the producer adopts the spawning thread's
        TaskMetrics instance, so spill/retry/compile counters keep landing
        in the query's task like the serial path.

    The faults.PREFETCH injection point fires once per upstream pull on
    the producer thread (scripts/pipeline_matrix.sh drives it)."""

    _PUT_POLL_S = 0.02

    def __init__(self, inner: Iterator[ColumnarBatch], depth: int,
                 name: str = "prefetch"):
        from ..memory.semaphore import TpuSemaphore
        from ..utils.metrics import TaskMetrics
        global PREFETCH_THREADS_STARTED
        self._inner = inner
        self._name = name
        self._q: _queue.Queue = _queue.Queue(maxsize=max(depth, 1))
        self._stop = threading.Event()
        self._tm = TaskMetrics.get()  # the consumer's (task's) metrics
        self._sem = TpuSemaphore.get()
        self._ctx = _qctx.current()  # the consumer's query context
        self._live_entry = _live.current_entry()  # the consumer's live view
        self._tm.prefetch_threads += 1
        PREFETCH_THREADS_STARTED += 1
        from .. import telemetry
        telemetry.register_prefetch(self)  # queue-occupancy gauge
        self._thread = threading.Thread(
            target=self._produce, name=f"srtpu-{name}", daemon=True)
        self._thread.start()

    # -- producer thread ---------------------------------------------------
    def _produce(self) -> None:
        from .. import faults
        from ..memory.spillable import SpillableColumnarBatch
        from ..utils.metrics import TaskMetrics
        TaskMetrics._tls.metrics = self._tm  # share the task's counters
        self._sem.adopt_task_hold()  # ride the task's admission permit
        _qctx.adopt(self._ctx)  # observe the consumer's cancel token
        _live.adopt_entry(self._live_entry)  # pulls stay query-attributed
        try:
            while not self._stop.is_set():
                _qctx.checkpoint()  # typed cancel crosses the queue below
                with spans.span("pipeline:prefetch",
                                kind=spans.KIND_IO) as sp:
                    faults.fire(faults.PREFETCH)
                    batch = next(self._inner, _PREFETCH_END)
                    if batch is _PREFETCH_END:
                        break
                    sp.inc(batches=1, rows=int(batch.row_count()))
                item = SpillableColumnarBatch(batch)
                del batch
                self._tm.prefetch_batches += 1
                from .. import telemetry
                telemetry.inc("tpu_prefetch_batches_total")
                if not self._put(item):
                    item.close()  # consumer is gone
                    return
            self._put(_PREFETCH_END)
        except BaseException as e:  # noqa: BLE001 — crosses the queue
            self._put(_PrefetchError(e))
        finally:
            # unwind this thread's reentrant counts; the adopted (task's)
            # permit is NOT released — it belongs to the consumer
            self._sem.complete_task()

    def _put(self, item) -> bool:
        """Queue put that gives up when the consumer has stopped (a full
        queue with a dead consumer must not wedge the thread)."""
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=self._PUT_POLL_S)
                return True
            except _queue.Full:
                continue
        return False

    # -- consumer side -----------------------------------------------------
    def _get(self):
        """Dequeue with a producer-liveness guard: a producer that died
        without its terminal token (a bug, every exit path posts one) must
        surface as a loud error, never an indefinite consumer block."""
        while True:
            try:
                return self._q.get(timeout=1.0)
            except _queue.Empty:
                if not self._thread.is_alive():
                    try:  # terminal token may have landed just before death
                        return self._q.get_nowait()
                    except _queue.Empty:
                        raise RuntimeError(
                            f"prefetch producer '{self._name}' died "
                            "without a result") from None

    def __iter__(self) -> Iterator[ColumnarBatch]:
        import time
        try:
            while True:
                _qctx.checkpoint()  # consumer-side cancellation point
                t0 = time.monotonic_ns()
                item = self._get()
                self._tm.prefetch_stall_ns += time.monotonic_ns() - t0
                if item is _PREFETCH_END:
                    return
                if isinstance(item, _PrefetchError):
                    raise item.exc
                try:
                    # no re-admission: this batch is the task's own
                    # in-flight stream (see SpillableColumnarBatch.get_batch)
                    batch = item.get_batch(acquire_semaphore=False)
                finally:
                    item.close()
                yield batch
        finally:
            self.close()

    def close(self) -> None:
        """Stop the producer, drain + close parked batches, join. Drains
        once more AFTER the join: a producer blocked in put() when the
        first drain freed queue space lands its item between drain and
        exit — that straggler must be closed too, not leaked."""
        self._stop.set()
        for _ in range(2):
            while True:
                try:
                    item = self._q.get_nowait()
                except _queue.Empty:
                    break
                if item is not _PREFETCH_END and \
                        not isinstance(item, _PrefetchError):
                    item.close()
            self._thread.join(timeout=10.0)


def start_prefetch(inner: Iterator[ColumnarBatch],
                   conf: Optional[TpuConf],
                   name: str = "prefetch") -> Optional[PrefetchIterator]:
    """A PrefetchIterator that pulls `inner` from this call on, before
    anyone iterates it; None when pipelined execution is off. Whoever holds
    it iterates it to its end or calls `close()`: a producer nobody drains
    parks at the queue's depth for good."""
    conf = conf or get_default_conf()
    if not conf.get("spark.rapids.tpu.pipeline.enabled"):
        return None
    depth = conf.get("spark.rapids.tpu.pipeline.prefetch.depth")
    if depth < 1:
        return None
    return PrefetchIterator(inner, depth, name)


def maybe_prefetch(inner: Iterator[ColumnarBatch],
                   conf: Optional[TpuConf],
                   name: str = "prefetch") -> Iterator[ColumnarBatch]:
    """Wrap `inner` in a PrefetchIterator when pipelined execution is on;
    pipeline-off returns `inner` UNCHANGED (the exact serial path, zero
    threads spawned)."""
    ahead = start_prefetch(inner, conf, name)
    return inner if ahead is None else iter(ahead)


class StaticExpr:
    """Identity-keyed wrapper so a bound Expression can ride as a jit static
    argument: Expression overloads __eq__/__gt__/… to BUILD expression trees,
    which breaks jax's static-argument hashing. `err_msgs` is the host-side
    message box paired with the traced ANSI error flags a kernel evaluating
    this expression returns (see kernel_errors)."""
    __slots__ = ("expr", "err_msgs")

    def __init__(self, expr):
        self.expr = expr
        self.err_msgs: list = []

    def __hash__(self):
        return id(self.expr)

    def __eq__(self, other):
        return isinstance(other, StaticExpr) and other.expr is self.expr


class UnaryTpuExec(TpuExec):
    @property
    def child(self) -> TpuExec:
        return self.children[0]

    @property
    def output(self) -> Schema:
        return self.child.output


def device_ctx(batch: ColumnarBatch, conf: TpuConf = None) -> EvalContext:
    ansi = (conf or get_default_conf()).is_ansi
    # errors is ALWAYS a list on device: raising can't happen mid-kernel, so
    # both ANSI violations and unconditional signals (raise_error/
    # assert_true) ride the same traced-flag channel; empty list = free
    return EvalContext(jnp, row_mask=batch.row_mask(), ansi=ansi, conf=conf,
                       errors=[])


def kernel_errors(ctx: EvalContext, msgs_box: list):
    """Extract the traced ANSI error flags from a kernel's context for return;
    messages land in msgs_box (stable across retraces: they depend only on the
    expression tree)."""
    entries = ctx.errors or ()
    msgs_box[:] = [m for _, m in entries]
    return tuple(f for f, _ in entries)


def kernel_notes(ctx: EvalContext, msgs_box: list, *counts):
    """`kernel_errors`, then counts taken while the kernel was traced, left at
    the box's tail: the compile service restores the box when the program
    comes from a cache, so the exec can add them to its metrics for every
    executed batch."""
    flags = kernel_errors(ctx, msgs_box)
    msgs_box.extend(counts)
    return flags


class GatherCounts:
    """An exec's two metrics of its kernels' row gathers, fed from the
    `(packed, alone)` of a `GatherTally` that `kernel_notes` left last in
    the kernel's box."""

    def __init__(self, metrics: M.MetricsSet):
        self.packed = metrics.create(M.NUM_PACKED_GATHER_ARRAYS, M.MODERATE)
        self.alone = metrics.create(M.NUM_SINGLE_GATHER_ARRAYS, M.MODERATE)

    def add(self, msgs_box: list) -> None:
        self.packed.add(msgs_box[-2])
        self.alone.add(msgs_box[-1])


def raise_kernel_errors(flags, msgs_box: list) -> None:
    """Host-side: raise the first ANSI violation a kernel reported."""
    for f, m in zip(flags, msgs_box):
        if bool(f):
            from ..errors import AnsiViolation
            raise AnsiViolation(m)


def raise_eager_errors(ctx: EvalContext) -> None:
    """After un-jitted (eager) device evaluation the error flags in
    ctx.errors are concrete — check and raise them in place."""
    for f, m in ctx.errors or ():
        if bool(f):
            from ..errors import AnsiViolation
            raise AnsiViolation(m)


def batch_vecs(batch: ColumnarBatch) -> List[Vec]:
    return [Vec.from_column(c) for c in batch.columns]


def vecs_to_batch(schema: Schema, vecs: Sequence[Vec], num_rows) -> ColumnarBatch:
    return ColumnarBatch(schema, tuple(v.to_column() for v in vecs),
                         jnp.asarray(num_rows, dtype=jnp.int32))
