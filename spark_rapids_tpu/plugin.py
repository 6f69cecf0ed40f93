"""Session bootstrap — driver/executor lifecycle (reference `Plugin.scala`:
RapidsDriverPlugin `:222` / RapidsExecutorPlugin `:275`; config fixup `:110-161`;
device init via `GpuDeviceManager.initializeGpuAndMemory`).

`TpuSession` is the user entry point: holds the conf, owns device initialization
(memory budget, admission semaphore), builds CPU plans via the DataFrame frontend,
rewrites them through `plan.Overrides`, and executes. `explain` mirrors
spark.rapids.sql.explain output."""

from __future__ import annotations

from typing import Dict, Optional

from .config import TpuConf
from .plan.nodes import PhysicalPlan
from .plan.overrides import Overrides


class TpuSession:
    _active: Optional["TpuSession"] = None

    def __init__(self, conf: Optional[Dict] = None):
        self.conf = TpuConf(conf)
        self._device_initialized = False
        self._last_profile = None
        self._last_stats = None
        self._last_plan = None
        TpuSession._active = self

    # ------------------------------------------------------------------ device
    def initialize_device(self) -> None:
        """Executor-side init (GpuDeviceManager.initializeGpuAndMemory analog):
        binds the device, sizes the memory budget, creates the semaphore, and
        installs any configured fault-injection rules (faults.py)."""
        if self._device_initialized:
            return
        from . import faults
        faults.install_from_conf(self.conf)
        from . import telemetry
        # live telemetry (registry/exporter/flight recorder): a no-op
        # unless spark.rapids.tpu.telemetry.enabled — the off path must
        # create no state and spawn no threads (telemetry_matrix.sh gate)
        telemetry.configure(self.conf)
        from . import rescache
        # result & fragment cache: a no-op unless
        # spark.rapids.tpu.rescache.enabled — the off path must create no
        # state and spawn no threads (rescache_matrix.sh gate)
        rescache.configure(self.conf)
        from . import stats
        # runtime statistics (cardinality history + optimizer feedback):
        # a no-op unless spark.rapids.tpu.stats.enabled — the off path
        # must create no state, spawn no threads, and leave planning
        # byte-identical (stats_matrix.sh gate)
        stats.configure(self.conf)
        from . import live
        # live query introspection (in-flight registry + slow-query
        # watchdog): a no-op unless spark.rapids.tpu.live.enabled — the
        # off path must create no state, spawn no threads, and keep
        # results byte-identical (liveview_matrix.sh gate)
        live.configure(self.conf)
        from .compile import CompileService
        # compile service first: warmup precompiles on a background thread
        # while the rest of init (and the first plan rewrite) proceeds
        CompileService.get().configure(self.conf)
        from .memory.device_manager import DeviceManager
        DeviceManager.initialize(self.conf)
        self._device_initialized = True

    # ----------------------------------------------------------------- queries
    def from_arrow(self, table, label: str = "memory"):
        from .frontend import DataFrame
        from .plan.nodes import CpuScanExec
        return DataFrame(self, CpuScanExec(table, label))

    def range(self, start: int, end: Optional[int] = None, step: int = 1):
        from .frontend import DataFrame
        from .plan.nodes import CpuRangeExec
        if end is None:
            start, end = 0, start
        return DataFrame(self, CpuRangeExec(start, end, step))

    def read_parquet(self, *paths, **options):
        from .frontend import DataFrame
        from .io.parquet import parquet_scan_plan
        return DataFrame(self, parquet_scan_plan(list(paths), self.conf,
                                                 **options))

    def read_csv(self, *paths, **options):
        from .frontend import DataFrame
        from .io.csv import csv_scan_plan
        return DataFrame(self, csv_scan_plan(list(paths), self.conf, **options))

    def read_json(self, *paths, **options):
        from .frontend import DataFrame
        from .io.json_ import json_scan_plan
        return DataFrame(self, json_scan_plan(list(paths), self.conf,
                                              **options))

    def read_orc(self, *paths, **options):
        from .frontend import DataFrame
        from .io.orc import orc_scan_plan
        return DataFrame(self, orc_scan_plan(list(paths), self.conf, **options))

    def read_avro(self, *paths, **options):
        from .frontend import DataFrame
        from .io.avro import avro_scan_plan
        return DataFrame(self, avro_scan_plan(list(paths), self.conf,
                                              **options))

    def read_hive_text(self, *paths, **options):
        """Hive delimited-text table scan (requires schema=Schema(...))."""
        from .frontend import DataFrame
        from .io.hive_text import hive_text_scan_plan
        return DataFrame(self, hive_text_scan_plan(list(paths), self.conf,
                                                   **options))

    def read_iceberg(self, path, columns=None, snapshot_id=None,
                     as_of_timestamp_ms=None):
        from .datasources.iceberg import IcebergTable
        if not self.conf.get("spark.rapids.sql.format.iceberg.enabled"):
            raise ValueError("iceberg scan disabled by conf "
                             "(spark.rapids.sql.format.iceberg.enabled)")
        return IcebergTable(self, path).to_df(
            columns, snapshot_id, as_of_timestamp_ms)

    # --------------------------------------------------------------- execution
    def _sched_context(self):
        """Build a QueryContext from the session conf, or None when no
        sched key opts in — the None path is byte-for-byte the
        pre-scheduler engine (no activation, no cancellation checks, no
        admission release at query end)."""
        c = self.conf
        deadline_ms = c.get("spark.rapids.tpu.sched.deadlineMs")
        tenant = c.get("spark.rapids.tpu.sched.tenant") or "default"
        priority = c.get("spark.rapids.tpu.sched.priority")
        if not (c.get("spark.rapids.tpu.sched.enabled") or deadline_ms > 0
                or tenant != "default" or priority != 0):
            return None
        from .sched import QueryContext
        return QueryContext(tenant=tenant, priority=priority,
                            deadline_s=deadline_ms / 1000.0
                            if deadline_ms > 0 else None)

    def execute_plan(self, plan: PhysicalPlan,
                     use_device: Optional[bool] = None, sched_ctx=None,
                     trace_id: Optional[str] = None):
        """Run a CPU plan through the override rewrite and execute; returns a
        pyarrow Table. `sched_ctx` (sched.QueryContext) carries an explicit
        tenant/priority/deadline/cancel-token for this query (the device
        service builds one per run_plan); otherwise the session conf's
        spark.rapids.tpu.sched.* keys apply. `trace_id` (or the context's)
        correlates this query's profile/flight records with the peer
        process that submitted it; absent, one is minted at query start."""
        import pyarrow as pa
        from .cpu.hostbatch import host_batch_to_arrow
        from .exec.base import TpuExec
        from .exec.transitions import device_batch_to_host
        from .plan.nodes import _concat_host
        from .utils import spans

        from .plan import nodes as _nodes
        _nodes.set_ansi_mode(self.conf.is_ansi)
        enabled = self.conf.is_sql_enabled if use_device is None else use_device

        def run():
            if enabled and self.conf.get("spark.rapids.sql.adaptive.enabled"):
                from .plan.adaptive import adaptive_execute
                return adaptive_execute(self, plan, use_device=enabled)
            return self._execute_rewritten(plan, enabled)

        ctx = sched_ctx or self._sched_context()
        tid = trace_id or (ctx.trace_id if ctx is not None else None) \
            or spans.new_trace_id()
        if ctx is not None and ctx.trace_id is None:
            ctx.trace_id = tid
        with spans.trace_scope(tid):
            if ctx is None:
                return run()
            from .sched import activate
            with activate(ctx):
                return run()

    def _execute_rewritten(self, plan: PhysicalPlan,
                           use_device: Optional[bool] = None):
        """Plan-rewrite + run one (sub)plan; returns a pyarrow Table. The
        adaptive loop calls this once per query stage.

        Whole-query rescache seam: with the result cache on, a plan whose
        fingerprint matches a stored result is answered from the host
        copy IMMEDIATELY — before the override rewrite and before any
        admission (a hit consumes no semaphore token and no scheduler
        grant; TaskMetrics.sched_admissions stays 0). Concurrent
        identical queries single-flight behind the first execution."""
        enabled = self.conf.is_sql_enabled if use_device is None else \
            use_device
        qh = None
        if enabled:
            self.initialize_device()
            from .utils.metrics import TaskMetrics
            # fresh counters per query, BEFORE the cache lookup: a hit's
            # rescache counters (and its zero admissions) must describe
            # THIS query, not whatever ran last on this thread
            TaskMetrics.reset()
            from . import rescache
            if rescache.is_enabled():
                qh = rescache.begin_query(plan, self.conf)
                if qh is not None and qh.hit is not None:
                    return qh.hit
        try:
            out = self._run_rewritten(plan, enabled)
        except BaseException:
            if qh is not None:
                # release the single-flight marker so a parked identical
                # query takes over as the next owner
                qh.abort()
            raise
        if qh is not None:
            qh.complete(out)
        return out

    def _run_rewritten(self, plan: PhysicalPlan, enabled: bool):
        """`_run_plan`, and for a device query one entry in the process's
        ring of recent queries (`recent_queries()`), whatever its end."""
        if not enabled:
            return self._run_plan(plan, enabled)
        import time
        from .utils import metrics, spans
        t0 = time.perf_counter()
        try:
            return self._run_plan(plan, enabled)
        finally:
            tm = metrics.TaskMetrics.get()
            metrics.note_query(
                time.perf_counter() - t0,
                getattr(self._last_plan, "name", plan.name),
                spans.task_metrics_dict(tm))
            if tm.compile_count:
                # its programs are here to stay: keep the cyclic collector
                # from walking them in the middle of every thirtieth query
                from . import settle_host_heap
                settle_host_heap()

    @staticmethod
    def recent_queries():
        """`(wall_s, label, task_metrics)` of the device queries this
        PROCESS finished last, oldest first, at most
        `utils.metrics.RECENT_QUERIES` (64) of them: with profiling off too,
        what says whether a slow query waited on the chip (`host_sync_ns`,
        `d2h_ns`) or on the host, and in which stage (every field of
        `utils.tracing.STAGES`). A longer window is covered in its last 64
        only."""
        from .utils import metrics
        return metrics.recent_queries()

    def _run_plan(self, plan: PhysicalPlan, enabled: bool):
        from .cpu.hostbatch import host_batch_to_arrow
        from .exec.base import TpuExec
        from .exec.transitions import device_batch_to_host
        from .plan.nodes import _concat_host
        from .utils import spans

        if enabled:
            self.initialize_device()
            ov = Overrides(self.conf)
            with spans.span("plan.rewrite"):
                result = ov.apply(plan)
            self._last_plan = result
            self._last_explain = ov.explain_string()
            if self._last_explain:
                print(self._last_explain)
        else:
            result = plan

        if isinstance(result, TpuExec):
            from . import telemetry
            from .errors import (CpuFallbackRequired, DeadlineExceededError,
                                 InjectedFault, QueryCancelledError,
                                 QueryRejectedError, RetryOOM,
                                 SplitAndRetryOOM)
            from .utils.metrics import TaskMetrics
            # per-query counter reset happens in _execute_rewritten, BEFORE
            # the rescache lookup (a TpuExec result implies enabled, which
            # implies the reset ran) — the explain line below still reports
            # only THIS query's retries
            from .memory.budget import MemoryBudget
            MemoryBudget.get().reset_peak()
            # query profiler: activated by the event-log dir or the
            # profile switch; otherwise zero overhead (spans stay no-ops)
            log_dir = self.conf.get("spark.rapids.tpu.metrics.eventLog.dir")
            prof = None
            if log_dir or self.conf.get(
                    "spark.rapids.tpu.metrics.profile.enabled"):
                prof = spans.begin_profile(label=result.name)
                prof.attach_plan(result)
            # live telemetry: per-op MetricsSet baselines (throughput
            # deltas fed at query end) + the query flight event; both are
            # one branch when telemetry is off
            op_baselines = telemetry.ops_baseline(result)
            # runtime statistics: per-operator MetricsSet baselines for
            # the estimate-vs-actual ledger (one bool when stats is off)
            from . import stats as _stats
            st_obs = _stats.begin(result, self.conf)
            # live query introspection: register this query as in-flight
            # (one bool when live is off) — the registry samples the same
            # MetricsSet baselines at each pull for progress/ETA
            from . import live as _lq
            lv = _lq.query_begin(result, self.conf, label=result.name)
            q_status = "ok"
            telemetry.flight("query", "begin", label=result.name)
            try:
                from .sched import context as _qctx
                if _qctx.current() is not None:
                    # scheduled queries pass the admission door at query
                    # start (the scheduler must own every path onto the
                    # device — lazy spillable acquisition alone would let
                    # small queries skip admission entirely); shed/
                    # deadline/cancel raise typed BEFORE any device work.
                    from .memory.semaphore import TpuSemaphore
                    TpuSemaphore.get().acquire_if_necessary()
                # pipelined execution: the plan's stream produces on a
                # bounded prefetch thread while this thread converts
                # results D2H — device compute overlaps the host sink.
                # Roots that already prefetch their own output (file
                # scans, coalesce inputs) are not wrapped again: a second
                # seam on the same edge re-parks every batch for no
                # added overlap.
                from .exec.base import maybe_prefetch
                from .exec.coalesce import TpuCoalesceBatchesExec
                from .io.scanbase import TpuFileScanExec
                stream = result.execute()
                if not isinstance(result, (TpuFileScanExec,
                                           TpuCoalesceBatchesExec)):
                    stream = maybe_prefetch(stream, self.conf,
                                            name="sink")
                host_batches = [device_batch_to_host(b)
                                for b in stream]
                # retry-storm visibility: when explain is on, surface the
                # task's OOM-retry/shuffle-recovery counters (incl. the
                # per-attempt backoff schedule) next to the plan output
                if self.conf.explain != "NONE":
                    tm_line = TaskMetrics.get().explain_string()
                    if tm_line:
                        print(tm_line)
            except CpuFallbackRequired:
                # the device layout cannot represent this data (e.g. a
                # string wider than the byte-matrix limit surfacing
                # mid-stream): re-run the stage on the host engine — plan
                # sources are idempotent, so a from-scratch CPU pass is
                # safe (the reference's whole-plan willNotWork fallback,
                # applied at runtime). Counted: these re-runs are silent
                # by design, so TaskMetrics must make them visible
                # (explain_string + profile report).
                TaskMetrics.get().cpu_fallback_reruns += 1
                telemetry.inc("tpu_cpu_fallback_reruns_total")
                telemetry.flight("query", "cpu_fallback_rerun",
                                 label=result.name)
                # the device stream aborted mid-way: its MetricsSet
                # deltas are PARTIAL actuals — recording them would
                # poison the cardinality history even though the query
                # (via the CPU rerun) ends "ok". Drop the observer.
                st_obs = None
                try:
                    host_batches = list(plan.execute_cpu())
                except BaseException:
                    # the rescue re-run ITSELF failed: exceptions inside
                    # this handler bypass the status-stamping clauses
                    # below, so stamp here or the finally records "ok"
                    q_status = "error"
                    raise
                if self.conf.explain != "NONE":
                    tm_line = TaskMetrics.get().explain_string()
                    if tm_line:
                        print(tm_line)
            except (QueryCancelledError, DeadlineExceededError,
                    QueryRejectedError) as e:
                # scheduler-typed unwinds: stamp the profile record so a
                # killed/shed query's event log says so, then re-raise —
                # the finally below still reclaims admission and closes
                # the profile
                q_status = (
                    "cancelled" if isinstance(e, QueryCancelledError)
                    else "deadline"
                    if isinstance(e, DeadlineExceededError)
                    else "rejected")
                if prof is not None:
                    prof.status = q_status
                # flight-recorder evidence for queries that died without a
                # profile: deadline/cancel dump immediately; rejections
                # count toward the storm detector (count_rejection at the
                # admission queue), not one dump per shed query
                if q_status in ("cancelled", "deadline"):
                    telemetry.incident(q_status, label=result.name,
                                       message=str(e))
                raise
            except (RetryOOM, SplitAndRetryOOM) as e:
                # a memory-pressure error ESCAPING the query is terminal:
                # every retry/split/spill rung below it gave up. This is
                # the black-box moment — the profile never lands because
                # the query never finishes
                q_status = "oom"
                telemetry.incident("terminal_oom", label=result.name,
                                   error=type(e).__name__, message=str(e))
                raise
            except InjectedFault as e:
                q_status = "error"
                telemetry.incident("injected_fault", label=result.name,
                                   message=str(e))
                raise
            except BaseException:
                q_status = "error"
                raise
            finally:
                from .sched import context as _qctx
                if _qctx.current() is not None:
                    # scheduled queries hold admission per QUERY, not per
                    # thread-lifetime: release every reentrant hold so the
                    # next queued query (possibly on another thread) gets
                    # the token. Unscheduled queries keep the historical
                    # per-thread hold semantics untouched.
                    from .memory.semaphore import TpuSemaphore
                    TpuSemaphore.get().complete_task()
                telemetry.ops_finish(op_baselines)
                telemetry.inc("tpu_queries_total", status=q_status)
                telemetry.flight("query", "end", label=result.name,
                                 status=q_status)
                # retire the live-registry entry (records this query's
                # wall time into the stats history on ok — the runtime
                # expectation the next run's ETA and the watchdog need)
                _lq.query_end(lv, q_status)
                # runtime statistics: derive actuals, record history,
                # keep the ledger for explain_analyze (discarded on a
                # non-ok unwind — partial actuals must not poison)
                summary = _stats.finish(st_obs, q_status)
                if summary is not None:
                    self._last_stats = summary
                if prof is not None:
                    # adaptive decisions ride the query record so the
                    # report tool and explain_profile surface them —
                    # `_adaptive_active` is scoped to the adaptive loop,
                    # so a later non-adaptive query cannot pick up a
                    # stale session-attribute log
                    prof.adaptive = list(
                        getattr(self, "_adaptive_active", None) or ())
                    spans.end_profile(prof)
                    prof.finish(TaskMetrics.get())
                    self._last_profile = prof
                    if log_dir:
                        try:
                            spans.write_event_log(
                                prof, log_dir,
                                max_bytes=self.conf.get(
                                    "spark.rapids.tpu.metrics.eventLog."
                                    "maxBytes"),
                                max_files=self.conf.get(
                                    "spark.rapids.tpu.metrics.eventLog."
                                    "maxFiles"))
                            if summary is not None:
                                _stats.write_records(
                                    summary, log_dir, prof.query_id,
                                    prof.trace_id,
                                    max_bytes=self.conf.get(
                                        "spark.rapids.tpu.metrics."
                                        "eventLog.maxBytes"),
                                    max_files=self.conf.get(
                                        "spark.rapids.tpu.metrics."
                                        "eventLog.maxFiles"))
                        except OSError as e:
                            # the profiler must never fail the query
                            import warnings
                            warnings.warn(
                                f"profile event log write failed: {e}",
                                RuntimeWarning, stacklevel=2)
        else:
            host_batches = list(result.execute_cpu())
        # host batches -> the answer's rows (decimals become Python objects
        # one by one here), the chip idle
        with spans.span("sink.rows"):
            merged = _concat_host(host_batches, plan.output)
            return host_batch_to_arrow(merged)

    def execute_plan_device_batches(self, plan: PhysicalPlan):
        """Run a plan fully on the TPU engine and return the DEVICE batches
        (no D2H) — the ColumnarRdd/ML-handoff path (`ColumnarRdd.scala:42`).
        Raises if any plan section fell back to CPU (a host hop would defeat
        the zero-copy contract)."""
        from .exec.base import TpuExec
        from .exec.transitions import TpuFromCpuExec
        self.initialize_device()
        ov = Overrides(self.conf)
        saved = self.conf.get("spark.rapids.sql.explain")
        self.conf.set("spark.rapids.sql.explain", "ALL")
        try:
            result = ov.apply(plan)
        finally:
            self.conf.set("spark.rapids.sql.explain", saved)

        def has_cpu_section(node) -> bool:
            if isinstance(node, TpuFromCpuExec):
                return True
            return any(has_cpu_section(c) for c in node.children)

        if not isinstance(result, TpuExec) or has_cpu_section(result):
            from .errors import PlanNotFullyOnDevice
            raise PlanNotFullyOnDevice(
                "plan did not fully convert to TPU execution; zero-copy "
                "device handoff needs an all-device plan:\n"
                + ov.explain_string())
        return list(result.execute())

    def from_device_batch(self, batch):
        """Wrap an existing device batch as a DataFrame source (inverse
        ML handoff; see udf/columnar_rdd.py)."""
        from .exec.transitions import device_batch_to_host
        from .cpu.hostbatch import host_batch_to_arrow
        return self.from_arrow(
            host_batch_to_arrow(device_batch_to_host(batch)),
            label="device-handoff")

    @property
    def last_plan(self):
        """The rewritten plan the most recent device query executed, with
        its operators' live metrics (None before the first query)."""
        return self._last_plan

    @property
    def last_profile(self):
        """The QueryProfile of the most recent profiled query (None when
        profiling was off). See utils/spans.py."""
        return self._last_profile

    def explain_profile(self) -> str:
        """Render the last profiled query's operator tree with its live
        metrics inline (the SQL-UI metrics analogue). Empty string when no
        profiled query has run — turn on
        spark.rapids.tpu.metrics.profile.enabled or set
        spark.rapids.tpu.metrics.eventLog.dir first."""
        if self._last_profile is None:
            return ""
        return self._last_profile.explain_profile()

    @property
    def last_stats(self):
        """The RuntimeStats ledger of the most recent stats-observed
        query (None when spark.rapids.tpu.stats.enabled is off)."""
        return self._last_stats

    def explain_analyze(self, plan: Optional[PhysicalPlan] = None,
                        use_device: Optional[bool] = None) -> str:
        """Execute `plan` (when given) and render the estimate-vs-actual
        operator tree: per-operator CBO estimate, observed rows, q-error,
        plus observed selectivity/fan-out/skew — the EXPLAIN ANALYZE
        analogue over the runtime-statistics ledger. With no plan, the
        last stats-observed query renders. Requires
        spark.rapids.tpu.stats.enabled (collection is the ledger)."""
        if plan is not None:
            if not self.conf.get("spark.rapids.tpu.stats.enabled"):
                raise ValueError(
                    "explain_analyze needs spark.rapids.tpu.stats.enabled"
                    "=true (runtime-statistics collection is the ledger "
                    "it renders)")
            # a run whose observer silently failed must render nothing,
            # not the PREVIOUS query's ledger labeled as this plan's
            self._last_stats = None
            self.execute_plan(plan, use_device=use_device)
        if self._last_stats is None:
            return ""
        return self._last_stats.render()

    def explain_plan(self, plan: PhysicalPlan) -> str:
        ov = Overrides(self.conf)
        saved = self.conf.get("spark.rapids.sql.explain")
        self.conf.set("spark.rapids.sql.explain", "ALL")
        try:
            ov.apply(plan)
        finally:
            self.conf.set("spark.rapids.sql.explain", saved)
        return ov.explain_string()

    @classmethod
    def active(cls) -> "TpuSession":
        if cls._active is None:
            cls._active = TpuSession()
        return cls._active
