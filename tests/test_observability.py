"""Observability suite (ISSUE-4, marker `observability`): span tracer +
QueryProfile registry, metrics-level filtering, thread-safe MetricsSet,
canonical-metric wiring (no orphan constants), trace_range exception
regression, event-log JSONL schema round-trip, the offline report tool,
parked-batch spill accounting, and the end-to-end profiled query.

scripts/profile_matrix.sh runs these standalone plus the bench-driven
emit/validate/disabled-path checks."""

import json
import os
import re
import threading
import time

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.utils import metrics as M
from spark_rapids_tpu.utils import spans
from spark_rapids_tpu.utils.metrics import MetricsSet, TaskMetrics
from spark_rapids_tpu.utils.spans import (QueryProfile, begin_profile,
                                          end_profile, span, validate_record,
                                          write_event_log)

pytestmark = pytest.mark.observability

SRC_ROOT = os.path.join(os.path.dirname(__file__), "..", "spark_rapids_tpu")


@pytest.fixture(autouse=True)
def _no_leaked_profile():
    """Every test must leave the module-global profile slot empty."""
    yield
    prof = spans.current_profile()
    if prof is not None:
        end_profile(prof)
    assert spans.current_profile() is None


# ---------------------------------------------------------------------------
# satellite: trace_range exception regression
# ---------------------------------------------------------------------------


class TestTraceRange:
    def test_metric_fed_when_region_raises(self):
        from spark_rapids_tpu.utils.tracing import trace_range
        m = M.Metric("t", M.ESSENTIAL, live=True)
        with pytest.raises(ValueError):
            with trace_range("failing", metric=m):
                time.sleep(0.005)
                raise ValueError("boom")
        # pre-fix the elapsed time was lost entirely on exception
        assert m.value >= 4_000_000  # >= 4ms in ns

    def test_metric_fed_on_success(self):
        from spark_rapids_tpu.utils.tracing import trace_range
        m = M.Metric("t", M.ESSENTIAL, live=True)
        with trace_range("ok", metric=m):
            time.sleep(0.002)
        assert m.value > 0


# ---------------------------------------------------------------------------
# satellite: no orphan canonical metric constants
# ---------------------------------------------------------------------------


class TestNoOrphanConstants:
    def _canonical_names(self):
        return [k for k, v in vars(M).items()
                if k.isupper() and isinstance(v, str)
                and k not in ("ESSENTIAL", "MODERATE", "DEBUG")]

    def test_every_constant_created_by_an_operator(self):
        """Each canonical name in utils/metrics.py must be CREATED somewhere
        in the engine (`.create(M.<NAME>...)`) — a declared-but-dead metric
        constant is an observability lie."""
        sources = []
        for root, _dirs, files in os.walk(SRC_ROOT):
            for f in files:
                if f.endswith(".py") and not f.endswith("metrics.py"):
                    with open(os.path.join(root, f)) as fh:
                        sources.append(fh.read())
        blob = "\n".join(sources)
        orphans = [name for name in self._canonical_names()
                   if not re.search(r"create\(\s*M\.%s\b" % name, blob)]
        assert not orphans, f"declared-but-dead metric constants: {orphans}"

    def test_constants_are_unique(self):
        names = self._canonical_names()
        values = [getattr(M, n) for n in names]
        assert len(set(values)) == len(values)


# ---------------------------------------------------------------------------
# satellite: MetricsSet thread safety + level filtering
# ---------------------------------------------------------------------------


class TestMetricsSet:
    def test_create_snapshot_concurrent(self):
        ms = MetricsSet("MODERATE")
        errors = []

        def worker(tid):
            try:
                for i in range(300):
                    m = ms.create(f"m{i % 20}", M.MODERATE)
                    m.add(1)
                    ms.snapshot()
                    _ = ms[f"m{(i + tid) % 20}"]
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=worker, args=(t,))
                   for t in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors
        snap = ms.snapshot()
        assert len(snap) == 20
        assert sum(snap.values()) == 8 * 300

    def test_create_same_name_returns_same_metric(self):
        ms = MetricsSet("MODERATE")
        assert ms.create("x") is ms.create("x")

    def test_level_filtering_live_and_noop(self):
        # ESSENTIAL session: only ESSENTIAL metrics are live
        ms = MetricsSet("ESSENTIAL")
        ess = ms.create("rows", M.ESSENTIAL)
        mod = ms.create("opTime", M.MODERATE)
        dbg = ms.create("peak", M.DEBUG)
        for m in (ess, mod, dbg):
            m.add(7)
            m.set_max(99)
        assert ess.live and ess.value == 99
        assert not mod.live and mod.value == 0  # dead metric is a no-op
        assert not dbg.live and dbg.value == 0
        assert set(ms.snapshot()) == {"rows"}

        # DEBUG session: everything is live
        ms2 = MetricsSet("DEBUG")
        assert ms2.create("a", M.ESSENTIAL).live
        assert ms2.create("b", M.MODERATE).live
        assert ms2.create("c", M.DEBUG).live

    def test_missing_metric_is_noop(self):
        ms = MetricsSet("MODERATE")
        ms["never-created"].add(5)  # must not raise
        assert ms.snapshot() == {}


# ---------------------------------------------------------------------------
# satellite: TaskMetrics.explain_string composition
# ---------------------------------------------------------------------------


class TestTaskMetricsExplain:
    def test_empty_when_clean(self):
        assert TaskMetrics().explain_string() == ""

    def test_all_parts_compose(self):
        tm = TaskMetrics()
        tm.retry_count = 2
        tm.split_retry_count = 1
        tm.retry_block_ns = 3_000_000
        tm.retry_backoff_ms = [2.0, 4.0]
        tm.shuffle_retry_count = 3
        tm.shuffle_bytes_written = 1000
        tm.shuffle_bytes_read = 900
        tm.shuffle_fetch_wait_ns = 2_000_000
        tm.compile_count = 4
        tm.compile_ns = 5_000_000
        s = tm.explain_string()
        assert s.startswith("TaskMetrics: ")
        assert "oomRetries=2" in s and "splitRetries=1" in s
        assert "backoffsMs=[2.0, 4.0]" in s
        assert "shuffleFetchRetries=3" in s
        assert "shuffleBytesWritten=1000" in s
        assert "shuffleBytesRead=900" in s
        assert "shuffleFetchWaitMs=2.0" in s
        assert "compiles=4" in s and "compileMs=5.0" in s
        # the four families are ';'-separated in declaration order
        assert s.count(";") == 3

    def test_thread_local_isolation(self):
        TaskMetrics.reset()
        TaskMetrics.get().retry_count = 5
        seen = []

        def other():
            seen.append(TaskMetrics.get().retry_count)

        t = threading.Thread(target=other)
        t.start()
        t.join()
        assert seen == [0]
        TaskMetrics.reset()


# ---------------------------------------------------------------------------
# tentpole: span tracer + QueryProfile
# ---------------------------------------------------------------------------


class TestSpans:
    def test_disabled_path_yields_shared_noop(self):
        # no profile: the scope is the profiler annotation alone, and what
        # the `with` hands out is the one shared no-op, never a Span
        assert spans.current_profile() is None
        with span("anything", kind="spill") as s1, span("else") as s2:
            assert s1 is spans.NOOP_SPAN and s2 is spans.NOOP_SPAN
            s1.inc(bytes=5)  # must be a no-op, not an error

    def test_nesting_via_thread_stack(self):
        prof = begin_profile("q")
        try:
            with span("outer", kind="phase") as outer:
                with span("inner", kind="spill", bytes=10) as inner:
                    time.sleep(0.001)
                assert inner.parent_id == outer.span_id
            assert outer.parent_id == QueryProfile.ROOT_SPAN_ID
        finally:
            end_profile(prof)
        prof.finish()
        named = {s.name: s for s in prof.spans}
        assert named["inner"].dur_ns > 0
        assert named["inner"].attrs["bytes"] == 10
        assert named["outer"].dur_ns >= named["inner"].dur_ns

    def test_worker_thread_spans_parent_to_root(self):
        prof = begin_profile("q")
        try:
            def worker():
                with span("w", kind="shuffle"):
                    pass
            t = threading.Thread(target=worker)
            t.start()
            t.join()
        finally:
            end_profile(prof)
        prof.finish()
        w = [s for s in prof.spans if s.name == "w"]
        assert len(w) == 1 and w[0].parent_id == QueryProfile.ROOT_SPAN_ID

    def test_suppressed_thread_records_nothing(self):
        # the AOT warmup thread suppresses itself so overlapping background
        # compiles never pollute the active query's profile
        prof = begin_profile("q")
        try:
            def worker():
                spans.suppress_in_thread()
                with span("warmup-compile", kind="compile"):
                    pass
            t = threading.Thread(target=worker)
            t.start()
            t.join()
        finally:
            end_profile(prof)
        prof.finish()
        assert prof.spans == []

    def test_span_exception_still_recorded(self):
        prof = begin_profile("q")
        try:
            with pytest.raises(RuntimeError):
                with span("failing", kind="compile"):
                    raise RuntimeError("x")
        finally:
            end_profile(prof)
        prof.finish()
        assert [s.name for s in prof.spans] == ["failing"]

    def test_finish_is_idempotent_and_snapshots_deltas(self):
        class FakeExec:
            def __init__(self, name):
                self._name = name
                self.metrics = MetricsSet("MODERATE")
                self.children = []

            @property
            def name(self):
                return self._name

            def _arg_string(self):
                return "[x]"

        parent, child = FakeExec("Parent"), FakeExec("Child")
        parent.children = [child]
        m = child.metrics.create("opTime", M.MODERATE)
        m.add(100)  # pre-query value: must NOT appear in the profile
        prof = QueryProfile("q")
        prof.attach_plan(parent)
        m.add(42)
        prof.finish()
        prof.finish()  # idempotent
        table = {t["name"]: t for t in prof.operator_table()}
        assert table["Child"]["values"]["opTime"] == 42
        assert table["Child"]["parent_id"] == table["Parent"]["op_id"]
        assert table["Parent"]["args"] == "[x]"
        assert "Child: opTime=" in prof.explain_profile().replace("[x]", "")


# ---------------------------------------------------------------------------
# tentpole: event-log JSONL schema round-trip
# ---------------------------------------------------------------------------


class TestEventLogRoundTrip:
    def _make_profile(self):
        prof = begin_profile("roundtrip")
        try:
            with span("spill:to_host", kind="spill", bytes=2048):
                pass
            with span("compile:exec.sort", kind="compile", op="exec.sort"):
                pass
        finally:
            end_profile(prof)
        tm = TaskMetrics()
        tm.retry_count = 1
        tm.retry_backoff_ms = [2.0]
        tm.shuffle_bytes_read = 77
        prof.finish(tm)
        return prof

    def test_records_validate_and_survive_json(self, tmp_path):
        prof = self._make_profile()
        path = write_event_log(prof, str(tmp_path))
        assert os.path.basename(path).startswith("events-")
        lines = open(path).read().splitlines()
        assert len(lines) == len(prof.to_records())
        for line in lines:
            rec = json.loads(line)
            assert validate_record(rec) == [], rec
        types = [json.loads(l)["type"] for l in lines]
        assert types.count("query") == 1
        assert types.count("span") == 3  # root + 2 phases
        qrec = json.loads(lines[0])
        assert qrec["v"] == spans.SCHEMA_VERSION
        assert qrec["task_metrics"]["shuffle_bytes_read"] == 77

    def test_append_only_across_queries(self, tmp_path):
        p1 = write_event_log(self._make_profile(), str(tmp_path))
        n1 = len(open(p1).read().splitlines())
        p2 = write_event_log(self._make_profile(), str(tmp_path))
        assert p1 == p2  # same per-process file, appended
        assert len(open(p2).read().splitlines()) == 2 * n1

    def test_validate_rejects_bad_records(self):
        assert validate_record({"v": 99, "type": "query"})
        assert validate_record({"v": 1, "type": "nope"})
        assert validate_record([1, 2, 3])
        errs = validate_record({"v": 1, "type": "span", "query_id": "a",
                               "span_id": "NOT_INT", "name": "n",
                               "kind": "martian", "start_ns": 0,
                               "dur_ns": 0, "attrs": {}})
        assert any("span_id" in e for e in errs)
        assert any("kind" in e for e in errs)


# ---------------------------------------------------------------------------
# tentpole: offline report tool on a synthetic log
# ---------------------------------------------------------------------------


def _synthetic_records(query_id, label, slow_op="TpuSortExec",
                       retries=False):
    tmetrics = {"retry_count": 3, "split_retry_count": 1,
                "retry_block_ns": 12_000_000,
                "retry_backoff_ms": [2.0, 4.0, 8.0],
                "shuffle_retry_count": 2} if retries else {}
    return [
        {"v": 1, "type": "query", "query_id": query_id, "label": label,
         "wall_ns": 50_000_000, "task_metrics": tmetrics,
         "n_operators": 2, "n_spans": 3},
        {"v": 1, "type": "operator", "query_id": query_id, "op_id": 0,
         "parent_id": None, "name": slow_op, "args": "",
         "metrics": {"sortTime": 30_000_000, "numOutputRows": 100,
                     "numOutputBatches": 2}},
        {"v": 1, "type": "operator", "query_id": query_id, "op_id": 1,
         "parent_id": 0, "name": "TpuScanExec", "args": "",
         "metrics": {"readTime": 1_000_000, "numOutputRows": 100,
                     "numOutputBatches": 2}},
        {"v": 1, "type": "span", "query_id": query_id, "span_id": 0,
         "parent_id": None, "name": label, "kind": "query",
         "start_ns": 0, "dur_ns": 50_000_000, "attrs": {}},
        {"v": 1, "type": "span", "query_id": query_id, "span_id": 1,
         "parent_id": 0, "name": "compile:exec.sort", "kind": "compile",
         "start_ns": 0, "dur_ns": 20_000_000, "attrs": {}},
        {"v": 1, "type": "span", "query_id": query_id, "span_id": 2,
         "parent_id": 0, "name": "spill:to_host", "kind": "spill",
         "start_ns": 0, "dur_ns": 5_000_000, "attrs": {"bytes": 4096}},
    ]


class TestReportTool:
    def _write(self, tmp_path, records, name="events-1.jsonl"):
        p = tmp_path / name
        with open(p, "w") as f:
            for r in records:
                f.write(json.dumps(r) + "\n")
        return str(p)

    def test_report_on_synthetic_log(self, tmp_path, capsys):
        from spark_rapids_tpu.tools.profile_report import main
        recs = _synthetic_records("q-1", "sortq", retries=True) + \
            _synthetic_records("q-2", "aggq", slow_op="TpuHashAggregateExec")
        self._write(tmp_path, recs)
        assert main([str(tmp_path), "--validate"]) == 0
        out = capsys.readouterr().out
        # top operators, slowest first
        assert out.index("TpuSortExec") < out.index("TpuScanExec")
        # breakdown has the compile/spill rows with the span totals
        assert "compile" in out and "20.0" in out
        assert "spill" in out and "5.0" in out and "4096" in out
        # retry storm surfaced with the backoff schedule
        assert "OOM retries=3" in out and "[2.0, 4.0, 8.0]" in out
        assert "shuffle fetch retries=2" in out
        # two queries -> comparison table
        assert "per-query comparison" in out
        assert "q-1" in out and "q-2" in out

    def test_validate_fails_on_corrupt_record(self, tmp_path, capsys):
        from spark_rapids_tpu.tools.profile_report import main
        recs = _synthetic_records("q-1", "sortq")
        recs[1] = {"v": 1, "type": "operator"}  # missing required fields
        self._write(tmp_path, recs)
        assert main([str(tmp_path), "--validate"]) == 1
        assert "INVALID" in capsys.readouterr().err

    def test_torn_tail_line_tolerated_without_validate(self, tmp_path,
                                                       capsys):
        from spark_rapids_tpu.tools.profile_report import main
        p = self._write(tmp_path, _synthetic_records("q-1", "sortq"))
        with open(p, "a") as f:
            f.write('{"v": 1, "type": "span", "trunc')  # crash mid-append
        assert main([str(tmp_path)]) == 0
        assert "TpuSortExec" in capsys.readouterr().out

    def test_json_model_output(self, tmp_path, capsys):
        from spark_rapids_tpu.tools.profile_report import main
        self._write(tmp_path, _synthetic_records("q-1", "sortq"))
        assert main([str(tmp_path), "--json"]) == 0
        model = json.loads(capsys.readouterr().out)
        assert model["queries"][0]["label"] == "sortq"
        assert model["queries"][0]["phases"]["spill"]["bytes"] == 4096


# ---------------------------------------------------------------------------
# engine wiring: parked-batch budget accounting + peak watermark
# ---------------------------------------------------------------------------


def _batch(n=2048):
    from spark_rapids_tpu.columnar import batch_from_arrow
    return batch_from_arrow(pa.table({
        "a": pa.array(np.arange(n, dtype=np.int64)),
        "b": pa.array(np.arange(n, dtype=np.float64)),
    }))


class TestParkedAccounting:
    def test_parking_over_budget_spills_older_runs(self):
        from spark_rapids_tpu.memory.budget import MemoryBudget
        from spark_rapids_tpu.memory.catalog import BufferCatalog
        from spark_rapids_tpu.memory.spillable import SpillableColumnarBatch
        BufferCatalog._instance = BufferCatalog(host_limit=1 << 30)
        b = _batch()
        size = b.device_memory_size()
        MemoryBudget.initialize(int(size * 1.5))
        TaskMetrics.reset()
        try:
            first = SpillableColumnarBatch(b)
            assert not first.spilled
            second = SpillableColumnarBatch(_batch())
            # parking the second run overflowed the budget: the OLDER run
            # spilled to host (bounded device residency), quietly — no
            # RetryOOM, no fault-injection allocation consumed
            assert first.spilled
            assert not second.spilled
            assert TaskMetrics.get().spill_to_host_ns > 0
            # re-acquiring unspills and rebalances the accounting
            got = first.get_batch()
            assert int(got.row_count()) == 2048
            first.close()
            second.close()
            assert MemoryBudget.get().used == 0
        finally:
            MemoryBudget.initialize(1 << 62)
            BufferCatalog._instance = None

    def test_note_parked_tracks_peak(self):
        from spark_rapids_tpu.memory.budget import MemoryBudget
        MemoryBudget.initialize(1 << 40)
        mb = MemoryBudget.get()
        mb.note_parked(1000)
        mb.note_parked(500)
        assert mb.peak_used >= 1500
        mb.release(1500)
        mb.reset_peak()
        assert mb.peak_used == mb.used
        MemoryBudget.initialize(1 << 62)


# ---------------------------------------------------------------------------
# end-to-end: profiled engine query -> tree + event log; disabled -> nothing
# ---------------------------------------------------------------------------


class TestProfiledQuery:
    def _table(self, n=512):
        rng = np.random.default_rng(3)
        return pa.table({
            "k": pa.array(rng.integers(0, 16, n)),
            "v": pa.array(rng.uniform(0.0, 1.0, n)),
        })

    def test_profile_collected_and_event_log_written(self, tmp_path):
        from spark_rapids_tpu.expr import col
        from spark_rapids_tpu.plugin import TpuSession
        log_dir = str(tmp_path / "events")
        s = TpuSession({"spark.rapids.sql.explain": "NONE",
                        "spark.rapids.sql.metrics.level": "DEBUG",
                        "spark.rapids.tpu.metrics.eventLog.dir": log_dir})
        out = s.from_arrow(self._table()).filter(col("v") > 0.5) \
            .sort("v").collect()
        assert out.num_rows > 0
        prof = s.last_profile
        assert prof is not None and prof.closed
        assert spans.current_profile() is None  # deactivated after the query
        text = s.explain_profile()
        assert "TpuSortExec" in text and "TpuFilterExec" in text
        assert "sortTime=" in text and "numOutputRows=" in text
        # the event log landed and every record validates
        files = [f for f in os.listdir(log_dir) if f.endswith(".jsonl")]
        assert len(files) == 1
        n_ops = n_queries = 0
        for line in open(os.path.join(log_dir, files[0])):
            rec = json.loads(line)
            assert validate_record(rec) == [], rec
            n_ops += rec["type"] == "operator"
            n_queries += rec["type"] == "query"
        assert n_queries == 1 and n_ops >= 3

    def test_in_memory_profile_without_event_log(self):
        from spark_rapids_tpu.expr import col
        from spark_rapids_tpu.plugin import TpuSession
        s = TpuSession({"spark.rapids.sql.explain": "NONE",
                        "spark.rapids.tpu.metrics.profile.enabled": True})
        s.from_arrow(self._table()).filter(col("v") > 0.5).collect()
        assert s.last_profile is not None
        assert "TpuFilterExec" in s.explain_profile()

    def test_disabled_run_collects_nothing(self, tmp_path):
        from spark_rapids_tpu.expr import col
        from spark_rapids_tpu.plugin import TpuSession
        s = TpuSession({"spark.rapids.sql.explain": "NONE"})
        out = s.from_arrow(self._table()).filter(col("v") > 0.5).collect()
        assert out.num_rows > 0
        assert s.last_profile is None
        assert s.explain_profile() == ""
        assert list(tmp_path.iterdir()) == []  # nothing written anywhere
