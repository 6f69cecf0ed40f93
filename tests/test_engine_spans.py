"""Engine spans on the profiler's clock, programs named by their op tag, and
the always-on counters at the same seams (ISSUE 27).

A small parquet join query runs under `jax.profiler`; the host events of the
trace are what a benchmark reader sees. On the CPU backend there is no device
plane, so a program's name is read off its lowered text."""

import glob
import signal
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu.columnar.batch import ColumnarBatch, batch_from_arrow
from spark_rapids_tpu.compile import service as svc_mod
from spark_rapids_tpu.compile.service import (CompileService, program_name,
                                              sjit)
from spark_rapids_tpu.expr import Sum, col, lit
from spark_rapids_tpu.plugin import TpuSession
from spark_rapids_tpu.utils import metrics, spans
from spark_rapids_tpu.utils.metrics import TaskMetrics
from spark_rapids_tpu.utils.tracing import SPAN_PREFIX, trace_range

pytestmark = pytest.mark.observability

LIMIT_S = 120
ROOT = "test.collect"
# every span name of ISSUE 27 section C that a warm device query opens
WARM_SPANS = {"plan.rewrite", "scan.walk", "scan.pack", "scan.h2d",
              "sync.row_count", "sink.d2h", "sink.rows"}
STAGES = ("trace", "lower", "backend")


@pytest.fixture(autouse=True)
def time_limit():
    """Each test's own limit: a hung query fails here, not at the suite's."""
    if threading.current_thread() is not threading.main_thread():
        yield
        return

    def expired(signum, frame):
        raise TimeoutError(f"test ran past {LIMIT_S} s")
    old = signal.signal(signal.SIGALRM, expired)
    signal.alarm(LIMIT_S)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


def _host_events(trace_dir):
    """(name, start_ns, duration_ns) of every host event of the one trace."""
    from jax.profiler import ProfileData
    found = glob.glob(str(trace_dir / "**" / "*.xplane.pb"), recursive=True)
    assert len(found) == 1, found
    return [(e.name, int(e.start_ns), int(e.duration_ns))
            for plane in ProfileData.from_file(found[0]).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]


def _traced(trace_dir, fn):
    """`fn()` under a profiler session without the Python tracer (its frames
    are what the engine spans are there to replace), inside one root
    annotation. Returns (result, wall seconds)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(ROOT):
            out = fn()
        wall = time.perf_counter() - t0
    finally:
        jax.profiler.stop_trace()
    return out, wall


@pytest.fixture(scope="module")
def star(tmp_path_factory):
    """A fact table of three row groups (so the scan takes its fused,
    packed path) and a filtered dimension, joined and summed: run cold and
    then warm, each under a profiler session of its own."""
    d = tmp_path_factory.mktemp("star")
    rng = np.random.default_rng(3)
    n = 6000
    pq.write_table(pa.table({
        "k": rng.integers(0, 50, n).astype(np.int64),
        "v": rng.integers(0, 100, n).astype(np.int64)}),
        str(d / "fact.parquet"), row_group_size=2000)
    pq.write_table(pa.table({
        "k": np.arange(50, dtype=np.int64),
        "name": [f"n{i:02d}" for i in range(50)]}), str(d / "dim.parquet"))
    CompileService.reset()
    session = TpuSession({"spark.rapids.sql.explain": "NONE"})
    session.initialize_device()
    fact = session.read_parquet(str(d / "fact.parquet"))
    dim = session.read_parquet(str(d / "dim.parquet"))
    df = (fact.join(dim.filter(col("k") < lit(40)), on="k")
          .group_by("name").agg(sv=Sum(col("v"))))
    runs = {}
    for name in ("cold", "warm"):
        out, wall = _traced(d / name, df.collect)
        runs[name] = {
            "rows": out.num_rows, "wall_s": wall,
            "events": _host_events(d / name),
            "tm": spans.task_metrics_dict(TaskMetrics.get())}
    # the ring is the process's: other tests' queries may have filled it
    runs["recent"] = TpuSession.recent_queries()[-2:]
    runs["compile"] = CompileService.get().stats.totals()
    runs["ops"] = sorted(CompileService.get().stats.per_op())
    yield runs
    CompileService.reset()


def _engine(events):
    """Engine spans of a trace by bare name -> [(start, duration)]."""
    out = {}
    for name, start, dur in events:
        if name.startswith(SPAN_PREFIX):
            out.setdefault(name[len(SPAN_PREFIX):], []).append((start, dur))
    return out


def _root(events):
    (start, dur), = [(s, d) for n, s, d in events if n == ROOT]
    return start, start + dur


class TestProgramNames:
    @pytest.fixture
    def service(self, tmp_path):
        CompileService.reset()
        service = CompileService.get()
        (tmp_path / "xprog").mkdir()
        service._dir = str(tmp_path / "xprog")
        yield service
        CompileService.reset()

    @pytest.mark.parametrize("path", ["compile", "persist", "direct"])
    def test_the_op_tag_reaches_the_lowered_module(self, service, path):
        op = "exec.test.add_one"
        kernel = sjit(lambda x: x + 1, op=op)
        x = jnp.arange(8)
        assert list(np.asarray(kernel(x))) == list(range(1, 9))
        want = f"jit_{program_name(op)}"
        if path == "compile":
            entry, = service._mem.values()
            assert entry.source == "compile"
            assert f"HloModule {want}," in entry.compiled.as_text()
        elif path == "persist":
            service.clear_memory()  # a restart: the entry comes back
            assert list(np.asarray(kernel(x))) == list(range(1, 9))
            entry, = service._mem.values()
            assert entry.source == "persist"
            assert f"module @{want} " in entry.compiled.lower(x).as_text()
        else:
            assert f"module @{want} " in kernel.direct.lower(x).as_text()
        # the shared kernel function keeps its own name
        assert kernel.fn.__name__ == "<lambda>"

    def test_a_digest_differs_when_the_name_does(self, service, monkeypatch):
        kernel = sjit(lambda x: x + 1, op="exec.test.add_one")
        leaves, treedef = jax.tree_util.tree_flatten((jnp.arange(8),))
        before = service._digest(kernel, (), leaves, treedef)
        assert before == service._digest(kernel, (), leaves, treedef)
        monkeypatch.setattr(svc_mod, "program_name",
                            lambda op: op.replace(".", "_"))
        assert service._digest(kernel, (), leaves, treedef) != before


class TestSpansReachTheTrace:
    def test_every_seam_of_a_warm_query_has_its_span(self, star):
        seen = _engine(star["warm"]["events"])
        assert WARM_SPANS <= set(seen), WARM_SPANS - set(seen)
        ops = {n for n in seen if n.startswith("op.")}
        assert {"op.TpuHashAggregateExec", "op.TpuBroadcastHashJoinExec",
                "op.TpuFileScanExec(parquet)"} <= ops
        # the spans that were there before reach the profiler as well
        assert "scan:parquet" in seen and "pipeline:prefetch" in seen

    def test_every_engine_span_lies_inside_the_root(self, star):
        for run in ("cold", "warm"):
            events = star[run]["events"]
            lo, hi = _root(events)
            for name, runs in _engine(events).items():
                for start, dur in runs:
                    assert lo <= start and start + dur <= hi, (run, name)

    def test_operator_spans_are_per_pull(self, star):
        """Summed, one operator's spans fit in the query: a stream-long
        annotation per operator would read depth x wall, and an operator
        that produced one batch was pulled twice (the batch, then the end)."""
        events = star["warm"]["events"]
        lo, hi = _root(events)
        seen = _engine(events)
        for name in (n for n in seen if n.startswith("op.")):
            assert sum(d for _, d in seen[name]) <= hi - lo, name
        assert len(seen["op.TpuHashAggregateExec"]) == 2

    def test_every_dispatch_is_annotated_under_its_op(self, star):
        seen = _engine(star["warm"]["events"])
        dispatched = {n[len("dispatch."):]: len(v) for n, v in seen.items()
                      if n.startswith("dispatch.")}
        assert sum(dispatched.values()) == \
            star["warm"]["tm"]["device_dispatches"]
        assert set(dispatched) <= set(star["ops"])
        assert "io.parquet.fused_multi_decode" in dispatched

    def test_a_compile_is_annotated_stage_by_stage(self, star):
        cold, warm = _engine(star["cold"]["events"]), \
            _engine(star["warm"]["events"])
        for op in star["ops"]:
            for stage in STAGES:
                assert len(cold[f"compile.{stage}.{op}"]) >= 1
            assert f"compile:{op}" in cold
        assert not [n for n in warm if n.startswith("compile")]

    def test_a_reload_is_annotated(self, tmp_path):
        CompileService.reset()
        try:
            service = CompileService.get()
            (tmp_path / "xprog").mkdir()
            service._dir = str(tmp_path / "xprog")
            kernel = sjit(lambda x: x * 2, op="exec.test.twice")
            kernel(jnp.arange(4))
            service.clear_memory()
            _traced(tmp_path / "trace", lambda: kernel(jnp.arange(4)))
            seen = _engine(_host_events(tmp_path / "trace"))
            assert len(seen["compile.reload.exec.test.twice"]) == 1
            assert len(seen["dispatch.exec.test.twice"]) == 1
        finally:
            CompileService.reset()


class TestCounters:
    def test_the_host_waits_are_counted_and_fit_in_the_query(self, star):
        for run in ("cold", "warm"):
            tm, wall_ns = star[run]["tm"], star[run]["wall_s"] * 1e9
            assert tm["host_sync_count"] > 0 and tm["host_sync_ns"] > 0
            assert tm["d2h_ns"] > 0
            assert tm["host_sync_ns"] + tm["d2h_ns"] <= wall_ns
            assert 0 < tm["h2d_ns"] <= wall_ns

    def test_h2d_bytes_are_the_packed_buffers(self, star):
        # three row groups in one packed buffer, the dimension's arrays in
        # a second transfer: the same bytes in both runs
        assert star["cold"]["tm"]["h2d_bytes"] == \
            star["warm"]["tm"]["h2d_bytes"] > 6000

    def test_the_compile_stages_fit_in_the_compile(self, star):
        c = star["compile"]
        assert c["compiles"] > 0
        for stage in STAGES:
            assert c[f"{stage}_ns"] > 0
        assert c["trace_ns"] + c["lower_ns"] + c["backend_ns"] \
            <= c["compile_ns"]
        assert star["cold"]["tm"]["compile_ns"] == c["compile_ns"]
        assert star["warm"]["tm"]["compile_count"] == 0

    def test_row_count_of_a_host_int_costs_and_records_nothing(self):
        TaskMetrics.reset()
        batch = batch_from_arrow(pa.table({"a": [1, 2, 3]}))
        on_host = ColumnarBatch(batch.schema, batch.columns, 3)
        assert on_host.row_count() == 3
        tm = TaskMetrics.get()
        assert tm.host_sync_count == 0 and tm.host_sync_ns == 0
        on_device = ColumnarBatch(batch.schema, batch.columns,
                                  jnp.asarray(3, jnp.int32))
        assert on_device.row_count() == 3
        assert tm.host_sync_count == 1 and tm.host_sync_ns > 0


class TestRecentQueries:
    def test_one_entry_per_collect(self, star):
        cold, warm = star["recent"]
        assert cold[1] == warm[1] == "TpuHashAggregateExec"
        assert cold[0] > warm[0] > 0
        assert warm[0] <= star["warm"]["wall_s"]
        assert warm[2]["host_sync_count"] == \
            star["warm"]["tm"]["host_sync_count"]
        assert cold[2]["compile_count"] > 0 == warm[2]["compile_count"]

    def test_the_ring_is_bounded(self):
        for i in range(metrics.RECENT_QUERIES + 10):
            metrics.note_query(float(i), "filler", {})
        recent = TpuSession.recent_queries()
        assert len(recent) == metrics.RECENT_QUERIES == 64
        assert recent[-1][0] == metrics.RECENT_QUERIES + 9
        assert recent[0][0] == 10.0

    def test_a_failed_query_is_recorded_too(self):
        session = TpuSession({"spark.rapids.sql.explain": "NONE"})
        session.initialize_device()
        metrics.note_query(0.0, "marker", {})

        class Boom(RuntimeError):
            pass

        def explode(plan, enabled):
            raise Boom()
        session._run_plan = explode
        plan = session.from_arrow(pa.table({"a": [1]})).plan
        with pytest.raises(Boom):
            session._run_rewritten(plan, True)
        marker, failed = TpuSession.recent_queries()[-2:]
        assert marker[1] == "marker" and failed[1] != "marker"
        assert failed[0] > 0


class TestThePrimitive:
    def test_no_profile_and_no_session_allocates_no_span(self, monkeypatch):
        made = []
        real = spans.Span.__init__

        def counting(self, *a, **kw):
            made.append(a)
            real(self, *a, **kw)
        monkeypatch.setattr(spans.Span, "__init__", counting)
        assert spans.current_profile() is None
        with spans.span("sync.row_count") as a, \
                spans.timed("sink.d2h", "d2h_ns") as b:
            assert a is spans.NOOP_SPAN and b is spans.NOOP_SPAN
        assert made == []
        prof = spans.begin_profile("q")
        try:
            with spans.span("scan.walk", kind=spans.KIND_IO):
                pass
        finally:
            spans.end_profile(prof)
        assert len(made) == 1

    def test_a_profiled_span_is_on_both_clocks(self, tmp_path):
        prof = spans.begin_profile("q")
        try:
            def region():
                with spans.span("scan.h2d", kind=spans.KIND_IO, bytes=7):
                    time.sleep(0.002)
            t0 = time.time_ns()
            _traced(tmp_path, region)
        finally:
            spans.end_profile(prof)
        prof.finish()
        sp, = prof.spans
        assert t0 <= sp.start_unix_ns <= time.time_ns()
        rec = [r for r in prof.to_records() if r.get("name") == "scan.h2d"]
        assert rec[0]["start_unix_ns"] == sp.start_unix_ns
        assert spans.validate_record(rec[0]) == []
        (start, dur), = _engine(_host_events(tmp_path))["scan.h2d"]
        assert dur >= 2_000_000 and abs(dur - sp.dur_ns) < 1_000_000

    def test_timed_counts_with_or_without_a_profile(self):
        TaskMetrics.reset()
        with spans.timed("scan.h2d", "h2d_ns", add={"h2d_bytes": 5}):
            time.sleep(0.001)
        tm = TaskMetrics.get()
        assert tm.h2d_ns >= 1_000_000 and tm.h2d_bytes == 5
        with pytest.raises(ValueError):
            with spans.timed("scan.h2d", "h2d_ns", add={"h2d_bytes": 5}):
                raise ValueError("inside")
        assert tm.h2d_bytes == 10  # an exception still charges the seam

    def test_every_engine_annotation_carries_the_prefix(self, tmp_path):
        def region():
            with trace_range("dispatch.exec.test"):
                pass
        _traced(tmp_path, region)
        names = {n for n, _, _ in _host_events(tmp_path)}
        assert SPAN_PREFIX + "dispatch.exec.test" in names
        assert "dispatch.exec.test" not in names


@pytest.mark.parametrize("alive", [3, 600])
@pytest.mark.parametrize("wide", [False, True], ids=["head", "overflow"])
def test_the_sink_ships_a_string_columns_live_rows_not_its_capacity(
        monkeypatch, wide, alive):
    """An answer of a few rows in a batch of a large capacity: the string
    matrix, lengths and overflow starts are cut to the live rows on the
    device, as validity and flat data always were, before they cross. Where
    more than a quarter of the capacity is alive they cross whole, and no
    program is compiled for the cut."""
    from spark_rapids_tpu.columnar import strings
    from spark_rapids_tpu.exec.transitions import device_batch_to_host
    words = ["N", "A" * 3, "R" * (300 if wide else 5)]
    t = pa.table({"flag": words + [""] * 1021, "n": list(range(1024))})
    b = batch_from_arrow(t)
    some = ColumnarBatch(b.schema, b.columns, jnp.asarray(alive, jnp.int32))
    crossed, real = [], strings.assemble_matrix

    def seen(head, lengths, overflow, n):
        crossed.append((head.shape[0], lengths.shape[0],
                        None if overflow is None else overflow[1].shape[0]))
        return real(head, lengths, overflow, n)
    monkeypatch.setattr(strings, "assemble_matrix", seen)
    hb = device_batch_to_host(some)
    rows = alive if 4 * alive <= 1024 else 1024
    assert crossed == [(rows, rows, rows if wide else None)]
    mat, lens = hb.vecs[0].data, hb.vecs[0].lengths
    assert mat.shape[0] == lens.shape[0] == alive
    assert [bytes(mat[i, :lens[i]]).decode() for i in range(3)] == words
    assert hb.vecs[1].data.tolist() == list(range(alive))
