"""Pipelined-execution suite (ISSUE-6): bounded async batch prefetch
(exec/base.py PrefetchIterator), the fused multi-chunk packed scan decode
(io/parquet_device.py), pipeline-on vs pipeline-off golden equality across
scan->filter->join->agg, the exchange slot-overflow grow-and-rerun loop
under a tight MemoryBudget with spill active, and the CPU-fallback
stage-re-run counter. Marker `pipeline`; scripts/pipeline_matrix.sh runs
these standalone plus the zero-threads / bit-exactness / fault gates."""

import os
import threading
import time

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu import faults
from spark_rapids_tpu.columnar.batch import batch_from_arrow, batch_to_arrow
from spark_rapids_tpu.config import TpuConf
from spark_rapids_tpu.errors import CpuFallbackRequired
from spark_rapids_tpu.exec import base as EB
from spark_rapids_tpu.exec.base import PrefetchIterator, maybe_prefetch
from spark_rapids_tpu.expr import Count, Sum, col
from spark_rapids_tpu.memory.budget import MemoryBudget
from spark_rapids_tpu.memory.catalog import BufferCatalog
from spark_rapids_tpu.plugin import TpuSession
from spark_rapids_tpu.utils.metrics import TaskMetrics

pytestmark = pytest.mark.pipeline


def _small_batch(i: int, n: int = 64):
    t = pa.table({"a": pa.array(np.arange(n, dtype=np.int64) + i * n),
                  "b": pa.array(np.full(n, float(i)))})
    return batch_from_arrow(t)


@pytest.fixture
def fresh_budget():
    MemoryBudget.initialize(1 << 62)
    yield MemoryBudget.get()
    MemoryBudget.initialize(1 << 62)


class TestPrefetchIterator:
    def test_order_and_values_preserved(self, fresh_budget):
        src = [_small_batch(i) for i in range(8)]
        out = list(PrefetchIterator(iter(src), depth=2, name="t"))
        assert len(out) == 8
        for i, b in enumerate(out):
            got = batch_to_arrow(b)
            assert got.column("a").to_pylist()[0] == i * 64

    def test_depth_bounds_producer_lookahead(self, fresh_budget):
        produced = []
        gate = threading.Event()

        def slow_src():
            for i in range(10):
                produced.append(i)
                yield _small_batch(i)

        pf = PrefetchIterator(slow_src(), depth=2, name="t")
        it = iter(pf)
        # producer fills the queue then blocks; depth 2 + 1 in flight
        deadline = time.monotonic() + 5
        while len(produced) < 3 and time.monotonic() < deadline:
            time.sleep(0.01)
        time.sleep(0.2)  # would overrun here if the bound were broken
        assert len(produced) <= 4  # depth(2) + queued put + 1 being built
        out = list(it)
        assert len(out) == 10
        assert len(produced) == 10
        gate.set()

    def test_parked_batches_are_budget_visible(self, fresh_budget):
        budget = fresh_budget
        base = budget.used
        TaskMetrics.reset()  # fresh counters: the wait below reads them

        def src():
            for i in range(4):
                yield _small_batch(i)

        pf = PrefetchIterator(src(), depth=2, name="t")
        deadline = time.monotonic() + 5
        while TaskMetrics.get().prefetch_batches < 2 and \
                time.monotonic() < deadline:
            time.sleep(0.01)
        # at least the queued batches are parked spillable and accounted
        assert budget.used > base
        list(pf)
        assert budget.used == base  # all parked accounting released

    def test_typed_error_propagates_with_original_type(self, fresh_budget):
        def src():
            yield _small_batch(0)
            raise CpuFallbackRequired("wide string key")

        pf = PrefetchIterator(src(), depth=2, name="t")
        it = iter(pf)
        next(it)
        with pytest.raises(CpuFallbackRequired, match="wide string"):
            next(it)
        pf._thread.join(timeout=5)
        assert not pf._thread.is_alive()

    def test_early_close_joins_thread_and_frees_parked(self, fresh_budget):
        before = len(BufferCatalog.get()._entries)

        def src():
            for i in range(100):
                yield _small_batch(i)

        pf = PrefetchIterator(src(), depth=3, name="t")
        it = iter(pf)
        next(it)
        it.close()  # consumer stops early (LIMIT analog)
        pf._thread.join(timeout=5)
        assert not pf._thread.is_alive()
        assert len(BufferCatalog.get()._entries) == before

    def test_fault_during_prefetched_pull_no_deadlock(self, fresh_budget):
        """ISSUE-6 CI case: a fault injected at the pipeline.prefetch
        point must cross the queue as the typed error and the producer
        thread must terminate — no deadlock, no hang."""
        def src():
            for i in range(10):
                yield _small_batch(i)

        with faults.inject(faults.PREFETCH, "error", nth=3,
                           error=ConnectionResetError) as rule:
            pf = PrefetchIterator(src(), depth=2, name="t")
            out = []
            t0 = time.monotonic()
            with pytest.raises(ConnectionResetError):
                for b in pf:
                    out.append(b)
            assert time.monotonic() - t0 < 10  # propagated, not wedged
            assert rule.fired == 1
            assert len(out) == 2  # the two pulls before the fault
        pf._thread.join(timeout=5)
        assert not pf._thread.is_alive()

    def test_pipeline_off_spawns_zero_threads(self):
        conf = TpuConf({"spark.rapids.tpu.pipeline.enabled": False})
        before = EB.PREFETCH_THREADS_STARTED
        src = [_small_batch(i) for i in range(3)]
        it = maybe_prefetch(iter(src), conf, name="t")
        assert list(it) == src  # the exact inner iterator, pass-through
        assert EB.PREFETCH_THREADS_STARTED == before

    def test_semaphore_not_held_by_dead_producer(self, fresh_budget):
        """Producer threads must release every admission permit they
        acquired (permits are per-thread; a leak would wedge the engine
        after `concurrentGpuTasks` prefetch threads)."""
        from spark_rapids_tpu.memory.semaphore import TpuSemaphore
        from spark_rapids_tpu.memory.spillable import SpillableColumnarBatch

        def src():
            # materializing a spillable acquires the semaphore on the
            # producer thread — the leak-prone shape
            sp = SpillableColumnarBatch(_small_batch(0))
            yield sp.get_batch()
            sp.close()

        sem = TpuSemaphore.get()
        for _ in range(3 * sem.permits):  # would deadlock on a leak
            out = list(PrefetchIterator(src(), depth=1, name="t"))
            assert len(out) == 1


class TestFusedMultiChunkDecode:
    @pytest.fixture(scope="class")
    def corpus(self, tmp_path_factory):
        import decimal
        rng = np.random.default_rng(5)
        n = 16_000
        mask = rng.uniform(size=n) < 0.15
        t = pa.table({
            "k": pa.array(rng.integers(0, 1 << 40, n), mask=mask),
            "v": pa.array(rng.uniform(size=n)),
            "g": pa.array(rng.integers(0, 99, n).astype(np.int32)),
            "s": pa.array(["s%d" % i if i % 7 else None
                           for i in range(n)]),
            "b": pa.array(rng.integers(0, 2, n).astype(bool)),
            "d": pa.array([decimal.Decimal(int(x)).scaleb(-2)
                           for x in rng.integers(-10**6, 10**6, n)],
                          pa.decimal128(9, 2)),
            "ts": pa.array(rng.integers(0, 10**15, n),
                           pa.timestamp("us", tz="UTC")),
        })
        path = str(tmp_path_factory.mktemp("pipe") / "c.parquet")
        pq.write_table(t, path, row_group_size=4096)
        return path

    def _decode(self, path, chunks):
        from spark_rapids_tpu.io.parquet import CpuParquetScanExec
        from spark_rapids_tpu.io.parquet_device import (device_decode_file,
                                                        file_supported)
        schema = CpuParquetScanExec([path]).output
        pf = file_supported(path, schema)
        tables = [batch_to_arrow(b) for b, _ in device_decode_file(
            pf, path, schema, chunks_per_dispatch=chunks)]
        return pa.concat_tables(tables)

    def test_multi_chunk_bit_equal_to_serial_and_host(self, corpus):
        from spark_rapids_tpu.io.scanbase import normalize_timestamps
        ref = normalize_timestamps(pq.read_table(corpus))
        serial = self._decode(corpus, 1)
        multi = self._decode(corpus, 4)
        assert serial.equals(ref)
        assert multi.equals(ref)

    def test_dispatches_reduced_at_least_4x(self, corpus):
        tm = TaskMetrics.get()
        tm.scan_dispatches = tm.scan_chunks = 0
        self._decode(corpus, 1)
        per_chunk_serial = tm.scan_dispatches / max(tm.scan_chunks, 1)
        tm.scan_dispatches = tm.scan_chunks = 0
        self._decode(corpus, 4)
        per_chunk_multi = tm.scan_dispatches / max(tm.scan_chunks, 1)
        assert per_chunk_serial >= 4 * per_chunk_multi, \
            (per_chunk_serial, per_chunk_multi)

    def test_overwide_string_group_falls_back_correct(self, tmp_path):
        """A value wider than string.maxWidth declines the string fast
        path: the dispatch group falls back to per-row-group decode
        (which builds the chunked long-string layout) — correct rows,
        never a crash."""
        n = 2000
        vals = ["x%d" % i for i in range(n)]
        vals[137] = "W" * 9000  # > default maxWidth 8192
        t = pa.table({"s": pa.array(vals),
                      "i": pa.array(np.arange(n, dtype=np.int64))})
        path = str(tmp_path / "wide.parquet")
        pq.write_table(t, path, row_group_size=256)
        out = self._decode(path, 4)
        assert out.column("i").to_pylist() == list(range(n))
        assert out.column("s").to_pylist() == vals


def _sweep_table(rng, n=12_000):
    return pa.table({
        "k": pa.array(rng.integers(0, 512, n)),
        "g": pa.array(rng.integers(0, 16, n).astype(np.int32)),
        "v": pa.array(rng.uniform(size=n)),
        "c": pa.array(rng.integers(0, 1 << 30, n)),
        "s": pa.array(["n%d" % (i % 997) if i % 11 else None
                       for i in range(n)]),
    })


class TestPipelineGoldenSweep:
    """Pipeline-on vs pipeline-off across scan -> filter -> join -> agg
    (ISSUE-6 satellite): rows and integer aggregates bit-identical; f64
    sums allclose (batch regrouping reorders additions, the documented
    variableFloatAgg caveat)."""

    @pytest.fixture(scope="class")
    def scene(self, tmp_path_factory):
        rng = np.random.default_rng(17)
        t = _sweep_table(rng)
        path = str(tmp_path_factory.mktemp("sweep") / "fact.parquet")
        pq.write_table(t, path, row_group_size=4096)
        dim = pa.table({"k": pa.array(np.arange(512)),
                        "w": pa.array(rng.integers(0, 1000, 512))})
        return path, dim

    @staticmethod
    def _run(scene, pipeline, agg):
        path, dim = scene
        sess = TpuSession({"spark.rapids.sql.enabled": True,
                           "spark.rapids.sql.explain": "NONE",
                           "spark.rapids.tpu.pipeline.enabled": pipeline})
        q = (sess.read_parquet(path)
             .filter(col("v") > 0.2)
             .join(sess.from_arrow(dim), on="k"))
        if agg:
            q = q.group_by("g").agg(total=Sum(col("c") + col("w")),
                                    fsum=Sum(col("v")),
                                    cnt=Count(col("k")))
            return q.collect().sort_by("g")
        return q.collect().sort_by([("c", "ascending")])

    @pytest.fixture(scope="class")
    def results(self, scene):
        """Each of the four engine runs executes ONCE for the class; the
        tests below assert different facets of the same outputs."""
        before = EB.PREFETCH_THREADS_STARTED
        off_rows = self._run(scene, False, agg=False)
        off_agg = self._run(scene, False, agg=True)
        off_threads = EB.PREFETCH_THREADS_STARTED - before
        before = EB.PREFETCH_THREADS_STARTED
        on_rows = self._run(scene, True, agg=False)
        on_agg = self._run(scene, True, agg=True)
        on_threads = EB.PREFETCH_THREADS_STARTED - before
        prefetched = TaskMetrics.get().prefetch_batches
        return (off_rows, off_agg, on_rows, on_agg, off_threads,
                on_threads, prefetched)

    def test_rows_bit_identical(self, results):
        off_rows, _, on_rows = results[0], results[1], results[2]
        assert on_rows.equals(off_rows)

    def test_agg_int_exact_float_close(self, results):
        off, on = results[1], results[3]
        assert on.column("g").equals(off.column("g"))
        assert on.column("total").equals(off.column("total"))
        assert on.column("cnt").equals(off.column("cnt"))
        np.testing.assert_allclose(np.array(on.column("fsum")),
                                   np.array(off.column("fsum")),
                                   rtol=1e-12)

    def test_prefetch_actually_engaged(self, results):
        assert results[5] > 0  # pipeline-on spawned prefetch threads
        assert results[6] > 0  # and batches actually flowed through them

    def test_pipeline_off_exact_serial_path(self, results):
        assert results[4] == 0  # pipeline-off spawned none


NDEV = 8


class TestExchangeOverflowUnderPressure:
    def test_slot_overflow_grow_rerun_with_spill_active(self, rng):
        """ISSUE-6 satellite (VERDICT weak #7): the ICI slot-overflow
        grow-and-rerun loop exercised under a TIGHT MemoryBudget with
        spill active — skewed rows overflow the bounded slot (retry
        larger), while parked spillables exceed the budget and spill to
        host for real. Rows must land exactly once."""
        from spark_rapids_tpu.exec import exchange as EX
        sess = TpuSession({"spark.rapids.sql.enabled": True,
                           "spark.rapids.sql.explain": "NONE",
                           "spark.rapids.shuffle.mode": "ICI",
                           "spark.rapids.tpu.mesh.shape": f"shuffle={NDEV}",
                           "spark.rapids.shuffle.ici.slotRows": 16,
                           "spark.rapids.sql.batchSizeRows": 512,
                           "spark.rapids.sql.batchSizeBytes": 1 << 18})
        sess.initialize_device()
        n = 3000
        t = pa.table({
            "id": pa.array(np.full(n, 7), type=pa.int64()),  # one hot key
            "val": pa.array(rng.normal(0, 1, n), type=pa.float64()),
            "o": pa.array(np.arange(n, dtype=np.int64)),
        })
        df = sess.from_arrow(t)
        q = (df.repartition(NDEV, "id")
               .sort("o"))
        try:
            # calibration pass: learn this query's peak device footprint
            # (bucket-tuner state from earlier tests shifts padded sizes,
            # so a hard-coded budget is brittle); then rerun under 70% of
            # it — parked spillables must spill, single reserves still fit
            MemoryBudget.initialize(1 << 62, sess.conf)
            MemoryBudget.get().reset_peak()
            q.collect()
            peak = MemoryBudget.get().peak_used
            MemoryBudget.initialize(max(int(peak * 0.7), 64 << 10),
                                    sess.conf)
            before_ov = EX.SLOT_OVERFLOW_RETRIES
            out = q.collect()
            tm = TaskMetrics.get()
            assert out.num_rows == n
            assert out.column("o").to_pylist() == list(range(n))
            assert EX.SLOT_OVERFLOW_RETRIES > before_ov  # grow-and-rerun ran
            assert tm.spill_to_host_ns > 0  # pressure really spilled
        finally:
            MemoryBudget.initialize(1 << 62)


class TestCpuFallbackRerunCounter:
    def test_long_key_groupby_counts_rerun(self, rng):
        """ISSUE-6 satellite (VERDICT weak #8): a GROUP BY on a key wider
        than string.headWidth re-runs the stage on host via
        CpuFallbackRequired — silently, before this counter."""
        sess = TpuSession({"spark.rapids.sql.enabled": True,
                           "spark.rapids.sql.explain": "NONE"})
        n = 300
        keys = [("K%03d" % (i % 3)) * 120 for i in range(n)]  # ~600B keys
        t = pa.table({"s": pa.array(keys),
                      "v": pa.array(np.ones(n))})
        q = sess.from_arrow(t).group_by("s").agg(n_=Count(col("v")))
        out = q.collect()
        assert out.num_rows == 3
        tm = TaskMetrics.get()
        assert tm.cpu_fallback_reruns >= 1
        assert "cpuFallbackReruns" in tm.explain_string()

    def test_no_fallback_counts_zero(self, rng):
        sess = TpuSession({"spark.rapids.sql.enabled": True,
                           "spark.rapids.sql.explain": "NONE"})
        t = pa.table({"g": pa.array(np.arange(100, dtype=np.int64) % 5),
                      "v": pa.array(np.ones(100))})
        sess.from_arrow(t).group_by("g").agg(n_=Count(col("v"))).collect()
        assert TaskMetrics.get().cpu_fallback_reruns == 0


class TestJoinProbeStartsAhead:
    """A hash join starts its probe side on a prefetch thread before it
    builds, unless the probe side's scan waits for the build side's keys or
    pipelined execution is off."""

    class _Side:
        def __init__(self, schema, batches, log, tag, gate=None):
            self.output, self.children = schema, ()
            self._batches, self._log, self._tag = batches, log, tag
            self._gate = gate

        def execute(self):
            self._log.append((self._tag, "start",
                              threading.current_thread().name))
            if self._gate is not None:
                # the build side does not end before the probe side began
                assert self._gate.wait(timeout=20)
            for b in self._batches:
                yield b
            self._log.append((self._tag, "end", ""))

    def _join(self, conf, dpp=False):
        from spark_rapids_tpu.columnar.batch import Schema
        from spark_rapids_tpu.exec.joins import TpuShuffledHashJoinExec
        left = pa.table({"k": pa.array([1, 2, 3, 4], type=pa.int64()),
                         "a": pa.array([10, 20, 30, 40], type=pa.int64())})
        right = pa.table({"k": pa.array([2, 4, 5], type=pa.int64()),
                          "b": pa.array([7, 8, 9], type=pa.int64())})
        log, probe_began = [], threading.Event()

        class Probe(self._Side):
            def execute(inner):
                probe_began.set()
                return super().execute()
        probe = Probe(Schema.from_arrow(left.schema),
                      [batch_from_arrow(left)], log, "probe")
        build = self._Side(Schema.from_arrow(right.schema),
                           [batch_from_arrow(right)], log, "build",
                           gate=None if dpp else probe_began)
        join = TpuShuffledHashJoinExec(probe, build, [col("k")], [col("k")],
                                       "inner", conf=conf)
        if dpp:
            class _Filt:
                def set_values(self, v):
                    log.append(("dpp", "set", ""))
            join.dpp_filters = [(0, _Filt())]
        return join, log

    def test_probe_side_runs_beside_the_build(self):
        conf = TpuConf({})
        before = EB.PREFETCH_THREADS_STARTED
        join, log = self._join(conf)
        out = [batch_to_arrow(b) for b in join.do_execute()]
        assert sum(t.num_rows for t in out) == 2
        assert EB.PREFETCH_THREADS_STARTED == before + 1
        starts = {tag: th for tag, what, th in log if what == "start"}
        assert starts["probe"].startswith("srtpu-join-probe")
        assert not starts["build"].startswith("srtpu-")

    def test_dpp_filters_keep_the_build_first(self):
        before = EB.PREFETCH_THREADS_STARTED
        join, log = self._join(TpuConf({}), dpp=True)
        out = [batch_to_arrow(b) for b in join.do_execute()]
        assert sum(t.num_rows for t in out) == 2
        assert EB.PREFETCH_THREADS_STARTED == before
        order = [(tag, what) for tag, what, _ in log]
        assert order.index(("dpp", "set")) < order.index(("probe", "start"))

    def test_pipeline_off_starts_nothing(self):
        conf = TpuConf({"spark.rapids.tpu.pipeline.enabled": False})
        before = EB.PREFETCH_THREADS_STARTED
        join, log = self._join(conf, dpp=True)
        join.dpp_filters = []
        list(join.do_execute())
        assert EB.PREFETCH_THREADS_STARTED == before
        order = [(tag, what) for tag, what, _ in log]
        assert order.index(("build", "end")) < order.index(("probe", "start"))

    def test_an_empty_build_side_stops_the_probe_thread(self):
        from spark_rapids_tpu.columnar.batch import Schema
        from spark_rapids_tpu.exec.joins import TpuShuffledHashJoinExec
        left = pa.table({"k": pa.array(np.arange(64), type=pa.int64())})
        right = pa.table({"k": pa.array([], type=pa.int64())})
        log = []
        probe = self._Side(Schema.from_arrow(left.schema),
                           [batch_from_arrow(left)] * 8, log, "probe")
        build = self._Side(Schema.from_arrow(right.schema), [], log, "build")
        join = TpuShuffledHashJoinExec(probe, build, [col("k")], [col("k")],
                                       "inner", conf=TpuConf({}))
        threads = {t.name for t in threading.enumerate()}
        assert list(join.do_execute()) == []
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and any(
                t.name.startswith("srtpu-join-probe") and t.is_alive()
                and t.name not in threads for t in threading.enumerate()):
            time.sleep(0.05)
        assert not [t for t in threading.enumerate()
                    if t.name.startswith("srtpu-join-probe")
                    and t.is_alive()]
