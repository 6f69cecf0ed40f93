"""Stage spans that charge their own counters: every span named in
`utils/tracing.STAGES` adds its wall time to its `TaskMetrics` field with or
without a profile, and the field reaches the query's record in the ring of
recent queries from whatever thread did the work (a prefetch producer, the
ORC walkers, which outlive the query)."""

import threading
import time

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest
from pyarrow import orc

from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.config import _REGISTRY
from spark_rapids_tpu.expr import Sum, col, lit
from spark_rapids_tpu.io import orc_device, parquet_device
from spark_rapids_tpu.io.walkers import walker_pool
from spark_rapids_tpu.plugin import TpuSession
from spark_rapids_tpu.utils import spans
from spark_rapids_tpu.utils.metrics import TaskMetrics
from spark_rapids_tpu.utils.tracing import STAGES, stage_of

pytestmark = pytest.mark.observability

FIELDS = sorted(set(STAGES.values()))
SLEEP_NS = 2_000_000


def _last_record():
    return TpuSession.recent_queries()[-1][2]


@pytest.fixture
def session():
    s = TpuSession({"spark.rapids.sql.explain": "NONE"})
    s.initialize_device()
    return s


@pytest.mark.parametrize("profiled", [False, True], ids=["off", "on"])
@pytest.mark.parametrize("stage", sorted(STAGES))
def test_a_stage_span_charges_its_field_in_the_ring(session, stage,
                                                   profiled):
    name = "dispatch.exec.test" if stage == "dispatch.*" else stage
    made = []

    def run_plan(plan, enabled):
        prof = spans.begin_profile("q") if profiled else None
        try:
            with spans.span(name):
                time.sleep(SLEEP_NS / 1e9)
        finally:
            if prof is not None:
                spans.end_profile(prof)
                made.extend(sp.name for sp in prof.spans)
    session._run_plan = run_plan
    TaskMetrics.reset()
    session._run_rewritten(session.from_arrow(pa.table({"a": [1]})).plan,
                           True)
    rec = _last_record()
    assert rec[STAGES[stage]] >= SLEEP_NS
    assert [f for f in FIELDS if f != STAGES[stage] and rec[f]] == []
    assert made == ([name] if profiled else [])


def test_the_table_names_each_stage_once():
    assert stage_of("dispatch.io.parquet.fused_multi_decode") == "dispatch.*"
    assert stage_of("sync.join_size") == "sync.join_size"
    for name in ("op.TpuSortExec", "sink.rows", "pipeline:prefetch",
                 "scan:parquet", "compile.trace.exec.sort", "dispatch"):
        assert stage_of(name) is None
    assert set(FIELDS) <= set(vars(TaskMetrics()))


@pytest.fixture(scope="module")
def star(tmp_path_factory):
    """A fact table of three row groups joined to two dimensions and summed:
    each join sizes its output once."""
    d = tmp_path_factory.mktemp("stages")
    rng = np.random.default_rng(5)
    n = 6000
    pq.write_table(pa.table({
        "k": rng.integers(0, 50, n).astype(np.int64),
        "j": rng.integers(0, 20, n).astype(np.int64),
        "v": rng.integers(0, 100, n).astype(np.int64)}),
        str(d / "fact.parquet"), row_group_size=2000)
    pq.write_table(pa.table({
        "k": np.arange(50, dtype=np.int64),
        "name": [f"n{i:02d}" for i in range(50)]}), str(d / "dim.parquet"))
    pq.write_table(pa.table({
        "j": np.arange(20, dtype=np.int64),
        "g": (np.arange(20) % 3).astype(np.int64)}), str(d / "dim2.parquet"))
    return d


def _star_query(session, d):
    fact = session.read_parquet(str(d / "fact.parquet"))
    dim = session.read_parquet(str(d / "dim.parquet"))
    dim2 = session.read_parquet(str(d / "dim2.parquet"))
    return (fact.join(dim.filter(col("k") < lit(40)), on="k")
            .join(dim2, on="j")
            .group_by("name", "g").agg(sv=Sum(col("v"))))


def test_a_device_query_records_every_stage_with_profiling_off(session,
                                                              star):
    df = _star_query(session, star)
    df.collect()
    df.collect()  # warm: no compile inside a dispatch
    rec = _last_record()
    assert rec["compile_count"] == 0
    assert spans.current_profile() is None
    for field in FIELDS:
        assert rec[field] > 0, field


def test_each_join_sizes_its_output_under_one_counted_sync(session, star,
                                                           monkeypatch):
    """`host_sync_count` is the device row counts read plus one sizing sync
    a join; both joins here take one probe batch."""
    import jax
    df = _star_query(session, star)
    df.collect()
    rows_read = []
    real = ColumnarBatch.row_count

    def counting(self):
        rows_read.append(isinstance(self.num_rows, jax.Array))
        return real(self)
    monkeypatch.setattr(ColumnarBatch, "row_count", counting)
    opened = []
    real_span = spans.span

    def seen(name, *a, **kw):
        opened.append(name)
        return real_span(name, *a, **kw)
    from spark_rapids_tpu.exec import joins
    monkeypatch.setattr(joins.spans, "span", seen)
    df.collect()
    rec = _last_record()
    assert opened.count("sync.join_size") == 2
    assert rec["host_sync_count"] == sum(rows_read) + 2


def test_a_prefetch_producers_walk_charges_the_consumers_query(
        session, tmp_path, monkeypatch):
    pq.write_table(pa.table({"a": np.arange(3000, dtype=np.int64)}),
                   str(tmp_path / "one.parquet"))
    walkers = []
    real = parquet_device._chunk_walks

    def slow(*a, **kw):
        walkers.append(threading.current_thread().name)
        time.sleep(SLEEP_NS / 1e9)
        return real(*a, **kw)
    monkeypatch.setattr(parquet_device, "_chunk_walks", slow)
    got = session.read_parquet(str(tmp_path / "one.parquet")).collect()
    assert got.num_rows == 3000
    assert walkers and all(w.startswith("srtpu-scan-") for w in walkers)
    assert _last_record()["scan_walk_ns"] >= SLEEP_NS * len(walkers)


@pytest.fixture
def orc_file(tmp_path):
    rng = np.random.default_rng(9)
    n = 5000
    path = str(tmp_path / "t.orc")
    orc.write_table(pa.table({
        "a": rng.integers(0, 1000, n).astype(np.int64),
        "b": rng.integers(0, 7, n).astype(np.int64),
        "s": pa.array([f"v{i % 5}" for i in range(n)])}), path)
    return path


def test_the_orc_walkers_charge_the_query_that_ran_them(session, orc_file,
                                                       monkeypatch):
    """Two queries on the same process-long walker threads: each walk counts
    in the query it walked for, none in the other's or the walker's own."""
    seen = []
    real = orc_device._column_streams

    def walked(*a, **kw):
        seen.append((threading.current_thread().name, TaskMetrics.get()))
        return real(*a, **kw)
    monkeypatch.setattr(orc_device, "_column_streams", walked)
    df = session.read_orc(orc_file)
    queries = []
    for _ in range(2):
        seen.clear()
        assert df.collect().num_rows == 5000
        rec, tm = _last_record(), TaskMetrics.get()
        assert rec["scan_host_decoded"] == 0 and rec["scan_walk_ns"] > 0
        assert len(seen) == 3
        assert all(name.startswith("srtpu-walk") and owner is tm
                   for name, owner in seen)
        queries.append((rec, tm))
    (rec_a, tm_a), (rec_b, tm_b) = queries
    assert tm_a is not tm_b
    # nothing of the second query's walks reached the first's counters
    assert tm_a.scan_walk_ns == rec_a["scan_walk_ns"]
    pool = walker_pool()
    n = pool._max_workers
    gate = threading.Barrier(n)

    def own():
        gate.wait(timeout=30)
        return TaskMetrics.get()
    owners = [f.result() for f in [pool.submit(own) for _ in range(n)]]
    assert all(o is not tm_a and o is not tm_b and o.scan_walk_ns == 0
               for o in owners)


def test_no_kernel_span_and_no_key_for_one(session, star):
    session.conf.set("spark.rapids.tpu.metrics.profile.enabled", True)
    _star_query(session, star).collect()
    names = [sp.name for sp in session._last_profile.spans]
    assert any(n.startswith("op.") for n in names)
    assert not [n for n in names if n.startswith("kernel:")]
    assert not [k for k in _REGISTRY if ".spans.kernel" in k]
