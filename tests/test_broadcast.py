"""Broadcast exchange + broadcast join tests (reference
GpuBroadcastExchangeExec.scala:94,320, GpuBroadcastHashJoinExecBase.scala,
GpuBroadcastNestedLoopJoinExecBase.scala)."""

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.exec.broadcast import TpuBroadcastExchangeExec
from spark_rapids_tpu.expr import Sum, col, lit
from spark_rapids_tpu.plan.overrides import Overrides
from spark_rapids_tpu.plugin import TpuSession

from test_queries import assert_same, make_table


@pytest.fixture(scope="module")
def session():
    return TpuSession({"spark.rapids.sql.enabled": True,
                       "spark.rapids.sql.explain": "NONE"})


def make_dim(rng, n=50):
    keys = rng.permutation(400)[:n]
    return pa.table({
        "id": pa.array(keys, type=pa.int64()),
        "w": pa.array(rng.uniform(0.5, 1.5, n), type=pa.float64()),
    })


def device_plan(session, df):
    return Overrides(session.conf).apply(df.plan).tree_string()


class TestBroadcastPlanning:
    def test_small_build_broadcasts(self, session, rng):
        fact = session.from_arrow(make_table(rng, n=500))
        dim = session.from_arrow(make_dim(rng))
        q = fact.join(dim, on="id", how="inner")
        tree = device_plan(session, q)
        assert "TpuBroadcastHashJoinExec" in tree
        assert "TpuBroadcastExchangeExec" in tree
        assert_same(q, sort_by=["id", "val", "w"])

    def test_threshold_disables(self, rng):
        s = TpuSession({"spark.rapids.sql.enabled": True,
                        "spark.rapids.sql.explain": "NONE",
                        "spark.rapids.sql.autoBroadcastJoinThreshold": -1})
        fact = s.from_arrow(make_table(rng, n=500))
        dim = s.from_arrow(make_dim(rng))
        q = fact.join(dim, on="id", how="inner")
        tree = device_plan(s, q)
        assert "TpuBroadcastExchangeExec" not in tree
        assert "TpuShuffledHashJoinExec" in tree

    def test_tiny_threshold_disables(self, rng):
        s = TpuSession({"spark.rapids.sql.enabled": True,
                        "spark.rapids.sql.explain": "NONE",
                        "spark.rapids.sql.autoBroadcastJoinThreshold": 16})
        fact = s.from_arrow(make_table(rng, n=500))
        dim = s.from_arrow(make_dim(rng))
        q = fact.join(dim, on="id", how="inner")
        assert "TpuBroadcastExchangeExec" not in device_plan(s, q)

    @pytest.mark.parametrize("how", ["right", "full"])
    def test_build_tracking_joins_never_broadcast(self, session, rng, how):
        fact = session.from_arrow(make_table(rng, n=500))
        dim = session.from_arrow(make_dim(rng))
        q = fact.join(dim, on="id", how=how)
        assert "TpuBroadcastExchangeExec" not in device_plan(session, q)
        assert_same(q, sort_by=["id", "val", "w"])

    @pytest.mark.parametrize("how", ["inner", "left", "semi", "anti"])
    def test_broadcast_join_types_correct(self, session, rng, how):
        fact = session.from_arrow(make_table(rng, n=500))
        dim = session.from_arrow(make_dim(rng))
        q = fact.join(dim, on="id", how=how)
        assert "TpuBroadcastExchangeExec" in device_plan(session, q)
        sort_cols = ["id", "val"] if how in ("semi", "anti") \
            else ["id", "val", "w"]
        assert_same(q, sort_by=sort_cols)

    def test_keyless_small_build_broadcasts(self, session, rng):
        left = session.from_arrow(make_table(rng, n=60))
        right = session.from_arrow(make_dim(rng, n=20))
        q = left.join(right, condition=col("val") > col("w"), how="inner")
        tree = device_plan(session, q)
        assert "TpuNestedLoopJoinExec" in tree
        assert "TpuBroadcastExchangeExec" in tree
        assert_same(q, sort_by=["id", "val", "w", "id"])


class _CountingChild:
    def __init__(self, batch, schema):
        self.batch = batch
        self.output = schema
        self.calls = 0
        self.children = ()

    def execute(self):
        self.calls += 1
        return iter([self.batch])


class TestBroadcastExchange:
    def test_reuse_executes_child_once(self, session, rng):
        from spark_rapids_tpu.columnar.batch import Schema, batch_from_arrow
        t = make_dim(rng)
        child = _CountingChild(batch_from_arrow(t), Schema.from_arrow(t.schema))
        ex = TpuBroadcastExchangeExec(child, session.conf)
        out1 = list(ex.do_execute())
        out2 = list(ex.do_execute())
        assert child.calls == 1  # ReusedExchange semantics
        assert len(out1) == 1 and len(out2) == 1
        from spark_rapids_tpu.columnar.batch import batch_to_arrow
        a = batch_to_arrow(out1[0]).sort_by([("id", "ascending")])
        b = batch_to_arrow(out2[0]).sort_by([("id", "ascending")])
        assert a.equals(b)
        assert a.num_rows == t.num_rows

    def test_empty_build(self, session):
        from spark_rapids_tpu.columnar.batch import Schema
        t = pa.table({"id": pa.array([], type=pa.int64())})
        child = _CountingChild(None, Schema.from_arrow(t.schema))
        child.execute = lambda: iter([])
        ex = TpuBroadcastExchangeExec(child, session.conf)
        assert list(ex.do_execute()) == []

    def test_broadcast_with_strings(self, session, rng):
        fact = session.from_arrow(make_table(rng, n=300))
        keys = rng.permutation(400)[:40]
        dim = session.from_arrow(pa.table({
            "id": pa.array(keys, type=pa.int64()),
            "tag": pa.array([None if k % 5 == 0 else f"t{k}" for k in keys]),
        }))
        q = fact.join(dim, on="id", how="left")
        assert "TpuBroadcastExchangeExec" in device_plan(session, q)
        assert_same(q, sort_by=["id", "val", "tag"])


class TestBroadcastStaysOnDevice:
    """A flat build side whose consumers sit in this process keeps no host
    blob: it is cut to the bucket a reader of the blob would rebuild and
    parked spillable (PERF.md, fault 17)."""

    def _exchange(self, session, table, cap_rows=None):
        from spark_rapids_tpu.columnar.batch import Schema, batch_from_arrow
        batch = batch_from_arrow(table) if cap_rows is None \
            else batch_from_arrow(table, capacity=cap_rows)
        child = _CountingChild(batch, Schema.from_arrow(table.schema))
        return TpuBroadcastExchangeExec(child, session.conf), child, batch

    def test_no_blob_and_the_blob_readers_bucket(self, session, rng):
        from spark_rapids_tpu.columnar.batch import batch_to_arrow
        from spark_rapids_tpu.columnar.padding import row_bucket
        t = pa.table({
            "id": pa.array(np.arange(40), type=pa.int64()),
            "tag": pa.array([None if k % 5 == 0 else f"t{k}" * 3
                             for k in range(40)])})
        ex, child, batch = self._exchange(session, t, cap_rows=4096)
        assert batch.capacity >= 4096
        out = list(ex.do_execute())
        again = list(ex.do_execute())
        assert child.calls == 1
        assert ex._blob is None and ex._parked is not None
        assert out[0].capacity == row_bucket(40) < batch.capacity
        assert batch_to_arrow(out[0]).equals(t)
        assert batch_to_arrow(again[0]).equals(t)
        # the padding is what a reader of the blob writes: zeros, not valid
        n = int(out[0].row_count())
        for c in out[0].columns:
            assert not np.asarray(c.validity)[n:].any()
            assert not np.asarray(c.data)[n:].any()
        assert ex._arg_string() == "[device]"

    def test_parked_entry_goes_with_the_node(self, session, rng):
        import gc

        from spark_rapids_tpu.memory.catalog import BufferCatalog
        gc.collect()
        before = len(BufferCatalog.get()._entries)
        ex, _, _ = self._exchange(session, make_dim(rng))
        out = list(ex.do_execute())
        assert len(BufferCatalog.get()._entries) == before + 1
        del ex, out
        gc.collect()
        assert len(BufferCatalog.get()._entries) == before

    def test_rescache_seam_keeps_the_blob(self, rng):
        from spark_rapids_tpu import rescache
        s = TpuSession({"spark.rapids.sql.enabled": True,
                        "spark.rapids.sql.explain": "NONE",
                        "spark.rapids.tpu.rescache.enabled": True})
        try:
            s.initialize_device()
            assert rescache.is_enabled()
            ex, _, _ = self._exchange(s, make_dim(rng))
            out = list(ex.do_execute())
            assert ex._blob is not None and ex._parked is None
            assert len(out) == 1
        finally:
            rescache.shutdown()

    def test_long_strings_take_the_blob(self, session):
        t = pa.table({"id": pa.array([1, 2], type=pa.int64()),
                      "s": pa.array(["x" * 5000, "y"])})
        ex, _, batch = self._exchange(session, t)
        if all(c.overflow is None for c in batch.columns):
            pytest.skip("no long-string layout at this width")
        out = list(ex.do_execute())
        assert ex._blob is not None and ex._parked is None
        from spark_rapids_tpu.columnar.batch import batch_to_arrow
        assert batch_to_arrow(out[0]).equals(t)
