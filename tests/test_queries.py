"""End-to-end query differential tests (the reference's SparkQueryCompareTestSuite
model: same query on CPU engine and TPU engine, compare results)."""

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.expr import (Average, Count, Divide, First, Last, Max, Min,
                                   Murmur3Hash, Sum, col, lit)
from spark_rapids_tpu.plugin import TpuSession


@pytest.fixture(scope="module")
def session():
    return TpuSession({"spark.rapids.sql.enabled": True,
                       "spark.rapids.sql.explain": "NONE"})


def assert_same(df, sort_by=None, approx_cols=()):
    """Run on both engines; compare (row-order-insensitive unless sorted)."""
    tpu = df.collect()
    cpu = df.collect_cpu()
    assert tpu.schema.equals(cpu.schema), f"{tpu.schema} != {cpu.schema}"
    if len(set(tpu.schema.names)) != len(tpu.schema.names):
        # joins can emit duplicate column names; uniquify identically on
        # both sides so arrow sort/column lookups work
        seen = {}
        uniq = []
        for n in tpu.schema.names:
            seen[n] = seen.get(n, 0) + 1
            uniq.append(n if seen[n] == 1 else f"{n}__dup{seen[n]}")
        tpu = tpu.rename_columns(uniq)
        cpu = cpu.rename_columns(uniq)
    if sort_by:
        keys = [(k, "ascending") for k in sort_by]
        tpu = tpu.sort_by(keys)
        cpu = cpu.sort_by(keys)
    assert tpu.num_rows == cpu.num_rows, f"{tpu.num_rows} != {cpu.num_rows}"
    for name in tpu.schema.names:
        a, b = tpu.column(name).to_pylist(), cpu.column(name).to_pylist()
        for i, (x, y) in enumerate(zip(a, b)):
            if x is None or y is None:
                assert x is None and y is None, f"{name}[{i}]: {x!r} vs {y!r}"
            elif isinstance(x, float) and name in approx_cols:
                assert x == y or abs(x - y) <= 1e-9 * max(abs(x), abs(y), 1.0), \
                    f"{name}[{i}]: {x!r} vs {y!r}"
            elif isinstance(x, float) and (x != x or y != y):
                assert x != x and y != y, f"{name}[{i}]: {x!r} vs {y!r}"
            else:
                assert x == y, f"{name}[{i}]: {x!r} vs {y!r}"
    return tpu


def make_table(rng, n=1000, null_frac=0.1):
    ids = rng.integers(0, 50, n)
    vals = rng.normal(0, 100, n)
    cats = np.array(["alpha", "beta", "gamma", "delta", None], dtype=object)[
        rng.integers(0, 5, n)]
    nulls = rng.random(n) < null_frac
    return pa.table({
        "id": pa.array(np.where(nulls, 0, ids), type=pa.int64(),
                       mask=nulls),
        "val": pa.array(vals, type=pa.float64()),
        "cat": pa.array(list(cats)),
        "small": pa.array(rng.integers(-100, 100, n), type=pa.int32()),
    })


class TestBasicQueries:
    def test_project_filter(self, session, rng):
        df = session.from_arrow(make_table(rng))
        q = df.filter(col("small") > 0).select(
            (col("id") * 2).alias("id2"),
            (col("val") + col("small")).alias("v"),
            col("cat"))
        assert_same(q, sort_by=["id2", "v"])

    def test_filter_all_rows(self, session, rng):
        df = session.from_arrow(make_table(rng, n=64))
        assert_same(df.filter(lit(True)), sort_by=["id", "val"])
        out = assert_same(df.filter(lit(False)))
        assert out.num_rows == 0

    def test_range_and_limit(self, session):
        q = session.range(0, 1000, 3).limit(17)
        out = assert_same(q)
        assert out.column("id").to_pylist() == list(range(0, 51, 3))

    def test_union(self, session, rng):
        a = session.from_arrow(make_table(rng, n=100))
        b = session.from_arrow(make_table(rng, n=200))
        assert_same(a.union(b), sort_by=["id", "val", "small"])


class TestAggregateQueries:
    def test_group_by_agg(self, session, rng):
        df = session.from_arrow(make_table(rng))
        q = df.group_by("id").agg(
            n=Count(col("val")),
            total=Sum(col("small")),
            lo=Min(col("val")),
            hi=Max(col("val")),
            avg=Average(col("val")),
        )
        assert_same(q, sort_by=["id"], approx_cols=("total", "avg"))

    def test_group_by_string_key(self, session, rng):
        df = session.from_arrow(make_table(rng))
        q = df.group_by("cat").agg(n=Count(col("id")),
                                   mx=Max(col("small")))
        assert_same(q, sort_by=["cat"])

    def test_global_agg(self, session, rng):
        df = session.from_arrow(make_table(rng, n=500))
        q = df.agg(n=Count(col("val")), s=Sum(col("small")),
                   mn=Min(col("small")), mx=Max(col("small")))
        assert_same(q)

    def test_global_agg_empty_input(self, session, rng):
        df = session.from_arrow(make_table(rng, n=50))
        q = df.filter(lit(False)).agg(n=Count(col("val")),
                                      s=Sum(col("small")))
        out = assert_same(q)
        assert out.to_pylist() == [{"n": 0, "s": None}]

    def test_count_star(self, session, rng):
        df = session.from_arrow(make_table(rng, n=300))
        q = df.group_by("cat").agg(n=Count())
        assert_same(q, sort_by=["cat"])

    def test_min_max_string(self, session, rng):
        df = session.from_arrow(make_table(rng))
        q = df.group_by("id").agg(lo=Min(col("cat")), hi=Max(col("cat")))
        assert_same(q, sort_by=["id"])

    def test_first_last(self, session, rng):
        # first/last are order-dependent; sort first so both engines agree
        df = session.from_arrow(make_table(rng, n=200)) \
            .sort("val").group_by("id") \
            .agg(f=First(col("small")), l=Last(col("small")))
        assert_same(df, sort_by=["id"])


class TestSortQueries:
    def test_sort_multi_key(self, session, rng):
        df = session.from_arrow(make_table(rng, n=300))
        q = df.sort(("cat", True, True), ("val", False, False))
        tpu = q.collect()
        cpu = q.collect_cpu()
        assert tpu.equals(cpu) or tpu.to_pylist() == cpu.to_pylist()

    def test_sort_nulls_positions(self, session, rng):
        df = session.from_arrow(make_table(rng, n=100))
        for asc, nf in [(True, True), (True, False), (False, True),
                        (False, False)]:
            q = df.sort(("id", asc, nf), ("val", True, True))
            tpu, cpu = q.collect(), q.collect_cpu()
            assert tpu.column("id").to_pylist() == cpu.column("id").to_pylist()

    @pytest.mark.parametrize("op", ["sort", "sort_desc", "window"])
    @pytest.mark.parametrize("source", ["all_filtered", "zero_rows"])
    def test_zero_row_batch_on_string_key(self, session, op, source):
        """A string sort key on a batch with no rows: the word-packed key
        builder must not infer its word count from an empty array (it raised
        on both engines' CPU paths)."""
        from spark_rapids_tpu.expr import RowNumber
        n = 0 if source == "zero_rows" else 50
        t = pa.table({"k": pa.array([f"key{i % 7}" for i in range(n)],
                                    pa.string()),
                      "v": pa.array(list(range(n)), pa.int64())})
        df = session.from_arrow(t)
        if source == "all_filtered":
            df = df.filter(col("v") > 1000)
        if op == "window":
            q = df.window(partition_by=[col("k")], order_by=[col("v")],
                          rn=RowNumber())
        else:
            asc = op == "sort"
            q = df.sort((col("k"), asc, asc), (col("v"), True, True))
        cpu, tpu = q.collect_cpu(), q.collect()
        assert cpu.num_rows == 0 and tpu.num_rows == 0
        assert tpu.schema.equals(cpu.schema)


class TestJoinQueries:
    def _tables(self, session, rng, payload="narrow"):
        left = session.from_arrow(make_table(rng, n=400))
        dim = pa.table({
            "id": pa.array(list(range(0, 40)) + [None], type=pa.int64()),
            "name": pa.array([f"name_{i}" for i in range(40)] + [None]),
        })
        if payload == "narrow_and_wide":
            # a description of up to 200 bytes beside the 8-byte name: the
            # row gathers cut the one into words and move the other whole
            dim = dim.append_column("descr", pa.array(
                [None if i % 7 == 3 else ("word%d " % i) * (i % 29)
                 for i in range(41)]))
        right = session.from_arrow(dim)
        return left, right

    @pytest.mark.parametrize("payload", ["narrow", "narrow_and_wide"])
    @pytest.mark.parametrize("how", ["inner", "left", "right", "full", "semi",
                                     "anti"])
    def test_join_types(self, session, rng, how, payload):
        left, right = self._tables(session, rng, payload)
        q = left.join(right, on="id", how=how)
        sort_cols = ["id", "val"] if how in ("semi", "anti") else None
        tpu = q.collect()
        cpu = q.collect_cpu()
        assert tpu.num_rows == cpu.num_rows, f"{how}: row count"
        # order-insensitive multiset comparison
        def key(t):
            return sorted(map(str, t.to_pylist()))
        assert key(tpu) == key(cpu), f"{how}: rows differ"

    def test_join_duplicate_keys(self, session, rng):
        a = session.from_arrow(pa.table({
            "k": pa.array([1, 1, 2, 3, None], type=pa.int64()),
            "x": pa.array([10, 11, 20, 30, 40], type=pa.int64())}))
        b = session.from_arrow(pa.table({
            "k": pa.array([1, 1, 1, 2, None], type=pa.int64()),
            "y": pa.array([100, 101, 102, 200, 300], type=pa.int64())}))
        q = a.join(b, on="k", how="inner")
        tpu, cpu = q.collect(), q.collect_cpu()
        assert tpu.num_rows == cpu.num_rows == 7  # 2*3 + 1

    def test_join_then_agg(self, session, rng):
        left, right = self._tables(session, rng)
        q = left.join(right, on="id", how="inner") \
            .group_by("name").agg(n=Count(), s=Sum(col("small")))
        assert_same(q, sort_by=["name"])


class TestFallback:
    def test_explain_reports_fallback(self, session, rng):
        # DOUBLE -> STRING cast is not device-supported -> node falls back
        df = session.from_arrow(make_table(rng, n=64)).select(
            col("val").cast(T.STRING).alias("s"))
        explain = df.explain()
        assert "cast double -> string is not supported" in explain
        # and the query still runs correctly via CPU fallback
        tpu, cpu = df.collect(), df.collect_cpu()
        assert tpu.equals(cpu)

    def test_disable_expression_conf(self, rng):
        s = TpuSession({"spark.rapids.sql.expression.Length": "false",
                        "spark.rapids.sql.explain": "NONE"})
        df = s.from_arrow(pa.table({"s": pa.array(["ab", "xyz"])}))
        from spark_rapids_tpu.expr import Length
        q = df.select(Length(col("s")).alias("n"))
        explain = q.explain()
        assert "Length" in explain and "disabled" in explain
        assert q.collect().column("n").to_pylist() == [2, 3]

    def test_strict_mode_raises(self, rng):
        s = TpuSession({"spark.rapids.sql.test.enabled": True})
        df = s.from_arrow(pa.table({"v": pa.array([1.5])}))
        q = df.select(col("v").cast(T.STRING))
        with pytest.raises(AssertionError, match="fell back"):
            q.collect()


class TestSample:
    def test_sample_differential(self, session, rng):
        df = session.from_arrow(make_table(rng, n=2000))
        q = df.sample(0.3, seed=7)
        out = assert_same(q, sort_by=["id", "val"])
        assert 0.2 < out.num_rows / 2000 < 0.4

    def test_sample_deterministic_and_batch_invariant(self, rng):
        t = make_table(rng, n=1000)
        small = TpuSession({"spark.rapids.sql.explain": "NONE",
                            "spark.rapids.sql.batchSizeRows": 64})
        big = TpuSession({"spark.rapids.sql.explain": "NONE",
                          "spark.rapids.sql.batchSizeRows": 100000})
        key = [("id", "ascending"), ("val", "ascending")]
        a = small.from_arrow(t).sample(0.5, seed=3).collect().sort_by(key)
        b = big.from_arrow(t).sample(0.5, seed=3).collect().sort_by(key)
        assert a.equals(b)  # global-ordinal hashing is batch-size invariant

    def test_sample_edge_fractions(self, session, rng):
        df = session.from_arrow(make_table(rng, n=100))
        assert df.sample(0.0).collect().num_rows == 0
        assert df.sample(1.0).collect().num_rows == 100
        with pytest.raises(ValueError):
            df.sample(1.5)

    def test_sample_then_agg(self, session, rng):
        from spark_rapids_tpu.expr import Count, lit
        df = session.from_arrow(make_table(rng, n=500))
        q = df.sample(0.4, seed=11).group_by("cat").agg(n=Count(lit(1)))
        assert_same(q, sort_by=["cat"])
