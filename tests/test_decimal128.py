"""Decimal128 (precision > 18) tests — two-limb device representation vs
the CPU engine and python-Decimal hand oracles (reference:
decimalExpressions.scala + spark-rapids-jni decimal128 kernels)."""

import decimal
import random

import numpy as np
import pyarrow as pa
import pytest

decimal.getcontext().prec = 60

from spark_rapids_tpu import types as T
from spark_rapids_tpu.expr import (Cast, Count, First, Last, Max, Min, Sum,
                                   col, lit)
from spark_rapids_tpu.plugin import TpuSession

from test_queries import assert_same

D = decimal.Decimal


@pytest.fixture(scope="module")
def session():
    return TpuSession({"spark.rapids.sql.enabled": True,
                       "spark.rapids.sql.explain": "NONE"})


def dec_table(seed=3, n=400, digits=30, scale=3, null_frac=0.1):
    rnd = random.Random(seed)
    vals = [None if rnd.random() < null_frac else
            D(rnd.randint(-(10 ** digits), 10 ** digits)).scaleb(-scale)
            for _ in range(n)]
    return pa.table({
        "d": pa.array(vals, type=pa.decimal128(digits + scale, scale)),
        "g": pa.array([i % 7 for i in range(n)], type=pa.int32()),
        "i": pa.array(range(n), type=pa.int64()),
    }), vals


class TestDecimal128:
    def test_roundtrip_and_placement(self, session):
        t, _ = dec_table()
        df = session.from_arrow(t)
        q = df.select("i", "d")
        assert "not supported" not in q.explain()  # runs ON device
        out = assert_same(q, sort_by=["i"])
        assert out.column("d").to_pylist() == t.column("d").to_pylist()

    def test_group_aggregates_vs_python(self, session):
        t, vals = dec_table()
        df = session.from_arrow(t)
        q = df.group_by("g").agg(s=Sum(col("d")), mn=Min(col("d")),
                                 mx=Max(col("d")), c=Count(col("d")))
        out = assert_same(q, sort_by=["g"])
        rows = out.sort_by([("g", "ascending")]).to_pylist()
        for g in range(7):
            sel = [v for i, v in enumerate(vals) if i % 7 == g
                   and v is not None]
            assert rows[g]["s"] == sum(sel)
            assert rows[g]["mn"] == min(sel)
            assert rows[g]["mx"] == max(sel)
            assert rows[g]["c"] == len(sel)

    def test_add_subtract_overflow_null(self, session):
        big = D(10 ** 37)
        t = pa.table({"a": pa.array([big, -big, D(1)],
                                    type=pa.decimal128(38, 0)),
                      "b": pa.array([big, -big, D(2)],
                                    type=pa.decimal128(38, 0))})
        df = session.from_arrow(t)
        q = df.select(s=col("a") + col("b"), d=col("a") - col("b"))
        out = assert_same(q)
        got = sorted(out.column("s").to_pylist(), key=str)
        # 2e37 fits in precision 38; 1+2=3 fits
        assert D(2 * 10 ** 37) in got and D(-2 * 10 ** 37) in got
        assert D(3) in got

    def test_mixed_scale_add(self, session):
        t = pa.table({
            "a": pa.array([D("1.50"), D("-2.25")],
                          type=pa.decimal128(25, 2)),
            "b": pa.array([D("0.125"), D("10.000")],
                          type=pa.decimal128(30, 3)),
        })
        df = session.from_arrow(t)
        out = assert_same(df.select(s=col("a") + col("b")))
        assert sorted(out.column("s").to_pylist()) == [D("1.625"),
                                                      D("7.750")]

    def test_comparisons_and_filter(self, session):
        t, vals = dec_table(seed=9)
        df = session.from_arrow(t)
        zero = lit(D(0), T.DecimalType(33, 3))
        q = df.filter(col("d") > zero)
        want = sum(1 for v in vals if v is not None and v > 0)
        assert q.collect().num_rows == q.collect_cpu().num_rows == want

    def test_sort_order(self, session):
        t, vals = dec_table(seed=5, n=200)
        df = session.from_arrow(t)
        out = df.select("d", "i").sort("d").collect()
        got = [v for v in out.column("d").to_pylist() if v is not None]
        assert got == sorted(got)

    def test_rescale_casts_half_up(self, session):
        vals = [D("1.235"), D("-1.235"), D("99999999999999999999999.995"),
                D("0.004"), None]
        t = pa.table({"d": pa.array(vals, type=pa.decimal128(26, 3))})
        df = session.from_arrow(t)
        q = df.select(up=Cast(col("d"), T.DecimalType(30, 5)),
                      down=Cast(col("d"), T.DecimalType(26, 2)))
        out = assert_same(q)
        ups = out.column("up").to_pylist()
        downs = out.column("down").to_pylist()
        for v, u, dn in zip(vals, ups, downs):
            if v is None:
                assert u is None and dn is None
                continue
            assert u == v.quantize(D("0.00001"))
            assert dn == v.quantize(D("0.01"),
                                    rounding=decimal.ROUND_HALF_UP)

    def test_cast_overflow_to_narrow_null(self, session):
        t = pa.table({"d": pa.array([D(10 ** 25), D(5)],
                                    type=pa.decimal128(30, 0))})
        df = session.from_arrow(t)
        out = assert_same(df.select(x=Cast(col("d"), T.DecimalType(20, 1))))
        got = out.column("x").to_pylist()
        assert None in got and D("5.0") in got

    def test_sum_widens_to_128(self, session):
        # dec64 input whose SUM type is decimal(28) -> limb accumulation
        rnd = random.Random(11)
        vals = [D(rnd.randint(-(10 ** 17), 10 ** 17)) for _ in range(500)]
        t = pa.table({"d": pa.array(vals, type=pa.decimal128(18, 0)),
                      "g": pa.array([0] * 500, type=pa.int32())})
        df = session.from_arrow(t)
        out = assert_same(df.group_by("g").agg(s=Sum(col("d"))))
        assert out.column("s").to_pylist() == [sum(vals)]

    def test_first_last_if_coalesce(self, session):
        from spark_rapids_tpu.expr import Coalesce, If
        t, vals = dec_table(seed=7, n=100)
        df = session.from_arrow(t)
        zero = lit(D(0), T.DecimalType(33, 3))
        q = df.select("i", c=Coalesce(col("d"), zero),
                      f=If(col("d") > zero, col("d"), zero))
        assert_same(q, sort_by=["i"])

    def test_distributed_dec128_agg(self):
        s = TpuSession({"spark.rapids.sql.enabled": True,
                        "spark.rapids.sql.explain": "NONE",
                        "spark.rapids.shuffle.mode": "ICI",
                        "spark.rapids.tpu.mesh.shape": "shuffle=8",
                        "spark.rapids.sql.autoBroadcastJoinThreshold": -1})
        t, vals = dec_table(seed=13, n=600)
        df = s.from_arrow(t)
        q = df.group_by("g").agg(sm=Sum(col("d")), mn=Min(col("d")))
        out = assert_same(q, sort_by=["g"])
        rows = out.sort_by([("g", "ascending")]).to_pylist()
        for g in range(7):
            sel = [v for i, v in enumerate(vals) if i % 7 == g
                   and v is not None]
            assert rows[g]["sm"] == sum(sel)


class TestWideExactness:
    """Round-3 advisor regressions: 128-bit rescale wrap aliasing, Spark's
    allowPrecisionLoss result type, exact wide compares, -2^127 bound."""

    def test_addsub_rescale_no_wrap_alias(self, session):
        # dec(38,0) + dec(38,10): types as (38,6) under adjustPrecisionScale
        # and values up to 10^31 stay EXACT (the old 128-bit rescale wrapped
        # 34028236692093846346337460743 into ~-0.177 with validity=true)
        big = [D(34028236692093846346337460743), D(10) ** 30,
               D(-(10 ** 28)), D(7)]
        t = pa.table({
            "a": pa.array(big, type=pa.decimal128(38, 0)),
            "b": pa.array([D("0.5"), D(0), D("0.0000000001"), D("-7")],
                          type=pa.decimal128(38, 10)),
            "i": pa.array(range(4), type=pa.int64()),
        })
        df = session.from_arrow(t)
        q = df.select("i", s=col("a") + col("b"), d=col("a") - col("b"))
        out = assert_same(q, sort_by=["i"]).sort_by([("i", "ascending")])
        st = out.schema.field("s").type
        assert (st.precision, st.scale) == (38, 6)
        got = out.to_pylist()
        assert got[0]["s"] == D("34028236692093846346337460743.5")
        assert got[1]["s"] == D(10) ** 30
        assert got[2]["s"] == D(-(10 ** 28))  # 1e-10 rounds away at scale 6
        assert got[3]["s"] == D(0) and got[3]["d"] == D(14)

    def test_addsub_true_overflow_still_nulls(self, session):
        mx = D(10) ** 37 * 9  # 9e37, near the 38-digit cap
        t = pa.table({"a": pa.array([mx, mx], type=pa.decimal128(38, 0)),
                      "b": pa.array([mx, -mx], type=pa.decimal128(38, 0)),
                      "i": pa.array([0, 1], type=pa.int64())})
        df = session.from_arrow(t)
        out = assert_same(df.select("i", s=col("a") + col("b")),
                          sort_by=["i"]).sort_by([("i", "ascending")])
        got = out.to_pylist()
        assert got[0]["s"] is None      # 1.8e38 overflows (38,0)
        assert got[1]["s"] == D(0)

    def test_compare_wide_scale_gap_exact(self, session):
        # comparing dec(38,0) vs dec(38,10) forces a 10-digit rescale that
        # wrapped in 128 bits and misordered huge values
        a = [D(10) ** 30, D(34028236692093846346337460743), D(-(10 ** 29))]
        b = [D("0.5"), D("1.5"), D("0.5")]
        t = pa.table({"a": pa.array(a, type=pa.decimal128(38, 0)),
                      "b": pa.array(b, type=pa.decimal128(38, 10)),
                      "i": pa.array(range(3), type=pa.int64())})
        df = session.from_arrow(t)
        out = assert_same(df.select("i", gt=col("a") > col("b"),
                                    lt=col("a") < col("b")),
                          sort_by=["i"]).sort_by([("i", "ascending")])
        got = out.to_pylist()
        assert [g["gt"] for g in got] == [True, True, False]
        assert [g["lt"] for g in got] == [False, False, True]

    def test_cast_upscale_no_wrap_alias(self, session):
        # dec(38,0) -> dec(38,10): values >= 10^28 must null (true overflow),
        # never alias back into bounds through a wrapped multiply
        vals = [D(34028236692093846346337460743), D(10) ** 27, D(5)]
        t = pa.table({"d": pa.array(vals, type=pa.decimal128(38, 0)),
                      "i": pa.array(range(3), type=pa.int64())})
        df = session.from_arrow(t)
        q = df.select("i", c=Cast(col("d"), T.DecimalType(38, 10)))
        out = assert_same(q, sort_by=["i"]).sort_by([("i", "ascending")])
        got = out.to_pylist()
        assert got[0]["c"] is None
        assert got[1]["c"] == D(10) ** 27
        assert got[2]["c"] == D(5)

    def test_adjust_precision_scale_unit(self):
        from spark_rapids_tpu.expr.decimal128 import (add_result_type,
                                                      adjust_precision_scale)
        r = add_result_type(T.DecimalType(38, 0), T.DecimalType(38, 10))
        assert (r.precision, r.scale) == (38, 6)
        r = add_result_type(T.DecimalType(10, 2), T.DecimalType(12, 4))
        assert (r.precision, r.scale) == (13, 4)  # no adjustment needed
        r = adjust_precision_scale(77, 38)
        assert (r.precision, r.scale) == (38, 6)
        r = adjust_precision_scale(40, 3)
        assert (r.precision, r.scale) == (38, 3)  # min_scale=3 floor holds

    def test_in_bounds_int128_min(self):
        from spark_rapids_tpu.expr.decimal128 import in_bounds, split_int
        hi, lo = split_int(-(2 ** 127))
        ok = in_bounds(np, np.array([hi], np.int64),
                       np.array([lo], np.int64), 38)
        assert not bool(ok[0])

    def test_integral_to_dec64_cast_no_wrap(self, session):
        # CAST(1844674408L AS DECIMAL(18,10)): 1844674408 * 10^10 wraps
        # int64 to 6290448384 which passed the old post-hoc bound check
        t = pa.table({"v": pa.array([1844674408, 12345678, -(2 ** 63)],
                                    type=pa.int64()),
                      "i": pa.array(range(3), type=pa.int64())})
        df = session.from_arrow(t)
        q = df.select("i", c=Cast(col("v"), T.DecimalType(18, 10)))
        out = assert_same(q, sort_by=["i"]).sort_by([("i", "ascending")])
        got = out.to_pylist()
        assert got[0]["c"] is None          # 1.8e9 needs 10 int digits > 8
        assert got[1]["c"] == D(12345678)
        assert got[2]["c"] is None          # int64-min: abs() wraps


class TestSinkWithoutPythonObjects:
    """`collect()` hands arrow the unscaled integers as 16-byte words, no
    `Decimal` per row (PERF.md, fault 4): equal to arrow's own conversion of
    Python decimals, at the types' limits, negative, null."""

    @pytest.mark.parametrize("precision, scale", [
        (7, 2), (17, 2), (18, 0), (19, 4), (27, 2), (38, 17), (38, 0)])
    def test_equal_to_arrows_conversion(self, precision, scale):
        from spark_rapids_tpu.cpu.hostbatch import host_vec_to_arrow
        from spark_rapids_tpu.expr.base import Vec
        from spark_rapids_tpu.expr.decimal128 import split_int, to_decimal
        rng = random.Random(precision * 100 + scale)
        lim = 10 ** precision - 1
        ints = [rng.randint(-lim, lim) for _ in range(500)] + \
            [lim, -lim, 0, -1, 1, 2 ** 63, -2 ** 63, 2 ** 64 - 1]
        ints = [x for x in ints if abs(x) <= lim]
        valid = np.array([rng.random() < 0.85 for _ in ints])
        dt = T.DecimalType(precision, scale)
        if precision > T.DecimalType.MAX_LONG_DIGITS:
            data = np.array([split_int(x) for x in ints], dtype=np.int64)
        else:
            data = np.array(ints, dtype=np.int64)
        got = host_vec_to_arrow(Vec(dt, data, valid), len(ints))
        got.validate(full=True)
        want = pa.array([to_decimal(x, scale) if ok else None
                         for x, ok in zip(ints, valid)],
                        type=pa.decimal128(precision, scale))
        assert got.equals(want) and got.null_count == want.null_count

    def test_no_row_and_no_null(self):
        from spark_rapids_tpu.cpu.hostbatch import host_vec_to_arrow
        from spark_rapids_tpu.expr.base import Vec
        dt = T.DecimalType(17, 2)
        empty = host_vec_to_arrow(
            Vec(dt, np.zeros(0, np.int64), np.zeros(0, bool)), 0)
        assert len(empty) == 0 and empty.type == pa.decimal128(17, 2)
        full = host_vec_to_arrow(
            Vec(dt, np.array([-105, 250], np.int64), np.ones(2, bool)), 2)
        assert full.to_pylist() == [decimal.Decimal("-1.05"),
                                    decimal.Decimal("2.50")]
        assert full.buffers()[0] is None
