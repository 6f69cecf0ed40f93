"""`lit()` of a date, a datetime and a `decimal.Decimal`: the type Spark's
`Literal.apply` / `DecimalType.fromDecimal` gives, a repr that names value
and type (two literals may not share a compiled program), and the value on
both engines."""

import datetime
import decimal

import pyarrow as pa
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.compile.service import CompileService
from spark_rapids_tpu.expr import col, lit
from spark_rapids_tpu.plugin import TpuSession

from test_queries import assert_same

D = decimal.Decimal


@pytest.fixture(scope="module")
def session():
    return TpuSession({"spark.rapids.sql.enabled": True,
                       "spark.rapids.sql.explain": "NONE"})


@pytest.mark.parametrize("value,want", [
    (datetime.date(1998, 9, 2), T.DATE),
    (datetime.datetime(1998, 9, 2, 12, 30), T.TIMESTAMP),
    (D("1"), T.DecimalType(1, 0)),
    (D("0"), T.DecimalType(1, 0)),
    (D("0.01"), T.DecimalType(2, 2)),
    (D("-12.340"), T.DecimalType(5, 3)),
    (D("1E+2"), T.DecimalType(3, 0)),
    (D("12345678901234567890.5"), T.DecimalType(21, 1)),
], ids=repr)
def test_inferred_type_and_repr(value, want):
    e = lit(value)
    assert e.data_type == want
    assert repr(e) == f"lit({value!r})"
    # the same value under another type is another literal
    assert repr(lit(value, T.DecimalType(30, 5))) != repr(e)


def test_unsupported_values_still_raise():
    with pytest.raises(TypeError, match="cannot infer literal type"):
        lit(D("NaN"))
    with pytest.raises(TypeError, match="cannot infer literal type"):
        lit(object())


def test_values_on_both_engines(session):
    t = pa.table({"d": pa.array([datetime.date(1998, 9, 1),
                                 datetime.date(1998, 9, 2),
                                 datetime.date(1998, 9, 3), None],
                                pa.date32()),
                  "m": pa.array([D("0.05"), D("0.00"), D("0.10"), None],
                                pa.decimal128(12, 2))})
    df = session.from_arrow(t)
    q = df.select(le=col("d") <= lit(datetime.date(1998, 9, 2)),
                  one_minus=lit(D("1")) - col("m"),
                  day=lit(datetime.date(1970, 1, 11)),
                  ts=lit(datetime.datetime(1970, 1, 1, 0, 0, 1, 5)),
                  big=lit(D("12345678901234567890.5")))
    assert "not supported" not in q.explain()
    out = assert_same(q)
    assert out.column("le").to_pylist() == [True, True, False, None]
    assert out.schema.field("one_minus").type == pa.decimal128(13, 2)
    assert out.column("one_minus").to_pylist() == [
        D("0.95"), D("1.00"), D("0.90"), None]
    assert out.column("day").to_pylist()[0] == datetime.date(1970, 1, 11)
    assert out.column("ts").cast(pa.int64()).to_pylist()[0] == 1_000_005
    assert out.column("big").to_pylist()[0] == D("12345678901234567890.5")
    kept = df.filter(col("d") <= lit(datetime.date(1998, 9, 2))).select("d")
    assert assert_same(kept).num_rows == 2


def test_two_values_compile_two_programs(session):
    t = pa.table({"d": pa.array([datetime.date(1998, 9, 1),
                                 datetime.date(1998, 9, 3)], pa.date32()),
                  "m": pa.array([D("0.05"), D("0.10")], pa.decimal128(12, 2))})
    df = session.from_arrow(t)
    stats = CompileService.get().stats

    def compiles(q):
        before = stats.totals()["compiles"]
        out = q.collect()
        return out, stats.totals()["compiles"] - before
    first = df.select(le=col("d") <= lit(datetime.date(1998, 9, 2)))
    out, n = compiles(first)
    assert out.column("le").to_pylist() == [True, False] and n >= 1
    # the same literal again: the program is found
    assert compiles(df.select(
        le=col("d") <= lit(datetime.date(1998, 9, 2))))[1] == 0
    out, n = compiles(df.select(le=col("d") <= lit(datetime.date(1998, 9, 3))))
    assert out.column("le").to_pylist() == [True, True] and n >= 1
    out, n = compiles(df.select(p=col("m") * lit(D("2"))))
    assert out.column("p").to_pylist() == [D("0.10"), D("0.20")] and n >= 1
    out, n = compiles(df.select(p=col("m") * lit(D("3"))))
    assert out.column("p").to_pylist() == [D("0.15"), D("0.30")] and n >= 1
    # Decimal('2') and Decimal('2.0') differ in type, so in program
    out, n = compiles(df.select(p=col("m") * lit(D("2.0"))))
    assert out.schema.field("p").type == pa.decimal128(15, 3) and n >= 1
