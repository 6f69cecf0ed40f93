"""`sum` and `avg` of a decimal over a window are exact and carry Spark's
types (decimal(p + 10, s) and decimal(p + 4, s + 4)): over a whole partition
on the device, by the grouped aggregate's own sorted-segment sums gathered
back by partition, and under every frame in the CPU engine, on Python
integers. No decimal aggregate over a window ran on either engine before: the
device summed limbs as float64 under the decimal type, the CPU engine raised
`IndexError`, and `avg` was a float mean (PERF.md, fault 12). A frame without
an exact device kernel is tagged off the device with its reason."""

import decimal
import random

import pyarrow as pa
import pytest

from spark_rapids_tpu.expr import Average, Count, Max, Min, Sum, col
from spark_rapids_tpu.expr.windowexprs import (RangeFrame, RowFrame,
                                               WindowAggregate)
from spark_rapids_tpu.plugin import TpuSession
from spark_rapids_tpu.utils import metrics as M

from test_queries import assert_same

D = decimal.Decimal
WIDE = decimal.Context(prec=120)


@pytest.fixture(scope="module")
def session():
    return TpuSession({"spark.rapids.sql.enabled": True,
                       "spark.rapids.sql.explain": "NONE"})


def dec(unscaled, scale):
    return None if unscaled is None else WIDE.scaleb(D(unscaled), -scale)


def oracle(keys, unscaled, typ, rows_of):
    """Per row the exact (sum, avg) over the rows `rows_of(i)` names, from
    Python integers: the sum at scale s within p + 10 digits, the average
    HALF_UP at scale s + 4."""
    sums, avgs = [], []
    for i in range(len(keys)):
        vals = [unscaled[j] for j in rows_of(i) if unscaled[j] is not None]
        if not vals:
            sums.append(None)
            avgs.append(None)
            continue
        total = sum(vals)
        sums.append(dec(total, typ[1])
                    if abs(total) < 10 ** min(typ[0] + 10, 38) else None)
        q = (2 * abs(total) * 10 ** 4 + len(vals)) // (2 * len(vals))
        avgs.append(dec(-q if total < 0 else q, typ[1] + 4)
                    if q < 10 ** min(typ[0] + 4, 38) else None)
    return sums, avgs


def table(keys, unscaled, typ):
    return pa.table({"i": pa.array(range(len(keys)), pa.int32()),
                     "k": pa.array(keys, pa.int32()),
                     "v": pa.array([dec(u, typ[1]) for u in unscaled],
                                   pa.decimal128(*typ))})


def drawn(typ, n, parts, seed):
    rnd = random.Random(seed)
    keys = [rnd.randrange(parts) for _ in range(n)]
    # decimal(38, s): small enough that a partition's sum keeps 38 digits
    top = 10 ** (typ[0] - (4 if typ[0] == 38 else 0))
    unscaled = [None if rnd.random() < 0.12 or k == 3
                else rnd.randint(-top + 1, top - 1) for k in keys]
    return keys, unscaled


@pytest.mark.parametrize("typ", [(7, 2), (17, 2), (38, 6)],
                         ids=lambda t: f"decimal_{t[0]}_{t[1]}")
def test_whole_partition_sum_and_avg_are_exact_on_both_engines(session, typ):
    """Partitions with nulls, one of nulls only (k = 3), and 333 rows in a
    512-row batch, so the kernel sees a dead tail."""
    keys, unscaled = drawn(typ, 333, 7, typ[0])
    q = session.from_arrow(table(keys, unscaled, typ)).window(
        partition_by=[col("k")], s=Sum(col("v")), a=Average(col("v")),
        c=Count(col("v")))
    assert "not supported" not in q.explain() and "!" not in q.explain()
    out = assert_same(q, sort_by=["i"])
    assert out.schema.field("s").type == pa.decimal128(
        min(typ[0] + 10, 38), typ[1])
    assert out.schema.field("a").type == pa.decimal128(
        min(typ[0] + 4, 38), typ[1] + 4)
    sums, avgs = oracle(keys, unscaled, typ, lambda i: [
        j for j in range(len(keys)) if keys[j] == keys[i]])
    assert out.column("s").to_pylist() == sums
    assert out.column("a").to_pylist() == avgs
    assert any(s is None for s in sums) and any(s is not None for s in sums)
    node = session.last_plan
    while node.name != "TpuWindowExec":
        node = node.children[0]
    snap = node.metrics.snapshot()
    assert snap[M.NUM_DECIMAL_WINDOW_AGGS] == 2
    assert snap[M.NUM_WINDOW_PARTITIONS] == len(set(keys))


@pytest.mark.parametrize("typ", [(7, 2), (38, 6)],
                         ids=lambda t: f"decimal_{t[0]}_{t[1]}")
def test_one_partition_of_every_row(session, typ):
    keys, unscaled = drawn(typ, 200, 5, 77 + typ[0])
    q = session.from_arrow(table(keys, unscaled, typ)).window(
        s=Sum(col("v")), a=Average(col("v")))
    assert "!" not in q.explain()
    out = assert_same(q, sort_by=["i"])
    sums, avgs = oracle(keys, unscaled, typ, lambda i: range(len(keys)))
    assert out.column("s").to_pylist() == sums
    assert out.column("a").to_pylist() == avgs
    assert len(set(sums)) == 1 and sums[0] is not None


def test_a_sum_past_64_bits_and_a_sum_that_leaves_its_type(session):
    """decimal(38, 0) values near the type's limit: two of them sum past 38
    digits (null, Spark's non-ANSI overflow), one stays."""
    big = 10 ** 38 - 1
    keys = [0, 0, 1, 2, 2, 2]
    unscaled = [big, 1, big, -big, -5, 4]
    q = session.from_arrow(table(keys, unscaled, (38, 0))).window(
        partition_by=[col("k")], s=Sum(col("v")))
    out = assert_same(q, sort_by=["i"])
    assert out.column("s").to_pylist() == [
        None, None, dec(big, 0), None, None, None]
    # 2^64 is crossed without leaving the type
    unscaled = [2 ** 63, 2 ** 63, 2 ** 63, -2 ** 63, -2 ** 63, 7]
    q = session.from_arrow(table(keys, unscaled, (38, 0))).window(
        partition_by=[col("k")], s=Sum(col("v")), a=Average(col("v")))
    out = assert_same(q, sort_by=["i"])
    assert out.column("s").to_pylist() == [
        dec(2 ** 64, 0)] * 2 + [dec(2 ** 63, 0)] + [dec(7 - 2 ** 64, 0)] * 3
    assert out.column("a").to_pylist()[0] == dec(2 ** 63 * 10 ** 4, 4)


def _tagged(q):
    return [line for line in q.explain().splitlines() if "!" in line]


def test_a_running_sum_within_18_digits_stays_on_the_device(session):
    keys, unscaled = drawn((7, 2), 150, 4, 5)
    running = RowFrame(None, 0)
    q = session.from_arrow(table(keys, unscaled, (7, 2))).window(
        partition_by=[col("k")], order_by=[col("i")],
        s=WindowAggregate(Sum(col("v")), running))
    assert not _tagged(q)
    out = assert_same(q, sort_by=["i"])
    assert out.schema.field("s").type == pa.decimal128(17, 2)
    sums, _ = oracle(keys, unscaled, (7, 2), lambda i: [
        j for j in range(i + 1) if keys[j] == keys[i]])
    assert out.column("s").to_pylist() == sums


@pytest.mark.parametrize("what,frame", [
    ("avg_running_rows", RowFrame(None, 0)),
    ("avg_default_range", None),
    ("avg_bounded", RowFrame(-2, 1)),
    ("sum128_running_rows", RowFrame(None, 0)),
    ("sum128_bounded", RowFrame(-1, 1))])
def test_a_frame_without_an_exact_kernel_is_tagged_and_answered_exactly(
        session, what, frame):
    """A running or bounded average, and a running or bounded sum past 18
    digits, leave the device with the reason in the plan; the CPU engine
    answers in the decimal type, every digit."""
    typ = (7, 2) if what.startswith("avg") else (17, 2)
    keys, unscaled = drawn(typ, 120, 4, len(what))
    agg = Average(col("v")) if what.startswith("avg") else Sum(col("v"))
    q = session.from_arrow(table(keys, unscaled, typ)).window(
        partition_by=[col("k")], order_by=[col("i")],
        x=agg if frame is None else WindowAggregate(agg, frame))
    tagged = _tagged(q)
    assert tagged and "no exact device kernel" in " ".join(tagged)
    out = assert_same(q, sort_by=["i"])
    want_t = pa.decimal128(typ[0] + 4, typ[1] + 4) \
        if what.startswith("avg") else pa.decimal128(typ[0] + 10, typ[1])
    assert out.schema.field("x").type == want_t

    def rows_of(i):
        mine = [j for j in range(len(keys)) if keys[j] == keys[i]]
        at = mine.index(i)
        lo = 0 if frame is None or frame.lower is None \
            else max(0, at + frame.lower)
        hi = at if frame is None or frame.upper == 0 \
            else min(len(mine) - 1, at + frame.upper)
        return mine[lo:hi + 1]
    sums, avgs = oracle(keys, unscaled, typ, rows_of)
    assert out.column("x").to_pylist() == (
        avgs if what.startswith("avg") else sums)


def test_min_max_first_last_of_a_128_bit_decimal_over_a_window(session):
    """min / max of 128-bit limbs have no device kernel over a window: the
    CPU engine compares Python integers (it compared the limb pairs as a
    matrix before)."""
    from spark_rapids_tpu.expr import First
    keys = [0, 0, 0, 1, 1, 2]
    unscaled = [5, -2 ** 70, 2 ** 70, None, 3, None]
    q = session.from_arrow(table(keys, unscaled, (38, 0))).window(
        partition_by=[col("k")], lo=Min(col("v")), hi=Max(col("v")))
    assert "no device kernel for 128-bit decimals" in q.explain()
    out = assert_same(q, sort_by=["i"])
    assert out.column("lo").to_pylist() == [dec(-2 ** 70, 0)] * 3 + [
        dec(3, 0)] * 2 + [None]
    assert out.column("hi").to_pylist() == [dec(2 ** 70, 0)] * 3 + [
        dec(3, 0)] * 2 + [None]
    q = session.from_arrow(table(keys, unscaled, (38, 0))).window(
        partition_by=[col("k")], order_by=[col("i")],
        f=WindowAggregate(First(col("v")), RangeFrame(None, None)))
    assert not _tagged(q)
    out = assert_same(q, sort_by=["i"])
    assert out.column("f").to_pylist() == [dec(5, 0)] * 3 + [None] * 3


def test_no_window_aggregate_returns_a_float_under_a_decimal_type(session):
    """The arrays behind every decimal window column are integers."""
    keys, unscaled = drawn((17, 2), 64, 3, 11)
    q = session.from_arrow(table(keys, unscaled, (17, 2))).window(
        partition_by=[col("k")], s=Sum(col("v")), a=Average(col("v")))
    for got in (q.collect(), q.collect_cpu()):
        for name in ("s", "a"):
            assert pa.types.is_decimal(got.schema.field(name).type)
            for v in got.column(name).to_pylist():
                assert v is None or isinstance(v, D)
