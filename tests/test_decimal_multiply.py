"""Decimal x decimal `Multiply` against Python's `decimal` with a wide context
(reference: `DecimalUtils.multiply128`; Spark's `DecimalPrecision` result
type and `adjustPrecisionScale`): the exact product in 32-bit limbs, HALF_UP
where the bound lowered the scale, null on overflow, a raise under ANSI,
and the same limbs under numpy and jax.numpy."""

import decimal
import random

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.errors import AnsiViolation
from spark_rapids_tpu.expr import col, lit
from spark_rapids_tpu.expr import decimal128 as D128
from spark_rapids_tpu.expr.base import EvalContext, Vec
from spark_rapids_tpu.plugin import TpuSession

from test_queries import assert_same

D = decimal.Decimal
CTX = decimal.Context(prec=120)

# (left type, right type, Spark's result type)
PAIRS = [((12, 2), (13, 2), (26, 4)),
         ((26, 4), (13, 2), (38, 6)),
         ((15, 2), (16, 2), (32, 4)),
         ((38, 10), (38, 10), (38, 6)),
         ((7, 2), (9, 3), (17, 5)),
         ((18, 0), (18, 6), (37, 6))]
IDS = [f"{a[0]}_{a[1]}x{b[0]}_{b[1]}" for a, b, _ in PAIRS]


@pytest.fixture(scope="module")
def session():
    return TpuSession({"spark.rapids.sql.enabled": True,
                       "spark.rapids.sql.explain": "NONE"})


@pytest.fixture(scope="module")
def ansi_session():
    return TpuSession({"spark.rapids.sql.enabled": True,
                       "spark.rapids.sql.explain": "NONE",
                       "spark.sql.ansi.enabled": True})


def dec(unscaled: int, scale: int) -> D:
    return CTX.scaleb(D(unscaled), -scale)


def oracle(a: int, b: int, lt, rt, out):
    """Unscaled operands -> the product Spark returns, or None: exact, then
    HALF_UP at the result scale, null past the result precision."""
    if a is None or b is None:
        return None
    exact = a * b
    drop = lt[1] + rt[1] - out[1]
    if drop:
        q, r = divmod(abs(exact), 10 ** drop)
        q += 2 * r >= 10 ** drop
        exact = -q if exact < 0 else q
    return None if abs(exact) >= 10 ** out[0] else dec(exact, out[1])


def operands(rnd, lt, rt, n=160):
    """Seeded operands: the types' limits and their neighbours (so the
    128-bit and 256-bit carries are reached), small values, random values
    of every magnitude, nulls on either side."""
    la, ra = 10 ** lt[0] - 1, 10 ** rt[0] - 1
    edge = [(la, ra), (-la, ra), (la, -ra), (-la, -ra), (la, 1), (1, ra),
            (0, ra), (la, 0), (-1, -1), (la - 1, ra - 1),
            (2 ** 63 % (la + 1), 2 ** 31 % (ra + 1)), (None, 5), (7, None),
            (None, None)]
    while len(edge) < n:
        a = rnd.randint(0, 10 ** rnd.randint(0, lt[0]) - 1)
        b = rnd.randint(0, 10 ** rnd.randint(0, rt[0]) - 1)
        edge.append((rnd.choice((a, -a)), rnd.choice((b, -b))))
    return edge


def table(rows, lt, rt):
    return pa.table({
        "i": pa.array(range(len(rows)), pa.int32()),
        "a": pa.array([None if a is None else dec(a, lt[1])
                       for a, _ in rows], pa.decimal128(*lt)),
        "b": pa.array([None if b is None else dec(b, rt[1])
                       for _, b in rows], pa.decimal128(*rt))})


@pytest.mark.parametrize("lt,rt,out", PAIRS, ids=IDS)
def test_result_type_is_sparks(lt, rt, out):
    e = col("a") * col("b")
    from spark_rapids_tpu.columnar.batch import Schema
    from spark_rapids_tpu.expr.base import bind_references
    schema = Schema(("a", "b"), (T.DecimalType(*lt), T.DecimalType(*rt)))
    assert bind_references(e, schema).data_type == T.DecimalType(*out)


@pytest.mark.parametrize("lt,rt,out", PAIRS, ids=IDS)
def test_product_exact_on_both_engines(session, lt, rt, out):
    rows = operands(random.Random(hash((lt, rt)) & 0xFFFF), lt, rt)
    q = session.from_arrow(table(rows, lt, rt)).select(
        "i", p=col("a") * col("b"))
    assert "not supported" not in q.explain()
    got = assert_same(q, sort_by=["i"])
    assert got.schema.field("p").type == pa.decimal128(*out)
    want = [oracle(a, b, lt, rt, out) for a, b in rows]
    assert got.column("p").to_pylist() == want
    # the limits overflow the bounded types and nothing else does silently
    assert (want[0] is None) == (lt[0] + rt[0] > 38)


@pytest.mark.parametrize("sign", [1, -1], ids=["positive", "negative"])
def test_half_up_ties_where_the_scale_was_lowered(session, sign):
    lt = rt = (38, 10)
    out = (38, 6)  # ideal (77, 20): 14 digits dropped
    halves = [5, 15, 25, 35, 49999, 50000, 50001, 149999, 150000, 250000]
    rows = [(sign * h, 10 ** 9) for h in halves]  # h x 10^9 / 10^14
    got = session.from_arrow(table(rows, lt, rt)).select(
        "i", p=col("a") * col("b"))
    got = assert_same(got, sort_by=["i"]).column("p").to_pylist()
    assert got == [oracle(a, b, lt, rt, out) for a, b in rows]
    # 0.5 -> 1 and 2.5 -> 3 away from zero, where HALF_EVEN gives 0 and 2
    assert got[4] == dec(0, 6) and got[5] == dec(sign * 1, 6)
    assert got[7] == dec(sign * 1, 6) and got[8] == dec(sign * 2, 6)
    assert got[9] == dec(sign * 3, 6)


def test_overflow_is_null_and_raises_under_ansi(session, ansi_session):
    lt, rt = (26, 4), (13, 2)
    rows = [(10 ** 26 - 1, 10 ** 13 - 1), (10 ** 25, 10 ** 12), (3, 4)]
    q = session.from_arrow(table(rows, lt, rt)).select(p=col("a") * col("b"))
    assert assert_same(q).column("p").to_pylist() == [
        None, dec(10 ** 37, 6), dec(12, 6)]
    bad = ansi_session.from_arrow(table(rows, lt, rt)).select(
        p=col("a") * col("b"))
    with pytest.raises(AnsiViolation, match="ARITHMETIC_OVERFLOW"):
        bad.collect()
    with pytest.raises(AnsiViolation, match="ARITHMETIC_OVERFLOW"):
        bad.collect_cpu()
    # an overflow pattern under a null raises nothing
    ok = ansi_session.from_arrow(table(
        [(None, 10 ** 13 - 1), (10 ** 26 - 1, None), (3, 4)], lt, rt))
    assert assert_same(ok.select(p=col("a") * col("b"))) \
        .column("p").to_pylist() == [None, None, dec(12, 6)]


def test_integer_times_decimal_follows_spark(session):
    t = pa.table({"n": pa.array([3, -7, None, 2 ** 31 - 1], pa.int32()),
                  "d": pa.array([D("1.25"), D("-0.01"), D("9.99"),
                                 D("99999999.99")], pa.decimal128(10, 2))})
    q = session.from_arrow(t).select(a=col("n") * col("d"),
                                     b=col("d") * lit(12),
                                     c=lit(D("0.5")) * col("d"))
    got = assert_same(q)
    # int column -> decimal(10,0); the literal 12 -> decimal(2,0)
    assert got.schema.field("a").type == pa.decimal128(21, 2)
    assert got.schema.field("b").type == pa.decimal128(13, 2)
    assert got.schema.field("c").type == pa.decimal128(12, 3)
    assert got.column("a").to_pylist() == [
        D("3.75"), D("0.07"), None, (2 ** 31 - 1) * D("99999999.99")]
    assert got.column("b").to_pylist() == [
        D("15.00"), D("-0.12"), D("119.88"), D("1199999999.88")]
    assert got.column("c").to_pylist() == [
        D("0.625"), D("-0.005"), D("4.995"), D("49999999.995")]


@pytest.mark.parametrize("lt,rt,out", PAIRS, ids=IDS)
def test_numpy_and_jax_give_the_same_limbs(lt, rt, out):
    import jax.numpy as jnp
    from spark_rapids_tpu.expr.arithmetic import Multiply
    from spark_rapids_tpu.expr.base import BoundReference
    rows = [(a or 0, b or 0) for a, b in
            operands(random.Random(11), lt, rt, n=64)]

    def vec(xp, vals, typ):
        dt = T.DecimalType(*typ)
        if D128.is_dec128(dt):
            data = np.array([D128.split_int(v) for v in vals], np.int64)
        else:
            data = np.array(vals, np.int64)
        return Vec(dt, xp.asarray(data), xp.ones(len(vals), dtype=bool))

    e = Multiply(BoundReference(0, T.DecimalType(*lt)),
                 BoundReference(1, T.DecimalType(*rt)))
    outs = []
    for xp in (np, jnp):
        ctx = EvalContext(xp, row_mask=xp.ones(len(rows), dtype=bool))
        outs.append(e.eval(ctx, [vec(xp, [a for a, _ in rows], lt),
                                 vec(xp, [b for _, b in rows], rt)]))
    assert outs[0].dtype == outs[1].dtype == T.DecimalType(*out)
    valid = np.asarray(outs[0].validity)
    assert (valid == np.asarray(outs[1].validity)).all()
    assert (np.asarray(outs[0].data)[valid]
            == np.asarray(outs[1].data)[valid]).all()
    want = [oracle(a, b, lt, rt, out) for a, b in rows]
    assert [w is not None for w in want] == valid.tolist()


def test_wide_mul_is_the_256_bit_product():
    rnd = random.Random(5)
    vals = [(rnd.getrandbits(128), rnd.getrandbits(128)) for _ in range(50)]
    vals += [(2 ** 128 - 1, 2 ** 128 - 1), (2 ** 64, 2 ** 64), (0, 1)]

    def limbs(xs):
        return [np.array([(x >> (32 * k)) & 0xFFFFFFFF for x in xs],
                         np.uint64) for k in range(4)]
    prod = D128.wide_mul(np, limbs([a for a, _ in vals]),
                         limbs([b for _, b in vals]))
    assert len(prod) == 8
    got = [sum(int(prod[k][i]) << (32 * k) for k in range(8))
           for i in range(len(vals))]
    assert got == [a * b for a, b in vals]
