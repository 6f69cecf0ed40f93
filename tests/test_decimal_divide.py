"""`/` on decimals is exact and in Spark's type: the result type by
`DecimalPrecision` (scale max(6, s1 + p2 + 1), precision p1 - s1 + s2 +
scale, bounded at 38 keeping the integral digits), the value by Spark's two
roundings (the quotient at 38 significant digits HALF_UP, then HALF_UP at the
result scale), a zero divisor null (DIVIDE_BY_ZERO under ANSI), a quotient out
of the type null (ARITHMETIC_OVERFLOW under ANSI), on the device in limbs and
in the CPU engine on Python's `decimal`. It used to divide the unscaled
integers in float64 and hand a DOUBLE back, and raised on 128-bit operands.
Non-decimal operands keep the double path bit for bit."""

import decimal
import random

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.errors import AnsiViolation
from spark_rapids_tpu.expr import col, lit
from spark_rapids_tpu.expr import decimal128 as D128
from spark_rapids_tpu.plugin import TpuSession

from test_queries import assert_same

D = decimal.Decimal
WIDE = decimal.Context(prec=200)
AT38 = decimal.Context(prec=38, rounding=decimal.ROUND_HALF_UP)


@pytest.fixture(scope="module")
def session():
    return TpuSession({"spark.rapids.sql.enabled": True,
                       "spark.rapids.sql.explain": "NONE"})


def spark_divide_type(p1, s1, p2, s2):
    """`DecimalPrecision` for Divide with allowPrecisionLoss, written out."""
    scale = max(6, s1 + p2 + 1)
    precision = p1 - s1 + s2 + scale
    if precision > 38:
        integral = precision - scale
        scale = max(38 - integral, min(scale, 6))
        precision = 38
    return precision, scale


def spark_divide(a, b, out):
    """Python-decimal oracle: `BigDecimal.divide` at 38 significant digits
    HALF_UP, then `toPrecision(p, s, HALF_UP)`; None for a null operand, a
    zero divisor or a quotient out of the type."""
    if a is None or b is None or b == 0:
        return None
    q = AT38.divide(a, b).quantize(D(1).scaleb(-out[1]),
                                   rounding=decimal.ROUND_HALF_UP,
                                   context=WIDE)
    return q if abs(WIDE.scaleb(q, out[1])) < 10 ** out[0] else None


def dec(unscaled, scale):
    return None if unscaled is None else WIDE.scaleb(D(unscaled), -scale)


def divided(session, xs, ys, tx, ty):
    """x / y on both engines (compared), with the result's arrow type."""
    t = pa.table({"x": pa.array(xs, pa.decimal128(*tx)),
                  "y": pa.array(ys, pa.decimal128(*ty))})
    q = session.from_arrow(t).select((col("x") / col("y")).alias("q"))
    assert "not supported" not in q.explain()
    out = assert_same(q)
    return out.schema.field("q").type, out.column("q").to_pylist()


# (p1, s1, p2, s2) -> Spark's result, worked by hand from the rule
TYPE_TABLE = [
    ((7, 2, 7, 2), (17, 10)), ((17, 2, 17, 2), (37, 20)),
    ((21, 2, 27, 2), (38, 17)),     # query 98's ratio
    ((38, 6, 38, 6), (38, 6)), ((38, 0, 1, 0), (38, 6)),
    ((10, 0, 38, 38), (38, 6)), ((38, 38, 38, 0), (38, 38)),
    ((12, 2, 3, 0), (16, 6)), ((5, 0, 5, 0), (11, 6)),
    ((18, 4, 9, 3), (31, 14)), ((20, 10, 20, 10), (38, 18)),
    ((38, 10, 20, 5), (38, 6)),
]


@pytest.mark.parametrize("operands,want", TYPE_TABLE,
                         ids=lambda v: "_".join(map(str, v)))
def test_result_type_is_sparks(operands, want):
    p1, s1, p2, s2 = operands
    assert spark_divide_type(*operands) == want
    e = col("x") / col("y")
    from spark_rapids_tpu.columnar.batch import Schema
    from spark_rapids_tpu.expr.base import bind_references
    bound = bind_references(e, Schema(
        ("x", "y"), (T.DecimalType(p1, s1), T.DecimalType(p2, s2))))
    assert bound.data_type == T.DecimalType(*want)
    assert D128.divide_result_type(
        T.DecimalType(p1, s1), T.DecimalType(p2, s2)) == T.DecimalType(*want)


def test_an_integral_beside_a_decimal_is_its_decimal(session):
    """Spark casts an integral literal beside a decimal to just its digits
    (`DecimalType.fromLiteral`) and an int column to decimal(10,0)."""
    t = pa.table({"x": pa.array([D("3.00"), D("-7.53"), None],
                                pa.decimal128(17, 2)),
                  "n": pa.array([7, 0, 3], pa.int32())})
    q = session.from_arrow(t).select((col("x") / lit(100)).alias("a"),
                                     (col("x") / col("n")).alias("b"),
                                     (lit(100) / col("x")).alias("c"))
    out = assert_same(q)
    assert out.schema.field("a").type == pa.decimal128(21, 6)
    assert out.schema.field("b").type == pa.decimal128(28, 13)
    assert out.schema.field("c").type == pa.decimal128(23, 18)
    assert out.column("a").to_pylist() == [D("0.030000"), D("-0.075300"),
                                           None]
    assert out.column("b").to_pylist() == [D("0.4285714285714"), None, None]
    assert out.column("c").to_pylist() == [
        D("33.333333333333333333"),
        spark_divide(D(100), D("-7.53"), (23, 18)), None]


@pytest.mark.parametrize("tx,ty", [((21, 2), (27, 2)), ((7, 2), (7, 2)),
                                   ((17, 2), (12, 5)), ((38, 6), (38, 6)),
                                   ((38, 0), (9, 0)), ((10, 0), (38, 38))],
                         ids=lambda t: f"decimal_{t[0]}_{t[1]}")
def test_values_against_python_decimal(session, tx, ty):
    """Random operands over every magnitude up to the types' limits, both
    signs, unequal scales, zero divisors and nulls."""
    rnd = random.Random(tx[0] * 1000 + ty[0])
    out = spark_divide_type(*tx, *ty)

    def draw(p):
        top = 10 ** rnd.randint(1, p)
        return rnd.choice([rnd.randint(-top + 1, top - 1), 10 ** p - 1,
                           -(10 ** p - 1), 1, -1, 3, 7])
    xs = [draw(tx[0]) for _ in range(160)] + [None, 5, 0, 10 ** tx[0] - 1]
    ys = [draw(ty[0]) for _ in range(160)] + [3, None, 7, 1]
    xs += [12345, 0, None]
    ys += [0, 0, 0]
    xs, ys = [dec(v, tx[1]) for v in xs], [dec(v, ty[1]) for v in ys]
    typ, got = divided(session, xs, ys, tx, ty)
    assert typ == pa.decimal128(*out)
    want = [spark_divide(a, b, out) for a, b in zip(xs, ys)]
    assert got == want
    assert sum(v is not None for v in want) >= 40
    if (tx, ty) in (((38, 6), (38, 6)), ((38, 0), (9, 0)),
                    ((10, 0), (38, 38))):
        # some quotients leave decimal(38, 6): null, not a wrapped value
        assert sum(w is None and a is not None and b not in (None, 0)
                   for w, a, b in zip(want, xs, ys)) >= 1


def double_rounding_case(p1, s1, p2, s2, q_digits, b_digits):
    """(x, y) whose exact quotient at the result scale is Q + F with F one
    half less 1 / (2 |y|): the first rounding (38 significant digits) lifts
    the fraction to exactly one half and the second rounds it up, where one
    rounding at the result scale would round down."""
    out = spark_divide_type(p1, s1, p2, s2)
    k = out[1] - s1 + s2
    m = 10 ** k
    rnd = random.Random(q_digits * 100 + b_digits)
    while True:
        q = rnd.randrange(10 ** (q_digits - 1), 10 ** q_digits)
        if (2 * q + 1) % 5 == 0:
            continue
        b0 = pow(2 * q + 1, -1, 2 * m)
        lo = 10 ** (b_digits - 1)
        b = b0 + (lo // (2 * m) + 1) * 2 * m
        a, rem = divmod(q * b + (b - 1) // 2, m)
        assert rem == 0
        if a < 10 ** p1 and b < 10 ** min(b_digits, p2) and \
                10 ** (38 - q_digits) <= b:
            return dec(a, s1), dec(b, s2), dec(q + 1, out[1]), out


@pytest.mark.parametrize("types,q_digits,b_digits", [
    ((21, 2, 27, 2), 12, 27), ((38, 0, 38, 0), 14, 31),
    ((38, 6, 38, 6), 10, 34)], ids=["q98", "38_0", "38_6"])
def test_spark_rounds_twice(session, types, q_digits, b_digits):
    x, y, want, out = double_rounding_case(*types, q_digits, b_digits)
    # the oracle agrees that two roundings differ from one here
    once = decimal.Context(prec=150).divide(x, y).quantize(
        D(1).scaleb(-out[1]), rounding=decimal.ROUND_HALF_UP, context=WIDE)
    assert spark_divide(x, y, out) == want and once != want
    typ, got = divided(session, [x, -x, x], [y, y, -y], types[:2], types[2:])
    assert typ == pa.decimal128(*out)
    assert got == [want, -want, -want]


def test_half_up_at_the_result_scale_and_unequal_scales(session):
    # 1 / 8 = 0.125 exactly; at scale 6 nothing is rounded; 2 / 3 and -2 / 3
    # round away from zero in the last place
    typ, got = divided(session, [D("1"), D("2"), D("-2"), D("0.5")],
                       [D("8"), D("3"), D("3"), D("0.004")], (3, 1), (4, 3))
    assert typ == pa.decimal128(*spark_divide_type(3, 1, 4, 3))
    assert got == [D("0.125000"), D("0.666667"), D("-0.666667"),
                   D("125.000000")]


def test_ansi_raises_on_a_zero_divisor_and_on_overflow():
    ansi = TpuSession({"spark.rapids.sql.enabled": True,
                       "spark.rapids.sql.explain": "NONE",
                       "spark.sql.ansi.enabled": True})

    def frame(xs, ys, tx, ty):
        t = pa.table({"x": pa.array(xs, pa.decimal128(*tx)),
                      "y": pa.array(ys, pa.decimal128(*ty))})
        return ansi.from_arrow(t).select((col("x") / col("y")).alias("q"))
    zero = frame([D("1.00"), D("2.00")], [D("4.00"), D("0.00")],
                 (17, 2), (17, 2))
    for run in (zero.collect, zero.collect_cpu):
        with pytest.raises(AnsiViolation, match="DIVIDE_BY_ZERO"):
            run()
    # a null beside the zero divisor is no division: no error
    fine = frame([None, D("2.00")], [D("0.00"), D("4.00")], (17, 2), (17, 2))
    assert assert_same(fine).column("q").to_pylist() == [
        None, D("0.50000000000000000000")]
    big = frame([dec(10 ** 38 - 1, 0)], [dec(1, 3)], (38, 0), (4, 3))
    for run in (big.collect, big.collect_cpu):
        with pytest.raises(AnsiViolation, match="ARITHMETIC_OVERFLOW"):
            run()


def test_non_decimal_operands_keep_the_double_path_bit_for_bit(session):
    rng = np.random.default_rng(7)
    a = rng.integers(-10 ** 12, 10 ** 12, 400)
    b = rng.integers(-50, 50, 400)
    f = rng.normal(0, 1e6, 400)
    t = pa.table({"a": a, "b": b, "f": f, "i": b.astype(np.int32)})
    q = session.from_arrow(t).select((col("a") / col("b")).alias("ab"),
                                     (col("f") / col("i")).alias("fi"),
                                     (col("a") / col("f")).alias("af"))
    out = assert_same(q)
    for name in ("ab", "fi", "af"):
        assert out.schema.field(name).type == pa.float64()
    with np.errstate(divide="ignore", invalid="ignore"):
        want_ab = a.astype(np.float64) / b.astype(np.float64)
        want_fi = f / b.astype(np.float64)
    got_ab = out.column("ab").to_pylist()
    got_fi = out.column("fi").to_pylist()
    for i in range(400):
        if b[i] == 0:
            assert got_ab[i] is None and got_fi[i] is None
        else:
            assert np.float64(got_ab[i]).tobytes() == want_ab[i].tobytes()
            assert np.float64(got_fi[i]).tobytes() == want_fi[i].tobytes()
    assert np.array_equal(
        np.asarray(out.column("af").to_pylist(), np.float64), a / f)


def test_a_decimal_beside_a_double_divides_by_value(session):
    """Spark casts the decimal to double: its value, not its unscaled
    integer."""
    t = pa.table({"x": pa.array([D("1.50"), D("-3.00"), None],
                                pa.decimal128(7, 2)),
                  "f": pa.array([2.0, 0.0, 1.0], pa.float64())})
    q = session.from_arrow(t).select((col("x") / col("f")).alias("a"),
                                     (col("f") / col("x")).alias("b"))
    out = assert_same(q)
    assert out.schema.field("a").type == pa.float64()
    assert out.column("a").to_pylist() == [0.75, None, None]
    assert out.column("b").to_pylist() == [2.0 / 1.5, -0.0, None]


def test_the_kernel_divides_in_one_loop_with_no_64_bit_division():
    """The v5e compiler takes 22 s over a 64-bit `//` and minutes over an
    unrolled restoring division (PERF.md, PR 29): the lowered kernel holds
    one `while` and no integer divide."""
    import jax
    import jax.numpy as jnp

    def kernel(ahi, alo, bhi, blo):
        return D128.div_half_up(jnp, ahi, alo, 21, 17, bhi, blo)
    arg = jax.ShapeDtypeStruct((256,), jnp.int64)
    text = jax.jit(kernel).lower(arg, arg, arg, arg).as_text()
    assert text.count("stablehlo.while") == 1
    assert "stablehlo.divide" not in text and "stablehlo.remainder" not in text


def test_a_projection_counts_its_decimal_divides(session):
    from spark_rapids_tpu.utils import metrics as M
    t = pa.table({"x": pa.array([D("1.00"), D("2.00")], pa.decimal128(9, 2)),
                  "y": pa.array([D("3.00"), D("7.00")], pa.decimal128(9, 2)),
                  "f": pa.array([1.0, 2.0])})
    q = session.from_arrow(t).select((col("x") / col("y")).alias("a"),
                                     (col("f") / col("f")).alias("b"),
                                     (col("y") / col("x")).alias("c"))
    for runs in (1, 2):     # the second run's program comes from the cache
        q.collect()
        node = session.last_plan
        while node.name != "TpuProjectExec":
            node = node.children[0]
        assert node.metrics.snapshot()[M.NUM_DECIMAL_DIVIDES] == 2
