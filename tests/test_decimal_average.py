"""`avg` of a decimal column is exact (Spark: the sum as decimal(p + 10, s),
divided by the count, HALF_UP at scale s + 4, in decimal(p + 4, s + 4)) on
the device path and in the CPU engine, in complete mode and through
partial -> final. It used to sum the unscaled values in float64 and hand a
DOUBLE back under the decimal type: avg of [1.00, 2.00] read 150.000000."""

import decimal
import random

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.expr import Average, Count, Sum, col
from spark_rapids_tpu.expr import decimal128 as D128
from spark_rapids_tpu.plugin import TpuSession

from test_queries import assert_same

D = decimal.Decimal
CTX = decimal.Context(prec=120)


@pytest.fixture(scope="module")
def session():
    return TpuSession({"spark.rapids.sql.enabled": True,
                       "spark.rapids.sql.explain": "NONE"})


def half_up_avg(vals, scale_out):
    """Python-decimal oracle: sum / count, HALF_UP at `scale_out`."""
    vals = [v for v in vals if v is not None]
    if not vals:
        return None
    q = CTX.divide(sum(vals, D(0)), D(len(vals)))
    return q.quantize(D(1).scaleb(-scale_out), rounding=decimal.ROUND_HALF_UP,
                      context=CTX)


def grouped(session, keys, vals, typ):
    t = pa.table({"k": pa.array(keys, pa.int32()),
                  "q": pa.array(vals, pa.decimal128(*typ))})
    q = session.from_arrow(t).group_by("k").agg(a=Average(col("q")))
    assert "not supported" not in q.explain()
    out = assert_same(q, sort_by=["k"])
    assert out.schema.field("a").type == pa.decimal128(
        min(typ[0] + 4, 38), min(typ[1] + 4, 38))
    return out.sort_by([("k", "ascending")]).column("a").to_pylist()


def test_the_two_cases_that_were_wrong(session):
    got = grouped(session, [1, 1, 2, 2, 2],
                  [D("1.00"), D("2.00"), D("3.00"), D("3.00"), D("4.01")],
                  (15, 2))
    assert got == [D("1.500000"), D("3.336667")]
    assert [str(x) for x in got] == ["1.500000", "3.336667"]


@pytest.mark.parametrize("typ", [(15, 2), (12, 2), (7, 2), (38, 6), (38, 0),
                                 (20, 10)],
                         ids=lambda t: f"decimal_{t[0]}_{t[1]}")
def test_random_groups_against_python_decimal(session, typ):
    rnd = random.Random(typ[0] * 100 + typ[1])
    n, groups = 600, 9
    keys = [rnd.randrange(groups) for _ in range(n)]
    # decimal(38, s): values small enough that the sum keeps its 38 digits
    # and the average, with four more decimals, keeps its own
    top = 10 ** (typ[0] - (7 if typ[0] == 38 else 0))
    vals = [None if rnd.random() < 0.1 else
            CTX.scaleb(D(rnd.randint(-top + 1, top - 1)), -typ[1])
            for _ in range(n)]
    got = grouped(session, keys, vals, typ)
    scale_out = min(typ[1] + 4, 38)
    want = [half_up_avg([v for k, v in zip(keys, vals) if k == g], scale_out)
            for g in sorted(set(keys))]
    assert got == want


def test_half_up_ties_negatives_and_a_group_of_nulls(session):
    def run(keys, vals):
        t = pa.table({"k": pa.array(keys, pa.int32()),
                      "q": pa.array(vals, pa.decimal128(9, 6))})
        q = session.from_arrow(t).group_by("k").agg(a=Average(col("q")),
                                                    c=Count(col("q")))
        return assert_same(q, sort_by=["k"]).sort_by([("k", "ascending")])
    out = run([0] * 8 + [1] * 8 + [2, 2] + [3],
              [D("0.000001")] + [D("0")] * 7            # 1.25e-7
              + [D("-0.000001")] * 3 + [D("0")] * 5     # -3.75e-7
              + [None, None] + [D("-1.000001")])
    assert out.column("a").to_pylist() == [
        D("0.0000001250"), D("-0.0000003750"), None, D("-1.0000010000")]
    assert out.column("c").to_pylist() == [8, 8, 0, 1]
    # a true tie of the last digit: 1e-6 / 32 = 3.125e-8 at scale 10, the
    # dropped digit exactly 5 -> away from zero on both signs (HALF_EVEN
    # would keep ...312)
    out = run([0] * 32 + [1] * 32,
              [D("0.000001")] + [D("0")] * 31
              + [D("-0.000001")] + [D("0")] * 31)
    assert out.column("a").to_pylist() == [D("0.0000000313"),
                                           D("-0.0000000313")]


def test_global_average_and_empty_input(session):
    t = pa.table({"q": pa.array([D("1.10"), D("2.25"), None],
                                pa.decimal128(12, 2))})
    q = session.from_arrow(t).agg(a=Average(col("q")), s=Sum(col("q")))
    out = assert_same(q)
    assert out.column("a").to_pylist() == [D("1.675000")]
    none = pa.table({"q": pa.array([None, None], pa.decimal128(12, 2))})
    q = session.from_arrow(none).agg(a=Average(col("q")))
    assert assert_same(q).column("a").to_pylist() == [None]


def test_partial_then_final_through_two_batches_equals_complete(session,
                                                                monkeypatch):
    """Two input batches take the aggregate's partial -> merge -> final
    kernels (sum as decimal(p + 10, s), count as long, merged by sum)."""
    rnd = random.Random(29)
    n = 500
    keys = [rnd.randrange(5) for _ in range(n)]
    vals = [None if rnd.random() < 0.15 else
            CTX.scaleb(D(rnd.randint(-10 ** 12 + 1, 10 ** 12 - 1)), -2)
            for _ in range(n)]
    keys += [7, 7]          # a group of nulls only
    vals += [None, None]
    t = pa.table({"k": pa.array(keys, pa.int32()),
                  "q": pa.array(vals, pa.decimal128(12, 2))})
    agg = dict(a=Average(col("q")), c=Count(col("q")))
    one = session.from_arrow(t).group_by("k").agg(**agg)
    complete = assert_same(one, sort_by=["k"])
    small = TpuSession({"spark.rapids.sql.enabled": True,
                        "spark.rapids.sql.explain": "NONE",
                        "spark.rapids.sql.batchSizeRows": 128})
    two = small.from_arrow(t).group_by("k").agg(**agg)
    from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
    seen = []
    real = TpuHashAggregateExec._multi_batch
    monkeypatch.setattr(
        TpuHashAggregateExec, "_multi_batch",
        lambda self, batches: seen.append(len(batches)) or real(self, batches))
    merged = two.collect().sort_by([("k", "ascending")])
    assert seen and seen[0] >= 2
    assert merged.schema.equals(complete.schema)
    assert merged.to_pylist() == \
        complete.sort_by([("k", "ascending")]).to_pylist()
    want = [half_up_avg([v for k, v in zip(keys, vals) if k == g], 6)
            for g in sorted(set(keys))]
    assert merged.column("a").to_pylist() == want
    assert want[-1] is None


def test_partial_buffers_are_spark_typed():
    f = Average(col("q"))
    from spark_rapids_tpu.columnar.batch import Schema
    from spark_rapids_tpu.expr.base import bind_references
    schema = Schema(("q",), (T.DecimalType(12, 2),))
    f = f.with_children([bind_references(f.child, schema)])
    assert f.partial_types() == [T.DecimalType(22, 2), T.LONG]
    assert f.data_type == T.DecimalType(16, 6)
    g = Average(bind_references(col("q"), Schema(("q",), (T.DOUBLE,))))
    assert g.partial_types() == [T.DOUBLE, T.LONG]


@pytest.mark.parametrize("precision,ks", [(38, (0, 4, 19)), (22, (4,)),
                                          (12, (4,))],
                         ids=["three_words", "two_words", "one_word"])
def test_div_count_half_up_for_every_count_width(precision, ks):
    """The limb division against python ints: 32-bit and 63-bit counts,
    sums of both signs up to the type's limit, numpy and jax.numpy alike;
    the precision decides how many 64-bit words and steps the loop takes."""
    import jax.numpy as jnp
    rnd = random.Random(precision)
    top = 10 ** precision - 1
    cases = [(top, 1), (-top, 1), (top, 3), (1, 2), (-1, 2), (1, 3), (0, 5),
             (5, 10 ** 18), (top, 2 ** 63 - 1), (top // 7, 2 ** 32),
             (top // 7, 2 ** 32 - 1), (top // 7, 2 ** 32 + 1),
             (-(top // 10), 2 ** 62 + 12345)]
    for _ in range(300):
        s = rnd.randint(-10 ** rnd.randint(1, precision) + 1,
                        10 ** rnd.randint(1, precision) - 1)
        cases.append((s, rnd.randint(1, 2 ** rnd.randint(1, 63) - 1)))
    limbs = np.array([D128.split_int(s) for s, _ in cases], np.int64)
    counts = np.array([c for _, c in cases], np.int64)
    for k in ks:
        want = []
        for s, c in cases:
            q = (2 * abs(s) * 10 ** k + c) // (2 * c)
            want.append(-q if s < 0 else q)
        for xp in (np, jnp):
            hi, lo, fits = D128.div_count_half_up(
                xp, xp.asarray(limbs[:, 0]), xp.asarray(limbs[:, 1]),
                precision, k, xp.asarray(counts))
            hi, lo, fits = (np.asarray(x) for x in (hi, lo, fits))
            for i, w in enumerate(want):
                assert bool(fits[i]) == (abs(w) < 2 ** 127), (cases[i], k)
                if fits[i]:
                    assert D128.join_int(int(hi[i]), int(lo[i])) == w, \
                        (cases[i], k, xp.__name__)
