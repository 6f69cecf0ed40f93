"""Row gathers on the device path (ops/rowops.py `gather_vecs`, `_RowMover`):
a batch's columns move by one index vector as rows of a few stacked uint32
matrices, not one gather an array.

Three guards: the device path bit for bit against the per-array numpy path
(`Vec.gather`) for every dtype, column count, index length and capacity; the
tallies and the two operator metrics for a batch shaped like TPC-H Q1's;
the filter's lowered kernel (at most four gathers of the batch's capacity
where sixteen stood)."""

import datetime
import decimal

import numpy as np
import pyarrow as pa
import pytest

import jax
import jax.numpy as jnp

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar import batch_from_arrow
from spark_rapids_tpu.expr import Average, Count, Sum, col, lit
from spark_rapids_tpu.expr.base import Vec, vec_map_arrays
from spark_rapids_tpu.ops.rowops import GatherTally, compact_vecs, gather_vecs
from spark_rapids_tpu.plugin import TpuSession
from spark_rapids_tpu.utils import metrics as M


def _ints(rng, n, dtype, *tail):
    info = np.iinfo(dtype)
    return rng.integers(info.min, info.max, (n,) + tail, dtype=dtype,
                        endpoint=True)


def _string(rng, n, width):
    return (rng.integers(0, 255, (n, width), dtype=np.uint8),
            rng.integers(0, width, n, dtype=np.int32, endpoint=True))


def _vec(rng, n, kind) -> Vec:
    """A vec of `n` rows of full-range values under a random validity."""
    valid = rng.random(n) < 0.8

    def flat(dt, data, lengths=None, **kw):
        return Vec(dt, data, valid, lengths, **kw)

    if kind == "bool":
        return flat(T.BOOLEAN, rng.random(n) < 0.5)
    if kind == "int8":
        return flat(T.BYTE, _ints(rng, n, np.int8))
    if kind == "int16":
        return flat(T.SHORT, _ints(rng, n, np.int16))
    if kind == "int32":
        return flat(T.INT, _ints(rng, n, np.int32))
    if kind == "int64":
        return flat(T.LONG, _ints(rng, n, np.int64))
    if kind == "float32":
        # every bit pattern: NaNs with payloads, both zeros, subnormals
        return flat(T.FLOAT, _ints(rng, n, np.int32).view(np.float32))
    if kind == "float64":
        return flat(T.DOUBLE, _ints(rng, n, np.int64).view(np.float64))
    if kind == "date":
        return flat(T.DATE, _ints(rng, n, np.int32))
    if kind == "timestamp":
        return flat(T.TIMESTAMP, _ints(rng, n, np.int64))
    if kind == "decimal64":
        return flat(T.DecimalType(12, 2), _ints(rng, n, np.int64))
    if kind == "decimal128":
        return flat(T.DecimalType(38, 6), _ints(rng, n, np.int64, 2))
    if kind.startswith("string"):
        data, lengths = _string(rng, n, int(kind[len("string"):]))
        return flat(T.STRING, data, lengths)
    if kind == "overflow":   # a 16-byte head, the tails in a shared blob
        data, lengths = _string(rng, n, 16)
        blob = rng.integers(0, 255, 4096, dtype=np.uint8)
        return flat(T.STRING, data, lengths * 9,
                    overflow=(blob, _ints(rng, n, np.int32) & 0xFFF))
    if kind == "struct":
        dt = T.StructType([T.StructField("a", T.LONG),
                           T.StructField("b", T.STRING)])
        return flat(dt, valid.copy(), children=(
            _vec(rng, n, "int64"), _vec(rng, n, "string8")))
    if kind == "array":
        elem = Vec(T.INT, _ints(rng, n, np.int32, 8),
                   rng.random((n, 8)) < 0.8)
        return flat(T.ArrayType(T.INT),
                    rng.integers(0, 8, n, dtype=np.int32, endpoint=True),
                    children=(elem,))
    raise AssertionError(kind)


KINDS = ["bool", "int8", "int16", "int32", "int64", "float32", "float64",
         "date", "timestamp", "decimal64", "decimal128", "string8",
         "string16", "string256", "overflow", "struct", "array"]


def _bits(a):
    a = np.asarray(a)
    return a.view(f"uint{8 * a.dtype.itemsize}") if a.dtype.kind == "f" else a


def _assert_same(got, want):
    """Leaf by leaf: dtype, shape and every bit."""
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype
        gl, wl = jax.tree_util.tree_leaves(g), jax.tree_util.tree_leaves(w)
        assert len(gl) == len(wl)
        for a, b in zip(gl, wl):
            assert a.dtype == b.dtype and a.shape == b.shape, (g.dtype, a, b)
            assert np.array_equal(_bits(a), _bits(b)), g.dtype


def _on_device(vecs, idx, tally=None):
    return jax.jit(lambda vs, i: gather_vecs(jnp, vs, i, tally))(
        [jax.tree_util.tree_map(jnp.asarray, v) for v in vecs],
        jnp.asarray(idx))


def _indices(rng, n, how):
    if how == "shorter":
        return rng.integers(0, n, max(n // 3, 1), dtype=np.int32)
    if how == "equal":
        return rng.permutation(n).astype(np.int32)
    if how == "longer":
        return rng.integers(0, n, 2 * n + 5, dtype=np.int32)
    if how == "repeated":
        return np.full(n, n // 2, dtype=np.int32)
    if how == "reversed":
        return np.arange(n - 1, -1, -1, dtype=np.int64)
    raise AssertionError(how)


@pytest.mark.parametrize("how", ["shorter", "equal", "longer", "repeated",
                                 "reversed"])
@pytest.mark.parametrize("kind", KINDS)
def test_every_dtype_moves_bit_for_bit(kind, how):
    rng = np.random.default_rng(len(kind) * 31 + len(how))
    n = 300
    vecs = [_vec(rng, n, kind)]
    idx = _indices(rng, n, how)
    _assert_same(_on_device(vecs, idx), [v.gather(np, idx) for v in vecs])


@pytest.mark.parametrize("cap", [0, 1, 128, 200])
@pytest.mark.parametrize("columns", [1, 8, 9, 17, 40])
def test_column_counts_and_capacities(columns, cap):
    """Matrix and flag-word boundaries: 40 columns carry more than 32
    validity flags and more than eight word planes."""
    rng = np.random.default_rng(columns * 1000 + cap)
    vecs = [_vec(rng, cap, KINDS[i % len(KINDS)]) for i in range(columns)]
    idx = rng.permutation(cap).astype(np.int32)
    tally = GatherTally()
    _assert_same(_on_device(vecs, idx, tally),
                 [v.gather(np, idx) for v in vecs])
    arrays = []     # every row-aligned array, down the children
    for v in vecs:
        vec_map_arrays(v, arrays.append)
    assert tally.packed + tally.alone == len(arrays)


def test_out_of_range_and_negative_indices_mean_what_they_do_to_an_array():
    rng = np.random.default_rng(5)
    n = 64
    vecs = [_vec(rng, n, k) for k in ("int64", "bool", "string8", "int16")]
    idx = np.array([-1, -n, 0, n - 1, n, n + 7, -n - 3, 2 ** 31 - 1],
                   dtype=np.int32)
    dev = [jax.tree_util.tree_map(jnp.asarray, v) for v in vecs]
    want = jax.jit(lambda vs, i: [v.gather(jnp, i) for v in vs])(dev, idx)
    _assert_same(_on_device(vecs, idx), want)


def test_an_array_shared_by_two_vecs_moves_once():
    rng = np.random.default_rng(6)
    v = _vec(rng, 100, "int64")
    twin = Vec(T.LONG, v.data, v.validity)          # sum(x) beside avg(x)
    other = Vec(T.LONG, v.data, rng.random(100) < 0.5)
    idx = rng.permutation(100).astype(np.int32)
    tally = GatherTally()
    got = jax.jit(lambda a, b, c, i: gather_vecs(jnp, [a, a, b, c], i, tally))(
        *[jax.tree_util.tree_map(jnp.asarray, x) for x in (v, twin, other)],
        idx)
    _assert_same(got, [x.gather(np, idx) for x in (v, v, twin, other)])
    # inside the program `a` is one pair of arrays; `b` and `c` arrive as
    # arguments of their own
    assert (tally.packed, tally.alone) == (6, 0)


def test_floats_and_a_wide_byte_matrix_are_gathered_alone():
    """float64 is emulated on the chip and float32 patterns (NaN payloads,
    -0.0, subnormals) did not come back equal from it as words: both take
    the lone gather, as the 256-byte matrix does."""
    rng = np.random.default_rng(7)
    bits = np.array([0x7fc00000, 0x7fc00001, 0xffc12345, 0x7f800001,
                     0x80000000, 0x00000000, 0x00000001, 0xff800000],
                    dtype=np.uint32)
    f32 = Vec(T.FLOAT, np.tile(bits, 7)[:50].view(np.float32),
              np.ones(50, bool))
    vecs = [f32] + [_vec(rng, 50, k)
                    for k in ("string256", "float64", "string16")]
    idx = rng.permutation(50).astype(np.int32)
    tally = GatherTally()
    got = _on_device(vecs, idx, tally)
    _assert_same(got, [v.gather(np, idx) for v in vecs])
    # alone: the two floats' data and the 256-byte matrix
    assert (tally.packed, tally.matrices, tally.alone) == (7, 1, 3)


def test_compaction_keeps_rows_in_order_with_the_count():
    rng = np.random.default_rng(8)
    n = 256
    vecs = [_vec(rng, n, k) for k in ("int64", "string8", "decimal128")]
    keep = rng.random(n) < 0.6
    got, count = jax.jit(lambda vs, k: compact_vecs(jnp, vs, k))(
        [jax.tree_util.tree_map(jnp.asarray, v) for v in vecs], keep)
    live = int(count)
    assert live == int(keep.sum())
    want = [v.gather(np, np.flatnonzero(keep)) for v in vecs]
    _assert_same([v.slice_rows(0, live) for v in got], want)


# ---- TPC-H Q1's shapes: the tallies, the metrics, the lowered filter --------

def _q1_table(n=1000):
    rng = np.random.default_rng(1)

    def dec(hi, precision, scale):
        return pa.array([decimal.Decimal(int(v)).scaleb(-scale)
                         for v in rng.integers(0, hi, n)],
                        pa.decimal128(precision, scale))
    day0 = datetime.date(1998, 6, 1)
    return pa.table({
        "l_quantity": dec(5000, 12, 2),
        "l_extendedprice": dec(10 ** 7, 12, 2),
        "l_discount": dec(11, 12, 2),
        "l_tax": dec(9, 12, 2),
        "l_shipdate": pa.array([day0 + datetime.timedelta(int(d))
                                for d in rng.integers(0, 200, n)]),
        "l_returnflag": pa.array(rng.choice(["R", "A", "N"], n)),
        "l_linestatus": pa.array(rng.choice(["O", "F"], n)),
    })


def _q1(session, t):
    one = lit(decimal.Decimal("1.00"))
    disc_price = col("l_extendedprice") * (one - col("l_discount"))
    return (session.from_arrow(t)
            .filter(col("l_shipdate") <= lit(datetime.date(1998, 9, 2)))
            .select(col("l_returnflag"), col("l_linestatus"),
                    col("l_quantity"), col("l_extendedprice"),
                    col("l_discount"), disc_price.alias("disc_price"),
                    (disc_price * (one + col("l_tax"))).alias("charge"))
            .group_by("l_returnflag", "l_linestatus")
            .agg(sum_qty=Sum(col("l_quantity")),
                 sum_base_price=Sum(col("l_extendedprice")),
                 sum_disc_price=Sum(col("disc_price")),
                 sum_charge=Sum(col("charge")),
                 avg_qty=Average(col("l_quantity")),
                 avg_price=Average(col("l_extendedprice")),
                 avg_disc=Average(col("l_discount")),
                 count_order=Count())
            .sort("l_returnflag", "l_linestatus"))


def _find(node, name):
    if node.name == name:
        return node
    for c in node.children:
        found = _find(c, name)
        if found is not None:
            return found
    return None


def test_q1_batch_packs_sixteen_arrays_into_two_matrices():
    batch = batch_from_arrow(_q1_table())
    vecs = [Vec.from_column(c) for c in batch.columns]
    tally = GatherTally()
    jax.make_jaxpr(lambda vs, i: gather_vecs(jnp, vs, i, tally))(
        vecs, jnp.arange(batch.capacity, dtype=jnp.int32))
    assert (tally.packed, tally.matrices, tally.alone) == (16, 2, 0)


def test_q1_operators_report_every_array_packed():
    session = TpuSession({})
    t = _q1_table()
    got = _q1(session, t).collect()
    want = _q1(TpuSession({}), t).collect_cpu()
    assert got.to_pylist() == want.to_pylist()
    for name, packed in (("TpuFilterExec", 16), ("TpuHashAggregateExec", None),
                         ("TpuSortExec", 22)):
        snap = _find(session.last_plan, name).metrics.snapshot()
        assert snap[M.NUM_SINGLE_GATHER_ARRAYS] == 0, name
        if packed is None:
            assert snap[M.NUM_PACKED_GATHER_ARRAYS] > 16, name
        else:
            assert snap[M.NUM_PACKED_GATHER_ARRAYS] == packed, name


def _gathers_of(jaxpr, length):
    """Gather equations, down every sub-jaxpr, that produce `length` rows."""
    found = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "gather" and \
                length in eqn.outvars[0].aval.shape:
            found += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _gathers_of(sub, length)
    return found


def test_filter_kernel_lowers_to_a_few_gathers():
    t = _q1_table()
    session = TpuSession({})
    session.from_arrow(t).filter(
        col("l_shipdate") <= lit(datetime.date(1998, 9, 2))).collect()
    node = _find(session.last_plan, "TpuFilterExec")
    batch = batch_from_arrow(t)
    jaxpr = jax.make_jaxpr(node._kernel.fn)(batch).jaxpr
    assert 1 <= _gathers_of(jaxpr, batch.capacity) <= 4
