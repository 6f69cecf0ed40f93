"""The grouped aggregate's integer sums and counts over sorted segments
(ops/rowops.py `prefix_sum`, `segment_ends`, `sorted_segment_sum`,
`SortedSegments`; exec/aggregate.py is their caller): a prefix sum and a
difference at the group ends where a scatter-add was.

Three guards: the helpers value for value against `np.add.at` and
`np.cumsum`; the lowered aggregate kernels (TPC-H Q1's has no scatter that
adds 64-bit integers and no reduce-window, a double sum keeps its scatter,
and the two operator metrics say so); every integer-routed aggregate in
complete, partial -> merge -> final and global form against the CPU engine."""

import decimal
import re

import numpy as np
import pyarrow as pa
import pytest

import jax
import jax.numpy as jnp

from spark_rapids_tpu.columnar import batch_from_arrow
from spark_rapids_tpu.expr import (Average, BitXorAgg, BoolAnd, BoolOr,
                                   CollectList, CollectSet, Count, CountIf,
                                   Max, Min, Sum, col)
from spark_rapids_tpu.ops.rowops import (SortedSegments, compaction_order,
                                         prefix_sum, segment_ends,
                                         sorted_segment_sum)
from spark_rapids_tpu.plugin import TpuSession
from spark_rapids_tpu.utils import metrics as M


# ---- the prefix sum against np.cumsum ---------------------------------------

@pytest.mark.parametrize("dtype", [np.int32, np.int64])
@pytest.mark.parametrize("shape", [(1,), (7,), (127,), (128,), (129,),
                                   (4096,), (16385,), ((1 << 17) + 3,),
                                   (3, 1), (3, 129), (5, 16385)],
                         ids=lambda s: "x".join(map(str, s)))
def test_prefix_sum_equals_cumsum(shape, dtype):
    info = np.iinfo(dtype)
    # full-range values: the running sum wraps, as np.cumsum's does
    x = np.random.default_rng(sum(shape)).integers(
        info.min, info.max, shape, dtype=dtype, endpoint=True)
    got = jax.jit(prefix_sum)(x)
    assert got.dtype == dtype and got.shape == shape
    with np.errstate(over="ignore"):
        assert np.array_equal(np.asarray(got), np.cumsum(x, axis=-1,
                                                         dtype=dtype))


# ---- the helper against an np.add.at oracle ----------------------------------

def _layout(cap, sizes):
    """Rows sorted by group as the aggregate kernel holds them: groups of
    `sizes` rows first, the dead rows after; gid as `group_ids_from_sorted`
    returns it, and the ends from the compaction order of the starts."""
    live = int(sum(sizes))
    assert live <= cap
    gid = np.full(cap, cap - 1, np.int32)
    gid[:live] = np.repeat(np.arange(len(sizes), dtype=np.int32), sizes)
    starts = np.zeros(cap, bool)
    starts[np.cumsum([0] + list(sizes))[:-1][np.asarray(sizes) > 0]] = True
    row_mask = np.arange(cap) < live
    ng = int(np.count_nonzero(np.asarray(sizes) > 0))
    ends = segment_ends(compaction_order(jnp, jnp.asarray(starts)),
                        jnp.int32(ng), jnp.int32(live))
    return gid, row_mask, ends, ng


def _sizes(name, cap):
    rng = np.random.default_rng(cap)
    if name == "one_group":
        return [cap]
    if name == "every_row_its_own_group":
        return [1] * cap
    if name == "dead_tail":
        return [s for s in (cap // 3, cap // 5, 1) if s] or [0]
    if name == "all_rows_dead":
        return [0]
    if name == "one_group_of_more_than_half":
        return [cap // 2 + 1] + [1] * (cap // 4)
    if name == "random":
        cuts = np.sort(rng.integers(0, cap, min(cap, 40)))
        return [int(s) for s in np.diff(np.concatenate(([0], cuts))) if s]
    raise AssertionError(name)


_CAPS = [1, 127, 128, 129, (1 << 17) + 3]
_LAYOUTS = ["one_group", "every_row_its_own_group", "dead_tail",
            "all_rows_dead", "one_group_of_more_than_half", "random"]


def _oracle(contrib, gid, cap):
    """np.add.at along the last axis, in the contribution's own dtype."""
    want = np.zeros(contrib.shape[:-1] + (cap,), contrib.dtype)
    for row_out, row_in in zip(want.reshape(-1, cap),
                               contrib.reshape(-1, contrib.shape[-1])):
        np.add.at(row_out, gid, row_in)
    return want


@pytest.mark.parametrize("cap", _CAPS)
@pytest.mark.parametrize("name", _LAYOUTS)
def test_sorted_segment_sum_equals_add_at(name, cap):
    gid, row_mask, ends, ng = _layout(cap, _sizes(name, cap))
    assert np.all(np.diff(np.asarray(ends)) >= 0)       # sorted, as promised
    rng = np.random.default_rng(cap + len(name))
    nulls = rng.random(cap) < 0.2                       # nulls inside groups
    valid = row_mask & ~nulls
    sum_fn = jax.jit(sorted_segment_sum)
    # int64 values, (n,) and (K, n); dead and null rows contribute zero
    v64 = np.where(valid, rng.integers(-1 << 43, 1 << 43, (3, cap)), 0)
    for contrib in (v64[0], v64):
        got = sum_fn(contrib, ends, jnp.int32(ng))
        assert got.dtype == np.int64
        assert np.array_equal(np.asarray(got), _oracle(contrib, gid, cap))
    # int32 counts
    flags = valid.astype(np.int32)
    got = sum_fn(flags, ends, jnp.int32(ng))
    assert got.dtype == np.int32
    assert np.array_equal(np.asarray(got), _oracle(flags, gid, cap))


def test_running_prefix_wraps_and_every_segment_is_exact():
    """Twelve groups of ten rows of 2^59: every group's sum is 10 * 2^59 <
    2^63, the running prefix passes 2^63 in the second group and wraps
    seven times; the differences are the true sums all the same."""
    cap, sizes = 128, [10] * 12
    gid, row_mask, ends, ng = _layout(cap, sizes)
    contrib = np.where(row_mask, np.int64(1) << 59, 0)
    with np.errstate(over="ignore"):
        assert np.cumsum(contrib)[19] < 0                # it does wrap
    got = np.asarray(jax.jit(sorted_segment_sum)(contrib, ends,
                                                 jnp.int32(ng)))
    want = _oracle(contrib, gid, cap)
    assert np.array_equal(got, want)
    assert got[:12].tolist() == [10 * (1 << 59)] * 12 and not got[12:].any()
    # and where the segments' own sums wrap, they wrap as np.add.at's do
    big = np.where(row_mask, np.iinfo(np.int64).max, 0)
    with np.errstate(over="ignore"):
        assert np.array_equal(
            np.asarray(jax.jit(sorted_segment_sum)(big, ends, jnp.int32(ng))),
            _oracle(big, gid, cap))


@pytest.mark.parametrize("keyed", [True, False], ids=["keyed", "global"])
def test_sorted_segments_routes_by_dtype(keyed):
    cap = 300
    sizes = [120, 1, 60] if keyed else [181]
    gid, row_mask, _, ng = _layout(cap, sizes)
    starts = np.zeros(cap, bool)
    starts[np.cumsum([0] + sizes)[:-1]] = True
    order = compaction_order(jnp, jnp.asarray(starts)) if keyed else None
    if not keyed:
        gid = np.zeros(cap, np.int32)
    segs = SortedSegments(jnp, jnp.asarray(gid), jnp.int32(ng),
                          jnp.asarray(row_mask), order)
    rng = np.random.default_rng(3)
    ints = np.where(row_mask, rng.integers(-99, 99, cap), 0)
    wide = np.where(row_mask[:, None], rng.integers(0, 2, (cap, 5)), 0)
    floats = np.where(row_mask, rng.random(cap), 0.0)
    assert np.array_equal(np.asarray(segs.sum(jnp.asarray(ints))),
                          _oracle(ints, gid, cap))
    assert np.array_equal(np.asarray(segs.sum(jnp.asarray(wide))),
                          _oracle(wide.T, gid, cap).T)    # rows on axis 0
    counts = segs.count(jnp.asarray(row_mask))
    assert counts.dtype == np.int64
    assert np.array_equal(np.asarray(counts),
                          _oracle(row_mask.astype(np.int64), gid, cap))
    assert (segs.prefix_routed, segs.scattered) == (3, 0)
    # several contributions over the same rows, one stacked reduction
    both = segs.sums(jnp.asarray(ints), jnp.asarray(row_mask))
    assert [t.dtype for t in both] == [np.int64, np.int64]
    assert np.array_equal(np.asarray(both[0]), _oracle(ints, gid, cap))
    assert np.array_equal(np.asarray(both[1]), np.asarray(counts))
    assert (segs.prefix_routed, segs.scattered) == (5, 0)
    got = np.asarray(segs.sum(jnp.asarray(floats)))
    assert np.allclose(got, _oracle(floats, gid, cap), rtol=1e-12)
    segs.minmax("max", jnp.asarray(ints))
    assert (segs.prefix_routed, segs.scattered) == (5, 2)


# ---- what the aggregate kernels lower to -------------------------------------

def _dec(values, precision, scale):
    q = decimal.Decimal(1).scaleb(-scale)
    return pa.array([None if v is None else decimal.Decimal(v).quantize(q)
                     for v in values], pa.decimal128(precision, scale))


def _q1_table(n=500):
    rng = np.random.default_rng(1)
    money = lambda hi: [str(round(float(x), 2)) for x in rng.uniform(0, hi, n)]
    return pa.table({
        "l_returnflag": pa.array(rng.choice(["R", "A", "N"], n)),
        "l_linestatus": pa.array(rng.choice(["O", "F"], n)),
        "l_quantity": _dec(money(50), 12, 2),
        "l_extendedprice": _dec(money(90000), 12, 2),
        "l_discount": _dec(money(0.1), 12, 2),
        "disc_price": _dec(money(90000), 26, 4),
        "charge": _dec(money(90000), 38, 6),
    })


def _q1_aggregate(session, t):
    """TPC-H Q1's aggregate as `benchmark/queries/q1_pricing_summary.py`
    writes it: two string keys, four 128-bit sums, three decimal averages,
    count(*)."""
    return (session.from_arrow(t)
            .group_by("l_returnflag", "l_linestatus")
            .agg(sum_qty=Sum(col("l_quantity")),
                 sum_base_price=Sum(col("l_extendedprice")),
                 sum_disc_price=Sum(col("disc_price")),
                 sum_charge=Sum(col("charge")),
                 avg_qty=Average(col("l_quantity")),
                 avg_price=Average(col("l_extendedprice")),
                 avg_disc=Average(col("l_discount")),
                 count_order=Count()))


def _aggregate_exec(node):
    if node.name == "TpuHashAggregateExec":
        return node
    for c in node.children:
        found = _aggregate_exec(c)
        if found is not None:
            return found
    return None


def _run_and_lower(df_of, t):
    """(answer, the aggregate's two route metrics, the StableHLO of its
    kernel on the table's batch)."""
    session = TpuSession({})
    got = df_of(session, t).collect()
    agg = _aggregate_exec(session.last_plan)
    assert agg.mode == "complete"
    snap = agg.metrics.snapshot()
    text = jax.jit(agg._kernel.fn).lower(batch_from_arrow(t)).as_text()
    return got, (snap[M.NUM_PREFIX_REDUCTIONS],
                 snap[M.NUM_SCATTER_REDUCTIONS]), text


_SCATTER = re.compile(r'"stablehlo\.scatter"\(.*?\}\) :', re.S)


def _adding_scatters(text, dtype):
    """Scatters of the program whose update computation adds `dtype`s."""
    return [m.group(0) for m in _SCATTER.finditer(text)
            if re.search(r"stablehlo\.add[^\n]*tensor<%s>" % dtype,
                         m.group(0))]


def test_q1_kernel_lowers_without_an_integer_scatter_add():
    got, routes, text = _run_and_lower(_q1_aggregate, _q1_table())
    assert _adding_scatters(text, "i64") == []
    assert _adding_scatters(text, "i32") == []
    assert "reduce_window" not in text
    # four sums and three averages of three 43-bit chunks and a count
    # (one stacked reduction each), and count(*); nothing scatters
    assert routes == (7 * 4 + 1, 0)
    # and its answer is the CPU engine's
    session = TpuSession({})
    keys = ("l_returnflag", "l_linestatus")
    assert _rows(got, keys) == _rows(
        _q1_aggregate(session, _q1_table()).collect_cpu(), keys)


def test_double_sum_keeps_its_scatter():
    rng = np.random.default_rng(2)
    t = pa.table({"k": pa.array(rng.integers(0, 9, 400)),
                  "x": pa.array(rng.random(400))})
    _, routes, text = _run_and_lower(
        lambda s, t: s.from_arrow(t).group_by("k").agg(sx=Sum(col("x"))), t)
    assert len(_adding_scatters(text, "f64")) == 1
    assert _adding_scatters(text, "i64") == []
    assert routes == (1, 1)          # the count of valid rows, the sum


def test_min_max_keep_their_scatters():
    rng = np.random.default_rng(4)
    t = pa.table({"k": pa.array(rng.integers(0, 9, 400)),
                  "x": pa.array(rng.integers(-50, 50, 400))})
    _, routes, text = _run_and_lower(
        lambda s, t: s.from_arrow(t).group_by("k").agg(lo=Min(col("x")),
                                                       hi=Max(col("x"))), t)
    assert len(_SCATTER.findall(text)) == 2
    assert _adding_scatters(text, "i64") == []
    assert routes == (2, 2)          # two counts of valid rows, min and max


# ---- every integer-routed aggregate against the CPU engine --------------------

def _mixed_table(n=700, cap_pad=True):
    """Null keys, null values, one group of more than half the rows; 700
    rows pad to a larger capacity, so the batch has a dead tail."""
    rng = np.random.default_rng(9)
    k = rng.integers(0, 12, n)
    k[rng.random(n) < 0.55] = 3                         # the big group
    nulls = lambda p: rng.random(n) < p
    ints = rng.integers(-10 ** 6, 10 ** 6, n)
    return pa.table({
        "k": pa.array(k, mask=nulls(0.05)),
        "s": pa.array(rng.choice(["a", "bb", "ccc"], n), mask=nulls(0.05)),
        "i": pa.array(ints, mask=nulls(0.2)),
        "j": pa.array(rng.integers(-2 ** 31, 2 ** 31 - 1, n).astype(np.int32),
                      mask=nulls(0.2)),
        "b": pa.array(rng.random(n) < 0.5, mask=nulls(0.2)),
        "d": _dec([None if m else str(v / 100) for v, m in
                   zip(ints, nulls(0.2))], 12, 2),
        "w": _dec([None if m else str(v * 10 ** 9) for v, m in
                   zip(ints, nulls(0.2))], 38, 6),
        "name": pa.array(["n%03d" % v for v in rng.integers(0, 500, n)],
                         mask=nulls(0.2)),
    })


_AGGS = dict(
    cnt=lambda: Count(), cnt_i=lambda: Count(col("i")),
    sum_i=lambda: Sum(col("i")), sum_d=lambda: Sum(col("d")),
    sum_w=lambda: Sum(col("w")), avg_d=lambda: Average(col("d")),
    avg_w=lambda: Average(col("w")), hits=lambda: CountIf(col("b")),
    lo_i=lambda: Min(col("i")), hi_w=lambda: Max(col("w")),
    lo_name=lambda: Min(col("name")), hi_name=lambda: Max(col("name")),
    every=lambda: BoolAnd(col("b")), some=lambda: BoolOr(col("b")),
    parity=lambda: BitXorAgg(col("j")))


def _rows(table, keys):
    rows = table.to_pylist()
    return sorted(rows, key=lambda r: tuple(
        (r[k] is None, r[k]) for k in keys))


@pytest.mark.parametrize("keys", [("k", "s"), ()], ids=["grouped", "global"])
@pytest.mark.parametrize("form", ["complete", "partial_merge_final"])
def test_integer_routed_aggregates_equal_the_cpu_engine(form, keys):
    t = _mixed_table()
    # the second form cuts the input into batches of 256 rows, so the
    # aggregate runs raw -> partial per batch, merges, and finishes
    conf = {} if form == "complete" else \
        {"spark.rapids.sql.batchSizeRows": 256}
    session = TpuSession(conf)

    def df(s):
        return s.from_arrow(t).group_by(*keys).agg(
            **{name: make() for name, make in _AGGS.items()})

    got = df(session).collect()
    agg = _aggregate_exec(session.last_plan)
    assert agg is not None
    want = df(TpuSession({})).collect_cpu()
    assert got.schema.names == want.schema.names
    assert _rows(got, keys) == _rows(want, keys)
    batches = agg.children[0].metrics.snapshot()[M.NUM_OUTPUT_BATCHES]
    assert batches == (1 if form == "complete" else 3)
    assert agg.metrics.snapshot()[M.NUM_PREFIX_REDUCTIONS] > 0


def test_single_pass_kernels_count_by_the_prefix_route():
    """collect_list / collect_set: the per-group counts that size and fill
    the lists, over rows sorted a second time inside their groups."""
    t = _mixed_table()

    def df(s):
        return s.from_arrow(t).group_by("k").agg(
            items=CollectList(col("i")), distinct=CollectSet(col("j")),
            n=Count(col("i")))

    def rows(table):
        return _rows(pa.Table.from_pylist(
            [{k: sorted(v) if isinstance(v, list) else v
              for k, v in r.items()} for r in table.to_pylist()]), ("k",))

    session = TpuSession({})
    got = df(session).collect()
    assert rows(got) == rows(df(TpuSession({})).collect_cpu())
    snap = _aggregate_exec(session.last_plan).metrics.snapshot()
    # the phase-2 kernel: two list sizes, count(i) and its has; the two
    # ranks' bases are min-scatters
    assert (snap[M.NUM_PREFIX_REDUCTIONS],
            snap[M.NUM_SCATTER_REDUCTIONS]) == (4, 2)
