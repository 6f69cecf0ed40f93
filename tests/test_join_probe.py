"""The equi-join's probe (exec/joins.py `_probe_counts` / `_expand_join`):
positions found by counting, phase 2 fed by phase 1's arrays.

Four guards: the lowered programs' loop and sort counts (no `while`: the
searches are a merge rank and a slot map; the recompute of the probe inside
the expand must not come back), `counts`/`lo`/`order` value for value against
a numpy statement of the two-search probe they replaced, the merge rank and
the expand's slot map against `np.searchsorted`, and all seven join types end
to end against pandas on the same data."""

import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

import jax
import jax.numpy as jnp

from spark_rapids_tpu.columnar import batch_from_arrow
from spark_rapids_tpu.columnar.batch import empty_batch
from spark_rapids_tpu.exec import joins
from spark_rapids_tpu.exec.base import batch_vecs
from spark_rapids_tpu.exec.joins import (_expand_join, _merge_rank,
                                         _probe_counts, _slot_counts)
from spark_rapids_tpu.expr.hashing import hash_vecs
from spark_rapids_tpu.ops.rowops import prefix_sum, slot_runs
from spark_rapids_tpu.plugin import TpuSession

INT32_MAX = np.iinfo(np.int32).max
ALL_TYPES = ["inner", "left", "right", "full", "semi", "anti", "existence"]


def _keys(values, nulls=()):
    return pa.array([None if i in nulls else int(v)
                     for i, v in enumerate(values)], pa.int64())


def _batch(values, nulls=(), payload="v"):
    n = len(values)
    return batch_from_arrow(pa.table({
        "k": _keys(values, nulls),
        payload: pa.array(np.arange(n, dtype=np.int32))}))


# ---- loop and sort counts of the lowered programs --------------------------

def _lowered(fn, static_argnums, *args) -> str:
    return jax.jit(fn, static_argnums=static_argnums).lower(*args).as_text()


@pytest.fixture(scope="module")
def lowering_inputs():
    rng = np.random.default_rng(0)
    probe = _batch(rng.integers(0, 50, 1000), nulls={3, 17}, payload="a")
    build = _batch(rng.integers(0, 50, 100), nulls={5}, payload="b")
    return probe, build, _probe_counts.fn(probe, build, (0,), (0,))


def test_probe_lowers_to_one_search(lowering_inputs):
    """The range starts' one search is a merge now: no loop, and four sorts,
    two for the build side's two-key stable_lexsort and two for the merge
    (the probe and build hashes together, then back to probe order), at a
    build capacity of 128, the least a batch has."""
    probe, build, _ = lowering_inputs
    text = _lowered(_probe_counts.fn, _probe_counts.static_argnums,
                    probe, build, (0,), (0,))
    assert build.capacity == 128
    assert text.count("stablehlo.while") == 0
    assert text.count("stablehlo.sort") == 4


@pytest.mark.parametrize("how", ["inner", "left", "full", "semi"])
def test_expand_does_not_probe_again(lowering_inputs, how):
    probe, build, phase1 = lowering_inputs
    text = _lowered(_expand_join.fn, _expand_join.static_argnums,
                    probe, build, *phase1, (0,), (0,), 4096, how, None, False)
    # its own slot map (marks and a prefix sum, no loop) and its own
    # compaction, nothing of phase 1: a second copy of the probe would add
    # the build's two sorts and the merge's two
    assert text.count("stablehlo.while") == 0
    assert text.count("stablehlo.sort") == 1


# ---- counts / lo / order against the two-search probe, in numpy ------------

def _oracle(probe, build, hash_rows):
    """The probe as it was: int64 hashes, invalid build rows exiled to 2**62,
    stable argsort, searchsorted left and right."""
    pk, bk = batch_vecs(probe)[0], batch_vecs(build)[0]
    pvalid = np.asarray(pk.validity & probe.row_mask())
    bvalid = np.asarray(bk.validity & build.row_mask())
    ph = np.asarray(hash_rows(jnp, [pk])).astype(np.int64)
    bh = np.where(bvalid, np.asarray(hash_rows(jnp, [bk])).astype(np.int64),
                  np.int64(2 ** 62))
    order = np.argsort(bh, kind="stable")
    bh_sorted = bh[order]
    lo = np.searchsorted(bh_sorted, ph, side="left")
    hi = np.searchsorted(bh_sorted, ph, side="right")
    return np.where(pvalid, hi - lo, 0), lo, order, pvalid, bvalid


def _hash_constant(xp, vecs, seed=42):
    return xp.full(vecs[0].validity.shape, 12345, dtype=np.int32)


def _hash_with_max(xp, vecs, seed=42):
    """Keys divisible by 3 hash to INT32_MAX, the word the exiled build rows
    sit under; the rest keep their murmur3."""
    return xp.where(vecs[0].data % 3 == 0, np.int32(INT32_MAX),
                    hash_vecs(xp, vecs, seed))


def _case(name):
    rng = np.random.default_rng(11)
    if name == "duplicate_build_keys":
        return _batch(rng.integers(0, 12, 300)), _batch(rng.integers(0, 8, 90))
    if name == "null_keys_both_sides":
        return (_batch(rng.integers(0, 40, 300), nulls=set(range(0, 300, 7))),
                _batch(rng.integers(0, 40, 90), nulls=set(range(0, 90, 4))))
    if name == "all_build_keys_null":
        return (_batch(rng.integers(0, 40, 300)),
                _batch(rng.integers(0, 40, 20), nulls=set(range(20))))
    if name == "padding_rows":  # 130 and 17 rows in capacities padded beyond
        return _batch(rng.integers(0, 9, 130)), _batch(rng.integers(0, 9, 17))
    if name == "empty_build":
        return (_batch(rng.integers(0, 9, 50)),
                empty_batch(_batch([1]).schema, 1))
    if name == "build_of_one_row":
        return _batch(rng.integers(0, 3, 50)), _batch([1])
    if name == "build_of_one_null_row":
        return _batch(rng.integers(0, 3, 50)), _batch([1], nulls={0})
    if name in ("every_hash_equal", "hash_int32_max"):
        return (_batch(rng.integers(0, 10, 200), nulls={1, 50}),
                _batch(rng.integers(0, 10, 40), nulls={0, 7, 39}))
    if name == "probe_capacity_below_build":  # 20 probes, 300 build rows
        return (_batch(rng.integers(0, 60, 20), nulls={4}),
                _batch(rng.integers(0, 60, 300), nulls={0, 299}))
    if name == "every_probe_ties_a_build_hash":
        build = rng.integers(0, 1000, 64)
        return (_batch(rng.choice(build, 400)), _batch(build))
    if name == "all_probe_keys_null":
        return (_batch(rng.integers(0, 9, 40), nulls=set(range(40))),
                _batch(rng.integers(0, 9, 30)))
    if name == "hash_int32_max_no_valid_build_hit":
        # probes hash to INT32_MAX (0, 3, 6, 9); the only build rows under
        # that word are the exiled ones (null keys)
        return (_batch(rng.integers(0, 10, 200)),
                _batch([1, 2, 4, 5, 3, 6], nulls={4, 5}))
    raise AssertionError(name)


_HASHES = {"every_hash_equal": _hash_constant,
           "hash_int32_max": _hash_with_max,
           "hash_int32_max_no_valid_build_hit": _hash_with_max}


@pytest.mark.parametrize("name", [
    "duplicate_build_keys", "null_keys_both_sides", "all_build_keys_null",
    "padding_rows", "empty_build", "build_of_one_row",
    "build_of_one_null_row", "every_hash_equal", "hash_int32_max",
    "hash_int32_max_no_valid_build_hit", "probe_capacity_below_build",
    "every_probe_ties_a_build_hash", "all_probe_keys_null"])
def test_phase1_equals_two_search_oracle(monkeypatch, name):
    probe, build = _case(name)
    hash_rows = _HASHES.get(name, hash_vecs)
    monkeypatch.setattr(joins, "hash_vecs", hash_rows)
    counts, lo, order, pvalid, bvalid = (
        np.asarray(x) for x in _probe_counts.fn(probe, build, (0,), (0,)))
    want = _oracle(probe, build, hash_rows)
    for got, exp, what in zip((counts, lo, order, pvalid, bvalid), want,
                              ("counts", "lo", "order", "pvalid", "bvalid")):
        np.testing.assert_array_equal(got, exp, err_msg=what)
    assert counts.dtype == lo.dtype == order.dtype == np.int32
    if name == "padding_rows":
        assert probe.capacity > 130 and build.capacity > 17
    if name == "every_hash_equal":  # the whole valid build is one run
        assert set(counts[pvalid]) == {int(bvalid.sum())}
    if name == "probe_capacity_below_build":
        assert probe.capacity < build.capacity
    if name == "every_probe_ties_a_build_hash":
        assert pvalid.sum() == 400 and (counts[pvalid] > 0).all()


# ---- the merge rank and the expand's slot map against np.searchsorted ------

def _rank_case(name):
    rng = np.random.default_rng(40)
    top = np.int64(INT32_MAX)
    if name == "ties":
        k = np.sort(rng.integers(0, 50, 200))
        return rng.integers(-5, 55, 700), k
    if name == "int32_max_both_sides":
        k = np.concatenate([np.sort(rng.integers(0, 99, 60)),
                            np.full(40, top)])
        return np.where(rng.random(300) < 0.3, top,
                        rng.integers(0, 120, 300)), k
    if name == "int32_min_both_sides":
        low = -top - 1
        k = np.concatenate([np.full(5, low), np.sort(rng.integers(0, 9, 20))])
        return np.where(rng.random(90) < 0.5, low, rng.integers(-3, 12, 90)), k
    if name == "fewer_queries_than_keys":
        return rng.integers(0, 1000, 7), np.sort(rng.integers(0, 1000, 900))
    if name == "one_key":
        return rng.integers(0, 3, 50), np.array([1])
    if name == "every_key_equal":
        return rng.integers(5, 8, 130), np.full(64, 6)
    if name == "full_range_hashes":
        k = np.sort(rng.integers(-top - 1, top + 1, 4096))
        q = rng.integers(-top - 1, top + 1, 20000)
        q[::3] = rng.choice(k, q[::3].shape[0])
        return q, k
    raise AssertionError(name)


@pytest.mark.parametrize("name", [
    "ties", "int32_max_both_sides", "int32_min_both_sides",
    "fewer_queries_than_keys", "one_key", "every_key_equal",
    "full_range_hashes"])
def test_merge_rank_equals_searchsorted_left(name):
    q, k = (np.asarray(a, np.int32) for a in _rank_case(name))
    got = jax.jit(_merge_rank)(jnp.asarray(q), jnp.asarray(k))
    assert got.dtype == np.int32 and got.shape == q.shape
    np.testing.assert_array_equal(np.asarray(got),
                                  np.searchsorted(k, q, side="left"))


def _slot_case(name):
    rng = np.random.default_rng(41)
    mid = rng.integers(0, 4, 300)
    if name == "zero_rows_at_both_ends_total_below_cap":
        return np.concatenate([np.zeros(9), mid, np.zeros(13)]), 1024
    if name == "zero_rows_at_both_ends_total_above_cap":
        return np.concatenate([np.zeros(9), mid, np.zeros(13)]), 200
    if name == "total_equals_cap":
        return np.array([0, 3, 0, 0, 5, 0]), 8
    if name == "every_row_zero":
        return np.zeros(50), 16
    if name == "one_row_fills_past_cap":
        return np.array([0, 0, 70, 0]), 64
    if name == "cap_no_multiple_of_a_lane":
        return rng.integers(0, 3, 5000), 3001
    raise AssertionError(name)


@pytest.mark.parametrize("name", [
    "zero_rows_at_both_ends_total_below_cap",
    "zero_rows_at_both_ends_total_above_cap", "total_equals_cap",
    "every_row_zero", "one_row_fills_past_cap", "cap_no_multiple_of_a_lane"])
def test_expand_slot_map_equals_searchsorted_right(name):
    """The expand's probe row for every output slot, as `_expand_join`
    makes it: the slot counts' prefix sum, then `slot_runs`."""
    counts, out_cap = _slot_case(name)
    counts = np.asarray(counts, np.int32)
    offsets = np.cumsum(counts)
    got = jax.jit(lambda c: slot_runs(prefix_sum(c), out_cap))(counts)
    want = np.clip(np.searchsorted(offsets, np.arange(out_cap), "right"),
                   0, counts.shape[0] - 1)
    np.testing.assert_array_equal(np.asarray(got), want)


def _searching_expand(monkeypatch):
    """`_expand_join` as it was: a flat cumsum and the scan search."""
    monkeypatch.setattr(joins, "prefix_sum", jnp.cumsum)
    monkeypatch.setattr(joins, "slot_runs", lambda offsets, cap: jnp.clip(
        jnp.searchsorted(offsets, jnp.arange(cap, dtype=np.int32),
                         side="right").astype(np.int32),
        0, offsets.shape[0] - 1))


@pytest.mark.parametrize("how", ALL_TYPES)
def test_expand_equals_the_searching_expand(monkeypatch, lowering_inputs,
                                            how):
    """Every output array, its order and its count, against the expand with
    the search put back, on the probe's own phase-1 arrays."""
    probe, build, phase1 = lowering_inputs
    total = int(jnp.sum(_slot_counts(jnp, phase1[0], probe.row_mask(), how)))
    out_cap = max(total, probe.capacity)

    def expand():
        out = _expand_join.fn(probe, build, *phase1, (0,), (0,), out_cap,
                              how, None, False)
        return [np.asarray(x) for x in jax.tree_util.tree_leaves(out[:3])]

    got = expand()
    _searching_expand(monkeypatch)
    want = expand()
    assert len(got) == len(want)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)


# ---- seven join types end to end against pandas ----------------------------

def _tables():
    rng = np.random.default_rng(5)
    lt = pa.table({"k": _keys(rng.integers(0, 14, 260),
                              nulls=set(range(0, 260, 9))),
                   "a": pa.array(np.arange(260, dtype=np.int32))})
    rt = pa.table({"k": _keys(rng.integers(4, 20, 70),
                              nulls=set(range(0, 70, 6))),
                   "b": pa.array(np.arange(70, dtype=np.int32))})
    return lt, rt


def _pandas_join(lt: pa.Table, rt: pa.Table, how: str) -> list:
    """Spark's equi-join on `k` through pandas: a null key matches nothing
    (pandas would pair NA with NA, so null-key rows are merged apart)."""
    l = lt.to_pandas(types_mapper=pd.ArrowDtype).rename(columns={"k": "lk"})
    r = rt.to_pandas(types_mapper=pd.ArrowDtype).rename(columns={"k": "rk"})
    ln, rn = l[l.lk.notna()], r[r.rk.notna()]
    if how in ("semi", "anti", "existence"):
        exists = l.lk.notna() & l.lk.isin(rn.rk)
        if how == "existence":
            out = l.assign(exists=exists.astype(bool))
        else:
            out = l[exists if how == "semi" else ~exists]
    else:
        out = ln.merge(rn, how={"full": "outer"}.get(how, how),
                       left_on="lk", right_on="rk")
        apart = []
        if how in ("left", "full"):
            apart.append(l[l.lk.isna()])
        if how in ("right", "full"):
            apart.append(r[r.rk.isna()])
        out = pd.concat([out] + apart)[["lk", "a", "rk", "b"]]
    return _rows(out.astype(object).where(out.notna(), None)
                 .itertuples(index=False))


def _rows(tuples) -> list:
    key = lambda t: tuple((v is None, v) for v in t)
    return sorted((tuple(None if v is None else int(v) for v in t)
                   for t in tuples), key=key)


@pytest.fixture
def fresh_programs():
    """Programs compiled while the row hash is patched must not outlive the
    test (nor may a cached true-hash program serve it)."""
    from spark_rapids_tpu.compile import CompileService
    CompileService.reset()
    yield
    CompileService.reset()


def _engine_rows(how):
    lt, rt = _tables()
    s = TpuSession({"spark.rapids.sql.enabled": True,
                    "spark.rapids.sql.explain": "NONE"})
    got = s.from_arrow(lt).join(s.from_arrow(rt), on="k", how=how).collect()
    plan = repr(s.last_plan)
    assert "HashJoin" in plan and "NestedLoop" not in plan, plan
    return _rows(zip(*(c.to_pylist() for c in got.columns))), \
        _pandas_join(lt, rt, how)


@pytest.mark.parametrize("how", ALL_TYPES)
def test_join_types_equal_pandas(how):
    got, want = _engine_rows(how)
    assert got == want


@pytest.mark.parametrize("how", ALL_TYPES)
def test_join_types_equal_pandas_when_every_hash_collides(
        monkeypatch, fresh_programs, how):
    """One hash for every key: each probe row's candidate range is the whole
    valid build side, and the equality re-check alone decides the pairs."""
    monkeypatch.setattr(joins, "hash_vecs", _hash_constant)
    got, want = _engine_rows(how)
    assert got == want
