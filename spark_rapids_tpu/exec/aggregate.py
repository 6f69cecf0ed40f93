"""Hash aggregate exec (reference `aggregate.scala`: GpuHashAggregateExec `:1454`,
GpuHashAggregateIterator `:497` with merge passes and sort-based fallback).

TPU lowering (ARCHITECTURE.md #4): grouping is sort-by-keys + boundary detection +
segmented reductions — the idiomatic XLA mapping of cudf's hash groupby. A "complete"
mode aggregates a coalesced input in one kernel; partial/final modes carry
(sum,count)-style buffers across the exchange exactly like the reference's partial
aggregates. Input batches are merged with repeated partial aggregation when they
exceed the batch target, which is the reference's merge-pass structure."""

from __future__ import annotations

from typing import Iterator, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import types as T
from ..columnar.batch import ColumnarBatch, Schema
from ..compile import instance_jit, kernel_key
from ..expr.base import Expression, Vec, bind_references, output_name
from ..expr.aggregates import (AggregateFunction, ApproximatePercentile,
                               Average, CollectList, CollectSet, Count, First,
                               Last, Max, Min, Sum, _VarianceFamily)
from ..ops.rowops import (GatherTally, SortedSegments, compaction_order,
                          gather_vecs,
                          group_ids_from_sorted, lexsort_indices,
                          segment_reduce, segment_sum_count, sort_keys_for)
from ..plan.nodes import AggExpr
from ..utils import metrics as M
from .base import (GatherCounts, TpuExec, UnaryTpuExec, batch_vecs,
                   device_ctx, kernel_notes, vecs_to_batch)
from .coalesce import concat_batches


def _vals_equal(xp, v: Vec, shift: int):
    """row i equals row i-shift in a sorted value vec (bool[cap-shift])."""
    if v.is_string:
        return (v.data[shift:] == v.data[:-shift]).all(axis=1) & \
            (v.lengths[shift:] == v.lengths[:-shift])
    if v.data.ndim == 2:  # decimal128 limb pairs
        return (v.data[shift:] == v.data[:-shift]).all(axis=1)
    return v.data[shift:] == v.data[:-shift]


def _sorted_by_keys(xp, key_vecs: List[Vec], all_vecs: List[Vec], row_mask,
                    tally: GatherTally = None):
    """(`all_vecs` sorted by the keys with the dead rows last, their row
    mask, the permutation). Dead rows sort last, so the sorted mask is the
    first `live` rows and needs no gather of its own."""
    cap = row_mask.shape[0]
    groups = [[(~row_mask).astype(np.int8)]]
    for kv in key_vecs:
        groups.append(sort_keys_for(xp, kv, True, True))
    order = lexsort_indices(xp, groups, cap)
    live = xp.sum(row_mask).astype(np.int32)
    sorted_mask = xp.arange(cap, dtype=np.int32) < live
    return gather_vecs(xp, all_vecs, order, tally), sorted_mask, order


def _group_segments(xp, skeys: List[Vec], sorted_mask,
                    tally: GatherTally = None):
    """(segments, representative key rows) of rows sorted by `skeys` with the
    live rows first; no keys is one group over the live rows. One sort of a
    one-byte flag compacts the group-start rows: its permutation gathers
    the representatives and gives every group's end."""
    cap = sorted_mask.shape[0]
    if not skeys:
        return SortedSegments(xp, xp.zeros(cap, dtype=np.int32),
                              xp.asarray(1, dtype=np.int32), sorted_mask), []
    gid, ng, starts = group_ids_from_sorted(xp, skeys, sorted_mask)
    order = compaction_order(xp, starts)
    return (SortedSegments(xp, gid, ng, sorted_mask, order),
            gather_vecs(xp, skeys, order, tally))


def _kernel_notes(ctx, box: list, segs: SortedSegments, tally: GatherTally):
    """What a kernel's trace leaves its host side, in the kernel's box: the
    ANSI messages, one per returned error flag, then the two counts of
    segmented reductions by route and the two of row-gathered arrays by
    route (`_run` adds them to the metrics)."""
    return kernel_notes(ctx, box, segs.prefix_routed, segs.scattered,
                        tally.packed, tally.alone)


def _seg_sum(xp, data, segs: SortedSegments):
    """Segmented sum supporting 1D and 2D (rows along axis 0) inputs; dead
    rows hold zero."""
    if xp is np:
        out = np.zeros((segs.cap,) + data.shape[1:], dtype=data.dtype)
        np.add.at(out, segs.gid, data)
        return out
    return segs.sum(data)


def _seg_count(xp, flags, segs: SortedSegments):
    """Per-group count of the rows where `flags` (bool, false on dead rows),
    int64."""
    if xp is np:
        return _seg_sum(xp, flags.astype(np.int64), segs)
    return segs.count(flags)


def _seg_sums(xp, segs: SortedSegments, *contribs):
    """Per-group int64 totals of several integer or bool (cap,)
    contributions over the same rows, zero / false on dead rows: one stacked
    reduction on the device."""
    if xp is np:
        return tuple(_seg_sum(xp, c.astype(np.int64), segs) for c in contribs)
    return segs.sums(*contribs)


def _seg_minmax_2d(xp, op: str, data, segs: SortedSegments, neutral):
    """Segmented min/max over a 2D matrix (invalid rows pre-neutralized)."""
    if xp is np:
        out = np.full((segs.cap, data.shape[1]), neutral, dtype=data.dtype)
        (np.minimum if op == "min" else np.maximum).at(out, segs.gid, data)
        return out
    return segs.minmax(op, data)


def avg_decimal(xp, func, sbufs: List[Vec], bi: int,
                segs: SortedSegments, row_mask, merging: bool,
                output_partial: bool) -> List[Vec]:
    """Decimal AVG, exact: the sum as Spark's decimal(p + 10, s) through
    the decimal SUM path and the count as long (partials merge by sum),
    then sum / count rounded HALF_UP at the result scale in limbs
    (decimal128.div_count_half_up). Null for a group without a value,
    or whose sum left its type."""
    from ..expr.decimal128 import (div_count_half_up, in_bounds,
                                   is_dec128, pack_limbs, widen_operand)
    sum_t, out_t = func.sum_type, func.data_type
    v = sbufs[bi]
    cap = segs.cap
    valid = v.validity & row_mask
    merged = ()
    if merging:
        # the partial counts, and the partials that counted rows and
        # lost their sum (overflow): they make the merged sum null, as
        # Spark's sum.left + sum.right does
        cv = sbufs[bi + 1]
        merged = (xp.where(cv.validity & row_mask, cv.data, 0),
                  row_mask & ~v.validity & (cv.data > 0))
    # the count (over raw rows the average's own) rides with the sum
    if is_dec128(sum_t):
        s, n, *merged = sum_dec128(xp, sum_t, v, segs, row_mask, merged)
    else:  # <= 18 digits: cannot overflow an int64 accumulator
        data, n, *merged = _seg_sums(
            xp, segs, xp.where(valid, v.data.astype(np.int64), 0), valid,
            *merged)
        s = Vec(sum_t, data, n > 0)
    if merging:
        c, lost = merged
        s = Vec(sum_t, s.data, s.validity & (lost == 0))
    else:
        c = n
    if output_partial:
        return [s, Vec(T.LONG, c, xp.ones(cap, dtype=bool))]
    hi, lo, fits = div_count_half_up(xp, *widen_operand(xp, s),
                                     sum_t.precision,
                                     out_t.scale - sum_t.scale, c)
    ok = s.validity & (c > 0) & fits & \
        in_bounds(xp, hi, lo, out_t.precision)
    if is_dec128(out_t):
        return [Vec(out_t, pack_limbs(xp, hi, lo), ok)]
    return [Vec(out_t, lo.astype(np.int64), ok)]


def sum_dec128(xp, out_t, v: Vec, segs: SortedSegments,
               row_mask, more=()):
    """Decimal128 SUM via carry-free chunk sums (decimal128.sum_chunks):
    three independent segment-sums reconstruct the 128-bit total.
    Partial buffers carry the same decimal type, so merge passes rerun
    the identical kernel. Overflow past precision -> null (Spark).
    Returns (sum, count of valid rows, totals of `more`): the chunks,
    the count and the caller's further contributions are one stacked
    reduction."""
    from ..expr.decimal128 import (in_bounds, is_dec128, pack_limbs,
                                   sum_chunks, sum_recombine,
                                   widen_operand)
    valid = v.validity & row_mask
    hi, lo = widen_operand(xp, v)
    hi = xp.where(valid, hi, np.int64(0))
    lo = xp.where(valid, lo, np.int64(0))
    s0, s1, s2, count, *more = _seg_sums(
        xp, segs, *sum_chunks(xp, hi, lo), valid, *more)
    shi, slo = sum_recombine(xp, s0, s1, s2)
    ok = (count > 0) & in_bounds(xp, shi, slo, out_t.precision)
    data = pack_limbs(xp, shi, slo) if is_dec128(out_t) else \
        slo.astype(np.int64)
    return (Vec(out_t, data, ok), count, *more)


class TpuHashAggregateExec(UnaryTpuExec):
    """Modes: complete (raw->final), partial (raw->partial buffers),
    final (partial->final). Multi-batch inputs aggregate per batch, park the
    results as spillable batches, and merge pairwise under the OOM-retry
    framework (GpuHashAggregateIterator's merge passes). The reference's
    sort-based re-aggregation FALLBACK has no separate code path here: the
    primary algorithm already IS sort+segmented-reduce, so high-cardinality
    inputs degrade smoothly (merges stop shrinking but never overflow a hash
    table); memory pressure is absorbed by spill/split-retry instead."""

    def __init__(self, group_exprs: Sequence[Expression],
                 aggs: Sequence[AggExpr], child: TpuExec, conf=None,
                 mode: str = "complete", agg_bind_schema: Schema = None,
                 partitioned_input: bool = False):
        super().__init__([child], conf)
        assert mode in ("complete", "partial", "final")
        self.mode = mode
        self.group_exprs = list(group_exprs)
        self.aggs = list(aggs)
        # final mode consumes partial buffers positionally — group/agg exprs
        # reference the ORIGINAL input schema (pre-partial), so a final exec
        # whose child carries the partial wire layout binds against the
        # original schema passed by the distribution pass
        bind_schema = agg_bind_schema or child.output
        # partitioned_input: child is a key-exchange, so groups are disjoint
        # across input batches and final aggregation runs per batch (per shard)
        self.partitioned_input = partitioned_input
        self._bound_groups = [bind_references(e, bind_schema)
                              for e in self.group_exprs]
        self._bound_aggs = []
        for a in self.aggs:
            f = a.func
            if f.child is not None:
                f = f.with_children([bind_references(f.child, bind_schema)])
            self._bound_aggs.append(AggExpr(f, a.name))
        self.agg_time = self.metrics.create(M.AGG_TIME, M.MODERATE)
        self.prefix_reductions = self.metrics.create(
            M.NUM_PREFIX_REDUCTIONS, M.MODERATE)
        self.scatter_reductions = self.metrics.create(
            M.NUM_SCATTER_REDUCTIONS, M.MODERATE)
        self.gathers = GatherCounts(self.metrics)
        self._sp_maxes_jit = None
        self._sp_kernel_jit: dict = {}

        knames = [output_name(e, f"k{i}") for i, e in enumerate(self.group_exprs)]
        ktypes = [e.data_type for e in self._bound_groups]
        if mode == "partial":
            names, tps = list(knames), list(ktypes)
            for a in self._bound_aggs:
                pts = a.func.partial_types()
                for j, pt in enumerate(pts):
                    names.append(f"{a.name}__p{j}")
                    tps.append(pt)
            self._schema = Schema(tuple(names), tuple(tps))
        else:
            self._schema = Schema(
                tuple(knames + [a.name for a in self._bound_aggs]),
                tuple(ktypes + [a.func.data_type for a in self._bound_aggs]))

        # the partial-buffer schema (inter-batch/exchange wire layout)
        pnames, ptps = list(knames), list(ktypes)
        for a in self._bound_aggs:
            for j, pt in enumerate(a.func.partial_types()):
                pnames.append(f"{a.name}__p{j}")
                ptps.append(pt)
        self._partial_schema = Schema(tuple(pnames), tuple(ptps))

        # ANSI error-message boxes: each kernel variant gets its OWN box —
        # a shared one would be clobbered by whichever kernel traced last,
        # truncating another kernel's flag tuple in raise_kernel_errors.
        # self._err_msgs serves the single-pass kernels (one expression
        # tree shared by every fanout-bucket specialization).
        self._err_msgs: list = []
        self._kernel_boxes: dict = {}
        # eager-fanout group keys / agg inputs (split, str_to_map, pandas
        # UDFs) cannot be traced: run the kernels un-jitted, like
        # TpuProjectExec's black-box mode — jnp ops still hit the device
        from .basic import has_host_black_box
        self._eager = has_host_black_box(
            list(self._bound_groups) +
            [a.func.child for a in self._bound_aggs])
        raw_in = mode in ("complete", "partial")
        self._kernel = self._make_kernel(
            input_partial=not raw_in,
            output_partial=(mode == "partial"))
        # multi-batch machinery: raw->partial for the first pass,
        # partial->partial for merge passes, partial->final to finish
        self._partial_kernel = self._make_kernel(False, True) \
            if raw_in else None
        self._merge_kernel = self._make_kernel(True, True)
        self._final_kernel = self._make_kernel(True, False) \
            if mode != "partial" else None

    @property
    def output(self) -> Schema:
        return self._schema

    # ------------------------------------------------------------------
    def _make_kernel(self, input_partial: bool, output_partial: bool):
        bound_groups = self._bound_groups
        bound_aggs = self._bound_aggs
        out_schema = self._partial_schema if output_partial else self._schema
        msgs_box: list = []

        def kernel(batch: ColumnarBatch):
            xp = jnp
            ctx = device_ctx(batch, self.conf)
            vecs = batch_vecs(batch)
            mask = batch.row_mask()
            cap = batch.capacity
            nk = len(bound_groups)
            if input_partial:
                # partial layout: key columns first, then buffers
                keys = list(vecs[:nk])
            else:
                keys = [e.eval(ctx, vecs) for e in bound_groups]

            if input_partial:
                buf_vecs: List[List[Vec]] = []
                off = nk
                for a in bound_aggs:
                    k = len(a.func.partial_types())
                    buf_vecs.append(vecs[off:off + k])
                    off += k
            else:
                buf_vecs = []
                for a in bound_aggs:
                    if a.func.child is None:
                        buf_vecs.append([Vec(T.LONG,
                                             xp.ones(cap, dtype=np.int64),
                                             mask)])
                    else:
                        buf_vecs.append([a.func.child.eval(ctx, vecs)])

            tally = GatherTally()
            if keys:
                all_vecs = list(keys) + [v for grp in buf_vecs for v in grp]
                sorted_vecs, sorted_mask, _ = _sorted_by_keys(
                    xp, keys, all_vecs, mask, tally)
                skeys = sorted_vecs[:len(keys)]
                sbufs = sorted_vecs[len(keys):]
            else:
                sorted_vecs, sorted_mask = (
                    [v for grp in buf_vecs for v in grp], mask)
                skeys, sbufs = [], sorted_vecs
            segs, reps = _group_segments(xp, skeys, sorted_mask, tally)

            out_vecs: List[Vec] = list(reps)
            bi = 0
            for a in bound_aggs:
                out_vecs.extend(self._agg_one(xp, a.func, sbufs, bi, segs,
                                              sorted_mask, input_partial,
                                              output_partial, ctx=ctx))
                bi += len(a.func.partial_types()) if input_partial else 1
            return vecs_to_batch(out_schema, out_vecs, segs.num_groups), \
                _kernel_notes(ctx, msgs_box, segs, tally)

        # merge/final kernels (input_partial) only read partial buffers —
        # never the black-box expressions — so they stay jitted even in
        # eager mode
        jitted = kernel if (self._eager and not input_partial) \
            else instance_jit(
                kernel, op="exec.aggregate",
                key=self._agg_kernel_key(input_partial, output_partial),
                msgs_box=msgs_box)
        self._kernel_boxes[jitted] = msgs_box
        return jitted

    def _agg_kernel_key(self, input_partial: bool,
                        output_partial: bool) -> str:
        return kernel_key(
            input_partial, output_partial,
            [repr(e) for e in self._bound_groups],
            [(repr(a.func), a.name) for a in self._bound_aggs],
            self._schema, self._partial_schema, conf=self.conf)

    def _run(self, kernel, batch: ColumnarBatch) -> ColumnarBatch:
        """Invoke an aggregation kernel and surface its ANSI error flags
        (single-pass kernels share self._err_msgs; see __init__)."""
        from .base import raise_kernel_errors
        out, errs = kernel(batch)
        box = self._kernel_boxes.get(kernel, self._err_msgs)
        raise_kernel_errors(errs, box)
        self.prefix_reductions.add(box[-4])     # _kernel_notes' tail
        self.scatter_reductions.add(box[-3])
        self.gathers.add(box)
        return out

    def _agg_one(self, xp, func: AggregateFunction, sbufs: List[Vec], bi: int,
                 segs: SortedSegments, row_mask, input_partial: bool,
                 output_partial: bool, ctx=None) -> List[Vec]:
        """Produce output vecs for one aggregate (list of partial buffers when
        output_partial, single final value otherwise). `ctx` (when given)
        carries the ANSI error channel: integral SUM accumulation overflow
        reports through it (Spark ANSI raises on BIGINT sum overflow; the
        reference checks the accumulator the same way)."""
        merging = input_partial
        cap = segs.cap

        def seg(op, v: Vec, acc_dtype=None):
            valid = v.validity & row_mask
            data = v.data if acc_dtype is None else v.data.astype(acc_dtype)
            if op == "sum":
                out, cnt = segment_sum_count(xp, data, segs, valid)
            else:
                out = segment_reduce(xp, op, data, segs, valid)
                cnt = segment_reduce(xp, "count", data, segs, valid)
            return out, cnt > 0

        if isinstance(func, Count):
            v = sbufs[bi]
            if merging:
                data, _ = seg("sum", v, np.int64)
            else:
                valid = v.validity & row_mask
                data = segment_reduce(xp, "count", v.data, segs, valid)
            return [Vec(T.LONG, data.astype(np.int64),
                        xp.ones(cap, dtype=bool))]
        if isinstance(func, Average) and \
                isinstance(func.data_type, T.DecimalType):
            return avg_decimal(xp, func, sbufs, bi, segs, row_mask,
                               merging, output_partial)
        if isinstance(func, Average):
            if merging:
                s, sv = seg("sum", sbufs[bi], np.float64)
                c, _ = seg("sum", sbufs[bi + 1], np.int64)
            else:
                v = sbufs[bi]
                s, sv = seg("sum", v, np.float64)
                valid = v.validity & row_mask
                c = segment_reduce(xp, "count", v.data, segs, valid)
            if output_partial:
                return [Vec(T.DOUBLE, s, c > 0),
                        Vec(T.LONG, c.astype(np.int64),
                            xp.ones(cap, dtype=bool))]
            avg = s / xp.maximum(c, 1)
            return [Vec(T.DOUBLE, avg, c > 0)]
        if isinstance(func, Sum):
            from ..expr.decimal128 import is_dec128
            v = sbufs[bi]
            if isinstance(func.data_type, T.DecimalType) and \
                    (is_dec128(func.data_type) or is_dec128(v.dtype)):
                return [sum_dec128(xp, func.data_type, v, segs, row_mask)[0]]
            out_t = func.data_type if not merging else v.dtype
            acc = np.float64 if T.is_floating(out_t) else np.int64
            data, has = seg("sum", v, acc)
            if ctx is not None and ctx.ansi and T.is_integral(out_t):
                # int64 accumulation wraps silently; a parallel float64 sum
                # tracks the true magnitude to ~2^10 ulp, so a wrap (error
                # ~k*2^64) separates cleanly from rounding at the 2^62 line
                from ..expr.base import ansi_raise
                fsum, _ = seg("sum", Vec(T.DOUBLE,
                                         v.data.astype(np.float64),
                                         v.validity), np.float64)
                wrapped = xp.abs(fsum - data.astype(np.float64)) \
                    > np.float64(2 ** 62)
                saved, ctx.row_mask = ctx.row_mask, None
                ansi_raise(ctx, wrapped & has,
                           "[ARITHMETIC_OVERFLOW] long overflow")
                ctx.row_mask = saved
            return [Vec(func.data_type if not output_partial else
                        func.partial_types()[0],
                        data.astype(func.data_type.np_dtype), has)]
        if isinstance(func, (Min, Max)):
            from ..expr.decimal128 import is_dec128
            op = "min" if isinstance(func, Min) else "max"
            v = sbufs[bi]
            if v.is_string:
                return [self._minmax_string(xp, op, v, segs, row_mask)]
            if is_dec128(v.dtype):
                return [self._minmax_dec128(xp, op, v, segs, row_mask)]
            data, has = seg(op, v)
            return [Vec(v.dtype, data.astype(v.dtype.np_dtype), has)]
        if isinstance(func, _VarianceFamily):
            if merging:
                s, _ = seg("sum", sbufs[bi], np.float64)
                s2, _ = seg("sum", sbufs[bi + 1], np.float64)
                c, _ = seg("sum", sbufs[bi + 2], np.int64)
                c = c.astype(np.int64)
            else:
                v = sbufs[bi]
                x = v.data.astype(np.float64)
                s, _ = seg("sum", Vec(T.DOUBLE, x, v.validity), np.float64)
                s2, _ = seg("sum", Vec(T.DOUBLE, x * x, v.validity),
                            np.float64)
                c = segment_reduce(xp, "count", x, segs,
                                   v.validity & row_mask).astype(np.int64)
            if output_partial:
                return [Vec(T.DOUBLE, s, c > 0), Vec(T.DOUBLE, s2, c > 0),
                        Vec(T.LONG, c, xp.ones(cap, dtype=bool))]
            cf = c.astype(np.float64)
            mean = s / xp.maximum(cf, 1.0)
            m2 = xp.maximum(s2 - cf * mean * mean, 0.0)
            if func.sample:
                var = m2 / xp.maximum(cf - 1.0, 1.0)
                has = c > 1
            else:
                var = m2 / xp.maximum(cf, 1.0)
                has = c > 0
            out = xp.sqrt(var) if func.sqrt else var
            return [Vec(T.DOUBLE, out, has)]
        from ..expr.aggregates import (BoolAnd, BoolOr, CountIf,
                                       _BitAgg, _MomentFamily)
        if isinstance(func, CountIf):
            v = sbufs[bi]
            if merging:
                data, _ = seg("sum", v, np.int64)
            else:
                hit = v.validity & row_mask & v.data.astype(bool)
                data = _seg_count(xp, hit, segs)
            return [Vec(T.LONG, data.astype(np.int64),
                        xp.ones(cap, dtype=bool))]
        if isinstance(func, (BoolAnd, BoolOr)):
            is_and = isinstance(func, BoolAnd)
            v = sbufs[bi]
            valid = v.validity & row_mask
            contrib = xp.where(valid, v.data.astype(np.int8),
                               np.int8(1 if is_and else 0))
            out = segment_reduce(xp, "min" if is_and else "max", contrib,
                                 segs, row_mask)
            has = _seg_count(xp, valid, segs) > 0
            return [Vec(T.BOOLEAN, out.astype(bool), has)]
        if isinstance(func, _BitAgg):
            v = sbufs[bi]
            valid = v.validity & row_mask
            nbits = v.data.dtype.itemsize * 8
            x = v.data.astype(np.int64)
            shifts = xp.arange(nbits, dtype=np.int64)[None, :]
            bits = ((x[:, None] >> shifts) & 1).astype(np.int8)
            if func.op == "and":
                bits = xp.where(valid[:, None], bits, np.int8(1))
                red = _seg_minmax_2d(xp, "min", bits, segs, np.int8(1))
            elif func.op == "or":
                bits = xp.where(valid[:, None], bits, np.int8(0))
                red = _seg_minmax_2d(xp, "max", bits, segs, np.int8(0))
            else:  # xor = per-bit parity
                bits = xp.where(valid[:, None], bits, np.int8(0))
                red = _seg_sum(xp, bits.astype(np.int32), segs) & 1
            val = (red.astype(np.int64) << shifts).sum(axis=1)
            has = _seg_count(xp, valid, segs) > 0
            return [Vec(func.data_type,
                        val.astype(func.data_type.np_dtype), has)]
        if isinstance(func, _MomentFamily):
            if merging:
                s1, _ = seg("sum", sbufs[bi], np.float64)
                s2, _ = seg("sum", sbufs[bi + 1], np.float64)
                s3, _ = seg("sum", sbufs[bi + 2], np.float64)
                s4, _ = seg("sum", sbufs[bi + 3], np.float64)
                c, _ = seg("sum", sbufs[bi + 4], np.int64)
                c = c.astype(np.int64)
            else:
                v = sbufs[bi]
                x = v.data.astype(np.float64)
                vv = v.validity
                pows = []
                for p in (1, 2, 3, 4):
                    pows.append(seg("sum", Vec(T.DOUBLE, x ** p, vv),
                                    np.float64)[0])
                s1, s2, s3, s4 = pows
                c = _seg_count(xp, vv & row_mask, segs)
            if output_partial:
                ones = xp.ones(cap, dtype=bool)
                return [Vec(T.DOUBLE, s1, c > 0), Vec(T.DOUBLE, s2, c > 0),
                        Vec(T.DOUBLE, s3, c > 0), Vec(T.DOUBLE, s4, c > 0),
                        Vec(T.LONG, c, ones)]
            cf = xp.maximum(c.astype(np.float64), 1.0)
            mu = s1 / cf
            m2 = s2 - cf * mu * mu
            m3 = s3 - 3 * mu * s2 + 2 * cf * mu ** 3
            m4 = s4 - 4 * mu * s3 + 6 * mu * mu * s2 - 3 * cf * mu ** 4
            from ..expr.aggregates import Skewness as _Skew
            zero_var = m2 <= 0
            safe_m2 = xp.where(zero_var, 1.0, m2)
            if isinstance(func, _Skew):
                out = xp.sqrt(cf) * m3 / safe_m2 ** 1.5
            else:
                out = cf * m4 / (safe_m2 * safe_m2) - 3.0
            out = xp.where(zero_var, np.nan, out)
            return [Vec(T.DOUBLE, out, c > 0)]
        if isinstance(func, (First, Last)):
            v = sbufs[bi]
            is_first = isinstance(func, First) and not isinstance(func, Last)
            valid = row_mask & (v.validity if func.ignore_nulls else
                                xp.ones(cap, dtype=bool))
            idx = xp.arange(cap, dtype=np.int64)
            sentinel = np.int64(cap)
            key = xp.where(valid, idx, sentinel if is_first else np.int64(-1))
            pick = segment_reduce(xp, "min" if is_first else "max", key,
                                  segs, row_mask)
            got = (pick != sentinel) if is_first else (pick >= 0)
            safe = xp.clip(pick, 0, cap - 1)
            out = gather_vecs(xp, [v], safe)[0]
            return [Vec(out.dtype, out.data, out.validity & got, out.lengths)]
        raise NotImplementedError(type(func).__name__)

    def _minmax_dec128(self, xp, op: str, v: Vec, segs: SortedSegments,
                       row_mask) -> Vec:
        """128-bit extremum in two ordered passes: segment-extreme of the
        high limb, then of the unsigned low order among rows matching it —
        (ext_hi, ext_lo) IS the extreme value."""
        from ..expr.decimal128 import _s, _u
        valid = v.validity & row_mask
        hi = v.data[:, 0]
        lo_key = _s(xp, _u(xp, v.data[:, 1]) ^ np.uint64(1 << 63))
        info = np.iinfo(np.int64)
        neutral = info.max if op == "min" else info.min
        hi_m = xp.where(valid, hi, neutral)
        h_ext = segment_reduce(xp, op, hi_m, segs, row_mask)
        cand = valid & (hi == h_ext[segs.gid])
        lo_m = xp.where(cand, lo_key, neutral)
        l_ext = segment_reduce(xp, op, lo_m, segs, row_mask)
        out_lo = _s(xp, _u(xp, l_ext) ^ np.uint64(1 << 63))
        has = _seg_count(xp, valid, segs) > 0
        data = xp.stack([h_ext, out_lo], axis=1)
        return Vec(v.dtype, data, has)

    def _minmax_string(self, xp, op: str, v: Vec, segs: SortedSegments,
                       row_mask) -> Vec:
        """min/max over strings: segmented argmin via ordering keys is complex;
        use iterative halving? Round 1: order rows by (gid, string) and take the
        group-start (min) / group-end (max) row."""
        gid, cap = segs.gid, segs.cap
        valid = v.validity & row_mask
        groups = [[gid.astype(np.int32)]]
        groups.append([(~valid).astype(np.int8)])  # invalid rows last
        groups.append(sort_keys_for(xp, v, op == "min", False)[1:])
        order = lexsort_indices(xp, groups, cap)
        sv = gather_vecs(xp, [v], order)[0]
        # gid is sorted already and leads the keys: the order moves rows
        # inside their groups only, so gid[order] is gid and `segs` holds
        sgid = gid
        svalid = valid[order]
        # first row of each gid run in this ordering is the min (or max)
        first_of_gid = xp.concatenate(
            [xp.ones(1, dtype=bool), sgid[1:] != sgid[:-1]])
        pick_idx = xp.where(first_of_gid, xp.arange(cap), 0)
        out = segment_reduce(xp, "max", xp.where(first_of_gid,
                                                 xp.arange(cap, dtype=np.int64),
                                                 np.int64(-1)),
                             segs, xp.ones(cap, dtype=bool))
        has = segment_reduce(xp, "count", sv.data[:, 0], segs, svalid) > 0
        safe = xp.clip(out, 0, cap - 1)
        res = gather_vecs(xp, [sv], safe)[0]
        return Vec(v.dtype, res.data, has, res.lengths)

    # ------------------------------------------------------------------
    # single-pass aggregates (collect_list/collect_set/approx_percentile):
    # output fanout is data-dependent, so the exec concatenates the input,
    # measures per-group counts on device, picks a static fanout bucket with
    # one host sync, and runs a dedicated kernel (the join-expansion shape)
    def _has_single_pass(self) -> bool:
        return any(a.func.single_pass for a in self._bound_aggs)

    def _single_pass_execute(self, batches) -> Iterator[ColumnarBatch]:
        from ..columnar.padding import width_bucket
        with self.agg_time.timed():
            b = concat_batches(batches) if len(batches) > 1 else batches[0]
            # jit caches live on the instance so they die with the exec (a
            # module-level cache keyed by self would pin every exec forever)
            if self._sp_maxes_jit is None:
                self._sp_maxes_jit = self._sp_group_maxes if self._eager \
                    else instance_jit(
                        self._sp_group_maxes, op="exec.aggregate.sp_maxes",
                        key=self._agg_kernel_key(False, False))
            maxes = self._sp_maxes_jit(b)
            ks = tuple(
                width_bucket(max(int(m), 1)) if isinstance(
                    a.func, (CollectList, CollectSet)) else
                width_bucket(max(len(a.func.percentages), 1))
                for a, m in zip(
                    [a for a in self._bound_aggs if a.func.single_pass],
                    maxes))
            kern = self._sp_kernel_jit.get(ks)
            if kern is None:
                import functools
                kern = functools.partial(self._sp_kernel, ks=ks)
                if not self._eager:
                    kern = instance_jit(
                        kern, op="exec.aggregate.single_pass",
                        key=kernel_key(self._agg_kernel_key(False, False),
                                       ks),
                        msgs_box=self._err_msgs)
                self._sp_kernel_jit[ks] = kern
            out = self._run(kern, b)
        self.num_output_rows.add(out.row_count())
        yield self._count_output(out)

    def _sp_group_maxes(self, batch: ColumnarBatch):
        """Phase 1: max per-group valid count for each single-pass aggregate
        (host picks the fanout bucket from these)."""
        xp = jnp
        _, svals, segs, _, smask, _ = self._sp_prepare(xp, batch)
        cap = batch.capacity
        out = []
        for a, v in zip(self._bound_aggs, svals):
            if not a.func.single_pass:
                continue
            data = v.data if v.data.ndim == 1 else v.lengths
            counts = segment_reduce(xp, "count", data, segs,
                                    v.validity & smask)
            out.append(xp.max(counts).astype(np.int32))
        return tuple(out)

    def _sp_kernel(self, batch: ColumnarBatch, ks: tuple):
        """Phase 2: full output kernel with static fanout buckets per
        single-pass aggregate; normal aggregates ride along."""
        xp = jnp
        tally = GatherTally()
        _, svals, segs, reps, smask, ctx = self._sp_prepare(xp, batch, tally)
        cap = batch.capacity
        out_vecs: List[Vec] = list(reps)
        ki = 0
        for a, v in zip(self._bound_aggs, svals):
            if a.func.single_pass:
                out_vecs.extend(self._sp_agg_one(xp, a.func, v, segs, smask,
                                                 ks[ki]))
                ki += 1
            else:
                buf = [v] if v is not None else \
                    [Vec(T.LONG, xp.ones(cap, dtype=np.int64), smask)]
                out_vecs.extend(self._agg_one(xp, a.func, buf, 0, segs,
                                              smask, False, False, ctx=ctx))
        return vecs_to_batch(self._schema, out_vecs, segs.num_groups), \
            _kernel_notes(ctx, self._err_msgs, segs, tally)

    def _sp_prepare(self, xp, batch: ColumnarBatch, tally=None):
        """Evaluate keys + agg children and sort everything by the keys; the
        shared front half of both single-pass kernels."""
        ctx = device_ctx(batch, self.conf)
        vecs = batch_vecs(batch)
        mask = batch.row_mask()
        cap = batch.capacity
        keys = [e.eval(ctx, vecs) for e in self._bound_groups]
        vals = [a.func.child.eval(ctx, vecs) if a.func.child is not None
                else None for a in self._bound_aggs]
        present = [v for v in vals if v is not None]
        if keys:
            all_vecs = keys + present
            sorted_vecs, sorted_mask, _ = _sorted_by_keys(xp, keys, all_vecs,
                                                          mask, tally)
            skeys = sorted_vecs[:len(keys)]
            rest = iter(sorted_vecs[len(keys):])
            svals = [None if v is None else next(rest) for v in vals]
        else:
            skeys, svals, sorted_mask = [], vals, mask
        segs, reps = _group_segments(xp, skeys, sorted_mask, tally)
        return skeys, svals, segs, reps, sorted_mask, ctx

    def _sp_agg_one(self, xp, func, v: Vec, segs: SortedSegments, row_mask,
                    k: int):
        """One single-pass aggregate over key-sorted rows: re-sort its rows by
        (gid, validity, value) and build the per-group result."""
        gid, cap = segs.gid, segs.cap
        valid = v.validity & row_mask
        groups = [[gid.astype(np.int32)], [(~valid).astype(np.int8)]]
        groups.append(sort_keys_for(xp, v, True, False)[1:])
        order = lexsort_indices(xp, groups, cap)
        sv = gather_vecs(xp, [v], order)[0]
        sgid = gid      # == gid[order], as in _minmax_string
        svalid = valid[order]

        counts = segment_reduce(xp, "count", sv.data if sv.data.ndim == 1
                                else sv.lengths, segs, svalid) \
            .astype(np.int32)
        if isinstance(func, CollectSet):
            prev_same = xp.concatenate(
                [xp.zeros(1, dtype=bool),
                 (sgid[1:] == sgid[:-1]) & _vals_equal(xp, sv, 1)])
            svalid = svalid & ~prev_same
            counts = segment_reduce(
                xp, "count", sv.data if sv.data.ndim == 1 else sv.lengths,
                segs, svalid).astype(np.int32)
        if isinstance(func, (CollectList, CollectSet)):
            # rank of each kept row within its group (segmented cumsum)
            cs = xp.cumsum(svalid.astype(np.int32))
            base = segment_reduce(
                xp, "min", xp.where(svalid, cs - 1,
                                    np.int32(2**31 - 1)).astype(np.int64),
                segs, xp.ones(cap, dtype=bool)).astype(np.int32)
            rank = cs - 1 - base[sgid]
            # invalid rows scatter out of bounds and are DROPPED (mode=drop) —
            # scatter-set keeps negative values intact (a scatter-max over a
            # zero init would clamp them)
            rows = xp.where(svalid, sgid, cap).astype(np.int32)
            cols = xp.clip(xp.where(svalid, rank, 0), 0, k - 1)

            def scatter(leaf):
                out = xp.zeros((cap, k) + leaf.shape[1:], dtype=leaf.dtype)
                return out.at[rows, cols].set(leaf, mode="drop")

            from ..expr.base import vec_map_arrays
            elem = vec_map_arrays(
                Vec(sv.dtype, sv.data, svalid, sv.lengths, sv.children),
                scatter)
            sizes = counts
            return [Vec(func.data_type, sizes, xp.ones(cap, dtype=bool),
                        None, (elem,))]
        # approx_percentile: nearest-rank selection over the sorted values
        first_pos = segment_reduce(
            xp, "min", xp.where(svalid, xp.arange(cap, dtype=np.int64),
                                np.int64(cap)), segs, xp.ones(cap, dtype=bool))
        vals = sv.data.astype(np.float64)
        outs = []
        for q in func.percentages:
            idx = first_pos + xp.round(q * xp.maximum(counts - 1, 0)
                                       ).astype(np.int64)
            safe = xp.clip(idx, 0, cap - 1)
            outs.append(vals[safe])
        has = counts > 0
        if func.scalar:
            return [Vec(T.DOUBLE, outs[0], has)]
        elem_data = xp.stack(outs, axis=1)
        elem_data = xp.pad(elem_data,
                           ((0, 0), (0, k - len(outs))))
        elem = Vec(T.DOUBLE, elem_data,
                   xp.broadcast_to(has[:, None], (cap, k)))
        sizes = xp.where(has, len(outs), 0).astype(np.int32)
        return [Vec(func.data_type, sizes, has, None, (elem,))]

    # ------------------------------------------------------------------
    def do_execute(self) -> Iterator[ColumnarBatch]:
        batches = list(self.child.execute())
        if not batches:
            if self.group_exprs or self.mode == "partial":
                # grouped agg over empty input is empty; a partial side may
                # also emit nothing (the final side synthesizes the row)
                return
            # GLOBAL aggregate over zero input batches must still emit its
            # one row (Spark: SELECT count(*) over empty input = 0) — run
            # the kernel over a synthesized empty batch
            batches = [self._empty_input_batch()]
        if self._has_single_pass():
            yield from self._single_pass_execute(batches)
            return
        if self.mode == "partial":
            # map-side aggregation: one partial batch per input batch (shard),
            # feeding the exchange — no cross-batch merge here (that is the
            # final side's job), matching the reference's partial-agg tasks
            with self.agg_time.timed():
                for b in batches:
                    if len(batches) > 1 and int(b.row_count()) == 0:
                        continue
                    out = self._run(self._kernel, b)
                    self.num_output_rows.add(out.row_count())
                    yield self._count_output(out)
            return
        if self.partitioned_input and self.mode == "final" and self.group_exprs:
            # key-partitioned input: groups are disjoint across batches, so
            # each shard finalizes independently (per-shard reduce side)
            with self.agg_time.timed():
                for b in batches:
                    if int(b.row_count()) == 0:
                        continue
                    out = self._run(self._kernel, b)
                    self.num_output_rows.add(out.row_count())
                    yield self._count_output(out)
            return
        if len(batches) == 1:
            from ..errors import SplitAndRetryOOM
            from ..memory.retry import with_retry_no_split_spillable
            try:
                with self.agg_time.timed():
                    out = with_retry_no_split_spillable(
                        batches[0], lambda b: self._run(self._kernel, b))
            except SplitAndRetryOOM:
                # one batch too big to aggregate in a single device pass:
                # the multi-batch partial/merge/final machinery splits it
                yield from self._multi_batch(batches)
                return
            self.num_output_rows.add(out.row_count())
            yield self._count_output(out)
            return
        yield from self._multi_batch(batches)

    def _empty_input_batch(self) -> ColumnarBatch:
        """A 0-row device batch matching the child's output schema."""
        import pyarrow as pa
        from .. import types as T
        from ..columnar.batch import batch_from_arrow
        schema = self.child.output
        t = pa.table(
            [pa.array([], type=T.to_arrow(dt)) for dt in schema.types],
            names=list(schema.names))
        return batch_from_arrow(t)

    def _multi_batch(self, batches: List[ColumnarBatch]
                     ) -> Iterator[ColumnarBatch]:
        """Aggregate each batch, park results spillable, merge pairwise under
        the OOM-retry framework (GpuHashAggregateIterator merge passes)."""
        from ..memory.budget import MemoryBudget
        from ..memory.retry import split_batch_halves, with_retry
        from ..memory.spillable import SpillableColumnarBatch

        def first_pass(b: ColumnarBatch) -> ColumnarBatch:
            MemoryBudget.get().reserve(0)  # pre-flight / injection point
            if self.mode == "final":
                return b  # child already produced partial buffers
            return self._run(self._partial_kernel, b)

        pending: List[SpillableColumnarBatch] = []
        with self.agg_time.timed():
            for b in batches:
                for out in with_retry(SpillableColumnarBatch(b),
                                      lambda sp: first_pass(sp.get_batch()),
                                      split_batch_halves):
                    pending.append(SpillableColumnarBatch(out))

            def merge_pair(sp: SpillableColumnarBatch) -> ColumnarBatch:
                b = sp.get_batch()
                MemoryBudget.get().reserve(b.device_memory_size())
                try:
                    return self._run(self._merge_kernel, b)
                finally:
                    MemoryBudget.get().release(b.device_memory_size())

            while len(pending) > 1:
                a = pending.pop(0)
                c = pending.pop(0)
                pair = concat_batches([a.get_batch(), c.get_batch()])
                a.close()
                c.close()
                for out in with_retry(SpillableColumnarBatch(pair),
                                      merge_pair, split_batch_halves):
                    pending.append(SpillableColumnarBatch(out))

            last = pending.pop()
            result = last.get_batch()
            last.close()
            if self.mode != "partial":
                result = self._run(self._final_kernel, result)
        self.num_output_rows.add(result.row_count())
        yield self._count_output(result)

    def _arg_string(self):
        return (f"[{self.mode}, keys={[repr(e) for e in self.group_exprs]}, "
                f"aggs={[a.name for a in self.aggs]}]")
