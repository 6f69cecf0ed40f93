"""CPU physical plan — the host engine this framework accelerates.

In the reference, Spark Catalyst produces a CPU physical plan and the plugin's
`GpuOverrides` rewrites it (`GpuOverrides.scala:4235-4266`). pyspark is absent in this
environment, so this module is the Catalyst stand-in: a physical plan node tree with a
CPU interpreter carrying Spark execution semantics. `plan/overrides.py` treats these
nodes exactly as the reference treats `SparkPlan` nodes — wrap, tag, convert to
`exec/` TPU operators, or leave on CPU (fallback).

The CPU interpreter deliberately uses DIFFERENT algorithms from the TPU engine
(dict/unique-based grouping and joins vs. the device's sort-segmented kernels) so the
differential harness has an independent oracle, like CPU Spark is for the reference.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from .. import types as T
from ..columnar.batch import Schema
from ..cpu.hostbatch import HostBatch
from ..expr.base import (Alias, AttributeReference, BoundReference, EvalContext,
                         Expression, Vec, bind_references, output_name)
from ..expr.aggregates import AggregateFunction, Average, Count


class PhysicalPlan:
    """Base CPU plan node."""

    def __init__(self, children: Sequence["PhysicalPlan"]):
        self.children = list(children)

    @property
    def output(self) -> Schema:
        raise NotImplementedError

    def execute_cpu(self) -> Iterator[HostBatch]:
        raise NotImplementedError

    @property
    def name(self) -> str:
        return type(self).__name__

    def tree_string(self, indent: int = 0) -> str:
        s = "  " * indent + f"{self.name}{self._arg_string()}\n"
        for c in self.children:
            s += c.tree_string(indent + 1)
        return s

    def _arg_string(self) -> str:
        return ""


# set by the session before CPU execution (plugin.execute_plan): the oracle
# raises ANSI violations eagerly during eval, like Spark's interpreted path.
# Thread-local so concurrent sessions with different ANSI settings don't
# corrupt each other (execute_plan materializes eagerly, so within a thread
# the flag covers the whole consumption).
import threading

_TLS = threading.local()


def set_ansi_mode(ansi: bool) -> None:
    _TLS.ansi = ansi


def _ctx(n: int) -> EvalContext:
    return EvalContext(np, ansi=getattr(_TLS, "ansi", False),
                       row_mask=np.ones(n, dtype=bool))


def _concat_np_padded(arrs: List[np.ndarray]) -> np.ndarray:
    """Concat along axis 0, padding trailing dims (string width / array fanout)
    to the max across inputs."""
    nd = arrs[0].ndim
    if nd == 1:
        return np.concatenate(arrs)
    tgt = tuple(max(a.shape[d] for a in arrs) for d in range(1, nd))
    return np.concatenate(
        [np.pad(a, [(0, 0)] + [(0, t - a.shape[d + 1])
                               for d, t in enumerate(tgt)]) for a in arrs])


def _concat_vecs(cols: List[Vec]) -> Vec:
    # every buffer gets the padded concat: child validity/lengths share the
    # fanout dims of data, and fanout buckets can differ per batch
    kids = None if cols[0].children is None else tuple(
        _concat_vecs([c.children[i] for c in cols])
        for i in range(len(cols[0].children)))
    return Vec(cols[0].dtype, _concat_np_padded([c.data for c in cols]),
               _concat_np_padded([c.validity for c in cols]),
               None if cols[0].lengths is None
               else _concat_np_padded([c.lengths for c in cols]), kids)


def _concat_host(batches: List[HostBatch], schema: Schema) -> HostBatch:
    """Concatenate host batches (CPU engine collects whole partitions)."""
    if len(batches) == 1:
        return batches[0]
    if not batches:
        return HostBatch(schema, [_empty_vec(t) for t in schema.types], 0)
    vecs = [_concat_vecs([b.vecs[i] for b in batches])
            for i in range(len(schema.types))]
    return HostBatch(schema, vecs, sum(b.num_rows for b in batches))


def _empty_vec(dt: T.DataType, shape: tuple = (0,)) -> Vec:
    from ..expr.base import zero_vec
    return zero_vec(np, dt, shape)


class CpuScanExec(PhysicalPlan):
    """In-memory Arrow table scan (file scans live in io/ and produce this
    shape). `slices` > 1 streams the table as that many row slices — the
    AQE coalescer uses it so a staged exchange's output flows downstream
    at the COALESCED partition granularity."""

    def __init__(self, table, label: str = "memory", slices: int = 1):
        super().__init__([])
        self.table = table
        self.label = label
        self.slices = max(1, int(slices))
        self._schema = Schema.from_arrow(table.schema)

    @property
    def output(self) -> Schema:
        return self._schema

    def execute_cpu(self):
        from ..cpu.hostbatch import host_batch_from_arrow
        if self.slices == 1 or self.table.num_rows == 0:
            yield host_batch_from_arrow(self.table)
            return
        per = -(-self.table.num_rows // self.slices)
        for s in range(self.slices):
            part = self.table.slice(s * per, per)
            if part.num_rows:
                yield host_batch_from_arrow(part)

    def _arg_string(self):
        return f"[{self.label}, {self.table.num_rows} rows]"


class CpuProjectExec(PhysicalPlan):
    def __init__(self, exprs: Sequence[Expression], child: PhysicalPlan):
        super().__init__([child])
        self.exprs = list(exprs)
        self._bound = [bind_references(e, child.output) for e in self.exprs]
        names = tuple(output_name(e, f"col{i}") for i, e in enumerate(self.exprs))
        self._schema = Schema(names, tuple(e.data_type for e in self._bound))

    @property
    def output(self) -> Schema:
        return self._schema

    def execute_cpu(self):
        offset = 0
        for b in self.children[0].execute_cpu():
            ctx = _ctx(b.num_rows)
            ctx.partition_row_offset = offset
            offset += b.num_rows
            vecs = [e.eval(ctx, b.vecs) for e in self._bound]
            yield HostBatch(self._schema, vecs, b.num_rows)

    def _arg_string(self):
        return f"[{', '.join(map(repr, self.exprs))}]"


class CpuFilterExec(PhysicalPlan):
    def __init__(self, condition: Expression, child: PhysicalPlan):
        super().__init__([child])
        self.condition = condition
        self._bound = bind_references(condition, child.output)

    @property
    def output(self) -> Schema:
        return self.children[0].output

    def execute_cpu(self):
        for b in self.children[0].execute_cpu():
            ctx = _ctx(b.num_rows)
            pred = self._bound.eval(ctx, b.vecs)
            keep = np.nonzero(pred.data & pred.validity)[0]
            vecs = [v.gather(np, keep) for v in b.vecs]
            yield HostBatch(self.output, vecs, len(keep))

    def _arg_string(self):
        return f"[{self.condition!r}]"


@dataclasses.dataclass
class AggExpr:
    func: AggregateFunction
    name: str


class CpuHashAggregateExec(PhysicalPlan):
    """Dict-based grouping (np.unique over packed key rows) — intentionally a
    different algorithm from the device's sort-segmented reduction."""

    def __init__(self, group_exprs: Sequence[Expression],
                 aggs: Sequence[AggExpr], child: PhysicalPlan):
        super().__init__([child])
        self.group_exprs = list(group_exprs)
        self.aggs = list(aggs)
        self._bound_groups = [bind_references(e, child.output)
                              for e in self.group_exprs]
        self._bound_aggs = []
        for a in self.aggs:
            f = a.func
            if f.child is not None:
                f = f.with_children([bind_references(f.child, child.output)])
            self._bound_aggs.append(AggExpr(f, a.name))
        names = tuple([output_name(e, f"k{i}")
                       for i, e in enumerate(self.group_exprs)] +
                      [a.name for a in self.aggs])
        tps = tuple([e.data_type for e in self._bound_groups] +
                    [a.func.data_type for a in self._bound_aggs])
        self._schema = Schema(names, tps)

    @property
    def output(self) -> Schema:
        return self._schema

    def execute_cpu(self):
        child_batches = list(self.children[0].execute_cpu())
        b = _concat_host(child_batches, self.children[0].output)
        n = b.num_rows
        ctx = _ctx(n)
        keys = [e.eval(ctx, b.vecs) for e in self._bound_groups]
        gid, groups_index = _cpu_group_ids(keys, n)
        ng = len(groups_index)
        out_vecs: List[Vec] = [k.gather(np, groups_index) for k in keys]
        for a in self._bound_aggs:
            out_vecs.append(_cpu_agg(a.func, ctx, b, gid, ng))
        yield HostBatch(self._schema, out_vecs, ng)

    def _arg_string(self):
        return (f"[keys={[repr(e) for e in self.group_exprs]}, "
                f"aggs={[a.name for a in self.aggs]}]")


def _take_np(arr, idx):
    return arr[idx] if arr.ndim == 1 else arr[idx, :]


def _scalar_of(v: Vec, i: int):
    """Python value of row i of a host Vec (oracle helper). Nested rows
    (array/struct/map) round-trip through the arrow converter so e.g.
    collect_list over nested values yields real python structures."""
    if v.children is not None:
        from ..cpu.hostbatch import host_vec_to_arrow
        return host_vec_to_arrow(v.slice_rows(i, i + 1), 1).to_pylist()[0]
    if v.is_string:
        return bytes(v.data[i, :v.lengths[i]]).decode("utf-8", "replace")
    val = v.data[i]
    return val.item() if hasattr(val, "item") else val


def _key_bytes(keys: List[Vec], n: int) -> np.ndarray:
    """Pack key columns into fixed-width row bytes for np.unique grouping.
    Recurses through nested children, zeroing garbage beyond live slots so
    equal values pack to equal bytes regardless of padding contents."""
    if n == 0:
        return np.zeros((0, 1), np.uint8)
    parts: List[np.ndarray] = []

    def emit(arr):
        parts.append(np.ascontiguousarray(arr).view(np.uint8).reshape(n, -1))

    def rec(v: Vec, live: np.ndarray):
        val = v.validity & live
        emit(val.astype(np.uint8))
        if isinstance(v.dtype, T.ArrayType):
            sizes = np.where(val, v.data, 0).astype(np.int32)
            emit(sizes)
            k = v.children[0].data.shape[v.data.ndim]
            slot_live = val[..., None] & (np.arange(k) < sizes[..., None])
            rec(v.children[0], slot_live)
        elif isinstance(v.dtype, T.StructType):
            for c in v.children:
                rec(c, val)
        elif v.is_string:
            lens = np.where(val, v.lengths, 0).astype(np.int32)
            emit(lens)
            w = v.data.shape[-1]
            col_live = val[..., None] & (np.arange(w) < lens[..., None])
            emit(np.where(col_live, v.data, 0))
        else:
            data = v.data
            if np.issubdtype(data.dtype, np.floating):
                # canonicalize NaN and -0.0 so grouping matches Spark equality
                data = np.where(np.isnan(data), np.float64(np.nan), data)
                data = np.where(data == 0.0, 0.0, data).astype(v.data.dtype)
            emit(np.where(val, data, data.dtype.type(0)))

    for key in keys:
        rec(key, np.ones(n, dtype=bool))
    return np.concatenate(parts, axis=1) if parts else np.zeros((n, 1), np.uint8)


def _cpu_group_ids(keys: List[Vec], n: int):
    if not keys:
        return np.zeros(n, dtype=np.int64), np.zeros(1 if n >= 0 else 0,
                                                     dtype=np.int64)[:1]
    rows = _key_bytes(keys, n)
    packed = rows.view([("", rows.dtype)] * rows.shape[1]).ravel()
    _, first_idx, inv = np.unique(packed, return_index=True, return_inverse=True)
    # renumber groups by first appearance to keep deterministic order
    order = np.argsort(first_idx, kind="stable")
    remap = np.empty_like(order)
    remap[order] = np.arange(len(order))
    gid = remap[inv]
    return gid, first_idx[order]


def _cpu_agg(func: AggregateFunction, ctx, b: HostBatch, gid, ng) -> Vec:
    n = b.num_rows
    if func.child is None:  # count(*)
        data = np.bincount(gid, minlength=ng).astype(np.int64)
        return Vec(T.LONG, data, np.ones(ng, dtype=bool))
    v = func.child.eval(ctx, b.vecs)
    out_t = func.data_type
    if isinstance(func, Count):
        data = np.bincount(gid, weights=v.validity.astype(np.float64),
                           minlength=ng).astype(np.int64)
        return Vec(T.LONG, data, np.ones(ng, dtype=bool))
    valid_any = np.zeros(ng, dtype=bool)
    np.logical_or.at(valid_any, gid, v.validity)
    name = type(func).__name__
    if name == "CountIf":
        hit = v.validity & v.data.astype(bool)
        data = np.bincount(gid, weights=hit.astype(np.float64),
                           minlength=ng).astype(np.int64)
        return Vec(T.LONG, data, np.ones(ng, dtype=bool))
    if name in ("BoolAnd", "BoolOr"):
        out = np.zeros(ng, dtype=bool)
        for g in range(ng):
            sel = (gid == g) & v.validity
            vals = v.data[sel].astype(bool)
            if len(vals):
                out[g] = vals.all() if name == "BoolAnd" else vals.any()
        return Vec(T.BOOLEAN, out, valid_any)
    if name in ("BitAndAgg", "BitOrAgg", "BitXorAgg"):
        out = np.zeros(ng, dtype=np.int64)
        for g in range(ng):
            sel = (gid == g) & v.validity
            vals = [int(x) for x in v.data[sel]]
            if not vals:
                continue
            acc = vals[0]
            for x in vals[1:]:
                acc = (acc & x if name == "BitAndAgg" else
                       acc | x if name == "BitOrAgg" else acc ^ x)
            out[g] = acc
        return Vec(out_t, out.astype(out_t.np_dtype), valid_any)
    if name in ("Skewness", "Kurtosis"):
        out = np.zeros(ng, dtype=np.float64)
        has = np.zeros(ng, dtype=bool)
        x = v.data.astype(np.float64)
        for g in range(ng):
            sel = (gid == g) & v.validity
            vals = x[sel]
            c = len(vals)
            if c == 0:
                continue
            has[g] = True
            mu = vals.mean()
            m2 = ((vals - mu) ** 2).sum()
            if m2 <= 0:
                out[g] = np.nan
            elif name == "Skewness":
                m3 = ((vals - mu) ** 3).sum()
                out[g] = np.sqrt(c) * m3 / m2 ** 1.5
            else:
                m4 = ((vals - mu) ** 4).sum()
                out[g] = c * m4 / (m2 * m2) - 3.0
        return Vec(T.DOUBLE, out, has)
    if name in ("VariancePop", "VarianceSamp", "StddevPop", "StddevSamp"):
        out = np.zeros(ng, dtype=np.float64)
        has = np.zeros(ng, dtype=bool)
        x = v.data.astype(np.float64)
        for g in range(ng):
            sel = (gid == g) & v.validity
            c = int(sel.sum())
            if c == 0 or (func.sample and c < 2):
                continue
            has[g] = True
            out[g] = np.var(x[sel], ddof=1 if func.sample else 0)
        if func.sqrt:
            out = np.sqrt(out)
        return Vec(T.DOUBLE, out, has)
    if name in ("CollectList", "CollectSet"):
        from ..columnar.padding import width_bucket
        lists = []
        for g in range(ng):
            sel = (gid == g) & v.validity
            vals = [_scalar_of(v, i) for i in np.nonzero(sel)[0]]
            if name == "CollectSet":
                vals = sorted(set(vals))
            else:
                vals = sorted(vals)  # both engines emit value-sorted arrays
            lists.append(vals)
        import pyarrow as pa
        from ..cpu.hostbatch import host_vec_from_arrow
        arr = pa.array(lists, type=T.to_arrow(func.data_type))
        return host_vec_from_arrow(arr)
    if name == "ApproximatePercentile":
        x = v.data.astype(np.float64)
        rows = []
        for g in range(ng):
            sel = (gid == g) & v.validity
            vals = np.sort(x[sel])
            if len(vals) == 0:
                rows.append(None)
                continue
            picks = [float(vals[int(round(q * (len(vals) - 1)))])
                     for q in func.percentages]
            rows.append(picks[0] if func.scalar else picks)
        import pyarrow as pa
        from ..cpu.hostbatch import host_vec_from_arrow
        return host_vec_from_arrow(
            pa.array(rows, type=T.to_arrow(func.data_type)))
    if name == "Sum" and isinstance(out_t, T.DecimalType) and (
            out_t.precision > T.DecimalType.MAX_LONG_DIGITS or
            v.data.ndim == 2):
        # decimal128 oracle: exact python-int accumulation
        from ..expr.decimal128 import join_int, split_int
        sums = [0] * ng
        for i in np.nonzero(v.validity)[0]:
            if v.data.ndim == 2:
                sums[gid[i]] += join_int(int(v.data[i, 0]),
                                         int(v.data[i, 1]))
            else:
                sums[gid[i]] += int(v.data[i])
        bound = 10 ** out_t.precision - 1
        ok = np.array([abs(s) <= bound for s in sums])
        if out_t.precision > T.DecimalType.MAX_LONG_DIGITS:
            limbs = np.zeros((ng, 2), np.int64)
            for g, s in enumerate(sums):
                if ok[g]:
                    limbs[g] = split_int(s)
            return Vec(out_t, limbs, valid_any & ok)
        return Vec(out_t, np.array([s if o else 0
                                    for s, o in zip(sums, ok)], np.int64),
                   valid_any & ok)
    if name in ("Min", "Max") and v.data.ndim == 2 and not v.is_string:
        from ..expr.decimal128 import join_int, split_int
        best = [None] * ng
        for i in np.nonzero(v.validity)[0]:
            x = join_int(int(v.data[i, 0]), int(v.data[i, 1]))
            g = gid[i]
            if best[g] is None or (x < best[g] if name == "Min"
                                   else x > best[g]):
                best[g] = x
        limbs = np.zeros((ng, 2), np.int64)
        has = np.zeros(ng, bool)
        for g, x in enumerate(best):
            if x is not None:
                has[g] = True
                limbs[g] = split_int(x)
        return Vec(v.dtype, limbs, has)
    if name == "Average" and isinstance(out_t, T.DecimalType):
        # exact, in python ints: sum / count HALF_UP at the result scale
        # (what exec/aggregate.avg_decimal computes in limbs)
        from ..expr.decimal128 import join_int, split_int
        sums, cnts = [0] * ng, [0] * ng
        for i in np.nonzero(v.validity)[0]:
            sums[gid[i]] += join_int(int(v.data[i, 0]), int(v.data[i, 1])) \
                if v.data.ndim == 2 else int(v.data[i])
            cnts[gid[i]] += 1
        sum_bound = 10 ** func.sum_type.precision - 1
        out_bound = 10 ** out_t.precision - 1
        shift = 10 ** (out_t.scale - func.sum_type.scale)
        vals, ok = [0] * ng, np.zeros(ng, bool)
        for g in range(ng):
            if cnts[g] and abs(sums[g]) <= sum_bound:
                q = (2 * abs(sums[g]) * shift + cnts[g]) // (2 * cnts[g])
                vals[g] = -q if sums[g] < 0 else q
                ok[g] = q <= out_bound
        if out_t.precision > T.DecimalType.MAX_LONG_DIGITS:
            limbs = np.zeros((ng, 2), np.int64)
            for g in np.nonzero(ok)[0]:
                limbs[g] = split_int(vals[g])
            return Vec(out_t, limbs, ok)
        return Vec(out_t, np.array([x if o else 0 for x, o in zip(vals, ok)],
                                   np.int64), ok)
    if name in ("Sum", "Average"):
        acc_t = np.float64 if T.is_floating(v.dtype) or name == "Average" \
            else np.int64
        if name == "Sum" and ctx.ansi and acc_t is np.int64:
            # exact accumulator-overflow detection via python ints (Spark
            # ANSI: SUM over BIGINT raises instead of wrapping)
            sums = [0] * ng
            for i in np.nonzero(v.validity)[0]:
                sums[gid[i]] += int(v.data[i])
            if any(x < -2**63 or x > 2**63 - 1 for x in sums):
                from ..errors import AnsiViolation
                raise AnsiViolation("[ARITHMETIC_OVERFLOW] long overflow")
            return Vec(out_t, np.array(sums, dtype=np.int64), valid_any)
        contrib = np.where(v.validity, v.data, 0).astype(acc_t)
        s = np.zeros(ng, dtype=acc_t)
        np.add.at(s, gid, contrib)
        if name == "Sum":
            return Vec(out_t, s.astype(out_t.np_dtype), valid_any)
        cnt = np.bincount(gid, weights=v.validity.astype(np.float64),
                          minlength=ng)
        avg = np.divide(s, np.maximum(cnt, 1))
        return Vec(out_t, avg.astype(out_t.np_dtype), valid_any)
    if name in ("Min", "Max"):
        if v.is_string:
            # simple per-group loop (CPU oracle; strings rarely huge here)
            out_data = np.zeros((ng, v.data.shape[1]), np.uint8)
            out_len = np.zeros(ng, np.int32)
            seen = np.zeros(ng, dtype=bool)
            for i in range(n):
                if not v.validity[i]:
                    continue
                g = gid[i]
                s_bytes = bytes(v.data[i, :v.lengths[i]])
                if not seen[g]:
                    best = s_bytes
                else:
                    cur = bytes(out_data[g, :out_len[g]])
                    best = (min if name == "Min" else max)(cur, s_bytes)
                out_data[g, :] = 0
                out_data[g, :len(best)] = np.frombuffer(best, np.uint8)
                out_len[g] = len(best)
                seen[g] = True
            return Vec(v.dtype, out_data, seen, out_len)
        if np.issubdtype(v.data.dtype, np.floating):
            neutral = v.data.dtype.type(np.inf if name == "Min" else -np.inf)
        elif v.data.dtype == np.bool_:
            neutral = np.bool_(name == "Min")
        else:
            info = np.iinfo(v.data.dtype)
            neutral = v.data.dtype.type(info.max if name == "Min" else info.min)
        contrib = np.where(v.validity, v.data, neutral)
        out = np.full(ng, neutral, dtype=v.data.dtype)
        (np.minimum if name == "Min" else np.maximum).at(out, gid, contrib)
        return Vec(v.dtype, out, valid_any)
    if name in ("First", "Last"):
        idx = np.arange(n)
        sel = np.where(v.validity if func.ignore_nulls else np.ones(n, bool),
                       idx, -1)
        out_idx = np.full(ng, -1, dtype=np.int64)
        if name == "First":
            for i in range(n - 1, -1, -1):
                if sel[i] >= 0:
                    out_idx[gid[i]] = sel[i]
        else:
            for i in range(n):
                if sel[i] >= 0:
                    out_idx[gid[i]] = sel[i]
        got = out_idx >= 0
        safe = np.where(got, out_idx, 0)
        return Vec(v.dtype, _take_np(v.data, safe),
                   v.validity[safe] & got,
                   None if v.lengths is None else v.lengths[safe])
    raise NotImplementedError(name)


class CpuGenerateExec(PhysicalPlan):
    """CPU oracle for Generate (explode/posexplode, optionally _outer):
    child rows replicated per array element, generator columns appended
    (reference GenerateExec / GpuGenerateExec.scala)."""

    def __init__(self, generator, child: PhysicalPlan):
        from ..expr.collections import Explode
        super().__init__([child])
        assert isinstance(generator, Explode)
        self.generator = generator
        self._bound = bind_references(generator, child.output)
        co = child.output
        gen_out = self._bound.generator_output()
        self._schema = Schema(co.names + tuple(n for n, _ in gen_out),
                              co.types + tuple(t for _, t in gen_out))

    @property
    def output(self) -> Schema:
        return self._schema

    def execute_cpu(self):
        from ..cpu.hostbatch import vec_map_arrays
        outer = self._bound.outer
        for b in self.children[0].execute_cpu():
            n = b.num_rows
            arr = self._bound.children[0].eval(_ctx(n), b.vecs)
            elem = arr.children[0]
            k = elem.data.shape[1]
            sizes = np.where(arr.validity, arr.data, 0).astype(np.int64)
            slots = np.maximum(sizes, 1) if outer else sizes
            total = int(slots.sum())
            row_id = np.repeat(np.arange(n), slots)
            base = np.concatenate(([0], np.cumsum(slots)[:-1]))
            pos = np.arange(total) - np.repeat(base, slots)
            out_vecs = [v.gather(np, row_id) for v in b.vecs]
            live = pos < sizes[row_id]  # outer's filler row stays null
            if self._bound.position:
                # pos is NULL on the outer filler row too (Spark joins the
                # generator null row, nulling every generator column)
                out_vecs.append(Vec(T.INT, pos.astype(np.int32), live.copy()))
            safe = np.minimum(pos, max(k - 1, 0))
            col = vec_map_arrays(elem, lambda a: a[row_id, safe])
            col = Vec(col.dtype, col.data, col.validity & live, col.lengths,
                      col.children)
            yield HostBatch(self._schema, out_vecs + [col], total)

    def _arg_string(self):
        return f"[{self.generator!r}]"


class CpuHashJoinExec(PhysicalPlan):
    """CPU oracle join (independent of the device path). Covers equi joins with
    an optional extra condition, pure condition / cartesian joins (no keys), and
    join types inner/cross/left/right/full/semi/anti/existence. Reference
    semantics: GpuHashJoin.scala, GpuBroadcastNestedLoopJoinExecBase.scala,
    GpuCartesianProductExec.scala, ExistenceJoin handling."""

    def __init__(self, left: PhysicalPlan, right: PhysicalPlan,
                 left_keys: Sequence[Expression], right_keys: Sequence[Expression],
                 join_type: str = "inner", condition: Expression = None):
        super().__init__([left, right])
        self.left_keys = list(left_keys)
        self.right_keys = list(right_keys)
        self.join_type = "inner" if join_type == "cross" else join_type
        self.condition = condition
        self._bl = [bind_references(e, left.output) for e in self.left_keys]
        self._br = [bind_references(e, right.output) for e in self.right_keys]
        lo, ro = left.output, right.output
        combined = Schema(lo.names + ro.names, lo.types + ro.types)
        self._bcond = None if condition is None else \
            bind_references(condition, combined)
        from ..columnar.batch import join_output_schema
        self._schema = join_output_schema(lo, ro, self.join_type)

    @property
    def output(self) -> Schema:
        return self._schema

    def _candidate_pairs(self, left, right):
        """(li, ri) int64 arrays of key-equal candidate pairs; all pairs when
        keyless (cartesian / pure-condition join)."""
        nl, nr = left.num_rows, right.num_rows
        if not self._bl:
            return (np.repeat(np.arange(nl, dtype=np.int64), nr),
                    np.tile(np.arange(nr, dtype=np.int64), nl))
        lk = _key_bytes([e.eval(_ctx(nl), left.vecs) for e in self._bl], nl)
        rk = _key_bytes([e.eval(_ctx(nr), right.vecs) for e in self._br], nr)
        # null keys never match (standard equi-join): a key row is joinable only
        # if every key's validity byte is 1
        lvalid = _all_keys_valid([e.eval(_ctx(nl), left.vecs)
                                  for e in self._bl], nl)
        rvalid = _all_keys_valid([e.eval(_ctx(nr), right.vecs)
                                  for e in self._br], nr)
        rmap: dict = {}
        for r in np.nonzero(rvalid)[0]:
            rmap.setdefault(rk[r].tobytes(), []).append(r)
        li, ri = [], []
        for i in np.nonzero(lvalid)[0]:
            for r in rmap.get(lk[i].tobytes(), ()):
                li.append(i)
                ri.append(r)
        return (np.array(li, dtype=np.int64), np.array(ri, dtype=np.int64))

    def execute_cpu(self):
        left = _concat_host(list(self.children[0].execute_cpu()),
                            self.children[0].output)
        right = _concat_host(list(self.children[1].execute_cpu()),
                             self.children[1].output)
        nl, nr = left.num_rows, right.num_rows
        li0, ri0 = self._candidate_pairs(left, right)
        if self._bcond is not None and len(li0):
            pair_vecs = _gather_side(left, li0) + _gather_side(right, ri0)
            cv = self._bcond.eval(_ctx(len(li0)), pair_vecs)
            ok = np.asarray(cv.data, dtype=bool) & np.asarray(cv.validity)
            li0, ri0 = li0[ok], ri0[ok]

        jt = self.join_type
        matched_l = np.zeros(nl, dtype=bool)
        matched_l[li0] = True
        li, ri = list(li0), list(ri0)
        if jt == "inner":
            pass
        elif jt in ("left", "full", "right"):
            if jt in ("left", "full"):
                for i in np.nonzero(~matched_l)[0]:
                    li.append(i)
                    ri.append(-1)
            if jt in ("right", "full"):
                matched_r = np.zeros(nr, dtype=bool)
                matched_r[ri0] = True
                for r in np.nonzero(~matched_r)[0]:
                    li.append(-1)
                    ri.append(r)
        elif jt == "semi":
            li = list(np.nonzero(matched_l)[0])
        elif jt == "anti":
            li = list(np.nonzero(~matched_l)[0])
        elif jt == "existence":
            exists = Vec(T.BooleanType(), matched_l, np.ones(nl, dtype=bool))
            yield HostBatch(self._schema, list(left.vecs) + [exists], nl)
            return
        else:
            raise ValueError(jt)
        li = np.array(li, dtype=np.int64)
        ri = np.array(ri, dtype=np.int64)
        out_vecs = _gather_side(left, li) if jt in ("semi", "anti") else \
            _gather_side(left, li) + _gather_side(right, ri)
        yield HostBatch(self._schema, out_vecs, len(li))

    def _arg_string(self):
        cond = "" if self.condition is None else f", cond={self.condition!r}"
        return f"[{self.join_type}, keys={[repr(e) for e in self.left_keys]}" \
               f"{cond}]"


def _all_keys_valid(keys: List[Vec], n: int) -> np.ndarray:
    ok = np.ones(n, dtype=bool)
    for k in keys:
        ok &= k.validity
    return ok


def _gather_side(b: HostBatch, idx: np.ndarray) -> List[Vec]:
    """Gather with -1 meaning null row (outer join padding)."""
    missing = idx < 0
    safe = np.where(missing, 0, idx)
    out = []
    for v in b.vecs:
        if v.data.shape[0] == 0:
            # empty side of an outer join: every requested row is the null pad
            ev = _empty_vec(v.dtype, (len(idx),))
            out.append(ev)
            continue
        g = v.gather(np, safe)
        out.append(Vec(g.dtype, g.data, g.validity & ~missing, g.lengths,
                       g.children))
    return out


class CpuSortExec(PhysicalPlan):
    def __init__(self, orders: Sequence[Tuple[Expression, bool, bool]],
                 child: PhysicalPlan):
        """orders: (expr, ascending, nulls_first)."""
        super().__init__([child])
        self.orders = list(orders)
        self._bound = [(bind_references(e, child.output), a, nf)
                       for e, a, nf in self.orders]

    @property
    def output(self) -> Schema:
        return self.children[0].output

    def execute_cpu(self):
        from ..ops.rowops import sort_keys_for, lexsort_indices
        b = _concat_host(list(self.children[0].execute_cpu()),
                         self.children[0].output)
        ctx = _ctx(b.num_rows)
        groups = []
        for e, asc, nf in self._bound:
            groups.append(sort_keys_for(np, e.eval(ctx, b.vecs), asc, nf))
        order = lexsort_indices(np, groups, b.num_rows)
        vecs = [v.gather(np, order) for v in b.vecs]
        yield HostBatch(self.output, vecs, b.num_rows)

    def _arg_string(self):
        return f"[{[(repr(e), a, nf) for e, a, nf in self.orders]}]"


class CpuSampleExec(PhysicalPlan):
    """Bernoulli sample without replacement (GpuSampleExec analog): a
    deterministic splitmix64 hash of the GLOBAL row ordinal decides each row,
    so device and CPU engines select identical rows for a given seed."""

    def __init__(self, fraction: float, seed: int, child: PhysicalPlan):
        super().__init__([child])
        if not 0.0 <= fraction <= 1.0:
            raise ValueError(f"sample fraction must be in [0, 1]: {fraction}")
        self.fraction = float(fraction)
        self.seed = int(seed)

    @property
    def output(self) -> Schema:
        return self.children[0].output

    def execute_cpu(self):
        from ..ops.rowops import sample_mask
        offset = 0
        for b in self.children[0].execute_cpu():
            keep = sample_mask(np, b.num_rows, offset, self.fraction,
                               self.seed)
            offset += b.num_rows
            idx = np.nonzero(keep)[0]
            vecs = [_gather_host_vec(v, idx) for v in b.vecs]
            yield HostBatch(self.output, vecs, len(idx))

    def _arg_string(self):
        return f"[fraction={self.fraction}, seed={self.seed}]"


def _gather_host_vec(v: Vec, idx) -> Vec:
    return Vec(v.dtype, _take_np(v.data, idx), v.validity[idx],
               None if v.lengths is None else v.lengths[idx],
               None if v.children is None else tuple(
                   _gather_host_vec(c, idx) for c in v.children))


class CpuLimitExec(PhysicalPlan):
    def __init__(self, limit: int, child: PhysicalPlan, offset: int = 0):
        super().__init__([child])
        self.limit = limit
        self.offset = offset

    @property
    def output(self) -> Schema:
        return self.children[0].output

    def execute_cpu(self):
        remaining = self.limit
        skip = self.offset
        for b in self.children[0].execute_cpu():
            if remaining <= 0:
                break
            start = min(skip, b.num_rows)
            skip -= start
            take = min(remaining, b.num_rows - start)
            vecs = [v.slice_rows(start, start + take) for v in b.vecs]
            remaining -= take
            yield HostBatch(self.output, vecs, take)

    def _arg_string(self):
        return f"[{self.limit}]"


class CpuUnionExec(PhysicalPlan):
    def __init__(self, children: Sequence[PhysicalPlan]):
        super().__init__(children)

    @property
    def output(self) -> Schema:
        return self.children[0].output

    def execute_cpu(self):
        for c in self.children:
            yield from c.execute_cpu()


class CpuRangeExec(PhysicalPlan):
    def __init__(self, start: int, end: int, step: int = 1):
        super().__init__([])
        self.start, self.end, self.step = start, end, step
        self._schema = Schema(("id",), (T.LONG,))

    @property
    def output(self) -> Schema:
        return self._schema

    def execute_cpu(self):
        data = np.arange(self.start, self.end, self.step, dtype=np.int64)
        yield HostBatch(self._schema,
                        [Vec(T.LONG, data, np.ones(len(data), bool))],
                        len(data))

    def _arg_string(self):
        return f"[{self.start}, {self.end}, {self.step}]"


class CpuExpandExec(PhysicalPlan):
    """Multiple projections per input row (rollup/cube building block)."""

    def __init__(self, projections: Sequence[Sequence[Expression]],
                 names: Sequence[str], child: PhysicalPlan):
        super().__init__([child])
        self.projections = [list(p) for p in projections]
        self._bound = [[bind_references(e, child.output) for e in p]
                       for p in self.projections]
        tps = tuple(e.data_type for e in self._bound[0])
        self._schema = Schema(tuple(names), tps)

    @property
    def output(self) -> Schema:
        return self._schema

    def execute_cpu(self):
        for b in self.children[0].execute_cpu():
            ctx = _ctx(b.num_rows)
            for proj in self._bound:
                vecs = [e.eval(ctx, b.vecs) for e in proj]
                yield HostBatch(self._schema, vecs, b.num_rows)


class CpuWindowExec(PhysicalPlan):
    """CPU oracle for window functions: sort by (partition, order), then brute-
    force per-partition loops. Deliberately O(n*frame) python/numpy — an
    independent oracle for the device's scan-based kernels (the role CPU Spark
    plays for `GpuWindowExec.scala`)."""

    def __init__(self, window_exprs: Sequence[Tuple[Any, str]],
                 partition_spec: Sequence[Expression],
                 order_spec: Sequence[Tuple[Expression, bool, bool]],
                 child: PhysicalPlan):
        super().__init__([child])
        self.window_exprs = list(window_exprs)
        self.partition_spec = list(partition_spec)
        self.order_spec = list(order_spec)
        self._bound_part = [bind_references(e, child.output)
                            for e in self.partition_spec]
        self._bound_order = [(bind_references(e, child.output), a, nf)
                             for e, a, nf in self.order_spec]
        from ..expr.windowexprs import WindowAggregate, bind_window_fn
        self._bound_fns = [(bind_window_fn(f, child.output), name)
                           for f, name in self.window_exprs]
        for f, name in self._bound_fns:
            if isinstance(f, WindowAggregate) and f.func.child is not None \
                    and type(f.func).__name__ in ("Sum", "Average") \
                    and isinstance(f.func.child.data_type, T.StringType):
                raise TypeError(
                    f"window column {name}: {type(f.func).__name__} over "
                    "STRING is invalid")
        co = child.output
        names = co.names + tuple(n for _, n in self.window_exprs)
        tps = co.types + tuple(f.data_type for f, _ in self._bound_fns)
        self._schema = Schema(names, tps)

    @property
    def output(self) -> Schema:
        return self._schema

    def execute_cpu(self):
        from ..ops.rowops import gather_vecs, lexsort_indices, sort_keys_for
        b = _concat_host(list(self.children[0].execute_cpu()),
                         self.children[0].output)
        n = b.num_rows
        ctx = _ctx(n)
        part_vecs = [e.eval(ctx, b.vecs) for e in self._bound_part]
        order_vecs = [(e.eval(ctx, b.vecs), a, nf)
                      for e, a, nf in self._bound_order]
        groups = [sort_keys_for(np, v, True, True) for v in part_vecs]
        groups += [sort_keys_for(np, v, a, nf) for v, a, nf in order_vecs]
        perm = lexsort_indices(np, groups, n) if groups else np.arange(n)
        svecs = gather_vecs(np, b.vecs, perm)
        sorder_vecs = gather_vecs(np, [v for v, _, _ in order_vecs], perm)
        spart = _key_bytes(gather_vecs(np, part_vecs, perm), n)
        sorder = _key_bytes(sorder_vecs, n)

        # partition boundaries
        part_start = np.ones(n, dtype=bool)
        if n:
            part_start[1:] = np.any(spart[1:] != spart[:-1], axis=1) \
                if spart.shape[1] else False
            part_start[0] = True
        peer_start = part_start.copy()
        if n and sorder.shape[1]:
            peer_start[1:] |= np.any(sorder[1:] != sorder[:-1], axis=1)
        starts = np.nonzero(part_start)[0]
        bounds = list(starts) + [n]

        out_vecs = list(svecs)
        sctx = _ctx(n)
        for fn, name in self._bound_fns:
            out_vecs.append(self._eval_fn(fn, sctx, svecs, n, bounds,
                                          peer_start, sorder_vecs))
        yield HostBatch(self._schema, out_vecs, n)

    def _eval_fn(self, fn, ctx, svecs, n, bounds, peer_start,
                 sorder_vecs) -> Vec:
        from ..expr.windowexprs import (CumeDist, DenseRank, Lag, Lead,
                                        NthValue, NTile,
                                        PercentRank, RangeFrame, Rank,
                                        RowFrame, RowNumber, WindowAggregate,
                                        default_frame)
        parts = [(bounds[i], bounds[i + 1]) for i in range(len(bounds) - 1)]
        if isinstance(fn, RowNumber):
            data = np.zeros(n, np.int32)
            for lo, hi in parts:
                data[lo:hi] = np.arange(1, hi - lo + 1)
            return Vec(T.INT, data, np.ones(n, bool))
        if isinstance(fn, (Rank, DenseRank, PercentRank, CumeDist)):
            rank = np.zeros(n, np.int64)
            dense = np.zeros(n, np.int64)
            cnt = np.zeros(n, np.int64)
            peer_cnt = np.zeros(n, np.int64)
            for lo, hi in parts:
                r = d = 0
                for i in range(lo, hi):
                    if peer_start[i] or i == lo:
                        r = i - lo + 1
                        d += 1
                    rank[i] = r
                    dense[i] = d
                cnt[lo:hi] = hi - lo
                # rows <= last peer of i (for cume_dist)
                j = lo
                while j < hi:
                    k = j + 1
                    while k < hi and not peer_start[k]:
                        k += 1
                    peer_cnt[j:k] = k - lo
                    j = k
            if isinstance(fn, Rank):
                return Vec(T.INT, rank.astype(np.int32), np.ones(n, bool))
            if isinstance(fn, DenseRank):
                return Vec(T.INT, dense.astype(np.int32), np.ones(n, bool))
            if isinstance(fn, PercentRank):
                denom = np.maximum(cnt - 1, 1)
                out = np.where(cnt > 1, (rank - 1) / denom, 0.0)
                return Vec(T.DOUBLE, out.astype(np.float64), np.ones(n, bool))
            return Vec(T.DOUBLE, (peer_cnt / np.maximum(cnt, 1))
                       .astype(np.float64), np.ones(n, bool))
        if isinstance(fn, NTile):
            data = np.zeros(n, np.int32)
            for lo, hi in parts:
                c = hi - lo
                q, r = divmod(c, fn.buckets)
                for i in range(lo, hi):
                    rn = i - lo  # 0-based
                    if q == 0:
                        data[i] = rn + 1
                    elif rn < r * (q + 1):
                        data[i] = rn // (q + 1) + 1
                    else:
                        data[i] = r + (rn - r * (q + 1)) // q + 1
            return Vec(T.INT, data, np.ones(n, bool))
        if isinstance(fn, (Lead, Lag)):
            v = fn.children[0].eval(ctx, svecs)
            off = fn.offset if isinstance(fn, Lead) else -fn.offset
            idx = np.arange(n) + off
            part_id = np.cumsum(np.isin(np.arange(n), bounds[:-1])) - 1
            in_range = (idx >= 0) & (idx < n)
            safe = np.where(in_range, idx, 0)
            same = in_range & (part_id[safe] == part_id)
            data = _take_np(v.data, safe)
            valid = v.validity[safe] & same
            lens = None if v.lengths is None else v.lengths[safe]
            if fn.default is not None:
                from .. import types as TT
                dv = fn.default
                if isinstance(v.dtype, TT.StringType):
                    enc = dv.encode("utf-8")
                    w = max(v.data.shape[1], len(enc))
                    if w > v.data.shape[1]:
                        data = np.pad(data, ((0, 0), (0, w - v.data.shape[1])))
                    drow = np.zeros(w, np.uint8)
                    drow[:len(enc)] = np.frombuffer(enc, np.uint8)
                    data = np.where(same[:, None], data, drow)
                    lens = np.where(same, lens, len(enc)).astype(np.int32)
                else:
                    data = np.where(same, data, v.data.dtype.type(dv))
                valid = np.where(same, valid, True)
            return Vec(v.dtype, data, valid, lens)
        if isinstance(fn, NthValue):
            frame = fn.frame or default_frame(bool(self.order_spec))
            v = fn.children[0].eval(ctx, svecs)
            data = np.zeros(n, v.data.dtype) if v.lengths is None else None
            sdata = (np.zeros((n, v.data.shape[1]), np.uint8)
                     if v.lengths is not None else None)
            slens = np.zeros(n, np.int32) if v.lengths is not None else None
            valid = np.zeros(n, bool)
            for lo, hi in parts:
                for i in range(lo, hi):
                    flo, fhi = _cpu_frame_bounds(
                        frame, i, lo, hi, peer_start, sorder_vecs,
                        self.order_spec)
                    if fhi < flo:
                        continue
                    if fn.ignore_nulls:
                        cand = [j for j in range(flo, fhi + 1)
                                if v.validity[j]]
                        if len(cand) < fn.n:
                            continue
                        j = cand[fn.n - 1]
                    else:
                        j = flo + fn.n - 1
                        if j > fhi:
                            continue
                        if not v.validity[j]:
                            continue
                    valid[i] = True
                    if sdata is not None:
                        slens[i] = v.lengths[j]
                        sdata[i, :] = v.data[j, :]
                    else:
                        data[i] = v.data[j]
            if sdata is not None:
                return Vec(v.dtype, sdata, valid, slens)
            return Vec(v.dtype, data, valid)
        if isinstance(fn, WindowAggregate):
            frame = fn.frame or default_frame(bool(self.order_spec))
            func = fn.func
            child = func.child
            v = child.eval(ctx, svecs) if child is not None else None
            out_t = func.data_type
            # a decimal result is a Python int (its unscaled value) until it
            # is stored: a 128-bit one as its limb pair
            from ..expr.decimal128 import (is_dec128, split_int,
                                           unscaled_ints)
            wide = is_dec128(out_t)
            data = np.zeros((n, 2) if wide else n,
                            np.int64 if wide else out_t.np_dtype)
            valid = np.zeros(n, bool)
            ints = unscaled_ints(v.data) if v is not None and \
                isinstance(v.dtype, T.DecimalType) else None
            # string scratch only when the RESULT is a string (min/max/first/
            # last over strings) — Count over a string column yields LONG
            slens = sdata = None
            if v is not None and v.is_string and isinstance(out_t, T.StringType):
                sdata = np.zeros((n, v.data.shape[1]), np.uint8)
                slens = np.zeros(n, np.int32)
            is_count = type(func).__name__ == "Count"
            for lo, hi in parts:
                for i in range(lo, hi):
                    flo, fhi = _cpu_frame_bounds(
                        frame, i, lo, hi, peer_start, sorder_vecs,
                        self.order_spec)
                    if fhi < flo:
                        if is_count:  # COUNT over an empty frame is 0
                            valid[i] = True
                        continue
                    sl = slice(flo, fhi + 1)
                    r = _cpu_window_agg(func, v, sl, ints)
                    if r is None:
                        continue
                    valid[i] = True
                    if sdata is not None and isinstance(r, bytes):
                        sdata[i, :len(r)] = np.frombuffer(r, np.uint8)
                        slens[i] = len(r)
                    elif wide:
                        data[i] = split_int(r)
                    else:
                        data[i] = r
            if sdata is not None:
                return Vec(v.dtype, sdata, valid, slens)
            return Vec(out_t, data, valid)
        raise NotImplementedError(type(fn).__name__)

    def _arg_string(self):
        return (f"[{[n for _, n in self.window_exprs]}, "
                f"part={[repr(e) for e in self.partition_spec]}]")


def _cpu_frame_bounds(frame, i, lo, hi, peer_start, sorder_vecs, order_spec):
    """Inclusive (start, end) row indices of the frame for row i."""
    from ..expr.windowexprs import RangeFrame, RowFrame
    if isinstance(frame, RowFrame):
        flo = lo if frame.lower is None else max(lo, i + frame.lower)
        fhi = hi - 1 if frame.upper is None else min(hi - 1, i + frame.upper)
        return flo, fhi
    assert isinstance(frame, RangeFrame)
    if frame.lower is None and frame.upper is None:
        return lo, hi - 1
    if frame.lower is None and frame.upper == 0:
        # UNBOUNDED PRECEDING .. CURRENT ROW: through the last peer of row i
        k = i + 1
        while k < hi and not peer_start[k]:
            k += 1
        return lo, k - 1
    # value-offset range frame: rows whose single numeric order key lies in
    # [key(i)+lower, key(i)+upper] (Spark restricts these to one order column)
    if len(sorder_vecs) != 1:
        raise NotImplementedError(
            "value-offset RANGE frames require exactly one order column")
    key = sorder_vecs[0]
    if key.is_string:
        raise NotImplementedError(
            "value-offset RANGE frames need a numeric order column")
    _, ascending, _ = order_spec[0]
    if not key.validity[i]:
        # a null current row frames exactly its null peer group
        k = i + 1
        while k < hi and not peer_start[k]:
            k += 1
        j = i
        while j > lo and not peer_start[j]:
            j -= 1
        return j, k - 1
    # frame includes rows at sort-axis delta in [lower, upper]; for descending
    # order the sort axis is the negated key, so key(j) in [cur-upper, cur-lo]
    cur = key.data[i]
    if ascending:
        lo_v = -np.inf if frame.lower is None else cur + frame.lower
        hi_v = np.inf if frame.upper is None else cur + frame.upper
    else:
        lo_v = -np.inf if frame.upper is None else cur - frame.upper
        hi_v = np.inf if frame.lower is None else cur - frame.lower
    flo, fhi = hi, lo - 1  # empty unless a row matches
    for j in range(lo, hi):
        if not key.validity[j]:
            continue
        v = key.data[j]
        if lo_v <= v <= hi_v:
            flo = min(flo, j)
            fhi = max(fhi, j)
    return flo, fhi


def _cpu_window_agg(func, v, sl, ints=None):
    """Aggregate v[sl] (null-skipping; First/Last respect nulls, Spark default);
    returns python scalar / bytes / None. `ints` are a decimal column's
    unscaled values (`decimal128.unscaled_ints`): every decimal aggregate is computed
    on them, exactly, and returns the result's unscaled Python int (None
    where it leaves the result type, as Spark's non-ANSI overflow does)."""
    name = type(func).__name__
    if v is None:  # count(*)
        return sl.stop - sl.start
    valid = v.validity[sl]
    if name == "Count":
        return int(valid.sum())
    if name in ("First", "Last"):
        if getattr(func, "ignore_nulls", False):
            idxs = [k for k in range(sl.start, sl.stop) if v.validity[k]]
            if not idxs:
                return None
            j = idxs[0] if name == "First" else idxs[-1]
        else:
            j = sl.start if name == "First" else sl.stop - 1
        if not v.validity[j]:
            return None
        if v.is_string:
            return bytes(v.data[j, :v.lengths[j]])
        return v.data[j] if ints is None else ints[j]
    if not valid.any():
        return None
    if v.is_string:
        vals = [bytes(v.data[j, :v.lengths[j]])
                for j in range(sl.start, sl.stop) if v.validity[j]]
        if name == "Min":
            return min(vals)
        if name == "Max":
            return max(vals)
        raise NotImplementedError(f"{name} over strings")
    if ints is not None:
        vals = [ints[j] for j in range(sl.start, sl.stop) if v.validity[j]]
        if name == "Min":
            return min(vals)
        if name == "Max":
            return max(vals)
        total, out_t = sum(vals), func.data_type
        if name == "Sum":
            return total if abs(total) < 10 ** out_t.precision else None
        if name == "Average":
            # the sum as decimal(p + 10, s), then / count HALF_UP at s + 4
            if abs(total) >= 10 ** func.sum_type.precision:
                return None
            shift = 10 ** (out_t.scale - func.sum_type.scale)
            q = (2 * abs(total) * shift + len(vals)) // (2 * len(vals))
            if q >= 10 ** out_t.precision:
                return None
            return -q if total < 0 else q
        raise NotImplementedError(name)
    vals = v.data[sl][valid]
    if name == "Sum":
        return vals.sum()
    if name == "Min":
        return vals.min()
    if name == "Max":
        return vals.max()
    if name == "Average":
        return float(vals.astype(np.float64).mean())
    raise NotImplementedError(name)


@dataclasses.dataclass
class HashPartitionSpec:
    """Plan-level partitioning descriptors (Spark's Partitioning expressions).
    Lowered to device partitioners by exec/exchange.make_partitioner."""
    keys: List[Any]
    num_partitions: int

    def __repr__(self):
        return f"hashpartitioning({self.keys}, {self.num_partitions})"


@dataclasses.dataclass
class RangePartitionSpec:
    key: Any
    num_partitions: int
    ascending: bool = True
    nulls_first: bool = True

    def __repr__(self):
        return f"rangepartitioning({self.key}, {self.num_partitions})"


@dataclasses.dataclass
class RoundRobinPartitionSpec:
    num_partitions: int

    def __repr__(self):
        return f"roundrobinpartitioning({self.num_partitions})"


@dataclasses.dataclass
class SinglePartitionSpec:
    num_partitions: int = 1

    def __repr__(self):
        return "singlepartitioning"


class CpuShuffleExchangeExec(PhysicalPlan):
    """Partitioned exchange boundary. CPU engine is single-stream so this is a
    pass-through marker; the TPU conversion lowers it to the shuffle manager."""

    def __init__(self, partitioning, child: PhysicalPlan):
        super().__init__([child])
        self.partitioning = partitioning

    @property
    def output(self) -> Schema:
        return self.children[0].output

    def execute_cpu(self):
        yield from self.children[0].execute_cpu()

    def _arg_string(self):
        return f"[{self.partitioning}]"
