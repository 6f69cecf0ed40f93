"""Basic columnar operators (reference `basicPhysicalOperators.scala`:
GpuProjectExec incl. tiered projection, GpuFilterExec, GpuRangeExec, GpuUnionExec;
`GpuExpandExec.scala`; scan bridge)."""

from __future__ import annotations

from typing import Iterator, List, Sequence

import jax.numpy as jnp
import numpy as np

from .. import types as T
from ..columnar.batch import ColumnarBatch, Schema
from ..columnar.padding import row_bucket
from ..compile import instance_jit, kernel_key
from ..expr.base import (EvalContext, Expression, Vec, bind_references,
                         output_name)
from ..ops.rowops import GatherTally, compact_vecs
from ..utils import metrics as M
from .base import (GatherCounts, TpuExec, UnaryTpuExec, batch_vecs,
                   device_ctx, vecs_to_batch)


class TpuScanExec(TpuExec):
    """Host table -> device batches (the HostColumnarToGpu/RowToColumnar analog for
    the in-memory source; file scans in io/ feed the same shape)."""

    def __init__(self, table, conf=None, batch_rows: int = None):
        super().__init__([], conf)
        self.table = table
        self._schema = Schema.from_arrow(table.schema)
        self.batch_rows = batch_rows or self.conf.batch_size_rows

    @property
    def output(self) -> Schema:
        return self._schema

    def do_execute(self):
        from ..columnar.batch import batch_from_arrow
        n = self.table.num_rows
        step = self.batch_rows
        for off in range(0, max(n, 1), step):
            chunk = self.table.slice(off, min(step, n - off)) if n else \
                self.table
            b = batch_from_arrow(chunk)
            self.num_output_rows.add(chunk.num_rows)
            yield self._count_output(b)
            if n == 0:
                break


def has_host_black_box(exprs) -> bool:
    """True when any expression is a host black box (pandas UDF) or needs
    eager evaluation (data-dependent fanout, e.g. str_to_map/split): the
    enclosing kernel then runs un-jitted — jnp ops still execute on device,
    and the black box sees concrete arrays at the host hop."""
    from ..udf.pandas_udf import PandasUDF
    return any(e is not None and
               e.collect(lambda x: isinstance(x, PandasUDF) or
                         getattr(x, "needs_eager", False))
               for e in exprs)


class TpuProjectExec(UnaryTpuExec):
    def __init__(self, exprs: Sequence[Expression], child: TpuExec, conf=None):
        super().__init__([child], conf)
        self.exprs = list(exprs)
        self._bound = [bind_references(e, child.output) for e in self.exprs]
        names = tuple(output_name(e, f"col{i}") for i, e in enumerate(self.exprs))
        self._schema = Schema(names, tuple(e.data_type for e in self._bound))
        bound = self._bound

        self._err_msgs: list = []
        msgs_box = self._err_msgs

        def kernel(batch: ColumnarBatch, row_offset):
            from .base import kernel_notes
            ctx = device_ctx(batch, self.conf)
            ctx.partition_row_offset = row_offset
            vecs = batch_vecs(batch)
            outs = [e.eval(ctx, vecs) for e in bound]
            # the box's tail, for `do_execute`: the exact decimal divisions
            # this trace lowered
            return vecs_to_batch(self._schema, outs, batch.num_rows), \
                kernel_notes(ctx, msgs_box, ctx.decimal_divides)

        self.decimal_divides = self.metrics.create(M.NUM_DECIMAL_DIVIDES,
                                                   M.MODERATE)
        # a projection containing a host black box (pandas UDF) cannot be
        # traced: run it eagerly — jnp ops still execute on device, and the
        # UDF sees concrete arrays at the host hop. This is the in-process
        # equivalent of the reference splitting ArrowEvalPython into its own
        # exec (GpuArrowEvalPythonExec.scala:235).
        self._kernel = kernel if self._has_host_black_box() else \
            instance_jit(kernel, op="exec.project",
                         key=kernel_key(self._bound, self._schema,
                                        conf=self.conf),
                         msgs_box=self._err_msgs)

    def _has_host_black_box(self) -> bool:
        return has_host_black_box(self._bound)

    @property
    def output(self) -> Schema:
        return self._schema

    def do_execute(self):
        from .base import raise_kernel_errors
        # cumulative live-row offset across the batch stream (traced scalar:
        # a fresh offset must not retrace the kernel)
        offset = jnp.asarray(0, jnp.int64)
        for b in self.child.execute():
            with self.op_time.timed():
                out, errs = self._kernel(b, offset)
            offset = offset + jnp.asarray(b.row_count(), jnp.int64)
            raise_kernel_errors(errs, self._err_msgs)
            self.decimal_divides.add(self._err_msgs[-1])
            self.num_output_rows.add(b.row_count())
            yield self._count_output(out)

    def _arg_string(self):
        return f"[{', '.join(map(repr, self.exprs))}]"


class TpuFilterExec(UnaryTpuExec):
    def __init__(self, condition: Expression, child: TpuExec, conf=None):
        super().__init__([child], conf)
        self.condition = condition
        self.filter_time = self.metrics.create(M.FILTER_TIME, M.MODERATE)
        self._bound = bind_references(condition, child.output)
        bound = self._bound

        self._err_msgs: list = []
        msgs_box = self._err_msgs

        def kernel(batch: ColumnarBatch):
            from .base import kernel_notes
            ctx = device_ctx(batch, self.conf)
            vecs = batch_vecs(batch)
            pred = bound.eval(ctx, vecs)
            keep = pred.data & pred.validity & batch.row_mask()
            tally = GatherTally()
            out_vecs, new_n = compact_vecs(jnp, vecs, keep, tally)
            return vecs_to_batch(batch.schema, out_vecs, new_n), \
                kernel_notes(ctx, msgs_box, tally.packed, tally.alone)

        self.gathers = GatherCounts(self.metrics)

        # a condition containing a host black box (pandas UDF / eager
        # fanout expr) runs the kernel eagerly, like TpuProjectExec
        self._kernel = kernel if has_host_black_box([self._bound]) else \
            instance_jit(kernel, op="exec.filter",
                         key=kernel_key(self._bound, child.output,
                                        conf=self.conf),
                         msgs_box=self._err_msgs)

    def do_execute(self):
        from .base import raise_kernel_errors
        for b in self.child.execute():
            with self.op_time.timed(), self.filter_time.timed():
                out, errs = self._kernel(b)
            raise_kernel_errors(errs, self._err_msgs)
            self.gathers.add(self._err_msgs)
            self.num_output_rows.add(out.row_count())
            yield self._count_output(out)

    def _arg_string(self):
        return f"[{self.condition!r}]"


class TpuRangeExec(TpuExec):
    def __init__(self, start: int, end: int, step: int = 1, conf=None,
                 batch_rows: int = None):
        super().__init__([], conf)
        self.start, self.end, self.step = start, end, step
        self._schema = Schema(("id",), (T.LONG,))
        self.batch_rows = batch_rows or self.conf.batch_size_rows

    @property
    def output(self) -> Schema:
        return self._schema

    def do_execute(self):
        total = max(0, -(-(self.end - self.start) // self.step))
        done = 0
        while done < total or (total == 0 and done == 0):
            count = min(self.batch_rows, total - done)
            cap = row_bucket(count, op="range")
            base = self.start + done * self.step
            data = jnp.arange(cap, dtype=jnp.int64) * self.step + base
            col = Vec(T.LONG, data, jnp.ones(cap, dtype=bool))
            yield self._count_output(
                vecs_to_batch(self._schema, [col], count))
            self.num_output_rows.add(count)
            done += count
            if total == 0:
                break


class TpuUnionExec(TpuExec):
    def __init__(self, children: Sequence[TpuExec], conf=None):
        super().__init__(children, conf)

    @property
    def output(self) -> Schema:
        return self.children[0].output

    def do_execute(self):
        for c in self.children:
            for b in c.execute():
                self.num_output_rows.add(b.row_count())
                yield self._count_output(b)


class TpuExpandExec(UnaryTpuExec):
    def __init__(self, projections: Sequence[Sequence[Expression]],
                 names: Sequence[str], child: TpuExec, conf=None):
        super().__init__([child], conf)
        self.projections = [list(p) for p in projections]
        self._bound = [[bind_references(e, child.output) for e in p]
                       for p in self.projections]
        tps = tuple(e.data_type for e in self._bound[0])
        self._schema = Schema(tuple(names), tps)
        bound = self._bound
        self._err_msgs: list = []
        msgs_box = self._err_msgs

        def kernel(batch: ColumnarBatch):
            from .base import kernel_errors
            ctx = device_ctx(batch, self.conf)
            vecs = batch_vecs(batch)
            outs = [vecs_to_batch(self._schema,
                                  [e.eval(ctx, vecs) for e in proj],
                                  batch.num_rows)
                    for proj in bound]
            return outs, kernel_errors(ctx, msgs_box)

        self._kernel = instance_jit(
            kernel, op="exec.expand",
            key=kernel_key(self._bound, self._schema, conf=self.conf),
            msgs_box=self._err_msgs)

    @property
    def output(self) -> Schema:
        return self._schema

    def do_execute(self):
        from .base import raise_kernel_errors
        for b in self.child.execute():
            with self.op_time.timed():
                outs, errs = self._kernel(b)
            raise_kernel_errors(errs, self._err_msgs)
            for out in outs:
                self.num_output_rows.add(out.row_count())
                yield self._count_output(out)


class TpuLimitExec(UnaryTpuExec):
    """Local+global limit with offset (reference `limit.scala`)."""

    def __init__(self, limit: int, child: TpuExec, offset: int = 0, conf=None):
        super().__init__([child], conf)
        self.limit = limit
        self.offset = offset

    def do_execute(self):
        remaining = self.limit
        skip = self.offset
        for b in self.child.execute():
            if remaining <= 0:
                break
            n = b.row_count()
            start = min(skip, n)
            skip -= start
            take = min(remaining, n - start)
            if take <= 0:
                continue
            if start == 0:
                out = ColumnarBatch(b.schema, b.columns,
                                    jnp.asarray(take, jnp.int32))
            else:
                sliced = [v.slice_rows(start, None)
                          for v in batch_vecs(b)]
                out = vecs_to_batch(b.schema, sliced, take)
            remaining -= take
            self.num_output_rows.add(take)
            yield self._count_output(out)

    def _arg_string(self):
        return f"[{self.limit}]"


class TpuSampleExec(UnaryTpuExec):
    """Deterministic Bernoulli sample (GpuSampleExec analog); the row
    decision hashes the GLOBAL row ordinal, threaded across batches as a
    traced offset like the Project exec's monotonic-id plumbing."""

    def __init__(self, fraction: float, seed: int, child: TpuExec, conf=None):
        super().__init__([child], conf)
        self.fraction = float(fraction)
        self.seed = int(seed)
        frac, seed_v = self.fraction, self.seed

        def kernel(batch: ColumnarBatch, row_offset):
            from ..ops.rowops import sample_mask
            vecs = batch_vecs(batch)
            cap = batch.capacity
            keep = sample_mask(jnp, cap, row_offset, frac, seed_v) & \
                batch.row_mask()
            out_vecs, new_n = compact_vecs(jnp, vecs, keep)
            return vecs_to_batch(batch.schema, out_vecs, new_n)

        self._kernel = instance_jit(
            kernel, op="exec.sample",
            key=kernel_key(self.fraction, self.seed, conf=self.conf))

    @property
    def output(self) -> Schema:
        return self.child.output

    def do_execute(self):
        offset = jnp.asarray(0, jnp.int64)
        for b in self.child.execute():
            with self.op_time.timed():
                out = self._kernel(b, offset)
            offset = offset + jnp.asarray(b.row_count(), jnp.int64)
            self.num_output_rows.add(out.row_count())
            yield self._count_output(out)

    def _arg_string(self):
        return f"[fraction={self.fraction}, seed={self.seed}]"
