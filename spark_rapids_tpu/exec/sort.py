"""Sort exec (reference `GpuSortExec.scala:83`; out-of-core iterator `:239`).

Three modes, mirroring the reference: per-batch sort, single-batch
(coalesce-then-sort), and **out-of-core**: each input batch is sorted on
device into a run and parked spillable (the pending set); the merge phase is
host-orchestrated — only the SORT KEYS of each run come to the host, a global
numpy lexsort merges the key streams, and the device assembles each output
chunk by gathering the chunk's rows from the (re-acquired) runs and ordering
them by their global position. Device residency is bounded to one run plus
one chunk; payloads never visit the host."""

from __future__ import annotations

from typing import Iterator, List, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from .. import types as T
from ..columnar.batch import ColumnarBatch, Schema
from ..compile import instance_jit, kernel_key, sjit
from ..expr.base import Expression, Vec, bind_references
from ..ops.rowops import (GatherTally, gather_vecs, lexsort_indices,
                          sort_keys_for)
from ..utils import metrics as M
from .base import (GatherCounts, TpuExec, UnaryTpuExec, batch_vecs,
                   device_ctx, vecs_to_batch)
from .coalesce import concat_batches


class TpuSortExec(UnaryTpuExec):
    def __init__(self, orders: Sequence[Tuple[Expression, bool, bool]],
                 child: TpuExec, conf=None, each_batch: bool = False):
        """orders: (expr, ascending, nulls_first). each_batch: sort within each
        batch only (reference sortEachBatch, used below windows)."""
        super().__init__([child], conf)
        self.orders = list(orders)
        self.each_batch = each_batch
        self._bound = [(bind_references(e, child.output), a, nf)
                       for e, a, nf in self.orders]
        self.sort_time = self.metrics.create(M.SORT_TIME, M.MODERATE)
        self.gathers = GatherCounts(self.metrics)
        bound = self._bound
        self._err_msgs: list = []
        msgs_box = self._err_msgs

        def kernel(batch: ColumnarBatch):
            from .base import kernel_notes
            ctx = device_ctx(batch, self.conf)
            vecs = batch_vecs(batch)
            mask = batch.row_mask()
            groups = [[(~mask).astype(np.int8)]]  # padding rows last
            for e, asc, nf in bound:
                groups.append(sort_keys_for(jnp, e.eval(ctx, vecs), asc, nf))
            order = lexsort_indices(jnp, groups, batch.capacity)
            tally = GatherTally()
            out = gather_vecs(jnp, vecs, order, tally)
            return vecs_to_batch(batch.schema, out, batch.num_rows), \
                kernel_notes(ctx, msgs_box, tally.packed, tally.alone)

        self._kernel = instance_jit(
            kernel, op="exec.sort",
            key=kernel_key([(repr(e), a, nf) for e, a, nf in bound],
                           conf=self.conf),
            msgs_box=self._err_msgs)

    def sort_single_batch(self, batch: ColumnarBatch) -> ColumnarBatch:
        from .base import raise_kernel_errors
        with self.sort_time.timed():
            out, errs = self._kernel(batch)
        raise_kernel_errors(errs, self._err_msgs)
        self.gathers.add(self._err_msgs)
        return out

    def do_execute(self) -> Iterator[ColumnarBatch]:
        if self.each_batch:
            for b in self.child.execute():
                out = self.sort_single_batch(b)
                self.num_output_rows.add(out.row_count())
                yield self._count_output(out)
            return
        batches = list(self.child.execute())
        if not batches:
            return
        total = sum(int(b.row_count()) for b in batches)
        if len(batches) > 1 and total > self.conf.batch_size_rows:
            yield from self._out_of_core(batches)
            return
        from ..errors import SplitAndRetryOOM
        from ..memory.retry import with_retry_no_split_spillable
        try:
            # the merged copy is passed as a temporary: the spillable wrapper
            # takes the only reference, so a spill under pressure frees it
            out = with_retry_no_split_spillable(concat_batches(batches),
                                                self.sort_single_batch)
        except SplitAndRetryOOM:
            # too big to sort in one device pass: the out-of-core merge
            # sorts arbitrary sub-batches into runs and merges globally,
            # so splitting degrades instead of dying
            yield from self._out_of_core(batches)
            return
        self.num_output_rows.add(out.row_count())
        yield self._count_output(out)

    # -- out-of-core merge path (GpuOutOfCoreSortIterator analog) ----------
    def _host_key_groups(self, batch: ColumnarBatch) -> List[np.ndarray]:
        """D2H the sort-key arrays of a (sorted) run, host-comparable form."""
        from .base import raise_eager_errors
        ctx = device_ctx(batch, self.conf)
        vecs = batch_vecs(batch)
        n = int(batch.row_count())
        flat: List[np.ndarray] = []
        for e, asc, nf in self._bound:
            v = e.eval(ctx, vecs)
            raise_eager_errors(ctx)
            hv = Vec(v.dtype, np.asarray(v.data)[:n],
                     np.asarray(v.validity)[:n],
                     None if v.lengths is None else np.asarray(v.lengths)[:n])
            flat.extend(np.asarray(k)[:n] if np.ndim(k) else k
                        for k in sort_keys_for(np, hv, asc, nf))
        return flat

    def _out_of_core(self, batches: List[ColumnarBatch]
                     ) -> Iterator[ColumnarBatch]:
        from ..memory.budget import MemoryBudget
        from ..memory.retry import split_batch_halves, with_retry
        from ..memory.spillable import SpillableColumnarBatch

        def run_sort(sp: SpillableColumnarBatch) -> ColumnarBatch:
            MemoryBudget.get().reserve(0)  # pre-flight / injection point
            out = self.sort_single_batch(sp.get_batch())
            sp.close()
            return out

        # phase 1: device-sort each batch into a run; park spillable. Each
        # batch sorts under the OOM-retry seam — a split just yields more,
        # smaller runs, which the global key merge below handles unchanged.
        runs: List[SpillableColumnarBatch] = []
        host_keys: List[List[np.ndarray]] = []
        with self.sort_time.timed():
            for b in batches:
                sp0 = SpillableColumnarBatch(b)
                try:
                    for sorted_b in with_retry(sp0, run_sort,
                                               split_batch_halves):
                        host_keys.append(self._host_key_groups(sorted_b))
                        runs.append(SpillableColumnarBatch(sorted_b))
                finally:
                    sp0.close()  # no-op on success (run_sort closed it)

            # phase 2: host merge of the key streams (keys only; payload
            # stays on device inside the spill catalog)
            run_id = np.concatenate([np.full(len(k[0]), i, np.int32)
                                     for i, k in enumerate(host_keys)])
            row_id = np.concatenate([np.arange(len(k[0]), dtype=np.int32)
                                     for k in host_keys])
            merged_keys = [np.concatenate([host_keys[i][g]
                                           for i in range(len(runs))])
                           for g in range(len(host_keys[0]))]
            # least-significant first for np.lexsort; run/row ids as the
            # final tiebreak keep the merge stable across runs
            order = np.lexsort(tuple([row_id, run_id] + merged_keys[::-1]))

        chunk_rows = self.conf.batch_size_rows
        try:
            for at in range(0, len(order), chunk_rows):
                chunk = order[at:at + chunk_rows]
                with self.sort_time.timed():
                    out = self._assemble_chunk(runs, run_id, row_id, chunk)
                self.num_output_rows.add(out.row_count())
                yield self._count_output(out)
        finally:
            for r in runs:
                r.close()

    def _assemble_chunk(self, runs, run_id, row_id, chunk) -> ColumnarBatch:
        """Gather the chunk's rows per run, tag each with its position in the
        chunk, concat, and device-sort by position (exact global order)."""
        from ..columnar.padding import row_bucket
        pieces: List[ColumnarBatch] = []
        pos_in_chunk = np.arange(len(chunk), dtype=np.int64)
        schema = self.child.output
        pos_schema = Schema(schema.names + ("__pos__",),
                            schema.types + (T.LONG,))
        for i, run in enumerate(runs):
            sel = run_id[chunk] == i
            if not sel.any():
                continue
            rows = row_id[chunk][sel]
            pos = pos_in_chunk[sel]
            cap = row_bucket(len(rows))
            idx = np.zeros(cap, np.int32)
            idx[:len(rows)] = rows
            posv = np.zeros(cap, np.int64)
            posv[:len(rows)] = pos
            batch = run.get_batch()
            piece = _gather_rows_with_pos(batch, jnp.asarray(idx),
                                          jnp.asarray(posv),
                                          jnp.asarray(len(rows),
                                                      dtype=jnp.int32),
                                          pos_schema)
            pieces.append(piece)
        merged = concat_batches(pieces)
        ordered = _sort_by_pos(merged)
        # drop the __pos__ column
        return vecs_to_batch(schema, batch_vecs(ordered)[:-1],
                             merged.num_rows)

    def _arg_string(self):
        return f"[{[(repr(e), a, nf) for e, a, nf in self.orders]}]"


@sjit(op="exec.sort.gather_pos", static_argnums=(4,))
def _gather_rows_with_pos(batch: ColumnarBatch, idx, pos, count,
                          pos_schema: Schema):
    vecs = gather_vecs(jnp, batch_vecs(batch), idx)
    vecs.append(Vec(T.LONG, pos, jnp.ones(idx.shape[0], bool)))
    return vecs_to_batch(pos_schema, vecs, count)


@sjit(op="exec.sort.by_pos")
def _sort_by_pos(batch: ColumnarBatch) -> ColumnarBatch:
    vecs = batch_vecs(batch)
    mask = batch.row_mask()
    pos = jnp.where(mask, vecs[-1].data, jnp.int64(2 ** 62))
    order = jnp.argsort(pos)
    return vecs_to_batch(batch.schema, gather_vecs(jnp, vecs, order),
                         batch.num_rows)


class TpuTopKExec(UnaryTpuExec):
    """TakeOrderedAndProjectExec analog (`GpuOverrides.scala:3705`,
    `GpuTakeOrderedAndProject`): ORDER BY + LIMIT k without a full
    out-of-core sort. Each input batch sorts on device and keeps its first
    k rows; a running candidate batch of <= k rows merges with every
    batch's winners, so device residency is one input batch plus O(k) and
    host sees nothing. Offset slices the final candidates."""

    def __init__(self, orders: Sequence[Tuple[Expression, bool, bool]],
                 limit: int, child: TpuExec, conf=None, offset: int = 0):
        super().__init__([child], conf)
        self.orders = list(orders)
        self.limit = limit
        self.offset = offset
        self._k = limit + offset
        self._bound = [(bind_references(e, child.output), a, nf)
                       for e, a, nf in self.orders]
        self.sort_time = self.metrics.create(M.SORT_TIME, M.MODERATE)
        bound = self._bound
        from ..columnar.padding import row_bucket
        kcap = row_bucket(max(self._k, 1))
        k = self._k
        self._err_msgs: list = []
        msgs_box = self._err_msgs

        def topk(batch: ColumnarBatch):
            from .base import kernel_errors
            ctx = device_ctx(batch, self.conf)
            vecs = batch_vecs(batch)
            mask = batch.row_mask()
            groups = [[(~mask).astype(np.int8)]]  # padding rows last
            for e, asc, nf in bound:
                groups.append(sort_keys_for(jnp, e.eval(ctx, vecs), asc,
                                            nf))
            order = lexsort_indices(jnp, groups, batch.capacity)
            take = order[:kcap] if kcap <= batch.capacity else jnp.pad(
                order, (0, kcap - batch.capacity))
            out = gather_vecs(jnp, vecs, take)
            new_n = jnp.minimum(batch.num_rows, k)
            return vecs_to_batch(batch.schema, out, new_n), \
                kernel_errors(ctx, msgs_box)

        self._topk_kernel = instance_jit(
            topk, op="exec.topk",
            key=kernel_key([(repr(e), a, nf) for e, a, nf in bound],
                           kcap, k, conf=self.conf),
            msgs_box=self._err_msgs)

    def _topk(self, batch: ColumnarBatch) -> ColumnarBatch:
        from .base import raise_kernel_errors
        out, errs = self._topk_kernel(batch)
        raise_kernel_errors(errs, self._err_msgs)
        return out

    @property
    def output(self) -> Schema:
        return self.child.output

    def do_execute(self) -> Iterator[ColumnarBatch]:
        run = None
        for b in self.child.execute():
            with self.sort_time.timed():
                top = self._topk(b)
                run = top if run is None else \
                    self._topk(concat_batches([run, top]))
        if run is None:
            return
        if self.offset:
            n = run.row_count()
            start = min(self.offset, n)
            take = max(min(self.limit, n - start), 0)
            sliced = [v.slice_rows(start, None) for v in batch_vecs(run)]
            run = vecs_to_batch(run.schema, sliced, take)
        self.num_output_rows.add(run.row_count())
        yield self._count_output(run)

    def _arg_string(self):
        return f"[k={self.limit}, offset={self.offset}, " \
               f"orders={len(self.orders)}]"
