"""Host<->device transition operators (reference `GpuTransitionOverrides.scala`:
GpuRowToColumnarExec / GpuColumnarToRowExec / HostColumnarToGpu placement `:50-120`).

In this engine both sides are columnar, so the transitions are host-batch <-> device-
batch bridges: `TpuFromCpuExec` lifts a CPU subtree's output onto the device (the
HostColumnarToGpu analog); `CpuFromTpuExec` runs a device subtree and hands host
batches to a CPU parent (the GpuColumnarToRowExec analog)."""

from __future__ import annotations

from typing import Iterator, List

import jax.numpy as jnp
import numpy as np

from .. import types as T
from ..columnar.batch import ColumnarBatch, Schema
from ..columnar.column import Column
from ..columnar.padding import row_bucket, width_bucket
from ..cpu.hostbatch import HostBatch
from ..expr.base import Vec
from ..utils import spans
from .base import TpuExec, batch_vecs


def host_batch_to_device(hb: HostBatch) -> ColumnarBatch:
    n = hb.num_rows
    cap = row_bucket(n, op="transition")
    cols = []
    for v in hb.vecs:
        if v.is_nested:
            from ..cpu.hostbatch import vec_map_arrays

            def pad_ship(a):
                a = np.asarray(a)
                pad = [(0, cap - a.shape[0])] + [(0, 0)] * (a.ndim - 1)
                return jnp.asarray(np.pad(a, pad))

            cols.append(vec_map_arrays(v, pad_ship).to_column())
            continue
        valid = np.zeros(cap, dtype=bool)
        valid[:n] = v.validity
        if v.is_string:
            from ..columnar.strings import build_string_leaves, head_width
            if v.data.shape[1] <= head_width():
                w = width_bucket(max(v.data.shape[1], 1))
                data = np.zeros((cap, w), dtype=np.uint8)
                data[:n, :v.data.shape[1]] = v.data
                lens = np.zeros(cap, dtype=np.int32)
                lens[:n] = v.lengths
                cols.append(Column(v.dtype, jnp.asarray(data),
                                   jnp.asarray(valid), jnp.asarray(lens)))
                continue
            # long strings ship in the head+blob layout, not cap x width
            from ..columnar.strings import flatten_live_bytes
            flat, l = flatten_live_bytes(v.data, v.lengths, None, None, n)
            offsets = np.concatenate(([0], np.cumsum(l, dtype=np.int64)))
            head, lens_p, ovf = build_string_leaves(flat, offsets, l, cap)
            cols.append(Column(v.dtype, jnp.asarray(head),
                               jnp.asarray(valid), jnp.asarray(lens_p), None,
                               None if ovf is None else
                               (jnp.asarray(ovf[0]), jnp.asarray(ovf[1]))))
        else:
            data = np.zeros(cap, dtype=v.data.dtype)
            data[:n] = v.data
            cols.append(Column(v.dtype, jnp.asarray(data), jnp.asarray(valid)))
    return ColumnarBatch(hb.schema, tuple(cols), jnp.asarray(n, jnp.int32))


def device_batch_to_host(b: ColumnarBatch) -> HostBatch:
    """The sink's device -> host copy of one batch, under the `sink.d2h`
    span and `TaskMetrics.d2h_ns`. Its own row-count sync is part of it, so
    it reads `num_rows` directly and adds nothing to `host_sync_ns`."""
    with spans.timed("sink.d2h", "d2h_ns", kind=spans.KIND_IO):
        n = int(b.num_rows)
        # every flat column's copies are started before the first is waited
        # for, so they wait beside each other, and a string column's matrix,
        # lengths and overflow starts are cut to the live rows on the device
        # where that is most of the bytes: an answer of four rows at a
        # capacity of two million ships its rows, not 40 MB. Where most of
        # the capacity is alive they cross whole, as they always did, and
        # no program is compiled for the cut (0.2 s apiece on the chip, in
        # the first query of every process)
        cut = {}
        for i, c in enumerate(b.columns):
            if c.children is None:
                parts = [c.validity[:n], c.data[:n]]
                if c.is_string:
                    few = 4 * n <= c.lengths.shape[0]
                    parts[1] = c.data[:n] if few else c.data
                    parts.append(c.lengths[:n] if few else c.lengths)
                    if c.overflow is not None:
                        parts += [c.overflow[0], c.overflow[1][:n] if few
                                  else c.overflow[1]]
                for a in parts:
                    if hasattr(a, "copy_to_host_async"):
                        a.copy_to_host_async()
                cut[i] = parts
        vecs = []
        for i, c in enumerate(b.columns):
            if c.children is not None:
                from ..cpu.hostbatch import vec_map_arrays
                vecs.append(vec_map_arrays(Vec.from_column(c),
                                           lambda a: np.asarray(a)[:n]))
                continue
            valid, data, *more = cut[i]
            valid = np.asarray(valid)
            if c.is_string:
                from ..columnar.strings import assemble_matrix
                mat, lens = assemble_matrix(
                    data, more[0], tuple(more[1:]) or None, n)
                vecs.append(Vec(c.dtype, mat, valid, lens))
            else:
                vecs.append(Vec(c.dtype, np.asarray(data), valid))
        return HostBatch(b.schema, vecs, n)


class TpuFromCpuExec(TpuExec):
    """Device exec over a CPU subtree's output."""

    def __init__(self, cpu_plan, conf=None):
        super().__init__([], conf)
        self.cpu_plan = cpu_plan

    @property
    def output(self) -> Schema:
        return self.cpu_plan.output

    def do_execute(self) -> Iterator[ColumnarBatch]:
        for hb in self.cpu_plan.execute_cpu():
            b = host_batch_to_device(hb)
            self.num_output_rows.add(hb.num_rows)
            yield self._count_output(b)

    def tree_string(self, indent: int = 0) -> str:
        return ("  " * indent + "TpuFromCpuExec\n"
                + self.cpu_plan.tree_string(indent + 1))


class CpuFromTpuExec:
    """CPU plan node over a device subtree's output (duck-typed PhysicalPlan)."""

    def __init__(self, tpu_exec: TpuExec):
        self.tpu_exec = tpu_exec
        self.children: List = []

    @property
    def output(self) -> Schema:
        return self.tpu_exec.output

    @property
    def name(self) -> str:
        return "CpuFromTpuExec"

    def execute_cpu(self) -> Iterator[HostBatch]:
        for b in self.tpu_exec.execute():
            yield device_batch_to_host(b)

    def tree_string(self, indent: int = 0) -> str:
        return ("  " * indent + "CpuFromTpuExec\n"
                + self.tpu_exec.tree_string(indent + 1))
