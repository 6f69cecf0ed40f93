"""Partitioned exchange operators.

Reference: `GpuShuffleExchangeExecBase.scala:152` (dependency prep `:262`),
partition slicing `GpuPartitioning.scala:52,86`, post-shuffle coalesce
`GpuShuffleCoalesceExec.scala:41`.

Two paths, like the reference's shuffle modes:
  * local/host path (this module): the exec computes partition ids on device and
    compacts one output batch per partition — the moral equivalent of
    multithreaded-mode slicing; within one process the "transport" is nothing.
  * ICI path (parallel/collective.py): for distributed plans the same partition
    ids feed `all_to_all_exchange` under shard_map, moving rows between chips in
    one compiled collective (no per-buffer control protocol needed).
"""

from __future__ import annotations

from typing import Iterator, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import types as T
from ..columnar.batch import ColumnarBatch, Schema
from ..compile import sjit
from ..expr.base import Vec
from ..ops.rowops import compact_vecs
from ..parallel.partitioning import (HashPartitioning, RangePartitioning,
                                     RoundRobinPartitioning,
                                     SinglePartitioning, TpuPartitioning)
from ..utils import metrics as M
from .base import UnaryTpuExec, batch_vecs, vecs_to_batch
from .coalesce import concat_batches

__all__ = ["TpuShuffleExchangeExec", "make_partitioner"]

# process-wide count of executed mesh collectives (test/observability hook)
MESH_EXCHANGES = 0
# process-wide count of slot-overflow grow-and-rerun rounds (a bounded ICI
# slot overflowed on a skewed partition and the exchange retried larger)
SLOT_OVERFLOW_RETRIES = 0


def make_partitioner(spec, schema: Schema,
                     sample_batch: Optional[ColumnarBatch] = None
                     ) -> TpuPartitioning:
    """Lower a plan-level PartitionSpec (plan/nodes.py) to a device partitioner.
    Range bounds are computed from a sample, like Spark's driver-side sampling
    feeding `GpuRangePartitioner`."""
    from ..plan.nodes import (HashPartitionSpec, RangePartitionSpec,
                              RoundRobinPartitionSpec, SinglePartitionSpec)
    if isinstance(spec, HashPartitionSpec):
        return HashPartitioning.from_exprs(spec.keys, schema,
                                           spec.num_partitions)
    if isinstance(spec, RoundRobinPartitionSpec):
        return RoundRobinPartitioning(spec.num_partitions)
    if isinstance(spec, SinglePartitionSpec):
        return SinglePartitioning()
    if isinstance(spec, RangePartitionSpec):
        from ..expr.base import BoundReference, bind_references
        b = bind_references(spec.key, schema)
        if not isinstance(b, BoundReference):
            raise ValueError("range partition key must be a column reference")
        if sample_batch is None:
            raise ValueError("range partitioning needs a sample batch")
        col = sample_batch.columns[b.ordinal]
        n = int(sample_batch.row_count())
        v = Vec.from_column(col)
        vec = Vec(v.dtype, np.asarray(v.data)[:n], np.asarray(v.validity)[:n],
                  None if v.lengths is None else np.asarray(v.lengths)[:n])
        return RangePartitioning.from_sample(vec, b.ordinal,
                                             spec.num_partitions,
                                             spec.ascending, spec.nulls_first)
    raise TypeError(f"unknown partition spec {spec!r}")


class TpuShuffleExchangeExec(UnaryTpuExec):
    """Repartition the child's stream: one output batch per partition.

    Kernel shape: pid computation + per-partition stable compaction are jitted
    once per (schema, capacity); all partitions reuse the same compaction
    program with the partition id as a traced scalar."""

    # Set True by the sharded plan pass (mesh/plan.py) when the consumer
    # is shard-wise (zipped join / per-shard final aggregate): exchanged
    # partitions are handed downstream as zero-copy per-chip views
    # (addressable_shards) instead of gathered replicated slices. CLASS
    # attribute: mesh-off exchanges carry zero extra state.
    mesh_resident_out = False

    def __init__(self, spec, child, conf=None):
        super().__init__([child], conf)
        self.spec = spec
        self.partition_time = self.metrics.create(M.PARTITION_TIME, M.ESSENTIAL)
        self.num_partitions = self.metrics.create(M.NUM_PARTITIONS,
                                                  M.ESSENTIAL)
        self.write_time = self.metrics.create(M.WRITE_TIME, M.MODERATE)
        self.read_time = self.metrics.create(M.READ_TIME, M.MODERATE)

    def do_execute(self) -> Iterator[ColumnarBatch]:
        """Exchange-output rescache seam: an identical subplan's
        partitioned output replays from the cached fragments instead of
        re-executing the child and re-shuffling (local shuffle modes
        only; the ICI mesh path is gated off in rescache). Off (default)
        this is the produce path verbatim."""
        from .. import rescache
        yield from rescache.fragment_stream(self, "exchange",
                                            self._do_execute_produce)

    def _do_execute_produce(self) -> Iterator[ColumnarBatch]:
        batches = list(self.child.execute())
        mode = self.conf.get("spark.rapids.shuffle.mode")
        if mode == "ICI":
            from ..parallel.mesh import mesh_from_conf
            mesh = mesh_from_conf(self.conf)
            if mesh is not None and self.spec.num_partitions == mesh.size:
                # mesh mode always yields exactly ndev batches (empties
                # included) — downstream zipped execs rely on the alignment
                if not batches:
                    from ..columnar.batch import empty_batch
                    for _ in range(mesh.size):
                        yield self._count_output(
                            empty_batch(self.child.output, 1))
                    return
                yield from self._exchange_via_mesh(batches, mesh)
                return
            if mesh is not None and self.spec.num_partitions > 1 and \
                    self.conf.get("spark.rapids.tpu.mesh.enabled"):
                # shard-count vs partition-count mismatch the plan pass
                # could not (or was told not to) resize: degrade cleanly
                # to the host data plane below — never a wrong split.
                # Single-partition exchanges (collect/sort sinks) are by
                # design never mesh material and must not read as
                # degrades on the alert counter.
                from ..utils.metrics import TaskMetrics
                TaskMetrics.get().mesh_degraded += 1
                from .. import telemetry
                telemetry.inc("tpu_mesh_degraded_total")
        if not batches:
            return
        batch = concat_batches(batches)
        part = make_partitioner(self.spec, self.child.output, batch)
        n_parts = part.num_partitions
        self.num_partitions.set(n_parts)
        if mode in ("MULTITHREADED", "CACHE_ONLY") and n_parts > 1:
            yield from self._shuffle_via_manager(batch, part, n_parts, mode)
            return
        with self.partition_time.timed():
            pid = part.ids_for_batch(jnp, batch)
        # ICI mode in-process: device-resident slicing (the distributed data
        # plane is the compiled all_to_all in parallel/collective.py)
        from .. import stats, telemetry
        note_parts = (stats.is_enabled() or telemetry.is_enabled()) \
            and n_parts > 1
        for p in range(n_parts):
            with self.partition_time.timed():
                out = _slice_partition(batch, pid, p)
            if note_parts:
                # in-process slicing has no shuffle-write close; device
                # bytes of the sliced partition are the skew signal here
                pbytes = int(out.device_memory_size())
                telemetry.observe("tpu_exchange_partition_bytes", pbytes)
                stats.note_partition_bytes(self, {p: pbytes})
            if int(out.row_count()) == 0 and n_parts > 1:
                continue
            self.num_output_rows.add(out.row_count())
            yield self._count_output(out)

    def _shuffle_via_manager(self, batch, part, n_parts, mode):
        """Write every partition through the shuffle manager (serialize/
        compress on writer threads or device-resident cache), then read each
        reduce partition back — the full reference write/read path
        (`RapidsShuffleInternalManagerBase` getWriter/getReader), in-process.

        The write side runs under the OOM-retry seam: memory pressure while
        slicing/serializing splits the input and writes each piece under its
        own map id (the read side concats across map ids, so more, smaller
        map outputs are transparent). A failed attempt discards its partial
        map output before retrying — rows land exactly once."""
        import itertools
        from ..memory.budget import MemoryBudget
        from ..memory.retry import split_batch_halves, with_retry
        from ..memory.spillable import SpillableColumnarBatch
        from ..shuffle.manager import TpuShuffleManager, next_shuffle_id
        mgr = TpuShuffleManager.get(self.conf)
        codec = self.conf.get("spark.rapids.shuffle.compression.codec")
        sid = next_shuffle_id()
        next_map = itertools.count()
        # per-partition byte totals across pieces, kept locally so the
        # telemetry skew histogram samples each partition ONCE per
        # committed write (failed attempts never reach the fold below)
        part_totals: dict = {}

        def write_piece(sp: SpillableColumnarBatch) -> int:
            MemoryBudget.get().reserve(0)  # pre-flight / injection point
            b = sp.get_batch()
            mid = next(next_map)
            writer = mgr.get_writer(sid, map_id=mid, mode=mode, codec=codec)
            try:
                try:
                    with self.partition_time.timed():
                        pid = part.ids_for_batch(jnp, b)
                    for p in range(n_parts):
                        with self.partition_time.timed():
                            out = _slice_partition(b, pid, p)
                        if int(out.row_count()) == 0:
                            continue
                        with self.write_time.timed():
                            writer.write(p, out)
                finally:
                    # drain in-flight writer futures BEFORE any cleanup — a
                    # late store.put after cleanup would leak blocks forever
                    # in the process-singleton store
                    with self.write_time.timed():
                        writer.close()
            except BaseException:
                mgr.discard_map_output(sid, mid, n_parts)
                raise
            # runtime statistics: fold this piece's per-partition bytes
            # into the exec's skew histogram (one bool when stats is off)
            from .. import stats
            stats.note_partition_bytes(self, writer.partition_bytes)
            for p, nb in writer.partition_bytes.items():
                part_totals[p] = part_totals.get(p, 0) + nb
            sp.close()
            return mid

        from ..utils import spans
        try:
            sp0 = SpillableColumnarBatch(batch)
            # hand ownership to the spillable wrapper so a spill during the
            # OOM-retry loop can actually free the device arrays
            del batch
            with spans.span("shuffle:write", kind=spans.KIND_SHUFFLE,
                            shuffle_id=sid, partitions=n_parts):
                try:
                    list(with_retry(sp0, write_piece, split_batch_halves))
                finally:
                    sp0.close()  # no-op on success (write_piece closed it)
            from .. import telemetry
            for nb in part_totals.values():
                telemetry.observe("tpu_exchange_partition_bytes", nb)
            # release=True drops each partition's blocks as they are consumed,
            # bounding block-store retention to one partition at a time
            for p in range(n_parts):
                for b in M.timed_pulls(
                        mgr.read_partition(sid, p, mode=mode, release=True),
                        self.read_time):
                    if int(b.row_count()) == 0:
                        continue
                    self.num_output_rows.add(b.row_count())
                    yield self._count_output(b)
        finally:
            mgr.unregister_shuffle(sid)

    def _exchange_via_mesh(self, batches: List[ColumnarBatch],
                           mesh) -> Iterator[ColumnarBatch]:
        """Distributed data plane: rows move between mesh devices in ONE
        compiled lax.all_to_all (parallel/collective.py) — the planned-query
        integration of the ICI shuffle, replacing the reference's UCX p2p
        transport fed by `GpuShuffleExchangeExecBase.scala:262`. Yields exactly
        ndev batches, one per device partition, empties included so downstream
        zipped execs stay positionally aligned. Slot overflow is detected ON
        DEVICE and retried with a doubled slot_cap — rows are never dropped."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        from ..columnar.column import Column
        from ..columnar.padding import row_bucket
        from ..parallel.collective import build_exchange_fn
        from ..parallel.mesh import SHUFFLE_AXIS

        ndev = mesh.size
        schema = self.child.output
        mesh_on = self.conf.get("spark.rapids.tpu.mesh.enabled")
        ovf_results = {}
        aligned = None
        if mesh_on:
            # zero-copy input assembly: a child that already yielded one
            # per-device shard per mesh position (sharded scan, zipped
            # join, per-shard aggregate) skips the device-0 concat bounce
            # entirely — each shard pads on ITS chip and the global array
            # is stitched from the resident pieces (Theseus' keep-data-
            # on-device discipline applied to the exchange input seam)
            from ..plan.nodes import HashPartitionSpec
            if isinstance(self.spec, HashPartitionSpec):
                from ..mesh.shard import (aligned_device_shards,
                                          assemble_exchange_input)
                aligned = aligned_device_shards(batches, mesh)
        if aligned is not None:
            part = make_partitioner(self.spec, schema, None)
            with self.partition_time.timed():
                asm = assemble_exchange_input(aligned, mesh, part)
            if asm is None:
                aligned = None
            else:
                leaves, pid, has_lengths, cap = asm
                schema = aligned[0].schema
        if aligned is None:
            batch = concat_batches(batches)
            schema = batch.schema
            total = int(batch.row_count())
            cap = row_bucket(max((total + ndev - 1) // ndev, 1))
            g = batch.repadded(ndev * cap)
            part = make_partitioner(self.spec, self.child.output, batch)
            with self.partition_time.timed():
                pid = part.ids_for_batch(jnp, g)

            leaves = []
            has_lengths = []
            for c in g.columns:
                leaves.append(c.data)
                leaves.append(c.validity)
                has_lengths.append(c.lengths is not None)
                if c.lengths is not None:
                    leaves.append(c.lengths)
            sh = NamedSharding(mesh, P(SHUFFLE_AXIS))
            leaves = [jax.device_put(l, sh) for l in leaves]
            pid = jax.device_put(pid.astype(jnp.int32), sh)

            # long-string overflow columns: the head/lengths move with the
            # row plane above; the row-UNALIGNED tail blobs move through a
            # second BYTE-plane all_to_all (tail bytes of each device's row
            # segment, in row order, with a per-byte destination id) —
            # same collective, different unit
            ovf_ix = [ci for ci, c in enumerate(g.columns)
                      if c.overflow is not None]
            if ovf_ix:
                pid_np = np.asarray(pid)
                for ci in ovf_ix:
                    ovf_results[ci] = self._exchange_tail_bytes(
                        mesh, ndev, cap, g.columns[ci], pid_np, sh)

            ovf_heads = {ci: g.columns[ci].data.shape[1]
                         for ci in ovf_results}
        else:
            ovf_heads = {}

        conf_slot = self.conf.get("spark.rapids.shuffle.ici.slotRows")
        slot_cap = min(conf_slot, cap) if conf_slot > 0 else cap
        from ..utils import spans
        with spans.span("exchange:ici", kind=spans.KIND_SHUFFLE,
                        devices=ndev, aligned_input=int(aligned is not None)):
            while True:
                fn = build_exchange_fn(mesh, ndev, slot_cap=slot_cap)
                with self.partition_time.timed():
                    out_leaves, counts, overflowed = fn(leaves, pid)
                if not bool(overflowed):
                    break
                # a skewed partition overflowed the bounded slot: grow and
                # rerun (slot_cap == cap can never overflow, so this
                # terminates)
                global SLOT_OVERFLOW_RETRIES
                SLOT_OVERFLOW_RETRIES += 1
                slot_cap = min(slot_cap * 2, cap)
        global MESH_EXCHANGES
        MESH_EXCHANGES += 1
        # surfacing (satellite of the sharded-execution issue): the bare
        # process-wide global above stays as the historical test hook, but
        # the collective also lands in TaskMetrics (explain_string line),
        # telemetry counters, and the exchange's own metrics
        ici_bytes = sum(int(l.size) * l.dtype.itemsize for l in out_leaves)
        from ..utils.metrics import TaskMetrics
        tm = TaskMetrics.get()
        tm.mesh_exchanges += 1
        tm.mesh_ici_bytes += ici_bytes
        tm.mesh_out_devices = sorted(set(tm.mesh_out_devices).union(
            d.id for leaf in out_leaves for d in leaf.devices()))
        self.num_partitions.set(ndev)
        from .. import telemetry
        telemetry.inc("tpu_mesh_exchanges_total")
        telemetry.inc("tpu_mesh_ici_bytes_total", ici_bytes)

        counts = np.asarray(counts)
        out_cap = ndev * slot_cap
        # device-resident output: partitions hand downstream as zero-copy
        # views of the collective's own per-chip shards — the shard-wise
        # consumer (zipped join / per-shard final agg) computes on the
        # chip the rows already live on. Without the mark (or when a
        # shard is not addressable here) the historical gather-to-
        # replicated slice keeps every consumer working unchanged.
        resident = mesh_on and bool(self.mesh_resident_out)
        if resident:
            from ..mesh.shard import shard_view
            if shard_view(out_leaves[0], ndev - 1, out_cap) is None:
                resident = False
        devs = list(mesh.devices.flat)
        for p in range(ndev):
            lo = p * out_cap

            if resident:
                def grab(leaf, _p=p):
                    from ..mesh.shard import shard_view
                    return shard_view(leaf, _p, out_cap)
            else:
                def grab(leaf, _lo=lo):
                    return leaf[_lo:_lo + out_cap]
            cols = []
            i = 0
            for ci, dtype in enumerate(schema.types):
                data = grab(out_leaves[i])
                i += 1
                validity = grab(out_leaves[i])
                i += 1
                lengths = None
                if has_lengths[ci]:
                    lengths = grab(out_leaves[i])
                    i += 1
                overflow = None
                if ci in ovf_results:
                    overflow = self._partition_overflow(
                        ovf_results[ci], p, lengths,
                        ovf_heads[ci], int(counts[p]), out_cap)
                    if resident:
                        # the rebuilt tail plane is host-assembled; pin it
                        # to the shard's chip so the batch stays one-device
                        overflow = jax.device_put(overflow, devs[p])
                cols.append(Column(dtype, data, validity, lengths,
                                   overflow=overflow))
            out = ColumnarBatch(schema, tuple(cols),
                                jnp.asarray(counts[p], jnp.int32))
            self.num_output_rows.add(int(counts[p]))
            yield self._count_output(out)

    def _exchange_tail_bytes(self, mesh, ndev: int, cap: int, col,
                             pid_np: np.ndarray, sh):
        """Byte-plane all_to_all for one overflow column: each device's
        segment contributes its live rows' tail bytes IN ROW ORDER with a
        per-byte destination id. The collective's stable per-destination
        ordering then guarantees the arriving byte stream is the arriving
        row stream expanded — tail_start realigns with one cumsum.
        Returns (global byte leaf, per-device byte counts, byte out_cap)."""
        from ..columnar.padding import row_bucket
        from ..columnar.strings import segment_arange
        from ..parallel.collective import build_exchange_fn
        blob = np.asarray(col.overflow[0])
        tstart = np.asarray(col.overflow[1]).astype(np.int64)
        lens = np.asarray(col.lengths).astype(np.int64)
        hw = col.data.shape[1]
        tlen = np.maximum(lens - hw, 0)
        tlen[pid_np < 0] = 0  # padding rows carry no bytes
        per_dev = []
        max_bytes = 1
        for d in range(ndev):
            sl = slice(d * cap, (d + 1) * cap)
            tl = tlen[sl]
            idx = np.repeat(tstart[sl], tl) + segment_arange(tl)
            per_dev.append((blob[np.clip(idx, 0, blob.size - 1)],
                            np.repeat(pid_np[sl], tl).astype(np.int32)))
            max_bytes = max(max_bytes, per_dev[-1][0].size)
        bcap = row_bucket(max_bytes)
        stream = np.zeros(ndev * bcap, np.uint8)
        bpid = np.full(ndev * bcap, -1, np.int32)
        for d, (b, p) in enumerate(per_dev):
            stream[d * bcap:d * bcap + b.size] = b
            bpid[d * bcap:d * bcap + p.size] = p
        sleaf = jax.device_put(jnp.asarray(stream), sh)
        bp = jax.device_put(jnp.asarray(bpid), sh)
        # slot_cap == per-device byte capacity can never overflow (a source
        # holds at most bcap bytes total), so a single exchange suffices —
        # assert rather than retry so a broken invariant fails loud
        fn = build_exchange_fn(mesh, ndev, slot_cap=bcap)
        out, bcounts, ov = fn([sleaf], bp)
        if bool(ov):
            raise RuntimeError(
                "byte-plane exchange overflowed its provably-safe slot "
                "capacity (collective slotting invariant broken)")
        return out[0], np.asarray(bcounts), ndev * bcap

    @staticmethod
    def _partition_overflow(ovf_result, p: int, lengths, hw: int,
                            nrows: int, out_cap: int):
        """Rebuild one partition's (blob, tail_start) from the exchanged
        byte plane: arriving rows and bytes share the (source, row) order,
        so tail offsets are the exclusive cumsum of the arriving rows'
        tail lengths."""
        from ..columnar.strings import blob_bucket
        byte_leaf, bcounts, bcap_out = ovf_result
        nbytes = int(bcounts[p])
        seg = np.asarray(byte_leaf[p * bcap_out:p * bcap_out + nbytes])
        blob = np.zeros(blob_bucket(max(nbytes, 1)), np.uint8)
        blob[:nbytes] = seg
        lens = np.asarray(lengths[:out_cap]).astype(np.int64)
        tlen = np.maximum(lens - hw, 0)
        tlen[nrows:] = 0  # dead tail rows carry garbage lengths
        tail_start = np.zeros(out_cap, np.int32)
        if out_cap > 1:
            tail_start[1:] = np.cumsum(tlen[:-1]).astype(np.int32)
        return (jnp.asarray(blob), jnp.asarray(tail_start))

    def _arg_string(self):
        return f"[{self.spec}]"


@sjit(op="exec.exchange.slice")
def _slice_vecs(vecs, pid, p):
    keep = pid == p
    return compact_vecs(jnp, vecs, keep)


def _slice_partition(batch: ColumnarBatch, pid, p: int) -> ColumnarBatch:
    vecs, n = _slice_vecs(batch_vecs(batch), pid, jnp.asarray(p, jnp.int32))
    return vecs_to_batch(batch.schema, vecs, n)
