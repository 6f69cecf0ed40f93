"""Broadcast exchange (reference `GpuBroadcastExchangeExec.scala:94,320`:
`SerializeConcatHostBuffersDeserializeBatch` builds the broadcast table on
device, serializes it to HOST buffers once, and every consumer re-materializes
it on its device).

The child executes exactly once, across ALL consumers (`ReusedExchangeExec`
semantics come free from instance caching). What the consumers are handed
depends on where they may sit.

A flat build side whose consumers sit in this process, on this chip, in this
query stays on the device: cut to the row bucket a reader of the host blob
would rebuild, parked spillable (so it pins no device memory between uses:
the reference keeps such a spillable device copy beside its host buffers),
and handed to each consumer as it is. The host round trip this skips (D2H at
the batch's capacity, zstd, CRC, H2D) was 0.14-0.17 s of a query with a
10 MB build side, all of it with the chip idle (PERF.md, fault 17).

Where a consumer may sit somewhere else (another query: the rescache's
broadcast seam keys host bytes; another chip: a mesh plan's shards) or the
layout is one the cut does not handle (nested or long-string columns), the
result is framed through the shuffle serializer into one host blob, the
device copy is dropped, and each `do_execute()` deserializes the blob into a
fresh device batch via a single H2D transfer: the reference's host-buffer
broadcast, and what a multi-host driver would ship over DCN."""

from __future__ import annotations

import sys
import threading
import weakref
from typing import Iterator, Optional

import jax.numpy as jnp

from ..columnar.batch import ColumnarBatch, Schema
from ..columnar.padding import row_bucket
from ..compile import sjit
from ..expr.base import Vec, vec_map_arrays
from ..utils import metrics as M
from .base import TpuExec, UnaryTpuExec, vecs_to_batch
from .coalesce import concat_batches

__all__ = ["TpuBroadcastExchangeExec"]


@sjit(op="exec.broadcast.cut", static_argnums=(1,))
def _cut_kernel(batch: ColumnarBatch, out_cap: int) -> ColumnarBatch:
    """Rows [0, out_cap) of a flat batch whose live rows lie first, the
    padding zeroed: the batch a reader of the blob rebuilds."""
    live = jnp.arange(out_cap) < batch.num_rows

    def cut(a):
        a = a[:out_cap]
        keep = live.reshape((out_cap,) + (1,) * (a.ndim - 1))
        return jnp.where(keep, a, jnp.zeros((), a.dtype))
    vecs = [vec_map_arrays(Vec.from_column(c), cut) for c in batch.columns]
    return vecs_to_batch(batch.schema, vecs, batch.num_rows)


class TpuBroadcastExchangeExec(UnaryTpuExec):
    def __init__(self, child: TpuExec, conf=None):
        super().__init__([child], conf)
        self._blob: Optional[bytes] = None
        self._parked = None  # SpillableColumnarBatch: the device payload
        self._empty = False
        self._lock = threading.Lock()
        self.collect_time = self.metrics.create(M.COLLECT_TIME, M.ESSENTIAL)
        self.build_time = self.metrics.create(M.BUILD_TIME, M.MODERATE)
        self.data_size = self.metrics.create(M.DATA_SIZE, M.ESSENTIAL)
        # per-consumer re-materialization cost (blob -> device batch)
        self.broadcast_time = self.metrics.create(M.BROADCAST_TIME,
                                                  M.MODERATE)

    @property
    def output(self) -> Schema:
        return self.child.output

    def _blob_needed(self) -> bool:
        """Whether a consumer may sit outside this process, chip or query."""
        from .. import rescache
        if rescache.is_enabled() and self.conf.get(
                "spark.rapids.tpu.rescache.broadcast.enabled"):
            return True
        mesh = sys.modules.get("spark_rapids_tpu.mesh")
        return mesh is not None and mesh.is_active()

    def _materialize(self) -> None:
        with self._lock:
            if self._blob is not None or self._parked is not None \
                    or self._empty:
                return
            if not self._blob_needed():
                batch = self._build_batch()
                if batch is None:
                    self._empty = True
                    return
                if all(c.children is None and c.overflow is None
                       for c in batch.columns):
                    self._park(batch)
                    return
                self._blob = self._serialize(batch)
                self.data_size.add(len(self._blob))
                return
            # broadcast rescache seam: an identical build subtree's
            # host-serialized payload is reused across queries (instance
            # caching already dedups consumers WITHIN one query; the
            # fragment cache extends it across rebuilt exec trees). The
            # blob is host bytes, so a hit costs no device work.
            from .. import rescache
            blob = rescache.cached_blob(self, self._build_blob)
            if blob is None:
                self._empty = True
                return
            self._blob = blob
            self.data_size.add(len(blob))

    def _build_batch(self) -> Optional[ColumnarBatch]:
        """Execute the child once and concatenate the build side (None =
        empty build side)."""
        with self.collect_time.timed():
            batches = list(self.child.execute())
        if not batches:
            return None
        with self.build_time.timed():
            return concat_batches(batches)

    def _serialize(self, batch: ColumnarBatch) -> bytes:
        from ..shuffle.codec import checksum_supported
        from ..shuffle.serializer import serialize_batch
        with self.build_time.timed():
            codec = self.conf.get("spark.rapids.shuffle.compression.codec")
            return serialize_batch(
                batch, codec, checksum=checksum_supported()
                and self.conf.get(
                    "spark.rapids.shuffle.checksum.enabled"))

    def _build_blob(self) -> Optional[bytes]:
        batch = self._build_batch()
        return None if batch is None else self._serialize(batch)

    def _park(self, batch: ColumnarBatch) -> None:
        from ..memory.spillable import SpillableColumnarBatch
        with self.build_time.timed():
            out_cap = row_bucket(int(batch.row_count()), op="shuffle")
            if out_cap < batch.capacity:
                batch = _cut_kernel(batch, out_cap)
            self._parked = SpillableColumnarBatch(batch)
        # the catalog entry goes with the plan node, whoever drops it
        weakref.finalize(self, self._parked.close)
        self.data_size.add(self._parked.size_bytes)

    def do_execute(self) -> Iterator[ColumnarBatch]:
        self._materialize()
        if self._empty:
            return
        if self._parked is not None:
            with self.broadcast_time.timed():
                out = self._parked.get_batch()
            self.num_output_rows.add(out.row_count())
            yield self._count_output(out)
            return
        from ..shuffle.serializer import concat_host_tables, deserialize_table
        # verify=False: the blob was serialized in this process and never
        # left memory; re-hashing it for every consuming task buys nothing
        with self.broadcast_time.timed():
            table, _ = deserialize_table(self._blob, verify=False)
            out = concat_host_tables([table])
        self.num_output_rows.add(out.row_count())
        yield self._count_output(out)

    def _arg_string(self):
        return "[device]" if self._parked is not None else "[host-serialized]"
