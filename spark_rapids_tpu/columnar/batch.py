"""Columnar batches.

TPU counterpart of Spark's `ColumnarBatch` carrying `GpuColumnVector`s (reference
`GpuColumnVector.java:637` from(ColumnarBatch) / `:669` from(Table, DataType[])). A
`ColumnarBatch` here is a pytree: a tuple of `Column`s plus a traced scalar `num_rows`,
with the schema static. All columns share one capacity bucket. The traced row count is
what lets filters/joins change cardinality without recompiling (ARCHITECTURE.md #1).
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import types as T
from ..utils import spans
from .column import Column, from_arrow as col_from_arrow, from_numpy as col_from_numpy, \
    to_arrow as col_to_arrow
from .padding import row_bucket

__all__ = ["Schema", "ColumnarBatch", "batch_from_arrow", "batch_to_arrow",
           "batch_from_dict", "empty_batch"]


@dataclasses.dataclass(frozen=True)
class Schema:
    names: Tuple[str, ...]
    types: Tuple[T.DataType, ...]

    def __post_init__(self):
        assert len(self.names) == len(self.types)

    def __len__(self):
        return len(self.names)

    def index_of(self, name: str) -> int:
        return self.names.index(name)

    def field(self, i: int) -> Tuple[str, T.DataType]:
        return self.names[i], self.types[i]

    def to_arrow(self):
        import pyarrow as pa
        return pa.schema([pa.field(n, T.to_arrow(t))
                          for n, t in zip(self.names, self.types)])

    @staticmethod
    def from_arrow(schema) -> "Schema":
        return Schema(tuple(schema.names),
                      tuple(T.from_arrow(f.type) for f in schema))

    def __repr__(self):
        inner = ", ".join(f"{n}: {t.simple_string()}"
                          for n, t in zip(self.names, self.types))
        return f"Schema({inner})"


def join_output_schema(left: Schema, right: Schema, join_type: str) -> Schema:
    """Output schema of a join — the ONE definition shared by the CPU oracle
    and both device join execs so they can never drift. semi/anti project the
    left side; existence appends the bool `exists` flag; everything else
    (inner/cross/left/right/full) is the combined row."""
    if join_type in ("semi", "anti"):
        return left
    if join_type == "existence":
        return Schema(left.names + ("exists",),
                      left.types + (T.BooleanType(),))
    return Schema(left.names + right.names, left.types + right.types)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class ColumnarBatch:
    """columns: per-field device Columns; num_rows: traced int32 scalar."""

    schema: Schema
    columns: Tuple[Column, ...]
    num_rows: jnp.ndarray  # int32 scalar (device)

    def tree_flatten(self):
        return (tuple(self.columns), self.num_rows), self.schema

    @classmethod
    def tree_unflatten(cls, schema, leaves):
        columns, num_rows = leaves
        return cls(schema, tuple(columns), num_rows)

    # ------------------------------------------------------------------
    @property
    def num_cols(self) -> int:
        return len(self.columns)

    @property
    def capacity(self) -> int:
        if self.columns:
            return self.columns[0].capacity
        return 0

    def row_count(self) -> int:
        """Host-synchronizing logical row count (use only on host paths).
        On a device scalar the host blocks until every program queued before
        it has run: that wait is the `sync.row_count` span and
        `TaskMetrics.host_sync_ns/_count`. A host int costs and records
        nothing."""
        n = self.num_rows
        if not isinstance(n, jax.Array):
            return int(n)
        with spans.span("sync.row_count", add={"host_sync_count": 1}):
            return int(n)

    def row_mask(self) -> jnp.ndarray:
        """bool[cap]: True for live (non-padding) rows. Fused away by XLA."""
        return jnp.arange(self.capacity, dtype=jnp.int32) < self.num_rows

    def column(self, i: int) -> Column:
        return self.columns[i]

    def device_memory_size(self) -> int:
        return sum(c.device_memory_size() for c in self.columns)

    def with_columns(self, schema: Schema, columns: Sequence[Column],
                     num_rows=None) -> "ColumnarBatch":
        return ColumnarBatch(schema, tuple(columns),
                             self.num_rows if num_rows is None else num_rows)

    def select(self, indices: Sequence[int]) -> "ColumnarBatch":
        return ColumnarBatch(
            Schema(tuple(self.schema.names[i] for i in indices),
                   tuple(self.schema.types[i] for i in indices)),
            tuple(self.columns[i] for i in indices), self.num_rows)

    def repadded(self, new_cap: int) -> "ColumnarBatch":
        return ColumnarBatch(self.schema,
                             tuple(c.repadded(new_cap) for c in self.columns),
                             self.num_rows)


def batch_from_arrow(table, capacity: Optional[int] = None) -> ColumnarBatch:
    """pyarrow Table/RecordBatch -> device ColumnarBatch (the H2D boundary)."""
    n = table.num_rows
    cap = capacity or row_bucket(n, op="scan")
    cols: List[Column] = []
    for name in table.schema.names:
        col, _ = col_from_arrow(table.column(name), capacity=cap)
        cols.append(col)
    schema = Schema.from_arrow(table.schema)
    return ColumnarBatch(schema, tuple(cols), jnp.asarray(n, dtype=jnp.int32))


def batch_from_dict(data: dict, types_map: Optional[dict] = None,
                    capacity: Optional[int] = None) -> ColumnarBatch:
    """Convenience constructor from {name: np.ndarray/list} (tests, data_gen)."""
    names = tuple(data.keys())
    n = len(next(iter(data.values()))) if data else 0
    cap = capacity or row_bucket(n, op="scan")
    cols = []
    tps = []
    for name in names:
        vals = data[name]
        if types_map and name in types_map:
            dt = types_map[name]
        else:
            dt = _infer_type(vals)
        valid = None
        if isinstance(vals, (list, tuple)):
            valid = np.array([v is not None for v in vals])
            if isinstance(dt, T.StringType):
                pass
            else:
                vals = np.array([0 if v is None else v for v in vals],
                                dtype=dt.np_dtype)
        col, _ = col_from_numpy(dt, vals if not isinstance(vals, (list, tuple))
                                else list(vals), valid, capacity=cap)
        cols.append(col)
        tps.append(dt)
    return ColumnarBatch(Schema(names, tuple(tps)), tuple(cols),
                         jnp.asarray(n, dtype=jnp.int32))


def _infer_type(vals) -> T.DataType:
    if isinstance(vals, np.ndarray):
        k = vals.dtype
        m = {np.dtype(np.bool_): T.BOOLEAN, np.dtype(np.int8): T.BYTE,
             np.dtype(np.int16): T.SHORT, np.dtype(np.int32): T.INT,
             np.dtype(np.int64): T.LONG, np.dtype(np.float32): T.FLOAT,
             np.dtype(np.float64): T.DOUBLE}
        if k in m:
            return m[k]
        raise TypeError(f"cannot infer type for dtype {k}")
    for v in vals:
        if v is None:
            continue
        if isinstance(v, bool):
            return T.BOOLEAN
        if isinstance(v, int):
            return T.LONG
        if isinstance(v, float):
            return T.DOUBLE
        if isinstance(v, str):
            return T.STRING
    return T.NULL


def batch_to_arrow(batch: ColumnarBatch):
    """Device ColumnarBatch -> pyarrow Table (the D2H boundary)."""
    import pyarrow as pa
    n = batch.row_count()
    arrays = [col_to_arrow(c, n) for c in batch.columns]
    return pa.table(arrays, schema=batch.schema.to_arrow())


def empty_batch(schema: Schema, capacity: int = 0) -> ColumnarBatch:
    from ..expr.base import zero_vec
    cap = row_bucket(max(capacity, 1))
    cols = tuple(zero_vec(jnp, dt, (cap,)).to_column()
                 for dt in schema.types)
    return ColumnarBatch(schema, cols, jnp.asarray(0, jnp.int32))
