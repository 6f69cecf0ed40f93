"""Host (CPU-engine) columnar batches.

Mirrors the reference's host-side vectors (`RapidsHostColumnVector.java`,
`RapidsHostColumnVectorCore.java`): same logical layout as the device columns (data +
validity + byte-matrix strings) but numpy arrays at EXACT logical length — no padding,
no traced counts. The CPU engine evaluates the same xp-generic expression kernels over
these, making it the differential-testing peer that CPU Spark is in the reference's
harness (SURVEY.md §4)."""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import numpy as np

from .. import types as T
from ..columnar.batch import Schema
from ..columnar.padding import width_bucket
from ..expr.base import Vec

__all__ = ["HostBatch", "host_batch_from_arrow", "host_batch_to_arrow", "host_vec_from_arrow"]


@dataclasses.dataclass
class HostBatch:
    schema: Schema
    vecs: List[Vec]
    num_rows: int

    def vec(self, i: int) -> Vec:
        return self.vecs[i]


from ..expr.base import vec_map_arrays  # noqa: F401  (canonical home)


def host_vec_from_arrow(arr) -> Vec:
    import pyarrow as pa
    if isinstance(arr, pa.ChunkedArray):
        arr = arr.combine_chunks()
    dtype = T.from_arrow(arr.type)
    n = len(arr)
    valid = np.ones(n, dtype=bool) if arr.null_count == 0 else \
        np.asarray(arr.is_valid())
    if isinstance(dtype, T.ArrayType):
        # fixed-fanout layout: per-row size vector + [n, K] element matrix
        la = arr.cast(pa.large_list(arr.type.value_type))
        offs = np.frombuffer(la.buffers()[1], dtype=np.int64, count=n + 1,
                             offset=la.offset * 8)
        lens, scatter = _fanout_scatter(n, valid, offs)
        elem = vec_map_arrays(host_vec_from_arrow(la.values), scatter)
        return Vec(dtype, lens, valid, None, (elem,))
    if isinstance(dtype, T.MapType):
        # map layout = array layout with (keys, values) children: per-row
        # entry count + [n, K] parallel key/value matrices.
        # MapArray.offsets is already windowed to [n+1]; keys/items are the
        # full child arrays the offsets index into (verified behavior)
        offs = np.asarray(arr.offsets, dtype=np.int64)
        lens, scatter = _fanout_scatter(n, valid, offs)
        return Vec(dtype, lens, valid, None,
                   (vec_map_arrays(host_vec_from_arrow(arr.keys), scatter),
                    vec_map_arrays(host_vec_from_arrow(arr.items),
                                   scatter)))
    if isinstance(dtype, T.StructType):
        kids = tuple(host_vec_from_arrow(arr.field(i))
                     for i in range(arr.type.num_fields))
        return Vec(dtype, valid.copy(), valid, None, kids)
    if isinstance(dtype, T.StringType):
        la = arr.cast(pa.large_string())
        buffers = la.buffers()
        offsets = np.frombuffer(buffers[1], dtype=np.int64, count=n + 1,
                                offset=la.offset * 8)
        databuf = np.frombuffer(buffers[2], dtype=np.uint8) if buffers[2] else \
            np.zeros(0, np.uint8)
        lens = np.where(valid, np.diff(offsets), 0).astype(np.int32)
        w = width_bucket(int(lens.max()) if n and lens.size else 1)
        chars = np.zeros((n, w), dtype=np.uint8)
        if n:
            row_id = np.repeat(np.arange(n), lens)
            if row_id.size:
                out_starts = np.concatenate(([0], np.cumsum(lens)[:-1]))
                within = np.arange(row_id.size) - np.repeat(out_starts, lens)
                src = np.repeat(offsets[:-1], lens) + within
                chars[row_id, within] = databuf[src]
        return Vec(dtype, chars, valid, lens)
    if isinstance(dtype, T.DecimalType) and \
            dtype.precision > T.DecimalType.MAX_LONG_DIGITS:
        from ..expr.decimal128 import split_int, unscaled_int
        limbs = np.zeros((n, 2), np.int64)
        for i, v in enumerate(arr):
            if v.is_valid:
                limbs[i] = split_int(unscaled_int(v.as_py(), dtype.scale))
        return Vec(dtype, limbs, valid)
    npdt = dtype.np_dtype
    if npdt is None:
        raise TypeError(f"type not host-vec-backed: {arr.type}")
    if isinstance(dtype, T.DecimalType):
        from ..expr.decimal128 import unscaled_int
        vals = np.array([unscaled_int(v.as_py(), dtype.scale)
                         if v.is_valid else 0
                         for v in arr], dtype=np.int64)
    elif isinstance(dtype, (T.TimestampType, T.DateType)):
        ints = arr.cast(pa.int64() if isinstance(dtype, T.TimestampType)
                        else pa.int32())
        vals = ints.fill_null(0).to_numpy(zero_copy_only=False)
    elif arr.null_count:
        zero = False if isinstance(dtype, T.BooleanType) else 0
        vals = arr.fill_null(zero).to_numpy(zero_copy_only=False)
    else:
        vals = arr.to_numpy(zero_copy_only=False)
    if np.issubdtype(np.asarray(vals).dtype, np.floating) and not valid.all():
        vals = np.where(valid, vals, 0.0)
    return Vec(dtype, np.ascontiguousarray(vals).astype(npdt, copy=False), valid)


def _fanout_scatter(n: int, valid: np.ndarray, offs: np.ndarray):
    """Shared offsets->fixed-fanout machinery for list-shaped layouts
    (arrays and maps): per-row lengths plus a closure scattering any flat
    child buffer into its [n, K] slot matrix."""
    lens_raw = offs[1:] - offs[:-1]
    lens = np.where(valid, lens_raw, 0).astype(np.int32)
    k = width_bucket(int(lens.max())) if n and lens.size else 8
    row_id = np.repeat(np.arange(n), lens)
    within = (np.arange(row_id.size) -
              np.repeat(np.concatenate(([0], np.cumsum(lens)[:-1])), lens)) \
        if n else np.zeros(0, np.int64)
    src = np.repeat(offs[:-1], lens) + within if n else \
        np.zeros(0, np.int64)

    def scatter(leaf):
        out = np.zeros((n, k) + leaf.shape[1:], dtype=leaf.dtype)
        if row_id.size:
            out[row_id, within] = leaf[src]
        return out

    return lens, scatter


def host_batch_from_arrow(table) -> HostBatch:
    vecs = [host_vec_from_arrow(table.column(n)) for n in table.schema.names]
    return HostBatch(Schema.from_arrow(table.schema), vecs, table.num_rows)


def host_vec_to_arrow(v: Vec, num_rows: Optional[int] = None):
    import pyarrow as pa
    n = num_rows if num_rows is not None else v.validity.shape[0]
    valid = np.asarray(v.validity[:n]).astype(bool)
    mask = ~valid
    if isinstance(v.dtype, T.NullType):
        return pa.nulls(n)
    if isinstance(v.dtype, T.ArrayType):
        lens = np.where(valid, np.asarray(v.data[:n]), 0).astype(np.int64)
        elem = v.children[0]
        k = elem.data.shape[1] if elem.data.ndim >= 2 else 0
        keep = (np.arange(k)[None, :] < lens[:, None]) if n and k else \
            np.zeros((n, k), dtype=bool)

        def flatten(leaf):
            return np.asarray(leaf[:n])[keep]

        flat = vec_map_arrays(elem, flatten)
        values = host_vec_to_arrow(flat, int(lens.sum()))
        offsets = np.concatenate(([0], np.cumsum(lens)))
        out = pa.LargeListArray.from_arrays(offsets, values)
        if mask.any():
            # stamp the null bitmap on (from_arrays has no mask for lists)
            out = pa.Array.from_buffers(
                out.type, n,
                [pa.py_buffer(np.packbits(valid, bitorder="little").tobytes()),
                 out.buffers()[1]],
                null_count=int(mask.sum()), children=[values])
        return out.cast(pa.list_(out.type.value_type))
    if isinstance(v.dtype, T.MapType):
        lens = np.where(valid, np.asarray(v.data[:n]), 0).astype(np.int64)
        keys_m, items_m = v.children
        k = keys_m.validity.shape[1] if keys_m.validity.ndim >= 2 else 0
        keep = (np.arange(k)[None, :] < lens[:, None]) if n and k else \
            np.zeros((n, k), dtype=bool)

        def flatten(leaf):
            return np.asarray(leaf[:n])[keep]

        total = int(lens.sum())
        keys_a = host_vec_to_arrow(vec_map_arrays(keys_m, flatten), total)
        items_a = host_vec_to_arrow(vec_map_arrays(items_m, flatten), total)
        offsets = np.concatenate(([0], np.cumsum(lens))).astype(np.int32)
        out = pa.MapArray.from_arrays(offsets, keys_a, items_a)
        if mask.any():
            out = pa.Array.from_buffers(
                out.type, n,
                [pa.py_buffer(np.packbits(valid,
                                          bitorder="little").tobytes()),
                 out.buffers()[1]],
                null_count=int(mask.sum()),
                children=[out.values])
        return out
    if isinstance(v.dtype, T.StructType):
        fields = [host_vec_to_arrow(c, n) for c in v.children]
        return pa.StructArray.from_arrays(
            fields, names=[f.name for f in v.dtype.fields],
            mask=pa.array(mask))
    if v.is_string:
        chars = np.asarray(v.data[:n])
        lens = np.where(valid, np.asarray(v.lengths[:n]), 0).astype(np.int64)
        w = chars.shape[1] if chars.ndim == 2 else 0
        if n and w:
            keep = np.arange(w)[None, :] < lens[:, None]
            flat = chars[keep]
        else:
            flat = np.zeros(0, np.uint8)
        offsets = np.concatenate(([0], np.cumsum(lens)))
        return pa.Array.from_buffers(
            pa.large_string(), n,
            [pa.py_buffer(np.packbits(valid, bitorder="little").tobytes()),
             pa.py_buffer(offsets.astype(np.int64).tobytes()),
             pa.py_buffer(flat.tobytes())],
            null_count=int(mask.sum())).cast(pa.string())
    vals = np.asarray(v.data[:n])
    at = T.to_arrow(v.dtype)
    if isinstance(v.dtype, T.DecimalType):
        # arrow's decimal128 is the unscaled integer in 16 little-endian
        # bytes: the low word, then the high one (a 64-bit value's sign).
        # No Python object per row (PERF.md, fault 4).
        if vals.ndim == 2:
            hi, lo = vals[:, 0], vals[:, 1]
        else:
            lo = vals.astype(np.int64)
            hi = lo >> 63
        words = np.stack([lo.astype(np.int64), hi.astype(np.int64)], axis=1)
        words[mask] = 0
        bitmap = None if valid.all() else pa.py_buffer(
            np.packbits(valid, bitorder="little").tobytes())
        return pa.Array.from_buffers(
            at, n, [bitmap, pa.py_buffer(words.astype("<i8").tobytes())],
            null_count=int(mask.sum()))
    return pa.array(vals, type=at, mask=mask if mask.any() else None)


def host_batch_to_arrow(b: HostBatch):
    import pyarrow as pa
    arrays = [host_vec_to_arrow(v, b.num_rows) for v in b.vecs]
    return pa.table(arrays, schema=b.schema.to_arrow())
