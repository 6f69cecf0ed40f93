"""Shared by the query files: result tables as exact integer frames (a copy
of `chip_smoke._cents/_frame`, PR 23), the money column as a control carries
it, and the bytes a scan has to move."""

from __future__ import annotations

import numpy as np


def cents(arr):
    """decimal(p, 2) arrow array -> int64 unscaled numpy (nulls -> 0)."""
    import decimal

    import pyarrow as pa
    import pyarrow.compute as pc
    # narrowed first: pyarrow's sum is decimal(38, 2), and x100 of that has
    # no room; both casts are checked, so nothing is lost silently
    wide = pc.multiply(arr.cast(pa.decimal128(20, 2)),
                       pa.scalar(decimal.Decimal(100)))
    return pc.fill_null(wide.cast(pa.int64()), 0).to_numpy()


def frame(table, money=()):
    """Result table -> pandas, decimal columns as exact int64 cents plus a
    null mask (so equality is exact and cheap at 500k rows). A money column
    that is not a decimal is no answer of this engine's: it raises."""
    import pandas as pd
    import pyarrow as pa
    cols = {}
    for name in table.schema.names:
        col = table.column(name).combine_chunks()
        if name in money:
            if not pa.types.is_decimal(col.type) or col.type.scale != 2:
                raise TypeError(f"{name} came back as {col.type}, "
                                "not decimal(p, 2)")
            cols[name] = cents(col)
            cols[name + "_null"] = col.is_null().to_numpy(
                zero_copy_only=False)
        else:
            cols[name] = col.to_numpy(zero_copy_only=False)
    return pd.DataFrame(cols)


def carried(price, dtype: str):
    """A decimal arrow column as a control carries it: (float32 values that
    went through `dtype`, nulls as 0; null mask)."""
    import ml_dtypes  # noqa: F401 — registers bfloat16 with numpy
    import pyarrow as pa
    price = price.combine_chunks()
    null = price.is_null().to_numpy(zero_copy_only=False)
    val = (price.cast(pa.float64()).fill_null(0.0).to_numpy()
           .astype(np.dtype(dtype)).astype(np.float32))
    return val, null


def money(summed, has):
    """Float sums back to decimal(p, 2), rounded to the cent; null where
    `has` is false."""
    import decimal

    import pyarrow as pa
    import pyarrow.compute as pc
    cents_ = pa.array(np.rint(np.asarray(summed, np.float64) * 100)
                      .astype(np.int64)).cast(pa.decimal128(19, 0))
    return pc.if_else(pa.array(np.asarray(has, bool)),
                      pc.multiply(cents_, pa.scalar(decimal.Decimal("0.01"))),
                      pa.scalar(None, pa.decimal128(23, 2)))


def scanned_bytes(path: str, widths: dict) -> int:
    """Parquet bytes of the columns in `widths` plus their decoded bytes
    once (`widths`: column -> decoded bytes per row), from the footer."""
    import pyarrow.parquet as pq
    md = pq.ParquetFile(path).metadata
    total = md.num_rows * sum(widths.values())
    for rg in range(md.num_row_groups):
        for c in range(md.num_columns):
            chunk = md.row_group(rg).column(c)
            if chunk.path_in_schema in widths:
                total += chunk.total_compressed_size
    return total
