"""TPC-DS star corpus shared by `chip_smoke.py` and the benchmark to come:
a seeded, vectorised generator for `store_sales` and its dimensions, and
the four star queries written the way Spark hands them to the plugin —
hash joins WITH KEYS (`on=`), so they plan as `TpuBroadcastHashJoinExec`.
A bare `join(condition=a == b)` carries no keys and plans as a nested-loop
join over the cross product; never write an equi-join that way.

The fact table is PARQUET WITH A DECIMAL money column, so the device scan
path (decimal FLBA decode, fused multi-column program) is on the path."""

from __future__ import annotations

import json
import os

import numpy as np

# TPC-DS SF10 row counts (specification table 3-2)
SF10_ROWS = {"store_sales": 28_800_991, "date_dim": 73_049,
             "item": 102_000, "store": 102, "customer": 500_000}
ROW_GROUP = 1 << 20
# d_date_sk is a Julian day number: 2415022 = 1900-01-02, and store_sales
# spans 1998-01-02 .. 2003-01-02 (specification 3.4, dsdgen's data range)
_DATE_SK0 = 2_415_022
_SALES_SK = (2_450_816, 2_452_643)
_CATEGORIES = ("Books", "Children", "Electronics", "Home", "Jewelry",
               "Men", "Music", "Shoes", "Sports", "Women")
_STATES = ("TN", "SD", "AL", "GA", "OH", "MI", "TX", "IL", "NE")


def decimal_array(unscaled: np.ndarray, nulls: np.ndarray, precision: int,
                  scale: int):
    """int64 unscaled values -> pyarrow decimal128 without a Python loop:
    the low word is the value, the high word its sign extension."""
    import pyarrow as pa
    words = np.empty((len(unscaled), 2), dtype=np.int64)
    words[:, 0] = unscaled
    words[:, 1] = unscaled >> 63
    validity = np.packbits(~nulls, bitorder="little")
    return pa.Array.from_buffers(
        pa.decimal128(precision, scale), len(unscaled),
        [pa.py_buffer(validity), pa.py_buffer(words)],
        null_count=int(nulls.sum()))


def star_tables(seed: int, fact_rows: int) -> dict:
    """The star as pyarrow tables; dimensions always at SF10 size."""
    import pyarrow as pa
    rng = np.random.default_rng(seed)
    n = fact_rows
    n_dates, n_items = SF10_ROWS["date_dim"], SF10_ROWS["item"]
    n_stores, n_cust = SF10_ROWS["store"], SF10_ROWS["customer"]
    store_sales = pa.table({
        "ss_sold_date_sk": rng.integers(*_SALES_SK, n, dtype=np.int64),
        "ss_item_sk": rng.integers(1, n_items + 1, n, dtype=np.int64),
        "ss_store_sk": rng.integers(1, n_stores + 1, n, dtype=np.int64),
        "ss_customer_sk": rng.integers(1, n_cust + 1, n, dtype=np.int64),
        "ss_quantity": rng.integers(1, 101, n, dtype=np.int32),
        "ss_sales_price": decimal_array(
            rng.integers(0, 20_001, n, dtype=np.int64),
            rng.random(n) < 0.02, 7, 2),
    })
    sk = _DATE_SK0 + np.arange(n_dates, dtype=np.int64)
    days = np.datetime64("1900-01-02") + np.arange(n_dates)
    months = days.astype("datetime64[M]")
    date_dim = pa.table({
        "d_date_sk": sk,
        "d_year": (months.astype("datetime64[Y]").astype(np.int64)
                   + 1970).astype(np.int32),
        "d_moy": (months.astype(np.int64) % 12 + 1).astype(np.int32),
        # 1900-01-02 was a Tuesday; TPC-DS counts d_dow from Sunday = 0
        "d_dow": ((np.arange(n_dates) + 2) % 7).astype(np.int32),
    })
    item = pa.table({
        "i_item_sk": np.arange(1, n_items + 1, dtype=np.int64),
        "i_brand": pa.array(np.char.add(
            "brand #", rng.integers(1, 713, n_items).astype(str))),
        "i_category": pa.array(np.asarray(_CATEGORIES)[
            rng.integers(0, len(_CATEGORIES), n_items)]),
        "i_price": rng.uniform(0.09, 99.99, n_items).round(2),
    })
    store = pa.table({
        "s_store_sk": np.arange(1, n_stores + 1, dtype=np.int64),
        "s_state": pa.array(np.asarray(_STATES)[
            rng.integers(0, len(_STATES), n_stores)]),
    })
    customer = pa.table({
        "c_customer_sk": np.arange(1, n_cust + 1, dtype=np.int64),
        "c_birth_year": rng.integers(1924, 1993, n_cust, dtype=np.int32),
    })
    return {"store_sales": store_sales, "date_dim": date_dim, "item": item,
            "store": store, "customer": customer}


def write_star(data_dir: str, seed: int = 0,
               fact_rows: int = SF10_ROWS["store_sales"]) -> dict:
    """Write the star as snappy parquet (1M-row row groups) under
    `data_dir`, or reuse what a run with the same seed and rows left there.
    Returns {table: {"path", "rows", "bytes"}}."""
    import pyarrow.parquet as pq
    stamp = {"seed": seed, "fact_rows": fact_rows, "row_group": ROW_GROUP}
    manifest = os.path.join(data_dir, "MANIFEST.json")
    if os.path.exists(manifest):
        with open(manifest) as f:
            have = json.load(f)
        if have.get("stamp") == stamp and all(
                os.path.exists(t["path"]) for t in have["tables"].values()):
            return have["tables"]
    os.makedirs(data_dir, exist_ok=True)
    tables = {}
    for name, tbl in star_tables(seed, fact_rows).items():
        path = os.path.join(data_dir, f"{name}.parquet")
        pq.write_table(tbl, path, compression="snappy",
                       row_group_size=ROW_GROUP)
        tables[name] = {"path": path, "rows": tbl.num_rows,
                        "bytes": os.path.getsize(path)}
    with open(manifest, "w") as f:
        json.dump({"stamp": stamp, "tables": tables}, f)
    return tables


def star_queries(session, paths: dict) -> dict:
    """q3, q7, q96 and the per-customer rank over `paths` (table -> file).
    Each dimension's key is renamed to the fact column it joins, so the
    join carries hash keys."""
    from spark_rapids_tpu.expr import (Average, Count, RowNumber, Sum, col,
                                       lit)
    ss = session.read_parquet(paths["store_sales"])
    dd = session.read_parquet(paths["date_dim"])
    it = session.read_parquet(paths["item"])
    st = session.read_parquet(paths["store"])

    def dim(df, key, fact_key, *keep):
        return df.select(col(key).alias(fact_key), *map(col, keep))

    q3 = (ss.join(dim(dd, "d_date_sk", "ss_sold_date_sk", "d_year", "d_moy"),
                  on="ss_sold_date_sk")
          .filter(col("d_moy") == lit(11))
          .join(dim(it, "i_item_sk", "ss_item_sk", "i_brand"),
                on="ss_item_sk")
          .group_by("d_year", "i_brand")
          .agg(sum_agg=Sum(col("ss_sales_price"))))
    q7 = (ss.join(dim(it, "i_item_sk", "ss_item_sk", "i_category"),
                  on="ss_item_sk")
          .join(dim(st, "s_store_sk", "ss_store_sk", "s_state"),
                on="ss_store_sk")
          .filter(col("s_state") == lit("TN"))
          .group_by("i_category")
          .agg(q=Average(col("ss_quantity")), n=Count(lit(1))))
    per_cust = (ss.group_by("ss_customer_sk")
                .agg(spend=Sum(col("ss_sales_price")),
                     qty=Sum(col("ss_quantity"))))
    q68 = per_cust.window(partition_by=[],
                          order_by=[(col("spend"), False, False)],
                          rnk=RowNumber())
    q96 = (ss.join(dim(dd, "d_date_sk", "ss_sold_date_sk", "d_dow"),
                   on="ss_sold_date_sk")
           .filter((col("d_dow") == lit(6)) & (col("ss_quantity")
                                               > lit(50)))
           .join(dim(st, "s_store_sk", "ss_store_sk"), on="ss_store_sk")
           .agg(cnt=Count(lit(1))))
    return {"q3_brand_report": q3, "q7_star_avg": q7,
            "q68_window_rank": q68, "q96_selective_count": q96}
