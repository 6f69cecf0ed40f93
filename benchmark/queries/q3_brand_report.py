"""TPC-DS query 3 as published (specification v3, `query3.tpl` with the
qualification substitutions MANUFACT 128, MONTH 11, AGGC ss_ext_sales_price):

    select dt.d_year, item.i_brand_id brand_id, item.i_brand brand,
           sum(ss_ext_sales_price) sum_agg
    from date_dim dt, store_sales, item
    where dt.d_date_sk = store_sales.ss_sold_date_sk
      and store_sales.ss_item_sk = item.i_item_sk
      and item.i_manufact_id = 128 and dt.d_moy = 11
    group by dt.d_year, item.i_brand, item.i_brand_id
    order by dt.d_year, sum_agg desc, brand_id
    limit 100

written the way Spark hands it to the plugin: each scan reads only the columns
the query names (Catalyst's ReadSchema), each dimension's filter sits on its
scan, the filtered dimensions are the build sides of two inner hash joins with
keys, in the FROM clause's order."""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _frames import carried, frame, money, scanned_bytes  # noqa: E402

TABLES = ("store_sales", "date_dim", "item")
MANUFACT, MONTH, LIMIT = 128, 11, 100
# every number `compare` reports, with the most it may read; exact, so 0
LIMITS = {"rows_off": 0, "sums_off": 0}
_READ = {"store_sales": ["ss_sold_date_sk", "ss_item_sk",
                         "ss_ext_sales_price"],
         "date_dim": ["d_date_sk", "d_year", "d_moy"],
         "item": ["i_item_sk", "i_brand_id", "i_brand", "i_manufact_id"]}
_OUT = ["d_year", "brand_id", "brand", "sum_agg"]


def scans(session, paths: dict) -> dict:
    """The query's scans, one per table, with the columns it reads."""
    return {t: session.read_parquet(paths[t], columns=list(cols))
            for t, cols in _READ.items()}


def build(session, paths: dict):
    from spark_rapids_tpu.expr import Sum, col, lit
    s = scans(session, paths)
    dt = (s["date_dim"].filter(col("d_moy") == lit(MONTH))
          .select(col("d_date_sk").alias("ss_sold_date_sk"), col("d_year")))
    item = (s["item"].filter(col("i_manufact_id") == lit(MANUFACT))
            .select(col("i_item_sk").alias("ss_item_sk"),
                    col("i_brand_id"), col("i_brand")))
    return (s["store_sales"].join(dt, on="ss_sold_date_sk")
            .join(item, on="ss_item_sk")
            .group_by("d_year", "i_brand", "i_brand_id")
            .agg(sum_agg=Sum(col("ss_ext_sales_price")))
            # Spark's null order: first when ascending, last when descending
            .sort((col("d_year"), True, True), (col("sum_agg"), False, False),
                  (col("i_brand_id"), True, True))
            .limit(LIMIT)
            .select(col("d_year"), col("i_brand_id").alias("brand_id"),
                    col("i_brand").alias("brand"), col("sum_agg")))


def _joined(paths: dict):
    import pyarrow.compute as pc
    import pyarrow.parquet as pq
    ss = pq.read_table(paths["store_sales"], columns=_READ["store_sales"])
    dd = pq.read_table(paths["date_dim"], columns=_READ["date_dim"])
    it = pq.read_table(paths["item"], columns=_READ["item"])
    dd = dd.filter(pc.equal(dd["d_moy"], MONTH))
    it = it.filter(pc.equal(it["i_manufact_id"], MANUFACT))
    return (ss.join(dd.select(["d_date_sk", "d_year"]),
                    keys="ss_sold_date_sk", right_keys="d_date_sk",
                    join_type="inner")
            .join(it.select(["i_item_sk", "i_brand_id", "i_brand"]),
                  keys="ss_item_sk", right_keys="i_item_sk",
                  join_type="inner"))


def _ordered(year, brand_id, brand, sum_agg):
    """Grouped rows -> the published order and limit, as an arrow table."""
    import pyarrow as pa
    t = pa.table({"d_year": year, "brand_id": brand_id, "brand": brand,
                  "sum_agg": sum_agg})
    return t.sort_by([("d_year", "ascending"), ("sum_agg", "descending"),
                      ("brand_id", "ascending")]).slice(0, LIMIT)


def reference(paths: dict):
    """The answer from pyarrow's own reader, join, group-by and sort (its
    sort puts nulls last, which is Spark's place for them under `desc`; the
    ascending keys hold none after the inner joins)."""
    g = (_joined(paths).group_by(["d_year", "i_brand", "i_brand_id"])
         .aggregate([("ss_ext_sales_price", "sum")]))
    return _ordered(g["d_year"], g["i_brand_id"], g["i_brand"],
                    g["ss_ext_sales_price_sum"])


def control(paths: dict, dtype: str):
    """The reference with the money column carried in `dtype` ("bfloat16",
    "float32") and summed in float32, back to decimal at the end: what a
    device path that gave up exact decimals would return."""
    import pandas as pd
    import pyarrow as pa
    j = _joined(paths)
    val, null = carried(j["ss_ext_sales_price"], dtype)
    f = pd.DataFrame({"d_year": j["d_year"].to_numpy(),
                      "i_brand_id": j["i_brand_id"].to_numpy(),
                      "i_brand": j["i_brand"].to_numpy(zero_copy_only=False),
                      "v": val, "n": ~null})
    g = (f.groupby(["d_year", "i_brand", "i_brand_id"], sort=False)
         .agg(v=("v", "sum"), n=("n", "sum")).reset_index())
    return _ordered(g["d_year"].to_numpy(), g["i_brand_id"].to_numpy(),
                    pa.array(g["i_brand"]), money(g["v"], g["n"] > 0))


def compare(got, want) -> dict:
    """Row by row, in the order returned: `rows_off`, the places whose year,
    brand id or brand differ, and the rows one answer has more than the
    other; `sums_off`, the places with the same group and another sum. All
    0 for a right answer."""
    if got.schema.names != _OUT:
        raise TypeError(f"columns {got.schema.names}, not {_OUT}")
    g, w = frame(got, ["sum_agg"]), frame(want, ["sum_agg"])
    n = min(len(g), len(w))
    g, w = g.iloc[:n], w.iloc[:n]
    same = ((g["d_year"].to_numpy() == w["d_year"].to_numpy())
            & (g["brand_id"].to_numpy() == w["brand_id"].to_numpy())
            & (g["brand"].to_numpy() == w["brand"].to_numpy()))
    sums = ((g["sum_agg"].to_numpy() == w["sum_agg"].to_numpy())
            & (g["sum_agg_null"].to_numpy() == w["sum_agg_null"].to_numpy()))
    return {"rows_off": int((~same).sum()) + abs(got.num_rows - want.num_rows),
            "sums_off": int((same & ~sums).sum())}


def least_bytes(tables: dict) -> int:
    """The least bytes the query has to move through HBM, whatever
    implements it: the parquet bytes of the columns it reads, their decoded
    bytes once, and the result. From the tables' metadata alone."""
    widths = {"ss_sold_date_sk": 8, "ss_item_sk": 8, "ss_ext_sales_price": 8,
              "d_date_sk": 8, "d_year": 4, "d_moy": 4,
              "i_item_sk": 8, "i_brand_id": 4, "i_brand": 22,
              "i_manufact_id": 4}
    # the result: 100 rows of int32 + int32 + 22-byte brand + decimal
    return LIMIT * 46 + sum(
        scanned_bytes(tables[t]["path"], {c: widths[c] for c in cols})
        for t, cols in _READ.items())
