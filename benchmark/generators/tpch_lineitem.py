"""TPC-H `lineitem` from a seed, as snappy parquet in 1M-row row groups, drawn
by dbgen's rules (specification v3, clause 4.2.3; rebuilt from the rules as
the configuration's `assumed` lists them, not from dbgen's random streams,
which are not on this machine):

- an order has 1-7 lines; `o_orderdate` is uniform over 1992-01-01 ..
  1998-08-02; order keys are dbgen's sparse ones (8 used of every 32);
- `l_shipdate` = order date + 1..121 days, `l_receiptdate` = ship date +
  1..30 days; `l_quantity` 1..50; `l_partkey` uniform over SF x 200,000;
  `l_extendedprice` = quantity x the part's retail price, which is
  (90000 + (partkey / 10 mod 20001) + 100 x (partkey mod 1000)) cents;
  `l_discount` 0.00..0.10 and `l_tax` 0.00..0.08;
- `l_returnflag` is R or A (even odds) when the receipt date is on or before
  1995-06-17, else N; `l_linestatus` is O when the ship date is after
  1995-06-17, else F; no nulls; the file is in `l_orderkey, l_linenumber`
  order, so its dates are not ordered.

The four money columns are decimal(12,2) (the specification's "decimal":
-9,999,999,999.99 .. 9,999,999,999.99) and stored as INT64, which is how
Spark's own parquet writer stores a decimal of at most 18 digits
(`store_decimal_as_integer`). The row cut keeps the whole seven years: fewer
orders, not fewer days.

**What `--seed` changes, and why not more** (as `tpcds_star.py`, and for the
same reason). The engine's parquet decode program is specialised on each
column chunk's exact dictionary size, defined-value counts per bit width and
plain-suffix length (`io/parquet_device._col_sig`), so two files that differ
in any of those compile two programs: minutes on the v5e (PERF.md, Open
questions, faults 1 and 2). So the table comes from one fixed draw
(`BASE_SEED`), and `--seed` moves each row's four money columns (quantity,
extended price, discount, tax), whole, to another row of its writer's batch
of `BATCH_ROWS` rows (the writer looks at the dictionary's size and the
page's length once per batch, so every page, every dictionary and the row
where a dictionary gives way to PLAIN stay as they were). Keys, dates and
flags stay where they are. So every sum and every average of query 1 differs
with the seed, and no page, dictionary or filter count does: a run sees a
restart on files of a known layout and on sizes it has seen, never a file of
a new layout."""

from __future__ import annotations

import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
# the writer's batches and the decimal column without a Python loop: the
# star's generator found them, and both tables are written the same way
from tpcds_star import BATCH_ROWS, _blocks, decimal_array  # noqa: E402

SF10_ROWS = 59_986_052           # lineitem at SF10 (specification 4.2.5)
SF10_PARTS = 2_000_000           # SF x 200,000
BASE_SEED = 0
_ORDER_DATES = ("1992-01-01", "1998-08-02")
_CURRENT = np.datetime64("1995-06-17")
_MONEY = ("l_quantity", "l_extendedprice", "l_discount", "l_tax")


def _lineitem(rng, n: int) -> dict:
    lines = rng.integers(1, 8, n)                   # more than n rows' worth
    orders = int(np.searchsorted(np.cumsum(lines), n)) + 1
    ends = np.cumsum(lines[:orders])
    order = np.repeat(np.arange(orders), lines[:orders])[:n]
    linenumber = np.arange(n) - np.concatenate([[0], ends])[order] + 1
    # mk_sparse: the low 3 bits of the order's number stay, the rest move up 2
    key = ((np.arange(orders) >> 3) << 5) + (np.arange(orders) & 7) + 1
    day0, day1 = (np.datetime64(d) for d in _ORDER_DATES)
    odate = day0 + rng.integers(0, (day1 - day0).astype(int) + 1, orders)
    ship = odate[order] + rng.integers(1, 122, n)
    receipt = ship + rng.integers(1, 31, n)
    quantity = rng.integers(1, 51, n)
    partkey = rng.integers(1, SF10_PARTS + 1, n)
    retail = 90_000 + (partkey // 10) % 20_001 + 100 * (partkey % 1_000)
    returned = np.where(rng.integers(0, 2, n) == 0, "R", "A")
    return {
        "l_orderkey": key[order].astype(np.int64),
        "l_linenumber": linenumber.astype(np.int32),
        "l_quantity": (quantity * 100).astype(np.int64),
        "l_extendedprice": (quantity * retail).astype(np.int64),
        "l_discount": rng.integers(0, 11, n).astype(np.int64),
        "l_tax": rng.integers(0, 9, n).astype(np.int64),
        "l_returnflag": np.where(receipt <= _CURRENT, returned, "N"),
        "l_linestatus": np.where(ship > _CURRENT, "O", "F"),
        "l_shipdate": ship.astype("datetime64[D]"),
    }


def lineitem_table(seed: int, n: int, row_group: int):
    """`lineitem` as a pyarrow table of `n` rows: the fixed draw, its money
    columns dealt anew inside each writer's batch by `seed`."""
    import pyarrow as pa
    cols = _lineitem(np.random.default_rng([BASE_SEED, 0]), n)
    rng = np.random.default_rng([int(seed), 0])
    source = np.arange(n)
    for lo, hi in _blocks(n, row_group):
        source[lo:hi] = lo + rng.permutation(hi - lo)
    arrays = {}
    for name, v in cols.items():
        if name in _MONEY:
            arrays[name] = decimal_array(v[source], np.zeros(n, bool), 12, 2)
        else:
            arrays[name] = pa.array(v)
    return pa.table(arrays)


def write(data_dir: str, seed: int, config: dict, tables=None) -> dict:
    """Write `lineitem` under `data_dir`, or reuse what a run with the same
    stamp left there. The encodings are the writer's own choice (dictionary
    first, PLAIN past its 1 MiB limit). Returns {table: {"path", "rows",
    "bytes"}}."""
    import pyarrow.parquet as pq
    name = config["fact_table"]
    n, row_group = config["tables"][name]["rows"], config["row_group_rows"]
    stamp = {"seed": seed, "rows": n, "row_group": row_group,
             "generator": "tpch_lineitem.1"}
    manifest = os.path.join(data_dir, "MANIFEST.json")
    if os.path.exists(manifest):
        with open(manifest) as f:
            have = json.load(f)
        if have.get("stamp") == stamp and all(
                os.path.exists(t["path"]) for t in have["tables"].values()):
            return have["tables"]
    os.makedirs(data_dir, exist_ok=True)
    path = os.path.join(data_dir, f"{name}.parquet")
    table = lineitem_table(seed, n, row_group)
    pq.write_table(table, path,
                   compression=config.get("compression", "snappy"),
                   row_group_size=row_group, write_batch_size=BATCH_ROWS,
                   store_decimal_as_integer=True)
    written = {name: {"path": path, "rows": table.num_rows,
                      "bytes": os.path.getsize(path)}}
    with open(manifest, "w") as f:
        json.dump({"stamp": stamp, "tables": written}, f)
    return written
