"""TPC-DS store star from a seed: `store_sales`, `date_dim`, `item`, `store` as
snappy parquet in 1M-row row groups, drawn the way dsdgen draws them
(`w_store_sales.c`, `w_item.c`, `pricing.c`; rebuilt from their rules, not from
dsdgen's random streams or its `.dst` files, which are not on this machine):

- `store_sales` is written in date order, ticket by ticket: a ticket has 8-16
  line items (uniform) that share its date, customer and store; its items are
  consecutive entries of one permutation of `item` from a random start, so
  they are distinct inside a ticket and uniform over the table; tickets per
  day follow the calendar's three sales zones (the config's `date_zones`);
  9% of rows get a random null bitmap over every column but `ss_item_sk` and
  `ss_ticket_number` (4.5% nulls per nullable column, foreign keys too);
  quantity 1-100, wholesale 1.00-100.00, markup 0-200%, discount 0-100%, and
  `ss_ext_sales_price` = `ss_sales_price` x quantity to the cent.
- `item`: `i_manufact_id` 1-1000 and `i_manager_id` 1-100 uniform; category,
  class and brand as dsdgen's hierarchy builds them (`i_brand_id` = (category
  x 1000 + class) x 1000 + n; `i_brand` = the syllables of category x 10 +
  class, least digit first, then " #n": "exportiunivamalg #2", 11-22 bytes).
- `date_dim` is the calendar from 1900-01-02, one row per day.

The row cut keeps the whole five years: fewer tickets per day, not fewer days.

**What `--seed` changes, and why not more.** The engine's parquet decode
program is specialised on each column chunk's exact dictionary size,
defined-value counts per bit width and plain-suffix length
(`io/parquet_device._col_sig`), so two files that differ in any of those
compile two programs: minutes on the v5e (PERF.md, Open questions, first).
So the tables come from one fixed draw (`BASE_SEED`), and `--seed` moves rows'
values inside blocks of `BATCH_ROWS` rows, the writer's batch (it looks at the
dictionary's size and the page's length once per batch, so every page, every
dictionary and the row where a dictionary gives way to PLAIN stay as they
were): in `item`, an item's price, brand, class and category go to another
`i_item_sk` of the block, whole; in `store_sales`, a row's three pricing
columns go to another row of the block, whole, among rows with no null in
them. So every group's brand and every sum differ with the seed; the keys, the
calendar, the nulls' places and the ids that the templates filter on
(`i_manufact_id`, `i_manager_id`) do not: a filter keeps the same number of
rows under every seed. That too is for the engine's sake: it pads a batch to
a power of two and compiles per padded size, and when the manufacturer moved
with the seed, query 3's second join gave 252 rows on one seed and 282 on the
next, two sizes, and a run's set-up compiled a second set of programs (+26 s;
my chip run, PR 26). A run therefore sees a restart on files of a known
layout and on sizes it has seen, never a file of a new layout."""

from __future__ import annotations

import json
import os

import numpy as np

# TPC-DS SF10 row counts (specification table 3-2)
SF10_ROWS = {"store_sales": 28_800_991, "date_dim": 73_049,
             "item": 102_000, "store": 102, "customer": 500_000}
BASE_SEED = 0
PAGE_ROWS = 20_000    # the writer's data page, counted from the row group
BATCH_ROWS = 1_024    # the writer's batch, counted from the page
# d_date_sk is a Julian day number: 2415022 = 1900-01-02, and store_sales
# spans 1998-01-02 .. 2003-01-02 (specification 3.4, dsdgen's data range)
_DATE_SK0 = 2_415_022
_SALES_SK = (2_450_816, 2_452_643)
_NULL_ROWS, _NULL_BIT = 0.09, 0.5    # tdefs.h: store_sales nNullPct 900
# categories.dst: the categories with their number of classes
_CATEGORIES = (("Women", 4), ("Men", 4), ("Children", 4), ("Shoes", 4),
               ("Music", 4), ("Jewelry", 16), ("Home", 16), ("Sports", 16),
               ("Books", 16), ("Electronics", 16))
_SYLLABLES = ("univ", "amalg", "importo", "exporti", "edu pack", "scholar",
              "corp", "brand", "nameless", "maxi")
_BRANDS = 714         # distinct i_brand at SF10
_STATES = ("TN", "SD", "AL", "GA", "OH", "MI", "TX", "IL", "NE")
_PRICING = ("ss_quantity", "ss_sales_price", "ss_ext_sales_price")
_ITEM_STAYS = ("i_item_sk", "i_manufact_id", "i_manager_id")


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


def _word(number: int) -> str:
    """dsdgen's `mk_word`: the number's decimal digits as syllables, least
    digit first."""
    out = ""
    while number > 0:
        out += _SYLLABLES[number % 10]
        number //= 10
    return out


def _date_dim(n: int) -> dict:
    days = np.datetime64("1900-01-02") + np.arange(n)
    months = days.astype("datetime64[M]")
    moy = (months.astype(np.int64) % 12 + 1).astype(np.int32)
    return {
        "d_date_sk": _DATE_SK0 + np.arange(n, dtype=np.int64),
        "d_date": days.astype("datetime64[D]"),
        "d_year": (months.astype("datetime64[Y]").astype(np.int64)
                   + 1970).astype(np.int32),
        "d_moy": moy,
        "d_dom": ((days - months).astype(np.int64) + 1).astype(np.int32),
        "d_qoy": ((moy - 1) // 3 + 1).astype(np.int32),
        # 1900-01-02 was a Tuesday; TPC-DS counts d_dow from Sunday = 0
        "d_dow": ((np.arange(n) + 2) % 7).astype(np.int32),
    }


def _item(rng, n: int) -> dict:
    cat = rng.integers(0, len(_CATEGORIES), n)
    classes = np.asarray([c for _, c in _CATEGORIES])[cat]
    cls = rng.integers(0, 1 << 30, n) % classes + 1
    # 100 classes; 7 brands in each and an eighth in the first 14: 714
    first = np.concatenate([[0], np.cumsum([c for _, c in _CATEGORIES])])
    per_class = 7 + (first[cat] + cls - 1 < _BRANDS - 700)
    number = rng.integers(0, 1 << 30, n) % per_class + 1
    words = np.asarray([[_word((c + 1) * 10 + k) for k in range(17)]
                        for c in range(len(_CATEGORIES))])
    brand = np.char.add(np.char.add(words[cat, cls], " #"),
                        number.astype(str))
    return {
        "i_item_sk": np.arange(1, n + 1, dtype=np.int64),
        "i_current_price": rng.integers(9, 10_000, n, dtype=np.int64),
        "i_brand_id": (((cat + 1) * 1000 + cls) * 1000
                       + number).astype(np.int32),
        "i_brand": brand,
        "i_class_id": cls.astype(np.int32),
        "i_category_id": (cat + 1).astype(np.int32),
        "i_category": np.asarray([c for c, _ in _CATEGORIES])[cat],
        "i_manufact_id": rng.integers(1, 1001, n, dtype=np.int32),
        "i_manager_id": rng.integers(1, 101, n, dtype=np.int32),
    }


def _store_sales(rng, rows: dict, zones: dict) -> dict:
    n, n_items = rows["store_sales"], rows["item"]
    # tickets of 8-16 lines until n rows are out; the last one is cut short
    lines = rng.integers(8, 17, n // 8 + 1)
    tickets = int(np.searchsorted(np.cumsum(lines), n)) + 1
    ticket = np.repeat(np.arange(tickets), lines[:tickets])[:n]
    line = np.arange(n) - np.concatenate(
        [[0], np.cumsum(lines[:tickets])])[ticket]
    # tickets per day in proportion to the day's zone, in date order
    day_sk = np.arange(*_SALES_SK, dtype=np.int64)
    month = ((np.datetime64("1900-01-02") + (day_sk - _DATE_SK0))
             .astype("datetime64[M]").astype(np.int64) % 12 + 1)
    weight = np.zeros(len(day_sk))
    for span, w in zones.items():
        lo, hi = (int(x) for x in span.split("-"))
        weight[(month >= lo) & (month <= hi)] = w
    ends = np.cumsum(weight) / weight.sum() * tickets
    t_day = day_sk[np.searchsorted(ends, np.arange(tickets) + 0.5)]
    perm = rng.permutation(n_items) + 1
    start = rng.integers(0, n_items, tickets)
    t_cust = rng.integers(1, rows["customer"] + 1, tickets, dtype=np.int64)
    t_store = rng.integers(1, rows["store"] + 1, tickets, dtype=np.int64)
    # pricing.c: list = wholesale x (1 + markup), sales = list x (1 - discount)
    quantity = rng.integers(1, 101, n, dtype=np.int32)
    wholesale = rng.integers(100, 10_001, n)
    list_price = wholesale * (100 + rng.integers(0, 201, n)) // 100
    sales = list_price * (100 - rng.integers(0, 101, n)) // 100
    cols = {
        "ss_sold_date_sk": t_day[ticket],
        "ss_item_sk": perm[(start[ticket] + line) % n_items].astype(np.int64),
        "ss_customer_sk": t_cust[ticket],
        "ss_store_sk": t_store[ticket],
        "ss_ticket_number": ticket.astype(np.int64) + 1,
        "ss_quantity": quantity,
        "ss_sales_price": sales.astype(np.int64),
        "ss_ext_sales_price": (sales * quantity).astype(np.int64),
    }
    treated = rng.random(n) < _NULL_ROWS
    for name in list(cols):
        if name not in ("ss_item_sk", "ss_ticket_number"):
            cols[name + "_null"] = treated & (rng.random(n) < _NULL_BIT)
    return cols


def _base_columns(rows: dict, zones: dict, tables) -> dict:
    """The fixed draw: numpy columns per table (`BASE_SEED`); a null mask of
    column `c` is the entry `c + "_null"`."""
    out = {}
    makers = {
        "date_dim": lambda rng: _date_dim(rows["date_dim"]),
        "item": lambda rng: _item(rng, rows["item"]),
        "store": lambda rng: {
            "s_store_sk": np.arange(1, rows["store"] + 1, dtype=np.int64),
            "s_state": np.asarray(_STATES)[
                rng.integers(0, len(_STATES), rows["store"])]},
        "store_sales": lambda rng: _store_sales(rng, rows, zones),
    }
    # one stream per table, so leaving a table out changes no other
    for i, (name, make) in enumerate(makers.items()):
        if tables is None or name in tables:
            out[name] = make(np.random.default_rng([BASE_SEED, i]))
    return out


def _blocks(n: int, row_group: int):
    """(lo, hi) of every writer's batch: `BATCH_ROWS` from each page's start,
    pages `PAGE_ROWS` from each row group's start."""
    for rg in range(0, n, row_group):
        for page in range(rg, min(rg + row_group, n), PAGE_ROWS):
            stop = min(page + PAGE_ROWS, rg + row_group, n)
            for lo in range(page, stop, BATCH_ROWS):
                yield lo, min(lo + BATCH_ROWS, stop)


def _deal(cols: dict, moved: tuple, rng, row_group: int) -> dict:
    """Move the `moved` columns' values, whole rows of them, to other rows of
    the same writer's batch; rows with a null in one of them keep theirs."""
    n = len(cols[moved[0]])
    fixed = np.zeros(n, bool)
    for name in moved:
        fixed |= cols.get(name + "_null", False)
    source = np.arange(n)
    for lo, hi in _blocks(n, row_group):
        at = lo + np.flatnonzero(~fixed[lo:hi])
        source[at] = rng.permutation(at)
    return {**cols, **{name: cols[name][source] for name in moved}}


def star_tables(seed: int, rows: dict, row_group: int, zones: dict,
                tables=None) -> dict:
    """The star as pyarrow tables, `rows` per table; only `tables` if given."""
    import pyarrow as pa
    out = {}
    base = _base_columns(rows, zones, tables)
    for i, (name, cols) in enumerate(base.items()):
        rng = np.random.default_rng([int(seed), i])
        if name == "item":
            cols = _deal(cols, tuple(c for c in cols if c not in _ITEM_STAYS),
                         rng, row_group)
        elif name == "store_sales":
            cols = _deal(cols, _PRICING, rng, row_group)
        nulls = {c[:-5]: cols.pop(c) for c in list(cols)
                 if c.endswith("_null")}
        arrays = {}
        for c, v in cols.items():
            null = nulls.get(c)
            if c.endswith("_price"):
                arrays[c] = decimal_array(
                    v, np.zeros(len(v), bool) if null is None else null, 7, 2)
            elif v.dtype.kind == "U":
                arrays[c] = pa.array(v)
            else:
                arrays[c] = pa.array(v, mask=null)
        out[name] = pa.table(arrays)
    return out


def write(data_dir: str, seed: int, config: dict, tables=None) -> dict:
    """Write the config's tables (only `tables`, if given) under `data_dir`,
    or reuse what a run with the same stamp left there. The encodings are
    the writer's own choice (dictionary first, PLAIN past its 1 MiB limit).
    Returns {table: {"path", "rows", "bytes"}}."""
    import pyarrow.parquet as pq
    rows = {**SF10_ROWS,
            **{k: v["rows"] for k, v in config["tables"].items()}}
    row_group = config["row_group_rows"]
    want = sorted(tables if tables is not None else config["tables"])
    stamp = {"seed": seed, "rows": rows, "row_group": row_group,
             "zones": config["date_zones"], "tables": want,
             "generator": "tpcds_star.4"}
    manifest = os.path.join(data_dir, "MANIFEST.json")
    if os.path.exists(manifest):
        with open(manifest) as f:
            have = json.load(f)
        if have.get("stamp") == stamp and all(
                os.path.exists(t["path"]) for t in have["tables"].values()):
            return have["tables"]
    os.makedirs(data_dir, exist_ok=True)
    written = {}
    star = star_tables(seed, rows, row_group, config["date_zones"], want)
    for name, tbl in star.items():
        path = os.path.join(data_dir, f"{name}.parquet")
        pq.write_table(tbl, path,
                       compression=config.get("compression", "snappy"),
                       row_group_size=row_group, write_batch_size=BATCH_ROWS)
        written[name] = {"path": path, "rows": tbl.num_rows,
                         "bytes": os.path.getsize(path)}
    with open(manifest, "w") as f:
        json.dump({"stamp": stamp, "tables": written}, f)
    return written
