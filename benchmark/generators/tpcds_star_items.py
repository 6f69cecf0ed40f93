"""The TPC-DS store star of `tpcds_star.py` with `item` at the width the
item-revenue reports read it: `store_sales` and `date_dim` are that
generator's tables, drawn and dealt by its own code, byte for byte (so the
fact file has the layout the store-report configuration's has, and the
engine's decode program for it is compiled once for both), and `item` is its
fixed draw with four more columns as dsdgen's `w_item.c` fills them (rebuilt
from its rules as remembered; neither dsdgen nor its `.dst` files are on this
machine):

- `i_item_id` char(16): the business key, `mk_bkey`'s sixteen letters A-P
  (eight A for the high half, then the low half least digit first:
  "AAAAAAAABAAAAAAA" is 1), shared by two consecutive `i_item_sk` (dsdgen's
  revisions of one item; `item` is otherwise no slowly changing dimension
  here);
- `i_item_desc` varchar(200): words of a fixed vocabulary separated by one
  blank, cut at a length uniform in 1-200 (dsdgen's `gen_text`), from a
  random stream of its own, so no column of `tpcds_star.py` moves; two items
  share a description only where the cut is a few bytes long;
- `i_class` char(50): the class's name under its category (the names as
  remembered from `categories.dst`), by the `i_category_id` and `i_class_id`
  the base draw gave the item;
- `i_category` char(50) and the numeric columns are the base draw's.

Strings are written as they are, unpadded, as `i_brand` and `i_category`
are in `tpcds_star.py`.

**What `--seed` changes.** In `store_sales` what `tpcds_star.py` changes: a
row's three pricing columns go to another row of its 1,024-row writer batch.
In `item` only `i_current_price` moves inside the batch. Category, class,
brand and the keys stay where the fixed draw put them, because query 98
filters on `i_category`: a join keeps the same rows under every seed, so no
batch crosses a padded size from seed to seed (PERF.md, faults 1 and 2),
while every item's revenue, every class total and every ratio differ."""

from __future__ import annotations

import importlib.util
import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
# categories.dst: the classes of each category, in i_class_id order
_CLASSES = {
    "Women": ("dresses", "fragrances", "maternity", "swimwear"),
    "Men": ("accessories", "pants", "shirts", "sports-apparel"),
    "Children": ("infants", "newborn", "school-uniforms", "toddlers"),
    "Shoes": ("athletic", "kids", "mens", "womens"),
    "Music": ("classical", "country", "pop", "rock"),
    "Jewelry": ("birdal", "bracelets", "consignment", "costume", "custom",
                "diamonds", "earings", "estate", "gold", "jewelry boxes",
                "loose stones", "mens watch", "pendants", "rings",
                "semi-precious", "womens watch"),
    "Home": ("accent", "bathroom", "bedding", "blinds/shades",
             "curtains/drapes", "decor", "flatware", "furniture",
             "glassware", "kids", "lighting", "mattresses", "paint", "rugs",
             "tables", "wallpaper"),
    "Sports": ("archery", "athletic shoes", "baseball", "basketball",
               "camping", "fishing", "fitness", "football", "golf", "guns",
               "hockey", "optics", "outdoor", "pool", "sailing", "tennis"),
    "Books": ("arts", "business", "computers", "cooking", "entertainments",
              "fiction", "history", "home repair", "mystery", "parenting",
              "reference", "romance", "science", "self-help", "sports",
              "travel"),
    "Electronics": ("audio", "automotive", "cameras", "camcorders",
                    "dvd/vcr players", "disk drives", "karoke", "memory",
                    "monitors", "musical", "personal", "portable",
                    "scanners", "stereo", "televisions", "wireless"),
}
_WORDS = (
    "able", "about", "above", "accounts", "across", "actual", "after",
    "again", "against", "ages", "ago", "agreements", "also", "always",
    "american", "among", "annual", "areas", "around", "available", "away",
    "bad", "basic", "beautiful", "because", "best", "better", "big", "black",
    "books", "both", "british", "building", "businesses", "by", "cases",
    "central", "certain", "changes", "children", "clear", "clearly", "close",
    "common", "companies", "concerned", "conditions", "countries", "courses",
    "current", "days", "decisions", "deep", "details", "different",
    "difficult", "doubts", "early", "eastern", "economic", "effects",
    "else", "even", "events", "ever", "eyes", "facts", "families", "far",
    "feet", "final", "fine", "following", "forces", "foreign", "forms",
    "free", "friends", "full", "games", "general", "good", "great", "groups",
    "hands", "hard", "here", "high", "hours", "however", "human", "ideas",
    "important", "in", "indeed", "industrial", "interests", "international",
    "just", "kinds", "large", "late", "leaders", "legal", "less", "levels",
    "likely", "little", "local", "long", "main", "major", "members",
    "men", "methods", "military", "months", "more", "most", "much", "national",
    "natural", "necessary", "never", "new", "now", "numbers", "of", "often",
    "old", "only", "open", "other", "over", "parents", "particular", "parts",
    "patients", "perhaps", "physical", "plans", "points", "police",
    "political", "poor", "possible", "pounds", "private", "problems",
    "public", "quite", "rather", "real", "really", "recent", "relations",
    "right", "royal", "rules", "schools", "scottish", "services", "short",
    "significant", "simple", "single", "small", "so", "social", "special",
    "still", "strong", "students", "successful", "systems", "then", "there",
    "things", "thus", "times", "today", "together", "too", "total", "true",
    "usually", "various", "very", "white", "whole", "wide", "women", "words",
    "workers", "years", "yet", "young")
_DESC_WIDTH = 200
_ITEM_MOVES = ("i_current_price",)
_ITEM_STREAM = 100   # beside tpcds_star's table streams 0..3


def _star():
    spec = importlib.util.spec_from_file_location(
        "bench_generators_tpcds_star", os.path.join(HERE, "tpcds_star.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


S = _star()


def _item_ids(n: int) -> np.ndarray:
    """`mk_bkey` of (i_item_sk + 1) // 2: two revisions share an id."""
    number = (np.arange(1, n + 1) + 1) // 2
    letters = np.asarray(list("ABCDEFGHIJKLMNOP"))
    low = letters[(number[:, None] >> (4 * np.arange(8))) & 15]
    return np.char.add("AAAAAAAA", low.view("U8").ravel())


def _item_descs(rng, n: int) -> np.ndarray:
    """`gen_text`: random words, one blank between, cut at 1-200 bytes. One
    long random text; an item's description starts at a word of its own."""
    words = np.asarray(_WORDS)
    picks = words[rng.integers(0, len(words), 4 * n)]
    starts = np.concatenate([[0], np.cumsum(np.char.str_len(picks) + 1)])
    text = " ".join(picks.tolist())
    room = int(np.searchsorted(starts, len(text) - _DESC_WIDTH))
    begin = starts[rng.choice(room, n, replace=False)].tolist()
    cut = rng.integers(1, _DESC_WIDTH + 1, n).tolist()
    return np.asarray([text[b:b + k] for b, k in zip(begin, cut)])


def _wide_item(base: dict) -> dict:
    """The base draw's `item` columns plus the four the reports read, in the
    published column order."""
    n = len(base["i_item_sk"])
    names = np.full((len(S._CATEGORIES), 17), "", dtype="U16")
    for c, (category, _) in enumerate(S._CATEGORIES):
        names[c, 1:1 + len(_CLASSES[category])] = _CLASSES[category]
    extra = {
        "i_item_id": _item_ids(n),
        "i_item_desc": _item_descs(
            np.random.default_rng([S.BASE_SEED, _ITEM_STREAM]), n),
        "i_class": names[base["i_category_id"] - 1, base["i_class_id"]],
    }
    order = ("i_item_sk", "i_item_id", "i_item_desc", "i_current_price",
             "i_brand_id", "i_brand", "i_class_id", "i_class",
             "i_category_id", "i_category", "i_manufact_id", "i_manager_id")
    return {c: extra[c] if c in extra else base[c] for c in order}


def item_table(seed: int, rows: dict, row_group: int, zones: dict):
    """`item` as a pyarrow table: the fixed draw at the reports' width, its
    prices dealt anew by `seed` inside the writer's batches."""
    import pyarrow as pa
    cols = _wide_item(S._base_columns(rows, zones, ("item",))["item"])
    cols = S._deal(cols, _ITEM_MOVES,
                   np.random.default_rng([int(seed), _ITEM_STREAM]),
                   row_group)
    arrays = {}
    for c, v in cols.items():
        if c.endswith("_price"):
            arrays[c] = S.decimal_array(v, np.zeros(len(v), bool), 7, 2)
        else:
            arrays[c] = pa.array(v)
    return pa.table(arrays)


def write(data_dir: str, seed: int, config: dict, tables=None) -> dict:
    """Write the config's tables (only `tables`, if given) under `data_dir`,
    or reuse what a run with the same stamp left there. Returns
    {table: {"path", "rows", "bytes"}}."""
    import pyarrow.parquet as pq
    rows = {**S.SF10_ROWS,
            **{k: v["rows"] for k, v in config["tables"].items()}}
    row_group = config["row_group_rows"]
    want = sorted(tables if tables is not None else config["tables"])
    stamp = {"seed": seed, "rows": rows, "row_group": row_group,
             "zones": config["date_zones"], "tables": want,
             "generator": "tpcds_star_items.1"}
    manifest = os.path.join(data_dir, "MANIFEST.json")
    if os.path.exists(manifest):
        with open(manifest) as f:
            have = json.load(f)
        if have.get("stamp") == stamp and all(
                os.path.exists(t["path"]) for t in have["tables"].values()):
            return have["tables"]
    os.makedirs(data_dir, exist_ok=True)
    # the same list of tables as the store-report configuration asks for, so
    # that each table's stream, and with it every byte, is that generator's
    star = S.star_tables(seed, rows, row_group, config["date_zones"], want)
    if "item" in want:
        star["item"] = item_table(seed, rows, row_group,
                                  config["date_zones"])
    written = {}
    for name, tbl in star.items():
        path = os.path.join(data_dir, f"{name}.parquet")
        pq.write_table(tbl, path,
                       compression=config.get("compression", "snappy"),
                       row_group_size=row_group,
                       write_batch_size=S.BATCH_ROWS)
        written[name] = {"path": path, "rows": tbl.num_rows,
                         "bytes": os.path.getsize(path)}
    with open(manifest, "w") as f:
        json.dump({"stamp": stamp, "tables": written}, f)
    return written
