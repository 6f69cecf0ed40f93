"""TPC-H `lineitem` from a seed as one ORC file: `tpch_lineitem.py`'s table,
the same draws under the same seeds (that file, loaded from beside this one,
makes the rows; a test holds that both generators hand the same rows to their
writers), written the way a Hive warehouse holds it and Spark 3's
`df.write.orc` lays it out: ORC file version 0.12 (RLEv2 integers, decimals as
a zigzag base-128 varint DATA stream and an RLEv2 scale stream, strings
dictionary-encoded under `orc.dictionary.key.threshold` 0.8), snappy in
256 KiB blocks, 64 MiB stripes, a row index every 10,000 rows. The writer is
pyarrow's (the C++ ORC library), 1,024 rows a batch; the configuration's
`assumed` says what that stands for.

**What `--seed` changes:** what it changes in the parquet file. A row's four
money columns move, whole, to another row of a block of at most 1,024 rows
(`tpch_lineitem.lineitem_table`; the blocks are the parquet writer's, so both
files hold the same rows under one seed). A varint stream is then a
permutation of the same values: every stream's length, every run count of the
date and flag columns and the rows the filter keeps are the same under every
seed, and every sum and average differs."""

from __future__ import annotations

import importlib.util
import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def _beside(name: str):
    spec = importlib.util.spec_from_file_location(
        f"bench_generators_{name}", os.path.join(_HERE, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


PARQUET = _beside("tpch_lineitem")
BATCH_ROWS = PARQUET.BATCH_ROWS


def write(data_dir: str, seed: int, config: dict, tables=None) -> dict:
    """Write `lineitem` under `data_dir`, or reuse what a run with the same
    stamp left there. Returns {table: {"path", "rows", "bytes"}}."""
    from pyarrow import orc
    name = config["fact_table"]
    n, blocks = config["tables"][name]["rows"], config["draw_block_rows"]
    w = config["orc_writer"]
    stamp = {"seed": seed, "rows": n, "blocks": blocks, "writer": w,
             "generator": "tpch_lineitem_orc.1"}
    manifest = os.path.join(data_dir, "MANIFEST.json")
    if os.path.exists(manifest):
        with open(manifest) as f:
            have = json.load(f)
        if have.get("stamp") == stamp and all(
                os.path.exists(t["path"]) for t in have["tables"].values()):
            return have["tables"]
    os.makedirs(data_dir, exist_ok=True)
    path = os.path.join(data_dir, f"{name}.orc")
    table = PARQUET.lineitem_table(seed, n, blocks)
    orc.write_table(
        table, path, file_version=w["file_version"],
        compression=w["compression"], stripe_size=w["stripe_size"],
        compression_block_size=w["compression_block_size"],
        row_index_stride=w["row_index_stride"],
        dictionary_key_size_threshold=w["dictionary_key_size_threshold"],
        batch_size=BATCH_ROWS)
    written = {name: {"path": path, "rows": table.num_rows,
                      "bytes": os.path.getsize(path)}}
    with open(manifest, "w") as f:
        json.dump({"stamp": stamp, "tables": written}, f)
    return written
