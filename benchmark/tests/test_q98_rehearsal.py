"""`star.q98` rehearsed on the CPU backend at a small size through the
harness itself, and the faults its comparison has to read where the answer is
produced: a ratio altered in its 17th place, a ratio returned as a double; and
that a second seed compiles nothing, moves every revenue and ratio, and keeps
the row counts behind each join; and that the fact file is the store-report
configuration's, byte for byte."""
import decimal
import json
import os

import pyarrow as pa
import pytest
import run as R

ROWS = 60_000
CELL = "star.q98"
D = decimal.Decimal


def rehearse(capfd, seed=5):
    code = R.main(["--workload", CELL, "--seed", str(seed), "--seconds", "1",
                   "--trace", "0", "--rehearse-rows", str(ROWS)])
    out, err = capfd.readouterr()
    return code, json.loads(out.strip().splitlines()[-1]), err


def failing(result):
    return sorted(k for k, c in result["checks"].items()
                  if c["value"] > c["limit"])


def test_rehearsal_is_well_formed_and_not_correct_off_the_chip(capfd):
    code, result, err = rehearse(capfd)
    assert code == 1
    assert result["correct"] is False and result["failed"] == 0
    assert result["attempted"] >= 3
    assert result["metrics"] == {}
    assert failing(result) == ["not_on_tpu"]
    assert result["checks"]["sums_off"] == {"value": 0, "limit": 0}
    assert result["checks"]["rows_off"] == {"value": 0, "limit": 0}


def test_the_cell_reports_its_four_readers_and_the_end_to_end_metrics():
    cell = R.find_cell(R.ROOT, CELL)
    assert cell["chips"] == 1 and cell["config"]["session_conf"] == {}
    assert [m["name"] for m in cell["per_layer"]] == [
        "window_device_s", "sort_device_s", "join_payload_device_s",
        "decimal_div_hbm_roofline"]
    assert [m["name"] for m in cell["end_to_end"]] == [
        "query_s", "first_query_s", "setup_s"]
    # without a trace (or on an engine that names no programs) a reader
    # reports nothing and does not raise
    ctx = {"trace": None, "peaks": None, "cell": {
        "name": CELL, "chips": 1, "config": cell["config"],
        "traffic": cell["traffic"]}}
    for m in cell["per_layer"]:
        assert R.load_module(R.HERE, "layer_metrics",
                             m["name"]).read(ctx) is None


def test_an_answer_altered_in_its_17th_place_is_not_correct(capfd,
                                                            monkeypatch):
    from spark_rapids_tpu.frontend import DataFrame
    real = DataFrame.collect

    def altered(self):
        t = real(self)
        i = t.schema.get_field_index("revenueratio")
        col = t.column(i).to_pylist()
        at = next(k for k, v in enumerate(col) if v is not None)
        col[at] += D(1).scaleb(-17)
        return t.set_column(i, t.schema[i], pa.array(col, t.schema[i].type))
    monkeypatch.setattr(DataFrame, "collect", altered)
    _, result, _ = rehearse(capfd)
    assert failing(result) == ["not_on_tpu", "sums_off"]
    assert result["checks"]["sums_off"]["value"] == 1


def test_a_ratio_returned_as_a_double_is_not_correct(capfd, monkeypatch):
    """What `Divide` returned for every type before this configuration."""
    from spark_rapids_tpu.frontend import DataFrame
    real = DataFrame.collect

    def loose(self):
        t = real(self)
        i = t.schema.get_field_index("revenueratio")
        return t.set_column(i, "revenueratio", t.column(i).cast(pa.float64()))
    monkeypatch.setattr(DataFrame, "collect", loose)
    _, result, _ = rehearse(capfd)
    assert failing(result) == ["not_on_tpu", "sums_off"]
    assert result["checks"]["sums_off"]["value"] >= 50


def test_a_second_seed_compiles_nothing_and_moves_every_ratio():
    """One decode program per layout of a file's column chunks and operator
    programs per padded batch size: a seed may change neither."""
    import pyarrow.parquet as pq
    env = R.prepare(CELL, ROWS)
    q = env["queries"]["q98_item_revenue"]
    compiles, answers, items = [], [], []
    for seed in (1, 2_147_483_659, 77):
        _, paths, clients = R.deal(env, seed)
        session, frames = clients[0]
        rec = R.collect_once(session, env["jax"], "q", next(iter(
            frames.values())))
        assert not rec["faults"]
        compiles.append(rec["compiles"])
        answers.append(rec["answer"])
        items.append(pq.read_table(paths["item"]))
    assert compiles[1:] == [0, 0]
    assert len({a.num_rows for a in answers}) == 1
    n = answers[0].num_rows
    for a, b in ((answers[0], answers[1]), (answers[1], answers[2])):
        # the same items in the same places (an item's price is a key of
        # the comparison and moves with the seed, so `compare` reads rows)
        assert a["i_item_desc"].equals(b["i_item_desc"])
        assert q.compare(a, b)["rows_off"] >= 0.9 * n
        for name in ("itemrevenue", "revenueratio"):
            moved = sum(x != y for x, y in zip(a[name].to_pylist(),
                                               b[name].to_pylist()))
            assert moved >= 0.9 * n, name
    # what a template filters or joins on stays; the prices move
    for name in ("i_item_sk", "i_item_id", "i_item_desc", "i_class",
                 "i_category", "i_brand", "i_manufact_id"):
        assert items[0][name].equals(items[1][name]), name
    assert not items[0]["i_current_price"].equals(items[1]["i_current_price"])


def test_the_fact_file_is_the_store_report_configurations():
    """`star.q98` and `star.q3` must share one decode program: the same
    seed gives the same `store_sales` and `date_dim`, byte for byte."""
    import pyarrow.parquet as pq
    found = {}
    for cell in (CELL, "star.q3"):
        c = R.find_cell(R.ROOT, cell)
        config = c["config"]
        config["tables"]["store_sales"]["rows"] = ROWS
        tables = R.load_module(R.HERE, "generators",
                               config["generator"]).write(
            os.path.join(R.WORK, "data", f"same-file-{cell}"), 31, config,
            ["date_dim", "item", "store_sales"])
        found[cell] = {k: pq.read_table(v["path"]) for k, v in tables.items()}
    for name in ("store_sales", "date_dim"):
        assert found[CELL][name].equals(found["star.q3"][name]), name
        assert found[CELL][name].schema.equals(found["star.q3"][name].schema)
    wide, narrow = found[CELL]["item"], found["star.q3"]["item"]
    assert wide.num_columns == 12 and narrow.num_columns == 9
    assert wide["i_item_sk"].equals(narrow["i_item_sk"])


def test_item_has_the_reports_columns():
    import pyarrow.compute as pc
    import pyarrow.parquet as pq
    c = R.find_cell(R.ROOT, CELL)["config"]
    c["tables"]["store_sales"]["rows"] = ROWS
    tables = R.load_module(R.HERE, "generators", c["generator"]).write(
        os.path.join(R.WORK, "data", "q98-item-shape"), 3, c, ["item"])
    it = pq.read_table(tables["item"]["path"])
    assert it.num_rows == 102_000
    assert it.schema.field("i_current_price").type == pa.decimal128(7, 2)
    assert pc.max(pc.utf8_length(it["i_item_desc"])).as_py() == 200
    assert pc.min(pc.utf8_length(it["i_item_desc"])).as_py() == 1
    assert pc.count_distinct(it["i_item_desc"]).as_py() > 99_000
    ids = it["i_item_id"].to_pylist()
    assert ids[0] == ids[1] == "AAAAAAAABAAAAAAA" and ids[2] == ids[3]
    assert len(set(ids)) == 51_000 and {len(i) for i in ids} == {16}
    kept = it.filter(pc.is_in(it["i_category"], value_set=pa.array(
        ["Sports", "Books", "Home"])))
    assert 0.28 < kept.num_rows / it.num_rows < 0.32
    assert pc.count_distinct(kept["i_class"]).as_py() == 48
    assert all(it[c].null_count == 0 for c in it.schema.names)
