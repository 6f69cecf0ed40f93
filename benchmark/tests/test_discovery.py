"""A new configuration, query, traffic mix, cell and per-layer metric arrive as
new files plus entries in `BENCHMARK.json`: no file that is there is edited."""
import json
import os
import shutil

import run as R


def test_new_files_are_found_by_name(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(R.HERE, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (root / "benchmark").rglob("*")
              if p.is_file()}
    here = root / "benchmark"
    config = R.load_json(R.HERE, "configs", "tpcds-sf10-store-report.json")
    config.update(name="tpch-sf10-lineitem", chips=4, generator="lineitem",
                  session_conf={"spark.rapids.shuffle.mode": "ICI"})
    (here / "configs" / "tpch-sf10-lineitem.json").write_text(
        json.dumps(config))
    (here / "generators" / "lineitem.py").write_text(
        "def write(data_dir, seed, config, tables=None):\n    return {}\n")
    (here / "queries" / "q6_forecast.py").write_text(
        "TABLES = ('lineitem',)\nLIMITS = {'sums_off': 0}\n")
    (here / "traffic" / "q6_closed1.json").write_text(json.dumps(
        {"clients": 2, "queries": ["q6_forecast"]}))
    # a reader of its own over the trace's raw events: no edit to the
    # reduction or the harness
    (here / "layer_metrics" / "exposed_collective_s.py").write_text(
        "def read(ctx):\n"
        "    if not ctx.get('trace'):\n        return None\n"
        "    ops = ctx['trace']['events']['device_ops']\n"
        "    return sum(d for evs in ops.values() for n, _, d in evs\n"
        "               if 'all-to-all' in n) / 1e9 / len(ops)\n")
    bench = R.load_json(R.ROOT, "BENCHMARK.json")
    bench["configs"].append({
        "name": "tpch-sf10-lineitem", "source": "TPC-H spec v3",
        "file": "benchmark/configs/tpch-sf10-lineitem.json", "reduced": [],
        "why": "test"})
    bench["workloads"].append({
        "name": "lineitem.q6", "config": "tpch-sf10-lineitem",
        "traffic": "q6_closed1", "chips": 4, "why": "test"})
    bench["per_layer"].append({
        "name": "exposed_collective_s", "unit": "s", "better": "lower",
        "source": "device_trace", "layer": "exchange / mesh",
        "moves": "query_s", "workloads": ["lineitem.q6"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = R.find_cell(str(root), "lineitem.q6")
    assert cell["chips"] == 4
    assert cell["config"]["session_conf"] == {
        "spark.rapids.shuffle.mode": "ICI"}
    assert cell["traffic"]["queries"] == ["q6_forecast"]
    names = [m["name"] for m in cell["per_layer"]]
    assert "exposed_collective_s" in names
    # the metrics that list their cells do not follow into a cell they
    # do not list
    assert "scan_read_s" not in names
    assert [m["name"] for m in cell["end_to_end"]] == [
        m["name"] for m in bench["end_to_end"]]
    gen = R.load_module(cell["here"], "generators",
                        cell["config"]["generator"])
    assert gen.write("", 0, {}) == {}
    query = R.load_module(cell["here"], "queries", "q6_forecast")
    assert query.TABLES == ("lineitem",)
    reader = R.load_module(cell["here"], "layer_metrics",
                           "exposed_collective_s")
    trace = {"events": {"device_ops": {
        "/device:TPU:0": [("%all-to-all.1", 0, 2_000_000_000),
                          ("%fusion.2", 0, 5)],
        "/device:TPU:1": [("%all-to-all.1", 0, 1_000_000_000)]}}}
    assert reader.read({"trace": trace}) == 1.5
    assert reader.read({"trace": None}) is None
    # the old cells are as they were, and no old file changed
    old = R.find_cell(str(root), "star.q3")
    assert "exposed_collective_s" not in [m["name"] for m in old["per_layer"]]
    for p, content in before.items():
        assert p.read_bytes() == content


def test_every_named_file_exists():
    bench = R.load_json(R.ROOT, "BENCHMARK.json")
    for cell in bench["workloads"]:
        found = R.find_cell(R.ROOT, cell["name"])
        for q in found["traffic"]["queries"]:
            assert os.path.exists(os.path.join(R.HERE, "queries", q + ".py"))
        for m in found["per_layer"]:
            assert os.path.exists(
                os.path.join(R.HERE, "layer_metrics", m["name"] + ".py"))
