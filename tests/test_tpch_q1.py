"""TPC-H query 1 end to end through `TpuSession` on the benchmark's own
generator and query file (`benchmark/generators/tpch_lineitem.py`,
`benchmark/queries/q1_pricing_summary.py`): a device plan, every one of the
ten columns equal to the query file's plain integer reference, and the
reference's float64 control not equal at a size where its sums pass 2^53."""

import importlib.util
import os

import pyarrow as pa
import pytest

from spark_rapids_tpu.plugin import TpuSession

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")


def _load(kind, name):
    spec = importlib.util.spec_from_file_location(
        f"t1_{kind}_{name}", os.path.join(BENCH, kind, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def q1():
    return _load("queries", "q1_pricing_summary")


def _data(tmp_path_factory, rows, seed):
    gen = _load("generators", "tpch_lineitem")
    config = {"fact_table": "lineitem", "row_group_rows": 1 << 20,
              "tables": {"lineitem": {"rows": rows}}}
    made = gen.write(str(tmp_path_factory.mktemp(f"lineitem{rows}")), seed,
                     config)
    assert made["lineitem"]["rows"] == rows
    return {"lineitem": made["lineitem"]["path"]}


def _plan_names(node):
    return [node.name] + [n for c in node.children for n in _plan_names(c)]


@pytest.mark.parametrize("rows,seed", [(20_000, 7), (20_000, 2147483777),
                                       (4_999, 36)])
def test_q1_equals_the_plain_reference_in_all_ten_columns(
        q1, tmp_path_factory, rows, seed):
    paths = _data(tmp_path_factory, rows, seed)
    session = TpuSession({})
    got = q1.build(session, paths).collect()
    names = _plan_names(session.last_plan)
    assert names == ["TpuSortExec", "TpuHashAggregateExec", "TpuProjectExec",
                     "TpuFilterExec", "TpuFileScanExec(parquet)"]
    want = q1.reference(paths)
    assert got.schema.names == want.schema.names and len(names) == 5
    assert got.schema.equals(want.schema), (got.schema, want.schema)
    assert q1.compare(got, want) == {"rows_off": 0, "sums_off": 0}
    assert got.to_pylist() == want.to_pylist()
    assert got.num_rows == 4
    assert got.schema.field("sum_charge").type == pa.decimal128(38, 6)
    assert got.schema.field("avg_disc").type == pa.decimal128(16, 6)
    # the CPU engine computes the same digits from the same plan
    assert q1.build(session, paths).collect_cpu().to_pylist() \
        == want.to_pylist()


def test_the_seed_moves_every_sum_and_no_count(q1, tmp_path_factory):
    a = q1.reference(_data(tmp_path_factory, 20_000, 1))
    b = q1.reference(_data(tmp_path_factory, 20_000, 2))
    assert a.column("count_order").to_pylist() \
        == b.column("count_order").to_pylist()
    assert q1.compare(a, b)["rows_off"] == 0
    assert q1.compare(a, b)["sums_off"] >= 4 * 6


def test_float64_control_differs_once_the_sums_pass_2_53(q1,
                                                         tmp_path_factory):
    paths = _data(tmp_path_factory, 1 << 20, 7)
    want = q1.reference(paths)
    # sum_charge of the largest group, in units of 1e-6, is past 2^53
    assert max(int(x.scaleb(6)) for x in
               want.column("sum_charge").to_pylist()) > 2 ** 53
    off = q1.compare(q1.control(paths, "float64"), want)
    assert off["rows_off"] == 0 and off["sums_off"] >= 1
    assert q1.compare(q1.control(paths, "float32"), want)["sums_off"] >= 8
