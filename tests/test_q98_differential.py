"""TPC-DS query 98 (the benchmark's `star.q98`) at a small size on the CPU
backend: the engine's answer through `TpuSession.collect()` with default conf
against the query file's plain reference, every plan node a `Tpu*` exec, the
CPU engine against the same reference, and the faults the cell's comparison
has to read (a ratio altered in its 17th place, ratios truncated where Spark
rounds, a dropped row, two rows swapped, the ratio carried in float64)."""

import decimal
import importlib.util
import os

import pyarrow as pa
import pytest

from spark_rapids_tpu.plugin import TpuSession
from spark_rapids_tpu.utils import metrics as M

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
ROWS = 120_000
D = decimal.Decimal


def _load(kind, name):
    spec = importlib.util.spec_from_file_location(
        f"q98_test_{name}", os.path.join(BENCH, kind, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def q98(tmp_path_factory):
    """(query module, paths, reference) on seeded data of ROWS fact rows."""
    import json
    q = _load("queries", "q98_item_revenue")
    with open(os.path.join(BENCH, "configs",
                           "tpcds-sf10-store-item-revenue.json")) as f:
        config = json.load(f)
    config["tables"]["store_sales"]["rows"] = ROWS
    tables = _load("generators", config["generator"]).write(
        str(tmp_path_factory.mktemp("q98")), 2_147_483_659, config,
        sorted(q.TABLES))
    paths = {k: v["path"] for k, v in tables.items()}
    return q, paths, q.reference(paths)


def _names(node):
    return [node.name] + [n for c in node.children for n in _names(c)]


def _find(node, name):
    if node.name == name:
        return node
    for c in node.children:
        hit = _find(c, name)
        if hit is not None:
            return hit
    return None


def test_the_engine_equals_the_reference_with_every_node_on_the_device(q98):
    q, paths, want = q98
    session = TpuSession({})
    got = q.build(session, paths).collect()
    names = _names(session.last_plan)
    assert all(n.startswith("Tpu") for n in names), names
    assert {"TpuWindowExec", "TpuSortExec", "TpuHashAggregateExec",
            "TpuBroadcastHashJoinExec"} <= set(names)
    assert want.num_rows >= 100
    assert q.compare(got, want) == {"rows_off": 0, "sums_off": 0}
    assert got.schema.field("itemrevenue").type == pa.decimal128(17, 2)
    assert got.schema.field("revenueratio").type == pa.decimal128(38, 17)
    # a group without a price: its revenue and its ratio are null, and the
    # published order puts it first in its item id
    assert want.column("itemrevenue").null_count >= 1
    window = _find(session.last_plan, "TpuWindowExec")
    assert window.metrics.snapshot()[M.NUM_DECIMAL_WINDOW_AGGS] == 1
    assert window.metrics.snapshot()[M.NUM_WINDOW_PARTITIONS] == len(
        set(want.column("i_class").to_pylist()))
    project = _find(session.last_plan, "TpuProjectExec")   # the topmost
    assert project.metrics.snapshot()[M.NUM_DECIMAL_DIVIDES] == 1


def test_the_cpu_engine_equals_the_reference(q98):
    q, paths, want = q98
    got = q.build(TpuSession({}), paths).collect_cpu()
    assert q.compare(got, want) == {"rows_off": 0, "sums_off": 0}
    assert got.schema.field("revenueratio").type == pa.decimal128(38, 17)


def _replace(table, name, values):
    i = table.schema.get_field_index(name)
    return table.set_column(i, table.schema[i],
                            pa.array(values, table.schema[i].type))


def test_a_ratio_altered_in_its_17th_place_is_read(q98):
    q, _, want = q98
    ratios = want.column("revenueratio").to_pylist()
    at = next(i for i, r in enumerate(ratios) if r is not None)
    ratios[at] += D(1).scaleb(-17)
    assert q.compare(_replace(want, "revenueratio", ratios), want) == {
        "rows_off": 0, "sums_off": 1}
    cents = want.column("itemrevenue").to_pylist()
    cents[at] += D("0.01")
    assert q.compare(_replace(want, "itemrevenue", cents), want) == {
        "rows_off": 0, "sums_off": 1}


def test_truncated_ratios_are_read(q98):
    """The 17th place cut where Spark rounds half up: every ratio whose 18th
    digit is 5 or more differs."""
    q, _, want = q98
    revenue = want.column("itemrevenue").to_pylist()
    ratios = want.column("revenueratio").to_pylist()
    total = {}
    for cls, r in zip(want.column("i_class").to_pylist(), revenue):
        if r is not None:
            total[cls] = total.get(cls, 0) + r
    ctx = decimal.Context(prec=60)
    cut = [None if r is None else ctx.divide(r * 100, total[cls]).quantize(
        D(1).scaleb(-17), rounding=decimal.ROUND_DOWN, context=ctx)
        for cls, r in zip(want.column("i_class").to_pylist(), revenue)]
    differ = sum(a != b for a, b in zip(cut, ratios))
    assert differ >= 10
    assert q.compare(_replace(want, "revenueratio", cut), want) == {
        "rows_off": 0, "sums_off": differ}


def test_a_dropped_row_a_swap_and_a_wrong_type_are_read(q98):
    q, _, want = q98
    n = want.num_rows
    assert q.compare(want, want) == {"rows_off": 0, "sums_off": 0}
    dropped = want.take([i for i in range(n) if i != 1])
    assert q.compare(dropped, want)["rows_off"] == n - 1
    swapped = want.take([1, 0] + list(range(2, n)))
    assert q.compare(swapped, want)["rows_off"] == 2
    i = want.schema.get_field_index("revenueratio")
    loose = want.set_column(i, "revenueratio",
                            want.column(i).cast(pa.float64()))
    assert q.compare(loose, want) == {"rows_off": 0, "sums_off": n}
    with pytest.raises(TypeError):
        q.compare(want.drop_columns(["itemrevenue"]), want)


def test_the_float64_control_is_read(q98):
    """A double holds 15-16 of the ratio's up to 19 digits."""
    q, paths, want = q98
    low = q.compare(q.control(paths, "float64"), want)
    assert low["sums_off"] >= 1
    assert q.division_least_bytes(paths) == want.num_rows * 48
