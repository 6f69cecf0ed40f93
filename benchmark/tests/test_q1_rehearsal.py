"""`lineitem.q1` rehearsed on the CPU backend at a small size, and the faults
its comparison has to read: a sum altered in its last digit, an average
truncated where it is rounded half up, a dropped group, the float64 control
once the sums pass 2^53; and that a second seed compiles nothing."""
import decimal
import json
import os

import pyarrow as pa
import pytest
import run as R

ROWS = 60_000
CELL = "lineitem.q1"
D = decimal.Decimal


def rehearse(capfd, seed=5):
    code = R.main(["--workload", CELL, "--seed", str(seed), "--seconds", "1",
                   "--trace", "0", "--rehearse-rows", str(ROWS)])
    out, err = capfd.readouterr()
    return code, json.loads(out.strip().splitlines()[-1]), err


def failing(result):
    return sorted(k for k, c in result["checks"].items()
                  if c["value"] > c["limit"])


def paths_of(rows, seed):
    found = R.find_cell(R.ROOT, CELL)
    config = found["config"]
    config["tables"]["lineitem"]["rows"] = rows
    q = R.load_module(R.HERE, "queries", "q1_pricing_summary")
    tables = R.load_module(R.HERE, "generators", config["generator"]).write(
        os.path.join(R.WORK, "data", f"q1-test-{rows}"), seed, config,
        sorted(q.TABLES))
    return q, {k: v["path"] for k, v in tables.items()}


@pytest.fixture(scope="module")
def small():
    q, paths = paths_of(ROWS, 9)
    return q, paths, q.reference(paths)


def test_rehearsal_is_well_formed_and_not_correct_off_the_chip(capfd):
    code, result, err = rehearse(capfd)
    assert code == 1
    assert result["correct"] is False and result["failed"] == 0
    assert result["attempted"] >= 3
    assert result["metrics"] == {}
    assert failing(result) == ["not_on_tpu"]
    assert result["checks"]["sums_off"] == {"value": 0, "limit": 0}
    assert result["checks"]["rows_off"] == {"value": 0, "limit": 0}


def test_an_answer_altered_in_its_last_digit_is_not_correct(capfd,
                                                            monkeypatch):
    """One unit of the last place (1e-6) added to one sum_charge, where the
    answer is produced."""
    from spark_rapids_tpu.frontend import DataFrame
    real = DataFrame.collect

    def altered(self):
        t = real(self)
        i = t.schema.get_field_index("sum_charge")
        col = t.column(i).to_pylist()
        col[2] += D("0.000001")
        return t.set_column(i, t.schema[i], pa.array(col, t.schema[i].type))
    monkeypatch.setattr(DataFrame, "collect", altered)
    _, result, _ = rehearse(capfd)
    assert failing(result) == ["not_on_tpu", "sums_off"]
    assert result["checks"]["sums_off"]["value"] == 1


def _replace(table, name, values):
    i = table.schema.get_field_index(name)
    return table.set_column(i, table.schema[i],
                            pa.array(values, table.schema[i].type))


def test_a_truncated_average_is_read(small):
    """Averages cut at the sixth decimal instead of rounded half up: every
    average whose seventh decimal is 5 or more differs in its last digit."""
    q, paths, want = small
    got, cut = want, 0
    for name, total in (("avg_qty", "sum_qty"),
                        ("avg_price", "sum_base_price")):
        vals = []
        for s, n in zip(want.column(total).to_pylist(),
                        want.column("count_order").to_pylist()):
            vals.append((s / n).quantize(D("0.000001"),
                                         rounding=decimal.ROUND_DOWN))
        cut += sum(a != b for a, b in zip(vals,
                                          want.column(name).to_pylist()))
        got = _replace(got, name, vals)
    assert cut >= 1
    assert q.compare(got, want) == {"rows_off": 0, "sums_off": cut}


def test_a_dropped_group_a_swap_and_a_wrong_type_are_read(small):
    q, paths, want = small
    assert want.num_rows == 4
    assert q.compare(want, want) == {"rows_off": 0, "sums_off": 0}
    # the second group gone: two places hold another group, one row is short
    dropped = want.take([0, 2, 3])
    assert q.compare(dropped, want)["rows_off"] == 3
    assert q.compare(want.take([1, 0, 2, 3]), want)["rows_off"] == 2
    # the same digits under another type than the guaranteed one
    i = want.schema.get_field_index("sum_charge")
    loose = want.set_column(i, "sum_charge", want.column(i).cast(
        pa.float64()))
    assert q.compare(loose, want) == {"rows_off": 0, "sums_off": 4}
    with pytest.raises(TypeError):
        q.compare(want.drop_columns(["avg_disc"]), want)


def test_the_float64_control_fails_once_the_sums_pass_2_53():
    """At the cell's own 2,097,152 rows the largest group's sum_charge is
    ~4e16 units of 1e-6, past 2^53: float64 loses its last digits. At
    60,000 rows every sum is under 2^53 and float64 reads 0, which is why
    the limit-value cases of tier-1 and not this cell hold the line between
    a 64-bit accumulator and decimal(38,6)."""
    q, paths = paths_of(1 << 20, 9)
    want = q.reference(paths)
    low = q.compare(q.control(paths, "float64"), want)
    assert low["rows_off"] == 0 and low["sums_off"] >= 1
    assert q.compare(q.control(paths, "float32"), want)["sums_off"] >= 8
    q, paths = paths_of(ROWS, 9)
    assert q.compare(q.control(paths, "float64"), q.reference(paths)) == {
        "rows_off": 0, "sums_off": 0}


def test_a_second_seed_compiles_nothing_and_moves_every_sum():
    """One decode program per layout of a file's column chunks and operator
    programs per padded batch size: a seed may change neither."""
    import pyarrow.compute as pc
    import pyarrow.parquet as pq
    env = R.prepare(CELL, ROWS)
    q = env["queries"]["q1_pricing_summary"]
    compiles, kept, answers = [], set(), []
    for seed in (1, 2_147_483_659, 77):
        _, paths, clients = R.deal(env, seed)
        session, frames = clients[0]
        rec = R.collect_once(session, env["jax"], "q", next(iter(
            frames.values())))
        assert not rec["faults"]
        compiles.append(rec["compiles"])
        ship = pq.read_table(paths["lineitem"],
                             columns=["l_shipdate"])["l_shipdate"]
        kept.add(pc.sum(pc.less_equal(ship, q.CUTOFF)).as_py())
        answers.append(rec["answer"])
    assert compiles[1:] == [0, 0]
    assert len(kept) == 1
    assert q.compare(answers[0], answers[1])["sums_off"] >= 20
    assert q.compare(answers[1], answers[2])["sums_off"] >= 20
    assert q.projection_least_bytes(paths["lineitem"]) == kept.pop() * 56


def test_lineitem_has_dbgens_shape():
    import pyarrow.parquet as pq
    _, paths = paths_of(300_000, 2_147_483_659)
    md = pq.ParquetFile(paths["lineitem"]).metadata
    physical = {md.row_group(0).column(c).path_in_schema:
                md.row_group(0).column(c).physical_type
                for c in range(md.num_columns)}
    assert physical["l_extendedprice"] == "INT64"   # Spark's layout
    li = pq.read_table(paths["lineitem"]).to_pandas()
    assert li["l_orderkey"].is_monotonic_increasing
    per = li.groupby("l_orderkey")["l_linenumber"]
    assert per.max().iloc[:-1].between(1, 7).all()
    assert (per.max().iloc[:-1] == per.size().iloc[:-1]).all()
    assert set(li["l_returnflag"]) == {"A", "N", "R"}
    assert set(li["l_linestatus"]) == {"F", "O"}
    assert li["l_quantity"].between(1, 50).all()
    assert li["l_discount"].between(0, D("0.10")).all()
    assert li["l_tax"].between(0, D("0.08")).all()
    unit = li["l_extendedprice"] / li["l_quantity"]
    assert unit.between(D("900.00"), D("2099.00")).all()
    assert not li.isna().any().any()
