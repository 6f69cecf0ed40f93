"""`lineitem.q1_orc` rehearsed on the CPU backend at a small size, and the
faults its comparison has to read: a sum altered in its last digit, a dropped
group, two groups swapped, the float64 control once the sums pass 2^53, a
unit the host's reader decoded; that the ORC file holds the parquet cell's
rows under one seed; that a second seed compiles nothing; and the cell's four
readers on hand-built inputs."""
import decimal
import json
import os

import pyarrow as pa
import pytest
import run as R

ROWS = 60_000
CELL = "lineitem.q1_orc"
QUERY = "q1_pricing_summary_orc"
D = decimal.Decimal


def rehearse(capfd, seed=5):
    code = R.main(["--workload", CELL, "--seed", str(seed), "--seconds", "1",
                   "--trace", "0", "--rehearse-rows", str(ROWS)])
    out, err = capfd.readouterr()
    return code, json.loads(out.strip().splitlines()[-1]), err


def failing(result):
    return sorted(k for k, c in result["checks"].items()
                  if c["value"] > c["limit"])


def paths_of(rows, seed, cell=CELL, query=QUERY):
    config = R.find_cell(R.ROOT, cell)["config"]
    config["tables"]["lineitem"]["rows"] = rows
    q = R.load_module(R.HERE, "queries", query)
    tables = R.load_module(R.HERE, "generators", config["generator"]).write(
        os.path.join(R.WORK, "data", f"{query}-test-{rows}"), seed, config,
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
    for k in ("sums_off", "rows_off", "host_decoded", "scans_off_device"):
        assert result["checks"][k] == {"value": 0, "limit": 0}, k


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


def test_a_unit_decoded_by_the_host_is_not_correct(capfd, monkeypatch):
    """The default conf falls a stripe back to pyarrow when the device
    declines it; the answer is right and the run is not."""
    from spark_rapids_tpu.io import orc_device as O

    def declines(*a, **kw):
        raise O.DeviceDecodeUnsupported("declined by the test")
    monkeypatch.setattr(O, "decode_stripe", declines)
    _, result, _ = rehearse(capfd)
    assert failing(result) == ["host_decoded", "not_on_tpu"]
    assert result["checks"]["sums_off"]["value"] == 0


def test_an_engine_without_the_counter_is_refused_at_once(small, monkeypatch):
    """What the commit before PR 37 meets: a plain error where the query
    builds its scan, before anything compiles."""
    from spark_rapids_tpu.plugin import TpuSession
    q, paths, _ = small
    monkeypatch.setattr(q, "_COUNTER", "a_counter_this_engine_lacks")
    with pytest.raises(RuntimeError, match="a_counter_this_engine_lacks"):
        q.build(TpuSession({}), paths)


def test_a_dropped_group_a_swap_and_a_wrong_type_are_read(small):
    q, paths, want = small
    assert want.num_rows == 4

    def read(got):
        r = q.compare(got, want)
        return r["rows_off"], r["sums_off"]
    assert read(want) == (0, 0)
    assert read(want.take([0, 2, 3]))[0] == 3
    assert read(want.take([1, 0, 2, 3]))[0] == 2
    i = want.schema.get_field_index("sum_charge")
    loose = want.set_column(i, "sum_charge", want.column(i).cast(
        pa.float64()))
    assert read(loose) == (0, 4)
    with pytest.raises(TypeError):
        q.compare(want.drop_columns(["avg_disc"]), want)


def test_the_float64_control_fails_once_the_sums_pass_2_53():
    q, paths = paths_of(1 << 20, 9)
    want = q.reference(paths)
    low = q.compare(q.control(paths, "float64"), want)
    assert low["rows_off"] == 0 and low["sums_off"] >= 1
    assert q.compare(q.control(paths, "float32"), want)["sums_off"] >= 8


@pytest.mark.parametrize("seed", [9, 2_147_483_659])
def test_same_draws_as_parquet(seed):
    """Row for row and column for column what `tpch-sf10-lineitem`'s
    generator writes under the same seed, read back by pyarrow's two
    readers: a generator that drew anew, or dealt the money columns inside
    other blocks, fails here."""
    import pyarrow.parquet as pq
    from pyarrow import orc
    _, ours = paths_of(ROWS, seed)
    _, theirs = paths_of(ROWS, seed, "lineitem.q1", "q1_pricing_summary")
    a = orc.read_table(ours["lineitem"])
    b = pq.read_table(theirs["lineitem"])
    assert a.schema.names == b.schema.names and a.num_rows == ROWS
    for name in a.schema.names:
        assert a.column(name).to_pylist() == b.column(name).to_pylist(), name
    other = orc.read_table(paths_of(ROWS, seed + 1)[1]["lineitem"])
    assert other.column("l_tax").to_pylist() != a.column("l_tax").to_pylist()
    assert other.column("l_shipdate").equals(a.column("l_shipdate"))


def test_the_file_is_laid_out_as_the_configuration_says():
    q, paths = paths_of(300_000, 2_147_483_659)
    from pyarrow import orc
    f = orc.ORCFile(paths["lineitem"])
    assert (f.compression, f.compression_size, f.row_index_stride) == (
        "SNAPPY", 262144, 10000)
    assert str(f.file_version) == "0.12" and f.nstripes == 1
    streams = q.column_streams(paths["lineitem"])
    # a decimal(12,2) quantity of 1..50 is two varint bytes a value
    assert streams["l_quantity"]["streams"] >= 2 * 300_000
    assert q.decode_bytes(paths["lineitem"]) > 300_000 * 46
    assert q.least_bytes({"lineitem": {"path": paths["lineitem"]}}) > \
        sum(streams[c]["stored"] for c in q._READ)


def test_a_second_seed_compiles_nothing_and_moves_every_sum():
    env = R.prepare(CELL, ROWS)
    q = env["queries"][QUERY]
    compiles, answers = [], []
    for seed in (1, 2_147_483_659, 77):
        _, paths, clients = R.deal(env, seed)
        session, frames = clients[0]
        rec = R.collect_once(session, env["jax"], "q", next(iter(
            frames.values())))
        assert not rec["faults"]
        compiles.append(rec["compiles"])
        answers.append(rec["answer"])
    assert compiles[1:] == [0, 0]
    assert q.Q1.compare(answers[0], answers[1])["sums_off"] >= 20
    assert q.Q1.compare(answers[1], answers[2])["sums_off"] >= 20


# -- the cell's readers ------------------------------------------------------

def reader(name):
    return R.load_module(R.HERE, "layer_metrics", name)


def test_host_decoded_units_are_summed_over_the_windows_queries(monkeypatch):
    from spark_rapids_tpu.plugin import TpuSession
    ring = [(1.0, "q", {"scan_host_decoded": 5}),
            (1.0, "q", {"scan_host_decoded": 0}),
            (1.0, "q", {"scan_host_decoded": 2})]
    monkeypatch.setattr(TpuSession, "recent_queries",
                        staticmethod(lambda: ring))
    read = reader("orc_host_decoded").read
    assert read({"window": [{}, {}]}) == 2
    assert read({"window": [{}, {}, {}]}) == 7
    assert read({"window": []}) is None
    # an engine whose ring has no such counter reports nothing
    monkeypatch.setattr(TpuSession, "recent_queries",
                        staticmethod(lambda: [(1.0, "q", {"d2h_ns": 1})]))
    assert read({"window": [{}]}) is None


@pytest.mark.parametrize("name", ["orc_scan_device_s", "orc_scan_host_idle_s",
                                  "orc_decode_hbm_roofline"])
def test_without_a_trace_a_trace_reader_reports_nothing(name):
    assert reader(name).read({"trace": None, "peaks": None, "window": [],
                              "cell": {"chips": 1}}) is None


def test_the_rooflines_share_is_bytes_over_peak_over_the_programs_seconds(
        monkeypatch):
    q, paths = paths_of(ROWS, 9)
    roof = reader("orc_decode_hbm_roofline")
    config = R.find_cell(R.ROOT, CELL)["config"]
    data = os.path.join(R.WORK, "data", config["name"])
    os.makedirs(data, exist_ok=True)
    target = os.path.join(data, "lineitem.orc")
    if os.path.lexists(target):
        os.remove(target)
    os.link(paths["lineitem"], target)
    monkeypatch.setattr(
        roof, "_load", lambda kind, name, real=roof._load:
        type("M", (), {"read": staticmethod(lambda ctx: 0.5)})
        if name == "orc_scan_device_s" else real(kind, name))
    ctx = {"peaks": {"hbm_bytes_per_s": 1e9}, "trace": {},
           "cell": {"config": config, "traffic": {"queries": [QUERY]}}}
    assert roof.read(ctx) == pytest.approx(
        100.0 * q.decode_bytes(target) / 1e9 / 0.5)
    os.remove(target)
