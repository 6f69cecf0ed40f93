"""The rest of a run with the look for a chip skipped (`--rehearse-rows`), on
the CPU backend at a small size: the last line is well formed and `correct`
is false off the chip for that reason alone; with the timed path broken
underneath, the comparison itself fails; the control fails it too."""
import json
import os
import subprocess
import sys

import pytest
import run as R

ROWS = 120_000
CELLS = ["star.q3"]


def rehearse(capfd, cell, seed=5):
    code = R.main(["--workload", cell, "--seed", str(seed), "--seconds", "1",
                   "--trace", "0", "--rehearse-rows", str(ROWS)])
    out, err = capfd.readouterr()
    return code, json.loads(out.strip().splitlines()[-1]), err


def failing(result):
    return sorted(k for k, c in result["checks"].items()
                  if c["value"] > c["limit"])


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_is_well_formed_and_not_correct_off_the_chip(capfd, cell):
    code, result, err = rehearse(capfd, cell)
    assert code == 1
    assert list(result)[:5] == ["correct", "attempted", "failed", "metrics",
                                "device"]
    assert list(result)[-1] == "checks"
    assert "window" not in result         # nor under any other key
    assert result["correct"] is False and result["failed"] == 0
    assert result["attempted"] >= 3      # first, warm, the window's
    assert result["metrics"] == {}        # no CPU number under a metric name
    assert result["device"]["platform"] == "cpu"
    assert failing(result) == ["not_on_tpu"]
    last = err.strip().splitlines()[-len(result["checks"]):]
    assert all(line.startswith("check ") for line in last)


def test_off_the_chip_there_is_no_result():
    proc = subprocess.run(
        [sys.executable, os.path.join(R.HERE, "run.py"), "--workload",
         "star.q3", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 3
    assert '"correct"' not in proc.stdout


@pytest.mark.parametrize("cell", CELLS)
def test_an_altered_answer_is_not_correct(capfd, monkeypatch, cell):
    """One cent added to one sum, where the answer is produced."""
    import decimal

    import pyarrow as pa
    from spark_rapids_tpu.frontend import DataFrame
    real = DataFrame.collect

    def altered(self):
        t = real(self)
        i = next(i for i, f in enumerate(t.schema) if pa.types.is_decimal(
            f.type))
        col = t.column(i).to_pylist()
        at = next(j for j, v in enumerate(col) if v is not None)
        col[at] += decimal.Decimal("0.01")
        return t.set_column(i, t.schema[i], pa.array(col, t.schema[i].type))
    monkeypatch.setattr(DataFrame, "collect", altered)
    _, result, _ = rehearse(capfd, cell)
    assert "sums_off" in failing(result)
    assert result["checks"]["sums_off"]["value"] == 1


@pytest.mark.parametrize("cell", CELLS)
def test_half_of_the_batch_left_out_is_not_correct(capfd, monkeypatch, cell):
    """The program reads the first half of the fact rows; the reference
    reads them all."""
    import pyarrow.parquet as pq
    from spark_rapids_tpu.plugin import TpuSession
    real = TpuSession.read_parquet

    def half(self, path, **kw):
        if os.path.basename(path) == "store_sales.parquet":
            t = pq.read_table(path)
            path = os.path.join(os.path.dirname(path), "half.parquet")
            pq.write_table(t.slice(0, t.num_rows // 2), path)
        return real(self, path, **kw)
    monkeypatch.setattr(TpuSession, "read_parquet", half)
    _, result, _ = rehearse(capfd, cell)
    # the file is in date order: the later years' rows are gone
    assert "rows_off" in failing(result)


def test_a_query_served_without_the_device_counts_as_failed(capfd,
                                                            monkeypatch):
    from spark_rapids_tpu.frontend import DataFrame
    monkeypatch.setattr(DataFrame, "collect", DataFrame.collect_cpu)
    from spark_rapids_tpu.utils.metrics import TaskMetrics
    TaskMetrics.reset()
    _, result, _ = rehearse(capfd, "star.q3")
    assert "failed_queries" in failing(result)
    assert result["failed"] == result["attempted"]


def test_two_clients_each_run_the_mix(capfd, monkeypatch):
    """`clients` is data: two sessions, two loops, every answer compared."""
    real = R.load_json

    def two(*parts):
        got = real(*parts)
        if parts[-1] == "q3_closed1.json":
            got["clients"] = 2
        return got
    monkeypatch.setattr(R, "load_json", two)
    code, result, err = rehearse(capfd, "star.q3")
    assert failing(result) == ["not_on_tpu"]
    assert result["attempted"] >= 4       # first, warm and one a client


def control_paths(rows, seed):
    found = R.find_cell(R.ROOT, "star.q3")
    config = found["config"]
    config["tables"]["store_sales"]["rows"] = rows
    q = R.load_module(R.HERE, "queries", "q3_brand_report")
    tables = R.load_module(R.HERE, "generators", config["generator"]).write(
        os.path.join(R.WORK, "data", "control-test"), seed, config,
        sorted(q.TABLES))
    return q, {k: v["path"] for k, v in tables.items()}


def test_the_control_fails_and_float32_does_not():
    """The reference with money in bfloat16, put in the program's place, is
    not correct by the query's own comparison. float32 holds every sum of
    this cell exactly (each is under 2**24 cents at this row count) and
    reads 0: a lower precision that passes, named as such in PERF.md."""
    q, paths = control_paths(400_000, 9)
    want = q.reference(paths)
    low = q.compare(q.control(paths, "bfloat16"), want)
    assert low["sums_off"] + low["rows_off"] > 0.5 * want.num_rows
    assert any(low[k] > q.LIMITS[k] for k in q.LIMITS)
    assert q.compare(q.control(paths, "float32"), want) == {
        "rows_off": 0, "sums_off": 0}


def test_the_answer_is_held_to_the_published_order_and_limit():
    q, paths = control_paths(400_000, 9)
    want = q.reference(paths)
    assert 2 < want.num_rows <= q.LIMIT
    swapped = want.take([1, 0, *range(2, want.num_rows)])
    assert q.compare(swapped, want)["rows_off"] == 2
    assert q.compare(want.slice(0, want.num_rows - 1), want)["rows_off"] == 1
    assert q.compare(want, want) == {"rows_off": 0, "sums_off": 0}


def test_a_second_seed_compiles_nothing():
    """The engine compiles a decode program per layout of a file's column
    chunks (`_col_sig`) and its operator programs per padded batch size, so
    a seed may change neither: only a checkout's first run may compile."""
    env = R.prepare("star.q3", ROWS)
    q = env["queries"]["q3_brand_report"]
    compiles, kept, answers = [], set(), []
    for seed in (1, 2_147_483_659, 77):
        _, paths, clients = R.deal(env, seed)
        session, frames = clients[0]
        rec = R.collect_once(session, env["jax"], "q", next(iter(
            frames.values())))
        assert not rec["faults"]
        compiles.append(rec["compiles"])
        # nor the rows a filter keeps: programs compile per padded size
        kept.add(q._joined(paths).num_rows)
        answers.append(rec["answer"].to_pylist())
    assert compiles[1:] == [0, 0]
    assert len(kept) == 1
    assert answers[0] != answers[1] != answers[2]


def test_store_sales_has_dsdgens_shape():
    import pyarrow.parquet as pq
    _, b = control_paths(300_000, 2_147_483_659)
    ss = pq.read_table(b["store_sales"]).to_pandas()
    # dsdgen's shape: in date order, whole tickets, items distinct in one
    dates = ss["ss_sold_date_sk"].dropna()
    assert dates.is_monotonic_increasing
    per = ss.groupby("ss_ticket_number")["ss_item_sk"]
    assert (per.nunique() == per.size()).all()
    assert 8 <= per.size().iloc[:-1].min() and per.size().max() <= 16
    assert 0.03 < ss["ss_sold_date_sk"].isna().mean() < 0.06
