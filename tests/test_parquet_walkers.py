"""The parquet scan's host phase on the process's walker threads
(io/walkers.py): every column chunk of a dispatch group walked at once where
execution is pipelined, one after the other where it is not, and the same
products either way, down to the packed transfer's bytes."""

import threading
import time

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu.io import orc_device
from spark_rapids_tpu.io import parquet_device as pd
from spark_rapids_tpu.io.walkers import walk_all, walker_pool
from spark_rapids_tpu.plugin import TpuSession
from spark_rapids_tpu.utils.metrics import TaskMetrics


@pytest.fixture()
def session():
    return TpuSession({"spark.rapids.sql.enabled": True,
                       "spark.rapids.sql.explain": "NONE"})


def _nulls(rng, n, share=0.045):
    return rng.random(n) < share


def star_file(tmp_path, rng, n=6000):
    """`store_sales`-shaped: a date key in order and an item key with 4.5%
    nulls, dictionary pages, a price, and a brand string whose dictionary
    outgrows its page so the chunk goes on in PLAIN pages."""
    days = np.sort(rng.integers(2450816, 2452642, n))
    t = pa.table({
        "ss_sold_date_sk": pa.array(days, mask=_nulls(rng, n)),
        "ss_item_sk": pa.array(rng.integers(1, 2000, n),
                               mask=_nulls(rng, n)),
        "ss_ext_sales_price": pa.array(
            rng.integers(0, 10 ** 6, n).astype(np.int64),
            mask=_nulls(rng, n)),
        "i_brand": pa.array(["brand #%05d" % v
                             for v in rng.integers(0, 4000, n)],
                            mask=_nulls(rng, n)),
    })
    path = str(tmp_path / "star.parquet")
    pq.write_table(t, path, use_dictionary=True,
                   dictionary_pagesize_limit=2048, data_page_size=4096)
    return path


def lineitem_file(tmp_path, rng, n=8000):
    """`lineitem`-shaped: two row groups, no nulls, decimal(12,2) as INT64,
    a date and two one-letter dictionary flags."""
    dec = pa.decimal128(12, 2)
    t = pa.table({
        "l_quantity": pa.array(rng.integers(100, 5001, n).astype(object),
                               dec),
        "l_extendedprice": pa.array(
            rng.integers(10 ** 5, 10 ** 7, n).astype(object), dec),
        "l_shipdate": pa.array(rng.integers(8000, 10600, n)
                               .astype(np.int32), pa.date32()),
        "l_returnflag": pa.array(rng.choice(["R", "A", "N"], n)),
        "l_linestatus": pa.array(rng.choice(["O", "F"], n)),
    })
    path = str(tmp_path / "lineitem.parquet")
    pq.write_table(t, path, row_group_size=n // 2,
                   store_decimal_as_integer=True)
    return path


FILES = {"star": (star_file, [0]), "lineitem": (lineitem_file, [0, 1])}


def _open(session, path):
    df = session.read_parquet(path)
    session.initialize_device()
    return df, df.plan.output, pd.file_supported(path, df.plan.output)


def _same(a, b, where):
    """`a` and `b` hold the same values, byte for byte, down every array,
    sequence, mapping and slot; the native walk's allocation owner (`hold`)
    only in whether there is one."""
    if isinstance(a, np.ndarray):
        assert isinstance(b, np.ndarray), where
        assert (a.dtype, a.shape) == (b.dtype, b.shape), where
        assert a.tobytes() == b.tobytes(), where
    elif isinstance(a, (bytes, bytearray, memoryview)):
        assert bytes(a) == bytes(b), where
    elif isinstance(a, (list, tuple)):
        assert type(a) is type(b) and len(a) == len(b), where
        for i, (x, y) in enumerate(zip(a, b)):
            _same(x, y, f"{where}[{i}]")
    elif isinstance(a, dict):
        assert a.keys() == b.keys(), where
        for k in a:
            _same(a[k], b[k], f"{where}[{k!r}]")
    elif hasattr(type(a), "__slots__"):
        assert type(a) is type(b), where
        for cls in type(a).__mro__:
            for slot in getattr(cls, "__slots__", ()):
                x, y = getattr(a, slot, None), getattr(b, slot, None)
                if slot == "hold":
                    assert (x is None) == (y is None), where
                else:
                    _same(x, y, f"{where}.{slot}")
    else:
        assert a == b, where


@pytest.mark.parametrize("shape", sorted(FILES))
def test_walked_and_serial_host_phases_are_the_same(session, rng, tmp_path,
                                                    shape):
    make, rgs = FILES[shape]
    path = make(tmp_path, rng)
    df, schema, pf = _open(session, path)
    names = list(schema.names)
    with open(path, "rb") as f:
        serial, total = pd._read_chunks(pf, f, rgs, schema)
        walked, total_w = pd._read_chunks(pf, f, rgs, schema,
                                          walkers=walker_pool())
        one, nrows = pd._host_phase(pf, f, rgs[-1], schema,
                                    walkers=walker_pool())
    assert total == total_w == pf.metadata.num_rows
    _same(walked, serial, "chunks")
    _same(one, serial[-1][1], "host phase")
    assert nrows == serial[-1][2]
    kinds = {p.kind for _, works, _ in serial for w in works.values()
             for p in w.chunk.pages}
    assert "dict" in kinds      # the file is what it says
    if shape == "star":
        assert "plain" in kinds
        assert any(w.defruns is not None for w in serial[0][1].values())
    sig_s = pd._group_signatures(serial, names)
    sig_w = pd._group_signatures(walked, names)
    assert sig_s is not None
    _same(sig_w, sig_s, "signatures")       # the packed bytes too
    # and the scan, walked side by side, reads what pyarrow reads
    got = df.collect()
    want = pq.read_table(path)
    for name in names:
        assert got.column(name).to_pylist() == \
            want.column(name).to_pylist(), name


def test_a_walk_that_raises_ends_its_group_once_every_walk_has_ended(
        session, rng, tmp_path, monkeypatch):
    path = lineitem_file(tmp_path, rng)
    _, schema, pf = _open(session, path)
    bad = pf.metadata.row_group(0).column(0)
    real, started, ended = pd._decode_chunk, [], []

    def walk(buf, cm, optional):
        started.append(cm.path_in_schema)
        if cm.data_page_offset == bad.data_page_offset:
            raise pd.DeviceDecodeUnsupported("page kind")
        time.sleep(0.05)
        out = real(buf, cm, optional)
        ended.append(cm.path_in_schema)
        return out
    monkeypatch.setattr(pd, "_decode_chunk", walk)
    with open(path, "rb") as f:
        with pytest.raises(pd.DeviceDecodeUnsupported, match="page kind"):
            pd._read_chunks(pf, f, [0, 1], schema, walkers=walker_pool())
    # every walk that started had ended when the raise came, and the ones
    # that had not started never will
    assert len(ended) == len(started) - 1
    time.sleep(0.2)
    assert len(ended) == len(started) - 1


def test_a_column_that_raises_falls_back_for_its_group_only(
        rng, tmp_path, monkeypatch):
    """Row group 2's `l_shipdate` cannot decode on the device: its group
    ([2, 3]) leaves the fused decode, row group 2 goes to pyarrow and 3 to
    the per-row-group decode; the group [0, 1] is decoded whole."""
    path = lineitem_file(tmp_path, rng, n=8000)
    pq.write_table(pq.read_table(path), path, row_group_size=2000,
                   store_decimal_as_integer=True)
    meta = pq.ParquetFile(path).metadata
    bad = meta.row_group(2).column(2)
    assert bad.path_in_schema == "l_shipdate"
    real, fused, host = pd._decode_chunk, [], []

    def walk(buf, cm, optional):
        if cm.data_page_offset == bad.data_page_offset:
            raise pd.DeviceDecodeUnsupported("page kind")
        return real(buf, cm, optional)
    monkeypatch.setattr(pd, "_decode_chunk", walk)
    real_fused = pd.decode_row_groups_fused

    def fused_seen(pf, f, rgs, *a, **kw):
        out = real_fused(pf, f, rgs, *a, **kw)
        fused.append(list(rgs))
        return out
    monkeypatch.setattr(pd, "decode_row_groups_fused", fused_seen)
    real_read = pq.ParquetFile.read_row_group

    def read_seen(self, i, *a, **kw):
        host.append(i)
        return real_read(self, i, *a, **kw)
    monkeypatch.setattr(pq.ParquetFile, "read_row_group", read_seen)
    s = TpuSession({"spark.rapids.sql.enabled": True,
                    "spark.rapids.sql.explain": "NONE",
                    "spark.rapids.tpu.pipeline.scan.chunksPerDispatch": 2})
    got = s.read_parquet(path).collect()
    assert fused == [[0, 1]] and host == [2]
    want = pq.read_table(path)
    for name in want.column_names:
        assert got.column(name).to_pylist() == \
            want.column(name).to_pylist(), name


def test_the_walkers_charge_the_query_that_asked(session, rng, tmp_path,
                                                 monkeypatch):
    path = lineitem_file(tmp_path, rng)
    real, seen = pd._decode_chunk, []
    pause = 0.02

    def walk(*a, **kw):
        seen.append((threading.current_thread().name, TaskMetrics.get()))
        time.sleep(pause)
        return real(*a, **kw)
    monkeypatch.setattr(pd, "_decode_chunk", walk)
    assert session.read_parquet(path).collect().num_rows == 8000
    tm = TaskMetrics.get()
    rec = TpuSession.recent_queries()[-1][2]
    assert len(seen) == 2 * 5
    assert all(name.startswith("srtpu-walk") and owner is tm
               for name, owner in seen)
    # thread-seconds: every walk's pause is in, on however many threads
    assert rec["scan_walk_ns"] >= len(seen) * pause * 1e9


def test_orc_and_parquet_walk_on_one_pool(session, rng, tmp_path,
                                          monkeypatch):
    from pyarrow import orc
    pq_path = lineitem_file(tmp_path, rng)
    orc_path = str(tmp_path / "t.orc")
    orc.write_table(pa.table({"a": np.arange(3000, dtype=np.int64)}),
                    orc_path)
    pools = []
    real_stripe, real_chunks = orc_device.decode_stripe, pd._read_chunks

    def stripe(*a, walkers=None, **kw):
        pools.append(("orc", walkers))
        return real_stripe(*a, walkers=walkers, **kw)

    def chunks(pf, f, rgs, schema, host_cols=None, walkers=None):
        pools.append(("parquet", walkers))
        return real_chunks(pf, f, rgs, schema, host_cols, walkers)
    monkeypatch.setattr(orc_device, "decode_stripe", stripe)
    monkeypatch.setattr(pd, "_read_chunks", chunks)
    session.read_orc(orc_path).collect()
    session.read_parquet(pq_path).collect()
    assert {fmt for fmt, _ in pools} == {"orc", "parquet"}
    assert all(pool is walker_pool() for _, pool in pools)


@pytest.mark.parametrize("pipelined", [True, False])
def test_the_walkers_are_used_only_where_execution_is_pipelined(
        rng, tmp_path, monkeypatch, pipelined):
    """Off, the walk is the serial one on the thread that drives the scan,
    and nothing is handed to the pool; the batch is the same either way."""
    path = star_file(tmp_path, rng)
    pool, submitted = walker_pool(), []
    real_submit = pool.submit

    def submit(fn, *a, **kw):
        submitted.append(threading.current_thread().name)
        return real_submit(fn, *a, **kw)
    monkeypatch.setattr(pool, "submit", submit)
    s = TpuSession({"spark.rapids.sql.enabled": True,
                    "spark.rapids.sql.explain": "NONE",
                    "spark.rapids.tpu.pipeline.enabled": pipelined})
    got = s.read_parquet(path).collect()
    assert len(submitted) == (4 if pipelined else 0)
    want = pq.read_table(path)
    for name in want.column_names:
        assert got.column(name).to_pylist() == \
            want.column(name).to_pylist(), name


def test_walk_all_keeps_the_order_and_needs_no_walk():
    pool = walker_pool()
    assert walk_all(pool, []) == []
    walks = [lambda i=i: (time.sleep(0.01 * (5 - i)), i)[1]
             for i in range(5)]
    assert walk_all(pool, walks) == list(range(5))


def test_many_scans_share_the_walkers_and_each_counts_its_own():
    """More scan threads than walkers, switching as often as the
    interpreter lets them: every walk counts in the query that asked, and
    none is lost."""
    import sys
    callers, rounds, per = 8, 20, 6
    counted, errors = {}, []

    def scan(i):
        try:
            TaskMetrics.reset()
            tm = TaskMetrics.get()
            for _ in range(rounds):
                walk_all(walker_pool(), [
                    lambda: TaskMetrics.get().add("scan_chunks", 1)
                    for _ in range(per)])
            counted[i] = (tm, tm.scan_chunks)
        except BaseException as e:  # reported below, on the test's thread
            errors.append(e)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=scan, args=(i,))
                   for i in range(callers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not errors and not any(t.is_alive() for t in threads)
    assert sorted(counted) == list(range(callers))
    assert all(n == rounds * per for _, n in counted.values())
    assert len({id(tm) for tm, _ in counted.values()}) == callers
