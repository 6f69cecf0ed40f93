"""Device parquet decode (io/parquet_device.py): PLAIN values + RLE/bit-packed
definition levels decoded on device, differential against pyarrow on
generated files (reference GpuParquetScan device decode)."""

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu.plugin import TpuSession


@pytest.fixture()
def session():
    return TpuSession({"spark.rapids.sql.enabled": True,
                       "spark.rapids.sql.explain": "NONE"})


def plain_table(rng, n=5000, nulls=True):
    def mk(vals):
        if not nulls:
            return pa.array(vals)
        mask = rng.random(n) < 0.2
        return pa.array(vals, mask=mask)
    return pa.table({
        "i": mk(rng.integers(-2**31, 2**31 - 1, n).astype(np.int32)),
        "l": mk(rng.integers(-2**62, 2**62, n)),
        "f": mk(rng.normal(0, 1e3, n).astype(np.float32)),
        "d": mk(rng.normal(0, 1e6, n)),
        "b": mk(rng.integers(0, 2, n).astype(bool)),
    })


def write_plain(tmp_path, t, name="t.parquet", **kw):
    path = str(tmp_path / name)
    pq.write_table(t, path, use_dictionary=False, compression=kw.pop(
        "compression", "snappy"), **kw)
    return path


def _used_device_decode(session, path):
    from spark_rapids_tpu.io.parquet_device import (
        DeviceDecodeUnsupported, device_decode_file, file_supported)
    df = session.read_parquet(path)
    session.initialize_device()
    try:
        pf = file_supported(path, df.plan.output)
        batches = list(device_decode_file(pf, path, df.plan.output))
    except Exception:
        return False, None
    return True, batches[0][0] if batches else None


def _col_strings(col, nrows: int):
    """Decode a (possibly chunked-layout) string column to python strings."""
    import numpy as np
    from spark_rapids_tpu.columnar.strings import assemble_matrix
    mat, lens = assemble_matrix(col.data, col.lengths, col.overflow, nrows)
    return [bytes(np.asarray(mat[i, :int(lens[i])])).decode()
            for i in range(nrows)]


class TestNativeChunkWalk:
    """native/src/chunk_walk.cpp vs the python page walk (the semantic
    spec): same pages, same run tables, same payloads, on files with
    dict+plain spill, nulls, strings and both codecs."""

    @pytest.mark.parametrize("compression", ["snappy", "none"])
    def test_walk_matches_python(self, rng, tmp_path, compression):
        from spark_rapids_tpu.io import parquet_device as P
        from spark_rapids_tpu.native import runtime as R
        if not R.available():
            pytest.skip("native lib not built")
        n = 30000
        mask = rng.random(n) < 0.15
        t = pa.table({
            "l": pa.array(rng.integers(-10**14, 10**14, n), mask=mask),
            "lo": pa.array(rng.integers(0, 30, n), mask=mask),  # dict
            "s": pa.array([f"s{i % 211}" for i in range(n)], mask=mask),
            "b": pa.array(rng.integers(0, 2, n).astype(bool), mask=mask),
        })
        path = str(tmp_path / "w.parquet")
        pq.write_table(t, path, compression=compression)
        pf = pq.ParquetFile(path)
        rgm = pf.metadata.row_group(0)
        sch = pf.metadata.schema
        for ci in range(rgm.num_columns):
            cm = rgm.column(ci)
            optional = sch.column(ci).max_definition_level > 0
            with open(path, "rb") as f:
                f.seek(cm.dictionary_page_offset or cm.data_page_offset)
                buf = f.read(cm.total_compressed_size)
            nat = P._decode_chunk(buf, cm, optional)
            assert nat.hold is not None, "native walk did not engage"
            # python walk (native disabled for the call)
            lib, R._LIB = R._LIB, None
            try:
                ref = P._decode_chunk_inner(buf, cm, optional)
            finally:
                R._LIB = lib
            assert nat.total == ref.total
            assert nat.dict_count == ref.dict_count
            if ref.dict_raw is not None:
                assert bytes(np.asarray(nat.dict_raw)) == ref.dict_raw
            assert len(nat.pages) == len(ref.pages)
            for a, b in zip(nat.pages, ref.pages):
                assert (a.kind, a.bw, a.num_values, a.ndef) == \
                    (b.kind, b.bw, b.num_values, b.ndef)
                if a.kind == "plain":
                    assert np.array_equal(
                        np.frombuffer(np.ascontiguousarray(a.payload),
                                      np.uint8),
                        np.frombuffer(b.payload, np.uint8)
                        if not isinstance(b.payload, np.ndarray)
                        else b.payload.view(np.uint8))
                elif a.payload is not None:
                    # expand both run tables on host and compare values
                    def expand(runs, ndef, bw):
                        kinds, counts, values, bitoffs, packed = runs
                        bits = np.unpackbits(np.asarray(packed),
                                             bitorder="little")
                        out = []
                        for k, c, v, bo in zip(kinds, counts, values,
                                               bitoffs):
                            c = int(c)
                            if k == 0:
                                out.extend([int(v)] * c)
                            else:
                                sl = bits[bo:bo + c * bw] \
                                    .reshape(c, bw).astype(np.uint64)
                                out.extend(
                                    (sl << np.arange(bw, dtype=np.uint64)
                                     ).sum(axis=1).tolist())
                        return out[:ndef]
                    assert expand(a.payload, a.ndef, a.bw) == \
                        expand(b.payload, b.ndef, b.bw)


class TestDeviceParquetDecode:
    @pytest.mark.parametrize("compression", ["snappy", "none", "zstd"])
    def test_plain_roundtrip(self, session, rng, tmp_path, compression):
        t = plain_table(rng)
        path = write_plain(tmp_path, t, compression=compression)
        df = session.read_parquet(path)
        tpu = df.collect()
        assert tpu.num_rows == t.num_rows
        exact = pq.read_table(path)
        for name in t.schema.names:
            a = tpu.column(name).to_pylist()
            b = exact.column(name).to_pylist()
            assert a == b or all(
                (x is None and y is None) or x == y or
                (isinstance(x, float) and abs(x - y) < 1e-12)
                for x, y in zip(a, b)), name

    def test_device_path_actually_used(self, session, rng, tmp_path):
        path = write_plain(tmp_path, plain_table(rng, n=800))
        used, first = _used_device_decode(session, path)
        assert used and first is not None

    def test_no_nulls_required_like(self, session, rng, tmp_path):
        t = plain_table(rng, n=1200, nulls=False)
        path = write_plain(tmp_path, t)
        df = session.read_parquet(path)
        assert df.collect().equals(pq.read_table(path))

    def test_multiple_row_groups(self, session, rng, tmp_path):
        t = plain_table(rng, n=4000)
        path = write_plain(tmp_path, t, row_group_size=700)
        df = session.read_parquet(path)
        out = df.collect()
        exact = pq.read_table(path)
        assert out.column("l").to_pylist() == exact.column("l").to_pylist()
        assert out.column("i").to_pylist() == exact.column("i").to_pylist()

    def test_dictionary_files_take_device_path(self, session, rng,
                                               tmp_path):
        # round-2 verdict item 3 INVERTED: default pyarrow output
        # (dictionary-encoded) now decodes on device
        t = plain_table(rng, n=500)
        path = str(tmp_path / "dict.parquet")
        pq.write_table(t, path, use_dictionary=True)
        used, first = _used_device_decode(session, path)
        assert used and first is not None
        df = session.read_parquet(path)
        got = df.collect()
        exact = pq.read_table(path)
        for name in t.schema.names:
            assert got.column(name).to_pylist() == \
                exact.column(name).to_pylist(), name

    def test_plain_strings_take_device_path(self, session, rng, tmp_path):
        t = pa.table({"s": pa.array(["a", "bb", None, "ccc", "", None,
                                     "ünïcødé 字", "x" * 100])})
        path = write_plain(tmp_path, t)
        used, _ = _used_device_decode(session, path)
        assert used
        df = session.read_parquet(path)
        assert df.collect().column("s").to_pylist() == \
            t.column("s").to_pylist()

    def test_dict_strings_take_device_path(self, session, rng, tmp_path):
        n = 3000
        words = ["alpha", "beta", "gamma", "δδδ", "", "longer-value-here"]
        vals = [None if rng.random() < 0.15 else
                words[int(rng.integers(0, len(words)))] for _ in range(n)]
        t = pa.table({"s": pa.array(vals, type=pa.string()),
                      "l": pa.array(rng.integers(0, 50, n))})
        path = str(tmp_path / "ds.parquet")
        pq.write_table(t, path, use_dictionary=True)
        used, _ = _used_device_decode(session, path)
        assert used
        df = session.read_parquet(path)
        got = df.collect()
        assert got.column("s").to_pylist() == vals
        assert got.column("l").to_pylist() == t.column("l").to_pylist()

    def test_dict_to_plain_spill_pages(self, session, rng, tmp_path):
        # parquet writers fall back to PLAIN mid-chunk once the dictionary
        # outgrows its limit: chunks carry BOTH dict and plain data pages
        n = 6000
        vals = ["s%08d" % int(v) for v in rng.integers(0, n, n)]
        t = pa.table({"s": pa.array(vals)})
        path = str(tmp_path / "spill.parquet")
        pq.write_table(t, path, use_dictionary=True,
                       dictionary_pagesize_limit=1024, data_page_size=2048)
        df = session.read_parquet(path)
        assert df.collect().column("s").to_pylist() == vals

    def test_dict_many_small_pages_with_nulls(self, session, rng,
                                              tmp_path):
        n = 4000
        base = rng.integers(0, 40, n)
        mask = rng.random(n) < 0.25
        t = pa.table({"v": pa.array(base * 1000, mask=mask),
                      "f": pa.array(base.astype(np.float64) / 3,
                                    mask=~mask)})
        path = str(tmp_path / "dsmall.parquet")
        pq.write_table(t, path, use_dictionary=True, data_page_size=300)
        used, _ = _used_device_decode(session, path)
        assert used
        got = session.read_parquet(path).collect()
        exact = pq.read_table(path)
        assert got.column("v").to_pylist() == exact.column("v").to_pylist()
        assert got.column("f").to_pylist() == exact.column("f").to_pylist()

    def test_overwide_strings_decode_to_chunked_layout(self, session, rng,
                                                       tmp_path):
        # beyond spark.rapids.tpu.string.maxWidth the decoder builds the
        # CHUNKED long-string layout ON DEVICE (round-4; previously a
        # per-row-group host fallback): the device path stays in use and
        # the column carries a head matrix + shared tail blob
        wide = "w" * 20000
        t = pa.table({"s": pa.array(["a", wide, "b"])})
        path = write_plain(tmp_path, t)
        used, _ = _used_device_decode(session, path)
        assert used
        df = session.read_parquet(path)
        assert df.collect().column("s").to_pylist() == ["a", wide, "b"]
        assert df.collect_cpu().column("s").to_pylist() == ["a", wide, "b"]

    def test_megabyte_string_bounded_memory(self, session, rng, tmp_path):
        # a 1MB value must cost ~its own bytes on device, not cap * 1MB
        import numpy as np
        from spark_rapids_tpu.io.parquet_device import (device_decode_file,
                                                        file_supported)
        big = "Z" * (1 << 20)
        vals = [f"v{i}" for i in range(500)] + [big]
        t = pa.table({"s": pa.array(vals)})
        path = write_plain(tmp_path, t)
        schema = session.read_parquet(path).plan.output
        pf = file_supported(path, schema)
        batches = list(device_decode_file(pf, path, schema))
        total_bytes = sum(
            int(c.data.size) + (int(c.overflow[0].size)
                                if c.overflow is not None else 0)
            for b, _ in batches for c in b.columns)
        # head matrix (512*256) + blob (~1MB bucket) << cap * 1MB
        assert total_bytes < 4 * (1 << 20)
        got = [s for b, nr in batches
               for s in _col_strings(b.columns[0], int(nr))]
        assert got == vals

    def test_bool_across_many_small_pages(self, session, rng, tmp_path):
        # page bit-packing restarts per page: misalignment regression test
        n = 4000
        mask = rng.random(n) < 0.3
        t = pa.table({"b": pa.array(rng.integers(0, 2, n).astype(bool),
                                    mask=mask),
                      "l": pa.array(rng.integers(0, 10, n))})
        path = str(tmp_path / "b.parquet")
        pq.write_table(t, path, use_dictionary=False, data_page_size=100)
        used, _ = _used_device_decode(session, path)
        assert used
        df = session.read_parquet(path)
        assert df.collect().column("b").to_pylist() == \
            pq.read_table(path).column("b").to_pylist()

    def test_lz4_files_fall_back_cleanly(self, session, rng, tmp_path):
        t = plain_table(rng, n=300)
        path = str(tmp_path / "lz4.parquet")
        pq.write_table(t, path, use_dictionary=False, compression="lz4")
        used, _ = _used_device_decode(session, path)
        assert not used
        df = session.read_parquet(path)
        assert df.collect().num_rows == 300  # host path still works

    def test_v2_pages_fall_back_cleanly(self, session, rng, tmp_path):
        t = plain_table(rng, n=400)
        path = str(tmp_path / "v2.parquet")
        pq.write_table(t, path, use_dictionary=False,
                       data_page_version="2.0")
        df = session.read_parquet(path)  # must not crash
        got = df.collect()
        assert got.column("l").to_pylist() == \
            pq.read_table(path).column("l").to_pylist()

    def test_empty_file(self, session, tmp_path):
        t = pa.table({"i": pa.array([], type=pa.int32())})
        path = str(tmp_path / "empty.parquet")
        pq.write_table(t, path, use_dictionary=False)
        df = session.read_parquet(path)
        assert df.collect().num_rows == 0

    def test_query_over_device_decoded_scan(self, session, rng, tmp_path):
        from spark_rapids_tpu.expr import Count, Sum, col
        t = plain_table(rng, n=3000)
        path = write_plain(tmp_path, t)
        df = session.read_parquet(path)
        q = df.group_by("b").agg(c=Count(col("l")), s=Sum(col("i")))
        tpu = q.collect().sort_by([("b", "ascending")])
        cpu = q.collect_cpu().sort_by([("b", "ascending")])
        assert tpu.column("c").to_pylist() == cpu.column("c").to_pylist()
        assert tpu.column("s").to_pylist() == cpu.column("s").to_pylist()


def tpcds_like_table(rng, n=6000, nulls=True):
    """TPC-DS fact-table shape: decimal(7,2) money columns, surrogate-key
    longs, a date and a timestamp — the columns round-4's verdict said
    were evicting whole files from the device path."""
    import datetime
    import decimal

    def mk(vals, typ=None):
        mask = rng.random(n) < 0.1 if nulls else np.zeros(n, bool)
        if typ is not None and pa.types.is_decimal(typ):
            py = [None if mask[i] else
                  decimal.Decimal(int(vals[i])).scaleb(-typ.scale)
                  for i in range(n)]
            return pa.array(py, type=typ)
        return pa.array(vals, mask=mask, type=typ)

    epoch = datetime.date(1970, 1, 1)
    return pa.table({
        "ss_item_sk": pa.array(rng.integers(1, 200_000, n)),
        "ss_quantity": mk(rng.integers(1, 100, n).astype(np.int32)),
        "ss_sales_price": mk(rng.integers(0, 10**6, n),
                             pa.decimal128(7, 2)),
        "ss_ext_sales_price": mk(rng.integers(0, 10**8, n),
                                 pa.decimal128(9, 2)),
        "ss_net_paid_wide": mk(rng.integers(-10**18, 10**18, n),
                               pa.decimal128(30, 8)),
        "ss_sold_date": mk(np.array(
            [epoch + datetime.timedelta(days=int(x))
             for x in rng.integers(10_000, 12_000, n)]),
            pa.date32()),
        "ss_sold_ts": mk(rng.integers(-4 * 10**15, 4 * 10**15, n),
                         pa.timestamp("us")),
    })


class TestDecimalTimestampDeviceDecode:
    """Round-5 verdict item 1: decimal + date/timestamp device decode with
    PER-COLUMN fallback. The INVERTED tests assert TPC-DS-shaped columns
    now take the device path (decimal(7,2) FLBA, decimal(30,8) limb pairs,
    INT64 timestamps both units, INT96); golden oracle is pyarrow."""

    def _expected(self, path):
        from spark_rapids_tpu.io.scanbase import normalize_timestamps
        return normalize_timestamps(pq.read_table(path))

    def _assert_scan_matches(self, session, path):
        got = session.read_parquet(path).collect()
        exp = self._expected(path)
        for name in exp.schema.names:
            assert got.column(name).to_pylist() == \
                exp.column(name).to_pylist(), name

    def test_tpcds_shaped_file_fully_device_decoded(self, session, rng,
                                                    tmp_path):
        from spark_rapids_tpu.io.parquet_device import columns_supported
        t = tpcds_like_table(rng)
        path = str(tmp_path / "fact.parquet")
        pq.write_table(t, path, version="2.6")
        df = session.read_parquet(path)
        pf, bad = columns_supported(path, df.plan.output)
        assert bad == {}, bad  # INVERTED: nothing host-decodes
        self._assert_scan_matches(session, path)

    @pytest.mark.parametrize("use_dict", [True, False])
    def test_flba_decimals_plain_and_dict(self, session, rng, tmp_path,
                                          use_dict):
        import decimal
        n = 4000
        small = rng.integers(-10**6, 10**6, n)
        if use_dict:  # low cardinality so the dictionary engages
            small = rng.integers(0, 50, n) * 7 - 100
        vals = [decimal.Decimal(int(x)).scaleb(-2) for x in small]
        t = pa.table({"d": pa.array(vals, type=pa.decimal128(7, 2)),
                      "w": pa.array(
                          [decimal.Decimal(int(x)).scaleb(-8) * 10**9
                           for x in small], type=pa.decimal128(30, 8))})
        path = str(tmp_path / "d.parquet")
        pq.write_table(t, path, use_dictionary=use_dict)
        used, _ = _used_device_decode(session, path)
        assert used
        self._assert_scan_matches(session, path)

    def test_timestamp_millis_unit(self, session, rng, tmp_path):
        n = 2000
        t = pa.table({"ts": pa.array(rng.integers(-4 * 10**12,
                                                  4 * 10**12, n),
                                     pa.timestamp("ms"))})
        path = str(tmp_path / "ms.parquet")
        pq.write_table(t, path, version="2.4")
        used, _ = _used_device_decode(session, path)
        assert used
        self._assert_scan_matches(session, path)

    def test_int96_timestamps(self, session, rng, tmp_path):
        n = 2000
        micros = np.concatenate([
            rng.integers(-4 * 10**15, 4 * 10**15, n - 2),
            np.array([0, -1])])
        t = pa.table({"ts": pa.array(micros, pa.timestamp("us")),
                      "v": pa.array(rng.normal(size=n))})
        path = str(tmp_path / "i96.parquet")
        pq.write_table(t, path, use_deprecated_int96_timestamps=True)
        used, _ = _used_device_decode(session, path)
        assert used
        self._assert_scan_matches(session, path)

    def test_nanos_column_falls_back_siblings_on_device(
            self, session, rng, tmp_path):
        """PER-COLUMN fallback: a TIMESTAMP(NANOS) column host-decodes
        (Spark rejects NANOS outright) while its siblings still ride the
        device path; the merged batch matches pyarrow."""
        from spark_rapids_tpu.io.parquet_device import columns_supported
        n = 1500
        t = pa.table({
            "ns": pa.array(rng.integers(0, 10**15, n) * 1000,
                           pa.timestamp("ns")),
            "l": pa.array(rng.integers(-10**12, 10**12, n)),
            "s": pa.array([f"r{i % 53}" for i in range(n)])})
        path = str(tmp_path / "ns.parquet")
        pq.write_table(t, path, version="2.6")
        df = session.read_parquet(path)
        pf, bad = columns_supported(path, df.plan.output)
        assert set(bad) == {"ns"}
        self._assert_scan_matches(session, path)

    def test_file_decimal_scale_mismatch_falls_back(self, session, rng,
                                                    tmp_path):
        """A file whose decimal scale differs from the read schema must
        NOT silently decode with the wrong scale — that column host-falls
        back (where pyarrow casts), siblings stay on device."""
        import decimal
        from spark_rapids_tpu import types as T
        from spark_rapids_tpu.columnar.batch import Schema
        from spark_rapids_tpu.io.parquet_device import columns_supported
        t = pa.table({"d": pa.array([decimal.Decimal("1.50")],
                                    type=pa.decimal128(7, 2)),
                      "l": pa.array([3], type=pa.int64())})
        path = str(tmp_path / "mm.parquet")
        pq.write_table(t, path)
        schema = Schema(("d", "l"), (T.DecimalType(7, 3), T.LongType()))
        pf, bad = columns_supported(path, schema)
        assert set(bad) == {"d"}
        # the merged batch must carry the SCAN schema's scale: 1.50 read
        # at decimal(7,3) is still 1.50 (unscaled 1500), not 0.150
        from spark_rapids_tpu.columnar.batch import batch_to_arrow
        from spark_rapids_tpu.io.parquet_device import decode_row_group
        with open(path, "rb") as f:
            b, _ = decode_row_group(pf, f, 0, schema, host_cols=bad)
        assert b.columns[0].dtype == T.DecimalType(7, 3)
        back = batch_to_arrow(b)
        assert back.column("d").to_pylist() == [decimal.Decimal("1.500")]
        assert back.column("l").to_pylist() == [3]


def _run_table_cases():
    """(id, counts, cap) for `rowops.slot_runs`: the run tables a parquet chunk
    hands the decoder, and the ones it must not trip over."""
    r = np.random.default_rng(30)
    big = r.integers(0, 40, 65536)
    big[r.random(65536) < 0.2] = 0
    return [
        ("total_below_cap", [3, 1, 4, 1, 5], 32),
        ("total_equals_cap", [8, 8, 8, 8], 32),
        ("total_above_cap", [20, 20, 20], 32),
        ("end_on_cap_then_more", [16, 16, 5, 5], 32),
        ("empty_runs_in_the_middle", [4, 0, 0, 6, 0, 3, 0, 0, 0, 2], 24),
        ("empty_first_run", [0, 0, 7, 2], 16),
        ("padded_zero_tail", [5, 9, 2, 0, 0, 0, 0, 0], 32),
        ("all_runs_empty", [0, 0, 0, 0], 8),
        ("one_run", [11], 16),
        ("one_run_past_cap", [40], 16),
        ("one_slot", [1, 1], 1),
        ("65536_runs_a_fifth_empty", big, 1 << 20),
        ("65536_runs_cap_cuts_them", big, 700_001),
        ("cap_no_multiple_of_a_lane", r.integers(0, 5, 3000), 5000),
    ]


_RUN_TABLES = _run_table_cases()


class TestRunOfSlot:
    """The slot -> run map of every def-level and dictionary-index
    expansion is `searchsorted(cumsum(counts), arange(cap), "right")`; the
    decoder computes it without a search (one mark per run, one prefix
    sum), and the decode programs must stay free of loops."""

    @pytest.mark.parametrize("dtype", [np.int64, np.int32])
    @pytest.mark.parametrize(
        "counts,cap", [c[1:] for c in _RUN_TABLES],
        ids=[c[0] for c in _RUN_TABLES])
    def test_equals_searchsorted(self, counts, cap, dtype):
        """`dtype` is the ends' (the host ships int32, ORC's tables too)."""
        import jax
        import jax.numpy as jnp
        from spark_rapids_tpu.ops.rowops import slot_runs
        counts = np.asarray(counts, np.int64)
        want_ends = np.cumsum(counts)
        run = jax.jit(slot_runs, static_argnums=1)(
            jnp.asarray(want_ends.astype(dtype)), cap)
        want = np.clip(np.searchsorted(want_ends, np.arange(cap),
                                       side="right"), 0, len(counts) - 1)
        assert run.dtype == jnp.int32
        assert run.shape == (cap,)
        assert np.array_equal(np.asarray(run), want)
        # and it is jnp.searchsorted's own answer too, not only numpy's
        got = jnp.clip(jnp.searchsorted(jnp.cumsum(jnp.asarray(counts)),
                                        jnp.arange(cap), side="right"),
                       0, len(counts) - 1)
        assert np.array_equal(np.asarray(run), np.asarray(got))

    @pytest.mark.parametrize("n", [1, 7, 128, 129, 4096, 5000, 16385,
                                   (1 << 20) + 3])
    def test_prefix_sum_equals_cumsum(self, n):
        import jax
        from spark_rapids_tpu.ops.rowops import prefix_sum
        x = np.random.default_rng(n).integers(0, 4, n).astype(np.int32)
        got = jax.jit(prefix_sum)(x)
        assert got.dtype == np.int32
        assert np.array_equal(np.asarray(got), np.cumsum(x, dtype=np.int32))

    @pytest.mark.parametrize("nrows,caps,cap_total", [
        ((700, 300), (1024, 512), 1024),
        ((1024, 512), (1024, 512), 2048),
        ((5, 0, 9), (128, 128, 128), 128),
        ((0, 0), (128, 128), 128),
        ((77,), (128,), 128),
    ])
    def test_merged_slot_source_equals_searchsorted(self, nrows, caps,
                                                    cap_total):
        import jax.numpy as jnp
        from spark_rapids_tpu.io.parquet_device import _merged_slot_source
        src, live = _merged_slot_source(jnp.asarray(nrows, jnp.int64), caps,
                                        cap_total)
        cum = np.cumsum(nrows)
        j = np.arange(cap_total)
        c = np.clip(np.searchsorted(cum, j, side="right"), 0, len(caps) - 1)
        base = np.where(c > 0, cum[np.maximum(c - 1, 0)], 0)
        chunk_base = np.concatenate(([0], np.cumsum(caps)[:-1]))
        assert src.dtype == jnp.int32
        assert np.array_equal(np.asarray(src), chunk_base[c] + (j - base))
        assert np.array_equal(np.asarray(live), j < cum[-1])

    @staticmethod
    def _two_row_group_file(tmp_path):
        """Nulls in every column, a dictionary that grows page by page (so
        one chunk's index pages come in more than one bit width), a string
        column, two row groups."""
        r = np.random.default_rng(7)
        n = 6000
        mask = r.random(n) < 0.1
        grow = np.minimum(np.arange(n) % 3000, r.integers(0, 3000, n))
        t = pa.table({
            "k": pa.array(grow * 7, mask=mask),
            "v": pa.array(r.integers(0, 50, n).astype(np.int32), mask=mask),
            "s": pa.array(["w%d" % (i % 37) for i in range(n)], mask=mask),
        })
        path = str(tmp_path / "two_rg.parquet")
        pq.write_table(t, path, use_dictionary=True, row_group_size=3000,
                       data_page_size=512)
        return path, t

    @staticmethod
    def _open(session, path):
        from spark_rapids_tpu.io.parquet_device import file_supported
        df = session.read_parquet(path)
        session.initialize_device()
        return df, df.plan.output, file_supported(path, df.plan.output)

    def test_fused_multi_program_lowers_without_a_loop(self, session,
                                                       tmp_path):
        from spark_rapids_tpu.io import parquet_device as pd
        path, t = self._two_row_group_file(tmp_path)
        df, schema, pf = self._open(session, path)
        with open(path, "rb") as f:
            chunks, total = pd._read_chunks(pf, f, [0, 1], schema)
        groups_sig, caps, packed, _ = pd._group_signatures(
            chunks, list(schema.names))
        arrays = pd._unpack_program(
            tuple(m for _, ms in groups_sig for m in ms))(packed)
        widths = {p.bw for _, works, _ in chunks for w in works.values()
                  for p in w.chunk.pages if p.payload is not None
                  and p.kind == "dict"}
        assert len(widths) > 1, widths      # the file is what it says
        assert any(cs[1] if cs[0] == "string" else cs[4]
                   for cs in groups_sig[0][0])      # def levels present
        program = pd._fused_multi_program(
            groups_sig, tuple(caps), pd.row_bucket(total), False)
        nrows = np.asarray([n for _, _, n in chunks], np.int64)
        text = program.direct.lower(nrows, *arrays).as_text()
        assert "stablehlo.while" not in text
        assert "stablehlo.scatter" in text      # the marks, one per table
        # and the batch it decodes is still pyarrow's
        got = df.collect()
        for name in t.schema.names:
            assert got.column(name).to_pylist() == \
                t.column(name).to_pylist(), name

    def test_fused_decode_program_lowers_without_a_loop(self, session,
                                                        tmp_path):
        from spark_rapids_tpu.io import parquet_device as pd
        path, _ = self._two_row_group_file(tmp_path)
        _, schema, pf = self._open(session, path)
        with open(path, "rb") as f:
            works, nrows = pd._host_phase(pf, f, 0, schema)
        fused = [w for w in works.values() if w.ship is not None]
        assert len(fused) >= 2
        flat = []
        for w in fused:
            assert w.defruns is not None
            flat.extend(w.defruns)
            flat.extend(w.ship)
        cap = pd.row_bucket(nrows)
        program = pd._fused_decode_program(
            tuple(pd._col_sig(w) for w in fused), cap)
        text = program.direct.lower(np.int64(nrows), *flat).as_text()
        assert "stablehlo.while" not in text
        assert "stablehlo.scatter" in text
        outs = program(np.int64(nrows), *flat)
        exact = pq.read_table(path).slice(0, nrows)
        for w, (data, validity) in zip(fused, outs):
            want = exact.column(w.name).to_pylist()
            got = [int(d) if v else None for d, v in
                   zip(np.asarray(data)[:nrows], np.asarray(validity)[:nrows])]
            assert got == want, w.name


# -- one run table per column chunk (PR 38) -----------------------------------

def _varint(x: int) -> bytes:
    out = bytearray()
    while True:
        b, x = x & 0x7F, x >> 7
        out.append(b | 0x80 if x else b)
        if not x:
            return bytes(out)


def _hybrid(values, width: int, rng, empty_runs: bool) -> bytes:
    """Parquet's RLE/bit-packed hybrid encoding of `values`: eight or more
    equal values as a repeated run, the rest as bit-packed groups of eight,
    LSB first, 1-5 groups a run, the last group padded; `empty_runs` writes
    a repeated run of no values after every repeated run."""
    vbytes = (width + 7) // 8
    out = bytearray()
    i, n = 0, len(values)
    while i < n:
        j = i
        while j < n and values[j] == values[i]:
            j += 1
        if j - i >= 8:
            out += _varint((j - i) << 1)
            out += int(values[i]).to_bytes(vbytes, "little")
            if empty_runs:
                out += _varint(0) + bytes(vbytes)
            i = j
            continue
        m = min(8 * int(rng.integers(1, 6)), n - i)
        groups = -(-m // 8)
        acc = 0
        for k, v in enumerate(values[i:i + m]):
            acc |= int(v) << (k * width)
        out += _varint(groups << 1 | 1)
        out += acc.to_bytes(groups * width, "little")
        i += m
    return bytes(out)


def _hybrid_values(rng, n: int, width: int):
    """Repeats of 8-40 values between stretches of 1-59 random ones, every
    value under 2**width, the largest among them."""
    top = (1 << width) - 1
    parts, total = [], 0
    while total < n:
        if rng.random() < 0.4:
            parts.append(np.full(int(rng.integers(8, 41)),
                                 rng.integers(0, top, endpoint=True),
                                 np.uint64))
        else:
            parts.append(rng.integers(0, top, int(rng.integers(1, 60)),
                                      endpoint=True, dtype=np.uint64))
        total += len(parts[-1])
    out = np.concatenate(parts)[:n]
    out[n // 2] = top
    return out


def _runs_of(stream: bytes, n: int, width: int, walk: str, monkeypatch):
    """`_rle_runs` over `stream` by the native scan or the python loop."""
    from spark_rapids_tpu.io.parquet_device import _rle_runs
    from spark_rapids_tpu.native import runtime as R
    if walk == "native":
        if not R.available():
            pytest.skip("native lib not built")
    else:
        R.available()
        monkeypatch.setattr(R, "_LIB", None)
    return _rle_runs(memoryview(stream), n, width)


def _expand_host(parts):
    """The values of `_run_words`' parts, decoded on the host."""
    out = []
    for runs, width, n in parts:
        if runs is None:
            out.append(np.zeros(n, np.uint64))
            continue
        kinds, counts, values, bitoffs, packed = runs
        bits = np.unpackbits(np.asarray(packed), bitorder="little")
        for k, c, v, bo in zip(kinds, counts, values, bitoffs):
            c = int(c)
            if k == 0:
                out.append(np.full(c, int(v), np.uint64))
            else:
                sl = bits[bo:bo + c * width].reshape(c, width)
                out.append((sl.astype(np.uint64) << np.arange(
                    width, dtype=np.uint64)).sum(axis=1, dtype=np.uint64))
    return np.concatenate(out) if out else np.zeros(0, np.uint64)


class TestRunTables:
    """`_run_words` + `_unpack_runs`: a column chunk's dictionary-index or
    def-level stream, pages of any bit widths in one table, expanded by two
    stacked gathers a slot; against the values that were encoded."""

    @pytest.mark.parametrize("walk", ["native", "python"])
    @pytest.mark.parametrize("width", range(1, 33))
    def test_every_width_equals_the_encoded_values(self, width, walk,
                                                   monkeypatch):
        from spark_rapids_tpu.io import parquet_device as P
        r = np.random.default_rng(width)
        n = 3001
        want = _hybrid_values(r, n, width)
        stream = _hybrid(want, width, r, empty_runs=width % 3 == 0)
        runs = _runs_of(stream, n, width, walk, monkeypatch)
        assert set(np.asarray(runs[0]).tolist()) == {0, 1}  # both kinds
        ends, table, words = P._run_words([(runs, width, n)])
        cap = P.row_bucket(n)
        got = np.asarray(P._expand_rle_u32(ends, table, words, cap))
        assert np.array_equal(got[:n], want.astype(np.uint32))
        assert not got[n:].any()
        if width == 1:
            d = np.asarray(P._expand_def_levels(ends, table, words, cap))
            assert np.array_equal(d[:n], want == 1) and not d[n:].any()

    @pytest.mark.parametrize("layout", [
        "widths_grow_mid_chunk", "width_falls_back", "one_entry_pages",
        "empty_pages_and_empty_runs", "power_of_two_runs"])
    def test_pages_of_many_widths_share_one_table(self, layout, rng,
                                                  monkeypatch):
        """A chunk's pages as `_index_runs` hands them over: widths that
        change page by page, width-0 pages (a one-entry dictionary), pages
        and runs of no values, and a run count that needs no padding."""
        from spark_rapids_tpu.io import parquet_device as P
        spec = {"widths_grow_mid_chunk": [(3, 700), (5, 900), (9, 1200),
                                          (17, 2000), (20, 333)],
                "width_falls_back": [(12, 800), (2, 800), (32, 300)],
                "one_entry_pages": [(0, 500), (4, 64), (0, 7), (0, 1000)],
                "empty_pages_and_empty_runs": [(6, 0), (6, 600), (0, 0),
                                               (11, 431), (6, 0)],
                "power_of_two_runs": [(1, 8 * 64)]}[layout]
        parts, want = [], []
        for width, n in spec:
            if width == 0 or n == 0:
                parts.append((None, width, n))
                want.append(np.zeros(n, np.uint64))
                continue
            vals = _hybrid_values(rng, n, width)
            if layout == "power_of_two_runs":
                vals = rng.integers(0, 2, n, dtype=np.uint64)  # 64 groups
            stream = _hybrid(vals, width, rng, empty_runs=True)
            parts.append((_runs_of(stream, n, width, "native", monkeypatch),
                          width, n))
            want.append(vals)
        want = np.concatenate(want)
        assert np.array_equal(_expand_host(parts), want)
        ends, table, words = P._run_words(parts)
        runs = sum(len(p[0][0]) if p[0] is not None else int(p[2] > 0)
                   for p in parts)
        assert ends.shape[0] == P._pow2(runs) and table.shape == (3, P._pow2(
            runs))
        cap = P.row_bucket(len(want))
        got = np.asarray(P._expand_rle_u32(ends, table, words, cap))
        assert np.array_equal(got[:len(want)], want.astype(np.uint32))
        assert not got[len(want):].any()

    def test_bits_past_int32_are_refused(self):
        from spark_rapids_tpu.io import parquet_device as P
        runs = (np.ones(1, np.uint8), np.full(1, 8, np.int64),
                np.zeros(1, np.uint32), np.full(1, 2 ** 31 - 8, np.int64),
                np.zeros(8, np.uint8))
        P._run_words([(runs, 8, 8)])     # bits by offset: the bytes count
        with pytest.raises(P.DeviceDecodeUnsupported):
            P._run_words([((np.zeros(1, np.uint8), np.full(1, 2 ** 31,
                                                           np.int64),
                            np.zeros(1, np.uint32), np.zeros(1, np.int64),
                            np.zeros(1, np.uint8)), 1, 2 ** 31)])


def _scan_metrics(session):
    def find(node):
        if node.name == "TpuFileScanExec(parquet)":
            return node
        for c in node.children:
            hit = find(c)
            if hit is not None:
                return hit
        return None
    return find(session.last_plan).metrics.snapshot()


class TestAgainstPyarrow:
    """The whole decode of dictionary-coded chunks against pyarrow's: bit
    widths as dictionaries of 2 to 70,000 values give them, required and
    nullable columns with and without nulls, widths that change mid-chunk,
    and dispatch groups whose leading row groups do or do not fill their
    capacity."""

    @pytest.mark.parametrize("nulls", ["required", "no_nulls", "with_nulls"])
    @pytest.mark.parametrize("distinct", [2, 3, 100, 5000, 70000])
    def test_dictionary_widths(self, session, tmp_path, distinct, nulls):
        from spark_rapids_tpu.io import parquet_device as pd
        r = np.random.default_rng(distinct)
        rows = max(2 * distinct, 20000)
        vals = r.integers(0, distinct, rows)
        vals[:distinct] = r.permutation(distinct)
        mask = r.random(rows) < 0.1 if nulls == "with_nulls" else None
        field = pa.field("k", pa.int64(), nullable=nulls != "required")
        t = pa.table({"k": pa.array(vals * 3 - 7, mask=mask)},
                     schema=pa.schema([field]))
        path = str(tmp_path / "w.parquet")
        pq.write_table(t, path, use_dictionary=True, row_group_size=rows)
        df = session.read_parquet(path)
        got = df.collect()
        assert got.column("k").to_pylist() == \
            pq.read_table(path).column("k").to_pylist()
        pf = pd.file_supported(path, df.plan.output)
        with open(path, "rb") as f:
            works, _ = pd._host_phase(pf, f, 0, df.plan.output)
        w = works["k"]
        assert max(p.bw for p in w.chunk.pages) == \
            max((distinct - 1).bit_length(), 1)
        assert (w.defruns is not None) == (nulls == "with_nulls")
        snap = _scan_metrics(session)
        assert snap["parquetStackedGathers"] == \
            2 * (1 + (nulls == "with_nulls"))
        assert snap["parquetGathersElided"] == (nulls != "with_nulls")

    @pytest.mark.parametrize("page_size", [256, 512, 4096])
    def test_widths_change_mid_chunk(self, session, tmp_path, page_size):
        r = np.random.default_rng(page_size)
        n = 12000
        grow = np.minimum(np.arange(n), r.integers(0, n, n))
        t = pa.table({"k": pa.array(grow * 5, mask=r.random(n) < 0.05),
                      "s": pa.array(["v%d" % (g % 997) for g in grow])})
        path = str(tmp_path / "grow.parquet")
        pq.write_table(t, path, use_dictionary=True, row_group_size=n,
                       data_page_size=page_size)
        pf = pq.ParquetFile(path)
        enc = pf.metadata.row_group(0).column(0).encodings
        assert "PLAIN_DICTIONARY" in enc or "RLE_DICTIONARY" in enc
        got = session.read_parquet(path).collect()
        want = pq.read_table(path)
        for name in ("k", "s"):
            assert got.column(name).to_pylist() == \
                want.column(name).to_pylist(), name

    @staticmethod
    def _group_file(tmp_path, lead):
        """Three row groups, the first two full of their 4,096-row bucket
        or short of it; both columns hold nulls."""
        r = np.random.default_rng(4096)
        rg = 4096 if lead == "full" else 3000
        n = 2 * rg + 300
        t = pa.table({
            "k": pa.array(r.integers(0, 900, n), mask=r.random(n) < 0.1),
            "s": pa.array(["w%d" % (i % 37) for i in range(n)],
                          mask=r.random(n) < 0.1)})
        path = str(tmp_path / f"{lead}.parquet")
        pq.write_table(t, path, use_dictionary=True, row_group_size=rg)
        return path, t

    @pytest.mark.parametrize("lead", ["full", "short"])
    def test_merge_of_a_dispatch_group(self, session, tmp_path, lead):
        """Full leading row groups merge by concatenating, with no gather;
        a short one keeps the merge's gather, in stacked matrices."""
        path, t = self._group_file(tmp_path, lead)
        got = session.read_parquet(path).collect()
        for name in t.schema.names:
            assert got.column(name).to_pylist() == \
                t.column(name).to_pylist(), name
        snap = _scan_metrics(session)
        tables = 3 * 2 * 2      # row groups x columns x (def, index)
        if lead == "full":
            assert snap["parquetStackedGathers"] == 2 * tables
            assert snap["parquetGathersElided"] == 2 + 3    # k; s + lengths
        else:
            assert snap["parquetStackedGathers"] > 2 * tables
            assert snap["parquetGathersElided"] == 0

    def test_counts_come_back_with_a_cached_program(self, session,
                                                    tmp_path):
        """The counts are taken while the program is traced; a program the
        compile service hands back from its cache brings them along."""
        from spark_rapids_tpu.io import parquet_device as pd
        path, _ = self._group_file(tmp_path, "full")
        df = session.read_parquet(path)
        df.collect()
        first = _scan_metrics(session)
        pd._fused_multi_program.cache_clear()   # a fresh, untraced box
        df.collect()
        again = _scan_metrics(session)
        for name in ("parquetStackedGathers", "parquetGathersElided"):
            assert again[name] == first[name] > 0, name

    def test_full_chunks_lower_no_merge_gather(self, session, tmp_path):
        """Two full 1,048,576-row chunks: the program's gathers are two a
        run table, the dictionaries' and the null scatters' rank gathers,
        and nothing for the merge; and no loop."""
        import jax
        from spark_rapids_tpu.io import parquet_device as pd
        n = 1 << 20
        r = np.random.default_rng(38)
        t = pa.table({
            "k": pa.array(r.integers(0, 5000, 2 * n),
                          mask=r.random(2 * n) < 0.05),
            "q": pa.array(r.integers(1, 51, 2 * n).astype(np.int32))})
        path = str(tmp_path / "full.parquet")
        pq.write_table(t, path, use_dictionary=True, row_group_size=n)
        df = session.read_parquet(path)
        schema = df.plan.output
        pf = pd.file_supported(path, schema)
        with open(path, "rb") as f:
            chunks, total = pd._read_chunks(pf, f, [0, 1], schema)
        groups_sig, caps, packed, _ = pd._group_signatures(
            chunks, list(schema.names))
        arrays = pd._unpack_program(
            tuple(m for _, ms in groups_sig for m in ms))(packed)
        assert caps == [n, n] and [c[2] for c in chunks] == [n, n]
        program = pd._fused_multi_program(
            groups_sig, tuple(caps), pd.row_bucket(total), True)
        nrows = np.asarray([n, n], np.int64)
        tables = dicts = ranks = 0
        for colsigs, _ in groups_sig:
            for cs in colsigs:
                has_def, ndict = cs[4], cs[7]
                tables += has_def + (ndict > 0)
                dicts += ndict > 0
                ranks += has_def
        assert (tables, dicts, ranks) == (6, 4, 2)  # `q`: no def table

        def gathers(jaxpr):
            found = 0
            for eqn in jaxpr.eqns:
                found += eqn.primitive.name == "gather"
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    found += gathers(sub)
            return found
        jaxpr = jax.make_jaxpr(program.direct)(nrows, *arrays)
        assert gathers(jaxpr.jaxpr) == 2 * tables + dicts + ranks
        assert program.msgs_box == [2 * tables, 2 + 2 + 2]   # merge; `q`
        text = program.direct.lower(nrows, *arrays).as_text()
        assert "stablehlo.while" not in text
