"""TPC-H query 1 over `lineitem` stored as ORC (the benchmark's
`lineitem.q1_orc`) at a small size on the CPU backend, and the ORC decode's
own lines: the engine's answer against the query file's plain reference and
against the parquet cell's answer on the same draw; the varint fold at the
limits of its type; RLEv2 run tables of all four sub-encodings against the
host mirror, with one program for every table of a bucket; one set of
programs under every seed; the programs' names and dispatch counts; the
host-decode counter; the scan's spans."""

import decimal
import glob
import importlib.util
import json
import os

import jax
import numpy as np
import pyarrow as pa
import pytest
from pyarrow import orc

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import Schema, batch_to_arrow
from spark_rapids_tpu.compile.service import CompileService, program_name
from spark_rapids_tpu.io import orc_device as O
from spark_rapids_tpu.plugin import TpuSession
from spark_rapids_tpu.utils.metrics import TaskMetrics
from spark_rapids_tpu.utils.tracing import SPAN_PREFIX

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
ROWS = 60_000
D = decimal.Decimal


def _load(kind, name):
    spec = importlib.util.spec_from_file_location(
        f"q1orc_test_{name}", os.path.join(BENCH, kind, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        config = json.load(f)
    config["tables"]["lineitem"]["rows"] = ROWS
    return config


def _write(config, where, seed):
    tables = _load("generators", config["generator"]).write(
        str(where), seed, config, ["lineitem"])
    return {k: v["path"] for k, v in tables.items()}


@pytest.fixture(scope="module")
def q1(tmp_path_factory):
    """(ORC query module, ORC paths, reference, the engine's answer, its
    session) on seeded data of ROWS rows."""
    q = _load("queries", "q1_pricing_summary_orc")
    paths = _write(_config("tpch-sf10-lineitem-orc"),
                   tmp_path_factory.mktemp("q1orc"), 2_147_483_659)
    session = TpuSession({})
    # `compare` reads the process's ring of recent queries: what an earlier
    # test file's host-decoded scans left there is not this query's
    from spark_rapids_tpu.utils import metrics
    with metrics._recent_mu:
        metrics._recent.clear()
    got = q.build(session, paths).collect()
    return q, paths, q.reference(paths), got, session


def _names(node):
    return [node.name] + [n for c in node.children for n in _names(c)]


def _scan(node):
    if node.name.startswith("TpuFileScanExec"):
        return node
    for c in node.children:
        hit = _scan(c)
        if hit is not None:
            return hit
    return None


# -- the query ---------------------------------------------------------------

def test_the_engine_equals_the_reference_in_every_digit(q1):
    q, paths, want, got, session = q1
    names = _names(session.last_plan)
    assert names == ["TpuSortExec", "TpuHashAggregateExec", "TpuProjectExec",
                     "TpuFilterExec", "TpuFileScanExec(orc)"]
    assert want.num_rows == 4
    assert q.compare(got, want) == {"rows_off": 0, "sums_off": 0,
                                    "host_decoded": 0}
    assert got.schema.field("sum_charge").type == pa.decimal128(38, 6)
    assert got.schema.field("avg_disc").type == pa.decimal128(16, 6)


def test_the_answer_is_the_parquet_cells_on_the_same_draw(q1, tmp_path):
    q, _, _, got, _ = q1
    pq_paths = _write(_config("tpch-sf10-lineitem"), tmp_path,
                      2_147_483_659)
    pq_q = _load("queries", "q1_pricing_summary")
    theirs = pq_q.build(TpuSession({}), pq_paths).collect()
    assert pq_q.compare(got, theirs) == {"rows_off": 0, "sums_off": 0}
    assert pq_q.compare(got, pq_q.reference(pq_paths)) == {
        "rows_off": 0, "sums_off": 0}


def test_one_unit_of_the_last_place_is_read(q1):
    q, _, want, got, _ = q1
    i = got.schema.get_field_index("sum_charge")
    col = got.column(i).to_pylist()
    col[2] += D("0.000001")
    altered = got.set_column(i, got.schema[i],
                             pa.array(col, got.schema[i].type))
    read = q.compare(altered, want)
    assert (read["rows_off"], read["sums_off"]) == (0, 1)
    assert q.compare(got.take([0, 2, 3]), want)["rows_off"] == 3


def test_the_scan_counts_what_it_walked_and_decoded_nothing_on_the_host(q1):
    _, paths, _, _, session = q1
    snap = _scan(session.last_plan).metrics.snapshot()
    assert snap["hostDecodedUnits"] == 0 and snap["stripesHostDecoded"] == 0
    assert snap["colsHostDecoded"] == 0
    assert snap["orcRunsWalked"] > 1000
    # the four decimals' DATA streams, byte for byte
    footer = _load("queries", "q1_pricing_summary_orc").column_streams(
        paths["lineitem"])
    assert 0 < snap["orcVarintBytes"] <= sum(
        footer[c]["streams"] for c in ("l_quantity", "l_extendedprice",
                                       "l_discount", "l_tax"))
    assert snap["readTime"] > 0


# -- programs, seeds, names --------------------------------------------------

def _plans(path):
    """The host phase's (signature, shapes) of every column of the file's
    first stripe, as `decode_stripe` keys its programs."""
    f = orc.ORCFile(path)
    schema = Schema.from_arrow(f.schema)
    info, bad = O.columns_supported(path, schema)
    assert not bad
    st = info.stripes[0]
    cap = O.row_bucket(st.num_rows)
    out = {}
    with open(path, "rb") as fh:
        directory = O._stripe_footer(info, fh, st)
        for name, dt in zip(schema.names, schema.types):
            cid = info.col_ids[name]
            plan = O._column_plan(
                O._column_streams(info, fh, st, directory, cid),
                info.col_kinds[cid], dt, st.num_rows, cap, directory[2],
                O._ScanStats())
            out[name] = (plan.sig, tuple((a.shape, str(a.dtype))
                                         for a in plan.arrays))
    return out


def test_two_seeds_share_every_program_and_the_second_compiles_nothing(
        q1, tmp_path):
    q, paths, _, got, session = q1
    other = _write(_config("tpch-sf10-lineitem-orc"), tmp_path, 77)
    assert _plans(paths["lineitem"]) == _plans(other["lineitem"])
    second = q.build(session, other).collect()
    assert TaskMetrics.get().compile_count == 0
    # and every sum moved with the seed
    assert _load("queries", "q1_pricing_summary").compare(
        second, got)["sums_off"] >= 20
    assert q.compare(second, q.reference(other))["sums_off"] == 0


def test_the_scans_programs_are_service_programs_named_io_orc(q1):
    q, paths, _, _, session = q1
    q.scans(session, paths)["lineitem"].collect()
    # seven columns, a program each, and nothing else in a bare scan
    assert TaskMetrics.get().device_dispatches == 7
    ops = CompileService.get().stats.per_op()
    assert {"io.orc.decimal", "io.orc.int", "io.orc.string_dict"} <= set(ops)
    service = CompileService.get()
    named = [e for e in service._mem.values() if e.op.startswith("io.orc.")]
    assert named
    for entry in named:
        if entry.source == "compile":
            assert f"HloModule jit_{program_name(entry.op)}," in \
                entry.compiled.as_text()


def test_the_orc_scan_opens_the_scans_spans(q1, tmp_path):
    q, paths, _, _, session = q1
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    df = q.scans(session, paths)["lineitem"]
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        df.collect()
    finally:
        jax.profiler.stop_trace()
    tm = TaskMetrics.get()
    found, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    seen = {}
    for plane in ProfileData.from_file(found).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        name = e.name[len(SPAN_PREFIX):]
                        seen[name] = seen.get(name, 0) + 1
    assert {"scan.walk", "scan.pack", "scan.h2d", "scan:orc",
            "op.TpuFileScanExec(orc)"} <= set(seen), sorted(seen)
    # the stripe's footer and its seven columns; one transfer a column
    assert (seen["scan.walk"], seen["scan.h2d"]) == (8, 7)
    dispatched = {n: c for n, c in seen.items()
                  if n.startswith("dispatch.io.orc.")}
    assert sum(dispatched.values()) == 7 == tm.device_dispatches
    assert tm.h2d_bytes > 0 and tm.h2d_ns > 0


# -- the host-decode counter -------------------------------------------------

def test_a_zstd_file_counts_one_host_decoded_unit(tmp_path):
    rng = np.random.default_rng(5)
    t = pa.table({"a": rng.integers(0, 100, 3000),
                  "s": pa.array([f"v{i % 7}" for i in range(3000)])})
    path = str(tmp_path / "z.orc")
    orc.write_table(t, path, compression="zstd")
    session = TpuSession({})
    got = session.read_orc(path).collect()
    assert got.equals(t)
    assert TaskMetrics.get().scan_host_decoded == 1
    assert _scan(session.last_plan).metrics.snapshot()[
        "hostDecodedUnits"] == 1
    assert TpuSession.recent_queries()[-1][2]["scan_host_decoded"] == 1


def test_a_stripe_the_device_declines_counts_one_unit(tmp_path):
    """File version 0.11 writes RLEv1 integer streams: the footer sweep
    passes, the stripe's decode declines, pyarrow reads the stripe."""
    rng = np.random.default_rng(6)
    t = pa.table({"a": rng.integers(0, 100, 3000)})
    path = str(tmp_path / "v0.orc")
    orc.write_table(t, path, file_version="0.11")
    session = TpuSession({})
    assert session.read_orc(path).collect().equals(t)
    assert TaskMetrics.get().scan_host_decoded == 1
    snap = _scan(session.last_plan).metrics.snapshot()
    assert (snap["colsHostDecoded"], snap["stripesHostDecoded"],
            snap["hostDecodedUnits"]) == (0, 1, 1)


def test_a_host_decoded_column_counts_once_a_stripe(tmp_path, monkeypatch):
    rng = np.random.default_rng(6)
    n = 40_000
    t = pa.table({"a": rng.integers(0, 1 << 40, n),
                  "b": rng.integers(0, 100, n)})
    path = str(tmp_path / "two.orc")
    orc.write_table(t, path, stripe_size=64 * 1024)
    stripes = orc.ORCFile(path).nstripes
    assert stripes >= 2
    real = O.columns_supported

    def one_column_to_the_host(p, schema):
        info, bad = real(p, schema)
        return info, {**bad, "b": "sent to the host by the test"}
    monkeypatch.setattr(O, "columns_supported", one_column_to_the_host)
    session = TpuSession({})
    assert session.read_orc(path).collect().equals(t)
    assert TaskMetrics.get().scan_host_decoded == stripes
    snap = _scan(session.last_plan).metrics.snapshot()
    assert (snap["colsHostDecoded"], snap["stripesHostDecoded"],
            snap["hostDecodedUnits"]) == (1, 0, stripes)


# -- the walk of a stripe's columns ------------------------------------------

@pytest.mark.parametrize("pipelined", [True, False])
def test_the_columns_are_walked_side_by_side_where_execution_is_pipelined(
        q1, monkeypatch, pipelined):
    """Pipelined (the default), every column's host phase runs on one of the
    process's few walkers; off, on the thread that drives the scan. The
    batch is the same either way."""
    import threading
    q, paths, _, _, _ = q1
    real, where = O._column_plan, []

    def seen(*args, **kw):
        where.append(threading.current_thread().name)
        return real(*args, **kw)
    monkeypatch.setattr(O, "_column_plan", seen)
    session = TpuSession({"spark.rapids.tpu.pipeline.enabled": pipelined})
    got = q.scans(session, paths)["lineitem"].collect()
    assert len(where) == 7
    assert all(n.startswith("srtpu-walk") == pipelined for n in where)
    assert TaskMetrics.get().scan_host_decoded == 0
    want = orc.read_table(paths["lineitem"], columns=got.column_names)
    assert got.cast(want.schema).equals(want)


def test_a_column_the_walk_declines_ends_the_stripe_and_every_walk(
        q1, monkeypatch):
    """One column's walk raises on its walker: the stripe goes to the
    host's reader, counted, and no walk is left running behind it."""
    q, paths, _, _, _ = q1
    real, running = O._column_plan, []

    def declines_a_date(cs, kind, *args, **kw):
        running.append(1)
        try:
            if kind == O._K_DATE:
                raise O.DeviceDecodeUnsupported("declined by the test")
            return real(cs, kind, *args, **kw)
        finally:
            running.pop()
    monkeypatch.setattr(O, "_column_plan", declines_a_date)
    session = TpuSession({})
    got = q.scans(session, paths)["lineitem"].collect()
    assert not running
    snap = _scan(session.last_plan).metrics.snapshot()
    assert (snap["stripesHostDecoded"], snap["hostDecodedUnits"]) == (1, 1)
    want = orc.read_table(paths["lineitem"], columns=got.column_names)
    assert got.cast(want.schema).equals(want)


# -- the varint fold ---------------------------------------------------------

def _zz(v: int) -> int:
    return (v << 1) ^ (v >> 63)


def _uvarint(u: int) -> bytes:
    out = bytearray()
    while u >= 128:
        out.append((u & 0x7F) | 0x80)
        u >>= 7
    out.append(u)
    return bytes(out)


def _zigzag_varint(v: int) -> bytes:
    return _uvarint(_zz(v))


def _fold(stream: bytes, cap: int = 128):
    raw = np.frombuffer(stream, np.uint8)
    words = O._padded(raw, O._bucket(raw.size, 128)).view("<u4")
    return np.asarray(jax.jit(lambda w: O._varint_zigzag(w, cap))(words))


# zigzag values on both sides of every byte-count boundary up to nine bytes
_EDGES = [0, 1, -1, 63, -64, 64, -65, 8191, -8192, 8192, -8193,
          (1 << 20) - 1, -(1 << 20), 1 << 20, (1 << 27) - 1, -(1 << 27),
          1 << 27, (1 << 34) - 1, 1 << 34, (1 << 41) - 1, 1 << 41,
          (1 << 48) - 1, 1 << 48, (1 << 55) - 1, -(1 << 55), 1 << 55,
          -(1 << 55) - 1, 10 ** 18 - 1, -(10 ** 18 - 1)]


@pytest.mark.parametrize("value", _EDGES, ids=[str(v) for v in _EDGES])
def test_a_varint_at_a_limit_folds_exactly(value):
    enc = _zigzag_varint(value)
    assert 1 <= len(enc) <= 9
    assert int(_fold(enc)[0]) == value


def test_a_stream_of_mixed_lengths_folds_exactly():
    rng = np.random.default_rng(7)
    bits = rng.integers(0, 60, 100_000)
    vals = [int(rng.integers(0, 1 << 30)) % (1 << int(b) or 1) *
            (1 if i % 3 else -1) for i, b in enumerate(bits)]
    vals[:len(_EDGES)] = _EDGES
    stream = b"".join(_zigzag_varint(v) for v in vals)
    got = _fold(stream, cap=131_072)
    assert got[:len(vals)].tolist() == vals


def _scale_stream(n: int, scale: int) -> bytes:
    """RLEv2 SHORT_REPEAT runs of the zigzagged scale, ten values a run."""
    out = bytearray()
    while n > 0:
        c = min(max(n, 3), 10)
        out += bytes([c - 3, scale << 1])
        n -= c
    return bytes(out)


def test_the_decimal_stream_checks_hold_the_line():
    dt = T.DecimalType(18, 2)
    vals = [5, -7, 10 ** 18 - 1]

    def streams(data, scale=2):
        return O._ColStreams(O._E_DIRECT_V2, 0, {
            O._S_DATA: data, O._S_SECONDARY: _scale_stream(3, scale)})
    data = b"".join(_zigzag_varint(v) for v in vals)
    stats = O._ScanStats()
    words = O._decimal_stream(streams(data), dt, 3, stats)
    assert stats.varint_bytes == len(data) and words.dtype == np.uint32
    with pytest.raises(O.DeviceDecodeUnsupported, match="inside a value"):
        O._decimal_stream(streams(data + b"\x80"), dt, 3, O._ScanStats())
    with pytest.raises(O.DeviceDecodeUnsupported, match="short"):
        O._decimal_stream(streams(data[:2]), dt, 3, O._ScanStats())
    with pytest.raises(O.DeviceDecodeUnsupported, match="wider than 64"):
        O._decimal_stream(streams(b"\x80" * 9 + b"\x01\x01\x01"), dt, 3,
                          O._ScanStats())
    with pytest.raises(O.DeviceDecodeUnsupported, match="rescale"):
        O._decimal_stream(streams(data, scale=3), dt, 3, O._ScanStats())


def test_decimals_at_their_limits_come_back_from_a_file(tmp_path):
    edge = [D(v).scaleb(-2) for v in _EDGES]
    t = pa.table({"d": pa.array(edge * 40 + [None] * 7,
                                pa.decimal128(18, 2))})
    path = str(tmp_path / "d.orc")
    orc.write_table(t, path)
    schema = Schema.from_arrow(orc.ORCFile(path).schema)
    info = O.file_supported(path, schema)
    got = pa.concat_tables(
        [batch_to_arrow(b) for b, _ in O.device_decode_file(info, path,
                                                            schema)])
    assert got.column("d").to_pylist() == t.column("d").to_pylist()


# -- RLEv2 run tables --------------------------------------------------------

def _short_repeat(u: int, count: int) -> bytes:
    nbytes = max((u.bit_length() + 7) // 8, 1)
    return bytes([(nbytes - 1) << 3 | (count - 3)]) + u.to_bytes(nbytes,
                                                                "big")


def _direct(us, width: int) -> bytes:
    code = {w: w - 1 for w in range(1, 25)} | {32: 27, 64: 31}
    n = len(us) - 1
    return bytes([0x40 | code[width] << 1 | n >> 8, n & 0xFF]) + \
        O._pack_be(np.array(us, np.uint64), width)


def _fixed_delta(base_u: int, delta: int, count: int) -> bytes:
    n = count - 1
    return bytes([0xC0 | n >> 8, n & 0xFF]) + _uvarint(base_u) + \
        _zigzag_varint(delta)


def _written_runs(tmp_path, values, name):
    """The DATA stream pyarrow's writer makes of an int64 column."""
    path = str(tmp_path / f"{name}.orc")
    orc.write_table(pa.table({"v": pa.array(values, pa.int64())}), path)
    schema = Schema.from_arrow(orc.ORCFile(path).schema)
    info = O.file_supported(path, schema)
    with open(path, "rb") as fh:
        st = info.stripes[0]
        return O._column_streams(info, fh, st, O._stripe_footer(
            info, fh, st), info.col_ids["v"]).streams[O._S_DATA]


def _table(tmp_path, rng, repeats: int):
    """A signed stream of all four sub-encodings, `repeats` hand-made
    SHORT_REPEAT / DIRECT / fixed DELTA triples before the writer's own
    PATCHED_BASE and literal-DELTA runs, and the values it stands for."""
    stream, want = bytearray(), []
    for i in range(repeats):
        v = int(rng.integers(-1000, 1000))
        stream += _short_repeat(_zz(v), 3 + i % 8)
        want += [v] * (3 + i % 8)
        width = (1, 3, 7, 12, 16, 24, 32, 64)[i % 8 if i % 64 == 63
                                              else i % 7]
        hi = 1 << (width - 1)
        vs = [int(x) for x in rng.integers(-hi // 2, hi // 2 or 1,
                                           2 + i % 5)]
        stream += _direct([_zz(x) for x in vs], width)
        want += vs
        b, d, c = int(rng.integers(-50, 50)), int(rng.integers(-3, 4)), \
            2 + i % 9
        stream += _fixed_delta(_zz(b), d, c)
        want += [b + d * k for k in range(c)]
    outliers = np.where(rng.random(600) < 0.02, 1 << 40,
                        rng.integers(0, 100, 600))
    walk = np.cumsum(rng.integers(0, 1000, 600))
    for vals, name in ((outliers, "patched"), (walk, "walk")):
        stream += _written_runs(tmp_path, vals, f"{name}{repeats}")
        want += [int(x) for x in vals]
    return bytes(stream), want


def test_four_sub_encodings_expand_as_the_host_mirror_and_share_a_bucket(
        tmp_path):
    rng = np.random.default_rng(11)
    CompileService.reset()
    cap = 8192
    seen = set()
    # 3 x 300 and 3 x 330 runs (+ the writer's) lie in the 1,024 bucket,
    # 3 x 360 past its edge in the 2,048 one
    for repeats, bucket in ((300, 1024), (330, 1024), (360, 2048)):
        stream, want = _table(tmp_path, rng, repeats)
        rt = O._rlev2_runs(stream, len(want), True)
        kinds = set(np.asarray(rt.kinds).tolist())
        assert kinds == {0, 1, 2, 3}
        seen.add(len(rt.kinds))
        host = O._expand_runs_host(rt, len(want), True)
        assert host.tolist() == want
        ends, table, words, wide = rt.device_arrays(True, cap)
        assert ends.shape == (bucket,) and table.shape == (7, bucket)
        assert wide
        program = O._column_program(("int", False, wide, "int64"), cap)
        data, valid, _ = program(np.int32(len(want)), ends, table, words)
        assert np.asarray(data)[:len(want)].tolist() == want
        assert np.asarray(valid).sum() == len(want)
    assert len(seen) == 3
    stats = CompileService.get().stats.per_op()["io.orc.int"]
    assert stats["compiles"] == 2 and stats["hits"] >= 1
    CompileService.reset()


def test_the_native_walk_is_the_python_walk(tmp_path, monkeypatch):
    from spark_rapids_tpu.native import runtime as native
    if not native.available():
        pytest.skip("native runtime not built")
    rng = np.random.default_rng(12)
    stream, want = _table(tmp_path, rng, 200)
    fast = O._rlev2_runs(stream, len(want), True)
    with monkeypatch.context() as m:
        m.setattr(native, "orc_rlev2_scan", lambda *a: None)
        slow = O._rlev2_runs(stream, len(want), True)
    for a, b in zip(fast.arrays(), slow.arrays()):
        assert np.array_equal(a, b)
    for a, b in zip(fast.device_arrays(True, 4096),
                    slow.device_arrays(True, 4096)):
        assert np.array_equal(a, b)
    with pytest.raises(O.DeviceDecodeUnsupported):
        O._rlev2_runs(stream[:-3], len(want), True)
    with pytest.raises(O.DeviceDecodeUnsupported, match="short"):
        O._rlev2_runs(stream, len(want) + 1, True)
