"""The readers of the engine's own names (programs under their op tag, host
spans under the engine's prefix) and of its always-on counters, each on a
hand-built trace or ctx."""
import pytest
import run as R
import trace_reduce as T

from spark_rapids_tpu.compile.service import CompileService, program_name
from spark_rapids_tpu.plugin import TpuSession
from spark_rapids_tpu.utils import metrics
from spark_rapids_tpu.utils.tracing import SPAN_PREFIX

MS = 1_000_000
DECODE, EXPAND = "io.parquet.fused_multi_decode", "exec.join.expand"


def ev(name, start_ms, dur_ms):
    return (name, start_ms * MS, dur_ms * MS)


def srt(name, start_ms, dur_ms):
    return ev(SPAN_PREFIX + name, start_ms, dur_ms)


def module(op, fingerprint, start_ms, dur_ms):
    return ev(f"jit_{program_name(op)}({fingerprint})", start_ms, dur_ms)


def reader(name):
    return R.load_module(R.HERE, "layer_metrics", name)


@pytest.fixture
def service():
    """The compile service as a process has it after running both tags."""
    CompileService.reset()
    for op in (DECODE, EXPAND):
        CompileService.get().stats.bump(op, hits=1)
    yield CompileService.get()
    CompileService.reset()


# The query is 0-100 ms. The chip runs the decode 10-40 (an operation and a
# `while` nested in it), the expand 50-70, and an eager gather 80-85; it is
# idle 0-10 (plan rewrite, then the chunk walk), 40-50 (under `scan.h2d`),
# 70-80 (under a Python frame only) and 85-100 (the sink).
OPS = [ev("%fusion.1", 10, 30), ev("%while.2", 15, 10), ev("%sort.3", 50, 20),
       ev("%gather.4", 80, 5)]
MODULES = [module(DECODE, 71, 10, 31), module(EXPAND, 82, 50, 20),
           ev("jit_gather(93)", 80, 5)]
HOST = [ev(T.MARK, 0, 100), srt("plan.rewrite", 0, 2),
        srt("op.TpuFileScanExec(parquet)", 2, 48), srt("scan.walk", 3, 7),
        srt("scan.h2d", 41, 8), ev("$indexing.py:1174 rewriting_take", 70, 10),
        srt("sink.d2h", 86, 4), srt("sink.rows", 92, 8)]


def ctx_of(ops, modules, host, chips=1):
    return {"cell": {"chips": chips}, "window": [],
            "trace": {"events": {
                "device_ops": {d: list(v) for d, v in ops.items()},
                "device_modules": {d: list(v) for d, v in modules.items()},
                "host_events": list(host)}}}


@pytest.fixture
def ctx(service):
    return ctx_of({"/device:TPU:0": OPS}, {"/device:TPU:0": MODULES}, HOST)


def test_device_seconds_by_the_programs_op_tags(ctx):
    # the decode's nested `while` is inside its fusion's interval: a union
    assert reader("scan_device_s").read(ctx) == pytest.approx(0.030)
    assert reader("join_device_s").read(ctx) == pytest.approx(0.020)
    assert reader("untagged_device_s").read(ctx) == pytest.approx(0.005)
    assert reader("device_programs_per_query").read(ctx) == 3
    busy = T.reduce_events(ctx["trace"]["events"]["device_ops"], HOST)
    assert busy["busy_s"] == pytest.approx(0.055)


def test_a_tag_the_service_never_ran_is_untagged(ctx):
    ctx["trace"]["events"]["device_modules"]["/device:TPU:0"].append(
        module("exec.not.a.tag", 5, 90, 2))
    ctx["trace"]["events"]["device_ops"]["/device:TPU:0"].append(
        ev("%copy.9", 90, 2))
    assert reader("untagged_device_s").read(ctx) == pytest.approx(0.007)
    assert reader("device_programs_per_query").read(ctx) == 4


def test_an_operation_inside_no_program_is_untagged(ctx):
    ctx["trace"]["events"]["device_ops"]["/device:TPU:0"].append(
        ev("%copy.9", 45, 1))
    assert reader("untagged_device_s").read(ctx) == pytest.approx(0.006)


def test_idle_under_the_scans_spans_and_under_any_engine_span(ctx):
    # 3-10 under scan.walk and 41-49 under scan.h2d
    assert reader("scan_host_idle_s").read(ctx) == pytest.approx(0.015)
    # idle 45 ms: covered 0-10 (rewrite, the scan's pull), 40-50 (the pull),
    # 86-90 and 92-100 (the sink): 32 ms
    assert reader("idle_attributed_pct").read(ctx) == pytest.approx(
        100 * 32 / 45)


def test_the_root_annotation_attributes_nothing(service):
    host = [ev(T.MARK, 0, 100), ev("$frame", 0, 100)]
    ctx = ctx_of({"/device:TPU:0": OPS}, {"/device:TPU:0": MODULES}, host)
    assert reader("idle_attributed_pct").read(ctx) == 0.0
    assert reader("scan_host_idle_s").read(ctx) == 0.0


def test_everything_is_clipped_to_the_traced_query(service):
    ops = OPS + [ev("%fusion.1", -40, 30), ev("%sort.3", 95, 30)]
    modules = MODULES + [module(DECODE, 71, -40, 30),
                         module(EXPAND, 82, 95, 30)]
    host = HOST + [srt("scan.walk", -30, 20)]
    ctx = ctx_of({"/device:TPU:0": ops}, {"/device:TPU:0": modules}, host)
    assert reader("scan_device_s").read(ctx) == pytest.approx(0.030)
    assert reader("join_device_s").read(ctx) == pytest.approx(0.025)
    assert reader("device_programs_per_query").read(ctx) == 4
    assert reader("scan_host_idle_s").read(ctx) == pytest.approx(0.015)


def test_a_cell_of_one_chip_on_a_host_of_four_reads_its_own_chip(service):
    ops = {"/device:TPU:0": OPS, "/device:TPU:1": [ev("%copy.9", 5, 1)],
           "/device:TPU:2": [], "/device:TPU:3": []}
    modules = {"/device:TPU:0": MODULES,
               "/device:TPU:1": [ev("jit_copy(1)", 5, 1)]}
    ctx = ctx_of(ops, modules, HOST, chips=1)
    assert reader("scan_device_s").read(ctx) == pytest.approx(0.030)
    assert reader("untagged_device_s").read(ctx) == pytest.approx(0.005)
    assert reader("device_programs_per_query").read(ctx) == 3
    assert reader("scan_host_idle_s").read(ctx) == pytest.approx(0.015)
    both = ctx_of(ops, modules, HOST, chips=2)  # averaged over the two used
    assert reader("scan_device_s").read(both) == pytest.approx(0.015)
    assert reader("untagged_device_s").read(both) == pytest.approx(0.003)
    assert reader("device_programs_per_query").read(both) == 2


@pytest.mark.parametrize("name", [
    "scan_device_s", "join_device_s", "untagged_device_s",
    "device_programs_per_query", "scan_host_idle_s", "idle_attributed_pct"])
def test_without_a_trace_a_trace_reader_reports_nothing(name):
    assert reader(name).read({"trace": None, "cell": {"chips": 1}}) is None


@pytest.mark.parametrize("name", [
    "scan_device_s", "join_device_s", "untagged_device_s",
    "scan_host_idle_s", "idle_attributed_pct", "compile_trace_lower_s",
    "compile_backend_s", "slowest_query_device_wait_s"])
def test_on_an_engine_without_the_names_a_reader_reports_nothing(
        ctx, monkeypatch, name):
    """The parent commit: no `program_name`, no prefix, no stage counters,
    no ring. The reader returns None and does not raise."""
    from spark_rapids_tpu.compile import service
    from spark_rapids_tpu.utils import tracing
    monkeypatch.delattr(service, "program_name")
    monkeypatch.delattr(tracing, "SPAN_PREFIX")
    monkeypatch.delattr(TpuSession, "recent_queries")
    monkeypatch.setattr(service.CompileStats, "_FIELDS",
                        service.CompileStats._FIELDS[:9])
    CompileService.reset()
    CompileService.get().stats.bump(DECODE, hits=1)
    ctx["window"] = [{"seconds": 1.0}]
    assert reader(name).read(ctx) is None


def test_a_trace_without_the_mark_is_refused(service):
    ctx = ctx_of({"/device:TPU:0": OPS}, {"/device:TPU:0": MODULES},
                 [e for e in HOST if e[0] != T.MARK])
    with pytest.raises(ValueError, match="bench.collect"):
        reader("scan_device_s").read(ctx)


def test_the_compile_stages_come_from_the_services_totals(service):
    service.stats.bump(DECODE, compiles=1, compile_ns=70 * 10**9,
                       trace_ns=9 * 10**9, lower_ns=3 * 10**9,
                       backend_ns=57 * 10**9)
    service.stats.bump(EXPAND, compiles=1, compile_ns=10**9,
                       trace_ns=10**8, lower_ns=10**8, backend_ns=7 * 10**8)
    assert reader("compile_trace_lower_s").read({}) == pytest.approx(12.2)
    assert reader("compile_backend_s").read({}) == pytest.approx(57.7)


def test_the_slowest_query_of_the_window_is_read_from_the_ring():
    def tm(sync_s, d2h_s):
        return {"host_sync_ns": int(sync_s * 1e9), "d2h_ns": int(d2h_s * 1e9)}
    # set-up's queries, slower than any of the window, then the window's
    for wall, waits in [(80.0, tm(9, 1)), (5.9, tm(5.4, 0.1)),
                        (5.8, tm(5.5, 0.1)), (8.0, tm(5.45, 0.1)),
                        (5.8, tm(5.5, 0.1))]:
        metrics.note_query(wall, "TpuProjectExec", waits)
    read = reader("slowest_query_device_wait_s").read
    # the stalled query waited on the chip no longer than the others did
    assert read({"window": [{}] * 3}) == pytest.approx(5.55)
    assert read({"window": [{}] * 5}) == pytest.approx(10.0)
    assert read({"window": []}) is None
