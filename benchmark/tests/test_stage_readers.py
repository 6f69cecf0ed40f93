"""The readers of the engine's stage spans and stage counters, on a
hand-built trace and ring: idle time put down to the innermost engine span
over it, by stage, adding up to the query's idle time."""
import random

import pytest
import run as R
import trace_reduce as T

from spark_rapids_tpu.utils import metrics
from spark_rapids_tpu.utils.tracing import SPAN_PREFIX, stage_of

MS = 1_000_000
READERS = ("scan_walk_s", "scan_pack_s", "dispatch_host_s",
           "idle_dispatch_s", "idle_unstaged_s",
           "slowest_query_stage_excess_s")


def ev(name, start_ms, dur_ms):
    return (name, start_ms * MS, dur_ms * MS)


def srt(name, start_ms, dur_ms):
    return ev(SPAN_PREFIX + name, start_ms, dur_ms)


def reader(name):
    return R.load_module(R.HERE, "layer_metrics", name)


STAGES_MOD = reader("_stages")

# The query is 0-100 ms; the chip runs 10-40, 50-70 and 80-85, so it is idle
# 0-10, 40-50, 70-80 and 85-100 (45 ms). Spans nest as the engine opens
# them: a scan's pull holds its walk and a dispatch; a join's pull holds the
# expand's dispatch, a traced Python frame beside it.
OPS = [ev("%fusion.1", 10, 30), ev("%sort.3", 50, 20), ev("%gather.4", 80, 5)]
HOST = [ev(T.MARK, 0, 100), srt("plan.rewrite", 0, 2),
        srt("op.TpuFileScanExec(parquet)", 2, 48), srt("scan.walk", 3, 4),
        srt("dispatch.io.parquet.fused_multi_decode", 7, 4),
        srt("scan.h2d", 41, 8),
        srt("op.TpuBroadcastHashJoinExec", 65, 15),
        srt("dispatch.exec.join.expand", 72, 4),
        ev("$indexing.py:1174 rewriting_take", 70, 10),
        srt("sink.d2h", 86, 4), srt("sink.rows", 92, 8)]
SPLIT_MS = {"plan.rewrite": 2, "scan.walk": 4, "dispatch.*": 7,
            "scan.h2d": 8, "sink.d2h": 4, "unstaged": 20}


def ctx_of(host, ops=OPS):
    return {"cell": {"chips": 1}, "window": [],
            "trace": {"events": {"device_ops": {"/device:TPU:0": list(ops)},
                                 "device_modules": {},
                                 "host_events": list(host)}}}


def test_idle_is_split_by_the_innermost_stage():
    split = STAGES_MOD.idle_by_stage(ctx_of(HOST))
    assert split == {k: v * MS for k, v in SPLIT_MS.items()}
    assert reader("idle_dispatch_s").read(ctx_of(HOST)) == \
        pytest.approx(0.007)
    assert reader("idle_unstaged_s").read(ctx_of(HOST)) == \
        pytest.approx(0.020)


def test_the_stages_and_the_unstaged_add_up_to_the_idle_time():
    red = T.reduce_events({"/device:TPU:0": OPS}, HOST)
    idle_ns = round((red["window_s"] - red["busy_s"]) * 1e9)
    assert sum(STAGES_MOD.idle_by_stage(ctx_of(HOST)).values()) == idle_ns


def test_a_gap_under_a_pull_and_a_dispatch_goes_to_the_dispatch():
    host = [ev(T.MARK, 0, 100), srt("op.TpuSortExec", 0, 100),
            srt("dispatch.exec.sort", 42, 6)]
    split = STAGES_MOD.idle_by_stage(ctx_of(host))
    assert split["dispatch.*"] == 6 * MS
    assert split["unstaged"] == 39 * MS


def test_a_gap_under_a_pull_alone_is_unstaged():
    host = [ev(T.MARK, 0, 100), srt("op.TpuSortExec", 0, 100)]
    assert STAGES_MOD.idle_by_stage(ctx_of(host)) == {"unstaged": 45 * MS}


def test_the_benchmarks_own_annotation_counts_for_nothing():
    # `bench.collect` is the shortest event over 85-100 and has no prefix
    host = [ev(T.MARK, 0, 100), srt("sink.d2h", 0, 200)]
    split = STAGES_MOD.idle_by_stage(ctx_of(host))
    assert split == {"sink.d2h": 45 * MS}


def test_a_short_span_on_another_thread_outranks_a_long_one():
    host = [ev(T.MARK, 0, 100), srt("scan.walk", 40, 10),
            srt("sync.row_count", 44, 8)]
    split = STAGES_MOD.idle_by_stage(ctx_of(host))
    assert split["sync.row_count"] == 6 * MS
    assert split["scan.walk"] == 4 * MS


@pytest.mark.parametrize("seed", range(5))
def test_the_sweep_is_the_innermost_rule_nanosecond_by_nanosecond(seed):
    rng = random.Random(seed)
    names = ["op.A", "scan.walk", "dispatch.x", "sink.d2h", "pipeline:p"]
    host = [(SPAN_PREFIX + rng.choice(names), s, rng.randrange(1, 60))
            for s in (rng.randrange(0, 200) for _ in range(40))]
    ops = [("%op", s, rng.randrange(1, 15))
           for s in (rng.randrange(0, 200) for _ in range(12))]
    gaps = T.gaps(ops, 0, 200)
    got = STAGES_MOD.attribute(gaps, host, SPAN_PREFIX, stage_of)
    want = {}
    for a, b in gaps:
        for x in range(a, b):
            over = [(d, n) for n, s, d in host if s <= x < s + d]
            name = min(over)[1][len(SPAN_PREFIX):] if over else None
            key = (stage_of(name) if name else None) or "unstaged"
            want[key] = want.get(key, 0) + 1
    assert got == want
    assert sum(got.values()) == sum(b - a for a, b in gaps)


def _ring(*queries):
    """Queries as `(wall_s, {field: seconds})` into the engine's ring."""
    for wall, secs in queries:
        tm = {f: 0 for f in ("scan_walk_ns", "scan_pack_ns", "h2d_ns",
                             "dispatch_ns", "host_sync_ns", "d2h_ns",
                             "plan_rewrite_ns")}
        tm.update({f: int(s * 1e9) for f, s in secs.items()})
        metrics.note_query(wall, "TpuSortExec", tm)


def test_the_ring_readers_take_the_windows_queries():
    _ring((90.0, {"scan_walk_ns": 9.0, "dispatch_ns": 9.0}),   # set-up
          (1.0, {"scan_walk_ns": 0.2, "scan_pack_ns": 0.02,
                 "dispatch_ns": 0.03}),
          (1.0, {"scan_walk_ns": 0.3, "scan_pack_ns": 0.04,
                 "dispatch_ns": 0.03}),
          (1.2, {"scan_walk_ns": 0.25, "scan_pack_ns": 0.03,
                 "dispatch_ns": 0.05, "host_sync_ns": 0.1}))
    ctx = {"window": [{}] * 3}
    assert reader("scan_walk_s").read(ctx) == pytest.approx(0.25)
    assert reader("scan_pack_s").read(ctx) == pytest.approx(0.03)
    assert reader("dispatch_host_s").read(ctx) == pytest.approx(0.11 / 3)
    # the slowest query's largest excess over the median: its host sync
    assert reader("slowest_query_stage_excess_s").read(ctx) == \
        pytest.approx(0.1)
    assert reader("scan_walk_s").read({"window": []}) is None


@pytest.mark.parametrize("name", READERS)
def test_on_an_engine_without_the_stage_table_a_reader_reports_nothing(
        monkeypatch, name):
    """The parent commit: no `STAGES`, and ring records without the new
    fields. The reader returns None and does not raise."""
    from spark_rapids_tpu.utils import tracing
    monkeypatch.delattr(tracing, "STAGES")
    metrics.note_query(1.0, "TpuSortExec", {"host_sync_ns": 1, "d2h_ns": 1})
    ctx = ctx_of(HOST)
    ctx["window"] = [{"seconds": 1.0}]
    assert reader(name).read(ctx) is None


@pytest.mark.parametrize("name", ("idle_dispatch_s", "idle_unstaged_s"))
def test_without_a_trace_a_stage_reader_reports_nothing(name):
    assert reader(name).read({"trace": None, "cell": {"chips": 1}}) is None
