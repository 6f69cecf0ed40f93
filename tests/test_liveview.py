"""Live query introspection (ISSUE-13): in-flight registry, progress/ETA
from the statistics history, slow-query watchdog, and the surfaces —
/queries over HTTP, the `queries` service op, the fleet-gateway fan-out,
and the tpu_top console — plus the satellite tools (profile_report scan-
pushdown section, bench_compare).

Off-path contract is tested here and CI-gated by
scripts/liveview_matrix.sh: live.enabled=false spawns zero threads,
creates zero state, and keeps results byte-identical."""

import importlib.util
import json
import os
import signal
import socket as socketmod
import threading
import time
import urllib.request

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu import faults, live, stats, telemetry
from spark_rapids_tpu.errors import QueryCancelledError
from spark_rapids_tpu.expr import Sum, col
from spark_rapids_tpu.plugin import TpuSession
from spark_rapids_tpu.service import TpuServiceClient
from spark_rapids_tpu.service.protocol import recv_msg, send_msg
from spark_rapids_tpu.utils.spans import validate_record

pytestmark = pytest.mark.live

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _live_teardown():
    """Every test leaves live/telemetry/stats OFF so suites sharing this
    process keep their zero-thread assumptions (the configure calls are
    enable-only)."""
    yield
    live.shutdown()
    telemetry.shutdown()
    stats.shutdown()
    assert not live.is_enabled() and live.get() is None


def _table(n=40_000, seed=7, groups=32):
    rng = np.random.default_rng(seed)
    return pa.table({
        "g": pa.array(rng.integers(0, groups, n).astype(np.int32)),
        "v": pa.array(rng.uniform(size=n))})


def _run(sess, t):
    return (sess.from_arrow(t).filter(col("v") > 0.25)
            .group_by("g").agg(total=Sum(col("v")))).collect()


def _conf(**extra):
    base = {"spark.rapids.sql.explain": "NONE",
            "spark.rapids.tpu.live.enabled": True,
            # several batches per query so the pull hook fires often
            "spark.rapids.sql.batchSizeRows": 8192}
    base.update(extra)
    return base


def _slow_fault(times=60, delay_s=0.02):
    """A deterministic mid-query slowdown: every tracked device
    allocation sleeps, spreading wall time across the whole pull chain
    so pollers observe intermediate states."""
    return faults.inject(faults.ALLOC, "delay", nth=0, times=times,
                         delay_s=delay_s)


# ---------------------------------------------------------------------------
class TestOffPath:
    def test_off_by_default_zero_state(self):
        threads0 = threading.active_count()
        sess = TpuSession({"spark.rapids.sql.explain": "NONE"})
        _run(sess, _table())
        assert not live.is_enabled()
        assert live.get() is None and live.watchdog() is None
        assert threading.active_count() <= threads0
        snap = live.snapshot()
        assert snap == {"enabled": False, "pid": os.getpid(),
                        "queries": [], "recent": []}
        # the hot hook is a no-op without state
        live.note_pull(object())
        assert live.current_entry() is None

    def test_on_off_results_identical(self):
        t = _table()
        # sessions identical except the live switch: same batch sizing,
        # so float-sum grouping matches and equality is byte-exact
        off_conf = _conf()
        off_conf["spark.rapids.tpu.live.enabled"] = False
        off = _run(TpuSession(off_conf), t)
        on = _run(TpuSession(_conf()), t)
        assert on.sort_by("g").equals(off.sort_by("g"))


# ---------------------------------------------------------------------------
class TestRegistryLifecycle:
    def test_recent_entry_fields(self):
        sess = TpuSession(_conf())
        _run(sess, _table())
        snap = live.snapshot()
        assert snap["enabled"] and snap["queries"] == []
        rec = snap["recent"][-1]
        assert rec["status"] == "ok"
        assert rec["rows"] > 0 and rec["pulls"] > 0
        assert rec["operator"]  # the last pulled operator is stamped
        names = [o["name"] for o in rec["ops"]]
        assert "TpuHashAggregateExec" in names
        assert any(o["rows"] > 0 for o in rec["ops"])
        assert rec["tenant"] == "default" and rec["trace_id"]
        # no stats history => rows-only mode, fail-closed
        assert rec["progress"] is None and rec["eta_s"] is None
        json.dumps(snap)  # the wire shape must be JSON-clean

    def test_inflight_mid_query_monotonic_progress(self):
        sess = TpuSession(_conf(**{"spark.rapids.tpu.stats.enabled": True}))
        t = _table()
        _run(sess, t)  # populate history (rows + wall per fingerprint)
        seen, progress_seq = [], []
        stop = threading.Event()

        def poll():
            while not stop.is_set():
                for q in live.snapshot()["queries"]:
                    seen.append(q["query_id"])
                    if q["progress"] is not None:
                        progress_seq.append(q["progress"])
                time.sleep(0.005)

        poller = threading.Thread(target=poll, daemon=True)
        poller.start()
        with _slow_fault():
            _run(sess, t)
        stop.set()
        poller.join(timeout=5)
        assert seen, "query never appeared in the in-flight registry"
        assert progress_seq, "no progress fractions observed mid-query"
        assert progress_seq == sorted(progress_seq), \
            f"progress went backwards: {progress_seq}"
        assert all(0.0 <= p <= 1.0 for p in progress_seq)
        assert live.snapshot()["queries"] == []  # cleared on finish

    def test_recent_ring_bounded(self):
        sess = TpuSession(_conf(**{
            "spark.rapids.tpu.live.recentQueries": 3}))
        t = _table(n=4000)
        for _ in range(5):
            _run(sess, t)
        assert len(live.snapshot()["recent"]) == 3


# ---------------------------------------------------------------------------
class TestProgressEta:
    def test_progress_eta_with_history(self):
        sess = TpuSession(_conf(**{"spark.rapids.tpu.stats.enabled": True}))
        t = _table()
        _run(sess, t)
        first = live.snapshot()["recent"][-1]
        assert first["progress"] is None and first["eta_s"] is None
        _run(sess, t)
        rec = live.snapshot()["recent"][-1]
        assert rec["expected_wall_s"] and rec["expected_wall_s"] > 0
        assert rec["progress"] == pytest.approx(1.0)
        assert rec["eta_s"] == pytest.approx(0.0)
        # per-op expectations resolved from the fingerprint history
        assert any("expected_rows" in o and o.get("fraction") == 1.0
                   for o in rec["ops"])

    def test_wall_recorded_into_history(self):
        sess = TpuSession(_conf(**{"spark.rapids.tpu.stats.enabled": True}))
        _run(sess, _table())
        hist = stats.get()
        assert hist is not None
        walls = [e.wall_s for e in hist._entries.values() if e.wall_s > 0]
        assert walls, "no wall_s landed in the stats history"

    def test_deadline_fields_in_snapshot(self):
        from spark_rapids_tpu.sched import QueryContext, activate
        live.configure(_conf_obj())
        reg = live.get()
        ctx = QueryContext(tenant="t9", priority=2, deadline_s=30.0,
                           query_id="dl-q")
        with activate(ctx):
            entry = reg.begin(_dummy_exec(), None, "dl")
        snap = entry.snapshot()
        assert snap["query_id"] == "dl-q" and snap["tenant"] == "t9"
        assert snap["priority"] == 2
        assert snap["deadline_s"] == 30.0
        assert 0 < snap["remaining_s"] <= 30.0
        reg.end(entry, "ok")


def _conf_obj(**extra):
    from spark_rapids_tpu.config import TpuConf
    return TpuConf(_conf(**extra))


def _dummy_exec():
    from spark_rapids_tpu.exec.base import TpuExec
    return TpuExec([])


# ---------------------------------------------------------------------------
class TestWatchdog:
    def test_no_false_positive_without_history(self):
        sess = TpuSession(_conf(**{
            "spark.rapids.tpu.live.slowFactor": 0.001,
            "spark.rapids.tpu.live.watchdog.intervalMs": 20}))
        with _slow_fault(times=20):
            _run(sess, _table())  # slow AND first-ever: no history
        rec = live.snapshot()["recent"][-1]
        assert rec["slow"] is False
        assert live.watchdog().flags == 0

    def test_slow_query_incident_with_live_snapshot(self, tmp_path):
        sess = TpuSession(_conf(**{
            "spark.rapids.tpu.stats.enabled": True,
            "spark.rapids.tpu.telemetry.enabled": True,
            "spark.rapids.tpu.telemetry.flightRecorder.dir": str(tmp_path),
            "spark.rapids.tpu.live.slowFactor": 0.2,
            "spark.rapids.tpu.live.watchdog.intervalMs": 20}))
        t = _table()
        _run(sess, t)  # cold run: compile warmup (its wall is inflated)
        _run(sess, t)  # history run at WARM wall (latest record wins) —
        # the injected delay below then dominates slowFactor x history
        # regardless of whether this test runs standalone or mid-suite
        with _slow_fault(times=100, delay_s=0.03):
            _run(sess, t)
        rec = live.snapshot()["recent"][-1]
        assert rec["slow"] is True and "historical wall" in rec["slow_reason"]
        dumps = [f for f in os.listdir(tmp_path) if "slow_query" in f]
        assert dumps, f"no slow_query incident in {os.listdir(tmp_path)}"
        recs = [json.loads(line)
                for line in open(tmp_path / dumps[0])]
        bad = [validate_record(r) for r in recs if validate_record(r)]
        assert not bad, bad[:2]
        header = recs[0]
        assert header["reason"] == "slow_query"
        assert header["trace_id"] == rec["trace_id"]
        lv = header["attrs"]["live"]
        assert lv["query_id"] == rec["query_id"]
        assert lv["ops"], "no live operator snapshot in the incident"
        # exactly one incident per query
        assert live.watchdog().flags == 1

    def test_watchdog_cancel(self):
        sess = TpuSession(_conf(**{
            "spark.rapids.tpu.stats.enabled": True,
            "spark.rapids.tpu.sched.tenant": "wd",  # activates a context
            "spark.rapids.tpu.live.slowFactor": 0.1,
            "spark.rapids.tpu.live.watchdog.intervalMs": 20,
            "spark.rapids.tpu.live.watchdog.cancel": True}))
        t = _table()
        _run(sess, t)  # compile warmup
        _run(sess, t)  # history at warm wall
        with _slow_fault(times=400, delay_s=0.05):
            with pytest.raises(QueryCancelledError) as ei:
                _run(sess, t)
        assert "watchdog" in str(ei.value)
        from spark_rapids_tpu.memory.semaphore import TpuSemaphore
        TpuSemaphore._instance = None  # scheduled-query permit hygiene

    def test_deadline_approaching_flag(self):
        from spark_rapids_tpu.live.watchdog import Watchdog
        from spark_rapids_tpu.sched import QueryContext, activate
        live.configure(_conf_obj())
        reg = live.get()
        ctx = QueryContext(deadline_s=0.3)
        with activate(ctx):
            entry = reg.begin(_dummy_exec(), None, "dl")
        wd = Watchdog(reg, interval_s=999, slow_factor=3.0)
        assert wd.scan() == 0  # plenty of budget left: not flagged
        time.sleep(0.28)       # inside the last 10% of the deadline
        assert wd.scan() == 1
        assert entry.slow and "deadline" in entry.slow_reason
        assert wd.scan() == 0  # idempotent: one flag per query
        reg.end(entry, "deadline")


# ---------------------------------------------------------------------------
class TestSurfaces:
    def test_http_queries_endpoint_mid_query(self):
        sess = TpuSession(_conf(**{
            "spark.rapids.tpu.stats.enabled": True,
            "spark.rapids.tpu.telemetry.enabled": True,
            "spark.rapids.tpu.telemetry.http.port": 0}))
        t = _table()
        _run(sess, t)
        port = telemetry.http_server().port

        def get():
            return json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{port}/queries", timeout=5).read())

        seen = []
        stop = threading.Event()

        def poll():
            while not stop.is_set():
                seen.extend(q["query_id"] for q in get()["queries"])
                time.sleep(0.01)

        th = threading.Thread(target=poll, daemon=True)
        th.start()
        with _slow_fault():
            _run(sess, t)
        stop.set()
        th.join(timeout=5)
        assert seen, "in-flight query never visible on /queries"
        snap = get()
        assert snap["enabled"] and snap["queries"] == []
        assert snap["recent"]

    def test_telemetry_live_families(self):
        sess = TpuSession(_conf(**{
            "spark.rapids.tpu.stats.enabled": True,
            "spark.rapids.tpu.telemetry.enabled": True}))
        t = _table()
        _run(sess, t)
        from spark_rapids_tpu.telemetry import parse_prometheus
        found = {"queries": 0, "progress": 0}
        stop = threading.Event()

        def poll():
            while not stop.is_set():
                parsed = parse_prometheus(telemetry.render_prometheus())
                found["queries"] = max(
                    found["queries"],
                    sum(parsed.get("tpu_live_queries", {}).values()))
                found["progress"] = max(
                    found["progress"],
                    len(parsed.get("tpu_live_query_progress", {})))
                time.sleep(0.01)

        th = threading.Thread(target=poll, daemon=True)
        th.start()
        with _slow_fault():
            _run(sess, t)
        stop.set()
        th.join(timeout=5)
        assert found["queries"] >= 1, "tpu_live_queries never sampled >0"
        assert found["progress"] >= 1, \
            "tpu_live_query_progress never carried a series"

    def test_service_queries_op_and_surface_agreement(self, tmp_path):
        """The acceptance shape: during one running query, the HTTP
        endpoint, the service op, and the in-process registry all report
        the same query id (the gateway fan-out is TestGatewayFanout's
        job; the real-subprocess version is liveview_matrix.sh's)."""
        from spark_rapids_tpu.memory.semaphore import TpuSemaphore
        from spark_rapids_tpu.service.server import TpuDeviceService
        import pyarrow.parquet as pq
        rng = np.random.default_rng(5)
        big = pa.table({
            "k": pa.array(rng.integers(0, 50, 60_000).astype(np.int64)),
            "v": pa.array(rng.normal(0.1, 1.0, 60_000))})
        path = str(tmp_path / "t.parquet")
        pq.write_table(big, path)

        def attr(name, dt):
            return [{"class": "org.apache.spark.sql.catalyst.expressions."
                     "AttributeReference", "num-children": 0, "name": name,
                     "dataType": dt, "nullable": True, "metadata": {},
                     "exprId": {"id": 1, "jvmId": "x"}, "qualifier": []}]

        plan = json.dumps([{
            "class": "org.apache.spark.sql.execution.FileSourceScanExec",
            "num-children": 0, "relation": "HadoopFsRelation(parquet)",
            "output": [attr("k", "long"), attr("v", "double")],
            "tableIdentifier": "t"}])
        svc = TpuDeviceService(
            _conf(**{"spark.rapids.sql.enabled": True,
                     "spark.rapids.tpu.telemetry.enabled": True,
                     "spark.rapids.tpu.telemetry.http.port": 0,
                     "spark.rapids.sql.batchSizeRows": 4096}),
            str(tmp_path / "svc.sock"))
        th = threading.Thread(target=svc.serve_forever, daemon=True)
        th.start()
        try:
            for _ in range(400):
                if svc._listener is not None:
                    break
                time.sleep(0.01)
            port = telemetry.http_server().port
            qid = "agree-q1"
            done = threading.Event()

            def submit():
                with TpuServiceClient(str(tmp_path / "svc.sock"),
                                      deadline_s=120.0) as cli:
                    cli.run_plan(plan, paths={"t": [path]}, query_id=qid)
                done.set()

            sub = threading.Thread(target=submit, daemon=True)
            hits = {"http": False, "op": False, "reg": False}
            with TpuServiceClient(str(tmp_path / "svc.sock"),
                                  deadline_s=30.0) as poll_cli:
                with _slow_fault(times=200, delay_s=0.05):
                    sub.start()
                    deadline = time.monotonic() + 60
                    while not done.is_set() and \
                            time.monotonic() < deadline and \
                            not all(hits.values()):
                        body = json.loads(urllib.request.urlopen(
                            f"http://127.0.0.1:{port}/queries",
                            timeout=5).read())
                        if any(q["query_id"] == qid
                               for q in body["queries"]):
                            hits["http"] = True
                        lv = poll_cli.queries()
                        if any(q["query_id"] == qid
                               for q in lv["queries"]):
                            hits["op"] = True
                        if any(e.query_id == qid
                               for e in live.get().inflight()):
                            hits["reg"] = True
                        time.sleep(0.02)
                    sub.join(timeout=90)
            assert all(hits.values()), f"surfaces disagreed: {hits}"
            with TpuServiceClient(str(tmp_path / "svc.sock"),
                                  deadline_s=10.0) as cli:
                lv = cli.queries()
                assert lv["enabled"]
                assert any(r["query_id"] == qid for r in lv["recent"])
        finally:
            svc._stop.set()
            th.join(timeout=10)
            TpuSemaphore._instance = None


# ---------------------------------------------------------------------------
class _FakeLiveWorker(threading.Thread):
    """Thread server answering ping + queries with a canned live view."""

    def __init__(self, sock_path, name="fw"):
        super().__init__(daemon=True)
        self.sock_path = sock_path
        self.worker_name = name
        self.srv = socketmod.socket(socketmod.AF_UNIX,
                                    socketmod.SOCK_STREAM)
        self.srv.bind(sock_path)
        self.srv.listen(16)
        self.srv.settimeout(0.2)
        self._stop = threading.Event()

    def run(self):
        while not self._stop.is_set():
            try:
                conn, _ = self.srv.accept()
            except socketmod.timeout:
                continue
            threading.Thread(target=self._serve, args=(conn,),
                             daemon=True).start()
        self.srv.close()

    def _serve(self, conn):
        try:
            while True:
                header, _ = recv_msg(conn)
                if header.get("op") == "ping":
                    send_msg(conn, {"ok": True, "device": "fake"})
                elif header.get("op") == "queries":
                    send_msg(conn, {"ok": True, "live": {
                        "enabled": True, "pid": 1,
                        "queries": [{
                            "query_id": f"q-{self.worker_name}",
                            "label": "fake", "tenant": "default",
                            "status": "running", "started_ts": 1.0,
                            "elapsed_s": 0.5, "operator": "TpuFilterExec",
                            "rows": 10, "progress": 0.5, "eta_s": 0.5,
                            "slow": False, "ops": []}],
                        "recent": []}})
                else:
                    send_msg(conn, {"ok": False, "error": "nope"})
        except Exception:
            pass

    def close(self):
        self._stop.set()


class TestGatewayFanout:
    def _gateway(self, tmp_path, specs):
        from spark_rapids_tpu.fleet.gateway import FleetGateway
        gw_sock = str(tmp_path / "gw.sock")
        gw = FleetGateway(
            specs,
            {"spark.rapids.tpu.fleet.probe.intervalMs": 60_000,
             "spark.rapids.tpu.fleet.probe.timeoutSec": 1.0,
             "spark.rapids.tpu.fleet.dispatch.timeoutSec": 5.0},
            gw_sock)
        th = threading.Thread(target=gw.serve_forever, daemon=True)
        th.start()
        cli = TpuServiceClient(gw_sock, deadline_s=15.0).connect()
        return gw, th, cli

    def test_fanout_partial_annotated_never_error(self, tmp_path):
        ok = _FakeLiveWorker(str(tmp_path / "ok.sock"), name="ok")
        ok.start()
        drain = _FakeLiveWorker(str(tmp_path / "dr.sock"), name="dr")
        drain.start()
        specs = [("w_ok", ok.sock_path), ("w_drain", drain.sock_path),
                 ("w_dead", str(tmp_path / "nope.sock")),
                 ("w_open", str(tmp_path / "nope2.sock"))]
        gw, th, cli = self._gateway(tmp_path, specs)
        try:
            gw.registry.drain("w_drain")
            for _ in range(3):  # trip w_open's breaker
                gw.registry.note_failure("w_open", "boom")
            lv = cli.queries()
            assert lv["enabled"] and lv["role"] == "gateway"
            w = lv["workers"]
            # healthy worker: its query rides in, annotated
            assert w["w_ok"]["enabled"] and w["w_ok"]["queries"] == 1
            ids = {(q["query_id"], q["worker"]) for q in lv["queries"]}
            assert ("q-ok", "w_ok") in ids
            # draining worker still polled (its in-flight view matters)
            assert w["w_drain"]["draining"] is True
            assert ("q-dr", "w_drain") in ids
            # dead worker: error slot with breaker state, not an error
            assert "error" in w["w_dead"]
            assert "breaker" in w["w_dead"]
            # breaker-OPEN worker skipped without touching its socket
            assert w["w_open"] == {"breaker": "open", "draining": False,
                                   "outstanding": 0,
                                   "skipped": "breaker_open"}
        finally:
            cli.close()
            gw._stop.set()
            th.join(timeout=5)
            ok.close()
            drain.close()

    def test_fanout_all_dead_is_still_ok(self, tmp_path):
        specs = [("a", str(tmp_path / "a.sock")),
                 ("b", str(tmp_path / "b.sock"))]
        gw, th, cli = self._gateway(tmp_path, specs)
        try:
            lv = cli.queries()   # must not raise
            assert lv["queries"] == []
            assert set(lv["workers"]) == {"a", "b"}
            for slot in lv["workers"].values():
                assert "error" in slot or "skipped" in slot
        finally:
            cli.close()
            gw._stop.set()
            th.join(timeout=5)


# ---------------------------------------------------------------------------
class TestDebugSignal:
    def test_sigusr2_dumps_schema_valid_incident(self, tmp_path):
        sess = TpuSession(_conf(**{
            "spark.rapids.tpu.live.debugSignal": True,
            "spark.rapids.tpu.telemetry.enabled": True,
            "spark.rapids.tpu.telemetry.flightRecorder.dir":
                str(tmp_path)}))
        _run(sess, _table(n=4000))
        os.kill(os.getpid(), signal.SIGUSR2)
        deadline = time.monotonic() + 5
        dumps = []
        while time.monotonic() < deadline and not dumps:
            dumps = [f for f in os.listdir(tmp_path)
                     if "debug_signal" in f]
            time.sleep(0.02)
        assert dumps, f"no debug_signal dump in {os.listdir(tmp_path)}"
        recs = [json.loads(line) for line in open(tmp_path / dumps[0])]
        bad = [validate_record(r) for r in recs if validate_record(r)]
        assert not bad, bad[:2]
        assert recs[0]["reason"] == "debug_signal"
        lv = recs[0]["attrs"]["live"]
        assert lv["enabled"] and lv["recent"], \
            "live registry missing from the dump"
        # the ring events rode along (telemetry was on)
        assert any(r["type"] == "event" for r in recs)

    def test_debug_dump_standalone_without_telemetry(self, tmp_path):
        sess = TpuSession(_conf(**{
            "spark.rapids.tpu.metrics.eventLog.dir": str(tmp_path)}))
        # force live configuration without telemetry
        sess.initialize_device()
        path = live.debug_dump()
        assert path and os.path.exists(path)
        rec = json.loads(open(path).readline())
        assert not validate_record(rec), validate_record(rec)
        assert rec["type"] == "incident" and rec["n_events"] == 0
        assert rec["attrs"]["live"]["enabled"] is True


# ---------------------------------------------------------------------------
class TestSatelliteTools:
    @staticmethod
    def _query_record(qid="1-1", **tm):
        base_tm = {"scan_rows_pruned": 0, "scan_rowgroups_pruned": 0,
                   "scan_bytes_materialized": 0}
        base_tm.update(tm)
        return {"v": 2, "type": "query", "query_id": qid,
                "trace_id": "t" * 16, "label": "q", "status": "ok",
                "ts": 1.0, "wall_ns": 5_000_000, "task_metrics": base_tm,
                "n_operators": 1, "n_spans": 1, "adaptive": []}

    def test_profile_report_scan_pushdown_section(self, tmp_path, capsys):
        from spark_rapids_tpu.tools import profile_report as pr
        recs = [self._query_record(scan_rows_pruned=1900,
                                   scan_rowgroups_pruned=3,
                                   scan_bytes_materialized=4096)]
        model = pr.build_model(recs)
        text = pr.render_report(model)
        assert "scan pushdown:" in text
        assert "rowsPruned=1900" in text
        assert "rowGroupsPruned=3" in text
        assert "bytesMaterialized=4096B" in text
        assert "=== scan pushdown ===" in text
        pd = pr.pushdown_summary(model)
        assert pd == {"queries": 1, "rows_pruned": 1900,
                      "rowgroups_pruned": 3, "bytes_materialized": 4096}
        # --json carries the section too
        log = tmp_path / "events-1.jsonl"
        log.write_text(json.dumps(recs[0]) + "\n")
        assert pr.main([str(log), "--json"]) == 0
        out = json.loads(capsys.readouterr().out)
        assert out["pushdown"]["rows_pruned"] == 1900
        # a pushdown-free log renders no section
        assert pr.pushdown_summary(
            pr.build_model([self._query_record()])) == {}

    @staticmethod
    def _bench_compare():
        spec = importlib.util.spec_from_file_location(
            "bench_compare", os.path.join(REPO, "scripts",
                                          "bench_compare.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_bench_compare_diff_and_gate(self, tmp_path, capsys):
        bc = self._bench_compare()
        base = {"metric": "scan_join_agg_speedup_vs_cpu", "value": 2.0,
                "unit": "x", "vs_baseline": 1.0,
                "detail": {"pipeline_gbps": 3.0, "scan_decode_gbps_raw":
                           0.3, "scan_dispatches": 48, "rows": 1000}}
        new = {"metric": "scan_join_agg_speedup_vs_cpu", "value": 4.0,
               "unit": "x", "vs_baseline": 2.0,
               "detail": {"pipeline_gbps": 6.0, "scan_decode_gbps_raw":
                          0.6, "scan_dispatches": 4, "rows": 1000}}
        pb = tmp_path / "BENCH_a.json"
        pn = tmp_path / "BENCH_b.json"
        pb.write_text(json.dumps(base))
        # the driver-wrapper shape must load too
        pn.write_text(json.dumps({"n": 1, "parsed": new}))
        assert bc.main([str(pb), str(pn)]) == 0
        out = capsys.readouterr().out
        assert "2.000" in out and "pipeline_gbps" in out \
            and "scan_dispatches" in out
        assert bc.main([str(pb), str(pn), "--fail-below", "1.5"]) == 0
        assert bc.main([str(pb), str(pn), "--fail-below", "3.0"]) == 2
        # an errored run (null headline) always fails the gate
        pe = tmp_path / "BENCH_err.json"
        pe.write_text(json.dumps({"metric": "m", "value": None,
                                  "error": "runtime hung at first touch",
                                  "detail": {}}))
        assert bc.main([str(pb), str(pe), "--fail-below", "0.1"]) == 2
        # json model shape (drain the earlier renders first)
        capsys.readouterr()
        assert bc.main([str(pb), str(pn), "--json"]) == 0
        model = json.loads(capsys.readouterr().out)
        assert model["headline"][0]["speedup_vs_base"] == \
            pytest.approx(2.0)

    def test_tpu_top_render_units(self):
        from spark_rapids_tpu.tools import tpu_top
        assert tpu_top.progress_bar(None).endswith("?%")
        bar = tpu_top.progress_bar(0.5, width=10)
        assert bar.count("#") == 5 and "50%" in bar
        assert "100%" in tpu_top.progress_bar(1.5)  # clamped
        # a gateway-role snapshot renders annotated worker rows
        snaps = [{"name": "gw", "socket": "/s", "ok": True, "live": {
            "enabled": True, "role": "gateway",
            "workers": {"w0": {"breaker": "open", "draining": False,
                               "outstanding": 0,
                               "skipped": "breaker_open"},
                        "w1": {"breaker": "closed", "draining": True,
                               "outstanding": 2, "enabled": True,
                               "queries": 1}},
            "queries": [{"query_id": "q1", "worker": "w1",
                         "tenant": "a", "status": "running",
                         "operator": "TpuSortExec", "rows": 5,
                         "progress": 0.25, "eta_s": 1.5,
                         "elapsed_s": 0.5, "started_ts": 1.0,
                         "slow": True}],
            "recent": []}}]
        frame = tpu_top.render(snaps)
        assert "gw/w0" in frame and "skipped" in frame
        assert "gw/w1" in frame and "yes" in frame  # draining column
        assert "q1" in frame and "SLOW" in frame and "TpuSortExec" in frame
        assert "25%" in frame

    def test_tpu_top_once_against_service(self, tmp_path, capsys):
        from spark_rapids_tpu.memory.semaphore import TpuSemaphore
        from spark_rapids_tpu.service.server import TpuDeviceService
        from spark_rapids_tpu.tools import tpu_top
        svc = TpuDeviceService(
            _conf(**{"spark.rapids.sql.enabled": True,
                     "spark.rapids.tpu.telemetry.enabled": True}),
            str(tmp_path / "svc.sock"))
        th = threading.Thread(target=svc.serve_forever, daemon=True)
        th.start()
        try:
            for _ in range(400):
                if svc._listener is not None:
                    break
                time.sleep(0.01)
            _run(svc.session, _table(n=4000))
            assert tpu_top.main(
                ["--once", "--plain", f"svc={tmp_path / 'svc.sock'}"]) == 0
            out = capsys.readouterr().out
            assert "svc" in out and "in-flight queries" in out
            assert "recent:" in out  # the finished query shows up
            # a down endpoint degrades to a row, not a crash
            assert tpu_top.main(
                ["--once", "--plain", f"gone={tmp_path / 'no.sock'}"]) == 0
            assert "down" in capsys.readouterr().out
        finally:
            svc._stop.set()
            th.join(timeout=10)
            TpuSemaphore._instance = None
