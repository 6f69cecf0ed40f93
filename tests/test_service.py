"""Multi-process chip ownership (SURVEY §7 hard part; round-3 verdict #5).

One TpuDeviceService process owns the backend; REAL worker OS processes
(tests/service_worker.py via subprocess) contend through the cross-process
admission semaphore and submit Spark-plan JSON over the Arrow-IPC socket
ABI. Covers: FIFO admission ordering across processes with one token,
mutual exclusion (second worker admitted only after the first releases),
plan round-trips from two concurrent workers, token reclamation when a
worker dies holding admission, and wedged-service fail-fast
(DeviceStartupError under deadline — reference Plugin.scala:436-459;
admission analog GpuSemaphore.scala:67,125)."""

import json
import os
import signal
import socket as socketmod
import subprocess
import sys
import time

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu.errors import DeviceStartupError
from spark_rapids_tpu.service import TpuServiceClient

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "service_worker.py")


def _env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)  # workers don't need the 8-device mesh
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


def _start_server(sock, tokens=1):
    proc = subprocess.Popen(
        [sys.executable, "-m", "spark_rapids_tpu.service.server",
         "--socket", sock, "--platform", "cpu",
         "--conf", f"spark.rapids.sql.concurrentGpuTasks={tokens}"],
        cwd=REPO, env=_env(),
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    # wait for liveness (first connect also exercises the client deadline)
    try:
        TpuServiceClient(sock, deadline_s=60.0).connect().close()
    except BaseException:
        proc.kill()
        proc.wait()
        raise
    return proc


def _stop_server(proc, sock):
    try:
        with TpuServiceClient(sock, deadline_s=5.0) as cli:
            cli.shutdown()
    except Exception:
        pass
    try:
        proc.wait(timeout=10)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait()


def _worker(sock, name, *extra):
    return subprocess.Popen(
        [sys.executable, WORKER, "--socket", sock, "--name", name, *extra],
        cwd=REPO, env=_env(),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)


def _result(proc, timeout=60):
    out, err = proc.communicate(timeout=timeout)
    assert proc.returncode == 0, f"worker failed: {err[-2000:]}"
    return json.loads(out.strip().splitlines()[-1])


def _wait_for_file(path, msg, workers=(), deadline=30):
    """Poll for a marker file; on timeout kill outstanding workers so a
    failure cannot leave the module-scoped server's token held."""
    t0 = time.time()
    while not os.path.exists(path):
        if time.time() - t0 > deadline:
            for w in workers:
                w.kill()
            raise AssertionError(msg)
        time.sleep(0.01)


@pytest.fixture(scope="module")
def server(tmp_path_factory):
    sock = str(tmp_path_factory.mktemp("svc") / "tpu.sock")
    proc = _start_server(sock, tokens=1)
    yield sock
    _stop_server(proc, sock)


def scan_filter_plan():
    """FilterExec(v > 0) over FileSourceScanExec('t') as toJSON pre-order."""
    attr = lambda name, dt: [  # noqa: E731
        {"class": "org.apache.spark.sql.catalyst.expressions."
         "AttributeReference", "num-children": 0, "name": name,
         "dataType": dt, "nullable": True, "metadata": {},
         "exprId": {"id": 1, "jvmId": "x"}, "qualifier": []}]
    filt = {"class": "org.apache.spark.sql.execution.FilterExec",
            "num-children": 1,
            "condition": [{"class": "org.apache.spark.sql.catalyst."
                           "expressions.GreaterThan", "num-children": 2}]
            + attr("v", "double")
            + [{"class": "org.apache.spark.sql.catalyst.expressions."
                "Literal", "num-children": 0, "value": "0.0",
                "dataType": "double"}]}
    scan = {"class": "org.apache.spark.sql.execution.FileSourceScanExec",
            "num-children": 0, "relation": "HadoopFsRelation(parquet)",
            "output": [attr("k", "long"), attr("v", "double")],
            "tableIdentifier": "t"}
    return json.dumps([filt, scan])


@pytest.fixture(scope="module")
def plan_and_data(tmp_path_factory):
    d = tmp_path_factory.mktemp("svcdata")
    rng = np.random.default_rng(5)
    n = 3000
    t = pa.table({"k": pa.array(rng.integers(0, 50, n).astype(np.int64)),
                  "v": pa.array(rng.normal(0.1, 1.0, n))})
    path = str(d / "t.parquet")
    pq.write_table(t, path)
    plan_path = str(d / "plan.json")
    with open(plan_path, "w") as f:
        f.write(scan_filter_plan())
    expected = int(np.sum(np.asarray(t.column("v")) > 0.0))
    return plan_path, path, expected


class TestCrossProcessAdmission:
    def test_fifo_order_and_mutual_exclusion(self, server, tmp_path):
        """With ONE token, worker B (a separate OS process) is admitted
        only after worker A releases, and admission sequence numbers are
        FIFO."""
        held = str(tmp_path / "a_held")
        go = str(tmp_path / "a_go")
        wa = _worker(server, "A", "--held-marker", held,
                     "--hold-until", go)
        _wait_for_file(held, "worker A never admitted", (wa,))
        b_enter = str(tmp_path / "b_enter")
        wb = _worker(server, "B", "--enter-marker", b_enter)
        _wait_for_file(b_enter, "worker B never reached acquire", (wa, wb))
        time.sleep(0.6)  # B is parked in acquire() behind A's token
        try:
            assert wb.poll() is None, \
                "worker B finished while A held the token"
        finally:
            with open(go, "w") as f:
                f.write("go")
        ra = _result(wa)
        rb = _result(wb)
        assert ra["order"] < rb["order"]
        # mutual exclusion across processes: B admitted after A released
        assert rb["t_acquired"] >= ra["t_released"] - 0.05
        # and B genuinely waited (it was started while A held the token)
        assert rb["t_acquired"] - rb["t_enter_acquire"] >= 0.4

    def test_two_workers_run_plans_concurrently(self, server,
                                                plan_and_data):
        """Two worker processes each submit a Spark executedPlan JSON and
        get identical Arrow results back through the batch ABI."""
        plan_path, data_path, expected = plan_and_data
        paths = json.dumps({"t": [data_path]})
        ws = [_worker(server, f"W{i}", "--plan", plan_path,
                      "--paths", paths) for i in range(2)]
        results = [_result(w) for w in ws]
        for r in results:
            assert r["num_rows"] == expected
            assert r["columns"] == ["k", "v"]
        # both went through the same global admission sequence
        assert results[0]["order"] != results[1]["order"]

    def test_dead_worker_releases_token(self, server, tmp_path):
        """A worker killed while HOLDING admission must not leak the token
        (server releases on disconnect) — the next worker still gets in."""
        held = str(tmp_path / "k_held")
        wa = _worker(server, "K", "--held-marker", held,
                     "--hold-until", str(tmp_path / "never"))
        _wait_for_file(held, "worker K never admitted", (wa,))
        wa.send_signal(signal.SIGKILL)
        wa.wait(timeout=10)
        wb = _worker(server, "B2")
        rb = _result(wb, timeout=30)
        assert rb["order"] > 0


class TestWedgedServiceFailFast:
    def test_no_service_raises_under_deadline(self, tmp_path):
        sock = str(tmp_path / "absent.sock")
        t0 = time.time()
        with pytest.raises(DeviceStartupError):
            TpuServiceClient(sock, deadline_s=0.8).connect()
        assert time.time() - t0 < 5.0

    def test_wedged_service_raises_under_deadline(self, tmp_path):
        """A service that accepts connections but never answers (a wedged
        service) must surface DeviceStartupError, not
        hang the worker."""
        sock = str(tmp_path / "wedged.sock")
        srv = socketmod.socket(socketmod.AF_UNIX, socketmod.SOCK_STREAM)
        srv.bind(sock)
        srv.listen(4)
        try:
            t0 = time.time()
            with pytest.raises(DeviceStartupError):
                TpuServiceClient(sock, deadline_s=1.0).connect()
            assert time.time() - t0 < 6.0
        finally:
            srv.close()
