"""Worker-process client for the device-owner service.

Every call is deadline-bounded: a wedged service (accepts connections but
never answers, or never comes up) surfaces as DeviceStartupError within
`spark.rapids.tpu.device.startupTimeoutSec`
instead of hanging the worker, reusing the round-3 fail-fast contract
(`errors.py` DeviceStartupError; reference `Plugin.scala:436-459`)."""

from __future__ import annotations

import os
import socket
import time
from typing import Dict, Optional, Sequence

from ..errors import (AdmissionTimeoutError, DeadlineExceededError,
                      DeviceStartupError, QueryCancelledError,
                      QueryRejectedError, ServiceConnectionError)
from .protocol import ipc_to_table, request

__all__ = ["TpuServiceClient"]


class TpuServiceClient:
    """`event_log_dir` (or SPARK_RAPIDS_TPU_CLIENT_EVENTLOG_DIR) makes the
    client write one v2 event-log record per run_plan — the CLIENT half of
    cross-process trace correlation: the record carries the same trace id
    the request header shipped to the server, so
    `profile_report.py --trace` over both processes' logs stitches the
    round trip into one timeline."""

    def __init__(self, socket_path: str, deadline_s: float = 60.0,
                 event_log_dir: Optional[str] = None,
                 event_log_max_bytes: int = 0,
                 event_log_max_files: int = 10):
        self.socket_path = socket_path
        self.deadline_s = deadline_s
        self.event_log_dir = event_log_dir or os.environ.get(
            "SPARK_RAPIDS_TPU_CLIENT_EVENTLOG_DIR") or None
        # same rotation contract as the server's event log (a long-lived
        # worker's log is the same unbounded-growth problem)
        self.event_log_max_bytes = event_log_max_bytes or int(os.environ.get(
            "SPARK_RAPIDS_TPU_CLIENT_EVENTLOG_MAX_BYTES", "0") or 0)
        self.event_log_max_files = event_log_max_files
        self.last_trace_id: Optional[str] = None
        self._sock: Optional[socket.socket] = None

    # ------------------------------------------------------------------
    def connect(self, retry_interval: float = 0.05) -> "TpuServiceClient":
        """Connect + liveness ping under the deadline."""
        t0 = time.monotonic()
        last = "never attempted"
        while time.monotonic() - t0 < self.deadline_s:
            try:
                s = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
                s.settimeout(max(self.deadline_s -
                                 (time.monotonic() - t0), 0.05))
                s.connect(self.socket_path)
                self._sock = s
                rep = self._request({"op": "ping"})[0]
                if rep.get("ok"):
                    return self
                last = f"ping not ok: {rep}"
            except DeviceStartupError:
                raise
            except (OSError, ConnectionError) as e:
                last = f"{type(e).__name__}: {e}"
                self._sock = None
                time.sleep(retry_interval)
        raise DeviceStartupError(
            f"device service at {self.socket_path} not answering within "
            f"{self.deadline_s}s ({last})")

    def close(self) -> None:
        if self._sock is not None:
            self._sock.close()
            self._sock = None

    def __enter__(self):
        return self.connect()

    def __exit__(self, *exc):
        self.close()

    # ------------------------------------------------------------------
    def _request(self, header: dict, body: bytes = b""):
        if self._sock is None:
            raise DeviceStartupError("client not connected")
        self._sock.settimeout(self.deadline_s)
        op = header.get("op")
        try:
            return request(self._sock, header, body)
        except socket.timeout:
            raise DeviceStartupError(
                f"device service did not answer {op!r} "
                f"within {self.deadline_s}s (wedged service)")
        except (ConnectionError, OSError) as e:
            # the connection died MID-REQUEST: typed, with the endpoint and
            # op, so failover logic (fleet gateway) and external callers
            # can catch it without pattern-matching raw socket errors. The
            # server releases this connection's admission tokens on the
            # disconnect it just observed — nothing to clean up here.
            self.close()
            raise ServiceConnectionError(
                f"service connection to {self.socket_path} lost during "
                f"{op!r} ({type(e).__name__}: {e})",
                endpoint=self.socket_path, op=op or "",
                phase=getattr(e, "_wire_phase", "recv"), cause=e) from e

    # ------------------------------------------------------------------
    @staticmethod
    def _raise_typed(rep: dict) -> None:
        """Map a typed error reply onto its exception (errors.py)."""
        et = rep.get("error_type")
        msg = rep.get("error", "service error")
        if et == "rejected":
            raise QueryRejectedError(msg, depth=rep.get("depth", -1))
        if et == "cancelled":
            raise QueryCancelledError(msg,
                                      query_id=rep.get("query_id") or "")
        if et == "deadline":
            raise DeadlineExceededError(msg)
        if et == "connection":
            # a fleet gateway reporting that the worker connection died
            # mid-request and the request was not safe to re-dispatch
            raise ServiceConnectionError(
                msg, endpoint=rep.get("endpoint", ""),
                op=rep.get("op", ""), phase=rep.get("phase", "recv"))

    def acquire(self, timeout: Optional[float] = None,
                priority: int = 0, tenant: Optional[str] = None,
                deadline_s: Optional[float] = None,
                trace_id: Optional[str] = None) -> int:
        """Block until admitted; returns the global admission order. A
        server-side admission timeout raises AdmissionTimeoutError with the
        held/waiting contention diagnostics from the reply; a scheduler
        shed/deadline reply raises the matching typed error. priority/
        tenant/deadline_s take effect only on a scheduler-enabled server
        (FIFO servers ignore them)."""
        hdr = {"op": "acquire", "timeout": timeout}
        if priority:
            hdr["priority"] = priority
        if tenant:
            hdr["tenant"] = tenant
        if deadline_s:
            hdr["deadline_s"] = deadline_s
        if trace_id:
            hdr["trace"] = trace_id
        rep, _ = self._request(hdr)
        if not rep.get("ok"):
            self._raise_typed(rep)
            if rep.get("error_type") == "admission_timeout":
                raise AdmissionTimeoutError(
                    f"device admission not granted within {timeout}s "
                    f"(tokens held: {rep.get('held')}, queue depth: "
                    f"{rep.get('waiting')})",
                    held=rep.get("held", -1), waiting=rep.get("waiting", -1),
                    timeout_s=rep.get("timeout_s"))
            raise TimeoutError(rep.get("error", "admission failed"))
        return rep["order"]

    def release(self) -> None:
        self._request({"op": "release"})

    def run_plan(self, plan_json, paths: Optional[Dict[str, Sequence[str]]]
                 = None, use_device: bool = True,
                 query_id: Optional[str] = None, priority: int = 0,
                 tenant: Optional[str] = None,
                 deadline_s: Optional[float] = None,
                 trace_id: Optional[str] = None):
        """Submit a Spark executedPlan.toJSON; returns a pyarrow Table.
        `query_id` registers the run for the `cancel` op (issued from a
        DIFFERENT connection); priority/tenant/deadline_s attach the
        scheduling context the engine enforces (typed errors on
        cancel/deadline/shed). A trace id (given or minted, see
        `last_trace_id`) rides the header so the server's profile/flight
        records correlate with this call."""
        from ..utils import spans
        trace = trace_id or spans.current_trace() or spans.new_trace_id()
        self.last_trace_id = trace
        hdr = {"op": "run_plan", "plan": plan_json, "paths": paths or {},
               "use_device": use_device, "trace": trace}
        if query_id:
            hdr["query_id"] = query_id
        if priority:
            hdr["priority"] = priority
        if tenant:
            hdr["tenant"] = tenant
        if deadline_s:
            hdr["deadline_s"] = deadline_s
        t0 = time.monotonic_ns()
        status = "ok"
        try:
            rep, body = self._request(hdr)
            if not rep.get("ok"):
                status = rep.get("error_type") or "error"
                self._raise_typed(rep)
                raise RuntimeError(rep.get("unsupported")
                                   or rep.get("error"))
            return ipc_to_table(body)
        except BaseException:
            if status == "ok":
                status = "error"
            raise
        finally:
            self._log_client_op("run_plan", trace,
                                time.monotonic_ns() - t0, status,
                                query_id=query_id or "")

    def _log_client_op(self, op: str, trace: str, dur_ns: int,
                       status: str, **attrs) -> None:
        """Best-effort client-side event-log record (no event_log_dir =
        no-op; a logging failure never fails the call)."""
        if not self.event_log_dir:
            return
        try:
            from ..utils import spans
            spans.write_client_record(
                self.event_log_dir,
                spans.client_op_record(op, trace, dur_ns, status=status,
                                       socket=self.socket_path, **attrs),
                max_bytes=self.event_log_max_bytes,
                max_files=self.event_log_max_files)
        except Exception:
            pass

    def cancel(self, query_id: str, priority: Optional[int] = None,
               reason: str = "") -> dict:
        """Kill (default) or — with `priority` — deprioritize an in-flight
        run_plan submitted with that query_id on another connection.
        Returns the server's ack dict; raises on unknown query ids."""
        hdr: dict = {"op": "cancel", "query_id": query_id}
        if priority is not None:
            hdr["priority"] = priority
            hdr["kill"] = False
        if reason:
            hdr["reason"] = reason
        rep, _ = self._request(hdr)
        if not rep.get("ok"):
            raise KeyError(rep.get("error", f"cancel {query_id!r} failed"))
        return rep

    def stats(self) -> str:
        """Scrape the server's metrics registry over the socket: returns
        the same Prometheus text the HTTP /metrics endpoint serves.
        Raises RuntimeError when the server runs with telemetry off."""
        rep, body = self._request({"op": "stats"})
        if not rep.get("ok"):
            raise RuntimeError(rep.get("error", "stats unavailable"))
        return body.decode("utf-8")

    def cache_stats(self) -> dict:
        """The server's result/fragment-cache accounting (entries, bytes,
        hits/misses/stores per seam, evictions, single-flight waits).
        Raises RuntimeError when the server runs with the cache off."""
        rep, _ = self._request({"op": "cache_stats"})
        if not rep.get("ok"):
            raise RuntimeError(rep.get("error", "cache stats unavailable"))
        return rep["stats"]

    def cache_invalidate(self) -> int:
        """Drop every entry in the server's result/fragment cache;
        returns the number dropped. Raises RuntimeError when the server
        runs with the cache off."""
        rep, _ = self._request({"op": "cache_invalidate"})
        if not rep.get("ok"):
            raise RuntimeError(rep.get("error", "cache invalidate failed"))
        return rep["dropped"]

    def queries(self) -> dict:
        """The server's live query-introspection snapshot: in-flight
        queries (tenant, current operator, per-operator rows, progress/
        ETA where statistics history exists) plus recently finished
        ones. Against a fleet gateway this is the aggregated fleet view
        with per-worker breaker/draining annotations. Always answers —
        `enabled: false` when the server runs with live off."""
        rep, _ = self._request({"op": "queries"})
        if not rep.get("ok"):
            raise RuntimeError(rep.get("error", "queries unavailable"))
        return rep["live"]

    def health(self) -> dict:
        """The server's /healthz snapshot (device init state, admission
        alive probe, heartbeat peers, event-log writability). Works
        regardless of the server's telemetry switch."""
        rep, _ = self._request({"op": "health"})
        if not rep.get("ok"):
            raise RuntimeError(rep.get("error", "health unavailable"))
        return rep["health"]

    # ------------------------------------------------ fleet gateway admin
    def drain(self, worker: str, wait_s: Optional[float] = None) -> dict:
        """Mark a fleet worker draining (finish in-flight, route nothing
        new — rolling-restart prep). With `wait_s` the gateway blocks up
        to that long for the worker's in-flight queries to finish and the
        reply reports the remaining count. Gateway-only op."""
        hdr: dict = {"op": "drain", "worker": worker}
        if wait_s is not None:
            hdr["wait_s"] = wait_s
        rep, _ = self._request(hdr)
        if not rep.get("ok"):
            raise KeyError(rep.get("error", f"drain {worker!r} failed"))
        return rep

    def undrain(self, worker: str) -> dict:
        """Return a drained fleet worker to the routable pool."""
        rep, _ = self._request({"op": "undrain", "worker": worker})
        if not rep.get("ok"):
            raise KeyError(rep.get("error", f"undrain {worker!r} failed"))
        return rep

    def fleet_stats(self) -> dict:
        """The gateway's registry snapshot: per-worker breaker state,
        outstanding depth, dispatch/failure counts, draining flags, route
        decisions, and live query placements. Gateway-only op."""
        rep, _ = self._request({"op": "fleet_stats"})
        if not rep.get("ok"):
            raise RuntimeError(rep.get("error", "fleet stats unavailable"))
        return rep["fleet"]

    def shutdown(self) -> None:
        self._request({"op": "shutdown"})
