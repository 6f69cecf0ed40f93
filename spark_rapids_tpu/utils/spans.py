"""Hierarchical query-profile span tracer (reference observability stack:
`GpuMetric`/`GpuTaskMetrics` + NVTX ranges + SQL-UI metrics + the offline
profiling tool, here folded into one per-query subsystem).

Three layers:

  * **Spans** — nested wall-clock regions, query -> operator -> phase
    (kernel / compile / spill / shuffle-fetch / semaphore-wait), each
    carrying counters (rows, batches, bytes, …). Nesting comes from a
    per-thread stack; a span opened on a worker thread with no enclosing
    span parents to the query root.
  * **QueryProfile** — thread-safe per-query registry: the operator tree
    (registered from the exec plan before execution, so even never-pulled
    operators appear), a per-operator `MetricsSet` baseline/final snapshot
    pair (reused exec instances — e.g. cached broadcasts — report only
    THIS query's deltas), the finished span list, and the task-level
    `TaskMetrics` snapshot.
  * **Exporters** — a schema-versioned JSONL event log (append-only, one
    self-contained record per line so a torn tail line never poisons the
    file) and `explain_profile()`, the SQL-UI analogue: the operator tree
    rendered with live metric values inline plus a phase rollup.

One primitive, two clocks: `span()` always opens a profiler annotation
(`utils/tracing.trace_range`, name prefixed `SPAN_PREFIX`), so every engine
span reaches a `jax.profiler` trace on the device trace's clock, and records
a `Span` into the `QueryProfile` only when one is active. A span named in
`tracing.STAGES` adds its wall time to the stage's `TaskMetrics` field,
profile or not, and `add=` counts (bytes, calls) ride any span: the
always-on counters live at the seams the spans mark.

Disabled-path contract: when no profile is active, `span()` yields the
shared no-op span (no `Span` is allocated) and, with no profiler session,
its annotation is an atomic load — profiling costs nothing until
`spark.rapids.tpu.metrics.eventLog.dir` or
`spark.rapids.tpu.metrics.profile.enabled` turns it on.
"""

from __future__ import annotations

import itertools
import json
import os
import threading
import time
import uuid
from typing import Any, Dict, List, Optional

from .metrics import TaskMetrics
from .tracing import SPAN_PREFIX, trace_range

__all__ = ["SCHEMA_VERSION", "SPAN_PREFIX", "Span", "QueryProfile", "span",
           "current_profile", "begin_profile", "end_profile",
           "write_event_log", "validate_record", "task_metrics_dict",
           "new_trace_id", "current_trace", "trace_scope",
           "write_client_record", "client_op_record", "append_jsonl",
           "format_adaptive_decision", "incident_record", "to_json_line"]

# v2 (live telemetry): every record carries `trace_id` (cross-process
# correlation — the id minted at query start rides the service headers
# and shuffle fetch metadata) and query records add a wall-clock `ts`
# (epoch seconds) so `profile_report.py --trace` can stitch client- and
# server-process records into one timeline (per-process monotonic
# start_ns values are incomparable across processes). v1 records remain
# valid: `validate_record` accepts both versions.
SCHEMA_VERSION = 2

# span kinds — the phase classes the report tool aggregates by
KIND_QUERY = "query"
KIND_OPERATOR = "operator"
KIND_COMPILE = "compile"
KIND_SPILL = "spill"
KIND_SHUFFLE = "shuffle"
KIND_SEMAPHORE = "semaphore"
KIND_KERNEL = "kernel"
KIND_IO = "io"
KIND_PHASE = "phase"
KIND_SERVICE = "service"   # cross-process service ops (client-side records)
KIND_CACHE = "cache"       # result/fragment cache seams (rescache/)

_KINDS = (KIND_QUERY, KIND_OPERATOR, KIND_COMPILE, KIND_SPILL, KIND_SHUFFLE,
          KIND_SEMAPHORE, KIND_KERNEL, KIND_IO, KIND_PHASE, KIND_SERVICE,
          KIND_CACHE)


def new_trace_id() -> str:
    """Mint a trace id (16 hex chars): one per query, shared by every
    process that touches it."""
    return uuid.uuid4().hex[:16]


class Span:
    """One finished (or open) trace region."""

    __slots__ = ("span_id", "parent_id", "name", "kind", "start_ns",
                 "start_unix_ns", "end_ns", "attrs")

    def __init__(self, span_id: int, parent_id: int, name: str, kind: str,
                 attrs: Optional[Dict[str, Any]] = None):
        self.span_id = span_id
        self.parent_id = parent_id
        self.name = name
        self.kind = kind
        self.start_ns = time.monotonic_ns()
        # the wall clock too, so a JSONL record can be laid beside a trace
        self.start_unix_ns = time.time_ns()
        self.end_ns: Optional[int] = None
        self.attrs: Dict[str, Any] = dict(attrs) if attrs else {}

    @property
    def dur_ns(self) -> int:
        end = self.end_ns if self.end_ns is not None else time.monotonic_ns()
        return end - self.start_ns

    def inc(self, **counters: int) -> None:
        a = self.attrs
        for k, v in counters.items():
            a[k] = a.get(k, 0) + v

    def put(self, **attrs: Any) -> None:
        self.attrs.update(attrs)


class _NoopSpan:
    """Shared do-nothing span: the entire disabled-path surface."""

    __slots__ = ()

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def inc(self, **counters) -> None:
        pass

    def put(self, **attrs) -> None:
        pass


NOOP_SPAN = _NoopSpan()

_tls = threading.local()
_current: Optional["QueryProfile"] = None
_mu = threading.Lock()

# telemetry's flight recorder registers here so every FINISHED span also
# lands in the incident ring ((span, profile) -> None). None (default)
# costs one module-global read per span exit; telemetry.configure sets it,
# telemetry.shutdown clears it.
_flight_hook = None


def set_flight_hook(hook) -> None:
    global _flight_hook
    _flight_hook = hook


def _stack() -> list:
    s = getattr(_tls, "stack", None)
    if s is None:
        s = _tls.stack = []
    return s


class _Scope:
    """One open `span()`: the profiler annotation (which charges a stage's
    TaskMetrics field), a real Span inside the active profile when there is
    one, and the `add` counts."""

    __slots__ = ("_range", "_prof", "_name", "_kind", "_attrs", "_span",
                 "_add")

    def __init__(self, prof: Optional["QueryProfile"], name: str, kind: str,
                 attrs: Dict[str, Any],
                 add: Optional[Dict[str, int]] = None):
        self._range = trace_range(name)
        self._prof = prof
        self._name = name
        self._kind = kind
        self._attrs = attrs
        self._span: Optional[Span] = None
        self._add = add

    def __enter__(self):
        self._range.__enter__()
        if self._prof is None:
            return NOOP_SPAN
        stack = _stack()
        parent = stack[-1].span_id if stack else QueryProfile.ROOT_SPAN_ID
        self._span = self._prof._open_span(self._name, self._kind, parent,
                                           self._attrs)
        stack.append(self._span)
        return self._span

    def __exit__(self, *exc) -> bool:
        sp = self._span
        if sp is not None:
            sp.end_ns = time.monotonic_ns()
            stack = _stack()
            # tolerate interleaved generator frames: pop this span wherever
            # it is
            if stack and stack[-1] is sp:
                stack.pop()
            elif sp in stack:
                stack.remove(sp)
            self._prof._record(sp)
            hook = _flight_hook
            if hook is not None:  # telemetry flight recorder (late-bound)
                hook(sp, self._prof)
        self._range.__exit__(*exc)
        if self._add:
            tm = TaskMetrics.get()
            for field, delta in self._add.items():
                tm.add(field, delta)
        return False


def _active_profile() -> Optional["QueryProfile"]:
    prof = _current
    if prof is None or prof.closed or getattr(_tls, "suppress", False):
        return None
    return prof


def span(name: str, kind: str = KIND_PHASE,
         add: Optional[Dict[str, int]] = None, **attrs) -> _Scope:
    """Open a span: always the profiler annotation `SPAN_PREFIX + name`
    (which adds its wall time to the stage's TaskMetrics field where
    `tracing.STAGES` names `name`), and a `Span` under the active query
    profile when there is one (else the `with` yields the shared no-op).
    `add` maps further TaskMetrics counters to their increments (a byte
    count, a call count), profile or not. Usage:
    ``with span("spill:to_host", kind="spill") as sp: ...``"""
    return _Scope(_active_profile(), name, kind, attrs, add)


def suppress_in_thread() -> None:
    """Turn spans off for the CURRENT thread. Background engine work that
    overlaps queries by design (the AOT warmup thread) calls this so its
    compile spans never pollute whichever query profile happens to be
    active — TaskMetrics, being thread-local, already excludes it."""
    _tls.suppress = True


def current_profile() -> Optional["QueryProfile"]:
    return _current


class trace_scope:
    """Bind a trace id to the CURRENT thread for a scope (the query's
    engine-side lifetime). `begin_profile` adopts it, and telemetry
    flight-recorder events stamp it, so one id correlates the profile,
    incident evidence, and the peer process that carried it here in a
    service header. Nests (adaptive stages restore the outer id)."""

    def __init__(self, trace_id: Optional[str]):
        self._tid = trace_id
        self._prev: Optional[str] = None

    def __enter__(self) -> Optional[str]:
        self._prev = getattr(_tls, "trace", None)
        _tls.trace = self._tid
        return self._tid

    def __exit__(self, *exc) -> bool:
        _tls.trace = self._prev
        return False


def current_trace() -> Optional[str]:
    """The active trace id: this thread's trace scope, else the active
    profile's (worker threads with no scope still correlate)."""
    tid = getattr(_tls, "trace", None)
    if tid:
        return tid
    prof = _current
    return prof.trace_id if prof is not None else None


def begin_profile(label: str = "query",
                  trace_id: Optional[str] = None) -> "QueryProfile":
    """Activate a fresh QueryProfile as the process-wide current profile
    (queries execute serially per session; worker threads inherit it).
    `trace_id` defaults to the thread's trace scope, else a fresh mint."""
    global _current
    prof = QueryProfile(label,
                        trace_id=trace_id or getattr(_tls, "trace", None))
    with _mu:
        _current = prof
    return prof


def end_profile(prof: "QueryProfile") -> None:
    """Deactivate `prof` if it is still current (mismatches are ignored so
    an exception-unwound nested begin cannot clear someone else's profile)."""
    global _current
    with _mu:
        if _current is prof:
            _current = None


def format_adaptive_decision(d: Dict[str, Any]) -> str:
    """One `rule: k=v ...` line for an AQE decision — the single
    formatter behind explain_profile and profile_report, so the two
    renderings of the same decision log cannot drift apart."""
    rule = d.get("rule", "?")
    rest = " ".join(f"{k}={d[k]}" for k in sorted(d) if k != "rule")
    return f"{rule}: {rest}"


def task_metrics_dict(tm) -> Dict[str, Any]:
    """Flatten a TaskMetrics instance to a JSON-safe dict (ints, the
    backoff list and the mesh device-id list)."""
    out: Dict[str, Any] = {}
    for k in dir(tm):
        if k.startswith("_"):
            continue
        v = getattr(tm, k)
        if isinstance(v, bool) or callable(v):
            continue
        if isinstance(v, int):
            out[k] = v
        elif isinstance(v, list):
            out[k] = [x if isinstance(x, int) else float(x) for x in v]
    return out


class QueryProfile:
    """Per-query aggregation of spans, operator metrics, and task metrics."""

    ROOT_SPAN_ID = 0
    _qid_counter = itertools.count(1)

    def __init__(self, label: str = "query",
                 trace_id: Optional[str] = None):
        self.query_id = f"{os.getpid()}-{next(QueryProfile._qid_counter)}"
        self.label = label
        self.trace_id = trace_id or new_trace_id()
        self.start_ts = time.time()   # wall clock, cross-process alignable
        self.start_ns = time.monotonic_ns()
        self.end_ns: Optional[int] = None
        self.closed = False
        # 'ok' | 'cancelled' | 'deadline' | 'rejected' — set by the
        # session when a query unwinds with a scheduler-typed error, so a
        # killed query's profile record says so (sched_matrix.sh gates it)
        self.status = "ok"
        # adaptive-execution decisions (plan/adaptive.py `_adaptive_log`:
        # staging coalesces, skew splits, history pre-flags) — attached
        # by the session so explain_profile and the event-log query
        # record surface what AQE actually did, not just its effects
        self.adaptive: List[Dict[str, Any]] = []
        self.task_metrics: Dict[str, Any] = {}
        self._mu = threading.RLock()
        self._next_span = itertools.count(1)  # 0 is the query root
        self._spans: List[Span] = []
        self._op_ids: Dict[int, int] = {}     # id(exec) -> op_id
        self._op_meta: List[Dict[str, Any]] = []

    # ------------------------------------------------------------- spans
    def _open_span(self, name: str, kind: str, parent_id: int,
                   attrs: Dict[str, Any]) -> Span:
        with self._mu:
            sid = next(self._next_span)
        return Span(sid, parent_id, name, kind, attrs)

    def _record(self, sp: Span) -> None:
        with self._mu:
            if not self.closed:
                self._spans.append(sp)

    @property
    def spans(self) -> List[Span]:
        with self._mu:
            return list(self._spans)

    # --------------------------------------------------------- operators
    def attach_plan(self, root) -> None:
        """Register an exec tree (TpuExec) before execution: the profile
        then knows the full operator topology even for operators whose
        iterators are never pulled."""
        def walk(node, parent_id):
            oid = self._register(node, parent_id)
            for child in getattr(node, "children", ()):
                if hasattr(child, "metrics"):
                    walk(child, oid)
        walk(root, None)

    def _register(self, node, parent_id) -> int:
        with self._mu:
            key = id(node)
            if key in self._op_ids:
                return self._op_ids[key]
            oid = len(self._op_meta)
            self._op_ids[key] = oid
            try:
                args = node._arg_string()
            except Exception:
                args = ""
            self._op_meta.append({
                "op_id": oid,
                "parent_id": parent_id,
                "name": node.name,
                "args": args,
                "metrics_set": node.metrics,
                "baseline": node.metrics.snapshot(),
                "values": {},
            })
            return oid

    def ensure_operator(self, node) -> int:
        """op_id for `node`, registering it under the root on the fly if
        the plan walk never saw it (dynamically created execs)."""
        with self._mu:
            oid = self._op_ids.get(id(node))
        if oid is not None:
            return oid
        return self._register(node, None)

    # ------------------------------------------------------------ finish
    def finish(self, task_metrics=None) -> None:
        """Close the profile: snapshot every operator's metrics as deltas
        against its registration baseline, capture TaskMetrics, end the
        query span. Idempotent."""
        with self._mu:
            if self.closed:
                return
            self.end_ns = time.monotonic_ns()
            for meta in self._op_meta:
                final = meta["metrics_set"].snapshot()
                base = meta["baseline"]
                meta["values"] = {k: v - base.get(k, 0)
                                  for k, v in final.items()}
                meta.pop("metrics_set", None)
            if task_metrics is not None:
                self.task_metrics = task_metrics_dict(task_metrics)
            self.closed = True

    @property
    def wall_ns(self) -> int:
        end = self.end_ns if self.end_ns is not None else time.monotonic_ns()
        return end - self.start_ns

    # --------------------------------------------------------- exporters
    def operator_table(self) -> List[Dict[str, Any]]:
        with self._mu:
            return [{k: v for k, v in m.items() if k not in
                     ("metrics_set", "baseline")} for m in self._op_meta]

    def phase_totals(self) -> Dict[str, Dict[str, int]]:
        """Aggregate finished spans by kind: {kind: {count, dur_ns, bytes}}."""
        out: Dict[str, Dict[str, int]] = {}
        for sp in self.spans:
            d = out.setdefault(sp.kind, {"count": 0, "dur_ns": 0, "bytes": 0})
            d["count"] += 1
            d["dur_ns"] += sp.dur_ns
            d["bytes"] += int(sp.attrs.get("bytes", 0))
        return out

    def to_records(self) -> List[Dict[str, Any]]:
        """One schema-versioned JSON record per query/operator/span."""
        recs: List[Dict[str, Any]] = [{
            "v": SCHEMA_VERSION, "type": "query",
            "query_id": self.query_id, "trace_id": self.trace_id,
            "label": self.label,
            "status": self.status,
            "ts": self.start_ts,
            "wall_ns": self.wall_ns,
            "task_metrics": dict(self.task_metrics),
            "n_operators": len(self._op_meta),
            "n_spans": len(self._spans) + 1,
            "adaptive": list(self.adaptive),
        }]
        for m in self.operator_table():
            recs.append({
                "v": SCHEMA_VERSION, "type": "operator",
                "query_id": self.query_id, "trace_id": self.trace_id,
                "op_id": m["op_id"],
                "parent_id": m["parent_id"], "name": m["name"],
                "args": m["args"], "metrics": dict(m["values"]),
            })
        recs.append({
            "v": SCHEMA_VERSION, "type": "span",
            "query_id": self.query_id, "trace_id": self.trace_id,
            "span_id": self.ROOT_SPAN_ID,
            "parent_id": None, "name": self.label, "kind": KIND_QUERY,
            "start_ns": self.start_ns, "dur_ns": self.wall_ns, "attrs": {},
        })
        for sp in self.spans:
            recs.append({
                "v": SCHEMA_VERSION, "type": "span",
                "query_id": self.query_id, "trace_id": self.trace_id,
                "span_id": sp.span_id,
                "parent_id": sp.parent_id, "name": sp.name, "kind": sp.kind,
                "start_ns": sp.start_ns, "dur_ns": sp.dur_ns,
                "start_unix_ns": sp.start_unix_ns,
                "attrs": dict(sp.attrs),
            })
        return recs

    def explain_profile(self) -> str:
        """Operator tree with live metrics inline plus the phase rollup —
        the SQL-UI metrics analogue, as text."""
        table = self.operator_table()
        children: Dict[Optional[int], List[Dict[str, Any]]] = {}
        for m in table:
            children.setdefault(m["parent_id"], []).append(m)
        lines = [f"QueryProfile[{self.query_id}] {self.label} "
                 f"wall={_fmt_ns(self.wall_ns)}"]

        def fmt_metrics(vals: Dict[str, int]) -> str:
            parts = []
            for k in sorted(vals):
                v = vals[k]
                if not v:
                    continue
                parts.append(f"{k}={_fmt_ns(v)}" if k.lower().endswith("time")
                             else f"{k}={v}")
            return ", ".join(parts)

        def walk(m, depth):
            ms = fmt_metrics(m["values"])
            lines.append("  " * (depth + 1) + m["name"] + m["args"]
                         + (f": {ms}" if ms else ""))
            for c in children.get(m["op_id"], ()):
                walk(c, depth + 1)

        for root in children.get(None, ()):
            walk(root, 0)
        totals = self.phase_totals()
        if totals:
            lines.append("  phases:")
            for kind in sorted(totals):
                d = totals[kind]
                extra = f" bytes={d['bytes']}" if d["bytes"] else ""
                lines.append(f"    {kind}: n={d['count']} "
                             f"time={_fmt_ns(d['dur_ns'])}{extra}")
        if self.task_metrics:
            hot = {k: v for k, v in self.task_metrics.items()
                   if v and not isinstance(v, list)}
            if hot:
                lines.append("  task: " + ", ".join(
                    f"{k}={v}" for k, v in sorted(hot.items())))
        if self.adaptive:
            lines.append("  adaptive:")
            for d in self.adaptive:
                lines.append("    " + format_adaptive_decision(d))
        return "\n".join(lines)


def _fmt_ns(ns: int) -> str:
    if abs(ns) >= 1_000_000:
        return f"{ns / 1e6:.1f}ms"
    if abs(ns) >= 1_000:
        return f"{ns / 1e3:.1f}us"
    return f"{ns}ns"


# ------------------------------------------------------------------ event log
def _rotate(path: str, max_files: int) -> None:
    """Shift `path` -> `.1`, `.1` -> `.2`, ... keeping at most `max_files`
    rotated generations (the oldest falls off). Best-effort: rotation
    failure must not lose the append."""
    try:
        oldest = f"{path}.{max_files}"
        if os.path.exists(oldest):
            os.unlink(oldest)
        for i in range(max_files - 1, 0, -1):
            src = f"{path}.{i}"
            if os.path.exists(src):
                os.replace(src, f"{path}.{i + 1}")
        if os.path.exists(path):
            os.replace(path, f"{path}.1")
    except OSError:
        pass


# serializes size-check + rotate + append: concurrent scheduled queries
# finishing together on one per-process file must not BOTH see the cap
# crossed and double-rotate (which would shift a fresh generation up and
# drop the oldest retained log early)
_append_mu = threading.Lock()


def append_jsonl(path: str, payload: str, max_bytes: int = 0,
                 max_files: int = 10) -> str:
    """Append `payload` to a JSONL file with size-capped rotation: when
    `max_bytes` > 0 and the append would push the live file past it, the
    live file rotates to `.1` (shifting older generations up) first, so a
    long-lived server's event log is bounded at roughly
    `max_bytes * (max_files + 1)` on disk. The report tool reads rotated
    generations alongside live files."""
    with _append_mu:
        if max_bytes > 0:
            try:
                size = os.path.getsize(path)
            except OSError:
                size = 0
            if size > 0 and size + len(payload) > max_bytes:
                _rotate(path, max_files)
        with open(path, "a") as f:
            f.write(payload)
    return path


def write_event_log(prof: QueryProfile, log_dir: str,
                    max_bytes: int = 0, max_files: int = 10) -> str:
    """Append the profile's records to the per-process JSONL event log under
    `log_dir` (created if missing). Append-only, one self-contained record
    per line: a torn final line (crash mid-write) damages only itself, and
    concatenating logs from many executors is just `cat`. `max_bytes`
    (spark.rapids.tpu.metrics.eventLog.maxBytes) bounds the live file via
    `.1`/`.2`/... rotation; 0 keeps the historical unbounded append."""
    payload = "".join(json.dumps(r, separators=(",", ":"),
                                 default=_json_default) + "\n"
                      for r in prof.to_records())
    return _durable_append(log_dir, payload, max_bytes, max_files)


def _durable_append(log_dir: str, payload: str, max_bytes: int,
                    max_files: int) -> str:
    """The event log is a durable tier (utils/durable.py): a dead disk
    degrades logging to a no-op under the shared typed-warning/counter/
    incident sequence instead of failing the query that tried to log."""
    from . import durable
    t = durable.tier("eventlog", log_dir)

    def write():
        os.makedirs(log_dir, exist_ok=True)
        path = os.path.join(log_dir, f"events-{os.getpid()}.jsonl")
        return append_jsonl(path, payload, max_bytes, max_files)

    return t.run("append", write, default="")


def client_op_record(op: str, trace_id: str, dur_ns: int, status: str = "ok",
                     query_id: str = "", **attrs: Any) -> Dict[str, Any]:
    """A v2 span record describing one client-side service op (run_plan /
    acquire): what the CLIENT process contributes to a cross-process
    trace. `profile_report.py --trace` stitches these against the server
    profile records sharing the trace id."""
    a = {"status": status, "pid": os.getpid()}
    a.update(attrs)
    return {
        "v": SCHEMA_VERSION, "type": "span",
        "query_id": query_id or f"client-{os.getpid()}",
        "trace_id": trace_id,
        "span_id": 0, "parent_id": None,
        "name": f"client:{op}", "kind": KIND_SERVICE,
        "start_ns": time.monotonic_ns() - dur_ns, "dur_ns": dur_ns,
        # `ts` is the op START (records are built in the caller's finally,
        # i.e. at op end): every `ts` in the schema marks a beginning, and
        # the --trace timeline sorts by it — stamping the end here would
        # render the submitting client op AFTER the server query it caused
        "ts": time.time() - dur_ns / 1e9,
        "attrs": a,
    }


def write_client_record(log_dir: str, record: Dict[str, Any],
                        max_bytes: int = 0, max_files: int = 10) -> str:
    """Append one record to this process's event log (the client-side half
    of trace correlation; same file naming/rotation as write_event_log)."""
    payload = json.dumps(record, separators=(",", ":"),
                         default=_json_default) + "\n"
    return _durable_append(log_dir, payload, max_bytes, max_files)


def _json_default(o):
    try:
        import numpy as _np
        if isinstance(o, _np.integer):
            return int(o)
        if isinstance(o, _np.floating):
            return float(o)
    except Exception:
        pass
    return str(o)


def to_json_line(rec: Dict[str, Any]) -> str:
    """One compact JSONL line with the shared numpy-tolerant fallback —
    every incident/event writer serializes through this."""
    return json.dumps(rec, separators=(",", ":"), default=_json_default)


def incident_record(reason: str, trace_id: str = "", n_events: int = 0,
                    attrs: Optional[Dict[str, Any]] = None
                    ) -> Dict[str, Any]:
    """The schema-v2 incident HEADER record — the single composer behind
    FlightRecorder.dump and live.debug_dump's recorder-less fallback, so
    a schema change cannot make one writer's dumps invalid while the
    other's stay current."""
    return {"v": SCHEMA_VERSION, "type": "incident", "reason": reason,
            "trace_id": trace_id or "", "ts": time.time(),
            "pid": os.getpid(), "n_events": int(n_events),
            "attrs": dict(attrs or {})}


# ----------------------------------------------------------------- validation
_REQUIRED: Dict[str, Dict[str, Any]] = {
    "query": {"query_id": str, "label": str, "wall_ns": int,
              "task_metrics": dict, "n_operators": int, "n_spans": int},
    "operator": {"query_id": str, "op_id": int, "name": str,
                 "args": str, "metrics": dict},
    "span": {"query_id": str, "span_id": int, "name": str, "kind": str,
             "start_ns": int, "dur_ns": int, "attrs": dict},
}

# v2 additions: trace correlation on the profile record types, plus the
# flight-recorder incident-file types (recorder dumps validate with the
# same authority as event logs — one definition of "valid")
_REQUIRED_V2_EXTRA: Dict[str, Dict[str, Any]] = {
    "query": {"trace_id": str, "ts": (int, float)},
    "operator": {"trace_id": str},
    "span": {"trace_id": str},
}
_REQUIRED_V2_ONLY: Dict[str, Dict[str, Any]] = {
    "incident": {"reason": str, "trace_id": str, "ts": (int, float),
                 "pid": int, "n_events": int, "attrs": dict},
    "event": {"seq": int, "ts": (int, float), "t_ns": int, "kind": str,
              "name": str, "trace_id": str, "attrs": dict},
    # runtime statistics (stats/): one estimate-vs-actual record per
    # estimated operator per query — profile_report --stats ranks the
    # worst misestimates across queries from these
    "stats": {"query_id": str, "trace_id": str, "op": str, "digest": str,
              "est_rows": (int, float), "actual_rows": int,
              "q_error": (int, float), "attrs": dict},
}

_VALID_VERSIONS = (1, 2)


def _type_name(typ) -> str:
    if isinstance(typ, tuple):
        return "/".join(t.__name__ for t in typ)
    return typ.__name__


def validate_record(rec: Any) -> List[str]:
    """Schema check of one event-log / incident-file record; returns a
    list of problems (empty = valid). Shared by the report tool, the
    matrix scripts and the tests so 'valid' means one thing. Accepts both
    schema versions: v1 (pre-trace) records stay valid forever — mixed
    logs from old and new processes validate together — while v2 records
    additionally require `trace_id` (and `ts` on query records)."""
    errs: List[str] = []
    if not isinstance(rec, dict):
        return [f"record is {type(rec).__name__}, not an object"]
    v = rec.get("v")
    if v not in _VALID_VERSIONS:
        errs.append(f"schema version {v!r} not in {_VALID_VERSIONS}")
        v = SCHEMA_VERSION
    rtype = rec.get("type")
    req = dict(_REQUIRED.get(rtype, ()))
    if v >= 2:
        req.update(_REQUIRED_V2_EXTRA.get(rtype, ()))
        if not req:
            req = dict(_REQUIRED_V2_ONLY.get(rtype, ()))
    if not req:
        errs.append(f"unknown record type {rtype!r}"
                    + (" (v2-only type in a v1 record)"
                       if rtype in _REQUIRED_V2_ONLY else ""))
        return errs
    for field, typ in req.items():
        if field not in rec:
            errs.append(f"{rtype}: missing field {field!r}")
        elif isinstance(rec[field], bool) or \
                not isinstance(rec[field], typ):
            errs.append(f"{rtype}.{field}: expected {_type_name(typ)}, "
                        f"got {type(rec[field]).__name__}")
    if rtype == "span" and rec.get("kind") not in _KINDS:
        errs.append(f"span.kind {rec.get('kind')!r} not in {_KINDS}")
    return errs
