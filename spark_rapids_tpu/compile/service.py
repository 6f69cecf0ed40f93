"""Centralized XLA compile service — the single path to a compiled executable.

The reference engine pays kernel-LAUNCH costs but never compilation costs:
CUDA kernels take runtime sizes. This engine compiles one XLA program per
(operator, shape-bucket) and, before this service existed, did so through
~13 ad-hoc `jax.jit` call sites with no caching policy, no accounting, and a
cold compile on every process start — the compile-overhead amortization
problem "Rethinking Analytical Processing in the GPU Era" names, solved the
way Theseus solves it: a reusable compiled-operator library.

Architecture (see ARCHITECTURE.md "Compile service"):

  * cache key = `op name x instance key x static args x avals` — `op` is the
    operator family (e.g. ``exec.project``), the instance key digests
    whatever the kernel closure bakes in (bound expression reprs, output
    schema, eval-affecting conf), static args are the jit-static leaves and
    avals are the (shape, dtype, treedef) signature of the dynamic
    arguments. Identical queries in fresh exec instances therefore map to
    the SAME key and reuse the executable.
  * in-memory tier: LRU of AOT-compiled executables
    (`jax.jit(fn).lower(*args).compile()`), capacity
    ``spark.rapids.tpu.compile.cache.maxPrograms``.
  * persistent tier: serialized programs under
    ``spark.rapids.tpu.compile.cache.dir`` (empty = disabled) via
    `jax.export` (StableHLO + calling convention; the backend re-compiles on
    load but never re-traces) — each entry CRC32C-framed (shuffle/codec
    helper) so a torn or poisoned file is a miss + delete, never a wrong
    program.
  * single-flight: concurrent service threads asking for the same key wait
    on the first thread's compile instead of compiling twice.
  * observability: every program runs under `program_name(op)`, so a
    profiler trace names it by its operator family; global per-op
    `CompileStats` (a compile's wall time split into its trace, lower and
    backend stages) plus per-task counters in `TaskMetrics` (surfaced by
    `explain_string()`); a ``compile:<op>`` span around every real compile
    and, on the profiler's clock only, ``dispatch.<op>`` around every call
    and ``compile.<stage>.<op>`` around each stage.
  * faults: the ``compile`` injection point (faults.py) fires before a
    compile (error/wedge) and over persisted bytes on read (corrupt).
    ANY service failure degrades to a direct `jax.jit` call under a
    `CompileServiceWarning` — the service can slow a query down, never
    break it.

ANSI error-message boxes: kernels return traced error FLAGS and park the
matching messages in a host-side list at trace time (`exec.base
.kernel_errors`). A cache hit skips tracing, so the service snapshots each
box at compile time (and into the persisted entry's metadata) and restores
it on every hit — flag/message pairing survives executable reuse.
"""

from __future__ import annotations

import collections
import contextlib
import hashlib
import json
import os
import struct
import threading
import time
import warnings
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..errors import CompileServiceWarning
from ..utils import spans
from ..utils.tracing import trace_range

__all__ = ["CompileService", "CompileStats", "ServiceJit", "sjit",
           "instance_jit", "kernel_key", "program_name"]

_MAGIC = b"SRTC1"
_HDR = struct.Struct("<5sBII")  # magic, format, crc32c, meta length
_FMT_EXPORT = 2  # jax.export StableHLO blob (re-backend-compiles on load)

_EXPORT_REGISTERED = False


def _register_export_serialization() -> None:
    """Register the engine's custom pytree nodes with jax.export so
    ColumnarBatch/Column/Vec-shaped programs serialize (idempotent)."""
    global _EXPORT_REGISTERED
    if _EXPORT_REGISTERED:
        return
    import pickle

    import jax.export as jex

    from ..columnar.batch import ColumnarBatch
    from ..columnar.column import Column
    from ..expr.base import Vec
    for cls in (ColumnarBatch, Column, Vec):
        try:
            jex.register_pytree_node_serialization(
                cls, serialized_name=f"srtpu.{cls.__name__}",
                serialize_auxdata=pickle.dumps,
                deserialize_auxdata=pickle.loads)
        except ValueError:  # already registered (e.g. by a second session)
            pass
    _EXPORT_REGISTERED = True


def _leaf_sig(x) -> tuple:
    """(shape, dtype, placement) signature of one dynamic-argument leaf.
    Python scalars trace weakly typed, so only their TYPE keys the
    program. Placement joins the key because an AOT executable is
    compiled FOR its input shardings: mesh shard batches (mesh/shard.py)
    are committed each to their own chip, and an executable compiled for
    chip 0 rejects chip 3's inputs — without the placement component
    every per-shard call would evict/fall back instead of getting its own
    cached program. Uncommitted leaves (the entire single-device engine)
    contribute an empty component, so their keys are placement-free."""
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        dev = ""
        if getattr(x, "committed", False):
            try:
                ds = x.devices()
                if len(ds) == 1:
                    dev = f"d{next(iter(ds)).id}"
                else:
                    dev = str(x.sharding)
            except Exception:
                dev = ""
        return (tuple(x.shape), str(x.dtype), dev)
    return ("py", type(x).__name__)


def _static_sig(v) -> str:
    """Stable textual signature of one static argument. StaticExpr wraps an
    expression with identity hashing (for jax); its repr is the faithful
    key. Callables key by qualified name."""
    from ..exec.base import StaticExpr
    if isinstance(v, StaticExpr):
        return f"expr:{v.expr!r}"
    if callable(v):
        return (f"fn:{getattr(v, '__module__', '')}."
                f"{getattr(v, '__qualname__', repr(v))}")
    with np.printoptions(threshold=2 ** 31, precision=17):
        return repr(v)


# conf keys that can never change a traced program: kept OUT of the digest
# so toggling explain, pointing at a different compile-cache dir, or
# installing fault rules doesn't orphan every cached executable
_KEY_IRRELEVANT_PREFIXES = (
    "spark.rapids.sql.explain",
    "spark.rapids.sql.test.",
    "spark.rapids.tpu.test.",
    "spark.rapids.tpu.compile.",
    "spark.rapids.sql.metrics.",
)


def kernel_key(*parts, conf=None) -> str:
    """Digest closure-baked kernel parameters (bound expression reprs,
    schemas, mode flags) plus the eval-affecting conf into an instance key.
    Full repr under unbounded numpy print options so array-valued literals
    can't alias each other. The conf digest is deliberately BROAD (all
    settings minus the trace-irrelevant prefixes above): an unnecessary
    recompile is cheap, a wrongly shared executable is not."""
    with np.printoptions(threshold=2 ** 31, precision=17):
        text = "\x1f".join(repr(p) for p in parts)
        if conf is not None:
            text += "\x1f" + repr(sorted(
                (k, repr(v)) for k, v in conf._settings.items()
                if not k.startswith(_KEY_IRRELEVANT_PREFIXES)))
    return hashlib.sha256(text.encode()).hexdigest()[:24]


def program_name(op: str) -> str:
    """The name a program of operator family `op` is jitted under: the XLA
    module is `jit_<name>` and a profiler trace prints it so. Part of the
    program's digest, and of JAX's persistent-cache key."""
    return op


def _named(fn: Callable, op: str) -> Callable:
    """A fresh wrapper of `fn` called `program_name(op)`, for `jax.jit` to
    take the module's name from. `fn` itself is shared and keeps its name."""
    def program(*args):
        return fn(*args)
    program.__name__ = program.__qualname__ = program_name(op)
    return program


class _Entry:
    __slots__ = ("compiled", "msgs", "op", "source")

    def __init__(self, compiled: Callable, msgs: List[List[str]], op: str,
                 source: str):
        self.compiled = compiled
        self.msgs = msgs          # one snapshot per error-message box
        self.op = op
        self.source = source      # "compile" | "persist"


class CompileStats:
    """Process-wide compile accounting, per op and total."""

    # trace_ns + lower_ns + backend_ns: the AOT stages inside compile_ns
    _FIELDS = ("compiles", "compile_ns", "hits", "misses", "persist_hits",
               "persist_stores", "persist_errors", "poisoned", "fallbacks",
               "trace_ns", "lower_ns", "backend_ns")

    def __init__(self):
        self._mu = threading.Lock()
        self._per_op: Dict[str, Dict[str, int]] = {}

    def bump(self, op: str, **deltas: int) -> None:
        with self._mu:
            d = self._per_op.setdefault(
                op, {f: 0 for f in self._FIELDS})
            for k, v in deltas.items():
                d[k] += v

    def per_op(self) -> Dict[str, Dict[str, int]]:
        with self._mu:
            return {op: dict(d) for op, d in self._per_op.items()}

    def totals(self) -> Dict[str, int]:
        out = {f: 0 for f in self._FIELDS}
        for d in self.per_op().values():
            for k, v in d.items():
                out[k] += v
        return out

    def reset(self) -> None:
        with self._mu:
            self._per_op.clear()


class ServiceJit:
    """A compile-service-managed jitted callable: drop-in for `jax.jit(fn,
    static_argnums=...)`. `op` names the operator family; `key` digests
    whatever the closure bakes in (use `kernel_key`); `msgs_box` is the
    exec's ANSI message box (restored on cache hits). Marked hashable by
    identity so call sites can keep dict bookkeeping keyed on the jitted
    object (exec/aggregate.py's kernel boxes)."""

    __slots__ = ("fn", "op", "static_argnums", "key", "msgs_box", "_direct",
                 "_code_fp")

    def __init__(self, fn: Callable, op: str,
                 static_argnums: Sequence[int] = (), key: str = "",
                 msgs_box: Optional[list] = None):
        self.fn = fn
        self.op = op
        self.static_argnums = tuple(static_argnums)
        self.key = key
        self.msgs_box = msgs_box
        self._direct = None
        self._code_fp = None

    @property
    def code_fingerprint(self) -> str:
        """Bytecode digest of the kernel function: a code edit in a future
        build must invalidate persisted executables compiled by the old
        one (the digest feeds the cache key). Shallow by design — callee
        changes are caught by the jax-version component and, at worst, by
        the entry's op/key/avals churn — and cheap (computed once)."""
        if self._code_fp is None:
            fn = self.fn
            # unwrap functools.partial / bound methods to the code object
            while hasattr(fn, "func"):
                fn = fn.func
            code = getattr(fn, "__code__", None)
            if code is None:
                self._code_fp = repr(fn)
            else:
                h = hashlib.sha256()

                def feed(c):  # recurse nested code objects address-free
                    h.update(c.co_code)
                    for const in c.co_consts:
                        if hasattr(const, "co_code"):
                            feed(const)
                        else:
                            h.update(repr(const).encode())
                feed(code)
                self._code_fp = h.hexdigest()[:16]
        return self._code_fp

    @property
    def direct(self) -> Callable:
        """The plain `jax.jit` fallback (lazy; also the degraded path when
        the service is disabled or wounded)."""
        if self._direct is None:
            import jax
            self._direct = jax.jit(_named(self.fn, self.op),
                                   static_argnums=self.static_argnums)
        return self._direct

    def __call__(self, *args):
        return CompileService.get().call(self, args)


def sjit(fn: Callable = None, *, op: str, static_argnums: Sequence[int] = (),
         key: str = "", msgs_box: Optional[list] = None):
    """Decorator form for module-level kernels:
        @sjit(op="exec.sort.by_pos")
        def _sort_by_pos(batch): ...
    """
    def wrap(f):
        return ServiceJit(f, op=op, static_argnums=static_argnums, key=key,
                          msgs_box=msgs_box)
    return wrap if fn is None else wrap(fn)


def instance_jit(fn: Callable, *, op: str, key: str = "",
                 msgs_box: Optional[list] = None,
                 static_argnums: Sequence[int] = ()) -> ServiceJit:
    """Per-exec-instance kernels: `key` MUST digest everything the closure
    bakes into the trace (bound expressions, output schema, conf) — build it
    with `kernel_key`. Two instances with equal keys share the executable."""
    return ServiceJit(fn, op=op, static_argnums=static_argnums, key=key,
                      msgs_box=msgs_box)


class CompileService:
    """Process-wide program cache + compile pipeline (singleton)."""

    _instance: Optional["CompileService"] = None
    _cls_lock = threading.Lock()

    COMPILE_WAIT_S = 600.0  # single-flight waiters give up after this

    def __init__(self):
        self._mu = threading.Lock()
        self._mem: "collections.OrderedDict[str, _Entry]" = \
            collections.OrderedDict()
        self._inflight: Dict[str, threading.Event] = {}
        self._enabled = True
        self._max_programs = 512
        self._dir = ""
        # per-invocation kernel spans (spark.rapids.tpu.metrics.spans.
        # kernel.enabled): off by default — one span per batch per kernel
        self._kernel_spans = False
        self.stats = CompileStats()
        self._warned_persist = False
        self._tier = None  # utils/durable.DurableTier once a dir is set
        self.warmup_thread: Optional[threading.Thread] = None

    # ------------------------------------------------------------------
    @classmethod
    def get(cls) -> "CompileService":
        with cls._cls_lock:
            if cls._instance is None:
                cls._instance = CompileService()
            return cls._instance

    @classmethod
    def reset(cls) -> None:
        """Drop the singleton (tests). Running warmup threads finish
        against the old instance harmlessly."""
        with cls._cls_lock:
            cls._instance = None

    def configure(self, conf) -> None:
        """Apply `spark.rapids.tpu.compile.*` and kick off warmup/tuner per
        conf (TpuSession.initialize_device calls this)."""
        with self._mu:
            self._enabled = bool(
                conf.get("spark.rapids.tpu.compile.enabled"))
            self._max_programs = int(
                conf.get("spark.rapids.tpu.compile.cache.maxPrograms"))
            self._dir = conf.get("spark.rapids.tpu.compile.cache.dir") or ""
            self._kernel_spans = bool(conf.get(
                "spark.rapids.tpu.metrics.spans.kernel.enabled"))
        if self._dir:
            # durable-tier discipline (utils/durable.py): any IO failure —
            # here or on a later store/load — degrades the persistent tier
            # to memory-only under the shared warning/counter/incident
            # sequence; the in-memory LRU keeps serving
            from ..utils import durable
            self._tier = durable.tier("compile", self._dir)
            self._tier.run("mkdir", lambda: os.makedirs(self._dir,
                                                        exist_ok=True))
        from .tuner import BucketTuner
        BucketTuner.get().configure(conf)
        if self._enabled and conf.get(
                "spark.rapids.tpu.compile.warmup.enabled"):
            from .warmup import start_warmup
            self.warmup_thread = start_warmup(conf, self)

    # ------------------------------------------------------------------
    def clear_memory(self) -> None:
        """Drop the in-memory tier only (simulates a process restart: the
        next lookups fall through to the persistent tier)."""
        with self._mu:
            self._mem.clear()

    def cached_programs(self) -> int:
        with self._mu:
            return len(self._mem)

    @property
    def persistent_dir(self) -> str:
        return self._dir if self._persist_ok() else ""

    def _persist_ok(self) -> bool:
        if not self._dir:
            return False
        if self._tier is None or self._tier.path != self._dir:
            # tests point _dir at a tmpdir directly; lazily bind its tier
            from ..utils import durable
            self._tier = durable.tier("compile", self._dir)
        return self._tier.available()

    # ------------------------------------------------------------------
    def call(self, sj: ServiceJit, args: tuple):
        # digest, lookup and launch, on the profiler's clock only: one per
        # batch per kernel is too many for a query profile, where the
        # opt-in `kernel:<op>` span below stays what it was
        with trace_range(f"dispatch.{sj.op}"):
            return self._call(sj, args)

    def _call(self, sj: ServiceJit, args: tuple):
        if not self._enabled:
            self._count_dispatch(args)
            return sj.direct(*args)
        try:
            import jax
            statics, dyn, boxes = self._split(sj, args)
            leaves, treedef = jax.tree_util.tree_flatten(dyn)
            if any(isinstance(l, jax.core.Tracer) for l in leaves):
                # nested call inside another kernel's trace: an AOT
                # executable can't consume tracers — inline via plain jit
                # (jax's own nested-jit semantics), no cache bookkeeping.
                # NOT a device dispatch: it inlines into the outer program.
                return sj.direct(*args)
            digest = self._digest(sj, statics, leaves, treedef)
        except Exception:
            # unhashable/unsignable arguments: not service material
            self._count_dispatch(args)
            return sj.direct(*args)
        self._task_metrics().device_dispatches += 1
        entry = self._mem_get(sj, digest)
        if entry is None:
            entry = self._compile_or_wait(digest, sj, statics, dyn, boxes)
            if entry is None:
                # the compiling thread already warned with the real cause;
                # this thread just takes the degraded path
                return sj.direct(*args)
        self._restore_boxes(entry, boxes)
        try:
            if self._kernel_spans:
                with spans.span(f"kernel:{sj.op}", kind=spans.KIND_KERNEL,
                                op=sj.op):
                    return entry.compiled(*dyn)
            return entry.compiled(*dyn)
        except Exception as e:
            # a stale/poisoned executable must never fail the query: evict
            # and take the direct path (identical program, fresh trace)
            self._evict(digest)
            self._fallback(sj, f"cached executable rejected call: "
                               f"{type(e).__name__}: {e}")
            return sj.direct(*args)

    # ------------------------------------------------------------------
    def _split(self, sj: ServiceJit, args: tuple):
        """(static values, dynamic args, error-message boxes) for one call."""
        from ..exec.base import StaticExpr
        statics = tuple(args[i] for i in sj.static_argnums)
        dyn = tuple(a for i, a in enumerate(args)
                    if i not in sj.static_argnums)
        boxes = [] if sj.msgs_box is None else [sj.msgs_box]
        boxes += [s.err_msgs for s in statics if isinstance(s, StaticExpr)]
        return statics, dyn, boxes

    def _digest(self, sj: ServiceJit, statics: tuple, leaves: list,
                treedef) -> str:
        import jax
        text = "\x1f".join((
            sj.op, program_name(sj.op), sj.key, sj.code_fingerprint,
            jax.__version__,
            "|".join(_static_sig(s) for s in statics),
            repr(tuple(_leaf_sig(l) for l in leaves)),
            str(treedef),
        ))
        return hashlib.sha256(text.encode()).hexdigest()

    # ------------------------------------------------------------------
    def _mem_get(self, sj: ServiceJit, digest: str) -> Optional[_Entry]:
        with self._mu:
            entry = self._mem.get(digest)
            if entry is not None:
                self._mem.move_to_end(digest)
        if entry is not None:
            self.stats.bump(sj.op, hits=1)
            tm = self._task_metrics()
            tm.compile_cache_hits += 1
        return entry

    def _store_mem(self, digest: str, entry: _Entry) -> None:
        with self._mu:
            self._mem[digest] = entry
            self._mem.move_to_end(digest)
            while len(self._mem) > self._max_programs:
                self._mem.popitem(last=False)

    def _evict(self, digest: str) -> None:
        with self._mu:
            self._mem.pop(digest, None)

    # ------------------------------------------------------------------
    def _compile_or_wait(self, digest: str, sj: ServiceJit, statics: tuple,
                         dyn: tuple, boxes: List[list]) -> Optional[_Entry]:
        with self._mu:
            ev = self._inflight.get(digest)
            owner = ev is None
            if owner:
                ev = self._inflight[digest] = threading.Event()
        if not owner:
            ev.wait(timeout=self.COMPILE_WAIT_S)
            return self._mem_get(sj, digest)
        try:
            self.stats.bump(sj.op, misses=1)
            self._task_metrics().compile_cache_misses += 1
            entry = self._load_persistent(digest, sj)
            if entry is None:
                entry = self._do_compile(digest, sj, statics, dyn, boxes)
            if entry is not None:
                self._store_mem(digest, entry)
            return entry
        finally:
            with self._mu:
                self._inflight.pop(digest, None)
            ev.set()

    def _dyn_fn(self, sj: ServiceJit, statics: tuple) -> Callable:
        """Close the static arguments over `fn`, leaving a dynamic-only
        signature (what both the AOT compile and the export serialize)."""
        if not sj.static_argnums:
            return sj.fn
        static_at = dict(zip(sj.static_argnums, statics))

        def dyn_fn(*dyn):
            merged, di = [], 0
            for i in range(len(dyn) + len(statics)):
                if i in static_at:
                    merged.append(static_at[i])
                else:
                    merged.append(dyn[di])
                    di += 1
            return sj.fn(*merged)
        return dyn_fn

    def _do_compile(self, digest: str, sj: ServiceJit, statics: tuple,
                    dyn: tuple, boxes: List[list]) -> Optional[_Entry]:
        import jax

        from .. import faults
        try:
            faults.fire(faults.COMPILE)
            t0 = time.monotonic_ns()
            with spans.span(f"compile:{sj.op}", kind=spans.KIND_COMPILE,
                            op=sj.op):
                jitted = jax.jit(_named(self._dyn_fn(sj, statics), sj.op))
                with self._stage("trace", sj.op):
                    traced = jitted.trace(*dyn)
                with self._stage("lower", sj.op):
                    lowered = traced.lower()
                with self._stage("backend", sj.op):
                    compiled = lowered.compile()
            dt = time.monotonic_ns() - t0
        except Exception as e:
            # tracing errors are user errors and reproduce identically on
            # the direct path (which re-raises them to the caller with the
            # service out of the blame chain); injected faults land here too
            self._fallback(sj, f"{type(e).__name__}: {e}")
            return None
        self.stats.bump(sj.op, compiles=1, compile_ns=dt)
        tm = self._task_metrics()
        tm.compile_count += 1
        tm.compile_ns += dt
        entry = _Entry(compiled, [list(b) for b in boxes], sj.op, "compile")
        self._persist(digest, sj, jitted, dyn, entry)
        return entry

    @contextlib.contextmanager
    def _stage(self, stage: str, op: str):
        """One AOT stage of a compile (`trace`: the Python body to a jaxpr,
        `lower`: to StableHLO, `backend`: XLA's compile or its persistent
        cache's load), on the profiler's clock and in `CompileStats`."""
        t0 = time.monotonic_ns()
        try:
            with trace_range(f"compile.{stage}.{op}"):
                yield
        finally:
            self.stats.bump(op, **{f"{stage}_ns": time.monotonic_ns() - t0})

    @staticmethod
    def _restore_boxes(entry: _Entry, boxes: List[list]) -> None:
        for box, snap in zip(boxes, entry.msgs):
            box[:] = snap

    @staticmethod
    def _task_metrics():
        from ..utils.metrics import TaskMetrics
        return TaskMetrics.get()

    def _count_dispatch(self, args: tuple) -> None:
        """Count one host-side program launch UNLESS the call is nested in
        another kernel's trace (it inlines — no launch of its own)."""
        try:
            import jax
            leaves, _ = jax.tree_util.tree_flatten(args)
            if any(isinstance(l, jax.core.Tracer) for l in leaves):
                return
        except Exception:
            pass
        self._task_metrics().device_dispatches += 1

    def _fallback(self, sj: ServiceJit, why: str) -> None:
        self.stats.bump(sj.op, fallbacks=1)
        self._task_metrics().compile_fallbacks += 1
        warnings.warn(CompileServiceWarning(
            f"compile service degraded to direct jit for {sj.op}: {why}"),
            stacklevel=3)

    # ---------------------------------------------------------- persistence
    def _entry_path(self, digest: str) -> str:
        return os.path.join(self._dir, f"{digest}.xprog")

    def _persist(self, digest: str, sj: ServiceJit, jitted, dyn: tuple,
                 entry: _Entry) -> None:
        if not self._persist_ok():
            return
        try:
            # ENTRY-level serialization problems (an unexportable program)
            # warn and skip this entry; only the file IO below is tier
            # damage that degrades persistence as a whole
            import jax.export as jex
            _register_export_serialization()
            exported = jex.export(jitted)(*dyn)
            payload = bytes(exported.serialize())
            meta = json.dumps({"op": sj.op, "key": sj.key,
                               "msgs": entry.msgs}).encode()
            from ..shuffle.codec import crc32c
            body = meta + payload
            blob = _HDR.pack(_MAGIC, _FMT_EXPORT, crc32c(body),
                             len(meta)) + body
        except Exception as e:
            self.stats.bump(sj.op, persist_errors=1)
            self._persist_warn(f"could not persist {sj.op}: "
                               f"{type(e).__name__}: {e}")
            return

        def write() -> bool:
            path = self._entry_path(digest)
            tmp = f"{path}.tmp.{os.getpid()}.{threading.get_ident()}"
            with open(tmp, "wb") as f:
                f.write(blob)
            os.replace(tmp, path)
            return True

        if self._tier.run("store", write):
            self.stats.bump(sj.op, persist_stores=1)
        else:
            self.stats.bump(sj.op, persist_errors=1)

    def _load_persistent(self, digest: str, sj: ServiceJit) \
            -> Optional[_Entry]:
        if not self._persist_ok():
            return None
        path = self._entry_path(digest)

        def read():
            with open(path, "rb") as f:
                return f.read()

        # an absent entry is a plain miss; any other IO failure (EPERM,
        # EIO, vanished mount) degrades the tier to memory-only
        blob = self._tier.run("load", read, missing_ok=True)
        if blob is None:
            return None
        from .. import faults
        try:
            blob = faults.fire(faults.COMPILE, blob)
        except Exception as e:
            # degraded read: recompile from scratch (warn, count, continue)
            self._fallback(sj, f"injected persistent-read fault: {e}")
            return None
        with trace_range(f"compile.reload.{sj.op}"):
            entry = self._decode_entry(blob, digest, sj)
        if entry is None:
            # poisoned/torn/stale entry: delete so the recompile re-persists
            # a good one, and treat as a plain miss
            self.stats.bump(sj.op, poisoned=1)
            try:
                os.unlink(path)
            except OSError:
                pass
            return None
        self.stats.bump(sj.op, persist_hits=1)
        self._task_metrics().compile_persist_hits += 1
        return entry

    def _decode_entry(self, blob: bytes, digest: str, sj: ServiceJit) \
            -> Optional[_Entry]:
        try:
            if len(blob) < _HDR.size:
                return None
            magic, fmt, crc, meta_len = _HDR.unpack_from(blob)
            if magic != _MAGIC or fmt != _FMT_EXPORT:
                return None
            body = blob[_HDR.size:]
            if len(body) < meta_len:
                return None
            from ..shuffle.codec import crc32c
            if crc32c(body) != crc:
                return None
            meta = json.loads(body[:meta_len].decode())
            payload = body[meta_len:]
            import jax
            import jax.export as jex
            _register_export_serialization()
            exported = jex.deserialize(bytearray(payload))
            op = meta.get("op", sj.op)
            # jit around the exported call so the backend compile of the
            # restored StableHLO caches instead of recurring per dispatch
            compiled = jax.jit(_named(exported.call, op))
            msgs = [list(m) for m in meta.get("msgs", [])]
            return _Entry(compiled, msgs, op, "persist")
        except Exception:
            return None

    def persisted_entries(self) -> List[str]:
        """Digests present in the persistent tier (warmup preload walks
        these)."""
        if not self._persist_ok():
            return []
        return self._tier.run(
            "list", lambda: [f[:-len(".xprog")]
                             for f in os.listdir(self._dir)
                             if f.endswith(".xprog")], default=[])

    def persisted_meta(self, digest: str) -> Optional[dict]:
        """Cheap header+meta sniff of one persisted entry ({"op", "key",
        "msgs"}) without deserializing the program — warmup uses it to
        order fused-stage programs first. None on any damage."""
        if not self._persist_ok():
            return None

        def read():
            with open(self._entry_path(digest), "rb") as f:
                head = f.read(_HDR.size)
                if len(head) < _HDR.size:
                    return None
                magic, fmt, _crc, meta_len = _HDR.unpack_from(head)
                if magic != _MAGIC or fmt != _FMT_EXPORT:
                    return None
                meta = f.read(meta_len)
                if len(meta) < meta_len:
                    return None
                return json.loads(meta.decode())

        try:
            return self._tier.run("meta", read, missing_ok=True)
        except Exception:
            return None

    def preload_persistent(self, digest: str) -> bool:
        """Pull one persisted entry into the memory tier (warmup). Returns
        True when it loaded."""
        with self._mu:
            if digest in self._mem:
                return True
        sj = ServiceJit(lambda: None, op="warmup.preload")
        entry = self._load_persistent(digest, sj)
        if entry is None:
            return False
        self._store_mem(digest, entry)
        return True

    def _persist_warn(self, msg: str) -> None:
        if not self._warned_persist:
            self._warned_persist = True
            warnings.warn(CompileServiceWarning(
                f"persistent compile cache degraded: {msg}"))
